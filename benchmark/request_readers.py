"""The reductions over the batcher's request ring, and over the stall
counters of its tick ring.

``ContinuousBatcher`` leaves one record per request, written once, when
the request leaves it (finished, expired, shed, given back, left behind),
in a process-global ring, ``fleet.tracing.flight("batcher.request")``,
which outlives the batcher as the tick ring does (``tick_readers.py``).  A
record carries ``t_submit`` (``perf_counter`` when the request entered the
batcher's queue: the clock of ``run["t0"]``, ``run["t1"]`` and
``run["trace_window"]``), ``t_admit`` (its prefill's start), ``t_first``
(its first token on the host) and ``t_done``, ``prompt_tokens``,
``prefill_tokens`` (the padded width dispatched for it, chunks summed) and
``out_tokens``.  A request that never held a row has no ``t_admit``.

Every tick record carries what held the tick: ``gc_ms`` (collector pauses
that ended in it, on any thread) and, in the pipelined loop, ``ready`` (1
if the lagged decode block the tick read back had finished on the device
before the serve thread asked for it, else 0; None where it read none
back, and in a synchronous loop, which asks right after it dispatches).  A tick is a *stall* by the
program's own rule (``serving._tick_stall``), restated here from the fields
every tick ring has had: ``held_ms`` is ``wall_ms`` less ``idle_ms`` and
less ``batcher.admit`` and ``batcher.prefill_sync`` (a prefill of 7 k
tokens is a third of a second of honest work); a tick is a stall where
``held_ms`` is over both ``STALL_FACTOR`` x the median wall time of the
window's ``decode`` ticks and ``STALL_MIN_MS``, and nothing compiled in it.
docs/SERVING.md "Observability" lists the fields and the rule.

A program without the request ring, or whose ticks lack a counter (the
parent of the PR that brought them), gives a reader nothing to read, and it
returns None.
"""

from __future__ import annotations

import statistics
from typing import Any, Dict, List, Optional

from benchmark import tick_readers, window

REQUEST_COMPONENT = "batcher.request"
#: the program's stall rule (``serving.STALL_FACTOR``, ``STALL_MIN_MS``; a
#: test holds them equal)
STALL_FACTOR = 8.0
STALL_MIN_MS = 250.0


def ring() -> List[Dict[str, Any]]:
    from tfmesos_tpu.fleet.tracing import flight
    return flight(REQUEST_COMPONENT).snapshot()


def requests(run) -> List[Dict[str, Any]]:
    """The requests admitted inside the window ``[t0, t1)`` and outside
    the profiler's reach, as ``tick_readers.ticks`` defines it."""
    t0, t1 = run["t0"], run["t1"]
    tw0, tw1 = run.get("trace_window") or (None, None)
    out = []
    for rec in ring():
        t = rec["t_admit"]
        if t is None or not t0 <= t < t1:
            continue
        if tw0 is not None and tw0 - tick_readers.TRACE_LEAD_S <= t \
                <= tw1 + tick_readers.TRACE_SETTLE_S:
            continue
        out.append(rec)
    return out


def queue_wait_p90_ms(run) -> Optional[float]:
    """p90 of ``t_admit - t_submit``: the wait in the batcher's own
    queue."""
    waits = [r["t_admit"] - r["t_submit"] for r in requests(run)]
    return 1e3 * window.percentile(waits, 90) if waits else None


def submit_to_first_p90_ms(run) -> Optional[float]:
    """p90 of ``t_first - t_submit``: the program's own time to the first
    token, the queue included."""
    spans = [r["t_first"] - r["t_submit"] for r in requests(run)
             if r["t_first"] is not None]
    return 1e3 * window.percentile(spans, 90) if spans else None


def admit_to_first_ms_per_ktok_p50(run) -> Optional[float]:
    """Median of ``(t_first - t_admit)`` in ms per 1,000 tokens of padded
    prompt dispatched (``prefill_tokens``; a request that dispatched none,
    an import, is left out).  Per token because a faster program is further
    down the cell's fixed order and meets other widths."""
    per = [(r["t_first"] - r["t_admit"]) * 1e3 * 1e3 / r["prefill_tokens"]
           for r in requests(run)
           if r["t_first"] is not None and r["prefill_tokens"] > 0]
    return statistics.median(per) if per else None


def _held_ms(rec) -> float:
    ph = rec["phases"]
    return (rec["wall_ms"] - rec["idle_ms"] - ph.get("batcher.admit", 0.0)
            - ph.get("batcher.prefill_sync", 0.0))


def stall_share(run) -> Optional[float]:
    """The seconds a run lost: over ``ticks(run)``, ``held_ms`` less the
    median wall time of the ``decode`` ticks, summed over the ticks the
    rule calls stalls, as a percentage of the seconds all the ticks span.
    0.0 in a run that did not stall; None without a ``decode`` tick to
    take the median of."""
    recs = tick_readers.ticks(run)
    walls = [r["wall_ms"] for r in recs if r.get("kind") == "decode"]
    span_ms = sum(r["wall_ms"] for r in recs)
    if not walls or span_ms <= 0:
        return None
    median = statistics.median(walls)
    lost = sum(_held_ms(r) - median for r in recs
               if _held_ms(r) > max(STALL_MIN_MS, STALL_FACTOR * median)
               and not r["compiles"])
    return 100.0 * lost / span_ms


def gc_pause_share(run) -> Optional[float]:
    """Collector pauses that ended in ``ticks(run)``, as a percentage of
    the seconds the ticks span."""
    recs = [r for r in tick_readers.ticks(run) if "gc_ms" in r]
    span_ms = sum(r["wall_ms"] for r in recs)
    if span_ms <= 0:
        return None
    return 100.0 * sum(r["gc_ms"] for r in recs) / span_ms


def ready_on_arrival_share(run) -> Optional[float]:
    """Of the ticks of ``ticks(run)`` in which the pipelined loop read a
    lagged decode block back, the percentage whose block had finished on
    the device before the serve thread asked for it: the device was
    waiting for the host.  (A synchronous loop asks right after it
    dispatches and records nothing: None.)"""
    ready = [r["ready"] for r in tick_readers.ticks(run)
             if r.get("ready") is not None]
    return 100.0 * sum(ready) / len(ready) if ready else None
