"""The reductions the readers of the batcher's tick ring share.

``ContinuousBatcher`` leaves one record per pass of its serve loop (a
tick) in a process-global ring, ``fleet.tracing.flight("batcher.tick")``,
which outlives the batcher: the driver frees the batcher before the
readers run and hands them no handle to it.  A record carries ``t``
(``perf_counter`` at the tick's start, the clock of ``run["t0"]``,
``run["t1"]`` and ``run["trace_window"]``), ``wall_ms`` (to the next
tick's start), ``phases`` (name -> ms on the serve thread, flat, never
nested), ``idle_ms`` (the part of ``batcher.pull`` spent waiting for
arrivals with no row active), ``rows`` (rows in the tick's decode block, 0
without one), ``name`` (``decode.block`` where a block ran) and
``compiles`` (backend compiles that finished during the tick).
docs/SERVING.md "Observability" lists the fields and the phases.

A program without the ring (the parent of the PR that brought it) leaves
it empty, and every reader returns None.
"""

from __future__ import annotations

import statistics
from typing import Any, Dict, List, Optional

TICK_COMPONENT = "batcher.tick"
#: the phases in which the serve thread waits for the device
DEVICE_WAIT = ("batcher.prefill_sync", "batcher.readback")
#: Ticks that start within this many seconds before ``start_trace`` was
#: called may reach into the traced seconds.
TRACE_LEAD_S = 0.5
#: Seconds after ``stop_trace`` was called in which ``wall_ms`` still
#: shows the profiler at work (it serialises the trace under the
#: interpreter lock): the longest seen on the chip and a margin, PERF.md §6.
TRACE_SETTLE_S = 3.0


def ring() -> List[Dict[str, Any]]:
    from tfmesos_tpu.fleet.tracing import flight
    return flight(TICK_COMPONENT).snapshot()


def ticks(run) -> List[Dict[str, Any]]:
    """The ticks that started inside the window ``[t0, t1)`` and outside
    the profiler's reach, ``[tw0 - TRACE_LEAD_S, tw1 + TRACE_SETTLE_S]``
    around ``run["trace_window"]`` (every tick of the window where no
    trace was taken)."""
    t0, t1 = run["t0"], run["t1"]
    tw0, tw1 = run.get("trace_window") or (None, None)
    out = []
    for rec in ring():
        t = rec["t"]
        if not t0 <= t < t1:
            continue
        if tw0 is not None and \
                tw0 - TRACE_LEAD_S <= t <= tw1 + TRACE_SETTLE_S:
            continue
        out.append(rec)
    return out


def _wait_ms(rec) -> float:
    return sum(rec["phases"].get(p, 0.0) for p in DEVICE_WAIT)


def _span_s(recs) -> float:
    """Ticks tile the serve loop's time, so the seconds a set of ticks
    spans is the sum of their ``wall_ms`` (the profiler's hole left out)."""
    return sum(r["wall_ms"] for r in recs) / 1e3


def tick_host_ms_p50(run) -> Optional[float]:
    """Per tick of ``ticks(run)`` that ran a decode block: ``wall_ms`` less
    the device-wait phases (and an idle pull, which such a tick never
    has); the median."""
    host = [r["wall_ms"] - _wait_ms(r) - r["idle_ms"]
            for r in ticks(run) if r["name"] == "decode.block"]
    return statistics.median(host) if host else None


def host_gap_share(run) -> Optional[float]:
    """Over ``ticks(run)``: the time in phases that do not wait for the
    device, idle pulls left out, as a percentage of the seconds the ticks
    span.  In the synchronous loop the device is idle while the host
    works, so this reads close to the device's idle share."""
    recs = ticks(run)
    span = _span_s(recs)
    if span <= 0:
        return None
    host = sum(sum(r["phases"].values()) - _wait_ms(r) - r["idle_ms"]
               for r in recs)
    return 100.0 * host / 1e3 / span


def prefill_stall_share(run) -> Optional[float]:
    """Over ``ticks(run)``: the time in ``batcher.admit`` and
    ``batcher.prefill_sync`` of ticks whose decode block had rows, as a
    percentage of the seconds all the ticks span: how long decoding rows
    sat behind a prefill."""
    recs = ticks(run)
    span = _span_s(recs)
    if span <= 0:
        return None
    stall = sum(r["phases"].get("batcher.admit", 0.0)
                + r["phases"].get("batcher.prefill_sync", 0.0)
                for r in recs if r["rows"] > 0)
    return 100.0 * stall / 1e3 / span


def compiles_in_window(run) -> Optional[float]:
    """Backend compiles that finished during ``ticks(run)``: 0 in a run
    whose set-up warmed every shape."""
    recs = ticks(run)
    return float(sum(r["compiles"] for r in recs)) if recs else None
