"""From a profiler trace (``.xplane.pb``) to numbers.

Read with ``jax.profiler.ProfileData`` and nothing else.  What a v5e trace
holds (looked at by hand, PR 23): one plane per chip, ``/device:TPU:<n>``,
with the lines ``XLA Modules`` (one event per run of a compiled program,
named ``jit_<fn>(<fingerprint>)``), ``XLA Ops`` (one event per HLO
instruction run, named by the instruction's whole text, control flow such
as ``while`` included as an event that spans its body) and ``Async XLA
Ops``; and the plane ``/host:CPU`` with one line per host thread, called
after the thread's ``comm`` (``python3`` under the benchmark's command,
``python`` under another).  The serve thread's line carries the program's
own ``batcher.*`` spans, one per phase of a tick, around JAX's host spans
(``np.asarray(jax.Array)``, ``PjitFunction(fn)``, ...).  All on one clock,
nanoseconds.

What the program names (since PR 24): its jitted entry points
(``jit_decode_block``, ``jit_prefill``, ...: the module names), its kernels
(``flash_decode_paged.N``, ``flash_attention_fwd.N``) and the phases of a
tick.  Programs are told by those names (``module_runs``), the serve
thread's line by its spans (``load``); single instructions by XLA's own
text and shapes.
"""

from __future__ import annotations

import bisect
import dataclasses
import functools
import glob
import gzip
import os
import re
from typing import Dict, Iterable, List, Optional, Tuple

Event = Tuple[str, float, float]        # name, start_s, duration_s

#: instructions that only wrap others: their time is their children's
CONTROL_FLOW = {"while", "conditional", "call"}
#: the program's host spans: a host line that carries one is the serve
#: thread's, whatever the profiler calls it
HOST_SPANS = "batcher."
#: module name (before the fingerprint) -> what the program run is
PROGRAMS = (("jit_decode_block", "decode"), ("jit_prefill", "prefill"))
GAP_FLOOR_S = 20e-6
#: a run that ends within this of the device's last event ended with the trace
EDGE_S = 1e-6


@dataclasses.dataclass
class Device:
    name: str
    modules: List[Event]
    ops: List[Event]


@dataclasses.dataclass
class Trace:
    devices: List[Device]
    host: List[Event]               # the serve thread's host line
    t_min: float
    t_max: float

    @property
    def window_s(self) -> float:
        return self.t_max - self.t_min


def find_xplane(path: str) -> str:
    if os.path.isfile(path):
        return path
    files = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return files[-1]


def load(path: str) -> Trace:
    """``path``: an ``.xplane.pb``, the same gzipped (``.gz``), or a
    directory the profiler wrote into."""
    from jax.profiler import ProfileData
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            data = ProfileData.from_serialized_xspace(f.read())
    else:
        data = ProfileData.from_file(find_xplane(path))
    devices, host = [], []
    t_min, t_max = float("inf"), float("-inf")

    def events(line) -> List[Event]:
        nonlocal t_min, t_max
        out = []
        for e in line.events:
            s, d = e.start_ns * 1e-9, e.duration_ns * 1e-9
            out.append((e.name, s, d))
            t_min, t_max = min(t_min, s), max(t_max, s + d)
        return out

    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {ln.name: ln for ln in plane.lines}
            devices.append(Device(
                name=plane.name,
                modules=events(lines["XLA Modules"])
                if "XLA Modules" in lines else [],
                ops=events(lines["XLA Ops"]) if "XLA Ops" in lines else []))
        elif plane.name == "/host:CPU":
            for ln in plane.lines:
                if any(e.name.startswith(HOST_SPANS) for e in ln.events):
                    host.extend(events(ln))
    if t_min > t_max:
        t_min = t_max = 0.0
    return Trace(devices=devices, host=host, t_min=t_min, t_max=t_max)


# -- instruction text -------------------------------------------------------

_NAME = re.compile(r"^%?([\w.\-]+) = ")
_SHAPE = re.compile(r"^([a-z][a-z0-9]*\[[0-9,]*\])")


def parse_op(text: str) -> Dict[str, str]:
    name, shape, opcode = _parse(text)
    return {"name": name, "shape": shape, "opcode": opcode}


@functools.lru_cache(maxsize=None)
def _parse(text: str) -> Tuple[str, str, str]:
    """``%copy.53 = bf16[16,1300,8,64,128]{...} copy(...)`` ->
    name ``copy.53``, shape ``bf16[16,1300,8,64,128]`` (``(tuple)`` for a
    tuple), opcode ``copy``.  Text that is not an instruction comes back
    as its own name with an empty opcode.  A trace runs a few hundred
    distinct instructions tens of thousands of times: parsed once each."""
    m = _NAME.match(text)
    if not m:
        return text, "", ""
    rest = text[m.end():]
    depth, end = 0, len(rest)
    for i, c in enumerate(rest):
        if c in "([{":
            depth += 1
        elif c in ")]}":
            depth -= 1
        elif c == " " and depth == 0:
            end = i
            break
    typ, tail = rest[:end], rest[end + 1:]
    sm = _SHAPE.match(typ)
    shape = sm.group(1) if sm else "(tuple)"
    opcode = tail.split("(", 1)[0].strip()
    return m.group(1), shape, opcode


def _clean(label: str) -> str:
    return re.sub(r"[^A-Za-z0-9_.\-\[\],() ]", "_", label)[:120]


def op_label(text: str) -> str:
    p = parse_op(text)
    return _clean(f"{p['opcode']} {p['name']} {p['shape']}".strip())


def is_leaf(text: str) -> bool:
    return parse_op(text)["opcode"] not in CONTROL_FLOW


# -- intervals --------------------------------------------------------------

def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def busy_intervals(dev: Device) -> List[Tuple[float, float]]:
    return union((s, s + d) for _, s, d in dev.ops if d > 0)


def busy_s(trace: Trace) -> float:
    """Seconds in which an operation ran on the device, averaged over the
    chips in the trace."""
    if not trace.devices:
        return 0.0
    per = [sum(e - s for s, e in busy_intervals(d)) for d in trace.devices]
    return sum(per) / len(per)


def op_totals(trace: Trace, top: int = 10) -> List[List[object]]:
    """The leaf instructions that took most device time, summed over their
    runs and averaged over the chips: ``[[label, seconds], ...]``."""
    total: Dict[str, float] = {}
    for dev in trace.devices:
        for name, _, d in dev.ops:
            if is_leaf(name):
                total[name] = total.get(name, 0.0) + d
    n = max(1, len(trace.devices))
    ranked = sorted(total.items(), key=lambda kv: -kv[1])[:top]
    return [[op_label(k), v / n] for k, v in ranked]


def share_of_busy(trace: Trace, pick) -> Optional[float]:
    """Percent of device busy time in the leaf instructions ``pick(parsed,
    text)`` selects (union of their intervals over union of all)."""
    num = den = 0.0
    for dev in trace.devices:
        den += sum(e - s for s, e in busy_intervals(dev))
        sel = [(s, s + d) for name, s, d in dev.ops
               if d > 0 and is_leaf(name) and pick(parse_op(name), name)]
        num += sum(e - s for s, e in union(sel))
    return 100.0 * num / den if den > 0 else None


def idle_gaps(trace: Trace, top: int = 10) -> List[List[object]]:
    """The first chip's idle time by what the host was doing: each gap
    between busy intervals goes to the host span that overlaps it most."""
    if not trace.devices:
        return []
    busy = busy_intervals(trace.devices[0])
    gaps = [(a[1], b[0]) for a, b in zip(busy, busy[1:]) if b[0] > a[1]]
    host = sorted(trace.host, key=lambda e: e[1])
    starts = [e[1] for e in host]
    by: Dict[str, float] = {}
    longest = max((d for _, _, d in host), default=0.0)
    for s, e in gaps:
        if e - s < GAP_FLOOR_S:
            by["(gaps under 20 us)"] = by.get("(gaps under 20 us)", 0) + e - s
            continue
        lo = bisect.bisect_left(starts, s - longest)
        hi = bisect.bisect_right(starts, e)
        best, best_ov = "(no host span)", 0.0
        for name, hs, hd in host[lo:hi]:
            ov = min(e, hs + hd) - max(s, hs)
            if ov > best_ov:
                best, best_ov = name, ov
        by[best] = by.get(best, 0.0) + e - s
    ranked = sorted(by.items(), key=lambda kv: -kv[1])[:top]
    return [[_clean(k), v] for k, v in ranked]


def breakdown(trace: Trace) -> Dict[str, object]:
    return {"device_ops": op_totals(trace), "idle_gaps": idle_gaps(trace)}


# -- programs ---------------------------------------------------------------

_PROMPT = re.compile(r"[su]\d+\[1,(\d+)\]\S* %prompt(?:\.\d+)?[,)]")


def module_runs(trace: Trace) -> List[Dict[str, object]]:
    """Each whole run of a compiled program on the first chip, with what
    it is, by the name the program gave its entry point: a module whose
    name starts ``jit_decode_block`` is a decode block, ``jit_prefill`` a
    prefill, any other ``other``.  A prefill's padded width W comes from
    the first instruction under the run that takes the entry parameter
    ``%prompt`` (``s32[1,W]``) as an operand; None where there is none.

    The run that ends with the device's last event is left out, whole or
    not: the profiler closes a run still going when the trace stops at
    that moment (docqa_batch, PR 27: a ``jit_prefill`` 6,208 wide cut to
    610 ms, no layer-scan ``while`` under it, because an instruction's
    event is written when it ends).  A run going when the trace starts is
    not in it at all (both traces looked at begin with a whole run)."""
    if not trace.devices:
        return []
    dev = trace.devices[0]
    ops = sorted((s, name) for name, s, _ in dev.ops)
    starts = [o[0] for o in ops]
    t_last = max(s + d for _, s, d in dev.modules + dev.ops) \
        if dev.modules else 0.0
    out = []
    for name, s, d in dev.modules:
        if s + d >= t_last - EDGE_S:
            continue
        base = name.split("(", 1)[0]
        kind = next((k for prefix, k in PROGRAMS if base.startswith(prefix)),
                    "other")
        width = None
        if kind == "prefill":
            i = bisect.bisect_left(starts, s)
            while i < len(ops) and ops[i][0] < s + d and width is None:
                m = _PROMPT.search(ops[i][1])
                width = int(m.group(1)) if m else None
                i += 1
        out.append({"name": name, "start": s, "dur": d, "kind": kind,
                    "width": width})
    return out
