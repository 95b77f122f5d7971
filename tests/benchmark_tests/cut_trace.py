#!/usr/bin/env python3
"""Cut a recorded profiler trace down to what ``trace_reduce.load`` reads,
small enough to keep under ``testdata/``.

    python tests/benchmark_tests/cut_trace.py <trace.xplane.pb[.gz] | dir> <out.xplane.pb.gz> --from 0.40 --to 1.25

Kept: of every ``/device:TPU:<n>`` plane the lines ``XLA Modules`` and
``XLA Ops``; of ``/host:CPU`` the lines that carry the program's
``batcher.*`` spans, under the name the profiler gave them; only the events
that start inside ``[--from, --to)`` seconds after the earliest kept event;
of the metadata only the names of the events kept.  Every stat is dropped.
A test helper, not part of the yardstick and never part of a run.
"""

import argparse
import gzip
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
for path in (HERE, os.path.dirname(os.path.dirname(HERE))):
    if path not in sys.path:
        sys.path.insert(0, path)

import xplane_wire                                          # noqa: E402
from benchmark import trace_reduce                          # noqa: E402
from xplane_wire import decode, first                       # noqa: E402

DEVICE_LINES = ("XLA Modules", "XLA Ops")


def _names(plane):
    """metadata id -> event name, from the plane's ``event_metadata`` map."""
    out = {}
    for f, _, v in plane:
        if f == 4:
            meta = decode(first(decode(v), 2, b""))
            out[first(meta, 1, 0)] = first(meta, 2, b"").decode()
    return out


def _events(line, names):
    """``(metadata id, name, start_ps, duration_ps)`` of a line's events."""
    t0 = first(line, 3, 0) * 1000
    for f, _, v in line:
        if f == 4:
            ev = decode(v)
            meta = first(ev, 1, 0)
            yield (meta, names.get(meta, ""), t0 + first(ev, 2, 0),
                   first(ev, 3, 0))


def cut(xspace: bytes, t_from: float, t_to: float) -> bytes:
    kept = []       # (plane name, plane id, names, [(line name, id, events)])
    for _, _, p in (x for x in decode(xspace) if x[0] == 1):
        plane = decode(p)
        name = first(plane, 2, b"").decode()
        names = _names(plane)
        lines = [(first(ln, 2, b"").decode(), first(ln, 1, 0),
                  list(_events(ln, names)))
                 for ln in (decode(v) for f, _, v in plane if f == 3)]
        if name.startswith("/device:TPU:"):
            lines = [ln for ln in lines if ln[0] in DEVICE_LINES]
        elif name == "/host:CPU":
            lines = [ln for ln in lines if any(
                e[1].startswith(trace_reduce.HOST_SPANS) for e in ln[2])]
        else:
            continue
        kept.append((name, first(plane, 1, 0), names, lines))
    t_min = min(e[2] for _, _, _, lines in kept for ln in lines
                for e in ln[2])
    lo, hi = t_min + int(t_from * 1e12), t_min + int(t_to * 1e12)
    planes = []
    for name, plane_id, names, lines in kept:
        lines = [(ln, i, [(m, s, d) for m, _, s, d in ev if lo <= s < hi])
                 for ln, i, ev in lines]
        used = {m for _, _, ev in lines for m, _, _ in ev}
        planes.append(xplane_wire.plane(
            name, [xplane_wire.line(ln, ev, i) for ln, i, ev in lines],
            {m: names[m] for m in sorted(used)}, plane_id))
    return xplane_wire.space(planes)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("trace")
    p.add_argument("out")
    p.add_argument("--from", dest="t_from", type=float, default=0.0)
    p.add_argument("--to", dest="t_to", type=float, default=1.0)
    args = p.parse_args(argv)
    path = args.trace if args.trace.endswith(".gz") \
        else trace_reduce.find_xplane(args.trace)
    with (gzip.open if path.endswith(".gz") else open)(path, "rb") as f:
        data = cut(f.read(), args.t_from, args.t_to)
    with gzip.open(args.out, "wb", 9) as f:
        f.write(data)
    tr = trace_reduce.load(args.out)
    print(f"{args.out}: {os.path.getsize(args.out)} bytes, "
          f"{tr.window_s:.3f} s, {sum(len(d.ops) for d in tr.devices)} "
          f"device ops, {len(tr.host)} host events")
    return 0


if __name__ == "__main__":
    sys.exit(main())
