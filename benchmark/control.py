#!/usr/bin/env python3
"""Read what ``correct`` compares, for the program and for its control, on
several seeds in one process (set-up is long; a reading needs only a short
window at the cell's own load).

    python benchmark/control.py --workload <name> --seeds 1,2,3 --seconds 20

For a served model the control is int8 for the bf16 the configuration
states.  By default it is the plain reference computed with int8 weights
(the adapter's ``served_gaps(..., control=True)``): at every position of
the sampled requests it reads the reference-logit gap of the token the
control puts first, in the same run as the program's own reading.  With
``--program-int8 1`` it is the program itself serving the cell from its
weight-only int8 path (the adapter's ``int8_program_weights``): the run's
``correct`` then has to come out false.  One JSON line per seed goes to
stdout and to ``benchmark_out/control/<workload>.jsonl``.  The benchmark's own runs never
run this; the limits in the configuration files were set from its output
(PERF.md gives the readings).
"""

import time

T_START = time.perf_counter()

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--seconds", type=float, default=20.0)
    p.add_argument("--program-int8", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)
    from benchmark import harness
    spec, cell, config, traffic, driver = harness.load_cell(args.workload)
    out_dir = os.path.join(harness.ROOT, "benchmark_out", "control")
    os.makedirs(out_dir, exist_ok=True)
    t_start = T_START
    with open(os.path.join(out_dir, args.workload + ".jsonl"), "a") as f:
        for seed in (int(s) for s in args.seeds.split(",")):
            res = driver.run_cell(spec, cell, config, traffic, seed=seed,
                                  seconds=args.seconds, trace=False,
                                  t_start=t_start,
                                  control=not args.program_int8,
                                  program_int8=bool(args.program_int8))
            line = json.dumps({"workload": args.workload, "seed": seed,
                               "program_int8": bool(args.program_int8),
                               "seconds": args.seconds, "e2e": res["e2e"],
                               **res["check"]})
            print(line, flush=True)
            f.write(line + "\n")
            f.flush()
            t_start = time.perf_counter()
    return 0


if __name__ == "__main__":
    sys.exit(main())
