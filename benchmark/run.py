#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json.

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Runs from the root of a checkout, on the machine it is started on, in one
new process.  The last line of its standard output is one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics``, ``device``, in a
traced run ``breakdown``, and last ``compared`` (each number ``correct``
was decided from, with its limit; the same as the last lines of standard
error).  With ``--trace 0`` the metrics are the cell's end-to-end metrics,
with ``--trace 1`` its per-layer metrics.  It exits non-zero, and prints no
result, where JAX finds no TPU, fewer chips than the cell asks for, or a
device kind that ``peaks.json`` does not list.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a file found by its name (see README.md).
"""

import time

T_START = time.perf_counter()       # set-up is counted from here

import argparse
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)

    from benchmark import harness
    spec, cell, config, traffic, driver = harness.load_cell(args.workload)
    res = driver.run_cell(spec, cell, config, traffic, seed=args.seed,
                          seconds=args.seconds, trace=bool(args.trace),
                          t_start=T_START)
    sys.stdout.flush()
    compared = res["check"]["compared"]
    # each number compared beside its limit: the last lines on standard
    # error, and the last key of the result
    print("\n".join(harness.compared_lines(compared)), file=sys.stderr,
          flush=True)
    print(harness.result_line(
        correct=res["correct"], attempted=res["attempted"],
        failed=res["failed"], metrics=res["metrics"], device=res["device"],
        breakdown=res.get("breakdown"), compared=compared), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
