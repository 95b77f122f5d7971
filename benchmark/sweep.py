#!/usr/bin/env python3
"""Find an open-loop cell's knee: the cell's own schedule (the lengths, the
pairing and the order that a run of ``run_seconds`` offers; only the due
times are scaled) at several fixed rates, in one process, each for a short
window.

    python benchmark/sweep.py --workload mistral7b.chat_steady --rates 1.5,2,2.5,3 --seconds 30

For each rate one JSON line: requests offered and completed per second in the
window, TTFT and TPOT percentiles, and the median queue wait (due time to
admission) over the first and the last third of the window.  The knee is the
highest rate at which completions keep up with offers and the queue wait at
the window's end is no longer than at its start.  The cell's traffic file
then states 0.8 x the knee as a number; PERF.md keeps the sweep.  Not part
of a benchmark run.
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
    p.add_argument("--rates", required=True)
    p.add_argument("--seconds", type=float, default=30.0)
    p.add_argument("--seed", type=int, default=20260927)
    args = p.parse_args(argv)
    from benchmark import harness, traffic_gen, window
    spec, cell, config, traffic, driver = harness.load_cell(args.workload)
    stated = float(traffic["arrivals"]["rate_rps"])
    # a sweep reads rates, not outputs: the reference reads the longest
    # finished request only
    config["correct"]["sample_requests"] = 1
    out_dir = os.path.join(harness.ROOT, "benchmark_out", "sweep")
    os.makedirs(out_dir, exist_ok=True)
    t_start = T_START
    with open(os.path.join(out_dir, args.workload + ".jsonl"), "a") as f:
        for rate in (float(r) for r in args.rates.split(",")):
            sched = traffic_gen.make_schedule(
                traffic, args.seed, spec["run_seconds"], config["vocab_size"])
            for r in sched.requests:
                r.due_s *= stated / rate
            if sched.requests[-1].due_s < (sched.ramp_s + args.seconds
                                           + sched.grace_s):
                raise SystemExit(f"the schedule ends before a {args.seconds}"
                                 f" s window does at {rate} requests/s")
            res = driver.run_cell(spec, cell, config, traffic,
                                  seed=args.seed, seconds=args.seconds,
                                  trace=False, t_start=t_start,
                                  schedule=sched)
            recs, t0, t1 = res["records"], res["t0"], res["t1"]
            due = [r for r in recs if t0 <= r.due < t1]
            third = (t1 - t0) / 3

            def ms(values, q):
                return 1e3 * window.percentile(values, q) if values else None

            def wait(lo, hi):
                return ms([r.admit - r.due for r in due
                           if r.admit is not None and lo <= r.due < hi], 50)

            ttft = [r.first - r.due for r in due if r.first is not None]
            tpot = window.tpot_samples(recs, t0, t1)
            line = json.dumps({
                "workload": args.workload, "rate_rps": rate,
                "seconds": args.seconds, "seed": args.seed,
                "offered_rps": len(due) / (t1 - t0),
                "completed_rps": sum(1 for r in recs if r.done is not None
                                     and t0 <= r.done < t1) / (t1 - t0),
                "no_first_token": len(due) - len(ttft),
                "ttft_p50_ms": ms(ttft, 50),
                "ttft_p90_ms": ms(ttft, 90),
                "tpot_p50_ms": ms(tpot, 50),
                "tpot_p90_ms": ms(tpot, 90),
                "queue_wait_p50_ms_first_third": wait(t0, t0 + third),
                "queue_wait_p50_ms_last_third": wait(t1 - third, t1),
                "live_rows_mean": window.live_rows_mean(recs, t0, t1),
                "tok_s": res["e2e"]["tok_s"], "correct": res["correct"]})
            print(line, flush=True)
            f.write(line + "\n")
            f.flush()
            t_start = time.perf_counter()
    return 0


if __name__ == "__main__":
    sys.exit(main())
