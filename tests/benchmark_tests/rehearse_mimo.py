#!/usr/bin/env python3
"""Drive ``mimo.agent_batch`` at a tiny size through ``drivers/serve.py``
on the CPU: the MiMo adapter and its reference (keys and values of unequal
width, K/V heads by kind of layer, a sink, 2 of 8 experts held behind a
leading dense layer), a backlog whose prompts lie inside one window, end on
its edge and reach many windows past it, rows admitted into slots other rows
left, under the pipelined carry; one traced run with the int8 control read,
one served from the program's own int8 weights and one with the batcher's
sampler broken.  Prints one JSON line.  Started by test_benchmark_mimo.py;
never a measurement."""

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)
sys.path.insert(0, HERE)

import mimo_tiny  # noqa: E402

CELL = "mimo.agent_batch"
TRAFFIC = {
    "arrivals": {"kind": "backlog", "requests": 512}, "ramp_s": 1.0,
    "grace_s": 0.5, "block": 8, "schedule_seed": 45,
    "prompt": {"dist": "lognormal", "median": 20, "sigma": 1.1, "min": 4,
               "max": 92, "quantum": 4},
    "output": {"dist": "lognormal", "median": 12, "sigma": 0.5, "min": 4,
               "max": 32},
}


def spec():
    """The cell's per-layer entries as BENCHMARK.json lists them, under a
    cell name of this process's (a run's trace is kept in a directory named
    after cell and seed)."""
    full = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    name = f"{CELL}.{os.getpid()}"
    mine = [dict(m, workloads=[name]) for m in full["per_layer"]
            if CELL in m.get("workloads", [])]
    return {"workloads": [{"name": name, "config": "tiny", "traffic": "tiny",
                           "chips": 1, "why": "rehearsal"}],
            "end_to_end": [{"name": "tok_s", "unit": "tokens/s",
                            "workloads": [name]},
                           {"name": "setup_s", "unit": "s"}],
            "per_layer": mine}


def main() -> int:
    import jax.numpy as jnp
    from benchmark.drivers import serve
    from tfmesos_tpu import serving
    sp = spec()
    seed = 2 ** 31 + 3201
    lines = []
    config = mimo_tiny.tiny(rows=3, n_pages=72)

    def run(**kw):
        return serve.run_cell(sp, sp["workloads"][0], dict(config), TRAFFIC,
                              seed=seed, seconds=3, t_start=0.0,
                              require_chip=False, out=lines.append, **kw)

    sound = run(trace=True, control=True)
    int8 = run(trace=False, program_int8=True)
    ring = [r for r in serving.flight(serving.TICK_COMPONENT).snapshot()
            if "moe_routed" in r]

    def second_best(self, last, rids, steps):
        order = jnp.argsort(last.astype(jnp.float32), axis=-1)
        return order[..., -2].astype(jnp.int32)

    serving.ContinuousBatcher._sample = second_best
    broken = run(trace=False)
    print(json.dumps({
        "per_layer": [m["name"] for m in sp["per_layer"]],
        "sound": {"correct": sound["correct"], "check": sound["check"],
                  "metrics": sound["metrics"], "e2e": sound["e2e"],
                  "finished": sum(1 for r in sound["records"]
                                  if r.done is not None),
                  "prompts": sorted({r.prompt_len for r in sound["records"]
                                     if r.done is not None})},
        "ring": {"state_rows_max": max(r["state_rows"] for r in ring),
                 "assignments": sum(r["moe_assignments"] for r in ring),
                 "tile_rows": sum(r["moe_tile_rows"] for r in ring),
                 "touched": sum(r["moe_experts_touched"] for r in ring),
                 "routed": sum(r["moe_routed"] for r in ring),
                 "ctx": sum(r["ctx_positions"] for r in ring),
                 "swa": sum(r["swa_positions"] for r in ring),
                 "steps": sum(r["k"] for r in ring
                              if r["name"] == "decode.block"),
                 "blocks": sum(1 for r in ring
                               if r["name"] == "decode.block")},
        "int8": {"correct": int8["correct"], "check": int8["check"]},
        "broken": {"correct": broken["correct"], "check": broken["check"]},
        "lines": lines}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
