#!/usr/bin/env python3
"""Drive ``evabyte.longdoc_batch`` at a tiny size through ``drivers/serve.py``
on the CPU: the EvaByte adapter and its reference, a backlog of prompts of
1 to 5 windows, one traced run with the int8 control read and one with the
batcher's sampler broken.  Prints one JSON line.  Started by
test_benchmark_evabyte.py; never a measurement."""

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

CELL = "evabyte.longdoc_batch"
TINY = {
    "attention_class": "eva", "chunk_size": 8, "window_size": 64,
    "hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 4, "vocab_size": 320,
    "num_pred_heads": 8, "rms_norm_eps": 1e-5, "rope_theta": 100000,
    "norm_add_unit_offset": True, "fp32_skip_add": True, "fp32_logits": True,
    "torch_dtype": "float32", "driver": "serve", "model": "evabyte",
    "deployment": {"chips": 1, "rows": 4, "max_len": 512, "page_size": 8,
                   "n_pages": 48},
    "correct": {"sample_requests": 8, "limits": {"max_gap": 1e-3}},
}
TRAFFIC = {
    "arrivals": {"kind": "backlog", "requests": 512}, "ramp_s": 1.0,
    "grace_s": 0.5, "block": 8, "schedule_seed": 28,
    "prompt": {"dist": "lognormal", "median": 150, "sigma": 0.6, "min": 48,
               "max": 336, "quantum": 8},
    "output": {"dist": "lognormal", "median": 24, "sigma": 0.5, "min": 8,
               "max": 64},
}


def spec():
    """The cell's per-layer entries as BENCHMARK.json lists them, under a
    cell name of this process's (the driver keeps a run's trace in a
    directory named after cell and seed: two rehearsals at once must not
    share it)."""
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
    from benchmark import harness, window
    from benchmark.drivers import serve
    from tfmesos_tpu import serving
    sp = spec()
    seed = 2 ** 31 + 2801
    lines = []

    def run(**kw):
        return serve.run_cell(sp, sp["workloads"][0], dict(TINY), TRAFFIC,
                              seed=seed, seconds=3, t_start=0.0,
                              require_chip=False, out=lines.append, **kw)

    sound = run(trace=True, control=True)
    ring = [r for r in serving.flight(serving.TICK_COMPONENT).snapshot()
            if "eva_rolls" in r]

    def second_best(self, last, rids, steps):
        order = jnp.argsort(last.astype(jnp.float32), axis=-1)
        return order[..., -2].astype(jnp.int32)

    serving.ContinuousBatcher._sample = second_best
    broken = run(trace=False)
    print(json.dumps({
        "per_layer": [m["name"] for m in sp["per_layer"]],
        "sound": {"correct": sound["correct"], "check": sound["check"],
                  "metrics": sound["metrics"], "e2e": sound["e2e"],
                  "live_tokens_mean": window.live_tokens_mean(
                      sound["records"], sound["t0"], sound["t1"]),
                  "prompts": sorted({r.prompt_len for r in sound["records"]
                                     if r.done is not None})},
        "ring": {"rolls": sum(r["eva_rolls"] for r in ring),
                 "held_max": max(r["eva_summary_entries"]
                                 + r["eva_window_entries"] for r in ring)},
        "broken": {"correct": broken["correct"], "check": broken["check"]},
        "lines": lines}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
