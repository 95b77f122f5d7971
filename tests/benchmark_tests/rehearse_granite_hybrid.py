#!/usr/bin/env python3
"""Drive ``granite4h.gen_batch`` at a tiny size through ``drivers/serve.py``
on the CPU: the Granite-4.0-H adapter and its reference, a backlog whose
prompts end inside a Mamba chunk, on one and inside a bucket's padding, rows
admitted into slots other rows left; one traced run with the int8 control
read and one with the batcher's sampler broken.  Prints one JSON line.
Started by test_benchmark_granite_hybrid.py; never a measurement."""

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)
sys.path.insert(0, HERE)

import hybrid_tiny  # noqa: E402

CELL = "granite4h.gen_batch"
TRAFFIC = {
    "arrivals": {"kind": "backlog", "requests": 512}, "ramp_s": 1.0,
    "grace_s": 0.5, "block": 8, "schedule_seed": 32,
    "prompt": {"dist": "lognormal", "median": 40, "sigma": 0.6, "min": 8,
               "max": 120, "quantum": 8},
    "output": {"dist": "lognormal", "median": 12, "sigma": 0.5, "min": 4,
               "max": 32},
}

def _entry(name, unit, better, source, layer):
    return {"name": name, "unit": unit, "better": better, "source": source,
            "layer": layer, "moves": "tok_s", "workloads": [CELL]}


#: this cell's twelve entries as a ``benchmark`` PR would give them to
#: ``per_layer``: the seven twins (read by their base names' files; unit,
#: better, source and layer as their ``.docqa`` or chat twins state them)
#: and the five readers this PR brings (``layer_metrics/ssm_*.py``,
#: ``moe_*.py``).  BENCHMARK.json does not list them: a program PR may only
#: APPEND to ``per_layer``, and the older ``test_benchmark_tick_readers.py``
#: holds the tick ring's eight entries to be the last eight of the list, so
#: nothing can be appended behind them until that assertion goes (PERF.md,
#: section 7).  The cell joins ``pool_fill.docqa``'s list meanwhile.
HYBRID_ENTRIES = [
    _entry("gen_late_p99_ms.hybrid", "ms", "lower", "host_clock",
           "load generator"),
    _entry("decode_rows_mean.hybrid", "rows", "higher", "program_counter",
           "batcher"),
    _entry("prefill_p50_ms.hybrid", "ms", "lower", "device_trace",
           "model step"),
    _entry("decode_block_ms_p50.hybrid", "ms", "lower", "device_trace",
           "model step"),
    _entry("attn_kernel_share.hybrid", "%", "lower", "device_trace",
           "kernels"),
    _entry("pool_copy_share.hybrid", "%", "lower", "device_trace",
           "device copies"),
    _entry("paged_decode_roofline.hybrid", "%", "higher", "device_trace",
           "kernels"),
    _entry("ssm_state_roofline", "%", "higher", "device_trace", "kernels"),
    _entry("moe_expert_roofline", "%", "higher", "device_trace", "kernels"),
    _entry("ssm_share", "%", "lower", "device_trace", "kernels"),
    _entry("moe_share", "%", "lower", "device_trace", "kernels"),
    _entry("moe_load_max_over_mean", "x", "lower", "program_counter",
           "batcher"),
]

#: and the tick ring's four, which that PR would list under ``.hybrid``
#: too; read here so that ``compiles_in_window`` is seen to be 0
RING_ENTRIES = [
    _entry(n + ".hybrid", u, "lower", s, l)
    for n, u, s, l in (
        ("tick_host_ms_p50", "ms", "program_span", "batcher"),
        ("host_gap_share", "%", "program_span", "batcher"),
        ("prefill_stall_share", "%", "program_span", "batcher"),
        ("compiles_in_window", "compiles", "program_counter", "model step"))]


def spec():
    """The cell's per-layer entries of BENCHMARK.json, ``HYBRID_ENTRIES``
    and ``RING_ENTRIES``, under a cell name of this process's (a run's trace
    is kept in a directory named after cell and seed)."""
    full = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    name = f"{CELL}.{os.getpid()}"
    mine = [dict(m, workloads=[name]) for m in
            full["per_layer"] + HYBRID_ENTRIES + RING_ENTRIES
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
    config = hybrid_tiny.tiny("mmamm", rows=3, n_pages=40)

    def run(**kw):
        return serve.run_cell(sp, sp["workloads"][0], dict(config), TRAFFIC,
                              seed=seed, seconds=3, t_start=0.0,
                              require_chip=False, out=lines.append, **kw)

    sound = run(trace=True, control=True)
    ring = [r for r in serving.flight(serving.TICK_COMPONENT).snapshot()
            if "state_rows" in r]

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
                 "expert_max": max(r["moe_expert_max"] for r in ring),
                 "touched": sum(r["moe_experts_touched"] for r in ring),
                 "blocks": sum(1 for r in ring
                               if r["name"] == "decode.block")},
        "broken": {"correct": broken["correct"], "check": broken["check"]},
        "lines": lines}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
