#!/usr/bin/env python3
"""Drive the tiny hybrid configuration through ``drivers/serve.py`` on the
CPU, from a copy of ``benchmark/`` to which it was added as files: one
traced run as it is, one with the batcher's sampler broken.  Prints one
JSON line.  Started by test_benchmark_second_adapter.py; never a
measurement."""

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main() -> int:
    import jax.numpy as jnp
    from benchmark import harness, reference, tiny, traffic_gen, window
    from benchmark.models import mistral
    from benchmark.drivers import serve
    from tfmesos_tpu import serving

    def not_this_reference(*args, **kwargs):
        raise AssertionError("the Mistral reference read a configuration "
                             "that names another model")

    reference.served_gaps = not_this_reference
    config = harness.load_json("configs", "tinyhybrid.json")
    spec = tiny.tiny_spec()
    traffic = traffic_gen.load_traffic("tinyhybrid_batch")
    seed = 2 ** 31 + 177
    lines = []

    def run(**kw):
        return serve.run_cell(spec, spec["workloads"][1], config,
                              traffic, seed=seed, seconds=3,
                              t_start=0.0, require_chip=False,
                              out=lines.append, **kw)

    sound = run(trace=True, control=True)

    def second_best(self, last, rids, steps):
        order = jnp.argsort(last.astype(jnp.float32), axis=-1)
        return order[..., -2].astype(jnp.int32)

    serving.ContinuousBatcher._sample = second_best
    broken = run(trace=False)
    model = harness.load_model(config)
    counters = {k: config["deployment"][k]
                for k in ("rows", "n_pages", "page_size")}
    print(json.dumps({
        "benchmark": os.path.dirname(os.path.abspath(harness.__file__)),
        "counts": {
            "kv_bytes": model.kv_bytes_per_context_token(config),
            "kv_bytes_dense": mistral.kv_bytes_per_context_token(config),
            "token_slots": model.token_slots(config, counters),
            "token_slots_dense": mistral.token_slots(config, counters)},
        "sound": {"correct": sound["correct"], "check": sound["check"],
                  "metrics": sound["metrics"],
                  "live_tokens_mean": window.live_tokens_mean(
                      sound["records"], sound["t0"], sound["t1"])},
        "broken": {"correct": broken["correct"], "check": broken["check"]},
        "lines": lines}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
