"""Tiny stand-ins for a configuration, two traffic mixes and a spec, so that
the drivers can be rehearsed on the CPU.  Never a measurement."""

import copy

TINY_CONFIG = {
    "hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "vocab_size": 256, "rms_norm_eps": 1e-5, "rope_theta": 1e6,
    "sliding_window": None, "torch_dtype": "float32", "driver": "serve",
    "model": "mistral",
    "deployment": {"chips": 1, "rows": 4, "max_len": 256, "page_size": 16,
                   "n_pages": 80},
    "correct": {"sample_requests": 12, "limits": {"max_gap": 1e-3}},
}

TINY_OPEN = {
    "arrivals": {"kind": "open_loop", "rate_rps": 6.0},
    "ramp_s": 1.0, "grace_s": 2.0, "block": 8, "schedule_seed": 5,
    "prompt": {"dist": "lognormal", "median": 24, "sigma": 0.5, "min": 8,
               "max": 64},
    "output": {"dist": "lognormal", "median": 6, "sigma": 0.4, "min": 3,
               "max": 12},
}

TINY_BACKLOG = dict(
    TINY_OPEN, arrivals={"kind": "backlog", "requests": 2048}, grace_s=0.5,
    output={"dist": "lognormal", "median": 40, "sigma": 0.3, "min": 20,
            "max": 80})


def tiny_spec():
    cells = [("tiny.open", "tiny_open"), ("tiny.backlog", "tiny_backlog")]
    return {
        "workloads": [{"name": n, "config": "tiny", "traffic": t, "chips": 1,
                       "why": "rehearsal"} for n, t in cells],
        "end_to_end": [
            {"name": "ttft_p90_ms", "unit": "ms", "workloads": ["tiny.open"]},
            {"name": "tpot_p90_ms", "unit": "ms", "workloads": ["tiny.open"]},
            {"name": "tok_s", "unit": "tokens/s",
             "workloads": ["tiny.backlog"]},
            {"name": "setup_s", "unit": "s"}],
        "per_layer": [
            {"name": "gen_late_p99_ms", "unit": "ms", "moves": "ttft_p90_ms",
             "workloads": ["tiny.open"]},
            {"name": "pool_fill", "unit": "%", "moves": "tpot_p90_ms",
             "workloads": ["tiny.open"]},
            {"name": "gen_late_p99_ms.batch", "unit": "ms", "moves": "tok_s",
             "workloads": ["tiny.backlog"]},
            {"name": "decode_rows_mean.batch", "unit": "rows",
             "moves": "tok_s", "workloads": ["tiny.backlog"]},
            {"name": "pool_fill.batch", "unit": "%", "moves": "tok_s",
             "workloads": ["tiny.backlog"]},
            {"name": "prefill_p50_ms.batch", "unit": "ms", "moves": "tok_s",
             "workloads": ["tiny.backlog"]}],
    }


def config():
    return copy.deepcopy(TINY_CONFIG)
