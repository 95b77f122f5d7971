"""costs.py against a hand count, at the served configuration's sizes."""

import json
import os

import pytest

from benchmark import costs, harness


def serve_config():
    with open(os.path.join(harness.HERE, "configs",
                           "mistral7b-l16-serve.json")) as f:
        return json.load(f)


def test_hand_count_mistral_16_layers():
    m = serve_config()
    attn = 4096 * 4096 * 2 + 4096 * 1024 * 2        # wq, wo + wk, wv
    mlp = 3 * 4096 * 14336
    assert costs.weights_per_layer(m) == attn + mlp == 218103808
    head = 4096 * 32768
    assert costs.matmul_params(m) == 16 * 218103808 + head
    assert costs.n_params(m) == 16 * 218103808 + 2 * head + 33 * 4096
    assert costs.n_params(m) == pytest.approx(3.76e9, rel=0.01)
    # forward, per token, at context t: 2 per weight + QK^T and PV at t/2
    t = 4096
    assert costs.forward_flops_per_token(m, t) == \
        2.0 * costs.matmul_params(m) + 16 * 2.0 * t * 4096
    assert costs.train_flops_per_token(m, t) == \
        3 * costs.forward_flops_per_token(m, t)


def test_kv_bytes():
    m = serve_config()
    # 16 layers x (K and V) x 8 kv heads x 128 x 2 bytes
    assert costs.kv_bytes_per_context_token(m) == 16 * 2 * 8 * 128 * 2 == 65536
    dep = m["deployment"]
    assert costs.pool_bytes(m, dep["n_pages"], dep["page_size"]) == \
        1300 * 64 * 65536
    assert costs.pool_bytes(m, 1300, 64) == pytest.approx(5.45e9, rel=0.01)
