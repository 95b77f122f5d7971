"""The plain reference against the program's own forward pass at a tiny
size, float32 on the CPU; and its int8 control, which must differ."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import reference, tiny, weights
from benchmark.models import mistral


@pytest.fixture(scope="module")
def setup():
    config = tiny.config()
    config["hidden_size"], config["intermediate_size"] = 128, 256
    config["num_attention_heads"], config["head_dim"] = 4, 32
    w = weights.make_weights(config, 2 ** 31 + 3, dtype=jnp.float32)
    tokens = np.random.default_rng(0).integers(0, config["vocab_size"], 700,
                                               dtype=np.int32)
    return config, w, tokens


def program_logits(config, w, tokens):
    from tfmesos_tpu.models import transformer
    cfg = mistral.program_config(config, 1024)
    with jax.default_matmul_precision("highest"):
        return transformer.forward(cfg, w, jnp.asarray(tokens)[None])[0]


def test_reference_equals_program_forward(setup):
    config, w, tokens = setup
    # the program's rms_norm has eps 1e-6 built in: with the same eps the
    # two agree to float32 rounding over 700 positions (two query blocks)
    same_eps = dict(config, rms_norm_eps=1e-6)
    at = np.arange(0, 700, 7)
    ref = np.asarray(reference.logits_at(w, same_eps, tokens, at))
    got = np.asarray(program_logits(config, w, tokens))[at]
    assert ref.shape == got.shape == (100, config["vocab_size"])
    assert np.abs(got).max() > 1.0
    np.testing.assert_allclose(got, ref, atol=2e-4, rtol=0)
    # with the published eps (1e-5) the stated departure stays small
    ref5 = np.asarray(reference.logits_at(w, config, tokens, at))
    assert np.abs(ref5 - ref).max() < 2e-3


def test_reference_is_causal_under_padding(setup):
    config, w, tokens = setup
    a = np.asarray(reference.logits_at(w, config, tokens[:300], [10, 299]))
    b = np.asarray(reference.logits_at(w, config, tokens[:600], [10, 299]))
    np.testing.assert_allclose(a, b, atol=1e-5, rtol=0)


def test_int8_control_moves_the_logits(setup):
    config, w, tokens = setup
    at = np.arange(100, 500, 4)
    ref = np.asarray(reference.logits_at(w, config, tokens, at))
    low = np.asarray(reference.logits_at(w, config, tokens, at,
                                         quantize="int8"))
    err = np.abs(low - ref).max()
    assert 1e-3 < err < 0.5        # a real precision loss, not noise, not junk


def test_served_gaps_reads_greedy_tokens_as_zero_and_others_as_positive(setup):
    config, w, tokens = setup
    prompt = tokens[:40]
    served = []
    seq = list(prompt)
    for _ in range(6):              # greedy by the reference itself
        lg = np.asarray(reference.logits_at(w, config, np.array(seq),
                                            [len(seq) - 1]))[0]
        served.append(int(lg.argmax()))
        seq.append(served[-1])
    g = reference.served_gaps(w, config, prompt, served, control=True)
    assert g["gap"].shape == (6,) and np.all(g["gap"] == 0)
    assert np.all(g["control_gap"] >= 0)
    wrong = list(served)
    wrong[3] = (wrong[3] + 1) % config["vocab_size"]
    g2 = reference.served_gaps(w, config, prompt, wrong)
    assert g2["gap"][3] > 0 and np.all(g2["gap"][:3] == 0)


def test_fake_int8_has_127_levels_per_channel():
    w = jnp.asarray(np.random.default_rng(1).normal(size=(64, 8)), jnp.float32)
    q = np.asarray(reference.fake_int8(w, axis=0))
    for j in range(8):
        step = np.abs(np.asarray(w)[:, j]).max() / 127
        levels = np.round(q[:, j] / step)
        assert np.allclose(q[:, j], levels * step, atol=1e-6)
        assert np.abs(levels).max() == 127
