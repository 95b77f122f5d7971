"""Sliding-window layers beside full ones in one typed stack (PR 42): a
layer kind that carries its query heads, its rope and its window; rope as
data (YaRN's frequencies, a rotated fraction, a factor); the per-head output
gate; a feed-forward pattern with leading dense layers; the window cache (a
ring a row slot); the registries; and the programs of the stacks that were
there before, which must lower to the text they lowered to.

The logits comparisons run the program's ``decode_step`` and
``ContinuousBatcher`` against the plain reference of the benchmark
(``benchmark/models/laguna_reference.py``: float32, ``HIGHEST``, no cache),
at a tiny size: 1 + 4 layers, hidden 64, heads of 16 (half rotated under
YaRN in the full layers), 6 against 8 query heads over 2 K/V heads, a window
of 8, pages of 8, 16 experts top-4.  Tolerance: both sides compute in
float32 and differ in the order of their sums (flash blocks, the sorted
expert layout, the ring's order) and in ``rsqrt`` against ``1 / sqrt``:
logits of magnitude ~4 agree to 2e-5 absolute (1.7e-6 seen)."""

import hashlib
import itertools
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "benchmark_tests"))

import laguna_tiny as lt  # noqa: E402
from benchmark.models import laguna, laguna_reference  # noqa: E402
from tfmesos_tpu.models import transformer as T  # noqa: E402
from tfmesos_tpu.ops import attention as A  # noqa: E402
from tfmesos_tpu.ops.layers import rope, yarn_inv_freq  # noqa: E402

ATOL = 2e-5
F32 = jnp.float32


@pytest.fixture(scope="module")
def model():
    return lt.tiny("FSSSF", 1)


@pytest.fixture(scope="module")
def weights(model):
    return laguna.make_weights(model, 11, F32)


# -- rope as data -----------------------------------------------------------

def test_yarn_frequencies_against_the_formula_written_out():
    d, theta, factor, orig, fast, slow = 64, 500000.0, 64.0, 4096, 64.0, 1.0
    got = yarn_inv_freq(d, theta, factor, orig, fast, slow)
    c = lambda r: d * math.log(orig / (2 * math.pi * r)) / (
        2 * math.log(theta))
    lo, hi = max(math.floor(c(fast)), 0), min(math.ceil(c(slow)), d - 1)
    assert (lo, hi) == (5, 16)      # by hand: c(64) = 5.66, c(1) = 15.8
    want = []
    for i in range(d // 2):
        f = theta ** (-2 * i / d)
        ramp = min(max((i - lo) / (hi - lo), 0.0), 1.0)
        want.append(f / factor * ramp + f * (1 - ramp))
    np.testing.assert_allclose(got, np.asarray(want, np.float32), rtol=1e-6)
    assert got.dtype == np.float32 and got.shape == (32,)
    # the fastest pair keeps plain rope's frequency, the slowest is divided
    assert got[0] == 1.0 and got[-1] == pytest.approx(
        theta ** (-62 / 64) / 64, rel=1e-6)


@pytest.mark.parametrize("rotary_dim,factor", [(None, 1.0), (8, 1.0),
                                               (8, 1.4158883083359672),
                                               (16, 0.5)])
def test_rope_rotated_fraction_and_factor(rotary_dim, factor):
    rng = np.random.default_rng(0)
    x = rng.standard_normal((2, 5, 3, 16)).astype(np.float32)
    pos = np.asarray([[0, 1, 2, 3, 700], [9, 8, 7, 6, 5]], np.int32)
    inv = yarn_inv_freq(rotary_dim or 16, 500000.0, 64.0, 32, 4.0, 1.0)
    got = np.asarray(rope(jnp.asarray(x), jnp.asarray(pos), inv_freq=inv,
                          rotary_dim=rotary_dim, factor=factor))
    rd = rotary_dim or 16
    want = x.copy()
    for b, t, h in itertools.product(range(2), range(5), range(3)):
        for j in range(rd // 2):
            a = float(pos[b, t]) * float(inv[j])
            c, s = math.cos(a) * factor, math.sin(a) * factor
            x1, x2 = x[b, t, h, j], x[b, t, h, j + rd // 2]
            want[b, t, h, j] = x1 * c - x2 * s
            want[b, t, h, j + rd // 2] = x1 * s + x2 * c
    np.testing.assert_allclose(got, want, atol=2e-5)
    # the channels past the rotated ones pass through untouched
    np.testing.assert_array_equal(got[..., rd:], x[..., rd:])


def test_rope_old_call_is_unchanged():
    x = jnp.asarray(np.random.default_rng(1).standard_normal((1, 4, 2, 8)),
                    F32)
    pos = jnp.arange(4)[None]
    half = 4
    freqs = 10000.0 ** (-np.arange(half, dtype=np.float32) / half)
    np.testing.assert_allclose(
        rope(x, pos, 10000.0), rope(x, pos, inv_freq=freqs), atol=1e-6)


def test_ropespec_kwargs():
    spec = T.RopeSpec(theta=500000.0, fraction=0.5, yarn=(64, 4096, 64, 1))
    kw = spec.kwargs(128)
    assert kw["rotary_dim"] == 64 and kw["inv_freq"].shape == (32,)
    assert kw["factor"] == pytest.approx(0.1 * math.log(64) + 1)
    assert kw["factor"] == pytest.approx(1.4158883083359672)
    assert T.RopeSpec(theta=1e4).kwargs(128) == {"theta": 1e4}
    with pytest.raises(ValueError, match="rotates"):
        T.RopeSpec(fraction=0.01).kwargs(16)


# -- the per-head gate ------------------------------------------------------

def test_per_head_gate_against_hand_arithmetic():
    cfg = T.TransformerConfig(d_model=8, n_heads=2, attn_head_dim=4,
                              attn_gate="head", dtype=F32)
    rng = np.random.default_rng(2)
    o = rng.standard_normal((1, 3, 8)).astype(np.float32)
    h = rng.standard_normal((1, 3, 8)).astype(np.float32)
    wg = rng.standard_normal((8, 2)).astype(np.float32)
    got = np.asarray(T._attn_gated(cfg, jnp.asarray(o), jnp.asarray(h),
                                   {"wg": jnp.asarray(wg)}))
    gate = 1 / (1 + np.exp(-(h @ wg)))              # [1, 3, heads]
    want = (o.reshape(1, 3, 2, 4) * gate[..., None]).reshape(1, 3, 8)
    np.testing.assert_allclose(got, want, atol=1e-6)
    # the elementwise form (Solar's) is untouched: a gate a channel
    cfg = T.TransformerConfig(d_model=8, n_heads=2, attn_head_dim=4,
                              attn_gate=True, dtype=F32)
    wg8 = rng.standard_normal((8, 8)).astype(np.float32)
    got = np.asarray(T._attn_gated(cfg, jnp.asarray(o), jnp.asarray(h),
                                   {"wg": jnp.asarray(wg8)}))
    np.testing.assert_allclose(got, o / (1 + np.exp(-(h @ wg8))), atol=1e-6)
    shapes = jax.eval_shape(lambda: T.init_params(
        T.TransformerConfig(vocab_size=16, d_model=8, n_layers=1, n_heads=2,
                            attn_head_dim=4, attn_gate="head", d_ff=8),
        jax.random.PRNGKey(0)))
    assert shapes["layers"]["wg"].shape == (1, 8, 2)
    with pytest.raises(ValueError, match="attn_gate"):
        T.TransformerConfig(attn_gate="channel")


# -- the pattern ------------------------------------------------------------

def typed(kinds, lead=0, **kw):
    names = {"a": "attention", "w": "window", "m": "mamba"}
    n = len(kinds)
    extra = {}
    if lead or kw.pop("ffn", False):
        extra = dict(ffn_types=("dense",) * lead + ("sparse",) * (n - lead),
                     n_experts=4, top_k=2, moe_impl="grouped")
    if "w" in kinds:
        extra["window"] = 8
    if "m" in kinds:
        extra["mamba_heads"] = 2
    return T.TransformerConfig(
        vocab_size=32, d_model=16, n_layers=n, n_heads=2, d_ff=16,
        layer_types=tuple(names[c] for c in kinds), **extra, **kw)


def test_layer_period_and_runs_behind_a_leading_layer():
    cfg = typed("awwwa", lead=1)
    assert (cfg.n_lead_layers, cfg.layer_period) == (1, 4)
    assert cfg.layer_runs == (("window", 0, 3, 0), ("attention", 3, 1, 0))
    assert (cfg.n_attn_layers, cfg.n_window_layers, cfg.n_sparse_layers) \
        == (2, 3, 4)
    # the published 40 layers: [a w w w] x 10 with one leading dense layer
    # is 9 whole periods of [w w w a] behind it and a partial one
    cfg = typed("awww" * 10, lead=1)
    assert (cfg.layer_period, cfg.n_sparse_layers) == (4, 39)
    assert cfg.layer_runs == (("window", 0, 3, 0), ("attention", 3, 1, 0))
    assert typed("aawwa", lead=2).layer_runs == (
        ("window", 0, 2, 0), ("attention", 2, 1, 0))
    # no leading layer: whole periods only, as ever
    assert typed("mam").layer_period == 3
    assert typed("awaw", ffn=True).layer_period == 2
    assert typed("awaw", ffn=True).n_lead_layers == 0


@pytest.mark.parametrize("bad,match", [
    (dict(kinds="aw", window=None), "window"),
    (dict(kinds="aa", window=8), "window"),
    (dict(kinds="aa", window_heads=4), "window_heads"),
    (dict(kinds="aw", window_heads=3, n_kv_heads=2), "multiple"),
])
def test_window_kind_is_stated_whole(bad, match):
    kinds = bad.pop("kinds")
    names = {"a": "attention", "w": "window"}
    base = dict(vocab_size=32, d_model=16, n_layers=len(kinds), n_heads=2,
                layer_types=tuple(names[c] for c in kinds))
    if "w" in kinds:
        base["window"] = 8
    base.update(bad)
    with pytest.raises(ValueError, match=match):
        T.TransformerConfig(**base)


@pytest.mark.parametrize("ffn", [("sparse", "dense"), ("dense", "dense"),
                                 ("dense",), ("dense", "moe")])
def test_dense_layers_lead(ffn):
    with pytest.raises(ValueError, match="ffn_types"):
        T.TransformerConfig(
            vocab_size=32, d_model=16, n_layers=2, n_heads=2,
            layer_types=("attention", "attention"), ffn_types=ffn,
            n_experts=4, moe_impl="grouped")


def test_params_are_stacked_by_kind_and_by_feed_forward(model):
    cfg = laguna.program_config(model, 128)
    shapes = jax.tree_util.tree_map(
        lambda a: a.shape,
        jax.eval_shape(lambda: T.init_params(cfg, jax.random.PRNGKey(0))))
    lay = shapes["layers"]
    assert lay["attention"]["wq"] == (2, 64, 6 * 16)
    assert lay["window"]["wq"] == (3, 64, 8 * 16)
    assert lay["window"]["wo"] == (3, 8 * 16, 64)
    assert lay["attention"]["wg"] == (2, 64, 6)
    assert lay["window"]["wg"] == (3, 64, 8)
    assert lay["dense"]["w_gate"] == (1, 64, 96)
    assert lay["e_gate"] == (4, 16, 64, 32) and lay["router"] == (4, 64, 16)
    assert lay["s_down"] == (4, 32, 64) and lay["attn_norm"] == (5, 64)
    made = jax.tree_util.tree_map(
        lambda a: a.shape, jax.eval_shape(
            lambda: laguna.make_weights(model, 1, F32)))
    drawn = dict(shapes["layers"])
    drawn.pop("router_bias")        # no selection bias is drawn
    assert made["layers"] == drawn


# -- the window cache -------------------------------------------------------

def test_ring_bytes_do_not_depend_on_the_context(model):
    cfg = laguna.program_config(model, 128)
    state = jax.eval_shape(lambda: T.init_row_state(cfg, 4))
    assert set(state) == {"swa_k", "swa_v"}
    # [window layers, rows, K/V heads, window, head size]: a row holds the
    # window's positions and not one more, whatever max_len is
    assert state["swa_k"].shape == (3, 4, 2, 8, 16)
    per_row = sum(math.prod(s.shape) * s.dtype.itemsize
                  for s in state.values()) // 4
    assert per_row == laguna.state_bytes_per_row(model, itemsize=4)
    big = jax.eval_shape(lambda: T.init_row_state(
        laguna.program_config(model, 100000), 4))
    assert big["swa_k"].shape == state["swa_k"].shape
    # the pool backs the full layers only
    pool = jax.eval_shape(lambda: T.init_paged_cache(cfg, 10, 8))
    assert pool["k"].shape == (2, 10, 2, 8, 16)


@pytest.mark.parametrize("plen", [
    3,      # shorter than the window: decode starts inside it
    8,      # exactly the window
    9,      # one past: slot 0 is taken over by position 8
    29,     # several wraps in the prompt, the last one partial
    70,     # the bucket's padding lies past the last real position
])
def test_prefill_then_decode_logits_match_the_reference(model, weights, plen):
    prompt = np.random.default_rng(plen).integers(0, 256, plen)
    # 24 steps: the ring wraps three times while decoding
    got, toks, _ = lt.program_logits(model, weights, prompt, 24)
    want = lt.reference_logits(model, weights, prompt, toks)
    np.testing.assert_allclose(got, want, atol=ATOL)


@pytest.mark.parametrize("kinds,dense", [("FSSSFSSS", 1), ("FFSSF", 2),
                                         ("SF", 1)])
def test_other_patterns_match_the_reference(kinds, dense):
    """A partial last period, two leading layers, a leading window layer."""
    model = lt.tiny(kinds, dense)
    weights = laguna.make_weights(model, 5, F32)
    prompt = np.random.default_rng(3).integers(0, 256, 13)
    got, toks, _ = lt.program_logits(model, weights, prompt, 12)
    want = lt.reference_logits(model, weights, prompt, toks)
    np.testing.assert_allclose(got, want, atol=ATOL)


def test_a_reused_slot_shows_nothing_of_the_row_before(model, weights):
    rng = np.random.default_rng(7)
    first, second = rng.integers(0, 256, 40), rng.integers(0, 256, 5)
    _, _, store = lt.program_logits(model, weights, first, 20)
    clean, toks, _ = lt.program_logits(model, weights, second, 16)
    # the same slot after another row (its ring full of that row's K/V, to
    # beyond where the short prompt writes), and after a store of ones
    reused, toks_r, _ = lt.program_logits(model, weights, second, 16,
                                          store=store)
    dirty, toks_d, _ = lt.program_logits(model, weights, second, 16,
                                         dirty=True)
    assert toks == toks_r == toks_d
    np.testing.assert_array_equal(reused, clean)
    np.testing.assert_array_equal(dirty, clean)
    np.testing.assert_allclose(
        clean, lt.reference_logits(model, weights, second, toks), atol=ATOL)


def test_the_ring_holds_the_windows_positions(model, weights):
    """After a prompt of 13 and 6 steps (positions 0..18 written), slot s of
    a window layer's ring holds the K of the last position congruent to s:
    the window's 8 positions and nothing older."""
    from tfmesos_tpu.ops.layers import rms_norm
    prompt = np.random.default_rng(9).integers(0, 256, 13)
    _, toks, (_, state) = lt.program_logits(model, weights, prompt, 7)
    cfg = laguna.program_config(model, 128)
    seq = np.zeros(laguna_reference.Q_BLOCK, np.int32)     # padded: causal
    seq[:19] = np.concatenate([prompt, toks[:6]])
    # layer 1 is the first window layer; its input is layer 0's output,
    # which the reference gives: recompute layer 1's K by hand from it
    x = laguna_reference._embed(weights["embed"], jnp.asarray(seq), None)
    dm = laguna_reference.dims(model)
    inv, fac = laguna_reference.rope_tables(model, "attention")
    x = laguna_reference.mixer(x, weights["layers"], 0, 0, dm=dm,
                               kind="attention", heads=6, inv_freq=inv,
                               factor=fac, quantize=None)
    x, _ = laguna_reference.ffn_block(x, weights["layers"], 0, 0, dm=dm,
                                      dense=True, quantize=None)
    h = rms_norm(x, weights["layers"]["attn_norm"][1], 1e-6)
    k = (h @ weights["layers"]["window"]["wk"][0]).reshape(-1, 2, 16)
    k = rope(k[None], jnp.arange(len(seq))[None], 10000.0)[0]
    ring = np.asarray(state["swa_k"][0, 2])             # [KV, W, Dh]
    for slot in range(8):
        p = 18 - (18 - slot) % 8
        np.testing.assert_allclose(ring[:, slot], np.asarray(k[p]),
                                   atol=1e-5)
    assert cfg.window == 8


def test_continuous_batcher_serves_the_references_tokens(model, weights):
    """Through ``ContinuousBatcher`` under the pipelined carry, 3 row slots
    for 8 requests (slots re-used), contexts of 3 to 100 on both sides of
    the window: every served token's reference logit is the reference's best
    to within the tolerance (the comparison that decides ``correct``)."""
    from tfmesos_tpu import serving
    from tfmesos_tpu.serving import ContinuousBatcher, Request
    cfg = laguna.program_config(model, 128)
    b = ContinuousBatcher(cfg, weights, rows=3, max_len=128, page_size=8,
                          n_pages=72, prefill_bucket=8, multi_step=4,
                          pipeline_depth=None)
    assert b.pipeline_depth == 1 and b._pipelined
    assert b.suspend_bypass_reason == "sliding-window ring"
    rng = np.random.default_rng(1)
    reqs = [Request(prompt=rng.integers(0, 256, n).astype(np.int32),
                    max_new_tokens=m)
            for n, m in [(3, 20), (8, 30), (9, 12), (25, 40), (70, 30),
                         (5, 9), (16, 17), (40, 50)]]
    done = list(b.run(reqs))
    assert len(done) == len(reqs)
    for c in done:
        assert len(c.tokens) == c.request.max_new_tokens
        gap = laguna_reference.served_gaps(
            weights, model, c.request.prompt, c.tokens)["gap"]
        assert gap.max() <= ATOL, (len(c.request.prompt), gap.max())
    ring = [r for r in serving.flight(serving.TICK_COMPONENT).snapshot()
            if r.get("name") == "decode.block" and "swa_positions" in r]
    assert ring
    for r in ring:
        # a row holds min(context, window) positions in a window layer
        # (the draining tick reads a block back and dispatches none: 0)
        assert r["swa_positions"] <= 8 * r["rows"]
        assert r["swa_positions"] >= r["rows"] or not r["ctx_positions"]
        assert r["ctx_positions"] >= r["swa_positions"]
        # every assignment lies in a live tile, and a tile holds 16 rows
        # (the lagged loop books a block's counts where it is read back)
        assert r["moe_tile_rows"] >= r["moe_assignments"]
        assert r["moe_tile_rows"] % 16 == 0
    assert sum(r["moe_assignments"] for r in ring) > 0
    assert max(r["ctx_positions"] / r["swa_positions"] for r in ring
               if r["swa_positions"]) > 4
    assert b.row_state_bytes == 3 * laguna.state_bytes_per_row(model, 4)


# -- the registries ---------------------------------------------------------

def test_the_ring_closes_every_surface_with_one_reason():
    from tfmesos_tpu.serving import (BYPASS_ALLOWLIST,
                                     compute_bypass_reasons)
    reason = "sliding-window ring"
    closed = ("prefix_cache", "kv_tier", "suspend", "speculative",
              "kv_export")
    for reg, allowed in BYPASS_ALLOWLIST.items():
        assert (reason in allowed) == (reg in closed), reg
    for spec, shards, q, pd in itertools.product(
            (False, True), (1, 2), (False, True), (0, 1)):
        got = compute_bypass_reasons(window=True, speculative=spec,
                                     n_shards=shards, quantized_cache=q,
                                     pipeline_depth=pd)
        for reg, val in got.items():
            assert val is None or val in BYPASS_ALLOWLIST[reg], (reg, val)
        assert got["prefix_cache"] == got["speculative"] \
            == got["kv_export"] == reason
        if shards == 1:
            assert got["kv_tier"] == got["suspend"] == reason
        # the carry composes: the rings ride the donated pool
        assert got["pipeline"] == ("speculative decoding"
                                   if spec and pd else None)
    # nothing of it without window layers; a recurrent state's reason wins
    assert reason not in compute_bypass_reasons(pipeline_depth=1).values()
    both = compute_bypass_reasons(window=True, recurrent=True)
    assert both["suspend"] == "recurrent row state"


@pytest.mark.parametrize("what,kw", [
    ("a mesh", dict(mesh="mesh")),
    ("prefill_chunk", dict(prefill_chunk=8)),
    ("quantized_cache", dict(quantized_cache=True)),
    ("speculative", dict(draft_cfg="cfg", draft_params={})),
])
def test_what_a_window_row_cannot_do_is_refused(model, weights, what, kw):
    from tfmesos_tpu.serving import ContinuousBatcher
    cfg = laguna.program_config(model, 128)
    if "mesh" in kw:
        from jax.sharding import Mesh
        kw = dict(mesh=Mesh(np.asarray(jax.devices()[:1]), ("dp",)))
    if "draft_cfg" in kw:
        kw = dict(draft_cfg=cfg, draft_params=weights)
    with pytest.raises(ValueError, match=what):
        ContinuousBatcher(cfg, weights, rows=2, max_len=64, page_size=8,
                          n_pages=20, **kw)


# -- long prompts through the flash kernel ----------------------------------

@pytest.mark.parametrize("t,window", [(160, None), (160, 24), (200, 70),
                                      (129, None)])
def test_segmented_flash_attention_is_attention(monkeypatch, t, window):
    """Past ``flash_max_keys`` a forward-only call runs segment by segment
    and merges the partials by their log-sum-exps (here at 64 keys a call,
    the kernel interpreted)."""
    monkeypatch.setattr(A, "FLASH_MAX_KV_BYTES", 64 * 2 * A.LANES * 4)
    rng = np.random.default_rng(t)
    q = jnp.asarray(rng.standard_normal((1, t, 6, 16)), F32)
    k = jnp.asarray(rng.standard_normal((1, t, 2, 16)), F32)
    v = jnp.asarray(rng.standard_normal((1, t, 2, 16)), F32)
    got = A.flash_attention(q, k, v, causal=True, window=window,
                            interpret=True, forward_only=True)
    want = A.mha_reference(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(got, want, atol=2e-6)


@pytest.mark.parametrize("window", [None, 24])
def test_long_sequences_stay_differentiable(monkeypatch, window):
    """Only a caller that states ``forward_only`` (the serving prefill)
    takes the segmented forward, which has no VJP: a gradient through
    ``flash_attention`` past ``flash_max_keys`` runs the kernel pair it
    always ran."""
    monkeypatch.setattr(A, "FLASH_MAX_KV_BYTES", 64 * 2 * A.LANES * 4)
    monkeypatch.setattr(A, "_flash_segmented", lambda *a, **k: 1 / 0)
    rng = np.random.default_rng(7)
    q = jnp.asarray(rng.standard_normal((1, 160, 4, 16)), F32)
    k = jnp.asarray(rng.standard_normal((1, 160, 2, 16)), F32)
    v = jnp.asarray(rng.standard_normal((1, 160, 2, 16)), F32)

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v) ** 2)

    got = jax.grad(loss(lambda q, k, v: A.flash_attention(
        q, k, v, causal=True, window=window, interpret=True)),
        argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss(lambda q, k, v: A.mha_reference(
        q, k, v, causal=True, window=window)), argnums=(0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, atol=2e-5)


# -- the stacks that were there lower to the text they lowered to -----------

COMMON = dict(vocab_size=64, d_model=32, n_heads=4, n_kv_heads=2, d_ff=48,
              max_seq_len=64, dtype=F32, param_dtype=F32)
MOE = dict(n_experts=8, top_k=2, moe_impl="grouped", experts_held=4,
           shared_d_ff=16, norm_eps=1e-5, logits_dtype=F32, rope=False)
#: tiny stacks of the four accepted configurations' kinds, and the sha256
#: (first 16 hex digits) of their decode and prefill programs' lowered text
#: at PR 44 (whose ``_split_heads`` barrier is in every stack's projections),
#: taken with this file's code; the two plain stacks' (``plain``, ``eva``) at
#: PR 49, whose layer scan takes its layer of the stacked weights itself: the
#: typed stacks' text is PR 44's still
BEFORE = {
    "plain": (dict(n_layers=2), "2e0e647e1eee4004", "7997ffc6e8436ced"),
    "eva": (dict(n_layers=2, attention="eva", eva_chunk=2, eva_window=16,
                 n_pred_heads=2, residual_dtype=F32, logits_dtype=F32),
            "22e4142cd4715b16", "4ec387ca3e8a217c"),
    "granite": (dict(n_layers=4, layer_types=("mamba",) * 3 + ("attention",),
                     mamba_heads=4, mamba_head_dim=8, mamba_state=8,
                     mamba_chunk=8, embed_scale=2.0, residual_scale=0.5,
                     logits_scale=3.0, tie_embeddings=True, **MOE),
                "ceb3855bcd0409eb", "14b4081dff63eb58"),
    "solar": (dict(n_layers=4, layer_types=("attention",) + ("kda",) * 3,
                   kda_heads=2, kda_head_dim=8, kda_chunk=8,
                   kda_neg_eigval=True, attn_gate=True, attn_head_dim=8,
                   router_score="sigmoid", routed_scale=1.5, **MOE),
              "07188a8e83e898dd", "25b704156d285dbd"),
}


@pytest.mark.parametrize("what", ["decode", "prefill"])
@pytest.mark.parametrize("name", sorted(BEFORE))
def test_earlier_stacks_lower_to_the_same_text(name, what):
    kw, decode, prefill = BEFORE[name]
    cfg = T.TransformerConfig(**COMMON, **kw)
    rows, page, n_pages = 3, 8, 20
    params = jax.eval_shape(lambda: T.init_params(cfg, jax.random.PRNGKey(0)))

    def cache(b, fill):
        c = dict(jax.eval_shape(
            lambda: T.init_paged_cache(cfg, n_pages, page)))
        c["pages"] = jax.ShapeDtypeStruct((b, 8), jnp.int32)
        if cfg.keeps_row_state:
            c["state"] = jax.eval_shape(lambda: T.init_row_state(cfg, rows))
            if fill:
                c["slots"] = jax.ShapeDtypeStruct((b,), jnp.int32)
                c["valid"] = jax.ShapeDtypeStruct((b,), jnp.int32)
        return c

    tok = lambda b, t: jax.ShapeDtypeStruct((b, t), jnp.int32)
    if what == "decode":
        low = jax.jit(lambda p, c, t, at: T.decode_step(
            cfg, p, c, t, at)).lower(
                params, cache(rows, False), tok(rows, 1),
                jax.ShapeDtypeStruct((rows,), jnp.int32))
    else:
        low = jax.jit(lambda p, c, t: T.decode_step(cfg, p, c, t, 0)).lower(
            params, cache(1, True), tok(1, 16))
    got = hashlib.sha256(low.as_text().encode()).hexdigest()[:16]
    assert got == (decode if what == "decode" else prefill), (
        f"the {name} stack's {what} program lowers to other text than it "
        f"did: what was changed reaches a stack it should not")


# -- the expert layer's layout at many small experts ------------------------

@pytest.mark.parametrize("t,k,experts,held,off", [
    (37, 4, 16, 16, 0), (200, 8, 64, 24, 8), (513, 2, 8, 8, 0),
    (64, 3, 40, 5, 35), (128, 8, 256, 256, 0)])
def test_tile_rows_is_the_layouts_padding(t, k, experts, held, off):
    """What the batcher reports as ``moe_tile_rows`` (``moe.tile_rows`` of a
    step's counts) is what ``grouped_layout`` lays out under the tile
    ``grouped_experts`` picks for the step: the live tiles' rows."""
    from tfmesos_tpu.ops import moe
    rng = np.random.default_rng(t)
    idx = jnp.asarray(np.stack([rng.permutation(experts)[:k]
                                for _ in range(t)]), jnp.int32)
    tile = moe.pick_tile(t * k, experts)
    lay = moe.grouped_layout(idx, held, off, tile)
    want = int(lay["live_tiles"][0]) * tile
    assert int(moe.tile_rows(lay["counts"], t, k, experts)) == want
    # several steps and layers of counts at once, as a decode block has them
    both = jnp.stack([lay["counts"], lay["counts"]])[None]
    assert int(moe.tile_rows(both, t, k, experts)) == 2 * want
