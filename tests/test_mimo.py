"""K and V of unequal head size, K/V heads by kind of layer, a learned sink in
the window layers' softmax, the values scaled, and a K cache that lays two
heads' keys side by side (PR 45): the three attention kernels against their
references in interpret mode, and the program's ``decode_step`` and
``ContinuousBatcher`` against the plain reference of the benchmark
(``benchmark/models/mimo_reference.py``: float32, ``HIGHEST``, no cache) at
a tiny size: 1 + 6 layers ``a w w w w a w``, hidden 64, 8 query heads, keys
of 24 and values of 16 channels (8 rotated), 2 K/V heads in a full layer and
4 in a window layer, a window of 8, pages of 8, 8 experts top-2 of which 2
are held.  Tolerance: both sides compute in float32 and differ in the order
of their sums (flash blocks, the sorted expert layout, the ring's order) and
in ``rsqrt`` against ``1 / sqrt``: logits of magnitude ~4 agree to 2e-5
absolute (1.0e-6 seen); a kernel against its reference on unit-normal
inputs to 2e-5 (3e-7 seen)."""

import dataclasses
import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "benchmark_tests"))

import mimo_tiny as mt  # noqa: E402
from benchmark.models import mimo, mimo_reference  # noqa: E402
from tfmesos_tpu.models import transformer as T  # noqa: E402
from tfmesos_tpu.ops import attention as A  # noqa: E402

ATOL = 2e-5
F32 = jnp.float32
DK, DV = 24, 16


@pytest.fixture(scope="module")
def model():
    return mt.tiny()


@pytest.fixture(scope="module")
def weights(model):
    return mimo.make_weights(model, 11, F32)


@pytest.fixture(params=[128, 16], ids=["k_as_v", "k_packed"])
def lanes(request, monkeypatch):
    """The K caches in both layouts: at 128 lanes a key of 24 channels is
    laid out as V is; at 16 it is a lane tile and a half, and two heads'
    keys of a position lie side by side (``pack_k``), as 192 channels do on
    the chip."""
    monkeypatch.setattr(A, "LANES", request.param)
    return request.param


def normal(rng, *shape):
    return jnp.asarray(rng.standard_normal(shape), F32)


def packed(k, f):
    """[.., KV, M, D] -> [.., KV / f, M, f * D]: the layout ``pack_k`` names,
    built the long way round (head ``i`` of a row's channels ``i D .. (i +
    1) D``)."""
    *lead, kv, m, d = k.shape
    k = k.reshape(*lead, kv // f, f, m, d)
    return jnp.moveaxis(k, -3, -2).reshape(*lead, kv // f, m, f * d)


# -- the rule ---------------------------------------------------------------

@pytest.mark.parametrize("d,kv,f", [(128, 8, 1), (192, 4, 2), (192, 8, 2),
                                    (192, 1, 1), (64, 8, 1), (256, 4, 1),
                                    (320, 2, 2), (96, 4, 1)])
def test_pack_k_packs_a_tile_and_a_half(d, kv, f):
    """Two heads side by side where a key is whole lane tiles and a half
    past the first, and the heads pair up; every accepted configuration's
    128 channels stay as they were."""
    assert A.pack_k(d, kv) == f


def test_packing_is_a_reshape_of_a_positions_keys():
    """What the writes rely on: a position's keys ``[KV, D]`` as they lie
    ARE its ``[KV / f, f D]`` row of the packed cache."""
    k = normal(np.random.default_rng(0), 3, 4, 5, DK)      # [B, KV, M, D]
    tokens = jnp.moveaxis(k, 1, 2)                         # [B, M, KV, D]
    want = jnp.moveaxis(tokens.reshape(3, 5, 2, 2 * DK), 1, 2)
    np.testing.assert_array_equal(packed(k, 2), want)
    np.testing.assert_array_equal(A._unpack_k(packed(k, 2), 2), k)


def test_packed_queries_give_each_head_its_own_scores():
    rng = np.random.default_rng(1)
    q, k = normal(rng, 2, 4, 3, DK), normal(rng, 2, 4, 7, DK)
    got = jnp.einsum("bkrd,bkmd->bkrm", A._pack_queries(q, 2), packed(k, 2))
    want = jnp.einsum("bkrd,bkmd->bkrm", q, k)
    np.testing.assert_allclose(got.reshape(2, 4, 3, 7), want, atol=1e-5)


# -- the kernels, in interpret mode, against their references ----------------

@pytest.mark.parametrize("sink", [False, True], ids=["no_sink", "sink"])
@pytest.mark.parametrize("f", [1, 2], ids=["k_as_v", "k_packed"])
@pytest.mark.parametrize("g", [16, 8])
def test_flash_decode_at_unequal_head_sizes(g, f, sink):
    rng = np.random.default_rng(g + f)
    b, kv, m = 3, 4, 32
    q = normal(rng, b, kv * g, DK)
    k, v = normal(rng, 2, b, kv, m, DK), normal(rng, 2, b, kv, m, DV)
    pos = jnp.asarray([0, 13, 31], jnp.int32)
    s = normal(rng, kv * g) if sink else None
    got = A.flash_decode(q, packed(k, f), v, pos, layer=1, block_m=16,
                         interpret=True, sink=s)
    want = A._decode_reference(q, k[1], v[1], pos, DK ** -0.5, s)
    assert got.shape == (b, kv * g, DV)
    np.testing.assert_allclose(got, want, atol=ATOL)
    # the path a CPU takes reads the packed cache as the heads it holds
    np.testing.assert_allclose(
        A.flash_decode(q, packed(k, f), v, pos, layer=1, sink=s), want,
        atol=ATOL)


@pytest.mark.parametrize("self_kv", [False, True], ids=["pool", "deferred"])
@pytest.mark.parametrize("f", [1, 2], ids=["k_as_v", "k_packed"])
@pytest.mark.parametrize("g", [16, 8])
def test_flash_decode_paged_at_unequal_head_sizes(g, f, self_kv):
    rng = np.random.default_rng(10 * g + f)
    b, kv, ps, n_pages, width = 3, 4, 8, 20, 4
    q = normal(rng, b, kv * g, DK)
    k, v = (normal(rng, 2, n_pages, kv, ps, d) for d in (DK, DV))
    table = jnp.asarray(rng.permutation(n_pages)[:b * width].reshape(
        b, width), jnp.int32)
    pos = jnp.asarray([0, 9, 31], jnp.int32)
    own = None
    if self_kv:
        own = (normal(rng, b, 1, kv, DK), normal(rng, b, 1, kv, DV))
    want = A._paged_decode_reference(q, k, v, table, pos, DK ** -0.5,
                                     layer=1, self_kv=own)
    if own is not None and f > 1:       # the chunk as the pool holds keys
        own = (own[0].reshape(b, 1, kv // f, f * DK), own[1])
    got = A.flash_decode_paged(q, packed(k, f), v, table, pos, layer=1,
                               interpret=True, self_kv=own)
    assert got.shape == (b, kv * g, DV)
    np.testing.assert_allclose(got, want, atol=ATOL)
    np.testing.assert_allclose(
        A._paged_decode_reference(q, packed(k, f), v, table, pos,
                                  DK ** -0.5, layer=1, self_kv=own),
        want, atol=ATOL)


@pytest.mark.parametrize("sink", [False, True], ids=["no_sink", "sink"])
@pytest.mark.parametrize("t,window,max_keys", [
    (40, None, None), (40, 8, None), (200, 24, None), (160, None, 64),
    (160, 24, 64), (200, 70, 64)])
@pytest.mark.parametrize("g", [16, 8])
def test_flash_forward_at_unequal_head_sizes(monkeypatch, g, t, window,
                                             max_keys, sink):
    """The forward kernel, whole and (``max_keys``) in segments merged by
    their log-sum-exps: a sink joins one partial a query segment, so the
    merge counts it once."""
    if max_keys:
        monkeypatch.setattr(A, "FLASH_MAX_KV_BYTES",
                            max_keys * 2 * A.LANES * 4)
        assert A.flash_max_keys(DK, DV, 4) == max_keys
    rng = np.random.default_rng(t + g)
    kv = 2
    q, k, v = (normal(rng, 1, t, kv * g, DK), normal(rng, 1, t, kv, DK),
               normal(rng, 1, t, kv, DV))
    s = normal(rng, kv * g) if sink else None
    got = A.flash_attention(q, k, v, causal=True, window=window, sink=s,
                            interpret=True, forward_only=True)
    want = A.mha_reference(q, k, v, causal=True, window=window, sink=s)
    assert got.shape == (1, t, kv * g, DV)
    np.testing.assert_allclose(got, want, atol=ATOL)


@pytest.mark.parametrize("what", ["sink", "head sizes"])
def test_the_backward_keeps_one_head_size_and_no_sink(what):
    rng = np.random.default_rng(3)
    q, k = normal(rng, 1, 16, 4, DK), normal(rng, 1, 16, 2, DK)
    v = normal(rng, 1, 16, 2, DK if what == "sink" else DV)
    s = normal(rng, 4) if what == "sink" else None
    with pytest.raises(ValueError, match="forward_only"):
        A.flash_attention(q, k, v, causal=True, sink=s, interpret=True)


def test_a_sink_of_minus_infinity_is_no_sink_bit_for_bit():
    """In each kernel's recurrence and in each reference."""
    rng = np.random.default_rng(4)
    none = jnp.full((8,), -jnp.inf, F32)
    q, k, v = (normal(rng, 1, 40, 8, DK), normal(rng, 1, 40, 2, DK),
               normal(rng, 1, 40, 2, DV))
    for kw in ({"interpret": True, "forward_only": True}, {}):
        np.testing.assert_array_equal(
            A.flash_attention(q, k, v, causal=True, window=8, sink=none, **kw),
            A.flash_attention(q, k, v, causal=True, window=8, **kw))
    kc, vc = normal(rng, 2, 2, 16, DK), normal(rng, 2, 2, 16, DV)
    pos = jnp.asarray([3, 15], jnp.int32)
    for kw in ({"interpret": True}, {}):
        np.testing.assert_array_equal(
            A.flash_decode(q[0, :2], kc, vc, pos, sink=none, **kw),
            A.flash_decode(q[0, :2], kc, vc, pos, **kw))


def test_the_byte_budget_is_8192_keys_at_128_channels():
    """The five accepted configurations (keys and values of 128 bfloat16
    channels) cut their long prompts where they did; keys of 192 (256
    lanes) and values of 128 fit 5,461."""
    assert A.flash_max_keys(128, 128, 2) == 8192
    assert A.flash_max_keys(192, 128, 2) == 5461
    assert A._flash_tiles(8192, 8192, 128, 2) == A._flash_tiles(
        8192, 8192, 128, 2, v_dim=128)
    assert A._paged_block(8, 64, 128, 2, 128) == A._paged_block(
        8, 64, 128, 2, 128, d_v=128, pack=1)
    # a pair of heads is taken whole, and 192 + 128 channels are 320
    assert A._paged_block(4, 64, 192, 2, 296, d_v=128, pack=2) == (4, 8)


# -- the configuration states it --------------------------------------------

COMMON = dict(vocab_size=64, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2,
              d_ff=48, max_seq_len=64, dtype=F32, param_dtype=F32)
TYPED = dict(layer_types=("attention", "window"), window=8)


@pytest.mark.parametrize("field,value", [
    ("attn_v_head_dim", 4), ("window_kv_heads", 4), ("window_sink", True),
    ("attn_value_scale", 0.5)])
def test_the_new_fields_are_a_window_stacks_and_forward_refuses_them(
        field, value):
    with pytest.raises(ValueError, match="window"):
        T.TransformerConfig(**COMMON, **{field: value})
    cfg = T.TransformerConfig(**COMMON, **TYPED, **{field: value})
    with pytest.raises(NotImplementedError, match="serving"):
        T.forward(cfg, {}, jnp.zeros((1, 4), jnp.int32))


def test_params_and_caches_take_their_shapes_from_the_kind(model, lanes):
    cfg = mimo.program_config(model, 128)
    p = jax.eval_shape(lambda: T.init_params(cfg, jax.random.PRNGKey(0)))
    att, win = p["layers"]["attention"], p["layers"]["window"]
    assert att["wk"].shape == (2, 64, 2 * DK) and "sink" not in att
    assert att["wv"].shape == (2, 64, 2 * DV)
    assert win["wk"].shape == (5, 64, 4 * DK)
    assert win["wv"].shape == (5, 64, 4 * DV)
    assert att["wo"].shape == (2, 8 * DV, 64)
    assert win["wo"].shape == (5, 8 * DV, 64)
    assert win["sink"].shape == (5, 8) and win["sink"].dtype == F32
    f = 1 if lanes == 128 else 2
    assert cfg.k_pack() == cfg.k_pack("window") == f
    pool = jax.eval_shape(lambda: T.init_paged_cache(cfg, 20, 8))
    assert pool["k"].shape == (2, 20, 2 // f, 8, f * DK)
    assert pool["v"].shape == (2, 20, 2, 8, DV)
    state = jax.eval_shape(lambda: T.init_row_state(cfg, 3))
    assert state["swa_k"].shape == (5, 3, 4 // f, 8, f * DK)
    assert state["swa_v"].shape == (5, 3, 4, 8, DV)
    with pytest.raises(ValueError, match="one head size"):
        T.init_paged_cache(cfg, 20, 8, quantized=True)
    # the sinks stay float32 under the program's int8 weights
    q = T.quantize_params(cfg, jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, s.dtype), p))
    assert q["layers"]["window"]["sink"].dtype == F32


# -- the program against the plain reference ---------------------------------

@pytest.mark.parametrize("plen", [
    3,      # shorter than the window: decode starts inside it
    8,      # exactly the window
    9,      # one past: slot 0 is taken over by position 8
    29,     # several wraps in the prompt, the last one partial
    70,     # the bucket's padding lies past the last real position
])
def test_prefill_then_decode_logits_match_the_reference(model, weights, plen,
                                                        lanes):
    prompt = np.random.default_rng(plen).integers(0, 256, plen)
    # 24 steps: the ring wraps three times while decoding
    got, toks, _ = mt.program_logits(model, weights, prompt, 24, dirty=True)
    want = mt.reference_logits(model, weights, prompt, toks)
    np.testing.assert_allclose(got, want, atol=ATOL)


@pytest.mark.parametrize("plen", [5, 70])
def test_the_program_through_its_kernels_matches_the_reference(
        monkeypatch, model, weights, lanes, plen):
    """As on the chip: the prefill through the flash forward (in segments
    past a threshold of 32 keys, with the sink in the window layers), the
    full layers' steps through the paged kernel with the step's own K/V as
    its self operand, the rings through ``flash_decode`` with the sink;
    each in interpret mode."""
    monkeypatch.setattr(A, "FLASH_MAX_KV_BYTES", 32 * 2 * A.LANES * 4)
    for name in ("flash_attention", "flash_decode"):
        monkeypatch.setattr(A, name, functools.partial(
            getattr(A, name), interpret=True))
    monkeypatch.setattr(T, "_decode_kernel_kwargs",
                        lambda *a, **k: {"interpret": True})
    calls = dict(A.PAGED_CALL_STATS)
    prompt = np.random.default_rng(plen).integers(0, 256, plen)
    got, toks, _ = mt.program_logits(model, weights, prompt, 12, dirty=True)
    assert A.PAGED_CALL_STATS["kernel_calls"] > calls["kernel_calls"]
    want = mt.reference_logits(model, weights, prompt, toks)
    np.testing.assert_allclose(got, want, atol=ATOL)


@pytest.mark.parametrize("kinds,dense", [("FSSSSFSSSSS", 1), ("FFSSF", 2),
                                         ("SF", 1)])
def test_other_patterns_match_the_reference(kinds, dense, lanes):
    """A partial last period, two leading layers, a leading window layer."""
    model = mt.tiny(kinds, dense)
    weights = mimo.make_weights(model, 5, F32)
    prompt = np.random.default_rng(3).integers(0, 256, 13)
    got, toks, _ = mt.program_logits(model, weights, prompt, 12)
    want = mt.reference_logits(model, weights, prompt, toks)
    np.testing.assert_allclose(got, want, atol=ATOL)


def test_a_sinkless_window_layer_is_a_sink_of_minus_infinity(model, weights):
    """The program with ``window_sink`` and sinks of -inf computes, bit for
    bit, what it computes without the field and without the leaf."""
    cfg = mimo.program_config(model, 128)
    plain = dataclasses.replace(cfg, window_sink=False)
    lay = weights["layers"]
    off = {**weights, "layers": {**lay, "window": {
        **lay["window"], "sink": jnp.full_like(lay["window"]["sink"],
                                               -jnp.inf)}}}
    bare = {**weights, "layers": {**lay, "window": {
        k: v for k, v in lay["window"].items() if k != "sink"}}}
    prompt = jnp.asarray(np.random.default_rng(2).integers(0, 256, (1, 16)),
                         jnp.int32)

    def run(c, w):
        pages = jnp.arange(1, 17, dtype=jnp.int32)[None]
        cache = dict(T.init_paged_cache(c, 20, 8), pages=pages,
                     state=T.init_row_state(c, 2),
                     slots=jnp.asarray([1], jnp.int32),
                     valid=jnp.asarray([13], jnp.int32))
        logits, cache = T.decode_step(c, w, cache, prompt, 0)
        step = {"k": cache["k"], "v": cache["v"], "state": cache["state"],
                "pages": jnp.concatenate([jnp.zeros_like(pages), pages])}
        nxt, _ = T.decode_step(c, w, step, jnp.asarray([[0], [7]], jnp.int32),
                               jnp.asarray([0, 13], jnp.int32))
        return logits, nxt[1]

    for a, b in zip(run(cfg, off), run(plain, bare)):
        np.testing.assert_array_equal(a, b)
    # and the sinks as drawn do move the logits
    assert float(jnp.abs(run(cfg, weights)[0] - run(cfg, off)[0]).max()) > 1e-3


def test_the_shares_of_the_experts_add_up_behind_a_dense_layer():
    """The share test: behind a leading dense layer (the expert leaves are
    stacked over the sparse layers only), each of the four shares of two
    experts gives, through the program's expert layer told which experts it
    holds, its part of the routed sum; the parts add up to what the uncut
    reference's layer adds, and each is what the reference gives for that
    share.  Nothing is shared here, so nothing is counted twice."""
    whole = mt.tiny(held=8)
    w = mimo.make_weights(whole, 3, F32)["layers"]
    dm = mimo_reference.dims(whole)
    si = 3                              # the fourth sparse layer, layer 4
    h = normal(np.random.default_rng(5), 40, 64)
    want, _ = mimo_reference.routed_experts(h, w, si, dm, None)
    total = jnp.zeros_like(h)
    for shard in range(4):
        part = mt.tiny(held=2, shard=shard)
        cfg = mimo.program_config(part, 128)
        assert (cfg.experts_held, cfg.expert_offset) == (2, 2 * shard)
        assert cfg.n_lead_layers == 1 and cfg.n_sparse_layers == 6
        held = {k: w[k][:, 2 * shard:2 * shard + 2]
                for k in ("e_gate", "e_up", "e_down")}
        lp = {"router": w["router"][si], "router_bias": w["router_bias"][si],
              **held}
        got, aux = T._ffn(cfg, None, lp, h[None], expert_layer=si)
        ref, _ = mimo_reference.routed_experts(
            h, {**w, **held}, si, dm, None, held=(2 * shard, 2))
        np.testing.assert_allclose(got[0], ref, atol=ATOL)
        assert int(aux["expert_counts"].sum()) == int(
            ((mimo_reference.routing(h, w, si, dm)[1] // 2) == shard).sum())
        total = total + got[0]
    np.testing.assert_allclose(total, want, atol=ATOL)
    assert float(jnp.abs(want).max()) > 0.1


def test_continuous_batcher_serves_the_references_tokens(model, weights,
                                                         lanes):
    """Through ``ContinuousBatcher`` under the pipelined carry, 3 row slots
    for 8 requests (slots re-used), contexts of 3 to 100 on both sides of
    the window: every served token's reference logit is the reference's best
    to within the tolerance (the comparison that decides ``correct``), and
    the tick ring counts what the routers routed beside what fell here."""
    from tfmesos_tpu import serving
    from tfmesos_tpu.serving import ContinuousBatcher, Request
    cfg = mimo.program_config(model, 128)
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
        gap = mimo_reference.served_gaps(
            weights, model, c.request.prompt, c.tokens)["gap"]
        assert gap.max() <= ATOL, (len(c.request.prompt), gap.max())
    ring = [r for r in serving.flight(serving.TICK_COMPONENT).snapshot()
            if r.get("name") == "decode.block" and "moe_routed" in r]
    assert ring
    for r in ring:
        assert r["swa_positions"] <= 8 * r["rows"]
        assert r["ctx_positions"] >= r["swa_positions"]
        # every row's top-2 in each of 6 expert layers and 4 steps, of which
        # those on the 2 held experts of 8 are counted as assignments (the
        # lagged loop books a block's counts where it is read back)
        assert r["moe_routed"] in (0, 4 * 6 * 3 * 2)
        assert r["moe_assignments"] <= r["moe_routed"]
    routed = sum(r["moe_routed"] for r in ring)
    assert 0.1 < sum(r["moe_assignments"] for r in ring) / routed < 0.5
    assert b.row_state_bytes == 3 * mimo.state_bytes_per_row(model, 4)
