"""Typed layers (Mamba-2 beside attention), the row-state store and the
grouped expert layer, program side: the two SSM forms against each other,
the grouped kernels (interpret mode) against the plain layout, the grouped
layer against the every-expert form and under a manual ``ep`` axis, and
``ContinuousBatcher`` over a typed stack (what it refuses and bypasses, the
lag and block modes token for token, the tick ring's fields).  The float32
reference of the whole model is the benchmark's
(tests/benchmark_tests/test_benchmark_granite_hybrid.py)."""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from tfmesos_tpu.models import transformer as tr
from tfmesos_tpu.models.transformer import TransformerConfig, init_params
from tfmesos_tpu.ops import moe, ssm
from tfmesos_tpu.serving import (TICK_COMPONENT, ContinuousBatcher, Request,
                                 compute_bypass_reasons, flight)

F32 = jnp.float32


def typed_cfg(kinds="mmam", **kw):
    names = {"m": "mamba", "a": "attention"}
    base = dict(
        vocab_size=128, d_model=64, n_layers=len(kinds), n_heads=4,
        n_kv_heads=2, d_ff=32, max_seq_len=256,
        dtype=F32, param_dtype=F32,
        layer_types=tuple(names[c] for c in kinds), mamba_heads=8,
        mamba_head_dim=16, mamba_state=16, mamba_chunk=32, rope=False,
        attn_scale=0.05, embed_scale=0.05, residual_scale=0.5,
        logits_scale=4.0, tie_embeddings=True, norm_eps=1e-5,
        n_experts=8, top_k=3, moe_impl="grouped", experts_held=4,
        expert_offset=0, shared_d_ff=48)
    base.update(kw)
    return TransformerConfig(**base)


# -- the two forms of the recurrence ------------------------------------------

def _ssm_inputs(t, seed=0):
    rng = np.random.default_rng(seed)
    b, h, p, n = 2, 4, 8, 16
    x = rng.normal(size=(b, t, h, p)).astype(np.float32)
    dt = np.log1p(np.exp(rng.normal(size=(b, t, h)) - 2)).astype(np.float32)
    a = -np.exp(rng.uniform(0, 2.5, size=(h,))).astype(np.float32)
    bm = rng.normal(size=(b, t, n)).astype(np.float32)
    cm = rng.normal(size=(b, t, n)).astype(np.float32)
    s0 = rng.normal(size=(b, h, p, n)).astype(np.float32)
    return map(jnp.asarray, (x, dt, a, bm, cm, s0))


def _stepwise(x, dt, a, bm, cm, s):
    ys = []
    for i in range(x.shape[1]):
        y, s = ssm.ssm_update(s, x[:, i], dt[:, i], a, bm[:, i], cm[:, i])
        ys.append(y)
    return jnp.stack(ys, axis=1), s


@pytest.mark.parametrize("t,chunk", [(32, 8), (21, 8), (5, 8), (64, 64),
                                     (40, 16)])
def test_ssd_scan_is_the_recurrence(t, chunk):
    """The chunked form against one-token updates: the same sums in another
    order, float32 (1e-5 of the largest value)."""
    x, dt, a, bm, cm, s0 = _ssm_inputs(t)
    y, s = ssm.ssd_scan(x, dt, a, bm, cm, s0, chunk)
    want_y, want_s = _stepwise(x, dt, a, bm, cm, s0)
    assert float(jnp.abs(y - want_y).max()) <= 1e-5 * float(
        jnp.abs(want_y).max())
    assert float(jnp.abs(s - want_s).max()) <= 1e-5 * float(
        jnp.abs(want_s).max())


def test_a_position_with_dt_zero_leaves_the_state_alone():
    x, dt, a, bm, cm, s0 = _ssm_inputs(24, seed=1)
    live = 13
    dt = dt.at[:, live:].set(0.0)
    _, s = ssm.ssd_scan(x, dt, a, bm, cm, s0, 8)
    _, want = ssm.ssd_scan(x[:, :live], dt[:, :live], a, bm[:, :live],
                           cm[:, :live], s0, 8)
    assert float(jnp.abs(s - want).max()) <= 1e-6 * float(jnp.abs(want).max())


def test_conv_tail_is_taken_at_the_true_end():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(2, 10, 6)), F32)
    w = jnp.asarray(rng.normal(size=(4, 6)), F32)
    b = jnp.asarray(rng.normal(size=(6,)), F32)
    out, xp = ssm.causal_conv(x, w, b)
    want = sum(jnp.pad(x, ((0, 0), (3, 0), (0, 0)))[:, j:j + 10] * w[j]
               for j in range(4)) + b
    assert float(jnp.abs(out - want).max()) < 1e-6
    tail = ssm.conv_tail(xp, jnp.asarray([10, 2]), 4)
    np.testing.assert_array_equal(np.asarray(tail[0]), np.asarray(x[0, 7:]))
    np.testing.assert_array_equal(np.asarray(tail[1, 1:]),
                                  np.asarray(x[1, :2]))
    assert float(jnp.abs(tail[1, 0]).max()) == 0
    # one more token continues from the tail
    nxt = jnp.asarray(rng.normal(size=(2, 1, 6)), F32)
    step, _ = ssm.causal_conv(nxt[:1], w, b, tail=tail[:1])
    full, _ = ssm.causal_conv(jnp.concatenate([x[:1], nxt[:1]], 1), w, b)
    assert float(jnp.abs(step[0, 0] - full[0, -1]).max()) < 1e-6


# -- the decode step's one-pass update (the Pallas kernel, interpret mode) --------
#
# name -> (mamba layers, rows, heads, head size, state, channels a block may
# hold or None for what the budget gives, the layer updated).  The XLA
# ``ssm_update`` on the layer's slice is the specification.
UPDATE_CASES = {
    "toy_one_tile": (2, 2, 8, 16, 16, None, 0),
    "granite_head_two_blocks": (3, 3, 4, 64, 128, 128, 1),
    "blocks_of_two_tiles": (3, 2, 16, 32, 128, 256, 2),
    # six tiles a row, room for four: the rule takes three (it divides)
    "budget_does_not_divide": (2, 2, 12, 64, 32, 512, 1),
    "head_wider_than_a_tile": (2, 2, 2, 256, 16, 256, 0),
    "head_of_one_sublane_group": (2, 1, 32, 8, 16, None, 1),
}


def _update_inputs(lm, rows, h, p, n, seed=0):
    rng = np.random.default_rng(seed)
    store = rng.normal(size=(lm, rows, h * p, n)).astype(np.float32)
    x = rng.normal(size=(rows, h, p)).astype(np.float32)
    dt = np.log1p(np.exp(rng.normal(size=(rows, h)) - 2)).astype(np.float32)
    a = -np.exp(rng.uniform(0, 2.5, size=(h,))).astype(np.float32)
    bm = rng.normal(size=(rows, n)).astype(np.float32)
    cm = rng.normal(size=(rows, n)).astype(np.float32)
    return map(jnp.asarray, (store, x, dt, a, bm, cm))


def _hold_blocks_to(monkeypatch, channels, n):
    if channels is not None:
        monkeypatch.setattr(ssm, "_UPDATE_VMEM_BUDGET", 4 * channels * n * 4)


@pytest.mark.parametrize("name", sorted(UPDATE_CASES))
def test_update_kernel_is_the_xla_update_in_place(monkeypatch, name):
    """The kernel over the stacked store against ``ssm_update`` on the
    layer's slice: y and the layer's new state to float32 rounding (the
    128-term sum in another order), every other layer's bytes as they were."""
    lm, rows, h, p, n, channels, layer = UPDATE_CASES[name]
    store, x, dt, a, bm, cm = _update_inputs(lm, rows, h, p, n)
    _hold_blocks_to(monkeypatch, channels, n)
    block = ssm._update_block(h * p, n)
    assert (h * p) % block == 0 and block <= (channels or h * p)
    if name == "budget_does_not_divide":
        assert block == 3 * 128
    y, new = ssm.ssm_update_stacked(store, jnp.asarray(layer), x, dt, a, bm,
                                    cm, interpret=True)
    want_y, want = ssm.ssm_update(store[layer].reshape(rows, h, p, n), x, dt,
                                  a, bm, cm)
    assert y.shape == (rows, h, p) and y.dtype == F32
    assert float(jnp.abs(y - want_y).max()) <= 1e-5 * float(
        jnp.abs(want_y).max())
    assert float(jnp.abs(new[layer].reshape(want.shape) - want).max()) \
        <= 1e-6 * float(jnp.abs(want).max())
    for other in range(lm):
        if other != layer:
            np.testing.assert_array_equal(np.asarray(new[other]),
                                          np.asarray(store[other]))


@pytest.mark.parametrize("name", ["toy_one_tile", "blocks_of_two_tiles"])
def test_update_kernel_with_dt_zero_leaves_a_row_bit_for_bit(monkeypatch,
                                                             name):
    lm, rows, h, p, n, channels, layer = UPDATE_CASES[name]
    store, x, dt, a, bm, cm = _update_inputs(lm, rows, h, p, n, seed=1)
    _hold_blocks_to(monkeypatch, channels, n)
    dt = dt.at[rows - 1].set(0.0)
    _, new = ssm.ssm_update_stacked(store, layer, x, dt, a, bm, cm,
                                    interpret=True)
    np.testing.assert_array_equal(np.asarray(new[layer, rows - 1]),
                                  np.asarray(store[layer, rows - 1]))
    assert float(jnp.abs(new[layer, 0] - store[layer, 0]).max()) > 0


@pytest.mark.parametrize("hp,n,budget,want", [
    (8192, 128, None, 8192),        # Granite: a row-layer (4 MiB) is a block
    (8192, 128, 2 ** 21, 1024),
    (16384 * 2, 16, None, 16384),   # at most a tile's lanes of tiles
    (768, 32, 4 * 512 * 32 * 4, 384),
    (128, 16, None, 128), (200, 16, None, None)])
def test_update_block_rule(monkeypatch, hp, n, budget, want):
    if budget is not None:
        monkeypatch.setattr(ssm, "_UPDATE_VMEM_BUDGET", budget)
    assert ssm._update_block(hp, n) == want


def test_shapes_the_kernel_does_not_tile_take_the_xla_form():
    """A row that is not whole 128-channel tiles, or a head size that is
    not whole sublanes: the XLA form, whatever is forced."""
    for h, p in ((5, 8), (32, 4)):
        store, x, dt, a, bm, cm = _update_inputs(2, 2, h, p, 16)
        y, new = ssm.ssm_update_stacked(store, 1, x, dt, a, bm, cm,
                                        use_pallas=True)
        want_y, want = ssm.ssm_update(store[1].reshape(2, h, p, 16), x, dt,
                                      a, bm, cm)
        np.testing.assert_array_equal(np.asarray(y), np.asarray(want_y))
        np.testing.assert_array_equal(np.asarray(new[1]),
                                      np.asarray(want.reshape(2, h * p, 16)))


@pytest.mark.parametrize("kinds", ["mmam", "mm"])
def test_typed_decode_step_with_the_kernel_forced(monkeypatch, kinds):
    """One typed ``decode_step`` at the toy widths, a prefill and then two
    one-token steps, with the update kernel forced (interpret) against the
    XLA path: logits and the whole state store."""
    cfg = typed_cfg(kinds)
    params = init_params(cfg, jax.random.PRNGKey(3))
    rows, t = 2, 16
    rng_tokens = np.random.default_rng(5).integers(
        0, 128, size=(rows, t + 2)).astype(np.int32)

    xla_or_kernel = ssm.ssm_update_stacked
    kernel_calls = []

    def forced_update(*args):
        kernel_calls.append(args[1])
        return xla_or_kernel(*args, interpret=True)

    def run(forced):
        if forced:
            monkeypatch.setattr(ssm, "ssm_update_stacked", forced_update)
        cache = dict(tr.init_paged_cache(cfg, 8, 16),
                     state=tr.init_row_state(cfg, rows),
                     pages=jnp.arange(rows * 2, dtype=jnp.int32).reshape(
                         rows, 2))
        prompt = jnp.asarray(rng_tokens[:, :t])
        _, cache = tr.decode_step(
            cfg, params, dict(cache, slots=jnp.arange(rows, dtype=jnp.int32),
                              valid=jnp.asarray([t, t - 5], jnp.int32)),
            prompt, 0)
        outs = []
        for i, pos in enumerate(([t, t - 5], [t + 1, t - 4])):
            cache = {k: cache[k] for k in ("k", "v", "pages", "state")}
            logits, cache = tr.decode_step(
                cfg, params, cache, jnp.asarray(rng_tokens[:, t + i:t + i + 1]),
                jnp.asarray(pos, jnp.int32))
            outs.append(logits)
        return jnp.stack(outs), cache["state"]["ssm"]

    want_logits, want_state = run(False)
    logits, state = run(True)
    assert kernel_calls and float(jnp.abs(state).max()) > 0
    assert float(jnp.abs(logits - want_logits).max()) <= 1e-5 * float(
        jnp.abs(want_logits).max())
    assert float(jnp.abs(state - want_state).max()) <= 1e-5 * float(
        jnp.abs(want_state).max())


# -- the grouped expert layer ----------------------------------------------------

def _experts(held=6, d=32, f=48, layers=None, seed=0):
    rng = np.random.default_rng(seed)
    lead = () if layers is None else (layers,)
    mk = lambda *s: jnp.asarray(rng.normal(size=lead + s) / np.sqrt(s[-2]),
                                F32)
    return mk(held, d, f), mk(held, d, f), mk(held, f, d)


@pytest.mark.parametrize("tile,tokens", [(16, 20), (32, 50), (16, 3)])
def test_grouped_kernels_interpret_match_the_plain_layout(tile, tokens):
    """The Pallas kernels (interpret mode) against the einsum over tiles,
    on the same sorted layout; rows of dead tiles are undefined in both and
    left out."""
    held, e, k = 6, 10, 3
    wg, wu, wd = _experts(held, layers=3)
    rng = np.random.default_rng(tokens)
    h = jnp.asarray(rng.normal(size=(tokens, 32)), F32)
    top = jnp.asarray(np.stack([rng.permutation(e)[:k]
                                for _ in range(tokens)]), jnp.int32)
    lay = moe.grouped_layout(top, held, jnp.asarray(2, jnp.int32), tile)
    live = int(lay["live_tiles"][0]) * tile
    xs = h[lay["row_token"]]
    layer = jnp.asarray(1, jnp.int32)
    kw = dict(tile=tile, layer=layer)
    a = moe.grouped_swiglu(xs, wg, wu, lay["tile_expert"], lay["live_tiles"],
                           interpret=True, **kw)
    b = moe.grouped_swiglu(xs, wg, wu, lay["tile_expert"], lay["live_tiles"],
                           use_pallas=False, **kw)
    np.testing.assert_allclose(np.asarray(a[:live]), np.asarray(b[:live]),
                               rtol=1e-5, atol=1e-6)
    c = moe.grouped_matmul(b, wd, lay["tile_expert"], lay["live_tiles"],
                           interpret=True, **kw)
    d = moe.grouped_matmul(b, wd, lay["tile_expert"], lay["live_tiles"],
                           use_pallas=False, **kw)
    np.testing.assert_allclose(np.asarray(c[:live]), np.asarray(d[:live]),
                               rtol=1e-5, atol=1e-6)
    # the layout: every held assignment has a row of its own in a tile of
    # its expert, the counts are the assignments per held expert
    dest, valid = np.asarray(lay["dest"]), np.asarray(lay["valid"])
    local = np.asarray(top).reshape(-1) - 2
    assert valid.sum() == ((local >= 0) & (local < held)).sum()
    assert len(set(dest[valid])) == valid.sum() and dest[valid].max() < live
    te = np.asarray(lay["tile_expert"])
    assert (te[dest[valid] // tile] == local[valid]).all()
    assert (np.asarray(lay["counts"]) == np.bincount(local[valid],
                                                     minlength=held)).all()


@pytest.mark.parametrize("offset,held", [(0, 8), (0, 4), (4, 4), (2, 3)])
def test_grouped_experts_are_the_held_part_of_the_every_expert_form(
        offset, held):
    """Against ``_moe`` (every expert runs every token, a mask zeroes the
    rest) with the weights of the experts held elsewhere set to 0: the same
    routing, the same gates, the held experts' sum (float32, 1e-5)."""
    cfg = typed_cfg("a", experts_held=None, n_experts=8, moe_impl="dense",
                    layer_types=None, tie_embeddings=False, rope=True,
                    attn_scale=None, embed_scale=None, residual_scale=None,
                    logits_scale=None, shared_d_ff=None, mamba_heads=0)
    rng = np.random.default_rng(offset + held)
    wg, wu, wd = _experts(8, d=64, f=32, seed=3)
    router = jnp.asarray(rng.normal(size=(64, 8)) / 8, F32)
    h = jnp.asarray(rng.normal(size=(2, 11, 64)), F32)
    keep = jnp.zeros((8, 1, 1)).at[offset:offset + held].set(1.0)
    want, _ = tr._moe(cfg, {"router": router, "e_gate": wg, "e_up": wu,
                            "e_down": wd * keep}, h)
    gcfg = typed_cfg("a", experts_held=held, expert_offset=offset)
    sl = slice(offset, offset + held)
    got, aux = tr._moe_grouped(gcfg, {"router": router, "e_gate": wg[sl],
                                      "e_up": wu[sl], "e_down": wd[sl]}, h)
    assert float(jnp.abs(got - want).max()) <= 1e-5 * float(
        jnp.abs(want).max())
    logits = h.reshape(-1, 64) @ router
    _, top = jax.lax.top_k(logits, 3)
    want_counts = np.bincount(np.asarray(top).reshape(-1), minlength=8)[sl]
    assert (np.asarray(aux["expert_counts"]) == want_counts).all()


def test_grouped_experts_under_a_manual_ep_axis_sum_to_the_whole():
    """Two shards of four experts under ``shard_map`` (tokens alike on
    both, expert weights sharded, one psum): the uncut layer's result."""
    devs = jax.devices()[:2]
    if len(devs) < 2:
        pytest.skip("needs two devices")
    mesh = Mesh(np.array(devs), ("ep",))
    cfg = typed_cfg("a", experts_held=4)
    whole = typed_cfg("a", experts_held=8)
    wg, wu, wd = _experts(8, d=64, f=32, seed=5)
    rng = np.random.default_rng(1)
    router = jnp.asarray(rng.normal(size=(64, 8)) / 8, F32)
    h = jnp.asarray(rng.normal(size=(1, 9, 64)), F32)
    want, _ = tr._moe_grouped(whole, {"router": router, "e_gate": wg,
                                      "e_up": wu, "e_down": wd}, h)

    def local(router, wg, wu, wd, h):
        out, _ = tr._moe_grouped(cfg, {"router": router, "e_gate": wg,
                                       "e_up": wu, "e_down": wd}, h,
                                 ep_axis="ep")
        return out

    got = shard_map(local, mesh=mesh,
                    in_specs=(P(), P("ep"), P("ep"), P("ep"), P()),
                    out_specs=P(), check_vma=False)(router, wg, wu, wd, h)
    assert float(jnp.abs(got - want).max()) <= 1e-5 * float(
        jnp.abs(want).max())


# -- the configuration ---------------------------------------------------------

def test_layer_pattern_periods_and_runs():
    cfg = typed_cfg("mmammmmamm")
    assert cfg.layer_period == 5 and cfg.n_attn_layers == 2
    assert cfg.layer_runs == (("mamba", 0, 2, 0), ("attention", 2, 1, 0),
                              ("mamba", 3, 2, 2))
    assert typed_cfg("mam").layer_period == 3
    assert typed_cfg("aaaa").layer_runs == (("attention", 0, 1, 0),)
    with pytest.raises(ValueError, match="layer_types"):
        typed_cfg("mam", n_layers=4)
    with pytest.raises(ValueError, match="layer_types"):
        typed_cfg("ma", layer_types=("mamba", "window"))
    with pytest.raises(ValueError, match="grouped"):
        typed_cfg("ma", moe_impl="dense")
    with pytest.raises(ValueError, match="experts"):
        typed_cfg("ma", expert_offset=6)


def test_a_typed_stacks_parameters_cache_and_specs_read_the_pattern():
    cfg = typed_cfg("mmam")
    params = init_params(cfg, jax.random.PRNGKey(0))
    lay = params["layers"]
    assert "head" not in params and "wq" not in lay
    assert lay["attention"]["wq"].shape == (1, 64, 64)
    assert lay["mamba"]["in_proj"].shape == (3, 64, 2 * 128 + 2 * 16 + 8)
    assert lay["e_gate"].shape == (4, 4, 64, 32)
    assert lay["router"].shape == (4, 64, 8)
    assert lay["s_gate"].shape == (4, 64, 48)
    pool = tr.init_paged_cache(cfg, 10, 16)
    assert pool["k"].shape == (1, 10, 2, 16, 16)
    state = tr.init_row_state(cfg, 5)
    assert state["ssm"].shape == (3, 5, 128, 16)
    assert state["ssm"].dtype == jnp.float32         # never the compute dtype
    assert state["conv"].shape == (3, 5, 3, 128 + 32)
    q = tr.quantize_params(cfg, params)
    from tfmesos_tpu.ops.quant import QTensor
    assert isinstance(q["layers"]["mamba"]["in_proj"], QTensor)
    assert isinstance(q["layers"]["attention"]["wo"], QTensor)
    assert isinstance(q["layers"]["e_down"], QTensor) and "head" not in q
    assert not isinstance(q["layers"]["mamba"]["A_log"], QTensor)
    mesh = Mesh(np.array(jax.devices()[:1]), ("fsdp",))
    specs = tr.partition_specs(cfg, mesh)
    assert jax.tree_util.tree_structure(
        jax.tree_util.tree_map(lambda s: 0, specs,
                               is_leaf=lambda s: isinstance(s, P))) == \
        jax.tree_util.tree_structure(jax.tree_util.tree_map(lambda a: 0,
                                                            params))
    with pytest.raises(NotImplementedError, match="serving"):
        tr.forward(cfg, params, jnp.zeros((1, 8), jnp.int32))
    with pytest.raises(ValueError, match="linear"):
        tr.init_cache(cfg, 1, 32)


def test_a_configuration_that_states_no_pattern_has_the_old_parameters():
    """The fields a typed stack adds default to off: the homogeneous
    stack's parameter tree, pool and refusals are what they were."""
    cfg = TransformerConfig(vocab_size=64, d_model=32, n_layers=2, n_heads=4,
                            d_ff=64, max_seq_len=64)
    params = init_params(cfg, jax.random.PRNGKey(0))
    assert set(params) == {"embed", "layers", "norm_f", "head"}
    assert set(params["layers"]) == {"attn_norm", "mlp_norm", "wq", "wk",
                                     "wv", "wo", "w_gate", "w_up", "w_down"}
    assert tr.init_paged_cache(cfg, 4, 16)["k"].shape == (2, 4, 4, 16, 8)
    assert cfg.n_mamba_layers == 0 and cfg.layer_period == 1
    assert cfg.held_experts == 0 and cfg.shared_width == 0


# -- ContinuousBatcher over a typed stack ----------------------------------------

@pytest.fixture(scope="module")
def served():
    cfg = typed_cfg("mmam")
    params = init_params(cfg, jax.random.PRNGKey(1))
    rng = np.random.default_rng(0)
    reqs = [(rng.integers(0, 128, n).astype(np.int32), m)
            for n, m in ((21, 9), (40, 5), (7, 12), (64, 6), (33, 8),
                         (16, 4), (50, 7))]

    def run(**kw):
        b = ContinuousBatcher(cfg, params, rows=2, max_len=128, page_size=16,
                              prefill_bucket=16, **kw)
        out = {}
        for c in b.run(Request(prompt=p, max_new_tokens=m) for p, m in reqs):
            out[len(out)] = (tuple(c.request.prompt.tolist()),
                             tuple(int(t) for t in c.tokens))
        return b, sorted(out.values())

    return cfg, params, run


def test_batcher_streams_do_not_depend_on_lag_or_block_mode(served):
    """Seven requests through two row slots (every slot is reused): the
    sync loop, the pipelined carry and K = 4 blocks give the same tokens;
    the state store rides the donated pool through all of them."""
    _, _, run = served
    b, sync = run()
    assert b._recurrent and b._moe_counts and "state" in b.pool
    assert all(len(toks) for _, toks in sync)
    b, piped = run(pipeline_depth=1)
    assert b.pipeline_bypass_reason is None and b._pipelined
    assert piped == sync
    _, blocks = run(multi_step=4)
    assert blocks == sync


def test_the_lag_policy_left_to_the_batcher(served):
    """``pipeline_depth=None`` (what ``fleet/replica.py`` passes when the
    flag is not given): one block of lag where rows keep a recurrent state
    (which has closed suspend, the one surface the lagged carry closes),
    the synchronous loop for every other configuration; an explicit 0 or 1
    is kept; the streams are the synchronous loop's either way, the ring
    says which loop ran, and ``warmup()`` compiles what that loop runs."""
    from tfmesos_tpu import serving
    from tfmesos_tpu.fleet.replica import build_parser
    assert build_parser().parse_args([]).pipeline_depth is None
    assert build_parser().parse_args(
        ["--pipeline-depth", "0"]).pipeline_depth == 0
    cfg, params, run = served
    _, sync = run(pipeline_depth=0)
    b, auto = run(pipeline_depth=None)
    assert b.pipeline_depth == 1 and b._pipelined
    assert b.suspend_bypass_reason == "recurrent row state"
    assert auto == sync
    modes = {r["mode"] for r in flight(TICK_COMPONENT).snapshot()
             if r.get("batcher") == b.flight.value
             and r["name"] == "decode.block"}
    assert modes == {"pipelined"}
    b = ContinuousBatcher(cfg, params, rows=2, max_len=64, page_size=16,
                          prefill_bucket=16, pipeline_depth=None)
    b.warmup()
    before = serving._COMPILES[0]
    rng = np.random.default_rng(5)
    list(b.run(Request(prompt=rng.integers(0, 128, n).astype(np.int32),
                       max_new_tokens=6) for n in (5, 30, 17, 47, 9)))
    assert serving._COMPILES[0] == before
    dense = TransformerConfig(vocab_size=128, d_model=32, n_layers=2,
                              n_heads=2, n_kv_heads=1, d_ff=64,
                              max_seq_len=128, dtype=jnp.float32,
                              param_dtype=jnp.float32)
    d = ContinuousBatcher(dense, init_params(dense, jax.random.PRNGKey(2)),
                          rows=2, max_len=64, page_size=16,
                          pipeline_depth=None)
    assert d.pipeline_depth == 0 and not d._pipelined
    with pytest.raises(ValueError, match="pipeline_depth"):
        ContinuousBatcher(cfg, params, rows=2, max_len=64, page_size=16,
                          pipeline_depth=2)


def test_batcher_ring_carries_the_state_and_expert_fields(served):
    cfg, _, run = served
    b, _ = run()
    recs = [r for r in flight(TICK_COMPONENT).snapshot()
            if r.get("batcher") == b.flight.value and "state_rows" in r]
    blocks = [r for r in recs if r["name"] == "decode.block"]
    assert blocks and max(r["state_rows"] for r in recs) == 2
    for r in blocks:
        # 2 rows x top-3 x 4 layers at most fall on the 4 held experts
        assert 0 <= r["moe_assignments"] <= 2 * 3 * 4 * r["k"]
        assert r["moe_expert_max"] <= 2 * r["k"]
        assert r["moe_experts_touched"] <= min(r["moe_assignments"],
                                               4 * 4 * r["k"])
    assert sum(r["moe_assignments"] for r in blocks) > 0


def test_batcher_refuses_and_bypasses_what_a_row_state_closes(served):
    cfg, params, _ = served
    kw = dict(rows=2, max_len=128, page_size=16, prefill_bucket=16)
    for bad, match in ((dict(prefill_chunk=16), "prefill_chunk"),
                       (dict(quantized_cache=True), "quantized_cache"),
                       (dict(draft_cfg=cfg, draft_params=params),
                        "speculative")):
        with pytest.raises(ValueError, match=match):
            ContinuousBatcher(cfg, params, **kw, **bad)
    b = ContinuousBatcher(cfg, params, prefix_cache_pages=4, **kw)
    assert b.prefix_cache_bypass_reason == "recurrent row state"
    assert not b.prefix_cache_active and not b.preemptible
    assert b.suspend_bypass_reason == "recurrent row state"
    with pytest.raises(ValueError, match="recurrent row state"):
        b.export_kv(Request(prompt=np.arange(5, dtype=np.int32),
                            max_new_tokens=2))
    reasons = compute_bypass_reasons(recurrent=True, pipeline_depth=1)
    assert reasons["pipeline"] is None
    assert {reasons[k] for k in ("prefix_cache", "kv_tier", "suspend",
                                 "speculative", "kv_export")} == {
        "recurrent row state"}


def test_warmup_compiles_what_a_typed_stack_serves(served):
    cfg, params, _ = served
    from tfmesos_tpu import serving
    b = ContinuousBatcher(cfg, params, rows=2, max_len=64, page_size=16,
                          prefill_bucket=16)
    done = b.warmup()["compiled"]
    assert [c for c in done if c.startswith("prefill")] == [
        f"prefill[{w}]" for w in (16, 32, 48, 64)]
    assert any(c.startswith("decode") for c in done)
    assert not any(c.startswith("kv_export") for c in done)
    before = serving._COMPILES[0]
    rng = np.random.default_rng(3)
    list(b.run(Request(prompt=rng.integers(0, 128, n).astype(np.int32),
                       max_new_tokens=5) for n in (5, 30, 17, 47)))
    assert serving._COMPILES[0] == before
