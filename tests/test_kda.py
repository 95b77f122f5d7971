"""KDA layers (a gated delta rule with a decay per key channel) in a typed
stack, the attention output gate and the sigmoid router, program side: the
chunkwise form against the token-by-token recurrence, the stacked in-place
update against the specification (the XLA form, and the Pallas kernel in
interpret mode), the router's gate forms against hand
arithmetic, and ``ContinuousBatcher`` over a stack with KDA layers (what it
refuses and bypasses, the lag and block modes token for token, the tick
ring's fields, the named scopes).  The float32 reference of the whole model
is the benchmark's (tests/benchmark_tests/test_benchmark_solar_kda.py).

Tolerances.  Everything here is float32 on the CPU and differs in the order
of its sums only.  The chunkwise form solves a triangular system a chunk where
the recurrence applies one reflection-like factor ``I - beta k k^T`` a token:
with ``beta`` near 2 and a decay near 1 that factor does not contract, so
rounding is carried, not damped: measured 2e-7 .. 2e-5 of the largest output
over the cases below (the weak-decay, ``beta`` ~ 2 corner is the 2e-5);
``RTOL`` 1e-4 leaves a factor of five, and a wrong term (padding that reached
the state, a decay applied once too often) moves an output by 1e-2 or more.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tfmesos_tpu.models import transformer as tr
from tfmesos_tpu.models.transformer import TransformerConfig, init_params
from tfmesos_tpu.ops import kda, moe
from tfmesos_tpu.serving import (TICK_COMPONENT, ContinuousBatcher, Request,
                                 compute_bypass_reasons, flight)

F32 = jnp.float32
RTOL = 1e-4


def kda_cfg(kinds="akkk", **kw):
    names = {"k": "kda", "a": "attention", "m": "mamba"}
    base = dict(
        vocab_size=128, d_model=48, n_layers=len(kinds), n_heads=4,
        n_kv_heads=2, attn_head_dim=16, d_ff=32, max_seq_len=256,
        dtype=F32, param_dtype=F32,
        layer_types=tuple(names[c] for c in kinds), kda_heads=4,
        kda_head_dim=16, kda_chunk=16, kda_neg_eigval=True, rope=False,
        attn_gate=True, norm_eps=1e-5, n_experts=16, top_k=3,
        moe_impl="grouped", experts_held=4, expert_offset=4, shared_d_ff=32,
        router_score="sigmoid")
    base.update(kw)
    return TransformerConfig(**base)


# -- the forms of the recurrence ----------------------------------------------

def _inputs(t, glo, ghi, blo, bhi, pad=0, seed=0):
    """q, k normalised as the mixer hands them over; the per-step log-decay
    log-uniform in -[glo, ghi], beta uniform in [blo, bhi]; the last ``pad``
    positions are bucket padding (g = 0, beta = 0)."""
    b, h, dk, dv = 2, 3, 16, 8
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = kda.l2norm(jax.random.normal(ks[0], (b, t, h, dk))) * dk ** -0.5
    k = kda.l2norm(jax.random.normal(ks[1], (b, t, h, dk)))
    v = jax.random.normal(ks[2], (b, t, h, dv))
    g = -jnp.exp(jax.random.uniform(ks[3], (b, t, h, dk), minval=np.log(glo),
                                    maxval=np.log(ghi)))
    beta = jax.random.uniform(ks[4], (b, t, h), minval=blo, maxval=bhi)
    s0 = jax.random.normal(ks[5], (b, h, dk, dv))
    if pad:
        live = (jnp.arange(t) < t - pad)[None, :, None]
        g = jnp.where(live[..., None], g, 0.0)
        beta = jnp.where(live, beta, 0.0)
    return q, k, v, g, beta, s0


def _stepwise(q, k, v, g, beta, s0):
    def step(s, inp):
        o, s = kda.kda_update(s, *inp)
        return s, o
    mv = lambda a: jnp.moveaxis(a, 1, 0)
    s, o = jax.lax.scan(step, s0, tuple(map(mv, (q, k, v, g, beta))))
    return jnp.moveaxis(o, 0, 1), s


@pytest.mark.parametrize("t,chunk,pad", [
    (64, 16, 0),        # whole chunks
    (50, 16, 0),        # ends inside a chunk: padded up inside the scan
    (37, 64, 0),        # narrower than one chunk
    (48, 16, 13),       # bucket padding at the end (g = 0, beta = 0)
    (1, 16, 0),         # one token
])
@pytest.mark.parametrize("decay,step", [
    ((1e-3, 0.5), (0.0, 2.0)),      # the cell's range
    ((1e-3, 1e-2), (1.9, 2.0)),     # hardly any decay, beta near 2
    ((3.0, 8.0), (0.0, 0.1)),       # a decay no exp(-G) survives, beta near 0
])
def test_chunk_scan_is_the_recurrence(t, chunk, pad, decay, step):
    q, k, v, g, beta, s0 = _inputs(t, *decay, *step, pad=pad, seed=t)
    want_o, want_s = _stepwise(q, k, v, g, beta, s0)
    o, s = kda.kda_chunk_scan(q, k, v, g, beta, s0, chunk)
    assert o.shape == want_o.shape and o.dtype == F32 and s.dtype == F32
    n = t - pad
    scale = float(jnp.abs(want_o[:, :n]).max())
    assert float(jnp.abs(o - want_o)[:, :n].max()) <= RTOL * scale
    assert float(jnp.abs(s - want_s).max()) <= RTOL * float(
        jnp.abs(want_s).max())
    assert bool(jnp.isfinite(o).all()) and bool(jnp.isfinite(s).all())


def test_padding_leaves_the_state_alone():
    """The state after 35 real positions and 13 of padding is the state
    after the 35 (the padding's k, v and q are whatever they are)."""
    q, k, v, g, beta, s0 = _inputs(48, 0.05, 0.5, 0.2, 1.8, pad=13, seed=3)
    _, want = kda.kda_chunk_scan(*(a[:, :35] for a in (q, k, v, g, beta)),
                                 s0, 16)
    _, got = kda.kda_chunk_scan(q, k, v, g, beta, s0, 16)
    assert float(jnp.abs(got - want).max()) <= 1e-6 * float(
        jnp.abs(want).max())


@pytest.mark.parametrize("layer", [0, 1, 2])
def test_stacked_update_is_the_specification_in_place(layer):
    """``kda_update_stacked`` on one layer of a three-layer store against
    ``kda_update`` on that layer's state; the other layers' bytes are
    unchanged."""
    rows, h, dk, dv = 4, 3, 16, 8
    ks = jax.random.split(jax.random.PRNGKey(layer), 7)
    store = jax.random.normal(ks[0], (3, rows, h * dk, dv))
    q = kda.l2norm(jax.random.normal(ks[1], (rows, h, dk))) * dk ** -0.5
    k = kda.l2norm(jax.random.normal(ks[2], (rows, h, dk)))
    v = jax.random.normal(ks[3], (rows, h, dv))
    g = -jax.random.uniform(ks[4], (rows, h, dk))
    beta = 2 * jax.random.uniform(ks[5], (rows, h))
    o, new = jax.jit(kda.kda_update_stacked)(store, layer, q, k, v, g, beta)
    want_o, want = kda.kda_update(store[layer].reshape(rows, h, dk, dv), q,
                                  k, v, g, beta)
    assert o.shape == (rows, h, dv) and o.dtype == F32
    assert float(jnp.abs(o - want_o).max()) <= 1e-5 * float(
        jnp.abs(want_o).max())
    assert float(jnp.abs(new[layer].reshape(want.shape) - want).max()) \
        <= 1e-5 * float(jnp.abs(want).max())
    for other in set(range(3)) - {layer}:
        np.testing.assert_array_equal(np.asarray(new[other]),
                                      np.asarray(store[other]))


def test_the_update_reads_the_state_before_it_writes_it():
    """One step by hand (numpy, float64): S' = Diag(alpha) S; u = v - S'^T k;
    S = S' + beta k u^T; o = S^T q."""
    rng = np.random.default_rng(0)
    dk, dv = 6, 5
    s = rng.normal(size=(dk, dv))
    q, k, v = rng.normal(size=dk), rng.normal(size=dk), rng.normal(size=dv)
    g, beta = -rng.uniform(size=dk), 1.7
    sp = np.exp(g)[:, None] * s
    u = v - sp.T @ k
    want = sp + beta * np.outer(k, u)
    o, new = kda.kda_update(*(jnp.asarray(a, F32)[None, None] for a in
                              (s, q, k, v, g)), jnp.full((1, 1), beta, F32))
    np.testing.assert_allclose(np.asarray(new[0, 0]), want, rtol=1e-5,
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(o[0, 0]), want.T @ q, rtol=1e-5,
                               atol=1e-6)
    # the same thing as the issue writes it
    full = (np.eye(dk) - beta * np.outer(k, k)) @ (np.exp(g)[:, None] * s) \
        + beta * np.outer(k, v)
    np.testing.assert_allclose(want, full, rtol=1e-12)


# -- the decode step's one-pass update (the Pallas kernel, interpret mode) -----
#
# name -> (kda layers, rows, heads, key size, value size, channels a block may
# hold or None for what the budget gives, the layer updated).  ``kda_update``
# on the layer's slice is the specification.
UPDATE_CASES = {
    "heads_inside_one_tile": (3, 2, 8, 16, 128, None, 1),
    "a_head_a_tile_fewer_than_lanes": (3, 3, 3, 128, 128, None, 2),
    "blocks_of_two_heads": (2, 2, 4, 128, 128, 256, 1),
    # six heads of half a tile, room for two tiles: the rule takes one
    # (two do not divide three)
    "budget_does_not_divide": (2, 2, 6, 64, 128, 256, 0),
    "head_wider_than_a_tile": (2, 1, 2, 256, 128, None, 1),
    "value_of_two_lane_tiles": (2, 2, 4, 32, 256, None, 0),
}


def _update_inputs(lk, rows, h, dk, dv, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    store = jax.random.normal(ks[0], (lk, rows, h * dk, dv))
    q = kda.l2norm(jax.random.normal(ks[1], (rows, h, dk))) * dk ** -0.5
    k = kda.l2norm(jax.random.normal(ks[2], (rows, h, dk)))
    v = jax.random.normal(ks[3], (rows, h, dv))
    g = -jax.random.uniform(ks[4], (rows, h, dk))
    beta = 2 * jax.random.uniform(ks[5], (rows, h))
    return store, q, k, v, g, beta


def _hold_blocks_to(monkeypatch, channels, dv):
    if channels is not None:
        monkeypatch.setattr(kda, "_UPDATE_VMEM_BUDGET", 4 * channels * dv * 4)


@pytest.mark.parametrize("name", sorted(UPDATE_CASES))
def test_update_kernel_is_the_specification_in_place(monkeypatch, name):
    """The kernel over the stacked store against ``kda_update`` on the
    layer's slice: o and the layer's new state to float32 rounding (the
    sums over the key channels in another order), every other layer's bytes
    as they were."""
    lk, rows, h, dk, dv, channels, layer = UPDATE_CASES[name]
    store, q, k, v, g, beta = _update_inputs(lk, rows, h, dk, dv)
    _hold_blocks_to(monkeypatch, channels, dv)
    block = kda._update_block(h, dk, dv)
    assert (h * dk) % block == 0 and block % dk == 0 and block % 128 == 0
    assert block <= (channels or h * dk)
    if channels is not None:
        assert block < h * dk       # more than one block a row
    if name == "budget_does_not_divide":
        assert block == 128
    o, new = kda.kda_update_stacked(store, jnp.asarray(layer), q, k, v, g,
                                    beta, interpret=True)
    want_o, want = kda.kda_update(store[layer].reshape(rows, h, dk, dv), q,
                                  k, v, g, beta)
    assert o.shape == (rows, h, dv) and o.dtype == F32
    assert float(jnp.abs(o - want_o).max()) <= 1e-5 * float(
        jnp.abs(want_o).max())
    assert float(jnp.abs(new[layer].reshape(want.shape) - want).max()) \
        <= 1e-6 * float(jnp.abs(want).max())
    for other in set(range(lk)) - {layer}:
        np.testing.assert_array_equal(np.asarray(new[other]),
                                      np.asarray(store[other]))


@pytest.mark.parametrize("h,dk,channels,group_tiles,group", [
    (16, 128, None, 8, 8),      # a whole row, two iterations of eight heads
    (8, 128, 512, 2, 2),        # two blocks a row, two iterations a block
    (32, 16, None, 2, 16),      # eight heads a tile: two tiles an iteration
    (4, 256, None, 2, 1),       # a head of two tiles is an iteration
    (6, 128, None, 4, 3)])      # four tiles do not divide six: three
def test_update_kernel_loops_over_groups_of_heads(monkeypatch, h, dk,
                                                  channels, group_tiles,
                                                  group):
    """More heads a block than one loop iteration unrolls: the iterations
    roll their own tiles' columns into place and index the state, v, o and
    ``beta k . q`` from the iteration's number."""
    monkeypatch.setattr(kda, "_GROUP_TILES", group_tiles)
    _hold_blocks_to(monkeypatch, channels, 128)
    heads = kda._update_block(h, dk, 128) // dk
    assert kda._update_group(heads, dk) == group and heads > group
    store, q, k, v, g, beta = _update_inputs(2, 2, h, dk, 128, seed=h)
    o, new = kda.kda_update_stacked(store, jnp.asarray(1), q, k, v, g, beta,
                                    interpret=True)
    want_o, want = kda.kda_update(store[1].reshape(2, h, dk, 128), q, k, v,
                                  g, beta)
    assert float(jnp.abs(o - want_o).max()) <= 1e-5 * float(
        jnp.abs(want_o).max())
    assert float(jnp.abs(new[1].reshape(want.shape) - want).max()) \
        <= 1e-6 * float(jnp.abs(want).max())
    np.testing.assert_array_equal(np.asarray(new[0]), np.asarray(store[0]))


@pytest.mark.parametrize("name", ["heads_inside_one_tile",
                                  "blocks_of_two_heads"])
def test_update_kernel_leaves_a_padding_row_bit_for_bit(monkeypatch, name):
    """A row with ``g = 0`` and ``beta = 0`` (bucket padding, an idle slot)
    comes back as it was, to the bit; the other rows move."""
    lk, rows, h, dk, dv, channels, layer = UPDATE_CASES[name]
    store, q, k, v, g, beta = _update_inputs(lk, rows, h, dk, dv, seed=1)
    _hold_blocks_to(monkeypatch, channels, dv)
    g, beta = g.at[rows - 1].set(0.0), beta.at[rows - 1].set(0.0)
    _, new = kda.kda_update_stacked(store, layer, q, k, v, g, beta,
                                    interpret=True)
    np.testing.assert_array_equal(np.asarray(new[layer, rows - 1]),
                                  np.asarray(store[layer, rows - 1]))
    assert float(jnp.abs(new[layer, 0] - store[layer, 0]).max()) > 0


@pytest.mark.parametrize("h,dk,dv,budget,want", [
    (64, 128, 128, None, 8192),     # the cell: a row-layer (4 MiB) is a block
    (64, 128, 128, 2 ** 21, 1024),
    (256, 128, 128, None, 8192),
    (256, 128, 128, 2 ** 26, 16384),    # at most a tile's lanes of tiles
    (6, 64, 128, 4 * 256 * 128 * 4, 128),
    (3, 256, 128, None, 768),       # whole heads of two tiles each
    (3, 256, 128, 4 * 512 * 128 * 4, 256),
    (8, 16, 128, None, 128),
    (5, 16, 128, None, None),       # a row that is not whole tiles
    (32, 4, 128, None, None),       # a key size that is not whole sublanes
    (8, 16, 64, None, None),        # a value size that is not whole lanes
    (3, 64, 128, None, None),       # whole tiles would split a head
    (8, 128, 128, 4 * 64 * 128 * 4, None)])     # one head is over the budget
def test_update_block_rule(monkeypatch, h, dk, dv, budget, want):
    if budget is not None:
        monkeypatch.setattr(kda, "_UPDATE_VMEM_BUDGET", budget)
    assert kda._update_block(h, dk, dv) == want


@pytest.mark.parametrize("h,dk,dv", [(5, 16, 128), (32, 4, 128),
                                     (8, 16, 64)])
def test_shapes_the_kernel_does_not_tile_take_the_xla_form(h, dk, dv):
    """A row that is not whole 128-channel tiles, a key size that is not
    whole sublanes, a value size that is not whole lanes: the XLA form,
    whatever is forced (the kernel would not lower, and nothing raises)."""
    store, q, k, v, g, beta = _update_inputs(2, 2, h, dk, dv)
    o, new = kda.kda_update_stacked(store, 1, q, k, v, g, beta,
                                    use_pallas=True)
    want_o, want = kda.kda_update_stacked(store, 1, q, k, v, g, beta,
                                          use_pallas=False)
    np.testing.assert_array_equal(np.asarray(o), np.asarray(want_o))
    np.testing.assert_array_equal(np.asarray(new), np.asarray(want))
    text = jax.jit(lambda st: kda.kda_update_stacked(
        st, 1, q, k, v, g, beta, use_pallas=True)).lower(store).as_text()
    assert "kda_update" not in text


@pytest.mark.parametrize("kinds", ["akkk", "kk"])
def test_typed_decode_step_with_the_kernel_forced(monkeypatch, kinds):
    """One typed ``decode_step`` at toy widths the kernel tiles (8 heads of
    128: a mixer's keys and values have one size), a prefill and then two
    one-token steps, with the update kernel forced (interpret) against the
    XLA form: logits and the whole state store."""
    cfg = kda_cfg(kinds, kda_heads=8, kda_head_dim=128, d_model=64)
    assert kda._update_block(8, 128, 128) == 1024
    params = init_params(cfg, jax.random.PRNGKey(3))
    rows, t = 2, 16
    rng_tokens = np.random.default_rng(5).integers(
        0, 128, size=(rows, t + 2)).astype(np.int32)

    xla_or_kernel = kda.kda_update_stacked
    kernel_calls = []

    def forced_update(*args):
        kernel_calls.append(args[1])
        return xla_or_kernel(*args, interpret=True)

    def run(forced):
        if forced:
            monkeypatch.setattr(kda, "kda_update_stacked", forced_update)
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
                cfg, params, cache,
                jnp.asarray(rng_tokens[:, t + i:t + i + 1]),
                jnp.asarray(pos, jnp.int32))
            outs.append(logits)
        return jnp.stack(outs), cache["state"]["kda_s"]

    want_logits, want_state = run(False)
    logits, state = run(True)
    assert kernel_calls and float(jnp.abs(state).max()) > 0
    assert float(jnp.abs(logits - want_logits).max()) <= 1e-5 * float(
        jnp.abs(want_logits).max())
    assert float(jnp.abs(state - want_state).max()) <= 1e-5 * float(
        jnp.abs(want_state).max())


# -- the router's gate forms --------------------------------------------------

def test_sigmoid_router_against_hand_arithmetic():
    """Selection by ``s + b``, gates from ``s`` alone, renormalised, times
    the scale; the bias moves a choice without entering a gate."""
    logits = jnp.asarray([[2.0, 1.0, 0.0, -1.0, 0.5],
                          [0.0, 0.1, 0.2, 0.3, 0.4]], F32)
    bias = jnp.asarray([0.0, 0.0, 0.5, 0.0, 0.0], F32)
    gates, idx = moe.route(logits, 2, "sigmoid", bias, 2.5)
    s = 1 / (1 + np.exp(-np.asarray(logits, np.float64)))
    # row 0: s + b = [.881, .731, 1.0, .269, .622]: experts 2 and 0 (without
    # the bias: 0 and 1)
    assert sorted(np.asarray(idx[0]).tolist()) == [0, 2]
    assert sorted(np.asarray(moe.route(logits, 2, "sigmoid", None)[1][0]
                             ).tolist()) == [0, 1]
    for row in range(2):
        chosen = np.asarray(idx[row])
        want = s[row, chosen] / s[row, chosen].sum() * 2.5
        np.testing.assert_allclose(np.asarray(gates[row]), want, rtol=1e-6)
    # the softmax form is what it was
    g2, i2 = moe.route(logits, 2)
    top = np.sort(np.asarray(logits), axis=-1)[:, ::-1][:, :2]
    np.testing.assert_allclose(
        np.asarray(g2), np.exp(top) / np.exp(top).sum(-1, keepdims=True),
        rtol=1e-6)
    assert np.asarray(i2[0]).tolist() == [0, 1]


def test_grouped_experts_under_sigmoid_scores_match_every_expert_then_mask():
    rng = np.random.default_rng(1)
    t, d, f, e, held, off, k = 24, 32, 16, 12, 4, 4, 3
    h = jnp.asarray(rng.normal(size=(t, d)), F32)
    logits = jnp.asarray(rng.normal(size=(t, e)), F32)
    bias = jnp.asarray(0.3 * rng.normal(size=(e,)), F32)
    wg, wu = (jnp.asarray(rng.normal(size=(held, d, f)) / np.sqrt(d), F32)
              for _ in range(2))
    wd = jnp.asarray(rng.normal(size=(held, f, d)) / np.sqrt(f), F32)
    out, counts = moe.grouped_experts(h, logits, wg, wu, wd, off, top_k=k,
                                      held=held, score="sigmoid", bias=bias,
                                      scale=1.0)
    gates, idx = moe.route(logits, k, "sigmoid", bias)
    want = np.zeros((t, d))
    for j in range(held):
        w = np.asarray(jnp.sum(jnp.where(idx == j + off, gates, 0.0), -1))
        y = (jax.nn.silu(h @ wg[j]) * (h @ wu[j])) @ wd[j]
        want += w[:, None] * np.asarray(y)
    assert np.abs(np.asarray(out) - want).max() <= 1e-5 * np.abs(want).max()
    np.testing.assert_array_equal(
        np.asarray(counts),
        np.bincount(np.asarray(idx).reshape(-1), minlength=e)[off:off + held])


# -- the configuration and its leaves -----------------------------------------

def test_config_params_state_and_specs():
    cfg = kda_cfg("akkk")
    assert cfg.keeps_row_state and cfg.n_kda_layers == 3
    assert (cfg.n_attn_layers, cfg.n_mamba_layers, cfg.layer_period) == (
        1, 0, 4)
    assert cfg.layer_runs == (("attention", 0, 1, 0), ("kda", 1, 3, 0))
    assert cfg.head_dim == 16 != cfg.d_model // cfg.n_heads
    assert (cfg.kda_inner, cfg.kda_head_dim) == (64, 16)
    lay = init_params(cfg, jax.random.PRNGKey(0))["layers"]
    assert lay["attention"]["wg"].shape == (1, 48, 64)
    assert lay["attention"]["wq"].shape == (1, 48, 64)
    assert lay["attention"]["wk"].shape == (1, 48, 32)
    shapes = {k: v.shape for k, v in lay["kda"].items()}
    assert shapes == {
        "in_proj": (3, 48, 192), "conv_w": (3, 4, 192), "f_down": (3, 48, 16),
        "f_up": (3, 16, 64), "dt_bias": (3, 64), "A_log": (3, 4),
        "b_proj": (3, 48, 4), "g_down": (3, 48, 16), "g_up": (3, 16, 64),
        "norm": (3, 16), "out_proj": (3, 64, 48)}
    assert lay["router"].shape == (4, 48, 16)
    assert lay["router_bias"].shape == (4, 16)
    assert lay["router_bias"].dtype == F32
    assert lay["e_gate"].shape == (4, 4, 48, 32)
    state = tr.init_row_state(cfg, 5)
    assert set(state) == {"kda_s", "kda_conv"}
    assert state["kda_s"].shape == (3, 5, 64, 16)
    assert state["kda_s"].dtype == F32              # never the compute dtype
    assert state["kda_conv"].shape == (3, 5, 3, 192)
    assert tr.init_paged_cache(cfg, 6, 16)["k"].shape == (1, 6, 2, 16, 16)
    # three kinds in one stack: each kind's leaves, none of another's
    mixed = kda_cfg("mka", mamba_heads=4, mamba_head_dim=16, mamba_state=8)
    assert set(tr.init_row_state(mixed, 2)) == {"ssm", "conv", "kda_s",
                                                "kda_conv"}
    # int8: the large projections, not the low-rank gates or the biases
    from tfmesos_tpu.ops.quant import QTensor
    q = tr.quantize_params(cfg, init_params(cfg, jax.random.PRNGKey(0)))
    ql = q["layers"]
    for name in ("in_proj", "out_proj"):
        assert isinstance(ql["kda"][name], QTensor), name
    for name in ("f_down", "f_up", "g_down", "g_up", "b_proj", "A_log",
                 "dt_bias", "conv_w", "norm"):
        assert not isinstance(ql["kda"][name], QTensor), name
    assert isinstance(ql["attention"]["wg"], QTensor)
    assert not isinstance(ql["router_bias"], QTensor)
    assert not isinstance(ql["router"], QTensor)
    # the partition specs name every leaf the parameters have
    mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:1]), ("tp",))
    specs = tr.partition_specs(cfg, mesh)
    params = init_params(cfg, jax.random.PRNGKey(0))
    assert jax.tree_util.tree_structure(
        jax.tree_util.tree_map(lambda _: 0, params)) == \
        jax.tree_util.tree_structure(jax.tree_util.tree_map(
            lambda _: 0, specs,
            is_leaf=lambda s: isinstance(s, jax.sharding.PartitionSpec)))


def test_what_the_configuration_refuses():
    with pytest.raises(ValueError, match="layer_types"):
        kda_cfg("akkk", layer_types=("attention", "kda", "kda", "delta"))
    with pytest.raises(ValueError, match="kda_heads"):
        kda_cfg("ak", kda_heads=0)
    with pytest.raises(ValueError, match="router_score"):
        kda_cfg("ak", router_score="tanh")
    with pytest.raises(ValueError, match="grouped"):
        TransformerConfig(n_experts=4, router_score="sigmoid")
    cfg = kda_cfg("ak")
    with pytest.raises(NotImplementedError):
        tr.forward(cfg, init_params(cfg, jax.random.PRNGKey(0)),
                   jnp.zeros((1, 8), jnp.int32))
    gated = TransformerConfig(vocab_size=64, d_model=32, n_layers=1,
                              n_heads=2, d_ff=32, attn_gate=True)
    with pytest.raises(NotImplementedError):
        tr.forward(gated, init_params(gated, jax.random.PRNGKey(0)),
                   jnp.zeros((1, 8), jnp.int32))


def test_named_scopes_show_in_the_compiled_programs():
    """``kda`` around the mixer with ``kda.update`` (decode) /
    ``kda.chunk_scan`` (prefill) inside, ``attention.gate`` on the gate."""
    cfg = kda_cfg("ak")
    params = init_params(cfg, jax.random.PRNGKey(0))
    rows = 2
    cache = dict(tr.init_paged_cache(cfg, 8, 16),
                 state=tr.init_row_state(cfg, rows),
                 pages=jnp.arange(rows * 2, dtype=jnp.int32).reshape(rows, 2))
    step = jax.jit(lambda c, t, p: tr.decode_step(cfg, params, c, t, p))
    text = step.lower(cache, jnp.zeros((rows, 1), jnp.int32),
                      jnp.zeros((rows,), jnp.int32)).compile().as_text()
    for scope in ("kda/kda.update", "attention/attention.gate"):
        assert scope in text, scope
    fill = jax.jit(lambda c, t: tr.decode_step(cfg, params, c, t, 0))
    text = fill.lower(
        dict(cache, slots=jnp.arange(rows, dtype=jnp.int32),
             valid=jnp.asarray([20, 32], jnp.int32)),
        jnp.zeros((rows, 32), jnp.int32)).compile().as_text()
    for scope in ("kda/kda.chunk_scan", "attention/attention.gate"):
        assert scope in text, scope


# -- ContinuousBatcher over a stack with KDA layers ---------------------------

@pytest.fixture(scope="module")
def served():
    cfg = kda_cfg("akkk")
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
    sync loop, the pipelined carry (the batcher's own choice for a stack
    that keeps a row state, whatever the kind) and K = 4 blocks give the
    same tokens; the state store rides the donated pool through all."""
    _, _, run = served
    b, sync = run(pipeline_depth=0)
    assert b._recurrent and b._moe_counts
    assert set(b.pool["state"]) == {"kda_s", "kda_conv"}
    assert all(len(toks) for _, toks in sync)
    b, auto = run(pipeline_depth=None)
    assert b.pipeline_depth == 1 and b._pipelined
    assert b.pipeline_bypass_reason is None
    assert auto == sync
    modes = {r["mode"] for r in flight(TICK_COMPONENT).snapshot()
             if r.get("batcher") == b.flight.value
             and r["name"] == "decode.block"}
    assert modes == {"pipelined"}
    _, blocks = run(multi_step=4)
    assert blocks == sync


def test_batcher_ring_carries_the_state_and_expert_fields(served):
    _, _, run = served
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
    # a slot: 3 layers x (64 x 16 float32 + a 3 x 192 float32 conv tail)
    state = b.pool["state"]
    assert sorted(state) == ["kda_conv", "kda_s"]
    assert sum(a.nbytes // a.shape[1] for a in state.values()) \
        == 3 * (64 * 16 * 4 + 3 * 192 * 4)


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
    reasons = compute_bypass_reasons(recurrent=cfg.keeps_row_state,
                                     pipeline_depth=1)
    assert reasons["pipeline"] is None
    assert {reasons[k] for k in ("prefix_cache", "kv_tier", "suspend",
                                 "speculative", "kv_export")} == {
        "recurrent row state"}


def test_int8_weights_serve_and_differ(served):
    """The program's own weight-only int8 path runs a KDA stack, and is a
    different result (the control ``correct`` has to refuse)."""
    cfg, params, run = served
    _, want = run()
    b = ContinuousBatcher(cfg, tr.quantize_params(cfg, params), rows=2,
                          max_len=128, page_size=16, prefill_bucket=16)
    rng = np.random.default_rng(0)
    reqs = [Request(prompt=rng.integers(0, 128, n).astype(np.int32),
                    max_new_tokens=m) for n, m in ((21, 9), (40, 5), (7, 12))]
    got = sorted((tuple(c.request.prompt.tolist()),
                  tuple(int(t) for t in c.tokens)) for c in b.run(reqs))
    assert all(len(toks) for _, toks in got)
    assert [g for g in got if g not in want]
