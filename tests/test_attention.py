"""Flash-attention kernel vs reference (Pallas interpret mode on CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tfmesos_tpu.ops.attention import flash_attention, mha_reference


def _qkv(b=2, t=256, h=2, d=64, dtype=jnp.float32, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return tuple(jax.random.normal(k, (b, t, h, d), dtype) for k in ks)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_kernel_matches_reference(causal):
    q, k, v = _qkv()
    expected = mha_reference(q, k, v, causal=causal)
    got = flash_attention(q, k, v, causal=causal, block_q=128, block_k=128,
                          use_pallas=True, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                               rtol=2e-5, atol=2e-5)


def test_flash_kernel_small_blocks():
    q, k, v = _qkv(b=1, t=128, h=1, d=32)
    expected = mha_reference(q, k, v, causal=True)
    got = flash_attention(q, k, v, causal=True, block_q=32, block_k=64,
                          use_pallas=True, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                               rtol=2e-5, atol=2e-5)


def test_flash_gradient_via_recompute():
    q, k, v = _qkv(b=1, t=128, h=1, d=32)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True, use_pallas=True,
                                       interpret=True) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(mha_reference(q, k, v, causal=True) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, e in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(e),
                                   rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("causal,block_k", [(False, 128), (True, 64)])
def test_blockwise_backward_matches_reference(causal, block_k):
    """The Pallas two-kernel backward (dq / dk+dv, O(T·block) memory) must
    equal the vjp of the reference (which materializes the full T x T
    probabilities)."""
    q, k, v = _qkv(b=1, t=256, h=2, d=32, seed=3)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal,
                                       block_k=block_k, use_pallas=True,
                                       interpret=True) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(mha_reference(q, k, v, causal=causal) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, e in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(e),
                                   rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("kv_heads", [1, 2])
def test_flash_gqa_matches_repeated_reference(causal, kv_heads):
    """GQA-native kernels (kv index maps, no materialized repeat): forward
    AND both backward kernels must match the reference computed on
    explicitly repeated K/V — including the dk/dv group-sum."""
    b, t, h, d = 2, 128, 4, 32
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (b, t, h, d))
    k = jax.random.normal(ks[1], (b, t, kv_heads, d))
    v = jax.random.normal(ks[2], (b, t, kv_heads, d))
    g = h // kv_heads

    def ref_loss(q, k, v):
        kf = jnp.repeat(k, g, axis=2)
        vf = jnp.repeat(v, g, axis=2)
        o = mha_reference(q, kf, vf, causal=causal)
        return jnp.sum(o.astype(jnp.float32) ** 2)

    def flash_loss(q, k, v):
        o = flash_attention(q, k, v, causal=causal, block_q=32, block_k=64,
                            use_pallas=True, interpret=True)
        return jnp.sum(o.astype(jnp.float32) ** 2)

    ref, (dq_r, dk_r, dv_r) = jax.value_and_grad(ref_loss, argnums=(0, 1, 2))(
        q, k, v)
    got, (dq, dk, dv) = jax.value_and_grad(flash_loss, argnums=(0, 1, 2))(
        q, k, v)
    np.testing.assert_allclose(float(got), float(ref), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(dq), np.asarray(dq_r),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(dk), np.asarray(dk_r),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(dv), np.asarray(dv_r),
                               rtol=2e-4, atol=2e-4)


def test_flash_gqa_backward_multi_qblock_interleave():
    """t=1024 makes the backward pick 512-blocks, so the dkv grid's
    (q-block x group) streamed dim really interleaves (e//g > 0) — a
    mis-derived head/q-block index there passes single-block tests."""
    b, t, h, kvh, d = 1, 1024, 4, 2, 16
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(ks[0], (b, t, h, d))
    k = jax.random.normal(ks[1], (b, t, kvh, d))
    v = jax.random.normal(ks[2], (b, t, kvh, d))
    g = h // kvh

    def ref_loss(q, k, v):
        o = mha_reference(q, jnp.repeat(k, g, axis=2),
                          jnp.repeat(v, g, axis=2), causal=True)
        return jnp.sum(o.astype(jnp.float32) ** 2)

    def flash_loss(q, k, v):
        o = flash_attention(q, k, v, causal=True, use_pallas=True,
                            interpret=True)
        return jnp.sum(o.astype(jnp.float32) ** 2)

    _, (dq_r, dk_r, dv_r) = jax.value_and_grad(ref_loss, argnums=(0, 1, 2))(
        q, k, v)
    _, (dq, dk, dv) = jax.value_and_grad(flash_loss, argnums=(0, 1, 2))(
        q, k, v)
    np.testing.assert_allclose(np.asarray(dq), np.asarray(dq_r),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(dk), np.asarray(dk_r),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(dv), np.asarray(dv_r),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("window", [1, 16, 100, 1024])
def test_flash_sliding_window_matches_reference(window):
    """Sliding-window kernels (bounded k-loop + window mask, fwd AND both
    backward kernels' skip conditions) vs the masked reference.  Windows
    that are sub-block (1, 16), straddle blocks (100), and exceed the
    sequence (1024, == full causal) all must agree."""
    b, t, h, d = 1, 256, 2, 32
    ks = jax.random.split(jax.random.PRNGKey(11), 3)
    q, k, v = (jax.random.normal(kk, (b, t, h, d)) for kk in ks)

    def ref_loss(q, k, v):
        o = mha_reference(q, k, v, causal=True, window=window)
        return jnp.sum(o.astype(jnp.float32) ** 2)

    def flash_loss(q, k, v):
        o = flash_attention(q, k, v, causal=True, window=window,
                            block_q=64, block_k=64, use_pallas=True,
                            interpret=True)
        return jnp.sum(o.astype(jnp.float32) ** 2)

    ref, g_ref = jax.value_and_grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
    got, g_got = jax.value_and_grad(flash_loss, argnums=(0, 1, 2))(q, k, v)
    np.testing.assert_allclose(float(got), float(ref), rtol=1e-5)
    for a, e in zip(g_got, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(e),
                                   rtol=2e-4, atol=2e-4)


def test_window_validation():
    q = jnp.zeros((1, 64, 2, 16))
    with pytest.raises(ValueError, match="causal"):
        flash_attention(q, q, q, causal=False, window=8)
    with pytest.raises(ValueError, match="window"):
        flash_attention(q, q, q, causal=True, window=0)
    # window x sp COMPOSES as of round 4 (ring owner-index masking /
    # Ulysses pass-through) — equivalence is tested in
    # tests/test_parallel.py::test_attend_window_sp_composition; here just
    # assert the former hard-error path now runs.
    from tfmesos_tpu.ops.attention import attend, mha_reference
    from tfmesos_tpu.parallel.mesh import build_mesh
    import numpy as np
    qr = jax.random.normal(jax.random.PRNGKey(0), (1, 64, 2, 16),
                           jnp.float32)
    out = attend(qr, qr, qr, mesh=build_mesh({"sp": 8}), window=8)
    np.testing.assert_allclose(
        np.asarray(out),
        np.asarray(mha_reference(qr, qr, qr, causal=True, window=8)),
        rtol=2e-5, atol=2e-5)


def test_attend_mqa_on_tp_mesh_repeats_to_shard():
    """MQA (kv_heads=1) under tp=2: tp does not divide kv_heads, so the
    sharded path must repeat K/V to full width rather than die on an
    uneven shard_map split (the pre-GQA-kernel behavior)."""
    from tfmesos_tpu.ops.attention import attend
    from tfmesos_tpu.parallel.mesh import build_mesh

    mesh = build_mesh({"dp": 4, "tp": 2})
    b, t, h, d = 4, 64, 4, 16
    ks = jax.random.split(jax.random.PRNGKey(9), 3)
    q = jax.random.normal(ks[0], (b, t, h, d))
    k = jax.random.normal(ks[1], (b, t, 1, d))
    v = jax.random.normal(ks[2], (b, t, 1, d))
    ref = mha_reference(q, jnp.repeat(k, h, axis=2),
                        jnp.repeat(v, h, axis=2), causal=True)
    got = jax.jit(lambda q_, k_, v_: attend(q_, k_, v_, mesh=mesh))(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_cpu_fallback_and_unaligned_shapes():
    # Auto mode on CPU (or any unaligned seq len) must take the XLA path.
    q, k, v = _qkv(b=1, t=100, h=1, d=16)
    got = flash_attention(q, k, v, causal=True)  # use_pallas=None auto
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(mha_reference(q, k, v, causal=True)),
                               rtol=1e-5, atol=1e-5)


def test_attend_dispatch_on_dp_tp_mesh():
    """attend() dispatch: the first mesh (has sp>1) takes the ring-attention
    path; the second (dp/tp only) takes the shard_map flash path — both must
    match the single-device reference."""
    from tfmesos_tpu.ops.attention import attend
    from tfmesos_tpu.parallel.mesh import build_mesh

    mesh = build_mesh({"dp": 2, "tp": 2, "sp": 2})
    q, k, v = _qkv(b=4, t=32, h=4, d=16, seed=9)
    expected = mha_reference(q, k, v, causal=True)
    got = jax.jit(lambda q, k, v: attend(q, k, v, mesh=mesh, causal=True))(
        q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                               rtol=2e-5, atol=2e-5)

    mesh2 = build_mesh({"dp": 4, "tp": 2})
    got2 = jax.jit(lambda q, k, v: attend(q, k, v, mesh=mesh2, causal=True))(
        q, k, v)
    np.testing.assert_allclose(np.asarray(got2), np.asarray(expected),
                               rtol=2e-5, atol=2e-5)


def test_bf16_inputs():
    q, k, v = _qkv(dtype=jnp.bfloat16, t=128)
    got = flash_attention(q, k, v, causal=True, use_pallas=True, interpret=True)
    expected = mha_reference(q, k, v, causal=True)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(got, dtype=np.float32),
                               np.asarray(expected, dtype=np.float32),
                               rtol=2e-2, atol=2e-2)


def test_pick_block_legal_divisors():
    from tfmesos_tpu.ops.attention import _pick_block

    assert _pick_block(2048) == 512
    assert _pick_block(1024) == 512
    assert _pick_block(384) == 384
    assert _pick_block(640) == 128   # 512 does not divide 640
    assert _pick_block(100) == 100   # no 8-aligned divisor <= target: full dim
    assert _pick_block(8) == 8


def _prefill_widths(cell, window=None, bucket=64):
    """Every prefill width of a benchmark cell's committed schedule: prompts
    padded to the batcher's ``prefill_bucket``; an EVA prompt is prefilled
    in windows of ``window`` and a tail."""
    from benchmark import traffic_gen

    sched = traffic_gen.make_schedule(traffic_gen.load_traffic(cell), 1, 51,
                                      320)
    widths = []
    for r in sched.requests:
        w = -(-r.prompt.size // bucket) * bucket
        if window:
            widths += [window] * (w // window) + [w % window] * bool(w % window)
        else:
            widths.append(w)
    return widths


@pytest.mark.parametrize("cell,window,under_256_before", [
    ("docqa_batch", None, 1.0),         # every width an odd multiple of 64
    ("chat_steady", None, 0.648),
    ("longdoc_batch", 2048, 0.0),       # windows of 2048, tails of k x 256
])
def test_flash_tiles_over_the_committed_traffic(cell, window,
                                                under_256_before):
    """The counter of the forward's tile rule is a count over the traffic
    the benchmark commits: the share of prefill attention work (width
    squared) whose q tile has fewer than 256 rows.  The divisor rule
    (``_pick_block``, which the backward and the ring keep) left all of
    docqa_batch there, at 64 x 64; ``_flash_tiles`` leaves only the widths
    that are under 256 themselves."""
    from tfmesos_tpu.ops.attention import _flash_tiles, _pick_block

    widths = _prefill_widths(cell, window)
    work = float(sum(w * w for w in widths))
    before = sum(w * w for w in widths if _pick_block(w, 512) < 256) / work
    assert before == pytest.approx(under_256_before, abs=2e-3)
    for w in widths:
        bq, bk = _flash_tiles(w, w, 128, 2)
        assert min(bq, bk) >= min(w, 256), (w, bq, bk)
    if not window:
        assert min(widths) % 64 == 0 and max(widths) <= 8192
    if cell == "docqa_batch":
        assert all(w // 64 % 2 for w in widths)     # odd multiples of 64


@pytest.mark.parametrize("itemsize,head_dim,max_len", [
    (2, 128, 8192), (2, 64, 8192), (1, 128, 8192),
    (4, 128, 4096),     # float32: a KV head's K/V, resident, fits to here
])
def test_flash_tiles_legal_and_within_vmem(itemsize, head_dim, max_len):
    """Every width the batcher can offer (multiples of the prefill bucket
    up to ``max_len``) gets a Mosaic-legal tile near the target whose
    reservation (a KV head's K and V resident, double-buffered, the q/o
    blocks, the score block) stays under the chip's scoped VMEM limit."""
    from tfmesos_tpu.ops.attention import (_VMEM_SCOPED_LIMIT,
                                           _flash_tiles, _flash_vmem_bytes)

    sub = 8 * max(1, 4 // itemsize)
    for t in range(64, max_len + 1, 64):
        bq, bk = _flash_tiles(t, t, head_dim, itemsize)
        assert bq <= 512 and bk <= 512
        # a block is the whole (padded) length or lands on the tiling: the
        # dtype's sublanes for q rows, whole 128-lane rows of scores for k
        assert bq == t or bq % sub == 0, (t, bq)
        assert bk == t or bk % 128 == 0, (t, bk)
        assert bq >= min(t, 256) and bk >= min(t, 256), (t, bq, bk)
        n_q = -(-t // bq)
        assert n_q == -(-t // 512) and n_q * bq - t < n_q * sub, (t, bq)
        assert -(-t // bk) * bk - t < bk
        assert _flash_vmem_bytes(bq, bk, t, head_dim,
                                 itemsize) <= _VMEM_SCOPED_LIMIT, (t, bq, bk)
    # the caller's block arguments stay targets
    assert _flash_tiles(128, 128, 32, 4, 32, 64) == (32, 64)
    assert _flash_tiles(704, 704, 32, 4, 128, 128) == (120, 128)


def _dense_attention(q, k, v, causal, window=None, q_offset=0):
    """(o, lse) with query i at global position ``i + q_offset``; a row that
    sees no key reads zero and -inf, as the kernel's window form does."""
    g = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
    qpos = q_offset + jnp.arange(q.shape[1])[:, None]
    kpos = jnp.arange(k.shape[1])[None]
    ok = jnp.ones_like(kpos > qpos) if not causal else kpos <= qpos
    if window is not None:
        ok = ok & (kpos >= qpos - (window - 1))
    s = jnp.where(ok, s, -jnp.inf)
    lse = jax.nn.logsumexp(s, axis=-1, keepdims=True)
    p = jnp.where(ok, jnp.exp(s - jnp.where(jnp.isinf(lse), 0.0, lse)), 0.0)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v), lse


# name -> (t_q, t_k, q heads, kv heads, causal, window, q_offset,
#          (block_q, block_k) targets): every one a width its tile does not
# divide, so a block of zero padding is read, masked and cut off again.
RAGGED = {
    "causal_192_one_block": (192, 192, 8, 2, True, None, 0, (512, 512)),
    "causal_704": (704, 704, 8, 2, True, None, 0, (512, 512)),
    "causal_1088": (1088, 1088, 8, 2, True, None, 0, (512, 512)),
    "causal_704_small_tiles": (704, 704, 4, 1, True, None, 0, (128, 128)),
    "full_320_over_704": (320, 704, 4, 2, False, None, 0, (128, 256)),
    "full_704_last_block_all_padding_but_64": (64, 704, 2, 2, False, None, 0,
                                               (64, 128)),
    "window_100": (704, 704, 4, 2, True, 100, 0, (128, 256)),
    "window_ring_step_1": (192, 192, 4, 4, True, 256, 192, (128, 128)),
    "window_ring_step_2_rows_see_nothing": (192, 192, 2, 2, True, 300, 384,
                                            (128, 128)),
}


@pytest.mark.parametrize("name", sorted(RAGGED))
def test_flash_forward_at_ragged_widths(name):
    """``flash_attention_fwd`` at widths its tile does not divide, against
    the dense form: o and the log-sum-exp the backward and EVA continue
    from, in the caller's shapes, with no NaN from the padded tail."""
    from tfmesos_tpu.ops.attention import (_FlashCfg, _flash_forward,
                                           _flash_tiles)

    t, tk, h, kv, causal, window, q_offset, targets = RAGGED[name]
    ks = jax.random.split(jax.random.PRNGKey(len(name)), 3)
    q = jax.random.normal(ks[0], (1, t, h, 32))
    k = jax.random.normal(ks[1], (1, tk, kv, 32))
    v = jax.random.normal(ks[2], (1, tk, kv, 32))
    bq, bk = _flash_tiles(t, tk, 32, 4, *targets)
    assert (t % bq or tk % bk) or name.endswith("one_block")
    cfg = _FlashCfg(causal=causal, scale=32 ** -0.5, block_q=bq, block_k=bk,
                    interpret=True, q_per_kv=h // kv, window=window,
                    q_offset=q_offset)
    o, lse = _flash_forward(cfg, q, k, v)
    want_o, want_lse = _dense_attention(q, k, v, causal, window, q_offset)
    assert o.shape == q.shape and lse.shape == (1, h, t, 1)
    assert not np.isnan(np.asarray(o)).any()
    np.testing.assert_allclose(np.asarray(o), np.asarray(want_o),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(want_lse),
                               rtol=2e-5, atol=2e-5)
    if not q_offset:
        got = flash_attention(q, k, v, causal=causal, window=window,
                              block_q=targets[0], block_k=targets[1],
                              use_pallas=True, interpret=True)
        np.testing.assert_allclose(
            np.asarray(got),
            np.asarray(mha_reference(q, k, v, causal=causal, window=window)),
            rtol=2e-5, atol=2e-5)


def test_flash_gradient_at_a_ragged_width():
    """t = 320 under 128-targets: the forward pads q to 3 x 112 rows and K/V
    to 3 x 128, the backward tiles 320 by its own divisor (64) and is
    driven by the forward's ``lse`` in the caller's [B, H, T, 1]."""
    b, t, h, kvh, d = 1, 320, 4, 2, 32
    ks = jax.random.split(jax.random.PRNGKey(29), 3)
    q = jax.random.normal(ks[0], (b, t, h, d))
    k = jax.random.normal(ks[1], (b, t, kvh, d))
    v = jax.random.normal(ks[2], (b, t, kvh, d))

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True, block_q=128,
                                       block_k=128, use_pallas=True,
                                       interpret=True) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(mha_reference(q, k, v, causal=True) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, e in zip(gf, gr):
        assert not np.isnan(np.asarray(a)).any()
        np.testing.assert_allclose(np.asarray(a), np.asarray(e),
                                   rtol=2e-4, atol=2e-4)


def test_default_blocks_gradient_long_seq():
    """t=1024 exercises the 512-block backward grid (multiple q/k blocks per
    axis plus causal block skipping) in interpret mode."""
    q, k, v = _qkv(b=1, t=1024, h=1, d=32, seed=5)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True, use_pallas=True,
                                       interpret=True) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(mha_reference(q, k, v, causal=True) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, e in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(e),
                                   rtol=2e-4, atol=2e-4)


def test_cross_attention_gradient():
    """Asymmetric q/k lengths: the dq and dk/dv grids differ (t != tk)."""
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(ks[0], (1, 128, 2, 32), jnp.float32)
    k = jax.random.normal(ks[1], (1, 256, 2, 32), jnp.float32)
    v = jax.random.normal(ks[2], (1, 256, 2, 32), jnp.float32)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, use_pallas=True,
                                       interpret=True) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(mha_reference(q, k, v) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, e in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(e),
                                   rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# flash_decode: the single-token serving kernel


def _decode_inputs(b=2, m=1024, h=8, kv=2, d=64, dtype=jnp.float32, seed=0):
    # Caches in the kernel-native [B, KV, M, D] layout (init_cache's,
    # minus the layer dim).
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (b, h, d), dtype)
    kc = jax.random.normal(ks[1], (b, kv, m, d), dtype)
    vc = jax.random.normal(ks[2], (b, kv, m, d), dtype)
    return q, kc, vc


def _lane_major_quant(c):
    """int8-quantize a [B, KV, M, D] cache slice into the cache's
    LANE-MAJOR QTensor form (scales [B, KV, 1, M]); also returns the
    dequantized array for references."""
    from tfmesos_tpu.ops.quant import QTensor, quantize_tensor

    qt = quantize_tensor(c)     # per-position scales [B, KV, M, 1]
    lane = QTensor(qt.values, jnp.swapaxes(qt.scales, -1, -2))
    return lane, qt.dequantize(jnp.float32)


@pytest.mark.parametrize("pos", [0, 5, 511, 512, 700, 1023])
def test_flash_decode_matches_reference(pos):
    from tfmesos_tpu.ops.attention import _decode_reference, flash_decode
    q, kc, vc = _decode_inputs()
    ref = _decode_reference(q, kc, vc, pos, q.shape[-1] ** -0.5)
    got = flash_decode(q, kc, vc, pos, use_pallas=True, interpret=True,
                       block_m=256)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("kv,h", [(1, 4), (4, 4)])  # MQA / full MHA
def test_flash_decode_head_layouts(kv, h):
    from tfmesos_tpu.ops.attention import _decode_reference, flash_decode
    q, kc, vc = _decode_inputs(h=h, kv=kv, m=512)
    ref = _decode_reference(q, kc, vc, 300, q.shape[-1] ** -0.5)
    got = flash_decode(q, kc, vc, 300, use_pallas=True, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_decode_traced_pos_under_scan():
    """pos rides the kernel's scalar prefetch, so it may be a traced value
    (the generate() scan's carry) — the grid bound follows it."""
    from tfmesos_tpu.ops.attention import _decode_reference, flash_decode
    q, kc, vc = _decode_inputs(m=512)

    def step(c, p):
        return c, flash_decode(q, kc, vc, p, use_pallas=True,
                               interpret=True, block_m=128)

    _, outs = jax.lax.scan(step, 0, jnp.array([3, 129, 500], jnp.int32))
    for i, p in enumerate([3, 129, 500]):
        ref = _decode_reference(q, kc, vc, p, q.shape[-1] ** -0.5)
        np.testing.assert_allclose(np.asarray(outs[i]), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)


def test_flash_decode_bad_gqa_heads():
    from tfmesos_tpu.ops.attention import flash_decode
    q, kc, vc = _decode_inputs(h=4, kv=3, m=512)
    with pytest.raises(ValueError, match="multiple of kv heads"):
        flash_decode(q, kc, vc, 10)


@pytest.mark.parametrize("pos", [0, 511, 700])
def test_flash_decode_int8_cache(pos):
    """QTensor caches: HBM streams int8 and the per-position scales fold
    into the score/probability rows — bit-identical to dequantize-then-
    attend."""
    from tfmesos_tpu.ops.attention import _decode_reference, flash_decode
    q, kc, vc = _decode_inputs()
    kq, kd = _lane_major_quant(kc)
    vq, vd = _lane_major_quant(vc)
    ref = _decode_reference(q, kd, vd, pos, q.shape[-1] ** -0.5)
    got = flash_decode(q, kq, vq, pos, use_pallas=True, interpret=True,
                       block_m=256)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("quantized", [False, True])
def test_decode_step_kernel_path_matches_dense(quantized):
    """decode_step with the kernel gate forced open reproduces the dense
    einsum path's logits, for fp and int8 caches alike (the auto gate only
    opens on TPU)."""
    from tfmesos_tpu.models import transformer

    cfg = transformer.TransformerConfig(
        vocab_size=64, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=64, max_seq_len=640, dtype=jnp.float32)
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    prompt = jax.random.randint(jax.random.PRNGKey(1), (2, 9), 0,
                                cfg.vocab_size)
    cache0 = transformer.init_cache(cfg, 2, 640, quantized=quantized)
    logits, cache = transformer.decode_step(cfg, params, cache0, prompt, 0)
    tok = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)

    ref_logits, _ = transformer.decode_step(cfg, params, cache, tok, 9)

    orig = transformer._decode_kernel_kwargs
    transformer._decode_kernel_kwargs = (
        lambda cfg_, m, t, sharded, mesh=None, batch=None:
        {"use_pallas": True, "interpret": True} if t == 1 else None)
    try:
        got_logits, _ = transformer.decode_step(cfg, params, cache, tok, 9)
    finally:
        transformer._decode_kernel_kwargs = orig
    np.testing.assert_allclose(np.asarray(got_logits),
                               np.asarray(ref_logits), rtol=2e-4, atol=2e-4)


def test_flash_decode_ragged_positions():
    """pos as a [B] vector: each row's block loop bounds independently —
    the mixed-length serving case."""
    from tfmesos_tpu.ops.attention import _decode_reference, flash_decode
    q, kc, vc = _decode_inputs(b=3, m=1024, h=4, kv=2, d=32)
    posv = jnp.array([7, 600, 1023], jnp.int32)
    ref = _decode_reference(q, kc, vc, posv, q.shape[-1] ** -0.5)
    for i, p in enumerate([7, 600, 1023]):   # vector ref == per-row scalar
        ri = _decode_reference(q[i:i + 1], kc[i:i + 1], vc[i:i + 1], p,
                               q.shape[-1] ** -0.5)
        np.testing.assert_allclose(np.asarray(ref[i:i + 1]), np.asarray(ri),
                                   rtol=1e-6, atol=1e-6)
    got = flash_decode(q, kc, vc, posv, use_pallas=True, interpret=True,
                       block_m=256)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("pos", [0, 100])
def test_flash_decode_chunk_matches_reference(pos):
    """Chunked queries (q [B, t, H, D]): token tt attends cache positions
    <= pos + tt — the speculative-verify / chunked-prefill case."""
    from tfmesos_tpu.ops.attention import _decode_reference, flash_decode
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    b, m, h, kv, d, t = 2, 1024, 4, 2, 32, 5
    q = jax.random.normal(ks[0], (b, t, h, d), jnp.float32)
    kc = jax.random.normal(ks[1], (b, kv, m, d), jnp.float32)
    vc = jax.random.normal(ks[2], (b, kv, m, d), jnp.float32)
    ref = _decode_reference(q, kc, vc, pos, d ** -0.5)
    got = flash_decode(q, kc, vc, pos, use_pallas=True, interpret=True,
                       block_m=256)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_decode_chunk_ragged_and_int8():
    from tfmesos_tpu.ops.attention import _decode_reference, flash_decode
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    b, m, h, kv, d, t = 2, 512, 4, 2, 32, 3
    q = jax.random.normal(ks[0], (b, t, h, d), jnp.float32)
    kc = jax.random.normal(ks[1], (b, kv, m, d), jnp.float32)
    vc = jax.random.normal(ks[2], (b, kv, m, d), jnp.float32)
    posv = jnp.array([7, 400], jnp.int32)
    ref = _decode_reference(q, kc, vc, posv, d ** -0.5)
    got = flash_decode(q, kc, vc, posv, use_pallas=True, interpret=True,
                       block_m=128)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    kq, kd = _lane_major_quant(kc)
    vq, vd = _lane_major_quant(vc)
    ref8 = _decode_reference(q, kd, vd, posv, d ** -0.5)
    got8 = flash_decode(q, kq, vq, posv, use_pallas=True, interpret=True,
                        block_m=128)
    np.testing.assert_allclose(np.asarray(got8), np.asarray(ref8),
                               rtol=2e-5, atol=2e-5)


# What a grid step of several K/V heads, and of several rows of a one-block
# cache, can get wrong (PR 46).  name -> (b, kv, g, f, dv, sink, int8, t, m,
# block_m, VMEM budget in KiB or None for the kernel's own, the (rows,
# head_block) the rule must then give).  d = 32; ``f`` = 2 packs two heads'
# keys a K row and takes values of another width (dv != d).  A float32 head's
# K + V block of 16 positions stands in 16 KiB of VMEM (32 channels in a lane
# tile each; 12 KiB a head of a packed pair, whose 64 channels share one), an
# int8 head's of 32 in 8 KiB beside 16 KiB of scale tiles.  One block
# (m == block_m): positions ragged across the rows of ONE step, a row at
# position 0 beside a full one; several blocks (m > block_m): a step is one
# row and rows end at different blocks.
_DECODE_BLOCKS = {
    "mqa_rows_all": (4, 1, 4, 1, 32, False, False, 1, 16, 16, None, (4, 1)),
    "kv2_rows_all": (4, 2, 2, 1, 32, False, False, 1, 16, 16, None, (4, 2)),
    "kv8_rows_all": (4, 8, 1, 1, 32, False, False, 1, 16, 16, None, (4, 8)),
    "kv8_rows_2_of_4": (4, 8, 2, 1, 32, False, False, 1, 16, 16, 512, (2, 8)),
    "kv8_rows_3_of_6_t3": (6, 8, 1, 1, 32, False, False, 3, 16, 16, 1024,
                           (3, 8)),
    "kv8_heads_4_one_block": (3, 8, 2, 1, 32, False, False, 1, 16, 16, 128,
                              (1, 4)),
    "kv8_sink_rows_2": (4, 8, 2, 1, 32, True, False, 1, 16, 16, 512, (2, 8)),
    "kv8_int8_rows_2_t3": (4, 8, 2, 1, 32, False, True, 3, 32, 32, 768,
                           (2, 8)),
    "kv2_int8_rows_all": (3, 2, 4, 1, 32, False, True, 1, 32, 32, None,
                          (3, 2)),
    "packed_kv2_rows_all": (4, 2, 4, 2, 16, False, False, 1, 16, 16, None,
                            (4, 2)),
    "packed_kv8_sink_rows_2": (4, 8, 2, 2, 16, True, False, 1, 16, 16, 384,
                               (2, 8)),
    "packed_kv8_sink_rows_2_t3": (4, 8, 2, 2, 16, True, False, 3, 16, 16,
                                  384, (2, 8)),
    "packed_kv8_heads_4": (2, 8, 2, 2, 16, False, False, 1, 16, 16, 96,
                           (1, 4)),
    "blocks_4_kv1": (4, 1, 4, 1, 32, False, False, 1, 64, 16, None, (1, 1)),
    "blocks_4_kv8": (4, 8, 2, 1, 32, False, False, 1, 64, 16, None, (1, 8)),
    "blocks_4_kv8_heads_2_t3": (4, 8, 1, 1, 32, False, False, 3, 64, 16, 64,
                                (1, 2)),
    "blocks_4_kv8_sink": (4, 8, 2, 1, 32, True, False, 1, 64, 16, None,
                          (1, 8)),
    "blocks_2_kv8_int8_heads_4": (4, 8, 2, 1, 32, False, True, 1, 64, 32,
                                  192, (1, 4)),
    "blocks_4_packed_kv8_sink_t3": (4, 8, 2, 2, 16, True, False, 3, 64, 16,
                                    None, (1, 8)),
    "blocks_4_packed_kv8_heads_2": (4, 8, 2, 2, 16, False, False, 1, 64, 16,
                                    48, (1, 2)),
}


@pytest.mark.parametrize("name", sorted(_DECODE_BLOCKS))
def test_flash_decode_block_equivalence_matrix(name, monkeypatch):
    """``flash_decode`` (interpret mode) against ``_decode_reference`` over
    the block rule's cases: whatever rows and heads a grid step takes, every
    row keeps its own position and mask and every head its own K, V, scales
    and sink."""
    from tfmesos_tpu.ops import attention
    from tfmesos_tpu.ops.attention import _decode_reference, flash_decode

    b, kv, g, f, dv, sink, int8, t, m, block_m, budget, expect = \
        _DECODE_BLOCKS[name]
    d = 32
    if budget is not None:
        monkeypatch.setattr(attention, "_PAGED_VMEM_BUDGET", budget * 1024)
    assert attention._decode_block(b, kv, m // block_m, block_m, d, dv,
                                   1 if int8 else 4, int8, f) == expect
    ks = jax.random.split(jax.random.PRNGKey(46), 4)
    q = jax.random.normal(ks[0], (b, t, kv * g, d), jnp.float32)
    kc = jax.random.normal(ks[1], (b, kv, m, d), jnp.float32)
    vc = jax.random.normal(ks[2], (b, kv, m, dv), jnp.float32)
    logits = jax.random.normal(ks[3], (kv * g,)) if sink else None
    # a row at position 0, a full one, and the rest ending at different
    # blocks (or, in a cache of one block, at different slots of it)
    last = m - t
    pos = jnp.asarray(([0, last, block_m + 1, block_m - 1, 2 * block_m, 5]
                       if m > block_m else [0, last, 7, 3, last, 1])[:b],
                      jnp.int32)
    if int8:
        k_in, k_ref = _lane_major_quant(kc)
        v_in, v_ref = _lane_major_quant(vc)
    else:
        k_in, v_in, k_ref, v_ref = kc, vc, kc, vc
        if f > 1:   # [B, KV, M, D] -> [B, KV / f, M, f * D], heads side by side
            k_in = jnp.moveaxis(kc.reshape(b, kv // f, f, m, d), 2, 3).reshape(
                b, kv // f, m, f * d)
    want = _decode_reference(q, k_ref, v_ref, pos, d ** -0.5, logits)
    got = flash_decode(q, k_in, v_in, pos, use_pallas=True, interpret=True,
                       block_m=block_m, sink=logits)
    assert got.shape == (b, t, kv * g, dv)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("name,call,expect", [
    # (rows of the batch, kv heads, blocks of the cache, positions a block,
    # keys' channels, values', itemsize[, int8[, K heads a row]]) -> (rows,
    # head block) of a grid step
    ("mimo_rings", (128, 8, 1, 128, 192, 128, 2, False, 2), (4, 8)),
    ("laguna_rings", (128, 8, 1, 512, 128, 128, 2), (2, 8)),    # the budget
    ("dense_1024", (32, 8, 8, 1024, 128, 128, 2), (1, 8)),      # the budget
    ("dense_1024_one_block", (8, 8, 1, 1024, 128, 128, 2), (1, 8)),
    ("dense_1024_int8", (32, 8, 8, 1024, 128, 128, 1, True), (1, 8)),
    ("dense_32_heads", (16, 32, 16, 1024, 128, 128, 2), (1, 8)),
    ("dense_32_heads_int8", (16, 32, 16, 1024, 128, 128, 1, True), (1, 8)),
    ("dense_12_heads", (4, 12, 4, 1024, 128, 128, 2), (1, 6)),
    ("packed_16_heads", (8, 16, 8, 1024, 192, 128, 2, False, 2), (1, 4)),
    ("packed_6_heads", (8, 6, 8, 1024, 320, 256, 2, False, 2), (1, 2)),
    ("unpacked_192", (128, 8, 1, 128, 192, 128, 2), (4, 8)),    # 256 lanes
    ("flagship_d64", (8, 8, 1, 1024, 64, 64, 2), (1, 8)),   # a lane tile each
    ("flagship_d64_short", (8, 8, 1, 256, 64, 64, 2), (4, 8)),
    ("rings_several_blocks", (128, 8, 4, 128, 192, 128, 2, False, 2), (1, 8)),
    ("rows_of_6", (6, 8, 1, 128, 192, 128, 2, False, 2), (6, 8)),
    ("rows_of_14", (14, 8, 1, 128, 192, 128, 2, False, 2), (2, 8)),
    ("one_row", (1, 8, 1, 128, 128, 128, 2), (1, 8)),
    ("tests_f32", (3, 2, 1, 16, 32, 32, 4), (3, 2)),
    ("a_head_past_the_budget", (4, 2, 2, 1024, 2048, 2048, 4), (1, 1)),
    ("a_pair_past_the_budget", (4, 4, 2, 1024, 1984, 2048, 4, False, 2),
     (1, 2)),
    ("mqa", (32, 1, 8, 1024, 128, 128, 2), (1, 1)),
    ("mqa_ring", (32, 1, 1, 512, 128, 128, 2), (16, 1)),
])
def test_decode_block_rule(name, call, expect):
    """``_decode_block`` is a pure function of what a call sees: every head
    of a row where they fit, else a divisor of them in whole K rows; rows
    only of a cache that is one block; the double-buffered K + V blocks
    within the budget wherever one K row of heads fits at all."""
    from tfmesos_tpu.ops import attention

    rows, hb = attention._decode_block(*call)
    assert (rows, hb) == expect
    b, kv, blocks, block_m, d, dv, itemsize = call[:7]
    quantized = call[7] if len(call) > 7 else False
    f = call[8] if len(call) > 8 else 1
    assert kv % hb == 0 and hb % f == 0 and b % rows == 0
    assert rows == 1 or (blocks == 1 and hb == kv)
    moved = 2 * rows * hb * block_m * (d + dv) * itemsize    # unpadded
    if quantized:
        moved += 2 * rows * hb * 2 * 8 * 128 * 4 * -(-block_m // 128)
    assert moved <= attention._PAGED_VMEM_BUDGET or (rows, hb) == (1, f)


@pytest.mark.parametrize("cell,shapes,grid", [
    # (window layers, rows, kv, K heads a row, window, d, dv, q_per_kv)
    ("mimo.agent_batch", (5, 128, 8, 2, 128, 192, 128, 8), (32, 1, 1)),
    ("laguna.mixed_batch", (3, 128, 8, 1, 512, 128, 128, 8), (64, 1, 1)),
])
def test_ring_decode_grid_at_the_cells_shapes(cell, shapes, grid):
    """The mechanism's engagement is the grid itself: traced (nothing runs)
    at each cell's ring shapes, ONE ``flash_decode`` call of the rule's grid,
    a row's heads and the budget's rows a step, its one result still
    ``[rows, kv, t * g, dv]``."""
    from tfmesos_tpu.ops.attention import flash_decode

    layers, b, kv, f, w, d, dv, g = shapes
    bf16 = lambda *s: jax.ShapeDtypeStruct(s, jnp.bfloat16)
    jaxpr = jax.make_jaxpr(lambda q, k, v, pos, li: flash_decode(
        q, k, v, pos, layer=li, use_pallas=True))(
        bf16(b, kv * g, d), bf16(layers, b, kv // f, w, f * d),
        bf16(layers, b, kv, w, dv), jax.ShapeDtypeStruct((b,), jnp.int32),
        jax.ShapeDtypeStruct((), jnp.int32))
    calls = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "pallas_call"]
    assert len(calls) == 1
    assert calls[0].params["grid_mapping"].grid == grid
    assert calls[0].params["name"] == "flash_decode"
    assert [o.aval.shape for o in calls[0].outvars] == [(b, kv, g, dv)]


def test_decode_step_chunk_kernel_path_matches_dense():
    """decode_step on a multi-token chunk (the speculative-verify shape)
    with the kernel gate forced: logits match the einsum path, uniform
    and ragged positions."""
    from tfmesos_tpu.models import transformer

    cfg = transformer.TransformerConfig(
        vocab_size=64, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=64, max_seq_len=640, dtype=jnp.float32)
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    prompt = jax.random.randint(jax.random.PRNGKey(1), (2, 9), 0,
                                cfg.vocab_size)
    cache0 = transformer.init_cache(cfg, 2, 640)
    _, cache = transformer.decode_step(cfg, params, cache0, prompt, 0)
    chunk = jax.random.randint(jax.random.PRNGKey(2), (2, 5), 0,
                               cfg.vocab_size)
    orig = transformer._decode_kernel_kwargs
    force = lambda cfg_, m, t, sharded, mesh=None, batch=None: (
        {"use_pallas": True, "interpret": True})
    for pos in (9, jnp.array([9, 6], jnp.int32)):
        ref, _ = transformer.decode_step(cfg, params, cache, chunk, pos)
        transformer._decode_kernel_kwargs = force
        try:
            got, _ = transformer.decode_step(cfg, params, cache, chunk, pos)
        finally:
            transformer._decode_kernel_kwargs = orig
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=2e-4, atol=2e-4)


def test_flash_decode_paged_scrambled_pool():
    """Page-table indirection: the paged kernel over a scrambled pool
    equals the contiguous-cache reference, scalar and ragged positions,
    single tokens and chunks."""
    from tfmesos_tpu.ops.attention import _decode_reference, flash_decode_paged

    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    b, h, kv, d, ps, npg = 3, 4, 2, 32, 128, 8
    m = ps * npg
    q = jax.random.normal(ks[0], (b, h, d), jnp.float32)
    kc = jax.random.normal(ks[1], (b, kv, m, d), jnp.float32)
    vc = jax.random.normal(ks[2], (b, kv, m, d), jnp.float32)
    pool_n = b * npg + 5
    perm = np.random.RandomState(0).permutation(pool_n)[:b * npg].reshape(
        b, npg)
    # Pool layout is [P, KV, page, D] (page/head_dim trailing).
    k_pool = np.zeros((pool_n, kv, ps, d), np.float32)
    v_pool = np.zeros((pool_n, kv, ps, d), np.float32)
    for i in range(b):
        for j in range(npg):
            k_pool[perm[i, j]] = np.asarray(kc[i, :, j * ps:(j + 1) * ps])
            v_pool[perm[i, j]] = np.asarray(vc[i, :, j * ps:(j + 1) * ps])
    pt = jnp.asarray(perm, jnp.int32)
    for pos in (0, 200, jnp.array([5, 700, 1023], jnp.int32)):
        ref = _decode_reference(q, kc, vc, pos, d ** -0.5)
        got = flash_decode_paged(q, jnp.asarray(k_pool),
                                 jnp.asarray(v_pool), pt, pos,
                                 use_pallas=True, interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)
    qc = jax.random.normal(ks[0], (b, 4, h, d), jnp.float32)
    ref = _decode_reference(qc, kc, vc, 300, d ** -0.5)
    got = flash_decode_paged(qc, jnp.asarray(k_pool), jnp.asarray(v_pool),
                             pt, 300, use_pallas=True, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_decode_paged_deferred_self():
    """Deferred-write decode (self_kv): the pool holds positions < pos
    with stale garbage AT pos; the kernel must attend pool[0..pos-1] +
    the uncommitted self chunk, matching the committed-pool reference —
    scalar and ragged positions, including pos=0 (self only)."""
    from tfmesos_tpu.ops.attention import (_decode_reference,
                                           _paged_decode_reference,
                                           flash_decode_paged)

    ks = jax.random.split(jax.random.PRNGKey(7), 5)
    b, h, kv, d, ps, npg = 3, 4, 2, 32, 128, 4
    m = ps * npg
    q = jax.random.normal(ks[0], (b, h, d), jnp.float32)
    kc = jax.random.normal(ks[1], (b, kv, m, d), jnp.float32)
    vc = jax.random.normal(ks[2], (b, kv, m, d), jnp.float32)
    k_self = jax.random.normal(ks[3], (b, 1, kv, d), jnp.float32)
    v_self = jax.random.normal(ks[4], (b, 1, kv, d), jnp.float32)
    pt = jnp.asarray(np.arange(b * npg, dtype=np.int32).reshape(b, npg))
    pool = lambda c: c.reshape(b, kv, npg, ps, d).transpose(
        0, 2, 1, 3, 4).reshape(b * npg, kv, ps, d)
    k_pool, v_pool = pool(kc), pool(vc)
    for pos in (0, 5, 200, jnp.array([0, 130, 511], jnp.int32)):
        posv = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
        # Committed ground truth: self written at each row's position.
        put = jax.vmap(lambda c_, s_, p_: jax.lax.dynamic_update_slice(
            c_, s_[:, None], (0, p_, 0)))
        ref = _decode_reference(q, put(kc, k_self[:, 0], posv),
                                put(vc, v_self[:, 0], posv), pos,
                                d ** -0.5)
        got = flash_decode_paged(q, k_pool, v_pool, pt, pos,
                                 use_pallas=True, interpret=True,
                                 self_kv=(k_self, v_self))
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)
        # The gather-the-pages reference path takes the same self route.
        got_ref = _paged_decode_reference(q, k_pool, v_pool, pt, pos,
                                          d ** -0.5,
                                          self_kv=(k_self, v_self))
        np.testing.assert_allclose(np.asarray(got_ref), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    # int8 pools: the caller (transformer decode_step) pre-quantize-
    # dequantizes the self chunk, so the self operand matches a committed
    # slot up to rounding; the kernel's in-VMEM scale folds must agree
    # with dequantize-then-attend over the same pool.
    from tfmesos_tpu.ops.quant import (QTensor, quantize_int8_reference,
                                       quantize_tensor)

    qt_k, qt_v = quantize_tensor(kc), quantize_tensor(vc)
    kd, vd = qt_k.dequantize(jnp.float32), qt_v.dequantize(jnp.float32)
    lane = lambda qt: (   # [B,KV,M,1] scales -> pooled lane-major [P,KV,1,ps]
        qt.scales[..., 0].reshape(b, kv, npg, ps).transpose(0, 2, 1, 3)
        .reshape(b * npg, kv, ps)[:, :, None, :])
    k_pool8 = QTensor(pool(qt_k.values), jnp.asarray(lane(qt_k)))
    v_pool8 = QTensor(pool(qt_v.values), jnp.asarray(lane(qt_v)))
    rq = lambda c: (lambda v_, s_: v_.astype(jnp.float32)
                    * s_.astype(jnp.float32))(*quantize_int8_reference(c))
    k_self8, v_self8 = rq(k_self), rq(v_self)
    for pos in (5, jnp.array([0, 130, 511], jnp.int32)):
        posv = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
        put = jax.vmap(lambda c_, s_, p_: jax.lax.dynamic_update_slice(
            c_, s_[:, None], (0, p_, 0)))
        ref8 = _decode_reference(q, put(kd, k_self8[:, 0], posv),
                                 put(vd, v_self8[:, 0], posv), pos,
                                 d ** -0.5)
        got8 = flash_decode_paged(q, k_pool8, v_pool8, pt, pos,
                                  use_pallas=True, interpret=True,
                                  self_kv=(k_self8, v_self8))
        np.testing.assert_allclose(np.asarray(got8), np.asarray(ref8),
                                   rtol=2e-5, atol=2e-5)


# What a K/V block of several pages can get wrong (PR 31).  name ->
# (table width, rows' positions, scrambled page ids).  The block is held to
# 4 pages of 16 here (the rule's cap is patched, as is the budget where a
# case wants head_block < kv), so the widths are below a block (2), a block
# (4), not whole blocks (6, 10) and whole blocks (8); positions put a row's
# bound on a block's first page, on its last page, on the table's last
# entry, and at 0 -- a row with no live page (its self chunk alone) beside
# full rows; one table is wider than its longest row needs.
_PAGED_TABLES = {
    "w2": (2, (5, 0, 30), False),
    "w4_block": (4, (2, 62, 17), False),
    "w6_tail": (6, (65, 0, 94), False),
    "w6_scrambled": (6, (70, 94, 0, 33), True),
    "w8_two_blocks": (8, (63, 64, 126), True),
    "w10_scrambled": (10, (0, 158, 66, 127), True),
    "w10_short_rows": (10, (5, 70, 0), True),     # 1 + 2 + 1 steps of 9
}


@pytest.mark.parametrize("ps,kv,g,quantized,self_t,table", [
    # (page_size, kv heads, q_per_kv, int8 pools, fused self rows; 0 =
    # committed t=1 step; table: None = 4 pages in order, one block, else
    # a name in _PAGED_TABLES).  Sweeps the head-blocked grid (kv=1..4
    # hits head_block 1, 2, and 4 under the VMEM guard), the fused
    # multi-row step (K=4/8 — the speculative-verify shape) and blocks of
    # several pages over every table above.
    (16, 1, 4, False, 0, None),
    (16, 2, 2, False, 1, None),
    (16, 2, 2, False, 8, None),
    (32, 4, 1, False, 4, None),
    (16, 2, 2, True, 1, None),
    (16, 2, 2, True, 8, None),
    (32, 4, 2, True, 4, None),
    (128, 2, 2, False, 8, None),
    (16, 2, 2, False, 1, "w2"),
    (16, 2, 2, False, 1, "w4_block"),
    (16, 2, 2, False, 1, "w6_tail"),
    (16, 2, 2, False, 0, "w6_tail"),
    (16, 2, 2, False, 4, "w6_scrambled"),
    (16, 2, 2, True, 1, "w6_scrambled"),
    (16, 2, 2, True, 4, "w8_two_blocks"),
    (16, 2, 2, False, 1, "w8_two_blocks"),
    (16, 4, 1, False, 1, "w10_scrambled"),
    (16, 4, 1, False, 4, "w10_scrambled"),
    (16, 2, 2, False, 1, "w10_short_rows"),
    (16, 2, 2, True, 4, "w10_short_rows"),
    (16, 4, 2, False, 1, "w10_scrambled-hb2"),
    (16, 4, 2, True, 4, "w10_scrambled-hb2"),
], ids=lambda v: v if isinstance(v, str) else None if v is None else str(v))
def test_flash_decode_paged_equivalence_matrix(ps, kv, g, quantized,
                                               self_t, table, monkeypatch):
    """The paged kernel (head-parallel grid, K/V blocks of several pages,
    fused multi-row steps) vs the gather-the-pages reference across the
    config matrix: every cell must agree on the SAME pool — the
    bit-exactness bar every serving caller (int8, GQA, self_kv
    deferred decode, spec verify chunks) rides on."""
    from tfmesos_tpu.ops import attention
    from tfmesos_tpu.ops.attention import (_paged_decode_reference,
                                           flash_decode_paged)
    from tfmesos_tpu.ops.quant import (QTensor, quantize_int8_reference,
                                       quantize_tensor)

    ks = jax.random.split(jax.random.PRNGKey(11), 5)
    d, t = 32, max(1, self_t)
    h = kv * g
    if table is None:
        b, npg, positions, scrambled = 2, 4, None, False
    else:
        name, _, small_hb = table.partition("-")
        npg, positions, scrambled = _PAGED_TABLES[name]
        b = len(positions)
        monkeypatch.setattr(attention, "_PAGED_BLOCK_POSITIONS", 4 * ps)
        if small_hb:    # two heads' blocks of 4 pages fill the budget
            monkeypatch.setattr(attention, "_PAGED_VMEM_BUDGET",
                                4 * 2 * 4 * (ps * d * (1 if quantized else 4)
                                             + 4096 * quantized))
            assert attention._paged_block(
                kv, ps, d, 1 if quantized else 4, npg, quantized) == (2, 4)
        else:
            assert attention._paged_block(
                kv, ps, d, 1 if quantized else 4, npg,
                quantized)[1] == min(4, npg)
    m = ps * npg
    q = jax.random.normal(ks[0], (b, t, h, d), jnp.float32)
    kc = jax.random.normal(ks[1], (b, kv, m, d), jnp.float32)
    vc = jax.random.normal(ks[2], (b, kv, m, d), jnp.float32)
    # Row i's logical page j lives at pool page ids[i, j]: in order, or
    # scattered over a pool with pages to spare, so the pages of one
    # block are neither adjacent nor ascending.
    pool_n = b * npg + (7 if scrambled else 0)
    ids = (np.random.RandomState(3).permutation(pool_n)[:b * npg]
           if scrambled else np.arange(b * npg)).reshape(b, npg)

    def pool(c, width):     # [b, kv, m, width] -> [pool_n, kv, ps, width]
        out = np.zeros((pool_n, kv, ps, width), np.asarray(c).dtype)
        out[ids.reshape(-1)] = np.asarray(c).reshape(
            b, kv, npg, ps, width).transpose(0, 2, 1, 3, 4).reshape(
            b * npg, kv, ps, width)
        return jnp.asarray(out)

    if quantized:
        qt_k, qt_v = quantize_tensor(kc), quantize_tensor(vc)
        lane = lambda qt: jnp.swapaxes(pool(qt.scales, 1), -1, -2)
        k_pool = QTensor(pool(qt_k.values, d), lane(qt_k))
        v_pool = QTensor(pool(qt_v.values, d), lane(qt_v))
    else:
        k_pool, v_pool = pool(kc, d), pool(vc, d)
    pt = jnp.asarray(ids, jnp.int32)
    if self_t:
        rq = lambda c: (lambda v_, s_: v_.astype(jnp.float32)
                        * s_.astype(jnp.float32))(
            *quantize_int8_reference(c)) if quantized else c
        self_kv = (rq(jax.random.normal(ks[3], (b, t, kv, d),
                                        jnp.float32)),
                   rq(jax.random.normal(ks[4], (b, t, kv, d),
                                        jnp.float32)))
    else:
        self_kv = None
    hi = m - t if self_t else m - t - 1
    if positions is None:
        sweep = (jnp.array([0 if self_t else 1, hi], jnp.int32),
                 min(ps + 1, hi))
    else:       # a committed step attends its own slot: at least 1 position
        sweep = (jnp.asarray(np.clip(positions, 0 if self_t else 1, hi),
                             jnp.int32),)
    for pos in sweep:
        ref = _paged_decode_reference(q, k_pool, v_pool, pt, pos,
                                      d ** -0.5, self_kv=self_kv)
        got = flash_decode_paged(q, k_pool, v_pool, pt, pos,
                                 use_pallas=True, interpret=True,
                                 self_kv=self_kv)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("name,call,expect", [
    # (kv, page, head_dim, itemsize, table width[, int8]) -> (head block,
    # pages per block): 512 positions, the head block shrinking until
    # they fit the budget.
    ("mistral_docqa", (8, 64, 128, 2, 128), (8, 8)),     # 4 MB of blocks
    ("mistral_chat_w16", (8, 64, 128, 2, 16), (8, 8)),
    ("evabyte", (32, 64, 128, 2, 64), (16, 8)),          # 8 MB: the budget
    ("width_2", (8, 64, 128, 2, 2), (8, 2)),             # one step a row
    ("width_1", (8, 64, 128, 2, 1), (8, 1)),
    ("page_1024", (8, 1024, 128, 2, 8), (8, 1)),         # a value, not a mode
    ("page_128", (8, 128, 128, 2, 64), (8, 4)),
    ("page_16_f32", (2, 16, 32, 4, 4), (2, 4)),          # the tests' pools
    ("mistral_int8", (8, 64, 128, 1, 128, True), (8, 8)),
    ("evabyte_int8", (32, 64, 128, 1, 64, True), (16, 8)),
    ("wide_heads_f32", (8, 256, 256, 4, 32), (4, 2)),    # head block gives way
    ("huge_page_f32", (4, 1024, 256, 4, 8), (2, 1)),
    ("mqa", (1, 64, 128, 2, 128), (1, 8)),
])
def test_paged_block_rule(name, call, expect):
    """``_paged_block`` is a pure function of what a call sees; the
    double-buffered K + V blocks it picks always fit the budget."""
    from tfmesos_tpu.ops import attention

    hb, ppb = attention._paged_block(*call)
    assert (hb, ppb) == expect
    kv, ps, d, itemsize, width = call[:5]
    assert kv % hb == 0 and 1 <= ppb <= max(1, width)
    assert ppb * ps <= max(ps, attention._PAGED_BLOCK_POSITIONS)
    assert 4 * hb * ppb * ps * d * itemsize <= attention._PAGED_VMEM_BUDGET


@pytest.mark.parametrize("width,ppb,live", [
    (8, 4, (8, 0, 3, 0, 0, 5)),     # idle rows between live ones
    (6, 4, (6, 1, 0, 4)),           # a table not whole blocks wide
    (2, 2, (0, 2, 1)),              # the walk starts on an idle row
    (16, 8, (0, 0, 0)),             # nothing live: a step a row, no copy
    (5, 1, (5, 2, 0, 1)),           # one page a step
])
def test_paged_walk_moves_only_live_pages(width, ppb, live):
    """The grid walks a row's live blocks and one step of a row that has
    none, in row order, and flags a row's last step.  A slot's entry is
    the row's page while the row is live there and otherwise repeats
    what the slot fetched last on the walk, so the pipeline (which
    copies a slot when its index changes) moves each live page once and
    nothing else."""
    from tfmesos_tpu.ops.attention import _paged_walk

    rows = len(live)
    table = np.random.RandomState(5).permutation(rows * width).reshape(
        rows, width).astype(np.int32) + 1
    walk, fetch, total = _paged_walk(jnp.asarray(table),
                                     jnp.asarray(live, jnp.int32), ppb)
    n = rows * -(-width // ppb)
    walk, fetch = np.asarray(walk), np.asarray(fetch).reshape(n, ppb)
    assert walk.shape == (3, n)
    expect = [(r, j, j == max(1, -(-live[r] // ppb)) - 1)
              for r in range(rows)
              for j in range(max(1, -(-live[r] // ppb)))]
    assert int(total) == len(expect)
    held = [table[0, min(i, width - 1)] for i in range(ppb)]
    copies = 0
    for at, (r, j, last) in enumerate(expect):
        assert tuple(walk[:, at]) == (r, j, last)
        for i in range(ppb):
            e = j * ppb + i
            if e < live[r]:
                assert fetch[at, i] == table[r, e]
                copies += fetch[at, i] != held[i]
                held[i] = table[r, e]
            assert fetch[at, i] == held[i]     # dead: no change, no copy
    assert copies <= sum(live)


def test_stacked_cache_static_zero_layer_with_4d_cache():
    """A statically-zero layer index — python 0, numpy int32(0), a 0-d
    concrete array — over a 4-D (single-layer) cache must be accepted via
    the L=1 lift (operator.index), not spuriously rejected; a nonzero or
    traced index still needs the stacked 5-D cache."""
    from tfmesos_tpu.ops.attention import _decode_reference, flash_decode

    q, kc, vc = _decode_inputs(m=256)
    ref = _decode_reference(q, kc, vc, 100, q.shape[-1] ** -0.5)
    # Kernel path once (the scalar-prefetch consumer of the index) ...
    got = flash_decode(q, kc, vc, 100, layer=np.int32(0), use_pallas=True,
                       interpret=True, block_m=128)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    # ... and the cheap reference path for the other statically-zero forms.
    for zero in (0, np.int64(0), jnp.asarray(0, jnp.int32)):
        got = flash_decode(q, kc, vc, 100, layer=zero, use_pallas=False)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)
    for bad in (1, np.int32(2)):
        with pytest.raises(ValueError, match="stacked 5-D cache"):
            flash_decode(q, kc, vc, 100, layer=bad, use_pallas=False)

    def traced(li):
        return flash_decode(q, kc, vc, 100, layer=li, use_pallas=False)

    with pytest.raises(ValueError, match="stacked 5-D cache"):
        jax.jit(traced)(jnp.asarray(0, jnp.int32))
