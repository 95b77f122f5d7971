"""Fused head+cross-entropy (ops/layers.fused_linear_cross_entropy):
chunked loss/grads must match the materialize-the-logits reference exactly
(same fp32 reduction math, different grouping), and the transformer's
loss_fn must auto-select it only where it is the right call.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map

from tfmesos_tpu.models import transformer
from tfmesos_tpu.ops.layers import cross_entropy_loss, fused_linear_cross_entropy
from tfmesos_tpu.parallel.mesh import build_mesh


def _ref_loss(x, w, labels, z_loss=0.0):
    logits = x @ w.astype(x.dtype)
    return cross_entropy_loss(logits, labels, z_loss=z_loss)


@pytest.mark.parametrize("z_loss", [0.0, 1e-3])
@pytest.mark.parametrize("chunk", [7, 16, 1000])
def test_fused_ce_matches_reference_loss_and_grads(z_loss, chunk):
    d, v = 16, 37
    x = jax.random.normal(jax.random.PRNGKey(0), (3, 8, d), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (d, v), jnp.float32) * 0.3
    labels = jax.random.randint(jax.random.PRNGKey(2), (3, 8), 0, v)

    ref, (dx_ref, dw_ref) = jax.value_and_grad(_ref_loss, argnums=(0, 1))(
        x, w, labels, z_loss)
    got, (dx, dw) = jax.value_and_grad(
        lambda x_, w_: fused_linear_cross_entropy(x_, w_, labels, z_loss,
                                                  chunk),
        argnums=(0, 1))(x, w)

    np.testing.assert_allclose(float(got), float(ref), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(dx), np.asarray(dx_ref),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(dw), np.asarray(dw_ref),
                               rtol=1e-5, atol=1e-6)


def test_fused_ce_bf16_inputs_fp32_master_weight():
    """The model path: bf16 hidden states, fp32 master head — compute runs
    in bf16 (weight cast at the matmul, as the standard path does) but dw
    accumulates fp32 and returns at the master dtype."""
    d, v = 32, 64
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 6, d)).astype(jnp.bfloat16)
    w = jax.random.normal(jax.random.PRNGKey(1), (d, v), jnp.float32) * 0.2
    labels = jax.random.randint(jax.random.PRNGKey(2), (4, 6), 0, v)

    ref, (dx_ref, dw_ref) = jax.value_and_grad(_ref_loss, argnums=(0, 1))(
        x, w, labels)
    got, (dx, dw) = jax.value_and_grad(
        lambda x_, w_: fused_linear_cross_entropy(x_, w_, labels),
        argnums=(0, 1))(x, w)

    assert dx.dtype == jnp.bfloat16 and dw.dtype == jnp.float32
    np.testing.assert_allclose(float(got), float(ref), rtol=5e-3)
    np.testing.assert_allclose(np.asarray(dx, dtype=np.float32),
                               np.asarray(dx_ref, dtype=np.float32),
                               rtol=0.1, atol=5e-4)
    np.testing.assert_allclose(np.asarray(dw), np.asarray(dw_ref),
                               rtol=0.1, atol=5e-4)


TINY = transformer.TransformerConfig(
    vocab_size=64, d_model=32, n_layers=2, n_heads=2, d_ff=64,
    max_seq_len=32, dtype=jnp.float32)


def test_loss_fn_fused_matches_standard():
    params = transformer.init_params(TINY, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 17), 0,
                                TINY.vocab_size)
    batch = {"tokens": tokens}
    import dataclasses
    fused_cfg = dataclasses.replace(TINY, fused_ce=True, ce_chunk=8)
    plain_cfg = dataclasses.replace(TINY, fused_ce=False)

    l_fused, (g_fused,) = jax.value_and_grad(
        lambda p: transformer.loss_fn(fused_cfg, p, batch)[0], argnums=(0,))(
        params)
    l_plain, (g_plain,) = jax.value_and_grad(
        lambda p: transformer.loss_fn(plain_cfg, p, batch)[0], argnums=(0,))(
        params)

    np.testing.assert_allclose(float(l_fused), float(l_plain), rtol=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(g_fused),
                    jax.tree_util.tree_leaves(g_plain)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=1e-6)


def test_fused_ce_mode_auto_selection():
    params = transformer.init_params(TINY, jax.random.PRNGKey(0))
    mode = transformer._fused_ce_mode
    assert mode(TINY, params, None) == "dense"
    # Multi-device data-only meshes take the batch-sharded path: the dense
    # chunking would cut every chunk across the dp sharding.  The shard_map
    # needs the batch to divide over the data axes — indivisible (or
    # unknown) batches keep the GSPMD dense route.
    assert mode(TINY, params, build_mesh({"dp": 8}), batch_size=8) == "dp"
    assert mode(TINY, params, build_mesh({"dp": 8}), batch_size=6) == "dense"
    assert mode(TINY, params, build_mesh({"dp": 8})) == "dense"
    assert mode(TINY, params, build_mesh({"dp": 4, "fsdp": 2}),
                batch_size=16) == "dp"
    assert mode(TINY, params, build_mesh({"dp": 4, "tp": 2})) == "tp"
    assert mode(TINY, params, build_mesh({"sp": 8})) is None
    assert mode(TINY, params, build_mesh({"pp": 2, "dp": 4})) is None
    # Size-1 axes don't count: a degenerate tp axis is still data-only.
    assert mode(TINY, params, build_mesh({"dp": 8, "tp": 1}),
                batch_size=8) == "dp"
    qparams = transformer.quantize_params(TINY, params)
    assert mode(TINY, qparams, None) is None


def test_loss_fn_tp_mesh_matches_single_device():
    """The vocab-parallel path through loss_fn: loss AND grads on a
    dp x tp mesh must match the meshless (fused-dense) run."""
    mesh = build_mesh({"dp": 4, "tp": 2})
    params = transformer.init_params(TINY, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 17), 0,
                                TINY.vocab_size)
    batch = {"tokens": tokens}
    assert transformer._fused_ce_mode(TINY, params, mesh) == "tp"

    ref, g_ref = jax.value_and_grad(
        lambda p: transformer.loss_fn(TINY, p, batch)[0])(params)
    got, g = jax.jit(jax.value_and_grad(
        lambda p: transformer.loss_fn(TINY, p, batch, mesh)[0]))(params)

    np.testing.assert_allclose(float(got), float(ref), rtol=1e-5)
    for (pa, a), (_, b) in zip(
            jax.tree_util.tree_flatten_with_path(g)[0],
            jax.tree_util.tree_flatten_with_path(g_ref)[0]):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=1e-5, err_msg=str(pa))


@pytest.mark.parametrize("axes", [{"tp": 8}, {"dp": 2, "tp": 4},
                                  {"dp": 2, "fsdp": 2, "tp": 2}])
def test_vocab_parallel_ce_matches_reference(axes):
    from tfmesos_tpu.ops.layers import vocab_parallel_cross_entropy
    mesh = build_mesh(axes)
    d, v = 16, 64
    nb = axes.get("dp", 1) * axes.get("fsdp", 1)
    x = jax.random.normal(jax.random.PRNGKey(0), (2 * nb, 8, d), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (d, v), jnp.float32) * 0.3
    labels = jax.random.randint(jax.random.PRNGKey(2), (2 * nb, 8), 0, v)

    ref, (dx_ref, dw_ref) = jax.value_and_grad(_ref_loss, argnums=(0, 1))(
        x, w, labels, 1e-3)
    got, (dx, dw) = jax.jit(jax.value_and_grad(
        lambda x_, w_: vocab_parallel_cross_entropy(
            x_, w_, labels, mesh, z_loss=1e-3, chunk=8),
        argnums=(0, 1)))(x, w)

    np.testing.assert_allclose(float(got), float(ref), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(dx), np.asarray(dx_ref),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(dw), np.asarray(dw_ref),
                               rtol=1e-5, atol=1e-6)


def test_vocab_parallel_ce_through_trainer_machinery():
    """The tp fused-CE path composed with the full trainer stack:
    make_train_step with steps_per_call > 1 AND grad_accum > 1 on a
    dp x tp mesh must train (finite, decreasing-ish loss) — custom VJPs
    inside shard_maps inside scan inside scan inside jit."""
    import optax

    from tfmesos_tpu.train.trainer import make_train_step

    mesh = build_mesh({"dp": 4, "tp": 2})
    params = transformer.init_params(TINY, jax.random.PRNGKey(0))
    assert transformer._fused_ce_mode(TINY, params, mesh) == "tp"
    opt = optax.adamw(3e-3)
    step = make_train_step(
        lambda p, b: transformer.loss_fn(TINY, p, b, mesh), opt, mesh=mesh,
        param_specs=transformer.partition_specs(TINY, mesh),
        steps_per_call=2, grad_accum=2)
    params, opt_state = step.place(params, opt.init(params))

    rng = np.random.RandomState(0)
    losses = []
    for _ in range(6):
        batch = {"tokens": rng.randint(0, TINY.vocab_size,
                                       size=(2, 8, 17)).astype(np.int32)}
        params, opt_state, metrics = step(params, opt_state, batch)
        losses.append(float(metrics["loss"]))
    assert all(np.isfinite(l) for l in losses)
    assert losses[-1] < losses[0]


@pytest.mark.parametrize("axes", [{"dp": 8}, {"dp": 2, "fsdp": 4}])
def test_dp_fused_ce_matches_reference(axes):
    """The batch-sharded fused CE: loss AND grads on data-parallel meshes
    must match the materialize-the-logits reference."""
    from tfmesos_tpu.ops.layers import data_parallel_fused_cross_entropy
    mesh = build_mesh(axes)
    d, v = 16, 64
    x = jax.random.normal(jax.random.PRNGKey(0), (16, 8, d), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (d, v), jnp.float32) * 0.3
    labels = jax.random.randint(jax.random.PRNGKey(2), (16, 8), 0, v)

    ref, (dx_ref, dw_ref) = jax.value_and_grad(_ref_loss, argnums=(0, 1))(
        x, w, labels, 1e-3)
    got, (dx, dw) = jax.jit(jax.value_and_grad(
        lambda x_, w_: data_parallel_fused_cross_entropy(
            x_, w_, labels, mesh, 1e-3, 8),
        argnums=(0, 1)))(x, w)

    np.testing.assert_allclose(float(got), float(ref), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(dx), np.asarray(dx_ref),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(dw), np.asarray(dw_ref),
                               rtol=1e-5, atol=1e-6)


def test_fused_ce_on_dp_mesh_matches_single_device():
    """loss_fn's auto "dp" route end to end: loss and grads on a dp mesh
    must match the meshless (fused-dense) run."""
    mesh = build_mesh({"dp": 8})
    params = transformer.init_params(TINY, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 17), 0,
                                TINY.vocab_size)
    batch = {"tokens": tokens}
    assert transformer._fused_ce_mode(TINY, params, mesh,
                                      batch_size=8) == "dp"
    ref, g_ref = jax.value_and_grad(
        lambda p: transformer.loss_fn(TINY, p, batch)[0])(params)
    got, g = jax.jit(jax.value_and_grad(
        lambda p: transformer.loss_fn(TINY, p, batch, mesh)[0]))(params)
    np.testing.assert_allclose(float(got), float(ref), rtol=1e-5)
    for (pa, a), (_, b) in zip(
            jax.tree_util.tree_flatten_with_path(g)[0],
            jax.tree_util.tree_flatten_with_path(g_ref)[0]):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=1e-5, err_msg=str(pa))


@pytest.mark.parametrize("z_loss", [0.0, 1e-3])
def test_vocab_parallel_ce_inbody_matches_reference(z_loss):
    """The in-body vocab-parallel CE (the 1F1B loss tail): called INSIDE
    a shard_map with the head vocab-sharded, loss and in-body-vjp grads
    must match the dense reference."""
    from jax.sharding import PartitionSpec as P

    from tfmesos_tpu.ops.layers import vocab_parallel_ce_inbody

    d, v = 16, 64
    mesh = build_mesh({"tp": 8})
    x = jax.random.normal(jax.random.PRNGKey(0), (3, 8, d), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (d, v), jnp.float32) * 0.3
    labels = jax.random.randint(jax.random.PRNGKey(2), (3, 8), 0, v)

    ref, (dx_ref, dw_ref) = jax.value_and_grad(
        _ref_loss, argnums=(0, 1))(x, w, labels, z_loss)

    def local(xl, wl, ll):
        # In-body vjp, exactly as the 1F1B backward runs it.
        loss, vjp = jax.vjp(
            lambda x_, w_: vocab_parallel_ce_inbody(x_, w_, ll, "tp",
                                                    z_loss, 16), xl, wl)
        dx, dw = vjp(jnp.ones((), jnp.float32))
        return loss, dx, dw

    loss, dx, dw = shard_map(
        local, mesh=mesh, in_specs=(P(), P(None, "tp"), P()),
        out_specs=(P(), P(), P(None, "tp")), check_vma=False)(x, w, labels)
    np.testing.assert_allclose(float(loss), float(ref), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(dx), np.asarray(dx_ref),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(dw), np.asarray(dw_ref),
                               rtol=1e-5, atol=1e-6)


def test_lm_z_loss_consistent_across_paths():
    """cfg.z_loss (LM-head logit stabilizer) must produce the same loss on
    the unfused, fused-dense, dp-sharded, and tp vocab-parallel routes,
    and actually move the objective."""
    import dataclasses

    cfg = dataclasses.replace(TINY, z_loss=1e-3)
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 17), 0,
                                TINY.vocab_size)
    batch = {"tokens": tokens}
    base = float(transformer.loss_fn(
        dataclasses.replace(cfg, fused_ce=False), params, batch)[0])
    for mesh in (None, build_mesh({"dp": 8}), build_mesh({"dp": 4, "tp": 2})):
        got = float(jax.jit(lambda p, b, m=mesh: transformer.loss_fn(
            cfg, p, b, m)[0])(params, batch))
        np.testing.assert_allclose(got, base, rtol=1e-5)
    plain = float(transformer.loss_fn(
        dataclasses.replace(cfg, z_loss=0.0), params, batch)[0])
    assert base > plain
