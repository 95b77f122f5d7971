import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

from tfmesos_tpu.models import matrix_factorization as nmf
from tfmesos_tpu.models import mlp, transformer
from tfmesos_tpu.parallel.mesh import build_mesh
from tfmesos_tpu.train import data as datalib
from tfmesos_tpu.train.trainer import TrainLoop, TrainState, make_train_step

TINY = transformer.TransformerConfig(
    vocab_size=64, d_model=32, n_layers=2, n_heads=2, d_ff=64,
    max_seq_len=32, dtype=jnp.float32)


def test_transformer_forward_shape_and_loss():
    params = transformer.init_params(TINY, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 17), 0, TINY.vocab_size)
    logits = transformer.forward(TINY, params, tokens[:, :-1])
    assert logits.shape == (2, 16, TINY.vocab_size)
    loss, aux = transformer.loss_fn(TINY, params, {"tokens": tokens})
    assert np.isfinite(float(loss)) and float(loss) > 0
    assert "perplexity" in aux


def test_transformer_sp_mesh_matches_single_device():
    mesh = build_mesh({"sp": 8})
    params = transformer.init_params(TINY, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, TINY.vocab_size)
    ref = transformer.forward(TINY, params, tokens)
    got = jax.jit(lambda p, t: transformer.forward(TINY, p, t, mesh))(params, tokens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_transformer_pp_matches_sequential():
    mesh = build_mesh({"pp": 2, "dp": 4})
    params = transformer.init_params(TINY, jax.random.PRNGKey(0))
    # batch must split into microbatches (=pp stages) x dp shards
    tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0, TINY.vocab_size)
    ref = transformer.forward(TINY, params, tokens)
    got = jax.jit(lambda p, t: transformer.forward(TINY, p, t, mesh))(params, tokens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_transformer_moe_forward_and_specs():
    cfg = transformer.TransformerConfig(
        vocab_size=64, d_model=32, n_layers=2, n_heads=2, d_ff=64,
        n_experts=4, top_k=2, dtype=jnp.float32)
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, 64)
    logits = transformer.forward(cfg, params, tokens)
    assert logits.shape == (2, 16, 64)
    mesh = build_mesh({"ep": 4, "dp": 2})
    specs = transformer.partition_specs(cfg, mesh)
    assert specs["layers"]["e_gate"] == P(None, "ep", None, None)
    # axes absent from the mesh are dropped
    assert specs["layers"]["wq"] == P(None, None, None)


def test_transformer_switch_moe_on_ep_mesh():
    cfg = transformer.TransformerConfig(
        vocab_size=64, d_model=32, n_layers=2, n_heads=2, d_ff=64,
        n_experts=4, moe_impl="switch", dtype=jnp.float32)
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    mesh = build_mesh({"ep": 4, "dp": 2})
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 17), 0, 64)
    loss, aux = jax.jit(
        lambda p, b: transformer.loss_fn(cfg, p, b, mesh))(
        params, {"tokens": tokens})
    assert np.isfinite(float(loss))
    # And it trains: gradients through the all_to_all dispatch.
    g = jax.jit(jax.grad(
        lambda p: transformer.loss_fn(cfg, p, {"tokens": tokens}, mesh)[0]))(
        params)
    norm = sum(float(jnp.sum(jnp.abs(x)))
               for x in jax.tree_util.tree_leaves(g))
    assert np.isfinite(norm) and norm > 0


def test_transformer_partition_specs_tp_fsdp():
    cfg = TINY
    mesh = build_mesh({"fsdp": 2, "tp": 2, "dp": 2})
    specs = transformer.partition_specs(cfg, mesh)
    assert specs["layers"]["wq"] == P(None, "fsdp", "tp")
    assert specs["layers"]["wo"] == P(None, "tp", "fsdp")
    assert specs["embed"] == P("tp", "fsdp")


def test_transformer_trains():
    params = transformer.init_params(TINY, jax.random.PRNGKey(0))
    opt = optax.adamw(3e-3)
    step = make_train_step(
        lambda p, b: transformer.loss_fn(TINY, p, b), opt)
    batches = datalib.token_batches(8, 16, TINY.vocab_size, seed=0)
    state = TrainState(params, opt.init(params))
    loop = TrainLoop(step, state, log_every=1000)
    first = transformer.loss_fn(TINY, params, next(batches))[0]
    result = loop.run(batches, 30)
    assert result["final_metrics"]["loss"] < float(first)


def test_mlp_converges_on_synthetic_mnist():
    cfg = mlp.MLPConfig()
    params = mlp.init_params(cfg, jax.random.PRNGKey(0))
    opt = optax.sgd(0.1)  # reference lr is 0.01 (mnist_replica.py:71); 0.1 converges faster
    step = make_train_step(lambda p, b: mlp.loss_fn(cfg, p, b), opt)
    ds = datalib.SyntheticMNIST()
    loop = TrainLoop(step, TrainState(params, opt.init(params)), log_every=1000)
    # Reference workload scale: 200 steps, batch 100 (mnist_replica.py:70-73)
    loop.run(ds.batches(100), 200)
    ev = ds.eval_batch(512)
    _, aux = mlp.loss_fn(cfg, loop.state.params, ev)
    assert float(aux["accuracy"]) > 0.9


def test_scanned_steps_match_sequential():
    """steps_per_call=K must produce bit-identical params to K sequential
    single-step calls on the same batches."""
    cfg = mlp.MLPConfig(in_dim=16, hidden=8, n_classes=4)
    ds = datalib.SyntheticMNIST(n_classes=4, dim=16)
    opt = optax.sgd(0.1)
    k = 4
    gen = ds.batches(8, seed=3)
    batches = [next(gen) for _ in range(k)]

    # Fresh init per phase: the jit'd steps donate their buffers.
    seq_step = make_train_step(lambda p, b: mlp.loss_fn(cfg, p, b), opt)
    p_seq = mlp.init_params(cfg, jax.random.PRNGKey(0))
    o_seq = opt.init(p_seq)
    for b in batches:
        p_seq, o_seq, m_seq = seq_step(p_seq, o_seq, b)

    import numpy as onp
    stacked = {key: onp.stack([b[key] for b in batches]) for key in batches[0]}
    scan_step = make_train_step(lambda p, b: mlp.loss_fn(cfg, p, b), opt,
                                steps_per_call=k)
    p0 = mlp.init_params(cfg, jax.random.PRNGKey(0))
    p_scan, o_scan, m_scan = scan_step(p0, opt.init(p0), stacked)

    for a, e in zip(jax.tree_util.tree_leaves(p_scan),
                    jax.tree_util.tree_leaves(p_seq)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(e),
                                   rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(float(m_scan["loss"]), float(m_seq["loss"]),
                               rtol=1e-6)


def test_scanned_steps_with_explicit_batch_spec():
    """steps_per_call>1 + an explicit (per-step) batch spec: the spec is
    lifted over the steps dim, sharding B rather than K."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    mesh = build_mesh({"dp": 8})
    cfg = mlp.MLPConfig(in_dim=16, hidden=8, n_classes=4)
    opt = optax.sgd(0.1)
    step = make_train_step(lambda p, b: mlp.loss_fn(cfg, p, b), opt,
                           mesh=mesh,
                           batch_spec_tree=NamedSharding(mesh, P(("dp",))),
                           steps_per_call=3)  # K=3 does NOT divide dp=8
    params, opt_state = step.place(mlp.init_params(cfg, jax.random.PRNGKey(0)),
                                   opt.init(mlp.init_params(
                                       cfg, jax.random.PRNGKey(0))))
    ds = datalib.SyntheticMNIST(n_classes=4, dim=16)
    gen = ds.batches(16, seed=5)
    ms = [next(gen) for _ in range(3)]
    stacked = {k: np.stack([m[k] for m in ms]) for k in ms[0]}
    params, opt_state, metrics = step(params, opt_state, stacked)
    assert np.isfinite(float(metrics["loss"]))


def test_mlp_sharded_train_step_on_mesh():
    mesh = build_mesh({"dp": 8})
    cfg = mlp.MLPConfig()
    params = mlp.init_params(cfg, jax.random.PRNGKey(0))
    opt = optax.sgd(0.1)
    step = make_train_step(lambda p, b: mlp.loss_fn(cfg, p, b), opt, mesh=mesh)
    params, opt_state = step.place(params, opt.init(params))
    ds = datalib.SyntheticMNIST()
    batch = next(ds.batches(64))
    params2, opt_state, metrics = step(params, opt_state, batch)
    assert np.isfinite(float(metrics["loss"]))


def test_nmf_converges():
    cfg = nmf.NMFConfig(rows=64, cols=64, rank=8)
    params = nmf.init_params(cfg, jax.random.PRNGKey(0))
    v = datalib.nmf_matrix(64, 64, 8)
    opt = optax.adam(1e-2)
    step = make_train_step(lambda p, b: nmf.loss_fn(cfg, p, b), opt,
                           postprocess=nmf.project_nonnegative)
    state = TrainState(params, opt.init(params))
    batch = {"V": jnp.asarray(v)}
    first = float(nmf.loss_fn(cfg, params, batch)[0])
    loop = TrainLoop(step, state, log_every=1000)
    result = loop.run(iter(lambda: batch, None), 100)
    assert result["final_metrics"]["loss"] < first * 0.1
    assert float(jnp.min(loop.state.params["W"])) >= 0.0


def test_nmf_partition_specs():
    cfg = nmf.NMFConfig()
    mesh = build_mesh({"fsdp": 8})
    specs = nmf.partition_specs(cfg, mesh)
    assert specs["W"] == P("fsdp", None)
    assert specs["H"] == P(None, "fsdp")


def test_switch_moe_topk_aux_metrics_in_loss():
    """top_k=2 switch path: aux losses join the objective and the overflow
    fraction surfaces in metrics (VERDICT round-1 weakness #6)."""
    cfg = transformer.TransformerConfig(
        vocab_size=64, d_model=32, n_layers=2, n_heads=2, d_ff=64,
        n_experts=4, top_k=2, moe_impl="switch", dtype=jnp.float32)
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    mesh = build_mesh({"ep": 4, "dp": 2})
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 17), 0, 64)
    loss, metrics = jax.jit(
        lambda p, b: transformer.loss_fn(cfg, p, b, mesh))(
        params, {"tokens": tokens})
    assert np.isfinite(float(loss))
    for key in ("load_balance_loss", "router_z_loss", "moe_overflow_frac"):
        assert np.isfinite(float(metrics[key])), key
    assert 0.0 <= float(metrics["moe_overflow_frac"]) < 1.0
    # load-balance loss is ~1 at perfect balance and can't go below 1/E*E=1
    # times the Cauchy-Schwarz bound; a fresh random router sits near 1.
    assert 0.5 < float(metrics["load_balance_loss"]) < 4.0
    # And the aux term really reaches the router's gradient.
    g = jax.jit(jax.grad(
        lambda p: transformer.loss_fn(cfg, p, {"tokens": tokens}, mesh)[0]))(
        params)
    assert float(jnp.sum(jnp.abs(g["layers"]["router"]))) > 0


def test_transformer_pp_tp_dp_matches_sequential():
    """pp2 x tp2 x dp2 on the 8-device mesh: pipeline stages with manual-
    collective tensor parallelism inside (VERDICT round-1 weakness #7)."""
    mesh = build_mesh({"pp": 2, "tp": 2, "dp": 2})
    params = transformer.init_params(TINY, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0,
                                TINY.vocab_size)
    ref = transformer.forward(TINY, params, tokens)
    got = jax.jit(lambda p, t: transformer.forward(TINY, p, t, mesh))(
        params, tokens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_transformer_pp_circular_schedule():
    cfg = transformer.TransformerConfig(
        vocab_size=TINY.vocab_size, d_model=TINY.d_model, n_layers=4,
        n_heads=TINY.n_heads, d_ff=TINY.d_ff, max_seq_len=TINY.max_seq_len,
        dtype=jnp.float32, pp_schedule="circular", pp_virtual_stages=2)
    mesh = build_mesh({"pp": 2, "dp": 4})
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0,
                                cfg.vocab_size)
    ref = transformer.forward(cfg, params, tokens)
    got = jax.jit(lambda p, t: transformer.forward(cfg, p, t, mesh))(
        params, tokens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_decode_matches_forward_logits():
    """Prefill + incremental KV-cache decode must reproduce forward()'s
    logits position by position (same params, same tokens) — the exactness
    contract for dense configs (switch MoE is exact only up to capacity
    overflow; see decode_step's docstring)."""
    params = transformer.init_params(TINY, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 12), 0,
                                TINY.vocab_size)
    full = transformer.forward(TINY, params, tokens)

    # One-shot prefill of the whole sequence.
    cache = transformer.init_cache(TINY, 2, 16)
    logits, cache = transformer.decode_step(TINY, params, cache, tokens, 0)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(full),
                               rtol=2e-4, atol=2e-4)

    # Prefill half, then token-by-token: logits must still match.
    cache = transformer.init_cache(TINY, 2, 16)
    logits, cache = transformer.decode_step(TINY, params, cache,
                                            tokens[:, :6], 0)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(full[:, :6]),
                               rtol=2e-4, atol=2e-4)
    for i in range(6, 12):
        step_logits, cache = transformer.decode_step(
            TINY, params, cache, tokens[:, i:i + 1], i)
        np.testing.assert_allclose(np.asarray(step_logits[:, 0]),
                                   np.asarray(full[:, i]),
                                   rtol=2e-4, atol=2e-4)


def test_generate_greedy_is_consistent():
    """Greedy generation continues the prompt with exactly the argmax of
    forward() at each position (the KV path agrees with the full recompute),
    and jits end-to-end."""
    params = transformer.init_params(TINY, jax.random.PRNGKey(0))
    prompt = jax.random.randint(jax.random.PRNGKey(2), (2, 4), 0,
                                TINY.vocab_size)
    out = jax.jit(lambda p, t: transformer.generate(TINY, p, t, 6))(
        params, prompt)
    assert out.shape == (2, 10)
    assert np.array_equal(np.asarray(out[:, :4]), np.asarray(prompt))
    # Verify against the cache-free recompute: each new token is the argmax
    # of forward() over the sequence so far.
    seq = np.asarray(prompt)
    for i in range(6):
        logits = transformer.forward(TINY, params, jnp.asarray(seq))
        nxt = np.asarray(jnp.argmax(logits[:, -1], axis=-1))
        seq = np.concatenate([seq, nxt[:, None]], axis=1)
    assert np.array_equal(np.asarray(out), seq)


def test_sample_logits_top_k_top_p():
    rng = jax.random.PRNGKey(0)
    # A peaked distribution: token 3 dominates, then 7, then noise.
    logits = jnp.array([0.0, 1.0, 0.5, 8.0, 0.2, 0.1, 0.3, 6.0] * 2
                       ).reshape(2, 8)[:, :8]
    keys = jax.random.split(rng, 200)

    # temperature<=0 is exact argmax regardless of truncation knobs
    out = transformer.sample_logits(logits, keys[0], temperature=0.0,
                                    top_k=2, top_p=0.5)
    np.testing.assert_array_equal(np.asarray(out), [3, 3])

    # top_k=1 == greedy even at high temperature
    for k in keys[:20]:
        out = transformer.sample_logits(logits, k, temperature=5.0, top_k=1)
        np.testing.assert_array_equal(np.asarray(out), [3, 3])

    # top_k=2 only ever emits the two best tokens {3, 7}
    draws = np.stack([np.asarray(transformer.sample_logits(
        logits, k, temperature=3.0, top_k=2)) for k in keys])
    assert set(np.unique(draws)) <= {3, 7}
    assert len(set(np.unique(draws))) == 2  # and both actually occur

    # tight top_p keeps only the dominating token; loose top_p ~ unfiltered
    draws = np.stack([np.asarray(transformer.sample_logits(
        logits, k, temperature=1.0, top_p=0.5)) for k in keys[:20]])
    assert set(np.unique(draws)) == {3}
    a = transformer.sample_logits(logits, keys[0], temperature=2.0)
    b = transformer.sample_logits(logits, keys[0], temperature=2.0,
                                  top_p=1.0, top_k=8)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    # degenerate knob values fail loudly, not with trace-time shape errors
    with pytest.raises(ValueError):
        transformer.sample_logits(logits, keys[0], top_k=0)
    with pytest.raises(ValueError):
        transformer.sample_logits(logits, keys[0], top_p=0.0)


def test_generate_with_sampling_knobs():
    cfg = TINY
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    prompt = jax.random.randint(jax.random.PRNGKey(1), (2, 5), 0,
                                cfg.vocab_size)
    out = transformer.generate(cfg, params, prompt, 6,
                               rng=jax.random.PRNGKey(2), temperature=0.9,
                               top_k=10, top_p=0.9)
    assert out.shape == (2, 11)
    assert (np.asarray(out) >= 0).all() and (
        np.asarray(out) < cfg.vocab_size).all()


def test_generate_moe_model():
    cfg = transformer.TransformerConfig(
        vocab_size=64, d_model=32, n_layers=2, n_heads=2, d_ff=64,
        n_experts=4, top_k=2, moe_impl="switch", dtype=jnp.float32,
        capacity_factor=4.0)
    params = transformer.init_params(cfg, jax.random.PRNGKey(3))
    prompt = jax.random.randint(jax.random.PRNGKey(4), (1, 3), 0, 64)
    out = transformer.generate(cfg, params, prompt, 4, temperature=1.0,
                               rng=jax.random.PRNGKey(5))
    assert out.shape == (1, 7)
    assert np.all((np.asarray(out) >= 0) & (np.asarray(out) < 64))
    # Zero-budget generation returns the prompt unchanged.
    same = transformer.generate(cfg, params, prompt, 0)
    assert np.array_equal(np.asarray(same), np.asarray(prompt))


def test_grad_accum_matches_full_batch_step():
    """grad_accum=A must produce the same update as the full-batch step
    (mean of equal-size microbatch grads == full-batch grad)."""
    import optax
    from tfmesos_tpu.models import mlp
    from tfmesos_tpu.train.trainer import make_train_step

    cfg = mlp.MLPConfig(in_dim=16, hidden=8, n_classes=4)
    opt = optax.adam(0.01)
    batch = {
        "image": jax.random.normal(jax.random.PRNGKey(1), (32, 16)),
        "label": jax.random.randint(jax.random.PRNGKey(2), (32,), 0, 4),
    }
    # Fresh init per call: the jit'd steps donate their buffers.
    full = make_train_step(lambda p, b: mlp.loss_fn(cfg, p, b), opt)
    accum = make_train_step(lambda p, b: mlp.loss_fn(cfg, p, b), opt,
                            grad_accum=4)
    pa = mlp.init_params(cfg, jax.random.PRNGKey(0))
    pb = mlp.init_params(cfg, jax.random.PRNGKey(0))
    p1, _, m1 = full(pa, opt.init(pa), batch)
    p2, _, m2 = accum(pb, opt.init(pb), batch)
    np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]),
                               rtol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(p1),
                    jax.tree_util.tree_leaves(p2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


def test_grad_accum_composes_with_steps_per_call_and_mesh():
    import optax
    from tfmesos_tpu.models import mlp
    from tfmesos_tpu.parallel.mesh import build_mesh
    from tfmesos_tpu.parallel.sharding import make_global_batch
    from tfmesos_tpu.train.trainer import make_train_step

    mesh = build_mesh({"dp": 8})
    cfg = mlp.MLPConfig(in_dim=16, hidden=8, n_classes=4)
    params = mlp.init_params(cfg, jax.random.PRNGKey(0))
    opt = optax.sgd(0.1)
    step = make_train_step(lambda p, b: mlp.loss_fn(cfg, p, b), opt,
                           mesh=mesh, steps_per_call=2, grad_accum=2)
    params, opt_state = step.place(params, opt.init(params))
    batch = make_global_batch(mesh, {
        "image": np.random.RandomState(0).randn(2, 32, 16).astype(np.float32),
        "label": np.random.RandomState(1).randint(0, 4, (2, 32)),
    }, batch_dim=1)
    params, opt_state, metrics = step(params, opt_state, batch)
    assert np.isfinite(float(metrics["loss"]))


def test_sharded_decode_matches_single_device():
    """GSPMD decode: params placed per partition_specs and the cache per
    cache_specs on a dp4 x tp2 mesh; jit'd decode_step(sharded=True) must
    reproduce the single-device logits (XLA inserts the tp collectives)."""
    from jax.sharding import NamedSharding

    mesh = build_mesh({"dp": 4, "tp": 2})
    params = transformer.init_params(TINY, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 8), 0,
                                TINY.vocab_size)
    cache = transformer.init_cache(TINY, 4, 12)
    ref_logits, ref_cache = transformer.decode_step(TINY, params, cache,
                                                    tokens, 0)

    pspecs = transformer.partition_specs(TINY, mesh)
    place = lambda tree, specs: jax.tree_util.tree_map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), tree, specs,
        is_leaf=lambda n: isinstance(n, P))
    params_s = place(params, pspecs)
    cache_s = place(transformer.init_cache(TINY, 4, 12),
                    transformer.cache_specs(TINY, mesh))
    logits, cache2 = jax.jit(
        lambda p, c, t: transformer.decode_step(TINY, p, c, t, 0,
                                                sharded=True))(
        params_s, cache_s, tokens)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(ref_logits),
                               rtol=2e-4, atol=2e-4)
    # Incremental step on the sharded cache also matches.
    nxt = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
    ref_nxt = jnp.argmax(ref_logits[:, -1:], axis=-1).astype(jnp.int32)
    l2, _ = jax.jit(lambda p, c, t: transformer.decode_step(
        TINY, p, c, t, 8, sharded=True))(params_s, cache2, nxt)
    r2, _ = transformer.decode_step(TINY, params, ref_cache, ref_nxt, 8)
    np.testing.assert_allclose(np.asarray(l2), np.asarray(r2),
                               rtol=2e-4, atol=2e-4)


MOE_PP = transformer.TransformerConfig(
    vocab_size=64, d_model=32, n_layers=2, n_heads=2, d_ff=64,
    max_seq_len=32, dtype=jnp.float32, n_experts=4, top_k=2)


def test_transformer_moe_pp_matches_sequential():
    """Dense-MoE under pipeline parallelism: logits are bitwise the same
    math as the non-pp forward, and the router aux now rides the pipeline
    (PARITY round-2 roadmap item) instead of being refused."""
    mesh = build_mesh({"pp": 2, "dp": 4})
    params = transformer.init_params(MOE_PP, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0,
                                MOE_PP.vocab_size)
    ref = transformer.forward(MOE_PP, params, tokens)
    got, aux = jax.jit(lambda p, t: transformer.forward(
        MOE_PP, p, t, mesh, return_aux=True))(params, tokens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)
    # z-loss is a plain token mean, so the microbatched pipeline estimate
    # equals the full-batch value exactly; load balance is the mean of
    # per-microbatch statistics (positive, and ~1-ish when balanced).
    _, ref_aux = transformer.forward(MOE_PP, params, tokens, return_aux=True)
    np.testing.assert_allclose(float(aux["z_loss"]), float(ref_aux["z_loss"]),
                               rtol=1e-4)
    assert float(aux["load_balance_loss"]) > 0.5
    assert float(aux["overflow_frac"]) == 0.0


def test_transformer_moe_pp_aux_reference():
    """The pipeline's load-balance estimate equals the mean of the same
    statistic computed per (layer, dp-shard, microbatch) sequentially."""
    mesh = build_mesh({"pp": 2, "dp": 4})
    params = transformer.init_params(MOE_PP, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0,
                                MOE_PP.vocab_size)
    _, aux = jax.jit(lambda p, t: transformer.forward(
        MOE_PP, p, t, mesh, return_aux=True))(params, tokens)

    vals = []
    for shard in np.split(np.asarray(tokens), 4):       # dp shards
        for piece in np.split(shard, 2):                # microbatches (=pp)
            _, a = transformer.forward(MOE_PP, params, jnp.asarray(piece),
                                       return_aux=True)
            vals.append(float(a["load_balance_loss"]))
    np.testing.assert_allclose(float(aux["load_balance_loss"]),
                               float(np.mean(vals)), rtol=1e-4)


def test_transformer_moe_pp_ep_matches_pp():
    """Expert weights sharded over ep inside pipeline stages (manual slice
    + psum): identical logits to the replicated-expert pp path and to the
    non-pp forward."""
    mesh = build_mesh({"pp": 2, "ep": 2, "dp": 2})
    params = transformer.init_params(MOE_PP, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0,
                                MOE_PP.vocab_size)
    ref = transformer.forward(MOE_PP, params, tokens)
    got, aux = jax.jit(lambda p, t: transformer.forward(
        MOE_PP, p, t, mesh, return_aux=True))(params, tokens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)
    assert float(aux["load_balance_loss"]) > 0.5


def test_transformer_moe_pp_trains_with_aux_loss():
    """loss_fn no longer refuses MoE + pp: the aux losses join the
    objective and the router receives gradient through the pipeline."""
    mesh = build_mesh({"pp": 2, "ep": 2, "dp": 2})
    params = transformer.init_params(MOE_PP, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 17), 0,
                                MOE_PP.vocab_size)
    loss, metrics = jax.jit(lambda p, b: transformer.loss_fn(
        MOE_PP, p, b, mesh))(params, {"tokens": tokens})
    assert np.isfinite(float(loss))
    assert "load_balance_loss" in metrics
    g = jax.jit(jax.grad(lambda p: transformer.loss_fn(
        MOE_PP, p, {"tokens": tokens}, mesh)[0]))(params)
    assert float(jnp.sum(jnp.abs(g["layers"]["router"]))) > 0
    assert float(jnp.sum(jnp.abs(g["layers"]["e_gate"]))) > 0


def test_transformer_moe_pp_tp_matches_sequential():
    """MoE inside tp'd pipeline stages (round-2 PARITY gap: 'pp x tp
    excludes MoE layers'): per-expert Megatron width sharding — e_gate/e_up
    column-split, e_down row-split, one psum covering ep x tp."""
    mesh = build_mesh({"pp": 2, "tp": 2, "dp": 2})
    params = transformer.init_params(MOE_PP, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0,
                                MOE_PP.vocab_size)
    ref = transformer.forward(MOE_PP, params, tokens)
    got, aux = jax.jit(lambda p, t: transformer.forward(
        MOE_PP, p, t, mesh, return_aux=True))(params, tokens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)
    assert float(aux["load_balance_loss"]) > 0.5


def test_transformer_moe_pp_tp_ep_trains():
    """The full pp x tp x ep factorization: exact logits vs the meshless
    forward, and gradient reaches router and experts through the
    pipeline."""
    mesh = build_mesh({"pp": 2, "tp": 2, "ep": 2})
    params = transformer.init_params(MOE_PP, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 17), 0,
                                MOE_PP.vocab_size)
    ref = transformer.forward(MOE_PP, params, tokens[:, :-1])
    got = jax.jit(lambda p, t: transformer.forward(MOE_PP, p, t, mesh))(
        params, tokens[:, :-1])
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)
    g = jax.jit(jax.grad(lambda p: transformer.loss_fn(
        MOE_PP, p, {"tokens": tokens}, mesh)[0]))(params)
    assert float(jnp.sum(jnp.abs(g["layers"]["router"]))) > 0
    assert float(jnp.sum(jnp.abs(g["layers"]["e_down"]))) > 0


def test_transformer_moe_shared_experts_pp_tp():
    """Shared experts under pp x tp: the always-on dense FFN width-shards
    over tp beside the routed experts (its partial needs its own psum)."""
    cfg = transformer.TransformerConfig(
        vocab_size=64, d_model=32, n_layers=2, n_heads=2, d_ff=64,
        max_seq_len=32, dtype=jnp.float32, n_experts=4, top_k=2,
        n_shared_experts=1)
    mesh = build_mesh({"pp": 2, "tp": 2, "dp": 2})
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0,
                                cfg.vocab_size)
    ref = transformer.forward(cfg, params, tokens)
    got = jax.jit(lambda p, t: transformer.forward(cfg, p, t, mesh))(
        params, tokens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_transformer_gqa_pp_tp_matches_sequential():
    """GQA inside tp'd pipeline stages (round-2 refusal lifted): wk/wv
    shard at kv width; requires tp | kv_heads."""
    cfg = transformer.TransformerConfig(
        vocab_size=64, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2,
        max_seq_len=32, dtype=jnp.float32, d_ff=64)
    mesh = build_mesh({"pp": 2, "tp": 2, "dp": 2})
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0,
                                cfg.vocab_size)
    ref = transformer.forward(cfg, params, tokens)
    got = jax.jit(lambda p, t: transformer.forward(cfg, p, t, mesh))(
        params, tokens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)
    # tp not dividing kv_heads still fails fast with the clear message.
    bad = transformer.TransformerConfig(
        vocab_size=64, d_model=32, n_layers=2, n_heads=4, n_kv_heads=1,
        max_seq_len=32, dtype=jnp.float32, d_ff=64)
    bad_params = transformer.init_params(bad, jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="divide kv_heads"):
        transformer.forward(bad, bad_params, tokens, mesh)


def test_transformer_moe_switch_pp_tp():
    """Switch (capacity) MoE with tp-sharded expert widths under pp:
    reproduces the reference routing applied per (dp-shard, microbatch)."""
    cfg = transformer.TransformerConfig(
        vocab_size=64, d_model=32, n_layers=2, n_heads=2, d_ff=64,
        max_seq_len=32, dtype=jnp.float32, n_experts=4, top_k=2,
        moe_impl="switch")
    mesh = build_mesh({"pp": 2, "tp": 2, "dp": 2})
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0,
                                cfg.vocab_size)
    got = jax.jit(lambda p, t: transformer.forward(cfg, p, t, mesh))(
        params, tokens)
    pieces = []
    for shard in np.split(np.asarray(tokens), 2):   # dp shards
        outs = [transformer.forward(cfg, params, jnp.asarray(piece))
                for piece in np.split(shard, 2)]    # microbatches (=pp)
        pieces.append(np.concatenate([np.asarray(o) for o in outs]))
    ref = np.concatenate(pieces)
    np.testing.assert_allclose(np.asarray(got), ref, rtol=2e-4, atol=2e-4)


def test_transformer_moe_switch_pp_ep():
    """Switch (capacity) MoE under pp x ep: the replicated-token local
    dispatch must reproduce the single-device reference routing applied
    per (dp-shard, microbatch)."""
    cfg = transformer.TransformerConfig(
        vocab_size=64, d_model=32, n_layers=2, n_heads=2, d_ff=64,
        max_seq_len=32, dtype=jnp.float32, n_experts=4, top_k=2,
        moe_impl="switch")
    mesh = build_mesh({"pp": 2, "ep": 2, "dp": 2})
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0,
                                cfg.vocab_size)
    got, aux = jax.jit(lambda p, t: transformer.forward(
        cfg, p, t, mesh, return_aux=True))(params, tokens)

    # Reference: same routing semantics per (dp shard, microbatch) — the
    # meshless forward routes per its whole call, so call it piecewise.
    pieces = []
    for shard in np.split(np.asarray(tokens), 2):   # dp shards
        outs = [transformer.forward(cfg, params, jnp.asarray(piece))
                for piece in np.split(shard, 2)]    # microbatches (=pp)
        pieces.append(np.concatenate([np.asarray(o) for o in outs]))
    ref = np.concatenate(pieces)
    np.testing.assert_allclose(np.asarray(got), ref, rtol=2e-4, atol=2e-4)
    assert 0.0 <= float(aux["overflow_frac"]) < 1.0


def test_quantized_params_forward_close_and_decode_consistent():
    """Weight-only int8: quantized forward stays close to full precision
    (per-row absmax => ~0.4% weight error), and the decode path reproduces
    the quantized forward's logits exactly (same dequant-on-use math)."""
    from tfmesos_tpu.ops.quant import QTensor

    params = transformer.init_params(TINY, jax.random.PRNGKey(0))
    qparams = transformer.quantize_params(TINY, params)
    assert isinstance(qparams["embed"], QTensor)
    assert isinstance(qparams["layers"]["wq"], QTensor)
    assert qparams["layers"]["wq"].values.dtype == jnp.int8
    assert not isinstance(qparams["layers"]["attn_norm"], QTensor)

    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 12), 0,
                                TINY.vocab_size)
    full = np.asarray(transformer.forward(TINY, params, tokens),
                      np.float32)
    quant = np.asarray(transformer.forward(TINY, qparams, tokens),
                       np.float32)
    # Close in direction: per-position cosine similarity.
    f = full.reshape(-1, TINY.vocab_size)
    q = quant.reshape(-1, TINY.vocab_size)
    cos = np.sum(f * q, -1) / (np.linalg.norm(f, axis=-1)
                               * np.linalg.norm(q, axis=-1) + 1e-9)
    assert cos.min() > 0.99, cos.min()

    # Decode == forward under the SAME quantized params (exactness).
    cache = transformer.init_cache(TINY, 2, 16)
    logits, cache = transformer.decode_step(TINY, qparams, cache, tokens, 0)
    np.testing.assert_allclose(np.asarray(logits), quant, rtol=2e-4,
                               atol=2e-4)


def test_quantized_generate_runs():
    params = transformer.init_params(TINY, jax.random.PRNGKey(0))
    qparams = transformer.quantize_params(TINY, params)
    prompt = jax.random.randint(jax.random.PRNGKey(2), (2, 4), 0,
                                TINY.vocab_size)
    out = transformer.generate(TINY, qparams, prompt, max_new_tokens=6)
    assert out.shape == (2, 10)
    assert np.all(np.asarray(out[:, :4]) == np.asarray(prompt))


def test_sliding_window_model_and_decode():
    """window >= T reproduces full causal attention exactly; a small window
    changes the logits; and the decode path (masked cache reads) matches
    the windowed forward position by position."""
    import dataclasses

    full = TINY
    wide = dataclasses.replace(TINY, window=64)    # > max_seq_len
    narrow = dataclasses.replace(TINY, window=4)
    params = transformer.init_params(full, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                                full.vocab_size)

    ref = transformer.forward(full, params, tokens)
    np.testing.assert_allclose(
        np.asarray(transformer.forward(wide, params, tokens)),
        np.asarray(ref), rtol=1e-5, atol=1e-6)
    narrowed = transformer.forward(narrow, params, tokens)
    assert np.abs(np.asarray(narrowed) - np.asarray(ref)).max() > 1e-3

    # Decode: prefill + steady-state steps reproduce the windowed forward.
    cache = transformer.init_cache(narrow, 2, 16)
    logits, cache = transformer.decode_step(narrow, params, cache,
                                            tokens[:, :12], 0)
    np.testing.assert_allclose(np.asarray(logits),
                               np.asarray(narrowed[:, :12]), rtol=2e-4,
                               atol=2e-4)
    for pos in range(12, 16):
        step_logits, cache = transformer.decode_step(
            narrow, params, cache, tokens[:, pos:pos + 1], pos)
        np.testing.assert_allclose(np.asarray(step_logits[:, 0]),
                                   np.asarray(narrowed[:, pos]), rtol=2e-4,
                                   atol=2e-4)

    out = transformer.generate(narrow, params, tokens[:, :4], 4)
    assert out.shape == (2, 8)


def test_rolling_window_cache_is_window_sized_and_exact():
    """Windowed configs keep an O(window) rolling cache: the buffer is
    window-sized, and greedy generation far past the buffer length matches
    teacher-forced windowed forward() logits step by step."""
    import dataclasses

    cfg = dataclasses.replace(TINY, window=4, max_seq_len=32)
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    cache = transformer.init_cache(cfg, 2, 32)
    # [L, B, KV, M, Dh] with M = 4 slots only
    assert cache["k"].shape == (cfg.n_layers, 2, 2, 4, 16)

    q8 = transformer.init_cache(cfg, 2, 32, quantized=True)
    assert q8["k"].values.shape[3] == 4

    # March a 24-token teacher-forced stream through the rolling cache and
    # compare each step's logits to the windowed full-sequence forward.
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 24), 0,
                                cfg.vocab_size)
    ref = transformer.forward(cfg, params, tokens)
    logits, cache = transformer.decode_step(cfg, params, cache,
                                            tokens[:, :6], 0)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(ref[:, :6]),
                               rtol=2e-4, atol=2e-4)
    for pos in range(6, 24):  # wraps the 4-slot buffer many times
        step_logits, cache = transformer.decode_step(
            cfg, params, cache, tokens[:, pos:pos + 1], pos)
        np.testing.assert_allclose(np.asarray(step_logits[:, 0]),
                                   np.asarray(ref[:, pos]), rtol=2e-4,
                                   atol=2e-4, err_msg=f"pos {pos}")

    out = transformer.generate(cfg, params, tokens[:, :6], 18)
    assert out.shape == (2, 24)


def test_quantized_kv_cache_decode_close_and_generate():
    """int8 KV cache: per-position absmax quantization keeps multi-step
    decode logits close to the fp-cache run, and generate() threads the
    QTensor cache through its scan."""
    from tfmesos_tpu.ops.quant import QTensor

    params = transformer.init_params(TINY, jax.random.PRNGKey(0))
    prompt = jax.random.randint(jax.random.PRNGKey(1), (2, 12), 0,
                                TINY.vocab_size)
    fp = transformer.init_cache(TINY, 2, 16)
    q8 = transformer.init_cache(TINY, 2, 16, quantized=True)
    assert isinstance(q8["k"], QTensor) and q8["k"].values.dtype == jnp.int8

    lf, fp = transformer.decode_step(TINY, params, fp, prompt, 0)
    lq, q8 = transformer.decode_step(TINY, params, q8, prompt, 0)
    # Prefill logits: the chunk attends only to itself, identical math.
    np.testing.assert_allclose(np.asarray(lq), np.asarray(lf), rtol=2e-4,
                               atol=2e-4)
    # Steady-state steps read the (now quantized) cache: close, not equal.
    tok = jnp.argmax(lf[:, -1:], axis=-1).astype(jnp.int32)
    for pos in range(12, 15):
        lf, fp = transformer.decode_step(TINY, params, fp, tok, pos)
        lq, q8 = transformer.decode_step(TINY, params, q8, tok, pos)
        f = np.asarray(lf, np.float32).reshape(-1, TINY.vocab_size)
        q = np.asarray(lq, np.float32).reshape(-1, TINY.vocab_size)
        cos = np.sum(f * q, -1) / (np.linalg.norm(f, axis=-1)
                                   * np.linalg.norm(q, axis=-1) + 1e-9)
        assert cos.min() > 0.99, (pos, cos.min())
        tok = jnp.argmax(lf[:, -1:], axis=-1).astype(jnp.int32)

    out = transformer.generate(TINY, params, prompt, max_new_tokens=4,
                               quantized_cache=True)
    ref = transformer.generate(TINY, params, prompt, max_new_tokens=4)
    assert out.shape == (2, 16)
    # Greedy decode is int8-cache robust at this scale: same argmax path.
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_quantized_kv_cache_sharded_decode():
    """cache_specs(quantized=True) places an int8 cache on a dp x tp mesh
    and sharded decode stays close to the single-device int8-cache run."""
    mesh = build_mesh({"dp": 4, "tp": 2})
    params = transformer.init_params(TINY, jax.random.PRNGKey(0))
    prompt = jax.random.randint(jax.random.PRNGKey(1), (4, 8), 0,
                                TINY.vocab_size)
    ref_cache = transformer.init_cache(TINY, 4, 12, quantized=True)
    ref, _ = transformer.decode_step(TINY, params, ref_cache, prompt, 0)

    from jax.sharding import NamedSharding
    specs = transformer.partition_specs(TINY, mesh)
    cspecs = transformer.cache_specs(TINY, mesh, quantized=True)
    pp = jax.device_put(params, jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), specs,
        is_leaf=lambda s: isinstance(s, jax.sharding.PartitionSpec)))
    cache = jax.device_put(
        transformer.init_cache(TINY, 4, 12, quantized=True),
        jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s), cspecs,
            is_leaf=lambda s: isinstance(s, jax.sharding.PartitionSpec)))
    got, _ = jax.jit(lambda p, c, t: transformer.decode_step(
        TINY, p, c, t, 0, sharded=True))(pp, cache, prompt)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=2e-4,
                               atol=2e-4)


def test_quantized_moe_dense_forward():
    cfg = transformer.TransformerConfig(
        vocab_size=64, d_model=32, n_layers=2, n_heads=2, d_ff=64,
        max_seq_len=32, dtype=jnp.float32, n_experts=4, top_k=2)
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    qparams = transformer.quantize_params(cfg, params)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, 64)
    full = np.asarray(transformer.forward(cfg, params, tokens), np.float32)
    quant = np.asarray(transformer.forward(cfg, qparams, tokens), np.float32)
    f, q = full.reshape(-1, 64), quant.reshape(-1, 64)
    cos = np.sum(f * q, -1) / (np.linalg.norm(f, axis=-1)
                               * np.linalg.norm(q, axis=-1) + 1e-9)
    assert cos.min() > 0.98, cos.min()


def test_quantized_sharded_decode_matches_single_device():
    """int8 multi-chip decode: qparams placed per quantized_partition_specs
    (values take the weight's spec, scales drop the size-1 last dim) must
    reproduce the single-device quantized logits."""
    from jax.sharding import NamedSharding

    mesh = build_mesh({"dp": 4, "tp": 2})
    params = transformer.init_params(TINY, jax.random.PRNGKey(0))
    qparams = transformer.quantize_params(TINY, params)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 8), 0,
                                TINY.vocab_size)
    ref_logits, _ = transformer.decode_step(
        TINY, qparams, transformer.init_cache(TINY, 4, 12), tokens, 0)

    qspecs = transformer.quantized_partition_specs(TINY, mesh)
    place = lambda tree, specs: jax.tree_util.tree_map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), tree, specs,
        is_leaf=lambda n: isinstance(n, P))
    qparams_s = place(qparams, qspecs)
    cache_s = place(transformer.init_cache(TINY, 4, 12),
                    transformer.cache_specs(TINY, mesh))
    logits, _ = jax.jit(
        lambda p, c, t: transformer.decode_step(TINY, p, c, t, 0,
                                                sharded=True))(
        qparams_s, cache_s, tokens)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(ref_logits),
                               rtol=2e-4, atol=2e-4)


def test_quantized_switch_moe_generate_runs():
    """Switch-MoE configs quantize the dense trunk only (experts stay fp,
    _quantizable) — generate must run, not crash in the dispatch path."""
    cfg = transformer.TransformerConfig(
        vocab_size=64, d_model=32, n_layers=2, n_heads=2, d_ff=64,
        max_seq_len=16, dtype=jnp.float32, n_experts=4, top_k=1,
        moe_impl="switch")
    from tfmesos_tpu.ops.quant import QTensor
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    qparams = transformer.quantize_params(cfg, params)
    assert not isinstance(qparams["layers"]["e_gate"], QTensor)
    assert isinstance(qparams["layers"]["wq"], QTensor)
    prompt = jax.random.randint(jax.random.PRNGKey(2), (1, 4), 0, 64)
    out = transformer.generate(cfg, qparams, prompt, max_new_tokens=4)
    assert out.shape == (1, 8)


def test_scan_unroll_matches_rolled():
    """unroll=K is the same arithmetic as the rolled scan — bitwise-equal
    params after the fused multi-step call."""
    cfg = mlp.MLPConfig(in_dim=16, hidden=8, n_classes=4)
    opt = optax.sgd(0.1)
    base = mlp.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.RandomState(0)
    batch = {"image": rng.rand(4, 4, 16).astype(np.float32),
             "label": rng.randint(0, 4, size=(4, 4)).astype(np.int32)}

    outs = []
    for unroll in (1, 4):
        step = make_train_step(lambda p, b: mlp.loss_fn(cfg, p, b), opt,
                               steps_per_call=4, scan_unroll=unroll)
        params, opt_state, metrics = step(
            jax.tree_util.tree_map(jnp.copy, base), opt.init(base), batch)
        outs.append((params, float(metrics["loss"])))
    np.testing.assert_array_equal(np.asarray(outs[0][0]["w1"]),
                                  np.asarray(outs[1][0]["w1"]))
    assert outs[0][1] == outs[1][1]
    with pytest.raises(ValueError, match="divide"):
        make_train_step(lambda p, b: mlp.loss_fn(cfg, p, b), opt,
                        steps_per_call=4, scan_unroll=3)


def test_eval_step_and_evaluate():
    from tfmesos_tpu.train.trainer import evaluate, make_eval_step

    cfg = mlp.MLPConfig(hidden=16)
    params = mlp.init_params(cfg, jax.random.PRNGKey(0))
    ds = datalib.SyntheticMNIST()
    eval_step = make_eval_step(lambda p, b: mlp.loss_fn(cfg, p, b))
    out = evaluate(eval_step, params, ds.batches(32, seed=5), num_batches=3)
    assert set(out) >= {"loss", "accuracy"}
    assert np.isfinite(out["loss"])


def test_trainloop_metrics_jsonl(tmp_path):
    import json as jsonlib

    cfg = mlp.MLPConfig(in_dim=8, hidden=4, n_classes=2)
    opt = optax.sgd(0.1)
    params = mlp.init_params(cfg, jax.random.PRNGKey(0))
    step = make_train_step(lambda p, b: mlp.loss_fn(cfg, p, b), opt)
    rng = np.random.RandomState(0)

    def batches():
        while True:
            yield {"image": rng.rand(8, 8).astype(np.float32),
                   "label": rng.randint(0, 2, size=8).astype(np.int32)}

    path = str(tmp_path / "metrics.jsonl")
    loop = TrainLoop(step, TrainState(params, opt.init(params)),
                     log_every=2, metrics_path=path)
    loop.run(batches(), num_steps=6)
    lines = [jsonlib.loads(l) for l in open(path)]
    assert [l["step"] for l in lines] == [2, 4, 6]
    assert all("loss" in l and "wall_s" in l for l in lines)


GQA = transformer.TransformerConfig(
    vocab_size=64, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2,
    d_ff=64, max_seq_len=32, dtype=jnp.float32)


def test_gqa_matches_mha_with_repeated_kv():
    """GQA is exact: repeating each kv head over its query group in an MHA
    model reproduces the GQA forward bit-for-bit."""
    params = transformer.init_params(GQA, jax.random.PRNGKey(0))
    mha_cfg = transformer.TransformerConfig(
        vocab_size=64, d_model=32, n_layers=2, n_heads=4, d_ff=64,
        max_seq_len=32, dtype=jnp.float32)
    rep = GQA.n_heads // GQA.kv_heads
    dh = GQA.head_dim

    def widen(w):  # [L, d, kv*dh] -> [L, d, H*dh], repeating per kv head
        l, d, _ = w.shape
        return jnp.repeat(w.reshape(l, d, GQA.kv_heads, dh), rep,
                          axis=2).reshape(l, d, -1)

    mha_params = jax.tree_util.tree_map(lambda x: x, params)
    mha_params["layers"] = dict(params["layers"])
    mha_params["layers"]["wk"] = widen(params["layers"]["wk"])
    mha_params["layers"]["wv"] = widen(params["layers"]["wv"])

    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, 64)
    got = transformer.forward(GQA, params, tokens)
    ref = transformer.forward(mha_cfg, mha_params, tokens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_gqa_decode_matches_forward_and_cache_shrinks():
    params = transformer.init_params(GQA, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 12), 0, 64)
    full = transformer.forward(GQA, params, tokens)

    cache = transformer.init_cache(GQA, 2, 16)
    # [L, B, KV, M, Dh] — kv_heads=2
    assert cache["k"].shape == (2, 2, 2, 16, GQA.head_dim)

    logits, cache = transformer.decode_step(GQA, params, cache, tokens, 0)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(full),
                               rtol=2e-4, atol=2e-4)
    # incremental steps too
    for i in range(12, 14):
        nxt = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
        logits, cache = transformer.decode_step(GQA, params, cache, nxt, i)
    assert np.all(np.isfinite(np.asarray(logits)))


def test_gqa_generate_and_quantized():
    params = transformer.init_params(GQA, jax.random.PRNGKey(0))
    prompt = jax.random.randint(jax.random.PRNGKey(2), (1, 4), 0, 64)
    out = transformer.generate(GQA, params, prompt, max_new_tokens=6)
    assert out.shape == (1, 10)
    qparams = transformer.quantize_params(GQA, params)
    qout = transformer.generate(GQA, qparams, prompt, max_new_tokens=6)
    assert qout.shape == (1, 10)


def test_gqa_trains_on_sp_mesh():
    mesh = build_mesh({"sp": 8})
    params = transformer.init_params(GQA, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 17), 0, 64)
    loss, _ = jax.jit(lambda p, b: transformer.loss_fn(GQA, p, b, mesh))(
        params, {"tokens": tokens})
    assert np.isfinite(float(loss))
    # pp x tp composes with GQA since round 3 when tp | kv_heads; the
    # indivisible case still fails fast with a clear message.
    import dataclasses
    mqa = dataclasses.replace(GQA, n_kv_heads=1)
    mqa_params = transformer.init_params(mqa, jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="divide kv_heads"):
        transformer.forward(
            mqa, mqa_params,
            jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, 64),
            build_mesh({"pp": 2, "tp": 2, "dp": 2}))


def test_ragged_decode_step_matches_per_row():
    """Per-row positions through decode_step: batched ragged decode equals
    each row decoded alone at its own position (cache writes, attention
    bounds, and rope all follow the row's position)."""
    cfg = transformer.TransformerConfig(
        vocab_size=64, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=64, max_seq_len=64, dtype=jnp.float32)
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    lens = [5, 9, 3]
    b = len(lens)
    toks = jax.random.randint(jax.random.PRNGKey(1), (b, 12), 0,
                              cfg.vocab_size)
    ref_logits = []
    for i, L in enumerate(lens):
        c = transformer.init_cache(cfg, 1, 64)
        _, c = transformer.decode_step(cfg, params, c, toks[i:i + 1, :L], 0)
        lg, _ = transformer.decode_step(cfg, params, c,
                                        toks[i:i + 1, L:L + 1], L)
        ref_logits.append(np.asarray(lg[0, -1]))
    cache = transformer.init_cache(cfg, b, 64)
    _, cache = transformer.decode_step(cfg, params, cache,
                                       toks[:, :max(lens)], 0)
    lens_a = jnp.asarray(lens, jnp.int32)
    nxt = jnp.take_along_axis(toks, lens_a[:, None], axis=1)
    lg, cache = transformer.decode_step(cfg, params, cache, nxt, lens_a)
    for i in range(b):
        np.testing.assert_allclose(np.asarray(lg[i, -1]), ref_logits[i],
                                   rtol=2e-4, atol=2e-4)


def test_ragged_generate_matches_per_row():
    """generate(prompt_lens=...): each padded row's continuation equals
    generating from its unpadded prompt alone, landing right after the
    real prompt in the output."""
    cfg = transformer.TransformerConfig(
        vocab_size=64, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=64, max_seq_len=64, dtype=jnp.float32)
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    lens, new = [5, 9, 3], 6
    b = len(lens)
    toks = jax.random.randint(jax.random.PRNGKey(1), (b, 9), 0,
                              cfg.vocab_size)
    out = transformer.generate(cfg, params, toks, new,
                               prompt_lens=jnp.asarray(lens, jnp.int32))
    for i, L in enumerate(lens):
        ref = transformer.generate(cfg, params, toks[i:i + 1, :L], new)
        np.testing.assert_array_equal(np.asarray(out[i, :L + new]),
                                      np.asarray(ref[0]))


def test_ragged_rejects_windowed_configs():
    cfg = transformer.TransformerConfig(
        vocab_size=64, d_model=32, n_layers=2, n_heads=2, d_ff=64,
        max_seq_len=32, dtype=jnp.float32, window=8)
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    cache = transformer.init_cache(cfg, 2, 32)
    tok = jnp.zeros((2, 1), jnp.int32)
    with pytest.raises(ValueError, match="ragged"):
        transformer.decode_step(cfg, params, cache, tok,
                                jnp.array([1, 2], jnp.int32))


SPEC_DRAFT = transformer.TransformerConfig(
    vocab_size=64, d_model=16, n_layers=1, n_heads=2, d_ff=32,
    max_seq_len=128, dtype=jnp.float32)


def test_speculative_generate_exactness():
    """The speculative exactness property: output equals the target's own
    greedy continuation for ANY draft model — an unrelated draft only
    costs acceptance rate, never changes tokens."""
    cfg = transformer.TransformerConfig(
        vocab_size=64, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=64, max_seq_len=128, dtype=jnp.float32)
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    dparams = transformer.init_params(SPEC_DRAFT, jax.random.PRNGKey(7))
    toks = jax.random.randint(jax.random.PRNGKey(1), (3, 9), 0,
                              cfg.vocab_size)
    ref = np.asarray(transformer.generate(cfg, params, toks, 12))
    for nd in (1, 4, 6):
        spec = transformer.speculative_generate(
            cfg, params, SPEC_DRAFT, dparams, toks, 12, n_draft=nd)
        np.testing.assert_array_equal(np.asarray(spec), ref)
    # Self-draft: every proposal accepted, same answer.
    spec = transformer.speculative_generate(cfg, params, cfg, params,
                                            toks, 12, n_draft=3)
    np.testing.assert_array_equal(np.asarray(spec), ref)


def test_speculative_generate_ragged():
    cfg = transformer.TransformerConfig(
        vocab_size=64, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=64, max_seq_len=128, dtype=jnp.float32)
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    dparams = transformer.init_params(SPEC_DRAFT, jax.random.PRNGKey(7))
    toks = jax.random.randint(jax.random.PRNGKey(1), (3, 9), 0,
                              cfg.vocab_size)
    lens = jnp.array([4, 9, 6], jnp.int32)
    ref = np.asarray(transformer.generate(cfg, params, toks, 10,
                                          prompt_lens=lens))
    spec = np.asarray(transformer.speculative_generate(
        cfg, params, SPEC_DRAFT, dparams, toks, 10, n_draft=4,
        prompt_lens=lens))
    for i, ln in enumerate([4, 9, 6]):
        np.testing.assert_array_equal(spec[i, :ln + 10], ref[i, :ln + 10])


def test_ragged_sharded_decode_matches_per_row():
    """Ragged positions under GSPMD decode (dp4 x tp2): the vmapped
    per-row cache writes and [B, t] masks are plain ops, so sharded
    ragged decode must match each row decoded alone."""
    from jax.sharding import NamedSharding

    mesh = build_mesh({"dp": 4, "tp": 2})
    params = transformer.init_params(TINY, jax.random.PRNGKey(0))
    lens = [3, 6, 2, 5]
    toks = jax.random.randint(jax.random.PRNGKey(1), (4, 8), 0,
                              TINY.vocab_size)
    ref_logits = []
    for i, ln in enumerate(lens):
        c = transformer.init_cache(TINY, 1, 16)
        _, c = transformer.decode_step(TINY, params, c, toks[i:i + 1, :ln], 0)
        lg, _ = transformer.decode_step(TINY, params, c,
                                        toks[i:i + 1, ln:ln + 1], ln)
        ref_logits.append(np.asarray(lg[0, -1]))

    pspecs = transformer.partition_specs(TINY, mesh)
    place = lambda tree, specs: jax.tree_util.tree_map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), tree, specs,
        is_leaf=lambda n: isinstance(n, P))
    params_s = place(params, pspecs)
    cache_s = place(transformer.init_cache(TINY, 4, 16),
                    transformer.cache_specs(TINY, mesh))
    _, cache_s = jax.jit(lambda p, c, t: transformer.decode_step(
        TINY, p, c, t, 0, sharded=True))(params_s, cache_s,
                                         toks[:, :max(lens)])
    lens_a = jnp.asarray(lens, jnp.int32)
    nxt = jnp.take_along_axis(toks, lens_a[:, None], axis=1)
    lg, _ = jax.jit(lambda p, c, t, pv: transformer.decode_step(
        TINY, p, c, t, pv, sharded=True))(params_s, cache_s, nxt, lens_a)
    for i in range(4):
        np.testing.assert_allclose(np.asarray(lg[i, -1]), ref_logits[i],
                                   rtol=2e-4, atol=2e-4)


def test_speculative_sampling_distribution():
    """Speculative SAMPLING correctness (Leviathan): with an unrelated
    draft, committed-token marginals must match target-only sampling.
    Token 1 checks the closed-form prefill distribution; tokens 2-3 (from
    the rejection-sampling rounds) check empirically against generate()'s
    own sampling under a different RNG stream."""
    cfg = transformer.TransformerConfig(
        vocab_size=16, d_model=16, n_layers=1, n_heads=2, d_ff=32,
        max_seq_len=32, dtype=jnp.float32)
    draft = transformer.TransformerConfig(
        vocab_size=16, d_model=8, n_layers=1, n_heads=1, d_ff=16,
        max_seq_len=32, dtype=jnp.float32)
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    dparams = transformer.init_params(draft, jax.random.PRNGKey(9))
    B = 2048
    prompt = jnp.tile(jnp.array([[3, 7, 1, 12]], jnp.int32), (B, 1))
    spec = np.asarray(transformer.speculative_generate(
        cfg, params, draft, dparams, prompt, 3, n_draft=2,
        temperature=1.0, rng=jax.random.PRNGKey(5)))

    logits = transformer.forward(cfg, params, prompt[:1])
    pt = np.asarray(jax.nn.softmax(
        transformer.filter_logits(logits[0, -1], 1.0), -1))
    emp = np.bincount(spec[:, 4], minlength=16) / B
    assert np.max(np.abs(emp - pt)) < 0.04

    ref = np.asarray(transformer.generate(
        cfg, params, prompt, 3, temperature=1.0,
        rng=jax.random.PRNGKey(11)))
    for idx in (5, 6):
        es = np.bincount(spec[:, idx], minlength=16) / B
        er = np.bincount(ref[:, idx], minlength=16) / B
        assert np.max(np.abs(es - er)) < 0.05, idx


def test_speculative_sampling_self_draft_full_acceptance():
    """Draft == target: every proposal is accepted (ratio 1), so rounds
    commit n_draft+1 tokens each; output stays finite and in-vocab."""
    cfg = transformer.TransformerConfig(
        vocab_size=32, d_model=16, n_layers=1, n_heads=2, d_ff=32,
        max_seq_len=64, dtype=jnp.float32)
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (4, 6), 0,
                              cfg.vocab_size)
    out = np.asarray(transformer.speculative_generate(
        cfg, params, cfg, params, toks, 10, n_draft=3, temperature=0.7,
        top_k=8, rng=jax.random.PRNGKey(2)))
    assert out.shape == (4, 16)
    assert ((out >= 0) & (out < cfg.vocab_size)).all()


@pytest.mark.parametrize("quantized", [False, True])
def test_sharded_flash_decode_matches_einsum(quantized):
    """decode_step(sharded=True, mesh=...) routes single-token steps
    through the flash-decode kernel per shard (shard_map over the
    cache_specs layout: dp batch + tp kv-major head blocks); logits must
    match the GSPMD einsum path, fp and int8 caches alike."""
    from jax.sharding import NamedSharding

    cfg = transformer.TransformerConfig(
        vocab_size=64, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=64, max_seq_len=640, dtype=jnp.float32)
    mesh = build_mesh({"dp": 4, "tp": 2})
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    prompt = jax.random.randint(jax.random.PRNGKey(1), (4, 9), 0,
                                cfg.vocab_size)
    place = lambda tree, specs: jax.tree_util.tree_map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), tree, specs,
        is_leaf=lambda n: isinstance(n, P))
    params_s = place(params, transformer.partition_specs(cfg, mesh))
    cache_s = place(
        transformer.init_cache(cfg, 4, 640, quantized=quantized),
        transformer.cache_specs(cfg, mesh, quantized=quantized))
    _, cache_s = jax.jit(lambda p, c, t: transformer.decode_step(
        cfg, p, c, t, 0, sharded=True))(params_s, cache_s, prompt)
    tok = jnp.full((4, 1), 3, jnp.int32)

    ref, _ = jax.jit(lambda p, c, t: transformer.decode_step(
        cfg, p, c, t, 9, sharded=True))(params_s, cache_s, tok)

    orig = transformer._decode_kernel_kwargs
    force = (lambda cfg_, m, t, sharded, mesh=None, batch=None:
             {"use_pallas": True, "interpret": True})
    transformer._decode_kernel_kwargs = force
    try:
        got, _ = jax.jit(lambda p, c, t: transformer.decode_step(
            cfg, p, c, t, 9, sharded=True, mesh=mesh))(params_s, cache_s,
                                                       tok)
    finally:
        transformer._decode_kernel_kwargs = orig
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)

    # Chunked sharded verify shape (t=3): same per-shard kernel route.
    chunk = jax.random.randint(jax.random.PRNGKey(3), (4, 3), 0,
                               cfg.vocab_size)
    ref_c, _ = jax.jit(lambda p, c, t: transformer.decode_step(
        cfg, p, c, t, 9, sharded=True))(params_s, cache_s, chunk)
    transformer._decode_kernel_kwargs = force
    try:
        got_c, _ = jax.jit(lambda p, c, t: transformer.decode_step(
            cfg, p, c, t, 9, sharded=True, mesh=mesh))(params_s, cache_s,
                                                       chunk)
    finally:
        transformer._decode_kernel_kwargs = orig
    np.testing.assert_allclose(np.asarray(got_c), np.asarray(ref_c),
                               rtol=2e-4, atol=2e-4)

    # Indivisible batch (b=6 over dp4): the real gate must fall back to
    # the einsum instead of crashing in shard_map.
    assert transformer._decode_kernel_kwargs(
        cfg, 640, 1, True, mesh, batch=6) is None


def test_sharded_prefill_kernel_matches_einsum():
    """decode_step(sharded=True, mesh=...) prefill routes the chunk's
    self-attention through the flash kernel per shard (shard_map over
    dp batch + tp head blocks) instead of the O(t^2)-materializing
    einsum; logits and the written cache must match the einsum path."""
    from jax.sharding import NamedSharding

    cfg = transformer.TransformerConfig(
        vocab_size=64, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=64, max_seq_len=256, dtype=jnp.float32)
    mesh = build_mesh({"dp": 4, "tp": 2})
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    prompt = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0,
                                cfg.vocab_size)
    place = lambda tree, specs: jax.tree_util.tree_map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), tree, specs,
        is_leaf=lambda n: isinstance(n, P))
    params_s = place(params, transformer.partition_specs(cfg, mesh))
    cache0 = lambda: place(transformer.init_cache(cfg, 4, 256),
                           transformer.cache_specs(cfg, mesh))

    ref, ref_cache = jax.jit(lambda p, c, t: transformer.decode_step(
        cfg, p, c, t, 0, sharded=True, mesh=mesh))(params_s, cache0(),
                                                   prompt)

    orig = transformer._prefill_kernel_kwargs
    transformer._prefill_kernel_kwargs = (
        lambda cfg_, mesh_, b_, t_:
        {"interpret": True} if mesh_ is not None else None)
    try:
        got, got_cache = jax.jit(lambda p, c, t: transformer.decode_step(
            cfg, p, c, t, 0, sharded=True, mesh=mesh))(params_s, cache0(),
                                                       prompt)
    finally:
        transformer._prefill_kernel_kwargs = orig
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(got_cache["k"]),
                               np.asarray(ref_cache["k"]),
                               rtol=2e-4, atol=2e-4)

    # Real gate: the shape/mesh checks run BEFORE the backend check, so
    # they are exercised here on CPU — an unaligned chunk, an
    # indivisible batch, and a missing mesh (each would crash shard_map)
    # must fall back to the einsum, while the full eligibility rule
    # accepts this mesh/batch.
    assert transformer._prefill_kernel_kwargs(cfg, mesh, 4, 12) is None
    assert transformer._prefill_kernel_kwargs(cfg, mesh, 6, 128) is None
    assert transformer._prefill_kernel_kwargs(cfg, None, 4, 128) is None
    assert transformer._shard_map_mesh_ok(cfg, mesh, 4,
                                          need_n_heads_div=True)


def test_beam_search_beam1_is_greedy_and_scores_check():
    """beam=1 must equal greedy generation bitwise; with beam=4 the best
    sequence's total logprob is >= greedy's, and the returned scores
    match teacher-forced logprobs computed by forward()."""
    cfg = transformer.TransformerConfig(
        vocab_size=32, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=64, max_seq_len=64, dtype=jnp.float32)
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (3, 7), 0,
                              cfg.vocab_size)

    def seq_logprob(seq, tp):
        lg = transformer.forward(cfg, params, seq[:, :-1])
        lp = jax.nn.log_softmax(lg.astype(jnp.float32), -1)
        picked = jnp.take_along_axis(lp, seq[:, 1:][..., None], -1)[..., 0]
        return jnp.sum(picked[:, tp - 1:], axis=1)

    ref = transformer.generate(cfg, params, toks, 8)
    b1 = transformer.beam_search(cfg, params, toks, 8, beam=1)
    np.testing.assert_array_equal(np.asarray(b1), np.asarray(ref))

    b4, s4 = transformer.beam_search(cfg, params, toks, 8, beam=4,
                                     return_scores=True)
    lp_greedy = np.asarray(seq_logprob(ref, 7))
    lp_beam = np.asarray(seq_logprob(b4, 7))
    assert np.all(lp_beam >= lp_greedy - 1e-4)
    np.testing.assert_allclose(np.asarray(s4), lp_beam, rtol=1e-4,
                               atol=1e-4)


def test_beam_search_int8_cache_runs():
    cfg = transformer.TransformerConfig(
        vocab_size=32, d_model=32, n_layers=2, n_heads=2, d_ff=64,
        max_seq_len=64, dtype=jnp.float32)
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 6), 0,
                              cfg.vocab_size)
    out = transformer.beam_search(cfg, params, toks, 6, beam=3,
                                  quantized_cache=True)
    o = np.asarray(out)
    assert o.shape == (2, 12)
    assert ((o >= 0) & (o < cfg.vocab_size)).all()


def test_generate_shared_prefix_matches_concatenated():
    """generate(prefix=...) — prefill the shared prefix once at batch 1,
    broadcast its cache — must equal prepending the prefix to every row,
    uniform and ragged alike."""
    cfg = transformer.TransformerConfig(
        vocab_size=64, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=64, max_seq_len=128, dtype=jnp.float32)
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    prefix = jax.random.randint(jax.random.PRNGKey(5), (6,),
                                0, cfg.vocab_size)
    prompts = jax.random.randint(jax.random.PRNGKey(1), (3, 5),
                                 0, cfg.vocab_size)
    full = jnp.concatenate([jnp.broadcast_to(prefix, (3, 6)), prompts],
                           axis=1)
    ref = transformer.generate(cfg, params, full, 8)
    got = transformer.generate(cfg, params, prompts, 8, prefix=prefix)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))

    lens = jnp.array([2, 5, 3], jnp.int32)
    ref_r = transformer.generate(cfg, params, full, 8,
                                 prompt_lens=6 + lens)
    got_r = transformer.generate(cfg, params, prompts, 8, prefix=prefix,
                                 prompt_lens=lens)
    for i, ln in enumerate([2, 5, 3]):
        np.testing.assert_array_equal(np.asarray(got_r[i, :6 + ln + 8]),
                                      np.asarray(ref_r[i, :6 + ln + 8]))


def test_speculative_int8_cache_exactness():
    """Speculative with an int8 TARGET cache equals int8-cache greedy
    generate bitwise (committed positions quantize identically)."""
    cfg = transformer.TransformerConfig(
        vocab_size=64, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=64, max_seq_len=128, dtype=jnp.float32)
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    dparams = transformer.init_params(SPEC_DRAFT, jax.random.PRNGKey(7))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 9), 0,
                              cfg.vocab_size)
    ref = transformer.generate(cfg, params, toks, 10, quantized_cache=True)
    spec = transformer.speculative_generate(
        cfg, params, SPEC_DRAFT, dparams, toks, 10, n_draft=3,
        quantized_cache=True)
    np.testing.assert_array_equal(np.asarray(spec), np.asarray(ref))


def test_generate_stop_token():
    """stop_token freezes rows at their first stop emission (tail filled
    with the stop token, early exit when all rows stop); tokens before
    the stop are identical to a run without it."""
    cfg = transformer.TransformerConfig(
        vocab_size=64, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=64, max_seq_len=128, dtype=jnp.float32)
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (3, 7), 0,
                              cfg.vocab_size)
    plain = np.asarray(transformer.generate(cfg, params, toks, 12))
    gen_part = plain[:, 7:]
    absent = next(v for v in range(64)
                  if v not in set(gen_part.ravel().tolist()))
    same = np.asarray(transformer.generate(cfg, params, toks, 12,
                                           stop_token=absent))
    np.testing.assert_array_equal(same, plain)

    stop = int(gen_part[0, 4])
    out = np.asarray(transformer.generate(cfg, params, toks, 12,
                                          stop_token=stop))
    for i in range(3):
        row = out[i, 7:]
        hits = np.where(gen_part[i] == stop)[0]
        cut = hits[0] if len(hits) else 11
        np.testing.assert_array_equal(row[:cut + 1],
                                      gen_part[i][:cut + 1])
        if len(hits):
            assert (row[cut:] == stop).all()


def test_speculative_with_shared_prefix():
    """prefix + speculative compose: bitwise the target's greedy
    continuation of prefix+prompt, uniform and ragged."""
    cfg = transformer.TransformerConfig(
        vocab_size=64, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=64, max_seq_len=128, dtype=jnp.float32)
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    dparams = transformer.init_params(SPEC_DRAFT, jax.random.PRNGKey(7))
    prefix = jax.random.randint(jax.random.PRNGKey(5), (6,),
                                0, cfg.vocab_size)
    prompts = jax.random.randint(jax.random.PRNGKey(1), (3, 5),
                                 0, cfg.vocab_size)
    full = jnp.concatenate([jnp.broadcast_to(prefix, (3, 6)), prompts],
                           axis=1)
    ref = transformer.generate(cfg, params, full, 8)
    got = transformer.speculative_generate(
        cfg, params, SPEC_DRAFT, dparams, prompts, 8, n_draft=3,
        prefix=prefix)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
    lens = jnp.array([2, 5, 3], jnp.int32)
    ref_r = transformer.generate(cfg, params, full, 8, prompt_lens=6 + lens)
    got_r = transformer.speculative_generate(
        cfg, params, SPEC_DRAFT, dparams, prompts, 8, n_draft=3,
        prefix=prefix, prompt_lens=lens)
    for i, ln in enumerate([2, 5, 3]):
        np.testing.assert_array_equal(np.asarray(got_r[i, :6 + ln + 8]),
                                      np.asarray(ref_r[i, :6 + ln + 8]))


def test_paged_decode_matches_contiguous():
    """Paged KV cache (pool + page-table indirection, PagedAttention
    layout): with SCRAMBLED page assignments and ragged positions,
    decode_step must match the contiguous cache bit-for-tolerance on
    both the gather reference and the forced kernel path."""
    import random as pyrandom

    cfg = transformer.TransformerConfig(
        vocab_size=64, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=64, max_seq_len=256, dtype=jnp.float32)
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    lens = [5, 9, 3]
    b = len(lens)
    toks = jax.random.randint(jax.random.PRNGKey(1), (b, 12), 0,
                              cfg.vocab_size)
    cache = transformer.init_cache(cfg, b, 64)
    _, cache = transformer.decode_step(cfg, params, cache, toks[:, :9], 0)
    lens_a = jnp.asarray(lens, jnp.int32)
    nxt = jnp.take_along_axis(toks, lens_a[:, None], axis=1)
    lg_ref, cache = transformer.decode_step(cfg, params, cache, nxt, lens_a)
    nxt2 = jnp.argmax(lg_ref[:, -1:], -1).astype(jnp.int32)
    lg_ref2, _ = transformer.decode_step(cfg, params, cache, nxt2,
                                         lens_a + 1)

    alloc = transformer.PageAllocator(n_pages=32, page_size=8)
    pyrandom.Random(3).shuffle(alloc.free)
    for i in range(b):
        alloc.ensure(i, 13)
    pcache = transformer.init_paged_cache(cfg, 32, page_size=8)
    pcache["pages"] = alloc.table(range(b))
    _, pcache = transformer.decode_step(cfg, params, pcache, toks[:, :9], 0)
    lg_p, pcache = transformer.decode_step(cfg, params, pcache, nxt, lens_a)
    np.testing.assert_allclose(np.asarray(lg_p), np.asarray(lg_ref),
                               rtol=2e-4, atol=2e-4)

    orig = transformer._decode_kernel_kwargs
    transformer._decode_kernel_kwargs = (
        lambda *a, **k: {"use_pallas": True, "interpret": True})
    try:
        lg_k, _ = transformer.decode_step(cfg, params, pcache, nxt2,
                                          lens_a + 1)
    finally:
        transformer._decode_kernel_kwargs = orig
    np.testing.assert_allclose(np.asarray(lg_k), np.asarray(lg_ref2),
                               rtol=2e-4, atol=2e-4)


def test_page_allocator_lifecycle():
    alloc = transformer.PageAllocator(n_pages=4, page_size=8)
    alloc.ensure(0, 17)             # 3 pages
    alloc.ensure(1, 8)              # 1 page
    assert len(alloc.free) == 0
    with pytest.raises(RuntimeError, match="exhausted"):
        alloc.ensure(1, 9)
    t = np.asarray(alloc.table([0, 1]))
    assert t.shape == (2, 3)
    assert len(set(t[0].tolist()) | {int(t[1, 0])}) == 4  # all distinct
    alloc.release(0)
    assert len(alloc.free) == 3
    alloc.ensure(1, 24)             # grows with recycled pages
    assert len(alloc.rows[1]) == 3


def test_generate_over_paged_cache_matches():
    """generate(cache=paged) over scrambled pages equals the contiguous
    run bitwise (ragged)."""
    import random as pyrandom

    cfg = transformer.TransformerConfig(
        vocab_size=64, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=64, max_seq_len=256, dtype=jnp.float32)
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (3, 9), 0,
                              cfg.vocab_size)
    lens = jnp.array([4, 9, 6], jnp.int32)
    ref = transformer.generate(cfg, params, toks, 8, prompt_lens=lens)
    alloc = transformer.PageAllocator(n_pages=24, page_size=8)
    pyrandom.Random(5).shuffle(alloc.free)
    for i in range(3):
        alloc.ensure(i, 9 + 8)   # the PADDED prompt region + continuation
    pcache = transformer.init_paged_cache(cfg, 24, page_size=8)
    pcache["pages"] = alloc.table(range(3))
    got = transformer.generate(cfg, params, toks, 8, prompt_lens=lens,
                               cache=pcache)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def test_int8_paged_generate_matches_contiguous():
    """int8 page pool (per-position scales folded in-kernel): paged
    generate equals the contiguous int8-cache run bitwise, and the
    forced kernel path matches the gather reference."""
    import random as pyrandom

    cfg = transformer.TransformerConfig(
        vocab_size=64, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=64, max_seq_len=256, dtype=jnp.float32)
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (3, 9), 0,
                              cfg.vocab_size)
    lens = jnp.array([4, 9, 6], jnp.int32)
    ref = transformer.generate(cfg, params, toks, 8, prompt_lens=lens,
                               quantized_cache=True)
    alloc = transformer.PageAllocator(n_pages=24, page_size=8)
    pyrandom.Random(5).shuffle(alloc.free)
    for i in range(3):
        alloc.ensure(i, 17)
    pcache = transformer.init_paged_cache(cfg, 24, page_size=8,
                                          quantized=True)
    pcache["pages"] = alloc.table(range(3))
    got = transformer.generate(cfg, params, toks, 8, prompt_lens=lens,
                               cache=pcache)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))

    cache2 = transformer.init_paged_cache(cfg, 24, page_size=8,
                                          quantized=True)
    alloc2 = transformer.PageAllocator(24, 8)
    for i in range(3):
        alloc2.ensure(i, 17)
    cache2["pages"] = alloc2.table(range(3))
    _, cache2 = transformer.decode_step(cfg, params, cache2, toks, 0)
    nxt = jnp.take_along_axis(toks, lens[:, None], axis=1)
    ref_lg, _ = transformer.decode_step(cfg, params, cache2, nxt, lens)
    orig = transformer._decode_kernel_kwargs
    transformer._decode_kernel_kwargs = (
        lambda *a, **k: {"use_pallas": True, "interpret": True})
    try:
        got_lg, _ = transformer.decode_step(cfg, params, cache2, nxt, lens)
    finally:
        transformer._decode_kernel_kwargs = orig
    np.testing.assert_allclose(np.asarray(got_lg), np.asarray(ref_lg),
                               rtol=2e-4, atol=2e-4)


def _commit_reference(pool, chunks, page_table, pos):
    """``_paged_cache_write_all`` as it was before PR 25 (slices over layer
    and KV around the indexed page and offset): the ground truth of WHICH
    slot gets WHICH value, kept here because the chip relayouts the whole
    pool around this formulation."""
    from tfmesos_tpu.ops.quant import QTensor, quantize_int8_reference

    L, b, t, kvh, dh = chunks.shape
    ps = (pool.values if isinstance(pool, QTensor) else pool).shape[3]
    posv = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
    lpos = posv[:, None] + jnp.arange(t, dtype=jnp.int32)[None]
    blk = jnp.minimum(lpos // ps, page_table.shape[1] - 1)
    pages = jnp.take_along_axis(page_table, blk, axis=1).reshape(-1)
    offs = (lpos % ps).reshape(-1)
    x = chunks.transpose(1, 2, 0, 3, 4).reshape(b * t, L, kvh, dh)
    if isinstance(pool, QTensor):
        vals, scale = quantize_int8_reference(x)
        return QTensor(pool.values.at[:, pages, :, offs].set(vals),
                       pool.scales.at[:, pages, :, 0, offs].set(scale[..., 0]))
    return pool.at[:, pages, :, offs].set(x.astype(pool.dtype))


_COMMIT_SINK = 19
# name -> (page table, t, pos, pos is traced).  Page 8, table width 4, so
# max_len is 32 and a row parked there clamps from block 4 onto column 3.
_COMMIT_CASES = {
    # The steady-state token: ragged rows, two of them parked at max_len
    # over all-sink table rows (they collide on the sink, nowhere else).
    "t1_ragged_parked": ([[3, 7, 11, 2], [5, 9, 0, 13],
                          [_COMMIT_SINK] * 4, [17, 1, 4, 6],
                          [_COMMIT_SINK] * 4], 1, [5, 16, 32, 31, 32], True),
    # Chunked prefill / speculative verify: a traced start inside a page,
    # the chunk crossing page boundaries.
    "t5_traced_unaligned": ([[3, 7, 11, 2], [5, 9, 0, 13]], 5, [3, 13],
                            True),
    # The same chunk at a STATIC start that is not page-aligned (a shared
    # prefix of 13 tokens): rows form too.
    "t16_static_unaligned": ([[3, 7, 11, 2], [5, 9, 0, 13]], 16, 13, False),
    # Prefill: whole pages at a static aligned start; the padded tail runs
    # past row 1's pages onto the sink, and block 4 clamps onto column 3.
    "pages_padded_tail": ([[3, 7, 11, _COMMIT_SINK],
                           [5, 9, _COMMIT_SINK, _COMMIT_SINK]], 32, 8, False),
    "pages_from_zero": ([[3, 7, 11, 2], [5, 9, 0, 13]], 16, 0, False),
}


@pytest.mark.parametrize("quantized", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("case", sorted(_COMMIT_CASES))
def test_paged_commit_equals_reference_formulation(case, quantized):
    """The deferred commit writes the pool in the pool's own layout (rows
    or whole-page windows, PR 25): bit for bit the slots and values of the
    old formulation, values and scales, on every page but the sink (where
    parked rows and padded tails collide by design and nothing reads)."""
    from tfmesos_tpu.ops.quant import QTensor

    table, t, pos, traced = _COMMIT_CASES[case]
    table = jnp.asarray(table, jnp.int32)
    L, kvh, ps, dh, n_pages = 3, 2, 8, 16, 20
    rng = np.random.default_rng(sorted(_COMMIT_CASES).index(case))
    shape = (L, n_pages, kvh, ps, dh)
    if quantized:
        pool = QTensor(
            jnp.asarray(rng.integers(-127, 128, shape), jnp.int8),
            jnp.asarray(rng.uniform(0.5, 2.0, shape[:3] + (1, ps)),
                        jnp.float32))
    else:
        pool = jnp.asarray(rng.normal(size=shape), jnp.bfloat16)
    chunks = jnp.asarray(rng.normal(size=(L, table.shape[0], t, kvh, dh)),
                         jnp.bfloat16)
    if traced:
        run = lambda fn: jax.jit(fn)(pool, chunks, table,
                                     jnp.asarray(pos, jnp.int32))
    else:
        run = lambda fn: jax.jit(
            lambda p, c, tb: fn(p, c, tb, pos))(pool, chunks, table)
    got = run(transformer._paged_cache_write_all)
    want = run(_commit_reference)
    keep = np.arange(n_pages) != _COMMIT_SINK
    compared = lambda leaf: np.asarray(leaf, np.float32)[:, keep]
    changed = False
    for g, w, before in zip(*map(jax.tree_util.tree_leaves,
                                 (got, want, pool))):
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_array_equal(compared(g), compared(w))
        changed |= bool((compared(g) != compared(before)).any())
    assert changed      # the case wrote somewhere that is compared


def test_speculative_over_paged_cache():
    """Speculative decoding with a paged TARGET cache (verify chunks write
    and read through the page table) is bitwise the plain speculative
    run."""
    import random as pyrandom

    cfg = transformer.TransformerConfig(
        vocab_size=64, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=64, max_seq_len=256, dtype=jnp.float32)
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    dparams = transformer.init_params(SPEC_DRAFT, jax.random.PRNGKey(7))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 9), 0,
                              cfg.vocab_size)
    k, new = 3, 10
    ref = transformer.speculative_generate(cfg, params, SPEC_DRAFT,
                                           dparams, toks, new, n_draft=k)
    depth = transformer.speculative_cache_depth(9, new, k)
    alloc = transformer.PageAllocator(n_pages=16, page_size=8)
    pyrandom.Random(2).shuffle(alloc.free)
    for i in range(2):
        alloc.ensure(i, depth)
    pcache = transformer.init_paged_cache(cfg, 16, page_size=8)
    pcache["pages"] = alloc.table(range(2))
    got = transformer.speculative_generate(
        cfg, params, SPEC_DRAFT, dparams, toks, new, n_draft=k,
        cache=pcache)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def test_speculative_stop_token():
    """stop_token in speculative decoding: rows freeze once a committed
    token is the stop (loop exits early); tokens up to each row's first
    stop equal the target's greedy continuation, and an absent stop
    changes nothing."""
    cfg = transformer.TransformerConfig(
        vocab_size=64, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=64, max_seq_len=256, dtype=jnp.float32)
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    dparams = transformer.init_params(SPEC_DRAFT, jax.random.PRNGKey(7))
    toks = jax.random.randint(jax.random.PRNGKey(1), (3, 7), 0,
                              cfg.vocab_size)
    plain = np.asarray(transformer.generate(cfg, params, toks, 12))
    gen = plain[:, 7:]
    stop = int(gen[0, 4])
    spec = np.asarray(transformer.speculative_generate(
        cfg, params, SPEC_DRAFT, dparams, toks, 12, n_draft=3,
        stop_token=stop))
    for i in range(3):
        hits = np.where(gen[i] == stop)[0]
        cut = hits[0] if len(hits) else 11
        np.testing.assert_array_equal(spec[i, 7:7 + cut + 1],
                                      gen[i][:cut + 1])
    absent = next(v for v in range(64)
                  if v not in set(gen.ravel().tolist()))
    spec2 = np.asarray(transformer.speculative_generate(
        cfg, params, SPEC_DRAFT, dparams, toks, 12, n_draft=3,
        stop_token=absent))
    np.testing.assert_array_equal(spec2, plain)


def test_page_allocator_randomized_stress():
    """Random ensure/release traffic: rows never share pages, frees
    recycle, and capacity accounting stays exact."""
    import random as pyrandom

    rng = pyrandom.Random(0)
    alloc = transformer.PageAllocator(n_pages=64, page_size=8)
    live = set()
    for step in range(300):
        if live and rng.random() < 0.4:
            row = rng.choice(sorted(live))
            alloc.release(row)
            live.discard(row)
        else:
            row = rng.randrange(16)
            need = rng.randrange(1, 60)
            try:
                alloc.ensure(row, need)
                live.add(row)
            except RuntimeError:
                pass  # exhausted: fine, keep trading
        used = [p for r in alloc.rows.values() for p in r]
        assert len(used) == len(set(used))          # no sharing
        assert len(used) + len(alloc.free) == 64    # exact accounting


def test_split_heads_is_a_reshape_behind_a_barrier():
    """``_split_heads``: the values and gradient of a plain reshape, and the
    barrier that keeps the TPU compiler from folding the split into the
    projection's dot (``tests/test_tpu_compile.py`` holds what that buys)."""
    y = jnp.arange(2 * 3 * 8, dtype=jnp.float32).reshape(2, 3, 8)
    out = transformer._split_heads(y, 4)
    assert out.shape == (2, 3, 4, 2)
    np.testing.assert_array_equal(out, y.reshape(2, 3, 4, 2))
    grad = jax.grad(lambda a: (transformer._split_heads(a, 4) ** 2).sum())(y)
    np.testing.assert_array_equal(grad, 2 * y)
    text = jax.jit(lambda a: transformer._split_heads(a, 4)).lower(y).as_text()
    assert "optimization_barrier" in text


# -- decode_step's layer scan reads the weights where the stack lies (PR 49) --
#
# The scan runs over the layer index and the body takes its layer of every
# stacked leaf; until PR 49 the stack was the scan's ``xs``.  Same arithmetic
# in the same order: logits and cache are bit-identical to the ``xs`` form's,
# rolled (a page pool at any width, a short linear buffer) or unrolled by two
# (a linear buffer from 8,192 slots).

def _decode_step_over_xs(cfg, params, cache, tokens, pos):
    """``decode_step`` for a plain stack as it stood: the stacked weights
    as the (rolled) layer scan's ``xs``."""
    x, positions, _ = transformer._embed_chunk(cfg, params, tokens, pos)
    pages = cache.get("pages")

    def body(carry, layer):
        x, ck, cv = carry
        li, lp = layer
        x, ck, cv, chunks = transformer._block_decode(
            cfg, x, lp, ck, cv, li, positions, pos, pages=pages)
        return (x, ck, cv), chunks

    (x, new_k, new_v), chunks = jax.lax.scan(
        body, (x, cache["k"], cache["v"]),
        (jnp.arange(cfg.n_layers, dtype=jnp.int32), params["layers"]))
    if chunks is not None:
        new_k = transformer._paged_cache_write_all(new_k, chunks[0], pages,
                                                   pos)
        new_v = transformer._paged_cache_write_all(new_v, chunks[1], pages,
                                                   pos)
    out = {"k": new_k, "v": new_v}
    if pages is not None:
        out["pages"] = pages
    return transformer._final_logits(cfg, params, x), out


def _scan_toy(dtype=jnp.float32, int8=False):
    cfg = transformer.TransformerConfig(
        vocab_size=64, d_model=32, n_layers=4, n_heads=4, n_kv_heads=2,
        d_ff=64, max_seq_len=8192, dtype=dtype)
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, (transformer.quantize_params(cfg, params) if int8
                 else params)


def _scan_cache(cfg, kind, rows):
    """``paged``: a pool behind scrambled tables; ``short`` / ``long``: the
    linear buffer under / at the unroll's 8,192 slots."""
    if kind != "paged":
        return transformer.init_cache(cfg, rows,
                                      8192 if kind == "long" else 64)
    alloc = transformer.PageAllocator(n_pages=16, page_size=8)
    np.random.RandomState(3).shuffle(alloc.free)
    for i in range(rows):
        alloc.ensure(i, 16)
    cache = transformer.init_paged_cache(cfg, 16, page_size=8)
    cache["pages"] = alloc.table(range(rows))
    return cache


def _layer_scans(jaxpr, n_layers):
    """The ``unroll`` of every scan of ``n_layers`` iterations in ``jaxpr``
    or under it."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan" and eqn.params["length"] == n_layers:
            found.append(eqn.params["unroll"])
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _layer_scans(sub, n_layers)
    return found


@pytest.mark.parametrize("chunk", ["t1_ragged", "t5"])
@pytest.mark.parametrize("weights", ["bf16", "int8"])
@pytest.mark.parametrize("kind", ["paged", "short", "long"])
def test_decode_step_scan_matches_weights_as_xs(kind, weights, chunk):
    cfg, params = _scan_toy(jnp.bfloat16 if weights == "bf16"
                            else jnp.float32, int8=weights == "int8")
    rows = 3
    toks = jax.random.randint(jax.random.PRNGKey(1), (rows, 9), 0,
                              cfg.vocab_size)
    new = jax.jit(lambda c, tok, pos: transformer.decode_step(
        cfg, params, c, tok, pos))
    old = jax.jit(lambda c, tok, pos: _decode_step_over_xs(
        cfg, params, c, tok, pos))
    cache = _scan_cache(cfg, kind, rows)
    if chunk == "t5":
        args = (cache, toks[:, :5], 0)
    else:       # a row each at its own position, over a filled cache
        _, cache = new(cache, toks[:, :8], 0)
        args = (cache, toks[:, 8:], jnp.asarray([5, 8, 3], jnp.int32))
    got, want = new(*args), old(*args)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert np.isfinite(np.asarray(got[0], np.float32)).all()


@pytest.mark.parametrize("kind,width,unroll", [
    ("paged", 128, 1),      # 128 pages of 64: a logical 8,192, rolled
    ("paged", 32, 1),
    ("long", None, 2),      # the linear buffer at 8,192 slots keeps its two
    ("short", None, 1)])
def test_decode_step_unrolls_the_linear_buffer_only(kind, width, unroll):
    cfg, params = _scan_toy()
    if kind == "paged":
        cache = transformer.init_paged_cache(cfg, 4, page_size=64)
        cache["pages"] = jnp.zeros((2, width), jnp.int32)
    else:
        cache = _scan_cache(cfg, kind, 2)
    jaxpr = jax.make_jaxpr(lambda c, tok, pos: transformer.decode_step(
        cfg, params, c, tok, pos))(
            cache, jnp.zeros((2, 1), jnp.int32), jnp.zeros((2,), jnp.int32))
    assert _layer_scans(jaxpr.jaxpr, cfg.n_layers) == [unroll]
