"""Functions shipped to Mode-A tasks by the integration tests (must be
importable on the task side; the scheduler forwards sys.path)."""

import os


def ping(ctx, value):
    return {"rank": ctx.rank, "world": ctx.world_size,
            "job": f"{ctx.job_name}:{ctx.task_index}", "value": value}


def read_env(ctx, name):
    return os.environ.get(name)


def runtime_topology(ctx):
    """Regression probe for the silent-degradation bug: if distributed init
    quietly fails, each process sees only its own devices and process_count
    collapses to 1 while everything else still 'works'."""
    import jax
    return {"process_count": jax.process_count(),
            "device_count": jax.device_count(),
            "world_size": ctx.world_size}


def sharded_sum(ctx, total):
    """Distributed 'plus': a global array sharded across every process's
    devices, reduced with an XLA collective — 42 the TPU way."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = ctx.mesh()
    n = mesh.size
    # axis-agnostic: the default axis is dp, or fsdp when ps jobs exist
    sharding = NamedSharding(mesh, P(mesh.axis_names[0]))
    arr = jax.make_array_from_callback(
        (n,), sharding, lambda idx: np.array([total / n], dtype=np.float32))
    out = jax.jit(jnp.sum, out_shardings=NamedSharding(mesh, P()))(arr)
    return float(out)


def my_pid(ctx):
    return os.getpid()


def _place(mesh, tree, specs):
    """Place host-identical values as GLOBAL arrays on a (possibly
    cross-process) mesh: a spec-tree front-end over
    ``parallel.sharding.place_tree``."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from tfmesos_tpu.parallel.sharding import place_tree

    shardings = jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), specs,
        is_leaf=lambda n: isinstance(n, P))
    return place_tree(mesh, tree, shardings)


def multiaxis_train_step(ctx, axes):
    """One fused-CE transformer train step on a mesh whose MODEL axes may
    cross process boundaries (the production shape of the north star:
    tp/fsdp collectives spanning hosts — VERDICT r3 missing #2).  Returns
    topology + loss so the driver test can assert real cross-process
    collective participation, not just per-process math."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from tfmesos_tpu.models import transformer
    from tfmesos_tpu.models.transformer import _fused_ce_mode
    from tfmesos_tpu.parallel.mesh import build_mesh
    from tfmesos_tpu.parallel.sharding import batch_spec

    mesh = build_mesh(axes)
    tp = mesh.shape.get("tp", 1)
    heads = 2 * tp
    cfg = transformer.TransformerConfig(
        vocab_size=128, d_model=heads * 8, n_layers=2, n_heads=heads,
        d_ff=4 * heads * 8, max_seq_len=16, dtype=jnp.float32)
    params_host = transformer.init_params(cfg, jax.random.PRNGKey(0))
    specs = transformer.partition_specs(cfg, mesh)
    params = _place(mesh, params_host, specs)
    nd = 1
    for a in ("dp", "fsdp"):
        nd *= mesh.shape.get(a, 1)
    tokens = np.random.RandomState(0).randint(
        0, cfg.vocab_size, size=(2 * nd, 17)).astype(np.int32)
    batch = _place(mesh, {"tokens": tokens},
                   {"tokens": batch_spec(mesh, extra_dims=1)})

    @jax.jit
    def step(p, b):
        (l, _), g = jax.value_and_grad(
            lambda p_: transformer.loss_fn(cfg, p_, b, mesh),
            has_aux=True)(p)
        new = jax.tree_util.tree_map(lambda w, gg: w - 1e-2 * gg, p, g)
        return l, new

    loss, new_params = step(params, batch)
    jax.block_until_ready(new_params)
    return {"process_count": jax.process_count(),
            "device_count": jax.device_count(),
            "mesh_shape": dict(mesh.shape),
            "fused_mode": _fused_ce_mode(cfg, params_host, mesh),
            "loss": float(loss)}


def multiaxis_ragged_decode(ctx, axes):
    """One sharded ragged decode step (GSPMD: params per partition_specs,
    cache per cache_specs) across the cross-process mesh."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from tfmesos_tpu.models import transformer
    from tfmesos_tpu.parallel.mesh import build_mesh

    mesh = build_mesh(axes)
    tp = mesh.shape.get("tp", 1)
    cfg = transformer.TransformerConfig(
        vocab_size=128, d_model=32, n_layers=2, n_heads=max(4, tp),
        n_kv_heads=max(4, tp), d_ff=64, max_seq_len=64, dtype=jnp.float32)
    b = 1
    for a in ("dp", "fsdp"):
        b *= mesh.shape.get(a, 1)
    params = _place(mesh, transformer.init_params(cfg, jax.random.PRNGKey(6)),
                    transformer.partition_specs(cfg, mesh))
    cache = _place(mesh, transformer.init_cache(cfg, b, 64),
                   transformer.cache_specs(cfg, mesh))
    prompt = np.random.RandomState(5).randint(
        0, cfg.vocab_size, size=(b, 9)).astype(np.int32)
    repl = NamedSharding(mesh, P())

    @jax.jit
    def prefill(p, c, t):
        return transformer.decode_step(cfg, p, c, t, 0, sharded=True)

    _, cache = prefill(params, cache,
                       _place(mesh, prompt, P()))
    lens = np.random.RandomState(6).randint(2, 10, size=(b,)).astype(np.int32)
    tok = np.take_along_axis(prompt, (lens - 1)[:, None], axis=1)

    @jax.jit
    def ragged(p, c, t, pv):
        lg, _ = transformer.decode_step(cfg, p, c, t, pv, sharded=True)
        return jax.lax.with_sharding_constraint(
            jnp.all(jnp.isfinite(lg.astype(jnp.float32))), repl)

    finite = ragged(params, cache, _place(mesh, tok, P()),
                    _place(mesh, lens, P()))
    return {"process_count": jax.process_count(),
            "device_count": jax.device_count(),
            "mesh_shape": dict(mesh.shape),
            "logits_finite": bool(finite)}


def hybrid_mesh_probe(ctx, axes):
    """Build a hybrid DCN mesh through the real cross-process plumbing and
    report whether every tp group stays inside one process (= its
    collectives ride intra-process links, never the 'DCN' boundary)."""
    import jax
    from tfmesos_tpu.parallel.mesh import build_mesh

    mesh = build_mesh(axes)
    arr = mesh.devices    # ordered [dp, tp] for {"dp": n, "tp": m}
    tp_groups_intra = all(
        len({d.process_index for d in row}) == 1 for row in arr)
    dp_crosses = len({d.process_index for d in arr[:, 0]}) > 1
    return {"process_count": jax.process_count(),
            "device_count": jax.device_count(),
            "mesh_shape": dict(mesh.shape),
            "tp_groups_intra_process": tp_groups_intra,
            "dp_axis_crosses_processes": dp_crosses}


def sleep_forever(ctx, seconds=60.0):
    import time
    time.sleep(seconds)
    return "woke"


def train_chunk(ctx, params, k, lr, seed):
    """One dispatched chunk of k sync-SGD steps on the cluster mesh: batch
    sharded over every process, grads reduced by GSPMD collectives (the
    dp training loop a real driver runs via repeated cluster.run calls)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = ctx.mesh()
    ax = mesh.axis_names[0]
    repl = NamedSharding(mesh, P())
    w = jax.device_put(jnp.asarray(np.asarray(params["w"], np.float32)), repl)
    bs = 8 * mesh.size

    @jax.jit
    def step(w, x, y):
        def loss_fn(w):
            logits = x @ w
            logp = jax.nn.log_softmax(logits)
            return -jnp.mean(jnp.take_along_axis(logp, y[:, None], 1))
        loss, g = jax.value_and_grad(loss_fn)(w)
        return w - lr * g, loss

    rng = np.random.RandomState(seed)
    data_sh = NamedSharding(mesh, P(ax))
    loss = None
    for _ in range(k):
        xb = rng.randn(bs, 16).astype(np.float32)
        yb = (rng.randint(0, 4, size=bs)).astype(np.int32)
        x = jax.make_array_from_callback((bs, 16), data_sh,
                                         lambda idx: xb[idx])
        y = jax.make_array_from_callback((bs,), data_sh, lambda idx: yb[idx])
        w, loss = step(w, x, y)
    return {"w": np.asarray(w).tolist(), "loss": float(loss)}


def train_chunk_numpy(ctx, params, k, lr, seed):
    """A dispatched chunk of k softmax-regression SGD steps in PURE numpy
    (no jax — runs under extra_config={"no_jax": True}): bitwise
    deterministic given (params, seed), so chaos tests can assert a
    kill-recover-resume run reaches EXACTLY the loss of an uninterrupted
    one.  Every rank computes the same update; rank 0's result is the
    driver's."""
    import numpy as np

    w = np.asarray(params["w"], np.float32)
    rng = np.random.RandomState(seed)
    loss = None
    for _ in range(k):
        x = rng.randn(8, 16).astype(np.float32)
        y = rng.randint(0, 4, size=8)
        z = x @ w
        z -= z.max(axis=1, keepdims=True)
        p = np.exp(z)
        p /= p.sum(axis=1, keepdims=True)
        loss = float(-np.mean(np.log(p[np.arange(8), y] + 1e-12)))
        g = p
        g[np.arange(8), y] -= 1.0
        w = w - lr * (x.T @ (g / 8.0))
    return {"w": w.tolist(), "loss": loss}


def _cb_workload():
    """The continuous-batching cross-process workload, shared by the
    task-side entry point and the test's single-host reference."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from tfmesos_tpu.models import transformer
    from tfmesos_tpu.serving import Request

    cfg = transformer.TransformerConfig(
        vocab_size=128, d_model=32, n_layers=2, n_heads=4, n_kv_heads=4,
        d_ff=64, max_seq_len=64, dtype=jnp.float32)
    params = transformer.init_params(cfg, jax.random.PRNGKey(6))
    rng = np.random.RandomState(9)
    prompts = [rng.randint(0, 128, size=n).astype(np.int32)
               for n in (3, 9, 6, 12)]
    reqs = [Request(prompt=p, max_new_tokens=2 + (i % 3))
            for i, p in enumerate(prompts)]
    kw = dict(rows=2, max_len=32, page_size=8, prefill_bucket=8)
    return cfg, params, reqs, kw


def continuous_batching_mesh(ctx, axes, pipeline_depth=0):
    """Multi-chip continuous batching across the cross-process mesh: every
    process runs the identical admission loop, decode rides the dp x tp
    sharded paged pool (shard-local page tables), and host-read tokens are
    replicated — each process must yield the same completions.
    ``pipeline_depth=1`` additionally runs the lagged (device-carry) loop."""
    import jax
    from tfmesos_tpu.parallel.mesh import build_mesh
    from tfmesos_tpu.serving import ContinuousBatcher

    cfg, params, reqs, kw = _cb_workload()
    b = ContinuousBatcher(cfg, params, mesh=build_mesh(axes),
                          pipeline_depth=pipeline_depth, **kw)
    done = {c.rid: c.tokens for c in b.run(reqs)}
    return {"process_count": jax.process_count(),
            "device_count": jax.device_count(),
            "tokens": {str(k): [int(t) for t in v]
                       for k, v in sorted(done.items())}}

