"""Flagship transformer trainer: long-context + multi-axis parallelism.

Nothing in the reference reaches this scale (SURVEY §2.7: no TP/PP/SP/EP
anywhere); this example is the framework's showcase workload.  The mesh
comes from ``--mesh`` (tfrun flag or scheduler kwarg): sequence shards over
``sp`` (ring attention), heads/ff over ``tp``, experts over ``ep``, batch
over ``dp``/``fsdp``.

Local smoke (8 virtual CPU devices, 1 process):

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/transformer_train.py --mesh dp=2,sp=2,tp=2 --tiny

Cluster run:

    python bin/tfrun -w 4 -s 0 --mesh dp=2,sp=2 -- \
        python examples/transformer_train.py --steps 100
"""

import argparse
import sys
import time


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--steps", type=int, default=50)
    p.add_argument("--batch_size", type=int, default=8, help="global batch")
    p.add_argument("--seq_len", type=int, default=2048)
    p.add_argument("--learning_rate", type=float, default=3e-4)
    p.add_argument("--warmup", type=int, default=0,
                   help="linear LR warmup steps")
    p.add_argument("--lr-schedule", choices=["constant", "cosine"],
                   default="constant", dest="lr_schedule",
                   help="decay after warmup: constant or cosine to 10%% "
                        "of peak over --steps")
    p.add_argument("--grad-clip", type=float, default=0.0, dest="grad_clip",
                   help="global-norm gradient clipping (0 = off)")
    p.add_argument("--mesh", type=str, default=None,
                   help="override mesh axes, e.g. dp=2,sp=2,tp=2 (default: "
                        "cluster-provided or all-dp)")
    p.add_argument("--moe", type=int, default=0,
                   help="number of experts (0 = dense); uses the switch "
                        "all_to_all path when the mesh has an ep axis")
    p.add_argument("--top-k", type=int, default=1, dest="top_k",
                   help="experts per token on the switch path")
    p.add_argument("--pp-schedule", choices=["gpipe", "circular", "1f1b"],
                   default="gpipe", dest="pp_schedule",
                   help="pipeline schedule when the mesh has a pp axis "
                        "(1f1b: fused fwd+bwd step with an O(pp) "
                        "activation stash; dense configs, pp x dp only)")
    p.add_argument("--virtual-stages", type=int, default=1,
                   dest="virtual_stages",
                   help="interleaved chunks per pp device (circular only)")
    p.add_argument("--kv-heads", type=int, default=None, dest="kv_heads",
                   help="grouped-query attention: share each K/V head "
                        "across n_heads/kv_heads query heads")
    p.add_argument("--sp-impl", choices=["ring", "ulysses"], default="ring",
                   dest="sp_impl",
                   help="sequence parallelism over the sp axis: ppermute "
                        "ring or Ulysses all_to_all (heads %% sp == 0)")
    p.add_argument("--data", type=str, default=None,
                   help="path to a flat token file (TokenFileDataset "
                        "format); default: the synthetic bigram stream")
    p.add_argument("--data-dtype", type=str, default="uint16",
                   dest="data_dtype", choices=["uint16", "uint32"])
    p.add_argument("--tiny", action="store_true")
    p.add_argument("--ckpt-dir", type=str, default=None, dest="ckpt_dir",
                   help="Orbax checkpoint directory; restarting with the "
                        "same dir resumes from the latest step (params, "
                        "optimizer state AND the data stream position)")
    p.add_argument("--ckpt-every", type=int, default=50, dest="ckpt_every")
    args = p.parse_args()

    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import NamedSharding
    from tfmesos_tpu import runtime
    from tfmesos_tpu.cli import parse_mesh
    from tfmesos_tpu.models import transformer
    from tfmesos_tpu.parallel.sharding import batch_spec
    from tfmesos_tpu.train import data as datalib
    from tfmesos_tpu.train.trainer import make_train_step

    ctx = runtime.initialize()
    mesh = ctx.mesh(parse_mesh(args.mesh))
    # 1f1b is a TRAIN-step schedule (transformer.train_step_1f1b below);
    # forward-only paths (eval/generation) keep gpipe.
    fwd_schedule = "gpipe" if args.pp_schedule == "1f1b" \
        else args.pp_schedule
    # 1F1B differentiates INSIDE the stage shard_map, which the dense
    # top-k MoE supports (in-body-AD f/g collectives); switch dispatch
    # stays with the outer-AD schedules.  On a pp-less mesh the 1F1B
    # step never runs (see grads_fn guard below), so switch stays.
    moe_impl = ("dense" if args.pp_schedule == "1f1b"
                and mesh.shape.get("pp", 1) > 1 else "switch")
    if args.tiny:
        cfg = transformer.TransformerConfig(
            vocab_size=256, d_model=64,
            # Enough layers for the requested pipeline chunking (pp x
            # virtual stages), else the tiny default.
            n_layers=max(2, mesh.shape.get("pp", 1)
                         * args.virtual_stages),
            n_heads=max(4, 2 * mesh.shape.get("tp", 1)), d_ff=128,
            max_seq_len=args.seq_len, dtype=jnp.float32,
            n_experts=args.moe, top_k=args.top_k, moe_impl=moe_impl,
            pp_schedule=fwd_schedule, n_kv_heads=args.kv_heads,
            pp_virtual_stages=args.virtual_stages, sp_impl=args.sp_impl)
        seq_len = min(args.seq_len, 64 * max(1, mesh.shape.get("sp", 1)))
    else:
        cfg = transformer.TransformerConfig(
            vocab_size=8192, d_model=512, n_layers=8, n_heads=8, d_ff=1408,
            max_seq_len=args.seq_len, n_experts=args.moe,
            top_k=args.top_k, moe_impl=moe_impl,
            pp_schedule=fwd_schedule, n_kv_heads=args.kv_heads,
            pp_virtual_stages=args.virtual_stages, sp_impl=args.sp_impl)
        seq_len = args.seq_len
    if ctx.is_chief:
        dev = jax.devices()[0]
        print(f"device: platform={dev.platform} kind={dev.device_kind!r} "
              f"count={jax.device_count()}", flush=True)
        print(f"transformer: mesh={dict(mesh.shape)} seq={seq_len} "
              f"experts={cfg.n_experts}", flush=True)

    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    if args.lr_schedule == "cosine" or args.warmup:
        # warmup=0 starts at peak (no wasted lr=0 step); degenerate step
        # counts clamp so the cosine window is always >= 1 step.
        lr = optax.warmup_cosine_decay_schedule(
            init_value=(0.0 if args.warmup else args.learning_rate),
            peak_value=args.learning_rate,
            warmup_steps=args.warmup,
            decay_steps=max(args.steps, args.warmup + 1),
            end_value=(args.learning_rate * 0.1
                       if args.lr_schedule == "cosine"
                       else args.learning_rate))
    else:
        lr = args.learning_rate
    opt = optax.adamw(lr, weight_decay=0.01)
    if args.grad_clip > 0:
        opt = optax.chain(optax.clip_by_global_norm(args.grad_clip), opt)
    grads_fn = None
    if args.pp_schedule == "1f1b" and mesh.shape.get("pp", 1) > 1:
        def grads_fn(p_, b_):
            loss, grads = transformer.train_step_1f1b(cfg, p_, b_, mesh)
            return grads, loss, {"perplexity": jnp.exp(loss)}

    step = make_train_step(
        lambda p_, b_: transformer.loss_fn(cfg, p_, b_, mesh), opt, mesh=mesh,
        param_specs=transformer.partition_specs(cfg, mesh),
        batch_spec_tree=NamedSharding(mesh, batch_spec(mesh, extra_dims=1)),
        grads_fn=grads_fn)
    params, opt_state = step.place(params, opt.init(params))

    start_step = 0
    ckpt = None
    if args.ckpt_dir:
        if args.ckpt_every < 1:
            raise SystemExit(f"--ckpt-every must be >= 1, got "
                             f"{args.ckpt_every}")
        from tfmesos_tpu.train.checkpoint import CheckpointManager
        ckpt = CheckpointManager(args.ckpt_dir)
        latest = ckpt.latest_step()
        if latest is not None:
            params, opt_state = ckpt.restore((params, opt_state))
            start_step = latest
            if ctx.is_chief:
                print(f"resumed from step {start_step}", flush=True)
            if start_step >= args.steps:
                ckpt.close()
                if ctx.is_chief:
                    print(f"already trained to step {start_step} "
                          f">= --steps {args.steps}; nothing to do",
                          flush=True)
                return 0

    local_bs = max(1, args.batch_size // max(1, ctx.world_size))
    global_bs = local_bs * max(1, ctx.world_size)
    if args.data:
        ds = datalib.TokenFileDataset(args.data, dtype=args.data_dtype)
        # One full scan at startup: ids beyond the model's vocab would be
        # silently clamped by the embedding gather on TPU — corrupt
        # training with a plausible loss curve.  Fail loudly instead.
        top = int(ds.tokens.max())
        if top >= cfg.vocab_size:
            raise SystemExit(
                f"{args.data}: token id {top} >= model vocab "
                f"{cfg.vocab_size}; re-tokenize or adjust the config")
        stream = ds.batches(local_bs, seq_len, rank=ctx.rank,
                            world_size=max(1, ctx.world_size),
                            seed=100 + ctx.rank, start_step=start_step)
    else:
        stream = datalib.token_batches(local_bs, seq_len, cfg.vocab_size,
                                       seed=100 + ctx.rank,
                                       start_step=start_step)
    gen = datalib.prefetch(stream, mesh=mesh)
    t0 = time.perf_counter()
    metrics = {}
    for i in range(start_step, args.steps):
        params, opt_state, metrics = step(params, opt_state, next(gen))
        if ctx.is_chief and (i + 1) % 10 == 0:
            print(f"step {i + 1}: loss={float(metrics['loss']):.4f} "
                  f"ppl={float(metrics['perplexity']):.2f}", flush=True)
        if ckpt is not None and (i + 1) % args.ckpt_every == 0:
            ckpt.save(i + 1, (params, opt_state), wait=False)
    final_loss = float(metrics["loss"])  # host fetch drains the chain
    if ckpt is not None:
        if start_step < args.steps and args.steps % args.ckpt_every:
            ckpt.save(args.steps, (params, opt_state), wait=False)
        ckpt.close()
    dt = time.perf_counter() - t0
    if ctx.is_chief:
        tokens_per_sec = max(0, args.steps - start_step) * global_bs \
            * seq_len / dt
        print(f"Training elapsed time: {dt:f} s", flush=True)
        print(f"tokens/sec: {tokens_per_sec:.0f} "
              f"(per chip: {tokens_per_sec / jax.device_count():.0f})",
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
