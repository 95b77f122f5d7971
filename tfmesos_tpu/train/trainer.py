"""Sharded training loop machinery.

The reference's training mechanics live in user scripts: per-worker sessions
pushing gradients to parameter servers, `SyncReplicasOptimizer` for sync SGD,
`Supervisor` for init/recovery (mnist_replica.py:116-210).  All of that
collapses here into one jit'd step over a GSPMD mesh: params carry
NamedShardings (FSDP/TP/etc.), the batch is sharded over the data axes, and
XLA inserts the gradient all-reduce that parameter servers used to be.
Sync-SGD is therefore the *default* semantics; async PS has no TPU analogue
(and converges worse anyway).
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Iterator, Optional

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tfmesos_tpu.parallel.sharding import (batch_sharding, data_axes,
                                           fsdp_sharding_tree, place_tree)
from tfmesos_tpu.utils.logging import get_logger
from tfmesos_tpu.utils.profiling import trace

log = get_logger("tfmesos_tpu.trainer")


@dataclass
class TrainState:
    params: Any
    opt_state: Any
    step: int = 0


def make_train_step(loss_fn: Callable, optimizer: optax.GradientTransformation,
                    mesh: Optional[Mesh] = None,
                    param_specs: Optional[Any] = None,
                    batch_spec_tree: Optional[Any] = None,
                    postprocess: Optional[Callable] = None,
                    steps_per_call: int = 1,
                    grad_accum: int = 1,
                    scan_unroll: int = 1,
                    grads_fn: Optional[Callable] = None):
    """Build the jit'd train step.

    ``loss_fn(params, batch) -> (loss, metrics)``.  With a mesh, params/opt
    state are placed per ``param_specs`` (default: FSDP rules) and the batch
    per ``batch_spec_tree`` (default: leading dim over data axes); buffers
    are donated so params update in place.  ``postprocess`` (e.g. the NMF
    non-negativity projection) runs on the updated params inside the step.

    ``steps_per_call > 1`` compiles a ``lax.scan`` of that many optimizer
    steps into ONE dispatch: batch leaves carry a leading ``[steps_per_call,
    ...]`` dim and the host pays one round-trip per K steps — the dominant
    cost for small models.
    Returned metrics are the last step's.

    ``scan_unroll`` unrolls the fused-step ``lax.scan`` body that many
    iterations (must divide ``steps_per_call``): for tiny models the
    per-iteration scan overhead dominates the math, and unrolling lets XLA
    fuse across consecutive optimizer steps — same arithmetic, fewer
    kernel launches.  Leave at 1 for models whose step is compute-bound.

    ``grad_accum > 1`` splits each step's batch into that many microbatches
    and averages their gradients before the single optimizer update — the
    full-batch step for losses that are per-example means (equal micro
    sizes), at 1/grad_accum the activation memory, since each microbatch's
    backward completes before the next begins.  Loss terms that are
    *batch statistics* (e.g. MoE load-balance fractions) are computed per
    microbatch, a slightly different objective.  Returned metrics are
    microbatch means.  The per-step batch dim must divide evenly (and stay
    divisible by the data-axis size).

    ``grads_fn(params, batch) -> (grads, loss, metrics)`` replaces the
    default ``jax.value_and_grad(loss_fn)`` pass for schedules autodiff
    cannot express — e.g. ``transformer.train_step_1f1b``'s fused-1F1B
    pipeline pass.  Exclusive with ``grad_accum`` (such passes microbatch
    internally); ``loss_fn`` is ignored when given.
    """

    if grads_fn is not None and grad_accum != 1:
        raise ValueError("grads_fn and grad_accum are exclusive: a custom "
                         "gradient pass (e.g. the 1F1B pipeline step) does "
                         "its own microbatching")

    def grads_and_metrics(params, batch):
        if grads_fn is not None:
            # Custom gradient pass — e.g. transformer.train_step_1f1b,
            # whose fused fwd+bwd schedule jax.value_and_grad cannot
            # express.  Contract: (grads, loss, metrics).
            return grads_fn(params, batch)
        if grad_accum == 1:
            (loss, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, batch)
            return grads, loss, metrics
        micro = jax.tree_util.tree_map(
            lambda x: x.reshape(grad_accum, x.shape[0] // grad_accum,
                                *x.shape[1:]), batch)

        def acc(carry, mb):
            gsum, lsum = carry
            (loss, metrics), g = jax.value_and_grad(
                loss_fn, has_aux=True)(params, mb)
            gsum = jax.tree_util.tree_map(jnp.add, gsum, g)
            return (gsum, lsum + loss), metrics

        zeros = jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params)
        (gsum, lsum), metrics = jax.lax.scan(
            acc, (zeros, jnp.zeros((), jnp.float32)), micro)
        grads = jax.tree_util.tree_map(
            lambda g, p: (g / grad_accum).astype(p.dtype), gsum, params)
        # Microbatch MEANS for every metric, matching the reported loss
        # (exp(mean loss) still differs from mean perplexity — means of
        # nonlinear metrics are approximations either way).
        mean_metrics = jax.tree_util.tree_map(lambda m: jnp.mean(m, axis=0),
                                              metrics)
        return grads, lsum / grad_accum, mean_metrics

    def one_step(params, opt_state, batch):
        grads, loss, metrics = grads_and_metrics(params, batch)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        if postprocess is not None:
            params = postprocess(params)
        metrics = dict(metrics)
        metrics["loss"] = loss
        return params, opt_state, metrics

    if scan_unroll < 1 or steps_per_call % scan_unroll:
        raise ValueError(f"scan_unroll ({scan_unroll}) must divide "
                         f"steps_per_call ({steps_per_call})")
    if steps_per_call == 1:
        step_fn = one_step
    else:
        def step_fn(params, opt_state, batch):
            def body(carry, micro):
                p, o = carry
                p, o, metrics = one_step(p, o, micro)
                return (p, o), metrics
            (params, opt_state), metrics = jax.lax.scan(
                body, (params, opt_state), batch, unroll=scan_unroll)
            last = jax.tree_util.tree_map(lambda m: m[-1], metrics)
            return params, opt_state, last

    if mesh is None:
        return jax.jit(step_fn, donate_argnums=(0, 1))

    def place(params, opt_state):
        p_sh = (jax.tree_util.tree_map(lambda s: NamedSharding(mesh, s),
                                       param_specs,
                                       is_leaf=lambda s: isinstance(s, P))
                if param_specs is not None else fsdp_sharding_tree(params, mesh))
        # Optimizer moments mirror the param shardings (matched by path, not
        # shape: e.g. wq/wo share a shape but carry transposed specs).
        o_sh = _opt_shardings(opt_state, params, p_sh, mesh)
        params = place_tree(mesh, params, p_sh)
        opt_state = place_tree(mesh, opt_state, o_sh)
        return params, opt_state

    bdim = 1 if steps_per_call > 1 else 0  # [K, B, ...] stacks shard on B

    def lift_spec(sh):
        """User-provided specs describe ONE step's batch; with a scanned
        step, prepend the (unsharded) steps dim."""
        if bdim == 0:
            return sh
        return NamedSharding(sh.mesh, P(None, *sh.spec))

    user_spec_tree = (jax.tree_util.tree_map(
        lift_spec, batch_spec_tree,
        is_leaf=lambda s: isinstance(s, NamedSharding))
        if batch_spec_tree is not None else None)

    def constrain(x):
        if user_spec_tree is not None:
            return jax.lax.with_sharding_constraint(x, user_spec_tree)
        dims = [None] * x.ndim
        if x.ndim > bdim:
            dims[bdim] = data_axes(mesh)
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, P(*dims)))

    def sharded_step(params, opt_state, batch):
        batch = jax.tree_util.tree_map(constrain, batch)
        return step_fn(params, opt_state, batch)

    jitted = jax.jit(sharded_step, donate_argnums=(0, 1))
    jitted.place = place  # type: ignore[attr-defined]
    return jitted


def make_bn_train_step(loss_and_stats_fn, optimizer, mesh: Optional[Mesh] = None):
    """Train step for models with non-differentiable collection state (batch
    norm): gradients flow through ``params`` only; the extra state threads
    through as data.

    ``loss_and_stats_fn(params, batch_stats, batch) -> (loss,
    (new_batch_stats, metrics))``.  State dict: ``{"params", "batch_stats",
    "opt_state"}``.  With a mesh, ``step.place(state)`` gives params and
    optimizer moments FSDP placement when the mesh has an ``fsdp`` axis
    (replicated otherwise) and batch_stats replicated — the "ps role
    collapses into parameter sharding" mapping, for real.
    """
    import optax

    def step(state, batch):
        if mesh is not None:
            batch = jax.tree_util.tree_map(
                lambda x: jax.lax.with_sharding_constraint(
                    x, batch_sharding(mesh)), batch)

        (loss, (batch_stats, metrics)), grads = jax.value_and_grad(
            loss_and_stats_fn, has_aux=True)(state["params"],
                                             state["batch_stats"], batch)
        updates, opt_state = optimizer.update(grads, state["opt_state"],
                                              state["params"])
        params = optax.apply_updates(state["params"], updates)
        out_metrics = dict(metrics)
        out_metrics["loss"] = loss
        return ({"params": params, "batch_stats": batch_stats,
                 "opt_state": opt_state}, out_metrics)

    jitted = jax.jit(step, donate_argnums=(0,))
    if mesh is not None:
        def place(state):
            p_sh = fsdp_sharding_tree(state["params"], mesh)
            o_sh = _opt_shardings(state["opt_state"], state["params"], p_sh,
                                  mesh)
            return {
                "params": place_tree(mesh, state["params"], p_sh),
                "batch_stats": place_tree(mesh, state["batch_stats"]),
                "opt_state": place_tree(mesh, state["opt_state"], o_sh),
            }
        jitted.place = place
    return jitted


def _opt_shardings(opt_state, params, param_shardings, mesh):
    """Sharding tree for an optax state: each moment leaf takes the sharding
    of the parameter whose pytree path is a suffix of the leaf's own path
    (optax moment trees — ``mu``/``nu`` etc. — mirror the params tree
    exactly, nested under state wrappers).  Scalars/counters replicate.
    Matching by path avoids aliasing distinct params that share a shape."""

    def path_key(path):
        return tuple(str(getattr(k, "key", getattr(k, "idx", getattr(k, "name", k))))
                     for k in path)

    p_leaves = jax.tree_util.tree_flatten_with_path(params)[0]
    s_leaves = jax.tree_util.tree_leaves(
        param_shardings, is_leaf=lambda s: isinstance(s, NamedSharding))
    by_path = {path_key(path): (leaf.shape, sh)
               for (path, leaf), sh in zip(p_leaves, s_leaves)}
    replicated = NamedSharding(mesh, P())

    def assign(path, leaf):
        key = path_key(path)
        shape = getattr(leaf, "shape", ())
        for i in range(len(key)):
            hit = by_path.get(key[i:])
            if hit and hit[0] == shape:
                return hit[1]
        return replicated

    return jax.tree_util.tree_map_with_path(assign, opt_state)


def make_eval_step(loss_fn: Callable, mesh: Optional[Mesh] = None):
    """Jit'd forward-only step: ``loss_fn(params, batch) -> (loss,
    metrics)`` becomes ``eval_step(params, batch) -> metrics`` (loss
    included).  With a mesh, the batch is constrained onto the data axes
    like the train step's."""

    def step(params, batch):
        if mesh is not None:
            batch = jax.tree_util.tree_map(
                lambda x: jax.lax.with_sharding_constraint(
                    x, batch_sharding(mesh)), batch)
        loss, metrics = loss_fn(params, batch)
        out = dict(metrics)
        out["loss"] = loss
        return out

    return jax.jit(step)


def evaluate(eval_step: Callable, params, batches: Iterator,
             num_batches: int) -> Dict[str, float]:
    """Run ``num_batches`` eval steps and return the metric means — the
    validation half of the reference's trainers (mnist_replica.py:216-226
    evaluated once at the end; this is the reusable form)."""
    acc: Dict[str, list] = {}
    for _ in range(num_batches):
        # Keep device arrays: no host sync inside the loop, so batch N+1
        # dispatches while batch N still runs.
        for k, v in eval_step(params, next(batches)).items():
            acc.setdefault(k, []).append(v)
    return {k: float(sum(jnp.stack(vs)) / num_batches)
            for k, vs in acc.items()}


@dataclass
class TrainLoop:
    """Step loop with timing — the measurement point for the project metric
    (BASELINE.md: steps/sec/chip).

    ``metrics_path`` appends one JSON line per logged step
    (``{"step": N, "wall_s": ..., **metrics}``) — a machine-readable
    training curve with no dashboard dependency.

    ``checkpoint`` (a :class:`~tfmesos_tpu.train.checkpoint.
    CheckpointManager`) coordinates restart recovery: :meth:`resume`
    restores the latest saved ``TrainState`` (step offset included) before
    a run, and :meth:`run` saves every ``save_every`` global steps —
    ``save_async=True`` overlaps the Orbax write with the next steps.  The
    ``restores``/``resumed_step`` counters surface how a supervised job
    actually recovered (they ride the result dict too)."""

    step_fn: Callable
    state: TrainState
    log_every: int = 50
    name: str = "train"
    metrics_path: Optional[str] = None
    checkpoint: Optional[Any] = None
    save_every: int = 0
    save_async: bool = False
    restores: int = 0
    resumed_step: int = 0

    def state_dict(self) -> Dict[str, Any]:
        """The checkpointable form of ``state`` (also the restore
        template: leaves keep their shapes/dtypes/shardings)."""
        return {"params": self.state.params,
                "opt_state": self.state.opt_state,
                "step": jnp.asarray(self.state.step)}

    def resume(self) -> int:
        """Restore the latest checkpoint (if any) into ``state`` and
        return the step to resume from (0 on a cold start).  The caller
        owns realigning its batch iterator to that step — see
        ``supervisor.supervise_training`` for the stock skip-ahead."""
        if self.checkpoint is None:
            return 0
        restored = self.checkpoint.restore(self.state_dict())
        if restored is None:
            return 0
        self.state = TrainState(restored["params"], restored["opt_state"],
                                int(restored["step"]))
        self.restores += 1
        self.resumed_step = self.state.step
        log.info("%s resuming from checkpoint step %d", self.name,
                 self.state.step)
        return self.state.step

    def run(self, batches: Iterator[Dict[str, Any]], num_steps: int,
            on_metrics: Optional[Callable[[int, Dict], None]] = None) -> Dict[str, Any]:
        import json

        params, opt_state = self.state.params, self.state.opt_state
        t_start = time.perf_counter()
        metrics = {}
        sink = open(self.metrics_path, "a") if self.metrics_path else None

        def run_step(i):
            nonlocal params, opt_state, metrics
            batch = next(batches)
            params, opt_state, metrics = self.step_fn(params, opt_state, batch)
            gstep = self.state.step + i + 1
            if (self.checkpoint is not None and self.save_every
                    and gstep % self.save_every == 0):
                self.checkpoint.save(
                    gstep, {"params": params, "opt_state": opt_state,
                            "step": jnp.asarray(gstep)},
                    wait=not self.save_async)
            if (i + 1) % self.log_every == 0 or i + 1 == num_steps:
                metrics = {k: float(v) for k, v in metrics.items()}
                if sink:
                    sink.write(json.dumps(
                        {"step": gstep,
                         "wall_s": round(time.perf_counter() - t_start, 3),
                         **metrics}) + "\n")
                    sink.flush()
                if on_metrics:
                    on_metrics(i + 1, metrics)
                else:
                    log.info("%s step %d: %s", self.name, i + 1,
                             {k: round(v, 4) for k, v in metrics.items()})

        # Profile a bounded window, not the whole run: an unbounded trace of
        # a long job is multi-GB and unopenable.  No-op unless
        # TPUMESOS_TRACE_DIR is exported.
        import os
        traced = min(num_steps,
                     int(os.environ.get("TPUMESOS_TRACE_STEPS", "20")))
        try:
            with trace():
                for i in range(traced):
                    run_step(i)
            for i in range(traced, num_steps):
                run_step(i)
            jax.block_until_ready(params)
            if self.checkpoint is not None and self.save_async:
                self.checkpoint.wait_until_finished()
        finally:
            if sink:
                sink.close()
        elapsed = time.perf_counter() - t_start
        start_step = self.state.step
        self.state = TrainState(params, opt_state, start_step + num_steps)
        n_dev = max(1, jax.device_count())
        return {
            "elapsed_s": elapsed,
            "steps_per_sec": num_steps / elapsed,
            "steps_per_sec_per_chip": num_steps / elapsed / n_dev,
            "start_step": start_step,
            "final_step": self.state.step,
            "restores": self.restores,
            "resumed_step": self.resumed_step,
            "final_metrics": metrics,
        }
