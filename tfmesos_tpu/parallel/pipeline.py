"""Pipeline parallelism over the ``pp`` mesh axis.

Another axis the reference never had (SURVEY §2.7).  Layers are grouped into
stages whose parameters are *stacked* along a leading dim and sharded over
``pp`` — so each device holds one stage (or ``virtual_stages`` chunks of
one) — and microbatches flow through the ring with one ``ppermute`` hop per
tick.  All devices run every tick (SPMD).

Three schedules:

* ``"gpipe"`` — fill/drain; bubble fraction (S−1)/(M+S−1).
* ``"circular"`` — interleaved virtual stages: each device holds ``v``
  round-robin layer chunks and every microbatch laps the ring ``v`` times,
  shrinking the bubble to ≈(S−1)/(M·v) at the cost of v× more ppermute hops
  (tiny activations vs. the per-chunk matmuls they overlap with).
* 1F1B — same bubble as gpipe but forward and backward interleaved in one
  loop, bounding the live activation stash at S microbatch inputs instead
  of M.  Lives in :func:`pipeline_train_1f1b` (a fused train-step entry
  point) because autodiff of a forward-only schedule necessarily replays
  all-forwards-then-all-backwards.

Composes with dp/fsdp (activations stay sharded on their batch dims) AND
with tp: the stage body runs inside the full-mesh ``shard_map``, and
``param_partition`` shards each stage's weights over non-pp axes
(Megatron-style column/row splits).  What a stage must NOT do is open a
nested ``shard_map`` — write manual-collective stage bodies instead.
Under gpipe/circular (differentiated from OUTSIDE the shard_map) plain
``jax.lax.psum(..., "tp")`` collectives are fine
(models/transformer.py:_block_manual_tp is the worked example); under
1F1B the backward runs ``jax.vjp`` INSIDE the shard_map, where plain
psum's transpose double-counts — use the Megatron f/g pair
``collectives.broadcast_replicated_grad`` /
``collectives.psum_replicated_grad`` there (see
:func:`pipeline_train_1f1b`).
"""

from __future__ import annotations

from typing import Any, Callable, Optional, Sequence

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tfmesos_tpu.parallel.collectives import ppermute_shift
from tfmesos_tpu.parallel.sharding import data_axes


def stack_stage_params(stage_params: Sequence[Any]) -> Any:
    """Stack per-stage parameter pytrees along a new leading 'pp' dim."""
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *stage_params)


def stage_sharding_tree(stacked_params: Any, mesh: Mesh, axis: str = "pp") -> Any:
    """Each leaf's leading (stage) dim sharded over ``axis``."""
    return jax.tree_util.tree_map(
        lambda p: NamedSharding(mesh, P(axis, *([None] * (p.ndim - 1)))),
        stacked_params)


def _schedule_1f1b(n_stages: int, m: int, v: int = 1):
    """Greedy 1F1B timetable, computed at trace time (all sizes static).

    Returns ``(kind, mb, lap)`` int arrays of shape [T, S]: at tick t
    device s performs kind 0=idle / 1=forward / 2=backward on microbatch
    mb of its LOCAL chunk ``lap`` (global virtual chunk = lap*S + s; lap
    is always 0 at v=1).  The policy generalizes the classic one: device
    d keeps at most ``(S - d) + (v - 1)*S`` microbatch-chunks in flight
    (its interleaved warmup depth), prefers the ready backward with the
    lowest microbatch (deepest chunk on ties), and fills with the ready
    forward with the lowest microbatch (earliest chunk on ties).  At
    v=1 this is exactly the classic schedule: same bubble as gpipe,
    peak stash S microbatch inputs.  At v>1 every microbatch laps the
    ring v times (chunk c feeds chunk c+1, always one device to the
    right), shrinking the FILL/DRAIN bubble for v x more ppermute hops
    — worth wall time at bubble-bound shapes (deep pipe, few
    microbatches), and ~nothing once m >> pp amortizes the fill.
    """
    import numpy as np

    n_virt = n_stages * v
    last = n_virt - 1
    next_f = [0] * n_virt
    next_b = [0] * n_virt
    f_done = [[-1] * m for _ in range(n_virt)]
    b_done = [[-1] * m for _ in range(n_virt)]
    kinds, mbs, laps = [], [], []
    t = 0
    while any(nb < m for nb in next_b):
        # The last VIRTUAL chunk never runs a separate forward tick: its
        # backward recomputes the chunk inside the loss vjp anyway, so a
        # standalone forward would be discarded work.  Its "forward" is
        # the ARRIVAL of the previous chunk's output (immediate for a
        # 1-chunk pipeline, whose chunk-0 input is always at hand).
        while next_f[last] < m and (
                last == 0 or 0 <= f_done[last - 1][next_f[last]] < t):
            f_done[last][next_f[last]] = (
                t if last == 0 else f_done[last - 1][next_f[last]] + 1)
            next_f[last] += 1
        krow = [0] * n_stages
        mrow = [0] * n_stages
        lrow = [0] * n_stages
        for d in range(n_stages):
            chunks = [lap * n_stages + d for lap in range(v)]
            ready_b, ready_f = [], []
            for c in chunks:
                i, j = next_b[c], next_f[c]
                if i < m and (
                        (c == last and 0 <= f_done[c][i] <= t)
                        or (c < last and 0 <= b_done[c + 1][i] < t)):
                    ready_b.append(c)
                # Per-chunk in-flight stays under S so the mb%S stash
                # slots of one chunk never collide.
                if (c < last and j < m
                        and (c == 0 or 0 <= f_done[c - 1][j] < t)
                        and j - next_b[c] < n_stages):
                    ready_f.append(c)
            inflight = sum(next_f[c] - next_b[c] for c in chunks)
            depth = (n_stages - d) + (v - 1) * n_stages
            if ready_b and (inflight >= depth or not ready_f):
                c = min(ready_b, key=lambda c_: (next_b[c_], -c_))
                krow[d], mrow[d], lrow[d] = 2, next_b[c], c // n_stages
                b_done[c][next_b[c]] = t
                next_b[c] += 1
            elif ready_f and inflight < depth:
                c = min(ready_f, key=lambda c_: (next_f[c_], c_))
                krow[d], mrow[d], lrow[d] = 1, next_f[c], c // n_stages
                f_done[c][next_f[c]] = t
                next_f[c] += 1
        kinds.append(krow)
        mbs.append(mrow)
        laps.append(lrow)
        t += 1
        if t > 4 * v * (m + n_virt) + 8:  # safety: must terminate
            raise AssertionError("1f1b schedule did not converge")
    return (np.asarray(kinds, np.int32), np.asarray(mbs, np.int32),
            np.asarray(laps, np.int32))


def pipeline_train_1f1b(stage_fn: Callable[[Any, Any], Any],
                        loss_fn: Callable[..., Any],
                        stacked_params: Any, x, targets, mesh: Mesh,
                        axis: str = "pp",
                        num_microbatches: Optional[int] = None,
                        param_partition: Optional[Any] = None,
                        tail_params: Any = None,
                        tail_partition: Optional[Any] = None,
                        stage_aux: bool = False,
                        virtual_stages: int = 1,
                        seq_axis: Optional[str] = None):
    """One fused forward+backward pipeline pass on the 1F1B schedule.

    ``pipeline_apply`` is forward-only — under ``jax.grad`` autodiff
    replays its reverse, which is gpipe's all-forwards-then-all-backwards
    with every microbatch's activations live.  1F1B's point is the
    bounded stash, and that is only expressible with forward and backward
    interleaved in ONE loop — hence a training-step entry point rather
    than a ``schedule=`` flag.

    ``stage_fn(chunk_params, h) -> h`` as in ``pipeline_apply``.  Manual
    non-pp collectives are allowed, with one 1F1B-specific rule: the
    backward runs ``jax.vjp`` INSIDE the shard_map, where a plain
    ``lax.psum``'s transpose double-counts over its axis — use the
    Megatron f/g pair ``collectives.broadcast_replicated_grad`` (where a
    replicated activation fans out into per-shard compute) and
    ``collectives.psum_replicated_grad`` (after row-parallel matmuls),
    which carry their own transposes (tested:
    ``test_pipeline_1f1b_with_manual_tp_stage``).
    ``loss_fn(h_out, target_mb) -> scalar``
    (a per-microbatch MEAN, so the microbatch average equals the full
    batch loss).  Returns ``(loss, grads, dx)``: the mean loss, fp32
    parameter gradients with the stacked params' structure and sharding,
    and the gradient w.r.t. ``x`` (for an embedding layer upstream).
    ``targets`` are constants — no cotangent flows to them.

    ``tail_params`` (optional) are weights used INSIDE the loss — a final
    norm and unembedding head, say.  The loss contract becomes
    ``loss_fn(tail_params, h_out, target_mb)``, the tail rides into
    every stage (only the last differentiates it), and the return grows
    to ``(loss, grads, tail_grads, dx)`` with fp32 ``tail_grads``.
    ``tail_partition`` (optional) gives per-leaf PartitionSpecs for the
    tail — e.g. a vocab-sharded unembedding consumed by an in-body
    vocab-parallel CE (``ops/layers.vocab_parallel_ce_inbody``); leaves
    default to replicated, and tail grads keep the same specs.

    ``stage_aux=True`` changes the stage contract to
    ``stage_fn(chunk_params, h) -> (h, aux)`` where ``aux`` is a SCALAR
    auxiliary loss the stage contributes to the objective (e.g. MoE
    router load-balance/z losses, pre-weighted and normalized so the sum
    over stages is the model's aux term).  Each chunk's aux joins the
    loss at its BACKWARD tick: the vjp seeds the aux output with the
    same 1/m cotangent as the main loss, so router gradients flow even
    though no cotangent arrives from downstream stages, and the
    returned loss includes every stage's aux (summed over pp).

    ``virtual_stages=v`` (> 1) runs the INTERLEAVED timetable: device d
    owns chunks d, d+S, ..., every microbatch laps the ring v times, and
    each tick is 1/v the compute — shrinking the fill/drain bubble's
    wall-clock share by ~v for v x more (activation-sized) ppermute
    hops.  Stage-chunk grads return in the caller's GLOBAL chunk order.

    Memory: backward recomputes its chunk from the stashed chunk INPUT
    (standard 1F1B remat); each device holds at most S microbatch
    inputs PER LOCAL CHUNK (buffers of v*S slots — at v=1 the classic
    O(S) stash), independent of the microbatch count m.
    """
    if axis not in mesh.shape:
        raise ValueError(f"pipeline_train_1f1b: mesh {dict(mesh.shape)} has "
                         f"no {axis!r} axis (a size-1 axis is fine)")
    n_stages = mesh.shape[axis]
    m = num_microbatches or max(n_stages, 1)
    d_axis_names = data_axes(mesh) or ()
    dp_size = 1
    for a in d_axis_names:
        dp_size *= mesh.shape[a]
    if x.shape[0] % (m * dp_size):
        raise ValueError(f"batch {x.shape[0]} not divisible into {m} "
                         f"microbatches x {dp_size} data shards")
    if targets.shape[0] != x.shape[0]:
        raise ValueError(f"targets batch {targets.shape[0]} != x batch "
                         f"{x.shape[0]}")
    v = int(virtual_stages)
    if v < 1:
        raise ValueError("virtual_stages must be >= 1")
    if v > 1 and n_stages < 2:
        raise ValueError("interleaved virtual stages need a real pp axis "
                         "(n_stages >= 2); v chunks on one device is just "
                         "a deeper stage")
    n_chunks = jax.tree_util.tree_leaves(stacked_params)[0].shape[0]
    if n_chunks != max(n_stages, 1) * v:
        raise ValueError(f"1f1b runs {v} chunk(s) per stage: stacked "
                         f"params have {n_chunks} chunks for {n_stages} "
                         f"stages x virtual_stages={v}")
    if v > 1:
        # Interleaved layout: global chunk c runs on device c % S at
        # local index (lap) c // S.  Contiguous pp sharding gives device
        # d the local block [d*v, (d+1)*v), so permute global order
        # [c] -> [ (c % S)*v + c // S ] — same move as the circular
        # schedule — and inverse-permute the returned grads.
        perm = jnp.asarray([(i % n_stages) * v + i // n_stages
                            for i in range(n_stages * v)]).argsort()
        inv_perm = jnp.argsort(perm)
        stacked_params = jax.tree_util.tree_map(
            lambda p: jnp.take(p, perm, axis=0), stacked_params)

    kinds_np, mbs_np, laps_np = _schedule_1f1b(max(n_stages, 1), m, v)
    ticks = kinds_np.shape[0]

    def local(params, tail, xs, ts):
        stage = jax.lax.axis_index(axis) if n_stages > 1 else 0
        b_loc = xs.shape[0]
        micro = xs.reshape(m, b_loc // m, *xs.shape[1:])
        tmicro = ts.reshape(m, b_loc // m, *ts.shape[1:])
        mb_shape = micro.shape[1:]
        kinds = jnp.asarray(kinds_np)
        mbs = jnp.asarray(mbs_np)
        laps = jnp.asarray(laps_np)
        slots = max(n_stages, 1)

        def tick(t, carry):
            (h_buf, g_buf, dparams, dtail, dx, loss_acc, recv_f,
             recv_g) = carry
            kind = kinds[t, stage]
            mb = mbs[t, stage]
            lap = laps[t, stage]
            slot = lap * slots + mb % slots
            if v == 1:
                # lap is constantly 0: slice once, outside the hot loop's
                # dataflow, instead of a per-tick O(params) gather.
                chunk_p = jax.tree_util.tree_map(lambda p: p[0], params)
            else:
                chunk_p = jax.tree_util.tree_map(
                    lambda p: jax.lax.dynamic_index_in_dim(
                        p, lap, 0, keepdims=False), params)
            # File the values that arrived over the ring: what they are is
            # the neighbour's op last tick, read from the same table.
            # Chunk c always feeds chunk c+1 one device to the right (c-1
            # one left for cotangents); crossing the ring seam bumps the
            # receiving lap (device 0 receives lap l as chunk lap l+1,
            # device S-1 receives backward lap l as chunk lap l-1).
            prev_s = (stage - 1) % slots
            next_s = (stage + 1) % slots
            if n_stages > 1:
                up_kind = jnp.where(t > 0, kinds[t - 1, prev_s], 0)
                up_mb = mbs[jnp.maximum(t - 1, 0), prev_s]
                up_lap = laps[jnp.maximum(t - 1, 0), prev_s] + \
                    jnp.where(stage == 0, 1, 0)
                up_ok = (up_kind == 1) & ((stage > 0) | (up_lap < v))
                h_buf = jnp.where(
                    up_ok,
                    jax.lax.dynamic_update_index_in_dim(
                        h_buf, recv_f,
                        jnp.minimum(up_lap, v - 1) * slots
                        + up_mb % slots, 0), h_buf)
                dn_kind = jnp.where(t > 0, kinds[t - 1, next_s], 0)
                dn_mb = mbs[jnp.maximum(t - 1, 0), next_s]
                dn_lap = laps[jnp.maximum(t - 1, 0), next_s] - \
                    jnp.where(stage == slots - 1, 1, 0)
                dn_ok = (dn_kind == 2) & ((stage < slots - 1)
                                          | (dn_lap >= 0))
                g_buf = jnp.where(
                    dn_ok,
                    jax.lax.dynamic_update_index_in_dim(
                        g_buf, recv_g,
                        jnp.maximum(dn_lap, 0) * slots
                        + dn_mb % slots, 0), g_buf)

            z_send = jnp.zeros(mb_shape, xs.dtype)

            def do_idle(_):
                return (h_buf, dparams, dtail, dx, loss_acc, z_send, z_send)

            def do_fwd(_):
                # Compute one chunk forward; stash the chunk INPUT (the
                # 1F1B remat residual) and send the output down the ring.
                # (The aux scalar is recomputed — and differentiated — at
                # the chunk's backward tick; forward drops it.)
                inject = jax.lax.dynamic_index_in_dim(micro, mb, 0,
                                                      keepdims=False)
                h_in = jnp.where(
                    (stage == 0) & (lap == 0), inject,
                    jax.lax.dynamic_index_in_dim(h_buf, slot, 0,
                                                 keepdims=False))
                h_out = stage_fn(chunk_p, h_in)
                if stage_aux:
                    h_out = h_out[0]
                return (jax.lax.dynamic_update_index_in_dim(h_buf, h_in,
                                                            slot, 0),
                        dparams, dtail, dx, loss_acc, h_out, z_send)

            def do_bwd(_):
                # Recompute this chunk from the stashed input and vjp it.
                # The last stage seeds from the loss (cotangent 1/m);
                # earlier stages consume the cotangent off the ring.
                # Stage 0's stash IS the microbatch input — read it from
                # the (always-resident) batch, not the buffer, so the
                # 1-stage pipeline needs no forward ticks at all.
                inject = jax.lax.dynamic_index_in_dim(micro, mb, 0,
                                                      keepdims=False)
                h_stash = jnp.where(
                    (stage == 0) & (lap == 0), inject,
                    jax.lax.dynamic_index_in_dim(h_buf, slot, 0,
                                                 keepdims=False))
                tgt = jax.lax.dynamic_index_in_dim(tmicro, mb, 0,
                                                   keepdims=False)
                g_in = jax.lax.dynamic_index_in_dim(g_buf, slot, 0,
                                                    keepdims=False)

                def apply_stage(p, h):
                    """(h_out, aux): aux is 0 for plain stages, so one
                    code path serves both contracts."""
                    out = stage_fn(p, h)
                    if stage_aux:
                        return out[0], out[1].astype(jnp.float32)
                    return out, jnp.zeros((), jnp.float32)

                def last_chunk(_):
                    if tail_params is None:
                        def f(p, h):
                            out, aux = apply_stage(p, h)
                            return loss_fn(out, tgt).astype(jnp.float32) \
                                + aux
                        lval, vjp = jax.vjp(f, chunk_p, h_stash)
                        dp, dh = vjp(jnp.asarray(1.0 / m, lval.dtype))
                        dtl = zero_tail
                    else:
                        def f(p, h, tl):
                            out, aux = apply_stage(p, h)
                            return loss_fn(tl, out, tgt).astype(
                                jnp.float32) + aux
                        lval, vjp = jax.vjp(f, chunk_p, h_stash, tail)
                        dp, dh, dtl = vjp(jnp.asarray(1.0 / m, lval.dtype))
                        # fp32 like the other accumulators — and both cond
                        # branches must agree on dtypes (zero_tail is fp32).
                        dtl = jax.tree_util.tree_map(
                            lambda g: g.astype(jnp.float32), dtl)
                    return lval.astype(jnp.float32), dp, dh, dtl

                def mid_chunk(_):
                    (_, aux), vjp = jax.vjp(apply_stage, chunk_p, h_stash)
                    # The aux output takes the SAME 1/m seed as the loss:
                    # router grads flow from this stage's own aux term
                    # even though no loss cotangent arrives from the ring.
                    dp, dh = vjp((g_in, jnp.asarray(1.0 / m, jnp.float32)))
                    # Raw aux into the accumulator — the final /m turns the
                    # sum over microbatches into the mean, exactly as the
                    # last stage's lval.
                    return aux, dp, dh, zero_tail

                lval, dp, dh, dtl = jax.lax.cond(
                    (stage == slots - 1) & (lap == v - 1),
                    last_chunk, mid_chunk, None)

                def acc_at_lap(acc, g):
                    cur = jax.lax.dynamic_index_in_dim(acc, lap, 0,
                                                       keepdims=False)
                    return jax.lax.dynamic_update_index_in_dim(
                        acc, cur + g.astype(jnp.float32), lap, 0)

                new_dparams = jax.tree_util.tree_map(acc_at_lap, dparams,
                                                     dp)
                new_dtail = jax.tree_util.tree_map(
                    lambda acc, g: acc + g.astype(jnp.float32), dtail, dtl)
                new_dx = jnp.where(
                    (stage == 0) & (lap == 0),
                    jax.lax.dynamic_update_index_in_dim(
                        dx, dh.astype(dx.dtype), mb, 0), dx)
                return (h_buf, new_dparams, new_dtail, new_dx,
                        loss_acc + lval, z_send, dh.astype(xs.dtype))

            (h_buf, dparams, dtail, dx, loss_acc, send_f,
             send_g) = jax.lax.switch(kind, (do_idle, do_fwd, do_bwd), None)
            if n_stages > 1:
                recv_f = ppermute_shift(send_f, axis, 1)
                recv_g = ppermute_shift(send_g, axis, -1)
            return (h_buf, g_buf, dparams, dtail, dx, loss_acc, recv_f,
                    recv_g)

        h_buf0 = jnp.zeros((v * slots,) + mb_shape, xs.dtype)
        g_buf0 = jnp.zeros((v * slots,) + mb_shape, xs.dtype)
        dparams0 = jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params)
        zero_tail = jax.tree_util.tree_map(
            lambda p: jnp.zeros(jnp.shape(p), jnp.float32), tail)
        dx0 = jnp.zeros((m,) + mb_shape, jnp.float32)
        z = jnp.zeros(mb_shape, xs.dtype)
        carry = (h_buf0, g_buf0, dparams0, zero_tail, dx0,
                 jnp.zeros((), jnp.float32), z, z)
        carry = jax.lax.fori_loop(0, ticks, tick, carry)
        _, _, dparams, dtail, dx, loss_acc, _, _ = carry
        if n_stages > 1:
            # Every stage's loss_acc contributes (mid stages hold their
            # own aux terms; 0 for plain stages, so this reduces to the
            # last-stage-only extraction for dense models); tail grads
            # live on the last stage, dx on stage 0 — pp-broadcast them
            # so the caller sees pp-replicated outputs.  dparams stay
            # per-stage (that IS their sharding).
            loss = jax.lax.psum(loss_acc, axis)
            dtail = jax.tree_util.tree_map(
                lambda g: jax.lax.psum(
                    jnp.where(stage == slots - 1, g, jnp.zeros_like(g)),
                    axis), dtail)
            dx = jax.lax.psum(
                jnp.where(stage == 0, dx, jnp.zeros_like(dx)), axis)
        else:
            loss = loss_acc
        loss = loss / m
        if d_axis_names:
            # Each data shard ran its own batch slice: the global loss is
            # the shard mean, and so are the parameter grads (each shard
            # holds d(local mean)/dp; the mean of those is d(global
            # mean)/dp).  dx stays per-shard (it IS the local slice) but
            # rescales to global-mean semantics: d(local mean)/dx is
            # dp_size times d(global mean)/dx.
            loss = jax.lax.pmean(loss, d_axis_names)
            dparams = jax.tree_util.tree_map(
                lambda g: jax.lax.pmean(g, d_axis_names), dparams)
            dtail = jax.tree_util.tree_map(
                lambda g: jax.lax.pmean(g, d_axis_names), dtail)
            dx = dx / dp_size
        return loss, dparams, dtail, dx.reshape(b_loc, *xs.shape[1:])

    if param_partition is None:
        param_specs = jax.tree_util.tree_map(
            lambda p: P(axis, *([None] * (p.ndim - 1))), stacked_params)
    else:
        param_specs = jax.tree_util.tree_map(
            lambda p, spec: P(axis, *spec), stacked_params, param_partition)
    # seq_axis shards dim 1 (sequence): stage bodies see local shards and
    # handle the axis manually (einsum-ring attention, f/g-fanned weights,
    # an sp-reduced loss tail — see transformer.train_step_1f1b).
    x_spec = P(data_axes(mesh), seq_axis, *([None] * (x.ndim - 2)))
    t_spec = P(data_axes(mesh), seq_axis,
               *([None] * (targets.ndim - 2)))
    if tail_partition is None:
        tail_specs = jax.tree_util.tree_map(lambda _: P(), tail_params)
    else:
        tail_specs = jax.tree_util.tree_map(
            lambda _, s: s, tail_params, tail_partition,
            is_leaf=lambda n: isinstance(n, P))
    fn = shard_map(local, mesh=mesh,
                       in_specs=(param_specs, tail_specs, x_spec, t_spec),
                       out_specs=(P(), param_specs, tail_specs, x_spec),
                       check_vma=False)
    loss, grads, tail_grads, dx = fn(stacked_params, tail_params, x, targets)
    if v > 1:
        # Grads came back in the interleaved (permuted) chunk order;
        # restore the caller's global layer order.
        grads = jax.tree_util.tree_map(
            lambda g: jnp.take(g, inv_perm, axis=0), grads)
    if tail_params is None:
        return loss, grads, dx
    return loss, grads, tail_grads, dx


def pipeline_apply(stage_fn: Callable[[Any, Any], Any], stacked_params: Any,
                   x, mesh: Mesh, axis: str = "pp",
                   num_microbatches: Optional[int] = None,
                   param_partition: Optional[Any] = None,
                   schedule: str = "gpipe", virtual_stages: int = 1,
                   with_aux: bool = False,
                   seq_axis: Optional[str] = None):
    """Run ``x`` through the stage pipeline; returns the final activations.

    ``stage_fn(params, h) -> h`` applies ONE stage chunk (same activation
    shape in and out); it runs inside the mesh-wide shard_map and may use
    manual collectives over non-pp axes.  ``stacked_params`` leaves have
    leading dim = number of chunks (``pp`` for gpipe,
    ``pp * virtual_stages`` for circular, in global layer order).  ``x`` is
    ``[B, ...]``, split into microbatches along B.  ``param_partition``
    (optional) is a pytree of PartitionSpecs for each leaf's NON-leading
    dims, e.g. ``P("tp", None)`` to column-shard a weight over tp.

    ``with_aux`` (default off) changes the stage contract to
    ``stage_fn(params, h) -> (h, aux)`` where ``aux`` is a pytree of fp32
    scalars (e.g. router-health metrics); the call then returns
    ``(out, aux_mean)`` with each scalar averaged over every chunk
    execution — all chunks × all microbatches × the data shards — i.e. the
    microbatched analogue of the non-pp path's mean-over-layers-and-batch.
    (Statistics that are nonlinear in the batch, like the load-balance
    loss's fraction·probability product, are computed per microbatch and
    averaged — the same estimator gradient accumulation uses.)  Pass the
    aux pytree's *structure* (any pytree, values ignored) as ``with_aux``;
    ``with_aux=True`` infers it by abstractly evaluating ``stage_fn``,
    which only works for stage bodies free of manual collectives.

    ``seq_axis`` (optional) shards the activations' dim 1 (sequence)
    over that mesh axis: stage bodies then see LOCAL sequence shards
    and must handle the axis manually (e.g. the einsum-ring attention
    of ``models/transformer._block(sp_axis=...)`` with global rope
    positions); aux scalars additionally pmean over it (per-shard
    router statistics are an estimator of the full-sequence ones, like
    the microbatch estimator).
    """
    n_stages = mesh.shape[axis]
    if schedule not in ("gpipe", "circular"):
        raise ValueError(f"unknown schedule {schedule!r}")
    if virtual_stages > 1 and schedule != "circular":
        # Silently running gpipe over pp*v chunks would apply only the
        # first chunk on each device — wrong loss, no error.
        raise ValueError("virtual_stages > 1 requires schedule='circular'")
    aux_proto = None
    if with_aux is not False and with_aux is not True:
        aux_proto, with_aux = with_aux, True
    v = virtual_stages if schedule == "circular" else 1
    if n_stages == 1:
        n_chunks = jax.tree_util.tree_leaves(stacked_params)[0].shape[0]
        def chunk(i):
            return jax.tree_util.tree_map(lambda p: p[i], stacked_params)
        h = x
        if not with_aux:
            for i in range(n_chunks):
                h = stage_fn(chunk(i), h)
            return h
        auxes = []
        for i in range(n_chunks):
            h, aux = stage_fn(chunk(i), h)
            auxes.append(aux)
        return h, jax.tree_util.tree_map(
            lambda *xs: jnp.mean(jnp.stack(xs), axis=0), *auxes)
    m = num_microbatches or n_stages
    d_axes = data_axes(mesh)
    d_axis_names = d_axes or ()
    dp_size = 1
    for a in d_axis_names:
        dp_size *= mesh.shape[a]
    if x.shape[0] % (m * dp_size):
        raise ValueError(f"batch {x.shape[0]} not divisible into {m} "
                         f"microbatches x {dp_size} data shards")
    if schedule == "circular":
        if v < 1:
            raise ValueError("virtual_stages must be >= 1")
        if m % n_stages:
            raise ValueError(f"circular schedule needs microbatches ({m}) "
                             f"divisible by pp ({n_stages})")
        # Chunk c of the round-robin assignment (device s runs chunks
        # lap*pp + s) must land at the device's local index `lap` under
        # contiguous sharding: permute global order [c] -> [s*v + lap].
        perm = jnp.asarray([(i % n_stages) * v + i // n_stages
                            for i in range(n_stages * v)]).argsort()
        stacked_params = jax.tree_util.tree_map(
            lambda p: jnp.take(p, perm, axis=0), stacked_params)

    def local(params, xs):
        stage = jax.lax.axis_index(axis)
        b_loc = xs.shape[0]
        micro = xs.reshape(m, b_loc // m, *xs.shape[1:])
        mb_shape = micro.shape[1:]

        def chunk_params(lap):
            # local leading dim is v (1 for gpipe): pick this lap's chunk
            return jax.tree_util.tree_map(
                lambda p: jax.lax.dynamic_index_in_dim(p, lap, 0,
                                                       keepdims=False),
                params)

        def run_stage(lap, h):
            out = stage_fn(chunk_params(lap), h)
            return out if with_aux else (out, {})

        def tick(t, carry):
            received, outputs, aux_acc = carry
            u = t - stage
            r = jnp.where(u >= 0, u % n_stages, 0)
            w = u - r
            lap = jnp.where(u >= 0, (w % (n_stages * v)) // n_stages, 0)
            mb = jnp.where(u >= 0, (w // (n_stages * v)) * n_stages + r, 0)
            active = (u >= 0) & (mb < m)
            inject = jax.lax.dynamic_index_in_dim(
                micro, jnp.clip(mb, 0, m - 1), 0, keepdims=False)
            h = jnp.where((stage == 0) & (lap == 0), inject, received)
            out, aux = run_stage(lap, h)
            # Inactive ticks run the stage on garbage; their aux is masked
            # out (the activation path needs no mask — inactive outputs are
            # never emitted and get overwritten as they ride the ring).
            aux_acc = jax.tree_util.tree_map(
                lambda acc, a: acc + jnp.where(active, a, 0.0), aux_acc, aux)
            emit = active & (stage == n_stages - 1) & (lap == v - 1)
            out_idx = jnp.clip(mb, 0, m - 1)
            outputs = jax.lax.dynamic_update_index_in_dim(
                outputs,
                jnp.where(emit, out,
                          jax.lax.dynamic_index_in_dim(outputs, out_idx, 0,
                                                       keepdims=False)),
                out_idx, 0)
            received = ppermute_shift(out, axis, 1)
            return received, outputs, aux_acc

        outputs0 = jnp.zeros((m,) + mb_shape, xs.dtype)
        received0 = jnp.zeros(mb_shape, xs.dtype)
        aux0 = jax.tree_util.tree_map(
            lambda _: jnp.zeros((), jnp.float32),
            aux_proto if with_aux else {})
        _, outputs, aux_acc = jax.lax.fori_loop(
            0, m * v + n_stages - 1, tick, (received0, outputs0, aux0))
        # Results live on the last stage; broadcast them to every stage so
        # the caller sees a pp-replicated output.
        outputs = jax.lax.psum(
            jnp.where(stage == n_stages - 1, outputs, jnp.zeros_like(outputs)),
            axis_name=axis)
        out = outputs.reshape(b_loc, *xs.shape[1:])
        if not with_aux:
            return out
        # Mean over every chunk execution: each of the m microbatches runs
        # each of the n_stages*v chunks exactly once, spread over pp.
        aux_mean = jax.tree_util.tree_map(
            lambda a: jax.lax.psum(a, axis) / (m * n_stages * v), aux_acc)
        # Average over the data shards (each ring works its own batch
        # shard) and over seq_axis shards when the sequence is split
        # (per-shard router statistics estimate the full-sequence ones);
        # any remaining axis (tp/ep) already holds identical values —
        # stage bodies pmean/psum their collectives internally — so the
        # replicated out_spec is sound.
        red_axes = tuple(d_axis_names) + (
            (seq_axis,) if seq_axis else ())
        if red_axes:
            aux_mean = jax.tree_util.tree_map(
                lambda a: jax.lax.pmean(a, red_axes), aux_mean)
        return out, aux_mean

    if param_partition is None:
        param_specs = jax.tree_util.tree_map(
            lambda p: P(axis, *([None] * (p.ndim - 1))), stacked_params)
    else:
        param_specs = jax.tree_util.tree_map(
            lambda p, spec: P(axis, *spec), stacked_params, param_partition)
    # Activations shard over the data axes (each pipeline ring works on its
    # batch shard) — plus the sequence dim over seq_axis when given — and
    # replicate over pp/tp, where the ring/psum handle them.
    x_spec = P(data_axes(mesh), seq_axis, *([None] * (x.ndim - 2)))
    sp_size = mesh.shape.get(seq_axis, 1) if seq_axis else 1
    if with_aux:
        if aux_proto is None:
            # Infer the aux structure abstractly (collective-free stages
            # only — pass the structure explicitly otherwise).
            aux_proto = jax.eval_shape(
                lambda p, h: stage_fn(
                    jax.tree_util.tree_map(lambda q: q[0], p), h)[1],
                stacked_params,
                jnp.zeros((x.shape[0] // (m * dp_size),
                           x.shape[1] // sp_size) + x.shape[2:], x.dtype))
        out_specs = (x_spec, jax.tree_util.tree_map(lambda _: P(), aux_proto))
    else:
        out_specs = x_spec
    fn = shard_map(local, mesh=mesh,
                       in_specs=(param_specs, x_spec), out_specs=out_specs,
                       check_vma=False)
    return fn(stacked_params, x)
