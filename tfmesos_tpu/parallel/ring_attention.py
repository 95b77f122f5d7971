"""Ring attention: sequence/context parallelism over the ``sp`` mesh axis.

Long-context support the reference never had (SURVEY §2.7: no sequence axis
anywhere).  Sequences shard along time over ``sp``; each device computes
blockwise attention of its query block against every key/value block as the
K/V shards rotate around the ring via ``ppermute`` (one ICI hop per step),
with the online-softmax accumulation of flash attention so nothing is ever
materialized at full sequence length.  Memory per device is O(T/sp), compute
overlaps the rotation, and causal masking is exact across shards.

Two inner implementations:

* ``impl="flash"`` (default on TPU) — each ring step runs the Pallas flash
  kernels on the local shard pair and partial outputs merge through their
  logsumexps; a custom VJP re-rotates K/V in the backward and feeds the
  stored GLOBAL lse to the Mosaic dq/dkv kernels, so residual memory stays
  O(T/sp) (plain autodiff of the ring would checkpoint per-step score
  matrices — O(T²/sp)).
* ``impl="xla"`` — the original einsum ring with online softmax; ground
  truth and the CPU path.

Causal structure across shards is the standard ring decomposition: step 0
holds this device's own shard (true causal call); any later step holds a
shard that is either fully visible (owner before us) or fully masked
(owner after us), decided by one scalar — no per-element cross-shard masks.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.lax import axis_size
from jax.sharding import Mesh, PartitionSpec as P

from tfmesos_tpu.parallel.collectives import ppermute_shift
from tfmesos_tpu.parallel.sharding import data_axes


def ring_attention_local(q, k, v, axis: str = "sp", causal: bool = True,
                         scale: Optional[float] = None,
                         window: Optional[int] = None):
    """The per-device body; call inside ``shard_map`` with ``axis`` in scope.

    Shapes (local): q/k/v ``[B, T/sp, H, D]``.  At ring step ``i`` this
    device holds the K/V shard originally owned by ``(my_index - i) mod sp``,
    so global causal masking only needs the owner index.  A sliding
    ``window`` (causal only) tightens the same global-position mask: the
    owner index gives every held key its global position, so the window
    bound is exact across shards with no extra communication.
    """
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    sp = axis_size(axis)
    idx = jax.lax.axis_index(axis)
    b, tq, h, d = q.shape
    tk = k.shape[1]

    qf = q.astype(jnp.float32) * scale
    o = jnp.zeros((b, h, tq, d), jnp.float32)
    m = jnp.full((b, h, tq, 1), float("-inf"), jnp.float32)
    l = jnp.zeros((b, h, tq, 1), jnp.float32)

    qpos = idx * tq + jax.lax.broadcasted_iota(jnp.int32, (tq, tk), 0)

    for step in range(sp):  # static trip count: sp is a mesh constant
        src = (idx - step) % sp  # owner of the K/V shard we hold right now
        s = jnp.einsum("bqhd,bkhd->bhqk", qf, k.astype(jnp.float32))
        if causal:
            kpos = src * tk + jax.lax.broadcasted_iota(jnp.int32, (tq, tk), 1)
            bad = kpos > qpos
            if window is not None:
                bad = bad | (kpos < qpos - (window - 1))
            s = jnp.where(bad[None, None], float("-inf"), s)
        blockmax = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m, blockmax)
        # Fully-masked blocks leave m_new at -inf; subtract a finite proxy so
        # exp(-inf - finite) -> 0 instead of exp(-inf - -inf) -> nan.
        m_safe = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
        p = jnp.exp(s - m_safe)
        corr = jnp.exp(m - m_safe)  # m=-inf gives 0: first block overwrites
        l = l * corr + jnp.sum(p, axis=-1, keepdims=True)
        o = o * corr + jnp.einsum("bhqk,bkhd->bhqd", p, v.astype(jnp.float32))
        m = m_new
        if step != sp - 1:
            # Rotate K/V one hop around the ring (device i -> i+1).
            k = ppermute_shift(k, axis, 1)
            v = ppermute_shift(v, axis, 1)

    l = jnp.where(l == 0.0, 1.0, l)
    out = (o / l).transpose(0, 2, 1, 3)  # [B, Tq, H, D]
    return out.astype(q.dtype)


def _flash_cfg(q, scale, causal, interpret, window=None, q_offset=0):
    from tfmesos_tpu.ops import attention as A
    t = q.shape[1]
    return A._FlashCfg(causal=causal, scale=scale,
                       block_q=A._pick_block(t), block_k=A._pick_block(t),
                       interpret=bool(interpret), window=window,
                       q_offset=int(q_offset))


def _merge(o_acc, lse_acc, o_i, lse_i):
    """Merge two normalized partial attentions via their logsumexps.

    o: [B, T, H, D]; lse: [B, H, T, 1].  exp(-inf − finite) = 0 handles
    fully-masked partials.
    """
    lse_new = jnp.logaddexp(lse_acc, lse_i)
    w_a = jnp.exp(lse_acc - lse_new).transpose(0, 2, 1, 3)  # [B, T, H, 1]
    w_i = jnp.exp(lse_i - lse_new).transpose(0, 2, 1, 3)
    return o_acc * w_a + o_i * w_i, lse_new


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _ring_flash(q, k, v, axis, causal, scale, interpret, window):
    return _ring_flash_fwd(q, k, v, axis, causal, scale, interpret,
                           window)[0]


def _step_cfg(q, scale, causal, interpret, window, step):
    """Ring step cfg: with a sliding window every step runs the CAUSAL
    kernel with a static q_offset of step * shard_len — the same
    global-position arithmetic the einsum inner uses, so far-behind
    shards' k-blocks are SKIPPED by the kernel's window bound (O(T·W)
    work across shards, not just within one).  Without a window, steps
    past the first keep the full (causal=False) kernel and mask
    invisible shards wholesale, as before."""
    if window is None:
        return _flash_cfg(q, scale, causal if step == 0 else False,
                          interpret)
    return _flash_cfg(q, scale, True, interpret, window=window,
                      q_offset=step * q.shape[1])


def _ring_flash_fwd(q, k, v, axis, causal, scale, interpret, window):
    from tfmesos_tpu.ops import attention as A
    sp = axis_size(axis)
    idx = jax.lax.axis_index(axis)
    of = jnp.float32

    o, lse = A._flash_forward(
        _step_cfg(q, scale, causal, interpret, window, 0),
        q, k, v)                            # step 0: own shard, causal
    o = o.astype(of)
    kr, vr = k, v
    for step in range(1, sp):
        kr = ppermute_shift(kr, axis, 1)
        vr = ppermute_shift(vr, axis, 1)
        src = (idx - step) % sp  # owner of the shard we now hold
        o_i, lse_i = A._flash_forward(
            _step_cfg(q, scale, causal, interpret, window, step), q, kr,
            vr)
        if causal:
            visible = src < idx  # else: entirely in our future, masked
            lse_i = jnp.where(visible, lse_i, -jnp.inf)
            o_i = jnp.where(visible, o_i.astype(of), 0.0)
        else:
            o_i = o_i.astype(of)
        o, lse = _merge(o, lse, o_i, lse_i)
    out = o.astype(q.dtype)
    return out, (q, k, v, out, lse)


def _ring_flash_bwd(axis, causal, scale, interpret, window, res, g):
    """Re-rotate K/V and run the Mosaic backward per shard with the stored
    GLOBAL logsumexp (p = exp(s·scale − lse) is then already normalized over
    the full ring, so per-shard contributions just sum).  dk/dv accumulators
    ride the ring with their shards; after sp total hops every contribution
    is back on its owner."""
    from tfmesos_tpu.ops import attention as A
    q, k, v, out, lse = res
    sp = axis_size(axis)
    idx = jax.lax.axis_index(axis)

    dq, dk, dv = A._mha_bwd_pallas(
        _step_cfg(q, scale, causal, interpret, window, 0), q, k, v, out,
        lse, g, out_dtype=jnp.float32)
    kr, vr = k, v
    for step in range(1, sp):
        kr = ppermute_shift(kr, axis, 1)
        vr = ppermute_shift(vr, axis, 1)
        dk = ppermute_shift(dk, axis, 1)
        dv = ppermute_shift(dv, axis, 1)
        src = (idx - step) % sp
        dqc, dkc, dvc = A._mha_bwd_pallas(
            _step_cfg(q, scale, causal, interpret, window, step), q, kr,
            vr, out, lse, g, out_dtype=jnp.float32)
        if causal:
            visible = (src < idx).astype(jnp.float32)
            dqc = dqc * visible
            dkc = dkc * visible
            dvc = dvc * visible
        dq = dq + dqc
        dk = dk + dkc
        dv = dv + dvc
    # One final hop completes the full ring: contributions land home.
    dk = ppermute_shift(dk, axis, 1)
    dv = ppermute_shift(dv, axis, 1)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_ring_flash.defvjp(_ring_flash_fwd, _ring_flash_bwd)


def ring_attention(q, k, v, mesh: Mesh, axis: str = "sp", causal: bool = True,
                   scale: Optional[float] = None, impl: Optional[str] = None,
                   interpret: bool = False, window: Optional[int] = None):
    """Sharded entry point: q/k/v are global ``[B, T, H, D]`` arrays (or
    tracers under jit) with T sharded over ``axis``.

    Falls back to single-device flash/reference attention when the mesh has
    no (non-trivial) ``axis`` — so model code calls this unconditionally.
    ``impl=None`` auto-selects: Pallas-inner ring on TPU (or when
    ``interpret``), the einsum ring elsewhere.

    ``window`` (causal only): sliding-window attention, exact across
    shards — the owner-index arithmetic that bounds causal visibility
    also bounds the window, per step.  Both inners support it: the
    Pallas ring runs every step's kernels with a static ``q_offset`` of
    step x shard_len (the offset-window form), whose block bounds SKIP
    k-blocks outside the window — O(T·W) work across the whole ring —
    while the einsum inner masks by global position.
    """
    if impl not in (None, "flash", "xla"):
        raise ValueError(f"impl must be None, 'flash', or 'xla'; got {impl!r}")
    if window is not None:
        if not causal:
            raise ValueError("window requires causal=True")
        if window < 1:
            raise ValueError(f"window must be >= 1, got {window}")
    if axis not in mesh.shape or mesh.shape[axis] == 1:
        # Trivial-axis fallback: an ordinary single-device call (the
        # kernel's q/k blocks share one global origin: q_offset = 0).
        from tfmesos_tpu.ops.attention import flash_attention
        use_pallas = {None: None, "flash": True, "xla": False}[impl]
        return flash_attention(q, k, v, causal=causal, scale=scale,
                               interpret=interpret, use_pallas=use_pallas,
                               window=window)
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    local_t = q.shape[1] // mesh.shape[axis]
    if impl is None:
        on_tpu = jax.default_backend() == "tpu"
        impl = "flash" if (on_tpu or interpret) and local_t % 8 == 0 else "xla"
    elif impl == "flash":
        from tfmesos_tpu.ops.attention import _pick_block
        if _pick_block(local_t) > 1024:
            # Mirror flash_attention's forced-pallas guard: fail fast with
            # a clear error instead of an opaque Mosaic lowering failure.
            raise ValueError(
                f"ring_attention(impl='flash'): local shard length "
                f"{local_t} has no Mosaic-legal block tiling")
    spec = P(data_axes(mesh), axis, None, None)
    if impl == "flash":
        body = lambda q_, k_, v_: _ring_flash(
            q_, k_, v_, axis, bool(causal), float(scale), bool(interpret),
            None if window is None else int(window))
    else:
        body = lambda q_, k_, v_: ring_attention_local(
            q_, k_, v_, axis=axis, causal=causal, scale=scale, window=window)
    fn = shard_map(body, mesh=mesh, in_specs=(spec, spec, spec),
                       out_specs=spec, check_vma=False)
    return fn(q, k, v)
