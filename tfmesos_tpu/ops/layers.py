"""Elementwise/normalization building blocks.

Kept as plain jnp functions — XLA fuses these into surrounding matmuls on
TPU; a Pallas kernel would only pay off for exotic fusions the compiler
misses (none here yet).  fp32 accumulation for the reductions, compute dtype
preserved on the output.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P


def rms_norm(x, weight, eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps)).astype(x.dtype) * weight


def swiglu(x, w_gate, w_up, w_down):
    """SwiGLU MLP: silu(x·Wg) ⊙ (x·Wu) · Wd."""
    g = jax.nn.silu(x @ w_gate)
    return (g * (x @ w_up)) @ w_down


def yarn_inv_freq(rotary_dim: int, theta: float, factor: float,
                  original_max: int, beta_fast: float = 32.0,
                  beta_slow: float = 1.0):
    """YaRN's inverse frequencies for ``rotary_dim`` rotated channels
    (``rotary_dim // 2`` of them, float32, computed on the host): channel
    pair ``i`` of plain rope turns at ``f_i = theta ** (-2 i / D)``; the pairs
    that turn more than ``beta_fast`` times within ``original_max`` positions
    keep ``f_i``, those that turn fewer than ``beta_slow`` times get ``f_i /
    factor`` (interpolated), and a linear ramp over the pair index joins
    them (Peng et al., arXiv:2309.00071)."""
    import numpy as np
    d = rotary_dim
    f = theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)

    def pair_of(turns):     # the pair that makes ``turns`` turns in the span
        return d * math.log(original_max / (2 * math.pi * turns)) / (
            2 * math.log(theta))

    lo = max(math.floor(pair_of(beta_fast)), 0)
    hi = min(math.ceil(pair_of(beta_slow)), d - 1)
    ramp = np.clip((np.arange(d // 2, dtype=np.float64) - lo)
                   / max(hi - lo, 1e-3), 0.0, 1.0)
    return (f / factor * ramp + f * (1.0 - ramp)).astype(np.float32)


def rope(x, positions, theta: float = 10000.0, *, inv_freq=None,
         rotary_dim: Optional[int] = None, factor: float = 1.0):
    """Rotary position embedding over the last (head_dim) axis.

    ``x``: [..., T, H, D]; ``positions``: [..., T] int32.  What a layer kind
    states beyond ``theta``: ``inv_freq`` [rotary_dim // 2] float32, the
    inverse frequencies as data (:func:`yarn_inv_freq`; None: ``theta``'s);
    ``rotary_dim``, the FIRST so many of a head's channels are rotated
    (paired first half with second half inside them) and the rest pass
    through (None: all); ``factor`` multiplies cos and sin both (YaRN's
    attention factor).
    """
    d = x.shape[-1] if rotary_dim is None else rotary_dim
    half = d // 2
    if inv_freq is None:
        freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    else:
        freqs = jnp.asarray(inv_freq, jnp.float32)
    angles = positions[..., None].astype(jnp.float32) * freqs  # [..., T, half]
    cos = jnp.cos(angles)[..., None, :]  # broadcast over heads
    sin = jnp.sin(angles)[..., None, :]
    if factor != 1.0:
        cos, sin = cos * factor, sin * factor
    x1, x2 = x[..., :half], x[..., half:d]
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos,
                           *([] if d == x.shape[-1] else [x[..., d:]])],
                          axis=-1)
    return out.astype(x.dtype)


def cross_entropy_loss(logits, labels, z_loss: float = 0.0):
    """Mean softmax cross entropy in fp32; optional z-loss regularizer."""
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    loss = jnp.mean(logz - picked)
    if z_loss:
        loss = loss + z_loss * jnp.mean(logz ** 2)
    return loss


def _ce_chunk(n: int, target: int) -> int:
    """Largest divisor of ``n`` at or under ``target``; if the best divisor
    is tiny (awkward token counts — e.g. prime n — have none near the
    target), return ``n`` itself: one full-size chunk costs the same memory
    as the unfused path, whereas a scan of tiny matmuls would be
    pathologically slow."""
    target = min(n, max(1, target))
    c = target
    while n % c:
        c -= 1
    return c if c * 8 >= target else n


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def fused_linear_cross_entropy(x, w, labels, z_loss: float = 0.0,
                               chunk: int = 2048):
    """Mean softmax cross entropy of ``logits = x @ w`` WITHOUT materializing
    the full logits tensor.

    ``x``: [..., d] pre-head activations; ``w``: [d, V]; ``labels``: [...]
    int.  Tokens are flattened and processed in chunks of ``chunk`` (largest
    divisor of the token count at or under it): each chunk's logits live
    only inside one scan step, fwd and bwd — so peak memory carries one
    [chunk, V] block instead of [N, V] (at B8/T2048/V8192 fp32 that is
    64MB instead of 512MB), and the HBM never round-trips the full logits
    between the matmul, the softmax and their gradients.

    The price is one extra logits matmul in the backward (recompute from
    the saved per-token logsumexp) — +2·d·V FLOPs/token against the
    ~6·d·V the head already costs fwd+bwd, bought back several times over
    in bandwidth at large V.  Numerics match ``cross_entropy_loss`` (both
    reduce in fp32; only the reduction grouping differs).
    """
    loss, _ = _flce_fwd(x, w, labels, z_loss, chunk)
    return loss


def _flce_flatten(x, labels, chunk):
    d = x.shape[-1]
    xf = x.reshape(-1, d)
    lf = labels.reshape(-1)
    n = xf.shape[0]
    c = _ce_chunk(n, chunk)
    return xf.reshape(n // c, c, d), lf.reshape(n // c, c), n


def _flce_fwd(x, w, labels, z_loss, chunk):
    xs, ls, n = _flce_flatten(x, labels, chunk)
    wc = w.astype(x.dtype)

    def body(acc, inp):
        xc, lc = inp
        logits = (xc @ wc).astype(jnp.float32)          # [c, V]
        logz = jax.nn.logsumexp(logits, axis=-1)        # [c]
        picked = jnp.take_along_axis(
            logits, lc[:, None], axis=-1)[:, 0]
        s = jnp.sum(logz - picked)
        if z_loss:
            s = s + z_loss * jnp.sum(logz ** 2)
        return acc + s, logz

    total, logzs = jax.lax.scan(body, jnp.zeros((), jnp.float32), (xs, ls))
    return total / n, (x, w, labels, logzs)


def _flce_bwd(z_loss, chunk, res, g):
    x, w, labels, logzs = res
    xs, ls, n = _flce_flatten(x, labels, chunk)
    wc = w.astype(x.dtype)
    scale = g / n

    def body(dw_acc, inp):
        xc, lc, logz = inp
        logits = (xc @ wc).astype(jnp.float32)
        p = jnp.exp(logits - logz[:, None])             # softmax, [c, V]
        coeff = 1.0 + (2.0 * z_loss) * logz if z_loss else None
        dlogits = p * coeff[:, None] if z_loss else p
        dlogits = (dlogits - jax.nn.one_hot(lc, logits.shape[-1],
                                            dtype=jnp.float32)) * scale
        dlogits = dlogits.astype(x.dtype)
        dx_c = dlogits @ wc.T                           # [c, d]
        dw_acc = dw_acc + jax.lax.dot_general(
            xc, dlogits, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)         # [d, V] fp32
        return dw_acc, dx_c

    dw, dxs = jax.lax.scan(
        body, jnp.zeros(w.shape, jnp.float32), (xs, ls, logzs))
    dx = dxs.reshape(x.shape).astype(x.dtype)
    return dx, dw.astype(w.dtype), None


fused_linear_cross_entropy.defvjp(_flce_fwd, _flce_bwd)


def _vp_batch_axes(mesh):
    """(data axes, total data-parallel degree) for the vocab-parallel CE."""
    from tfmesos_tpu.parallel.sharding import data_axes

    batch = data_axes(mesh)
    nb = 1
    for a in (batch or ()):
        nb *= mesh.shape[a]
    return batch, nb


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def vocab_parallel_cross_entropy(x, w, labels, mesh, axis: str = "tp",
                                 z_loss: float = 0.0, chunk: int = 2048):
    """``fused_linear_cross_entropy`` for a tensor-parallel (vocab-sharded)
    unembedding: ``w`` [d, V] sharded over ``axis`` on its vocab dim, ``x``
    [B, T, d] and ``labels`` [B, T] sharded over the data axes and
    replicated over ``axis``.

    Each device computes chunked logits against its own [d, V/tp] shard;
    the softmax max / sum-exp / picked-label statistics psum over ``axis``
    (the Megatron vocab-parallel pattern), so no device ever holds more
    than a [chunk, V/tp] block — fwd or bwd.  The returned scalar is the
    global-mean loss, identical math to the unfused path.

    Forward and backward are each ONE explicit ``shard_map`` with all
    cross-device sums written out (tp psums for the softmax statistics and
    dx, data-axis psums for the loss and dw) — the custom VJP sits outside
    the shard_maps, so no gradient ever flows through shard_map's implicit
    replication/transpose rules.
    """
    loss, _ = _vp_fwd(x, w, labels, mesh, axis, z_loss, chunk)
    return loss


def _vp_fwd(x, w, labels, mesh, axis, z_loss, chunk):
    if w.shape[-1] % mesh.shape[axis]:
        raise ValueError(
            f"vocab size {w.shape[-1]} must divide over {axis} "
            f"({mesh.shape[axis]})")
    batch, nb = _vp_batch_axes(mesh)

    def local(xl, wl, ll):
        # Per-shard math shared with the in-body variant (_vpi_fwd
        # returns the LOCAL token mean); equal-sized data shards make
        # the mean-of-means the global mean.
        loss_loc, (_, _, _, logzs) = _vpi_fwd(xl, wl, ll, axis, z_loss,
                                              chunk)
        if batch:
            loss_loc = jax.lax.psum(loss_loc, batch) / nb
        return loss_loc, logzs

    loss, logzs = shard_map(
        local, mesh=mesh,
        in_specs=(P(batch, None, None), P(None, axis), P(batch, None)),
        out_specs=(P(), P(batch, None)), check_vma=False)(x, w, labels)
    return loss, (x, w, labels, logzs)


def _vp_bwd(mesh, axis, z_loss, chunk, res, g):
    x, w, labels, logzs = res
    batch, nb = _vp_batch_axes(mesh)

    def local(xl, wl, ll, logzs_l, gl):
        # Shared per-shard bwd body; dw stays fp32 until after the
        # cross-data-shard psum (accumulate wide, cast once).
        dx, dw = _vpi_grads(axis, z_loss, chunk, (xl, wl, ll, logzs_l),
                            gl / nb)
        if batch:
            dw = jax.lax.psum(dw, batch)                # all tokens' sum
        return dx, dw.astype(wl.dtype)

    dx, dw = shard_map(
        local, mesh=mesh,
        in_specs=(P(batch, None, None), P(None, axis), P(batch, None),
                  P(batch, None), P()),
        out_specs=(P(batch, None, None), P(None, axis)),
        check_vma=False)(x, w, labels, logzs, g)
    return dx, dw, None


vocab_parallel_cross_entropy.defvjp(_vp_fwd, _vp_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def vocab_parallel_ce_inbody(x, w, labels, axis: str = "tp",
                             z_loss: float = 0.0, chunk: int = 2048):
    """``vocab_parallel_cross_entropy``'s per-shard body as a standalone
    custom-VJP, for callers ALREADY INSIDE a ``shard_map`` with ``axis``
    manual — the 1F1B pipeline's loss tail.  ``w`` is this device's
    [d, V/tp] vocab shard, ``x``/``labels`` the local microbatch.  All
    tp collectives are written out explicitly in BOTH directions
    (softmax statistics psums forward, the dx psum backward), so the
    in-body ``jax.vjp`` the 1F1B backward runs never transposes a
    collective.  Returns the LOCAL token-mean loss; cross-data-shard
    averaging is the caller's (the pipeline pmean-reduces loss and
    grads over the data axes itself)."""
    loss, _ = _vpi_fwd(x, w, labels, axis, z_loss, chunk)
    return loss


def _vpi_fwd(x, w, labels, axis, z_loss, chunk):
    """Per-shard fwd body — also the inner engine of the shard_map'd
    ``vocab_parallel_cross_entropy`` (one implementation of the math)."""
    xs, ls, n_loc = _flce_flatten(x, labels, chunk)
    wc = w.astype(x.dtype)
    vloc = w.shape[-1]
    voff = jax.lax.axis_index(axis) * vloc

    def body(acc, inp):
        xc, lc = inp
        logits = (xc @ wc).astype(jnp.float32)          # [c, Vloc]
        m = jax.lax.pmax(jnp.max(logits, axis=-1), axis)
        se = jax.lax.psum(
            jnp.sum(jnp.exp(logits - m[:, None]), axis=-1), axis)
        logz = m + jnp.log(se)
        mine = (lc >= voff) & (lc < voff + vloc)
        idx = jnp.clip(lc - voff, 0, vloc - 1)
        picked = jax.lax.psum(
            jnp.where(mine, jnp.take_along_axis(
                logits, idx[:, None], axis=-1)[:, 0], 0.0), axis)
        s = jnp.sum(logz - picked)
        if z_loss:
            s = s + z_loss * jnp.sum(logz ** 2)
        return acc + s, logz

    total, logzs = jax.lax.scan(body, jnp.zeros((), jnp.float32),
                                (xs, ls))
    return total / n_loc, (x, w, labels, logzs)


def _vpi_grads(axis, z_loss, chunk, res, g):
    """Per-shard bwd body; returns (dx at x's dtype, dw in fp32) so the
    shard_map'd wrapper can psum dw across data shards BEFORE casting."""
    x, w, labels, logzs = res
    xs, ls, n_loc = _flce_flatten(x, labels, chunk)
    wc = w.astype(x.dtype)
    vloc = w.shape[-1]
    voff = jax.lax.axis_index(axis) * vloc
    scale = g / n_loc

    def body(dw_acc, inp):
        xc, lc, logz = inp
        logits = (xc @ wc).astype(jnp.float32)
        p = jnp.exp(logits - logz[:, None])             # local softmax cols
        if z_loss:
            p = p * (1.0 + (2.0 * z_loss) * logz)[:, None]
        mine = (lc >= voff) & (lc < voff + vloc)
        idx = jnp.clip(lc - voff, 0, vloc - 1)
        onehot = (jax.nn.one_hot(idx, vloc, dtype=jnp.float32)
                  * mine[:, None].astype(jnp.float32))
        dlogits = ((p - onehot) * scale).astype(x.dtype)
        dx_c = jax.lax.psum(dlogits @ wc.T, axis)       # every vocab shard
        dw_acc = dw_acc + jax.lax.dot_general(
            xc, dlogits, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return dw_acc, dx_c

    dw, dxs = jax.lax.scan(
        body, jnp.zeros(w.shape, jnp.float32), (xs, ls, logzs))
    return dxs.reshape(x.shape).astype(x.dtype), dw


def _vpi_bwd(axis, z_loss, chunk, res, g):
    dx, dw = _vpi_grads(axis, z_loss, chunk, res, g)
    return dx, dw.astype(res[1].dtype), None


vocab_parallel_ce_inbody.defvjp(_vpi_fwd, _vpi_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def data_parallel_fused_cross_entropy(x, w, labels, mesh, z_loss: float = 0.0,
                                      chunk: int = 2048):
    """``fused_linear_cross_entropy`` for data-parallel meshes: ``x``
    [B, T, d] and ``labels`` [B, T] batch-sharded over the data axes,
    ``w`` [d, V] replicated (or fsdp-sharded — GSPMD gathers it at the
    boundary exactly as the unfused head matmul would).

    Each device runs the chunked scan over ITS OWN tokens only, so no
    chunk ever cuts across the batch sharding (the naive chunked scan
    flattens [B·T] in an order that interleaves devices' shards, forcing
    GSPMD to reshard every step).  Loss and dw psum over the data axes;
    dx stays local.  Same math as the dense form — only the reduction
    grouping differs.
    """
    loss, _ = _dp_fwd(x, w, labels, mesh, z_loss, chunk)
    return loss


def _dp_fwd(x, w, labels, mesh, z_loss, chunk):
    batch, nb = _vp_batch_axes(mesh)

    def local(xl, wl, ll):
        xs, ls, n_loc = _flce_flatten(xl, ll, chunk)
        wc = wl.astype(xl.dtype)

        def body(acc, inp):
            xc, lc = inp
            logits = (xc @ wc).astype(jnp.float32)      # [c, V]
            logz = jax.nn.logsumexp(logits, axis=-1)
            picked = jnp.take_along_axis(logits, lc[:, None], axis=-1)[:, 0]
            s = jnp.sum(logz - picked)
            if z_loss:
                s = s + z_loss * jnp.sum(logz ** 2)
            return acc + s, logz

        total, logzs = jax.lax.scan(body, jnp.zeros((), jnp.float32),
                                    (xs, ls))
        if batch:
            total = jax.lax.psum(total, batch)          # global token sum
        return total / (n_loc * nb), logzs

    loss, logzs = shard_map(
        local, mesh=mesh,
        in_specs=(P(batch, None, None), P(None, None), P(batch, None)),
        out_specs=(P(), P(batch, None)), check_vma=False)(x, w, labels)
    return loss, (x, w, labels, logzs)


def _dp_bwd(mesh, z_loss, chunk, res, g):
    x, w, labels, logzs = res
    batch, nb = _vp_batch_axes(mesh)

    def local(xl, wl, ll, logzs_l, gl):
        xs, ls, n_loc = _flce_flatten(xl, ll, chunk)
        wc = wl.astype(xl.dtype)
        scale = gl / (n_loc * nb)

        def body(dw_acc, inp):
            xc, lc, logz = inp
            logits = (xc @ wc).astype(jnp.float32)
            p = jnp.exp(logits - logz[:, None])
            if z_loss:
                p = p * (1.0 + (2.0 * z_loss) * logz)[:, None]
            onehot = jax.nn.one_hot(lc, logits.shape[-1], dtype=jnp.float32)
            dlogits = ((p - onehot) * scale).astype(xl.dtype)
            dx_c = dlogits @ wc.T
            dw_acc = dw_acc + jax.lax.dot_general(
                xc, dlogits, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            return dw_acc, dx_c

        dw, dxs = jax.lax.scan(
            body, jnp.zeros(wl.shape, jnp.float32), (xs, ls, logzs_l))
        if batch:
            dw = jax.lax.psum(dw, batch)                # all tokens' sum
        return dxs.reshape(xl.shape).astype(xl.dtype), dw.astype(wl.dtype)

    dx, dw = shard_map(
        local, mesh=mesh,
        in_specs=(P(batch, None, None), P(None, None), P(batch, None),
                  P(batch, None), P()),
        out_specs=(P(batch, None, None), P(None, None)),
        check_vma=False)(x, w, labels, logzs, g)
    return dx, dw, None


data_parallel_fused_cross_entropy.defvjp(_dp_fwd, _dp_bwd)
