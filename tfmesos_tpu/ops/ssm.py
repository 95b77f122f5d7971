"""Mamba-2 (SSD) state-space mixer pieces, in plain XLA.

The layer, per head ``h`` of ``H`` (head size ``P``, state size ``N``, one
B/C group shared by every head), with ``a_t = dt_t * A_h`` (``A_h < 0``):

    S_t = exp(a_t) S_{t-1} + dt_t x_t B_t^T        S: [P, N]
    y_t = S_t C_t

Two forms of the same recurrence:

* :func:`ssm_update`: one token from a carried state: an elementwise pass
  over the state (read, decay, rank-1 add, write) and a reduction over ``N``,
  all float32: the decode step, bound by the state's bytes;
* :func:`ssd_scan`: a whole chunk of tokens from a given state, in chunks of
  ``chunk`` positions: inside a chunk the masked ``(C B^T) * decay`` product
  against ``dt x`` (the SSD form), between chunks the carried state.  A
  ``lax.scan`` over the chunks (named scope ``ssd_scan``), float32 with the
  products at ``Precision.HIGHEST`` wherever an operand carries a decay or
  the state (the published implementation computes the scan in float32).

A position with ``dt = 0`` leaves the state as it was (``exp(0) = 1`` and a
zero rank-1 term): that is how bucket padding is kept out of a row's state.

:func:`causal_conv` is the depthwise convolution in front of the scan, with
the ``K - 1`` inputs before the chunk (the row's conv tail) as history.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST


def causal_conv(x, w, b, tail=None):
    """Causal depthwise conv over time.  ``x``: [B, T, C]; ``w``: [K, C]
    (tap ``K - 1`` multiplies the current position); ``b``: [C]; ``tail``:
    [B, K - 1, C], the inputs before the chunk (zeros when None).  Returns
    (out [B, T, C] float32, the padded input [B, K - 1 + T, C] from which
    :func:`conv_tail` takes the next tail)."""
    bsz, t, c = x.shape
    k = w.shape[0]
    if tail is None:
        tail = jnp.zeros((bsz, k - 1, c), x.dtype)
    xp = jnp.concatenate([tail.astype(x.dtype), x], axis=1)
    wf = w.astype(jnp.float32)
    out = b.astype(jnp.float32)
    for j in range(k):
        out = out + xp[:, j:j + t].astype(jnp.float32) * wf[j]
    return out, xp


def conv_tail(xp, valid, k: int):
    """The ``k - 1`` inputs that end at each row's true last position:
    rows ``valid .. valid + k - 2`` of the padded input (``valid`` [B]: the
    number of real positions in the chunk)."""
    return jax.vmap(
        lambda x, v: jax.lax.dynamic_slice_in_dim(x, v, k - 1, 0))(xp, valid)


def ssm_update(state, x, dt, a, b, c):
    """One token.  ``state``: [B, H, P, N] float32; ``x``: [B, H, P];
    ``dt``: [B, H] (after softplus); ``a``: [H] (negative); ``b``, ``c``:
    [B, N].  Returns (y [B, H, P] float32, new state)."""
    with jax.named_scope("ssm_update"):
        f32 = jnp.float32
        dt = dt.astype(f32)
        decay = jnp.exp(dt * a.astype(f32))[..., None, None]
        dx = (dt[..., None] * x.astype(f32))[..., None]
        new = state * decay + dx * b.astype(f32)[:, None, None, :]
        y = jnp.sum(new * c.astype(f32)[:, None, None, :], axis=-1)
        return y, new


def ssd_scan(x, dt, a, b, c, state, chunk: int):
    """A chunk of ``T`` tokens from ``state``.  ``x``: [B, T, H, P];
    ``dt``: [B, T, H] float32 (after softplus; 0 at padding); ``a``: [H];
    ``b``, ``c``: [B, T, N]; ``state``: [B, H, P, N] float32.  Returns
    (y [B, T, H, P] float32, the state after the last position)."""
    bsz, t, h, p = x.shape
    n = b.shape[-1]
    q = min(chunk, t)
    pad = -t % q
    if pad:
        # whole chunks: the padding's dt is 0, so the state passes through
        x, dt, b, c = (jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2))
                       for v in (x, dt, b, c))
    nc = (t + pad) // q
    f32 = jnp.float32

    def chunks(v):      # [B, nc * q, ...] -> [nc, B, q, ...]
        return jnp.moveaxis(v.reshape(bsz, nc, q, *v.shape[2:]), 1, 0)

    causal = jnp.tril(jnp.ones((q, q), bool))[None, :, :, None]
    af = a.astype(f32)

    def body(s, inp):
        xq, dtq, bq, cq = inp
        da = dtq * af                                   # [B, q, H], <= 0
        cum = jnp.cumsum(da, axis=1)
        # inside the chunk: y_i += sum_{j<=i} exp(cum_i - cum_j) (C_i.B_j)
        #                                  dt_j x_j
        cb = jnp.einsum("bin,bjn->bij", cq, bq,
                        preferred_element_type=f32)     # bf16-exact operands
        seg = cum[:, :, None, :] - cum[:, None, :, :]   # [B, i, j, H]
        m = cb[..., None] * jnp.exp(jnp.where(causal, seg, -jnp.inf))
        xdt = xq.astype(f32) * dtq[..., None]           # [B, q, H, P]
        y = jnp.einsum("bijh,bjhp->bihp", m, xdt, precision=HI)
        # from the state before the chunk
        y = y + jnp.einsum("bin,bhpn->bihp", cq.astype(f32), s,
                           precision=HI) * jnp.exp(cum)[..., None]
        # the state after it
        to_end = jnp.exp(cum[:, -1:, :] - cum)[..., None]
        s = s * jnp.exp(cum[:, -1])[..., None, None] + jnp.einsum(
            "bjhp,bjn->bhpn", xdt * to_end, bq.astype(f32), precision=HI)
        return s, y

    with jax.named_scope("ssd_scan"):
        state, y = jax.lax.scan(body, state,
                                (chunks(x), chunks(dt), chunks(b), chunks(c)))
    y = jnp.moveaxis(y, 0, 1).reshape(bsz, nc * q, h, p)
    return y[:, :t], state
