"""Kimi Delta Attention (KDA) mixer pieces: a gated delta rule with a decay
per key channel over a matrix state per head, in plain XLA.

The layer, per head of ``H`` (key size ``dk``, value size ``dv``), with the
log-decay ``g_t`` in ``R^dk`` (``g_t <= 0``, ``alpha_t = exp(g_t)``), the step
``beta_t`` in ``(0, 2)`` and ``q_t``, ``k_t`` already l2-normalised (``q_t``
scaled by ``dk ** -0.5``):

    S'  = Diag(alpha_t) S_{t-1}                      S: [dk, dv]
    u_t = v_t - S'^T k_t
    S_t = S' + beta_t k_t u_t^T
    o_t = S_t^T q_t

that is ``S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t
v_t^T``: unlike the Mamba-2 update (``ops/ssm.py``) it READS the state
(``S'^T k_t``) before it writes it.  Three forms of the same recurrence:

* :func:`kda_update`: one token from a carried state, written as the
  equations above: the specification;
* :func:`kda_update_stacked`: that step on one layer of the whole stacked
  state store ``[Lk, rows, H * dk, dv]`` (heads and key channels ONE dim: the
  layout ``ssm_update`` moves for a Mamba store), in place under donation.
  ``o_t = S'^T q_t + beta_t (k_t . q_t) u_t`` and ``S'^T x = S^T (alpha x)``,
  so both reductions over the key channels read the state as it is stored in
  ONE pass, and the new state is written from a second read and never read
  back.  As the TPU compiler schedules it today the first pass copies the
  layer out of the store before it reduces it (a dynamic slice is not fused
  into a reduction): five passes over a layer's state where two would do,
  which is what a one-pass Pallas kernel after ``ops/ssm.py``'s ``ssm_update``
  would buy (PERF.md section 7);
* :func:`kda_chunk_scan`: a whole chunk of tokens from a given state, in
  chunks of ``chunk`` positions (the WY / UT-transform form).  With the
  cumulative log-decay ``G_t`` of a chunk, ``A_tj = sum_d k_t[d] k_j[d]
  exp(G_t[d] - G_j[d])`` for ``j < t`` and ``B_tj`` the same with ``q_t`` for
  ``j <= t``: ``(I + A Diag(beta)) U = V - (K * exp(G)) S_0`` is solved once
  a chunk (the unit lower-triangular inverse as a product of ``log2(chunk)``
  factors ``I + N^(2^i)``: ``N`` is nilpotent), then ``O = (Q * exp(G)) S_0 +
  B Diag(beta) U`` and ``S_C = Diag(exp(G_C)) S_0 + (K * exp(G_C - G))^T
  Diag(beta) U``.  Every exponent is a difference taken BEFORE the ``exp``
  and is ``<= 0``: no decay, however strong, leaves float32's range.  A
  ``lax.scan`` over the chunks, float32, every product that carries a decay
  or the state at ``Precision.HIGHEST``.

A position with ``g = 0`` and ``beta = 0`` leaves the state as it was: that is
how bucket padding is kept out of a row's state.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST


def l2norm(x, eps: float = 1e-6):
    """``x / sqrt(sum(x^2) + eps)`` over the last dim, float32."""
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


def kda_update(state, q, k, v, g, beta):
    """One token.  ``state``: [B, H, dk, dv] float32; ``q``, ``k``: [B, H,
    dk]; ``v``: [B, H, dv]; ``g``: [B, H, dk] (the log-decay, <= 0);
    ``beta``: [B, H].  Returns (o [B, H, dv] float32, new state)."""
    f32 = jnp.float32
    q, k, v = (a.astype(f32) for a in (q, k, v))
    s = state * jnp.exp(g.astype(f32))[..., None]
    u = v - jnp.sum(s * k[..., None], axis=-2)
    new = s + (beta.astype(f32)[..., None] * k)[..., None] * u[..., None, :]
    return jnp.sum(new * q[..., None], axis=-2), new


def kda_update_stacked(store, layer, q, k, v, g, beta):
    """:func:`kda_update` on layer ``layer`` (traced OK) of the stacked state
    store, in place.  ``store``: [Lk, rows, H * dk, dv] float32; ``q``, ``k``,
    ``g``: [rows, H, dk]; ``v``: [rows, H, dv]; ``beta``: [rows, H].  Returns
    (o [rows, H, dv] float32, the store with that layer's new state)."""
    rows, h, dk = k.shape
    dv = store.shape[-1]
    f32 = jnp.float32
    with jax.named_scope("kda.update"):
        q, k, v, beta = (a.astype(f32) for a in (q, k, v, beta))
        alpha = jnp.exp(g.astype(f32))
        # the store with heads and key channels apart, for the read AND the
        # write (a bitcast): every operand of the update then broadcasts
        # along a dim of its own, and none is laid out at the state's size
        # beside it (u over the key channels was: 805 MB a layer-step)
        store5 = store.reshape(store.shape[0], rows, h, dk, dv)
        s0 = store5[layer]
        # S'^T k = S^T (alpha k) and S'^T q likewise: one pass over the
        # state as it is stored
        akq = jnp.stack([alpha * k, alpha * q], axis=2)     # [rows, H, 2, dk]
        red = jnp.sum(s0[:, :, None] * akq[..., None], axis=-2)
        u = v - red[:, :, 0]
        bk = beta[..., None] * k
        new = s0 * alpha[..., None] + bk[..., None] * u[..., None, :]
        o = red[:, :, 1] + jnp.sum(bk * q, axis=-1, keepdims=True) * u
        return o, store5.at[layer].set(new).reshape(store.shape)


def _unit_lower_inverse(n):
    """``(I - n)^-1`` for a strictly lower-triangular ``n`` [.., C, C]:
    ``n`` is nilpotent (``n^C = 0``), so the Neumann series is the finite
    product ``(I + n)(I + n^2)(I + n^4)...``."""
    c = n.shape[-1]
    eye = jnp.eye(c, dtype=n.dtype)
    inv, power, reach = eye + n, n, 2
    while reach < c:
        power = jnp.matmul(power, power, precision=HI)
        inv = inv + jnp.matmul(inv, power, precision=HI)
        reach *= 2
    return inv


def kda_chunk_scan(q, k, v, g, beta, state, chunk: int):
    """A chunk of ``T`` tokens from ``state``.  ``q``, ``k``: [B, T, H, dk]
    (normalised); ``v``: [B, T, H, dv]; ``g``: [B, T, H, dk] float32 (0 at
    padding); ``beta``: [B, T, H] float32 (0 at padding); ``state``: [B, H,
    dk, dv] float32.  Returns (o [B, T, H, dv] float32, the state after the
    last position)."""
    bsz, t, h, dk = k.shape
    c = min(chunk, t)
    pad = -t % c
    if pad:
        # whole chunks: the padding's g and beta are 0, the state passes
        q, k, v, g, beta = (
            jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
            for a in (q, k, v, g, beta))
    nc = (t + pad) // c
    f32 = jnp.float32

    def chunks(a):      # [B, nc * c, H, ...] -> [nc, B, H, c, ...]
        a = a.reshape(bsz, nc, c, *a.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(a, 3, 2), 1, 0)

    lower = jnp.tril(jnp.ones((c, c), bool))
    strict = jnp.tril(jnp.ones((c, c), bool), -1)

    def body(s, inp):
        qc, kc, vc, gc, bc = inp                    # [B, H, c, ..]
        qc, kc, vc = (a.astype(f32) for a in (qc, kc, vc))
        cum = jnp.cumsum(gc, axis=2)                # [B, H, c, dk], <= 0
        # exp(G_t - G_j) for j <= t, the difference taken first
        seg = cum[:, :, :, None, :] - cum[:, :, None, :, :]
        dec = jnp.exp(jnp.where(lower[:, :, None], seg, -jnp.inf))
        kj = kc[:, :, None, :, :] * dec             # [B, H, t, j, dk]
        a = jnp.sum(kc[:, :, :, None, :] * kj, axis=-1)
        b = jnp.sum(qc[:, :, :, None, :] * kj, axis=-1)
        a = jnp.where(strict, a, 0.0) * bc[:, :, None, :]
        b = b * bc[:, :, None, :]
        ecum = jnp.exp(cum)
        rhs = vc - jnp.einsum("bhtd,bhdv->bhtv", kc * ecum, s, precision=HI)
        u = jnp.matmul(_unit_lower_inverse(-a), rhs, precision=HI)
        o = jnp.einsum("bhtd,bhdv->bhtv", qc * ecum, s, precision=HI) \
            + jnp.matmul(b, u, precision=HI)
        last = cum[:, :, -1:, :]
        k_end = kc * jnp.exp(last - cum) * bc[..., None]
        s = s * jnp.exp(last)[:, :, 0, :, None] + jnp.einsum(
            "bhtd,bhtv->bhdv", k_end, u, precision=HI)
        return s, o

    with jax.named_scope("kda.chunk_scan"):
        state, o = jax.lax.scan(
            body, state,
            (chunks(q), chunks(k), chunks(v), chunks(g), chunks(beta)))
    # [nc, B, H, c, dv] -> [B, T, H, dv]
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 1), 2, 3).reshape(
        bsz, nc * c, h, o.shape[-1])
    return o[:, :t], state
