"""Kimi Delta Attention (KDA) mixer pieces: a gated delta rule with a decay
per key channel over a matrix state per head: the recurrence in plain XLA,
and the decode step's one-token update as one Pallas pass over the state
store.

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
  so both reductions over the key channels read the state as it is stored,
  and the new state is written from that read and never read back: the
  decode step's single pass, below;
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

**The decode step's single pass** (:func:`kda_update_stacked`).  XLA compiles
the stacked update to three instructions a layer: it copies the layer out of
the store (a dynamic slice is not fused into a reduction), reduces the copy,
then reads and writes the layer in place: five passes over the state for
two.  On the TPU the update is therefore one Pallas kernel, named
``kda_update`` in a device trace (under the ``kda.update`` scope), over the
WHOLE stacked store (aliased to its output; the layer rides the scalar
prefetch, so no layer is sliced out or copied back): grid step ``(row,
block)`` holds whole heads of one row's state in VMEM, a whole row-layer
where the budget allows, and per head computes from the ``[dk, dv]`` tile it
holds the float32 expressions of the XLA form: ``red = S^T [alpha k, alpha
q]`` (``dk`` lies on sublanes: vreg adds and one sublane reduce a head),
``u = v - red_k``, ``new = S * alpha[:, None] + (beta k)[:, None] * u[None,
:]`` stored where the tile was read, ``o = red_q + (beta k . q) u`` as a
lane-dense ``[H, dv]`` row.  ``alpha``, ``alpha k``, ``alpha q`` and ``beta
k`` are wanted as COLUMNS over the key channels: they travel lane-dense
(``[rows, H * dk / 128, 128]`` views; a ``[.., 1]`` column would be padded
128 x in HBM), each is turned by one ``[128, 128]`` transpose a grid step,
and a head's column is then one lane of the turned tile broadcast over the
lanes (a lane gather a vreg: four a state vreg, all of it under the state's
DMA on a v5e); ``beta k . q`` crosses as scalars in SMEM.  The heads of a
block are walked by a loop, ``_GROUP_TILES`` tiles an iteration, so that the
body every program traces and lowers at start-up stays short.  Everything
stays float32 on the VPU.  Off the TPU (the CPU tests), and for shapes the kernel
does not tile (a row that is not whole 128-channel tiles, ``dk`` not whole
sublanes, ``dv`` not whole lanes), the XLA form runs: it is the
specification the kernel is tested against.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tfmesos_tpu.ops.moe import _on_tpu
# the update kernel tiles and budgets VMEM as ``ssm_update`` does: a state
# tile is ``[_TILE, dv]`` (``_TILE`` = 128 channels: a head's key channels,
# heads one after another; 16 vregs at dv = 128), the per-channel operands
# are ``[.., _TILE]`` lane-dense rows, and one state block in and one out,
# each double-buffered, take ``_UPDATE_VMEM_BUDGET`` at most (a whole
# ``[8192, 128]`` float32 row-layer, 4 MiB, is one block)
from tfmesos_tpu.ops.ssm import (_TILE, _UPDATE_VMEM_BUDGET,
                                 _UPDATE_VMEM_LIMIT)

HI = jax.lax.Precision.HIGHEST

#: tiles of ``_TILE`` channels one iteration of the update kernel's loop
#: unrolls (8 heads of 128): enough for the scheduler to hide a head's
#: reduce -> u -> write chain behind its neighbours'
_GROUP_TILES = 8


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


def _update_block(h: int, dk: int, dv: int) -> Optional[int]:
    """Channels of one state block of the update kernel, from what a call
    sees: the most whole heads that are whole tiles of ``_TILE`` channels,
    divide a row's ``h * dk`` channels, fit ``_UPDATE_VMEM_BUDGET`` (in +
    out, double-buffered, float32) and number at most ``_TILE`` tiles (a
    step turns its tiles' operands on the lanes of one tile).  None where a
    row is not whole tiles, ``dk`` not whole sublanes, ``dv`` not whole
    lanes, or one such unit is over the budget."""
    unit = math.lcm(dk, _TILE)
    if dk % 8 or dv % _TILE or (h * dk) % unit:
        return None
    units = h * dk // unit
    cap = min(_TILE * _TILE // unit, _UPDATE_VMEM_BUDGET // (4 * unit * dv * 4))
    return next((n * unit for n in range(min(units, cap), 0, -1)
                 if units % n == 0), None)


def _update_kernel(layer_ref, kq_ref, s_ref, a_ref, ak_ref, aq_ref, bk_ref,
                   v_ref, o_ref, y_ref, *, heads: int, dk: int, group: int):
    """``heads`` whole heads of one row's state of one layer: a ``[heads *
    dk, dv]`` block, ``group`` heads (whole tiles) a loop iteration.

    ``kq_ref`` [rows, H] (SMEM): ``beta k . q``; ``a_ref`` (``alpha``),
    ``ak_ref``, ``aq_ref``, ``bk_ref`` [H * dk / _TILE, _TILE]: the whole
    row, channel ``t * _TILE + i`` at ``[t, i]``; ``v_ref`` / ``y_ref`` [H,
    dv]: the whole row.  The four are wanted as columns over the key
    channels (sublanes, every lane alike): each crosses through ONE
    transpose a grid step, of a tile whose lane ``j`` belongs to the step's
    tile ``j``; an iteration rolls its own tiles to the first lanes, and a
    head's columns are then one lane of that, broadcast.  The loop keeps
    the body that is traced and lowered (at every start-up, compile cache
    or not) to ``group`` heads: a whole row of 64 unrolled cost every
    decode program 1.2 s."""
    del layer_ref
    row, j = pl.program_id(0), pl.program_id(1)
    tiles = heads * dk // _TILE
    whole = heads == v_ref.shape[0]     # one block a row: static indices
    first = 0 if whole else j * heads

    def columns(ref):
        x = ref[...] if whole else ref[pl.ds(j * tiles, tiles), :]
        if tiles < _TILE:
            x = jnp.concatenate(
                [x, jnp.zeros((_TILE - tiles, _TILE), jnp.float32)], axis=0)
        return x.T                      # [channel of a tile, the step's tile]

    turned = [columns(r) for r in (a_ref, ak_ref, aq_ref, bk_ref)]
    piece = math.gcd(dk, _TILE)         # channels of a head inside ONE tile

    def column(x, at):                  # channels at .. at + piece of a group
        return x[at % _TILE:at % _TILE + piece, at // _TILE:at // _TILE + 1]

    def heads_of(gi):
        """Heads ``gi * group .. (gi + 1) * group`` of the block."""
        a, ak, aq, bk = turned
        if heads > group:               # this group's tiles to lane 0 on
            a, ak, aq, bk = (
                pltpu.roll(x, (_TILE - gi * (group * dk // _TILE)) % _TILE,
                           axis=1) for x in turned)

        def rows(at):                   # of the block's state
            return pl.ds(pl.multiple_of(gi * group * dk + at, piece), piece)

        for h in range(group):
            pieces = range(h * dk, (h + 1) * dk, piece)
            head = pl.ds(first + gi * group + h, 1)
            red_k = red_q = 0.0
            for at in pieces:           # S^T [alpha k, alpha q]: one read
                s = s_ref[rows(at), :]
                red_k += jnp.sum(s * column(ak, at), axis=0, keepdims=True)
                red_q += jnp.sum(s * column(aq, at), axis=0, keepdims=True)
            u = v_ref[head, :] - red_k
            for at in pieces:
                o_ref[rows(at), :] = (s_ref[rows(at), :] * column(a, at)
                                      + column(bk, at) * u)
            y_ref[head, :] = red_q + kq_ref[row, first + gi * group + h] * u

    if heads == group:
        heads_of(0)
    else:
        jax.lax.fori_loop(0, heads // group,
                          lambda gi, _: heads_of(gi), None)


def _update_group(heads: int, dk: int) -> int:
    """Heads one loop iteration of the kernel unrolls: whole tiles, a
    divisor of the block's ``heads``, at most ``_GROUP_TILES`` tiles where
    one head is no more."""
    unit = math.lcm(dk, _TILE) // dk
    units = heads // unit
    cap = max(1, _GROUP_TILES * _TILE // (unit * dk))
    return unit * next(n for n in range(min(units, cap), 0, -1)
                       if units % n == 0)


def _update_call(store, layer, alpha, ak, aq, bk, kq, v, block: int,
                 interpret: bool):
    lk, rows, hp, dv = store.shape
    h = kq.shape[1]
    row_tiles = hp // _TILE
    s_spec = pl.BlockSpec((None, None, block, dv),
                          lambda r, j, li, kq: (li[0], r, j, 0))
    col_spec = pl.BlockSpec((None, row_tiles, _TILE),
                            lambda r, j, li, kq: (r, 0, 0))
    row_spec = pl.BlockSpec((None, h, dv), lambda r, j, li, kq: (r, 0, 0))
    return pl.pallas_call(
        functools.partial(_update_kernel, heads=block * h // hp, dk=hp // h,
                          group=_update_group(block * h // hp, hp // h)),
        out_shape=(jax.ShapeDtypeStruct(store.shape, store.dtype),
                   jax.ShapeDtypeStruct((rows, h, dv), jnp.float32)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(rows, hp // block),
            in_specs=[s_spec] + [col_spec] * 4 + [row_spec],
            out_specs=(s_spec, row_spec)),
        # the store (operand 2, behind the two prefetched scalars) IS the
        # first output: the kernel writes each block where it read it
        input_output_aliases={2: 0},
        name="kda_update", interpret=interpret,
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_UPDATE_VMEM_LIMIT),
    )(jnp.asarray(layer, jnp.int32).reshape(1), kq, store,
      *(x.reshape(rows, row_tiles, _TILE) for x in (alpha, ak, aq, bk)), v)


def kda_update_stacked(store, layer, q, k, v, g, beta,
                       use_pallas: Optional[bool] = None,
                       interpret: bool = False):
    """:func:`kda_update` on layer ``layer`` (traced OK) of the stacked state
    store, in place.  ``store``: [Lk, rows, H * dk, dv] float32; ``q``, ``k``,
    ``g``: [rows, H, dk]; ``v``: [rows, H, dv]; ``beta``: [rows, H].  Returns
    (o [rows, H, dv] float32, the store with that layer's new state).

    On the TPU (or ``interpret``) one Pallas pass reads each block of the
    layer's state once, reduces ``S^T [alpha k, alpha q]`` from it and writes
    the new state where the block was; the block comes from the shapes
    (``_update_block``).  Elsewhere, and for a row that is not whole
    128-channel tiles, a key size that is not whole sublanes or a value size
    that is not whole lanes, the XLA form runs."""
    rows, h, dk = k.shape
    dv = store.shape[-1]
    f32 = jnp.float32
    block = _update_block(h, dk, dv)
    with jax.named_scope("kda.update"):
        q, k, v, beta = (a.astype(f32) for a in (q, k, v, beta))
        alpha = jnp.exp(g.astype(f32))
        # S'^T k = S^T (alpha k) and S'^T q likewise: one pass over the
        # state as it is stored
        ak, aq, bk = alpha * k, alpha * q, beta[..., None] * k
        kq = jnp.sum(bk * q, axis=-1)
        if block is not None and (_on_tpu(use_pallas) or interpret):
            new, o = _update_call(store, layer, alpha, ak, aq, bk, kq, v,
                                  block, interpret)
            return o, new
        # the store with heads and key channels apart, for the read AND the
        # write (a bitcast): every operand of the update then broadcasts
        # along a dim of its own, and none is laid out at the state's size
        # beside it (u over the key channels was: 805 MB a layer-step)
        store5 = store.reshape(store.shape[0], rows, h, dk, dv)
        s0 = store5[layer]
        akq = jnp.stack([ak, aq], axis=2)                   # [rows, H, 2, dk]
        red = jnp.sum(s0[:, :, None] * akq[..., None], axis=-2)
        u = v - red[:, :, 0]
        new = s0 * alpha[..., None] + bk[..., None] * u[..., None, :]
        o = red[:, :, 1] + kq[..., None] * u
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
