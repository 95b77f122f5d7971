"""Mamba-2 (SSD) state-space mixer pieces: the recurrence in plain XLA, and
the decode step's one-token update as one Pallas pass over the state store.

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

**The decode step's single pass** (:func:`ssm_update_stacked`).  XLA compiles
``new = S * decay + dx * B`` followed by ``ssm.at[layer].set(new)`` and
``sum(new * C)`` to an in-place fusion and a second fusion that reads the
store AGAIN for the reduction: three passes over the state for two.  On the
TPU the update is therefore one Pallas kernel, named ``ssm_update`` in a
device trace, over the WHOLE stacked store ``[Lm, rows, H * P, N]`` (aliased
to its output; the layer rides the scalar prefetch, so no layer is sliced out
or copied back): grid step ``(row, block)`` holds one ``[block, N]`` block of
a row's state in VMEM, computes the same float32 expressions as
:func:`ssm_update`, stores the block where it came from and reduces
``new * C`` over ``N`` from the same registers.  Everything per channel
travels lane-dense (``[rows, H * P / 128, 128]`` views of ``dt x`` and of
``y``; a ``[.., 1]`` column would be padded 128 x in HBM) and the per-head
decay as scalars in SMEM.  Off the TPU (the CPU tests, any shape the kernel
does not tile) the XLA form runs: it is the specification the kernel is
tested against.

:func:`causal_conv` is the depthwise convolution in front of the scan, with
the ``K - 1`` inputs before the chunk (the row's conv tail) as history.
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

HI = jax.lax.Precision.HIGHEST

#: channels one tile of the update kernel covers: a state tile is
#: ``[_TILE, N]`` (16 vregs at N = 128) and the per-channel operands are
#: ``[.., _TILE]`` lane-dense rows
_TILE = 128
#: VMEM the update kernel's state blocks may take: one block in and one out,
#: each double-buffered by the pipeline.  At Granite's ``[8192, 128]`` float32
#: a row-layer (4 MiB) is one block: a grid step costs ~0.36 us whatever it
#: moves, so whole rows (64 steps a layer) read 0.86 ms a layer where blocks
#: of 1,024 channels (512 steps) read 1.03 (PERF.md section 6, PR 33).
_UPDATE_VMEM_BUDGET = 16 * 2 ** 20
#: the kernel's scoped VMEM: the state blocks, the row's lane-dense operands
#: and the two gather tiles
_UPDATE_VMEM_LIMIT = 32 * 2 ** 20


def causal_conv(x, w, b, tail=None):
    """Causal depthwise conv over time.  ``x``: [B, T, C]; ``w``: [K, C]
    (tap ``K - 1`` multiplies the current position); ``b``: [C] (None: no
    bias); ``tail``: [B, K - 1, C], the inputs before the chunk (zeros when
    None).  Returns
    (out [B, T, C] float32, the padded input [B, K - 1 + T, C] from which
    :func:`conv_tail` takes the next tail)."""
    bsz, t, c = x.shape
    k = w.shape[0]
    if tail is None:
        tail = jnp.zeros((bsz, k - 1, c), x.dtype)
    xp = jnp.concatenate([tail.astype(x.dtype), x], axis=1)
    wf = w.astype(jnp.float32)
    out = 0.0 if b is None else b.astype(jnp.float32)
    for j in range(k):
        out = out + xp[:, j:j + t].astype(jnp.float32) * wf[j]
    return out, xp


def conv_tail(xp, valid, k: int):
    """The ``k - 1`` inputs that end at each row's true last position:
    rows ``valid .. valid + k - 2`` of the padded input (``valid`` [B]: the
    number of real positions in the chunk)."""
    return jax.vmap(
        lambda x, v: jax.lax.dynamic_slice_in_dim(x, v, k - 1, 0))(xp, valid)


def _step_terms(x, dt, a):
    """What one token brings to the state, float32: the decay ``exp(dt A)``
    [B, H] and the input ``dt x`` [B, H, P]."""
    dt = dt.astype(jnp.float32)
    return (jnp.exp(dt * a.astype(jnp.float32)),
            dt[..., None] * x.astype(jnp.float32))


def ssm_update(state, x, dt, a, b, c):
    """One token.  ``state``: [B, H, P, N] float32; ``x``: [B, H, P];
    ``dt``: [B, H] (after softplus); ``a``: [H] (negative); ``b``, ``c``:
    [B, N].  Returns (y [B, H, P] float32, new state)."""
    with jax.named_scope("ssm_update"):
        f32 = jnp.float32
        decay, dx = _step_terms(x, dt, a)
        new = (state * decay[..., None, None]
               + dx[..., None] * b.astype(f32)[:, None, None, :])
        y = jnp.sum(new * c.astype(f32)[:, None, None, :], axis=-1)
        return y, new


def _update_block(hp: int, n: int) -> Optional[int]:
    """Channels of one state block of the update kernel, from what a call
    sees: the most whole tiles of ``_TILE`` channels that divide a row's
    ``hp`` channels, fit ``_UPDATE_VMEM_BUDGET`` (in + out, double-buffered,
    float32) and number at most ``_TILE`` (a step gathers its tiles' y on
    the lanes of one tile).  None where a row is not whole tiles, or one
    tile is over the budget."""
    if hp % _TILE:
        return None
    tiles = hp // _TILE
    cap = min(_TILE, _UPDATE_VMEM_BUDGET // (4 * _TILE * n * 4))
    return next((t * _TILE for t in range(min(tiles, cap), 0, -1)
                 if tiles % t == 0), None)


def _update_kernel(layer_ref, decay_ref, s_ref, dx_ref, b_ref, c_ref, o_ref,
                   y_ref, *, tiles: int, head: int):
    """One ``[tiles * _TILE, N]`` block of one row's state of one layer.

    ``decay_ref`` [rows, H] (SMEM); ``dx_ref`` / ``y_ref`` [hp / _TILE,
    _TILE]: the whole row, channel ``t * _TILE + i`` at ``[t, i]``;
    ``b_ref`` / ``c_ref`` [1, N].  A tile's ``dt x`` is wanted as a column
    (channel on sublanes, every lane alike) and its y comes out of the lane
    reduction as one: both cross through ONE transpose a grid step, of a
    tile whose lane ``j`` belongs to the step's tile ``j``, read and
    written a tile at a time by a lane mask (a transpose a tile, three
    ways, left the kernel bound by the XLU: PERF.md section 6, PR 33)."""
    del layer_ref
    unit = math.gcd(head, _TILE)        # channels of a tile under ONE head
    row, base = pl.program_id(0), pl.program_id(1) * tiles
    step = pl.ds(pl.multiple_of(base, 8) if tiles % 8 == 0 else base, tiles)
    b, c = b_ref[...], c_ref[...]
    lane = jax.lax.broadcasted_iota(jnp.int32, (_TILE, _TILE), 1)
    dx = dx_ref[step, :]
    if tiles < _TILE:
        dx = jnp.concatenate(
            [dx, jnp.zeros((_TILE - tiles, _TILE), jnp.float32)], axis=0)
    dx = dx.T                           # [channel of a tile, the step's tile]

    def tile(j, ys):
        mine = lane == j
        dx_col = jnp.sum(jnp.where(mine, dx, 0.0), axis=-1, keepdims=True)
        decayed = []
        for k in range(_TILE // unit):  # the heads of one tile: 2 at P = 64
            at = pl.ds(pl.multiple_of(j * _TILE + k * unit, unit), unit)
            decayed.append(s_ref[at, :] * decay_ref[
                row, ((base + j) * _TILE + k * unit) // head])
        new = jnp.concatenate(decayed, axis=0) + dx_col * b
        o_ref[pl.ds(pl.multiple_of(j * _TILE, _TILE), _TILE), :] = new
        y_col = jnp.sum(new * c, axis=-1, keepdims=True)
        return jnp.where(mine, y_col, ys)

    ys = jax.lax.fori_loop(0, tiles, tile,
                           jnp.zeros((_TILE, _TILE), jnp.float32))
    y_ref[step, :] = ys.T[:tiles, :]


def _update_call(store, layer, dx, decay, b, c, block: int, interpret: bool):
    lm, rows, hp, n = store.shape
    row_tiles = hp // _TILE       # a row's; a block holds block // _TILE
    s_spec = pl.BlockSpec((None, None, block, n),
                          lambda r, j, li, dec: (li[0], r, j, 0))
    row_spec = pl.BlockSpec((None, row_tiles, _TILE),
                            lambda r, j, li, dec: (r, 0, 0))
    bc_spec = pl.BlockSpec((None, 1, n), lambda r, j, li, dec: (r, 0, 0))
    return pl.pallas_call(
        functools.partial(_update_kernel, tiles=block // _TILE,
                          head=hp // decay.shape[1]),
        out_shape=(jax.ShapeDtypeStruct(store.shape, store.dtype),
                   jax.ShapeDtypeStruct((rows, row_tiles, _TILE),
                                        jnp.float32)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(rows, hp // block),
            in_specs=[s_spec, row_spec, bc_spec, bc_spec],
            out_specs=(s_spec, row_spec)),
        # the store (operand 2, behind the two prefetched scalars) IS the
        # first output: the kernel writes each block where it read it
        input_output_aliases={2: 0},
        name="ssm_update", interpret=interpret,
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_UPDATE_VMEM_LIMIT),
    )(jnp.asarray(layer, jnp.int32).reshape(1), decay, store,
      dx.reshape(rows, row_tiles, _TILE), b[:, None, :], c[:, None, :])


def ssm_update_stacked(store, layer, x, dt, a, b, c,
                       use_pallas: Optional[bool] = None,
                       interpret: bool = False):
    """:func:`ssm_update` on layer ``layer`` (traced OK) of the stacked state
    store, in place.  ``store``: [Lm, rows, H * P, N] float32; ``x``: [rows,
    H, P]; ``dt``: [rows, H]; ``a``: [H]; ``b``, ``c``: [rows, N].  Returns
    (y [rows, H, P] float32, the store with that layer's new state).

    On the TPU (or ``interpret``) one Pallas pass reads each block of the
    layer's state once, writes it where it was and emits y from the same
    block; the block comes from the shapes (``_update_block``).  Elsewhere,
    and for a row that is not whole 128-channel tiles or a head size that
    is not whole sublanes, the XLA form runs on the layer's slice."""
    rows, h, p = x.shape
    n = store.shape[-1]
    block = _update_block(h * p, n) if p % 8 == 0 else None
    if block is None or not (_on_tpu(use_pallas) or interpret):
        y, new = ssm_update(store[layer].reshape(rows, h, p, n), x, dt, a,
                            b, c)
        return y, store.at[layer].set(new.reshape(rows, h * p, n))
    with jax.named_scope("ssm_update"):
        decay, dx = _step_terms(x, dt, a)
        new, y = _update_call(store, layer, dx, decay,
                              b.astype(jnp.float32), c.astype(jnp.float32),
                              block, interpret)
    return y.reshape(rows, h, p), new


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
