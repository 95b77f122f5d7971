"""The expert layer that is told which experts it holds: route over every
expert, keep the assignments that fall on the held ones, lay them out sorted
by expert and run ONE grouped matmul over them.  No capacity, no drops, and no
expert sees a token that was not routed to it.

The layout (:func:`grouped_layout`): an assignment ``(token, expert, gate)``
on held expert ``e`` goes to row ``start[e] + rank`` of a sorted buffer, each
expert's rows padded up to a whole tile of ``tile`` rows so that a tile
belongs to one expert; assignments on experts held elsewhere go nowhere.
The buffer's height is static (every assignment could fall here, plus a
tile's padding per expert); the tiles past the live ones are skipped.

The kernels (:func:`grouped_matmul`, :func:`grouped_swiglu`; Pallas, named
``moe_grouped_matmul`` / ``moe_grouped_swiglu`` in a device trace): grid step
``(j, i)`` multiplies tile ``i`` of the sorted rows by column block ``j`` of
the weights of the tile's expert, read through a scalar-prefetched tile ->
expert table; a dead tile repeats the last live one's blocks (no copy) and
computes nothing.  Off the TPU the same layout runs through a plain einsum
over tiles.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: VMEM the double-buffered blocks of one grid step may take
_VMEM_LIMIT = 32 * 1024 * 1024
#: bytes of one weight block (the column block is sized to this)
_W_BLOCK_BYTES = 2 * 1024 * 1024


def pick_tile(assignments: int, n_experts: int) -> int:
    """Rows a tile of the sorted buffer holds: about half an expert's mean
    share, a power of two between 16 (a bf16 tile's sublanes) and 128 (the
    MXU's rows), so that padding every expert to whole tiles stays a small
    part of the rows."""
    mean = assignments / max(1, n_experts)
    tile = 16
    while tile < 128 and tile * 4 <= mean:
        tile *= 2
    return tile


def tile_rows(counts, tokens: int, top_k: int, n_experts: int):
    """Rows of the live tiles a step of ``tokens`` tokens is padded to:
    ``counts`` [..., held] assignments an expert, each rounded up to whole
    tiles of the step's ``pick_tile`` as ``grouped_layout`` lays them out
    (``live_tiles * tile`` there; a test holds the two together).  What a
    caller that has only the counts reports as the kernels' rows."""
    tile = pick_tile(tokens * top_k, n_experts)
    return jnp.sum(-(-counts // tile) * tile)


def grouped_layout(top_idx, held: int, offset, tile: int):
    """Where each assignment goes.  ``top_idx``: [T, k] expert ids over ALL
    experts; this shard holds experts ``offset .. offset + held - 1``
    (``offset`` may be traced).  Returns a dict:

    ``dest`` [T * k]: the assignment's row in the sorted buffer (``rows`` for
    one that falls on an expert held elsewhere); ``valid`` [T * k];
    ``row_token`` [rows]: the token each sorted row carries (0 for padding);
    ``tile_expert`` [rows / tile]: the held expert of each tile (a dead
    tile repeats the last live one's); ``live_tiles`` [1]; ``counts``
    [held]: assignments per held expert."""
    t, k = top_idx.shape
    a = t * k
    rows = -(-a // tile) * tile + held * tile
    e = top_idx.reshape(a).astype(jnp.int32) - offset
    valid = (e >= 0) & (e < held)
    e = jnp.where(valid, e, held)
    onehot = (e[:, None] == jnp.arange(held, dtype=jnp.int32)[None]
              ).astype(jnp.int32)                       # [a, held]
    seen = jnp.cumsum(onehot, axis=0)
    counts = seen[-1]
    rank = jnp.sum(seen * onehot, axis=1) - 1           # within its expert
    padded = -(-counts // tile) * tile
    end = jnp.cumsum(padded)
    start = end - padded
    dest = jnp.where(valid, start[jnp.minimum(e, held - 1)] + rank, rows)
    token = jnp.arange(a, dtype=jnp.int32) // k
    row_token = jnp.zeros((rows,), jnp.int32).at[dest].set(token, mode="drop")
    live = end[-1] // tile
    first = jnp.arange(rows // tile, dtype=jnp.int32) * tile
    first = jnp.minimum(first, jnp.maximum(end[-1] - tile, 0))
    tile_expert = jnp.minimum(
        jnp.searchsorted(end, first, side="right"), held - 1
    ).astype(jnp.int32)
    return {"dest": dest, "valid": valid, "row_token": row_token,
            "tile_expert": tile_expert,
            "live_tiles": live.reshape(1).astype(jnp.int32),
            "counts": counts}


def _col_block(k: int, n: int, itemsize: int) -> int:
    """Columns of a weight block: the widest multiple of 128 that divides
    ``n`` and keeps the block under ``_W_BLOCK_BYTES`` (``n`` itself where
    no multiple of 128 divides it: small test shapes)."""
    best = None
    for tn in range(128, n + 1, 128):
        if n % tn == 0 and k * tn * itemsize <= _W_BLOCK_BYTES:
            best = tn
    return best or (128 if n % 128 == 0 else n)


def _matmul_kernel(te_ref, live_ref, layer_ref, x_ref, w_ref, o_ref):
    del te_ref, layer_ref

    @pl.when(pl.program_id(1) < live_ref[0])
    def _():
        o_ref[...] = jnp.dot(x_ref[...], w_ref[...],
                             preferred_element_type=jnp.float32
                             ).astype(o_ref.dtype)


def _swiglu_kernel(te_ref, live_ref, layer_ref, x_ref, wg_ref, wu_ref,
                   o_ref):
    del te_ref, layer_ref

    @pl.when(pl.program_id(1) < live_ref[0])
    def _():
        x = x_ref[...]
        g = jnp.dot(x, wg_ref[...], preferred_element_type=jnp.float32)
        u = jnp.dot(x, wu_ref[...], preferred_element_type=jnp.float32)
        o_ref[...] = (jax.nn.silu(g) * u).astype(o_ref.dtype)


def _stacked(weights, layer):
    """The kernels' view of the weights: ``[L, held, K, N]`` stacks with the
    layer to run (a plain ``[held, K, N]`` is a stack of one)."""
    if layer is None:
        return [w[None] for w in weights], jnp.zeros((1,), jnp.int32)
    return list(weights), jnp.asarray(layer, jnp.int32).reshape(1)


def _grouped_call(kernel, name: str, x, weights, tile_expert, live_tiles,
                  layer, tile: int, interpret: bool):
    rows, k = x.shape
    weights, layer = _stacked(weights, layer)
    n = weights[0].shape[3]
    tn = _col_block(k, n, weights[0].dtype.itemsize)

    def tile_of(i, live):           # a dead tile repeats the last live one
        return jnp.minimum(i, jnp.maximum(live[0] - 1, 0))

    x_spec = pl.BlockSpec((tile, k), lambda j, i, te, live, li:
                          (tile_of(i, live), 0))
    w_spec = pl.BlockSpec((None, None, k, tn), lambda j, i, te, live, li:
                          (li[0], te[i], 0, j))
    o_spec = pl.BlockSpec((tile, tn), lambda j, i, te, live, li:
                          (tile_of(i, live), j))
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((rows, n), x.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(n // tn, rows // tile),
            in_specs=[x_spec] + [w_spec] * len(weights), out_specs=o_spec),
        name=name, interpret=interpret,
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
    )(tile_expert, live_tiles, layer, x, *weights)


def _tile_weights(w, tile_expert, layer):
    """Off the TPU: each tile's expert's weights, [tiles, K, N]."""
    if layer is not None:
        w = jax.lax.dynamic_index_in_dim(w, layer, 0, keepdims=False)
    return w[tile_expert]


def _on_tpu(use_pallas: Optional[bool]) -> bool:
    return (jax.default_backend() == "tpu" if use_pallas is None
            else bool(use_pallas))


def _tiles(x, tile: int):
    return x.reshape(x.shape[0] // tile, tile, x.shape[1])


def grouped_matmul(x, w, tile_expert, live_tiles, tile: int, layer=None,
                   use_pallas: Optional[bool] = None,
                   interpret: bool = False):
    """``x`` [rows, K] sorted rows (``grouped_layout``), ``w`` [held, K, N]
    (or the whole ``[L, held, K, N]`` stack with ``layer``, traced OK, the
    layer to run: the index rides the scalar prefetch and no layer is
    copied out): row ``r`` times the weights of its tile's expert.  Rows of
    dead tiles come back undefined."""
    if _on_tpu(use_pallas) or interpret:
        return _grouped_call(_matmul_kernel, "moe_grouped_matmul", x, (w,),
                             tile_expert, live_tiles, layer, tile, interpret)
    y = jnp.einsum("tmk,tkn->tmn", _tiles(x, tile),
                   _tile_weights(w, tile_expert, layer),
                   preferred_element_type=jnp.float32)
    return y.reshape(x.shape[0], -1).astype(x.dtype)


def grouped_swiglu(x, w_gate, w_up, tile_expert, live_tiles, tile: int,
                   layer=None, use_pallas: Optional[bool] = None,
                   interpret: bool = False):
    """``silu(x Wg) * (x Wu)`` with each row's tile's expert's weights, in
    one pass over the rows (float32 inside, ``x``'s dtype out)."""
    if _on_tpu(use_pallas) or interpret:
        return _grouped_call(_swiglu_kernel, "moe_grouped_swiglu", x,
                             (w_gate, w_up), tile_expert, live_tiles, layer,
                             tile, interpret)
    xt = _tiles(x, tile)
    g = jnp.einsum("tmk,tkn->tmn", xt,
                   _tile_weights(w_gate, tile_expert, layer),
                   preferred_element_type=jnp.float32)
    u = jnp.einsum("tmk,tkn->tmn", xt,
                   _tile_weights(w_up, tile_expert, layer),
                   preferred_element_type=jnp.float32)
    return (jax.nn.silu(g) * u).reshape(x.shape[0], -1).astype(x.dtype)


def route(router_logits, top_k: int, score: str = "softmax", bias=None,
          scale: float = 1.0):
    """(gates [T, k] float32, expert ids [T, k]) from float32 router logits
    [T, E].  ``score="softmax"``: the ``top_k`` largest logits, softmax over
    those kept.  ``score="sigmoid"``: scores ``s = sigmoid(logits)``, the
    ``top_k`` largest ``s + bias`` (``bias`` [E]: a selection bias, no part
    of the gate), gates ``s_i / sum of the chosen s`` times ``scale``."""
    if score == "softmax":
        top_vals, top_idx = jax.lax.top_k(router_logits, top_k)
        return jax.nn.softmax(top_vals, axis=-1), top_idx
    s = jax.nn.sigmoid(router_logits)
    _, top_idx = jax.lax.top_k(s if bias is None else s + bias, top_k)
    kept = jnp.take_along_axis(s, top_idx, axis=-1)
    return kept / jnp.sum(kept, axis=-1, keepdims=True) * scale, top_idx


@functools.partial(jax.jit, static_argnames=("top_k", "held", "tile",
                                             "use_pallas", "interpret",
                                             "score", "scale"))
def grouped_experts(h, router_logits, w_gate, w_up, w_down, offset,
                    layer=None, *,
                    top_k: int, held: int, tile: Optional[int] = None,
                    use_pallas: Optional[bool] = None,
                    interpret: bool = False, score: str = "softmax",
                    bias=None, scale: float = 1.0):
    """The held experts' part of a top-k routed expert layer.

    ``h``: [T, d] tokens; ``router_logits``: [T, E] float32 over ALL experts;
    ``w_gate``/``w_up``: [held, d, f], ``w_down``: [held, f, d]: the experts
    ``offset .. offset + held - 1`` (or whole ``[L, held, ..]`` stacks and
    ``layer``).  The gates are the softmax over the
    ``top_k`` kept logits, or :func:`route`'s other form (``score``,
    ``bias``, ``scale``).  Returns (out [T, d] in ``h``'s dtype: the sum
    over a token's assignments that fall on held experts of gate * expert
    output, accumulated in float32; counts [held] int32)."""
    t, d = h.shape
    gates, top_idx = route(router_logits, top_k, score, bias, scale)
    tile = tile or pick_tile(t * top_k, router_logits.shape[-1])
    lay = grouped_layout(top_idx, held, offset, tile)
    kw = dict(tile=tile, layer=layer, use_pallas=use_pallas,
              interpret=interpret)
    xs = h[lay["row_token"]]                                # sorted rows
    mid = grouped_swiglu(xs, w_gate, w_up, lay["tile_expert"],
                         lay["live_tiles"], **kw)
    ys = grouped_matmul(mid, w_down, lay["tile_expert"], lay["live_tiles"],
                        **kw)
    rows = ys.shape[0]
    # an assignment's gate on its sorted row, then a token's rows summed in
    # float32: [T * k, d] is gathered once, in the compute dtype
    row_gate = jnp.zeros((rows,), jnp.float32).at[lay["dest"]].set(
        gates.reshape(-1), mode="drop")
    ys = (ys.astype(jnp.float32) * row_gate[:, None]).astype(h.dtype)
    back = ys[jnp.minimum(lay["dest"], rows - 1)]
    back = jnp.where(lay["valid"][:, None], back, 0).reshape(t, top_k, d)
    out = jnp.sum(back, axis=1, dtype=jnp.float32)
    return out.astype(h.dtype), lay["counts"]
