"""Attention ops: reference MHA and a Pallas TPU flash-attention kernel.

The reference framework has no kernels of its own (SURVEY §2.6) — its FLOPs
live in TF's compiled runtime.  Ours live here: a blocked, online-softmax
forward kernel and a two-kernel (dq / dk+dv) backward, both tiled for the
MXU (fp32 accumulation, causal blocks skipped entirely, the backward reusing
the forward's stored logsumexp), with a plain-XLA reference implementation
as ground truth and CPU fallback.

Layouts follow the JAX convention ``[batch, seq, heads, head_dim]``.
"""

from __future__ import annotations

import functools
import math
import operator
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = float("-inf")

#: Paged-decode launch accounting: Python-level call counts, bumped once
#: per ``flash_decode_paged`` invocation.  Under ``jit`` a call site
#: counts once per TRACE (a ``lax.scan`` body traces once however many
#: steps it runs), so read it around eager call sequences.
PAGED_CALL_STATS = {"calls": 0, "kernel_calls": 0}

#: Per-core VMEM bytes the paged kernel's K + V blocks may claim, both
#: double-buffered by the pipeline (headroom for q, the self operands,
#: the softmax scratch and the score tile in the ~16 MB scoped budget).
_PAGED_VMEM_BUDGET = 8 * 2 ** 20

#: Positions one K/V block of the paged kernel covers at most.  On the
#: v5e a block of 512 is where a step's copies take as long as its heads'
#: softmax chains (~0.3 us a head and step whatever the block, PR 31's
#: sweep: PERF.md section 6): 87-91% of the HBM roofline over live pages,
#: against 51-59% at 128-256 positions; 1024 reads 3% better again and
#: doubles the VMEM and the operands.
_PAGED_BLOCK_POSITIONS = 512


def _paged_block(kv: int, ps: int, d: int, itemsize: int, width: int,
                 quantized: bool = False, d_v: Optional[int] = None,
                 pack: int = 1) -> tuple[int, int]:
    """``(head_block, pages_per_block)`` of the paged decode kernel, from
    what a call sees and nothing else.  One grid step covers a K/V block
    of ``pages_per_block`` pool pages of ``head_block`` heads each.  The
    block wants :data:`_PAGED_BLOCK_POSITIONS` positions, never more
    pages than the table is wide; the head block is the largest divisor
    of ``kv`` whose K + V blocks of that many pages, double-buffered, fit
    :data:`_PAGED_VMEM_BUDGET` -- the head block shrinks before the block
    does (EvaByte's 32 heads: 16 x 8 pages ran 26% faster than 32 x 4).
    Where one head's block does not fit either, one head takes the pages
    that do: a page that fills the budget alone (page 1024) yields one
    page a step, a value of the rule and not a mode.  An int8 pool's
    page carries one (8, 128)-padded float32 scale tile per 128
    positions and head.  ``d_v``: the values' head size where it is not
    the keys' ``d`` (a head's page is then ``d + d_v`` channels a
    position, not ``2 d``); ``pack``: K heads a row of the K pool holds
    side by side (``pack_k``), which a head block takes whole."""
    page_bytes = ps * (d + (d if d_v is None else d_v)) * itemsize
    if quantized:
        page_bytes += 2 * 8 * 128 * 4 * -(-ps // 128)
    pages = max(1, min(_PAGED_BLOCK_POSITIONS // ps, width))
    fit = lambda hb: _PAGED_VMEM_BUDGET // (2 * hb * page_bytes)
    for hb in range(kv, 0, -1):
        if kv % hb == 0 and hb % pack == 0 and fit(hb) >= pages:
            return hb, pages
    return pack, max(1, fit(pack))


def _decode_block(b: int, kv: int, blocks: int, block_m: int, d: int,
                  d_v: int, itemsize: int, quantized: bool = False,
                  pack: int = 1) -> tuple[int, int]:
    """``(rows, head_block)`` of the un-paged decode kernel
    (``flash_decode``), from what a call sees and nothing else: one grid
    step covers ``block_m`` positions of ``head_block`` K/V heads of
    ``rows`` batch rows.  The head block is the largest divisor of ``kv``
    (whole K rows of ``pack`` heads, ``pack_k``) whose K + V blocks,
    double-buffered, fit :data:`_PAGED_VMEM_BUDGET`, as they stand in VMEM:
    channels in whole lane tiles, positions in whole sublane tiles, an int8
    cache's (8, 128)-padded float32 scale tile per 128 positions and head
    beside them (``_paged_block``'s count).  Where every head of a row fits
    and the cache is ONE block (``blocks`` == 1: a window layer's ring),
    the step takes the largest divisor of ``b`` rows that still fits;
    where it is several blocks rows end at different ones (each row's index
    map pins at its own last live block), so a step is one row."""
    sublanes = 32 // itemsize       # a tile's rows: 8 float32, 16 bf16, 32 int8
    lanes = lambda c: -(-c // LANES) * LANES
    head_bytes = (-(-block_m // sublanes) * sublanes * itemsize
                  * (lanes(pack * d) // pack + lanes(d_v)))
    if quantized:
        head_bytes += 2 * 8 * 128 * 4 * -(-block_m // 128)
    fits = lambda heads: 2 * heads * head_bytes <= _PAGED_VMEM_BUDGET
    head_block = next((hb for hb in range(kv, pack, -1)
                       if kv % hb == 0 and hb % pack == 0 and fits(hb)), pack)
    rows = 1
    if blocks == 1 and head_block == kv:
        rows = max(n for n in range(1, b + 1)
                   if b % n == 0 and (n == 1 or fits(n * kv)))
    return rows, head_block


def _paged_walk(page_table, live_pages, pages_per_block: int):
    """The paged kernel's grid, flattened over the steps that have work:
    ``(walk, fetch, total)``.  Row after row, a row takes one step per
    K/V block that holds a live page of it (``live_pages[row]`` of its
    table's entries are live) and one step when it has none (its self
    operand and its output still want one), so the grid is ``total``
    steps long and no step is a dead one: a table is a power of two
    wider than its widest row and idle rows ride in every batch, and a
    step costs the pipeline its bookkeeping per operand whether it
    computes or not (PERF.md section 6, PR 31).

    ``walk`` [3, rows * steps] int32: the row of step ``s``, the K/V
    block of the row it covers, and whether it is the row's last.
    ``fetch`` [rows * steps * pages_per_block] int32: the pool page
    that page SLOT ``i`` of the block holds at step ``s``, at
    ``s * pages_per_block + i``.  For a live entry that is the row's
    own page; for an entry at or past the row's live pages it is the
    page the slot fetched LAST on the walk.  The pipeline copies a slot
    only when its index changes, so a dead entry moves nothing: only
    live pages move, each once (head blocks aside, which take the walk
    again).  Entries past ``total`` are never read."""
    b, width = page_table.shape
    ppb = pages_per_block
    steps = -(-width // ppb)
    count = jnp.clip(-(-live_pages // ppb), 1, steps)
    ends = jnp.cumsum(count)
    at = jnp.arange(b * steps, dtype=jnp.int32)
    row = jnp.minimum(jnp.sum(at[:, None] >= ends[None, :], axis=1,
                              dtype=jnp.int32), b - 1)
    block = jnp.minimum(at - (ends - count)[row], steps - 1)
    entry = block[:, None] * ppb + jnp.arange(ppb, dtype=jnp.int32)
    live = entry < live_pages[row][:, None]
    pages = page_table[row[:, None], jnp.minimum(entry, width - 1)]
    last_live = jax.lax.cummax(jnp.where(live, at[:, None], 0), axis=0)
    fetch = jnp.take_along_axis(pages, last_live, axis=0)
    walk = jnp.stack([row, block, (block == count[row] - 1).astype(jnp.int32)])
    return walk, fetch.reshape(-1), ends[-1]


def _check_gqa_heads(q, k, v):
    """Every attention path shares one clear failure for bad GQA shapes
    (e.g. 4 q heads over 3 kv heads would otherwise floor to rep=1 and die
    later in an opaque einsum shape error).  Heads stand at axis 2; K and
    V agree in their heads and may differ in their head size (the
    queries' is the keys', the result's the values')."""
    if q.shape[2] % k.shape[2] or k.shape[2] != v.shape[2]:
        raise ValueError(
            f"q heads ({q.shape[2]}) must be a multiple of kv heads "
            f"({k.shape[2]}/{v.shape[2]}, which must agree)")
    if q.shape[-1] != k.shape[-1]:
        raise ValueError(
            f"q and k must share a head size, got {q.shape[-1]} and "
            f"{k.shape[-1]} (v's, {v.shape[-1]}, is the result's)")


#: lanes of a vector register row: a cache's trailing dim is laid out in
#: HBM in whole multiples of it
LANES = 128


def pack_k(head_dim: int, kv_heads: int) -> int:
    """K heads a row of a K CACHE (the paged pool, a window layer's ring)
    holds side by side: 1, or 2 where a key's channels are a lane tile and
    a half (192 = 128 + 64) and the K/V heads pair up.  In the caches'
    native layout (``[.., positions, D]``, D trailing) a bfloat16 K of 192
    channels is padded to 256 lanes in HBM: 4/3 of its bytes held and read.
    Two heads' keys of one position side by side are 384 channels, three
    whole tiles: ``[.., KV / 2, positions, 2 D]``, which is a plain reshape
    of a position's ``[KV, D]`` keys, so every write stays as it was.  The
    decode kernels read a pair's block once with the pair's queries laid
    block-diagonal over it (``_pack_queries``); V keeps its layout."""
    return 2 if (head_dim > LANES and head_dim % LANES == LANES // 2
                 and kv_heads % 2 == 0) else 1


def _cache_pack(q, kc, vc) -> int:
    """``pack_k``'s factor as a decode cache's shapes show it (K/V heads at
    axis 2 of the stacked leaves): V's heads over K's rows of heads."""
    kv = vc.shape[2]
    f = kv // max(1, kc.shape[2])
    if q.shape[2] % kv or f < 1 or kc.shape[2] * f != kv:
        raise ValueError(
            f"q heads ({q.shape[2]}) must be a multiple of kv heads ({kv}; "
            f"the K cache holds them in {kc.shape[2]} rows, which must "
            f"divide them)")
    if kc.shape[-1] != f * q.shape[-1]:
        raise ValueError(
            f"a K cache row of {kc.shape[-1]} channels is not {f} heads of "
            f"the queries' size ({q.shape[-1]})")
    return f


def _unpack_k(k, f: int):
    """[.., KV / f, M, f * D] -> [.., KV, M, D] (the reference paths)."""
    if f == 1:
        return k
    *lead, rows, m, fd = k.shape
    k = k.reshape(*lead, rows, m, f, fd // f)
    return jnp.moveaxis(k, -2, -3).reshape(*lead, rows * f, m, fd // f)


def _pack_queries(qt, f: int):
    """[B, KV, r, D] query rows a K/V head -> [B, KV / f, f * r, f * D]: the
    rows of the ``f`` heads that share a packed K row, head ``i``'s in rows
    ``i r .. (i + 1) r`` with its channels at ``i D .. (i + 1) D`` and
    zeros elsewhere, so that ONE product with the row's block gives every
    head its own scores."""
    if f == 1:
        return qt
    b, kv, r, d = qt.shape
    q6 = qt.reshape(b, kv // f, f, r, 1, d) * jnp.eye(
        f, dtype=qt.dtype)[None, None, :, None, :, None]
    return q6.reshape(b, kv // f, f * r, f * d)


def _sink_softmax(s, sink):
    """Softmax over the last axis of ``s`` with one more logit, ``sink``
    (broadcast against ``s`` with a trailing 1), in the denominator only."""
    m = jnp.maximum(jnp.max(s, axis=-1, keepdims=True), sink)
    p = jnp.exp(s - m)
    return p / (jnp.sum(p, axis=-1, keepdims=True) + jnp.exp(sink - m))



def mha_reference(q, k, v, causal: bool = False, scale: Optional[float] = None,
                  window: Optional[int] = None, sink=None):
    """Plain-XLA scaled-dot-product attention (ground truth / fallback).

    Grouped-query attention is accepted directly: when ``k``/``v`` carry
    fewer heads than ``q`` (q heads per kv head = H // KV), they are
    broadcast up here — the kernels do the same mapping without
    materializing the repeat.

    ``window`` (requires ``causal``): sliding-window attention — query i
    sees keys [i-window+1, i] only.  ``sink`` ([H] float32): one logit a
    query head that joins the softmax's denominator and carries no value.
    K and V may differ in their head size."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    if window is not None and not causal:
        raise ValueError("window requires causal=True")
    _check_gqa_heads(q, k, v)
    if k.shape[2] != q.shape[2]:
        rep = q.shape[2] // k.shape[2]
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    if causal:
        tq, tk = scores.shape[-2], scores.shape[-1]
        qpos = jax.lax.broadcasted_iota(jnp.int32, (tq, tk), 0)
        kpos = jax.lax.broadcasted_iota(jnp.int32, (tq, tk), 1)
        bad = kpos > qpos
        if window is not None:
            bad = bad | (kpos < qpos - (window - 1))
        scores = jnp.where(bad, NEG_INF, scores)
    if sink is not None:
        probs = _sink_softmax(scores, jnp.asarray(sink, jnp.float32)[
            None, :, None, None]).astype(v.dtype)
    else:
        probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def _pick_block(dim: int, target: int = 512) -> int:
    """Largest Mosaic-legal (8-aligned or full-dim) divisor of ``dim`` that
    is <= ``target``; falls back to the whole dim (always legal)."""
    for c in (1024, 512, 384, 256, 128, 64, 32, 16, 8):
        if c <= min(dim, target) and dim % c == 0:
            return c
    return dim


class _FlashCfg(NamedTuple):
    causal: bool
    scale: float
    block_q: int
    block_k: int
    interpret: bool
    q_per_kv: int = 1  # GQA group size (q heads per kv head); 1 = MHA
    window: Optional[int] = None  # sliding window (causal only); None = full
    # Static GLOBAL offset of the query block's positions relative to the
    # key block's (query i is global position i + q_offset; key j is j).
    # Ring attention sets it to step * shard_len so causal/window masks
    # and block bounds are exact across shards; 0 = the ordinary
    # same-origin call.
    q_offset: int = 0


#: VMEM one Pallas call may claim on a v5e without asking for more
#: (Mosaic's scoped default); the forward's tile rule stays under it.
_VMEM_SCOPED_LIMIT = 16 * 2 ** 20


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _flash_vmem_bytes(block_q: int, block_k: int, t_k: int, head_dim: int,
                      itemsize: int, v_dim: Optional[int] = None) -> int:
    """VMEM the forward kernel reserves at a tile: one KV head's K and V
    whole (padded to ``block_k``) and the q / o / lse blocks, each
    double-buffered by the pipeline, plus the float32 score block, its
    probabilities in the operand dtype, and the (o, m, l) accumulators
    ([rows, 1] columns occupy whole 128-lane rows).  ``v_dim``: the
    values' head size where it is not the keys'."""
    # channels past one lane tile occupy whole tiles (192 stands as 256)
    lanes = lambda x: x if x <= LANES else _round_up(x, LANES)
    v_dim = lanes(head_dim if v_dim is None else v_dim)
    head_dim = lanes(head_dim)
    kv = 2 * _round_up(t_k, block_k) * (head_dim + v_dim) * itemsize
    qo = 2 * block_q * (head_dim + v_dim) * itemsize + 2 * block_q * 128 * 4
    scores = block_q * block_k * (2 * 4 + itemsize)
    acc = block_q * (v_dim + 2 * 128) * 4
    return kv + qo + scores + acc


def _flash_tiles(t_q: int, t_k: int, head_dim: int, itemsize: int,
                 target_q: int = 512, target_k: int = 512,
                 v_dim: Optional[int] = None):
    """(block_q, block_k) of the forward kernel, from the chip and not from
    the divisors of the lengths: a length at or under its target is one
    block; a longer one is cut into ``ceil(t / target)`` equal blocks,
    rounded up to the dtype's sublane tile (q) or to 128 lanes (k), and the
    ragged tail is padded and masked by ``_flash_forward``.  The larger
    block halves, down to 256, while :func:`_flash_vmem_bytes` passes the
    scoped limit (past that a KV head's resident K/V is what does not fit)."""
    sub = 8 * max(1, 4 // itemsize)

    def cut(t, target, align):
        target = max(sub, target // sub * sub)
        if t <= target:
            return t
        return min(_round_up(-(-t // -(-t // target)), align), target)

    bq, bk = cut(t_q, target_q, sub), cut(t_k, target_k, 128)
    while (max(bq, bk) > 256 and _flash_vmem_bytes(
            bq, bk, t_k, head_dim, itemsize, v_dim) > _VMEM_SCOPED_LIMIT):
        if bk >= bq:
            bk = _round_up(bk // 2, 128)
        else:
            bq = _round_up(bq // 2, sub)
    return bq, bk


def _flash_kernel(q_ref, k_ref, v_ref, *rest, cfg: _FlashCfg, seq_len: int):
    """One (batch, head, q-block) grid cell: stream K/V blocks with online
    softmax.  Accumulation in fp32; output cast back at the end.

    Refs are laid out ``[1, 1, T, D]`` — (seq, head_dim) must be the trailing
    dims so blocks land on the TPU's (8, 128) tiling.  ``seq_len`` is the
    number of real keys: the K/V refs are padded with zeros to a whole
    number of ``block_k`` blocks, and the padding is masked by position.

    Operands stay in their input dtype (bf16 runs the MXU at full rate) with
    fp32 accumulation via ``preferred_element_type``; softmax statistics are
    fp32 throughout.

    With a sink (``rest`` then starts with the [H] float32 logits, in SMEM)
    the head's logit starts the recurrence in the place of an empty one:
    running maximum ``b_h``, denominator 1, no value.  The softmax is then
    exact whatever the order of the blocks, and no row is ever empty.
    """
    sink_ref, o_ref, lse_ref = rest if len(rest) == 3 else (None, *rest)
    q = q_ref[0, 0, :, :]  # [bq, d], input dtype
    bq, bk = cfg.block_q, cfg.block_k
    q_lo = pl.program_id(2) * bq + cfg.q_offset  # first row's position
    nk = -(-seq_len // bk)
    ragged = seq_len % bk != 0
    # Blocks [0, n_clear) need no mask; [n_clear, hi) cross the causal
    # diagonal, a window's edge or the end of the keys and are masked.
    n_clear, hi = seq_len // bk, nk
    if cfg.causal:
        # Blocks strictly above the diagonal contribute nothing: bound the
        # loop instead of masking them (halves the FLOPs on average).
        hi = jnp.minimum(nk, pl.cdiv(q_lo + bq, bk))
        n_clear = jnp.minimum(n_clear, (q_lo + 1) // bk)
        if cfg.window is not None:
            # Sliding window: blocks entirely below every query's window
            # start also contribute nothing — total work is O(T·W) — and
            # every block a window reaches is masked.
            n_clear = jnp.maximum(0, (q_lo - (cfg.window - 1)) // bk)

    def step(masked, j, carry):
        o, m, l = carry
        if nk == 1:
            # One K block (whatever its length): read it whole — Mosaic
            # cannot prove a dynamic start of j * bk tile-aligned when bk
            # is not a multiple of 8.
            k_blk, v_blk = k_ref[0, 0, :, :], v_ref[0, 0, :, :]
        else:
            at = pl.ds(pl.multiple_of(j * bk, bk), bk)
            k_blk, v_blk = k_ref[0, 0, at, :], v_ref[0, 0, at, :]  # [bk, d]
        s = jax.lax.dot_general(q, k_blk, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)  # [bq, bk]
        s = s * cfg.scale
        if masked:
            kpos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            # Keys past the array are zeros, not absent: mask them by
            # position, whatever the causal structure says.
            bad = kpos >= seq_len if ragged else None
            if cfg.causal:
                qpos = q_lo + jax.lax.broadcasted_iota(
                    jnp.int32, (bq, bk), 0)
                above = kpos > qpos
                if cfg.window is not None:
                    above = above | (kpos < qpos - (cfg.window - 1))
                bad = above if bad is None else bad | above
            s = jnp.where(bad, NEG_INF, s)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        if cfg.window is not None:
            # A q row can be ENTIRELY outside the window in this k block
            # (the loop's lower bound fits the block's lowest row, not all
            # of them): m_new stays -inf there and exp(-inf - -inf) is NaN.
            # Zero those entries explicitly — plain causal never hits this
            # (block 0 is valid for every row).
            p = jnp.where(s == NEG_INF, 0.0, jnp.exp(s - m_new))
            corr = jnp.where(m == NEG_INF, 0.0, jnp.exp(m - m_new))
        else:
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1, keepdims=True)
        o_new = o * corr + jax.lax.dot_general(
            p.astype(v_blk.dtype), v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return o_new, m_new, l_new

    d = v_ref.shape[-1]
    m0, l0 = ((NEG_INF, 0.0) if sink_ref is None
              else (sink_ref[pl.program_id(1)], 1.0))
    carry = (jnp.zeros((bq, d), jnp.float32),
             jnp.full((bq, 1), m0, jnp.float32),
             jnp.full((bq, 1), l0, jnp.float32))
    if cfg.window is None and (cfg.causal or n_clear):
        carry = jax.lax.fori_loop(0, n_clear, functools.partial(step, False),
                                  carry)
    if cfg.causal or ragged:
        carry = jax.lax.fori_loop(n_clear, hi, functools.partial(step, True),
                                  carry)
    o, m, l = carry
    if cfg.window is not None:
        # With an offset window a whole q row (or the whole block: an
        # empty loop) can see NO key in this shard: emit a clean zero/-inf
        # partial instead of 0/0 NaNs, so the ring's lse merge drops it.
        empty = l == 0.0
        o_ref[0, 0, :, :] = jnp.where(
            empty, 0.0, o / jnp.where(empty, 1.0, l)).astype(o_ref.dtype)
        lse_ref[0, 0, :, :] = jnp.where(
            empty, NEG_INF, m + jnp.log(jnp.where(empty, 1.0, l)))
    else:
        o_ref[0, 0, :, :] = (o / l).astype(o_ref.dtype)
        # Per-query logsumexp of the SCALED scores: the backward pass
        # reuses it instead of re-sweeping Q.K^T (causal rows always hit
        # the diagonal, so l > 0 here).
        lse_ref[0, 0, :, :] = m + jnp.log(l)


def _flash_forward(cfg: _FlashCfg, q, k, v, sink=None):
    """The forward kernel at ``cfg``'s tile, which need not divide either
    length: q is padded to whole ``block_q`` blocks and K/V to whole
    ``block_k`` blocks with zeros (so a masked score never meets garbage
    in ``p @ v``), inside the transposes this wrapper makes anyway; the
    kernel masks keys past the array by position and the rows past ``T``
    are cut off again.  Returns ``o`` [B, T, H, Dv] and ``lse`` [B, H, T, 1]
    (K and V may differ in their head size).  ``sink`` ([H] float32): a
    logit a head in the softmax's denominator (and in ``lse``); the call is
    then named ``flash_attention_fwd_sink``, so that a device trace tells
    it from a sinkless forward of the same shapes."""
    b, t, h, d = q.shape
    dv = v.shape[-1]
    tk = k.shape[1]
    g = h // k.shape[2]  # q heads per kv head (1 = plain MHA)
    bq, bk = cfg.block_q, cfg.block_k
    t_pad = _round_up(t, bq)

    def blocked(x, block):
        # [B, T, H, D] -> [B, H, T, D]: (seq, head_dim) trailing for TPU
        # tiling; zeros up to a whole number of blocks.
        x = x.transpose(0, 2, 1, 3)
        pad = _round_up(x.shape[2], block) - x.shape[2]
        return jnp.pad(x, ((0, 0), (0, 0), (0, pad), (0, 0))) if pad else x

    qt, kt, vt = blocked(q, bq), blocked(k, bk), blocked(v, bk)
    # The q block innermost: a KV head's K and V stay resident across its
    # q blocks and its group's q heads (an unchanged block is not fetched
    # again), one fetch per KV head instead of one per q block per group.
    grid = (b, h, t_pad // bq)
    q_spec = pl.BlockSpec((1, 1, bq, d),
                          lambda bi, hi, qi: (bi, hi, qi, 0),
                          memory_space=pltpu.VMEM)
    # GQA without materializing the repeat: q head hi reads kv head hi//g
    # straight from the narrow K/V arrays via the index map.
    kv_spec = pl.BlockSpec((1, 1, kt.shape[2], d),
                           lambda bi, hi, qi: (bi, hi // g, 0, 0),
                           memory_space=pltpu.VMEM)
    lse_spec = pl.BlockSpec((1, 1, bq, 1),
                            lambda bi, hi, qi: (bi, hi, qi, 0),
                            memory_space=pltpu.VMEM)
    # V and the result at the values' head size (the keys' unless stated)
    v_spec = pl.BlockSpec((1, 1, kt.shape[2], dv),
                          lambda bi, hi, qi: (bi, hi // g, 0, 0),
                          memory_space=pltpu.VMEM)
    o_spec = pl.BlockSpec((1, 1, bq, dv),
                          lambda bi, hi, qi: (bi, hi, qi, 0),
                          memory_space=pltpu.VMEM)
    in_specs, operands = [q_spec, kv_spec, v_spec], [qt, kt, vt]
    if sink is not None:
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        operands.append(jnp.asarray(sink, jnp.float32).reshape(h))
    kernel = functools.partial(_flash_kernel, cfg=cfg, seq_len=tk)
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[o_spec, lse_spec],
        out_shape=[jax.ShapeDtypeStruct(qt.shape[:3] + (dv,), q.dtype),
                   jax.ShapeDtypeStruct((b, h, t_pad, 1), jnp.float32)],
        interpret=cfg.interpret,
        name=("flash_attention_fwd" if sink is None
              else "flash_attention_fwd_sink"),
        compiler_params=None if cfg.interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")),
        cost_estimate=pl.CostEstimate(
            flops=2 * b * h * t * tk * (d + dv),
            bytes_accessed=(q.size + k.size + v.size + b * t * h * dv)
            * q.dtype.itemsize,
            transcendentals=b * h * t * tk,
        ),
    )(*operands)
    return out[:, :, :t].transpose(0, 2, 1, 3), lse[:, :, :t]


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                         dq_ref, *, cfg: _FlashCfg):
    """dq, one (batch, head, q-block, k-block) grid step; k innermost.

    K/V blocks stream through VMEM double-buffered while the dq output block
    (index map constant along k) stays resident as the accumulator — the
    canonical Mosaic reduction pattern.  p = exp(s·scale − lse) is recomputed
    from the stored per-query logsumexp (no second online softmax), then
    ds = p ⊙ (do·vᵀ − Δ), dq += ds·k·scale  (Δ = rowsum(do ⊙ o),
    precomputed outside — one fused elementwise pass in XLA).
    """
    bq, bk = cfg.block_q, cfg.block_k
    qi, j = pl.program_id(2), pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        dq_ref[0, 0, :, :] = jnp.zeros_like(dq_ref[0, 0, :, :])

    def _step():
        q = q_ref[0, 0, :, :]
        do = do_ref[0, 0, :, :]
        lse = lse_ref[0, 0, :, :]       # [bq, 1] fp32
        delta = delta_ref[0, 0, :, :]   # [bq, 1] fp32
        k_blk = k_ref[0, 0, :, :]       # [bk, d]
        v_blk = v_ref[0, 0, :, :]
        s = jax.lax.dot_general(q, k_blk, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * cfg.scale
        p = jnp.exp(s - lse)            # [bq, bk] fp32
        if cfg.causal:
            qpos = (qi * bq + cfg.q_offset
                    + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0))
            kpos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            bad = kpos > qpos
            if cfg.window is not None:
                bad = bad | (kpos < qpos - (cfg.window - 1))
            p = jnp.where(bad, 0.0, p)
        dp = jax.lax.dot_general(do, v_blk, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta)).astype(k_blk.dtype)
        dq_ref[0, 0, :, :] += cfg.scale * jax.lax.dot_general(
            ds, k_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if cfg.causal:
        # Blocks strictly above the causal diagonal (or entirely below the
        # sliding window) contribute nothing.
        live = j * bk <= (qi + 1) * bq - 1 + cfg.q_offset
        if cfg.window is not None:
            live = live & ((j + 1) * bk - 1
                           >= qi * bq + cfg.q_offset - (cfg.window - 1))
        pl.when(live)(_step)
    else:
        _step()


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                          dk_ref, dv_ref, *, cfg: _FlashCfg):
    """dk and dv, one (batch, KV head, k-block, q-block x group) grid step;
    the innermost dim runs the group's q heads for each q-block.

    Q/do/lse/Δ blocks stream while the dk/dv output blocks accumulate in
    VMEM:  dv += pᵀ·do,  dk += dsᵀ·q·scale.  With grouped-query attention
    (``cfg.q_per_kv > 1``) this k-block's gradient sums over every query
    head sharing the kv head — the group ride-along on the streamed dim
    does that without a second reduction pass.  Under causality, q-blocks
    strictly before the diagonal see none of this k-block and are skipped.
    """
    bq, bk = cfg.block_q, cfg.block_k
    ki, e = pl.program_id(2), pl.program_id(3)
    i = e // cfg.q_per_kv  # q-block index (e also enumerates the group)

    @pl.when(e == 0)
    def _init():
        dk_ref[0, 0, :, :] = jnp.zeros_like(dk_ref[0, 0, :, :])
        dv_ref[0, 0, :, :] = jnp.zeros_like(dv_ref[0, 0, :, :])

    def _step():
        k_blk = k_ref[0, 0, :, :]  # [bk, d]
        v_blk = v_ref[0, 0, :, :]
        q = q_ref[0, 0, :, :]      # [bq, d]
        do = do_ref[0, 0, :, :]
        lse = lse_ref[0, 0, :, :]
        delta = delta_ref[0, 0, :, :]
        s = jax.lax.dot_general(q, k_blk, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * cfg.scale
        p = jnp.exp(s - lse)       # [bq, bk] fp32
        if cfg.causal:
            qpos = (i * bq + cfg.q_offset
                    + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0))
            kpos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            bad = kpos > qpos
            if cfg.window is not None:
                bad = bad | (kpos < qpos - (cfg.window - 1))
            p = jnp.where(bad, 0.0, p)
        dv_ref[0, 0, :, :] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v_blk, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta)).astype(q.dtype)
        dk_ref[0, 0, :, :] += cfg.scale * jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if cfg.causal:
        # q-blocks strictly before the diagonal (or beyond the window's
        # reach of this k-block) see none of it.
        live = (i + 1) * bq - 1 + cfg.q_offset >= ki * bk
        if cfg.window is not None:
            live = live & (i * bq + cfg.q_offset
                           <= (ki + 1) * bk - 1 + (cfg.window - 1))
        pl.when(live)(_step)
    else:
        _step()


def _mha_bwd_pallas(cfg: _FlashCfg, q, k, v, o, lse, do, out_dtype=None):
    """Mosaic backward: the standard two-kernel dq / dk+dv split, both
    reusing the forward's stored logsumexp.  ``out_dtype`` overrides the
    gradient dtype (callers that go on accumulating — ring attention —
    take fp32 to avoid a round-trip through bf16 per partial).

    Grids put the reduction dimension innermost with ``arbitrary`` semantics
    so operand blocks pipeline (HBM→VMEM double-buffering) while the output
    block is revisited in place; accumulation is fp32 (outputs cast back to
    the input dtype outside, one fused elementwise pass).
    """
    b, t, h, d = q.shape
    tk = k.shape[1]
    g = h // k.shape[2]  # q heads per kv head (1 = plain MHA)
    # The backward picks its own blocks: grid-step overhead dominates at the
    # forward's numbers (measured on v5e at B4/T2048/H8/D128 bf16: 128-blocks
    # run 1.8x slower than 512), and unlike the forward there is no online-
    # softmax state growing with block_q.
    bq, bk = _pick_block(t), _pick_block(tk)
    cfg = cfg._replace(block_q=bq, block_k=bk, q_per_kv=g)
    # [B, T, H, D] -> [B, H, T, D]: (seq, head_dim) trailing for TPU tiling.
    qt, kt, vt, dot_ = (x.transpose(0, 2, 1, 3) for x in (q, k, v, do))
    # Δ = rowsum(do ⊙ o): one fused elementwise+reduce pass, cheaper as XLA
    # than as a third kernel.
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1).transpose(0, 2, 1)[..., None]     # [B,H,T,1]

    params = None if cfg.interpret else pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"))
    flops_half = 4 * b * h * t * tk * d  # each kernel ~= forward FLOPs

    def outer_spec(block, width):  # indexed by grid dim 2 (output axis)
        return pl.BlockSpec((1, 1, block, width),
                            lambda bi, hi, i, j: (bi, hi, i, 0),
                            memory_space=pltpu.VMEM)

    def inner_spec(block, width):  # indexed by grid dim 3 (streamed axis)
        return pl.BlockSpec((1, 1, block, width),
                            lambda bi, hi, i, j: (bi, hi, j, 0),
                            memory_space=pltpu.VMEM)

    def kv_dq_spec(block, width):  # kv operand in the dq grid (GQA map)
        return pl.BlockSpec((1, 1, block, width),
                            lambda bi, hi, i, j: (bi, hi // g, j, 0),
                            memory_space=pltpu.VMEM)

    # dq grid: q-blocks outer (accumulator), k-blocks streamed; q head hi
    # reads kv head hi // g.
    dq = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, cfg=cfg),
        grid=(b, h, t // bq, tk // bk),
        in_specs=[outer_spec(bq, d), kv_dq_spec(bk, d), kv_dq_spec(bk, d),
                  outer_spec(bq, d), outer_spec(bq, 1), outer_spec(bq, 1)],
        out_specs=outer_spec(bq, d),
        out_shape=jax.ShapeDtypeStruct(qt.shape, jnp.float32),
        interpret=cfg.interpret,
        name="flash_attention_dq",
        compiler_params=params,
        cost_estimate=pl.CostEstimate(
            flops=flops_half,
            bytes_accessed=(2 * q.size + 2 * k.size) * q.dtype.itemsize,
            transcendentals=b * h * t * tk),
    )(qt, kt, vt, dot_, lse, delta)

    # dk/dv grid: one cell per KV head and k-block (accumulators); the
    # streamed dim enumerates (q-block x group) pairs so a kv head's
    # gradient sums over every q head sharing it.
    def q_dkv_spec(block, width):  # q-side operands in the dkv grid
        return pl.BlockSpec(
            (1, 1, block, width),
            lambda bi, hi, i, e: (bi, hi * g + e % g, e // g, 0),
            memory_space=pltpu.VMEM)

    def kv_dkv_spec(block, width):
        return pl.BlockSpec((1, 1, block, width),
                            lambda bi, hi, i, e: (bi, hi, i, 0),
                            memory_space=pltpu.VMEM)

    dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_dkv_kernel, cfg=cfg),
        grid=(b, h // g, tk // bk, (t // bq) * g),
        in_specs=[q_dkv_spec(bq, d), kv_dkv_spec(bk, d), kv_dkv_spec(bk, d),
                  q_dkv_spec(bq, d), q_dkv_spec(bq, 1), q_dkv_spec(bq, 1)],
        out_specs=[kv_dkv_spec(bk, d), kv_dkv_spec(bk, d)],
        out_shape=[jax.ShapeDtypeStruct(kt.shape, jnp.float32),
                   jax.ShapeDtypeStruct(vt.shape, jnp.float32)],
        interpret=cfg.interpret,
        name="flash_attention_dkv",
        compiler_params=params,
        cost_estimate=pl.CostEstimate(
            flops=flops_half,
            bytes_accessed=(2 * q.size + 2 * k.size) * q.dtype.itemsize,
            transcendentals=b * h * t * tk),
    )(qt, kt, vt, dot_, lse, delta)

    back = lambda x, ref: x.transpose(0, 2, 1, 3).astype(
        out_dtype or ref.dtype)
    return back(dq, q), back(dk, k), back(dv, v)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _flash(cfg: _FlashCfg, q, k, v):
    return _flash_forward(cfg, q, k, v)[0]


def _flash_fwd(cfg, q, k, v):
    o, lse = _flash_forward(cfg, q, k, v)
    return o, (q, k, v, o, lse)


def _flash_bwd(cfg, res, g):
    q, k, v, o, lse = res
    return _mha_bwd_pallas(cfg, q, k, v, o, lse, g)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q, k, v, causal: bool = False, scale: Optional[float] = None,
                    block_q: int = 512, block_k: int = 512,
                    use_pallas: Optional[bool] = None,
                    interpret: bool = False,
                    window: Optional[int] = None,
                    forward_only: bool = False, sink=None):
    """Blocked attention; Pallas kernel on TPU, reference math elsewhere.

    ``use_pallas=None`` auto-selects: the kernel runs when the default
    backend is TPU (or when ``interpret=True`` for tests) and shapes are
    block-aligned; otherwise the XLA reference path runs — same numerics,
    same signature, so model code never branches.

    Grouped-query attention: ``k``/``v`` may carry ``H // g`` heads for any
    integer ``g``; the kernels map q head ``h`` to kv head ``h // g`` via
    their index maps, so the repeat is never materialized.

    The forward's tile (``_flash_tiles``): a length at or under its block
    target is one block; a longer one is cut into ``ceil(t / target)`` equal
    blocks (q rounded up to the dtype's sublanes, k to 128 lanes) and the
    tail is zero-padded and masked by position, so no length degenerates to
    the small blocks its divisors would allow (704 = 64 x 11 runs 352 x 384).

    ``forward_only``: the caller takes no gradient of the result (the
    serving path's prefill states it).  Only then does a causal
    self-attention past ``flash_max_keys`` keys run segment by segment
    (``_flash_segmented``, which has no VJP); every other call goes through
    the differentiable kernel pair at any length, as it always has.

    K and V of unequal head size (the result has V's) and ``sink`` ([H]
    float32: a logit a query head that joins the softmax's denominator and
    carries no value) are the forward's: the backward kernels keep one head
    size and no sink, and a call that does not state ``forward_only``
    is refused with either.
    """
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    _check_gqa_heads(q, k, v)
    if window is not None:
        if not causal:
            raise ValueError("window requires causal=True")
        if window < 1:
            raise ValueError(f"window must be >= 1, got {window}")
    t, tk = q.shape[1], k.shape[1]
    # The block arguments are targets.  The forward takes its tile from
    # ``_flash_tiles`` (any length, the ragged tail padded and masked); the
    # backward takes the largest Mosaic-legal (8-aligned or full-dim)
    # divisor of each length itself.  A dim with no 8-aligned divisor comes
    # back as the full dim (legal, single block): past 1024 such a seq falls
    # back to XLA instead of dragging a whole [t, t] score block through
    # VMEM in the backward.
    aligned = _pick_block(t) <= 1024 and _pick_block(tk) <= 1024
    dv = v.shape[-1]
    block_q, block_k = _flash_tiles(t, tk, q.shape[-1], q.dtype.itemsize,
                                    block_q, block_k, dv)
    if use_pallas is None:
        on_tpu = jax.default_backend() == "tpu"
        use_pallas = aligned and (on_tpu or interpret)
    elif use_pallas and not aligned:
        # Fail fast on a forced-pallas misuse rather than dragging an
        # unaligned [t, t] score block through VMEM.
        raise ValueError(
            f"flash_attention(use_pallas=True): seq lens {t}/{tk} have no "
            f"Mosaic-legal block tiling for the backward kernels")
    if not use_pallas:
        return mha_reference(q, k, v, causal=causal, scale=scale,
                             window=window, sink=sink)
    forward_form = sink is not None or dv != q.shape[-1]
    if forward_form and not forward_only:
        raise ValueError(
            "flash_attention: K and V of unequal head size and a sink are "
            "the forward kernel's (the backward kernels keep Dk == Dv and "
            "no sink): state forward_only=True")
    max_keys = flash_max_keys(q.shape[-1], dv, q.dtype.itemsize)
    if forward_only and causal and t == tk and tk > max_keys:
        return _flash_segmented(q, k, v, float(scale), window,
                                bool(interpret), sink)
    cfg = _FlashCfg(causal=bool(causal), scale=float(scale),
                    block_q=block_q, block_k=block_k,
                    interpret=bool(interpret),
                    window=None if window is None else int(window))
    if forward_form:
        return _flash_forward(cfg, q, k, v, sink)[0]
    return _flash(cfg, q, k, v)


#: bytes of ONE K/V head's K and V (channels in whole lane tiles) one call
#: of the forward kernel may hold where the caller states ``forward_only``:
#: they are whole in VMEM, double-buffered, and at 16,384 keys of 128 + 128
#: bfloat16 channels (8 MiB) Mosaic asked 48.5 MB of a v5e's 48.  This is
#: 8,192 such keys: the count PR 42 set, as the bytes it stood for.
FLASH_MAX_KV_BYTES = 8192 * (128 + 128) * 2


def flash_max_keys(d_k: int, d_v: int, itemsize: int) -> int:
    """Keys one forward call may hold under ``FLASH_MAX_KV_BYTES``: 8,192
    of 128 + 128 bfloat16 channels, 5,461 of 192 (256 lanes) + 128."""
    return max(1, FLASH_MAX_KV_BYTES // (
        (_round_up(d_k, LANES) + _round_up(d_v, LANES)) * itemsize))


def _flash_segmented(q, k, v, scale: float, window: Optional[int],
                     interpret: bool, sink=None):
    """Causal self-attention of a sequence longer than ``flash_max_keys``
    (forward only, no VJP: the serving path's long prompts, which ask for it
    with ``flash_attention(forward_only=True)``): the sequence is cut
    into equal segments of at most that many positions, segment ``i``'s
    queries attend each segment ``j <= i`` of the keys their window reaches
    through the forward kernel at the static offset ``(i - j) * segment``,
    and the normalized partials are merged by their log-sum-exps, in
    float32.  A ``sink`` joins the FIRST partial of every query segment and
    no other: the merge then counts it once."""
    t = q.shape[1]
    max_keys = flash_max_keys(q.shape[-1], v.shape[-1], q.dtype.itemsize)
    n = -(-t // max_keys)
    seg = min(_round_up(-(-t // n), 512), max_keys)
    outs = []
    for i in range(n):
        cut = lambda x, a: x[:, a * seg:(a + 1) * seg]
        qi = cut(q, i)
        first = 0 if window is None else max(
            0, (i * seg - (window - 1)) // seg)
        o = lse = None
        for j in range(first, i + 1):
            kj, vj = cut(k, j), cut(v, j)
            bq, bk = _flash_tiles(qi.shape[1], kj.shape[1], q.shape[-1],
                                  q.dtype.itemsize, v_dim=v.shape[-1])
            oj, lj = _flash_forward(
                _FlashCfg(causal=True, scale=scale, block_q=bq, block_k=bk,
                          interpret=interpret, window=window,
                          q_offset=(i - j) * seg), qi, kj, vj,
                sink if j == first else None)
            oj = oj.astype(jnp.float32)
            if o is None:
                o, lse = oj, lj
            else:       # weights [B, T, H, 1] from lse [B, H, T, 1]
                new = jnp.logaddexp(lse, lj)
                o = (o * jnp.exp(lse - new).transpose(0, 2, 1, 3)
                     + oj * jnp.exp(lj - new).transpose(0, 2, 1, 3))
                lse = new
        outs.append(o.astype(q.dtype))
    return jnp.concatenate(outs, axis=1)


def _causal_with_lse(q, k, v, scale: float, interpret: bool = False):
    """Causal attention of a chunk over itself, with every query's
    log-sum-exp of the scaled scores ([B, H, t, 1], float32): the flash
    kernel's forward on TPU, the dense form elsewhere."""
    t = q.shape[1]
    if interpret or jax.default_backend() == "tpu":
        bq, bk = _flash_tiles(t, t, q.shape[-1], q.dtype.itemsize)
        return _flash_forward(
            _FlashCfg(causal=True, scale=float(scale), block_q=bq,
                      block_k=bk, interpret=bool(interpret),
                      q_per_kv=q.shape[2] // k.shape[2]), q, k, v)
    b, _, h, d = q.shape
    kv = k.shape[2]
    q5 = q.reshape(b, t, kv, h // kv, d)
    s = jnp.einsum("bqkgd,bmkd->bkgqm", q5, k).astype(jnp.float32) * scale
    qpos = jax.lax.broadcasted_iota(jnp.int32, (t, t), 0)
    kpos = jax.lax.broadcasted_iota(jnp.int32, (t, t), 1)
    s = jnp.where(kpos > qpos, NEG_INF, s)
    lse = jax.nn.logsumexp(s, axis=-1, keepdims=True)
    o = jnp.einsum("bkgqm,bmkd->bqkgd", jnp.exp(s - lse).astype(v.dtype), v)
    return o.reshape(b, t, h, d), lse.reshape(b, h, t, 1)


#: pages of cached summaries one XLA block of ``eva_prefill_attention`` reads
EVA_BLOCK_PAGES = 8


def eva_prefill_attention(q, k, v, k_pool, v_pool, layer, page_table,
                          n_cached, scale: Optional[float] = None,
                          interpret: bool = False):
    """EVA attention of a chunk that starts a window: query ``i`` attends
    the chunk's own tokens ``<= i`` exactly, together with the first
    ``n_cached`` ([B] int32) entries of its row's paged cache (the
    summaries of every earlier window), under ONE softmax normaliser.

    The chunk's own part is the flash kernel (``_causal_with_lse``); the
    cached part continues its online softmax in plain XLA over blocks of
    ``EVA_BLOCK_PAGES`` pages, as many as the longest row needs: the running
    maximum starts at the chunk's log-sum-exp with a weight of one.
    ``q``/``k``/``v``: [B, t, H|KV, D]; pools: the stacked
    [L, P, KV, page, D] with ``layer``; ``page_table``: [B, NP]."""
    b, t, h, d = q.shape
    kv, ps = k_pool.shape[2], k_pool.shape[3]
    g = h // kv
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    o, lse = _causal_with_lse(q, k, v, scale, interpret)
    np_ = page_table.shape[1]
    bp = min(EVA_BLOCK_PAGES, np_)
    q5 = q.reshape(b, t, kv, g, d)
    n_cached = jnp.broadcast_to(jnp.asarray(n_cached, jnp.int32), (b,))

    def body(i, carry):
        o, m, l = carry
        col = i * bp + jnp.arange(bp, dtype=jnp.int32)
        pg = page_table[:, jnp.minimum(col, np_ - 1)]               # [B, bp]
        # [B, bp, KV, page, D] -> [B, KV, bp * page, D]
        blk = lambda pool: pool[layer, pg].transpose(0, 2, 1, 3, 4).reshape(
            b, kv, bp * ps, d)
        s = jnp.einsum("btkgd,bkmd->btkgm", q5, blk(k_pool).astype(q.dtype),
                       preferred_element_type=jnp.float32) * scale
        # entries by their UNclamped column: a clamped one lies past
        # every row's n_cached
        ent = (col[:, None] * ps
               + jnp.arange(ps, dtype=jnp.int32)[None]).reshape(-1)
        live = ent[None] < n_cached[:, None]                        # [B, m]
        s = jnp.where(live[:, None, None, None], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p, axis=-1, keepdims=True)
        o = o * corr + jnp.einsum(
            "btkgm,bkmd->btkgd", p.astype(q.dtype),
            blk(v_pool).astype(q.dtype), preferred_element_type=jnp.float32)
        return o, m_new, l

    m0 = lse.reshape(b, kv, g, t, 1).transpose(0, 3, 1, 2, 4)
    carry = (o.reshape(b, t, kv, g, d).astype(jnp.float32), m0,
             jnp.ones_like(m0))
    n_blk = -(-jnp.max(n_cached) // (bp * ps))
    o, _, l = jax.lax.fori_loop(0, n_blk, body, carry)
    return (o / l).reshape(b, t, h, d).astype(q.dtype)


def _decode_reference(q, k_cache, v_cache, pos, scale, sink=None):
    """Dense masked attention of a query chunk over a KV cache (ground
    truth / non-TPU path for ``flash_decode``).  Grouped einsum: the cache
    streams at kv width, q heads grouped kv-major as [kv, g].  ``q`` is
    [B, H, D] (single token) or [B, t, H, D] (chunk; token tt sees
    positions <= pos + tt); the cache is the kernel-native
    [B, KV, M, D] (seq and head_dim trailing; V's head size may be another
    than K's, and is the result's).  ``sink``: [H] float32, a logit a head
    in the denominator only."""
    squeeze = q.ndim == 3
    if squeeze:
        q = q[:, None]
    b, t, h, d = q.shape
    kv = k_cache.shape[1]
    g = h // kv
    m = k_cache.shape[2]
    q5 = q.reshape(b, t, kv, g, d)
    s = jnp.einsum("btkgd,bkmd->bkgtm", q5, k_cache).astype(jnp.float32)
    s = s * scale
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
    kpos = jnp.arange(m, dtype=jnp.int32)
    bad = (kpos[None, None] >
           pos[:, None, None] + jnp.arange(t, dtype=jnp.int32)[None, :,
                                                               None])
    s = jnp.where(bad[:, None, None], NEG_INF, s)       # [b,kv,g,t,m]
    if sink is not None:
        p = _sink_softmax(s, jnp.asarray(sink, jnp.float32).reshape(
            1, kv, g, 1, 1)).astype(v_cache.dtype)
    else:
        p = jax.nn.softmax(s, axis=-1).astype(v_cache.dtype)
    o = jnp.einsum("bkgtm,bkmd->btkgd", p, v_cache)
    o = o.reshape(b, t, h, v_cache.shape[-1])
    return o[:, 0] if squeeze else o


def _decode_block_scores(q, k_blk, scale, ks_row=None):
    """[rows, block] score tile of one K block (int8 blocks convert in
    VMEM; per-position k scales fold post-dot) — shared by the linear
    and paged (kv-folded) decode kernels so their math cannot diverge."""
    s = jax.lax.dot_general(q, k_blk.astype(q.dtype),
                            (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    s = s * scale
    if ks_row is not None:
        s = s * ks_row.reshape(1, -1)
    return s


def _decode_accumulate(s, v_blk, acc, vs_row=None):
    """One online-softmax accumulation of a score tile against its V
    block: returns the updated (m, l, o) triple.  Handles all-masked
    tiles (exp(-inf - -inf) guarded) and the int8 per-position v-scale
    fold — the single definition both decode kernels run."""
    m_prev, l_prev, o_prev = acc
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.where(s == NEG_INF, 0.0, jnp.exp(s - m_new))
    corr = jnp.where(m_prev == NEG_INF, 0.0, jnp.exp(m_prev - m_new))
    l_new = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
    if vs_row is not None:
        p = p * vs_row.reshape(1, -1)
    if v_blk.dtype == jnp.int8:
        v_blk = v_blk.astype(jnp.float32)
    o_new = o_prev * corr + jax.lax.dot_general(
        p.astype(v_blk.dtype), v_blk, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    return m_new, l_new, o_new


def _flash_decode_kernel(s_ref, q_ref, k_ref, v_ref, *rest, block_m: int,
                         scale: float, quantized: bool, q_per_kv: int,
                         rows: int, head_block: int, blocks: int,
                         pack: int = 1, sink: bool = False):
    """One (row-block, head-block, m-block) grid step of cache-bounded
    decode: ``rows`` batch rows x ``head_block`` K/V heads of one block of
    ``block_m`` positions, the block ``_decode_block``'s.  A step costs the
    pipeline ~0.3 us that no copy hides (v5e, PERF.md section 6, PR 46), so
    a row's heads ride in one step where they fit, and several rows where
    the cache is one block long (a window layer's ring).

    The q block carries each kv head's rows for the WHOLE chunk, t-major:
    row r = chunk token (r // g), group member (r % g) — t = 1 in
    steady-state decode, t > 1 for speculative verify / chunked prefill.
    Chunk token tt sees cache positions <= pos_first + tt.

    ``s_ref`` holds the scalar-prefetched per-row triples (n_live_blocks,
    first chunk position, layer index); each row of the step reads its
    own.  Blocks past a row's bound are skipped AND their index map pins
    to the last live block, so Mosaic's unchanged-index elision never DMAs
    them — HBM traffic is O(pos), not O(max_len); ``rows`` > 1 only where
    the cache is ONE block, which every row has.  Online softmax
    accumulates across the m grid dim in VMEM scratch ([rows, head_block /
    pack, pack * t * g, ..]: a unit of the step is one row's one K row of
    heads); the normalized output writes once on the final step.  The
    units run one after another in a loop Mosaic unrolls, each unit's
    chain of product, softmax, product and scratch update free to overlap
    the next's (``_flash_decode_paged_kernel``'s ``heads``).

    K/V refs are blocks of the STACKED cache ([L, ..., block_m, d] — the
    layer index rides row 2 of the scalar prefetch into the index maps),
    so decoding never materializes a per-layer slice: the scan over
    layers reads O(pos) from the full buffer directly.

    ``quantized``: K/V refs are int8 with per-position fp32 scale refs
    following them.  The scales fold into the score/probability rows
    (k: s·kscale after the dot; v: (p·vscale)·v_int8), so the cache
    streams from HBM at int8 width — the dequantize never touches HBM.

    Deferred-write decode (an uncommitted current token riding in as a
    self operand) is a PAGED-path feature: only ``decode_step``'s paged
    single-host steps defer their pool commit, so only
    ``_flash_decode_paged_kernel`` carries the self block — the linear
    cache commits before attending and this kernel reads it directly.

    ``pack`` > 1 (``pack_k``; not with ``quantized``): a K row is ``pack``
    heads' keys side by side, [block_m, pack * d], and its q block the
    heads' rows block-diagonal over it (``_pack_queries``): one product
    gives head ``i`` its scores in rows ``i r .. (i + 1) r`` (r = t * g),
    which then meet head ``i``'s own V block and its rows of the scratch.
    ``sink``: a [head_block / pack, pack * r, 1] float32 operand, each
    row's head's logit, starts the recurrence in the place of an empty one
    (maximum the logit, denominator 1, no value).
    """
    it = list(rest)
    ks_ref = vs_ref = sink_ref = None
    if quantized:
        ks_ref, vs_ref = it[0], it[1]
        it = it[2:]
    if sink:
        sink_ref, it = it[0], it[1:]
    o_ref, o_acc, m_acc, l_acc = it
    bi = pl.program_id(0)
    j = pl.program_id(2)
    r = o_acc.shape[2] // pack      # rows of one K/V head: t * g
    # A cache of one block has no phase to tell apart: every step starts,
    # attends and finishes, in one basic block the scheduler may reorder.
    when = pl.when if blocks > 1 else (lambda cond: lambda fn: fn())

    def units(body):
        """``body(rw, hk)`` over the step's units: row ``rw`` of its rows,
        K row ``hk`` of its head block."""
        def of_row(rw, carry):
            def of_heads(hk, carry):
                body(rw, hk)
                return carry
            return jax.lax.fori_loop(0, head_block // pack, of_heads, carry,
                                     unroll=True)

        jax.lax.fori_loop(0, rows, of_row, 0, unroll=True)

    @when(j == 0)
    def _init():
        o_acc[...] = jnp.zeros_like(o_acc)
        if sink:
            m_acc[...] = jnp.broadcast_to(sink_ref[...][None], m_acc.shape)
            l_acc[...] = jnp.ones_like(l_acc)
        else:
            m_acc[...] = jnp.full_like(m_acc, NEG_INF)
            l_acc[...] = jnp.zeros_like(l_acc)

    # Several blocks: the step is ONE row (``_decode_block``) and this its
    # block bound (ragged serving); block 0 is live in every row.
    @when(j < s_ref[0, bi * rows])
    def _step():
        def unit(rw, hk):
            s = _decode_block_scores(           # q: [pack*t*g, pack*d]
                q_ref[rw, hk], k_ref[0, rw, hk], scale,
                ks_ref[0, rw, hk] if quantized else None)
            kpos = j * block_m + jax.lax.broadcasted_iota(jnp.int32, s.shape,
                                                          1)
            tt = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            tt = (tt % r if pack > 1 else tt) // q_per_kv
            # each row masked at its own first chunk position
            s = jnp.where(kpos > s_ref[1, bi * rows + rw] + tt, NEG_INF, s)
            for i in range(pack):
                at = (rw, hk, slice(i * r, (i + 1) * r))
                h = hk * pack + i
                m_acc[at], l_acc[at], o_acc[at] = _decode_accumulate(
                    s[at[2]], v_ref[0, rw, h],
                    (m_acc[at], l_acc[at], o_acc[at]),
                    vs_ref[0, rw, h] if quantized else None)

        units(unit)

    @when(j == pl.num_programs(2) - 1)
    def _finish():
        # Every row has at least one attended slot (block 0 holds
        # position 0), so l > 0.
        def unit(rw, hk):
            o = (o_acc[rw, hk] / l_acc[rw, hk]).astype(o_ref.dtype)
            for i in range(pack):
                o_ref[rw, hk * pack + i] = o[i * r:(i + 1) * r]

        units(unit)


def _dequant_lane_major(qt_leaf, dtype):
    """Dequantize a lane-major QTensor cache slice (values [..., M, D],
    scales [..., 1, M]): move the per-position scales back over the seq
    dim and multiply (test/CPU path — the kernel streams int8)."""
    return (qt_leaf.values.astype(dtype)
            * jnp.swapaxes(qt_leaf.scales, -1, -2).astype(dtype))


def _stacked_cache(k_cache, v_cache, layer):
    """Normalize a decode cache to its STACKED form: returns
    (kc, vc, k_scales, v_scales, layer_idx, quantized) with kc/vc
    [L, ..., M|page, D] and lane-major scales [L, ..., 1, M|page] (None
    when not quantized).  A 4-D cache is lifted to L=1 (``layer`` must
    then be None/0)."""
    from tfmesos_tpu.ops.quant import QTensor

    quantized = isinstance(k_cache, QTensor)
    kc = k_cache.values if quantized else k_cache
    vc = v_cache.values if quantized else v_cache
    ks = k_cache.scales if quantized else None
    vs = v_cache.scales if quantized else None
    if kc.ndim == 4:
        # Any STATICALLY-zero index is fine with an L=1 lift (python int,
        # numpy int32, 0-d concrete array — operator.index normalizes
        # them all); only a nonzero or traced index actually needs the
        # stacked form.
        if layer is not None:
            try:
                layer = operator.index(layer)
            except TypeError:
                layer = None    # traced: cannot prove it selects layer 0
            if layer != 0:
                raise ValueError("layer index needs a stacked 5-D cache")
        kc, vc = kc[None], vc[None]
        if quantized:
            ks, vs = ks[None], vs[None]
        layer = 0
    layer = jnp.asarray(0 if layer is None else layer, jnp.int32)
    return kc, vc, ks, vs, layer, quantized


def flash_decode(q, k_cache, v_cache, pos, scale: Optional[float] = None,
                 block_m: int = 1024, use_pallas: Optional[bool] = None,
                 interpret: bool = False, layer=None, sink=None):
    """Single-token decode attention over a KV cache, bounded at ``pos``.

    ``q``: [B, H, D] (one new token's heads, kv-major groups) or
    [B, t, H, D] (a CHUNK — speculative verify / chunked prefill; chunk
    token tt attends cache positions <= pos + tt, the cache already
    holding the chunk's own K/V);
    ``k_cache``/``v_cache``: the kernel-native layout [B, KV, M, D]
    ((seq, head_dim) trailing — no per-call transpose of cache-sized
    data), or the STACKED [L, B, KV, M, D] buffer with ``layer`` the
    (traced OK) layer index — the ``decode_step`` layer scan passes the
    whole cache and the index rides the scalar prefetch, so no per-layer
    slice is ever materialized.  Plain arrays, or int8 ``QTensor``s with
    LANE-MAJOR scales ([(L,) B, KV, 1, M], as ``init_cache`` builds
    them), in which case HBM streams int8 and the scales fold into the
    score rows; ``pos``: scalar int32, or a [B] vector for RAGGED
    batches (each row at its own position — the mixed-length serving
    case); traced OK either way (it rides the kernel's scalar prefetch,
    bounding each row's block loop independently).  Returns q's shape.

    The XLA einsum reads all M cache slots every step because ``pos`` is
    traced; this kernel's grid maps the out-of-range m-blocks to the last
    live block (never re-fetched), so per-step HBM traffic is
    O(pos·kv·D) — the difference between serving a 32k-slot cache at
    position 2k and paying for 32k.  GQA runs at cache width: the score
    block is [g, block_m] per kv head, no materialized repeat.

    ``block_m`` defaults to 1024 (the Mosaic tile ceiling): the grid
    iterates m/block_m steps even when the bound skips their DMA, so
    bigger blocks cut per-step grid overhead — measured 2.62 -> 2.25
    ms/step on the 16k-buffer decode_longctx config (v5e, round 5);
    ``_pick_block`` still clamps to a legal divisor for small caches.
    The grid is (B / rows, KV / head_block, M / block_m): a step takes
    ``head_block`` of a row's K/V heads (all of them where they fit) and,
    where the cache is one block (a window layer's ring), ``rows`` rows —
    ``_decode_block``'s, from the call's shapes and a VMEM budget.  One
    custom call named ``flash_decode`` with one result [B, KV, t * g, Dv]
    whatever the block (the benchmark's readers tell the kernel by both).

    K and V of unequal head size: ``v_cache`` [(L,) B, KV, M, Dv], the
    result [.., H, Dv].  A PACKED K cache (``pack_k``: [(L,) B, KV / f, M,
    f * D], ``f`` heads' keys of a position side by side) is told by its
    shape; plain arrays only.  ``sink`` ([H] float32): a logit a query head
    that joins the softmax's denominator and carries no value.
    """
    kc, vc, ksc, vsc, li, quantized = _stacked_cache(k_cache, v_cache,
                                                     layer)
    squeeze = q.ndim == 3
    if squeeze:
        q = q[:, None]
    b, t, h, d = q.shape
    kv, m, dv = vc.shape[2], kc.shape[3], vc.shape[-1]
    f = _cache_pack(q, kc, vc)      # heads at axis 2 of the stacked cache
    if quantized and (f > 1 or dv != d):
        raise ValueError("an int8 cache keeps one head size for K and V")
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    g = h // kv
    block_m = _pick_block(m, block_m)
    aligned = block_m <= 1024
    if use_pallas is None:
        on_tpu = jax.default_backend() == "tpu"
        use_pallas = aligned and (on_tpu or interpret)
    if not use_pallas:
        take = lambda a: jax.lax.dynamic_index_in_dim(a, li, 0,
                                                      keepdims=False)
        k_l, v_l = _unpack_k(take(kc), f), take(vc)
        if quantized:
            from tfmesos_tpu.ops.quant import QTensor
            k_l = _dequant_lane_major(QTensor(k_l, take(ksc)), q.dtype)
            v_l = _dequant_lane_major(QTensor(v_l, take(vsc)), q.dtype)
        out = _decode_reference(q, k_l, v_l, pos, scale, sink)
        return out[:, 0] if squeeze else out

    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
    # Per-row (block bound from the LAST chunk position, first position,
    # layer index) — all three ride the scalar prefetch.
    scalars = jnp.stack([(pos + t - 1) // block_m + 1, pos,
                         jnp.broadcast_to(li, (b,))])           # [3, B]
    if not quantized and q.dtype != kc.dtype:
        # e.g. bf16 queries over a caller-widened fp32 cache: the kernel's
        # dots need one operand dtype (promote, matching the einsum path).
        q = q.astype(jnp.promote_types(q.dtype, kc.dtype))
        kc = kc.astype(q.dtype)
    # Rows t-major per kv head: row = tt*g + group member (the kernel's
    # mask derives the token index as row // g).
    qt = q.reshape(b, t, kv, g, d).transpose(0, 2, 1, 3, 4).reshape(
        b, kv, t * g, d)

    # Grid (b // rows, kv // head_block, m // block_m): a step covers one
    # block of positions of head_block K/V heads (head_block // f K rows)
    # of rows batch rows, every operand blocked alike (_decode_block).  A
    # row's live-block bound pins its index map; rows > 1 only where the
    # cache is one block, the same for every row.
    blocks = m // block_m
    rows, head_block = _decode_block(b, kv, blocks, block_m, d, dv,
                                     kc.dtype.itemsize, quantized, f)
    assert rows == 1 or blocks == 1     # rows end at different blocks
    nk = head_block // f
    at_row = lambda bi, hi, j, s: (bi, hi, 0, 0)
    q_spec = pl.BlockSpec((rows, nk, f * t * g, f * d), at_row,
                          memory_space=pltpu.VMEM)
    o_spec = pl.BlockSpec((rows, head_block, t * g, dv), at_row,
                          memory_space=pltpu.VMEM)
    last_live = lambda bi, j, s: jnp.minimum(j, s[0, bi * rows] - 1)
    cache_spec = lambda heads, width: pl.BlockSpec(
        (1, rows, heads, block_m, width),
        lambda bi, hi, j, s: (s[2, 0], bi, hi, last_live(bi, j, s), 0),
        memory_space=pltpu.VMEM)
    in_specs = [q_spec, cache_spec(nk, f * d), cache_spec(head_block, dv)]
    operands = [_pack_queries(qt, f), kc, vc]
    if quantized:
        # Scales stay stacked lane-major [L, B, KV, 1, M]: positions on
        # the lane dim, same pinned index map as their values.
        sc_spec = pl.BlockSpec(
            (1, rows, head_block, 1, block_m),
            lambda bi, hi, j, s: (s[2, 0], bi, hi, 0, last_live(bi, j, s)),
            memory_space=pltpu.VMEM)
        in_specs += [sc_spec, sc_spec]
        operands += [ksc, vsc]
    if sink is not None:
        # each row's head's logit, in the rows' own order: [KV / f, f * t
        # * g, 1] (row = head in the pack, chunk token, group member)
        logits = jnp.broadcast_to(
            jnp.asarray(sink, jnp.float32).reshape(kv // f, f, 1, g),
            (kv // f, f, t, g)).reshape(kv // f, f * t * g, 1)
        in_specs.append(pl.BlockSpec((nk, f * t * g, 1),
                                     lambda bi, hi, j, s: (hi, 0, 0),
                                     memory_space=pltpu.VMEM))
        operands.append(logits)
    acc = lambda width: pltpu.VMEM((rows, nk, f * t * g, width), jnp.float32)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b // rows, kv // head_block, blocks),
        in_specs=in_specs,
        out_specs=o_spec,
        scratch_shapes=[acc(dv), acc(1), acc(1)])
    out = pl.pallas_call(
        functools.partial(_flash_decode_kernel, block_m=block_m,
                          scale=float(scale), quantized=quantized,
                          q_per_kv=g, rows=rows, head_block=head_block,
                          blocks=blocks, pack=f, sink=sink is not None),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kv, t * g, dv), q.dtype),
        interpret=interpret,
        name="flash_decode",
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=2 * b * t * h * m * (d + dv),
            bytes_accessed=((kc[0].size + vc[0].size) * kc.dtype.itemsize
                            + 2 * q.size * q.dtype.itemsize),
            transcendentals=b * t * h * m),
    )(scalars, *operands)
    out = out.reshape(b, kv, t, g, dv).transpose(0, 2, 1, 3, 4).reshape(
        b, t, h, dv)
    return out[:, 0] if squeeze else out


def _paged_decode_reference(q, k_pool, v_pool, page_table, pos, scale,
                            layer=None, self_kv=None):
    """Gather-the-pages ground truth: materialize each row's logical cache
    view from the pool ([P, KV, page, D], or the stacked
    [L, P, KV, page, D] with ``layer``; int8 QTensors dequantize) and run
    the dense masked reference.  ``self_kv`` (deferred-write decode):
    the uncommitted chunk's [B, t, KV, D] K/V is written into each row's
    view at its own positions [pos, pos + t - 1] — the pool slots there
    are stale (t = 1 in steady-state decode; t > 1 is the FUSED
    multi-row step: a speculative verify chunk or chunked-prefill tail
    attending before its commit)."""
    from tfmesos_tpu.ops.quant import QTensor

    kc, vc, ksc, vsc, li, quantized = _stacked_cache(k_pool, v_pool, layer)
    take = lambda a: jax.lax.dynamic_index_in_dim(a, li, 0, keepdims=False)
    # a packed K pool (``pack_k``) is read as the heads it holds
    f = _cache_pack(q[:, None] if q.ndim == 3 else q, kc, vc)
    k_pool, v_pool = _unpack_k(take(kc), f), take(vc)
    if quantized:
        # Paged pools carry LANE-MAJOR scales ([P, KV, 1, page]); move
        # them back over the positions to dequantize (test/CPU path —
        # the kernel consumes the lane-major layout directly).
        k_pool = _dequant_lane_major(QTensor(k_pool, take(ksc)), q.dtype)
        v_pool = _dequant_lane_major(QTensor(v_pool, take(vsc)), q.dtype)
    b = q.shape[0]
    kv, ps = k_pool.shape[1], k_pool.shape[2]
    np_ = page_table.shape[1]
    # [B, NP, KV, page, D] -> the contiguous [B, KV, NP*page, D] view.
    gather = lambda pool: pool[page_table].transpose(0, 2, 1, 3, 4).reshape(
        b, kv, np_ * ps, pool.shape[3])
    k_view, v_view = gather(k_pool), gather(v_pool)
    if self_kv is not None:
        posv = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
        put = lambda view, c: jax.vmap(
            lambda v_, c_, p_: jax.lax.dynamic_update_slice(
                v_, c_.astype(v_.dtype), (0, p_, 0)))(
            view, c.transpose(0, 2, 1, 3), posv)
        ks = self_kv[0]
        if f > 1:       # [B, t, KV / f, f * D] is [B, t, KV, D] as it lies
            ks = ks.reshape(*ks.shape[:2], kv, -1)
        k_view = put(k_view, ks)
        v_view = put(v_view, self_kv[1])
    return _decode_reference(q, k_view, v_view, pos, scale)


def _flash_decode_paged_kernel(s_ref, walk_ref, fetch_ref, q_ref, *rest,
                               page: int, pages_per_block: int,
                               scale: float, quantized: bool, q_per_kv: int,
                               head_block: int, self_attend: bool = False,
                               pack: int = 1):
    """One (head-block, step of the walk) grid step of paged decode.

    A step's K/V block is ``pages_per_block`` pages of one row: the pool
    rides in once per page slot, each slot a BlockSpec whose index map
    chases the scalar-prefetched fetch table, so a step brings
    ``pages_per_block`` dense [head_block, page, d] slabs (each
    contiguous in the pool's own layout, scattered in the pool) and
    joins them in VMEM into one block of ``pages_per_block * page``
    positions.  Per head the online softmax then runs ONCE over the
    block: a [t*g, d] x [d, block] product, one max/exp/sum, a
    [t*g, block] x [block, d] product and one update of the head's
    slice of the shared scratch -- the per-step costs nothing amortises
    (the step itself, the copies' issue, the MXU's latency on a thin
    product, three scratch read-modify-writes) are paid per block, not
    per page (v5e, PR 31: PERF.md section 6).  The block's size is
    ``_paged_block``'s, from the call's shapes and the VMEM budget.

    The grid's second dimension is ``_paged_walk``'s: the steps that
    have work, row after row -- a row's live blocks, or one step of a
    row that has none -- and no other, its length a traced scalar.
    ``walk_ref`` names each step's row, its block of the row and
    whether it is the row's last; the scratch is zeroed on a row's
    block 0 and the row's output written on its last step.  The
    head-block dimension is PARALLEL (``dimension_semantics`` -- head
    blocks share no accumulator state, so Mosaic may split them across
    megacore) and walks the rows again; when one slab holds every head
    (the common case) it is size 1.

    ``s_ref`` rows are (live pages, position bound, layer index).  A
    slot past the row's live pages (the tail of its last block, the
    one step of an idle row) holds the page the slot fetched last, so
    the pipeline's unchanged-index elision moves no byte for it: only
    live pages move, each once.  The position bound masks such a slot
    like the tail of the last live page.  The per-head math, int8
    scale folds included, is ``_flash_decode_kernel``'s slice for slice
    (a page's lane-major scales ride with it and join along the lanes).

    ``self_attend`` (deferred-write decode, a paged-only feature): the
    uncommitted chunk's K/V rides in as a [head_block, t, d] fp operand
    accumulated at the row's last step.  The pool bound is then
    EXCLUSIVE and token-independent — ``kpos > bound`` with
    bound = pos - 1, because the pool only holds committed positions
    < pos and the slots at [pos, pos + t - 1] are stale for EVERY chunk
    token — and the intra-chunk causal structure lives in the self
    block instead (chunk token tt attends self slots ss <= tt).  This
    is the FUSED multi-row step: a t-token chunk (speculative verify /
    chunked-prefill tail) retires t decode rows through ONE launch per
    layer, the page table scalar-prefetched once for the whole chunk
    instead of once per step.

    ``pack`` > 1 (``pack_k``; not with ``quantized``): a K slab is
    [head_block / pack, page, pack * d], a ROW of ``pack`` heads' keys
    side by side, and the q block those heads' rows block-diagonal over it
    (``_pack_queries``): one product a row of heads gives head ``i`` of it
    its scores in rows ``i r .. (i + 1) r`` (r = t * g), which meet the
    head's own V block and its slice of the scratch.  The self operand's K
    is packed the same way."""
    del fetch_ref  # consumed by the slots' index maps
    ppb = pages_per_block
    it = list(rest)
    k_refs, v_refs, it = it[:ppb], it[ppb:2 * ppb], it[2 * ppb:]
    ks_refs = vs_refs = kself_ref = vself_ref = None
    if quantized:
        ks_refs, vs_refs, it = it[:ppb], it[ppb:2 * ppb], it[2 * ppb:]
    if self_attend:
        kself_ref, vself_ref = it[0], it[1]
        it = it[2:]
    o_ref, o_acc, m_acc, l_acc = it
    at = pl.program_id(1)
    bi, j, last = walk_ref[0, at], walk_ref[1, at], walk_ref[2, at] == 1
    nb = s_ref[0, bi]
    bound = s_ref[1, bi]

    def heads(body):
        # One head of the slab after another, the head an index into the
        # leading dim of every block and of the scratch.  The loop is
        # UNROLLED by Mosaic (the heads' chains of product, softmax,
        # product and scratch update overlap: a rolled loop read 30%
        # slower at every shape) but its body is traced once: a Python
        # loop's head_block x 2 x pages_per_block block loads cost a
        # decode program over a second of tracing each on the serving
        # host (PERF.md section 6, PR 31).
        def step(h, carry):
            body(h)
            return carry

        jax.lax.fori_loop(0, head_block // pack, step, 0, unroll=True)

    tg = o_acc.shape[1]             # rows of one K/V head: t * g

    def accumulate(hk, s, v_of, vs_row=None):
        """Scores ``s`` of K row ``hk`` ([pack * r, block]: its heads' rows
        one under another) meet each head's own V block and scratch."""
        for i in range(pack):
            h = hk * pack + i if pack > 1 else hk
            m_acc[h], l_acc[h], o_acc[h] = _decode_accumulate(
                s[i * tg:(i + 1) * tg] if pack > 1 else s, v_of(h),
                (m_acc[h], l_acc[h], o_acc[h]), vs_row)

    @pl.when(j == 0)
    def _init():
        o_acc[...] = jnp.zeros_like(o_acc)
        m_acc[...] = jnp.full_like(m_acc, NEG_INF)
        l_acc[...] = jnp.zeros_like(l_acc)

    @pl.when(nb > 0)       # the walk's one step of a row with no live page
    def _step():
        kpos0 = j * (ppb * page)

        def head(h):
            # The block of head h: its pages' [page, d] slabs joined
            # over the positions (scales: [1, page] rows over the lanes).
            join = lambda refs, axis: jnp.concatenate(
                [r[0, 0, h] for r in refs], axis=axis)
            s = _decode_block_scores(
                q_ref[0, h], join(k_refs, 0), scale,    # q: [t*g, d]
                join(ks_refs, 1) if quantized else None)
            kpos = kpos0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            if self_attend:
                # Committed positions only, for every chunk token: the
                # chunk's own span is stale in the pool and rides the
                # self block, which carries the causal mask.
                s = jnp.where(kpos > bound, NEG_INF, s)
            else:
                tt = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
                tt = (tt % tg if pack > 1 else tt) // q_per_kv
                s = jnp.where(kpos > bound + tt, NEG_INF, s)
            accumulate(h, s, lambda hv: jnp.concatenate(
                [ref[0, 0, hv] for ref in v_refs], axis=0),
                join(vs_refs, 1) if quantized else None)

        heads(head)

    if self_attend:
        @pl.when(last)
        def _self():
            def head(h):
                q = q_ref[0, h]
                if kself_ref.shape[2] == 1:
                    # A one-token chunk makes this a [tg, d] x [d, 1]
                    # product, which Mosaic lowers as a broadcast
                    # multiply that must keep its element type: widen
                    # first (bf16 -> f32 in the broadcast is refused for
                    # grouped queries, tg > 1).
                    q = q.astype(jnp.float32)
                s = _decode_block_scores(q, kself_ref[0, h], scale)
                # Intra-chunk causality: self slot ss holds chunk token
                # ss's K/V, and row tt attends slots <= tt (t = 1 masks
                # nothing — the single-token deferred step unchanged).
                ss = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
                tt = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
                tt = (tt % tg if pack > 1 else tt) // q_per_kv
                s = jnp.where(ss > tt, NEG_INF, s)
                accumulate(h, s, lambda hv: vself_ref[0, hv])

            heads(head)

    @pl.when(last)
    def _finish():
        o_ref[0] = (o_acc[...] / l_acc[...]).astype(o_ref.dtype)


def flash_decode_paged(q, k_pool, v_pool, page_table, pos,
                       scale: Optional[float] = None,
                       use_pallas: Optional[bool] = None,
                       interpret: bool = False, layer=None, self_kv=None):
    """Decode attention over a PAGED KV cache: each row's logical cache is
    a list of physical pages in a shared pool (``page_table`` [B, NP]
    int32 — logical block j of row b lives at
    ``pool[page_table[b, j]]``), so mixed-length sequences share memory
    without per-row max_len buffers — the PagedAttention layout, realized
    on TPU by routing the page id through the kernel's scalar-prefetched
    BlockSpec index maps (block fetches chase the table, several pages
    to one K/V block: ``_paged_block``; the grid walks the blocks that
    hold a live page and no other: ``_paged_walk``).

    ``q``: [B, H, D] or [B, t, H, D]; ``k_pool``/``v_pool``:
    [P, KV, page, D] (page and head_dim trailing — the pool's NATIVE
    layout, so no per-call transpose of the shared pool), or the STACKED
    [L, P, KV, page, D] pool with ``layer`` the (traced OK) layer index
    — the layer scan passes the whole pool and the index rides the
    scalar prefetch, so no per-layer slice is materialized.  Plain
    arrays or int8 ``QTensor``s (LANE-MAJOR scales [(L,) P, KV, 1,
    page], as ``init_paged_cache`` builds them; HBM streams int8 and the
    per-position scales fold into the score rows in-kernel);
    ``pos``: scalar or [B] int32 — positions [0..pos(+t-1)] must be
    backed by pages.  Returns q's shape.

    ``self_kv`` (deferred-write decode): the uncommitted chunk's
    ([B, t, KV, D], [B, t, KV, D]) K/V attends as a SELF operand while
    the pool still holds only positions < pos — t = 1 is the
    steady-state deferred step, t > 1 the FUSED multi-row step
    (speculative verify / chunked-prefill tails): t decode rows retire
    through one launch per layer, the page table prefetched once for
    the chunk (int8 pools: pre-quantize-dequantize the chunk so its
    numerics match a committed slot).

    K and V of unequal head size: ``v_pool`` [(L,) P, KV, page, Dv], the
    result [.., H, Dv].  A PACKED K pool (``pack_k``: [(L,) P, KV / f,
    page, f * D]) is told by its shape, and ``self_kv``'s K is then packed
    the same way ([B, t, KV / f, f * D]: a reshape); plain arrays only.
    """
    PAGED_CALL_STATS["calls"] += 1
    kp, vp, ksc, vsc, li, quantized = _stacked_cache(k_pool, v_pool, layer)
    squeeze = q.ndim == 3
    if squeeze:
        q = q[:, None]
    b, t, h, d = q.shape
    kv, ps, dv = vp.shape[2], kp.shape[3], vp.shape[-1]
    f = _cache_pack(q, kp, vp)      # kv heads at axis 2 of the pool
    if quantized and (f > 1 or dv != d):
        raise ValueError("an int8 pool keeps one head size for K and V")
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    g = h // kv
    # Eligibility only requires ONE head's page to fit the VMEM budget
    # double-buffered: _paged_block then takes as many pages and heads
    # per grid step as the budget allows, so big kv x page x d products
    # shrink the block instead of losing the kernel.
    aligned = (ps % 8 == 0 and ps <= 1024
               and 2 * f * ps * (d + dv) * kp.dtype.itemsize
               <= _PAGED_VMEM_BUDGET)
    if use_pallas is None:
        on_tpu = jax.default_backend() == "tpu"
        use_pallas = aligned and (on_tpu or interpret)
    elif use_pallas and not aligned:
        raise ValueError(
            f"flash_decode_paged(use_pallas=True): page_size {ps} with "
            f"d={d} is not kernel-eligible (page must be a multiple of "
            f"8, <= 1024, and one head's K/V slabs must fit VMEM)")
    if not use_pallas:
        out = _paged_decode_reference(q, k_pool, v_pool, page_table, pos,
                                      scale, layer=layer, self_kv=self_kv)
        return out[:, 0] if squeeze else out

    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
    if self_kv is None:
        nb = (pos + t - 1) // ps + 1
        bound = pos
    else:
        # Deferred writes: the pool holds positions < pos only — bound
        # the block loop and the mask EXCLUSIVELY; the current token
        # rides the self operands instead of its (stale) cache slot.
        nb = -(-pos // ps)              # ceil(pos / ps); 0 when pos == 0
        bound = pos - 1
    scalars = jnp.stack([nb, bound,
                         jnp.broadcast_to(li, (b,))])           # [3, B]
    page_table = jnp.asarray(page_table, jnp.int32)
    if not quantized and q.dtype != kp.dtype:
        q = q.astype(jnp.promote_types(q.dtype, kp.dtype))
        kp = kp.astype(q.dtype)
    qt = q.reshape(b, t, kv, g, d).transpose(0, 2, 1, 3, 4).reshape(
        b, kv, t * g, d)

    PAGED_CALL_STATS["kernel_calls"] += 1
    # Grid (kv // head_block, the walk's steps): a step covers
    # pages_per_block pages of head_block heads of one row, and the walk
    # holds the steps that have work and no other (_paged_walk).  The
    # pool rides in once per page SLOT of the block, each slot's index
    # map chasing the fetch table, so a page's [head_block, page, d]
    # slab -- contiguous in the pool's layout -- stays one dense copy,
    # the pipeline double-buffers every slot and starts the next step's
    # (the next row's first) copies under this step's compute.
    np_ = page_table.shape[1]
    head_block, ppb = _paged_block(kv, ps, d, kp.dtype.itemsize, np_,
                                   quantized, dv, f)
    walk, fetch, total = _paged_walk(page_table, nb, ppb)
    q_spec = pl.BlockSpec((1, head_block // f, f * t * g, f * d),
                          lambda hi, at, s, wk, ft: (wk[0, at], hi, 0, 0),
                          memory_space=pltpu.VMEM)
    o_spec = pl.BlockSpec((1, head_block, t * g, dv),
                          lambda hi, at, s, wk, ft: (wk[0, at], hi, 0, 0),
                          memory_space=pltpu.VMEM)

    def slot_specs(block_shape):
        return [pl.BlockSpec(
            block_shape,
            lambda hi, at, s, wk, ft, i=i: (s[2, 0], ft[at * ppb + i],
                                            hi, 0, 0),
            memory_space=pltpu.VMEM) for i in range(ppb)]

    in_specs = ([q_spec] + slot_specs((1, 1, head_block // f, ps, f * d))
                + slot_specs((1, 1, head_block, ps, dv)))
    # pools (page, d)-trailing
    operands = [_pack_queries(qt, f)] + [kp] * ppb + [vp] * ppb
    if quantized:
        # Scales as [L, P, KV, 1, page]: positions on the lane dim, each
        # page's beside its values under the same index map.
        in_specs += 2 * slot_specs((1, 1, head_block, 1, ps))
        operands += [ksc] * ppb + [vsc] * ppb       # already lane-major
    if self_kv is not None:
        # [B, t, KV, D] model-layout chunks -> [B, KV, t, D] t-slot fp
        # blocks (int8 pools: the caller pre-quantize-dequantizes so
        # numerics match a committed slot exactly).
        kself, vself = (c.transpose(0, 2, 1, 3).astype(q.dtype)
                        for c in self_kv)
        self_spec = lambda heads, width: pl.BlockSpec(
            (1, heads, t, width),
            lambda hi, at, s, wk, ft: (wk[0, at], hi, 0, 0),
            memory_space=pltpu.VMEM)
        in_specs += [self_spec(head_block // f, f * d),
                     self_spec(head_block, dv)]
        operands += [kself, vself]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(kv // head_block, total),
        in_specs=in_specs,
        out_specs=o_spec,
        scratch_shapes=[pltpu.VMEM((head_block, t * g, dv), jnp.float32),
                        pltpu.VMEM((head_block, t * g, 1), jnp.float32),
                        pltpu.VMEM((head_block, t * g, 1), jnp.float32)])
    # Static cost estimate.  bytes_accessed charges the slabs this
    # call can actually DMA — b rows x live pages x one K + one V
    # [KV, page, d] slab — never the WHOLE pool
    # (the old estimate charged pool bytes: a 1000-page pool serving 4
    # rows x 16 live pages overstated the traffic ~30x and mis-ranked
    # the kernel for the XLA scheduler).  flops/transcendentals use the
    # per-row block bound when ``pos`` is concrete (direct calls,
    # tests, benches); under jit the bound is traced and the TABLE
    # width is the static ceiling — the walk still holds the live
    # steps only either way.
    try:
        est_nb = int(jnp.max(nb))
    except jax.errors.ConcretizationTypeError:
        est_nb = np_
    est_nb = max(1, min(est_nb, np_))
    slab_bytes = kv * ps * (d + dv) * kp.dtype.itemsize // 2
    out = pl.pallas_call(
        functools.partial(_flash_decode_paged_kernel, page=ps,
                          pages_per_block=ppb, scale=float(scale),
                          quantized=quantized, q_per_kv=g,
                          head_block=head_block,
                          self_attend=self_kv is not None, pack=f),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kv, t * g, dv), q.dtype),
        interpret=interpret,
        name="flash_decode_paged",
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=2 * b * t * h * est_nb * ps * (d + dv),
            bytes_accessed=(2 * b * est_nb * slab_bytes
                            + 2 * q.size * q.dtype.itemsize),
            transcendentals=b * t * h * est_nb * ps),
    )(scalars, walk, fetch, *operands)
    out = out.reshape(b, kv, t, g, dv).transpose(0, 2, 1, 3, 4).reshape(
        b, t, h, dv)
    return out[:, 0] if squeeze else out


def sharded_flash_decode(q, k_cache, v_cache, pos, mesh, layer=None, **kw):
    """``flash_decode`` under GSPMD decode: shard_map over the data axes
    (batch) and tp (kv-major head blocks — the transformer
    ``cache_specs`` layout), each device running the kernel on its local
    [L, b_loc, kv_loc, M, D] cache block.  Requires tp | kv_heads (the
    same alignment condition as ``sharded_flash_attention``).  The output
    stays head-sharded; the caller's output projection contracts it and
    GSPMD inserts the tp psum exactly as on the einsum path.  ``k_cache``
    / ``v_cache`` are the STACKED [L, B, KV, M, D] buffers (lane-major
    int8 ``QTensor``s pair up per leaf; ``layer`` selects the layer
    in-kernel); ``q`` may be [B, H, D] or a chunk [B, t, H, D]."""
    from jax.sharding import PartitionSpec as P

    from tfmesos_tpu.ops.quant import QTensor
    from tfmesos_tpu.parallel.sharding import data_axes

    batch = data_axes(mesh)
    heads = "tp" if mesh.shape.get("tp", 1) > 1 else None
    qspec = (P(batch, heads, None) if q.ndim == 3
             else P(batch, None, heads, None))
    cspec = P(None, batch, heads, None, None)
    if isinstance(k_cache, QTensor):
        cspec = QTensor(cspec, P(None, batch, heads, None, None))
    li = jnp.asarray(0 if layer is None else layer, jnp.int32)
    fn = shard_map(
        lambda q_, k_, v_, p_, l_: flash_decode(q_, k_, v_, p_, layer=l_,
                                                **kw),
        mesh=mesh, in_specs=(qspec, cspec, cspec, P(batch), P()),
        out_specs=qspec, check_vma=False)
    return fn(q, k_cache, v_cache, pos, li)


def sharded_flash_attention(q, k, v, mesh, causal: bool = False,
                            scale: Optional[float] = None, **kw):
    """Flash attention under explicit sharding: shard_map over the mesh's
    batch axes (dp/fsdp) and head axis (tp) so each device runs the Pallas
    kernel on its local [b_loc, T, h_loc, D] block.  Sequence stays
    unsharded here — use ring attention when an ``sp`` axis exists."""
    from jax.sharding import PartitionSpec as P

    from tfmesos_tpu.parallel.sharding import data_axes

    _check_gqa_heads(q, k, v)
    batch = data_axes(mesh)
    heads = "tp" if "tp" in mesh.shape and mesh.shape["tp"] > 1 else None
    if heads is not None and k.shape[2] % mesh.shape["tp"]:
        # GQA/MQA with tp not dividing kv_heads: shard at full head width
        # (tp | kv_heads is also exactly when per-shard h//g grouping stays
        # aligned, so narrower K/V can only ride when it holds).
        rep = q.shape[2] // k.shape[2]
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    spec = P(batch, None, heads, None)
    if batch is None and heads is None:
        return flash_attention(q, k, v, causal=causal, scale=scale, **kw)
    fn = shard_map(
        lambda q_, k_, v_: flash_attention(q_, k_, v_, causal=causal,
                                           scale=scale, **kw),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False)
    return fn(q, k, v)


def attend(q, k, v, mesh=None, causal: bool = True,
           scale: Optional[float] = None, sp_impl: str = "ring",
           window: Optional[int] = None, **kw):
    """One attention entry point for model code: sequence parallelism when
    the mesh shards the sequence (``sp``) — ring attention by default, or
    Ulysses all-to-all with ``sp_impl="ulysses"`` — sharded flash kernel
    when it shards batch/heads, plain flash/reference otherwise.

    Grouped-query K/V (fewer heads than q) pass straight through to the
    flash/reference paths (head-index mapping, no repeat) and to Ulysses
    (narrow-width K/V all-to-all when sp divides kv_heads); the ring works
    per-head, so GQA inputs are broadcast up for it here."""
    _check_gqa_heads(q, k, v)
    if mesh is not None and "sp" in mesh.shape and mesh.shape["sp"] > 1:
        # Sliding windows compose with both sp paths: Ulysses attends the
        # full sequence after its all-to-all (window passes through to the
        # kernel), and the ring bounds the window exactly across shards
        # on either inner (Pallas via per-step q_offset kernels, einsum
        # via owner-index masks).
        if sp_impl == "ulysses":
            from tfmesos_tpu.parallel.ulysses import ulysses_attention
            return ulysses_attention(q, k, v, mesh, causal=causal,
                                     scale=scale, window=window)
        if k.shape[2] != q.shape[2]:
            rep = q.shape[2] // k.shape[2]
            k = jnp.repeat(k, rep, axis=2)
            v = jnp.repeat(v, rep, axis=2)
        if sp_impl != "ring":
            raise ValueError(f"sp_impl must be 'ring' or 'ulysses', "
                             f"got {sp_impl!r}")
        from tfmesos_tpu.parallel.ring_attention import ring_attention
        return ring_attention(q, k, v, mesh, causal=causal, scale=scale,
                              window=window)
    if mesh is not None:
        return sharded_flash_attention(q, k, v, mesh, causal=causal,
                                       scale=scale, window=window, **kw)
    return flash_attention(q, k, v, causal=causal, scale=scale,
                           window=window, **kw)
