"""The plain reference of MiMo-V2-Flash's block (``mimo``): full-attention
layers beside sliding-window ones (1:5), keys of ``head_dim`` and values of
``v_head_dim`` channels, each kind of layer with its own count of K/V heads
and its own rope base, a learned sink in the window layers' softmax, the
values scaled, a leading dense layer and then sigmoid-routed experts chosen
under a selection bias, with no shared expert, in straightforward
``jax.numpy``, float32, every matmul at ``Precision.HIGHEST``.  No kernel, no
cache, no batching: a window layer is softmax attention under a band mask.
It imports nothing of the program (``benchmark/reference.py``'s pieces only)
and takes the benchmark's own weights (``mimo.make_weights``).

Pre-norm blocks, RMSNorm at ``layernorm_epsilon``, no biases: ``h = x +
mixer(norm1(x))``, ``y = h + ffn(norm2(h))``; ``logits = norm_f(y) @ head``
(untied).

* attention, both kinds (``hybrid_layer_pattern[i]``: 0 full, 1 window):
  ``num_attention_heads`` query heads; ``q = n W_q`` [heads x head_dim], ``k
  = n W_k`` [KV x head_dim], ``v = n W_v`` [KV x v_head_dim]; the first
  ``int(head_dim x partial_rotary_factor)`` channels of a q / k head rotate
  (pairs ``(j, j + rd / 2)`` inside them, the ``rotate_half`` convention),
  the rest pass through; scores ``q . k / sqrt(head_dim)``; the values times
  ``attention_value_scale``; ``x += [o_1 .. o_H] W_o``, ``W_o`` [heads x
  v_head_dim, hidden];
* a full layer: ``num_key_value_heads`` K/V heads, ``rope_theta``, causal
  softmax over every earlier position, no sink
  (``add_full_attention_sink_bias`` false);
* a window layer: ``swa_num_key_value_heads`` K/V heads, ``swa_rope_theta``,
  position ``t`` attends ``max(0, t - sliding_window + 1) .. t``, and a
  learned sink (``add_swa_attention_sink_bias``): one float ``b_h`` a head
  and layer joins the softmax's denominator and carries no value, ``p_j =
  exp(s_j - m) / (sum_j exp(s_j - m) + exp(b_h - m))``, ``m = max(max_j s_j,
  b_h)``;
* feed-forward of a layer with ``moe_layer_freq[i]`` 0: SwiGLU of
  ``intermediate_size``; of every other layer: router ``hidden ->
  n_routed_experts`` (the PUBLISHED count), float32; ``s =
  sigmoid(logits)``; the ``num_experts_per_tok`` largest of ``s + bias``
  chosen (``topk_method`` ``noaux_tc``: the bias selects and is no part of
  the gate; ``n_group`` 1: no group limit); gates the chosen scores over
  their sum (``norm_topk_prob``), times ``routed_scaling_factor`` (null: 1);
  expert ``e``: ``silu(h Wg[e]) * (h Wu[e])`` then ``Wd[e]`` at
  ``moe_intermediate_size``; no shared expert.

**The share this chip holds.**  The experts ``offset .. offset + held - 1``
of every sparse layer (``n_routed_experts`` in the cut file; ``published``
has the router's width) and the first ``vocab_size`` rows of embedding and
head: the reference adds, for every token, only the assignments that fall on
held experts, as the program does, and leaves out what the absent experts
would add (they are other chips' partial sums).

**Departures from the published description: none in the equations; what the
config leaves open** (the configuration's ``assumed`` has each): no q/k norm
(no key for one); the ``rotate_half`` pairing; the window counts the query's
own position; ``attention_chunk_size`` is read by nothing (the family is
described as sliding-window, not chunked); the sink as a logit in the
denominator only.  The three multi-token prediction layers the family is
described with have no key in the config and are not here.

``quantize="int8"`` is the control of ``correct``, as in ``reference.py``: the
embedding, the head and every large projection (what the program's own int8
path quantizes) rounded to int8; the router, its bias, the sinks and the
norms stay as they are.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import HI, _w, fake_int8, rms_norm

Q_BLOCK = 128       # attention is computed in blocks of query rows
#: a sequence longer than two blocks is padded to a multiple of this many
#: positions, so that the sampled requests share a few compiled shapes (a
#: new length compiles every layer's functions anew on the chip)
SEQ_BLOCK = 4096
E_BLOCK = 4         # at most this many experts are upcast to float32 at a time

KINDS = {0: "attention", 1: "window"}


class Dims(NamedTuple):
    d: int
    heads: int
    hd: int             # a query's and a key's head size
    vd: int             # a value's head size
    dense: int          # the dense layers' width
    f: int              # one expert's width
    experts: int        # the router's width (the published count)
    held: int           # experts held here, offset .. offset + held - 1
    offset: int
    top_k: int
    routed_scale: float
    value_scale: float
    window: int
    eps: float


def dims(model: Dict[str, Any]) -> Dims:
    if (model.get("attention_bias") or model.get("n_shared_experts")
            or model["scoring_func"] != "sigmoid"
            or model["topk_method"] != "noaux_tc"
            or int(model["n_group"]) != 1 or not model["norm_topk_prob"]
            or model["swa_head_dim"] != model["head_dim"]
            or model["swa_v_head_dim"] != model["v_head_dim"]
            or model["swa_num_attention_heads"]
            != model["num_attention_heads"]):
        raise ValueError(
            "the reference runs sigmoid scores under a selection bias "
            "without groups or a shared expert, no attention bias, and one "
            "head size and one count of query heads for both kinds of layer")
    pub, dep = model.get("published", {}), model.get("deployment", {})
    held = int(model["n_routed_experts"])
    return Dims(
        d=int(model["hidden_size"]), heads=int(model["num_attention_heads"]),
        hd=int(model["head_dim"]), vd=int(model["v_head_dim"]),
        dense=int(model["intermediate_size"]),
        f=int(model["moe_intermediate_size"]),
        experts=int(pub.get("n_routed_experts", held)), held=held,
        offset=int(dep.get("expert_shard", 0)) * held,
        top_k=int(model["num_experts_per_tok"]),
        routed_scale=float(model["routed_scaling_factor"] or 1.0),
        value_scale=float(model["attention_value_scale"]),
        window=int(model["sliding_window"]),
        eps=float(model["layernorm_epsilon"]))


def layer_kinds(model: Dict[str, Any]) -> Sequence[str]:
    """``attention`` | ``window`` a layer."""
    return [KINDS[int(k)] for k in model["hybrid_layer_pattern"]]


def kind_kv(model: Dict[str, Any]) -> Dict[str, int]:
    """K/V heads of each kind of layer."""
    return {"attention": int(model["num_key_value_heads"]),
            "window": int(model["swa_num_key_value_heads"])}


def kind_sink(model: Dict[str, Any]) -> Dict[str, bool]:
    return {"attention": bool(model["add_full_attention_sink_bias"]),
            "window": bool(model["add_swa_attention_sink_bias"])}


def n_dense(model: Dict[str, Any]) -> int:
    """The leading dense layers; every later layer is sparse."""
    freq = [int(x) for x in model["moe_layer_freq"]]
    lead = next((i for i, k in enumerate(freq) if k), len(freq))
    if 0 in freq[lead:]:
        raise ValueError("dense layers lead the stack")
    return lead


def rotary_dim(model: Dict[str, Any]) -> int:
    """Channels of a head that rotate: the first so many."""
    return int(int(model["head_dim"]) * float(model["partial_rotary_factor"]))


def rope_freqs(model: Dict[str, Any], kind: str) -> Tuple[float, ...]:
    """Inverse frequencies of the rotated pairs of a kind of layer, computed
    here in float64 and handed on as float32, independent of the program's."""
    rd = rotary_dim(model)
    theta = float(model["swa_rope_theta" if kind == "window"
                        else "rope_theta"])
    return tuple(float(x) for x in
                 theta ** (-np.arange(0, rd, 2, dtype=np.float64) / rd))


def rope(x, positions, inv_freq):
    """x [T, H, D]: the first ``2 * len(inv_freq)`` channels rotate, pairs
    (j, j + half) inside them; the rest pass through."""
    half = len(inv_freq)
    ang = positions.astype(jnp.float32)[:, None] * jnp.asarray(
        inv_freq, jnp.float32)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2, rest = x[..., :half], x[..., half:2 * half], x[..., 2 * half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos, rest],
                           -1)


def attention_mixer(h, att, ai, dm: Dims, kv: int, window: Optional[int],
                    sink: bool, inv_freq, quantize):
    """Causal grouped-query attention on h [T, d] (T a multiple of
    ``Q_BLOCK``), by blocks of query rows; weights at layer ``ai`` of the
    kind's stacked leaves.  ``window``: a position attends the last so many
    (itself included), None: all; a block of a window layer reads only the
    keys its rows can reach (the ``window - 1`` before its first row up to
    its last row).  ``sink``: the layer's ``sink`` [heads] joins every
    row's denominator."""
    t = h.shape[0]
    g = dm.heads // kv
    pos = jnp.arange(t)
    q = jnp.dot(h, _w(att["wq"], ai, quantize), precision=HI)
    k = jnp.dot(h, _w(att["wk"], ai, quantize), precision=HI)
    v = jnp.dot(h, _w(att["wv"], ai, quantize), precision=HI)
    q = rope(q.reshape(t, dm.heads, dm.hd), pos, inv_freq)
    k = rope(k.reshape(t, kv, dm.hd), pos, inv_freq)
    v = v.reshape(t, kv, dm.vd) * dm.value_scale
    qb = q.reshape(t // Q_BLOCK, Q_BLOCK, kv, g, dm.hd)
    scale = dm.hd ** -0.5
    b = (_w(att["sink"], ai, None).reshape(kv, g, 1, 1) if sink else None)
    # a window block's keys: ``back`` positions before its first row (whole
    # blocks of zeros in front, masked by position)
    back = 0 if window is None else -(-(window - 1) // Q_BLOCK) * Q_BLOCK
    span = t if window is None else back + Q_BLOCK
    kp = jnp.pad(k, ((back, 0), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((back, 0), (0, 0), (0, 0)))

    def block(args):
        qi, i = args
        start = 0 if window is None else i * Q_BLOCK    # in the padded keys
        kj = jax.lax.dynamic_slice_in_dim(kp, start, span, 0)
        vj = jax.lax.dynamic_slice_in_dim(vp, start, span, 0)
        kpos = (start - back + jnp.arange(span))[None, :]
        qpos = (i * Q_BLOCK + jnp.arange(Q_BLOCK))[:, None]
        s = jnp.einsum("qkgd,tkd->kgqt", qi, kj, precision=HI) * scale
        bad = (kpos > qpos) | (kpos < 0)
        if window is not None:
            bad = bad | (kpos < qpos - (window - 1))
        s = jnp.where(bad[None, None], -jnp.inf, s)
        if b is None:
            p = jax.nn.softmax(s, axis=-1)
        else:
            m = jnp.maximum(jnp.max(s, axis=-1, keepdims=True), b)
            e = jnp.exp(s - m)
            p = e / (jnp.sum(e, axis=-1, keepdims=True) + jnp.exp(b - m))
        return jnp.einsum("kgqt,tkd->qkgd", p, vj, precision=HI)

    o = jax.lax.map(block, (qb, jnp.arange(t // Q_BLOCK)))
    return jnp.dot(o.reshape(t, dm.heads * dm.vd),
                   _w(att["wo"], ai, quantize), precision=HI)


def swiglu(h, wg, wu, wd):
    g = jnp.dot(h, wg, precision=HI)
    u = jnp.dot(h, wu, precision=HI)
    return jnp.dot(jax.nn.silu(g) * u, wd, precision=HI)


def routing(h, layers, si, dm: Dims):
    """(gates [T, k] float32, expert ids [T, k], margin [T]) over ALL
    experts: scores ``sigmoid(logits)``, the ``top_k`` largest ``score +
    bias``, gates the chosen scores over their sum, times the scaling
    factor.  ``margin``: how far, in ``score + bias``, the last expert chosen
    stands above the first one left out WHERE exactly one of the two is held
    here (else infinite): only such a change of places changes this chip's
    part of the sum.  The router is never quantized."""
    logits = jnp.dot(h, _w(layers["router"], si, None), precision=HI)
    s = jax.nn.sigmoid(logits)
    top, idx = jax.lax.top_k(s + _w(layers["router_bias"], si, None),
                             dm.top_k + 1)
    here = (idx >= dm.offset) & (idx < dm.offset + dm.held)
    margin = jnp.where(here[:, -2] != here[:, -1], top[:, -2] - top[:, -1],
                       jnp.inf)
    idx = idx[:, :-1]
    kept = jnp.take_along_axis(s, idx, axis=-1)
    return (kept / jnp.sum(kept, axis=-1, keepdims=True) * dm.routed_scale,
            idx, margin)


def routed_experts(h, layers, si, dm: Dims, quantize, held=None):
    """The held experts' part of the routed sum (for every token, the sum
    over its assignments that fall on experts ``offset .. offset + held -
    1`` of gate * expert(h)) and the routing's margin [T].  ``E_BLOCK``
    experts are upcast at a time.  ``held``: (offset, count) of another
    share of the experts, whose leaves ``layers`` then holds (the share
    test's)."""
    offset, n = (dm.offset, dm.held) if held is None else held
    gates, idx, margin = routing(h, layers, si, dm)

    def expert(leaf, e):
        w = jax.lax.dynamic_index_in_dim(
            jax.lax.dynamic_index_in_dim(leaf, si, 0, keepdims=False),
            e, 0, keepdims=False).astype(jnp.float32)
        return fake_int8(w, axis=0) if quantize == "int8" else w

    def one(e):
        weight = jnp.sum(jnp.where(idx == e + offset, gates, 0.0), axis=1)
        return weight[:, None] * swiglu(
            h, expert(layers["e_gate"], e), expert(layers["e_up"], e),
            expert(layers["e_down"], e))

    eb = max(m for m in range(1, E_BLOCK + 1) if n % m == 0)

    def block(acc, es):
        return acc + sum(one(es[j]) for j in range(eb)), None

    out, _ = jax.lax.scan(block, jnp.zeros_like(h),
                          jnp.arange(n).reshape(-1, eb))
    return out, margin


@functools.partial(jax.jit, static_argnames=(
    "dm", "kind", "kv", "sink", "inv_freq", "quantize"))
def mixer(x, layers, li, ki, *, dm: Dims, kind: str, kv: int, sink: bool,
          inv_freq, quantize):
    """``x + mixer(norm1(x))`` of layer ``li``, the ``ki``-th of its kind."""
    h = rms_norm(x, _w(layers["attn_norm"], li, None), dm.eps)
    return x + attention_mixer(
        h, layers[kind], ki, dm, kv,
        dm.window if kind == "window" else None, sink, inv_freq, quantize)


@functools.partial(jax.jit, static_argnames=("dm", "dense", "quantize"))
def ffn_block(x, layers, li, fi, *, dm: Dims, dense: bool, quantize):
    """(``x + ffn(norm2(x))``, the routing's margin [T]: infinite in a
    dense layer) of layer ``li``, the ``fi``-th of its feed-forward kind
    (the dense and the sparse leaves are stacked apart)."""
    h = rms_norm(x, _w(layers["mlp_norm"], li, None), dm.eps)
    if dense:
        de = layers["dense"]
        return x + swiglu(h, *(_w(de[k], fi, quantize)
                               for k in ("w_gate", "w_up", "w_down"))), \
            jnp.full(x.shape[:1], jnp.inf, jnp.float32)
    routed, margin = routed_experts(h, layers, fi, dm, quantize)
    return x + routed, margin


@functools.partial(jax.jit, static_argnames=("quantize",))
def _embed(embed, tokens, quantize):
    rows = jnp.take(embed, tokens, axis=0).astype(jnp.float32)
    if quantize == "int8":
        rows = fake_int8(rows, axis=1)
    return rows


@functools.partial(jax.jit, static_argnames=("eps", "quantize"))
def _head(x, at, norm_f, head, *, eps, quantize):
    h = rms_norm(jnp.take(x, at, axis=0), norm_f.astype(jnp.float32), eps)
    w = head.astype(jnp.float32)
    if quantize == "int8":
        w = fake_int8(w, axis=0)
    return jnp.dot(h, w, precision=HI)


def hidden(weights, model: Dict[str, Any], tokens: np.ndarray,
           quantize: Optional[str] = None):
    """(final hidden states [T_padded, d] of one sequence, before the last
    norm; every position's smallest routing margin over the sparse layers
    [T_padded]).  The sequence is padded to a multiple of ``Q_BLOCK`` (of
    ``SEQ_BLOCK`` past two blocks); attention is causal, so the padding
    touches no real position."""
    dm, kv, sinks = dims(model), kind_kv(model), kind_sink(model)
    lead = n_dense(model)
    n = int(len(tokens))
    pad = SEQ_BLOCK if n > 2 * Q_BLOCK else Q_BLOCK
    toks = np.zeros(-(-n // pad) * pad, np.int32)
    toks[:n] = tokens
    x = _embed(weights["embed"], jnp.asarray(toks), quantize)
    seen = dict.fromkeys(KINDS.values(), 0)
    margin = jnp.full(toks.shape, jnp.inf, jnp.float32)
    for li, kind in enumerate(layer_kinds(model)):
        x = mixer(x, weights["layers"], li, seen[kind], dm=dm, kind=kind,
                  kv=kv[kind], sink=sinks[kind],
                  inv_freq=rope_freqs(model, kind), quantize=quantize)
        seen[kind] += 1
        dense = li < lead
        x, m = ffn_block(x, weights["layers"], li,
                         li if dense else li - lead, dm=dm, dense=dense,
                         quantize=quantize)
        margin = jnp.minimum(margin, m)
    return x, margin


def logits_at(weights, model: Dict[str, Any], tokens: np.ndarray,
              at: Sequence[int], quantize: Optional[str] = None):
    """Reference logits [len(at), vocab] at positions ``at`` of one
    sequence: the distribution of the token AFTER each position."""
    return read_at(weights, model, tokens, at, quantize)[0]


def read_at(weights, model: Dict[str, Any], tokens: np.ndarray,
            at: Sequence[int], quantize: Optional[str] = None):
    """(``logits_at``, the routing margin of each of those positions: the
    smallest over the sparse layers)."""
    x, margin = hidden(weights, model, tokens, quantize)
    at = jnp.asarray(np.asarray(at, np.int32))
    return _head(x, at, weights["norm_f"], weights["head"],
                 eps=dims(model).eps, quantize=quantize), margin[at]


def served_gaps(weights, model: Dict[str, Any], prompt: np.ndarray,
                served: Sequence[int], control: bool = False
                ) -> Dict[str, np.ndarray]:
    """As ``reference.served_gaps``: at each served position, how far the
    served token's reference logit lies below the reference's best; with
    ``control`` also the gap of the token the int8 control puts first.

    ``gap`` and ``control_gap`` hold the positions whose routing is decided
    FOR THIS CHIP: where the reference's own margin (``routing``: the last
    expert chosen over the first left out, counted only where exactly one
    of the two is held here) is at least ``correct.decided_margin`` in
    every sparse layer (absent or 0: every position).  What was read at all
    of them comes back as ``gap_all`` / ``control_gap_all`` beside
    ``margin``."""
    served = np.asarray(served, np.int32)
    seq = np.concatenate([np.asarray(prompt, np.int32), served[:-1]])
    p = len(prompt)
    at = np.arange(p - 1, p - 1 + len(served))
    ref, margin = read_at(weights, model, seq, at)
    margin = np.asarray(margin, np.float64)
    decided = margin >= float(
        model.get("correct", {}).get("decided_margin", 0.0))
    best = jnp.max(ref, axis=-1)

    def below_best(tokens):
        gap = best - jnp.take_along_axis(ref, tokens[:, None], axis=-1)[:, 0]
        return np.asarray(gap, np.float64)

    gap = below_best(jnp.asarray(served))
    out = {"gap": gap[decided], "gap_all": gap, "margin": margin}
    if control:
        low = logits_at(weights, model, seq, at, quantize="int8")
        cgap = below_best(jnp.argmax(low, axis=-1))
        out.update(control_gap=cgap[decided], control_gap_all=cgap)
    return out
