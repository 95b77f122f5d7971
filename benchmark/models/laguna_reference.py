"""The plain reference of Laguna-XS.2's block (``laguna``): full-attention
layers beside sliding-window ones (1:3), each kind with its own count of
query heads and its own rotary embedding, a per-head sigmoid output gate, a
leading dense layer and then sigmoid-routed experts with a shared one, in
straightforward ``jax.numpy``, float32, every matmul at
``Precision.HIGHEST``.  No kernel, no cache, no batching: a window layer is
full softmax attention under a band mask.  It imports nothing of the program
(``benchmark/reference.py``'s pieces only) and takes the benchmark's own
weights (``laguna.make_weights``).

Pre-norm blocks, RMSNorm at ``rms_norm_eps``, no biases: ``h = x +
mixer(norm1(x))``, ``y = h + ffn(norm2(h))``; ``logits = norm_f(y) @ head``
(untied).

* attention layer ``i`` (``layer_types[i]``): ``num_attention_heads_per_
  layer[i]`` query heads over ``num_key_value_heads`` K/V heads of
  ``head_dim``; rotary embedding by the layer's kind (``rope_parameters``):
  the first ``partial_rotary_factor * head_dim`` channels of a head rotate
  (pairs ``(j, j + D/2)`` inside them, the ``rotate_half`` convention), the
  rest pass through; ``rope_type`` ``default``: ``f_j = theta ** (-2j / D)``;
  ``yarn``: ``lo = floor(c(beta_fast))``, ``hi = ceil(c(beta_slow))`` with
  ``c(r) = D ln(original_max / (2 pi r)) / (2 ln theta)`` clamped to ``[0, D -
  1]``, ``ramp_j = clip((j - lo) / (hi - lo), 0, 1)``, ``inv_j = (f_j /
  factor) ramp_j + f_j (1 - ramp_j)``, and cos and sin both times
  ``attention_factor``; causal softmax of ``head_dim ** -0.5 q . k`` over
  every earlier position (``full_attention``) or over positions ``max(0, t -
  sliding_window + 1) .. t`` (``sliding_attention``); then ``o_h <- o_h *
  sigmoid(n w_g[:, h])`` and ``W_o``;
* feed-forward of a ``dense`` layer: SwiGLU of ``intermediate_size``;
* of a ``sparse`` layer: router ``hidden -> num_experts``, float32; ``s =
  sigmoid(logits)``; the ``num_experts_per_tok`` largest chosen; gates the
  chosen scores over their sum times ``moe_routed_scaling_factor``, applied
  to the expert's output; expert ``e``: ``silu(h Wg[e]) * (h Wu[e])`` then
  ``Wd[e]`` at ``moe_intermediate_size``; plus the shared expert at
  ``shared_expert_intermediate_size`` on every token.

**Departures from the published description: none in the equations; two
readings the config does not spell out** (the configuration's ``assumed``
has the argument for each): ``gating: true`` is read as the per-HEAD sigmoid
output gate from the block's normed input (``w_g`` [hidden, heads]: the
published shapes then count 33.44 B parameters, the published 33.4 B; an
elementwise gate would give 34.07 B), and the router as the DeepSeek-V3
family's sigmoid router (``moe_routed_scaling_factor`` with renormalised
top-k) without a selection bias, which the config has no key for.  No q/k
norm (no key for one).

``quantize="int8"`` is the control of ``correct``, as in ``reference.py``: the
embedding, the head and every large projection (what the program's own int8
path quantizes) rounded to int8; the per-head gate's projection is one of
them (the program quantizes ``wg``), the router and the norms stay as they
are.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import HI, _w, fake_int8, rms_norm

Q_BLOCK = 128       # attention is computed in blocks of query rows
#: a sequence longer than two blocks is padded to a multiple of this many
#: positions, so that the sampled requests share a few compiled shapes: on
#: the v5e a new length compiles every layer's functions anew in ~30 s and
#: then computes in 2-8 s (14 requests at multiples of 1,024 read in 605 s
#: with their int8 control; the four that met a compiled shape in 2-18 s)
SEQ_BLOCK = 4096
E_BLOCK = 4         # at most this many experts are upcast to float32 at a time

KINDS = {"full_attention": "attention", "sliding_attention": "window"}


class Dims(NamedTuple):
    d: int
    kv: int
    hd: int
    dense: int          # the dense layers' width
    f: int              # one expert's width
    shared: int         # the shared expert's width
    experts: int
    top_k: int
    routed_scale: float
    window: int
    eps: float


def dims(model: Dict[str, Any]) -> Dims:
    if model.get("moe_apply_router_weight_on_input") \
            or model.get("attention_bias") or not model.get("gating"):
        raise ValueError("the reference gates the experts' OUTPUT, has no "
                         "attention bias and gates every head's output")
    return Dims(
        d=int(model["hidden_size"]), kv=int(model["num_key_value_heads"]),
        hd=int(model["head_dim"]), dense=int(model["intermediate_size"]),
        f=int(model["moe_intermediate_size"]),
        shared=int(model["shared_expert_intermediate_size"]),
        experts=int(model["num_experts"]),
        top_k=int(model["num_experts_per_tok"]),
        routed_scale=float(model["moe_routed_scaling_factor"]),
        window=int(model["sliding_window"]),
        eps=float(model["rms_norm_eps"]))


def layer_kinds(model: Dict[str, Any]) -> Sequence[str]:
    """``attention`` | ``window`` a layer."""
    return [KINDS[k] for k in model["layer_types"]]


def kind_heads(model: Dict[str, Any]) -> Dict[str, int]:
    """Query heads of each kind of layer (one count a kind)."""
    out: Dict[str, int] = {}
    for kind, h in zip(layer_kinds(model),
                       model["num_attention_heads_per_layer"]):
        if out.setdefault(kind, int(h)) != int(h):
            raise ValueError(f"{kind} layers differ in their query heads")
    return out


def n_dense(model: Dict[str, Any]) -> int:
    """The leading dense layers; every later layer is sparse."""
    kinds = list(model["mlp_layer_types"])
    lead = next((i for i, k in enumerate(kinds) if k != "dense"), len(kinds))
    if "dense" in kinds[lead:]:
        raise ValueError("dense layers lead the stack")
    return lead


def rope_tables(model: Dict[str, Any], kind: str
                ) -> Tuple[Tuple[float, ...], float]:
    """(inverse frequencies of the rotated pairs, the factor on cos and sin)
    of a kind of layer, from ``rope_parameters``: computed here in float64
    and handed on as float32, independent of the program's own."""
    key = {v: k for k, v in KINDS.items()}[kind]
    rp = model["rope_parameters"][key]
    rd = int(int(model["head_dim"]) * float(rp["partial_rotary_factor"]))
    theta = float(rp["rope_theta"])
    j = np.arange(0, rd, 2, dtype=np.float64)
    f = theta ** (-j / rd)
    if rp["rope_type"] == "default":
        return tuple(float(x) for x in f), 1.0
    if rp["rope_type"] != "yarn":
        raise ValueError(f"rope_type {rp['rope_type']!r}")

    def c(turns):
        return rd * math.log(float(rp["original_max_position_embeddings"])
                             / (2 * math.pi * turns)) / (2 * math.log(theta))

    lo = max(math.floor(c(float(rp["beta_fast"]))), 0)
    hi = min(math.ceil(c(float(rp["beta_slow"]))), rd - 1)
    ramp = np.clip((np.arange(rd // 2) - lo) / max(hi - lo, 1e-3), 0, 1)
    inv = f / float(rp["factor"]) * ramp + f * (1 - ramp)
    return tuple(float(x) for x in inv), float(rp["attention_factor"])


def rope(x, positions, inv_freq, factor):
    """x [T, H, D]: the first ``2 * len(inv_freq)`` channels rotate, pairs
    (j, j + half) inside them; cos and sin times ``factor``."""
    half = len(inv_freq)
    ang = positions.astype(jnp.float32)[:, None] * jnp.asarray(
        inv_freq, jnp.float32)
    cos = (jnp.cos(ang) * factor)[:, None, :]
    sin = (jnp.sin(ang) * factor)[:, None, :]
    x1, x2, rest = x[..., :half], x[..., half:2 * half], x[..., 2 * half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos, rest],
                           -1)


def attention_mixer(h, att, ai, dm: Dims, heads: int, window: Optional[int],
                    inv_freq, factor, quantize):
    """Gated causal grouped-query attention on h [T, d] (T a multiple of
    ``Q_BLOCK``), by blocks of query rows; weights at layer ``ai`` of the
    kind's stacked leaves; ``window``: a position attends the last so many
    (itself included), None: all."""
    t = h.shape[0]
    g = heads // dm.kv
    pos = jnp.arange(t)
    q = jnp.dot(h, _w(att["wq"], ai, quantize), precision=HI)
    k = jnp.dot(h, _w(att["wk"], ai, quantize), precision=HI)
    v = jnp.dot(h, _w(att["wv"], ai, quantize), precision=HI)
    q = rope(q.reshape(t, heads, dm.hd), pos, inv_freq, factor)
    k = rope(k.reshape(t, dm.kv, dm.hd), pos, inv_freq, factor)
    qb = q.reshape(t // Q_BLOCK, Q_BLOCK, dm.kv, g, dm.hd)
    v = v.reshape(t, dm.kv, dm.hd)
    scale = dm.hd ** -0.5

    def block(args):
        qi, i = args
        s = jnp.einsum("qkgd,tkd->kgqt", qi, k, precision=HI) * scale
        qpos = (i * Q_BLOCK + jnp.arange(Q_BLOCK))[:, None]
        bad = pos[None, :] > qpos
        if window is not None:
            bad = bad | (pos[None, :] < qpos - (window - 1))
        s = jnp.where(bad[None, None], -jnp.inf, s)
        return jnp.einsum("kgqt,tkd->qkgd", jax.nn.softmax(s, axis=-1), v,
                          precision=HI)

    o = jax.lax.map(block, (qb, jnp.arange(t // Q_BLOCK)))
    gate = jax.nn.sigmoid(
        jnp.dot(h, _w(att["wg"], ai, quantize), precision=HI))  # [T, heads]
    o = o.reshape(t, heads, dm.hd) * gate[:, :, None]
    return jnp.dot(o.reshape(t, heads * dm.hd), _w(att["wo"], ai, quantize),
                   precision=HI)


def swiglu(h, wg, wu, wd):
    g = jnp.dot(h, wg, precision=HI)
    u = jnp.dot(h, wu, precision=HI)
    return jnp.dot(jax.nn.silu(g) * u, wd, precision=HI)


def routing(h, layers, si, dm: Dims):
    """(gates [T, k] float32, expert ids [T, k], margin [T]): scores
    ``sigmoid(logits)``, the ``top_k`` largest, gates the chosen scores over
    their sum, times the scaling factor; ``margin``: how far, in router
    logits, the last expert chosen stands above the first one left out (how
    decided a token's routing is here).  The router is never quantized."""
    logits = jnp.dot(h, _w(layers["router"], si, None), precision=HI)
    top, idx = jax.lax.top_k(logits, dm.top_k + 1)
    kept, idx = jax.nn.sigmoid(top[:, :-1]), idx[:, :-1]
    return (kept / jnp.sum(kept, axis=-1, keepdims=True) * dm.routed_scale,
            idx, top[:, -2] - top[:, -1])


def routed_experts(h, layers, si, dm: Dims, quantize):
    """For every token, the sum over its assignments of gate * expert(h),
    every expert held, and the routing's margin [T].  ``E_BLOCK`` experts
    are upcast at a time."""
    gates, idx, margin = routing(h, layers, si, dm)

    def expert(leaf, e):
        w = jax.lax.dynamic_index_in_dim(
            jax.lax.dynamic_index_in_dim(leaf, si, 0, keepdims=False),
            e, 0, keepdims=False).astype(jnp.float32)
        return fake_int8(w, axis=0) if quantize == "int8" else w

    def one(e):
        weight = jnp.sum(jnp.where(idx == e, gates, 0.0), axis=1)
        return weight[:, None] * swiglu(
            h, expert(layers["e_gate"], e), expert(layers["e_up"], e),
            expert(layers["e_down"], e))

    eb = max(n for n in range(1, E_BLOCK + 1) if dm.experts % n == 0)

    def block(acc, es):
        return acc + sum(one(es[j]) for j in range(eb)), None

    out, _ = jax.lax.scan(block, jnp.zeros_like(h),
                          jnp.arange(dm.experts).reshape(-1, eb))
    return out, margin


@functools.partial(jax.jit, static_argnames=(
    "dm", "kind", "heads", "inv_freq", "factor", "quantize"))
def mixer(x, layers, li, ki, *, dm: Dims, kind: str, heads: int, inv_freq,
          factor: float, quantize):
    """``x + mixer(norm1(x))`` of layer ``li``, the ``ki``-th of its kind."""
    h = rms_norm(x, _w(layers["attn_norm"], li, None), dm.eps)
    return x + attention_mixer(
        h, layers[kind], ki, dm, heads,
        dm.window if kind == "window" else None, inv_freq, factor, quantize)


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
    shared = swiglu(h, *(_w(layers[k], fi, quantize)
                         for k in ("s_gate", "s_up", "s_down")))
    routed, margin = routed_experts(h, layers, fi, dm, quantize)
    return x + routed + shared, margin


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
    dm, heads, lead = dims(model), kind_heads(model), n_dense(model)
    n = int(len(tokens))
    pad = SEQ_BLOCK if n > 2 * Q_BLOCK else Q_BLOCK
    toks = np.zeros(-(-n // pad) * pad, np.int32)
    toks[:n] = tokens
    x = _embed(weights["embed"], jnp.asarray(toks), quantize)
    seen = dict.fromkeys(KINDS.values(), 0)
    margin = jnp.full(toks.shape, jnp.inf, jnp.float32)
    for li, kind in enumerate(layer_kinds(model)):
        inv_freq, factor = rope_tables(model, kind)
        x = mixer(x, weights["layers"], li, seen[kind], dm=dm, kind=kind,
                  heads=heads[kind], inv_freq=inv_freq, factor=factor,
                  quantize=quantize)
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

    **Read at the positions whose routing is decided.**  With 256 experts
    the eighth and the ninth router logit of a token lie ~0.06 apart in the
    mean, and the program's bfloat16 residual stream moves a logit by a
    tenth of that: in a quarter of the tokens some layer's ninth expert
    changes places with its eighth, which moves that token's layer output
    by ``moe_routed_scaling_factor / num_experts_per_tok`` of an expert's,
    in the program and in its int8 control alike (PERF.md has the
    readings).  Such a token tells the reference nothing about the
    arithmetic, so ``gap`` and ``control_gap`` hold only the positions where
    the reference's own margin, in every sparse layer, is at least
    ``correct.decided_margin`` router logits (absent: every position); what
    was read at all of them comes back as ``gap_all`` / ``control_gap_all``
    beside ``margin``."""
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
