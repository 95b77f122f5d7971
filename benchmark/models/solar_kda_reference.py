"""The plain reference of Solar-Open2's block (``solar_open2``): KDA layers
(Kimi Delta Attention: a gated delta rule with a decay per key channel, Kimi
Linear, arXiv:2510.26692) beside gated NoPE grouped-query attention, every
layer followed by sigmoid-routed experts and a shared expert, in
straightforward ``jax.numpy``, float32, every matmul at
``Precision.HIGHEST``.  No kernel, no cache, no batching, no chunking: the
recurrence is written as the recurrence, a scan over positions.  It imports
nothing of the program (``benchmark/reference.py``'s pieces only) and takes
the benchmark's own weights (``solar_kda.make_weights``).

Pre-norm blocks, RMSNorm at ``rms_norm_eps``: ``h = x + mixer(norm1(x))``,
``y = h + experts(norm2(h)) + shared(norm2(h))``; ``logits = norm_f(y) @
head`` (untied).

* KDA layer (every layer not in ``gqa_layers``; ``H`` heads of ``dk = dv``):
  ``[q | k | v] = x W_in`` (each ``H * dk`` wide, no bias); a causal depthwise
  conv of ``short_conv_kernel_size`` taps over all three, then SiLU; per head
  ``q = l2norm(q) * dk ** -0.5``, ``k = l2norm(k)`` (``x / sqrt(sum x^2 +
  1e-6)``); the log-decay per head and key channel ``g = -exp(A_log[h]) *
  softplus(W_f_up (W_f_down x) + dt_bias)``; the step ``beta = sigmoid(W_b
  x)`` per head, doubled where ``kda_allow_neg_eigval``; from ``S = 0``:
  ``S' = Diag(exp(g_t)) S_{t-1}``, ``u_t = v_t - S'^T k_t``, ``S_t = S' +
  beta_t k_t u_t^T``, ``o_t = S_t^T q_t``; out ``W_o [rmsnorm_head(o_t) *
  gain * sigmoid(W_g_up (W_g_down x))]``;
* attention layer (``gqa_layers``): ``num_attention_heads`` query and
  ``num_key_value_heads`` K/V heads of ``head_dim``, NO positional embedding
  (``use_rope`` false), causal softmax of ``head_dim ** -0.5 q . k``; where
  ``use_gqa_gate``: ``W_o [attn * sigmoid(x W_g)]``, the gate elementwise
  from the block's normed input;
* expert block: router ``hidden -> E`` (all ``published`` experts), float32;
  ``s = sigmoid(logits)``; the ``num_experts_per_tok`` largest ``s + b``
  (``router_bias``: a selection bias, no part of the gate); gates ``s_i /
  sum of the chosen s`` (``norm_topk_prob``) times ``routed_scaling_factor``;
  expert ``e``: ``silu(h Wg[e]) * (h Wu[e])`` then ``Wd[e]``; the shared
  expert the same at ``n_shared_experts * moe_intermediate_size``, always on.

**The share.**  The configuration holds ``n_routed_experts`` of the
``published`` count in every layer (the experts ``expert_shard *
n_routed_experts`` onward of a deployment that divides each layer's experts
over ``expert_parallel`` chips) and rows ``0 .. vocab_size - 1`` of the
published embedding and head.  The router keeps its published width; what the
experts held elsewhere would add is left out here exactly as in the program,
and that partial result goes on to the next layer.  ``routed_experts``,
``shared_mlp`` and ``mixer`` are separate functions so that a test can add the
shares up against the uncut layer.

``quantize="int8"`` is the control of ``correct``, as in ``reference.py``: the
embedding, the head and every large projection (what the program's own int8
path quantizes) rounded to int8; the low-rank gates, ``b_proj``, the conv, the
router and the per-channel leaves stay as they are.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import HI, _w, fake_int8, rms_norm

Q_BLOCK = 256       # attention is computed in blocks of query rows
E_BLOCK = 4         # at most this many experts are upcast to float32 at a time
L2_EPS = 1e-6


class Dims(NamedTuple):
    d: int
    heads: int
    kv: int
    hd: int
    f: int              # one expert's width
    shared: int         # the shared expert's width
    experts: int        # the router's width (published)
    held: int           # experts held here
    offset: int         # the first of them
    top_k: int
    routed_scale: float
    k_heads: int
    k_hd: int
    k_conv: int
    neg_eigval: bool
    gate: bool
    eps: float


def dims(model: Dict[str, Any]) -> Dims:
    pub = model.get("published", {})
    held = int(model["n_routed_experts"])
    dep = model.get("deployment", {})
    lin = model["linear_attn_config"]
    if model.get("use_rope") or not model.get("norm_topk_prob", True):
        raise ValueError("the reference applies no positional embedding "
                         "(use_rope false) and renormalises the chosen "
                         "scores (norm_topk_prob true)")
    if model.get("kda_use_full_proj") or lin.get("num_kv_heads"):
        raise ValueError("the reference's decay and gate are low-rank "
                         "(kda_use_full_proj false) and its KDA heads are "
                         "not grouped (num_kv_heads null)")
    if int(model.get("first_k_dense_replace", 0)):
        raise ValueError("every layer has experts (first_k_dense_replace 0)")
    return Dims(
        d=int(model["hidden_size"]), heads=int(model["num_attention_heads"]),
        kv=int(model["num_key_value_heads"]), hd=int(model["head_dim"]),
        f=int(model["moe_intermediate_size"]),
        shared=int(model["n_shared_experts"])
        * int(model["moe_intermediate_size"]),
        experts=int(pub.get("n_routed_experts", held)), held=held,
        offset=int(dep.get("expert_shard", 0)) * held,
        top_k=int(model["num_experts_per_tok"]),
        routed_scale=float(model["routed_scaling_factor"]),
        k_heads=int(lin["num_heads"]), k_hd=int(lin["head_dim"]),
        k_conv=int(lin["short_conv_kernel_size"]),
        neg_eigval=bool(model["kda_allow_neg_eigval"]),
        gate=bool(model["use_gqa_gate"]), eps=float(model["rms_norm_eps"]))


def layer_kinds(model: Dict[str, Any]) -> Sequence[str]:
    gqa = set(int(i) for i in model["gqa_layers"])
    return ["attention" if li in gqa else "kda"
            for li in range(int(model["num_hidden_layers"]))]


# -- the mixers -------------------------------------------------------------

def attention_mixer(h, att, ai, dm: Dims, quantize):
    """Gated NoPE causal grouped-query attention on h [T, d] (T a multiple
    of ``Q_BLOCK``), by blocks of query rows; weights at attention layer
    ``ai`` of the stacked leaves."""
    t = h.shape[0]
    g = dm.heads // dm.kv
    q = jnp.dot(h, _w(att["wq"], ai, quantize), precision=HI)
    k = jnp.dot(h, _w(att["wk"], ai, quantize), precision=HI)
    v = jnp.dot(h, _w(att["wv"], ai, quantize), precision=HI)
    qb = q.reshape(t // Q_BLOCK, Q_BLOCK, dm.kv, g, dm.hd)
    k = k.reshape(t, dm.kv, dm.hd)
    v = v.reshape(t, dm.kv, dm.hd)
    kpos = jnp.arange(t)
    scale = dm.hd ** -0.5

    def block(args):
        qi, i = args
        s = jnp.einsum("qkgd,tkd->kgqt", qi, k, precision=HI) * scale
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.where(kpos[None, None, None, :] > qpos[None, None, :, None],
                      -jnp.inf, s)
        return jnp.einsum("kgqt,tkd->qkgd", jax.nn.softmax(s, axis=-1), v,
                          precision=HI)

    o = jax.lax.map(block, (qb, jnp.arange(t // Q_BLOCK)))
    o = o.reshape(t, dm.heads * dm.hd)
    if dm.gate:
        o = o * jax.nn.sigmoid(
            jnp.dot(h, _w(att["wg"], ai, quantize), precision=HI))
    return jnp.dot(o, _w(att["wo"], ai, quantize), precision=HI)


def l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def kda_mixer(h, kda, ki, dm: Dims, quantize):
    """The KDA mixer on h [T, d]: the delta rule as a scan over the
    positions, from an empty state."""
    t = h.shape[0]
    nh, dk, kc = dm.k_heads, dm.k_hd, dm.k_conv
    hk = nh * dk
    qkv = jnp.dot(h, _w(kda["in_proj"], ki, quantize), precision=HI)
    w = _w(kda["conv_w"], ki, None)                     # [K, 3 * hk]
    pad = jnp.concatenate([jnp.zeros((kc - 1, 3 * hk), qkv.dtype), qkv])
    act = jax.nn.silu(sum(pad[j:j + t] * w[j] for j in range(kc)))
    q = l2norm(act[:, :hk].reshape(t, nh, dk)) * dk ** -0.5
    k = l2norm(act[:, hk:2 * hk].reshape(t, nh, dk))
    v = act[:, 2 * hk:].reshape(t, nh, dk)
    f = jnp.dot(jnp.dot(h, _w(kda["f_down"], ki, None), precision=HI),
                _w(kda["f_up"], ki, None), precision=HI)
    g = -jnp.exp(_w(kda["A_log"], ki, None))[:, None] * jax.nn.softplus(
        f + _w(kda["dt_bias"], ki, None)).reshape(t, nh, dk)
    beta = jax.nn.sigmoid(
        jnp.dot(h, _w(kda["b_proj"], ki, None), precision=HI))    # [T, H]
    if dm.neg_eigval:
        beta = 2.0 * beta

    def step(s, inp):
        qt, kt, vt, gt, bt = inp
        s = jnp.exp(gt)[:, :, None] * s                 # [H, dk, dv]
        u = vt - jnp.sum(s * kt[:, :, None], axis=1)
        s = s + (bt[:, None] * kt)[:, :, None] * u[:, None, :]
        return s, jnp.sum(s * qt[:, :, None], axis=1)

    _, o = jax.lax.scan(step, jnp.zeros((nh, dk, dk), jnp.float32),
                        (q, k, v, g, beta))
    gate = jnp.dot(jnp.dot(h, _w(kda["g_down"], ki, None), precision=HI),
                   _w(kda["g_up"], ki, None), precision=HI)
    o = rms_norm(o, _w(kda["norm"], ki, None), dm.eps) * jax.nn.sigmoid(
        gate).reshape(t, nh, dk)
    return jnp.dot(o.reshape(t, hk), _w(kda["out_proj"], ki, quantize),
                   precision=HI)


# -- the expert block -------------------------------------------------------

def routing(h, layers, li, dm: Dims):
    """(gates [T, k] float32, expert ids [T, k]) over ALL experts: scores
    ``sigmoid(logits)``, the ``top_k`` largest ``score + bias``, gates the
    chosen scores over their sum, times the scaling factor.  The router is
    never quantized (it decides the routing)."""
    logits = jnp.dot(h, _w(layers["router"], li, None), precision=HI)
    s = jax.nn.sigmoid(logits)
    _, idx = jax.lax.top_k(s + _w(layers["router_bias"], li, None), dm.top_k)
    kept = jnp.take_along_axis(s, idx, axis=-1)
    return kept / jnp.sum(kept, axis=-1, keepdims=True) * dm.routed_scale, idx


def routed_experts(h, layers, li, dm: Dims, quantize):
    """The held experts' part of the routed sum: for every token, the sum
    over its assignments that fall on experts ``offset .. offset + held -
    1`` of gate * expert(h).  ``E_BLOCK`` experts are upcast at a time."""
    gates, idx = routing(h, layers, li, dm)

    def expert(leaf, e):
        w = jax.lax.dynamic_index_in_dim(
            jax.lax.dynamic_index_in_dim(leaf, li, 0, keepdims=False),
            e, 0, keepdims=False).astype(jnp.float32)
        return fake_int8(w, axis=0) if quantize == "int8" else w

    def one(e):
        weight = jnp.sum(jnp.where(idx == e + dm.offset, gates, 0.0), axis=1)
        g = jnp.dot(h, expert(layers["e_gate"], e), precision=HI)
        u = jnp.dot(h, expert(layers["e_up"], e), precision=HI)
        y = jnp.dot(jax.nn.silu(g) * u, expert(layers["e_down"], e),
                    precision=HI)
        return weight[:, None] * y

    eb = max(n for n in range(1, E_BLOCK + 1) if dm.held % n == 0)

    def block(acc, es):
        return acc + sum(one(es[j]) for j in range(eb)), None

    out, _ = jax.lax.scan(block, jnp.zeros_like(h),
                          jnp.arange(dm.held).reshape(dm.held // eb, eb))
    return out


def shared_mlp(h, layers, li, quantize):
    g = jnp.dot(h, _w(layers["s_gate"], li, quantize), precision=HI)
    u = jnp.dot(h, _w(layers["s_up"], li, quantize), precision=HI)
    return jnp.dot(jax.nn.silu(g) * u, _w(layers["s_down"], li, quantize),
                   precision=HI)


# -- the model --------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("dm", "kind", "quantize"))
def mixer(x, layers, li, ki, *, dm: Dims, kind: str, quantize):
    """``x + mixer(norm1(x))`` of layer ``li``, the ``ki``-th of its kind."""
    h = rms_norm(x, _w(layers["attn_norm"], li, None), dm.eps)
    if kind == "attention":
        return x + attention_mixer(h, layers["attention"], ki, dm, quantize)
    return x + kda_mixer(h, layers["kda"], ki, dm, quantize)


@functools.partial(jax.jit, static_argnames=("dm", "quantize"))
def expert_block(x, layers, li, *, dm: Dims, quantize):
    """``x + experts(norm2(x)) + shared(norm2(x))`` of layer ``li``, the
    routed sum over the held experts only."""
    h = rms_norm(x, _w(layers["mlp_norm"], li, None), dm.eps)
    return x + routed_experts(h, layers, li, dm, quantize) + shared_mlp(
        h, layers, li, quantize)


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
    """Final hidden states [T_padded, d] of one sequence (before the last
    norm).  The sequence is padded to a multiple of ``Q_BLOCK``; attention
    is causal and the recurrence runs forward, so the padding touches no
    real position."""
    dm = dims(model)
    n = int(len(tokens))
    toks = np.zeros(-(-n // Q_BLOCK) * Q_BLOCK, np.int32)
    toks[:n] = tokens
    x = _embed(weights["embed"], jnp.asarray(toks), quantize)
    seen = {"attention": 0, "kda": 0}
    for li, kind in enumerate(layer_kinds(model)):
        x = mixer(x, weights["layers"], li, seen[kind], dm=dm, kind=kind,
                  quantize=quantize)
        x = expert_block(x, weights["layers"], li, dm=dm, quantize=quantize)
        seen[kind] += 1
    return x


def logits_at(weights, model: Dict[str, Any], tokens: np.ndarray,
              at: Sequence[int], quantize: Optional[str] = None):
    """Reference logits [len(at), vocab] at positions ``at`` of one
    sequence: the distribution of the token AFTER each position, over the
    held slice of the vocabulary."""
    dm = dims(model)
    x = hidden(weights, model, tokens, quantize)
    return _head(x, jnp.asarray(np.asarray(at, np.int32)),
                 weights["norm_f"], weights["head"], eps=dm.eps,
                 quantize=quantize)


def served_gaps(weights, model: Dict[str, Any], prompt: np.ndarray,
                served: Sequence[int], control: bool = False
                ) -> Dict[str, np.ndarray]:
    """As ``reference.served_gaps``: at each served position, how far the
    served token's reference logit lies below the reference's best; with
    ``control`` also the gap of the token the int8 control puts first."""
    served = np.asarray(served, np.int32)
    seq = np.concatenate([np.asarray(prompt, np.int32), served[:-1]])
    p = len(prompt)
    at = np.arange(p - 1, p - 1 + len(served))
    ref = logits_at(weights, model, seq, at)
    best = jnp.max(ref, axis=-1)
    gap = best - jnp.take_along_axis(ref, jnp.asarray(served)[:, None],
                                     axis=-1)[:, 0]
    out = {"gap": np.asarray(gap, np.float64)}
    if control:
        low = logits_at(weights, model, seq, at, quantize="int8")
        pick = jnp.argmax(low, axis=-1)
        cgap = best - jnp.take_along_axis(ref, pick[:, None], axis=-1)[:, 0]
        out["control_gap"] = np.asarray(cgap, np.float64)
    return out
