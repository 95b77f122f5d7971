"""The plain reference of Granite-4.0-H's block (``granitemoehybrid``): Mamba-2
layers beside NoPE attention layers, every layer followed by the same expert
block, in straightforward ``jax.numpy``, float32, every matmul at
``Precision.HIGHEST``.  No kernel, no cache, no batching, no chunking: the
recurrence is written as the recurrence, a scan over positions.  It imports
nothing of the program (``benchmark/reference.py``'s pieces only) and takes
the benchmark's own weights (``granite_hybrid.make_weights``).

With ``r = residual_multiplier`` and RMSNorm at ``rms_norm_eps``:

* model: ``x = embed[tok] * embedding_multiplier``; per layer ``x = x + r *
  mixer(norm1(x))``, then ``h = norm2(x)``, ``x = x + r * (experts(h) +
  shared(h))``; ``logits = norm_f(x) @ embed.T / logits_scaling`` (tied);
* attention layer: q, k, v, o without bias, ``num_attention_heads`` query and
  ``num_key_value_heads`` K/V heads of ``head_dim``, NO positional embedding,
  causal softmax of ``attention_multiplier * q . k``;
* mamba layer: ``in_proj -> [z | xBC | dt]`` (``d_inner = mamba_n_heads *
  mamba_d_head``, one B/C group of ``mamba_d_state``), ``xBC = silu(causal
  depthwise conv of mamba_d_conv taps + bias)`` split ``x [T, H, P]``, ``B``,
  ``C [T, N]``; ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``;
  ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T``, ``y_t = S_t C_t + D x_t``;
  ``y = rmsnorm(y * silu(z)) * g`` (the gate before the norm, one group over
  ``d_inner``); ``out_proj``;
* expert block: router ``hidden -> E`` (all ``published`` experts), float32
  logits, the ``num_experts_per_tok`` largest, softmax over those kept;
  expert ``e``: ``silu(h Wg[e]) * (h Wu[e])`` then ``Wd[e]``; the shared MLP
  the same at ``shared_intermediate_size``, always on.

**The share.**  The configuration holds ``num_local_experts`` of the
``published`` count in every layer, the experts ``expert_shard *
num_local_experts`` onward of a deployment that divides each layer's experts
over ``expert_parallel`` chips.  The router keeps its published width; what
the experts held elsewhere would add is left out here exactly as in the
program, and that partial result goes on to the next layer.
``routed_experts``, ``shared_mlp`` and ``mixer`` are separate functions so
that a test can add the shares up against the uncut layer.

``quantize="int8"`` is the control of ``correct``, as in ``reference.py``:
every matmul weight and the embedding rounded to int8.
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


class Dims(NamedTuple):
    d: int
    heads: int
    kv: int
    hd: int
    f: int              # one expert's width
    shared: int         # the shared MLP's width
    experts: int        # the router's width (published)
    held: int           # experts held here
    offset: int         # the first of them
    top_k: int
    m_heads: int
    m_hd: int
    m_state: int
    m_conv: int
    eps: float
    attn_scale: float
    embed_mult: float
    resid_mult: float
    logits_div: float


def dims(model: Dict[str, Any]) -> Dims:
    pub = model.get("published", {})
    held = int(model["num_local_experts"])
    dep = model.get("deployment", {})
    if int(model["mamba_n_groups"]) != 1:
        raise ValueError("the reference shares B and C over every head "
                         "(mamba_n_groups 1)")
    if (int(model["mamba_expand"]) * int(model["hidden_size"])
            != int(model["mamba_n_heads"]) * int(model["mamba_d_head"])):
        raise ValueError("mamba_expand * hidden_size is not mamba_n_heads * "
                         "mamba_d_head")
    d = int(model["hidden_size"])
    heads = int(model["num_attention_heads"])
    return Dims(
        d=d, heads=heads, kv=int(model["num_key_value_heads"]),
        hd=int(model.get("head_dim") or d // heads),
        f=int(model["intermediate_size"]),
        shared=int(model["shared_intermediate_size"]),
        experts=int(pub.get("num_local_experts", held)), held=held,
        offset=int(dep.get("expert_shard", 0)) * held,
        top_k=int(model["num_experts_per_tok"]),
        m_heads=int(model["mamba_n_heads"]), m_hd=int(model["mamba_d_head"]),
        m_state=int(model["mamba_d_state"]),
        m_conv=int(model["mamba_d_conv"]),
        eps=float(model["rms_norm_eps"]),
        attn_scale=float(model["attention_multiplier"]),
        embed_mult=float(model["embedding_multiplier"]),
        resid_mult=float(model["residual_multiplier"]),
        logits_div=float(model["logits_scaling"]))


def layer_kinds(model: Dict[str, Any]) -> Sequence[str]:
    kinds = list(model["layer_types"])
    if len(kinds) != int(model["num_hidden_layers"]):
        raise ValueError("layer_types does not list num_hidden_layers")
    return kinds


# -- the mixers -------------------------------------------------------------

def attention_mixer(h, att, ai, dm: Dims, quantize):
    """NoPE causal grouped-query attention on h [T, d] (T a multiple of
    ``Q_BLOCK``), by blocks of query rows; weights at attention layer
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

    def block(args):
        qi, i = args
        s = jnp.einsum("qkgd,tkd->kgqt", qi, k, precision=HI) * dm.attn_scale
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.where(kpos[None, None, None, :] > qpos[None, None, :, None],
                      -jnp.inf, s)
        return jnp.einsum("kgqt,tkd->qkgd", jax.nn.softmax(s, axis=-1), v,
                          precision=HI)

    o = jax.lax.map(block, (qb, jnp.arange(t // Q_BLOCK)))
    return jnp.dot(o.reshape(t, dm.heads * dm.hd),
                   _w(att["wo"], ai, quantize), precision=HI)


def mamba_mixer(h, mam, mi, dm: Dims, quantize):
    """The Mamba-2 mixer on h [T, d]: the recurrence as a scan over the
    positions, from an empty state."""
    t = h.shape[0]
    di, n, kc = dm.m_heads * dm.m_hd, dm.m_state, dm.m_conv
    proj = jnp.dot(h, _w(mam["in_proj"], mi, quantize), precision=HI)
    z, xbc, dt = (proj[:, :di], proj[:, di:2 * di + 2 * n],
                  proj[:, 2 * di + 2 * n:])
    w = _w(mam["conv_w"], mi, None)                     # [K, C]
    pad = jnp.concatenate([jnp.zeros((kc - 1, xbc.shape[1]), xbc.dtype), xbc])
    conv = _w(mam["conv_b"], mi, None) + sum(
        pad[j:j + t] * w[j] for j in range(kc))
    act = jax.nn.silu(conv)
    x = act[:, :di].reshape(t, dm.m_heads, dm.m_hd)
    b, c = act[:, di:di + n], act[:, di + n:]
    dt = jax.nn.softplus(dt + _w(mam["dt_bias"], mi, None))    # [T, H]
    a = -jnp.exp(_w(mam["A_log"], mi, None))                   # [H]

    def step(s, inp):
        xt, bt, ct, dtt = inp
        s = (jnp.exp(dtt * a)[:, None, None] * s
             + (dtt[:, None] * xt)[:, :, None] * bt[None, None, :])
        return s, jnp.sum(s * ct[None, None, :], axis=-1)

    _, y = jax.lax.scan(step, jnp.zeros((dm.m_heads, dm.m_hd, n),
                                        jnp.float32), (x, b, c, dt))
    y = y + _w(mam["D"], mi, None)[:, None] * x
    y = rms_norm(y.reshape(t, di) * jax.nn.silu(z),
                 _w(mam["norm"], mi, None), dm.eps)
    return jnp.dot(y, _w(mam["out_proj"], mi, quantize), precision=HI)


# -- the expert block -------------------------------------------------------

def routing(h, layers, li, dm: Dims):
    """(gates [T, k] float32, expert ids [T, k]) over ALL experts: the
    ``top_k`` largest router logits, softmax over those kept.  The router
    is never quantized (it decides the routing)."""
    logits = jnp.dot(h, _w(layers["router"], li, None), precision=HI)
    vals, idx = jax.lax.top_k(logits, dm.top_k)
    return jax.nn.softmax(vals, axis=-1), idx


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
    """``x + r * mixer(norm1(x))`` of layer ``li``, the ``ki``-th of its
    kind."""
    h = rms_norm(x, _w(layers["attn_norm"], li, None), dm.eps)
    if kind == "attention":
        y = attention_mixer(h, layers["attention"], ki, dm, quantize)
    else:
        y = mamba_mixer(h, layers["mamba"], ki, dm, quantize)
    return x + dm.resid_mult * y


@functools.partial(jax.jit, static_argnames=("dm", "quantize"))
def expert_block(x, layers, li, *, dm: Dims, quantize):
    """``x + r * (experts(norm2(x)) + shared(norm2(x)))`` of layer ``li``,
    the routed sum over the held experts only."""
    h = rms_norm(x, _w(layers["mlp_norm"], li, None), dm.eps)
    y = routed_experts(h, layers, li, dm, quantize) + shared_mlp(
        h, layers, li, quantize)
    return x + dm.resid_mult * y


@functools.partial(jax.jit, static_argnames=("mult", "quantize"))
def _embed(embed, tokens, mult, quantize):
    rows = jnp.take(embed, tokens, axis=0).astype(jnp.float32)
    if quantize == "int8":
        rows = fake_int8(rows, axis=1)
    return rows * mult


@functools.partial(jax.jit, static_argnames=("eps", "div", "quantize"))
def _head(x, at, norm_f, embed, *, eps, div, quantize):
    h = rms_norm(jnp.take(x, at, axis=0), norm_f.astype(jnp.float32), eps)
    w = embed.astype(jnp.float32)
    if quantize == "int8":
        w = fake_int8(w, axis=1)        # one scale per vocabulary row
    return jnp.einsum("td,vd->tv", h, w, precision=HI) / div


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
    x = _embed(weights["embed"], jnp.asarray(toks), dm.embed_mult, quantize)
    seen = {"attention": 0, "mamba": 0}
    for li, kind in enumerate(layer_kinds(model)):
        x = mixer(x, weights["layers"], li, seen[kind], dm=dm, kind=kind,
                  quantize=quantize)
        x = expert_block(x, weights["layers"], li, dm=dm, quantize=quantize)
        seen[kind] += 1
    return x


def logits_at(weights, model: Dict[str, Any], tokens: np.ndarray,
              at: Sequence[int], quantize: Optional[str] = None):
    """Reference logits [len(at), vocab] at positions ``at`` of one
    sequence: the distribution of the token AFTER each position."""
    dm = dims(model)
    x = hidden(weights, model, tokens, quantize)
    return _head(x, jnp.asarray(np.asarray(at, np.int32)),
                 weights["norm_f"], weights["embed"], eps=dm.eps,
                 div=dm.logits_div, quantize=quantize)


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
