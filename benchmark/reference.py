"""The plain reference: the Mistral decoder block in straightforward
``jax.numpy``, float32, every matmul at ``Precision.HIGHEST``.

RMSNorm (eps from the published config), grouped-query attention with
rotary embeddings (half-split pairing, ``rope_theta`` from the config, the
Hugging Face convention), SwiGLU, untied head.  No kernels, no cache, no
batching.  It imports nothing of the program and takes nothing the program
has made: the weights are the benchmark's own (``weights.py``).  It walks
the stacked ``[L, ...]`` leaves one layer at a time and upcasts that layer
only, so the full width fits beside the served weights.

Departure from the program, on purpose: the program's ``rms_norm`` has
eps 1e-6 built in, the published config says 1e-5 and the reference follows
the config.  At unit-scale activations the two differ by ~5e-6 relative.

``quantize="int8"`` is the control of ``correct``: the same mathematics
with every matmul weight and the embedding rounded to int8 (symmetric, one
scale per output channel), the precision below the bf16 the configuration
states.  It must come out as NOT correct.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import costs

HI = jax.lax.Precision.HIGHEST
Q_BLOCK = 512       # attention is computed in blocks of query rows


def rms_norm(x, gain, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * gain


def rope(x, positions, theta):
    """x [T, H, D]; pairs (i, i + D/2) rotate by pos * theta^(-2i/D)."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def fake_int8(w, axis):
    """Round to int8 with one scale per slice along every axis but
    ``axis`` (the contraction axis), and return the float32 value."""
    scale = jnp.max(jnp.abs(w), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return jnp.clip(jnp.round(w / scale), -127, 127) * scale


def _w(leaf, li, quantize):
    w = jax.lax.dynamic_index_in_dim(leaf, li, 0, keepdims=False)
    w = w.astype(jnp.float32)
    if quantize == "int8" and w.ndim == 2:
        w = fake_int8(w, axis=0)
    return w


def attention(q, k, v, heads, kv):
    """Causal grouped-query attention, plain softmax, by query blocks.
    q [T, heads, D], k/v [T, kv, D]; T a multiple of Q_BLOCK."""
    t, _, hd = q.shape
    g = heads // kv
    qb = q.reshape(t // Q_BLOCK, Q_BLOCK, kv, g, hd)
    kpos = jnp.arange(t)

    def block(args):
        qi, i = args
        s = jnp.einsum("qkgd,tkd->kgqt", qi, k, precision=HI)
        s = s / jnp.sqrt(jnp.float32(hd))
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.where(kpos[None, None, None, :] > qpos[None, None, :, None],
                      -jnp.inf, s)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("kgqt,tkd->qkgd", p, v, precision=HI)

    o = jax.lax.map(block, (qb, jnp.arange(t // Q_BLOCK)))
    return o.reshape(t, heads * hd)


@functools.partial(jax.jit, static_argnames=("dims", "eps", "theta",
                                             "quantize"))
def layer(x, layers, li, *, dims, eps, theta, quantize):
    """One decoder block on x [T, d] float32, weights from the stacked
    leaves at layer ``li``."""
    d, heads, kv, hd = dims
    t = x.shape[0]
    pos = jnp.arange(t)
    h = rms_norm(x, _w(layers["attn_norm"], li, None), eps)
    q = jnp.dot(h, _w(layers["wq"], li, quantize), precision=HI)
    k = jnp.dot(h, _w(layers["wk"], li, quantize), precision=HI)
    v = jnp.dot(h, _w(layers["wv"], li, quantize), precision=HI)
    q = rope(q.reshape(t, heads, hd), pos, theta)
    k = rope(k.reshape(t, kv, hd), pos, theta)
    o = attention(q, k, v.reshape(t, kv, hd), heads, kv)
    x = x + jnp.dot(o, _w(layers["wo"], li, quantize), precision=HI)
    h = rms_norm(x, _w(layers["mlp_norm"], li, None), eps)
    gate = jnp.dot(h, _w(layers["w_gate"], li, quantize), precision=HI)
    up = jnp.dot(h, _w(layers["w_up"], li, quantize), precision=HI)
    ffn = jnp.dot(jax.nn.silu(gate) * up, _w(layers["w_down"], li, quantize),
                  precision=HI)
    return x + ffn


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
    norm).  The sequence is padded to a multiple of Q_BLOCK; attention is
    causal, so the padding touches no real position."""
    n = int(len(tokens))
    t = -(-n // Q_BLOCK) * Q_BLOCK
    toks = np.zeros(t, np.int32)
    toks[:n] = tokens
    x = _embed(weights["embed"], jnp.asarray(toks), quantize)
    dims = tuple(costs.dims(model)[:4])     # d, heads, kv, hd
    for li in range(int(model["num_hidden_layers"])):
        x = layer(x, weights["layers"], li, dims=dims,
                  eps=float(model["rms_norm_eps"]),
                  theta=float(model["rope_theta"]), quantize=quantize)
    return x


def logits_at(weights, model: Dict[str, Any], tokens: np.ndarray,
              at: Sequence[int], quantize: Optional[str] = None):
    """Reference logits [len(at), vocab] at positions ``at`` of one
    sequence: the distribution of the token AFTER each position."""
    x = hidden(weights, model, tokens, quantize)
    return _head(x, jnp.asarray(np.asarray(at, np.int32)),
                 weights["norm_f"], weights["head"],
                 eps=float(model["rms_norm_eps"]), quantize=quantize)


def served_gaps(weights, model: Dict[str, Any], prompt: np.ndarray,
                served: Sequence[int], control: bool = False
                ) -> Dict[str, np.ndarray]:
    """For one finished request: at each served position, how far the
    served token's reference logit lies below the reference's best
    (``gap``, >= 0; 0 where the served token is the reference's own).
    With ``control``, also the gap of the token that the int8 control puts
    first at that position (``control_gap``)."""
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
