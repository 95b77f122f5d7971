"""The plain reference of EvaByte's block: EVA chunked attention (Zheng et
al., "Efficient Attention via Control Variates"; EvaByte's public
``eva.py``) in straightforward ``jax.numpy``, float32, every matmul at
``Precision.HIGHEST``.  No kernel, no cache, no batching; it imports nothing
of the program (``benchmark/reference.py``'s pieces only) and takes the
benchmark's own weights (``evabyte.make_weights``).

The layer, per head ``h`` (``d`` = head size, ``s = d**-0.5``, ``c`` =
``chunk_size``, ``W`` = ``window_size``, ``w(i) = i // W``), with ``q_i``,
``k_i`` rotated by RoPE at their absolute positions:

* chunk ``j`` covers positions ``c j .. c j + c - 1``; with ``a`` the softmax
  over the chunk's positions of ``s * phi_h . k_m``, its summary is
  ``k~_j = sum_m a_m k_m + mu_h`` and ``v~_j = sum_m a_m v_m`` (``phi``,
  ``mu`` learned per layer and head; ASSUMED: this pooling, and that the
  summaries are built from rotated keys);
* ``o_i`` = softmax over ``S_i`` of ``s * q_i . key`` applied to the values,
  ``S_i`` = the positions ``m <= i`` of ``i``'s own window (exact) together
  with every chunk of every earlier window (``c j < W w(i)``), under one
  normaliser.  For ``i < W`` this is plain causal attention;
* the block is pre-norm, ``x + Wo EVA(norm(x))`` then ``x + SwiGLU(norm(x))``,
  ``norm(x) = x * rsqrt(mean x^2 + eps) * (1 + g)`` (the unit offset); the
  head is ``[hidden, num_pred_heads * vocab]`` and head 0 is the next byte
  (ASSUMED: the order of the heads in the matrix).  All heads are computed.

Two forms of the attention, which a test holds to each other: the equations
as one mask over ``[T, T + T / c]`` (every position beside every chunk; small
sizes), and window by window (at the published widths, so that 32 heads of
26 K positions fit).  ``quantize="int8"`` is the control of ``correct``, as in
``reference.py``: matmul weights and the embedding rounded to int8.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import costs
from benchmark.reference import HI, _w, fake_int8, rms_norm, rope

Q_BLOCK = 512       # queries of one window are attended in blocks of rows
DENSE_MAX = 1024    # up to here the one-mask form is used


def summaries(k, v, phi, mu, chunk):
    """k, v [T, H, D] (T a multiple of ``chunk``); phi, mu [H, D].  One
    (key, value) pair per chunk: [T / chunk, H, D] each."""
    t, h, d = k.shape
    kc = k.reshape(t // chunk, chunk, h, d)
    vc = v.reshape(t // chunk, chunk, h, d)
    a = jax.nn.softmax(
        jnp.einsum("jchd,hd->jch", kc, phi, precision=HI)
        / jnp.sqrt(jnp.float32(d)), axis=1)[..., None]
    return jnp.sum(a * kc, axis=1) + mu[None], jnp.sum(a * vc, axis=1)


def attention_dense(q, k, v, ks, vs, chunk, window):
    """The equations as one mask over [T, T + T / chunk]."""
    t, h, d = q.shape
    i = jnp.arange(t)[:, None]
    m = jnp.arange(t)[None]
    j = jnp.arange(t // chunk)[None]
    exact = (m // window == i // window) & (m <= i)
    pooled = chunk * j < window * (i // window)
    keys = jnp.concatenate([k, ks], axis=0)
    vals = jnp.concatenate([v, vs], axis=0)
    s = jnp.einsum("ihd,mhd->him", q, keys, precision=HI)
    s = s / jnp.sqrt(jnp.float32(d))
    s = jnp.where(jnp.concatenate([exact, pooled], axis=1)[None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("him,mhd->ihd", p, vals, precision=HI).reshape(t, h * d)


def attention_windows(q, k, v, ks, vs, chunk, window):
    """The same, a block of one window's queries at a time: its window's
    positions up to each query, and the chunks of the windows before."""
    t, h, d = q.shape
    qb = min(Q_BLOCK, window)
    per = window // chunk
    n_w = t // window
    kw = k.reshape(n_w, window, h, d)
    vw = v.reshape(n_w, window, h, d)
    jj = jnp.arange(ks.shape[0])

    def block(b):
        w, r = b // (window // qb), b % (window // qb)
        qi = jax.lax.dynamic_slice_in_dim(q, b * qb, qb, 0)
        keys = jnp.concatenate([kw[w], ks], axis=0)
        vals = jnp.concatenate([vw[w], vs], axis=0)
        s = jnp.einsum("ihd,mhd->him", qi, keys, precision=HI)
        s = s / jnp.sqrt(jnp.float32(d))
        ipos = r * qb + jnp.arange(qb)[:, None]
        ok = jnp.concatenate(
            [jnp.arange(window)[None] <= ipos,
             jnp.broadcast_to(jj[None] < w * per, (qb, jj.shape[0]))], axis=1)
        s = jnp.where(ok[None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("him,mhd->ihd", p, vals, precision=HI)

    o = jax.lax.map(block, jnp.arange(t // qb))
    return o.reshape(t, h * d)


@functools.partial(jax.jit, static_argnames=(
    "dims", "eps", "theta", "chunk", "window", "quantize"))
def layer(x, layers, li, *, dims, eps, theta, chunk, window, quantize):
    """One block on x [T, d] float32 (T a multiple of ``window``, or at
    most one window and a multiple of ``chunk``), weights from the stacked
    leaves at layer ``li``."""
    d, heads, kv, hd = dims
    t = x.shape[0]
    pos = jnp.arange(t)
    h = rms_norm(x, 1.0 + _w(layers["attn_norm"], li, None), eps)
    q = jnp.dot(h, _w(layers["wq"], li, quantize), precision=HI)
    k = jnp.dot(h, _w(layers["wk"], li, quantize), precision=HI)
    v = jnp.dot(h, _w(layers["wv"], li, quantize), precision=HI)
    q = rope(q.reshape(t, heads, hd), pos, theta)
    k = rope(k.reshape(t, kv, hd), pos, theta)
    v = v.reshape(t, kv, hd)
    ks, vs = summaries(k, v, _w(layers["eva_phi"], li, None),
                       _w(layers["eva_mu"], li, None), chunk)
    attend = attention_dense if t <= DENSE_MAX else attention_windows
    o = attend(q, k, v, ks, vs, chunk, window)
    x = x + jnp.dot(o, _w(layers["wo"], li, quantize), precision=HI)
    h = rms_norm(x, 1.0 + _w(layers["mlp_norm"], li, None), eps)
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
    h = rms_norm(jnp.take(x, at, axis=0), 1.0 + norm_f.astype(jnp.float32),
                 eps)
    w = head.astype(jnp.float32)
    if quantize == "int8":
        w = fake_int8(w, axis=0)
    return jnp.dot(h, w, precision=HI)


def padded_len(n: int, chunk: int, window: int) -> int:
    """Positions the forward pass runs over: whole chunks inside the first
    window, whole windows past it.  Attention is causal and a position's
    summaries are of closed windows only, so the padding reaches no real
    position."""
    if n <= min(window, DENSE_MAX):
        return -(-n // chunk) * chunk
    return -(-n // window) * window


def hidden(weights, model: Dict[str, Any], tokens: np.ndarray,
           quantize: Optional[str] = None):
    """Final hidden states [T_padded, d] of one sequence (before the last
    norm)."""
    n = int(len(tokens))
    chunk, window = int(model["chunk_size"]), int(model["window_size"])
    toks = np.zeros(padded_len(n, chunk, window), np.int32)
    toks[:n] = tokens
    x = _embed(weights["embed"], jnp.asarray(toks), quantize)
    dims = tuple(costs.dims(model)[:4])     # d, heads, kv, hd
    for li in range(int(model["num_hidden_layers"])):
        x = layer(x, weights["layers"], li, dims=dims,
                  eps=float(model["rms_norm_eps"]),
                  theta=float(model["rope_theta"]), chunk=chunk,
                  window=window, quantize=quantize)
    return x


def logits_at(weights, model: Dict[str, Any], tokens: np.ndarray,
              at: Sequence[int], quantize: Optional[str] = None):
    """Reference logits [len(at), num_pred_heads, vocab] at positions
    ``at`` of one sequence: head ``p`` is the distribution of the byte
    ``p + 1`` after each position."""
    x = hidden(weights, model, tokens, quantize)
    out = _head(x, jnp.asarray(np.asarray(at, np.int32)),
                weights["norm_f"], weights["head"],
                eps=float(model["rms_norm_eps"]), quantize=quantize)
    return out.reshape(len(at), int(model["num_pred_heads"]),
                       int(model["vocab_size"]))


def served_gaps(weights, model: Dict[str, Any], prompt: np.ndarray,
                served: Sequence[int], control: bool = False
                ) -> Dict[str, np.ndarray]:
    """As ``reference.served_gaps``, on head 0 (the next byte, which is
    what the program decodes): at each served position, how far the served
    token's reference logit lies below the reference's best; with
    ``control`` also the gap of the token the int8 control puts first."""
    served = np.asarray(served, np.int32)
    seq = np.concatenate([np.asarray(prompt, np.int32), served[:-1]])
    p = len(prompt)
    at = np.arange(p - 1, p - 1 + len(served))
    ref = logits_at(weights, model, seq, at)[:, 0]
    best = jnp.max(ref, axis=-1)
    gap = best - jnp.take_along_axis(ref, jnp.asarray(served)[:, None],
                                     axis=-1)[:, 0]
    out = {"gap": np.asarray(gap, np.float64)}
    if control:
        low = logits_at(weights, model, seq, at, quantize="int8")[:, 0]
        pick = jnp.argmax(low, axis=-1)
        cgap = best - jnp.take_along_axis(ref, pick[:, None], axis=-1)[:, 0]
        out["control_gap"] = np.asarray(cgap, np.float64)
    return out
