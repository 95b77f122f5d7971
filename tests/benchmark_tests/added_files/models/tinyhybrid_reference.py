"""The plain reference of the tiny hybrid decoder (``tinyhybrid.py``):
float32, every matmul at ``Precision.HIGHEST``, no cache, no batching; the
pieces two blocks share (RMSNorm, rotary embedding, causal GQA, the int8
rounding of the control) come from ``benchmark/reference.py``, the
yardstick's own.  Per layer ``li``:

    if li % attention_every == 0:   x = x + Attn(RMSNorm(x)) Wo
    x = x + SwiGLU(RMSNorm(x))
    logits = RMSNorm(x) E^T / sqrt(d)          (E the embedding: tied)
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import (HI, Q_BLOCK, attention, fake_int8, rms_norm,
                                 rope)


def _w(leaf, li, quantize):
    w = leaf[li].astype(jnp.float32)
    return fake_int8(w, axis=0) if quantize == "int8" else w


def logits_at(weights, model: Dict[str, Any], tokens: np.ndarray,
              at: Sequence[int], quantize: Optional[str] = None):
    n = int(len(tokens))
    t = -(-n // Q_BLOCK) * Q_BLOCK
    toks = np.zeros(t, np.int32)
    toks[:n] = tokens
    d, heads, kv, hd = (int(model[k]) for k in (
        "hidden_size", "num_attention_heads", "num_key_value_heads",
        "head_dim"))
    eps, theta = float(model["rms_norm_eps"]), float(model["rope_theta"])
    embed = weights["embed"].astype(jnp.float32)
    if quantize == "int8":
        embed = fake_int8(embed, axis=1)
    lw = weights["layers"]
    x = embed[jnp.asarray(toks)]
    pos = jnp.arange(t)
    for li in range(int(model["num_hidden_layers"])):
        if li % int(model["attention_every"]) == 0:
            h = rms_norm(x, lw["attn_norm"][li].astype(jnp.float32), eps)
            q = jnp.dot(h, _w(lw["wq"], li, quantize), precision=HI)
            k = jnp.dot(h, _w(lw["wk"], li, quantize), precision=HI)
            v = jnp.dot(h, _w(lw["wv"], li, quantize), precision=HI)
            o = attention(rope(q.reshape(t, heads, hd), pos, theta),
                          rope(k.reshape(t, kv, hd), pos, theta),
                          v.reshape(t, kv, hd), heads, kv)
            x = x + jnp.dot(o, _w(lw["wo"], li, quantize), precision=HI)
        h = rms_norm(x, lw["mlp_norm"][li].astype(jnp.float32), eps)
        gate = jnp.dot(h, _w(lw["w_gate"], li, quantize), precision=HI)
        up = jnp.dot(h, _w(lw["w_up"], li, quantize), precision=HI)
        x = x + jnp.dot(jax.nn.silu(gate) * up,
                        _w(lw["w_down"], li, quantize), precision=HI)
    h = rms_norm(x[jnp.asarray(np.asarray(at, np.int32))],
                 weights["norm_f"].astype(jnp.float32), eps)
    return jnp.dot(h, embed.T, precision=HI) / jnp.sqrt(jnp.float32(d))


def served_gaps(weights, model: Dict[str, Any], prompt: np.ndarray,
                served: Sequence[int], control: bool = False
                ) -> Dict[str, np.ndarray]:
    """As ``benchmark/reference.py:served_gaps``: per served token how far
    its reference logit lies below the reference's best, and with
    ``control`` the same for the token the int8 control puts first."""
    served = np.asarray(served, np.int32)
    seq = np.concatenate([np.asarray(prompt, np.int32), served[:-1]])
    at = np.arange(len(prompt) - 1, len(prompt) - 1 + len(served))
    ref = logits_at(weights, model, seq, at)
    best = jnp.max(ref, axis=-1)

    def below_best(tokens):
        return np.asarray(best - jnp.take_along_axis(
            ref, jnp.asarray(tokens)[:, None], axis=-1)[:, 0], np.float64)

    out = {"gap": below_best(served)}
    if control:
        low = logits_at(weights, model, seq, at, quantize="int8")
        out["control_gap"] = below_best(jnp.argmax(low, axis=-1))
    return out
