"""The model adapter of EvaByte's block: RMSNorm with the unit offset and a
stated epsilon, rotary multi-head attention of kind EVA (``attention_class:
"eva"``: exact inside a window of ``window_size``, one summary per chunk of
``chunk_size`` of every earlier window), SwiGLU, a float32 residual stream
and float32 logits, an untied head of ``num_pred_heads`` byte vocabularies.
Its plain reference is ``evabyte_reference.py``; README.md lists what an
adapter defines.

What differs from ``mistral.py`` for the readers: the paged pool holds cache
ENTRIES, not context positions.  A row at context ``T`` holds
``cache_entries(config, T)`` of them (``window_size / chunk_size`` per closed
window, and the current window's positions), so
``kv_bytes_per_context_token`` is the bytes of one ENTRY here, and a reader
that multiplies it by context positions (``pool_fill``,
``paged_decode_roofline``) would read several times too high: the cell's own
readers (``layer_metrics/eva_*.py``) do the arithmetic through
``cache_entries`` and ``decode_read_bytes``.
"""

from __future__ import annotations

import math
from typing import Any, Dict, List, Sequence

from benchmark import costs
from benchmark.models.evabyte_reference import served_gaps

__all__ = ["program_config", "make_weights", "int8_program_weights",
           "served_gaps", "kv_bytes_per_context_token", "pool_leaf_shapes",
           "paged_kernel_shape", "token_slots", "cache_entries",
           "decode_read_bytes"]


def program_config(config: Dict[str, Any], max_len: int):
    """What ``ContinuousBatcher`` is built with.  A program that has no
    EVA attention cannot run the configuration, and says so at once."""
    import dataclasses

    import jax.numpy as jnp
    from tfmesos_tpu.models.transformer import TransformerConfig
    fields = {f.name for f in dataclasses.fields(TransformerConfig)}
    need = {"attention", "eva_chunk", "eva_window", "norm_eps", "norm_offset",
            "residual_dtype", "logits_dtype", "n_pred_heads"}
    if not need <= fields:
        raise SystemExit(
            f"benchmark: this program's TransformerConfig has no "
            f"{sorted(need - fields)}: it cannot run attention_class "
            f"{config['attention_class']!r}")
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
        config["torch_dtype"]]
    return TransformerConfig(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        n_layers=config["num_hidden_layers"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        d_ff=config["intermediate_size"], max_seq_len=max_len,
        rope_theta=float(config["rope_theta"]), dtype=dtype,
        param_dtype=dtype, attention=config["attention_class"],
        eva_chunk=config["chunk_size"], eva_window=config["window_size"],
        norm_eps=float(config["rms_norm_eps"]),
        norm_offset=bool(config["norm_add_unit_offset"]),
        residual_dtype=jnp.float32 if config["fp32_skip_add"] else None,
        logits_dtype=jnp.float32 if config["fp32_logits"] else None,
        n_pred_heads=config["num_pred_heads"])


def shapes(config: Dict[str, Any]) -> Dict[str, Any]:
    """Every drawn leaf with its scale: ``weights.py``'s seven stacked
    dense leaves, the head over all prediction heads, and EVA's pooling
    query ``phi`` and key offset ``mu`` at unit scale (so that a chunk's
    pooling weights are far from uniform)."""
    d, heads, kv, hd, f, l, v = costs.dims(config)
    res = 1.0 / math.sqrt(2 * l)
    return {
        "embed": ((v, d), 1.0),
        "head": ((d, int(config["num_pred_heads"]) * v), 1 / math.sqrt(d)),
        "layers": {
            "wq": ((l, d, heads * hd), 1 / math.sqrt(d)),
            "wk": ((l, d, kv * hd), 1 / math.sqrt(d)),
            "wv": ((l, d, kv * hd), 1 / math.sqrt(d)),
            "wo": ((l, heads * hd, d), res / math.sqrt(heads * hd)),
            "w_gate": ((l, d, f), 1 / math.sqrt(d)),
            "w_up": ((l, d, f), 1 / math.sqrt(d)),
            "w_down": ((l, f, d), res / math.sqrt(f)),
            "eva_phi": ((l, kv, hd), 1.0),
            "eva_mu": ((l, kv, hd), 1.0),
        },
    }


def make_weights(config: Dict[str, Any], seed: int, dtype=None,
                 out_shardings=None):
    """The whole tree in one jitted call, from the seed, as ``weights.py``
    makes Mistral's (the chip's own bit generator, stacked leaves a layer
    at a time).  Norm gains are drawn near 0: with the unit offset the norm
    scales by ``1 + g``."""
    import jax
    import jax.numpy as jnp
    dtype = dtype or jnp.bfloat16
    sh = shapes(config)
    dm = costs.dims(config)

    def build(key):
        keys = iter(jax.random.split(key, 16))

        def draw(shape_scale):
            shape, scale = shape_scale
            k = next(keys)
            if len(shape) == 3:
                x = jax.lax.map(
                    lambda kk: jax.random.normal(kk, shape[1:], dtype),
                    jax.random.split(k, shape[0]))
            else:
                x = jax.random.normal(k, shape, dtype)
            return x * jnp.asarray(scale, dtype)

        def gain(shape):
            return (0.1 * jax.random.normal(next(keys), shape,
                                            jnp.float32)).astype(dtype)

        layers = {k: draw(v) for k, v in sorted(sh["layers"].items())}
        layers["attn_norm"] = gain((dm.layers, dm.d))
        layers["mlp_norm"] = gain((dm.layers, dm.d))
        return {"embed": draw(sh["embed"]), "layers": layers,
                "norm_f": gain((dm.d,)), "head": draw(sh["head"])}

    key = jax.random.key(int(seed) % (2 ** 63), impl="rbg")
    return jax.jit(build, out_shardings=out_shardings)(key)


def int8_program_weights(cfg, weights):
    """The program's own weight-only int8 path: what ``control.py
    --program-int8 1`` serves from, and ``correct`` has to refuse."""
    from tfmesos_tpu.models.transformer import quantize_params
    return quantize_params(cfg, weights)


def cache_entries(config: Dict[str, Any], context_len):
    """Cache entries a row at context ``context_len`` holds (a number or a
    numpy array): ``window_size / chunk_size`` summaries per closed window
    and the current window's positions."""
    w = int(config["window_size"])
    return (context_len // w * (w // int(config["chunk_size"]))
            + context_len % w)


def kv_bytes_per_context_token(config: Dict[str, Any],
                               itemsize: int = 2) -> int:
    """Bytes of one cache ENTRY over all layers (a summary or an exact
    position: the same K/V shape).  Multiply by ``cache_entries``, not by
    context positions."""
    return costs.kv_bytes_per_context_token(config, itemsize)


def decode_read_bytes(config: Dict[str, Any], context_lens: Sequence[int],
                      itemsize: int = 2) -> int:
    """Bytes of cached entries the paged-decode kernel has to read for one
    decode step of each row in ``context_lens`` (its context before the
    step): the kernel's bytes, for its share of the HBM roofline.  It does
    no operation per byte worth counting beside them (q_per_kv = 1)."""
    per = kv_bytes_per_context_token(config, itemsize)
    return int(sum(int(cache_entries(config, int(t))) for t in context_lens)
               * per)


def pool_leaf_shapes(config: Dict[str, Any], counters: Dict[str, int]
                     ) -> List[List[int]]:
    """The shapes a whole-pool copy would have: a K or V leaf of the pool,
    ``[layers, pages, kv_heads, page, head_dim]``, and one layer of it."""
    m = costs.dims(config)
    pool = [m.layers, counters["n_pages"], m.kv, counters["page_size"], m.hd]
    return [pool, pool[1:]]


def paged_kernel_shape(config: Dict[str, Any], rows: int) -> List[int]:
    """The paged-decode kernel's output: ``[rows, kv_heads, q_per_kv,
    head_dim]`` (``[rows, 32, 1, 128]``: multi-head, one query per head)."""
    m = costs.dims(config)
    return [rows, m.kv, m.heads // m.kv, m.hd]


def token_slots(config: Dict[str, Any], counters: Dict[str, int]) -> int:
    """ENTRIES the reserved pool can hold: every page backs ``page_size``
    entries of every layer."""
    return counters["n_pages"] * counters["page_size"]
