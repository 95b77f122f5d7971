"""The model adapter of the Mistral/Llama-shaped dense decoder: RMSNorm,
rotary GQA, SwiGLU, untied head, one homogeneous stack whose every layer
keeps K and V in the paged pool.  A thin file over ``weights.py`` (the
weights, from the seed), ``reference.py`` (the plain reference and its int8
control) and ``costs.py`` (bytes from shapes).  README.md lists what an
adapter defines; a configuration names its adapter with ``"model"``.
"""

from __future__ import annotations

from typing import Any, Dict, List

from benchmark import costs
from benchmark.costs import kv_bytes_per_context_token
from benchmark.reference import served_gaps
from benchmark.weights import make_weights

__all__ = ["program_config", "make_weights", "int8_program_weights",
           "served_gaps", "kv_bytes_per_context_token", "pool_leaf_shapes",
           "paged_kernel_shape", "token_slots"]


def program_config(config: Dict[str, Any], max_len: int):
    """What ``ContinuousBatcher`` is built with."""
    import jax.numpy as jnp
    from tfmesos_tpu.models.transformer import TransformerConfig
    if config.get("sliding_window"):
        raise SystemExit("paged serving does not take a sliding window")
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
        config["torch_dtype"]]
    return TransformerConfig(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        n_layers=config["num_hidden_layers"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        d_ff=config["intermediate_size"], max_seq_len=max_len,
        rope_theta=float(config["rope_theta"]), dtype=dtype,
        param_dtype=dtype)


def int8_program_weights(cfg, weights):
    """The program's own weight-only int8 path: what ``control.py
    --program-int8 1`` serves from, and ``correct`` has to refuse."""
    from tfmesos_tpu.models.transformer import quantize_params
    return quantize_params(cfg, weights)


def pool_leaf_shapes(config: Dict[str, Any], counters: Dict[str, int]
                     ) -> List[List[int]]:
    """The shapes a whole-pool copy would have: a K or V leaf of the pool,
    ``[layers, pages, kv_heads, page, head_dim]``, and one layer of it."""
    m = costs.dims(config)
    pool = [m.layers, counters["n_pages"], m.kv, counters["page_size"], m.hd]
    return [pool, pool[1:]]


def paged_kernel_shape(config: Dict[str, Any], rows: int) -> List[int]:
    """The paged-decode kernel's output: ``[rows, kv_heads, q_per_kv,
    head_dim]``."""
    m = costs.dims(config)
    return [rows, m.kv, m.heads // m.kv, m.hd]


def token_slots(config: Dict[str, Any], counters: Dict[str, int]) -> int:
    """Context tokens the reserved pool can hold: every page backs
    ``page_size`` positions of every layer."""
    return counters["n_pages"] * counters["page_size"]
