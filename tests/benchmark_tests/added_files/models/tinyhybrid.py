"""A second model adapter, brought as added files only (the rehearsal of
``tests/benchmark_tests/test_benchmark_second_adapter.py``): a tiny decoder
that differs from the Mistral block where the architectures a later PR may
draw do.  Its head is tied to the embedding, and only every
``attention_every``-th layer (layers 0, 2, ...) has attention and keeps K
and V: the others are a norm and a SwiGLU.

The program has one block, so the adapter lays the model out in the tree
that block takes: ``head`` is the embedding transposed, and a layer
without attention gets an output projection of zeros.  The plain reference
(``tinyhybrid_reference.py``) reads the model as it is described: it never
touches ``head`` nor a skipped layer's attention weights.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from benchmark.models.mistral import (int8_program_weights,      # noqa: F401
                                      paged_kernel_shape, pool_leaf_shapes,
                                      program_config)
from benchmark.models.tinyhybrid_reference import served_gaps   # noqa: F401
from benchmark.weights import make_weights as _dense_weights


def has_attention(config: Dict[str, Any], layer: int) -> bool:
    return layer % int(config["attention_every"]) == 0


def make_weights(config: Dict[str, Any], seed: int, dtype=jnp.float32):
    w = _dense_weights(config, seed, dtype=dtype)
    keep = jnp.asarray([has_attention(config, li) for li in
                        range(int(config["num_hidden_layers"]))], dtype)
    layers = dict(w["layers"], wo=w["layers"]["wo"] * keep[:, None, None])
    scale = jnp.asarray(int(config["hidden_size"]) ** -0.5, dtype)
    return jax.block_until_ready(
        dict(w, layers=layers, head=w["embed"].T * scale))


def kv_bytes_per_context_token(config: Dict[str, Any],
                               itemsize: int = 2) -> int:
    """K and V of one position, in the layers that have attention."""
    n = sum(has_attention(config, li)
            for li in range(int(config["num_hidden_layers"])))
    return (n * 2 * int(config["num_key_value_heads"])
            * int(config["head_dim"]) * itemsize)


def token_slots(config: Dict[str, Any], counters: Dict[str, int]) -> int:
    """The program's pool backs every layer of a page; this model keeps K
    and V in one layer of ``attention_every``, so by its own count the
    reservation has room for that many times the positions the pages
    name."""
    layers = int(config["num_hidden_layers"])
    held = sum(has_attention(config, li) for li in range(layers))
    return layers * counters["n_pages"] * counters["page_size"] // held
