"""Operations and bytes from shapes: the yardstick's own arithmetic.

``train_flops_per_token`` is a copy of ``bench.py:transformer_flops_per_token``
(matmul FLOPs only, forward + backward = 3 x forward, causal attention at the
average length T/2, nothing recomputed counted), corrected for grouped-query
attention: ``bench.py`` counts the four projections as ``4 d^2``, which
over-counts a model whose K and V projections are ``kv_heads / n_heads`` as
wide.  ``model`` is the configuration file's ``model`` group.
"""

from __future__ import annotations

from typing import Any, Dict, NamedTuple


class Dims(NamedTuple):
    d: int          # hidden size
    heads: int
    kv: int         # key/value heads
    hd: int         # head size
    f: int          # feed-forward width
    layers: int
    vocab: int


def dims(model: Dict[str, Any]) -> Dims:
    """The sizes every part of the yardstick needs, from a configuration
    file's published keys."""
    d = int(model["hidden_size"])
    heads = int(model["num_attention_heads"])
    return Dims(d=d, heads=heads, kv=int(model["num_key_value_heads"]),
                hd=int(model.get("head_dim") or d // heads),
                f=int(model["intermediate_size"]),
                layers=int(model["num_hidden_layers"]),
                vocab=int(model["vocab_size"]))


def weights_per_layer(model: Dict[str, Any]) -> int:
    d, heads, kv, hd, f, _, _ = dims(model)
    return d * heads * hd * 2 + d * kv * hd * 2 + 3 * d * f


def matmul_params(model: Dict[str, Any]) -> int:
    """Weights that take part in a matmul for every token: the layers and
    the untied head (the embedding is a gather)."""
    d, _, _, _, _, layers, vocab = dims(model)
    return layers * weights_per_layer(model) + d * vocab


def n_params(model: Dict[str, Any]) -> int:
    d, _, _, _, _, layers, vocab = dims(model)
    return matmul_params(model) + d * vocab + (2 * layers + 1) * d


def forward_flops_per_token(model: Dict[str, Any], t: int) -> float:
    d, heads, _, hd, _, layers, _ = dims(model)
    return 2.0 * matmul_params(model) + layers * 2.0 * t * heads * hd


def train_flops_per_token(model: Dict[str, Any], t: int) -> float:
    return 3.0 * forward_flops_per_token(model, t)


def kv_bytes_per_context_token(model: Dict[str, Any],
                               itemsize: int = 2) -> int:
    """Bytes of cached K and V that one decode step must read for each
    position of its context, over all layers: what ``flash_decode_paged``
    cannot avoid reading."""
    _, _, kv, hd, _, layers, _ = dims(model)
    return layers * 2 * kv * hd * itemsize


def pool_bytes(model: Dict[str, Any], n_pages: int, page_size: int,
               itemsize: int = 2) -> int:
    return n_pages * page_size * kv_bytes_per_context_token(model, itemsize)
