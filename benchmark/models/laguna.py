"""The model adapter of Laguna-XS.2's block (``laguna``): sliding-window
attention (512) beside full attention 3:1, 64 against 48 query heads over 8
K/V heads, a rotary embedding per kind of layer (YaRN over half a head's
channels; plain rope over all of them), a per-head output gate, a leading
dense layer, then 256 sigmoid-routed experts top-8 of width 512, all held,
and a shared one.  Its plain reference is ``laguna_reference.py``; README.md
lists what an adapter defines.

For the readers: only the FULL-attention layers keep pages (8,192 B a
position with two of them, a pool ``[2, pages, 8, 64, 128]``).  A window
layer keeps a ring of the last ``sliding_window`` positions' K and V a row
slot, whatever the row's context: ``state_bytes_per_row`` counts it,
``swa_read_bytes`` what a decode step at a context has to read of it,
``swa_kernel_shape`` how a device trace tells its decode kernel (the
un-paged ``flash_decode``: its result is ``[rows, kv heads, query heads a
K/V head, head size]``, 8 and not the full layers' 6).  ``expert_layers``
counts the layers that hold experts (the leading dense layer holds none),
``held_experts`` the experts of one such layer that this chip holds,
``expert_step_bytes`` / ``expert_kernel_rows`` what one layer's grouped
expert kernels read and run over.
"""

from __future__ import annotations

import math
from typing import Any, Dict, List

from benchmark.models import laguna_reference as ref
from benchmark.models.laguna_reference import served_gaps

__all__ = ["program_config", "make_weights", "int8_program_weights",
           "served_gaps", "kv_bytes_per_context_token", "pool_leaf_shapes",
           "paged_kernel_shape", "token_slots", "state_bytes_per_row",
           "expert_layers", "held_experts", "expert_kernel_rows",
           "expert_step_bytes",
           "swa_read_bytes", "swa_kernel_shape", "swa_prefill_heads",
           "parameters"]


def _counts(config: Dict[str, Any]) -> Dict[str, int]:
    kinds = ref.layer_kinds(config)
    lead = ref.n_dense(config)
    return {"attention": kinds.count("attention"),
            "window": kinds.count("window"), "layers": len(kinds),
            "dense": lead, "sparse": len(kinds) - lead}


def program_config(config: Dict[str, Any], max_len: int):
    """What ``ContinuousBatcher`` is built with.  A program that has no
    window layers beside full ones, no rope per kind, no per-head gate or no
    feed-forward pattern cannot run the configuration, and says so at once
    (before a weight is drawn)."""
    import dataclasses

    import jax.numpy as jnp
    from tfmesos_tpu.models import transformer
    from tfmesos_tpu.models.transformer import TransformerConfig
    fields = {f.name for f in dataclasses.fields(TransformerConfig)}
    need = {"layer_types", "window_heads", "window_rope", "attn_rope",
            "ffn_types", "expert_d_ff", "attn_gate", "attn_head_dim",
            "router_score", "routed_scale", "shared_d_ff"}
    if not need <= fields or "window" not in getattr(
            transformer, "LAYER_KINDS", ()):
        raise SystemExit(
            f"benchmark: this program's TransformerConfig has no "
            f"{sorted(need - fields) or 'window layers'}: it cannot run "
            f"model_type {config['model_type']!r}")
    dm, heads = ref.dims(config), ref.kind_heads(config)
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
        config["torch_dtype"]]
    if config["tie_word_embeddings"]:
        raise SystemExit("benchmark: laguna runs an untied head only")

    def spec(kind):
        rp = config["rope_parameters"][kind]
        yarn = None
        if rp["rope_type"] == "yarn":
            yarn = (float(rp["factor"]),
                    int(rp["original_max_position_embeddings"]),
                    float(rp["beta_fast"]), float(rp["beta_slow"]))
        return transformer.RopeSpec(
            theta=float(rp["rope_theta"]),
            fraction=float(rp["partial_rotary_factor"]), yarn=yarn,
            attention_factor=rp.get("attention_factor"))

    return TransformerConfig(
        vocab_size=config["vocab_size"], d_model=dm.d,
        n_layers=config["num_hidden_layers"], n_heads=heads["attention"],
        n_kv_heads=dm.kv, attn_head_dim=dm.hd, d_ff=dm.dense,
        max_seq_len=max_len, dtype=dtype, param_dtype=dtype,
        layer_types=tuple(ref.layer_kinds(config)),
        window=dm.window, window_heads=heads["window"],
        window_rope=spec("sliding_attention"),
        attn_rope=spec("full_attention"),
        ffn_types=tuple(config["mlp_layer_types"]), expert_d_ff=dm.f,
        attn_gate="head", norm_eps=dm.eps, logits_dtype=jnp.float32,
        n_experts=dm.experts, top_k=dm.top_k, moe_impl="grouped",
        shared_d_ff=dm.shared, router_score="sigmoid",
        routed_scale=dm.routed_scale)


def shapes(config: Dict[str, Any]) -> Dict[str, Any]:
    """Every drawn matmul leaf with its scale, in the tree the program
    takes: the norms stacked over every layer, the mixers' leaves by kind,
    the dense feed-forward's over the leading layers, the expert layer's
    over the sparse ones.  Dense leaves at 1/sqrt(fan_in); what is added to
    the residual stream by a further 1/sqrt(2 L), so that the layers
    together add unit variance to it."""
    dm, n, heads = ref.dims(config), _counts(config), ref.kind_heads(config)
    d, ls, nd = dm.d, n["sparse"], n["dense"]
    res = 1.0 / math.sqrt(2 * n["layers"])
    s = 1 / math.sqrt(d)
    hkv = dm.kv * dm.hd

    def attn(la, h):
        hq = h * dm.hd
        return {"wq": ((la, d, hq), s), "wk": ((la, d, hkv), s),
                "wv": ((la, d, hkv), s), "wg": ((la, d, h), s),
                "wo": ((la, hq, d), res / math.sqrt(hq))}

    def mlp(n_, f, names):
        g, u, dn = names
        return {g: ((n_, d, f), s), u: ((n_, d, f), s),
                dn: ((n_, f, d), res / math.sqrt(f))}

    return {
        "embed": ((config["vocab_size"], d), 1.0),
        "head": ((d, config["vocab_size"]), s),
        "layers": {
            "attention": attn(n["attention"], heads["attention"]),
            "window": attn(n["window"], heads["window"]),
            "dense": mlp(nd, dm.dense, ("w_gate", "w_up", "w_down")),
            "router": ((ls, d, dm.experts), s),
            "e_gate": ((ls, dm.experts, d, dm.f), s),
            "e_up": ((ls, dm.experts, d, dm.f), s),
            "e_down": ((ls, dm.experts, dm.f, d), res / math.sqrt(dm.f)),
            **mlp(ls, dm.shared, ("s_gate", "s_up", "s_down")),
        },
    }


def parameters(config: Dict[str, Any]) -> int:
    """Parameters this chip holds: every drawn leaf and the norm gains."""
    def count(group):
        return sum(count(v) if isinstance(v, dict) else math.prod(v[0])
                   for v in group.values())
    dm, n = ref.dims(config), _counts(config)
    return count(shapes(config)) + 2 * n["layers"] * dm.d + dm.d


def make_weights(config: Dict[str, Any], seed: int, dtype=None,
                 out_shardings=None):
    """The whole tree in one jitted call, from the seed (the chip's own bit
    generator, stacked leaves a layer at a time); norm gains near 1.  No
    selection bias: the config has no key for one, and the program's
    sigmoid router takes none where the leaf is absent."""
    import jax
    import jax.numpy as jnp
    dtype = dtype or jnp.bfloat16
    sh = shapes(config)
    dm, n = ref.dims(config), _counts(config)

    def build(key):
        keys = iter(jax.random.split(key, 48))

        def draw(shape_scale):
            shape, scale = shape_scale
            k = next(keys)
            if len(shape) >= 3:
                x = jax.lax.map(
                    lambda kk: jax.random.normal(kk, shape[1:], dtype),
                    jax.random.split(k, shape[0]))
            else:
                x = jax.random.normal(k, shape, dtype)
            return x * jnp.asarray(scale, dtype)

        def near(shape, centre, std):
            return (centre + std * jax.random.normal(
                next(keys), shape, jnp.float32)).astype(dtype)

        def tree(group):
            return {k: (tree(v) if isinstance(v, dict) else draw(v))
                    for k, v in sorted(group.items())}

        layers = tree(sh["layers"])
        layers["attn_norm"] = near((n["layers"], dm.d), 1.0, 0.1)
        layers["mlp_norm"] = near((n["layers"], dm.d), 1.0, 0.1)
        return {"embed": draw(sh["embed"]), "head": draw(sh["head"]),
                "layers": layers, "norm_f": near((dm.d,), 1.0, 0.1)}

    key = jax.random.key(int(seed) % (2 ** 63), impl="rbg")
    return jax.jit(build, out_shardings=out_shardings)(key)


def int8_program_weights(cfg, weights):
    """The program's own weight-only int8 path: what ``control.py
    --program-int8 1`` serves from, and ``correct`` has to refuse."""
    from tfmesos_tpu.models.transformer import quantize_params
    return quantize_params(cfg, weights)


def kv_bytes_per_context_token(config: Dict[str, Any],
                               itemsize: int = 2) -> int:
    """Bytes of cached K and V a decode step must read per position of
    context from the PAGES: the full-attention layers' only (a window
    layer's are ``swa_read_bytes``)."""
    dm = ref.dims(config)
    return _counts(config)["attention"] * 2 * dm.kv * dm.hd * itemsize


def pool_leaf_shapes(config: Dict[str, Any], counters: Dict[str, int]
                     ) -> List[List[int]]:
    """The shapes a whole-pool copy would have: a K or V leaf of the pool,
    ``[full layers, pages, kv_heads, page, head_dim]``, and one layer of
    it."""
    dm = ref.dims(config)
    pool = [_counts(config)["attention"], counters["n_pages"], dm.kv,
            counters["page_size"], dm.hd]
    return [pool, pool[1:]]


def paged_kernel_shape(config: Dict[str, Any], rows: int) -> List[int]:
    """The paged kernel runs the full layers: 6 query heads a K/V head."""
    dm = ref.dims(config)
    return [rows, dm.kv, ref.kind_heads(config)["attention"] // dm.kv, dm.hd]


def token_slots(config: Dict[str, Any], counters: Dict[str, int]) -> int:
    """Context tokens the reserved pool (the full layers') can hold."""
    return counters["n_pages"] * counters["page_size"]


def state_bytes_per_row(config: Dict[str, Any], itemsize: int = 2) -> int:
    """Bytes one row slot holds beside its pages, whatever its context: a
    ring of ``sliding_window`` positions' K and V a window layer."""
    dm = ref.dims(config)
    return (_counts(config)["window"] * 2 * dm.kv * dm.window * dm.hd
            * itemsize)


def swa_kernel_shape(config: Dict[str, Any], rows: int) -> List[int]:
    """The window layers' decode kernel's result (``flash_decode`` over the
    ring): ``[rows, kv heads, query heads a K/V head, head size]``."""
    dm = ref.dims(config)
    return [rows, dm.kv, ref.kind_heads(config)["window"] // dm.kv, dm.hd]


def swa_prefill_heads(config: Dict[str, Any]) -> int:
    """Query heads of a window layer: a prefill's windowed flash attention
    is told from the full layers' by them."""
    return ref.kind_heads(config)["window"]


def swa_read_bytes(config: Dict[str, Any], context: int,
                   itemsize: int = 2) -> int:
    """Bytes of K and V the window layers HAVE to read for one decode step
    of a row whose new token stands at position ``context`` (it attends
    itself and the ``min(context, window - 1)`` positions before it),
    over all window layers."""
    dm = ref.dims(config)
    return (_counts(config)["window"] * 2 * dm.kv * dm.hd * itemsize
            * min(context + 1, dm.window))


def expert_layers(config: Dict[str, Any]) -> int:
    """Layers that hold an expert layer (the leading dense ones hold
    none): what a decode step runs the grouped kernels over."""
    return _counts(config)["sparse"]


def held_experts(config: Dict[str, Any]) -> int:
    """Experts of a layer this chip holds: all of them."""
    return ref.dims(config).experts


def expert_kernel_rows(config: Dict[str, Any], tokens: int) -> int:
    """Rows of the sorted buffer the grouped expert kernels run over for a
    step of ``tokens`` tokens (their outputs' leading dim): every
    assignment could fall here, plus a tile's padding per held expert.
    Mirrors ``tfmesos_tpu/ops/moe.py`` (``pick_tile``, ``grouped_layout``);
    a test holds the two together."""
    dm = ref.dims(config)
    a = tokens * dm.top_k
    mean = a / dm.experts
    tile = 16
    while tile < 128 and tile * 4 <= mean:
        tile *= 2
    return -(-a // tile) * tile + dm.experts * tile


def expert_step_bytes(config: Dict[str, Any], touched: float,
                      itemsize: int = 2) -> Dict[str, float]:
    """Bytes of expert weights ONE layer's grouped kernels have to read in
    a step in which ``touched`` experts took at least one assignment (the
    tick ring's ``moe_experts_touched``), by kernel: the gate and up
    matrices, and the down matrix (2,097,152 B each).  A decode step gives
    an expert 4 of 128 rows, ~8 flops a byte: the bytes bound it."""
    dm = ref.dims(config)
    one = dm.d * dm.f * itemsize
    return {"moe_grouped_swiglu": 2 * touched * one,
            "moe_grouped_matmul": touched * one}
