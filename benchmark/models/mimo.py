"""The model adapter of MiMo-V2-Flash's block (``mimo``): sliding-window
attention (128) with a learned sink beside full attention 5:1, keys of 192
and values of 128 channels, 8 against 4 K/V heads by kind of layer, a rope
base per kind over the first 64 channels of a head, the values scaled, a
leading dense layer, then 256 sigmoid-routed experts top-8 under a selection
bias, of which this chip holds 16, and a sliced vocabulary.  Its plain
reference is ``mimo_reference.py``; README.md lists what an adapter defines.

For the readers: only the FULL-attention layers keep pages (5,120 B a
position with two of them: 4 K/V heads x (192 + 128) channels x 2 B a
layer, what a decode step HAS to read whatever the layout pads).  The
program lays two heads' keys of a position side by side (384 channels,
three whole lane tiles: ``tfmesos_tpu/ops/attention.pack_k``), so the K leaf
is ``[2, pages, 2, 64, 384]`` beside V's ``[2, pages, 4, 64, 128]``
(``pool_leaf_shapes``).  A window layer keeps a ring of the last
``sliding_window`` positions' K and V a row slot, whatever the row's
context: ``state_bytes_per_row`` counts it, ``swa_read_bytes`` what a decode
step at a context has to read of it, ``swa_kernel_shape`` how a device trace
tells its decode kernel (the un-paged ``flash_decode``: its result is
``[rows, kv heads, query heads a K/V head, value head size]``, ``[rows, 8, 8,
128]`` and not the full layers' ``[rows, 4, 16, 128]``).  Both kinds have 64
query heads, so a prompt's windowed forward is NOT told by its heads
(``swa_prefill_heads`` is not defined here): it carries the sink and is a
kernel of its own name, ``swa_forward_ops``.  ``expert_layers`` counts the
layers that hold experts (the leading dense layer holds none),
``held_experts`` the experts of one such layer that this chip holds,
``expert_step_bytes`` / ``expert_kernel_rows`` what one layer's grouped
expert kernels read and run over, ``attn_fwd_flops`` what a prompt's
attention forward has to multiply.
"""

from __future__ import annotations

import math
import re
from typing import Any, Dict, List, Tuple

from benchmark.models import mimo_reference as ref
from benchmark.models.mimo_reference import served_gaps

__all__ = ["program_config", "make_weights", "int8_program_weights",
           "served_gaps", "kv_bytes_per_context_token", "pool_leaf_shapes",
           "paged_kernel_shape", "token_slots", "state_bytes_per_row",
           "expert_layers", "held_experts", "expert_kernel_rows",
           "expert_step_bytes", "swa_read_bytes", "swa_kernel_shape",
           "swa_forward_ops", "attn_fwd_flops", "parameters"]

#: the selection bias: small beside a score's spread (sigmoid of a unit
#: normal), and not zero, from the seed (a trained bias BALANCES the load;
#: at 0.005 an expert's share of the rows moves by about a tenth)
ROUTER_BIAS_STD = 0.005
#: how a device trace names the forward kernel that carries a sink
_FWD_SINK = re.compile(r"^%?flash_attention_fwd_sink[.\d]* = ")


def _counts(config: Dict[str, Any]) -> Dict[str, int]:
    kinds = ref.layer_kinds(config)
    lead = ref.n_dense(config)
    return {"attention": kinds.count("attention"),
            "window": kinds.count("window"), "layers": len(kinds),
            "dense": lead, "sparse": len(kinds) - lead}


def program_config(config: Dict[str, Any], max_len: int):
    """What ``ContinuousBatcher`` is built with.  A program that has no
    value head size, no K/V heads by kind of layer, no sink or no value
    scale cannot run the configuration, and says so at once (before a
    weight is drawn)."""
    import dataclasses

    import jax.numpy as jnp
    from tfmesos_tpu.models import transformer
    from tfmesos_tpu.models.transformer import TransformerConfig
    fields = {f.name for f in dataclasses.fields(TransformerConfig)}
    need = {"layer_types", "window_rope", "attn_rope", "ffn_types",
            "expert_d_ff", "attn_head_dim", "attn_v_head_dim",
            "window_kv_heads", "window_sink", "attn_value_scale",
            "router_score", "routed_scale", "experts_held", "expert_offset"}
    if not need <= fields or "window" not in getattr(
            transformer, "LAYER_KINDS", ()):
        raise SystemExit(
            f"benchmark: this program's TransformerConfig has no "
            f"{sorted(need - fields) or 'window layers'}: it cannot run "
            f"model_type {config['model_type']!r}")
    dm, kv, sinks = ref.dims(config), ref.kind_kv(config), \
        ref.kind_sink(config)
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
        config["torch_dtype"]]
    if config["tie_word_embeddings"] or sinks["attention"]:
        raise SystemExit("benchmark: mimo runs an untied head and no sink "
                         "in the full layers")
    frac = ref.rotary_dim(config) / dm.hd

    def spec(key):
        return transformer.RopeSpec(theta=float(config[key]), fraction=frac)

    return TransformerConfig(
        vocab_size=config["vocab_size"], d_model=dm.d,
        n_layers=config["num_hidden_layers"], n_heads=dm.heads,
        n_kv_heads=kv["attention"], attn_head_dim=dm.hd,
        attn_v_head_dim=dm.vd, attn_value_scale=dm.value_scale,
        d_ff=dm.dense, max_seq_len=max_len, dtype=dtype, param_dtype=dtype,
        layer_types=tuple(ref.layer_kinds(config)), window=dm.window,
        window_kv_heads=kv["window"], window_sink=sinks["window"],
        window_rope=spec("swa_rope_theta"), attn_rope=spec("rope_theta"),
        ffn_types=tuple("sparse" if int(x) else "dense"
                        for x in config["moe_layer_freq"]),
        expert_d_ff=dm.f, norm_eps=dm.eps, logits_dtype=jnp.float32,
        n_experts=dm.experts, top_k=dm.top_k, moe_impl="grouped",
        experts_held=dm.held, expert_offset=dm.offset,
        router_score="sigmoid", routed_scale=dm.routed_scale)


def shapes(config: Dict[str, Any]) -> Dict[str, Any]:
    """Every drawn matmul leaf with its scale, in the tree the program
    takes: the norms stacked over every layer, the mixers' leaves by kind,
    the dense feed-forward's over the leading layers, the expert layer's
    (the held experts') over the sparse ones.  Dense leaves at
    1/sqrt(fan_in); what is added to the residual stream by a further
    1/sqrt(2 L), so that the layers together add unit variance to it."""
    dm, n, kv = ref.dims(config), _counts(config), ref.kind_kv(config)
    d, ls, nd = dm.d, n["sparse"], n["dense"]
    res = 1.0 / math.sqrt(2 * n["layers"])
    s = 1 / math.sqrt(d)
    hq, ho = dm.heads * dm.hd, dm.heads * dm.vd

    def attn(la, k):
        return {"wq": ((la, d, hq), s), "wk": ((la, d, k * dm.hd), s),
                "wv": ((la, d, k * dm.vd), s),
                "wo": ((la, ho, d), res / math.sqrt(ho))}

    return {
        "embed": ((config["vocab_size"], d), 1.0),
        "head": ((d, config["vocab_size"]), s),
        "layers": {
            "attention": attn(n["attention"], kv["attention"]),
            "window": attn(n["window"], kv["window"]),
            "dense": {"w_gate": ((nd, d, dm.dense), s),
                      "w_up": ((nd, d, dm.dense), s),
                      "w_down": ((nd, dm.dense, d),
                                 res / math.sqrt(dm.dense))},
            "router": ((ls, d, dm.experts), s),
            "e_gate": ((ls, dm.held, d, dm.f), s),
            "e_up": ((ls, dm.held, d, dm.f), s),
            "e_down": ((ls, dm.held, dm.f, d), res / math.sqrt(dm.f)),
        },
    }


def parameters(config: Dict[str, Any]) -> int:
    """Parameters this chip holds: every drawn leaf, the norm gains, the
    window layers' sinks and the selection bias."""
    def count(group):
        return sum(count(v) if isinstance(v, dict) else math.prod(v[0])
                   for v in group.values())
    dm, n = ref.dims(config), _counts(config)
    sinks = n["window"] * dm.heads if ref.kind_sink(config)["window"] else 0
    return (count(shapes(config)) + 2 * n["layers"] * dm.d + dm.d + sinks
            + n["sparse"] * dm.experts)


def make_weights(config: Dict[str, Any], seed: int, dtype=None,
                 out_shardings=None):
    """The whole tree in one jitted call, from the seed (the chip's own bit
    generator, stacked leaves a layer at a time); norm gains near 1, the
    sinks ~N(0, 1) and the selection bias ~N(0, 0.005), both float32."""
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

        def near(shape, centre, std, dt=dtype):
            return (centre + std * jax.random.normal(
                next(keys), shape, jnp.float32)).astype(dt)

        def tree(group):
            return {k: (tree(v) if isinstance(v, dict) else draw(v))
                    for k, v in sorted(group.items())}

        layers = tree(sh["layers"])
        layers["attn_norm"] = near((n["layers"], dm.d), 1.0, 0.1)
        layers["mlp_norm"] = near((n["layers"], dm.d), 1.0, 0.1)
        layers["router_bias"] = near((n["sparse"], dm.experts), 0.0,
                                     ROUTER_BIAS_STD, jnp.float32)
        if ref.kind_sink(config)["window"]:
            layers["window"]["sink"] = near((n["window"], dm.heads), 0.0,
                                            1.0, jnp.float32)
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
    layer's are ``swa_read_bytes``), keys and values at their own widths,
    whatever a layout pads: 2 x 4 x (192 + 128) x 2 B = 5,120."""
    dm = ref.dims(config)
    return (_counts(config)["attention"] * ref.kind_kv(config)["attention"]
            * (dm.hd + dm.vd) * itemsize)


def _k_pack(config: Dict[str, Any], kind: str) -> int:
    """K heads a row of the kind's K cache holds side by side: mirrors
    ``tfmesos_tpu/ops/attention.pack_k`` (a test holds the two together)."""
    hd, kv = ref.dims(config).hd, ref.kind_kv(config)[kind]
    return 2 if hd > 128 and hd % 128 == 64 and kv % 2 == 0 else 1


def pool_leaf_shapes(config: Dict[str, Any], counters: Dict[str, int]
                     ) -> List[List[int]]:
    """The shapes a whole-pool copy would have: the K leaf and the V leaf
    of the pool (they differ: ``[full layers, pages, kv_heads / f, page, f x
    192]`` and ``[full layers, pages, kv_heads, page, 128]``), and one layer
    of each."""
    dm, kv = ref.dims(config), ref.kind_kv(config)["attention"]
    f = _k_pack(config, "attention")
    lead = [_counts(config)["attention"], counters["n_pages"]]
    k = lead + [kv // f, counters["page_size"], f * dm.hd]
    v = lead + [kv, counters["page_size"], dm.vd]
    return [k, k[1:], v, v[1:]]


def paged_kernel_shape(config: Dict[str, Any], rows: int) -> List[int]:
    """The paged kernel runs the full layers: 16 query heads a K/V head,
    its result the values' head size."""
    dm, kv = ref.dims(config), ref.kind_kv(config)["attention"]
    return [rows, kv, dm.heads // kv, dm.vd]


def token_slots(config: Dict[str, Any], counters: Dict[str, int]) -> int:
    """Context tokens the reserved pool (the full layers') can hold."""
    return counters["n_pages"] * counters["page_size"]


def state_bytes_per_row(config: Dict[str, Any], itemsize: int = 2) -> int:
    """Bytes one row slot holds beside its pages, whatever its context: a
    ring of ``sliding_window`` positions' K and V a window layer: 5 x 8 x
    128 x (192 + 128) x 2 B = 3,276,800."""
    dm = ref.dims(config)
    return (_counts(config)["window"] * ref.kind_kv(config)["window"]
            * dm.window * (dm.hd + dm.vd) * itemsize)


def swa_kernel_shape(config: Dict[str, Any], rows: int) -> List[int]:
    """The window layers' decode kernel's result (``flash_decode`` over the
    ring): ``[rows, kv heads, query heads a K/V head, value head size]``."""
    dm, kv = ref.dims(config), ref.kind_kv(config)["window"]
    return [rows, kv, dm.heads // kv, dm.vd]


def swa_read_bytes(config: Dict[str, Any], context: int,
                   itemsize: int = 2) -> int:
    """Bytes of K and V the window layers HAVE to read for one decode step
    of a row whose new token stands at position ``context`` (it attends
    itself and the ``min(context, window - 1)`` positions before it),
    over all window layers."""
    dm = ref.dims(config)
    return (_counts(config)["window"] * ref.kind_kv(config)["window"]
            * (dm.hd + dm.vd) * itemsize * min(context + 1, dm.window))


def swa_forward_ops(run) -> List[Tuple[float, float]]:
    """(start, duration) of the window layers' forward kernel in a device
    trace: the flash forward that carries a sink is a kernel of its own
    name (both kinds of layer have 64 query heads here, so the heads do not
    tell them apart)."""
    return [(s, d) for name, s, d in run["trace"].devices[0].ops
            if d > 0 and _FWD_SINK.match(name)]


def attn_fwd_flops(config: Dict[str, Any], t: int) -> int:
    """Multiply-adds x 2 a prompt of ``t`` positions HAS to do in the
    attention forward (scores and values, no projection), over all layers:
    a full layer attends ``t (t + 1) / 2`` (query, key) pairs, a window
    layer ``sum_p min(p + 1, window)``, each pair ``heads x (192 + 128) x
    2``."""
    dm, n = ref.dims(config), _counts(config)
    pair = dm.heads * (dm.hd + dm.vd) * 2
    w = min(t, dm.window)
    band = w * (w + 1) // 2 + (t - w) * dm.window
    return pair * (n["attention"] * (t * (t + 1) // 2) + n["window"] * band)


def expert_layers(config: Dict[str, Any]) -> int:
    """Layers that hold an expert layer (the leading dense ones hold
    none): what a decode step runs the grouped kernels over."""
    return _counts(config)["sparse"]


def held_experts(config: Dict[str, Any]) -> int:
    """Experts of a layer this chip holds: 16 of the router's 256."""
    return ref.dims(config).held


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
    return -(-a // tile) * tile + dm.held * tile


def expert_step_bytes(config: Dict[str, Any], touched: float,
                      itemsize: int = 2) -> Dict[str, float]:
    """Bytes of the held experts' weights ONE layer's grouped kernels have
    to read in a step in which ``touched`` of the held experts took at
    least one assignment (the tick ring's ``moe_experts_touched``), by
    kernel: the gate and up matrices, and the down matrix (16,777,216 B
    each).  A decode step gives an expert 4 of 128 rows, ~8 flops a byte:
    the bytes bound it."""
    dm = ref.dims(config)
    one = dm.d * dm.f * itemsize
    return {"moe_grouped_swiglu": 2 * touched * one,
            "moe_grouped_matmul": touched * one}
