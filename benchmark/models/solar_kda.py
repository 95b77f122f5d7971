"""The model adapter of Solar-Open2's block (``solar_open2``): KDA layers (a
gated delta rule with a decay per key channel, a float32 matrix state a head)
beside gated NoPE grouped-query attention, 3:1, every layer followed by 320
sigmoid-routed experts top-8 and a shared expert, an untied head over a
sliced vocabulary.  Its plain reference is ``solar_kda_reference.py``;
README.md lists what an adapter defines.

For the readers: only the ATTENTION layers keep K/V (one layer of four: 4,096
B a position, a pool ``[1, pages, 8, 64, 128]``).  The KDA layers' state is a
row's, whatever its context: ``state_bytes_per_row`` counts it,
``kda_step_bytes`` what one decode step must read and write of it,
``kda_store_shape`` / ``kda_carry_shape`` how a device trace names the store
and a prefill's chunk scan (``benchmark/kda_readers.py``), and
``expert_step_bytes`` / ``expert_kernel_rows`` what one layer's grouped expert
kernels read and run over.

The configuration holds a SHARE of each layer's experts (``n_routed_experts``
of the ``published`` count, the experts of chip ``expert_shard`` of
``expert_parallel``) and of the vocabulary (rows ``0 .. vocab_size - 1`` of the
published embedding and head); the router keeps its published width.
"""

from __future__ import annotations

import math
from typing import Any, Dict, List

from benchmark.models import solar_kda_reference as ref
from benchmark.models.solar_kda_reference import served_gaps

__all__ = ["program_config", "make_weights", "int8_program_weights",
           "served_gaps", "kv_bytes_per_context_token", "pool_leaf_shapes",
           "paged_kernel_shape", "token_slots", "state_bytes_per_row",
           "kda_store_shape", "kda_carry_shape", "kda_step_bytes",
           "expert_kernel_rows", "expert_step_bytes", "parameters"]

#: the prefill's chunk of the delta rule (Kimi Linear's; the configuration's
#: ``assumed`` says so)
KDA_CHUNK = 64
#: ``b_proj`` times 1.5 / sqrt(hidden): ``beta = 2 sigmoid(.)`` then spreads
#: over (0, 2) and not around 1
BETA_GAIN = 1.5
#: ``f_up`` times 0.5 / sqrt(rank): the token's part of the log-decay moves
#: it by a factor of e either way, around what ``dt_bias`` and ``A_log`` set
DECAY_GAIN = 0.5
#: the selection bias: small beside a score's spread (sigmoid of a unit
#: normal), and not zero.  At the top-8 of 320 (a logit near 2) a score moves
#: by a tenth of its logit, so 0.005 moves an expert's share of the rows by
#: about a tenth; 0.02 moved it by half (a trained bias BALANCES the load)
ROUTER_BIAS_STD = 0.005


def _counts(config: Dict[str, Any]) -> Dict[str, int]:
    kinds = ref.layer_kinds(config)
    return {"attention": kinds.count("attention"),
            "kda": kinds.count("kda"), "layers": len(kinds)}


def program_config(config: Dict[str, Any], max_len: int):
    """What ``ContinuousBatcher`` is built with.  A program that has no KDA
    mixer, attention gate or sigmoid router cannot run the configuration,
    and says so at once (before a weight is drawn)."""
    import dataclasses

    import jax.numpy as jnp
    from tfmesos_tpu.models.transformer import TransformerConfig
    fields = {f.name for f in dataclasses.fields(TransformerConfig)}
    need = {"layer_types", "kda_heads", "kda_head_dim", "kda_conv",
            "kda_chunk", "kda_neg_eigval", "attn_gate", "attn_head_dim",
            "router_score", "routed_scale", "experts_held", "expert_offset",
            "shared_d_ff"}
    if not need <= fields:
        raise SystemExit(
            f"benchmark: this program's TransformerConfig has no "
            f"{sorted(need - fields)}: it cannot run model_type "
            f"{config['model_type']!r}")
    dm = ref.dims(config)
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
        config["torch_dtype"]]
    if config["tie_word_embeddings"]:
        raise SystemExit("benchmark: solar_kda runs an untied head only")
    return TransformerConfig(
        vocab_size=config["vocab_size"], d_model=dm.d,
        n_layers=config["num_hidden_layers"], n_heads=dm.heads,
        n_kv_heads=dm.kv, attn_head_dim=dm.hd, d_ff=dm.f,
        max_seq_len=max_len, dtype=dtype, param_dtype=dtype,
        layer_types=tuple(ref.layer_kinds(config)),
        kda_heads=dm.k_heads, kda_head_dim=dm.k_hd, kda_conv=dm.k_conv,
        kda_chunk=KDA_CHUNK, kda_neg_eigval=dm.neg_eigval, rope=False,
        attn_gate=dm.gate, norm_eps=dm.eps, logits_dtype=jnp.float32,
        n_experts=dm.experts, top_k=dm.top_k, moe_impl="grouped",
        experts_held=dm.held, expert_offset=dm.offset, shared_d_ff=dm.shared,
        router_score="sigmoid", routed_scale=dm.routed_scale)


def shapes(config: Dict[str, Any]) -> Dict[str, Any]:
    """Every drawn matmul leaf with its scale, in the tree the program
    takes: the leaves every layer has stacked ``[L, ...]``, the mixers' by
    kind.  Dense leaves at 1/sqrt(fan_in); what is added to the residual
    stream by a further 1/sqrt(2 L), so that the layers together add unit
    variance to it."""
    dm, n = ref.dims(config), _counts(config)
    l, la, lk = n["layers"], n["attention"], n["kda"]
    d, f, sf, r = dm.d, dm.f, dm.shared, dm.k_hd
    hq, hkv, hk = dm.heads * dm.hd, dm.kv * dm.hd, dm.k_heads * dm.k_hd
    res = 1.0 / math.sqrt(2 * l)
    s = 1 / math.sqrt(d)
    att = {"wq": ((la, d, hq), s), "wk": ((la, d, hkv), s),
           "wv": ((la, d, hkv), s), "wo": ((la, hq, d), res / math.sqrt(hq))}
    if dm.gate:
        att["wg"] = ((la, d, hq), s)
    return {
        "embed": ((config["vocab_size"], d), 1.0),
        "head": ((d, config["vocab_size"]), s),
        "layers": {
            "router": ((l, d, dm.experts), s),
            "e_gate": ((l, dm.held, d, f), s),
            "e_up": ((l, dm.held, d, f), s),
            "e_down": ((l, dm.held, f, d), res / math.sqrt(f)),
            "s_gate": ((l, d, sf), s),
            "s_up": ((l, d, sf), s),
            "s_down": ((l, sf, d), res / math.sqrt(sf)),
            "attention": att,
            "kda": {
                "in_proj": ((lk, d, 3 * hk), s),
                "out_proj": ((lk, hk, d), res / math.sqrt(hk)),
                "conv_w": ((lk, dm.k_conv, 3 * hk),
                           1 / math.sqrt(dm.k_conv)),
                "f_down": ((lk, d, r), s),
                "f_up": ((lk, r, hk), DECAY_GAIN / math.sqrt(r)),
                "g_down": ((lk, d, r), s),
                "g_up": ((lk, r, hk), 1 / math.sqrt(r)),
                "b_proj": ((lk, d, dm.k_heads), BETA_GAIN * s),
            },
        },
    }


def parameters(config: Dict[str, Any]) -> int:
    """Parameters this chip holds: every drawn leaf and the per-channel
    ones (norm gains, ``dt_bias``, ``A_log``, the selection bias)."""
    def count(group):
        return sum(count(v) if isinstance(v, dict) else math.prod(v[0])
                   for v in group.values())
    dm, n = ref.dims(config), _counts(config)
    small = (2 * n["layers"] * dm.d + dm.d + n["layers"] * dm.experts
             + n["kda"] * (dm.k_heads * dm.k_hd + dm.k_heads + dm.k_hd))
    return count(shapes(config)) + small


def make_weights(config: Dict[str, Any], seed: int, dtype=None,
                 out_shardings=None):
    """The whole tree in one jitted call, from the seed (the chip's own bit
    generator, stacked leaves a layer at a time).  ``dt_bias`` is drawn so
    that ``softplus(dt_bias + ...)`` spreads over 2e-3 .. 6e-2 and ``-exp(
    A_log)`` over -1 .. -4: with the token's part (``DECAY_GAIN``) a step's
    log-decay lies within about -1e-3 .. -0.5, far from 0 and from float32's
    range over a chunk.  ``beta`` spreads over (0, 2) (``BETA_GAIN``), the
    norm gains lie near 1, the selection bias is small and not zero."""
    import jax
    import jax.numpy as jnp
    dtype = dtype or jnp.bfloat16
    sh = shapes(config)
    dm, n = ref.dims(config), _counts(config)
    l, lk = n["layers"], n["kda"]
    hk = dm.k_heads * dm.k_hd

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

        def near(shape, centre, std, to=dtype):
            return (centre + std * jax.random.normal(
                next(keys), shape, jnp.float32)).astype(to)

        def uniform(shape, lo, hi):
            return jax.random.uniform(next(keys), shape, jnp.float32, lo, hi)

        def tree(group):
            return {k: (tree(v) if isinstance(v, dict) else draw(v))
                    for k, v in sorted(group.items())}

        layers = tree(sh["layers"])
        layers["attn_norm"] = near((l, dm.d), 1.0, 0.1)
        layers["mlp_norm"] = near((l, dm.d), 1.0, 0.1)
        layers["router_bias"] = near((l, dm.experts), 0.0, ROUTER_BIAS_STD,
                                     jnp.float32)
        kda = layers["kda"]
        dt0 = jnp.exp(uniform((lk, hk), math.log(2e-3), math.log(6e-2)))
        kda["dt_bias"] = (dt0 + jnp.log(-jnp.expm1(-dt0))).astype(dtype)
        kda["A_log"] = jnp.log(uniform((lk, dm.k_heads), 1.0, 4.0)
                               ).astype(dtype)
        kda["norm"] = near((lk, dm.k_hd), 1.0, 0.1)
        # out_proj with zero sum over each head's channels (Granite's second
        # degeneracy, ``granite_hybrid.py``), and every channel's conv taps
        # at unit power.  SiLU leaves v a positive mean, so about a quarter
        # of the power of a KDA layer's gated output is the same for every
        # token; with taps of equal power it is constant within a head and a
        # zero-sum out_proj adds none of it to the residual stream.  Through
        # a random out_proj it was one direction that every row's hidden
        # state shared, an offset on every expert's router logit: with the
        # selection bias at 0.02 the fullest held expert took 20-45 of a
        # decode step's 192 rows and 10-12 of a step's 160 held experts
        # none, a count that differed between seeds and moved the step's
        # time by 0.04 ms an expert (PERF.md section 6, PR 37).
        cw = kda["conv_w"].astype(jnp.float32)
        kda["conv_w"] = (cw * jax.lax.rsqrt(
            jnp.sum(cw * cw, axis=1, keepdims=True))).astype(dtype)
        op = kda["out_proj"].astype(jnp.float32).reshape(
            lk, dm.k_heads, dm.k_hd, dm.d)
        kda["out_proj"] = (op - op.mean(axis=2, keepdims=True)).reshape(
            lk, hk, dm.d).astype(dtype)
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
    context: the ATTENTION layers' only (the KDA layers keep none)."""
    dm = ref.dims(config)
    return _counts(config)["attention"] * 2 * dm.kv * dm.hd * itemsize


def pool_leaf_shapes(config: Dict[str, Any], counters: Dict[str, int]
                     ) -> List[List[int]]:
    """The shapes a whole-pool copy would have: a K or V leaf of the pool,
    ``[attention layers, pages, kv_heads, page, head_dim]``, and one layer
    of it."""
    dm = ref.dims(config)
    pool = [_counts(config)["attention"], counters["n_pages"], dm.kv,
            counters["page_size"], dm.hd]
    return [pool, pool[1:]]


def paged_kernel_shape(config: Dict[str, Any], rows: int) -> List[int]:
    dm = ref.dims(config)
    return [rows, dm.kv, dm.heads // dm.kv, dm.hd]


def token_slots(config: Dict[str, Any], counters: Dict[str, int]) -> int:
    return counters["n_pages"] * counters["page_size"]


def kda_store_shape(config: Dict[str, Any], rows: int) -> List[int]:
    """The stacked KDA state store as the decode step's update reads and
    writes it: ``[kda layers, rows, heads * head size, head size]`` float32
    (the program keeps heads and key channels as one dim)."""
    dm = ref.dims(config)
    return [_counts(config)["kda"], rows, dm.k_heads * dm.k_hd, dm.k_hd]


def kda_carry_shape(config: Dict[str, Any]) -> List[int]:
    """What a prefill's chunk scan carries from chunk to chunk: one row's
    state, ``[1, heads, head size, head size]`` float32."""
    dm = ref.dims(config)
    return [1, dm.k_heads, dm.k_hd, dm.k_hd]


def state_bytes_per_row(config: Dict[str, Any], itemsize: int = 2) -> int:
    """Bytes of recurrent state one row slot holds over all KDA layers,
    whatever its context: the float32 matrix states and the conv tail over
    [q | k | v]."""
    dm = ref.dims(config)
    hk = dm.k_heads * dm.k_hd
    return _counts(config)["kda"] * (
        hk * dm.k_hd * 4 + (dm.k_conv - 1) * 3 * hk * itemsize)


def kda_step_bytes(config: Dict[str, Any], rows: int) -> int:
    """Bytes of KDA state one decode step of ``rows`` rows has to read and
    write, over all KDA layers: the state is float32, read once and written
    once.  ~3 flops a byte: bound by the bytes."""
    dm = ref.dims(config)
    return (2 * rows * _counts(config)["kda"]
            * dm.k_heads * dm.k_hd * dm.k_hd * 4)


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
    kernel: the gate and up matrices, and the down matrix (10,485,760 B
    each).  A decode step gives an expert ~5 of 192 rows, ~10 flops a byte:
    the bytes bound it."""
    dm = ref.dims(config)
    one = dm.d * dm.f * itemsize
    return {"moe_grouped_swiglu": 2 * touched * one,
            "moe_grouped_matmul": touched * one}
