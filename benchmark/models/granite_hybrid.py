"""The model adapter of Granite-4.0-H's block (``granitemoehybrid``): typed
layers (Mamba-2 beside NoPE grouped-query attention), every layer followed
by 72 routed experts top-10 and a shared MLP, four multipliers, a tied head.
Its plain reference is ``granite_hybrid_reference.py``; README.md lists what
an adapter defines.

What differs from ``mistral.py`` for the readers: only the ATTENTION layers
keep K/V, so ``kv_bytes_per_context_token`` and ``pool_leaf_shapes`` are
theirs alone (one layer of ten: 4,096 B a position, a pool ``[1, pages, 8,
64, 128]``).  The mamba layers' state is a row's, whatever its context:
``state_bytes_per_row`` counts it, ``ssm_step_bytes`` what one decode step
must read and write of it, ``expert_step_bytes`` what one layer's grouped
expert kernels must read of the held experts' weights; the cell's own readers
(``layer_metrics/ssm_*.py``, ``moe_*.py``) do their arithmetic through these.

The configuration holds a SHARE of each layer's experts (``num_local_experts``
of the ``published`` count, the experts of chip ``expert_shard`` of
``expert_parallel``); the router keeps its published width.
"""

from __future__ import annotations

import math
from typing import Any, Dict, List

from benchmark.models import granite_hybrid_reference as ref
from benchmark.models.granite_hybrid_reference import served_gaps

__all__ = ["program_config", "make_weights", "int8_program_weights",
           "served_gaps", "kv_bytes_per_context_token", "pool_leaf_shapes",
           "paged_kernel_shape", "token_slots", "state_bytes_per_row",
           "ssm_state_shape", "ssd_carry_shape", "ssm_step_bytes",
           "expert_kernel_rows",
           "expert_step_bytes"]

#: the embedding's scale: x = embed * embedding_multiplier stays small beside
#: what the layers add, or the tied head would return the input token (the
#: input token's own logit is embed . (12 embed + ...) and grows with
#: sqrt(hidden); at 1/768 it stood 4.2 standard deviations over the rest on
#: the chip and 24-34 of 64 rows repeated their last token, PERF.md §6 PR 32)
EMBED_STD = 1.0 / 3072.0
#: in_proj's B and C columns, times 1/sqrt(hidden): the state's part of a
#: mamba layer's y is then of the size of the skip's (D x)
BC_GAIN = 3.0


def _counts(config: Dict[str, Any]) -> Dict[str, int]:
    kinds = ref.layer_kinds(config)
    return {"attention": kinds.count("attention"),
            "mamba": kinds.count("mamba"), "layers": len(kinds)}


def program_config(config: Dict[str, Any], max_len: int):
    """What ``ContinuousBatcher`` is built with.  A program that has no
    typed layers, Mamba mixer or held experts cannot run the configuration,
    and says so at once."""
    import dataclasses

    import jax.numpy as jnp
    from tfmesos_tpu.models.transformer import TransformerConfig
    fields = {f.name for f in dataclasses.fields(TransformerConfig)}
    need = {"layer_types", "mamba_heads", "mamba_state", "rope", "attn_scale",
            "embed_scale", "residual_scale", "logits_scale",
            "tie_embeddings", "experts_held", "expert_offset", "shared_d_ff"}
    if not need <= fields:
        raise SystemExit(
            f"benchmark: this program's TransformerConfig has no "
            f"{sorted(need - fields)}: it cannot run model_type "
            f"{config['model_type']!r}")
    dm = ref.dims(config)
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
        config["torch_dtype"]]
    if config["position_embedding_type"] != "nope":
        raise SystemExit("benchmark: granite_hybrid runs position_embedding_"
                         "type 'nope' only")
    if dm.hd * dm.heads != dm.d:
        raise SystemExit("benchmark: the program's head size is hidden_size "
                         "/ num_attention_heads")
    return TransformerConfig(
        vocab_size=config["vocab_size"], d_model=dm.d,
        n_layers=config["num_hidden_layers"], n_heads=dm.heads,
        n_kv_heads=dm.kv, d_ff=dm.f,
        max_seq_len=max_len, dtype=dtype, param_dtype=dtype,
        layer_types=tuple(config["layer_types"]),
        mamba_heads=dm.m_heads, mamba_head_dim=dm.m_hd,
        mamba_state=dm.m_state, mamba_conv=dm.m_conv, mamba_chunk=config["mamba_chunk_size"],
        rope=False, attn_scale=dm.attn_scale, embed_scale=dm.embed_mult,
        residual_scale=dm.resid_mult, logits_scale=dm.logits_div,
        tie_embeddings=bool(config["tie_word_embeddings"]),
        norm_eps=dm.eps, logits_dtype=jnp.float32,
        n_experts=dm.experts, top_k=dm.top_k, moe_impl="grouped",
        experts_held=dm.held, expert_offset=dm.offset, shared_d_ff=dm.shared)


def shapes(config: Dict[str, Any]) -> Dict[str, Any]:
    """Every drawn matmul leaf with its scale, in the tree the program
    takes: the leaves every layer has stacked ``[L, ...]``, the mixers' by
    kind.  Dense leaves at 1/sqrt(fan_in); what is added to the residual
    stream by a further 1/(r sqrt(2 L)), so that the layers together add
    unit variance to it."""
    dm, n = ref.dims(config), _counts(config)
    l, la, lm = n["layers"], n["attention"], n["mamba"]
    d, f, sf = dm.d, dm.f, dm.shared
    di = dm.m_heads * dm.m_hd
    res = 1.0 / (dm.resid_mult * math.sqrt(2 * l))
    s = 1 / math.sqrt(d)
    return {
        "embed": ((config["vocab_size"], d), EMBED_STD),
        "layers": {
            "router": ((l, d, dm.experts), s),
            "e_gate": ((l, dm.held, d, f), s),
            "e_up": ((l, dm.held, d, f), s),
            "e_down": ((l, dm.held, f, d), res / math.sqrt(f)),
            "s_gate": ((l, d, sf), s),
            "s_up": ((l, d, sf), s),
            "s_down": ((l, sf, d), res / math.sqrt(sf)),
            "attention": {
                "wq": ((la, d, dm.heads * dm.hd), s),
                "wk": ((la, d, dm.kv * dm.hd), s),
                "wv": ((la, d, dm.kv * dm.hd), s),
                "wo": ((la, dm.heads * dm.hd, d),
                       res / math.sqrt(dm.heads * dm.hd)),
            },
            "mamba": {
                "in_proj": ((lm, d, 2 * di + 2 * dm.m_state + dm.m_heads), s),
                "out_proj": ((lm, di, d), res / math.sqrt(di)),
                "conv_w": ((lm, dm.m_conv, di + 2 * dm.m_state),
                           1 / math.sqrt(dm.m_conv)),
            },
        },
    }


def make_weights(config: Dict[str, Any], seed: int, dtype=None,
                 out_shardings=None):
    """The whole tree in one jitted call, from the seed (the chip's own bit
    generator, stacked leaves a layer at a time).  ``dt_bias`` is drawn so
    that ``dt = softplus(dt_bias + ...)`` spreads over 1e-3 .. 1e-1,
    ``A = -exp(A_log)`` over -1 .. -16, ``D`` and the norm gains near 1, the
    conv's bias near 0: steps and decays far from 0 and 1."""
    import jax
    import jax.numpy as jnp
    dtype = dtype or jnp.bfloat16
    sh = shapes(config)
    dm, n = ref.dims(config), _counts(config)
    l, lm = n["layers"], n["mamba"]
    di = dm.m_heads * dm.m_hd

    def build(key):
        keys = iter(jax.random.split(key, 40))

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

        def uniform(shape, lo, hi):
            return jax.random.uniform(next(keys), shape, jnp.float32, lo, hi)

        def tree(group):
            return {k: (tree(v) if isinstance(v, dict) else draw(v))
                    for k, v in sorted(group.items())}

        layers = tree(sh["layers"])
        layers["attn_norm"] = near((l, dm.d), 1.0, 0.1)
        layers["mlp_norm"] = near((l, dm.d), 1.0, 0.1)
        mam = layers["mamba"]
        # B and C columns of in_proj at BC_GAIN: [z | x | B | C | dt]
        gain = jnp.ones((mam["in_proj"].shape[-1],), dtype).at[
            2 * di:2 * di + 2 * dm.m_state].set(BC_GAIN)
        mam["in_proj"] = mam["in_proj"] * gain
        dt0 = jnp.exp(uniform((lm, dm.m_heads), math.log(1e-3),
                              math.log(1e-1)))
        mam["dt_bias"] = (dt0 + jnp.log(-jnp.expm1(-dt0))).astype(dtype)
        mam["A_log"] = jnp.log(uniform((lm, dm.m_heads), 1.0, 16.0)
                               ).astype(dtype)
        mam["D"] = near((lm, dm.m_heads), 1.0, 0.1)
        mam["norm"] = near((lm, di), 1.0, 0.1)
        mam["conv_b"] = near((lm, di + 2 * dm.m_state), 0.0, 0.1)
        # out_proj with zero sum over each head's channels.  The part of a
        # mamba layer's y that is the same for every token (silu's positive
        # mean through x, B . C and the gate) is constant within a head, so
        # it then adds nothing to the residual stream.  With a tied head
        # the embedding has to stay small, so nothing else keeps rows
        # apart: ten random layers each added ~8% of common power, the
        # rows' hidden states grew alike with depth and by layers 7-9 one
        # expert took 55-62 of a decode step's 64 rows (PERF.md §6 PR 32).
        op = mam["out_proj"].astype(jnp.float32).reshape(
            lm, dm.m_heads, dm.m_hd, dm.d)
        mam["out_proj"] = (op - op.mean(axis=2, keepdims=True)).reshape(
            lm, di, dm.d).astype(dtype)
        return {"embed": draw(sh["embed"]), "layers": layers,
                "norm_f": near((dm.d,), 1.0, 0.1)}

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
    context: the ATTENTION layers' only (the mamba layers keep none)."""
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


def ssm_state_shape(config: Dict[str, Any], rows: int) -> List[int]:
    """One mamba layer's recurrent state over the row slots, as the decode
    step's update reads and writes it: ``[rows, heads * head size, state]``
    float32 (the program keeps heads and head channels as one dim)."""
    dm = ref.dims(config)
    return [rows, dm.m_heads * dm.m_hd, dm.m_state]


def ssd_carry_shape(config: Dict[str, Any]) -> List[int]:
    """What a prefill's SSD scan carries from chunk to chunk: one row's
    state, ``[1, heads, head size, state]`` float32."""
    dm = ref.dims(config)
    return [1, dm.m_heads, dm.m_hd, dm.m_state]


def state_bytes_per_row(config: Dict[str, Any], itemsize: int = 2) -> int:
    """Bytes of recurrent state one row slot holds over all mamba layers,
    whatever its context: the float32 SSM state and the conv tail."""
    dm = ref.dims(config)
    ssm = dm.m_heads * dm.m_hd * dm.m_state * 4
    conv = (dm.m_conv - 1) * (dm.m_heads * dm.m_hd + 2 * dm.m_state) * itemsize
    return _counts(config)["mamba"] * (ssm + conv)


def ssm_step_bytes(config: Dict[str, Any], rows: int) -> int:
    """Bytes of SSM state one decode step of ``rows`` rows has to read and
    write, over all mamba layers: the state is float32, read once and
    written once.  ~2 flops a byte: bound by the bytes."""
    dm = ref.dims(config)
    return (2 * rows * _counts(config)["mamba"]
            * dm.m_heads * dm.m_hd * dm.m_state * 4)


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
    least one assignment (the program counts them: the tick ring's
    ``moe_experts_touched``; all 36 of a layer in most decode steps of 64
    rows), by kernel: the gate and up matrices, and the down matrix.  A
    decode step is bound by these bytes: an expert takes ~9 of 64 rows,
    ~18 flops a byte."""
    dm = ref.dims(config)
    one = dm.d * dm.f * itemsize
    return {"moe_grouped_swiglu": 2 * touched * one,
            "moe_grouped_matmul": touched * one}
