"""The Pallas kernels of the main path compile for a v5e — with no chip.

The TPU compiler is installed wherever libtpu is, and compiles for a device
that is *described*, not attached (``jax.experimental.topologies``).  That
catches what interpret mode cannot: tilings Mosaic refuses, too much VMEM,
lowerings the installed JAX no longer has.  Nothing runs, so nothing here
says anything about results or speed.  Shapes are the flagship's (bf16,
8 heads of 64, 8 layers, page 64, rows 8, max_len 1024) and the variants
the model code can select (GQA, head dim 128, windows, int8 caches, the
shard_map wrappers, ring and Ulysses inner kernels).

Every case forces the kernel path (``use_pallas=True`` / ``impl="flash"``):
the ``jax.default_backend()`` gates see the CPU here.
"""

import os
import re
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from tfmesos_tpu.ops.attention import (flash_attention, flash_decode,
                                       flash_decode_paged,
                                       sharded_flash_attention,
                                       sharded_flash_decode)
from tfmesos_tpu.ops.quant import QTensor, quantize_int8
from tfmesos_tpu.parallel.ring_attention import ring_attention
from tfmesos_tpu.parallel.ulysses import ulysses_attention

BF16, I8, I32, F32 = jnp.bfloat16, jnp.int8, jnp.int32, jnp.float32
B, H, D, L = 8, 8, 64, 8            # rows, heads, head dim, layers
M, PAGE, NP, POOL = 1024, 64, 16, 129   # max_len, page, table width, pages


@pytest.fixture(scope="module")
def topo():
    """A described 2x2 v5e.  The persistent compile cache is off around
    these compiles: an executable for an unattached device is written to it
    but cannot be read back, and every later run would warn."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no libtpu, or it refuses
        pytest.skip(f"cannot describe a v5e topology here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _grad(fn):
    return jax.grad(lambda q, k, v: fn(q, k, v).astype(F32).sum(),
                    argnums=(0, 1, 2))


def _flash(**kw):
    return lambda q, k, v: flash_attention(q, k, v, causal=True,
                                           use_pallas=True, **kw)


def _paged(q, k, v, table, pos, layer, *self_kv):
    return flash_decode_paged(q, k, v, table, pos, layer=layer,
                              use_pallas=True, self_kv=self_kv or None)


def _linear(q, k, v, pos, layer):
    return flash_decode(q, k, v, pos, layer=layer, use_pallas=True)


def _qtensor(shape):
    """An int8 cache/pool leaf with its lane-major scales."""
    return QTensor((shape, I8), (shape[:-2] + (1, shape[-2]), F32))


_POS, _LAYER, _TABLE = ((B,), I32), ((), I32), ((B, NP), I32)
_POOL = ((L, POOL, H, PAGE, D), BF16)


def _grouped(tile):
    """The grouped expert layer's two kernels over one sorted buffer, the
    expert stacks whole and the layer index riding the scalar prefetch."""
    from tfmesos_tpu.ops import moe

    def fn(x, wg, wu, wd, tile_expert, live, layer):
        mid = moe.grouped_swiglu(x, wg, wu, tile_expert, live, tile,
                                 layer=layer, use_pallas=True)
        return moe.grouped_matmul(mid, wd, tile_expert, live, tile,
                                  layer=layer, use_pallas=True)
    return fn


def _cell_experts(tokens):
    """A step of ``granite4h.gen_batch``: 36 held experts of 10 layers,
    4096 -> 768 -> 4096, ``tokens`` x top-10 assignments over 72."""
    from tfmesos_tpu.ops import moe
    tile = moe.pick_tile(tokens * 10, 72)
    rows = -(-tokens * 10 // tile) * tile + 36 * tile
    return (None, lambda m: _grouped(tile),
            [((rows, 4096), BF16), ((10, 36, 4096, 768), BF16),
             ((10, 36, 4096, 768), BF16), ((10, 36, 768, 4096), BF16),
             ((rows // tile,), I32), ((1,), I32), ((), I32)], 2)


def _cell_paged(rows, kv, g, width, pages, t, int8=False):
    """A decode step of a benchmark cell: 16 layers, heads of 128, page 64."""
    shape = (16, pages, kv, PAGE, 128)
    pool = _qtensor(shape) if int8 else (shape, BF16)
    return (None, lambda m: _paged,
            [((rows, t, kv * g, 128), BF16), pool, pool,
             ((rows, width), I32), ((rows,), I32), _LAYER]
            + [((rows, t, kv, 128), BF16)] * 2, 1)


# name -> (mesh axes or None, fn(mesh), argument (shape, dtype[, spec])s,
#          kernels expected in the program)
CASES = {
    "flash_fwd": (None, lambda m: _flash(),
                  [((B, 2048, H, D), BF16)] * 3, 1),
    "flash_fwd_bwd": (None, lambda m: _grad(_flash()),
                      [((B, 2048, H, D), BF16)] * 3, 3),
    "flash_gqa_fwd_bwd": (None, lambda m: _grad(_flash()),
                          [((B, 2048, H, D), BF16)]
                          + [((B, 2048, 2, D), BF16)] * 2, 3),
    "flash_window_fwd_bwd": (None, lambda m: _grad(_flash(window=256)),
                             [((B, 2048, H, D), BF16)] * 3, 3),
    # A length with no 8-aligned divisor runs as ONE block; Mosaic refused
    # its dynamically-sliced K read (found by transformer.generate on the
    # chip at a 410-token prompt) until the single block was read whole.
    "flash_odd_length_fwd_bwd": (None, lambda m: _grad(_flash()),
                                 [((1, 410, H, D), BF16)] * 3, 3),
    "flash_d128_fwd_bwd": (None, lambda m: _grad(_flash()),
                           [((4, 1024, H, 128), BF16)] * 3, 3),
    # The benchmark's prefills (32 query heads over 8 KV heads of 128): the
    # widest the docqa_batch cell offers and a chat width, both odd
    # multiples of the 64-token bucket, so the forward's tile (496 x 512,
    # 352 x 384) divides neither and a KV head's K/V is resident in VMEM
    # at its longest.  Forward only: serving never differentiates.
    "flash_fwd_gqa_t7744": (None, lambda m: _flash(),
                            [((1, 7744, 32, 128), BF16)]
                            + [((1, 7744, 8, 128), BF16)] * 2, 1),
    "flash_fwd_gqa_t704": (None, lambda m: _flash(),
                           [((1, 704, 32, 128), BF16)]
                           + [((1, 704, 8, 128), BF16)] * 2, 1),
    "decode_linear": (None, lambda m: _linear,
                      [((B, H, D), BF16)]
                      + [((L, B, H, M, D), BF16)] * 2 + [_POS, _LAYER], 1),
    "decode_linear_int8": (None, lambda m: _linear,
                           [((B, H, D), BF16)]
                           + [_qtensor((L, B, H, M, D))] * 2
                           + [_POS, _LAYER], 1),
    "paged_t1": (None, lambda m: _paged,
                 [((B, H, D), BF16), _POOL, _POOL, _TABLE, _POS, _LAYER], 1),
    "paged_t1_self": (None, lambda m: _paged,
                      [((B, 1, H, D), BF16), _POOL, _POOL, _TABLE, _POS,
                       _LAYER] + [((B, 1, H, D), BF16)] * 2, 1),
    "paged_t8_self": (None, lambda m: _paged,
                      [((B, 8, H, D), BF16), _POOL, _POOL, _TABLE, _POS,
                       _LAYER] + [((B, 8, H, D), BF16)] * 2, 1),
    "paged_int8_t1_self": (None, lambda m: _paged,
                           [((B, 1, H, D), BF16)]
                           + [_qtensor((L, POOL, H, PAGE, D))] * 2
                           + [_TABLE, _POS, _LAYER]
                           + [((B, 1, H, D), BF16)] * 2, 1),
    # Grouped queries over a one-token self chunk: Mosaic refused this
    # lowering until the self scores were widened to f32.
    "paged_gqa_t1_self": (None, lambda m: _paged,
                          [((B, 1, H, D), BF16)]
                          + [((L, POOL, 2, PAGE, D), BF16)] * 2
                          + [_TABLE, _POS, _LAYER]
                          + [((B, 1, 2, D), BF16)] * 2, 1),
    # The benchmark's decode steps (PR 31): the paged kernel's K/V block is
    # several pages, each a page slot of the pipeline's, and its VMEM is worked
    # out from these shapes.  Mistral-7B's (32 rows, 8 KV heads x 4 queries
    # of 128, table width 128: 8 heads x 8 pages a step) and EvaByte's (16
    # rows, 32 heads x 1, width 64: 16 heads x 8 pages), the 16-layer pools
    # of their cells, the deferred self operand; a speculative chunk
    # (t = 4) and an int8 pool.
    # granite4h.gen_batch: the grouped expert kernels at a decode step's 64
    # rows (tiles of 16), a mid prefill (tiles of 32) and the widest (128),
    # and the paged kernel at 64 rows over the one-layer pool
    "moe_grouped_decode_r64": _cell_experts(64),
    "moe_grouped_prefill_t512": _cell_experts(512),
    "moe_grouped_prefill_t4096": _cell_experts(4096),
    "paged_granite_w128_self": (None, lambda m: _paged,
                                [((64, 1, 32, 128), BF16)]
                                + [((1, 4096, 8, PAGE, 128), BF16)] * 2
                                + [((64, 128), I32), ((64,), I32), _LAYER]
                                + [((64, 1, 8, 128), BF16)] * 2, 1),
    "paged_mistral_w128_self": _cell_paged(32, 8, 4, 128, 1300, 1),
    "paged_evabyte_w64_self": _cell_paged(16, 32, 1, 64, 372, 1),
    "paged_mistral_w128_t4_self": _cell_paged(32, 8, 4, 128, 1300, 4),
    "paged_mistral_w128_int8_self": _cell_paged(32, 8, 4, 128, 1300, 1,
                                                int8=True),
    "quantize_int8": (None, lambda m: lambda x: quantize_int8(
        x, use_pallas=True), [((4096, 512), F32)], 1),
    "quantize_int8_stochastic": (None, lambda m: lambda x: quantize_int8(
        x, stochastic=True, seed=3, use_pallas=True),
        [((4096, 512), F32)], 1),
    "sharded_flash_decode": (
        {"dp": 2, "tp": 2},
        lambda m: lambda q, k, v, pos, layer: sharded_flash_decode(
            q, k, v, pos, m, layer=layer, use_pallas=True),
        [((B, H, D), BF16, P("dp", "tp", None))]
        + [((L, B, H, M, D), BF16, P(None, "dp", "tp", None, None))] * 2
        + [((B,), I32, P("dp")), ((), I32, P())], 1),
    "sharded_flash_attention_fwd_bwd": (
        {"fsdp": 2, "tp": 2},
        lambda m: _grad(lambda q, k, v: sharded_flash_attention(
            q, k, v, m, causal=True, use_pallas=True)),
        [((B, 2048, H, D), BF16, P("fsdp", None, "tp", None))] * 3, 3),
    "ring_flash_fwd_bwd": (
        {"dp": 1, "sp": 4},
        lambda m: _grad(lambda q, k, v: ring_attention(
            q, k, v, m, causal=True, impl="flash")),
        [((2, 4096, H, D), BF16, P("dp", "sp", None, None))] * 3, 3),
    "ulysses_flash_fwd_bwd": (
        {"dp": 1, "sp": 4},
        lambda m: _grad(lambda q, k, v: ulysses_attention(
            q, k, v, m, causal=True, use_pallas=True)),
        [((2, 4096, H, D), BF16, P("dp", "sp", None, None))] * 3, 3),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(topo, name):
    axes, make, args, n_kernels = CASES[name]
    mesh = None
    if axes is not None:
        mesh = Mesh(np.array(topo.devices).reshape(tuple(axes.values())),
                    tuple(axes))

    def struct(shape, dtype, spec=None):
        sharding = (SingleDeviceSharding(topo.devices[0]) if mesh is None
                    else NamedSharding(mesh, spec))
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    def arg(a):
        if isinstance(a, QTensor):
            return QTensor(struct(*a.values), struct(*a.scales))
        return struct(*a)

    compiled = jax.jit(make(mesh)).lower(*map(arg, args)).compile()
    assert compiled.as_text().count("tpu_custom_call") >= n_kernels


# -- whole steps at the benchmark cells' widths --------------------------------
#
# ``decode_step`` of each benchmark configuration, compiled whole for the
# described v5e with the kernel paths forced (the gates ask
# ``jax.default_backend()``, which is the CPU here), over donated caches at the
# cells' sizes.  name -> (the program's configuration, pages in the pool, row
# slots of recurrent state or rings (0: a plain stack), the gates to force).
# A step is compiled once a file whichever tests read it.

def _step_models():
    from tfmesos_tpu.models import transformer
    cfg = partial(transformer.TransformerConfig, dtype=BF16, param_dtype=BF16)
    return {
        # Mistral-7B's widths, 16 layers (a small pool is placed in another
        # memory space and says nothing)
        "mistral": (cfg(
            vocab_size=32768, d_model=4096, n_layers=16, n_heads=32,
            n_kv_heads=8, d_ff=14336, max_seq_len=8192, rope_theta=1e6),
            1300, 0, ("attend",)),
        # EvaByte's: 32 heads of 128 over 32 K/V heads, a float32 stream
        "evabyte": (cfg(
            vocab_size=320, d_model=4096, n_layers=16, n_heads=32,
            n_kv_heads=32, d_ff=11008, max_seq_len=32768, rope_theta=1e5,
            attention="eva", eva_chunk=16, eva_window=2048, norm_eps=1e-5,
            norm_offset=True, residual_dtype=F32, logits_dtype=F32,
            n_pred_heads=8),
            372, 0, ("backend",)),
        # Granite-4.0-H-Small's, one period: 9 Mamba-2 layers and one
        # attention layer, 36 of 72 experts held
        "granite": (cfg(
            vocab_size=100352, d_model=4096, n_layers=10, n_heads=32,
            n_kv_heads=8, d_ff=768, max_seq_len=8192,
            layer_types=("mamba",) * 5 + ("attention",) + ("mamba",) * 4,
            mamba_heads=128, mamba_head_dim=64, mamba_state=128, rope=False,
            attn_scale=0.0078125, embed_scale=12.0, residual_scale=0.22,
            logits_scale=16.0, tie_embeddings=True, norm_eps=1e-5,
            logits_dtype=F32, n_experts=72, top_k=10, moe_impl="grouped",
            experts_held=36, shared_d_ff=1536),
            4096, 64, ("attend", "moe", "ssm")),
        # Solar-Open2's, one period ``a k k k``, 40 of 320 experts held, a
        # sliced vocabulary
        "solar": (cfg(
            vocab_size=24576, d_model=4096, n_layers=4, n_heads=64,
            n_kv_heads=8, attn_head_dim=128, d_ff=1280, max_seq_len=8192,
            layer_types=("attention", "kda", "kda", "kda"), kda_heads=64,
            kda_head_dim=128, kda_neg_eigval=True, rope=False, attn_gate=True,
            norm_eps=1e-5, logits_dtype=F32, n_experts=320, top_k=8,
            moe_impl="grouped", experts_held=40, shared_d_ff=1280,
            router_score="sigmoid"),
            9216, 192, ("attend", "moe", "kda")),
        # Laguna-XS.2's: [full | window x 3 | full] behind a leading dense
        # layer, 256 experts of 512 all held
        "laguna": (cfg(
            vocab_size=100352, d_model=2048, n_layers=5, n_heads=48,
            n_kv_heads=8, attn_head_dim=128, d_ff=8192, max_seq_len=17408,
            layer_types=("attention", "window", "window", "window",
                         "attention"),
            window=512, window_heads=64,
            window_rope=transformer.RopeSpec(theta=10000.0),
            attn_rope=transformer.RopeSpec(theta=500000.0, fraction=0.5,
                                           yarn=(64.0, 4096, 64.0, 1.0)),
            ffn_types=("dense",) + ("sparse",) * 4, expert_d_ff=512,
            attn_gate="head", norm_eps=1e-6, logits_dtype=F32, n_experts=256,
            top_k=8, moe_impl="grouped", shared_d_ff=512,
            router_score="sigmoid", routed_scale=2.5),
            7168, 128, ("attend", "moe", "backend")),
        # MiMo-V2-Flash's: [full | window x 4 | full | window] behind a
        # leading dense layer, keys of 192 and values of 128 channels, 4
        # against 8 K/V heads, a sink a window head, 16 of 256 experts held,
        # a sliced vocabulary
        "mimo": (cfg(
            vocab_size=19072, d_model=4096, n_layers=7, n_heads=64,
            n_kv_heads=4, attn_head_dim=192, attn_v_head_dim=128,
            attn_value_scale=0.707, d_ff=16384, max_seq_len=18944,
            layer_types=("attention",) + ("window",) * 4
            + ("attention", "window"),
            window=128, window_kv_heads=8, window_sink=True,
            window_rope=transformer.RopeSpec(theta=10000.0,
                                             fraction=64 / 192),
            attn_rope=transformer.RopeSpec(theta=5e6, fraction=64 / 192),
            ffn_types=("dense",) + ("sparse",) * 6, expert_d_ff=2048,
            norm_eps=1e-5, logits_dtype=F32, n_experts=256, top_k=8,
            moe_impl="grouped", experts_held=16, router_score="sigmoid"),
            15360, 128, ("attend", "moe", "backend")),
    }


_STEPS = {}


def _compiled_step(topo, model, rows, t, width, start=None, int8=False,
                   max_len=None):
    """``(cfg, params, compiled, text)`` of ``model``'s ``decode_step`` over
    ``rows`` x ``t`` tokens and a page table ``width`` wide; ``params`` are
    the parameters' shapes.  ``start``: ``"ragged"`` a traced [rows] vector
    (what ``t == 1`` defaults to), ``"traced"`` a traced scalar, or a static
    int (``t > 1``: 0, a prefill from an empty cache).  ``max_len``: over the
    un-paged linear cache of so many slots a row (``init_cache``) in the
    pool's place; ``width`` says nothing then."""
    from tfmesos_tpu.models import transformer
    from tfmesos_tpu.ops import attention, kda, moe, ssm

    if start is None:
        start = "ragged" if t == 1 else 0
    key = (model, rows, t, width, start, int8, max_len)
    if key in _STEPS:
        return _STEPS[key]
    cfg, n_pages, slots, gates = _step_models()[model]
    one_chip = SingleDeviceSharding(topo.devices[0])

    def struct(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    params = jax.eval_shape(
        lambda: transformer.init_params(cfg, jax.random.PRNGKey(0)))
    if max_len is not None:
        cache = dict(jax.eval_shape(lambda: transformer.init_cache(
            cfg, rows, max_len, quantized=int8)))
    else:
        cache = dict(jax.eval_shape(lambda: transformer.init_paged_cache(
            cfg, n_pages, PAGE, quantized=int8)))
    if slots:
        cache["state"] = jax.eval_shape(
            lambda: transformer.init_row_state(cfg, slots))
        if t > 1:
            cache["slots"] = jnp.zeros((rows,), I32)
            cache["valid"] = jnp.zeros((rows,), I32)
    if max_len is None:
        cache["pages"] = jnp.zeros((rows, width), I32)
    tokens = jax.ShapeDtypeStruct((rows, t), I32, sharding=one_chip)
    # A static start is closed over; a traced one is the step's last argument.
    traced = () if isinstance(start, int) else (jax.ShapeDtypeStruct(
        (rows,) if start == "ragged" else (), I32, sharding=one_chip),)
    step = jax.jit(lambda p, c, tok, *pos: transformer.decode_step(
        cfg, p, c, tok, *(pos or (start,))), donate_argnums=1)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(transformer, "_decode_kernel_kwargs",
                   lambda *a, **k: {"use_pallas": True})
        if "attend" in gates:
            mp.setattr(transformer, "attend",
                       partial(attention.attend, use_pallas=True))
        if "backend" in gates:
            # the chunk's own part through the flash kernel and the window
            # mixer's flash_decode, as on the chip: by the backend
            mp.setattr(attention.jax, "default_backend", lambda: "tpu")
        for name, mod in (("moe", moe), ("ssm", ssm), ("kda", kda)):
            if name in gates:
                mp.setattr(mod, "_on_tpu", lambda use: True)
        compiled = step.lower(
            *jax.tree_util.tree_map(struct, (params, cache)), tokens,
            *traced).compile()
    _STEPS[key] = cfg, params, compiled, compiled.as_text()
    return _STEPS[key]


# -- the deferred K/V commit keeps the pool's layout (PR 25) ------------------
#
# Mistral's step over its donated page pool (1300 pages of 64, 32 rows, table
# width 128).  The commit after the layer scan (``_paged_cache_write_all``)
# must scatter into the pool in the pool's own layout: a scatter the TPU compiler gives another
# operand layout comes wrapped in two copies of the WHOLE pool per leaf,
# which cost 33 ms of a 53 ms decode block on the chip.  name -> (int8 pool,
# rows, t, start position: "ragged" a traced [B] vector, "traced" a traced
# scalar, or a static int).
COMMIT_CASES = {
    "t1_ragged": (False, 32, 1, "ragged"),
    "t8_traced_start": (False, 32, 8, "traced"),
    "prefill_t704": (False, 1, 704, 0),
    "int8_t1_ragged": (True, 32, 1, "ragged"),
    "int8_prefill_t704": (True, 1, 704, 0),
}


@pytest.mark.parametrize("name", sorted(COMMIT_CASES))
def test_paged_commit_never_relayouts_the_pool(topo, name):
    quantized, rows, t, start = COMMIT_CASES[name]
    n_pages = _step_models()["mistral"][1]
    cfg, _, _, text = _compiled_step(topo, "mistral", rows, t, 128, start,
                                     int8=quantized)
    assert "tpu_custom_call" in text            # the kernel path was taken
    assert " scatter(" in text                  # and the commit is in there
    # The K/V leaf ([L, P, KV, page, Dh]; an int8 pool's ``values``).  The
    # int8 pool's small lane-major scales leaf is not held to this: the
    # paged kernel takes it in another layout than the program's
    # parameters have, whatever the commit does.
    leaf = (f"{'s8' if quantized else 'bf16'}[{cfg.n_layers},{n_pages},"
            f"{cfg.kv_heads},{PAGE},{cfg.head_dim}]")
    moved = re.findall(r"= " + re.escape(leaf)
                       + r"\S* (?:copy|transpose)\([^)]*\)", text)
    assert not moved, f"{leaf} is relayouted: {moved[:2]}"


# -- no step moves a stacked weight (PR 44) ------------------------------------
#
# Every matmul reads its layer of a stacked parameter where the parameter
# lies.  Where an elementwise op, a slice or a pad read a projection's heads,
# the TPU compiler folded the head split into the dot, which then wanted the
# weight as [heads, Dh, in]: each run of the program cut a layer of ``wq`` and
# of ``wk`` out of the stack and wrote it again transposed (a table under 128
# pages: inside the scan over layers; from 128 pages on, where the scan is
# unrolled by two: the whole stack, hoisted), 1.0 ms of a 12.6 ms Mistral
# decode block on the chip; every other configuration's steps did the same
# to their attention, window or KDA projections.  The five
# configurations' decode and prefill steps at their cells' widths, Mistral's
# in both regimes.  name -> (model, rows, t, table width, start).
WEIGHT_CASES = {
    "mistral_decode_w32": ("mistral", 32, 1, 32, None),
    "mistral_decode_w128": ("mistral", 32, 1, 128, None),
    "mistral_prefill_t704_w32": ("mistral", 1, 704, 32, None),
    "mistral_prefill_t704_w128": ("mistral", 1, 704, 128, None),
    "evabyte_decode": ("evabyte", 16, 1, 62, None),
    "evabyte_prefill_window_2048": ("evabyte", 1, 2048, 62, "traced"),
    "granite_decode": ("granite", 64, 1, 128, None),
    "granite_prefill_t1024": ("granite", 1, 1024, 128, None),
    "solar_decode": ("solar", 192, 1, 128, None),
    "solar_prefill_t1024": ("solar", 1, 1024, 128, None),
    "laguna_decode": ("laguna", 128, 1, 17408 // PAGE, None),
    "laguna_prefill_t448": ("laguna", 1, 448, 17408 // PAGE, None),
    "mimo_decode": ("mimo", 128, 1, 18944 // PAGE, None),
    "mimo_prefill_t2112": ("mimo", 1, 2112, 18944 // PAGE, None),
}


def _weight_moves(params, text, min_bytes=1 << 20):
    """The instructions of the compiled ``text`` that copy, transpose or
    materialise a stacked weight of ``params["layers"]``, a layer of it, a
    group of layers or the whole stack: a top-level ``copy`` / ``transpose``
    one of whose operands has the shape of the stack ``[L, ...]``, of one
    layer (``[1, ...]`` or ``[...]``) or of the stack as a scan unrolled by
    ``u`` sees it (``[L/u, u, ...]`` and an iteration's ``[u, ...]``, for
    every ``u`` dividing ``L``), and a top-level fusion that reads one such
    shape and results in one (a slice written out; a dot reads one and
    results in activations).  Matrices of at least ``min_bytes`` a layer;
    instructions inside a fusion's computation are not the program's."""
    views = set()
    for leaf in jax.tree_util.tree_leaves(params["layers"]):
        if leaf.ndim < 3 or (leaf.size // leaf.shape[0]
                             * leaf.dtype.itemsize) < min_bytes:
            continue
        dt = {"bfloat16": "bf16", "float32": "f32"}[str(leaf.dtype)]
        n, rest = leaf.shape[0], ",".join(map(str, leaf.shape[1:]))
        views.add(f"{dt}[{rest}]")
        for u in range(1, n + 1):
            if n % u == 0:
                views |= {f"{dt}[{u},{rest}]", f"{dt}[{n // u},{u},{rest}]"}
    shape_of = dict(re.findall(r"%([\w.\-]+) = (\w+\[[\d,]*\])", text))
    fused = set(re.findall(r" fusion\(.*?calls=%([\w.\-]+)", text))
    moves, inside = [], False
    for ln in text.splitlines():
        head = re.match(r"(?:ENTRY )?%([\w.\-]+) \(.*\{$", ln)
        if head:
            inside = head.group(1) in fused
            continue
        m = re.match(r"\s*(?:ROOT )?%\S+ = (\w+\[[\d,]*\])\S* "
                     r"(copy|transpose|fusion)\(([^)]*)\)", ln)
        if inside or not m:
            continue
        result, op, operands = m.groups()
        reads = any(shape_of.get(o) in views
                    for o in re.findall(r"%([\w.\-]+)", operands))
        if reads and (op != "fusion" or result in views):
            moves.append(ln.strip()[:160])
    return moves


@pytest.mark.parametrize("name", sorted(WEIGHT_CASES))
def test_step_never_copies_a_stacked_weight(topo, name):
    model, rows, t, width, start = WEIGHT_CASES[name]
    _, params, _, text = _compiled_step(topo, model, rows, t, width, start)
    assert "tpu_custom_call" in text            # the kernel paths were taken
    moves = _weight_moves(params, text)
    assert not moves, f"{len(moves)} weight moves, e.g. {moves[:3]}"


def test_weight_moves_reads_the_parents_program():
    """The reader on the lines the parent's Mistral steps compiled to (the
    per-layer form, the hoisted form) and on lines it must pass over."""
    params = {"layers": {"wq": jax.ShapeDtypeStruct((16, 4096, 4096), BF16),
                         "norm": jax.ShapeDtypeStruct((16, 4096), BF16)}}
    sliced = ("  %gte.1 = bf16[16,4096,4096]{2,1,0} get-tuple-element(%t)\n"
              "  %slice_fusion.6 = bf16[1,4096,4096]{2,1,0:T(8,128)(2,1)} "
              "fusion(%gte.1, %i), kind=kLoop, calls=%fc.95\n"
              "  %copy.130 = bf16[1,4096,4096]{1,2,0:T(8,128)(2,1)} "
              "copy(%slice_fusion.6), metadata={}\n")
    assert len(_weight_moves(params, sliced)) == 2
    hoisted = ("  %p.1 = bf16[16,4096,4096]{2,1,0} parameter(9)\n"
               "  %copy.177 = bf16[16,4096,4096]{1,2,0:T(8,128)(2,1)} "
               "copy(%p.1), sharding={replicated}\n")
    assert len(_weight_moves(params, hoisted)) == 1
    # PR 48's parent: the weights as the scan's xs under ``unroll=2``, two
    # layers of a stacked MLP weight written out once an iteration
    params["layers"]["w_down"] = jax.ShapeDtypeStruct((16, 14336, 4096), BF16)
    grouped = ("  %gte = bf16[8,2,14336,4096]{3,2,1,0} "
               "get-tuple-element(%t), index=5\n"
               "  %dynamic-slice_bitcast_fusion.57 = bf16[2,14336,4096]"
               "{2,1,0:T(8,128)(2,1)} fusion(%gte, %i), kind=kLoop, "
               "calls=%fc.57\n")
    assert len(_weight_moves(params, grouped)) == 1
    clean = ("%fc.95 (a: bf16[16,4096,4096]) -> bf16[1,4096,4096] {\n"
             "  %a = bf16[16,4096,4096]{2,1,0} parameter(0)\n"
             "  %copy.3 = bf16[16,4096,4096]{2,1,0} copy(%a)\n"
             "}\n"
             "%body (t: (bf16[16,4096,4096])) -> bf16[32,4096] {\n"
             "  %gte.1 = bf16[16,4096,4096]{2,1,0} get-tuple-element(%t)\n"
             "  %fusion.7 = bf16[32,4096]{1,0} fusion(%gte.1, %x), "
             "kind=kOutput, calls=%fc.95\n"
             "  %x.1 = bf16[4096,4096]{1,0} fusion(%y, %z), kind=kLoop, "
             "calls=%fc.96\n"
             "}\n")
    assert _weight_moves(params, clean) == []


# -- the un-paged buffer keeps its unroll, and its weights in place (PR 49) ---
#
# ``decode_step`` over ``init_cache``'s linear buffer at Mistral's widths, 4
# rows, one token a row (``generate``'s step; no cell runs it).  From 8,192
# slots a row the layer loop is unrolled by two, which the text shows as two
# ``flash_decode`` calls in the loop's body; under that a short buffer and
# every page pool keep the rolled loop's one.  With the weights as the scan's
# xs the unrolled form wrote two layers of every stacked weight out once an
# iteration (the parent's step at 16,384 slots: 7 such moves, 0.79 GB of
# temporaries); read where the stack lies, no form moves one.
# name -> (max_len, start, ``flash_decode`` calls in the program)
UNPAGED_CASES = {
    "m16384_scalar": (16384, "traced", 2),
    "m16384_ragged": (16384, "ragged", 2),
    "m4096_scalar": (4096, "traced", 1),
}


@pytest.mark.parametrize("name", sorted(UNPAGED_CASES))
def test_unpaged_step_unrolls_by_its_length_and_moves_no_weight(topo, name):
    max_len, start, calls = UNPAGED_CASES[name]
    _, params, _, text = _compiled_step(topo, "mistral", 4, 1, None, start,
                                        max_len=max_len)
    assert len(re.findall(r"%flash_decode[.\d]* = \S+ custom-call\(",
                          text)) == calls
    moves = _weight_moves(params, text)
    assert not moves, f"{len(moves)} weight moves, e.g. {moves[:3]}"


# -- a typed stack's state store and pool stay where they are (PR 32) ---------
#
# ``decode_step`` over a typed stack at Granite-4.0-H-Small's widths (10
# layers, 9 of them Mamba-2: a 2.4 GB float32 row-state store beside a
# one-layer page pool), compiled whole for the described v5e with the kernel
# paths forced.  The store and the pool are donated and must be updated in
# place: with heads and head channels as two dims of the store the compiler
# gave it the layout the SSD scan's last einsum liked and wrapped the
# prefill's state write in two copies of the WHOLE store.  The decode step
# updates a layer's state in ONE pass, the ``ssm_update`` kernel (PR 33): as
# XLA compiled it, a second fusion read the store again for y.

@pytest.mark.parametrize("name,rows,t", [("decode_r64", 64, 1),
                                         ("prefill_t1024", 1, 1024)])
def test_typed_step_never_relayouts_the_state_store(topo, name, rows, t):
    slots = _step_models()["granite"][2]
    _, _, _, text = _compiled_step(topo, "granite", rows, t, 128)
    for kernel in ("moe_grouped_swiglu", "moe_grouped_matmul"):
        assert kernel in text, kernel
    assert ("flash_decode_paged" if t == 1 else "flash_attention_fwd") in text
    for leaf in (f"f32[9,{slots},8192,128]", f"bf16[1,4096,8,{PAGE},128]",
                 "bf16[10,36,4096,768]", "bf16[10,36,768,4096]"):
        moved = re.findall(r"= " + re.escape(leaf)
                           + r"\S* (?:copy|transpose|slice)\([^)]*\)", text)
        assert not moved, f"{leaf} is copied: {moved[:2]}"
    if t == 1:
        # The one-token update is ONE pass (PR 33): the Pallas kernel takes
        # the store and returns it, and no fusion reads it a second time
        # (control flow, tuples and the kernel itself may name it).
        store = f"f32[9,{slots},8192,128]"
        calls = [ln for ln in text.splitlines()
                 if " custom-call(" in ln and "ssm_update" in ln]
        assert calls
        for ln in calls:
            result, operands = ln.split(" custom-call(", 1)
            assert store in result and store in operands, ln[:200]
        again = [ln.strip()[:160] for ln in text.splitlines()
                 if re.search(r" fusion\(.*" + re.escape(store), ln)]
        assert not again, f"a fusion reads the store: {again[:2]}"


# -- a KDA stack's state store stays where it is (PR 37) -----------------------
#
# ``decode_step`` over a typed stack at Solar-Open2's widths as the cell
# ``solar2.reason_batch`` runs it (one period ``a k k k``: a 2.4 GB float32
# KDA state store over 192 row slots beside a one-layer page pool, 40 held
# experts of 320 a layer, a sliced vocabulary), compiled whole for the
# described v5e with the kernel paths forced.  The store, the pool and the
# expert stacks must be updated or read in place: no copy, transpose or slice
# of any of them.  The one-token update is ONE pass, the ``kda_update`` kernel
# (PR 38; ``ops/kda.py``): it takes the donated store and returns it, and no
# fusion names the store or one whole layer of it.  As XLA compiled the
# update, it copied the layer out of the store, reduced the copy and then
# read and wrote the layer in place: five passes for two.

@pytest.mark.parametrize("name,rows,t", [("decode_r192", 192, 1),
                                         ("prefill_t1024", 1, 1024)])
def test_kda_step_never_relayouts_the_state_store(topo, name, rows, t):
    _, n_pages, slots, _ = _step_models()["solar"]
    _, _, compiled, text = _compiled_step(topo, "solar", rows, t, 128)
    for kernel in ("moe_grouped_swiglu", "moe_grouped_matmul"):
        assert kernel in text, kernel
    assert ("flash_decode_paged" if t == 1 else "flash_attention_fwd") in text
    store = f"f32[3,{slots},8192,128]"
    assert store in text
    for leaf in (store, f"f32[3,{slots},64,128,128]",
                 f"bf16[1,{n_pages},8,{PAGE},128]",
                 "bf16[4,40,4096,1280]", "bf16[4,40,1280,4096]"):
        moved = re.findall(r"= " + re.escape(leaf)
                           + r"\S* (?:copy|transpose|slice)\([^)]*\)", text)
        assert not moved, f"{leaf} is copied: {moved[:2]}"
    if t == 1:
        # The one-token update is ONE pass (PR 38): the Pallas kernel takes
        # the store and returns it, and no fusion names the store or one
        # whole layer of it in any view (control flow, tuples, bitcasts and
        # the kernel itself may).
        calls = [ln for ln in text.splitlines()
                 if " custom-call(" in ln and "kda_update" in ln]
        assert calls
        for ln in calls:
            result, operands = ln.split(" custom-call(", 1)
            assert store in result and store in operands, ln[:200]
        views = (store, f"f32[3,{slots},64,128,128]",
                 f"f32[1,{slots},8192,128]", f"f32[{slots},8192,128]",
                 f"f32[{slots},64,128,128]")
        again = [ln.strip()[:160] for ln in text.splitlines()
                 if " fusion(" in ln and any(v in ln for v in views)]
        assert not again, f"a fusion passes the state: {again[:2]}"
    # beside 6.6 GB of weights, 2.5 GB of state and 2.4 GB of pool: a step's
    # temporaries (a decode step holds no layer of the state since PR 38:
    # its activations at 192 rows; a prefill's activations and one chunk's
    # [64, 64, 128] decay terms a head)
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < (1.5e8 if t == 1 else 2.5e9), \
        mem.temp_size_in_bytes


# -- EVA attention at EvaByte's widths (PR 28) ---------------------------------
#
# The programs ``ContinuousBatcher`` dispatches under ``attention="eva"``,
# compiled whole for the described v5e at the benchmark's widths (hidden
# 4096, 32 heads of 128 over 32 K/V heads, 16 layers, 372 pages of 64
# entries, 16 rows, a table of 62 pages): the paged kernel at
# ``q_per_kv = 1``, a window's prefill (the flash kernel over the chunk, the
# summaries before it in XLA blocks, its K/V committed layer by layer as
# whole pages) and the program that closes a window.  Each donates the pool
# and must leave it where it is.  name -> (what, rows, t).
EVA_CASES = {
    "decode_t1": ("step", 16, 1),
    "prefill_window_2048": ("step", 1, 2048),
    "prefill_tail_256": ("step", 1, 256),
    "close_window": ("close", 1, 0),
}


@pytest.mark.parametrize("name", sorted(EVA_CASES))
def test_eva_programs_compile_and_keep_the_pool_in_place(topo, monkeypatch,
                                                         name):
    from tfmesos_tpu.models import transformer
    from tfmesos_tpu.ops import attention

    what, rows, t = EVA_CASES[name]
    cfg, n_pages, _, _ = _step_models()["evabyte"]
    width = 62
    assert width == -(-cfg.cache_entries_peak(0, 32768) // PAGE)
    if what == "close":
        one_chip = SingleDeviceSharding(topo.devices[0])

        def struct(x):
            return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

        params = jax.tree_util.tree_map(struct, jax.eval_shape(
            lambda: transformer.init_params(cfg, jax.random.PRNGKey(0))))
        pool = jax.tree_util.tree_map(struct, jax.eval_shape(
            lambda: transformer.init_paged_cache(cfg, n_pages, PAGE)))
        table = jax.ShapeDtypeStruct((rows, width), I32, sharding=one_chip)
        scalar = jax.ShapeDtypeStruct((), I32, sharding=one_chip)
        monkeypatch.setattr(transformer, "_decode_kernel_kwargs",
                            lambda *a, **k: {"use_pallas": True})
        monkeypatch.setattr(attention.jax, "default_backend", lambda: "tpu")
        compiled = jax.jit(
            lambda p, c, tb, w: transformer.eva_close_window(cfg, p, c, tb, w),
            donate_argnums=1).lower(params, pool, table, scalar).compile()
        text = compiled.as_text()
    else:
        # a window's chunk starts at a traced position
        _, _, compiled, text = _compiled_step(
            topo, "evabyte", rows, t, width, "ragged" if t == 1 else "traced")
    assert " scatter(" in text
    if what == "step":
        assert "tpu_custom_call" in text
        # decode: flash_decode_paged; prefill: flash_attention_fwd
        assert ("flash_decode_paged" if t == 1 else
                "flash_attention_fwd") in text
    leaf = f"bf16[{cfg.n_layers},{n_pages},{cfg.kv_heads},{PAGE},128]"
    moved = re.findall(r"= " + re.escape(leaf)
                       + r"\S* (?:copy|transpose)\([^)]*\)", text)
    assert not moved, f"{leaf} is relayouted: {moved[:2]}"
    mem = compiled.memory_analysis()
    # beside 6.5 GB of weights and 6.2 GB of pool: a window's prefill keeps
    # its temporaries under half a gigabyte (its K/V is committed per layer)
    assert mem.temp_size_in_bytes < 0.5e9, mem.temp_size_in_bytes


def test_eva_pipelined_decode_program_compiles_for_v5e(topo, monkeypatch):
    """The program the EVA stack's batcher dispatches since PR 41 (the
    lagged carry: ``decode_block_pipelined``, what ``pipeline_depth=None``
    resolves to under EVA), lowered from a tiny batcher built here on the
    CPU and compiled for the described v5e with the paged kernel forced:
    the carry's three vectors come back beside the pool and the tokens, the
    pool is donated and updated in place."""
    from tfmesos_tpu.models import transformer
    from tfmesos_tpu.serving import ContinuousBatcher

    cfg = transformer.TransformerConfig(
        vocab_size=320, d_model=256, n_layers=2, n_heads=2, n_kv_heads=2,
        d_ff=512, max_seq_len=2048, rope_theta=1e5, dtype=BF16,
        param_dtype=BF16, attention="eva", eva_chunk=16, eva_window=1024,
        norm_eps=1e-5, norm_offset=True, residual_dtype=F32,
        logits_dtype=F32, n_pred_heads=8)
    rows, n_pages = 4, 24
    b = ContinuousBatcher(
        cfg, transformer.init_params(cfg, jax.random.PRNGKey(0)), rows=rows,
        max_len=2048, page_size=PAGE, n_pages=n_pages, prefill_bucket=PAGE,
        pipeline_depth=None)
    assert b._pipelined and b._decode.__name__ == "decode_block_pipelined"
    monkeypatch.setattr(transformer, "_decode_kernel_kwargs",
                        lambda *a, **k: {"use_pallas": True})
    one_chip = SingleDeviceSharding(topo.devices[0])

    def struct(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    vec = jax.ShapeDtypeStruct((rows,), I32, sharding=one_chip)
    mask = jax.ShapeDtypeStruct((rows,), jnp.bool_, sharding=one_chip)
    width = b._decode_widths()[-1]
    assert width == -(-cfg.cache_entries_peak(0, 2048) // PAGE) == 17
    table = jax.ShapeDtypeStruct((rows, width), I32, sharding=one_chip)
    compiled = b._decode.lower(
        jax.tree_util.tree_map(struct, b.params),
        jax.tree_util.tree_map(struct, b.pool), table, mask, vec, vec, vec,
        vec, vec, vec, vec).compile()
    text = compiled.as_text()
    assert "flash_decode_paged" in text and "tpu_custom_call" in text
    out = jax.tree_util.tree_leaves(compiled.output_shardings)
    assert len(out) == len(jax.tree_util.tree_leaves(b.pool)) + 4
    leaf = f"bf16[{cfg.n_layers},{n_pages},{cfg.kv_heads},{PAGE},128]"
    assert leaf in text
    moved = re.findall(r"= " + re.escape(leaf)
                       + r"\S* (?:copy|transpose)\([^)]*\)", text)
    assert not moved, f"{leaf} is relayouted: {moved[:2]}"



# -- window layers beside full ones at Laguna-XS.2's widths (PR 42) -----------
#
# The programs ``ContinuousBatcher`` dispatches for the typed stack [full |
# window x 3 | full] with a leading dense layer, compiled whole for the
# described v5e at the benchmark's widths (hidden 2048, 48 / 64 query heads
# over 8 K/V heads of 128, 256 experts of 512 all held, 7168 pages of 64, 128
# rows, rings of 512 positions): the paged kernel at 6 query heads a K/V
# head, ``flash_decode`` over the rings at 8, the windowed flash kernel, and
# a prompt past ``flash_max_keys`` (the segmented forward: whole, a KV head's
# K and V asked 48.5 MB of VMEM).  Each donates pool and rings and must leave
# them where they are.

@pytest.mark.parametrize("name,rows,t", [("decode_r128", 128, 1),
                                         ("prefill_t448", 1, 448),
                                         ("prefill_t16256", 1, 16256)])
def test_window_stack_compiles_and_keeps_the_rings_in_place(
        topo, name, rows, t):
    _, n_pages, slots, _ = _step_models()["laguna"]
    _, _, compiled, text = _compiled_step(topo, "laguna", rows, t,
                                          17408 // PAGE)
    for kernel in ("moe_grouped_swiglu", "moe_grouped_matmul"):
        assert kernel in text, kernel
    if t == 1:
        # the full layers' kernel at 6 query heads a K/V head, the rings' at 8
        assert re.search(r"%flash_decode_paged[.\d]* = bf16\[128,8,6,128\]",
                         text)
        assert re.search(r"%flash_decode[.\d]* = bf16\[128,8,8,128\]", text)
    else:
        # both kinds' prefill kernels, told apart by their query heads
        assert re.search(r"%flash_attention_fwd[.\d]* = \(bf16\[1,48,", text)
        assert re.search(r"%flash_attention_fwd[.\d]* = \(bf16\[1,64,", text)
    ring = f"bf16[3,{slots},8,512,128]"
    assert ring in text
    for leaf in (ring, f"bf16[2,{n_pages},8,{PAGE},128]",
                 "bf16[4,256,2048,512]", "bf16[4,256,512,2048]"):
        moved = re.findall(r"= " + re.escape(leaf)
                           + r"\S* (?:copy|transpose|slice)\([^)]*\)", text)
        assert not moved, f"{leaf} is copied: {moved[:2]}"
    # beside 7.7 GB of weights, 3.8 GB of pool and 0.8 GB of rings: a
    # step's temporaries (a 16 k prompt's sorted expert rows and its
    # attention operands; a decode step's activations at 128 rows)
    mem = compiled.memory_analysis()
    limit = {1: 5e7, 448: 1.5e8, 16256: 2.2e9}[t]
    assert mem.temp_size_in_bytes < limit, mem.temp_size_in_bytes


# -- keys and values of unequal width, a sink, K heads side by side (PR 45) ----
#
# MiMo-V2-Flash's first seven layers compiled whole for the described v5e at
# the benchmark's widths (hidden 4096, 64 query heads, keys of 192 and values
# of 128 channels, 4 K/V heads in a full layer and 8 in a window layer, 16 of
# 256 experts of 2048 held, 15360 pages of 64, 128 rows, rings of 128
# positions): the paged kernel at 16 query heads a K/V head over a pool whose
# K leaf lays two heads' keys side by side, ``flash_decode`` over rings laid
# out the same way with a sink a head, the flash forward with and without a
# sink, and a prompt past ``flash_max_keys`` (5,461 keys of 192 + 128
# channels).  Each donates pool and rings and must leave them where they are.

def _tiled_bytes(shape, layout, itemsize=2):
    """Bytes an array of ``shape`` occupies in HBM under ``layout`` (XLA's
    text: minor-to-major dims, then the tile, e.g. ``4,3,2,1,0:T(8,128)(2,1)``
    for a row-major bfloat16 array): the minor-most dim in whole tiles of
    lanes, the next in whole tiles of sublanes (times the packing)."""
    order, tiles = layout.split(":", 1)
    order = [int(x) for x in order.split(",")]
    sub, lanes = map(int, re.match(r"T\((\d+),(\d+)\)", tiles).groups())
    pack = re.search(r"\)\((\d+),1\)", tiles)
    sub *= int(pack.group(1)) if pack else 1
    dims = list(shape)
    dims[order[0]] = -(-dims[order[0]] // lanes) * lanes
    dims[order[1]] = -(-dims[order[1]] // sub) * sub
    return int(np.prod(dims)) * itemsize


def test_tiled_bytes_pads_a_key_of_192_channels_to_256_lanes():
    """Row-major, as the kernels read a cache: 192 channels stand in 256
    lanes; two heads' keys side by side (384) pad nothing."""
    row_major = "4,3,2,1,0:T(8,128)(2,1)"
    assert _tiled_bytes((2, 10, 4, 64, 192), row_major) == 2 * 10 * 4 * 64 \
        * 256 * 2
    assert _tiled_bytes((2, 10, 2, 64, 384), row_major) == 2 * 10 * 2 * 64 \
        * 384 * 2
    assert _tiled_bytes((2, 10, 4, 64, 128), row_major) == 2 * 10 * 4 * 64 \
        * 128 * 2


@pytest.mark.parametrize("name,rows,t", [("decode_r128", 128, 1),
                                         ("prefill_t2112", 1, 2112),
                                         ("prefill_t16896", 1, 16896)])
def test_unequal_head_sizes_compile_and_the_pool_pads_nothing(
        topo, name, rows, t):
    _, n_pages, slots, _ = _step_models()["mimo"]
    _, _, compiled, text = _compiled_step(topo, "mimo", rows, t,
                                          18944 // PAGE)
    for kernel in ("moe_grouped_swiglu", "moe_grouped_matmul"):
        assert kernel in text, kernel
    if t == 1:
        # the full layers' kernel at 16 query heads a K/V head, the rings' at
        # 8; both results have the values' 128 channels
        assert re.search(r"%flash_decode_paged[.\d]* = bf16\[128,4,16,128\]",
                         text)
        assert re.search(r"%flash_decode[.\d]* = bf16\[128,8,8,128\]", text)
    else:
        # both kinds' prefill kernels have 64 query heads: the window
        # layers' carries the sink and is told by its name
        assert re.search(r"%flash_attention_fwd[.\d]* = \(bf16\[1,64,", text)
        assert re.search(r"%flash_attention_fwd_sink[.\d]* = \(bf16\[1,64,",
                         text)
    if t == 16896:      # in four segments of at most 5,461 keys
        assert re.search(r"%flash_attention_fwd[.\d]* = \(bf16\[1,64,4608,",
                         text)
    pool_k, pool_v = (2, n_pages, 2, PAGE, 384), (2, n_pages, 4, PAGE, 128)
    ring_k, ring_v = (5, slots, 4, 128, 384), (5, slots, 8, 128, 128)
    as_text = lambda s: "bf16[" + ",".join(map(str, s)) + "]"
    for leaf in (pool_k, pool_v, ring_k, ring_v, (6, 16, 4096, 2048),
                 (6, 16, 2048, 4096)):
        assert as_text(leaf) in text, leaf
        moved = re.findall(r"= " + re.escape(as_text(leaf))
                           + r"\S* (?:copy|transpose|slice)\([^)]*\)", text)
        assert not moved, f"{leaf} is copied: {moved[:2]}"
    # the pool as the compiled step holds it costs the 5,120 B a position
    # docs/SERVING.md states: 2 full layers x 4 K/V heads x (192 + 128)
    # channels x 2 B, nothing padded (in the native layout K alone would
    # stand at 2 x 4 x 256 x 2 B and a position at 6,144); the rings
    # 3,276,800 B a row slot
    def held(shape):
        layouts = set(re.findall(re.escape(as_text(shape))
                                 + r"\{([\d,]+:T\([^}]*?\))(?:S\(\d\))?\}",
                                 text))
        assert len(layouts) == 1, (shape, layouts)
        return _tiled_bytes(shape, layouts.pop())

    assert (held(pool_k) + held(pool_v)) // (n_pages * PAGE) == 5120
    assert (held(ring_k) + held(ring_v)) // slots == 3276800
    # beside 6.9 GB of weights, 5.0 GB of pool and 0.4 GB of rings: a
    # step's temporaries (the longest prompt's attention operands in four
    # segments, its sorted expert rows)
    mem = compiled.memory_analysis()
    limit = {1: 5e7, 2112: 6e8, 16896: 3.0e9}[t]
    assert mem.temp_size_in_bytes < limit, mem.temp_size_in_bytes
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.3e9


# -- the rings' decode kernel by rows and heads (PR 46) ------------------------
#
# ``flash_decode`` takes a row's K/V heads, and several rows of a one-block
# ring, in one grid step (``_decode_block``).  What the benchmark's readers
# tell the kernel by stays: ONE custom call a window layer named
# ``flash_decode`` whose one result is ``[rows, kv heads, t * g, dv]``.  And
# the rings ride into it WHERE THEY LIE: whole, in the store's own row-major
# layout, no instruction of the step copying or transposing a ring, a layer of
# one or anything of a ring's extent in front of the call.
# model -> (table width, K ring, V ring)
RING_CASES = {
    "laguna": (17408 // PAGE, (3, 128, 8, 512, 128), (3, 128, 8, 512, 128)),
    "mimo": (18944 // PAGE, (5, 128, 4, 128, 384), (5, 128, 8, 128, 128)),
}


@pytest.mark.parametrize("model", sorted(RING_CASES))
def test_ring_decode_keeps_its_name_its_result_and_the_rings_in_place(
        topo, model):
    width, ring_k, ring_v = RING_CASES[model]
    _, _, _, text = _compiled_step(topo, model, 128, 1, width)
    calls = re.findall(r"%flash_decode[.\d]* = (\S+) custom-call\(.*?"
                       r"operand_layout_constraints=\{(.*?)\}, \w+=", text)
    assert calls, "no flash_decode custom call in the step"
    row_major = lambda s: ("bf16[" + ",".join(map(str, s)) + "]{"
                           + ",".join(map(str, range(len(s) - 1, -1, -1)))
                           + "}")
    for result, operands in calls:
        assert result.startswith("bf16[128,8,8,128]{"), result
        assert row_major(ring_k) in operands, operands
        assert row_major(ring_v) in operands, operands
    # a ring, a layer of one (with or without its leading 1), in any order
    # of dims
    extents = {tuple(sorted(s[cut:])) for s in (ring_k, ring_v)
               for cut in (0, 1)}
    moved = []
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%\S+ = (.*?) (?:copy|copy-start|"
                     r"transpose)\(", line)
        for dims in re.findall(r"bf16\[([\d,]+)\]", m.group(1)) if m else ():
            dims = tuple(sorted(int(x) for x in dims.split(",") if x != "1"))
            if dims in extents:
                moved.append(line.strip()[:160])
    assert not moved, moved[:2]
