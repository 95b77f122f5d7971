"""Flagship model: decoder-only transformer, TPU-first.

Nothing like this exists in the reference (its largest workload is a
2-layer MLP, SURVEY §2.5) — this is the model family that exercises every
mesh axis the framework offers:

* ``dp``/``fsdp`` — batch sharding + FSDP parameter sharding (the GSPMD
  successor of parameter servers),
* ``tp`` — Megatron-style tensor parallelism (heads/ff sharded, vocab-
  parallel embedding/head),
* ``sp`` — ring attention over the sequence (parallel/ring_attention.py),
* ``pp`` — pipeline stages over layer groups (parallel/pipeline.py),
* ``ep`` — expert-parallel MoE blocks.

Design choices for the MXU/XLA: stacked per-layer parameters consumed by
``lax.scan`` (one compiled block, L iterations), bf16 compute with fp32
master params and fp32 softmax/normalization accumulation, static shapes
throughout, optional ``jax.checkpoint`` rematerialization.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.lax import axis_size
from jax.sharding import Mesh, PartitionSpec as P

from tfmesos_tpu.ops.attention import attend, mha_reference
from tfmesos_tpu.ops.layers import (cross_entropy_loss, yarn_inv_freq,
                                    data_parallel_fused_cross_entropy,
                                    fused_linear_cross_entropy, rms_norm,
                                    vocab_parallel_ce_inbody,
                                    rope,
                                    vocab_parallel_cross_entropy)
from tfmesos_tpu.ops.quant import QTensor, quantize_tensor


def _wt(p, dtype):
    """Weight-on-use: dequantize an int8 :class:`QTensor` or cast a plain
    array to the compute dtype.  Matmul call sites should prefer
    :func:`_qmm` — round-5 chip measurement showed XLA materializing the
    scale*convert product from this form instead of fusing it into the
    dot, costing MORE bandwidth than bf16 weights; kept for the einsum
    sites (MoE experts) where the activation fold does not apply
    directly."""
    if isinstance(p, QTensor):
        return p.dequantize(dtype)
    return p.astype(dtype)


def _qmm(x, p, dtype):
    """``x @ W`` for a plain or int8 weight.  A QTensor's per-input-
    channel scales ([K, 1], K the contraction dim) commute across the
    dot, so they fold into the (tiny) activation — ``(x * s) @ values``
    — and the remaining pure int8->dtype convert DOES fuse into the
    matmul, leaving HBM reading the int8 bytes only.  Measured on a v5e
    chip at decode shapes (M=8, K=N=2048): 0.14 ms vs 0.34 ms for
    ``x @ dequantize(W)`` and 0.36 ms for bf16 weights — the form that
    makes int8 weights actually FASTER than bf16, not just smaller."""
    if isinstance(p, QTensor):
        s = p.scales.reshape(p.scales.shape[:-2] + (-1,)).astype(dtype)
        return (x * s) @ p.values.astype(dtype)
    return x @ p.astype(dtype)


def _embed_lookup(p, tokens, dtype):
    """Embedding gather for plain or quantized tables: gather int8 rows and
    their scales, then dequantize only the gathered rows."""
    if isinstance(p, QTensor):
        return (p.values[tokens].astype(dtype)
                * p.scales[tokens].astype(dtype))
    return p.astype(dtype)[tokens]


def _norm(cfg, x, gain):
    """RMSNorm of the residual stream ``x`` (the compute dtype, or float32
    under ``residual_dtype``; statistics in float32 either way) with the
    block's gain, coming back in the compute dtype.  A configuration that
    states neither an epsilon nor the unit offset passes ``rms_norm``
    nothing more than it always did."""
    g = gain.astype(x.dtype)
    if cfg.norm_offset:
        g = 1 + g
    kw = {} if cfg.norm_eps is None else {"eps": cfg.norm_eps}
    return rms_norm(x, g, **kw).astype(cfg.dtype)


#: the kinds of mixer a typed stack's ``layer_types`` may name
LAYER_KINDS = ("attention", "mamba", "kda", "window")
#: what a layer's second half may be (``TransformerConfig.ffn_types``)
FFN_KINDS = ("dense", "sparse")


@dataclass(frozen=True)
class RopeSpec:
    """The rotary embedding a kind of attention layer states: ``theta``;
    ``fraction`` of a head's channels rotated (the first ones; the rest pass
    through); ``yarn`` = ``(factor, original_max_position_embeddings,
    beta_fast, beta_slow)`` for YaRN's inverse frequencies (None: plain
    ``theta ** (-2 i / D)``); ``attention_factor`` multiplies cos and sin
    (None: YaRN's ``0.1 ln(factor) + 1``, 1 without YaRN)."""
    theta: float = 10000.0
    fraction: float = 1.0
    yarn: Optional[Tuple[float, int, float, float]] = None
    attention_factor: Optional[float] = None

    def kwargs(self, head_dim: int) -> Dict[str, Any]:
        """What ``ops/layers.rope`` takes beyond the positions; YaRN's
        frequencies are computed here, once, in float32 on the host."""
        rd = int(head_dim * self.fraction)
        if rd < 2 or rd % 2 or rd > head_dim:
            raise ValueError(f"rope fraction {self.fraction} of head_dim "
                             f"{head_dim} rotates {rd} channels: need an "
                             f"even count in 2..head_dim")
        kw: Dict[str, Any] = {"theta": self.theta}
        if rd != head_dim:
            kw["rotary_dim"] = rd
        factor = self.attention_factor
        if self.yarn is not None:
            kw["inv_freq"] = yarn_inv_freq(rd, self.theta, *self.yarn)
            if factor is None:
                factor = 0.1 * math.log(self.yarn[0]) + 1.0
        if factor is not None and factor != 1.0:
            kw["factor"] = float(factor)
        return kw


@dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32000
    d_model: int = 512
    n_layers: int = 8
    n_heads: int = 8
    d_ff: int = 2048
    # Grouped-query attention: n_kv_heads < n_heads shares each K/V head
    # across n_heads/n_kv_heads query heads — exact attention with a
    # KV cache (and wk/wv) smaller by that factor, the standard serving
    # memory/bandwidth win.  None = full multi-head attention.
    n_kv_heads: Optional[int] = None
    max_seq_len: int = 2048
    # Sliding-window attention (Mistral-style): each query sees the last
    # `window` positions only.  None = full causal attention.  The flash
    # kernel bounds its k-loop to the window (O(T·W) work); decode masks
    # cache reads the same way.  Does not compose with sp (ring/Ulysses).
    window: Optional[int] = None
    rope_theta: float = 10000.0
    dtype: Any = jnp.bfloat16          # compute dtype (MXU-friendly)
    param_dtype: Any = jnp.float32     # master params
    remat: bool = False                # jax.checkpoint each block
    # MoE (0 experts = no MoE):
    n_experts: int = 0
    top_k: int = 2
    # Shared experts (DeepSeek-style): this many always-on expert FFNs
    # beside the routed ones — every token takes routed(top-k) + shared.
    # Stored as ONE fused FFN of width n_shared_experts * d_ff (identical
    # math to summing separate experts, one matmul).
    n_shared_experts: int = 0
    # "dense": exact top-k, every expert computes everything (masked) —
    # simple, shardable over ep as pure weight sharding.
    # "switch": top-1 routing with capacity + real all_to_all token dispatch
    # over the ep axis (parallel/moe.py) — the scalable path.
    moe_impl: str = "dense"
    capacity_factor: float = 1.25
    # Switch-transformer aux weighting: load-balance at 1e-2, z-loss at 1e-3.
    router_aux_weight: float = 0.01
    router_z_weight: float = 1e-3
    # Pipeline schedule: "gpipe", or "circular" with v>1 virtual stages per
    # device (bubble shrinks ~v-fold; needs n_layers % (pp*v) == 0).
    pp_schedule: str = "gpipe"
    pp_virtual_stages: int = 1
    # Sequence parallelism over sp: "ring" (O(T/sp) memory, no head
    # constraint) or "ulysses" (two all_to_alls, full-T flash locally;
    # needs n_heads % sp == 0).  See parallel/ulysses.py for the trade.
    sp_impl: str = "ring"
    # Fused head+cross-entropy: never materializes the [B·T, V] logits
    # through fwd+bwd.  None = auto (see _fused_ce_mode): the dense form
    # (ops/layers.fused_linear_cross_entropy) on single-device and
    # data-only meshes, the tp vocab-parallel form
    # (vocab_parallel_cross_entropy) when tp divides the vocab; sp/pp/ep
    # meshes and QTensor (serving) heads use the standard path.  True asks
    # for fusion even where auto declines (the dense form, relying on
    # GSPMD to partition the chunks); False disables fusion everywhere.
    fused_ce: Optional[bool] = None
    ce_chunk: int = 2048
    # LM-head z-loss (PaLM-style logit-drift stabilizer): adds
    # z_loss · mean(logsumexp(logits)²) to the objective.  All four CE
    # paths (unfused, fused-dense, dp-sharded, tp vocab-parallel)
    # implement it identically.
    z_loss: float = 0.0
    # Attention kind.  "full": causal softmax over every earlier position
    # (with ``window``: the last ``window`` of them).  "eva": EVA chunked
    # attention (Zheng et al., "Efficient Attention via Control Variates";
    # EvaByte): a query attends exactly to the positions of its own window
    # of ``eva_window`` and to ONE summary per chunk of ``eva_chunk``
    # positions of every earlier window, under one softmax normaliser
    # (``eva_summarize`` has the pooling).  Serving path only: the paged
    # cache then holds ENTRIES, not positions (``cache_entries``): summaries
    # that only grow, and the current window's exact K/V.
    attention: str = "full"
    eva_chunk: int = 16
    eva_window: int = 2048
    # RMSNorm as the configuration states it: ``norm_eps`` None keeps
    # ``rms_norm``'s own epsilon (nothing is passed, so programs that do not
    # state one are unchanged); ``norm_offset`` scales by (1 + g) and
    # initialises g at 0.
    norm_eps: Optional[float] = None
    norm_offset: bool = False
    # dtype of the residual stream and its adds (None: the compute dtype),
    # and of the logits (None: the compute dtype).  Honoured by the decode
    # path (``decode_step``); ``forward`` refuses configurations that set
    # them.
    residual_dtype: Any = None
    logits_dtype: Any = None
    # Multi-token prediction heads on the output: the head matrix is
    # [d_model, n_pred_heads * vocab_size], head 0 (the first vocab_size
    # columns) is the next token.  Decoding computes head 0 only.
    n_pred_heads: int = 1
    # A LAYER PATTERN (None: one homogeneous stack of attention blocks, as
    # ever).  One entry per layer, "attention" | "mamba" | "kda": the mixer
    # of each block; every block's second half is the same feed-forward /
    # expert layer.  The stack is scanned over periods of the pattern (the
    # shortest prefix that repeats) and, inside a period, over each run of
    # layers of one kind.  Parameters of a typed stack: the leaves every
    # layer has (norms, feed-forward, experts) stacked [L, ...] as before,
    # the mixers' stacked by kind under ``layers["attention"]`` /
    # ``layers["mamba"]`` / ``layers["kda"]``.
    # Serving path only (``decode_step`` through a paged cache): K/V pages
    # for the attention layers (the pool's leading dim is their count) and
    # a per-row recurrent state for the mamba and kda layers
    # (``init_row_state``: the leaves of the kinds present).
    layer_types: Optional[Tuple[str, ...]] = None
    # "window": a second kind of attention layer beside "attention", with
    # its own query heads (``window_heads``; None: n_heads), its own K/V
    # heads (``window_kv_heads``; None: the stack's ``kv_heads``), its own
    # rope (``window_rope``; None: plain rope at ``rope_theta``) and a
    # sliding window: position t attends positions max(0, t - window + 1)
    # .. t (``window``, which with ``layer_types`` is these layers' and no
    # one else's).  The head sizes are the stack's (``head_dim`` for queries
    # and keys, ``v_head_dim`` for values).  ``window_sink``: a learned
    # logit a window head (the leaf ``sink`` [window layers, heads] float32
    # beside ``wq``) joins the softmax's denominator and carries no value:
    # p_j = exp(s_j - m) / (sum_j exp(s_j - m) + exp(b_h - m)).  Its mixer
    # leaves are stacked under ``layers["window"]`` (wq / wk / wv / wo may
    # all differ in shape from the "attention" kind's).  It keeps no pages:
    # a row slot holds a RING of the last ``window`` positions' K and V a
    # window layer (``init_row_state``: ``swa_k`` / ``swa_v``, slot =
    # position mod window, keys after rope), O(window) a row whatever its
    # context.  ``attn_rope``: the rope of the "attention" kind (and of a
    # homogeneous stack's decode path) where it is more than ``rope_theta``.
    window_heads: Optional[int] = None
    window_kv_heads: Optional[int] = None
    window_sink: bool = False
    window_rope: Optional[RopeSpec] = None
    attn_rope: Optional[RopeSpec] = None
    # A FEED-FORWARD PATTERN beside the mixer pattern (None: every block's
    # second half is the same).  One entry per layer, "dense" | "sparse":
    # the LEADING layers may be dense (a SwiGLU of width ``d_ff``, leaves
    # ``layers["dense"]`` stacked over them), every later one is sparse (the
    # expert layer; its leaves, router and shared expert included, are
    # stacked over the sparse layers only, the experts ``expert_d_ff`` wide,
    # None: d_ff).  The leading layers run before the scanned periods, and
    # ``layer_period`` / ``layer_runs`` describe the layers behind them.
    ffn_types: Optional[Tuple[str, ...]] = None
    expert_d_ff: Optional[int] = None
    # The Mamba-2 (SSD) mixer: ``mamba_heads`` heads of ``mamba_head_dim``
    # (d_inner = their product), a state of ``mamba_state`` per head
    # channel, ONE B/C group shared by every head, a causal depthwise conv
    # of ``mamba_conv`` taps over [x | B | C], prefill in chunks of
    # ``mamba_chunk``.  The row state is float32 (``init_row_state``): a
    # bfloat16 state is a different result, not a faster one.
    mamba_heads: int = 0
    mamba_head_dim: int = 64
    mamba_state: int = 128
    mamba_conv: int = 4
    mamba_chunk: int = 256
    # The KDA mixer (Kimi Delta Attention, ``ops/kda.py``): a gated delta
    # rule over a matrix state per head with a decay per key channel.
    # ``kda_heads`` heads of ``kda_head_dim`` (keys and values alike), a
    # causal depthwise conv of ``kda_conv`` taps over [q | k | v], the decay
    # and the output gate through low-rank projections of the head's
    # size, prefill in chunks of ``kda_chunk``; ``kda_neg_eigval`` doubles ``beta`` (steps in (0, 2):
    # the transition may have negative eigenvalues).  The row state is
    # float32, [heads * head_dim, head_dim] a layer (``init_row_state``).
    kda_heads: int = 0
    kda_head_dim: int = 128
    kda_conv: int = 4
    kda_chunk: int = 64
    kda_neg_eigval: bool = False
    # Attention as a configuration states it: ``rope`` False applies no
    # positional embedding; ``attn_scale`` is the softmax scale (None:
    # head_dim ** -0.5); ``attn_head_dim`` the head size (None: d_model /
    # n_heads); ``attn_gate`` multiplies the attention's output, before
    # ``wo``, by ``sigmoid(x W_g)`` taken from the block's normed input (an
    # elementwise output gate: the leaf ``wg`` beside ``wq``, [d, heads *
    # head_dim]; ``attn_gate="head"``: one gate a HEAD, ``wg`` [d, heads]).
    # ``attn_v_head_dim``: the VALUES' head size where it is not the keys'
    # (None: ``head_dim``): ``wv`` [d, KV * Dv], ``wo`` [heads * Dv, d], the
    # caches' V leaves Dv wide; ``attn_value_scale`` multiplies the values
    # (attention is linear in them, so they are scaled once, as projected:
    # what the caches hold is scaled).  Both are a typed stack's with window
    # layers (the serving path's; ``docs/SERVING.md`` "The hybrid cache").
    rope: bool = True
    attn_scale: Optional[float] = None
    attn_head_dim: Optional[int] = None
    attn_v_head_dim: Optional[int] = None
    attn_value_scale: Optional[float] = None
    attn_gate: Any = False
    # Multipliers (None: absent): the embedding's output is scaled by
    # ``embed_scale``, every block adds ``residual_scale`` times its mixer
    # / feed-forward output, the logits are divided by ``logits_scale``.
    embed_scale: Optional[float] = None
    residual_scale: Optional[float] = None
    logits_scale: Optional[float] = None
    # The head is the embedding's transpose (no ``head`` leaf).
    tie_embeddings: bool = False
    # Experts held HERE, beside ``n_experts`` (the router's width): the
    # expert leaves hold ``experts_held`` experts (None: all), the experts
    # ``expert_offset .. expert_offset + experts_held - 1``.  The router
    # still picks over all ``n_experts``; assignments that fall on experts
    # held elsewhere add nothing here.  ``moe_impl="grouped"`` only.
    experts_held: Optional[int] = None
    expert_offset: int = 0
    # Width of the always-on shared MLP where the configuration states it
    # by itself (None: n_shared_experts * d_ff).
    shared_d_ff: Optional[int] = None
    # How the router's logits become gates (``moe_impl="grouped"``).
    # "softmax": the top-k largest logits, softmax over those kept.
    # "sigmoid": scores ``s = sigmoid(logits)``; the top-k largest ``s + b``
    # (``b``: the leaf ``router_bias`` [L, E] float32, a selection bias
    # that is no part of the gate); gates ``s_i / sum of the chosen s``,
    # times ``routed_scale``.
    router_score: str = "softmax"
    routed_scale: float = 1.0

    @property
    def head_dim(self) -> int:
        if self.attn_head_dim is not None:
            return self.attn_head_dim
        return self.d_model // self.n_heads

    @property
    def v_head_dim(self) -> int:
        """The values' head size (the keys' unless stated)."""
        return (self.head_dim if self.attn_v_head_dim is None
                else self.attn_v_head_dim)

    def __post_init__(self):
        if self.window is not None and self.window < 1:
            raise ValueError(f"window must be >= 1, got {self.window} "
                             f"(use None for full causal attention)")
        if self.attention not in ("full", "eva"):
            raise ValueError(f"attention must be 'full' or 'eva', got "
                             f"{self.attention!r}")
        if self.attention == "eva":
            if self.window is not None:
                raise ValueError("attention='eva' has its own window "
                                 "(eva_window); leave window None")
            if (self.eva_chunk < 1 or self.eva_window < self.eva_chunk
                    or self.eva_window % self.eva_chunk):
                raise ValueError(
                    f"eva_window ({self.eva_window}) must be a positive "
                    f"multiple of eva_chunk ({self.eva_chunk})")
        if self.n_pred_heads < 1:
            raise ValueError(f"n_pred_heads must be >= 1, got "
                             f"{self.n_pred_heads}")
        if self.moe_impl not in ("dense", "switch", "grouped"):
            raise ValueError(f"moe_impl must be 'dense', 'switch' or "
                             f"'grouped', got {self.moe_impl!r}")
        if self.experts_held is not None or self.expert_offset:
            if self.moe_impl != "grouped":
                raise ValueError("experts_held / expert_offset need "
                                 "moe_impl='grouped' (the expert layer that "
                                 "is told which experts it holds)")
            if not (0 <= self.expert_offset
                    and self.expert_offset + self.held_experts
                    <= self.n_experts and self.held_experts >= 1):
                raise ValueError(
                    f"experts {self.expert_offset}..+{self.held_experts} "
                    f"are not among the router's {self.n_experts}")
        if self.layer_types is not None:
            object.__setattr__(self, "layer_types", tuple(self.layer_types))
            bad = set(self.layer_types) - set(LAYER_KINDS)
            if bad or len(self.layer_types) != self.n_layers:
                raise ValueError(
                    f"layer_types takes n_layers ({self.n_layers}) entries "
                    f"of 'attention' | 'mamba' | 'kda' | 'window', got "
                    f"{self.layer_types!r}")
            windowed = "window" in self.layer_types
            if self.attention != "full" or (self.window is None) == windowed:
                raise ValueError(
                    "layer_types composes with full attention only (no "
                    "EVA); window is the 'window' layers' and is stated "
                    "with them and only with them")
            if windowed and (self.window_heads or self.n_heads) % \
                    self.kind_kv_heads("window"):
                raise ValueError(
                    f"window_heads ({self.window_heads}) must be a "
                    f"multiple of the K/V heads "
                    f"({self.kind_kv_heads('window')})")
            if "mamba" in self.layer_types and (
                    self.mamba_heads < 1 or self.mamba_conv < 2):
                raise ValueError("mamba layers need mamba_heads >= 1 and "
                                 "mamba_conv >= 2")
            if "kda" in self.layer_types and (
                    self.kda_heads < 1 or self.kda_conv < 2
                    or self.kda_chunk < 1):
                raise ValueError("kda layers need kda_heads >= 1, kda_conv "
                                 ">= 2 and kda_chunk >= 1")
        if (any(v is not None for v in (
                self.window_heads, self.window_rope, self.window_kv_heads))
                or self.window_sink) \
                and "window" not in (self.layer_types or ()):
            raise ValueError("window_heads / window_kv_heads / window_sink / "
                             "window_rope are the 'window' layers' "
                             "(layer_types)")
        if (self.attn_v_head_dim is not None
                or self.attn_value_scale is not None) \
                and "window" not in (self.layer_types or ()):
            # what moves or shares pages by shape (export, the KV tier, a
            # suspended row's snapshot) knows one head size, and is closed
            # where a row keeps a window layer's ring
            raise ValueError("attn_v_head_dim / attn_value_scale are served "
                             "in a typed stack with 'window' layers "
                             "(layer_types)")
        if self.attn_gate not in (False, True, "head"):
            raise ValueError(f"attn_gate must be False, True (elementwise) "
                             f"or 'head', got {self.attn_gate!r}")
        if self.ffn_types is not None:
            object.__setattr__(self, "ffn_types", tuple(self.ffn_types))
            lead = self.n_lead_layers
            if (self.layer_types is None or not self.n_experts
                    or self.moe_impl != "grouped"
                    or len(self.ffn_types) != self.n_layers
                    or set(self.ffn_types) - set(FFN_KINDS)
                    or lead == self.n_layers
                    or "dense" in self.ffn_types[lead:]):
                raise ValueError(
                    f"ffn_types takes n_layers ({self.n_layers}) entries, "
                    f"'dense' for leading layers only and 'sparse' behind "
                    f"them, in a typed stack (layer_types) with grouped "
                    f"experts; got {self.ffn_types!r}")
        elif self.expert_d_ff is not None:
            raise ValueError("expert_d_ff is stated with ffn_types (the "
                             "dense layers take d_ff)")
        if self.router_score not in ("softmax", "sigmoid"):
            raise ValueError(f"router_score must be 'softmax' or 'sigmoid', "
                             f"got {self.router_score!r}")
        if ((self.router_score != "softmax" or self.routed_scale != 1.0)
                and self.moe_impl != "grouped"):
            raise ValueError("router_score / routed_scale need "
                             "moe_impl='grouped' (the one expert layer that "
                             "knows more than one gate form)")

    @property
    def held_experts(self) -> int:
        """Experts whose weights this shard holds."""
        return (self.n_experts if self.experts_held is None
                else self.experts_held)

    @property
    def shared_width(self) -> int:
        """Width of the always-on shared MLP (0: none)."""
        if self.shared_d_ff is not None:
            return self.shared_d_ff
        return self.n_shared_experts * self.d_ff

    @property
    def layer_kinds(self) -> Tuple[str, ...]:
        return self.layer_types or ("attention",) * self.n_layers

    @property
    def n_attn_layers(self) -> int:
        """Layers that keep K/V: the paged pool's leading dim."""
        return self.layer_kinds.count("attention")

    @property
    def n_mamba_layers(self) -> int:
        """Mamba-2 layers: an SSM state and a conv tail a row."""
        return self.layer_kinds.count("mamba")

    @property
    def n_kda_layers(self) -> int:
        """KDA layers: a matrix state a head and a conv tail a row."""
        return self.layer_kinds.count("kda")

    @property
    def attn_window(self) -> Optional[int]:
        """The window of the "attention" kind's layers: ``window`` for a
        homogeneous stack, none in a typed one (there ``window`` is the
        "window" kind's)."""
        return None if self.layer_types is not None else self.window

    @property
    def n_window_layers(self) -> int:
        """Sliding-window layers: a ring of K and V a row."""
        return self.layer_kinds.count("window")

    @property
    def keeps_row_state(self) -> bool:
        """Rows keep a state beside their pages whose size does not depend
        on their context, whatever the kind of layer that keeps it
        (``init_row_state``): a recurrent state, or a window layer's ring."""
        return any(kind != "attention" for kind in self.layer_kinds)

    @property
    def n_lead_layers(self) -> int:
        """Leading layers whose feed-forward is dense (``ffn_types``): they
        run before the scanned periods."""
        kinds = self.ffn_types or ()
        return next((i for i, k in enumerate(kinds) if k != "dense"),
                    len(kinds))

    @property
    def n_sparse_layers(self) -> int:
        """Layers that hold an expert layer: what the expert leaves, the
        router and the shared expert are stacked over."""
        return (self.n_layers - self.n_lead_layers) if self.n_experts else 0

    @property
    def expert_width(self) -> int:
        return self.d_ff if self.expert_d_ff is None else self.expert_d_ff

    def kind_heads(self, kind: str) -> int:
        """Query heads of an attention layer of ``kind``."""
        if kind == "window" and self.window_heads is not None:
            return self.window_heads
        return self.n_heads

    def kind_kv_heads(self, kind: str) -> int:
        """K/V heads of an attention layer of ``kind``."""
        if kind == "window" and self.window_kv_heads is not None:
            return self.window_kv_heads
        return self.kv_heads

    def k_pack(self, kind: str = "attention") -> int:
        """K heads a row of the kind's K cache holds side by side
        (``ops/attention.pack_k``: 2 where a key is a lane tile and a half
        wide, so that the cache pads nothing in HBM; else 1)."""
        from tfmesos_tpu.ops.attention import pack_k
        return pack_k(self.head_dim, self.kind_kv_heads(kind))

    @property
    def layer_period(self) -> int:
        """Length of the shortest prefix of the pattern behind the leading
        layers (``n_lead_layers``; none: ``layer_types`` whole) that,
        repeated, gives that pattern.  Behind leading layers the last
        period may be partial (the published pattern starts at layer 0, so
        a stack of whole periods of it ends the leading layers short of
        whole periods behind them)."""
        lead = self.n_lead_layers
        kinds = self.layer_kinds[lead:]
        n = len(kinds)
        return next(p for p in range(1, n + 1)
                    if (lead or n % p == 0)
                    and kinds == (kinds[:p] * -(-n // p))[:n])

    @staticmethod
    def _runs(period):
        runs, seen = [], dict.fromkeys(LAYER_KINDS, 0)
        for j, kind in enumerate(period):
            if runs and runs[-1][0] == kind:
                runs[-1][2] += 1
            else:
                runs.append([kind, j, 1, seen[kind]])
            seen[kind] += 1
        return tuple(tuple(r) for r in runs)

    @property
    def layer_runs(self):
        """One period as runs of layers of one kind: ``(kind, first layer
        in the period, layers, first index among the period's layers of
        that kind)``."""
        lead = self.n_lead_layers
        return self._runs(self.layer_kinds[lead:lead + self.layer_period])

    @property
    def mamba_inner(self) -> int:
        return self.mamba_heads * self.mamba_head_dim

    @property
    def mamba_conv_dim(self) -> int:
        """Channels of the conv: [x | B | C], B and C of one group."""
        return self.mamba_inner + 2 * self.mamba_state

    @property
    def kda_inner(self) -> int:
        return self.kda_heads * self.kda_head_dim

    @property
    def eva_summaries(self) -> int:
        """Summary entries one closed window leaves in the cache."""
        return self.eva_window // self.eva_chunk

    def cache_entries(self, length):
        """Cache entries a row at context ``length`` holds (int or array):
        under EVA, ``eva_summaries`` per closed window plus the current
        window's positions; one per position otherwise.  Also the entry
        index at which position ``length`` is written."""
        if self.attention != "eva":
            return length
        return (length // self.eva_window * self.eva_summaries
                + length % self.eva_window)

    def cache_entries_peak(self, lo: int, hi: int) -> int:
        """The most entries a row holds while its context grows from ``lo``
        to ``hi``: a window is held exactly until it is closed, so a row
        that crosses a window's end peaks just before the close."""
        if self.attention != "eva":
            return hi
        w = hi // self.eva_window
        peak = self.cache_entries(hi)
        if w > lo // self.eva_window:
            peak = max(peak, (w - 1) * self.eva_summaries + self.eva_window)
        return peak

    @property
    def kv_heads(self) -> int:
        kv = self.n_heads if self.n_kv_heads is None else self.n_kv_heads
        if kv < 1 or self.n_heads % kv:
            raise ValueError(f"n_heads ({self.n_heads}) must be a positive "
                             f"multiple of n_kv_heads ({kv})")
        return kv


def init_params(cfg: TransformerConfig, rng) -> Dict[str, Any]:
    if cfg.shared_width and not cfg.n_experts:
        raise ValueError(
            "n_shared_experts requires n_experts > 0 — without routed "
            "experts there is nothing to share beside; widen d_ff instead")
    d, f, l = cfg.d_model, cfg.d_ff, cfg.n_layers
    keys = iter(jax.random.split(rng, 48 if cfg.ffn_types else
                                 32 if cfg.layer_types else 16))

    def norm(shape, scale):
        return (jax.random.normal(next(keys), shape, cfg.param_dtype)
                * scale).astype(cfg.param_dtype)

    # a gain g with the unit offset scales by (1 + g): the identity is 0
    gain = jnp.zeros if cfg.norm_offset else jnp.ones

    def attn_leaves(la, kind):
        """The leaves of ``la`` attention layers of ``kind``."""
        heads, kv = cfg.kind_heads(kind), cfg.kind_kv_heads(kind)
        hd, ho = heads * cfg.head_dim, heads * cfg.v_head_dim
        leaves = {
            "wq": norm((la, d, hd), 1 / math.sqrt(d)),
            "wk": norm((la, d, kv * cfg.head_dim), 1 / math.sqrt(d)),
            "wv": norm((la, d, kv * cfg.v_head_dim), 1 / math.sqrt(d)),
            "wo": norm((la, ho, d), 1 / math.sqrt(ho) / math.sqrt(2 * l)),
        }
        if cfg.attn_gate:
            leaves["wg"] = norm(
                (la, d, heads if cfg.attn_gate == "head" else ho),
                1 / math.sqrt(d))
        if kind == "window" and cfg.window_sink:
            # the sink's logit: float32 whatever the parameters' dtype
            leaves["sink"] = jax.random.normal(next(keys), (la, heads),
                                               jnp.float32)
        return leaves

    la = cfg.n_attn_layers
    attn = attn_leaves(la, "attention")
    layers = {"attn_norm": gain((l, d), cfg.param_dtype),
              "mlp_norm": gain((l, d), cfg.param_dtype)}
    if cfg.layer_types is None:
        layers.update(attn)
    else:
        # typed stack: the mixers' leaves by kind, [layers of that kind, ..]
        if la:
            layers["attention"] = attn
        if cfg.n_window_layers:
            layers["window"] = attn_leaves(cfg.n_window_layers, "window")
        u = lambda shape, lo, hi: jax.random.uniform(
            next(keys), shape, jnp.float32, lo, hi)
        lm = cfg.n_mamba_layers
        if lm:
            di, nh, cd = cfg.mamba_inner, cfg.mamba_heads, cfg.mamba_conv_dim
            # dt = softplus(dt_bias + ...) spread over 1e-3 .. 1e-1 and
            # A = -exp(A_log) over -1 .. -16: steps and decays far from 0/1
            dt0 = jnp.exp(u((lm, nh), math.log(1e-3), math.log(1e-1)))
            layers["mamba"] = {
                "in_proj": norm((lm, d, di + cd + nh), 1 / math.sqrt(d)),
                "conv_w": norm((lm, cfg.mamba_conv, cd),
                               1 / math.sqrt(cfg.mamba_conv)),
                "conv_b": norm((lm, cd), 0.1),
                "dt_bias": (dt0 + jnp.log(-jnp.expm1(-dt0))
                            ).astype(cfg.param_dtype),
                "A_log": jnp.log(u((lm, nh), 1.0, 16.0)
                                 ).astype(cfg.param_dtype),
                "D": jnp.ones((lm, nh), cfg.param_dtype),
                "norm": jnp.ones((lm, di), cfg.param_dtype),
                "out_proj": norm((lm, di, d),
                                 1 / math.sqrt(di) / math.sqrt(2 * l)),
            }
        lk = cfg.n_kda_layers
        if lk:
            nh, hk, r = cfg.kda_heads, cfg.kda_inner, cfg.kda_head_dim
            # softplus(dt_bias + ...) spread over 1e-3 .. 1e-1 and
            # -exp(A_log) over -1 .. -8: a step's log-decay far from 0
            dt0 = jnp.exp(u((lk, hk), math.log(1e-3), math.log(1e-1)))
            layers["kda"] = {
                # [q | k | v], each heads * head_dim wide
                "in_proj": norm((lk, d, 3 * hk), 1 / math.sqrt(d)),
                "conv_w": norm((lk, cfg.kda_conv, 3 * hk),
                               1 / math.sqrt(cfg.kda_conv)),
                "f_down": norm((lk, d, r), 1 / math.sqrt(d)),
                "f_up": norm((lk, r, hk), 1 / math.sqrt(r)),
                "dt_bias": (dt0 + jnp.log(-jnp.expm1(-dt0))
                            ).astype(cfg.param_dtype),
                "A_log": jnp.log(u((lk, nh), 1.0, 8.0)
                                 ).astype(cfg.param_dtype),
                "b_proj": norm((lk, d, nh), 1 / math.sqrt(d)),
                "g_down": norm((lk, d, r), 1 / math.sqrt(d)),
                "g_up": norm((lk, r, hk), 1 / math.sqrt(r)),
                "norm": jnp.ones((lk, cfg.kda_head_dim), cfg.param_dtype),
                "out_proj": norm((lk, hk, d),
                                 1 / math.sqrt(hk) / math.sqrt(2 * l)),
            }
    if cfg.attention == "eva":
        # the chunk pooling's query and the summaries' key offset, per
        # layer and head (unit scale: pooling weights far from uniform)
        layers.update(
            eva_phi=norm((l, cfg.kv_heads, cfg.head_dim), 1.0),
            eva_mu=norm((l, cfg.kv_heads, cfg.head_dim), 1.0))
    if cfg.n_experts:
        e, eh = cfg.n_experts, cfg.held_experts
        # the expert layer's leaves are stacked over the layers that have
        # one (all of them without ``ffn_types``), ``expert_width`` wide
        ls, fe = cfg.n_sparse_layers, cfg.expert_width
        if cfg.n_lead_layers:
            nl = cfg.n_lead_layers
            layers["dense"] = {
                "w_gate": norm((nl, d, f), 1 / math.sqrt(d)),
                "w_up": norm((nl, d, f), 1 / math.sqrt(d)),
                "w_down": norm((nl, f, d),
                               1 / math.sqrt(f) / math.sqrt(2 * l)),
            }
        layers.update(
            router=norm((ls, d, e), 1 / math.sqrt(d)),
            e_gate=norm((ls, eh, d, fe), 1 / math.sqrt(d)),
            e_up=norm((ls, eh, d, fe), 1 / math.sqrt(d)),
            e_down=norm((ls, eh, fe, d),
                        1 / math.sqrt(fe) / math.sqrt(2 * l)),
        )
        if cfg.router_score == "sigmoid":
            # the selection bias: float32 whatever the parameters' dtype
            layers["router_bias"] = jnp.zeros((ls, e), jnp.float32)
        if cfg.shared_width:
            sf = cfg.shared_width
            layers.update(
                s_gate=norm((ls, d, sf), 1 / math.sqrt(d)),
                s_up=norm((ls, d, sf), 1 / math.sqrt(d)),
                s_down=norm((ls, sf, d),
                            1 / math.sqrt(sf) / math.sqrt(2 * l)),
            )
    else:
        layers.update(
            w_gate=norm((l, d, f), 1 / math.sqrt(d)),
            w_up=norm((l, d, f), 1 / math.sqrt(d)),
            w_down=norm((l, f, d), 1 / math.sqrt(f) / math.sqrt(2 * l)),
        )
    params = {
        "embed": norm((cfg.vocab_size, d), 1.0),
        "layers": layers,
        "norm_f": gain((d,), cfg.param_dtype),
        "head": norm((d, cfg.n_pred_heads * cfg.vocab_size),
                     1 / math.sqrt(d)),
    }
    if cfg.tie_embeddings:
        del params["head"]
    return params


#: weight leaves worth quantizing — the big matmul operands.  Norms are
#: tiny and precision-critical; the router is tiny and decides routing.
_QUANT_KEYS = frozenset(
    {"wq", "wk", "wv", "wo", "wg", "w_gate", "w_up", "w_down",
     "e_gate", "e_up", "e_down", "s_gate", "s_up", "s_down",
     "in_proj", "out_proj"})


def _quantizable(cfg: TransformerConfig, key: str) -> bool:
    """Which layer leaves quantize_params converts.  Switch-MoE expert
    weights stay fp: the capacity-dispatch path (parallel/moe.py) consumes
    raw arrays inside shard_map bodies, and re-plumbing QTensors through
    its all_to_all hops buys little — switch decode is dominated by the
    dense trunk it shares with everything else."""
    if cfg.moe_impl == "switch" and key.startswith("e_"):
        return False
    return key in _QUANT_KEYS


def quantize_params(cfg: TransformerConfig, params) -> Dict[str, Any]:
    """Weight-only int8 quantization (per-row absmax, ``ops/quant.py``).

    Returns a params tree where the embedding table, unembedding head, and
    every per-layer projection/FFN/expert weight are :class:`QTensor`s;
    norms and the router stay fp32.  The tree drops into ``forward``,
    ``decode_step`` and ``generate`` unchanged — weights dequantize at the
    consuming matmul, so HBM streams int8.  That is the serving win:
    steady-state decode at t=1 is weight-bandwidth-bound, and int8 halves
    the bytes per step vs bf16 (~4x vs these fp32 master params).
    """
    def quantize(tree):
        # a typed stack keeps its mixers' leaves one level down, by kind
        return {k: (quantize(v) if isinstance(v, dict) else
                    quantize_tensor(v) if _quantizable(cfg, k) else v)
                for k, v in tree.items()}

    out = {
        "embed": quantize_tensor(params["embed"]),
        "layers": quantize(params["layers"]),
        "norm_f": params["norm_f"],
    }
    if "head" in params:
        out["head"] = quantize_tensor(params["head"])
    return out


def _qswiglu(h, w_gate, w_up, w_down, dtype):
    """swiglu unrolled over :func:`_qmm` so int8 weights ride the
    activation-folded form at every matmul — one helper for the dense
    MLP and the MoE shared expert (a fix to the fold must hit both)."""
    g = jax.nn.silu(_qmm(h, w_gate, dtype))
    return _qmm(g * _qmm(h, w_up, dtype), w_down, dtype)


def _mlp(cfg: TransformerConfig, lp, h):
    return _qswiglu(h, lp["w_gate"], lp["w_up"], lp["w_down"], cfg.dtype)


def _zero_aux():
    z = jnp.zeros((), jnp.float32)
    return {"load_balance_loss": z, "z_loss": z, "overflow_frac": z}


def _moe_switch(cfg: TransformerConfig, mesh, lp, h):
    """Expert-parallel switch MoE: flatten tokens and run the all_to_all
    dispatch path (top-k, capacity-limited — not identical math to the
    dense path; choose per config).  Meshless calls use the single-device
    reference with the SAME routing semantics, so a model trained with
    moe_impl="switch" evaluates identically without a mesh.  Returns
    (out, aux) — the router-health metrics loss_fn folds into training."""
    from tfmesos_tpu.parallel.moe import switch_moe, switch_moe_reference
    b, t, d = h.shape
    flat = h.reshape(b * t, d)
    router = lp["router"].astype(cfg.dtype)
    if mesh is None:
        out, aux = switch_moe_reference(flat, router, lp["e_gate"],
                                        lp["e_up"], lp["e_down"],
                                        capacity_factor=cfg.capacity_factor,
                                        top_k=cfg.top_k, return_aux=True)
    else:
        out, aux = switch_moe(flat, router, lp["e_gate"], lp["e_up"],
                              lp["e_down"], mesh,
                              capacity_factor=cfg.capacity_factor,
                              top_k=cfg.top_k, return_aux=True)
    return out.reshape(b, t, d), aux


def _moe(cfg: TransformerConfig, lp, h, ep_axis: Optional[str] = None,
         tp_axis: Optional[str] = None, inbody_ad: bool = False):
    """Top-k routed MoE, computed densely over the expert axis.

    Every expert processes every token and the router mask zeroes the
    unrouted ones — mathematically exact top-k routing whose weights shard
    cleanly over ``ep``.  (A dispatch/all_to_all data path that skips the
    masked compute is the standard optimization; this dense form trades
    FLOPs for simplicity and zero token overflow.)  Returns (out, aux).

    ``ep_axis`` enables the manual-collective form for pipeline stages:
    expert weights arrive as local ``ep`` shards, the (replicated) router
    picks over all E experts, each device computes only its local experts'
    slice of the masked einsum and the partials ``psum`` over ``ep`` —
    bitwise the same math as the GSPMD path.  ``tp_axis`` additionally
    shards every expert's FFN width (Megatron-per-expert: e_gate/e_up
    column-sharded [e_loc, d, f/tp], e_down row-sharded [e_loc, f/tp, d]);
    the e_down contraction then yields a partial sum and the same psum
    covers both axes.

    ``inbody_ad=True`` (the 1F1B train step, which runs ``jax.vjp``
    INSIDE the stage's shard_map) swaps the collectives for the Megatron
    f/g pair: the per-shard-divergent compute (expert einsums and the
    sliced mask) sits between a ``broadcast_replicated_grad`` fan-in and
    a ``psum_replicated_grad`` reduction, so the transposes sum partial
    cotangents exactly once; the router logits and aux losses stay in
    the replicated domain OUTSIDE the fan, where every shard computes
    identical values and identical gradients."""
    e = cfg.n_experts
    logits = (h @ lp["router"].astype(cfg.dtype)).astype(jnp.float32)  # [B,T,E]
    top_vals, top_idx = jax.lax.top_k(logits, cfg.top_k)
    gates = jax.nn.softmax(top_vals, axis=-1)  # [B,T,k]
    # mask[b,t,e] = gate weight if e is among the top-k for (b,t), else 0
    onehot = jax.nn.one_hot(top_idx, e, dtype=jnp.float32)
    mask = (onehot * gates[..., None]).sum(axis=-2)
    psum_axes = tuple(a for a in (ep_axis, tp_axis) if a is not None)
    if inbody_ad and psum_axes:
        from tfmesos_tpu.parallel.collectives import (
            broadcast_replicated_grad, psum_replicated_grad)
        fan = lambda v: broadcast_replicated_grad(v, psum_axes)
        red = lambda v: psum_replicated_grad(v, psum_axes)
    else:
        fan = lambda v: v
        red = ((lambda v: jax.lax.psum(v, psum_axes)) if psum_axes
               else (lambda v: v))
    h_l = fan(h)
    mask = fan(mask)
    if ep_axis is not None:
        eg = lp["e_gate"]
        e_loc = (eg.values if isinstance(eg, QTensor) else eg).shape[0]
        idx = jax.lax.axis_index(ep_axis)
        mask = jax.lax.dynamic_slice_in_dim(mask, idx * e_loc, e_loc, axis=-1)
    g = jax.nn.silu(jnp.einsum("btd,edf->btef", h_l,
                               _wt(lp["e_gate"], cfg.dtype)))
    u = jnp.einsum("btd,edf->btef", h_l, _wt(lp["e_up"], cfg.dtype))
    y = jnp.einsum("btef,efd->bted", g * u, _wt(lp["e_down"], cfg.dtype))
    out = red(jnp.einsum("bted,bte->btd", y, mask.astype(cfg.dtype)))
    probs = jax.nn.softmax(logits, axis=-1)
    f = jnp.sum(onehot, axis=(0, 1, 2)) / (onehot.shape[0] * onehot.shape[1]
                                           * cfg.top_k)
    aux = {
        "load_balance_loss": e * jnp.sum(
            f * jnp.mean(probs, axis=(0, 1))),
        "z_loss": jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2),
        "overflow_frac": jnp.zeros((), jnp.float32),  # dense path drops none
    }
    return out, aux


#: the routed experts' leaves: [L, held, ...] stacks that the grouped kernels
#: index by layer themselves
_EXPERT_LEAVES = ("e_gate", "e_up", "e_down")


def _moe_grouped(cfg: TransformerConfig, lp, h,
                 ep_axis: Optional[str] = None, layer=None):
    """Top-k routed experts in the sorted, grouped, drop-free form
    (``ops/moe.py``): the router picks over all ``n_experts``, the
    assignments that fall on the experts THIS shard holds (``held_experts``
    of them from ``expert_offset``; under a manual ``ep_axis`` the shard's
    index moves the offset on) are sorted by expert and one grouped matmul
    runs over them: no capacity, no drops, no every-expert-every-token.
    What the experts held elsewhere would add is another shard's partial
    sum: joined by one psum under ``ep_axis``, left out without one.
    ``layer`` (traced OK): the expert leaves are whole ``[L, held, ...]``
    stacks and this is the layer to run (the kernels index it through their
    scalar prefetch; no layer's experts are copied out).  Returns (out,
    aux); ``aux["expert_counts"]`` [held] int32 is how many assignments
    each held expert took."""
    from tfmesos_tpu.ops.moe import grouped_experts
    b, t, d = h.shape
    flat = h.reshape(b * t, d)
    # float32 logits (bf16 operands, accumulated and kept in float32): a
    # logit rounded to bf16 ties with its neighbours and moves the top-k
    logits = jnp.dot(flat, lp["router"].astype(cfg.dtype),
                     preferred_element_type=jnp.float32)
    held = cfg.held_experts
    offset = jnp.asarray(cfg.expert_offset, jnp.int32)
    if ep_axis is not None:
        offset = offset + jax.lax.axis_index(ep_axis) * held
    ws = [lp[k] for k in _EXPERT_LEAVES]
    if layer is not None and isinstance(ws[0], QTensor):
        # int8 experts dequantize on use: one layer's, not the stack's
        ws = [jax.tree_util.tree_map(
            lambda a: jax.lax.dynamic_index_in_dim(a, layer, 0, False), w)
            for w in ws]
        layer = None
    out, counts = grouped_experts(
        flat, logits, *(_wt(w, cfg.dtype) for w in ws), offset, layer,
        top_k=cfg.top_k, held=held, score=cfg.router_score,
        bias=lp.get("router_bias"), scale=cfg.routed_scale)
    if ep_axis is not None:
        out = jax.lax.psum(out, ep_axis)
    return out.reshape(b, t, d), {**_zero_aux(), "expert_counts": counts}


def _ffn(cfg: TransformerConfig, mesh, lp, h, ep_axis: Optional[str] = None,
         tp_axis: Optional[str] = None, inbody_ad: bool = False,
         expert_layer=None):
    """The block's feed-forward dispatch (dense / switch / dense-MoE /
    grouped) — shared by the train and decode paths so they cannot drift.
    ``expert_layer``: see ``_moe_grouped``'s ``layer``.

    ``ep_axis``/``tp_axis`` select the manual-collective MoE forms for use
    inside a pipeline stage's shard_map body (tokens replicated over
    ep/tp, expert weights ep-sharded and/or width-sharded over tp,
    outputs psum'd).  ``inbody_ad=True`` (1F1B) swaps the collectives for
    the transpose-carrying f/g pair — dense top-k MoE only (the switch
    dispatch path still assumes outer differentiation)."""
    if not cfg.n_experts:
        return _mlp(cfg, lp, h), _zero_aux()
    if cfg.moe_impl == "grouped" and (ep_axis is not None
                                      or tp_axis is not None):
        if tp_axis is not None or inbody_ad:
            raise ValueError("moe_impl='grouped' shards whole experts over "
                             "ep; it has no tp or in-body-AD form")
        out, aux = _moe_grouped(cfg, lp, h, ep_axis=ep_axis,
                                layer=expert_layer)
    elif ep_axis is not None or tp_axis is not None:
        if cfg.moe_impl == "switch":
            if inbody_ad:
                raise ValueError(
                    "moe_impl='switch' does not support in-body AD (1F1B);"
                    " use the dense top-k MoE or pp_schedule="
                    "'gpipe'/'circular'")
            from tfmesos_tpu.parallel.moe import switch_moe_replicated_local
            b, t, d = h.shape
            out, aux = switch_moe_replicated_local(
                h.reshape(b * t, d), lp["router"].astype(cfg.dtype),
                lp["e_gate"], lp["e_up"], lp["e_down"], ep_axis=ep_axis,
                capacity_factor=cfg.capacity_factor, top_k=cfg.top_k,
                tp_axis=tp_axis)
            out = out.reshape(b, t, d)
        else:
            out, aux = _moe(cfg, lp, h, ep_axis=ep_axis, tp_axis=tp_axis,
                            inbody_ad=inbody_ad)
    elif cfg.moe_impl == "switch":
        # Same model function with or without a mesh (switch_moe falls back
        # to its single-device reference when the ep axis is absent).
        out, aux = _moe_switch(cfg, mesh, lp, h)
    elif cfg.moe_impl == "grouped":
        out, aux = _moe_grouped(cfg, lp, h, layer=expert_layer)
    else:
        out, aux = _moe(cfg, lp, h)
    if cfg.shared_width:
        # Always-on shared expert(s): dense FFN added to the routed output.
        # The shared weights replicate over ep; under manual tp their width
        # shards like the dense MLP's, so the partial needs its own psum
        # (the f/g pair under in-body AD, fanning h over tp alone — the
        # shared compute is replicated over ep).
        h_s = h
        if inbody_ad and tp_axis is not None:
            from tfmesos_tpu.parallel.collectives import (
                broadcast_replicated_grad, psum_replicated_grad)
            h_s = broadcast_replicated_grad(h, tp_axis)
        shared = _qswiglu(h_s, lp["s_gate"], lp["s_up"], lp["s_down"],
                          cfg.dtype)
        if tp_axis is not None:
            shared = (psum_replicated_grad(shared, tp_axis) if inbody_ad
                      else jax.lax.psum(shared, tp_axis))
        out = out + shared
    return out, aux


def _dense_tp_attn_partition() -> Dict[str, P]:
    """Per-leaf NON-leading-dim PartitionSpecs for a manual-tp stage's
    attention half (Megatron column/row splits) — shared by the
    gpipe/circular pp path and the 1F1B train step so the two tables
    cannot drift."""
    return {
        "attn_norm": P(None, None), "mlp_norm": P(None, None),
        "wq": P(None, None, "tp"), "wk": P(None, None, "tp"),
        "wv": P(None, None, "tp"), "wo": P(None, "tp", None),
    }


def _dense_tp_mlp_partition() -> Dict[str, P]:
    return {"w_gate": P(None, None, "tp"), "w_up": P(None, None, "tp"),
            "w_down": P(None, "tp", None)}


def _moe_param_partition(ep_axis: Optional[str],
                         tp_axis: Optional[str]) -> Dict[str, P]:
    """Per-leaf NON-leading-dim specs for the MoE FFN half: whole experts
    over ep, per-expert Megatron FFN widths over tp, router replicated
    (every device routes over all E experts) — shared by the
    gpipe/circular pp route and the 1F1B train step so the tables cannot
    drift."""
    return {
        "router": P(None, None, None),
        "e_gate": P(None, ep_axis, None, tp_axis),
        "e_up": P(None, ep_axis, None, tp_axis),
        "e_down": P(None, ep_axis, tp_axis, None),
    }


def _shared_expert_partition(tp_axis: Optional[str]) -> Dict[str, P]:
    """Shared (always-on) experts: width-sharded over tp like the dense
    MLP, replicated over ep — shared by both pp routes."""
    return {"s_gate": P(None, None, tp_axis), "s_up": P(None, None, tp_axis),
            "s_down": P(None, tp_axis, None)}


def _replicated_attn_partition() -> Dict[str, P]:
    """Attention half fully replicated (the ep-only stage layout: only
    expert weights shard) — shared by both pp routes."""
    return {
        "attn_norm": P(None, None), "mlp_norm": P(None, None),
        "wq": P(None, None, None), "wk": P(None, None, None),
        "wv": P(None, None, None), "wo": P(None, None, None),
    }


def _block_manual_tp(cfg: TransformerConfig, x, lp, positions,
                     tp_axis: str = "tp", ep_axis: Optional[str] = None,
                     inbody_ad: bool = False,
                     sp_axis: Optional[str] = None):
    """Megatron-style block with MANUAL tp collectives, for use inside a
    pipeline stage (nested shard_map is not allowed there, explicit psum
    is).  ``lp`` leaves arrive as local tp shards: wq/wk/wv column-sharded
    [d, hd/tp] (wk/wv at kv width for GQA — requires tp | kv_heads so the
    local h//g head grouping stays aligned), wo row-sharded [hd/tp, d],
    w_gate/w_up [d, f/tp], w_down [f/tp, d]; norms replicated.  One psum
    after each row-parallel matmul — the textbook 2-collectives-per-block
    tp pattern.  With experts, the FFN half runs the manual-collective MoE
    (``_ffn`` with tp/ep axes: expert widths tp-sharded, experts
    ep-sharded).  Returns (x, aux).

    ``inbody_ad=True`` (dense configs; the 1F1B train step) swaps the
    collectives for the Megatron f/g pair that carry their own
    transposes — required when the stage is differentiated with
    ``jax.vjp`` INSIDE the shard_map, where plain psum's transpose
    double-counts over tp (parallel/collectives.py)."""
    tp = axis_size(tp_axis)
    heads_loc = cfg.n_heads // tp
    kv_loc = cfg.kv_heads // tp
    b, t, _ = x.shape
    if inbody_ad:
        from tfmesos_tpu.parallel.collectives import (
            broadcast_replicated_grad, psum_replicated_grad)
        fan = lambda v_: broadcast_replicated_grad(v_, tp_axis)
        red = lambda v_: psum_replicated_grad(v_, tp_axis)
    else:
        fan = lambda v_: v_
        red = lambda v_: jax.lax.psum(v_, tp_axis)
    h = fan(_norm(cfg, x, lp["attn_norm"]))
    q = _qmm(h, lp["wq"], cfg.dtype).reshape(b, t, heads_loc, cfg.head_dim)
    k = _qmm(h, lp["wk"], cfg.dtype).reshape(b, t, kv_loc, cfg.head_dim)
    v = _qmm(h, lp["wv"], cfg.dtype).reshape(b, t, kv_loc, cfg.head_dim)
    q = rope(q, positions, cfg.rope_theta)
    k = rope(k, positions, cfg.rope_theta)
    if sp_axis is not None:
        # tp x sp: local HEADS x local SEQUENCE, positions global (the
        # caller offsets them) — see _sp_attend.
        o = _sp_attend(cfg, q, k, v, sp_axis, inbody_ad)
    else:
        o = attend(q, k, v, mesh=None, causal=True,
                   window=cfg.window)  # local heads
    x = x + red(_qmm(o.reshape(b, t, -1), lp["wo"], cfg.dtype))
    h = _norm(cfg, x, lp["mlp_norm"])
    if cfg.n_experts:
        # The MoE half fans/reduces internally (over ep AND tp — the f/g
        # pair when inbody_ad, plain psum otherwise).
        ffn, aux = _ffn(cfg, None, lp, h, ep_axis=ep_axis, tp_axis=tp_axis,
                        inbody_ad=inbody_ad)
        return x + ffn, aux
    ffn = _mlp(cfg, lp, fan(h))                   # local d_ff shard
    return x + red(ffn), _zero_aux()


def _sp_gather_attention(cfg: TransformerConfig, q, k, v, axis: str):
    """Sequence-parallel attention by K/V all_gather: the local q shard
    attends the FULL gathered sequence with global-position masks.

    This is the sp form for stage bodies that run inside DIVERGENT
    control flow (the 1F1B tick's ``lax.switch``): an all_gather lowers
    to a SUBGROUP collective over the sp group — like the tp psums the
    fused schedule already runs in branches — whereas the einsum ring's
    ``ppermute`` lowers with a global participant set and deadlocks
    when pipeline stages take different branches.  Trades the ring's
    overlapped O(T/sp) K/V residency for one gather; q/dq stay sharded
    and the all_gather transposes to a reduce_scatter, so in-body vjp
    sums per-shard dK/dV contributions exactly once."""
    scale = 1.0 / math.sqrt(cfg.head_dim)
    tq = q.shape[1]
    # Gather the NARROW (kv-width) K/V and broadcast GQA groups locally
    # afterwards: 1/g the collective bytes and gathered residency.
    kg = jax.lax.all_gather(k, axis, axis=1, tiled=True)    # [B, T, KV, D]
    vg = jax.lax.all_gather(v, axis, axis=1, tiled=True)
    g = q.shape[2] // kg.shape[2]
    if g > 1:
        kg = jnp.repeat(kg, g, axis=2)
        vg = jnp.repeat(vg, g, axis=2)
    tk = kg.shape[1]
    idx = jax.lax.axis_index(axis)
    qpos = idx * tq + jax.lax.broadcasted_iota(jnp.int32, (tq, tk), 0)
    kpos = jax.lax.broadcasted_iota(jnp.int32, (tq, tk), 1)
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32) * scale,
                   kg.astype(jnp.float32))
    bad = kpos > qpos
    if cfg.window is not None:
        bad = bad | (kpos < qpos - (cfg.window - 1))
    s = jnp.where(bad[None, None], float("-inf"), s)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, vg.astype(jnp.float32))
    return o.astype(q.dtype)


def _sp_attend(cfg: TransformerConfig, q, k, v, sp_axis: str,
               inbody_ad: bool):
    """Manual sequence-parallel attention dispatch, shared by the dense
    and manual-tp stage blocks (q/k/v may be tp-local head shards): the
    K/V-gather form under in-body AD (1F1B's divergent branches; GQA
    broadcasts AFTER the gather), the einsum ring under outer AD
    (lockstep gpipe ticks; the ring helper matches heads one-for-one,
    so GQA broadcasts before the hops)."""
    if inbody_ad:
        return _sp_gather_attention(cfg, q, k, v, sp_axis)
    g = q.shape[2] // k.shape[2]
    if g > 1:
        k = jnp.repeat(k, g, axis=2)
        v = jnp.repeat(v, g, axis=2)
    from tfmesos_tpu.parallel.ring_attention import ring_attention_local
    return ring_attention_local(q, k, v, axis=sp_axis, causal=True,
                                window=cfg.window)


def _block(cfg: TransformerConfig, mesh: Optional[Mesh], x, lp, positions,
           ep_axis: Optional[str] = None, inbody_ad: bool = False,
           sp_axis: Optional[str] = None):
    """One transformer block.  ``sp_axis`` selects the MANUAL
    sequence-parallel form for use inside a pipeline stage's shard_map
    body (a nested shard_map is not allowed there): activations arrive
    as local sequence shards and ``positions`` must already be GLOBAL.
    Attention runs the einsum ring (``ring_attention_local``) under
    outer AD, or the K/V-gather form under ``inbody_ad`` (the 1F1B
    tick's branches — see ``_sp_gather_attention``)."""
    b, t, d = x.shape
    with jax.named_scope("attention"):
        h = _norm(cfg, x, lp["attn_norm"])
        q = _qmm(h, lp["wq"], cfg.dtype).reshape(b, t, cfg.n_heads,
                                                 cfg.head_dim)
        k = _qmm(h, lp["wk"], cfg.dtype).reshape(b, t, cfg.kv_heads,
                                                 cfg.head_dim)
        v = _qmm(h, lp["wv"], cfg.dtype).reshape(b, t, cfg.kv_heads,
                                                 cfg.head_dim)
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
        if sp_axis is not None:
            o = _sp_attend(cfg, q, k, v, sp_axis, inbody_ad)
        else:
            # GQA (kv_heads < n_heads) flows through attend() at kv
            # width: the flash kernels map q head h -> kv head
            # h // (H/KV) in their index maps, so training never
            # materializes the repeated K/V; the sp impls broadcast up
            # internally.
            o = attend(q, k, v, mesh=mesh, causal=True, sp_impl=cfg.sp_impl,
                       window=cfg.window)
        x = x + _qmm(o.reshape(b, t, -1), lp["wo"], cfg.dtype)
    with jax.named_scope("mlp"):
        h = _norm(cfg, x, lp["mlp_norm"])
        ffn, aux = _ffn(cfg, mesh, lp, h, ep_axis=ep_axis,
                        inbody_ad=inbody_ad)
        return x + ffn, aux


def forward(cfg: TransformerConfig, params, tokens, mesh: Optional[Mesh] = None,
            return_aux: bool = False):
    """tokens [B, T] int32 → logits [B, T, V] (plus per-layer-averaged router
    aux metrics when ``return_aux``)."""
    x, aux = forward_hidden(cfg, params, tokens, mesh)
    logits = _qmm(x, params["head"], cfg.dtype)
    return (logits, aux) if return_aux else logits


def forward_hidden(cfg: TransformerConfig, params, tokens,
                   mesh: Optional[Mesh] = None):
    """The trunk: tokens [B, T] → (final-norm hidden states [B, T, d],
    per-layer-averaged router aux metrics).  ``forward`` applies the
    unembedding head on top; ``loss_fn`` may instead feed the hidden states
    to the fused head+cross-entropy, which never materializes full logits.

    Sequence positions are global even when activations are sp-sharded:
    ring attention receives the full logical sequence sharded along T, and
    rope positions follow the global index.
    """
    if cfg.layer_types is not None or cfg.moe_impl == "grouped":
        raise NotImplementedError(
            "forward() runs one homogeneous stack with the trainer's expert "
            "forms; layer_types and moe_impl='grouped' are the serving "
            "path's (decode_step through a paged cache)")
    if (cfg.attention != "full" or cfg.n_pred_heads != 1
            or cfg.residual_dtype is not None
            or cfg.logits_dtype is not None
            or not cfg.rope or cfg.tie_embeddings or cfg.attn_gate
            or cfg.attn_head_dim is not None
            or any(v is not None for v in (
                cfg.attn_scale, cfg.embed_scale, cfg.residual_scale,
                cfg.logits_scale))):
        raise NotImplementedError(
            "forward() runs full attention with rope at head_dim ** -0.5, "
            "one untied output head and a residual stream in the compute "
            "dtype; attention='eva', n_pred_heads, residual_dtype, "
            "logits_dtype, rope=False, attn_scale, the multipliers and "
            "tie_embeddings are the serving path's (decode_step through a "
            "paged cache)")
    b, t = tokens.shape
    x = _embed_lookup(params["embed"], tokens, cfg.dtype)
    positions = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (b, t))

    block = lambda x_, lp_, pos: _block(cfg, mesh, x_, lp_, pos)
    if cfg.remat:
        block = jax.checkpoint(block)

    aux = _zero_aux()
    pp = mesh.shape.get("pp", 1) if mesh is not None else 1
    if pp > 1:
        from tfmesos_tpu.parallel.pipeline import pipeline_apply
        tp = mesh.shape.get("tp", 1)
        n_chunks = pp * cfg.pp_virtual_stages
        if cfg.n_layers % n_chunks:
            raise ValueError(f"{cfg.n_layers} layers not divisible into "
                             f"{n_chunks} pipeline chunks")
        per = cfg.n_layers // n_chunks
        stacked = jax.tree_util.tree_map(
            lambda p: p.reshape(n_chunks, per, *p.shape[1:]),
            params["layers"])

        # Stages compose with tp via MANUAL collectives (weights sharded
        # over tp, one psum per row-parallel matmul) — nested shard_map is
        # not allowed inside the pipeline's own shard_map.
        ep = mesh.shape.get("ep", 1)
        ep_axis = "ep" if (cfg.n_experts and ep > 1) else None
        # pp x sp: shard the SEQUENCE over sp inside stages — manual
        # ring/gather attention with global rope positions (dense tp
        # stages compose: local heads x local sequence).  The sequence
        # stays replicated when it does not divide over sp and for
        # switch MoE (its capacity-based token dropping is a
        # FULL-sequence competition — deciding it per T/sp shard would
        # silently change which tokens drop).
        sp = mesh.shape.get("sp", 1)
        sp_axis = ("sp" if (sp > 1 and t % sp == 0
                            and not (cfg.n_experts
                                     and cfg.moe_impl == "switch"))
                   else None)
        if tp > 1:
            if cfg.kv_heads % tp:
                raise ValueError(
                    f"pp x tp needs tp ({tp}) to divide kv_heads "
                    f"({cfg.kv_heads}) so the local head grouping stays "
                    f"aligned; lower tp or raise kv_heads")
            stage_block = lambda c, lp_, pos: _block_manual_tp(
                cfg, c, lp_, pos, ep_axis=ep_axis, sp_axis=sp_axis)
            partition = _dense_tp_attn_partition()
            if cfg.n_experts:
                # Per-expert Megatron: FFN widths shard over tp, whole
                # experts over ep (when present).
                partition.update(_moe_param_partition(ep_axis, "tp"))
                if cfg.n_shared_experts:
                    partition.update(_shared_expert_partition("tp"))
            else:
                partition.update(_dense_tp_mlp_partition())
        else:
            stage_block = lambda c, lp_, pos: _block(cfg, None, c, lp_, pos,
                                                     ep_axis=ep_axis,
                                                     sp_axis=sp_axis)
            # Expert weights shard over ep inside the stage (the router
            # stays replicated so every device routes over all E experts).
            partition = None
            if ep_axis:
                partition = {
                    **_replicated_attn_partition(),
                    **_moe_param_partition(ep_axis, None),
                }
                if cfg.n_shared_experts:
                    partition.update(_shared_expert_partition(None))
        if cfg.remat:
            stage_block = jax.checkpoint(stage_block)

        # Router aux rides the pipeline when experts are on: stages return
        # per-chunk aux means and pipeline_apply averages them over chunks
        # x microbatches (the grad-accumulation estimator of the non-pp
        # batch statistics).
        with_aux = _zero_aux() if cfg.n_experts else False

        def stage_fn(stage_params, h):
            pos = jnp.arange(h.shape[1], dtype=jnp.int32)
            if sp_axis is not None:
                # Local shard i holds global positions
                # [i*t_loc, (i+1)*t_loc): rope and the ring's causal
                # bounds both follow the global index.
                pos = pos + jax.lax.axis_index(sp_axis) * h.shape[1]
            pos = jnp.broadcast_to(pos, h.shape[:2])

            def body(carry, lp):
                out, layer_aux = stage_block(carry, lp, pos)
                return out, layer_aux
            out, stacked_aux = jax.lax.scan(body, h, stage_params)
            if with_aux is False:
                return out
            return out, jax.tree_util.tree_map(jnp.mean, stacked_aux)

        x = pipeline_apply(stage_fn, stacked, x, mesh,
                           param_partition=partition,
                           schedule=cfg.pp_schedule,
                           virtual_stages=cfg.pp_virtual_stages,
                           with_aux=with_aux, seq_axis=sp_axis)
        if with_aux is not False:
            x, aux = x
    else:
        def body(carry, lp):
            out, layer_aux = block(carry, lp, positions)
            return out, layer_aux
        x, stacked_aux = jax.lax.scan(body, x, params["layers"])
        aux = jax.tree_util.tree_map(jnp.mean, stacked_aux)

    return _norm(cfg, x, params["norm_f"]), aux


def init_cache(cfg: TransformerConfig, batch: int, max_len: int,
               dtype=None, quantized: bool = False) -> Dict[str, Any]:
    """KV cache for autoregressive decoding: stacked
    [L, B, KV, M, head_dim] K/V buffers — kv-head-major with (seq,
    head_dim) trailing, the flash-decode kernel's native tiling, so
    decode never transposes cache-sized data.  The layer scan CARRIES
    the stacked buffers and each step writes its token slot in place at
    its layer index (``_cache_write``); per-step HBM traffic is the slot
    write plus what attention actually reads — never a restack of the
    whole buffer.

    ``quantized=True`` stores the cache as int8 :class:`QTensor`s with
    one fp32 absmax scale per (layer, batch, head, position), held
    LANE-MAJOR ([L, B, KV, 1, M] — positions on the trailing dim, as the
    kernel consumes them) — long-context decode streams the whole cache
    every step, so halving its bytes vs bf16 is the long-prompt analogue
    of weight-only int8.  Writes quantize the incoming K/V chunk; reads
    fold the scales in-kernel (or dequantize at the attention einsum).

    With sliding-window attention (``cfg.window``) the buffer is a ROLLING
    cache of ``window`` slots (slot = position mod window): a position's
    slot is reclaimed exactly when it leaves the window, so memory and
    per-step cache bandwidth are O(window) regardless of how long
    generation runs.
    """
    if cfg.attention == "eva":
        raise ValueError("attention='eva' keeps summaries and a window in "
                         "a paged cache (init_paged_cache); it has no "
                         "linear one")
    if cfg.layer_types is not None:
        raise ValueError("a typed stack (layer_types) keeps pages and row "
                         "states (init_paged_cache, init_row_state); it has "
                         "no linear cache")
    if cfg.window is not None:
        max_len = min(max_len, cfg.window)
    shape = (cfg.n_layers, batch, cfg.kv_heads, max_len, cfg.head_dim)
    if quantized:
        if dtype is not None:
            raise ValueError("init_cache: dtype and quantized=True conflict "
                             "(an int8 cache's dtypes are fixed)")

        def buf():
            # Distinct buffers for k and v, matching the fp path — aliasing
            # one QTensor for both halves would break if decode ever donates
            # the cache (the same buffer donated twice).
            return QTensor(jnp.zeros(shape, jnp.int8),
                           jnp.ones(shape[:-2] + (1, max_len), jnp.float32))

        return {"k": buf(), "v": buf()}
    dtype = dtype or cfg.dtype
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def init_paged_cache(cfg: TransformerConfig, n_pages: int,
                     page_size: int = 128, dtype=None,
                     quantized: bool = False) -> Dict[str, Any]:
    """A PAGED KV cache: one physical pool of ``n_pages`` pages per layer,
    shared by every sequence — rows map logical cache blocks to pool
    pages through a ``page_table`` ([B, NP] int32, built by
    :class:`PageAllocator`), so mixed-length sequences consume memory
    proportional to their LENGTH, not to a per-row max_len buffer (the
    PagedAttention serving layout; docs/SERVING.md).

    Pass ``{"k", "v", "pages"}`` (this dict plus the allocator's table
    under ``"pages"``) to ``decode_step``.  ``quantized=True`` stores the
    pool as int8 with per-position scales (the paged kernel folds them
    into the score rows, so HBM streams int8 pages).  Windowed (rolling)
    configs address by slot and don't page.

    The leaves: ``v`` [attention layers, P, KV, page, Dv] and ``k``
    [attention layers, P, KV / f, page, f * Dk], ``f`` = ``cfg.k_pack()``
    heads' keys of a position side by side (1 for every head size that is
    whole lane tiles: K then has V's layout; 2 at 192 channels, which the
    native layout would pad to 256 lanes in HBM).  A position's keys
    ``[KV, Dk]`` ARE ``[KV / f, f * Dk]`` as they lie, so the writes take
    the packed chunk by a reshape; the paged kernel reads the pool as laid
    out, no transpose or pad a call.
    """
    if cfg.window is not None and cfg.layer_types is None:
        raise ValueError("paged caches do not compose with sliding-window "
                         "configs (rolling caches address by slot); a typed "
                         "stack's 'window' layers keep a ring a row "
                         "(init_row_state) beside the other layers' pages")
    if cfg.attention == "eva":
        # A row's table is [summary pages | window pages]: a closed window
        # leaves whole pages of summaries, so every window starts a page.
        if cfg.eva_summaries % page_size:
            raise ValueError(
                f"attention='eva' pages need eva_window / eva_chunk "
                f"({cfg.eva_summaries} summaries a window) to be a "
                f"multiple of page_size ({page_size})")
        if quantized:
            raise ValueError("attention='eva' summaries are pooled from "
                             "the cached entries: an int8 pool is refused")
    if page_size % 8 or page_size > 1024:
        raise ValueError(f"page_size ({page_size}) must be a multiple of "
                         f"8 and <= 1024 (the kernel's tile shape)")
    if quantized:
        if dtype is not None:
            raise ValueError("init_paged_cache: dtype and quantized=True "
                             "conflict (an int8 pool's dtypes are fixed)")
        if cfg.v_head_dim != cfg.head_dim:
            raise ValueError("an int8 pool keeps one head size for K and V "
                             "(attn_v_head_dim is a plain pool's)")
        shape = (cfg.n_attn_layers, n_pages, cfg.kv_heads, page_size,
                 cfg.head_dim)

        def buf():
            # Scales are LANE-MAJOR ([..., 1, page] — positions on the
            # trailing dim), deviating from QTensor's usual trailing-1
            # convention, so the kernel consumes them without a per-call
            # transpose of pool-capacity-sized data.  flash_decode_paged
            # and its reference are the only consumers.
            return QTensor(jnp.zeros(shape, jnp.int8),
                           jnp.ones(shape[:-2] + (1, page_size),
                                    jnp.float32))

        return {"k": buf(), "v": buf()}
    dtype = dtype or cfg.dtype
    # (page, head_dim) trailing — the kernel's native layout, so serving
    # never transposes the shared pool.
    f = cfg.k_pack()
    lead = (cfg.n_attn_layers, n_pages)
    return {"k": jnp.zeros(lead + (cfg.kv_heads // f, page_size,
                                   f * cfg.head_dim), dtype),
            "v": jnp.zeros(lead + (cfg.kv_heads, page_size, cfg.v_head_dim),
                           dtype)}


def init_row_state(cfg: TransformerConfig, rows: int) -> Dict[str, Any]:
    """The recurrent state of ``rows`` row slots, for the layers that keep
    no K/V: the leaves of the kinds of layer present (``_ROW_STATE`` names
    each kind's, in the order the layer scans carry them).  Mamba layers:
    ``ssm`` [mamba layers, rows, heads * head_dim, state] float32 (heads and
    head channels as one dim, see ``_mamba_mixer``) and ``conv`` [mamba
    layers, rows, mamba_conv - 1, conv channels], the conv's inputs before
    each row's next position, in the compute dtype.  KDA layers: ``kda_s``
    [kda layers, rows, heads * head_dim, head_dim] float32 (heads and key
    channels as one dim) and ``kda_conv`` [kda layers, rows, kda_conv - 1,
    3 * heads * head_dim], the inputs of the conv over [q | k | v].  Window
    layers: ``swa_k`` [window layers, rows, KV / f, window, f * Dk] and
    ``swa_v`` [window layers, rows, KV, window, Dv], a ring of the last
    ``window`` positions' K and V (KV the window kind's K/V heads, Dk / Dv
    the keys' and the values' head sizes, ``f`` = ``cfg.k_pack("window")``
    heads' keys of a slot side by side: 1 unless a key is a lane tile and a
    half).  Its size does not depend on a row's context.  Pass it to ``decode_step`` under
    ``cache["state"]`` beside the pool; a one-token step reads and writes
    every slot, a prefill from position 0 starts from an empty state and
    writes its rows' slots (``cache["slots"]``), whatever they held."""
    state = {}
    lm, lk, lw = cfg.n_mamba_layers, cfg.n_kda_layers, cfg.n_window_layers
    if lw:
        # K and V of the last ``window`` positions (slot = position mod
        # window, keys after rope), in the layout ``flash_decode`` reads:
        # the window kind's K/V heads, V at its own head size, K with
        # ``k_pack("window")`` heads' keys of a slot side by side
        kv, f = cfg.kind_kv_heads("window"), cfg.k_pack("window")
        state.update(
            swa_k=jnp.zeros((lw, rows, kv // f, cfg.window,
                             f * cfg.head_dim), cfg.dtype),
            swa_v=jnp.zeros((lw, rows, kv, cfg.window, cfg.v_head_dim),
                            cfg.dtype))
    if lm:
        state.update(
            ssm=jnp.zeros((lm, rows, cfg.mamba_inner, cfg.mamba_state),
                          jnp.float32),
            conv=jnp.zeros((lm, rows, cfg.mamba_conv - 1, cfg.mamba_conv_dim),
                           cfg.dtype))
    if lk:
        state.update(
            kda_s=jnp.zeros((lk, rows, cfg.kda_inner, cfg.kda_head_dim),
                            jnp.float32),
            kda_conv=jnp.zeros(
                (lk, rows, cfg.kda_conv - 1, 3 * cfg.kda_inner), cfg.dtype))
    return state


class PageAllocator:
    """Host-side page bookkeeping for :func:`init_paged_cache` (numpy,
    no jax): a free list over ``n_pages`` and per-row page lists.  The
    serving loop allocates pages as sequences grow (``ensure``), frees
    them when requests finish (``release``), and hands ``table()`` to
    ``decode_step`` each call.  Rows it serves may come and go — that
    admission control is the caller's loop, as docs/SERVING.md notes."""

    def __init__(self, n_pages: int, page_size: int):
        import numpy as np

        self._np = np
        self.page_size = int(page_size)
        self.free = list(range(n_pages - 1, -1, -1))
        self.rows: Dict[int, list] = {}
        # Optional allocation-pressure hook: called with the free list
        # empty, returns True after putting at least one page back on it
        # (the serving prefix cache reclaims zero-ref cached pages this
        # way — retained pages stay resident until someone actually
        # needs the HBM, never blocking an allocation that could be
        # served by evicting).
        self.reclaim = None

    def _take(self) -> int:
        if not self.free:
            while self.reclaim is not None and self.reclaim():
                if self.free:
                    break
            if not self.free:
                raise RuntimeError("page pool exhausted")
        return self.free.pop()

    def ensure(self, row: int, length: int) -> None:
        """Back positions [0, length) of ``row`` with pages."""
        need = -(-int(length) // self.page_size)
        pages = self.rows.setdefault(row, [])
        while len(pages) < need:
            pages.append(self._take())

    def release(self, row: int) -> None:
        self.free.extend(reversed(self.rows.pop(row, [])))

    def trim(self, row: int, length: int) -> int:
        """Free ``row``'s pages behind the first ``length`` entries (an
        EVA window closed: its summaries stay, its pages go); returns how
        many went."""
        keep = -(-int(length) // self.page_size)
        pages = self.rows.get(row, [])
        gone = pages[keep:]
        del pages[keep:]
        self.free.extend(reversed(gone))
        return len(gone)

    def reserve_page(self) -> int:
        """Permanently take one page out of circulation and return its id
        (serving uses this as a write sink for inactive decode rows)."""
        return self._take()

    def free_count(self) -> int:
        return len(self.free)

    def allocated(self, row: int) -> int:
        """Pages currently backing ``row``."""
        return len(self.rows.get(row, []))

    def table(self, rows, width: Optional[int] = None,
              fill: int = 0) -> "jnp.ndarray":
        """[len(rows), NP] table.  NP defaults to the longest listed row's
        page count; pass ``width`` to fix the shape (one compiled decode
        shape for a whole serving run).  Unused entries hold ``fill`` —
        never FETCHED (the per-row block bound stops first), but batched
        decode steps WRITE one position per row each step, so continuous
        serving points them at a reserved sink page."""
        np = self._np
        lists = [self.rows.get(r, []) for r in rows]
        if width is None:
            width = max(1, max((len(p) for p in lists), default=1))
        t = np.full((len(lists), width), fill, np.int32)
        for i, pages in enumerate(lists):
            t[i, :len(pages)] = pages
        return jnp.asarray(t)


def _paged_cache_write(pool, chunk, li, page_table, pos):
    """Write a [B, t, H, Dh] chunk into layer ``li`` of the STACKED page
    pool ([L, P, KV, page, Dh]; int8 QTensors quantize per position on
    the way in) at logical positions ``pos..pos+t-1`` per row (``pos``
    scalar or [B]): one scatter over (page, offset) pairs chased through
    the table.  The pool is a layer-scan CARRY and the scatter is meant to
    update it in place.  Only the mesh path (``_sharded_paged_step``)
    still writes per layer through here.  It has the window shape (a slice
    over KV between the indexed page and offset) that made the TPU
    compiler relayout the whole pool around ``_paged_cache_write_all``'s
    scatter on the single-host path until PR 25; whether it does so per
    shard too is unmeasured (PERF.md §7)."""
    b, t = chunk.shape[:2]
    ps = (pool.values if isinstance(pool, QTensor) else pool).shape[3]
    posv = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
    lpos = posv[:, None] + jnp.arange(t, dtype=jnp.int32)[None]   # [B, t]
    # Clamp the block index explicitly: serving parks inactive rows at
    # position max_len, whose block can be one past the table width when
    # max_len is a page multiple.  A parked row's whole table row is the
    # sink page, so the clamped entry is still the sink — but make that a
    # guarantee of this code, not of out-of-bounds gather semantics.
    blk = jnp.minimum(lpos // ps, page_table.shape[1] - 1)
    pages = jnp.take_along_axis(page_table, blk, axis=1).reshape(-1)
    offs = (lpos % ps).reshape(-1)

    def put(buf, x):
        return buf.at[li, pages, :, offs].set(
            x.reshape(b * t, *x.shape[2:]).astype(buf.dtype))

    if isinstance(pool, QTensor):
        from tfmesos_tpu.ops.quant import quantize_int8_reference
        vals, scale = quantize_int8_reference(chunk)
        # Scales pool is lane-major [L, P, KV, 1, page] (see
        # init_paged_cache): scatter at (layer, page, :, 0, offset).
        scales = pool.scales.at[li, pages, :, 0, offs].set(
            scale.reshape(b * t, scale.shape[2]))
        return QTensor(put(pool.values, vals), scales)
    return put(pool, chunk)


def _paged_cache_write_all(pool, chunks, page_table, pos,
                           aligned: bool = False, layer0=0):
    """Commit ALL layers' deferred chunks ([L, B, t, KV, Dh], stacked by
    the decode layer scan) into the page pool ([L, P, KV, page, Dh]) with
    one scatter per pool leaf, expressed IN THE POOL'S OWN LAYOUT.

    The layout rule: every pool dim in front of the scatter's window is
    indexed, and the window is a trailing slab of the pool
    ([Dh], or [page, Dh]).  The TPU compiler then scatters into a bitcast
    of the donated pool, in place.  A window that skips dims (slices over
    layer and KV around the indexed page and offset, as this function had
    it before) makes the compiler pick another operand layout and wrap
    the scatter in two whole-pool copies per leaf: on the v5e those four
    copies of a 2.7 GB leaf were 33 ms of a 53 ms decode block (PERF.md,
    PR 25) — the cost was the relayout, never the scatter.

    Two windows, chosen from static shapes:

    * rows — window [Dh], (layer, page, kv, offset) indexed per token: t = 1
      (the steady-state deferred token) and any chunk whose start is traced
      or not page-aligned (chunked prefill, fused tick, speculative verify);
    * pages — window [page, Dh], (layer, page, kv) indexed per block: a
      chunk of whole pages at a static page-aligned ``pos`` (prefill).  A
      TPU scatter walks its index rows one by one, and a prefill in the
      rows form has t*L*KV of them.

    Same index math (sink clamp included) and the same per-row absmax
    int8 rule as the per-layer ``_paged_cache_write``; the lane-major
    scales leaf ([L, P, KV, 1, page]) follows the same rule with a scalar
    or a [1, page] window.  ``aligned`` vouches that a TRACED scalar
    ``pos`` is page-aligned (an EVA window's first entry), which opens the
    pages form to it; ``layer0`` (traced OK) is the pool layer of
    ``chunks[0]``, for a caller inside the layer scan that commits its own
    layer's chunk ([1, B, t, KV, Dh]) instead of stacking it."""
    L, b, t, kvh, dh = chunks.shape
    ps = (pool.values if isinstance(pool, QTensor) else pool).shape[3]
    last = page_table.shape[1] - 1
    li = (layer0 + jnp.arange(L, dtype=jnp.int32))[None, :, None]
    ki = jnp.arange(kvh, dtype=jnp.int32)[None, None, :]
    whole_pages = t % ps == 0 and (
        aligned or (isinstance(pos, int) and pos % ps == 0))
    if whole_pages:
        nb = t // ps
        blk = jnp.minimum(pos // ps + jnp.arange(nb, dtype=jnp.int32), last)
        at = scale_at = (li, page_table[:, blk].reshape(-1, 1, 1), ki)
        # [L, B, nb, page, KV, Dh] -> [B*nb, L, KV, page, Dh] page windows.
        x = chunks.reshape(L, b, nb, ps, kvh, dh).transpose(
            1, 2, 0, 4, 3, 5).reshape(b * nb, L, kvh, ps, dh)
    else:
        posv = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
        lpos = posv[:, None] + jnp.arange(t, dtype=jnp.int32)[None]  # [B, t]
        blk = jnp.minimum(lpos // ps, last)
        pages = jnp.take_along_axis(page_table, blk, axis=1)
        at = (li, pages.reshape(-1, 1, 1), ki, (lpos % ps).reshape(-1, 1, 1))
        scale_at = at[:3] + (0, at[3])
        # [L, B, t, KV, Dh] -> [B*t, L, KV, Dh] update rows.
        x = chunks.transpose(1, 2, 0, 3, 4).reshape(b * t, L, kvh, dh)

    if isinstance(pool, QTensor):
        from tfmesos_tpu.ops.quant import quantize_int8_reference
        vals, scale = quantize_int8_reference(x)
        # [..., page, 1] -> the scales leaf's [1, page] window (pages), or
        # the scalar at (.., 0, offset) (rows).
        scale = jnp.swapaxes(scale, -1, -2) if whole_pages else scale[..., 0]
        return QTensor(pool.values.at[at].set(vals),
                       pool.scales.at[scale_at].set(scale))
    return pool.at[at].set(x.astype(pool.dtype))


def _cache_write(cache, chunk, li, pos, rolling: bool = False):
    """Insert a [B, t, H, Dh] K or V chunk at position ``pos`` of layer
    ``li`` of the STACKED cache ([L, B, KV, M, Dh]), quantizing on the
    way in when the cache is int8 (the same per-row absmax rule as
    weight quantization — ops/quant.py).  The cache is a layer-scan
    CARRY and every path below is an indexed in-place update on the full
    buffer — one slot's traffic per step, never a buffer restack.

    ``pos`` may be a [B] vector (ragged serving: each row writes at its
    own position — a vmapped per-row dynamic slice; non-rolling caches
    only).

    ``rolling`` (window configs): position p writes slot p mod M — a
    single-token step is one wrapped dynamic slice; a longer chunk
    (prefill, static ``pos``) keeps its last M tokens via a modular
    scatter.  Non-rolling caches keep the plain dynamic-slice write (which
    supports traced multi-token positions — the buffer never wraps).
    """
    m = (cache.values if isinstance(cache, QTensor) else cache).shape[3]
    t = chunk.shape[1]
    ragged = getattr(pos, "ndim", 0) == 1
    if ragged and rolling:
        raise ValueError("ragged positions do not compose with rolling "
                         "(windowed) caches")

    def _put(buf, x, axis):
        """Write ``x`` (shaped like ``buf[li]``, t positions on ``axis``
        of the full buffer) at ``pos`` of layer ``li`` — values and
        scales share every branch; only the position axis differs (3 for
        [L, B, KV, M, Dh'] values, 4 for [L, B, KV, 1, M] scales)."""
        def start(p, rank, ax):
            s = [0] * rank
            s[0], s[ax] = li, p
            return tuple(s)

        if ragged:
            # Per row b: buf[:, b] gets its row's chunk at its own
            # position (the batch dim drops, shifting the axis by one).
            return jax.vmap(
                lambda b_, x_, p_: jax.lax.dynamic_update_slice(
                    b_, x_[None], start(p_, b_.ndim, axis - 1)),
                in_axes=(1, 0, 0), out_axes=1)(buf, x, pos)
        if not rolling:
            return jax.lax.dynamic_update_slice(
                buf, x[None], start(pos, buf.ndim, axis))
        if t == 1:
            return jax.lax.dynamic_update_slice(
                buf, x[None], start(pos % m, buf.ndim, axis))
        if not isinstance(pos, int):
            raise ValueError("multi-token rolling-cache writes need a "
                             "static position (prefill); decode rolls one "
                             "token at a time")
        if pos + t <= m:
            return jax.lax.dynamic_update_slice(
                buf, x[None], start(pos, buf.ndim, axis))
        # Wrapping prefill (one-time): modular scatter on the layer slice,
        # written back whole — chunk-sized work at a static position.
        keep = jax.lax.slice_in_dim(x, max(0, t - m), t, axis=axis - 1)
        idx = (jnp.arange(pos + t - keep.shape[axis - 1], pos + t)) % m
        lay = jax.lax.dynamic_index_in_dim(buf, li, 0, keepdims=False)
        lay = lay.at[(slice(None),) * (axis - 1) + (idx,)].set(keep)
        return jax.lax.dynamic_update_slice(
            buf, lay[None], start(0, buf.ndim, axis))

    def put(buf, x):
        # x [B, t, KV, Dh'] -> head-major [B, KV, t, Dh'] (a chunk-sized
        # transpose; the cache itself is already head-major).
        return _put(buf, x.transpose(0, 2, 1, 3).astype(buf.dtype), 3)

    def put_scales(buf, s):
        # s [B, t, KV, 1] -> lane-major [B, KV, 1, t] (positions on the
        # trailing dim, matching the [L, B, KV, 1, M] scales buffer).
        return _put(buf, s.transpose(0, 2, 3, 1), 4)

    if isinstance(cache, QTensor):
        from tfmesos_tpu.ops.quant import quantize_int8_reference
        vals, scale = quantize_int8_reference(chunk)
        return QTensor(put(cache.values, vals),
                       put_scales(cache.scales, scale))
    return put(cache, chunk)


def _cache_read(cache, li, dtype):
    """The [B, KV, M, Dh] view of layer ``li`` that einsum attention
    consumes; int8 caches dequantize here (the convert+scale fuses into
    the einsum, so HBM streams int8); fp caches pass through at their own
    dtype (a caller-widened fp32 cache keeps fp32 attention math, as
    before).  Kernel paths never call this — they read the stacked
    buffer directly at the layer index."""
    from tfmesos_tpu.ops.attention import _dequant_lane_major

    take = lambda a: jax.lax.dynamic_index_in_dim(a, li, 0, keepdims=False)
    if isinstance(cache, QTensor):
        return _dequant_lane_major(
            QTensor(take(cache.values), take(cache.scales)), dtype)
    return take(cache)


def cache_specs(cfg: TransformerConfig, mesh: Mesh,
                quantized: bool = False) -> Dict[str, Any]:
    """PartitionSpecs for the KV cache ([L, B, KV, M, Dh]): batch over the
    data axes, heads over tp — the decode analogue of ``partition_specs``.
    Place the cache (and params) with these and jit
    ``decode_step(..., sharded=True)``: every op is then a plain einsum,
    so GSPMD inserts the tp collectives — no manual decode variant
    needed.  With GQA the cache's head axis is ``kv_heads``, so tp must
    divide it.  ``quantized=True`` mirrors an int8 ``init_cache``: each
    leaf becomes a QTensor of specs (the lane-major scales
    [L, B, KV, 1, M] shard on the same leading dims)."""
    from tfmesos_tpu.parallel.sharding import data_axes
    tp = mesh.shape.get("tp", 1)
    if tp > 1 and cfg.kv_heads % tp:
        raise ValueError(
            f"cache_specs: tp ({tp}) must divide kv_heads "
            f"({cfg.kv_heads}) to shard the KV cache's head axis")
    spec = _filter_spec(P(None, data_axes(mesh), "tp", None, None), mesh)
    if quantized:
        spec = QTensor(values=spec, scales=spec)
    return {"k": spec, "v": spec}


def paged_cache_specs(cfg: TransformerConfig, mesh: Mesh,
                      quantized: bool = False) -> Dict[str, Any]:
    """PartitionSpecs for a PAGED pool ([L, P, KV, page, Dh]): the PAGE
    axis over the data axes — each data shard owns a sub-pool that its
    rows' page tables index with shard-LOCAL ids (serving's allocator
    maintains that invariant) — and kv heads over tp.  Place the pool
    (and params per ``partition_specs``) with these and jit
    ``decode_step(..., sharded=True, mesh=mesh)``: the page
    gather/scatter then runs per shard inside a shard_map island
    (``_sharded_paged_step``) while everything around it stays plain
    GSPMD einsums.  ``quantized=True`` mirrors an int8
    ``init_paged_cache`` (lane-major scales share the values' spec)."""
    from tfmesos_tpu.parallel.sharding import data_axes
    tp = mesh.shape.get("tp", 1)
    if tp > 1 and (cfg.kv_heads % tp or cfg.n_heads % tp):
        raise ValueError(
            f"paged_cache_specs: tp ({tp}) must divide kv_heads "
            f"({cfg.kv_heads}) and n_heads ({cfg.n_heads}) to shard the "
            f"pool's head axis")
    spec = _filter_spec(P(None, data_axes(mesh), "tp", None, None), mesh)
    if quantized:
        # Lane-major scales [L, P, KV, 1, page]: same sharded dims, and
        # the trailing entries are already None.
        spec = QTensor(values=spec, scales=spec)
    return {"k": spec, "v": spec}


def _check_sharded_paged(cfg: TransformerConfig, mesh: Optional[Mesh],
                         batch: int, n_pages: int):
    """Validate a sharded paged decode call; returns (data_axes_prod, tp)."""
    if mesh is None:
        raise ValueError(
            "sharded paged decode needs the mesh: place the pool per "
            "paged_cache_specs and pass decode_step(..., sharded=True, "
            "mesh=mesh)")
    real = {a for a, s in mesh.shape.items() if s > 1}
    if not real <= {"dp", "fsdp", "tp"}:
        raise ValueError(
            f"sharded paged decode runs on data (dp/fsdp) x tp meshes; "
            f"got axes {sorted(real)}")
    nd = 1
    for a in ("dp", "fsdp"):
        nd *= mesh.shape.get(a, 1)
    tp = mesh.shape.get("tp", 1)
    if cfg.kv_heads % tp or cfg.n_heads % tp:
        raise ValueError(
            f"tp ({tp}) must divide kv_heads ({cfg.kv_heads}) and "
            f"n_heads ({cfg.n_heads})")
    if batch % nd:
        raise ValueError(
            f"batch ({batch}) must divide over the data axes ({nd})")
    if n_pages % nd:
        raise ValueError(
            f"pool pages ({n_pages}) must divide over the data axes "
            f"({nd}) — each shard owns an equal sub-pool")
    return nd, tp


def _sharded_paged_step(cfg: TransformerConfig, mesh: Mesh, q, k, v, ck,
                        cv, li, pages, positions, attend: bool = True):
    """Paged write + paged attention as ONE shard_map island over the
    ``paged_cache_specs`` layout ([L, P, KV, page, Dh] pools, carried
    whole with ``li`` the layer index — writes scatter in place at the
    index and the kernel reads through its scalar prefetch, exactly as
    on the single-host path).  Each data shard owns a sub-pool whose
    pages its rows' table entries index LOCALLY, so the gather/scatter
    indirection never crosses shards; heads shard over tp with GQA
    grouping preserved per shard (tp divides both head counts).  No
    collective runs inside — the tp output reduction stays with GSPMD
    at the surrounding wo matmul.  ``attend=False`` (prefill from an
    empty cache: the chunk attends only to itself) writes the pages and
    lets the caller compute self-attention as a plain partitionable
    einsum."""
    from tfmesos_tpu.parallel.sharding import data_axes

    da = data_axes(mesh)
    qkv = _filter_spec(P(da, None, "tp", None), mesh)
    pool = _filter_spec(P(None, da, "tp", None, None), mesh)
    if isinstance(ck, QTensor):
        pool = QTensor(values=pool, scales=pool)
    tbl = _filter_spec(P(da, None), mesh)
    li = jnp.asarray(li, jnp.int32)

    def write(ck, cv, k, v, li, pages, posv):
        ck = _paged_cache_write(ck, k, li, pages, posv)
        cv = _paged_cache_write(cv, v, li, pages, posv)
        return ck, cv

    if not attend:
        def local(q, k, v, ck, cv, li, pages, positions):
            ck, cv = write(ck, cv, k, v, li, pages, positions[:, 0])
            return ck, cv

        fn = shard_map(local, mesh=mesh,
                       in_specs=(qkv, qkv, qkv, pool, pool, P(), tbl, tbl),
                       out_specs=(pool, pool), check_vma=False)
        ck, cv = fn(q, k, v, ck, cv, li, pages, positions)
        return None, ck, cv

    t = q.shape[1]
    m = _cache_logical_len(ck, pages)
    kernel_kw = _decode_kernel_kwargs(cfg, m, t, False)

    def local(q, k, v, ck, cv, li, pages, positions):
        posv = positions[:, 0]
        ck, cv = write(ck, cv, k, v, li, pages, posv)
        from tfmesos_tpu.ops.attention import (_paged_decode_reference,
                                               flash_decode_paged)
        if kernel_kw is not None:
            o = flash_decode_paged(q, ck, cv, pages, posv, layer=li,
                                   **kernel_kw)
        else:
            o = _paged_decode_reference(q, ck, cv, pages, posv,
                                        1.0 / math.sqrt(cfg.head_dim),
                                        layer=li)
        return o, ck, cv

    fn = shard_map(local, mesh=mesh,
                   in_specs=(qkv, qkv, qkv, pool, pool, P(), tbl, tbl),
                   out_specs=(qkv, pool, pool), check_vma=False)
    return fn(q, k, v, ck, cv, li, pages, positions)


def _decode_kernel_kwargs(cfg: TransformerConfig, m: int, t: int,
                          sharded: bool, mesh: Optional[Mesh] = None,
                          batch: Optional[int] = None):
    """kwargs for ``flash_decode`` when the cache-bounded kernel applies,
    else None — single tokens (t=1) and short chunks (speculative verify
    / chunked prefill; capped so the resident [t·g, block] score rows
    stay kernel-shaped).  TPU only; fp or int8 QTensor caches (the kernel
    folds the int8 scales into the score rows); full buffers
    (rolling-window caches address by slot); m large enough that the
    O(pos) HBM bound beats the kernel's fixed cost.

    Sharded decode: a pallas_call cannot be GSPMD-partitioned, but with
    an explicit ``mesh`` whose axes are data + tp (the ``cache_specs``
    layout) the kernel runs per shard under a shard_map
    (``sharded_flash_decode``); other meshes keep the einsum."""
    if (t > 64 or cfg.attn_window is not None or m < 512
            or jax.default_backend() != "tpu"):
        return None
    if not sharded:
        return {}
    return {} if _shard_map_mesh_ok(cfg, mesh, batch) else None


def _cache_logical_len(cache_leaf, pages=None) -> int:
    """Logical attended length of a stacked cache leaf: slots of a
    [L, B, KV, M, Dh] linear buffer, or table-width x page for a
    [L, P, KV, page, Dh] pool (the position axis is 3 in both layouts —
    ONE place that knows it)."""
    buf = cache_leaf.values if isinstance(cache_leaf, QTensor) else \
        cache_leaf
    return pages.shape[1] * buf.shape[3] if pages is not None \
        else buf.shape[3]


def _shard_map_mesh_ok(cfg: TransformerConfig, mesh: Optional[Mesh],
                       batch: Optional[int],
                       need_n_heads_div: bool = False) -> bool:
    """Whether a per-shard kernel (shard_map over the ``cache_specs`` /
    ``paged_cache_specs`` layout) is eligible on this mesh: real axes
    within data (dp/fsdp) + tp, the batch dividing over the data axes
    (the GSPMD einsum has no such constraint, so indivisible batches
    fall back), and tp dividing kv_heads (plus n_heads when the caller
    shards full-width q heads).  ONE definition of the eligibility rule
    — the decode and prefill kernel gates both call it."""
    if mesh is None:
        return False
    real = {a for a, s in mesh.shape.items() if s > 1}
    tp = mesh.shape.get("tp", 1)
    nd = 1
    for a in ("dp", "fsdp"):
        nd *= mesh.shape.get(a, 1)
    if batch is not None and batch % nd:
        return False
    if need_n_heads_div and cfg.n_heads % tp:
        return False
    return real <= {"dp", "fsdp", "tp"} and cfg.kv_heads % tp == 0


def _prefill_kernel_kwargs(cfg: TransformerConfig, mesh: Optional[Mesh],
                           batch: int, t: int):
    """kwargs for ``sharded_flash_attention`` on the SHARDED prefill path,
    else None (keep the GSPMD ``mha_reference`` einsum).  The prefill
    chunk attends only to itself, so the training flash kernel applies —
    a pallas_call cannot be GSPMD-partitioned, but on the data + tp
    meshes of the ``cache_specs``/``paged_cache_specs`` layouts it runs
    per shard under a shard_map, skipping the einsum's O(t^2)
    materialized score tensor.  Shape/mesh gates run BEFORE the backend
    check so they stay testable off-TPU; t must tile (multiple of 8)
    and be big enough to beat the einsum's fixed cost.  Monkeypatch
    point for CPU tests (interpret mode)."""
    if t % 8 or t < 128:
        return None
    if not _shard_map_mesh_ok(cfg, mesh, batch, need_n_heads_div=True):
        return None
    if jax.default_backend() != "tpu":
        return None
    return {}


def _residual(cfg: TransformerConfig, x, y):
    """``x + y`` in the residual stream's dtype, ``y`` scaled by the
    configuration's residual multiplier where it states one."""
    y = y.astype(x.dtype)
    if cfg.residual_scale is not None:
        y = y * jnp.asarray(cfg.residual_scale, x.dtype)
    return x + y


def _rope_kwargs(cfg: TransformerConfig, kind: str = "attention"):
    """What ``rope`` takes for an attention layer of ``kind``: the kind's
    :class:`RopeSpec` where the configuration states one, else plain rope
    at ``rope_theta`` over every channel."""
    spec = cfg.window_rope if kind == "window" else cfg.attn_rope
    if spec is None:
        return {"theta": cfg.rope_theta}
    return spec.kwargs(cfg.head_dim)


def _split_heads(y, heads: int):
    """A projection's product ``y`` [B, t, heads * Dh] as [B, t, heads, Dh].

    The product stands as a matrix first (the barrier).  Left to itself the
    TPU compiler folds the split into the dot wherever an elementwise op, a
    slice or a pad reads the heads (rope's half-heads, a gate's sigmoid, the
    kernels' padded query groups): the dot then wants its weight as [heads,
    Dh, in], which is no view of the stored [in, out], and every run of the
    program cuts a layer of the weight out of its stack and transposes it
    (``wq`` and ``wk``: 1.0 ms of a 12.6 ms Mistral-7B decode block on the
    v5e, PR 44).  With it the matmul reads the parameter where it lies and
    the split costs a pass over the activations."""
    b, t, n = y.shape
    return jax.lax.optimization_barrier(y).reshape(b, t, heads, n // heads)


def _project_qkv(cfg: TransformerConfig, h, lp, positions,
                 kind: str = "attention"):
    """q [B, t, heads of ``kind``, Dh], k [B, t, KV of ``kind``, Dh] and v
    [B, t, KV, Dv] of the normed input ``h``, q and k under the kind's rope
    (``cfg.rope``), v times ``attn_value_scale`` where one is stated."""
    kv = cfg.kind_kv_heads(kind)
    q = _split_heads(_qmm(h, lp["wq"], cfg.dtype), cfg.kind_heads(kind))
    k = _split_heads(_qmm(h, lp["wk"], cfg.dtype), kv)
    v = _split_heads(_qmm(h, lp["wv"], cfg.dtype), kv)
    if cfg.attn_value_scale is not None:
        v = v * jnp.asarray(cfg.attn_value_scale, v.dtype)
    if cfg.rope:
        rkw = _rope_kwargs(cfg, kind)
        q = rope(q, positions, **rkw)
        k = rope(k, positions, **rkw)
    return q, k, v


def _attn_gated(cfg: TransformerConfig, o, h, lp):
    """``attn_gate``: the attention's output ``o`` [B, t, heads * head_dim]
    times ``sigmoid(h W_g)``, elementwise or (``"head"``) one gate a head."""
    gate = jax.nn.sigmoid(_qmm(h, lp["wg"], cfg.dtype))
    if cfg.attn_gate == "head":
        b, t, _ = o.shape
        return (o.reshape(b, t, -1, cfg.v_head_dim)
                * gate[..., None]).reshape(b, t, -1)
    return o * gate


def _packed_keys(cfg: TransformerConfig, k, kind: str = "attention"):
    """A chunk's keys [B, t, KV, Dh] as the kind's K cache holds a position
    ([B, t, KV / f, f * Dh], ``cfg.k_pack``): the same values as they lie."""
    f = cfg.k_pack(kind)
    if f == 1:
        return k
    b, t, kv, dh = k.shape
    return k.reshape(b, t, kv // f, f * dh)


def _block_decode(cfg: TransformerConfig, x, lp, ck, cv, li, positions,
                  pos, sharded: bool = False, mesh: Optional[Mesh] = None,
                  pages=None, kpos=None):
    """One block over a token chunk with cached history: the attention
    half (:func:`_attend_decode`, which also owns the cache) and the MLP,
    each under its ``named_scope`` so a profile names the parts."""
    with jax.named_scope("attention"):
        x, ck, cv, chunk = _attend_decode(cfg, x, lp, ck, cv, li, positions,
                                          pos, sharded, mesh, pages, kpos)
    with jax.named_scope("mlp"):
        h = _norm(cfg, x, lp["mlp_norm"])
        ffn, _ = _ffn(cfg, None, lp, h)
        return _residual(cfg, x, ffn), ck, cv, chunk


def _attend_decode(cfg: TransformerConfig, x, lp, ck, cv, li, positions,
                   pos, sharded: bool, mesh: Optional[Mesh], pages,
                   kpos=None):
    """The attention half of a block over a token chunk with cached
    history; returns ``(x, ck, cv, deferred chunk or None)``.

    ``x``: [B, t, d] (t = chunk length; 1 in steady-state decode);
    ``ck``/``cv``: the STACKED cache ([L, B, KV, M, Dh], or the paged
    pool [L, P, KV, page, Dh]) carried through the layer scan, with
    ``li`` this block's layer index — writes update one slot in place at
    the index and the kernels read O(pos) at the index through their
    scalar prefetch, so the full buffer is never restacked or sliced
    (the single-host paged pool is not written here at all: its chunk
    comes back deferred and ``decode_step`` commits every layer's at once,
    in the pool's own layout — ``_paged_cache_write_all``);
    ``positions``: [B, t] per-row global positions of the chunk (rows
    differ in the ragged case); ``pos``: first chunk position — scalar
    (python int or traced) or [B] vector, as handed to ``_cache_write``.
    A multi-token prefill from an empty cache attends chunk-to-chunk (flash
    kernel when ``sharded=False``; a plain einsum when ``sharded=True`` so
    GSPMD can partition it — a pallas_call under sharded jit cannot be).
    Steady-state (t=1) queries take the flash-decode kernel when
    ``_decode_kernel_kwargs`` opens the gate — directly, or per shard via
    ``sharded_flash_decode`` when a mesh is given — and otherwise fall to
    the dense einsum over the cache with an offset causal mask.

    ``kpos`` ([B], EVA only): the cache ENTRY at which each row's chunk
    starts (``cfg.cache_entries`` of its position): what the paged kernel
    bounds its reads by, while ``positions`` keep feeding RoPE.  A
    multi-token EVA chunk lies inside one window and attends the summaries
    in front of it together with itself (``eva_prefill_attention``).
    """
    b, t, _ = x.shape
    m = _cache_logical_len(ck, pages)
    h = _norm(cfg, x, lp["attn_norm"])
    q, k, v = _project_qkv(cfg, h, lp, positions)
    # a stated softmax scale rides to whichever attention runs below
    skw = {} if cfg.attn_scale is None else {"scale": cfg.attn_scale}
    rolling = cfg.attn_window is not None
    self_attn_prefill = t > 1 and isinstance(pos, int) and pos == 0
    o_paged = None
    # Single-host paged steps DEFER their pool commit: the per-layer
    # write-then-attend order would spend 2L scatters per step, each
    # with its own launch.  Instead the chunk rides into attention as a
    # SELF operand (kernel: a [head_block, t, d] block accumulated at
    # the last page step, causal across the chunk's own tokens;
    # reference: written into the gathered view) and decode_step
    # commits ALL layers' chunks in one scatter per pool leaf after the
    # scan (_paged_cache_write_all: in the pool's own layout, 0.35 ms
    # per leaf at 32 rows on the v5e).  t > 1 is the
    # fused multi-row step (speculative verify / chunked-prefill
    # tails): t rows retire through ONE attention launch per layer and
    # one commit pair per dispatch, instead of per-layer write-then-
    # attend scatters.
    defer = pages is not None and not sharded
    # the chunk's keys as the pool holds a position's (``k_pack``)
    kp = _packed_keys(cfg, k) if defer else k
    if pages is not None and sharded:
        # Multi-chip serving: write + paged attention per shard (the page
        # indirection cannot be GSPMD-partitioned; everything around it
        # stays plain einsums).  Prefill-from-empty writes in the island
        # and attends chunk-to-chunk outside it.
        with jax.named_scope("paged_attention"):
            o_paged, ck, cv = _sharded_paged_step(
                cfg, mesh, q, k, v, ck, cv, li, pages, positions,
                attend=not self_attn_prefill)
    elif pages is not None:
        pass    # single-host paged: deferred — decode_step commits
    else:
        with jax.named_scope("cache_write"):
            ck = _cache_write(ck, k, li, pos, rolling=rolling)
            cv = _cache_write(cv, v, li, pos, rolling=rolling)
    kv = cfg.kv_heads
    g = cfg.n_heads // kv
    if cfg.attention == "eva" and t > 1:
        from tfmesos_tpu.ops.attention import eva_prefill_attention
        with jax.named_scope("eva_prefill_attention"):
            o = eva_prefill_attention(q, k, v, ck, cv, li, pages, kpos)
        # A window's chunk is committed here, layer by layer, in the pool's
        # own layout (whole pages at the window's first entry): stacked
        # over the layers for one commit after the scan, 2048 positions of
        # 32 K/V heads would stand beside the pool as half a gigabyte.
        with jax.named_scope("paged_cache_write"):
            ck, cv = (_paged_cache_write_all(c, x[None], pages, kpos[0],
                                             aligned=True, layer0=li)
                      for c, x in ((ck, k), (cv, v)))
        defer = False
    elif t > 1 and isinstance(pos, int) and pos == 0:
        # Prefill from an empty cache: the chunk only attends to itself —
        # [t, t] instead of a [t, M] score tensor over the (mostly empty)
        # cache.  GQA stays at kv width (both impls group internally).
        if sharded:
            pkw = _prefill_kernel_kwargs(cfg, mesh, b, t)
            if pkw is not None:
                # data x tp mesh: the flash kernel per shard (shard_map)
                # instead of the einsum's O(t^2) materialized scores.
                from tfmesos_tpu.ops.attention import \
                    sharded_flash_attention
                o = sharded_flash_attention(q, k, v, mesh, causal=True,
                                            window=cfg.attn_window, **pkw)
            else:
                o = mha_reference(q, k, v, causal=True,
                                  window=cfg.attn_window)
        else:
            o = attend(q, k, v, mesh=None, causal=True,
                       window=cfg.attn_window, forward_only=True, **skw)
    elif o_paged is not None:
        o = o_paged
    elif pages is not None:
        # Paged attention: pool-page indirection through the kernel's
        # scalar-prefetched index maps (TPU), or the gather-the-pages
        # reference elsewhere.  Single-host path (the pool gather does
        # not GSPMD-partition).
        from tfmesos_tpu.ops.attention import (_paged_decode_reference,
                                               flash_decode_paged)
        self_kv = None
        if defer:
            # int8 pools: quantize-dequantize the chunk so the self
            # operand matches a committed slot up to rounding — the
            # kernel folds a committed slot's fp32 scale into the
            # probability row post-dot, while the self operand rides in
            # pre-multiplied, so the two orderings can differ in the
            # last float ulp even though the int8 values and scales are
            # identical.
            if isinstance(ck, QTensor):
                from tfmesos_tpu.ops.quant import quantize_int8_reference
                rq = lambda c: (lambda v_, s_: (v_.astype(cfg.dtype)
                                                * s_.astype(cfg.dtype)))(
                    *quantize_int8_reference(c))
                self_kv = (rq(k), rq(v))
            else:
                self_kv = (kp, v)
        kw = _decode_kernel_kwargs(cfg, m, t, False)
        at = positions[:, 0] if kpos is None else kpos
        with jax.named_scope("paged_attention"):
            if kw is not None:
                o = flash_decode_paged(q, ck, cv, pages, at, layer=li,
                                       self_kv=self_kv, **kw, **skw)
            else:
                o = _paged_decode_reference(
                    q, ck, cv, pages, at,
                    skw.get("scale", 1.0 / math.sqrt(cfg.head_dim)),
                    layer=li, self_kv=self_kv)
    elif (kernel_kw := _decode_kernel_kwargs(cfg, m, t, sharded, mesh,
                                             batch=b)) is not None:
        # Cache-bounded flash-decode kernel (t=1 steps and short chunks —
        # speculative verify / chunked prefill): scalar-prefetched block
        # bound caps per-step HBM traffic at O(pos) cache slots instead of
        # the full buffer, independently per row
        # (ops/attention.flash_decode).  Under sharded decode with an
        # explicit mesh it runs per shard via shard_map (batch + kv-major
        # tp head blocks).
        if sharded:
            from tfmesos_tpu.ops.attention import sharded_flash_decode
            o = sharded_flash_decode(q, ck, cv, positions[:, 0], mesh,
                                     layer=li, **kernel_kw)
        else:
            from tfmesos_tpu.ops.attention import flash_decode
            o = flash_decode(q, ck, cv, positions[:, 0], layer=li,
                             **kernel_kw)
    else:
        # Grouped einsum over this layer's cache slice: the KV blocks
        # stream from HBM once at kv_heads width (int8 when quantized) —
        # never materialized at n_heads.
        ck_r = _cache_read(ck, li, cfg.dtype)
        cv_r = _cache_read(cv, li, cfg.dtype)
        q5 = q.reshape(b, t, kv, g, cfg.head_dim)
        s = jnp.einsum("btkgd,bkmd->bkgtm", q5, ck_r).astype(jnp.float32)
        s = s / math.sqrt(cfg.head_dim)
        if cfg.attn_window is not None:
            # Rolling cache: slot j holds global position p - ((p - j) % M)
            # (the latest position congruent to j not after p).  Negative
            # slot positions are not yet written; everything resident is
            # within the window when M == window.
            if t > 1:
                raise ValueError("chunked decode over a rolling windowed "
                                 "cache is not supported; decode one token "
                                 "per step after the prefill")
            p0 = positions[0, 0]    # rolling caches are never ragged
            slot = jax.lax.broadcasted_iota(jnp.int32, (t, m), 1)
            spos = p0 - ((p0 - slot) % m)
            bad = (spos < 0) | (spos < p0 - (cfg.attn_window - 1))
            bad = bad[None]
        else:
            kpos = jax.lax.broadcasted_iota(jnp.int32, (t, m), 1)
            bad = kpos[None] > positions[:, :, None]    # [b, t, m]
        s = jnp.where(bad[:, None, None], -jnp.inf, s)
        probs = jax.nn.softmax(s, axis=-1).astype(cv_r.dtype)
        o = jnp.einsum("bkgtm,bkmd->btkgd", probs, cv_r)
    o = o.reshape(b, t, -1)
    if cfg.attn_gate:
        with jax.named_scope("attention.gate"):
            o = _attn_gated(cfg, o, h, lp)
    x = _residual(cfg, x, _qmm(o, lp["wo"], cfg.dtype))
    return x, ck, cv, ((kp, v) if defer else None)


def _embed_chunk(cfg: TransformerConfig, params, tokens, pos):
    """A token chunk into the residual stream, and its positions: ``(x [B,
    t, d], positions [B, t], ragged)``; ``pos`` is the chunk's first
    position (scalar, or [B] for a ragged batch)."""
    b, t = tokens.shape
    with jax.named_scope("embed"):
        x = _embed_lookup(params["embed"], tokens, cfg.dtype)
        if cfg.embed_scale is not None:
            x = x * jnp.asarray(cfg.embed_scale, cfg.dtype)
        if cfg.residual_dtype is not None:
            x = x.astype(cfg.residual_dtype)
    ragged = getattr(pos, "ndim", 0) == 1
    offs = jnp.arange(t, dtype=jnp.int32)
    pos_arr = jnp.asarray(pos, jnp.int32)
    positions = jnp.broadcast_to(
        (pos_arr[:, None] if ragged else pos_arr) + offs, (b, t))
    return x, positions, ragged


def _final_logits(cfg: TransformerConfig, params, x):
    """The last norm and the head (tied or not), divided by the
    configuration's ``logits_scale`` where it states one."""
    with jax.named_scope("lm_head"):
        x = _norm(cfg, x, params["norm_f"])
        logits = _head_logits(cfg, x, params.get("head"),
                              embed=params["embed"])
        if cfg.logits_scale is not None:
            logits = logits / jnp.asarray(cfg.logits_scale, logits.dtype)
    return logits


def decode_step(cfg: TransformerConfig, params, cache, tokens, pos,
                sharded: bool = False, mesh: Optional[Mesh] = None):
    """Advance decoding by a token chunk.

    ``tokens``: [B, t] (the prompt at prefill, one token per step after);
    ``pos``: first global position of the chunk (python int or traced), or
    a [B] int32 vector for RAGGED batches — each row decodes at its own
    position (mixed-length serving: cache writes, attention bounds, and
    rope all follow the per-row position; not with windowed configs).
    Returns (logits [B, t, V], updated cache).

    For multi-chip decode, pass ``sharded=True``, place the params per
    ``partition_specs`` and the cache per ``cache_specs``, and jit: every
    op is then a plain einsum GSPMD can partition (batch over the data
    axes, heads over tp).  ``sharded=False`` (the ``generate`` path) may
    use the Pallas flash kernel for the prefill chunk instead.  sp and pp
    are training-side axes with no decode analogue here.

    Passing the ``mesh`` alongside ``sharded=True`` additionally lets
    single-token steps AND short chunks (speculative verify / chunked
    prefill) run the flash-decode kernel per shard (shard_map over the
    ``cache_specs`` layout: batch axes + tp head blocks) — O(pos)-bounded
    cache reads on every chip; without a mesh, or when the batch does not
    divide over the data axes, the sharded path keeps the plain einsum.

    Exactness contract: dense and dense-MoE configs reproduce ``forward()``
    logits position by position to numerical tolerance (the two paths use
    different attention accumulation orders).  Capacity-based switch MoE
    routes per chunk (tokens only compete within one ``decode_step`` call),
    so decode matches the training-time forward only up to capacity
    overflow — exact whenever nothing overflows, which per-token steps
    (n = B tokens) essentially never do.  That is the standard trade:
    dropping tokens by batch-order competition at inference would be worse
    than the mismatch.
    """
    if cfg.layer_types is not None:
        if sharded or mesh is not None:
            raise ValueError("a typed stack (layer_types) decodes on a "
                             "single host")
        return _typed_decode_step(cfg, params, cache, tokens, pos)
    b, t = tokens.shape
    x, positions, ragged = _embed_chunk(cfg, params, tokens, pos)
    if ragged and cfg.window is not None:
        raise ValueError("ragged positions do not compose with "
                         "sliding-window (rolling-cache) configs")
    pos_arr = jnp.asarray(pos, jnp.int32)

    pages = cache.get("pages")
    kpos = None
    if cfg.attention == "eva":
        # The cache holds entries, not positions: a row's chunk is read up
        # to, and committed at, the entry of its first position.  A
        # multi-token chunk lies inside one window (its entries are as
        # consecutive as its positions).
        if pages is None or sharded:
            raise ValueError("attention='eva' decodes through a single-"
                             "host paged cache (init_paged_cache)")
        if t > 1 and ragged:
            raise ValueError("an EVA chunk of several tokens starts at one "
                             "position for every row")
        kpos = jnp.broadcast_to(cfg.cache_entries(pos_arr), (b,))
    if pages is not None and sharded:
        # Multi-chip paged serving: pool placed per paged_cache_specs
        # (pages over the data axes with shard-local table ids, kv heads
        # over tp); validated once here, executed per layer as a
        # shard_map island (_sharded_paged_step).
        n_pool = (cache["k"].values if isinstance(cache["k"], QTensor)
                  else cache["k"]).shape[1]
        _check_sharded_paged(cfg, mesh, b, n_pool)

    # The cache is the scan CARRY, not xs/ys: each layer writes its token
    # slot in place at its index and the attention kernels read O(pos) at
    # the index.  Scanning the cache through xs/ys instead would restack
    # the ENTIRE [L, ...] buffer every step — ~2 GB of HBM traffic per
    # token at max_len=16k, an order of magnitude over the einsum's own
    # read cost (measured round 5).  The weights are no xs either: the scan
    # runs over the layer index alone and the body takes its layer of every
    # stacked leaf, so each matmul reads its weight where the stack lies,
    # rolled or unrolled.  As xs of a scan unrolled by two, XLA views a
    # stacked weight as [L/2, 2, ...] and writes an iteration's two layers
    # out: the whole model copied once a run, 18 ms of a 37 ms Mistral
    # decode block at 128 pages a row (v5e, PR 47).
    layers = params["layers"]

    def body(carry, li):
        x, ck, cv = carry
        lp = jax.tree_util.tree_map(
            lambda a: jax.lax.dynamic_index_in_dim(a, li, 0, keepdims=False),
            layers)
        x, ck, cv, chunks = _block_decode(cfg, x, lp, ck, cv, li,
                                          positions, pos, sharded=sharded,
                                          mesh=mesh, pages=pages, kpos=kpos)
        return (x, ck, cv), chunks

    # The un-paged linear buffer of 8,192 slots or more keeps its 2-wide
    # unroll (round 5: cross-layer DMA overlap, 1759 -> 2497 tok/s at
    # max_len=16k, while short buffers LOST ~6% to it and m=4k was a wash:
    # hence the gate on the buffer's static length).  On the v5e at
    # Mistral's widths, 4 rows, max_len 16,384 (PR 49): 328.5 ms a step
    # against 432.9 rolled with a position a row (the ragged write's passes
    # over the cache overlap), 14.38 against 14.41 with a scalar position.
    # A page pool always takes the rolled loop: with the weights read in
    # place the unroll buys it nothing (32 rows: 18.58 ms rolled against
    # 19.17 two-wide at 128 pages a row, 11.64 against 11.72 at 32; PR 47).
    unroll = 2 if (pages is None
                   and _cache_logical_len(cache["k"]) >= 8192) else 1
    (x, new_k, new_v), chunks = jax.lax.scan(
        body, (x, cache["k"], cache["v"]),
        jnp.arange(cfg.n_layers, dtype=jnp.int32), unroll=unroll)
    if chunks is not None:
        # Deferred single-token paged writes (see _block_decode): commit
        # every layer's chunk in one scatter per pool leaf.
        with jax.named_scope("paged_cache_write"):
            at = pos
            if kpos is not None:    # EVA: the entry of each row's position
                at = kpos if ragged else kpos[0]
            new_k = _paged_cache_write_all(new_k, chunks[0], pages, at)
            new_v = _paged_cache_write_all(new_v, chunks[1], pages, at)
    logits = _final_logits(cfg, params, x)
    out_cache = {"k": new_k, "v": new_v}
    if pages is not None:
        out_cache["pages"] = pages
    return logits, out_cache


def _mamba_mixer(cfg: TransformerConfig, x, lp, state, mi, slots, valid,
                 positions=None):
    """The Mamba-2 mixer of one block over a token chunk; returns ``(x,
    (ssm, conv))`` with layer ``mi`` of the stacked row state ``state =
    (ssm, conv)`` updated.

    ``x``: [B, t, d]; ``ssm`` [Lm, rows, H * P, N] float32 and ``conv`` [Lm,
    rows, K - 1, C]: the state store (``init_row_state``), carried through
    the layer scans.  ``t == 1``: every row is a slot (B == rows): one step
    of the recurrence from the slot's state.  ``t > 1``: a prefill from an
    EMPTY state (whatever the slots held), in chunks of ``mamba_chunk``;
    ``valid`` [B] is each row's number of real positions (the rest is
    bucket padding, which gets ``dt = 0`` and so leaves the state alone; the
    conv tail is taken at the true end) and ``slots`` [B] the slots the
    final states are written to."""
    from tfmesos_tpu.ops.ssm import (causal_conv, conv_tail, ssd_scan,
                                     ssm_update_stacked)
    ssm, conv = state
    b, t, _ = x.shape
    f32 = jnp.float32
    nh, hp, ns = cfg.mamba_heads, cfg.mamba_head_dim, cfg.mamba_state
    di, cd, kc = cfg.mamba_inner, cfg.mamba_conv_dim, cfg.mamba_conv
    h = _norm(cfg, x, lp["attn_norm"])
    proj = _qmm(h, lp["in_proj"], cfg.dtype)
    z, xbc, dt = proj[..., :di], proj[..., di:di + cd], proj[..., di + cd:]
    dt = jax.nn.softplus(dt.astype(f32) + lp["dt_bias"].astype(f32))
    a = -jnp.exp(lp["A_log"].astype(f32))
    if t == 1:
        act, xp = causal_conv(xbc, lp["conv_w"], lp["conv_b"], tail=conv[mi])
        new_tail = xp[:, 1:]
    else:
        live = jnp.arange(t, dtype=jnp.int32)[None] < valid[:, None]
        dt = jnp.where(live[..., None], dt, 0.0)
        act, xp = causal_conv(xbc, lp["conv_w"], lp["conv_b"])
        new_tail = conv_tail(xp, valid, kc)
    act = jax.nn.silu(act).astype(cfg.dtype)
    xs = act[..., :di].reshape(b, t, nh, hp)
    bm, cm = act[..., di:di + ns], act[..., di + ns:]
    if t == 1:
        # one pass over the layer's state, in place in the stacked store (a
        # Pallas kernel on the TPU: ops/ssm.py)
        y, ssm = ssm_update_stacked(ssm, mi, xs[:, 0], dt[:, 0], a,
                                    bm[:, 0], cm[:, 0])
        y = y[:, None]
        conv = conv.at[mi].set(new_tail.astype(conv.dtype))
    else:
        y, new = ssd_scan(xs, dt, a, bm, cm,
                          jnp.zeros((b, nh, hp, ns), f32), cfg.mamba_chunk)
        # (layer, slot) indexed, the window a trailing slab of the store.
        # The store keeps heads and head channels as ONE dim: with them
        # apart the compiler gave the store the layout the scan's last
        # einsum liked (heads and channels swapped) and relayouted all of
        # it around this write, two 2.4 GB copies a prefill; now only the
        # update (4 MB) can be relayouted.
        ssm = ssm.at[mi, slots].set(
            new.reshape(b, nh * hp, ns).astype(ssm.dtype))
        conv = conv.at[mi, slots].set(new_tail.astype(conv.dtype))
    y = y + lp["D"].astype(f32)[:, None] * xs.astype(f32)
    # gated RMSNorm, the gate before the norm, one group over d_inner
    y = y.reshape(b, t, di) * jax.nn.silu(z.astype(f32))
    kw = {} if cfg.norm_eps is None else {"eps": cfg.norm_eps}
    y = rms_norm(y, lp["norm"].astype(f32), **kw).astype(cfg.dtype)
    return _residual(cfg, x, _qmm(y, lp["out_proj"], cfg.dtype)), (ssm, conv)


def _kda_mixer(cfg: TransformerConfig, x, lp, state, ki, slots, valid,
               positions=None):
    """The KDA mixer of one block over a token chunk (``ops/kda.py`` has the
    recurrence); returns ``(x, (s, conv))`` with layer ``ki`` of the stacked
    row state ``state = (s, conv)`` updated.

    ``x``: [B, t, d]; ``s`` [Lk, rows, H * dk, dv] float32 and ``conv`` [Lk,
    rows, K - 1, 3 * H * dk]: the state store (``init_row_state``).  ``t ==
    1``: every row is a slot: one step of the recurrence from the slot's
    state.  ``t > 1``: a prefill from an EMPTY state, in chunks of
    ``kda_chunk``; bucket padding (positions from ``valid`` [B] on) gets
    ``g = 0`` and ``beta = 0`` and so leaves the state alone, the conv tail
    is taken at the true end, and the final states go to ``slots`` [B]."""
    from tfmesos_tpu.ops.kda import (kda_chunk_scan, kda_update_stacked,
                                     l2norm)
    from tfmesos_tpu.ops.ssm import causal_conv, conv_tail
    s, conv = state
    b, t, _ = x.shape
    f32 = jnp.float32
    nh, dk, hk, kc = (cfg.kda_heads, cfg.kda_head_dim, cfg.kda_inner,
                      cfg.kda_conv)
    h = _norm(cfg, x, lp["attn_norm"])
    qkv = _qmm(h, lp["in_proj"], cfg.dtype)
    # the log-decay per head and key channel, and the step per head: float32
    f = _split_heads(
        _qmm(_qmm(h, lp["f_down"], cfg.dtype), lp["f_up"], cfg.dtype), nh)
    g = jax.nn.softplus(
        f.astype(f32) + lp["dt_bias"].astype(f32).reshape(nh, dk)
    ) * -jnp.exp(lp["A_log"].astype(f32))[:, None]
    beta = jax.nn.sigmoid(_qmm(h, lp["b_proj"], cfg.dtype).astype(f32))
    if cfg.kda_neg_eigval:
        beta = 2.0 * beta
    if t == 1:
        act, xp = causal_conv(qkv, lp["conv_w"], None, tail=conv[ki])
        new_tail = xp[:, 1:]
    else:
        live = jnp.arange(t, dtype=jnp.int32)[None] < valid[:, None]
        g = jnp.where(live[..., None, None], g, 0.0)
        beta = jnp.where(live[..., None], beta, 0.0)
        act, xp = causal_conv(qkv, lp["conv_w"], None)
        new_tail = conv_tail(xp, valid, kc)
    act = jax.nn.silu(act).astype(cfg.dtype).reshape(b, t, 3, nh, dk)
    q = l2norm(act[:, :, 0]) * dk ** -0.5
    k, v = l2norm(act[:, :, 1]), act[:, :, 2]
    if t == 1:
        o, s = kda_update_stacked(s, ki, q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                                  beta[:, 0])
        o = o[:, None]
        conv = conv.at[ki].set(new_tail.astype(conv.dtype))
    else:
        o, new = kda_chunk_scan(q, k, v, g, beta,
                                jnp.zeros((b, nh, dk, dk), f32),
                                cfg.kda_chunk)
        # (layer, slot) indexed; heads and key channels ONE dim of the
        # store, as the mamba store's (see _mamba_mixer)
        s = s.at[ki, slots].set(new.reshape(b, hk, dk).astype(s.dtype))
        conv = conv.at[ki, slots].set(new_tail.astype(conv.dtype))
    # RMSNorm per head, then the low-rank sigmoid gate
    kw = {} if cfg.norm_eps is None else {"eps": cfg.norm_eps}
    gate = _split_heads(
        _qmm(_qmm(h, lp["g_down"], cfg.dtype), lp["g_up"], cfg.dtype), nh)
    o = rms_norm(o, lp["norm"].astype(f32), **kw) * jax.nn.sigmoid(
        gate.astype(f32))
    o = o.reshape(b, t, hk).astype(cfg.dtype)
    return _residual(cfg, x, _qmm(o, lp["out_proj"], cfg.dtype)), (s, conv)


def _window_mixer(cfg: TransformerConfig, x, lp, state, wi, slots, valid,
                  positions):
    """A sliding-window attention layer over a token chunk; returns ``(x,
    (ring_k, ring_v))`` with layer ``wi`` of the stacked rings updated.

    ``ring_k`` [Lw, rows, KV / f, W, f * Dh] / ``ring_v`` [Lw, rows, KV, W,
    Dv] (``init_row_state``; KV the window kind's, ``f`` its ``k_pack``): slot
    ``p mod W`` of a row holds position ``p``'s K / V (keys after rope), W =
    ``cfg.window``, so a row that has written position ``t`` holds exactly
    the window's positions ``max(0, t - W + 1) .. t`` in slots ``0 ..
    min(t, W - 1)``: what is VALID is told by the position alone, and a slot
    taken over from another request shows nothing of it (its prompt writes
    the slots it fills; the others lie past ``min(t, W - 1)`` until the
    row's own steps write them).  ``t == 1``: every row is a slot; the
    step writes its position's K / V, then attends the ring through
    ``flash_decode`` bounded at ``min(t, W - 1)`` (softmax does not mind the
    ring's order).  ``t > 1``: a prefill from position 0, windowed flash
    attention over the chunk itself; the last W real positions (``valid``
    [B] each row's; the rest is padding) go to the rings of ``slots``.  With
    ``window_sink`` the layer's ``sink`` [heads] rides into either kernel."""
    from tfmesos_tpu.ops.attention import flash_decode
    rk, rv = state
    b, t, _ = x.shape
    w = cfg.window
    h = _norm(cfg, x, lp["attn_norm"])
    q, k, v = _project_qkv(cfg, h, lp, positions, "window")
    skw = {} if cfg.attn_scale is None else {"scale": cfg.attn_scale}
    if cfg.window_sink:
        skw["sink"] = lp["sink"]
    kp = _packed_keys(cfg, k, "window")     # as the ring holds a slot's keys
    # The writes below are scatters IN THE RINGS' OWN LAYOUT, by the rule of
    # ``_paged_cache_write_all``: every dim in front of the window is
    # indexed and the window is a trailing slab ([Dh] a step, [W, Dh] a
    # prompt), so the compiler scatters into the donated store in place (a
    # per-row dynamic-update-slice under vmap carried the store rows-major
    # through the layer scan and transposed all of it back a layer).
    heads = lambda c: jnp.arange(c.shape[2], dtype=jnp.int32)[None]
    if t == 1:
        pos = positions[:, 0]
        with jax.named_scope("swa.write"):
            at = lambda c: (wi, jnp.arange(b, dtype=jnp.int32)[:, None],
                            heads(c), (pos % w)[:, None])
            rk = rk.at[at(kp)].set(kp[:, 0].astype(rk.dtype))
            rv = rv.at[at(v)].set(v[:, 0].astype(rv.dtype))
        with jax.named_scope("swa.decode"):
            o = flash_decode(q, rk, rv, jnp.minimum(pos, w - 1), layer=wi,
                             **skw)
    else:
        with jax.named_scope("swa.prefill"):
            o = attend(q, k, v, mesh=None, causal=True, window=w,
                       forward_only=True, **skw)
        with jax.named_scope("swa.write"):
            # slot s takes the last real position congruent to s (a prompt
            # shorter than W leaves the slots past its end alone: whatever
            # is gathered for them lies past the bound until overwritten)
            last = valid[:, None] - 1
            src = last - (last - jnp.arange(w, dtype=jnp.int32)[None]) % w
            src = jnp.clip(src, 0, t - 1)[:, :, None, None]

            def put(ring, c):
                keep = jnp.take_along_axis(c, src, axis=1)  # [B, W, KV, Dh]
                return ring.at[wi, slots[:, None], heads(c)].set(
                    keep.transpose(0, 2, 1, 3).astype(ring.dtype))

            rk, rv = put(rk, kp), put(rv, v)
    o = o.reshape(b, t, -1)
    if cfg.attn_gate:
        with jax.named_scope("attention.gate"):
            o = _attn_gated(cfg, o, h, lp)
    return _residual(cfg, x, _qmm(o, lp["wo"], cfg.dtype)), (rk, rv)


#: the leaves of a kind's row state (``init_row_state``), in the order the
#: layer scans carry them, and the kind's mixer
_ROW_STATE = {"mamba": ("ssm", "conv"), "kda": ("kda_s", "kda_conv"),
              "window": ("swa_k", "swa_v")}
_MIXERS = {"mamba": _mamba_mixer, "kda": _kda_mixer,
           "window": _window_mixer}


def _typed_decode_step(cfg: TransformerConfig, params, cache, tokens, pos):
    """``decode_step`` for a typed stack (``layer_types``): a single-host
    paged cache for the attention layers and a row-state store for the
    mamba and kda layers, scanned over the pattern's periods and, inside one,
    over each run of layers of a kind.

    ``cache``: ``k``/``v`` ([attention layers, P, KV, page, Dh]), ``pages``,
    ``state`` (``init_row_state``: each kind's leaves) and, for a prefill
    (t > 1, which starts at position 0 from an empty state), ``slots`` [B]
    (the row slots to fill) and ``valid`` [B] (real positions per row; the
    rest is padding).
    With ``valid`` the logits come back at each row's LAST real position
    only ([B, 1, V]): the head is not run over a prompt.  Returns (logits,
    cache); the cache gains ``expert_counts`` [L, held] int32 where the
    expert layer is the grouped one (assignments per held expert, this
    step)."""
    b, t = tokens.shape
    pages, state = cache.get("pages"), cache.get("state")
    if pages is None or (cfg.keeps_row_state and state is None):
        raise ValueError("a typed stack decodes through a paged cache and, "
                         "with mamba or kda layers, a row state "
                         "(init_paged_cache, init_row_state)")
    if t > 1 and not (isinstance(pos, int) and pos == 0):
        raise ValueError("a typed stack's chunk of several tokens is a "
                         "prefill from position 0")
    slots, valid = cache.get("slots"), cache.get("valid")
    if t > 1 and cfg.keeps_row_state:
        if slots is None:
            raise ValueError("a prefill names the row slots it fills "
                             "(cache['slots'])")
        if valid is None:
            valid = jnp.full((b,), t, jnp.int32)
    x, positions, _ = _embed_chunk(cfg, params, tokens, pos)

    per, runs = cfg.layer_period, cfg.layer_runs
    lead = cfg.n_lead_layers
    n_per = (cfg.n_layers - lead) // per
    per_kind = {kind: sum(r[2] for r in runs if r[0] == kind)
                for kind in LAYER_KINDS}
    grouped = bool(cfg.n_experts) and cfg.moe_impl == "grouped"
    lay = params["layers"]
    # The stacks stay whole and a layer is INDEXED out of them (a slice of
    # a stack, e.g. a run's layers as a scan's xs, is a copy of its
    # weights); the grouped expert kernels take the whole expert stacks
    # and the layer index (``_EXPERT_LEAVES``: no copy of a layer's experts
    # in front of a kernel either).
    common = {k: v for k, v in lay.items()
              if k not in LAYER_KINDS and k != "dense"}
    experts = ({k: common.pop(k) for k in _EXPERT_LEAVES} if grouped
               else {})
    # behind leading dense layers (``ffn_types``) the expert layer's leaves
    # are stacked over the sparse layers only; the norms over every layer
    norms = ({k: common.pop(k) for k in ("attn_norm", "mlp_norm")}
             if lead else {})

    def at(tree, i):
        return jax.tree_util.tree_map(
            lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False),
            tree)

    def one_layer(kind, carry, li, ki, dense=False):
        """Layer ``li`` of the stack, the ``ki``-th of its kind (a pool /
        state layer index); ``dense``: a leading layer, its second half the
        dense feed-forward."""
        x, ck, cv, st = carry
        # its index among the sparse layers (no op where nothing leads)
        si = li - lead if lead else li
        lp = {**at(norms, li),
              **at(lay["dense"] if dense else common, li if dense else si),
              **at(lay[kind], ki)}
        if kind == "attention":
            with jax.named_scope("attention"):
                x, ck, cv, chunk = _attend_decode(
                    cfg, x, lp, ck, cv, ki, positions, pos, False, None,
                    pages)
                # committed here, layer by layer, in the pool's own layout:
                # attention layers are few among the pattern's
                with jax.named_scope("paged_cache_write"):
                    ck = _paged_cache_write_all(ck, chunk[0][None], pages,
                                                pos, layer0=ki)
                    cv = _paged_cache_write_all(cv, chunk[1][None], pages,
                                                pos, layer0=ki)
        else:
            with jax.named_scope(kind):
                x, new = _MIXERS[kind](cfg, x, lp, st[kind], ki, slots,
                                       valid, positions)
                st = {**st, kind: new}
        with jax.named_scope("mlp"):
            h = _norm(cfg, x, lp["mlp_norm"])
            if dense:
                ffn, aux = _mlp(cfg, lp, h), None
            else:
                ffn, aux = _ffn(cfg, None, {**lp, **experts}, h,
                                expert_layer=si if grouped else None)
            x = _residual(cfg, x, ffn)
        counts = (aux["expert_counts"] if grouped and aux is not None
                  else jnp.zeros((0,), jnp.int32))
        return (x, ck, cv, st), counts

    def run_of(carry, pi, run):
        """One run of layers of a kind in period ``pi`` (traced OK)."""
        kind, j0, n, k0 = run
        li0, ki0 = pi * per + j0, pi * per_kind[kind] + k0
        if lead:
            li0, ki0 = li0 + lead, ki0 + lead_kind[kind]
        return jax.lax.scan(
            lambda cr, i: one_layer(kind, cr, li0 + i, ki0 + i),
            carry, jnp.arange(n, dtype=jnp.int32))

    def period(carry, pi):
        counts = []
        for run in runs:
            carry, c = run_of(carry, pi, run)
            counts.append(c)
        return carry, jnp.concatenate(counts, axis=0)

    # the row state rides the scans as one tuple of leaves a kind
    st = {kind: tuple(state[leaf] for leaf in leaves)
          for kind, leaves in _ROW_STATE.items() if kind in cfg.layer_kinds}
    carry = (x, cache["k"], cache["v"], st)
    # the leading layers, before the scanned periods: each the first so
    # many of its kind (``lead_kind``: what the periods' indices start at)
    lead_kind = dict.fromkeys(LAYER_KINDS, 0)
    for li, kind in enumerate(cfg.layer_kinds[:lead]):
        carry, _ = one_layer(kind, carry, li, lead_kind[kind], dense=True)
        lead_kind[kind] += 1
    carry, counts = jax.lax.scan(period, carry,
                                 jnp.arange(n_per, dtype=jnp.int32))
    # a last, partial period (behind leading layers only)
    tail = []
    for run in cfg._runs(cfg.layer_kinds[lead + n_per * per:]):
        carry, c = run_of(carry, n_per, run)
        tail.append(c)
    x, new_k, new_v, st = carry
    if valid is not None and t > 1:     # the head at the last real position
        x = jnp.take_along_axis(x, (valid - 1)[:, None, None], axis=1)
    logits = _final_logits(cfg, params, x)
    out_cache = {"k": new_k, "v": new_v, "pages": pages}
    if cfg.keeps_row_state:
        out_cache["state"] = {
            leaf: new for kind, leaves in st.items()
            for leaf, new in zip(_ROW_STATE[kind], leaves)}
    if grouped:     # [sparse layers, held]
        counts = counts.reshape(n_per * per, -1)
        out_cache["expert_counts"] = (
            jnp.concatenate([counts] + tail) if tail else counts)
    return logits, out_cache


def _head_logits(cfg: TransformerConfig, x, head, embed=None):
    """Next-token logits: head 0 of ``n_pred_heads`` (the first
    ``vocab_size`` columns of the head matrix), in ``logits_dtype`` where
    the configuration states one (bf16 operands, accumulated and kept in
    float32).  With ``tie_embeddings`` the head is ``embed``'s transpose
    (contracted in place, never transposed)."""
    if cfg.tie_embeddings:
        scales = None
        if isinstance(embed, QTensor):      # per-row scales: per logit
            embed, scales = embed.values, embed.scales[:, 0]
        logits = jnp.einsum(
            "...d,vd->...v", x, embed.astype(cfg.dtype),
            preferred_element_type=cfg.logits_dtype or cfg.dtype)
        if scales is not None:
            logits = logits * scales.astype(logits.dtype)
        return logits
    if cfg.n_pred_heads > 1:
        v = cfg.vocab_size
        head = (QTensor(head.values[:, :v], head.scales)
                if isinstance(head, QTensor) else head[:, :v])
    if cfg.logits_dtype is None:
        return _qmm(x, head, cfg.dtype)
    if isinstance(head, QTensor):
        s = head.scales.reshape(head.scales.shape[:-2] + (-1,))
        x, head = x * s.astype(cfg.dtype), head.values
    return jnp.matmul(x, head.astype(cfg.dtype),
                      preferred_element_type=cfg.logits_dtype)


def eva_summarize(cfg: TransformerConfig, k, v, phi, mu):
    """One summary per chunk of ``eva_chunk`` entries: with ``a`` the
    softmax over a chunk's entries of ``head_dim**-0.5 * phi . k``, the
    summary's key is ``sum a k + mu`` and its value ``sum a v``.  ``k``,
    ``v``: [L, B, n, KV, Dh] (rotated keys); ``phi``, ``mu``: [L, KV, Dh].
    Returns ([L, B, n / eva_chunk, KV, Dh],) x 2 in ``k``'s dtype, the
    arithmetic in float32."""
    L, b, n, kvh, dh = k.shape
    c = cfg.eva_chunk
    kf = k.astype(jnp.float32).reshape(L, b, n // c, c, kvh, dh)
    vf = v.astype(jnp.float32).reshape(L, b, n // c, c, kvh, dh)
    phi = phi.astype(jnp.float32)[:, None, None, None]
    a = jax.nn.softmax(
        jnp.sum(kf * phi, axis=-1) / math.sqrt(dh), axis=3)[..., None]
    ks = jnp.sum(a * kf, axis=3) + mu.astype(jnp.float32)[:, None, None]
    vs = jnp.sum(a * vf, axis=3)
    return ks.astype(k.dtype), vs.astype(v.dtype)


def eva_close_window(cfg: TransformerConfig, params, pool, pages, window):
    """Close window ``window`` (scalar int32) of the rows whose table rows
    are ``pages`` ([B, NP]): read its ``eva_window`` exact entries from the
    pages, pool them (:func:`eva_summarize`) and write the
    ``eva_summaries`` results over the window's first entries, where the
    next window's exact entries will follow them.  The pages behind are
    the caller's to free.  A window's first entry is page-aligned
    (``init_paged_cache`` checks), so both sides move whole pages; one
    layer is read at a time."""
    ps = pool["k"].shape[3]
    s_ent, w_ent = cfg.eva_summaries, cfg.eva_window
    cols = window * (s_ent // ps) + jnp.arange(w_ent // ps, dtype=jnp.int32)
    pg = pages[:, cols]                                     # [B, W/ps]
    lay = params["layers"]

    def one_layer(li):
        def entries(leaf):
            # [B, W/ps, KV, ps, Dh] -> [1, B, W, KV, Dh]
            x = leaf[li, pg]
            b, n, kvh, _, dh = x.shape
            return x.transpose(0, 1, 3, 2, 4).reshape(1, b, n * ps, kvh, dh)

        ks, vs = eva_summarize(cfg, entries(pool["k"]), entries(pool["v"]),
                               lay["eva_phi"][li][None],
                               lay["eva_mu"][li][None])
        return ks[0], vs[0]

    ks, vs = jax.lax.map(one_layer,
                         jnp.arange(cfg.n_layers, dtype=jnp.int32))
    at = window * s_ent
    return {"k": _paged_cache_write_all(pool["k"], ks, pages, at,
                                        aligned=True),
            "v": _paged_cache_write_all(pool["v"], vs, pages, at,
                                        aligned=True)}


def _check_sampling_args(top_k: Optional[int], top_p: Optional[float]):
    if top_k is not None and top_k < 1:
        raise ValueError(f"top_k must be >= 1, got {top_k}")
    if top_p is not None and not 0.0 < top_p <= 1.0:
        raise ValueError(f"top_p must be in (0, 1], got {top_p}")


def filter_logits(logits, temperature: float = 1.0,
                  top_k: Optional[int] = None,
                  top_p: Optional[float] = None):
    """Temperature-scale ``logits`` [..., V] and mask everything outside
    the ``top_k`` highest-logit tokens and/or the ``top_p`` nucleus (the
    smallest set of tokens whose probability mass reaches ``top_p``; the
    argmax token always survives) to -inf.  ``softmax`` of the result is
    the sampling distribution — exposed separately because speculative
    sampling needs the full distribution, not just a draw.  Requires
    ``temperature > 0``.  Static shapes throughout — sorts and masks, no
    dynamic gathers — so it scans/jits cleanly.
    """
    if temperature <= 0.0:
        raise ValueError("filter_logits needs temperature > 0 (greedy "
                         "sampling has no distribution to filter)")
    _check_sampling_args(top_k, top_p)
    logits = logits.astype(jnp.float32) / temperature
    if top_k is not None and top_k < logits.shape[-1]:
        kth = jax.lax.top_k(logits, top_k)[0][..., -1:]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    if top_p is not None and top_p < 1.0:
        sorted_logits = jnp.sort(logits, axis=-1)[..., ::-1]  # descending
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        # Keep tokens whose PRECEDING cumulative mass is < top_p (the
        # first excluded token is the one that pushes the mass past it);
        # the argmax's preceding mass is 0, so it always survives.
        keep = (jnp.cumsum(probs, axis=-1) - probs) < top_p
        threshold = jnp.min(jnp.where(keep, sorted_logits, jnp.inf),
                            axis=-1, keepdims=True)
        logits = jnp.where(logits < threshold, -jnp.inf, logits)
    return logits


def sample_logits(logits, key, temperature: float = 1.0,
                  top_k: Optional[int] = None,
                  top_p: Optional[float] = None):
    """Sample token ids from ``logits`` [..., V]: greedy when
    ``temperature <= 0``, else a categorical draw from
    ``filter_logits`` (temperature / top-k / top-p nucleus)."""
    if temperature <= 0.0:
        _check_sampling_args(top_k, top_p)
        return jnp.argmax(logits.astype(jnp.float32), axis=-1).astype(
            jnp.int32)
    filtered = filter_logits(logits, temperature, top_k, top_p)
    return jax.random.categorical(key, filtered, axis=-1).astype(jnp.int32)


def generate(cfg: TransformerConfig, params, prompt, max_new_tokens: int,
             rng=None, temperature: float = 0.0,
             top_k: Optional[int] = None, top_p: Optional[float] = None,
             quantized_cache: bool = False, prompt_lens=None,
             prefix=None, stop_token: Optional[int] = None, cache=None):
    """Autoregressive generation: prefill the prompt in one pass, then one
    fused scan step per token (KV cache; greedy, temperature, top-k and/or
    top-p nucleus sampling — see ``sample_logits``).

    ``quantized_cache`` stores K/V as int8 (``init_cache``) — combined
    with ``quantize_params`` this is the full int8 serving config.

    ``prompt``: [B, Tp] int32.  Returns [B, Tp + max_new_tokens]
    (``[B, T0 + Tp + max_new_tokens]`` with a prefix).

    ``prompt_lens`` ([B] int32, optional) serves a RAGGED batch: row i's
    real prompt is ``prompt[i, :prompt_lens[i]]`` (right-padding ignored —
    causal attention plus per-row position bounds keep pad slots
    invisible, and each row's generated tokens overwrite them in the
    cache).  Row i's continuation lands right after its real prompt in
    the returned array; later entries are padding.

    ``prefix`` ([T0] int32, optional) is a SHARED prompt prefix (system
    prompt): prefilled ONCE at batch 1 and its cache broadcast to every
    row — the prompt-caching serving pattern.  Equivalent to prepending
    it to every row of ``prompt``, at 1/B the prefix prefill cost.

    ``stop_token``: rows that emit it freeze (their tail fills with the
    stop token), and decoding EXITS EARLY once every row has stopped —
    tokens up to each row's first stop are identical to a run without
    ``stop_token``.

    ``cache``: a caller-managed cache — notably a PAGED one
    (``init_paged_cache`` + a :class:`PageAllocator` table under
    ``"pages"``), whose pages must back every position the run touches.
    """
    b, tp = prompt.shape
    t0 = 0 if prefix is None else prefix.shape[0]
    if max_new_tokens <= 0:
        # Keep the documented [B, T0 + Tp] shape in the degenerate case.
        if prefix is None:
            return prompt
        return jnp.concatenate(
            [jnp.broadcast_to(prefix, (b, t0)), prompt], axis=1)
    if rng is None:
        rng = jax.random.PRNGKey(0)

    def sample(logits, key):
        return sample_logits(logits, key, temperature, top_k, top_p)

    logits, cache = _prefill(cfg, params, prompt, t0 + tp + max_new_tokens,
                             quantized=quantized_cache, prefix=prefix,
                             cache=cache)
    rng, key = jax.random.split(rng)
    if prompt_lens is None:
        next_logits = logits[:, -1]
        pos0 = jnp.asarray(t0 + tp, jnp.int32)
    else:
        lens = jnp.asarray(prompt_lens, jnp.int32)
        # Row i's next token follows its LAST REAL token, not the padding.
        next_logits = jnp.take_along_axis(
            logits, (lens - 1)[:, None, None], axis=1)[:, 0]
        pos0 = t0 + lens
    tok = sample(next_logits, key)

    def step_once(cache, tok, pos, rng):
        logits, cache = decode_step(cfg, params, cache, tok[:, None], pos)
        rng, key = jax.random.split(rng)
        return cache, sample(logits[:, -1], key), rng

    if stop_token is None:
        def body(carry, _):
            cache, tok, pos, rng = carry
            cache, nxt, rng = step_once(cache, tok, pos, rng)
            return (cache, nxt, pos + 1, rng), tok

        (cache, tok, _, _), toks = jax.lax.scan(
            body, (cache, tok, pos0, rng), None,
            length=max_new_tokens - 1)
        generated = jnp.concatenate(
            [jnp.moveaxis(toks, 0, 1), tok[:, None]], axis=1)
    else:
        # while_loop instead of scan: exit as soon as every row stopped
        # (short answers don't pay for max_new_tokens steps).  The REAL
        # sampled token keeps feeding the model — only the recorded
        # output freezes — so cache/RNG state stays bit-identical to a
        # stop-free run and the before-the-stop equality guarantee is
        # unconditional (frozen rows feeding synthetic stop tokens could
        # otherwise perturb batch statistics, e.g. capacity-MoE routing).
        stop = jnp.asarray(stop_token, jnp.int32)
        gen0 = jnp.full((b, max_new_tokens), stop, jnp.int32)
        gen0 = jax.lax.dynamic_update_slice(gen0, tok[:, None], (0, 0))
        done0 = tok == stop

        def cond(state):
            i = state[4]
            return (i < max_new_tokens - 1) & ~jnp.all(state[5])

        def wbody(state):
            cache, tok, pos, rng, i, done, gen = state
            cache, nxt, rng = step_once(cache, tok, pos, rng)
            rec = jnp.where(done, stop, nxt)
            gen = jax.lax.dynamic_update_slice(gen, rec[:, None], (0, i + 1))
            return (cache, nxt, pos + 1, rng, i + 1, done | (nxt == stop),
                    gen)

        state = (cache, tok, pos0, rng, jnp.asarray(0, jnp.int32), done0,
                 gen0)
        state = jax.lax.while_loop(cond, wbody, state)
        generated = state[6]
    lead = (jnp.broadcast_to(prefix, (b, t0)),) if prefix is not None else ()
    if prompt_lens is None:
        return jnp.concatenate([*lead, prompt, generated], axis=1)
    # Scatter each row's continuation right after its real prompt.
    out = jnp.concatenate(
        [*lead, prompt, jnp.zeros((b, max_new_tokens), prompt.dtype)],
        axis=1)
    idx = (t0 + lens)[:, None] + jnp.arange(max_new_tokens,
                                            dtype=jnp.int32)[None]
    return _scatter_rows(out, idx, generated)


def _prefill(cfg: TransformerConfig, params, prompt, depth: int,
             quantized: bool = False, prefix=None, cache=None):
    """Fresh-cache prefill shared by the generation entry points: with a
    ``prefix``, prefill it ONCE at batch 1, broadcast the cache to the
    prompt's batch (the cache batch axis is 1), then prefill the per-row
    prompt chunk at position t0.  Returns (prompt-chunk logits, cache).

    ``cache`` (optional) supplies a caller-managed cache instead — a
    preallocated contiguous one, or a PAGED dict ({"k", "v", "pages"};
    the caller's allocator must back every position the generation will
    touch).  Not combinable with ``prefix`` (whose batch-1 broadcast
    assumes this function owns the buffer)."""
    b = prompt.shape[0]
    if cache is not None:
        if prefix is not None:
            raise ValueError("generate: prefix and a caller-provided "
                             "cache cannot combine (the prefix broadcast "
                             "owns the buffer layout)")
        return decode_step(cfg, params, cache, prompt, 0)
    cache = init_cache(cfg, 1 if prefix is not None else b, depth,
                       quantized=quantized)
    if prefix is None:
        return decode_step(cfg, params, cache, prompt, 0)
    _, cache = decode_step(cfg, params, cache, prefix[None, :], 0)
    cache = jax.tree_util.tree_map(lambda x: jnp.repeat(x, b, axis=1),
                                   cache)
    return decode_step(cfg, params, cache, prompt, prefix.shape[0])


def _scatter_rows(out, idx, vals, mode: Optional[str] = None):
    """Row-wise scatter: ``out[i, idx[i]] = vals[i]`` (idx/vals may carry a
    trailing per-row dim).  ``mode="drop"`` discards out-of-bounds entries
    — the masked-write idiom (duplicate clipped indices have no defined
    scatter winner, so masking via OOB indices is the safe form)."""
    return jax.vmap(lambda o, i, v: o.at[i].set(v, mode=mode))(
        out, idx, vals)


def beam_search(cfg: TransformerConfig, params, prompt,
                max_new_tokens: int, beam: int = 4,
                quantized_cache: bool = False, return_scores: bool = False):
    """Deterministic beam search: keep the ``beam`` highest-total-logprob
    continuations, expanding all of them each step in one batched decode
    (the cache carries B·W rows; parent rows are gathered when beams
    reorder).  Returns the best sequence per row, [B, Tp + new] (with the
    per-row best total logprob when ``return_scores``).

    ``beam=1`` reduces to greedy decoding exactly.  Uniform prompts only
    (compose with ragged serving by bucketing lengths).
    """
    b, tp = prompt.shape
    w = int(beam)
    if w < 1:
        raise ValueError(f"beam must be >= 1, got {beam}")
    if max_new_tokens <= 0:
        return (prompt, jnp.zeros((b,), jnp.float32)) if return_scores \
            else prompt
    depth = tp + max_new_tokens
    cache = init_cache(cfg, b, depth, quantized=quantized_cache)
    logits, cache = decode_step(cfg, params, cache, prompt, 0)
    logp0 = jax.nn.log_softmax(logits[:, -1].astype(jnp.float32), -1)

    # First expansion: top-W tokens of the prefill distribution seed the
    # beams (no duplicate-beam trick needed — beams differ from step 0).
    scores, tok = jax.lax.top_k(logp0, w)               # [B, W]
    tok = tok.astype(jnp.int32)
    # Tile the cache W times: rows grouped beam-major per batch row
    # ([b0w0, b0w1, ..., b1w0, ...]) so row index = b*W + w.
    cache = jax.tree_util.tree_map(lambda x: jnp.repeat(x, w, axis=1),
                                   cache)
    hist = jnp.zeros((b, w, max_new_tokens), jnp.int32)
    hist = hist.at[:, :, 0].set(tok)

    def step(carry, i):
        cache, tok, scores, hist = carry
        logits, cache = decode_step(cfg, params, cache,
                                    tok.reshape(b * w, 1), tp + i)
        logp = jax.nn.log_softmax(
            logits[:, -1].astype(jnp.float32), -1)      # [B*W, V]
        v = logp.shape[-1]
        total = scores[:, :, None] + logp.reshape(b, w, v)
        scores, flat = jax.lax.top_k(total.reshape(b, w * v), w)
        parent = flat // v                              # [B, W]
        tok = (flat % v).astype(jnp.int32)
        # Reorder beam state to follow the surviving parents.
        rows = (jnp.arange(b, dtype=jnp.int32)[:, None] * w
                + parent).reshape(-1)                   # [B*W] global rows
        cache = jax.tree_util.tree_map(
            lambda c: jnp.take(c, rows, axis=1), cache)
        hist = jnp.take_along_axis(hist, parent[:, :, None], axis=1)
        hist = jax.lax.dynamic_update_index_in_dim(
            hist, tok, i + 1, axis=2)
        return (cache, tok, scores, hist), None

    (cache, tok, scores, hist), _ = jax.lax.scan(
        step, (cache, tok, scores, hist),
        jnp.arange(max_new_tokens - 1, dtype=jnp.int32))
    best = jnp.argmax(scores, axis=1)                   # [B]
    best_hist = jnp.take_along_axis(
        hist, best[:, None, None], axis=1)[:, 0]        # [B, new]
    out = jnp.concatenate([prompt, best_hist], axis=1)
    if return_scores:
        return out, jnp.take_along_axis(scores, best[:, None], 1)[:, 0]
    return out


def greedy_accept_counts(drafts, g):
    """Greedy speculative acceptance: given draft proposals [B, k] and
    the target's greedy tokens over the verify chunk [B, k+1], return
    the per-row commit count — the leading run of draft==target matches
    plus one (the target's correction, or its bonus token when every
    proposal matched).  Shared by ``speculative_generate`` and the
    continuous batcher's speculative rounds (the subtle bit is the
    argmin-over-[match|False] form: it returns the FIRST mismatch index,
    or k when there is none)."""
    k = drafts.shape[1]
    match = drafts == g[:, :k]
    a = jnp.argmin(jnp.concatenate(
        [match, jnp.zeros((match.shape[0], 1), bool)],
        axis=1).astype(jnp.int32), axis=1)
    return a + 1


def rejection_accept(drafts, pd, pt, u):
    """Speculative rejection sampling's accept/correct math, shared by
    ``speculative_generate`` and the continuous batcher's sampled rounds.

    ``drafts`` [B, k] proposals, ``pd`` [B, k, V] their draft
    distributions, ``pt`` [B, k+1, V] the target's (filtered)
    distributions over the verify chunk, ``u`` [B, k] uniform draws.
    Accept proposal j iff ``u_j < pt(x_j)/pd(x_j)`` (computed as
    ``u*pd < pt``, robust as pd → 0); ``a`` is the first rejection index
    (k when all accepted).  Returns ``(a, dist)`` where ``dist`` [B, V]
    is the correction distribution at index a — norm(max(0, pt − pd)),
    with pd zero-padded at index k so the all-accepted bonus draw (from
    pt_k itself) falls out of the same formula."""
    b, k = drafts.shape
    ptx = jnp.take_along_axis(pt[:, :k], drafts[..., None], -1)[..., 0]
    pdx = jnp.take_along_axis(pd, drafts[..., None], -1)[..., 0]
    acc = u * pdx < ptx
    a = jnp.argmin(jnp.concatenate(
        [acc, jnp.zeros((b, 1), bool)], axis=1).astype(jnp.int32), axis=1)
    pd_pad = jnp.concatenate(
        [pd, jnp.zeros((b, 1, pd.shape[-1]), pd.dtype)], axis=1)
    pt_a = jnp.take_along_axis(pt, a[:, None, None], 1)[:, 0]
    pd_a = jnp.take_along_axis(pd_pad, a[:, None, None], 1)[:, 0]
    resid = jnp.maximum(pt_a - pd_a, 0.0)
    norm = jnp.sum(resid, -1, keepdims=True)
    dist = jnp.where(norm > 1e-9, resid / jnp.maximum(norm, 1e-9), pt_a)
    return a, dist


def speculative_cache_depth(prompt_len: int, max_new_tokens: int,
                            n_draft: int, prefix_len: int = 0) -> int:
    """Cache positions ``speculative_generate`` may touch (its overshoot
    slack included): size contiguous caches — or back paged rows
    (``PageAllocator.ensure``) — with AT LEAST this many positions."""
    return prefix_len + prompt_len + max_new_tokens + 2 * n_draft + 1


def speculative_generate(cfg: TransformerConfig, params,
                         draft_cfg: TransformerConfig, draft_params,
                         prompt, max_new_tokens: int, n_draft: int = 4,
                         prompt_lens=None, temperature: float = 0.0,
                         top_k: Optional[int] = None,
                         top_p: Optional[float] = None, rng=None,
                         quantized_cache: bool = False, prefix=None,
                         cache=None, stop_token: Optional[int] = None):
    """Speculative decoding: a cheap DRAFT model proposes ``n_draft``
    tokens per round, the target model scores them all in ONE chunked
    decode, and the leading accepted run commits (plus one
    correction/bonus token) — between 1 and ``n_draft + 1`` tokens per
    target dispatch.

    ``temperature <= 0`` (default): greedy — a draft commits while it
    matches the target's own argmax, and the output is EXACTLY the target
    model's greedy continuation, whatever the draft proposes (a bad draft
    only costs speed).  ``temperature > 0``: speculative SAMPLING
    (Leviathan et al.) — draft token x is accepted with probability
    ``min(1, p_target(x)/p_draft(x))``; on rejection the correction is
    drawn from ``norm(max(0, p_target − p_draft))``, and when every draft
    survives a bonus token is drawn from the target's next distribution.
    The committed tokens are distributed exactly as target-only sampling
    under the same temperature/top-k/top-p filtering.

    Both models run on the ragged per-row position machinery, so each
    batch row accepts at its own rate.  ``prompt``: [B, Tp];
    ``prompt_lens`` and ``prefix`` as in :func:`generate` (a shared
    prefix prefills ONCE per model at batch 1 and broadcasts into both
    caches).  Returns [B, (T0 +) Tp + max_new_tokens] with row i's
    continuation right after its real prompt.

    ``cache``: a caller-managed TARGET cache (e.g. a paged pool); it
    must back at least :func:`speculative_cache_depth` positions per
    row.  ``stop_token``: rows freeze once a committed token is the
    stop and the loop exits when all rows have stopped; tokens up to
    each row's FIRST stop equal a stop-free run, but — unlike
    :func:`generate`, which fills the tail with the stop token — the
    tail after the stop is UNSPECIFIED (same-round overshoot tokens,
    then zeros); truncate at the first stop as ``examples/serve.py``
    does.
    """
    if cfg.window is not None or draft_cfg.window is not None:
        raise ValueError("speculative decoding does not compose with "
                         "sliding-window configs (rolling caches cannot "
                         "be ragged)")
    b, tp = prompt.shape
    if max_new_tokens <= 0:
        # Keep the documented [B, T0 + Tp] shape in the degenerate case.
        if prefix is None:
            return prompt
        return jnp.concatenate(
            [jnp.broadcast_to(prefix, (b, prefix.shape[0])), prompt],
            axis=1)
    k = int(n_draft)
    if k < 1:
        raise ValueError(f"n_draft must be >= 1, got {n_draft}")
    sampling = temperature > 0.0
    if rng is None:
        rng = jax.random.PRNGKey(0)
    t0 = 0 if prefix is None else prefix.shape[0]
    # Slack: a row can overshoot to committed = max_new + k (pos =
    # lens + max_new + k - 1) and, frozen, keeps verifying k+1-token
    # chunks at that position — writes reach lens + max_new + 2k.
    depth = speculative_cache_depth(tp, max_new_tokens, k, prefix_len=t0)
    # ``quantized_cache``/caller-provided ``cache`` (e.g. a paged pool —
    # its pages must back depth-many positions) apply to the TARGET cache
    # (where the bytes are); the draft is small by construction and stays
    # an internal fp buffer.
    logits, cache = _prefill(cfg, params, prompt, depth,
                             quantized=quantized_cache, prefix=prefix,
                             cache=cache)
    _, draft_cache = _prefill(draft_cfg, draft_params, prompt, depth,
                              prefix=prefix)
    if prompt_lens is None:
        lens = jnp.full((b,), tp, jnp.int32)
    else:
        lens = jnp.asarray(prompt_lens, jnp.int32)
    first_logits = jnp.take_along_axis(
        logits, (lens - 1)[:, None, None], axis=1)[:, 0]
    rng, key0 = jax.random.split(rng)
    tok = sample_logits(first_logits, key0, temperature, top_k, top_p)
    lens = t0 + lens                    # absolute positions from here on
    # One committed token exists already (the prefill's sample).
    lead = (jnp.broadcast_to(prefix, (b, t0)),) if prefix is not None else ()
    out = jnp.concatenate(
        [*lead, prompt, jnp.zeros((b, max_new_tokens), prompt.dtype)],
        axis=1)
    out = _scatter_rows(out, lens, tok)
    limit = lens + max_new_tokens       # first out index past row's region

    def commit(out, pos, n_commit, vals):
        # Commit the first n_commit vals right after each row's last
        # committed token.  Masked/overflow entries get an out-of-bounds
        # index and drop — clipping instead would alias real indices, and
        # duplicate scatter indices have no defined winner.
        j = jnp.arange(k + 1, dtype=jnp.int32)[None]
        idx = pos[:, None] + 1 + j
        mask = (j < n_commit[:, None]) & (idx < limit[:, None])
        return _scatter_rows(out, jnp.where(mask, idx, out.shape[1]), vals,
                             mode="drop")

    def advance(committed, n_commit, vals):
        # ``stop_token``: a row whose committed run contains the stop
        # freezes (its quota fills) — the loop exits once every row has
        # stopped.  Tokens after a row's first stop within the same
        # round's commit are unspecified; truncate at the stop (as
        # examples/serve.py does).
        nxt = committed + n_commit
        if stop_token is None:
            return nxt
        j = jnp.arange(k + 1, dtype=jnp.int32)[None]
        hit = jnp.any((vals == stop_token) & (j < n_commit[:, None]),
                      axis=1)
        return jnp.where(hit, max_new_tokens, nxt)

    def greedy_round(state):
        cache, draft_cache, tok, pos, committed, out, rng = state
        active = committed < max_new_tokens

        # Draft k tokens autoregressively (t=1 ragged steps).  k+1 scan
        # steps: the extra one writes the last proposal's K/V at pos+k
        # (proposal discarded), so a fully-accepted round never leaves a
        # hole the draft would condition on for the rest of the row.
        def dstep(carry, _):
            dcache, dtok, dpos = carry
            lg, dcache = decode_step(draft_cfg, draft_params, dcache,
                                     dtok[:, None], dpos)
            nxt = jnp.argmax(lg[:, -1], -1).astype(jnp.int32)
            return (dcache, nxt, dpos + 1), nxt

        (draft_cache, _, _), drafts = jax.lax.scan(
            dstep, (draft_cache, tok, pos), None, length=k + 1)
        drafts = jnp.moveaxis(drafts, 0, 1)[:, :k]      # [B, k]

        # Target scores the whole drafted chunk in one ragged decode.
        chunk = jnp.concatenate([tok[:, None], drafts], axis=1)  # [B, k+1]
        lg, cache = decode_step(cfg, params, cache, chunk, pos)
        g = jnp.argmax(lg, -1).astype(jnp.int32)        # [B, k+1] greedy
        counts = greedy_accept_counts(drafts, g)
        a = counts - 1                                  # leading-run length
        n_commit = jnp.where(active, counts, 0)
        out = commit(out, pos, n_commit, g)
        tok = jnp.where(active,
                        jnp.take_along_axis(g, a[:, None], axis=1)[:, 0],
                        tok)
        return (cache, draft_cache, tok, pos + n_commit,
                advance(committed, n_commit, g), out, rng)

    def sampling_round(state):
        cache, draft_cache, tok, pos, committed, out, rng = state
        active = committed < max_new_tokens
        rng, kd, ka, kr = jax.random.split(rng, 4)

        # Draft k sampled tokens, keeping each step's full distribution.
        def dstep(carry, key):
            dcache, dtok, dpos = carry
            lg, dcache = decode_step(draft_cfg, draft_params, dcache,
                                     dtok[:, None], dpos)
            f = filter_logits(lg[:, -1], temperature, top_k, top_p)
            nxt = jax.random.categorical(key, f, axis=-1).astype(jnp.int32)
            return (dcache, nxt, dpos + 1), (nxt, jax.nn.softmax(f, -1))

        # k+1 steps for the same backfill-the-last-slot reason as the
        # greedy round; the extra proposal and its distribution drop.
        (draft_cache, _, _), (drafts, pd) = jax.lax.scan(
            dstep, (draft_cache, tok, pos), jax.random.split(kd, k + 1))
        drafts = jnp.moveaxis(drafts, 0, 1)[:, :k]      # [B, k]
        pd = jnp.moveaxis(pd, 0, 1)[:, :k]              # [B, k, V]

        chunk = jnp.concatenate([tok[:, None], drafts], axis=1)
        lg, cache = decode_step(cfg, params, cache, chunk, pos)
        pt = jax.nn.softmax(
            filter_logits(lg, temperature, top_k, top_p), -1)  # [B, k+1, V]

        # Accept x_j with prob min(1, pt(x_j)/pd(x_j)); correct at the
        # first rejection from norm(max(0, pt − pd)) — rejection_accept
        # carries the shared math.
        u = jax.random.uniform(ka, (b, k))
        a, dist = rejection_accept(drafts, pd, pt, u)
        repl = jax.random.categorical(
            kr, jnp.log(dist + 1e-20), axis=-1).astype(jnp.int32)

        n_commit = jnp.where(active, a + 1, 0)
        j = jnp.arange(k + 1, dtype=jnp.int32)[None]
        cand = jnp.concatenate(
            [drafts, jnp.zeros((b, 1), jnp.int32)], axis=1)
        vals = jnp.where(j == a[:, None], repl[:, None], cand)
        out = commit(out, pos, n_commit, vals)
        tok = jnp.where(active, repl, tok)
        return (cache, draft_cache, tok, pos + n_commit,
                advance(committed, n_commit, vals), out, rng)

    committed0 = jnp.ones((b,), jnp.int32)
    if stop_token is not None:
        committed0 = jnp.where(tok == stop_token, max_new_tokens,
                               committed0)
    state = (cache, draft_cache, tok, lens, committed0, out, rng)
    state = jax.lax.while_loop(
        lambda s: jnp.any(s[4] < max_new_tokens),
        sampling_round if sampling else greedy_round, state)
    return state[5]


def _fused_ce_mode(cfg: TransformerConfig, params, mesh: Optional[Mesh],
                   batch_size: Optional[int] = None) -> Optional[str]:
    """Which fused head+CE path ``loss_fn`` takes: "dense" (single device),
    "dp" (batch-sharded chunks on multi-device data-only meshes — the naive
    dense chunking would cut every chunk across the dp sharding), "tp"
    (vocab-parallel over the tp axis), or None (the standard
    materialize-the-logits path — sp shards the token dim the chunking
    would cut across, pp computes the loss outside the pipeline body, ep
    leaves activation replication to GSPMD)."""
    if isinstance(params["head"], QTensor):
        return None  # serving trees stay on the dequantize-at-matmul path
    if cfg.fused_ce is False:
        return None
    if mesh is None:
        return "dense"
    real = {a for a, s in mesh.shape.items() if s > 1}
    if not real:
        return "dense"
    if real <= {"dp", "fsdp"}:
        # The shard_map'd dp path needs the batch to divide over the data
        # axes (the GSPMD dense route didn't); fall back when it doesn't
        # (e.g. a final partial batch) or when the caller can't say.
        nd = 1
        for a in real:
            nd *= mesh.shape[a]
        if batch_size is not None and batch_size % nd == 0:
            return "dp"
        return "dense"
    if real <= {"dp", "fsdp", "tp"} and cfg.vocab_size % mesh.shape["tp"] == 0:
        return "tp"
    return "dense" if cfg.fused_ce else None


def loss_fn(cfg: TransformerConfig, params, batch, mesh: Optional[Mesh] = None):
    """Next-token prediction: batch = {"tokens": [B, T+1]}.

    With experts enabled, the router's auxiliary losses join the objective
    (standard switch-transformer weighting) and the realized token-overflow
    fraction is surfaced in the metrics."""
    tokens = batch["tokens"]
    mode = _fused_ce_mode(cfg, params, mesh, batch_size=tokens.shape[0])
    if mode is not None:
        x, aux = forward_hidden(cfg, params, tokens[:, :-1], mesh)
        # Pass the master-dtype head: the ops compute in x.dtype but
        # accumulate dw in fp32 and return it at the param dtype.
        if mode == "tp":
            loss = vocab_parallel_cross_entropy(
                x, params["head"], tokens[:, 1:], mesh,
                z_loss=cfg.z_loss, chunk=cfg.ce_chunk)
        elif mode == "dp":
            loss = data_parallel_fused_cross_entropy(
                x, params["head"], tokens[:, 1:], mesh,
                cfg.z_loss, cfg.ce_chunk)
        else:
            loss = fused_linear_cross_entropy(
                x, params["head"], tokens[:, 1:], z_loss=cfg.z_loss,
                chunk=cfg.ce_chunk)
    else:
        logits, aux = forward(cfg, params, tokens[:, :-1], mesh,
                              return_aux=True)
        loss = cross_entropy_loss(logits, tokens[:, 1:],
                                  z_loss=cfg.z_loss)
    metrics = {"perplexity": jnp.exp(loss)}
    if cfg.n_experts:
        # Under pp the aux rides the pipeline per microbatch (gpipe-style
        # estimator of the full-batch statistics); without pp it is the
        # exact batch statistic.  Either way it joins the objective.
        loss = (loss
                + cfg.router_aux_weight * aux["load_balance_loss"]
                + cfg.router_z_weight * aux["z_loss"])
        metrics.update(load_balance_loss=aux["load_balance_loss"],
                       router_z_loss=aux["z_loss"],
                       moe_overflow_frac=aux["overflow_frac"])
    return loss, metrics


def train_step_1f1b(cfg: TransformerConfig, params, batch,
                    mesh: Mesh, num_microbatches: Optional[int] = None):
    """One fused 1F1B forward+backward pass of the LM objective on a
    pp x dp/fsdp mesh: returns ``(loss, grads)`` with ``grads`` matching
    ``params``' structure (fp32), ready for any optax update.

    This is the memory-bounded alternative to ``jax.grad(loss_fn)`` over
    the gpipe/circular pipeline: the live activation stash is one chunk
    input per pipeline slot (O(pp), not O(microbatches)) because forward
    and backward interleave inside ``pipeline_train_1f1b``'s single loop.
    The embedding differentiates through the returned dx, and the final
    norm + unembedding head ride as tail params of the loss stage.

    Scope: dense AND dense-top-k-MoE configs on pp x tp x ep (+ dp/fsdp)
    meshes.  tp stages run the manual-collective Megatron block with the
    in-body-AD f/g collectives, and the loss tail is the in-body
    VOCAB-PARALLEL fused CE (``ops/layers.vocab_parallel_ce_inbody``:
    the unembedding shards over tp, no device holds more than a
    [chunk, V/tp] logits block — fwd or bwd); a vocab that does not
    divide over tp falls back to the replicated fused-CE tail, as
    ``loss_fn`` does.  MoE stages shard whole experts over ep (and
    per-expert FFN widths over tp) with the in-body-AD f/g collectives,
    and carry the router aux losses as per-stage scalar aux terms seeded
    alongside the loss vjp (``pipeline_train_1f1b(stage_aux=True)``) —
    the same layer-mean estimator the gpipe route uses, so grads match
    ``jax.grad(loss_fn)`` on the same mesh.  ``cfg.pp_virtual_stages > 1``
    runs the INTERLEAVED 1F1B timetable (device d owns layer chunks d,
    d+pp, ...; every microbatch laps the ring v times), shrinking the
    bubble for v x more ppermute hops at the same per-chunk stash rule.
    sp shards the SEQUENCE inside stages — composing with tp into the
    full pp x tp x sp x dp stack (local heads x local sequence):
    attention is the K/V all_gather form (``_sp_gather_attention`` — a
    ppermute ring's global participant set would deadlock in the tick's
    divergent branches), weights and the loss tail fan/reduce over sp
    with the f/g pair, and router aux averages per shard.
    ``moe_impl='switch'`` stays with the gpipe/circular schedules.
    """
    pp = mesh.shape.get("pp", 1)
    tp = mesh.shape.get("tp", 1)
    ep = mesh.shape.get("ep", 1)
    sp = mesh.shape.get("sp", 1)
    real = {a for a, s in mesh.shape.items() if s > 1}
    if not real <= {"pp", "tp", "dp", "fsdp", "ep", "sp"}:
        raise ValueError(
            f"train_step_1f1b supports pp x tp x ep x sp x dp/fsdp "
            f"meshes; got {dict(mesh.shape)}")
    if sp > 1 and (batch["tokens"].shape[1] - 1) % sp:
        raise ValueError(
            f"sequence length {batch['tokens'].shape[1] - 1} must divide "
            f"over sp ({sp})")
    if tp > 1 and cfg.kv_heads % tp:
        raise ValueError(f"1f1b x tp needs tp ({tp}) to divide kv_heads "
                         f"({cfg.kv_heads})")
    if tp > 1 and cfg.d_ff % tp:
        raise ValueError(f"1f1b x tp needs tp ({tp}) to divide d_ff "
                         f"({cfg.d_ff}) for the Megatron FFN split")
    if ep > 1 and not cfg.n_experts:
        raise ValueError("an ep axis needs n_experts > 0")
    if cfg.n_experts and cfg.n_experts % max(ep, 1):
        raise ValueError(f"ep ({ep}) must divide n_experts "
                         f"({cfg.n_experts})")
    if cfg.n_experts and cfg.moe_impl == "switch":
        raise ValueError("train_step_1f1b runs the dense top-k MoE "
                         "(moe_impl='switch' assumes outer "
                         "differentiation); use pp_schedule="
                         "'gpipe'/'circular' for switch dispatch")
    v = cfg.pp_virtual_stages
    if v > 1 and pp < 2:
        raise ValueError("pp_virtual_stages > 1 needs a real pp axis")
    n_chunks = max(pp, 1) * v
    if cfg.n_layers % n_chunks:
        raise ValueError(f"{cfg.n_layers} layers not divisible into "
                         f"{n_chunks} pipeline chunks "
                         f"({pp} stages x {v} virtual)")
    from tfmesos_tpu.parallel.pipeline import pipeline_train_1f1b

    tokens = batch["tokens"]
    inp, tgt = tokens[:, :-1], tokens[:, 1:]
    per = cfg.n_layers // n_chunks
    stacked = jax.tree_util.tree_map(
        lambda p: p.reshape(n_chunks, per, *p.shape[1:]),
        params["layers"])

    ep_axis = "ep" if (cfg.n_experts and ep > 1) else None
    sp_axis = "sp" if sp > 1 else None
    partition = None
    if tp > 1:
        # forward_hidden's dense tp partition table (shared helpers);
        # stages run the manual Megatron block with in-body-AD
        # collectives.
        partition = {**_dense_tp_attn_partition(),
                     **(_moe_param_partition(ep_axis, "tp")
                        if cfg.n_experts else _dense_tp_mlp_partition())}
        if cfg.n_shared_experts:
            partition.update(_shared_expert_partition("tp"))
    elif ep_axis:
        partition = {
            **_replicated_attn_partition(),
            **_moe_param_partition(ep_axis, None),
        }
        if cfg.n_shared_experts:
            partition.update(_shared_expert_partition(None))

    # MoE stages return a pre-weighted scalar aux loss (their layers'
    # summed router terms, normalized by n_layers so the sum over stages
    # is the model's layer-mean aux — the same estimator loss_fn's gpipe
    # route uses); pipeline_train_1f1b seeds it alongside the loss vjp.
    stage_aux = bool(cfg.n_experts)

    def stage_fn(stage_params, h):
        if sp_axis is not None:
            # Sequence shards: weights are REPLICATED over sp but consumed
            # by per-shard-divergent (local-token) compute — fan them
            # through the f operator so the in-body vjp psums their
            # partial gradients over sp exactly once.
            from tfmesos_tpu.parallel.collectives import (
                broadcast_replicated_grad)
            stage_params = jax.tree_util.tree_map(
                lambda w: broadcast_replicated_grad(w, sp_axis),
                stage_params)
        pos = jnp.arange(h.shape[1], dtype=jnp.int32)
        if sp_axis is not None:
            pos = pos + jax.lax.axis_index(sp_axis) * h.shape[1]
        pos = jnp.broadcast_to(pos, h.shape[:2])
        if tp > 1:
            body = lambda c, lp: _block_manual_tp(cfg, c, lp, pos,
                                                  ep_axis=ep_axis,
                                                  inbody_ad=True,
                                                  sp_axis=sp_axis)
        else:
            body = lambda c, lp: _block(cfg, None, c, lp, pos,
                                        ep_axis=ep_axis,
                                        inbody_ad=(ep_axis is not None
                                                   or sp_axis is not None),
                                        sp_axis=sp_axis)
        if cfg.remat:
            body = jax.checkpoint(body)
        out, layer_aux = jax.lax.scan(body, h, stage_params)
        if not stage_aux:
            return out
        aux = (cfg.router_aux_weight
               * jnp.sum(layer_aux["load_balance_loss"])
               + cfg.router_z_weight * jnp.sum(layer_aux["z_loss"])
               ) / cfg.n_layers
        if sp_axis is not None:
            # Per-shard (local-token) router statistics: average over sp
            # with the transpose-carrying reduction so the 1/m aux seed
            # flows back at 1/sp per shard, not sp-times over.
            from tfmesos_tpu.parallel.collectives import (
                psum_replicated_grad)
            aux = psum_replicated_grad(aux, sp_axis) / sp
        return out, aux.astype(jnp.float32)

    def tail_loss(tail, h, tgt_mb):
        # Fused head+CE: never materializes the [mb, T, vocab] logits —
        # the same bounded-memory route loss_fn takes, which matters
        # doubly on the schedule whose point is the O(pp) stash.  Under
        # tp the head arrives vocab-sharded and the in-body
        # vocab-parallel CE psums the softmax statistics explicitly
        # (its custom VJP keeps the in-loop backward collective-safe).
        # Under sp the tail weights fan (f operator) into per-shard
        # compute and the local-token mean reduces over sp with the
        # identity-transpose psum, so each shard's backward sees the
        # 1/sp-scaled seed exactly once.
        if sp_axis is not None:
            from tfmesos_tpu.parallel.collectives import (
                broadcast_replicated_grad, psum_replicated_grad)
            tail = jax.tree_util.tree_map(
                lambda w: broadcast_replicated_grad(w, sp_axis), tail)
        x = _norm(cfg, h, tail["norm_f"])
        if vocab_parallel_tail:
            loss = vocab_parallel_ce_inbody(x, tail["head"], tgt_mb,
                                            "tp", cfg.z_loss,
                                            cfg.ce_chunk)
        else:
            loss = fused_linear_cross_entropy(x, tail["head"], tgt_mb,
                                              z_loss=cfg.z_loss,
                                              chunk=cfg.ce_chunk)
        if sp_axis is not None:
            loss = psum_replicated_grad(loss, sp_axis) / sp
        return loss

    x, vjp_embed = jax.vjp(
        lambda e: _embed_lookup(e, inp, cfg.dtype), params["embed"])
    tail = {"norm_f": params["norm_f"], "head": params["head"]}
    # Vocab-parallel tail only when the vocab divides over tp; otherwise
    # keep the replicated fused-CE tail (same fallback rule as
    # _fused_ce_mode's tp branch — an indivisible vocab must not refuse
    # a config the replicated tail trains fine).
    vocab_parallel_tail = tp > 1 and cfg.vocab_size % tp == 0
    tail_partition = ({"norm_f": P(None), "head": P(None, "tp")}
                      if vocab_parallel_tail else None)
    loss, g_stacked, g_tail, dx = pipeline_train_1f1b(
        stage_fn, tail_loss, stacked, x, tgt, mesh,
        num_microbatches=num_microbatches, tail_params=tail,
        param_partition=partition, tail_partition=tail_partition,
        stage_aux=stage_aux, virtual_stages=v, seq_axis=sp_axis)
    (g_embed,) = vjp_embed(dx.astype(x.dtype))
    grads = {
        "embed": jax.tree_util.tree_map(
            lambda g: g.astype(jnp.float32), g_embed),
        "layers": jax.tree_util.tree_map(
            lambda g: g.reshape(cfg.n_layers, *g.shape[2:]), g_stacked),
        "norm_f": g_tail["norm_f"],
        "head": g_tail["head"],
    }
    return loss, grads


def _quantized_spec(s: P) -> QTensor:
    """The PartitionSpec pair for a QTensor leaf: ``values`` takes the
    weight's spec, ``scales`` the same minus the last dim (their trailing
    dim is 1, which cannot shard)."""
    parts = tuple(s)
    return QTensor(values=s,
                   scales=P(*(parts[:-1] + (None,))) if parts else P())


def _filter_spec(spec: P, mesh: Mesh) -> P:
    """Drop axes the mesh doesn't have (size-1 axes included)."""
    def keep(a):
        if a is None:
            return None
        if isinstance(a, (tuple, list)):
            kept = tuple(x for x in a if x in mesh.shape and mesh.shape[x] > 1)
            return kept if kept else None
        return a if a in mesh.shape and mesh.shape[a] > 1 else None
    return P(*(keep(a) for a in spec))


def partition_specs(cfg: TransformerConfig, mesh: Mesh) -> Dict[str, Any]:
    """PartitionSpec tree: Megatron-style tp, fsdp on the complementary dim,
    ep over experts.  The layer-stack dim (dim 0) is left unsharded here;
    the pp path re-shapes it into stages itself."""
    tp = mesh.shape.get("tp", 1)
    if tp > 1 and (cfg.kv_heads * cfg.head_dim) % tp:
        raise ValueError(
            f"partition_specs: tp ({tp}) must divide the GQA kv projection "
            f"width ({cfg.kv_heads} kv heads x {cfg.head_dim})")
    attn = {
        "wq": P(None, "fsdp", "tp"),
        "wk": P(None, "fsdp", "tp"),
        "wv": P(None, "fsdp", "tp"),
        "wo": P(None, "tp", "fsdp"),
    }
    if cfg.attn_gate:
        attn["wg"] = P(None, "fsdp", "tp")
    layer = {"attn_norm": P(None, None), "mlp_norm": P(None, None)}
    if cfg.layer_types is None:
        layer.update(attn)
    else:
        # a typed stack: the mixers' leaves by kind; the mamba mixer's
        # projections shard like a dense MLP's, its per-head leaves not
        if cfg.n_attn_layers:
            layer["attention"] = attn
        if cfg.n_mamba_layers:
            layer["mamba"] = {
                "in_proj": P(None, "fsdp", None),
                "out_proj": P(None, None, "fsdp"),
                "conv_w": P(None, None, None), "conv_b": P(None, None),
                "dt_bias": P(None, None), "A_log": P(None, None),
                "D": P(None, None), "norm": P(None, None)}
        if cfg.n_kda_layers:
            layer["kda"] = {
                "in_proj": P(None, "fsdp", None),
                "out_proj": P(None, None, "fsdp"),
                "conv_w": P(None, None, None),
                "f_down": P(None, None, None), "f_up": P(None, None, None),
                "g_down": P(None, None, None), "g_up": P(None, None, None),
                "b_proj": P(None, None, None),
                "dt_bias": P(None, None), "A_log": P(None, None),
                "norm": P(None, None)}
    if cfg.attention == "eva":
        layer.update(eva_phi=P(None, "tp", None), eva_mu=P(None, "tp", None))
    if cfg.n_experts:
        layer.update(
            router=P(None, "fsdp", None),
            e_gate=P(None, "ep", "fsdp", "tp"),
            e_up=P(None, "ep", "fsdp", "tp"),
            e_down=P(None, "ep", "tp", "fsdp"),
        )
        if cfg.router_score == "sigmoid":
            layer["router_bias"] = P(None, None)
        if cfg.shared_width:
            layer.update(
                s_gate=P(None, "fsdp", "tp"),
                s_up=P(None, "fsdp", "tp"),
                s_down=P(None, "tp", "fsdp"),
            )
    else:
        layer.update(
            w_gate=P(None, "fsdp", "tp"),
            w_up=P(None, "fsdp", "tp"),
            w_down=P(None, "tp", "fsdp"),
        )
    tree = {
        "embed": P("tp", "fsdp"),
        "layers": layer,
        "norm_f": P(None),
        "head": P("fsdp", "tp"),
    }
    if cfg.tie_embeddings:
        del tree["head"]
    return jax.tree_util.tree_map(
        lambda s: _filter_spec(s, mesh), tree,
        is_leaf=lambda s: isinstance(s, P))


def quantized_partition_specs(cfg: TransformerConfig, mesh: Mesh
                              ) -> Dict[str, Any]:
    """``partition_specs`` for a ``quantize_params`` tree: each quantized
    leaf becomes a QTensor of specs — ``values`` takes the weight's spec,
    ``scales`` the same minus the last dim (their trailing dim is 1, which
    cannot shard).  Place qparams with this and multi-chip sharded decode
    works exactly as with fp params (``decode_step(..., sharded=True)``).
    """
    specs = partition_specs(cfg, mesh)

    def quantized(tree):
        return {k: (quantized(v) if isinstance(v, dict) else
                    _quantized_spec(v) if _quantizable(cfg, k) else v)
                for k, v in tree.items()}

    out = {
        "embed": _quantized_spec(specs["embed"]),
        "layers": quantized(specs["layers"]),
        "norm_f": specs["norm_f"],
    }
    if "head" in specs:
        out["head"] = _quantized_spec(specs["head"])
    return out
