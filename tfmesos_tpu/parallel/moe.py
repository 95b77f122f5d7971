"""Expert parallelism: switch/top-k MoE with a real all_to_all data path.

The flagship transformer's default MoE computes every expert densely and
masks (models/transformer.py:_moe) — exact but O(E) FLOPs.  This module is
the scalable path: top-k routing (k=1 switch-style by default) with a
capacity limit, experts sharded over the ``ep`` mesh axis, and tokens
physically exchanged with two ``lax.all_to_all`` hops (dispatch to expert
owners, combine back) so each device computes only its own experts.  This is
the standard TPU MoE layout: the all_to_alls ride ICI and the per-expert
matmuls stay dense and MXU-shaped ``[capacity, d] x [d, f]``.

Semantics (shared by the naive reference and the sharded path, so they are
bit-comparable in tests): each token takes its top-k experts; an assignment
lands if it arrives within the expert's capacity, with slot priority by
choice rank then batch order (all first choices beat any second choice);
kept assignments are weighted by the router probability (renormalized over
the top-k when k > 1, raw switch-style when k == 1); dropped assignments
contribute zero (the residual stream carries the token).

Router health is surfaced rather than assumed: ``return_aux=True`` yields
the standard auxiliary load-balance loss (E·Σ_e f_e·P_e — 1.0 at perfect
balance), the router z-loss (mean log²-sum-exp, which keeps logits from
drifting into saturation), and the realized token-overflow fraction.
"""

from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.lax import axis_size
from jax.sharding import Mesh, PartitionSpec as P


def _routing(x, router_w, n_experts: int, capacity: int, top_k: int = 1
             ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Shared routing math.

    Returns ``combine`` [n, E, C] — fp32 gate weight of each kept
    (token, expert, slot) assignment (the dispatch mask is ``combine > 0``)
    — and the aux metrics dict.
    """
    logits = (x @ router_w).astype(jnp.float32)              # [n, E]
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, top_k)               # [n, k]
    if top_k == 1:
        gates = top_p                                        # switch: raw prob
    else:
        gates = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    onehot = jax.nn.one_hot(top_e, n_experts, dtype=jnp.float32)  # [n, k, E]
    # Slot assignment with choice priority: cumsum in choice-major order so
    # every token's first choice outranks any token's second choice.
    n = x.shape[0]
    flat = onehot.transpose(1, 0, 2).reshape(top_k * n, n_experts)
    pos_flat = jnp.cumsum(flat, axis=0) * flat - 1.0
    pos = pos_flat.reshape(top_k, n, n_experts).transpose(1, 0, 2)  # [n,k,E]
    keep = (pos >= 0.0) & (pos < capacity)
    slot = jnp.clip(pos, 0, capacity - 1).astype(jnp.int32)
    combine = jnp.sum(
        onehot[..., None]
        * jax.nn.one_hot(slot, capacity, dtype=jnp.float32)
        * keep[..., None].astype(jnp.float32)
        * gates[..., None, None],
        axis=1)                                              # [n, E, C]

    # Aux stats over the PRE-capacity assignment (the load balance you want
    # to fix is visible before the capacity limit starts dropping tokens).
    f = jnp.sum(onehot, axis=(0, 1)) / (n * top_k)           # assignment frac
    p_mean = jnp.mean(probs, axis=0)                         # mean router prob
    aux = {
        "load_balance_loss": n_experts * jnp.sum(f * p_mean),
        "z_loss": jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2),
        "overflow_frac": 1.0 - jnp.sum(keep) / (n * top_k),
    }
    return combine, aux


def _expert_ffn(tokens, w_gate, w_up, w_down, compute_dtype):
    """Per-expert SwiGLU over [E_loc, C', d] token blocks."""
    t = tokens.astype(compute_dtype)
    g = jax.nn.silu(jnp.einsum("ecd,edf->ecf", t, w_gate.astype(compute_dtype)))
    u = jnp.einsum("ecd,edf->ecf", t, w_up.astype(compute_dtype))
    return jnp.einsum("ecf,efd->ecd", g * u, w_down.astype(compute_dtype))


def _capacity(n_tokens: int, n_experts: int, factor: float,
              top_k: int = 1) -> int:
    return max(1, math.ceil(n_tokens * top_k * factor / n_experts))


def switch_moe_reference(x, router_w, w_gate, w_up, w_down,
                         capacity_factor: float = 1.25, top_k: int = 1,
                         return_aux: bool = False):
    """Naive single-device top-k MoE (ground truth for the sharded path).

    x: [n, d]; router_w: [d, E]; w_gate/w_up: [E, d, f]; w_down: [E, f, d].
    """
    n, d = x.shape
    e = router_w.shape[-1]
    capacity = _capacity(n, e, capacity_factor, top_k)
    combine, aux = _routing(x, router_w, e, capacity, top_k)
    dispatch = (combine > 0.0).astype(jnp.float32)
    expert_in = jnp.einsum("nec,nd->ecd", dispatch, x.astype(jnp.float32))
    expert_out = _expert_ffn(expert_in, w_gate, w_up, w_down, x.dtype)
    out = jnp.einsum("nec,ecd->nd", combine,
                     expert_out.astype(jnp.float32)).astype(x.dtype)
    return (out, aux) if return_aux else out


def switch_moe_local(x, router_w, w_gate, w_up, w_down, axis: str = "ep",
                     capacity_factor: float = 1.25, top_k: int = 1):
    """Per-device body (call inside shard_map): tokens local [n_loc, d],
    experts local [E/ep, d, f]; two all_to_all hops move token blocks to
    their expert owners and back.  Returns (out, aux) with aux scalars
    averaged over the ``axis`` group (callers pmean the data axes)."""
    ep = axis_size(axis)
    n_loc, d = x.shape
    e_loc = w_gate.shape[0]
    e = e_loc * ep
    capacity = _capacity(n_loc, e, capacity_factor, top_k)

    combine, aux = _routing(x, router_w, e, capacity, top_k)  # [n, E, C]
    dispatch = (combine > 0.0).astype(jnp.float32)
    expert_in = jnp.einsum("nec,nd->ecd", dispatch,
                           x.astype(jnp.float32))            # [E, C, d]

    # Hop 1: split the expert dim across the ring; device p receives, from
    # every peer, the token blocks destined for ITS experts.  tiled=True
    # keeps ranks stable (shape[split] /= ep, shape[concat] *= ep) and has a
    # well-defined transpose for the backward pass.
    blocks = expert_in.reshape(ep, e_loc, capacity, d)
    received = jax.lax.all_to_all(blocks, axis, split_axis=0, concat_axis=2,
                                  tiled=True)
    # received: [1, e_loc, ep*C, d], capacity axis grouped by source device.
    received = received.reshape(e_loc, ep * capacity, d)

    out = _expert_ffn(received, w_gate, w_up, w_down, x.dtype)  # [e_loc, ep*C, d]

    # Hop 2: send each source device its processed block back.
    out = out.astype(jnp.float32).reshape(e_loc, ep, capacity, d)
    out = jnp.moveaxis(out, 1, 0)                            # [ep, e_loc, C, d]
    returned = jax.lax.all_to_all(out, axis, split_axis=0, concat_axis=0,
                                  tiled=True)
    # returned: [ep, e_loc, C, d] indexed by expert-owner rank — i.e.
    # [E, C, d] in global expert order for my local tokens.
    returned = returned.reshape(e, capacity, d)

    combined = jnp.einsum("nec,ecd->nd", combine, returned)
    aux = {k: jax.lax.pmean(v, axis) for k, v in aux.items()}
    return combined.astype(x.dtype), aux


def switch_moe_replicated_local(x, router_w, w_gate, w_up, w_down,
                                ep_axis: str = None,
                                capacity_factor: float = 1.25,
                                top_k: int = 1, tp_axis: str = None):
    """Capacity MoE for ep-REPLICATED tokens (the pipeline-stage layout).

    Inside ``pipeline_apply`` activations replicate over ``ep`` while the
    expert weights shard over it, so no all_to_all is needed: every device
    already holds every token, computes the capacity slots of its LOCAL
    experts only, and the partial outputs ``psum`` over ``ep``.  Same
    routing semantics as ``switch_moe_local`` (slot priority, capacity
    drops, gate weighting); the router weight must be replicated so every
    device sees the full [n, E] logits.  ``ep_axis=None`` runs all experts
    locally (pp without ep).  ``tp_axis`` additionally shards every
    expert's FFN width (w_gate/w_up [e_loc, d, f/tp], w_down
    [e_loc, f/tp, d]) — the w_down contraction yields a partial sum, so
    one psum covers both axes.  Returns (out, aux); aux is identical
    across the ep/tp groups by construction.
    """
    if not ep_axis and not tp_axis:
        return switch_moe_reference(x, router_w, w_gate, w_up, w_down,
                                    capacity_factor, top_k=top_k,
                                    return_aux=True)
    n, d = x.shape
    e_loc = w_gate.shape[0]
    e = e_loc * (axis_size(ep_axis) if ep_axis else 1)
    capacity = _capacity(n, e, capacity_factor, top_k)
    combine, aux = _routing(x, router_w, e, capacity, top_k)  # [n, E, C]
    if ep_axis:
        idx = jax.lax.axis_index(ep_axis)
        combine = jax.lax.dynamic_slice_in_dim(combine, idx * e_loc, e_loc,
                                               axis=1)       # [n, e_loc, C]
    dispatch = (combine > 0.0).astype(jnp.float32)
    expert_in = jnp.einsum("nec,nd->ecd", dispatch, x.astype(jnp.float32))
    expert_out = _expert_ffn(expert_in, w_gate, w_up, w_down, x.dtype)
    out = jnp.einsum("nec,ecd->nd", combine, expert_out.astype(jnp.float32))
    psum_axes = tuple(a for a in (ep_axis, tp_axis) if a)
    return jax.lax.psum(out, psum_axes).astype(x.dtype), aux


def switch_moe(x, router_w, w_gate, w_up, w_down, mesh: Mesh,
               axis: str = "ep", capacity_factor: float = 1.25,
               top_k: int = 1, return_aux: bool = False):
    """Sharded entry point: x [n, d] sharded over the data axes, experts
    sharded over ``axis``.  Falls back to the reference when the mesh has no
    (non-trivial) ``axis``."""
    if axis not in mesh.shape or mesh.shape[axis] == 1:
        return switch_moe_reference(x, router_w, w_gate, w_up, w_down,
                                    capacity_factor, top_k=top_k,
                                    return_aux=return_aux)
    from tfmesos_tpu.parallel.sharding import data_axes
    batch = data_axes(mesh)
    dspec = P(batch, None)
    espec = P(axis, None, None)
    batch_names = (tuple(a for a in (batch if isinstance(batch, tuple)
                                     else (batch,)) if a)
                   if batch is not None else ())

    def body(x_, r_, g_, u_, dn_):
        out, aux = switch_moe_local(x_, r_, g_, u_, dn_, axis=axis,
                                    capacity_factor=capacity_factor,
                                    top_k=top_k)
        if batch_names:
            aux = {k: jax.lax.pmean(v, batch_names) for k, v in aux.items()}
        return out, aux

    fn = shard_map(
        body, mesh=mesh,
        in_specs=(dspec, P(None, None), espec, espec, espec),
        out_specs=(dspec, {k: P() for k in ("load_balance_loss", "z_loss",
                                            "overflow_frac")}),
        check_vma=False)
    out, aux = fn(x, router_w, w_gate, w_up, w_down)
    return (out, aux) if return_aux else out
