"""Ulysses-style sequence parallelism: all-to-all over the ``sp`` axis.

The second of the two standard long-context strategies (beside ring
attention — the reference has neither, SURVEY §2.7/§5).  Activations arrive
sequence-sharded ``[B, T/sp, H, D]``; one ``all_to_all`` re-shards them from
the sequence dim to the heads dim, so every device runs EXACT attention over
the full sequence for its ``H/sp`` heads; a second ``all_to_all`` swaps the
sharding back.  Per device that is two a2a hops per attention call (three —
q, stacked K/V, output — on the grouped-query path, which moves H/KV-fold
fewer K/V bytes in exchange) versus the ring's ``sp`` ppermute hops —
cheaper on ICI whenever heads divide evenly — while the flash kernel sees
full-length sequences (its causal block skipping works globally, where the
ring must mask per shard).

Trade-offs vs ring attention (both exact):

* Ulysses needs ``n_heads % sp == 0``; the ring has no head constraint.
* Ulysses peak activation is O(T) per device for 1/sp of the heads (the
  full-sequence view exists only inside the attention call); the ring
  keeps everything at O(T/sp).  For sequences that fit, Ulysses wins on
  collective volume; for extreme lengths the ring is the memory-safe pick.
* A2a rides ICI as one fused collective; the ring pipelines hops behind
  compute.  Measure on the target topology; model code
  flips with ``TransformerConfig(sp_impl="ulysses")``.
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.lax import axis_size
from jax.sharding import Mesh, PartitionSpec as P

from tfmesos_tpu.parallel.sharding import data_axes


def ulysses_attention_local(q, k, v, axis: str = "sp", causal: bool = True,
                            scale: Optional[float] = None,
                            interpret: bool = False,
                            use_pallas: Optional[bool] = None,
                            window: Optional[int] = None):
    """Per-device body (call inside ``shard_map`` with ``axis`` in scope).

    Local shapes ``[B, T/sp, H, D]`` in, same out.  ``all_to_all`` with
    ``tiled=True`` splits the head dim across the group and concatenates
    the gathered sequence shards — after the hop each device holds
    ``[B, T, H/sp, D]`` and attention is an ordinary single-device call
    (the Pallas flash kernel on TPU) — so a sliding ``window`` passes
    straight through to it.
    """
    from tfmesos_tpu.ops.attention import flash_attention

    sp = axis_size(axis)
    h, hk = q.shape[2], k.shape[2]
    if h % sp:
        raise ValueError(f"ulysses needs heads ({h}) divisible by the sp "
                         f"axis ({sp}); use ring attention instead")

    if hk != h and hk % sp == 0:
        # GQA at kv width: separate hops for q and the stacked K/V pair —
        # the K/V a2a moves h/hk-fold fewer bytes, and splitting both head
        # dims sp-ways keeps local grouping aligned with the global
        # mapping (q head s·H/sp + j ↔ kv head s·KV/sp + j//g), which the
        # GQA-native flash kernel consumes directly.
        qh = jax.lax.all_to_all(q, axis, split_axis=2, concat_axis=1,
                                tiled=True)
        kv = jax.lax.all_to_all(jnp.stack((k, v)), axis, split_axis=3,
                                concat_axis=2, tiled=True)
        o = flash_attention(qh, kv[0], kv[1], causal=causal, scale=scale,
                            interpret=interpret, use_pallas=use_pallas,
                            window=window)
        return jax.lax.all_to_all(o, axis, split_axis=1, concat_axis=2,
                                  tiled=True)

    if hk != h:
        # GQA with sp not dividing kv_heads: broadcast up first.
        k = jnp.repeat(k, h // hk, axis=2)
        v = jnp.repeat(v, h // hk, axis=2)
    # One stacked hop for q/k/v (dims shift by the stack dim), one for the
    # output — the documented two collectives per attention call.
    qkv = jax.lax.all_to_all(jnp.stack((q, k, v)), axis, split_axis=3,
                             concat_axis=2, tiled=True)
    qh, kh, vh = qkv[0], qkv[1], qkv[2]
    o = flash_attention(qh, kh, vh, causal=causal, scale=scale,
                        interpret=interpret, use_pallas=use_pallas,
                        window=window)
    return jax.lax.all_to_all(o, axis, split_axis=1, concat_axis=2,
                              tiled=True)


def ulysses_attention(q, k, v, mesh: Mesh, axis: str = "sp",
                      causal: bool = True, scale: Optional[float] = None,
                      interpret: bool = False,
                      use_pallas: Optional[bool] = None,
                      window: Optional[int] = None):
    """Sharded entry point: q/k/v are global ``[B, T, H, D]`` arrays with T
    sharded over ``axis``; falls back to plain flash/reference attention
    when the mesh has no (non-trivial) ``axis``."""
    from tfmesos_tpu.ops.attention import flash_attention

    if axis not in mesh.shape or mesh.shape[axis] == 1:
        return flash_attention(q, k, v, causal=causal, scale=scale,
                               interpret=interpret, use_pallas=use_pallas,
                               window=window)
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    spec = P(data_axes(mesh), axis, None, None)
    body = lambda q_, k_, v_: ulysses_attention_local(
        q_, k_, v_, axis=axis, causal=causal, scale=scale,
        interpret=interpret, use_pallas=use_pallas, window=window)
    fn = shard_map(body, mesh=mesh, in_specs=(spec, spec, spec),
                       out_specs=spec, check_vma=False)
    return fn(q, k, v)
