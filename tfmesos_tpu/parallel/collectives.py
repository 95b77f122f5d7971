"""Collective helpers over the device mesh.

The reference delegates all tensor traffic to TensorFlow's gRPC runtime
(SURVEY §2.8); here the data plane is XLA collectives over ICI/DCN, and these
helpers are the small vocabulary the rest of the framework uses.  Everything
is a thin, named wrapper over ``jax.lax`` collectives so call sites read as
intent ("average gradients over dp") rather than mechanics.
"""

from __future__ import annotations

from typing import Optional, Sequence, Union

import jax
import jax.numpy as jnp
from jax.lax import axis_size

AxisName = Union[str, Sequence[str]]


def all_reduce_sum(x, axis: AxisName):
    return jax.lax.psum(x, axis_name=axis)


def all_reduce_mean(x, axis: AxisName):
    return jax.lax.pmean(x, axis_name=axis)


def grad_sync(grads, axis: AxisName):
    """Average a gradient pytree across the data-parallel axis — the GSPMD
    successor of PS apply-gradients (reference mnist_replica.py:116-157)."""
    return jax.tree_util.tree_map(lambda g: jax.lax.pmean(g, axis_name=axis), grads)


def all_gather(x, axis: AxisName, *, axis_index: int = 0, tiled: bool = True):
    return jax.lax.all_gather(x, axis_name=axis, axis=axis_index, tiled=tiled)


def reduce_scatter(x, axis: AxisName, *, axis_index: int = 0):
    return jax.lax.psum_scatter(x, axis_name=axis, scatter_dimension=axis_index,
                                tiled=True)


def broadcast_replicated_grad(x, axis: AxisName):
    """Identity forward, ``psum`` backward — the input-side twin of
    :func:`psum_replicated_grad` (Megatron's *f* operator to its *g*).

    Use it where a tp-replicated activation FANS OUT into per-shard
    compute (e.g. ``h @ w1_columns``): each shard's backward produces
    only its columns' contribution to dL/dh, and the psum in the
    transpose reassembles the full cotangent.  Needed only when the
    stage is differentiated with ``jax.vjp`` inside a ``shard_map``
    (1F1B); outer differentiation through the shard_map inserts the
    same transpose automatically."""
    @jax.custom_vjp
    def _bcast(v):
        return v

    _bcast.defvjp(lambda v: (v, None),
                  lambda _, g: (jax.lax.psum(g, axis),))
    return _bcast(x)


def psum_replicated_grad(x, axis: AxisName):
    """``psum`` whose backward is the IDENTITY — for manual-collective
    stage bodies that are differentiated with ``jax.vjp`` INSIDE a
    ``shard_map`` (the 1F1B pipeline's in-loop backward).

    Math: for y = Σ_i x_i computed on every shard, dL/dx_i = dL/dy —
    the identity — whenever downstream consumes y uniformly across the
    axis (the Megatron row-parallel case, where the cotangent is
    replicated).  Plain ``lax.psum``'s transpose under
    ``check_vma=False`` manual mode cannot assume the cotangent is
    replicated and inserts another psum, scaling gradients by the axis
    size; differentiating THROUGH the shard_map from outside (the
    gpipe/circular route) does not hit this, which is why those
    schedules use plain psum.
    """
    @jax.custom_vjp
    def _psum(v):
        return jax.lax.psum(v, axis)

    _psum.defvjp(lambda v: (jax.lax.psum(v, axis), None),
                 lambda _, g: (g,))
    return _psum(x)


def ppermute_shift(x, axis: str, shift: int = 1):
    """Rotate values around a ring axis (the building block of ring attention
    and pipeline transfer); ``shift=+1`` sends to the next-higher index."""
    n = axis_size(axis)
    perm = [(i, (i + shift) % n) for i in range(n)]
    return jax.lax.ppermute(x, axis_name=axis, perm=perm)


def axis_index(axis: str):
    return jax.lax.axis_index(axis)


def barrier(axis: AxisName):
    """Cheap cross-device barrier: reduce a scalar nobody reads."""
    return jax.lax.psum(jnp.zeros((), jnp.float32), axis_name=axis)
