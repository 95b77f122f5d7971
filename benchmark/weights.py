"""The benchmark's own weights: made on the device from ``--seed`` in one
jitted call, in the type they are served in, in the layout
``tfmesos_tpu.models.transformer`` takes (stacked ``[L, ...]`` leaves).

They are the benchmark's, not the program's: the program under test and
the plain reference (``reference.py``) are both handed these arrays, and
neither makes any.  The scales are the usual ones (1/sqrt(fan_in), the
residual outputs by a further 1/sqrt(2 L)), so that activations keep unit
scale through the depth and logits come out near N(0, 1).

The generator is JAX's ``rbg`` key (the chip's own bit generator): drawing
3.8 G values through threefry took 26 s of every run's set-up on the v5e.
"""

from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

from benchmark import costs


def shapes(model: Dict[str, Any]) -> Dict[str, Any]:
    d, heads, kv, hd, f, l, v = costs.dims(model)
    res = 1.0 / math.sqrt(2 * l)
    return {
        "embed": ((v, d), 1.0),
        "head": ((d, v), 1 / math.sqrt(d)),
        "layers": {
            "wq": ((l, d, heads * hd), 1 / math.sqrt(d)),
            "wk": ((l, d, kv * hd), 1 / math.sqrt(d)),
            "wv": ((l, d, kv * hd), 1 / math.sqrt(d)),
            "wo": ((l, heads * hd, d), res / math.sqrt(heads * hd)),
            "w_gate": ((l, d, f), 1 / math.sqrt(d)),
            "w_up": ((l, d, f), 1 / math.sqrt(d)),
            "w_down": ((l, f, d), res / math.sqrt(f)),
        },
    }


def make_weights(model: Dict[str, Any], seed: int, dtype=jnp.bfloat16,
                 out_shardings=None):
    """The whole tree in one jitted call.  Norm gains are drawn near 1 (not
    exactly 1) so that a path which forgot them would show."""
    sh = shapes(model)
    d, l = costs.dims(model).d, costs.dims(model).layers

    def build(key):
        keys = iter(jax.random.split(key, 16))

        def draw(shape_scale):
            # stacked leaves are drawn a layer at a time, so that the
            # generator's raw bits never stand beside the whole tree
            shape, scale = shape_scale
            k = next(keys)
            if len(shape) == 3:
                x = jax.lax.map(
                    lambda kk: jax.random.normal(kk, shape[1:], dtype),
                    jax.random.split(k, shape[0]))
            else:
                x = jax.random.normal(k, shape, dtype)
            return x * jnp.asarray(scale, dtype)

        def gain(shape):
            return (1.0 + 0.1 * jax.random.normal(next(keys), shape,
                                                  jnp.float32)).astype(dtype)

        layers = {k: draw(v) for k, v in sorted(sh["layers"].items())}
        layers["attn_norm"] = gain((l, d))
        layers["mlp_norm"] = gain((l, d))
        return {"embed": draw(sh["embed"]), "layers": layers,
                "norm_f": gain((d,)), "head": draw(sh["head"])}

    key = jax.random.key(int(seed) % (2 ** 63), impl="rbg")
    fn = jax.jit(build, out_shardings=out_shardings)
    return fn(key)
