"""Random weights from the seed, made on the device in one jitted call.

The tree has the layout the program's model consumes (its shapes come
from ``jax.eval_shape`` of the program's initializer, which computes no
values); every value is drawn here, so the program and the reference are
handed the same weights and neither made them.  Leaves are filled by name:
norm ``scale`` ones, norm ``bias`` zeros, embedding and head ``table``
N(0, 0.02), projection ``w`` N(0, 1/fan_in).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def key_of(seed: int):
    """A JAX PRNG key for any integer seed (wider than 32 bits too)."""
    s = seed % 2**64
    return jax.random.fold_in(jax.random.PRNGKey(s & 0x7FFFFFFF), s >> 31)


def _leaf_name(path) -> str:
    return str(getattr(path[-1], "key", path[-1]))


def _fill(key, name: str, sds):
    shape, dtype = sds.shape, sds.dtype
    if name == "scale":
        return jnp.ones(shape, dtype)
    if name == "bias":
        return jnp.zeros(shape, dtype)
    if name == "table":
        return 0.02 * jax.random.normal(key, shape, dtype)
    if name == "w":
        return jax.random.normal(key, shape, dtype) * (shape[-2] ** -0.5)
    raise ValueError(f"no rule to initialise a weight named {name!r}")


def make_weights(shapes, seed: int, *, device=None):
    """Weights for the tree of ``ShapeDtypeStruct``s ``shapes``, drawn from
    ``seed`` in one jitted call; placed on ``device`` when given."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def build(key):
        keys = jax.random.split(key, len(flat))
        return jax.tree_util.tree_unflatten(
            treedef, [_fill(k, _leaf_name(p), s)
                      for k, (p, s) in zip(keys, flat)])

    fn = jax.jit(build) if device is None else jax.jit(
        build, out_shardings=jax.sharding.SingleDeviceSharding(device))
    return fn(key_of(seed))
