"""Random weights from the seed, made on the device in one jitted call.

The program says only which leaves its parameter tree has, their shapes
and their shardings; the values come from here.  Every matrix is drawn
with standard deviation 1/sqrt(fan in), so each layer adds to the residual
stream at about the scale of the stream itself and attention scores stay
near unit scale.  Random layers at that scale keep rounding differences
from growing much with depth, which is what lets a comparison with the
float32 reference see a fault and not only rounding.

Calling `make` twice with the same seed, tree and shardings gives the
same arrays bit for bit: the reference makes its own copy that way,
after the program's state is freed.
"""
from __future__ import annotations

import math
import zlib

import jax
import jax.numpy as jnp


def key_of(seed: int) -> jax.Array:
    """A key for any whole number up to 2**63, not only 32-bit seeds."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


def _leaf(path: tuple, shape: tuple, key: jax.Array, dtype, stacked: bool):
    name = path[-1]
    k = jax.random.fold_in(key, zlib.crc32("/".join(path).encode()))
    if name.endswith("norm"):
        return jnp.ones(shape, dtype)
    if name == "tok":
        return jax.random.normal(k, shape, jnp.float32).astype(dtype)
    own = shape[1:] if stacked else shape
    fan_in = own[0] if len(own) > 1 else 1
    std = 1.0 / math.sqrt(fan_in)
    return (std * jax.random.normal(k, shape, jnp.float32)).astype(dtype)


def _path(p) -> tuple:
    return tuple(str(getattr(e, "key", getattr(e, "idx", e))) for e in p)


def make(shapes, seed: int, shardings=None, dtype=jnp.bfloat16):
    """Arrays for the tree of ``jax.ShapeDtypeStruct`` ``shapes``.

    A leaf under a top-level ``layers`` key is a stack of per-layer
    weights along its first axis; its fan in is read past that axis."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    paths = [_path(p) for p, _ in flat]

    def build(key):
        leaves = [_leaf(p, s.shape, key, dtype, p[0] == "layers")
                  for p, (_, s) in zip(paths, flat)]
        return jax.tree_util.tree_unflatten(treedef, leaves)

    fn = jax.jit(build, out_shardings=shardings)
    return fn(key_of(seed))
