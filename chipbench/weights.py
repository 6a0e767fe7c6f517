"""Seeded random weights, shared by the program's build and the reference.

Every weight is a pure function of ``(seed, name, layer)``: the program's
parameter trees are filled leaf by leaf from it in one jitted call, and
the float32 reference regenerates the same values layer by layer, so it
never takes an array the program has made.  Values are drawn in float32,
scaled, and rounded to the served dtype; the reference widens that
rounded value back to float32.
"""

from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp
import numpy as np

# every matrix is normal with standard deviation fan_in ** -0.5, the
# compressor's cross-attention too: at a smaller scale its softmax over
# thousands of shot tokens is so diffuse that a prefix hardly depends on
# its task, and the comparison could not see a compressor fault
_ONES = ("scale",)  # norm gains
_ROWS = ("embed/tokens", "mem_tokens")  # rows of width d: std d ** -0.5


def root_key(seed: int) -> jax.Array:
    """A key from any whole seed (64 bits are used)."""
    seed = int(seed)
    key = jax.random.key(np.uint32(seed & 0xFFFFFFFF))
    return jax.random.fold_in(key, np.uint32((seed >> 32) & 0xFFFFFFFF))


def _std(name: str, shape) -> float:
    if name.endswith(_ROWS):
        return shape[-1] ** -0.5
    return shape[-2] ** -0.5


def leaf(key: jax.Array, name: str, layer, shape, dtype) -> jax.Array:
    """The weight ``name`` (of layer ``layer``, or None outside the layer
    stack) with per-layer ``shape``, in ``dtype``."""
    if name.rsplit("/", 1)[-1] in _ONES:
        return jnp.ones(shape, dtype)
    k = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
    if layer is not None:
        k = jax.random.fold_in(k, layer)
    x = jax.random.normal(k, shape, jnp.float32) * _std(name, shape)
    return x.astype(dtype)


def stacked(key: jax.Array, name: str, layers: int, shape, dtype):
    """``leaf`` for layers 0..layers-1, stacked on a leading axis."""
    return jax.vmap(lambda i: leaf(key, name, i, shape, dtype))(
        jnp.arange(layers, dtype=jnp.int32))
