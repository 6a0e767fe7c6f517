"""Single import point for the Pallas TPU compiler settings every kernel
shares.

Every kernel in this package imports ``CompilerParams`` from here (the
``pltpu-compat`` lint rule checks it), so a future rename in jax is a
one-line change.
"""

from __future__ import annotations

import jax
import jax.experimental.pallas.tpu as pltpu
import jax.numpy as jnp

CompilerParams = pltpu.CompilerParams


def mxu_precision(dtype):
    """Matmul precision for in-kernel ``dot_general`` on ``dtype``
    operands: float32 gets full-precision MXU passes, because the default
    rounds it to bfloat16 (measured on a TPU v5e: the f32 kernels then
    matched the bf16 ones' error, ~4e-3 against the f32 oracle)."""
    return jax.lax.Precision.HIGHEST if dtype == jnp.float32 else None
