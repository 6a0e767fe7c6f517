"""Grouped (per-expert) matmul Pallas TPU kernel for MoE expert compute.

``(E, C, D) x (E, D, F) -> (E, C, F)`` — the inner loop of the sort-based
capacity MoE (repro/models/moe.py): tokens are already bucketed into
per-expert capacity buffers, so expert compute is a batch of E
independent matmuls.

TPU mapping: grid ``(E, nc, nf, nd)`` with the contraction (D) axis
innermost/sequential accumulating into an f32 VMEM scratch tile, and the
expert / row / column axes parallel. Blocks are MXU-shaped
(bc × bd)·(bd × bf) with 128-aligned defaults; weights tiles are the
streamed operand (a fresh (bd, bf) slab per step), activation tiles are
reused across the f-sweep.

This layout is deliberately *not* a megablocks port (DESIGN.md §3): on
TPU the capacity-buffer formulation keeps every matmul dense and
identical in shape, which the MXU pipeline rewards far more than the
variable-size group handling megablocks does for CUDA warps.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.kernels.pltpu_compat import CompilerParams as _CompilerParams
from repro.kernels.pltpu_compat import mxu_precision


def _gmm_kernel(x_ref, w_ref, o_ref, acc):
    idd = pl.program_id(3)

    @pl.when(idd == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)

    acc[...] += jax.lax.dot_general(
        x_ref[0], w_ref[0], (((1,), (0,)), ((), ())),
        precision=mxu_precision(x_ref.dtype),
        preferred_element_type=jnp.float32)

    @pl.when(idd == pl.num_programs(3) - 1)
    def _finish():
        o_ref[0] = acc[...].astype(o_ref.dtype)


def _pad(x, mult, axis):
    p = (-x.shape[axis]) % mult
    if not p:
        return x
    w = [(0, 0)] * x.ndim
    w[axis] = (0, p)
    return jnp.pad(x, w)


@functools.partial(
    jax.jit,
    static_argnames=("block_c", "block_d", "block_f", "interpret"))
def gmm(x, w, *, block_c=128, block_d=512, block_f=512, interpret=False):
    """Per-expert matmul: (E,C,D) x (E,D,F) -> (E,C,F)."""
    E, C, D = x.shape
    _, _, F = w.shape
    bc = min(block_c, max(C, 8))
    bd = min(block_d, max(D, 8))
    bf = min(block_f, max(F, 8))
    xp = _pad(_pad(x, bc, 1), bd, 2)
    wp = _pad(_pad(w, bd, 1), bf, 2)
    Cp, Dp = xp.shape[1], xp.shape[2]
    Fp = wp.shape[2]

    out = pl.pallas_call(
        _gmm_kernel,
        grid=(E, Cp // bc, Fp // bf, Dp // bd),
        in_specs=[
            pl.BlockSpec((1, bc, bd), lambda e, ic, jf, kd: (e, ic, kd)),
            pl.BlockSpec((1, bd, bf), lambda e, ic, jf, kd: (e, kd, jf)),
        ],
        out_specs=pl.BlockSpec((1, bc, bf), lambda e, ic, jf, kd: (e, ic, jf)),
        out_shape=jax.ShapeDtypeStruct((E, Cp, Fp), x.dtype),
        scratch_shapes=[pltpu.VMEM((bc, bf), jnp.float32)],
        compiler_params=_CompilerParams(
            dimension_semantics=(pltpu.PARALLEL, pltpu.PARALLEL,
                                 pltpu.PARALLEL, pltpu.ARBITRARY)),
        interpret=interpret,
    )(xp, wp)
    return out[:, :C, :F]
