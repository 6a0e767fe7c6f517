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

Row counts (``counts``, one per expert, scalar-prefetched): an expert's
buffer holds its rows first, so a row tile at or past its count is
padding.  Such a grid step computes nothing and points every block at
the step before it (or, before any live step, at the first live one),
so it moves no data either: the matmuls cost the routed rows padded to
the row tile, not the whole buffer.  The rows of a skipped tile are left
unwritten; the caller reads only the first ``counts[e]`` rows.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.kernels.pltpu_compat import CompilerParams as _CompilerParams
from repro.kernels.pltpu_compat import mxu_precision


def _gmm_step(x_ref, w_ref, o_ref, acc, idd, nd):
    @pl.when(idd == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)

    acc[...] += jax.lax.dot_general(
        x_ref[0], w_ref[0], (((1,), (0,)), ((), ())),
        precision=mxu_precision(x_ref.dtype),
        preferred_element_type=jnp.float32)

    @pl.when(idd == nd - 1)
    def _finish():
        o_ref[0] = acc[...].astype(o_ref.dtype)


def _gmm_kernel(x_ref, w_ref, o_ref, acc):
    _gmm_step(x_ref, w_ref, o_ref, acc, pl.program_id(3), pl.num_programs(3))


def _gmm_counted_kernel(live_ref, src_e, src_c, tail, x_ref, w_ref, o_ref,
                        acc):
    # grid indices are read out here: a branch may not ask for them
    step = pl.program_id(0) * pl.num_programs(1) + pl.program_id(1)
    idd, nd = pl.program_id(3), pl.num_programs(3)

    @pl.when(live_ref[step] == 1)
    def _live():
        _gmm_step(x_ref, w_ref, o_ref, acc, idd, nd)


def _tile(n: int, cap: int) -> int:
    """Block length along a dim of ``n``: the whole dim when it fits
    ``cap``, else the largest multiple of 128 up to ``cap`` dividing
    ``n`` (no padding, so no copy of a weight per call), else ``cap``."""
    if n <= cap:
        return max(n, 8)
    fits = [t for t in range(128, cap + 1, 128) if n % t == 0]
    return fits[-1] if fits else cap


def _pad(x, mult, axis):
    p = (-x.shape[axis]) % mult
    if not p:
        return x
    w = [(0, 0)] * x.ndim
    w[axis] = (0, p)
    return jnp.pad(x, w)


def _row_block(C: int, block_c: int = 256) -> int:
    """Rows of one grid step for an ``(E, C, D)`` buffer."""
    return min(block_c, max(C, 8))


def _skip_plan(counts, E: int, nc: int, bc: int):
    """Per grid step ``(e, ic)``, flattened: whether it is live, the
    ``(expert, row tile)`` its blocks point at, and whether a dead step
    follows a live one (its inner blocks then stay at the last ones)."""
    steps = jnp.arange(E * nc, dtype=jnp.int32)
    live = (steps % nc) * bc < jnp.repeat(counts.astype(jnp.int32), nc)
    last = jax.lax.cummax(jnp.where(live, steps, -1))
    src = jnp.where(last >= 0, last, jnp.argmax(live).astype(jnp.int32))
    return (live.astype(jnp.int32), src // nc, src % nc,
            (last >= 0).astype(jnp.int32))


@functools.partial(
    jax.jit,
    static_argnames=("block_c", "block_d", "block_f", "interpret"))
def gmm(x, w, counts=None, *, block_c=256, block_d=512, block_f=512,
        interpret=False):
    """Per-expert matmul: (E,C,D) x (E,D,F) -> (E,C,F).  Blocks divide D
    and F where a multiple of 128 does (DeepSeek's 1,408-wide experts take
    whole-width blocks), so the weights are read as they lie.  With
    ``counts`` (E,) int32 only each expert's first ``counts[e]`` rows are
    computed (the rest of the output is undefined)."""
    E, C, D = x.shape
    _, _, F = w.shape
    bc = _row_block(C, block_c)
    bd = _tile(D, max(block_d, 1536))
    bf = _tile(F, max(block_f, 1536))
    xp = _pad(_pad(x, bc, 1), bd, 2)
    wp = _pad(_pad(w, bd, 1), bf, 2)
    Cp, Dp = xp.shape[1], xp.shape[2]
    Fp = wp.shape[2]
    nc, nf, nd = Cp // bc, Fp // bf, Dp // bd
    out_shape = jax.ShapeDtypeStruct((E, Cp, Fp), x.dtype)
    scratch = [pltpu.VMEM((bc, bf), jnp.float32)]

    if counts is None:
        out = pl.pallas_call(
            _gmm_kernel,
            grid=(E, nc, nf, nd),
            in_specs=[
                pl.BlockSpec((1, bc, bd), lambda e, ic, jf, kd: (e, ic, kd)),
                pl.BlockSpec((1, bd, bf), lambda e, ic, jf, kd: (e, kd, jf)),
            ],
            out_specs=pl.BlockSpec((1, bc, bf),
                                   lambda e, ic, jf, kd: (e, ic, jf)),
            out_shape=out_shape,
            scratch_shapes=scratch,
            compiler_params=_CompilerParams(
                dimension_semantics=(pltpu.PARALLEL, pltpu.PARALLEL,
                                     pltpu.PARALLEL, pltpu.ARBITRARY)),
            interpret=interpret,
            name="expert_gmm",
        )(xp, wp)
        return out[:, :C, :F]

    def at(e, ic, jf, kd, live, src_e, src_c, tail):
        """A step's (expert, row tile, f block, d block): its own when
        live, else the neighbouring live step's, so no block moves."""
        s = e * nc + ic
        a, t = live[s], tail[s]
        return (src_e[s], src_c[s], a * jf + (1 - a) * t * (nf - 1),
                a * kd + (1 - a) * t * (nd - 1))

    def x_map(*g):
        e, c, _, k = at(*g)
        return e, c, k

    def w_map(*g):
        e, _, f, k = at(*g)
        return e, k, f

    def o_map(*g):
        e, c, f, _ = at(*g)
        return e, c, f

    plan = _skip_plan(counts, E, nc, bc)
    out = pl.pallas_call(
        _gmm_counted_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(plan),
            grid=(E, nc, nf, nd),
            in_specs=[pl.BlockSpec((1, bc, bd), x_map),
                      pl.BlockSpec((1, bd, bf), w_map)],
            out_specs=pl.BlockSpec((1, bc, bf), o_map),
            scratch_shapes=scratch,
        ),
        out_shape=out_shape,
        # a dead step leans on its neighbour's blocks: the steps run in
        # order on one core
        compiler_params=_CompilerParams(
            dimension_semantics=(pltpu.ARBITRARY,) * 4),
        interpret=interpret,
        name="expert_gmm",
    )(*plan, xp, wp)
    return out[:, :C, :F]
