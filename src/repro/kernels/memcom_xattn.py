"""MemCom layer-wise 1-head cross-attention Pallas TPU kernel.

The paper's compression hot spot: at every transformer layer, m memory
queries attend over t source-token representations with a *single* head
of width d_model — ``O = softmax(Q K^T / sqrt(D)) V`` with
Q (B, m, D), K = V (B, t, D), m ≤ 2k, t ≤ 6k+, D up to 8192.

TPU adaptation (DESIGN.md §3): a 1-head attention offers no head axis to
batch over, so a generic attention kernel would issue one (m × t) matmul
with a D-wide contraction per layer — fine for the MXU only if the tiles
are staged right. We tile it as a blocked matmul pipeline in VMEM:

* grid ``(B, nm, nt)``, the t-axis innermost/sequential (online softmax
  state in scratch), m and batch parallel;
* Q tile (bm, D) stays resident across the whole t sweep (it is the
  reused operand: every K tile contracts against it);
* K/V tiles (bt, D) stream through; logits (bm, bt) never touch HBM;
* the D-wide contraction is the MXU-friendly axis — D is a multiple of
  128 for every assigned arch (576, 960, 1024, …, 8192), so the
  (bm × D)·(D × bt) product runs at full systolic occupancy without the
  head-dim padding waste a 64/80-wide head would suffer.

VMEM: the pipeline keeps two buffers of every input and output tile
(Q, K, V and O), plus the f32 acc (bm, D) and the (bm, bt) f32 logits
temporaries.  ``_pick_blocks`` picks the largest (bm, bt) whose total fits
``_VMEM_BUDGET`` and the call raises the compiler's scoped-VMEM limit to
``_VMEM_LIMIT`` (the default scoped limit, 16 MiB on v5e, would refuse
D ≥ 2304 at useful tile sizes).  E.g. D = 2304 in bf16 gets bm = bt = 512 (25.5 MiB);
D = 8192 gets bm = 128, bt = 256 (28.4 MiB).

No mask: every memory token sees every source token (the paper's
compressor is bidirectional over the source), so padding of t is handled
with an explicit validity test on the block's global column index.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.kernels.pltpu_compat import CompilerParams as _CompilerParams
from repro.kernels.pltpu_compat import mxu_precision

NEG_INF = -1e30
_VMEM_BUDGET = 40 * 1024 * 1024
_VMEM_LIMIT = 64 * 1024 * 1024


def _xattn_kernel(q_ref, k_ref, v_ref, o_ref, acc, m_scr, l_scr,
                  *, scale: float, t_total: int, block_t: int):
    it = pl.program_id(2)
    nt = pl.num_programs(2)

    @pl.when(it == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)

    q = q_ref[0]  # (bm, D)
    k = k_ref[0]  # (bt, D)
    v = v_ref[0]  # (bt, D)
    prec = mxu_precision(q.dtype)
    logits = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), precision=prec,
        preferred_element_type=jnp.float32) * scale  # (bm, bt)
    col = it * block_t + jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
    logits = jnp.where(col < t_total, logits, NEG_INF)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, logits.max(axis=-1, keepdims=True))
    p = jnp.exp(logits - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * corr + p.sum(axis=-1, keepdims=True)
    m_scr[...] = m_new
    pv = jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())), precision=prec,
        preferred_element_type=jnp.float32)
    acc[...] = acc[...] * corr + pv

    @pl.when(it == nt - 1)
    def _finish():
        o_ref[0] = (acc[...] / jnp.maximum(l_scr[...], 1e-37)).astype(o_ref.dtype)


def _vmem_bytes(bm: int, bt: int, D: int, itemsize: int) -> int:
    """VMEM one grid step holds: double-buffered Q/O (bm, D) and K/V
    (bt, D) tiles, the f32 accumulator, and ~3 (bm, bt) f32 temporaries
    (logits, probabilities, mask)."""
    tiles = 2 * (2 * bm * D + 2 * bt * D) * itemsize
    return tiles + bm * D * 4 + 3 * bm * bt * 4


def _pick_blocks(D: int, itemsize: int) -> tuple[int, int]:
    """Largest (bm, bt) whose :func:`_vmem_bytes` fits the VMEM budget."""
    for bm, bt in ((512, 512), (256, 512), (256, 256), (128, 256),
                   (128, 128), (64, 128), (32, 128)):
        if _vmem_bytes(bm, bt, D, itemsize) <= _VMEM_BUDGET:
            return bm, bt
    return 16, 128


@functools.partial(
    jax.jit, static_argnames=("scale", "block_m", "block_t", "interpret"))
def memcom_xattn(q, k, v, *, scale=None, block_m=None, block_t=None,
                 interpret=False):
    """(B,M,D) x (B,T,D) -> (B,M,D) 1-head cross attention, no mask."""
    B, M, D = q.shape
    T = k.shape[1]
    if scale is None:
        scale = D**-0.5
    auto_m, auto_t = _pick_blocks(D, q.dtype.itemsize)
    bm = min(block_m or auto_m, max(M, 8))
    bt = min(block_t or auto_t, max(T, 8))

    pad_m = (-M) % bm
    pad_t = (-T) % bt
    qp = jnp.pad(q, ((0, 0), (0, pad_m), (0, 0))) if pad_m else q
    kp = jnp.pad(k, ((0, 0), (0, pad_t), (0, 0))) if pad_t else k
    vp = jnp.pad(v, ((0, 0), (0, pad_t), (0, 0))) if pad_t else v
    nm, nt = (M + pad_m) // bm, (T + pad_t) // bt

    kernel = functools.partial(
        _xattn_kernel, scale=scale, t_total=T, block_t=bt)
    out = pl.pallas_call(
        kernel,
        grid=(B, nm, nt),
        in_specs=[
            pl.BlockSpec((1, bm, D), lambda b, im, it: (b, im, 0)),
            pl.BlockSpec((1, bt, D), lambda b, im, it: (b, it, 0)),
            pl.BlockSpec((1, bt, D), lambda b, im, it: (b, it, 0)),
        ],
        out_specs=pl.BlockSpec((1, bm, D), lambda b, im, it: (b, im, 0)),
        out_shape=jax.ShapeDtypeStruct((B, M + pad_m, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bm, D), jnp.float32),
            pltpu.VMEM((bm, 1), jnp.float32),
            pltpu.VMEM((bm, 1), jnp.float32),
        ],
        compiler_params=_CompilerParams(
            dimension_semantics=(pltpu.PARALLEL, pltpu.PARALLEL,
                                 pltpu.ARBITRARY),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="memcom_xattn",
    )(qp, kp, vp)
    return out[:, :M]
