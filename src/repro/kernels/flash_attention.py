"""Flash attention Pallas TPU kernel (GQA, position-masked, online softmax).

The one kernel behind every attention call in the framework: causal
self-attention (train/prefill), prefix attention (MemCom memory slots),
decode (1 query row against a long cache), and enc-dec cross attention —
all expressed through the (q_pos, kv_pos) contract of
:func:`repro.kernels.ref.attention_ref`.

TPU mapping
-----------
The wrapper moves heads in front of the sequence (``(B, H, S, D)``), so
every block's last two dims are a (rows, head-width) tile: rows a
multiple of 8, the head width whole.  That is the layout Mosaic's (8, 128)
tiling rule accepts for every head width (64/80/128/256 pad to lanes once
per tile), batch size and GQA group.

Grid ``(B, Hq, nq, nk)`` — the KV-block axis is innermost and
``ARBITRARY`` (sequential) so the online-softmax state for one (batch,
head, q-block) lives in VMEM scratch across its KV sweep; batch/head/
q-block axes are ``PARALLEL``. Blocks:

* q/o   (bq, D)  — one head's query tile.
* k/v   (bk, D)  — indexed by ``h // G`` (GQA: G q-heads share one KV
  head, so consecutive q-heads reuse the same KV tile).
* q positions ``(bq, 1)`` and kv positions ``(1, bk)`` int32 — a column
  and a row, so the causal test ``kv_pos <= q_pos`` broadcasts to the
  logits tile with no transpose.  Decode, sliding windows and MemCom's
  "memory slots visible to everyone" all reduce to position vectors, no
  mask tensors in HBM.
* lse   (bq, 1) f32 per head.

``bk`` must be a multiple of 128 when the KV axis spans several blocks
(the kv-position row is lane-tiled); the default 512 is.

Scratch: acc (bq, D) f32, running max m and sum l (bq, 1) f32.

Block-level skip: a KV block whose minimum kv_pos exceeds the block's
maximum q_pos contributes nothing under the causal mask — `pl.when`
skips its matmuls (the flash causal ~2× FLOP saving, decided from the
loaded position tiles, so it also fires for decode where q_pos is a
cache offset, not a diagonal).
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


def _attn_kernel(
    q_pos_ref, kv_pos_ref, q_ref, k_ref, v_ref,  # inputs
    o_ref, lse_ref,  # outputs
    acc, m_scr, l_scr,  # scratch
    *, scale: float, causal: bool, softcap: float,
):
    ik = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)

    q_pos = q_pos_ref[...]  # (bq, 1) int32
    kv_pos = kv_pos_ref[...]  # (1, bk) int32

    def compute():
        q = q_ref[...]  # (bq, D)
        k = k_ref[...]  # (bk, D)
        v = v_ref[...]  # (bk, Dv)
        prec = mxu_precision(q.dtype)
        logits = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), precision=prec,
            preferred_element_type=jnp.float32) * scale
        if softcap:
            logits = softcap * jnp.tanh(logits / softcap)
        valid = kv_pos >= 0
        if causal:
            valid = valid & (kv_pos <= q_pos)
        valid = jnp.broadcast_to(valid, logits.shape)
        logits = jnp.where(valid, logits, NEG_INF)

        m_prev = m_scr[...]  # (bq, 1)
        m_new = jnp.maximum(m_prev, logits.max(axis=-1, keepdims=True))
        p = jnp.where(valid, jnp.exp(logits - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)  # (bq, 1)
        l_scr[...] = l_scr[...] * corr + p.sum(axis=-1, keepdims=True)
        m_scr[...] = m_new
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())), precision=prec,
            preferred_element_type=jnp.float32)
        acc[...] = acc[...] * corr + pv

    if causal:
        # skip blocks strictly above the causal frontier (padding slots
        # carry kv_pos == -1 and never raise the block minimum)
        kv_lo = jnp.where(kv_pos >= 0, kv_pos, jnp.int32(2**30)).min()
        pl.when(kv_lo <= q_pos.max())(compute)
    else:
        compute()

    @pl.when(ik == nk - 1)
    def _finish():
        l = l_scr[...]
        out = acc[...] / jnp.maximum(l, 1e-37)
        o_ref[...] = jnp.where(l > 0, out, 0.0).astype(o_ref.dtype)
        lse_ref[...] = jnp.where(
            l > 0, m_scr[...] + jnp.log(jnp.maximum(l, 1e-37)), NEG_INF)


def _pad_to(x, mult, axis, value=0):
    pad = (-x.shape[axis]) % mult
    if not pad:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


def _round_up(n: int, mult: int) -> int:
    return -(-n // mult) * mult


@functools.partial(
    jax.jit,
    static_argnames=("causal", "softcap", "scale", "block_q", "block_k",
                     "return_lse", "interpret"),
)
def flash_attention(
    q, k, v, *, q_pos, kv_pos, causal=True, softcap=0.0, scale=None,
    block_q=512, block_k=512, return_lse=False, interpret=False,
):
    """(B,Sq,Hq,D) x (B,Skv,Hkv,D) -> (B,Sq,Hq,Dv) [, lse (B,Sq,Hq)]."""
    B, Sq, Hq, D = q.shape
    _, Skv, Hkv, Dv = v.shape
    assert Hq % Hkv == 0, (Hq, Hkv)
    G = Hq // Hkv
    if scale is None:
        scale = D**-0.5

    # row blocks are multiples of 8 (the sublane tile); a sequence shorter
    # than the block is padded to one block
    bq = min(block_q, _round_up(Sq, 8))
    bk = min(block_k, _round_up(Skv, 8))
    # head-major: (B, H, S, D)
    qp = _pad_to(q, bq, axis=1).transpose(0, 2, 1, 3)
    kp = _pad_to(k, bk, axis=1).transpose(0, 2, 1, 3)
    vp = _pad_to(v, bk, axis=1).transpose(0, 2, 1, 3)
    # padded q rows: positions below every valid kv so causal masks all;
    # padded kv slots: -1 marks invalid under both mask kinds
    q_pos_p = _pad_to(q_pos.astype(jnp.int32), bq, axis=1,
                      value=-(2**30))[:, :, None]  # (B, Sqp, 1)
    kv_pos_p = _pad_to(kv_pos.astype(jnp.int32), bk, axis=1,
                       value=-1)[:, None, :]  # (B, 1, Skvp)
    Sqp, Skvp = qp.shape[2], kp.shape[2]
    nq, nk = Sqp // bq, Skvp // bk

    kernel = functools.partial(
        _attn_kernel, scale=scale, causal=causal, softcap=softcap)
    out, lse = pl.pallas_call(
        kernel,
        grid=(B, Hq, nq, nk),
        in_specs=[
            pl.BlockSpec((None, bq, 1), lambda b, h, iq, ik: (b, iq, 0)),
            pl.BlockSpec((None, 1, bk), lambda b, h, iq, ik: (b, 0, ik)),
            pl.BlockSpec((None, None, bq, D),
                         lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((None, None, bk, D),
                         lambda b, h, iq, ik: (b, h // G, ik, 0)),
            pl.BlockSpec((None, None, bk, Dv),
                         lambda b, h, iq, ik: (b, h // G, ik, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, None, bq, Dv),
                         lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((None, None, bq, 1),
                         lambda b, h, iq, ik: (b, h, iq, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, Hq, Sqp, Dv), q.dtype),
            jax.ShapeDtypeStruct((B, Hq, Sqp, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, Dv), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        compiler_params=_CompilerParams(
            dimension_semantics=(pltpu.PARALLEL, pltpu.PARALLEL,
                                 pltpu.PARALLEL, pltpu.ARBITRARY)),
        interpret=interpret,
        name="flash_attention",
    )(q_pos_p, kv_pos_p, qp, kp, vp)

    out = out.transpose(0, 2, 1, 3)[:, :Sq]
    if return_lse:
        return out, lse[..., 0].transpose(0, 2, 1)[:, :Sq]
    return out
