"""Paged decode-attention Pallas TPU kernel (block-pool KV cache).

Serving counterpart of :mod:`repro.kernels.flash_attention`: K/V live in a
single lane-merged ``(num_blocks, block_size, W)`` pool per layer — or one
``(layers, num_blocks, block_size, W)`` stack for the scanned layers, read
at a layer index — and each batch slot owns a *block table* — a row of
physical block ids — instead of a contiguous cache stripe.  A pool row
holds the ``Hkv`` heads of ``D`` lanes side by side; the serving cache
pads it with zeros to ``W``, a multiple of 128 lanes, because the TPU lays
out an array whose minor dim is off the 128-lane tile with another dim
minor, which the kernel cannot read in place.  N slots seated on the same
compressed ICL task point at the same prefix blocks, so the pool holds
each distinct task's memory once (O(tasks), not O(slots)).

TPU mapping
-----------
Grid ``(B, nb)`` — one program per slot *walking that slot's block
table*; the block axis is innermost and ``ARBITRARY`` (sequential) so the
online-softmax state lives in VMEM scratch across the walk, exactly the
flash-decode inner loop.  Each program serves every head of its slot, so
a pool block is streamed once per slot, not once per (slot, head).

The physical block to stream is data-dependent (``table[b, j]``), which a
plain ``BlockSpec`` index map cannot express — block tables and per-slot
lengths ride in as **scalar-prefetch** operands
(``pltpu.PrefetchScalarGridSpec``), available to the index maps before the
kernel body runs, so the pipeline DMAs pool block ``table[b, j]`` while
program ``j-1`` computes.  Every block's last two dims satisfy Mosaic's
(8, 128) tiling rule without moving the pool:

* q        (Hq*Sp, D)   — the slot's last S query rows padded to Sp (a
  multiple of 8), head-major, so KV head ``g``'s query group is the
  contiguous row range ``[g*G*Sp, (g+1)*G*Sp)`` (GQA fold).
* k/v pool (bs, W)      — pool block ``table[b*nb + j]`` (of layer
  ``layer`` in a stack) as the pool stores it: rows of all KV heads side
  by side, head ``g`` at the lane range ``[g*D, (g+1)*D)``.  The pool is
  kept in this layout, so the block is read where it lies; a
  ``(..., Hkv, D)`` pool would have to be relaid out whole on every call.
  ``bs`` must be a multiple of 8.
* tables   (B*nb,) int32 SMEM — flattened so the index map stays 1-D.
* lengths  (B,)    int32 SMEM — drives masking *and* the per-slot early
  skip: a block whose start position is at or past ``lengths[b]`` is
  skipped via ``pl.when`` (idle slots cost ~nothing; young slots pay only
  for blocks they filled).
* layer    (1,)    int32 SMEM — stacked pools only: the layer whose
  blocks the index map names.

Unused table entries must still hold a *valid* pool index (the engine
keeps them at 0, a reserved scratch block) — they are never read into the
softmax because the length mask precedes them, but the DMA engine does
fetch whatever the index map names.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.kernels.jnp_impl import merged_heads
from repro.kernels.pltpu_compat import CompilerParams as _CompilerParams
from repro.kernels.pltpu_compat import mxu_precision

NEG_INF = -1e30


def _paged_kernel(
    *refs, scale: float, softcap: float, block_size: int, s_valid: int,
    s_pad: int, kv_heads: int, group: int, head_dim: int, v_dim: int,
    num_scalars: int,
):
    # scalar prefetch (SMEM): tables, lengths[, layer]; then the inputs,
    # the output and the scratch
    _tbl_ref, len_ref = refs[:2]
    q_ref, k_ref, v_ref, o_ref, acc, m_scr, l_scr = refs[num_scalars:]
    b = pl.program_id(0)
    j = pl.program_id(1)
    nb = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)

    length = len_ref[b]
    start = j * block_size

    @pl.when(start < length)
    def _compute():
        rows = group * s_pad  # query rows of one KV head's group
        # query row r holds token r % s_pad of its head, at cache position
        # length - s_valid + r % s_pad; padded tokens are masked out
        tok = jax.lax.broadcasted_iota(jnp.int32, (rows, block_size), 0) % s_pad
        pos = start + jax.lax.broadcasted_iota(
            jnp.int32, (rows, block_size), 1)
        valid = (tok < s_valid) & (pos <= length - s_valid + tok)
        prec = mxu_precision(q_ref.dtype)
        for g in range(kv_heads):
            r = pl.ds(g * rows, rows)
            q = q_ref[r, :]  # (rows, D)
            k = k_ref[:, pl.ds(g * head_dim, head_dim)]  # (bs, D)
            v = v_ref[:, pl.ds(g * v_dim, v_dim)]  # (bs, Dv)
            logits = jax.lax.dot_general(
                q, k.astype(q.dtype), (((1,), (1,)), ((), ())),
                precision=prec, preferred_element_type=jnp.float32) * scale
            if softcap:
                logits = softcap * jnp.tanh(logits / softcap)
            logits = jnp.where(valid, logits, NEG_INF)

            m_prev = m_scr[r, :]  # (rows, 1)
            m_new = jnp.maximum(m_prev, logits.max(axis=-1, keepdims=True))
            p = jnp.where(valid, jnp.exp(logits - m_new), 0.0)
            corr = jnp.exp(m_prev - m_new)
            l_scr[r, :] = l_scr[r, :] * corr + p.sum(axis=-1, keepdims=True)
            m_scr[r, :] = m_new
            pv = jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                precision=prec,
                preferred_element_type=jnp.float32)
            acc[r, :] = acc[r, :] * corr + pv

    @pl.when(j == nb - 1)
    def _finish():
        l = l_scr[...]
        out = acc[...] / jnp.maximum(l, 1e-37)
        o_ref[...] = jnp.where(l > 0, out, 0.0).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("kv_heads", "softcap", "scale", "interpret"),
)
def paged_flash_decode(
    q, k_pool, v_pool, *, block_tables, lengths, layer=None, kv_heads=None,
    softcap=0.0, scale=None, interpret=False,
):
    """(B,S,Hq,D) x pool (N,bs,W) x tables (B,nb) -> (B,S,Hq,Dv).

    The pools are lane-merged: row ``r`` of block ``n`` holds every KV
    head side by side, head ``g`` at lanes ``[g*D, (g+1)*D)``; rows
    zero-padded past the heads name ``kv_heads`` (see
    :func:`repro.kernels.jnp_impl.merged_heads`).  With ``layer`` (an
    int32 scalar, traced) they are per-layer stacks ``(layers, N, bs, W)``
    and the kernel streams blocks of that layer only, so a stack carried
    through the layer scan is read where it lies.  Slot ``b`` attends
    causally within its logical cache positions ``[0, lengths[b])``;
    logical block ``j`` resolves to pool block ``block_tables[b, j]``.
    """
    B, S, Hq, D = q.shape
    stacked = layer is not None
    bs, W = k_pool.shape[-2:]
    Wv = v_pool.shape[-1]
    Hkv, Dv = merged_heads(k_pool, v_pool, D, kv_heads)
    assert Hkv * D <= W and Hkv * Dv <= Wv and Hq % Hkv == 0, (W, Hkv, Hq)
    G = Hq // Hkv
    nb = block_tables.shape[1]
    if scale is None:
        scale = D**-0.5

    # pad query rows to a multiple of the 8-sublane tile; padded rows are
    # masked via the in-kernel token < s_valid test and sliced off below
    Sp = -(-S // 8) * 8
    if Sp != S:
        q = jnp.pad(q, ((0, 0), (0, Sp - S), (0, 0), (0, 0)))
    q_rows = q.transpose(0, 2, 1, 3).reshape(B, Hq * Sp, D)

    scalars = [block_tables.astype(jnp.int32).reshape(-1),  # (B*nb,)
               lengths.astype(jnp.int32)]
    if stacked:
        scalars.append(jnp.asarray(layer, jnp.int32).reshape(1))

        def pool_spec(w):
            return pl.BlockSpec(
                (None, None, bs, w),
                lambda b, j, tbl, lens, lyr: (lyr[0], tbl[b * nb + j], 0, 0))

        def row_spec(rows, w):
            return pl.BlockSpec((None, rows, w),
                                lambda b, j, tbl, lens, lyr: (b, 0, 0))
    else:
        def pool_spec(w):
            return pl.BlockSpec(
                (None, bs, w), lambda b, j, tbl, lens: (tbl[b * nb + j], 0, 0))

        def row_spec(rows, w):
            return pl.BlockSpec((None, rows, w),
                                lambda b, j, tbl, lens: (b, 0, 0))

    kernel = functools.partial(
        _paged_kernel, scale=scale, softcap=softcap, block_size=bs,
        s_valid=S, s_pad=Sp, kv_heads=Hkv, group=G, head_dim=D, v_dim=Dv,
        num_scalars=len(scalars))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(B, nb),
        in_specs=[row_spec(Hq * Sp, D), pool_spec(W), pool_spec(Wv)],
        out_specs=row_spec(Hq * Sp, Dv),
        scratch_shapes=[
            pltpu.VMEM((Hq * Sp, Dv), jnp.float32),
            pltpu.VMEM((Hq * Sp, 1), jnp.float32),
            pltpu.VMEM((Hq * Sp, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hq * Sp, Dv), q.dtype),
        compiler_params=_CompilerParams(
            dimension_semantics=(pltpu.PARALLEL, pltpu.ARBITRARY)),
        interpret=interpret,
        name="paged_flash_decode",
    )(*scalars, q_rows, k_pool, v_pool)
    return out.reshape(B, Hq, Sp, Dv).transpose(0, 2, 1, 3)[:, :S]


def _dense_block_size(L: int, cap: int = 512):
    """Rows per block when a dense ``L``-row cache stripe is read as pool
    blocks: the largest multiple of 8 that divides ``L`` and is at most
    ``cap``, or ``None`` when ``L`` is not a multiple of 8."""
    if L % 8:
        return None
    return max(b for b in range(8, min(L, cap) + 1, 8) if L % b == 0)


@functools.partial(
    jax.jit,
    static_argnames=("softcap", "scale", "interpret"),
)
def dense_flash_decode(q, k, v, *, lengths, softcap=0.0, scale=None,
                       interpret=False):
    """Decode over dense per-slot caches ``k``/``v`` (B, L, Hkv, D) with
    the paged kernel.  Slot ``b``'s stripe is read as ``L // bs``
    consecutive pool blocks (a reshape to ``(B * nb, bs, Hkv*D)`` and
    identity block tables), so every head of a slot is served from one
    pass over its rows — no head-major transpose of the cache on each
    step.  The reshape merges the head axes into lanes, which on the TPU
    relays the stripes out unless they are stored lane-merged already.
    ``L`` must be a multiple of 8 (:func:`_dense_block_size` picks the
    rows per block)."""
    B, L = k.shape[:2]
    bs = _dense_block_size(L)
    assert bs is not None, f"cache length {L} is not a multiple of 8"
    nb = L // bs
    tables = jnp.arange(B * nb, dtype=jnp.int32).reshape(B, nb)
    return paged_flash_decode(
        q, k.reshape(B * nb, bs, -1), v.reshape(B * nb, bs, -1),
        block_tables=tables, lengths=lengths, softcap=softcap, scale=scale,
        interpret=interpret)


def _latent_kernel(
    *refs, scale: float, block_size: int, blocks: int, s_valid: int,
    heads: int, v_dim: int, num_scalars: int,
):
    # scalar prefetch (SMEM): tables, lengths[, layer]; then the queries,
    # ``blocks`` pool blocks, the output and the scratch
    len_ref = refs[1]
    q_ref = refs[num_scalars]
    pool_refs = refs[num_scalars + 1:num_scalars + 1 + blocks]
    o_ref, acc, m_scr, l_scr = refs[num_scalars + 1 + blocks:]
    b = pl.program_id(0)
    j = pl.program_id(1)
    nb = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)

    length = len_ref[b]
    span = blocks * block_size
    start = j * span

    @pl.when(start < length)
    def _compute():
        rows = q_ref.shape[0]  # every head's query rows: one latent head
        row = jax.lax.broadcasted_iota(jnp.int32, (rows, span), 0)
        tok = sum((row >= t * heads).astype(jnp.int32)  # row // heads
                  for t in range(1, s_valid)) if s_valid > 1 else 0
        pos = start + jax.lax.broadcasted_iota(jnp.int32, (rows, span), 1)
        valid = (row < s_valid * heads) & (pos <= length - s_valid + tok)
        prec = mxu_precision(q_ref.dtype)
        q = q_ref[...]  # (rows, W)
        kv = jnp.concatenate([r[...] for r in pool_refs], axis=0)  # (span, W)
        logits = jax.lax.dot_general(
            q, kv.astype(q.dtype), (((1,), (1,)), ((), ())),
            precision=prec, preferred_element_type=jnp.float32) * scale
        logits = jnp.where(valid, logits, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, logits.max(axis=-1, keepdims=True))
        p = jnp.where(valid, jnp.exp(logits - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + p.sum(axis=-1, keepdims=True)
        m_scr[...] = m_new
        v = kv[:, :v_dim]  # the value is the key row's first lanes
        acc[...] = acc[...] * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())), precision=prec,
            preferred_element_type=jnp.float32)

    @pl.when(j == nb - 1)
    def _finish():
        l = l_scr[...]
        out = acc[...] / jnp.maximum(l, 1e-37)
        o_ref[...] = jnp.where(l > 0, out, 0.0).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("v_dim", "scale", "blocks", "interpret"))
def paged_latent_decode(
    q, pool, *, block_tables, lengths, v_dim, layer=None, scale=None,
    blocks=8, interpret=False,
):
    """Absorbed-MLA decode: (B,S,H,K) x latent pool (N,bs,W) -> (B,S,H,v_dim).

    Every query head of a slot reads the same latent rows: the key is a
    row's first ``K`` lanes (``[ckv | k_rope]``; the pool's pad lanes
    past ``K`` are zero and meet zero query lanes) and the value its first
    ``v_dim`` lanes (``ckv``), so one DMA of a pool block feeds both and
    the pool is read where it lies.  With ``layer`` (traced int32) the
    pool is a per-layer stack ``(layers, N, bs, W)``.  Each grid step
    streams ``blocks`` table columns of one slot (the table is padded to
    a multiple with the reserved block 0, masked by ``lengths``), so
    short blocks do not pay a grid step each.  Masking and the per-slot
    skip are those of :func:`paged_flash_decode`.
    """
    B, S, H, K = q.shape
    stacked = layer is not None
    bs, W = pool.shape[-2:]
    assert K <= W and v_dim <= K, (K, W, v_dim)
    nb = block_tables.shape[1]
    P = max(1, min(blocks, nb))
    nbp = -(-nb // P) * P
    tables = block_tables.astype(jnp.int32)
    if nbp != nb:
        tables = jnp.pad(tables, ((0, 0), (0, nbp - nb)))
    if scale is None:
        scale = K**-0.5
    # token-major query rows, S·H of them padded to a multiple of 8 as a
    # whole (16 heads of one decode token fill two sublane tiles)
    R = -(-(S * H) // 8) * 8
    q_rows = jnp.pad(q.reshape(B, S * H, K), ((0, 0), (0, R - S * H),
                                              (0, W - K)))

    scalars = [tables.reshape(-1), lengths.astype(jnp.int32)]
    if stacked:
        scalars.append(jnp.asarray(layer, jnp.int32).reshape(1))

        def pool_spec(t):
            return pl.BlockSpec(
                (None, None, bs, W),
                lambda b, j, tbl, lens, lyr: (lyr[0], tbl[(b * nbp) + j * P + t],
                                              0, 0))

        def row_spec(rows, w):
            return pl.BlockSpec((None, rows, w),
                                lambda b, j, tbl, lens, lyr: (b, 0, 0))
    else:
        def pool_spec(t):
            return pl.BlockSpec(
                (None, bs, W),
                lambda b, j, tbl, lens: (tbl[(b * nbp) + j * P + t], 0, 0))

        def row_spec(rows, w):
            return pl.BlockSpec((None, rows, w),
                                lambda b, j, tbl, lens: (b, 0, 0))

    kernel = functools.partial(
        _latent_kernel, scale=scale, block_size=bs, blocks=P, s_valid=S,
        heads=H, v_dim=v_dim, num_scalars=len(scalars))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(B, nbp // P),
        in_specs=[row_spec(R, W)] + [pool_spec(t) for t in range(P)],
        out_specs=row_spec(R, v_dim),
        scratch_shapes=[
            pltpu.VMEM((R, v_dim), jnp.float32),
            pltpu.VMEM((R, 1), jnp.float32),
            pltpu.VMEM((R, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, R, v_dim), q.dtype),
        compiler_params=_CompilerParams(
            dimension_semantics=(pltpu.PARALLEL, pltpu.ARBITRARY)),
        interpret=interpret,
        name="mla_latent_decode",
    )(*scalars, q_rows, *([pool] * P))
    return out[:, :S * H].reshape(B, S, H, v_dim)
