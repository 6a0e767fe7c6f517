"""Streaming (flash-style) pure-jnp implementations.

These are the production paths on CPU and the dry-run lowering; the Pallas
kernels in this package implement the same contracts for the TPU target.
All return values match :mod:`repro.kernels.ref` oracles to float tolerance.

Design notes
------------
* ``attention_chunked`` — rectangular KV streaming with online softmax.
  O(Sq * kv_chunk) live memory instead of O(Sq * Skv).  Used for
  cross-/prefix-attention and decode.
* ``attention_causal_blocked`` — q-chunked with per-chunk KV scans that stop
  at the diagonal, so compiled FLOPs are causal-optimal (~2x less than a
  rectangular mask).  Requires q_pos = kv_pos = offset + arange(S) (pure
  self-attention), which the model guarantees by construction.
* Partial results carry (out, lse) so prefix attention and self attention
  can be combined exactly (flash-decoding style) via
  ``combine_attention_partials``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def _gqa_fold(q, num_kv):
    B, Sq, Hq, Dk = q.shape
    return q.reshape(B, Sq, num_kv, Hq // num_kv, Dk)


def _apply_softcap(logits, softcap):
    if softcap:
        return softcap * jnp.tanh(logits / softcap)
    return logits


def attention_chunked(
    q, k, v, *, q_pos, kv_pos, causal=True, softcap=0.0, scale=None,
    kv_chunk=1024, return_lse=False,
):
    """Rectangular streaming attention with online softmax."""
    B, Sq, Hq, Dk = q.shape
    _, Skv, Hkv, _ = k.shape
    Dv = v.shape[-1]
    if scale is None:
        scale = Dk**-0.5
    kv_chunk = min(kv_chunk, Skv)
    pad = (-Skv) % kv_chunk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        kv_pos = jnp.pad(kv_pos, ((0, 0), (0, pad)), constant_values=-1)
    n_chunks = (Skv + pad) // kv_chunk

    qh = _gqa_fold(q, Hkv)  # (B,Sq,Hkv,G,Dk)
    G = Hq // Hkv

    def body(carry, xs):
        acc, m, l = carry
        kc, vc, pc = xs  # (B,C,Hkv,Dk) (B,C,Hkv,Dv) (B,C)
        logits = jnp.einsum("bqhgd,bchd->bqhgc", qh, kc).astype(jnp.float32) * scale
        logits = _apply_softcap(logits, softcap)
        valid = pc[:, None, :] >= 0
        if causal:
            valid = valid & (pc[:, None, :] <= q_pos[:, :, None])
        else:
            valid = jnp.broadcast_to(valid, (B, Sq, kv_chunk))
        logits = jnp.where(valid[:, :, None, None, :], logits, NEG_INF)
        m_new = jnp.maximum(m, logits.max(axis=-1))
        # guard: keep m_new finite so exp() never sees (-inf) - (-inf)
        m_safe = jnp.maximum(m_new, NEG_INF)
        p = jnp.exp(logits - m_safe[..., None])
        corr = jnp.exp(m - m_safe)
        l = l * corr + p.sum(axis=-1)
        pv = jnp.einsum("bqhgc,bchd->bqhgd", p.astype(v.dtype), vc).astype(jnp.float32)
        acc = acc * corr[..., None] + pv
        return (acc, m_safe, l), None

    acc0 = jnp.zeros((B, Sq, Hkv, G, Dv), jnp.float32)
    m0 = jnp.full((B, Sq, Hkv, G), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, Sq, Hkv, G), jnp.float32)
    xs = (
        k.reshape(B, n_chunks, kv_chunk, Hkv, Dk).swapaxes(0, 1),
        v.reshape(B, n_chunks, kv_chunk, Hkv, Dv).swapaxes(0, 1),
        kv_pos.reshape(B, n_chunks, kv_chunk).swapaxes(0, 1),
    )
    (acc, m, l), _ = jax.lax.scan(body, (acc0, m0, l0), xs)
    out = (acc / jnp.maximum(l, 1e-37)[..., None]).astype(q.dtype)
    out = jnp.where((l > 0)[..., None], out, 0).reshape(B, Sq, Hq, Dv).astype(q.dtype)
    if return_lse:
        lse = jnp.where(l > 0, m + jnp.log(jnp.maximum(l, 1e-37)), NEG_INF)
        return out, lse.reshape(B, Sq, Hq)
    return out


def attention_causal_blocked(
    q, k, v, *, offset=0, softcap=0.0, scale=None, q_chunk=512, kv_chunk=512,
    return_lse=False,
):
    """Causal self-attention, FLOP-optimal blocking.

    Assumes q_pos = kv_pos = offset + arange(S): blocks strictly above the
    diagonal are skipped *statically* so they never enter the HLO.
    """
    B, S, Hq, Dk = q.shape
    Hkv = k.shape[2]
    Dv = v.shape[-1]
    G = Hq // Hkv
    if scale is None:
        scale = Dk**-0.5
    q_chunk = min(q_chunk, S)
    kv_chunk = min(kv_chunk, S)
    if S % q_chunk or S % kv_chunk or q_chunk % kv_chunk:
        # fall back to rectangular streaming with explicit positions
        pos = offset + jnp.arange(S, dtype=jnp.int32)[None, :].repeat(B, 0)
        return attention_chunked(
            q, k, v, q_pos=pos, kv_pos=pos, causal=True, softcap=softcap,
            scale=scale, kv_chunk=kv_chunk, return_lse=return_lse,
        )

    nq = S // q_chunk
    outs, lses = [], []
    tri = jnp.tril(jnp.ones((q_chunk, q_chunk), bool))

    for i in range(nq):
        qi = _gqa_fold(q[:, i * q_chunk : (i + 1) * q_chunk], Hkv)
        # ---- strictly-below-diagonal blocks: rectangular scan ----
        n_full = (i * q_chunk) // kv_chunk
        acc = jnp.zeros((B, q_chunk, Hkv, G, Dv), jnp.float32)
        m = jnp.full((B, q_chunk, Hkv, G), NEG_INF, jnp.float32)
        l = jnp.zeros((B, q_chunk, Hkv, G), jnp.float32)

        if n_full:
            def body(carry, xs, qi=qi):
                acc, m, l = carry
                kc, vc = xs
                logits = jnp.einsum("bqhgd,bchd->bqhgc", qi, kc).astype(jnp.float32) * scale
                logits = _apply_softcap(logits, softcap)
                m_new = jnp.maximum(m, logits.max(axis=-1))
                p = jnp.exp(logits - m_new[..., None])
                corr = jnp.exp(m - m_new)
                l = l * corr + p.sum(axis=-1)
                pv = jnp.einsum("bqhgc,bchd->bqhgd", p.astype(v.dtype), vc).astype(jnp.float32)
                acc = acc * corr[..., None] + pv
                return (acc, m_new, l), None

            xs = (
                k[:, : n_full * kv_chunk].reshape(B, n_full, kv_chunk, Hkv, Dk).swapaxes(0, 1),
                v[:, : n_full * kv_chunk].reshape(B, n_full, kv_chunk, Hkv, Dv).swapaxes(0, 1),
            )
            (acc, m, l), _ = jax.lax.scan(body, (acc, m, l), xs)

        # ---- diagonal block: triangular mask ----
        kd = k[:, i * q_chunk : (i + 1) * q_chunk]
        vd = v[:, i * q_chunk : (i + 1) * q_chunk]
        logits = jnp.einsum("bqhgd,bchd->bqhgc", qi, kd).astype(jnp.float32) * scale
        logits = _apply_softcap(logits, softcap)
        logits = jnp.where(tri[None, :, None, None, :], logits, NEG_INF)
        m_new = jnp.maximum(m, logits.max(axis=-1))
        p = jnp.exp(logits - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l = l * corr + p.sum(axis=-1)
        pv = jnp.einsum("bqhgc,bchd->bqhgd", p.astype(v.dtype), vd).astype(jnp.float32)
        acc = acc * corr[..., None] + pv

        outs.append((acc / jnp.maximum(l, 1e-37)[..., None]).astype(q.dtype))
        lses.append(m_new + jnp.log(jnp.maximum(l, 1e-37)))

    out = jnp.concatenate(outs, axis=1).reshape(B, S, Hq, Dv)
    if return_lse:
        lse = jnp.concatenate(lses, axis=1).reshape(B, S, Hq)
        return out, lse
    return out


def decode_attention_lengths(
    q, k, v, *, lengths, softcap=0.0, scale=None, kv_chunk=256,
):
    """Per-slot length-masked decode attention with unseated-tail skipping.

    ``q`` holds each slot's last ``Sq`` tokens (cache positions
    ``lengths[b]-Sq .. lengths[b]-1``); ``k``/``v`` are the full fixed-size
    caches.  Slot ``b`` attends to cache positions ``< lengths[b]`` only, so
    ragged continuous-batching slots never see each other's unseated tail or
    stale KV from a previous occupant of the slot.

    KV chunks that start at or beyond ``max(lengths)`` are skipped at
    runtime via ``lax.cond`` — the cache is allocated at ``max_len`` but a
    young batch only pays for the chunks it has actually filled.
    """
    B, Sq, Hq, Dk = q.shape
    _, Skv, Hkv, _ = k.shape
    Dv = v.shape[-1]
    G = Hq // Hkv
    if scale is None:
        scale = Dk**-0.5
    kv_chunk = min(kv_chunk, Skv)
    pad = (-Skv) % kv_chunk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    n_chunks = (Skv + pad) // kv_chunk

    qh = _gqa_fold(q, Hkv)
    q_pos = lengths[:, None] - Sq + jnp.arange(Sq, dtype=jnp.int32)[None, :]
    live_end = jnp.max(lengths)  # chunks past this hold no seated KV at all

    def attend(carry, start):
        acc, m, l = carry
        kc = jax.lax.dynamic_slice_in_dim(k, start, kv_chunk, axis=1)
        vc = jax.lax.dynamic_slice_in_dim(v, start, kv_chunk, axis=1)
        pos = start + jnp.arange(kv_chunk, dtype=jnp.int32)
        logits = jnp.einsum("bqhgd,bchd->bqhgc", qh, kc).astype(jnp.float32) * scale
        logits = _apply_softcap(logits, softcap)
        # pos <= q_pos already bounds pos < lengths[b] (q_pos max = lengths-1)
        valid = pos[None, None, :] <= q_pos[:, :, None]
        logits = jnp.where(valid[:, :, None, None, :], logits, NEG_INF)
        m_new = jnp.maximum(m, logits.max(axis=-1))
        p = jnp.exp(logits - m_new[..., None])
        # exp(NEG_INF - NEG_INF) = 1: re-zero masked slots so a row with no
        # valid KV yet (lengths[b] < Sq) accumulates l = 0, not kv_chunk
        p = jnp.where(valid[:, :, None, None, :], p, 0.0)
        corr = jnp.exp(m - m_new)
        l = l * corr + p.sum(axis=-1)
        pv = jnp.einsum("bqhgc,bchd->bqhgd", p.astype(v.dtype), vc).astype(jnp.float32)
        acc = acc * corr[..., None] + pv
        return acc, m_new, l

    def body(carry, c):
        start = c * kv_chunk
        carry = jax.lax.cond(start < live_end, attend,
                             lambda carry, _start: carry, carry, start)
        return carry, None

    acc0 = jnp.zeros((B, Sq, Hkv, G, Dv), jnp.float32)
    m0 = jnp.full((B, Sq, Hkv, G), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, Sq, Hkv, G), jnp.float32)
    (acc, _, l), _ = jax.lax.scan(
        body, (acc0, m0, l0), jnp.arange(n_chunks, dtype=jnp.int32))
    out = (acc / jnp.maximum(l, 1e-37)[..., None]).astype(q.dtype)
    return jnp.where((l > 0)[..., None], out, 0).reshape(B, Sq, Hq, Dv)


# ---------------------------------------------------------------------------
# Paged KV cache primitives (vLLM-style block pool + per-slot block tables)
# ---------------------------------------------------------------------------


def _pool_blocks(pool, blk, layer):
    """Blocks ``blk`` (any int shape) of a pool, or of layer ``layer`` of
    a stacked pool: ``blk.shape + (block_size, ...)``."""
    return pool[blk] if layer is None else pool[layer, blk]


def merged_heads(k_pool, v_pool, head_dim, kv_heads=None):
    """``(Hkv, Dv)`` of lane-merged K/V pools whose K heads are
    ``head_dim`` wide: rows that hold the heads exactly give ``Hkv =
    width // head_dim`` and ``Dv = v_width // Hkv``; rows zero-padded past
    the heads (to the 128-lane tile) name ``kv_heads``, and then hold K
    and V heads of ``head_dim`` lanes each."""
    if kv_heads is None:
        Hkv = k_pool.shape[-1] // head_dim
        return Hkv, v_pool.shape[-1] // Hkv
    return kv_heads, head_dim


def paged_scatter(pool, new, block_tables, starts, valid=None, layer=None):
    """Write ``new[b, s]`` into the block pool at logical cache position
    ``starts[b] + s`` of slot ``b``.

    ``pool`` is ``(num_blocks, block_size, ...)``, or with ``layer`` (a
    traced int) a per-layer stack ``(layers, num_blocks, block_size, ...)``
    written at that layer only — the rest of the stack is untouched, so a
    stack carried through the layer scan is updated in place.  ``new`` is
    ``(B, S, ...)`` and is reshaped to the pool's trailing dims: an
    attention pool keeps each row's heads lane-merged, ``(Hkv*hd,)``
    zero-padded to its width, while ``new`` arrives as ``(Hkv, hd)``.
    ``block_tables`` ``(B, num_table_cols)`` int32 maps each slot's
    logical block ``j`` to a physical pool block; ``starts`` ``(B,)``
    int32.  Positions are translated token-wise (``block = table[b, pos //
    bs]``, ``offset = pos % bs``) so a write may straddle physical blocks
    that are not adjacent in the pool.  The write is one scatter, in
    place on the pool's buffer: on the TPU it costs about 6 µs a layer in
    a step program, where a ``dynamic_update_slice`` per row costs some
    60 µs for 32 rows.

    ``valid`` (B,) int32 (optional) is the ragged-lane mask for the fused
    serving step: only lanes ``s < valid[b]`` carry real tokens, the rest
    are geometry padding (speculative lanes past a slot's budget, chunk
    lanes of other slots).  Invalid lanes are routed to physical block 0 —
    the allocator's reserved trash block — so they can never corrupt an
    allocated block.  The table column is also clamped: an invalid lane's
    ``pos // bs`` may exceed the table width, and take_along_axis's clamp
    semantics would otherwise read the *last* column (a real block for a
    full slot)."""
    lead = 0 if layer is None else 1  # per-layer stack axis
    bs, trail = pool.shape[lead + 1], pool.shape[lead + 2:]
    B, S = new.shape[:2]
    if len(trail) == 1:  # lane-merged rows, zero-padded to the pool width
        new = new.reshape(B, S, -1)
        new = jnp.pad(new, ((0, 0), (0, 0), (0, trail[0] - new.shape[-1])))
    new = new.reshape(B, S, *trail)
    pos = starts[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]  # (B,S)
    col = pos // bs
    if valid is not None:
        col = jnp.clip(col, 0, block_tables.shape[1] - 1)
    blk = jnp.take_along_axis(block_tables, col, axis=1)  # (B,S)
    if valid is not None:
        lane = jnp.arange(S, dtype=jnp.int32)[None, :]
        blk = jnp.where(lane < valid[:, None], blk, 0)  # 0 == trash block
    idx = (blk, pos % bs) if layer is None else (layer, blk, pos % bs)
    return pool.at[idx].set(new.astype(pool.dtype))


def paged_gather(pool, block_tables, layer=None):
    """Materialize each slot's logical cache view from the pool (layer
    ``layer`` of a stacked pool): ``(num_blocks, bs, ...) x (B, nb) ->
    (B, nb*bs, ...)``.  Only the named blocks are read."""
    B, nb = block_tables.shape
    view = _pool_blocks(pool, block_tables, layer)  # (B, nb, bs, ...)
    return view.reshape(B, nb * view.shape[2], *view.shape[3:])


def paged_decode_attention_lengths(
    q, k_pool, v_pool, *, block_tables, lengths, softcap=0.0, scale=None,
    layer=None, kv_heads=None,
):
    """Streaming paged decode attention: walk each slot's block table.

    Same contract as :func:`decode_attention_lengths` except K/V live in a
    shared pool and slot ``b``'s cache positions ``[j*bs, (j+1)*bs)``
    resolve to pool block ``block_tables[b, j]``.  A pool is ``(num_blocks,
    block_size, Hkv, D)`` or lane-merged ``(num_blocks, block_size, W)``
    (``kv_heads`` as in :func:`repro.kernels.ops.paged_decode_attention`);
    with ``layer`` it carries a leading per-layer axis and only that
    layer's blocks are read.  One gather of ``(B, bs, ...)`` per
    table column — never the materialized ``(B, nb*bs, ...)`` view — and
    columns at or beyond ``max(lengths)`` are skipped at runtime via
    ``lax.cond``.
    """
    B, Sq, Hq, Dk = q.shape
    lead = 0 if layer is None else 1
    bs = k_pool.shape[lead + 1]
    if k_pool.ndim == lead + 4:
        Hkv, Dv = k_pool.shape[-2], v_pool.shape[-1]
    else:
        Hkv, Dv = merged_heads(k_pool, v_pool, Dk, kv_heads)
    G = Hq // Hkv
    nb = block_tables.shape[1]
    if scale is None:
        scale = Dk**-0.5

    qh = _gqa_fold(q, Hkv)
    q_pos = lengths[:, None] - Sq + jnp.arange(Sq, dtype=jnp.int32)[None, :]
    live_end = jnp.max(lengths)

    def attend(carry, j):
        acc, m, l = carry
        blk = jax.lax.dynamic_slice_in_dim(block_tables, j, 1, axis=1)[:, 0]
        kc = _pool_blocks(k_pool, blk, layer)
        vc = _pool_blocks(v_pool, blk, layer)
        kc = kc.reshape(B, bs, -1)[..., :Hkv * Dk].reshape(B, bs, Hkv, Dk)
        vc = vc.reshape(B, bs, -1)[..., :Hkv * Dv].reshape(B, bs, Hkv, Dv)
        pos = j * bs + jnp.arange(bs, dtype=jnp.int32)
        logits = jnp.einsum("bqhgd,bchd->bqhgc", qh, kc.astype(qh.dtype))
        logits = logits.astype(jnp.float32) * scale
        logits = _apply_softcap(logits, softcap)
        valid = pos[None, None, :] <= q_pos[:, :, None]
        logits = jnp.where(valid[:, :, None, None, :], logits, NEG_INF)
        m_new = jnp.maximum(m, logits.max(axis=-1))
        p = jnp.exp(logits - m_new[..., None])
        p = jnp.where(valid[:, :, None, None, :], p, 0.0)
        corr = jnp.exp(m - m_new)
        l = l * corr + p.sum(axis=-1)
        pv = jnp.einsum("bqhgc,bchd->bqhgd", p.astype(vc.dtype), vc)
        acc = acc * corr[..., None] + pv.astype(jnp.float32)
        return acc, m_new, l

    def body(carry, j):
        carry = jax.lax.cond(j * bs < live_end, attend,
                             lambda carry, _j: carry, carry, j)
        return carry, None

    acc0 = jnp.zeros((B, Sq, Hkv, G, Dv), jnp.float32)
    m0 = jnp.full((B, Sq, Hkv, G), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, Sq, Hkv, G), jnp.float32)
    (acc, _, l), _ = jax.lax.scan(
        body, (acc0, m0, l0), jnp.arange(nb, dtype=jnp.int32))
    out = (acc / jnp.maximum(l, 1e-37)[..., None]).astype(q.dtype)
    return jnp.where((l > 0)[..., None], out, 0).reshape(B, Sq, Hq, Dv)


def combine_attention_partials(parts):
    """Exact combination of attention computed over disjoint KV sets.

    parts: list of (out (B,S,H,Dv), lse (B,S,H)).
    """
    lses = jnp.stack([p[1] for p in parts])  # (P,B,S,H)
    outs = jnp.stack([p[0] for p in parts])  # (P,B,S,H,Dv)
    m = lses.max(axis=0)
    w = jnp.exp(lses - m[None])  # (P,B,S,H)
    denom = w.sum(axis=0)
    w = w / jnp.maximum(denom, 1e-37)
    out = (outs.astype(jnp.float32) * w[..., None]).sum(axis=0)
    return out.astype(parts[0][0].dtype)


# ---------------------------------------------------------------------------
# Mamba2 SSD — chunked (state-space duality) implementation
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("chunk",))
def ssd_chunked(x, dt, A, Bm, Cm, *, init_state=None, chunk=256):
    """Chunk-parallel SSD.  Same contract as :func:`repro.kernels.ref.ssd_ref`.

    Per chunk: quadratic intra-chunk term (attention-like, in matmul form,
    MXU-friendly) + inter-chunk state recurrence carried by a scan.
    """
    B, S, H, P = x.shape
    G, N = Bm.shape[2], Bm.shape[3]
    rep = H // G
    if init_state is None:
        init_state = jnp.zeros((B, H, P, N), jnp.float32)
    chunk = min(chunk, S)
    pad = (-S) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        Bm = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0), (0, 0)))
        Cm = jnp.pad(Cm, ((0, 0), (0, pad), (0, 0), (0, 0)))
    Sp = S + pad
    nc = Sp // chunk

    xf = x.astype(jnp.float32).reshape(B, nc, chunk, H, P).swapaxes(0, 1)
    dtf = dt.astype(jnp.float32).reshape(B, nc, chunk, H).swapaxes(0, 1)
    Bf = jnp.repeat(Bm.astype(jnp.float32), rep, axis=2).reshape(B, nc, chunk, H, N).swapaxes(0, 1)
    Cf = jnp.repeat(Cm.astype(jnp.float32), rep, axis=2).reshape(B, nc, chunk, H, N).swapaxes(0, 1)

    tri = jnp.tril(jnp.ones((chunk, chunk), bool))

    def body(h, xs):
        xc, dtc, bc, cc = xs  # (B,Q,H,P) (B,Q,H) (B,Q,H,N) (B,Q,H,N)
        a = dtc * A[None, None, :]  # (B,Q,H) log-decay per step
        cum = jnp.cumsum(a, axis=1)  # inclusive
        # intra-chunk: y_i += sum_{j<=i} (C_i.B_j) exp(cum_i - cum_j) dt_j x_j
        decay = cum[:, :, None, :] - cum[:, None, :, :]  # (B,Q_i,Q_j,H)
        L = jnp.where(tri[None, :, :, None], jnp.exp(decay), 0.0)
        gcb = jnp.einsum("bihn,bjhn->bijh", cc, bc)
        w = gcb * L * dtc[:, None, :, :]  # (B,Qi,Qj,H)
        y = jnp.einsum("bijh,bjhp->bihp", w, xc)
        # inter-chunk: y_i += C_i . (h_prev * exp(cum_i))
        y = y + jnp.einsum("bihn,bhpn->bihp", cc * jnp.exp(cum)[..., None], h)
        # chunk state: h = h*exp(cum_last) + sum_j exp(cum_last - cum_j) dt_j B_j x_j^T
        seg = jnp.exp(cum[:, -1:, :] - cum)  # (B,Q,H)
        h = h * jnp.exp(cum[:, -1])[..., None, None] + jnp.einsum(
            "bjh,bjhp,bjhn->bhpn", seg * dtc, xc, bc
        )
        return h, y

    final, ys = jax.lax.scan(body, init_state, (xf, dtf, Bf, Cf))
    y = ys.swapaxes(0, 1).reshape(B, Sp, H, P)[:, :S]
    return y.astype(x.dtype), final


def ssd_decode_step(state, x, dt, A, Bm, Cm):
    """Single-token recurrent SSD update.

    state: (B,H,P,N); x: (B,H,P); dt: (B,H); Bm/Cm: (B,G,N).
    Returns (y (B,H,P), new_state).
    """
    H = x.shape[1]
    G = Bm.shape[1]
    rep = H // G
    Bh = jnp.repeat(Bm, rep, axis=1).astype(jnp.float32)
    Ch = jnp.repeat(Cm, rep, axis=1).astype(jnp.float32)
    dtf = dt.astype(jnp.float32)
    dA = jnp.exp(dtf * A[None, :])
    state = state * dA[..., None, None] + (dtf[..., None] * x.astype(jnp.float32))[..., None] * Bh[:, :, None, :]
    y = jnp.einsum("bhpn,bhn->bhp", state, Ch)
    return y.astype(x.dtype), state
