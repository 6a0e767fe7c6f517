"""Public kernel entry points with backend dispatch.

Every op has three implementations:

* ``dense``  — :mod:`repro.kernels.ref` oracle (tiny shapes, tests)
* ``jnp``    — streaming :mod:`repro.kernels.jnp_impl` (CPU, dry-run lowering)
* ``pallas`` — TPU kernels in this package (``interpret=True`` on CPU tests)

``impl="auto"`` picks ``pallas`` for every op on the TPU backend,
whatever the size.  Elsewhere it picks ``jnp``, or ``dense`` for very
small problems where blocking overhead dominates.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import jnp_impl, ref

_FORCED_IMPL: Optional[str] = None


def set_default_impl(impl: Optional[str]) -> None:
    """Force an implementation globally (None restores auto)."""
    global _FORCED_IMPL
    _FORCED_IMPL = impl


def _resolve(impl: str, small: bool) -> str:
    if impl != "auto":
        return impl
    if _FORCED_IMPL is not None:
        return _FORCED_IMPL
    if jax.default_backend() == "tpu":
        return "pallas"
    return "dense" if small else "jnp"


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------


def _interpret() -> bool:
    """Pallas kernels compile for the TPU and run interpreted elsewhere."""
    return jax.default_backend() != "tpu"


def _head_parallel(mesh, *operands, head_axis=2):
    """True when a mesh with a >1 "model" axis is installed and every
    head-carrying operand's head dim divides it — the condition for
    splitting a kernel by head (GQA: Hq and Hkv must both split)."""
    from repro.sharding.serving import model_axis_size

    n = model_axis_size(mesh)
    return n > 1 and all(x.shape[head_axis] % n == 0 for x in operands)


def _per_device(mesh, fn, head_args, rep_args, out_ndims=4, head_axes=None,
                split=None):
    """Run the Pallas call ``fn(*head_args, *rep_args)`` with ``mesh``
    installed.  Mosaic kernels cannot be partitioned automatically, so on
    a multi-device mesh every device runs the kernel under shard_map: on
    its own head slice when each of ``head_args`` splits its head axis
    (``head_axes``, one per head arg, default 2) over "model"
    (``out_ndims``: the outputs' ranks, head axis 2), else on the whole,
    replicated operands.  ``split`` overrides the divisibility test where
    a head axis holds more than the heads (lane-merged pools)."""
    if mesh is None or mesh.devices.size <= 1:
        return fn(*head_args, *rep_args)
    from repro.sharding.serving import shard_map_heads, shard_map_replicated

    if split is None:
        split = bool(head_args) and _head_parallel(mesh, *head_args)
    if split:
        wrapped = shard_map_heads(fn, mesh, head_args=len(head_args),
                                  replicated_args=len(rep_args),
                                  out_ndims=out_ndims, head_axes=head_axes)
    else:
        wrapped = shard_map_replicated(fn, mesh)
    return wrapped(*head_args, *rep_args)


def _flash(q, k, v, q_pos, kv_pos, *, mesh, **kw):
    from repro.kernels import flash_attention  # lazy: TPU-targeted

    def run(q, k, v, q_pos, kv_pos):
        return flash_attention.flash_attention(
            q, k, v, q_pos=q_pos, kv_pos=kv_pos, interpret=_interpret(), **kw)

    return _per_device(mesh, run, (q, k, v), (q_pos, kv_pos),
                       out_ndims=(4, 3) if kw.get("return_lse") else 4)


def attention(q, k, v, *, q_pos, kv_pos, causal=True, softcap=0.0, scale=None,
              impl="auto", kv_chunk=1024, return_lse=False, mesh=None):
    """General position-masked GQA attention (prefix / decode / cross).
    ``mesh``: the operands live on it; the Pallas kernel then runs per
    device (by head where heads divide the "model" axis)."""
    small = q.shape[1] * k.shape[1] <= 256 * 256
    impl = _resolve(impl, small)
    if impl == "dense":
        out = ref.attention_ref(q, k, v, q_pos=q_pos, kv_pos=kv_pos,
                                causal=causal, softcap=softcap, scale=scale)
        if return_lse:
            # dense path recomputes lse explicitly (tests only)
            _, lse = jnp_impl.attention_chunked(
                q, k, v, q_pos=q_pos, kv_pos=kv_pos, causal=causal,
                softcap=softcap, scale=scale, kv_chunk=max(k.shape[1], 1),
                return_lse=True)
            return out, lse
        return out
    if impl == "pallas":
        return _flash(q, k, v, q_pos, kv_pos, mesh=mesh, causal=causal,
                      softcap=softcap, scale=scale, return_lse=return_lse)
    return jnp_impl.attention_chunked(
        q, k, v, q_pos=q_pos, kv_pos=kv_pos, causal=causal, softcap=softcap,
        scale=scale, kv_chunk=kv_chunk, return_lse=return_lse)


def self_attention_causal(q, k, v, *, offset=0, softcap=0.0, scale=None,
                          impl="auto", q_chunk=512, kv_chunk=512,
                          return_lse=False, mesh=None):
    """Pure causal self-attention (q_pos = kv_pos = offset + arange(S))."""
    S = q.shape[1]
    small = S * S <= 512 * 512
    impl = _resolve(impl, small)
    if impl == "dense":
        B = q.shape[0]
        pos = jnp.broadcast_to(offset + jnp.arange(S, dtype=jnp.int32), (B, S))
        out = ref.attention_ref(q, k, v, q_pos=pos, kv_pos=pos, causal=True,
                                softcap=softcap, scale=scale)
        if return_lse:
            _, lse = jnp_impl.attention_causal_blocked(
                q, k, v, offset=offset, softcap=softcap, scale=scale,
                q_chunk=min(q_chunk, S), kv_chunk=min(kv_chunk, S),
                return_lse=True)
            return out, lse
        return out
    if impl == "pallas":
        B = q.shape[0]
        pos = jnp.broadcast_to(offset + jnp.arange(S, dtype=jnp.int32), (B, S))
        return _flash(q, k, v, pos, pos, mesh=mesh, causal=True,
                      softcap=softcap, scale=scale, return_lse=return_lse)
    return jnp_impl.attention_causal_blocked(
        q, k, v, offset=offset, softcap=softcap, scale=scale,
        q_chunk=q_chunk, kv_chunk=kv_chunk, return_lse=return_lse)


def decode_attention(q, k, v, *, lengths, softcap=0.0, scale=None,
                     impl="auto", kv_chunk=256, mesh=None):
    """Per-slot length-aware decode attention (continuous batching).

    ``q`` (B, S, Hq, D) holds each slot's last S tokens; ``k``/``v``
    (B, L, Hkv, D) are the full fixed-size caches; ``lengths`` (B,) int32 is
    each slot's total valid length *including* the S new tokens.  Slot ``b``
    attends causally within cache positions ``[0, lengths[b])`` — nothing
    beyond its own seated prefix + written tokens is visible, so slots with
    different compressed prefixes and ragged prompts share one batched step.

    The jnp path skips KV chunks beyond ``max(lengths)`` at runtime; the
    pallas path reads each slot's stripe as consecutive pool blocks of the
    paged kernel (no copy of the cache; the flash kernel with per-slot
    position masks when ``L`` is not a multiple of 8).

    ``mesh``: tensor-parallel serving.  Q/K/V split on the head axis over
    the mesh's "model" axis while ``lengths`` stays replicated — the jnp
    path is pinned head-parallel via a sharding constraint (GSPMD handles
    the rest), the pallas path runs per-shard under ``shard_map`` (pallas
    has no GSPMD partitioning rule).  Heads that don't divide the axis
    run replicated.
    """
    B, S = q.shape[:2]
    small = S * k.shape[1] <= 256 * 256
    impl = _resolve(impl, small)
    if impl == "pallas" and k.shape[1] % 8 == 0:
        from repro.kernels import paged_attention  # lazy: TPU-targeted

        def run(q, k, v, lengths):
            return paged_attention.dense_flash_decode(
                q, k, v, lengths=lengths, softcap=softcap, scale=scale,
                interpret=_interpret())

        return _per_device(mesh, run, (q, k, v), (lengths,))
    if impl in ("dense", "pallas"):
        L = k.shape[1]
        slot = jnp.arange(L, dtype=jnp.int32)
        kv_pos = jnp.broadcast_to(slot[None, :], (B, L))
        q_pos = lengths[:, None] - S + jnp.arange(S, dtype=jnp.int32)[None, :]
        if impl == "dense":
            return ref.attention_ref(q, k, v, q_pos=q_pos, kv_pos=kv_pos,
                                     causal=True, softcap=softcap, scale=scale)
        return _flash(q, k, v, q_pos, kv_pos, mesh=mesh, causal=True,
                      softcap=softcap, scale=scale)
    if _head_parallel(mesh, q, k, v):
        from repro.sharding.serving import constrain_heads

        q = constrain_heads(q, mesh)
        k = constrain_heads(k, mesh)
        v = constrain_heads(v, mesh)
    return jnp_impl.decode_attention_lengths(
        q, k, v, lengths=lengths, softcap=softcap, scale=scale,
        kv_chunk=kv_chunk)


def paged_decode_attention(q, k_pool, v_pool, *, block_tables, lengths,
                           layer=None, kv_heads=None, softcap=0.0, scale=None,
                           impl="auto", mesh=None):
    """Per-slot decode attention over a paged (block-pool) KV cache.

    ``q`` (B, S, Hq, D) holds each slot's last S tokens; ``k_pool`` /
    ``v_pool`` are the shared physical pools, lane-merged
    ``(num_blocks, block_size, W)`` as the paged cache stores them (rows
    zero-padded past their heads name ``kv_heads``, see
    :func:`jnp_impl.merged_heads`), or ``(num_blocks, block_size, Hkv,
    D)``; with ``layer`` (a traced int) they carry a leading per-layer
    axis and only that layer's blocks are read.  ``block_tables`` (B, nb)
    int32 maps slot ``b``'s logical block ``j`` to a pool block;
    ``lengths`` (B,) is each slot's total valid length *including* the S
    new tokens.  Slot ``b`` attends causally within logical positions
    ``[0, lengths[b])`` — identical semantics to :func:`decode_attention`
    on the materialized view, but prefix blocks shared between slots are
    stored (and streamed) once.

    The jnp path gathers one ``(B, block_size, ...)`` chunk per table
    column and skips columns past ``max(lengths)``; the pallas path walks
    the tables with scalar-prefetched indices (one grid program per slot
    reusing the flash-decode inner loop); the dense path materializes each
    slot's view and defers to :func:`decode_attention`'s oracle.

    ``mesh``: tensor-parallel serving.  Q splits on its head axis and the
    pools on their lane axis, by whole KV heads, over the "model" mesh
    axis where the heads divide it and the rows hold them exactly
    (replicated otherwise); ``block_tables``, ``lengths`` and ``layer``
    are replicated on every shard (the table resolves block *indices*,
    identical per head shard — the control plane never shards).  The jnp
    path is pinned head-parallel with a sharding constraint; the pallas
    path runs per-shard under ``shard_map``.
    """
    B, S, Hq, D = q.shape
    lead = 0 if layer is None else 1
    if k_pool.ndim == lead + 4:  # (..., Hkv, D): merge the heads into lanes
        k_pool = k_pool.reshape(*k_pool.shape[:-2], -1)
        v_pool = v_pool.reshape(*v_pool.shape[:-2], -1)
    bs = k_pool.shape[lead + 1]
    Hkv, Dv = jnp_impl.merged_heads(k_pool, v_pool, D, kv_heads)
    L = block_tables.shape[1] * bs
    small = S * L <= 256 * 256
    impl = _resolve(impl, small)
    if impl == "dense":
        k = jnp_impl.paged_gather(k_pool, block_tables, layer)
        v = jnp_impl.paged_gather(v_pool, block_tables, layer)
        k = k[..., :Hkv * D].reshape(B, L, Hkv, D).astype(q.dtype)
        v = v[..., :Hkv * Dv].reshape(B, L, Hkv, Dv).astype(q.dtype)
        slot = jnp.arange(L, dtype=jnp.int32)
        kv_pos = jnp.broadcast_to(slot[None, :], (B, L))
        q_pos = lengths[:, None] - S + jnp.arange(S, dtype=jnp.int32)[None, :]
        return ref.attention_ref(q, k, v, q_pos=q_pos, kv_pos=kv_pos,
                                 causal=True, softcap=softcap, scale=scale)
    from repro.sharding.serving import model_axis_size

    n = model_axis_size(mesh)
    exact = k_pool.shape[-1] == Hkv * D and v_pool.shape[-1] == Hkv * Dv
    split = n > 1 and exact and Hq % n == 0 and Hkv % n == 0
    if impl == "pallas":
        from repro.kernels import paged_attention  # lazy: TPU-targeted

        def run(q, k_pool, v_pool, block_tables, lengths, *lyr):
            return paged_attention.paged_flash_decode(
                q, k_pool, v_pool, block_tables=block_tables,
                lengths=lengths, layer=lyr[0] if lyr else None,
                kv_heads=None if exact else Hkv, softcap=softcap,
                scale=scale, interpret=_interpret())

        rep = (block_tables, lengths)
        if layer is not None:
            rep += (jnp.asarray(layer, jnp.int32),)
        return _per_device(mesh, run, (q, k_pool, v_pool), rep,
                           head_axes=(2, -1, -1), split=split)
    if split:
        from repro.sharding.serving import constrain_heads

        q = constrain_heads(q, mesh)
        k_pool = constrain_heads(k_pool, mesh, axis=k_pool.ndim - 1)
        v_pool = constrain_heads(v_pool, mesh, axis=v_pool.ndim - 1)
    return jnp_impl.paged_decode_attention_lengths(
        q, k_pool, v_pool, block_tables=block_tables, lengths=lengths,
        softcap=softcap, scale=scale, layer=layer,
        kv_heads=None if exact else Hkv)


def paged_latent_attention(q, pool, *, block_tables, lengths, v_dim,
                           layer=None, scale=None, impl="auto", mesh=None):
    """Absorbed-MLA decode over a paged latent pool.

    ``q`` (B, S, H, K) holds each slot's last S query rows folded into the
    latent space; ``pool`` is ``(num_blocks, block_size, W)`` latent rows
    (``W >= K``, lanes past the row zero), or with ``layer`` a per-layer
    stack of them.  Keys are a row's first ``K`` lanes and values its
    first ``v_dim``: all H heads share the one latent head.  Masking and
    tables as in :func:`paged_decode_attention`.  The pallas path is
    :func:`repro.kernels.paged_attention.paged_latent_decode` (replicated
    on every device of a mesh: one latent head offers nothing to split);
    the others materialize each slot's view."""
    B, S, H, K = q.shape
    lead = 0 if layer is None else 1
    L = block_tables.shape[1] * pool.shape[lead + 1]
    impl = _resolve(impl, S * L <= 256 * 256)
    if impl == "pallas":
        from repro.kernels import paged_attention  # lazy: TPU-targeted

        def run(q, pool, block_tables, lengths, *lyr):
            return paged_attention.paged_latent_decode(
                q, pool, block_tables=block_tables, lengths=lengths,
                v_dim=v_dim, layer=lyr[0] if lyr else None, scale=scale,
                interpret=_interpret())

        rep = (q, pool, block_tables, lengths)
        if layer is not None:
            rep += (jnp.asarray(layer, jnp.int32),)
        return _per_device(mesh, run, (), rep)
    view = jnp_impl.paged_gather(pool, block_tables, layer)  # (B, L, W)
    k = view[:, :, None, :K].astype(q.dtype)
    v = view[:, :, None, :v_dim].astype(q.dtype)
    if impl == "dense":
        kv_pos = jnp.broadcast_to(jnp.arange(L, dtype=jnp.int32)[None], (B, L))
        q_pos = lengths[:, None] - S + jnp.arange(S, dtype=jnp.int32)[None, :]
        return ref.attention_ref(q, k, v, q_pos=q_pos, kv_pos=kv_pos,
                                 causal=True, scale=scale)
    return jnp_impl.decode_attention_lengths(q, k, v, lengths=lengths,
                                             scale=scale)


def attention_with_prefix(q, k_self, v_self, k_pre, v_pre, *, pre_pos=None,
                          offset=None, softcap=0.0, scale=None, impl="auto",
                          mesh=None):
    """Causal self-attention plus a fully-visible KV prefix (MemCom memory).

    Computed as two FLOP-optimal partials merged exactly via log-sum-exp —
    the flash-decoding decomposition.  ``offset`` defaults to the prefix
    length (target tokens sit after the memory slots in RoPE space).
    """
    m = k_pre.shape[1]
    B = q.shape[0]
    if offset is None:
        offset = m
    if pre_pos is None:
        pre_pos = jnp.broadcast_to(jnp.arange(m, dtype=jnp.int32), (B, m))
    o_self, l_self = self_attention_causal(
        q, k_self, v_self, offset=offset, softcap=softcap, scale=scale,
        impl=impl, return_lse=True, mesh=mesh)
    q_pos = jnp.broadcast_to(
        offset + jnp.arange(q.shape[1], dtype=jnp.int32), (B, q.shape[1]))
    o_pre, l_pre = attention(
        q, k_pre, v_pre, q_pos=q_pos, kv_pos=pre_pos, causal=False,
        softcap=softcap, scale=scale, impl=impl, return_lse=True, mesh=mesh)
    return jnp_impl.combine_attention_partials([(o_self, l_self), (o_pre, l_pre)])


# ---------------------------------------------------------------------------
# MemCom layer-wise cross-attention (the paper's compressor hot spot)
# ---------------------------------------------------------------------------


def memcom_xattn(q, k, v, *, scale=None, impl="auto", mesh=None):
    """1-head cross-attention, head width = d_model: (B,M,D)x(B,T,D)->(B,M,D).
    With a ``mesh`` the Pallas kernel runs whole on every device (one head
    offers nothing to split)."""
    small = q.shape[1] * k.shape[1] <= 256 * 256
    impl = _resolve(impl, small)
    if impl == "dense":
        return ref.memcom_xattn_ref(q, k, v, scale=scale)
    if impl == "pallas":
        from repro.kernels import memcom_xattn as kx

        def run(q, k, v):
            return kx.memcom_xattn(q, k, v, scale=scale,
                                   interpret=_interpret())

        return _per_device(mesh, run, (), (q, k, v))
    # jnp streaming: reuse chunked attention with a single head
    B, M, D = q.shape
    T = k.shape[1]
    qh = q[:, :, None, :]
    kh = k[:, :, None, :]
    vh = v[:, :, None, :]
    q_pos = jnp.zeros((B, M), jnp.int32)
    kv_pos = jnp.zeros((B, T), jnp.int32)
    out = jnp_impl.attention_chunked(
        qh, kh, vh, q_pos=q_pos, kv_pos=kv_pos, causal=False, scale=scale,
        kv_chunk=1024)
    return out[:, :, 0, :]


# ---------------------------------------------------------------------------
# Grouped matmul (MoE expert compute)
# ---------------------------------------------------------------------------


def gmm_uses_kernel(impl="auto") -> bool:
    """Whether :func:`gmm` runs the Pallas kernel for ``impl``."""
    return _resolve(impl, small=False) == "pallas"


def gmm_rows(C: int) -> int:
    """Rows a kernel call without counts computes for each expert of a
    ``C``-row buffer (``C`` padded to whole row tiles)."""
    from repro.kernels import moe_gmm

    bc = moe_gmm._row_block(C)
    return -(-C // bc) * bc


def gmm(x, w, *, counts=None, block_c=None, impl="auto"):
    """(E,C,D) x (E,D,F) -> (E,C,F) per-expert matmul.  ``counts`` (E,):
    only each expert's first ``counts[e]`` rows are needed; the kernel
    then computes those alone (in row tiles of ``block_c``) and leaves the
    other rows undefined, the oracles compute every row."""
    small = x.shape[0] * x.shape[1] * x.shape[2] <= 64 * 64 * 64
    impl = _resolve(impl, small)
    if impl == "pallas":
        from repro.kernels import moe_gmm

        kw = {} if block_c is None else {"block_c": block_c}
        return moe_gmm.gmm(x, w, counts, interpret=_interpret(), **kw)
    return ref.gmm_ref(x, w)


# ---------------------------------------------------------------------------
# Mamba2 SSD
# ---------------------------------------------------------------------------


def ssd(x, dt, A, Bm, Cm, *, init_state=None, chunk=256, impl="auto"):
    small = x.shape[1] <= 64
    impl = _resolve(impl, small)
    if impl == "dense":
        return ref.ssd_ref(x, dt, A, Bm, Cm, init_state=init_state)
    if impl == "pallas":
        from repro.kernels import ssd_scan

        return ssd_scan.ssd(x, dt, A, Bm, Cm, init_state=init_state,
                            chunk=chunk, interpret=_interpret())
    return jnp_impl.ssd_chunked(x, dt, A, Bm, Cm, init_state=init_state, chunk=chunk)


ssd_decode_step = jnp_impl.ssd_decode_step

# paged-cache primitives (pure jnp, re-exported so model code depends on
# ops alone and the pallas kernel module stays a lazy import).
# layer= addresses one layer of a stacked pool in place.
# paged_scatter(valid=) is the fused serving step's ragged-lane contract:
# lanes >= valid[b] are geometry padding and land in the trash block.
paged_scatter = jnp_impl.paged_scatter
paged_gather = jnp_impl.paged_gather
