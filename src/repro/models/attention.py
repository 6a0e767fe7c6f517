"""GQA attention block: train / prefill / decode / cross / MemCom-prefix.

RoPE positions and mask order are deliberately decoupled: masking always
follows sequential text order (``mask_offset + arange``) while RoPE may use
M-RoPE 3-D position streams (Qwen2-VL).

MemCom integration: ``prefix`` carries the layer's compressed memory
representations, either as hidden states ``{"h": (B, m, D)}`` (training —
K/V derived through this layer's frozen projections, differentiable into
the compressor) or as a precomputed compressed KV cache
``{"k": (B, m, Hkv, hd), "v": ...}`` (serving).  Target tokens sit at
positions ``m..m+S`` and see every memory slot (positions ``0..m-1``).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.kernels import ops
from repro.models.layers import apply_rope
from repro.models.param import ParamBuilder
from repro.sharding.ctx import head_sharded


def init_attention(b: ParamBuilder, cfg: ModelConfig, name: str = "attn",
                   num_heads: int | None = None) -> None:
    d, hd = cfg.d_model, cfg.hd
    nh = num_heads or cfg.num_heads
    nkv = num_heads or cfg.num_kv_heads
    ab = b.child(name)
    ab.make("wq", (d, nh * hd), ("embed", "heads"))
    ab.make("wk", (d, nkv * hd), ("embed", "kv_heads"))
    ab.make("wv", (d, nkv * hd), ("embed", "kv_heads"))
    ab.make("wo", (nh * hd, d), ("heads", "embed"), fan_in=nh * hd)
    if cfg.attn_qkv_bias:
        ab.make("bq", (nh * hd,), ("heads",), init="zeros")
        ab.make("bk", (nkv * hd,), ("kv_heads",), init="zeros")
        ab.make("bv", (nkv * hd,), ("kv_heads",), init="zeros")


def _proj(x, w, b, n, hd):
    y = x @ w
    if b is not None:
        y = y + b
    return y.reshape(*x.shape[:-1], n, hd)


def project_q(p, cfg: ModelConfig, x, positions):
    q = _proj(x, p["wq"], p.get("bq"), -1, cfg.hd)
    if cfg.pos_embed == "rope":
        q = apply_rope(q, positions, cfg.rope_theta, cfg.mrope_sections)
    return q


def project_kv(p, cfg: ModelConfig, x, positions):
    """Roped K and V from hidden states — also used to build the MemCom
    compressed cache from memory representations (positions 0..m-1)."""
    k = _proj(x, p["wk"], p.get("bk"), -1, cfg.hd)
    v = _proj(x, p["wv"], p.get("bv"), -1, cfg.hd)
    if cfg.pos_embed == "rope":
        k = apply_rope(k, positions, cfg.rope_theta, cfg.mrope_sections)
    return k, v


def scatter_rows(cache, new, starts, valid=None):
    """Write ``new[b]`` into ``cache[b]`` at per-slot offsets ``starts[b]``
    along the sequence axis — the continuous-batching cache write, where
    every slot sits at its own ``base_len + tokens_consumed`` position.

    ``valid`` (B,) int32 (optional) is the fused-step ragged-lane mask:
    only lanes ``s < valid[b]`` are written; the rest scatter to the
    out-of-bounds sentinel row ``max_len`` and are dropped.  The masked
    path must NOT use ``dynamic_update_slice`` — its clamp semantics
    would shift a window whose garbage tail crosses ``max_len`` *back*
    over valid cache rows."""
    if valid is None:
        def one(c, u, s):
            return jax.lax.dynamic_update_slice_in_dim(
                c, u.astype(c.dtype), s, axis=0)
        return jax.vmap(one)(cache, new, starts)
    L = cache.shape[1]
    S = new.shape[1]
    pos = starts[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]  # (B,S)
    lane = jnp.arange(S, dtype=jnp.int32)[None, :]
    dest = jnp.where(lane < valid[:, None], pos, L)  # L = OOB -> dropped

    def one(c, u, d):
        return c.at[d].set(u.astype(c.dtype), mode="drop")

    return jax.vmap(one)(cache, new, dest)


def _prefix_kv(p, cfg: ModelConfig, prefix: dict):
    if "k" in prefix:
        return prefix["k"], prefix["v"]
    h = prefix["h"]
    B, m = h.shape[0], h.shape[1]
    pos = jnp.broadcast_to(jnp.arange(m, dtype=jnp.int32), (B, m))
    if cfg.mrope_sections:
        pos = jnp.broadcast_to(pos, (3, B, m))
    return project_kv(p, cfg, h, pos)


def apply_attention(
    p,
    cfg: ModelConfig,
    x,
    *,
    positions,
    mask_offset=0,
    prefix: Optional[dict] = None,
    cache: Optional[dict] = None,
    cache_index=None,
    kv_source=None,
    decode: bool = False,
    block_tables=None,
    lane_valid=None,
    mesh=None,
    layer=None,
    impl: str = "auto",
):
    """Returns (out (B,S,D), new_cache_or_None).  ``mesh`` (tensor-parallel
    serving) reaches the kernels, which split Q/K/V by head over its
    "model" axis while positions, per-slot lengths and block tables stay
    replicated — see :mod:`repro.sharding.serving`.

    ``lane_valid`` (B,) int32 (fused serving step, per-slot decode only)
    marks how many of the S lanes carry real tokens per slot: invalid
    lanes' KV writes are dropped (dense) or routed to the trash block
    (paged).  The attention *read* needs no masking — ``lengths =
    cache_index + S`` puts lane ``s`` at query position ``cache_index +
    s``, and causality already hides every cache row an invalid lane
    could have written.

    With ``block_tables`` (B, nb) the cache entries are *paged*: ``k``/``v``
    are shared lane-merged ``(num_blocks, block_size, W)`` pools (see
    :func:`init_paged_attn_cache`) and slot ``b``'s cache position ``p``
    lives at ``(block_tables[b, p // bs], p % bs)``.  With ``layer`` (a
    traced int, the layer scan's index) they are the whole stack
    ``(layers, num_blocks, block_size, W)``: this layer writes its rows
    into it at ``layer`` and reads only its own blocks, and the updated
    stack is returned, so no layer's pool is ever sliced out or copied.
    Decode requires the per-slot length vector; prefill continues behind
    the seated blocks (static ``cache_index`` base, as in the dense path).
    """
    B, S, _ = x.shape
    softcap = cfg.attn_logit_softcap
    scale = cfg.hd**-0.5

    # ---------------- cross-attention (enc-dec) ----------------
    if kv_source is not None or (cache is not None and "ck" in cache):
        q = _proj(x, p["wq"], p.get("bq"), -1, cfg.hd)  # no rope (whisper)
        if cache is not None and "ck" in cache:
            if kv_source is not None:  # prefill: project and store
                k = _proj(kv_source, p["wk"], p.get("bk"), -1, cfg.hd)
                v = _proj(kv_source, p["wv"], p.get("bv"), -1, cfg.hd)
                cache = {"ck": k.astype(cache["ck"].dtype), "cv": v.astype(cache["cv"].dtype)}
            k, v = cache["ck"], cache["cv"]
        else:
            k = _proj(kv_source, p["wk"], p.get("bk"), -1, cfg.hd)
            v = _proj(kv_source, p["wv"], p.get("bv"), -1, cfg.hd)
        F = k.shape[1]
        q_pos = jnp.zeros((B, S), jnp.int32)
        kv_pos = jnp.zeros((B, F), jnp.int32)
        out = ops.attention(q, k.astype(q.dtype), v.astype(q.dtype), q_pos=q_pos,
                            kv_pos=kv_pos, causal=False, softcap=softcap,
                            scale=scale, impl=impl, mesh=mesh)
        return out.reshape(B, S, -1) @ p["wo"], cache

    q = project_q(p, cfg, x, positions)

    # ---------------- decode: read/write KV cache ----------------
    if decode:
        assert cache is not None and cache_index is not None
        k_new, v_new = project_kv(p, cfg, x, positions)
        if block_tables is not None:
            # paged: scatter the new tokens into each slot's tail block,
            # then walk the block tables (shared prefix blocks are read by
            # every slot seated on the task but stored once)
            assert jnp.ndim(cache_index) == 1, "paged decode needs (slots,) lengths"
            k_pool = ops.paged_scatter(cache["k"], k_new, block_tables,
                                       cache_index, valid=lane_valid,
                                       layer=layer)
            v_pool = ops.paged_scatter(cache["v"], v_new, block_tables,
                                       cache_index, valid=lane_valid,
                                       layer=layer)
            out = ops.paged_decode_attention(
                q, k_pool, v_pool, block_tables=block_tables,
                lengths=cache_index + S, layer=layer,
                kv_heads=cfg.num_kv_heads, softcap=softcap, scale=scale,
                impl=impl, mesh=mesh)
            return out.reshape(B, S, -1) @ p["wo"], {"k": k_pool, "v": v_pool}
        if jnp.ndim(cache_index) == 1:
            # per-slot lengths (continuous batching): each slot writes at its
            # own offset and is masked to its own seated region only
            k_cache = scatter_rows(cache["k"], k_new, cache_index,
                                   valid=lane_valid)
            v_cache = scatter_rows(cache["v"], v_new, cache_index,
                                   valid=lane_valid)
            out = ops.decode_attention(
                q, k_cache.astype(q.dtype), v_cache.astype(q.dtype),
                lengths=cache_index + S, softcap=softcap, scale=scale,
                impl=impl, mesh=mesh)
            return out.reshape(B, S, -1) @ p["wo"], {"k": k_cache, "v": v_cache}
        k_cache = jax.lax.dynamic_update_slice_in_dim(
            cache["k"], k_new.astype(cache["k"].dtype), cache_index, axis=1)
        v_cache = jax.lax.dynamic_update_slice_in_dim(
            cache["v"], v_new.astype(cache["v"].dtype), cache_index, axis=1)
        max_len = k_cache.shape[1]
        slot = jnp.arange(max_len, dtype=jnp.int32)
        kv_pos = jnp.where(slot < cache_index + S, slot, -1)
        kv_pos = jnp.broadcast_to(kv_pos, (B, max_len))
        q_pos = cache_index + jnp.arange(S, dtype=jnp.int32)
        q_pos = jnp.broadcast_to(q_pos, (B, S))
        out = ops.attention(q, k_cache.astype(q.dtype), v_cache.astype(q.dtype),
                            q_pos=q_pos, kv_pos=kv_pos, causal=True,
                            softcap=softcap, scale=scale, impl=impl, mesh=mesh)
        return out.reshape(B, S, -1) @ p["wo"], {"k": k_cache, "v": v_cache}

    # ---------------- train / prefill: full self-attention ----------------
    k, v = project_kv(p, cfg, x, positions)
    # TP-attention layout — one seq gather per layer instead of one per
    # q-chunk/kv-chunk inside the streaming kernels.  Applied only when
    # the KV heads divide the model axis: otherwise the GQA fold reshape
    # (Hq → Hkv×G) cannot preserve the shard and XLA falls back to
    # "involuntary full rematerialization" (measured: +3 % on jamba,
    # whose kv=8 < 16 — EXPERIMENTS.md §Perf H4).
    k_sh = head_sharded(k)
    if k_sh is not k:
        q, k, v = head_sharded(q), k_sh, head_sharded(v)
    if (prefix is None and cache is not None
            and isinstance(cache_index, int) and cache_index > 0):
        # prefill continuation: slots [0, cache_index) are already seated
        # (compressed memory or an earlier prefill segment) — attend to
        # them as a fully-visible prefix.  Static start only.
        if block_tables is not None:
            bs = cache["k"].shape[-2]
            nbt = -(-cache_index // bs)  # ceil: blocks covering the base
            blk = block_tables[:, :nbt]
            lanes = cfg.num_kv_heads * cfg.hd
            prefix = {
                key: ops.paged_gather(cache[key], blk, layer)
                [:, :cache_index, :lanes]
                .reshape(B, cache_index, cfg.num_kv_heads, cfg.hd)
                .astype(x.dtype)
                for key in ("k", "v")
            }
        else:
            prefix = {"k": cache["k"][:, :cache_index].astype(x.dtype),
                      "v": cache["v"][:, :cache_index].astype(x.dtype)}
    if prefix is not None:
        k_pre, v_pre = _prefix_kv(p, cfg, prefix)
        m = k_pre.shape[1]
        out = ops.attention_with_prefix(
            q, k, v, k_pre.astype(q.dtype), v_pre.astype(q.dtype),
            offset=mask_offset if mask_offset else m,
            softcap=softcap, scale=scale, impl=impl, mesh=mesh)
    else:
        out = ops.self_attention_causal(q, k, v, offset=mask_offset,
                                        softcap=softcap, scale=scale, impl=impl,
                                        mesh=mesh)
    new_cache = None
    if cache is not None:  # prefill writes the cache
        start = cache_index if cache_index is not None else 0
        if block_tables is not None:
            starts = jnp.full((B,), start, jnp.int32)
            new_cache = {
                "k": ops.paged_scatter(cache["k"], k, block_tables, starts,
                                       layer=layer),
                "v": ops.paged_scatter(cache["v"], v, block_tables, starts,
                                       layer=layer),
            }
        else:
            new_cache = {
                "k": jax.lax.dynamic_update_slice_in_dim(
                    cache["k"], k.astype(cache["k"].dtype), start, axis=1),
                "v": jax.lax.dynamic_update_slice_in_dim(
                    cache["v"], v.astype(cache["v"].dtype), start, axis=1),
            }
    return out.reshape(B, S, -1) @ p["wo"], new_cache


def init_attn_cache(cfg: ModelConfig, batch: int, max_len: int, dtype) -> dict:
    nkv, hd = cfg.num_kv_heads, cfg.hd
    return {
        "k": jnp.zeros((batch, max_len, nkv, hd), dtype),
        "v": jnp.zeros((batch, max_len, nkv, hd), dtype),
    }


def init_paged_attn_cache(cfg: ModelConfig, num_blocks: int, block_size: int,
                          dtype) -> dict:
    """One layer's K/V block pools, lane-merged: ``(num_blocks, block_size,
    W)``, head ``g`` at lanes ``[g*hd, (g+1)*hd)`` of every row and zeros
    from ``Hkv*hd`` up to ``W``, the next multiple of 128 lanes — the
    layout the paged decode kernel reads a block in, so no step relays the
    pool out.  (With rows off the 128-lane tile, e.g. 320 lanes, the TPU
    would lay the pool out with the blocks minor and every step would
    relay it out on entry and back on exit; the tiled layout pads such
    rows to 384 lanes in memory anyway.)  Writes and reads reshape
    ``(..., Hkv, hd)`` rows to and from it on the rows they touch only."""
    nkv, hd = cfg.num_kv_heads, cfg.hd
    lanes = -(-nkv * hd // 128) * 128
    return {
        "k": jnp.zeros((num_blocks, block_size, lanes), dtype),
        "v": jnp.zeros((num_blocks, block_size, lanes), dtype),
    }


def init_cross_cache(cfg: ModelConfig, batch: int, num_frames: int, dtype) -> dict:
    nh, hd = cfg.num_heads, cfg.hd
    return {
        "ck": jnp.zeros((batch, num_frames, nh, hd), dtype),
        "cv": jnp.zeros((batch, num_frames, nh, hd), dtype),
    }
