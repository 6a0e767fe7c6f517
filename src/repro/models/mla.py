"""Multi-head Latent Attention (DeepSeek-V2).

Train/prefill use the standard (non-absorbed) form; decode uses the
*absorbed* form, where attention runs directly in the compressed latent
space: queries are folded through W_uk so the whole step is MQA with one
shared (kv_lora + rope)-wide key and a kv_lora-wide value — this is the
memory/computation win that makes the 512-float-per-token cache usable.

MemCom composes naturally: the compressed memory representations O^i are
pushed through the frozen W_dkv, so the prefix cache is itself an MLA
latent cache (two-level compression — see DESIGN.md §4).

The cache holds one latent row per position, ``[ckv | k_rope]``
(``kv_lora_rank + qk_rope_head_dim`` lanes): the key of the absorbed
decode is the whole row and its value the first ``kv_lora_rank`` lanes,
so both are read from the one array with no concatenation.  A paged pool
pads the row with zeros to a multiple of 128 lanes (``[ckv 512 | k_rope
64 | 0 64]`` at DeepSeek-V2 widths), the layout the TPU reads in place.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.kernels import ops
from repro.models.layers import apply_rope, rope_attention_scale
from repro.models.param import ParamBuilder


def init_mla(b: ParamBuilder, cfg: ModelConfig, name: str = "attn") -> None:
    m = cfg.mla
    d, nh = cfg.d_model, cfg.num_heads
    ab = b.child(name)
    if m.q_lora_rank is None:
        ab.make("wq", (d, nh * m.qk_head_dim), ("embed", "heads"))
    else:
        ab.make("wdq", (d, m.q_lora_rank), ("embed", "mla_lora"))
        ab.make("q_norm", (m.q_lora_rank,), ("mla_lora",), init="ones")
        ab.make("wuq", (m.q_lora_rank, nh * m.qk_head_dim), ("mla_lora", "heads"))
    ab.make("wdkv", (d, m.kv_lora_rank + m.qk_rope_head_dim), ("embed", "mla_lora"))
    ab.make("kv_norm", (m.kv_lora_rank,), ("mla_lora",), init="ones")
    ab.make("wukv", (m.kv_lora_rank, nh * (m.qk_nope_head_dim + m.v_head_dim)),
            ("mla_lora", "heads"))
    ab.make("wo", (nh * m.v_head_dim, d), ("heads", "embed"), fan_in=nh * m.v_head_dim)


def softmax_scale(cfg: ModelConfig) -> float:
    """``qk_head_dim ** -0.5``, times YaRN's ``mscale ** 2`` if scaled."""
    return cfg.mla.qk_head_dim**-0.5 * rope_attention_scale(cfg.rope_scaling)


def pool_width(cfg: ModelConfig) -> int:
    """Lanes of a paged latent pool row: the latent row padded to 128."""
    return -(-cfg.mla.latent_dim // 128) * 128


def _rms(x, scale, eps):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt((xf**2).mean(-1, keepdims=True) + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def _rope(cfg: ModelConfig, x, positions):
    return apply_rope(x, positions, cfg.rope_theta, scaling=cfg.rope_scaling)


def _latent(p, cfg: ModelConfig, x, positions):
    """x -> (ckv_norm (B,S,R), k_rope (B,S,1,rd))."""
    m = cfg.mla
    ckv_full = x @ p["wdkv"]
    ckv, k_rope = jnp.split(ckv_full, [m.kv_lora_rank], axis=-1)
    ckv = _rms(ckv, p["kv_norm"], cfg.norm_eps)
    k_rope = _rope(cfg, k_rope[:, :, None, :], positions)
    return ckv, k_rope


def latent_rows(p, cfg: ModelConfig, x, positions):
    """x (B,S,d) -> the latent cache rows ``[ckv | k_rope]`` (B,S,R+rd):
    what the cache stores, for the prompt's own tokens and for MemCom's
    O^i alike."""
    ckv, k_rope = _latent(p, cfg, x, positions)
    return jnp.concatenate([ckv, k_rope[:, :, 0, :]], axis=-1)


def _split_rows(cfg: ModelConfig, rows):
    """Latent rows (…, >= R+rd lanes) -> (ckv (…, R), k_rope (…, rd))."""
    m = cfg.mla
    return rows[..., :m.kv_lora_rank], rows[..., m.kv_lora_rank:m.latent_dim]


def _queries(p, cfg: ModelConfig, x, positions):
    m = cfg.mla
    nh = cfg.num_heads
    if m.q_lora_rank is None:
        q = x @ p["wq"]
    else:
        q = _rms(x @ p["wdq"], p["q_norm"], cfg.norm_eps) @ p["wuq"]
    q = q.reshape(*x.shape[:-1], nh, m.qk_head_dim)
    q_nope, q_rope = jnp.split(q, [m.qk_nope_head_dim], axis=-1)
    return q_nope, _rope(cfg, q_rope, positions)


def _expand_kv(p, cfg: ModelConfig, ckv):
    m = cfg.mla
    nh = cfg.num_heads
    kv = (ckv @ p["wukv"]).reshape(*ckv.shape[:-1], nh, m.qk_nope_head_dim + m.v_head_dim)
    return jnp.split(kv, [m.qk_nope_head_dim], axis=-1)  # k_nope, v


def _heads_k(k_nope, k_rope):
    """Per-head keys: each head's nope lanes, then the shared rope lanes."""
    rd = k_rope.shape[-1]
    return jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope, (*k_nope.shape[:3], rd))], axis=-1)


def apply_mla(
    p,
    cfg: ModelConfig,
    x,
    *,
    positions,
    mask_offset=0,
    prefix: Optional[dict] = None,
    cache: Optional[dict] = None,
    cache_index=None,
    decode: bool = False,
    block_tables=None,
    lane_valid=None,
    mesh=None,
    layer=None,
    impl: str = "auto",
):
    """Returns (out, new_cache_or_None).  Cache = {"lat"}: latent rows
    ``[ckv | k_rope]``, dense ``(B, L, R+rd)`` or a paged pool ``(N, bs,
    W)`` (rows zero-padded to ``W`` lanes, see :func:`pool_width`).

    ``lane_valid`` (B,) int32 (fused serving step, per-slot decode only):
    lanes ``s >= lane_valid[b]`` are geometry padding — their latent-cache
    writes are dropped (dense) or routed to the trash block (paged); the
    absorbed-MQA read is already causally masked per lane, exactly as in
    :func:`repro.models.attention.apply_attention`.

    ``mesh`` is accepted for decode-kernel parity with
    :func:`repro.models.attention.apply_attention` but the absorbed-MQA
    decode runs with a *single* shared latent KV head — nothing to split
    on the model axis, so the latent cache stays replicated and the
    kernels fall back to their unsharded form (the per-head q_abs/out
    einsums around them still partition under GSPMD).

    With ``block_tables`` the latent cache is paged: ``lat`` is a
    ``(num_blocks, block_size, W)`` pool indexed per slot through the
    table — the absorbed-MQA decode walks blocks instead of a contiguous
    stripe (:func:`repro.kernels.ops.paged_latent_attention`), and prefix
    blocks shared across slots are stored once.  With ``layer`` it is the
    layer scan's whole stack, written at ``layer`` in place (see
    :func:`repro.models.attention.apply_attention`).
    """
    m = cfg.mla
    B, S, _ = x.shape
    scale = softmax_scale(cfg)

    if decode:
        with jax.named_scope("mla.decode"):
            return _absorbed_decode(p, cfg, x, positions, cache, cache_index,
                                    block_tables, lane_valid, mesh, layer,
                                    impl, scale)

    # ---------------- train / prefill: non-absorbed ----------------
    with jax.named_scope("mla.prefill"):
        q_nope, q_rope = _queries(p, cfg, x, positions)
        if (prefix is None and cache is not None
                and isinstance(cache_index, int) and cache_index > 0):
            # prefill continuation over already-seated latent rows
            if block_tables is not None:
                bs_blk = cache["lat"].shape[-2]
                nbt = -(-cache_index // bs_blk)
                rows = ops.paged_gather(cache["lat"], block_tables[:, :nbt],
                                        layer)
            else:
                rows = cache["lat"]
            prefix = {"lat": rows[:, :cache_index]}
        ckv, k_rope = _latent(p, cfg, x, positions)
        k_nope, v = _expand_kv(p, cfg, ckv)
        k = _heads_k(k_nope, k_rope)
        q = jnp.concatenate([q_nope, q_rope], axis=-1)

        if prefix is not None:
            if "lat" in prefix:
                ckv_pre, kr_pre = _split_rows(cfg, prefix["lat"])
            else:  # derive the latent prefix from compressed hiddens O^i
                h_pre = prefix["h"]
                mlen = h_pre.shape[1]
                pre_pos = jnp.broadcast_to(jnp.arange(mlen, dtype=jnp.int32), (B, mlen))
                ckv_pre, kr4 = _latent(p, cfg, h_pre, pre_pos)
                kr_pre = kr4[:, :, 0, :]
            kn_pre, v_pre = _expand_kv(p, cfg, ckv_pre.astype(x.dtype))
            mlen = ckv_pre.shape[1]
            k_pre = _heads_k(kn_pre, kr_pre[:, :, None, :].astype(x.dtype))
            out = ops.attention_with_prefix(
                q, k, v, k_pre.astype(q.dtype), v_pre.astype(q.dtype),
                offset=mask_offset if mask_offset else mlen, scale=scale,
                impl=impl, mesh=mesh)
        else:
            out = ops.self_attention_causal(q, k, v, offset=mask_offset,
                                            scale=scale, impl=impl, mesh=mesh)
        new_cache = None
        if cache is not None:
            start = cache_index if cache_index is not None else 0
            rows = jnp.concatenate([ckv, k_rope[:, :, 0, :]], axis=-1)
            if block_tables is not None:
                starts = jnp.full((B,), start, jnp.int32)
                lat = ops.paged_scatter(cache["lat"], rows, block_tables,
                                        starts, layer=layer)
            else:
                lat = jax.lax.dynamic_update_slice_in_dim(
                    cache["lat"], rows.astype(cache["lat"].dtype), start,
                    axis=1)
            new_cache = {"lat": lat}
        return out.reshape(B, S, -1) @ p["wo"], new_cache


def _absorbed_decode(p, cfg, x, positions, cache, cache_index, block_tables,
                     lane_valid, mesh, layer, impl, scale):
    """Decode in the latent space: queries folded through W_uk read the
    latent rows as one shared key head (all lanes) and value head (the
    first R lanes); the output is unfolded through W_uv."""
    m = cfg.mla
    B, S, _ = x.shape
    nh = cfg.num_heads
    assert cache is not None and cache_index is not None
    q_nope, q_rope = _queries(p, cfg, x, positions)
    rows = latent_rows(p, cfg, x, positions)  # (B,S,R+rd)
    per_slot = jnp.ndim(cache_index) == 1
    if block_tables is not None:
        assert per_slot, "paged decode needs (slots,) lengths"
        lat = ops.paged_scatter(cache["lat"], rows, block_tables, cache_index,
                                valid=lane_valid, layer=layer)
    elif per_slot:
        from repro.models.attention import scatter_rows

        lat = scatter_rows(cache["lat"], rows, cache_index, valid=lane_valid)
    else:
        lat = jax.lax.dynamic_update_slice_in_dim(
            cache["lat"], rows.astype(cache["lat"].dtype), cache_index, axis=1)
    # fold q through W_uk:  q_abs[b,s,h,R] = q_nope . wuk[h]
    wukv = p["wukv"].reshape(m.kv_lora_rank, nh, m.qk_nope_head_dim + m.v_head_dim)
    q_abs = jnp.einsum("bshd,rhd->bshr", q_nope, wukv[:, :, :m.qk_nope_head_dim])
    q_eff = jnp.concatenate([q_abs, q_rope.astype(q_abs.dtype)], axis=-1)
    lengths = cache_index + S
    if block_tables is not None:
        o_lat = ops.paged_latent_attention(
            q_eff, lat, block_tables=block_tables, lengths=lengths,
            layer=layer, v_dim=m.kv_lora_rank, scale=scale, impl=impl,
            mesh=mesh)
    else:
        k_eff = lat[:, :, None, :].astype(q_eff.dtype)
        v_eff = lat[:, :, None, :m.kv_lora_rank].astype(q_eff.dtype)
        if per_slot:
            o_lat = ops.decode_attention(q_eff, k_eff, v_eff, lengths=lengths,
                                         scale=scale, impl=impl, mesh=mesh)
        else:
            max_len = k_eff.shape[1]
            slot = jnp.arange(max_len, dtype=jnp.int32)
            kv_pos = jnp.broadcast_to(jnp.where(slot < lengths, slot, -1),
                                      (B, max_len))
            q_pos = jnp.broadcast_to(cache_index + jnp.arange(S, dtype=jnp.int32), (B, S))
            o_lat = ops.attention(q_eff, k_eff, v_eff, q_pos=q_pos,
                                  kv_pos=kv_pos, causal=True, scale=scale,
                                  impl=impl, mesh=mesh)  # (B,S,nh,R)
    out = jnp.einsum("bshr,rhd->bshd", o_lat, wukv[:, :, m.qk_nope_head_dim:])
    return out.reshape(B, S, -1) @ p["wo"], {"lat": lat}


def init_mla_cache(cfg: ModelConfig, batch: int, max_len: int, dtype) -> dict:
    return {"lat": jnp.zeros((batch, max_len, cfg.mla.latent_dim), dtype)}


def init_paged_mla_cache(cfg: ModelConfig, num_blocks: int, block_size: int,
                         dtype) -> dict:
    return {"lat": jnp.zeros((num_blocks, block_size, pool_width(cfg)), dtype)}
