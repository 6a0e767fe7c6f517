"""Multi-head Latent Attention (DeepSeek-V2).

Train/prefill use the standard (non-absorbed) form; decode uses the
*absorbed* form, where attention runs directly in the compressed latent
space: queries are folded through W_uk so the whole step is MQA with one
shared (kv_lora + rope)-wide key and a kv_lora-wide value — this is the
memory/computation win that makes the 512-float-per-token cache usable.

MemCom composes naturally: the compressed memory representations O^i are
pushed through the frozen W_dkv, so the prefix cache is itself an MLA
latent cache (two-level compression — see DESIGN.md §4).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.kernels import ops
from repro.models.layers import apply_rope
from repro.models.param import ParamBuilder


def init_mla(b: ParamBuilder, cfg: ModelConfig, name: str = "attn") -> None:
    m = cfg.mla
    d, nh = cfg.d_model, cfg.num_heads
    ab = b.child(name)
    ab.make("wdq", (d, m.q_lora_rank), ("embed", "mla_lora"))
    ab.make("q_norm", (m.q_lora_rank,), ("mla_lora",), init="ones")
    ab.make("wuq", (m.q_lora_rank, nh * m.qk_head_dim), ("mla_lora", "heads"))
    ab.make("wdkv", (d, m.kv_lora_rank + m.qk_rope_head_dim), ("embed", "mla_lora"))
    ab.make("kv_norm", (m.kv_lora_rank,), ("mla_lora",), init="ones")
    ab.make("wukv", (m.kv_lora_rank, nh * (m.qk_nope_head_dim + m.v_head_dim)),
            ("mla_lora", "heads"))
    ab.make("wo", (nh * m.v_head_dim, d), ("heads", "embed"), fan_in=nh * m.v_head_dim)


def _rms(x, scale, eps):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt((xf**2).mean(-1, keepdims=True) + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def _latent(p, cfg: ModelConfig, x, positions):
    """x -> (ckv_norm (B,S,R), k_rope (B,S,1,rd)) — the MLA cache entries."""
    m = cfg.mla
    ckv_full = x @ p["wdkv"]
    ckv, k_rope = jnp.split(ckv_full, [m.kv_lora_rank], axis=-1)
    ckv = _rms(ckv, p["kv_norm"], cfg.norm_eps)
    k_rope = apply_rope(k_rope[:, :, None, :], positions, cfg.rope_theta)
    return ckv, k_rope


def _queries(p, cfg: ModelConfig, x, positions):
    m = cfg.mla
    nh = cfg.num_heads
    cq = _rms(x @ p["wdq"], p["q_norm"], cfg.norm_eps)
    q = (cq @ p["wuq"]).reshape(*x.shape[:-1], nh, m.qk_head_dim)
    q_nope, q_rope = jnp.split(q, [m.qk_nope_head_dim], axis=-1)
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)
    return q_nope, q_rope


def _expand_kv(p, cfg: ModelConfig, ckv):
    m = cfg.mla
    nh = cfg.num_heads
    kv = (ckv @ p["wukv"]).reshape(*ckv.shape[:-1], nh, m.qk_nope_head_dim + m.v_head_dim)
    return jnp.split(kv, [m.qk_nope_head_dim], axis=-1)  # k_nope, v


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
    """Returns (out, new_cache_or_None).  Cache = {"ckv", "kr"}.

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

    With ``block_tables`` the latent cache is paged: ``ckv``/``kr`` are
    ``(num_blocks, block_size, ...)`` pools indexed per slot through the
    table — the absorbed-MQA decode walks blocks instead of a contiguous
    stripe, and prefix blocks shared across slots are stored once.  With
    ``layer`` they are the layer scan's whole stacks, written at ``layer``
    in place (see :func:`repro.models.attention.apply_attention`).
    """
    m = cfg.mla
    B, S, _ = x.shape
    nh = cfg.num_heads
    scale = m.qk_head_dim**-0.5

    q_nope, q_rope = _queries(p, cfg, x, positions)

    if decode:  # ---------------- absorbed decode ----------------
        assert cache is not None and cache_index is not None
        ckv_new, kr_new = _latent(p, cfg, x, positions)
        per_slot = jnp.ndim(cache_index) == 1
        if block_tables is not None:
            assert per_slot, "paged decode needs (slots,) lengths"
            ckv_cache = ops.paged_scatter(cache["ckv"], ckv_new, block_tables,
                                          cache_index, valid=lane_valid,
                                          layer=layer)
            kr_cache = ops.paged_scatter(cache["kr"], kr_new[:, :, 0, :],
                                         block_tables, cache_index,
                                         valid=lane_valid, layer=layer)
        elif per_slot:
            from repro.models.attention import scatter_rows

            ckv_cache = scatter_rows(cache["ckv"], ckv_new, cache_index,
                                     valid=lane_valid)
            kr_cache = scatter_rows(cache["kr"], kr_new[:, :, 0, :],
                                    cache_index, valid=lane_valid)
        else:
            ckv_cache = jax.lax.dynamic_update_slice_in_dim(
                cache["ckv"], ckv_new.astype(cache["ckv"].dtype), cache_index, axis=1)
            kr_cache = jax.lax.dynamic_update_slice_in_dim(
                cache["kr"], kr_new[:, :, 0, :].astype(cache["kr"].dtype), cache_index, axis=1)
        # fold q through W_uk:  q_abs[b,s,h,R] = q_nope . wuk[h]
        wukv = p["wukv"].reshape(m.kv_lora_rank, nh, m.qk_nope_head_dim + m.v_head_dim)
        wuk = wukv[:, :, : m.qk_nope_head_dim]
        q_abs = jnp.einsum("bshd,rhd->bshr", q_nope, wuk)
        q_eff = jnp.concatenate([q_abs, q_rope], axis=-1)  # (B,S,nh,R+rd)
        # MQA: 1 shared kv head (dense caches: axis 1 = positions; paged:
        # the whole pool is concatenated — same O(cache) data movement as
        # dense; splitting the latent/rope dot inside the kernel would
        # remove it entirely)
        ckv_l, kr_l = ckv_cache, kr_cache
        if layer is not None:  # this layer's pools out of the scan's stacks
            ckv_l, kr_l = ckv_cache[layer], kr_cache[layer]
        k_eff = jnp.concatenate([ckv_l, kr_l], axis=-1)[:, :, None, :]
        v_eff = ckv_l[:, :, None, :]
        if block_tables is not None:
            o_lat = ops.paged_decode_attention(
                q_eff, k_eff.astype(q_eff.dtype), v_eff.astype(q_eff.dtype),
                block_tables=block_tables, lengths=cache_index + S,
                scale=scale, impl=impl, mesh=mesh)
        elif per_slot:
            o_lat = ops.decode_attention(
                q_eff, k_eff.astype(q_eff.dtype), v_eff.astype(q_eff.dtype),
                lengths=cache_index + S, scale=scale, impl=impl, mesh=mesh)
        else:
            max_len = k_eff.shape[1]
            slot = jnp.arange(max_len, dtype=jnp.int32)
            kv_pos = jnp.broadcast_to(jnp.where(slot < cache_index + S, slot, -1), (B, max_len))
            q_pos = jnp.broadcast_to(cache_index + jnp.arange(S, dtype=jnp.int32), (B, S))
            o_lat = ops.attention(q_eff, k_eff.astype(q_eff.dtype), v_eff.astype(q_eff.dtype),
                                  q_pos=q_pos, kv_pos=kv_pos, causal=True,
                                  scale=scale, impl=impl, mesh=mesh)  # (B,S,nh,R)
        wuv = wukv[:, :, m.qk_nope_head_dim :]
        out = jnp.einsum("bshr,rhd->bshd", o_lat, wuv)
        return out.reshape(B, S, -1) @ p["wo"], {"ckv": ckv_cache, "kr": kr_cache}

    # ---------------- train / prefill: non-absorbed ----------------
    if (prefix is None and cache is not None
            and isinstance(cache_index, int) and cache_index > 0):
        # prefill continuation over already-seated latent slots
        if block_tables is not None:
            bs_blk = cache["ckv"].shape[-2]
            nbt = -(-cache_index // bs_blk)
            blk = block_tables[:, :nbt]
            prefix = {
                key: ops.paged_gather(cache[key], blk, layer)[:, :cache_index]
                for key in ("ckv", "kr")
            }
        else:
            prefix = {"ckv": cache["ckv"][:, :cache_index],
                      "kr": cache["kr"][:, :cache_index]}
    ckv, k_rope = _latent(p, cfg, x, positions)
    k_nope, v = _expand_kv(p, cfg, ckv)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope, (*k_nope.shape[:3], m.qk_rope_head_dim))], axis=-1)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)

    if prefix is not None:
        if "ckv" in prefix:
            ckv_pre, kr_pre = prefix["ckv"], prefix["kr"]
        else:  # derive latent prefix from compressed memory hiddens O^i
            h_pre = prefix["h"]
            mlen = h_pre.shape[1]
            pre_pos = jnp.broadcast_to(jnp.arange(mlen, dtype=jnp.int32), (B, mlen))
            ckv_pre, kr4 = _latent(p, cfg, h_pre, pre_pos)
            kr_pre = kr4[:, :, 0, :]
        kn_pre, v_pre = _expand_kv(p, cfg, ckv_pre)
        mlen = ckv_pre.shape[1]
        k_pre = jnp.concatenate(
            [kn_pre, jnp.broadcast_to(kr_pre[:, :, None, :], (*kn_pre.shape[:3], m.qk_rope_head_dim))],
            axis=-1)
        out = ops.attention_with_prefix(
            q, k, v, k_pre.astype(q.dtype), v_pre.astype(q.dtype),
            offset=mask_offset if mask_offset else mlen, scale=scale, impl=impl,
            mesh=mesh)
    else:
        out = ops.self_attention_causal(q, k, v, offset=mask_offset,
                                        scale=scale, impl=impl, mesh=mesh)
    new_cache = None
    if cache is not None:
        start = cache_index if cache_index is not None else 0
        if block_tables is not None:
            starts = jnp.full((B,), start, jnp.int32)
            new_cache = {
                "ckv": ops.paged_scatter(cache["ckv"], ckv, block_tables,
                                         starts, layer=layer),
                "kr": ops.paged_scatter(cache["kr"], k_rope[:, :, 0, :],
                                        block_tables, starts, layer=layer),
            }
        else:
            new_cache = {
                "ckv": jax.lax.dynamic_update_slice_in_dim(
                    cache["ckv"], ckv.astype(cache["ckv"].dtype), start, axis=1),
                "kr": jax.lax.dynamic_update_slice_in_dim(
                    cache["kr"], k_rope[:, :, 0, :].astype(cache["kr"].dtype), start, axis=1),
            }
    return out.reshape(B, S, -1) @ p["wo"], new_cache


def init_mla_cache(cfg: ModelConfig, batch: int, max_len: int, dtype) -> dict:
    m = cfg.mla
    return {
        "ckv": jnp.zeros((batch, max_len, m.kv_lora_rank), dtype),
        "kr": jnp.zeros((batch, max_len, m.qk_rope_head_dim), dtype),
    }


def init_paged_mla_cache(cfg: ModelConfig, num_blocks: int, block_size: int,
                         dtype) -> dict:
    m = cfg.mla
    return {
        "ckv": jnp.zeros((num_blocks, block_size, m.kv_lora_rank), dtype),
        "kr": jnp.zeros((num_blocks, block_size, m.qk_rope_head_dim), dtype),
    }
