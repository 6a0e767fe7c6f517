"""Transformer block: sequence mixer + channel MLP, all families.

``memcom`` (when given) injects the paper's compression cross-attention
between the self-attention and MLP residual branches and captures
``omega`` — the per-layer compressed representation handed to the target.
"""

from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from repro.config import LayerDesc, ModelConfig
from repro.models.attention import (
    apply_attention,
    init_attention,
    init_attn_cache,
    init_cross_cache,
    init_paged_attn_cache,
)
from repro.models.layers import apply_mlp, apply_norm, init_mlp, init_norm
from repro.models.mamba2 import apply_mamba, init_mamba, init_mamba_cache
from repro.models.mla import apply_mla, init_mla, init_mla_cache, init_paged_mla_cache
from repro.models.moe import apply_moe, init_moe
from repro.models.param import ParamBuilder
from repro.models.xattn import apply_memcom_xattn


def init_block(b: ParamBuilder, cfg: ModelConfig, desc: LayerDesc) -> None:
    init_norm(b, cfg, "norm1")
    if desc.mixer == "attn":
        init_attention(b, cfg)
    elif desc.mixer == "mla":
        init_mla(b, cfg)
    elif desc.mixer == "mamba":
        init_mamba(b, cfg)
    else:
        raise ValueError(desc.mixer)
    if desc.cross_attn:
        init_norm(b, cfg, "norm_x")
        init_attention(b, cfg, name="xattn_enc")
    if desc.mlp != "none":
        init_norm(b, cfg, "norm2")
        if desc.mlp == "moe":
            init_moe(b, cfg)
        else:
            init_mlp(b, cfg)


def apply_block(
    p,
    cfg: ModelConfig,
    desc: LayerDesc,
    h,
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
    encoder_out=None,
    memcom: Optional[dict] = None,
    impl: str = "auto",
):
    """Returns (h, new_cache_or_None, aux{moe_loss, moe_rows, omega}):
    ``moe_rows`` the row counts of :func:`repro.models.moe.apply_moe`
    (held experts + 1 + tokens,) int32, for an MoE block (None
    otherwise).

    ``block_tables`` routes the attention/MLA cache entries through the
    paged block-pool layout; recurrent (conv/ssm) and cross-attention
    entries stay per-slot dense either way.  ``layer`` (the layer scan's
    index) says the pool entries are the scan's whole per-layer stacks,
    updated in place at that layer.

    ``lane_valid`` (fused serving step) masks ragged decode lanes in the
    attention/MLA cache writes.  Recurrent mixers cannot honour it (the
    SSM state would advance over garbage lanes regardless), which is why
    the engine gates the fused path to attention/MLA-only layouts.
    """
    aux = {"moe_loss": jnp.float32(0.0), "moe_rows": None, "omega": None}
    new_cache = {} if cache is not None else None

    # ---- sequence mixer ----
    hn = apply_norm(p["norm1"], cfg, h)
    if desc.mixer == "attn":
        self_cache = None
        if cache is not None and "k" in cache:
            self_cache = {"k": cache["k"], "v": cache["v"]}
        o, c = apply_attention(
            p["attn"], cfg, hn, positions=positions, mask_offset=mask_offset,
            prefix=prefix, cache=self_cache, cache_index=cache_index,
            decode=decode, block_tables=block_tables, lane_valid=lane_valid,
            mesh=mesh, layer=layer, impl=impl)
        if c is not None:
            new_cache.update(c)
    elif desc.mixer == "mla":
        self_cache = None
        if cache is not None and "lat" in cache:
            self_cache = {"lat": cache["lat"]}
        o, c = apply_mla(
            p["attn"], cfg, hn, positions=positions, mask_offset=mask_offset,
            prefix=prefix, cache=self_cache, cache_index=cache_index,
            decode=decode, block_tables=block_tables, lane_valid=lane_valid,
            mesh=mesh, layer=layer, impl=impl)
        if c is not None:
            new_cache.update(c)
    else:  # mamba
        self_cache = None
        if cache is not None and "conv" in cache:
            self_cache = {"conv": cache["conv"], "ssm": cache["ssm"]}
        init_state = None
        if prefix is not None and "ssm" in prefix:
            init_state = prefix["ssm"]  # hybrid MemCom state handoff
        o, c = apply_mamba(p["mamba"], cfg, hn, cache=self_cache,
                           decode=decode, init_state=init_state, impl=impl)
        if c is not None:
            new_cache.update(c)
    h = h + o

    # ---- enc-dec cross-attention (whisper decoder) ----
    if desc.cross_attn:
        hx = apply_norm(p["norm_x"], cfg, h)
        cross_cache = None
        if cache is not None and "ck" in cache:
            cross_cache = {"ck": cache["ck"], "cv": cache["cv"]}
        o, c = apply_attention(p["xattn_enc"], cfg, hx, positions=positions,
                               kv_source=encoder_out, cache=cross_cache,
                               mesh=mesh, impl=impl)
        if c is not None:
            new_cache.update(c)
        h = h + o

    # ---- MemCom compression cross-attention (Memory-LLM only) ----
    if memcom is not None:
        h = h + apply_memcom_xattn(memcom["params"]["memx"], cfg, h,
                                   memcom["src"], impl=impl, mesh=mesh)
        aux["omega"] = h  # O^i — the layer's compressed representation

    # ---- channel MLP ----
    if desc.mlp != "none":
        hn = apply_norm(p["norm2"], cfg, h)
        if desc.mlp == "moe":
            o, aux["moe_loss"], aux["moe_rows"] = apply_moe(
                p["moe"], cfg, hn, impl=impl)
        else:
            o = apply_mlp(p["mlp"], cfg, hn)
        h = h + o
    return h, new_cache, aux


def init_block_cache(cfg: ModelConfig, desc: LayerDesc, batch: int,
                     max_len: int, dtype) -> dict:
    if desc.mixer == "attn":
        c = init_attn_cache(cfg, batch, max_len, dtype)
    elif desc.mixer == "mla":
        c = init_mla_cache(cfg, batch, max_len, dtype)
    else:
        c = init_mamba_cache(cfg, batch, dtype)
    if desc.cross_attn:
        assert cfg.encoder is not None
        c.update(init_cross_cache(cfg, batch, cfg.encoder.num_frames, dtype))
    return c


def init_block_paged_cache(cfg: ModelConfig, desc: LayerDesc, num_blocks: int,
                           block_size: int, slots: int, dtype) -> dict:
    """Paged layout: attention/MLA KV pooled over ``num_blocks`` physical
    blocks (shared across slots via block tables); recurrent state and
    cross-attention KV stay per-slot (they are O(1) resp. fixed-size per
    slot — paging them buys nothing)."""
    if desc.mixer == "attn":
        c = init_paged_attn_cache(cfg, num_blocks, block_size, dtype)
    elif desc.mixer == "mla":
        c = init_paged_mla_cache(cfg, num_blocks, block_size, dtype)
    else:
        c = init_mamba_cache(cfg, slots, dtype)
    if desc.cross_attn:
        assert cfg.encoder is not None
        c.update(init_cross_cache(cfg, slots, cfg.encoder.num_frames, dtype))
    return c
