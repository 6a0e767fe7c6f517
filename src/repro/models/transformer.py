"""The model: embedding → (prefix blocks, scanned period blocks) → head.

One ``forward`` serves all three MemCom stacks:

* Source-LLM   — ``capture_hiddens=True`` → per-layer input reps H^i
* Memory-LLM   — ``memcom={"params": …, "src": …}`` → per-layer O^i
* Target-LLM   — ``prefix=…`` → attends to compressed per-layer context

Layer-wise quantities (params, caches, captured hiddens, prefixes, omegas)
all share the *Layerwise* layout::

    {"prefix": [per-layer, ...], "period": {"l0": stacked(repeats, ...), ...}}

so the three stacks (which are copies of the same architecture) can
exchange them directly, and the period part rides through ``jax.lax.scan``
as xs/ys with a leading ``repeats`` dim — except a paged cache's block
pools, which the scan carries whole and updates in place layer by layer.

See docs/ARCHITECTURE.md for the layout's batch-axis conventions and the
per-layer O^i prefix formats each mixer family exchanges.
"""

from __future__ import annotations

import math
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.models.blocks import (
    apply_block,
    init_block,
    init_block_cache,
    init_block_paged_cache,
)
from repro.models.layers import (
    apply_mlp,
    apply_norm,
    init_mlp,
    init_norm,
    sinusoidal_pos_embed,
    softcap,
)
from repro.models.attention import apply_attention, init_attention
from repro.models.param import ParamBuilder
from repro.sharding.ctx import constrain
from repro.utils.rng import Keys

#: cache leaves that are block pools under block tables (attention K/V,
#: MLA latents); every other leaf is per-slot
POOL_KEYS = ("k", "v", "lat")


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------


def init_params(cfg: ModelConfig, seed: int | Keys = 0, abstract: bool = False):
    params, _ = _build(cfg, seed, abstract)
    return params


def param_specs(cfg: ModelConfig):
    """Logical-axis tree matching init_params structure (abstract build)."""
    _, axes = _build(cfg, 0, abstract=True)
    return axes


def abstract_params(cfg: ModelConfig):
    params, _ = _build(cfg, 0, abstract=True)
    return params


def _build(cfg: ModelConfig, seed, abstract: bool):
    cfg.validate()
    keys = seed if isinstance(seed, Keys) else Keys(seed)
    dtype = jnp.dtype(cfg.dtype)
    b = ParamBuilder(keys, dtype, abstract)

    eb = b.child("embed")
    eb.make("tokens", (cfg.vocab_size, cfg.d_model), ("vocab", "embed"),
            init="normal", scale=cfg.d_model**-0.5)
    if cfg.pos_embed == "learned":
        eb.make("pos", (cfg.max_seq, cfg.d_model), (None, "embed"),
                init="normal", scale=0.02)

    if cfg.encoder is not None:
        enc = b.child("encoder")
        pb = enc.child("period", stack=cfg.encoder.num_layers)
        lb = pb.child("l0")
        init_norm(lb, cfg, "norm1")
        init_attention(lb, cfg)
        init_norm(lb, cfg, "norm2")
        init_mlp(lb, cfg, d_ff=cfg.encoder.d_ff, mlp_type="gelu_mlp")
        init_norm(enc, cfg, "final_norm")

    for i, desc in enumerate(cfg.layout.prefix):
        init_block(b.child(f"prefix_{i}"), cfg, desc)
    if cfg.layout.repeats:
        pb = b.child("period", stack=cfg.layout.repeats)
        for j, desc in enumerate(cfg.layout.period):
            init_block(pb.child(f"l{j}"), cfg, desc)

    init_norm(b, cfg, "final_norm")
    if not cfg.tie_embeddings:
        b.make("lm_head", (cfg.d_model, cfg.vocab_size), ("embed", "vocab"))
    return b.build()


# ---------------------------------------------------------------------------
# Layerwise helpers
# ---------------------------------------------------------------------------


def _lw_prefix(lw, i):
    if lw is None:
        return None
    entry = lw.get("prefix")
    if entry is None:
        return None
    return entry[i]


def _lw_period(lw):
    if lw is None:
        return {}
    return lw.get("period") or {}


def layerwise(prefix_list, period_dict):
    out = {}
    if prefix_list:
        out["prefix"] = prefix_list
    if period_dict:
        out["period"] = period_dict
    return out


# ---------------------------------------------------------------------------
# Encoder (whisper stub frontend: precomputed frame embeddings)
# ---------------------------------------------------------------------------


def encode(enc_params, cfg: ModelConfig, frames, *, impl: str = "auto",
           unroll: bool = False):
    B, F, D = frames.shape
    h = frames + sinusoidal_pos_embed(F, D).astype(frames.dtype)[None]

    def body(h, lp):
        p = lp["l0"]
        hn = apply_norm(p["norm1"], cfg, h)
        o, _ = apply_attention(p["attn"], cfg, hn, positions=None,
                               kv_source=hn, impl=impl)
        h = h + o
        hn = apply_norm(p["norm2"], cfg, h)
        h = h + apply_mlp(p["mlp"], cfg, hn, mlp_type="gelu_mlp")
        return h, None

    h, _ = jax.lax.scan(body, h, enc_params["period"], unroll=True if unroll else 1)
    return apply_norm(enc_params["final_norm"], cfg, h)


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def forward(
    params,
    cfg: ModelConfig,
    *,
    tokens=None,
    embeds=None,
    positions=None,
    mask_offset=0,
    prefix: Optional[dict] = None,  # Layerwise compressed context (MemCom)
    cache: Optional[dict] = None,  # Layerwise KV/state cache
    cache_index=None,
    decode: bool = False,
    block_tables=None,  # (B, nb) int32: paged-cache block tables
    lane_valid=None,  # (B,) int32: fused-step ragged-lane mask (decode)
    mesh=None,  # tensor-parallel serving mesh (reaches the decode kernels)

    capture_hiddens: bool = False,
    memcom: Optional[dict] = None,  # {"params": Layerwise, "src": Layerwise}
    encoder_frames=None,
    encoder_out=None,
    remat: bool = False,
    remat_policy: Optional[Any] = None,
    logits: bool = True,
    unroll: bool = False,  # unroll layer scans (dry-run cost extraction)
    impl: str = "auto",
):
    """Returns (logits_or_hidden, aux).

    aux keys: "cache" (Layerwise), "hiddens" (Layerwise, layer inputs H^i),
    "omega" (Layerwise, Memory-LLM compressed reps O^i), "moe_loss",
    "moe_rows" ((MoE layers, held experts + 1 + B·S) int32 row counts,
    :func:`repro.models.moe.split_rows`, or None without MoE layers),
    "encoder_out".
    """
    if embeds is None:
        h = jnp.take(params["embed"]["tokens"], tokens, axis=0)
    else:
        h = embeds
    h = constrain(h)  # residual-stream sharding (repro.sharding.ctx)
    B, S = h.shape[0], h.shape[1]
    if cfg.embed_scale:
        h = h * jnp.asarray(math.sqrt(cfg.d_model), h.dtype)
    start = cache_index if (decode and cache_index is not None) else mask_offset
    per_slot = decode and cache_index is not None and jnp.ndim(cache_index) == 1
    if per_slot:
        # continuous batching: each slot decodes at its own length
        pos2d = cache_index[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]
        if cfg.pos_embed == "learned":
            h = h + jnp.take(params["embed"]["pos"], pos2d, axis=0).astype(h.dtype)
        if positions is None:
            positions = pos2d
            if cfg.mrope_sections:
                positions = jnp.broadcast_to(positions, (3, B, S))
    else:
        if cfg.pos_embed == "learned":
            pe = jax.lax.dynamic_slice_in_dim(params["embed"]["pos"], start, S, axis=0)
            h = h + pe[None].astype(h.dtype)
        if positions is None:
            positions = jnp.broadcast_to(start + jnp.arange(S, dtype=jnp.int32), (B, S))
            if cfg.mrope_sections:
                positions = jnp.broadcast_to(positions, (3, B, S))

    if cfg.encoder is not None and encoder_frames is not None and encoder_out is None:
        encoder_out = encode(params["encoder"], cfg, encoder_frames, impl=impl,
                             unroll=unroll)

    aux_loss = jnp.float32(0.0)
    n_prefix = len(cfg.layout.prefix)
    caps_p, omegas_p, caches_p, rows_p = [], [], [], []

    memx_params = memcom["params"] if memcom is not None else None
    memx_src = memcom["src"] if memcom is not None else None

    def one_block(p, desc, h, *, lpre, lcache, lmemx, lsrc, layer=None):
        mem = None
        if lmemx is not None and desc.mixer in ("attn", "mla"):
            mem = {"params": lmemx, "src": lsrc}
        return apply_block(
            p, cfg, desc, h, positions=positions, mask_offset=mask_offset,
            prefix=lpre, cache=lcache, cache_index=cache_index, decode=decode,
            block_tables=block_tables, lane_valid=lane_valid, mesh=mesh,
            layer=layer, encoder_out=encoder_out, memcom=mem, impl=impl)

    for i, desc in enumerate(cfg.layout.prefix):
        if capture_hiddens:
            caps_p.append(h)
        fn = one_block
        if remat:
            fn = jax.checkpoint(one_block, policy=remat_policy,
                                static_argnums=(1,))
        h, c, a = fn(params[f"prefix_{i}"], desc, h,
                     lpre=_lw_prefix(prefix, i), lcache=_lw_prefix(cache, i),
                     lmemx=_lw_prefix(memx_params, i),
                     lsrc=_lw_prefix(memx_src, i))
        h = constrain(h)
        aux_loss = aux_loss + a["moe_loss"]
        if a["moe_rows"] is not None:
            rows_p.append(a["moe_rows"][None])
        if c is not None:
            caches_p.append(c)
        if a["omega"] is not None:
            omegas_p.append(a["omega"])

    period_caches, period_caps, period_omegas, period_rows = {}, {}, {}, {}
    if cfg.layout.repeats:
        # Under block tables the period's block pools ride in the carry
        # as whole (repeats, ...) stacks that each layer updates in place
        # at its own index; sliced through xs/ys instead, every layer's
        # pool would be copied out and stacked back on every step.
        # Per-slot leaves (conv/ssm/cross) stay xs/ys.
        pools, rest = {}, _lw_period(cache)
        if block_tables is not None:
            pools = {key: {k: x for k, x in c.items() if k in POOL_KEYS}
                     for key, c in rest.items()}
            pools = {key: c for key, c in pools.items() if c}
            rest = {key: {k: x for k, x in c.items() if k not in POOL_KEYS}
                    for key, c in rest.items()}
        xs = (
            params["period"],
            _lw_period(prefix),
            rest,
            _lw_period(memx_params),
            _lw_period(memx_src),
            jnp.arange(cfg.layout.repeats, dtype=jnp.int32),
        )

        def body(carry, xs):
            h, aux, pools = carry
            lp, lpre, lcache, lmemx, lsrc, layer = xs
            new_caches, new_pools, caps, omegas, rows = {}, {}, {}, {}, {}
            for j, desc in enumerate(cfg.layout.period):
                key = f"l{j}"
                if capture_hiddens:
                    caps[key] = h
                lc = lcache.get(key) if lcache else None
                if key in pools:
                    lc = {**(lc or {}), **pools[key]}
                h, c, a = one_block(
                    lp[key], desc, h,
                    lpre=lpre.get(key) if lpre else None,
                    lcache=lc,
                    lmemx=lmemx.get(key) if lmemx else None,
                    lsrc=lsrc.get(key) if lsrc else None,
                    layer=layer if key in pools else None)
                h = constrain(h)
                aux = aux + a["moe_loss"]
                if a["moe_rows"] is not None:
                    rows[key] = a["moe_rows"]
                if c is not None:
                    if key in pools:
                        new_pools[key] = {k: c[k] for k in pools[key]}
                        c = {k: x for k, x in c.items() if k not in pools[key]}
                    new_caches[key] = c
                if a["omega"] is not None:
                    omegas[key] = a["omega"]
            return (h, aux, new_pools), (new_caches, caps, omegas, rows)

        scan_body = jax.checkpoint(body, policy=remat_policy) if remat else body
        (h, aux_loss, pools), (period_caches, period_caps, period_omegas,
                               period_rows) = \
            jax.lax.scan(scan_body, (h, aux_loss, pools), xs,
                         unroll=True if unroll else 1)
        for key, c in pools.items():
            period_caches[key] = {**period_caches[key], **c}

    hn = apply_norm(params["final_norm"], cfg, h)
    out = hn
    if logits:
        if cfg.tie_embeddings:
            out = hn @ params["embed"]["tokens"].T
        else:
            out = hn @ params["lm_head"]
        out = softcap(out, cfg.final_logit_softcap)

    rows_p += [period_rows[key] for key in sorted(period_rows)]
    aux = {
        "moe_loss": aux_loss,
        "moe_rows": jnp.concatenate(rows_p) if rows_p else None,
        "cache": layerwise(caches_p, period_caches) if cache is not None else None,
        "hiddens": layerwise(caps_p, period_caps) if capture_hiddens else None,
        "omega": layerwise(omegas_p, period_omegas) if memcom is not None else None,
        "encoder_out": encoder_out,
    }
    return out, aux


# ---------------------------------------------------------------------------
# Cache init
# ---------------------------------------------------------------------------


def init_cache(cfg: ModelConfig, batch: int, max_len: int, dtype=None):
    dtype = dtype or jnp.dtype(cfg.dtype)
    prefix = [
        init_block_cache(cfg, desc, batch, max_len, dtype)
        for desc in cfg.layout.prefix
    ]
    period = {}
    if cfg.layout.repeats:
        for j, desc in enumerate(cfg.layout.period):
            one = init_block_cache(cfg, desc, batch, max_len, dtype)
            period[f"l{j}"] = jax.tree.map(
                lambda x: jnp.zeros((cfg.layout.repeats,) + x.shape, x.dtype), one)
    return layerwise(prefix, period)


def init_paged_cache(cfg: ModelConfig, num_blocks: int, block_size: int,
                     slots: int, dtype=None):
    """Block-pool KV cache: attention/MLA leaves are a single
    ``(num_blocks, block_size, ...)`` physical pool per layer (period
    section stacks a pool per repeat on the leading axis, as always),
    addressed through per-slot block tables; attention K/V rows are
    lane-merged ``(Hkv*hd,)``.  Recurrent conv/ssm and cross-attention
    leaves keep the per-slot ``(slots, ...)`` layout."""
    dtype = dtype or jnp.dtype(cfg.dtype)
    prefix = [
        init_block_paged_cache(cfg, desc, num_blocks, block_size, slots, dtype)
        for desc in cfg.layout.prefix
    ]
    period = {}
    if cfg.layout.repeats:
        for j, desc in enumerate(cfg.layout.period):
            one = init_block_paged_cache(cfg, desc, num_blocks, block_size,
                                         slots, dtype)
            period[f"l{j}"] = jax.tree.map(
                lambda x: jnp.zeros((cfg.layout.repeats,) + x.shape, x.dtype), one)
    return layerwise(prefix, period)
