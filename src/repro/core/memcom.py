"""MemCom — the paper's contribution (§4), as a composable JAX module.

Parameter tree::

    {"source":     <transformer params, init = copy of target>,
     "memory_llm": <transformer params, init = copy of target>,
     "memx":       Layerwise cross-attention params (attn/mla layers only),
     "mem_tokens": (m, d) learnable memory token embeddings}

``compress`` runs the Source-LLM with per-layer capture, then the
Memory-LLM over the memory tokens with the compression cross-attention,
and packages the per-layer O^i as a *prefix* the frozen Target-LLM
consumes.  For hybrid (Jamba-style) architectures, Mamba layers hand off
the Source-LLM's exact final SSM state instead (DESIGN.md §4).

Training: Phase-1 trains only {memx, mem_tokens}; Phase-2 additionally
unfreezes {source, memory_llm}.  The target is frozen in both phases.

docs/ARCHITECTURE.md documents this parameter tree, the per-layer O^i
prefix formats, and the serving-time handoff in one place.
"""

from __future__ import annotations

from dataclasses import dataclass, field, replace
from typing import List, Optional

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.models import transformer as tfm
from repro.models.param import ParamBuilder
from repro.models.xattn import init_memcom_xattn
from repro.utils.rng import Keys
from repro.utils.pytree import tree_map_with_path


def _needs_state_handoff(cfg: ModelConfig) -> bool:
    if cfg.memcom is None or not cfg.memcom.ssm_state_handoff:
        return False
    return any(d.mixer == "mamba" for d in cfg.layout.descriptors())


def init_memx(cfg: ModelConfig, seed: int | Keys = 0, abstract: bool = False):
    """Layerwise cross-attention params — only attn/mla layers get one."""
    keys = seed if isinstance(seed, Keys) else Keys(seed)
    b = ParamBuilder(keys, jnp.dtype(cfg.dtype), abstract)
    for i, desc in enumerate(cfg.layout.prefix):
        if desc.mixer in ("attn", "mla"):
            init_memcom_xattn(b.child("prefix").child(str(i)), cfg)
    if cfg.layout.repeats:
        pb = b.child("period", stack=cfg.layout.repeats)
        for j, desc in enumerate(cfg.layout.period):
            if desc.mixer in ("attn", "mla"):
                init_memcom_xattn(pb.child(f"l{j}"), cfg)
    params, _ = b.build()
    # repackage: {"prefix": [... or None], "period": {...}}
    out = {}
    if cfg.layout.prefix:
        out["prefix"] = [
            params.get("prefix", {}).get(str(i))
            for i in range(len(cfg.layout.prefix))
        ]
    if cfg.layout.repeats and params.get("period"):
        out["period"] = params["period"]
    return out


def memcom_axes(cfg: ModelConfig):
    """Logical-axis tree matching init_memcom structure (for sharding rules)."""
    keys = Keys(0)
    b = ParamBuilder(keys, jnp.dtype(cfg.dtype), abstract=True)
    for i, desc in enumerate(cfg.layout.prefix):
        if desc.mixer in ("attn", "mla"):
            init_memcom_xattn(b.child("prefix").child(str(i)), cfg)
    if cfg.layout.repeats:
        pb = b.child("period", stack=cfg.layout.repeats)
        for j, desc in enumerate(cfg.layout.period):
            if desc.mixer in ("attn", "mla"):
                init_memcom_xattn(pb.child(f"l{j}"), cfg)
    _, axes = b.build()
    memx_axes = {}
    if cfg.layout.prefix:
        memx_axes["prefix"] = [
            axes.get("prefix", {}).get(str(i))
            for i in range(len(cfg.layout.prefix))
        ]
    if cfg.layout.repeats and axes.get("period"):
        memx_axes["period"] = axes["period"]
    from repro.models.transformer import param_specs

    tgt_axes = param_specs(cfg)
    return {
        "source": tgt_axes,
        "memory_llm": tgt_axes,
        "memx": memx_axes,
        "mem_tokens": (None, "embed"),
    }


def init_memcom(cfg: ModelConfig, target_params, seed: int | Keys = 0,
                abstract: bool = False):
    assert cfg.memcom is not None, f"{cfg.name}: set ModelConfig.memcom"
    keys = seed if isinstance(seed, Keys) else Keys(seed)
    m = cfg.memcom.num_memory_tokens
    if abstract:
        copy = lambda t: jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), t)
        mem_tokens = jax.ShapeDtypeStruct((m, cfg.d_model), jnp.dtype(cfg.dtype))
    else:
        copy = lambda t: jax.tree.map(jnp.array, t)
        mem_tokens = (cfg.d_model**-0.5 * jax.random.normal(
            keys("mem_tokens"), (m, cfg.d_model), jnp.float32)
        ).astype(jnp.dtype(cfg.dtype))
    return {
        "source": copy(target_params),
        "memory_llm": copy(target_params),
        "memx": init_memx(cfg, keys.child("memx"), abstract),
        "mem_tokens": mem_tokens,
    }


def init_models(cfg: ModelConfig, mesh=None, rules=None, *, seed: int = 0):
    """Random target (``seed``) and MemCom compressor (``seed + 1``).
    With a mesh both are created on their shards from their logical axes
    — a model too large for one device is never built on one."""
    if mesh is None:
        target = tfm.init_params(cfg, seed)
        return target, init_memcom(cfg, target, seed + 1)
    from repro.sharding.rules import init_sharded

    target = init_sharded(lambda: tfm.init_params(cfg, seed),
                          tfm.param_specs(cfg), mesh, rules)
    compressor = init_sharded(lambda t: init_memcom(cfg, t, seed + 1),
                              memcom_axes(cfg), mesh, rules, target)
    return target, compressor


def compress(mc_params, cfg: ModelConfig, source_tokens=None, *,
             source_embeds=None, encoder_frames=None, remat: bool = False,
             unroll: bool = False, impl: str = "auto", mesh=None):
    """Many-shot tokens (B, T) -> Layerwise compressed prefix for the target.

    Returns (prefix, info).  prefix entries: attn/mla -> {"h": O^i (B,m,D)};
    mamba -> {"ssm": final source state (B,H,P,N)}.  ``mesh``: the
    compressor's parameters are sharded over it (the kernels then run
    per device).
    """
    B = (source_tokens if source_tokens is not None else source_embeds).shape[0]
    mem = cfg.memcom.num_memory_tokens

    state_cache = None
    if _needs_state_handoff(cfg):
        state_cache = _mamba_only_cache(cfg, B)

    _, aux_s = tfm.forward(
        mc_params["source"], cfg, tokens=source_tokens, embeds=source_embeds,
        capture_hiddens=True,
        cache=state_cache, cache_index=0 if state_cache is not None else None,
        encoder_frames=encoder_frames, logits=False, remat=remat,
        unroll=unroll, impl=impl, mesh=mesh)

    mem_embeds = jnp.broadcast_to(
        mc_params["mem_tokens"][None], (B, mem, cfg.d_model)
    ).astype(mc_params["mem_tokens"].dtype)
    _, aux_m = tfm.forward(
        mc_params["memory_llm"], cfg, embeds=mem_embeds,
        memcom={"params": _memx_wrap(mc_params["memx"]), "src": aux_s["hiddens"]},
        encoder_out=aux_s["encoder_out"], logits=False, remat=remat,
        unroll=unroll, impl=impl, mesh=mesh)

    prefix = build_prefix(cfg, aux_m["omega"], aux_s["cache"])
    info = {"encoder_out": aux_s["encoder_out"]}
    return prefix, info


# ---------------------------------------------------------------------------
# Chunked (stateful) compression — the online-serving variant
# ---------------------------------------------------------------------------


@dataclass
class CompressionState:
    """Carry-over between :func:`compress_chunk` calls: the Source-LLM's
    cache (KV for attention/MLA continuation, conv/ssm recurrence for
    mamba) plus the per-layer hiddens H^i captured so far.

    The state lets a t-token shot set compile in fixed-budget slices —
    chunk k prefills positions [offset, offset+w) behind the cached
    [0, offset) context, exactly the engine's prefill-continuation path —
    so a serving loop can interleave compression with decode steps
    (:mod:`repro.serving.compiler`).
    """

    cache: dict                      # Layerwise source cache (functional)
    offset: int = 0                  # source tokens consumed so far
    hiddens: List[dict] = field(default_factory=list)  # per-chunk H^i
    encoder_out: Optional[jax.Array] = None


def begin_compress(cfg: ModelConfig, batch: int, total_len: int, *,
                   mc_params=None, encoder_frames=None,
                   impl: str = "auto") -> CompressionState:
    """Open a chunked compression over ``total_len`` source tokens.

    Allocates a full Source-LLM cache (attention KV *and* recurrent
    state — unlike the one-shot :func:`compress`, every family needs its
    running context carried across chunk boundaries).
    """
    encoder_out = None
    if cfg.encoder is not None and encoder_frames is not None:
        assert mc_params is not None, "encoder configs need mc_params"
        encoder_out = tfm.encode(mc_params["source"]["encoder"], cfg,
                                 encoder_frames, impl=impl)
    return CompressionState(cache=tfm.init_cache(cfg, batch, total_len),
                            encoder_out=encoder_out)


def compress_chunk(mc_params, cfg: ModelConfig, state: CompressionState,
                   tokens, *, impl: str = "auto",
                   mesh=None) -> CompressionState:
    """Run the Source-LLM over one chunk of the shot set and fold the
    result into ``state``.  ``tokens`` is (B, w); ``state.offset`` must be
    a python int (the continuation slice is static, as in engine prefill —
    one trace per (width, offset) pair).  Returns the advanced state."""
    offset = state.offset
    assert isinstance(offset, int)
    _, aux = tfm.forward(
        mc_params["source"], cfg, tokens=tokens, capture_hiddens=True,
        cache=state.cache, cache_index=offset, mask_offset=offset,
        encoder_out=state.encoder_out, logits=False, impl=impl, mesh=mesh)
    return replace(state, cache=aux["cache"], offset=offset + tokens.shape[1],
                   hiddens=state.hiddens + [aux["hiddens"]])


def finish_compress(mc_params, cfg: ModelConfig, state: CompressionState, *,
                    impl: str = "auto", mesh=None):
    """Close a chunked compression: concatenate the captured H^i along the
    source-time axis, run the Memory-LLM once over the m memory tokens,
    and package the per-layer prefix.  Same return shape as
    :func:`compress`."""
    assert state.hiddens, "no chunks were compressed"
    if len(state.hiddens) == 1:
        hiddens = state.hiddens[0]
    else:  # time is axis -2 in both sections ((B,T,D) / (repeats,B,T,D))
        hiddens = jax.tree.map(
            lambda *xs: jnp.concatenate(xs, axis=-2), *state.hiddens)
    B = jax.tree.leaves(hiddens)[0].shape[-3]
    mem = cfg.memcom.num_memory_tokens
    mem_embeds = jnp.broadcast_to(
        mc_params["mem_tokens"][None], (B, mem, cfg.d_model)
    ).astype(mc_params["mem_tokens"].dtype)
    _, aux_m = tfm.forward(
        mc_params["memory_llm"], cfg, embeds=mem_embeds,
        memcom={"params": _memx_wrap(mc_params["memx"]), "src": hiddens},
        encoder_out=state.encoder_out, logits=False, impl=impl, mesh=mesh)
    prefix = build_prefix(cfg, aux_m["omega"], state.cache)
    return prefix, {"encoder_out": state.encoder_out}


def compress_chunked(mc_params, cfg: ModelConfig, source_tokens, *,
                     chunk_size: int, encoder_frames=None,
                     impl: str = "auto"):
    """Chunked :func:`compress`: identical output, computed in
    ``chunk_size``-token slices with the Source-LLM cache carried across
    slices (parity asserted in ``tests/test_compiler.py``)."""
    T = source_tokens.shape[1]
    state = begin_compress(cfg, source_tokens.shape[0], T,
                           mc_params=mc_params,
                           encoder_frames=encoder_frames, impl=impl)
    for lo in range(0, T, chunk_size):
        state = compress_chunk(mc_params, cfg, state,
                               source_tokens[:, lo:lo + chunk_size],
                               impl=impl)
    return finish_compress(mc_params, cfg, state, impl=impl)


def _memx_wrap(memx):
    """Wrap each layer's xattn params under the key blocks expect."""
    out = {}
    if "prefix" in memx:
        out["prefix"] = [
            None if p is None else {"memx": p["memx"]} for p in memx["prefix"]
        ]
    if "period" in memx:
        out["period"] = {k: {"memx": v["memx"]} for k, v in memx["period"].items()}
    return out


def _mamba_only_cache(cfg: ModelConfig, batch: int):
    """A cache holding only mamba conv/ssm states (no KV allocation)."""
    from repro.models.mamba2 import init_mamba_cache

    prefix = [
        init_mamba_cache(cfg, batch, jnp.dtype(cfg.dtype))
        if desc.mixer == "mamba" else {}
        for desc in cfg.layout.prefix
    ]
    period = {}
    for j, desc in enumerate(cfg.layout.period):
        if desc.mixer != "mamba":
            continue
        one = init_mamba_cache(cfg, batch, jnp.dtype(cfg.dtype))
        period[f"l{j}"] = jax.tree.map(
            lambda x: jnp.zeros((cfg.layout.repeats,) + x.shape, x.dtype), one)
    return tfm.layerwise(prefix, period)


def build_prefix(cfg: ModelConfig, omega, source_cache):
    """Assemble the target's per-layer compressed context."""
    out = {}
    if cfg.layout.prefix:
        entries = []
        oi = 0
        omega_prefix = (omega or {}).get("prefix", [])
        for i, desc in enumerate(cfg.layout.prefix):
            if desc.mixer in ("attn", "mla"):
                entries.append({"h": omega_prefix[oi]})
                oi += 1
            else:
                entries.append({"ssm": source_cache["prefix"][i]["ssm"]})
        out["prefix"] = entries
    period = {}
    oi = 0
    omega_period_keys = sorted((omega or {}).get("period", {}).keys())
    for j, desc in enumerate(cfg.layout.period):
        key = f"l{j}"
        if desc.mixer in ("attn", "mla"):
            # omega period dict keys follow layer order among attn layers
            period[key] = {"h": omega["period"][key]}
        else:
            period[key] = {"ssm": source_cache["period"][key]["ssm"]}
    if period:
        out["period"] = period
    del oi, omega_period_keys
    return out


def memcom_loss(mc_params, target_params, cfg: ModelConfig, batch, *,
                remat: bool = False, unroll: bool = False, impl: str = "auto"):
    """Next-token CE on target-segment tokens (paper's training objective).

    batch: {"source": (B,T), "target": (B,S), "target_mask": (B,S)}.
    Labels are target shifted by one; the last position predicts nothing.
    """
    prefix, info = compress(
        mc_params, cfg, batch.get("source"),
        source_embeds=batch.get("source_embeds"),
        encoder_frames=batch.get("frames"), remat=remat, unroll=unroll,
        impl=impl)
    m = cfg.memcom.num_memory_tokens
    logits, aux = tfm.forward(
        target_params, cfg, tokens=batch["target"], prefix=prefix,
        mask_offset=m, encoder_out=info["encoder_out"], remat=remat,
        unroll=unroll, impl=impl)
    loss = next_token_loss(logits, batch["target"], batch.get("target_mask"))
    return loss + aux["moe_loss"], {"ce": loss, "moe": aux["moe_loss"]}


def next_token_loss(logits, tokens, mask=None):
    lp = jax.nn.log_softmax(logits[:, :-1].astype(jnp.float32), axis=-1)
    tgt = tokens[:, 1:]
    ll = jnp.take_along_axis(lp, tgt[..., None], axis=-1)[..., 0]
    if mask is not None:
        w = mask[:, 1:].astype(jnp.float32)
    else:
        w = jnp.ones_like(ll)
    return -(ll * w).sum() / jnp.maximum(w.sum(), 1.0)


def trainable_mask(mc_params, phase: int):
    """Bool pytree: which compressor params receive gradients."""
    if phase == 2:
        return jax.tree.map(lambda _: True, mc_params)

    def mark(path, _):
        return path.startswith("memx") or path.startswith("mem_tokens")

    return tree_map_with_path(mark, mc_params)
