"""Dry-run / production cell builders: (arch × shape × mesh) → jittable
step + fully-abstract, sharding-annotated inputs.

Objectives per shape kind (EXPERIMENTS.md §Dry-run records the mapping):

* ``train``   → MemCom training step (the paper's workload): compressor
  fwd/bwd + frozen-target fwd/bwd-to-activations + AdamW on the trainable
  subtree (Phase-1 by default — the paper's headline setting).  Archs the
  technique doesn't apply to (attention-free mamba2) lower a plain LM
  train step instead (DESIGN.md §Arch-applicability).
* ``prefill`` → the system's offline compression pass: Source-LLM +
  Memory-LLM over the many-shot tokens → per-layer compressed KV cache
  materialized through the frozen target projections.  (mamba2: vanilla
  prefill — its post-prompt SSM state *is* the compressed cache.)
* ``decode``  → vanilla serve step: one new token per sequence against a
  seq_len KV cache (the paper's *baseline* inference cost — what MemCom
  removes).  ``decode_compressed`` lowers the MemCom-served counterpart
  (m memory slots + a small generation window) for the §Perf comparison.

Everything is abstract: ``jax.eval_shape`` builds the state trees,
shardings are attached to ``ShapeDtypeStruct``s, nothing is allocated.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.config import ModelConfig, SHAPES, ShapeSpec
from repro.configs import get_config
from repro.core import icae as icae_lib
from repro.core import memcom
from repro.launch import costs
from repro.models import transformer as tfm
from repro.optim import AdamW, clip_by_global_norm, warmup_cosine
from repro.serving.engine import materialize_prefix
from repro.sharding.rules import (
    FSDP_RULES, Rules, batch_sharding, logical_to_shardings, replicated,
    spec_for,
)
from repro.utils.pytree import tree_map_with_path

# Archs whose family makes MemCom inapplicable (train falls back to LM).
ATTENTION_FREE = ("mamba2-370m",)
# Sub-quadratic archs that run long_500k.
SUBQUADRATIC = ("mamba2-370m", "jamba-1.5-large-398b")


def shape_by_name(name: str) -> ShapeSpec:
    for s in SHAPES:
        if s.name == name:
            return s
    raise KeyError(name)


def cell_is_skipped(arch: str, shape_name: str) -> Optional[str]:
    """Return a skip reason or None (spec: long_500k is sub-quadratic-only)."""
    shape = shape_by_name(shape_name)
    if shape.subquadratic_only and arch not in SUBQUADRATIC:
        return ("full-attention arch: 500k decode needs sub-quadratic "
                "attention (DESIGN.md §4)")
    return None


# ---------------------------------------------------------------------------
# Sharding helpers
# ---------------------------------------------------------------------------


def _sds(shape, dtype, sharding=None):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _with_shardings(abstract_tree, sharding_tree):
    return jax.tree.map(
        lambda a, s: _sds(a.shape, a.dtype, s), abstract_tree, sharding_tree)


def _data_axes(mesh: Mesh):
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def _batch_spec(mesh: Mesh, batch: int, ndim: int):
    axes = _data_axes(mesh)
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    lead = axes if batch % n == 0 else None
    return NamedSharding(mesh, P(lead, *([None] * (ndim - 1))))


def act_sharding_for(mesh: Mesh, cfg: ModelConfig, batch: int, seq: int):
    """Residual-stream constraint: batch→data axes, seq→model."""
    axes = _data_axes(mesh)
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    b = axes if batch % n == 0 else None
    s = "model" if seq % mesh.shape["model"] == 0 and seq > 1 else None
    return NamedSharding(mesh, P(b, s, None))


def param_shardings(cfg: ModelConfig, mesh: Mesh, rules: Rules = FSDP_RULES):
    abstract = tfm.abstract_params(cfg)
    axes = tfm.param_specs(cfg)
    return logical_to_shardings(abstract, axes, mesh, rules), abstract


def memcom_shardings(cfg: ModelConfig, mesh: Mesh, rules: Rules = FSDP_RULES):
    tgt_abs = tfm.abstract_params(cfg)
    mc_abs = memcom.init_memcom(cfg, tgt_abs, abstract=True)
    mc_axes = memcom.memcom_axes(cfg)
    return logical_to_shardings(mc_abs, mc_axes, mesh, rules), mc_abs


def cache_shardings(cfg: ModelConfig, mesh: Mesh, cache_abstract):
    """KV/state cache shardings: batch→data axes, cache-seq→model."""
    daxes = _data_axes(mesh)
    n_data = 1
    for a in daxes:
        n_data *= mesh.shape[a]
    n_model = mesh.shape["model"]

    def leaf_spec(path: str, leaf):
        name = path.rsplit("/", 1)[-1]
        shape = leaf.shape
        stacked = path.startswith("period")
        off = 1 if stacked else 0
        entries = [None] * len(shape)
        # batch dim
        bdim = off
        if shape[bdim] % n_data == 0:
            entries[bdim] = daxes
        if name in ("k", "v", "ck", "cv", "lat"):
            sdim = off + 1  # cache sequence
            if shape[sdim] % n_model == 0:
                entries[sdim] = "model"
        elif name == "ssm":  # (B, H, P, N): heads → model
            hdim = off + 1
            if shape[hdim] % n_model == 0:
                entries[hdim] = "model"
        elif name == "conv":  # (B, W-1, conv_dim): channels → model
            cdim = off + 2
            if shape[cdim] % n_model == 0:
                entries[cdim] = "model"
        return NamedSharding(mesh, P(*entries))

    return tree_map_with_path(leaf_spec, cache_abstract)


def opt_shardings(state_abstract, p_shardings, mesh: Mesh):
    from repro.sharding.rules import opt_state_shardings

    return opt_state_shardings(state_abstract, p_shardings, mesh)


# ---------------------------------------------------------------------------
# Input specs (ShapeDtypeStruct stand-ins for every model input)
# ---------------------------------------------------------------------------


def input_specs(arch: str, shape_name: str, mesh: Mesh,
                objective: Optional[str] = None) -> dict:
    """Abstract, sharded batch inputs for one cell."""
    cfg = get_config(arch)
    shape = shape_by_name(shape_name)
    objective = objective or default_objective(arch, shape)
    B = shape.global_batch
    i32 = jnp.int32

    def tok(n, b=B):
        return _sds((b, n), i32, _batch_spec(mesh, b, 2))

    out: dict = {}
    if objective == "memcom_train":
        T, S = costs.train_split(shape)
        out["source"] = tok(T)
        out["target"] = tok(S)
        out["target_mask"] = _sds((B, S), i32, _batch_spec(mesh, B, 2))
    elif objective == "lm_train":
        out["tokens"] = tok(shape.seq_len)
    elif objective in ("compress", "prefill"):
        out["source"] = tok(shape.seq_len)
    elif objective.startswith("decode"):
        out["tokens"] = tok(1)
        out["cache_index"] = _sds((), i32)
    else:
        raise ValueError(objective)
    if cfg.encoder is not None and objective in (
            "memcom_train", "lm_train", "compress", "prefill"):
        e = cfg.encoder
        out["frames"] = _sds((B, e.num_frames, cfg.d_model),
                             jnp.dtype(cfg.dtype), _batch_spec(mesh, B, 3))
    return out


def default_objective(arch: str, shape: ShapeSpec) -> str:
    if shape.kind == "train":
        return "lm_train" if arch in ATTENTION_FREE else "memcom_train"
    if shape.kind == "prefill":
        return "prefill" if arch in ATTENTION_FREE else "compress"
    return "decode"


# ---------------------------------------------------------------------------
# Step builders
# ---------------------------------------------------------------------------


def _stop_frozen(tree, mask):
    return jax.tree.map(
        lambda x, m: x if m else jax.lax.stop_gradient(x), tree, mask)


def build_memcom_train_step(cfg: ModelConfig, *, phase: int = 1,
                            impl: str = "auto", remat: bool = True,
                            clip: float = 1.0):
    """(mc_params, opt_state, target_params, batch) → (mc, opt, metrics).

    Weight grads exist only for the phase's trainable subtree
    (``stop_gradient`` on frozen leaves ⇒ XLA never forms their dL/dW);
    activation grads still flow through every stack, faithful to the
    paper's training scheme.
    """
    sched = warmup_cosine(2e-4 if phase == 1 else 2e-6,
                          warmup_steps=500, total_steps=20_000)

    def loss_fn(mc, target_params, batch):
        mask = memcom.trainable_mask(mc, phase)
        mc = _stop_frozen(mc, mask)
        return memcom.memcom_loss(mc, target_params, cfg, batch,
                                  remat=remat, impl=impl)

    def step(mc, opt_state, target_params, batch):
        (loss, aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            mc, target_params, batch)
        grads, gnorm = clip_by_global_norm(grads, clip)
        opt = AdamW(lr=sched, mask=memcom.trainable_mask(mc, phase))
        mc, opt_state = opt.step(mc, grads, opt_state)
        return mc, opt_state, {"loss": loss, "grad_norm": gnorm, **aux}

    return step, None


def build_lm_train_step(cfg: ModelConfig, *, impl: str = "auto",
                        remat: bool = True, clip: float = 1.0):
    opt = AdamW(lr=warmup_cosine(1e-4, warmup_steps=500, total_steps=20_000))

    def loss_fn(params, batch):
        logits, aux = tfm.forward(
            params, cfg, tokens=batch["tokens"],
            encoder_frames=batch.get("frames"), remat=remat, impl=impl)
        loss = memcom.next_token_loss(logits, batch["tokens"])
        return loss + aux["moe_loss"], {"ce": loss}

    def step(params, opt_state, batch):
        (loss, aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, batch)
        grads, gnorm = clip_by_global_norm(grads, clip)
        params, opt_state = opt.step(params, grads, opt_state)
        return params, opt_state, {"loss": loss, "grad_norm": gnorm, **aux}

    return step, opt


def build_compress_step(cfg: ModelConfig, *, impl: str = "auto",
                        remat: bool = False):
    """(mc_params, target_params, batch) → materialized compressed cache."""

    def step(mc, target_params, batch):
        prefix, info = memcom.compress(
            mc, cfg, batch.get("source"),
            encoder_frames=batch.get("frames"), remat=remat, impl=impl)
        cache = materialize_prefix(target_params, cfg, prefix)
        return cache, info.get("encoder_out")

    return step


def build_prefill_step(cfg: ModelConfig, max_len: int, *, impl: str = "auto"):
    """Vanilla prefill: run the prompt, write the KV/state cache."""

    def step(params, batch):
        B = batch["source"].shape[0]
        cache = tfm.init_cache(cfg, B, max_len)
        logits, aux = tfm.forward(
            params, cfg, tokens=batch["source"], cache=cache, cache_index=0,
            encoder_frames=batch.get("frames"), impl=impl)
        return logits[:, -1:], aux["cache"]

    return step


def build_decode_step(cfg: ModelConfig, *, impl: str = "dense"):
    """(params, cache, batch) → (logits, new cache). One-token serve step."""

    def step(params, cache, batch):
        logits, aux = tfm.forward(
            params, cfg, tokens=batch["tokens"], cache=cache,
            cache_index=batch["cache_index"], decode=True, impl=impl)
        return logits, aux["cache"]

    return step


# ---------------------------------------------------------------------------
# Cell assembly: everything jax.jit needs for one (arch × shape × mesh)
# ---------------------------------------------------------------------------


def default_rules_for(cfg: ModelConfig, mesh: Mesh) -> Rules:
    """Shipped posture: FSDP + EP-only expert weights — unless the arch's
    expert count does not divide the model axis, in which case EP cannot
    shard the experts and the pre-fix posture (expert d_model FSDP) is
    the measured-better fallback (EXPERIMENTS.md §Perf, granite)."""
    from repro.sharding.rules import FSDP_EP_EMBED_RULES

    if cfg.moe is not None and cfg.moe.num_experts % mesh.shape["model"]:
        return FSDP_EP_EMBED_RULES
    return FSDP_RULES


def build_cell(arch: str, shape_name: str, mesh: Mesh, *,
               objective: Optional[str] = None, phase: int = 1,
               rules: Optional[Rules] = None, impl: str = "auto",
               decode_window: int = 0, moe_groups: int = 0,
               cfg_override: Optional[ModelConfig] = None) -> dict:
    """Returns {step, args (abstract+sharded), donate, act_sharding, meta}.

    ``moe_groups`` > 0 switches the MoE dispatch to group-local sort with
    that many groups (hillclimb 1; 0 keeps the config's default)."""
    import dataclasses as _dc

    cfg = cfg_override or get_config(arch)
    if rules is None:
        rules = default_rules_for(cfg, mesh)
    if moe_groups and cfg.moe is not None:
        cfg = cfg.replace(moe=_dc.replace(cfg.moe,
                                          dispatch_groups=moe_groups))
    shape = shape_by_name(shape_name)
    objective = objective or default_objective(arch, shape)
    batch = input_specs(arch, shape_name, mesh, objective)
    B = shape.global_batch

    if objective == "memcom_train":
        step, opt = build_memcom_train_step(cfg, phase=phase, impl=impl)
        mc_sh, mc_abs = memcom_shardings(cfg, mesh, rules)
        tgt_sh, tgt_abs = param_shardings(cfg, mesh, rules)
        mask = memcom.trainable_mask(mc_abs, phase)
        opt_abs = jax.eval_shape(
            AdamW(lr=0.0, mask=mask).init, mc_abs)
        opt_sh = opt_shardings(opt_abs, mc_sh, mesh)
        args = (
            _with_shardings(mc_abs, mc_sh),
            _with_shardings(opt_abs, opt_sh),
            _with_shardings(tgt_abs, tgt_sh),
            batch,
        )
        T, S = costs.train_split(shape)
        act = act_sharding_for(mesh, cfg, B, T)
        return dict(step=step, args=args, donate=(0, 1), act_sharding=act,
                    objective=objective, cfg=cfg, shape=shape, phase=phase)

    if objective == "lm_train":
        step, opt = build_lm_train_step(cfg, impl=impl)
        p_sh, p_abs = param_shardings(cfg, mesh, rules)
        opt_abs = jax.eval_shape(AdamW(lr=0.0).init, p_abs)
        opt_sh = opt_shardings(opt_abs, p_sh, mesh)
        args = (
            _with_shardings(p_abs, p_sh),
            _with_shardings(opt_abs, opt_sh),
            batch,
        )
        act = act_sharding_for(mesh, cfg, B, shape.seq_len)
        return dict(step=step, args=args, donate=(0, 1), act_sharding=act,
                    objective=objective, cfg=cfg, shape=shape, phase=None)

    if objective == "compress":
        step = build_compress_step(cfg, impl=impl)
        mc_sh, mc_abs = memcom_shardings(cfg, mesh, rules)
        tgt_sh, tgt_abs = param_shardings(cfg, mesh, rules)
        args = (
            _with_shardings(mc_abs, mc_sh),
            _with_shardings(tgt_abs, tgt_sh),
            batch,
        )
        act = act_sharding_for(mesh, cfg, B, shape.seq_len)
        return dict(step=step, args=args, donate=(), act_sharding=act,
                    objective=objective, cfg=cfg, shape=shape, phase=None)

    if objective == "prefill":
        step = build_prefill_step(cfg, max_len=shape.seq_len, impl=impl)
        p_sh, p_abs = param_shardings(cfg, mesh, rules)
        args = (_with_shardings(p_abs, p_sh), batch)
        act = act_sharding_for(mesh, cfg, B, shape.seq_len)
        return dict(step=step, args=args, donate=(), act_sharding=act,
                    objective=objective, cfg=cfg, shape=shape, phase=None)

    if objective.startswith("decode"):
        # decode: 1 new token against a cache of seq_len (vanilla baseline)
        # decode_compressed: cache = m memory slots + a generation window
        if objective == "decode_compressed":
            assert cfg.memcom is not None
            L = cfg.memcom.num_memory_tokens + (decode_window or 256)
        else:
            L = shape.seq_len
        step = build_decode_step(cfg, impl=impl if impl != "auto" else "dense")
        p_sh, p_abs = param_shardings(cfg, mesh, rules)
        cache_abs = jax.eval_shape(
            functools.partial(tfm.init_cache, cfg, B, L))
        cache_sh = cache_shardings(cfg, mesh, cache_abs)
        args = (
            _with_shardings(p_abs, p_sh),
            _with_shardings(cache_abs, cache_sh),
            batch,
        )
        return dict(step=step, args=args, donate=(1,), act_sharding=None,
                    objective=objective, cfg=cfg, shape=shape, phase=None)

    raise ValueError(objective)
