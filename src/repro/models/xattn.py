"""MemCom's per-layer compression cross-attention (paper §4, App. D).

Variants: "1head" (paper default — a single head of width d_model),
"mha" (multi-head), "mqa" (multi-query).  Q comes from the Memory-LLM's
post-self-attention hidden state (pre-normed for stability), K = V are the
Source-LLM's *raw* layer-input representations, faithful to
``O^i = XAttn(Q=H_mem^i, K=H_src^i, V=H_src^i)``.
"""

from __future__ import annotations

import jax.numpy as jnp

from repro.config import ModelConfig
from repro.kernels import ops
from repro.models.layers import init_norm, apply_norm
from repro.models.param import ParamBuilder


def init_memcom_xattn(b: ParamBuilder, cfg: ModelConfig) -> None:
    d = cfg.d_model
    mc = cfg.memcom
    xb = b.child("memx")
    init_norm(xb, cfg, "norm")
    if mc.xattn_kind == "mqa":
        H = mc.xattn_heads
        hd = d // H
        # paper: modules are randomly initialized (trained in Phase-1);
        # wo gets a small scale so the initial perturbation of the memory
        # stream is mild but gradients flow to q/k/v from step one.
        xb.make("wq", (d, H * hd), ("embed", "heads"), scale=0.5)
        xb.make("wk", (d, hd), ("embed", "heads"), scale=0.5)
        xb.make("wv", (d, hd), ("embed", "heads"), scale=0.5)
        xb.make("wo", (H * hd, d), ("heads", "embed"), scale=0.1)
    else:  # "1head" (H=1) or "mha"
        xb.make("wq", (d, d), ("embed", "heads"), scale=0.5)
        xb.make("wk", (d, d), ("embed", "heads"), scale=0.5)
        xb.make("wv", (d, d), ("embed", "heads"), scale=0.5)
        xb.make("wo", (d, d), ("heads", "embed"), scale=0.1)


def apply_memcom_xattn(p, cfg: ModelConfig, mem_h, src_h, *, impl: str = "auto",
                       mesh=None):
    """mem_h: (B, m, D) memory residual; src_h: (B, T, D) source layer reps.
    Returns the cross-attention output (B, m, D) to be residually added.
    ``mesh`` reaches the kernels (Pallas runs per device on a mesh)."""
    mc = cfg.memcom
    q_in = apply_norm(p["norm"], cfg, mem_h)
    B, M, D = q_in.shape
    T = src_h.shape[1]

    if mc.xattn_kind == "1head":
        q = q_in @ p["wq"]
        k = src_h @ p["wk"]
        v = src_h @ p["wv"]
        o = ops.memcom_xattn(q, k, v, impl=impl, mesh=mesh)
        return o @ p["wo"]

    H = mc.xattn_heads
    kv_heads = 1 if mc.xattn_kind == "mqa" else H
    hd = D // H
    q = (q_in @ p["wq"]).reshape(B, M, H, hd)
    k = (src_h @ p["wk"]).reshape(B, T, kv_heads, hd)
    v = (src_h @ p["wv"]).reshape(B, T, kv_heads, hd)
    q_pos = jnp.zeros((B, M), jnp.int32)
    kv_pos = jnp.zeros((B, T), jnp.int32)
    o = ops.attention(q, k, v, q_pos=q_pos, kv_pos=kv_pos, causal=False,
                      impl=impl, mesh=mesh)
    return o.reshape(B, M, D) @ p["wo"]
