"""Norms, positional embeddings (RoPE / M-RoPE / learned), MLPs."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.models.param import ParamBuilder


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def init_norm(b: ParamBuilder, cfg: ModelConfig, name: str, dim: int | None = None):
    d = dim or cfg.d_model
    nb = b.child(name)
    nb.make("scale", (d,), ("embed",), init="ones")
    if cfg.norm_type == "layernorm":
        nb.make("bias", (d,), ("embed",), init="zeros")


def apply_norm(p, cfg: ModelConfig, x):
    xf = x.astype(jnp.float32)
    if cfg.norm_type == "layernorm":
        mu = xf.mean(-1, keepdims=True)
        var = ((xf - mu) ** 2).mean(-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + cfg.norm_eps)
        y = y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    else:  # rmsnorm
        var = (xf**2).mean(-1, keepdims=True)
        y = xf * jax.lax.rsqrt(var + cfg.norm_eps) * p["scale"].astype(jnp.float32)
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE / M-RoPE
# ---------------------------------------------------------------------------


def rope_freqs(head_dim: int, theta: float, scaling=None) -> jax.Array:
    """Rotary frequencies of a ``head_dim``-lane head; with a YaRN
    ``scaling`` (:class:`repro.config.YarnScaling`) they are blended
    between interpolated (``/ factor``) and original by YaRN's ramp."""
    freqs = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, jnp.float32) / head_dim))
    if scaling is None:
        return freqs
    low, high = _yarn_correction_range(scaling, head_dim, theta)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(head_dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    keep = 1.0 - ramp  # 1: original frequency, 0: interpolated
    return freqs / scaling.factor * ramp + freqs * keep


def _yarn_correction_range(scaling, head_dim: int, theta: float):
    """YaRN's ramp bounds: the frequency slots that turn ``beta_fast``
    resp. ``beta_slow`` times over the original context."""
    n = scaling.original_max_position_embeddings

    def dim(rotations):
        return (head_dim * math.log(n / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    return (max(math.floor(dim(scaling.beta_fast)), 0),
            min(math.ceil(dim(scaling.beta_slow)), head_dim - 1))


def _yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def rope_attention_scale(scaling) -> float:
    """Factor on the softmax scale under YaRN scaling (1 without)."""
    if scaling is None:
        return 1.0
    return _yarn_mscale(scaling.factor, scaling.mscale_all_dim) ** 2


def apply_rope(x: jax.Array, positions: jax.Array, theta: float,
               mrope_sections=(), scaling=None) -> jax.Array:
    """x: (B, S, H, Dh); positions: (B, S) or (3, B, S) for M-RoPE.

    M-RoPE (Qwen2-VL): the Dh/2 frequency slots are partitioned into
    (temporal, height, width) sections; each section uses the matching
    position stream.  Text tokens carry identical t/h/w positions, which
    reduces exactly to standard RoPE.

    ``scaling`` (YaRN) changes the frequencies and multiplies cos/sin by
    ``mscale(factor, mscale) / mscale(factor, mscale_all_dim)``.
    """
    Dh = x.shape[-1]
    freqs = rope_freqs(Dh, theta, scaling)  # (Dh/2,)
    if positions.ndim == 3:
        assert mrope_sections, "3-D positions require mrope_sections"
        sec_ids = jnp.repeat(
            jnp.arange(len(mrope_sections)),
            jnp.asarray(mrope_sections),
            total_repeat_length=Dh // 2,
        )  # (Dh/2,) in {0,1,2}
        pos = positions.astype(jnp.float32)  # (3,B,S)
        # angle[b,s,f] = pos[sec(f), b, s] * freqs[f]
        angles = jnp.take(pos, sec_ids, axis=0)  # (Dh/2, B, S)
        angles = jnp.moveaxis(angles, 0, -1) * freqs  # (B,S,Dh/2)
    else:
        angles = positions.astype(jnp.float32)[..., None] * freqs  # (B,S,Dh/2)
    cos = jnp.cos(angles)[:, :, None, :]  # (B,S,1,Dh/2)
    sin = jnp.sin(angles)[:, :, None, :]
    if scaling is not None:
        mag = (_yarn_mscale(scaling.factor, scaling.mscale)
               / _yarn_mscale(scaling.factor, scaling.mscale_all_dim))
        if mag != 1.0:
            cos, sin = cos * mag, sin * mag
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_pos_embed(num_pos: int, dim: int) -> jax.Array:
    pos = jnp.arange(num_pos, dtype=jnp.float32)[:, None]
    i = jnp.arange(dim // 2, dtype=jnp.float32)[None, :]
    angle = pos / (10000 ** (2 * i / dim))
    return jnp.concatenate([jnp.sin(angle), jnp.cos(angle)], axis=-1)


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------


def _act(name: str):
    return {"silu": jax.nn.silu, "gelu": jax.nn.gelu}[name]


def init_mlp(b: ParamBuilder, cfg: ModelConfig, d_ff: int | None = None,
             mlp_type: str | None = None):
    d, f = cfg.d_model, d_ff or cfg.d_ff
    t = mlp_type or cfg.mlp_type
    mb = b.child("mlp")
    if t == "gelu_mlp":
        mb.make("wi", (d, f), ("embed", "ff"))
        mb.make("bi", (f,), ("ff",), init="zeros")
        mb.make("wo", (f, d), ("ff", "embed"))
        mb.make("bo", (d,), ("embed",), init="zeros")
    else:  # swiglu / geglu
        mb.make("wg", (d, f), ("embed", "ff"))
        mb.make("wi", (d, f), ("embed", "ff"))
        mb.make("wo", (f, d), ("ff", "embed"))


def apply_mlp(p, cfg: ModelConfig, x, mlp_type: str | None = None):
    t = mlp_type or cfg.mlp_type
    if t == "gelu_mlp":
        h = jax.nn.gelu(x @ p["wi"] + p["bi"])
        return h @ p["wo"] + p["bo"]
    act = jax.nn.silu if t == "swiglu" else jax.nn.gelu
    return (act(x @ p["wg"]) * (x @ p["wi"])) @ p["wo"]


def softcap(x, cap: float):
    if cap:
        return cap * jnp.tanh(x / cap)
    return x
