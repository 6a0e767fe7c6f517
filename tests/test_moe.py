"""MoE dispatch tests: dense-reference equivalence at lossless capacity,
group-local == global dispatch, capacity-drop accounting, shared experts."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import LayerDesc, LayerLayout, MoEConfig, ModelConfig
from repro.models.moe import apply_moe, init_moe, _capacity
from repro.models.param import ParamBuilder
from repro.utils.rng import Keys


def _cfg(E=8, k=2, cf=1.25, groups=1, shared=0):
    return ModelConfig(
        name="moe-test", family="moe",
        layout=LayerLayout.uniform(LayerDesc("attn", "moe"), 1),
        d_model=32, num_heads=2, num_kv_heads=2, d_ff=64, vocab_size=64,
        moe=MoEConfig(num_experts=E, top_k=k, expert_d_ff=64,
                      capacity_factor=cf, dispatch_groups=groups,
                      num_shared_experts=shared, shared_d_ff=64),
        dtype="float32")


def _params(cfg, seed=0):
    b = ParamBuilder(Keys(seed), jnp.float32)
    init_moe(b, cfg)
    params, _ = b.build()
    return params["moe"]


def _dense_reference(p, cfg, x):
    """Every token through its top-k experts, no capacity limit."""
    m = cfg.moe
    B, S, D = x.shape
    xf = x.reshape(-1, D)
    logits = (xf @ p["router"]).astype(jnp.float32)
    probs = jax.nn.softmax(logits, -1)
    gates, ids = jax.lax.top_k(probs, m.top_k)
    gates = gates / gates.sum(-1, keepdims=True)
    # run every expert on every token, then select
    h = jnp.einsum("nd,edf->nef", xf, p["wg"])
    h = jax.nn.silu(h) * jnp.einsum("nd,edf->nef", xf, p["wi"])
    y_all = jnp.einsum("nef,efd->ned", h, p["wo"])  # (N, E, D)
    y = jnp.take_along_axis(y_all, ids[..., None], axis=1)  # (N, k, D)
    return (y * gates[..., None]).sum(1).reshape(B, S, D)


def test_moe_matches_dense_at_lossless_capacity(rng):
    cfg = _cfg(E=8, k=2, cf=8.0 / 2.0)  # C >= N·k/E·(E/k) = N: no drops
    p = _params(cfg)
    x = jnp.asarray(rng.standard_normal((2, 16, 32)) * 0.5, jnp.float32)
    y, aux, _ = apply_moe(p, cfg, x)
    y_ref = _dense_reference(p, cfg, x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               atol=1e-5, rtol=1e-5)
    assert float(aux) >= 0


@pytest.mark.parametrize("groups", [2, 4])
def test_grouped_dispatch_matches_global(rng, groups):
    """At lossless capacity, G-group dispatch == global dispatch exactly
    (same tokens reach the same experts; only the sort is local)."""
    cfg1 = _cfg(E=8, k=2, cf=4.0)
    cfgG = _cfg(E=8, k=2, cf=4.0, groups=groups)
    p = _params(cfg1)
    x = jnp.asarray(rng.standard_normal((2, 16, 32)) * 0.5, jnp.float32)
    y1, a1, _ = apply_moe(p, cfg1, x)
    yG, aG, _ = apply_moe(p, cfgG, x)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(yG),
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(float(a1), float(aG), rtol=1e-6)


def test_capacity_drops_zero_dropped_tokens(rng):
    """At capacity_factor→0 every token is dropped: output = 0 (plus
    shared expert if any) — the drop path must not corrupt outputs."""
    cfg = _cfg(E=8, k=2, cf=1e-9)
    p = _params(cfg)
    x = jnp.asarray(rng.standard_normal((1, 4, 32)), jnp.float32)
    # capacity rounds up to 8, so shrink further: N=4 tokens, C=8 means
    # nothing actually drops here — use many tokens instead
    x_big = jnp.asarray(rng.standard_normal((1, 512, 32)), jnp.float32)
    y, _, _ = apply_moe(p, cfg, x_big)
    # C=8 slots per expert × 8 experts = 64 of 1024 assignments survive
    kept_rows = (np.abs(np.asarray(y)).sum(-1) > 0).sum()
    assert kept_rows <= 64 * 2  # each kept assignment affects ≤1 token/expert


def test_shared_expert_always_on(rng):
    cfg = _cfg(E=4, k=1, cf=1e-9, shared=1)
    p = _params(cfg)
    x = jnp.asarray(rng.standard_normal((1, 256, 32)), jnp.float32)
    y, _, _ = apply_moe(p, cfg, x)
    # even fully-dropped tokens get the shared-expert path
    assert (np.abs(np.asarray(y)).sum(-1) > 0).all()


def test_capacity_formula():
    m = MoEConfig(num_experts=16, top_k=2, expert_d_ff=64,
                  capacity_factor=1.25)
    C = _capacity(m, 1024)
    assert C == 160  # 1.25 * 1024 * 2 / 16 = 160 (already 8-aligned)
    assert _capacity(m, 10) == 8  # floor
