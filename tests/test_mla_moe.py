"""DeepSeek-V2's mechanisms at a small size on the CPU: the expert share
(guide §4 identity), dropless serving dispatch, YaRN against its closed
form, and the latent paged-decode kernel against its plain form."""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import ModelConfig, MoEConfig, LayerDesc, LayerLayout
from repro.configs import get_config, get_smoke_config
from repro.kernels import ops
from repro.models.layers import apply_mlp, rope_freqs
from repro.models.mla import softmax_scale
from repro.models.moe import _dropless_block, apply_moe, init_moe, split_rows
from repro.models.param import ParamBuilder
from repro.utils.rng import Keys


def _moe_cfg(**kw):
    moe = MoEConfig(num_experts=64, top_k=6, expert_d_ff=16,
                    num_shared_experts=2, shared_d_ff=16,
                    norm_topk_prob=False, capacity_factor=None)
    return ModelConfig(
        name="moe", family="moe",
        layout=LayerLayout.uniform(LayerDesc("attn", "moe"), 1),
        d_model=32, num_heads=2, num_kv_heads=2, d_ff=64, vocab_size=64,
        moe=dataclasses.replace(moe, **kw), dtype="float32")


def _moe_params(cfg, seed=0):
    b = ParamBuilder(Keys(seed), jnp.float32)
    init_moe(b, cfg)
    return b.build()[0]["moe"]


def test_expert_shares_sum_to_the_whole_layer(rng):
    """Over the 8 shares of a 64-expert layer, each holding 8 experts, the
    routed parts summed, with the shared experts counted once, equal the
    uncut layer; the shares' row counts are the uncut layer's, and each
    token's held pairs over the shares add up to its k."""
    whole = _moe_cfg()
    p = _moe_params(whole)
    x = jnp.asarray(rng.standard_normal((2, 24, 32)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        y_all, _, rows_all = apply_moe(p, whole, x)
        total, rows = 0.0, []
        for s in range(8):
            cfg = _moe_cfg(held_experts=8, expert_offset=8 * s)
            share = dict(p, **{k: p[k][8 * s:8 * s + 8]
                               for k in ("wg", "wi", "wo")})
            y, _, r = apply_moe(share, cfg, x)
            total = total + y
            rows.append(np.asarray(r)[None])
        shared_only = apply_mlp(p["shared"]["mlp"], whole, x,
                                mlp_type="swiglu")
    np.testing.assert_allclose(np.asarray(total - 7 * shared_only),
                               np.asarray(y_all), atol=1e-5, rtol=1e-5)
    experts, _, tokens = zip(*(split_rows(r, 8) for r in rows))
    whole_experts, _, whole_tokens = split_rows(np.asarray(rows_all)[None], 64)
    np.testing.assert_array_equal(np.concatenate(experts, 1), whole_experts)
    np.testing.assert_array_equal(sum(tokens), whole_tokens)
    assert whole_tokens.tolist() == [[6] * 48]


def _dense_moe(p, cfg, x):
    """Every routed pair computed, gates as configured (numpy)."""
    m = cfg.moe
    x = np.asarray(x, np.float64).reshape(-1, cfg.d_model)
    logits = x @ np.asarray(p["router"], np.float64)
    probs = np.exp(logits - logits.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    ids = np.argsort(-probs, -1, kind="stable")[:, :m.top_k]
    gates = np.take_along_axis(probs, ids, -1)
    if m.norm_topk_prob:
        gates /= gates.sum(-1, keepdims=True)
    silu = lambda z: z / (1 + np.exp(-z))
    y = np.zeros_like(x)
    for n in range(len(x)):
        for g, e in zip(gates[n], ids[n]):
            e -= m.expert_offset
            if 0 <= e < m.held:
                w = {k: np.asarray(p[k][e], np.float64) for k in ("wg", "wi", "wo")}
                y[n] += g * (silu(x[n] @ w["wg"]) * (x[n] @ w["wi"])) @ w["wo"]
    sh = {k: np.asarray(p["shared"]["mlp"][k], np.float64)
          for k in ("wg", "wi", "wo")}
    return y + (silu(x @ sh["wg"]) * (x @ sh["wi"])) @ sh["wo"]


@pytest.mark.parametrize("norm_topk_prob", [False, True])
def test_dropless_dispatch_keeps_every_token_on_one_expert(norm_topk_prob):
    """Every token routed to the same held expert: a dropless layer
    (capacity_factor None) computes all of them; the capacity dispatch
    (1.25) drops most."""
    cfg = _moe_cfg(held_experts=8, norm_topk_prob=norm_topk_prob)
    p = _moe_params(cfg)
    router = np.zeros((32, 64), np.float32)
    router[:, 0] = 4.0  # x > 0: expert 0 first for every token
    router[:, 1:6] = np.linspace(1.0, 0.5, 5)
    p = dict(p, router=jnp.asarray(router))
    x = jnp.asarray(np.abs(np.random.default_rng(3).standard_normal(
        (1, 40, 32))) + 0.1, jnp.float32)
    with jax.default_matmul_precision("highest"):
        y, _, rows = apply_moe(p, cfg, x)
        dropped, _, _ = apply_moe(
            p, cfg.replace(moe=dataclasses.replace(cfg.moe,
                                                   capacity_factor=1.25)), x)
    per_expert, computed, per_token = split_rows(np.asarray(rows)[None], 8)
    assert per_expert.tolist() == [[40] * 6 + [0, 0]]
    assert per_token.tolist() == [[6] * 40]
    assert computed.tolist() == [8 * 40]  # every buffer row, on the CPU
    want = _dense_moe(p, cfg, x)
    np.testing.assert_allclose(np.asarray(y).reshape(40, 32), want,
                               atol=1e-4, rtol=1e-4)
    assert np.abs(np.asarray(dropped).reshape(40, 32) - want).max() > 1e-2


def test_dropless_kernel_computes_only_the_routed_rows(rng):
    """Through the grouped-matmul kernel (interpreted), a dropless layer
    hands each expert's row count over: the kernel computes the routed
    rows in whole row tiles (the count the layer reports) and the layer
    agrees with the einsum path, which computes every buffer row."""
    cfg = _moe_cfg(held_experts=8)
    p = _moe_params(cfg)
    x = jnp.asarray(rng.standard_normal((2, 40, 32)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want, _, rows_e = apply_moe(p, cfg, x, impl="jnp")
        got, _, rows_k = apply_moe(p, cfg, x, impl="pallas")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5,
                               rtol=1e-5)
    per_expert, computed, per_token = split_rows(np.asarray(rows_k)[None], 8)
    bc = _dropless_block(cfg.moe, 80)
    assert bc == 16  # twice the even share of 80 * 6 / 64 rows, rounded up
    assert computed.tolist() == [int((-(-per_expert // bc) * bc).sum())]
    assert computed[0] < 8 * 80  # the einsum path's every buffer row
    e_experts, e_computed, e_tokens = split_rows(np.asarray(rows_e)[None], 8)
    np.testing.assert_array_equal(per_expert, e_experts)
    np.testing.assert_array_equal(per_token, e_tokens)
    assert e_computed.tolist() == [8 * 80]


def test_yarn_frequencies_and_scale_match_the_closed_form():
    """DeepSeek-V2-Lite's YaRN (factor 40 over 4,096 positions, beta 32/1,
    mscale 0.707 on both): the ramp runs from slot 10 to slot 23 of the 32
    frequency slots; slots up to 10 keep the base frequency, slots from 23
    on are divided by 40; the softmax scale is 192 ** -0.5 * (1 + 0.1 *
    0.707 * ln 40) ** 2 = 0.1147; cos/sin keep magnitude 1."""
    cfg = get_config("deepseek-v2-lite")
    base = 1.0 / 10000.0 ** (np.arange(0, 64, 2) / 64)
    ramp = np.clip((np.arange(32) - 10) / (23 - 10), 0, 1)
    want = base / 40 * ramp + base * (1 - ramp)
    got = np.asarray(rope_freqs(64, cfg.rope_theta, cfg.rope_scaling))
    np.testing.assert_allclose(got, want, rtol=1e-6)
    np.testing.assert_allclose(got[:11], base[:11], rtol=1e-6)
    np.testing.assert_allclose(got[23:], base[23:] / 40, rtol=1e-6)
    scale = 192 ** -0.5 * (1 + 0.1 * 0.707 * math.log(40)) ** 2
    assert softmax_scale(cfg) == pytest.approx(scale, rel=1e-9)
    assert softmax_scale(cfg) == pytest.approx(0.1147, abs=5e-5)
    # without scaling the frequencies are the plain ones
    np.testing.assert_allclose(np.asarray(rope_freqs(64, 1e4)), base,
                               rtol=1e-6)


@pytest.mark.parametrize("stacked,blocks", [(False, 8), (True, 2)])
def test_latent_paged_kernel_matches_plain_attention(stacked, blocks, rng):
    """The latent decode kernel (interpreted) reads K = a row's first K
    lanes and V = its first v_dim lanes of one padded pool, per slot
    through the table (``blocks`` columns a grid step, the table padded
    to a multiple), masked by length: it agrees with the plain form."""
    from repro.kernels.paged_attention import paged_latent_decode

    B, H, K, Vd, W, N, bs, nb = 3, 4, 40, 32, 128, 20, 8, 5
    pool = rng.standard_normal((N, bs, W)).astype(np.float32)
    pool[..., K:] = 0.0
    if stacked:
        pool = np.stack([pool * 0.5, pool])
    tables = rng.permutation(np.arange(1, N))[:B * nb].reshape(B, nb)
    tables = tables.astype(np.int32)
    lengths = np.array([1, 17, 40], np.int32)
    q = jnp.asarray(rng.standard_normal((B, 1, H, K)), jnp.float32)
    layer = jnp.int32(1) if stacked else None
    kw = dict(block_tables=jnp.asarray(tables), lengths=jnp.asarray(lengths),
              v_dim=Vd, layer=layer, scale=0.3)
    with jax.default_matmul_precision("highest"):
        got = paged_latent_decode(q, jnp.asarray(pool), blocks=blocks,
                                  interpret=True, **kw)
        want = ops.paged_latent_attention(q, jnp.asarray(pool), impl="dense",
                                          **kw)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5,
                               rtol=2e-5)


@pytest.mark.parametrize("S,H", [(3, 4), (2, 16)])
def test_latent_paged_kernel_reads_several_tokens_a_slot(S, H, rng):
    """S query tokens a slot (a fused step's lanes): the S·H query rows
    are padded to a multiple of 8 as a whole, each token masked causally
    at its own position, as the plain form."""
    from repro.kernels.paged_attention import paged_latent_decode

    B, K, Vd, W, N, bs, nb = 2, 40, 32, 128, 12, 8, 4
    pool = rng.standard_normal((N, bs, W)).astype(np.float32)
    pool[..., K:] = 0.0
    tables = rng.permutation(np.arange(1, N))[:B * nb].reshape(B, nb)
    lengths = np.array([S, 21], np.int32)
    q = jnp.asarray(rng.standard_normal((B, S, H, K)), jnp.float32)
    kw = dict(block_tables=jnp.asarray(tables.astype(np.int32)),
              lengths=jnp.asarray(lengths), v_dim=Vd, scale=0.3)
    with jax.default_matmul_precision("highest"):
        got = paged_latent_decode(q, jnp.asarray(pool), blocks=2,
                                  interpret=True, **kw)
        want = ops.paged_latent_attention(q, jnp.asarray(pool), impl="dense",
                                          **kw)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5,
                               rtol=2e-5)


def test_every_step_program_counts_its_moe_rows():
    """The held experts' row counts ride with every MoE step program's
    output, the fused step's included: every top-k pair of every real
    token (prompts, and each answer token fed back) is routed or absent,
    none of the bucket padding's or idle slots' (those are padded rows),
    ``gmm_calls`` counts kernel calls only (none on the CPU's einsum
    path), and the fused engine serves the classic engine's greedy
    tokens."""
    from repro.models import transformer as tfm
    from repro.serving import Request, VirtualClock
    from repro.serving.engine import ServingEngine

    cfg = get_smoke_config("deepseek-v2-lite")
    cfg = cfg.replace(moe=dataclasses.replace(cfg.moe, held_experts=8))
    params = tfm.init_params(cfg, 0)
    layers = sum(d.mlp == "moe" for d in cfg.layout.descriptors())
    rng = np.random.default_rng(1)
    prompts = [rng.integers(4, cfg.vocab_size, n).astype(np.int32)
               for n in (5, 11, 8, 3)]

    def run(fused):
        eng = ServingEngine(cfg, params, slots=2, max_len=40,
                            kv_layout="paged", block_size=4,
                            clock=VirtualClock(), fused_step=fused,
                            fused_chunk_tokens=4)
        calls = []
        prefill = eng._prefill

        def counted(*args):
            calls.append(args[2].shape[1])
            return prefill(*args)

        eng._prefill = counted
        reqs = [Request(tokens=p, max_new=5, arrival_s=0.002 * j)
                for j, p in enumerate(prompts)]
        out = eng.serve(reqs)
        return [list(map(int, out[r.uid])) for r in reqs], eng.stats(), calls

    toks, classic, _ = run(False)
    fused_toks, fused, calls = run(True)
    assert fused_toks == toks
    real = sum(map(len, prompts)) + len(prompts) * (5 - 1)
    for st in (classic, fused):
        m = st["moe"]
        assert m["routed_rows"] + m["absent_rows"] == \
            layers * cfg.moe.top_k * real
        assert m["routed_rows"] > 0 and m["absent_rows"] > 0
        assert m["padded_rows"] > 0  # bucket padding, idle slots
        assert m["gmm_calls"] == 0
    assert fused["engine"]["fused_steps"] > len(calls)
