"""Per-kernel validation: Pallas (interpret mode) and streaming-jnp paths
against the pure-jnp oracles in repro.kernels.ref, swept over shapes and
dtypes (deliverable c)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import jnp_impl, ops, ref
from repro.kernels import flash_attention as fa
from repro.kernels import memcom_xattn as mxk
from repro.kernels import moe_gmm, ssd_scan

jax.config.update("jax_enable_x64", False)


def _rand(rng, *shape, dtype=np.float32, scale=0.5):
    return jnp.asarray(rng.standard_normal(shape) * scale, dtype)


TOL = {"float32": 2e-5, "bfloat16": 2e-2}


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

ATTN_CASES = [
    # (B, Sq, Skv, Hq, Hkv, D, causal, softcap)
    (1, 64, 64, 4, 4, 32, True, 0.0),     # MHA causal
    (2, 96, 96, 4, 2, 64, True, 0.0),     # GQA causal
    (2, 128, 128, 8, 1, 32, True, 50.0),  # MQA + softcap (gemma2)
    (1, 37, 53, 4, 2, 64, False, 0.0),    # cross, ragged shapes
    (2, 1, 80, 4, 2, 64, True, 0.0),      # decode row
    (1, 200, 100, 2, 2, 128, True, 0.0),  # Sq > Skv
    # head-major tiles: GQA groups of 3 (smollm-360m's 15/5 heads), B > 1
    # with S = 1 (padded to one 8-row tile), Sq off the 8-row tile and
    # spanning two q blocks
    (3, 1, 100, 15, 5, 64, True, 0.0),
    (2, 13, 40, 6, 2, 32, True, 0.0),
    (1, 45, 45, 6, 2, 32, True, 50.0),
]


@pytest.mark.parametrize("case", ATTN_CASES)
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_flash_attention_vs_ref(rng, case, dtype):
    B, Sq, Skv, Hq, Hkv, D, causal, softcap = case
    dt = jnp.dtype(dtype)
    q = _rand(rng, B, Sq, Hq, D).astype(dt)
    k = _rand(rng, B, Skv, Hkv, D).astype(dt)
    v = _rand(rng, B, Skv, Hkv, D).astype(dt)
    if causal and Sq == 1:  # decode: q sits at the cache frontier
        q_pos = jnp.full((B, Sq), Skv - 30, jnp.int32)
        kv_pos = jnp.where(jnp.arange(Skv) < Skv - 29, jnp.arange(Skv), -1)
        kv_pos = jnp.broadcast_to(kv_pos, (B, Skv)).astype(jnp.int32)
    else:
        q_pos = jnp.broadcast_to(jnp.arange(Sq), (B, Sq)).astype(jnp.int32)
        kv_pos = jnp.broadcast_to(jnp.arange(Skv), (B, Skv)).astype(jnp.int32)
    o_ref = ref.attention_ref(
        q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32),
        q_pos=q_pos, kv_pos=kv_pos, causal=causal, softcap=softcap)
    o_pal = fa.flash_attention(
        q, k, v, q_pos=q_pos, kv_pos=kv_pos, causal=causal, softcap=softcap,
        block_q=32, block_k=32, interpret=True)
    np.testing.assert_allclose(
        np.asarray(o_pal, np.float32), np.asarray(o_ref), atol=TOL[dtype],
        rtol=TOL[dtype])


@pytest.mark.parametrize("case", ATTN_CASES[:4])
def test_jnp_chunked_vs_ref(rng, case):
    B, Sq, Skv, Hq, Hkv, D, causal, softcap = case
    q = _rand(rng, B, Sq, Hq, D)
    k = _rand(rng, B, Skv, Hkv, D)
    v = _rand(rng, B, Skv, Hkv, D)
    q_pos = jnp.broadcast_to(jnp.arange(Sq), (B, Sq)).astype(jnp.int32)
    kv_pos = jnp.broadcast_to(jnp.arange(Skv), (B, Skv)).astype(jnp.int32)
    o_ref = ref.attention_ref(q, k, v, q_pos=q_pos, kv_pos=kv_pos,
                              causal=causal, softcap=softcap)
    o_jnp = jnp_impl.attention_chunked(
        q, k, v, q_pos=q_pos, kv_pos=kv_pos, causal=causal, softcap=softcap,
        kv_chunk=32)
    np.testing.assert_allclose(np.asarray(o_jnp), np.asarray(o_ref),
                               atol=2e-5, rtol=2e-5)


def test_causal_blocked_vs_ref(rng):
    B, S, Hq, Hkv, D = 2, 128, 4, 2, 32
    q, k, v = _rand(rng, B, S, Hq, D), _rand(rng, B, S, Hkv, D), _rand(rng, B, S, Hkv, D)
    pos = jnp.broadcast_to(jnp.arange(S), (B, S)).astype(jnp.int32)
    o_ref = ref.attention_ref(q, k, v, q_pos=pos, kv_pos=pos, causal=True)
    for q_chunk, kv_chunk in [(32, 32), (64, 32), (128, 128)]:
        o = jnp_impl.attention_causal_blocked(
            q, k, v, q_chunk=q_chunk, kv_chunk=kv_chunk)
        np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref),
                                   atol=2e-5, rtol=2e-5)


def test_attention_with_prefix_exact(rng):
    """Prefix+self decomposition (LSE merge) == dense attention over the
    concatenated [prefix ; self] sequence."""
    B, S, m, Hq, Hkv, D = 2, 48, 16, 4, 2, 32
    q = _rand(rng, B, S, Hq, D)
    k_self, v_self = _rand(rng, B, S, Hkv, D), _rand(rng, B, S, Hkv, D)
    k_pre, v_pre = _rand(rng, B, m, Hkv, D), _rand(rng, B, m, Hkv, D)
    out = ops.attention_with_prefix(q, k_self, v_self, k_pre, v_pre,
                                    impl="jnp")
    # dense reference over concatenated kv
    k_cat = jnp.concatenate([k_pre, k_self], axis=1)
    v_cat = jnp.concatenate([v_pre, v_self], axis=1)
    kv_pos = jnp.concatenate(
        [jnp.arange(m)[None].repeat(B, 0),
         (m + jnp.arange(S))[None].repeat(B, 0)], axis=1).astype(jnp.int32)
    q_pos = (m + jnp.arange(S))[None].repeat(B, 0).astype(jnp.int32)
    o_ref = ref.attention_ref(q, k_cat, v_cat, q_pos=q_pos, kv_pos=kv_pos,
                              causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(o_ref),
                               atol=2e-5, rtol=2e-5)


def test_lse_merge_matches_monolithic(rng):
    """combine_attention_partials is an exact merge, not an approximation."""
    B, S, H, D = 1, 32, 2, 16
    q = _rand(rng, B, S, H, D)
    k = _rand(rng, B, 64, H, D)
    v = _rand(rng, B, 64, H, D)
    pos = jnp.arange(64)[None].astype(jnp.int32)
    q_pos = jnp.full((B, S), 63, jnp.int32)
    whole = ref.attention_ref(q, k, v, q_pos=q_pos, kv_pos=pos, causal=True)
    parts = []
    for lo, hi in [(0, 32), (32, 64)]:
        o, l = jnp_impl.attention_chunked(
            q, k[:, lo:hi], v[:, lo:hi], q_pos=q_pos, kv_pos=pos[:, lo:hi],
            causal=True, kv_chunk=16, return_lse=True)
        parts.append((o, l))
    merged = jnp_impl.combine_attention_partials(parts)
    np.testing.assert_allclose(np.asarray(merged), np.asarray(whole),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("L", [53, 56])
@pytest.mark.parametrize("S", [1, 3])
@pytest.mark.parametrize("impl", ["dense", "jnp", "pallas"])
def test_decode_attention_per_slot_lengths(rng, impl, S, L):
    """Continuous-batching decode: slot b sees exactly cache[:lengths[b]],
    whatever the other slots' lengths, on every backend.  ``L`` = 56 runs
    the Pallas path over the stripe as paged blocks, 53 the flash kernel."""
    B, Hq, Hkv, D = 4, 6, 2, 32
    q = _rand(rng, B, S, Hq, D)
    k = _rand(rng, B, L, Hkv, D)
    v = _rand(rng, B, L, Hkv, D)
    lengths = jnp.asarray([S, 17, 40, L], jnp.int32)  # ragged, incl. edges
    slot = jnp.arange(L, dtype=jnp.int32)
    kv_pos = jnp.where(slot[None] < lengths[:, None], slot[None], -1)
    q_pos = lengths[:, None] - S + jnp.arange(S, dtype=jnp.int32)[None]
    want = ref.attention_ref(q, k, v, q_pos=q_pos, kv_pos=kv_pos, causal=True)
    got = ops.decode_attention(q, k, v, lengths=lengths, impl=impl,
                               kv_chunk=16)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_auto_resolves_to_pallas_on_tpu(monkeypatch):
    """On the TPU backend ``auto`` runs the Pallas kernel at every size;
    the dense oracle stays the CPU small-shape path and an explicit
    choice."""
    assert ops._resolve("auto", small=True) == "dense"
    assert ops._resolve("auto", small=False) == "jnp"
    monkeypatch.setattr(ops.jax, "default_backend", lambda: "tpu")
    assert ops._resolve("auto", small=True) == "pallas"
    assert ops._resolve("auto", small=False) == "pallas"
    assert ops._resolve("dense", small=True) == "dense"


def test_decode_attention_ignores_unseated_tail(rng):
    """Garbage beyond each slot's length must not leak into the output —
    the per-slot masking the serving engine relies on for slot isolation."""
    B, L, Hq, Hkv, D = 2, 48, 4, 2, 16
    q = _rand(rng, B, 1, Hq, D)
    k = _rand(rng, B, L, Hkv, D)
    v = _rand(rng, B, L, Hkv, D)
    lengths = jnp.asarray([9, 21], jnp.int32)
    base = ops.decode_attention(q, k, v, lengths=lengths, impl="jnp",
                                kv_chunk=8)
    mask = (jnp.arange(L)[None, :, None, None] >= lengths[:, None, None, None])
    k2 = jnp.where(mask, 1e3, k)  # blow up the unseated tail
    v2 = jnp.where(mask, -1e3, v)
    poisoned = ops.decode_attention(q, k2, v2, lengths=lengths, impl="jnp",
                                    kv_chunk=8)
    np.testing.assert_array_equal(np.asarray(base), np.asarray(poisoned))


def test_decode_attention_empty_slot_is_zero(rng):
    """A slot with no valid KV (lengths=0) returns zeros like the oracle,
    not a uniform average of garbage values."""
    q = _rand(rng, 2, 1, 4, 16)
    k = _rand(rng, 2, 24, 2, 16)
    v = _rand(rng, 2, 24, 2, 16)
    lengths = jnp.asarray([0, 5], jnp.int32)
    out = ops.decode_attention(q, k, v, lengths=lengths, impl="jnp",
                               kv_chunk=8)
    assert np.all(np.asarray(out)[0] == 0)
    kv_pos = jnp.where(jnp.arange(24)[None] < lengths[:, None],
                       jnp.arange(24)[None], -1).astype(jnp.int32)
    want = ref.attention_ref(q, k, v, q_pos=lengths[:, None] - 1,
                             kv_pos=kv_pos, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# memcom cross-attention
# ---------------------------------------------------------------------------

XATTN_CASES = [
    (1, 8, 64, 64), (2, 48, 100, 64), (2, 32, 128, 256), (1, 17, 33, 128),
]


@pytest.mark.parametrize("case", XATTN_CASES)
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_memcom_xattn_vs_ref(rng, case, dtype):
    B, M, T, D = case
    dt = jnp.dtype(dtype)
    q, k, v = (_rand(rng, B, M, D).astype(dt), _rand(rng, B, T, D).astype(dt),
               _rand(rng, B, T, D).astype(dt))
    o_ref = ref.memcom_xattn_ref(
        q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32))
    o_pal = mxk.memcom_xattn(q, k, v, block_m=16, block_t=32, interpret=True)
    np.testing.assert_allclose(np.asarray(o_pal, np.float32),
                               np.asarray(o_ref), atol=TOL[dtype],
                               rtol=TOL[dtype])
    o_jnp = ops.memcom_xattn(q, k, v, impl="jnp")
    np.testing.assert_allclose(np.asarray(o_jnp, np.float32),
                               np.asarray(o_ref), atol=TOL[dtype],
                               rtol=TOL[dtype])


# ---------------------------------------------------------------------------
# grouped matmul
# ---------------------------------------------------------------------------

GMM_CASES = [(1, 8, 16, 8), (3, 20, 48, 36), (4, 64, 128, 64), (2, 7, 9, 5)]


@pytest.mark.parametrize("case", GMM_CASES)
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_gmm_vs_ref(rng, case, dtype):
    E, C, D, F = case
    dt = jnp.dtype(dtype)
    x, w = _rand(rng, E, C, D).astype(dt), _rand(rng, E, D, F).astype(dt)
    g_ref = ref.gmm_ref(x.astype(jnp.float32), w.astype(jnp.float32))
    g_pal = moe_gmm.gmm(x, w, block_c=8, block_d=16, block_f=16,
                        interpret=True)
    np.testing.assert_allclose(np.asarray(g_pal, np.float32),
                               np.asarray(g_ref), atol=10 * TOL[dtype],
                               rtol=10 * TOL[dtype])


# ---------------------------------------------------------------------------
# Mamba2 SSD
# ---------------------------------------------------------------------------

SSD_CASES = [
    # (B, S, H, P, G, N, chunk)
    (1, 32, 2, 8, 1, 8, 8),
    (2, 70, 4, 16, 2, 8, 16),
    (1, 64, 4, 32, 4, 16, 32),
    (2, 33, 2, 8, 1, 4, 16),  # ragged
]


@pytest.mark.parametrize("case", SSD_CASES)
@pytest.mark.parametrize("with_init", [False, True])
def test_ssd_vs_ref(rng, case, with_init):
    B, S, H, P, G, N, chunk = case
    x = _rand(rng, B, S, H, P)
    dt = jnp.abs(_rand(rng, B, S, H)) * 0.2
    A = -jnp.abs(jnp.asarray(rng.standard_normal(H), np.float32))
    Bm, Cm = _rand(rng, B, S, G, N), _rand(rng, B, S, G, N)
    h0 = _rand(rng, B, H, P, N) if with_init else None
    y_ref, hf_ref = ref.ssd_ref(x, dt, A, Bm, Cm, init_state=h0)
    y_pal, hf_pal = ssd_scan.ssd(x, dt, A, Bm, Cm, init_state=h0,
                                 chunk=chunk, interpret=True)
    np.testing.assert_allclose(np.asarray(y_pal), np.asarray(y_ref),
                               atol=5e-5, rtol=5e-5)
    np.testing.assert_allclose(np.asarray(hf_pal), np.asarray(hf_ref),
                               atol=5e-5, rtol=5e-5)
    y_jnp, hf_jnp = jnp_impl.ssd_chunked(x, dt, A, Bm, Cm, init_state=h0,
                                         chunk=chunk)
    np.testing.assert_allclose(np.asarray(y_jnp), np.asarray(y_ref),
                               atol=5e-5, rtol=5e-5)
    np.testing.assert_allclose(np.asarray(hf_jnp), np.asarray(hf_ref),
                               atol=5e-5, rtol=5e-5)


def test_ssd_decode_matches_scan(rng):
    """Token-by-token recurrent decode == chunked prefill outputs."""
    B, S, H, P, G, N = 1, 16, 2, 8, 1, 8
    x = _rand(rng, B, S, H, P)
    dt = jnp.abs(_rand(rng, B, S, H)) * 0.2
    A = -jnp.abs(jnp.asarray(rng.standard_normal(H), np.float32))
    Bm, Cm = _rand(rng, B, S, G, N), _rand(rng, B, S, G, N)
    y_ref, hf_ref = ref.ssd_ref(x, dt, A, Bm, Cm)
    state = jnp.zeros((B, H, P, N), jnp.float32)
    ys = []
    for t in range(S):
        y, state = jnp_impl.ssd_decode_step(
            state, x[:, t], dt[:, t], A, Bm[:, t], Cm[:, t])
        ys.append(y)
    y_dec = jnp.stack(ys, axis=1)
    np.testing.assert_allclose(np.asarray(y_dec), np.asarray(y_ref),
                               atol=5e-5, rtol=5e-5)
    np.testing.assert_allclose(np.asarray(state), np.asarray(hf_ref),
                               atol=5e-5, rtol=5e-5)
