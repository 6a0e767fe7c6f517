"""Paged KV-cache tests: block allocator, ops-level paged/dense decode
parity over ragged lengths (jnp + pallas-interpret), engine parity,
copy-on-write isolation, admission gating, PrefixStore LRU eviction
with the seated-refcount guard, and in-place pool updates (stacked pools
read and written at a layer index, the donated cache)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core import memcom
from repro.kernels import ops
from repro.models import transformer as tfm
from repro.serving import engine as engine_mod
from repro.serving import (
    BlockAllocationError,
    BlockAllocator,
    OutOfBlocksError,
    PrefixSeatedError,
    Request,
    ServingEngine,
    materialize_prefix,
)


# ---------------------------------------------------------------------------
# Block allocator
# ---------------------------------------------------------------------------


def test_allocator_basics():
    a = BlockAllocator(8, 4)  # block 0 reserved -> 7 usable
    assert a.free_count == 7
    blocks = a.alloc(3)
    assert len(set(blocks)) == 3 and 0 not in blocks
    assert a.free_count == 4
    a.incref(blocks[0])
    a.decref(blocks[0])
    assert a.refcount(blocks[0]) == 1  # still held once
    a.decref(blocks[0])
    assert a.refcount(blocks[0]) == 0 and a.free_count == 5
    with pytest.raises(BlockAllocationError):
        a.decref(blocks[0])  # double free
    with pytest.raises(BlockAllocationError):
        a.incref(blocks[0])  # unallocated
    with pytest.raises(OutOfBlocksError):
        a.alloc(6)
    assert a.blocks_for(0) == 0
    assert a.blocks_for(4) == 1
    assert a.blocks_for(5) == 2


# ---------------------------------------------------------------------------
# Ops-level parity: paged vs dense decode over ragged lengths
# ---------------------------------------------------------------------------


def _paged_copy(k, v, bs, rng):
    """Split a dense (B, L, H, D) cache into a shuffled block pool plus
    per-slot tables (pool block order deliberately non-contiguous)."""
    B, L = k.shape[:2]
    nb = L // bs
    perm = rng.permutation(B * nb) + 1  # keep block 0 as the trash block
    tables = perm.reshape(B, nb).astype(np.int32)
    pool_k = np.zeros((B * nb + 1, bs) + k.shape[2:], k.dtype)
    pool_v = np.zeros((B * nb + 1, bs) + v.shape[2:], v.dtype)
    for b in range(B):
        for j in range(nb):
            pool_k[tables[b, j]] = k[b, j * bs:(j + 1) * bs]
            pool_v[tables[b, j]] = v[b, j * bs:(j + 1) * bs]
    return jnp.asarray(pool_k), jnp.asarray(pool_v), jnp.asarray(tables)


@pytest.mark.parametrize("impl", ["jnp", "pallas"])
@pytest.mark.parametrize("hq,hkv", [(4, 2), (4, 1)])  # GQA and MQA folds
def test_paged_decode_matches_dense(rng, impl, hq, hkv):
    B, L, D, bs = 4, 64, 16, 8
    lengths = jnp.asarray([1, 13, 40, 64], jnp.int32)  # ragged, incl. edges
    k = np.asarray(rng.standard_normal((B, L, hkv, D)), np.float32)
    v = np.asarray(rng.standard_normal((B, L, hkv, D)), np.float32)
    q = jnp.asarray(rng.standard_normal((B, 1, hq, D)), jnp.float32)
    pool_k, pool_v, tables = _paged_copy(k, v, bs, rng)

    want = ops.decode_attention(q, jnp.asarray(k), jnp.asarray(v),
                                lengths=lengths, impl="jnp")
    got = ops.paged_decode_attention(q, pool_k, pool_v, block_tables=tables,
                                     lengths=lengths, impl=impl)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4)


@pytest.mark.parametrize("S", [1, 3])
@pytest.mark.parametrize("bs", [8, 16])
def test_paged_pallas_gqa3_vs_ref(rng, S, bs):
    """The paged kernel's row layout (heads side by side in one pool row,
    query groups of G*Sp rows) against the oracle: GQA groups of 3 as in
    smollm-360m's 15/5 heads, S query rows padded to 8, several slots."""
    B, L, hq, hkv, D = 3, 48, 15, 5, 16
    lengths = jnp.asarray([S, 21, 48], jnp.int32)
    k = np.asarray(rng.standard_normal((B, L, hkv, D)), np.float32)
    v = np.asarray(rng.standard_normal((B, L, hkv, D)), np.float32)
    q = jnp.asarray(rng.standard_normal((B, S, hq, D)), jnp.float32)
    pool_k, pool_v, tables = _paged_copy(k, v, bs, rng)
    want = ops.paged_decode_attention(q, pool_k, pool_v, block_tables=tables,
                                      lengths=lengths, impl="dense")
    got = ops.paged_decode_attention(q, pool_k, pool_v, block_tables=tables,
                                     lengths=lengths, impl="pallas")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("L,bs", [(8, 8), (48, 48), (576, 288), (1040, 208)])
def test_dense_block_size(L, bs):
    from repro.kernels.paged_attention import _dense_block_size

    assert _dense_block_size(L) == bs
    assert _dense_block_size(L + 3) is None


@pytest.mark.parametrize("S", [1, 3])
def test_dense_stripe_as_paged_blocks_vs_ref(rng, S):
    """Dense-layout decode through the paged kernel: each slot's stripe
    read as consecutive pool blocks (576 rows -> 2 blocks of 288), GQA
    15/5, S query rows padded to 8, ragged lengths across block edges."""
    B, L, hq, hkv, D = 3, 576, 15, 5, 16
    lengths = jnp.asarray([S, 200, 576], jnp.int32)
    k = jnp.asarray(rng.standard_normal((B, L, hkv, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, L, hkv, D)), jnp.float32)
    q = jnp.asarray(rng.standard_normal((B, S, hq, D)), jnp.float32)
    want = ops.decode_attention(q, k, v, lengths=lengths, impl="dense")
    got = ops.decode_attention(q, k, v, lengths=lengths, impl="pallas")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_paged_scatter_then_decode(rng):
    """paged_scatter lands tokens at per-slot positions: scattering into
    the pool equals writing the dense cache rows."""
    B, L, H, D, bs = 2, 32, 2, 8, 8
    starts = jnp.asarray([5, 11], jnp.int32)
    k = np.asarray(rng.standard_normal((B, L, H, D)), np.float32)
    new = jnp.asarray(rng.standard_normal((B, 1, H, D)), jnp.float32)
    pool, _, tables = _paged_copy(k, k, bs, rng)
    pool = ops.paged_scatter(pool, new, tables, starts)
    view = np.asarray(ops.paged_gather(pool, tables))
    for b in range(B):
        np.testing.assert_array_equal(view[b, int(starts[b])],
                                      np.asarray(new)[b, 0])


# ---------------------------------------------------------------------------
# Engine-level parity and isolation
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def setup():
    cfg = get_smoke_config("smollm-135m")
    params = tfm.init_params(cfg, 0)
    mc = memcom.init_memcom(cfg, params, 1)
    return cfg, params, mc


def _materialize(setup, rng, n=40):
    cfg, params, mc = setup
    src = jnp.asarray(rng.integers(4, cfg.vocab_size, (1, n)), jnp.int32)
    return materialize_prefix(params, cfg, memcom.compress(mc, cfg, src)[0])


def test_paged_engine_matches_dense_ragged(setup, rng):
    """Ragged prompts + shared prefix + mid-stream refill: token streams
    identical across layouts (block_size 16 > m=8 so the prefix tail block
    is partial — seat/COW/refill all exercised)."""
    cfg, params, _ = setup
    m = cfg.memcom.num_memory_tokens
    mat = _materialize(setup, rng)
    prompts = [rng.integers(4, cfg.vocab_size, n).astype(np.int32)
               for n in (5, 11, 3)]
    outs = []
    for layout, kw in (("dense", {}), ("paged", {"block_size": 16})):
        eng = ServingEngine(cfg, params, slots=2, max_len=m + 24,
                            kv_layout=layout, **kw)
        eng.add_prefix("task", mat)
        reqs = [Request(tokens=p, max_new=4, prefix="task") for p in prompts]
        out = eng.serve(reqs)
        outs.append([out[r.uid] for r in reqs])
    for d, p in zip(*outs):
        np.testing.assert_array_equal(d, p)


def test_cow_isolation(setup, rng):
    """Two slots seated on one task: slot 0 prefills + decodes (forcing a
    copy-on-write of the shared partial tail block); slot 1's visible
    prefix blocks stay bit-identical and its block table still names the
    original shared blocks."""
    cfg, params, _ = setup
    m = cfg.memcom.num_memory_tokens
    mat = _materialize(setup, rng)
    # block_size 16 > m=8: the whole prefix lives in one *partial* block,
    # so slot 0's first prompt token must trigger the COW
    eng = ServingEngine(cfg, params, slots=2, max_len=m + 24,
                        kv_layout="paged", block_size=16)
    eng.add_prefix("task", mat)
    eng.seat_prefix(0, "task")
    eng.seat_prefix(1, "task")
    shared = eng.store.blocks("task")
    assert eng._slot_blocks[0] == shared and eng._slot_blocks[1] == shared

    def slot1_view():
        """Slot 1's visible cache content: every KV leaf of its blocks."""
        tables = jnp.asarray(eng.tables[1:2])
        leaves = []
        for entry in eng.cache.get("prefix", []):
            for key in ("k", "v", "lat"):
                if key in entry:
                    leaves.append(np.asarray(
                        ops.paged_gather(entry[key], tables))[:, :m])
        for entry in eng.cache.get("period", {}).values():
            for key in ("k", "v", "lat"):
                if key in entry:
                    for r in range(entry[key].shape[0]):
                        leaves.append(np.asarray(
                            ops.paged_gather(entry[key][r], tables))[:, :m])
        return leaves

    before = slot1_view()
    out = eng.serve([Request(tokens=rng.integers(4, cfg.vocab_size, 6)
                             .astype(np.int32), max_new=5, prefix="task")])
    assert len(out) == 1
    # slot 0 went through serve -> COW: its tail block is now private
    assert eng._slot_blocks[0] != shared
    assert eng._slot_blocks[1] == shared  # untouched
    after = slot1_view()
    for a, b in zip(before, after):
        np.testing.assert_array_equal(a, b)  # bit-identical


def test_refill_frees_private_blocks_not_prefix(setup, rng):
    """More requests than slots: refills free each slot's private blocks
    back to the pool while the store's prefix blocks stay resident — the
    allocator ends exactly where a fresh double-seat would."""
    cfg, params, _ = setup
    m = cfg.memcom.num_memory_tokens
    mat = _materialize(setup, rng)
    eng = ServingEngine(cfg, params, slots=2, max_len=m + 24,
                        kv_layout="paged", block_size=8)
    eng.add_prefix("task", mat)
    prefix_blocks = set(eng.store.blocks("task"))
    reqs = [Request(tokens=rng.integers(4, cfg.vocab_size, 4)
                    .astype(np.int32), max_new=2, prefix="task")
            for _ in range(6)]
    eng.serve(reqs)
    # prefix blocks still resident (store ref) and seated in the 2 slots
    for b in prefix_blocks:
        assert eng.alloc.refcount(b) >= 1
    # every non-prefix allocated block is accounted to a live slot table
    live = set(eng._slot_blocks[0]) | set(eng._slot_blocks[1]) | prefix_blocks
    assert eng.alloc.used_count == len(live)


def test_admission_gated_on_free_blocks(setup, rng):
    """A pool that only fits one request's window at a time still serves
    every request (admission defers, slots refill), and an impossible
    request fails fast instead of deadlocking."""
    cfg, params, _ = setup
    m = cfg.memcom.num_memory_tokens
    mat = _materialize(setup, rng)
    # prefix: 1 block; each request needs <= 2 private blocks (bucket 8 +
    # decode) + COW headroom — 4 free blocks serve exactly one at a time
    eng = ServingEngine(cfg, params, slots=2, max_len=m + 16,
                        kv_layout="paged", block_size=8, num_blocks=6)
    eng.add_prefix("task", mat)
    prompts = [rng.integers(4, cfg.vocab_size, 5).astype(np.int32)
               for _ in range(3)]
    reqs = [Request(tokens=p, max_new=3, prefix="task") for p in prompts]
    out = eng.serve(reqs)
    assert len(out) == 3
    solo = ServingEngine(cfg, params, slots=1, max_len=m + 16,
                         kv_layout="paged", block_size=8)
    solo.add_prefix("task", mat)
    want = solo.serve([Request(tokens=prompts[0], max_new=3, prefix="task")])
    np.testing.assert_array_equal(out[reqs[0].uid],
                                  next(iter(want.values())))


def test_admission_reserves_decode_windows(setup, rng):
    """Two long-decoding requests whose prefill fits but whose *combined*
    decode windows exceed the pool: the gate must reserve each admitted
    request's whole window, deferring the second request instead of
    letting both slots race the pool empty mid-decode."""
    cfg, params, _ = setup
    # 4 usable blocks; each request: 8-token prompt (1 block) + decode to
    # 18 tokens (3 blocks total) -> both prefills fit (2 blocks), but the
    # decode windows need 6 > 4
    eng = ServingEngine(cfg, params, slots=2, max_len=24,
                        kv_layout="paged", block_size=8, num_blocks=5)
    prompts = [rng.integers(4, cfg.vocab_size, 8).astype(np.int32)
               for _ in range(2)]
    reqs = [Request(tokens=p, max_new=10) for p in prompts]
    out = eng.serve(reqs)  # unfixed: OutOfBlocksError mid-decode
    assert sorted(len(v) for v in out.values()) == [10, 10]
    for p, r in zip(prompts, reqs):
        solo = ServingEngine(cfg, params, slots=1, max_len=24,
                             kv_layout="paged", block_size=8)
        want = solo.serve([Request(tokens=p, max_new=10)])
        np.testing.assert_array_equal(out[r.uid], next(iter(want.values())))


def test_admission_gate_impossible_request(setup, rng):
    cfg, params, _ = setup
    m = cfg.memcom.num_memory_tokens
    mat = _materialize(setup, rng)
    # 2 usable blocks: 1 holds the prefix, and a 9-token prompt (bucket 16)
    # needs 2 more — impossible even after reclaiming free slots
    tiny = ServingEngine(cfg, params, slots=1, max_len=m + 16,
                         kv_layout="paged", block_size=8, num_blocks=3)
    tiny.add_prefix("task", mat)
    big = rng.integers(4, cfg.vocab_size, 9).astype(np.int32)
    with pytest.raises(OutOfBlocksError):
        tiny.serve([Request(tokens=big, max_new=3, prefix="task")])


def test_paged_hybrid_recurrent_state(rng):
    """Hybrid (attn+mamba) paged serving: recurrent leaves stay per-slot
    and a slot turnover still clears them — identical requests before and
    after a refill produce identical tokens."""
    cfg = get_smoke_config("jamba-1.5-large-398b")
    params = tfm.init_params(cfg, 0)
    mc = memcom.init_memcom(cfg, params, 1)
    m = cfg.memcom.num_memory_tokens
    mats = []
    for _ in range(2):
        src = jnp.asarray(rng.integers(4, cfg.vocab_size, (1, 24)), jnp.int32)
        mats.append(materialize_prefix(params, cfg,
                                       memcom.compress(mc, cfg, src)[0]))
    eng = ServingEngine(cfg, params, slots=2, max_len=m + 24,
                        kv_layout="paged", block_size=16)
    eng.add_prefix("A", mats[0])
    eng.add_prefix("B", mats[1])
    prompt = rng.integers(4, cfg.vocab_size, 6).astype(np.int32)
    reqs = [Request(tokens=prompt, max_new=3, prefix="A"),
            Request(tokens=prompt, max_new=3, prefix="B"),
            Request(tokens=prompt, max_new=3, prefix="A")]  # refills a slot
    out = eng.serve(reqs)
    np.testing.assert_array_equal(out[reqs[0].uid], out[reqs[2].uid])


def test_paged_mla_engine_parity(rng):
    """MLA latent cache paged vs dense (absorbed decode walks the latent
    block pool)."""
    cfg = get_smoke_config("deepseek-v2-236b")
    params = tfm.init_params(cfg, 0)
    prompts = [rng.integers(4, cfg.vocab_size, n).astype(np.int32)
               for n in (4, 9)]
    outs = []
    for layout in ("dense", "paged"):
        eng = ServingEngine(cfg, params, slots=2, max_len=24,
                            kv_layout=layout)
        out = eng.serve([Request(tokens=p, max_new=3) for p in prompts])
        outs.append([out[k] for k in sorted(out)])
    for d, p in zip(*outs):
        np.testing.assert_array_equal(d, p)


# ---------------------------------------------------------------------------
# PrefixStore LRU eviction + seated guard
# ---------------------------------------------------------------------------


def test_prefix_store_lru_eviction_and_seated_guard(setup, rng):
    cfg, params, _ = setup
    m = cfg.memcom.num_memory_tokens
    eng = ServingEngine(cfg, params, slots=2, max_len=m + 16,
                        kv_layout="paged", block_size=8, prefix_capacity=2)
    mats = [_materialize(setup, rng) for _ in range(3)]
    eng.add_prefix("t0", mats[0])
    eng.add_prefix("t1", mats[1])
    eng.seat_prefix(0, "t0")

    # capacity 2: inserting t2 must evict the LRU *unseated* entry (t1,
    # even though t0 is older) and free its blocks
    free_before = eng.alloc.free_count
    eng.add_prefix("t2", mats[2])
    assert "t1" not in eng.store and "t0" in eng.store and "t2" in eng.store
    # t1's blocks went back to the pool and t2 drew the same number (the
    # LIFO free list may hand t2 the very same ids)
    assert eng.alloc.free_count == free_before

    # explicit eviction of a seated prefix refuses
    with pytest.raises(PrefixSeatedError):
        eng.store.evict("t0")
    assert eng.store.seated("t0") and not eng.store.seated("t2")

    # all resident prefixes seated + at capacity -> put raises
    eng.seat_prefix(1, "t2")
    with pytest.raises(PrefixSeatedError):
        eng.add_prefix("t3", mats[1])

    # unseating (slot refill onto another task) makes t0 evictable again
    eng.seat_prefix(0, "t2")
    assert not eng.store.seated("t0")
    eng.add_prefix("t3", mats[1])
    assert "t0" not in eng.store


# ---------------------------------------------------------------------------
# Exact block_size boundaries (seat / prefill / decode accounting audit)
# ---------------------------------------------------------------------------


def _block_leaves(eng, blocks):
    """Bit-exact content of the given pool blocks across every KV leaf."""
    out = []
    for entry in eng.cache.get("prefix", []):
        for key in ("k", "v", "lat"):
            if key in entry:
                out.append(np.asarray(entry[key][np.asarray(blocks)]))
    for entry in eng.cache.get("period", {}).values():
        for key in ("k", "v", "lat"):
            if key in entry:
                out.append(np.asarray(entry[key][:, np.asarray(blocks)]))
    return out


def test_exact_block_multiple_prefix_no_cow(setup, rng):
    """Prefix length an exact block multiple: the tail block is *full*, so
    seating and prefilling behind it must neither copy-on-write nor touch
    the shared blocks — and the served tokens still match the dense
    engine."""
    cfg, params, _ = setup
    m = cfg.memcom.num_memory_tokens
    bs = m // 2 if m % 2 == 0 else m  # m % bs == 0 either way
    assert m % bs == 0
    mat = _materialize(setup, rng)
    prompts = [rng.integers(4, cfg.vocab_size, n).astype(np.int32)
               for n in (5, 3)]

    dense = ServingEngine(cfg, params, slots=2, max_len=m + 24)
    dense.add_prefix("task", mat)
    reqs = [Request(tokens=p, max_new=4, prefix="task") for p in prompts]
    want = dense.serve(reqs)

    eng = ServingEngine(cfg, params, slots=2, max_len=m + 24,
                        kv_layout="paged", block_size=bs)
    eng.add_prefix("task", mat)
    shared = eng.store.blocks("task")
    assert len(shared) == m // bs  # exactly full blocks, no partial tail
    before = _block_leaves(eng, shared)
    reqs2 = [Request(tokens=p, max_new=4, prefix="task") for p in prompts]
    got = eng.serve(reqs2)
    for r, r2 in zip(reqs, reqs2):
        np.testing.assert_array_equal(want[r.uid], got[r2.uid])
    # both slots still point at the shared blocks for the prefix region —
    # no COW fired (a full tail block is never written into)
    for slot in range(2):
        assert eng._slot_blocks[slot][:len(shared)] == shared
    after = _block_leaves(eng, shared)
    for a, b in zip(before, after):
        np.testing.assert_array_equal(a, b)
    # the +1 tail-COW reserve only applies to partial tails
    probe = Request(tokens=prompts[0], max_new=4, prefix="task")
    need = eng._blocks_needed(probe, m)
    n = len(probe.tokens)
    cap = eng.max_len - m
    from repro.serving.compiler import pow2_bucket
    width = max(1, min(pow2_bucket(n, 8), cap))
    expect = (eng.alloc.blocks_for(m + max(width, n + probe.max_new))
              - eng.alloc.blocks_for(m))
    assert need == expect  # no spurious +1 at the exact boundary


def test_decode_across_block_boundary_exact_base(setup, rng):
    """Recurrent-free exact-width prefill (prompt + decode budget chosen so
    decode writes cross into a fresh block exactly at a boundary): the
    decode-time allocation draws down the admission reservation and the
    tokens match dense."""
    cfg, params, _ = setup
    m = cfg.memcom.num_memory_tokens
    bs = 4
    mat = _materialize(setup, rng)
    # width buckets to 8; n + max_new = 12 > 8 forces decode allocations,
    # and m + 8 .. m + 12 crosses a block boundary when m % 4 == 0
    prompt = rng.integers(4, cfg.vocab_size, 7).astype(np.int32)
    dense = ServingEngine(cfg, params, slots=1, max_len=m + 24)
    dense.add_prefix("task", mat)
    want = next(iter(dense.serve(
        [Request(tokens=prompt, max_new=5, prefix="task")]).values()))

    eng = ServingEngine(cfg, params, slots=1, max_len=m + 24,
                        kv_layout="paged", block_size=bs)
    eng.add_prefix("task", mat)
    got = next(iter(eng.serve(
        [Request(tokens=prompt, max_new=5, prefix="task")]).values()))
    np.testing.assert_array_equal(want, got)
    assert int(eng._reserved[0]) == 0  # finished slot returned its reserve


def test_admission_need_is_exact_at_block_boundary(setup, rng):
    """Pool sized to the *exact* worst-case need admits and serves; one
    block fewer fails fast with OutOfBlocksError — i.e. the admission
    accounting neither under- nor over-reserves at an exact-multiple
    base."""
    cfg, params, _ = setup
    m = cfg.memcom.num_memory_tokens
    bs = m if m > 0 else 4  # prefix occupies exactly one full block
    mat = _materialize(setup, rng)
    prompt = rng.integers(4, cfg.vocab_size, 3).astype(np.int32)

    probe = ServingEngine(cfg, params, slots=1, max_len=m + 16,
                          kv_layout="paged", block_size=bs)
    probe.add_prefix("task", mat)
    req = Request(tokens=prompt, max_new=2, prefix="task")
    need = probe._blocks_needed(req, m)
    store_blocks = len(probe.store.blocks("task"))

    exact = 1 + store_blocks + need  # trash + resident prefix + window
    eng = ServingEngine(cfg, params, slots=1, max_len=m + 16,
                        kv_layout="paged", block_size=bs, num_blocks=exact)
    eng.add_prefix("task", mat)
    out = eng.serve([Request(tokens=prompt, max_new=2, prefix="task")])
    assert len(next(iter(out.values()))) == 2

    tight = ServingEngine(cfg, params, slots=1, max_len=m + 16,
                          kv_layout="paged", block_size=bs,
                          num_blocks=exact - 1)
    tight.add_prefix("task", mat)
    with pytest.raises(OutOfBlocksError):
        tight.serve([Request(tokens=prompt, max_new=2, prefix="task")])


# ---------------------------------------------------------------------------
# In-place pool updates: stacked pools read at a layer index, donated cache
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("S", [1, 3])
@pytest.mark.parametrize("hq,hkv", [(4, 2), (15, 5), (4, 1)])
def test_paged_flash_decode_stacked_layer_matches_slice(rng, hq, hkv, S):
    """The kernel on a stacked lane-merged pool and a layer index reads
    exactly that layer's blocks: the same result as the 3-D call on the
    layer's slice (interpret mode, GQA and MQA folds)."""
    from repro.kernels import paged_attention as pa

    R, N, bs, D, B, nb = 3, 13, 8, 16, 3, 4
    kst = jnp.asarray(rng.standard_normal((R, N, bs, hkv * D)), jnp.float32)
    vst = jnp.asarray(rng.standard_normal((R, N, bs, hkv * D)), jnp.float32)
    q = jnp.asarray(rng.standard_normal((B, S, hq, D)), jnp.float32)
    tables = jnp.asarray(rng.integers(1, N, (B, nb)), jnp.int32)
    lengths = jnp.asarray([S, 17, nb * bs], jnp.int32)
    for layer in range(R):
        want = pa.paged_flash_decode(q, kst[layer], vst[layer],
                                     block_tables=tables, lengths=lengths,
                                     interpret=True)
        got = pa.paged_flash_decode(q, kst, vst, block_tables=tables,
                                    lengths=lengths, layer=jnp.int32(layer),
                                    interpret=True)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        jnp_got = ops.paged_decode_attention(
            q, kst, vst, block_tables=tables, lengths=lengths,
            layer=jnp.int32(layer), impl="jnp")
        np.testing.assert_allclose(np.asarray(jnp_got), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("impl", ["jnp", "pallas"])
def test_paged_decode_ignores_row_padding(rng, impl):
    """Rows padded past their heads to the 128-lane tile (the serving
    cache's pools) read like rows that hold the heads exactly, whatever
    the padding lanes hold."""
    N, bs, hq, hkv, D, B, nb = 9, 8, 15, 5, 16, 3, 3
    k = jnp.asarray(rng.standard_normal((N, bs, hkv * D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((N, bs, hkv * D)), jnp.float32)
    junk = jnp.asarray(rng.standard_normal((N, bs, 128 - hkv * D)),
                       jnp.float32)
    q = jnp.asarray(rng.standard_normal((B, 1, hq, D)), jnp.float32)
    tables = jnp.asarray(rng.integers(1, N, (B, nb)), jnp.int32)
    lengths = jnp.asarray([1, 10, nb * bs], jnp.int32)
    want = ops.paged_decode_attention(q, k, v, block_tables=tables,
                                      lengths=lengths, impl="dense")
    got = ops.paged_decode_attention(
        q, jnp.concatenate([k, junk], -1), jnp.concatenate([v, junk], -1),
        block_tables=tables, lengths=lengths, kv_heads=hkv, impl=impl)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_paged_scatter_gather_one_layer_of_a_stack(rng):
    """A write at ``layer`` lands in that layer's blocks only (rows given
    as (Hkv, hd) land lane-merged); a gather at ``layer`` reads them
    back."""
    R, N, bs, H, D = 3, 9, 4, 2, 8
    stack = jnp.asarray(rng.standard_normal((R, N, bs, H * D)), jnp.float32)
    tables = jnp.asarray([[3, 5], [7, 1]], jnp.int32)
    starts = jnp.asarray([2, 5], jnp.int32)
    new = jnp.asarray(rng.standard_normal((2, 3, H, D)), jnp.float32)
    out = ops.paged_scatter(stack, new, tables, starts, layer=jnp.int32(1))
    want = np.asarray(stack).copy()
    want[1] = np.asarray(ops.paged_scatter(stack[1], new, tables, starts))
    np.testing.assert_array_equal(np.asarray(out), want)
    view = np.asarray(ops.paged_gather(out, tables, jnp.int32(1)))
    for b in range(2):
        s = int(starts[b])
        np.testing.assert_array_equal(
            view[b, s:s + 3], np.asarray(new[b]).reshape(3, H * D))


@pytest.mark.parametrize("fused", [False, True])
def test_donated_paged_engine_matches_functional(setup, rng, fused,
                                                 monkeypatch):
    """An engine that donates its cache to the step programs serves the
    same tokens as one that keeps the functional contract, through
    ragged prompts, a shared partial prefix block (COW) and refills — and
    the fused step's chunked joins when ``fused``."""
    cfg, params, _ = setup
    m = cfg.memcom.num_memory_tokens
    mat = _materialize(setup, rng)
    prompts = [rng.integers(4, cfg.vocab_size, n).astype(np.int32)
               for n in (5, 11, 3, 7)]
    outs = []
    for donate in (False, True):
        # the host CPU keeps the functional contract unless told otherwise
        monkeypatch.setattr(engine_mod, "_donates_cache", lambda *_: donate)
        eng = ServingEngine(cfg, params, slots=2, max_len=m + 24,
                            kv_layout="paged", block_size=16,
                            fused_step=fused, fused_chunk_tokens=4)
        assert eng.donate_cache is donate
        eng.add_prefix("task", mat)
        reqs = [Request(tokens=p, max_new=4, prefix="task") for p in prompts]
        out = eng.serve(reqs)
        outs.append([out[r.uid].tolist() for r in reqs])
    assert outs[0] == outs[1]


def test_scoring_prefill_keeps_the_donated_cache(setup, rng, monkeypatch):
    """``persist=False`` scoring reads the pool through a non-donating
    twin of the prefill: after it, the engine's cache is still readable
    and unchanged, and serving goes on from it as if it never ran."""
    cfg, params, _ = setup
    mat = _materialize(setup, rng)
    m = cfg.memcom.num_memory_tokens
    query = rng.integers(4, cfg.vocab_size, 6).astype(np.int32)
    labels = np.arange(4, 12, dtype=np.int32)
    prompt = rng.integers(4, cfg.vocab_size, 5).astype(np.int32)
    monkeypatch.setattr(engine_mod, "_donates_cache", lambda *_: True)
    eng = ServingEngine(cfg, params, slots=2, max_len=m + 24,
                        kv_layout="paged", block_size=8)
    monkeypatch.undo()
    assert eng.donate_cache
    eng.add_prefix("task", mat)
    eng.seat_prefix(0, "task")
    before = [np.asarray(x) for x in jax.tree.leaves(eng.cache)]
    pred = eng.score_labels(np.empty((0,), np.int32), query, labels)
    assert pred in labels
    after = jax.tree.leaves(eng.cache)
    assert not any(x.is_deleted() for x in after)
    for a, b in zip(before, after):
        np.testing.assert_array_equal(a, np.asarray(b))
    got = eng.serve([Request(tokens=prompt, max_new=4, prefix="task")])
    fresh = ServingEngine(cfg, params, slots=2, max_len=m + 24,
                          kv_layout="paged", block_size=8)
    fresh.add_prefix("task", mat)
    want = fresh.serve([Request(tokens=prompt, max_new=4, prefix="task")])
    np.testing.assert_array_equal(next(iter(got.values())),
                                  next(iter(want.values())))
