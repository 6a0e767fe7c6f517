"""Online prefix compiler tests: chunked-compress parity (jnp +
pallas-interpret), online == offline serving (token-exact, attn/MLA/
hybrid, dense + paged), single-flight dedup, decode/compile
interleaving, and mid-compile LRU eviction pressure."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core import memcom
from repro.models import transformer as tfm
from repro.serving import (
    PrefixCompiler,
    Request,
    ServingEngine,
    Tracer,
    materialize_prefix,
)


@pytest.fixture(scope="module")
def setup():
    cfg = get_smoke_config("smollm-135m")
    params = tfm.init_params(cfg, 0)
    mc = memcom.init_memcom(cfg, params, 1)
    return cfg, params, mc


def _assert_tree_close(a, b, atol):
    for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_allclose(np.asarray(la), np.asarray(lb),
                                   atol=atol, rtol=atol)


# ---------------------------------------------------------------------------
# Chunked compress parity
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("impl", ["jnp", "pallas"])
def test_chunked_compress_parity(setup, rng, impl):
    """compress in 16-token slices (Source-LLM cache carried across
    chunks) == one-shot compress, on the streaming-jnp and
    pallas-interpret backends."""
    cfg, params, mc = setup
    src = jnp.asarray(rng.integers(4, cfg.vocab_size, (2, 48)), jnp.int32)
    one, _ = memcom.compress(mc, cfg, src, impl=impl)
    chk, _ = memcom.compress_chunked(mc, cfg, src, chunk_size=16, impl=impl)
    _assert_tree_close(one, chk, 1e-4)


@pytest.mark.parametrize("arch", ["deepseek-v2-236b", "jamba-1.5-large-398b"])
def test_chunked_compress_parity_families(arch, rng):
    """MLA latent caches and hybrid SSM state survive chunk boundaries:
    the recurrence/latents carried across chunks land on the one-shot
    result — with a ragged final chunk (40 = 16 + 16 + 8).

    The MoE layers of the MLA config are swapped for dense MLPs here:
    top-k expert routing amplifies 1e-7 attention-order noise into a
    discontinuous 3e-3 jump whenever a router score sits at a tie, which
    measures the router's chaos, not chunking (the end-to-end greedy
    serving test below keeps the stock MoE config).
    """
    import dataclasses

    cfg = get_smoke_config(arch)
    layout = dataclasses.replace(
        cfg.layout,
        prefix=tuple(dataclasses.replace(d, mlp="dense")
                     if d.mlp == "moe" else d for d in cfg.layout.prefix),
        period=tuple(dataclasses.replace(d, mlp="dense")
                     if d.mlp == "moe" else d for d in cfg.layout.period))
    cfg = cfg.replace(layout=layout)
    params = tfm.init_params(cfg, 0)
    mc = memcom.init_memcom(cfg, params, 1)
    src = jnp.asarray(rng.integers(4, cfg.vocab_size, (1, 40)), jnp.int32)
    one, _ = memcom.compress(mc, cfg, src)
    chk, _ = memcom.compress_chunked(mc, cfg, src, chunk_size=16)
    _assert_tree_close(one, chk, 1e-4)


# ---------------------------------------------------------------------------
# Online serving == offline serving
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch", ["smollm-135m", "deepseek-v2-236b",
                                  "jamba-1.5-large-398b"])
@pytest.mark.parametrize("layout", ["dense", "paged"])
def test_online_compile_matches_offline(arch, layout, rng):
    """A raw_shots request (compile on the serving path, chunked) emits
    exactly the tokens of the offline compress → materialize →
    add_prefix path, per family and KV layout."""
    cfg = get_smoke_config(arch)
    params = tfm.init_params(cfg, 0)
    mc = memcom.init_memcom(cfg, params, 1)
    m = cfg.memcom.num_memory_tokens
    shots = rng.integers(4, cfg.vocab_size, 40).astype(np.int32)
    prompt = rng.integers(4, cfg.vocab_size, 5).astype(np.int32)

    offline = ServingEngine(cfg, params, slots=1, max_len=m + 24,
                            kv_layout=layout)
    kv = materialize_prefix(params, cfg,
                            memcom.compress(mc, cfg, jnp.asarray(shots[None]))[0])
    offline.add_prefix("task", kv)
    want = offline.serve([Request(tokens=prompt, max_new=5, prefix="task")])

    online = ServingEngine(cfg, params, slots=1, max_len=m + 24,
                           kv_layout=layout, compressor=mc,
                           compile_token_budget=16)
    req = Request(tokens=prompt, max_new=5, prefix="task", raw_shots=shots)
    got = online.serve([req])
    np.testing.assert_array_equal(got[req.uid], next(iter(want.values())))
    assert online.stats()["compiler"]["compiled"] == 1


# ---------------------------------------------------------------------------
# Single-flight dedup
# ---------------------------------------------------------------------------


def test_single_flight_dedup(setup, rng):
    """Two requests waiting on one (content-addressed) task trigger one
    compilation and one store entry; both outputs match the offline
    reference."""
    cfg, params, mc = setup
    m = cfg.memcom.num_memory_tokens
    shots = rng.integers(4, cfg.vocab_size, 40).astype(np.int32)
    prompt = rng.integers(4, cfg.vocab_size, 6).astype(np.int32)

    eng = ServingEngine(cfg, params, slots=2, max_len=m + 24,
                        compressor=mc, compile_token_budget=16)
    r1 = Request(tokens=prompt, max_new=4, raw_shots=shots)
    r2 = Request(tokens=prompt, max_new=4, raw_shots=shots.copy())
    assert r1.prefix == r2.prefix  # same bytes -> same auto name
    out = eng.serve([r1, r2])

    stats = eng.stats()
    assert stats["compiler"]["jobs"] == 1
    assert stats["compiler"]["deduped"] == 1
    assert stats["prefix_store"]["puts"] == 1

    kv = materialize_prefix(params, cfg,
                            memcom.compress(mc, cfg, jnp.asarray(shots[None]))[0])
    solo = ServingEngine(cfg, params, slots=1, max_len=m + 24)
    solo.add_prefix("ref", kv)
    want = solo.serve([Request(tokens=prompt, max_new=4, prefix="ref")])
    want = next(iter(want.values()))
    np.testing.assert_array_equal(out[r1.uid], want)
    np.testing.assert_array_equal(out[r2.uid], want)


def test_compiler_unit_budget_and_states(setup):
    """PrefixCompiler alone: budget-bounded chunking, job state
    transitions, single-flight joins, install bookkeeping."""
    cfg, params, mc = setup
    comp = PrefixCompiler(mc, cfg, params)
    toks = np.arange(4, 44, dtype=np.int32)
    job = comp.submit("t", toks)
    assert job.status == "queued" and comp.pending()
    assert comp.submit("t", toks) is job  # joined, not restarted
    assert comp.stats["deduped"] == 1

    assert comp.step(16) == []  # 16 of 40 tokens
    assert job.status == "compiling" and job.consumed == 16
    assert comp.step(None) == ["t"]  # run to completion
    assert job.status == "compiled" and job.remaining == 0
    assert comp.ready() == ["t"] and job.materialized is not None
    comp.mark_installed("t")
    assert job.status == "installed" and not comp.pending()
    # resubmit after install = recompile (the store evicted it)
    assert comp.submit("t", toks) is not job


# ---------------------------------------------------------------------------
# Decode keeps stepping during a compile (the tentpole's acceptance)
# ---------------------------------------------------------------------------


def test_decode_continues_during_compile(setup, rng):
    """With compile_token_budget set, a seated slot keeps emitting tokens
    while a cold task compiles: decode steps land *between* compile
    chunks, and the warm request's output is byte-identical to a serve
    with no compile in flight."""
    cfg, params, mc = setup
    m = cfg.memcom.num_memory_tokens
    shots_a = rng.integers(4, cfg.vocab_size, 40).astype(np.int32)
    shots_b = rng.integers(4, cfg.vocab_size, 48).astype(np.int32)
    prompt = rng.integers(4, cfg.vocab_size, 5).astype(np.int32)
    kv_a = materialize_prefix(
        params, cfg, memcom.compress(mc, cfg, jnp.asarray(shots_a[None]))[0])

    tracer = Tracer(capacity=None)
    eng = ServingEngine(cfg, params, slots=2, max_len=m + 40,
                        compressor=mc, compile_token_budget=8, tracer=tracer)
    eng.add_prefix("A", kv_a)
    warm = Request(tokens=prompt, max_new=20, prefix="A")
    cold = Request(tokens=prompt, max_new=3, raw_shots=shots_b)
    out = eng.serve([warm, cold])

    names = [e["name"] for e in tracer.events()]
    compile_idx = [i for i, n in enumerate(names) if n == "compile_chunk"]
    decode_between = [i for i, n in enumerate(names) if n == "decode_step"
                      and compile_idx[0] < i < compile_idx[-1]]
    assert len(compile_idx) >= 3, names  # 48 tokens / 8-token budget
    assert decode_between, names  # decode interleaved with compilation
    assert eng.stats()["engine"]["decode_steps_during_compile"] >= 3

    solo = ServingEngine(cfg, params, slots=1, max_len=m + 40)
    solo.add_prefix("A", kv_a)
    want = solo.serve([Request(tokens=prompt, max_new=20, prefix="A")])
    np.testing.assert_array_equal(out[warm.uid], next(iter(want.values())))


# ---------------------------------------------------------------------------
# Mid-compile LRU eviction pressure (paged)
# ---------------------------------------------------------------------------


def test_mid_compile_lru_eviction_pressure(setup, rng):
    """prefix_capacity=1: task B compiles while task A (the sole resident
    prefix) is seated and decoding.  B's install is deferred — evicting A
    under a live slot would raise PrefixSeatedError — until A's request
    finishes; then A is evicted, B seats, and B's waiter completes with
    the exact offline output."""
    cfg, params, mc = setup
    m = cfg.memcom.num_memory_tokens
    shots_a = rng.integers(4, cfg.vocab_size, 40).astype(np.int32)
    shots_b = rng.integers(4, cfg.vocab_size, 40).astype(np.int32)
    prompt = rng.integers(4, cfg.vocab_size, 5).astype(np.int32)
    kv_a = materialize_prefix(
        params, cfg, memcom.compress(mc, cfg, jnp.asarray(shots_a[None]))[0])

    eng = ServingEngine(cfg, params, slots=1, max_len=m + 24,
                        kv_layout="paged", prefix_capacity=1,
                        compressor=mc, compile_token_budget=8)
    eng.add_prefix("A", kv_a)
    ra = Request(tokens=prompt, max_new=10, prefix="A")
    rb = Request(tokens=prompt, max_new=4, prefix="B", raw_shots=shots_b)
    out = eng.serve([ra, rb])

    stats = eng.stats()
    assert stats["prefix_store"]["evictions"] >= 1  # A made way for B
    assert "B" in eng.store and "A" not in eng.store
    # B compiled while A was decoding (not after)
    assert stats["engine"]["decode_steps_during_compile"] >= 2

    kv_b = materialize_prefix(
        params, cfg, memcom.compress(mc, cfg, jnp.asarray(shots_b[None]))[0])
    solo = ServingEngine(cfg, params, slots=1, max_len=m + 24,
                         kv_layout="paged")
    solo.add_prefix("B", kv_b)
    want = solo.serve([Request(tokens=prompt, max_new=4, prefix="B")])
    np.testing.assert_array_equal(out[rb.uid], next(iter(want.values())))


# ---------------------------------------------------------------------------
# Counters
# ---------------------------------------------------------------------------


def test_pin_does_not_outlive_install(setup, rng):
    """The LRU pin protecting a waiting request's prefix is scoped to the
    install itself: after serve() returns, add_prefix can evict the (now
    unseated, unreferenced) prefix instead of raising PrefixSeatedError."""
    cfg, params, mc = setup
    m = cfg.memcom.num_memory_tokens
    shots = rng.integers(4, cfg.vocab_size, 40).astype(np.int32)
    prompt = rng.integers(4, cfg.vocab_size, 5).astype(np.int32)
    eng = ServingEngine(cfg, params, slots=1, max_len=m + 24,
                        kv_layout="paged", prefix_capacity=1,
                        compressor=mc)
    eng.serve([Request(tokens=prompt, max_new=2, raw_shots=shots)])
    eng.serve([Request(tokens=prompt, max_new=2)])  # unseats the slot
    kv = materialize_prefix(params, cfg,
                            memcom.compress(mc, cfg, jnp.asarray(shots[None]))[0])
    eng.add_prefix("C", kv)  # must LRU-evict, not raise
    assert "C" in eng.store and len(eng.store) == 1


def test_raw_shots_without_compressor_raises(setup, rng):
    cfg, params, _ = setup
    eng = ServingEngine(cfg, params, slots=1, max_len=32)
    req = Request(tokens=[5], max_new=1,
                  raw_shots=rng.integers(4, cfg.vocab_size, 8))
    with pytest.raises(ValueError, match="compressor"):
        eng.serve([req])


def test_store_counters_via_stats(setup, rng):
    """hit/miss/put counters flow from the store through
    ServingEngine.stats(); a resident prefix counts a hit, a raw-shots
    cold task a miss."""
    cfg, params, mc = setup
    m = cfg.memcom.num_memory_tokens
    shots = rng.integers(4, cfg.vocab_size, 40).astype(np.int32)
    prompt = rng.integers(4, cfg.vocab_size, 5).astype(np.int32)
    eng = ServingEngine(cfg, params, slots=1, max_len=m + 24,
                        compressor=mc)
    cold = Request(tokens=prompt, max_new=2, raw_shots=shots)
    eng.serve([cold])
    warm = Request(tokens=prompt, max_new=2, prefix=cold.prefix)
    eng.serve([warm])
    s = eng.stats()["prefix_store"]
    assert s["misses"] == 1 and s["hits"] == 1 and s["puts"] == 1
    e = eng.stats()["engine"]
    assert e["prefills"] == 2 and e["tokens_generated"] >= 2
