"""Fused decode-step + speculative decoding tests: greedy parity with the
classic engine (dense/paged x jnp/pallas-interpret), KV-rollback exactness
at paged block boundaries, fused token accounting under random chunk
schedules (hypothesis), the jit-compile bucket-ladder regression, and the
masked paged-scatter lane contract."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core import memcom
from repro.kernels import ops
from repro.models import transformer as tfm
from repro.serving import Request, Tracer, VirtualClock
from repro.serving.engine import ServingEngine

PROMPT_LENS = (5, 11, 8, 3, 7, 9)
MAX_NEW = 6


@pytest.fixture(scope="module")
def setup():
    cfg = get_smoke_config("smollm-135m")
    params = tfm.init_params(cfg, 0)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(4, cfg.vocab_size, n).astype(np.int32)
               for n in PROMPT_LENS]
    return cfg, params, prompts


@pytest.fixture(scope="module")
def ref(setup):
    """Greedy reference per prompt from the classic (non-fused) engine.
    Greedy decode is deterministic per request, so fused/spec/churn runs
    must reproduce these tokens exactly regardless of batching schedule."""
    cfg, params, prompts = setup
    eng = ServingEngine(cfg, params, slots=len(prompts), max_len=40)
    reqs = [Request(tokens=p, max_new=MAX_NEW) for p in prompts]
    out = eng.serve(reqs)
    return [list(map(int, out[r.uid])) for r in reqs]


def _serve(eng, prompts, idx, **req_kw):
    reqs = [Request(tokens=prompts[i], max_new=MAX_NEW,
                    **{k: (v[j] if isinstance(v, list) else v)
                       for k, v in req_kw.items()})
            for j, i in enumerate(idx)]
    out = eng.serve(reqs)
    return [list(map(int, out[r.uid])) for r in reqs]


# ---------------------------------------------------------------------------
# Greedy parity: fused step and speculative decoding are pure perf features
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("layout", ["dense", "paged"])
def test_fused_join_greedy_identity(setup, ref, layout):
    """Staggered arrivals into a 2-slot fused engine force the chunked
    join path; every request's greedy tokens match the classic engine."""
    cfg, params, prompts = setup
    eng = ServingEngine(cfg, params, slots=2, max_len=40, kv_layout=layout,
                        clock=VirtualClock(), fused_step=True,
                        fused_chunk_tokens=4)
    idx = [0, 1, 2, 3, 4]
    got = _serve(eng, prompts, idx,
                 arrival_s=[0.002 * j for j in range(len(idx))])
    assert got == [ref[i] for i in idx]
    es = eng.stats()["engine"]
    assert es["fused_prefill_chunks"] > 0  # joins actually streamed


@pytest.mark.parametrize("impl", ["jnp", "pallas"])
@pytest.mark.parametrize("layout", ["dense", "paged"])
def test_spec_greedy_identity(setup, ref, layout, impl):
    """Self-drafted speculative decoding is token-identical to the plain
    engine, and on plain prompts the self-draft accepts everything."""
    cfg, params, prompts = setup
    eng = ServingEngine(cfg, params, slots=2, max_len=40, kv_layout=layout,
                        impl=impl, fused_step=True, spec_draft="self",
                        spec_k=2)
    idx = [0, 1]
    assert _serve(eng, prompts, idx) == [ref[i] for i in idx]
    es = eng.stats()["engine"]
    assert es["draft_proposed"] > 0
    assert es["draft_accepted"] == es["draft_proposed"]  # drafter == target
    assert es["accept_rate"] == 1.0


def test_spec_cross_drafter_identity(setup, ref):
    """A drafter with different weights mostly misses — acceptance drops,
    rollback engages — but greedy output never changes."""
    cfg, params, prompts = setup
    drafter = (cfg, tfm.init_params(cfg, 123))
    eng = ServingEngine(cfg, params, slots=2, max_len=40, fused_step=True,
                        spec_draft=drafter, spec_k=2)
    idx = [0, 1, 2]
    assert _serve(eng, prompts, idx) == [ref[i] for i in idx]
    es = eng.stats()["engine"]
    assert es["draft_proposed"] > 0
    assert es["draft_accepted"] < es["draft_proposed"]  # rollbacks happened


def test_spec_sampled_runs_and_conserves(setup):
    """Sampled acceptance (temperature > 0) completes every request with
    exactly max_new tokens and keeps the draft counters consistent."""
    cfg, params, prompts = setup
    eng = ServingEngine(cfg, params, slots=2, max_len=40, kv_layout="paged",
                        fused_step=True, spec_draft="self", spec_k=2)
    # sharp temperature: the random smoke weights are near-uniform, so a
    # soft temperature would put ~1/vocab mass on the drafted argmax token
    # and (correctly) accept nothing; at 0.05 the sampled rule fires
    got = _serve(eng, prompts, [0, 1, 2], temperature=0.05)
    assert all(len(t) == MAX_NEW for t in got)
    es = eng.stats()["engine"]
    assert 0 < es["draft_accepted"] <= es["draft_proposed"]


# ---------------------------------------------------------------------------
# KV rollback at paged block boundaries
# ---------------------------------------------------------------------------


def test_paged_block_boundary_rollback(setup, ref):
    """block_size=4 with spec_k=3: accepted runs repeatedly straddle block
    boundaries and rejected drafts leave garbage in the next block.  A
    low-acceptance drafter forces rollbacks right at the boundary; tokens
    must still be bit-identical to the classic engine."""
    cfg, params, prompts = setup
    drafter = (cfg, tfm.init_params(cfg, 7))
    eng = ServingEngine(cfg, params, slots=2, max_len=40, kv_layout="paged",
                        block_size=4, fused_step=True, spec_draft=drafter,
                        spec_k=3)
    idx = [1, 2, 0, 4]
    assert _serve(eng, prompts, idx) == [ref[i] for i in idx]

    # and the all-accept extreme: lengths jump k+1 per step across blocks
    eng = ServingEngine(cfg, params, slots=2, max_len=40, kv_layout="paged",
                        block_size=4, fused_step=True, spec_draft="self",
                        spec_k=3)
    assert _serve(eng, prompts, idx) == [ref[i] for i in idx]
    assert eng.stats()["engine"]["accept_rate"] == 1.0


# ---------------------------------------------------------------------------
# Token accounting under random chunk schedules (hypothesis)
# ---------------------------------------------------------------------------

def _check_token_conservation(setup, ref, idx, chunk, stagger, spec_k):
    """Whatever chunk schedule the fused step runs — random prompt mix,
    chunk width, arrival stagger, with or without speculation — tokens are
    conserved: every request emits exactly max_new, outputs match the
    greedy reference, every joined prompt token is streamed exactly once,
    and the decode counter equals total output minus the first tokens."""
    cfg, params, prompts = setup
    kw = {} if spec_k == 0 else {"spec_draft": "self", "spec_k": spec_k}
    tracer = Tracer(capacity=None)
    eng = ServingEngine(cfg, params, slots=2, max_len=40, kv_layout="paged",
                        clock=VirtualClock(), fused_step=True,
                        fused_chunk_tokens=chunk, tracer=tracer, **kw)
    got = _serve(eng, prompts, idx,
                 arrival_s=[stagger * j for j in range(len(idx))])
    assert got == [ref[i] for i in idx]
    es = eng.stats()["engine"]
    assert es["tokens_generated"] == len(idx) * MAX_NEW - len(idx)
    joined = sum(e["args"]["prompt_tokens"] for e in tracer.events()
                 if e["name"] == "admission" and e["args"].get("fused_join"))
    assert es["fused_prefill_tokens"] == joined


try:
    from hypothesis import HealthCheck, given, settings, strategies as st

    SHORT = settings(max_examples=6, deadline=None,
                     suppress_health_check=[HealthCheck.too_slow])

    @SHORT
    @given(idx=st.lists(st.integers(0, len(PROMPT_LENS) - 1),
                        min_size=3, max_size=5),
           chunk=st.sampled_from([2, 4]),
           stagger=st.sampled_from([0.0005, 0.002]),
           spec_k=st.sampled_from([0, 2]))
    def test_fused_token_conservation(setup, ref, idx, chunk, stagger,
                                      spec_k):
        _check_token_conservation(setup, ref, idx, chunk, stagger, spec_k)

except ImportError:
    # hypothesis is optional: fall back to seeded random schedules so the
    # property is still exercised
    _sched_rng = np.random.default_rng(42)
    _CASES = [(list(_sched_rng.integers(0, len(PROMPT_LENS), size=n)),
               int(_sched_rng.choice([2, 4])),
               float(_sched_rng.choice([0.0005, 0.002])),
               int(_sched_rng.choice([0, 2])))
              for n in (3, 4, 5, 4, 3, 5)]

    @pytest.mark.parametrize("idx,chunk,stagger,spec_k", _CASES)
    def test_fused_token_conservation(setup, ref, idx, chunk, stagger,
                                      spec_k):
        _check_token_conservation(setup, ref, idx, chunk, stagger, spec_k)


# ---------------------------------------------------------------------------
# jit-compile accounting and the bucket-ladder regression
# ---------------------------------------------------------------------------


def test_jit_compiles_bucket_ladder(setup):
    """stats() reports per-family compile counts, and the pow2 bucket
    ladder caps them: six distinct prompt lengths through the fused+spec
    engine compile only a handful of programs, and replaying the same
    workload compiles nothing new."""
    cfg, params, prompts = setup
    eng = ServingEngine(cfg, params, slots=2, max_len=48,
                        clock=VirtualClock(), fused_step=True,
                        fused_chunk_tokens=4, spec_draft="self", spec_k=2)
    idx = list(range(len(PROMPT_LENS)))
    arrivals = [0.002 * j for j in range(len(idx))]
    _serve(eng, prompts, idx, arrival_s=arrivals)
    jc = eng.stats()["engine"]["jit_compiles"]
    assert jc and all(isinstance(v, int) for v in jc.values())
    # spec lanes dominate the width bucket, so the chunk ladder collapses
    # onto very few fused geometries
    assert jc.get("fused", 0) <= 2
    assert jc.get("draft", 0) <= 1
    assert sum(jc.values()) <= 12

    _serve(eng, prompts, idx, arrival_s=arrivals)  # replay: all warm
    assert eng.stats()["engine"]["jit_compiles"] == jc


# ---------------------------------------------------------------------------
# Masked paged-scatter lane contract
# ---------------------------------------------------------------------------


def test_paged_scatter_valid_routes_to_trash(rng):
    """Lanes >= valid[b] are geometry padding: they land in physical block
    0 (the allocator's trash block) and never touch an allocated block."""
    B, S, bs, nb, H, D = 2, 4, 4, 3, 2, 4
    pool = np.asarray(rng.standard_normal((B * nb + 1, bs, H, D)),
                      np.float32)
    tables = (np.arange(B * nb).reshape(B, nb) + 1).astype(np.int32)
    new = np.asarray(rng.standard_normal((B, S, H, D)), np.float32)
    starts = jnp.asarray([2, 5], jnp.int32)
    valid = jnp.asarray([3, 0], jnp.int32)

    out = np.asarray(ops.paged_scatter(
        jnp.asarray(pool), jnp.asarray(new), jnp.asarray(tables), starts,
        valid=valid))
    # slot 0: lanes 0..2 land at logical positions 2..4 (straddling blocks)
    for s in range(3):
        pos = 2 + s
        np.testing.assert_array_equal(out[tables[0, pos // bs], pos % bs],
                                      new[0, s])
    # slot 0 lane 3 and all of slot 1 are invalid: every allocated block
    # equals the original pool except the three written rows
    untouched = out.copy()
    for s in range(3):
        pos = 2 + s
        untouched[tables[0, pos // bs], pos % bs] = \
            pool[tables[0, pos // bs], pos % bs]
    np.testing.assert_array_equal(untouched[1:], pool[1:])


def test_paged_scatter_valid_clamps_table_column(rng):
    """Regression: an invalid lane whose position runs past the table
    width must not let take_along_axis's clamp route it into the *last*
    column's real block."""
    B, S, bs, nb, H, D = 1, 4, 2, 2, 1, 2
    pool = np.asarray(rng.standard_normal((nb + 1, bs, H, D)), np.float32)
    tables = jnp.asarray([[1, 2]], jnp.int32)  # table width 2 == max_len 4
    new = np.asarray(rng.standard_normal((B, S, H, D)), np.float32)
    # start at the last valid position: lanes 1..3 run to positions 4..6,
    # i.e. columns 2..3 — past the table
    out = np.asarray(ops.paged_scatter(
        jnp.asarray(pool), jnp.asarray(new), tables,
        jnp.asarray([3], jnp.int32), valid=jnp.asarray([1], jnp.int32)))
    np.testing.assert_array_equal(out[1], pool[1])        # block 1 intact
    np.testing.assert_array_equal(out[2, 0], pool[2, 0])  # pos 2 intact
    np.testing.assert_array_equal(out[2, 1], new[0, 0])   # the one write
