"""Tiered prefix cache tests: bit-exact HBM→host→disk→HBM round trips
(dense + paged), token-identical serving from every tier (jnp +
pallas-interpret), park/wake FIFO on cold-prefix misses, decode/promote
interleaving, the seated-eviction guard, disk-shard restart recovery,
and codec round trips for the shared compress/decompress helpers."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.store import compress_bytes, decompress_bytes
from repro.configs import get_smoke_config
from repro.core import memcom
from repro.models import transformer as tfm
from repro.serving import (
    PrefixSeatedError,
    Request,
    ServingEngine,
    Tracer,
    materialize_prefix,
)
from repro.serving.prefix_store import take_prefix_row
from repro.utils.pytree import tree_flatten_with_names


@pytest.fixture(scope="module")
def setup():
    cfg = get_smoke_config("smollm-135m")
    params = tfm.init_params(cfg, 0)
    mc = memcom.init_memcom(cfg, params, 1)
    return cfg, params, mc


def _compress_kv(cfg, params, mc, shots):
    prefix, _ = memcom.compress(mc, cfg, jnp.asarray(shots[None]))
    return materialize_prefix(params, cfg, prefix)


def _assert_rows_bit_exact(a, b):
    fa, fb = tree_flatten_with_names(a), tree_flatten_with_names(b)
    assert [n for n, _ in fa] == [n for n, _ in fb]
    for (name, la), (_, lb) in zip(fa, fb):
        la, lb = np.asarray(la), np.asarray(lb)
        assert la.dtype == lb.dtype and la.shape == lb.shape, name
        np.testing.assert_array_equal(la, lb, err_msg=name)


# ---------------------------------------------------------------------------
# Codec round trips (the shared checkpoint/disk-tier helpers)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("codec", ["zstd", "zlib", "raw"])
def test_codec_round_trip(codec):
    if codec == "zstd":
        pytest.importorskip("zstandard")
    payload = np.random.default_rng(0).bytes(4096) + b"\x00" * 4096
    tag, blob = compress_bytes(payload, codec)
    assert tag == codec
    assert decompress_bytes(blob, tag) == payload
    if codec != "raw":
        assert len(blob) < len(payload)  # the zero run must compress


def test_codec_default_and_unknown():
    tag, blob = compress_bytes(b"x" * 100)  # default codec
    assert tag in ("zstd", "zlib")
    assert decompress_bytes(blob, tag) == b"x" * 100
    with pytest.raises(ValueError, match="unknown checkpoint codec"):
        compress_bytes(b"", "lz4")
    with pytest.raises(ValueError, match="unknown checkpoint codec"):
        decompress_bytes(b"", "lz4")


# ---------------------------------------------------------------------------
# Bit-exact tier round trips
# ---------------------------------------------------------------------------


def test_dense_round_trip_bit_exact(setup, rng, tmp_path):
    """HBM→host→disk→HBM leaves a dense prefix row byte-identical."""
    cfg, params, mc = setup
    m = cfg.memcom.num_memory_tokens
    kv = _compress_kv(cfg, params, mc,
                      rng.integers(4, cfg.vocab_size, 40).astype(np.int32))
    ref = jax.tree.map(np.asarray, take_prefix_row(kv, 0))

    eng = ServingEngine(cfg, params, slots=1, max_len=m + 24,
                        host_capacity=4, disk_dir=str(tmp_path))
    eng.add_prefix("t", kv)
    eng.store.demote("t")
    assert eng.store.tier_of("t") == "host"
    _assert_rows_bit_exact(ref, eng.store._host["t"])
    eng.store.spill("t")
    assert eng.store.tier_of("t") == "disk"
    assert "t" not in eng.store  # HBM residency only

    eng.store.submit_promotion("t")
    eng.store.promote_step(None)
    promoted = eng.store.promoted_row("t")
    _assert_rows_bit_exact(ref, promoted)
    eng.store.put_row("t", promoted)
    eng.store.mark_promoted("t")
    _assert_rows_bit_exact(ref, eng.store.get("t"))
    ts = eng.stats()["prefix_tiers"]
    assert ts["demotes"] == 1 and ts["spills"] == 1 and ts["disk_loads"] == 1


def test_paged_round_trip_bit_exact(setup, rng, tmp_path):
    """The paged gather (pool blocks → host row) and re-scatter land on
    the dense reference row bit for bit, through the disk tier."""
    cfg, params, mc = setup
    m = cfg.memcom.num_memory_tokens
    kv = _compress_kv(cfg, params, mc,
                      rng.integers(4, cfg.vocab_size, 40).astype(np.int32))
    ref = jax.tree.map(np.asarray, take_prefix_row(kv, 0))

    eng = ServingEngine(cfg, params, slots=1, max_len=m + 24,
                        kv_layout="paged", host_capacity=4,
                        disk_dir=str(tmp_path))
    eng.add_prefix("t", kv)
    eng.store.demote("t")  # pool-block gather → host row
    _assert_rows_bit_exact(ref, eng.store._host["t"])
    eng.store.spill("t")
    assert eng.store.tier_of("t") == "disk"

    eng.store.submit_promotion("t")
    eng.store.promote_step(None)
    _assert_rows_bit_exact(ref, eng.store.promoted_row("t"))
    eng.cache = eng.store.put_row("t", eng.store.promoted_row("t"), eng.cache)
    eng.store.mark_promoted("t")
    # gather it back out of the (new) pool blocks: still bit-exact
    eng.store.demote("t")
    _assert_rows_bit_exact(ref, eng.store._host["t"])


@pytest.mark.parametrize("arch", ["deepseek-v2-236b", "jamba-1.5-large-398b"])
@pytest.mark.parametrize("layout", ["dense", "paged"])
def test_family_round_trip_bit_exact(arch, layout, rng, tmp_path):
    """MLA latents (ckv/kr, prefix+period sections) and hybrid SSM state
    survive the full demote→spill→promote cycle bit-exactly and serve
    token-identically — the per-family leaf keys all take the same path
    the GQA k/v leaves do."""
    cfg = get_smoke_config(arch)
    params = tfm.init_params(cfg, 0)
    mc = memcom.init_memcom(cfg, params, 1)
    m = cfg.memcom.num_memory_tokens
    shots = rng.integers(4, cfg.vocab_size, 40).astype(np.int32)
    prompt = rng.integers(4, cfg.vocab_size, 5).astype(np.int32)
    kv = _compress_kv(cfg, params, mc, shots)
    ref = jax.tree.map(np.asarray, take_prefix_row(kv, 0))

    eng = ServingEngine(cfg, params, slots=2, max_len=m + 24,
                        kv_layout=layout, host_capacity=4,
                        disk_dir=str(tmp_path), promote_layer_budget=1)
    eng.add_prefix("t", kv)
    want = next(iter(eng.serve(
        [Request(tokens=prompt, max_new=5, prefix="t")]).values()))
    eng.serve([Request(tokens=prompt, max_new=1)])  # unseat
    eng.store.demote("t")
    _assert_rows_bit_exact(ref, eng.store._host["t"])
    eng.store.spill("t")
    out = eng.serve([Request(tokens=prompt, max_new=5, prefix="t")])
    np.testing.assert_array_equal(next(iter(out.values())), want)


# ---------------------------------------------------------------------------
# Token-identical serving from every tier
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("layout", ["dense", "paged"])
@pytest.mark.parametrize("impl", ["jnp", "pallas"])
def test_serve_token_identical_across_tiers(setup, rng, tmp_path,
                                            layout, impl):
    """The same greedy request emits identical tokens whether its prefix
    is warm in HBM, promoted from host, loaded from disk, or compiled
    fresh from raw shots — dense and paged, jnp and pallas-interpret."""
    cfg, params, mc = setup
    m = cfg.memcom.num_memory_tokens
    shots = rng.integers(4, cfg.vocab_size, 40).astype(np.int32)
    prompt = rng.integers(4, cfg.vocab_size, 5).astype(np.int32)
    kv = _compress_kv(cfg, params, mc, shots)

    eng = ServingEngine(cfg, params, slots=2, max_len=m + 24,
                        kv_layout=layout, impl=impl, compressor=mc,
                        compile_token_budget=16, host_capacity=4,
                        disk_dir=str(tmp_path / layout),
                        promote_layer_budget=1)
    eng.add_prefix("t", kv)

    def one(prefix="t", raw=None):
        out = eng.serve([Request(tokens=prompt, max_new=5, prefix=prefix,
                                 raw_shots=raw)])
        return next(iter(out.values()))

    warm = one()
    eng.serve([Request(tokens=prompt, max_new=1)])  # unseat slot 0
    eng.store.demote("t")
    assert eng.store.tier_of("t") == "host"
    host_hit = one()
    eng.serve([Request(tokens=prompt, max_new=1)])
    eng.store.demote("t")
    eng.store.spill("t")
    assert eng.store.tier_of("t") == "disk"
    disk_hit = one()
    fresh = one(prefix=None, raw=shots)  # content-addressed fresh compile

    np.testing.assert_array_equal(host_hit, warm)
    np.testing.assert_array_equal(disk_hit, warm)
    np.testing.assert_array_equal(fresh, warm)
    ts = eng.stats()["prefix_tiers"]
    assert ts["host_promotes"] == 2 and ts["disk_loads"] == 1
    assert eng.stats()["compiler"]["compiled"] == 1  # fresh path only


def test_raw_shots_prefer_promotion_over_recompile(setup, rng):
    """A request that carries raw_shots for a task sitting in the host
    tier promotes instead of recompiling — the whole point of demoting
    rather than destroying."""
    cfg, params, mc = setup
    m = cfg.memcom.num_memory_tokens
    shots = rng.integers(4, cfg.vocab_size, 40).astype(np.int32)
    prompt = rng.integers(4, cfg.vocab_size, 5).astype(np.int32)

    eng = ServingEngine(cfg, params, slots=1, max_len=m + 24,
                        compressor=mc, host_capacity=4)
    cold = Request(tokens=prompt, max_new=3, raw_shots=shots)
    want = eng.serve([cold])[cold.uid]
    eng.serve([Request(tokens=prompt, max_new=1)])  # unseat
    eng.store.demote(cold.prefix)

    again = Request(tokens=prompt, max_new=3, raw_shots=shots.copy())
    got = eng.serve([again])[again.uid]
    np.testing.assert_array_equal(got, want)
    assert eng.stats()["compiler"]["jobs"] == 1  # no second compile
    assert eng.stats()["prefix_tiers"]["host_promotes"] == 1


# ---------------------------------------------------------------------------
# Park/wake FIFO order on a cold-prefix miss
# ---------------------------------------------------------------------------


def test_park_wake_fifo_on_cold_miss(setup, rng):
    """A request parked on a promoting prefix wakes at its original
    arrival position: it precedes later arrivals but never overtakes an
    earlier one, and warm traffic is admitted while it waits."""
    cfg, params, mc = setup
    m = cfg.memcom.num_memory_tokens
    prompt = rng.integers(4, cfg.vocab_size, 5).astype(np.int32)
    kv_a = _compress_kv(cfg, params, mc,
                        rng.integers(4, cfg.vocab_size, 40).astype(np.int32))
    kv_b = _compress_kv(cfg, params, mc,
                        rng.integers(4, cfg.vocab_size, 40).astype(np.int32))

    tracer = Tracer(capacity=None)
    eng = ServingEngine(cfg, params, slots=1, max_len=m + 24,
                        host_capacity=4, promote_layer_budget=1,
                        tracer=tracer)
    eng.add_prefix("A", kv_a)
    eng.add_prefix("B", kv_b)
    eng.store.demote("B")

    r1 = Request(tokens=prompt, max_new=2, prefix="B")   # parks
    r2 = Request(tokens=prompt, max_new=2, prefix="A")   # warm, runs first
    r3 = Request(tokens=prompt, max_new=2, prefix="B")   # parks (joined)
    eng.serve([r1, r2, r3])

    rid = {r.uid: eng._rids[r.uid] for r in (r1, r2, r3)}
    events = tracer.events()
    parked = [e["id"] for e in events
              if e["ph"] == "b" and e["name"] == "waiting_on_prefix"]
    assert parked == [str(rid[r1.uid]), str(rid[r3.uid])]
    admits = [e["args"]["rid"] for e in events if e["name"] == "admission"]
    # one slot: strict admission order — warm r2 immediately, then the
    # woken cold requests in arrival order
    assert admits == [rid[r2.uid], rid[r1.uid], rid[r3.uid]]
    assert eng.stats()["prefix_tiers"]["host_promotes"] == 1  # single-flight


# ---------------------------------------------------------------------------
# Decode keeps stepping during a budgeted promotion
# ---------------------------------------------------------------------------


def test_decode_continues_during_promotion(setup, rng):
    """With promote_layer_budget set, a seated slot keeps emitting tokens
    while a cold prefix copies up: decode steps land *between* promote
    chunks, and the warm request's output is byte-identical to a serve
    with no promotion in flight."""
    cfg, params, mc = setup
    m = cfg.memcom.num_memory_tokens
    prompt = rng.integers(4, cfg.vocab_size, 5).astype(np.int32)
    kv_a = _compress_kv(cfg, params, mc,
                        rng.integers(4, cfg.vocab_size, 40).astype(np.int32))
    kv_b = _compress_kv(cfg, params, mc,
                        rng.integers(4, cfg.vocab_size, 48).astype(np.int32))

    tracer = Tracer(capacity=None)
    eng = ServingEngine(cfg, params, slots=2, max_len=m + 40,
                        host_capacity=4, promote_layer_budget=1,
                        tracer=tracer)
    eng.add_prefix("A", kv_a)
    eng.add_prefix("B", kv_b)
    eng.store.demote("B")
    warm = Request(tokens=prompt, max_new=12, prefix="A")
    cold = Request(tokens=prompt, max_new=3, prefix="B")
    out = eng.serve([warm, cold])

    names = [e["name"] for e in tracer.events()]
    promote_idx = [i for i, n in enumerate(names) if n == "promote_chunk"]
    decode_between = [i for i, n in enumerate(names) if n == "decode_step"
                      and promote_idx[0] < i < promote_idx[-1]]
    assert len(promote_idx) >= 2, names  # budget=1 forces chunking
    assert decode_between, names
    assert eng.stats()["engine"]["decode_steps_during_promote"] >= 2

    solo = ServingEngine(cfg, params, slots=1, max_len=m + 40)
    solo.add_prefix("A", kv_a)
    want = solo.serve([Request(tokens=prompt, max_new=12, prefix="A")])
    np.testing.assert_array_equal(out[warm.uid], next(iter(want.values())))


# ---------------------------------------------------------------------------
# Seated guard, LRU demotion, spill pressure, restart recovery
# ---------------------------------------------------------------------------


def test_seated_prefix_never_demoted(setup, rng):
    """Evicting (= demoting) a prefix whose blocks are seated in a live
    slot still raises PrefixSeatedError, and no cold copy is created —
    a prefix is never demoted out from under a slot."""
    cfg, params, mc = setup
    m = cfg.memcom.num_memory_tokens
    kv = _compress_kv(cfg, params, mc,
                      rng.integers(4, cfg.vocab_size, 40).astype(np.int32))
    eng = ServingEngine(cfg, params, slots=1, max_len=m + 24,
                        kv_layout="paged", host_capacity=4)
    eng.add_prefix("t", kv)
    eng.seat_prefix(0, "t")
    with pytest.raises(PrefixSeatedError):
        eng.store.demote("t")
    assert eng.store.tier_of("t") == "hbm"
    assert not eng.store.host_names()


def test_paged_lru_demotes_instead_of_destroying(setup, rng):
    """prefix_capacity=1: registering task B LRU-evicts task A — with
    tiers configured A lands in the host tier instead of vanishing, and
    serving A afterwards promotes it back (no recompile possible: the
    engine has no compressor)."""
    cfg, params, mc = setup
    m = cfg.memcom.num_memory_tokens
    prompt = rng.integers(4, cfg.vocab_size, 5).astype(np.int32)
    kv_a = _compress_kv(cfg, params, mc,
                        rng.integers(4, cfg.vocab_size, 40).astype(np.int32))
    kv_b = _compress_kv(cfg, params, mc,
                        rng.integers(4, cfg.vocab_size, 40).astype(np.int32))

    ref = ServingEngine(cfg, params, slots=1, max_len=m + 24,
                        kv_layout="paged")
    ref.add_prefix("A", kv_a)
    want = ref.serve([Request(tokens=prompt, max_new=4, prefix="A")])

    eng = ServingEngine(cfg, params, slots=1, max_len=m + 24,
                        kv_layout="paged", prefix_capacity=1,
                        host_capacity=4)
    eng.add_prefix("A", kv_a)
    eng.add_prefix("B", kv_b)  # LRU-demotes A
    assert eng.store.tier_of("A") == "host"
    assert eng.store.tier_of("B") == "hbm"
    out = eng.serve([Request(tokens=prompt, max_new=4, prefix="A")])
    np.testing.assert_array_equal(next(iter(out.values())),
                                  next(iter(want.values())))
    # B was LRU-demoted in turn to make room for A's promotion
    assert eng.store.tier_of("B") == "host"


def test_dense_lru_capacity(setup, rng):
    """The dense store now takes prefix_capacity too: over-capacity puts
    evict (and, tiered, demote) the least-recently-used entry."""
    cfg, params, mc = setup
    m = cfg.memcom.num_memory_tokens
    kv = _compress_kv(cfg, params, mc,
                      rng.integers(4, cfg.vocab_size, 40).astype(np.int32))
    eng = ServingEngine(cfg, params, slots=1, max_len=m + 24,
                        prefix_capacity=2, host_capacity=4)
    eng.add_prefix("A", kv)
    eng.add_prefix("B", kv)
    eng.add_prefix("C", kv)  # evicts A (LRU)
    assert sorted(eng.store.hbm.names()) == ["B", "C"]
    assert eng.store.tier_of("A") == "host"


def test_host_pressure_spills_to_disk(setup, rng, tmp_path):
    """Demotions past host_capacity push the LRU host row to disk; with
    no disk tier it is dropped and counted."""
    cfg, params, mc = setup
    m = cfg.memcom.num_memory_tokens
    kv = _compress_kv(cfg, params, mc,
                      rng.integers(4, cfg.vocab_size, 40).astype(np.int32))
    eng = ServingEngine(cfg, params, slots=1, max_len=m + 24,
                        host_capacity=1, disk_dir=str(tmp_path))
    for name in ("A", "B", "C"):
        eng.add_prefix(name, kv)
        eng.store.demote(name)
    assert eng.store.tier_of("C") == "host"
    assert {eng.store.tier_of(n) for n in "AB"} == {"disk"}
    assert eng.stats()["prefix_tiers"]["spills"] == 2

    eng2 = ServingEngine(cfg, params, slots=1, max_len=m + 24,
                         host_capacity=1)  # no disk tier
    eng2.add_prefix("A", kv)
    eng2.add_prefix("B", kv)
    eng2.store.demote("A")
    eng2.store.demote("B")  # pushes A out with nowhere to go
    assert eng2.store.tier_of("A") is None
    assert eng2.stats()["prefix_tiers"]["host_drops"] == 1


def test_disk_shards_survive_restart(setup, rng, tmp_path):
    """A fresh engine pointed at an existing disk_dir indexes the shards
    and serves their tasks token-identically — no recompile."""
    cfg, params, mc = setup
    m = cfg.memcom.num_memory_tokens
    prompt = rng.integers(4, cfg.vocab_size, 5).astype(np.int32)
    kv = _compress_kv(cfg, params, mc,
                      rng.integers(4, cfg.vocab_size, 40).astype(np.int32))

    eng = ServingEngine(cfg, params, slots=1, max_len=m + 24,
                        host_capacity=0, disk_dir=str(tmp_path))
    eng.add_prefix("t", kv)
    want = eng.serve([Request(tokens=prompt, max_new=4, prefix="t")])
    eng.serve([Request(tokens=prompt, max_new=1)])  # unseat
    eng.store.demote("t")  # straight to disk
    assert os.listdir(str(tmp_path))

    eng2 = ServingEngine(cfg, params, slots=1, max_len=m + 24,
                         host_capacity=0, disk_dir=str(tmp_path))
    assert eng2.store.tier_of("t") == "disk"
    out = eng2.serve([Request(tokens=prompt, max_new=4, prefix="t")])
    np.testing.assert_array_equal(next(iter(out.values())),
                                  next(iter(want.values())))
    assert eng2.stats()["compiler"] is None  # nothing to compile with


def test_install_defers_on_queued_work(setup, rng):
    """Regression: a promoted prefix whose install cannot evict (the
    sole HBM entry is pinned by a *queued* request) must defer — the
    drain runs before admission, so the queue can be non-empty with
    every slot free — not crash serve().  The queued request runs,
    unpins, and the install lands."""
    cfg, params, mc = setup
    m = cfg.memcom.num_memory_tokens
    prompt = rng.integers(4, cfg.vocab_size, 5).astype(np.int32)
    kv_a = _compress_kv(cfg, params, mc,
                        rng.integers(4, cfg.vocab_size, 40).astype(np.int32))
    kv_c = _compress_kv(cfg, params, mc,
                        rng.integers(4, cfg.vocab_size, 40).astype(np.int32))

    eng = ServingEngine(cfg, params, slots=1, max_len=m + 24,
                        prefix_capacity=1, host_capacity=4,
                        promote_layer_budget=1)
    eng.add_prefix("A", kv_a)
    eng.add_prefix("C", kv_c)  # LRU-demotes A to host
    eng.store.demote("C")      # now: HBM empty, host = {A, C}
    # promote A back so serving can start from it HBM-resident
    out = eng.serve([Request(tokens=prompt, max_new=2, prefix="A")])
    r1 = Request(tokens=prompt, max_new=8, prefix="A")
    r2 = Request(tokens=prompt, max_new=2, prefix="C")  # parks, promotes
    r3 = Request(tokens=prompt, max_new=2, prefix="A")  # queued: pins A
    out = eng.serve([r1, r2, r3])
    assert len(out) == 3 and all(len(v) for v in out.values())
    assert eng.store.tier_of("C") == "hbm"  # install landed eventually


def test_unknown_cold_prefix_still_raises(setup, rng):
    """Tiering must not swallow genuinely unknown prefixes."""
    cfg, params, _ = setup
    eng = ServingEngine(cfg, params, slots=1, max_len=32, host_capacity=4)
    with pytest.raises(KeyError, match="nope"):
        eng.serve([Request(tokens=[5], max_new=1, prefix="nope")])


# ---------------------------------------------------------------------------
# Promotion under a model mesh lands pre-sharded (forced 4-device host)
# ---------------------------------------------------------------------------

_SHARDED_SCRIPT = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json
import numpy as np
import jax
import jax.numpy as jnp
from repro.configs import get_smoke_config
from repro.core import memcom
from repro.launch.mesh import make_serving_mesh
from repro.models import transformer as tfm
from repro.serving import Request, ServingEngine, materialize_prefix

report = {}
rng = np.random.default_rng(0)
cfg = get_smoke_config("smollm-135m").replace(
    d_model=128, num_heads=8, num_kv_heads=4, d_ff=256)
params = tfm.init_params(cfg, 0)
mc = memcom.init_memcom(cfg, params, 1)
m = cfg.memcom.num_memory_tokens
shots = jnp.asarray(rng.integers(4, cfg.vocab_size, (1, 40)), jnp.int32)
kv = materialize_prefix(params, cfg, memcom.compress(mc, cfg, shots)[0])
prompt = rng.integers(4, cfg.vocab_size, 5).astype(np.int32)


def tiered_cycle(eng):
    # warm -> unseat -> demote -> promoted serve, returning both outputs
    warm = eng.serve([Request(tokens=prompt, max_new=5, prefix="t")])
    eng.serve([Request(tokens=prompt, max_new=1)])
    eng.store.demote("t")
    hit = eng.serve([Request(tokens=prompt, max_new=5, prefix="t")])
    return (next(iter(warm.values())).tolist(),
            next(iter(hit.values())).tolist())


ref = ServingEngine(cfg, params, slots=2, max_len=m + 24, host_capacity=4,
                    promote_layer_budget=1)
ref.add_prefix("t", kv)
want_warm, want_hit = tiered_cycle(ref)
report["single_device_identical"] = want_warm == want_hit

for layout, kw in (("dense", {}),
                   ("paged", dict(kv_layout="paged", block_size=4))):
    for model in (2, 4):
        mesh = make_serving_mesh(model=model)
        eng = ServingEngine(cfg, params, slots=2, max_len=m + 24, mesh=mesh,
                            host_capacity=4, promote_layer_budget=1, **kw)
        eng.add_prefix("t", kv)
        got_warm, got_hit = tiered_cycle(eng)
        report[f"{layout}_{model}_tokens"] = (
            got_warm == want_warm and got_hit == want_warm)
        # the promoted row landed pre-sharded: every kv_heads leaf of the
        # store entry (dense) splits "model" on its head axis
        if layout == "dense":
            entry = eng.store.get("t")
            specs = [tuple(x.sharding.spec)
                     for e in ([entry["period"][k] for k in entry.get("period", {})]
                               + entry.get("prefix", []))
                     for key, x in e.items() if key in ("k", "v")]
            report[f"sharded_landing_{model}"] = (
                bool(specs) and all("model" in s for s in specs))
        report[f"{layout}_{model}_promotes"] = (
            eng.stats()["prefix_tiers"]["host_promotes"] == 1)

print(json.dumps(report))
"""


@pytest.mark.slow
def test_tiered_promotion_sharded(tmp_path):
    """Forced-4-device host: tiered serving is token-identical to single
    device on 2-/4-way model meshes (dense + paged), and the promoted
    rows land with their head axes split over "model" — pre-sharded, no
    replicated detour."""
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    script = tmp_path / "tiered_sharded.py"
    script.write_text(_SHARDED_SCRIPT)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(root, "src")
    res = subprocess.run([sys.executable, str(script)], capture_output=True,
                         text=True, timeout=1800, env=env, cwd=root)
    assert res.returncode == 0, res.stderr[-3000:]
    import json

    report = json.loads(res.stdout.strip().splitlines()[-1])
    for key, val in report.items():
        assert val, f"{key} failed"
