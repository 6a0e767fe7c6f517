"""The backlog cell's readers: the prefill programs' share of the step
programs' device time, the mean live slots per decode step, and the two
programs' MFU, on a small hand-made window and on the program runs of
50 ms recorded on the chip in ``mistral7b.backlog``
(``fixtures/programs/mistral7b_backlog_modules.json``); each returns
nothing where there is nothing to read."""

from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

from chipbench import flops, spec, xplane

NAMES = ("prefill_share.backlog", "occupancy.backlog", "decode_mfu.backlog",
         "prefill_mfu.backlog")
SHAPE = flops.Shape(d_model=4096, num_heads=32, num_kv_heads=8, hd=128,
                    d_ff=14336, vocab_size=32768, num_layers=8, m=768)
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
MS = 1_000_000  # ns
FIXTURE = Path(__file__).resolve().parent / "fixtures" / "programs" / \
    "mistral7b_backlog_modules.json"


def _window():
    """Two admissions' prefills (64 and 32 tokens behind a 768-row
    prefix), a decode step of 3 live slots, one prefill, a decode step of
    2; a slice program in between is neither."""
    E = xplane.Event
    runs = [("jit_paged_prefill_fn(7)", 0, 9), ("jit_paged_prefill_fn(8)",
                                                10, 17),
            ("jit_paged_decode_fn(3)", 18, 40), ("jit_slice(1)", 41, 42),
            ("jit_paged_prefill_fn(8)", 43, 50),
            ("jit_paged_decode_fn(3)", 51, 71)]
    mods = [E(n, n, s * MS, e * MS) for n, s, e in runs]
    red = xplane.Reduced(chips=[xplane.Chip(modules=mods)], host=[])
    lengths = np.full(32, 768, np.int64)
    calls = {"prefill": [(64, 768), (32, 768), (32, 768)],
             "decode": [lengths + 40, lengths + 41]}
    served = SimpleNamespace(
        calls=calls, stats={"engine": {"decode_steps": 2,
                                       "tokens_generated": 5}})
    return SimpleNamespace(trace=red, served=served, shape=SHAPE, peak=PEAK)


def _read(name, ctx):
    return spec.load_metric(name).read(ctx)


def test_backlog_readers_on_a_small_window():
    ctx = _window()
    pre, dec = (9 + 7 + 7) * 1e-3, (22 + 20) * 1e-3
    assert _read("prefill_share.backlog", ctx) == pytest.approx(
        100 * pre / (pre + dec))
    assert _read("occupancy.backlog", ctx) == 2.5
    work = sum(flops.prefill(SHAPE, w, b)["model_flops"]
               for w, b in ctx.served.calls["prefill"])
    assert _read("prefill_mfu.backlog", ctx) == pytest.approx(
        100 * work / pre / 197e12)
    work = sum(flops.decode_step(SHAPE, n + 1)["model_flops"]
               for n in ctx.served.calls["decode"])
    assert _read("decode_mfu.backlog", ctx) == pytest.approx(
        100 * work / dec / 197e12)
    for name in NAMES:
        v = _read(name, ctx)
        assert 0 < v and (name.startswith("occupancy") or v < 100)


def test_backlog_readers_read_nothing_from_an_empty_window():
    ctx = _window()
    ctx.trace = xplane.Reduced(chips=[xplane.Chip()], host=[])
    ctx.served.calls = {"decode": [], "prefill": []}
    ctx.served.stats = {"engine": {"decode_steps": 0,
                                   "tokens_generated": 0}}
    for name in NAMES:
        assert _read(name, ctx) is None


def test_recorded_window_finds_the_prefill_programs_by_name():
    """50 ms of a traced backlog window on the chip: six batch-1 prefills
    of 6.2-6.4 ms (64- and 32-token buckets behind the 768-row prefix)
    and no decode step, so the prefill share has no base and is not
    read; the MFU of the prefills is."""
    red = xplane.load_excerpt(FIXTURE)
    runs = [e for e in red.chips[0].modules
            if xplane.kind_of(e.label, xplane.PROGRAMS) == "prefill"]
    assert len(runs) == 6
    assert red.program_seconds("prefill") == pytest.approx(
        sum(e.end - e.start for e in runs) / 1e9)
    assert all(6.1e6 < e.end - e.start < 6.5e6 for e in runs)
    assert red.program_seconds("decode") == 0
    served = SimpleNamespace(calls={"prefill": [(64, 768)] * 6,
                                    "decode": []}, stats={})
    ctx = SimpleNamespace(trace=red, served=served, shape=SHAPE, peak=PEAK)
    assert _read("prefill_share.backlog", ctx) is None
    assert _read("occupancy.backlog", ctx) is None
    assert _read("decode_mfu.backlog", ctx) is None
    assert 0 < _read("prefill_mfu.backlog", ctx) < 100
