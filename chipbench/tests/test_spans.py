"""The engine-loop readers: queue wait and release lateness from the
``request_log`` stamps, and device-idle time put down to the loop's
``serve.*`` host phases, on a hand-made trace and on an excerpt recorded
on the chip (``fixtures/smollm360m_warm_loop.json``)."""

from pathlib import Path
from types import SimpleNamespace

import pytest

from chipbench import spans, spec, xplane

FIXTURE = Path(__file__).resolve().parent / "fixtures" / \
    "smollm360m_warm_loop.json"
READERS = ("queue_wait_ms.open", "release_late_ms.open", "host_gap_ms.open")


def _hand(annotated=True):
    E = xplane.Event
    mods = [E("jit_paged_decode_fn(1)", "jit_paged_decode_fn(1)", 0, 40),
            E("jit_slice(3)", "jit_slice(3)", 45, 46),
            E("jit_paged_prefill_fn(2)", "jit_paged_prefill_fn(2)", 60, 100),
            E("jit_paged_decode_fn(1)", "jit_paged_decode_fn(1)", 130, 170),
            E("jit_paged_decode_fn(1)", "jit_paged_decode_fn(1)", 300, 340)]
    host = [E("np.asarray", "np.asarray", 30, 42)]
    if annotated:
        host += [E(n, n, s, e) for n, s, e in [
            ("serve.decode.fetch", 30, 42), ("serve.tokens", 42, 50),
            ("serve.admit", 50, 55),  # 55-57: under no annotation
            ("serve.prefill.dispatch", 57, 62),
            ("serve.prefill.fetch", 62, 105), ("serve.tokens", 105, 120),
            ("serve.decode.dispatch", 120, 131),  # 170-175: none
            ("serve.idle", 175, 290), ("serve.release", 290, 295),
            ("serve.decode.dispatch", 295, 301)]]
    return xplane.Reduced(chips=[xplane.Chip(modules=mods)], host=host)


def _share(red):
    """Share of the idle time that lies under some serve.* annotation."""
    split = spans.idle_by_phase(red)
    return 1 - split.get(None, 0.0) / sum(split.values())


def _ctx(red=None, log=None):
    log = log if log is not None else {}
    served = SimpleNamespace(uids=list(log), log=log)
    return SimpleNamespace(served=served, trace=red)


def test_idle_split_by_phase():
    split = spans.idle_by_phase(_hand())
    assert split[None] == 2 + 5
    assert split["serve.idle"] == 115
    assert split["serve.tokens"] == 3 + 4 + 15
    assert split["serve.decode.dispatch"] == 10 + 5
    assert sum(split.values()) == 5 + 14 + 30 + 130
    # 57 ns of host work over 4 step programs (the slice is not one)
    assert spans.host_gap_ms(_hand()) == pytest.approx(57 / 4 / 1e6)
    assert _share(_hand()) == pytest.approx(1 - 7 / 179)
    read = spec.load_metric("host_gap_ms.open").read
    assert read(_ctx(_hand())) == pytest.approx(57 / 4 / 1e6)


def test_idle_under_serve_idle_is_not_a_host_gap():
    red = _hand()
    red.host = [h for h in red.host if h.name in ("serve.idle",)]
    assert spans.host_gap_ms(red) == 0.0
    assert _share(red) == pytest.approx(115 / 179)


def test_nothing_to_read_is_none():
    """A program without the annotations or the stamps (the parent of
    this change) reads None, never 0."""
    assert spans.host_gap_ms(_hand(annotated=False)) is None
    assert set(spans.idle_by_phase(_hand(annotated=False))) == {None}
    empty = xplane.Reduced(chips=[xplane.Chip()], host=[])
    assert spans.host_gap_ms(empty) is None
    old = {1: {"arrival_s": 0.0, "first_token_s": 0.2, "finish_s": 0.3}}
    for name in READERS:
        assert spec.load_metric(name).read(
            _ctx(_hand(annotated=False), old)) is None


def test_stamp_means():
    log = {7: {"arrival_s": 1.0, "released_s": 1.002, "admitted_s": 1.05,
               "first_token_s": 1.09},
           9: {"arrival_s": 2.0, "released_s": 2.004, "admitted_s": 2.15,
               "first_token_s": 2.2}}
    ctx = _ctx(_hand(), log)
    assert spec.load_metric("queue_wait_ms.open").read(ctx) == \
        pytest.approx((50 + 150) / 2)
    assert spec.load_metric("release_late_ms.open").read(ctx) == \
        pytest.approx((2 + 4) / 2)


def test_chip_excerpt_loop_phases():
    """An excerpt of a traced window on a TPU v5e, from a decode step's
    start: the chip's idle time falls under the loop's phases, and the
    breakdown names its gaps after them."""
    red = xplane.load_excerpt(FIXTURE)
    assert _share(red) >= 0.95
    assert 0 < spans.host_gap_ms(red) < 20
    gaps = [name for name, s in red.breakdown()["idle_gaps"] if s > 1e-4]
    assert gaps and all(name.startswith(spans.PHASE) for name in gaps)
