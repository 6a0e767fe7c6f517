"""The trace reduction: busy time as a union of intervals, kernel and
program time by name, and the breakdown, on a trace excerpt recorded on
the chip (``fixtures/``) and on a hand-made one."""

import json
from pathlib import Path

import pytest

from chipbench import xplane

FIXTURES = Path(__file__).resolve().parent / "fixtures"


def _hand():
    E = xplane.Event
    ops = [E("fusion.1", "fusion.1", 0, 10),
           E("paged_flash_decode custom-call.2", "custom-call.2", 5, 20),
           E("fusion.3", "fusion.3", 30, 40),
           E("flash_attention custom-call.4", "custom-call.4", 100, 150)]
    mods = [E("jit_fn(1)", "jit_fn(1)", 0, 40),
            E("jit_paged_prefill_fn(2)", "jit_paged_prefill_fn(2)", 100, 150)]
    host = [E("ExecuteHelper", "ExecuteHelper", 35, 99),
            E("sleep", "sleep", 40, 60)]
    return xplane.Reduced(chips=[xplane.Chip(ops=ops, modules=mods)],
                          host=host)


def test_busy_union_kernels_programs():
    r = _hand()
    assert r.busy_s == pytest.approx((20 + 10 + 50) / 1e9)
    assert r.kernel_seconds("paged_decode") == pytest.approx(15 / 1e9)
    assert r.kernel_seconds("flash") == pytest.approx(50 / 1e9)
    assert r.kernel_seconds("no_such_kernel") == 0.0
    assert r.program_seconds("decode") == pytest.approx(40 / 1e9)
    assert r.program_seconds("prefill") == pytest.approx(50 / 1e9)
    b = r.breakdown()
    assert b["device_ops"][0] == ["custom-call.4", pytest.approx(50 / 1e9)]
    assert b["idle_gaps"][0] == ["ExecuteHelper", pytest.approx(60 / 1e9)]
    assert b["idle_gaps"][1] == ["no host event traced",
                                 pytest.approx(10 / 1e9)]


def test_excerpt_round_trip(tmp_path):
    r = _hand()
    xplane.save_excerpt(r, tmp_path / "x.json", 0, 1000)
    back = xplane.load_excerpt(tmp_path / "x.json")
    assert back.busy_s == r.busy_s
    assert back.kernel_seconds("flash") == r.kernel_seconds("flash")


@pytest.mark.parametrize("path", sorted(FIXTURES.glob("*.json")),
                         ids=lambda p: p.stem)
def test_chip_excerpt(path):
    """A 50 ms excerpt of a traced window on a TPU v5e: the union of the
    operations' intervals is no longer than their sum nor than the span,
    and the kernels the metrics read are found by name."""
    r = xplane.load_excerpt(path)
    ops = r.chips[0].ops
    span = max(o.end for o in ops) - min(o.start for o in ops)
    total = sum(o.end - o.start for o in ops)
    assert 0 < r.busy_s * 1e9 <= min(span, total) + 1
    assert r.kernel_seconds("paged_decode") > 0
    assert r.program_seconds("decode") > 0
    names = json.loads(path.read_text())
    assert names["ops"] and names["modules"]
