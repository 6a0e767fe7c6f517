"""Telemetry tests: metrics-registry semantics (counters/gauges/
histograms/label sets, Prometheus text exposition), MetricGroup's
dict-facade contract, tracer ring-buffer (flight recorder) behaviour and
Chrome-trace schema, byte-identical trace dumps across same-seed churn
simulations, tracer-on/off token identity (dense, paged, fused+spec),
and the ``stats()`` deep-copy regression."""

import json
import math

import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core import memcom
from repro.models import transformer as tfm
from repro.serving import (
    MetricsRegistry,
    Request,
    ServingEngine,
    Tracer,
    TrafficConfig,
    VirtualClock,
    generate_trace,
    validate_chrome_trace,
)
from repro.serving.telemetry import (
    NULL_TRACER,
    REQUIRED_SPANS,
    Counter,
    Gauge,
    Histogram,
    MetricGroup,
)


@pytest.fixture(scope="module")
def setup():
    cfg = get_smoke_config("smollm-135m")
    params = tfm.init_params(cfg, 0)
    mc = memcom.init_memcom(cfg, params, 1)
    return cfg, params, mc


#: same churn scenario as tests/test_traffic.py: catalog exceeds
#: prefix/host capacity and two priority classes queue hot, so online
#: compiles, demotions, host→HBM promotions and preemptions all fire —
#: which is what makes its trace cover the full REQUIRED_SPANS taxonomy
CHURN = TrafficConfig(num_tasks=5, num_requests=12, context_tokens=24,
                      rate_rps=300.0, priority_classes=2)


def _churn_engine(cfg, params, mc, disk_dir, **kw):
    m = cfg.memcom.num_memory_tokens
    base = dict(slots=2, max_len=m + 32, compressor=mc,
                compile_token_budget=8, prefix_capacity=2,
                host_capacity=2, disk_dir=str(disk_dir),
                promote_layer_budget=1, clock=VirtualClock(),
                priority_aging_s=0.05)
    base.update(kw)
    return ServingEngine(cfg, params, **base)


def _churn_run(cfg, params, mc, disk_dir, **kw):
    """One churn simulation; returns (engine, tokens in trace order)."""
    trace = generate_trace(CHURN, 0)
    eng = _churn_engine(cfg, params, mc, disk_dir, **kw)
    out = eng.serve(list(trace.requests))
    return eng, [list(map(int, out[r.uid])) for r in trace.requests]


# ---------------------------------------------------------------------------
# Metrics registry
# ---------------------------------------------------------------------------


def test_counter_gauge_basics():
    reg = MetricsRegistry()
    c = reg.counter("requests_total", "requests", labelnames=("priority",))
    c.inc(priority=0)
    c.inc(2, priority=0)
    c.inc(priority=1)
    assert c.value(priority=0) == 3 and c.value(priority=1) == 1
    with pytest.raises(ValueError):
        c.inc(-1, priority=0)          # counters only go up
    with pytest.raises(ValueError):
        c.inc(1, wrong_label=0)        # undeclared label set
    g = reg.gauge("queue_depth")
    g.set(4)
    g.inc()
    g.dec(2)
    assert g.value() == 3


def test_registry_idempotent_and_kind_mismatch():
    reg = MetricsRegistry()
    a = reg.counter("jobs_total", labelnames=("family",))
    b = reg.counter("jobs_total", labelnames=("family",))
    assert a is b                      # same name -> same metric object
    with pytest.raises(ValueError):
        reg.gauge("jobs_total")        # kind mismatch
    with pytest.raises(ValueError):
        reg.counter("jobs_total")      # label-set mismatch


def test_histogram_hand_computed_quantiles():
    """Bucket-interpolated quantiles against hand arithmetic on buckets
    (1, 2, 5): observations [1, 2, 3] put one count in each of the first
    three buckets, so p99's rank 2.97 lands in (2, 5] with 2 below."""
    h = Histogram("lat", buckets=(1.0, 2.0, 5.0))
    for v in (1.0, 2.0, 3.0):
        h.observe(v)
    assert math.isclose(h.percentile(99), 2.0 + 3.0 * 0.97)
    assert math.isclose(h.percentile(50), 1.0 + 1.0 * 0.5)
    snap = h.snapshot()
    assert snap["le"] == [1.0, 2.0, 5.0, "+Inf"]
    assert snap["counts"] == [1, 1, 1, 0]
    assert snap["count"] == 3 and math.isclose(snap["sum"], 6.0)
    h.observe(100.0)                   # +Inf bucket clamps to top bound
    assert h.quantile(1.0) == 5.0
    assert Histogram("empty", buckets=(1.0,)).quantile(0.5) == 0.0
    with pytest.raises(ValueError):
        Histogram("bad", buckets=(2.0, 1.0))  # not strictly increasing


def test_histogram_quantile_edge_cases():
    """Regression: an empty histogram reports 0.0 from any quantile
    (never NaN or a crash), and a single finite bucket reports its bound
    — interpolating against the fabricated 0 lower edge would invent
    precision the buckets don't have."""
    assert Histogram("e", buckets=(1.0, 2.0)).quantile(0.5) == 0.0
    assert Histogram("e2", buckets=(1.0, 2.0)).quantile(0.99) == 0.0
    h = Histogram("one", buckets=(4.0,))
    assert h.quantile(0.5) == 0.0       # still empty -> 0.0
    h.observe(3.0)
    assert h.quantile(0.5) == 4.0       # single bucket -> the bound
    h.observe(100.0)                    # lands in +Inf
    assert h.quantile(0.99) == 4.0      # clamps to the only finite bound
    assert h.quantile(0.0) == 4.0
    # labeled series keep per-series behavior: one observed, one empty
    h2 = Histogram("lab", buckets=(2.0,), labelnames=("k",))
    h2.observe(1.0, k="a")
    assert h2.quantile(0.5, k="a") == 2.0
    assert h2.quantile(0.5, k="b") == 0.0


def test_prometheus_text_exposition():
    reg = MetricsRegistry()
    reg.counter("zebra_total", "last alphabetically").inc(7)
    c = reg.counter("apple_total", "first", labelnames=("kind",))
    c.inc(1, kind="b")
    c.inc(2, kind="a")
    h = reg.histogram("lat_seconds", "latency", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    text = reg.render_prometheus()
    assert text.endswith("\n")
    lines = text.splitlines()
    # metrics render in name order regardless of registration order
    assert lines[0] == "# HELP apple_total first"
    assert lines[1] == "# TYPE apple_total counter"
    # label sets in sorted order
    assert lines[2] == 'apple_total{kind="a"} 2'
    assert lines[3] == 'apple_total{kind="b"} 1'
    # histogram buckets are cumulative and end with +Inf, then sum/count
    assert 'lat_seconds_bucket{le="0.1"} 1' in lines
    assert 'lat_seconds_bucket{le="1"} 2' in lines
    assert 'lat_seconds_bucket{le="+Inf"} 2' in lines
    assert "lat_seconds_sum 0.55" in lines
    assert "lat_seconds_count 2" in lines
    assert "zebra_total 7" in lines
    # deterministic: rendering twice is byte-identical
    assert text == reg.render_prometheus()


def test_metric_group_preserves_dict_contract():
    """The stats-dict facade: every `stats["k"] += 1` call site keeps
    working, values keep their python type, and the same numbers show up
    under `{prefix}_{key}` in the registry."""
    reg = MetricsRegistry()
    grp = reg.group("store", {"hits": 0, "misses": 0, "ratio": 0.0})
    grp["hits"] += 3
    grp["misses"] += 1
    grp["ratio"] = 0.75
    assert dict(grp) == {"hits": 3, "misses": 1, "ratio": 0.75}
    assert isinstance(grp["hits"], int)       # type preserved: resets via
    assert type(grp["hits"])(0) == 0          # type(v)(0) stay exact
    assert len(grp) == 3 and "hits" in grp
    assert reg.get("store_hits").value() == 3
    with pytest.raises(KeyError):
        grp["unknown"]
    with pytest.raises(TypeError):
        del grp["hits"]                       # keys fixed at registration
    assert "store_hits 3" in reg.render_prometheus()


# ---------------------------------------------------------------------------
# Tracer: flight recorder + Chrome-trace schema
# ---------------------------------------------------------------------------


def test_flight_recorder_ring_buffer():
    clock = VirtualClock()
    tr = Tracer(clock, capacity=4)
    for i in range(10):
        clock.advance(0.001)
        tr.instant("engine", f"ev{i}")
    assert len(tr.events()) == 4
    assert tr.dropped == 6
    assert [e["name"] for e in tr.events()] == ["ev6", "ev7", "ev8", "ev9"]
    assert tr.chrome_trace()["otherData"]["dropped_events"] == 6
    tr.clear()
    assert tr.events() == [] and tr.dropped == 0
    with pytest.raises(ValueError):
        Tracer(capacity=0)


def test_chrome_trace_schema(tmp_path):
    clock = VirtualClock()
    tr = Tracer(clock, dump_path=str(tmp_path / "flight.json"))
    tr.span("engine", "decode_step", 0.0, 0.001, active=2)
    tr.instant("slot0", "finish", rid=0)
    tr.begin_async("scheduler", "waiting_on_prefix", 7, prefix="t")
    clock.advance(0.002)
    tr.end_async("scheduler", "waiting_on_prefix", 7)
    tr.span("weird-track", "custom", 0.0, 0.001)
    trace = tr.chrome_trace()
    assert validate_chrome_trace(trace) == []
    evs = trace["traceEvents"]
    meta = [e for e in evs if e["ph"] == "M"]
    names = {e["args"]["name"] for e in meta if e["name"] == "thread_name"}
    assert {"engine", "slot0", "scheduler", "weird-track"} <= names
    by_name = {e["name"]: e for e in evs if e["ph"] != "M"}
    assert by_name["decode_step"]["ph"] == "X"
    assert math.isclose(by_name["decode_step"]["dur"], 1000.0)  # µs
    assert by_name["decode_step"]["args"] == {"active": 2}
    assert by_name["finish"]["s"] == "t"                 # instant scope
    assert by_name["waiting_on_prefix"]["id"] == "7"     # async pairing
    # fixed tids: shared tracks stay put, slots offset, unknowns >= 1024
    tid = {e["args"]["name"]: e["tid"]
           for e in meta if e["name"] == "thread_name"}
    assert tid["engine"] == 1 and tid["scheduler"] == 4
    assert tid["slot0"] == 16 and tid["weird-track"] >= 1024
    # dump round-trips through JSON and dump_on_error is best-effort
    path = tr.dump_on_error()
    assert json.load(open(path)) == trace
    assert Tracer(clock).dump_on_error() is None         # no path set


def test_validate_chrome_trace_catches_malformed():
    assert validate_chrome_trace({}) == ["traceEvents missing or empty"]
    bad = {"traceEvents": [
        {"ph": "X", "pid": 1, "tid": 1, "name": "a", "ts": 0.0},  # no dur
        {"ph": "b", "pid": 1, "tid": 1, "name": "w", "ts": 0.0},  # no id
        {"ph": "i", "pid": 1, "tid": 1, "name": "x"},             # no ts
    ]}
    errs = validate_chrome_trace(bad, require_spans=("missing_span",))
    assert any("missing 'dur'" in e for e in errs)
    assert any("missing 'id'" in e for e in errs)
    assert any("missing 'ts'" in e for e in errs)
    assert any("missing_span" in e for e in errs)


def test_phase_span_on_loop_track():
    """A phase is one span of its own on the "loop" track, timed by the
    tracer's clock, with the phase's arguments."""
    clock = VirtualClock()
    tr = Tracer(clock)
    with tr.phase("admit", rid=3):
        clock.advance(0.004)
    with tr.phase("tokens"):
        pass
    a, b = tr.events()
    assert (a["track"], a["name"], a["args"]) == ("loop", "serve.admit",
                                                  {"rid": 3})
    assert math.isclose(a["dur"], 0.004) and b["t"] == a["t"] + a["dur"]
    assert b["name"] == "serve.tokens" and b["dur"] == 0.0
    tid = {e["args"]["name"]: e["tid"] for e in tr.chrome_trace()
           ["traceEvents"] if e["name"] == "thread_name"}
    assert tid == {"loop": 5}


def test_null_tracer_is_inert():
    assert NULL_TRACER.enabled is False
    NULL_TRACER.span("engine", "x", 0.0)
    NULL_TRACER.instant("engine", "y")
    with NULL_TRACER.phase("admit", rid=0):
        pass
    assert NULL_TRACER.events() == []
    assert NULL_TRACER.chrome_trace() == {"traceEvents": []}
    assert NULL_TRACER.dump_on_error() is None


def test_virtual_clock_charge_counters():
    clock = VirtualClock()
    reg = MetricsRegistry()
    clock.attach_metrics(reg)
    clock.attach_metrics(reg)                 # idempotent per registry
    clock.charge("decode_step", 3)
    clock.charge("compile_token", 8)
    units = reg.get("virtual_clock_charged_units_total")
    secs = reg.get("virtual_clock_charged_seconds_total")
    assert units.value(kind="decode_step") == 3.0
    assert math.isclose(secs.value(kind="decode_step"),
                        3 * clock.costs["decode_step"])
    assert math.isclose(clock.now,
                        3 * clock.costs["decode_step"]
                        + 8 * clock.costs["compile_token"])


# ---------------------------------------------------------------------------
# stats() deep copy
# ---------------------------------------------------------------------------


def test_stats_returns_deep_copy(setup):
    """Mutating the dict `stats()` returned must not corrupt the live
    registry — the bench mutates/serializes these dicts freely."""
    cfg, params, _ = setup
    eng = ServingEngine(cfg, params, slots=1, max_len=40,
                        clock=VirtualClock())
    eng.serve([Request(tokens=np.array([5, 6, 7], np.int32), max_new=4)])
    s1 = eng.stats()
    golden = json.dumps(s1, sort_keys=True)
    s1["engine"]["decode_steps"] = -999       # vandalize every level
    s1["budgets"]["compile_token_budget"] = -1
    s1["prefix_store"].clear()
    assert json.dumps(eng.stats(), sort_keys=True) == golden


# ---------------------------------------------------------------------------
# Trace determinism + token identity under churn
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def churn_traced(setup, tmp_path_factory):
    """Two traced same-seed churn sims + one untraced, shared by the
    determinism / identity / coverage tests below (each sim is a full
    engine lifetime — run them once)."""
    cfg, params, mc = setup
    root = tmp_path_factory.mktemp("churn-traced")
    runs = []
    for sub in ("a", "b"):
        tracer, reg = Tracer(), MetricsRegistry()
        eng, tokens = _churn_run(cfg, params, mc, root / sub,
                                 tracer=tracer, metrics=reg)
        runs.append({"dumps": tracer.dumps(), "tokens": tokens,
                     "registry": reg, "log": eng.request_log})
    eng, tokens_off = _churn_run(cfg, params, mc, root / "off")
    return runs[0], runs[1], tokens_off, eng.request_log


def test_trace_byte_identical_across_same_seed_runs(churn_traced):
    a, b, *_ = churn_traced
    assert a["dumps"] == b["dumps"]           # byte-for-byte
    assert len(a["dumps"]) > 1000             # and non-trivial


def test_request_stamps_in_order(churn_traced):
    """Under churn (queueing, parking on compiles and promotions,
    preemption) every request's stamps run due → released → admitted →
    first token → finish, queue wait plus admission-to-first-token is its
    TTFT, and the stamps are the same with tracing off."""
    a, _, _, log_off = churn_traced
    order = ("arrival_s", "released_s", "admitted_s", "first_token_s",
             "finish_s")
    assert len(a["log"]) == CHURN.num_requests
    for r in a["log"].values():
        stamps = [r[k] for k in order]
        assert stamps == sorted(stamps), r
        ttft = r["first_token_s"] - r["arrival_s"]
        assert math.isclose((r["admitted_s"] - r["arrival_s"])
                            + (r["first_token_s"] - r["admitted_s"]), ttft,
                            rel_tol=0, abs_tol=1e-12)
    assert any(r["admitted_s"] > r["released_s"] for r in a["log"].values())
    assert sorted(map(sorted, (r.items() for r in a["log"].values()))) == \
        sorted(map(sorted, (r.items() for r in log_off.values())))


def test_trace_covers_request_lifecycle(churn_traced):
    """The churn trace contains every span the taxonomy guarantees:
    admission, waiting_on_prefix, compile_chunk, promote_chunk,
    preempt, resume, decode_step."""
    a, *_ = churn_traced
    trace = json.loads(a["dumps"])
    assert validate_chrome_trace(trace, require_spans=REQUIRED_SPANS) == []


def test_tracer_on_off_token_identity_dense(churn_traced):
    """Telemetry only reads the clock: the traced churn run emits
    exactly the tokens of the untraced one."""
    a, _, tokens_off, _ = churn_traced
    assert a["tokens"] == tokens_off


def test_tracer_on_off_token_identity_paged(setup, tmp_path):
    cfg, params, mc = setup
    tracer = Tracer()
    _, on = _churn_run(cfg, params, mc, tmp_path / "on",
                       kv_layout="paged", tracer=tracer)
    _, off = _churn_run(cfg, params, mc, tmp_path / "off",
                        kv_layout="paged")
    assert on == off
    assert validate_chrome_trace(tracer.chrome_trace()) == []


def test_tracer_on_off_token_identity_fused_spec(setup):
    """Fused step + self-speculative decoding, traced vs untraced —
    and the trace carries the spec_accept + fused_step events."""
    cfg, params, _ = setup
    rng = np.random.default_rng(0)
    prompts = [rng.integers(4, cfg.vocab_size, n).astype(np.int32)
               for n in (5, 11, 8)]

    def run(tracer=None):
        eng = ServingEngine(cfg, params, slots=2, max_len=40,
                            clock=VirtualClock(), fused_step=True,
                            spec_draft="self", spec_k=2, tracer=tracer)
        reqs = [Request(tokens=p, max_new=6) for p in prompts]
        out = eng.serve(reqs)
        return [list(map(int, out[r.uid])) for r in reqs]

    tracer = Tracer()
    assert run(tracer) == run(None)
    names = {e["name"] for e in tracer.events()}
    assert "spec_accept" in names
    assert "fused_step" in names


def test_churn_prometheus_exposition(churn_traced):
    """The registry a churn engine filled renders every subsystem's
    series: engine/compiler/store/tier counters, scheduler gauges, the
    decode-gap histogram and the virtual-clock charge counters."""
    a, b, *_ = churn_traced
    text = a["registry"].render_prometheus()
    for needle in (
            "# TYPE serving_engine_decode_steps gauge",
            "# TYPE serving_compiler_jobs gauge",
            "serving_prefix_store_hits",
            "serving_prefix_tiers_demotes",
            "serving_sched_submitted_total",
            "serving_sched_preemptions_total",
            "# TYPE serving_decode_gap_seconds histogram",
            'serving_decode_gap_seconds_bucket{le="+Inf"}',
            'serving_ttft_seconds_count{priority="0"}',
            'virtual_clock_charged_units_total{kind="decode_step"}',
            "serving_jit_compiles_total{",
    ):
        assert needle in text, f"missing {needle!r}"
    # deterministic end to end: same seed -> same exposition
    assert text == b["registry"].render_prometheus()
