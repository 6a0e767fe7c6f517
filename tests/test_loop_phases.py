"""The serving loop's host phases on the profiler's clock: every phase of
``ServingEngine._serve_impl`` runs under a ``serve.*`` annotation, and the
annotations are siblings, never nested, so a trace reader can put each
stretch of device-idle time down to exactly one of them."""

import glob
import os
from collections import defaultdict

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs import get_smoke_config
from repro.models import transformer as tfm
from repro.serving import Request, ServingEngine, Tracer

#: phases a paged serve with timed arrivals and no compile must show
LOOP_PHASES = {"serve.release", "serve.idle", "serve.admit",
               "serve.prefill.dispatch", "serve.prefill.fetch",
               "serve.decode.dispatch", "serve.decode.fetch", "serve.tokens"}


@pytest.fixture(scope="module")
def setup():
    cfg = get_smoke_config("smollm-135m")
    return cfg, tfm.init_params(cfg, 0)


def _requests(cfg, arrivals):
    rng = np.random.default_rng(0)
    return [Request(tokens=rng.integers(4, cfg.vocab_size, 5 + 2 * i)
                    .astype(np.int32), max_new=3, arrival_s=a)
            for i, a in enumerate(arrivals)]


def _host_phases(trace_dir):
    """serve.* events per host line: [(start, end, name, stats)]."""
    path = max(glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                         recursive=True), key=os.path.getmtime)
    lines = defaultdict(list)
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("serve."):
                    lines[(plane.name, line.name)].append(
                        (ev.start_ns, ev.start_ns + ev.duration_ns, ev.name,
                         dict(ev.stats)))
    return lines


def test_phases_on_profiler_clock_never_nest(setup, tmp_path):
    cfg, params = setup
    eng = ServingEngine(cfg, params, slots=2, max_len=48, kv_layout="paged",
                        block_size=8, tracer=Tracer(capacity=None))
    eng.serve(_requests(cfg, [0.0, 0.0, 0.0]))  # compile every program
    eng.tracer.clear()
    reqs = _requests(cfg, [0.0, 0.03, 0.06, 0.09])
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        out = eng.serve(reqs)
    finally:
        jax.profiler.stop_trace()
    assert all(len(out[r.uid]) == 3 for r in reqs)

    lines = _host_phases(tmp_path)
    assert lines, "no serve.* annotation on any host plane"
    assert all(plane == "/host:CPU" for plane, _ in lines)
    events = [ev for evs in lines.values() for ev in evs]
    assert {name for _, _, name, _ in events} >= LOOP_PHASES
    for evs in lines.values():
        evs.sort()
        for (s0, e0, n0, _), (s1, e1, n1, _) in zip(evs, evs[1:]):
            assert e0 <= s1, f"{n1} opens inside {n0}"
    rids = {st["rid"] for _, _, name, st in events
            if name == "serve.admit" and "rid" in st}
    assert rids == {0, 1, 2, 3}
    idle = [st for _, _, name, st in events if name == "serve.idle"]
    assert idle and all({"due_s", "sleep_ms"} <= set(st) for st in idle)

    # the ring buffer holds the same phases, one span each, on the
    # engine clock and the "loop" track
    loop = [e for e in eng.tracer.events() if e["track"] == "loop"]
    assert {e["name"] for e in loop} == {n for _, _, n, _ in events}
    assert sum(e["name"] == "serve.admit" for e in loop) == \
        sum(n == "serve.admit" for _, _, n, _ in events)
