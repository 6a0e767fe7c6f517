"""One run of one cell: set-up, the measured window, the reduction of a
traced window to per-layer metrics, and the comparison that decides
``correct``.  ``chipbench/run.py`` is the command; ``chipbench/control.py``
reuses the pieces to read the comparison's limits."""

from __future__ import annotations

import gc
import shutil
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional

import numpy as np

from chipbench import flops, traffic, xplane

TRACE_CAP_S = 6.0     # seconds of arrivals in a traced window
SAMPLE_TOKENS = 240   # served tokens the correctness check reads, at least


def task_name(t: int) -> str:
    return f"task{t}"


@dataclass
class Served:
    """What one ``serve`` call did."""

    queries: List[traffic.Query]
    uids: List[int]
    outputs: Dict[int, np.ndarray]
    log: Dict[int, dict]
    seconds: float                 # the serve call, start to end
    stats: dict
    calls: Dict[str, list] = field(default_factory=dict)
    compiles: int = 0              # programs built or loaded in the window


class Run:
    """The program under test, built for one cell and one seed."""

    def __init__(self, cell, traffic_: traffic.Traffic, seed: int,
                 log: Callable[[str], None]):
        self.cell, self.traffic, self.seed, self.say = cell, traffic_, seed, log
        self.cfg = cell.adapter.program_config(cell.config)
        self.engine = None

    # ---- set-up ---------------------------------------------------------

    def setup(self) -> Dict[str, float]:
        import jax
        from repro.serving import Request, ServingEngine

        c, mix, t = self.cell.config, self.cell.mix, self.traffic
        phases = {}
        t0 = time.perf_counter()
        target, compressor = self.cell.adapter.program_weights(self.cfg,
                                                               self.seed)
        jax.block_until_ready((target, compressor))
        phases["weights_s"] = time.perf_counter() - t0
        e = mix["engine"]
        m, bs = c["num_memory_tokens"], e["block_size"]
        q_hi, new_hi = mix["query"]["tokens"][1], mix["query"]["max_new"][1]
        private = -(-(q_hi + new_hi) // bs) + 1
        self.engine = ServingEngine(
            self.cfg, target, slots=e["slots"], max_len=m + q_hi + new_hi,
            kv_layout="paged", block_size=bs,
            num_blocks=1 + len(t.shots) * -(-m // bs) + e["slots"] * private,
            compressor=compressor)
        del target, compressor
        # every task is compressed through the engine's own online compiler
        rng = np.random.default_rng(self.seed & (2**63 - 1))
        q_lo = mix["query"]["tokens"][0]
        t0 = time.perf_counter()
        self.engine.serve([
            Request(tokens=rng.integers(0, c["vocab_size"], q_lo),
                    max_new=1, prefix=task_name(k), raw_shots=shots,
                    stop_token=None) for k, shots in enumerate(t.shots)])
        done = self.engine.stats()["compiler"]["compiled"]
        if done != len(t.shots):
            raise RuntimeError(f"compiled {done} of {len(t.shots)} tasks")
        phases["compress_s"] = time.perf_counter() - t0
        # one request per query length warms every prefill bucket, and
        # the longest answers warm the decode step
        t0 = time.perf_counter()
        self.engine.serve([
            Request(tokens=rng.integers(0, c["vocab_size"], n),
                    max_new=new_hi, stop_token=None,
                    prefix=task_name(n % len(t.shots)))
            for n in range(q_lo, q_hi + 1)])
        phases["warmup_s"] = time.perf_counter() - t0
        return phases

    # ---- the window -----------------------------------------------------

    def serve(self, queries: List[traffic.Query], *, trace_dir=None,
              record: bool = False) -> Served:
        import jax
        from repro.serving import Request

        reqs = [Request(tokens=q.tokens, max_new=q.max_new,
                        prefix=task_name(q.task), stop_token=None,
                        arrival_s=q.arrival_s)
                for q in queries]
        eng = self.engine
        eng.reset_stats()
        calls = _record_calls(eng) if record else {}
        compiles = _CompileCounter()
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0  # no per-call python events
            jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
        with compiles:
            t0 = time.perf_counter()
            outputs = eng.serve(reqs)
            seconds = time.perf_counter() - t0
        if trace_dir is not None:
            jax.profiler.stop_trace()
        if record:
            _unrecord(eng)
        return Served(queries=queries, uids=[r.uid for r in reqs],
                      outputs=outputs, log=dict(eng.request_log),
                      seconds=seconds, stats=eng.stats(), calls=calls,
                      compiles=compiles.n)

    def free(self) -> None:
        """Drop every device buffer the program holds, so the reference
        runs on an empty chip."""
        import jax

        self.engine = None
        gc.collect()
        for a in jax.live_arrays():
            a.delete()


class _CompileCounter:
    """Counts programs compiled or fetched from the persistent cache while
    active (there should be none inside a window)."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")

    _listening = None

    def __init__(self):
        self.n, self.on = 0, False
        if _CompileCounter._listening is None:
            import jax.monitoring as mon

            mon.register_event_duration_secs_listener(
                lambda *a, **k: _CompileCounter._listening._hear(*a, **k))
        _CompileCounter._listening = self

    def _hear(self, event, _secs, **_kw):
        if self.on and event in self.EVENTS:
            self.n += 1

    def __enter__(self):
        self.on = True

    def __exit__(self, *_):
        self.on = False


def _record_calls(eng) -> Dict[str, list]:
    """Wrap the engine's step programs to note each call's shapes (traced
    runs only): decode steps' per-slot lengths and prefills' (width,
    base)."""
    calls = {"decode": [], "prefill": []}
    saved = {"_decode_greedy": eng._decode_greedy, "_prefill": eng._prefill}
    dec, pre = saved["_decode_greedy"], saved["_prefill"]

    def decode(params, cache, tok, lengths, *rest):
        calls["decode"].append(np.asarray(lengths))
        return dec(params, cache, tok, lengths, *rest)

    def prefill(params, cache, tokens, slot, *rest):
        calls["prefill"].append((int(tokens.shape[1]), int(rest[-1])))
        return pre(params, cache, tokens, slot, *rest)

    eng._decode_greedy, eng._prefill = decode, prefill
    eng._recorded = saved
    return calls


def _unrecord(eng) -> None:
    for k, v in eng._recorded.items():
        setattr(eng, k, v)
    del eng._recorded


# ---- end-to-end metrics ------------------------------------------------


def end_to_end(served: Served) -> Dict[str, float]:
    """TTFT and TPOT 95th percentiles over all requests of the window
    (exact, numpy's linear interpolation), and the completed requests per
    second of the window's ``serve`` call."""
    log = [served.log[u] for u in served.uids]
    done = sum(1 for r in log if r["finish_s"] is not None)
    ttft = [r["first_token_s"] - r["arrival_s"] for r in log
            if r["first_token_s"] is not None]
    tpot = [(r["finish_s"] - r["first_token_s"]) / (r["tokens"] - 1)
            for r in log if r["finish_s"] is not None and r["tokens"] >= 2]
    out = {}
    if ttft:
        out["ttft_p95_ms"] = 1e3 * float(np.percentile(ttft, 95))
    if tpot:
        out["tpot_p95_ms"] = 1e3 * float(np.percentile(tpot, 95))
    if done:
        out["queries_per_s"] = done / served.seconds
    return out


def incomplete(served: Served) -> int:
    return sum(1 for q, u in zip(served.queries, served.uids)
               if len(served.outputs.get(u, ())) != q.max_new)


# ---- correctness -------------------------------------------------------


def sample(served: Served, traffic_: traffic.Traffic, seed: int,
           tokens: int) -> List[int]:
    """Indices of finished requests to compare, drawn from the seed: the
    longest (largest shot set, then longest prompt plus answer) first,
    then others until ``tokens`` served tokens are covered."""
    done = [i for i, u in enumerate(served.uids)
            if len(served.outputs.get(u, ())) > 0]
    size = lambda i: (len(traffic_.shots[served.queries[i].task]),
                      len(served.queries[i].tokens)
                      + len(served.outputs[served.uids[i]]))
    longest = max(done, key=size)
    rng = np.random.default_rng(np.random.SeedSequence(
        [int(seed) & (2**63 - 1), 0x636b]))
    order = [longest] + [i for i in rng.permutation(done) if i != longest]
    picked, n = [], 0
    for i in order:
        picked.append(int(i))
        n += len(served.outputs[served.uids[i]])
        if n >= tokens:
            break
    return picked


def reference_inputs(served: Served, traffic_: traffic.Traffic,
                     picked: List[int]):
    """The reference's shots, fed tokens and read positions for the
    sample, and the served token at each read position."""
    tasks = sorted({served.queries[i].task for i in picked})
    index = {t: j for j, t in enumerate(tasks)}
    queries, want = [], []
    for i in picked:
        q, out = served.queries[i], served.outputs[served.uids[i]]
        fed = np.concatenate([q.tokens, out[:-1]]).astype(np.int32)
        pos = len(q.tokens) - 1 + np.arange(len(out))
        queries.append((index[q.task], fed, pos))
        want.append(np.asarray(out, np.int64))
    return [traffic_.shots[t] for t in tasks], queries, np.concatenate(want)


def logit_gaps(ref: np.ndarray, tokens: np.ndarray) -> np.ndarray:
    """How far each token's reference logit lies below the reference's
    best at its position."""
    return ref.max(1) - ref[np.arange(len(tokens)), tokens]


# ---- one run -----------------------------------------------------------


def run_cell(cell, devs, peak, *, seed, seconds, trace, t_start, trace_dir,
             log, sample_tokens=SAMPLE_TOKENS, control=False) -> dict:
    """One run.  With ``control`` the comparison reads the control in the
    program's place: the reference in float8, at each position of the
    same prompts and served tokens, the token it ranks first."""
    conf = cell.config
    window = min(seconds, TRACE_CAP_S) if trace else seconds
    tr = traffic.generate(cell.mix, conf["vocab_size"], seed, window)
    run = Run(cell, tr, seed, log)
    phases = run.setup()
    setup_s = time.time() - t_start
    log(f"set-up {setup_s:.3f} s ({', '.join(f'{k} {v:.3f}' for k, v in phases.items())})")

    served = run.serve(tr.queries, trace_dir=trace_dir if trace else None,
                       record=trace)
    log(f"window: {len(tr.queries)} requests in {served.seconds:.3f} s, "
        f"{served.compiles} programs compiled or loaded inside it")
    stats = [d.memory_stats() or {} for d in devs]
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs),
              "memory_peak_bytes": max(int(s.get("peak_bytes_in_use", 0))
                                       for s in stats)}
    metrics: Dict[str, dict] = {}
    breakdown = None
    if trace:
        red = xplane.reduce(trace_dir, [d.id for d in devs],
                            summary_path=trace_dir.parent / "ops.json")
        if red.chips[0].modules:  # 50 ms from the middle of the window
            mid = red.chips[0].modules[len(red.chips[0].modules) // 2]
            xplane.save_excerpt(red, trace_dir.parent / "excerpt.json",
                                mid.start, 50_000_000)
        device["busy_s"] = red.busy_s
        device["window_s"] = served.seconds
        ctx = MetricContext(cell=cell, shape=flops.Shape.of(conf), peak=peak,
                            served=served, trace=red, window_s=served.seconds)
        from chipbench import spec

        for m in cell.per_layer:
            value = spec.load_metric(m["name"]).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        breakdown = red.breakdown()
    else:
        e2e = end_to_end(served)
        e2e["setup_s"] = setup_s
        for m in cell.end_to_end:
            if m["name"] in e2e:
                metrics[m["name"]] = {"value": e2e[m["name"]],
                                      "unit": m["unit"]}

    failed = incomplete(served)
    picked = sample(served, tr, seed, sample_tokens)
    shots, queries, want = reference_inputs(served, tr, picked)
    run.free()
    t0 = time.perf_counter()
    ref = cell.reference.logits(conf, seed, shots, queries)
    if control:
        want = cell.reference.logits(conf, seed, shots, queries,
                                     control=True).argmax(1)
    gap = float(logit_gaps(ref, want).max())
    log(f"reference{' and control' if control else ''}: {len(picked)} "
        f"requests, {len(want)} served tokens, {len(shots)} tasks, "
        f"{time.perf_counter() - t0:.3f} s")
    limit = conf["limits"]["max_logit_gap"]
    checks = {
        "incomplete_requests": {"value": failed, "limit": 0},
        "max_logit_gap": {"value": gap, "limit": limit},
    }
    result = {
        "correct": bool(failed == 0 and gap <= limit),
        "attempted": len(served.queries), "failed": failed,
        "metrics": metrics, "device": device,
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["window_compiles"] = served.compiles
    result["checks"] = checks
    return result


@dataclass
class MetricContext:
    """What a per-layer metric reader may read."""

    cell: object
    shape: flops.Shape
    peak: dict
    served: Served
    trace: "xplane.Reduced"
    window_s: float
