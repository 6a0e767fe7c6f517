"""Production serving launcher: offline compression + continuous-batching
compressed-cache serving behind one CLI (the paper's cloud-edge
deployment, §1).

    PYTHONPATH=src python -m repro.launch.serve --arch smollm-135m --smoke \
        --requests 6 --tasks 2 --slots 4 --max-new 8

Stages:
  1. "cloud": load/initialize the compressor, compress each ICL task's
     many-shot context once, materialize the per-layer compressed KV
     through the frozen target projections, and register it in the
     engine's PrefixStore.
  2. "edge": a continuous-batching ServingEngine seats each request's
     compressed task memory in its own slot and serves ragged
     generate/classify traffic — more requests than slots is fine,
     finished slots refill mid-decode.

``--raw-shots`` removes stage 1 from the critical path: requests carry
their raw many-shot context and the engine's online PrefixCompiler
compresses each unseen task *inside* the serving loop — in
``--compile-budget``-token chunks interleaved with decode steps, so
already-seated slots keep emitting tokens while a cold task compiles
(single-flight: concurrent requests for one task share one compile).
``--stats`` prints the engine's cache/compile counters either way.

``--host-capacity``/``--disk-dir`` put a memory hierarchy behind the
HBM prefix store (``--prefix-capacity`` bounds HBM residency): evicted
compressed prefixes demote to pinned host RAM, spill to
codec-compressed disk shards under host pressure, and promote back
host→HBM in ``--promote-budget``-chunk steps interleaved with decode
when a request names them again.  Combined with ``--raw-shots``
(content-addressed prefix names) a restart pointing ``--disk-dir`` at a
previous run's directory promotes the spilled shards instead of
recompiling those tasks; in offline-compress mode stage 1 always
re-registers fresh prefixes, superseding any old shards.

``--kv-layout paged`` swaps the per-slot dense cache for the block-pool
paged cache: every slot seated on the same task points its block table
at one shared physical copy of the compressed prefix (copy-on-write on
the partial tail block), so prefix memory is O(tasks) instead of
O(slots).  ``--block-size``/``--num-blocks`` size the pool; admission is
gated on free blocks.  See docs/ARCHITECTURE.md.

``--traffic zipf`` (Poisson arrivals) / ``--traffic onoff`` (bursty
ON-OFF) replaces the fixed request batch with a seeded production-shaped
workload: a Zipf-popularity catalog of ``--traffic-tasks`` ICL tasks
(requests carry raw shots, so unseen tasks compile online and evicted
ones churn through the tiers) served at ``--traffic-rate`` requests per
*simulated* second against the engine's virtual clock —
``--priority-classes N`` splits requests into preemptible priority
classes (``--priority-aging`` bounds starvation), ``--slo-ttft`` sets
the TTFT SLO the goodput line reports against, and
``--autotune-budgets`` lets the engine trade compile/promote budgets
against the observed decode gap.  Same seed, same numbers, any host.

``--fused-step`` folds admission prefills (in ``--fused-chunk-tokens``
pieces) and online compile chunks into the batched decode dispatch, so
churn never opens a decode gap; ``--spec-draft smollm-135m --spec-k 2``
adds speculative decoding on the same fused lanes (a small drafter — or
``self`` — proposes k tokens per slot, verified in one step; greedy
output is token-identical to the non-speculative engine).

``--mesh M`` (or ``--mesh DxM``) runs the whole edge stage
tensor-parallel: target params placed from their logical axes, KV
caches/pools split by head over the mesh "model" axis, block tables and
per-slot lengths replicated (see docs/ARCHITECTURE.md §"Sharded
serving").  Target and compressor are created on their shards.  With
``JAX_PLATFORMS=cpu`` set and too few devices the launcher forces
``--xla_force_host_platform_device_count`` *before the first jax
import* — so ``--mesh 2`` works on single-CPU CI out of the box; on any
other platform the devices must exist, and a missing accelerator is an
error rather than a CPU run.  ``--rules {baseline,fsdp}`` picks the
weight-sharding rule set.

Without ``--smoke`` the model keeps its published widths and vocabulary
(the synthetic traffic draws its ids from the low end of it); ``--smoke``
shrinks both to the CPU-sized config and the 388-id synthetic vocabulary.
The persistent compilation cache lives in ``$JAX_COMPILATION_CACHE_DIR``
or ``<checkout>/.jax_cache/`` (:mod:`repro.launch.compile_cache`).

On a fleet the same entry point runs with the production mesh and
sharded weights (launch/steps.py `compress` + `decode` objectives are
the dry-run-proven lowerings of stages 1 and 2).
"""

from __future__ import annotations

import os
import sys


def _parse_mesh(spec: str):
    """"M" -> (1, M) model-parallel; "DxM" -> (data, model)."""
    parts = spec.lower().split("x")
    if len(parts) == 1:
        data, model = 1, int(parts[0])
    elif len(parts) == 2:
        data, model = int(parts[0]), int(parts[1])
    else:
        raise ValueError(f"bad mesh spec {spec!r}: use M or DxM")
    if data < 1 or model < 1:
        raise ValueError(f"bad mesh spec {spec!r}: axes must be >= 1")
    return data, model


def _mesh_device_fallback() -> None:
    """``--mesh N`` on the CPU needs N host devices, and the host-platform
    device count locks at the first jax import — so peek at argv *before*
    any jax import and force the placeholder topology when the operator
    has not set XLA_FLAGS themselves.  Only with ``JAX_PLATFORMS=cpu``
    set explicitly: elsewhere a failed accelerator start must not turn
    into a run over fake CPU devices."""
    if os.environ.get("JAX_PLATFORMS") != "cpu":
        return
    spec = None
    for i, arg in enumerate(sys.argv):
        if arg.startswith("--mesh="):
            spec = arg.split("=", 1)[1]
        elif arg == "--mesh" and i + 1 < len(sys.argv):
            spec = sys.argv[i + 1]
    if not spec:
        return
    try:
        data, model = _parse_mesh(spec)
    except ValueError:
        return  # let argparse report the malformed spec with context
    existing = os.environ.get("XLA_FLAGS", "")
    if data * model > 1 and \
            "--xla_force_host_platform_device_count" not in existing:
        # append rather than replace: unrelated XLA_FLAGS (fast-math etc.)
        # must survive; an operator-forced device count always wins
        os.environ["XLA_FLAGS"] = (existing + " " if existing else "") + \
            f"--xla_force_host_platform_device_count={data * model}"


_mesh_device_fallback()

import argparse  # noqa: E402  (the device fallback must precede jax)
import json
import time  # reprolint: ignore-file[wall-clock] -- the live server stamps real arrival/finish times; tests use VirtualClock

import jax.numpy as jnp
import numpy as np

from repro.configs import ARCH_IDS, get_config, get_smoke_config
from repro.core import memcom
from repro.data import (ICLTaskSpec, SyntheticVocab, build_manyshot_prompt,
                        make_episode, make_query)
from repro.launch.compile_cache import enable_compile_cache
from repro.models import transformer as tfm
from repro.serving import Request, ServingEngine, materialize_prefix
from repro.utils.pytree import tree_bytes


def main(argv=None):
    """Run the launcher on ``argv`` (default: the command line)."""
    # no prefix abbreviations: the pre-jax-import device-count fallback
    # scans argv for the literal --mesh, so an abbreviated --mes must be
    # rejected here rather than silently skip the forced topology
    ap = argparse.ArgumentParser(allow_abbrev=False)
    ap.add_argument("--arch", choices=ARCH_IDS, default="smollm-135m")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--tasks", type=int, default=2,
                    help="distinct compressed ICL tasks to serve in one batch")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--context-tokens", type=int, default=96)
    ap.add_argument("--classify", action="store_true",
                    help="serve ICL label queries instead of generation")
    ap.add_argument("--kv-layout", choices=("dense", "paged"), default="dense",
                    help="dense: per-slot cache stripes; paged: block-pool "
                         "cache where slots seated on the same compressed "
                         "task share its prefix blocks (copy-on-write)")
    ap.add_argument("--block-size", type=int, default=8,
                    help="tokens per physical KV block (paged layout only)")
    ap.add_argument("--num-blocks", type=int, default=None,
                    help="physical blocks in the paged pool (default: "
                         "slots+4 worst-case windows)")
    ap.add_argument("--prefix-capacity", type=int, default=None,
                    help="max HBM-resident compressed prefixes (LRU past "
                         "it; default unbounded)")
    ap.add_argument("--host-capacity", type=int, default=None,
                    help="enable the tiered prefix cache: HBM evictions "
                         "demote to a pinned-host tier holding up to N "
                         "prefixes (0 = demote straight to disk)")
    ap.add_argument("--disk-dir", default=None,
                    help="disk tier directory: host pressure spills "
                         "codec-compressed prefix shards here, and shards "
                         "from a previous run are promoted instead of "
                         "recompiled")
    ap.add_argument("--promote-budget", type=int, default=None,
                    help="max per-layer host->HBM chunks copied per "
                         "serve-loop iteration during a promotion "
                         "(default: whole prefix at once — decode stalls "
                         "for the full copy)")
    ap.add_argument("--raw-shots", action="store_true",
                    help="skip the offline compress stage: requests carry "
                         "their raw many-shot context and the engine "
                         "compiles each unseen task online, interleaved "
                         "with decode")
    ap.add_argument("--compile-budget", type=int, default=None,
                    help="max source tokens compiled per serve-loop "
                         "iteration (default: a whole task at once — "
                         "decode stalls for the full compile)")
    ap.add_argument("--stats", action="store_true",
                    help="print engine cache/compile counters after serving")
    ap.add_argument("--traffic", choices=("zipf", "onoff"), default=None,
                    help="serve a seeded synthetic workload instead of the "
                         "fixed batch: Zipf-popularity task catalog under "
                         "Poisson (zipf) or bursty ON-OFF (onoff) arrivals "
                         "on the engine's virtual clock")
    ap.add_argument("--priority-classes", type=int, default=1,
                    help="traffic mode: priority classes to draw requests "
                         "from (class 0 most urgent; >1 enables preemption "
                         "pressure)")
    ap.add_argument("--traffic-requests", type=int, default=32)
    ap.add_argument("--traffic-tasks", type=int, default=8,
                    help="catalog size; set above --prefix-capacity/"
                         "--host-capacity to make the tiers churn")
    ap.add_argument("--traffic-rate", type=float, default=200.0,
                    help="arrival rate in requests per simulated second")
    ap.add_argument("--zipf-alpha", type=float, default=1.1)
    ap.add_argument("--priority-aging", type=float, default=None,
                    help="seconds of queue wait per one-class priority "
                         "boost (anti-starvation; default off)")
    ap.add_argument("--slo-ttft", type=float, default=0.02,
                    help="traffic mode: TTFT SLO in simulated seconds")
    ap.add_argument("--autotune-budgets", action="store_true",
                    help="halve/double --compile-budget/--promote-budget "
                         "against the observed decode gap")
    ap.add_argument("--target-gap", type=float, default=2e-3,
                    help="decode-gap target (simulated s) for "
                         "--autotune-budgets")
    ap.add_argument("--seed", type=int, default=0,
                    help="traffic trace seed (same seed -> same workload "
                         "and, on the virtual clock, same metrics)")
    ap.add_argument("--fused-step", action=argparse.BooleanOptionalAction,
                    default=False,
                    help="fuse admission prefill chunks / compile chunks "
                         "into the batched decode dispatch (pure "
                         "attention/MLA archs): new requests join by "
                         "streaming their prompt through the decode step "
                         "instead of opening a prefill-sized decode gap")
    ap.add_argument("--fused-chunk-tokens", type=int, default=16,
                    help="prompt tokens a joining slot streams per fused "
                         "step (--fused-step)")
    ap.add_argument("--spec-draft", default=None, metavar="ARCH|self",
                    help="speculative decoding drafter: an arch id (its "
                         "smoke config drafts for the target) or 'self' "
                         "(the target drafts for itself — the acceptance "
                         "upper bound).  Needs --fused-step and --spec-k")
    ap.add_argument("--spec-k", type=int, default=0,
                    help="draft tokens proposed and verified per fused "
                         "step and slot (0 = speculative decoding off)")
    ap.add_argument("--mesh", default=None,
                    help="serve tensor-parallel: M (model-parallel ways) or "
                         "DxM (data x model); with JAX_PLATFORMS=cpu forces "
                         "the host device count so it runs anywhere")
    ap.add_argument("--rules", choices=("baseline", "fsdp"),
                    default="baseline",
                    help="weight-sharding rule set for --mesh (baseline: "
                         "tensor/expert parallel; fsdp: +embed over data)")
    ap.add_argument("--http-port", type=int, default=None, metavar="PORT",
                    help="serve the telemetry plane over HTTP while the "
                         "engine runs: GET /metrics (Prometheus text), "
                         "/healthz, /debug/state, /debug/trace on "
                         "127.0.0.1:PORT (0 = pick an ephemeral port, "
                         "printed at startup)")
    ap.add_argument("--http-linger", type=float, default=0.0, metavar="S",
                    help="keep the process (and --http-port server) alive "
                         "S seconds after serving finishes, so external "
                         "scrapers/smoke tests can curl the final state")
    ap.add_argument("--metrics", default=None)
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="record a full request-lifecycle trace and write "
                         "it as Chrome-trace/Perfetto JSON (open at "
                         "ui.perfetto.dev); on the virtual clock the file "
                         "is byte-identical for one (scenario, seed)")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="write the engine's MetricsRegistry in Prometheus "
                         "text exposition format after serving")
    ap.add_argument("--flight-recorder", type=int, default=None,
                    metavar="N",
                    help="bound the tracer's ring buffer to the last N "
                         "events (the flight recorder: dumped to "
                         "--trace-out on a crash); default keeps all")
    args = ap.parse_args(argv)
    if args.tasks < 1 or args.slots < 1 or args.requests < 1:
        ap.error("--tasks, --slots and --requests must all be >= 1")
    if args.block_size < 1:
        ap.error("--block-size must be >= 1")
    if args.compile_budget is not None and args.compile_budget < 1:
        ap.error("--compile-budget must be >= 1")
    if args.promote_budget is not None and args.promote_budget < 1:
        ap.error("--promote-budget must be >= 1")
    if args.host_capacity is not None and args.host_capacity < 0:
        ap.error("--host-capacity must be >= 0")
    if args.flight_recorder is not None and args.flight_recorder < 1:
        ap.error("--flight-recorder must be >= 1")
    if args.raw_shots and args.classify:
        ap.error("--raw-shots serves generation traffic (classify goes "
                 "through the offline seat path)")
    if args.traffic and (args.classify or args.raw_shots):
        ap.error("--traffic generates its own raw-shot requests (drop "
                 "--classify/--raw-shots)")
    if args.autotune_budgets and \
            args.compile_budget is None and args.promote_budget is None:
        ap.error("--autotune-budgets needs --compile-budget and/or "
                 "--promote-budget to tune")
    if (args.spec_k > 0) != (args.spec_draft is not None):
        ap.error("--spec-draft and --spec-k come together (both or neither)")
    if args.spec_k and not args.fused_step:
        ap.error("--spec-k rides the fused step: add --fused-step")
    if args.fused_chunk_tokens < 1:
        ap.error("--fused-chunk-tokens must be >= 1")
    if args.spec_draft is not None and args.spec_draft != "self" \
            and args.spec_draft not in ARCH_IDS:
        ap.error(f"--spec-draft must be 'self' or one of {ARCH_IDS}")
    if args.http_port is not None and args.http_port < 0:
        ap.error("--http-port must be >= 0 (0 picks an ephemeral port)")
    if args.http_linger < 0:
        ap.error("--http-linger must be >= 0")
    if args.http_linger and args.http_port is None:
        ap.error("--http-linger needs --http-port")

    enable_compile_cache()
    vocab = SyntheticVocab()
    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    if args.smoke:
        cfg = cfg.replace(vocab_size=vocab.size)
    if vocab.size > cfg.vocab_size:
        raise SystemExit(f"{cfg.name}: vocabulary of {cfg.vocab_size} ids "
                         f"cannot hold the {vocab.size} synthetic ids")
    if cfg.memcom is None:
        raise SystemExit(f"{args.arch}: attention-free — serve with the "
                         "native SSM state snapshot")
    m = cfg.memcom.num_memory_tokens

    mesh = rules = None
    if args.mesh:
        from repro.launch.mesh import make_serving_mesh
        from repro.sharding.rules import BASELINE_RULES, FSDP_RULES

        data, model = _parse_mesh(args.mesh)
        mesh = make_serving_mesh(model=model, data=data)
        rules = {"baseline": BASELINE_RULES, "fsdp": FSDP_RULES}[args.rules]
        print(f"[edge] tensor-parallel mesh {data}x{model} "
              f"(data x model), rules={args.rules}")

    print(f"[cloud] target {cfg.name} ({cfg.param_count()/1e6:.1f}M), "
          f"m={m} memory tokens, {args.tasks} task(s)")
    target, compressor = memcom.init_models(cfg, mesh, rules)

    rng = np.random.default_rng(0)
    paged_kw = {}
    if args.kv_layout == "paged":
        paged_kw = dict(block_size=args.block_size,
                        num_blocks=args.num_blocks)
    spec_draft = None
    if args.spec_k:
        if args.spec_draft == "self":
            spec_draft = "self"
            print(f"[edge] self-speculative decoding, k={args.spec_k}")
        else:
            dcfg = (get_smoke_config(args.spec_draft) if args.smoke
                    else get_config(args.spec_draft)).replace(
                        vocab_size=cfg.vocab_size)
            spec_draft = (dcfg, tfm.init_params(dcfg, 1))
            print(f"[edge] speculative decoding: drafter {dcfg.name} "
                  f"({dcfg.param_count()/1e6:.1f}M), k={args.spec_k}")
    clock = None
    if args.traffic:
        # traffic replays timed arrivals against a virtual clock: time
        # advances through the engine's work-cost model, so the SLO
        # numbers are simulated seconds, reproducible for one seed
        from repro.serving import VirtualClock

        clock = VirtualClock()
    tracer = None
    if args.trace_out or args.flight_recorder or args.http_port is not None:
        from repro.serving import Tracer

        # the tracer binds to the engine's clock at construction, so on
        # a --traffic run the spans sit on simulated time; --http-port
        # implies one so GET /debug/trace has a flight recorder to dump
        tracer = Tracer(capacity=args.flight_recorder,
                        dump_path=args.trace_out)
        print(f"[edge] tracing: flight recorder "
              f"{'unbounded' if args.flight_recorder is None else args.flight_recorder}"
              f" event(s)"
              + (f", dump -> {args.trace_out}" if args.trace_out else ""))
    registry = watchdog = None
    if args.traffic or args.http_port is not None:
        from repro.serving import MetricsRegistry

        registry = MetricsRegistry()
    if args.traffic:
        # SLO burn-rate watchdog over the virtual clock: alerts land as
        # tracer instants + serving_alerts_total counters (scrapeable
        # via --http-port /metrics), and the page-severity degradation
        # hook sheds lowest-priority admissions while active
        from repro.serving import ShedDegrade, SLOWatchdog, default_rules

        watchdog = SLOWatchdog(default_rules(slo_ttft_s=args.slo_ttft),
                               metrics=registry, tracer=tracer,
                               degrade_hook=ShedDegrade())
    engine = ServingEngine(cfg, target, slots=args.slots,
                           max_len=m + 24 + args.max_new + 16,
                           kv_layout=args.kv_layout,
                           compressor=(compressor
                                       if args.raw_shots or args.traffic
                                       else None),
                           compile_token_budget=args.compile_budget,
                           prefix_capacity=args.prefix_capacity,
                           host_capacity=args.host_capacity,
                           disk_dir=args.disk_dir,
                           promote_layer_budget=args.promote_budget,
                           mesh=mesh, rules=rules,
                           clock=clock,
                           priority_aging_s=args.priority_aging,
                           autotune_budgets=args.autotune_budgets,
                           target_decode_gap_s=(args.target_gap
                                                if args.autotune_budgets
                                                else None),
                           fused_step=args.fused_step,
                           fused_chunk_tokens=args.fused_chunk_tokens,
                           spec_draft=spec_draft, spec_k=args.spec_k,
                           tracer=tracer, metrics=registry,
                           watchdog=watchdog,
                           **paged_kw)
    http_server = None
    if args.http_port is not None:
        from repro.serving import TelemetryServer

        http_server = TelemetryServer(engine, port=args.http_port)
        port = http_server.start()
        print(f"[edge] http telemetry on 127.0.0.1:{port} "
              "(/metrics /healthz /debug/state /debug/trace)")
    if engine.tiers is not None:
        preloaded = engine.tiers.disk_names()
        print(f"[edge] tiered prefix cache: host capacity "
              f"{'unbounded' if args.host_capacity is None else args.host_capacity}"
              f", disk {args.disk_dir or '(none)'}"
              + (f", {len(preloaded)} shard(s) indexed from a previous run"
                 if preloaded else ""))

    tasks, payload = [], 0
    t0 = time.perf_counter()
    for t in range(0 if args.traffic else args.tasks):
        task = ICLTaskSpec(vocab, num_labels=8, keys_per_label=4)
        episode = make_episode(task, rng)
        prompt = build_manyshot_prompt(task, episode, rng,
                                       budget=args.context_tokens)
        if not args.raw_shots:  # stage 1: compress offline, register
            prefix, _ = memcom.compress(compressor, cfg,
                                        jnp.asarray(prompt[None]), mesh=mesh)
            kv = materialize_prefix(target, cfg, prefix)
            engine.add_prefix(f"task{t}", kv)
            payload += tree_bytes(kv)
        tasks.append((f"task{t}", task, episode, prompt))
    t_compress = time.perf_counter() - t0
    if args.traffic:
        pass  # the trace carries its own raw shots; no offline stage
    elif args.raw_shots:
        print(f"[edge] no offline stage: {args.tasks} task(s) will compile "
              f"online, {'whole-task' if args.compile_budget is None else str(args.compile_budget) + '-token'} "
              "chunks interleaved with decode")
    else:
        print(f"[cloud] compressed {args.tasks}x{args.context_tokens} tokens "
              f"-> {m} slots/layer each in {t_compress:.2f}s; "
              f"payload {payload/1e3:.1f} KB total")
    metrics = {"arch": cfg.name, "m": m, "tasks": args.tasks,
               "slots": args.slots, "context_tokens": args.context_tokens,
               "compress_s": t_compress, "payload_bytes": payload,
               "kv_layout": args.kv_layout, "raw_shots": args.raw_shots,
               "compile_budget": args.compile_budget,
               "prefix_capacity": args.prefix_capacity,
               "host_capacity": args.host_capacity,
               "disk_dir": args.disk_dir,
               "promote_budget": args.promote_budget,
               "mesh": args.mesh, "rules": args.rules if args.mesh else None,
               "fused_step": args.fused_step,
               "spec_draft": args.spec_draft, "spec_k": args.spec_k}
    if args.kv_layout == "paged":
        print(f"[edge] paged pool: {engine.alloc.num_blocks} blocks x "
              f"{engine.block_size} tokens, "
              f"{engine.alloc.used_count} resident after task registration")
        metrics.update(block_size=engine.block_size,
                       num_blocks=engine.alloc.num_blocks,
                       blocks_resident=engine.alloc.used_count)

    if args.traffic:
        from repro.serving import TrafficConfig, generate_trace, slo_metrics

        tcfg = TrafficConfig(
            num_tasks=args.traffic_tasks, zipf_alpha=args.zipf_alpha,
            context_tokens=args.context_tokens,
            num_requests=args.traffic_requests,
            process="poisson" if args.traffic == "zipf" else "onoff",
            rate_rps=args.traffic_rate,
            priority_classes=args.priority_classes)
        trace = generate_trace(tcfg, args.seed, vocab=vocab)
        print(f"[edge] traffic: {tcfg.num_requests} requests over "
              f"{tcfg.num_tasks} task(s), zipf {tcfg.zipf_alpha}, "
              f"{tcfg.process} arrivals @ {tcfg.rate_rps:.0f} r/s "
              f"(simulated), {tcfg.priority_classes} priority class(es), "
              f"seed {args.seed}")
        t0 = time.perf_counter()
        out = engine.serve(list(trace.requests))
        wall = time.perf_counter() - t0
        devices = 1
        if args.mesh:
            d_, m_ = _parse_mesh(args.mesh)
            devices = d_ * m_
        slo = slo_metrics(engine.request_log, slo_ttft_s=args.slo_ttft,
                          devices=devices, gap_samples=engine.gap_samples)
        generated = int(sum(len(v) for v in out.values()))
        print(f"[edge] {slo['completed']}/{slo['requests']} completed, "
              f"{generated} tokens in {slo['duration_s']*1e3:.1f} ms "
              f"simulated ({wall:.2f}s wall): TTFT p50 "
              f"{slo['ttft_p50_s']*1e3:.2f} / p99 "
              f"{slo['ttft_p99_s']*1e3:.2f} ms, goodput "
              f"{slo['goodput_rps']:.1f} r/s @ SLO "
              f"{args.slo_ttft*1e3:.0f} ms, "
              f"{slo['tokens_per_s_per_device']:.0f} tok/s/device, "
              f"decode-gap p99 {slo['decode_gap_p99_s']*1e3:.2f} ms, "
              f"{slo['preemptions']} preemption(s)")
        for cls, row in sorted(slo["per_class"].items()):
            print(f"[edge]   class {cls}: "
                  f"{row['completed']}/{row['requests']} done, TTFT p50 "
                  f"{row['ttft_p50_s']*1e3:.2f} ms, {row['slo_attained']} "
                  f"in SLO, {row['preemptions']} preempted")
        fires = sum(1 for e in watchdog.alert_log if e["kind"] == "fire")
        print(f"[edge] watchdog: {fires} alert fire(s), "
              f"{len(watchdog.alert_log) - fires} clear(s) over "
              f"{len(watchdog.rules)} burn-rate rule(s)")
        metrics["traffic"] = {
            "process": tcfg.process, "seed": args.seed,
            "traffic_tasks": tcfg.num_tasks, "rate_rps": tcfg.rate_rps,
            "zipf_alpha": tcfg.zipf_alpha,
            "priority_classes": tcfg.priority_classes,
            "wall_s": wall, "generated": generated,
            "alerts": watchdog.report(), **slo}
    elif args.classify:
        hits = 0
        t0 = time.perf_counter()
        for i in range(args.requests):
            name, task, episode, prompt = tasks[i % len(tasks)]
            engine.seat_prefix(0, name)
            q, label = make_query(task, episode, prompt, rng)
            pred = engine.score_labels(np.empty((0,), np.int32), q,
                                       vocab.label_ids())
            hits += int(pred - vocab.label_base == label)
        dt = time.perf_counter() - t0
        print(f"[edge] {args.requests} label queries in {dt:.2f}s "
              f"({hits}/{args.requests} correct — untrained compressor "
              f"unless loaded from a checkpoint)")
        metrics.update(queries=args.requests, correct=hits, serve_s=dt)
    else:
        # ragged prompts, round-robin over tasks, per-request stop budget;
        # with --raw-shots each request carries its task's many-shot
        # context and the first request per task triggers the (deduped)
        # online compile
        reqs = [
            Request(tokens=rng.integers(4, vocab.size,
                                        int(rng.integers(4, 12))),
                    max_new=args.max_new, prefix=tasks[i % len(tasks)][0],
                    raw_shots=(tasks[i % len(tasks)][3]
                               if args.raw_shots else None),
                    stop_token=None)
            for i in range(args.requests)
        ]
        t0 = time.perf_counter()
        out = engine.serve(reqs)
        dt = time.perf_counter() - t0
        generated = int(sum(len(v) for v in out.values()))
        tok_s = generated / dt
        print(f"[edge] served {args.requests} ragged requests "
              f"({args.tasks} compressed tasks, {args.slots} slots) in "
              f"{dt:.2f}s: {generated} tokens, {tok_s:.1f} tok/s, "
              f"attending to <= {m}+prompt slots/layer per request")
        metrics.update(requests=args.requests, generated=generated,
                       serve_s=dt, tokens_per_s=tok_s)
        if args.raw_shots:
            cs = engine.stats()["compiler"]
            print(f"[edge] online compile: {cs['jobs']} job(s), "
                  f"{cs['deduped']} deduped submit(s), {cs['chunks']} "
                  f"chunk(s) / {cs['tokens']} source tokens")
        if args.fused_step:
            es = engine.stats()["engine"]
            line = (f"[edge] fused: {es['fused_steps']} fused step(s), "
                    f"{es['fused_prefill_tokens']} prompt tokens streamed "
                    f"in {es['fused_prefill_chunks']} chunk(s), "
                    f"{es['fused_compile_chunks']} compile chunk(s) fused")
            if args.spec_k:
                line += (f"; speculative: {es['draft_accepted']}/"
                         f"{es['draft_proposed']} drafts accepted "
                         f"({es['accept_rate']:.0%})")
            print(line)

    if args.stats:
        stats = engine.stats()
        print("[stats]", json.dumps(stats, indent=1))
        metrics["stats"] = stats

    if args.trace_out:
        path = tracer.dump(args.trace_out)
        n = len(tracer.events())
        print(f"[edge] trace -> {path} ({n} event(s)"
              + (f", {tracer.dropped} dropped by the flight recorder"
                 if tracer.dropped else "") + ")")

    if args.metrics_out:
        parent = os.path.dirname(args.metrics_out)
        if parent:
            os.makedirs(parent, exist_ok=True)
        with open(args.metrics_out, "w") as f:
            f.write(engine.metrics.render_prometheus())
        print(f"[edge] prometheus metrics -> {args.metrics_out}")

    if args.metrics:
        with open(args.metrics, "w") as f:
            json.dump(metrics, f, indent=1)
        print(f"metrics -> {args.metrics}")

    if http_server is not None:
        if args.http_linger:
            print(f"[edge] http telemetry lingering {args.http_linger:g}s "
                  f"on 127.0.0.1:{http_server.bound_port}", flush=True)
            time.sleep(args.http_linger)
        http_server.stop()


if __name__ == "__main__":
    main()
