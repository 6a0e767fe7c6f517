"""Serving-cost benchmark: the paper's actual deliverable — decode cost
against a compressed m-slot cache vs the full t-token cache, plus a
continuous-batching scenario (two distinct compressed tasks, ragged
prompts, per-slot stop budgets, mid-stream slot refill) measuring the
multi-tenant serving shape end to end, an ``online_compile`` section
(cold-task time-to-first-token and the decode-throughput dip while a
compile is in flight, interleaved vs fully stalled), and a
``prefix_tiering`` section (time-to-first-token down the HBM → host →
disk → recompile ladder, and the decode dip while a demoted prefix
promotes back, interleaved vs stalled), and a ``traffic`` section
(seeded Zipf/Poisson load over a catalog exceeding cache capacity:
TTFT p50/p99, goodput, decode-gap p99 and tokens/s/device on a virtual
clock, fixed vs autotuned budgets — ``benchmarks/traffic.py``).

Measures (CPU wall-clock, informational) and reports the structural
ratios that transfer to TPU: per-step attended KV slots, cache bytes,
attention FLOPs.  The 32k-decode roofline cells in EXPERIMENTS.md §Perf
make the same comparison at production scale from the compiled dry-run.

``--smoke`` swaps the cached pretrained target for a random-init one and
shrinks the sweep — the CI-speed configuration that exercises the whole
serving path (GitHub Actions runs it on every push).
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile
import time  # reprolint: ignore-file[wall-clock] -- SLO bench measures real host latency; deterministic runs inject VirtualClock

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import common as C
from benchmarks.traffic import run_traffic
from repro.core import memcom
from repro.models import transformer as tfm
from repro.serving import Request, ServingEngine
from repro.serving.engine import materialize_prefix, write_prefix_to_cache
from repro.utils.pytree import tree_bytes


def run(ratio: int = 8, decode_steps: int = 16, smoke: bool = False,
        sharded: bool = True):
    import dataclasses

    if smoke:  # CI configuration: random target, no pretraining artifact
        cfg0 = C.target_config()
        target = tfm.init_params(cfg0, 0)
        decode_steps = 4
    else:
        cfg0, target = C.get_or_pretrain_target()
    m = C.RATIOS[ratio]
    cfg0 = cfg0.replace(
        memcom=dataclasses.replace(cfg0.memcom, num_memory_tokens=m))
    t = C.SOURCE_LEN
    B = 4
    rng = np.random.default_rng(0)
    source = jnp.asarray(rng.integers(4, cfg0.vocab_size, (B, t)), jnp.int32)

    def decode_loop(cache, start):
        @jax.jit
        def step(cache, tok, i):
            logits, aux = tfm.forward(target, cfg0, tokens=tok, cache=cache,
                                      cache_index=i, decode=True)
            return aux["cache"], jnp.argmax(logits[:, -1:], -1).astype(jnp.int32)

        tok = jnp.ones((B, 1), jnp.int32)
        cache, tok = step(cache, tok, start)  # compile
        jax.block_until_ready(tok)
        t0 = time.perf_counter()
        for i in range(decode_steps):
            cache, tok = step(cache, tok, start + 1 + i)
        jax.block_until_ready(tok)
        return (time.perf_counter() - t0) / decode_steps

    # vanilla: prefill t tokens, decode against t-slot cache
    full_cache = tfm.init_cache(cfg0, B, t + decode_steps + 2)
    _, aux = tfm.forward(target, cfg0, tokens=source, cache=full_cache,
                         cache_index=0)
    sec_full = decode_loop(aux["cache"], t)
    bytes_full = tree_bytes(aux["cache"])

    # compressed: m memory slots + decode window
    mc = memcom.init_memcom(cfg0, target, 1)
    prefix, _ = memcom.compress(mc, cfg0, source)
    kv = materialize_prefix(target, cfg0, prefix)
    small_cache = tfm.init_cache(cfg0, B, m + decode_steps + 2)
    small_cache = write_prefix_to_cache(cfg0, small_cache, kv)
    sec_comp = decode_loop(small_cache, m)
    bytes_comp = tree_bytes(small_cache)

    rows = [
        ("full-context", t, f"{sec_full*1e3:.2f}", f"{bytes_full/1e6:.2f}"),
        (f"memcom-{ratio}x", m, f"{sec_comp*1e3:.2f}", f"{bytes_comp/1e6:.2f}"),
    ]
    print("\n" + C.fmt_table(
        rows, ("serving path", "KV slots", "ms/token (CPU)", "cache MB")) + "\n")
    print(f"cache-bytes ratio: {bytes_full / bytes_comp:.2f}x "
          f"(structural, transfers to TPU)\n")

    cb = run_continuous_batching(cfg0, target, mc, m, rng,
                                 num_requests=4 if smoke else 8)
    pvd = run_paged_vs_dense(cfg0, target, mc, m, rng,
                             slot_counts=(1, 4) if smoke else (1, 4, 16),
                             decode_steps=4 if smoke else 8)
    fs = run_fused_spec(cfg0, target, mc, m, rng, smoke=smoke)
    oc = run_online_compile(cfg0, target, mc, m, rng,
                            warm_new=12 if smoke else 24)
    pt = run_prefix_tiering(cfg0, target, mc, m, rng,
                            warm_new=12 if smoke else 24)
    tr = run_traffic(cfg0, target, mc, m, rng, smoke=smoke)
    sd = run_sharded_decode(smoke) if sharded else None

    C.write_result("serving_bench", {
        "ratio": ratio, "m": m, "t": t,
        "ms_full": sec_full * 1e3, "ms_compressed": sec_comp * 1e3,
        "cache_bytes_full": bytes_full, "cache_bytes_compressed": bytes_comp,
        "continuous_batching": cb, "paged_vs_dense": pvd,
        "fused_spec": fs, "online_compile": oc, "prefix_tiering": pt,
        "traffic": tr, "sharded_decode": sd})
    return rows


def run_continuous_batching(cfg, target, mc, m, rng, *, slots=4,
                            num_requests=8):
    """Multi-tenant serving shape: two distinct compressed task prefixes
    seated per slot, ragged prompts, per-slot budgets forcing mid-stream
    refill.  Reports throughput and the admission/refill trace."""
    srcs = [jnp.asarray(rng.integers(4, cfg.vocab_size, (1, C.SOURCE_LEN)),
                        jnp.int32) for _ in range(2)]
    engine = ServingEngine(cfg, target, slots=slots, max_len=m + 48)
    for i, s in enumerate(srcs):
        prefix, _ = memcom.compress(mc, cfg, s)
        engine.add_prefix(f"task{i}", materialize_prefix(target, cfg, prefix))

    reqs = [
        Request(tokens=rng.integers(4, cfg.vocab_size,
                                    int(rng.integers(3, 13))),
                max_new=int(rng.integers(4, 10)),
                prefix=f"task{i % 2}")
        for i in range(num_requests)
    ]
    # warm every prefill bucket the ragged lengths (3..12) can hit, plus
    # the decode step (max_new=2: the first token comes from prefill, so
    # only the second forces a decode), so the timed region measures
    # serving not jit
    engine.serve([Request(tokens=np.arange(4, 8, dtype=np.int32), max_new=2,
                          prefix="task0"),
                  Request(tokens=np.arange(4, 13, dtype=np.int32), max_new=2,
                          prefix="task1")])
    t0 = time.perf_counter()
    out = engine.serve(reqs)
    dt = time.perf_counter() - t0
    generated = int(sum(len(v) for v in out.values()))
    ragged = sorted({len(r.tokens) for r in reqs})
    print(C.fmt_table(
        [(num_requests, 2, slots, ragged, generated, f"{generated/dt:.1f}")],
        ("requests", "tasks", "slots", "prompt lens", "tokens", "tok/s (CPU)"),
    ) + "\n")
    return {"requests": num_requests, "tasks": 2, "slots": slots,
            "generated": generated, "serve_s": dt,
            "tokens_per_s": generated / dt}


def _kv_leaf_bytes(cache):
    """Total bytes of the attention/MLA KV leaves of a Layerwise cache."""
    from repro.serving.prefix_store import _KV_KEYS

    total = 0
    for entry in cache.get("prefix", []):
        for key in _KV_KEYS:
            if key in entry:
                total += entry[key].size * entry[key].dtype.itemsize
    for entry in cache.get("period", {}).values():
        for key in _KV_KEYS:
            if key in entry:
                total += entry[key].size * entry[key].dtype.itemsize
    return total


def run_paged_vs_dense(cfg, target, mc, m, rng, *, slot_counts=(1, 4, 16),
                       decode_steps=8, block_size=8):
    """The paged refactor's headline: N slots seated on *one* compressed
    task.  Dense copies the m-token prefix into every slot's cache stripe
    (prefix memory O(slots)); paged stores it once in shared ref-counted
    blocks (O(tasks)) — the table reports prefix KV bytes, total KV bytes
    per slot, and the batched decode-step latency at each pool size."""
    src = jnp.asarray(rng.integers(4, cfg.vocab_size, (1, C.SOURCE_LEN)),
                      jnp.int32)
    kv = materialize_prefix(target, cfg, memcom.compress(mc, cfg, src)[0])
    prompt = rng.integers(4, cfg.vocab_size, 4).astype(np.int32)
    max_len = m + 24

    rows, out = [], {"block_size": block_size, "m": m,
                     "slot_counts": list(slot_counts), "dense": [], "paged": []}
    for slots in slot_counts:
        for layout in ("dense", "paged"):
            eng = ServingEngine(cfg, target, slots=slots, max_len=max_len,
                                kv_layout=layout,
                                **({"block_size": block_size}
                                   if layout == "paged" else {}))
            eng.add_prefix("task", kv)
            for s in range(slots):
                eng.seat_prefix(s, "task")
                eng._prefill_slot(s, prompt)
            # drive the decode step exactly as serve() does: lengths
            # advance each step and (paged) the active slots' tables grow
            # before the write position crosses into a new block
            lengths = eng.base + len(prompt)  # np, mutated in place
            active = range(slots)
            step = eng._decode_greedy

            def one_step(cache, ids):
                if layout == "paged":
                    eng._ensure_decode_blocks(active, lengths)
                    args = (jnp.asarray(eng.tables),)
                else:
                    args = ()
                ids, cache = step(eng.params, cache, ids,
                                  jnp.asarray(lengths, jnp.int32), *args)
                lengths[:] += 1
                return cache, ids

            tok = jnp.ones((slots, 1), jnp.int32)
            cache, ids = one_step(eng.cache, tok)  # compile, untimed
            jax.block_until_ready(ids)
            t0 = time.perf_counter()
            for _ in range(decode_steps):
                cache, ids = one_step(cache, ids[:, None])
            jax.block_until_ready(ids)
            ms_step = (time.perf_counter() - t0) / decode_steps * 1e3

            kv_total = _kv_leaf_bytes(eng.cache)
            if layout == "paged":
                # shared physical copy: the store's resident blocks
                block_bytes = kv_total // eng.alloc.num_blocks
                prefix_bytes = len(eng.store.blocks("task")) * block_bytes
                used_bytes = eng.alloc.used_count * block_bytes
            else:
                # one stripe per slot: every slot carries its own copy
                prefix_bytes = kv_total // max_len * m
                used_bytes = kv_total
            rows.append((layout, slots, f"{prefix_bytes/1e3:.1f}",
                         f"{used_bytes/1e3/slots:.1f}", f"{ms_step:.2f}"))
            out[layout].append({
                "slots": slots, "prefix_kv_bytes": int(prefix_bytes),
                "kv_bytes_per_slot": used_bytes / slots,
                "ms_per_decode_step": ms_step})

    print(C.fmt_table(rows, ("layout", "slots", "prefix KV (KB, all slots)",
                             "KV/slot (KB)", "ms/step (CPU)")) + "\n")
    d1, d16 = out["dense"][0], out["dense"][-1]
    p1, p16 = out["paged"][0], out["paged"][-1]
    print(f"prefix KV growth 1 -> {slot_counts[-1]} slots: "
          f"dense {d16['prefix_kv_bytes']/d1['prefix_kv_bytes']:.1f}x, "
          f"paged {p16['prefix_kv_bytes']/p1['prefix_kv_bytes']:.2f}x "
          "(shared blocks)\n")
    return out


def run_fused_spec(cfg, target, mc, m, rng, *, smoke=False):
    """The fused-step + speculative-decoding headline numbers.

    * **decode-gap p99 under churn** (virtual clock, so the numbers are
      work-model seconds, reproducible): staggered arrivals mix warm
      admissions and one cold raw-shot compile into a 2-slot engine.
      Unfused, every admission prefill and compile chunk lands *between*
      decode steps and widens the gap; fused, joins stream through the
      decode dispatch and compile chunks ride the same program, so the
      gap stays at the idle engine's (zero charged work between steps).
    * **tokens accepted per step** over the spec_k ladder: greedy
      no-prefix requests self-drafted (the acceptance upper bound) —
      each fused step verifies k drafts + 1, so tokens/step climbs
      toward k+1 while output stays token-identical to k=0.
    """
    from repro.serving import VirtualClock

    max_new = 6 if smoke else 12
    max_len = m + 32 + max_new
    shots_cold = rng.integers(4, cfg.vocab_size,
                              C.SOURCE_LEN).astype(np.int32)
    kv_warm = materialize_prefix(target, cfg, memcom.compress(
        mc, cfg, jnp.asarray(rng.integers(4, cfg.vocab_size,
                                          (1, C.SOURCE_LEN)), jnp.int32))[0])
    prompts = [rng.integers(4, cfg.vocab_size, int(n)).astype(np.int32)
               for n in (5, 9, 7, 11, 6, 8)]

    def churn_engine(fused):
        eng = ServingEngine(cfg, target, slots=2, max_len=max_len,
                            compressor=mc, compile_token_budget=16,
                            clock=VirtualClock(), fused_step=fused,
                            fused_chunk_tokens=8)
        eng.add_prefix("warm", kv_warm)
        return eng

    def churn_reqs():
        return [Request(tokens=p, max_new=max_new, arrival_s=0.0015 * i,
                        **({"prefix": "warm"} if i % 2 == 0 else
                           {"prefix": "cold", "raw_shots": shots_cold}))
                for i, p in enumerate(prompts)]

    # idle reference: slots-many warm requests, no mid-decode admission,
    # no compile — nothing is ever charged between decode steps
    idle = churn_engine(fused=False)
    idle.serve([Request(tokens=p, max_new=max_new, prefix="warm")
                for p in prompts[:2]])
    p99_idle = idle.stats()["engine"]["decode_gap_p99_s"]

    gap_rows, out = [], {"max_new": max_new,
                         "decode_gap_p99_idle_s": p99_idle}
    for fused in (False, True):
        eng = churn_engine(fused)
        eng.serve(churn_reqs())
        es = eng.stats()["engine"]
        key = "fused" if fused else "unfused"
        out[f"decode_gap_p99_{key}_s"] = es["decode_gap_p99_s"]
        out[f"churn_{key}"] = {
            k: es[k] for k in ("decode_steps", "fused_steps",
                               "fused_prefill_chunks", "fused_compile_chunks",
                               "decode_gap_max_s", "decode_gap_p99_s")}
        gap_rows.append((key, es["decode_steps"],
                         es["fused_prefill_chunks"],
                         es["fused_compile_chunks"],
                         f"{es['decode_gap_p99_s']*1e3:.3f}"))
    gap_rows.append(("idle", idle.stats()["engine"]["decode_steps"],
                     "-", "-", f"{p99_idle*1e3:.3f}"))
    print(C.fmt_table(gap_rows, ("engine (churn)", "decode steps",
                                 "prompt chunks fused",
                                 "compile chunks fused",
                                 "decode-gap p99 ms (virtual)")) + "\n")

    ladder_rows, ladder = [], []
    ref = None
    for k in (0, 1, 2, 4):
        kw = ({} if k == 0 else
              {"fused_step": True, "spec_draft": "self", "spec_k": k})
        eng = ServingEngine(cfg, target, slots=2, max_len=max_len, **kw)
        reqs = [Request(tokens=p, max_new=max_new) for p in prompts[:4]]
        res = eng.serve(reqs)
        toks = [list(map(int, res[r.uid])) for r in reqs]
        if k == 0:
            ref = toks
        es = eng.stats()["engine"]
        tps = es["tokens_generated"] / max(es["decode_steps"], 1)
        ladder.append({"k": k, "tokens_per_step": tps,
                       "accept_rate": es["accept_rate"],
                       "decode_steps": es["decode_steps"],
                       "identical": toks == ref})
        ladder_rows.append((k, es["decode_steps"], f"{tps:.2f}",
                            f"{es['accept_rate']:.0%}", toks == ref))
    print(C.fmt_table(ladder_rows, ("spec_k", "decode steps", "tokens/step",
                                    "accept rate", "== k=0 output")) + "\n")
    print(f"fused churn decode-gap p99 {out['decode_gap_p99_fused_s']*1e3:.3f}"
          f" ms vs idle {p99_idle*1e3:.3f} ms (unfused churn "
          f"{out['decode_gap_p99_unfused_s']*1e3:.3f} ms); self-drafted "
          f"greedy workload accepts >1 token/step from spec_k>=1\n")
    out["spec_ladder"] = ladder
    return out


def run_online_compile(cfg, target, mc, m, rng, *, compile_budget=16,
                       warm_new=24):
    """The online prefix compiler on the serving path.  Two measurements:

    * **time-to-first-token**, warm (prefix resident) vs cold (the
      request carries raw shots and the engine compiles them first);
    * **decode dip**: a warm slot decodes ``warm_new`` tokens while a
      cold task compiles — ``interleaved`` bounds *source-pass* work to
      ``compile_budget`` tokens between decode steps, ``stalled``
      compiles the whole task in one gap.  The per-engine decode-gap
      counters make the dip visible: the stalled run fits one decode
      step inside the whole compile where the interleaved run fits one
      per chunk, and the stalled max gap carries the full source pass
      where the interleaved max gap carries one chunk plus the finish
      pass (Memory-LLM + materialize — a single program in either mode,
      since it consumes *all* H^i at once; at toy scale it dominates
      both, so the gap ratio only opens up with the source length).
    """
    shots_warm = jnp.asarray(rng.integers(4, cfg.vocab_size,
                                          (1, C.SOURCE_LEN)), jnp.int32)
    shots_cold = rng.integers(4, cfg.vocab_size, C.SOURCE_LEN).astype(np.int32)
    kv_warm = materialize_prefix(
        target, cfg, memcom.compress(mc, cfg, shots_warm)[0])
    prompt = rng.integers(4, cfg.vocab_size, 4).astype(np.int32)

    def fresh_engine(budget):
        eng = ServingEngine(cfg, target, slots=2, max_len=m + 8 + warm_new + 8,
                            compressor=mc, compile_token_budget=budget)
        eng.add_prefix("warm", kv_warm)
        # untimed mirror of the measured workload (distinct shot content →
        # its own task): compiles the prefill/decode programs *and* this
        # budget's chunk/finish programs, so the timed run measures the
        # serving loop, not jit tracing
        warm_shots = rng.integers(4, cfg.vocab_size,
                                  C.SOURCE_LEN).astype(np.int32)
        eng.serve([Request(tokens=prompt, max_new=warm_new, prefix="warm"),
                   Request(tokens=prompt, max_new=2, raw_shots=warm_shots)])
        eng.reset_stats()
        return eng

    eng = fresh_engine(None)
    t0 = time.perf_counter()
    eng.serve([Request(tokens=prompt, max_new=1, prefix="warm")])
    ttft_warm = time.perf_counter() - t0
    t0 = time.perf_counter()
    eng.serve([Request(tokens=prompt, max_new=1, raw_shots=shots_cold)])
    ttft_cold = time.perf_counter() - t0

    out = {"compile_budget": compile_budget, "source_len": C.SOURCE_LEN,
           "ttft_warm_s": ttft_warm, "ttft_cold_s": ttft_cold}
    rows = [("ttft", "warm", f"{ttft_warm*1e3:.1f}", "-", "-"),
            ("ttft", "cold", f"{ttft_cold*1e3:.1f}", "-", "-")]
    for mode, budget in (("interleaved", compile_budget), ("stalled", None)):
        eng = fresh_engine(budget)
        reqs = [Request(tokens=prompt, max_new=warm_new, prefix="warm"),
                Request(tokens=prompt, max_new=2, raw_shots=shots_cold)]
        t0 = time.perf_counter()
        eng.serve(reqs)
        dt = time.perf_counter() - t0
        es = eng.stats()["engine"]
        gaps = max(es["decode_gaps"], 1)
        out[mode] = {
            "serve_s": dt,
            "decode_steps": es["decode_steps"],
            "decode_steps_during_compile": es["decode_steps_during_compile"],
            "decode_gap_max_s": es["decode_gap_max_s"],
            "decode_gap_mean_s": es["decode_gap_sum_s"] / gaps,
        }
        rows.append((mode, "warm+cold", f"{dt*1e3:.1f}",
                     f"{es['decode_gap_max_s']*1e3:.1f}",
                     es["decode_steps_during_compile"]))
    print(C.fmt_table(rows, ("section", "request", "total ms (CPU)",
                             "max decode gap ms", "decode during compile"))
          + "\n")
    print(f"decode steps inside the compile window: "
          f"{out['interleaved']['decode_steps_during_compile']} interleaved "
          f"vs {out['stalled']['decode_steps_during_compile']} stalled "
          "(stalled pays the whole source pass in one gap; the finish "
          "pass is one gap in both modes)\n")
    return out


def run_prefix_tiering(cfg, target, mc, m, rng, *, promote_budget=2,
                       warm_new=24):
    """The tiered prefix cache's headline numbers.  Two measurements:

    * **time-to-first-token by tier** — the same request served with its
      compressed prefix warm in HBM, demoted to the host tier, spilled
      to a disk shard, and (the tierless baseline) recompiled from raw
      shots.  The tier ladder is the point: every tier hit is a full
      online compile *avoided* — host/disk TTFT only pays promotion
      (a host→HBM copy, plus a shard read) where the recompile row pays
      the whole Source-LLM + Memory-LLM pass.
    * **decode dip during a promotion** — a warm slot decodes
      ``warm_new`` tokens while a cold prefix copies up.  ``interleaved``
      bounds the copy to ``promote_budget`` per-layer chunks between
      decode steps; ``stalled`` copies the whole row in one gap.  The
      decode-gap counters make the dip visible exactly as in the
      ``online_compile`` section.
    """
    import shutil
    import tempfile

    shots_warm = jnp.asarray(rng.integers(4, cfg.vocab_size,
                                          (1, C.SOURCE_LEN)), jnp.int32)
    shots_cold = rng.integers(4, cfg.vocab_size, C.SOURCE_LEN).astype(np.int32)
    kv_warm = materialize_prefix(
        target, cfg, memcom.compress(mc, cfg, shots_warm)[0])
    kv_b = materialize_prefix(target, cfg, memcom.compress(
        mc, cfg, jnp.asarray(rng.integers(4, cfg.vocab_size,
                                          (1, C.SOURCE_LEN)), jnp.int32))[0])
    prompt = rng.integers(4, cfg.vocab_size, 4).astype(np.int32)
    disk = tempfile.mkdtemp(prefix="prefix-tiering-")

    def fresh_engine(budget):
        eng = ServingEngine(cfg, target, slots=2,
                            max_len=m + 8 + warm_new + 8,
                            compressor=mc, compile_token_budget=16,
                            host_capacity=4, disk_dir=disk,
                            promote_layer_budget=budget)
        eng.add_prefix("task", kv_warm)
        # untimed warmup: compiles the prefill/decode programs and this
        # budget's chunk/finish programs (promotion itself jits nothing —
        # it is pure device_put traffic), so the timed serves measure the
        # tier machinery, not tracing
        warm_shots = rng.integers(4, cfg.vocab_size,
                                  C.SOURCE_LEN).astype(np.int32)
        eng.serve([Request(tokens=prompt, max_new=warm_new, prefix="task"),
                   Request(tokens=prompt, max_new=2, raw_shots=warm_shots)])
        # one untimed demote/promote cycle: first-transfer warmup (host→
        # device copies are lazily initialized) stays out of the ladder
        eng.store.demote("task")
        eng.serve([Request(tokens=prompt, max_new=1, prefix="task")])
        eng.reset_stats()
        return eng

    def ttft(eng, **req_kw):
        t0 = time.perf_counter()
        eng.serve([Request(tokens=prompt, max_new=1, **req_kw)])
        return time.perf_counter() - t0

    eng = fresh_engine(None)
    ttft_warm = ttft(eng, prefix="task")
    eng.store.demote("task")  # dense store: seated slots hold copies
    ttft_host = ttft(eng, prefix="task")
    eng.store.demote("task")
    eng.store.spill("task")
    ttft_disk = ttft(eng, prefix="task")
    ttft_recompile = ttft(eng, raw_shots=shots_cold)
    ts = eng.stats()["prefix_tiers"]

    out = {"promote_budget": promote_budget, "source_len": C.SOURCE_LEN,
           "ttft_warm_hbm_s": ttft_warm, "ttft_host_hit_s": ttft_host,
           "ttft_disk_hit_s": ttft_disk, "ttft_recompile_s": ttft_recompile,
           "tier_counters": ts}
    rows = [("ttft", "warm HBM", f"{ttft_warm*1e3:.1f}", "-", "-"),
            ("ttft", "host hit", f"{ttft_host*1e3:.1f}", "-", "-"),
            ("ttft", "disk hit", f"{ttft_disk*1e3:.1f}", "-", "-"),
            ("ttft", "recompile", f"{ttft_recompile*1e3:.1f}", "-", "-")]

    for mode, budget in (("interleaved", promote_budget), ("stalled", None)):
        eng = fresh_engine(budget)
        eng.add_prefix("cold", kv_b)
        eng.store.demote("cold")
        reqs = [Request(tokens=prompt, max_new=warm_new, prefix="task"),
                Request(tokens=prompt, max_new=2, prefix="cold")]
        t0 = time.perf_counter()
        eng.serve(reqs)
        dt = time.perf_counter() - t0
        es = eng.stats()["engine"]
        gaps = max(es["decode_gaps"], 1)
        out[mode] = {
            "serve_s": dt,
            "decode_steps": es["decode_steps"],
            "decode_steps_during_promote": es["decode_steps_during_promote"],
            "decode_gap_max_s": es["decode_gap_max_s"],
            "decode_gap_mean_s": es["decode_gap_sum_s"] / gaps,
            "promote_bytes": eng.stats()["prefix_tiers"]["promote_bytes"],
        }
        rows.append((mode, "warm+cold", f"{dt*1e3:.1f}",
                     f"{es['decode_gap_max_s']*1e3:.1f}",
                     es["decode_steps_during_promote"]))
    shutil.rmtree(disk, ignore_errors=True)

    print(C.fmt_table(rows, ("section", "request", "total ms (CPU)",
                             "max decode gap ms", "decode during promote"))
          + "\n")
    print(f"tier ladder TTFT (CPU ms): HBM {ttft_warm*1e3:.1f} -> host "
          f"{ttft_host*1e3:.1f} -> disk {ttft_disk*1e3:.1f} -> recompile "
          f"{ttft_recompile*1e3:.1f}; every tier hit is one online "
          "compile avoided\n")
    return out


def run_sharded_decode(smoke: bool, *, mesh_sizes=(1, 2, 4),
                       layouts=("dense", "paged")):
    """Per-step decode latency under tensor-parallel serving, dense and
    paged, at mesh sizes 1/2/4 — the structural check that the engine
    runs *unchanged* at every mesh size.

    Each cell is one ``repro.launch.serve --mesh N`` run.  On the TPU
    backend all cells run in this process over sub-meshes of the
    attached chips: a chip belongs to one process, and this one already
    holds them.  On the CPU each cell is a fresh subprocess, because the
    host-platform device count locks at the first jax init and every
    mesh size needs its own forced placeholder topology; there the
    absolute ms/step measures GSPMD partitioning overhead, not speedup
    (the "devices" share one core).
    """
    in_process = jax.default_backend() == "tpu"
    requests, max_new = (3, 4) if smoke else (6, 12)
    out, rows = {}, []
    for layout in layouts:
        cells = out.setdefault(layout, [])
        for n in mesh_sizes:
            fd, path = tempfile.mkstemp(suffix=".json")
            os.close(fd)
            argv = ["--arch", "smollm-135m", "--smoke",
                    "--requests", str(requests), "--tasks", "2",
                    "--slots", "2", "--max-new", str(max_new),
                    "--kv-layout", layout, "--mesh", str(n),
                    "--stats", "--metrics", path]
            try:
                if in_process:
                    from repro.launch import serve

                    serve.main(argv)
                else:
                    env = dict(os.environ, JAX_PLATFORMS="cpu",
                               XLA_FLAGS="--xla_force_host_platform_"
                                         f"device_count={n}")
                    res = subprocess.run(
                        [sys.executable, "-m", "repro.launch.serve", *argv],
                        capture_output=True, text=True, timeout=900, env=env)
                    if res.returncode != 0:
                        raise RuntimeError(
                            f"sharded_decode cell (mesh={n}, {layout}) "
                            "failed:\n" + res.stderr[-2000:])
                with open(path) as f:
                    metrics = json.load(f)
            finally:
                os.unlink(path)
            es = metrics["stats"]["engine"]
            steps = max(es["decode_steps"], 1)
            cell = {
                "mesh_model": n,
                "decode_steps": es["decode_steps"],
                "decode_time_s": es["decode_time_s"],
                "ms_per_step": es["decode_time_s"] / steps * 1e3,
                "serve_s": metrics["serve_s"],
                "tokens_per_s": metrics["tokens_per_s"],
            }
            cells.append(cell)
            rows.append((layout, f"1x{n}", es["decode_steps"],
                         f"{cell['ms_per_step']:.2f}"))
    print(C.fmt_table(
        rows, ("kv layout", "mesh (data x model)", "decode steps",
               f"ms/step ({jax.default_backend()})")) + "\n")
    if not in_process:
        print("sharded_decode: one subprocess per mesh size (device count "
              "locks at jax init); on a single physical CPU the forced "
              "devices share one core, so ms/step tracks partitioning "
              "overhead — the speedup column needs real devices\n")
    return out


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="random-init target + shrunk sweep (CI speed)")
    ap.add_argument("--ratio", type=int, default=8, choices=sorted(C.RATIOS))
    ap.add_argument("--no-sharded", action="store_true",
                    help="skip the sharded_decode subprocess sweep (the "
                         "tier-1 CI job passes this; the sharded-smoke job "
                         "runs the full set)")
    args = ap.parse_args()
    run(ratio=args.ratio, smoke=args.smoke, sharded=not args.no_sharded)
