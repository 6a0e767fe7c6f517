"""Continuous-batching serving engine over compressed caches.

Deployment story (paper §1: cloud compresses offline, edge serves):

1. ``core.compress`` produces per-layer O^i once, offline, per ICL task.
2. :func:`~repro.serving.prefix_store.materialize_prefix` pushes O^i
   through the frozen target's K/V (or MLA latent) projections → a
   compressed KV cache of m slots (mamba layers keep their handed-off
   state).  A :class:`~repro.serving.prefix_store.PrefixStore` caches one
   such prefix per task.
3. :class:`ServingEngine` runs a fixed pool of batch slots.  Each request
   names the compressed task memory it wants; the engine seats that
   prefix into the request's slot, prefills the prompt *behind it*, and
   decodes.  Slots are fully independent:

   * **ragged admission** — prompts of any length enter whichever slot is
     free; prefill is per-slot (padded to a few static buckets, so no
     recompilation) while decode stays one batched step;
   * **per-slot masking** — every step attends to that slot's own
     ``base_len + tokens_consumed`` cache region only (a (slots,) length
     vector threaded down to :func:`repro.kernels.ops.decode_attention`),
     so two tasks seated in neighbouring slots can never cross-attend;
   * **per-slot stop** — a slot finishing (its stop token or its budget)
     frees immediately and the scheduler refills it mid-decode.

Two KV layouts (``kv_layout=``):

* ``dense`` — per-slot ``(slots, max_len, …)`` cache stripes; seating
  copies the prefix into the slot's rows (prefix memory O(slots)).
* ``paged`` — one ``(num_blocks, block_size, …)`` physical pool per
  layer plus per-slot block tables; slots seated on the same task share
  its ref-counted prefix blocks (prefix memory O(tasks)), with
  copy-on-write only for a partially-filled tail block, private blocks
  freed on refill, and admission gated on free blocks.  Where the pool
  lives on an accelerator the step programs take the cache donated and
  update the pool in place.

With ``host_capacity=``/``disk_dir=`` set, the HBM store is fronted by
a :class:`~repro.serving.tiers.TieredPrefixStore`: evictions demote the
compressed prefix to pinned host RAM (and under host pressure to disk)
instead of destroying it, and a request naming a cold prefix parks
``waiting_on_prefix`` while the row is promoted back host→HBM in
``promote_layer_budget``-chunk steps interleaved with decode — the same
stay-responsive contract as online compilation.

Scheduling under load (the traffic harness, ``serving/traffic.py``):

* ``Request.priority`` classes (lower = more urgent) with an optional
  anti-starvation aging rule (``priority_aging_s=``), FIFO within class;
* **preemption** — when the best queued request's class outranks a
  running slot's and it cannot be admitted, the worst victim slot is
  evicted: its paged blocks are released (the prefix itself stays
  store-resident and demotes through the normal tier path under
  pressure), the request re-queues at its arrival position, and on
  re-admission the engine re-prefills ``prompt + already-emitted`` so
  decode resumes token-exact — the same machinery as a mid-decode refill;
* ``Request.arrival_s`` replays a timed trace: serve() holds each
  request until the engine clock reaches its offset;
* an injected ``clock=`` (see :class:`~repro.serving.clock.VirtualClock`)
  makes every timing — arrivals, TTFT, decode gaps, aging, the budget
  autotuner — a deterministic function of the work performed, so the
  whole simulation is reproducible in CI; the default is wall time;
* ``autotune_budgets=`` trades ``compile_token_budget`` /
  ``promote_layer_budget`` against the observed decode gap: budgets are
  halved while the mean gap overshoots ``target_decode_gap_s`` and
  doubled back (capped at 8× the configured value) while it undershoots.

Fused step (``fused_step=True``, pure attention/MLA layouts): one jitted
program per bucketed lane width carries every seated slot's decode lane
*plus* one bounded token chunk — a joining request's prompt streaming in
``fused_chunk_tokens``-sized pieces, or a :class:`PrefixCompiler` compile
chunk — so admission and compile churn never open a decode gap.  With
``spec_draft=``/``spec_k=`` the same lanes carry speculative decoding: a
greedy drafter proposes k tokens per slot, the fused step scores k+1
positions at once, and acceptance (greedy prefix match, or Leviathan
residual sampling on the request's own rng stream) rolls the per-slot
length vector forward — rejection is an implicit KV rollback in both
layouts.  See docs/ARCHITECTURE.md §"Fused step & speculative decoding".

See docs/ARCHITECTURE.md for the cache layouts and scheduling design.
"""

from __future__ import annotations

import copy
import dataclasses
import time
from collections import OrderedDict
from typing import Dict, Iterable, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import ModelConfig
from repro.core import memcom
from repro.kernels import ops
from repro.models import transformer as tfm
from repro.sharding import rules as sharding_rules
from repro.sharding.serving import constrain_cache, shard_cache
from repro.serving.block_pool import (
    TRASH_BLOCK,
    BlockAllocator,
    OutOfBlocksError,
)
from repro.serving.compiler import PrefixCompiler, pow2_bucket
from repro.serving.prefix_store import (  # re-exported for compatibility
    _KV_KEYS,
    PagedPrefixStore,
    PrefixSeatedError,
    PrefixStore,
    _map_rowwise,
    clear_slot_state,
    copy_paged_block,
    materialize_prefix,
    seat_prefix_row,
    write_prefix_to_cache,
)
from repro.serving.scheduler import Request, Scheduler
from repro.serving.telemetry import (
    NULL_TRACER,
    MetricGroup,
    MetricsRegistry,
    Tracer,
)
from repro.serving.tiers import TieredPrefixStore

__all__ = [
    "ServingEngine", "PrefixStore", "PagedPrefixStore", "PrefixCompiler",
    "Request", "Scheduler", "TieredPrefixStore", "materialize_prefix",
    "write_prefix_to_cache", "Tracer", "MetricsRegistry",
]


def _slice_slot(cache, slot):
    """View one batch slot of a Layerwise cache (keeps a size-1 batch dim)."""
    def f(c, _p, axis):
        return {k: jax.lax.dynamic_slice_in_dim(x, slot, 1, axis)
                for k, x in c.items()}
    return _map_rowwise(cache, None, f)


def _merge_slot(cache, row, slot):
    """Write a size-1-batch cache back into slot ``slot``."""
    def f(c, p, axis):
        return {k: jax.lax.dynamic_update_slice_in_dim(
            c[k], p[k].astype(c[k].dtype), slot, axis) for k in c}
    return _map_rowwise(cache, row, f)


def _slice_slot_paged(cache, slot):
    """Paged prefill view: per-slot leaves (conv/ssm/cross) sliced to a
    size-1 batch; pooled KV leaves pass through whole — the pool is global
    and the block-table row scopes the write to this slot's blocks."""
    def f(c, _p, axis):
        return {k: x if k in _KV_KEYS
                else jax.lax.dynamic_slice_in_dim(x, slot, 1, axis)
                for k, x in c.items()}
    return _map_rowwise(cache, None, f)


def _merge_slot_paged(cache, new, slot):
    """Merge a paged batch-1 prefill result back: pooled leaves are taken
    wholesale (the scatter already landed in the right blocks), per-slot
    leaves land back in their slot row."""
    def f(c, p, axis):
        return {k: p[k] if k in _KV_KEYS
                else jax.lax.dynamic_update_slice_in_dim(
                    c[k], p[k].astype(c[k].dtype), slot, axis)
                for k in c}
    return _map_rowwise(cache, new, f)


def _bucket(n: int, cap: int) -> int:
    """Static prefill widths: next power of two (min 8), clamped to the
    slot's remaining cache space.  A handful of buckets ⇒ a handful of
    prefill compilations, ever."""
    return max(1, min(pow2_bucket(n, 8), cap))


def _donates_cache(kv_layout: str, mesh) -> bool:
    """Do the step programs take the cache donated?  Donation lets a step
    update the paged pool in place (the layer scan carries it whole, see
    ``transformer.forward``), so no step allocates or copies a second
    pool: the paged layout donates wherever its pool lives on an
    accelerator.  On the host CPU the steps keep the functional contract,
    so a caller there may hold on to a cache it passed in.  The dense
    layout's stripes still ride the layer scan as xs/ys, where donation
    would only add a copy of the whole stack."""
    device = mesh.devices.flat[0] if mesh is not None else jax.devices()[0]
    return kv_layout == "paged" and device.platform != "cpu"


def _lane_capable(cfg: ModelConfig) -> bool:
    """Can this architecture absorb garbage decode lanes?  The fused step
    (and the drafter's masked decode) pad every slot to a shared lane
    width W and rely on (a) valid-masked KV scatters and (b) per-lane
    causal masking to make the padding invisible.  Recurrent mixers break
    (a)/(b) — the SSM state advances over garbage lanes — and
    cross-attention/encoder stacks have non-causal reads, so the fused
    path is gated to pure attention/MLA layouts."""
    descs = list(cfg.layout.prefix) + list(cfg.layout.period)
    return (cfg.encoder is None
            and all(d.mixer in ("attn", "mla") for d in descs)
            and not any(d.cross_attn for d in descs))


class ServingEngine:
    def __init__(self, cfg: ModelConfig, target_params, *, slots: int,
                 max_len: int, impl: str = "auto",
                 prefix_store: Optional[PrefixStore] = None,
                 kv_layout: str = "dense", block_size: int = 8,
                 num_blocks: Optional[int] = None,
                 prefix_capacity: Optional[int] = None,
                 compressor=None,
                 compile_token_budget: Optional[int] = None,
                 host_capacity: Optional[int] = None,
                 disk_dir: Optional[str] = None,
                 promote_layer_budget: Optional[int] = None,
                 mesh=None, rules=None,
                 clock=None, priority_aging_s: Optional[float] = None,
                 preemption: bool = True,
                 autotune_budgets: bool = False,
                 target_decode_gap_s: Optional[float] = None,
                 autotune_interval: int = 16,
                 fused_step: bool = False,
                 fused_chunk_tokens: int = 16,
                 spec_draft=None, spec_k: int = 0,
                 tracer: Optional[Tracer] = None,
                 metrics: Optional[MetricsRegistry] = None,
                 watchdog=None):
        if kv_layout not in ("dense", "paged"):
            raise ValueError(f"kv_layout must be dense or paged, got "
                             f"{kv_layout!r}")
        if compile_token_budget is not None and compile_token_budget < 1:
            raise ValueError("compile_token_budget must be >= 1 (or None)")
        if promote_layer_budget is not None and promote_layer_budget < 1:
            raise ValueError("promote_layer_budget must be >= 1 (or None)")
        if autotune_budgets:
            if target_decode_gap_s is None or target_decode_gap_s <= 0:
                raise ValueError("autotune_budgets needs a positive "
                                 "target_decode_gap_s")
            if compile_token_budget is None and promote_layer_budget is None:
                raise ValueError("autotune_budgets needs at least one of "
                                 "compile_token_budget/promote_layer_budget")
            if autotune_interval < 1:
                raise ValueError("autotune_interval must be >= 1")
        if fused_chunk_tokens < 1:
            raise ValueError("fused_chunk_tokens must be >= 1")
        if spec_k < 0:
            raise ValueError("spec_k must be >= 0")
        if (spec_k > 0) != (spec_draft is not None):
            raise ValueError("speculative decoding needs both spec_draft "
                             "and spec_k >= 1 (or neither)")
        if spec_k > 0 and not fused_step:
            raise ValueError("speculative decoding rides the fused step — "
                             "pass fused_step=True with spec_k")
        if (fused_step or spec_k) and not _lane_capable(cfg):
            raise ValueError(
                f"{cfg.name}: fused_step/speculative decoding need a pure "
                "attention/MLA layout — recurrent (mamba), cross-attention "
                "and encoder stacks cannot absorb masked garbage lanes")
        # injected clock (VirtualClock in tests/simulation, wall time in
        # production).  charge()/advance_to() are duck-typed: absent on a
        # wall clock, charging is a no-op and waits become short sleeps.
        self.clock = clock if clock is not None else time.perf_counter
        charge = getattr(self.clock, "charge", None)
        self._charge = charge if charge is not None else (lambda *_: None)
        # telemetry: a no-op tracer by default (bit-exact serving, near-
        # zero cost) and a fresh registry unless the caller shares one.
        # The tracer reads the *engine's* clock so spans line up with
        # request_log / gap samples on the same timeline.
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        self.tracer = tracer if tracer is not None else NULL_TRACER
        if self.tracer.enabled and self.tracer.clock is None:
            self.tracer.clock = self.clock
        attach = getattr(self.clock, "attach_metrics", None)
        if attach is not None:
            attach(self.metrics)  # charged-seconds counters by work kind
        # SLO burn-rate watchdog (opt-in): fed from the TTFT/gap observe
        # sites and stepped once per loop iteration.  Its degradation
        # hook may set shed_floor (admission shedding) / degrade_hint
        # (autotuner pressure) while a page alert is active.
        self.watchdog = watchdog
        self.shed_floor: Optional[int] = None
        self.degrade_hint = False
        self.last_step_t: Optional[float] = None  # /healthz liveness
        if watchdog is not None:
            if watchdog.clock is None:
                watchdog.clock = self.clock
            watchdog.attach_engine(self)
        self.priority_aging_s = priority_aging_s
        self.preemption = preemption
        self._autotune = autotune_budgets
        self.target_decode_gap_s = target_decode_gap_s
        self.autotune_interval = autotune_interval
        self._budget_init = (compile_token_budget, promote_layer_budget)
        self._gap_samples: List[float] = []  # every decode gap (p50/p99)
        self._gap_window: List[float] = []   # gaps since last autotune step
        self.request_log: Dict[int, dict] = {}  # per-request SLO timings
        if cfg.moe is not None and cfg.moe.capacity_factor is not None:
            # serving routes dropless: every program (compress, prefill,
            # decode) sizes each held expert's buffer for all its tokens,
            # so no request's output depends on its batch-mates' routing
            cfg = cfg.replace(moe=dataclasses.replace(cfg.moe,
                                                      capacity_factor=None))
        self.cfg = cfg
        self.slots = slots
        self.max_len = max_len
        self.impl = impl
        self.kv_layout = kv_layout
        # tensor-parallel serving: target and compressor params placed via
        # their logical-axis trees, KV caches/pools split by head over the
        # mesh "model" axis, block tables and per-slot lengths replicated
        # host-side — the python control plane (scheduler, allocator,
        # stores) is mesh-oblivious by construction
        self.mesh = mesh
        self.rules = None
        if mesh is not None:
            self.rules = rules if rules is not None else \
                sharding_rules.BASELINE_RULES
            target_params = jax.device_put(
                target_params,
                sharding_rules.logical_to_shardings(
                    target_params, tfm.param_specs(cfg), mesh, self.rules))
            if compressor is not None:
                compressor = jax.device_put(
                    compressor,
                    sharding_rules.logical_to_shardings(
                        compressor, memcom.memcom_axes(cfg), mesh,
                        self.rules))
        elif rules is not None:
            raise ValueError("rules given without a mesh")
        self.params = target_params
        # online prefix compiler: requests carrying raw_shots compile their
        # compressed prefix *on the serving path*, at most
        # compile_token_budget source tokens per loop iteration (None =
        # whole task at once — decode stalls for the full compile)
        self.compile_token_budget = compile_token_budget
        self.compiler = (PrefixCompiler(compressor, cfg, self.params,
                                        impl=impl, mesh=mesh,
                                        rules=self.rules)
                         if compressor is not None else None)
        if self.compiler is not None:
            self.compiler.stats = self.metrics.group(
                "serving_compiler", self.compiler.stats,
                help="online prefix compiler counter")
        # the counter "dict" is a registry-backed MetricGroup: every
        # `self._counters[k] += 1` site lands in a `serving_engine_*`
        # gauge, stats() stays a view over the registry, and the
        # Prometheus renderer sees live values
        self._counters = self.metrics.group("serving_engine", {
            "decode_steps": 0, "prefills": 0, "tokens_generated": 0,
            "decode_steps_during_compile": 0, "compile_chunks_interleaved": 0,
            "decode_steps_during_promote": 0, "promote_steps_interleaved": 0,
            "decode_gap_max_s": 0.0, "decode_gap_sum_s": 0.0,
            "decode_gaps": 0, "decode_time_s": 0.0,
            "preemptions": 0, "preempted_tokens_refilled": 0,
            "autotune_shrinks": 0, "autotune_grows": 0,
            # fused step: decode + chunk work in one dispatch
            "fused_steps": 0, "fused_chunks": 0,
            "fused_prefill_chunks": 0, "fused_prefill_tokens": 0,
            "fused_compile_chunks": 0,
            # speculative decoding
            "spec_rounds": 0, "draft_proposed": 0, "draft_accepted": 0,
        }, help="engine loop counter")
        # MoE dispatch, summed from the row counts each MoE step program
        # returns with its output: the real tokens' pairs routed to a
        # held expert and those whose expert is held elsewhere, rows the
        # grouped matmuls computed beyond the routed ones (tile padding,
        # bucket padding and idle slots), grouped-matmul kernel calls,
        # and (layer, held expert) pairs that got at least one row
        self._moe_counters = (self.metrics.group("serving_moe", {
            "routed_rows": 0, "absent_rows": 0, "padded_rows": 0,
            "gmm_calls": 0, "expert_hits": 0,
        }, help="MoE dispatch counter") if any(
            d.mlp == "moe" for d in cfg.layout.descriptors()) else None)
        self._gmm_kernel = ops.gmm_uses_kernel(impl)
        self._m_gap = self.metrics.histogram(
            "serving_decode_gap_seconds",
            "non-decode time between consecutive decode steps")
        self._m_ttft = self.metrics.histogram(
            "serving_ttft_seconds", "arrival to first token",
            labelnames=("priority",))
        self._m_latency = self.metrics.histogram(
            "serving_request_latency_seconds", "arrival to finish",
            labelnames=("priority",))
        self._m_jit = self.metrics.counter(
            "serving_jit_compiles_total",
            "jitted-program builds by step-function family",
            labelnames=("family",))
        self.base = np.zeros((slots,), np.int64)  # per-slot seated memory
        self.base_len = 0  # batch-wide seat_compressed() compat
        self._seated: List[Optional[str]] = [None] * slots  # named prefix
        self._dirty = np.zeros((slots,), bool)  # slot used since seating
        # recurrent layers can't absorb right-padding (the state would
        # advance over pad tokens), so prefill exact lengths for them
        descs = list(cfg.layout.prefix) + list(cfg.layout.period)
        self._recurrent = any(d.mixer == "mamba" for d in descs)
        self._pad_prefill = not self._recurrent

        if kv_layout == "paged":
            if prefix_store is not None:
                raise ValueError(
                    "paged engines own their PagedPrefixStore (its blocks "
                    "live in the engine's pool); pass prefix_capacity instead")
            table_width = -(-max_len // block_size)
            if num_blocks is None:
                # every slot's worst case, headroom for 4 resident task
                # prefixes, plus the reserved trash block
                num_blocks = 1 + (slots + 4) * table_width
            self.block_size = block_size
            self.alloc = BlockAllocator(num_blocks, block_size)
            self.cache = tfm.init_paged_cache(cfg, num_blocks, block_size,
                                              slots)
            self.tables = np.full((slots, table_width), TRASH_BLOCK, np.int32)
            self._slot_blocks: List[List[int]] = [[] for _ in range(slots)]
            # blocks promised to admitted-but-unfinished requests: decode
            # allocations draw them down; _can_admit nets them off the
            # free count so concurrent slots can't race the pool empty
            self._reserved = np.zeros((slots,), np.int64)
            self._reserved_pending = 0  # admitted, not yet prefilled
            self.store = PagedPrefixStore(cfg, self.alloc,
                                          capacity=prefix_capacity)
        else:
            self.cache = tfm.init_cache(cfg, slots, max_len)
            self.store = (prefix_store if prefix_store is not None
                          else PrefixStore(cfg, capacity=prefix_capacity))
        # adopt the HBM store's hit/miss counters into the registry
        # *before* a TieredPrefixStore fronts it — the tiered facade's
        # `stats` property delegates to this same dict
        if not isinstance(self.store.stats, MetricGroup):
            self.store.stats = self.metrics.group(
                "serving_prefix_store", self.store.stats,
                help="HBM prefix store counter")
        # tiered prefix cache: with a host and/or disk tier configured,
        # the HBM store is fronted by a TieredPrefixStore — evictions
        # demote down the hierarchy instead of dropping, and cold
        # prefixes promote back asynchronously (budgeted per decode step)
        self.promote_layer_budget = promote_layer_budget
        self.tiers: Optional[TieredPrefixStore] = None
        if host_capacity is not None or disk_dir is not None:
            self.store = self.tiers = TieredPrefixStore(
                self.store, host_capacity=host_capacity, disk_dir=disk_dir,
                mesh=mesh, rules=self.rules, cache_ref=lambda: self.cache)
            self.tiers.tier_stats = self.metrics.group(
                "serving_prefix_tiers", self.tiers.tier_stats,
                help="tiered prefix cache counter")
        # KV stripes/pools split by head on the "model" axis (a paged
        # pool's lanes by whole heads), recurrent state by channel/head;
        # everything non-divisible replicates
        pool_rows = ((cfg.num_kv_heads, cfg.hd) if kv_layout == "paged"
                     else None)
        self.cache = shard_cache(self.cache, mesh, self.rules, pool_rows)
        rules = self.rules

        def pin(cache):
            # hold the step *outputs* to the seeded cache layout — left to
            # itself GSPMD drifts (e.g. re-sharding KV on head_dim), and
            # every later step then pays a reshard of the whole pool
            return constrain_cache(cache, mesh, rules, pool_rows)

        def out(logits, aux):
            # MoE stacks return the held experts' row counts beside the
            # logits, fetched with them (stats()["moe"])
            if aux["moe_rows"] is None:
                return logits
            return logits, aux["moe_rows"]

        def prefill_fn(params, cache, tokens, slot, base):
            row = _slice_slot(cache, slot)
            logits, aux = tfm.forward(
                params, cfg, tokens=tokens, cache=row, cache_index=base,
                mask_offset=base, mesh=mesh, impl=impl)
            return (out(logits[0], aux),
                    pin(_merge_slot(cache, aux["cache"], slot)))

        def paged_prefill_fn(params, cache, tokens, slot, table_row, base):
            row = _slice_slot_paged(cache, slot)
            logits, aux = tfm.forward(
                params, cfg, tokens=tokens, cache=row, cache_index=base,
                mask_offset=base, block_tables=table_row[None, :], mesh=mesh,
                impl=impl)
            return (out(logits[0], aux),
                    pin(_merge_slot_paged(cache, aux["cache"], slot)))

        def decode_fn(params, cache, tok, lengths):
            logits, aux = tfm.forward(
                params, cfg, tokens=tok, cache=cache, cache_index=lengths,
                decode=True, mesh=mesh, impl=impl)
            return out(logits[:, -1], aux), pin(aux["cache"])

        def paged_decode_fn(params, cache, tok, lengths, tables):
            logits, aux = tfm.forward(
                params, cfg, tokens=tok, cache=cache, cache_index=lengths,
                decode=True, block_tables=tables, mesh=mesh, impl=impl)
            return out(logits[:, -1], aux), pin(aux["cache"])

        def greedy(step):
            def fn(params, cache, tok, lengths, *rest):
                logits, new_cache = step(params, cache, tok, lengths, *rest)
                if isinstance(logits, tuple):  # MoE: the row counts follow
                    logits, rows = logits
                    ids = jnp.argmax(logits, -1).astype(jnp.int32)
                    return jnp.concatenate([ids, rows.reshape(-1)]), new_cache
                # argmax on device: ship (slots,) ids, not (slots, vocab)
                return jnp.argmax(logits, -1).astype(jnp.int32), new_cache
            # runs under the step's own name (jit_paged_decode_fn), which
            # is how a profiler trace tells the decode program apart
            fn.__name__ = fn.__qualname__ = step.__name__
            return fn

        self.donate_cache = _donates_cache(kv_layout, mesh)
        donate = (1,) if self.donate_cache else ()
        # base is static: prefill-continuation slices the seated cache
        # region with a python int (one trace per (bucket, base) pair);
        # slot, lengths and block tables are traced, so admission/refill
        # (and block re-mapping) never recompile
        if kv_layout == "paged":
            prefill, decode, base_arg = paged_prefill_fn, paged_decode_fn, 5
        else:
            prefill, decode, base_arg = prefill_fn, decode_fn, 4
        self._prefill = jax.jit(prefill, static_argnums=(base_arg,),
                                donate_argnums=donate)
        # one-shot scoring (persist=False) must leave the cache it reads
        self._prefill_keep = (jax.jit(prefill, static_argnums=(base_arg,))
                              if donate else self._prefill)
        self._decode = jax.jit(decode, donate_argnums=donate)
        self._decode_greedy = jax.jit(greedy(decode), donate_argnums=donate)
        self._pin = pin

        # ---- fused step + speculative decoding ----
        # One jitted program family carries the batched decode lanes PLUS
        # an optional bounded token chunk (a joining slot's prefill, or a
        # PrefixCompiler compile chunk) in a single dispatch.  Lane widths
        # are pow2-bucketed so the program ladder stays small; the ladder
        # is observable through stats()["engine"]["jit_compiles"].
        self.fused = bool(fused_step)
        self.fused_chunk_tokens = int(fused_chunk_tokens)
        self._joining: "OrderedDict[int, dict]" = OrderedDict()
        self._programs: "OrderedDict[Tuple, object]" = OrderedDict()
        self._program_cap = 128  # LRU: evicting forces a later re-jit
        # per-family program-build counts (bucketed geometry keys).  These
        # are engine-lifetime — reset_stats() leaves them alone so the
        # bench/traffic harness can see recompile churn across serves.
        self._jit_compiles: Dict[str, int] = {}
        self._geom_seen: set = set()
        self.spec_k = int(spec_k)
        self._draft_cfg = None
        self._draft_params = None
        if self.spec_k:
            if spec_draft == "self":
                # self-speculation: the target drafts for itself (no
                # compressed prefix, plain positions) — the upper bound
                # for acceptance and the bench's greedy workload
                self._draft_cfg, self._draft_params = cfg, self.params
            else:
                self._draft_cfg, self._draft_params = spec_draft
            dcfg = self._draft_cfg
            if dcfg.vocab_size != cfg.vocab_size:
                raise ValueError(
                    f"drafter vocab {dcfg.vocab_size} != target vocab "
                    f"{cfg.vocab_size} — drafts would be meaningless")
            if not _lane_capable(dcfg):
                raise ValueError(
                    f"drafter {dcfg.name}: needs a pure attention/MLA "
                    "layout (its cache rolls forward by accepted length)")
            # the drafter keeps its own dense per-slot cache regardless of
            # the engine's KV layout: it is the small sibling config, so
            # slots × max_len of its KV is cheap, and it never shares
            # prefix blocks (it drafts from the plain prompt)
            self._draft_cache = tfm.init_cache(dcfg, slots, max_len)
            self._draft_len = np.zeros((slots,), np.int64)

    # ------------------------------------------------------------------
    # Prefix seating
    # ------------------------------------------------------------------

    def add_prefix(self, name: str, materialized, batch_index: int = 0) -> str:
        """Register a materialized compressed prefix under ``name``.  In
        the paged layout this scatters the prefix into pool blocks once —
        every slot later seated on it shares that single physical copy."""
        if self.kv_layout == "paged":
            self.cache = self.store.put(name, materialized, self.cache,
                                        batch_index)
            return name
        return self.store.put(name, materialized, batch_index)

    # ---- paged block bookkeeping ----

    def _release_slot_blocks(self, slot: int) -> None:
        """Drop this slot's references: private blocks return to the free
        pool; shared prefix blocks persist (the PrefixStore holds a ref)."""
        for b in self._slot_blocks[slot]:
            self.alloc.decref(b)
        self._slot_blocks[slot] = []
        self.tables[slot, :] = TRASH_BLOCK

    def _seat_blocks(self, slot: int, name: str) -> None:
        """Point one slot's block table at a resident prefix's blocks."""
        self._release_slot_blocks(slot)
        blocks = self.store.blocks(name)
        for b in blocks:
            self.alloc.incref(b)
        self._slot_blocks[slot] = blocks
        self.tables[slot, :len(blocks)] = blocks

    def seat_prefix(self, slot: int, name: str) -> None:
        """Install task ``name``'s compressed memory into one slot."""
        self.cache = clear_slot_state(self.cache, slot)
        if self.kv_layout == "paged":
            self._seat_blocks(slot, name)
            state = self.store.state_row(name)
            if state is not None:  # recurrent handoff stays per-slot
                self.cache = seat_prefix_row(self.cache, state, slot)
        else:
            self.cache = seat_prefix_row(self.cache, self.store.get(name), slot)
        self.base[slot] = self.store.base_len(name)
        self._seated[slot] = name
        self._dirty[slot] = False

    def seat_compressed(self, prefix_materialized) -> None:
        """Compat: install an offline-compressed context batch-wide (row b
        of the materialized prefix seats slot b).  Rows are also kept in the
        PrefixStore so dirtied slots can be re-seated on later serves."""
        assert self.cfg.memcom is not None
        self.base_len = self.cfg.memcom.num_memory_tokens
        if self.kv_layout == "paged":
            for b in range(self.slots):
                name = self._COMPAT + str(b)
                # unseat first so a re-put never trips the eviction guard
                self._release_slot_blocks(b)
                self.cache = self.store.put(name, prefix_materialized,
                                            self.cache, batch_index=b)
                self.seat_prefix(b, name)
        else:
            self.cache = write_prefix_to_cache(self.cfg, self.cache,
                                               prefix_materialized)
            self.base[:] = self.base_len
            for b in range(self.slots):
                self.store.put(self._COMPAT + str(b), prefix_materialized,
                               batch_index=b)
        self._seated = [None] * self.slots
        self._dirty[:] = False

    _COMPAT = "__seated_"  # reserved PrefixStore names for seat_compressed

    def _reset_slot(self, slot: int) -> None:
        """Prepare a slot for a request with no named prefix: restore the
        engine-wide seated context (seat_compressed) if the slot no longer
        holds it — a named prefix displaced it, or (recurrent families) a
        previous occupant advanced its state — else serve context-free."""
        if self._seated[slot] is None and not \
                (self._recurrent and self._dirty[slot]):
            return  # slot content still valid as-is
        if self._COMPAT + str(slot) in self.store:
            self.seat_prefix(slot, self._COMPAT + str(slot))
            self._seated[slot] = None  # engine-wide context, not request-named
        else:
            self.cache = clear_slot_state(self.cache, slot)
            if self.kv_layout == "paged":
                self._release_slot_blocks(slot)
            self.base[slot] = 0
            self._seated[slot] = None
            self._dirty[slot] = False

    def _restore_slot(self, slot: int) -> None:
        """Refresh the context a slot already holds (named prefix, or the
        engine-wide seated one) when its recurrent state may have been
        advanced by earlier generation — attention KV at [0, m) is never
        overwritten, so only recurrent families need this."""
        if not (self._recurrent and self._dirty[slot]):
            return
        if self._seated[slot] is not None:
            self.seat_prefix(slot, self._seated[slot])
        elif self._COMPAT + str(slot) in self.store:
            self.seat_prefix(slot, self._COMPAT + str(slot))
            self._seated[slot] = None
        else:
            self.cache = clear_slot_state(self.cache, slot)
            self._dirty[slot] = False

    # ------------------------------------------------------------------
    # Fused step + speculative decoding programs
    # ------------------------------------------------------------------

    def _note_geometry(self, family: str, key) -> None:
        """Count one jit compilation against a step-function family the
        first time a (bucketed) geometry key is seen — the per-family
        totals surface as ``stats()["engine"]["jit_compiles"]`` so
        recompile churn is visible in the traffic bench."""
        k = (family, key)
        if k not in self._geom_seen:
            self._geom_seen.add(k)
            self._jit_compiles[family] = self._jit_compiles.get(family, 0) + 1
            self._m_jit.inc(family=family)

    def _program(self, family: str, key: Tuple, make):
        """Geometry-keyed jitted-program registry (LRU-bounded)."""
        full = (family,) + key
        fn = self._programs.get(full)
        if fn is None:
            fn = self._programs[full] = make()
            self._jit_compiles[family] = self._jit_compiles.get(family, 0) + 1
            self._m_jit.inc(family=family)
            while len(self._programs) > self._program_cap:
                self._programs.popitem(last=False)
        else:
            self._programs.move_to_end(full)
        return fn

    def _fused_program(self, W: int, greedy: bool, comp_geom):
        """The fused step for lane width ``W``: batched decode lanes (+
        speculative verify lanes) for every slot, an optional prefill
        chunk lane for a joining slot, and — when ``comp_geom =
        (offset, width, cache_len)`` — a PrefixCompiler chunk, all in one
        jitted dispatch.  Ragged lanes are masked by ``valids``: invalid
        lanes' KV writes are dropped (dense) / trashed (paged) and their
        outputs ignored; the attention read needs no masking because lane
        ``s`` of slot ``b`` sits at query position ``starts[b] + s`` and
        causality hides everything an invalid lane could touch."""
        cfg, impl, mesh = self.cfg, self.impl, self.mesh
        pin = self._pin
        donate = (1,) if self.donate_cache else ()
        body = (self.compiler.chunk_body(comp_geom[0])
                if comp_geom is not None else None)

        def make():
            def run(params, cache, tokens, starts, valids, tables, comp):
                logits, aux = tfm.forward(
                    params, cfg, tokens=tokens, cache=cache,
                    cache_index=starts, decode=True, block_tables=tables,
                    lane_valid=valids, mesh=mesh, impl=impl)
                out = (jnp.argmax(logits, -1).astype(jnp.int32)
                       if greedy else logits)
                if aux["moe_rows"] is not None:  # fetched with the output
                    out = (out, aux["moe_rows"])
                comp_out = None
                if body is not None:
                    compressor, src_cache, chunk = comp
                    comp_out = body(compressor, src_cache, chunk)
                return out, pin(aux["cache"]), comp_out

            return jax.jit(run, donate_argnums=donate)

        return self._program("fused", (W, bool(greedy), comp_geom), make)

    def _draft_prog(self, k: int):
        """k drafter proposal steps + one catch-up step, scanned in one
        program.  The catch-up step consumes the last draft (KV write
        only), so after a fully-accepted round the drafter cache already
        contains every token the target consumed — no position drift."""
        dcfg, impl, max_len = self._draft_cfg, self.impl, self.max_len

        def make():
            def run(dparams, dcache, pending, lens):
                def body(carry, _):
                    cache, tok, ln = carry
                    # drop writes past the drafter stripe: an unmasked
                    # scatter would *clamp* and corrupt the tail rows
                    ok = (ln < max_len).astype(jnp.int32)
                    logits, aux = tfm.forward(
                        dparams, dcfg, tokens=tok[:, None], cache=cache,
                        cache_index=ln, decode=True, lane_valid=ok,
                        impl=impl)
                    nxt = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)
                    return (aux["cache"], nxt, ln + 1), nxt

                (cache, _, _), drafts = jax.lax.scan(
                    body, (dcache, pending, lens), None, length=k + 1)
                # steps 0..k-1 emit d1..dk; step k only rolls the cache
                return jnp.swapaxes(drafts, 0, 1)[:, :k], cache

            return jax.jit(run)

        return self._program("draft", (k,), make)

    def _draft_prefill(self, slot: int, tokens) -> None:
        """(Re)build the drafter's stripe for one slot from position 0:
        the drafter sees the plain prompt (+ any resumed tokens), never
        the compressed prefix — that only lowers acceptance for prefixed
        tasks, never correctness, since every draft is verified."""
        dcfg, impl = self._draft_cfg, self.impl
        n = len(tokens)
        width = max(1, min(pow2_bucket(n, 8), self.max_len))
        padded = np.zeros((1, width), np.int32)
        padded[0, :n] = tokens

        def make():
            def run(dparams, dcache, toks, s):
                row = _slice_slot(dcache, s)
                _, aux = tfm.forward(dparams, dcfg, tokens=toks, cache=row,
                                     cache_index=0, mask_offset=0, impl=impl)
                return _merge_slot(dcache, aux["cache"], s)

            return jax.jit(run)

        prog = self._program("draft_prefill", (width,), make)
        self._draft_cache = prog(self._draft_params, self._draft_cache,
                                 jnp.asarray(padded), jnp.int32(slot))
        self._draft_len[slot] = n
        self._charge("draft_step", 1)

    @staticmethod
    def _softmax_row(logits_row: np.ndarray, temperature: float) -> np.ndarray:
        z = np.asarray(logits_row, np.float64) / temperature
        z -= z.max()
        p = np.exp(z)
        return p / p.sum()

    def _spec_sample(self, logits_rows: np.ndarray, drafts: np.ndarray,
                     temperature: float, rng: np.random.Generator):
        """Sampled (Leviathan-style) acceptance against a *greedy* drafter:
        the draft distribution is a point mass at d, so d is accepted with
        probability p(d) and a rejection resamples from the renormalized
        residual (p with d zeroed) — the emitted sequence is distributed
        exactly as token-by-token sampling from the target.  Returns
        (emitted tokens, number of accepted drafts); draws come from the
        request's own rng stream."""
        emitted: List[int] = []
        accepted = 0
        for j, d in enumerate(np.asarray(drafts, np.int64)):
            p = self._softmax_row(logits_rows[j], temperature)
            if rng.uniform() < p[d]:
                emitted.append(int(d))
                accepted += 1
                continue
            q = p.copy()
            q[d] = 0.0
            tot = q.sum()
            if tot <= 0.0:  # target is (numerically) a point mass at d too
                emitted.append(int(d))
                accepted += 1
                continue
            emitted.append(int(rng.choice(len(q), p=q / tot)))
            return emitted, accepted
        # every draft accepted: bonus token from the last verify lane
        p = self._softmax_row(logits_rows[len(drafts)], temperature)
        emitted.append(int(rng.choice(len(p), p=p)))
        return emitted, accepted

    # ------------------------------------------------------------------
    # Continuous-batching serve loop
    # ------------------------------------------------------------------

    def serve(self, requests: Iterable[Request], *,
              seed: int = 0) -> Dict[int, np.ndarray]:
        """Serve requests to completion (see :meth:`_serve_impl` for the
        full contract).  If the loop dies, the tracer's flight recorder
        dumps its ring buffer (when a dump path is configured) before
        the exception propagates — the last N events are the post-mortem."""
        try:
            return self._serve_impl(requests, seed=seed)
        except BaseException:
            self.tracer.dump_on_error()
            raise

    def _serve_impl(self, requests: Iterable[Request], *,
                    seed: int = 0) -> Dict[int, np.ndarray]:
        """Serve a batch of ragged, per-task requests to completion.

        Returns {request.uid: generated tokens}.  Output includes the stop
        token when one fired.  More requests than slots is fine — finished
        slots are refilled mid-decode.

        Requests carrying ``raw_shots`` whose prefix is not resident are
        parked (``waiting_on_prefix``) while the engine's
        :class:`PrefixCompiler` compiles them online: each loop iteration
        runs one batched decode step for the seated slots, then at most
        ``compile_token_budget`` source tokens of compilation — already-
        seated slots keep emitting tokens throughout a compile.

        With a tiered store, a request naming a demoted/spilled prefix
        parks the same way while the row is promoted back host→HBM, at
        most ``promote_layer_budget`` per-layer chunks per iteration —
        promotion beats recompiling even when the request carries
        ``raw_shots``.

        Requests carrying ``arrival_s`` are held until the engine clock
        reaches that offset from serve() start — that is how the traffic
        harness replays a timed Poisson/ON-OFF trace.  Per-request
        timings land in ``self.request_log`` for the SLO metrics, as
        offsets on the engine clock from serve() start: ``arrival_s`` (the
        due time), ``released_s`` (handed to the scheduler),
        ``admitted_s`` (its first admission began), ``first_token_s`` and
        ``finish_s``, in that order, plus the preemption count.

        Each host phase of the loop runs under ``self.tracer.phase``, so
        a profiler trace shows it as a ``serve.*`` annotation beside the
        device's work; phases are siblings, never nested.
        """
        epoch = self.clock()  # request_log times are offsets from here
        sched = Scheduler(self.slots, clock=self.clock,
                          aging_interval_s=self.priority_aging_s,
                          metrics=self.metrics)
        self.request_log = {}
        tr = self.tracer
        # trace ids are serve-local arrival ordinals, NOT Request.uid:
        # uids come from a process-global counter, so two runs of the
        # same scenario in one process would dump different JSON — rids
        # keep the trace a pure function of (scenario, seed)
        self._rids: Dict[int, int] = {}
        self._epoch = epoch
        requests = list(requests)
        # validate the whole batch before the first side effect: a bad
        # request must not leave earlier ones' compile jobs orphaned in
        # the (engine-lifetime) compiler with their waiters discarded
        for req in requests:
            self._check_request(req)

        def _arrive(req: Request) -> None:
            rid = self._rids[req.uid] = len(self._rids)
            now = self.clock() - epoch
            self.request_log[req.uid] = {
                "priority": int(req.priority),
                "arrival_s": float(req.arrival_s if req.arrival_s is not None
                                   else now),
                "released_s": now, "admitted_s": None,
                "first_token_s": None, "finish_s": None,
                "tokens": 0, "preemptions": 0,
            }
            if tr.enabled:
                tr.instant("scheduler", "arrive", rid=rid,
                           priority=int(req.priority))
            self._submit(sched, req)

        # timed requests wait in arrival order until the clock reaches
        # them; untimed ones submit immediately (classic batch serve)
        future = sorted((r for r in requests if r.arrival_s is not None),
                        key=lambda r: (r.arrival_s, r.uid))
        for req in requests:
            if req.arrival_s is None:
                _arrive(req)

        # per-request sampling streams: folding Request.uid into the seed
        # makes each request's tokens a function of (seed, request) alone —
        # one shared stream would make sampled outputs depend on admission
        # order and slot interleaving (whichever slot sampled first stole
        # the next draw)
        streams: Dict[int, np.random.Generator] = {}

        def _stream(req: Request) -> np.random.Generator:
            rng = streams.get(req.uid)
            if rng is None:
                rng = streams[req.uid] = np.random.default_rng(
                    np.random.SeedSequence([int(seed), int(req.uid)]))
            return rng

        results: Dict[int, np.ndarray] = {}
        pending = np.zeros((self.slots,), np.int32)  # next token per slot
        lengths = self.base.copy()  # per-slot valid cache length
        paged = self.kv_layout == "paged"
        # a resumed request re-prefills prompt + already-emitted tokens,
        # so the paged gate must size its window on that longer prefill
        can_seat = ((lambda r: self._can_admit(r, sched.resume_len(r.uid)))
                    if paged else None)
        if self.watchdog is not None:
            base_seat = can_seat

            def can_seat(r, _base=base_seat):
                # degradation hook: while a page alert holds shed_floor,
                # park lower-priority admissions — but only while some
                # slot is still running, so shedding an idle engine can
                # never deadlock the simulation
                if (self.shed_floor is not None
                        and int(r.priority) >= self.shed_floor
                        and sched.active_slots()):
                    return False
                return True if _base is None else _base(r)
        last_decode_done: Optional[float] = None
        self.last_step_t = self.clock()

        def _finish(slot):
            req, toks = sched.finish(slot)
            if paged:
                self._reserved[slot] = 0  # unused decode headroom returns
            streams.pop(req.uid, None)
            results[req.uid] = toks
            log = self.request_log[req.uid]
            log["finish_s"] = self.clock() - epoch
            log["tokens"] = int(len(toks))
            self._m_latency.observe(log["finish_s"] - log["arrival_s"],
                                    priority=log["priority"])
            if tr.enabled:
                tr.instant(f"slot{slot}", "finish",
                           rid=self._rids[req.uid], tokens=len(toks))

        while sched.has_work() or future:
            wd = self.watchdog
            if wd is not None:
                wd_steps0 = self._counters["decode_steps"]
                wd_toks0 = self._counters["tokens_generated"]
            with tr.phase("release"):
                # release timed arrivals whose moment has come
                now_s = self.clock() - epoch
                while future and future[0].arrival_s <= now_s:
                    _arrive(future.pop(0))
            if not sched.has_work():
                # idle until the next arrival: a virtual clock jumps
                # there, a wall clock sleeps in short slices
                self._advance_to(epoch + future[0].arrival_s)
                continue
            with tr.phase("admit"):
                if self.compiler is not None:
                    self._drain_compiler(sched)
                if self.tiers is not None:
                    self._drain_promoter(sched)
                admitted = sched.admit(can_seat)
                if paged and not admitted and not sched.active_slots() \
                        and sched.pending:
                    # nothing running and the head request doesn't pass
                    # the free-block gate: reclaim every free slot's
                    # private blocks, then retry once — fail fast instead
                    # of spinning
                    self._reclaim_free_slots(sched)
                    admitted = sched.admit(can_seat)
                    if not admitted:
                        raise OutOfBlocksError(
                            f"paged KV pool ({self.alloc.num_blocks} blocks "
                            f"of {self.block_size}) cannot hold the next "
                            "request even with every free slot reclaimed — "
                            "grow num_blocks or evict resident prefixes")
                if self.preemption and sched.pending:
                    admitted += self._preempt_for_priority(
                        sched, can_seat,
                        protected={s for s, _ in admitted}
                        | set(self._joining))
                # fused chunked admission: while other slots are
                # mid-decode, a new request "joins" — its prompt streams
                # through the fused step in fused_chunk_tokens-sized chunk
                # lanes instead of one monolithic prefill gap.  A slot
                # that is itself mid-join counts as busy too: its chunks
                # flow through fused steps, so a classic prefill here
                # would land between them as a gap.  Only with nothing
                # decoding *and* no join in flight does the classic
                # per-slot prefill stall nobody and stay the fast path.
                admitted_slots = {s for s, _ in admitted}
                busy_decode = any(s not in admitted_slots
                                  and s not in self._joining
                                  for s in sched.active_slots())
            for slot, req in admitted:
                rid = self._rids[req.uid]
                log = self.request_log[req.uid]
                with tr.phase("admit", rid=rid):
                    t_adm = self.clock()
                    if log["admitted_s"] is None:
                        log["admitted_s"] = t_adm - epoch
                    if req.prefix is not None:
                        # skip the re-seat when the slot provably still
                        # holds this prefix (KV region [0, m) is never
                        # overwritten; only recurrent state can have been
                        # advanced)
                        if self._seated[slot] != req.prefix or \
                                self._recurrent:
                            self.seat_prefix(slot, req.prefix)
                    else:
                        self._reset_slot(slot)
                    # a preempted request resumes by re-prefilling
                    # everything it had already consumed *and emitted*
                    # behind the seated prefix — byte-for-byte the refill
                    # path, so the rebuilt KV state (and thus every later
                    # token) is exact
                    resumed = sched.emitted_tokens(slot)
                    toks = (np.concatenate([req.tokens, resumed])
                            if resumed.size else req.tokens)
                    if paged:
                        # the gate's pending reservation becomes this
                        # slot's: prefill allocates its share now, the
                        # rest stays reserved for the decode steps to draw
                        # down
                        self._reserved_pending -= self._blocks_needed(
                            req, self._req_base(req),
                            extra=resumed.size)  # what the gate added
                        base = int(self.base[slot])
                        need = self._blocks_needed(req, base,
                                                   extra=resumed.size)
                    if resumed.size:
                        self._counters["preempted_tokens_refilled"] += \
                            int(resumed.size)
                        if tr.enabled:
                            tr.instant(f"slot{slot}", "resume", rid=rid,
                                       tokens=int(resumed.size))
                    if self.fused and (busy_decode or self._joining):
                        self._joining[slot] = {"req": req, "toks": toks,
                                               "consumed": 0, "t0": t_adm}
                        lengths[slot] = self.base[slot]
                        if paged:
                            # the whole window stays reserved; chunk
                            # prefills and decode steps draw it down as
                            # they allocate
                            self._reserved[slot] = need
                        continue
                    if paged:
                        n = len(toks)
                        width = (_bucket(n, self.max_len - base)
                                 if self._pad_prefill else n)
                        covered = (self.alloc.blocks_for(base + width)
                                   - self.alloc.blocks_for(base)
                                   + (1 if base % self.block_size else 0))
                        self._reserved[slot] = max(0, need - covered)
                row_logits = self._prefill_slot(slot, toks)
                with tr.phase("tokens", rid=rid):
                    lengths[slot] = self.base[slot] + len(toks)
                    if self.spec_k:
                        self._draft_prefill(slot, toks)
                    tok = self._sample_row(row_logits, req.temperature,
                                           _stream(req))
                    pending[slot] = tok
                    if tr.enabled:
                        tr.span(f"slot{slot}", "admission", t_adm, rid=rid,
                                prefix=req.prefix, prompt_tokens=len(toks),
                                resumed=int(resumed.size))
                    if log["first_token_s"] is None:
                        log["first_token_s"] = self.clock() - epoch
                        self._m_ttft.observe(
                            log["first_token_s"] - log["arrival_s"],
                            priority=log["priority"])
                        if wd is not None:
                            wd.observe("ttft", log["first_token_s"]
                                       - log["arrival_s"])
                    if sched.record_token(slot, tok):
                        _finish(slot)
            active = sched.active_slots()
            compiling = (self.compiler is not None
                         and self.compiler.has_compile_work())
            promoting = (self.tiers is not None
                         and self.tiers.has_promote_work())
            if not active:
                if promoting:
                    # nothing decoding: chunking the host→HBM copy stalls
                    # nobody — run the head promotion to completion (it
                    # is the cheaper path to an admissible request, so it
                    # goes before compile work)
                    self._promote_step(None)
                elif compiling:
                    # nothing decoding: an iteration's worth of compile
                    # work stalls nobody — run the head job to completion
                    # so cold-task time-to-first-token is as low as it gets
                    self._compile_step(None)
                continue  # admit the next queued/woken requests (or exit)
            decode_lanes = [s for s in active if s not in self._joining]
            chunk_slot = next(iter(self._joining)) if self._joining else None
            comp = None
            if (self.fused and compiling and chunk_slot is None
                    and self.compile_token_budget is not None):
                # the chunk lane is free: stage a compile chunk to ride
                # the fused step (one dispatch, zero extra decode gap)
                comp = self.compiler.peek_chunk(self.compile_token_budget)
            spec = bool(self.spec_k and decode_lanes)
            use_fused = self.fused and (spec or chunk_slot is not None
                                        or comp is not None)
            if not use_fused:
                # ---- classic single-token decode step ----
                with tr.phase("decode.dispatch"):
                    greedy = all(sched.request_in(s).temperature <= 0
                                 for s in active)
                    self._note_geometry("decode", (bool(greedy),))
                    step = self._decode_greedy if greedy else self._decode
                    step_args = ()
                    if paged:
                        # grow each active slot's table before its write
                        # crosses into an unallocated block (idle slots
                        # write into their own stale blocks or the trash
                        # block — both masked)
                        self._ensure_decode_blocks(active, lengths)
                        step_args = (jnp.asarray(self.tables),)
                    t_start = self.clock()
                    out, self.cache = step(
                        self.params, self.cache, jnp.asarray(pending[:, None]),
                        jnp.asarray(lengths, jnp.int32), *step_args)
                    self._charge("decode_step", 1)
                    # the batched step advances *every* slot's recurrent
                    # state (idle rows included), so all slots are dirty
                    self._dirty[:] = True
                with tr.phase("decode.fetch"):
                    # greedy: (slots,) ids; else logits
                    out = self._fetch(out, np.isin(np.arange(self.slots),
                                                   active))
                with tr.phase("tokens"):
                    self._counters["decode_time_s"] += self.clock() - t_start
                    if last_decode_done is not None:
                        # decode gap = non-decode time since the previous
                        # step — admissions, prefills, and (above all)
                        # compile chunks; the online_compile bench reads
                        # the dip off these
                        self._note_gap(t_start - last_decode_done)
                    last_decode_done = self.last_step_t = self.clock()
                    if tr.enabled:
                        tr.span("engine", "decode_step", t_start,
                                last_decode_done, active=len(active))
                    self._counters["decode_steps"] += 1
                    if compiling:
                        self._counters["decode_steps_during_compile"] += 1
                    if promoting:
                        self._counters["decode_steps_during_promote"] += 1
                    for slot in active:
                        lengths[slot] += 1  # the step consumed its token
                        req = sched.request_in(slot)
                        tok = int(out[slot]) if greedy else self._sample_row(
                            out[slot], req.temperature, _stream(req))
                        pending[slot] = tok
                        self._counters["tokens_generated"] += 1
                        if self.spec_k:
                            self._draft_len[slot] += 1
                        if sched.record_token(slot, tok):
                            _finish(slot)
                if compiling:
                    # interleave: at most compile_token_budget source tokens
                    # of compilation behind this decode step, then decode
                    self._compile_step(self.compile_token_budget)
                    self._counters["compile_chunks_interleaved"] += 1
                if promoting:
                    # interleave: at most promote_layer_budget per-layer
                    # host→HBM chunks behind this decode step, then decode
                    self._promote_step(self.promote_layer_budget)
                    self._counters["promote_steps_interleaved"] += 1
            else:
                # ---- fused step: decode lanes + one chunk, one dispatch --
                # everything below up to the post-step bookkeeping happens
                # inside the decode-step timing window, so admission/compile
                # churn never widens the measured decode gap
                with tr.phase("fused.dispatch"):
                    t_start = self.clock()
                    drafts = None
                    k_eff = np.zeros((self.slots,), np.int64)
                    if spec:
                        for s in decode_lanes:
                            req = sched.request_in(s)
                            left = req.max_new - len(sched.emitted_tokens(s))
                            k_eff[s] = max(0, min(
                                self.spec_k, left - 1,
                                self.max_len - int(lengths[s]) - 1))
                        drafts, self._draft_cache = self._draft_prog(
                            self.spec_k)(
                            self._draft_params, self._draft_cache,
                            jnp.asarray(pending),
                            jnp.asarray(self._draft_len, jnp.int32))
                        drafts = np.asarray(drafts)
                        self._charge("draft_step", self.spec_k + 1)
                        self._counters["spec_rounds"] += 1
                    chunk_n, jn = 0, None
                    if chunk_slot is not None:
                        jn = self._joining[chunk_slot]
                        chunk_n = min(len(jn["toks"]) - jn["consumed"],
                                      self.fused_chunk_tokens)
                    lanes = 1 + (self.spec_k if spec else 0)
                    W = pow2_bucket(max(lanes, chunk_n), 1)
                    tokens_in = np.zeros((self.slots, W), np.int32)
                    valids = np.zeros((self.slots,), np.int32)
                    for s in decode_lanes:
                        tokens_in[s, 0] = pending[s]
                        kk = int(k_eff[s])
                        if kk:
                            tokens_in[s, 1:1 + kk] = drafts[s, :kk]
                        valids[s] = 1 + kk
                    completing = False
                    if chunk_slot is not None:
                        c0 = jn["consumed"]
                        tokens_in[chunk_slot, :chunk_n] = \
                            jn["toks"][c0:c0 + chunk_n]
                        valids[chunk_slot] = chunk_n
                        completing = c0 + chunk_n == len(jn["toks"])
                    greedy = all(sched.request_in(s).temperature <= 0
                                 for s in decode_lanes)
                    if completing and jn["req"].temperature > 0:
                        greedy = False  # the chunk's first token is sampled
                    if paged:
                        self._ensure_decode_blocks(decode_lanes, lengths,
                                                   widths=valids)
                        if chunk_slot is not None:
                            got = self._prepare_prefill(
                                chunk_slot, int(lengths[chunk_slot]), chunk_n)
                            self._reserved[chunk_slot] = max(
                                0, int(self._reserved[chunk_slot]) - got)
                    comp_geom = comp_args = None
                    cw = 0
                    if comp is not None:
                        job, offset, cw, clen = comp
                        comp_geom = (offset, cw, clen)
                        comp_args = (self.compiler.compressor,
                                     job.state.cache,
                                     self.compiler.chunk_tokens(job, cw))
                    prog = self._fused_program(W, greedy, comp_geom)
                    out, self.cache, comp_out = prog(
                        self.params, self.cache, jnp.asarray(tokens_in),
                        jnp.asarray(lengths, jnp.int32), jnp.asarray(valids),
                        jnp.asarray(self.tables) if paged else None,
                        comp_args)
                    self._charge("decode_step", 1)
                    if chunk_n:
                        self._charge("prefill_token", chunk_n)
                    if comp is not None:
                        self._charge("compile_token", cw)
                    self._dirty[:] = True
                with tr.phase("fused.fetch"):
                    # greedy: (slots, W) ids; else logits
                    out = self._fetch(
                        out, (np.arange(W) < valids[:, None]).reshape(-1))
                with tr.phase("tokens"):
                    self._counters["decode_time_s"] += self.clock() - t_start
                    if last_decode_done is not None:
                        self._note_gap(t_start - last_decode_done)
                    last_decode_done = self.last_step_t = self.clock()
                    if tr.enabled:
                        tr.span("engine", "fused_step", t_start,
                                last_decode_done, lanes=len(decode_lanes),
                                chunk_tokens=int(chunk_n),
                                compile_tokens=int(cw))
                    self._counters["decode_steps"] += 1
                    self._counters["fused_steps"] += 1
                    if chunk_n or comp is not None:
                        self._counters["fused_chunks"] += 1
                    if compiling:
                        self._counters["decode_steps_during_compile"] += 1
                    if promoting:
                        self._counters["decode_steps_during_promote"] += 1
                    if chunk_slot is not None:
                        jn["consumed"] += chunk_n
                        lengths[chunk_slot] += chunk_n
                        self._counters["fused_prefill_chunks"] += 1
                        self._counters["fused_prefill_tokens"] += int(chunk_n)
                        if completing:
                            del self._joining[chunk_slot]
                            req = jn["req"]
                            self._counters["prefills"] += 1
                            if greedy:
                                tok = int(out[chunk_slot, chunk_n - 1])
                            else:
                                tok = self._sample_row(
                                    out[chunk_slot, chunk_n - 1],
                                    req.temperature, _stream(req))
                            pending[chunk_slot] = tok
                            if self.spec_k:
                                self._draft_prefill(chunk_slot, jn["toks"])
                            if tr.enabled:
                                tr.span(f"slot{chunk_slot}", "admission",
                                        jn["t0"], rid=self._rids[req.uid],
                                        prefix=req.prefix,
                                        prompt_tokens=len(jn["toks"]),
                                        fused_join=True)
                            log = self.request_log[req.uid]
                            if log["first_token_s"] is None:
                                log["first_token_s"] = self.clock() - epoch
                                self._m_ttft.observe(
                                    log["first_token_s"] - log["arrival_s"],
                                    priority=log["priority"])
                                if wd is not None:
                                    wd.observe("ttft", log["first_token_s"]
                                               - log["arrival_s"])
                            if sched.record_token(chunk_slot, tok):
                                _finish(chunk_slot)
                    for s in decode_lanes:
                        req = sched.request_in(s)
                        kk = int(k_eff[s])
                        if kk == 0:  # plain decode lane (no drafts)
                            lengths[s] += 1
                            tok = (int(out[s, 0]) if greedy
                                   else self._sample_row(
                                       out[s, 0], req.temperature,
                                       _stream(req)))
                            pending[s] = tok
                            self._counters["tokens_generated"] += 1
                            if self.spec_k:
                                self._draft_len[s] += 1
                            if sched.record_token(s, tok):
                                _finish(s)
                            continue
                        self._counters["draft_proposed"] += kk
                        dr = drafts[s, :kk]
                        if greedy or req.temperature <= 0:
                            # greedy acceptance: the longest prefix where
                            # the drafter matched the target's argmax — the
                            # emitted tokens are exactly the
                            # non-speculative sequence
                            g = (out[s, :kk + 1] if greedy else
                                 np.argmax(out[s, :kk + 1], axis=-1))
                            a = 0
                            while a < kk and int(dr[a]) == int(g[a]):
                                a += 1
                            emitted = [int(t) for t in g[:a + 1]]
                        else:
                            emitted, a = self._spec_sample(
                                out[s, :kk + 1], dr, req.temperature,
                                _stream(req))
                        self._counters["draft_accepted"] += a
                        if tr.enabled:
                            tr.instant(f"slot{s}", "spec_accept",
                                       rid=self._rids[req.uid],
                                       proposed=kk, accepted=int(a))
                        # implicit KV rollback: only the accepted prefix
                        # counts — rejected lanes' cache rows sit beyond
                        # the new length (dense) / in private tail blocks
                        # (paged) and are causally invisible until
                        # overwritten next round
                        lengths[s] += len(emitted)
                        self._draft_len[s] += len(emitted)
                        pending[s] = emitted[-1]
                        fin = False
                        for t in emitted:
                            self._counters["tokens_generated"] += 1
                            if sched.record_token(s, t):
                                fin = True
                                break
                        if fin:
                            _finish(s)
                    if comp is not None:
                        self.compiler.absorb_chunk(job, comp_out[0],
                                                   comp_out[1], cw)
                        self._counters["fused_compile_chunks"] += 1
                        self._counters["compile_chunks_interleaved"] += 1
                        if tr.enabled:
                            # the chunk rode the fused dispatch: its span
                            # is the step's own window on the compiler
                            # track
                            tr.span("compiler", "compile_chunk", t_start,
                                    last_decode_done, tokens=int(cw),
                                    fused=True)
                if comp is None and compiling \
                        and self.compile_token_budget is None:
                    # unbudgeted compile cannot ride the chunk lane — run
                    # the whole job behind this step (the stalled baseline)
                    self._compile_step(None)
                    self._counters["compile_chunks_interleaved"] += 1
                if promoting:
                    self._promote_step(self.promote_layer_budget)
                    self._counters["promote_steps_interleaved"] += 1
            if wd is not None:
                # goodput proxy: tokens emitted per engine step this
                # iteration (spec acceptance raises it above 1/lane)
                dsteps = self._counters["decode_steps"] - wd_steps0
                if dsteps:
                    wd.observe(
                        "tokens_per_step",
                        (self._counters["tokens_generated"] - wd_toks0)
                        / dsteps)
                wd.step()
            if self._autotune and \
                    len(self._gap_window) >= self.autotune_interval:
                self._autotune_step()
        self._refresh_gauges()
        return results

    def _preempt_for_priority(self, sched: Scheduler, can_seat,
                              protected=frozenset()):
        """Evict at most one running slot when the best queued request's
        class strictly outranks it (base classes — aging never triggers
        preemption) and admission left it stuck.  The victim is the worst
        running request (lowest class, then most emitted tokens, then
        highest slot); its paged blocks are released (the prefix itself
        stays store-resident and demotes through the normal tier path
        under capacity pressure) and the scheduler stashes its emitted
        tokens for a token-exact resume.  Slots in ``protected`` — seated
        by this loop iteration's admit() but not yet prefilled, so the
        caller still holds (slot, request) pairs for them — are never
        picked as victims.  Returns the (slot, request) pairs the retried
        admission seated.  One victim per loop iteration bounds
        preemption thrash."""
        cand = sched.best_queued()
        if cand is None:
            return []
        victims = [s for s in sched.active_slots()
                   if s not in protected
                   and sched.request_in(s).priority > cand.priority]
        if not victims:
            return []
        victim = max(victims, key=lambda s: (sched.request_in(s).priority,
                                             len(sched.emitted_tokens(s)), s))
        req = sched.preempt(victim)
        if self.kv_layout == "paged":
            self._release_slot_blocks(victim)
            self._reserved[victim] = 0
            self.base[victim] = 0
            self._seated[victim] = None
        self._counters["preemptions"] += 1
        self.request_log[req.uid]["preemptions"] += 1
        if self.tracer.enabled:
            self.tracer.instant(f"slot{victim}", "preempt",
                                rid=self._rids[req.uid],
                                by_priority=int(cand.priority))
        return sched.admit(can_seat)

    def _advance_to(self, t: float) -> None:
        """Wait until the clock reads ``t``: a virtual clock jumps there;
        a wall clock sleeps one short slice (the loop re-checks).  The
        ``serve.idle`` phase carries the due time (``due_s``, from serve()
        start) and the planned sleep (``sleep_ms``): how late the loop
        woke is the phase's length less ``sleep_ms``."""
        jump = getattr(self.clock, "advance_to", None)
        sleep = 0.0 if jump is not None else min(max(0.0, t - self.clock()),
                                                 0.02)
        with self.tracer.phase("idle", due_s=t - self._epoch,
                               sleep_ms=1e3 * sleep):
            if jump is not None:
                jump(t)
            elif sleep > 0:
                time.sleep(sleep)

    def _note_gap(self, gap: float) -> None:
        """Record one decode gap: non-decode time since the last step."""
        c = self._counters
        c["decode_gap_max_s"] = max(c["decode_gap_max_s"], gap)
        c["decode_gap_sum_s"] += gap
        c["decode_gaps"] += 1
        self._gap_samples.append(gap)
        self._gap_window.append(gap)
        self._m_gap.observe(gap)
        if self.watchdog is not None:
            self.watchdog.observe("decode_gap", gap)

    def _autotune_step(self) -> None:
        """Feedback controller on the compile/promote budgets: while the
        mean decode gap over the last window overshoots the target, halve
        the budgets (smaller interleaved slices → tighter gaps, slower
        compile/promote completion); while it undershoots half the
        target, double them back, capped at 8× their configured values."""
        window = self._gap_window
        mean_gap = sum(window) / len(window)
        del window[:]
        init_c, init_p = self._budget_init
        # a page alert's degradation hint counts as an overshoot: tighten
        # background budgets even when the mean gap still looks healthy
        if mean_gap > self.target_decode_gap_s or self.degrade_hint:
            changed = False
            if self.compile_token_budget is not None \
                    and self.compile_token_budget > 1:
                self.compile_token_budget = self.compile_token_budget // 2
                changed = True
            if self.promote_layer_budget is not None \
                    and self.promote_layer_budget > 1:
                self.promote_layer_budget = self.promote_layer_budget // 2
                changed = True
            if changed:
                self._counters["autotune_shrinks"] += 1
                if self.tracer.enabled:
                    self.tracer.instant(
                        "engine", "autotune", action="shrink",
                        compile_budget=self.compile_token_budget,
                        promote_budget=self.promote_layer_budget)
        elif mean_gap < self.target_decode_gap_s / 2:
            changed = False
            if init_c is not None and self.compile_token_budget < init_c * 8:
                self.compile_token_budget = min(
                    self.compile_token_budget * 2, init_c * 8)
                changed = True
            if init_p is not None and self.promote_layer_budget < init_p * 8:
                self.promote_layer_budget = min(
                    self.promote_layer_budget * 2, init_p * 8)
                changed = True
            if changed:
                self._counters["autotune_grows"] += 1
                if self.tracer.enabled:
                    self.tracer.instant(
                        "engine", "autotune", action="grow",
                        compile_budget=self.compile_token_budget,
                        promote_budget=self.promote_layer_budget)

    # ------------------------------------------------------------------
    # Online prefix compilation (PrefixCompiler integration)
    # ------------------------------------------------------------------

    def _check_request(self, req: Request) -> None:
        """Side-effect-free validation of one request (no counters, no
        compile submission): raises the same errors `_submit` would."""
        if req.prefix is not None and req.prefix not in self.store:
            if self.tiers is not None and self.tiers.cold_resident(req.prefix):
                # demoted/spilled prefix: promotable, no recompile needed
                base = self.tiers.cold_base_len(req.prefix)
            elif req.raw_shots is None:
                raise KeyError(
                    f"unknown prefix {req.prefix!r}; registered: "
                    f"{sorted(self.store.names()) or '(none)'}")
            elif self.compiler is None:
                raise ValueError(
                    f"request {req.uid} carries raw_shots but the engine "
                    "has no compressor — pass ServingEngine(compressor=...)")
            else:
                # worst-case seat: m memory slots (0 for state-only tasks)
                base = (self.cfg.memcom.num_memory_tokens
                        if self.cfg.memcom else 0)
        elif req.prefix is not None:
            base = self.store.base_len(req.prefix)
        else:
            # no-prefix requests land on either the engine-wide seated base
            # or a slot reset to 0 — base_len is the worst case
            base = self.base_len
        self._validate_len(req, base)

    def _submit(self, sched: Scheduler, req: Request) -> None:
        """Route one (already validated) request into the scheduler:
        resident prefix (or no prefix) goes straight to the FIFO queue.
        A request whose prefix is not HBM-resident is parked
        ``waiting_on_prefix`` while the prefix is *promoted* from a cold
        tier (if the tiered store holds it — even when the request also
        carries raw_shots, promotion beats recompiling) or, failing
        that, compiled from its raw_shots.  Both paths are single-flight
        — N requests for one task trigger one promotion/compile."""
        if req.prefix is not None:
            hit = self.store.lookup(req.prefix)
            if not hit:
                if self.tiers is not None and \
                        self.tiers.cold_resident(req.prefix):
                    self.tiers.submit_promotion(req.prefix,
                                                priority=req.priority)
                else:
                    self.compiler.submit(req.prefix, req.raw_shots,
                                         priority=req.priority)
                sched.park(req)
                if self.tracer.enabled:
                    self.tracer.begin_async(
                        "scheduler", "waiting_on_prefix",
                        self._rids[req.uid], prefix=req.prefix)
                return
        sched.submit(req)

    def _validate_len(self, req: Request, base: int) -> None:
        need = base + len(req.tokens) + req.max_new
        if need > self.max_len:
            raise ValueError(
                f"request {req.uid}: prefix+prompt+max_new={need} "
                f"exceeds max_len={self.max_len}")

    def _compile_step(self, token_budget: Optional[int]) -> None:
        with self.tracer.phase("compile_chunk"):
            before = self.compiler.stats["tokens"]
            t0 = self.clock()
            self.compiler.step(token_budget)
            consumed = self.compiler.stats["tokens"] - before
        if consumed:
            self._charge("compile_token", consumed)
            if self.tracer.enabled:
                self.tracer.span("compiler", "compile_chunk", t0,
                                 tokens=int(consumed))

    # ------------------------------------------------------------------
    # Async tier promotion (TieredPrefixStore integration)
    # ------------------------------------------------------------------

    def _promote_step(self, chunk_budget: Optional[int]) -> None:
        with self.tracer.phase("promote_chunk"):
            before = self.tiers.tier_stats["promote_chunks"]
            t0 = self.clock()
            self.tiers.promote_step(chunk_budget)
            copied = self.tiers.tier_stats["promote_chunks"] - before
        if copied:
            self._charge("promote_chunk", copied)
            if self.tracer.enabled:
                self.tracer.span("promoter", "promote_chunk", t0,
                                 chunks=int(copied))

    def _drain_promoter(self, sched: Scheduler) -> None:
        """Install at most one finished promotion into the HBM store and
        wake its waiting requests (same one-per-call reasoning as
        :meth:`_drain_compiler`: the woken requests seat — and thereby
        pin — the promoted prefix before a later install's LRU runs)."""
        ready = self.tiers.ready_promotions()
        if not ready:
            return
        name = ready[0]
        row = self.tiers.promoted_row(name)
        if self.kv_layout == "paged":
            def put():
                self.cache = self.store.put_row(name, row, self.cache)
        else:
            def put():
                self.store.put_row(name, row)
        if not self._install(put, sched):
            return  # paged seat pressure: retry on a later iteration
        self.tiers.mark_promoted(name)
        if self.tracer.enabled:
            self.tracer.instant("promoter", "promoted", prefix=name)
        for req in sched.wake(name):
            if self.tracer.enabled:
                self.tracer.end_async("scheduler", "waiting_on_prefix",
                                      self._rids[req.uid])

    def _drain_compiler(self, sched: Scheduler) -> None:
        """Install at most one finished compilation into the store and
        wake its waiting requests.  One per call on purpose: the woken
        requests admit — and thereby seat/pin — the fresh prefix before a
        *later* install's LRU eviction could reclaim it."""
        ready = self.compiler.ready()
        if not ready:
            return
        name = ready[0]
        if not self._try_install(name, self.compiler.job(name).materialized,
                                 sched):
            return  # paged seat pressure: retry on a later iteration
        self.compiler.mark_installed(name)
        if self.tracer.enabled:
            self.tracer.instant("compiler", "prefix_installed", prefix=name)
        for req in sched.wake(name):
            if self.tracer.enabled:
                self.tracer.end_async("scheduler", "waiting_on_prefix",
                                      self._rids[req.uid])

    def _try_install(self, name: str, materialized, sched: Scheduler) -> bool:
        """Make a compiled prefix store-resident (see :meth:`_install`)."""
        if self.kv_layout == "paged":
            def put():
                self.cache = self.store.put(name, materialized, self.cache)
        else:
            def put():
                self.store.put(name, materialized)
        return self._install(put, sched)

    def _install(self, put, sched: Scheduler) -> bool:
        """Run one store-residency ``put`` under capacity pressure.  An
        uncapped dense store never fails; a capped store can hit LRU
        capacity with every resident prefix seated or pinned
        (:class:`PrefixSeatedError`), and the paged pool can be exhausted
        (:class:`OutOfBlocksError`) — then free slots' stale references
        are released and the install retried; still failing, it is
        deferred while anything is running, and raised only when nothing
        ever could free capacity."""
        # queued/waiting requests' prefixes must survive this install's LRU;
        # the pin is scoped to the put calls (eviction only happens inside
        # them) so a stale set can never block later add_prefix calls
        self.store.pinned = sched.referenced_prefixes()
        try:
            try:
                put()
                return True
            except (PrefixSeatedError, OutOfBlocksError):
                # finished-but-not-reseated slots still hold block
                # references; releasing a *free* slot's blocks is always
                # safe (dense slots hold copies, nothing to reclaim)
                if self.kv_layout == "paged":
                    self._reclaim_free_slots(sched)
                    try:
                        put()
                        return True
                    except (PrefixSeatedError, OutOfBlocksError):
                        pass
                if sched.active_slots() or sched.pending:
                    # a running slot will free capacity when it finishes —
                    # and a *queued* request will run, finish, and unpin
                    # its prefix (the drain precedes admission, so the
                    # queue can be non-empty with every slot free); defer
                    return False
                raise
        finally:
            self.store.pinned = set()

    def reset_stats(self) -> None:
        """Zero every counter (engine, store, compiler) — benches call this
        after their untimed jit-warmup serves."""
        for k in self._counters:
            self._counters[k] = type(self._counters[k])(0)
        for k in self._moe_counters or ():
            self._moe_counters[k] = 0
        self._gap_samples = []
        self._gap_window = []
        for k in self.store.stats:
            self.store.stats[k] = 0
        if self.compiler is not None:
            for k in self.compiler.stats:
                self.compiler.stats[k] = 0
        if self.tiers is not None:
            for k in self.tiers.tier_stats:
                self.tiers.tier_stats[k] = 0

    def stats(self) -> Dict[str, Optional[dict]]:
        """Cache/compile behaviour counters: engine loop counts, the
        prefix store's hit/miss/put/eviction counters, the online
        compiler's job/chunk/dedup counters, and (paged) pool occupancy.
        Reported by ``launch/serve.py --stats`` and read by the
        ``online_compile`` section of ``benchmarks/serving_bench.py``.

        The counters live in the engine's :class:`MetricsRegistry`
        (``self.metrics``) — this dict is a *snapshot view* over it,
        deep-copied so callers can never mutate live counters through
        the returned reference."""
        self._refresh_gauges()
        engine = dict(self._counters)
        gaps = self._gap_samples
        engine["decode_gap_p50_s"] = \
            float(np.percentile(gaps, 50)) if gaps else 0.0
        engine["decode_gap_p99_s"] = \
            float(np.percentile(gaps, 99)) if gaps else 0.0
        # per step-function family jit-compile counts (bucketed geometry
        # keys).  Engine-lifetime — reset_stats() leaves them alone — so a
        # bench can assert the fused bucket ladder caps recompiles.
        engine["jit_compiles"] = dict(self._jit_compiles)
        prop = engine["draft_proposed"]
        engine["accept_rate"] = (engine["draft_accepted"] / prop
                                 if prop else 0.0)
        out: Dict[str, Optional[dict]] = {
            "engine": engine,
            "prefix_store": dict(self.store.stats),
            "compiler": (dict(self.compiler.stats)
                         if self.compiler is not None else None),
            # live budget values sit outside _counters: the autotuner
            # mutates them and reset_stats must not zero them
            "budgets": {
                "compile_token_budget": self.compile_token_budget,
                "promote_layer_budget": self.promote_layer_budget,
                "autotune": bool(self._autotune),
            },
        }
        if self._moe_counters is not None:
            out["moe"] = dict(self._moe_counters)
        if self.fused or self.spec_k:
            out["fused"] = {
                "enabled": self.fused,
                "chunk_tokens": self.fused_chunk_tokens,
                "spec_k": self.spec_k,
                "draft": (self._draft_cfg.name
                          if self._draft_cfg is not None else None),
            }
        if self.tiers is not None:
            out["prefix_tiers"] = self.tiers.tier_snapshot()
        if self.kv_layout == "paged":
            out["pool"] = {
                "num_blocks": self.alloc.num_blocks,
                "block_size": self.block_size,
                "blocks_used": self.alloc.used_count,
                "blocks_free": self.alloc.free_count,
            }
        if self.mesh is not None:
            out["mesh"] = {name: int(self.mesh.shape[name])
                           for name in self.mesh.axis_names}
        return copy.deepcopy(out)

    def _refresh_gauges(self) -> None:
        """Push point-in-time values (pool occupancy, live budgets) into
        registry gauges so a Prometheus scrape between serves is fresh."""
        g = self.metrics.gauge
        g("serving_budget_compile_tokens",
          "live compile token budget (autotuned)").set(
              self.compile_token_budget)
        g("serving_budget_promote_layers",
          "live promote layer-chunk budget (autotuned)").set(
              self.promote_layer_budget)
        if self.kv_layout == "paged":
            g("serving_pool_blocks_used",
              "paged KV pool blocks in use").set(self.alloc.used_count)
            g("serving_pool_blocks_free",
              "paged KV pool blocks free").set(self.alloc.free_count)

    @property
    def gap_samples(self) -> List[float]:
        """Every decode gap observed since the last reset_stats() — the
        traffic harness computes its decode-gap percentiles from these."""
        return list(self._gap_samples)

    def _prefill_slot(self, slot: int, tokens: np.ndarray,
                      persist: bool = True) -> np.ndarray:
        """Prefill one slot's prompt behind its seated prefix; returns the
        last real token's logits row.  ``persist=False`` leaves the engine
        cache untouched (one-shot scoring)."""
        n = len(tokens)
        base = int(self.base[slot])
        cap = self.max_len - base
        assert 0 < n <= cap, (n, cap)
        with self.tracer.phase("prefill.dispatch"):
            self._counters["prefills"] += 1
            width = _bucket(n, cap) if self._pad_prefill else n
            self._note_geometry("prefill", (width, base))
            self._charge("prefill_token", width)
            padded = np.zeros((1, width), np.int32)
            padded[0, :n] = tokens
            # a donating prefill would consume the cache it reads
            prefill = self._prefill if persist else self._prefill_keep
            if self.kv_layout == "paged":
                snap = None
                if not persist:
                    snap = (self.alloc.snapshot(), self.tables[slot].copy(),
                            list(self._slot_blocks[slot]))
                self._prepare_prefill(slot, base, width)
                logits, new_cache = prefill(
                    self.params, self.cache, jnp.asarray(padded),
                    jnp.int32(slot), jnp.asarray(self.tables[slot]), base)
                if snap is not None:
                    # one-shot scoring: roll the allocator and table back;
                    # the discarded blocks may hold scatter garbage, but a
                    # block is only ever read after being re-allocated
                    # *and* re-written
                    self.alloc.restore(snap[0])
                    self.tables[slot] = snap[1]
                    self._slot_blocks[slot] = snap[2]
            else:
                logits, new_cache = prefill(
                    self.params, self.cache, jnp.asarray(padded),
                    jnp.int32(slot), base)
            if persist:
                self.cache = new_cache
                self._dirty[slot] = True
        with self.tracer.phase("prefill.fetch"):
            if isinstance(logits, tuple):  # MoE: the row counts follow
                row, rows = jax.device_get((logits[0][n - 1], logits[1]))
                self._note_rows(rows, np.arange(width) < n)
                return row
            return np.asarray(logits[n - 1])

    def _fetch(self, out, valid: np.ndarray) -> np.ndarray:
        """A step's output on the host; for MoE stacks the held experts'
        row counts ride with it, as a pair or (greedy decode) after the
        slots' ids, and are split off into the counters.  ``valid`` says
        which of the step's tokens are real."""
        if self._moe_counters is None:
            return np.asarray(out)
        if isinstance(out, tuple):
            out, rows = jax.device_get(out)
        else:
            out = np.asarray(out)
            out, rows = out[:self.slots], out[self.slots:]
        self._note_rows(rows, valid)
        return out

    def _note_rows(self, rows: np.ndarray, valid: np.ndarray) -> None:
        """Count one MoE program's dispatch from its ``moe_rows``
        (:func:`repro.models.moe.split_rows`) over the tokens that
        ``valid`` marks real; the rest of their top-k pairs went to
        experts held elsewhere."""
        from repro.models.moe import split_rows

        m = self.cfg.moe
        rows = np.asarray(rows).reshape(-1, m.held + 1 + len(valid))
        per_expert, computed, per_token = split_rows(rows, m.held)
        layers, routed = rows.shape[0], int(per_token[:, valid].sum())
        c = self._moe_counters
        c["routed_rows"] += routed
        c["absent_rows"] += layers * int(valid.sum()) * m.top_k - routed
        c["padded_rows"] += int(computed.sum()) - routed
        if self._gmm_kernel:  # gate, up and down projections a layer
            c["gmm_calls"] += 3 * layers
        c["expert_hits"] += int((per_expert > 0).sum())

    # ------------------------------------------------------------------
    # Paged capacity management
    # ------------------------------------------------------------------

    def _reclaim_free_slots(self, sched: Scheduler) -> None:
        """Release every *free* slot's block references (finished-but-not-
        reseated slots still hold them) — always safe, and the shared
        recovery move when the pool or the prefix store is out of room."""
        for slot in sched.free_slots():
            self._release_slot_blocks(slot)
            self.base[slot] = 0
            self._seated[slot] = None

    def _cow_block(self, slot: int, table_index: int) -> None:
        """Copy-on-write one table entry: copy the physical block, drop
        this slot's reference to the shared original, re-point the table
        at the private copy."""
        blocks = self._slot_blocks[slot]
        new = self.alloc.alloc(1)[0]
        self.cache = copy_paged_block(self.cache, blocks[table_index], new)
        self.alloc.decref(blocks[table_index])
        blocks[table_index] = new
        self.tables[slot, table_index] = new

    def _prepare_prefill(self, slot: int, base: int, width: int) -> int:
        """Make the slot's table cover positions [0, base + width):
        copy-on-write a *shared* partial tail block (the prompt's first
        token would land inside it), then allocate fresh private blocks
        for the rest of the prefill window.  Returns how many blocks were
        drawn from the free pool (COW copy + fresh) so callers streaming
        a prompt chunkwise can draw down the slot's reservation."""
        bs = self.block_size
        blocks = self._slot_blocks[slot]
        got = 0
        if base % bs and blocks:
            ti = base // bs  # the partially-filled tail block's table index
            if self.alloc.refcount(blocks[ti]) > 1:  # shared: store/slots
                self._cow_block(slot, ti)
                got += 1
        need = self.alloc.blocks_for(base + width) - len(blocks)
        if need > 0:
            fresh = self.alloc.alloc(need)
            self.tables[slot, len(blocks):len(blocks) + need] = fresh
            blocks.extend(fresh)
            got += need
        return got

    def _ensure_decode_blocks(self, active, lengths, widths=None) -> None:
        """Before a decode step, extend each active slot's table so every
        incoming write position is block-backed — ``widths[slot]`` lanes
        starting at ``lengths[slot]`` (one token when ``widths`` is None;
        the fused step's speculative verify lanes pass more).  Allocations
        draw down the slot's admission-time reservation."""
        bs = self.block_size
        for slot in active:
            w = 1 if widths is None else max(1, int(widths[slot]))
            first = int(lengths[slot]) // bs
            last = (int(lengths[slot]) + w - 1) // bs
            blocks = self._slot_blocks[slot]
            while len(blocks) <= last:
                fresh = self.alloc.alloc(1)[0]
                self.tables[slot, len(blocks)] = fresh
                blocks.append(fresh)
                self._reserved[slot] = max(0, self._reserved[slot] - 1)
            for bi in range(first, last + 1):
                if self.alloc.refcount(blocks[bi]) > 1:
                    # defensive: a decode write into a still-shared block
                    # (cannot happen after a >=1-token prefill, but COW is
                    # cheaper than a corrupted shared prefix)
                    self._cow_block(slot, bi)

    def _blocks_needed(self, req: Request, base: int, extra: int = 0) -> int:
        """Worst-case private blocks for a request's whole window:
        prefill bucket, decode budget, and a possible tail-block COW.
        ``extra`` counts already-emitted tokens a preempted request will
        re-prefill on resume (they move from the decode budget into the
        prefill width, which can only widen the bucket)."""
        n = len(req.tokens) + extra
        cap = self.max_len - base
        width = _bucket(n, cap) if self._pad_prefill else n
        total = base + max(width, len(req.tokens) + req.max_new)
        return (self.alloc.blocks_for(total) - self.alloc.blocks_for(base)
                + (1 if base % self.block_size else 0))

    def _req_base(self, req: Request) -> int:
        return (self.store.base_len(req.prefix) if req.prefix
                else self.base_len)

    def _can_admit(self, req: Request, extra: int = 0) -> bool:
        """Free-block admission gate: the request's whole private window
        must fit in the pool *net of other active slots' outstanding
        reservations* — a seated slot never stalls (or dies) mid-decode
        waiting for memory.  A True return reserves the window: the
        scheduler admits exactly the requests this approves."""
        need = self._blocks_needed(req, self._req_base(req), extra=extra)
        outstanding = int(self._reserved.sum()) + self._reserved_pending
        if need > self.alloc.free_count - outstanding:
            return False
        self._reserved_pending += need
        return True

    @staticmethod
    def _sample_row(logits_row: np.ndarray, temperature: float,
                    rng: np.random.Generator) -> int:
        if temperature <= 0:
            return int(np.argmax(logits_row))
        z = logits_row.astype(np.float64) / temperature
        z -= z.max()
        p = np.exp(z)
        return int(rng.choice(len(p), p=p / p.sum()))

    # ------------------------------------------------------------------
    # Compat APIs (lock-step batch generation, label scoring)
    # ------------------------------------------------------------------

    def generate(self, prompts, max_new: int, temperature: float = 0.0,
                 seed: int = 0, stop_token: Optional[int] = None) -> np.ndarray:
        """Batch-generate over the slot pool.  ``prompts`` is a (slots, S)
        array or a list of ragged 1-D token arrays (one per slot).  Returns
        a (slots, n) array; with a stop token, slots now terminate
        *independently* and shorter rows are right-padded with the stop
        token.  ``max_new=0`` (or every slot producing nothing) returns a
        well-shaped ``(slots, 0)`` array instead of crashing in the pad."""
        rows: List[np.ndarray] = [np.asarray(p, np.int32) for p in prompts]
        assert len(rows) == self.slots, (len(rows), self.slots)
        if max_new == 0:  # Request requires max_new >= 1 — nothing to do
            return np.zeros((self.slots, 0), np.int32)
        reqs = [Request(tokens=r, max_new=max_new, stop_token=stop_token,
                        temperature=temperature) for r in rows]
        results = self.serve(reqs, seed=seed)
        outs = [results[r.uid] for r in reqs]
        n = max((len(o) for o in outs), default=0)
        if n == 0:
            return np.zeros((self.slots, 0), np.int32)
        fill = stop_token if stop_token is not None else 0
        return np.stack([np.pad(o, (0, n - len(o)), constant_values=fill)
                         for o in outs])

    def score_labels(self, context: np.ndarray, query: np.ndarray,
                     label_ids: np.ndarray) -> int:
        """Constrained classification: argmax over label token ids for the
        next token after [compressed prefix; context; query]."""
        toks = np.concatenate([context, query]).astype(np.int32)
        self._restore_slot(0)  # refresh stale recurrent state, keep context
        row = self._prefill_slot(0, toks, persist=False)  # stateless scoring
        return int(label_ids[np.argmax(row[label_ids])])
