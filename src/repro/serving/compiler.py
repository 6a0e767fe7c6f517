"""Online prefix compiler: many-shot compression inside the serving loop.

The offline story (``launch/serve.py`` stage 1) assumes every ICL task's
compressed prefix was materialized ahead of time.  The
:class:`PrefixCompiler` removes that assumption: a :class:`~repro.serving
.scheduler.Request` may carry its **raw shot tokens** (``raw_shots``),
and the engine compiles them *on the inference path* —

    raw shots ──compress_chunk×N──▶ prefix O^i ──materialize_prefix──▶
    PrefixStore / PagedPrefixStore ──▶ waiting requests wake

— in fixed token-budget chunks interleaved with decode steps, so slots
already seated keep emitting tokens while a cold task compiles
(``ServingEngine(compile_token_budget=…)`` sets the per-iteration
budget; ``None`` compiles a whole task in one go, the stalled baseline
measured by ``benchmarks/serving_bench.py``'s ``online_compile``
section).

Single-flight dedup: jobs are keyed by prefix name — requests that name
the same task (or carry byte-identical shot sets, which hash to the same
auto-generated name) share one compilation, however many arrive while it
is in flight.

Compilation is the path of last resort: with a tiered prefix store
(``serving/tiers.py``) an *evicted* prefix is demoted down the memory
hierarchy rather than destroyed, and the engine routes a cold request
to the (much cheaper) promotion path first — the compiler only sees
tasks no tier has ever held.

The compiler is pure control plane + functional jax calls: it owns no
engine state.  The engine drives it (``step``), installs finished
prefixes into its store (handling paged LRU/`PrefixSeatedError`
deferral), and wakes the scheduler's ``waiting_on_prefix`` requests.
See docs/ARCHITECTURE.md for the request lifecycle.
"""

from __future__ import annotations

import itertools
from collections import OrderedDict
from dataclasses import dataclass, field, replace
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import ModelConfig
from repro.core import memcom
from repro.serving.prefix_store import materialize_prefix
from repro.sharding.rules import BASELINE_RULES
from repro.sharding.serving import constrain_cache


def pow2_bucket(n: int, floor: int) -> int:
    """Snap ``n`` up to a power of two, at least ``floor`` — the one
    bucketing rule for every shape the serving path compiles against
    (engine prefill widths, compiler source-cache lengths)."""
    return max(floor, 1 << (max(1, n) - 1).bit_length())


def _bucket_len(n: int) -> int:
    """Source-cache lengths snap to powers of two (min 16): the chunk
    programs are keyed by (offset, width, cache_len), so tasks of similar
    size share compilations; the unused cache tail is never read."""
    return pow2_bucket(n, 16)

#: job lifecycle (the ``compiling`` stage of the request lifecycle)
_STAGES = ("queued", "compiling", "compiled", "installed")


@dataclass
class CompileJob:
    """One task's compilation: raw shot tokens → materialized prefix.

    ``status``: ``queued`` (no chunk run yet) → ``compiling`` (source
    cache live, ``consumed`` of ``len(tokens)`` processed) → ``compiled``
    (materialized prefix ready, not yet resident in the engine's store —
    installation can be deferred under paged seat pressure) →
    ``installed``.
    """

    name: str
    tokens: np.ndarray                         # (T,) int32 shot tokens
    status: str = "queued"
    consumed: int = 0
    state: Optional[memcom.CompressionState] = None
    materialized: Optional[dict] = None        # set when status >= compiled
    widths: List[int] = field(default_factory=list)  # chunk widths run
    priority: int = 0                          # best class waiting on it
    seq: int = 0                               # submission order (FIFO ties)

    def __post_init__(self):
        self.tokens = np.asarray(self.tokens, np.int32).reshape(-1)
        if self.tokens.size == 0:
            raise ValueError(f"job {self.name!r}: empty shot set")

    @property
    def remaining(self) -> int:
        return len(self.tokens) - self.consumed


class PrefixCompiler:
    """Compiles raw many-shot prompts into materialized prefixes, a
    token-budgeted chunk at a time, with single-flight dedup per task.

    A mid-flight job always runs to completion first (one source cache
    lives at a time, so in-flight compile memory is bounded by one
    task's window regardless of queue depth); among queued jobs the best
    ``(priority, submission order)`` starts next — plain FIFO when every
    request shares one priority class.  ``step(budget)`` is the only
    compute entry point — the serving loop calls it between decode steps.
    """

    def __init__(self, compressor, cfg: ModelConfig, target_params, *,
                 impl: str = "auto", mesh=None, rules=None):
        if cfg.memcom is None:
            raise ValueError(f"{cfg.name}: ModelConfig.memcom is unset — "
                             "nothing to compile prefixes with")
        self.compressor = compressor
        self.cfg = cfg
        self.target_params = target_params
        self.impl = impl
        # tensor-parallel serving: the finish pass pins the materialized
        # per-layer KV to the engine's head-sharded pool layout, so a
        # fresh compile lands directly in the sharded store/pools — no
        # replicated detour (and no host gather) on the install path
        self.mesh = mesh
        self.rules = rules
        self._jobs: "OrderedDict[str, CompileJob]" = OrderedDict()
        self._seq = itertools.count()  # submission order for FIFO ties
        # compiled programs: chunk steps keyed by their static geometry
        # (offset, width, cache_len), the finish/materialize pass by its
        # chunk-width pattern.  All-but-last chunks share the budget width
        # and the cache length is pow2-bucketed, so same-bucket tasks
        # reuse programs; only the remainder chunk and the finish pass are
        # per-(T mod budget) — recurrent families forbid padding the last
        # chunk (pads would advance the SSM state).  Both caches are
        # LRU-bounded so a long-lived engine serving many task lengths
        # cannot accumulate programs forever.
        self._chunk_jit: "OrderedDict[Tuple[int, int, int], object]" = \
            OrderedDict()
        self._finish_jit: "OrderedDict[Tuple[Tuple[int, ...], int], object]" \
            = OrderedDict()
        self._jit_cache_cap = 64
        self.stats: Dict[str, int] = {
            "jobs": 0,          # distinct compilations started
            "deduped": 0,       # submits that joined an in-flight job
            "chunks": 0,        # compress_chunk calls
            "tokens": 0,        # source tokens consumed
            "compiled": 0,      # jobs finished (materialized)
        }

    # ---- queue side ----

    def submit(self, name: str, raw_shots, priority: int = 0) -> CompileJob:
        """Request compilation of ``raw_shots`` under ``name``.

        Single-flight: a second submit for a name whose job is still
        queued/compiling/compiled joins that job (first writer wins on
        the token content; the job takes the *best* priority class any
        joiner asked for).  Installed jobs were dropped from the table,
        so a name the store has since evicted is simply recompiled.
        """
        job = self._jobs.get(name)
        if job is not None:
            self.stats["deduped"] += 1
            job.priority = min(job.priority, priority)
            return job
        job = CompileJob(name=name, tokens=raw_shots, priority=priority,
                         seq=next(self._seq))
        self._jobs[name] = job
        self.stats["jobs"] += 1
        return job

    def job(self, name: str) -> CompileJob:
        return self._jobs[name]

    def has_compile_work(self) -> bool:
        """Any job still consuming source tokens?"""
        return any(j.status in ("queued", "compiling")
                   for j in self._jobs.values())

    def ready(self) -> List[str]:
        """Names compiled but not yet installed into the engine's store."""
        return [n for n, j in self._jobs.items() if j.status == "compiled"]

    def pending(self) -> bool:
        """Anything between submission and store residency?"""
        return any(j.status != "installed" for j in self._jobs.values())

    def mark_installed(self, name: str) -> None:
        """Drop a job once its prefix is store-resident.  The entry is
        deleted outright — keeping it would grow ``_jobs`` (and pin every
        task's shot tokens) for the engine's lifetime; a resubmit after a
        later store eviction simply opens a fresh job."""
        job = self._jobs.pop(name)
        assert job.status == "compiled", job.status
        job.status = "installed"
        job.materialized = None  # resident in the store now; drop our copy
        job.state = None

    # ---- compute side ----

    @staticmethod
    def _cached(cache: "OrderedDict", cap: int, key, make):
        fn = cache.get(key)
        if fn is None:
            fn = cache[key] = make()
            while len(cache) > cap:
                cache.popitem(last=False)  # drop the oldest program
        else:
            cache.move_to_end(key)
        return fn

    def chunk_body(self, offset: int):
        """The pure computation of one chunk step: ``(compressor, cache,
        tokens) -> (new_cache, hiddens)``.  Exposed unjitted so the
        engine's *fused* serving step can inline a compile chunk into
        the same program as the batched decode — one dispatch instead of
        a decode gap (see ``ServingEngine(fused_step=True)``)."""
        cfg, impl, mesh = self.cfg, self.impl, self.mesh

        def run(compressor, cache, tokens):
            state = memcom.CompressionState(cache=cache, offset=offset)
            state = memcom.compress_chunk(compressor, cfg, state, tokens,
                                          impl=impl, mesh=mesh)
            return state.cache, state.hiddens[0]

        return run

    def _chunk_fn(self, offset: int, width: int, cache_len: int):
        """One compiled chunk step.  Eager ``compress_chunk`` would
        re-trace its scans every call — the whole point of chunking
        (short, predictable gaps between decode steps) dies without jit —
        so chunk programs are compiled once per static geometry and
        reused across tasks."""
        body = self.chunk_body(offset)
        return self._cached(self._chunk_jit, self._jit_cache_cap,
                            (offset, width, cache_len),
                            lambda: jax.jit(body))

    def _finish_fn(self, widths: Tuple[int, ...], cache_len: int):
        """Compiled finish: Memory-LLM pass over the accumulated H^i +
        prefix packaging + materialization through the frozen target.
        One program in either budget mode — the Memory-LLM cross-attends
        *all* H^i at once, so this pass cannot be sliced the way the
        source pass can (the one decode gap chunking does not bound)."""
        cfg, impl, total = self.cfg, self.impl, sum(widths)
        mesh, rules = self.mesh, self.rules

        def make():
            def run(compressor, target_params, cache, hiddens):
                state = memcom.CompressionState(
                    cache=cache, offset=total, hiddens=list(hiddens))
                prefix, _ = memcom.finish_compress(compressor, cfg, state,
                                                   impl=impl, mesh=mesh)
                out = materialize_prefix(target_params, cfg, prefix)
                if mesh is not None:
                    out = constrain_cache(out, mesh,
                                          rules or BASELINE_RULES)
                return out

            return jax.jit(run)

        return self._cached(self._finish_jit, self._jit_cache_cap,
                            (widths, cache_len), make)

    def _live_job(self) -> Optional[CompileJob]:
        """The job the next chunk belongs to: one live source cache at a
        time, so a mid-flight job always runs to completion; otherwise
        the best ``(priority, seq)`` queued job starts — FIFO within a
        class."""
        job = next((j for j in self._jobs.values()
                    if j.status == "compiling"), None)
        if job is None:
            queued = [j for j in self._jobs.values() if j.status == "queued"]
            job = (min(queued, key=lambda j: (j.priority, j.seq))
                   if queued else None)
        return job

    def peek_chunk(self, token_budget: Optional[int] = None
                   ) -> Optional[Tuple[CompileJob, int, int, int]]:
        """Describe — and stage — the chunk the next :meth:`step` would
        run: ``(job, offset, width, cache_len)``, or None when no job
        has source tokens left.  Initializes the job's source cache
        (``begin_compress``) so ``job.state.cache`` is ready to feed a
        chunk program.  The engine's fused step uses this to key/trace
        its combined decode+compile program, then hands the result to
        :meth:`absorb_chunk`."""
        job = self._live_job()
        if job is None:
            return None
        if job.state is None:
            job.state = memcom.begin_compress(
                self.cfg, 1, _bucket_len(len(job.tokens)),
                mc_params=self.compressor, impl=self.impl)
            job.status = "compiling"
        w = (job.remaining if token_budget is None
             else min(job.remaining, token_budget))
        return job, job.consumed, w, _bucket_len(len(job.tokens))

    def chunk_tokens(self, job: CompileJob, width: int):
        """The (1, width) token slice the next chunk consumes."""
        return jnp.asarray(
            job.tokens[None, job.consumed:job.consumed + width])

    def absorb_chunk(self, job: CompileJob, cache, hid, width: int
                     ) -> List[str]:
        """Fold one chunk's result back into the job: advance the source
        state, bump the counters, and — when the last source token has
        been consumed — run the (jitted) finish/materialize pass.
        Returns ``[job.name]`` if the job just compiled, else ``[]``."""
        job.state = replace(job.state, cache=cache,
                            offset=job.consumed + width,
                            hiddens=job.state.hiddens + [hid])
        job.consumed += width
        job.widths.append(width)
        self.stats["chunks"] += 1
        self.stats["tokens"] += width
        if job.remaining:
            return []
        fn = self._finish_fn(tuple(job.widths),
                             _bucket_len(len(job.tokens)))
        job.materialized = fn(self.compressor, self.target_params,
                              job.state.cache, tuple(job.state.hiddens))
        job.state = None  # free the source cache
        job.status = "compiled"
        self.stats["compiled"] += 1
        return [job.name]

    def step(self, token_budget: Optional[int] = None) -> List[str]:
        """Advance compilation by up to ``token_budget`` source tokens
        (``None`` = run the head job to completion — the stalled
        baseline).  Returns the names that finished this call."""
        finished: List[str] = []
        budget = token_budget
        while budget is None or budget > 0:
            nxt = self.peek_chunk(budget)
            if nxt is None:
                break
            job, offset, w, cache_len = nxt
            fn = self._chunk_fn(offset, w, cache_len)
            cache, hid = fn(self.compressor, job.state.cache,
                            self.chunk_tokens(job, w))
            finished += self.absorb_chunk(job, cache, hid, w)
            if budget is not None:
                budget -= w
            elif finished:
                break  # None = one whole job, not the whole queue
        return finished
