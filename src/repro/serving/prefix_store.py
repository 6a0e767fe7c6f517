"""Materialized compressed prefixes: projection, storage, per-slot seating.

The compress → serve handoff (paper §1) in three steps:

1. :func:`materialize_prefix` pushes the compressor's per-layer output
   O^i through the frozen target's projections, yielding the layer-family
   cache entries (``attn → k/v``, ``mla → lat``, ``mamba → ssm``
   passthrough; see docs/ARCHITECTURE.md for the exact shapes).
2. :class:`PrefixStore` caches one materialized prefix per ICL task — the
   "many users, each with their own compressed task memory" serving shape.
3. :func:`seat_prefix_row` installs a stored prefix into *one batch slot*
   of a live engine cache, so different slots of the same decode batch can
   serve different tasks (:func:`write_prefix_to_cache` is the batch-wide
   variant kept for single-task serving and parity tests).

Layer caches use the Layerwise layout (``{"prefix": [...], "period":
{"l0": stacked, ...}}``); prefix-section leaves carry the batch on axis 0,
period-section leaves on axis 1 (axis 0 is the scan's ``repeats`` dim).
"""

from __future__ import annotations

from collections import OrderedDict
from functools import partial
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.kernels import ops
from repro.models.attention import project_kv
from repro.models.mla import latent_rows  # the latent cache's rows
from repro.models.transformer import POOL_KEYS as _KV_KEYS
from repro.serving.block_pool import BlockAllocator


def materialize_prefix(target_params, cfg: ModelConfig, prefix):
    """Turn {"h": O^i} entries into precomputed compressed caches:
    attn -> {"k","v"}; mla -> {"lat"} (rows ``[ckv | k_rope]`` through
    the target's ``wdkv``/``kv_norm`` and rotary positions 0..m-1);
    mamba -> passthrough state."""

    def project(desc, layer_params, entry):
        if "h" not in entry:
            return entry
        h = entry["h"]
        B, m = h.shape[0], h.shape[1]
        pos = jnp.broadcast_to(jnp.arange(m, dtype=jnp.int32), (B, m))
        if cfg.mrope_sections:
            pos = jnp.broadcast_to(pos, (3, B, m))
        if desc.mixer == "mla":
            return {"lat": latent_rows(layer_params["attn"], cfg, h, pos)}
        k, v = project_kv(layer_params["attn"], cfg, h, pos)
        return {"k": k, "v": v}

    out = {}
    if "prefix" in prefix:
        out["prefix"] = [
            project(desc, target_params[f"prefix_{i}"], prefix["prefix"][i])
            for i, desc in enumerate(cfg.layout.prefix)
        ]
    if "period" in prefix:
        period = {}
        for j, desc in enumerate(cfg.layout.period):
            key = f"l{j}"
            entry = prefix["period"][key]
            lp = jax.tree.map(lambda x: x, target_params["period"][key])
            fn = partial(project, desc)
            period[key] = jax.vmap(fn)(lp, entry)  # map over stacked layers
        out["period"] = period
    return out


def write_prefix_to_cache(cfg: ModelConfig, cache, prefix):
    """Seat compressed memory slots at cache positions [0, m) — batch-wide
    (row b of the materialized prefix lands in slot b)."""

    def seat(c, p):
        c = dict(c)
        for key in _KV_KEYS:
            if key in p:
                c[key] = jax.lax.dynamic_update_slice_in_dim(
                    c[key], p[key].astype(c[key].dtype), 0, axis=1)
        if "ssm" in p:
            c["ssm"] = p["ssm"].astype(c["ssm"].dtype)
        return c

    out = {}
    if "prefix" in cache:
        out["prefix"] = [seat(c, p) for c, p in
                         zip(cache["prefix"], prefix.get("prefix", []))]
    if "period" in cache:
        out["period"] = {}
        for key, c in cache["period"].items():
            p = prefix.get("period", {}).get(key)
            if p is None:
                out["period"][key] = c
                continue
            # both stacked on the layer dim: seat per-layer via vmap
            out["period"][key] = jax.vmap(seat)(c, p)
    return out


# ---------------------------------------------------------------------------
# Per-slot seating
# ---------------------------------------------------------------------------


def _map_rowwise(cache, other, fn):
    """Apply ``fn(cache_entry, other_entry, batch_axis)`` across both
    Layerwise sections (batch axis 0 for prefix entries, 1 for period)."""
    out = {}
    if "prefix" in cache:
        out["prefix"] = [
            fn(c, other["prefix"][i] if other else None, 0)
            for i, c in enumerate(cache["prefix"])
        ]
    if "period" in cache:
        out["period"] = {
            key: fn(c, (other or {}).get("period", {}).get(key), 1)
            for key, c in cache["period"].items()
        }
    return out


def clear_slot_state(cache, slot: int):
    """Zero one slot's recurrent state (mamba conv/ssm) ahead of a refill.

    KV entries don't need clearing — stale keys beyond a slot's length are
    masked by the per-slot decode path — but SSM/conv prefill *continues*
    from the cached state, so a refilled slot must not inherit its previous
    occupant's recurrence.
    """

    def clear(c, _p, axis):
        c = dict(c)
        for key in ("conv", "ssm"):
            if key in c:
                idx = (slot,) if axis == 0 else (slice(None), slot)
                c[key] = c[key].at[idx].set(0)
        return c

    return _map_rowwise(cache, None, clear)


def seat_prefix_row(cache, row, slot: int):
    """Install a single-task prefix (one :class:`PrefixStore` entry) into
    batch slot ``slot`` of a live cache: KV entries land at positions
    [0, m) of that slot's rows; SSM state replaces the slot's state."""

    def seat(c, p, axis):
        if p is None:
            return c
        c = dict(c)
        for key in _KV_KEYS:
            if key in p:
                # batch-free row leaves put m where the cache keeps batch
                m = p[key].shape[axis]
                idx = (slot, slice(0, m)) if axis == 0 else \
                    (slice(None), slot, slice(0, m))
                c[key] = c[key].at[idx].set(p[key].astype(c[key].dtype))
        if "ssm" in p:
            idx = (slot,) if axis == 0 else (slice(None), slot)
            c["ssm"] = c["ssm"].at[idx].set(p["ssm"].astype(c["ssm"].dtype))
        return c

    return _map_rowwise(cache, row, seat)


def take_prefix_row(materialized, batch_index: int = 0):
    """Extract one batch row of a :func:`materialize_prefix` output as a
    batch-free per-layer row dict."""

    def take_row(c, _p, axis):
        out = {}
        for key, x in c.items():
            out[key] = x[batch_index] if axis == 0 else x[:, batch_index]
        return out

    return _map_rowwise(materialized, None, take_row)


class PrefixStore:
    """In-memory cache of materialized compressed prefixes, one per task.

    Entries are stored batch-free (a single task's per-layer cache rows);
    :meth:`put` extracts one batch row from a :func:`materialize_prefix`
    output, and engines seat entries into individual slots via
    :func:`seat_prefix_row`.

    ``capacity`` (optional) bounds resident prefixes LRU-style, like the
    paged store: inserting past capacity evicts the least-recently-used
    entry not in :attr:`pinned`.  Dense seating *copies* a prefix into
    the slot's cache stripe, so — unlike the paged store — evicting a
    seated entry is safe and never raises.

    ``demote_hook`` (set by :class:`~repro.serving.tiers
    .TieredPrefixStore`) receives ``(name, row)`` just before an entry is
    dropped, so evictions demote the prefix down the memory hierarchy
    instead of destroying it.
    """

    def __init__(self, cfg: ModelConfig, capacity: Optional[int] = None):
        if capacity is not None and capacity < 1:
            raise ValueError("capacity must be >= 1 (or None)")
        self.cfg = cfg
        self.capacity = capacity
        self._entries: "OrderedDict[str, dict]" = OrderedDict()
        self._base_len: Dict[str, int] = {}
        self.stats = _new_store_stats()
        self.pinned: set = set()  # names the LRU must skip (engine-kept)
        self.demote_hook = None   # called (name, row) before an evict drops

    def put(self, name: str, materialized, batch_index: int = 0) -> str:
        return self.put_row(name, take_prefix_row(materialized, batch_index))

    def put_row(self, name: str, row) -> str:
        """Make an already batch-free per-layer row resident (the tiered
        promotion path lands here — no materialized batch to slice)."""
        if name not in self._entries:
            while self.capacity is not None and \
                    len(self._entries) >= self.capacity:
                self._evict_lru()
        self._entries[name] = row
        self._entries.move_to_end(name)
        self._base_len[name] = _row_base_len(row)
        self.stats["puts"] += 1
        return name

    def _evict_lru(self) -> None:
        for name in self._entries:  # oldest first
            if name not in self.pinned:
                self.evict(name)
                return
        raise PrefixSeatedError(
            f"PrefixStore at capacity ({self.capacity}) and every resident "
            "prefix is pinned by a queued or waiting request — grow the "
            "capacity or finish requests")

    def lookup(self, name: str) -> bool:
        """Counted residency check — the serve-path ``hit``/``miss``
        counters exposed through ``ServingEngine.stats()``."""
        hit = name in self._entries
        self.stats["hits" if hit else "misses"] += 1
        return hit

    def evict(self, name: str, demote: bool = True) -> None:
        """``demote=False`` skips the hook — for replace-path evictions,
        where fresh content supersedes the old copy and demoting it would
        only waste a device→host copy (and possibly spill an innocent
        LRU host row)."""
        self._check(name)
        if demote and self.demote_hook is not None:
            # Dense entries own their KV arrays outright — no pool blocks,
            # no seating — so eviction can never race a seated slot; the
            # raise-before-demote guard is a paged-store concern.
            # reprolint: ignore[demote-guard] -- dense KV is owned, not pooled
            self.demote_hook(name, self._entries[name])
        del self._entries[name]
        del self._base_len[name]
        self.stats["evictions"] += 1

    def get(self, name: str) -> dict:
        self._check(name)
        self._entries.move_to_end(name)  # LRU recency
        return self._entries[name]

    def base_len(self, name: str) -> int:
        """Memory-slot count the prefix occupies at the cache front
        (0 for pure state handoff, e.g. mamba-only prefixes)."""
        self._check(name)
        return self._base_len[name]

    def _check(self, name: str) -> None:
        if name not in self._entries:
            raise KeyError(f"unknown prefix {name!r}; registered: "
                           f"{sorted(self._entries) or '(none)'}")

    def __contains__(self, name) -> bool:
        return name in self._entries

    def __len__(self) -> int:
        return len(self._entries)

    def names(self):
        return tuple(self._entries)


# ---------------------------------------------------------------------------
# Paged (block-resident) prefixes
# ---------------------------------------------------------------------------


def write_prefix_row_to_blocks(cache, row, block_ids: List[int]):
    """Scatter a batch-free prefix row's KV leaves into pool blocks.

    ``block_ids`` are the physical blocks holding logical positions
    ``[0, m)``; every layer writes the *same* block ids into its own pool
    (one block table resolves every layer, vLLM-style).  Non-KV leaves
    (ssm state) are left for per-slot seating via :func:`seat_prefix_row`.
    """
    ids = jnp.asarray(block_ids, jnp.int32)[None, :]  # (1, nbt)
    zero = jnp.zeros((1,), jnp.int32)

    def scatter(pool, new):
        # rows given as (m, Hkv, hd) land lane-merged in an attention pool
        return ops.paged_scatter(pool, new[None], ids, zero)

    def write(c, p, axis):
        c = dict(c)
        for key in _KV_KEYS:
            if key in p:
                if axis == 0:  # prefix section: pool (N, bs, ...), row (m, ...)
                    c[key] = scatter(c[key], p[key])
                else:  # period: pool (repeats, N, bs, ...), row (repeats, m, ...)
                    c[key] = jax.vmap(scatter)(c[key], p[key])
        return c

    return _map_rowwise(cache, row, write)


def copy_paged_block(cache, src: int, dst: int):
    """Device-side copy of one physical block across every KV pool leaf —
    the copy-on-write when a slot must write into a shared partial block."""

    def cp(c, _p, axis):
        c = dict(c)
        for key in _KV_KEYS:
            if key in c:
                if axis == 0:
                    c[key] = c[key].at[dst].set(c[key][src])
                else:
                    c[key] = c[key].at[:, dst].set(c[key][:, src])
        return c

    return _map_rowwise(cache, None, cp)


def strip_kv_leaves(row) -> Optional[dict]:
    """Drop block-resident KV leaves from a prefix row, keeping per-slot
    state (ssm handoff).  Returns None when nothing remains to seat."""
    found = [False]

    def strip(c, _p, axis):
        out = {k: v for k, v in c.items() if k not in _KV_KEYS}
        if out:
            found[0] = True
        return out

    stripped = _map_rowwise(row, None, strip)
    return stripped if found[0] else None


class PrefixSeatedError(RuntimeError):
    """Refused to evict a prefix whose blocks are still seated in slots."""


class PagedPrefixStore:
    """Block-resident compressed prefixes with ref-counts and LRU eviction.

    The paged counterpart of :class:`PrefixStore`: ``put`` scatters a
    task's materialized KV into freshly allocated pool blocks *once*;
    engines seat a task into a slot by pointing the slot's block table at
    those blocks (``blocks()`` + ``BlockAllocator.incref``), so N slots on
    one task share one physical copy.  The store holds one reference per
    resident prefix; a block's refcount therefore exceeds 1 exactly while
    some slot is seated on it.

    ``capacity`` bounds the number of resident prefixes LRU-style:
    inserting past capacity evicts the least-recently-used *unseated*
    entry (seated entries are deferred — skipped over); if every resident
    prefix is seated, :class:`PrefixSeatedError` is raised.  Explicitly
    evicting a seated prefix always raises.
    """

    def __init__(self, cfg: ModelConfig, allocator: BlockAllocator,
                 capacity: Optional[int] = None):
        if capacity is not None and capacity < 1:
            raise ValueError("capacity must be >= 1 (or None)")
        self.cfg = cfg
        self.alloc = allocator
        self.capacity = capacity
        self._entries: "OrderedDict[str, dict]" = OrderedDict()
        self.stats = _new_store_stats()
        # names the LRU must skip even when unseated: the engine keeps this
        # set at the prefixes still referenced by queued or waiting_on_prefix
        # requests (a parked request's freshly compiled prefix must survive
        # until that request seats it)
        self.pinned: set = set()
        # tiered serving: called (name, entry) after the seated guard but
        # before the blocks are released, so an evicted prefix's KV can be
        # read back out of the pool and demoted to host instead of dropped
        self.demote_hook = None

    def lookup(self, name: str) -> bool:
        """Counted residency check (see :meth:`PrefixStore.lookup`)."""
        hit = name in self._entries
        self.stats["hits" if hit else "misses"] += 1
        return hit

    def put(self, name: str, materialized, cache, batch_index: int = 0):
        """Make ``materialized`` row ``batch_index`` block-resident under
        ``name``.  Returns the updated Layerwise cache (pools are
        functional jax arrays).  Re-putting an existing name replaces it —
        which requires the old entry to be unseated."""
        return self.put_row(name, take_prefix_row(materialized, batch_index),
                            cache)

    def put_row(self, name: str, row, cache):
        """:meth:`put` for an already batch-free row (the tiered
        promotion path: host leaves land on device pre-sharded, then
        scatter straight into pool blocks here)."""
        if name in self._entries:
            # replace: raises PrefixSeatedError if still seated; the old
            # copy is superseded, not demoted
            self.evict(name, demote=False)
        while self.capacity is not None and len(self._entries) >= self.capacity:
            self._evict_lru()
        base_len = _row_base_len(row)
        blocks = self.alloc.alloc(self.alloc.blocks_for(base_len))
        if blocks:
            cache = write_prefix_row_to_blocks(cache, row, blocks)
        self._entries[name] = {
            "blocks": blocks,
            "base_len": base_len,
            "state": strip_kv_leaves(row),
        }
        self.stats["puts"] += 1
        return cache

    def _evict_lru(self) -> None:
        for name, entry in self._entries.items():  # oldest first
            if name not in self.pinned and not self._seated(entry):
                self.evict(name)
                return
        raise PrefixSeatedError(
            f"PrefixStore at capacity ({self.capacity}) and every resident "
            "prefix is seated in a slot or pinned by a waiting request — "
            "grow the pool or finish requests")

    def _seated(self, entry) -> bool:
        return any(self.alloc.refcount(b) > 1 for b in entry["blocks"])

    def seated(self, name: str) -> bool:
        """True while at least one engine slot points at this prefix's
        blocks (the store's own reference is not counted)."""
        return self._seated(self._get(name, touch=False))

    def evict(self, name: str, demote: bool = True) -> None:
        """Release a prefix's blocks back to the pool.  Raises
        :class:`PrefixSeatedError` while any slot is still seated on it —
        freeing blocks under a live block table would let the allocator
        hand them to another slot mid-decode.  ``demote=False`` skips the
        hook (replace-path evictions supersede the old copy)."""
        entry = self._get(name, touch=False)
        if self._seated(entry):
            raise PrefixSeatedError(
                f"prefix {name!r} is seated in at least one slot")
        if demote and self.demote_hook is not None:
            # the hook gathers the KV out of the pool while the blocks
            # are still referenced (and therefore still hold this prefix)
            self.demote_hook(name, entry)
        for b in entry["blocks"]:
            self.alloc.decref(b)
        del self._entries[name]
        self.stats["evictions"] += 1

    # ---- lookups (refresh LRU recency) ----

    def blocks(self, name: str) -> List[int]:
        return list(self._get(name)["blocks"])

    def base_len(self, name: str) -> int:
        return self._get(name)["base_len"]

    def state_row(self, name: str) -> Optional[dict]:
        return self._get(name)["state"]

    def _get(self, name: str, touch: bool = True) -> dict:
        if name not in self._entries:
            raise KeyError(f"unknown prefix {name!r}; registered: "
                           f"{sorted(self._entries) or '(none)'}")
        if touch:
            self._entries.move_to_end(name)
        return self._entries[name]

    def __contains__(self, name) -> bool:
        return name in self._entries

    def __len__(self) -> int:
        return len(self._entries)

    def names(self):
        return tuple(self._entries)


def _new_store_stats() -> Dict[str, int]:
    """Cache-behaviour counters both stores expose via
    ``ServingEngine.stats()``: serve-path residency ``hits``/``misses``
    (:meth:`PrefixStore.lookup`), entries made resident (``puts``) and
    entries released (``evictions`` — LRU, explicit, and re-put
    replacement alike)."""
    return {"hits": 0, "misses": 0, "puts": 0, "evictions": 0}


def _row_base_len(row) -> int:
    """Slot count of a batch-free prefix row: the m dim of its first KV
    leaf (prefix-section KV leaves are (m, ...); period (repeats, m, ...))."""
    for e in row.get("prefix", []):
        for key in _KV_KEYS:
            if key in e:
                return int(e[key].shape[0])
    for e in row.get("period", {}).values():
        for key in _KV_KEYS:
            if key in e:
                return int(e[key].shape[1])
    return 0
