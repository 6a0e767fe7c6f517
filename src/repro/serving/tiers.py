"""Tiered prefix cache: HBM ↔ pinned-host ↔ disk for compressed prefixes.

MemCom's value proposition is that a task's many-shots compress *once*
into a small per-layer soft-token summary reused across every request
for that task — but the HBM stores alone make eviction destructive:
under multi-tenant pressure an LRU'd prefix forces a full online
recompile (``serving/compiler.py``) on the next request, paying the
compression cost the paper amortized away.  :class:`TieredPrefixStore`
turns eviction into *demotion* down a memory hierarchy:

    HBM (PrefixStore / PagedPrefixStore)      seat-ready, device arrays
      │ evict ──▶ demote                 ▲ promote (chunked, async)
      ▼                                  │
    host tier (pinned RAM, numpy rows)  ─┘
      │ over host_capacity ──▶ spill     ▲ load (counted ``disk_loads``)
      ▼                                  │
    disk tier (one codec-compressed shard per prefix) ────────┘

* **Demote** — the stores' ``demote_hook`` fires on every evict (LRU and
  explicit alike): dense entries copy to host numpy; paged entries
  gather their KV back out of the pool blocks (plus the stripped
  per-slot state from ``strip_kv_leaves``) *before* the blocks are
  released, reconstructing the same batch-free row the dense store
  keeps.  A prefix seated in a live slot still raises
  :class:`~repro.serving.prefix_store.PrefixSeatedError` — nothing is
  ever demoted out from under a slot.
* **Spill** — past ``host_capacity`` the LRU host row is written to
  ``disk_dir`` as a single shard (msgpack header + one compressed blob,
  reusing :func:`repro.checkpoint.store.compress_bytes` — zstd with
  zlib fallback, codec recorded in the header).  Shards are committed
  with an atomic rename and re-indexed on startup, so a restarted
  server promotes yesterday's prefixes instead of recompiling them.
* **Promote** — a request naming a cold prefix parks in the scheduler's
  ``waiting_on_prefix`` stage (exactly like a compiling task) while the
  engine copies the row host→HBM in **per-layer chunks**, at most
  ``promote_layer_budget`` chunks between decode steps (mirroring
  ``compile_token_budget``), so seated slots keep emitting tokens
  through a promotion.  On a mesh each chunk is ``device_put`` with its
  pool-layout :func:`~repro.sharding.serving.leaf_sharding`, so
  promotion lands pre-sharded — no replicated detour, no host
  gather/scatter round-trip.

Tiers are **exclusive** (a name lives in exactly one tier) and moves
are **bit-exact**: the row that comes back up is byte-identical to the
one that went down, so a request's greedy output cannot depend on which
tier its prefix was served from (asserted in ``tests/test_tiers.py``).

The class fronts the HBM store: residency checks (``in``, ``lookup``)
and all seat-path lookups delegate, so the engine's seating/refcount
logic is tier-oblivious.  See docs/ARCHITECTURE.md §"Prefix memory
hierarchy".
"""

from __future__ import annotations

import hashlib
import itertools
import os
import struct
from collections import OrderedDict, deque
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import msgpack
import numpy as np

from repro.checkpoint.store import compress_bytes, decompress_bytes
from repro.serving.prefix_store import (
    _KV_KEYS,
    PagedPrefixStore,
    _map_rowwise,
    _row_base_len,
)

__all__ = ["TieredPrefixStore", "PromotionJob"]

_SHARD_SUFFIX = ".prefix"
_MAGIC = b"MCPF"  # MemCom prefix shard
_VERSION = 1


def _host_tree(tree):
    """Device tree → host numpy tree (bit-exact copy)."""
    return jax.tree.map(np.asarray, tree)


def _tree_nbytes(tree) -> int:
    return sum(int(x.nbytes) for x in jax.tree.leaves(tree))


@dataclass
class PromotionJob:
    """One prefix's asynchronous host→HBM copy.

    ``pending`` holds per-layer host chunks (prefix-section entries plus
    per-repeat slices of the stacked period sections); the engine drains
    up to ``promote_layer_budget`` of them between decode steps.  When
    the last chunk lands, the device row is assembled and the job turns
    ``ready`` — the engine installs it into the HBM store (with the same
    paged-pressure deferral as a compiled prefix) and wakes the parked
    requests.
    """

    name: str
    source: str                       # "host" | "disk"
    host_row: dict                    # the full host row (structure + state)
    base_len: int
    pending: deque = field(default_factory=deque)
    dev_prefix: Dict[int, dict] = field(default_factory=dict)
    dev_period: Dict[str, Dict[int, dict]] = field(default_factory=dict)
    status: str = "promoting"         # -> "ready" (installed jobs are dropped)
    row: Optional[dict] = None        # assembled device row when ready
    total_chunks: int = 0
    priority: int = 0                 # best class waiting on it
    seq: int = 0                      # submission order (FIFO ties)

    @property
    def remaining(self) -> int:
        return len(self.pending)


class TieredPrefixStore:
    """HBM store front with pinned-host and disk tiers behind it.

    Wraps a :class:`~repro.serving.prefix_store.PrefixStore` or
    :class:`~repro.serving.prefix_store.PagedPrefixStore` (``hbm``):
    every seat-path method the engine uses (``lookup``, ``put``,
    ``blocks``, ``base_len``, ``state_row``, ``evict``, ``in``, …)
    behaves exactly like the wrapped store, while evictions demote and
    :meth:`submit_promotion` / :meth:`promote_step` implement the
    budgeted upward path.

    ``host_capacity`` bounds the host tier (``None`` = unbounded; ``0``
    = demotions go straight to disk); past it the LRU host row spills to
    ``disk_dir`` (or, with no disk tier, is dropped — counted).
    """

    def __init__(self, hbm, *, host_capacity: Optional[int] = None,
                 disk_dir: Optional[str] = None, mesh=None, rules=None,
                 cache_ref=None):
        if host_capacity is not None and host_capacity < 0:
            raise ValueError("host_capacity must be >= 0 (or None)")
        self.hbm = hbm
        self.host_capacity = host_capacity
        self.disk_dir = disk_dir
        self.mesh = mesh
        self.rules = rules
        # paged demotion reads the evicted blocks back out of the live
        # pools, which the engine owns functionally — this thunk returns
        # the engine's current cache at demotion time
        self._cache_ref = cache_ref
        self._host: "OrderedDict[str, dict]" = OrderedDict()
        self._host_base: Dict[str, int] = {}
        self._disk: Dict[str, str] = {}       # name -> shard path
        self._disk_base: Dict[str, int] = {}
        self._jobs: "OrderedDict[str, PromotionJob]" = OrderedDict()
        self._job_seq = itertools.count()  # submission order for FIFO ties
        self.tier_stats: Dict[str, int] = {
            "hbm_hits": 0,        # serve-path lookups answered from HBM
            "host_promotes": 0,   # completed host→HBM promotions
            "disk_loads": 0,      # shards read (disk→promotion path)
            "demotes": 0,         # HBM evictions captured into the host tier
            "spills": 0,          # host rows written to disk
            "promote_bytes": 0,   # bytes copied host→HBM
            "promote_chunks": 0,  # per-layer chunks copied host→HBM
            "host_drops": 0,      # host-pressure casualties with no disk tier
        }
        hbm.demote_hook = self._demote
        if disk_dir:
            os.makedirs(disk_dir, exist_ok=True)
            self._scan_disk()

    # ------------------------------------------------------------------
    # HBM front (the engine's store API)
    # ------------------------------------------------------------------

    def __getattr__(self, attr):
        # everything not overridden (get/blocks/base_len/state_row/
        # seated/evict/capacity/alloc/...) behaves as the HBM store
        if attr == "hbm":  # guard: never recurse before __init__ ran
            raise AttributeError(attr)
        return getattr(self.hbm, attr)

    def __contains__(self, name) -> bool:
        return name in self.hbm  # residency == seatable == HBM

    def __len__(self) -> int:
        return len(self.hbm)

    @property
    def stats(self):
        return self.hbm.stats

    @property
    def pinned(self):
        return self.hbm.pinned

    @pinned.setter
    def pinned(self, names):
        self.hbm.pinned = names

    def names(self) -> Tuple[str, ...]:
        """Every tier's names, hottest tier first (HBM, host, disk)."""
        return tuple(dict.fromkeys(
            tuple(self.hbm.names()) + tuple(self._host) + tuple(self._disk)))

    def lookup(self, name: str) -> bool:
        hit = name in self.hbm
        if hit:
            self.tier_stats["hbm_hits"] += 1
        return self.hbm.lookup(name)

    def put(self, name: str, materialized, *args, **kwargs):
        out = self.hbm.put(name, materialized, *args, **kwargs)
        self._forget_cold(name)  # fresh content supersedes any cold copy
        return out

    def put_row(self, name: str, row, *args, **kwargs):
        out = self.hbm.put_row(name, row, *args, **kwargs)
        self._forget_cold(name)
        return out

    # ------------------------------------------------------------------
    # Cold residency
    # ------------------------------------------------------------------

    def tier_of(self, name: str) -> Optional[str]:
        """"hbm" | "host" | "disk" | "promoting" | None."""
        if name in self.hbm:
            return "hbm"
        if name in self._jobs:
            return "promoting"
        if name in self._host:
            return "host"
        if name in self._disk:
            return "disk"
        return None

    def cold_resident(self, name: str) -> bool:
        """True when ``name`` is recoverable without recompiling — in the
        host or disk tier, or already mid-promotion."""
        return self.tier_of(name) in ("host", "disk", "promoting")

    def cold_base_len(self, name: str) -> int:
        """base_len of a not-yet-promoted prefix (request validation)."""
        if name in self._jobs:
            return self._jobs[name].base_len
        if name in self._host:
            return self._host_base[name]
        if name in self._disk:
            return self._disk_base[name]
        raise KeyError(f"prefix {name!r} is not in a cold tier")

    def host_names(self) -> Tuple[str, ...]:
        return tuple(self._host)

    def disk_names(self) -> Tuple[str, ...]:
        return tuple(self._disk)

    def _forget_cold(self, name: str) -> None:
        self._host.pop(name, None)
        self._host_base.pop(name, None)
        self._jobs.pop(name, None)
        path = self._disk.pop(name, None)
        self._disk_base.pop(name, None)
        if path is not None and os.path.exists(path):
            os.remove(path)

    # ------------------------------------------------------------------
    # Downward path: demote (HBM→host) and spill (host→disk)
    # ------------------------------------------------------------------

    def demote(self, name: str) -> None:
        """Evict ``name`` from HBM, capturing it into the host tier
        (raises :class:`PrefixSeatedError` while any slot is seated on
        it — the hook only fires after the wrapped store's guard)."""
        self.hbm.evict(name)

    def _demote(self, name: str, payload) -> None:
        """The stores' ``demote_hook``: dense hands the device row, paged
        hands its ``{"blocks", "base_len", "state"}`` entry (blocks still
        referenced, so the pool still holds this prefix's KV)."""
        if isinstance(self.hbm, PagedPrefixStore):
            row = self._gather_paged(payload)
        else:
            row = _host_tree(payload)
        self._host_insert(name, row)
        self.tier_stats["demotes"] += 1

    def _gather_paged(self, entry) -> dict:
        """Read a paged prefix back out of the pool blocks into the same
        batch-free row layout the dense store keeps: KV gathered from
        positions ``[0, base_len)`` of the entry's blocks, merged with
        the stripped per-slot state (ssm handoff)."""
        cache = self._cache_ref()
        base = int(entry["base_len"])
        ids = jnp.asarray(list(entry["blocks"]), jnp.int32)
        cfg = self.hbm.cfg

        def take(c, _p, axis):
            out = {}
            if base == 0:
                return out
            for key in _KV_KEYS:
                if key in c:
                    if axis == 0:     # pool (N, bs, ...), row (m, ...)
                        g = jnp.take(c[key], ids, axis=0)
                        g = g.reshape((-1,) + g.shape[2:])[:base]
                    else:             # pool (R, N, bs, ...), row (R, m, ...)
                        g = jnp.take(c[key], ids, axis=1)
                        g = g.reshape(g.shape[:1] + (-1,) + g.shape[3:])
                        g = g[:, :base]
                    if key in ("k", "v"):  # lane-merged rows -> (Hkv, hd)
                        lanes = cfg.num_kv_heads * cfg.hd
                        g = g[..., :lanes].reshape(
                            g.shape[:-1] + (cfg.num_kv_heads, cfg.hd))
                    elif key == "lat":  # latent rows, without the pad lanes
                        g = g[..., :cfg.mla.latent_dim]
                    out[key] = np.asarray(g)
            return out

        row = _map_rowwise(cache, None, take)
        state = entry.get("state")
        if state is not None:
            host_state = _host_tree(state)
            for i, e in enumerate(host_state.get("prefix", [])):
                row["prefix"][i].update(e)
            for key, e in host_state.get("period", {}).items():
                row["period"][key].update(e)
        return row

    def _host_insert(self, name: str, row: dict) -> None:
        self._host[name] = row
        self._host.move_to_end(name)
        self._host_base[name] = _row_base_len(row)
        while self.host_capacity is not None and \
                len(self._host) > self.host_capacity:
            if not self._spill_lru():
                break  # everything left is mid-promotion; run over budget

    def _spill_lru(self) -> bool:
        for name in self._host:  # oldest first
            if name in self._jobs:
                continue  # a promotion is reading this row; skip it
            row = self._host.pop(name)
            base = self._host_base.pop(name)
            if self.disk_dir:
                self.spill_row(name, row, base)
            else:
                self.tier_stats["host_drops"] += 1
            return True
        return False

    def spill(self, name: str) -> str:
        """Explicitly move one host row to disk; returns the shard path."""
        if name not in self._host:
            raise KeyError(f"prefix {name!r} is not in the host tier")
        row = self._host.pop(name)
        base = self._host_base.pop(name)
        return self.spill_row(name, row, base)

    def spill_row(self, name: str, row: dict, base_len: int) -> str:
        if not self.disk_dir:
            raise ValueError("no disk tier configured (disk_dir is unset)")
        path = self._shard_path(name)
        self._write_shard(path, name, row, base_len)
        self._disk[name] = path
        self._disk_base[name] = base_len
        self.tier_stats["spills"] += 1
        return path

    # ------------------------------------------------------------------
    # Upward path: budgeted, chunked promotion
    # ------------------------------------------------------------------

    def submit_promotion(self, name: str, priority: int = 0) -> PromotionJob:
        """Start (or join — single-flight per name) the host→HBM copy of
        a cold prefix.  A disk-resident prefix is loaded into the job
        first (counted ``disk_loads``); its shard stays on disk until the
        promoted row is installed.  The job takes the best priority class
        any joiner asked for; :meth:`promote_step` serves jobs in
        ``(priority, submission order)`` order."""
        job = self._jobs.get(name)
        if job is not None:
            job.priority = min(job.priority, priority)
            return job
        if name in self._host:
            row, source = self._host[name], "host"
            self._host.move_to_end(name)
        elif name in self._disk:
            row = self._read_shard(self._disk[name])
            self.tier_stats["disk_loads"] += 1
            source = "disk"
        else:
            raise KeyError(f"prefix {name!r} is not in a cold tier; "
                           f"tiers: {self.names() or '(none)'}")
        job = PromotionJob(name=name, source=source, host_row=row,
                           base_len=_row_base_len(row), priority=priority,
                           seq=next(self._job_seq))
        for i, entry in enumerate(row.get("prefix", [])):
            if entry:
                job.pending.append(("prefix", i, entry))
        for key, entry in row.get("period", {}).items():
            if not entry:
                continue
            repeats = next(iter(entry.values())).shape[0]
            for j in range(repeats):
                job.pending.append(
                    ("period", key, j, {k: v[j] for k, v in entry.items()}))
        job.total_chunks = len(job.pending)
        self._jobs[name] = job
        return job

    def has_promote_work(self) -> bool:
        return any(j.status == "promoting" for j in self._jobs.values())

    def ready_promotions(self) -> List[str]:
        return [n for n, j in self._jobs.items() if j.status == "ready"]

    def promoted_row(self, name: str) -> dict:
        job = self._jobs[name]
        assert job.status == "ready", job.status
        return job.row

    def promote_step(self, chunk_budget: Optional[int] = None) -> List[str]:
        """Copy up to ``chunk_budget`` per-layer chunks host→HBM (``None``
        = run the head job to completion — the stalled baseline).  Jobs
        advance in ``(priority, submission order)`` order — strictly FIFO
        when every request shares one class (already-copied chunks of a
        job a later, more urgent submission overtakes stay staged on
        device, so no work is lost).  Returns the names turned ready."""
        finished: List[str] = []
        budget = chunk_budget
        while True:
            promoting = [j for j in self._jobs.values()
                         if j.status == "promoting"]
            job = (min(promoting, key=lambda j: (j.priority, j.seq))
                   if promoting else None)
            if job is None or (budget is not None and budget <= 0):
                break
            n = job.remaining if budget is None else min(job.remaining, budget)
            for _ in range(n):
                self._copy_chunk(job, job.pending.popleft())
            if budget is not None:
                budget -= n
            if not job.pending:
                job.row = self._assemble(job)
                job.status = "ready"
                finished.append(job.name)
                if budget is None:
                    break  # None = one whole job, not the whole queue
        return finished

    def mark_promoted(self, name: str) -> None:
        """Count a completed promotion (the install's ``put_row`` already
        removed the job and the stale cold copies via ``_forget_cold`` —
        the move up the hierarchy is complete)."""
        self._jobs.pop(name, None)
        self.tier_stats["host_promotes"] += 1

    def _copy_chunk(self, job: PromotionJob, chunk) -> None:
        entry = chunk[-1]
        dev = {k: self._put_leaf(k, v) for k, v in entry.items()}
        if chunk[0] == "prefix":
            job.dev_prefix[chunk[1]] = dev
        else:
            job.dev_period.setdefault(chunk[1], {})[chunk[2]] = dev
        self.tier_stats["promote_chunks"] += 1
        self.tier_stats["promote_bytes"] += _tree_nbytes(entry)

    def _put_leaf(self, key: str, arr: np.ndarray):
        if self.mesh is None:
            return jnp.asarray(arr)
        return jax.device_put(arr, self._put_leaf_sharding(key, arr))

    def _assemble(self, job: PromotionJob) -> dict:
        """Reassemble the device row from the copied chunks, preserving
        the host row's structure (empty layer entries included)."""
        hr = job.host_row
        row: dict = {}
        if "prefix" in hr:
            row["prefix"] = [job.dev_prefix.get(i, {})
                             for i in range(len(hr["prefix"]))]
        if "period" in hr:
            row["period"] = {}
            for key, entry in hr["period"].items():
                layers = job.dev_period.get(key)
                if not layers:
                    row["period"][key] = {}
                    continue
                stacked = {
                    k: jnp.stack([layers[j][k] for j in range(len(layers))])
                    for k in layers[0]
                }
                if self.mesh is not None:
                    # device-to-device re-pin: stacking may have let GSPMD
                    # drift the layout; no host round-trip here
                    stacked = {k: jax.device_put(
                        v, self._put_leaf_sharding(k, v))
                        for k, v in stacked.items()}
                row["period"][key] = stacked
        return row

    def _put_leaf_sharding(self, key: str, arr):
        from repro.sharding.serving import BASELINE_RULES, leaf_sharding

        return leaf_sharding(key, arr, self.mesh,
                             self.rules or BASELINE_RULES)

    # ------------------------------------------------------------------
    # Disk shards (checkpoint codec machinery, one file per prefix)
    # ------------------------------------------------------------------

    def _shard_path(self, name: str) -> str:
        digest = hashlib.sha1(name.encode()).hexdigest()[:16]
        return os.path.join(self.disk_dir, digest + _SHARD_SUFFIX)

    def _write_shard(self, path: str, name: str, row: dict,
                     base_len: int) -> None:
        entries, raws, offset = [], [], 0
        for leaf_path, arr in _flatten_row(row):
            raw = np.asarray(arr).tobytes()
            entries.append({"path": leaf_path, "shape": list(arr.shape),
                            "dtype": str(arr.dtype), "offset": offset,
                            "nbytes": len(raw)})
            raws.append(raw)
            offset += len(raw)
        codec, blob = compress_bytes(b"".join(raws))
        # structure survives separately from the leaves: layer entries
        # with no leaves (and absent sections) must round-trip too
        structure = {"prefix_len": (len(row["prefix"])
                                    if "prefix" in row else None),
                     "period_keys": (sorted(row["period"])
                                     if "period" in row else None)}
        header = msgpack.packb({"version": _VERSION, "name": name,
                                "codec": codec, "base_len": base_len,
                                "structure": structure, "entries": entries})
        tmp = path + ".tmp"
        with open(tmp, "wb") as f:
            f.write(_MAGIC + struct.pack("<I", len(header)))
            f.write(header)
            f.write(blob)
        os.replace(tmp, path)  # atomic commit (mirrors checkpoint/store.py)

    def _read_header(self, f) -> dict:
        magic = f.read(len(_MAGIC))
        if magic != _MAGIC:
            raise ValueError(f"{f.name}: not a prefix shard "
                             f"(bad magic {magic!r})")
        (hlen,) = struct.unpack("<I", f.read(4))
        return msgpack.unpackb(f.read(hlen))

    def _read_shard(self, path: str) -> dict:
        import ml_dtypes  # noqa: F401  (registers bfloat16 with numpy)

        with open(path, "rb") as f:
            header = self._read_header(f)
            data = decompress_bytes(f.read(), header["codec"])
        leaves = {}
        for e in header["entries"]:
            raw = data[e["offset"]:e["offset"] + e["nbytes"]]
            leaves[e["path"]] = np.frombuffer(
                raw, dtype=np.dtype(e["dtype"])).reshape(e["shape"])
        return _unflatten_row(leaves, header["structure"])

    def _scan_disk(self) -> None:
        """Index pre-existing shards so a restarted server promotes
        yesterday's prefixes instead of recompiling them."""
        for fname in sorted(os.listdir(self.disk_dir)):
            if not fname.endswith(_SHARD_SUFFIX):
                continue
            path = os.path.join(self.disk_dir, fname)
            try:
                with open(path, "rb") as f:
                    header = self._read_header(f)
            except (ValueError, struct.error):
                continue  # foreign file; leave it alone
            self._disk[header["name"]] = path
            self._disk_base[header["name"]] = int(header["base_len"])

    # ------------------------------------------------------------------
    # Introspection (ServingEngine.stats())
    # ------------------------------------------------------------------

    def tier_snapshot(self) -> Dict[str, int]:
        out = dict(self.tier_stats)
        out["hbm_resident"] = len(self.hbm)
        out["host_resident"] = len(self._host)
        out["disk_resident"] = len(self._disk)
        out["promotions_in_flight"] = len(self._jobs)
        return out


# ---------------------------------------------------------------------------
# Row (de)serialization helpers
# ---------------------------------------------------------------------------


def _flatten_row(row: dict) -> List[Tuple[str, np.ndarray]]:
    """Deterministic (path, leaf) pairs for a batch-free prefix row:
    ``prefix/<i>/<key>`` and ``period/<lkey>/<key>``."""
    flat: List[Tuple[str, np.ndarray]] = []
    for i, entry in enumerate(row.get("prefix", [])):
        for key in sorted(entry):
            flat.append((f"prefix/{i}/{key}", entry[key]))
    for lkey in sorted(row.get("period", {})):
        entry = row["period"][lkey]
        for key in sorted(entry):
            flat.append((f"period/{lkey}/{key}", entry[key]))
    return flat


def _unflatten_row(leaves: Dict[str, np.ndarray],
                   structure: Dict) -> dict:
    row: dict = {}
    if structure["prefix_len"] is not None:
        row["prefix"] = [{} for _ in range(structure["prefix_len"])]
    if structure["period_keys"] is not None:
        row["period"] = {k: {} for k in structure["period_keys"]}
    for path, arr in leaves.items():
        section, mid, key = path.split("/")
        if section == "prefix":
            row["prefix"][int(mid)][key] = arr
        else:
            row["period"][mid][key] = arr
    return row
