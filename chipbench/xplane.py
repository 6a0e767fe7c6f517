"""Reduce a JAX profiler trace to device time: busy intervals, the time
of each kernel and of each step program, and the longest idle gaps.

The profiler writes ``<dir>/plugins/profile/<run>/<host>.xplane.pb``.
Each chip is a plane named ``/device:TPU:<id>`` whose ``XLA Ops`` line
holds one event per operation run and whose ``XLA Modules`` line holds
one event per program run.  Kernels and programs are found by the
substrings below, matched against an event's name and its ``long_name``
and ``hlo_module`` statistics, so a refactor that keeps the names keeps
the numbers.
"""

from __future__ import annotations

import glob
import json
import os
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Sequence, Tuple

# what an operation or program is: the first kind, in this order, one of
# whose substrings its label holds
KERNELS: Dict[str, Tuple[str, ...]] = {
    "paged_decode": ("paged_flash_decode", "_paged_kernel", "paged_decode"),
    "flash": ("flash_attention", "_flash_kernel", "flash"),
}
PROGRAMS: Dict[str, Tuple[str, ...]] = {
    "decode": ("jit_fn", "jit_paged_decode_fn"),
    "prefill": ("jit_paged_prefill_fn",),
}
_LABEL_STATS = ("long_name", "hlo_module", "tf_op", "hlo_op")


@dataclass
class Event:
    label: str          # name plus the statistics that identify it
    name: str
    start: int          # ns
    end: int            # ns


@dataclass
class Chip:
    ops: List[Event] = field(default_factory=list)
    modules: List[Event] = field(default_factory=list)


def _label(ev) -> str:
    parts = [ev.name]
    try:
        for k, v in ev.stats:
            if k in _LABEL_STATS:
                parts.append(str(v))
    except Exception:  # a stat the reader cannot decode names nothing
        pass
    return " ".join(parts)


def _union(intervals: Sequence[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def kind_of(label: str, table: Dict[str, Tuple[str, ...]]):
    return next((k for k, pats in table.items()
                 if any(p in label for p in pats)), None)


@dataclass
class Reduced:
    chips: List[Chip]
    host: List[Event]

    @property
    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the chips."""
        tot = 0.0
        for c in self.chips:
            tot += sum(e - s for s, e in _union([(o.start, o.end)
                                                 for o in c.ops]))
        return tot / max(1, len(self.chips)) / 1e9

    def _seconds(self, events_of, table, kind) -> float:
        tot = sum(e.end - e.start for c in self.chips for e in events_of(c)
                  if kind_of(e.label, table) == kind)
        return tot / max(1, len(self.chips)) / 1e9

    def kernel_seconds(self, kind: str) -> float:
        """Device seconds of one kernel's operations, averaged over chips."""
        return self._seconds(lambda c: c.ops, KERNELS, kind)

    def program_seconds(self, kind: str) -> float:
        """Device seconds of one step program's runs, averaged over chips."""
        return self._seconds(lambda c: c.modules, PROGRAMS, kind)

    def breakdown(self, n: int = 10) -> dict:
        """The operations that took the most device time, and the longest
        idle gaps named by the host event that overlapped them most."""
        per = defaultdict(int)
        for c in self.chips:
            for o in c.ops:
                per[o.name] += o.end - o.start
        k = max(1, len(self.chips))
        ops = sorted(per.items(), key=lambda kv: -kv[1])[:n]
        gaps = []
        if self.chips:
            busy = _union([(o.start, o.end) for o in self.chips[0].ops])
            holes = [(a[1], b[0]) for a, b in zip(busy, busy[1:])]
            holes.sort(key=lambda h: h[0] - h[1])
            for s, e in holes[:n]:
                gaps.append([self._host_at(s, e), (e - s) / 1e9])
        return {"device_ops": [[name, t / k / 1e9] for name, t in ops],
                "idle_gaps": gaps}

    def _host_at(self, s: int, e: int) -> str:
        best, name = 0, "no host event traced"
        for h in self.host:
            ov = min(e, h.end) - max(s, h.start)
            if ov > best:
                best, name = ov, h.name
        return name


def save_excerpt(red: Reduced, path, start_ns: int, span_ns: int) -> None:
    """Write chip 0's operations and programs, and the host events, that
    start within ``span_ns`` of ``start_ns`` as JSON: a small trace
    recorded on the chip, for the reduction's tests."""
    def keep(evs):
        return [[e.label, e.name, e.start - start_ns, e.end - start_ns]
                for e in evs if 0 <= e.start - start_ns < span_ns]

    c = red.chips[0]
    with open(path, "w") as f:
        json.dump({"ops": keep(c.ops), "modules": keep(c.modules),
                   "host": keep(red.host)}, f)


def load_excerpt(path) -> Reduced:
    with open(path) as f:
        d = json.load(f)
    ev = lambda rows: [Event(*r) for r in rows]
    return Reduced(chips=[Chip(ops=ev(d["ops"]), modules=ev(d["modules"]))],
                   host=ev(d["host"]))


def trace_file(trace_dir) -> str:
    files = glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(files, key=os.path.getmtime)


def reduce(trace_dir, device_ids: Sequence[int], *,
           summary_path=None) -> Reduced:
    """Read the newest trace under ``trace_dir`` for the chips
    ``device_ids``.  With ``summary_path`` the most expensive labels of
    every line are written there as JSON, to see how kernels are named."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(trace_file(trace_dir))
    want = [f"/device:TPU:{i}" for i in device_ids]
    chips, host, summary = [], [], {}
    for plane in pd.planes:
        ours = any(plane.name == w or plane.name.startswith(w + " ")
                   for w in want)
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    host.append(Event(ev.name, ev.name, ev.start_ns,
                                      ev.start_ns + ev.duration_ns))
            continue
        chip = Chip()
        for line in plane.lines:
            dest = {"XLA Ops": chip.ops, "XLA Modules": chip.modules}.get(
                line.name) if ours else None
            top = defaultdict(lambda: [0, 0])
            for ev in line.events:
                lab = _label(ev)
                if dest is not None:
                    dest.append(Event(lab, ev.name, ev.start_ns,
                                      ev.start_ns + ev.duration_ns))
                t = top[lab[:300]]
                t[0] += 1
                t[1] += ev.duration_ns
            summary[f"{plane.name} | {line.name}"] = sorted(
                ([k, c, d] for k, (c, d) in top.items()),
                key=lambda x: -x[2])[:60]
        if ours:
            chips.append(chip)
    if summary_path is not None:
        with open(summary_path, "w") as f:
            json.dump(summary, f, indent=1)
    if not chips:
        raise ValueError(f"the trace holds none of the planes {want}: "
                         f"{[p.name for p in pd.planes]}")
    return Reduced(chips=chips, host=host)
