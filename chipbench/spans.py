"""Arithmetic of the serving loop's own stamps and spans, shared by the
engine-loop readers in ``metrics/``.

The engine stamps every request in ``request_log`` on its own clock, as
offsets from the start of the serve call: ``arrival_s`` (due),
``released_s`` (handed to the scheduler), ``admitted_s`` (its admission
began), ``first_token_s``, ``finish_s``.  It also runs each host phase of
its loop under a profiler annotation ``serve.<phase>``; the phases are
siblings, so at any instant at most one of them is open, and each stretch
of device-idle time can be put down to the phase the host was in.

Every function returns None where the run holds nothing to read (a
program without the stamps or the annotations), never 0.
"""

from __future__ import annotations

from bisect import bisect_right
from collections import defaultdict
from typing import Dict, Optional

from chipbench import xplane

PHASE = "serve."        # prefix of the loop's annotations
IDLE = "serve.idle"     # waiting for the next arrival: no host work


def mean_wait_ms(served, stamp: str):
    """Mean over the window's requests of ``stamp - arrival_s``, in ms."""
    waits = [r[stamp] - r["arrival_s"]
             for r in (served.log[u] for u in served.uids)
             if r.get(stamp) is not None]
    return 1e3 * sum(waits) / len(waits) if waits else None


def step_runs(red: "xplane.Reduced") -> list:
    """Chip 0's decode and prefill program runs, in time order."""
    return sorted((e for e in red.chips[0].modules
                   if xplane.kind_of(e.label, xplane.PROGRAMS)),
                  key=lambda e: e.start)


def idle_by_phase(red: "xplane.Reduced") -> Optional[Dict]:
    """Chip 0's device-idle nanoseconds between consecutive program runs
    on its ``XLA Modules`` line, from the first step program's start to
    the last one's end, split by the ``serve.*`` annotation open over
    each stretch (key None: under no annotation)."""
    if not red.chips:
        return None
    steps = step_runs(red)
    if not steps:
        return None
    lo, hi = steps[0].start, max(e.end for e in steps)
    busy = xplane._union([(e.start, e.end) for e in red.chips[0].modules
                          if e.end > lo and e.start < hi])
    phases = sorted((h.start, h.end, h.name) for h in red.host
                    if h.name.startswith(PHASE))
    starts = [p[0] for p in phases]
    out: Dict = defaultdict(float)
    for (_, s), (e, _) in zip(busy, busy[1:]):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        left = e - s
        i = max(0, bisect_right(starts, s) - 1)
        while i < len(phases) and phases[i][0] < e:
            ov = min(e, phases[i][1]) - max(s, phases[i][0])
            if ov > 0:
                out[phases[i][2]] += ov
                left -= ov
            i += 1
        out[None] += max(0.0, left)
    return dict(out)


def host_gap_ms(red: "xplane.Reduced"):
    """Device-idle time under a loop phase other than ``serve.idle``,
    per decode or prefill program run, in ms: how long the host keeps
    the chip waiting between steps."""
    split = idle_by_phase(red)
    if not split or all(k is None for k in split):
        return None
    work = sum(v for k, v in split.items() if k not in (None, IDLE))
    return work / len(step_runs(red)) / 1e6
