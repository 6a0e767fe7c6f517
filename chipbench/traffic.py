"""One generator for every traffic mix: a mix file holds only numbers.

A mix names a catalog of tasks (how many, their many-shot lengths, and
the Zipf skew of task choice; every task is compressed before the
window), the query and answer lengths, the arrivals, and the engine's
geometry (slots, block size).  Token ids are drawn from the
configuration's own vocabulary.  Arrivals are one of two processes, each
with ``rate_per_s`` requests per second of the window:

* ``poisson``: open loop, exponential gaps at that rate;
* ``backlog``: the window's whole batch is queued at once, every request
  due at 0 (a dataset labelled in bulk); the serving loop drains it as
  fast as it can.

Sizes, task popularity counts and inter-arrival gaps are stratified
quantiles of the stated distributions.  The timeline of the work (when
each request is due, its prompt length and its answer length) is one
fixed shuffle of them for a given number of requests, the same for every
seed; the seed draws the shots, which task each request asks for, and
every token id.  So the seed changes which request is which, never how
much work arrives when: the order of the gaps decides where the bursts
fall, and with it the tails, which then differ from seed to seed no more
than between two runs of one seed.  The Zipf weights and the exponential
gaps follow ``serving/traffic.py``'s ``zipf_weights`` and Poisson
arrivals.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import List

import numpy as np


@dataclass
class Query:
    task: int             # catalog index
    tokens: np.ndarray    # (S,) int32 prompt behind the task's prefix
    max_new: int
    arrival_s: float      # due time, from the start of the window


@dataclass
class Traffic:
    shots: List[np.ndarray]   # catalog: one many-shot prompt per task
    queries: List[Query]      # the window's requests, in arrival order


def zipf_weights(n: int, alpha: float) -> np.ndarray:
    """P(rank k) proportional to (k+1)^-alpha."""
    w = np.arange(1, n + 1, dtype=np.float64) ** (-float(alpha))
    return w / w.sum()


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def _spread(values, n: int) -> np.ndarray:
    """``n`` draws of a value list, as evenly as the count allows."""
    values = np.asarray(values)
    return values[(np.arange(n) * len(values)) // n]


def _counts(weights: np.ndarray, n: int) -> np.ndarray:
    """Largest-remainder rounding of ``n * weights`` to whole counts."""
    raw = weights * n
    c = np.floor(raw).astype(int)
    c[np.argsort(-(raw - c), kind="stable")[: n - c.sum()]] += 1
    return c


def num_queries(mix: dict, seconds: float) -> int:
    return max(1, int(round(mix["arrivals"]["rate_per_s"] * seconds)))


def _exp_quantiles(n: int, mean: float) -> np.ndarray:
    return -np.log1p(-_quantiles(n)) * mean


def generate(mix: dict, vocab: int, seed: int, seconds: float) -> Traffic:
    rng = np.random.default_rng(np.random.SeedSequence(
        [int(seed) & (2**63 - 1), 0x6368]))
    cat = mix["catalog"]
    n_tasks = cat["tasks"]
    shot_lens = rng.permutation(_spread(cat["shot_tokens"], n_tasks))
    shots = [rng.integers(0, vocab, int(n), dtype=np.int32)
             for n in shot_lens]
    # popularity rank -> task: rank k is the k-th hottest task
    rank_task = rng.permutation(n_tasks)

    n = num_queries(mix, seconds)
    counts = _counts(zipf_weights(n_tasks, cat["zipf_alpha"]), n)
    tasks = rng.permutation(np.repeat(rank_task, counts))
    timeline = np.random.default_rng(np.random.SeedSequence([n, 0x74696d65]))
    q = mix["query"]
    lens = timeline.permutation(np.round(
        q["tokens"][0] + _quantiles(n) * (q["tokens"][1] + 1 - q["tokens"][0])
        - 0.5).astype(int))
    news = timeline.permutation(np.round(
        q["max_new"][0] + _quantiles(n) * (q["max_new"][1] + 1
                                           - q["max_new"][0])
        - 0.5).astype(int))
    a = mix["arrivals"]
    if a["process"] == "poisson":
        gaps = _exp_quantiles(n, 1.0 / a["rate_per_s"])
        due = np.maximum(np.cumsum(timeline.permutation(gaps))
                         - gaps.mean(), 0.0)
    elif a["process"] == "backlog":
        due = np.zeros(n)
    else:
        raise ValueError(f"unknown arrival process {a['process']!r}")
    queries = [Query(task=int(tasks[i]),
                     tokens=rng.integers(0, vocab, int(lens[i]),
                                         dtype=np.int32),
                     max_new=int(news[i]), arrival_s=float(due[i]))
               for i in range(n)]
    return Traffic(shots=shots, queries=queries)
