"""Traffic generation and the end-to-end arithmetic: exact percentiles
over all requests."""

import numpy as np
import pytest

from chipbench import bench, traffic

MIX = {"catalog": {"tasks": 64,
                   "shot_tokens": [3072, 3584, 4096], "zipf_alpha": 1.0},
       "query": {"tokens": [16, 64], "max_new": [1, 8]},
       "arrivals": {"process": "poisson", "rate_per_s": 40.0},
       "engine": {"slots": 32, "block_size": 16}}


def test_same_work_for_every_seed_in_another_order():
    """Every seed gets the same timeline of sizes and due times; the seed
    changes which task each request asks for and every token id."""
    a = traffic.generate(MIX, 49152, 2**31 + 11, 30)
    b = traffic.generate(MIX, 49152, 7, 30)
    assert len(a.queries) == len(b.queries) == 1200
    for f in (lambda q: len(q.tokens), lambda q: q.max_new,
              lambda q: q.arrival_s):
        assert list(map(f, a.queries)) == list(map(f, b.queries))
    assert sorted(len(s) for s in a.shots) == sorted(len(s) for s in b.shots)
    assert [q.task for q in a.queries] != [q.task for q in b.queries]
    assert not np.array_equal(a.queries[0].tokens, b.queries[0].tokens)
    lens = [len(q.tokens) for q in a.queries]
    assert lens != sorted(lens)  # a shuffle, not sorted sizes
    assert np.diff([q.arrival_s for q in a.queries]).std() > 0.01
    assert max(q.arrival_s for q in a.queries) < 30
    ids = np.concatenate([q.tokens for q in a.queries])
    assert ids.max() < 49152 and ids.max() > 40000  # the model's vocabulary


def test_zipf_popularity():
    t = traffic.generate(MIX, 49152, 3, 30)
    counts = np.bincount([q.task for q in t.queries], minlength=64)
    w = traffic.zipf_weights(64, 1.0)
    assert sorted(counts)[::-1][:3] == sorted(np.round(w * 1200))[::-1][:3]


def test_unknown_arrival_process_is_an_error():
    mix = dict(MIX, arrivals={"process": "backlog", "rate_per_s": 10})
    with pytest.raises(ValueError):
        traffic.generate(mix, 100, 3, 5)


def _served(log, seconds, max_new=None):
    n = len(log)
    qs = [traffic.Query(task=0, tokens=np.zeros(4, np.int32),
                        max_new=(max_new or [r["tokens"] for r in log])[i],
                        arrival_s=log[i]["arrival_s"]) for i in range(n)]
    uids = list(range(100, 100 + n))
    return bench.Served(queries=qs, uids=uids,
                        outputs={u: np.zeros(r["tokens"], np.int32)
                                 for u, r in zip(uids, log)},
                        log=dict(zip(uids, log)), seconds=seconds, stats={})


def test_percentiles_are_exact_over_all_requests():
    rng = np.random.default_rng(0)
    log = []
    for i in range(101):
        a = float(i) * 0.01
        f = a + float(rng.uniform(0.01, 0.5))
        k = int(rng.integers(1, 9))
        log.append({"arrival_s": a, "first_token_s": f,
                    "finish_s": f + 0.02 * (k - 1), "tokens": k})
    out = bench.end_to_end(_served(log, 4.0))
    ttft = [r["first_token_s"] - r["arrival_s"] for r in log]
    assert out["ttft_p95_ms"] == 1e3 * np.percentile(ttft, 95)
    tpot = [(r["finish_s"] - r["first_token_s"]) / (r["tokens"] - 1)
            for r in log if r["tokens"] >= 2]
    assert out["tpot_p95_ms"] == 1e3 * np.percentile(tpot, 95)


def test_incomplete_and_gap():
    log = [{"arrival_s": 0.0, "first_token_s": 0.1, "finish_s": 0.2,
            "tokens": 2}] * 3
    s = _served(log, 1.0, max_new=[2, 3, 2])
    assert bench.incomplete(s) == 1
    ref = np.array([[0.0, 2.0, 1.0], [3.0, 0.0, 2.5]])
    np.testing.assert_allclose(bench.logit_gaps(ref, np.array([1, 2])),
                               [0.0, 0.5])
