"""Traffic generation and the end-to-end arithmetic: exact percentiles
over all requests, and completed requests per second."""

import hashlib

import numpy as np
import pytest

from chipbench import bench, spec, traffic

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
    mix = dict(MIX, arrivals={"process": "onoff", "rate_per_s": 10})
    with pytest.raises(ValueError):
        traffic.generate(mix, 100, 3, 5)


def test_backlog_queues_every_request_at_zero():
    """round(rate x seconds) requests, all due at 0; the same sizes for
    every seed, in the order Poisson would give them; the seed draws the
    tasks, shots and ids."""
    mix = dict(MIX, arrivals={"process": "backlog", "rate_per_s": 41.5})
    a = traffic.generate(mix, 32768, 2**31 + 11, 30)
    b = traffic.generate(mix, 32768, 7, 30)
    assert len(a.queries) == len(b.queries) == round(41.5 * 30) == 1245
    assert {q.arrival_s for q in a.queries + b.queries} == {0.0}
    for f in (lambda q: len(q.tokens), lambda q: q.max_new):
        assert list(map(f, a.queries)) == list(map(f, b.queries))
    assert [q.task for q in a.queries] != [q.task for q in b.queries]
    assert not np.array_equal(a.shots[0], b.shots[0])
    poisson = traffic.generate(dict(MIX, arrivals={"process": "poisson",
                                                   "rate_per_s": 41.5}),
                               32768, 7, 30)
    assert [(q.task, len(q.tokens), q.max_new) for q in b.queries] == \
        [(q.task, len(q.tokens), q.max_new) for q in poisson.queries]
    short = traffic.generate(mix, 32768, 7, bench.TRACE_CAP_S)
    assert len(short.queries) == round(41.5 * bench.TRACE_CAP_S)


def _digest(t):
    h = hashlib.sha256()
    for s in t.shots:
        h.update(s.tobytes())
    for q in t.queries:
        h.update(q.tokens.tobytes())
        h.update(repr((q.task, q.max_new, q.arrival_s)).encode())
    return h.hexdigest()


@pytest.mark.parametrize("seed,seconds,n,digest", [
    (2**31 + 11, 30, 432,
     "e8b6cf0a7330a48b137e5f14307b5f69d6552a92bc209aa4608c9ceeacb37bbb"),
    (7, 6, 86,
     "fe920034a5a6bbab98d0519c52162e337ef8e1b40799cca486a9a8ed7b36805e"),
])
def test_poisson_warm_queries_are_unchanged(seed, seconds, n, digest):
    """The committed Poisson cell gets, byte for byte, the shots and
    queries the generator gave it before the backlog process came."""
    cell = spec.load_cell("smollm360m.warm")
    t = traffic.generate(cell.mix, cell.config["vocab_size"], seed, seconds)
    assert len(t.queries) == n
    assert _digest(t) == digest


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


def test_queries_per_s_counts_completed_requests_over_the_serve_call():
    log = [{"arrival_s": 0.0, "first_token_s": 0.1 * i, "finish_s": 0.1 * i,
            "tokens": 1} for i in range(1, 8)]
    log.append({"arrival_s": 0.0, "first_token_s": None, "finish_s": None,
                "tokens": 0})
    out = bench.end_to_end(_served(log, 2.5))
    assert out["queries_per_s"] == 7 / 2.5
    assert "tpot_p95_ms" not in out  # no request had two tokens
