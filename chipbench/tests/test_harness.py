"""A whole run of a cell at a tiny size on the CPU, past the harness's
look for a chip: it comes out correct, and with the timed path or the
compressor broken underneath (a served token altered where it is
produced; a decode step that returns the cache it was given; a slot
seated with another task's prefix; a cross-attention that returns zeros)
it comes out not correct."""

import contextlib
import json
import time
from pathlib import Path

import jax
import pytest

from chipbench import bench, faults, spec
from chipbench.adapters import dense_gqa_memcom as adapter
from chipbench.reference import dense_gqa_memcom as reference

ROOT = Path(__file__).resolve().parents[2]
LIMIT = json.loads((ROOT / "chipbench/configs/smollm360m.json").read_text()
                   )["limits"]["max_logit_gap"]
TINY = {"name": "tiny", "architecture": "dense_gqa_memcom",
        "hidden_size": 64, "intermediate_size": 96,
        "num_attention_heads": 4, "num_key_value_heads": 2,
        "num_hidden_layers": 2, "vocab_size": 256, "rope_theta": 100000.0,
        "rms_norm_eps": 1e-5, "tie_word_embeddings": True,
        "max_position_embeddings": 512, "torch_dtype": "bfloat16",
        "num_memory_tokens": 8, "limits": {"max_logit_gap": LIMIT}}
MIX = {"name": "tinymix",
       "catalog": {"tasks": 4, "shot_tokens": [64, 96], "zipf_alpha": 1.0},
       "query": {"tokens": [4, 12], "max_new": [2, 6]},
       "arrivals": {"process": "poisson", "rate_per_s": 40.0},
       "engine": {"slots": 4, "block_size": 8}}
PEAK = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}


def run_tiny(fault=None, seed=2**31 + 99, control=False, mix=MIX):
    cell = spec.Cell(name="tiny.open", chips=1, config=TINY, mix=mix,
                     end_to_end=[{"name": n, "unit": u} for n, u in
                                 (("ttft_p95_ms", "ms"), ("tpot_p95_ms", "ms"),
                                  ("queries_per_s", "q/s"),
                                  ("setup_s", "s"))],
                     per_layer=[], adapter=adapter, reference=reference)
    with fault() if fault else contextlib.nullcontext():
        return bench.run_cell(
            cell, jax.devices()[:1], PEAK, seed=seed, seconds=0.5,
            trace=False, t_start=time.time(), trace_dir=None,
            log=lambda _: None, sample_tokens=60, control=control)


def test_sound_run_is_correct():
    res = run_tiny()
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] == 20
    assert set(res["metrics"]) == {"ttft_p95_ms", "tpot_p95_ms",
                                   "queries_per_s", "setup_s"}
    assert res["window_compiles"] == 0
    assert list(res)[-1] == "checks"
    assert res["device"]["platform"] == "cpu"


def test_backlog_run_is_correct():
    """The whole window queued at once: every request completes, and the
    rate is all of them over the serve call."""
    res = run_tiny(mix=dict(MIX, arrivals={"process": "backlog",
                                           "rate_per_s": 40.0}))
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] == 20
    assert res["metrics"]["queries_per_s"]["value"] > 0
    assert res["window_compiles"] == 0


@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
def test_broken_timed_path_is_not_correct(fault):
    res = run_tiny(faults.FAULTS[fault])
    assert not res["correct"], res["checks"]
    assert res["checks"]["max_logit_gap"]["value"] > LIMIT
