"""The control of the ``correct`` comparison, at a size a test run holds:
the float32 reference with every linear layer in float8 put in the
program's place reads over the limit on the same sample on which the
program reads under it.  On the chip the same readings are taken at
each cell's own size by ``chipbench/control.py``."""

import json
import time
from pathlib import Path

import jax
import pytest

from chipbench import bench, control, spec
from chipbench.adapters import dense_gqa_memcom as adapter
from chipbench.reference import dense_gqa_memcom as reference

ROOT = Path(__file__).resolve().parents[2]
LIMIT = json.loads((ROOT / "chipbench/configs/smollm360m.json").read_text()
                   )["limits"]["max_logit_gap"]
SMALL = {"name": "small", "architecture": "dense_gqa_memcom",
         "hidden_size": 128, "intermediate_size": 256,
         "num_attention_heads": 4, "num_key_value_heads": 2,
         "num_hidden_layers": 12, "vocab_size": 1024, "rope_theta": 100000.0,
         "rms_norm_eps": 1e-5, "tie_word_embeddings": True,
         "max_position_embeddings": 512, "torch_dtype": "bfloat16",
         "num_memory_tokens": 16, "limits": {"max_logit_gap": LIMIT}}
MIX = {"name": "smallmix",
       "catalog": {"tasks": 4, "shot_tokens": [128, 192],
                   "zipf_alpha": 1.0},
       "query": {"tokens": [8, 16], "max_new": [2, 6]},
       "arrivals": {"process": "poisson", "rate_per_s": 40.0},
       "engine": {"slots": 4, "block_size": 8}}


CELL = spec.Cell(name="small.open", chips=1, config=SMALL, mix=MIX,
                 end_to_end=[], per_layer=[], adapter=adapter,
                 reference=reference)


@pytest.mark.parametrize("seed", [2, 3])
def test_control_reads_over_the_limit(seed):
    out = control.readings(CELL, seed, 1.0, True, lambda _: None, tokens=80)
    assert out["incomplete"] == 0
    assert out["program_gap"] <= LIMIT < out["control_gap"], out


def test_control_run_is_not_correct():
    """The control in the program's place, through a whole run's own
    comparison, comes out not correct."""
    res = bench.run_cell(CELL, jax.devices()[:1], {}, seed=4, seconds=1.0,
                         trace=False, t_start=time.time(), trace_dir=None,
                         log=lambda _: None, sample_tokens=80, control=True)
    assert res["failed"] == 0
    assert not res["correct"], res["checks"]
