"""The benchmark's copies of the FLOP arithmetic equal
``repro/launch/costs.py`` at the configuration's shapes and at
Mistral-7B's (32/8 heads of 128, untied head), and the roofline and MFU
sums over recorded calls follow from them."""

import json
from pathlib import Path

import numpy as np
import pytest

from chipbench import flops, readers
from chipbench.adapters import dense_gqa_memcom as adapter

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


SMOLLM = json.loads((CONFIGS / "smollm360m.json").read_text())
MISTRAL = dict(SMOLLM, hidden_size=4096, intermediate_size=14336,
               num_attention_heads=32, num_key_value_heads=8,
               num_hidden_layers=8, vocab_size=32768, rope_theta=1e6,
               tie_word_embeddings=False, num_memory_tokens=768)


@pytest.mark.parametrize("name,conf", [("smollm360m", SMOLLM),
                                       ("mistral7b", MISTRAL)])
def test_copies_equal_costs(name, conf):
    from repro.config import LayerDesc
    from repro.launch import costs

    c = dict(conf, name=name)
    cfg = adapter.program_config(c)
    s = flops.Shape.of(c)
    desc = LayerDesc("attn", "dense")
    for n_q, ctx in ((1, 0), (1, 700), (64, 576), (6144, 3000)):
        assert flops.attn_flops(s, n_q, ctx) == costs._attn_flops(cfg, n_q, ctx)
        assert flops.mlp_flops(s, n_q) == costs._mlp_flops(cfg, desc, n_q)
        assert flops.logits_flops(s, n_q) == costs._logits_flops(cfg, n_q)


def test_decode_and_prefill_sums():
    s = flops.Shape(d_model=8, num_heads=2, num_kv_heads=1, hd=4, d_ff=16,
                    vocab_size=10, num_layers=3, m=5)
    w = flops.decode_step(s, np.array([6, 9]))
    assert w["kernel_flops"] == 3 * 4 * 15 * 2 * 4
    assert w["kernel_bytes"] == 3 * (15 * 2 * 1 * 4 + 2 * 2 * 2 * 4) * 2
    assert w["model_flops"] == (3 * (flops.attn_flops(s, 2, 0)
                                     + flops.mlp_flops(s, 2))
                                + w["kernel_flops"] + flops.logits_flops(s, 2))
    p = flops.prefill(s, 4, 5)
    assert p["kernel_flops"] == 3 * 4 * (4 * 5 + 10) * 2 * 4
    peak = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert flops.roofline_seconds(50, 30, peak) == 3.0
    assert flops.roofline_seconds(500, 3, peak) == 5.0


class _Trace:
    def __init__(self, kernel, program):
        self.k, self.p = kernel, program

    def kernel_seconds(self, kind):
        return self.k.get(kind, 0.0)

    def program_seconds(self, kind):
        return self.p.get(kind, 0.0)


class _Ctx:
    def __init__(self, trace, calls):
        self.trace = trace
        self.shape = flops.Shape(d_model=8, num_heads=2, num_kv_heads=1, hd=4,
                                 d_ff=16, vocab_size=10, num_layers=3, m=5)
        self.peak = {"bf16_flops_per_s": 1e3, "hbm_bytes_per_s": 1e2}

        class S:
            pass
        self.served = S()
        self.served.calls = calls


def test_readers_roofline_and_mfu():
    calls = {"decode": [np.array([5, 8])], "prefill": [(4, 5)]}
    w = flops.decode_step(_Ctx(None, {}).shape, np.array([6, 9]))
    ideal = flops.roofline_seconds(w["kernel_flops"], w["kernel_bytes"],
                                   {"bf16_flops_per_s": 1e3,
                                    "hbm_bytes_per_s": 1e2})
    ctx = _Ctx(_Trace({"paged_decode": 2 * ideal}, {"decode": 1.0}), calls)
    assert readers.decode_attn_roofline(ctx) == pytest.approx(50.0)
    assert readers.program_mfu(ctx, "decode") == pytest.approx(
        100 * w["model_flops"] / 1e3)
    # nothing traced or nothing recorded: no number, never 0
    assert readers.prefill_attn_roofline(ctx) is None
    assert readers.decode_attn_roofline(_Ctx(_Trace({}, {}), calls)) is None
