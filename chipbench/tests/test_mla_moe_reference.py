"""The DeepSeek-V2 float32 reference against the serving engine, at a
small size on the CPU: the engine's paged prefill and cached paged decode
logits agree with the reference's, and an engine that drops tokens at
the old capacity (1.25) or renormalises the top-k gates does not."""

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import weights as W
from chipbench.adapters import mla_moe_memcom as adapter
from chipbench.reference import mla_moe_memcom as ref

ROOT = Path(__file__).resolve().parents[2]
PUBLISHED = json.loads((ROOT / "chipbench/configs/dsv2lite.json").read_text())

# DeepSeek-V2-Lite's keys at a tiny width: no query LoRA, one dense layer
# then MoE layers holding 4 of 8 experts (top 2, gates not renormalised),
# YaRN over 16 original positions so the shots lie past them
TINY = dict(
    PUBLISHED, name="tiny", hidden_size=64, intermediate_size=96,
    kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    num_attention_heads=4, num_key_value_heads=4, moe_intermediate_size=24,
    n_routed_experts=4, num_experts_per_tok=2, num_hidden_layers=3,
    vocab_size=128, max_position_embeddings=512, torch_dtype="float32",
    num_memory_tokens=8, published={"n_routed_experts": 8},
    rope_scaling=dict(PUBLISHED["rope_scaling"],
                      original_max_position_embeddings=16))
# the served logits agree to float32 rounding (both compute in float32 at
# highest precision, only the order of sums differs: 4e-6 read); the
# 200-token shots put more tokens on some expert than the old capacity
# (1.25 x its even share) holds, and a dropped token moves them by 7e-3,
# renormalised gates by far more
TOL = 1e-4


def engine_logits(conf, seed, shots, prompt, new, *, capacity=None,
                  renormalise=False):
    """(prompt's last position + ``new`` decode steps, vocab) logits of
    the paged engine, sampling at temperature 1 so that the logits leave
    the device; and the tokens it served."""
    from repro.models import moe
    from repro.serving import Request, ServingEngine

    if renormalise:
        conf = dict(conf, norm_topk_prob=True)
    cfg = adapter.program_config(conf)
    target, comp = adapter.program_weights(cfg, seed)
    eng = ServingEngine(cfg, target, slots=1, max_len=32, kv_layout="paged",
                        block_size=8, compressor=comp)
    rows = []
    prefill, decode = eng._prefill, eng._decode

    def pre(params, cache, tokens, *rest):
        (logits, counts), cache = prefill(params, cache, tokens, *rest)
        rows.append(logits[len(prompt) - 1])
        return (logits, counts), cache

    def dec(*args):
        (logits, counts), cache = decode(*args)
        rows.append(logits[0])
        return (logits, counts), cache

    saved = moe._capacity
    if capacity is not None:  # the old capacity dispatch, dropping tokens
        moe._capacity = lambda m, n: max(8, -(-int(capacity * n * m.top_k
                                                  / m.num_experts) // 8) * 8)
    try:
        with jax.default_matmul_precision("highest"):
            eng.serve([Request(tokens=prompt, max_new=1, prefix="task",
                               raw_shots=shots, stop_token=None)])
            eng._prefill, eng._decode = pre, dec
            out = eng.serve([Request(tokens=prompt, max_new=new,
                                     prefix="task", temperature=1.0,
                                     stop_token=None)], seed=3)
    finally:
        moe._capacity = saved
    served = next(iter(out.values()))
    assert eng.stats()["moe"]["routed_rows"] > 0
    return np.stack([np.asarray(r) for r in rows]), served


def reference_logits(conf, seed, shots, prompt, served, **kw):
    fed = np.concatenate([prompt, served[:-1]]).astype(np.int32)
    pos = len(prompt) - 1 + np.arange(len(served))
    return ref.logits(conf, seed, [shots], [(0, fed, pos)], **kw)


@pytest.fixture(scope="module")
def case():
    rng = np.random.default_rng(0)
    shots = rng.integers(0, 128, 200).astype(np.int32)
    prompt = rng.integers(0, 128, 6).astype(np.int32)
    return shots, prompt


def test_engine_matches_reference_through_prefill_and_paged_decode(case):
    shots, prompt = case
    got, served = engine_logits(TINY, 2**31 + 7, shots, prompt, 5)
    want = reference_logits(TINY, 2**31 + 7, shots, prompt, served)
    assert got.shape == want.shape == (5, 128)
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)


@pytest.mark.parametrize("fault", ["capacity", "renormalise"])
def test_comparison_catches_dropping_or_renormalising(case, fault):
    shots, prompt = case
    kw = {"capacity": 1.25} if fault == "capacity" else {"renormalise": True}
    got, served = engine_logits(TINY, 2**31 + 7, shots, prompt, 5, **kw)
    want = reference_logits(TINY, 2**31 + 7, shots, prompt, served)
    assert np.abs(got - want).max() > 10 * TOL


def test_control_departs_from_reference(case):
    shots, prompt = case
    served = np.arange(4, dtype=np.int32)
    exact = reference_logits(TINY, 9, shots, prompt, served)
    low = reference_logits(TINY, 9, shots, prompt, served, control=True)
    assert 1e-3 < np.abs(low - exact).max() < 10.0


def test_adapter_leaves_are_the_seeded_weights():
    """Leaf-by-leaf draws (name hash traced) equal
    :func:`chipbench.weights.leaf` for single and stacked leaves."""
    key = W.root_key(2**31 + 12345)
    name = "target/period/l0/moe/wg"
    got = adapter.leaf(key, name, (3, 2, 8, 4), jnp.bfloat16, layers=3)
    for i in range(3):
        want = W.leaf(key, name, i, (2, 8, 4), jnp.bfloat16)
        assert bool(jnp.all(got[i] == want))
    got = adapter.leaf(key, "target/lm_head", (8, 4), jnp.bfloat16)
    assert bool(jnp.all(got == W.leaf(key, "target/lm_head", None, (8, 4),
                                      jnp.bfloat16)))
    assert bool(jnp.all(adapter.leaf(key, "x/attn/kv_norm", (5,),
                                     jnp.bfloat16) == 1))


def test_tiny_run_is_correct():
    """A whole run of the backlog cell's path at a tiny size in bfloat16:
    every request completes and the served tokens' reference logits lie
    within the configuration's limit of the best."""
    import time

    from chipbench import bench, spec

    conf = dict(TINY, torch_dtype="bfloat16", limits=PUBLISHED["limits"])
    mix = {"name": "tinybacklog",
           "catalog": {"tasks": 3, "shot_tokens": [150, 200], "zipf_alpha": 1.0},
           "query": {"tokens": [4, 12], "max_new": [1, 6]},
           "arrivals": {"process": "backlog", "rate_per_s": 40.0},
           "engine": {"slots": 8, "block_size": 8}}
    cell = spec.Cell(name="tiny.backlog", chips=1, config=conf, mix=mix,
                     end_to_end=[{"name": "queries_per_s", "unit": "q/s"},
                                 {"name": "setup_s", "unit": "s"}],
                     per_layer=[], adapter=adapter, reference=ref)
    res = bench.run_cell(cell, jax.devices()[:1], {}, seed=2**31 + 99,
                         seconds=0.5, trace=False, t_start=time.time(),
                         trace_dir=None, log=lambda _: None,
                         sample_tokens=40)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] == 20
    assert set(res["metrics"]) == {"queries_per_s", "setup_s"}
