"""The DeepSeek-V2-Lite cell: its files resolve by name, the configuration
keeps every published number but the two it cuts, the new readers read
nothing where there is nothing to read, and the latent-decode and expert
arithmetic agrees with a hand count."""

import json
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

from chipbench import flops_mla_moe, spec, xplane

ROOT = Path(__file__).resolve().parents[2]
NAMES = ("mla_decode_roofline.backlog256", "expert_gmm_roofline.backlog256",
         "moe_share.backlog256", "expert_pad_share.backlog256")
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
MS = 1_000_000  # ns


def test_dsv2lite_cell_resolves():
    cell = spec.load_cell("dsv2lite.backlog256")
    c = cell.config
    assert c["architecture"] == "mla_moe_memcom"
    assert (c["hidden_size"], c["kv_lora_rank"], c["qk_rope_head_dim"],
            c["moe_intermediate_size"]) == (2048, 512, 64, 1408)
    assert c["q_lora_rank"] is None and c["num_experts_per_tok"] == 6
    assert (c["num_hidden_layers"], c["n_routed_experts"]) == (14, 8)
    assert c["published"] == {"num_hidden_layers": 27, "n_routed_experts": 64}
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = {x["name"]: x for x in bench["configs"]}["dsv2lite"]
    assert entry["reduced"] == ["num_hidden_layers", "n_routed_experts"]
    assert cell.mix["engine"]["slots"] == 256
    assert cell.mix["arrivals"]["process"] == "backlog"
    assert [m["name"] for m in cell.end_to_end] == ["queries_per_s",
                                                    "setup_s"]
    # with the backlog cells' architecture-free readers: the prefill
    # programs' share and the decode batch's occupancy
    assert {m["name"] for m in cell.per_layer} == set(NAMES) | {
        "prefill_share.backlog", "occupancy.backlog"}
    cfg = cell.adapter.program_config(c)
    assert (cfg.moe.num_experts, cfg.moe.held, cfg.moe.top_k) == (64, 8, 6)
    assert not cfg.moe.norm_topk_prob and cfg.mla.q_lora_rank is None
    assert cfg.layout.num_layers == 14 and len(cfg.layout.prefix) == 1
    assert cfg.rope_scaling.factor == 40.0
    assert callable(cell.reference.logits)


def test_mistral_open_cell_resolves():
    cell = spec.load_cell("mistral7b.open")
    assert cell.mix["arrivals"]["process"] == "poisson"
    assert cell.mix["engine"] == {"slots": 32, "block_size": 16}
    assert [m["name"] for m in cell.end_to_end] == [
        "ttft_p95_ms", "tpot_p95_ms", "setup_s"]
    assert all(m["name"].endswith(".open") for m in cell.per_layer)


def _ctx(trace, calls, moe):
    conf = spec.load_cell("dsv2lite.backlog256").config
    served = SimpleNamespace(calls=calls, stats={"moe": moe} if moe else {})
    return SimpleNamespace(cell=SimpleNamespace(config=conf), trace=trace,
                           served=served, peak=PEAK)


def test_readers_read_nothing_from_an_empty_window():
    empty = xplane.Reduced(chips=[xplane.Chip()], host=[])
    ctx = _ctx(empty, {"decode": [], "prefill": []}, None)
    for name in NAMES:
        assert spec.load_metric(name).read(ctx) is None
    zero = {"routed_rows": 0, "absent_rows": 0, "padded_rows": 0,
            "gmm_calls": 0, "expert_hits": 0}
    ctx = _ctx(empty, {"decode": [], "prefill": []}, zero)
    for name in NAMES:
        assert spec.load_metric(name).read(ctx) is None


def test_readers_on_a_small_window():
    """One decode program of 10 ms holding 3 ms of latent kernel, 1 ms of
    expert matmul kernel after 1 ms fetching its weights out of the layer
    stack, 1 ms of a router op and 0.5 ms of a shared expert's; one
    prefill of 4 ms.  Labels are HLO text, as the chip's trace has them."""
    E = xplane.Event
    ops = [E("%mla_latent_decode.3 = bf16[256,128,512]{2,1,0} custom-call("
             "s32[14336]{0} %r, bf16[13,2305,16,640]{3,2,1,0} %p)", "m",
             0, 3 * MS),
           E("%dynamic-slice_bitcast_fusion.6 = bf16[8,2048,1408]{2,1,0} "
             "fusion(bf16[13,8,2048,1408]{3,2,1,0} %w)", "s", 3 * MS, 4 * MS),
           E("%expert_gmm.2 = bf16[8,256,1408]{2,1,0} custom-call("
             "bf16[8,256,2048]{2,1,0} %x, bf16[8,2048,1408]{2,1,0} %s)", "g",
             4 * MS, 5 * MS),
           E("%fusion.1 = f32[256,64]{1,0} fusion(bf16[256,2048]{1,0} %h, "
             "bf16[13,2048,64]{2,1,0} %r)", "f", 5 * MS, 6 * MS),
           E("%fusion.360 = bf16[256,2816]{1,0} fusion("
             "bf16[13,2048,2816]{2,1,0} %w)", "h", 6 * MS, 6.5 * MS),
           E("%fusion.9 = bf16[256,2048]{1,0} fusion(bf16[256,2048]{1,0} %a)",
             "o", 6.5 * MS, 7 * MS),
           # the layer scan's loop spans the ops above and lists the
           # stacked weights among its operands: not counted itself
           E("%while.4 = (s32[]{:T(128)}, bf16[13,8,2048,1408]{3,2,1,0}) "
             "while(%tuple.1), condition=%cond, body=%body", "w",
             3 * MS, 7 * MS)]
    mods = [E("jit_paged_decode_fn(1)", "d", 0, 10 * MS),
            E("jit_paged_prefill_fn(2)", "p", 10 * MS, 14 * MS)]
    red = xplane.Reduced(chips=[xplane.Chip(ops=ops, modules=mods)], host=[])
    lengths = np.full(256, 800, np.int64)
    moe = {"routed_rows": 24 * 8 * 13, "absent_rows": 0,
           "padded_rows": 2048 * 13 - 24 * 8 * 13, "gmm_calls": 39,
           "expert_hits": 8 * 13}
    ctx = _ctx(red, {"decode": [lengths], "prefill": [(64, 768)]}, moe)
    dims = flops_mla_moe.Dims.of(ctx.cell.config)
    w = flops_mla_moe.latent_decode(dims, lengths + 1)
    ideal = max(w["flops"] / 197e12, w["bytes"] / 819e9)
    assert spec.load_metric(NAMES[0]).read(ctx) == pytest.approx(
        100 * ideal / 3e-3)
    w = flops_mla_moe.expert_gmm(dims, moe["routed_rows"], moe["expert_hits"])
    ideal = max(w["flops"] / 197e12, w["bytes"] / 819e9)
    assert spec.load_metric(NAMES[1]).read(ctx) == pytest.approx(
        100 * ideal / 2e-3)
    assert spec.load_metric(NAMES[2]).read(ctx) == pytest.approx(
        100 * 3.5 / 14)
    assert spec.load_metric(NAMES[3]).read(ctx) == pytest.approx(
        100 * (2048 - 192) / 2048)


def test_arithmetic_against_a_hand_count():
    """2 layers, 2 heads, latent key 6 lanes (value 4), d 8, experts of
    width 3; two slots reading 5 and 3 rows."""
    dims = flops_mla_moe.Dims(d=8, heads=2, rank=4, latent=6, layers=2,
                              expert_f=3)
    w = flops_mla_moe.latent_decode(dims, [5, 3])
    # per layer: each head scores 8 rows of 6 lanes and sums 8 rows of 4:
    # 2 * 2 heads * 8 rows * (6 + 4) = 320 operations
    assert w["flops"] == 2 * 320
    # per layer: 8 rows of 6 lanes, two slots' 2 query rows of 6 and
    # output rows of 4: (48 + 2 * 2 * 10) * 2 bytes
    assert w["bytes"] == 2 * (48 + 40) * 2
    w = flops_mla_moe.expert_gmm(dims, routed_rows=5, expert_hits=2)
    # 5 rows through gate, up (8 x 3) and down (3 x 8): 5 * 3 * 2 * 24
    assert w["flops"] == 5 * 3 * 2 * 24
    # 2 experts' 3 matrices of 24, and 5 rows of 8 in and out
    assert w["bytes"] == (2 * 3 * 24 + 5 * 2 * 8) * 2
