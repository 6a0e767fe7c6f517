"""The harness finds a cell's configuration, mix and metric readers from
files by name alone: a new cell needs new files and entries, no edit."""

import json
import shutil
from pathlib import Path

import pytest

from chipbench import spec

ROOT = Path(__file__).resolve().parents[2]


def test_committed_benchmark_loads_every_cell():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"])
        assert cell.config["name"] == w["config"]
        assert cell.mix["name"] == w["traffic"]
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer
        for m in cell.per_layer:
            assert callable(spec.load_metric(m["name"]).read)
            assert m["moves"] in names


def test_new_cell_from_files_alone(tmp_path):
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    conf = json.loads((ROOT / "chipbench/configs/smollm360m.json").read_text())
    conf["num_hidden_layers"] = 4
    (tmp_path / "chipbench/configs/newmodel.json").write_text(json.dumps(conf))
    (tmp_path / "chipbench/mixes/newmix.json").write_text(json.dumps(
        {"catalog": {"tasks": 2, "shot_tokens": [64],
                     "zipf_alpha": 1.0},
         "query": {"tokens": [4, 8], "max_new": [1, 2]},
         "arrivals": {"process": "poisson", "rate_per_s": 5},
         "engine": {"slots": 2, "block_size": 8}}))
    (tmp_path / "chipbench/metrics/new_metric.x.py").write_text(
        "def read(ctx):\n    return 42.0\n")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps({
        "configs": [{"name": "newmodel",
                     "file": "chipbench/configs/newmodel.json"}],
        "workloads": [{"name": "newmodel.newmix", "config": "newmodel",
                       "traffic": "newmix", "chips": 1}],
        "end_to_end": [{"name": "setup_s", "unit": "s"},
                       {"name": "queries_per_s", "unit": "queries/s",
                        "workloads": ["newmodel.newmix"]},
                       {"name": "other", "unit": "s", "workloads": ["x"]}],
        "per_layer": [{"name": "new_metric.x", "unit": "%",
                       "workloads": ["newmodel.newmix"]}]}))
    cell = spec.load_cell("newmodel.newmix", root=tmp_path)
    assert cell.config["num_hidden_layers"] == 4
    assert cell.mix["arrivals"]["rate_per_s"] == 5
    assert [m["name"] for m in cell.end_to_end] == ["setup_s",
                                                    "queries_per_s"]
    assert spec.load_metric("new_metric.x", root=tmp_path).read(None) == 42.0
    with pytest.raises(KeyError):
        spec.load_cell("nope", root=tmp_path)


def test_unknown_device_kind_is_an_error():
    assert spec.load_peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        spec.load_peaks("cpu")


def test_mistral_backlog_cell_resolves():
    """Mistral-7B v0.3 at its published widths, one stage of 8 of its 32
    layers; only the depth differs from the source."""
    cell = spec.load_cell("mistral7b.backlog")
    c = cell.config
    assert (c["hidden_size"], c["intermediate_size"]) == (4096, 14336)
    assert (c["num_attention_heads"], c["num_key_value_heads"]) == (32, 8)
    assert (c["vocab_size"], c["rope_theta"]) == (32768, 1e6)
    assert not c["tie_word_embeddings"] and c["num_memory_tokens"] == 768
    assert c["num_hidden_layers"] == 8 and c["published"] == {
        "num_hidden_layers": 32}
    assert c["limits"]["max_logit_gap"] > 0
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = {x["name"]: x for x in bench["configs"]}["mistral7b"]
    assert entry["reduced"] == ["num_hidden_layers"]
    assert cell.mix["arrivals"]["process"] == "backlog"
    assert [m["name"] for m in cell.end_to_end] == ["queries_per_s",
                                                    "setup_s"]
    assert {m["name"] for m in cell.per_layer} == {
        "prefill_share.backlog", "occupancy.backlog", "decode_mfu.backlog",
        "prefill_mfu.backlog"}
    assert {m["moves"] for m in cell.per_layer} == {"queries_per_s"}
    assert cell.adapter.program_config(c).hd == 128
