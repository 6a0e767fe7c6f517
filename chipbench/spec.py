"""Find a cell's parts by name: ``BENCHMARK.json`` names the cell, its
configuration and traffic mix; the files live under this directory.

* configuration ``<c>``: the file ``BENCHMARK.json`` gives (a JSON
  object of sizes, Hugging Face key names), whose ``architecture`` key
  names ``adapters/<architecture>.py`` (how the program is built) and
  ``reference/<architecture>.py`` (the plain float32 reference);
* traffic mix ``<t>``: ``mixes/<t>.json``;
* per-layer metric ``<name>``: ``metrics/<name>.py``, a module with
  ``read(ctx) -> float | None``.

Adding a cell, a configuration, a mix or a metric therefore means adding
files and entries, never editing this harness.
"""

from __future__ import annotations

import importlib
import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path
from types import ModuleType
from typing import Dict, List

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


@dataclass
class Cell:
    name: str
    chips: int
    config: dict          # the configuration file, plus its "name"
    mix: dict             # the traffic mix file, plus its "name"
    end_to_end: List[dict]
    per_layer: List[dict]
    adapter: ModuleType
    reference: ModuleType


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_metric(name: str, root: Path = ROOT) -> ModuleType:
    path = root / "chipbench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(workload: str, root: Path = ROOT) -> Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[workload]
    conf_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = json.loads((root / conf_entry["file"]).read_text())
    config["name"] = conf_entry["name"]
    mix = json.loads((root / "chipbench" / "mixes"
                      / f"{w['traffic']}.json").read_text())
    mix["name"] = w["traffic"]
    arch = config["architecture"]
    return Cell(
        name=workload, chips=int(w["chips"]), config=config, mix=mix,
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, workload)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, workload)],
        adapter=importlib.import_module(f"chipbench.adapters.{arch}"),
        reference=importlib.import_module(f"chipbench.reference.{arch}"))


def load_peaks(kind: str, root: Path = ROOT) -> Dict[str, float]:
    table = json.loads((root / "chipbench" / "peaks.json").read_text())
    if kind not in table["devices"]:
        raise KeyError(f"device kind {kind!r} is not in chipbench/peaks.json")
    return table["devices"][kind]
