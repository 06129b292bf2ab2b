"""Finds a cell's files by the names in ``BENCHMARK.json``."""
from __future__ import annotations

import dataclasses
import glob
import importlib
import json
import os
from types import ModuleType
from typing import List

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def _load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    model: dict            # the configuration file, as run
    family: ModuleType     # families/<the configuration's "family">.py
    traffic_name: str
    traffic: dict          # the traffic file
    end_to_end: List[dict]  # the cell's end-to-end metrics (BENCHMARK.json)
    per_layer: List[dict]   # its per-layer metrics, each with "file" loaded
    benchmark: dict


def benchmark(root: str = ROOT) -> dict:
    return _load(os.path.join(root, "BENCHMARK.json"))


def _listed(metric: dict, cell: str, reporting: set) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric.get("moves") is None or metric["moves"] in reporting


def family(model: dict) -> ModuleType:
    """The module of the model family a configuration names under
    ``family``: ``families/<name>.py``. No default: a configuration without
    the key, or naming a family that has no module, is an error."""
    found = sorted(os.path.basename(p)[:-3] for p in glob.glob(
        os.path.join(HERE, "families", "[!_]*.py")))
    name = model.get("family")
    if name not in found:
        raise SystemExit(f"chipbench: the configuration names the model "
                         f"family {name!r}; chipbench/families/ has {found}")
    return importlib.import_module("chipbench.families." + name)


def load_cell(name: str, root: str = ROOT) -> Cell:
    bench = benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"chipbench: no workload {name!r} in BENCHMARK.json; "
                         f"it has {sorted(cells)}")
    w = cells[name]
    config = next(c for c in bench["configs"] if c["name"] == w["config"])
    model = _load(os.path.join(root, config["file"]))
    traffic = _load(os.path.join(HERE, "traffic", w["traffic"] + ".json"))
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    reporting = {m["name"] for m in e2e}
    layer = []
    for m in bench["per_layer"]:
        if _listed(m, name, reporting):
            layer.append(dict(m, file=_load(os.path.join(
                HERE, "metrics", m["name"] + ".json"))))
    return Cell(name, w["chips"], w["config"], model, family(model),
                w["traffic"], traffic, e2e, layer, bench)


def rehearsal_model(model: dict) -> dict:
    """The configuration at a size the CPU runs in seconds, as its family
    cuts it: the rehearsal proves control flow, never a number."""
    return family(model).rehearsal(model)
