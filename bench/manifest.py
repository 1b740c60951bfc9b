"""BENCHMARK.json and the files of a cell, found by name.

Adding a cell, a traffic mix, a configuration or a per-layer metric means
adding files; nothing here names one of them.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import re
import sys
from pathlib import Path
from typing import Any, Dict, List

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def load_json(path: Path) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def load_manifest(root: Path = ROOT) -> Dict[str, Any]:
    return load_json(root / "BENCHMARK.json")


def load_module(path: Path):
    """Import one file by path; its module name is derived from the path."""
    name = "bench_file_" + re.sub(r"\W", "_", str(path.relative_to(
        path.parents[1])))
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    entry: Dict[str, Any]          # the workloads entry of BENCHMARK.json
    params: Dict[str, Any]         # workloads/<cell>.json
    config: Dict[str, Any]         # configs/<config>.json
    traffic: Dict[str, Any]        # traffic/<mix>.json
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]
    bench: Path

    @property
    def chips(self) -> int:
        return int(self.entry["chips"])

    def runner(self):
        return load_module(self.bench / "runners"
                           / f"{self.config['runner']}.py")

    def reference(self):
        return load_module(self.bench / "reference"
                           / f"{self.config['reference']}.py")

    def reader(self, metric: str):
        return load_module(self.bench / "metrics" / f"{metric}.py")


def metrics_for(manifest: Dict[str, Any], cell: str):
    """(end-to-end, per-layer) metric entries this cell reports.

    A metric with a ``workloads`` key is reported in those cells; one
    without it, in every cell that reports the end-to-end metric it moves.
    """
    e2e = [m for m in manifest["end_to_end"]
           if cell in m.get("workloads", [cell])]
    names = {m["name"] for m in e2e}
    layer = [m for m in manifest["per_layer"]
             if (cell in m["workloads"] if "workloads" in m
                 else m["moves"] in names)]
    return e2e, layer


def resolve(manifest: Dict[str, Any], name: str,
            bench: Path = BENCH) -> Cell:
    entries = {w["name"]: w for w in manifest["workloads"]}
    if name not in entries:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                       f"{sorted(entries)}")
    entry = entries[name]
    params = load_json(bench / "workloads" / f"{name}.json")
    for key in ("config", "traffic", "chips"):
        if params.get(key) != entry[key]:
            raise ValueError(f"workloads/{name}.json has {key}="
                             f"{params.get(key)!r}, BENCHMARK.json "
                             f"{entry[key]!r}")
    config = load_json(bench / "configs" / f"{entry['config']}.json")
    traffic = load_json(bench / "traffic" / f"{entry['traffic']}.json")
    e2e, layer = metrics_for(manifest, name)
    return Cell(name, entry, params, config, traffic, e2e, layer, bench)
