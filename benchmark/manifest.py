"""Finding a cell's files by the names in BENCHMARK.json.

Nothing here knows a configuration, a traffic mix, a kind or a metric by
name: a later PR adds a file and an entry and edits nothing that is there.

  configs/<config>.json          sizes, source, reduced, assumed
  traffic/<traffic>.json         `kind` and that kind's parameters
  kinds/<kind>.py                run(cell, opts) -> Record, one per kind
  layer_metrics/<metric>.json    layer, unit, moves, reader, field
  readers/<reader>.py            read(metric, obs) -> number or None
"""
from __future__ import annotations

import importlib.util
import json
import os
from dataclasses import dataclass, field

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


@dataclass
class Cell:
    name: str
    config_name: str
    traffic_name: str
    chips: int
    why: str
    config: dict
    traffic: dict
    end_to_end: list = field(default_factory=list)   # BENCHMARK.json entries
    per_layer: list = field(default_factory=list)    # ... merged with files
    bench_dir: str = BENCH_DIR

    @property
    def kind(self) -> str:
        return self.traffic["kind"]


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_manifest(root: str = ROOT) -> dict:
    return _load_json(os.path.join(root, "BENCHMARK.json"))


def bench_dir_of(manifest: dict, root: str = ROOT) -> str:
    """The benchmark's code directory: the first of `paths`."""
    return os.path.join(root, manifest["paths"][0])


def _applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def load_cell(workload: str, root: str = ROOT) -> Cell:
    """The cell named `workload`, found through its BENCHMARK.json entry
    (never by parsing the name: `gpt-1.3b` holds a dot)."""
    m = load_manifest(root)
    entries = [w for w in m["workloads"] if w["name"] == workload]
    if not entries:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; it has "
                       f"{[w['name'] for w in m['workloads']]}")
    w = entries[0]
    cfg_entry = next(c for c in m["configs"] if c["name"] == w["config"])
    bdir = bench_dir_of(m, root)
    config = _load_json(os.path.join(root, cfg_entry["file"]))
    traffic = _load_json(os.path.join(bdir, "traffic",
                                      w["traffic"] + ".json"))
    per_layer = []
    for pl in m["per_layer"]:
        if not _applies(pl, workload):
            continue
        spec = _load_json(os.path.join(bdir, "layer_metrics",
                                       pl["name"] + ".json"))
        per_layer.append({**spec, **pl})
    return Cell(name=workload, config_name=w["config"],
                traffic_name=w["traffic"], chips=int(w["chips"]),
                why=w["why"], config=config, traffic=traffic,
                end_to_end=[e for e in m["end_to_end"]
                            if _applies(e, workload)],
                per_layer=per_layer, bench_dir=bdir)


def _load_module(bench_dir: str, group: str, name: str):
    path = os.path.join(bench_dir, group, name + ".py")
    if not os.path.exists(path):
        raise FileNotFoundError(
            f"no {group[:-1]} {name!r}: {path} does not exist")
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{group}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_kind(cell: Cell):
    """The driver of the cell's traffic kind: kinds/<kind>.py."""
    return _load_module(cell.bench_dir, "kinds", cell.kind)


def load_reader(cell: Cell, name: str):
    """A per-layer reader: readers/<name>.py."""
    return _load_module(cell.bench_dir, "readers", name)
