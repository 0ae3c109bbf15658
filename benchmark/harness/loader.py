"""Find a cell's files by the names BENCHMARK.json gives: nothing about a
cell is written in Python.

  workloads[i].config   -> configs[j].file          (the deck, beside it refs.json)
  workloads[i].traffic  -> <bench>/traffic/<traffic>.json
  per_layer[k].name     -> <bench>/layer_metrics/<name>.json (+ <name>.py)

A later PR adds a configuration, a mix or a layer metric as new files and
new entries; no file here is edited to take them.
"""

from __future__ import annotations

import dataclasses
import json
import os


class BenchmarkError(Exception):
    """The benchmark's own files do not fit together: no result is printed."""


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    config_dir: str
    refs: dict            # geometry (str) -> {"energy_total_ha": ...}
    refs_rehearse: dict
    traffic_name: str
    traffic: dict
    end_to_end: list      # BENCHMARK.json entries reported in this cell
    layer_metrics: list   # [(BENCHMARK.json entry, layer_metrics/<name>.json)]
    bench_dir: str
    root: str


def _read(path: str) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise BenchmarkError(f"missing file {path}") from None


def _in_cell(entry: dict, cell_name: str) -> bool:
    return "workloads" not in entry or cell_name in entry["workloads"]


def load_benchmark(root: str) -> dict:
    return _read(os.path.join(root, "BENCHMARK.json"))


def load_cell(root: str, workload: str, bench: dict | None = None) -> Cell:
    bench = bench or load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise BenchmarkError(
            f"no workload {workload!r} in BENCHMARK.json (has {sorted(cells)})")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    if w["config"] not in configs:
        raise BenchmarkError(f"workload {workload}: no config {w['config']!r}")
    bench_dir = os.path.join(root, bench["paths"][0])
    cfile = os.path.join(root, configs[w["config"]]["file"])
    cdir = os.path.dirname(cfile)
    config = _read(cfile)
    refs_path = os.path.join(cdir, "refs.json")
    refs = _read(refs_path)["geometries"] if os.path.exists(refs_path) else {}
    reh_path = os.path.join(cdir, "refs_rehearse.json")
    reh = _read(reh_path)["geometries"] if os.path.exists(reh_path) else {}
    traffic = _read(os.path.join(bench_dir, "traffic", w["traffic"] + ".json"))
    e2e = [m for m in bench["end_to_end"] if _in_cell(m, workload)]
    layer = []
    for m in bench["per_layer"]:
        if _in_cell(m, workload):
            spec = _read(os.path.join(bench_dir, "layer_metrics",
                                      m["name"] + ".json"))
            layer.append((m, spec))
    return Cell(name=workload, chips=int(w["chips"]), config_name=w["config"],
                config=config, config_dir=cdir, refs=refs, refs_rehearse=reh,
                traffic_name=w["traffic"], traffic=traffic, end_to_end=e2e,
                layer_metrics=layer, bench_dir=bench_dir, root=root)
