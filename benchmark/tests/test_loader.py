"""A configuration, a mix and a layer metric are each added as new files and
new entries of BENCHMARK.json; no file that was there is edited."""

import hashlib
import json
import os
import shutil

import pytest

from benchmark.harness import loader, sources
from conftest import ROOT


def _digests(top):
    out = {}
    for d, _, files in os.walk(top):
        for f in files:
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, top)] = hashlib.sha256(fh.read()).hexdigest()
    return out


@pytest.fixture
def copy(tmp_path):
    root = str(tmp_path / "checkout")
    os.makedirs(root)
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    shutil.copytree(os.path.join(ROOT, "benchmark"), os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    return root


def test_every_cell_of_the_benchmark_loads():
    bench = loader.load_benchmark(ROOT)
    for w in bench["workloads"]:
        cell = loader.load_cell(ROOT, w["name"], bench)
        assert cell.chips == w["chips"]
        assert cell.config["deck"]["parameters"]["precision_wf"] == "fp32"
        assert cell.traffic["runner"] in ("direct", "engine")
        assert cell.refs, f"{w['name']}: no stored references"
        assert {m["name"] for m in cell.end_to_end} >= {"setup_s", "scf_s"}
        assert cell.layer_metrics


def test_new_files_only(copy):
    bdir = os.path.join(copy, "benchmark")
    before = _digests(bdir)
    # a configuration: a directory of its own
    cdir = os.path.join(bdir, "configs", "si2-k222-new")
    shutil.copytree(os.path.join(bdir, "configs", "si2-k444-us"), cdir)
    cfile = os.path.join(cdir, "config.json")
    with open(cfile) as f:
        config = json.load(f)
    config["name"] = "si2-k222-new"
    config["deck"]["parameters"]["ngridk"] = [2, 2, 2]
    with open(cfile, "w") as f:
        json.dump(config, f)
    # a mix: a data file
    with open(os.path.join(bdir, "traffic", "three-clients.json"), "w") as f:
        json.dump({"kind": "serve-outstanding", "runner": "engine",
                   "clients": 3, "num_slices": 1, "who": "a test"}, f)
    # a layer metric: a spec and a reader of its own
    with open(os.path.join(bdir, "layer_metrics", "jobs_seen.json"), "w") as f:
        json.dump({"kind": "python", "args": {"plus": 1}}, f)
    with open(os.path.join(bdir, "layer_metrics", "jobs_seen.py"), "w") as f:
        f.write("def read(record, args):\n"
                "    return len(record['jobs']) + args['plus']\n")
    bpath = os.path.join(copy, "BENCHMARK.json")
    with open(bpath) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "si2-k222-new", "source": "test",
                             "file": "benchmark/configs/si2-k222-new/config.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "new.cell", "config": "si2-k222-new",
                               "traffic": "three-clients", "chips": 1,
                               "why": "test"})
    bench["per_layer"].append({"name": "jobs_seen", "unit": "count",
                               "better": "higher", "source": "program_counter",
                               "layer": "serving", "moves": "scf_s",
                               "workloads": ["new.cell"]})
    with open(bpath, "w") as f:
        json.dump(bench, f)

    cell = loader.load_cell(copy, "new.cell")
    assert cell.config["deck"]["parameters"]["ngridk"] == [2, 2, 2]
    assert cell.traffic["clients"] == 3
    names = [m["name"] for m, _ in cell.layer_metrics]
    assert "jobs_seen" in names and "serve_overhead_ms" not in names
    spec = [s for m, s in cell.layer_metrics if m["name"] == "jobs_seen"][0]
    value = sources.read_metric(spec, os.path.join(bdir, "layer_metrics"),
                                "jobs_seen", {"jobs": [{}, {}]})
    assert value == 3
    # the old cells still load, and nothing that was there changed
    assert loader.load_cell(copy, "si2-k444.scf").config_name == "si2-k444-us"
    after = _digests(bdir)
    assert {k: after[k] for k in before} == before


def test_missing_pieces_are_errors(copy):
    with pytest.raises(loader.BenchmarkError):
        loader.load_cell(copy, "no-such-cell")
    os.remove(os.path.join(copy, "benchmark", "traffic", "scf-loop.json"))
    with pytest.raises(loader.BenchmarkError):
        loader.load_cell(copy, "si2-k444.scf")


def test_unknown_device_kind_is_an_error():
    from benchmark.harness import costs

    assert costs.load_peaks("TPU v5 lite")["hbm_gbps"] == 819.0
    with pytest.raises(KeyError):
        costs.load_peaks("TPU v9 imaginary")
