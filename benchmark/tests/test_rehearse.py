"""--rehearse runs each cell's command end to end on the CPU backend (a
four-chip cell on four virtual devices); the last line parses to the
contract's keys and always says "correct": false."""

import json
import subprocess
import sys

import pytest

from benchmark.harness import loader
from conftest import ROOT

BENCH = loader.load_benchmark(ROOT)
KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def rehearse(cell, trace):
    cmd = [sys.executable, *BENCH["command"][1:], "--workload", cell,
           "--seed", "2147483900", "--seconds", "2", "--trace", str(trace),
           "--rehearse"]
    p = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True,
                       timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    lines = p.stdout.strip().splitlines()
    for line in lines[:-1]:  # every earlier line names the device
        assert {"platform", "device_kind", "count"} <= set(json.loads(line))
    return json.loads(lines[-1])


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_rehearsal_prints_the_contracts_line(cell):
    chips = {w["name"]: w["chips"] for w in BENCH["workloads"]}[cell]
    out = rehearse(cell, 0)
    assert KEYS <= set(out) and out["correct"] is False
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert out["device"]["count"] == chips
    e2e = {m["name"] for m in BENCH["end_to_end"]
           if "workloads" not in m or cell in m["workloads"]}
    assert set(out["metrics"]) == e2e
    assert all(m["value"] > 0 for m in out["metrics"].values())


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_traced_rehearsal_reports_layer_metrics(cell):
    out = rehearse(cell, 1)
    assert out["correct"] is False
    assert {"busy_s", "window_s"} <= set(out["device"])
    assert 0 < out["device"]["busy_s"] <= out["device"]["window_s"]
    declared = {m["name"] for m in BENCH["per_layer"]
                if "workloads" not in m or cell in m["workloads"]}
    assert set(out["metrics"]) <= declared
    assert {"scf_iters", "iter_ms", "device_idle"} <= set(out["metrics"])
    assert len(out["breakdown"]["device_ops"]) <= 10


def test_unknown_workload_prints_no_result():
    p = subprocess.run([sys.executable, *BENCH["command"][1:], "--workload",
                        "nope", "--rehearse"], cwd=ROOT, capture_output=True,
                       text=True, timeout=120)
    assert p.returncode != 0 and p.stdout.strip() == ""
