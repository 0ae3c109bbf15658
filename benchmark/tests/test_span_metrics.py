"""The six metrics of PR 25 in a traced rehearsal (CPU backend; host events
stand in for device operations), and the idle-gap table in the ``notes``
event: every idle nanosecond inside the capture has an owner, and the table
adds up to the idle time."""

import json
import subprocess
import sys

import pytest

from benchmark.harness import loader
from conftest import ROOT

BENCH = loader.load_benchmark(ROOT)
NEW = {"context_build_ms", "iter_self_ms", "idle_band_solve_ms",
       "idle_tail_ms", "queue_wait_ms", "serve_self_ms"}


def test_the_new_metrics_are_declared_with_files_of_their_own():
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    assert NEW <= set(by_name)
    assert [m["name"] for m in BENCH["per_layer"]][-6:] == [
        "context_build_ms", "iter_self_ms", "idle_band_solve_ms",
        "idle_tail_ms", "queue_wait_ms", "serve_self_ms"]  # appended
    for name in NEW:
        m = by_name[name]
        serve_only = name in ("queue_wait_ms", "serve_self_ms")
        assert m.get("workloads") == (["si2-k444.serve"] if serve_only
                                      else None)
        assert m["moves"] == ("jobs_per_min" if serve_only else "scf_s")
        spec = loader._read(f"{ROOT}/benchmark/layer_metrics/{name}.json")
        assert spec["layer"] == m["layer"] and spec["unit"] == m["unit"]
        assert spec["source"] == m["source"]


@pytest.mark.parametrize("cell", ["si2-k444.serve", "si16-gamma.scf"])
def test_traced_rehearsal_reports_them_and_the_idle_gap_table(cell):
    cmd = [sys.executable, *BENCH["command"][1:], "--workload", cell,
           "--seed", "2147483901", "--seconds", "2", "--trace", "1",
           "--rehearse"]
    p = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True,
                       timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    lines = [json.loads(ln) for ln in p.stdout.strip().splitlines()]
    out = lines[-1]
    declared = {m["name"] for m in BENCH["per_layer"]
                if "workloads" not in m or cell in m["workloads"]}
    assert set(out["metrics"]) <= declared
    assert NEW & declared <= set(out["metrics"])
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert 0 < m["context_build_ms"] < m["job_setup_ms"]
    assert 0 <= m["iter_self_ms"] < m["iter_ms"]
    assert m["idle_band_solve_ms"] >= 0 and m["idle_tail_ms"] >= 0
    if cell.endswith(".serve"):
        assert m["queue_wait_ms"] > 0 and 0 <= m["serve_self_ms"] < 1000

    (notes,) = [ln for ln in lines if ln.get("event") == "notes"]
    table, cap = notes["idle_gaps"], notes["idle_capture"]
    steps = loader.load_cell(ROOT, cell, BENCH).config["trace_capture_steps"]
    assert cap["iterations"] == steps
    assert sum(s for _, s in table) == pytest.approx(cap["idle_in_capture_s"])
    assert [s for _, s in table] == sorted((s for _, s in table), reverse=True)
    assert cap["owned_share"] > 0.9
    assert {n for n, _ in table} & {"scf.band_solve", "scf.iteration"}
    # the capture lies inside the trace's window, and the idle metrics are
    # the table's rows per traced iteration
    assert cap["capture_s"] <= out["device"]["window_s"] * 1.001
    detail = notes["idle_detail"]
    assert m["idle_band_solve_ms"] == pytest.approx(
        1e3 * detail["scf.band_solve"][0] / steps)
