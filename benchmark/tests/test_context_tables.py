"""The two metrics of PR 47: what is left of the context build once the
tables that read no position are found in the process's memo
(``context_positions_ms``), and the record that they were found
(``context_tables_reused``). Both are found by name, wherever later PRs put
their own entries."""

import json
import subprocess
import sys

from benchmark.harness import loader
from conftest import ROOT

BENCH = loader.load_benchmark(ROOT)
NEW = {"context_positions_ms": ("ms", "lower", "program_span"),
       "context_tables_reused": ("count", "higher", "program_counter")}


def test_both_are_declared_for_every_cell_with_files_of_their_own():
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    for name, (unit, better, source) in NEW.items():
        m = by_name[name]
        assert "workloads" not in m  # every cell that reports scf_s
        assert (m["unit"], m["better"], m["source"]) == (unit, better, source)
        assert (m["layer"], m["moves"]) == ("job set-up", "scf_s")
        spec = loader._read(f"{ROOT}/benchmark/layer_metrics/{name}.json")
        assert (spec["layer"], spec["unit"], spec["source"], spec["moves"]) \
            == (m["layer"], m["unit"], m["source"], m["moves"])
    for w in BENCH["workloads"]:
        names = {m["name"] for m, _ in
                 loader.load_cell(ROOT, w["name"], BENCH).layer_metrics}
        assert set(NEW) <= names


def test_a_rehearsal_reuses_the_tables_on_every_counted_job():
    cmd = [sys.executable, *BENCH["command"][1:], "--workload",
           "si2-k444.scf", "--seed", "2147483947", "--seconds", "2",
           "--trace", "1", "--rehearse"]
    p = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True,
                       timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    m = {k: v["value"] for k, v in out["metrics"].items()}
    # the lattice's tables and the one species': the warm-up job built them
    assert m["context_tables_reused"] == 2
    assert 0 < m["context_positions_ms"] < m["context_build_ms"]
