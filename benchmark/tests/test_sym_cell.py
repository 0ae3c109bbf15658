"""The symmetric cell (si2-k666-sym.scf on si2-k666-us-sym): the cell loads,
its stored references are the plain code's on the whole mesh with the
program's wedge as witness, make_refs_sym.py refuses what it should, the
rehearsal's counted job is within its limit on the CPU, and the three layer
metrics the cell adds are this cell's only. Entries are found by name: none of
these tests pins the end of a list."""

import copy
import json
import os
import subprocess
import sys

import pytest

from benchmark import make_refs_sym, plain_pwus
from benchmark.harness import decks, loader, sources
from benchmark.make_refs_folded import WITNESS_TOL_HA_PER_CELL
from conftest import ROOT

MDIR = os.path.join(ROOT, "benchmark", "layer_metrics")
CELL, CONFIG = "si2-k666-sym.scf", "si2-k666-us-sym"
NEW = ("sym_pw_per_scf", "sym_ms", "sym_tables_ms")
ORBITS = [1, 8, 8, 4, 6, 24, 24, 24, 12, 6, 24, 12, 3, 24, 24, 12]


@pytest.fixture(scope="module")
def cell():
    return loader.load_cell(ROOT, CELL)


@pytest.fixture(scope="module")
def bench():
    return loader.load_benchmark(ROOT)


# -- the cell and its configuration ------------------------------------------

def test_the_cell_is_the_issues(cell, bench):
    (w,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert (w["config"], w["traffic"], w["chips"]) == (CONFIG, "scf-loop", 1)
    (c,) = [c for c in bench["configs"] if c["name"] == CONFIG]
    assert c["reduced"] == cell.config["reduced"] == ["geometries"]
    assert c["source"] == cell.config["source"] and len(c["source"]) <= 200
    assert cell.traffic["runner"] == "direct" and cell.traffic["clients"] == 1
    assert {m["name"] for m in cell.end_to_end} == {"setup_s", "scf_s"}


def test_the_deck_is_the_stock_deck_with_symmetry_left_on(cell):
    """si2-k444-us's deck with use_symmetry true, the 6x6x6 mesh and the
    iteration cap; ideal positions, one geometry."""
    twin = loader.load_cell(ROOT, "si2-k444.scf").config
    mine, other = cell.config["deck"], twin["deck"]
    assert mine["control"] == other["control"]
    assert mine["synthetic"] == other["synthetic"]
    differ = {k for k in mine["parameters"]
              if mine["parameters"][k] != other["parameters"].get(k)}
    assert differ == {"use_symmetry", "ngridk", "num_dft_iter"}
    assert mine["parameters"]["use_symmetry"] is True
    assert "use_ibz" not in mine["parameters"]  # the schema's default
    assert mine["parameters"]["ngridk"] == [6, 6, 6]
    g = cell.config["geometry"]
    assert (g["supercell"], g["displacement_bohr"], g["geometries"]) == (1, 0.0, 1)
    assert decks.job_deck(cell.config, 0)["synthetic"]["positions"] == [
        [0.0, 0.0, 0.0], [0.25, 0.25, 0.25]]
    sym = cell.config["symmetry"]
    assert sym["num_ops"] == 48 and sym["kpoints_mesh"] == 216
    assert sym["kpoints_irreducible"] == 16 == len(sym["orbit_sizes"])
    assert sym["orbit_sizes"] == ORBITS and sum(ORBITS) == 216
    assert cell.config["guarantee"]["energy_tol_ha_per_atom"] == 5e-6
    assert cell.config["expected_path"] == "batched+fused"
    reh = cell.config["rehearse"]["parameters"]
    assert reh["use_symmetry"] is True and reh["ngridk"] == [3, 3, 3]


# -- the stored references ---------------------------------------------------

@pytest.mark.parametrize("block, mesh, bands, wedge", [
    ("deck", [6, 6, 6], 26, 16), ("rehearse", [3, 3, 3], 8, 4)])
def test_stored_references_are_the_plain_codes_on_the_whole_mesh(
        cell, block, mesh, bands, wedge):
    refs = cell.refs if block == "deck" else cell.refs_rehearse
    assert set(refs) == {"0"}
    run = refs["0"]["kmesh_run"]
    assert run["by"] == "benchmark/plain_pwus.py" and run["cells"] == 1
    assert run["ngridk"] == mesh and run["num_bands"] == bands
    assert run["num_kpoints"] == mesh[0] * mesh[1] * mesh[2]  # no symmetry
    assert refs["0"]["energy_total_ha"] == run["energy_per_cell_ha"]
    assert sum(run["terms_ha_per_cell"].values()) == pytest.approx(
        run["energy_per_cell_ha"], abs=1e-12)
    witness = refs["0"]["witness_run_scf"]
    assert witness["path"] == "batched+fused"
    assert witness["num_kpoints"] == wedge  # the program's run is the wedge
    assert abs(witness["energy_per_cell_ha"] - run["energy_per_cell_ha"]) \
        <= WITNESS_TOL_HA_PER_CELL
    assert witness["minus_plain_ha_per_cell"] == pytest.approx(
        witness["energy_per_cell_ha"] - run["energy_per_cell_ha"], abs=1e-12)


def test_plain_code_imports_nothing_of_the_program():
    with open(plain_pwus.__file__) as f:
        lines = [ln for ln in f if ln.lstrip().startswith(("import ", "from "))]
    assert lines and not any("sirius_tpu" in ln or "jax" in ln for ln in lines)


def test_witness_deck_keeps_symmetry_and_the_plain_deck_drops_it(cell):
    sym_deck, full_deck = make_refs_sym.decks_of(cell.config, "rehearse")
    assert sym_deck["parameters"]["use_symmetry"] is True
    assert full_deck["parameters"]["use_symmetry"] is False
    assert sym_deck["parameters"]["precision_wf"] == "fp64"
    a = copy.deepcopy(sym_deck)
    a["parameters"].pop("use_symmetry")
    b = copy.deepcopy(full_deck)
    b["parameters"].pop("use_symmetry")
    assert a == b


@pytest.mark.parametrize("why, edit", [
    ("displaced", lambda c: c["geometry"].update(displacement_bohr=0.03)),
    ("supercell", lambda c: c["geometry"].update(supercell=2)),
    ("no symmetry", lambda c: c["deck"]["parameters"].update(
        use_symmetry=False)),
])
def test_refs_script_refuses_another_problem(cell, why, edit):
    config = copy.deepcopy(cell.config)
    edit(config)
    with pytest.raises(ValueError):
        make_refs_sym.decks_of(config, "deck")


# -- the rehearsal on the CPU ------------------------------------------------

@pytest.fixture(scope="module")
def traced_rehearsal(bench):
    cmd = [sys.executable, *bench["command"][1:], "--workload", CELL,
           "--seed", "2147483900", "--seconds", "2", "--trace", "1",
           "--rehearse"]
    p = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True,
                       timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    return [json.loads(line) for line in p.stdout.strip().splitlines()]


def test_rehearsal_jobs_are_within_their_limit(traced_rehearsal):
    jobs = [e for e in traced_rehearsal if e.get("event") == "job"]
    assert jobs
    for j in jobs:
        assert j["ok"] and j["converged"] and j["path"] == "batched+fused"
        assert j["abs_de_ha"] <= j["de_limit_ha"] == 1e-5
    (window,) = [e for e in traced_rehearsal if e.get("event") == "window"]
    assert window["failed"] == 0
    assert traced_rehearsal[-1]["correct"] is False  # a rehearsal never counts


def test_rehearsal_reports_the_counter_and_the_span_not_the_device_time(
        traced_rehearsal):
    m = traced_rehearsal[-1]["metrics"]
    assert m["sym_pw_per_scf"]["value"] == 3 * m["scf_iters"]["value"]
    assert m["sym_tables_ms"]["value"] > 0
    assert "sym_ms" not in m  # the CPU backend has no device plane


# -- the three layer metrics ---------------------------------------------------

def test_new_metrics_are_this_cells_only(bench):
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW:
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["moves"] == "scf_s"
        assert os.path.exists(os.path.join(MDIR, name + ".json"))
    assert by_name["sym_ms"]["source"] == "device_trace"
    assert by_name["sym_pw_per_scf"]["source"] == "program_counter"
    assert by_name["sym_tables_ms"]["source"] == "program_span"
    for w in bench["workloads"]:
        if w["name"] != CELL:
            other = loader.load_cell(ROOT, w["name"], bench)
            assert not {m["name"] for m, _ in other.layer_metrics} & set(NEW)


def read(name, record):
    spec = loader._read(os.path.join(MDIR, name + ".json"))
    return sources.read_metric(spec, MDIR, name, record)


def job(sym_pw, *table_s, result=True):
    spans = [{"name": "scf.setup.symmetry", "dur_s": s} for s in table_s]
    spans.append({"name": "scf.setup", "dur_s": 0.5})
    counters = {} if sym_pw is None else {"num_sym_pw": sym_pw}
    return {"result": {"counters": counters} if result else None,
            "spans": spans, "seconds": 3.0}


SCOPES = {"name": "trace.scopes", "busy_s": 0.4, "steps": 2, "by_scope": {
    "step_density": {"s": 0.020, "ops": 9},
    "step_density/sym_pw": {"s": 0.004, "ops": 3},
    "step_density/sym_dm": {"s": 0.001, "ops": 2},
    "step_vloc/sym_pw": {"s": 0.005, "ops": 3},
    "step_ledger/sym_pw": {"s": 0.006, "ops": 3},
    "davidson_hpsi": {"s": 0.3, "ops": 50}}}
ON_CHIP = {"busy_s": 0.4, "modules": [["jit__step_impl", 0.05]]}


def test_sym_pw_per_scf_is_the_jobs_counter():
    record = {"jobs": [job(18), job(21), job(24), job(99, result=False)]}
    assert read("sym_pw_per_scf", record) == 21
    assert read("sym_pw_per_scf", {"jobs": [job(0)]}) == 0


def test_sym_tables_ms_is_the_median_span():
    record = {"jobs": [job(18, 0.050), job(18, 0.070), job(18, 0.060)]}
    assert read("sym_tables_ms", record) == pytest.approx(60.0)


def test_sym_ms_sums_the_paths_that_end_in_a_symmetriser():
    record = {"trace": ON_CHIP,
              "trace_job": {"spans": [{"name": "trace.stop"}, SCOPES]}}
    assert read("sym_ms", record) == pytest.approx(
        1000.0 * (0.004 + 0.001 + 0.005 + 0.006) / 2)


@pytest.mark.parametrize("name, record", [
    ("sym_pw_per_scf", {"jobs": []}),
    ("sym_pw_per_scf", {"jobs": [job(None)]}),             # the parent
    ("sym_tables_ms", {"jobs": []}),
    ("sym_tables_ms", {"jobs": [job(18)]}),                # the parent
    ("sym_ms", {"trace": ON_CHIP}),
    ("sym_ms", {"trace": ON_CHIP, "trace_job": None}),
    ("sym_ms", {"trace": ON_CHIP, "trace_job": {"spans": [
        {"name": "trace.capture"}, {"name": "trace.stop"}]}}),
    ("sym_ms", {"trace": ON_CHIP, "trace_job": {"spans": [dict(
        SCOPES, by_scope={"step_density": {"s": 0.02, "ops": 9}})]}}),  # the parent
    ("sym_ms", {"trace": ON_CHIP, "trace_job": {"spans": [dict(
        SCOPES, steps=None)]}}),
    ("sym_ms", {"trace": {"busy_s": 0.03, "modules": []},   # a CPU rehearsal
                "trace_job": {"spans": [SCOPES]}}),
    ("sym_ms", {"trace": None, "trace_job": {"spans": [SCOPES]}}),
])
def test_new_metrics_read_nothing_where_nothing_is(name, record):
    assert read(name, record) is None
