"""The generic-k cell (si16-k223.scf on si16-k223-us): the cell loads, its deck
is the twin's (si16-k222-us) on the [2, 2, 3] mesh, its one stored reference
is the plain code's on the folded [4, 4, 6] mesh with the program's own run
as witness, the rehearsal's counted jobs are within their limit on the CPU and
book every eigenproblem as a complex one, and the three layer metrics the cell
adds are this cell's only. Entries are found by name: none of these tests pins
the end of a list."""

import json
import os
import subprocess
import sys

import pytest

from benchmark import make_refs_folded_kmesh
from benchmark.harness import decks, loader, sources
from benchmark.make_refs_folded import WITNESS_TOL_HA_PER_CELL
from conftest import ROOT

MDIR = os.path.join(ROOT, "benchmark", "layer_metrics")
CELL, CONFIG = "si16-k223.scf", "si16-k223-us"
TWIN_CELL = "si16-k222.scf"
NEW = ("eigh_reduce_ms", "eigh_kernel_ms", "complex_eigh_per_scf")


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
    assert c["reduced"] == cell.config["reduced"] == ["kpoints", "geometries"]
    assert set(cell.config["reduced_why"]) == set(c["reduced"])
    assert c["source"] == cell.config["source"] and len(c["source"]) <= 200
    assert c["file"] == f"benchmark/configs/{CONFIG}/config.json"
    assert cell.config["architecture"] is None  # a deployment, not a model
    assert cell.traffic["runner"] == "direct" and cell.traffic["clients"] == 1
    assert {m["name"] for m in cell.end_to_end} == {"setup_s", "scf_s"}


def test_the_deck_is_the_twins_on_the_223_mesh(cell):
    """No width is cut: cutoffs, bands, precision, tolerances and padding
    are si16-k222-us's; the mesh (and the iteration cap, set from the chip)
    is all that differs."""
    twin = loader.load_cell(ROOT, TWIN_CELL).config
    mine, other = cell.config["deck"], twin["deck"]
    assert mine["control"] == other["control"]
    assert mine["synthetic"] == other["synthetic"]
    differ = {k for k in mine["parameters"]
              if mine["parameters"][k] != other["parameters"].get(k)}
    assert {"ngridk"} <= differ <= {"ngridk", "num_dft_iter"}
    p = mine["parameters"]
    assert p["ngridk"] == [2, 2, 3] and "shiftk" not in p
    assert (p["gk_cutoff"], p["pw_cutoff"], p["num_bands"]) == (6.0, 20.0, 64)
    assert p["use_symmetry"] is False and p["precision_wf"] == "fp32"
    assert cell.config["geometry"] == twin["geometry"] == {
        "supercell": 2, "displacement_bohr": 0.0, "rng_base": 1000,
        "geometries": 1}
    assert decks.atoms(cell.config) == 16
    assert cell.config["guarantee"]["energy_tol_ha_per_atom"] == 5e-6
    assert cell.config["expected_path"] == "batched+fused"
    assert cell.config["trace_capture_steps"] == 1
    assert cell.config["reference"]["overrides"] == twin["reference"]["overrides"]
    reh = cell.config["rehearse"]
    assert reh["parameters"]["ngridk"] == [2, 2, 3]
    assert reh["parameters"]["num_bands"] == 8
    assert reh["geometry"] == {"supercell": 1, "geometries": 1}


# -- the stored references ---------------------------------------------------

@pytest.mark.parametrize("block, mesh, cells, solved", [
    ("deck", [4, 4, 6], 8, 52), ("rehearse", [2, 2, 3], 1, 8)])
def test_stored_reference_is_the_plain_codes_on_the_folded_mesh(
        cell, block, mesh, cells, solved):
    refs = cell.refs if block == "deck" else cell.refs_rehearse
    assert set(refs) == {"0"}  # one geometry: only the ideal positions fold
    run = refs["0"]["kmesh_run"]
    assert run["by"] == "benchmark/plain_pwus.py" and run["cells"] == cells
    assert run["ngridk"] == mesh and run["num_bands"] == 8
    assert run["num_kpoints"] == mesh[0] * mesh[1] * mesh[2]  # no time reversal
    assert refs["0"]["energy_total_ha"] == cells * run["energy_per_cell_ha"]
    assert sum(run["terms_ha_per_cell"].values()) == pytest.approx(
        run["energy_per_cell_ha"], abs=1e-12)
    witness = refs["0"]["witness_run_scf"]
    assert witness["path"] == "batched+fused"
    assert witness["num_kpoints"] == solved  # the program pairs k with -k
    assert abs(witness["minus_plain_ha_per_cell"]) <= WITNESS_TOL_HA_PER_CELL
    assert witness["minus_plain_ha_per_cell"] == pytest.approx(
        witness["energy_per_cell_ha"] - run["energy_per_cell_ha"], abs=1e-12)


def test_refs_script_takes_the_mesh_as_it_stands(cell):
    deck, cells = make_refs_folded_kmesh.folded_deck(cell.config, "deck")
    assert cells == 8 and deck["parameters"]["ngridk"] == [4, 4, 6]
    assert deck["parameters"]["num_bands"] == 8
    assert deck["parameters"]["precision_wf"] == "fp64"
    assert len(deck["synthetic"]["positions"]) == 2
    deck, cells = make_refs_folded_kmesh.folded_deck(cell.config, "rehearse")
    assert cells == 1 and deck["parameters"]["ngridk"] == [2, 2, 3]


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


def test_rehearsal_reports_the_counter_and_not_the_device_times(
        traced_rehearsal):
    m = traced_rehearsal[-1]["metrics"]
    # 8 k-points, two eigenproblems a step and one a solve, all complex
    assert m["complex_eigh_per_scf"]["value"] == 8 * (
        2 * m["davidson_steps_per_scf"]["value"] + m["scf_iters"]["value"])
    assert "eigh_reduce_ms" not in m and "eigh_kernel_ms" not in m  # no device plane
    assert {"rayleigh_ritz_share", "local_op_share", "hpsi_per_scf"} <= set(m)
    assert "kset_rows_per_s" not in m and "kset_eigh_share" not in m  # the twin's


# -- the three layer metrics -------------------------------------------------

def test_new_metrics_are_this_cells_only(bench):
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW:
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["moves"] == "scf_s"
        assert by_name[name]["layer"] == "band solve"
        assert os.path.exists(os.path.join(MDIR, name + ".json"))
    assert by_name["eigh_reduce_ms"]["source"] == "device_trace"
    assert by_name["eigh_kernel_ms"]["source"] == "device_trace"
    assert by_name["complex_eigh_per_scf"]["source"] == "program_counter"
    for w in bench["workloads"]:
        if w["name"] != CELL:
            other = loader.load_cell(ROOT, w["name"], bench)
            assert not {m["name"] for m, _ in other.layer_metrics} & set(NEW)


def read(name, record):
    spec = loader._read(os.path.join(MDIR, name + ".json"))
    return sources.read_metric(spec, MDIR, name, record)


def job(n_complex, result=True):
    counters = {"num_subspace_eigh": 1000}
    if n_complex is not None:
        counters["num_complex_subspace_eigh"] = n_complex
    return {"result": {"counters": counters} if result else None,
            "spans": [], "seconds": 12.0}


SCOPES = {"name": "trace.scopes", "busy_s": 0.5, "steps": 2, "by_scope": {
    "davidson_rr": {"s": 0.120, "ops": 90},
    "davidson_rr/eigh_reduce": {"s": 0.016, "ops": 40},
    "davidson_rr/eigh_kernel": {"s": 0.100, "ops": 30},
    "davidson_ortho": {"s": 0.009, "ops": 12},
    "davidson_ortho/eigh_reduce": {"s": 0.002, "ops": 4},
    "davidson_ortho/eigh_kernel": {"s": 0.006, "ops": 3},
    "outer/davidson_rr/eigh_reduce": {"s": 0.004, "ops": 4},
    "davidson_hpsi": {"s": 0.3, "ops": 50}}}
ON_CHIP = {"busy_s": 0.5, "modules": [["jit_davidson_kset", 0.4]]}


def test_complex_eigh_per_scf_is_the_jobs_counter():
    record = {"jobs": [job(1000), job(1096), job(1192), job(9, result=False)]}
    assert read("complex_eigh_per_scf", record) == 1096
    assert read("complex_eigh_per_scf", {"jobs": [job(0)]}) == 0  # a fallback


def test_the_two_halves_read_the_paths_under_davidson_rr():
    record = {"trace": ON_CHIP,
              "trace_job": {"spans": [{"name": "trace.stop"}, SCOPES]}}
    assert read("eigh_reduce_ms", record) == pytest.approx(
        1000.0 * (0.016 + 0.004) / 2)
    assert read("eigh_kernel_ms", record) == pytest.approx(1000.0 * 0.100 / 2)


REAL = dict(SCOPES, by_scope={k: v for k, v in SCOPES["by_scope"].items()
                              if "eigh_reduce" not in k})


@pytest.mark.parametrize("name, record", [
    ("complex_eigh_per_scf", {"jobs": []}),
    ("complex_eigh_per_scf", {"jobs": [job(None)]}),          # the parent
    ("eigh_reduce_ms", {"trace": ON_CHIP}),
    ("eigh_reduce_ms", {"trace": ON_CHIP, "trace_job": None}),
    ("eigh_kernel_ms", {"trace": ON_CHIP, "trace_job": {"spans": [
        {"name": "trace.capture"}, {"name": "trace.stop"}]}}),  # before PR 36
    ("eigh_reduce_ms", {"trace": ON_CHIP,
                        "trace_job": {"spans": [REAL]}}),     # a real subspace
    ("eigh_kernel_ms", {"trace": ON_CHIP, "trace_job": {"spans": [dict(
        SCOPES, steps=None)]}}),
    ("eigh_kernel_ms", {"trace": {"busy_s": 0.03, "modules": []},  # a CPU rehearsal
                        "trace_job": {"spans": [SCOPES]}}),
    ("eigh_reduce_ms", {"trace": None, "trace_job": {"spans": [SCOPES]}}),
])
def test_new_metrics_read_nothing_where_nothing_is(name, record):
    assert read(name, record) is None
