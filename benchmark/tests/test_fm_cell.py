"""The ferromagnetic cell (fm2-k444.scf on fm2-k444-us): the cell loads, its
configuration has every key the accepted ones have, its one stored reference
is the plain collinear-spin code's on the whole mesh with the program's own
run as witness, the species' four bars stand in the configuration, the CPU
rehearsal's counted jobs are within their limit and hold the reference's
moment, and the three layer metrics the cell adds are this cell's only.
Entries are found by name: none of these tests pins the end of a list."""

import json
import os
import subprocess
import sys

import pytest

from benchmark import make_refs_spin
from benchmark.harness import decks, loader, sources
from conftest import ROOT

MDIR = os.path.join(ROOT, "benchmark", "layer_metrics")
CELL, CONFIG = "fm2-k444.scf", "fm2-k444-us"
MODEL = "si2-k666-sym.scf"  # the accepted configuration this one is modelled on
NEW = {"xc_spin_ms": ("device_trace", "iteration tail", "ms"),
       "moment_ub": ("program_counter", "SCF driver", "uB"),
       "spin_channels": ("program_counter", "band solve", "count")}


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
    assert len(w["why"]) <= 200
    (c,) = [c for c in bench["configs"] if c["name"] == CONFIG]
    assert c["reduced"] == cell.config["reduced"] == ["geometries"]
    assert set(cell.config["reduced_why"]) == set(c["reduced"])
    assert c["source"] == cell.config["source"] and len(c["source"]) <= 200
    assert "test03" in c["source"] and "config 3" in c["source"]
    assert c["file"] == f"benchmark/configs/{CONFIG}/config.json"
    assert cell.config["architecture"] is None  # a deployment, not a model
    assert cell.traffic["runner"] == "direct" and cell.traffic["clients"] == 1
    assert {m["name"] for m in cell.end_to_end} == {"setup_s", "scf_s"}
    # one cell on the configuration: no served or four-chip twin
    assert [x["name"] for x in bench["workloads"] if x["config"] == CONFIG] == [CELL]


def test_configuration_has_every_key_the_accepted_ones_have(cell):
    model = loader.load_cell(ROOT, MODEL).config
    mine = cell.config
    assert set(model) - {"symmetry"} <= set(mine)
    assert set(model["deck"]["parameters"]) <= set(mine["deck"]["parameters"])
    assert set(model["rehearse"]) <= set(mine["rehearse"])
    assert set(model["guarantee"]) == set(mine["guarantee"])
    assert set(model["reference"]) == set(mine["reference"])
    mine_over, model_over = (json.loads(json.dumps(c["reference"]["overrides"]))
                             for c in (mine, model))
    mine_over["parameters"].pop("num_dft_iter")
    model_over["parameters"].pop("num_dft_iter")
    assert mine_over == model_over  # f64, 1e-8 / 1e-9, the fused path
    for key in ("species", "lattice", "lattice_constant_bohr", "ngridk",
                "smearing_width", "num_bands", "starting_moment_ub_per_atom"):
        assert key in mine["assumed"], key


def test_the_deck_is_the_issues(cell):
    p = cell.config["deck"]["parameters"]
    assert (p["gk_cutoff"], p["pw_cutoff"], p["ngridk"]) == (6.0, 20.0, [4, 4, 4])
    assert p["use_symmetry"] is False and p["num_mag_dims"] == 1
    assert p["xc_functionals"] == ["XC_LDA_X", "XC_LDA_C_PZ"]
    assert p["precision_wf"] == "fp32"
    assert (p["density_tol"], p["energy_tol"]) == (1e-5, 1e-5)
    assert 16 <= p["num_bands"] <= 20
    assert p["smearing_width"] in (0.025, 0.02, 0.01, 0.005)
    assert cell.config["deck"]["control"] == {"ngk_pad_quantum": 16,
                                              "verbosity": 0}
    syn = cell.config["deck"]["synthetic"]
    assert syn["ultrasoft"] is True and syn["species"] == "dshell"
    m = syn["moments"]
    assert len(m) == 2 and m[0] == m[1] and m[0][:2] == [0, 0] and m[0][2] > 0
    assert cell.config["geometry"] == {
        "supercell": 1, "displacement_bohr": 0.0, "rng_base": 1000,
        "geometries": 1, "a": 10.26}
    assert decks.atoms(cell.config) == 2
    assert cell.config["guarantee"]["energy_tol_ha_per_atom"] == 5e-6  # not widened
    assert cell.config["guarantee"]["converged"] is True
    assert cell.config["expected_path"] == "batched+fused"
    assert cell.config["trace_capture_steps"] == 1
    deck = decks.job_deck(cell.config, 0)
    assert deck["synthetic"]["species"] == "dshell"  # the harness passes them on
    assert deck["synthetic"]["moments"] == m and deck["synthetic"]["a"] == 10.26
    reh = cell.config["rehearse"]
    assert (reh["parameters"]["gk_cutoff"], reh["parameters"]["pw_cutoff"],
            reh["parameters"]["ngridk"]) == (3.0, 7.0, [2, 2, 2])
    assert reh["parameters"]["num_mag_dims"] == 1
    assert reh["synthetic"] == syn  # the species is not tuned there


def test_the_species_four_bars_stand_in_the_configuration(cell):
    """At the deck's cutoffs, mesh and smearing in f64: a moment of at least
    1 uB a cell, 1e-3 Ha under the non-magnetic state, converged from the
    deck's start in at most 40 iterations, one state from two starts."""
    mag = cell.config["magnetism"]
    assert mag["num_mag_dims"] == 1
    assert mag["moment_ref_ub"] >= 1.0
    assert mag["e_fm_minus_e_nm_ha"] <= -1e-3
    bars = mag["bars"]
    assert bars["iterations_from_the_decks_start"] <= 40
    two = bars["two_starts"]
    assert two["start_ub_per_atom"] == [2.0, 4.0]
    assert abs(two["energy_ha"][0] - two["energy_ha"][1]) <= 1e-7
    assert abs(two["moment_ub"][0] - two["moment_ub"][1]) <= 1e-5
    # the same numbers as the stored reference's
    ref = cell.refs["0"]
    assert mag["moment_ref_ub"] == pytest.approx(ref["moment_total_ub"], abs=1e-6)
    assert mag["e_fm_minus_e_nm_ha"] == pytest.approx(
        ref["nonmagnetic_run"]["e_fm_minus_e_nm_ha"], abs=1e-7)


# -- the stored references ---------------------------------------------------

@pytest.mark.parametrize("block, mesh, solved", [
    ("deck", [4, 4, 4], 36), ("rehearse", [2, 2, 2], 8)])
def test_stored_reference_is_the_plain_spin_codes(cell, block, mesh, solved):
    refs = cell.refs if block == "deck" else cell.refs_rehearse
    geometry = dict(cell.config["geometry"],
                    **cell.config[block].get("geometry", {}))
    assert set(refs) == {str(g) for g in range(geometry["geometries"])} == {"0"}
    ref = refs["0"]
    run = ref["kmesh_run"]
    assert run["by"] == "benchmark/plain_pwus_spin.py" and run["cells"] == 1
    assert run["ngridk"] == mesh
    assert run["num_bands"] == cell.config[block]["parameters"]["num_bands"]
    assert run["num_kpoints"] == mesh[0] * mesh[1] * mesh[2]  # no time reversal
    assert ref["energy_total_ha"] == run["energy_per_cell_ha"]
    assert ref["moment_total_ub"] == run["moment_total_ub"] >= 0.5
    assert sum(run["terms_ha_per_cell"].values()) == pytest.approx(
        run["energy_per_cell_ha"], abs=1e-11)
    # at least four empty bands above the majority channel's highest occupied
    assert run["num_bands"] - max(run["bands_occupied"]) >= 4
    assert max(run["last_band_occupation"]) == 0.0
    nm = ref["nonmagnetic_run"]
    assert nm["e_fm_minus_e_nm_ha"] == pytest.approx(
        run["energy_per_cell_ha"] - nm["energy_per_cell_ha"], abs=1e-12)
    assert nm["e_fm_minus_e_nm_ha"] <= -1e-3
    witness = ref["witness_run_scf"]
    assert witness["path"] == "batched+fused"
    assert witness["num_kpoints"] == solved  # the program pairs k with -k
    assert abs(witness["minus_plain_ha_per_cell"]) <= \
        make_refs_spin.WITNESS_TOL_HA_PER_CELL
    assert abs(witness["moment_minus_plain_ub"]) <= make_refs_spin.WITNESS_TOL_UB
    assert witness["minus_plain_ha_per_cell"] == pytest.approx(
        witness["energy_per_cell_ha"] - run["energy_per_cell_ha"], abs=1e-12)


def test_refs_script_refuses_what_the_plain_code_does_not_know(cell):
    deck = decks.reference_deck(cell.config, 0, "deck")
    assert make_refs_spin.start_moment(deck) == 2.0
    assert deck["parameters"]["precision_wf"] == "fp64"
    for section, key, value in (("synthetic", "species", "si"),
                                ("parameters", "num_mag_dims", 0),
                                ("parameters", "use_symmetry", True),
                                ("parameters", "xc_functionals",
                                 ["XC_GGA_X_PBE", "XC_GGA_C_PBE"])):
        bad = json.loads(json.dumps(deck))
        bad[section][key] = value
        with pytest.raises(ValueError, match="plain_pwus_spin"):
            make_refs_spin.plain_runs(bad)
    bad = json.loads(json.dumps(deck))
    bad["synthetic"]["moments"] = [[0, 0, 2.0], [0, 0, -2.0]]
    with pytest.raises(ValueError, match="equal on both atoms"):
        make_refs_spin.start_moment(bad)


def test_plain_code_imports_nothing_of_the_program():
    with open(os.path.join(ROOT, "benchmark", "plain_pwus_spin.py")) as f:
        text = f.read()
    imports = [line for line in text.splitlines()
               if line.lstrip().startswith(("import ", "from "))]
    assert imports and not any("sirius_tpu" in line or "jax" in line
                               for line in imports)


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


def test_rehearsal_reports_the_moment_and_the_channels(cell, traced_rehearsal):
    m = traced_rehearsal[-1]["metrics"]
    assert m["spin_channels"]["value"] == 2
    # the rehearsal's weak moment is soft in 32 bits: 7e-4 uB off
    assert m["moment_ub"]["value"] == pytest.approx(
        cell.refs_rehearse["0"]["moment_total_ub"], abs=5e-3)
    # the scope ran; on the CPU backend the reader sees host events (as
    # xc_gga_ms's does there): a number, but no device time
    assert m["xc_spin_ms"]["value"] > 0
    # 7 placements a step; rows of two channels in the density
    assert m["tail_box_fills_per_scf"]["value"] == 7 * m["scf_iters"]["value"]
    assert {"job_setup_ms", "setup_potential_ms", "hpsi_per_scf",
            "davidson_steps_per_scf"} <= set(m)


# -- the three layer metrics -------------------------------------------------

def test_new_metrics_are_this_cells_only(bench):
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name, (source, layer, unit) in NEW.items():
        entry = by_name[name]
        assert entry["workloads"] == [CELL] and entry["moves"] == "scf_s"
        assert (entry["source"], entry["layer"], entry["unit"]) == (
            source, layer, unit)
        spec = loader._read(os.path.join(MDIR, name + ".json"))
        assert (spec["source"], spec["layer"], spec["unit"]) == (
            source, layer, unit)
    for w in bench["workloads"]:
        if w["name"] != CELL:
            other = loader.load_cell(ROOT, w["name"], bench)
            assert not {m["name"] for m, _ in other.layer_metrics} & set(NEW)


def read(name, record):
    spec = loader._read(os.path.join(MDIR, name + ".json"))
    return sources.read_metric(spec, MDIR, name, record)


def job(moment=None, channels=None, result=True):
    res = {"counters": {}}
    if moment is not None:
        res["magnetisation"] = {"total": [0.0, 0.0, moment], "atoms": []}
    if channels is not None:
        res["counters"]["num_spin_channels"] = channels
    return {"result": res if result else None, "spans": [], "seconds": 4.5}


SCOPES = {"name": "trace.scopes", "busy_s": 0.25, "steps": 2, "by_scope": {
    "step_xc": {"s": 0.012, "ops": 90},
    "step_xc/xc_spin": {"s": 0.008, "ops": 60},
    "step_xc/xc_gga": {"s": 0.5, "ops": 9}}}


def test_the_three_read_what_they_say():
    record = {"jobs": [job(5.6363, 2), job(5.6361, 2), job(5.6365, 2),
                       job(result=False)],
              "trace_job": {"spans": [{"name": "trace.stop"}, SCOPES]}}
    assert read("moment_ub", record) == 5.6363
    assert read("spin_channels", record) == 2
    assert read("xc_spin_ms", record) == pytest.approx(1000.0 * 0.008 / 2)
    assert read("moment_ub", {"jobs": [job(0.0, 2)]}) == 0.0  # a lost moment


@pytest.mark.parametrize("name, record", [
    ("moment_ub", {"jobs": []}),
    ("moment_ub", {"jobs": [job(None, 1)]}),          # one channel: no record
    ("spin_channels", {"jobs": [job(None, None)]}),   # the parent books none
    ("xc_spin_ms", {"jobs": [], "trace_job": None}),
    ("xc_spin_ms", {"jobs": [], "trace_job": {"spans": [
        {"name": "trace.capture"}, {"name": "trace.stop"}]}}),
    ("xc_spin_ms", {"jobs": [], "trace_job": {"spans": [dict(
        SCOPES, by_scope={"step_xc": {"s": 0.01, "ops": 3}})]}}),  # the parent
])
def test_new_metrics_read_nothing_where_nothing_is(name, record):
    assert read(name, record) is None
