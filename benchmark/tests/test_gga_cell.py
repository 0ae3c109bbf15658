"""The PBE cell (si16-gamma-pbe.scf on si16-gamma-us-pbe): its stored
references against the plain PBE code that wrote them, what
make_refs_folded_pbe.py refuses, the three layer metrics the cell adds, and
that the cell's entry is ISSUE 34's."""

import copy
import json
import os

import pytest

from benchmark import make_refs_folded_pbe as pbe_refs
from benchmark import plain_pwus_pbe
from benchmark.harness import loader, sources
from benchmark.make_refs_folded import WITNESS_TOL_HA_PER_CELL
from conftest import ROOT

MDIR = os.path.join(ROOT, "benchmark", "layer_metrics")
CELL = "si16-gamma-pbe.scf"
NEW = ("fused_step_share", "fused_step_ms", "xc_gradient_ffts_per_scf")


@pytest.fixture(scope="module")
def cell():
    return loader.load_cell(ROOT, CELL)


# -- the stored references ---------------------------------------------------

@pytest.mark.parametrize("block", ["deck", "rehearse"])
def test_stored_references_are_the_plain_pbe_codes(cell, block):
    refs = cell.refs if block == "deck" else cell.refs_rehearse
    deck, cells = pbe_refs.folded_deck(cell.config, block)
    assert cells == 8 and set(refs) == {"0"}
    assert deck["parameters"]["xc_functionals"] == pbe_refs.FUNCTIONALS
    assert deck["parameters"]["precision_wf"] == "fp64"
    run = refs["0"]["kmesh_run"]
    assert run["by"] == "benchmark/plain_pwus_pbe.py" and run["cells"] == 8
    assert run["xc_functionals"] == pbe_refs.FUNCTIONALS
    assert run["ngridk"] == deck["parameters"]["ngridk"] == [2, 2, 2]
    assert run["num_bands"] == 8 and run["num_kpoints"] == 8
    assert run["rho_min"] > 1e-3  # nowhere near the vacuum threshold
    assert refs["0"]["energy_total_ha"] == pytest.approx(
        8 * run["energy_per_cell_ha"], abs=1e-12)
    assert sum(run["terms_ha_per_cell"].values()) == pytest.approx(
        run["energy_per_cell_ha"], abs=1e-12)
    witness = refs["0"]["witness_run_scf"]
    assert witness["path"] == "batched+fused"
    assert abs(witness["energy_per_cell_ha"] - run["energy_per_cell_ha"]) \
        <= WITNESS_TOL_HA_PER_CELL


def test_rehearsal_reference_is_what_the_plain_code_gives_today(cell):
    """The plain code run again on the rehearsal's folded deck (6 s): the
    stored number to 1e-9 Ha a cell (both runs end under a residual of
    1e-12; the deck block's 33 s run is left to make_refs_folded_pbe.py)."""
    deck, cells = pbe_refs.folded_deck(cell.config, "rehearse")
    plain = pbe_refs.plain_energy(deck)
    assert plain["converged"]
    stored = cell.refs_rehearse["0"]
    assert plain["energy_total_ha"] == pytest.approx(
        stored["kmesh_run"]["energy_per_cell_ha"], abs=1e-9)
    assert plain["box"] == stored["kmesh_run"]["box"]


def test_gradient_correction_is_in_the_stored_energy(cell):
    """PBE and LDA differ by tens of mHa a cell: the PBE reference is not the
    LDA twin's by another name."""
    twin = loader.load_cell(ROOT, "si16-gamma.scf").refs["0"]["energy_total_ha"]
    assert abs(cell.refs["0"]["energy_total_ha"] - twin) > 1e-2


def test_plain_pbe_code_imports_nothing_of_the_program():
    with open(plain_pwus_pbe.__file__) as f:
        lines = [ln for ln in f if ln.lstrip().startswith(("import ", "from "))]
    assert lines and not any("sirius_tpu" in ln or "jax" in ln for ln in lines)


# -- what the refs script refuses --------------------------------------------

PLAIN = {"converged": True, "energy_total_ha": -8.7, "iterations": 11,
         "num_kpoints": 8, "box": [50, 50, 50], "rho_min": 4e-3,
         "kinetic": 3.0, "nonlocal": 1.2, "local": -3.0, "hartree": 1.0,
         "xc": -2.5, "ewald": -8.4}
WITNESS = {"energy_per_cell_ha": -8.7 - 3e-8, "scf_iterations": 11,
           "num_kpoints": 8, "path": "batched+fused", "wall_s_cpu": 1.0}


def test_entry_is_cells_times_the_plain_energy(cell):
    deck, cells = pbe_refs.folded_deck(cell.config)
    e = pbe_refs.entry_of(deck, cells, PLAIN, WITNESS, 12.34)
    assert e["energy_total_ha"] == 8 * -8.7
    assert e["kmesh_run"]["by"] == "benchmark/plain_pwus_pbe.py"
    assert e["witness_run_scf"]["minus_plain_ha_per_cell"] == \
        pytest.approx(-3e-8, abs=1e-12)
    assert "minus_plain_ha_per_cell" not in WITNESS  # the input is not edited


@pytest.mark.parametrize("off", [2e-6, -2e-6, float("nan")])
def test_a_witness_too_far_from_the_plain_code_is_refused(cell, off):
    deck, cells = pbe_refs.folded_deck(cell.config)
    far = dict(WITNESS, energy_per_cell_ha=-8.7 + off)
    with pytest.raises(RuntimeError, match="differ by more than"):
        pbe_refs.entry_of(deck, cells, PLAIN, far, 1.0)


def test_an_unconverged_plain_run_is_refused(cell):
    deck, cells = pbe_refs.folded_deck(cell.config)
    with pytest.raises(RuntimeError, match="did not converge"):
        pbe_refs.entry_of(deck, cells, dict(PLAIN, converged=False), WITNESS,
                          1.0)


@pytest.mark.parametrize("why, edit", [
    ("lda", lambda d: d["parameters"].update(
        xc_functionals=["XC_LDA_X", "XC_LDA_C_PZ"])),
    ("pbesol", lambda d: d["parameters"].update(
        xc_functionals=["XC_GGA_X_PBE_SOL", "XC_GGA_C_PBE_SOL"])),
    ("symmetry", lambda d: d["parameters"].update(use_symmetry=True)),
    ("smearing", lambda d: d["parameters"].update(smearing="fermi_dirac")),
    ("norm-conserving", lambda d: d["synthetic"].update(ultrasoft=False)),
    ("positions", lambda d: d["synthetic"].update(
        positions=[[0.0, 0.0, 0.0], [0.26, 0.25, 0.25]])),
])
def test_plain_pbe_code_refuses_another_problem(cell, why, edit):
    deck, _ = pbe_refs.folded_deck(cell.config, "rehearse")
    edit(deck)
    with pytest.raises(ValueError):
        pbe_refs.plain_energy(deck)


def test_a_displaced_supercell_does_not_fold(cell):
    config = copy.deepcopy(cell.config)
    config["geometry"]["displacement_bohr"] = 0.03
    with pytest.raises(ValueError):
        pbe_refs.folded_deck(config)


# -- the three layer metrics ---------------------------------------------------

def read(name, record):
    spec = loader._read(os.path.join(MDIR, name + ".json"))
    return sources.read_metric(spec, MDIR, name, record)


def job(transforms, *step_s, result=True):
    spans = [{"name": "scf.fused_step", "dur_s": s} for s in step_s]
    spans.append({"name": "scf.band_solve", "dur_s": 9.0})
    counters = {} if transforms is None else {
        "num_xc_gradient_transforms": transforms}
    return {"result": {"counters": counters} if result else None,
            "spans": spans, "seconds": 10.0}


def test_fused_step_ms_is_the_median_step_span():
    record = {"jobs": [job(91, 0.100, 0.120, 0.110), job(98, 0.300, 0.105),
                       job(0, 5.0, result=False)]}  # raised: not counted
    assert read("fused_step_ms", record) == pytest.approx(110.0)


def test_xc_gradient_ffts_per_scf_is_the_jobs_counter():
    record = {"jobs": [job(91, 0.1), job(98, 0.1), job(105, 0.1)]}
    assert read("xc_gradient_ffts_per_scf", record) == 98
    assert read("xc_gradient_ffts_per_scf", {"jobs": [job(0, 0.1)]}) == 0


def test_fused_step_share_is_the_modules_time_over_busy_time():
    trace = {"busy_s": 2.0, "modules": [["jit_davidson_gamma", 1.5],
                                        ["jit__step_impl", 0.45],
                                        ["jit_density_gamma", 0.02]]}
    assert read("fused_step_share", {"trace": trace}) == pytest.approx(22.5)


@pytest.mark.parametrize("name, record", [
    ("fused_step_ms", {"jobs": []}),
    ("fused_step_ms", {"jobs": [{"result": {}, "spans": [
        {"name": "scf.potential", "dur_s": 1.0}]}]}),   # a host tail
    ("xc_gradient_ffts_per_scf", {"jobs": []}),
    ("xc_gradient_ffts_per_scf", {"jobs": [job(None, 0.1)]}),  # the parent
    ("fused_step_share", {}),
    ("fused_step_share", {"trace": None}),
    ("fused_step_share", {"trace": {"busy_s": 0.0, "modules": []}}),
    ("fused_step_share", {"trace": {"busy_s": 2.0, "modules": [
        ["jit_davidson_gamma", 1.5]]}}),                # no such module
    ("fused_step_share", {"trace": {"busy_s": 2.0}}),   # a CPU rehearsal
])
def test_new_metrics_read_nothing_where_nothing_is(name, record):
    assert read(name, record) is None


# -- the cell's entry ----------------------------------------------------------

def test_the_cell_is_the_issues(cell):
    bench = loader.load_benchmark(ROOT)
    (w,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert w == bench["workloads"][-1]  # appended
    assert (w["config"], w["traffic"], w["chips"]) == (
        "si16-gamma-us-pbe", "scf-loop", 1)
    assert len(bench["workloads"]) == 7
    assert sum(x["chips"] == 4 for x in bench["workloads"]) == 1
    entry = bench["configs"][-1]
    assert entry["name"] == "si16-gamma-us-pbe"
    assert entry["reduced"] == cell.config["reduced"] == [
        "atoms", "num_bands", "geometries"]
    assert entry["source"] == cell.config["source"]
    assert len(entry["source"]) <= 200
    assert cell.config["architecture"] is None
    assert cell.config["expected_path"] == "gamma"
    p = cell.config["deck"]["parameters"]
    assert p["xc_functionals"] == ["XC_GGA_X_PBE", "XC_GGA_C_PBE"]
    assert (p["gk_cutoff"], p["pw_cutoff"], p["num_bands"], p["ngridk"]) == (
        6.0, 20.0, 64, [1, 1, 1])
    assert (p["density_tol"], p["energy_tol"], p["precision_wf"]) == (
        1e-5, 1e-5, "fp32")
    assert p["use_symmetry"] is False and p["smearing_width"] == 0.025
    assert cell.config["geometry"] == {
        "supercell": 2, "displacement_bohr": 0.0, "rng_base": 1000,
        "geometries": 1}
    assert cell.config["guarantee"]["energy_tol_ha_per_atom"] * 16 == \
        pytest.approx(8e-5)
    # everything but the functional is the LDA twin's deck: same shapes, so
    # the same band-solve executable
    twin = loader.load_cell(ROOT, "si16-gamma.scf").config["deck"]
    mine = copy.deepcopy(cell.config["deck"])
    for d in (twin, mine):
        d = d["parameters"]
        d.pop("xc_functionals"), d.pop("num_dft_iter")
    assert json.dumps(mine, sort_keys=True) == json.dumps(twin, sort_keys=True)
    # a job that does not converge fails fast (num_dft_iter_why)
    assert p["num_dft_iter"] <= 24 and "num_dft_iter_why" in cell.config
    assert "rehearse_why" in cell.config
    assert "trace_capture_steps_why" in cell.config


def test_the_new_metrics_are_appended_for_this_cell_only(cell):
    bench = loader.load_benchmark(ROOT)
    last = bench["per_layer"][-3:]
    assert tuple(m["name"] for m in last) == NEW
    for m in last:
        assert m["workloads"] == [CELL] and m["moves"] == "scf_s"
        assert m["layer"] == "iteration tail"
        spec = loader._read(os.path.join(MDIR, m["name"] + ".json"))
        assert (spec["layer"], spec["unit"], spec["source"]) == (
            m["layer"], m["unit"], m["source"])
    assert [m["source"] for m in last] == [
        "device_trace", "program_span", "program_counter"]
    mine = [e["name"] for e, _ in cell.layer_metrics]
    assert set(NEW) <= set(mine) and "tail_box_fills_per_scf" in mine
    other = [e["name"] for e, _ in
             loader.load_cell(ROOT, "si16-gamma.scf").layer_metrics]
    assert not set(NEW) & set(other)
