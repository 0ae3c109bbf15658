"""The reference of a supercell on a k-mesh (si16-k222-us): the folded deck
benchmark/make_refs_folded_kmesh.py builds, what it refuses, what it stored,
and the two layer metrics the cell adds (kset_rows_per_s, kset_eigh_share)."""

import copy
import os

import pytest

from benchmark import make_refs_folded_kmesh as kmesh
from benchmark.harness import loader, name_share, sources
from conftest import ROOT

MDIR = os.path.join(ROOT, "benchmark", "layer_metrics")
CELL = "si16-k222.scf"


@pytest.fixture(scope="module")
def cell():
    return loader.load_cell(ROOT, CELL)


def scaled(config, supercell, mesh, num_bands):
    """The cell's configuration at another supercell, mesh and band count."""
    c = copy.deepcopy(config)
    c["geometry"]["supercell"] = supercell
    c["deck"]["parameters"].update(ngridk=list(mesh), num_bands=num_bands)
    return c


def test_folded_deck_is_the_two_atom_cell_on_the_444_mesh(cell):
    deck, cells = kmesh.folded_deck(cell.config)
    p = deck["parameters"]
    assert cells == 8 and p["ngridk"] == [4, 4, 4] and p["num_bands"] == 8
    assert p["precision_wf"] == "fp64" and p["use_symmetry"] is False
    assert (p["gk_cutoff"], p["pw_cutoff"]) == (6.0, 20.0)
    assert (p["density_tol"], p["energy_tol"]) == (1e-8, 1e-9)
    assert deck["synthetic"]["positions"] == [[0.0, 0.0, 0.0],
                                              [0.25, 0.25, 0.25]]
    assert deck["synthetic"]["a"] == pytest.approx(10.26)
    small, one = kmesh.folded_deck(cell.config, "rehearse")
    assert one == 1 and small["parameters"]["ngridk"] == [2, 2, 2]
    assert small["parameters"]["num_bands"] == 8


@pytest.mark.parametrize("n, mesh, bands, folded, per_k", [
    (2, (2, 2, 2), 64, [4, 4, 4], 8),
    (2, (1, 1, 2), 64, [2, 2, 4], 8),
    (3, (2, 2, 2), 216, [6, 6, 6], 8),
    (3, (1, 1, 1), 108, [3, 3, 3], 4),
    (1, (4, 4, 4), 26, [4, 4, 4], 26),
])
def test_fold_rule_mesh_times_n_bands_over_n_cubed(cell, n, mesh, bands,
                                                   folded, per_k):
    deck, cells = kmesh.folded_deck(scaled(cell.config, n, mesh, bands))
    assert cells == n ** 3
    assert deck["parameters"]["ngridk"] == folded
    assert deck["parameters"]["num_bands"] == per_k
    assert len(deck["synthetic"]["positions"]) == 2


def test_gamma_supercell_folds_as_make_refs_folded_does():
    """On a Gamma deck the two scripts build the same deck."""
    from benchmark import make_refs_folded

    config = loader.load_cell(ROOT, "si54-gamma.scf").config
    assert kmesh.folded_deck(config) == make_refs_folded.folded_deck(config)


@pytest.mark.parametrize("why, edit", [
    ("displaced", lambda c: c["geometry"].update(displacement_bohr=0.03)),
    ("shifted", lambda c: c["deck"]["parameters"].update(shiftk=[1, 1, 1])),
    ("bands", lambda c: c["deck"]["parameters"].update(num_bands=60)),
    ("mesh", lambda c: c["deck"]["parameters"].update(ngridk=[2, 2])),
])
def test_what_does_not_fold_is_refused(cell, why, edit):
    config = copy.deepcopy(cell.config)
    edit(config)
    with pytest.raises(ValueError):
        kmesh.folded_deck(config)


PLAIN = {"converged": True, "energy_total_ha": -8.5, "iterations": 11,
         "num_kpoints": 64, "box": [50, 50, 50], "kinetic": 3.0,
         "nonlocal": 1.0, "local": -3.0, "hartree": 1.0, "xc": -2.5,
         "ewald": -8.0}
WITNESS = {"energy_per_cell_ha": -8.5 - 3e-8, "scf_iterations": 9,
           "num_kpoints": 36, "path": "batched+fused", "wall_s_cpu": 1.0}


def test_entry_is_cells_times_the_plain_energy(cell):
    deck, cells = kmesh.folded_deck(cell.config)
    e = kmesh.entry_of(deck, cells, PLAIN, WITNESS, 12.34)
    assert e["energy_total_ha"] == 8 * -8.5
    assert e["kmesh_run"]["by"] == "benchmark/plain_pwus.py"
    assert e["kmesh_run"]["ngridk"] == [4, 4, 4]
    assert e["witness_run_scf"]["minus_plain_ha_per_cell"] == \
        pytest.approx(-3e-8, abs=1e-12)
    assert "minus_plain_ha_per_cell" not in WITNESS  # the input is not edited


@pytest.mark.parametrize("off", [2e-6, -2e-6, float("nan")])
def test_a_witness_too_far_from_the_plain_code_is_refused(cell, off):
    deck, cells = kmesh.folded_deck(cell.config)
    far = dict(WITNESS, energy_per_cell_ha=-8.5 + off)
    with pytest.raises(RuntimeError, match="differ by more than"):
        kmesh.entry_of(deck, cells, PLAIN, far, 1.0)


def test_an_unconverged_plain_run_is_refused(cell):
    deck, cells = kmesh.folded_deck(cell.config)
    with pytest.raises(RuntimeError, match="did not converge"):
        kmesh.entry_of(deck, cells, dict(PLAIN, converged=False), WITNESS, 1.0)


@pytest.mark.parametrize("block", ["deck", "rehearse"])
def test_stored_references_are_the_plain_codes(cell, block):
    refs = cell.refs if block == "deck" else cell.refs_rehearse
    deck, cells = kmesh.folded_deck(cell.config, block)
    assert set(refs) == {"0"}
    run = refs["0"]["kmesh_run"]
    assert run["by"] == "benchmark/plain_pwus.py" and run["cells"] == cells
    assert run["ngridk"] == deck["parameters"]["ngridk"]
    assert run["num_bands"] == 8
    # the plain code solves every point of the mesh: no time reversal
    assert run["num_kpoints"] == run["ngridk"][0] ** 3
    assert refs["0"]["energy_total_ha"] == pytest.approx(
        cells * run["energy_per_cell_ha"], abs=1e-12)
    assert sum(run["terms_ha_per_cell"].values()) == pytest.approx(
        run["energy_per_cell_ha"], abs=1e-12)
    witness = refs["0"]["witness_run_scf"]
    assert witness["path"] == "batched+fused"
    assert abs(witness["energy_per_cell_ha"] - run["energy_per_cell_ha"]) \
        <= kmesh.WITNESS_TOL_HA_PER_CELL


def test_the_cell_is_the_issues(cell):
    bench = loader.load_benchmark(ROOT)
    assert cell.chips == 1 and cell.traffic_name == "scf-loop"
    assert cell.config["expected_path"] == "batched+fused"
    assert cell.config["deck"]["parameters"]["ngridk"] == [2, 2, 2]
    assert cell.config["deck"]["parameters"]["num_bands"] == 64
    entry = [c for c in bench["configs"] if c["name"] == "si16-k222-us"][0]
    assert entry["reduced"] == cell.config["reduced"] == ["kpoints",
                                                          "geometries"]
    assert entry["source"] == cell.config["source"]
    g = cell.config["guarantee"]
    assert g["energy_tol_ha_per_atom"] * 16 == pytest.approx(8e-5)
    # a job that does not converge fails fast: at most 1.6 times what the
    # chip needs, which num_dft_iter_why states
    assert cell.config["deck"]["parameters"]["num_dft_iter"] <= 24
    (m,) = [m for m in bench["per_layer"] if m["name"] == "kset_rows_per_s"]
    assert m["workloads"] == [CELL] and m["moves"] == "scf_s"
    assert bench["per_layer"][-2] is m  # appended, kset_eigh_share after it
    spec = loader._read(os.path.join(MDIR, "kset_rows_per_s.json"))
    assert (spec["layer"], spec["unit"], spec["source"]) == (
        m["layer"], m["unit"], m["source"])
    assert "kset_rows_per_s" in [e["name"] for e, _ in cell.layer_metrics]
    other = loader.load_cell(ROOT, "si2-k444.scf")
    assert "kset_rows_per_s" not in [e["name"] for e, _ in other.layer_metrics]


def rows_per_s(record):
    spec = loader._read(os.path.join(MDIR, "kset_rows_per_s.json"))
    return sources.read_metric(spec, MDIR, "kset_rows_per_s", record)


def job(rows, *band_solve_s, result=True):
    spans = [{"name": "scf.band_solve", "dur_s": s} for s in band_solve_s]
    spans.append({"name": "scf.fused_step", "dur_s": 100.0})
    res = {"counters": {"num_loc_op_applied": rows}} if result else None
    return {"result": res, "spans": spans, "seconds": 50.0}


def test_kset_rows_per_s_is_rows_over_fenced_band_solve_seconds():
    record = {"jobs": [job(15000, 1.0, 2.0),      # 5000 rows/s
                       job(30000, 2.0, 2.0),      # 7500
                       job(9000, 0.5, 0.5),       # 9000
                       job(1e9, 1.0, result=False)]}  # raised: not counted
    assert rows_per_s(record) == pytest.approx(7500.0)


@pytest.mark.parametrize("record", [
    {"jobs": []},
    {"jobs": [job(15000)]},                         # no band-solve span
    {"jobs": [{"result": {"counters": {}}, "spans": [
        {"name": "scf.band_solve", "dur_s": 1.0}]}]},  # no counter
    {"jobs": [job(0, 1.0)]},
])
def test_kset_rows_per_s_reads_nothing_where_nothing_is(record):
    assert rows_per_s(record) is None


def eigh_share(record):
    spec = loader._read(os.path.join(MDIR, "kset_eigh_share.json"))
    return sources.read_metric(spec, MDIR, "kset_eigh_share", record)


def test_kset_eigh_share_is_the_accepted_reading_for_this_cell_only(cell):
    """subspace_eigh_share's workloads list may not be edited, so the cell
    carries the same reading under its own name: same pattern, same reader,
    same layer; and it says so about the deck (config.json subspace_path)."""
    bench = loader.load_benchmark(ROOT)
    mine = bench["per_layer"][-1]
    (accepted,) = [m for m in bench["per_layer"]
                   if m["name"] == "subspace_eigh_share"]
    assert mine["name"] == "kset_eigh_share" and mine["workloads"] == [CELL]
    assert CELL not in accepted["workloads"]
    for key in ("unit", "better", "source", "layer", "moves"):
        assert mine[key] == accepted[key]
    spec = loader._read(os.path.join(MDIR, "kset_eigh_share.json"))
    old = loader._read(os.path.join(MDIR, "subspace_eigh_share.json"))
    assert spec["args"]["pattern"] == old["args"]["pattern"]
    assert spec["args"]["note"] != old["args"]["note"]
    assert "kset_eigh_share" in [e["name"] for e, _ in cell.layer_metrics]
    other = loader.load_cell(ROOT, "si2-k444.scf")
    assert "kset_eigh_share" not in [e["name"] for e, _ in other.layer_metrics]
    assert "time-reversal invariant" in cell.config["subspace_path"]


# one device, ns: the chunk loop [0, 1000) holds two EighTpu calls of the
# real subspace, [100, 140) and [500, 520); on the parent's program the same
# place holds a Jacobi sweep loop [100, 400) with its rotations inside
REAL = {"devices": ["/device:TPU:0"],
        "names": ["while.33 wide.region_1", "custom-call.93 EighTpu",
                  "custom-call.94 EighTpu"],
        "dev": [0, 0, 0], "name": [0, 1, 2],
        "start_ns": [0.0, 100.0, 500.0], "dur_ns": [1000.0, 40.0, 20.0],
        "window_ns": [0.0, 1000.0]}
CPLX = dict(REAL, names=["while.71 wide.region_1",
                         "while.78 wide.EighJacobiSweeps_body.0.clone",
                         "while.80 wide.ApplyRotations_body.0.clone"],
            start_ns=[0.0, 100.0, 150.0], dur_ns=[1000.0, 300.0, 200.0])


@pytest.mark.parametrize("raw, share, ops", [
    (REAL, 6.0, {"custom-call.93 EighTpu", "custom-call.94 EighTpu"}),
    (CPLX, 30.0, {"while.78 wide.EighJacobiSweeps_body.0.clone",
                  "while.80 wide.ApplyRotations_body.0.clone"})])
def test_kset_eigh_share_finds_either_eigensolver_by_name(raw, share, ops):
    record = {"trace_raw": raw}
    assert eigh_share(record) == pytest.approx(share)
    assert set(record["notes"]["kset_eigh_ops"]["ops_s_per_device"]) == ops
    assert "subspace_eigh_ops" not in record["notes"]


def test_kset_eigh_share_reads_nothing_without_a_trace():
    assert eigh_share({"trace_raw": None}) is None
    assert eigh_share({}) is None
    assert name_share.share_of_busy(
        REAL, loader._read(os.path.join(MDIR, "kset_eigh_share.json"))
        ["args"]["pattern"])[0] == pytest.approx(6.0)
