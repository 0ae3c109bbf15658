"""collective_share and subspace_eigh_share: operations found by name in the
trace, as a share of the device's busy time (harness/name_share.py), worked
out by hand on a small trace; and the folded reference's deck."""

import json
import os

import pytest

from benchmark.harness import loader, name_share, sources
from conftest import ROOT

MDIR = os.path.join(ROOT, "benchmark", "layer_metrics")

# two devices, times in ns. Device 0: a while loop of the eigensolver
# [0, 40) with a rotation [10, 20) inside it, an all-gather [50, 60), its
# done half [70, 75), a plain fusion [60, 100). Device 1: a fusion [0, 80),
# an all-reduce [80, 100).
RAW = {
    "devices": ["/device:TPU:0", "/device:TPU:1"],
    "names": ["while.127 wide.EighJacobiSweeps_body.0.clone",
              "fusion.9 ApplyRotations_body.3", "all-gather-start.3",
              "all-gather-done.3", "fusion.16 fused_computation.7",
              "all-reduce.13 region_30.31.clone", "custom-call.189 EighTpu"],
    "dev": [0, 0, 0, 0, 0, 1, 1],
    "name": [0, 1, 2, 3, 4, 4, 5],
    "start_ns": [0.0, 10.0, 50.0, 70.0, 60.0, 0.0, 80.0],
    "dur_ns": [40.0, 10.0, 10.0, 5.0, 40.0, 80.0, 20.0],
    "window_ns": [0.0, 100.0],
}


def spec(name):
    with open(os.path.join(MDIR, name + ".json")) as f:
        return json.load(f)


def test_collective_share_is_a_union_over_busy_time_mean_over_devices():
    record = {"trace_raw": RAW}
    got = sources.read_metric(spec("collective_share"), MDIR,
                              "collective_share", record)
    # device 0: busy [0,40) + [50,100) = 90, collectives [50,60) + [70,75) = 15
    # device 1: busy 100, collectives 20
    assert got == pytest.approx((100 * 15 / 90 + 100 * 20 / 100) / 2)
    note = record["notes"]["collective_ops"]
    assert note["share_pct"] == got
    assert note["ops_s_per_device"]["all-reduce.13 region_30.31.clone"] == \
        pytest.approx(20e-9 / 2)
    assert "fusion.16 fused_computation.7" not in note["ops_s_per_device"]


def test_subspace_eigh_share_counts_nested_operations_once():
    record = {"trace_raw": RAW}
    got = sources.read_metric(spec("subspace_eigh_share"), MDIR,
                              "subspace_eigh_share", record)
    # device 0: the rotation lies inside the while loop: 40 of 90; device 1: 0
    assert got == pytest.approx((100 * 40 / 90 + 0.0) / 2)
    assert set(record["notes"]["subspace_eigh_ops"]["ops_s_per_device"]) == {
        RAW["names"][0], RAW["names"][1]}


def test_subspace_eigh_share_counts_the_qdwh_program_by_its_conditionals():
    """Above 256 rows the TPU expands eigh into a QDWH program: an agenda
    loop whose body is one conditional, with the EighTpu base cases inside
    it (si54-gamma.scf: conditional.20/.21/.1 hold 1.71 s of a capture of
    8.29 s, the base cases 0.26 s of those)."""
    raw = {
        "devices": ["/device:TPU:0"],
        "names": ["while.236 wide.region_20.138.sunk.clone",
                  "conditional.20", "custom-call.77 EighTpu",
                  "fusion.6934 fused_computation.6035.clone"],
        "dev": [0, 0, 0, 0],
        "name": [0, 1, 2, 3],
        "start_ns": [0.0, 1.0, 5.0, 40.0],
        "dur_ns": [40.0, 38.0, 10.0, 60.0],
        "window_ns": [0.0, 100.0],
    }
    record = {"trace_raw": raw}
    got = sources.read_metric(spec("subspace_eigh_share"), MDIR,
                              "subspace_eigh_share", record)
    assert got == pytest.approx(38.0)  # the conditional, its base case once
    assert "while.236 wide.region_20.138.sunk.clone" not in \
        record["notes"]["subspace_eigh_ops"]["ops_s_per_device"]


def test_the_program_emits_no_conditional_of_its_own():
    """What lets a conditional in the trace be read as the eigensolver's:
    no module of sirius_tpu calls lax.cond or lax.switch (the lint rules
    under analysis/ only name them)."""
    import re

    found = []
    for top, _, files in os.walk(os.path.join(ROOT, "sirius_tpu")):
        if os.sep + "analysis" in top:
            continue
        for name in files:
            if name.endswith(".py"):
                with open(os.path.join(top, name)) as f:
                    for n, line in enumerate(f, 1):
                        code = line.split("#")[0]
                        if re.search(r"lax\.(cond|switch)\(", code):
                            found.append(f"{name}:{n}")
    assert not found, found


def test_no_trace_is_nothing_to_read_and_no_match_is_zero():
    for name in ("collective_share", "subspace_eigh_share"):
        assert sources.read_metric(spec(name), MDIR, name,
                                   {"trace_raw": None}) is None
    assert name_share.share_of_busy(RAW, "no-such-operation")[0] == 0.0
    empty = dict(RAW, dev=[], name=[], start_ns=[], dur_ns=[])
    assert name_share.share_of_busy(empty, "eigh") == (None, {})


def test_finalize_ms_reads_the_programs_span():
    record = {"jobs": [
        {"result": {}, "spans": [{"name": "scf.finalize", "dur_s": 0.5},
                                 {"name": "scf.setup", "dur_s": 9.0}]},
        {"result": {}, "spans": [{"name": "scf.finalize", "dur_s": 0.7}]},
        {"result": None, "spans": [{"name": "scf.finalize", "dur_s": 99.0}]}]}
    got = sources.read_metric(spec("finalize_ms"), MDIR, "finalize_ms", record)
    assert got == pytest.approx(600.0)
    assert sources.read_metric(spec("finalize_ms"), MDIR, "finalize_ms",
                               {"jobs": [{"result": {}, "spans": []}]}) is None


def test_folded_deck_is_the_two_atom_cell_on_the_kmesh():
    from benchmark import make_refs_folded

    cell = loader.load_cell(ROOT, "si54-gamma.scf")
    deck, cells = make_refs_folded.folded_deck(cell.config)
    p = deck["parameters"]
    assert cells == 27 and p["ngridk"] == [3, 3, 3] and p["num_bands"] == 8
    assert p["precision_wf"] == "fp64" and p["use_symmetry"] is False
    assert (p["gk_cutoff"], p["pw_cutoff"]) == (6.0, 20.0)
    assert len(deck["synthetic"]["positions"]) == 2
    assert deck["synthetic"]["a"] == pytest.approx(10.26)
    small, cells = make_refs_folded.folded_deck(cell.config, "rehearse")
    assert cells == 8 and small["parameters"]["ngridk"] == [2, 2, 2]
    # the stored references are the plain code's energy times the number of
    # cells; the program's own k-mesh run is a witness beside it
    for refs in (cell.refs, cell.refs_rehearse):
        run = refs["0"]["kmesh_run"]
        assert run["by"] == "benchmark/plain_pwus.py"
        assert refs["0"]["energy_total_ha"] == pytest.approx(
            run["cells"] * run["energy_per_cell_ha"], abs=1e-12)
        assert sum(run["terms_ha_per_cell"].values()) == pytest.approx(
            run["energy_per_cell_ha"], abs=1e-12)
        witness = refs["0"]["witness_run_scf"]
        assert abs(witness["energy_per_cell_ha"]
                   - run["energy_per_cell_ha"]) <= \
            make_refs_folded.WITNESS_TOL_HA_PER_CELL
    # a configuration whose atoms move does not fold
    with pytest.raises(ValueError):
        make_refs_folded.folded_deck(
            loader.load_cell(ROOT, "si16-gamma.scf").config)


def test_the_plain_reference_imports_nothing_of_the_program():
    import ast

    with open(os.path.join(ROOT, "benchmark", "plain_pwus.py")) as f:
        tree = ast.parse(f.read())
    roots = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            roots.update(a.name.split(".")[0] for a in node.names)
        elif isinstance(node, ast.ImportFrom):
            roots.add((node.module or "").split(".")[0])
    assert roots <= {"__future__", "argparse", "itertools", "json", "math",
                     "time", "numpy", "scipy"}, roots
