"""The stock deck's symmetry (the benchmark's si2-k666-us-sym): with
``use_symmetry`` left at the schema's default the undisplaced diamond cell
runs on the irreducible wedge of its mesh with unequal weights, and the fused
step symmetrises the density matrix, the new density and v_eff over the 48
operations. An exact symmetry changes no energy, so the wedge, the full mesh
and the plain code on all k-points (benchmark/plain_pwus.py, whose number the
benchmark stores) have to agree; held here at the rehearsal size, gk 3 /
pw 7, 8 bands, mesh [3, 3, 3]: 4 k-points for 14 (27 without time reversal).
"""

import copy
import json
import os

import jax
import numpy as np
import pytest

from sirius_tpu.config.schema import load_config
from sirius_tpu.crystal.kpoints import irreducible_kmesh
from sirius_tpu.crystal.symmetry import CrystalSymmetry
from sirius_tpu.dft.scf import run_scf
from sirius_tpu.obs import events as obs_events
from sirius_tpu.obs import spans
from sirius_tpu.serve.scheduler import build_job_context

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BAR_HA = 1e-5  # the configuration's guarantee: 5e-6 Ha an atom, 2 atoms


def _config(name):
    with open(os.path.join(ROOT, "benchmark", "configs", name,
                           "config.json")) as f:
        return json.load(f)


def rehearsal(name="si2-k666-us-sym", **params):
    """The configuration's rehearsal deck as a job gets it (ideal
    positions), with ``params`` laid over its parameters."""
    d = {k: copy.deepcopy(v) for k, v in _config(name)["rehearse"].items()
         if k != "geometry"}
    d["parameters"].update(params)
    return d


F64 = dict(precision_wf="fp64", density_tol=1e-9, energy_tol=1e-10)


def run(d, devices, events=None):
    if events is not None:
        d = copy.deepcopy(d)
        d["control"]["events_path"] = str(events)
    with spans.capture() as cap:
        cfg = load_config(copy.deepcopy(d))
        ctx = build_job_context(cfg, ".")
        r = run_scf(cfg, ctx=ctx, devices=devices)
    r["_spans"], r["_nk"] = list(cap.records), int(ctx.gkvec.num_kpoints)
    r["_weights"] = np.asarray(ctx.kweights)
    return r


@pytest.fixture(scope="module")
def one_device():
    return jax.devices()[1:2]  # a compute device that is not the host's


@pytest.fixture(scope="module")
def wedge_f64(one_device):
    return run(rehearsal(**F64), one_device)


@pytest.fixture(scope="module")
def wedge_f32(one_device, tmp_path_factory):
    ev = tmp_path_factory.mktemp("sym") / "events.jsonl"
    r = run(rehearsal(), one_device, events=ev)
    r["_events"] = obs_events.read_events(str(ev))
    return r


def test_wedge_has_four_weighted_kpoints(wedge_f64):
    assert wedge_f64["_nk"] == 4
    np.testing.assert_allclose(np.sort(wedge_f64["_weights"] * 27),
                               [1, 6, 8, 12], atol=1e-12)
    assert wedge_f64["converged"]
    assert wedge_f64["placement"]["path"] == "batched+fused"


@pytest.mark.parametrize("rule", ["by_energy", "by_residual"])
def test_wedge_is_the_full_mesh_f64(rule, wedge_f64, one_device):
    """Under the solver's default exit (a step's move of the Rayleigh
    quotient) and under the residual rule."""
    solver = ({} if rule == "by_energy" else
              {"iterative_solver": {"converge_by_energy": 0,
                                    "residual_tolerance": 1e-10}})
    if solver:
        wedge = run(dict(rehearsal(**F64), **solver), one_device)
    else:
        wedge = wedge_f64
    full = run(dict(rehearsal(use_symmetry=False, **F64), **solver),
               one_device)
    assert full["_nk"] == 14 and wedge["_nk"] == 4
    assert full["counters"]["num_sym_pw"] == 0
    for r in (wedge, full):
        assert r["converged"] and r["placement"]["path"] == "batched+fused"
    assert abs(wedge["energy"]["total"] - full["energy"]["total"]) <= 1e-8


def test_f32_wedge_is_within_the_bar_of_f64(wedge_f32, wedge_f64):
    r = wedge_f32
    assert r["converged"] and r["placement"]["path"] == "batched+fused"
    assert r["placement"]["band_solve"][1] == "float32"
    assert r["placement"]["fused_step"][1] == "float32"
    assert abs(r["energy"]["total"] - wedge_f64["energy"]["total"]) <= BAR_HA


def test_plain_code_on_every_kpoint_is_the_programs_wedge(wedge_f64):
    """The stored reference's route: plain_pwus on all 27 k-points, equal
    weights, no symmetry, no time reversal, nothing of sirius_tpu."""
    from threadpoolctl import threadpool_limits

    from benchmark import plain_pwus

    p = rehearsal()["parameters"]
    # 150-row matrices: one BLAS thread runs them in 2 s, the default pool
    # in 13 s alone and in minutes beside the suite's other workers
    with threadpool_limits(limits=1):
        plain = plain_pwus.scf(
            ngridk=tuple(p["ngridk"]), gk_cutoff=p["gk_cutoff"],
            pw_cutoff=p["pw_cutoff"], num_bands=p["num_bands"],
            smearing_width=p["smearing_width"], lattice_constant=10.26,
            density_tol=1e-12)
    assert plain["converged"] and plain["num_kpoints"] == 27
    assert abs(plain["energy_total_ha"]
               - wedge_f64["energy"]["total"]) <= 1e-6
    with open(os.path.join(ROOT, "benchmark", "configs", "si2-k666-us-sym",
                           "refs_rehearse.json")) as f:
        stored = json.load(f)["geometries"]["0"]["energy_total_ha"]
    assert abs(stored - plain["energy_total_ha"]) <= 1e-10


def test_the_666_mesh_of_the_diamond_group_has_sixteen_points():
    a = 10.26
    lattice = a / 2 * np.array([[0.0, 1, 1], [1, 0, 1], [1, 1, 0]])
    pos = np.array([[0.0, 0, 0], [0.25, 0.25, 0.25]])
    sym = CrystalSymmetry.find(lattice, pos, np.zeros(2, dtype=np.int32),
                               np.zeros((2, 3)), 0)
    assert sym.num_ops == 48
    kpts, kw = irreducible_kmesh([6, 6, 6], [0, 0, 0], sym, use_symmetry=True,
                                 time_reversal=True)
    orbits = _config("si2-k666-us-sym")["symmetry"]["orbit_sizes"]
    assert len(kpts) == 16 == len(orbits)
    np.testing.assert_allclose(kw * 216, orbits, atol=1e-10)
    assert abs(kw.sum() - 1.0) <= 1e-14
    full, fw = irreducible_kmesh([6, 6, 6], [0, 0, 0], None,
                                 use_symmetry=False, time_reversal=True)
    assert len(full) == 112 and abs(fw.sum() - 1.0) <= 1e-14


# ---- counters, spans, events --------------------------------------------

def test_three_symmetrisations_a_step_are_counted(wedge_f32):
    c, iters = wedge_f32["counters"], wedge_f32["num_scf_iterations"]
    assert c["num_sym_pw"] == 3 * iters
    assert c["num_kpoints_solved"] == 4
    (done,) = [e for e in wedge_f32["_events"] if e["kind"] == "scf_done"]
    assert done["num_sym_pw"] == 3 * iters


def test_a_deck_without_symmetry_counts_none(one_device):
    r = run(rehearsal("si2-k444-us"), one_device)
    assert r["converged"] and r["counters"]["num_sym_pw"] == 0
    names = {s["name"] for s in r["_spans"]}
    assert "scf.setup.symmetry" not in names
    (setup,) = [s for s in r["_spans"] if s["name"] == "scf.setup"]
    assert "symmetry" not in setup
    assert {s["sym_ops"] for s in r["_spans"]
            if s["name"] == "scf.fused_step"} == {0}
    (search,) = [s for s in r["_spans"] if s["name"] == "context.symmetry"]
    assert search["num_ops"] == 0 and search["kpoints_irreducible"] == 8


def test_spans_say_what_the_symmetry_is_and_what_it_cost(wedge_f32):
    by = {}
    for s in wedge_f32["_spans"]:
        by.setdefault(s["name"], []).append(s)
    (setup,) = by["scf.setup"]
    assert setup["symmetry"] == {"num_ops": 48, "kpoints_mesh": 27,
                                 "kpoints_irreducible": 4}
    steps = by["scf.fused_step"]
    assert len(steps) == wedge_f32["num_scf_iterations"]
    assert {s["sym_ops"] for s in steps} == {48}
    # the rotation tables: once a job, inside scf.setup
    (tables,) = by["scf.setup.symmetry"]
    assert tables["parent_id"] == setup["span_id"]
    assert tables["num_ops"] == 48
    # the group search: a child of the context build
    (search,), (build,) = by["context.symmetry"], by["serve.context_build"]
    assert search["parent_id"] == build["span_id"]
    assert (search["num_ops"], search["kpoints_mesh"],
            search["kpoints_irreducible"]) == (48, 27, 4)
