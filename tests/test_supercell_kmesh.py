"""A supercell on a k-mesh (the benchmark's si16-k222-us): the undisplaced
n x n x n supercell on the Gamma-centred mesh m is the 2-atom cell on the
Gamma-centred mesh n*m (benchmark/make_refs_folded_kmesh.py rests on it), it
runs through the batched k-set solve with the fused tail, and run_scf says
how large that one program is.

The fold is held here on the mesh [1, 1, 2] of the supercell against
[2, 2, 4] of the cell: the [2, 2, 2] mesh of a 16-atom cell in f64 does not
finish in a test's time on the CPU backend; the rule is the same along each
axis."""

import copy
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sirius_tpu.config.schema import load_config
from sirius_tpu.dft import band_solve
from sirius_tpu.dft.scf import run_scf
from sirius_tpu.obs import spans
from sirius_tpu.serve.scheduler import build_job_context

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PARAMS = {
    "gk_cutoff": 3.0, "pw_cutoff": 7.0, "use_symmetry": False,
    "xc_functionals": ["XC_LDA_X", "XC_LDA_C_PZ"], "smearing_width": 0.025,
    "num_dft_iter": 60, "precision_wf": "fp64", "density_tol": 1e-8,
    "energy_tol": 1e-9,
}


def deck(supercell, ngridk, num_bands, **params):
    return {"parameters": dict(PARAMS, ngridk=list(ngridk),
                               num_bands=num_bands, **params),
            "control": {"ngk_pad_quantum": 16, "verbosity": 0},
            "synthetic": {"ultrasoft": True, "supercell": supercell}}


def context(d):
    cfg = load_config(copy.deepcopy(d))
    return cfg, build_job_context(cfg, ".")


def run(d, devices):
    cfg, ctx = context(d)
    with spans.capture() as cap:
        r = run_scf(cfg, ctx=ctx, devices=devices)
    r["_spans"] = list(cap.records)
    return r


@pytest.fixture(scope="module")
def one_device():
    return jax.devices()[1:2]  # a compute device that is not the host's


def test_supercell_on_a_kmesh_is_the_cell_on_the_finer_mesh(one_device):
    big = run(deck(2, (1, 1, 2), 64), one_device)
    small = run(deck(1, (2, 2, 4), 8), one_device)
    for r in (big, small):
        assert r["converged"] and r["placement"]["path"] == "batched+fused"
    assert big["counters"]["num_kpoints_solved"] == 2
    assert abs(big["energy"]["total"] - 8 * small["energy"]["total"]) <= 1e-8


@pytest.fixture(scope="module")
def rehearsal_deck():
    """The rehearsal deck of the benchmark's si16-k222-us: the 2-atom cell
    on the 2x2x2 mesh, 8 bands, 32-bit."""
    with open(os.path.join(ROOT, "benchmark", "configs", "si16-k222-us",
                           "config.json")) as f:
        config = json.load(f)
    return {k: copy.deepcopy(v) for k, v in config["rehearse"].items()
            if k != "geometry"}


@pytest.fixture(scope="module")
def eight_k_f32(rehearsal_deck, one_device):
    return run(rehearsal_deck, one_device)


def test_eight_kpoints_on_one_device_take_the_batched_solve(rehearsal_deck,
                                                            one_device):
    cfg, ctx = context(rehearsal_deck)
    assert ctx.gkvec.num_kpoints == 8  # every point of the mesh is its own -k
    band = band_solve.choose(ctx, cfg, one_device, serial_bands=False,
                             hub=None, paw=None, mgga=False,
                             wf_dtype=jnp.complex64)
    assert isinstance(band, band_solve.KsetSolver)
    assert band.mesh is None and band.feeds_fused


def test_f32_kset_job_is_within_the_bar_of_f64(rehearsal_deck, eight_k_f32,
                                               one_device):
    d = copy.deepcopy(rehearsal_deck)
    d["parameters"].update(precision_wf="fp64", density_tol=1e-8,
                           energy_tol=1e-9)
    f64 = run(d, one_device)
    r = eight_k_f32
    assert r["converged"] and f64["converged"]
    assert r["placement"]["path"] == "batched+fused"
    assert r["placement"]["band_solve"][1] == "float32"
    atoms = 2
    assert abs(r["energy"]["total"] - f64["energy"]["total"]) <= 5e-6 * atoms


def test_setup_span_says_how_large_the_kset_program_is(rehearsal_deck,
                                                       eight_k_f32):
    (setup,) = [s for s in eight_k_f32["_spans"] if s["name"] == "scf.setup"]
    _, ctx = context(rehearsal_deck)
    nk, nb = 8, 8
    # one application to [X; P]: a complex64 coarse box a row, 2 nb rows a k
    assert setup["kset"] == {
        "nk": nk, "ngk_max": int(ctx.gkvec.ngk_max), "subspace_rows": 3 * nb,
        # PR 41: every point of the mesh its own -k, one weight
        "generic_kpoints": 0, "weights": [1],
        "workspace_bytes": nk * 2 * nb * int(np.prod(ctx.fft_coarse.dims)) * 8,
        # PR 33: the set's rows go through each box transform together
        "local_rows": [nk * nb, 2 * nk * nb], "local_layout": "rows_minor",
        # PR 44: one complex subspace, lowered here for the CPU (LAPACK's
        # call; on the chip the same deck reads "tridiagonal_real")
        "subspace_eigh": {"form": "library", "rows": 3 * nb, "batch": nk}}
    assert setup["kset"]["ngk_max"] % 16 == 0  # control.ngk_pad_quantum


def test_result_counts_the_kpoints_it_solved(eight_k_f32):
    c = eight_k_f32["counters"]
    assert c["num_kpoints_solved"] == 8
    iters = eight_k_f32["num_scf_iterations"]
    # PR 37: the counts are of what ran. The steps of every solve's loop
    # (one loop the set, on one device), under the bound of 20 a solve
    steps = c["num_davidson_steps"]
    assert iters <= steps < 20 * iters
    # rows a band of a k-point over the job, readable without knowing about
    # time reversal: one application a step and one on exit, two blocks at
    # each chunk boundary (a chunk every five steps of a solve, so between
    # steps / 5 and (steps + 4 iters) / 5 of them), and the LCAO block
    # (between one and two rows a band) once a job
    per = c["num_loc_op_applied"] / c["num_kpoints_solved"] / 8
    assert steps + iters + 2 * steps / 5 + 1 <= per
    assert per <= steps + iters + 2 * (steps + 4 * iters) / 5 + 2
    # PR 35: two eigenproblems a step and ortho's one, a solve a k-point
    assert c["num_subspace_eigh"] == 8 * (2 * steps + iters)


def test_time_reversal_leaves_36_of_the_444_mesh(one_device):
    """The benchmark's si2-k444-us: a 64-point mesh, 36 k-points solved."""
    r = run(deck(1, (4, 4, 4), 8, num_dft_iter=1), one_device)
    assert r["counters"]["num_kpoints_solved"] == 36
    (setup,) = [s for s in r["_spans"] if s["name"] == "scf.setup"]
    assert setup["kset"]["nk"] == 36
    # the complex subspace, lowered for the CPU: LAPACK's call (on a TPU the
    # same deck reads "tridiagonal_real", solvers/subspace_eigh.form)
    assert setup["kset"]["subspace_eigh"] == {
        "form": "library", "rows": 24, "batch": 36}


def test_gamma_job_has_no_kset_plan(one_device):
    r = run(deck(1, (1, 1, 1), 8, num_dft_iter=1), one_device)
    assert r["placement"]["path"] == "gamma"
    (setup,) = [s for s in r["_spans"] if s["name"] == "scf.setup"]
    assert "kset" not in setup
    assert r["counters"]["num_kpoints_solved"] == 1


def test_kset_plan_is_a_devices_share_on_a_mesh(rehearsal_deck):
    d = copy.deepcopy(rehearsal_deck)
    d["parameters"]["num_dft_iter"] = 1
    one = run(d, jax.devices()[:1])
    four = run(d, jax.devices()[:4])
    plan1 = [s for s in one["_spans"] if s["name"] == "scf.setup"][0]["kset"]
    plan4 = [s for s in four["_spans"] if s["name"] == "scf.setup"][0]["kset"]
    assert four["placement"]["mesh"]
    assert plan4["workspace_bytes"] * 4 == plan1["workspace_bytes"]
    assert plan4["nk"] == plan1["nk"] == 8
