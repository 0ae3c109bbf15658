"""A k-set with generic k-points (the benchmark's si16-k223-us): on the
Gamma-centred [2, 2, 3] mesh the four points with k_z = 0 are their own -k
and the eight with k_z = +-1/3 pair under time reversal into four generic
ones, so 8 k-points are solved with weights 1/12 and 2/12 on the k-set
solve's complex subspace (solvers/subspace_eigh.py's reduction on a TPU). The
twin deck, si16-k222-us on [2, 2, 2], has the same count of k-points, all
invariant, and since PR 44 runs the same program (until then a second one
with real subspace matrices; tests/test_kset_solver.py).

Held here at the rehearsal's size (the 2-atom cell, gk 3 / pw 7, 8 bands):
what the context and the set-up span say, the fold on an anisotropic mesh
that benchmark/make_refs_folded_kmesh.py rests on ([1, 1, 3] of the supercell
against [2, 2, 6] of the cell: k-points, weights and spheres point by point
in tier-1, the two f64 energies as a slow test, since that 16-atom complex128
job takes 46 s alone and a quarter of an hour beside five other workers), the
f32 fused path against the stored plain reference
under both exit rules of the band solve, and the reduction at the cell's
size, [8, 192, 192] complex64, on matrices with parked directions."""

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
from sirius_tpu.solvers import subspace_eigh

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIGS = os.path.join(ROOT, "benchmark", "configs")


def rehearsal(config_name):
    """(deck, stored plain energy) of a configuration's rehearsal block."""
    with open(os.path.join(CONFIGS, config_name, "config.json")) as f:
        config = json.load(f)
    with open(os.path.join(CONFIGS, config_name, "refs_rehearse.json")) as f:
        ref = json.load(f)["geometries"]["0"]["energy_total_ha"]
    deck = {k: copy.deepcopy(v) for k, v in config["rehearse"].items()
            if k != "geometry"}
    return deck, ref


def context(deck):
    cfg = load_config(copy.deepcopy(deck))
    return cfg, build_job_context(cfg, ".")


def run(deck, devices):
    cfg, ctx = context(deck)
    with spans.capture() as cap:
        r = run_scf(cfg, ctx=ctx, devices=devices)
    (r["_setup"],) = [s for s in cap.records if s["name"] == "scf.setup"]
    return r


@pytest.fixture(scope="module")
def one_device():
    return jax.devices()[1:2]  # a compute device that is not the host's


@pytest.fixture(scope="module")
def k223():
    return rehearsal("si16-k223-us")


@pytest.fixture(scope="module")
def k222():
    return rehearsal("si16-k222-us")


# --- (a) what the context holds and what the program is told ---------------

def test_the_223_mesh_is_eight_kpoints_four_of_them_generic(k223, k222):
    _, ctx = context(k223[0])
    assert ctx.gkvec.num_kpoints == 8  # of the mesh's 12
    w = np.asarray(ctx.kweights) * 12
    assert sorted(np.rint(w).astype(int)) == [1, 1, 1, 1, 2, 2, 2, 2]
    assert np.abs(w - np.rint(w)).max() < 1e-12 and abs(w.sum() - 12) < 1e-12
    generic = band_solve.generic_kpoints(ctx.gkvec.kpoints)
    assert generic.sum() == 4
    # the generic points are the pairs (k, -k) folded to one: weight 2/12;
    # the invariant ones (k_z = 0) stand for themselves
    assert np.array_equal(np.rint(w).astype(int) == 2, generic)
    assert np.all(np.abs(np.asarray(ctx.gkvec.kpoints)[~generic, 2]) < 1e-12)
    # the twin: as many k-points, one weight, none generic
    _, twin = context(k222[0])
    assert twin.gkvec.num_kpoints == 8
    assert not band_solve.generic_kpoints(twin.gkvec.kpoints).any()
    assert np.allclose(np.asarray(twin.kweights), 1.0 / 8)


def test_generic_points_or_none_the_solver_is_the_complex_one(k223, k222,
                                                              one_device):
    for (deck, _), generic in ((k223, 4), (k222, 0)):
        cfg, ctx = context(deck)
        band = band_solve.choose(ctx, cfg, one_device, serial_bands=False,
                                 hub=None, paw=None, mgga=False,
                                 wf_dtype=jnp.complex64)
        assert isinstance(band, band_solve.KsetSolver)
        assert band.complex_subspace is True
        kset = band.plan(jnp.complex64)["kset"]
        assert kset["generic_kpoints"] == generic
        assert kset["subspace_eigh"]["form"] == subspace_eigh.form(
            jnp.complex64, one_device[0].platform)


# --- (c) the f32 fused path against the stored plain reference -------------

@pytest.fixture(scope="module", params=[{}, {"converge_by_energy": 0}],
                ids=["by-energy", "by-residual"])
def job223(request, k223, one_device):
    deck = dict(k223[0], iterative_solver=request.param)
    return run(deck, one_device)


def test_f32_job_meets_the_plain_reference_within_the_cells_bar(job223, k223):
    r = job223
    assert r["converged"] and r["placement"]["path"] == "batched+fused"
    assert r["placement"]["band_solve"][1] == "float32"
    # the cell's bar: 5e-6 Ha an atom, 2 atoms here; the reference is
    # benchmark/plain_pwus.py's energy on all 12 points of the mesh
    assert abs(r["energy"]["total"] - k223[1]) <= 5e-6 * 2


def test_every_eigenproblem_of_the_job_is_a_complex_one(job223):
    c = job223["counters"]
    assert c["num_kpoints_solved"] == 8
    steps, iters = c["num_davidson_steps"], job223["num_scf_iterations"]
    assert c["num_subspace_eigh"] == 8 * (2 * steps + iters)
    assert c["num_complex_subspace_eigh"] == c["num_subspace_eigh"]


def test_setup_span_says_which_kset_this_is(job223, k223):
    kset = job223["_setup"]["kset"]
    nb = k223[0]["parameters"]["num_bands"]
    assert kset["nk"] == 8 and "real_subspace" not in kset
    assert kset["generic_kpoints"] == 4 and kset["weights"] == [1, 2]
    # the complex subspace lowered for this platform, the CPU: LAPACK's call
    # (on the chip the same deck reads "tridiagonal_real")
    platform = jax.devices()[0].platform
    assert kset["subspace_eigh"] == {
        "form": subspace_eigh.form(jnp.complex64, platform),
        "rows": 3 * nb, "batch": 8}
    assert subspace_eigh.form(jnp.complex64, "tpu") == "tridiagonal_real"


def test_the_twin_on_222_books_the_same_complex_program(k223, k222,
                                                        one_device):
    first = copy.deepcopy(k223[0])
    first["parameters"]["num_dft_iter"] = 1
    run(first, one_device)
    deck = copy.deepcopy(k222[0])
    deck["parameters"]["num_dft_iter"] = 2  # the counters are booked at the end
    r = run(deck, one_device)
    c = r["counters"]
    # same density sphere, boxes, electron count and k-point count: the
    # twin's fused step is this deck's StepConstants record and input
    # shapes, so a process that has run one runs the other on its program
    assert r["_setup"]["fused_step"] == "reused"
    assert c["num_fused_step_traces"] == 0
    assert c["num_complex_subspace_eigh"] == c["num_subspace_eigh"] > 0
    kset = r["_setup"]["kset"]
    assert "real_subspace" not in kset and kset["generic_kpoints"] == 0
    assert kset["weights"] == [1]


# --- (b) the fold on an anisotropic mesh -----------------------------------

def fold_decks(k223):
    """(supercell 2 on [1, 1, 3] with 64 bands, the cell on [2, 2, 6] with
    8), at the rehearsal's cutoffs."""
    big, small = copy.deepcopy(k223[0]), copy.deepcopy(k223[0])
    big["parameters"].update(ngridk=[1, 1, 3], num_bands=64)
    big["synthetic"]["supercell"] = 2
    small["parameters"].update(ngridk=[2, 2, 6], num_bands=8)
    return big, small


def test_the_fold_of_113_onto_226_point_by_point(k223):
    """What make_refs_folded_kmesh.py rests on, component by component: a
    k-point K of the supercell n on [m1, m2, m3] and its n^3 images
    (K + j) / n are points of the cell's mesh [n m1, n m2, n m3], each once
    (up to the -k the program pairs it with), the weights add up to the
    mesh's, and the sphere |G + K| < gk_cutoff of the supercell is the
    union of the images' spheres."""
    n = 2
    (_, big), (_, small) = (context(d) for d in fold_decks(k223))
    kb, ks = np.asarray(big.gkvec.kpoints), np.asarray(small.gkvec.kpoints)
    assert len(kb) == 2 and len(ks) == 16  # of 3 and 24 mesh points
    shifts = np.array([[i, j, k] for i in range(n) for j in range(n)
                       for k in range(n)], dtype=np.float64)

    def lengths(ctx, ik):
        m = np.asarray(ctx.gkvec.mask[ik]) > 0
        return np.sum(np.asarray(ctx.gkvec.gkcart[ik])[m] ** 2, axis=-1)

    def same(a, b):  # equal up to a reciprocal lattice vector
        return np.abs(a - b - np.rint(a - b)).max() < 1e-9

    weight = np.zeros(len(ks))
    for ik, k_big in enumerate(kb):
        hits = []
        for image in (k_big + shifts) / n:
            (hit,) = [i for i, k in enumerate(ks)
                      if same(image, k) or same(image, -k)]
            hits.append(hit)
            weight[hit] += big.kweights[ik] / n ** 3
        assert len(set(hits)) == n ** 3  # each point of the fine mesh once
        folded = np.sort(np.concatenate([lengths(small, i) for i in hits]))
        mine = np.sort(lengths(big, ik))
        assert mine.shape == folded.shape
        assert np.abs(mine - folded).max() < 1e-9
    assert np.abs(weight - np.asarray(small.kweights)).max() < 1e-12


@pytest.mark.slow  # 46 s alone, 830 s beside five other workers' tests
def test_supercell_on_113_is_the_cell_on_226(k223, one_device):
    """The same fold in energies: E(supercell) = n^3 E(cell), f64."""
    base = copy.deepcopy(k223[0])
    base["parameters"].update(precision_wf="fp64", density_tol=1e-8,
                              energy_tol=1e-9)
    big, small = fold_decks((base, None))
    rb, rs = run(big, one_device), run(small, one_device)
    for r in (rb, rs):
        assert r["converged"] and r["placement"]["path"] == "batched+fused"
    # Gamma (weight 1/3) and (0, 0, 1/3) (weight 2/3): one generic point
    assert rb["counters"]["num_kpoints_solved"] == 2
    assert rb["_setup"]["kset"]["generic_kpoints"] == 1
    assert rb["_setup"]["kset"]["weights"] == [1, 2]
    assert abs(rb["energy"]["total"] - 8 * rs["energy"]["total"]) <= 1e-8


# --- (d) the reduction at the cell's size ----------------------------------

def parked_matrices(seed, batch=8, nb=64):
    """[batch, 3 nb, 3 nb] complex128 of the kind _rayleigh_ritz hands over
    on this deck: a kept block (a random Hermitian matrix with a silicon
    subspace's spread of a few Ha), and beside it the projected-out
    directions, zero rows and columns with 1 + |kept|_inf on the diagonal
    (the zero P block of a chunk's first step is nb of them; later steps
    park a few)."""
    rng = np.random.default_rng(seed)
    n = 3 * nb
    out = np.zeros((batch, n, n), dtype=np.complex128)
    for i in range(batch):
        kept = n - (nb if i % 2 == 0 else int(rng.integers(1, 9)))
        a = rng.standard_normal((kept, kept)) + 1j * rng.standard_normal((kept, kept))
        q, _ = np.linalg.qr(a)
        e = np.sort(rng.uniform(-0.3, 6.0, kept))
        blk = (q * e) @ q.conj().T
        blk = 0.5 * (blk + blk.conj().T)
        out[i, :kept, :kept] = blk
        shift = 1.0 + np.abs(blk).sum(axis=1).max()
        idx = np.arange(kept, n)
        out[i, idx, idx] = shift
        p = rng.permutation(n)  # parked directions anywhere in the matrix
        out[i] = out[i][np.ix_(p, p)]
    return out


@pytest.mark.parametrize("seed", [41, 2231, 2**31 + 7])
def test_reduction_at_8x192_with_parked_directions(seed):
    a = parked_matrices(seed).astype(np.complex64)
    e, v = jax.jit(subspace_eigh.eigh_tridiagonal_real)(jnp.asarray(a))
    assert e.shape == (8, 192) and v.shape == (8, 192, 192)
    e, v = np.asarray(e, np.float64), np.asarray(v, np.complex128)
    a64 = a.astype(np.complex128)
    norm = np.linalg.norm(a64, 2, axis=(-2, -1))[:, None]
    eps = float(np.finfo(np.float32).eps)
    vh = np.swapaxes(v.conj(), -1, -2)
    # backward error |A - V E V^H|_max / |A|_2. 190 reflectors, each unitary
    # to a few eps and applied as a rank-two update, leave errors that add
    # like a random walk, sqrt(190) ~ 14 of them, not 190: 20 eps =
    # 2.4e-6 |A| holds with a margin of two over what seeded matrices read
    # (about 1e-6, the figure PR 35 measured on the chip at 78 rows). The
    # energy's bar is what it protects: 2.4e-6 x a shift of some hundred Ha
    # would be 5e-4 Ha if it landed on the wanted Ritz values whole; it does
    # not, because the error of the eigenvalue is the next bound
    back = np.abs(a64 - (v * e[:, None, :]) @ vh).max(axis=(-2, -1))
    assert (back / norm[:, 0]).max() <= 20 * eps
    # |V^H V - 1|_max: Q is a product of the same 190 reflectors and Y is
    # the library's; 80 eps is the bound tests/test_subspace_eigh.py holds
    # the random 8 x 192 case to
    assert np.abs(vh @ v - np.eye(192)).max() <= 80 * eps
    # eigenvalues against numpy's f64 eigh of the same numbers, relative to
    # |A|_2 (the parked shift sets it): Weyl's bound is the backward error
    # in the 2-norm; 20 eps as above, which the input's own rounding to
    # complex64 does not enter (both sides get the rounded matrix)
    assert (np.abs(e - np.linalg.eigvalsh(a64)) / norm).max() <= 20 * eps
    assert np.all(np.diff(e, axis=-1) >= 0)
