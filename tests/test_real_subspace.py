"""The batched k-set solve with real subspace matrices (solvers/davidson.py,
REAL SUBSPACE; dft/band_solve.KsetSolver): where every k-point of the set is
time-reversal invariant the block is kept Theta-real, Theta x (G) =
conj(x(-G - 2k)), exactly, and the subspace eigenproblems are real symmetric.
On the TPU that is the one-kernel eigensolver in place of Jacobi sweep loops
(PERF.md section 6, PR 31); here it has to give the complex solve's bands and
energies."""

import copy

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sirius_tpu.config.schema import load_config
from sirius_tpu.dft import band_solve
from sirius_tpu.dft.scf import _initial_subspace, run_scf
from sirius_tpu.parallel.batched import (
    davidson_kset, initialize_subspace_kset, make_hkset_params, split_cplx,
)
from sirius_tpu.serve.scheduler import build_job_context

PARAMS = {
    "gk_cutoff": 3.0, "pw_cutoff": 7.0, "use_symmetry": False,
    "xc_functionals": ["XC_LDA_X", "XC_LDA_C_PZ"], "smearing_width": 0.025,
    "num_dft_iter": 60, "precision_wf": "fp64", "density_tol": 1e-8,
    "energy_tol": 1e-9, "num_bands": 8,
}


def deck(ngridk, **params):
    return {"parameters": dict(PARAMS, ngridk=list(ngridk), **params),
            "control": {"ngk_pad_quantum": 16, "verbosity": 0},
            "synthetic": {"ultrasoft": True}}


def context(d):
    cfg = load_config(copy.deepcopy(d))
    return cfg, build_job_context(cfg, ".")


@pytest.fixture(scope="module")
def ctx222():
    return context(deck((2, 2, 2)))[1]


@pytest.fixture(scope="module")
def tr222(ctx222):
    return band_solve.time_reversal_index(ctx222.gkvec)


def off_theta(x, tr):
    """|x - Theta x| over |x| of a block: 0 of a Theta-real one, O(1) of
    one with arbitrary phases."""
    return np.linalg.norm(x - band_solve._theta(x, tr)) / np.linalg.norm(x)


def test_index_is_the_slot_of_minus_g_minus_2k(ctx222, tr222):
    gk = ctx222.gkvec
    assert tr222.shape == (8, gk.ngk_max)
    for ik in range(8):
        n = int(np.sum(np.asarray(gk.mask[ik]) > 0))
        m = np.asarray(gk.millers[ik])
        two_k = np.rint(2 * np.asarray(gk.kpoints[ik])).astype(int)
        assert np.array_equal(m[tr222[ik, :n]], -m[:n] - two_k)
        assert np.array_equal(tr222[ik][tr222[ik]], np.arange(gk.ngk_max))
        assert np.array_equal(tr222[ik, n:], np.arange(n, gk.ngk_max))


@pytest.mark.parametrize("ngridk, admits", [
    ((1, 1, 1), True), ((2, 2, 2), True), ((1, 1, 2), True),
    ((4, 4, 4), False), ((3, 3, 3), False), ((2, 2, 4), False)])
def test_only_a_time_reversal_invariant_set_admits_it(ngridk, admits):
    _, ctx = context(deck(ngridk))
    assert (band_solve.time_reversal_index(ctx.gkvec) is not None) == admits


def _kset(ctx, mgga=False):
    return band_solve.KsetSolver(ctx, ctx.cfg, jax.devices()[1:2], None, None,
                                 None, mgga)


def test_mgga_and_gamma_alone_keep_the_complex_solve(ctx222):
    """What the span says is what runs: ``real_subspace`` is the solver's
    ``tr``, and with it the program gets the index. Gamma alone through
    KsetSolver (GammaSolver refused: several devices, the MD driver's deck)
    keeps the complex program it had, though the index exists for it."""
    plain = _kset(ctx222)
    assert plain.tr is not None and plain._theta_index() is not None
    assert plain.plan(jnp.complex64)["kset"]["real_subspace"] is True
    mgga = _kset(ctx222, mgga=True)
    assert mgga.tr is None and mgga._theta_index() is None
    assert mgga.plan(jnp.complex64)["kset"]["real_subspace"] is False
    _, ctx_gamma = context(deck((1, 1, 1)))
    assert band_solve.time_reversal_index(ctx_gamma.gkvec) is not None
    gamma = _kset(ctx_gamma)
    assert gamma.tr is None and gamma._theta_index() is None
    assert gamma.plan(jnp.complex64)["kset"]["real_subspace"] is False


def test_only_the_kset_solver_plans_span_fields(ctx222):
    serial = band_solve.SerialSolver(ctx222, ctx222.cfg, None)
    assert serial.plan(jnp.complex64) == {}
    assert not hasattr(serial, "tr")  # the independent witness stays complex
    for cls in (band_solve.GammaSolver, band_solve.GshardSolver,
                band_solve.ChunkedSolver):
        assert cls.plan(None, jnp.complex64) == {}


def test_lcao_block_is_made_theta_real_row_by_row(ctx222, tr222):
    x = _initial_subspace(ctx222)
    rng = np.random.default_rng(3)
    mask = np.asarray(ctx222.gkvec.mask)[:, None, None, :]
    noise = (rng.standard_normal(x.shape) + 1j * rng.standard_normal(x.shape))
    block = np.concatenate([x, 1j * x[:, :, :2], noise[:, :, :3] * mask], 2)
    assert off_theta(block, tr222) > 0.1
    y = band_solve.theta_real_block(block, tr222)
    assert off_theta(y, tr222) <= 1e-14
    nao = x.shape[2]
    # an atomic orbital is Theta-real already and stays what it was; i times
    # one is Theta-imaginary and comes back as the orbital (up to a sign)
    assert np.allclose(y[:, :, :nao], x, atol=1e-14)
    assert np.allclose(np.abs(y[:, :, nao:nao + 2]), np.abs(x[:, :, :2]),
                       atol=1e-14)
    # a random row keeps at least half its norm
    n2 = lambda a: np.sum(np.abs(a) ** 2, axis=-1)
    assert np.all(n2(y[:, :, nao + 2:]) >= 0.5 * n2(block[:, :, nao + 2:]) - 1e-12)


@pytest.fixture(scope="module")
def kset_problem(ctx222, tr222):
    rng = np.random.default_rng(0)
    veff = 0.1 * rng.standard_normal(tuple(ctx222.fft_coarse.dims))
    x = band_solve.theta_real_block(_initial_subspace(ctx222), tr222)
    return veff, x


@pytest.mark.parametrize("dtype, tol", [(jnp.complex128, 1e-7),
                                        (jnp.complex64, 2e-4)])
def test_real_subspace_solve_gives_the_complex_solves_bands(
        ctx222, tr222, kset_problem, dtype, tol):
    veff, x = kset_problem
    rdt = np.float64 if dtype == jnp.complex128 else np.float32
    ps = make_hkset_params(ctx222, veff, dtype=dtype)
    pr, pi = (jnp.asarray(a) for a in split_cplx(x, rdt))
    theta = jnp.asarray(tr222.astype(np.int32))
    out = {}
    for name, index in (("complex", None), ("real", theta)):
        a, b = initialize_subspace_kset(ps, pr, pi, 8, theta_index=index)
        ev, a, b, rn, _ = davidson_kset(
            ps, a, b, num_steps=40, res_tol=rdt(1e-12), theta_index=index,
            by_energy=False)
        out[name] = (np.asarray(ev), np.asarray(a) + 1j * np.asarray(b),
                     np.asarray(rn))
    ev_c, x_c, rn_c = out["complex"]
    ev_r, x_r, rn_r = out["real"]
    assert np.abs(ev_r - ev_c).max() <= tol
    # it converges as the complex one (the last band of a block is slow)
    assert rn_r.max() <= max(10.0 * rn_c.max(), 1e-4)
    # Theta-real to the last bit, in either precision; the complex solve's
    # vectors carry arbitrary phases
    assert np.array_equal(x_r, band_solve._theta(x_r, tr222))
    assert off_theta(x_c, tr222) > 0.1


def _eigh_dtypes(jaxpr, found):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name.startswith("eigh"):
            found.append(eqn.invars[0].aval.dtype)
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    _eigh_dtypes(inner, found)
    return found


def test_real_subspace_program_has_no_complex_eigh(ctx222, tr222,
                                                   kset_problem):
    veff, x = kset_problem
    ps = make_hkset_params(ctx222, veff, dtype=jnp.complex64)
    pr, pi = (jnp.asarray(a[:, :, :8]) for a in split_cplx(x, np.float32))
    tol = np.float32(1e-6)
    theta = jnp.asarray(tr222.astype(np.int32))
    real = _eigh_dtypes(jax.make_jaxpr(
        lambda *a: davidson_kset(*a, num_steps=5, theta_index=theta))(
            ps, pr, pi).jaxpr, [])
    cplx = _eigh_dtypes(jax.make_jaxpr(
        lambda *a: davidson_kset(*a, num_steps=5, res_tol=tol))(
            ps, pr, pi).jaxpr, [])
    assert real and set(real) == {np.dtype(np.float32)}
    # the complex program's eigh follows the backend it is lowered for
    # (solvers/subspace_eigh.py): at every site the library's complex call
    # and, for the TPU, a real one behind the tridiagonal reduction
    assert set(cplx) == {np.dtype(np.complex64), np.dtype(np.float32)}
    assert cplx.count(np.dtype(np.complex64)) == len(real)
    assert cplx.count(np.dtype(np.float32)) == len(real)


@pytest.fixture(scope="module")
def one_device():
    return jax.devices()[1:2]


def _run(d, devices, **kw):
    cfg, ctx = context(d)
    return run_scf(cfg, ctx=ctx, devices=devices, **kw), ctx


@pytest.mark.parametrize("itsol", [{}, {"converge_by_energy": 0}],
                         ids=["by-energy", "by-residual"])
def test_scf_with_real_subspace_meets_the_complex_one(one_device, itsol):
    # two programs held to one iteration count and 1e-9 Ha, under the
    # default rule of the band solve's exit (two to five steps a solve
    # here, 12 iterations) and under the residual rule (9 iterations)
    d = dict(deck((2, 2, 2)), iterative_solver=itsol)
    real, _ = _run(d, one_device)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(band_solve, "time_reversal_index", lambda gkvec: None)
        cplx, _ = _run(d, one_device)
    for r in (real, cplx):
        assert r["converged"] and r["placement"]["path"] == "batched+fused"
    assert abs(real["energy"]["total"] - cplx["energy"]["total"]) <= 1e-9
    assert real["num_scf_iterations"] == cplx["num_scf_iterations"]
    assert np.allclose(real["band_energies"], cplx["band_energies"],
                       atol=1e-6)


def test_four_devices_give_the_one_device_energy():
    d = deck((2, 2, 2))
    one, _ = _run(d, jax.devices()[:1])
    four, _ = _run(d, jax.devices()[:4])
    assert four["placement"]["mesh"]
    assert abs(four["energy"]["total"] - one["energy"]["total"]) <= 1e-9


def test_a_loaded_block_enters_the_real_subspace(ctx222, tr222, one_device):
    """One program a deck: whatever a resume file or a warm start holds is
    made Theta-real on the way in. A real-subspace run's block comes back
    bit for bit, so a resumed run repeats the uninterrupted one; rows with
    arbitrary phases come back as the Theta-real rows of their lines."""
    band = band_solve.choose(ctx222, ctx222.cfg, one_device,
                             serial_bands=False, hub=None, paw=None,
                             mgga=False, wf_dtype=jnp.complex128)
    assert isinstance(band, band_solve.KsetSolver)
    x = band_solve.theta_real_block(_initial_subspace(ctx222), tr222)
    band.load(x)
    assert np.array_equal(band.psi, x) and band._theta_index() is not None
    phases = np.exp(2j * np.pi * np.random.default_rng(1).random(x.shape[:3]))
    band.load(x * phases[..., None])
    assert np.array_equal(band.psi, band_solve._theta(band.psi, tr222))
    scale = np.sum(x.conj() * band.psi, -1) / np.sum(np.abs(x) ** 2, -1)
    assert np.allclose(scale.imag, 0.0, atol=1e-13)
    assert np.all(np.abs(scale) >= np.sqrt(0.5) - 1e-13)
    assert np.allclose(band.psi, scale[..., None] * x, atol=1e-13)
    band.restart(_initial_subspace(ctx222))
    assert band.psi is None and band._theta_index() is not None


def test_warm_start_from_a_real_subspace_run_stays_on_it(one_device):
    """The psi a real-subspace run hands on (keep_state, an autosave) is
    Theta-real to the last bit, so a run started from it takes the real
    subspace again and lands on the same energy."""
    d = deck((2, 2, 2), density_tol=1e-7, energy_tol=1e-8)
    cold, ctx = _run(d, one_device, keep_state=True)
    state = cold["_state"]
    tr = band_solve.time_reversal_index(ctx.gkvec)
    assert np.array_equal(state["psi"], band_solve._theta(state["psi"], tr))
    warm, _ = _run(d, one_device,
                   initial_guess=(state["rho_g"], state["psi"]))
    assert warm["converged"]
    assert warm["num_scf_iterations"] < cold["num_scf_iterations"]
    assert abs(warm["energy"]["total"] - cold["energy"]["total"]) <= 1e-8
