"""The batched k-set solve has ONE subspace (dft/band_solve.KsetSolver,
solvers/davidson.py): complex Hermitian matrices whatever the k-points are.
Until PR 44 a set whose every k-point is its own -k (the Gamma-centred 2x2x2
mesh: "the tri deck" below) ran a second program with real subspace
matrices; the ledger put the two level and the fork went. Held here: the one
program gives the serial per-k solve's bands, it gives that deck the energy
the parent's real program gave it, a block enters the solve as it is handed
over, and two sets of one shape share one executable whatever their k-points'
values."""

import copy
import functools
import inspect
import json
import os
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sirius_tpu.config.schema import load_config
from sirius_tpu.dft import band_solve
from sirius_tpu.dft.scf import _initial_subspace, run_scf
from sirius_tpu.obs import metrics as obs_metrics
from sirius_tpu.obs import spans
from sirius_tpu.parallel import batched
from sirius_tpu.parallel.batched import (
    davidson_kset, initialize_subspace_kset, make_hkset_params, split_cplx,
)
from sirius_tpu.serve.scheduler import build_job_context
from sirius_tpu.solvers import subspace_eigh
from sirius_tpu.solvers.davidson import davidson, stages, subspace_rotate

PARAMS = {
    "gk_cutoff": 3.0, "pw_cutoff": 7.0, "use_symmetry": False,
    "xc_functionals": ["XC_LDA_X", "XC_LDA_C_PZ"], "smearing_width": 0.025,
    "num_dft_iter": 60, "precision_wf": "fp64", "density_tol": 1e-8,
    "energy_tol": 1e-9, "num_bands": 8,
}
# the parent's real program on the tri deck, f64 on the CPU
with open(os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                       "tri_deck_parent.json")) as _f:
    PARENT = json.load(_f)


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
def one_device():
    return jax.devices()[1:2]


@pytest.mark.parametrize("ngridk, generic", [
    ((1, 1, 1), 0), ((2, 2, 2), 0), ((1, 1, 2), 0),
    ((4, 4, 4), 28), ((3, 3, 3), 13), ((2, 2, 4), 4)])
def test_generic_kpoints_of_a_mesh(ngridk, generic):
    """The ``kset.generic_kpoints`` field: the k-points solved that are not
    their own -k. Only meshes of ones and twos have none."""
    _, ctx = context(deck(ngridk))
    found = band_solve.generic_kpoints(ctx.gkvec.kpoints)
    assert found.shape == (ctx.gkvec.num_kpoints,)
    assert int(found.sum()) == generic


def _kset(ctx, hub=None, mgga=False):
    return band_solve.KsetSolver(ctx, ctx.cfg, jax.devices()[1:2], None, None,
                                 hub, mgga)


def _chosen(ctx, devices, wf_dtype=jnp.complex128):
    """The solver run_scf would pick for the deck: the k-set one."""
    band = band_solve.choose(ctx, ctx.cfg, devices, serial_bands=False,
                             hub=None, paw=None, mgga=False, wf_dtype=wf_dtype)
    assert isinstance(band, band_solve.KsetSolver)
    return band


def _zero_potential_inputs(ctx):
    """A first solve's Inputs: the host path's, V_eff = 0 and the bare D."""
    veff = np.zeros((1,) + tuple(ctx.fft_coarse.dims))
    return band_solve.Inputs(
        pot=SimpleNamespace(veff_r_coarse=veff, vtau_r_coarse=None),
        d_by_spin=[np.asarray(ctx.beta.dion)])


def test_every_kset_solver_is_on_the_complex_subspace(ctx222):
    """Plain, Hubbard, mGGA, Gamma alone through KsetSolver (GammaSolver
    refused: several devices, the MD driver's deck) and a set with generic
    points: one rule, complex Hermitian matrices, and the span's
    ``subspace_eigh.form`` follows from the dtype and the platform alone."""
    nk, ngk = ctx222.gkvec.num_kpoints, int(ctx222.gkvec.ngk_max)
    hub = SimpleNamespace(phi_s_gk=[np.zeros((2, ngk), complex)] * nk)
    solvers = [_kset(ctx222), _kset(ctx222, hub=hub), _kset(ctx222, mgga=True),
               _kset(context(deck((1, 1, 1)))[1]),
               _kset(context(deck((2, 2, 3)))[1])]
    for band in solvers:
        assert band.complex_subspace is True
        for platform, wf_dtype, form in (
                ("cpu", jnp.complex64, "library"),
                ("cpu", jnp.complex128, "library"),
                ("tpu", jnp.complex64, "tridiagonal_real")):
            band.dev = SimpleNamespace(platform=platform)
            kset = band.plan(wf_dtype)["kset"]
            assert "real_subspace" not in kset
            assert kset["subspace_eigh"]["form"] == form
            assert form == subspace_eigh.form(wf_dtype, platform)


def test_only_the_kset_solver_plans_span_fields(ctx222):
    serial = band_solve.SerialSolver(ctx222, ctx222.cfg, None)
    assert serial.plan(jnp.complex64) == {}
    assert serial.complex_subspace is True
    for cls in (band_solve.GammaSolver, band_solve.GshardSolver,
                band_solve.ChunkedSolver):
        assert cls.plan(None, jnp.complex64) == {}


class _Handed(Exception):
    """Raised by a spy in place of a device program, with its operands."""


def _first_program_operands(band, ctx, entry):
    """What band.solve hands ``entry`` of parallel/batched.py in its first
    solve: the (re, im) pair of the block, as host arrays."""
    def spy(ps, re, im, *a, **kw):
        raise _Handed(np.asarray(re), np.asarray(im))

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(batched, entry, spy)
        with pytest.raises(_Handed) as handed:
            band.solve(_zero_potential_inputs(ctx), 1e-6, jnp.complex128)
    return handed.value.args


def _arbitrary_block(ctx):
    """Atomic orbitals, i times two of them and a random tail: rows of
    every phase (what the parent's solver replaced by Theta-real ones)."""
    x = _initial_subspace(ctx)
    rng = np.random.default_rng(3)
    mask = np.asarray(ctx.gkvec.mask)[:, None, None, :]
    noise = rng.standard_normal(x.shape) + 1j * rng.standard_normal(x.shape)
    return np.concatenate([x, 1j * x[:, :, :2], noise[:, :, :3] * mask], 2)


def test_lcao_block_reaches_the_first_solve_as_handed_over(ctx222,
                                                           one_device):
    band = _chosen(ctx222, one_device)
    block = _arbitrary_block(ctx222)
    band.restart(block)
    re, im = _first_program_operands(band, ctx222, "initialize_subspace_kset")
    assert np.array_equal(re, block.real) and np.array_equal(im, block.imag)


def test_a_loaded_block_reaches_the_first_solve_as_handed_over(ctx222,
                                                               one_device):
    """Whatever a resume file or a warm start holds enters the solve bit
    for bit, so a resumed run repeats the uninterrupted one."""
    band = _chosen(ctx222, one_device)
    x = _initial_subspace(ctx222)[:, :, :8]
    phases = np.exp(2j * np.pi * np.random.default_rng(1).random(x.shape[:3]))
    block = x * phases[..., None]
    band.load(block)
    assert band.psi is block and band.psi_big is None
    re, im = _first_program_operands(band, ctx222, "davidson_kset")
    assert np.array_equal(re, block.real) and np.array_equal(im, block.imag)
    band.restart(_initial_subspace(ctx222))
    assert band.psi is None and band.psi_big is not None


@pytest.mark.parametrize("dtype, tol", [(jnp.complex128, 1e-7),
                                        (jnp.complex64, 2e-4)])
def test_kset_solve_gives_the_serial_solves_bands(ctx222, dtype, tol):
    """The set's one program against the per-k solve of the same H from the
    same start (the serial path's operator, ops/hamiltonian.apply_h_s on one
    k-point's FFT box, and its own diagonals)."""
    from sirius_tpu.ops.hamiltonian import apply_h_s, make_hk_params

    ctx = ctx222
    rdt = np.float64 if dtype == jnp.complex128 else np.float32
    veff = 0.1 * np.random.default_rng(0).standard_normal(
        tuple(ctx.fft_coarse.dims))
    ps = make_hkset_params(ctx, veff, dtype=dtype)
    pr, pi = (jnp.asarray(a) for a in split_cplx(_initial_subspace(ctx), rdt))
    a, b = initialize_subspace_kset(ps, pr, pi, 8)
    rule = dict(num_steps=60, res_tol=rdt(1e-12), by_energy=False)
    ev, _, _, rn, _ = davidson_kset(ps, a, b, **rule)
    x0 = np.asarray(a) + 1j * np.asarray(b)
    for ik in range(ctx.gkvec.num_kpoints):
        prm = make_hk_params(ctx, ik, veff, None, dtype=dtype)
        h_diag, o_diag = band_solve._h_o_diag(ctx, ik, 0.0, ctx.beta.dion)
        ev_k, _, rn_k, _ = davidson(
            apply_h_s, prm, jnp.asarray(x0[ik, 0], dtype),
            jnp.asarray(h_diag, rdt), jnp.asarray(o_diag, rdt), prm.mask,
            **rule)
        assert np.abs(np.asarray(ev[ik, 0]) - np.asarray(ev_k)).max() <= tol
        # it converges as the serial one (the last band of a block is slow)
        assert float(rn[ik, 0].max()) <= max(10.0 * float(rn_k.max()), 1e-4)


@functools.lru_cache(maxsize=None)
def _tri_job(rule):
    """One run of the tri deck on one device under an exit rule of the band
    solve, with its scf.setup span."""
    itsol = {} if rule == "by-energy" else {"converge_by_energy": 0}
    cfg, ctx = context(dict(deck((2, 2, 2)), iterative_solver=itsol))
    with spans.capture() as cap:
        r = run_scf(cfg, ctx=ctx, devices=jax.devices()[1:2])
    (r["_setup"],) = [s for s in cap.records if s["name"] == "scf.setup"]
    return r


@pytest.mark.parametrize("rule", ["by-energy", "by-residual"])
def test_tri_deck_keeps_the_parents_energy(rule):
    """The one program held to what the parent's real program gave this
    deck (tests/data/tri_deck_parent.json: commit aab082e, f64, the CPU):
    the comparison PR 31's test made between the two programs, the default
    rule of the band solve's exit (12 iterations) and the residual rule
    (9)."""
    r, parent = _tri_job(rule), PARENT[rule]
    assert PARENT["commit"].startswith("aab082e")
    assert r["converged"] and r["placement"]["path"] == "batched+fused"
    assert abs(r["energy"]["total"] - parent["energy_total_ha"]) <= 1e-9
    assert r["num_scf_iterations"] == parent["num_scf_iterations"]
    assert np.allclose(r["band_energies"], parent["band_energies"], atol=1e-6)


def test_tri_deck_books_the_complex_program():
    r = _tri_job("by-energy")
    c = r["counters"]
    assert c["num_complex_subspace_eigh"] == c["num_subspace_eigh"] > 0
    kset = r["_setup"]["kset"]
    assert kset["generic_kpoints"] == 0 and "real_subspace" not in kset
    assert kset["subspace_eigh"]["form"] == "library"  # the CPU's


def test_tri_and_generic_sets_of_one_shape_share_one_program(one_device):
    """Which program a deck gets follows from its shapes, not from its
    k-points' values: after a set of generic points has been solved, a set
    of zone-boundary points of the same length and padded ``ngk`` compiles
    nothing, set-up of the solve included."""
    shifted = [[0.1, 0, 0], [0.4, 0.05, 0], [0, 0.45, 0.1], [0.45, 0.4, 0.45]]
    boundary = [[0, 0, 0], [0.5, 0, 0], [0, 0.5, 0], [0.5, 0.5, 0.5]]
    obs_metrics.install_jax_listeners()

    def solve(vk):
        _, ctx = context(deck((1, 1, 1), vk=vk))
        band = _chosen(ctx, one_device, jnp.complex64)
        band.restart(_initial_subspace(ctx))
        inputs = _zero_potential_inputs(ctx)
        before = obs_metrics.backend_compiles_this_thread()
        out = band.solve(inputs, 1e-4, jnp.complex64)
        jax.block_until_ready(out.pr)
        compiled = obs_metrics.backend_compiles_this_thread() - before
        generic = band_solve.generic_kpoints(ctx.gkvec.kpoints)
        return compiled, generic, ctx.gkvec.mask.shape

    first, generic, shape = solve(shifted)
    assert first > 0 and generic.all()
    second, generic, shape2 = solve(boundary)
    assert not generic.any() and shape2 == shape
    assert second == 0


def test_kset_entry_points_take_no_theta_index():
    for fn in (davidson, davidson_kset, initialize_subspace_kset, stages,
               subspace_rotate):
        names = set(inspect.signature(fn).parameters)
        assert not {n for n in names if "theta" in n}, (fn, names)
    assert list(inspect.signature(davidson_kset).parameters) == [
        "params", "psi_re", "psi_im", "num_steps", "res_tol", "mesh",
        "by_energy"]


def _run(d, devices, **kw):
    cfg, ctx = context(d)
    return run_scf(cfg, ctx=ctx, devices=devices, **kw)


def test_four_devices_give_the_one_device_energy():
    d = deck((2, 2, 2))
    one = _run(d, jax.devices()[:1])
    four = _run(d, jax.devices()[:4])
    assert four["placement"]["mesh"]
    assert abs(four["energy"]["total"] - one["energy"]["total"]) <= 1e-9


def test_warm_start_from_a_kset_run_lands_on_its_energy(one_device):
    """The psi a run hands on (keep_state, an autosave) starts the next
    one, which takes fewer iterations to the same energy."""
    d = deck((2, 2, 2), density_tol=1e-7, energy_tol=1e-8)
    cold = _run(d, one_device, keep_state=True)
    state = cold["_state"]
    warm = _run(d, one_device, initial_guess=(state["rho_g"], state["psi"]))
    assert warm["converged"]
    assert warm["num_scf_iterations"] < cold["num_scf_iterations"]
    assert abs(warm["energy"]["total"] - cold["energy"]["total"]) <= 1e-8
