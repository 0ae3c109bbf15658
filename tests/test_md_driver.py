"""Born-Oppenheimer MD driver (sirius_tpu/md/driver.py) on the tiny
silicon deck: force consistency at step 0, NVE conservation with ASPC
iteration reduction and compile-once stepping, trajectory output, and
kill/resume equality via fault injection.

One short NVE trajectory is shared module-wide; the expensive properties
(conservation, extrapolation payoff, recompile count, trajectory file) are
separate assertions against the same run."""

import os

import numpy as np
import pytest

from sirius_tpu.testing import synthetic_silicon_context
from sirius_tpu.utils import faults

pytestmark = pytest.mark.faults

DECK = dict(
    gk_cutoff=3.0, pw_cutoff=7.0, ngridk=(1, 1, 1), num_bands=8,
    ultrasoft=True, use_symmetry=False,
    extra_params={"num_dft_iter": 40, "density_tol": 5e-9,
                  "energy_tol": 1e-10},
)


def _md_cfg(tmpdir, tag, itsol=None, **md):
    ctx = synthetic_silicon_context(**DECK)
    cfg = ctx.cfg
    for key, value in (itsol or {}).items():
        setattr(cfg.iterative_solver, key, value)
    cfg.md.dt_fs = 1.0
    cfg.md.temperature_k = 300.0
    cfg.md.seed = 11
    cfg.control.autosave_tag = tag
    for k, v in md.items():
        setattr(cfg.md, k, v)
    return cfg, ctx


@pytest.fixture(scope="module")
def nve_run(tmp_path_factory):
    from sirius_tpu.md.driver import run_md

    d = str(tmp_path_factory.mktemp("md_nve"))
    cfg, ctx = _md_cfg(
        d, "nve", ensemble="nve", num_steps=4,
        trajectory_path="traj.xyz", autosave_every=0,
    )
    res = run_md(cfg, base_dir=d, ctx=ctx)
    return d, res


def test_nve_energy_conservation(nve_run):
    """4 fs of NVE on the converged deck: the conserved energy stays
    within 1e-5 Ha of its initial value (force-consistency at the SCF
    tolerance; the 50-step acceptance run is the slow-tier twin)."""
    _, res = nve_run
    assert all(r["converged"] for r in res["records"])
    assert res["drift"]["max_abs"] < 1e-5
    # the system is actually moving (T(0) = 300 K)
    assert res["records"][-1]["temperature_k"] > 100.0


def test_aspc_reduces_scf_iterations(nve_run):
    """The ASPC-extrapolated warm start must cut the per-step SCF cost by
    >= 30% against the cold first evaluation (ISSUE acceptance bar)."""
    _, res = nve_run
    iters = res["scf_iterations"]
    cold = iters[0]
    warm = float(np.mean(iters[2:]))
    assert warm <= 0.7 * cold, (cold, iters)


def test_compile_once_stepping(nve_run):
    """Zero XLA backend compiles after the first step: every later step's
    context has identical shapes and hits the executable cache."""
    _, res = nve_run
    assert res["backend_compiles_after_first_step"] == 0
    per_step = [r["backend_compiles"] for r in res["records"][1:]]
    assert per_step == [0] * len(per_step)


def test_trajectory_extended_xyz(nve_run):
    """The trajectory file holds one parseable extended-XYZ frame per
    step plus the initial frame."""
    d, res = nve_run
    path = os.path.join(d, "traj.xyz")
    lines = open(path).read().splitlines()
    natoms = 2
    frame = natoms + 2
    assert len(lines) == frame * (res["num_steps"] + 1)
    assert lines[0].strip() == "2"
    assert "Lattice=" in lines[1] and "energy=" in lines[1]
    for ln in (2, 3):
        parts = lines[ln].split()
        assert parts[0] == "Si" and len(parts) == 10
        np.asarray(parts[1:], dtype=float)  # parses


def test_md_forces_match_finite_difference():
    """-dF/dR by central finite difference of the free energy at the MD
    step-0 geometry equals the analytic force the driver integrates
    (through the same context_at_positions plumbing). The FD sides
    warm-start from the converged step-0 state, so this costs one cold
    and two short SCF runs."""
    from sirius_tpu.dft.geometry import context_at_positions
    from sirius_tpu.dft.scf import run_scf

    ctx = synthetic_silicon_context(
        positions=np.array([[0.0, 0, 0], [0.21, 0.27, 0.23]]), **DECK
    )
    cfg = ctx.cfg
    cfg.control.print_forces = True
    res = run_scf(cfg, ctx=ctx, keep_state=True)
    assert res["converged"]
    f = np.asarray(res["forces"])
    state = res["_state"]
    lat = ctx.unit_cell.lattice
    base = ctx.unit_cell.positions
    h_cart = 2e-3
    dx_frac = np.linalg.solve(lat.T, np.array([h_cart, 0, 0]))
    e = {}
    for sgn in (+1, -1):
        pos = base + sgn * np.array([[0, 0, 0], dx_frac])
        c = context_at_positions(cfg, ".", pos, ctx.unit_cell)
        r = run_scf(
            cfg, ctx=c, initial_guess=(state["rho_g"], state["psi"])
        )
        assert r["converged"]
        e[sgn] = r["energy"]["free"]
    f_fd = -(e[+1] - e[-1]) / (2 * h_cart)
    np.testing.assert_allclose(f[1, 0], f_fd, atol=5e-5)


@pytest.mark.parametrize("itsol, vtol, etol", [
    (None, 1e-9, 1e-8),
    ({"converge_by_energy": 0, "residual_tolerance": 1e-10}, 1e-12, 1e-10),
], ids=["by-energy", "by-residual"])
def test_kill_resume_replays_trajectory(tmp_path, itsol, vtol, etol):
    """An MD run killed right after the step-2 checkpoint
    (utils/faults.py md.autosave_kill) and resumed from the /md group
    reproduces the uninterrupted trajectory on the host path: positions,
    velocities and the conserved quantity all match. NVT so the
    thermostat's counter-based noise replay is exercised too.

    A resumed step starts without the previous step's mixer history and
    band tolerance (the checkpoint holds densities and wave functions), so
    it meets the uninterrupted one as far as its SCF converges. Under the
    default rule of the band solve's exit (a step's move of the eigenvalue,
    PR 37) that is 5.7e-11 in the velocities and 3.8e-10 in the conserved
    quantity; with band solves converged by their residuals to 1e-10 from
    the first iteration on, 3.4e-13 and 4.1e-14: the 1e-12 and 1e-10 the
    static 20 steps were held to."""
    from sirius_tpu.md.driver import default_md_autosave_path, run_md

    d = str(tmp_path)
    md = dict(ensemble="nvt_csvr", thermostat_tau_fs=20.0, num_steps=3,
              autosave_every=1, itsol=itsol)
    cfg_ref, ctx_ref = _md_cfg(d, "ref", **md)
    ref = run_md(cfg_ref, base_dir=d, ctx=ctx_ref)

    cfg_a, ctx_a = _md_cfg(d, "kill", **md)
    faults.install([("md.autosave_kill", 2, "raise")])
    with pytest.raises(faults.SimulatedKill):
        run_md(cfg_a, base_dir=d, ctx=ctx_a)
    faults.clear()

    cfg_b, ctx_b = _md_cfg(d, "kill", **md)
    ckpt = default_md_autosave_path(cfg_b, d)
    assert os.path.exists(ckpt)
    res = run_md(cfg_b, base_dir=d, ctx=ctx_b, resume=ckpt)
    assert res["steps_run"] == 1
    np.testing.assert_allclose(
        res["positions_cart"], ref["positions_cart"], atol=1e-10
    )
    np.testing.assert_allclose(
        res["velocities"], ref["velocities"], atol=vtol
    )
    assert abs(
        res["records"][-1]["e_cons"] - ref["records"][-1]["e_cons"]
    ) < etol


def test_resume_rejects_non_md_checkpoint(tmp_path):
    from sirius_tpu.io.checkpoint import save_state
    from sirius_tpu.md.driver import run_md

    cfg, ctx = _md_cfg(str(tmp_path), "plain", num_steps=1)
    p = os.path.join(str(tmp_path), "scf_only.h5")
    save_state(p, ctx, rho_g=np.zeros(ctx.gvec.num_gvec, dtype=complex))
    with pytest.raises(ValueError, match="/md group"):
        run_md(cfg, base_dir=str(tmp_path), ctx=ctx, resume=p)


@pytest.mark.slow
def test_nve_50_step_acceptance(tmp_path):
    """The ISSUE acceptance trajectory: 50 NVE steps conserve energy to
    < 1e-4 Ha on the tiny deck (slow tier)."""
    from sirius_tpu.md.driver import run_md

    cfg, ctx = _md_cfg(str(tmp_path), "accept", ensemble="nve",
                       num_steps=50, autosave_every=0)
    res = run_md(cfg, base_dir=str(tmp_path), ctx=ctx)
    assert all(r["converged"] for r in res["records"])
    assert res["drift"]["max_abs"] < 1e-4
    assert res["backend_compiles_after_first_step"] == 0
