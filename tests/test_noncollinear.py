"""Non-collinear magnetism: physics invariants.

1. Collinear consistency — a system with all moments along z solved through
   the 2x2 spinor machinery must reproduce the collinear (diagonal) SCF
   total energy: the spin-block Hamiltonian is block-diagonal then.
2. Rotational invariance — rotating every initial moment rigidly (z -> x)
   must leave the total energy unchanged (the energy functional depends
   only on |m| and relative orientations).
Reference behavior: hamiltonian/local_operator.cpp:380-460,
density.cpp:636-700, potential/xc.cpp:229-404.
"""

import numpy as np
import pytest

from sirius_tpu.dft.scf import run_scf
from sirius_tpu.testing import synthetic_silicon_context


def _run(mag_dims, moments, nb=10, converge_by_energy=1, **extra):
    params = {
        "num_mag_dims": mag_dims,
        "smearing_width": 0.01,
        "density_tol": 1e-7,
        "energy_tol": 1e-8,
        "num_dft_iter": 60,
    }
    params.update(extra)
    ctx = synthetic_silicon_context(
        gk_cutoff=3.5, pw_cutoff=9.0, ngridk=(1, 1, 1), num_bands=nb,
        ultrasoft=True, use_symmetry=False, extra_params=params,
        moments=np.asarray(moments, float),
    )
    ctx.cfg.iterative_solver.converge_by_energy = converge_by_energy
    return run_scf(ctx.cfg, ctx=ctx)


@pytest.mark.parametrize("converge_by_energy, mtol", [(1, 1e-4), (0, 1e-6)],
                         ids=["by-energy", "by-residual"])
def test_nc_matches_collinear_for_z_moments(converge_by_energy, mtol):
    mom_z = [[0, 0, 0.5], [0, 0, 0.5]]
    # a transverse moment is first order in the bands' error, an eigenvalue
    # second order: under the default rule of the band solve's exit (a
    # step's move of the eigenvalue, PR 37) the spinor run leaves 2.5e-5 at
    # density_tol 1e-7 where symmetry says 0, with band solves converged by
    # their residuals 2.0e-10 (the 1e-6 the static 20 steps were held to)
    r_col = _run(1, mom_z, nb=8, converge_by_energy=converge_by_energy)
    r_nc = _run(3, mom_z, nb=16, converge_by_energy=converge_by_energy)
    assert r_col["converged"] and r_nc["converged"]
    assert abs(r_nc["energy"]["total"] - r_col["energy"]["total"]) < 2e-6
    # z-moments agree; transverse components vanish
    mz_col = r_col["magnetisation"]["total"][2]
    m_nc = r_nc["magnetisation"]["total"]
    assert abs(m_nc[2] - mz_col) < 1e-4
    assert abs(m_nc[0]) < mtol and abs(m_nc[1]) < mtol


def test_nc_energy_invariant_under_moment_rotation():
    mom_z = [[0, 0, 0.5], [0, 0, 0.5]]
    mom_x = [[0.5, 0, 0], [0.5, 0, 0]]
    r_z = _run(3, mom_z, nb=16)
    r_x = _run(3, mom_x, nb=16)
    assert r_z["converged"] and r_x["converged"]
    assert abs(r_z["energy"]["total"] - r_x["energy"]["total"]) < 2e-6
    # the moment direction follows the seed
    assert abs(r_x["magnetisation"]["total"][0] - r_z["magnetisation"]["total"][2]) < 1e-4
