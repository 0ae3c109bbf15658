"""Checksum tracing (SURVEY §5): the env-gated per-stage checksums must
agree between the single-device (serial_bands) and mesh-sharded SCF paths
— the cheap cross-mesh nondeterminism tripwire the reference ships as
env::print_checksum()."""

import numpy as np
import pytest

from sirius_tpu.testing import synthetic_silicon_context
from sirius_tpu.utils import checksums


@pytest.fixture(autouse=True)
def _enable_checksums(monkeypatch):
    monkeypatch.setenv("SIRIUS_TPU_PRINT_CHECKSUM", "1")
    checksums.reset()
    yield
    checksums.reset()


def _run(serial: bool, niter: int = 2, ngridk=(2, 2, 2), devices=None,
         **itsol):
    from sirius_tpu.dft.scf import run_scf

    ctx = synthetic_silicon_context(
        gk_cutoff=3.0, pw_cutoff=7.0, ngridk=ngridk, num_bands=8,
        ultrasoft=True, use_symmetry=False,
        extra_params={"num_dft_iter": niter},
    )
    for key, value in itsol.items():
        setattr(ctx.cfg.iterative_solver, key, value)
    checksums.reset()
    run_scf(ctx.cfg, ctx=ctx, serial_bands=serial, devices=devices)
    return {k: list(v) for k, v in checksums.records().items()}


# The band solve's exit (PR 37) under the two rules the comparisons below
# run: the deck's default (a step's move of the eigenvalue under 1e-2 and
# falling, a few steps a solve) and bands converged by their residual norms
# to 1e-10 in at most 60 steps. A k-point whose bands have converged is held
# to the bit (solvers/davidson.py, HOLD), so a device that solves eight
# k-points in one loop and eight devices that each leave on their own give
# the same bands.
RULES = pytest.mark.parametrize("itsol", [
    {}, dict(num_steps=60, converge_by_energy=0, residual_tolerance=1e-10),
], ids=["by-energy", "by-residual"])


def test_checksums_recorded_per_stage():
    rec = _run(serial=True)
    for tag in ("rho_new", "veff", "evals"):
        assert tag in rec, f"missing checksum stage {tag}"
        assert len(rec[tag]) == 2  # one per SCF iteration


def _agree(a, b, stages, what):
    assert set(a) == set(b) and set(stages) <= set(a)
    for tag in stages:
        assert len(a[tag]) == len(b[tag])
        for x, y in zip(a[tag], b[tag]):
            np.testing.assert_allclose(
                complex(x), complex(y), rtol=1e-8, atol=1e-8,
                err_msg=f"stage {tag} diverges between {what}",
            )


@pytest.mark.parametrize("ngridk, stages", [
    ((2, 2, 3), ("evals", "rho_new", "veff")),
    ((2, 2, 2), ("evals", "rho_new", "veff"))])
@RULES
def test_single_vs_mesh_checksums_agree(ngridk, stages, itsol):
    """Sharded (8 virtual devices via conftest) vs serial paths: the same
    physics to near-machine precision, caught stage by stage. The tripwire
    presumes one algorithm on both sides, which a k-set with a generic
    point has (mesh [2,2,3]: 8 k-points solved, 4 generic) and, the k-set
    solve having one subspace, a k-set without one too (mesh [2,2,2]: every
    k-point its own -k). Converged agreement of the k-set solve with the
    serial one is tests/test_kset_solver.py's.

    Since PR 33 the two sides also apply the local operator in two forms,
    the serial side the FFT of one block and the k-set the set's rows
    through DFT products (ops/local.py), equal to rounding and no longer to
    the bit. A band the solve leaves unconverged is then the rounding's: a
    locked band's search direction is its rounding noise normalised, and the
    block's top band, which the deck's 20 steps leave 1.3e-2 from converged
    in the second iteration (band 7: 2e-4), moved by 1.3e-5 in that sum
    (bands 1-7 by 2e-10). So the deck gives the solve 40 steps, after which
    every band of both sides is within 1e-9 of converged and the sums are
    held as before: readings 6e-13 and 3e-10."""
    itsol = dict({"num_steps": 40}, **itsol)
    a = _run(serial=True, ngridk=ngridk, **itsol)
    b = _run(serial=False, ngridk=ngridk, **itsol)
    _agree(a, b, stages, "serial and mesh")


@pytest.mark.parametrize("ngridk", [(2, 2, 3), (2, 2, 2)])
@RULES
def test_one_device_vs_mesh_checksums_agree(ngridk, itsol):
    """One algorithm on both sides: the batched k-set solve on one device
    and sharded over the 8-device mesh, every stage, the eigenvalue sums
    included, whatever the solves leave unconverged. One device's loop takes
    its slowest k-point's steps and each device of the mesh its own: a
    k-point that is done is held, so it is the same on both sides (left to
    step on it was not: PR 37, solvers/davidson.py HOLD)."""
    import jax

    a = _run(serial=False, ngridk=ngridk, devices=jax.devices()[:1], **itsol)
    b = _run(serial=False, ngridk=ngridk, **itsol)
    _agree(a, b, ("evals", "rho_new", "veff"), "one device and mesh")
