"""Sharded-path correctness: the k-set-batched band solve and density
reduction must produce the same numbers under an explicit multi-device
("k", "b") mesh as on a single device (GSPMD is a layout annotation, not a
different algorithm — reference parallel spec SURVEY §2.8,
src/context/simulation_context.cpp:1300-1349 mpi grid).

All jit boundaries are real-array pairs (parallel/batched.py real-boundary
contract). Runs on the 8-device virtual CPU mesh set up by conftest.py."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from sirius_tpu.dft.occupation import find_fermi
from sirius_tpu.parallel.batched import (
    davidson_kset,
    density_kset,
    make_hkset_params,
    split_cplx,
)
from sirius_tpu.parallel.mesh import make_mesh, shard_kset
from sirius_tpu.testing import synthetic_silicon_context


@pytest.fixture(scope="module")
def kset_problem():
    ctx = synthetic_silicon_context(
        gk_cutoff=3.0, pw_cutoff=7.0, ngridk=(2, 2, 2), num_bands=8,
        use_symmetry=False,
    )
    params = make_hkset_params(ctx, np.full(ctx.fft_coarse.dims, 0.05))
    rng = np.random.default_rng(7)
    nk, ns, nb, ngk = ctx.gkvec.num_kpoints, 1, 8, ctx.gkvec.ngk_max
    psi = (
        rng.standard_normal((nk, ns, nb, ngk))
        + 1j * rng.standard_normal((nk, ns, nb, ngk))
    ) * ctx.gkvec.mask[:, None, None, :]
    pr, pi = split_cplx(psi)
    return ctx, params, jnp.asarray(pr), jnp.asarray(pi)


def _shard_params(params, mesh):
    kvec = NamedSharding(mesh, P("k", None))
    kmat = NamedSharding(mesh, P("k", None, None))
    return params._replace(
        ekin=jax.device_put(params.ekin, kvec),
        mask=jax.device_put(params.mask, kvec),
        fft_index=jax.device_put(params.fft_index, kvec),
        beta_re=jax.device_put(params.beta_re, kmat),
        beta_im=jax.device_put(params.beta_im, kmat),
        h_diag=jax.device_put(params.h_diag, kvec),
        o_diag=jax.device_put(params.o_diag, kvec),
    )


def test_davidson_kset_sharded_matches_serial(kset_problem):
    ctx, params, pr, pi = kset_problem
    assert len(jax.devices()) >= 8, "conftest must provide 8 virtual devices"
    ev_ref, pr_ref, pi_ref, rn_ref, _ = davidson_kset(
        params, pr, pi, num_steps=6)

    mesh = make_mesh(num_k=4, num_b=2)
    with mesh:
        ps = _shard_params(params, mesh)
        pr_sh, pi_sh = shard_kset(mesh, pr), shard_kset(mesh, pi)
        ev, pr2, pi2, rn, _ = davidson_kset(ps, pr_sh, pi_sh, num_steps=6)
        jax.block_until_ready(ev)
    np.testing.assert_allclose(np.asarray(ev), np.asarray(ev_ref), atol=1e-9)
    np.testing.assert_allclose(np.asarray(rn), np.asarray(rn_ref), atol=1e-7)


def test_density_kset_sharded_matches_serial(kset_problem):
    ctx, params, pr, pi = kset_problem
    occ_w = jnp.ones((pr.shape[0], 1, pr.shape[2])) * jnp.asarray(
        ctx.kweights
    )[:, None, None]
    rho_ref = density_kset(params, pr, pi, occ_w)

    mesh = make_mesh(num_k=4, num_b=2)
    with mesh:
        ps = _shard_params(params, mesh)
        pr_sh, pi_sh = shard_kset(mesh, pr), shard_kset(mesh, pi)
        occ_sh = jax.device_put(occ_w, NamedSharding(mesh, P("k", None, "b")))
        rho = density_kset(ps, pr_sh, pi_sh, occ_sh)
        jax.block_until_ready(rho)
    # contraction over the sharded k axis is a psum XLA inserts; identical
    # up to reduction-order rounding
    np.testing.assert_allclose(np.asarray(rho), np.asarray(rho_ref), atol=1e-10)


def test_full_iteration_sharded_end_to_end(kset_problem):
    """davidson -> fermi -> density under the mesh: the dryrun path, in CI."""
    ctx, params, pr, pi = kset_problem
    mesh = make_mesh(num_k=2, num_b=4)
    with mesh:
        ps = _shard_params(params, mesh)
        pr_sh, pi_sh = shard_kset(mesh, pr), shard_kset(mesh, pi)
        ev, pr2, pi2, rn, _ = davidson_kset(ps, pr_sh, pi_sh, num_steps=4)
        mu, occ, ent = find_fermi(
            ev, jnp.asarray(ctx.kweights), 8.0, 0.025, max_occupancy=2.0
        )
        rho = density_kset(
            ps, pr2, pi2, occ * jnp.asarray(ctx.kweights)[:, None, None]
        )
        jax.block_until_ready(rho)
    rho = np.asarray(rho)
    assert np.all(np.isfinite(rho))
    assert rho.sum() > 0
    assert np.all(np.isfinite(np.asarray(ev)))
