"""The Gamma path's device programs, compiled for a described v5e:2x2: the
two longest compiles of tests/test_tpu_compile.py (whose docstring says what
such a compile shows and why the topology is described inside a fixture), in
a file of their own so that ``--dist loadfile`` can give them to another
worker. Fixtures and helpers are that file's, imported and not copied."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from tests.test_tpu_compile import (  # noqa: F401  (fixtures by name)
    NUM_STEPS, RULE, _check, _compile, _ctx, _fused, _fused_args,
    _library_eigh, _lowered_text, _one_step_body, _shapes, no_compile_cache,
    topo,
)


@pytest.fixture(scope="module")
def ctx_gamma():
    return _ctx((1, 1, 1))


def test_gamma_band_solve_one_chip(topo, no_compile_cache, ctx_gamma):
    """The packed-real Gamma solve of run_scf's `gamma` path (band_solve.GammaSolver)."""
    from sirius_tpu.ops.gamma import (
        build_gamma_map, davidson_gamma, initialize_subspace_gamma,
        make_gamma_params,
    )

    ctx = ctx_gamma
    one = SingleDeviceSharding(topo.devices[0])
    gm = build_gamma_map(np.asarray(ctx.gkvec.millers[0]),
                         np.asarray(ctx.gkvec.mask[0]))
    gp = _shapes(make_gamma_params(
        ctx, np.zeros(ctx.fft_coarse.dims), gm, rdtype=jnp.float32), one)
    nb, ngk = ctx.num_bands, ctx.gkvec.ngk_max
    x0 = jax.ShapeDtypeStruct((nb, ngk), np.float32, sharding=one)
    diag = jax.ShapeDtypeStruct((ngk,), np.float32, sharding=one)
    tol = jax.ShapeDtypeStruct((), np.float32, sharding=one)
    lower = lambda: davidson_gamma.lower(
        gp, x0, diag, diag, num_steps=NUM_STEPS, res_tol=tol, **RULE)
    _one_step_body(_check(_compile(lower), no_64bit=True))
    # real matrices bypass solvers/subspace_eigh.py's reduction by dtype:
    # the program is lowered to the text it has with the library's call
    mine = _lowered_text(lower)
    with _library_eigh():
        assert mine == _lowered_text(lower)
    nbig = jax.ShapeDtypeStruct((nb + 6, ngk), np.float32, sharding=one)
    _check(_compile(lambda: initialize_subspace_gamma.lower(gp, nbig, nb=nb)),
           no_64bit=True)


def test_gamma_fused_tail_one_chip(topo, no_compile_cache):
    """The Gamma path's iteration tail at the widths of the benchmark's
    si16-gamma-us (16 atoms, 64 bands, gk 6 / pw 20): the hand-off of the
    packed solve (solve_inputs_device, unpack_device), density_gamma, the
    density matrix and the fused step, as run_scf's `gamma` path
    feeds them."""
    from sirius_tpu.ops.gamma import (
        build_gamma_map, density_gamma, make_gamma_params, pack_index,
        solve_inputs_device, unpack_device,
    )
    from sirius_tpu.parallel.batched import density_matrix_kset

    ctx = _ctx((1, 1, 1), supercell=2, num_bands=64)
    assert ctx.unit_cell.num_atoms == 16
    one = SingleDeviceSharding(topo.devices[0])
    f32 = np.float32
    gm = build_gamma_map(np.asarray(ctx.gkvec.millers[0]),
                         np.asarray(ctx.gkvec.mask[0]))
    gp = _shapes(make_gamma_params(
        ctx, np.zeros(ctx.fft_coarse.dims), gm, rdtype=jnp.float32), one)
    nb, ngk = ctx.num_bands, ctx.gkvec.ngk_max
    nbeta = ctx.beta.num_beta_total
    dims = tuple(ctx.fft_coarse.dims)

    def sds(*shape):
        return jax.ShapeDtypeStruct(shape, f32, sharding=one)

    _check(_compile(lambda: solve_inputs_device.lower(
        _shapes(pack_index(gm, ngk), one), gp.mask_p, sds(ngk),
        sds(1, *dims), sds(1, nbeta, nbeta), sds(1, 1, ngk))),
        no_64bit=True)
    _check(_compile(lambda: unpack_device.lower(gp, sds(1, nb, ngk))),
           no_64bit=True)
    _check(_compile(lambda: density_gamma.lower(
        gp, sds(1, nb, ngk), sds(1, nb))), no_64bit=True)
    _check(_compile(lambda: density_matrix_kset.lower(
        sds(1, nbeta, ngk), sds(1, nbeta, ngk), sds(1, 1, nb, ngk),
        sds(1, 1, nb, ngk), sds(1, 1, nb))), no_64bit=True)
    fused = _fused(ctx)
    args = _fused_args(fused, ctx, nb, one, one, one)
    _check(_compile(lambda: fused._step.lower(*args)), no_64bit=True)
