"""Gamma-point real-storage trick (ops/gamma.py): the packed-real basis is
an isometry of the Gamma-symmetric subspace, the packed H/S application
equals the complex one, and the generic davidson solver reproduces the
complex path's eigenvalues on packed real vectors.

Reference semantics: wave_functions.hpp:1589-1626, 1683-1696 (reduce_gvec
half-G storage + real GEMMs)."""

import numpy as np
import pytest

import jax.numpy as jnp


@pytest.fixture(scope="module")
def ctx():
    from sirius_tpu.testing import synthetic_silicon_context

    return synthetic_silicon_context(
        gk_cutoff=4.0, pw_cutoff=12.0, ngridk=(1, 1, 1), num_bands=8,
        use_symmetry=False,
    )


@pytest.fixture(scope="module")
def gm(ctx):
    from sirius_tpu.ops.gamma import build_gamma_map

    return build_gamma_map(
        np.asarray(ctx.gkvec.millers[0]), np.asarray(ctx.gkvec.mask[0])
    )


def _random_packed(gm, ctx, nb, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((nb, ctx.gkvec.ngk_max))
    P = len(gm.rep)
    x[:, 1 + 2 * P:] = 0.0  # padded slots
    return x


def test_isometry_and_roundtrip(ctx, gm):
    from sirius_tpu.ops.gamma import pack, unpack

    x = _random_packed(gm, ctx, 3)
    c = unpack(gm, x)
    # Gamma symmetry: c(-G) = conj(c(G))
    np.testing.assert_allclose(
        c[:, gm.par], np.conj(c[:, gm.rep]), atol=1e-14
    )
    # inner products match: sum x_a x_b == Re <a|b>
    gram_packed = x @ x.T
    gram_cplx = np.real(c @ np.conj(c).T)
    np.testing.assert_allclose(gram_packed, gram_cplx, atol=1e-12)
    # round trip
    np.testing.assert_allclose(pack(gm, c), x, atol=1e-13)


def test_apply_equivalence(ctx, gm):
    from sirius_tpu.ops.gamma import (
        apply_h_s_gamma,
        make_gamma_params,
        pack,
        unpack,
    )
    from sirius_tpu.ops.hamiltonian import apply_h_s, make_hk_params

    rng = np.random.default_rng(1)
    veff = rng.standard_normal(ctx.fft_coarse.dims) * 0.1
    gp = make_gamma_params(ctx, veff, gm=gm)
    hp = make_hk_params(ctx, 0, veff)
    x = _random_packed(gm, ctx, 4, seed=2)
    c = unpack(gm, x)
    hx, sx = apply_h_s_gamma(gp, jnp.asarray(x))
    hc, sc = apply_h_s(hp, jnp.asarray(c))
    np.testing.assert_allclose(
        unpack(gm, np.asarray(hx)), np.asarray(hc), atol=1e-10
    )
    np.testing.assert_allclose(
        unpack(gm, np.asarray(sx)), np.asarray(sc), atol=1e-10
    )


def test_davidson_gamma_matches_complex(ctx, gm):
    from sirius_tpu.ops.gamma import (
        davidson_gamma,
        make_gamma_params,
        pack_diags,
        unpack,
    )
    from sirius_tpu.ops.hamiltonian import apply_h_s, make_hk_params
    from sirius_tpu.parallel.batched import compute_h_diag, compute_o_diag
    from sirius_tpu.solvers.davidson import davidson

    rng = np.random.default_rng(3)
    veff = rng.standard_normal(ctx.fft_coarse.dims) * 0.05
    v0 = float(np.mean(veff))
    nb = 6
    gp = make_gamma_params(ctx, veff, gm=gm)
    hp = make_hk_params(ctx, 0, veff)
    h_diag = compute_h_diag(ctx, np.asarray(ctx.beta.dion)[None], v0)[0, 0]
    o_diag = compute_o_diag(ctx)[0]
    hd_p, od_p = pack_diags(gm, h_diag, o_diag)
    x0 = _random_packed(gm, ctx, nb, seed=4)
    ev_g, xg, rn_g = davidson_gamma(
        gp, jnp.asarray(x0), jnp.asarray(hd_p), jnp.asarray(od_p),
        num_steps=25, res_tol=1e-12,
    )
    from sirius_tpu.ops.gamma import unpack as _unpack

    c0 = _unpack(gm, x0)
    ev_c, xc, rn_c = davidson(
        apply_h_s, hp, jnp.asarray(c0),
        jnp.asarray(h_diag), jnp.asarray(o_diag),
        hp.mask, num_steps=25, res_tol=1e-12,
    )
    np.testing.assert_allclose(np.asarray(ev_g), np.asarray(ev_c), atol=5e-9)


def test_unpack_device_matches_unpack(ctx, gm):
    """The device-side unpack (the hand-off of the packed solve to the
    fused tail) is the host unpack: the same gathers, as a (re, im) pair."""
    from sirius_tpu.ops.gamma import make_gamma_params, unpack, unpack_device

    gp = make_gamma_params(ctx, np.zeros(ctx.fft_coarse.dims), gm=gm)
    x = _random_packed(gm, ctx, 5, seed=6).reshape(1, 5, -1)
    re, im = unpack_device(gp, jnp.asarray(x))
    c = unpack(gm, x)
    assert re.shape == im.shape == c.shape
    np.testing.assert_allclose(np.asarray(re), c.real, rtol=0, atol=1e-14)
    np.testing.assert_allclose(np.asarray(im), c.imag, rtol=0, atol=1e-14)


def test_pack_diags_device_matches_pack_diags(ctx, gm):
    """Sphere-order preconditioner diagonals gathered to packed order on
    the device, leading axes riding along, against the host pack_diags."""
    from sirius_tpu.ops.gamma import (
        make_gamma_params, pack_diags, pack_diags_device, pack_index)
    from sirius_tpu.parallel.batched import compute_h_diag, compute_o_diag

    rng = np.random.default_rng(5)
    nbeta = ctx.beta.num_beta_total
    dion = np.stack([
        np.asarray(ctx.beta.dion) + 0.1 * np.diag(rng.standard_normal(nbeta))
        for _ in range(2)])
    h_diag = compute_h_diag(ctx, dion, v0=-0.3)[0]  # [ns, ngk], 1e4 padded
    o_diag = compute_o_diag(ctx)[0]
    gp = make_gamma_params(ctx, np.zeros(ctx.fft_coarse.dims), gm=gm)
    hp, op = pack_diags_device(
        jnp.asarray(pack_index(gm, ctx.gkvec.ngk_max)), gp.mask_p,
        jnp.asarray(h_diag), jnp.asarray(o_diag))
    for s in range(2):
        hp_ref, op_ref = pack_diags(gm, h_diag[s], o_diag)
        np.testing.assert_allclose(np.asarray(hp[s]), hp_ref, rtol=0,
                                   atol=1e-14)
        np.testing.assert_allclose(np.asarray(op), op_ref, rtol=0, atol=1e-14)


def test_density_gamma_matches_complex(ctx, gm):
    """|Re psi(r)|^2 off the packed block, a spin axis riding along, is the
    complex path's density_kset of the unpacked block."""
    from sirius_tpu.ops.gamma import density_gamma, make_gamma_params, unpack
    from sirius_tpu.parallel.batched import (
        density_kset, make_hkset_params, split_cplx)

    gp = make_gamma_params(ctx, np.zeros(ctx.fft_coarse.dims), gm=gm)
    x = np.stack([_random_packed(gm, ctx, 4, seed=s) for s in (7, 8)])
    occ_w = np.random.default_rng(9).uniform(0.0, 2.0, size=(2, 4))
    acc = density_gamma(gp, jnp.asarray(x), jnp.asarray(occ_w))
    ps = make_hkset_params(ctx, np.zeros((2,) + tuple(ctx.fft_coarse.dims)))
    pr, pi = split_cplx(unpack(gm, x)[None])
    ref = density_kset(ps, jnp.asarray(pr), jnp.asarray(pi),
                       jnp.asarray(occ_w[None]))
    np.testing.assert_allclose(np.asarray(acc), np.asarray(ref), rtol=1e-12,
                               atol=1e-12)
