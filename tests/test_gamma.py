"""Gamma-point real-storage trick (ops/gamma.py): the packed-real basis is
an isometry of the Gamma-symmetric subspace, the packed H/S application
equals the complex one, and the generic davidson solver reproduces the
complex path's eigenvalues on packed real vectors.

Reference semantics: wave_functions.hpp:1589-1626, 1683-1696 (reduce_gvec
half-G storage + real GEMMs)."""

import re

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


@pytest.mark.parametrize("dtype", ["float64", "float32"])
@pytest.mark.parametrize("rows", [1, 4, 5])
def test_apply_equivalence(ctx, gm, rows, dtype):
    """Two real bands a complex box (an odd block travels with a row of
    zeros) against the complex path's one band a box."""
    from sirius_tpu.ops.gamma import apply_h_s_gamma, make_gamma_params, unpack
    from sirius_tpu.ops.hamiltonian import apply_h_s, make_hk_params

    rng = np.random.default_rng(1)
    veff = rng.standard_normal(ctx.fft_coarse.dims) * 0.1
    gp = make_gamma_params(ctx, veff, gm=gm, rdtype=jnp.dtype(dtype))
    hp = make_hk_params(ctx, 0, veff)
    x = _random_packed(gm, ctx, rows, seed=2)
    c = unpack(gm, x)
    hx, sx = apply_h_s_gamma(gp, jnp.asarray(x, dtype=dtype))
    assert hx.dtype == sx.dtype == jnp.dtype(dtype)
    assert hx.shape == sx.shape == x.shape
    hc, sc = apply_h_s(hp, jnp.asarray(c))
    for got, ref in ((hx, hc), (sx, sc)):
        ref = np.asarray(ref)
        err = np.abs(unpack(gm, np.asarray(got, dtype=np.float64)) - ref).max()
        if dtype == "float64":
            assert err < 1e-10
        else:
            assert err < 3e-5 * np.abs(ref).max()


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
    ev_g, xg, rn_g, _ = davidson_gamma(
        gp, jnp.asarray(x0), jnp.asarray(hd_p), jnp.asarray(od_p),
        num_steps=25, res_tol=1e-12, by_energy=False,
    )
    from sirius_tpu.ops.gamma import unpack as _unpack

    c0 = _unpack(gm, x0)
    ev_c, xc, rn_c, _ = davidson(
        apply_h_s, hp, jnp.asarray(c0),
        jnp.asarray(h_diag), jnp.asarray(o_diag),
        hp.mask, num_steps=25, res_tol=1e-12, by_energy=False,
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


@pytest.mark.parametrize("nb", [8, 7])
def test_density_gamma_matches_complex(ctx, gm, nb):
    """Re^2 and Im^2 of the paired boxes off the packed block (an odd band
    count padded with weight 0), a spin axis riding along, is the complex
    path's density_kset of the unpacked block."""
    from sirius_tpu.ops.gamma import density_gamma, make_gamma_params, unpack
    from sirius_tpu.parallel.batched import (
        density_kset, make_hkset_params, split_cplx)

    gp = make_gamma_params(ctx, np.zeros(ctx.fft_coarse.dims), gm=gm)
    x = np.stack([_random_packed(gm, ctx, nb, seed=s) for s in (7, 8)])
    occ_w = np.random.default_rng(9).uniform(0.0, 2.0, size=(2, nb))
    acc = density_gamma(gp, jnp.asarray(x), jnp.asarray(occ_w))
    ps = make_hkset_params(ctx, np.zeros((2,) + tuple(ctx.fft_coarse.dims)))
    pr, pi = split_cplx(unpack(gm, x)[None])
    ref = density_kset(ps, jnp.asarray(pr), jnp.asarray(pi),
                       jnp.asarray(occ_w[None]))
    assert acc.shape == (2,) + tuple(ctx.fft_coarse.dims)
    np.testing.assert_allclose(np.asarray(acc), np.asarray(ref), rtol=1e-12,
                               atol=1e-12)


def _fft_batches(hlo: str):
    """Leading (batch) dimension of every fft instruction of an HLO text."""
    return sorted(int(m) for m in re.findall(
        r"= c(?:64|128)\[(\d+),\d+,\d+,\d+\]\S* fft\(", hlo))


@pytest.mark.parametrize("nb", [6, 5])
def test_davidson_gamma_ffts_are_paired(ctx, gm, nb):
    """The structural proof that no unpaired transform is left: every fft
    of the compiled Davidson program (chunk boundary on [X; P], step, exit:
    an inverse and a forward each) and of the LCAO rotation has a batch of
    ceil(rows / 2)."""
    from sirius_tpu.ops.gamma import (
        davidson_gamma, initialize_subspace_gamma, make_gamma_params)

    gp = make_gamma_params(ctx, np.zeros(ctx.fft_coarse.dims), gm=gm)
    ngk = ctx.gkvec.ngk_max
    x0 = jnp.zeros((nb, ngk))
    diag = jnp.ones(ngk)
    hlo = davidson_gamma.lower(
        gp, x0, diag, diag, num_steps=10, res_tol=1e-6).compile().as_text()
    half = -(-nb // 2)
    assert _fft_batches(hlo) == sorted([nb] * 2 + [half] * 4)
    nbig = nb + 3
    hlo = initialize_subspace_gamma.lower(
        gp, jnp.zeros((nbig, ngk)), nb=nb).compile().as_text()
    assert _fft_batches(hlo) == [-(-nbig // 2)] * 2


def _counted_run(ngridk, num_bands, iters=3):
    """The result, and the (steps, chunks) every band solve booked."""
    import jax

    from sirius_tpu.dft import band_solve
    from sirius_tpu.dft.scf import run_scf
    from sirius_tpu.testing import synthetic_silicon_context

    c = synthetic_silicon_context(
        gk_cutoff=3.0, pw_cutoff=7.0, ngridk=ngridk, num_bands=num_bands,
        ultrasoft=True, use_symmetry=False,
        extra_params={"num_dft_iter": iters})
    ran = []
    book = band_solve.count_solve
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(band_solve, "count_solve", lambda cnt, r, *a, **kw: (
            ran.append(np.reshape(r, (-1, 2))), book(cnt, r, *a, **kw)))
        # one compute device: a Gamma-only deck then takes the packed solve
        r = run_scf(c.cfg, ctx=c, devices=jax.devices()[1:2])
    assert len(ran) == r["num_scf_iterations"]
    assert all(1 <= s <= c.cfg.iterative_solver.num_steps
               for solve in ran for s in solve[:, 0])
    return r, ran


def test_num_fft_boxes_counts_paired_applications():
    """counters.num_fft_boxes of a Gamma run_scf is two transforms a PAIR of
    rows of every application (the LCAO block, then the Davidson blocks of
    each iteration): the engagement record num_fft_boxes /
    num_loc_op_applied is 1 + O(1/nb), where a k-mesh run reads 2."""
    from sirius_tpu.solvers.davidson import apply_blocks, num_applies

    nb = 7
    r, ran = _counted_run((1, 1, 1), nb)
    assert r["placement"]["path"] == "gamma"
    cnt = r["counters"]
    # one loop a solve (one spin channel); the counters are of what it ran
    ran = [tuple(int(v) for v in solve[0]) for solve in ran]
    assert cnt["num_davidson_steps"] == sum(steps for steps, _ in ran)
    assert cnt["num_subspace_eigh"] == sum(2 * steps + 1 for steps, _ in ran)
    lcao = cnt["num_loc_op_applied"] - sum(
        num_applies(steps, chunks, nb) for steps, chunks in ran)
    assert nb <= lcao <= 2 * nb
    want = 2 * -(-lcao // 2) + sum(
        2 * times * -(-rows // 2)
        for steps, chunks in ran
        for rows, times in apply_blocks(steps, chunks, nb))
    assert cnt["num_fft_boxes"] == want
    assert 1.0 <= cnt["num_fft_boxes"] / cnt["num_loc_op_applied"] < 1.0 + 2 / nb


def test_num_fft_boxes_is_two_a_row_on_a_kmesh():
    r, ran = _counted_run((2, 2, 2), 8, iters=2)
    assert r["placement"]["path"].startswith("batched")
    cnt = r["counters"]
    # a row of `ran` a k-point, each the one loop's count on its device
    assert all(solve.shape == (8, 2) and (solve == solve[0]).all()
               for solve in ran)
    assert cnt["num_davidson_steps"] == sum(int(s[0, 0]) for s in ran)
    assert cnt["num_loc_op_applied"] > 0
    assert cnt["num_fft_boxes"] == 2 * cnt["num_loc_op_applied"]
