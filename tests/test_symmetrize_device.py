"""The fused step's symmetrisers against the host's (dft/density.py).

The host `symmetrize_pw` is the plain form: a loop over the operations, each
image added into place (`np.add.at`, a scatter). The device form sums the
operations on the host into one small matrix a star of G-vectors
(`build_sym_pw_tables`) and applies it with two gathers; the two are held
against each other here on seeded random fields, in both precisions, for the
48 operations of the diamond group and for the antiferromagnetic cell whose
sublattice swaps carry spin_sign -1 (`axial_z`). No test runs an SCF:
tests/test_symmetric_kmesh.py does.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sirius_tpu.dft import density
from sirius_tpu.testing import synthetic_silicon_context

DECK = dict(gk_cutoff=3.0, pw_cutoff=7.0, ngridk=(1, 1, 1), num_bands=8,
            ultrasoft=True, use_symmetry=True)


@pytest.fixture(scope="module")
def ctxs():
    return {
        "diamond": synthetic_silicon_context(**DECK),
        "afm": synthetic_silicon_context(
            **DECK, moments=[[0, 0, 0.5], [0, 0, -0.5]],
            extra_params={"num_mag_dims": 1}),
    }


def _field(ctx, seed):
    rng = np.random.default_rng(seed)
    ng = ctx.gvec.num_gvec
    return rng.standard_normal(ng) + 1j * rng.standard_normal(ng)


def _tables(ctx, rdt):
    return jax.tree_util.tree_map(
        lambda a: jnp.asarray(a, dtype=rdt if a.dtype.kind == "f" else None),
        density.build_sym_pw_tables(ctx))


CASES = [("diamond", False), ("afm", False), ("afm", True)]


@pytest.mark.parametrize("name", ["diamond", "afm"])
def test_every_operation_permutes_the_sphere(ctxs, name):
    """Each idx row is a permutation, and the vectorised look-up is the
    dictionary look-up it replaced."""
    ctx = ctxs[name]
    ng = ctx.gvec.num_gvec
    cache = density.sym_rot_cache(ctx)
    assert len(cache) == ctx.symmetry.num_ops
    lut = {tuple(m): i for i, m in enumerate(ctx.gvec.millers)}
    for op, (idx, phase, ssign) in zip(ctx.symmetry.ops, cache):
        assert np.array_equal(np.sort(idx), np.arange(ng))
        gm = ctx.gvec.millers @ op.w_k.T
        assert np.array_equal(idx, [lut[tuple(m)] for m in gm])
        assert np.array_equal(phase, np.exp(-2j * np.pi * (gm @ op.t)))
        assert ssign == op.spin_sign
    assert {c[2] for c in cache} == {1, -1}  # det(R) R_zz


@pytest.mark.parametrize("name", ["diamond", "afm"])
def test_star_tables_cover_the_sphere_once(ctxs, name):
    ctx = ctxs[name]
    ng, nops = ctx.gvec.num_gvec, ctx.symmetry.num_ops
    tb = density.build_sym_pw_tables(ctx)
    m, nstars = tb["members"].shape
    assert tb["members"].dtype == tb["slot"].dtype == np.int32
    assert m <= nops and m * nstars >= ng
    assert np.array_equal(tb["members"].reshape(-1)[tb["slot"]], np.arange(ng))
    assert len(set(tb["slot"].tolist())) == ng
    # a pad's row and column are zero, so what it gathers never counts
    pad = np.ones(m * nstars, dtype=bool)
    pad[tb["slot"]] = False
    pad = pad.reshape(m, nstars)
    mat = tb["m_re"] + 1j * tb["m_im"]
    assert not mat.transpose(0, 2, 1)[pad].any()  # rows (i, s)
    assert not mat.transpose(1, 2, 0)[pad].any()  # columns (j, s)
    # a star's matrix is an average of unit phases: rows sum to at most 1
    assert np.abs(mat).sum(axis=1).max() <= 1 + 1e-12
    assert set(tb) == ({"members", "slot", "m_re", "m_im"} if name == "diamond"
                       else {"members", "slot", "m_re", "m_im", "ax_re", "ax_im"})


@pytest.mark.parametrize("name, axial", CASES)
def test_device_form_is_the_host_sum_f64(ctxs, name, axial):
    ctx = ctxs[name]
    f = _field(ctx, 11)
    want = density.symmetrize_pw(ctx, f, axial_z=axial)
    sym = jax.jit(density.symmetrize_pw_device, static_argnames="axial_z")
    got = np.asarray(sym(jnp.asarray(f), _tables(ctx, jnp.float64),
                         axial_z=axial))
    assert got.dtype == np.complex128
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-12)
    assert np.abs(want).max() > 0.05  # the projection is not empty
    # idempotent, as the ledger's S_SYM invariant reads it
    again = np.asarray(sym(jnp.asarray(got), _tables(ctx, jnp.float64),
                           axial_z=axial))
    np.testing.assert_allclose(again, got, rtol=0, atol=1e-12)


@pytest.mark.parametrize("name, axial", CASES)
def test_device_form_is_the_host_sum_f32(ctxs, name, axial):
    """32-bit tables and field: an output is a sum of at most 48 products
    whose weights |M_ij| sum to at most 1, each factor rounded once, so the
    error is within (48 / 2 + 2) eps of max|f|; 32 eps is the bar."""
    ctx = ctxs[name]
    f = _field(ctx, 12)
    want = density.symmetrize_pw(ctx, f, axial_z=axial)
    got = np.asarray(density.symmetrize_pw_device(
        jnp.asarray(f, dtype=jnp.complex64), _tables(ctx, jnp.float32),
        axial_z=axial))
    assert got.dtype == np.complex64
    eps = float(np.finfo(np.float32).eps)
    assert np.abs(got - want).max() <= 32 * eps * np.abs(f).max()


def test_device_form_is_invariant_under_every_operation(ctxs):
    ctx = ctxs["diamond"]
    fs = np.asarray(density.symmetrize_pw_device(
        jnp.asarray(_field(ctx, 13)), _tables(ctx, jnp.float64)))
    for idx, phase, _ in density.sym_rot_cache(ctx):
        # f(S g) = f(g) e^{-2 pi i (S g).t}
        np.testing.assert_allclose(fs[idx], fs * phase, atol=1e-12)


@pytest.mark.parametrize("name", ["diamond", "afm"])
def test_density_matrix_form_is_the_host_sum(ctxs, name):
    ctx = ctxs[name]
    ns, nbeta = ctx.num_spins, ctx.beta.num_beta_total
    rng = np.random.default_rng(14)
    dm = (rng.standard_normal((ns, nbeta, nbeta))
          + 1j * rng.standard_normal((ns, nbeta, nbeta)))
    dm = dm + np.conj(np.swapaxes(dm, 1, 2))
    want = density.symmetrize_density_matrix(ctx, dm)
    tb = jax.tree_util.tree_map(jnp.asarray, density.build_dm_sym_tables(ctx))
    got = np.asarray(jax.jit(density.symmetrize_density_matrix_device)(
        jnp.asarray(dm), tb))
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-12)
    assert np.abs(want).max() > 0.1
    assert ns == (2 if name == "afm" else 1) and bool(tb["flipneg"].any())


def test_symmetry_tables_are_built_once_a_context_under_one_span(ctxs):
    from sirius_tpu.obs import spans

    ctx = synthetic_silicon_context(**DECK)
    with spans.capture() as cap:
        tb = density.symmetry_tables(ctx)
        assert density.symmetry_tables(ctx) is tb
    (rec,) = cap.by_name("scf.setup.symmetry")
    assert rec["num_ops"] == 48 and rec["ng"] == ctx.gvec.num_gvec
    assert set(tb) == {"sym", "dm_sym"}


@pytest.mark.parametrize("do_sym, polarized, n", [
    (False, False, 0), (False, True, 0), (True, False, 3), (True, True, 5)])
def test_num_sym_pw(do_sym, polarized, n):
    """The new density, v_eff and the ledger's invariant; a moment adds
    itself and b_z."""
    assert density.num_sym_pw(do_sym, polarized) == n
