"""Local operator / iterative solver tests (mirrors reference test_hloc and
test_davidson): FFT-applied H vs densely built H, solver vs dense eigh."""

import jax
import jax.numpy as jnp
import numpy as np

from sirius_tpu.core import Gvec, GkVec, FFTGrid
from sirius_tpu.core.fftgrid import g_to_r
from sirius_tpu.ops.local import apply_local
from sirius_tpu.solvers.davidson import davidson
from sirius_tpu.solvers.eigen import build_h_s_matrices, exact_diag, eigh_gen


def _dense_apply(params, psi):
    h, s = params
    return psi @ h.T, psi @ s.T


def _setup(gk_cutoff=4.0, kpt=(0.0, 0.0, 0.0)):
    lat = np.diag([7.0, 7.5, 8.0])
    gv = Gvec.build(lat, gmax=2.5 * gk_cutoff)
    fft = FFTGrid.for_cutoff(lat, 2 * gk_cutoff)  # coarse (wave-function) box
    gk = GkVec.build(gv, np.array([kpt]), gk_cutoff, fft)
    # a smooth random potential from low G components, hermitized so V(r)
    # is real: V(-G) = V(G)*
    rng = np.random.default_rng(7)
    vg = np.zeros(gv.num_gvec, dtype=np.complex128)
    nlow = 40
    vg[:nlow] = rng.standard_normal(nlow) * 0.3 + 1j * rng.standard_normal(nlow) * 0.1
    idx_minus = gv.index_of_millers(-gv.millers)
    vg = 0.5 * (vg + np.conj(vg[idx_minus]))
    vg[0] = 0.2  # constant shift
    # map to the coarse box (production scheme: V_eff applied on coarse grid;
    # all |G-G'| differences of the gk sphere stay within 2*gk_cutoff)
    gv_coarse = Gvec.build(lat, 2 * gk_cutoff, fft=fft)
    vg_coarse = vg[gv.index_of_millers(gv_coarse.millers)]
    veff_r = np.asarray(
        g_to_r(jnp.asarray(vg_coarse), jnp.asarray(gv_coarse.fft_index), fft.dims)
    ).real
    return lat, gv, fft, gk, vg, veff_r


def test_apply_local_matches_dense():
    lat, gv, fft, gk, vg, veff_r = _setup()
    n = int(gk.num_gk[0])
    gkd = {"millers": gk.millers[0, :n], "ekin": gk.kinetic()[0, :n]}
    h, s = build_h_s_matrices(gkd, vg, gv.index_of_millers)
    # hermiticity of the dense build
    np.testing.assert_allclose(h, h.conj().T, atol=1e-12)
    rng = np.random.default_rng(3)
    psi = rng.standard_normal((5, gk.ngk_max)) + 1j * rng.standard_normal((5, gk.ngk_max))
    psi = psi * gk.mask[0]
    hpsi = apply_local(
        jnp.asarray(psi),
        jnp.asarray(veff_r.reshape(fft.dims)),
        jnp.asarray(gk.kinetic()[0]),
        jnp.asarray(gk.fft_index[0]),
        fft.dims,
        jnp.asarray(gk.mask[0]),
    )
    expect = psi[:, :n] @ h.T
    np.testing.assert_allclose(np.asarray(hpsi)[:, :n], expect, atol=1e-10)


def test_free_electrons():
    lat, gv, fft, gk, vg, veff_r = _setup()
    psi = np.zeros((3, gk.ngk_max), dtype=np.complex128)
    for b in range(3):
        psi[b, b] = 1.0
    hpsi = apply_local(
        jnp.asarray(psi),
        jnp.zeros(fft.dims),
        jnp.asarray(gk.kinetic()[0]),
        jnp.asarray(gk.fft_index[0]),
        fft.dims,
        jnp.asarray(gk.mask[0]),
    )
    ek = gk.kinetic()[0]
    for b in range(3):
        np.testing.assert_allclose(np.asarray(hpsi)[b, b], ek[b], rtol=1e-12)
        assert np.abs(np.asarray(hpsi)[b, np.arange(gk.ngk_max) != b]).max() < 1e-14


def test_davidson_matches_dense_eigh():
    lat, gv, fft, gk, vg, veff_r = _setup()
    n = int(gk.num_gk[0])
    gkd = {"millers": gk.millers[0, :n], "ekin": gk.kinetic()[0, :n]}
    h, _ = build_h_s_matrices(gkd, vg, gv.index_of_millers)
    nev = 6
    e_ref, _ = exact_diag(h, None, nev)

    from sirius_tpu.ops.hamiltonian import HkParams, apply_h_s as apply_hk

    params = HkParams(
        veff_r=jnp.asarray(veff_r.reshape(fft.dims)),
        ekin=jnp.asarray(gk.kinetic()[0]),
        mask=jnp.asarray(gk.mask[0]),
        fft_index=jnp.asarray(gk.fft_index[0]),
        beta=jnp.zeros((0, gk.ngk_max), dtype=jnp.complex128),
        dion=jnp.zeros((0, 0)),
        qmat=jnp.zeros((0, 0)),
    )
    rng = np.random.default_rng(11)
    x0 = rng.standard_normal((nev, gk.ngk_max)) + 1j * rng.standard_normal((nev, gk.ngk_max))
    h_diag = np.where(gk.mask[0] > 0, gk.kinetic()[0] + veff_r.mean(), 1e4)
    evals, x, rnorm, _ = davidson(
        apply_hk,
        params,
        jnp.asarray(x0),
        jnp.asarray(h_diag),
        jnp.ones(gk.ngk_max),
        params.mask,
        num_steps=60,
        res_tol=1e-9,
        by_energy=False,
    )
    np.testing.assert_allclose(np.asarray(evals), e_ref, atol=1e-8)
    assert np.asarray(rnorm).max() < 1e-6


def test_davidson_generalized():
    # small synthetic generalized problem through the same code path:
    # S = I + low-rank positive; compare against scipy gen eigh
    rng = np.random.default_rng(5)
    n, nev = 40, 4
    a = rng.standard_normal((n, n)) + 1j * rng.standard_normal((n, n))
    h = (a + a.conj().T) / 2 + np.diag(np.arange(n) * 2.0)
    b = rng.standard_normal((n, 3)) + 1j * rng.standard_normal((n, 3))
    s = np.eye(n) + 0.3 * b @ b.conj().T
    import scipy.linalg

    e_ref = scipy.linalg.eigh(h, s, eigvals_only=True)[:nev]
    hj, sj = jnp.asarray(h), jnp.asarray(s)

    x0 = jnp.asarray(rng.standard_normal((nev, n)) + 1j * rng.standard_normal((nev, n)))
    evals, x, rnorm, _ = davidson(
        _dense_apply,
        (hj, sj),
        x0,
        jnp.real(jnp.diag(hj)),
        jnp.real(jnp.diag(sj)),
        jnp.ones(n),
        num_steps=60,
        res_tol=1e-10,
        by_energy=False,
    )
    np.testing.assert_allclose(np.asarray(evals), e_ref, atol=1e-6)
    # eigh_gen agrees too
    e2, _ = eigh_gen(hj, sj)
    np.testing.assert_allclose(np.asarray(e2)[:nev], e_ref, atol=1e-9)
