"""The subspace eigensolver (solvers/subspace_eigh.py): a complex Hermitian
matrix lowered for the TPU is reduced to a real tridiagonal one and solved by
the library's real eigh; every other case is the library's call on the input
itself. The CPU rule picks the library, so the reduction is called directly
here; what the TPU's compiler makes of it is tests/test_tpu_compile.py's."""

import contextlib
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sirius_tpu.solvers import subspace_eigh as se

davidson_mod = importlib.import_module("sirius_tpu.solvers.davidson")


def hermitian(rng, b, n, norm=18.0):
    a = rng.standard_normal((b, n, n)) + 1j * rng.standard_normal((b, n, n))
    a = a + a.conj().transpose(0, 2, 1)
    return a * (norm / np.linalg.norm(a, 2, axis=(1, 2)))[:, None, None]


def check(a, dtype):
    """eigh_tridiagonal_real of a (cast to dtype) against numpy's f64 eigh of
    the same numbers: eigenvalues, residual and unitarity, on the scale of
    the matrix."""
    a = np.asarray(a).astype(dtype)
    e, v = jax.jit(se.eigh_tridiagonal_real)(jnp.asarray(a))
    assert e.dtype == np.zeros((), dtype).real.dtype and v.dtype == dtype
    e, v = np.asarray(e, np.float64), np.asarray(v, np.complex128)
    a64 = a.astype(np.complex128)
    n = a.shape[-1]
    norm = np.maximum(np.linalg.norm(a64, 2, axis=(-2, -1)), 1e-300)[..., None]
    eps = np.finfo(np.zeros((), dtype).real.dtype).eps
    tol = 1e-12 if dtype == np.complex128 else 20 * eps
    assert np.all(np.diff(e, axis=-1) >= 0)  # ascending, as the library's
    assert (np.abs(e - np.linalg.eigh(a64)[0]) / norm).max() <= tol
    res = np.abs(a64 @ v - v * e[..., None, :]).max(axis=-2) / norm
    assert res.max() <= tol
    assert np.abs(np.swapaxes(v.conj(), -1, -2) @ v - np.eye(n)).max() <= tol * 4


@pytest.mark.parametrize("dtype", [np.complex128, np.complex64],
                         ids=["c128", "c64"])
@pytest.mark.parametrize("b, n", [(36, 78), (8, 192), (3, 1), (3, 2), (3, 3)],
                         ids=["36x78", "8x192", "n1", "n2", "n3"])
def test_reduction_meets_numpy_f64(b, n, dtype):
    check(hermitian(np.random.default_rng(n), b, n), dtype)


@pytest.mark.parametrize("dtype", [np.complex128, np.complex64],
                         ids=["c128", "c64"])
def test_nearly_diagonal_matrix(dtype):
    """What the solver hands over near convergence."""
    a = hermitian(np.random.default_rng(1), 2, 78)
    d = np.zeros_like(a)
    i = np.arange(78)
    d[:, i, i] = a[:, i, i]
    check(d + 1e-6 * a, dtype)
    check(d, dtype)  # exactly diagonal: every step is the identity


@pytest.mark.parametrize("dtype", [np.complex128, np.complex64],
                         ids=["c128", "c64"])
def test_zero_rows_and_columns(dtype):
    """A column whose tail is exactly zero is the identity step (the zero P
    block of a solve's first step, locked rows): no 0/0 anywhere."""
    a = hermitian(np.random.default_rng(2), 3, 78)
    a[0, 26:52] = 0
    a[0, :, 26:52] = 0
    a[1, 40:] = 0
    a[1, :, 40:] = 0
    a[2] = 0
    check(a, dtype)


@pytest.mark.parametrize("dtype", [np.complex128, np.complex64],
                         ids=["c128", "c64"])
def test_exactly_degenerate_pairs(dtype):
    """Kramers pairs of the spinor solve: [[A, -conj(B)], [B, conj(A)]] with
    A Hermitian and B antisymmetric has every eigenvalue twice."""
    rng = np.random.default_rng(3)
    n = 39
    a = hermitian(rng, 1, n)[0]
    b = rng.standard_normal((n, n)) + 1j * rng.standard_normal((n, n))
    b = b - b.T
    m = np.block([[a, -b.conj()], [b, a.conj()]])
    assert np.allclose(m, m.conj().T)
    e = np.linalg.eigvalsh(m)
    assert np.abs(e[0::2] - e[1::2]).max() < 1e-12
    check(m[None], dtype)


@contextlib.contextmanager
def solver_eigh(fn):
    """`fn` at the solver's three eigh sites; trace inside through a fresh
    jit, so that no cached trace holds the other eigh."""
    old, davidson_mod.eigh = davidson_mod.eigh, fn
    try:
        yield
    finally:
        davidson_mod.eigh = old


def rayleigh_ritz(eigh_fn, hsub, ssub, nev):
    with solver_eigh(eigh_fn):
        e, c = jax.jit(lambda h, s: davidson_mod._rayleigh_ritz(h, s, nev))(
            jnp.asarray(hsub), jnp.asarray(ssub))
    return np.asarray(e), np.asarray(c)


@pytest.mark.parametrize("dtype, tol", [(np.complex128, 1e-11),
                                        (np.complex64, 3e-5)],
                         ids=["c128", "c64"])
def test_rayleigh_ritz_with_parked_directions(dtype, tol):
    """_rayleigh_ritz's own matrices: a rank-deficient overlap (a third of
    the block repeated, a zero row) whose projected-out directions are parked
    at 1 + |at|_inf on the diagonal, with zero rows and columns beside it."""
    rng = np.random.default_rng(4)
    nb, ng = 26, 200
    v = rng.standard_normal((3 * nb, ng)) + 1j * rng.standard_normal((3 * nb, ng))
    v[2 * nb:] = v[:nb]          # P = X: rank nb short
    v[nb + 3] = 0                # a locked row of W
    h = hermitian(rng, 1, ng, norm=12.0)[0]
    hsub = (v.conj() @ h @ v.T).astype(dtype)
    ssub = (v.conj() @ v.T).astype(dtype)
    hsub, ssub = 0.5 * (hsub + hsub.conj().T), 0.5 * (ssub + ssub.conj().T)
    e_lib, _ = rayleigh_ritz(se._library, hsub, ssub, nb)
    e_red, c = rayleigh_ritz(se.eigh_tridiagonal_real, hsub, ssub, nb)
    scale = np.abs(e_lib).max()
    assert np.abs(e_red - e_lib).max() <= tol * scale
    # Ritz vectors are S-orthonormal and their Ritz values are e
    c64 = c.astype(np.complex128)
    s64, h64 = ssub.astype(np.complex128), hsub.astype(np.complex128)
    assert np.abs(c64.conj().T @ s64 @ c64 - np.eye(nb)).max() <= 30 * tol
    ritz = np.real(np.diag(c64.conj().T @ h64 @ c64))
    assert np.abs(ritz - e_red).max() <= 30 * tol * scale


@pytest.mark.parametrize("dtype, tol", [(np.complex128, 1e-12),
                                        (np.complex64, 2e-5)],
                         ids=["c128", "c64"])
def test_tridiagonal_form_is_lapacks(dtype, tol):
    """d and |e| against LAPACK's hetrd (jax.lax.linalg.tridiagonal, CPU
    only: it has no TPU lowering, which is why the loop is ours). Both start
    from the first column, so T is the same up to the phases of e; and
    Q T Q^H gives the matrix back."""
    a = hermitian(np.random.default_rng(5), 4, 78).astype(dtype)
    q, d, e = jax.jit(jax.vmap(se.tridiagonalize))(jnp.asarray(a))
    _, d_ref, e_ref, _ = jax.lax.linalg.tridiagonal(jnp.asarray(a), lower=True)
    norm = 18.0
    assert np.abs(np.asarray(d) - np.asarray(d_ref)).max() <= tol * norm
    assert np.abs(np.abs(np.asarray(e)) - np.abs(np.asarray(e_ref))).max() <= tol * norm
    q, d, e = (np.asarray(x).astype(np.complex128) for x in (q, d, e))
    i = np.arange(77)
    t = np.zeros((4, 78, 78), np.complex128)
    t[:, np.arange(78), np.arange(78)] = d
    t[:, i + 1, i] = e
    t[:, i, i + 1] = e.conj()
    back = q @ t @ q.conj().transpose(0, 2, 1)
    assert np.abs(back - a).max() <= tol * norm


def test_a_huge_and_a_tiny_matrix_are_scaled():
    a = hermitian(np.random.default_rng(6), 1, 12, norm=1.0)[0]
    for scale in (1e-30, 1e25):
        m = (a * scale).astype(np.complex64)
        e, v = se.eigh_tridiagonal_real(jnp.asarray(m))
        ref = np.linalg.eigvalsh(m.astype(np.complex128))
        assert np.all(np.isfinite(np.asarray(v)))
        assert np.abs(np.asarray(e, np.float64) - ref).max() <= 20 * 1.2e-7 * scale


def test_form_follows_dtype_and_platform():
    assert se.form(jnp.complex64, "tpu") == "tridiagonal_real"
    assert se.form(jnp.complex128, "tpu") == "tridiagonal_real"
    for platform in ("cpu", "cuda", "rocm"):
        assert se.form(jnp.complex64, platform) == "library"
    for platform in ("tpu", "cpu"):
        assert se.form(jnp.float32, platform) == "library"
        assert se.form(jnp.float64, platform) == "library"


@pytest.mark.parametrize("dtype", [np.float32, np.float64], ids=["f32", "f64"])
def test_real_input_traces_to_the_parents_jaxpr(dtype):
    """GammaSolver's packed-real subspace: eigh on the input itself, no
    platform switch, nothing of the reduction."""
    a = jnp.zeros((5, 24, 24), dtype)
    assert str(jax.make_jaxpr(se.eigh)(a)) == str(jax.make_jaxpr(jnp.linalg.eigh)(a))


@pytest.mark.parametrize("dtype", [np.complex64, np.complex128],
                         ids=["c64", "c128"])
def test_complex_input_lowered_for_the_cpu_is_lapack(dtype):
    """The platform is read at lowering: the CPU program calls LAPACK's
    heevd on the input and holds no loop and no conditional."""
    a = jnp.asarray(hermitian(np.random.default_rng(7), 3, 24).astype(dtype))
    f = jax.jit(jax.vmap(se.eigh))
    txt = f.lower(a).compile().as_text()
    assert "heevd" in txt
    assert "while(" not in txt and "conditional(" not in txt
    e, v = f(a)
    e_ref, v_ref = jnp.linalg.eigh(a)
    assert np.array_equal(np.asarray(e), np.asarray(e_ref))
    assert np.array_equal(np.asarray(v), np.asarray(v_ref))


def test_davidson_with_the_reduction_meets_the_librarys_bands():
    """One complex64 solve at a generic k-point, the reduction forced into
    the solver where the CPU rule would pick LAPACK."""
    from sirius_tpu.dft import band_solve
    from sirius_tpu.dft.scf import _initial_subspace
    from sirius_tpu.ops.hamiltonian import apply_h_s, make_hk_params
    from tests.test_kset_solver import context, deck

    _, ctx = context(deck((1, 1, 1), vk=[[0.11, 0.23, 0.31]]))
    rng = np.random.default_rng(8)
    veff = 0.1 * rng.standard_normal(tuple(ctx.fft_coarse.dims))
    prm = make_hk_params(ctx, 0, veff, None, dtype=jnp.complex64)
    h_diag, o_diag = band_solve._h_o_diag(ctx, 0, 0.0, ctx.beta.dion)
    x0 = jnp.asarray(_initial_subspace(ctx)[0, 0, :8], jnp.complex64)
    args = (prm, x0, jnp.asarray(h_diag, jnp.float32),
            jnp.asarray(o_diag, jnp.float32), prm.mask)
    solve = davidson_mod.davidson.__wrapped__
    tol = np.float32(1e-7)

    def solve_with(fn):
        with solver_eigh(fn):
            return jax.jit(lambda *a: solve(
                apply_h_s, *a, num_steps=12, res_tol=tol))(*args)

    out = {"library": solve_with(se._library),
           "reduction": solve_with(se.eigh_tridiagonal_real)}
    ev_l, _, rn_l, _ = (np.asarray(x) for x in out["library"])
    ev_r, x_r, rn_r, _ = (np.asarray(x) for x in out["reduction"])
    assert np.all(np.isfinite(x_r))
    assert np.abs(ev_r - ev_l).max() <= 2e-5
    assert rn_r.max() <= max(10.0 * rn_l.max(), 1e-4)
