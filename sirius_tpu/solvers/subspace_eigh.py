"""The eigensolver of the band solvers' subspace matrices.

One algorithm, ``eigh``; who diagonalises follows the two things that decide
what a compiler makes of it, the dtype and the backend the program is lowered
for:

* a real symmetric matrix: the library's call, whatever the backend (on the
  TPU that is the ``EighTpu`` kernel up to 256 rows and a QDWH program above);
* a complex Hermitian matrix lowered for the CPU or a GPU: the library's
  call, LAPACK or cuSOLVER, better than any loop of ours;
* a complex Hermitian matrix lowered for the TPU: the TPU's compiler expands
  the library's call into Jacobi sweep loops whose time is whole-matrix
  passes through HBM (1 ms a 78-row matrix, what the real kernel charges for
  192 rows; PERF.md section 6, PR 35). So there the matrix is reduced to a
  real one of the same size first:

    1. Householder reflectors H_k = 1 - tau_k v_k v_k^H take A to a Hermitian
       tridiagonal T = Q^H A Q, Q = H_0 H_1 ... (fixed shapes, masks in place
       of shrinking slices, batched by whatever vmap the caller is under);
    2. a diagonal of unit phases D, the running product of e_j / |e_j| over
       the subdiagonal e, makes D^H T D real symmetric;
    3. the library's eigh of that real matrix: D^H T D = Y E Y^T;
    4. V = Q D Y.

The platform is read when the program is lowered (``lax.platform_dependent``:
one branch is lowered, the compiled program holds no conditional). Every
product is float32 arithmetic at full precision: the reduction's are
elementwise multiply-adds and reductions, the one matrix product (Q D) Y is at
``Precision.HIGHEST``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

FORM_TRIDIAGONAL_REAL = "tridiagonal_real"
FORM_LIBRARY = "library"


def form(dtype, platform: str) -> str:
    """Which of the two forms ``eigh`` is for a matrix of ``dtype`` in a
    program lowered for ``platform``: the rule of the module docstring, for
    whoever records the choice (band_solve.KsetSolver.plan)."""
    reduced = jnp.dtype(dtype).kind == "c" and platform == "tpu"
    return FORM_TRIDIAGONAL_REAL if reduced else FORM_LIBRARY


def _library(a):
    with jax.named_scope("eigh_kernel"):
        e, v = jnp.linalg.eigh(a)
    return e, v


def tridiagonalize(a):
    """(q, d, e) of one Hermitian matrix a [n, n]: a = q T q^H with q unitary
    and T Hermitian tridiagonal, real diagonal d [n] and subdiagonal
    e [n - 1] (T[k + 1, k] = e[k], complex). Step k reflects column k's
    entries below the diagonal onto the subdiagonal; a column whose tail
    below the subdiagonal is exactly zero is left alone (tau = 0, the identity:
    the parked and projected-out directions of _rayleigh_ritz and a solve's
    zero P block produce such columns), and its subdiagonal entry keeps its
    phase, which ``_phases`` takes out afterwards."""
    n = a.shape[-1]
    idx = jnp.arange(n, dtype=jnp.int32)
    zero = jnp.zeros((), a.dtype)

    def step(k, carry):
        a, q = carry
        col = jax.lax.dynamic_index_in_dim(a, k, axis=1, keepdims=False)
        below = idx > k
        sub = idx == k + 1
        alpha = jnp.sum(jnp.where(sub, col, zero))
        tail2 = jnp.sum(jnp.where(
            below & ~sub, jnp.real(col) ** 2 + jnp.imag(col) ** 2, 0.0))
        absa = jnp.abs(alpha)
        xnorm = jnp.sqrt(tail2 + absa * absa)
        phase = jnp.where(absa > 0, alpha / jnp.where(absa > 0, absa, 1.0), 1.0)
        # v = x + phase |x| e_{k+1}: no cancellation in its leading entry
        v = jnp.where(below, col, zero) + jnp.where(sub, phase * xnorm, zero)
        vc = jnp.conj(v)
        # H = 1 - tau v v^H, tau = 2 / v^H v = 1 / (|x| (|x| + |alpha|)): a
        # quotient, which is IEEE on the TPU where rsqrt is not (and XLA
        # turns 1 / sqrt into rsqrt): a tau off by 1e-6 is a reflector that
        # is not unitary by as much, 76 times over
        live = tail2 > 0
        tau = jnp.where(live, 1.0 / jnp.where(live, xnorm * (xnorm + absa), 1.0), 0.0)
        # H A H = A - (v w^H + w v^H), w = p - (tau / 2) (v^H p) v, p = tau A v
        p = tau * jnp.sum(a * v[None, :], axis=1)
        w = p - (0.5 * tau * jnp.real(jnp.sum(vc * p))) * v
        a = a - (v[:, None] * jnp.conj(w)[None, :] + w[:, None] * vc[None, :])
        # Q H = Q - (tau Q v) v^H
        qv = tau * jnp.sum(q * v[None, :], axis=1)
        q = q - qv[:, None] * vc[None, :]
        return a, q

    a, q = jax.lax.fori_loop(0, max(n - 2, 0), step, (a, jnp.eye(n, dtype=a.dtype)))
    d = jnp.real(jnp.diagonal(a))
    e = jnp.diagonal(a, offset=-1)
    return q, d, e


def _phases(e):
    """The unit diagonal D [n] that makes D^H T D real with a non-negative
    subdiagonal |e|: D[0] = 1, D[k + 1] = D[k] e[k] / |e[k]|; a zero entry
    takes phase 1."""
    ae = jnp.abs(e)
    ph = jnp.where(ae > 0, e / jnp.where(ae > 0, ae, 1.0), 1.0)
    dd = jnp.concatenate([jnp.ones((1,), e.dtype), jnp.cumprod(ph)])
    return dd / jnp.abs(dd)  # a product of n unit numbers drifts by n eps


def _tridiagonal_real_one(a):
    # eigh_reduce / eigh_kernel: the names a capture's table reads the two
    # halves by (obs/device_scopes.py); metadata only
    with jax.named_scope("eigh_reduce"):
        a = 0.5 * (a + jnp.conj(a.T))  # the library's call symmetrises too
        # a power of two on the scale of the matrix: the reflectors' norms
        # are sums of squares, which a tiny or a huge matrix would flush or
        # overflow
        amax = jnp.maximum(jnp.max(jnp.abs(jnp.real(a))),
                           jnp.max(jnp.abs(jnp.imag(a))))
        _, ex = jnp.frexp(amax)
        scale = jnp.ldexp(jnp.ones((), amax.dtype), ex)
        q, d, e = tridiagonalize(a / scale)
    with jax.named_scope("eigh_kernel"):
        ae = jnp.abs(e)
        t = jnp.diag(d) + jnp.diag(ae, 1) + jnp.diag(ae, -1)
        ev, y = jnp.linalg.eigh(t, symmetrize_input=False)
        qd = q * _phases(e)[None, :]
        hi = jax.lax.Precision.HIGHEST
        v = jax.lax.complex(jnp.matmul(jnp.real(qd), y, precision=hi),
                            jnp.matmul(jnp.imag(qd), y, precision=hi))
        return ev * scale, v


def eigh_tridiagonal_real(a):
    """The reduction form for complex Hermitian a [..., n, n], on any
    backend: (eigenvalues ascending [..., n], eigenvectors [..., n, n])."""
    fn = _tridiagonal_real_one
    for _ in range(a.ndim - 2):
        fn = jax.vmap(fn)
    return fn(a)


def eigh(a):
    """(eigenvalues ascending, eigenvectors) of the Hermitian matrix (or
    batch of matrices) a: the module docstring's rule."""
    if form(a.dtype, "tpu") == FORM_LIBRARY:  # the library's on every backend
        return _library(a)
    return jax.lax.platform_dependent(
        a, tpu=eigh_tridiagonal_real, default=_library)
