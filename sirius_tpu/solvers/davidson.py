"""Blocked iterative eigensolver for (H, S), fixed-shape and jit-able.

The reference uses a growing-subspace block Davidson with locking and
restarts (src/hamiltonian/davidson.hpp:107-856). Growing subspaces mean
dynamic shapes — poison for XLA — so the TPU design is a locked-block
LOBPCG-style iteration with a constant 3*nb subspace [X, K R, P]:

  1. R = H X - eval S X, soft-locked by convergence mask
  2. K R: Teter-style diagonal preconditioner (reference residuals_aux.cu
     apply_preconditioner: p = h_diag - e*o_diag; p <- (1+p+sqrt(1+(p-1)^2))/2)
  3. Rayleigh-Ritz on V = [X, KR, P] with a rank-revealing (eigh-based)
     overlap regularization instead of Cholesky — ill-conditioned subspace
     directions are projected out, not crashed on
  4. X' = V C_low, P' = V C_low minus the X-block contribution

Every step is dense batched linear algebra (MXU) + ONE H/S application to
the new preconditioned-residual block: H X and H P are carried through the
scan and updated by the same linear combinations as X and P (the reference
likewise applies H only to the newly-added subspace block per iteration,
davidson.hpp:751-801). In single precision the carried blocks drift and the
Rayleigh-Ritz step amplifies the inconsistency (variational feedback), so
every `refresh_every` steps the carried H X / H P are recomputed with a true
application (chunked scan, still ~3x fewer H applies than re-applying to the
full 3nb subspace each step). The iteration count is static (config
iterative_solver.num_steps).

REAL SUBSPACE (``theta_index``). Where H and S commute with an antiunitary
map Theta and every row of the block is Theta-real (Theta x = x), the
subspace matrices V^H H V and V^H S V are real symmetric: the Theta-real
vectors are a real vector space on which H and S act as real symmetric
operators. That is so at a time-reversal-invariant k-point (2k a reciprocal
lattice vector) for a real local potential and real D and Q, with
Theta x (G) = conj(x(-G - 2k)), complex conjugation of psi(r);
``theta_index`` is the slot of -G - 2k for every slot
(dft/band_solve.time_reversal_index). Given it, the eigensolver gets the
real parts. On the TPU that is another program: a complex Hermitian eigh is
expanded into Jacobi sweep loops of about a microsecond an operation, a real
symmetric one up to 256 rows is one kernel (PERF.md section 6, PR 31).

The block V = [X, W, P] has to be Theta-real exactly, not to rounding: what
lies outside the real space is invisible to the real Rayleigh-Ritz step and
is carried along by its coefficients all the same. The new block W is where
it comes in: W is a residual divided by its own norm, so near convergence it
is rounding noise of norm one, half of it outside the real space, and in
float32 the bands lost their orthonormality within one SCF (a total energy
1.2 Ha too low on the CPU backend). So W, and H W and S W as they leave the
operator, are replaced by their Theta-real parts (w + Theta w) / 2, a gather
each (it costs nothing measurable beside the transforms), and so is the
block that enters. X and P then stay Theta-real by themselves: they are
real combinations (`_combine`), masks and real scalings of Theta-real rows,
and slot -G - 2k sees the conjugate of the arithmetic slot G sees.
Projecting W alone was tried on the chip and is not enough there: 17 SCF
iterations for 13 and an energy 1.0e-4 Ha off where this form reads 1e-5
(PERF.md section 6, PR 31).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from sirius_tpu.solvers.subspace_eigh import eigh

# refresh cadence of the carried H X / H P blocks; scf.py's H-application
# counters derive from this, keep them in sync via this constant
REFRESH_EVERY = 5


def apply_blocks(num_steps: int, nb: int, refresh_every: int = REFRESH_EVERY):
    """The H applications of one davidson() call as (rows, times) pairs:
    2nb rows at every chunk boundary ([X; P]; in the first chunk P is zero,
    and applied to all the same), nb rows per step for the new block and
    nb on exit. The one source of num_applies and of the box count."""
    nchunks = -(-num_steps // refresh_every)
    return ((2 * nb, nchunks), (nb, num_steps + 1))


def num_applies(num_steps: int, nb: int, refresh_every: int = REFRESH_EVERY) -> int:
    """H-applications (in band rows) of one davidson() call."""
    return sum(rows * times
               for rows, times in apply_blocks(num_steps, nb, refresh_every))


def num_eigh(num_steps: int) -> int:
    """Subspace eigenproblems of one davidson() call: the overlap and the
    reduced H of every step's _rayleigh_ritz, and ortho's Gram matrix."""
    return 2 * num_steps + 1


def count_applies(counters, blocks, copies: int = 1, rows_per_box: int = 1,
                  components: int = 1) -> None:
    """Book `copies` times the H applications `blocks` ((rows, times) pairs)
    into the run's counters: band rows into num_loc_op_applied (the
    reference's counter) and, into num_fft_boxes, the complex 3-D box
    transforms the local operator ran for them: one inverse and one forward
    a box; a box a row (`components` of them for a spinor row), or
    ceil(rows / rows_per_box) boxes an application where several real bands
    share one (ops/gamma.ROWS_PER_BOX)."""
    for rows, times in blocks:
        counters["num_loc_op_applied"] += copies * times * rows
        counters["num_fft_boxes"] += (
            copies * times * 2 * components * -(-rows // rows_per_box))


def residual_health(rnorm, blowup: float = 1e2) -> tuple[float, bool]:
    """(max residual norm, healthy?) of a band solve's exit residuals —
    the band-solve sentinel of the SCF supervisor (dft/recovery.py). A
    non-finite or blown-up residual means the solver stagnated or the
    subspace collapsed; the supervisor then retries with a deeper subspace
    or falls back to dense diagonalization."""
    import numpy as np

    r = np.asarray(rnorm, dtype=np.float64)
    if r.size == 0:
        return 0.0, True
    rmax = float(np.max(r)) if np.all(np.isfinite(r)) else float("inf")
    return rmax, np.isfinite(rmax) and rmax <= blowup


def _rayleigh_ritz(hsub: jax.Array, ssub: jax.Array, nev: int):
    """Lowest-nev gen-EVP of a possibly rank-deficient subspace pair."""
    s, u = eigh(ssub)
    smax = jnp.max(jnp.abs(s))
    # rank cutoff must scale with the working precision: eigh noise sits at
    # ~eps*smax (1e-7 for c64), so a fixed 1e-13 would rsqrt-amplify noise
    # directions in single precision. The floor also bounds the rsqrt
    # amplification to ~3e5: directions barely above eps*smax get blended
    # with ~1e7 coefficients whose cancellation error feeds back through
    # the carried H X blocks and can blow the iteration up (observed with
    # exactly-degenerate Kramers pairs in the SO spinor solve)
    eps = jnp.finfo(ssub.real.dtype).eps
    good = s > jnp.maximum(50.0 * eps, 1e-11) * smax
    t = u * jnp.where(good, jax.lax.rsqrt(jnp.where(good, s, 1.0)), 0.0)[None, :]
    at = t.conj().T @ hsub @ t
    # park the projected-out directions just above the spectrum of the kept
    # block (its inf-norm bounds it). The shift must stay on the scale of
    # the problem: an eigensolver whose error is relative to the matrix norm
    # (the TPU's; measured with a fixed 1e6 in f32: Ritz values off by O(1))
    # otherwise loses the wanted eigenvalues under eps * shift
    shift = 1.0 + jnp.max(jnp.sum(jnp.abs(at), axis=1))
    at = at + jnp.diag(jnp.where(good, 0.0, shift).astype(at.dtype))
    e, y = eigh(at)
    c = t @ y
    return e[:nev], c[:, :nev]


def theta_real(a, theta_index):
    """The Theta-real part of every row of a block [..., ng]; the block as
    it is where the solve is complex (no index). Module docstring."""
    if theta_index is None:
        return a
    return 0.5 * (a + jnp.conj(a[..., theta_index]))


def _combine(c, v, theta_index):
    """c^T v for Ritz coefficients c [m, n] and a block v [m, ng]. With real
    coefficients (``theta_index``) it is two real products, of the real and
    of the imaginary parts: half the arithmetic, and Theta-real rows give
    Theta-real rows to the last bit, which a complex product with a zero
    imaginary part does not on the TPU (its three-product form rounds the
    imaginary part with the real one's size)."""
    if theta_index is None:
        return c.T @ v
    return jax.lax.complex(c.T @ jnp.real(v), c.T @ jnp.imag(v))


def _subspace_matrix(a, theta_index):
    """A subspace matrix as the eigensolver gets it: real where the block
    is Theta-real."""
    return a if theta_index is None else jnp.real(a)


def subspace_rotate(x, hx, sx, nb: int, mask=None, theta_index=None):
    """Lowest-nb Ritz vectors of the trial block x given carried H x / S x:
    shared by the LCAO initialize-subspace paths (serial host and batched
    device); pure jnp, callable inside or outside jit. With ``theta_index``
    the rows of x are Theta-real and so are the Ritz vectors."""
    x = theta_real(x, theta_index)
    hx, sx = theta_real(hx, theta_index), theta_real(sx, theta_index)
    hsub = _subspace_matrix(x.conj() @ hx.T, theta_index)
    ssub = _subspace_matrix(x.conj() @ sx.T, theta_index)
    hsub = 0.5 * (hsub + hsub.conj().T)
    ssub = 0.5 * (ssub + ssub.conj().T)
    _, c = _rayleigh_ritz(hsub, ssub, nb)
    xn = _combine(c, x, theta_index)
    if mask is not None:
        xn = xn * mask
    nrm = jnp.real(jnp.sum(xn.conj() * _combine(c, sx, theta_index), axis=1))
    return xn / jnp.sqrt(jnp.maximum(nrm, 1e-30))[:, None]


def _precondition(r: jax.Array, h_diag: jax.Array, o_diag: jax.Array, eval_: jax.Array):
    """Reference apply_preconditioner (residuals_aux.cu): smooth Teter-like."""
    p = h_diag[None, :] - eval_[:, None] * o_diag[None, :]
    p = 0.5 * (1.0 + p + jnp.sqrt(1.0 + (p - 1.0) ** 2))
    return r / p


@partial(jax.jit, static_argnames=("apply_fn", "num_steps", "refresh_every"))
def davidson(
    apply_fn,  # (params, psi [nb, ng]) -> (h psi, s psi); a STABLE module-
    # level function — closures would retrace the jit per call site
    params,  # pytree of per-k Hamiltonian data (ops.hamiltonian.HkParams)
    x0: jax.Array,  # [nb, ng] initial guess
    h_diag: jax.Array,  # [ng] H diagonal (preconditioner)
    o_diag: jax.Array,  # [ng] S diagonal
    mask: jax.Array,  # [ng] valid-G mask
    num_steps: int = 20,
    res_tol: float = 1e-6,
    refresh_every: int = REFRESH_EVERY,
    theta_index: jax.Array | None = None,  # [ng] int: REAL SUBSPACE above
):
    """Returns (eval [nb], X [nb, ng], res_norms [nb])."""
    nb = x0.shape[0]

    def apply_h_s(psi):
        hpsi, spsi = apply_fn(params, psi)
        return theta_real(hpsi, theta_index), theta_real(spsi, theta_index)

    def ortho(x):
        g = _subspace_matrix((x * mask) @ (x * mask).conj().T, theta_index)
        s, u = eigh(g)
        good = s > 50.0 * jnp.finfo(g.real.dtype).eps * jnp.max(jnp.abs(s))
        t = u * jnp.where(good, jax.lax.rsqrt(jnp.where(good, s, 1.0)), 0.0)[None, :]
        return _combine(t.conj(), x, theta_index)

    with jax.named_scope("davidson_ortho"):
        x = ortho(theta_real(x0 * mask, theta_index))

    def step(carry, _):
        x, hx, sx, p, hp, sp = carry
        # Ritz values of current block (H X, S X carried, no re-application).
        # Guard the quotient: a rank-deficient Rayleigh-Ritz (heavy Kramers
        # degeneracy + locking) can hand back a ~zero Ritz vector, and a
        # 0/0 here NaN-poisons the whole scan (observed: Au SO spinor solve)
        # The named_scope blocks tag the emitted HLO (every instruction's
        # op_name) with the stage names: the four obs/costs.py models and
        # davidson_residual for the new block's residual and
        # preconditioning. A trace capture (obs/trace.py) reads them back
        # from the executable it holds and records the device seconds of
        # each in its trace.scopes table (obs/device_scopes.py, whose
        # SCOPES lists every name) — host spans cannot cut inside this jit.
        with jax.named_scope("davidson_residual"):
            den = jnp.real(jnp.sum(x.conj() * sx, axis=1))
            evals = jnp.real(jnp.sum(x.conj() * hx, axis=1)) / jnp.where(
                jnp.abs(den) > 1e-30, den, 1.0
            )
            r = (hx - evals[:, None] * sx) * mask
            rnorm = jnp.sqrt(jnp.real(jnp.sum(jnp.abs(r) ** 2, axis=1)))
            conv = rnorm < res_tol
            w = jnp.where(conv[:, None], 0.0, _precondition(r, h_diag, o_diag, evals)) * mask
            # project out X and normalize rows: keeps the 3nb overlap
            # matrix well-conditioned so the rank-revealing cutoff doesn't
            # stall convergence near the solution
            w = theta_real(w - (w @ x.conj().T) @ x, theta_index)
            w = w / jnp.maximum(jnp.linalg.norm(w, axis=1, keepdims=True), 1e-30)
        # the ONLY H/S application of the step: the new block
        with jax.named_scope("davidson_hpsi"):
            hw, sw = apply_h_s(w)
        with jax.named_scope("davidson_inner"):
            v = jnp.concatenate([x, w, p], axis=0)  # (3nb, ng)
            hv = jnp.concatenate([hx, hw, hp], axis=0)
            sv = jnp.concatenate([sx, sw, sp], axis=0)
            hsub = _subspace_matrix(v.conj() @ hv.T, theta_index)
            ssub = _subspace_matrix(v.conj() @ sv.T, theta_index)
            hsub = 0.5 * (hsub + hsub.conj().T)
            ssub = 0.5 * (ssub + ssub.conj().T)
        with jax.named_scope("davidson_rr"):
            e, c = _rayleigh_ritz(hsub, ssub, nb)
        with jax.named_scope("davidson_rotate"):
            # X' = V C and the carried H X' = (H V) C, S X' = (S V) C exactly
            xn = _combine(c, v, theta_index) * mask
            hxn = _combine(c, hv, theta_index) * mask
            sxn = _combine(c, sv, theta_index) * mask
            # new search direction: the non-X part of the update
            # (row-normalized, with the same scale applied to the carried
            # H P / S P)
            cp = c.at[:nb, :].set(0.0)
            pn = _combine(cp, v, theta_index) * mask
            pscale = 1.0 / jnp.maximum(
                jnp.linalg.norm(pn, axis=1, keepdims=True), 1e-30)
            pn = pn * pscale
            hpn = _combine(cp, hv, theta_index) * mask * pscale
            spn = _combine(cp, sv, theta_index) * mask * pscale
        return (xn, hxn, sxn, pn, hpn, spn), rnorm

    def chunk(carry, steps):
        """One refresh boundary, a true H/S application to [X; P], and the
        `steps` steps after it on the carried blocks."""
        x, p = carry
        with jax.named_scope("davidson_hpsi"):
            hxp, sxp = apply_h_s(jnp.concatenate([x, p], axis=0))
        (x, _, _, p, _, _), _ = jax.lax.scan(
            step, (x, hxp[:nb], sxp[:nb], p, hxp[nb:], sxp[nb:]), None,
            length=steps,
        )
        return x, p

    # The chunks of refresh_every steps are ONE loop over one body, not one
    # loop each: the body holds the subspace eigensolver twice, which above
    # 256 rows the TPU expands into a program of its own (QDWH divide and
    # conquer), and a 648-row solve's four copies of it made an executable
    # of 215 MB that no compile cache of 192 MiB could hold. The body is the
    # same for every chunk, the first included: there P is exactly zero and
    # H is applied to nb rows of zeros (1 in 29 of a solve's applications).
    # Choosing the X-only application there by lax.cond gave non-finite
    # fields on the TPU once the solve was vmapped over k (PERF.md, PR 27).
    carry = (x, jnp.zeros_like(x))
    nfull, rest = divmod(num_steps, refresh_every)
    if nfull:
        carry, _ = jax.lax.scan(
            lambda c, _: (chunk(c, refresh_every), None), carry, None,
            length=nfull,
        )
    if rest:
        carry = chunk(carry, rest)
    x = carry[0]
    # fresh application for the exit values: the carried H X accumulates
    # linear-combination rounding (matters in c64)
    with jax.named_scope("davidson_hpsi"):
        hx, sx = apply_h_s(x)
    with jax.named_scope("davidson_residual"):
        den = jnp.real(jnp.sum(x.conj() * sx, axis=1))
        evals = jnp.real(jnp.sum(x.conj() * hx, axis=1)) / jnp.where(
            jnp.abs(den) > 1e-30, den, 1.0
        )
        rnorm = jnp.sqrt(jnp.real(jnp.sum(jnp.abs(hx - evals[:, None] * sx) ** 2, axis=1)))
        # normalize to <x|S|x> = 1 (den floored: a zero Ritz vector must
        # come back as a zero row, not NaN/Inf)
        x = x / jnp.sqrt(jnp.maximum(den, 1e-30))[:, None]
    return evals, x, rnorm
