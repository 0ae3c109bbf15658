"""Blocked iterative eigensolver for (H, S), fixed-shape and jit-able.

The reference uses a growing-subspace block Davidson with locking and
restarts (src/hamiltonian/davidson.hpp:107-856). Growing subspaces mean
dynamic shapes — poison for XLA — so the TPU design is a fixed-block
LOBPCG-style iteration with a constant 3*nb subspace [X, K R, P]:

  1. R = H X - eval S X of every band (NO LOCK below)
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
full 3nb subspace each step).

THE TRIP COUNT IS DYNAMIC. ``num_steps`` (iterative_solver.num_steps) is the
maximum, as in the reference: both loops are ``lax.while_loop``s that end when
no band is unconverged (davidson.hpp:613-725). A band is converged, with
``by_energy`` (iterative_solver.converge_by_energy, the reference's default),
when the step moved its eigenvalue by less than ``res_tol``, which the SCF
loop starts at iterative_solver.energy_tolerance and tightens with the
density residual (dft/mixer.schedule_res_tol); without it, when its residual
norm is under ``res_tol``. Either bar is floored at what the working
precision resolves, TOL_FLOOR_EPS * eps * max(1, max|e|) (1.9e-6 in float32,
3.6e-15 in float64 for |e| <= 1): a test below the resolution passes by luck
only, and steps taken there add rounding noise to the block (in float32 the
exit residuals of 20 steps were ten times those of 5, PERF.md section 6,
PR 37). The predicate lives on the device: no host sync. A set of
problems (a k-set, its spins) is solved by ONE pair of loops whose stages
are vmapped over the set (``stages``, ``solve``; parallel/batched.py): the
predicate is a scalar, "some band of some lane is unconverged", and the set
takes its slowest lane's steps (HOLD below: a finished lane waits unchanged).
The loops themselves are never vmapped: JAX's
batching rule would make the predicate ``any`` and put a select that holds
the finished lanes around every carried block, and that program did not come
back in 400 s on the chip at si2-k444-us's size (36 k-points, the complex
subspace; PERF.md section 6, PR 37). Inside a ``shard_map`` over k each
device runs its own loops and leaves on its own k-points.
The solve returns the steps and chunks it ran; ``apply_blocks`` /
``num_eigh`` turn them into the H applications and eigenproblems that ran.

NO LOCK. Until PR 37 a converged band's new direction was zeroed (a soft
lock); in float32 the residual bar was never met, so on the chip the lock
had never engaged. With the eigenvalue rule it did, in every solve, and its
zero rows are directions _rayleigh_ritz has to project out and park: on the
chip the 54-atom Gamma cell (648-row subspaces, 100 and more zero rows of
them) then took all 20 steps of every solve and did not converge in 22 SCF
iterations (PERF.md section 6, PR 37). So inside a problem that still has an
unconverged band every band keeps its new direction: a step is the step the
static loop took.

HOLD. A problem whose bands have ALL converged is not stepped any further:
the step replaces its Ritz coefficients by the identity, so X, H X and S X
stay as they are to the bit and P becomes zero, while the set's loop runs on
for its other lanes. A finished problem left to step on is not harmless: its
new block is rounding noise normalised, and in float64 a k-point that idled
for 25 steps beside a slow one lost its two top bands (residual 2e-11 ->
1e-1 in one step, back to 8e-6 by step 60 on another eigenpair 1.3e-2 Ha
up; tests/test_checksums.py's [2,2,2] deck, PERF.md section 6, PR 37). Held,
a lane's answer is its own solve's whatever its neighbours need, so one
device and a mesh, whose devices each leave on their own k-points, give the
same bands to the bit. The select is on the 3nb x nb coefficients, never on
a block, and a held problem stays held: its solve has ended.

ONE SUBSPACE. The subspace matrices V^H H V and V^H S V are complex
Hermitian for every k-set, whatever its k-points; they are real only at
Gamma, through ops/gamma.py's packed form of the block.
"""

from __future__ import annotations

from functools import partial
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

from sirius_tpu.solvers.subspace_eigh import eigh

# refresh cadence of the carried H X / H P blocks; scf.py's H-application
# counters derive from this, keep them in sync via this constant
REFRESH_EVERY = 5


# the convergence bar's floor in units of eps * max(1, max|e|): an eigenvalue
# or a residual norm of the working precision is not resolved below it
TOL_FLOOR_EPS = 16.0


def max_chunks(num_steps: int, refresh_every: int = REFRESH_EVERY) -> int:
    """Chunks of a davidson() call that runs all of its ``num_steps``."""
    return -(-num_steps // refresh_every)


def apply_blocks(steps: int, chunks: int, nb: int):
    """The H applications of one davidson() call that ran ``steps`` steps in
    ``chunks`` chunks, as (rows, times) pairs: 2nb rows at every chunk
    boundary ([X; P]; in the first chunk P is zero, and applied to all the
    same), nb rows per step for the new block and nb on exit. The one source
    of num_applies and of the box count."""
    return ((2 * nb, chunks), (nb, steps + 1))


def num_applies(steps: int, chunks: int, nb: int) -> int:
    """H-applications (in band rows) of one davidson() call."""
    return sum(rows * times for rows, times in apply_blocks(steps, chunks, nb))


def num_eigh(steps: int) -> int:
    """Subspace eigenproblems of one davidson() call: the overlap and the
    reduced H of every step's _rayleigh_ritz, and ortho's Gram matrix."""
    return 2 * steps + 1


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


def count_solve(counters, ran, nb: int, copies: int = 1,
                rows_per_box: int = 1, components: int = 1,
                complex_subspace: bool = True) -> None:
    """Book one band solve of a set from what its loops ran: ``ran`` is the
    fetched (steps, chunks) of every loop of the solve (an array [..., 2] or
    a list of them; a k-set's rows are a k-point each), ``copies`` the
    lanes behind a row. H applications and boxes through count_applies,
    num_subspace_eigh, and num_davidson_steps: the steps of the solve's
    longest loop. ``complex_subspace``: the subspace matrices are complex
    Hermitian (every solve but the packed-real Gamma one), so each
    eigenproblem is also one of num_complex_subspace_eigh: those a program
    lowered for the TPU puts through the reduction (subspace_eigh.form
    ``tridiagonal_real``); an explicit 0 on the packed-real subspace."""
    import numpy as np

    rows = np.asarray(ran).reshape(-1, 2)
    for steps, chunks in rows.tolist():
        count_applies(counters, apply_blocks(steps, chunks, nb), copies=copies,
                      rows_per_box=rows_per_box, components=components)
        solved = copies * num_eigh(steps)
        counters["num_subspace_eigh"] += solved
        counters["num_complex_subspace_eigh"] += solved * complex_subspace
    counters["num_davidson_steps"] += int(rows[:, 0].max())


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


def subspace_rotate(x, hx, sx, nb: int, mask=None):
    """Lowest-nb Ritz vectors of the trial block x given carried H x / S x:
    shared by the LCAO initialize-subspace paths (serial host and batched
    device); pure jnp, callable inside or outside jit."""
    hsub = x.conj() @ hx.T
    ssub = x.conj() @ sx.T
    hsub = 0.5 * (hsub + hsub.conj().T)
    ssub = 0.5 * (ssub + ssub.conj().T)
    _, c = _rayleigh_ritz(hsub, ssub, nb)
    xn = c.T @ x
    if mask is not None:
        xn = xn * mask
    nrm = jnp.real(jnp.sum(xn.conj() * (c.T @ sx), axis=1))
    return xn / jnp.sqrt(jnp.maximum(nrm, 1e-30))[:, None]


def _precondition(r: jax.Array, h_diag: jax.Array, o_diag: jax.Array, eval_: jax.Array):
    """Reference apply_preconditioner (residuals_aux.cu): smooth Teter-like."""
    p = h_diag[None, :] - eval_[:, None] * o_diag[None, :]
    p = 0.5 * (1.0 + p + jnp.sqrt(1.0 + (p - 1.0) ** 2))
    return r / p


class Stages(NamedTuple):
    """One eigenproblem's solve as the functions its loops call (solve()):
    start(x0) -> x; refresh(x, p) -> (hx, sx, hp, sp); step(x, hx, sx, p,
    hp, sp, conv) -> the same seven, conv [nb] the bands the step leaves
    converged; finish(x) -> (evals, x, rnorm)."""

    start: Callable
    refresh: Callable
    step: Callable
    finish: Callable


def stages(apply_fn, params, h_diag, o_diag, mask, res_tol,
           by_energy: bool = True) -> Stages:
    """The Stages of one (H, S) problem; davidson()'s arguments. Pure
    functions of arrays: a set's solve vmaps each over its lanes."""
    def ritz(x, hx, sx):
        # <x|S|x> and the Rayleigh quotients of the rows, off carried blocks.
        # Guard the quotient: a rank-deficient Rayleigh-Ritz (heavy Kramers
        # degeneracy) can hand back a ~zero Ritz vector, and a 0/0 here
        # NaN-poisons the whole loop (observed: Au SO spinor solve)
        den = jnp.real(jnp.sum(x.conj() * sx, axis=1))
        return den, jnp.real(jnp.sum(x.conj() * hx, axis=1)) / jnp.where(
            jnp.abs(den) > 1e-30, den, 1.0)

    def start(x0):
        with jax.named_scope("davidson_ortho"):
            x = x0 * mask
            g = (x * mask) @ (x * mask).conj().T
            s, u = eigh(g)
            good = s > 50.0 * jnp.finfo(g.real.dtype).eps * jnp.max(jnp.abs(s))
            t = u * jnp.where(
                good, jax.lax.rsqrt(jnp.where(good, s, 1.0)), 0.0)[None, :]
            return t.conj().T @ x

    def refresh(x, p):
        # a chunk's boundary: a true H/S application to [X; P]. In the first
        # chunk P is exactly zero and H is applied to nb rows of zeros:
        # choosing the X-only application there by lax.cond gave non-finite
        # fields on the TPU once the solve was vmapped over k (PERF.md,
        # PR 27)
        nb = x.shape[0]
        with jax.named_scope("davidson_hpsi"):
            hxp, sxp = apply_fn(params, jnp.concatenate([x, p], axis=0))
        return hxp[:nb], sxp[:nb], hxp[nb:], sxp[nb:]

    def step(x, hx, sx, p, hp, sp, conv):
        nb = x.shape[0]
        # Ritz values of current block (H X, S X carried, no re-application).
        # The named_scope blocks tag the emitted HLO (every instruction's
        # op_name) with the stage names: the four obs/costs.py models and
        # davidson_residual for the new block's residual and
        # preconditioning. A trace capture (obs/trace.py) reads them back
        # from the executable it holds and records the device seconds of
        # each in its trace.scopes table (obs/device_scopes.py, whose
        # SCOPES lists every name) — host spans cannot cut inside this jit.
        with jax.named_scope("davidson_residual"):
            _, evals = ritz(x, hx, sx)
            r = (hx - evals[:, None] * sx) * mask
            # every band keeps its new direction, converged or not (NO LOCK
            # above)
            w = _precondition(r, h_diag, o_diag, evals) * mask
            # project out X and normalize rows: keeps the 3nb overlap
            # matrix well-conditioned so the rank-revealing cutoff doesn't
            # stall convergence near the solution
            w = w - (w @ x.conj().T) @ x
            w = w / jnp.maximum(jnp.linalg.norm(w, axis=1, keepdims=True), 1e-30)
        # the ONLY H/S application of the step: the new block
        with jax.named_scope("davidson_hpsi"):
            hw, sw = apply_fn(params, w)
        with jax.named_scope("davidson_inner"):
            v = jnp.concatenate([x, w, p], axis=0)  # (3nb, ng)
            hv = jnp.concatenate([hx, hw, hp], axis=0)
            sv = jnp.concatenate([sx, sw, sp], axis=0)
            hsub = v.conj() @ hv.T
            ssub = v.conj() @ sv.T
            hsub = 0.5 * (hsub + hsub.conj().T)
            ssub = 0.5 * (ssub + ssub.conj().T)
        with jax.named_scope("davidson_rr"):
            _, c = _rayleigh_ritz(hsub, ssub, nb)
            # HOLD above: a problem whose bands have all converged keeps
            # its block to the bit while the set's loop goes on
            c = jnp.where(
                jnp.all(conv), jnp.eye(3 * nb, nb, dtype=c.dtype), c)
        with jax.named_scope("davidson_rotate"):
            # X' = V C and the carried H X' = (H V) C, S X' = (S V) C exactly
            xn = (c.T @ v) * mask
            hxn = (c.T @ hv) * mask
            sxn = (c.T @ sv) * mask
            # new search direction: the non-X part of the update
            # (row-normalized, with the same scale applied to the carried
            # H P / S P)
            cp = c.at[:nb, :].set(0.0)
            pn = (cp.T @ v) * mask
            pscale = 1.0 / jnp.maximum(
                jnp.linalg.norm(pn, axis=1, keepdims=True), 1e-30)
            pn = pn * pscale
            hpn = (cp.T @ hv) * mask * pscale
            spn = (cp.T @ sv) * mask * pscale
        with jax.named_scope("davidson_residual"):
            # which bands this step leaves converged: the reference's rule
            # (the eigenvalue's move in the step) or the new block's
            # residual norms. The eigenvalue is the Rayleigh quotient of the
            # rotated block, not the eigensolver's own value: an eigensolver's
            # error is first order in eps x the norm of the 3nb matrix (the
            # TPU's: 1e-5 of it), a quotient's second order
            _, evals_n = ritz(xn, hxn, sxn)
            if by_energy:
                moved = jnp.abs(evals_n - evals)
            else:
                moved = jnp.sqrt(jnp.real(jnp.sum(
                    jnp.abs((hxn - evals_n[:, None] * sxn) * mask) ** 2,
                    axis=1)))
            # the bar, floored at the precision's resolution
            floor = TOL_FLOOR_EPS * jnp.finfo(x.dtype).eps
            # a held problem stays held (HOLD above)
            conv = jnp.all(conv) | (moved < jnp.maximum(
                jnp.asarray(res_tol, moved.dtype),
                floor * jnp.maximum(1.0, jnp.max(jnp.abs(evals_n)))))
        return xn, hxn, sxn, pn, hpn, spn, conv

    def finish(x):
        # fresh application for the exit values: the carried H X accumulates
        # linear-combination rounding (matters in c64)
        with jax.named_scope("davidson_hpsi"):
            hx, sx = apply_fn(params, x)
        with jax.named_scope("davidson_residual"):
            den, evals = ritz(x, hx, sx)
            rnorm = jnp.sqrt(jnp.real(jnp.sum(
                jnp.abs(hx - evals[:, None] * sx) ** 2, axis=1)))
            # normalize to <x|S|x> = 1 (den floored: a zero Ritz vector must
            # come back as a zero row, not NaN/Inf)
            x = x / jnp.sqrt(jnp.maximum(den, 1e-30))[:, None]
        return evals, x, rnorm

    return Stages(start, refresh, step, finish)


def solve(st: Stages, x0, num_steps: int,
          refresh_every: int = REFRESH_EVERY):
    """The loops of a solve, THE TRIP COUNT above. ``st`` holds one
    problem's stages (davidson()) or a set's, each vmapped over the set's
    lanes with x0 and every block [lanes..., nb, ng] (parallel/batched.py):
    the set then has ONE trip count, its slowest lane's, and the predicate
    is a scalar whatever the lanes are. Returns finish()'s values and ran =
    int32 (steps, chunks)."""

    def live(conv, n):
        # steps are left and some band (of some lane) is unconverged
        return (n < num_steps) & ~jnp.all(conv)

    def step(state):
        *blocks, n = state
        return (*st.step(*blocks), n + 1)

    def chunk(state):
        """One refresh boundary and the steps after it on the carried
        blocks: refresh_every of them, or fewer where the solve ends first."""
        x, p, conv, n, nchunks = state
        hx, sx, hp, sp = st.refresh(x, p)
        x, _, _, p, _, _, conv, n1 = jax.lax.while_loop(
            lambda s: (s[7] - n < refresh_every) & live(s[6], s[7]), step,
            (x, hx, sx, p, hp, sp, conv, n))
        return x, p, conv, n1, nchunks + 1

    # The chunks are ONE loop over one body that holds ONE loop over the
    # step, not a loop a chunk: the step holds the subspace eigensolver
    # twice, which above 256 rows the TPU expands into a program of its own
    # (QDWH divide and conquer), and a 648-row solve's four copies of it
    # made an executable of 215 MB that no compile cache of 192 MiB could
    # hold. The body is the same for every chunk, the first included. The
    # loops have no lax.cond (under vmap it is a select over both branches,
    # non-finite on the TPU: PERF.md, PR 27), only the while_loops' own
    # predicates, and those are scalars: the stages are vmapped, never the
    # loops (a while_loop vmapped over k gets a batched predicate and a
    # select around every carried block, and that program did not come back
    # on the chip: PERF.md section 6, PR 37).
    x = st.start(x0)
    zero = jnp.zeros((), jnp.int32)
    x, _, _, steps, chunks = jax.lax.while_loop(
        lambda s: live(s[2], s[3]), chunk,
        (x, jnp.zeros_like(x), jnp.zeros(x.shape[:-1], bool), zero, zero))
    return (*st.finish(x), jnp.stack([steps, chunks]))


@partial(jax.jit, static_argnames=(
    "apply_fn", "num_steps", "refresh_every", "by_energy"))
def davidson(
    apply_fn,  # (params, psi [nb, ng]) -> (h psi, s psi); a STABLE module-
    # level function — closures would retrace the jit per call site
    params,  # pytree of per-k Hamiltonian data (ops.hamiltonian.HkParams)
    x0: jax.Array,  # [nb, ng] initial guess
    h_diag: jax.Array,  # [ng] H diagonal (preconditioner)
    o_diag: jax.Array,  # [ng] S diagonal
    mask: jax.Array,  # [ng] valid-G mask
    num_steps: int = 20,  # the most steps a solve takes
    res_tol: float = 1e-2,
    refresh_every: int = REFRESH_EVERY,
    by_energy: bool = True,  # res_tol bars the eigenvalue's move in a step
    # (iterative_solver.converge_by_energy); False: the residual norm
):
    """Returns (eval [nb], X [nb, ng], res_norms [nb], ran [2]): ran holds
    the steps and the chunks the solve ran (int32), THE TRIP COUNT above."""
    st = stages(apply_fn, params, h_diag, o_diag, mask, res_tol,
                by_energy=by_energy)
    return solve(st, x0, num_steps, refresh_every)
