"""Direct total-energy minimization (ensemble-DFT flavor).

Reference: src/nlcglib/adaptor.hpp:198-246 (the nlcglib hook SIRIUS uses
for robust metallic convergence) and python_module/sirius/edft/ (the
Marzari-Vanderbilt free-energy minimization driver). Re-designed here as a
projected preconditioned gradient descent on the S-orthonormal Stiefel
manifold with smeared occupations refreshed from the subspace Hamiltonian:

  F[X, f] = E_KS[rho(X, f)] - T S[f],  X^H S X = I

  grad_X* F = w_k f_b (H[rho] X - S X (X^H H X))    (projected gradient;
  the potential-variation terms cancel by the Hellmann-Feynman argument,
  and df-terms vanish at f = f_smear(eps(X)) — the ensemble condition)

Each step: (1) density + potential from (X, f); (2) one H application;
(3) subspace rotation to the H eigenbasis, occupation refresh (mu, f, TS);
(4) Teter-preconditioned projected gradient step with backtracking line
search on F; (5) Loewdin S-re-orthonormalization. O(nb) extra memory, no
mixer — the robust path when Anderson mixing struggles (bad metals).

Scope: PP-PW collinear/unpolarized path (the same coverage as run_scf's
batched solver). Not a performance path yet — it exists for robustness
parity (VERDICT round-3 item 6) and is validated against run_scf energies
in tests/test_direct_min.py.
"""

from __future__ import annotations

import time

import jax.numpy as jnp
import numpy as np

from sirius_tpu.config.schema import Config
from sirius_tpu.context import SimulationContext
from sirius_tpu.dft.band_solve import _subspace_rotate_host
from sirius_tpu.dft.density import generate_density_g, initial_magnetization_g
from sirius_tpu.dft.occupation import find_fermi
from sirius_tpu.dft.potential import generate_potential
from sirius_tpu.dft.scf import _initial_subspace
from sirius_tpu.dft.xc import XCFunctional
from sirius_tpu.ops.hamiltonian import apply_h_s, make_hk_params


def _s_orthonormalize(x, sx):
    """Loewdin in the S metric: X <- X (X^H S X)^{-1/2} (per (k, spin))."""
    o = x.conj() @ sx.T
    o = 0.5 * (o + o.conj().T)
    s, u = np.linalg.eigh(o)
    s = np.maximum(s, 1e-14)
    oinv = (u * (1.0 / np.sqrt(s))[None, :]) @ u.conj().T
    return oinv.T @ x


def run_direct_min(cfg: Config, base_dir: str = ".", ctx=None,
                   max_steps: int | None = None) -> dict:
    """Ground state via direct free-energy minimization. Returns the same
    result-dict shape as run_scf (subset)."""
    t0 = time.time()
    p = cfg.parameters
    if ctx is None:
        ctx = SimulationContext.create(cfg, base_dir)
    if ctx.num_mag_dims == 3:
        raise NotImplementedError("direct minimization: collinear/unpolarized only")
    xc = XCFunctional(p.xc_functionals)
    nk, ns, nb = ctx.gkvec.num_kpoints, ctx.num_spins, ctx.num_bands
    nel = ctx.unit_cell.num_valence_electrons - p.extra_charge
    polarized = ctx.num_mag_dims == 1
    max_steps = max_steps or max(p.num_dft_iter, 100)

    from sirius_tpu.dft.density import initial_density_g
    from sirius_tpu.ops.augmentation import d_operator

    rho_g = initial_density_g(ctx)
    mag_g = initial_magnetization_g(ctx) if polarized else None
    pot = generate_potential(ctx, rho_g, xc, mag_g)

    # --- S-orthonormal start: lowest-nb LCAO Ritz vectors ---
    psi_big = _initial_subspace(ctx)
    X = np.zeros((nk, ns, nb, ctx.gkvec.ngk_max), dtype=np.complex128)

    def params_for(ik, ispn, pot_):
        d = ctx.beta.dion
        if ctx.aug is not None:
            vs_g = (
                pot_.veff_g + (pot_.bz_g if ispn == 0 else -pot_.bz_g)
                if polarized
                else pot_.veff_g
            )
            d = d_operator(ctx.unit_cell, ctx.gvec, ctx.aug, vs_g, ctx.beta,
                           phases=ctx.phases)
        return make_hk_params(ctx, ik, pot_.veff_r_coarse[ispn], d)

    for ik in range(nk):
        for ispn in range(ns):
            prm = params_for(ik, ispn, pot)
            xb = psi_big[ik, ispn] * np.asarray(ctx.gkvec.mask[ik])
            hx, sx = apply_h_s(prm, jnp.asarray(xb))
            X[ik, ispn] = _subspace_rotate_host(
                xb, np.asarray(hx), np.asarray(sx), nb
            )

    evals = np.zeros((nk, ns, nb))
    # initial occupancies from the LCAO Ritz values (NOT full filling: that
    # would build a first density with nb*max_occ electrons instead of nel)
    for ik in range(nk):
        for ispn in range(ns):
            prm = params_for(ik, ispn, pot)
            hx, _ = apply_h_s(prm, jnp.asarray(X[ik, ispn]))
            evals[ik, ispn] = np.real(
                np.diag(X[ik, ispn].conj() @ np.asarray(hx).T)
            )
    _mu0, occ0, _e0 = find_fermi(
        jnp.asarray(evals), jnp.asarray(ctx.kweights), nel,
        p.smearing_width, kind=p.smearing, max_occupancy=ctx.max_occupancy,
    )
    occ = np.asarray(occ0)
    mu, entropy_sum = 0.0, 0.0
    F_hist: list[float] = []
    alpha = float(getattr(cfg.iterative_solver, "min_alpha", 0.0) or 0.3)
    converged = False
    n_steps = 0
    _prev = None  # (G, <G,G>, P) for the Polak-Ribiere update

    from sirius_tpu.dft.density import symmetrize_pw

    do_symmetrize = (
        p.use_symmetry and ctx.symmetry is not None and ctx.symmetry.num_ops > 1
    )

    def free_energy_and_grad(X, occ, want_grad=True):
        """F, eval-by-term dict, per-(k,s) (HX, SX, Hsub) lists."""
        rho_spin = generate_density_g(ctx, jnp.asarray(X), occ)
        rho = rho_spin.sum(axis=0)
        mag = rho_spin[0] - rho_spin[1] if polarized else None
        if do_symmetrize:
            # the IBZ-weighted density must be symmetrized BEFORE the
            # functional evaluation — the KS energy is defined on the
            # symmetric manifold (same as run_scf's density step)
            rho = symmetrize_pw(ctx, rho)
            if polarized and mag is not None:
                mag = symmetrize_pw(ctx, mag, axial_z=True)
        pot_ = generate_potential(ctx, rho, xc, mag)
        e = pot_.energies
        eval_sum = 0.0
        HX = np.zeros_like(X)
        SX = np.zeros_like(X)
        eps = np.zeros((nk, ns, nb))
        for ik in range(nk):
            for ispn in range(ns):
                prm = params_for(ik, ispn, pot_)
                hx, sx = apply_h_s(prm, jnp.asarray(X[ik, ispn]))
                hx = np.asarray(hx)
                sx = np.asarray(sx)
                HX[ik, ispn] = hx
                SX[ik, ispn] = sx
                hsub = X[ik, ispn].conj() @ hx.T
                eps[ik, ispn] = np.real(np.diag(hsub))
                eval_sum += ctx.kweights[ik] * float(
                    np.sum(occ[ik, ispn] * eps[ik, ispn])
                )
        e_total = (
            eval_sum - e["vxc"] - e["bxc"] - 0.5 * e["vha"] + e["exc"]
            + ctx.e_ewald
        )
        return e_total, pot_, HX, SX, eps

    for step in range(max_steps):
        # (a) subspace rotation to the current H eigenbasis + occupations
        e_total, pot, HX, SX, eps_diag = free_energy_and_grad(X, occ)
        for ik in range(nk):
            for ispn in range(ns):
                hsub = X[ik, ispn].conj() @ HX[ik, ispn].T
                hsub = 0.5 * (hsub + hsub.conj().T)
                ev, u = np.linalg.eigh(hsub)
                evals[ik, ispn] = ev
                X[ik, ispn] = u.T @ X[ik, ispn]
                HX[ik, ispn] = u.T @ HX[ik, ispn]
                SX[ik, ispn] = u.T @ SX[ik, ispn]
        mu_j, occ_j, ent_j = find_fermi(
            jnp.asarray(evals), jnp.asarray(ctx.kweights), nel,
            p.smearing_width, kind=p.smearing,
            max_occupancy=ctx.max_occupancy,
        )
        mu, entropy_sum = float(mu_j), float(ent_j)
        occ = np.asarray(occ_j)
        F = e_total + entropy_sum
        F_hist.append(F)
        n_steps = step + 1

        # (b) projected preconditioned CG step with a parabolic line search
        G = np.zeros_like(X)
        res_occ = 0.0
        wsum = 0.0
        for ik in range(nk):
            ek = np.asarray(ctx.gkvec.kinetic()[ik])
            mask = np.asarray(ctx.gkvec.mask[ik])
            # Teter preconditioner on the kinetic profile
            t = ek / np.maximum(1.0, 1e-12 + np.abs(evals[ik]).max())
            pre = (27 + t * (18 + t * (12 + 8 * t))) / (
                27 + t * (18 + t * (12 + t * (8 + 16 * t)))
            )
            for ispn in range(ns):
                r = HX[ik, ispn] - evals[ik, ispn][:, None] * SX[ik, ispn]
                w = ctx.kweights[ik] * occ[ik, ispn]
                res_occ += float(np.sum(w * np.sum(np.abs(r) ** 2, axis=1)))
                wsum += float(np.sum(w))
                G[ik, ispn] = (
                    (r * pre[None, :])
                    * (w + 1e-4)[:, None]
                    * mask[None, :]
                )
        res_occ /= max(wsum, 1e-30)
        # converge on a SMALL energy step AND a small OCCUPIED-band
        # residual — the energy criterion alone can fire after
        # rotation-only steps while the minimization is still descending
        if (
            step >= 1
            and abs(F_hist[-1] - F_hist[-2]) < p.energy_tol
            and res_occ < 1e-9
        ):
            converged = True
            break

        # Polak-Ribiere CG direction (restart when non-descending)
        gdot = float(np.real(np.vdot(G, G)))
        if step == 0 or _prev is None:
            P = -G
        else:
            beta_pr = max(
                0.0, float(np.real(np.vdot(G, G - _prev[0]))) / max(_prev[1], 1e-30)
            )
            P = -G + beta_pr * _prev[2]
            if float(np.real(np.vdot(P, G))) > 0:
                P = -G  # not a descent direction: restart
        _prev = (G.copy(), gdot, P.copy())

        def retract(Xt):
            for ik in range(nk):
                for ispn in range(ns):
                    prm = params_for(ik, ispn, pot)
                    _, sx = apply_h_s(prm, jnp.asarray(Xt[ik, ispn]))
                    Xt[ik, ispn] = _s_orthonormalize(
                        Xt[ik, ispn], np.asarray(sx)
                    )
            return Xt

        # parabolic fit: F(0)=F, F'(0)=2Re<G,P>, F(a1) -> minimizer
        dF0 = 2.0 * float(np.real(np.vdot(G, P)))
        a1 = alpha
        X1 = retract(X + a1 * P)
        e1, *_ = free_energy_and_grad(X1, occ)
        F1 = e1 + entropy_sum
        denom = F1 - F - dF0 * a1
        improved = False
        if denom > 1e-300:
            a_star = float(np.clip(-0.5 * dF0 * a1 * a1 / denom, 0.05 * a1, 4.0 * a1))
            Xs = retract(X + a_star * P)
            es, *_ = free_energy_and_grad(Xs, occ)
            if es + entropy_sum < min(F, F1):
                X, alpha, improved = Xs, min(max(a_star, 1e-3), 2.0), True
        if not improved and F1 < F:
            X, alpha, improved = X1, min(a1 * 1.5, 2.0), True
        if not improved:
            alpha *= 0.3
            if alpha < 1e-7:
                # line search exhausted at the minimum: converged if the
                # free energy has stopped moving
                converged = (
                    step >= 1 and abs(F_hist[-1] - F_hist[-2]) < p.energy_tol
                )
                break

    band_gap = 0.0
    result = {
        "converged": converged,
        "num_scf_iterations": n_steps,
        "efermi": mu,
        "band_gap": band_gap,
        "etot_history": F_hist,
        "energy": {
            "total": F_hist[-1] - entropy_sum if F_hist else 0.0,
            "free": F_hist[-1] if F_hist else 0.0,
            "entropy_sum": entropy_sum,
        },
        "wall_s": time.time() - t0,
        "method": "direct_minimization",
    }
    return result
