"""Atomic forces for the PP-PW method.

Reference: src/geometry/force.cpp — total = vloc + ewald + core (NLCC) +
nonloc + us (augmentation) + usnl + scf_corr + hubbard contributions
(force.hpp:44-66), symmetrized over the space group.

All G-space sums are host-side numpy einsums over precomputed tables; the
k-dependent non-local part reuses the device beta tables with one extra
einsum per Cartesian direction (the reference generates separate gradient
beta projectors, beta_projectors_gradient.hpp — here the gradient is just
the analytic -i(G+k) factor).

Conventions: forces in Ha/bohr, Cartesian, one row per atom.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np
from scipy.special import erfc

from sirius_tpu.context import SimulationContext
from sirius_tpu.core.gvec import minus_phases
from sirius_tpu.dft.ewald import ewald_lambda
from sirius_tpu.dft.radial_tables import rho_core_form_factor, vloc_ff


def _form_factor_force(
    ctx: SimulationContext, field_g: np.ndarray, ff_fn, skip=lambda t: False
) -> np.ndarray:
    """Shared shell-form-factor force kernel:
    F_a = Re sum_G 4 pi conj(field(G)) ff_a(|G|) iG e^{-i G r_a}."""
    uc = ctx.unit_cell
    out = np.zeros((uc.num_atoms, 3))
    qshell = np.sqrt(ctx.gvec.shell_g2)
    for it, t in enumerate(uc.atom_types):
        if skip(t):
            continue
        ff = np.asarray(ff_fn(t, qshell))[ctx.gvec.shell_idx]
        for ia in uc.atoms_of_type(it):
            phase = np.exp(-2j * np.pi * (ctx.gvec.millers @ uc.positions[ia]))
            w = 4.0 * np.pi * np.conj(field_g) * ff * phase
            out[ia] = np.real(1j * (w[:, None] * ctx.gvec.gcart).sum(axis=0))
    return out


def forces_vloc(ctx: SimulationContext, rho_g: np.ndarray) -> np.ndarray:
    """Local-potential force (reference force.cpp calc_forces_vloc)."""
    return _form_factor_force(ctx, rho_g, vloc_ff(ctx.cfg.settings.pseudo_grid_cutoff))


def forces_core(ctx: SimulationContext, vxc_g: np.ndarray) -> np.ndarray:
    """NLCC force: core density against V_xc (reference calc_forces_core)."""
    return _form_factor_force(
        ctx, vxc_g, rho_core_form_factor, skip=lambda t: t.rho_core is None
    )


def forces_scf_corr(ctx: SimulationContext, rho_resid_g: np.ndarray) -> np.ndarray:
    """First-order correction for incomplete SCF: the local-potential force
    of the density residual rho_out - rho_in (reference calc_forces_scf_corr);
    vanishes at convergence."""
    return _form_factor_force(
        ctx, rho_resid_g, vloc_ff(ctx.cfg.settings.pseudo_grid_cutoff)
    )


def forces_ewald(ctx: SimulationContext) -> np.ndarray:
    """Point-ion Ewald forces (reference calc_forces_ewald)."""
    uc = ctx.unit_cell
    gv = ctx.gvec
    omega = uc.omega
    z = np.asarray([uc.atom_types[t].zn for t in uc.type_of_atom])
    lam = ewald_lambda(ctx.cfg.parameters.pw_cutoff, omega)
    natom = uc.num_atoms
    out = np.zeros((natom, 3))
    # G-space: F_a = (4 pi / Omega) z_a sum_G!=0 G e^{-G^2/4lam}/G^2
    #                Im[e^{-i G r_a} S(G)]
    g2 = gv.glen2[1:]
    # e^{+iG.r}, (ng-1, na): the context's table holds the conjugate
    phases = np.conj(minus_phases(ctx.phases, gv.millers, uc.positions)[1:])
    s = phases @ z
    w = np.exp(-g2 / (4 * lam)) / g2
    for ia in range(natom):
        # F_a = (4 pi/Omega) z_a sum_G w G Im[e^{iG r_a} conj(S)]
        t = np.imag(phases[:, ia] * np.conj(s)) * w
        out[ia] = (4.0 * np.pi / omega) * z[ia] * (t[:, None] * gv.gcart[1:]).sum(axis=0)
    # real-space
    rc = 10.0 / np.sqrt(lam)
    inv = np.linalg.inv(uc.lattice)
    nmax = np.ceil(rc * np.linalg.norm(inv, axis=0)).astype(int) + 1
    ts = np.array(
        np.meshgrid(*[np.arange(-n, n + 1) for n in nmax], indexing="ij")
    ).reshape(3, -1).T
    tcart = ts @ uc.lattice
    pos = uc.positions_cart()
    d = pos[:, None, None, :] - pos[None, :, None, :] - tcart[None, None, :, :]
    dist = np.linalg.norm(d, axis=-1)
    mask = (dist > 1e-10) & (dist < rc)
    a = np.sqrt(lam)
    with np.errstate(divide="ignore", invalid="ignore"):
        scal = np.where(
            mask,
            (erfc(a * dist) / dist + 2 * a / np.sqrt(np.pi) * np.exp(-lam * dist**2))
            / np.where(mask, dist**2, 1.0),
            0.0,
        )
    zz = z[:, None, None] * z[None, :, None]
    out += np.einsum("abt,abti->ai", zz * scal, d)
    return out


def forces_nonloc(
    ctx: SimulationContext,
    psi,  # [nk, ns, nb, ngk] jnp
    occ: np.ndarray,  # [nk, ns, nb]
    evals: np.ndarray,  # [nk, ns, nb]
    d_by_spin: list[np.ndarray],
) -> np.ndarray:
    """Beta-projector force: F_a,i = -2 Re sum_{k,s,b} w f
    conj(<d_i beta|psi>) (D - eps Q) <beta|psi> summed over a's projectors;
    d_i beta = -i (G+k)_i beta (reference non_local_functor.hpp)."""
    uc = ctx.unit_cell
    nbeta = ctx.beta.num_beta_total
    out = np.zeros((uc.num_atoms, 3))
    if nbeta == 0:
        return out
    qmat = ctx.beta.qmat
    for ik in range(ctx.gkvec.num_kpoints):
        beta = jnp.asarray(ctx.beta.beta_gk[ik])  # (nbeta, ngk)
        gk = jnp.asarray(ctx.gkvec.gkcart[ik])  # (ngk, 3)
        for ispn in range(psi.shape[1]):
            ps = psi[ik, ispn]  # (nb, ngk)
            bp = np.asarray(jnp.einsum("xg,bg->bx", jnp.conj(beta), ps))
            bpg = np.asarray(
                jnp.einsum("xg,gi,bg->bxi", jnp.conj(beta), gk, ps)
            )  # <beta| (G+k)_i |psi> -> conj(<d beta|psi>) = -i ...
            f = occ[ik, ispn] * ctx.gkvec.weights[ik]
            eps = evals[ik, ispn]
            dmat = d_by_spin[ispn]
            for b in range(ps.shape[0]):
                if abs(f[b]) < 1e-14:
                    continue
                eff = dmat - (eps[b] * qmat if qmat is not None else 0.0)
                # conj(<d_i beta|psi>) = conj(i <beta (G+k)_i | psi>)...
                # d_i beta = -i (G+k)_i beta => <d_i beta|psi> = i (G+k)_i-weighted
                dbp = 1j * bpg[b]  # (nbeta, 3)
                contrib = 2.0 * np.real(
                    np.einsum("xi,xy,y->xi", np.conj(dbp), eff, bp[b])
                )
                for ia, off, nbf in ctx.beta.atom_blocks(uc):
                    out[ia] -= f[b] * contrib[off : off + nbf].sum(axis=0)
    return out


def forces_us(
    ctx: SimulationContext,
    veff_g: np.ndarray,
    bz_g: np.ndarray | None,
    dm_blocks_by_spin: list,
) -> np.ndarray:
    """Augmentation force: the Q(G) charge moving with the atom against the
    effective potential (reference calc_forces_us):
    F_a = -Omega Re sum_G conj(V^s(G)) n^a Q(G) (-iG) e^{-i G r_a}."""
    uc = ctx.unit_cell
    out = np.zeros((uc.num_atoms, 3))
    if ctx.aug is None:
        return out
    ns = len(dm_blocks_by_spin)
    for ispn in range(ns):
        vs = veff_g if bz_g is None else (veff_g + bz_g if ispn == 0 else veff_g - bz_g)
        for it, at in enumerate(ctx.aug.per_type):
            if at is None:
                continue
            w2 = np.where(at.xi1 == at.xi2, 1.0, 2.0)
            for ia in uc.atoms_of_type(it):
                dmp = w2 * np.real(dm_blocks_by_spin[ispn][ia][at.xi1, at.xi2])
                phase = np.exp(-2j * np.pi * (ctx.gvec.millers @ uc.positions[ia]))
                qn = dmp @ at.q_pw  # (ng,)
                w = uc.omega * np.conj(vs) * qn * phase
                out[ia] += np.real(1j * (w[:, None] * ctx.gvec.gcart).sum(axis=0))
    return out


def symmetrize_forces(ctx: SimulationContext, f: np.ndarray) -> np.ndarray:
    """F'_{perm[a]} = R F_a averaged over ops (reference
    symmetrize_forces.hpp)."""
    if ctx.symmetry is None or ctx.symmetry.num_ops <= 1:
        return f
    out = np.zeros_like(f)
    for op in ctx.symmetry.ops:
        out[op.perm] += f @ op.rot_cart.T
    return out / ctx.symmetry.num_ops


def total_forces(
    ctx: SimulationContext,
    rho_g: np.ndarray,
    vxc_g: np.ndarray,
    veff_g: np.ndarray,
    bz_g,
    psi,
    occ,
    evals,
    d_by_spin,
    dm_blocks_by_spin,
    rho_resid_g: np.ndarray | None = None,
) -> dict:
    terms = {
        "vloc": forces_vloc(ctx, rho_g),
        "core": forces_core(ctx, vxc_g),
        "ewald": forces_ewald(ctx),
        "nonloc": forces_nonloc(ctx, psi, occ, evals, d_by_spin),
        "us": forces_us(ctx, veff_g, bz_g, dm_blocks_by_spin),
    }
    if rho_resid_g is not None:
        terms["scf_corr"] = forces_scf_corr(ctx, rho_resid_g)
    tot = sum(terms.values())
    terms["total"] = symmetrize_forces(ctx, tot)
    return terms


def forces_hubbard(ctx, hub, um_local, psi, occ: np.ndarray,
                   max_occupancy: float = 2.0) -> np.ndarray:
    """DFT+U force: F_a = -sum_{m1,m2,s} um(m1,m2) d n(m2,m1)/d R_a
    (reference hubbard_occupancies_derivatives.cpp, displacement branch;
    local blocks, "simple hubbard correction" scope — the same support
    boundary as the reference's force path, which raises for the
    non-collinear/ +V derivative combinations).

    n(m1,m2) = sum f <phi^S_m1|psi><psi|phi^S_m2> with
    phi^S = phi + beta q <beta|phi>. Derivatives use the -i(G+k) phase
    trick on phi (attaching to the orbital's atom) and on beta
    (attaching to each projector's atom for the ultrasoft S part)."""
    uc = ctx.unit_cell
    nat = uc.num_atoms
    out = np.zeros((nat, 3))
    if hub is None or um_local is None:
        return out
    nh = hub.num_hub_total
    nbeta = ctx.beta.num_beta_total
    qmat = ctx.beta.qmat
    own = np.zeros(nh, dtype=np.int64)
    for b in hub.blocks:
        own[b.off : b.off + b.nm] = b.ia
    beta_own = np.zeros(max(nbeta, 1), dtype=np.int64)
    if nbeta:
        for ia, off, nbf in ctx.beta.atom_blocks(uc):
            beta_own[off : off + nbf] = ia
    phis_all = hub.phi_s_gk
    phib_all = hub.phi_gk if hub.phi_gk is not None else hub.phi_s_gk
    for ik in range(ctx.gkvec.num_kpoints):
        phis = np.asarray(phis_all[ik])  # S phi [nh, ngk]
        phib = np.asarray(phib_all[ik])  # bare phi
        gk = np.asarray(ctx.gkvec.gkcart[ik])  # [ngk, 3]
        beta = (
            np.asarray(ctx.beta.beta_gk[ik]) if nbeta else None
        )
        for ispn in range(psi.shape[1]):
            ps = np.asarray(psi[ik, ispn])  # [nb, ngk]
            f = occ[ik, ispn] * ctx.kweights[ik] / max_occupancy
            um = um_local[ispn]  # um(m1, m2)
            hp = np.conj(phis) @ ps.T  # <phi^S_m|psi_b>  [nh, nb]
            # A[m] = sum_m2 um(m, m2) f_b <psi_b|phi^S_m2>: the partner
            # factor each derivative row contracts against
            A = um @ (np.conj(hp) * f[None, :])  # [nh, nb] (uses um(m1,m2))
            if nbeta and qmat is not None:
                beta_psi = np.conj(beta) @ ps.T  # [nbeta, nb]
                bphi = np.conj(beta) @ phib.T  # <beta_y|phi_m> [nbeta, nh]
            for x in range(3):
                # own-orbital phase derivative uses the BARE phi (the
                # S-augmented phi's phase mixes in the beta atoms' phases,
                # which the explicit beta chain below accounts for —
                # FD-verified attribution)
                dhp = (np.conj(phib) * (1j * gk[:, x])[None, :]) @ ps.T
                row = 2.0 * np.real(np.sum(dhp * A, axis=1))  # per m1
                np.add.at(out[:, x], own, -row * max_occupancy)
                if nbeta and qmat is not None:
                    dbeta_psi = (
                        np.conj(beta) * (1j * gk[:, x])[None, :]
                    ) @ ps.T  # <d beta|psi> [nbeta, nb]
                    dbphi = (
                        np.conj(beta) * (1j * gk[:, x])[None, :]
                    ) @ phib.T  # <d beta_y|phi_m> (beta displaced)
                    # beta-atom attribution: q_xy [conj<b_y|phi> <db_x|psi>
                    #   - conj<db_y|phi> <b_x|psi>]  (FD-verified signs)
                    t1 = np.einsum(
                        "xy,ym,xb->xmb", qmat, np.conj(bphi), dbeta_psi
                    )
                    t2 = np.einsum(
                        "xy,ym,xb->xmb", qmat, np.conj(dbphi), beta_psi
                    )
                    # attributions (qmat is block-diagonal per atom, so
                    # the x- and y-row atoms coincide): the <d beta|psi>
                    # piece (t1) and the <d beta_y|phi> piece (t2) both
                    # attach to the beta atom; translation invariance puts
                    # the -t2 partner on the ORBITAL's atom
                    per_beta = 2.0 * np.real(
                        np.einsum("xmb,mb->x", t1 + t2, A)
                    )
                    np.add.at(
                        out[:, x], beta_own, -per_beta * max_occupancy
                    )
                    per_m = 2.0 * np.real(
                        np.einsum("xmb,mb->m", t2, A)
                    )
                    np.add.at(out[:, x], own, per_m * max_occupancy)
    return out
