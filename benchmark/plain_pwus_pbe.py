#!/usr/bin/env python3
"""A plain plane-wave ultrasoft-pseudopotential SCF with the PBE functional,
in numpy float64: the reference of the gradient-corrected folded
configuration (make_refs_folded_pbe.py), the twin of plain_pwus.py.

It imports nothing of sirius_tpu. From plain_pwus.py (which is not edited)
it takes the helpers that state the problem and not the functional: the
closed-form transforms of the synthetic silicon species, the G-sphere, the
Ewald sum, the Fermi search and the rule that sizes the real-space box. What
it shares with the program under test is the statement of the problem only:
the diamond cell and its lattice constant, the species, the cutoffs,
Gaussian smearing, a Gamma-centred k-mesh without symmetry, the box rule (it
is part of the functional's definition: E_xc is a sum over the box's
points), the vacuum threshold of libxc (a density under 2e-13 is vacuum),
and the functional's published definition:

  exchange     PBE (Perdew, Burke, Ernzerhof, PRL 77, 3865 (1996)):
               e_x = n eps_x^unif(n) F_x(s),  F_x = 1 + kappa
               - kappa / (1 + mu s^2 / kappa),  s = |grad n| / (2 k_F n),
               kappa 0.804, mu = beta pi^2 / 3 (libxc's XC_GGA_X_PBE)
  correlation  PBE: e_c = n (eps_c^unif(r_s) + H(r_s, t)),
               t = |grad n| / (2 k_s n), on the PW92 parametrisation of
               eps_c^unif with the "modified" constants (A 0.0310907: one
               digit more than the paper's, as libxc's XC_GGA_C_PBE is
               defined on lda_c_pw_mod), beta 0.06672455060314922,
               gamma (1 - ln 2) / pi^2

Every departure from the program:

  program under test                      here
  --------------------------------------  --------------------------------
  e(n_up, n_dn, sigma_uu, ud, dd) through the unpolarized e(n, sigma) alone
  the polarized form, its potentials by   with de/dn and de/dsigma written
  jax.grad (eight autodiff derivatives)   out by hand, chain rule term by
                                          term (pbe_x, pbe_c below)
  PW92 with full spin interpolation       the zeta = 0 branch only
  gradient and divergence through its     gradient by iG on the density
  FFT-box gather/scatter maps             sphere and numpy's fftn on the
                                          box; v_xc = de/dn
                                          - 2 div(de/dsigma grad n)
  E = sum f*eps - double counting         E = T + E_nl + E_loc + E_H + E_xc
      + scf correction                        + E_ewald, term by term
  radial integrals by splines, H by FFT   closed forms, dense H and S with
  and block Davidson, Broyden mixing      LAPACK, Anderson mixing of rho(G)

(the last rows as in plain_pwus.py, whose header has the full table).

Hartree atomic units. sigma = |grad n|^2, libxc's convention. The 2-atom
cell at gk_cutoff 6, pw_cutoff 20 on the 2x2x2 mesh takes about a minute.
"""

from __future__ import annotations

import math

import numpy as np
from scipy.linalg import eigh

try:  # as a module of the checkout, or run from benchmark/ as a script
    from benchmark.plain_pwus import (
        D_ION, ZN, aug_q, beta_q, box_dims, ewald, fermi, real_ylm,
        rho_atom_q, smooth5, sphere, vloc_q)
except ImportError:
    from plain_pwus import (
        D_ION, ZN, aug_q, beta_q, box_dims, ewald, fermi, real_ylm,
        rho_atom_q, smooth5, sphere, vloc_q)

# ---------------------------------------------------------------------------
# PBE, unpolarized: energy per volume e(n, sigma), de/dn and de/dsigma

KAPPA = 0.804
BETA = 0.06672455060314922
GAMMA = (1.0 - math.log(2.0)) / math.pi ** 2
MU = BETA * math.pi ** 2 / 3.0  # 0.2195149727645171
DENS_TH = 2e-13  # below: vacuum, as libxc's density threshold has it
# PW92, zeta = 0, "modified" A (libxc lda_c_pw_mod)
PW_A, PW_A1 = 0.0310907, 0.21370
PW_B = (7.5957, 3.5876, 1.6382, 0.49294)


def pbe_x(n, sigma):
    """PBE exchange: e = A n^(4/3) F(s2), s2 = sigma / (4 kF^2 n^2)."""
    a = -0.75 * (3.0 / math.pi) ** (1.0 / 3.0)
    c = 1.0 / (4.0 * (3.0 * math.pi ** 2) ** (2.0 / 3.0))  # s2 = c sigma n^-8/3
    n13 = n ** (1.0 / 3.0)
    s2 = c * sigma / (n13 ** 8)
    den = 1.0 + MU * s2 / KAPPA
    f = 1.0 + KAPPA - KAPPA / den
    df = MU / den ** 2  # dF/ds2
    e = a * n * n13 * f
    # ds2/dn = -(8/3) s2 / n; ds2/dsigma = c n^-8/3
    de_dn = (4.0 / 3.0) * a * n13 * f - (8.0 / 3.0) * a * n13 * df * s2
    de_ds = a * df * c / n13 ** 4
    return e, de_dn, de_ds


def pw92_mod(rs):
    """eps_c^unif(rs) at zeta = 0 and its derivative in rs."""
    b1, b2, b3, b4 = PW_B
    sq = np.sqrt(rs)
    q0 = -2.0 * PW_A * (1.0 + PW_A1 * rs)
    q1 = 2.0 * PW_A * (b1 * sq + b2 * rs + b3 * rs * sq + b4 * rs * rs)
    dq1 = PW_A * (b1 / sq + 2.0 * b2 + 3.0 * b3 * sq + 4.0 * b4 * rs)
    lg = np.log1p(1.0 / q1)
    eps = q0 * lg
    deps = -2.0 * PW_A * PW_A1 * lg - q0 * dq1 / (q1 * q1 + q1)
    return eps, deps


def pbe_c(n, sigma):
    """PBE correlation: e = n (eps(rs) + H(eps, y)), y = t^2."""
    rs = (3.0 / (4.0 * math.pi * n)) ** (1.0 / 3.0)
    eps, deps_drs = pw92_mod(rs)
    deps_dn = deps_drs * (-rs / (3.0 * n))
    # y = sigma / (4 ks^2 n^2), ks^2 = 4 kF / pi: y = ct sigma n^-7/3
    ct = math.pi / (16.0 * (3.0 * math.pi ** 2) ** (1.0 / 3.0))
    n13 = n ** (1.0 / 3.0)
    y = ct * sigma / (n13 ** 7)
    b = BETA / GAMMA
    ex = np.exp(-eps / GAMMA)
    aa = b / (ex - 1.0)
    daa_deps = b * ex / (GAMMA * (ex - 1.0) ** 2)
    num = 1.0 + aa * y
    den = 1.0 + aa * y + (aa * y) ** 2
    r = y * num / den
    h = GAMMA * np.log1p(b * r)
    dh_dr = BETA / (1.0 + b * r)
    dr_dy = ((1.0 + 2.0 * aa * y) * den
             - y * num * (aa + 2.0 * aa * aa * y)) / den ** 2
    dr_da = (y * y * den - y * num * (y + 2.0 * aa * y * y)) / den ** 2
    dh_dn = dh_dr * (dr_da * daa_deps * deps_dn + dr_dy * (-7.0 / 3.0) * y / n)
    e = n * (eps + h)
    de_dn = eps + h + n * (deps_dn + dh_dn)
    de_ds = n * dh_dr * dr_dy * ct / n13 ** 7
    return e, de_dn, de_ds


def pbe(rho, sigma):
    """e, de/drho, de/dsigma of PBE exchange + correlation at every point
    (flat arrays); a point whose density is under DENS_TH is vacuum: zero."""
    e = np.zeros_like(rho)
    v = np.zeros_like(rho)
    vs = np.zeros_like(rho)
    ok = rho > DENS_TH
    n, s = rho[ok], sigma[ok]
    ex, vx, sx = pbe_x(n, s)
    ec, vc, sc = pbe_c(n, s)
    e[ok], v[ok], vs[ok] = ex + ec, vx + vc, sx + sc
    return e, v, vs


# ---------------------------------------------------------------------------

def scf(ngridk=(2, 2, 2), gk_cutoff=6.0, pw_cutoff=20.0, num_bands=8,
        smearing_width=0.025, lattice_constant=10.26, density_tol=1e-10,
        max_iter=80, log=None):
    """Total energy (without the smearing's entropy term, as the program
    reports `energy.total`) of the 2-atom diamond cell of the synthetic
    silicon under PBE on the Gamma-centred k-mesh. Returns a dictionary."""
    say = log or (lambda *a: None)
    lattice = lattice_constant / 2 * np.array([[0.0, 1, 1], [1, 0, 1], [1, 1, 0]])
    omega = abs(np.linalg.det(lattice))
    recip = 2 * np.pi * np.linalg.inv(lattice).T  # rows b_i
    frac = np.array([[0.0, 0, 0], [0.25, 0.25, 0.25]])
    tau = frac @ lattice
    nat = len(tau)
    nel = ZN * nat
    dims = box_dims(lattice, pw_cutoff)
    npt = dims[0] * dims[1] * dims[2]

    # density / potential sphere
    mg, g = sphere(recip, np.zeros(3), pw_cutoff, [(n - 1) // 2 for n in dims])
    glen = np.linalg.norm(g, axis=1)
    ig0 = int(np.argmin(glen))
    box_of_g = tuple(mg.T % np.array(dims)[:, None])
    phase = np.exp(-1j * g @ tau.T).T  # [atom, G]: exp(-i G tau)
    vloc_g = phase.sum(0) * vloc_q(glen) / omega
    qaug = aug_q(glen)  # [4, G]
    coul = np.where(glen > 0, 4 * np.pi / np.where(glen > 0, glen, 1) ** 2, 0)

    def to_box(f_g):
        box = np.zeros(dims, complex)
        box[box_of_g] = f_g
        return box

    def to_r(f_g):
        return np.real(np.fft.ifftn(to_box(f_g)) * npt)

    def to_g(f_r):
        return (np.fft.fftn(f_r) / npt)[box_of_g]

    # k-points: the whole Gamma-centred mesh, weight 1/N each
    kpts = np.array([[i / ngridk[0], j / ngridk[1], k / ngridk[2]]
                     for i in range(ngridk[0]) for j in range(ngridk[1])
                     for k in range(ngridk[2])])
    kpts = kpts - np.round(kpts)
    wk = np.full(len(kpts), 1.0 / len(kpts))
    half = [int(gk_cutoff * np.linalg.norm(a) / (2 * np.pi)) + 2
            for a in lattice]
    # a box for |psi|^2 that holds every difference of two sphere vectors
    wdims = tuple(smooth5(4 * h + 2) for h in half)
    ks = []
    for kf in kpts:
        m, q = sphere(recip, kf, gk_cutoff, half)
        qlen = np.linalg.norm(q, axis=1)
        il = np.array([1, -1j, -1j, -1j])  # (-i)^l
        beta = np.concatenate([
            4 * np.pi / math.sqrt(omega) * il[:, None] * real_ylm(q)
            * beta_q(qlen) * np.exp(-1j * q @ t)[None, :] for t in tau])
        dm = m[:, None, :] - m[None, :, :]
        ks.append({
            "m": m, "kin": 0.5 * qlen ** 2, "beta": beta,
            "diff": np.ravel_multi_index(
                tuple(np.moveaxis(dm % np.array(dims), -1, 0)),
                dims).astype(np.int32),
            "wbox": tuple(m.T % np.array(wdims)[:, None]),
        })
    say(f"box {dims}, {len(glen)} G, {len(kpts)} k-points of "
        f"{min(len(k['m']) for k in ks)}-{max(len(k['m']) for k in ks)} "
        f"plane waves")
    qmat = np.tile(aug_q(np.zeros(1))[:, 0], nat)  # q_xi,xi per projector
    e_ewald = ewald(lattice, recip, omega, tau, np.full(nat, ZN))

    rho_g = phase.sum(0) * rho_atom_q(glen)
    rho_g = rho_g * (nel / omega / rho_g[ig0].real)

    def potential(rho):
        rho_r = to_r(rho)
        grad = [to_r(1j * g[:, i] * rho) for i in range(3)]
        sigma = grad[0] ** 2 + grad[1] ** 2 + grad[2] ** 2
        shape = rho_r.shape
        # the density as the functional sees it: never negative
        e_r, v_r, vs_r = (a.reshape(shape) for a in pbe(
            np.maximum(rho_r, 0.0).ravel(), sigma.ravel()))
        vxc_g = to_g(v_r)
        for i in range(3):  # - 2 div(de/dsigma grad rho)
            vxc_g = vxc_g - 1j * g[:, i] * to_g(2.0 * vs_r * grad[i])
        vha = coul * rho
        veff = vloc_g + vha + vxc_g
        parts = {
            "hartree": 0.5 * omega * np.real(np.vdot(rho, vha)),
            "local": omega * np.real(np.vdot(rho, vloc_g)),
            "xc": omega / npt * np.sum(e_r),
            "rho_min": float(rho_r.min()),
            "sigma_max": float(sigma.max()),
        }
        return veff, parts

    def bands_and_density(veff):
        vflat = to_box(veff).ravel()
        # D = D_ion + int V_eff(r) Q(r - tau) d^3r, diagonal
        dmat = np.concatenate([
            D_ION + np.real(np.sum((veff * np.conj(ph))[None, :] * qaug, 1))
            for ph in phase])
        evals, kept = [], []
        for k in ks:
            b = k["beta"]
            h = vflat[k["diff"]] + np.diag(k["kin"]) + (b.T * dmat) @ b.conj()
            s = np.eye(len(k["kin"])) + (b.T * qmat) @ b.conj()
            ev, c = eigh(h, s, subset_by_index=[0, num_bands - 1])
            evals.append(ev)
            kept.append(c)
        evals = np.array(evals)
        mu, occ = fermi(evals, wk, nel, smearing_width)
        rho_r = np.zeros(wdims)
        dens = np.zeros(4 * nat)
        e_kin = e_nl = 0.0
        dion = np.tile(D_ION, nat)
        for k, c, f, w in zip(ks, kept, occ, wk):
            p = k["beta"].conj() @ c  # <beta|psi>, [proj, band]
            wf = w * f
            pp = np.real(np.sum(np.abs(p) ** 2 * wf[None, :], axis=1))
            dens += pp
            e_nl += np.sum(pp * dion)
            e_kin += np.sum(wf * (k["kin"] @ np.abs(c) ** 2))
            for n in range(num_bands):
                box = np.zeros(wdims, complex)
                box[k["wbox"]] = c[:, n]
                rho_r += wf[n] * np.abs(np.fft.ifftn(box)) ** 2
        rho_r *= rho_r.size ** 2 / omega
        rho_w = np.fft.fftn(rho_r) / rho_r.size
        rho = rho_w[tuple(mg.T % np.array(wdims)[:, None])]
        # |rho_ps(G)| vanishes beyond 2 gk, which the work box holds
        rho = np.where(glen <= 2 * gk_cutoff + 1e-8, rho, 0)
        for a in range(nat):
            rho = rho + phase[a] * (dens[4 * a:4 * a + 4] @ qaug) / omega
        return rho, {"kinetic": e_kin, "nonlocal": e_nl, "efermi": mu,
                     "evals": evals, "occ": occ}

    # Anderson mixing of rho(G)
    hist_x, hist_f = [], []
    beta_mix, depth = 0.6, 8
    out = None
    for it in range(1, max_iter + 1):
        veff, _ = potential(rho_g)
        rho_out, band = bands_and_density(veff)
        _, parts = potential(rho_out)
        energy = (band["kinetic"] + band["nonlocal"] + parts["local"]
                  + parts["hartree"] + parts["xc"] + e_ewald)
        resid = rho_out - rho_g
        rms = math.sqrt(np.sum(np.abs(resid) ** 2) / len(resid))
        nel_out = rho_out[ig0].real * omega
        say(f"it {it:2d}  E {energy:.12f}  rms {rms:.3e}  N {nel_out:.10f}")
        out = {"energy_total_ha": energy, "rms": rms, "iterations": it,
               "electrons": nel_out, "ewald": e_ewald, **parts,
               "kinetic": band["kinetic"], "nonlocal": band["nonlocal"],
               "efermi": band["efermi"], "box": list(dims),
               "num_gvec": len(glen), "num_kpoints": len(kpts),
               "band_energies_gamma": band["evals"][0].tolist()}
        if rms < density_tol:
            out["converged"] = True
            return out
        hist_x.append(rho_g)
        hist_f.append(resid)
        hist_x, hist_f = hist_x[-depth:], hist_f[-depth:]
        x, f = rho_g, resid
        if len(hist_f) > 1:
            df = np.array([hist_f[-1] - h for h in hist_f[:-1]])
            dx = np.array([hist_x[-1] - h for h in hist_x[:-1]])
            a = np.real(df.conj() @ df.T)
            rhs = np.real(df.conj() @ resid)
            gam = np.linalg.lstsq(a, rhs, rcond=1e-12)[0]
            x = rho_g - gam @ dx
            f = resid - gam @ df
        rho_g = x + beta_mix * f
    out["converged"] = False
    return out


if __name__ == "__main__":
    import argparse
    import json
    import time

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--ngridk", type=int, default=2)
    ap.add_argument("--gk", type=float, default=6.0)
    ap.add_argument("--pw", type=float, default=20.0)
    ap.add_argument("--bands", type=int, default=8)
    a = ap.parse_args()
    t0 = time.time()
    r = scf((a.ngridk,) * 3, a.gk, a.pw, a.bands, log=print)
    r["wall_s"] = round(time.time() - t0, 1)
    print(json.dumps(r))
