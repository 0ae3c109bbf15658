#!/usr/bin/env python3
"""A plain plane-wave ultrasoft-pseudopotential SCF in numpy float64: the
reference of the folded configurations (make_refs_folded.py).

It imports nothing of sirius_tpu. What it shares with the program under test
is the statement of the problem only: the diamond cell and its lattice
constant, the synthetic silicon species (restated below from its closed
forms), the cutoffs, LDA exchange + Perdew-Zunger 81 correlation, Gaussian
smearing, a Gamma-centred k-mesh without symmetry, and the rule that sizes
the real-space box on which the XC functional is evaluated. Everything else
is done the other way:

  program under test                      here
  --------------------------------------  --------------------------------
  radial integrals by splines on a grid   closed forms of the transforms
  H applied by FFT, block Davidson        dense H and S, LAPACK's generalized
                                          Hermitian eigensolver
  density accumulated on the coarse box,  |psi|^2 on one box, augmentation
  augmentation by a GEMM over shells      summed atom by atom
  E = sum f*eps - double counting         E = T + E_nl + E_loc + E_H + E_xc
      + scf correction                        + E_ewald, term by term
  Broyden mixing of (rho, D)              Anderson mixing of rho(G)
  autodiff XC potential                   the functionals' derivatives by hand
  Ewald sum of dft/ewald.py               its own Ewald sum

Hartree atomic units. A 2-atom cell at gk_cutoff 6, pw_cutoff 20 on the
3x3x3 mesh (27 k-points of about 985 plane waves) takes a few minutes.
"""

from __future__ import annotations

import itertools
import math

import numpy as np
from scipy.linalg import eigh
from scipy.special import erf, erfc

# ---------------------------------------------------------------------------
# the synthetic ultrasoft silicon (sirius_tpu.testing.synthetic_silicon_type
# tabulates these radial functions; here are their Bessel transforms)
#
#   v_loc(r)     = -Z erf(r) / r
#   r beta_0(r)  = 2 r exp(-r^2)          l = 0,  D_ion 0.8, q 0.05 r^2 e^{-2r^2}
#   r beta_1(r)  = 1.5 r^2 exp(-r^2)      l = 1,  D_ion 0.4, q 0.03 r^2 e^{-2r^2}
#   rho_atom(r)  ~ exp(-0.8 r)            (the start density only)
ZN = 4.0
D_ION = np.array([0.8, 0.4, 0.4, 0.4])  # s, px, py, pz
Q_AMP = np.array([0.05, 0.03, 0.03, 0.03])


def vloc_q(q):
    """int v_loc(r) exp(-i q r) d^3r, the Coulomb tail's q = 0 term left to
    the Ewald sum: what stays there is int (v_loc + Z/r) d^3r = pi Z."""
    q2 = np.where(q > 0, q * q, 1.0)
    return np.where(q > 0, -4 * np.pi * ZN * np.exp(-q2 / 4) / q2, np.pi * ZN)


def beta_q(q):
    """int r beta_l(r) j_l(q r) r dr for the four projectors, [4, len(q)]."""
    s = 2.0 * math.sqrt(np.pi) / 4 * np.exp(-q * q / 4)
    p = 1.5 * math.sqrt(np.pi) / 8 * q * np.exp(-q * q / 4)
    return np.stack([s, p, p, p])


def aug_q(q):
    """int Q_xi,xi(r) exp(-i q r) d^3r, [4, len(q)] (the channels are l = 0
    and diagonal, so Q is a spherical Gaussian on every projector)."""
    g = math.sqrt(np.pi) / (4 * 2.0 ** 1.5) * np.exp(-q * q / 8)
    return Q_AMP[:, None] * g[None, :]


def rho_atom_q(q):
    """Transform of exp(-0.8 r), any norm (the start is scaled to the
    electron count)."""
    return 1.0 / (0.64 + q * q) ** 2


def real_ylm(qvec):
    """Real spherical harmonics of l = 0 and l = 1, [4, n]."""
    qlen = np.linalg.norm(qvec, axis=1)
    unit = qvec / np.where(qlen > 0, qlen, 1.0)[:, None]
    y = np.empty((4, len(qvec)))
    y[0] = 1 / math.sqrt(4 * np.pi)
    y[1:] = math.sqrt(3 / (4 * np.pi)) * unit.T
    return y


# ---------------------------------------------------------------------------
# exchange and correlation, unpolarized: energy per volume and potential

def lda_x_pz(rho):
    e = np.zeros_like(rho)
    v = np.zeros_like(rho)
    ok = rho > 2e-13  # below: vacuum, as libxc's density threshold has it
    n = rho[ok]
    cx = 0.75 * (3 / np.pi) ** (1 / 3)
    ex = -cx * n ** (4 / 3)
    vx = -(4 / 3) * cx * n ** (1 / 3)
    rs = (3 / (4 * np.pi * n)) ** (1 / 3)
    hi = rs >= 1
    gam, b1, b2 = -0.1423, 1.0529, 0.3334
    a, b, c, d = 0.0311, -0.048, 0.002, -0.0116
    sq = np.sqrt(rs)
    den = 1 + b1 * sq + b2 * rs
    eps_lo = gam / den
    deps_lo = -gam * (0.5 * b1 / sq + b2) / den ** 2
    ln = np.log(rs)
    eps_hi = a * ln + b + c * rs * ln + d * rs
    deps_hi = a / rs + c * (ln + 1) + d
    eps = np.where(hi, eps_lo, eps_hi)
    deps = np.where(hi, deps_lo, deps_hi)
    e[ok] = ex + n * eps
    v[ok] = vx + eps - rs / 3 * deps  # d(n eps)/dn, d rs/dn = -rs/(3n)
    return e, v


# ---------------------------------------------------------------------------

def smooth5(n):
    while True:
        m = n
        for p in (2, 3, 5):
            while m % p == 0:
                m //= p
        if m == 1:
            return n
        n += 1


def box_dims(lattice, gmax):
    """The reference code's real-space box for a sphere of radius gmax: it
    is part of the functional's definition, because XC is evaluated on it."""
    lens = np.linalg.norm(lattice, axis=1)
    return tuple(smooth5(int(2 * gmax * x / (2 * np.pi)) + 3) for x in lens)


def sphere(recip, center, gmax, half):
    """Miller indices m with |(m + center) B| <= gmax."""
    rng = [np.arange(-h, h + 1) for h in half]
    m = np.stack(np.meshgrid(*rng, indexing="ij"), -1).reshape(-1, 3)
    q = (m + center) @ recip
    keep = np.sum(q * q, axis=1) <= gmax * gmax + 1e-10
    return m[keep], q[keep]


def ewald(lattice, recip, omega, pos_cart, charges, eta=1.2):
    nr = 6
    shifts = np.array(list(itertools.product(range(-nr, nr + 1), repeat=3)))
    rvecs = shifts @ lattice
    gmax = 12.0 * eta
    m, g = sphere(recip, np.zeros(3), gmax,
                  [int(gmax * np.linalg.norm(a) / (2 * np.pi)) + 1
                   for a in lattice])
    g2 = np.sum(g * g, axis=1)
    g, g2 = g[g2 > 1e-12], g2[g2 > 1e-12]
    e = 0.0
    for a, ta in enumerate(pos_cart):
        for b, tb in enumerate(pos_cart):
            d = np.linalg.norm(ta - tb + rvecs, axis=1)
            d = d[d > 1e-10]
            real = np.sum(erfc(eta * d) / d)
            rec = 4 * np.pi / omega * np.sum(
                np.exp(-g2 / (4 * eta * eta)) / g2 * np.cos(g @ (ta - tb)))
            e += 0.5 * charges[a] * charges[b] * (real + rec)
    e -= eta / math.sqrt(np.pi) * np.sum(charges ** 2)
    e -= np.pi * np.sum(charges) ** 2 / (2 * omega * eta * eta)
    return e


def fermi(evals, weights, nel, width):
    """mu and occupations (of 2) with Gaussian smearing, by bisection."""
    def occ(mu):
        return 1.0 + erf((mu - evals) / width)  # 2 * (1/2)(1 + erf)
    lo, hi = evals.min() - 10, evals.max() + 10
    for _ in range(200):
        mid = 0.5 * (lo + hi)
        if np.sum(weights[:, None] * occ(mid)) < nel:
            lo = mid
        else:
            hi = mid
    mu = 0.5 * (lo + hi)
    return mu, occ(mu)


def scf(ngridk=(3, 3, 3), gk_cutoff=6.0, pw_cutoff=20.0, num_bands=8,
        smearing_width=0.025, lattice_constant=10.26, density_tol=1e-10,
        max_iter=80, log=None):
    """Total energy (without the smearing's entropy term, as the program
    reports `energy.total`) of the 2-atom diamond cell of the synthetic
    silicon on the Gamma-centred k-mesh. Returns a dictionary."""
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
        ylm = real_ylm(q)
        rad = beta_q(qlen)
        il = np.array([1, -1j, -1j, -1j])  # (-i)^l
        beta = np.concatenate([
            4 * np.pi / math.sqrt(omega) * il[:, None] * ylm * rad
            * np.exp(-1j * q @ t)[None, :] for t in tau])  # [atom*4, G]
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
        exc_r, vxc_r = lda_x_pz(rho_r)
        vha = coul * rho
        veff = vloc_g + vha + to_g(vxc_r)
        parts = {
            "hartree": 0.5 * omega * np.real(np.vdot(rho, vha)),
            "local": omega * np.real(np.vdot(rho, vloc_g)),
            "xc": omega / npt * np.sum(exc_r),
            "rho_min": float(rho_r.min()),
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
    ap.add_argument("--ngridk", type=int, default=3)
    ap.add_argument("--gk", type=float, default=6.0)
    ap.add_argument("--pw", type=float, default=20.0)
    ap.add_argument("--bands", type=int, default=8)
    a = ap.parse_args()
    t0 = time.time()
    r = scf((a.ngridk,) * 3, a.gk, a.pw, a.bands, log=print)
    r["wall_s"] = round(time.time() - t0, 1)
    print(json.dumps(r))
