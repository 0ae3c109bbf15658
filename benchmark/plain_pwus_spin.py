#!/usr/bin/env python3
"""A plain plane-wave ultrasoft-pseudopotential SCF with two spin channels
(collinear, LSDA) in numpy float64: the reference of the ferromagnetic
configuration fm2-k444-us (make_refs_spin.py), the twin of plain_pwus.py.

It imports nothing of sirius_tpu. From plain_pwus.py (which is not edited)
it takes the helpers that state the problem and not the species or the
functional: the G-sphere, the Ewald sum and the rule that sizes the
real-space box. What it shares with the program under test is the statement
of the problem only: the diamond cell and its lattice constant, the synthetic
d-shell species (sirius_tpu.testing.synthetic_dshell_type tabulates its
radial functions; their Bessel transforms are restated below in closed
form), the cutoffs, Gaussian smearing with one Fermi level over both
channels, a Gamma-centred k-mesh without symmetry, the box rule, libxc's
vacuum threshold (a spin channel under 1e-13 is vacuum) and the functional's
published definition:

  exchange     Slater, spin-scaled: e_x(n_up, n_dn) = (e_x(2 n_up)
               + e_x(2 n_dn)) / 2, e_x(n) = -(3/4)(3/pi)^(1/3) n^(4/3)
  correlation  Perdew-Zunger 81 (PRB 23, 5048, appendix C): eps_c(r_s, zeta)
               = eps_U + f(zeta) (eps_P - eps_U) with the paper's two
               parameter sets and f = ((1+zeta)^(4/3) + (1-zeta)^(4/3) - 2)
               / (2^(4/3) - 2), which is libxc's XC_LDA_C_PZ

Every departure from the program:

  program under test                      here
  --------------------------------------  --------------------------------
  36 k-points of the 4x4x4 mesh, k and    all 64 k-points, weight 1/64 each,
  -k paired by time reversal              no time reversal, no symmetry
  both channels' bands in one batched     dense H_sigma and S, LAPACK's
  block Davidson, H by DFT products       generalized Hermitian eigensolver
  v_up, v_dn by jax.grad of e(n_up,n_dn)  the derivatives by hand (lsda)
  radial integrals by splines on a grid   closed forms of the transforms
  density on the coarse box, augmentation |psi|^2 on one box, augmentation
  by a GEMM over shells                   summed atom by atom
  E = sum f*eps - double counting         E = T + E_nl + E_loc + E_H + E_xc
      + scf correction                        + E_ewald, term by term
  Broyden mixing of (rho, m, D)           Anderson mixing of [rho(G); m(G)]
  start moment: a compact bump in the     start moment: a Gaussian on each
  atomic sphere                           atom

Hartree atomic units; moments in Bohr magnetons (electrons). The 2-atom
cell at gk_cutoff 6, pw_cutoff 20 on the 4x4x4 mesh (64 k-points of about
985 plane waves, two channels) takes about half an hour.
"""

from __future__ import annotations

import math
from concurrent.futures import ThreadPoolExecutor

import numpy as np
from scipy.linalg import eigh
from scipy.special import erf

try:  # as a module of the checkout, or run from benchmark/ as a script
    from benchmark.plain_pwus import box_dims, ewald, smooth5, sphere
except ImportError:
    from plain_pwus import box_dims, ewald, smooth5, sphere

# ---------------------------------------------------------------------------
# the synthetic d-shell species (sirius_tpu.testing.synthetic_dshell_type)
#
#   v_loc(r)     = -Z erf(r) / r                                     Z = 8
#   r beta_0(r)  = 2 r exp(-r^2)            l = 0, D 2.0, q 0.05 r^2 e^{-2r^2}
#   r beta_1(r)  = 1.5 r^2 exp(-r^2)        l = 1, D 3.0, q 0.03 r^2 e^{-2r^2}
#   r beta_2(r)  = N r^3 exp(-r^2/(2 w^2))  l = 2, D -6.0, no augmentation,
#                  w = 0.4, N^2 int r^6 exp(-r^2/w^2) dr = 1
#   rho_atom(r)  ~ exp(-0.8 r)              (the start density only)
ZN = 8.0
WIDTH = 0.4
L_OF = np.array([0, 1, 1, 1, 2, 2, 2, 2, 2])  # the 9 projectors of an atom
D_ION = np.array([2.0, 3.0, -6.0])[L_OF]
Q_AMP = np.array([0.05, 0.03, 0.0])[L_OF]
NPROJ = len(L_OF)
DENS_TH = 1e-13  # a spin channel below: vacuum, libxc's density threshold


def vloc_q(q):
    """int v_loc(r) exp(-i q r) d^3r, the Coulomb tail's q = 0 term left to
    the Ewald sum: what stays there is int (v_loc + Z/r) d^3r = pi Z."""
    q2 = np.where(q > 0, q * q, 1.0)
    return np.where(q > 0, -4 * np.pi * ZN * np.exp(-q2 / 4) / q2, np.pi * ZN)


def beta_radial(q):
    """int r beta_l(r) j_l(q r) r dr of the three channels, [3, len(q)]:
    int r^(l+2) exp(-a r^2) j_l(q r) dr = sqrt(pi) q^l exp(-q^2 / (4a))
    / (2^(l+2) a^(l+3/2))."""
    def gauss(l, a):
        return (math.sqrt(np.pi) * q ** l * np.exp(-q * q / (4 * a))
                / (2 ** (l + 2) * a ** (l + 1.5)))

    norm = 1.0 / math.sqrt(15.0 * math.sqrt(np.pi) * WIDTH ** 7 / 16.0)
    return np.stack([2.0 * gauss(0, 1.0), 1.5 * gauss(1, 1.0),
                     norm * gauss(2, 1.0 / (2 * WIDTH * WIDTH))])


def beta_q(q):
    """The same for the nine projectors, [9, len(q)]."""
    return beta_radial(q)[L_OF]


def aug_q(q):
    """int Q_xi,xi(r) exp(-i q r) d^3r, [9, len(q)] (the channels are l = 0
    and diagonal: a spherical Gaussian on s and p, nothing on d)."""
    g = math.sqrt(np.pi) / (4 * 2.0 ** 1.5) * np.exp(-q * q / 8)
    return Q_AMP[:, None] * g[None, :]


def rho_atom_q(q):
    """Transform of exp(-0.8 r), any norm (the start is scaled to the
    electron count)."""
    return 1.0 / (0.64 + q * q) ** 2


def real_ylm(qvec):
    """Real spherical harmonics of l = 0, 1, 2 on the directions of qvec,
    [9, n]; any orthonormal basis of a shell serves, D being a multiple of
    the unit matrix inside it."""
    qlen = np.linalg.norm(qvec, axis=1)
    x, y, z = (qvec / np.where(qlen > 0, qlen, 1.0)[:, None]).T
    c1 = math.sqrt(3 / (4 * np.pi))
    c2 = math.sqrt(15 / (4 * np.pi))
    return np.stack([
        np.full(len(qvec), 1 / math.sqrt(4 * np.pi)),
        c1 * x, c1 * y, c1 * z,
        math.sqrt(5 / (16 * np.pi)) * (3 * z * z - 1),
        c2 * x * z, c2 * y * z, 0.5 * c2 * (x * x - y * y), c2 * x * y])


# ---------------------------------------------------------------------------
# exchange and correlation with two spin channels: energy per volume and the
# two potentials, derivatives by hand

PZ_U = dict(gam=-0.1423, b1=1.0529, b2=0.3334, a=0.0311, b=-0.048, c=0.002,
            d=-0.0116)
PZ_P = dict(gam=-0.0843, b1=1.3981, b2=0.2611, a=0.01555, b=-0.0269,
            c=0.0007, d=-0.0048)


def pz_eps(rs, gam, b1, b2, a, b, c, d):
    """eps_c(r_s) of one parameter set and its derivative in r_s."""
    sq = np.sqrt(rs)
    den = 1 + b1 * sq + b2 * rs
    ln = np.log(rs)
    eps = np.where(rs >= 1, gam / den, a * ln + b + c * rs * ln + d * rs)
    deps = np.where(rs >= 1, -gam * (0.5 * b1 / sq + b2) / den ** 2,
                    a / rs + c * (ln + 1) + d)
    return eps, deps


def lsda(n_up, n_dn):
    """e_xc per volume, v_up = de/dn_up and v_dn = de/dn_dn for Slater
    exchange + PZ81 correlation. A channel under DENS_TH is vacuum: the
    energy is taken with the channel at the threshold and its potential
    is zero."""
    dead_up, dead_dn = n_up < DENS_TH, n_dn < DENS_TH
    nu = np.where(dead_up, DENS_TH, n_up)
    nd = np.where(dead_dn, DENS_TH, n_dn)
    cx = 0.75 * (3 / np.pi) ** (1 / 3)
    e = -0.5 * cx * ((2 * nu) ** (4 / 3) + (2 * nd) ** (4 / 3))
    v_up = -(4 / 3) * cx * (2 * nu) ** (1 / 3)
    v_dn = -(4 / 3) * cx * (2 * nd) ** (1 / 3)
    n = nu + nd
    zeta = (nu - nd) / n
    rs = (3 / (4 * np.pi * n)) ** (1 / 3)
    eu, deu = pz_eps(rs, **PZ_U)
    ep, dep = pz_eps(rs, **PZ_P)
    den = 2 ** (4 / 3) - 2
    f = ((1 + zeta) ** (4 / 3) + (1 - zeta) ** (4 / 3) - 2) / den
    df = (4 / 3) * ((1 + zeta) ** (1 / 3) - (1 - zeta) ** (1 / 3)) / den
    eps = eu + f * (ep - eu)
    # d(n eps)/dn at fixed zeta (d rs/dn = -rs / (3n)), then the zeta term:
    # d zeta/d n_up = (1 - zeta)/n, d zeta/d n_dn = -(1 + zeta)/n
    common = eps - rs / 3 * (deu + f * (dep - deu))
    spin = df * (ep - eu)
    e = e + n * eps
    v_up = v_up + common + spin * (1 - zeta)
    v_dn = v_dn + common - spin * (1 + zeta)
    return e, np.where(dead_up, 0.0, v_up), np.where(dead_dn, 0.0, v_dn)


# ---------------------------------------------------------------------------

def fermi(evals, weights, nel, width):
    """mu and occupations (of 1 a spin channel) with Gaussian smearing, one
    level over both channels, by bisection. evals [nk, 2, nb]."""
    def occ(mu):
        return 0.5 * (1.0 + erf((mu - evals) / width))
    lo, hi = evals.min() - 10, evals.max() + 10
    for _ in range(200):
        mid = 0.5 * (lo + hi)
        if np.sum(weights[:, None, None] * occ(mid)) < nel:
            lo = mid
        else:
            hi = mid
    mu = 0.5 * (lo + hi)
    return mu, occ(mu)


def scf(ngridk=(4, 4, 4), gk_cutoff=6.0, pw_cutoff=20.0, num_bands=16,
        smearing_width=0.025, lattice_constant=10.26, start_moment=2.0,
        density_tol=1e-10, max_iter=120, workers=1, log=None):
    """Total energy (without the smearing's entropy term, as the program
    reports `energy.total`) and total moment of the 2-atom diamond cell of
    the d-shell species on the Gamma-centred k-mesh, from `start_moment`
    Bohr magnetons an atom. With start_moment 0 the two channels stay equal
    and one is solved: the non-magnetic state. workers > 1 solves that many
    k-points at a time in threads (LAPACK releases the interpreter's lock;
    give the BLAS one thread each then: OPENBLAS_NUM_THREADS=1). Returns a
    dictionary."""
    say = log or (lambda *a: None)
    magnetic = start_moment != 0.0
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
    ng = len(glen)
    ig0 = int(np.argmin(glen))
    box_of_g = tuple(mg.T % np.array(dims)[:, None])
    phase = np.exp(-1j * g @ tau.T).T  # [atom, G]: exp(-i G tau)
    vloc_g = phase.sum(0) * vloc_q(glen) / omega
    qaug = aug_q(glen)  # [9, G]
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
    il = (-1j) ** L_OF  # (-i)^l
    ks = []
    for kf in kpts:
        m, q = sphere(recip, kf, gk_cutoff, half)
        qlen = np.linalg.norm(q, axis=1)
        beta = np.concatenate([
            4 * np.pi / math.sqrt(omega) * il[:, None] * real_ylm(q)
            * beta_q(qlen) * np.exp(-1j * q @ t)[None, :]
            for t in tau])  # [atom*9, G]
        dm = m[:, None, :] - m[None, :, :]
        ks.append({
            "m": m, "kin": 0.5 * qlen ** 2, "beta": beta,
            "diff": np.ravel_multi_index(
                tuple(np.moveaxis(dm % np.array(dims), -1, 0)),
                dims).astype(np.int32),
            "wbox": tuple(m.T % np.array(wdims)[:, None]),
        })
    say(f"box {dims}, {ng} G, {len(kpts)} k-points of "
        f"{min(len(k['m']) for k in ks)}-{max(len(k['m']) for k in ks)} "
        f"plane waves, {2 if magnetic else 1} channel(s) solved")
    qmat = np.tile(aug_q(np.zeros(1))[:, 0], nat)  # q_xi,xi per projector
    dion = np.tile(D_ION, nat)
    e_ewald = ewald(lattice, recip, omega, tau, np.full(nat, ZN))
    sphere_in_wbox = tuple(mg.T % np.array(wdims)[:, None])

    rho_g = phase.sum(0) * rho_atom_q(glen)
    rho_g = rho_g * (nel / omega / rho_g[ig0].real)
    mag_g = phase.sum(0) * np.exp(-glen ** 2 / 16.0) * (start_moment / omega)

    def potential(rho, mag):
        """v_up(G), v_dn(G) and the energy integrals of (rho, m)."""
        rho_r, mag_r = to_r(rho), to_r(mag)
        rho_r = np.maximum(rho_r, 1e-20)
        mag_r = np.clip(mag_r, -rho_r, rho_r)
        exc_r, vup_r, vdn_r = lsda(0.5 * (rho_r + mag_r), 0.5 * (rho_r - mag_r))
        vha = coul * rho
        common = vloc_g + vha
        parts = {
            "hartree": 0.5 * omega * np.real(np.vdot(rho, vha)),
            "local": omega * np.real(np.vdot(rho, vloc_g)),
            "xc": omega / npt * np.sum(exc_r),
            "rho_min": float(to_r(rho).min()),
        }
        return (common + to_g(vup_r), common + to_g(vdn_r)), parts

    def bands_and_density(veff):
        """Both channels' bands in veff = (v_up, v_dn), one Fermi level, the
        new (rho, m)."""
        spins = (0, 1) if magnetic else (0,)
        evals = np.empty((len(ks), 2, num_bands))
        kept = [[None, None] for _ in ks]
        vflat, dmat = {}, {}
        for s in spins:
            vflat[s] = to_box(veff[s]).ravel()
            # D_sigma = D_ion + int v_sigma(r) Q(r - tau) d^3r, diagonal
            dmat[s] = np.concatenate([
                D_ION + np.real(np.sum((veff[s] * np.conj(ph))[None, :]
                                       * qaug, 1)) for ph in phase])

        def solve(job):
            s, k = job[0], ks[job[1]]
            b = k["beta"]
            h = (vflat[s][k["diff"]] + np.diag(k["kin"])
                 + (b.T * dmat[s]) @ b.conj())
            sm = np.eye(len(k["kin"])) + (b.T * qmat) @ b.conj()
            return eigh(h, sm, subset_by_index=[0, num_bands - 1])

        jobs = [(s, ik) for s in spins for ik in range(len(ks))]
        if workers > 1:
            with ThreadPoolExecutor(workers) as pool:
                solved = list(pool.map(solve, jobs))
        else:
            solved = [solve(j) for j in jobs]
        for (s, ik), (ev, c) in zip(jobs, solved):
            evals[ik, s], kept[ik][s] = ev, c
        if not magnetic:
            evals[:, 1] = evals[:, 0]
        mu, occ = fermi(evals, wk, nel, smearing_width)
        new = []
        e_kin = e_nl = 0.0
        for s in spins:
            rho_r = np.zeros(wdims)
            dens = np.zeros(NPROJ * nat)
            for ik, (k, w) in enumerate(zip(ks, wk)):
                c = kept[ik][s]
                p = k["beta"].conj() @ c  # <beta|psi>, [proj, band]
                wf = w * occ[ik, s]
                dens += np.real(np.sum(np.abs(p) ** 2 * wf[None, :], axis=1))
                e_kin += np.sum(wf * (k["kin"] @ np.abs(c) ** 2))
                for n in range(num_bands):
                    box = np.zeros(wdims, complex)
                    box[k["wbox"]] = c[:, n]
                    rho_r += wf[n] * np.abs(np.fft.ifftn(box)) ** 2
            e_nl += np.sum(dens * dion)
            rho_r *= rho_r.size ** 2 / omega
            rho = (np.fft.fftn(rho_r) / rho_r.size)[sphere_in_wbox]
            # |rho_ps(G)| vanishes beyond 2 gk, which the work box holds
            rho = np.where(glen <= 2 * gk_cutoff + 1e-8, rho, 0)
            for a in range(nat):
                rho = rho + phase[a] * (
                    dens[NPROJ * a:NPROJ * (a + 1)] @ qaug) / omega
            new.append(rho)
        if not magnetic:
            new.append(new[0])
            e_kin, e_nl = 2 * e_kin, 2 * e_nl
        return new[0] + new[1], new[0] - new[1], {
            "kinetic": e_kin, "nonlocal": e_nl, "efermi": mu,
            "evals": evals, "occ": occ}

    # Anderson mixing of [rho(G); m(G)]
    hist_x, hist_f = [], []
    beta_mix, depth = 0.6, 8
    out = None
    x = np.concatenate([rho_g, mag_g])
    for it in range(1, max_iter + 1):
        veff, _ = potential(x[:ng], x[ng:])
        rho_out, mag_out, band = bands_and_density(veff)
        _, parts = potential(rho_out, mag_out)
        energy = (band["kinetic"] + band["nonlocal"] + parts["local"]
                  + parts["hartree"] + parts["xc"] + e_ewald)
        resid = np.concatenate([rho_out, mag_out]) - x
        rms = math.sqrt(np.sum(np.abs(resid) ** 2) / ng)
        nel_out = rho_out[ig0].real * omega
        moment = mag_out[ig0].real * omega
        say(f"it {it:2d}  E {energy:.12f}  rms {rms:.3e}  N {nel_out:.10f}"
            f"  M {moment:.8f}")
        occ = band["occ"]
        out = {"energy_total_ha": energy, "moment_total_ub": moment,
               "rms": rms, "iterations": it,
               "electrons": nel_out, "ewald": e_ewald, **parts,
               "kinetic": band["kinetic"], "nonlocal": band["nonlocal"],
               "efermi": band["efermi"], "box": list(dims),
               "num_gvec": ng, "num_kpoints": len(kpts),
               "num_spin_channels_solved": 2 if magnetic else 1,
               # bands with more than 1e-6 of an electron at some k-point,
               # and the largest occupation of the last band, a channel
               "bands_occupied": [int((occ[:, s] > 1e-6).sum(1).max())
                                  for s in (0, 1)],
               "last_band_occupation": [float(occ[:, s, -1].max())
                                        for s in (0, 1)],
               "band_energies_gamma": band["evals"][0].tolist()}
        if rms < density_tol:
            out["converged"] = True
            return out
        hist_x.append(x)
        hist_f.append(resid)
        hist_x, hist_f = hist_x[-depth:], hist_f[-depth:]
        xm, f = x, resid
        if len(hist_f) > 1:
            df = np.array([hist_f[-1] - h for h in hist_f[:-1]])
            dx = np.array([hist_x[-1] - h for h in hist_x[:-1]])
            a = np.real(df.conj() @ df.T)
            rhs = np.real(df.conj() @ resid)
            gam = np.linalg.lstsq(a, rhs, rcond=1e-12)[0]
            xm = x - gam @ dx
            f = resid - gam @ df
        x = xm + beta_mix * f
    out["converged"] = False
    return out


if __name__ == "__main__":
    import argparse
    import json
    import time

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--ngridk", type=int, default=4)
    ap.add_argument("--gk", type=float, default=6.0)
    ap.add_argument("--pw", type=float, default=20.0)
    ap.add_argument("--bands", type=int, default=16)
    ap.add_argument("--smearing", type=float, default=0.025)
    ap.add_argument("--moment", type=float, default=2.0,
                    help="starting moment an atom; 0: the non-magnetic state")
    ap.add_argument("--workers", type=int, default=1)
    a = ap.parse_args()
    t0 = time.time()
    r = scf((a.ngridk,) * 3, a.gk, a.pw, a.bands, a.smearing,
            start_moment=a.moment, workers=a.workers, log=print)
    r["wall_s"] = round(time.time() - t0, 1)
    print(json.dumps(r))
