"""Ultrasoft/PAW augmentation operator Q(G) and its contractions.

Reference: src/density/augmentation_operator.cpp (Q_{xi xi'}(G) tables),
Density::generate_rho_aug (density.cpp:1395, GPU kernels sum_q_pw_dm_pw.cu)
and Potential::generate_D_operator_matrix (generate_d_operator_matrix.cpp:26).

Conventions (validated against the reference):
  Q_{xi1 xi2}(G) = (4 pi / Omega) sum_{lm3} (-i)^{l3} R_{lm3}(^G)
                   <R_{lm1} R_{lm2} R_{lm3}>  RI_aug(rf12, l3, |G|)
  RI_aug(rf12, l3, q) = int j_{l3}(q r) Q^{l3}_{rf1 rf2}(r) dr
                        (species files store Q(r) including the r^2 factor)
  q_mtrx = Omega * Q(G=0)            (augmentation_operator.cpp:100-110)
  rho_aug(G) = sum_a sum_{xi1 xi2} n^a_{xi1 xi2} Q_{xi1 xi2}(G) e^{-i G r_a}
  D^a_{xi1 xi2} = d_ion + Omega * sum_G conj(V_eff(G)) Q_{xi1 xi2}(G) e^{-i G r_a}
  n^a_{xi1 xi2} = sum_{k,s,b} w_k f conj(<beta_xi1|psi>) <beta_xi2|psi>

Only the packed upper triangle of (xi1 <= xi2) is stored, mirroring the
reference's nqlm = nbf(nbf+1)/2 layout.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from sirius_tpu.core.gvec import AtomPhases, Gvec, minus_phases
from sirius_tpu.core.radial import RadialIntegralTable
from sirius_tpu.core.sht import gaunt_rlm, lm_index, num_lm, ylm_real
from sirius_tpu.crystal.unit_cell import UnitCell


@dataclasses.dataclass
class AugmentationType:
    """Per-species augmentation tables."""

    q_pw: np.ndarray  # (nqlm, ng) complex: Q_{packed}(G), no atom phase
    xi1: np.ndarray  # (nqlm,) unpacked pair indices
    xi2: np.ndarray
    q_mtrx: np.ndarray  # (nbf, nbf) = Omega * Q(0)


@dataclasses.dataclass
class Augmentation:
    """One entry an atom type, None where the type has no augmentation
    (the context's species tables build and keep them: build_type)."""

    per_type: list[AugmentationType | None]


def aug_radial_tables(t, qmax: float) -> list:
    """Per-l3 spline tables of RI_aug(packed rf12, l3, q), evaluable at
    arbitrary q <= qmax (used for shells here; for strained |G| in the
    stress calculator)."""
    lmax3 = 2 * t.lmax_beta
    nbrf = t.num_beta
    nrf12 = nbrf * (nbrf + 1) // 2
    qfuncs = np.zeros((nrf12, lmax3 + 1, len(t.r)))
    for ch in t.augmentation:
        i, j = min(ch.i, ch.j), max(ch.i, ch.j)
        idx = j * (j + 1) // 2 + i
        qfuncs[idx, ch.l, : len(ch.qr)] = ch.qr
    return [
        RadialIntegralTable.build(
            t.r, qfuncs[:, l3, :], np.full(nrf12, l3), qmax=qmax, m=0
        )
        for l3 in range(lmax3 + 1)
    ]


def build_type(t, gvec: Gvec, omega: float) -> AugmentationType:
    nbf = t.num_beta_lm
    qshell = np.sqrt(gvec.shell_g2)
    tabs = aug_radial_tables(t, qmax=qshell[-1] + 1e-9)
    q_pw = q_pw_at(t, tabs, gvec.gcart, omega)
    nqlm = nbf * (nbf + 1) // 2
    xi1 = np.zeros(nqlm, dtype=np.int32)
    xi2 = np.zeros(nqlm, dtype=np.int32)
    for b in range(nbf):
        for a in range(b + 1):
            xi1[b * (b + 1) // 2 + a] = a
            xi2[b * (b + 1) // 2 + a] = b
    q0 = q_pw[:, 0].real * omega
    q_mtrx = np.zeros((nbf, nbf))
    q_mtrx[xi2, xi1] = q0
    q_mtrx[xi1, xi2] = q0
    return AugmentationType(q_pw=q_pw, xi1=xi1, xi2=xi2, q_mtrx=q_mtrx)


def q_pw_at(t, tabs, gcart: np.ndarray, omega: float) -> np.ndarray:
    """Q_{packed}(G) for arbitrary Cartesian G vectors (no atom phase):
    the build_type formula with the radial tables evaluated at |G| and the
    real harmonics at ^G — the strained-lattice evaluation path of the
    stress calculator (reference sigma_us uses d/dq tables instead,
    stress.cpp)."""
    lb = t.lmax_beta
    lmax3 = 2 * lb
    nbf = t.num_beta_lm
    idxrf, ls, ms = t.beta_lm_table()
    glen = np.linalg.norm(gcart, axis=1)
    rhat = np.where(
        glen[:, None] > 1e-30,
        gcart / np.maximum(glen, 1e-30)[:, None],
        np.array([0.0, 0, 1.0]),
    )
    rlm3 = ylm_real(lmax3, rhat)
    gaunt = gaunt_rlm(lb, lb, lmax3)
    mi_l3 = np.asarray([(-1j) ** l for l in range(lmax3 + 1)])
    l_of_lm3 = np.asarray([int(np.sqrt(lm)) for lm in range(num_lm(lmax3))])
    ri = np.stack([tabs[l3](glen) for l3 in range(lmax3 + 1)], axis=1)
    nqlm = nbf * (nbf + 1) // 2
    q_pw = np.zeros((nqlm, len(glen)), dtype=np.complex128)
    pref = 4.0 * np.pi / omega
    for b in range(nbf):
        for a in range(b + 1):
            idx12 = b * (b + 1) // 2 + a
            ra, rb = int(idxrf[a]), int(idxrf[b])
            rf12 = max(ra, rb) * (max(ra, rb) + 1) // 2 + min(ra, rb)
            lm_a = lm_index(int(ls[a]), int(ms[a]))
            lm_b = lm_index(int(ls[b]), int(ms[b]))
            acc = np.zeros(len(glen), dtype=np.complex128)
            for lm3 in np.nonzero(np.abs(gaunt[lm_a, lm_b]) > 1e-14)[0]:
                l3 = l_of_lm3[lm3]
                acc += (
                    mi_l3[l3]
                    * gaunt[lm_a, lm_b, lm3]
                    * rlm3[:, lm3]
                    * ri[rf12, l3, :]
                )
            q_pw[idx12] = pref * acc
    return q_pw


def rho_aug_g(
    uc: UnitCell,
    gvec: Gvec,
    aug: Augmentation,
    dm: list,  # per-atom (nbf_a, nbf_a) complex density-matrix blocks
    q_pw_by_type: list | None = None,  # optional Q(G) override (e.g. the
    # strained-lattice tables of the stress calculator)
    phases: AtomPhases | None = None,  # the context's atom-phase table
) -> np.ndarray:
    """Augmentation charge rho_aug(G) on the fine set."""
    out = np.zeros(gvec.num_gvec, dtype=np.complex128)
    for it, at in enumerate(aug.per_type):
        if at is None:
            continue
        atoms = uc.atoms_of_type(it)
        q_pw = at.q_pw if q_pw_by_type is None else q_pw_by_type[it]
        # packed real dm with factor 2 off-diagonal:
        # sum_{xi1 xi2} n Q = sum_packed w * Re(n) * Q  (n hermitian, Q sym)
        w = np.where(at.xi1 == at.xi2, 1.0, 2.0)
        dmp = np.stack(
            [w * np.real(dm[ia][at.xi1, at.xi2]) for ia in atoms]
        )  # (na_t, nqlm)
        ph = minus_phases(phases, gvec.millers, uc.positions, atoms)  # (ng, na_t)
        # (ng, na_t) @ (na_t, nqlm) -> then contract with q_pw
        out += np.einsum("ga,aq,qg->g", ph, dmp, q_pw, optimize=True)
    return out


def d_operator(
    uc: UnitCell,
    gvec: Gvec,
    aug: Augmentation,
    veff_g: np.ndarray,
    beta,  # BetaProjectors (bare D + packed block layout)
    include_dion: bool = True,
    phases: AtomPhases | None = None,  # the context's atom-phase table
) -> np.ndarray:
    """Full D matrix: bare D_ion plus the augmentation term
    Omega sum_G conj(V_eff(G)) Q(G) e^{-i G r_a} per atom.

    include_dion=False returns the augmentation integral alone — the
    magnetic-field components D(Bx/By/Bz) of the non-collinear D operator
    (reference generate_d_operator_matrix.cpp loops iv over all field
    components; only iv=0 carries the ionic part)."""
    d = beta.dion.copy() if include_dion else np.zeros_like(beta.dion)
    omega = uc.omega
    vq_by_atom = {}
    for it, at in enumerate(aug.per_type):
        if at is None:
            continue
        atoms = uc.atoms_of_type(it)
        ph = minus_phases(phases, gvec.millers, uc.positions, atoms)  # (ng, na_t)
        vq = omega * np.real(at.q_pw @ (np.conj(veff_g)[:, None] * ph))  # (nqlm, na_t)
        for j, ia in enumerate(atoms):
            vq_by_atom[ia] = (at, vq[:, j])
    for ia, off, nbf in beta.atom_blocks(uc):
        if ia not in vq_by_atom:
            continue
        at, v = vq_by_atom[ia]
        block = np.zeros((nbf, nbf))
        block[at.xi1, at.xi2] = v
        block[at.xi2, at.xi1] = v
        d[off : off + nbf, off : off + nbf] += block
    return d


# ---------------------------------------------------------------------------
# Device-resident augmentation (jit twins of rho_aug_g / d_operator for the
# fused SCF step). The ragged per-type structure is pre-flattened into
# dense tables once; the per-iteration contractions become pure einsums and
# flat-index scatters over the full [nbeta, nbeta] D matrix.
# ---------------------------------------------------------------------------


def build_aug_device_tables(uc: UnitCell, gvec: Gvec, aug: Augmentation,
                            beta, phases: AtomPhases | None = None
                            ) -> list[dict]:
    """Per-type numpy tables for rho_aug_g_device / d_operator_device.

    gidx flattens the (off + xi1, off + xi2) positions of each atom's
    packed pairs into the [nbeta * nbeta] D matrix (the upper/packed site);
    lo_idx is the mirrored (off + xi2, off + xi1) site with lo_mask zeroing
    the diagonal pairs — together they reproduce the host d_operator's
    symmetric block fill without double-counting xi1 == xi2."""
    nbeta = beta.num_beta_total
    offs = {ia: off for ia, off, _ in beta.atom_blocks(uc)}
    out = []
    for it, at in enumerate(aug.per_type):
        if at is None:
            continue
        atoms = uc.atoms_of_type(it)
        ph = minus_phases(phases, gvec.millers, uc.positions, atoms)
        gidx = np.stack([
            (offs[ia] + at.xi1).astype(np.int64) * nbeta + (offs[ia] + at.xi2)
            for ia in atoms
        ]).astype(np.int32)  # (na_t, nqlm)
        lo_idx = np.stack([
            (offs[ia] + at.xi2).astype(np.int64) * nbeta + (offs[ia] + at.xi1)
            for ia in atoms
        ]).astype(np.int32)
        out.append({
            "q_re": np.real(at.q_pw),
            "q_im": np.imag(at.q_pw),
            "ph_re": np.real(ph),
            "ph_im": np.imag(ph),
            "w": np.where(at.xi1 == at.xi2, 1.0, 2.0),
            "gidx": gidx,
            "lo_idx": lo_idx,
            "lo_mask": (at.xi1 != at.xi2).astype(np.float64),
        })
    return out


def rho_aug_g_device(dm: jnp.ndarray, tables: list[dict],
                     ng: int) -> jnp.ndarray:
    """Jit-safe rho_aug_g over all spin channels at once: dm complex
    [ns, nbeta, nbeta] (full matrix, inside the compiled program), returns
    [ns, ng] complex."""
    ns = dm.shape[0]
    dm_flat = dm.reshape(ns, -1)
    out = jnp.zeros((ns, ng), dtype=dm.dtype)
    for t in tables:
        q = jax.lax.complex(t["q_re"], t["q_im"])
        ph = jax.lax.complex(t["ph_re"], t["ph_im"])
        dmp = t["w"][None, None, :] * jnp.real(dm_flat[:, t["gidx"]])
        out = out + jnp.einsum("ga,saq,qg->sg", ph, dmp.astype(q.dtype), q)
    return out


def d_operator_device(veff_g: jnp.ndarray, dion: jnp.ndarray,
                      tables: list[dict], omega: float) -> jnp.ndarray:
    """Jit-safe d_operator for one effective-potential channel: veff_g
    complex [ng], dion real [nbeta, nbeta] bare matrix; returns the full
    real D [nbeta, nbeta]."""
    nbeta = dion.shape[0]
    d = dion.reshape(-1)
    for t in tables:
        q = jax.lax.complex(t["q_re"], t["q_im"])
        ph = jax.lax.complex(t["ph_re"], t["ph_im"])
        vq = omega * jnp.real(
            jnp.einsum("qg,g,ga->aq", q, jnp.conj(veff_g), ph))  # (na, nqlm)
        vq = vq.astype(d.dtype)
        d = d.at[t["gidx"].reshape(-1)].add(vq.reshape(-1))
        d = d.at[t["lo_idx"].reshape(-1)].add(
            (vq * t["lo_mask"][None, :]).reshape(-1))
    return d.reshape(nbeta, nbeta)
