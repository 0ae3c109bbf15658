"""Atomic-orbital PW coefficients for the LCAO initial subspace
(reference: initialize_subspace.hpp:27 per-k LCAO guess, built from
Radial_integrals_atomic_wf). Same construction as beta projectors:
phi_lm(G+k) = (-i)^l (4 pi / sqrt(Omega)) R_lm(^G+k) RI(|G+k|) e^{-i(G+k).r_a}
with RI(q) = int j_l(q r) chi(r) r dr (files store chi = r*phi)."""

from __future__ import annotations

import numpy as np

from sirius_tpu.core.gvec import GkVec
from sirius_tpu.core.radial import RadialIntegralTable
from sirius_tpu.core.sht import lm_index, ylm_real
from sirius_tpu.crystal.unit_cell import UnitCell
from sirius_tpu.ops.beta import gk_directions


def ao_form(t, qlen: np.ndarray, rhat: np.ndarray, omega: float,
            qmax: float) -> np.ndarray | None:
    """phi_lm(G+k) of one species with no atom phase and no mask:
    (4 pi / sqrt(Omega)) (-i)^l R_lm(^G+k) RI(|G+k|), shape
    [nao_lm, nk, ngk] (one contiguous [nk, ngk] block an orbital, the left
    factor of atomic_orbitals' product as it stands there; ops/beta.beta_form
    is its twin). ``qlen``, ``rhat``: ops/beta.gk_directions. Reads no
    position: a function of the k-spheres and the species' atomic wave
    functions (l, chi)."""
    if not t.atomic_wfs:
        return None
    nk, ngk = qlen.shape
    table = RadialIntegralTable.build(
        t.r, np.stack([w.chi for w in t.atomic_wfs]),
        np.array([w.l for w in t.atomic_wfs]), qmax, m=1)
    ri = table(qlen.reshape(-1)).reshape(len(t.atomic_wfs), nk, ngk)
    rlm = ylm_real(max(w.l for w in t.atomic_wfs), rhat)  # (nk, ngk, nlm)
    pref = 4.0 * np.pi / np.sqrt(omega)
    form = np.empty((t.num_atomic_wf_lm, nk, ngk), dtype=np.complex128)
    xi = 0
    for iw, w in enumerate(t.atomic_wfs):
        for m in range(-w.l, w.l + 1):
            form[xi] = pref * (-1j) ** w.l * rlm[..., lm_index(w.l, m)] * ri[iw]
            xi += 1
    return form


def atomic_orbitals(uc: UnitCell, gkvec: GkVec, qmax: float,
                    forms: list | None = None) -> np.ndarray:
    """Returns (nk, nao_tot, ngk_max) complex orbitals, or (nk, 0, ngk).
    ``forms``: ao_form of every atom type on this k-set, where the caller
    keeps them (the context's species tables); built here otherwise.
    Everything below them reads the atoms' positions."""
    nk, ngk = gkvec.num_kpoints, gkvec.ngk_max
    nao = sum(uc.atom_types[it].num_atomic_wf_lm for it in uc.type_of_atom)
    out = np.zeros((nk, nao, ngk), dtype=np.complex128)
    if nao == 0:
        return out
    if forms is None:
        qlen, rhat = gk_directions(gkvec)
        forms = [ao_form(t, qlen, rhat, uc.omega, qmax)
                 for t in uc.atom_types]
    # phase e^{-i(G+k).r_a}: (G+k).r_a = 2 pi (m + k) . x_a
    mk = gkvec.millers + gkvec.kpoints[:, None, :]
    off = 0
    for ia in range(uc.num_atoms):
        it = uc.type_of_atom[ia]
        if forms[it] is None:
            continue
        phase = np.exp(-2j * np.pi * (mk @ uc.positions[ia]))  # (nk, ngk)
        for xi, form in enumerate(forms[it]):
            out[:, off + xi, :] = form * phase * gkvec.mask
        off += len(forms[it])
    return out
