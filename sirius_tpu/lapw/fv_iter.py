"""Matrix-free first-variational LAPW operator + iterative Davidson solve.

Re-design of the reference's apply_fv_h_o (hamiltonian.hpp:217-349) and the
iterative FP diagonalization (diagonalize_fp.hpp:271): H and O are applied
to trial-vector blocks without ever forming the (nG+nlo)^2 matrices.

TPU-shaped decomposition of the dense assembly (lapw/fv.py assemble_fv):

  interstitial  theta / V.theta / ZORA-kinetic convolutions -> FFT pairs
                (the kinetic (G+k).(G'+k) factor splits over 3 cartesian
                gradient components exactly like the mGGA tau operator)
  MT spherical  C ov C^H and C hs C^H sandwiches -> einsums over the
                matching coefficients C [nG, lmmax, 2]
  MT nonsph.    conj(W) V W^T with the small per-atom V [nidx, nidx]
  apw-lo / lo-lo  small dense couplings

Everything is jnp inside one stable apply function driven by the SAME
generalized-Davidson driver as the plane-wave path (solvers/davidson.py),
so the dense diagonalize_fv becomes the verification fallback
(VERDICT r4 item 9). The overlap's near-singular APW directions are handled
by the driver's rank-revealing orthogonalization — the iterative analogue
of the reference's num_singular guard (diagonalize_fp.hpp:238).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from sirius_tpu.core.sht import lm_index, num_lm


class FvParams(NamedTuple):
    """Per-k matrix-free fv operator data (pytree of jnp arrays)."""

    # interstitial real-space boxes
    theta_r: jax.Array       # [n1,n2,n3] step function
    vtheta_r: jax.Array      # [n1,n2,n3] veff * theta
    kin_r: jax.Array         # [n1,n2,n3] theta (or theta/M for ZORA/IORA)
    fft_index: jax.Array     # [nG] int32 into the flat box
    gkc: jax.Array           # [nG, 3] cartesian G+k
    # per-atom MT data, stacked over atoms with a common lmmax
    C: jax.Array             # [nat, nG, lmmax, 2] matching coefficients
    ovl: jax.Array           # [nat, lmmax, 2, 2] radial overlaps per lm
    hsl: jax.Array           # [nat, lmmax, 2, 2] spherical-H per lm
    # nonspherical MT sandwich, W maps basis -> MT expansion entries
    V: jax.Array             # [nat, nidx, nidx] (zero-padded)
    Wlo: jax.Array           # [nat, nlo_tot, nidx] lo rows of W
    # apw-lo spherical couplings: value at the lo's (lm) for each lo col
    lo_lm: jax.Array         # [nlo_tot] int lm of each lo column
    lo_atom: jax.Array       # [nlo_tot] int atom of each lo column
    lo_ou: jax.Array         # [nlo_tot] <u|lo>, <udot|lo>, h analogues
    lo_od: jax.Array
    lo_hu: jax.Array
    lo_hd: jax.Array
    lo_o: jax.Array          # [nlo_tot, nlo_tot] lo-lo overlap (same atom/lm)
    lo_h: jax.Array          # [nlo_tot, nlo_tot]


def build_fv_params(gk_millers, k_frac, lattice, positions, rmt_by_atom,
                    basis_by_atom, v_mt_lm_by_atom, theta_r, veff_r,
                    kin_r, dims, omega) -> FvParams:
    """Assemble the small per-atom pieces (host, numpy) — the same
    ingredients the dense assemble_fv consumes, kept unreduced."""
    from sirius_tpu.lapw.basis import matching_coefficients
    from sirius_tpu.lapw.density_fp import mt_index
    from sirius_tpu.lapw.fv import gaunt_hybrid
    from sirius_tpu.lapw.quad import radial_weights

    recip = 2.0 * np.pi * np.linalg.inv(lattice).T
    gk_cart = (gk_millers + k_frac) @ recip
    ng = len(gk_millers)
    nat = len(positions)

    # the stacked layout (C, ovl, W slots) assumes ONE lmax_apw across
    # atoms — true for every caller (parameters.lmax_apw is global); the
    # dense assemble_fv would support per-atom sizes, so fail loudly here
    # rather than silently truncating if that ever changes
    lmaxes = {b.lmax_apw for b in basis_by_atom}
    if len(lmaxes) != 1:
        raise NotImplementedError(
            f"matrix-free fv needs a common lmax_apw, got {sorted(lmaxes)}; "
            "use the dense solver (iterative_solver.type=exact)"
        )
    lmax = basis_by_atom[0].lmax_apw
    lmmax = num_lm(lmax)

    lo_index = []
    for ia in range(nat):
        for ilo, lof in enumerate(basis_by_atom[ia].lo):
            for m in range(-lof.l, lof.l + 1):
                lo_index.append((ia, ilo, lof.l, m))
    nlo = len(lo_index)

    C = np.zeros((nat, ng, lmmax, 2), dtype=np.complex128)
    ovl = np.zeros((nat, lmmax, 2, 2))
    hsl = np.zeros((nat, lmmax, 2, 2))
    nidx_max = 0
    per_atom_nidx = []
    for ia in range(nat):
        b = basis_by_atom[ia]
        _, lm_of, _ = mt_index(b, lmax)
        per_atom_nidx.append(len(lm_of))
        nidx_max = max(nidx_max, len(lm_of))
    V = np.zeros((nat, nidx_max, nidx_max), dtype=np.complex128)
    Wlo = np.zeros((nat, nlo, nidx_max), dtype=np.complex128)
    lo_lm = np.zeros(nlo, dtype=np.int32)
    lo_atom = np.zeros(nlo, dtype=np.int32)
    lo_ou = np.zeros(nlo)
    lo_od = np.zeros(nlo)
    lo_hu = np.zeros(nlo)
    lo_hd = np.zeros(nlo)
    lo_o = np.zeros((nlo, nlo))
    lo_h = np.zeros((nlo, nlo))

    for ia in range(nat):
        b = basis_by_atom[ia]
        r = b.r
        A, B = matching_coefficients(
            gk_cart, positions[ia], gk_millers, k_frac, rmt_by_atom[ia],
            b, omega,
        )
        C[ia] = np.stack([A, B], axis=2)
        ov = np.zeros((lmax + 1, 2, 2))
        hs = np.zeros((lmax + 1, 2, 2))
        for l in range(lmax + 1):
            for i, fi in enumerate(b.aw[l]):
                for jj, fj in enumerate(b.aw[l]):
                    ov[l, i, jj] = b.overlap(fi, fj)
                    hs[l, i, jj] = b.h_sph(fi, fj)
        l_of_lm = np.concatenate([[l] * (2 * l + 1) for l in range(lmax + 1)])
        ovl[ia] = ov[l_of_lm]
        hsl[ia] = hs[l_of_lm]

        v_lm = v_mt_lm_by_atom[ia]
        if v_lm is not None and np.abs(v_lm[1:]).max() > 1e-14:
            lmax_pot = int(np.sqrt(v_lm.shape[0])) - 1
            gh = gaunt_hybrid(lmax, lmax_pot, lmax)
            rf, lm_of, rf_of = mt_index(b, lmax)
            nidx = len(lm_of)
            wr2 = radial_weights(r) * r * r
            F = np.stack(rf)
            RI = np.einsum("ax,Lx,bx,x->abL", F, v_lm, F, wr2, optimize=True)
            RI[:, :, 0] = 0.0
            GG = gh[lm_of[:, None], :, lm_of[None, :]]
            V[ia, :nidx, :nidx] = np.einsum(
                "pqL,pqL->pq", GG, RI[rf_of[:, None], rf_of[None, :], :]
            )
        # lo rows of W (APW rows are handled through C in the apply)
        kk = 2 * lmmax
        for col, (ja, ilo, l, m) in enumerate(lo_index):
            if ja == ia:
                Wlo[ia, col, kk] = 1.0
                kk += 1

    for col, (ja, ilo, l, m) in enumerate(lo_index):
        b = basis_by_atom[ja]
        lof = b.lo[ilo]
        lo_lm[col] = lm_index(l, m)
        lo_atom[col] = ja
        lo_ou[col] = b.overlap(b.aw[l][0], lof)
        lo_od[col] = b.overlap(b.aw[l][1], lof)
        lo_hu[col] = b.h_sph(b.aw[l][0], lof)
        lo_hd[col] = b.h_sph(b.aw[l][1], lof)
        for col2, (ja2, ilo2, l2, m2) in enumerate(lo_index):
            if ja2 == ja and l2 == l and m2 == m:
                lof2 = b.lo[ilo2]
                lo_o[col, col2] = b.overlap(lof, lof2)
                lo_h[col, col2] = b.h_sph(lof, lof2)

    # flat index of each G-vector in the FFT box
    i0 = np.mod(gk_millers[:, 0], dims[0])
    i1 = np.mod(gk_millers[:, 1], dims[1])
    i2 = np.mod(gk_millers[:, 2], dims[2])
    fft_index = (i0 * dims[1] + i1) * dims[2] + i2
    asx = lambda a: jnp.asarray(a)
    return FvParams(
        theta_r=asx(theta_r), vtheta_r=asx(veff_r * theta_r),
        kin_r=asx(kin_r if kin_r is not None else theta_r),
        fft_index=jnp.asarray(fft_index.astype(np.int32)),
        gkc=asx(gk_cart),
        C=asx(C), ovl=asx(ovl), hsl=asx(hsl), V=asx(V), Wlo=asx(Wlo),
        lo_lm=jnp.asarray(lo_lm), lo_atom=jnp.asarray(lo_atom),
        lo_ou=asx(lo_ou), lo_od=asx(lo_od), lo_hu=asx(lo_hu),
        lo_hd=asx(lo_hd), lo_o=asx(lo_o), lo_h=asx(lo_h),
    )


def apply_fv_h_o(p: FvParams, x: jax.Array):
    """(H x, O x) for a trial block x [nb, nG + nlo] — matrix-free."""
    dims = p.theta_r.shape
    n = dims[0] * dims[1] * dims[2]
    ng = p.gkc.shape[0]
    nlo = p.lo_lm.shape[0]
    nat, _, lmmax, _ = p.C.shape
    cg = x[:, :ng]
    clo = x[:, ng:]
    batch = cg.shape[:-1]

    def conv(field_r, c):
        box = jnp.zeros(batch + (n,), dtype=c.dtype).at[..., p.fft_index].add(c)
        fr = jnp.fft.ifftn(box.reshape(batch + dims), axes=(-3, -2, -1))
        return (
            jnp.fft.fftn(fr * field_r, axes=(-3, -2, -1))
            .reshape(batch + (n,))[..., p.fft_index]
        )

    # interstitial: O += theta conv; H += V.theta conv + kinetic
    ox_g = conv(p.theta_r, cg)
    hx_g = conv(p.vtheta_r, cg)
    for c in range(3):
        hx_g = hx_g + 0.5 * p.gkc[:, c] * conv(p.kin_r, p.gkc[:, c] * cg)

    # MT spherical sandwiches: O = conj(C) ov C^T over (m, i) blocks, so the
    # column contraction is UNconjugated and the row map conjugated
    # (dense: O[g,h] = conj(C)[g,m,i] ovl[m,i,j] C[h,m,j])
    F = jnp.einsum("agmj,bg->bamj", p.C, cg)
    ox_g = ox_g + jnp.einsum("agmi,amij,bamj->bg", jnp.conj(p.C), p.ovl, F)
    hx_g = hx_g + jnp.einsum("agmi,amij,bamj->bg", jnp.conj(p.C), p.hsl, F)

    # nonspherical MT: y = conj(W) V W^T x with W = [C-part | lo rows]
    # MT expansion vector per atom: t[b, a, p] with p = (2*lmmax APW slots,
    # then lo slots); APW slots interleave (u, udot) per lm
    t_apw = F.reshape(F.shape[0], nat, lmmax * 2)  # (m, i) -> 2m+i order
    # reorder (m, i) from [m, i] blocks: F is [b, a, m, i] with i fastest ->
    # matches W's interleaved layout [2m, 2m+1]
    t_lo = jnp.einsum("alp,bl->bap", p.Wlo, clo)
    t = jnp.concatenate([t_apw, t_lo[..., 2 * lmmax:]], axis=-1) \
        if p.V.shape[-1] > 2 * lmmax else t_apw[..., : p.V.shape[-1]]
    vt = jnp.einsum("apq,baq->bap", p.V, t)
    # back: APW part via conj(C), lo part via conj(Wlo)
    vt_apw = vt[..., : 2 * lmmax].reshape(F.shape[0], nat, lmmax, 2)
    hx_g = hx_g + jnp.einsum("agmi,bami->bg", jnp.conj(p.C), vt_apw)
    hx_lo_ns = jnp.einsum("alp,bap->bl", jnp.conj(p.Wlo), vt)

    # apw-lo spherical couplings
    # column side: (H x)_G += conj(A[:,lm]) hu clo + conj(B[:,lm]) hd clo
    Asel = jnp.take_along_axis(
        p.C[p.lo_atom, :, :, 0], p.lo_lm[:, None, None], axis=2
    )[..., 0]  # [nlo, nG]
    Bsel = jnp.take_along_axis(
        p.C[p.lo_atom, :, :, 1], p.lo_lm[:, None, None], axis=2
    )[..., 0]
    ox_g = ox_g + jnp.einsum(
        "lg,l,bl->bg", jnp.conj(Asel), p.lo_ou, clo
    ) + jnp.einsum("lg,l,bl->bg", jnp.conj(Bsel), p.lo_od, clo)
    hx_g = hx_g + jnp.einsum(
        "lg,l,bl->bg", jnp.conj(Asel), p.lo_hu, clo
    ) + jnp.einsum("lg,l,bl->bg", jnp.conj(Bsel), p.lo_hd, clo)
    # row side (conjugate transpose)
    ox_lo = jnp.einsum("lg,l,bg->bl", Asel, p.lo_ou, cg) + jnp.einsum(
        "lg,l,bg->bl", Bsel, p.lo_od, cg
    )
    hx_lo = jnp.einsum("lg,l,bg->bl", Asel, p.lo_hu, cg) + jnp.einsum(
        "lg,l,bg->bl", Bsel, p.lo_hd, cg
    )
    # lo-lo
    ox_lo = ox_lo + clo @ p.lo_o.T
    hx_lo = hx_lo + clo @ p.lo_h.T + hx_lo_ns

    return (
        jnp.concatenate([hx_g, hx_lo], axis=-1),
        jnp.concatenate([ox_g, ox_lo], axis=-1),
    )


def fv_diag(p: FvParams):
    """(h_diag, o_diag) preconditioner diagonals for the davidson driver."""
    ng = p.gkc.shape[0]
    ekin = 0.5 * jnp.sum(p.gkc * p.gkc, axis=1)
    th0 = jnp.real(jnp.mean(p.theta_r))
    v0 = jnp.real(jnp.mean(p.vtheta_r))
    # MT diagonal contribution of the spherical sandwiches
    mt_o = jnp.einsum("agmi,amij,agmj->g", jnp.conj(p.C), p.ovl, p.C).real
    mt_h = jnp.einsum("agmi,amij,agmj->g", jnp.conj(p.C), p.hsl, p.C).real
    h_g = ekin * th0 + v0 + mt_h
    o_g = th0 + mt_o
    o_lo = jnp.diag(p.lo_o)
    h_lo = jnp.diag(p.lo_h)
    return (
        jnp.concatenate([h_g, h_lo]),
        jnp.concatenate([o_g, jnp.maximum(o_lo, 1e-8)]),
    )


def davidson_fv(p: FvParams, nev: int, num_steps: int = 30,
                res_tol: float = 1e-8, x0=None, seed: int = 7):
    """Iterative lowest-nev solve of the matrix-free fv problem.

    Returns (evals [nev], X [nev, ntot], res_norms). The dense
    diagonalize_fv is the verification fallback for this path."""
    from sirius_tpu.solvers.davidson import davidson

    ng = p.gkc.shape[0]
    ntot = ng + p.lo_lm.shape[0]
    if x0 is None:
        rng = np.random.default_rng(seed)
        x0 = rng.standard_normal((nev, ntot)) + 1j * rng.standard_normal(
            (nev, ntot)
        )
        # damp high-G components
        damp = 1.0 / (1.0 + np.asarray(0.5 * np.sum(np.asarray(p.gkc) ** 2, axis=1)))
        x0[:, :ng] *= damp
        x0 = jnp.asarray(x0)
    h_diag, o_diag = fv_diag(p)
    mask = jnp.ones(ntot)
    ev, x, rn, _ = davidson(
        apply_fv_h_o, p, x0, h_diag, o_diag, mask,
        num_steps=num_steps, res_tol=res_tol, by_energy=False,
    )
    return ev, x, rn
