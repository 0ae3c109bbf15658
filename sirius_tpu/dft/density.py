"""Charge density: initial guess and generation from wave functions.

Reference: src/density/density.cpp (initial_density :137, generate :1105,
add_k_point_contribution_rg :700-760). The reference loops bands with
per-band FFTs and accumulates |psi(r)|^2 with OMP/CUDA kernels
(density_rg.cu); here the whole band block is one batched FFT and the
occupation-weighted reduction is a single einsum, jitted per k-point.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from sirius_tpu.context import SimulationContext
from sirius_tpu.core.fftgrid import g_to_r, r_to_g
from sirius_tpu.obs import spans as obs_spans


def initial_density_g(ctx: SimulationContext) -> np.ndarray:
    """Superposition of free-atom densities, normalized to the electron
    count (reference density.cpp:137 initial_density_pseudo)."""
    rho_g = ctx.rho_atomic_g.copy()
    nel = ctx.unit_cell.num_valence_electrons
    n0 = rho_g[0].real * ctx.unit_cell.omega
    if abs(n0) < 1e-12:
        raise ValueError("free-atom density missing in species files")
    rho_g *= nel / n0
    return rho_g


@partial(jax.jit, static_argnames=("dims",))
def _accumulate_k(
    psi: jax.Array,  # [nspin, nb, ngk]
    occ_w: jax.Array,  # [nspin, nb] occupation * k-weight
    fft_index: jax.Array,
    dims: tuple[int, int, int],
) -> jax.Array:
    """sum_{s,b} occ_w[s,b] |psi_sb(r)|^2 on the coarse box (one batched FFT)."""
    n = dims[0] * dims[1] * dims[2]
    batch = psi.shape[:-1]
    box = jnp.zeros(batch + (n,), dtype=psi.dtype).at[..., fft_index].add(psi)
    fr = jnp.fft.ifftn(box.reshape(batch + dims), axes=(-3, -2, -1)) * n
    return jnp.einsum("sb,sbxyz->xyz", occ_w, jnp.abs(fr) ** 2)


def density_from_coarse_acc(ctx: SimulationContext, acc: np.ndarray) -> np.ndarray:
    """Finalize the per-spin density from the occupation-weighted |psi(r)|^2
    accumulation on the coarse box: divide by Omega, transform to coarse G,
    map to the fine G set. acc: [nspin, n1, n2, n3] real."""
    dims = ctx.fft_coarse.dims
    ns = acc.shape[0]
    out = np.zeros((ns, ctx.gvec.num_gvec), dtype=np.complex128)
    for ispn in range(ns):
        rho_r_coarse = np.asarray(acc[ispn]) / ctx.unit_cell.omega
        rho_g_coarse = np.asarray(
            r_to_g(jnp.asarray(rho_r_coarse, dtype=jnp.complex128),
                   jnp.asarray(ctx.gvec_coarse.fft_index), dims)
        )
        out[ispn, ctx.coarse_to_fine] = rho_g_coarse
    return out


def generate_density_g(
    ctx: SimulationContext,
    psi_all: jnp.ndarray,  # [nk, nspin, nb, ngk_max]
    occ: np.ndarray,  # [nk, nspin, nb]
) -> np.ndarray:
    """Per-spin valence density [nspin, ng_fine] from occupied wave
    functions (unsymmetrized; the SCF symmetrizes the assembled total).

    psi are S-normalized PW coefficients; |psi(r)|^2 accumulated on the
    coarse box, divided by Omega, transformed to coarse G, mapped to fine G.
    """
    dims = ctx.fft_coarse.dims
    nk = ctx.gkvec.num_kpoints
    ns = psi_all.shape[1]
    acc = np.zeros((ns,) + tuple(dims))
    for ispn in range(ns):
        a = jnp.zeros(dims)
        for ik in range(nk):
            ow = jnp.asarray(occ[ik, ispn : ispn + 1] * ctx.kweights[ik])
            a = a + _accumulate_k(
                psi_all[ik, ispn : ispn + 1], ow,
                jnp.asarray(ctx.gkvec.fft_index[ik]), dims,
            )
        acc[ispn] = np.asarray(a)
    return density_from_coarse_acc(ctx, acc)


def atomic_sphere_radii(uc, rmax: float = 2.0) -> np.ndarray:
    """Per-atom non-overlapping sphere radii: half the nearest-neighbor
    distance over periodic images (including an atom's own images, so
    single-atom cells are covered), capped at rmax (reference
    control.rmt_max flavor)."""
    pos = uc.positions_cart()
    ts = np.array(
        np.meshgrid(*[[-1, 0, 1]] * 3, indexing="ij")
    ).reshape(3, -1).T @ uc.lattice
    d = np.linalg.norm(
        pos[:, None, None, :] - pos[None, :, None, :] - ts[None, None, :, :],
        axis=-1,
    )
    d[d < 1e-8] = np.inf
    return np.minimum(0.5 * d.min(axis=(1, 2)), rmax)


def initial_magnetization_vec_g(ctx: SimulationContext) -> np.ndarray:
    """[3, ng] initial (mx, my, mz) from per-atom starting moment vectors.

    Two seeds, selected by settings.smooth_initial_mag exactly like the
    reference (density.cpp initial_density_pseudo):
      - smooth: per-atom Gaussian exp(-G^2/(4 alpha)), alpha = 4 — sharply
        peaked at the atom (~1.4 m e/a0^3 at r=0), which is what gives the
        first iteration a strong exchange splitting on localized shells;
      - default: compact normalized bump w(R, x) = (1 - (x/R)^2) e^{x/R} /
        (3.18866 R^3) inside an atomic sphere."""
    from sirius_tpu.core.radial import sbessel_integral

    uc = ctx.unit_cell
    gv = ctx.gvec
    out = np.zeros((3, gv.num_gvec), dtype=np.complex128)
    if not np.any(np.abs(uc.moments) > 1e-12):
        return out
    smooth = bool(ctx.cfg.settings.smooth_initial_mag)
    rad = atomic_sphere_radii(uc)
    qshell = np.sqrt(gv.shell_g2)
    for ia in range(uc.num_atoms):
        mvec = uc.moments[ia]
        if np.all(np.abs(mvec) < 1e-12):
            continue
        if smooth:
            alpha = 4.0
            ff = np.exp(-gv.shell_g2 / (4.0 * alpha))[gv.shell_idx]
        else:
            r = np.linspace(1e-8, rad[ia], 400)
            w = (1 - (r / rad[ia]) ** 2) * np.exp(r / rad[ia]) / (
                3.1886583903476735 * rad[ia] ** 3
            )
            ff = sbessel_integral(r, 4.0 * np.pi * w, 0, qshell, m=2)[gv.shell_idx]
        phase = np.exp(-2j * np.pi * (gv.millers @ uc.positions[ia]))
        for i in range(3):
            if abs(mvec[i]) > 1e-12:
                out[i] += (mvec[i] / uc.omega) * ff * phase
    return out


def initial_magnetization_g(ctx: SimulationContext) -> np.ndarray:
    """Initial z-magnetization (collinear): z-component of the vector seed."""
    return initial_magnetization_vec_g(ctx)[2]


def symmetrize_pw(
    ctx: SimulationContext, f_g: np.ndarray, axial_z: bool = False
) -> np.ndarray:
    """Symmetrize PW coefficients over the space group.

    f'(r) = (1/N) sum_S f(S^{-1} r) with S: x -> W x + t gives, for
    g' = (W^{-1})^T g = w_k g:
        f'(g') += f(g) e^{-2 pi i g'. t} / N
    (reference symmetrize_pw_function.hpp via Gvec_shells remap). The sphere
    is rotation-invariant so every image lands inside the set; rotation
    tables per op are cached on the context's gvec.

    axial_z: the field is the z-component of an axial vector (collinear
    magnetization / B_xc): each op's contribution carries its spin_sign
    (= det(R) R_zz, reference spin_rotation S(2,2)) — without it AFM
    sublattice-swap ops average the staggered field to zero."""
    out = np.zeros_like(f_g)
    for idx, phase, ssign in sym_rot_cache(ctx):
        np.add.at(out, idx, f_g * (phase * ssign if axial_z else phase))
    return out / ctx.symmetry.num_ops


def sym_rot_cache(ctx: SimulationContext) -> list:
    """Per operation (idx, phase, spin_sign): G-vector ig goes to idx[ig]
    with the phase e^{-2 pi i g'.t}. Built once a context. Each idx row is a
    permutation of the sphere (an image outside it raises)."""
    cache = getattr(ctx, "_sym_rot_cache", None)
    if cache is None:
        gv = ctx.gvec
        cache = []
        for op in ctx.symmetry.ops:
            gm = gv.millers @ op.w_k.T  # rows g' = w_k g
            idx = gv.index_of_millers(gm)
            if idx.min() < 0:
                raise KeyError("a symmetry operation takes a G-vector out "
                               "of the density sphere")
            phase = np.exp(-2j * np.pi * (gm @ op.t))
            cache.append((idx, phase, op.spin_sign))
        ctx._sym_rot_cache = cache
    return cache


def _beta_rotation_blocks(ctx: SimulationContext, op):
    """Per-atom-type block-diagonal Rlm rotation matrices for one symmetry
    op (shared by the collinear and non-collinear dm symmetrizers)."""
    from sirius_tpu.ops.hubbard import rlm_rotation_matrix

    uc = ctx.unit_cell
    dcache: dict = {}
    rot_by_type: dict = {}
    for ia, off, nbf in ctx.beta.atom_blocks(uc):
        it = uc.type_of_atom[ia]
        if it in rot_by_type:
            continue
        t = uc.atom_types[it]
        rmats = []
        for b in t.beta:
            if b.l not in dcache:
                dcache[b.l] = rlm_rotation_matrix(op.rot_cart, b.l)
            rmats.append(dcache[b.l])
        full = np.zeros((nbf, nbf))
        pos = 0
        for m in rmats:
            k = m.shape[0]
            full[pos : pos + k, pos : pos + k] = m
            pos += k
        rot_by_type[it] = full
    return rot_by_type


def symmetrize_density_matrix(ctx: SimulationContext, dm: np.ndarray) -> np.ndarray:
    """Symmetrize the beta-projector density matrix over the space group
    (reference src/symmetry/symmetrize_density_matrix.hpp): the IBZ k-sum
    only yields the full-BZ density matrix after averaging over operations,
    dm'[S a] += D(S) dm[a] D(S)^T per atom block, with D block-diagonal over
    the radial functions (real-harmonic Wigner blocks per l).

    dm: [ns, nbeta_tot, nbeta_tot] complex. Collinear spin channels swap
    under ops whose spin_sign is -1 (AFM sublattice swaps: the reference's
    spin_rotation maps up<->dn there); with spin_sign +1 they transform
    independently. Only the per-atom diagonal blocks are symmetrized and
    returned — inter-atom blocks come back zero (no consumer reads them;
    the reference stores the dm per atom and has no inter-atom blocks at
    all)."""
    sym = ctx.symmetry
    if sym is None or sym.num_ops <= 1:
        return dm
    uc = ctx.unit_cell
    ns = dm.shape[0]
    blocks = list(ctx.beta.atom_blocks(uc))
    off_by_atom = {ia: off for ia, off, _ in blocks}
    out = np.zeros_like(dm)
    for op in sym.ops:
        rot_by_type = _beta_rotation_blocks(ctx, op)
        flip = ns == 2 and op.spin_sign < 0
        for ia, off, nbf in blocks:
            r = rot_by_type[uc.type_of_atom[ia]]
            joff = off_by_atom[int(op.perm[ia])]
            for ispn in range(ns):
                src = (1 - ispn) if flip else ispn
                out[ispn, joff : joff + nbf, joff : joff + nbf] += (
                    r @ dm[src, off : off + nbf, off : off + nbf] @ r.T
                )
    return out / sym.num_ops


def symmetrize_density_matrix_nc(ctx: SimulationContext, dm3: np.ndarray) -> np.ndarray:
    """Non-collinear density-matrix symmetrization.

    dm3: [3, nbeta, nbeta] complex spin components (uu, dd, ud) — the du
    block is the Hermitian conjugate. Decompose per atom into the scalar
    d0 = uu + dd and the AXIAL vector (dx, dy, dz) = (ud + ud^H,
    i(ud - ud^H), uu - dd); the scalar transforms with the Wigner blocks
    alone, the vector additionally rotates with det(R) R (reference
    symmetrize_density_matrix.hpp spin_rotation branch)."""
    sym = ctx.symmetry
    if sym is None or sym.num_ops <= 1:
        return dm3
    uc = ctx.unit_cell
    blocks = list(ctx.beta.atom_blocks(uc))
    off_by_atom = {ia: off for ia, off, _ in blocks}
    out = np.zeros_like(dm3)
    for op in sym.ops:
        rot_by_type = _beta_rotation_blocks(ctx, op)
        srot = np.linalg.det(op.rot_cart) * op.rot_cart  # axial-vector rotation
        for ia, off, nbf in blocks:
            r = rot_by_type[uc.type_of_atom[ia]]
            joff = off_by_atom[int(op.perm[ia])]
            sl_i = slice(off, off + nbf)
            sl_j = slice(joff, joff + nbf)
            uu, dd, ud = dm3[0, sl_i, sl_i], dm3[1, sl_i, sl_i], dm3[2, sl_i, sl_i]
            d0 = uu + dd
            dvec = np.stack([ud + ud.conj().T, 1j * (ud - ud.conj().T), uu - dd])
            d0r = r @ d0 @ r.T
            dvr = np.einsum("ij,jab->iab", srot, [r @ c @ r.T for c in dvec])
            out[0, sl_j, sl_j] += 0.5 * (d0r + dvr[2])
            out[1, sl_j, sl_j] += 0.5 * (d0r - dvr[2])
            out[2, sl_j, sl_j] += 0.5 * (dvr[0] - 1j * dvr[1])
    return out / sym.num_ops


def rho_real_space(ctx: SimulationContext, rho_g: np.ndarray) -> np.ndarray:
    """rho(r) on the fine box."""
    return np.asarray(
        g_to_r(jnp.asarray(rho_g), jnp.asarray(ctx.gvec.fft_index), ctx.gvec.fft.dims)
    ).real


def atomic_moments(ctx: SimulationContext, mag_g: np.ndarray) -> np.ndarray:
    """Integral of m_z inside each atom's non-overlapping sphere (reference
    Density::get_magnetisation MT moments):
    int_{|r-ra|<R} e^{iG.r} dr = e^{iG.ra} (4 pi / G^3)(sin GR - GR cos GR).
    """
    gv = ctx.gvec
    uc = ctx.unit_cell
    glen = np.sqrt(gv.glen2)
    # reference per-atom moments use uniform control.rmt_max spheres
    # (simulation_context.cpp:977); stay non-overlapping within that cap
    radii = atomic_sphere_radii(uc, rmax=ctx.cfg.control.rmt_max)
    out = np.empty(uc.num_atoms)
    for ia in range(uc.num_atoms):
        radius = float(radii[ia])
        gr = glen * radius
        w = np.empty_like(gr)
        small = gr < 1e-8
        w[~small] = 4.0 * np.pi / np.maximum(glen[~small], 1e-30) ** 3 * (
            np.sin(gr[~small]) - gr[~small] * np.cos(gr[~small])
        )
        w[small] = 4.0 * np.pi * radius**3 / 3.0
        phase = np.exp(2j * np.pi * (gv.millers @ uc.positions[ia]))
        out[ia] = float(np.real(mag_g @ (w * phase)))
    return out


# ---------------------------------------------------------------------------
# Device-resident symmetrization (jit twins of symmetrize_pw /
# symmetrize_density_matrix for the fused SCF step). The host variants keep
# python loops over ops with np.add.at; on device the sum over the ops of a
# plane-wave field is pre-contracted into one small matrix a star of
# G-vectors, and the density matrix's into one batched einsum, from tables
# built once a context (symmetry_tables).
# ---------------------------------------------------------------------------


def build_sym_pw_tables(ctx: SimulationContext):
    """Star-block tables for symmetrize_pw_device. An operation permutes the
    sphere inside the stars (orbits) of the group, so the sum over the
    operations is one small matrix a star, summed here on the host in f64:

        f'(g_i) = sum_j M_s[i, j] f(g_j),
        M_s[i, j] = (1/N) sum_{S: S g_j = g_i} e^{-2 pi i g_i . t_S}

    for the members g_1..g_m of star s (m <= N; smaller stars are padded
    with zero rows and columns). The stars are the minor axis of every
    table: ``members`` int32 [m, nstars] (the sphere index of member i of
    star s; a pad points at 0), ``slot`` int32 [ng] (where in the flattened
    [m, nstars] block a G-vector lives), ``m_re``/``m_im`` [m, m, nstars].
    A collinear moment adds ``ax_re``/``ax_im``, the same sum with each
    operation's spin_sign (the z-component of an axial vector).

    The device needs two gathers of about ng elements a call and reads the
    matrices once; the scatter-add of every operation's image, 48 x ng
    elements, cost 0.14 s a call on a TPU v5e at ng = 36 325 (PERF.md
    section 6, PR 38)."""
    cache = sym_rot_cache(ctx)
    ng = ctx.gvec.num_gvec
    nops = len(cache)
    # {S g: S in the group} is g's star, so its least index names it
    label = np.minimum.reduce([c[0] for c in cache])
    order = np.argsort(label, kind="stable")
    first = np.flatnonzero(np.r_[True, np.diff(label[order]) > 0])
    sizes = np.diff(np.r_[first, ng])
    nstars, m = len(first), int(sizes.max())
    star = np.empty(ng, dtype=np.int64)
    pos = np.empty(ng, dtype=np.int64)
    star[order] = np.repeat(np.arange(nstars), sizes)
    pos[order] = np.arange(ng) - np.repeat(first, sizes)
    members = np.zeros((m, nstars), dtype=np.int32)
    members[pos, star] = np.arange(ng)
    mat = np.zeros((m, m, nstars), dtype=np.complex128)
    axial = np.zeros_like(mat) if ctx.num_mag_dims == 1 else None
    for idx, phase, ssign in cache:
        # one operation is a permutation: no (i, j, s) repeats inside it
        mat[pos[idx], pos, star] += phase
        if axial is not None:
            axial[pos[idx], pos, star] += phase * ssign
    tb = {
        "members": members,
        "slot": (pos * nstars + star).astype(np.int32),
        "m_re": np.real(mat) / nops,
        "m_im": np.imag(mat) / nops,
    }
    if axial is not None:
        tb["ax_re"] = np.real(axial) / nops
        tb["ax_im"] = np.imag(axial) / nops
    return tb


def symmetry_tables(ctx: SimulationContext) -> dict:
    """The fused step's two rotation tables ("sym": build_sym_pw_tables,
    "dm_sym": build_dm_sym_tables), built once a context under the host span
    ``scf.setup.symmetry``: run_scf asks for them before its first host
    symmetrisation, so the span holds the whole of the group's table work."""
    tb = getattr(ctx, "_sym_tables", None)
    if tb is None:
        with obs_spans.span("scf.setup.symmetry",
                            num_ops=int(ctx.symmetry.num_ops),
                            ng=int(ctx.gvec.num_gvec)):
            tb = {"sym": build_sym_pw_tables(ctx),
                  "dm_sym": build_dm_sym_tables(ctx)}
        ctx._sym_tables = tb
    return tb


def symmetrize_pw_device(f_g: jnp.ndarray, tb: dict,
                         axial_z: bool = False) -> jnp.ndarray:
    """Jit-safe symmetrize_pw: f_g complex [ng] (inside the compiled
    program), tb from build_sym_pw_tables as device arrays. Every star's
    members are gathered into a column, multiplied by the star's matrix
    (products and a sum in the working precision, no matmul unit and so no
    matmul precision) and put back."""
    with jax.named_scope("sym_pw"):
        m_re, m_im = ((tb["ax_re"], tb["ax_im"]) if axial_z
                      else (tb["m_re"], tb["m_im"]))
        f = f_g[tb["members"]]  # [m, nstars]
        f_re, f_im = jnp.real(f)[None], jnp.imag(f)[None]
        out = jax.lax.complex(
            jnp.sum(m_re * f_re - m_im * f_im, axis=1),
            jnp.sum(m_re * f_im + m_im * f_re, axis=1))
        return out.reshape(-1)[tb["slot"]]


def num_sym_pw(do_symmetrize: bool, polarized: bool) -> int:
    """symmetrize_pw_device calls one fused step runs: the new density, the
    effective potential and the ledger's idempotency invariant; a moment
    adds itself and b_z (counters.num_sym_pw; 0 without symmetry)."""
    return (3 + (2 if polarized else 0)) if do_symmetrize else 0


def build_dm_sym_tables(ctx: SimulationContext):
    """Per-op dense beta-rotation matrices for the collinear density-matrix
    symmetrization: S_op[nops, nbeta, nbeta] with
    S[joff + i, off + j] = r[i, j] (joff the permuted atom's block), so
    dm' = (1/N) sum_op S dm S^T reproduces symmetrize_density_matrix's
    per-block r @ dm_block @ r.T scattered to the permuted block. flipneg
    marks ops with spin_sign < 0 (collinear channel swap); blockmask zeroes
    the inter-atom blocks the host variant never writes."""
    sym = ctx.symmetry
    uc = ctx.unit_cell
    nbeta = ctx.beta.num_beta_total
    blocks = list(ctx.beta.atom_blocks(uc))
    off_by_atom = {ia: off for ia, off, _ in blocks}
    ops = sym.ops if sym is not None and sym.num_ops > 1 else []
    s_ops = np.zeros((max(len(ops), 1), nbeta, nbeta))
    flipneg = np.zeros(max(len(ops), 1), dtype=bool)
    if not ops:
        s_ops[0] = np.eye(nbeta)
    for io, op in enumerate(ops):
        rot_by_type = _beta_rotation_blocks(ctx, op)
        flipneg[io] = op.spin_sign < 0
        for ia, off, nbf in blocks:
            r = rot_by_type[uc.type_of_atom[ia]]
            joff = off_by_atom[int(op.perm[ia])]
            s_ops[io, joff : joff + nbf, off : off + nbf] = r
    blockmask = np.zeros((nbeta, nbeta))
    for _, off, nbf in blocks:
        blockmask[off : off + nbf, off : off + nbf] = 1.0
    return {"s_ops": s_ops, "flipneg": flipneg, "blockmask": blockmask}


def symmetrize_density_matrix_device(dm: jnp.ndarray, tb: dict) -> jnp.ndarray:
    """Jit-safe symmetrize_density_matrix: dm complex [ns, nbeta, nbeta]
    inside the compiled program, tb from build_dm_sym_tables. For ns == 2
    the spin channels swap under flipneg ops exactly like the host."""
    ns = dm.shape[0]
    nops = tb["s_ops"].shape[0]
    with jax.named_scope("sym_dm"):
        if ns == 2:
            dms = jnp.where(tb["flipneg"][:, None, None, None],
                            dm[None, ::-1], dm[None])
        else:
            dms = jnp.broadcast_to(dm[None], (nops,) + dm.shape)
        out = jnp.einsum("oij,osjk,olk->sil", tb["s_ops"], dms, tb["s_ops"])
        return out * tb["blockmask"][None] / nops


def atomic_moments_vec(ctx: SimulationContext, mvec_g: np.ndarray) -> np.ndarray:
    """Per-atom (mx, my, mz) sphere integrals — vector form of
    atomic_moments for non-collinear runs. mvec_g: [3, ng]."""
    return np.stack(
        [atomic_moments(ctx, mvec_g[i]) for i in range(3)], axis=1
    )  # [natoms, 3]
