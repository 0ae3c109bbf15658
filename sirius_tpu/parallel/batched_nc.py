"""K-set-batched spinor band solve and 4-component density accumulation
(non-collinear magnetism), real-boundary contract like parallel/batched.py.

The whole k-set solves in ONE vmapped program; spinors are flattened into
the G axis ([nb, 2*ngk]) so the fixed-shape Davidson is reused unchanged.
Density accumulation produces the reference's 4 real fields
(rho, mz, mx, my) from the spinor components in a single contraction
(reference density.cpp:636-700 add_k_point_contribution_rg_noncollinear:
up = |psi_u|^2, dn = |psi_d|^2, mx = 2 Re psi_u psi_d*, my = -2 Im).
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from sirius_tpu.ops.spinor import NcHkParams, apply_h_s_nc, nc_h_o_diag
from sirius_tpu.solvers.davidson import Stages, solve, stages


class NcSetParams(NamedTuple):
    """Batched-over-k spinor Hamiltonian data, real leaves only.

    Complex tables are (re, im) pairs (see parallel/batched.py)."""

    veff_uu: jax.Array  # [n1,n2,n3]
    veff_dd: jax.Array
    bx: jax.Array
    by: jax.Array
    ekin: jax.Array  # [nk, ngk]
    mask: jax.Array  # [nk, ngk]
    fft_index: jax.Array  # [nk, ngk]
    beta_re: jax.Array  # [nk, nbeta, ngk]
    beta_im: jax.Array
    dmat_re: jax.Array  # [4, nbeta, nbeta]
    dmat_im: jax.Array
    qmat_re: jax.Array  # [4, nbeta, nbeta]
    qmat_im: jax.Array
    h_diag: jax.Array  # [nk, 2*ngk]
    o_diag: jax.Array  # [nk, 2*ngk]


def _cplx(re, im):
    return jax.lax.complex(re, im)


def make_nc_set_params(
    ctx, veff_boxes, dmat_blocks, qmat_blocks=None, dtype=jnp.complex128,
    v0: float = 0.0, prev: NcSetParams | None = None,
) -> NcSetParams:
    """veff_boxes: (v_uu, v_dd, bx, by) coarse real boxes; dmat_blocks:
    [4, nbeta, nbeta] complex (uu, dd, ud, du); qmat_blocks defaults to the
    spin-diagonal augmentation Q.

    prev: pass the previous iteration's params to reuse the constant device
    tables (projectors, kinetic, masks, Q) — only the potential-dependent
    leaves are re-uploaded (like KsetSolver._params in dft/band_solve.py)."""
    from sirius_tpu.ops.hamiltonian import real_dtype_of
    from sirius_tpu.parallel.batched import split_cplx

    nbeta = ctx.beta.num_beta_total
    nk = ctx.gkvec.num_kpoints
    rdtype = real_dtype_of(dtype)
    v_uu, v_dd, bx, by = [np.asarray(v) for v in veff_boxes]
    h_diag, o_diag = nc_h_o_diag(ctx, np.real(dmat_blocks), v0)
    dr, di = split_cplx(dmat_blocks, rdtype)
    asr = lambda a: jnp.asarray(a, dtype=rdtype)
    if prev is not None and prev.veff_uu.dtype == np.dtype(rdtype):
        return prev._replace(
            veff_uu=asr(v_uu), veff_dd=asr(v_dd), bx=asr(bx), by=asr(by),
            dmat_re=jnp.asarray(dr), dmat_im=jnp.asarray(di),
            h_diag=asr(h_diag),
        )
    if qmat_blocks is None:
        q = ctx.beta.qmat if ctx.beta.qmat is not None else np.zeros((nbeta, nbeta))
        z = np.zeros_like(q)
        qmat_blocks = np.stack([q, q, z, z]).astype(np.complex128)
    beta = (
        np.asarray(ctx.beta.beta_gk)
        if nbeta
        else np.zeros((nk, 0, ctx.gkvec.ngk_max), dtype=np.complex128)
    )
    br, bi = split_cplx(beta, rdtype)
    qr, qi = split_cplx(qmat_blocks, rdtype)
    return NcSetParams(
        veff_uu=asr(v_uu), veff_dd=asr(v_dd), bx=asr(bx), by=asr(by),
        ekin=asr(ctx.gkvec.kinetic()),
        mask=asr(ctx.gkvec.mask),
        fft_index=jnp.asarray(ctx.gkvec.fft_index),
        beta_re=jnp.asarray(br), beta_im=jnp.asarray(bi),
        dmat_re=jnp.asarray(dr), dmat_im=jnp.asarray(di),
        qmat_re=jnp.asarray(qr), qmat_im=jnp.asarray(qi),
        h_diag=asr(h_diag), o_diag=asr(o_diag),
    )


@partial(jax.jit, static_argnames=("num_steps", "by_energy"))
def davidson_kset_nc(
    params: NcSetParams, psi_re, psi_im, num_steps: int = 20,
    res_tol: float = 1e-2, by_energy: bool = True,
):
    """psi_re/psi_im: [nk, nb, 2*ngk] flattened spinors ->
    (evals [nk, nb], psi_re', psi_im', rnorm [nk, nb], ran [nk, 2]: the
    steps and chunks the set's loop ran, beside every k-point)."""
    dmat = _cplx(params.dmat_re, params.dmat_im)
    qmat = _cplx(params.qmat_re, params.qmat_im)

    def stage(name):
        # the set's stage `name` (solvers/davidson.Stages), every k-point's:
        # the loops stay outside the vmap, so the set has one trip count
        def one_k(ekin, mask, fft_index, beta_re, beta_im, h_diag, o_diag,
                  *blocks):
            pk = NcHkParams(
                veff_uu=params.veff_uu, veff_dd=params.veff_dd,
                bx=params.bx, by=params.by,
                ekin=ekin, mask=mask, fft_index=fft_index,
                beta=_cplx(beta_re, beta_im), dmat=dmat, qmat=qmat,
            )
            return getattr(stages(
                apply_h_s_nc, pk, h_diag, o_diag, jnp.tile(mask, 2), res_tol,
                by_energy=by_energy), name)(*blocks)

        return lambda *blocks: jax.vmap(one_k)(
            params.ekin, params.mask, params.fft_index, params.beta_re,
            params.beta_im, params.h_diag, params.o_diag, *blocks)

    ev, x, rn, ran = solve(Stages(*map(stage, Stages._fields)),
                           _cplx(psi_re, psi_im), num_steps)
    return (ev, jnp.real(x), jnp.imag(x), rn,
            jnp.broadcast_to(ran, (ev.shape[0], 2)))


@jax.jit
def density_kset_nc(params: NcSetParams, psi_re, psi_im, occ_w):
    """4-component coarse-box density (rho, mz, mx, my).

    psi: [nk, nb, 2*ngk] flattened spinors; occ_w: [nk, nb] occupation x
    k-weight. Returns [4, n1, n2, n3] real."""
    psi = _cplx(psi_re, psi_im)
    dims = params.veff_uu.shape
    n = dims[0] * dims[1] * dims[2]

    def one_k(fft_index, psi_k, ow):
        nb = psi_k.shape[0]
        ngk = fft_index.shape[0]
        p = psi_k.reshape(nb, 2, ngk)
        box = jnp.zeros((nb, 2, n), dtype=p.dtype).at[..., fft_index].add(p)
        fr = jnp.fft.ifftn(box.reshape((nb, 2) + dims), axes=(-3, -2, -1)) * n
        up = jnp.einsum("b,bxyz->xyz", ow, jnp.abs(fr[:, 0]) ** 2)
        dn = jnp.einsum("b,bxyz->xyz", ow, jnp.abs(fr[:, 1]) ** 2)
        z2 = jnp.einsum("b,bxyz->xyz", ow, fr[:, 0] * jnp.conj(fr[:, 1]))
        return jnp.stack([
            up + dn, up - dn, 2.0 * jnp.real(z2), -2.0 * jnp.imag(z2)
        ])

    return jnp.sum(jax.vmap(one_k)(params.fft_index, psi, occ_w), axis=0)


@jax.jit
def density_matrix_kset_nc(beta_re, beta_im, psi_re, psi_im, occ_w):
    """Spin-resolved non-local density matrix, 3 components (uu, dd, ud):
    n^{ss'}_{xy} = sum_{k,b} occ_w <beta_x|psi_s> conj(<beta_y|psi_s'>)
    (reference density.cpp:901-1025 add_k_point_contribution_dm_pwpp_
    noncollinear; the du block is the Hermitian conjugate and not stored).

    psi: [nk, nb, 2*ngk]; returns (re, im) of [3, nbeta, nbeta]."""
    rdt = jnp.promote_types(beta_re.dtype, psi_re.dtype)
    beta = _cplx(beta_re.astype(rdt), beta_im.astype(rdt))
    psi = _cplx(psi_re.astype(rdt), psi_im.astype(rdt))

    def one_k(beta_k, psi_k, ow):
        nb = psi_k.shape[0]
        ngk = beta_k.shape[-1]
        p = psi_k.reshape(nb, 2, ngk)
        bp = jnp.einsum("xg,bsg->bsx", jnp.conj(beta_k), p)
        uu = jnp.einsum("b,bx,by->xy", ow, bp[:, 0], jnp.conj(bp[:, 0]))
        dd = jnp.einsum("b,bx,by->xy", ow, bp[:, 1], jnp.conj(bp[:, 1]))
        ud = jnp.einsum("b,bx,by->xy", ow, bp[:, 0], jnp.conj(bp[:, 1]))
        return jnp.stack([uu, dd, ud])

    dm = jnp.sum(jax.vmap(one_k)(beta, psi, occ_w), axis=0)
    return jnp.real(dm), jnp.imag(dm)
