"""K-set-batched band solve: the whole (k, spin) loop as ONE jitted/vmapped
computation, shardable over the ("k", "b") mesh.

The reference loops local k-points serially per MPI rank
(diagonalize.hpp:58); on TPU the padded fixed-shape per-k arrays (GkVec)
make the entire k-set one solve (the solver's loops over stages vmapped over
the set, solvers/davidson.py) — a single XLA program that
shards over the mesh: each device runs its own k-points (over_k_pool), and
the one collective is the psum over "k" that closes the density.

REAL-BOUNDARY CONTRACT: every jitted entry point here takes and returns
REAL arrays only; complex leaves of the parameter pytree are stored as
(re, im) pairs and the complex working arrays exist only inside the
compiled programs. (The shape dates from a backend that could not move
complex arrays; the stock TPU backend can, and the code works on it
unchanged.)

This is the PRODUCTION band-solve path: dft/scf.run_scf drives it each SCF
iteration with the per-spin screened D matrices and Hubbard potentials
batched in (a serial per-(k, spin) fallback remains for debugging).
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec

from sirius_tpu.ops.hamiltonian import HkParams, apply_h_s
from sirius_tpu.parallel.mesh import KSET_PARAM_SPECS
from sirius_tpu.solvers.davidson import Stages, solve, stages

# over_k_pool's specs: a leading k axis split over "k", or one copy a device
_K, _REP = PartitionSpec("k"), PartitionSpec()


class HkSetParams(NamedTuple):
    """Batched-over-(k, spin) Hamiltonian data, real leaves only.

    Per-k leaves carry a leading nk axis; spin-dependent leaves (potential,
    screened D, Hubbard V) carry an ns axis. ns == num_spins of the run
    (1 for unpolarized, 2 collinear). Complex tables are split into re/im
    real arrays (see module docstring)."""

    veff_r: jax.Array  # [ns, n1,n2,n3] effective potential per spin channel
    ekin: jax.Array  # [nk, ngk]
    mask: jax.Array  # [nk, ngk]
    fft_index: jax.Array  # [nk, ngk]
    beta_re: jax.Array  # [nk, nbeta, ngk]
    beta_im: jax.Array  # [nk, nbeta, ngk]
    dion: jax.Array  # [ns, nbeta, nbeta] screened D per spin
    qmat: jax.Array  # [nbeta, nbeta] shared
    h_diag: jax.Array  # [nk, ns, ngk]
    o_diag: jax.Array  # [nk, ngk] (S is spin-independent)
    # [nk, m1, m2, m3] int32 ops/local.cube_inverse_map: the spheres' map on
    # their bounding cube, for the local operator over the whole set
    cube: jax.Array
    hub_re: jax.Array = None  # [nk, nhub, ngk] S-weighted Hubbard orbitals
    hub_im: jax.Array = None
    vhub_re: jax.Array = None  # [nk, ns, nhub, nhub] (per-k: +V phases)
    vhub_im: jax.Array = None


def _cplx(re, im):
    """Complex from a re/im pair — ONLY call inside a jitted program."""
    return jax.lax.complex(re, im)


def split_cplx(a, rdtype=None):
    """Host-side split of a numpy complex array into a (re, im) real pair."""
    a = np.asarray(a)
    re = np.ascontiguousarray(np.real(a))
    im = np.ascontiguousarray(np.imag(a))
    if rdtype is not None:
        re = re.astype(rdtype)
        im = im.astype(rdtype)
    return re, im


def join_cplx(re, im):
    """Host-side join of a (re, im) device/real pair into numpy complex."""
    return np.asarray(re).astype(np.complex128) + 1j * np.asarray(im)


def compute_h_diag(ctx, dion, v0: float = 0.0):
    """h_diag [nk, ns, ngk]: H preconditioner diagonal for the whole k-set
    (reference get_h_o_diag_pw); changes every SCF iteration with the
    screened D. dion: [ns, nbeta, nbeta]."""
    nbeta = ctx.beta.num_beta_total
    nk = ctx.gkvec.num_kpoints
    ns = dion.shape[0]
    ekin = ctx.gkvec.kinetic()
    h_diag = np.empty((nk, ns, ctx.gkvec.ngk_max))
    for ik in range(nk):
        b = ctx.beta.beta_gk[ik]
        for ispn in range(ns):
            h = ekin[ik] + v0
            if nbeta:
                # sum_xy conj(b_xg) D_xy b_yg as one GEMM and a column sum
                # (the three-operand einsum walks nbeta^2 ngk terms)
                h = h + np.real(np.sum(np.conj(b) * (dion[ispn] @ b), axis=0))
            h_diag[ik, ispn] = np.where(ctx.gkvec.mask[ik] > 0, h, 1e4)
    return h_diag


def compute_h_diag_device(ekin, mask, beta_re, beta_im, dion, v0):
    """Traced twin of compute_h_diag for the fused device-resident SCF
    step: all inputs are arrays already on device (ekin/mask [nk, ngk],
    beta pair [nk, nbeta, ngk], dion [ns, nbeta, nbeta], v0 traced scalar).
    Returns [nk, ns, ngk]. Call only inside a compiled program."""
    h = ekin[:, None, :] + v0
    if beta_re.shape[1]:
        b = _cplx(beta_re, beta_im)
        h = h + jnp.real(
            jnp.einsum("kxg,sxy,kyg->ksg", jnp.conj(b), dion, b)
        )
    else:
        h = jnp.broadcast_to(h, (h.shape[0], dion.shape[0], h.shape[2]))
    return jnp.where(mask[:, None, :] > 0, h, 1e4)


def compute_o_diag(ctx):
    """o_diag [nk, ngk]: S preconditioner diagonal; potential-independent
    (only the constant augmentation Q enters), computed once per run."""
    nbeta = ctx.beta.num_beta_total
    nk = ctx.gkvec.num_kpoints
    qmat = ctx.beta.qmat if ctx.beta.qmat is not None else np.zeros((nbeta, nbeta))
    o_diag = np.empty((nk, ctx.gkvec.ngk_max))
    for ik in range(nk):
        o = np.ones(ctx.gkvec.ngk_max)
        if nbeta:
            b = ctx.beta.beta_gk[ik]
            o = o + np.real(np.sum(np.conj(b) * (qmat @ b), axis=0))
        o_diag[ik] = np.where(ctx.gkvec.mask[ik] > 0, o, 1.0)
    return o_diag


def hkset_slice_r(params: HkSetParams, ik: int = 0, ispn: int = 0):
    """Single-(k, spin) real-leaf view of a batched HkSetParams, as a dict
    suitable for jit closure constants or real-boundary jit args. Rebuild
    the complex HkParams INSIDE the jitted program with hk_complex()."""
    return dict(
        veff_r=params.veff_r[ispn],
        ekin=params.ekin[ik],
        mask=params.mask[ik],
        fft_index=params.fft_index[ik],
        beta_re=params.beta_re[ik],
        beta_im=params.beta_im[ik],
        dion=params.dion[ispn],
        qmat=params.qmat,
        hub_re=None if params.hub_re is None else params.hub_re[ik],
        hub_im=None if params.hub_im is None else params.hub_im[ik],
        vhub_re=None if params.vhub_re is None else params.vhub_re[ik, ispn],
        vhub_im=None if params.vhub_im is None else params.vhub_im[ik, ispn],
    )


def hk_complex(p: dict) -> HkParams:
    """Assemble the complex per-k HkParams from real leaves; call only
    inside jit (complex must never cross the program boundary)."""
    return HkParams(
        veff_r=p["veff_r"],
        ekin=p["ekin"],
        mask=p["mask"],
        fft_index=p["fft_index"],
        beta=_cplx(p["beta_re"], p["beta_im"]),
        dion=p["dion"],
        qmat=p["qmat"],
        hub=None if p["hub_re"] is None else _cplx(p["hub_re"], p["hub_im"]),
        vhub=None if p["vhub_re"] is None else _cplx(p["vhub_re"], p["vhub_im"]),
    )


def make_hkset_params(
    ctx,
    veff_r_coarse,
    d_full=None,
    dtype=jnp.complex128,
    v0: float = 0.0,
    hub_phi=None,
    vhub=None,
) -> HkSetParams:
    """veff_r_coarse: [n1,n2,n3] or [ns, n1,n2,n3]; d_full: [nbeta,nbeta] or
    [ns,nbeta,nbeta] screened D (defaults to the bare dion); v0: average
    effective potential veff(G=0), included in the preconditioner diagonal
    exactly like the serial path (_h_o_diag). All leaves are REAL arrays."""
    from sirius_tpu.ops.hamiltonian import real_dtype_of
    from sirius_tpu.ops.local import cube_inverse_map

    nbeta = ctx.beta.num_beta_total
    nk = ctx.gkvec.num_kpoints
    veff = np.asarray(veff_r_coarse)
    if veff.ndim == 3:
        veff = veff[None]
    ns = veff.shape[0]
    dion = ctx.beta.dion if d_full is None else np.asarray(d_full)
    if dion.ndim == 2:
        dion = np.broadcast_to(dion, (ns,) + dion.shape)
    qmat = ctx.beta.qmat if ctx.beta.qmat is not None else np.zeros((nbeta, nbeta))

    rdtype = real_dtype_of(dtype)
    ekin = ctx.gkvec.kinetic()
    h_diag = compute_h_diag(ctx, dion, v0)
    o_diag = compute_o_diag(ctx)
    beta = (
        np.asarray(ctx.beta.beta_gk)
        if nbeta
        else np.zeros((nk, 0, ctx.gkvec.ngk_max), dtype=np.complex128)
    )
    beta_re, beta_im = split_cplx(beta, rdtype)
    hub_pair = (None, None) if hub_phi is None else split_cplx(hub_phi, rdtype)
    vhub_pair = (None, None) if vhub is None else split_cplx(vhub, rdtype)
    asr = lambda a: jnp.asarray(a, dtype=rdtype)
    return HkSetParams(
        veff_r=asr(veff),
        ekin=asr(ekin),
        mask=asr(ctx.gkvec.mask),
        fft_index=jnp.asarray(ctx.gkvec.fft_index),
        beta_re=jnp.asarray(beta_re),
        beta_im=jnp.asarray(beta_im),
        dion=asr(dion),
        qmat=asr(qmat),
        h_diag=asr(h_diag),
        o_diag=asr(o_diag),
        hub_re=None if hub_pair[0] is None else jnp.asarray(hub_pair[0]),
        hub_im=None if hub_pair[1] is None else jnp.asarray(hub_pair[1]),
        vhub_re=None if vhub_pair[0] is None else jnp.asarray(vhub_pair[0]),
        vhub_im=None if vhub_pair[1] is None else jnp.asarray(vhub_pair[1]),
        cube=jnp.asarray(cube_inverse_map(ctx.gkvec)),
    )


def kset_param_specs(params: HkSetParams) -> HkSetParams:
    """The ("k", "b") mesh's PartitionSpec of every leaf `params` has."""
    return HkSetParams(**{
        name: None if leaf is None else KSET_PARAM_SPECS[name]
        for name, leaf in params._asdict().items()})


def over_k_pool(fn, mesh, in_specs, out_specs):
    """`fn` as it is where there is no mesh; on the ("k", "b") mesh, `fn`
    inside a shard_map over "k" ("b" stays with the partitioner): every
    device runs the per-k program on its own k-points. A k-pool shares
    nothing inside the band solve, and the partitioner does not know it of
    a kernel: left to it, the TPU's eigh custom call is run on the whole
    k-set on every chip behind an all-gather of its operands (compiled for a
    described v5e:2x2, PERF.md section 6, PR 35)."""
    if mesh is None:
        return fn
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, axis_names={"k"},
                         check_vma=False)


@partial(jax.jit, static_argnames=("nb", "mesh"))
def initialize_subspace_kset(params: HkSetParams, psi_re, psi_im, nb: int,
                             mesh=None):
    """LCAO subspace initialization for the whole (k, spin) set: one H/S
    application to the full atomic-orbital block (+ random tail), one
    generalized Rayleigh-Ritz, keep the lowest nb Ritz vectors (reference
    initialize_subspace.hpp:27 per-k, :279 kset driver). The input block is
    [nk, ns, nbig, ngk] with nbig >= nb; truncating atomic orbitals to nb
    BEFORE the rotation loses orbital characters and mis-seeds the band
    solver (Fe 3d, test03). ``mesh``: the ("k", "b") mesh the operands are
    sharded on (over_k_pool).

    Returns (psi_re, psi_im) [nk, ns, nb, ngk]."""
    return over_k_pool(
        partial(_initialize_subspace_kset, nb=nb), mesh,
        (kset_param_specs(params), _K, _K),
        (_K, _K),
    )(params, psi_re, psi_im)


def _initialize_subspace_kset(params, psi_re, psi_im, nb):
    from sirius_tpu.solvers.davidson import subspace_rotate

    psi = _cplx(psi_re, psi_im)
    has_hub = params.hub_re is not None

    def one_k(ekin, mask, fft_index, beta_re, beta_im, hub_re_k, hub_im_k,
              vhub_re_k, vhub_im_k, psi_k, cube_k):
        def one_spin(veff_s, dion_s, vhub_re_s, vhub_im_s, x0):
            pk = HkParams(
                veff_r=veff_s,
                ekin=ekin,
                mask=mask,
                fft_index=fft_index,
                beta=_cplx(beta_re, beta_im),
                dion=dion_s,
                qmat=params.qmat,
                hub=None if hub_re_k is None else _cplx(hub_re_k, hub_im_k),
                vhub=None if vhub_re_s is None else _cplx(vhub_re_s, vhub_im_s),
                cube=cube_k,
            )
            x = x0 * mask
            hx, sx = apply_h_s(pk, x)
            return subspace_rotate(x, hx, sx, nb, mask=mask)

        return jax.vmap(
            one_spin,
            in_axes=(0, 0, None if not has_hub else 0,
                     None if not has_hub else 0, 0),
        )(params.veff_r, params.dion, vhub_re_k, vhub_im_k, psi_k)

    hub_ax = 0 if has_hub else None
    x = jax.vmap(
        one_k,
        in_axes=(0, 0, 0, 0, 0, hub_ax, hub_ax, hub_ax, hub_ax, 0, 0),
    )(
        params.ekin, params.mask, params.fft_index, params.beta_re,
        params.beta_im, params.hub_re, params.hub_im,
        params.vhub_re, params.vhub_im, psi, params.cube,
    )
    return jnp.real(x), jnp.imag(x)


@partial(jax.jit, static_argnames=("num_steps", "mesh", "by_energy"))
def davidson_kset(
    params: HkSetParams, psi_re, psi_im, num_steps: int = 20,
    res_tol: float = 1e-2, mesh=None, by_energy: bool = True,
):
    """Solve bands at every (k, spin) in one program: one pair of loops over
    the solver's stages, each vmapped over the set (solvers/davidson.py, THE
    TRIP COUNT: the set takes its slowest k-point's steps, a k-point that is
    done is held). ``mesh``: the ("k", "b") mesh the operands are sharded
    on (over_k_pool): each device's loop ends on its own k-points, so the
    program still holds no collective.

    psi_re/psi_im: [nk, ns, nb, ngk] real pair ->
    (evals [nk, ns, nb], psi_re', psi_im', rnorm [nk, ns, nb], ran [nk, 2]:
    the steps and chunks the set's loop ran on the device that holds the
    k-point)."""
    return over_k_pool(
        partial(_davidson_kset, num_steps=num_steps, by_energy=by_energy),
        mesh,
        (kset_param_specs(params), _K, _K, _REP),
        (_K, _K, _K, _K, _K),
    )(params, psi_re, psi_im, res_tol)


def _davidson_kset(params, psi_re, psi_im, res_tol, num_steps, by_energy):
    has_hub = params.hub_re is not None
    hub_ax = 0 if has_hub else None

    def stage(name):
        """The set's stage `name` (solvers/davidson.Stages): every (k, spin)
        lane's, vmapped over blocks [nk, ns, nb, ngk]. The loops stay
        outside the vmap, so the set has one trip count."""

        def one_k(ekin, mask, fft_index, beta_re, beta_im, h_diag_k, o_diag,
                  hub_re_k, hub_im_k, vhub_re_k, vhub_im_k, cube_k, *blocks_k):
            def one_spin(veff_s, dion_s, vhub_re_s, vhub_im_s, h_diag_s,
                         *blocks):
                pk = HkParams(
                    veff_r=veff_s,
                    ekin=ekin,
                    mask=mask,
                    fft_index=fft_index,
                    beta=_cplx(beta_re, beta_im),
                    dion=dion_s,
                    qmat=params.qmat,
                    hub=None if hub_re_k is None else _cplx(hub_re_k, hub_im_k),
                    vhub=(None if vhub_re_s is None
                          else _cplx(vhub_re_s, vhub_im_s)),
                    cube=cube_k,
                )
                return getattr(stages(
                    apply_h_s, pk, h_diag_s, o_diag, mask, res_tol,
                    by_energy=by_energy), name)(*blocks)

            return jax.vmap(
                one_spin,
                in_axes=(0, 0, hub_ax, hub_ax, 0) + (0,) * len(blocks_k),
            )(params.veff_r, params.dion, vhub_re_k, vhub_im_k, h_diag_k,
              *blocks_k)

        def over_set(*blocks):
            return jax.vmap(
                one_k,
                in_axes=(0, 0, 0, 0, 0, 0, 0, hub_ax, hub_ax, hub_ax, hub_ax, 0)
                + (0,) * len(blocks),
            )(
                params.ekin, params.mask, params.fft_index, params.beta_re,
                params.beta_im, params.h_diag, params.o_diag,
                params.hub_re, params.hub_im, params.vhub_re, params.vhub_im,
                params.cube, *blocks,
            )

        return over_set

    ev, x, rn, ran = solve(Stages(*map(stage, Stages._fields)),
                           _cplx(psi_re, psi_im), num_steps)
    # the loop's count beside each of the device's k-points: under the
    # shard_map every device hands back its own
    return (ev, jnp.real(x), jnp.imag(x), rn,
            jnp.broadcast_to(ran, (ev.shape[0], 2)))


@partial(jax.jit, static_argnames=("mesh",))
def density_kset(params: HkSetParams, psi_re, psi_im, occ_w, mesh=None):
    """Coarse-box density sum_{k,b} occ_w |psi(r)|^2 per spin, over the
    whole k-set in one program: every band row goes sphere -> cube -> box
    by the local operator's inverse passes, k x (spin, band) rows on the
    minor axis (ops/local.py, ROWS ON THE LANES), and the weighted squares
    are summed over the rows. ``mesh``: the ("k", "b") mesh the operands are
    sharded on (over_k_pool): each device carries its own k-points' rows
    and the program closes with one psum over "k".

    occ_w: [nk, ns, nb] occupation x k-weight. Returns [ns, n1, n2, n3]
    (real)."""
    return over_k_pool(
        partial(_density_kset, dims=params.veff_r.shape[-3:],
                axis=None if mesh is None else "k"),
        mesh, (_K, _K, _K, _K), _REP,
    )(params.cube, psi_re, psi_im, occ_w)


def _density_kset(cube, psi_re, psi_im, occ_w, dims, axis):
    from sirius_tpu.ops.local import rows_to_box

    nk, ns, nb, ngk = psi_re.shape
    n = dims[0] * dims[1] * dims[2]
    with jax.named_scope("density_kset"):  # the name in a capture's table
        xr, xi = rows_to_box(
            _cplx(psi_re, psi_im).reshape(nk, ns * nb, ngk), cube, dims,
            psi_re.dtype)
        # the passes carry ifftn's 1/n: psi(r) is n times the planes
        a = (xr * xr + xi * xi).reshape(dims + (nk, ns, nb))
        rho = jnp.einsum("xyzksb,ksb->sxyz", a, occ_w * (float(n) * n),
                         precision=jax.lax.Precision.HIGHEST)
        return rho if axis is None else jax.lax.psum(rho, axis)


@jax.jit
def density_matrix_kset(beta_re, beta_im, psi_re, psi_im, occ_w):
    """Non-local density matrix n^sigma_{xi xi'} = sum_{k,b} occ_w
    conj(<beta_xi|psi>) <beta_xi'|psi>, contracted over the whole k-set
    (reference add_k_point_contribution_dm_pwpp, density.cpp:847-901).

    beta_re/beta_im: [nk, nbeta, ngk] projector tables (pass the
    full-precision f64 pair so the accumulation precision is independent of
    the wave-function working dtype). Returns a (re, im) pair of
    [ns, nbeta, nbeta]."""
    rdt = jnp.promote_types(beta_re.dtype, psi_re.dtype)
    beta = _cplx(beta_re.astype(rdt), beta_im.astype(rdt))
    psi = _cplx(psi_re.astype(rdt), psi_im.astype(rdt))

    def one_k(beta_k, psi_k, ow):
        bp = jnp.einsum("xg,sbg->sbx", jnp.conj(beta_k), psi_k)
        return jnp.einsum("sb,sbx,sby->sxy", ow, jnp.conj(bp), bp)

    with jax.named_scope("density_matrix"):
        dm = jnp.sum(jax.vmap(one_k)(beta, psi, occ_w), axis=0)
        return jnp.real(dm), jnp.imag(dm)
