"""meta-GGA machinery for the PP-PW path: kinetic-energy density and the
tau-dependent Hamiltonian term.

The mGGA Kohn-Sham operator gains -1/2 div(v_tau grad .), applied in the
plane-wave basis with three extra FFT pairs per band block:

  (H_tau psi)_G = 1/2 sum_c (G+k)_c FFT[ v_tau(r) IFFT[(G+k)_c psi]_r ]_G

and the density side needs tau(r) = 1/2 sum_{k,b} occ_w |grad psi|^2.

Kept as a SEPARATE module from ops/hamiltonian.py + parallel/batched.py:
the tau term wraps the standard apply_h_s as a closure passed into the
davidson driver, so the validated non-mGGA programs are byte-identical.
Reference counterpart: the libxc mGGA surface of xc_functional_base.hpp
plus the tau handling in potential/xc.cpp (xc_use_lapl = false branch).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from sirius_tpu.ops.hamiltonian import HkParams, apply_h_s


def _cplx(re, im):
    return jax.lax.complex(re, im)


def apply_h_s_mgga(params: HkParams, vtau_r: jax.Array, gkc: jax.Array,
                   psi: jax.Array):
    """(H psi, S psi) including the tau term. vtau_r: [n1,n2,n3] real;
    gkc: [ngk, 3] cartesian G+k components."""
    h, s = apply_h_s(params, psi)
    dims = params.veff_r.shape
    n = dims[0] * dims[1] * dims[2]
    psi = psi * params.mask
    batch = psi.shape[:-1]
    acc = jnp.zeros_like(psi)
    for c in range(3):
        gpsi = gkc[:, c] * psi
        box = (
            jnp.zeros(batch + (n,), dtype=psi.dtype)
            .at[..., params.fft_index]
            .add(gpsi)
        )
        gr = jnp.fft.ifftn(box.reshape(batch + dims), axes=(-3, -2, -1))
        back = (
            jnp.fft.fftn(gr * vtau_r, axes=(-3, -2, -1))
            .reshape(batch + (n,))[..., params.fft_index]
        )
        acc = acc + gkc[:, c] * back
    return (h + 0.5 * acc * params.mask), s


@partial(jax.jit, static_argnames=("dims",))
def tau_kset(fft_index, gkc, psi_re, psi_im, occ_w, dims: tuple):
    """Coarse-box kinetic-energy density tau(r) = 1/2 sum occ_w |grad psi|^2
    per spin, contracted over the k-set (companion of density_kset).

    fft_index: [nk, ngk]; gkc: [nk, ngk, 3]; psi: [nk, ns, nb, ngk];
    occ_w: [nk, ns, nb]. Returns [ns, n1, n2, n3] real."""
    psi = _cplx(psi_re, psi_im)
    n = dims[0] * dims[1] * dims[2]

    def one_k(fft_index_k, gkc_k, psi_k, ow):
        batch = psi_k.shape[:-1]
        out = 0.0
        for c in range(3):
            gpsi = gkc_k[:, c] * psi_k
            box = (
                jnp.zeros(batch + (n,), dtype=psi_k.dtype)
                .at[..., fft_index_k]
                .add(gpsi)
            )
            gr = jnp.fft.ifftn(box.reshape(batch + dims), axes=(-3, -2, -1)) * n
            out = out + jnp.einsum("sb,sbxyz->sxyz", ow, jnp.abs(gr) ** 2)
        return 0.5 * out

    return jnp.sum(jax.vmap(one_k)(fft_index, gkc, psi, occ_w), axis=0)


@partial(jax.jit, static_argnames=("num_steps", "by_energy"))
def davidson_kset_mgga(params, vtau_r, gkc, psi_re, psi_im,
                       num_steps: int = 20, res_tol: float = 1e-2,
                       by_energy: bool = True):
    """davidson_kset with the tau term in the operator. params: HkSetParams;
    vtau_r: [ns, n1,n2,n3] real; gkc: [nk, ngk, 3] real. Same returns as
    parallel.batched.davidson_kset."""
    from sirius_tpu.solvers.davidson import Stages, solve, stages

    has_hub = params.hub_re is not None
    hub_ax = 0 if has_hub else None

    def stage(name):
        # the set's stage `name`, every (k, spin) lane's, as in
        # parallel/batched._davidson_kset: the loops stay outside the vmap
        def one_k(ekin, mask, fft_index, gkc_k, beta_re, beta_im, h_diag_k,
                  o_diag, hub_re_k, hub_im_k, vhub_re_k, vhub_im_k, cube_k,
                  *blocks_k):
            def one_spin(veff_s, dion_s, vtau_s, vhub_re_s, vhub_im_s,
                         h_diag_s, *blocks):
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

                def apply_fn(p, x):
                    return apply_h_s_mgga(p, vtau_s, gkc_k, x)

                return getattr(stages(
                    apply_fn, pk, h_diag_s, o_diag, mask, res_tol,
                    by_energy=by_energy), name)(*blocks)

            return jax.vmap(
                one_spin,
                in_axes=(0, 0, 0, hub_ax, hub_ax, 0) + (0,) * len(blocks_k),
            )(params.veff_r, params.dion, vtau_r, vhub_re_k, vhub_im_k,
              h_diag_k, *blocks_k)

        def over_set(*blocks):
            return jax.vmap(
                one_k,
                in_axes=(0, 0, 0, 0, 0, 0, 0, 0, hub_ax, hub_ax, hub_ax,
                         hub_ax, 0) + (0,) * len(blocks),
            )(
                params.ekin, params.mask, params.fft_index, gkc,
                params.beta_re, params.beta_im, params.h_diag, params.o_diag,
                params.hub_re, params.hub_im, params.vhub_re, params.vhub_im,
                params.cube, *blocks,
            )

        return over_set

    ev, x, rn, ran = solve(Stages(*map(stage, Stages._fields)),
                           _cplx(psi_re, psi_im), num_steps)
    return (ev, jnp.real(x), jnp.imag(x), rn,
            jnp.broadcast_to(ran, (ev.shape[0], 2)))
