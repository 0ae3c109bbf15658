"""Non-collinear effective potential: XC in the locally-diagonal spin frame.

The reference (src/potential/xc.cpp:229-404 xc_rg_magnetic) evaluates the
collinear XC functional on the projected densities
n_{up/dn} = (rho_xc +- |m|)/2 and directs the resulting scalar field
B_xc = (v_up - v_dn)/2 along the local magnetization direction m-hat
(sign-guarded). Everything else (Poisson, V_loc, symmetrization) is the
scalar machinery; the magnetization vector field is symmetrized as an
AXIAL vector: m'_i(g') = det(R) R_ij m_j(g).

Vector component order here is (x, y, z); the reference's internal Field4D
order is (rho, mz, mx, my) — only the storage order differs, cited
per-formula.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from sirius_tpu.context import SimulationContext
from sirius_tpu.dft.density import sym_rot_cache, symmetrize_pw
from sirius_tpu.dft.poisson import hartree_potential_g
from sirius_tpu.dft.potential import (
    _divergence_g,
    _gradient_r,
    _inner_rr,
    _to_g,
    _to_r,
)
from sirius_tpu.dft.xc import XCFunctional

import jax.numpy as jnp


@dataclasses.dataclass
class NcPotentialResult:
    veff_g: np.ndarray  # fine G: charge part (V_loc + V_H + V_xc)
    bvec_g: np.ndarray  # [3, ng] fine G: (Bx, By, Bz)
    veff_boxes: tuple  # (v_uu, v_dd, bx, by) coarse real boxes
    vha_g: np.ndarray
    vxc_g: np.ndarray
    energies: dict


def symmetrize_vector_pw(ctx: SimulationContext, mvec_g: np.ndarray) -> np.ndarray:
    """Axial-vector PW symmetrization over the magnetic space group:
    m'_i(g') = (1/N) sum_S det(R) R_ij m_j(g) e^{-2 pi i g'.t}
    (reference symmetrize_field4d.hpp with the ops' spin rotations; the
    scalar index/phase cache from symmetrize_pw is reused)."""
    sym = ctx.symmetry
    cache = sym_rot_cache(ctx)
    out = np.zeros_like(mvec_g)
    for op, (idx, phase, _ssign) in zip(sym.ops, cache):
        rot = np.linalg.det(op.rot_cart) * op.rot_cart  # axial vector
        m_rot = rot @ mvec_g  # [3, ng]
        buf = np.zeros_like(mvec_g)
        # scatter: component i of the image at g' = w_k g
        np.add.at(buf, (slice(None), idx), m_rot * phase[None, :])
        out += buf
    return out / sym.num_ops


def generate_potential_nc(
    ctx: SimulationContext,
    rho_g: np.ndarray,
    xc: XCFunctional,
    mvec_g: np.ndarray,  # [3, ng] (mx, my, mz)
) -> NcPotentialResult:
    dims = ctx.gvec.fft.dims

    vha_g = np.asarray(
        hartree_potential_g(jnp.asarray(rho_g), jnp.asarray(ctx.gvec.glen2))
    )
    rho_r = _to_r(ctx, rho_g)
    rho_core_r = (
        _to_r(ctx, ctx.rho_core_g) if np.any(ctx.rho_core_g) else np.zeros(dims)
    )
    m_r = np.stack([_to_r(ctx, mvec_g[i]) for i in range(3)])
    m_len = np.sqrt(np.sum(m_r**2, axis=0))

    rho_xc = np.maximum(rho_r + rho_core_r, 1e-20)
    ml = np.minimum(m_len, rho_xc)
    n_up = 0.5 * (rho_xc + ml)
    n_dn = 0.5 * (rho_xc - ml)
    if xc.is_gga:
        # gradients of the projected channel densities (reference builds
        # grad of rho_up/dn AFTER the |m| projection, xc.cpp:415-426)
        up_g = _to_g(ctx, n_up)
        dn_g = _to_g(ctx, n_dn)
        gu = _gradient_r(ctx, up_g)
        gd = _gradient_r(ctx, dn_g)
        suu = sum(g * g for g in gu)
        sdd = sum(g * g for g in gd)
        sud = sum(a * b for a, b in zip(gu, gd))
        out = xc.evaluate_polarized(
            jnp.asarray(n_up.ravel()), jnp.asarray(n_dn.ravel()),
            jnp.asarray(suu.ravel()), jnp.asarray(sud.ravel()),
            jnp.asarray(sdd.ravel()),
        )
        v_up = np.asarray(out["v_up"]).reshape(dims)
        v_dn = np.asarray(out["v_dn"]).reshape(dims)
        vsuu = np.asarray(out["vsigma_uu"]).reshape(dims)
        vsud = np.asarray(out["vsigma_ud"]).reshape(dims)
        vsdd = np.asarray(out["vsigma_dd"]).reshape(dims)
        div_u = _to_r(ctx, _divergence_g(ctx, [2 * vsuu * a + vsud * b for a, b in zip(gu, gd)]))
        div_d = _to_r(ctx, _divergence_g(ctx, [2 * vsdd * b + vsud * a for a, b in zip(gu, gd)]))
        v_up = v_up - div_u
        v_dn = v_dn - div_d
    else:
        out = xc.evaluate_polarized(jnp.asarray(n_up.ravel()), jnp.asarray(n_dn.ravel()))
        v_up = np.asarray(out["v_up"]).reshape(dims)
        v_dn = np.asarray(out["v_dn"]).reshape(dims)
    e_r = np.asarray(out["e"]).reshape(dims)
    vxc_r = 0.5 * (v_up + v_dn)
    bxc_scalar = 0.5 * (v_up - v_dn)
    # direct B along m-hat (reference xc.cpp:386-400; its sign guard
    # s = sign((n_up - n_dn) bxc) is the identity here because
    # n_up - n_dn = |m| >= 0 by construction, so abs(bxc)*s == bxc)
    mhat = np.where(m_len[None] > 1e-8, m_r / np.maximum(m_len, 1e-30)[None], 0.0)
    b_r = bxc_scalar[None] * mhat  # [3, box]

    exc_r = e_r / np.maximum(rho_xc, 1e-25)
    vxc_g = _to_g(ctx, vxc_r)
    veff_g = ctx.vloc_g + vha_g + vxc_g
    bvec_g = np.stack([_to_g(ctx, b_r[i]) for i in range(3)])
    if ctx.symmetry is not None and ctx.symmetry.num_ops > 1 and ctx.cfg.parameters.use_symmetry:
        veff_g = symmetrize_pw(ctx, veff_g)
        bvec_g = symmetrize_vector_pw(ctx, bvec_g)

    def to_coarse(f_g):
        from sirius_tpu.core.fftgrid import g_to_r

        return np.asarray(
            g_to_r(
                jnp.asarray(f_g[ctx.coarse_to_fine]),
                jnp.asarray(ctx.gvec_coarse.fft_index),
                ctx.fft_coarse.dims,
            )
        ).real

    v_c = to_coarse(veff_g)
    bx_c, by_c, bz_c = (to_coarse(bvec_g[i]) for i in range(3))
    veff_boxes = (v_c + bz_c, v_c - bz_c, bx_c, by_c)

    vloc_r = _to_r(ctx, ctx.vloc_g)
    vha_r = _to_r(ctx, vha_g)
    veff_r_fine = _to_r(ctx, veff_g)
    b_r_sym = np.stack([_to_r(ctx, bvec_g[i]) for i in range(3)])
    m_r_post = m_r  # energies use the pre-symmetrization m (both symmetrized upstream)
    energies = {
        "vha": _inner_rr(ctx, rho_r, vha_r),
        "vxc": _inner_rr(ctx, rho_r, vxc_r),
        "vloc": _inner_rr(ctx, rho_r, vloc_r),
        "veff": _inner_rr(ctx, rho_r, veff_r_fine),
        "exc": _inner_rr(ctx, rho_r + rho_core_r, exc_r),
        "bxc": sum(
            _inner_rr(ctx, m_r_post[i], b_r_sym[i]) for i in range(3)
        ),
    }
    return NcPotentialResult(
        veff_g=veff_g,
        bvec_g=bvec_g,
        veff_boxes=veff_boxes,
        vha_g=vha_g,
        vxc_g=vxc_g,
        energies=energies,
    )
