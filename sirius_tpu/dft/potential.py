"""Effective potential generation (reference: src/potential/potential.cpp:236
Potential::generate): Poisson -> XC (unpolarized or collinear) -> V_eff
assembly, plus the energy integrals the reference reports (energy.hpp:280).

Collinear magnetism follows the reference's Field4D layout: charge rho and
magnetization m_z; the XC potential splits into the charge part V_xc and the
field B_z = (V_up - V_dn)/2 applied with opposite sign per spin
(potential/xc.cpp). Spin-independent pieces (V_loc, V_H) enter both spin
channels.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from sirius_tpu.context import SimulationContext
from sirius_tpu.core.fftgrid import (
    box_inverse_map,
    g_to_r,
    g_to_r_gather,
    r_to_g,
)
from sirius_tpu.core.hilo import dot_scaled
from sirius_tpu.dft.density import symmetrize_pw, symmetrize_pw_device
from sirius_tpu.dft.poisson import hartree_potential_g
from sirius_tpu.dft.xc import XCFunctional


@dataclasses.dataclass
class PotentialResult:
    veff_g: np.ndarray  # fine G: charge part (V_loc + V_H + V_xc)
    bz_g: np.ndarray | None  # fine G: z field B_z (collinear) or None
    veff_r_coarse: np.ndarray  # [ns, coarse box] per-spin V for H application
    vha_g: np.ndarray
    vxc_g: np.ndarray  # fine G: XC potential alone (forces/NLCC)
    energies: dict
    # mGGA only: per-spin v_tau = de/dtau on the COARSE box for the
    # -1/2 div(v_tau grad) operator (ops/mgga.py); None otherwise
    vtau_r_coarse: np.ndarray | None = None


def _to_r(ctx, f_g):
    return np.asarray(
        g_to_r(jnp.asarray(f_g), jnp.asarray(ctx.gvec.fft_index), ctx.gvec.fft.dims)
    ).real


def _to_g(ctx, f_r):
    return np.asarray(
        r_to_g(
            jnp.asarray(f_r.astype(np.complex128)),
            jnp.asarray(ctx.gvec.fft_index),
            ctx.gvec.fft.dims,
        )
    )


def _inner_rr(ctx: SimulationContext, f_r: np.ndarray, g_r: np.ndarray) -> float:
    """Real-space integral over the cell: (Omega/N) sum_r f g."""
    return float(np.sum(f_r * g_r) * ctx.unit_cell.omega / f_r.size)


def _gradient_r(ctx, f_g):
    """grad f as three real-space fields."""
    return [
        _to_r(ctx, 1j * ctx.gvec.gcart[:, i] * f_g) for i in range(3)
    ]


def _divergence_g(ctx, vec_r):
    """div of a real-space vector field, returned in G space."""
    out = np.zeros(ctx.gvec.num_gvec, dtype=np.complex128)
    for i in range(3):
        out += 1j * ctx.gvec.gcart[:, i] * _to_g(ctx, vec_r[i])
    return out


def generate_potential(
    ctx: SimulationContext,
    rho_g: np.ndarray,
    xc: XCFunctional,
    mag_g: np.ndarray | None = None,
    tau_g: np.ndarray | None = None,
) -> PotentialResult:
    """tau_g (mGGA only): per-spin kinetic-energy density [ns, num_gvec]
    on the fine G set (ops/mgga.tau_kset through density_from_coarse_acc)."""
    dims = ctx.gvec.fft.dims
    polarized = mag_g is not None
    if xc.is_mgga and tau_g is None:
        raise ValueError("mGGA functional needs tau_g")
    tau_r = (
        None if tau_g is None
        else np.stack([_to_r(ctx, t) for t in np.atleast_2d(tau_g)])
    )

    vha_g = np.asarray(
        hartree_potential_g(jnp.asarray(rho_g), jnp.asarray(ctx.gvec.glen2))
    )
    rho_r = _to_r(ctx, rho_g)
    rho_core_r = (
        _to_r(ctx, ctx.rho_core_g) if np.any(ctx.rho_core_g) else np.zeros(dims)
    )

    if polarized:
        mag_r = _to_r(ctx, mag_g)
        # clip |m| <= rho_xc (reference density guard) and split channels;
        # the core charge is unpolarized and split evenly
        rho_xc = np.maximum(rho_r + rho_core_r, 1e-20)
        m = np.clip(mag_r, -rho_xc, rho_xc)
        n_up = 0.5 * (rho_xc + m)
        n_dn = 0.5 * (rho_xc - m)
        if xc.is_gga:
            gu = _gradient_r(ctx, 0.5 * (rho_g + ctx.rho_core_g + mag_g))
            gd = _gradient_r(ctx, 0.5 * (rho_g + ctx.rho_core_g - mag_g))
            suu = sum(g * g for g in gu)
            sdd = sum(g * g for g in gd)
            sud = sum(a * b for a, b in zip(gu, gd))
            taus = {}
            if xc.is_mgga:
                taus = dict(
                    tau_up=jnp.asarray(tau_r[0].ravel()),
                    tau_dn=jnp.asarray(tau_r[1].ravel()),
                )
            out = xc.evaluate_polarized(
                jnp.asarray(n_up.ravel()), jnp.asarray(n_dn.ravel()),
                jnp.asarray(suu.ravel()), jnp.asarray(sud.ravel()), jnp.asarray(sdd.ravel()),
                **taus,
            )
            v_up = np.asarray(out["v_up"]).reshape(dims)
            v_dn = np.asarray(out["v_dn"]).reshape(dims)
            vsuu = np.asarray(out["vsigma_uu"]).reshape(dims)
            vsud = np.asarray(out["vsigma_ud"]).reshape(dims)
            vsdd = np.asarray(out["vsigma_dd"]).reshape(dims)
            # v_s -= div(2 vs_ss grad n_s + vs_sd grad n_other)
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
        bz_r = 0.5 * (v_up - v_dn)
    else:
        rho_xc = np.maximum(rho_r + rho_core_r, 0.0)
        if xc.is_gga:
            g = _gradient_r(ctx, rho_g + ctx.rho_core_g)
            sigma = g[0] ** 2 + g[1] ** 2 + g[2] ** 2
            out = xc.evaluate(
                jnp.asarray(rho_xc.ravel()), jnp.asarray(sigma.ravel()),
                tau=None if not xc.is_mgga else jnp.asarray(tau_r[0].ravel()),
            )
            vxc_r = np.asarray(out["v"]).reshape(dims)
            vs = np.asarray(out["vsigma"]).reshape(dims)
            vxc_r = vxc_r - _to_r(ctx, _divergence_g(ctx, [2.0 * vs * gi for gi in g]))
        else:
            out = xc.evaluate(jnp.asarray(rho_xc.ravel()))
            vxc_r = np.asarray(out["v"]).reshape(dims)
        e_r = np.asarray(out["e"]).reshape(dims)
        bz_r = None

    exc_r = e_r / np.maximum(rho_xc, 1e-25)

    vxc_g = _to_g(ctx, vxc_r)
    veff_g = ctx.vloc_g + vha_g + vxc_g
    bz_g = _to_g(ctx, bz_r) if polarized else None
    if ctx.symmetry is not None and ctx.symmetry.num_ops > 1 and ctx.cfg.parameters.use_symmetry:
        veff_g = symmetrize_pw(ctx, veff_g)
        if bz_g is not None:
            bz_g = symmetrize_pw(ctx, bz_g, axial_z=True)

    # per-spin potentials on the coarse box for the local operator
    def to_coarse(f_g):
        return np.asarray(
            g_to_r(
                jnp.asarray(f_g[ctx.coarse_to_fine]),
                jnp.asarray(ctx.gvec_coarse.fft_index),
                ctx.fft_coarse.dims,
            )
        ).real

    if polarized:
        v_r = to_coarse(veff_g)
        b_r = to_coarse(bz_g)
        veff_r_coarse = np.stack([v_r + b_r, v_r - b_r])
    else:
        veff_r_coarse = to_coarse(veff_g)[None]

    # mGGA: v_tau per spin, smoothed through the coarse G set for the
    # -1/2 div(v_tau grad) operator; plus the int v_tau tau integral that
    # the eval_sum double-counting correction needs
    vtau_r_coarse = None
    e_vtau_tau = 0.0
    if xc.is_mgga:
        if polarized:
            vt = [
                np.asarray(out["vtau_up"]).reshape(dims),
                np.asarray(out["vtau_dn"]).reshape(dims),
            ]
        else:
            vt = [np.asarray(out["vtau"]).reshape(dims)]
        vtau_r_coarse = np.stack([to_coarse(_to_g(ctx, v)) for v in vt])
        e_vtau_tau = sum(
            _inner_rr(ctx, tau_r[s], vt[s]) for s in range(len(vt))
        )

    # energy integrals (reference names; valence rho except exc)
    vloc_r = _to_r(ctx, ctx.vloc_g)
    vha_r = _to_r(ctx, vha_g)
    veff_r_fine = _to_r(ctx, veff_g)
    energies = {
        "vha": _inner_rr(ctx, rho_r, vha_r),
        "vxc": _inner_rr(ctx, rho_r, vxc_r),
        "vloc": _inner_rr(ctx, rho_r, vloc_r),
        "veff": _inner_rr(ctx, rho_r, veff_r_fine),
        "exc": _inner_rr(ctx, rho_r + rho_core_r, exc_r),
        "bxc": _inner_rr(ctx, mag_r, _to_r(ctx, bz_g)) if polarized else 0.0,
        "vtau_tau": e_vtau_tau,
    }
    return PotentialResult(
        veff_g=veff_g,
        bz_g=bz_g,
        veff_r_coarse=veff_r_coarse,
        vha_g=vha_g,
        vxc_g=vxc_g,
        energies=energies,
        vtau_r_coarse=vtau_r_coarse,
    )


# ---------------------------------------------------------------------------
# Device-resident potential generation (jit twin of generate_potential for
# the fused SCF step, LDA/GGA; mGGA stays on the host fallback). All
# transforms and the XC evaluation run as traced jnp ops so the whole
# Poisson -> XC -> assembly chain compiles into the fused iteration; the
# context tables arrive as a device-array dict so nothing host-resident is
# captured in the compiled program.
# ---------------------------------------------------------------------------


def build_potential_device_tables(ctx: SimulationContext) -> dict:
    """Constant context tables (numpy) for generate_potential_device. The
    two inverse maps are how the step fills its boxes (g_to_r_gather);
    constant_fields_device adds the two real boxes that never change."""
    return {
        "glen2": ctx.gvec.glen2,
        "gcart": ctx.gvec.gcart,
        "fft_index": ctx.gvec.fft_index,
        "inv_index": box_inverse_map(
            ctx.gvec.fft_index, ctx.gvec.fft.num_points),
        "inv_index_coarse": box_inverse_map(
            ctx.gvec_coarse.fft_index, ctx.fft_coarse.num_points),
        "c2f": ctx.coarse_to_fine,
        "vloc_re": np.real(ctx.vloc_g),
        "vloc_im": np.imag(ctx.vloc_g),
        "core_re": np.real(ctx.rho_core_g),
        "core_im": np.imag(ctx.rho_core_g),
    }


@partial(jax.jit, static_argnums=(1,))
def constant_fields_device(tb: dict, dims: tuple) -> dict:
    """The two fine-box fields of generate_potential_device whose input is
    the same in every iteration of a job, from the uploaded tables, in their
    precision and on their device: rho_core(r) and v_loc(r). Transformed
    once a job (FusedScf.__init__) and carried in tb as real boxes."""
    def to_r(re, im):
        return jnp.real(
            g_to_r_gather(jax.lax.complex(re, im), tb["inv_index"], dims))

    return {
        "rho_core_r": to_r(tb["core_re"], tb["core_im"]),
        "vloc_r": to_r(tb["vloc_re"], tb["vloc_im"]),
    }


def num_box_fills(xc: XCFunctional, polarized: bool) -> int:
    """Sphere-to-box placements one generate_potential_device runs: rho,
    v_ha and v_eff on the fine box and v_eff on the coarse one; a moment
    adds itself and b_z on both boxes; a gradient correction three
    components a density and one divergence a potential."""
    spins = 2 if polarized else 1
    return 4 + (3 if polarized else 0) + (4 * spins if xc.is_gga else 0)


def num_gradient_transforms(xc: XCFunctional, polarized: bool) -> int:
    """Fine-box transforms, both directions, that a gradient correction adds
    to one generate_potential_device: a spin channel's three gradient
    components back, its three products v_sigma grad n forward, their
    divergence back; none for LDA."""
    return 7 * (2 if polarized else 1) if xc.is_gga else 0


def generate_potential_device(
    xc: XCFunctional,
    rho_g: jnp.ndarray,  # [ng] complex (inside the compiled program)
    mag_g: jnp.ndarray | None,
    tb: dict,
    dims: tuple,
    dims_coarse: tuple,
    omega: float,
    sym_tb: dict | None = None,
) -> dict:
    """Traced generate_potential: returns veff_g/bz_g/vha_g/vxc_g (complex,
    program-internal), veff_r_coarse [ns, coarse box] real and the energy
    integrals as traced (hi, lo) pairs of scalars (core/hilo.py). sym_tb (density.build_sym_pw_tables)
    enables the in-program PW symmetrization of veff/bz. tb is
    build_potential_device_tables plus constant_fields_device, uploaded."""
    if xc.is_mgga:
        raise ValueError("device potential path does not support mGGA")
    polarized = mag_g is not None
    n = dims[0] * dims[1] * dims[2]
    cdt = rho_g.dtype

    def to_r(f_g):
        return jnp.real(g_to_r_gather(f_g, tb["inv_index"], tuple(dims)))

    def to_g(f_r):
        return r_to_g(f_r.astype(cdt), tb["fft_index"], tuple(dims))

    def gradient_r(f_g):
        return [to_r(1j * tb["gcart"][:, i] * f_g) for i in range(3)]

    def divergence_g(vec_r):
        return sum(
            1j * tb["gcart"][:, i] * to_g(vec_r[i]) for i in range(3)
        )

    def inner_rr(f_r, g_r):
        # (hi, lo): in float32 one word cannot hold a few hundred Ha to the
        # 1e-5 Ha the convergence test asks about (core/hilo.py); in float64
        # lo is zero and hi the plain sum
        return dot_scaled(f_r, g_r, omega / n)

    # step_hartree / step_xc / step_vloc (and xc_gga inside step_xc) name
    # the operations of the three stages for a capture's scope table
    # (obs/device_scopes.py): metadata only, the traced order is untouched
    vloc_g = jax.lax.complex(tb["vloc_re"], tb["vloc_im"]).astype(cdt)
    rho_core_g = jax.lax.complex(tb["core_re"], tb["core_im"]).astype(cdt)
    with jax.named_scope("step_hartree"):
        vha_g = hartree_potential_g(rho_g, tb["glen2"])
    with jax.named_scope("step_xc"):
        rho_r = to_r(rho_g)
        rho_core_r = tb["rho_core_r"]
        xc_r = _xc_fields(xc, rho_g, mag_g, rho_r, rho_core_g, rho_core_r,
                          dims, to_r, gradient_r, divergence_g)
        vxc_r, bz_r, rho_xc, mag_r = (xc_r[k] for k in
                                      ("vxc_r", "bz_r", "rho_xc", "mag_r"))
        exc_r = xc_r["e_r"] / jnp.maximum(rho_xc, 1e-25)
        vxc_g = to_g(vxc_r)
    with jax.named_scope("step_vloc"):
        veff_g = vloc_g + vha_g + vxc_g
    with jax.named_scope("step_xc"):
        bz_g = to_g(bz_r) if polarized else None
    with jax.named_scope("step_vloc"):
        if sym_tb is not None:
            veff_g = symmetrize_pw_device(veff_g, sym_tb)
            if bz_g is not None:
                bz_g = symmetrize_pw_device(bz_g, sym_tb, axial_z=True)

        def to_coarse(f_g):
            return jnp.real(g_to_r_gather(
                f_g[tb["c2f"]], tb["inv_index_coarse"], tuple(dims_coarse)))

        if polarized:
            v_r = to_coarse(veff_g)
            b_r = to_coarse(bz_g)
            veff_r_coarse = jnp.stack([v_r + b_r, v_r - b_r])
        else:
            veff_r_coarse = to_coarse(veff_g)[None]

    zero = jnp.zeros((), dtype=rho_r.dtype)
    # each an (hi, lo) pair, see inner_rr; traced in the order of the dict
    with jax.named_scope("step_hartree"):
        e_vha = inner_rr(rho_r, to_r(vha_g))
    with jax.named_scope("step_xc"):
        e_vxc = inner_rr(rho_r, vxc_r)
    with jax.named_scope("step_vloc"):
        e_vloc = inner_rr(rho_r, tb["vloc_r"])
        e_veff = inner_rr(rho_r, to_r(veff_g))
    with jax.named_scope("step_xc"):
        e_exc = inner_rr(rho_r + rho_core_r, exc_r)
        e_bxc = inner_rr(mag_r, to_r(bz_g)) if polarized else (zero, zero)
    return {
        "veff_g": veff_g,
        "bz_g": bz_g,
        "veff_r_coarse": veff_r_coarse,
        "vha_g": vha_g,
        "vxc_g": vxc_g,
        "energies": {"vha": e_vha, "vxc": e_vxc, "vloc": e_vloc,
                     "veff": e_veff, "exc": e_exc, "bxc": e_bxc},
    }


def _xc_fields(xc, rho_g, mag_g, rho_r, rho_core_g, rho_core_r, dims, to_r,
               gradient_r, divergence_g) -> dict:
    """The XC part of generate_potential_device on the fine box: v_xc(r),
    b_z(r) (None without a moment), the energy density e(r), the density the
    functional saw and the moment's field (None)."""
    polarized = mag_g is not None
    mag_r = None
    if polarized:
        # the two-channel branch, named for a capture's trace.scopes
        # (step_xc/xc_spin), as xc_gga is
        with jax.named_scope("xc_spin"):
            mag_r = to_r(mag_g)
            rho_xc = jnp.maximum(rho_r + rho_core_r, 1e-20)
            m = jnp.clip(mag_r, -rho_xc, rho_xc)
            n_up = 0.5 * (rho_xc + m)
            n_dn = 0.5 * (rho_xc - m)
            if xc.is_gga:
                with jax.named_scope("xc_gga"):
                    gu = gradient_r(0.5 * (rho_g + rho_core_g + mag_g))
                    gd = gradient_r(0.5 * (rho_g + rho_core_g - mag_g))
                    suu = sum(g * g for g in gu)
                    sdd = sum(g * g for g in gd)
                    sud = sum(a * b for a, b in zip(gu, gd))
                    out = xc.evaluate_polarized(
                        n_up.ravel(), n_dn.ravel(),
                        suu.ravel(), sud.ravel(), sdd.ravel(),
                    )
                    v_up = out["v_up"].reshape(dims)
                    v_dn = out["v_dn"].reshape(dims)
                    vsuu = out["vsigma_uu"].reshape(dims)
                    vsud = out["vsigma_ud"].reshape(dims)
                    vsdd = out["vsigma_dd"].reshape(dims)
                    v_up = v_up - to_r(divergence_g(
                        [2 * vsuu * a + vsud * b for a, b in zip(gu, gd)]))
                    v_dn = v_dn - to_r(divergence_g(
                        [2 * vsdd * b + vsud * a for a, b in zip(gu, gd)]))
            else:
                out = xc.evaluate_polarized(n_up.ravel(), n_dn.ravel())
                v_up = out["v_up"].reshape(dims)
                v_dn = out["v_dn"].reshape(dims)
            e_r = out["e"].reshape(dims)
            vxc_r = 0.5 * (v_up + v_dn)
            bz_r = 0.5 * (v_up - v_dn)
    else:
        rho_xc = jnp.maximum(rho_r + rho_core_r, 0.0)
        if xc.is_gga:
            with jax.named_scope("xc_gga"):
                g = gradient_r(rho_g + rho_core_g)
                sigma = g[0] ** 2 + g[1] ** 2 + g[2] ** 2
                out = xc.evaluate(rho_xc.ravel(), sigma.ravel())
                vxc_r = out["v"].reshape(dims)
                vs = out["vsigma"].reshape(dims)
                vxc_r = vxc_r - to_r(
                    divergence_g([2.0 * vs * gi for gi in g]))
        else:
            out = xc.evaluate(rho_xc.ravel())
            vxc_r = out["v"].reshape(dims)
        e_r = out["e"].reshape(dims)
        bz_r = None

    return {"vxc_r": vxc_r, "bz_r": bz_r, "e_r": e_r, "rho_xc": rho_xc,
            "mag_r": mag_r}
