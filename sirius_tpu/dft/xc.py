"""Exchange-correlation functionals, implemented natively in JAX.

The reference wraps libxc (src/potential/xc_functional_base.hpp, xc.cpp:421);
libxc is not available here and a handful of analytic functionals covers the
whole verification suite: XC_LDA_X, XC_LDA_C_PZ, XC_LDA_C_PW, XC_GGA_X_PBE,
XC_GGA_C_PBE (names follow libxc so reference decks load unchanged).

Design: each functional is a pure scalar energy density e(n_up, n_dn [,
sigma_uu, sigma_ud, sigma_dd]) per unit volume (libxc's n * eps). All
potentials (v_rho, v_sigma) are exact jax derivatives of e — no hand-coded
derivative formulas to get wrong, and the same code path is autodiff-able
end-to-end for forces/stress later.

Hartree atomic units throughout. sigma = |grad n|^2 contractions, libxc
convention.

evaluate()/evaluate_polarized() are traced inside the fused device-resident
SCF step (dft/fused.py) in addition to the host path: they must stay pure
jnp on traced inputs — no numpy coercion, python branching on data, or host
callbacks. Handed a tracer they emit the lines of _eval into the caller's
program; handed concrete arrays (every host caller) they run _host_xc, one
compiled program of the process.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from sirius_tpu.utils.profiler import counters

_TINY = 1e-25
# vacuum threshold for a spin channel (libxc dens_threshold analog)
_DENS_TH = 1e-13


# ---------------------------------------------------------------------------
# Elementary functions for the gradient-corrected functionals in float32.
#
# The TPU evaluates float32 exp, log, log1p and the general pow on its
# transcendental unit to about 1e-6 relative, with a bias (PERF.md, PR 34:
# exp -8e-7 +- 1.6e-6, log1p +- 6e-5 of itself on [0.004, 3]; the CPU backend
# 3e-8), where sqrt, cbrt, division and the multiply-adds of a polynomial are
# IEEE. PBE correlation is built on three logarithms and an exponential at
# every point of the box, and the bias summed over the box (-5e-5 Ha on 16
# atoms, 3e-6 Ha an atom of a 5e-6 bar) went into the energy through the
# potential the bands saw. So the float32 forms below are the classic
# range-reduction + polynomial ones (Cephes logf / expf, under one ulp) in
# plain arithmetic, their derivatives written as what they are; any other
# dtype takes the library's function, which is exact enough there. Cube
# roots go through cbrt, which is accurate on both and whose derivative is
# ans / (3 x), never a pow. Only the GGA functionals and PW92 use these:
# the LDA pair X + PZ, which every LDA deck runs, is as it was, bit for bit.

_LN2_HI = 0.693359375
_LN2_LO = -2.12194440e-4


def _is_f32(x) -> bool:
    return jnp.asarray(x).dtype == jnp.float32


def _log1p_f32(x):
    """log(1 + x) of a float32 x > -1/2, Cephes logf on u = 1 + x = m 2^e
    with m in [sqrt(1/2), sqrt(2)): a degree-8 polynomial in f = m - 1, e ln 2
    in two words. Where e = 0, f is x itself, so the rounding of 1 + x never
    enters (no compensation term for a compiler to fold away)."""
    bits = jax.lax.bitcast_convert_type(1.0 + x, jnp.int32)
    e = (bits >> 23) - 126
    m = jax.lax.bitcast_convert_type(
        (bits & 0x007FFFFF) | 0x3F000000, jnp.float32)  # [0.5, 1)
    small = m < 0.70710678
    e = jnp.where(small, e - 1, e)
    f = jnp.where(e == 0, x, jnp.where(small, m + m, m) - 1.0)
    e = e.astype(jnp.float32)
    z = f * f
    p = 7.0376836292e-2
    for c in (-1.1514610310e-1, 1.1676998740e-1, -1.2420140846e-1,
              1.4249322787e-1, -1.6668057665e-1, 2.0000714765e-1,
              -2.4999993993e-1, 3.3333331174e-1):
        p = p * f + c
    y = f * z * p + _LN2_LO * e - 0.5 * z
    return f + y + _LN2_HI * e


def _exp_f32(x):
    """exp of a float32 of moderate size (|x| < 80), Cephes expf: x = n ln 2
    + r with ln 2 in two words, a degree-5 polynomial in r, 2^n by its bits."""
    n = jnp.floor(1.44269504088896341 * x + 0.5)
    r = x - n * _LN2_HI - n * _LN2_LO
    p = 1.9875691500e-4
    for c in (1.3981999507e-3, 8.3334519073e-3, 4.1665795894e-2,
              1.6666665459e-1, 5.0000001201e-1):
        p = p * r + c
    two_n = jax.lax.bitcast_convert_type(
        (n.astype(jnp.int32) + 127) << 23, jnp.float32)
    return (p * r * r + r + 1.0) * two_n


@jax.custom_jvp
def _log1p(x):
    return _log1p_f32(x) if _is_f32(x) else jnp.log1p(x)


@_log1p.defjvp
def _log1p_jvp(primals, tangents):
    (x,), (g,) = primals, tangents
    return _log1p(x), g / (1.0 + x)


@jax.custom_jvp
def _exp(x):
    return _exp_f32(x) if _is_f32(x) else jnp.exp(x)


@_exp.defjvp
def _exp_jvp(primals, tangents):
    (x,), (g,) = primals, tangents
    ans = _exp(x)
    return ans, g * ans


def _lda_x_e(nu: jnp.ndarray, nd: jnp.ndarray) -> jnp.ndarray:
    """Slater exchange energy per volume, spin-scaled."""
    cx = (3.0 / 4.0) * (3.0 / jnp.pi) ** (1.0 / 3.0)
    return -cx / 2.0 * ((2 * nu) ** (4.0 / 3.0) + (2 * nd) ** (4.0 / 3.0))


def _pz_eps(rs: jnp.ndarray, pol: bool, log=jnp.log) -> jnp.ndarray:
    """Perdew-Zunger 81 correlation energy per particle at zeta=0 or 1."""
    if pol:
        gamma, b1, b2 = -0.0843, 1.3981, 0.2611
        a, b, c, d = 0.01555, -0.0269, 0.0007, -0.0048
    else:
        gamma, b1, b2 = -0.1423, 1.0529, 0.3334
        a, b, c, d = 0.0311, -0.048, 0.002, -0.0116
    lo = gamma / (1.0 + b1 * jnp.sqrt(rs) + b2 * rs)
    hi = a * log(rs) + b + c * rs * log(rs) + d * rs
    return jnp.where(rs >= 1.0, lo, hi)


def _zeta_f(zeta: jnp.ndarray, pow43=lambda x: x ** (4.0 / 3.0)) -> jnp.ndarray:
    return (pow43(1 + zeta) + pow43(1 - zeta) - 2.0) / (
        2.0 ** (4.0 / 3.0) - 2.0
    )


def _lda_c_pz_e(nu: jnp.ndarray, nd: jnp.ndarray) -> jnp.ndarray:
    n = nu + nd
    zeta = jnp.clip((nu - nd) / n, -1.0, 1.0)
    rs = (3.0 / (4.0 * jnp.pi * n)) ** (1.0 / 3.0)
    eu = _pz_eps(rs, False)
    ep = _pz_eps(rs, True)
    return n * (eu + _zeta_f(zeta) * (ep - eu))


# The LDA pair with two spin channels (PR 45). In float32 the TPU's log is
# off by up to 4e-4 of itself near 1 (1.4e-6 in the mean on [1e-3, 4]) and
# the general pow that jax.grad makes of x ** (4/3), x ** (4/3 - 1), by 1e-6
# with a bias (v_up, v_dn +7.6e-7 of themselves in the mean against float64;
# PERF.md section 6, PR 45), where cbrt and the polynomial log are good to
# 7e-8 there: some 5e-6 Ha of a 1e-5 Ha bar on the 2-atom ferromagnetic cell. So
# the polarised call (evaluate_polarized) takes the forms below: 4/3 powers
# as x cbrt(x) with the derivative written out (it is finite at zeta = +-1,
# where autodiff of x cbrt(x) is 0/0), r_s by cbrt, log(r_s) by _log1p. The
# unpolarised call keeps the lines above: every deck with one spin channel
# runs the program it ran, bit for bit.

@jax.custom_jvp
def _pow43(x):
    return x * jnp.cbrt(x)


@_pow43.defjvp
def _pow43_jvp(primals, tangents):
    (x,), (g,) = primals, tangents
    c = jnp.cbrt(x)
    return x * c, g * (4.0 / 3.0) * c


def _log_rs(rs):
    return _log1p(rs - 1.0)


def _lda_x_spin_e(nu: jnp.ndarray, nd: jnp.ndarray) -> jnp.ndarray:
    cx = (3.0 / 4.0) * (3.0 / jnp.pi) ** (1.0 / 3.0)
    return -cx / 2.0 * (_pow43(2 * nu) + _pow43(2 * nd))


def _lda_c_pz_spin_e(nu: jnp.ndarray, nd: jnp.ndarray) -> jnp.ndarray:
    n = nu + nd
    zeta = jnp.clip((nu - nd) / n, -1.0, 1.0)
    rs = jnp.cbrt(3.0 / (4.0 * jnp.pi * n))
    eu = _pz_eps(rs, False, _log_rs)
    ep = _pz_eps(rs, True, _log_rs)
    return n * (eu + _zeta_f(zeta, _pow43) * (ep - eu))


def _pw92_g(rs: jnp.ndarray, a, a1, b1, b2, b3, b4) -> jnp.ndarray:
    s = jnp.sqrt(rs)
    den = 2.0 * a * (b1 * s + b2 * rs + b3 * rs * s + b4 * rs * rs)
    return -2.0 * a * (1 + a1 * rs) * _log1p(1.0 / den)


def _lda_c_pw_e(nu: jnp.ndarray, nd: jnp.ndarray, mod: bool = False) -> jnp.ndarray:
    """Perdew-Wang 92 correlation, full spin interpolation.

    mod=True selects the PW_MOD constants (libxc lda_c_pw_mod: one more
    digit on the A coefficients) — the parametrization PBE correlation is
    DEFINED on. libxc's XC_GGA_C_PBE builds on pw_mod, XC_LDA_C_PW on the
    published PW92 digits; the ~1e-5-relative difference in eps_c is a
    reproducible 1e-5 Ha-class shift on PBE deck totals."""
    n = nu + nd
    zeta = jnp.clip((nu - nd) / n, -1.0, 1.0)
    rs = jnp.cbrt(3.0 / (4.0 * jnp.pi * n))
    a0, a1, a2 = (
        (0.0310907, 0.01554535, 0.0168869) if mod
        else (0.031091, 0.015545, 0.016887)
    )
    ec0 = _pw92_g(rs, a0, 0.21370, 7.5957, 3.5876, 1.6382, 0.49294)
    ec1 = _pw92_g(rs, a1, 0.20548, 14.1189, 6.1977, 3.3662, 0.62517)
    # alpha_c(rs) = -G(fit): the PW92 spin-stiffness fit parametrizes -alpha_c,
    # so mac (= alpha_c) enters the interpolation with a POSITIVE sign.
    mac = -_pw92_g(rs, a2, 0.11125, 10.357, 3.6231, 0.88026, 0.49671)
    fz = _zeta_f(zeta)
    fpp0 = 8.0 / (9.0 * (2.0 ** (4.0 / 3.0) - 2.0))
    z4 = zeta**4
    eps = ec0 + mac * fz / fpp0 * (1 - z4) + (ec1 - ec0) * fz * z4
    return n * eps


def _vwn_f(rs, a, x0, b, c):
    """VWN5 Pade fit of a correlation-energy channel (Vosko-Wilk-Nusair
    1980 Eq. 4.4; reference via libxc XC_LDA_C_VWN)."""
    x = jnp.sqrt(rs)
    X = lambda t: t * t + b * t + c
    Q = jnp.sqrt(4.0 * c - b * b)
    atn = jnp.arctan(Q / (2.0 * x + b))
    return a * (
        jnp.log(x * x / X(x))
        + 2.0 * b / Q * atn
        - b * x0 / X(x0) * (
            jnp.log((x - x0) ** 2 / X(x))
            + 2.0 * (b + 2.0 * x0) / Q * atn
        )
    )


def _lda_c_vwn_e(nu: jnp.ndarray, nd: jnp.ndarray) -> jnp.ndarray:
    """VWN5 correlation, full spin interpolation (same structure as PW92)."""
    n = nu + nd
    zeta = jnp.clip((nu - nd) / n, -1.0, 1.0)
    rs = (3.0 / (4.0 * jnp.pi * n)) ** (1.0 / 3.0)
    ec0 = _vwn_f(rs, 0.0310907, -0.10498, 3.72744, 12.9352)
    ec1 = _vwn_f(rs, 0.01554535, -0.325, 7.06042, 18.0578)
    alc = _vwn_f(rs, -1.0 / (6.0 * jnp.pi**2), -0.0047584, 1.13107, 13.0045)
    fz = _zeta_f(zeta)
    fpp0 = 8.0 / (9.0 * (2.0 ** (4.0 / 3.0) - 2.0))
    z4 = zeta**4
    eps = ec0 + alc * fz / fpp0 * (1 - z4) + (ec1 - ec0) * fz * z4
    return n * eps


_PBE_KAPPA = 0.804
_PBE_MU = 0.2195149727645171
_PBE_BETA = 0.06672455060314922
_PBE_GAMMA = (1.0 - jnp.log(2.0)) / jnp.pi**2
# PBEsol (Perdew et al. 2008): restore the gradient expansion for exchange
_PBESOL_MU = 10.0 / 81.0
_PBESOL_BETA = 0.046


def _pbe_x_half(n2: jnp.ndarray, sigma4: jnp.ndarray, mu: float) -> jnp.ndarray:
    """PBE-family exchange per volume for a fully polarized channel
    (2n_sigma, 4 sigma_ss), halved by the caller's spin-scaling."""
    kf = jnp.cbrt(3.0 * jnp.pi**2 * n2)
    ex_lda = -(3.0 / (4.0 * jnp.pi)) * kf * n2
    s2 = sigma4 / jnp.maximum(4.0 * kf**2 * n2**2, _TINY)
    fx = 1.0 + _PBE_KAPPA - _PBE_KAPPA / (1.0 + mu * s2 / _PBE_KAPPA)
    return ex_lda * fx


def _pbe_x_e(nu, nd, suu, sud, sdd, mu: float = _PBE_MU) -> jnp.ndarray:
    return 0.5 * (
        _pbe_x_half(2 * nu, 4 * suu, mu) + _pbe_x_half(2 * nd, 4 * sdd, mu)
    )


def _pbe_c_e(nu, nd, suu, sud, sdd, beta: float = _PBE_BETA) -> jnp.ndarray:
    n = nu + nd
    zeta = jnp.clip((nu - nd) / n, -1.0, 1.0)
    sigma = suu + 2 * sud + sdd
    eps_lda = _lda_c_pw_e(nu, nd, mod=True) / n  # libxc: PBE is on pw_mod
    phi = 0.5 * (jnp.cbrt(1 + zeta) ** 2 + jnp.cbrt(1 - zeta) ** 2)
    kf = jnp.cbrt(3.0 * jnp.pi**2 * n)
    ks = jnp.sqrt(4.0 * kf / jnp.pi)
    t2 = sigma / jnp.maximum((2.0 * phi * ks * n) ** 2, _TINY)
    a_den = _exp(-eps_lda / (_PBE_GAMMA * phi**3)) - 1.0
    aa = beta / _PBE_GAMMA / jnp.maximum(a_den, _TINY)
    num = 1.0 + aa * t2
    h = _PBE_GAMMA * phi**3 * _log1p(
        beta / _PBE_GAMMA * t2 * num / (1.0 + aa * t2 + aa**2 * t2**2)
    )
    return n * (eps_lda + h)


def _pbesol_x_e(nu, nd, suu, sud, sdd) -> jnp.ndarray:
    return _pbe_x_e(nu, nd, suu, sud, sdd, mu=_PBESOL_MU)


def _pbesol_c_e(nu, nd, suu, sud, sdd) -> jnp.ndarray:
    return _pbe_c_e(nu, nd, suu, sud, sdd, beta=_PBESOL_BETA)


# ---------------------------------------------------------------------------
# SCAN meta-GGA (Sun, Ruzsinszky, Perdew, PRL 115, 036402 (2015)).
# Implemented as the ENERGY density only; v_rho / v_sigma / v_tau all come
# from jax.grad — the TPU-native replacement for the reference's hand-coded
# libxc mGGA surface (xc_functional_base.hpp:1043+). tau is the positive KS
# kinetic-energy density (1/2) sum occ |grad psi|^2 per spin.

_SCAN_K1 = 0.065
_SCAN_MU = 10.0 / 81.0
_SCAN_B2 = jnp.sqrt(5913.0 / 405000.0)
_SCAN_B1 = (511.0 / 13500.0) / (2.0 * _SCAN_B2)
_SCAN_B3 = 0.5
_SCAN_B4 = _SCAN_MU**2 / _SCAN_K1 - 1606.0 / 18225.0 - _SCAN_B1**2
_SCAN_H0X = 1.174
_SCAN_A1 = 4.9479
_SCAN_C1X, _SCAN_C2X, _SCAN_DX = 0.667, 0.8, 1.24
_SCAN_C1C, _SCAN_C2C, _SCAN_DC = 0.64, 1.5, 0.7
_SCAN_B1C, _SCAN_B2C, _SCAN_B3C = 0.0285764, 0.0889, 0.125541
_SCAN_CHI = 0.12802585262625815
_SCAN_GAMMA = 0.031091


def _scan_interp(alpha, c1, c2, d):
    """SCAN's alpha-interpolation f(alpha): exp(-c1 a/(1-a)) below a=1,
    -d exp(c2/(1-a)) above; smooth and bounded with safe clamping (the
    exact function hits exp(-inf)=0 at alpha=1 from both sides)."""
    am1 = alpha - 1.0
    lo = jnp.exp(-c1 * alpha / jnp.maximum(-am1, 1e-12))
    hi = -d * jnp.exp(-c2 / jnp.maximum(am1, 1e-12))
    return jnp.where(alpha < 1.0, lo, hi)


def _scan_x_half(n2, sigma4, tau2):
    """SCAN exchange per volume of one fully-polarized channel (2n, 4sigma,
    2tau); spin-scaling Ex[nu,nd] = (Ex[2nu] + Ex[2nd])/2 by the caller."""
    n2 = jnp.maximum(n2, _TINY)
    kf = (3.0 * jnp.pi**2 * n2) ** (1.0 / 3.0)
    ex_lda = -(3.0 / (4.0 * jnp.pi)) * kf * n2
    s2 = sigma4 / jnp.maximum(4.0 * kf**2 * n2**2, _TINY)
    s = jnp.sqrt(jnp.maximum(s2, _TINY))
    tau_w = sigma4 / (8.0 * n2)
    tau_u = 0.3 * (3.0 * jnp.pi**2) ** (2.0 / 3.0) * n2 ** (5.0 / 3.0)
    alpha = jnp.maximum(tau2 - tau_w, 0.0) / jnp.maximum(tau_u, _TINY)
    x = _SCAN_MU * s2 * (
        1.0 + (_SCAN_B4 * s2 / _SCAN_MU) * jnp.exp(-jnp.abs(_SCAN_B4) * s2 / _SCAN_MU)
    ) + (
        _SCAN_B1 * s2 + _SCAN_B2 * (1.0 - alpha) * jnp.exp(-_SCAN_B3 * (1.0 - alpha) ** 2)
    ) ** 2
    h1x = 1.0 + _SCAN_K1 - _SCAN_K1 / (1.0 + x / _SCAN_K1)
    fx = _scan_interp(alpha, _SCAN_C1X, _SCAN_C2X, _SCAN_DX)
    gx = 1.0 - jnp.exp(-_SCAN_A1 / jnp.sqrt(s))
    fx_tot = (h1x + fx * (_SCAN_H0X - h1x)) * gx
    return ex_lda * fx_tot


def _scan_x_e(nu, nd, suu, sud, sdd, tu, td):
    return 0.5 * (
        _scan_x_half(2 * nu, 4 * suu, 2 * tu)
        + _scan_x_half(2 * nd, 4 * sdd, 2 * td)
    )


def _scan_c_e(nu, nd, suu, sud, sdd, tu, td):
    n = jnp.maximum(nu + nd, _TINY)
    zeta = jnp.clip((nu - nd) / n, -0.999999, 0.999999)
    sigma = suu + 2.0 * sud + sdd
    tau = tu + td
    rs = (3.0 / (4.0 * jnp.pi * n)) ** (1.0 / 3.0)
    kf = (3.0 * jnp.pi**2 * n) ** (1.0 / 3.0)
    s2 = sigma / jnp.maximum(4.0 * kf**2 * n**2, _TINY)
    s = jnp.sqrt(jnp.maximum(s2, _TINY))
    ds = 0.5 * ((1.0 + zeta) ** (5.0 / 3.0) + (1.0 - zeta) ** (5.0 / 3.0))
    tau_w = sigma / (8.0 * n)
    tau_u = 0.3 * (3.0 * jnp.pi**2) ** (2.0 / 3.0) * n ** (5.0 / 3.0) * ds
    alpha = jnp.maximum(tau - tau_w, 0.0) / jnp.maximum(tau_u, _TINY)
    phi = 0.5 * ((1.0 + zeta) ** (2.0 / 3.0) + (1.0 - zeta) ** (2.0 / 3.0))

    # eps_c^1: PW92 + H1 (PBE-like with rs-dependent beta)
    eps_lsda = _lda_c_pw_e(nu, nd, mod=True) / n
    beta_rs = 0.066725 * (1.0 + 0.1 * rs) / (1.0 + 0.1778 * rs)
    t2 = (
        (3.0 * jnp.pi**2 / 16.0) ** (2.0 / 3.0)
        * s2
        / jnp.maximum(phi**2 * rs, _TINY)
    )
    w1 = jnp.expm1(-eps_lsda / (_SCAN_GAMMA * phi**3))
    y = beta_rs / (_SCAN_GAMMA * jnp.maximum(w1, _TINY)) * t2
    gy = (1.0 + 4.0 * y) ** (-0.25)
    h1 = _SCAN_GAMMA * phi**3 * jnp.log1p(w1 * (1.0 - gy))
    eps1 = eps_lsda + h1

    # eps_c^0: low-density limit + H0
    eps_lda0 = -_SCAN_B1C / (1.0 + _SCAN_B2C * jnp.sqrt(rs) + _SCAN_B3C * rs)
    w0 = jnp.expm1(-eps_lda0 / _SCAN_B1C)
    ginf = (1.0 + 4.0 * _SCAN_CHI * s2) ** (-0.25)
    h0 = _SCAN_B1C * jnp.log1p(w0 * (1.0 - ginf))
    dxz = 0.5 * ((1.0 + zeta) ** (4.0 / 3.0) + (1.0 - zeta) ** (4.0 / 3.0))
    gc = (1.0 - 2.3631 * (dxz - 1.0)) * (1.0 - zeta**12)
    eps0 = (eps_lda0 + h0) * gc

    fc = _scan_interp(alpha, _SCAN_C1C, _SCAN_C2C, _SCAN_DC)
    return n * (eps1 + fc * (eps0 - eps1))


_LDA_FUNCS = {
    "XC_LDA_X": _lda_x_e,
    "XC_LDA_C_PZ": _lda_c_pz_e,
    "XC_LDA_C_PW": _lda_c_pw_e,
    "XC_LDA_C_VWN": _lda_c_vwn_e,
}
# what evaluate_polarized runs in place of the entries above
_LDA_SPIN_FUNCS = {
    "XC_LDA_X": _lda_x_spin_e,
    "XC_LDA_C_PZ": _lda_c_pz_spin_e,
}
_GGA_FUNCS = {
    "XC_GGA_X_PBE": _pbe_x_e,
    "XC_GGA_C_PBE": _pbe_c_e,
    "XC_GGA_X_PBE_SOL": _pbesol_x_e,
    "XC_GGA_C_PBE_SOL": _pbesol_c_e,
}
_MGGA_FUNCS = {
    "XC_MGGA_X_SCAN": _scan_x_e,
    "XC_MGGA_C_SCAN": _scan_c_e,
}


class XCFunctional:
    """A sum of named functionals with autodiff potentials.

    evaluate() operates on flat arrays of density (and sigma for GGA) and
    returns libxc-style quantities:
      e        energy per volume (sum over functionals)
      v_up/dn  d e / d n_sigma
      vsigma_{uu,ud,dd}  d e / d sigma_ab   (GGA only)
    """

    def __init__(self, names: list[str]):
        unknown = [
            n for n in names
            if n not in _LDA_FUNCS and n not in _GGA_FUNCS
            and n not in _MGGA_FUNCS
        ]
        if unknown:
            raise ValueError(f"unsupported xc functional(s): {unknown}")
        self.names = list(names)
        self.is_mgga = any(n in _MGGA_FUNCS for n in names)
        # mGGA needs the full gradient machinery too
        self.is_gga = self.is_mgga or any(n in _GGA_FUNCS for n in names)

    def _energy(self, nu, nd, suu, sud, sdd, tu, td, spin=False):
        nu = jnp.maximum(nu, _TINY)
        nd = jnp.maximum(nd, _TINY)
        e = jnp.zeros_like(nu)
        lda = {**_LDA_FUNCS, **_LDA_SPIN_FUNCS} if spin else _LDA_FUNCS
        for name in self.names:
            if name in lda:
                e = e + lda[name](nu, nd)
            elif name in _GGA_FUNCS:
                e = e + _GGA_FUNCS[name](nu, nd, suu, sud, sdd)
            else:
                e = e + _MGGA_FUNCS[name](nu, nd, suu, sud, sdd, tu, td)
        return e

    def _eval(self, nu, nd, suu, sud, sdd, tu, td, spin=False):
        """The traced form (inside a device program): all seven derivatives,
        the energy density by a second pass. Its operations and their order
        are the fused step's compiled program: leave them as they are.
        ``spin``: the call has two channels (_energy takes _LDA_SPIN_FUNCS)."""
        up0, dn0, clean = _sanitized(nu, nd, suu, sud, sdd)
        grads = jax.grad(
            lambda a, b, c, d, f, g, h: jnp.sum(
                self._energy(a, b, c, d, f, g, h, spin)
            ),
            argnums=(0, 1, 2, 3, 4, 5, 6),
        )
        masked = _mask_dead(up0, dn0, grads(*clean, tu, td))
        return (self._energy(*clean, tu, td, spin), *masked)

    def _eval_once(self, nu, nd, suu, sud, sdd, tu, td, spin=False):
        """The host form (under _host_xc's jit): energy density and
        derivatives from one pass, with respect to the arguments the class
        reads; the derivatives it does not have are None."""
        up0, dn0, clean = _sanitized(nu, nd, suu, sud, sdd)

        def total(*args):
            e = self._energy(*args, spin)
            return jnp.sum(e), e

        nargs = 7 if self.is_mgga else 5 if self.is_gga else 2
        (_, e), grads = jax.value_and_grad(
            total, argnums=tuple(range(nargs)), has_aux=True
        )(*clean, tu, td)
        masked = _mask_dead(up0, dn0, grads)
        return (e, *masked, *(None,) * (7 - nargs))

    def _polarized(self, eval_fn, rho_up, rho_dn, sigma_uu=None,
                   sigma_ud=None, sigma_dd=None, tau_up=None, tau_dn=None):
        z = jnp.zeros_like(rho_up)
        e, vu, vd, vsuu, vsud, vsdd, vtu, vtd = eval_fn(
            rho_up, rho_dn,
            z if sigma_uu is None else sigma_uu,
            z if sigma_ud is None else sigma_ud,
            z if sigma_dd is None else sigma_dd,
            z if tau_up is None else tau_up,
            z if tau_dn is None else tau_dn,
            spin=True,
        )
        out = {"e": e, "v_up": vu, "v_dn": vd}
        if self.is_gga:
            out.update(vsigma_uu=vsuu, vsigma_ud=vsud, vsigma_dd=vsdd)
        if self.is_mgga:
            out.update(vtau_up=vtu, vtau_dn=vtd)
        return out

    def _unpolarized(self, eval_fn, rho, sigma=None, tau=None):
        half = 0.5 * rho
        z = jnp.zeros_like(rho)
        s4 = z if sigma is None else 0.25 * sigma
        t2 = z if tau is None else 0.5 * tau
        e, vu, vd, vsuu, vsud, vsdd, vtu, vtd = eval_fn(
            half, half, s4, s4, s4, t2, t2
        )
        out = {"e": e, "v": 0.5 * (vu + vd)}
        if self.is_gga:
            out["vsigma"] = 0.25 * (vsuu + vsud + vsdd)
        if self.is_mgga:
            out["vtau"] = 0.5 * (vtu + vtd)
        return out

    def evaluate_polarized(self, rho_up, rho_dn, sigma_uu=None, sigma_ud=None,
                           sigma_dd=None, tau_up=None, tau_dn=None):
        args = (rho_up, rho_dn, sigma_uu, sigma_ud, sigma_dd, tau_up, tau_dn)
        if _traced(args):
            return self._polarized(self._eval, *args)
        return _host_xc(tuple(self.names), True, args)

    def evaluate(self, rho, sigma=None, tau=None):
        """Unpolarized: rho is the total density, sigma = |grad rho|^2,
        tau the total positive KS kinetic-energy density. Returns e (per
        volume), v = de/drho, vsigma = de/dsigma, vtau = de/dtau."""
        args = (rho, sigma, tau)
        if _traced(args):
            return self._unpolarized(self._eval, *args)
        return _host_xc(tuple(self.names), False, args)


def _sanitized(nu, nd, suu, sud, sdd):
    """libxc-style density threshold: a spin channel below _DENS_TH is
    vacuum. The clip in the caller can produce EXACTLY zero channels (fully
    polarized points, m = -rho); autodiff of the GGA chain at n = 0 with
    finite sigma yields inf * 0 = NaN in v/vsigma even though the energy
    itself is finite (observed: test30 NiO FM mid-SCF). Inputs are sanitized
    BEFORE the grad (the double-where pattern) and dead-channel outputs
    masked to zero (_mask_dead), which is what libxc's dens_threshold does.
    Returns the two dead-channel masks and the five sanitized inputs."""
    th = _DENS_TH
    up0 = nu < th
    dn0 = nd < th
    return up0, dn0, (
        jnp.where(up0, th, nu),
        jnp.where(dn0, th, nd),
        jnp.where(up0, 0.0, suu),
        jnp.where(up0 | dn0, 0.0, sud),
        jnp.where(dn0, 0.0, sdd),
    )


def _mask_dead(up0, dn0, grads):
    """Zero the derivatives (v_up, v_dn, vsigma_uu, vsigma_ud, vsigma_dd,
    vtau_up, vtau_dn, or the first few of them) of a dead channel. de/dtau
    diverges as n^{-2/3} at the sanitized point n = th: a dead channel must
    get vtau = 0 too (libxc dens_threshold)."""
    dead = (up0, dn0, up0, None, dn0, up0, dn0)  # None: either channel
    return tuple(
        jnp.where(up0 | dn0 if d is None else d, 0.0, g)
        for d, g in zip(dead, grads)
    )


def _traced(args) -> bool:
    return any(isinstance(a, jax.core.Tracer) for a in args)


@partial(jax.jit, static_argnums=(0, 1))
def _host_xc(names: tuple, polarized: bool, args: tuple):
    """evaluate / evaluate_polarized of concrete arrays as one compiled
    program where the arrays live (the CPU backend in float64 under
    runtime.host_scope()): a program of the process for the functional's
    names, the spin treatment, which inputs are there, their length and
    dtype, whichever XCFunctional of whichever job asks."""
    # runs where JAX traces the body: the asking job's count of new programs
    counters["num_host_xc_traces"] += 1
    xc = XCFunctional(list(names))
    wrap = xc._polarized if polarized else xc._unpolarized
    return wrap(xc._eval_once, *args)
