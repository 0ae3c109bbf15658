"""XC functional tests: known analytic values, autodiff-potential consistency
with finite differences, spin-symmetry consistency (mirrors reference
test_pppw_xc and the libxc reference values)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sirius_tpu.dft.xc import XCFunctional


def test_lda_x_known_value():
    xc = XCFunctional(["XC_LDA_X"])
    rho = jnp.array([1.0])
    out = xc.evaluate(rho)
    eps = float(out["e"][0])  # energy per volume at rho=1 == eps per particle
    np.testing.assert_allclose(eps, -(3 / 4) * (3 / np.pi) ** (1 / 3), rtol=1e-12)
    # v_x = (4/3) eps_x for LDA exchange
    np.testing.assert_allclose(float(out["v"][0]), 4 / 3 * eps, rtol=1e-12)


def test_lda_c_pz_known_value():
    # PZ at rs=2 (low-density branch): eps_c = gamma/(1+b1*sqrt(2)+b2*2)
    rs = 2.0
    rho = 3 / (4 * np.pi * rs**3)
    xc = XCFunctional(["XC_LDA_C_PZ"])
    out = xc.evaluate(jnp.array([rho]))
    expect = -0.1423 / (1 + 1.0529 * np.sqrt(2.0) + 0.3334 * 2.0)
    np.testing.assert_allclose(float(out["e"][0]) / rho, expect, rtol=1e-10)


def test_lda_c_pw_known_value():
    # PW92 eps_c(rs=2, zeta=0) = -0.044757 Ha (published)
    rs = 2.0
    rho = 3 / (4 * np.pi * rs**3)
    xc = XCFunctional(["XC_LDA_C_PW"])
    out = xc.evaluate(jnp.array([rho]))
    np.testing.assert_allclose(float(out["e"][0]) / rho, -0.04476, rtol=1e-3)


def _eps_c(names, rs, zeta):
    n = 3 / (4 * np.pi * rs**3)
    nu = jnp.array([0.5 * n * (1 + zeta)])
    nd = jnp.array([0.5 * n * (1 - zeta)])
    out = XCFunctional(names).evaluate_polarized(nu, nd)
    return float(out["e"][0]) / n


def test_lda_c_pw_intermediate_zeta_matches_pz():
    # PW92 and PZ81 fit the same QMC data; at intermediate polarization they
    # agree to better than ~1e-3 Ha/e. Round-1 had the spin-stiffness sign
    # flipped, which broke this by up to 0.014 Ha/e (ADVICE r1).
    for rs in (1.0, 2.0, 5.0):
        for zeta in (0.3, 0.5, 0.8):
            pw = _eps_c(["XC_LDA_C_PW"], rs, zeta)
            pz = _eps_c(["XC_LDA_C_PZ"], rs, zeta)
            assert abs(pw - pz) < 2.5e-3, (rs, zeta, pw, pz)


def test_lda_c_pw_monotonic_in_polarization():
    # |eps_c| decreases with polarization: eps_c(zeta) is monotonically
    # increasing (toward less negative) on zeta in [0, 1].
    for rs in (0.5, 2.0, 10.0):
        eps = [_eps_c(["XC_LDA_C_PW"], rs, z) for z in np.linspace(0.0, 1.0, 11)]
        assert np.all(np.diff(eps) > 0), (rs, eps)


@pytest.mark.parametrize("names", [["XC_LDA_X", "XC_LDA_C_PZ"], ["XC_LDA_C_PW"]])
def test_vxc_matches_finite_difference(names):
    xc = XCFunctional(names)
    rho = jnp.array([0.02, 0.3, 1.1, 4.0])
    out = xc.evaluate(rho)
    h = 1e-6
    for i in range(len(rho)):
        ep = float(xc.evaluate(rho.at[i].add(h))["e"].sum())
        em = float(xc.evaluate(rho.at[i].add(-h))["e"].sum())
        np.testing.assert_allclose(float(out["v"][i]), (ep - em) / (2 * h), rtol=1e-5)


def test_spin_consistency_lda():
    xc = XCFunctional(["XC_LDA_X", "XC_LDA_C_PZ"])
    rho = jnp.array([0.2, 0.9])
    unpol = xc.evaluate(rho)
    pol = xc.evaluate_polarized(rho / 2, rho / 2)
    np.testing.assert_allclose(np.asarray(pol["e"]), np.asarray(unpol["e"]), rtol=1e-12)
    np.testing.assert_allclose(np.asarray(pol["v_up"]), np.asarray(unpol["v"]), rtol=1e-12)


def test_fully_polarized_exchange():
    # E_x[n,0] = 2^{1/3} E_x[n/2,n/2]
    xc = XCFunctional(["XC_LDA_X"])
    n = jnp.array([0.7])
    ep = xc.evaluate_polarized(n, jnp.array([1e-30]))
    eu = xc.evaluate(n)
    np.testing.assert_allclose(
        float(ep["e"][0]), 2 ** (1 / 3) * float(eu["e"][0]), rtol=1e-9
    )


def test_pbe_reduces_to_lda_at_zero_gradient():
    xcp = XCFunctional(["XC_GGA_X_PBE"])
    xcl = XCFunctional(["XC_LDA_X"])
    rho = jnp.array([0.5, 1.5])
    sig = jnp.zeros(2)
    np.testing.assert_allclose(
        np.asarray(xcp.evaluate(rho, sig)["e"]),
        np.asarray(xcl.evaluate(rho)["e"]),
        rtol=1e-10,
    )


def test_pbe_enhancement_factor():
    # F_x(s) = 1 + kappa - kappa/(1 + mu s^2/kappa); test at s=1
    kappa, mu = 0.804, 0.2195149727645171
    rho = 1.0
    kf = (3 * np.pi**2 * rho) ** (1 / 3)
    s = 1.0
    sigma = (2 * kf * rho * s) ** 2
    xcp = XCFunctional(["XC_GGA_X_PBE"])
    xcl = XCFunctional(["XC_LDA_X"])
    fx = float(xcp.evaluate(jnp.array([rho]), jnp.array([sigma]))["e"][0]) / float(
        xcl.evaluate(jnp.array([rho]))["e"][0]
    )
    np.testing.assert_allclose(fx, 1 + kappa - kappa / (1 + mu / kappa), rtol=1e-8)


def test_pbe_c_vsigma_finite_difference():
    xc = XCFunctional(["XC_GGA_C_PBE"])
    rho = jnp.array([0.8])
    sig = jnp.array([0.3])
    out = xc.evaluate(rho, sig)
    h = 1e-6
    ep = float(xc.evaluate(rho, sig + h)["e"][0])
    em = float(xc.evaluate(rho, sig - h)["e"][0])
    np.testing.assert_allclose(float(out["vsigma"][0]), (ep - em) / (2 * h), rtol=1e-5)


def test_pbesol_differs_from_pbe_only_in_gradient_terms():
    xcs = XCFunctional(["XC_GGA_X_PBE_SOL", "XC_GGA_C_PBE_SOL"])
    xcp = XCFunctional(["XC_GGA_X_PBE", "XC_GGA_C_PBE"])
    rho = jnp.array([0.6])
    # zero gradient: identical (same LDA limits)
    np.testing.assert_allclose(
        float(xcs.evaluate(rho, jnp.zeros(1))["e"][0]),
        float(xcp.evaluate(rho, jnp.zeros(1))["e"][0]),
        rtol=1e-12,
    )
    # finite gradient: PBEsol's weaker mu gives less negative exchange
    sig = jnp.array([1.5])
    es = float(XCFunctional(["XC_GGA_X_PBE_SOL"]).evaluate(rho, sig)["e"][0])
    ep = float(XCFunctional(["XC_GGA_X_PBE"]).evaluate(rho, sig)["e"][0])
    assert es > ep


def test_pbesol_x_enhancement_factor():
    # F_x(s=1) = 1 + kappa - kappa/(1 + mu_sol/kappa), mu_sol = 10/81
    kappa, mu = 0.804, 10.0 / 81.0
    rho = 1.0
    kf = (3 * np.pi**2 * rho) ** (1 / 3)
    sigma = (2 * kf * rho) ** 2
    fx = float(
        XCFunctional(["XC_GGA_X_PBE_SOL"]).evaluate(jnp.array([rho]), jnp.array([sigma]))["e"][0]
    ) / float(XCFunctional(["XC_LDA_X"]).evaluate(jnp.array([rho]))["e"][0])
    np.testing.assert_allclose(fx, 1 + kappa - kappa / (1 + mu / kappa), rtol=1e-8)


def test_vwn_consistent_with_sibling_fits():
    """VWN5, PW92 and PZ parametrize the same Ceperley-Alder QMC data;
    they agree to well under 1 mHa/electron over the physical rs range at
    every polarization (measured max |VWN-PW92| = 4.6e-4 at rs=0.5). Also
    pin the high-density limit slope d eps/d ln rs -> A = 0.0310907."""
    import jax.numpy as jnp

    from sirius_tpu.dft.xc import _lda_c_pw_e, _lda_c_vwn_e

    def eps(f, rs, z):
        n = 3.0 / (4.0 * jnp.pi * rs**3)
        nu = 0.5 * n * (1 + z)
        nd = 0.5 * n * (1 - z)
        return float(f(jnp.asarray([nu]), jnp.asarray([nd]))[0] / n)

    for rs in (0.5, 1.0, 2.0, 5.0, 10.0):
        for z in (0.0, 0.5, 1.0):
            dv = abs(eps(_lda_c_vwn_e, rs, z) - eps(_lda_c_pw_e, rs, z))
            assert dv < 6e-4, (rs, z, dv)
    # high-density logarithmic slope (exact RPA coefficient)
    s = (eps(_lda_c_vwn_e, 0.01, 0.0) - eps(_lda_c_vwn_e, 0.012, 0.0)) / (
        np.log(0.01) - np.log(0.012)
    )
    assert abs(s - 0.0310907) < 2e-3


# ---------------------------------------------------------------------------
# The host entry (dft/xc._host_xc): what evaluate / evaluate_polarized run
# when handed concrete arrays, against the op-by-op evaluation it replaced.

LDA = ["XC_LDA_X", "XC_LDA_C_PZ"]
PBE = ["XC_GGA_X_PBE", "XC_GGA_C_PBE"]
SCAN = ["XC_MGGA_X_SCAN", "XC_MGGA_C_SCAN"]


def _plain_eval(xc, nu, nd, suu, sud, sdd, tu, td):
    """XCFunctional._eval as it stood before the host entry, run eagerly:
    all seven derivatives from jax.grad, the energy density by a second
    evaluation. The plain reference, kept here."""
    th = 1e-13
    up0 = nu < th
    dn0 = nd < th
    nu_s = jnp.where(up0, th, nu)
    nd_s = jnp.where(dn0, th, nd)
    suu_s = jnp.where(up0, 0.0, suu)
    sud_s = jnp.where(up0 | dn0, 0.0, sud)
    sdd_s = jnp.where(dn0, 0.0, sdd)
    vu, vd, vsuu, vsud, vsdd, vtu, vtd = jax.grad(
        lambda *a: jnp.sum(xc._energy(*a)), argnums=tuple(range(7))
    )(nu_s, nd_s, suu_s, sud_s, sdd_s, tu, td)
    return (
        xc._energy(nu_s, nd_s, suu_s, sud_s, sdd_s, tu, td),
        jnp.where(up0, 0.0, vu), jnp.where(dn0, 0.0, vd),
        jnp.where(up0, 0.0, vsuu), jnp.where(up0 | dn0, 0.0, vsud),
        jnp.where(dn0, 0.0, vsdd),
        jnp.where(up0, 0.0, vtu), jnp.where(dn0, 0.0, vtd),
    )


def _plain(xc, polarized, rho, sig, tau):
    """The plain reference's unpolarised / polarised wrap: rho, sig, tau are
    the one, three and one arrays of evaluate (sig, tau None where the
    class does not read them) or the two, three and two of
    evaluate_polarized."""
    z = jnp.zeros_like(rho[0])
    if polarized:
        e, vu, vd, vsuu, vsud, vsdd, vtu, vtd = _plain_eval(
            xc, *rho, *(sig or (z, z, z)), *(tau or (z, z)))
        out = {"e": e, "v_up": vu, "v_dn": vd}
        if xc.is_gga:
            out.update(vsigma_uu=vsuu, vsigma_ud=vsud, vsigma_dd=vsdd)
        if xc.is_mgga:
            out.update(vtau_up=vtu, vtau_dn=vtd)
        return out
    half = 0.5 * rho[0]
    s4 = z if sig is None else 0.25 * sig[0]
    t2 = z if tau is None else 0.5 * tau[0]
    e, vu, vd, vsuu, vsud, vsdd, vtu, vtd = _plain_eval(
        xc, half, half, s4, s4, s4, t2, t2)
    out = {"e": e, "v": 0.5 * (vu + vd)}
    if xc.is_gga:
        out["vsigma"] = 0.25 * (vsuu + vsud + vsdd)
    if xc.is_mgga:
        out["vtau"] = 0.5 * (vtu + vtd)
    return out


@pytest.mark.parametrize("names,polarized", [
    (LDA, False), (LDA, True), (PBE, False), (PBE, True), (SCAN, True),
], ids=["lda", "lda-pol", "pbe", "pbe-pol", "scan-pol"])
def test_host_entry_equals_plain_evaluation(names, polarized):
    """4096 points of a box's spread of densities, with vacuum points below
    the density threshold and an exactly empty channel (a fully polarised
    point after the caller's clip) among them."""
    n = 4096
    rng = np.random.default_rng(43)
    xc = XCFunctional(names)

    def channel():
        """Density, gradient and kinetic-energy density of one channel:
        reduced gradients up to 3, tau above its von Weizsaecker bound."""
        dens = 10.0 ** rng.uniform(-5, 0.5, n)
        kf = np.cbrt(3.0 * np.pi**2 * dens)
        u = rng.standard_normal((3, n))
        grad = 2.0 * kf * dens * rng.uniform(0, 3, n) * u / np.linalg.norm(
            u, axis=0)
        tau = np.sum(grad**2, axis=0) / (8.0 * dens) + rng.uniform(
            0, 3, n) * 0.3 * kf**2 * dens
        return dens, grad, tau

    nu, gu, tu = channel()
    nu[:8] = 10.0 ** rng.uniform(-20, -14, 8)  # vacuum
    if polarized:
        nd, gd, td = channel()
        nd[4:40] = 0.0  # an empty channel; 4..8: both dead
        rho = (nu, nd)
        sig = (np.sum(gu * gu, axis=0), np.sum(gu * gd, axis=0),
               np.sum(gd * gd, axis=0)) if xc.is_gga else None
        tau = (tu, td) if xc.is_mgga else None
    else:
        nu[8:40] = 0.0
        rho = (nu,)
        sig = (np.sum(gu * gu, axis=0),) if xc.is_gga else None
        tau = (tu,) if xc.is_mgga else None
    rho, sig, tau = jax.tree_util.tree_map(jnp.asarray, (rho, sig, tau))
    evaluate = xc.evaluate_polarized if polarized else xc.evaluate
    got = evaluate(*rho, *(sig or ()), *(tau or ()))
    want = _plain(xc, polarized, rho, sig, tau)
    assert sorted(got) == sorted(want)
    # 1e-13 of each output's own size at the point: the value, or for a
    # derivative that passes through zero what it multiplies into,
    # |e| over the total of its conjugate variable (de = v dn + ...)
    conj = {"v": sum(rho), "vsigma": sum(sig or ()), "vtau": sum(tau or ())}
    e = np.abs(np.asarray(want["e"]))
    for k, w in want.items():
        w, g = np.asarray(w), np.asarray(got[k])
        assert g.dtype == np.float64 and np.all(np.isfinite(g)), k
        size = np.abs(w) if k == "e" else np.maximum(
            np.abs(w), e / np.maximum(conj[k.split("_")[0]], 1e-300))
        assert np.all(np.abs(g - w) <= 1e-13 * size), k
        assert np.array_equal(g == 0.0, w == 0.0), k  # the dead points
    dead = np.asarray(rho[0]) < 1e-13
    assert dead.sum() >= 8
    assert not np.any(np.asarray(got["v_up" if polarized else "v"])[dead])


def test_host_program_belongs_to_the_process():
    """A second XCFunctional of the same names (every job builds one) finds
    the first one's program: no new trace, for either spin treatment."""
    from sirius_tpu.utils.profiler import counters

    rho = jnp.linspace(0.01, 1.0, 321)  # a length no other test compiles
    first = XCFunctional(PBE)
    first.evaluate(rho, rho)
    first.evaluate_polarized(rho, rho, rho, rho, rho)
    before = counters["num_host_xc_traces"]
    assert before >= 2
    again = XCFunctional(list(PBE))
    a = again.evaluate(rho, rho)
    again.evaluate_polarized(rho, rho, rho, rho, rho)
    assert counters["num_host_xc_traces"] == before
    np.testing.assert_array_equal(
        np.asarray(a["v"]), np.asarray(first.evaluate(rho, rho)["v"]))
    # another length is another program, and says so
    again.evaluate(rho[:320], rho[:320])
    assert counters["num_host_xc_traces"] == before + 1


@pytest.fixture(scope="module")
def small_ctx():
    from sirius_tpu.testing import synthetic_silicon_context

    return synthetic_silicon_context(
        gk_cutoff=3.0, pw_cutoff=7.0, ngridk=(1, 1, 1), num_bands=8,
        ultrasoft=True, use_symmetry=False)


@pytest.mark.parametrize("polarized", [False, True], ids=["unpol", "pol"])
@pytest.mark.parametrize("names", [LDA, PBE], ids=["lda", "pbe"])
def test_device_potential_holds_no_nested_program(small_ctx, names, polarized):
    """Inside a device program the functional is the caller's own lines:
    generate_potential_device's jaxpr calls no compiled sub-program for XC
    (what it holds of pjit is jnp's own, not _host_xc), and tracing it
    books no host program."""
    from sirius_tpu.dft import potential
    from sirius_tpu.utils.profiler import counters

    ctx = small_ctx
    dims = tuple(ctx.gvec.fft.dims)
    tb = {k: jnp.asarray(v)
          for k, v in potential.build_potential_device_tables(ctx).items()}
    tb.update(potential.constant_fields_device(tb, dims))
    rho_g = jnp.asarray(ctx.rho_core_g + 0.01 / (1.0 + ctx.gvec.glen2))
    mag_g = 0.2 * rho_g if polarized else None
    before = counters["num_host_xc_traces"]
    jaxpr = jax.make_jaxpr(
        lambda r, m, t: potential.generate_potential_device(
            XCFunctional(names), r, m, t, dims, tuple(ctx.fft_coarse.dims),
            float(ctx.unit_cell.omega)))(rho_g, mag_g, tb)
    assert counters["num_host_xc_traces"] == before

    def called(jp):
        for eqn in jp.eqns:
            if eqn.primitive.name in ("pjit", "jit", "closed_call",
                                      "core_call", "custom_vjp_call"):
                yield eqn.params.get("name", eqn.primitive.name)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from called(sub)

    names_called = set(called(jaxpr.jaxpr))
    assert names_called  # the walk sees jnp's own (clip, where, ...)
    assert not any("xc" in n or "eval" in n for n in names_called), \
        names_called
