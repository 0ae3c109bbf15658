"""Poisson / Ewald / form-factor tests against analytic results; the host
potential (dft/potential.generate_potential) against stored energies."""

import jax.numpy as jnp
import numpy as np
import pytest

from sirius_tpu.core import Gvec
from sirius_tpu.dft.ewald import ewald_energy
from sirius_tpu.dft.poisson import hartree_energy, hartree_potential_g
from sirius_tpu.dft.radial_tables import vloc_form_factor
from sirius_tpu.crystal.atom_type import AtomType


def test_ewald_nacl_madelung():
    # rock salt with nearest-neighbor distance d=1: E/pair = -M, M = 1.7475646
    a = 2.0  # conventional cube, d = a/2 = 1
    lat = a / 2 * np.array([[0.0, 1, 1], [1, 0, 1], [1, 1, 0]])
    gv = Gvec.build(lat, gmax=30.0)
    pos = np.array([[0.0, 0, 0], [0.5, 0.5, 0.5]])
    e = ewald_energy(lat, pos, np.array([1.0, -1.0]), gv.gcart, gv.millers, 30.0)
    np.testing.assert_allclose(e, -1.747564594633, rtol=1e-9)


def test_ewald_matches_gaussian_hartree():
    # Ewald energy of a single unit point charge == Hartree energy of a
    # narrow Gaussian (images negligible) minus the Gaussian self-energy.
    a = 8.0
    lat = np.eye(3) * a
    gv = Gvec.build(lat, gmax=40.0)
    sigma = 0.3
    e_ewald = ewald_energy(lat, np.zeros((1, 3)), np.array([1.0]), gv.gcart, gv.millers, 40.0)
    # rho(G) = e^{-sigma^2 G^2/2}/Omega for Gaussian at origin
    rho_g = np.exp(-0.5 * sigma**2 * gv.glen2) / gv.omega
    vha = hartree_potential_g(jnp.asarray(rho_g), jnp.asarray(gv.glen2))
    eh = float(hartree_energy(jnp.asarray(rho_g), vha, gv.omega))
    self_energy = 1.0 / (2.0 * np.sqrt(np.pi) * sigma)
    # E_H omits G=0 against the uniform background; the point-charge Ewald's
    # corresponding term is -(2 pi / Omega) sigma^2 (Gaussian spread charge)
    background = 2.0 * np.pi * sigma**2 / gv.omega
    np.testing.assert_allclose(e_ewald, eh - self_energy - background, atol=2e-6)


def _erf_pseudo_atom(z=1.0):
    """Analytic species: V_loc(r) = -z erf(r)/r (Gaussian-smeared Coulomb)."""
    r = np.geomspace(1e-7, 12.0, 900)
    from scipy.special import erf

    return AtomType(
        label="X", symbol="X", zn=z, pseudo_type="NC", r=r,
        vloc=-z * erf(r) / r, beta=[], d_ion=np.zeros((0, 0)),
        augmentation=[], atomic_wfs=[], rho_total=None, rho_core=None,
        core_correction=False,
    )


def test_vloc_form_factor_analytic():
    at = _erf_pseudo_atom(z=2.0)
    q = np.array([0.0, 0.5, 1.5, 4.0, 9.0])
    ff = vloc_form_factor(at, q)
    # for V = -z erf(r)/r: ff(q) = -z e^{-q^2/4}/q^2, ff(0) = z/4
    # (int_0^inf r erfc(r) dr = 1/4)
    np.testing.assert_allclose(ff[0], 2.0 / 4.0, rtol=1e-8)
    expect = -2.0 * np.exp(-q[1:] ** 2 / 4) / q[1:] ** 2
    np.testing.assert_allclose(ff[1:], expect, atol=1e-10)


def test_hartree_potential_g0_zero():
    rho = jnp.array([1.0 + 0j, 0.5, 0.25])
    g2 = jnp.array([0.0, 1.0, 4.0])
    v = hartree_potential_g(rho, g2)
    assert float(jnp.abs(v[0])) == 0.0
    np.testing.assert_allclose(np.asarray(v[1:]), 4 * np.pi * np.array([0.5, 0.0625]))


# generate_potential of the synthetic 2-atom ultrasoft deck's start density
# (with a fifth of it as moment where polarised), as the tree before PR 43
# printed them: the functional op by op through XCFunctional._eval
STORED = {
    ("lda", False): {"vxc": -2.8977478189617987, "exc": -2.223584979186792,
                     "veff": -3.2563889876165804, "bxc": 0.0},
    ("lda", True): {"vxc": -2.891499385999391, "exc": -2.2343923126864795,
                    "veff": -3.250140554654172,
                    "bxc": -0.021696562308607056},
    ("pbe", False): {"vxc": -2.8954329863057024, "exc": -2.221241260672714,
                     "veff": -3.2540741549604837, "bxc": 0.0},
    ("pbe", True): {"vxc": -2.8886071182892037, "exc": -2.2328317448070414,
                    "veff": -3.247248286943985,
                    "bxc": -0.023264079493100456},
}
FUNCTIONALS = {"lda": ["XC_LDA_X", "XC_LDA_C_PZ"],
               "pbe": ["XC_GGA_X_PBE", "XC_GGA_C_PBE"]}


@pytest.mark.parametrize("polarized", [False, True], ids=["unpol", "pol"])
@pytest.mark.parametrize("functional", ["lda", "pbe"])
def test_host_potential_equals_stored_energies(functional, polarized):
    """The host potential with its functional as one compiled program
    (dft/xc._host_xc) integrates to the energies of the op-by-op one."""
    from sirius_tpu.dft.density import initial_density_g
    from sirius_tpu.dft.potential import generate_potential
    from sirius_tpu.dft.xc import XCFunctional
    from sirius_tpu.testing import synthetic_silicon_context

    names = FUNCTIONALS[functional]
    ctx = synthetic_silicon_context(
        gk_cutoff=3.0, pw_cutoff=7.0, ngridk=(1, 1, 1), num_bands=8,
        ultrasoft=True, use_symmetry=False,
        extra_params={"xc_functionals": names})
    rho_g = np.asarray(initial_density_g(ctx))
    pot = generate_potential(
        ctx, rho_g, XCFunctional(names), 0.2 * rho_g if polarized else None)
    for k, want in STORED[functional, polarized].items():
        assert pot.energies[k] == pytest.approx(want, rel=1e-12, abs=0.0), k


def test_fused_job_spans_both_host_potentials(tmp_path):
    """A fused job's tree holds the start potential under scf.setup and the
    reported energy's under scf.finalize, both saying their functional ran
    compiled; the job after it in the process traces no host program."""
    from sirius_tpu.config.schema import load_config
    from sirius_tpu.dft.scf import run_scf
    from sirius_tpu.obs import spans
    from sirius_tpu.serve.scheduler import build_job_context

    deck = {
        "parameters": {
            "gk_cutoff": 3.0, "pw_cutoff": 7.0, "ngridk": [1, 1, 1],
            "num_bands": 8, "use_symmetry": False,
            "xc_functionals": FUNCTIONALS["pbe"],
            "smearing_width": 0.025, "num_dft_iter": 2,
        },
        "control": {"ngk_pad_quantum": 16, "telemetry": True},
        "synthetic": {"ultrasoft": True},
    }
    traces = []
    for _ in range(2):
        cfg = load_config(deck)
        ctx = build_job_context(cfg, str(tmp_path))
        with spans.capture() as cap:
            res = run_scf(cfg, base_dir=str(tmp_path), ctx=ctx)
        assert res["placement"]["path"].endswith("fused")
        traces.append(res["counters"]["num_host_xc_traces"])
        by_id = {r["span_id"]: r for r in cap.records}
        for stage in ("setup", "finalize"):
            (pot,) = [r for r in cap.records
                      if r["name"] == f"scf.{stage}.potential"]
            assert pot["host_xc"] == "compiled" and pot["xc"] == "gga"
            assert by_id[pot["parent_id"]]["name"] == f"scf.{stage}"
    assert traces[0] in (0, 1) and traces[1] == 0
