"""A Gamma-only n x n x n supercell of the undisplaced crystal is the 2-atom
cell on the Gamma-centred n x n x n k-mesh: the benchmark's folded reference
(benchmark/make_refs_folded.py) rests on it, and it ties the packed-real
Gamma solve to the batched k-set solve, which share no compiled program.

Also here, because they are what a Gamma cell of 54 atoms needed: the fused
step's energy terms as two float32 words (core/hilo.py), and the (k, b) mesh
of the four-chip cell on four virtual devices."""

import copy

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sirius_tpu.config.schema import load_config
from sirius_tpu.dft.scf import run_scf
from sirius_tpu.serve.scheduler import build_job_context

PARAMS = {
    "gk_cutoff": 3.0, "pw_cutoff": 7.0, "use_symmetry": False,
    "xc_functionals": ["XC_LDA_X", "XC_LDA_C_PZ"], "smearing_width": 0.025,
    "num_dft_iter": 60, "precision_wf": "fp64", "density_tol": 1e-8,
    "energy_tol": 1e-9,
}


def deck(supercell, ngridk, num_bands, **params):
    return {"parameters": dict(PARAMS, ngridk=list(ngridk),
                               num_bands=num_bands, **params),
            "control": {"ngk_pad_quantum": 16, "verbosity": 0},
            "synthetic": {"ultrasoft": True, "supercell": supercell}}


def run(d, devices):
    cfg = load_config(copy.deepcopy(d))
    return run_scf(cfg, ctx=build_job_context(cfg, "."), devices=devices)


@pytest.fixture(scope="module")
def one_device():
    return jax.devices()[1:2]  # a compute device that is not the host's


@pytest.fixture(scope="module")
def kmesh_222(one_device):
    """The 2-atom cell on the 2x2x2 mesh, 8 bands a k-point, f64."""
    r = run(deck(1, (2, 2, 2), 8), one_device)
    assert r["converged"] and r["placement"]["path"] == "batched+fused"
    return r


@pytest.fixture(scope="module")
def supercell_222_f64(one_device):
    """Its 2x2x2 supercell at Gamma, 64 bands, f64."""
    r = run(deck(2, (1, 1, 1), 64), one_device)
    assert r["converged"] and r["placement"]["path"] == "gamma"
    assert "fused_step" in r["placement"]
    return r


def test_gamma_supercell_is_the_cell_on_the_folded_kmesh(kmesh_222,
                                                        supercell_222_f64):
    e_fold = 8 * kmesh_222["energy"]["total"]
    assert abs(supercell_222_f64["energy"]["total"] - e_fold) <= 1e-8


def _plain():
    import os
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmark import plain_pwus

    return plain_pwus


def test_plain_reference_meets_the_programs_kmesh_run(kmesh_222):
    """benchmark/plain_pwus.py, the numpy SCF that writes si54-gamma-us's
    stored reference and imports nothing of sirius_tpu, against the
    program's own f64 run of the same 2-atom k-mesh deck: 3.3e-8 Ha."""
    r = _plain().scf((2, 2, 2), 3.0, 7.0, 8, density_tol=1e-11)
    assert r["converged"]
    assert abs(r["energy_total_ha"] - kmesh_222["energy"]["total"]) <= 1e-7
    assert abs(r["ewald"] - kmesh_222["energy"]["ewald"]) <= 1e-9
    assert abs(r["electrons"] - 8.0) <= 1e-10


@pytest.mark.parametrize("what", ["vloc", "beta_s", "beta_p", "q_s", "q_p"])
def test_plain_reference_restates_the_tabulated_species(what):
    """plain_pwus carries the synthetic silicon as closed forms of its
    Bessel transforms; the program tabulates the radial functions
    (testing.synthetic_silicon_type). A spline quadrature of the table is
    the closed form."""
    from scipy.interpolate import CubicSpline
    from scipy.special import spherical_jn

    from sirius_tpu.testing import synthetic_silicon_type

    plain = _plain()
    t = synthetic_silicon_type(ultrasoft=True)
    r = np.asarray(t.r)
    q = np.array([0.0, 0.3, 1.0, 2.5, 6.0])

    def quad(f):
        return float(CubicSpline(r, f).integrate(r[0], r[-1]))

    if what == "vloc":  # the short-ranged part, v + Z/r
        short = (np.asarray(t.vloc) + t.zn / r) * r * r
        got = [4 * np.pi * quad(short * spherical_jn(0, x * r)) for x in q]
        want = plain.vloc_q(q) + np.where(
            q > 0, 4 * np.pi * t.zn / np.where(q > 0, q, 1.0) ** 2, 0.0)
    elif what.startswith("beta"):
        i = ("beta_s", "beta_p").index(what)
        got = [quad(np.asarray(t.beta[i].rbeta) * r
                    * spherical_jn(t.beta[i].l, x * r)) for x in q]
        want = plain.beta_q(q)[i]
        assert np.allclose(np.diag(t.d_ion), plain.D_ION[:2])
    else:
        i = ("q_s", "q_p").index(what)
        got = [quad(np.asarray(t.augmentation[i].qr)
                    * spherical_jn(0, x * r)) for x in q]
        want = plain.aug_q(q)[i]
    assert np.allclose(got, want, rtol=0, atol=2e-7)


def test_f32_supercell_meets_the_folded_reference(kmesh_222,
                                                  supercell_222_f64,
                                                  one_device):
    """The benchmark's check of si54-gamma.scf at the rehearsal's size: the
    32-bit path against 8 x the f64 k-mesh energy, 5e-6 Ha an atom, in at
    most seven iterations more than the f64 run of the same deck. Seven, not
    the four ISSUE 27 asked for: on the CPU backend this deck's f32 Anderson
    tail bounces between rms 1e-4 and 1e-5 for a few iterations and the count
    comes out anywhere from 10 to 14 for the f64 run's 7, before this PR and
    after it (PERF.md section 7: an iteration count of a 32-bit path is a
    chip number)."""
    r = run(deck(2, (1, 1, 1), 64, precision_wf="fp32", density_tol=1e-5,
                 energy_tol=1e-5), one_device)
    f64 = run(deck(2, (1, 1, 1), 64, density_tol=1e-5, energy_tol=1e-5),
              one_device)
    assert r["converged"] and r["placement"]["path"] == "gamma"
    assert r["placement"]["band_solve"][1] == "float32"
    e_fold = 8 * kmesh_222["energy"]["total"]
    assert abs(r["energy"]["total"] - e_fold) <= 5e-6 * 16
    assert r["num_scf_iterations"] <= f64["num_scf_iterations"] + 7


@pytest.fixture(scope="module")
def step_of_54_atoms(one_device):
    """The fused step of a 54-atom Gamma cell with the inputs of its first
    call, and a function that runs it in either precision on the same
    float32 values with `more` added to the accumulated coarse-grid density
    (a uniform shift, of `more` electrons). Returns
    (energy without the Ewald term, the raw scalar record)."""
    from sirius_tpu.dft import fused as fused_mod
    from sirius_tpu.dft.fused import FusedCarry, FusedScf

    seen = {}
    sound_init, sound_step = FusedScf.__init__, FusedScf.step

    def keep_init(self, *a, **kw):
        sound_init(self, *a, **kw)
        seen["init"] = (a, kw)

    def keep_step(self, carry, *args):
        # the call's inputs, on the host, before the carry is donated
        seen["carry"] = [np.asarray(x) for x in carry]
        seen["args"] = [np.asarray(x) for x in args]
        return sound_step(self, carry, *args)

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(FusedScf, "__init__", keep_init)
        mp.setattr(FusedScf, "step", keep_step)
        r = run(deck(3, (1, 1, 1), 216, num_dft_iter=1), one_device)
    assert r["placement"]["path"] == "gamma" and "carry" in seen
    built = {}

    def energy(wf_dtype, more):
        if wf_dtype not in built:
            a, kw = seen["init"]
            built[wf_dtype] = FusedScf(
                *a, **dict(kw, wf_dtype=wf_dtype, exec_cache=None))
        f = built[wf_dtype]
        rdt = np.dtype(f.rdt.name)

        def cast(x):  # the same float32 values for both precisions
            if np.issubdtype(x.dtype, np.floating):
                return jnp.asarray(x.astype(np.float32).astype(rdt))
            return jnp.asarray(x)

        args = [seen["args"][0] + np.float32(more)] + seen["args"][1:]
        _, o = f.step(FusedCarry(*[cast(x) for x in seen["carry"]]),
                      *[cast(x) for x in args])
        raw = np.asarray(o["scalars"])
        assert raw.dtype == rdt
        s = fused_mod.fold_scalars(raw)
        return (s[fused_mod.S_EVAL] - s[fused_mod.S_VXC]
                - s[fused_mod.S_BXC] - 0.5 * s[fused_mod.S_VHA]
                + s[fused_mod.S_EXC] + s[fused_mod.S_E2]
                - s[fused_mod.S_E1]), raw

    return energy, r["energy"]["ewald"]


def test_f32_step_resolves_the_energy_of_a_54_atom_cell(step_of_54_atoms):
    """At |E| ~ 235 Ha one float32 word resolves 1.5e-5 Ha, and the loop's
    test asks whether the energy moved by 1e-5. The fused step's summed
    terms are (hi, lo) pairs, so from the same inputs the float32 step's
    energy is the float64 step's to 3e-5 Ha. Not to the 2e-6 Ha ISSUE 27
    asked for: every real-space integral comes out 1.4e-7 of itself
    smaller, the float32 inverse FFT's normalisation (1/N rounded, times N)
    squared. That offset is the same in every iteration; the loop's test
    sees differences. And where the count the float32 step accumulated is
    the electron count to rounding (fused.CHARGE_EPS), the output density's
    G = 0 component is set to it: 1e-3 electrons more in the accumulated
    charge (a uniform shift of the coarse-grid density), which move the
    float64 step's energy by 3.2e-5 Ha, move the float32 step's by what
    re-rounding every element of the density moves it, under 1e-5. (The
    job's first step, whose mixing is linear: later the Anderson
    coefficients come out of a float32 least-squares solve and the mixed
    density is another, as good, iterate.)"""
    from sirius_tpu.dft import fused as fused_mod

    energy, e_ewald = step_of_54_atoms
    e64, raw64 = energy(jnp.complex128, 0.0)
    g64, _ = energy(jnp.complex128, 1e-3)
    e32, raw32 = energy(jnp.complex64, 0.0)
    g32, grown32 = energy(jnp.complex64, 1e-3)
    assert abs(e64 + e_ewald) > 200.0  # a cell of this size
    assert not raw64[fused_mod.NUM_SCALARS:].any()  # f64: one word
    assert raw32[fused_mod.NUM_SCALARS:].any()      # f32: two
    assert abs(e32 - e64) <= 3e-5
    # the accumulated charge did move, by 1e-3 electrons ...
    assert abs(grown32[fused_mod.S_NEL] - raw32[fused_mod.S_NEL] - 1e-3) < 2e-4
    # ... and the float32 energy did not, where the float64 one does
    assert abs(g32 - e32) <= 1e-5
    assert abs(g64 - e64) >= 2.5e-5


def test_a_charge_fault_is_not_renormalised_away(step_of_54_atoms):
    """The count is set only where the accumulated one is the count to
    rounding. 0.02 electrons too many, as a lost band norm or a wrong
    augmentation charge would leave, stay in the float32 step's density as
    they do in the float64 step's and the host tail's: in S_NEL, and in the
    energy, which the benchmark compares."""
    from sirius_tpu.dft import fused as fused_mod

    energy, _ = step_of_54_atoms
    e64, raw64 = energy(jnp.complex128, 0.0)
    f64, fault64 = energy(jnp.complex128, 2e-2)
    e32, raw32 = energy(jnp.complex64, 0.0)
    f32, fault32 = energy(jnp.complex64, 2e-2)
    for raw, fault in ((raw64, fault64), (raw32, fault32)):
        assert 1.5e-2 < fault[fused_mod.S_NEL] - raw[fused_mod.S_NEL] < 2.5e-2
        assert fault[fused_mod.S_FINITE] == 1.0
    assert abs(f64 - e64) > 3e-4
    assert abs((f32 - e32) - (f64 - e64)) <= 3e-5


def test_kpool_deck_on_four_devices_matches_one(tmp_path):
    """The four-chip cell's rehearsal deck in f64: run_scf on four devices
    picks a (k, b) mesh, says so in the result and in the scf.setup span,
    spreads the band solve over all four, and gives the one-device energy."""
    import json
    import os

    from sirius_tpu.obs import spans

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs", "si2-k444-us",
                           "config.json")) as f:
        config = json.load(f)
    d = {k: copy.deepcopy(v) for k, v in config["rehearse"].items()
         if k != "geometry"}
    for section, over in config["reference"]["overrides"].items():
        d.setdefault(section, {}).update(over)
    one = run(d, jax.devices()[:1])
    with spans.capture() as cap:
        four = run(d, jax.devices()[:4])
    assert one["converged"] and four["converged"]
    assert one["placement"]["mesh"] is None
    mesh = four["placement"]["mesh"]
    assert mesh and set(mesh) == {"k", "b"} and mesh["k"] * mesh["b"] == 4
    assert four["placement"]["path"] == "batched+fused"
    assert len(set(four["placement"]["band_solve"][2])) == 4
    setup = [s for s in cap.records if s["name"] == "scf.setup"]
    assert setup and setup[0]["mesh"] == mesh
    assert abs(four["energy"]["total"] - one["energy"]["total"]) <= 1e-9
