"""A second spin channel through the batched k-set solve and the fused step
(PR 45, configuration fm2-k444-us): the scheduler hands the deck's species
and starting moments to the cell, the synthetic d-shell species is what the
plain reference's closed forms say it is, the polarised X + PZ of dft/xc is
the plain code's hand-written LSDA point by point, and the configuration's
rehearsal deck ends in the plain code's ferromagnetic state in f64 and stays
under the guarantee's bar in 32-bit types, on path batched+fused with the
spans and counters that say so."""

import copy
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import plain_pwus_spin as plain
from benchmark.harness import decks
from sirius_tpu import testing
from sirius_tpu.config.schema import load_config
from sirius_tpu.core.radial import sbessel_integral
from sirius_tpu.dft import band_solve
from sirius_tpu.dft.scf import run_scf
from sirius_tpu.dft.xc import XCFunctional
from sirius_tpu.obs import metrics, spans
from sirius_tpu.serve.scheduler import build_job_context

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CDIR = os.path.join(ROOT, "benchmark", "configs", "fm2-k444-us")
with open(os.path.join(CDIR, "config.json")) as _f:
    CONFIG = json.load(_f)

TINY = {
    "gk_cutoff": 2.0, "pw_cutoff": 6.0, "use_symmetry": False,
    "xc_functionals": ["XC_LDA_X", "XC_LDA_C_PZ"], "ngridk": [1, 1, 1],
    "num_bands": 12, "num_mag_dims": 1,
}


def _cfg(parameters, synthetic, control=None):
    return load_config(copy.deepcopy({
        "parameters": parameters,
        "control": dict({"ngk_pad_quantum": 16, "verbosity": 0},
                        **(control or {})),
        "synthetic": synthetic}))


# -- the scheduler and the cell ----------------------------------------------

@pytest.fixture
def cell_only(monkeypatch):
    """build_job_context up to the cell it hands SimulationContext.create
    (the rehearsal runs below go all the way)."""
    monkeypatch.setattr(testing, "context_of_cell",
                        lambda cfg, uc, base_dir=".": uc)


@pytest.mark.parametrize("moments, want", [
    ([[0, 0, 2.0], [0, 0, -1.0]], [[0, 0, 2.0], [0, 0, -1.0]]),
    ([0, 0, 1.5], [[0, 0, 1.5], [0, 0, 1.5]]),
    (None, [[0, 0, 0], [0, 0, 0]]),
])
def test_scheduler_hands_species_and_moments_to_the_cell(cell_only, moments, want):
    syn = {"ultrasoft": True, "species": "dshell", "a": 10.4}
    if moments is not None:
        syn["moments"] = moments
    uc = build_job_context(_cfg(TINY, syn), ".")
    (t,) = uc.atom_types
    assert (t.label, t.symbol, t.zn) == ("Xd", "Xd", 8.0)  # no element's name
    assert [b.l for b in t.beta] == [0, 1, 2] and t.num_beta_lm == 9
    assert np.array_equal(np.diag(t.d_ion), [2.0, 3.0, -6.0])
    assert np.array_equal(uc.moments, np.asarray(want, float))
    assert uc.lattice[0, 1] == 5.2 and uc.num_atoms == 2


def test_default_species_is_the_silicon_with_no_moment(cell_only):
    uc = build_job_context(_cfg(TINY, {"ultrasoft": True}), ".")
    (t,) = uc.atom_types
    assert t.label == "Si" and [b.l for b in t.beta] == [0, 1]
    assert len(t.r) == 700 and not uc.moments.any()


@pytest.mark.parametrize("synthetic, word", [
    ({"species": "iron"}, "unknown synthetic species 'iron'"),
    ({"species": "dshell", "moments": [[0, 0, 1.0]]}, "moments of shape (1, 3)"),
    ({"species": "dshell", "moments": [[0, 0, 1.0]] * 3}, "moments of shape (3, 3)"),
    ({"moments": [1.0, 2.0]}, "moments of shape (2,)"),
])
def test_scheduler_refuses_at_set_up(synthetic, word):
    with pytest.raises(ValueError, match=word.replace("(", r"\(").replace(")", r"\)")):
        build_job_context(_cfg(TINY, dict({"ultrasoft": True}, **synthetic)), ".")


def test_a_supercell_takes_one_moment_or_one_an_atom():
    """synthetic_silicon_context refused supercell > 1 with explicit moments
    until PR 45; the shared helper tiles one vector and takes a full list."""
    uc = testing.synthetic_cell("si", supercell=2, moments=[0, 0, 0.5])
    assert uc.moments.shape == (16, 3) and np.all(uc.moments[:, 2] == 0.5)
    full = np.arange(48.0).reshape(16, 3)
    assert np.array_equal(
        testing.synthetic_cell("si", supercell=2, moments=full).moments, full)
    with pytest.raises(ValueError, match="one for each of the 16"):
        testing.synthetic_cell("si", supercell=2, moments=np.zeros((2, 3)))


# -- the species against the plain code's closed forms -----------------------

Q = np.linspace(0.0, 12.0, 25)


@pytest.mark.parametrize("l", [0, 1, 2])
def test_dshell_projector_transform_is_the_closed_form(l):
    t = testing.synthetic_dshell_type()
    (b,) = [b for b in t.beta if b.l == l]
    got = sbessel_integral(t.r, b.rbeta, l, Q, m=1)
    want = plain.beta_radial(Q)[l]
    assert np.abs(got - want).max() <= 2e-8 * np.abs(want).max()
    if l == 2:  # the channel's norm: int (r beta)^2 dr = 1
        assert sbessel_integral(t.r, b.rbeta ** 2, 0, [0.0], m=0)[0] == \
            pytest.approx(1.0, abs=1e-7)


def test_dshell_augmentation_and_charge_are_the_plain_codes():
    t = testing.synthetic_dshell_type()
    assert (plain.ZN, plain.WIDTH) == (t.zn, testing.DSHELL_WIDTH)
    assert np.array_equal(plain.D_ION[[0, 1, 4]], np.diag(t.d_ion))
    assert [(a.i, a.j, a.l) for a in t.augmentation] == [(0, 0, 0), (1, 1, 0)]
    for a in t.augmentation:  # nothing on the d channel
        got = sbessel_integral(t.r, a.qr, 0, Q, m=0)
        want = plain.aug_q(Q)[[0, 1][a.i]]
        assert np.abs(got - want).max() <= 2e-8 * np.abs(want).max()
    assert not plain.aug_q(Q)[4:].any()
    # real harmonics of the nine projectors: orthonormal on the sphere
    rng = np.random.default_rng(3)
    v = rng.normal(size=(200000, 3))
    y = plain.real_ylm(v)
    gram = 4 * np.pi * (y @ y.T) / v.shape[0]
    assert np.abs(gram - np.eye(9)).max() < 0.02


# -- the functional, point by point ------------------------------------------

def test_polarised_lda_is_the_plain_codes_by_hand():
    """e, v_up, v_dn of X + PZ in f64 against plain_pwus_spin.lsda: zeta from
    -1 to 1 (both ends: a dead channel), r_s on both sides of 1."""
    rs = np.concatenate([np.geomspace(0.2, 0.999, 40), [1.0],
                         np.geomspace(1.001, 30.0, 40)])
    zeta = np.concatenate([[-1.0, -0.999999], np.linspace(-0.99, 0.99, 37),
                           [0.999999, 1.0]])
    n = (3 / (4 * np.pi * rs ** 3))[:, None] * np.ones_like(zeta)[None, :]
    nu = (0.5 * n * (1 + zeta)[None, :]).ravel()
    nd = (0.5 * n * (1 - zeta)[None, :]).ravel()
    out = XCFunctional(["XC_LDA_X", "XC_LDA_C_PZ"]).evaluate_polarized(
        jnp.asarray(nu), jnp.asarray(nd))
    assert out["e"].dtype == jnp.float64
    e, vu, vd = plain.lsda(nu, nd)
    assert np.abs(np.asarray(out["e"]) - e).max() <= 1e-12 * np.abs(e).max()
    for got, want in ((out["v_up"], vu), (out["v_dn"], vd)):
        assert np.abs(np.asarray(got) - want).max() <= 1e-10 * np.abs(want).max()
    assert np.all(vd.reshape(len(rs), -1)[:, -1] == 0)  # zeta = 1: no n_dn
    assert np.all(np.asarray(out["v_up"]).reshape(len(rs), -1)[:, 0] == 0)


# -- the rehearsal deck: f64 against the plain code, f32 under the bar -------

def _rehearsal_deck(overrides=None):
    deck = decks.job_deck(CONFIG, 0, "rehearse")
    for section, over in (overrides or {}).items():
        deck.setdefault(section, {}).update(over)
    return deck


def _run(deck, devices=None):
    metrics.set_enabled(True)
    cfg = load_config(copy.deepcopy(deck))
    ctx = build_job_context(cfg, ".")
    with spans.capture() as cap:
        r = run_scf(cfg, ctx=ctx, devices=devices)
    r["_spans"] = list(cap.records)
    (r["_setup"],) = [s for s in cap.records if s["name"] == "scf.setup"]
    return r


@pytest.fixture(scope="module")
def rehearsal():
    """The rehearsal deck twice: as the reference's witness runs it (f64,
    tight tolerances) and as the cell runs it (32-bit, on compute device
    cpu:1 so that the placement record has a device to show)."""
    with open(os.path.join(CDIR, "refs_rehearse.json")) as f:
        ref = json.load(f)["geometries"]["0"]
    f64 = _run(_rehearsal_deck(CONFIG["reference"]["overrides"]))
    f32 = _run(_rehearsal_deck(), devices=jax.devices()[1:2])
    return ref, f64, f32


def test_rehearsal_f64_ends_in_the_plain_codes_state(rehearsal):
    ref, f64, _ = rehearsal
    assert f64["converged"] and f64["placement"]["path"] == "batched+fused"
    assert abs(f64["energy"]["total"] - ref["energy_total_ha"]) <= 1e-6
    moment = f64["magnetisation"]["total"][2]
    assert abs(moment - ref["moment_total_ub"]) <= 1e-4
    # a moment to compare, and a state a lost moment would miss by 100 bars
    assert ref["moment_total_ub"] >= 0.5
    assert ref["nonmagnetic_run"]["e_fm_minus_e_nm_ha"] <= -1e-3
    assert ref["kmesh_run"]["num_kpoints"] == 8  # no time reversal there


def test_rehearsal_f32_is_under_the_bar_on_the_fused_path(rehearsal):
    ref, _, f32 = rehearsal
    bar = CONFIG["guarantee"]["energy_tol_ha_per_atom"] * 2
    assert bar == 1e-5
    assert f32["converged"]
    assert abs(f32["energy"]["total"] - ref["energy_total_ha"]) <= bar
    # the weak moment of the low cutoffs is soft: 32-bit runs end 7e-4 uB
    # off (the full deck's, 5.6 uB, 1.5e-4 on the chip)
    assert abs(f32["magnetisation"]["total"][2] - ref["moment_total_ub"]) <= 5e-3
    pl = f32["placement"]
    assert pl["path"] == CONFIG["expected_path"] == "batched+fused"
    for stage in ("band_solve", "fused_step", "density", "mixing", "potential"):
        assert pl[stage][1] in ("float32", "complex64"), (stage, pl[stage])


def test_spans_and_counters_say_two_channels(rehearsal):
    _, _, f32 = rehearsal
    nb = CONFIG["rehearse"]["parameters"]["num_bands"]
    iters = f32["num_scf_iterations"]
    c = f32["counters"]
    assert c["num_spin_channels"] == 2
    assert c["num_kpoints_solved"] == 8
    assert c["num_tail_box_fills"] == 7 * iters
    assert c["num_density_rows"] == 8 * 2 * nb * iters
    setup = f32["_setup"]
    assert setup["spin"] == {"num_spins": 2, "num_mag_dims": 1,
                             "start_moment_ub": 4.0}
    steps = [s for s in f32["_spans"] if s["name"] == "scf.fused_step"]
    assert len(steps) == iters
    assert all(s["box_fills"] == 7 and s["xc"] == "lda"
               and s["polarized"] is True for s in steps)
    its = [s for s in f32["_spans"] if s["name"] == "scf.iteration"]
    assert [s["moment_ub"] for s in its] == f32["mag_history"]
    assert len(its) == iters and its[-1]["moment_ub"] == pytest.approx(
        f32["magnetisation"]["total"][2], abs=5e-3)
    dens = [s for s in f32["_spans"] if s["name"] == "scf.density"]
    assert all(s["rows"] == 8 * 2 * nb for s in dens)


def test_a_spin_channel_doubles_the_batch_and_folds_by_itself():
    """subspace_eigh.batch (and scf.density's rows, above) of the polarised
    deck are twice the same deck's with one channel, at equal nb;
    kset.local_rows is not: a potential per channel makes the local operator
    one call a channel (ops/local.box_round_trip), each carrying the
    k-points' nk x nb rows, so the field reads what one channel reads."""
    plans = {}
    for nmd in (0, 1):
        deck = _rehearsal_deck({"parameters": {"num_mag_dims": nmd}})
        cfg = load_config(deck)
        ctx = build_job_context(cfg, ".")
        band = band_solve.choose(
            ctx, cfg, jax.devices()[:1], serial_bands=False, hub=None,
            paw=None, mgga=False, wf_dtype=jnp.complex64)
        plans[nmd] = band.plan(jnp.complex64)["kset"]
    one, two = plans[0], plans[1]
    assert one["nk"] == two["nk"] == 8
    assert one["subspace_rows"] == two["subspace_rows"] == 48
    assert two["subspace_eigh"]["batch"] == 2 * one["subspace_eigh"]["batch"] == 16
    assert two["workspace_bytes"] == 2 * one["workspace_bytes"]
    assert two["local_rows"] == one["local_rows"] == [8 * 16, 2 * 8 * 16]


# -- the unpolarised deck is left as it was -----------------------------------

def test_unpolarised_step_is_reused_around_a_polarised_job(rehearsal):
    """The silicon deck before and after the polarised jobs of this module:
    the process's step program of its record is still there (`reused`, no
    new trace) and the energy is the same to the bit."""
    deck = {"parameters": {
        "gk_cutoff": 3.0, "pw_cutoff": 7.0, "use_symmetry": False,
        "xc_functionals": ["XC_LDA_X", "XC_LDA_C_PZ"], "smearing_width": 0.025,
        "num_dft_iter": 4, "precision_wf": "fp32", "density_tol": 1e-12,
        "energy_tol": 1e-12, "num_bands": 8, "ngridk": [2, 2, 2]},
        "control": {"ngk_pad_quantum": 16, "verbosity": 0},
        "synthetic": {"ultrasoft": True}}
    dev = jax.devices()[1:2]
    first = _run(deck, devices=dev)
    assert first["_setup"]["spin"]["num_spins"] == 1
    assert first["counters"]["num_spin_channels"] == 1
    assert "magnetisation" not in first
    steps = [s for s in first["_spans"] if s["name"] == "scf.fused_step"]
    assert all(s["box_fills"] == 4 and s["polarized"] is False for s in steps)
    assert all("moment_ub" not in s for s in first["_spans"]
               if s["name"] == "scf.iteration")
    polarised = _run(_rehearsal_deck({"parameters": {"num_dft_iter": 2}}),
                     devices=dev)
    assert polarised["_setup"]["fused_step"] == "reused"  # the fixture's
    again = _run(deck, devices=dev)
    assert again["_setup"]["fused_step"] == "reused"
    assert again["counters"]["num_fused_step_traces"] == 0
    assert again["energy"]["total"] == first["energy"]["total"]
    assert again["etot_history"] == first["etot_history"]


# -- the full deck (slow) -----------------------------------------------------

@pytest.mark.slow
def test_full_deck_f64_against_the_plain_code():
    """The deck itself on the 4x4x4 mesh in f64 against the stored plain
    reference: about four minutes on eight idle cores."""
    with open(os.path.join(CDIR, "refs.json")) as f:
        ref = json.load(f)["geometries"]["0"]
    r = _run(decks.reference_deck(CONFIG, 0, "deck"))
    assert r["converged"] and r["counters"]["num_kpoints_solved"] == 36
    assert abs(r["energy"]["total"] - ref["energy_total_ha"]) <= 1e-6
    assert abs(r["magnetisation"]["total"][2] - ref["moment_total_ub"]) <= 1e-4
