"""The fused step is a program of the process (dft/fused.step_program): a
ground state after the first finds the step its constants name and traces,
lowers and compiles nothing, whoever calls run_scf and with no exec_cache;
what changes the constants (mixer beta, functional, precision) traces once
more; nothing the table holds keeps a FusedScf or its tables alive; the
table is bounded."""

import dataclasses
import gc
import threading
import weakref

import jax
import numpy as np
import pytest

from sirius_tpu.dft import fused as fused_mod
from sirius_tpu.obs import events as obs_events
from sirius_tpu.obs import metrics as obs_metrics
from sirius_tpu.obs import spans
from sirius_tpu.testing import synthetic_silicon_context

IDEAL = np.array([[0.0, 0, 0], [0.25, 0.25, 0.25]])


def _positions(g):
    """Geometry g of the deck: the second atom displaced, the lattice and
    the cutoffs (so every table's shape) the same."""
    if g == 0:
        return IDEAL
    d = np.random.default_rng(1000 + g).uniform(-0.004, 0.004, 3)
    return IDEAL + np.array([np.zeros(3), d])


def _job(g=0, precision="fp64", beta=None, xc=None, polish=0.0, events=None):
    """One run_scf of the rehearsal deck (Gamma, one compute device: the
    packed-real solve and the fused tail), no exec_cache; returns (result,
    backend compiles on this thread, the scf.setup span)."""
    from sirius_tpu.dft.scf import run_scf

    extra = {"num_dft_iter": 40, "precision_wf": precision}
    if xc is not None:
        extra["xc_functionals"] = list(xc)
    ctx = synthetic_silicon_context(
        gk_cutoff=3.0, pw_cutoff=7.0, ngridk=(1, 1, 1), num_bands=8,
        ultrasoft=True, use_symmetry=False, positions=_positions(g),
        extra_params=extra)
    ctx.cfg.control.telemetry = True
    if beta is not None:
        ctx.cfg.mixer.beta = beta
    ctx.cfg.settings.fp32_to_fp64_rms = polish
    if events is not None:
        ctx.cfg.control.events_path = str(events)
    obs_metrics.install_jax_listeners()
    before = obs_metrics.backend_compiles_this_thread()
    with spans.capture() as cap:
        r = run_scf(ctx.cfg, ctx=ctx, devices=jax.devices()[1:2])
    compiles = obs_metrics.backend_compiles_this_thread() - before
    (setup,) = cap.by_name("scf.setup")
    assert r["converged"] and "fused_step" in r["placement"]
    return r, compiles, setup


@pytest.fixture
def fresh_table():
    """A process that has built no step yet, as far as the table goes."""
    with fused_mod._step_programs_lock:
        fused_mod._step_programs.clear()
    yield fused_mod._step_programs
    with fused_mod._step_programs_lock:
        fused_mod._step_programs.clear()


def _traces(r):
    return r["counters"]["num_fused_step_traces"]


@pytest.mark.parametrize("precision", ["fp32", "fp64"])
def test_second_ground_state_reuses_the_step(precision, fresh_table,
                                             tmp_path):
    """Two run_scf calls back to back on two displaced geometries: the
    second traces no step, compiles nothing, and its energy is to the last
    bit the energy the same geometry gives when it is the first job a
    process runs."""
    r0, c0, s0 = _job(0, precision, events=tmp_path / "e0.jsonl")
    assert _traces(r0) == 1 and s0["fused_step"] == "traced" and c0 > 0
    assert s0["fused"] is True
    r1, c1, s1 = _job(1, precision, events=tmp_path / "e1.jsonl")
    assert _traces(r1) == 0 and s1["fused_step"] == "reused"
    assert [obs_events.read_events(str(tmp_path / f), kind="scf_done")[0][
        "num_fused_step_traces"] for f in ("e0.jsonl", "e1.jsonl")] == [1, 0]
    assert c1 == 0, f"{c1} backend compiles in a job that found its step"
    assert len(fresh_table) == 1
    assert r1["energy"]["total"] != r0["energy"]["total"]
    # the same geometry first in a process: the table empty, a new trace
    fresh_table.clear()
    r1_first, _, s1_first = _job(1, precision)
    assert _traces(r1_first) == 1 and s1_first["fused_step"] == "traced"
    assert r1_first["energy"]["total"] == r1["energy"]["total"]
    assert r1_first["num_scf_iterations"] == r1["num_scf_iterations"]


@pytest.mark.parametrize("change", ["mixer_beta", "functional", "polish"])
def test_changed_constants_trace_once_more(change, fresh_table):
    """What enters the record traces a program of its own, once: another
    mixer beta, another functional, and the fp32 -> fp64 polish switch
    (the job keeps its 32-bit step and builds the 64-bit one)."""
    base, _, _ = _job(0, "fp32")
    assert _traces(base) == 1
    kw = {"mixer_beta": {"beta": 0.55},
          "functional": {"xc": ("XC_GGA_X_PBE", "XC_GGA_C_PBE")},
          "polish": {"polish": 1e-4}}[change]
    r, _, setup = _job(0, "fp32", **kw)
    assert _traces(r) == 1
    # the span says what the table answered the job's first record
    assert setup["fused_step"] == ("reused" if change == "polish"
                                   else "traced")
    assert len(fresh_table) == 2
    if change == "polish":
        assert r["placement"]["fused_step"][1] == "float64"
        assert ({str(rec.rdt) for rec in fresh_table}
                == {"float32", "float64"})
    again, compiles, _ = _job(1, "fp32", **kw)
    assert _traces(again) == 0 and compiles == 0


def test_a_finished_job_leaves_no_fusedscf_behind(fresh_table, monkeypatch):
    """The step holds its record, not the instance: once a job's result
    is dropped its FusedScf (and the tables on the device with it) is gone
    by reference counting alone, no cycle for the collector to find."""
    made = []
    sound_init = fused_mod.FusedScf.__init__

    def keep_init(self, *a, **kw):
        sound_init(self, *a, **kw)
        made.append(weakref.ref(self))

    monkeypatch.setattr(fused_mod.FusedScf, "__init__", keep_init)
    gc.collect()
    gc.disable()
    try:
        r, _, _ = _job(0, "fp32")
        del r
        assert len(made) == 1 and made[0]() is None
    finally:
        gc.enable()
    # what the table keeps: the record bound to _step_impl, nothing else
    (rec,) = fresh_table
    bound = fresh_table[rec].__wrapped__
    assert bound.func is fused_mod._step_impl and bound.args == (rec,)
    assert not bound.keywords
    for f in dataclasses.fields(rec):
        assert not isinstance(getattr(rec, f.name), (jax.Array, np.ndarray))
    assert hash(rec) == hash(dataclasses.replace(rec))


def _record(i=0):
    return fused_mod.StepConstants(
        cdt=np.dtype("complex64"), rdt=np.dtype("float32"), ns=1, ng=100,
        omega=270.0, nel=8.0, charge_tol=1e-5, dims=(12, 12, 12),
        dims_coarse=(8, 8, 8), kind="anderson", mix_beta=0.5 + 1e-3 * i,
        max_history=8, has_aug=True, do_symmetrize=False, polarized=False,
        xc=("XC_LDA_X", "XC_LDA_C_PZ"))


def test_table_evicts_past_its_bound(fresh_table):
    """Least recently used out, at the bound the engine's cache had."""
    cap = fused_mod.STEP_PROGRAMS_MAX
    assert cap == 32
    first, found = fused_mod.step_program(_record(0))
    assert not found
    for i in range(1, cap):
        fused_mod.step_program(_record(i))
    again, found = fused_mod.step_program(_record(0))  # now the newest
    assert found and again is first and len(fresh_table) == cap
    _, found = fused_mod.step_program(_record(cap))
    assert not found and len(fresh_table) == cap
    assert _record(1) not in fresh_table and _record(0) in fresh_table
    # an equal record made apart finds the same program
    twin, found = fused_mod.step_program(
        dataclasses.replace(_record(0), xc=tuple(["XC_LDA_X", "XC_LDA_C_PZ"])))
    assert found and twin is first


def test_table_under_threads(fresh_table):
    """Engine slices ask for steps at once: every caller gets a program
    bound to its own record and the table stays within its bound."""
    cap = fused_mod.STEP_PROGRAMS_MAX
    errors = []

    def worker(seed):
        for i in np.random.default_rng(seed).integers(0, cap + 8, 300):
            rec = _record(int(i))
            step, _ = fused_mod.step_program(rec)
            if step.__wrapped__.args != (rec,):
                errors.append("a program of another record")
            with fused_mod._step_programs_lock:
                if len(fresh_table) > cap:
                    errors.append("over the bound")

    threads = [threading.Thread(target=worker, args=(s,)) for s in range(16)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not any(t.is_alive() for t in threads)
    assert not errors and 0 < len(fresh_table) <= cap
