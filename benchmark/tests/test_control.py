"""The two proofs that the check can fail.

1. The rest of a run with the timed path broken underneath: the look for a
   chip is skipped (run_cell is driven directly, on the CPU backend at the
   rehearsal size), run_scf's answer is altered where it is produced, and
   ``correct`` comes out false; with the sound path it comes out true.
2. The lower-precision control: run_scf's f32 matmuls forced from `highest`
   to one bf16 pass. The CPU backend computes f32 matmuls in f32 whatever the
   setting, so here the test shows that the lower precision reaches run_scf's
   scope; that the verdict is then false is shown on the chip (PERF.md) and
   asserted here only where a TPU is attached.
"""

import os

import jax
import pytest

from benchmark import run as bench_run
from benchmark.harness import loader
from conftest import ROOT


def drive(tmp_path, cell_name="si2-k444.scf", seconds=1.0):
    cell = loader.load_cell(ROOT, cell_name)
    lines = []
    devices = jax.devices()[:1]
    out = bench_run.run_cell(
        cell, devices, devices[0].platform, seed=2147483801, seconds=seconds,
        trace=False, block="rehearse", say=lambda **kw: lines.append(kw),
        workdir=str(tmp_path / "work"))
    return out, lines


def test_sound_path_is_correct_and_altered_answer_is_not(tmp_path, monkeypatch):
    out, lines = drive(tmp_path)
    assert out["correct"] is True and out["failed"] == 0
    jobs = [kw for kw in lines if kw.get("event") == "job"]
    assert jobs and all(j["abs_de_ha"] <= j["de_limit_ha"] for j in jobs)

    from sirius_tpu.dft import scf

    sound = scf.run_scf

    def altered(*a, **kw):  # the answer altered where it is produced
        result = sound(*a, **kw)
        result["energy"]["total"] += 1e-4
        return result

    monkeypatch.setattr(scf, "run_scf", altered)
    out, lines = drive(tmp_path)
    assert out["correct"] is False and out["failed"] == out["attempted"] >= 1
    assert all("over" in kw["why"] for kw in lines if kw.get("event") == "job")


def test_a_run_that_does_not_converge_is_not_correct(tmp_path, monkeypatch):
    from sirius_tpu.dft import scf

    sound = scf.run_scf

    def cut_short(cfg, *a, **kw):  # the SCF loop stopped before convergence
        cfg.parameters.num_dft_iter = 2
        return sound(cfg, *a, **kw)

    monkeypatch.setattr(scf, "run_scf", cut_short)
    out, _ = drive(tmp_path)
    assert out["correct"] is False


def test_lower_precision_control_reaches_run_scf(tmp_path, monkeypatch):
    from sirius_tpu import runtime
    from sirius_tpu.dft import scf

    monkeypatch.setattr(runtime, "scf_scope", runtime.scf_scope)  # restored
    bench_run.force_matmul_precision("default")
    seen = []
    sound = scf.run_scf

    def watched(*a, **kw):  # what run_scf's own scope sets, seen from inside
        with runtime.scf_scope():
            seen.append(jax.config.jax_default_matmul_precision)
        return sound(*a, **kw)

    monkeypatch.setattr(scf, "run_scf", watched)
    out, _ = drive(tmp_path)
    assert seen and set(seen) == {"default"}
    if jax.devices()[0].platform == "tpu":
        assert out["correct"] is False
