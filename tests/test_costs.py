"""Analytic cost model (sirius_tpu/obs/costs.py): hand-counted FLOP
checks, the shared accelerator peak table + env overrides, graceful
degradation of the XLA cost_analysis cross-check, and the perf-gate
comparison logic (sirius_tpu/obs/perf.py)."""

import math

import pytest

from sirius_tpu.obs import costs
from sirius_tpu.obs import perf


# ---------------------------------------------------------------------------
# hand-counted FLOPs (must match EXACTLY — these are the published
# formulas, not approximations)


def test_fft_flops_hand_count():
    # 8x8x8 box: N = 512, 5 N log2 N = 5 * 512 * 9 = 23040
    assert costs.fft_flops((8, 8, 8)) == 23040.0
    # batch scales linearly
    assert costs.fft_flops((8, 8, 8), batch=3) == 3 * 23040.0
    # non-power-of-two box: exact 5 N log2 N
    n = 6 * 6 * 6
    assert costs.fft_flops((6, 6, 6)) == pytest.approx(5.0 * n * math.log2(n))


def test_beta_gemm_flops_hand_count():
    # [nb=4, ngk=100] x [ngk=100, nbeta=10] complex GEMM:
    # 8 flops per complex MAC -> 8 * 4 * 10 * 100 = 32000
    assert costs.beta_gemm_flops(4, 10, 100) == 32000.0


def test_hpsi_flops_hand_count():
    # one band, no projectors, 8^3 box, ngk=100:
    # 2 FFTs (2*23040) + pointwise (7*512) + kinetic (8*100)
    assert costs.hpsi_flops(1, 100, 0, (8, 8, 8)) == (
        2 * 23040.0 + 7.0 * 512 + 8.0 * 100)
    # projector term: 8 * (3 * nbeta * ngk + 2 * nbeta^2) per band
    with_beta = costs.hpsi_flops(1, 100, 5, (8, 8, 8))
    without = costs.hpsi_flops(1, 100, 0, (8, 8, 8))
    assert with_beta - without == 8.0 * (3 * 5 * 100 + 2 * 25)
    # bands scale linearly
    assert costs.hpsi_flops(6, 100, 5, (8, 8, 8)) == 6 * with_beta


def test_davidson_applies_matches_solver():
    from sirius_tpu.solvers.davidson import num_applies

    # what ran: steps and chunks; without chunks, a solve that takes every
    # step of its bound (a chunk every REFRESH_EVERY steps)
    assert costs.davidson_applies(10, 8) == num_applies(10, 2, 8)
    assert costs.davidson_applies(7, 4, chunks=3) == num_applies(7, 3, 4)
    assert costs.davidson_applies(7, 4) == num_applies(7, 2, 4)
    full = costs.davidson_cost(8, 200, 18, (12, 12, 12), 20)
    ran = costs.davidson_cost(8, 200, 18, (12, 12, 12), 6, chunks=2)
    assert 0 < ran.flops < full.flops and 0 < ran.bytes < full.bytes


# ---------------------------------------------------------------------------
# peak table + overrides


def test_peak_table_and_overrides(monkeypatch):
    monkeypatch.delenv("BENCH_PEAK_GFLOPS", raising=False)
    monkeypatch.delenv("SIRIUS_TPU_PEAK_GFLOPS", raising=False)
    monkeypatch.delenv("SIRIUS_TPU_PEAK_GBPS", raising=False)
    # keyed by device_kind; the v5e row is the published bf16 peak
    assert costs.peak_gflops("TPU v5 lite") == 197e3
    assert costs.peak_gbps("TPU v5 lite") == 819.0
    import os

    assert costs.peak_gflops("cpu") == 76.8 * (os.cpu_count() or 1)
    # an accelerator kind that is not in the table is an error
    with pytest.raises(KeyError, match="TPU v9"):
        costs.peak_gflops("TPU v9")
    with pytest.raises(KeyError, match="tpu"):
        costs.peak_gbps("tpu")
    # env override (unlisted hardware) wins over the table
    monkeypatch.setenv("BENCH_PEAK_GFLOPS", "1234.5")
    assert costs.peak_gflops("TPU v5 lite") == 1234.5
    monkeypatch.delenv("BENCH_PEAK_GFLOPS")
    monkeypatch.setenv("SIRIUS_TPU_PEAK_GFLOPS", "42.0")
    assert costs.peak_gflops("whatever") == 42.0
    # explicit (config) override wins over everything
    assert costs.peak_gflops("TPU v5 lite", override=7.0) == 7.0


def test_roofline_and_mfu():
    c = costs.StageCost(flops=1e9, bytes=1e9)  # intensity 1 flop/byte
    # bandwidth-bound: ceiling = intensity * bw, not the compute peak
    assert c.roofline_gflops(peak=100.0, bw_gbps=10.0) == 10.0
    # compute-bound when intensity is high
    c2 = costs.StageCost(flops=1e12, bytes=1e6)
    assert c2.roofline_gflops(peak=100.0, bw_gbps=10.0) == 100.0
    # byte-free models hit the compute roof
    assert costs.StageCost(flops=1.0).roofline_gflops(peak=50.0) == 50.0
    assert c.mfu(dur_s=1.0, peak=100.0) == pytest.approx(0.01)
    ann = costs.annotate_span(0.5, 1e9, 1e9, peak=100.0)
    assert ann["gflops"] == pytest.approx(2.0)
    assert ann["mfu"] == pytest.approx(0.02)


def test_scf_stage_costs_cover_span_names():
    sc = costs.scf_stage_costs(
        nk=2, ns=1, nb=8, ngk=200, nbeta=18, box=(12, 12, 12), ng=800,
        num_steps=10)
    for stage in ("scf.band_solve", "scf.d_matrix", "scf.occupations",
                  "scf.density", "scf.mixing", "scf.potential",
                  "scf.fused_step", "scf.readback", "scf.iteration"):
        assert stage in sc
    assert sc["scf.band_solve"].flops > 0
    # iteration aggregates the host per-stage work
    assert sc["scf.iteration"].flops == pytest.approx(sum(
        sc[s].flops for s in ("scf.band_solve", "scf.d_matrix",
                              "scf.occupations", "scf.density",
                              "scf.mixing", "scf.potential")))
    # band solve scales with nk * ns
    sc2 = costs.scf_stage_costs(
        nk=4, ns=1, nb=8, ngk=200, nbeta=18, box=(12, 12, 12), ng=800,
        num_steps=10)
    assert sc2["scf.band_solve"].flops == 2 * sc["scf.band_solve"].flops


# ---------------------------------------------------------------------------
# XLA cross-check: must degrade gracefully, never raise


def test_xla_cost_analysis_graceful_on_garbage():
    class NotJitted:
        def lower(self, *a, **k):
            raise RuntimeError("no lowering here")

    assert costs.xla_cost_analysis(NotJitted()) is None
    assert costs.xla_flops(NotJitted()) is None


def test_xla_cost_analysis_real_backend():
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp

    f = jax.jit(lambda a, b: a @ b)
    x = jnp.ones((32, 32), jnp.float32)
    ca = costs.xla_cost_analysis(f, x, x)
    if ca is None:
        pytest.skip("backend provides no cost_analysis")
    assert isinstance(ca, dict)
    fl = costs.xla_flops(f, x, x)
    if fl is not None:
        # 32^3 MACs: XLA counts 2 flops per MAC
        assert fl == pytest.approx(2 * 32**3, rel=0.5)


def test_xla_crosscheck_agrees_on_matmul():
    # the analytic GEMM count vs XLA's own, where available (skip if not)
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp

    nb, ngk, nbeta = 8, 128, 16
    f = jax.jit(lambda a, b: a @ b)
    a = jnp.ones((nb, ngk), jnp.complex64)
    b = jnp.ones((ngk, nbeta), jnp.complex64)
    fl = costs.xla_flops(f, a, b)
    if fl is None:
        pytest.skip("backend provides no flop counts")
    analytic = costs.beta_gemm_flops(nb, nbeta, ngk)
    # complex flop accounting differs across XLA versions (2, 6 or 8
    # per MAC); same order of magnitude is the contract
    assert analytic / 8 <= fl <= analytic * 2


# ---------------------------------------------------------------------------
# perf gate comparison logic


def _entry(stages, iter_median=0.1):
    return {"tiers": {"small": {
        "iteration_median_s": iter_median,
        "stages": stages,
    }}}


def test_compare_flags_regression_and_respects_tolerance():
    base = _entry({"scf.band_solve": {
        "median_s": 0.10, "tol_ratio": 1.5}})
    # within tolerance: 1.4x and above the abs floor -> no regression
    ok = _entry({"scf.band_solve": {"median_s": 0.14}})
    assert perf.compare(base, ok) == []
    # beyond tolerance -> regression
    bad = _entry({"scf.band_solve": {"median_s": 0.20}})
    regs = perf.compare(base, bad)
    assert len(regs) == 1 and regs[0]["kind"] == "slower"
    assert regs[0]["ratio"] == pytest.approx(2.0)
    # --min-ratio floors the tolerance (2.0x slower allowed at 2.5 floor)
    assert perf.compare(base, bad, min_ratio=2.5) == []


def test_compare_abs_floor_suppresses_microsecond_noise():
    base = _entry({"scf.mixing": {"median_s": 1e-4, "tol_ratio": 1.5}})
    # 3x ratio but only +0.2 ms absolute: below the jitter floor
    cur = _entry({"scf.mixing": {"median_s": 3e-4}})
    assert perf.compare(base, cur) == []


def test_compare_missing_stage_is_regression():
    base = _entry({"scf.density": {"median_s": 0.05, "tol_ratio": 1.5}})
    regs = perf.compare(base, _entry({}))
    assert len(regs) == 1 and regs[0]["kind"] == "missing"


def test_compare_normalized_shares():
    # absolute times doubled uniformly (slower machine): shares identical,
    # normalized gate stays green
    base = _entry({"scf.band_solve": {"median_s": 0.05, "tol_ratio": 1.5}},
                  iter_median=0.10)
    cur = _entry({"scf.band_solve": {"median_s": 0.10}}, iter_median=0.20)
    assert perf.compare(base, cur, normalize=True) == []
    # same machine speed but the stage doubled its share -> regression
    cur2 = _entry({"scf.band_solve": {"median_s": 0.10}}, iter_median=0.10)
    regs = perf.compare(base, cur2, normalize=True)
    assert len(regs) == 1 and regs[0]["unit"] == "share"


def test_baseline_file_round_trip(tmp_path):
    p = tmp_path / "PERF_BASELINE.json"
    import json

    doc = {"schema": perf.SCHEMA, "series": [_entry({})]}
    p.write_text(json.dumps(doc))
    assert perf.load_baseline(str(p))["series"]
    p.write_text(json.dumps({"schema": 999, "series": [1]}))
    with pytest.raises(SystemExit):
        perf.load_baseline(str(p))
