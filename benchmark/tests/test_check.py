"""What fails a job: no stored reference, an energy over the bar, no
convergence, a path or a width other than the configuration's."""

import copy

from benchmark.harness import check

PLACEMENT = {"path": "batched+fused",
             "band_solve": ["tpu", "complex64", [0]],
             "fused_step": ["tpu", "float32", [0]],
             "density": ["tpu", "float32", [0]],
             "mixing": ["tpu", "float32", [0]],
             "potential": ["tpu", "float32", [0]]}
REFS = {"0": {"energy_total_ha": -8.5}}


def job(energy=-8.500001, geometry=0, converged=True, placement=PLACEMENT):
    return {"geometry": geometry, "result": {
        "converged": converged, "num_scf_iterations": 6,
        "energy": {"total": energy}, "placement": copy.deepcopy(placement)}}


def judge(rec, platform="tpu", path="batched+fused"):
    return check.judge(rec, REFS, 2, 5e-6, platform, path)


def test_sound_job_passes_with_its_numbers_beside_the_limit():
    rec = judge(job())
    assert rec["ok"] and rec["de_limit_ha"] == 1e-5
    assert abs(rec["abs_de_ha"] - 1e-6) < 1e-12


def test_missing_reference_is_a_failure_not_a_pass():
    rec = judge(job(geometry=5))
    assert not rec["ok"] and "no stored reference" in rec["why"]


def test_energy_over_the_bar_nan_and_no_convergence_fail():
    assert not judge(job(energy=-8.50002))["ok"]
    assert not judge(job(energy=float("nan")))["ok"]
    assert not judge(job(converged=False))["ok"]


def test_placement_off_the_expected_path_fails():
    assert "path" in judge(job(), path="gamma")["why"]
    wide = dict(PLACEMENT, band_solve=["tpu", "complex128", [0]])
    assert "band_solve" in judge(job(placement=wide))["why"]
    host = dict(PLACEMENT, fused_step=["cpu", "float32", [0]])
    assert "fused_step" in judge(job(placement=host))["why"]
    assert not judge({"geometry": 0, "error": "RuntimeError: x"})["ok"]


def test_a_four_chip_cell_wants_the_band_solve_on_four_devices():
    rec = check.judge(job(), REFS, 2, 5e-6, "tpu", "batched+fused", chips=4)
    assert "1 device(s)" in rec["why"]
    wide = dict(PLACEMENT, band_solve=["tpu", "complex64", [0, 1, 2, 3]])
    assert check.judge(job(placement=wide), REFS, 2, 5e-6, "tpu",
                       "batched+fused", chips=4)["ok"]
