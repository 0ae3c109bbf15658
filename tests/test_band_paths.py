"""The band-solve decision (dft/band_solve.choose) on its own: which of the
five solvers a deck gets from what the code can observe, the refusals word
for word, the OOM ladder's swap, and that run_scf reports the chosen
solver's word. Before PR 29 the decision could only be observed through a
whole SCF."""

from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sirius_tpu.dft import band_solve
from sirius_tpu.testing import synthetic_silicon_context


def _ctx(ngridk=(1, 1, 1), **extra):
    return synthetic_silicon_context(
        gk_cutoff=3.0, pw_cutoff=7.0, ngridk=ngridk, num_bands=8,
        ultrasoft=True, use_symmetry=False,
        extra_params={"num_dft_iter": 40, **extra},
    )


@pytest.fixture(scope="module")
def gamma_ctx():
    return _ctx()


def _choose(ctx, ndev=1, control=None, **flags):
    """choose() on `ndev` of the suite's virtual devices with `control`
    keys set on the deck for the call only."""
    saved = {k: getattr(ctx.cfg.control, k) for k in control or {}}
    for k, v in (control or {}).items():
        setattr(ctx.cfg.control, k, v)
    kw = dict(serial_bands=False, hub=None, paw=None, mgga=False,
              wf_dtype=jnp.complex128)
    kw.update(flags)
    try:
        return band_solve.choose(ctx, ctx.cfg, jax.devices()[:ndev], **kw)
    finally:
        for k, v in saved.items():
            setattr(ctx.cfg.control, k, v)


def _hub_stub(ctx):
    # KsetSolver stacks the Hubbard orbitals of every k-point at set-up
    ngk = ctx.gkvec.ngk_max
    return SimpleNamespace(
        phi_s_gk=[np.zeros((1, ngk), np.complex128)] * ctx.gkvec.num_kpoints)


# (case, devices, control keys, choose() flags, solver word, feeds_fused)
TABLE = [
    ("gamma_one_device", 1, {}, {}, "gamma", True),
    ("gamma_four_devices", 4, {}, {}, "batched", True),
    ("serial_bands", 1, {}, {"serial_bands": True}, "serial", False),
    ("reduce_gvec_off", 1, {"reduce_gvec": False}, {}, "batched", True),
    ("hubbard_at_gamma", 1, {}, {"hub": "stub"}, "batched", True),
    ("mgga_at_gamma", 1, {}, {"mgga": True}, "batched", True),
    ("chunked_forced", 1, {"beta_chunked": "force"}, {}, "beta_chunked",
     False),
    ("chunked_budget_tripped", 1,
     {"beta_chunked": "auto", "beta_chunk_budget_bytes": 1.0}, {},
     "beta_chunked", False),
    ("chunked_not_with_paw", 1, {"beta_chunked": "force"}, {"paw": object()},
     "gamma", True),
    ("gshard_forced", 2, {"gshard": "force", "collective_probe": False}, {},
     "gshard", False),
    ("gshard_budget_tripped", 2,
     {"gshard": "auto", "gshard_budget_bytes": 1.0,
      "collective_probe": False}, {}, "gshard", False),
    ("gshard_off_on_two_devices", 2, {"gshard": False}, {}, "batched", True),
]


@pytest.mark.parametrize("case,ndev,control,flags,word,feeds", TABLE,
                         ids=[row[0] for row in TABLE])
def test_choose_table(gamma_ctx, case, ndev, control, flags, word, feeds):
    ctx = gamma_ctx
    if ndev > 1 and "gshard" in word:
        dims = ctx.fft_coarse.dims
        assert dims[0] % ndev == 0 and dims[1] % ndev == 0, dims
    if flags.get("hub") == "stub":
        flags = dict(flags, hub=_hub_stub(ctx))
    band = _choose(ctx, ndev, control, **flags)
    assert (band.name, band.feeds_fused) == (word, feeds)
    assert band.gshard_devices == (ndev if word == "gshard" else 0)
    if word == "gshard":
        assert dict(band.mesh.shape) == {"g": ndev}
    # the chunked regime is recorded whatever was chosen (degrade reads it)
    assert band.chunk_foot == (
        ctx.beta.num_beta_total * ctx.gkvec.ngk_max * 16)


def test_choose_kmesh_is_batched_on_the_production_mesh():
    ctx = _ctx((4, 4, 4))
    band = _choose(ctx, 4)
    assert (band.name, band.feeds_fused) == ("batched", True)
    assert dict(band.mesh.shape) == {"k": 4, "b": 1}
    assert not band.chunk_ok  # nk > 1: outside the chunked regime
    one = _choose(ctx, 1)
    assert one.name == "batched" and one.mesh is None


def test_gshard_refusals_word_for_word(gamma_ctx):
    ctx = gamma_ctx
    dims = ctx.fft_coarse.dims
    ndev = next(n for n in (3, 5, 7) if dims[0] % n or dims[1] % n)
    with pytest.raises(ValueError) as e:
        _choose(ctx, ndev, {"gshard": "force"})
    assert str(e.value) == (
        f"control.gshard is forced but the coarse box {dims} is not "
        f"divisible by {ndev} devices along x and y, so the G-sharded band "
        "solve cannot engage")
    with pytest.raises(NotImplementedError) as e:
        _choose(ctx, 2, {"gshard": "force"}, mgga=True)
    assert str(e.value) == (
        "mGGA with the G-sharded band solve is not supported; set "
        "control.gshard = false")


def test_degrade_swaps_gamma_for_the_chunked_projectors(gamma_ctx):
    ctx = gamma_ctx
    band = _choose(ctx)
    assert band.name == "gamma" and band.chunk_ok
    assert band_solve.oom_state(band, ctx.cfg) == {
        "beta_chunked": False, "beta_chunk_eligible": True,
        "beta_chunk_can_halve": int(ctx.cfg.control.beta_chunk_size) > 16}
    stay = SimpleNamespace(shrink_beta_budget=False, force_beta_chunked=False)
    assert band_solve.degrade(band, stay, ctx.cfg) is band
    force = SimpleNamespace(shrink_beta_budget=False, force_beta_chunked=True)
    new = band_solve.degrade(band, force, ctx.cfg)
    assert (new.name, new.feeds_fused) == ("beta_chunked", False)
    assert band_solve.oom_state(new, ctx.cfg)["beta_chunked"]
    # outside the regime the ladder's rung changes nothing
    serial = _choose(ctx, serial_bands=True)
    assert band_solve.degrade(serial, force, ctx.cfg) is serial


@pytest.mark.parametrize("ngridk,device_scf", [
    ((1, 1, 1), True), ((1, 1, 1), False), ((2, 2, 2), True),
    ((2, 2, 2), False)], ids=["gamma+fused", "gamma", "kset+fused", "kset"])
def test_result_path_is_the_chosen_solvers_word(ngridk, device_scf):
    from sirius_tpu.dft.scf import run_scf

    ctx = _ctx(ngridk, density_tol=1e-5, energy_tol=1e-5)
    ctx.cfg.control.device_scf = device_scf
    dev = jax.devices()[1:2]
    band = band_solve.choose(
        ctx, ctx.cfg, dev, serial_bands=False, hub=None, paw=None,
        mgga=False, wf_dtype=jnp.complex128)
    res = run_scf(ctx.cfg, ctx=ctx, devices=dev)
    assert res["converged"]
    want = band.name_fused if device_scf else band.name
    assert res["placement"]["path"] == want
    assert want == {(1, True): "gamma", (1, False): "gamma",
                    (2, True): "batched+fused", (2, False): "batched"}[
        ngridk[0], device_scf]
