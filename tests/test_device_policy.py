"""Device dtype policy and placement rule (sirius_tpu/runtime.py), on the CPU:
the fused step follows the band solve's precision, 64-bit device work is
refused on a TPU, run_scf reports truthfully where each stage ran, and the
compile-cache helper never sets a directory when the environment does."""

import functools
import os
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sirius_tpu import runtime
from sirius_tpu.testing import synthetic_silicon_context


def _ctx(ngridk=(2, 2, 2), **extra):
    return synthetic_silicon_context(
        gk_cutoff=3.0, pw_cutoff=7.0, ngridk=ngridk, num_bands=8,
        ultrasoft=True, use_symmetry=False,
        extra_params={"num_dft_iter": 40, **extra},
    )


def _fused(ctx, wf_dtype):
    from sirius_tpu.dft.fused import FusedScf
    from sirius_tpu.dft.mixer import Mixer
    from sirius_tpu.dft.xc import XCFunctional

    mixer = Mixer(ctx.cfg.mixer, ctx.gvec.glen2, num_components=1,
                  omega=ctx.unit_cell.omega)
    xc = XCFunctional(ctx.cfg.parameters.xc_functionals)
    return FusedScf(ctx, xc, mixer, False, False, wf_dtype=wf_dtype)


def _step_jaxpr(fused, ctx):
    rdt = fused.rdt
    nk, nb, ngk = ctx.gkvec.num_kpoints, ctx.num_bands, ctx.gkvec.ngk_max
    nbeta = ctx.beta.num_beta_total
    pot0 = SimpleNamespace(veff_g=np.zeros(fused.ng, np.complex128), bz_g=None)
    carry = fused.init_carry(np.zeros(fused.nx, np.complex128), pot0)
    z = lambda *shape: jnp.zeros(shape, rdt)
    from sirius_tpu.dft.fused import _step_impl

    return jax.make_jaxpr(functools.partial(_step_impl, fused.constants))(
        fused.tables, carry, z(1, *fused.dims_coarse), z(1, nbeta, nbeta),
        z(1, nbeta, nbeta), z(nk, 1, nb), z(nk, 1, nb), z(),
        z(nk, 1, nb, ngk), z(nk, 1, nb, ngk))


def _avals(jaxpr):
    """Every aval of a jaxpr, sub-jaxprs (scan/cond/pjit bodies) included."""
    for v in [*jaxpr.invars, *jaxpr.outvars, *jaxpr.constvars]:
        yield v.aval
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            yield v.aval
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _avals(sub)


def test_fused_complex64_traces_without_64bit_avals():
    ctx = _ctx()
    with runtime.scf_scope():
        jaxpr = _step_jaxpr(_fused(ctx, jnp.complex64), ctx)
    # (a weak-typed scalar is a python literal on its way into a 32-bit
    # op, folded at compile time — tests/test_tpu_compile.py checks that no
    # 64-bit type is left in the chip's HLO)
    wide = {str(a) for a in _avals(jaxpr.jaxpr)
            if str(getattr(a, "dtype", "")) in ("float64", "complex128")
            and not (a.weak_type and a.shape == ())}
    assert not wide, f"64-bit avals in the complex64 fused step: {wide}"


def test_fused_complex128_is_the_f64_program_and_energies_hold():
    ctx = _ctx()
    with runtime.scf_scope():
        jaxpr = _step_jaxpr(_fused(ctx, jnp.complex128), ctx)
    narrow = {str(a.dtype) for a in _avals(jaxpr.jaxpr)
              if getattr(a, "dtype", None) is not None
              and str(a.dtype) in ("float32", "complex64")}
    assert not narrow, f"32-bit avals in the complex128 fused step: {narrow}"

    # fused (device-resident) against the host path on the same f64 deck:
    # the pre-existing agreement bar of tests/test_fused_scf.py
    from sirius_tpu.dft.scf import run_scf

    tight = {"density_tol": 5e-9, "energy_tol": 1e-10}
    res = {}
    for mode in ("auto", "off"):
        c = _ctx(**tight)
        c.cfg.control.device_scf = mode
        res[mode] = run_scf(c.cfg, ctx=c)
    assert res["auto"]["placement"]["path"] == "batched+fused"
    assert res["off"]["placement"]["path"] == "batched"
    assert res["auto"]["converged"] and res["off"]["converged"]
    assert abs(res["auto"]["energy"]["total"]
               - res["off"]["energy"]["total"]) < 1e-8


@pytest.mark.parametrize("case", ["precision_wf", "fp32_to_fp64_rms", "resume"])
def test_64bit_device_work_is_refused_on_a_tpu(case, tmp_path):
    """The platform is steered from here: run_scf reads it off the devices
    it is handed, and refuses before it touches them."""
    from sirius_tpu.dft.scf import run_scf

    fake_tpu = [SimpleNamespace(platform="tpu", device_kind="TPU v5 lite", id=0)]
    ctx = _ctx(precision_wf="fp64" if case == "precision_wf" else "fp32")
    kw = {}
    if case == "fp32_to_fp64_rms":
        ctx.cfg.settings.fp32_to_fp64_rms = 1e-4
    if case == "resume":
        # an autosave written after the fp32 -> fp64 polish switch fired
        c0 = _ctx((1, 1, 1), precision_wf="fp32", num_dft_iter=3)
        c0.cfg.settings.fp32_to_fp64_rms = 1.0
        c0.cfg.control.autosave_every = 1
        c0.cfg.control.autosave_path = str(tmp_path / "auto.h5")
        run_scf(c0.cfg, ctx=c0)
        ctx = _ctx((1, 1, 1), precision_wf="fp32")
        kw["resume"] = c0.cfg.control.autosave_path
    key = {"precision_wf": "precision_wf", "resume": "wf_fp64",
           "fp32_to_fp64_rms": "fp32_to_fp64_rms"}[case]
    with pytest.raises(ValueError, match=f"(?s){key}.*TPU v5 lite"):
        run_scf(ctx.cfg, ctx=ctx, devices=fake_tpu, **kw)
    # the same deck on a CPU device is not refused at set-up
    runtime.refuse_64bit_on(jax.devices()[:1], key)


def test_large_subspace_on_a_tpu_mesh_is_refused():
    """3 * num_bands > 256 inside a mesh program crashes the TPU compiler
    (runtime.TPU_MESH_EIGH_MAX); run_scf asks before it builds the mesh
    programs. Fake devices: only platform/kind are read."""
    fake = [SimpleNamespace(platform="tpu", device_kind="TPU v5 lite", id=i)
            for i in range(4)]
    runtime.refuse_large_subspace_on_tpu_mesh(fake, 85)
    with pytest.raises(ValueError, match="num_bands = 86.*258 > 256"):
        runtime.refuse_large_subspace_on_tpu_mesh(fake, 86)
    # the CPU backend compiles any size on a mesh (the suite does)
    runtime.refuse_large_subspace_on_tpu_mesh(jax.devices(), 500)


FUSED_STAGES = ("band_solve", "occupations", "density", "fused_step",
                "mixing", "potential")


def test_placement_record_is_truthful_on_cpu():
    """Compute device cpu:1, host device cpu:0: the band solve and the fused
    tail, of the Gamma path and of the batched path, must sit on cpu:1 in
    the working precision; with control.device_scf off the Gamma path's
    f64 tail is on the host."""
    from sirius_tpu.dft.scf import run_scf
    from sirius_tpu.obs import spans

    dev = jax.devices()[1:2]
    tol = {"density_tol": 1e-5, "energy_tol": 1e-5, "precision_wf": "fp32"}

    ctx = _ctx((1, 1, 1), **tol)
    with spans.capture() as cap:
        res = run_scf(ctx.cfg, ctx=ctx, devices=dev)
    pl = res["placement"]
    assert pl["path"] == "gamma" and pl["mesh"] is None
    for stage in FUSED_STAGES:
        assert pl[stage] == ["cpu", "float32", [1]], (stage, pl[stage])
    # the record is the engagement counter: every iteration's tail was the
    # fused step, none of it a host stage
    names = [r["name"] for r in cap.records]
    assert names.count("scf.fused_step") == res["num_scf_iterations"]
    assert names.count("scf.readback") == res["num_scf_iterations"]
    assert names.count("scf.d_matrix") == 1  # the first iteration's host D
    assert not {"scf.potential", "scf.mixing"} & set(names)

    ctx = _ctx((1, 1, 1), **tol)
    ctx.cfg.control.device_scf = False
    pl = run_scf(ctx.cfg, ctx=ctx, devices=dev)["placement"]
    assert pl["path"] == "gamma" and "fused_step" not in pl
    assert pl["band_solve"] == ["cpu", "float32", [1]]
    assert pl["density"][0] == "host" and pl["mixing"][0] == "host"
    assert pl["potential"][1] == "float64"
    assert pl["occupations"] == ["cpu", "float64", [0]]  # the host device

    ctx = _ctx((2, 2, 2), **tol)
    pl = run_scf(ctx.cfg, ctx=ctx, devices=dev)["placement"]
    assert pl["path"] == "batched+fused"
    for stage in FUSED_STAGES:
        assert pl[stage] == ["cpu", "float32", [1]], (stage, pl[stage])
    assert pl["psi_shard_devices"] == [1]

    # the default mesh of the suite: every shard on its own device
    ctx = _ctx((2, 2, 2), density_tol=1e-7, energy_tol=1e-8)
    pl = run_scf(ctx.cfg, ctx=ctx)["placement"]
    assert pl["path"] == "batched+fused" and pl["mesh"] == {"k": 8, "b": 1}
    assert pl["band_solve"][1] == "float64"
    assert pl["psi_shard_devices"] == list(range(8))


def test_compile_cache_helper(monkeypatch):
    saved = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}
    try:
        # set from outside: no directory is set in code
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
        jax.config.update("jax_compilation_cache_dir", None)
        info = runtime.enable_compile_cache()
        assert info["from_env"] is True
        assert jax.config.jax_compilation_cache_dir is None
        # not set: the fixed in-checkout path, never a temp/pid/time name
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        info = runtime.enable_compile_cache()
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert info == {"dir": os.path.join(repo, ".jax_cache"),
                        "from_env": False}
        assert jax.config.jax_compilation_cache_dir == info["dir"]
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0
        assert jax.config.jax_persistent_cache_min_entry_size_bytes == 0
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)


def test_select_platform_compares_with_the_running_backend():
    # the suite's backend is up and is the CPU: "cpu" is a no-op, "tpu" an
    # error (never a silent CPU run), anything else is not a choice
    runtime.select_platform(None)
    runtime.select_platform("cpu")
    with pytest.raises(RuntimeError, match="tpu.*running JAX backend.*cpu"):
        runtime.select_platform("tpu")
    with pytest.raises(ValueError):
        runtime.select_platform("gpu")
