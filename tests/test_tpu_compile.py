"""The main path's device programs, compiled by the TPU's own compiler for a
described (not attached) v5e:2x2 at the smoke deck's real widths
(chip_smoke.py: Si-2 ultrasoft, gk 6 / pw 20, 26 bands), in 32-bit types.

A compile that passes is not a chip run: nothing executes, no result or time
comes out of it. What it guards at no chip time is that the chip's compiler
accepts every program (complex eigh/cholesky, non-power-of-two FFT boxes,
the all_to_all pair and beta psum inside shard_map), that each fits the
16 GB of one chip, and that no 64-bit type is left in a band-solve program.

The topology is described inside a fixture of this file — never at import,
in a skipif or in parametrize (on-chip-measurement guide section 2): only
the xdist worker that runs this file may load the TPU library. The two
Gamma compiles are tests/test_tpu_compile_gamma.py, which imports this
file's fixtures and helpers: where ``--dist loadfile`` gives the two files
to two workers, both load the library, which the driver's command allows
(ALLOW_MULTIPLE_LIBTPU_LOAD=1); without it the second file's fixture skips.
"""

import contextlib
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

HBM_BYTES = 16e9
NUM_STEPS = 20
# the band solve as run_scf gives it to the chip by default: num_steps the
# bound of its two while loops, the reference's convergence rule
# (iterative_solver.converge_by_energy; solvers/davidson.py, THE TRIP COUNT)
RULE = {"by_energy": True}


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # whatever the TPU library raises where absent
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module", autouse=False)
def no_compile_cache():
    """A described-chip executable can be written to the persistent cache
    but not read back without a chip; keep these compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _deck(ngridk, supercell=1, num_bands=None, symmetry=False):
    return {
        "parameters": {
            "gk_cutoff": 6.0, "pw_cutoff": 20.0, "ngridk": list(ngridk),
            "num_bands": num_bands or 26 * supercell**3,
            "use_symmetry": symmetry,
            "precision_wf": "fp32",
            "xc_functionals": ["XC_LDA_X", "XC_LDA_C_PZ"],
        },
        "synthetic": {"ultrasoft": True, "supercell": supercell},
    }


def _ctx(ngridk, supercell=1, num_bands=None, symmetry=False):
    from sirius_tpu.config.schema import load_config
    from sirius_tpu.serve.scheduler import build_job_context

    return build_job_context(
        load_config(_deck(ngridk, supercell, num_bands, symmetry)), ".")


@pytest.fixture(scope="module")
def ctx_kmesh():
    return _ctx((2, 2, 2))


def _shapes(tree, sharding):
    """Shapes, not arrays: nothing can be put on a described device.
    `sharding` is one sharding for every leaf or a function leaf -> sharding."""
    pick = sharding if callable(sharding) else (lambda a: sharding)
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a), a.dtype, sharding=pick(a)),
        tree)


def runtime_scope():
    from sirius_tpu import runtime

    return runtime.scf_scope()


def _compile(lowered):
    with runtime_scope():
        return lowered().compile()


def _check(compiled, no_64bit=False):
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes + m.generated_code_size_in_bytes)
    assert total < HBM_BYTES, m
    txt = compiled.as_text()
    if no_64bit:
        assert "c128[" not in txt and "f64[" not in txt
    return txt


def _eigh_batches(txt):
    """Leading dimension of every EighTpu custom call's eigenvalue output:
    the matrices one call of the kernel carries on one chip."""
    import re

    return [int(m.group(1)) for m in re.finditer(
        r"= \(f32\[(\d+),[^\n]*custom_call_target=\"EighTpu\"", txt)]


def _one_step_body(txt, target="EighTpu"):
    """The solve's chunks and steps are one loop each around ONE step: its
    two eigenproblems and ortho's one are all the program holds (a copy a
    chunk made si54's executable 215 MB, solvers/davidson.py)."""
    assert txt.count(f'custom_call_target="{target}"') == 3


def _no_jacobi(txt):
    """A complex subspace program after PR 35: the real kernel behind the
    tridiagonal reduction (solvers/subspace_eigh.py), no Jacobi sweep loop
    and no conditional (subspace_eigh_share reads conditionals as the QDWH
    program's: the platform switch has to be gone from the compiled text)."""
    assert "EighTpu" in txt
    assert "EighJacobiSweeps" not in txt and "ApplyRotations" not in txt
    assert " conditional(" not in txt


@contextlib.contextmanager
def _library_eigh():
    """Programs traced inside have the parent's call, jnp.linalg.eigh, at
    the solver's three sites (solvers/davidson.py)."""
    import importlib

    dav = importlib.import_module("sirius_tpu.solvers.davidson")
    old, dav.eigh = dav.eigh, lambda a: jnp.linalg.eigh(a)
    jax.clear_caches()
    try:
        with runtime_scope():
            yield
    finally:
        dav.eigh = old
        jax.clear_caches()


def _lowered_text(lower):
    with runtime_scope():
        return _strip_loc(lower().as_text())


def _strip_loc(txt):
    import re

    return re.sub(r"loc\(.*?\)|#loc.*", "", txt)


def _kset_inputs(ctx, nb):
    from sirius_tpu.parallel.batched import make_hkset_params

    nk, ngk = ctx.gkvec.num_kpoints, ctx.gkvec.ngk_max
    ps = make_hkset_params(
        ctx, np.zeros(ctx.fft_coarse.dims), dtype=jnp.complex64)
    psi = np.zeros((nk, 1, nb, ngk), np.float32)
    return ps, psi


def _fused(ctx, symmetrize=False):
    from sirius_tpu.dft.fused import FusedScf
    from sirius_tpu.dft.mixer import Mixer
    from sirius_tpu.dft.xc import XCFunctional

    cfg = ctx.cfg
    mixer = Mixer(cfg.mixer, ctx.gvec.glen2, num_components=1,
                  omega=ctx.unit_cell.omega)
    return FusedScf(ctx, XCFunctional(cfg.parameters.xc_functionals), mixer,
                    False, symmetrize, wf_dtype=jnp.complex64)


def _fused_args(fused, ctx, nb, rep, psi_sh, ev_sh):
    """ShapeDtypeStructs of FusedScf.step's operands as run_scf feeds them."""
    from types import SimpleNamespace

    nk, ngk, ng = ctx.gkvec.num_kpoints, ctx.gkvec.ngk_max, fused.ng
    nbeta = ctx.beta.num_beta_total
    f32 = np.float32
    pot0 = SimpleNamespace(veff_g=np.zeros(ng, np.complex128), bz_g=None)
    carry = fused.init_carry(np.zeros(fused.nx, np.complex128), pot0)

    def sds(shape, sh):
        return jax.ShapeDtypeStruct(shape, f32, sharding=sh)

    return (
        _shapes(fused.tables, rep), _shapes(carry, rep),
        sds((1,) + fused.dims_coarse, rep),
        sds((1, nbeta, nbeta), rep), sds((1, nbeta, nbeta), rep),
        sds((nk, 1, nb), ev_sh), sds((nk, 1, nb), ev_sh), sds((), rep),
        sds((nk, 1, nb, ngk), psi_sh), sds((nk, 1, nb, ngk), psi_sh),
    )


def _collectives(txt, kind):
    """The lines of a compiled program that start a collective `kind`."""
    return [ln for ln in txt.splitlines()
            if f" {kind}(" in ln or f" {kind}-start(" in ln]


def _cube_density(txt):
    """density_kset goes sphere -> cube -> box by gathers and products
    (ops/local.rows_to_box): no FFT and no scatter is left in it."""
    ops = [ln for ln in txt.splitlines() if " fft(" in ln or " scatter(" in ln]
    assert not ops, ops[:3]


def test_kset_band_solve_one_chip(topo, no_compile_cache, ctx_kmesh):
    """The batched k-set solve (complex Hermitian eigh at 3*nb inside):
    reduced to real tridiagonal matrices, all the set's in each kernel call."""
    from sirius_tpu.parallel.batched import (
        davidson_kset, density_kset, initialize_subspace_kset,
    )

    ctx = ctx_kmesh
    one = SingleDeviceSharding(topo.devices[0])
    ps, psi = _kset_inputs(ctx, ctx.num_bands)
    ps, psi = _shapes(ps, one), _shapes(psi, one)
    tol = jax.ShapeDtypeStruct((), np.float32, sharding=one)
    txt = _check(_compile(lambda: davidson_kset.lower(
        ps, psi, psi, num_steps=NUM_STEPS, res_tol=tol, **RULE)), no_64bit=True)
    _no_jacobi(txt)
    _one_step_body(txt)
    assert set(_eigh_batches(txt)) == {ctx.gkvec.num_kpoints}
    _no_jacobi(_check(_compile(lambda: initialize_subspace_kset.lower(
        ps, psi, psi, nb=ctx.num_bands)), no_64bit=True))
    occ = jax.ShapeDtypeStruct(psi.shape[:3], np.float32, sharding=one)
    _cube_density(_check(_compile(
        lambda: density_kset.lower(ps, psi, psi, occ)), no_64bit=True))


def test_fused_step_one_chip(topo, no_compile_cache, ctx_kmesh):
    """The FusedScf step program built for complex64, donated carry."""
    ctx = ctx_kmesh
    one = SingleDeviceSharding(topo.devices[0])
    fused = _fused(ctx)
    args = _fused_args(fused, ctx, ctx.num_bands, one, one, one)
    _check(_compile(lambda: fused._step.lower(*args)), no_64bit=True)


def test_fused_step_with_symmetry_one_chip(topo, no_compile_cache):
    """The step of the stock deck (use_symmetry at its default): the wedge of
    the 2x2x2 mesh, the 48-operation symmetrisers at pw_cutoff 20 (36 325
    G-vectors in 972 stars). They gather and sum; nothing scatters."""
    ctx = _ctx((2, 2, 2), symmetry=True)
    assert ctx.symmetry.num_ops == 48 and ctx.gkvec.num_kpoints == 3
    one = SingleDeviceSharding(topo.devices[0])
    fused = _fused(ctx, symmetrize=True)
    sym = fused.tables["sym"]
    assert sym["members"].shape == (48, 972) and sym["members"].dtype == np.int32
    assert sym["m_re"].shape == (48, 48, 972) and sym["m_re"].dtype == np.float32
    args = _fused_args(fused, ctx, ctx.num_bands, one, one, one)
    txt = _check(_compile(lambda: fused._step.lower(*args)), no_64bit=True)
    named = [ln for ln in txt.splitlines() if "/sym_pw/" in ln]
    assert named and not any(" scatter(" in ln for ln in named)


def test_fft_pair_c64_one_chip(topo, no_compile_cache, ctx_kmesh):
    """r_to_g / g_to_r and the fused step's gather twin on the fine box (not
    a power of two) in complex64."""
    from sirius_tpu.core.fftgrid import g_to_r, g_to_r_gather, r_to_g

    ctx = ctx_kmesh
    one = SingleDeviceSharding(topo.devices[0])
    dims = tuple(ctx.gvec.fft.dims)
    idx = _shapes(np.asarray(ctx.gvec.fft_index), one)
    box = jax.ShapeDtypeStruct(dims, np.complex64, sharding=one)
    sph = jax.ShapeDtypeStruct((ctx.gvec.num_gvec,), np.complex64, sharding=one)
    _check(_compile(lambda: r_to_g.lower(box, idx, dims)), no_64bit=True)
    _check(_compile(lambda: g_to_r.lower(sph, idx, dims)), no_64bit=True)
    inv = jax.ShapeDtypeStruct((int(np.prod(dims)),), np.int32, sharding=one)
    txt = _check(_compile(lambda: g_to_r_gather.lower(sph, inv, dims)),
                 no_64bit=True)
    assert "scatter" not in txt


def test_kb_mesh_step_four_chips(topo, no_compile_cache, ctx_kmesh):
    """Batched solve + fused step on the (k, b) production mesh, sharded as
    run_scf shards them; the k-reduction of the density is a collective."""
    from sirius_tpu.parallel.batched import davidson_kset, density_kset
    from sirius_tpu.parallel.mesh import KSET_PARAM_SPECS, production_mesh

    ctx = ctx_kmesh
    nb = ctx.num_bands
    mesh, psi_spec = production_mesh(
        ctx.gkvec.num_kpoints, nb, devices=topo.devices)
    assert mesh is not None and mesh.devices.size == 4
    rep = NamedSharding(mesh, P())
    psi_sh = NamedSharding(mesh, psi_spec)
    ev_sh = NamedSharding(mesh, P(*psi_spec[:3]))
    ps, psi = _kset_inputs(ctx, nb)
    ps = ps._replace(**{
        name: _shapes(leaf, NamedSharding(mesh, KSET_PARAM_SPECS[name]))
        for name, leaf in ps._asdict().items() if leaf is not None})
    psi = _shapes(psi, psi_sh)
    tol = jax.ShapeDtypeStruct((), np.float32, sharding=rep)
    # the benchmark's four-chip cell: the 36 k-points of the 4x4x4 mesh, the
    # complex subspace. Inside the shard_map over "k" (batched.over_k_pool)
    # each chip's kernel calls carry its own 9 matrices and the program holds
    # no collective; left to the partitioner they carry all 36 behind
    # all-gathers (compiled once for PR 35, not kept as a test's compile)
    ctx444 = _ctx((4, 4, 4))
    assert production_mesh(ctx444.gkvec.num_kpoints, nb,
                           devices=topo.devices)[0].shape == mesh.shape
    ps4, psi4 = _kset_inputs(ctx444, nb)
    ps4 = ps4._replace(**{
        name: _shapes(leaf, NamedSharding(mesh, KSET_PARAM_SPECS[name]))
        for name, leaf in ps4._asdict().items() if leaf is not None})
    psi4 = _shapes(psi4, psi_sh)
    txt = _check(_compile(lambda: davidson_kset.lower(
        ps4, psi4, psi4, num_steps=NUM_STEPS, res_tol=tol, **RULE, mesh=mesh)),
        no_64bit=True)
    _no_jacobi(txt)
    _one_step_body(txt)
    assert set(_eigh_batches(txt)) == {ctx444.gkvec.num_kpoints // 4} == {9}
    for kind in ("all-gather", "all-reduce", "all-to-all",
                 "collective-permute", "reduce-scatter"):
        assert not _collectives(txt, kind), kind
    # ... and the 2x2x2 mesh, every k-point its own -k: the same program
    # at two k-points a chip
    txt = _check(_compile(lambda: davidson_kset.lower(
        ps, psi, psi, num_steps=NUM_STEPS, res_tol=tol, **RULE, mesh=mesh)),
        no_64bit=True)
    _no_jacobi(txt)
    assert set(_eigh_batches(txt)) == {ctx.gkvec.num_kpoints // 4}
    occ = jax.ShapeDtypeStruct(psi.shape[:3], np.float32, sharding=ev_sh)
    txt = _check(_compile(lambda: density_kset.lower(
        ps, psi, psi, occ, mesh=mesh)), no_64bit=True)
    _cube_density(txt)
    # each chip's k-points go through the cube by themselves (over_k_pool);
    # the sum over the k-sharded axis is the program's one collective
    assert len(_collectives(txt, "all-reduce")) == 1
    for kind in ("all-gather", "all-to-all", "collective-permute",
                 "reduce-scatter"):
        assert not _collectives(txt, kind), kind
    fused = _fused(ctx)
    args = _fused_args(fused, ctx, nb, rep, psi_sh, ev_sh)
    _check(_compile(lambda: fused._step.lower(*args)), no_64bit=True)


def test_gshard_apply_four_chips(topo, no_compile_cache):
    """The G-sharded H/S application (slab FFT over the "g" mesh) for the
    Gamma supercell of chip_smoke.py --chips 4: the all_to_all pair and the
    beta psum inside shard_map must survive the chip's compiler."""
    from sirius_tpu.parallel.dist_fft import _gshard_inner, gshard_partition

    ctx = _ctx((1, 1, 1), supercell=2)
    dims = tuple(ctx.fft_coarse.dims)
    assert dims[0] % 4 == 0 and dims[1] % 4 == 0, dims
    mesh = Mesh(np.array(topo.devices).reshape(4), ("g",))
    _, lidx, _ = gshard_partition(np.asarray(ctx.gkvec.millers[0]), dims, 4)
    ngk = lidx.size
    nb, nbeta = ctx.num_bands, ctx.beta.num_beta_total

    def sds(shape, dtype, spec):
        return jax.ShapeDtypeStruct(
            shape, dtype, sharding=NamedSharding(mesh, spec))

    f32, c64 = np.float32, np.complex64
    inner = _gshard_inner(mesh, dims[0] // 4, dims[1], dims[2])
    txt = _check(_compile(lambda: inner.lower(
        sds((nb, ngk), c64, P(None, "g")), sds((ngk,), f32, P("g")),
        sds((ngk,), f32, P("g")), sds((nbeta, ngk), c64, P(None, "g")),
        sds((ngk,), lidx.dtype, P("g")), sds((nbeta, nbeta), f32, P()),
        sds((nbeta, nbeta), f32, P()), sds(dims, f32, P(None, "g", None)),
    )), no_64bit=True)
    assert "all-to-all" in txt and "all-reduce" in txt
