"""The fused step's sphere-to-box placement (PR 30): a gather through the
box's inverse map (core/fftgrid.box_inverse_map, g_to_r_gather) in place of
a scalar scatter, and the two fields that never change in a job transformed
once (dft/potential.constant_fields_device). Nothing of it may move one bit
of the result: every comparison here is np.array_equal."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sirius_tpu.core.fftgrid import box_inverse_map, g_to_r, g_to_r_gather
from sirius_tpu.dft import potential
from sirius_tpu.dft.xc import XCFunctional
from sirius_tpu.testing import synthetic_silicon_context

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def ctx():
    return synthetic_silicon_context(
        gk_cutoff=3.0, pw_cutoff=7.0, ngridk=(1, 1, 1), num_bands=8,
        ultrasoft=True, use_symmetry=False)


def _sphere(ctx, which):
    if which == "fine":
        return ctx.gvec.fft_index, tuple(ctx.gvec.fft.dims)
    return ctx.gvec_coarse.fft_index, tuple(ctx.fft_coarse.dims)


def _bits_equal(a, b):
    a, b = np.asarray(a), np.asarray(b)
    return (a.dtype == b.dtype and a.shape == b.shape
            and np.array_equal(np.real(a), np.real(b))
            and np.array_equal(np.imag(a), np.imag(b)))


@pytest.mark.parametrize("which", ["fine", "coarse"])
def test_inverse_map_of_a_sphere(ctx, which):
    idx, dims = _sphere(ctx, which)
    ng, nbox = len(idx), int(np.prod(dims))
    inv = box_inverse_map(idx, nbox)
    assert inv.dtype == np.int32 and inv.shape == (nbox,)
    # every coefficient appears once, at its own cell
    assert np.array_equal(inv[idx], np.arange(ng))
    assert np.array_equal(np.sort(inv[inv != ng]), np.arange(ng))
    # every other cell holds ng, one past the end
    outside = np.ones(nbox, bool)
    outside[idx] = False
    assert np.all(inv[outside] == ng) and outside.sum() == nbox - ng


def test_inverse_map_refuses_a_repeated_index(ctx):
    idx = np.array(ctx.gvec.fft_index)
    idx[5] = idx[3]
    with pytest.raises(ValueError, match="unique"):
        box_inverse_map(idx, ctx.gvec.fft.num_points)


@pytest.mark.parametrize("rows", [None, 3], ids=["single", "block3"])
@pytest.mark.parametrize("dtype", [np.complex64, np.complex128])
def test_gather_twin_equals_g_to_r_bit_for_bit(ctx, dtype, rows):
    idx, dims = _sphere(ctx, "fine")
    ng = len(idx)
    rng = np.random.default_rng(30)
    shape = (ng,) if rows is None else (rows, ng)
    f = (rng.standard_normal(shape) + 1j * rng.standard_normal(shape))
    f = jnp.asarray((f / (1.0 + ctx.gvec.glen2)).astype(dtype))
    inv = jnp.asarray(box_inverse_map(idx, int(np.prod(dims))))
    want = g_to_r(f, jnp.asarray(idx), dims)
    got = g_to_r_gather(f, inv, dims)
    assert got.dtype == want.dtype == np.dtype(dtype)
    assert _bits_equal(got, want)
    if rows is not None:  # a block's rows are the single fields' too
        for i in range(rows):
            assert _bits_equal(got[i], g_to_r_gather(f[i], inv, dims))


def _parent_formula(xc, ctx, fills):
    """generate_potential_device as the parent ran it: every placement a
    scatter-add through g_to_r, rho_core(r) and v_loc(r) transformed inside
    the program, in every call."""
    dims = tuple(ctx.gvec.fft.dims)
    dims_c = tuple(ctx.fft_coarse.dims)
    idx = {dims: jnp.asarray(ctx.gvec.fft_index),
           dims_c: jnp.asarray(ctx.gvec_coarse.fft_index)}

    def scatter(f_g, inv, d):
        fills.append(d)
        return g_to_r(f_g, idx[tuple(d)], tuple(d))

    def run(rho_g, mag_g, tb):
        cdt = rho_g.dtype
        const = {
            "rho_core_r": jnp.real(g_to_r(
                jax.lax.complex(tb["core_re"], tb["core_im"]).astype(cdt),
                idx[dims], dims)),
            "vloc_r": jnp.real(g_to_r(
                jax.lax.complex(tb["vloc_re"], tb["vloc_im"]).astype(cdt),
                idx[dims], dims)),
        }
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(potential, "g_to_r_gather", scatter)
            return potential.generate_potential_device(
                xc, rho_g, mag_g, {**tb, **const}, dims, dims_c,
                float(ctx.unit_cell.omega))

    return jax.jit(run)


@pytest.mark.parametrize("polarized", [False, True], ids=["unpol", "pol"])
@pytest.mark.parametrize("functional", ["lda", "gga"])
def test_potential_with_hoisted_tables_equals_parent_formula(
        ctx, functional, polarized):
    """Every returned field and every (hi, lo) energy pair of the step's
    potential, float32 as on the chip: the gather-placed, hoisted form
    against the parent's scatter-placed one, bit for bit; and the number of
    placements the program runs is the one FusedScf books."""
    xc = XCFunctional(
        ["XC_LDA_X", "XC_LDA_C_PZ"] if functional == "lda"
        else ["XC_GGA_X_PBE", "XC_GGA_C_PBE"])
    cdt, rdt = np.complex64, np.float32
    dims = tuple(ctx.gvec.fft.dims)
    dims_c = tuple(ctx.fft_coarse.dims)
    tb = {
        k: jnp.asarray(v, dtype=rdt if v.dtype.kind == "f" else None)
        for k, v in potential.build_potential_device_tables(ctx).items()
    }
    tb.update(potential.constant_fields_device(tb, dims))
    assert tb["rho_core_r"].dtype == tb["vloc_r"].dtype == rdt
    assert tb["rho_core_r"].shape == tb["vloc_r"].shape == dims

    rng = np.random.default_rng(31)
    ng = ctx.gvec.num_gvec
    # a Hermitian-like, decaying field around the starting density
    bump = (rng.standard_normal(ng) + 1j * rng.standard_normal(ng)) * 1e-3
    rho_g = jnp.asarray((ctx.rho_core_g * 0 + np.asarray(
        _start_density(ctx)) + bump / (1.0 + ctx.gvec.glen2)).astype(cdt))
    mag_g = jnp.asarray((0.2 * np.asarray(rho_g)).astype(cdt)) \
        if polarized else None

    @jax.jit
    def change(rho_g, mag_g, tb):
        return potential.generate_potential_device(
            xc, rho_g, mag_g, tb, dims, dims_c, float(ctx.unit_cell.omega))

    fills = []
    want = _parent_formula(xc, ctx, fills)(rho_g, mag_g, tb)
    got = change(rho_g, mag_g, tb)
    flat_w, tree_w = jax.tree_util.tree_flatten(want)
    flat_g, tree_g = jax.tree_util.tree_flatten(got)
    assert tree_w == tree_g
    for w, g in zip(flat_w, flat_g):
        assert np.all(np.isfinite(np.asarray(g).view(rdt)))
        assert _bits_equal(g, w)
    assert float(np.asarray(got["energies"]["vha"][0])) != 0.0
    # the parent's program ran two placements more: the two hoisted fields
    assert len(fills) == potential.num_box_fills(xc, polarized)
    assert fills.count(dims_c) == (2 if polarized else 1)
    if functional == "lda" and not polarized:
        assert len(fills) == 4


def _start_density(ctx):
    from sirius_tpu.dft.density import initial_density_g

    return initial_density_g(ctx)


@pytest.mark.parametrize("ngridk", [(1, 1, 1), (2, 2, 2)],
                         ids=["gamma", "kmesh222"])
def test_run_scf_books_four_box_fills_an_iteration(ngridk):
    """A fused run_scf (the low-cutoff Gamma deck on one device, the 2x2x2
    k-mesh rehearsal deck on the mesh) books four sphere-to-box placements
    a fused step (LDA, unpolarised), names the placement in the span, and
    uploads the new tables once, at FusedScf.__init__: the transfer budget
    of the step and of its span stay 0."""
    from sirius_tpu.dft.scf import run_scf
    from sirius_tpu.obs import spans

    gamma = ngridk == (1, 1, 1)
    c = synthetic_silicon_context(
        gk_cutoff=3.0, pw_cutoff=7.0, ngridk=ngridk, num_bands=8,
        ultrasoft=True, use_symmetry=False,
        extra_params={"num_dft_iter": 3})
    with spans.capture() as cap:
        r = run_scf(c.cfg, ctx=c,
                    devices=jax.devices()[1:2] if gamma else None)
    assert r["placement"]["path"] == ("gamma" if gamma else "batched+fused")
    iters = r["num_scf_iterations"]
    assert iters == 3
    assert r["counters"]["num_tail_box_fills"] == 4 * iters
    steps = [s for s in cap.records if s["name"] == "scf.fused_step"]
    assert len(steps) == iters
    assert all(s["box_fill"] == "gather" for s in steps)


def test_new_tables_upload_once_and_budgets_hold():
    """Static side of the same contract (sirius-lint's transfer rules):
    FusedScf.step and the scf::fused_step span cross the host boundary 0
    times, the loop once; and the tables are program inputs (the trace
    signature sees the inverse maps and the two real boxes)."""
    from sirius_tpu.analysis import transferrules
    from sirius_tpu.analysis.core import ProjectIndex, collect_files

    project = ProjectIndex(ROOT, collect_files(ROOT, ("sirius_tpu",)))
    rows = transferrules.budget_report(project)
    with open(os.path.join(ROOT, "TRANSFER_BUDGET.json")) as fh:
        assert len(rows) == len(json.load(fh)["regions"])
    for row in rows:
        assert not row["stale"] and row["count"] <= row["budget"], row
    by = {(row["function"], row["kind"]): row for row in rows}
    assert by[("FusedScf.step", "body")]["count"] == 0
    assert by[("_run_scf_inner", "with:scf::fused_step")]["count"] == 0
    assert by[("_run_scf_inner", "loop-if:fused is not None")]["count"] == 1
