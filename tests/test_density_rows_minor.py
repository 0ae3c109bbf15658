"""The k-set density on the cube (parallel/batched.density_kset, PR 42):
every band row goes sphere -> cube -> box by the local operator's inverse
DFT passes with k x (spin, band) rows on the minor axis, and the weighted
squares are summed over the rows. Held here against the form it replaced,
written out below as the plain reference (a scatter-add into a zeroed box
a row, jnp.fft.ifftn, the weighted sum), at the rehearsal's size: in f64
to 1e-12 and in f32 to rounding, one spin channel and two, on the
all-invariant [2, 2, 2] mesh and the generic [2, 2, 3] one with its unequal
weights, with garbage in the padded slots (the cube's table never reads
them); the electron count; and what a job books of it
(counters.num_density_rows, the scf.density span's fields), with the
explicit 0 of a job at Gamma."""

import copy

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from sirius_tpu.dft.scf import run_scf
from sirius_tpu.obs import spans
from sirius_tpu.parallel.batched import (
    density_kset, make_hkset_params, split_cplx,
)
from sirius_tpu.parallel.mesh import (
    make_mesh, place_kset_params, shard_kset,
)
from sirius_tpu.testing import synthetic_silicon_context
from tests.test_generic_kset import context, rehearsal

NB = 8


def plain_density(fft_index, psi, occ_w, dims):
    """sum_{k,b} occ_w |psi(r)|^2 a spin channel: each row scattered into a
    zeroed box and transformed whole, k-point by k-point."""
    n = int(np.prod(dims))
    rho = 0.0
    for idx_k, psi_k, ow in zip(fft_index, psi, occ_w):
        box = jnp.zeros(psi_k.shape[:-1] + (n,), psi_k.dtype)
        box = box.at[..., idx_k].add(psi_k)
        fr = jnp.fft.ifftn(box.reshape(psi_k.shape[:-1] + tuple(dims)),
                           axes=(-3, -2, -1)) * n
        rho = rho + jnp.einsum("sb,sbxyz->sxyz", ow, jnp.abs(fr) ** 2)
    return rho


@pytest.fixture(scope="module", params=[(2, 2, 2), (2, 2, 3)],
                ids=["k222", "k223"])
def kset(request):
    ctx = synthetic_silicon_context(
        gk_cutoff=3.0, pw_cutoff=7.0, ngridk=request.param, num_bands=NB,
        use_symmetry=False)
    w = np.asarray(ctx.kweights)
    # [2, 2, 3] folds its (k, -k) pairs: 8 points, weights 1/12 and 2/12
    assert (len(set(np.rint(w * 12).astype(int))) == 2) == (
        request.param == (2, 2, 3))
    return ctx


def block(ctx, ns, dtype, garbage=False, seed=11):
    """(params, pr, pi, occ_w, psi) of a random masked block [nk, ns, NB,
    ngk]; ``garbage``: the padded slots of what the program is handed hold
    1e3-sized numbers instead of zeros (psi, the reference's, keeps zeros)."""
    rng = np.random.default_rng(seed)
    nk, ngk = ctx.gkvec.mask.shape
    rdt = np.zeros((), dtype).real.dtype
    shape = (nk, ns, NB, ngk)
    mask = np.asarray(ctx.gkvec.mask)[:, None, None, :]
    raw = rng.standard_normal(shape) + 1j * rng.standard_normal(shape)
    psi = raw * mask
    given = psi + 1e3 * raw * (1 - mask) if garbage else psi
    params = make_hkset_params(
        ctx, np.full((ns,) + tuple(ctx.fft_coarse.dims), 0.05), dtype=dtype)
    pr, pi = (jnp.asarray(a) for a in split_cplx(given, rdt))
    occ_w = jnp.asarray(
        rng.uniform(0.0, 2.0, shape[:3])
        * np.asarray(ctx.kweights)[:, None, None], dtype=rdt)
    return params, pr, pi, occ_w, jnp.asarray(psi.astype(dtype))


@pytest.mark.parametrize("garbage", [False, True], ids=["masked", "garbage"])
@pytest.mark.parametrize("ns", [1, 2])
@pytest.mark.parametrize("dtype,rtol", [(np.complex128, 1e-12),
                                        (np.complex64, 2e-6)],
                         ids=["f64", "f32"])
def test_cube_density_is_the_plain_density(kset, ns, dtype, rtol, garbage):
    params, pr, pi, occ_w, psi = block(kset, ns, dtype, garbage)
    dims = tuple(kset.fft_coarse.dims)
    rho = density_kset(params, pr, pi, occ_w)
    assert rho.shape == (ns,) + dims and rho.dtype == pr.dtype
    ref = plain_density(params.fft_index, psi, occ_w, dims)
    scale = float(jnp.max(jnp.abs(ref)))
    assert float(jnp.max(jnp.abs(rho - ref))) <= rtol * scale
    if ns == 2:  # the channels are not mixed up
        assert float(jnp.max(jnp.abs(rho[0] - ref[1]))) > 1e-3 * scale


@pytest.mark.parametrize("ns", [1, 2])
def test_cube_density_counts_the_electrons(kset, ns):
    """sum_r rho / n = sum occ_w sum_G |c|^2, a spin channel."""
    params, pr, pi, occ_w, psi = block(kset, ns, np.complex128)
    rho = density_kset(params, pr, pi, occ_w)
    n = np.prod(kset.fft_coarse.dims)
    want = jnp.einsum("ksb,ksbg->s", occ_w, jnp.abs(psi) ** 2)
    np.testing.assert_allclose(np.asarray(rho.sum(axis=(1, 2, 3)) / n),
                               np.asarray(want), rtol=1e-12)


def test_the_program_holds_one_form(kset):
    """No FFT and no scatter in what is lowered: products and gathers."""
    params, pr, pi, occ_w, _ = block(kset, 1, np.complex64)
    txt = density_kset.lower(params, pr, pi, occ_w).as_text()
    assert "fft" not in txt and "scatter" not in txt
    assert "dot_general" in txt and "gather" in txt


@pytest.mark.parametrize("ns", [1, 2])
@pytest.mark.parametrize("num_k,num_b", [(4, 2), (2, 4), (8, 1)])
def test_each_device_carries_its_own_kpoints_on_the_mesh(kset, num_k, num_b,
                                                        ns):
    """density_kset(mesh=...): the form inside the shard_map over "k",
    closed by the psum; "b" (here the CPU's virtual devices) is left to the
    partitioner and must not change the numbers."""
    params, pr, pi, occ_w, _ = block(kset, ns, np.complex128)
    ref = density_kset(params, pr, pi, occ_w)
    mesh = make_mesh(num_k=num_k, num_b=num_b)
    ps = place_kset_params(params, mesh, None)
    rho = density_kset(
        ps, shard_kset(mesh, pr), shard_kset(mesh, pi),
        jax.device_put(occ_w, NamedSharding(mesh, P("k", None, "b"))),
        mesh=mesh)
    assert rho.sharding.is_fully_replicated
    np.testing.assert_allclose(np.asarray(rho), np.asarray(ref),
                               rtol=0, atol=1e-12 * float(jnp.max(ref)))


def _job(deck, devices):
    cfg, ctx = context(deck)
    with spans.capture() as cap:
        r = run_scf(cfg, ctx=ctx, devices=devices)
    return r, ctx, [s for s in cap.records if s["name"] == "scf.density"]


def test_a_kset_job_books_its_density_rows():
    """nk x ns x nb rows an iteration, and the span says by which form."""
    deck, _ = rehearsal("si16-k223-us")
    r, ctx, density = _job(deck, jax.devices()[1:2])
    assert r["placement"]["path"] == "batched+fused"
    iters = r["num_scf_iterations"]
    nk, nb = ctx.gkvec.num_kpoints, ctx.num_bands
    assert (nk, ctx.num_spins, nb) == (8, 1, NB)
    assert r["counters"]["num_density_rows"] == nk * nb * iters
    assert len(density) == iters
    from sirius_tpu.ops.local import sphere_cube

    for s in density:
        assert s["form"] == "rows_minor" and s["rows"] == nk * nb
        assert tuple(s["cube"]) == sphere_cube(ctx.gkvec)


def test_the_host_tail_books_them_too():
    deck = copy.deepcopy(rehearsal("si16-k223-us")[0])
    deck.setdefault("control", {})["device_scf"] = "off"
    deck["parameters"]["num_dft_iter"] = 2
    r, ctx, density = _job(deck, jax.devices()[1:2])
    assert r["placement"]["path"] == "batched"
    assert r["counters"]["num_density_rows"] == 8 * NB * 2
    assert [s["form"] for s in density] == ["rows_minor"] * 2


def test_a_gamma_job_books_an_explicit_zero():
    deck = copy.deepcopy(rehearsal("si16-gamma-us")[0])
    deck["parameters"]["num_dft_iter"] = 2
    r, _, density = _job(deck, jax.devices()[1:2])
    assert r["placement"]["path"].startswith("gamma")
    assert "num_density_rows" in r["counters"]  # an explicit 0, not a gap
    assert r["counters"]["num_density_rows"] == 0
    assert density and not any("form" in s or "rows" in s for s in density)
