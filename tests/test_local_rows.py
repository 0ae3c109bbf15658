"""The k-set band solve's local operator with the rows on the minor axis
(ops/local.py, ROWS ON THE LANES; PERF.md section 6, PR 33): vmapped over
the k-points of a set with one potential, ``box_round_trip`` runs the set's
rows through each box transform together; called on one block it is the
scatter / FFT / gather lines apply_h_s always ran. Here it has to give the
per-k answers, leave the one-block program alone, add no collective on the
(k, b) mesh and say in the span what it runs."""

import copy

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sirius_tpu.config.schema import load_config
from sirius_tpu.dft import band_solve
from sirius_tpu.ops import hamiltonian, local
from sirius_tpu.ops.hamiltonian import HkParams, apply_h_s
from sirius_tpu.ops.mgga import apply_h_s_mgga
from sirius_tpu.parallel.batched import (
    davidson_kset, make_hkset_params, split_cplx,
)
from sirius_tpu.parallel.mesh import place_kset_params
from sirius_tpu.serve.scheduler import build_job_context

PARAMS = {
    "gk_cutoff": 3.0, "pw_cutoff": 7.0, "use_symmetry": False,
    "xc_functionals": ["XC_LDA_X", "XC_LDA_C_PZ"], "smearing_width": 0.025,
    "num_dft_iter": 60, "precision_wf": "fp64", "density_tol": 1e-8,
    "energy_tol": 1e-9, "num_bands": 8,
}
NHUB = 3


def context(ngridk):
    cfg = load_config(copy.deepcopy({
        "parameters": dict(PARAMS, ngridk=list(ngridk)),
        "control": {"ngk_pad_quantum": 16, "verbosity": 0},
        "synthetic": {"ultrasoft": True}}))
    return build_job_context(cfg, ".")


@pytest.fixture(scope="module")
def ctx223():
    """A generic mesh: 8 k-points solved, spheres of unequal size, padded."""
    ctx = context((2, 2, 3))
    num_gk = np.asarray(ctx.gkvec.num_gk)
    assert len(set(num_gk.tolist())) > 1 and num_gk.max() < ctx.gkvec.ngk_max
    return ctx


def _problem(ctx, dtype, ns, rows, seed=0):
    """(HkSetParams of ns spin channels with Hubbard tables, a masked block
    [nk, ns, rows, ngk], vtau [ns, box], gkc [nk, ngk, 3])."""
    rng = np.random.default_rng(seed)
    gk = ctx.gkvec
    nk, ngk = gk.num_kpoints, gk.ngk_max
    dims = tuple(ctx.fft_coarse.dims)
    mask = np.asarray(gk.mask)
    cplx = lambda *s: rng.standard_normal(s) + 1j * rng.standard_normal(s)
    nbeta = ctx.beta.num_beta_total
    d = rng.standard_normal((ns, nbeta, nbeta))
    vh = cplx(nk, ns, NHUB, NHUB)
    ps = make_hkset_params(
        ctx, 0.1 * rng.standard_normal((ns,) + dims),
        d_full=d + d.transpose(0, 2, 1), dtype=dtype,
        hub_phi=cplx(nk, NHUB, ngk) * mask[:, None, :],
        vhub=vh + vh.conj().transpose(0, 1, 3, 2))
    psi = cplx(nk, ns, rows, ngk) * mask[:, None, None, :]
    rdt = ps.veff_r.dtype
    return (ps, jnp.asarray(psi, dtype),
            jnp.asarray(0.1 * rng.standard_normal((ns,) + dims), rdt),
            jnp.asarray(gk.gkcart, rdt))


def _hk(ps, ik, ispn, hubbard, cube=True):
    c = jax.lax.complex
    return HkParams(
        cube=ps.cube[ik] if cube else None,
        veff_r=ps.veff_r[ispn], ekin=ps.ekin[ik], mask=ps.mask[ik],
        fft_index=ps.fft_index[ik], beta=c(ps.beta_re[ik], ps.beta_im[ik]),
        dion=ps.dion[ispn], qmat=ps.qmat,
        hub=c(ps.hub_re[ik], ps.hub_im[ik]) if hubbard else None,
        vhub=c(ps.vhub_re[ik, ispn], ps.vhub_im[ik, ispn]) if hubbard
        else None)


def _apply(pk, vtau_s, gkc_k, x, mgga):

    return apply_h_s_mgga(pk, vtau_s, gkc_k, x) if mgga else apply_h_s(pk, x)


def _per_k(ps, psi, vtau, gkc, hubbard, mgga):
    """One block at a time: nothing is batched."""
    nk, ns = psi.shape[:2]
    out = [[_apply(_hk(ps, ik, s, hubbard), vtau[s], gkc[ik], psi[ik, s], mgga)
            for s in range(ns)] for ik in range(nk)]
    return tuple(jnp.stack([jnp.stack([o[j] for o in row]) for row in out])
                 for j in range(2))


def _over_the_set(ps, psi, vtau, gkc, hubbard, mgga, cube=True):
    """The vmaps of parallel/batched.davidson_kset: k outside, spin inside
    (``cube`` False: operator parameters that lack the spheres' table)."""
    def one_k(ik, gkc_k, psi_k):
        def one_spin(ispn, vtau_s, x):
            return _apply(_hk(ps, ik, ispn, hubbard, cube), vtau_s, gkc_k, x,
                          mgga)

        return jax.vmap(one_spin)(jnp.arange(psi.shape[1], dtype=jnp.int32), vtau, psi_k)

    return jax.vmap(one_k)(jnp.arange(psi.shape[0], dtype=jnp.int32), gkc, psi)


def _primitives(jaxpr, found):
    for eqn in jaxpr.eqns:
        found.add(eqn.primitive.name)
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    _primitives(inner, found)
    return found


def _check(ctx, dtype, tol, ns, rows, hubbard=False, mgga=False):
    ps, psi, vtau, gkc = _problem(ctx, dtype, ns, rows)
    want = _per_k(ps, psi, vtau, gkc, hubbard, mgga)
    got = jax.jit(lambda *a: _over_the_set(*a, hubbard, mgga))(
        ps, psi, vtau, gkc)
    for g, w in zip(got, want):
        assert g.dtype == w.dtype == dtype
        assert float(jnp.max(jnp.abs(g - w)) / jnp.max(jnp.abs(w))) <= tol


def test_cube_table_is_the_inverse_of_the_spheres_map(ctx223):
    gk = ctx223.gkvec
    dims, cube = tuple(gk.fft.dims), local.sphere_cube(gk)
    assert all(m < n for m, n in zip(cube, dims))  # the sphere leaves room
    inv = local.cube_inverse_map(gk)
    assert inv.shape == (gk.num_kpoints,) + cube and inv.dtype == np.int32
    cells = local._cube_cells(np.asarray(gk.fft_index, np.int64), dims, cube)
    for ik in range(gk.num_kpoints):
        n = int(gk.num_gk[ik])
        assert np.array_equal(inv[ik].ravel()[cells[ik, :n]], np.arange(n))
        assert np.count_nonzero(inv[ik] != gk.ngk_max) == n
        # a cell's Miller index is its coordinate less the cube's offset
        c = np.stack(np.unravel_index(cells[ik, :n], cube), axis=1)
        assert np.array_equal(c - np.asarray(cube) // 2, gk.millers[ik, :n])


# (a) a step's block (nb rows), the chunk boundary's [X; P] (2 nb) and a
# count that fills no lane tile, in both precisions
@pytest.mark.parametrize("rows", [8, 16, 5])
@pytest.mark.parametrize("dtype, tol", [(jnp.complex128, 1e-12),
                                        (jnp.complex64, 2e-6)])
def test_the_set_together_gives_the_per_k_answers(ctx223, dtype, tol, rows):
    _check(ctx223, dtype, tol, 1, rows)


# (b) a potential a spin channel folds channel by channel; the Hubbard and
# tau terms stay per k around it
@pytest.mark.parametrize("case", ["two_spins", "hubbard", "mgga",
                                  "two_spins_hubbard_mgga"])
def test_spin_channels_hubbard_and_mgga_wrappers_give_the_per_k_answers(
        ctx223, case):
    _check(ctx223, jnp.complex128, 1e-12, 2 if "two_spins" in case else 1, 8,
           hubbard="hubbard" in case, mgga="mgga" in case)


@pytest.mark.parametrize("ns", [1, 2])
def test_the_set_goes_through_dft_products_and_one_block_through_the_fft(
        ctx223, ns):
    """Engagement: over the set no fft and no scatter-add is left (the box is
    filled by a gather, the passes are products); on one block the fft is."""
    ps, psi, vtau, gkc = _problem(ctx223, jnp.complex64, ns, 8)
    over = _primitives(jax.make_jaxpr(
        lambda *a: _over_the_set(*a, False, False))(ps, psi, vtau, gkc).jaxpr,
        set())
    assert "fft" not in over and "scatter-add" not in over
    assert {"dot_general", "gather"} <= over
    one = _primitives(jax.make_jaxpr(
        lambda x: apply_h_s(_hk(ps, 0, 0, False), x))(psi[0, 0]).jaxpr, set())
    assert {"fft", "scatter-add"} <= one and "dot_general" in one


@pytest.mark.parametrize("ns", [1, 2])
def test_a_set_without_the_cube_table_is_refused(ctx223, ns):
    """Every HkSetParams carries the table (a required leaf); an operator
    built without it and vmapped over k has no second form to fall into."""
    ps, psi, vtau, gkc = _problem(ctx223, jnp.complex64, ns, 8)
    assert ps.cube.shape == (psi.shape[0],) + local.sphere_cube(ctx223.gkvec)
    with pytest.raises(TypeError, match="cube table"):
        _over_the_set(ps, psi, vtau, gkc, False, False, cube=False)
    assert "cube" not in type(ps)._field_defaults


def _parent_apply_h_s(params, psi):
    """ops/hamiltonian.apply_h_s as it stood before PR 33, line for line."""
    dims = params.veff_r.shape
    n = dims[0] * dims[1] * dims[2]
    psi = psi * params.mask
    batch = psi.shape[:-1]
    box = jnp.zeros(batch + (n,), dtype=psi.dtype).at[..., params.fft_index].add(psi)
    fr = jnp.fft.ifftn(box.reshape(batch + dims), axes=(-3, -2, -1))
    vpsi = (
        jnp.fft.fftn(fr * params.veff_r, axes=(-3, -2, -1))
        .reshape(batch + (n,))[..., params.fft_index]
    )
    ekin = jnp.where(params.mask > 0, params.ekin, 0.0)
    hpsi = ekin * psi + vpsi
    spsi = psi
    if params.beta.shape[0]:
        bp = jnp.einsum("xg,bg->bx", jnp.conj(params.beta), psi)
        hpsi = hpsi + jnp.einsum("bx,xy,yg->bg", bp, params.dion, params.beta)
        spsi = spsi + jnp.einsum("bx,xy,yg->bg", bp, params.qmat, params.beta)
    if params.hub is not None and params.hub.shape[0]:
        hp = jnp.einsum("mg,bg->bm", jnp.conj(params.hub), psi)
        hpsi = hpsi + jnp.einsum("bm,mn,ng->bg", hp, params.vhub, params.hub)
    return hpsi * params.mask, spsi * params.mask


_parent_apply_h_s.__name__ = "apply_h_s"


# (c) the reference paths (SerialSolver, _lcao_rotate, bands, direct
# minimisation, linear response, the numerics probes) call it on one block
@pytest.mark.parametrize("hubbard", [False, True])
@pytest.mark.parametrize("dtype", [jnp.complex128, jnp.complex64])
def test_one_block_lowers_to_the_text_it_had(ctx223, dtype, hubbard):
    ps, psi, _, _ = _problem(ctx223, dtype, 1, 8)
    # make_hk_params, which those paths build their operator with, has no
    # cube table
    pk, x = _hk(ps, 1, 0, hubbard, cube=False), psi[1, 0]
    now = jax.jit(apply_h_s).lower(pk, x).as_text()
    assert now == jax.jit(_parent_apply_h_s).lower(pk, x).as_text()
    assert "stablehlo.fft" in now


_COLLECTIVES = ("all-gather", "all-reduce", "all-to-all", "reduce-scatter",
                "collective-permute")


def _collectives(ctx, devices):
    band = band_solve.choose(ctx, ctx.cfg, devices, serial_bands=False,
                             hub=None, paw=None, mgga=False,
                             wf_dtype=jnp.complex64)
    assert isinstance(band, band_solve.KsetSolver) and band.mesh is not None
    assert dict(band.mesh.shape) == {"k": 4, "b": 1}
    ps, psi, _, _ = _problem(ctx, jnp.complex64, 1, 8)
    ps = place_kset_params(ps._replace(
        hub_re=None, hub_im=None, vhub_re=None, vhub_im=None), band.mesh, None)
    pr, pi = (band._place_psi(jnp.asarray(a))
              for a in split_cplx(np.asarray(psi), np.float32))
    txt = davidson_kset.lower(
        ps, pr, pi, num_steps=2, res_tol=np.float32(1e-6)).compile().as_text()
    return {c: txt.count(f" {c}(") + txt.count(f" {c}-start(")
            for c in _COLLECTIVES}


# (d) the k axis is sharded over the mesh's "k": k folds into the rows shard
# by shard
def test_the_fold_adds_no_collective_on_the_k_b_mesh(ctx223, monkeypatch):
    devices = jax.devices()[:4]
    folded = _collectives(ctx223, devices)
    monkeypatch.setattr(
        hamiltonian, "box_round_trip",
        lambda psi, index, veff, cube: local._round_trip_block(psi, index, veff))
    jax.clear_caches()
    try:
        per_k = _collectives(ctx223, devices)
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    assert sum(per_k.values()) > 0  # the parent's program has its own
    for c in _COLLECTIVES:
        assert folded[c] <= per_k[c], (c, folded, per_k)


# (e) the engagement record of the scf.setup span: the rows are those of
# the block a device is given, whatever the mesh does with its "b"
@pytest.mark.parametrize("ndev, k_b, band_axis, per_device", [
    (1, None, None, (8, 8)), (4, (4, 1), "b", (2, 8)),
    (4, (2, 2), "b", (4, 4)), (4, (2, 2), None, (4, 8))])
def test_plan_says_how_many_rows_a_box_transform_carries(
        ctx223, ndev, k_b, band_axis, per_device):
    devices = jax.devices()[:ndev]
    if k_b in (None, (4, 1)):  # the mesh the deck gets by itself
        band = band_solve.choose(ctx223, ctx223.cfg, devices,
                                 serial_bands=False, hub=None, paw=None,
                                 mgga=False, wf_dtype=jnp.complex64)
        assert isinstance(band, band_solve.KsetSolver)
        assert band.mesh is None if k_b is None else (
            tuple(band.mesh.shape.values()) == k_b)
    else:  # bands over "b", or a "b" that replicates (multi-host's fallback)
        mesh = jax.sharding.Mesh(np.array(devices).reshape(k_b), ("k", "b"))
        band = band_solve.KsetSolver(
            ctx223, ctx223.cfg, devices, mesh,
            jax.sharding.PartitionSpec("k", None, band_axis, None), None,
            False)
    kset = band.plan(jnp.complex64)["kset"]
    assert (ctx223.gkvec.num_kpoints, ctx223.num_bands) == (8, 8)
    rows = per_device[0] * per_device[1]
    assert kset["local_rows"] == [rows, 2 * rows]
    assert kset["local_layout"] == "rows_minor"
    # PR 35: the matrices one eigh call carries on one device are its
    # k-points (a split over "b" does not divide them)
    assert kset["subspace_eigh"] == {
        "form": "library", "rows": 24, "batch": per_device[0]}
