"""Distributed 3-D FFT over a "g" mesh axis (slab decomposition).

Reference mechanism: SpFFT slab FFTs over z-columns of the box with MPI
transposes (src/core/fft/gvec.hpp:805 Gvec_fft, fft.hpp:29-95), used when
a replicated FFT box per band stops fitting (Si-511 class: ~1e6 G x ~2e3
bands). TPU-native equivalent: shard the box's FIRST axis over the "g"
mesh axis, do local FFTs over the two unsharded axes, one
lax.all_to_all re-slab, then the FFT along the remaining axis —
exactly the slab algorithm, with the MPI alltoall replaced by the ICI
collective.

Layouts (P = mesh size along "g"):
  x-slabs:  [n1/P, n2, n3]  per shard (sharded axis 0)
  y-slabs:  [n1, n2/P, n3]  per shard (sharded axis 1)

fft3d(box sharded x-slabs) -> full FFT, sharded y-slabs; ifft3d inverts.
n1 and n2 must be divisible by P (good_fft_size can always pad to a
multiple — the driver chooses box dims with the mesh in mind).

All entry points are shard_map'ed pure functions: call them inside jit
with arrays already device-put to the matching NamedSharding (see
tests/test_dist_fft.py for the canonical wiring).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_shard_map = jax.shard_map


def x_slab_spec() -> P:
    """Spec of a [..., n1, n2, n3] box sharded into x-slabs over "g"."""
    return P(None, "g", None, None)


def y_slab_spec() -> P:
    return P(None, None, "g", None)


def _fft_local_yz(slab):
    return jnp.fft.fftn(slab, axes=(-2, -1))


def _reslab_x_to_y(slab, axis_name: str):
    """[n1/P, n2, n3] x-slab -> [n1, n2/P, n3] y-slab via one all_to_all.

    Split the y axis into P blocks, exchange so every shard receives its
    y-block from all x-slabs, and concatenate along x."""
    # slab: [..., n1p, n2, n3] -> split axis -2 into P chunks, all_to_all
    # over the chunk axis, then merge the received x-chunks along axis -3
    # (named_scope tags the HLO so device profiles and xprof group the
    # exchange under a stable name the timeline exporter knows)
    with jax.named_scope("collective.all_to_all_x2y"):
        return jax.lax.all_to_all(
            slab, axis_name, split_axis=slab.ndim - 2,
            concat_axis=slab.ndim - 3, tiled=True,
        )


def _reslab_y_to_x(slab, axis_name: str):
    with jax.named_scope("collective.all_to_all_y2x"):
        return jax.lax.all_to_all(
            slab, axis_name, split_axis=slab.ndim - 3,
            concat_axis=slab.ndim - 2, tiled=True,
        )


def fft3d_shard(slab, axis_name: str = "g"):
    """Forward 3-D FFT of an x-slab-sharded box; result is y-slab sharded.

    slab: [..., n1/P, n2, n3] local block (call inside shard_map)."""
    slab = _fft_local_yz(slab)
    slab = _reslab_x_to_y(slab, axis_name)  # [..., n1, n2/P, n3]
    return jnp.fft.fft(slab, axis=-3)


def ifft3d_shard(slab, axis_name: str = "g"):
    """Inverse of fft3d_shard: y-slab-sharded spectrum -> x-slab box."""
    slab = jnp.fft.ifft(slab, axis=-3)
    slab = _reslab_y_to_x(slab, axis_name)  # [..., n1/P, n2, n3]
    return jnp.fft.ifftn(slab, axes=(-2, -1))


def make_dist_fft(mesh: Mesh, dims: tuple[int, int, int], batch: int):
    """jitted (fft, ifft) pair over `mesh`'s "g" axis for boxes
    [batch, n1, n2, n3]; inputs/outputs carry the slab NamedShardings."""
    npg = mesh.shape["g"]
    n1, n2, _ = dims
    if n1 % npg or n2 % npg:
        raise ValueError(
            f"box dims {dims} not divisible by mesh axis g={npg}; pick "
            "good_fft_size multiples of the mesh size"
        )
    xs = NamedSharding(mesh, x_slab_spec())
    ys = NamedSharding(mesh, y_slab_spec())

    fwd = jax.jit(
        _shard_map(
            partial(fft3d_shard, axis_name="g"),
            mesh=mesh, in_specs=x_slab_spec(), out_specs=y_slab_spec(),
        ),
        in_shardings=xs, out_shardings=ys,
    )
    inv = jax.jit(
        _shard_map(
            partial(ifft3d_shard, axis_name="g"),
            mesh=mesh, in_specs=y_slab_spec(), out_specs=x_slab_spec(),
        ),
        in_shardings=ys, out_shardings=xs,
    )
    return fwd, inv


def make_apply_veff_dist(mesh: Mesh, dims: tuple[int, int, int]):
    """Distributed local-operator core V.psi: spectral boxes in, spectral
    boxes out, every stage slab-sharded over "g" (the reference's per-band
    SpFFT loop body, local_operator.cpp:320-370, as two distributed
    transforms around a sharded pointwise multiply).

    Returns a jitted fn(psi_spec [nb, n1, n2, n3] y-slab-sharded spectrum,
    veff_r [n1, n2, n3] x-slab-sharded real potential) -> y-slab spectrum
    of V.psi. With the module's conventions (f(r) = N ifftn(F)) the N
    factors cancel: F' = fft3d(ifft3d(F) * V)."""
    npg = mesh.shape["g"]
    n1, n2, _ = dims
    if n1 % npg or n2 % npg:
        raise ValueError(f"box dims {dims} not divisible by g={npg}")
    ys = NamedSharding(mesh, y_slab_spec())
    vxs = NamedSharding(mesh, P("g", None, None))

    def _core(psi_spec, veff):
        r = ifft3d_shard(psi_spec, "g")  # [nb, n1/P, n2, n3] x-slab real
        r = r * veff[None]
        return fft3d_shard(r, "g")

    return jax.jit(
        _shard_map(
            _core, mesh=mesh,
            in_specs=(y_slab_spec(), P("g", None, None)),
            out_specs=y_slab_spec(),
        ),
        in_shardings=(ys, vxs), out_shardings=ys,
    )


# ---------------------------------------------------------------------------
# G-sharded Hamiltonian application: the slab path packaged as a davidson-
# compatible operator (equivalence-tested through a full band solve; the
# SCF driver selects it for the single-k Si-supercell-class regime — not
# yet auto-dispatched from run_scf). The G sphere is
# partitioned by the x index of each G's box slot, so every shard scatters
# its own coefficients into its own x-slab locally; the local operator runs
# as (ifft yz) -> all_to_all -> (ifft x) -> x V -> (fft x) -> all_to_all ->
# (fft yz); the beta-projector contractions reduce over "g" with one psum.
# ---------------------------------------------------------------------------


def gshard_partition(millers, dims, nparts: int):
    """Partition a G set by box x-slab.

    Returns (order [ngk_pad_total], local_index [nparts, ngk_loc],
    counts [nparts]): `order` maps the new (shard-major, padded) G layout
    back to the original G index (-1 = padding); local_index holds each
    shard's flattened LOCAL box indices (slab layout [n1/P, n2, n3]),
    with padding pointing at slot 0 alongside zero coefficients."""
    import numpy as np

    n1, n2, n3 = dims
    if nparts <= 0 or n1 % nparts:
        raise ValueError(f"n1={n1} not divisible into {nparts} x-slabs")
    i0 = np.mod(np.asarray(millers)[:, 0], n1)
    i1 = np.mod(np.asarray(millers)[:, 1], n2)
    i2 = np.mod(np.asarray(millers)[:, 2], n3)
    n1p = n1 // nparts
    part = i0 // n1p
    counts = np.bincount(part, minlength=nparts)
    ngk_loc = int(counts.max())
    order = np.full((nparts, ngk_loc), -1, dtype=np.int64)
    lidx = np.zeros((nparts, ngk_loc), dtype=np.int64)
    for p in range(nparts):
        sel = np.nonzero(part == p)[0]
        order[p, : len(sel)] = sel
        lidx[p, : len(sel)] = (
            (i0[sel] - p * n1p) * n2 + i1[sel]
        ) * n3 + i2[sel]
    return order, lidx, counts


def reorder_to_gshard(arr, order):
    """Gather the last axis of `arr` into the (shard-major, padded) layout;
    padding slots get zeros."""
    import numpy as np

    flat = order.reshape(-1)
    safe = np.maximum(flat, 0)
    out = np.asarray(arr)[..., safe]
    out = np.where(flat >= 0, out, 0.0)
    return out


def reorder_from_gshard(arr, order, ngk: int):
    """Inverse of reorder_to_gshard (padding dropped)."""
    import numpy as np

    flat = order.reshape(-1)
    out = np.zeros(arr.shape[:-1] + (ngk,), dtype=np.asarray(arr).dtype)
    ok = flat >= 0
    out[..., flat[ok]] = np.asarray(arr)[..., ok]
    return out


_GSHARD_INNER_CACHE: dict = {}


def _gshard_inner(mesh: Mesh, n1p: int, n2: int, n3: int):
    """Jitted shard_map operator body, cached per (mesh, slab geometry) —
    a STABLE callable so repeated factory calls (new potential each SCF
    iteration) hit the same compiled program instead of retracing a fresh
    closure (the no-closure rule of ops/hamiltonian.py)."""
    key = (id(mesh), n1p, n2, n3)
    hit = _GSHARD_INNER_CACHE.get(key)
    if hit is not None:
        return hit
    nloc = n1p * n2 * n3
    gspec = P(None, "g")
    gspec1 = P("g")

    def _apply(psi_loc, ekin_loc, mask_loc, beta_loc, lidx_loc, dion_r,
               qmat_r, veff_loc):
        # psi_loc: [nb, ngk_loc] this shard's coefficients
        nb = psi_loc.shape[0]
        psi_loc = psi_loc * mask_loc
        box = jnp.zeros((nb, nloc), dtype=psi_loc.dtype)
        box = box.at[:, lidx_loc].add(psi_loc)
        box = box.reshape(nb, n1p, n2, n3)
        # spectrum x-slab -> real y-slab
        fr = jnp.fft.ifftn(box, axes=(-2, -1))
        fr = _reslab_x_to_y(fr, "g")  # [nb, n1, n2/P, n3]
        fr = jnp.fft.ifft(fr, axis=-3)
        fr = fr * veff_loc[None]  # veff_loc: [n1, n2/P, n3] y-slab
        # real y-slab -> spectrum x-slab
        fr = jnp.fft.fft(fr, axis=-3)
        fr = _reslab_y_to_x(fr, "g")
        fr = jnp.fft.fftn(fr, axes=(-2, -1))
        vpsi = fr.reshape(nb, nloc)[:, lidx_loc] * mask_loc
        hpsi = jnp.where(mask_loc > 0, ekin_loc, 0.0) * psi_loc + vpsi
        spsi = psi_loc
        if beta_loc.shape[0]:
            with jax.named_scope("collective.psum_beta"):
                bp = jax.lax.psum(
                    jnp.einsum("xg,bg->bx", jnp.conj(beta_loc), psi_loc),
                    "g",
                )
            hpsi = hpsi + jnp.einsum("bx,xy,yg->bg", bp, dion_r, beta_loc)
            spsi = spsi + jnp.einsum("bx,xy,yg->bg", bp, qmat_r, beta_loc)
        return hpsi * mask_loc, spsi * mask_loc

    inner = jax.jit(
        _shard_map(
            _apply, mesh=mesh,
            in_specs=(gspec, gspec1, gspec1, P(None, "g"), gspec1, P(), P(),
                      P(None, "g", None)),
            out_specs=(gspec, gspec),
        )
    )
    _GSHARD_INNER_CACHE[key] = inner
    return inner


def make_apply_h_s_gshard(mesh: Mesh, dims, lidx, ekin_g, mask_g,
                          beta_g, dion, qmat, veff_r):
    """G-sharded (H psi, S psi) over the mesh's "g" axis.

    All *_g tables are in the shard-major gshard layout (callers apply
    reorder_to_gshard with the `order` from gshard_partition) and are
    device_put by this factory; psi arguments use the same layout:
    [nb, nparts*ngk_loc] with NamedSharding P(None, "g").

    Covers the kinetic + local + beta-projector (D/Q) terms of
    ops.hamiltonian.apply_h_s — equality asserted through a full davidson
    solve in tests/test_gshard_apply.py. Hubbard U is NOT applied on this
    path; +U runs use the replicated operator (the flagship G-sharded
    regime is plain Si-supercell class)."""
    import numpy as np

    npg = mesh.shape["g"]
    n1, n2, n3 = dims
    if n1 % npg or n2 % npg:
        raise ValueError(f"box dims {dims} not divisible by g={npg}")
    n1p = n1 // npg
    nloc = n1p * n2 * n3

    gspec = P(None, "g")     # [nb, ngk] arrays
    gspec1 = P("g")          # 1-D per-G tables
    gshard = NamedSharding(mesh, gspec)
    gshard1 = NamedSharding(mesh, gspec1)
    rep = NamedSharding(mesh, P())

    ekin_d = jax.device_put(jnp.asarray(ekin_g), gshard1)
    mask_d = jax.device_put(jnp.asarray(mask_g), gshard1)
    beta_d = jax.device_put(jnp.asarray(beta_g), NamedSharding(mesh, P(None, "g")))
    lidx_d = jax.device_put(jnp.asarray(lidx.reshape(-1)), gshard1)
    dion_d = jax.device_put(jnp.asarray(dion), rep)
    qmat_d = jax.device_put(jnp.asarray(qmat), rep)
    # real potential in the Y-slab layout the multiply needs; it is passed
    # per CALL (params slot) so SCF iterations with a new potential reuse
    # the same compiled program instead of retracing a fresh closure
    veff_sharding = NamedSharding(mesh, P(None, "g", None))
    veff_d = jax.device_put(jnp.asarray(np.asarray(veff_r)), veff_sharding)

    inner = _gshard_inner(mesh, n1p, n2, n3)

    def apply_h_s_gshard(params, psi):
        """davidson-compatible apply. params:
          None              -> factory veff + factory dion
          veff              -> new potential, factory dion
          (veff, dion)      -> per-SCF-iteration potential AND screened D
        (all leaves same shape/sharding as the factory ones, so iterations
        reuse the compiled program without retracing)."""
        d = dion_d
        if isinstance(params, tuple):
            v, d = params
        else:
            v = veff_d if params is None else params
        return inner(psi, ekin_d, mask_d, beta_d, lidx_d, d, qmat_d, v)

    apply_h_s_gshard.sharding_veff = veff_sharding
    apply_h_s_gshard.veff0 = veff_d
    return apply_h_s_gshard, gshard


# ---------------------------------------------------------------------------
# collective attribution probes
#
# A host timer cannot see inside one jitted apply — the exchanges, local
# FFTs, and the beta psum all fuse into one program. These probes compile
# each piece SEPARATELY at the deck's real shapes, warm it, then time it
# fenced, giving a measured per-call cost for every named collective. The
# SCF layer multiplies these by analytic apply counts to split the
# measured scf.band_solve wall into compute vs collective sub-spans, and
# bench_gshard_large writes them per-ndev into GSHARD_LARGE.json.
# ---------------------------------------------------------------------------


def probe_collectives(mesh: Mesh, dims: tuple[int, int, int], batch: int,
                      nbeta: int = 0, ngk: int | None = None,
                      dtype=jnp.complex128, reps: int = 3) -> dict:
    """Time each named collective of the G-sharded apply in isolation.

    batch: the band-block size the solver actually applies (nb rows per
    H.psi). ngk: padded G-count for the beta-psum probe (defaults to the
    box volume / 8, roughly the cutoff-sphere fill of a production deck).
    Returns {span_name: seconds per call (median of reps)}; each probe
    also records a ``collective.*`` span so the timeline shows them.
    """
    import time as _time

    import numpy as np

    from sirius_tpu.obs import spans as _spans

    npg = mesh.shape["g"]
    n1, n2, n3 = dims
    if n1 % npg or n2 % npg:
        raise ValueError(f"box dims {dims} not divisible by g={npg}")
    xs = NamedSharding(mesh, x_slab_spec())
    ys = NamedSharding(mesh, y_slab_spec())

    box = jax.device_put(
        jnp.ones((batch, n1, n2, n3), dtype=dtype), xs)
    box_y = jax.device_put(
        jnp.ones((batch, n1, n2, n3), dtype=dtype), ys)

    def _fft_local_apply(slab):
        # the four local-FFT stages of one apply, exchanges elided
        fr = jnp.fft.ifftn(slab, axes=(-2, -1))
        fr = jnp.fft.ifft(fr, axis=-3)
        fr = jnp.fft.fft(fr, axis=-3)
        return jnp.fft.fftn(fr, axes=(-2, -1))

    probes: dict[str, tuple] = {
        "collective.all_to_all_x2y": (
            jax.jit(_shard_map(
                partial(_reslab_x_to_y, axis_name="g"), mesh=mesh,
                in_specs=x_slab_spec(), out_specs=y_slab_spec()),
                in_shardings=xs, out_shardings=ys),
            (box,)),
        "collective.all_to_all_y2x": (
            jax.jit(_shard_map(
                partial(_reslab_y_to_x, axis_name="g"), mesh=mesh,
                in_specs=y_slab_spec(), out_specs=x_slab_spec()),
                in_shardings=ys, out_shardings=xs),
            (box_y,)),
        "collective.fft_local": (
            jax.jit(_shard_map(
                _fft_local_apply, mesh=mesh,
                in_specs=x_slab_spec(), out_specs=x_slab_spec()),
                in_shardings=xs, out_shardings=xs),
            (box,)),
    }

    if nbeta > 0:
        if ngk is None:
            ngk = max(npg, (n1 * n2 * n3) // 8 // npg * npg)
        gsh = NamedSharding(mesh, P(None, "g"))
        psi = jax.device_put(jnp.ones((batch, ngk), dtype=dtype), gsh)
        beta = jax.device_put(jnp.ones((nbeta, ngk), dtype=dtype), gsh)

        def _beta_psum(b, p):
            with jax.named_scope("collective.psum_beta"):
                return jax.lax.psum(
                    jnp.einsum("xg,bg->bx", jnp.conj(b), p), "g")

        probes["collective.psum_beta"] = (
            jax.jit(_shard_map(
                _beta_psum, mesh=mesh,
                in_specs=(P(None, "g"), P(None, "g")), out_specs=P())),
            (beta, psi))

    out = {}
    for name, (fn, arglist) in probes.items():
        jax.block_until_ready(fn(*arglist))  # compile + warm
        times = []
        for _ in range(max(1, reps)):
            t0 = _time.perf_counter()
            jax.block_until_ready(fn(*arglist))
            times.append(_time.perf_counter() - t0)
        med = float(np.median(times))
        _spans.record(name, med, ndev=npg, batch=batch,
                      dims=list(dims), reps=len(times))
        out[name] = med
    return out
