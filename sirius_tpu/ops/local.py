"""Local part of H: kinetic + effective potential via batched FFTs.

Reference: Local_operator::apply_h (src/hamiltonian/local_operator.cpp:273)
runs a per-band loop of {backward FFT, multiply by V(r), forward FFT} with
MPI shuffles around it. Here the whole band block transforms at once —
jnp.fft.fftn batches over the leading axis, XLA fuses the potential multiply
— which is the key TPU win (SURVEY.md §7 "hard parts").

All functions are shape-polymorphic over leading batch axes and jit-able;
they run inside the SCF step jit.

ROWS ON THE LANES (``box_round_trip``, PERF.md section 6, PR 33). The round
trip sphere -> box -> V(r) -> box -> sphere of one [nb, ngk] block is
``_round_trip_block``: a scatter into the box, jnp.fft both ways, a gather
back. Vmapped over the k-points of a set, the compiler keeps k as a batch
axis it does not merge with the rows, and with 64 (or 26) rows a k-point a
box axis ends up along the TPU's 128 lanes, half of them empty. But what
depends on k in the round trip is only the sphere's map: V(r) and the
transforms are the same for every k of a spin channel. So the batching rule
of ``box_round_trip`` turns "vmapped over k with one V(r)" into ONE block of
k x rows rows with the row axis minor, ``_round_trip_rows_minor``: the
coefficients are held as (re, im) planes [c1, c2, c3, rows], filled by a
gather of whole lane rows through the sphere's inverse map, and each of the
three passes a direction is a product with a DFT matrix along one axis,
which leaves the axis order alone. Written as products, the passes need not
start from the whole box: the sphere lies in a cube of Miller indices
(``sphere_cube``: 28 of the box's 60 points an axis at gk_cutoff 6), so the
inverse passes go cube -> box with [n, m] matrices, one axis at a time, and
the forward ones box -> cube; the planes the box would hold zeros in, or
whose coefficients nobody reads, are never computed. The cube's inverse map
is a host table (``cube_inverse_map``, the ``cube`` leaf every HkSetParams
carries and the k-set programs hand to HkParams), and its shape is what
tells the program the cube's size; a call vmapped with one potential and no
table is refused. A potential per slice of the mapped axis (the spin
channels) is a loop over the slices, so that each folds by itself under the
vmap over k outside it. Called on one block, nothing is batched, the table
is not read (ops/hamiltonian.make_hk_params leaves it out) and the lines are
the ones apply_h_s always ran. The k-set density is the inverse half alone
(``rows_to_box``, then the weighted squares summed over the rows:
parallel/batched.density_kset, PR 42).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


def _round_trip_block(psi, fft_index, veff_r):
    """FFT[ V(r) * FFT^-1[psi] ] on the sphere for a block psi [..., ngk]
    (masked by the caller: padded slots carry zeros and scatter to cell 0
    additively)."""
    dims = veff_r.shape
    n = dims[0] * dims[1] * dims[2]
    batch = psi.shape[:-1]
    # named for the capture's scope table (obs/device_scopes.py): metadata
    # of the emitted operations, nothing the compiler optimises by
    with jax.named_scope("local_op"):
        box = jnp.zeros(batch + (n,), dtype=psi.dtype).at[..., fft_index].add(psi)
        fr = jnp.fft.ifftn(box.reshape(batch + dims), axes=(-3, -2, -1))
        return (
            jnp.fft.fftn(fr * veff_r, axes=(-3, -2, -1))
            .reshape(batch + (n,))[..., fft_index]
        )


def sphere_cube(gkvec) -> tuple[int, int, int]:
    """The cube of Miller indices that holds every sphere of the k-set: an
    axis of m points carries the indices -(m // 2) ... m - m // 2 - 1; at
    most the box's own length, which holds them all."""
    valid = np.asarray(gkvec.mask) > 0
    mil = np.asarray(gkvec.millers)[valid]
    lo, hi = -mil.min(axis=0), mil.max(axis=0)
    return tuple(int(min(n, max(2 * a, 2 * b + 1)))
                 for n, a, b in zip(gkvec.fft.dims, lo, hi))


def _cube_cells(fft_index, dims, cube):
    """The cell of the cube [m1, m2, m3] (flattened) that holds the box
    cell ``fft_index`` of a sphere; numpy or jnp."""
    i3 = fft_index % dims[2]
    i2 = (fft_index // dims[2]) % dims[1]
    i1 = fft_index // (dims[1] * dims[2])
    c1, c2, c3 = ((i + m // 2) % n
                  for i, m, n in zip((i1, i2, i3), cube, dims))
    return (c1 * cube[1] + c2) * cube[2] + c3


def cube_inverse_map(gkvec) -> np.ndarray:
    """[nk, m1, m2, m3] int32: for every cell of ``sphere_cube`` the slot of
    the plane wave of k-point ik that lives there, and ngk (one past the
    end: a row of zeros) where there is none. Host-side, built once."""
    from sirius_tpu.core.fftgrid import box_inverse_map

    cube = sphere_cube(gkvec)
    nk, ngk = gkvec.mask.shape
    cells = _cube_cells(np.asarray(gkvec.fft_index, dtype=np.int64),
                        tuple(gkvec.fft.dims), cube)
    inv = np.empty((nk, cube[0] * cube[1] * cube[2]), dtype=np.int32)
    for ik in range(nk):
        n = int(np.sum(np.asarray(gkvec.mask[ik]) > 0))  # valid slots lead
        # raises where two plane waves meet in a cell: a cube too small
        inv_k = box_inverse_map(cells[ik, :n], inv.shape[1])
        inv[ik] = np.where(inv_k == n, ngk, inv_k)
    return inv.reshape((nk,) + cube)


def _dft_matrix(n: int, m: int, inverse: bool, rdt) -> np.ndarray:
    """One axis of the box transform between the cube's m points (Miller
    index c - m // 2) and the box's n, as a (re, im) pair of real matrices
    [2, out, in]: box <- cube exp(+2 pi i j (c - m // 2) / n) / n, the 1/n
    of jnp.fft.ifftn with it, or cube <- box exp(-...). Built on the host in
    float64 from j (c - m // 2) mod n, so that the working precision rounds
    each entry once."""
    j, c = np.arange(n), np.arange(m) - m // 2
    ang = 2.0 * np.pi * (np.outer(j, c) % n) / n
    w = np.exp(1j * ang) / n if inverse else np.exp(-1j * ang).T
    return np.stack([w.real, w.imag]).astype(rdt)


_PASS = ("cab,bxyr->caxyr", "cab,xbyr->cxayr", "cab,xybr->cxyar")


def _dft_pass(w, xr, xi, axis: int):
    """One pass of the transform along axis ``axis`` of the planes
    [., ., ., rows]: (wr + i wi)(xr + i xi), as two real products with the
    stacked matrix; no other axis moves."""
    hi = jax.lax.Precision.HIGHEST
    tr = jnp.einsum(_PASS[axis], w, xr, precision=hi)
    ti = jnp.einsum(_PASS[axis], w, xi, precision=hi)
    return tr[0] - ti[1], tr[1] + ti[0]


def rows_to_box(psi, cube, dims, rdt):
    """The inverse half of the round trip for a k-set: psi [nk, rows, ngk]
    and cube [nk, m1, m2, m3] (``cube_inverse_map``) -> the (re, im) planes
    [n1, n2, n3, nk x rows] of jnp.fft.ifftn of every row's box (its 1/n
    with it), rows minor. A gather of whole lane rows through the cube's
    map (an empty cell reads the row of zeros appended at ngk; padded
    slots are never read), then cube -> box one axis a pass. The local
    operator's first half and all of the k-set density's transform
    (parallel/batched.density_kset)."""
    nk, rows, _ = psi.shape
    m = cube.shape[1:]
    psi_t = jnp.concatenate(
        [jnp.swapaxes(psi, 1, 2), jnp.zeros((nk, 1, rows), psi.dtype)],
        axis=1)
    x = jax.vmap(lambda p, i: p[i], out_axes=1)(psi_t, cube.reshape(nk, -1))
    x = x.reshape(m + (nk * rows,))  # [m1, m2, m3, k x rows]
    xr, xi = jnp.real(x), jnp.imag(x)
    for axis in (2, 1, 0):
        w = _dft_matrix(dims[axis], m[axis], True, rdt)
        xr, xi = _dft_pass(w, xr, xi, axis)
    return xr, xi


def _round_trip_rows_minor(psi, fft_index, veff_r, cube):
    """The round trip for a k-set, psi [nk, rows, ngk], fft_index [nk, ngk]
    and cube [nk, m1, m2, m3] (``cube_inverse_map``) with one V(r), as one
    block of nk x rows rows on the minor axis. The same sums as
    ``_round_trip_block`` k-point by k-point, in another order: equal to
    rounding, not to the bit."""
    nk, rows, _ = psi.shape
    dims, m = veff_r.shape, cube.shape[1:]
    rdt = veff_r.dtype
    with jax.named_scope("local_op"):
        xr, xi = rows_to_box(psi, cube, dims, rdt)
        v = veff_r[..., None]
        xr, xi = xr * v, xi * v
        for axis in (0, 1, 2):
            w = _dft_matrix(dims[axis], m[axis], False, rdt)
            xr, xi = _dft_pass(w, xr, xi, axis)
        x = jax.lax.complex(xr, xi).reshape(-1, nk, rows)
        # padded slots read the cell of G = 0; apply_h_s masks them
        out = jax.vmap(lambda b, i: b[i], in_axes=(1, 0))(
            x, _cube_cells(fft_index, dims, m))
        return jnp.swapaxes(out, 1, 2)


@jax.custom_batching.custom_vmap
def box_round_trip(psi, fft_index, veff_r, cube):
    """FFT[ V(r) * FFT^-1[psi] ] on the sphere: psi [..., ngk] masked,
    fft_index [ngk], veff_r [n1, n2, n3] real, cube [m1, m2, m3] the
    sphere's ``cube_inverse_map`` (None where one block is all that is ever
    applied). Module docstring."""
    return _round_trip_block(psi, fft_index, veff_r)


@box_round_trip.def_vmap
def _box_round_trip_vmap(axis_size, in_batched, psi, fft_index, veff_r, cube):
    psi_b, index_b, veff_b, cube_b = in_batched
    if veff_b:
        # a potential per slice (spin channels): one call each, so that an
        # outer vmap over k still meets a call with an unbatched potential
        pick = lambda a, batched, s: a[s] if batched else a  # noqa: E731
        out = [
            box_round_trip(pick(psi, psi_b, s), pick(fft_index, index_b, s),
                           veff_r[s], pick(cube, cube_b, s))
            for s in range(axis_size)
        ]
        return jnp.stack(out), True
    if cube is None:
        raise TypeError(
            "box_round_trip vmapped with one potential needs the spheres' "
            "cube table (HkParams.cube, ops/local.cube_inverse_map)")
    spread = lambda a, batched: a if batched else (  # noqa: E731
        jnp.broadcast_to(a, (axis_size,) + a.shape))
    psi = spread(psi, psi_b)
    out = _round_trip_rows_minor(
        psi.reshape(axis_size, -1, psi.shape[-1]), spread(fft_index, index_b),
        veff_r, spread(cube, cube_b))
    return out.reshape(psi.shape), True


@partial(jax.jit, static_argnums=(4,))
def apply_local(
    psi: jax.Array,  # [..., nb, ngk] complex PW coefficients
    veff_r: jax.Array,  # [n1, n2, n3] real effective potential on the box
    ekin: jax.Array,  # [ngk] |G+k|^2/2 (padded slots large -> masked below)
    fft_index: jax.Array,  # [ngk] int32
    dims: tuple[int, int, int],
    mask: jax.Array | None = None,  # [ngk] 1/0 validity
) -> jax.Array:
    """H_loc psi = ekin * psi + FFT^-1[ V(r) * FFT[psi] ] (per band, batched)."""
    if mask is not None:
        psi = psi * mask
    vpsi = _round_trip_block(psi, fft_index, veff_r.reshape(dims))
    ek = jnp.where(mask > 0, ekin, 0.0) if mask is not None else ekin
    out = ek * psi + vpsi
    if mask is not None:
        out = out * mask
    return out


def psi_to_grid(psi: jax.Array, fft_index: jax.Array, dims: tuple[int, int, int]) -> jax.Array:
    """psi(G) -> psi(r) on the box, batched; normalization: psi(r) = sum_G
    c(G) e^{iGr} so that (1/N) sum_r |psi(r)|^2 = sum_G |c|^2."""
    n = dims[0] * dims[1] * dims[2]
    batch = psi.shape[:-1]
    box = jnp.zeros(batch + (n,), dtype=psi.dtype).at[..., fft_index].add(psi)
    return jnp.fft.ifftn(box.reshape(batch + dims), axes=(-3, -2, -1)) * n
