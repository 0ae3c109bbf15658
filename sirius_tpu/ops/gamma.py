"""Gamma-point real-storage band solve (the reference's "Gamma trick").

At k = 0 the Bloch coefficients of a real-in-r wave function obey
c(-G) = conj(c(G)); the reference exploits this with half-G storage and
real GEMMs (src/core/wf/wave_functions.hpp:1589-1626, 1683-1696
`reduce_gvec`, and the SPLA real-GEMM path). The TPU-native form chosen
here keeps the SAME array length but re-bases it to REAL numbers:

  x = [ c(0),  sqrt(2) Re c(G_1..G_P),  sqrt(2) Im c(G_1..G_P) ]

over one representative G of each (G, -G) pair. The map is an isometry:
sum_slots x_a x_b == Re <a|b> of the full complex sphere, so EVERY inner
product, Rayleigh-Ritz block, residual norm and preconditioner step of the
generic fixed-shape solver (solvers/davidson.py) works unchanged on these
real vectors — the subspace eigenproblems become real-symmetric (syevd
instead of heevd) and the big band-block GEMMs become real (4x fewer real
multiplies on the MXU than complex at equal slot count).

The H application unpacks to the complex sphere with pure gathers (no
matmul) and sends TWO bands through every complex FFT box: at Gamma each
band is real in r, so rows j and j + ceil(nb/2) of a block travel as psi_a +
i psi_b (box coefficients c_a + i c_b). The real potential multiplies the complex
field (real part psi_a v, imaginary part psi_b v), one forward transform
brings back F = v_a + i v_b, and the re-pack, which averages each (G, -G)
pair with the isometry's signs, is the projector onto the Hermitian part:
v_a = pack(F), v_b = pack(-i F). Half the transforms, scatters and gathers
of the one-band-a-box form, and nothing else: the density pairs its bands
the same way (Re^2 and Im^2 of one inverse transform), and ROWS_PER_BOX
tells the H-application counters of dft/scf.py. Beta projectors are packed
once with the same isometry, making <beta|psi> and the D/Q expansions real
GEMMs too.

Eligibility (wired in dft/scf.run_scf): Gamma-only k-set, no Hubbard
(complex per-k U apply), no mGGA, no G-sharding. Collinear spins are fine
(per-spin solve). The solve hands the fused device-resident tail
(dft/fused.py) what the batched solve does, without leaving the device:
solve_inputs_device cuts the next solve's potential, D and packed
preconditioner diagonal from the fused step's outputs, unpack_device gives
the band block as a (re, im) pair, density_gamma the coarse-box density.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

SQRT2 = np.sqrt(2.0)
# real bands a complex FFT box carries through the local operator and the
# density (the H-application counters of dft/scf.py read it)
ROWS_PER_BOX = 2


class GammaMap(NamedTuple):
    """Host-side pairing of the Gamma G-sphere (built once per context).

    Sphere-array index spaces: `rep`/`par` index into the ngk sphere
    arrays; packed layout is [zero | P representatives (Re) | P (Im)]."""

    zero: int  # sphere index of G = 0
    rep: np.ndarray  # [P] sphere index of each pair representative
    par: np.ndarray  # [P] sphere index of the partner -G
    # gather maps for device-side unpack (length ngk, sphere order):
    slot_re: np.ndarray  # packed slot holding Re of this G (or c0)
    slot_im: np.ndarray  # packed slot holding Im of this G (self for G=0)
    im_sign: np.ndarray  # +1 rep, -1 partner, 0 for G = 0
    scale: np.ndarray  # 1/sqrt2 for pairs, 1 for G = 0


def build_gamma_map(millers: np.ndarray, mask: np.ndarray) -> GammaMap:
    """millers: [ngk, 3] integer G of the Gamma sphere (valid where mask).

    Padded slots (mask == 0) are treated as extra 'zero' singletons mapped
    onto themselves with im_sign 0 — they stay exactly zero through the
    solve (the packed mask kills them)."""
    ngk = len(millers)
    valid = mask > 0
    index_of = {}
    for i in range(ngk):
        if valid[i]:
            index_of[tuple(int(v) for v in millers[i])] = i
    zero = index_of[(0, 0, 0)]
    rep, par = [], []
    seen = np.zeros(ngk, dtype=bool)
    seen[zero] = True
    for i in range(ngk):
        if seen[i] or not valid[i]:
            continue
        m = tuple(int(v) for v in millers[i])
        j = index_of.get((-m[0], -m[1], -m[2]))
        if j is None:
            raise ValueError(f"Gamma sphere not inversion-closed at G={m}")
        rep.append(i)
        par.append(j)
        seen[i] = seen[j] = True
    rep = np.asarray(rep, dtype=np.int32)
    par = np.asarray(par, dtype=np.int32)
    P = len(rep)
    slot_re = np.zeros(ngk, dtype=np.int32)
    slot_im = np.zeros(ngk, dtype=np.int32)
    im_sign = np.zeros(ngk)
    scale = np.ones(ngk)
    slot_re[zero] = 0
    slot_im[zero] = 0
    slot_re[rep] = 1 + np.arange(P)
    slot_im[rep] = 1 + P + np.arange(P)
    im_sign[rep] = 1.0
    scale[rep] = 1.0 / SQRT2
    slot_re[par] = 1 + np.arange(P)
    slot_im[par] = 1 + P + np.arange(P)
    im_sign[par] = -1.0
    scale[par] = 1.0 / SQRT2
    # padded slots: park them on their own packed positions past the data
    # region if any exist (ngk > 1 + 2P), else they'd alias slot 0
    pad = np.where(~valid)[0]
    if len(pad):
        base = 1 + 2 * P
        extra = base + np.arange(len(pad))
        if extra.max() >= ngk:
            raise ValueError("padded Gamma sphere inconsistent with pairing")
        slot_re[pad] = extra
        slot_im[pad] = extra
        im_sign[pad] = 0.0
        scale[pad] = 0.0
    return GammaMap(
        zero=int(zero), rep=rep, par=par, slot_re=slot_re,
        slot_im=slot_im, im_sign=im_sign, scale=scale,
    )


def pack(gm: GammaMap, c: np.ndarray) -> np.ndarray:
    """Complex sphere coefficients [..., ngk] -> packed real [..., ngk].

    Projects onto the Gamma-symmetric subspace (c(-G) := conj(c(G)) is
    enforced by construction, arbitrary input allowed)."""
    ngk = c.shape[-1]
    out = np.zeros(c.shape[:-1] + (ngk,), dtype=np.float64)
    out[..., 0] = np.real(c[..., gm.zero])
    # average the pair to make the projection exact for asymmetric input
    avg = 0.5 * (c[..., gm.rep] + np.conj(c[..., gm.par]))
    out[..., 1 : 1 + len(gm.rep)] = SQRT2 * np.real(avg)
    out[..., 1 + len(gm.rep) : 1 + 2 * len(gm.rep)] = SQRT2 * np.imag(avg)
    return out


def unpack(gm: GammaMap, x: np.ndarray) -> np.ndarray:
    """Packed real [..., ngk] -> complex sphere coefficients [..., ngk]."""
    xr = np.take(x, gm.slot_re, axis=-1)
    xi = np.take(x, gm.slot_im, axis=-1)
    return gm.scale * (xr + 1j * gm.im_sign * xi)


class GammaParams(NamedTuple):
    """Pytree for the packed-real H/S application at Gamma."""

    veff_r: jax.Array  # [n1,n2,n3] real
    ekin_p: jax.Array  # [ngk] kinetic at each packed slot's G
    mask_p: jax.Array  # [ngk] packed validity mask
    fft_index: jax.Array  # [ngk] sphere scatter index (full set)
    slot_re: jax.Array  # [ngk] gather maps (sphere order)
    slot_im: jax.Array
    im_sign: jax.Array
    scale: jax.Array
    zero_idx: jax.Array  # scalar: sphere position of G = 0
    beta_p: jax.Array  # [nbeta, ngk] packed real projectors
    dion: jax.Array  # [nbeta, nbeta] real
    qmat: jax.Array  # [nbeta, nbeta] real


def make_gamma_params(ctx, veff_r_coarse, gm: GammaMap, dmat=None,
                      rdtype=jnp.float64):
    """Build GammaParams for ik = 0 of a Gamma-only context. Constant
    tables (beta_p, gather maps, ekin) depend only on (ctx, rdtype) —
    callers should build once and `_replace(veff_r=..., dion=...)` per
    iteration (see run_scf's gamma branch)."""
    nbeta = ctx.beta.num_beta_total
    ngk = ctx.gkvec.ngk_max
    ekin = ctx.gkvec.kinetic()[0]
    # packed-slot kinetic: slot 0 -> G=0, Re/Im slots -> their pair's G
    ekin_p = np.zeros(ngk)
    ekin_p[0] = ekin[gm.zero]
    P = len(gm.rep)
    ekin_p[1 : 1 + P] = ekin[gm.rep]
    ekin_p[1 + P : 1 + 2 * P] = ekin[gm.rep]
    mask_p = np.zeros(ngk)
    mask_p[: 1 + 2 * P] = 1.0
    if nbeta:
        beta_p = pack(gm, np.asarray(ctx.beta.beta_gk[0]))
    else:
        beta_p = np.zeros((0, ngk))
    qmat = ctx.beta.qmat if ctx.beta.qmat is not None else np.zeros((nbeta, nbeta))
    dmat = ctx.beta.dion if dmat is None else dmat
    return GammaParams(
        veff_r=jnp.asarray(veff_r_coarse, dtype=rdtype),
        ekin_p=jnp.asarray(ekin_p, dtype=rdtype),
        mask_p=jnp.asarray(mask_p, dtype=rdtype),
        fft_index=jnp.asarray(ctx.gkvec.fft_index[0]),
        slot_re=jnp.asarray(gm.slot_re),
        slot_im=jnp.asarray(gm.slot_im),
        im_sign=jnp.asarray(gm.im_sign, dtype=rdtype),
        scale=jnp.asarray(gm.scale, dtype=rdtype),
        zero_idx=jnp.asarray(gm.zero),
        beta_p=jnp.asarray(beta_p, dtype=rdtype),
        dion=jnp.asarray(np.real(dmat), dtype=rdtype),
        qmat=jnp.asarray(np.real(qmat), dtype=rdtype),
    )


def pack_diags(gm: GammaMap, h_diag: np.ndarray, o_diag: np.ndarray):
    """Preconditioner diagonals in packed order (values follow each slot's
    G; the packed H/S diagonals are exactly these by the isometry)."""
    P = len(gm.rep)
    hp = np.full_like(h_diag, 1e4)
    op = np.ones_like(o_diag)
    hp[0] = h_diag[gm.zero]
    op[0] = o_diag[gm.zero]
    hp[1 : 1 + P] = h_diag[gm.rep]
    op[1 : 1 + P] = o_diag[gm.rep]
    hp[1 + P : 1 + 2 * P] = h_diag[gm.rep]
    op[1 + P : 1 + 2 * P] = o_diag[gm.rep]
    return hp, op


def pack_index(gm: GammaMap, ngk: int) -> np.ndarray:
    """Sphere index of each packed slot's G, [zero | rep | rep | 0...]
    ([ngk], built once per context): the gather form of pack_diags for
    pack_diags_device. The slots past 1 + 2P are the ones mask_p zeroes."""
    P = len(gm.rep)
    idx = np.zeros(ngk, dtype=np.int32)
    idx[0] = gm.zero
    idx[1 : 1 + P] = gm.rep
    idx[1 + P : 1 + 2 * P] = gm.rep
    return idx


@jax.jit
def pack_diags_device(idx, mask_p, h_diag, o_diag):
    """Device twin of pack_diags: sphere-order diagonals [..., ngk] already
    on the device (the fused step's h_diag) -> packed order, 1e4 / 1.0 in
    the slots mask_p (GammaParams) marks unused. Two gathers, no host
    round trip."""
    hp = jnp.where(mask_p > 0, jnp.take(h_diag, idx, axis=-1), 1e4)
    op = jnp.where(mask_p > 0, jnp.take(o_diag, idx, axis=-1), 1.0)
    return hp, op


@jax.jit
def solve_inputs_device(idx, mask_p, o_diag, veff_r, dion, h_diag):
    """The potential-dependent leaves of the next packed solves, cut from
    the fused step's device outputs (veff_r [ns, n1,n2,n3], dion [ns,
    nbeta, nbeta], h_diag [1, ns, ngk] in sphere order) in o_diag's
    precision: per spin (veff_r, dion, packed h_diag, packed o_diag). One
    program for all spins, so no index scalar travels from the host as an
    eager `a[ispn]` would send."""
    rdt = o_diag.dtype
    hp, op = pack_diags_device(idx, mask_p, h_diag[0].astype(rdt), o_diag)
    return [(veff_r[s].astype(rdt), dion[s].astype(rdt), hp[s], op)
            for s in range(veff_r.shape[0])]


def _unpack_pair(params: GammaParams, x: jax.Array):
    """(re, im) of the complex sphere coefficients of packed x [..., ngk]:
    pure gathers (no matmul), in x's precision."""
    xr = jnp.take(x, params.slot_re, axis=-1)
    xi = jnp.take(x, params.slot_im, axis=-1)
    return params.scale * xr, params.scale * params.im_sign * xi


@jax.jit
def unpack_device(params: GammaParams, x: jax.Array):
    """Device twin of unpack for consumers that take the band block as a
    real (re, im) pair (FusedScf.step, density_matrix_kset): packed real
    [..., ngk] -> (re, im), each [..., ngk] in sphere order."""
    return _unpack_pair(params, x)


def _pair_to_r(params: GammaParams, x: jax.Array):
    """The packed (and masked) block x [..., nb, ngk], TWO rows a complex
    box, in real space: row j and row j + h, h = ceil(nb/2), travel as
    psi_a(r) + i psi_b(r); [..., h, n1, n2, n3], with ifftn's 1/N in it. The
    two halves are contiguous slices: no strided gather on the way in, one
    concatenate on the way out. An odd nb travels with a row of zeros (a
    pad whose width is the static shape's parity)."""
    dims = params.veff_r.shape
    n = dims[0] * dims[1] * dims[2]
    x = jnp.pad(x, [(0, 0)] * (x.ndim - 2) + [(0, x.shape[-2] % 2), (0, 0)])
    h = x.shape[-2] // 2
    re, im = _unpack_pair(params, x)
    # c_a + i c_b; lax.complex keeps the working precision (a bare `1j *`
    # would promote f32 -> c128, which a TPU does not run)
    c = jax.lax.complex(re[..., :h, :] - im[..., h:, :],
                        im[..., :h, :] + re[..., h:, :])
    lead = c.shape[:-1]
    box = jnp.zeros(lead + (n,), dtype=c.dtype).at[..., params.fft_index].add(c)
    return jnp.fft.ifftn(box.reshape(lead + dims), axes=(-3, -2, -1))


def apply_h_s_gamma(params: GammaParams, x: jax.Array):
    """(H x, S x) for a packed-real band block x [nb, ngk]."""
    nb, npack = x.shape[-2:]
    x = x * params.mask_p
    # the two scopes name the operations for a capture's table
    # (obs/device_scopes.py): metadata only
    with jax.named_scope("local_op"):
        fr = _pair_to_r(params, x)
        # the potential is real: Re (fr v) = psi_a v, Im (fr v) = psi_b v
        vr = jax.lax.complex(jnp.real(fr) * params.veff_r,
                             jnp.imag(fr) * params.veff_r)
        f = (
            jnp.fft.fftn(vr, axes=(-3, -2, -1))
            .reshape(fr.shape[:-3] + (-1,))[..., params.fft_index]
        )
        # F = v_a + i v_b with v_a, v_b Hermitian: the re-pack's pair average
        # is (F(G) + conj F(-G)) / 2, so pack(F) = v_a and pack(-i F) = v_b;
        # one after the other they are the block's rows again
        both = jnp.concatenate(
            [f, jax.lax.complex(jnp.imag(f), -jnp.real(f))], axis=-2)
        vpack = _pack_device(both, params.slot_re, params.slot_im,
                             params.im_sign, params.scale, params.zero_idx,
                             npack)[..., :nb, :]
    ekin = jnp.where(params.mask_p > 0, params.ekin_p, 0.0)
    hx = ekin * x + vpack
    sx = x
    if params.beta_p.shape[0]:
        with jax.named_scope("beta_proj"):
            bp = jnp.einsum("xg,bg->bx", params.beta_p, x)
            hx = hx + jnp.einsum("bx,xy,yg->bg", bp, params.dion, params.beta_p)
            sx = sx + jnp.einsum("bx,xy,yg->bg", bp, params.qmat, params.beta_p)
    return hx * params.mask_p, sx * params.mask_p


def _pack_device(vg, slot_re, slot_im, im_sign, scale, zero_idx, npack):
    """Scatter the complex sphere array vg [..., ngk] into packed real
    slots. Each packed Re/Im slot receives contributions from BOTH pair
    members; averaging them (0.5 * sum of the two isometry images) is
    exact for Hermitian-symmetric vg and projects out rounding noise:
    Re v(-G) = Re v(G), Im v(-G) = -Im v(G) (the im_sign gather aligns
    the two)."""
    # NOTE float(...) keeps the scalar weakly typed: a bare np.float64
    # scalar would promote the whole f32 pipeline to f64
    half_sqrt2 = float(0.5 * SQRT2)
    w = (scale > 0).astype(scale.dtype)
    re_part = half_sqrt2 * jnp.real(vg) * w
    im_part = half_sqrt2 * jnp.imag(vg) * im_sign * w
    out = jnp.zeros(vg.shape[:-1] + (npack,), dtype=re_part.dtype)
    out = out.at[..., slot_re].add(re_part)
    out = out.at[..., slot_im].add(im_part)
    # slot 0 was filled by the G=0 re-scatter at sqrt2/2 weight (and 0 from
    # the im-scatter) — overwrite with the exact real value
    zero_val = jnp.take(jnp.real(vg), zero_idx, axis=-1)
    return out.at[..., 0].set(zero_val)


@partial(jax.jit, static_argnames=("nb",))
def initialize_subspace_gamma(params: GammaParams, xb, nb: int):
    """First-iteration LCAO rotation on packed-real vectors: one H/S
    application to the full atomic-orbital block, keep the lowest nb Ritz
    vectors (the Gamma twin of batched.initialize_subspace_kset) — one
    program, so no python scalar of the eager form reaches the device as
    a 64-bit operand."""
    from sirius_tpu.solvers.davidson import subspace_rotate

    hx, sx = apply_h_s_gamma(params, xb)
    return subspace_rotate(xb, hx, sx, nb, mask=params.mask_p).astype(xb.dtype)


@partial(jax.jit, static_argnames=("num_steps", "by_energy"))
def davidson_gamma(params: GammaParams, x0, h_diag_p, o_diag_p,
                   num_steps: int = 20, res_tol: float = 1e-2,
                   by_energy: bool = True):
    """Jit wrapper: the generic fixed-shape solver on packed real arrays
    (subspace blocks become real-symmetric; GEMMs real)."""
    from sirius_tpu.solvers.davidson import davidson

    return davidson(
        apply_h_s_gamma, params, x0, h_diag_p, o_diag_p, params.mask_p,
        num_steps=num_steps, res_tol=res_tol, by_energy=by_energy,
    )


@jax.jit
def density_gamma(params: GammaParams, x: jax.Array, occ_w: jax.Array):
    """Coarse-box density sum_b occ_w[..., b] |psi_b(r)|^2 from a
    packed-real band block x [..., nb, ngk] (Gamma-only k-set; occ_w
    [..., nb] includes the k-weight and max_occupancy; a leading spin axis
    rides along). Returns [..., n1, n2, n3] real."""
    nb = x.shape[-2]
    n = params.veff_r.size
    with jax.named_scope("density_gamma"):  # the name in a capture's table
        fr = _pair_to_r(params, x * params.mask_p) * n
        # two bands a box: band j is the real part, band j + h the imaginary
        # part; the zero row of an odd nb gets weight 0
        w = jnp.pad(occ_w, [(0, 0)] * (occ_w.ndim - 1) + [(0, nb % 2)])
        h = w.shape[-1] // 2
        return (jnp.einsum("...b,...bxyz->...xyz", w[..., :h], jnp.real(fr) ** 2)
                + jnp.einsum("...b,...bxyz->...xyz", w[..., h:], jnp.imag(fr) ** 2))
