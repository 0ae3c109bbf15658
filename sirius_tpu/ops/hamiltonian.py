"""Per-k Hamiltonian application as a pure function over a parameter pytree.

Keeping all per-k data (potential box, kinetic energies, projector tables)
in one NamedTuple pytree — rather than captured in python closures — means
the jitted solver compiles ONCE for the whole k-set and every SCF iteration
(closures would retrace per call; measured 20x+ end-to-end difference).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from sirius_tpu.ops.local import box_round_trip


def real_dtype_of(dtype):
    """The real dtype paired with a complex working dtype (single source for
    the precision-tier mapping)."""
    return jnp.float32 if dtype == jnp.complex64 else jnp.float64


class HkParams(NamedTuple):
    """Everything needed to apply H and S at one k-point (pytree)."""

    veff_r: jax.Array  # [n1,n2,n3] effective potential on coarse box
    ekin: jax.Array  # [ngk]
    mask: jax.Array  # [ngk]
    fft_index: jax.Array  # [ngk] int32
    beta: jax.Array  # [nbeta, ngk] (nbeta may be 0)
    dion: jax.Array  # [nbeta, nbeta]
    qmat: jax.Array  # [nbeta, nbeta]; all-zero if norm-conserving
    hub: jax.Array = None  # [nhub, ngk] S-weighted Hubbard orbitals (or None)
    vhub: jax.Array = None  # [nhub, nhub] Hubbard potential matrix (or None)
    # [m1, m2, m3] int32 ops/local.cube_inverse_map of this k-point: the
    # k-set programs fill it, and only a vmap over the set reads it
    cube: jax.Array = None


def make_hk_params(
    ctx,
    ik: int,
    veff_r_coarse: np.ndarray,
    dmat: np.ndarray | None = None,
    dtype=jnp.complex128,
    hub_phi: np.ndarray | None = None,  # (nhub, ngk) for this k
    vhub: np.ndarray | None = None,  # (nhub, nhub), one spin channel
) -> HkParams:
    """dmat: full D matrix (bare D_ion + ultrasoft V_eff augmentation term);
    defaults to the bare D_ion for norm-conserving runs. dtype selects the
    wave-function precision (complex64 = reference precision_wf fp32; the
    TPU hot path)."""
    nbeta = ctx.beta.num_beta_total
    beta = ctx.beta.beta_gk[ik] if nbeta else np.zeros((0, ctx.gkvec.ngk_max))
    qmat = (
        ctx.beta.qmat
        if ctx.beta.qmat is not None
        else np.zeros((nbeta, nbeta))
    )
    rdtype = real_dtype_of(dtype)
    return HkParams(
        veff_r=jnp.asarray(veff_r_coarse, dtype=rdtype),
        ekin=jnp.asarray(ctx.gkvec.kinetic()[ik], dtype=rdtype),
        mask=jnp.asarray(ctx.gkvec.mask[ik], dtype=rdtype),
        fft_index=jnp.asarray(ctx.gkvec.fft_index[ik]),
        beta=jnp.asarray(beta, dtype=dtype),
        dion=jnp.asarray(ctx.beta.dion if dmat is None else dmat, dtype=rdtype),
        qmat=jnp.asarray(qmat, dtype=rdtype),
        hub=None if hub_phi is None else jnp.asarray(hub_phi, dtype=dtype),
        vhub=None if vhub is None else jnp.asarray(vhub, dtype=dtype),
    )


def apply_h_s(params: HkParams, psi: jax.Array) -> tuple[jax.Array, jax.Array]:
    """(H psi, S psi) for a band block psi [nb, ngk]."""
    psi = psi * params.mask
    # one block runs the scatter / FFT / gather lines; vmapped over a k-set
    # with one potential, the set's rows go through the box together
    vpsi = box_round_trip(psi, params.fft_index, params.veff_r, params.cube)
    ekin = jnp.where(params.mask > 0, params.ekin, 0.0)
    hpsi = ekin * psi + vpsi
    spsi = psi
    if params.beta.shape[0]:
        with jax.named_scope("beta_proj"):
            bp = jnp.einsum("xg,bg->bx", jnp.conj(params.beta), psi)
            hpsi = hpsi + jnp.einsum("bx,xy,yg->bg", bp, params.dion, params.beta)
            # qmat is all-zero for norm-conserving species; the extra einsum
            # is negligible next to the FFTs and keeps the pytree static
            spsi = spsi + jnp.einsum("bx,xy,yg->bg", bp, params.qmat, params.beta)
    if params.hub is not None and params.hub.shape[0]:
        # Hubbard U: H psi += sum_{mn} phi_n V_{mn} <phi_m|psi>
        hp = jnp.einsum("mg,bg->bm", jnp.conj(params.hub), psi)
        hpsi = hpsi + jnp.einsum("bm,mn,ng->bg", hp, params.vhub, params.hub)
    return hpsi * params.mask, spsi * params.mask
