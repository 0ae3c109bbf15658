"""Non-self-consistent band structure along a k-path (reference: sirius.scf
task k_point_path + apps/bands/bands.py plotting data).

The converged density/potential defines a fixed Hamiltonian; bands at each
path point are solved with the same blocked iterative solver on a fresh
|G+k| sphere."""

from __future__ import annotations

import numpy as np


def band_path(
    ctx,
    pot,
    kpoints: np.ndarray,  # (nk, 3) fractional path vertices (already sampled)
    num_bands: int | None = None,
    d_full=None,
    vhub: np.ndarray | None = None,  # converged Hubbard potential [ns, ...]
) -> dict:
    import dataclasses as _dc

    import jax.numpy as jnp

    from sirius_tpu.core.gvec import GkVec
    from sirius_tpu.ops.beta import BetaProjectors
    from sirius_tpu.ops.hamiltonian import HkParams, apply_h_s
    from sirius_tpu.solvers.davidson import davidson

    nb = num_bands or ctx.num_bands
    kpts = np.atleast_2d(np.asarray(kpoints, dtype=np.float64))
    gk = GkVec.build(ctx.gvec, kpts, ctx.cfg.parameters.gk_cutoff, ctx.fft_coarse)
    beta = BetaProjectors.build(ctx.unit_cell, gk, qmax=ctx.cfg.parameters.gk_cutoff + 1e-9)
    hub_path = None
    if vhub is not None and ctx.cfg.parameters.hubbard_correction:
        # rebuild the Hubbard orbital tables on the path k-points so NSCF
        # bands include the converged U potential
        from sirius_tpu.ops.hubbard import HubbardData

        # path projectors share the cell layout, so the SCF qmat applies
        ctx_path = _dc.replace(
            ctx, gkvec=gk, beta=_dc.replace(beta, qmat=ctx.beta.qmat)
        )
        hub_path = HubbardData.build(ctx_path)
    ns = ctx.num_spins
    dion = ctx.beta.dion if d_full is None else d_full
    qmat = ctx.beta.qmat if ctx.beta.qmat is not None else np.zeros_like(dion)
    rng = np.random.default_rng(7)
    evals = np.zeros((len(kpts), ns, nb))
    for ik in range(len(kpts)):
        ekin = gk.kinetic()[ik]
        for ispn in range(ns):
            veff_r = pot.veff_r_coarse[ispn]
            params = HkParams(
                veff_r=jnp.asarray(veff_r),
                ekin=jnp.asarray(ekin),
                mask=jnp.asarray(gk.mask[ik]),
                fft_index=jnp.asarray(gk.fft_index[ik]),
                beta=jnp.asarray(beta.beta_gk[ik], dtype=jnp.complex128),
                dion=jnp.asarray(dion if np.ndim(dion) == 2 else dion[ispn]),
                qmat=jnp.asarray(qmat),
                hub=None if hub_path is None else jnp.asarray(hub_path.phi_s_gk[ik]),
                vhub=None if hub_path is None else jnp.asarray(vhub[ispn]),
            )
            x0 = (
                rng.standard_normal((nb, gk.ngk_max))
                + 1j * rng.standard_normal((nb, gk.ngk_max))
            ) / (1.0 + ekin)[None, :]
            h_diag = np.where(gk.mask[ik] > 0, ekin + float(np.real(pot.veff_g[0])), 1e4)
            ev, _, _, _ = davidson(
                apply_h_s, params, jnp.asarray(x0 * gk.mask[ik]),
                jnp.asarray(h_diag), jnp.ones(gk.ngk_max), jnp.asarray(gk.mask[ik]),
                num_steps=40, res_tol=1e-8, by_energy=False,
            )
            evals[ik, ispn] = np.asarray(ev)
    return {"kpoints": kpts.tolist(), "bands": evals.tolist()}


def sample_path(vertices: np.ndarray, points_per_segment: int = 20) -> np.ndarray:
    """Linear interpolation between path vertices."""
    vs = np.atleast_2d(np.asarray(vertices, dtype=np.float64))
    out = []
    for i in range(len(vs) - 1):
        for j in range(points_per_segment):
            out.append(vs[i] + (vs[i + 1] - vs[i]) * j / points_per_segment)
    out.append(vs[-1])
    return np.asarray(out)
