"""Non-collinear SCF ground-state driver (num_mag_dims = 3).

Mirrors dft/scf.run_scf for spinor wave functions: one flattened-spinor
band set per k-point ([nb, 2*ngk]), 4-component density (rho, mx, my, mz),
vector B_xc from the locally-diagonal XC projection, and spin-block D/Q
operators. Reference call stack: dft_ground_state.cpp:178-427 with the
num_mag_dims()==3 branches of density.cpp, potential/xc.cpp and
hamiltonian/local_operator.cpp.

Spin-orbit coupling enters only through the (dmat, qmat) spin blocks and
the j-resolved projector transform (ops/so.py); the loop here is agnostic.
"""

from __future__ import annotations

import time

import jax.numpy as jnp
import numpy as np

from sirius_tpu.config.schema import Config
from sirius_tpu.context import SimulationContext
from sirius_tpu.dft.density import (
    initial_density_g,
    initial_magnetization_vec_g,
    rho_real_space,
    symmetrize_density_matrix_nc,
    symmetrize_pw,
)
from sirius_tpu.dft.mixer import Mixer, initial_res_tol, schedule_res_tol
from sirius_tpu.dft.occupation import find_fermi
from sirius_tpu.dft.potential_nc import (
    generate_potential_nc,
    symmetrize_vector_pw,
)
from sirius_tpu.dft.xc import XCFunctional
from sirius_tpu.ops.atomic import atomic_orbitals
from sirius_tpu.ops.augmentation import d_operator, rho_aug_g
from sirius_tpu.ops.spinor import spin_blocks_from_components
from sirius_tpu.parallel.batched import join_cplx, split_cplx
from sirius_tpu.parallel.batched_nc import (
    davidson_kset_nc,
    density_kset_nc,
    density_matrix_kset_nc,
    make_nc_set_params,
)
from sirius_tpu.utils.profiler import counters, profile, reset_timers, timer_report


def _initial_spinors(ctx: SimulationContext) -> np.ndarray:
    """LCAO spinors [nk, nb, 2*ngk]: orbital j fills bands 2j (up) and
    2j+1 (down); the rest are damped-random in both components."""
    nk = ctx.gkvec.num_kpoints
    nb = ctx.num_bands
    ngk = ctx.gkvec.ngk_max
    ao = atomic_orbitals(ctx.unit_cell, ctx.gkvec,
                         ctx.cfg.parameters.gk_cutoff + 1e-9,
                         forms=ctx.ao_forms)
    rng = np.random.default_rng(42)
    psi = np.zeros((nk, nb, 2, ngk), dtype=np.complex128)
    nao = ao.shape[1]
    for ik in range(nk):
        j = 0
        for b in range(nb):
            if j < nao:
                psi[ik, b, b % 2] = ao[ik, j]
                if b % 2 == 1:
                    j += 1
            else:
                damp = 1.0 / (1.0 + ctx.gkvec.kinetic()[ik])
                psi[ik, b, :] = (
                    rng.standard_normal((2, ngk))
                    + 1j * rng.standard_normal((2, ngk))
                ) * damp
        psi[ik] *= ctx.gkvec.mask[ik][None, None, :]
    return psi.reshape(nk, nb, 2 * ngk)


def _dm_component_blocks(ctx, dm3):
    """Per-atom aux blocks for the 4 augmentation fields (rho, mz, mx, my)
    from the (uu, dd, ud) spin components (reference density_matrix_aux,
    density.cpp:1784-1811). Each returned matrix is Hermitian so the packed
    symmetric Q contraction in rho_aug_g is exact."""
    uu, dd, ud = dm3
    return {
        "rho": uu + dd,
        "mz": uu - dd,
        "mx": ud + ud.conj().T,
        "my": 1j * (ud - ud.conj().T),
    }


def run_scf_nc(
    cfg: Config,
    base_dir: str = ".",
    ctx: SimulationContext | None = None,
) -> dict:
    t0 = time.time()
    reset_timers()
    p = cfg.parameters
    if ctx is None:
        ctx = SimulationContext.create(cfg, base_dir)
    assert ctx.num_mag_dims == 3
    xc = XCFunctional(p.xc_functionals)
    if xc.is_mgga:
        # evaluate_polarized would silently default tau to zero and the
        # spinor apply has no tau operator
        raise NotImplementedError("mGGA with non-collinear magnetism")
    nk, nb = ctx.gkvec.num_kpoints, ctx.num_bands
    nel = ctx.unit_cell.num_valence_electrons - p.extra_charge
    if nb * ctx.max_occupancy < nel - 1e-12:
        raise ValueError(f"num_bands={nb} cannot hold {nel} electrons (spinor)")
    if cfg.hubbard.local:
        raise NotImplementedError("Hubbard+non-collinear is not implemented yet")
    wf_dtype = jnp.complex64 if p.precision_wf == "fp32" else jnp.complex128
    from sirius_tpu.ops.hamiltonian import real_dtype_of

    so = bool(getattr(p, "so_correction", False))
    so_data = None
    if so:
        from sirius_tpu.ops.so import SpinOrbitData

        so_data = SpinOrbitData.build(ctx)
        if so_data is None:
            raise ValueError(
                "so_correction requested but no species has j-resolved "
                "(relativistic) beta projectors"
            )

    rho_g = initial_density_g(ctx)
    mvec_g = initial_magnetization_vec_g(ctx)
    psi = _initial_spinors(ctx)

    pot = generate_potential_nc(ctx, rho_g, xc, mvec_g)
    mixer = Mixer(
        cfg.mixer, ctx.gvec.glen2, num_components=4, omega=ctx.unit_cell.omega
    )
    ng = ctx.gvec.num_gvec

    do_symmetrize = (
        p.use_symmetry and ctx.symmetry is not None and ctx.symmetry.num_ops > 1
    )
    if ctx.beta.num_beta_total:
        _bre, _bim = split_cplx(np.asarray(ctx.beta.beta_gk))
        beta_dev = (jnp.asarray(_bre), jnp.asarray(_bim))
    else:
        beta_dev = None

    def pack(r, m):
        return np.concatenate([r, m[0], m[1], m[2]])

    def unpack(x):
        return x[:ng], np.stack([x[ng : 2 * ng], x[2 * ng : 3 * ng], x[3 * ng :]])

    x_mix = pack(rho_g, mvec_g)
    evals = np.zeros((nk, nb))
    pr = pi = None
    ps = None  # device param tables, constants reused across iterations
    mu, occ, entropy_sum = 0.0, jnp.zeros((nk, 1, nb)), 0.0
    etot_history, rms_history = [], []
    e_prev, converged, rms, scf_correction = None, False, 0.0, 0.0
    num_iter_done = 0
    itsol = cfg.iterative_solver
    # adaptive band-solve tolerance (reference dft_ground_state.cpp:252-259);
    # see run_scf — a static bar stalls tight decks (test09: density_tol 1e-6
    # with a 1e-6 locked-band noise floor never meets the bar in 100 iters)
    res_tol = initial_res_tol(itsol)

    for it in range(p.num_dft_iter):
        # --- spin-block D operator ---
        if ctx.aug is not None:
            d0 = d_operator(ctx.unit_cell, ctx.gvec, ctx.aug, pot.veff_g,
                            ctx.beta, phases=ctx.phases)
            db = [
                d_operator(
                    ctx.unit_cell, ctx.gvec, ctx.aug, pot.bvec_g[i], ctx.beta,
                    include_dion=False, phases=ctx.phases,
                )
                for i in range(3)
            ]
        else:
            d0 = ctx.beta.dion
            db = [None, None, None]
        if so_data is not None:
            # SO: blocks built from the j-resolved f-coefficients
            # (Eq. 19 PhysRevB.71.115106; non_local_operator.cpp:110-200)
            dmat_blocks = so_data.d_blocks(np.asarray(d0), db)
            qmat_blocks = so_data.q_blocks()
        else:
            dmat_blocks = spin_blocks_from_components(d0, db[2], db[0], db[1])
            qmat_blocks = None
        v0 = float(np.real(pot.veff_g[0]))
        with profile("scf::band_solve"):
            ps = make_nc_set_params(
                ctx, pot.veff_boxes, dmat_blocks, qmat_blocks,
                dtype=wf_dtype, v0=v0, prev=ps,
            )
            rdt = real_dtype_of(wf_dtype)
            if pr is None or pr.dtype != np.dtype(rdt):
                src = psi if psi is not None else join_cplx(pr, pi)
                pr, pi = split_cplx(np.asarray(src), rdt)
            ev, pr, pi, rn, ran = davidson_kset_nc(
                ps, pr, pi,
                num_steps=itsol.num_steps,
                res_tol=res_tol,
                by_energy=bool(itsol.converge_by_energy),
            )
            psi = None
            evals = np.asarray(ev, dtype=np.float64)
            from sirius_tpu.solvers.davidson import count_solve

            # what the solve ran, fetched with its eigenvalues (a host
            # tail); a spinor row is two component boxes (ops/spinor.py)
            count_solve(counters, ran, nb, components=2)

        # --- occupations (spinor bands: max occupancy 1) ---
        mu, occ, entropy_sum = find_fermi(
            jnp.asarray(evals[:, None, :]),
            jnp.asarray(ctx.kweights),
            nel,
            p.smearing_width,
            kind=p.smearing,
            max_occupancy=1.0,
        )
        occ_np = np.asarray(occ)[:, 0, :]

        # --- 4-component density ---
        occ_w = jnp.asarray(occ_np * ctx.kweights[:, None])
        with profile("scf::density"):
            from sirius_tpu.dft.density import density_from_coarse_acc

            rho4 = np.asarray(density_kset_nc(ps, pr, pi, occ_w))
            # rho4 order: (rho, mz, mx, my) on the coarse box
            fields = density_from_coarse_acc(ctx, rho4)
        rho_new = fields[0]
        mvec_new = np.stack([fields[2], fields[3], fields[1]])  # (mx, my, mz)

        if ctx.aug is not None:
            dm_re, dm_im = density_matrix_kset_nc(
                *beta_dev, pr, pi, occ_w
            )
            dm3 = np.asarray(dm_re) + 1j * np.asarray(dm_im)
            if so_data is not None:
                dm3 = so_data.rotate_dm(dm3)
            if do_symmetrize:
                dm3 = symmetrize_density_matrix_nc(ctx, dm3)
            comp = _dm_component_blocks(ctx, dm3)
            blocks = list(ctx.beta.atom_blocks(ctx.unit_cell))

            def aug(mat):
                bl = [mat[off : off + nbf, off : off + nbf] for _, off, nbf in blocks]
                return rho_aug_g(ctx.unit_cell, ctx.gvec, ctx.aug, bl,
                                 phases=ctx.phases)

            rho_new = rho_new + aug(comp["rho"])
            mvec_new = mvec_new + np.stack(
                [aug(comp["mx"]), aug(comp["my"]), aug(comp["mz"])]
            )
        if cfg.control.verification >= 1:
            nel_got = float(np.real(rho_new[0]) * ctx.unit_cell.omega)
            if abs(nel_got - nel) > 1e-6 * max(1.0, nel):
                import warnings

                warnings.warn(
                    f"electron count from density {nel_got:.8f} != {nel:.8f}"
                )
        if do_symmetrize:
            rho_new = symmetrize_pw(ctx, rho_new)
            mvec_new = symmetrize_vector_pw(ctx, mvec_new)

        if not np.all(np.isfinite(evals)) or not np.isfinite(
            np.sum(np.abs(rho_new))
        ):
            bad = [
                name
                for name, a in [
                    ("evals", evals),
                    ("rho_new", rho_new),
                    ("mvec_new", mvec_new),
                    ("veff_in", np.asarray(pot.veff_boxes)),
                    ("bvec_in", np.asarray(pot.bvec_g)),
                    ("rho_in", rho_g),
                    ("mvec_in", mvec_g),
                ]
                if not np.all(np.isfinite(np.asarray(a)))
            ]
            raise FloatingPointError(
                f"non-collinear SCF diverged at iteration {it + 1}: "
                f"non-finite {bad}"
            )
        x_new = pack(rho_new, mvec_new)
        rms = mixer.rms(x_mix, x_new)
        x_mix = mixer.mix(x_mix, x_new)
        # use_hartree density bar = Hartree energy of (mixed - new), the
        # reference's convergence metric (dft_ground_state.cpp:251,353)
        eha_res = mixer.residual_hartree_energy(x_mix, x_new)
        dens_metric = (
            eha_res if (mixer.use_hartree and eha_res is not None) else rms
        )
        res_tol = schedule_res_tol(itsol, res_tol, dens_metric, nel,
                                   mixer.use_hartree and eha_res is not None)
        rho_g, mvec_g = unpack(x_mix)

        def _epot(r_out, m_out, p_):
            e = float(np.real(np.vdot(r_out, p_.veff_g))) * ctx.unit_cell.omega
            e += sum(
                float(np.real(np.vdot(m_out[i], p_.bvec_g[i])))
                * ctx.unit_cell.omega
                for i in range(3)
            )
            return e

        e1 = _epot(rho_new, mvec_new, pot)
        with profile("scf::potential"):
            pot = generate_potential_nc(ctx, rho_g, xc, mvec_g)
        scf_correction = (
            _epot(rho_new, mvec_new, pot) - e1 if p.use_scf_correction else 0.0
        )
        eval_sum = float(np.sum(ctx.kweights[:, None] * occ_np * evals))
        e = pot.energies
        e_total = (
            eval_sum - e["vxc"] - e["bxc"] - 0.5 * e["vha"] + e["exc"]
            + ctx.e_ewald + scf_correction
        )
        etot_history.append(e_total + float(entropy_sum))
        rms_history.append(rms)
        num_iter_done = it + 1
        de = abs(e_total - e_prev) if e_prev is not None else np.inf
        e_prev = e_total
        if de < p.energy_tol and dens_metric < p.density_tol:
            converged = True
            break

    # --- final report ---
    if psi is None:
        psi = join_cplx(pr, pi)
    from sirius_tpu.dft.density import atomic_moments_vec

    rho_r = rho_real_space(ctx, rho_g)
    e = pot.energies
    eval_sum = float(np.sum(ctx.kweights[:, None] * np.asarray(occ)[:, 0, :] * evals))
    e_total = (
        eval_sum - e["vxc"] - e["bxc"] - 0.5 * e["vha"] + e["exc"]
        + ctx.e_ewald + scf_correction
    )
    mom_atoms = atomic_moments_vec(ctx, mvec_g)
    # total moment: cell integral of m (G=0 term)
    mom_total = [float(np.real(mvec_g[i][0]) * ctx.unit_cell.omega) for i in range(3)]
    result = {
        "converged": bool(converged),
        "num_scf_iterations": num_iter_done,
        "rho_min": float(rho_r.min()),
        "etot_history": etot_history,
        "rms_history": rms_history,
        "scf_time": time.time() - t0,
        "energy": {
            "total": e_total,
            "free": e_total + float(entropy_sum),
            "eval_sum": eval_sum,
            "kin": eval_sum - e["veff"] - e["bxc"],
            "veff": e["veff"],
            "vha": e["vha"],
            "vxc": e["vxc"],
            "vloc": e["vloc"],
            "exc": e["exc"],
            "bxc": e["bxc"],
            "ewald": ctx.e_ewald,
            "entropy_sum": float(entropy_sum),
            "scf_correction": scf_correction,
        },
        "efermi": float(mu),
        "band_gap": 0.0,
        "magnetisation": {
            "total": mom_total,
            "atoms": [list(map(float, m)) for m in mom_atoms],
        },
        "timers": timer_report(),
    }
    return result
