"""Host-driven per-step SCF flow (the QE embedding contract, SURVEY §3.5).

The reference's C API lets the host own the SCF loop: it calls
sirius_find_eigen_states, reads band energies, sets occupancies (or asks
for them), calls sirius_generate_density, pulls rho with
sirius_get_pw_coeffs, MIXES ON THE HOST, pushes the mixed density (or
effective potential) back with sirius_set_pw_coeffs, regenerates the
potential, repeats (src/api/sirius_api.cpp: sirius_find_eigen_states,
sirius_generate_density, sirius_generate_effective_potential,
sirius_set/get_pw_coeffs, sirius_get_wave_functions).

GroundStateStepper is that flow's engine over the jax core: it exposes the
same primitives as separate calls on persistent state. run_scf remains the
single-shot driver; the stepper reuses the identical building blocks
(d_operator, batched davidson_kset, find_fermi, density accumulation,
generate_potential), so a host-driven loop converges to the same ground
state.

Scope: PP-PW norm-conserving/ultrasoft/PAW, unpolarized or collinear.
Hubbard and non-collinear flows stay in run_scf for now.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from sirius_tpu.config.schema import Config
from sirius_tpu.context import SimulationContext
from sirius_tpu.dft.density import (
    initial_density_g,
    initial_magnetization_g,
    symmetrize_density_matrix,
    symmetrize_pw,
)
from sirius_tpu.dft.occupation import find_fermi
from sirius_tpu.dft.potential import generate_potential
from sirius_tpu.dft.xc import XCFunctional
from sirius_tpu.ops.augmentation import d_operator, rho_aug_g


class GroundStateStepper:
    def __init__(self, cfg: Config, base_dir: str = ".", ctx=None):
        p = cfg.parameters
        if p.electronic_structure_method != "pseudopotential":
            raise NotImplementedError("stepper drives the PP-PW method only")
        self.cfg = cfg
        self.ctx = ctx if ctx is not None else SimulationContext.create(cfg, base_dir)
        if self.ctx.num_mag_dims == 3:
            raise NotImplementedError("stepper: collinear/unpolarized only")
        if cfg.hubbard.local:
            raise NotImplementedError("stepper: Hubbard not wired yet")
        self.xc = XCFunctional(p.xc_functionals)
        self.polarized = self.ctx.num_mag_dims == 1
        self.ns = self.ctx.num_spins
        self.nb = self.ctx.num_bands
        self.nk = self.ctx.gkvec.num_kpoints

        from sirius_tpu.dft import paw as paw_mod

        self._paw_mod = paw_mod
        self.paw = paw_mod.PawData.build(self.ctx)
        self.paw_dm = self.paw.initial_dm(self.ctx) if self.paw else None

        self.rho_g = initial_density_g(self.ctx)
        self.mag_g = initial_magnetization_g(self.ctx) if self.polarized else None
        self.pot = None
        self.evals = None
        self.occ = None
        self.efermi = 0.0
        self.entropy_sum = 0.0
        self.rho_out_g = None  # output (unmixed) density of the last
        self.mag_out_g = None  # generate_density call
        self._pr = self._pi = None  # device-resident wave functions
        self._psi_big = None
        self._kset_cache = {}
        self._paw_res = None
        self._e_paw_one_el = 0.0
        self.generate_effective_potential()

    # --- potential ---------------------------------------------------

    def generate_effective_potential(self):
        """Potential from the CURRENT input density (after the host pushed
        a mixed rho via set_pw_coeffs). Reference
        sirius_generate_effective_potential."""
        if self.paw is not None:
            self._paw_res = self._paw_mod.compute_paw(
                self.paw, self.paw_dm, self.xc
            )
            self._e_paw_one_el = self._paw_mod.one_elec_energy(
                self.paw, self.paw_dm, self._paw_res["dij_atoms"]
            )
        self.pot = generate_potential(self.ctx, self.rho_g, self.xc, self.mag_g)

    # --- band solve ---------------------------------------------------

    def _d_by_spin(self):
        ctx = self.ctx
        out = []
        for ispn in range(self.ns):
            if ctx.aug is not None:
                vs = self.pot.veff_g + (
                    (self.pot.bz_g if ispn == 0 else -self.pot.bz_g)
                    if self.polarized
                    else 0.0
                )
                out.append(d_operator(ctx.unit_cell, ctx.gvec, ctx.aug, vs,
                                      ctx.beta, phases=ctx.phases))
            else:
                out.append(ctx.beta.dion)
        if self.paw is not None:
            out = self._paw_mod.add_dij_to_d(
                self.paw, self._paw_res["dij_atoms"], out
            )
        return out

    def find_eigen_states(self, num_steps: int | None = None):
        """One band solve with the current potential (reference
        sirius_find_eigen_states). Warm-starts from the previous call."""
        from sirius_tpu.dft.scf import _initial_subspace
        from sirius_tpu.parallel.batched import (
            davidson_kset,
            initialize_subspace_kset,
            make_hkset_params,
            split_cplx,
        )

        ctx = self.ctx
        itsol = self.cfg.iterative_solver
        steps = itsol.num_steps if num_steps is None else num_steps
        v0 = float(np.real(self.pot.veff_g[0]))
        ps = make_hkset_params(
            ctx, self.pot.veff_r_coarse[: self.ns],
            np.stack(self._d_by_spin()), dtype=jnp.complex128, v0=v0,
        )
        self._ps = ps
        if self._pr is None:
            if self._psi_big is None:
                self._psi_big = _initial_subspace(ctx)
            pb_re, pb_im = split_cplx(self._psi_big, np.float64)
            self._pr, self._pi = initialize_subspace_kset(
                ps, jnp.asarray(pb_re), jnp.asarray(pb_im), self.nb
            )
            self._psi_big = None
        ev, self._pr, self._pi, rn, _ = davidson_kset(
            ps, self._pr, self._pi,
            num_steps=steps, res_tol=itsol.residual_tolerance,
            by_energy=False,
        )
        self.evals = np.asarray(ev, dtype=np.float64)
        return self.evals

    # --- occupations --------------------------------------------------

    def find_band_occupancies(self):
        p = self.cfg.parameters
        nel = self.ctx.unit_cell.num_valence_electrons - p.extra_charge
        mu, occ, ent = find_fermi(
            jnp.asarray(self.evals), jnp.asarray(self.ctx.kweights), nel,
            p.smearing_width, kind=p.smearing,
            max_occupancy=self.ctx.max_occupancy,
        )
        self.efermi = float(mu)
        self.occ = np.asarray(occ)
        self.entropy_sum = float(ent)
        return self.occ

    def get_band_energies(self, ik: int, ispn: int) -> np.ndarray:
        return np.asarray(self.evals[ik, ispn])

    def set_band_occupancies(self, ik: int, ispn: int, occ) -> None:
        if self.occ is None:
            self.occ = np.zeros((self.nk, self.ns, self.nb))
        self.occ[ik, ispn] = np.asarray(occ)

    def get_wave_functions(self, ik: int, ispn: int) -> np.ndarray:
        """[nb, ngk_max] PW coefficients (valid part padded with zeros)."""
        from sirius_tpu.parallel.batched import join_cplx

        # join only the requested slice — the full k-set array is the
        # largest object of the run
        return join_cplx(self._pr[ik, ispn], self._pi[ik, ispn])

    # --- density ------------------------------------------------------

    def generate_density(self):
        """Output density from the current (psi, occ) — NOT mixed into the
        input density; the host owns mixing (reference
        sirius_generate_density + host-side mixer)."""
        from sirius_tpu.dft.density import density_from_coarse_acc
        from sirius_tpu.parallel.batched import (
            density_kset,
            density_matrix_kset,
            join_cplx,
            split_cplx,
        )

        ctx = self.ctx
        occ_w = jnp.asarray(self.occ * ctx.kweights[:, None, None])
        rho_spin = density_from_coarse_acc(
            ctx, np.asarray(density_kset(self._ps, self._pr, self._pi, occ_w))
        )
        if ctx.aug is not None:
            if ctx.beta.num_beta_total:
                bre, bim = split_cplx(np.asarray(ctx.beta.beta_gk))
                dm_re, dm_im = density_matrix_kset(
                    jnp.asarray(bre), jnp.asarray(bim), self._pr, self._pi, occ_w
                )
                dm = join_cplx(dm_re, dm_im)
                if self._do_sym():
                    dm = symmetrize_density_matrix(ctx, dm)
                for ispn in range(self.ns):
                    blocks = [
                        dm[ispn, off : off + nbf, off : off + nbf]
                        for _, off, nbf in ctx.beta.atom_blocks(ctx.unit_cell)
                    ]
                    rho_spin[ispn] += rho_aug_g(
                        ctx.unit_cell, ctx.gvec, ctx.aug, blocks,
                        phases=ctx.phases,
                    )
                if self.paw is not None:
                    self.paw_dm = self.paw.dm_from_density_matrix(dm)
        rho_new = rho_spin.sum(axis=0)
        mag_new = rho_spin[0] - rho_spin[1] if self.polarized else None
        if self._do_sym():
            rho_new = symmetrize_pw(self.ctx, rho_new)
            if self.polarized:
                mag_new = symmetrize_pw(self.ctx, mag_new, axial_z=True)
        self.rho_out_g = rho_new
        self.mag_out_g = mag_new
        return rho_new

    def _do_sym(self) -> bool:
        return (
            self.cfg.parameters.use_symmetry
            and self.ctx.symmetry is not None
            and self.ctx.symmetry.num_ops > 1
        )

    # --- data exchange (reference sirius_set/get_pw_coeffs) -----------

    def get_pw_coeffs(self, label: str) -> np.ndarray:
        out = {
            "rho": self.rho_g,
            "rho_out": self.rho_out_g,
            "magz": self.mag_g,
            "magz_out": self.mag_out_g,
            "veff": None if self.pot is None else self.pot.veff_g,
            "vha": None if self.pot is None else self.pot.vha_g,
            "vxc": None if self.pot is None else self.pot.vxc_g,
        }.get(label)
        if out is None:
            raise KeyError(f"unknown/unset pw field '{label}'")
        return out

    def set_pw_coeffs(self, label: str, values: np.ndarray) -> None:
        v = np.asarray(values, dtype=np.complex128)
        if v.shape != (self.ctx.gvec.num_gvec,):
            raise ValueError(
                f"expected {self.ctx.gvec.num_gvec} PW coefficients, got {v.shape}"
            )
        if label == "rho":
            self.rho_g = v
        elif label == "magz":
            self.mag_g = v
        else:
            raise KeyError(f"set_pw_coeffs supports 'rho'/'magz', not '{label}'")

    # --- energy -------------------------------------------------------

    def total_energy(self) -> dict:
        """Energy terms from the current (evals, occ, pot) — the same
        assembly as run_scf's report (valid once the band solve used the
        potential generated from the current input density)."""
        e = self.pot.energies
        eval_sum = float(
            np.sum(self.ctx.kweights[:, None, None] * self.occ * self.evals)
        )
        e_total = (
            eval_sum - e["vxc"] - e["bxc"] - 0.5 * e["vha"] + e["exc"]
            + self.ctx.e_ewald
            + (
                self._paw_res["e_total"] - self._e_paw_one_el
                if self.paw is not None
                else 0.0
            )
        )
        return {
            "total": e_total,
            "free": e_total + self.entropy_sum,
            "eval_sum": eval_sum,
            "entropy_sum": self.entropy_sum,
            "kin": eval_sum - e["veff"] - e["bxc"],
            "scf_correction": 0.0,  # the host owns mixing in this flow
            **{k: e[k] for k in ("vha", "vxc", "exc", "bxc", "veff", "vloc")},
            "ewald": self.ctx.e_ewald,
        }

    # --- real-space grid exchange (reference sirius_set/get_rg_values) --

    def rg_dims(self) -> tuple:
        return tuple(self.ctx.gvec.fft.dims)

    def get_rg_values(self, label: str) -> np.ndarray:
        """Field values on the FULL fine real-space box [n1, n2, n3]."""
        from sirius_tpu.core.fftgrid import g_to_r
        import jax.numpy as jnp

        f_g = self.get_pw_coeffs(label)
        box = g_to_r(
            jnp.asarray(f_g), jnp.asarray(self.ctx.gvec.fft_index),
            self.ctx.gvec.fft.dims,
        )
        return np.real(np.asarray(box))

    def set_rg_values(self, label: str, values: np.ndarray) -> None:
        from sirius_tpu.core.fftgrid import r_to_g
        import jax.numpy as jnp

        v = np.asarray(values, dtype=np.float64)
        if v.shape != tuple(self.ctx.gvec.fft.dims):
            raise ValueError(
                f"expected box {self.ctx.gvec.fft.dims}, got {v.shape}"
            )
        f_g = np.asarray(
            r_to_g(
                jnp.asarray(v, dtype=jnp.complex128),
                jnp.asarray(self.ctx.gvec.fft_index), self.ctx.gvec.fft.dims,
            )
        )
        self.set_pw_coeffs(label, f_g)

    # --- checkpointing (reference sirius_save_state/load_state) ---------

    def save_state(self, path: str) -> None:
        from sirius_tpu.io.checkpoint import save_state as _save

        from sirius_tpu.parallel.batched import join_cplx

        psi = None if self._pr is None else join_cplx(self._pr, self._pi)
        _save(
            path, self.ctx,
            rho_g=self.rho_g, mag_g=self.mag_g,
            psi=psi, band_energies=self.evals,
            band_occupancies=self.occ, paw_dm=self.paw_dm,
        )

    def load_state(self, path: str) -> None:
        from sirius_tpu.io.checkpoint import load_state as _load

        st = _load(path, self.ctx)
        self.rho_g = np.asarray(st["rho_g"])
        if self.polarized and st.get("mag_g") is not None:
            self.mag_g = np.asarray(st["mag_g"])
        if st.get("psi") is not None:
            from sirius_tpu.parallel.batched import split_cplx

            pr, pi = split_cplx(np.asarray(st["psi"]), np.float64)
            self._pr, self._pi = jnp.asarray(pr), jnp.asarray(pi)
        if st.get("band_energies") is not None:
            self.evals = np.asarray(st["band_energies"])
        if st.get("band_occupancies") is not None:
            self.occ = np.asarray(st["band_occupancies"])
        if self.paw is not None and st.get("paw_dm") is not None:
            self.paw_dm = np.asarray(st["paw_dm"])

    # --- Sternheimer solve for a QE-driven DFPT loop (reference
    # sirius_linear_solver, backed by solvers/multi_cg) ------------------

    def linear_solver(self, vkq, psi, eigvals, dvpsi, alpha_pv: float = 0.0,
                      spin: int = 1, tol: float = 1e-8) -> np.ndarray:
        """Solve (H - eps_n S + alpha_pv P_occ) |dpsi_n> = -|dvpsi_n>.

        psi/dvpsi: [ngk, n] column vectors at this k (the host's layout);
        returns dpsi with the same shape. Single-k embedding: vkq must
        match one of the context's k-points."""
        from sirius_tpu.dft.linear_response import solve_sternheimer_k
        from sirius_tpu.ops.hamiltonian import apply_h_s, make_hk_params

        ctx = self.ctx
        kpts = np.asarray(ctx.gkvec.kpoints)
        ik = int(np.argmin(np.sum((kpts - np.asarray(vkq)) ** 2, axis=1)))
        ispn = max(0, int(spin) - 1)
        if self.pot is None:
            self.generate_effective_potential()
        d = self._d_by_spin()[ispn]
        prm = make_hk_params(ctx, ik, self.pot.veff_r_coarse[ispn], d)
        ngk_max = ctx.gkvec.ngk_max
        # host arrays are [n, ngk_host]; pad/crop to the context's ngk_max
        psi_rows = np.zeros((psi.shape[1], ngk_max), dtype=np.complex128)
        dv_rows = np.zeros_like(psi_rows)
        ncp = min(psi.shape[0], ngk_max)
        psi_rows[:, :ncp] = np.asarray(psi).T[:, :ncp]
        dv_rows[:, :ncp] = np.asarray(dvpsi).T[:, :ncp]
        dpsi, _niter, _res = solve_sternheimer_k(
            apply_h_s, prm, psi_rows, np.asarray(eigvals), dv_rows,
            alpha_pv=alpha_pv, tol=tol,
        )
        out = np.zeros((psi.shape[0], psi.shape[1]), dtype=np.complex128)
        out[:ncp, :] = np.asarray(dpsi).T[:ncp, :]
        return out
