"""Structural relaxation of atomic positions (reference: sirius.scf task
ground_state_relax driven by Force + the vcsqnm optimizer for variable-cell;
here fixed-cell BFGS over Cartesian positions using the analytic forces).

Each objective evaluation is a converged SCF; successive steps warm-start
from the previous step's wave functions and a delta-extrapolated density
(rho_prev - rho_atomic(old positions) + rho_atomic(new positions)). The
geometry-step plumbing (fixed-shape context rebuild, delta-density guess,
warm-start assembly) is shared with the MD driver via dft/geometry.py; the
fused step is compiled once a process (dft/fused.step_program), so every
step after the first reuses it. exec_cache, where the serving engine passes
its own, is handed on to run_scf for its books."""

from __future__ import annotations

import numpy as np


def relax_atoms(
    cfg,
    base_dir: str = ".",
    max_steps: int = 30,
    force_tol: float = 1e-4,
    ctx=None,
    exec_cache=None,
    devices=None,
) -> dict:
    import sirius_tpu.context as cm
    from sirius_tpu.dft.geometry import (
        context_at_positions,
        delta_density_guess,
        warm_start_state,
    )
    from sirius_tpu.dft.scf import run_scf

    cfg.control.print_forces = True
    if ctx is None:
        ctx = cm.SimulationContext.create(cfg, base_dir)
    uc0 = ctx.unit_cell
    lat = uc0.lattice
    pos = uc0.positions.copy()
    history = []
    res = None

    warm = {"state": None, "rho_at": None}

    def scf_at(positions):
        from sirius_tpu.dft.density import initial_density_g

        c = context_at_positions(cfg, base_dir, positions, uc0)
        rho_at = initial_density_g(c)
        state = warm["state"]
        if state is not None:
            # delta-density extrapolation across the geometry step
            # (QE-style): carry the bonding rearrangement, move the atomic
            # superposition with the nuclei
            state = warm_start_state(
                state,
                rho_g=delta_density_guess(
                    state["rho_g"], warm["rho_at"], rho_at
                ),
            )
        out = run_scf(
            cfg, ctx=c, initial_state=state, keep_state=True,
            exec_cache=exec_cache, devices=devices,
        )
        warm["state"] = out.get("_state")
        warm["rho_at"] = rho_at
        return out

    # simple BFGS on cartesian coordinates with analytic gradient
    x = (pos @ lat).ravel()
    n = x.size
    h_inv = np.eye(n) / 5.0  # initial inverse Hessian ~ optical phonon scale
    g_prev = None
    x_prev = None
    for step in range(max_steps):
        res = scf_at(np.linalg.solve(lat.T, x.reshape(-1, 3).T).T)
        f = np.asarray(res["forces"])
        g = -f.ravel()  # gradient of free energy
        fmax = float(np.abs(f).max())
        history.append({
            "step": step,
            "free": res["energy"]["free"],
            "fmax": fmax,
            "scf_iterations": int(res["num_scf_iterations"]),
        })
        if fmax < force_tol:
            break
        if g_prev is not None:
            s = x - x_prev
            y = g - g_prev
            sy = float(s @ y)
            if sy > 1e-12:
                hy = h_inv @ y
                h_inv = (
                    h_inv
                    + np.outer(s, s) * (sy + y @ hy) / sy**2
                    - (np.outer(hy, s) + np.outer(s, hy)) / sy
                )
        dx = -h_inv @ g
        # trust radius
        norm = np.linalg.norm(dx)
        if norm > 0.25:
            dx *= 0.25 / norm
        x_prev, g_prev = x.copy(), g.copy()
        x = x + dx
    return {
        "converged": history[-1]["fmax"] < force_tol if history else False,
        "num_steps": len(history),
        "history": history,
        "final_positions": np.mod(
            np.linalg.solve(lat.T, x.reshape(-1, 3).T).T, 1.0
        ).tolist(),
        "ground_state": res,
    }
