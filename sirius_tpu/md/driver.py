"""Born-Oppenheimer MD driver: converged SCF + analytic forces per step,
compile-once across the trajectory.

Each velocity-Verlet step evaluates forces by running the full SCF at the
new positions. Three pieces make the stepping cheap:

- the SimulationContext at every step is rebuilt at the displaced
  positions with identical array shapes (dft/geometry.py
  context_at_positions), so the fused SCF iteration and every module-jit
  helper hit their compiled executables — zero XLA recompiles after the
  first step (tracked via serve/cache.py's jax.monitoring listener);
- the fused step is a program of the process (dft/fused.step_program),
  found again by every run_scf call of the trajectory;
- the SCF warm-starts from ASPC-extrapolated density and subspace-aligned
  extrapolated wave functions (md/extrapolate.py), which cuts the
  iterations per step severalfold against the superposition-of-atoms cold
  start.

Restart: every md.autosave_every steps the driver checkpoints a /md group
(io/checkpoint.py) holding step counter, positions, velocities, forces,
thermostat work and the extrapolation histories. Thermostat noise is
counter-based in (seed, step), so a resumed trajectory replays the exact
noise sequence of the uninterrupted run — resume equality is a test, not
a hope (tests/test_md_driver.py).
"""

from __future__ import annotations

import json
import os
import sys
import time
import warnings

import numpy as np

from sirius_tpu.md.extrapolate import AspcExtrapolator, SubspaceExtrapolator
from sirius_tpu.obs import events as obs_events
from sirius_tpu.obs import metrics as obs_metrics
from sirius_tpu.obs import spans as obs_spans
from sirius_tpu.obs import tracing as obs_tracing
from sirius_tpu.obs.log import get_logger, job_context
from sirius_tpu.runtime import PLATFORMS, enable_compile_cache, select_platform

logger = get_logger("md")

_STEPS = obs_metrics.REGISTRY.counter(
    "md_steps_total", "MD steps integrated")
_STEP_SECONDS = obs_metrics.REGISTRY.histogram(
    "md_step_seconds", "wall time per MD step")
_SCF_PER_STEP = obs_metrics.REGISTRY.histogram(
    "md_scf_iterations_per_step", "SCF iterations each MD step needed",
    buckets=(1, 2, 3, 5, 8, 12, 20, 40, 80))
_DRIFT = obs_metrics.REGISTRY.gauge(
    "md_conserved_drift_ha", "conserved-quantity drift from step 0")
_XERR = obs_metrics.REGISTRY.gauge(
    "md_extrapolation_rel_error", "relative ASPC density prediction error")
from sirius_tpu.md.integrator import (
    BOHR_TO_ANG,
    FS_TO_AU,
    HA_TO_EV,
    ConservedTracker,
    Thermostat,
    kinetic_energy,
    masses_au,
    maxwell_boltzmann_velocities,
    temperature_k,
    velocity_verlet_step,
)

# 1 Ha/bohr^3 in GPa (for the optional per-step pressure report)
HA_BOHR3_TO_GPA = 29421.02648438959


def default_md_autosave_path(cfg, base_dir: str) -> str:
    """MD restart checkpoint location: control.autosave_path when set,
    else <base_dir>/sirius_md_autosave[.tag].h5 (job-scoped like the SCF
    autosave so shared workdirs do not clobber)."""
    explicit = str(getattr(cfg.control, "autosave_path", "") or "")
    if explicit:
        return explicit
    tag = str(getattr(cfg.control, "autosave_tag", "") or "")
    name = f"sirius_md_autosave.{tag}.h5" if tag else "sirius_md_autosave.h5"
    return os.path.join(base_dir, name)


def _orthonormalize(psi: np.ndarray) -> np.ndarray:
    """Per-(k, spin) QR re-orthonormalization of an extrapolated psi: the
    linear combination of orthonormal history members is only approximately
    orthonormal, and the band solver expects a proper frame. Masked G rows
    are zero in every history member, so they stay zero."""
    out = np.empty_like(psi)
    nk, ns = psi.shape[:2]
    for ik in range(nk):
        for ispn in range(ns):
            q, _ = np.linalg.qr(psi[ik, ispn].T)
            out[ik, ispn] = q.T
    return out


def _write_xyz_frame(fh, ctx, r_cart, velocities, forces, step, e_pot_ha):
    """Append one extended-XYZ frame (ase-compatible): positions [Å],
    velocities [Å/fs], forces [eV/Å], energy [eV]."""
    uc = ctx.unit_cell
    lat = (uc.lattice * BOHR_TO_ANG).reshape(-1)
    syms = [uc.atom_types[t].symbol for t in uc.type_of_atom]
    fh.write(f"{uc.num_atoms}\n")
    fh.write(
        'Lattice="' + " ".join(f"{x:.10f}" for x in lat) + '" '
        "Properties=species:S:1:pos:R:3:vel:R:3:forces:R:3 "
        f"energy={e_pot_ha * HA_TO_EV:.10f} step={step} pbc=\"T T T\"\n"
    )
    pos = r_cart * BOHR_TO_ANG
    vel = velocities * BOHR_TO_ANG * FS_TO_AU  # bohr/a.u. -> Å/fs
    frc = forces * (HA_TO_EV / BOHR_TO_ANG)
    for i, s in enumerate(syms):
        fh.write(
            f"{s:2s} "
            + " ".join(f"{x: .10f}" for x in pos[i])
            + " " + " ".join(f"{x: .10f}" for x in vel[i])
            + " " + " ".join(f"{x: .10f}" for x in frc[i])
            + "\n"
        )
    fh.flush()


def run_md(*args, **kwargs) -> dict:
    """Trace-context front door (see _run_md_impl): one trace for the
    whole trajectory — every md_step and inner SCF span shares it, so a
    timeline export reconstructs the full MD run, and an ambient trace
    (serve/campaigns) is continued rather than forked."""
    with obs_tracing.ensure_trace():
        return _run_md_impl(*args, **kwargs)


def _run_md_impl(
    cfg,
    base_dir: str = ".",
    ctx=None,
    exec_cache=None,
    resume: str | None = None,
) -> dict:
    """Run cfg.md.num_steps of Born-Oppenheimer MD; returns the per-step
    records, conserved-quantity drift, SCF cost and recompile statistics.

    resume: path to a /md checkpoint (default_md_autosave_path) — continues
    the trajectory from the saved step, replaying the uninterrupted run.
    exec_cache: the serving engine's serve.cache.ExecutableCache, handed on
    to run_scf for its books (the fused step is compiled once a process
    with or without one, dft/fused.step_program)."""
    from sirius_tpu.dft.geometry import context_at_positions, warm_start_state
    from sirius_tpu.dft.scf import run_scf
    from sirius_tpu.io.checkpoint import load_state, save_state
    from sirius_tpu.serve.cache import (
        backend_compiles_total,
        install_compile_listener,
    )
    from sirius_tpu.utils import faults

    md = cfg.md
    if md.num_steps < 1:
        raise ValueError(f"md.num_steps must be >= 1, got {md.num_steps}")
    if md.dt_fs <= 0.0:
        raise ValueError(f"md.dt_fs must be positive, got {md.dt_fs}")
    # forces every step are the point of BOMD; stress only when asked
    cfg.control.print_forces = True
    if md.compute_stress:
        cfg.control.print_stress = True
    # the MD driver owns checkpointing; a mid-SCF autosave inside each step
    # would clobber the trajectory file with single-step state
    cfg.control.autosave_every = 0

    install_compile_listener()
    if ctx is None:
        # honours the species-file-free "synthetic" deck section the same
        # way sirius-serve does; plain decks fall through to
        # SimulationContext.create. The lattice does not move in a run, so
        # every later step's context (dft/geometry.context_at_positions)
        # finds this one's G-vector sets, k-spheres and species tables in
        # the process's memo and builds only what reads the positions
        from sirius_tpu.serve.scheduler import build_job_context

        ctx = build_job_context(cfg, base_dir)
    uc0 = ctx.unit_cell
    natoms = uc0.num_atoms
    if natoms < 1:
        raise ValueError("MD needs at least one atom")
    lattice = np.asarray(uc0.lattice, dtype=np.float64)
    lat_inv = np.linalg.inv(lattice)
    masses = masses_au(uc0)
    dt = md.dt_fs * FS_TO_AU

    thermostat = Thermostat(
        ensemble=md.ensemble,
        temperature=md.temperature_k,
        tau_fs=md.thermostat_tau_fs,
        seed=md.seed,
        remove_com=md.remove_com,
    )
    tracker = ConservedTracker(natoms)
    rho_x = AspcExtrapolator(md.extrapolation_order, md.extrapolation_kind)
    psi_x = SubspaceExtrapolator(
        md.extrapolation_order if md.extrapolate_psi else 0,
        md.extrapolation_kind,
    )

    autosave_path = default_md_autosave_path(cfg, base_dir)
    compiles_start = backend_compiles_total()
    scf_iters: list[int] = []
    carry = {"state": None}  # previous step's converged _state (mag/PAW ride)

    def evaluate(r_cart, step_index):
        """SCF + forces at cartesian positions; the force_fn of the
        integrator. Warm-starts from the extrapolators, falls back to a
        cold superposition-of-atoms start when the warm SCF fails."""
        frac = r_cart @ lat_inv
        ctx_step = context_at_positions(cfg, base_dir, frac, uc0)
        if md.extrapolation_kind == "off":
            # true A/B baseline: superposition-of-atoms cold start every
            # step, no carry-over at all (tools/bench_md.py measures the
            # extrapolation payoff against exactly this)
            init = None
        else:
            with obs_spans.span("md.extrapolate", step=step_index):
                rho_pred = rho_x.predict()
                psi_pred = psi_x.predict()
                if psi_pred is not None:
                    psi_pred = _orthonormalize(psi_pred)
                init = warm_start_state(
                    carry["state"], rho_g=rho_pred, psi=psi_pred
                )
        with obs_spans.span("md.scf", step=step_index, warm=init is not None):
            res = run_scf(
                cfg, base_dir, ctx=ctx_step, initial_state=init,
                keep_state=True, exec_cache=exec_cache,
            )
        if not res.get("converged", False) and init is not None:
            # MD-level recovery ladder rung: the extrapolated guess can be
            # poisoned after an SCF-level recovery event; one cold retry
            warnings.warn(
                f"MD step {step_index}: warm-started SCF did not converge; "
                "retrying from the atomic superposition"
            )
            with obs_spans.span("md.scf", step=step_index, warm=False):
                res = run_scf(
                    cfg, base_dir, ctx=ctx_step, keep_state=True,
                    exec_cache=exec_cache,
                )
        if not res.get("converged", False):
            warnings.warn(
                f"MD step {step_index}: SCF unconverged after cold retry; "
                "continuing with the last iterate's forces"
            )
        state = res["_state"]
        carry["state"] = state
        xerr = None
        if init is not None and init.get("rho_g") is not None:
            # how good was the predictor? relative L2 distance between the
            # extrapolated density and the converged one
            rho_conv = np.asarray(state["rho_g"])
            dnorm = np.linalg.norm(rho_conv)
            if dnorm > 0:
                xerr = float(
                    np.linalg.norm(np.asarray(init["rho_g"]) - rho_conv)
                    / dnorm)
                _XERR.set(xerr)
        rho_x.push(state["rho_g"])
        psi_x.push(state["psi"])
        f = np.asarray(res["forces"], dtype=np.float64)
        e_pot = float(res["energy"]["free"])
        extra = {
            "scf_iterations": int(res["num_scf_iterations"]),
            "converged": bool(res.get("converged", False)),
            "recovery": res.get("recovery"),
            "extrapolation_error": xerr,
        }
        if md.compute_stress and "stress" in res:
            s = np.asarray(res["stress"], dtype=np.float64)
            extra["stress"] = s
            extra["pressure_gpa"] = float(-np.trace(s) / 3.0 * HA_BOHR3_TO_GPA)
        scf_iters.append(extra["scf_iterations"])
        return f, e_pot, extra

    step0 = 0
    if resume:
        saved = load_state(resume, ctx)
        mdres = saved.get("md")
        if mdres is None:
            raise ValueError(
                f"checkpoint '{resume}' has no /md group (not an MD "
                "restart file, or the G set changed since it was written)"
            )
        step0 = int(mdres["step"])
        r_cart = np.asarray(mdres["positions_cart"], dtype=np.float64)
        velocities = np.asarray(mdres["velocities"], dtype=np.float64)
        f_cur = np.asarray(mdres["forces"], dtype=np.float64)
        e_pot = float(mdres["e_pot"])
        tracker.restore(mdres)
        rho_x.restore(mdres.get("rho_history"))
        psi_x.restore(mdres.get("psi_history"))
        carry["state"] = {
            "rho_g": np.asarray(saved["rho_g"]),
            "mag_g": saved.get("mag_g"),
            "psi": np.asarray(saved["psi"]) if "psi" in saved else None,
            "paw_dm": saved.get("paw_dm"),
        }
    else:
        r_cart = np.asarray(uc0.positions, dtype=np.float64) @ lattice
        velocities = maxwell_boltzmann_velocities(
            masses, md.temperature_k, seed=md.seed, remove_com=md.remove_com
        )
        f_cur, e_pot, _ = evaluate(r_cart, step_index=0)

    records: list[dict] = []
    traj_fh = None
    if md.trajectory_path:
        tpath = md.trajectory_path
        if not os.path.isabs(tpath):
            tpath = os.path.join(base_dir, tpath)
        traj_fh = open(tpath, "a" if resume else "w")
        if not resume:
            _write_xyz_frame(
                traj_fh, ctx, r_cart, velocities, f_cur, 0, e_pot
            )
    compiles_after_first = None
    t_start = time.time()

    def checkpoint(step_done):
        md_state = {
            "step": step_done,
            "positions_cart": r_cart,
            "velocities": velocities,
            "forces": f_cur,
            "e_pot": e_pot,
            "seed": md.seed,
            "dt_fs": md.dt_fs,
            "ensemble": md.ensemble,
        }
        md_state.update(tracker.export())
        rh, ph = rho_x.export(), psi_x.export()
        if rh is not None:
            md_state["rho_history"] = rh
        if ph is not None:
            md_state["psi_history"] = ph
        state = carry["state"] or {}
        save_state(
            autosave_path, ctx,
            rho_g=np.asarray(state.get("rho_g")),
            mag_g=state.get("mag_g"),
            psi=state.get("psi"),
            paw_dm=state.get("paw_dm"),
            md_state=md_state,
        )
        obs_events.emit("checkpoint", step=step_done, path=autosave_path,
                        scope="md")
        # simulate preemption right after the durable checkpoint: the
        # resumed trajectory must replay the uninterrupted one
        faults.check("md.autosave_kill", step_done)

    try:
        if not resume:
            tracker.record(kinetic_energy(velocities, masses), e_pot)
        for step in range(step0, md.num_steps):
            n0 = backend_compiles_total()
            t_step0 = time.time()
            with job_context(step=step + 1):
                # md.integrate parents the md.extrapolate / md.scf spans
                # fired from the evaluate() force callback
                with obs_spans.span("md.integrate", step=step + 1):
                    r_cart, velocities, f_cur, e_pot, extra = (
                        velocity_verlet_step(
                            r_cart, velocities, f_cur, masses, dt, thermostat,
                            step, lambda r: evaluate(r, step_index=step + 1),
                            tracker,
                        ))
            e_kin = kinetic_energy(velocities, masses)
            e_cons = tracker.record(e_kin, e_pot)
            rec = {
                "step": step + 1,
                "time_fs": (step + 1) * md.dt_fs,
                "e_pot": e_pot,
                "e_kin": e_kin,
                "e_cons": e_cons,
                "temperature_k": temperature_k(
                    velocities, masses, md.remove_com
                ),
                "scf_iterations": extra["scf_iterations"],
                "converged": extra["converged"],
                "backend_compiles": backend_compiles_total() - n0,
            }
            if "pressure_gpa" in extra:
                rec["pressure_gpa"] = extra["pressure_gpa"]
            records.append(rec)
            _STEPS.inc()
            _STEP_SECONDS.observe(time.time() - t_step0)
            _SCF_PER_STEP.observe(rec["scf_iterations"])
            drift_now = tracker.drift()
            _DRIFT.set(drift_now["max_abs"])
            obs_events.emit(
                "md_step", **rec, drift=drift_now["max_abs"],
                dt=time.time() - t_step0,
                extrapolation_error=extra.get("extrapolation_error"),
            )
            if step == step0:
                compiles_after_first = backend_compiles_total()
            if traj_fh is not None:
                _write_xyz_frame(
                    traj_fh, ctx, r_cart, velocities, f_cur, step + 1, e_pot
                )
            if md.autosave_every > 0 and (step + 1) % md.autosave_every == 0:
                checkpoint(step + 1)
    finally:
        if traj_fh is not None:
            traj_fh.close()

    elapsed = time.time() - t_start
    steps_run = md.num_steps - step0
    return {
        "records": records,
        "num_steps": md.num_steps,
        "steps_run": steps_run,
        "dt_fs": md.dt_fs,
        "ensemble": md.ensemble,
        "positions_cart": r_cart.tolist(),
        "positions_frac": (r_cart @ lat_inv).tolist(),
        "velocities": velocities.tolist(),
        "forces": f_cur.tolist(),
        "drift": tracker.drift(),
        "scf_iterations": scf_iters,
        "mean_scf_iterations": (
            float(np.mean(scf_iters)) if scf_iters else 0.0
        ),
        "backend_compiles_total": backend_compiles_total() - compiles_start,
        "backend_compiles_after_first_step": (
            backend_compiles_total() - compiles_after_first
            if compiles_after_first is not None
            else 0
        ),
        "steps_per_minute": (
            60.0 * steps_run / elapsed if elapsed > 0 else 0.0
        ),
        "elapsed_s": elapsed,
        "exec_cache": None if exec_cache is None else exec_cache.stats(),
        "autosave_path": autosave_path,
    }


def run_md_from_file(path: str, resume: str | None = None) -> int:
    """CLI entry body: load the deck, run the trajectory, write
    md_output.json next to the working directory and print a per-step
    summary line (the sirius-scf output.json convention)."""
    from sirius_tpu.config import load_config

    cfg = load_config(path)
    base_dir = os.path.dirname(os.path.abspath(path))
    if resume == "auto":
        from sirius_tpu.io.checkpoint import find_resumable

        resume = find_resumable(default_md_autosave_path(cfg, base_dir))
        if resume:
            logger.warning("resuming MD from %s", resume)
    result = run_md(cfg, base_dir, resume=resume)
    for rec in result["records"]:
        print(
            f"step {rec['step']:5d}  t={rec['time_fs']:9.3f} fs  "
            f"E_pot={rec['e_pot']:.10f} Ha  T={rec['temperature_k']:8.2f} K  "
            f"E_cons={rec['e_cons']:.10f} Ha  "
            f"scf_iters={rec['scf_iterations']}"
        )
    d = result["drift"]
    print(
        f"conserved-quantity drift: {d['max_abs']:.3e} Ha "
        f"({d['max_abs_per_atom']:.3e} Ha/atom); "
        f"mean SCF iterations/step: {result['mean_scf_iterations']:.2f}; "
        f"backend compiles after first step: "
        f"{result['backend_compiles_after_first_step']}"
    )
    with open("md_output.json", "w") as f:
        json.dump(result, f, indent=2, default=float)
    return 0


def main(argv: list[str] | None = None) -> int:
    """sirius-md mini-app (pyproject [project.scripts])."""
    import argparse

    p = argparse.ArgumentParser(
        prog="sirius-md",
        description="Born-Oppenheimer molecular dynamics on the "
        "TPU-native SCF engine (sirius_tpu.md)",
    )
    p.add_argument("input", nargs="?", default="sirius.json",
                   help="JSON input file with an 'md' section")
    p.add_argument(
        "--resume", default=None, metavar="PATH|auto",
        help="resume from an /md checkpoint; 'auto' probes the default "
        "autosave path",
    )
    p.add_argument(
        "--platform", default=None, choices=list(PLATFORMS),
        help="JAX platform (same semantics as sirius-scf)",
    )
    p.add_argument("-v", "--verbose", action="count", default=0,
                   help="raise log level (-v info, -vv debug)")
    args = p.parse_args(argv)
    if not os.path.isfile(args.input):
        print(f"sirius-md: input file not found: {args.input}",
              file=sys.stderr)
        return 2
    from sirius_tpu.obs.log import setup as _log_setup

    _log_setup(args.verbose)
    platform = args.platform
    if platform is None:
        try:
            with open(args.input) as f:
                if (json.load(f).get("control", {})
                        .get("processing_unit") == "cpu"):
                    platform = "cpu"
        except (OSError, json.JSONDecodeError):
            pass
    select_platform(platform)
    enable_compile_cache()
    return run_md_from_file(args.input, resume=args.resume)


if __name__ == "__main__":
    raise SystemExit(main())
