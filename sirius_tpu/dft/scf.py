"""SCF ground-state driver (reference: src/dft/dft_ground_state.cpp find
:178-427 and the sirius.scf mini-app output JSON).

Orchestration is host python; the hot pieces (per-k solver, density
accumulation, potential algebra) are jitted. The per-k eigensolve warm-starts
from the previous iteration's wave functions.
"""

from __future__ import annotations

import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from sirius_tpu.config.schema import Config, load_config
from sirius_tpu.context import SimulationContext
from sirius_tpu.core.hilo import pair_eps
from sirius_tpu.dft import band_solve
from sirius_tpu.dft.density import (
    atomic_moments,
    generate_density_g,
    initial_density_g,
    initial_magnetization_g,
    rho_real_space,
    symmetrize_pw,
    symmetry_tables,
)
from sirius_tpu.dft.mixer import Mixer, initial_res_tol, schedule_res_tol
from sirius_tpu.dft.occupation import find_fermi
from sirius_tpu.dft.potential import generate_potential
from sirius_tpu.dft.recovery import ScfSupervisor
from sirius_tpu.dft.xc import XCFunctional
from sirius_tpu.ops.atomic import atomic_orbitals
from sirius_tpu.ops.augmentation import d_operator, rho_aug_g
from sirius_tpu.obs import costs as obs_costs
from sirius_tpu.obs import events as obs_events
from sirius_tpu.obs import metrics as obs_metrics
from sirius_tpu.obs import numerics as obs_numerics
from sirius_tpu.obs import spans as obs_spans
from sirius_tpu.obs import tracing as obs_tracing
from sirius_tpu.obs.log import get_logger
from sirius_tpu.obs.trace import CAPTURE as obs_trace
from sirius_tpu import runtime
from sirius_tpu.utils import checksums as _cks
from sirius_tpu.utils import devfail
from sirius_tpu.utils import faults
from sirius_tpu.utils.profiler import counters, profile, timer_report

logger = get_logger("dft.scf")

_ITERATIONS = obs_metrics.REGISTRY.counter(
    "scf_iterations_total", "SCF iterations executed")
_ITER_SECONDS = obs_metrics.REGISTRY.histogram(
    "scf_iteration_seconds", "wall time per SCF iteration",
    buckets=(0.001, 0.005, 0.01, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0,
             10.0, 30.0, 60.0))
_RMS = obs_metrics.REGISTRY.gauge(
    "scf_density_rms", "latest density residual RMS")
_ETOT = obs_metrics.REGISTRY.gauge(
    "scf_total_energy_ha", "latest total energy [Ha]")
_RUNS = obs_metrics.REGISTRY.counter(
    "scf_runs_total", "run_scf completions by outcome")
_AUTOSAVES = obs_metrics.REGISTRY.counter(
    "scf_autosaves_total", "mid-run checkpoint writes")
_FORECAST_ITERS = obs_metrics.REGISTRY.gauge(
    "scf_forecast_iterations",
    "forecasted total SCF iterations to convergence (obs/forecast.py)")
_FORECAST_WARNING = obs_metrics.REGISTRY.gauge(
    "scf_forecast_warning",
    "divergence early-warning score in [0, 1] (obs/forecast.py)")
_STRAGGLER = obs_metrics.REGISTRY.counter(
    "scf_straggler_preempts_total",
    "runs preempted at a snapshot boundary by the straggler watchdog")


def _initial_subspace(ctx: SimulationContext) -> jnp.ndarray:
    """LCAO + random-fill initial trial vectors [nk, nspin, nbig, ngk].

    nbig = max(num_bands, num_atomic_orbitals): the FULL atomic-orbital set
    must enter the initial subspace even when it exceeds num_bands —
    truncating it drops whole orbital characters (e.g. 3 of the 5 Fe 3d
    orbitals with nb=10, nao=13) and the band solver then locks on higher
    eigenpairs it can reach instead (reference initialize_subspace.hpp:27
    always spans all atomic wfs and keeps the lowest nb Ritz vectors;
    run_scf performs that rotation at the first iteration)."""
    with obs_spans.span("scf.setup.subspace") as sp:
        # the orbitals' forms are the species' and the random rows the
        # lattice's (context._TABLES): only the atoms' phases are built here
        ao = atomic_orbitals(ctx.unit_cell, ctx.gkvec,
                             ctx.cfg.parameters.gk_cutoff + 1e-9,
                             forms=ctx.ao_forms)
        nk, nao, ngk = ao.shape
        nbig = max(ctx.num_bands, nao)
        psi = np.empty((nk, ctx.num_spins, nbig, ngk), dtype=np.complex128)
        # (masked a second time, as ever: the padded slots' zeros keep
        # their signs, and the block its bytes)
        ao *= ctx.gkvec.mask[:, None, :]
        psi[:, :, :nao] = ao[:, None]
        if nbig > nao:
            # damped so that the random vectors are smooth-ish
            psi[:, :, nao:] = ctx.random_rows(nbig - nao)[:, None]
        sp.set(atomic_orbitals=int(nao), random_rows=int(nbig - nao))
    # host numpy: the band solves upload it themselves, as a (re, im) pair
    # on the batched path (parallel/batched.py real-boundary contract)
    return psi


def default_autosave_path(cfg, base_dir: str) -> str:
    """Default autosave location, job-scoped when control.autosave_tag is
    set so several jobs sharing a workdir (the serving engine) do not
    clobber each other's checkpoints."""
    tag = str(getattr(cfg.control, "autosave_tag", "") or "")
    name = f"sirius_autosave.{tag}.h5" if tag else "sirius_autosave.h5"
    return os.path.join(base_dir, name)


def run_scf(*args, **kwargs) -> dict:
    """Trace-context front door: a standalone SCF gets its own trace_id;
    one inherited from serve/campaigns (scheduler enters the job's
    trace_context) is kept, so every span/event of this run carries the
    end-to-end trace. ``scf.run`` is the root of the run's span tree
    (scf.setup, scf.iteration x N, scf.finalize): leaving it, by return
    or by raise, closes whatever of the run is still open. See
    _run_scf_inner for the full contract."""
    cfg = args[0] if args else kwargs["cfg"]
    with obs_tracing.ensure_trace(), runtime.scf_scope():
        obs_metrics.set_enabled(bool(getattr(
            getattr(cfg, "control", None), "telemetry", True)))
        with obs_spans.span("scf.run"):
            return _run_scf_inner(*args, **kwargs)


def _run_scf_inner(
    cfg: Config,
    base_dir: str = ".",
    restart_from: str | None = None,
    save_to: str | None = None,
    ctx: SimulationContext | None = None,
    initial_state: dict | None = None,
    keep_state: bool = False,
    serial_bands: bool = False,
    resume: str | None = None,
    exec_cache=None,
    devices=None,
    initial_guess: tuple | None = None,
) -> dict:
    """initial_state: optional in-memory warm start {rho_g, mag_g, psi}
    (e.g. the `_state` of a previous run_scf at nearby atomic positions,
    used by relax/vcrelax between geometry steps); its optional "scf"
    sub-dict {mix_x, mix_f, res_tol} re-seeds the quasi-Newton mixer
    history and band tolerance (see initial_guess below). initial_guess:
    the simple front door to the same machinery — a (rho_g, psi) pair
    (either may be None) validated against the context shapes, e.g. an
    extrapolated density and wave functions from an MD predictor; a
    third element, the "scf" hint dict of a previous run's `_state`,
    additionally imports that run's mixer (x, f) history — a multisecant
    model of the SCF Jacobian that stays accurate at a nearby geometry,
    so the first mix() of the warm run takes a quasi-Newton step instead
    of a plain damped one (cross-job handoff, campaigns/handoff.py).
    keep_state: attach that
    state to the result as `_state` (costs a host copy of all wave
    functions; only geometry drivers ask for it). serial_bands: use the
    per-(k, spin) debug path instead of the production one-program batched
    k-set solve (parallel/batched.py). resume: path to a mid-SCF autosave
    (control.autosave_every) — restarts the loop at the saved iteration
    with the full mixer/wave-function/tolerance state, bit-reproducibly on
    the host path; unlike restart_from (density-only warm start of a NEW
    run), resume continues the SAME run after preemption.

    exec_cache: optional serve.cache.ExecutableCache — the serving
    engine's books of executable hits and misses; the fused step itself is
    a program of the process (fused.step_program) with or without one.
    devices: explicit device list
    to run on (a scheduler slice); defaults to jax.devices()."""
    t0 = time.time()
    from sirius_tpu.utils.profiler import reset_timers

    reset_timers()
    if os.environ.get("SIRIUS_TPU_FAULTS"):
        # child processes (tools/soak_scf.py) inherit their fault plan via
        # the environment; in-process plans (faults.install) are untouched
        faults.load_env()
    _setup_span = obs_spans.open_span("scf.setup")
    obs_metrics.install_jax_listeners()
    if cfg.control.verbosity >= 1:
        # deck-driven verbosity keeps printing per-iteration lines even
        # when the CLI -v flag was not given
        from sirius_tpu.obs.log import setup as _log_setup

        _log_setup(cfg.control.verbosity)
    if getattr(cfg.control, "events_path", ""):
        ep = cfg.control.events_path
        obs_events.configure(
            ep if os.path.isabs(ep) else os.path.join(base_dir, ep))
    if getattr(cfg.control, "trace_capture", ""):
        tc = cfg.control.trace_capture
        obs_trace.request(
            tc if os.path.isabs(tc) else os.path.join(base_dir, tc),
            steps=int(getattr(cfg.control, "trace_capture_steps", 5)),
            skip=1)  # from the job's second iteration: the steady state
    p = cfg.parameters
    if ctx is None:
        ctx = SimulationContext.create(cfg, base_dir)
    xc = XCFunctional(p.xc_functionals)
    nk, ns, nb = ctx.gkvec.num_kpoints, ctx.num_spins, ctx.num_bands
    nel = ctx.unit_cell.num_valence_electrons - p.extra_charge
    mgga = xc.is_mgga
    if mgga:
        if serial_bands:
            raise NotImplementedError("mGGA: production batched path only")
        if any(t.paw is not None for t in ctx.unit_cell.atom_types):
            raise NotImplementedError("mGGA with PAW is not supported")
        if ctx.aug is not None:
            import warnings

            warnings.warn(
                "mGGA with ultrasoft augmentation: tau is computed from the "
                "smooth wave functions only (no augmentation tau), matching "
                "the common PW-code approximation"
            )

    if nb * ctx.max_occupancy * ctx.num_spins < nel - 1e-12:
        raise ValueError(
            f"num_bands={nb} cannot hold {nel} electrons "
            f"(max {nb * ctx.max_occupancy * ctx.num_spins})"
        )
    # compute devices of this run (a scheduler slice, or everything); the
    # band solve and the fused step are placed on them explicitly, all other
    # jnp work is host work (runtime.py placement rule)
    _devs = list(devices) if devices is not None else jax.devices()
    if p.precision_wf not in ("fp32", "fp64"):
        raise ValueError(f"precision_wf must be fp32 or fp64, got '{p.precision_wf}'")
    if p.precision_wf == "fp64":
        runtime.refuse_64bit_on(_devs, 'parameters.precision_wf = "fp64"')
    if cfg.settings.fp32_to_fp64_rms > 0:
        runtime.refuse_64bit_on(_devs, "settings.fp32_to_fp64_rms > 0")
    if ctx.num_mag_dims == 3:
        from sirius_tpu.dft.scf_nc import run_scf_nc

        if restart_from or initial_state is not None or keep_state:
            raise NotImplementedError(
                "non-collinear SCF does not support checkpoint/warm-start "
                "state passing yet"
            )
        if save_to:
            import warnings

            warnings.warn(
                "non-collinear SCF does not write checkpoints yet; "
                "save_to ignored"
            )
        return run_scf_nc(cfg, base_dir, ctx=ctx)
    polarized = ctx.num_mag_dims == 1
    # wave-function precision: fp32 runs the band solve in complex64
    # (reference precision_wf, dft_ground_state.cpp:216-304 fp32 SCF with
    # fp64 polish via settings.fp32_to_fp64_rms)
    wf_dtype = jnp.complex64 if p.precision_wf == "fp32" else jnp.complex128

    from sirius_tpu.ops.hubbard import (
        HubbardData,
        constraint_reference_matrix,
        constraint_update,
        hubbard_potential_and_energy,
        initial_occupancy,
        occupation_matrix,
        register_sym_ops,
        symmetrize_occupation,
        u_matrix_for_k,
    )

    hub = HubbardData.build(ctx)
    vhub = None  # per-k apply matrices [nk, ns, nhub, nhub] (or None)
    um_nl: list = []
    om_nl = None
    hub_lagrange = None
    hub_om_cons = None
    hub_cons_state = {"err": np.inf, "steps": 0}
    hub_cons_active = False
    e_hub = e_hub_one_el = 0.0
    if hub is not None:
        register_sym_ops(hub, ctx)
        n0 = initial_occupancy(ctx, hub, ns)
        hub_om_cons = constraint_reference_matrix(hub, ns)
        if hub_om_cons is not None:
            # constrained blocks start AT the target occupancy (reference
            # Occupation_matrix::init constrained_calculation branch)
            n0 = np.where(np.abs(hub_om_cons) > 0, hub_om_cons, n0)
        om_nl0 = [
            np.zeros((ns, 2 * e["il"] + 1, 2 * e["jl"] + 1), dtype=np.complex128)
            for e in hub.nonloc
        ]
        hub_cons_active = hub_om_cons is not None
        um_local, um_nl, e_hub, e_hub_one_el = hubbard_potential_and_energy(
            hub, n0, ctx.max_occupancy, om_nl=om_nl0,
            lagrange=None, om_cons=None,
        )
        vhub = np.stack([
            u_matrix_for_k(hub, um_local, um_nl, ctx.gkvec.kpoints[ik])
            for ik in range(nk)
        ])

    # --- PAW on-site machinery (dft/paw.py; None when no PAW species) ---
    from sirius_tpu.dft import paw as paw_mod

    paw = paw_mod.PawData.build(ctx)
    paw_dm = paw.initial_dm(ctx) if paw is not None else None

    rho_g = initial_density_g(ctx)
    mag_g = initial_magnetization_g(ctx) if polarized else None
    if restart_from:
        from sirius_tpu.io.checkpoint import load_state

        state = load_state(restart_from, ctx)
        rho_g = state["rho_g"]
        if polarized:
            mag_g = state.get("mag_g", mag_g)
        if paw is not None and state.get("paw_dm") is not None:
            paw_dm = np.asarray(state["paw_dm"])
    resume_scf = None
    _resume_psi = None
    if resume:
        from sirius_tpu.io.checkpoint import load_state

        state = load_state(resume, ctx)
        rho_g = state["rho_g"]
        if polarized and state.get("mag_g") is not None:
            mag_g = state["mag_g"]
        if paw is not None and state.get("paw_dm") is not None:
            paw_dm = np.asarray(state["paw_dm"])
        resume_scf = state.get("scf")
        _resume_psi = state.get("psi")
    psi = None
    guess_scf = None
    if initial_state is not None:
        rho_g = np.asarray(initial_state["rho_g"])
        if polarized and initial_state.get("mag_g") is not None:
            mag_g = np.asarray(initial_state["mag_g"])
        if paw is not None and initial_state.get("paw_dm") is not None:
            paw_dm = np.asarray(initial_state["paw_dm"])
        guess_scf = initial_state.get("scf")
        prev_psi = initial_state.get("psi")
        if prev_psi is not None and prev_psi.shape == (
            nk, ns, nb, ctx.gkvec.ngk_max,
        ):
            psi = np.asarray(prev_psi) * ctx.gkvec.mask[:, None, None, :]
    if initial_guess is not None:
        if len(initial_guess) == 3:
            guess_rho, guess_psi, guess_scf = initial_guess
        else:
            guess_rho, guess_psi = initial_guess
        if guess_rho is not None:
            guess_rho = np.asarray(guess_rho)
            if guess_rho.shape != rho_g.shape:
                raise ValueError(
                    f"initial_guess density shape {guess_rho.shape} does not "
                    f"match the context G set {rho_g.shape}"
                )
            rho_g = guess_rho.astype(np.complex128)
        if guess_psi is not None:
            guess_psi = np.asarray(guess_psi)
            want = (nk, ns, nb, ctx.gkvec.ngk_max)
            if guess_psi.shape != want:
                raise ValueError(
                    f"initial_guess wave-function shape {guess_psi.shape} "
                    f"does not match (nk, ns, nb, ngk_max) = {want}"
                )
            psi = guess_psi * ctx.gkvec.mask[:, None, None, :]
    if _resume_psi is not None and _resume_psi.shape == (
        nk, ns, nb, ctx.gkvec.ngk_max,
    ):
        # the autosaved wave functions warm-start the resumed band solve —
        # required for bit-reproducible host-path continuation
        psi = np.asarray(_resume_psi) * ctx.gkvec.mask[:, None, None, :]
    # first PAW on-site update (from the file-occupation guess or the
    # restored/warm-started dm)
    paw_res = paw_mod.compute_paw(paw, paw_dm, xc) if paw is not None else None
    e_paw_one_el = (
        paw_mod.one_elec_energy(paw, paw_dm, paw_res["dij_atoms"])
        if paw is not None
        else 0.0
    )
    # mGGA bootstrap: no wave functions yet -> tau = 0 (SCAN's alpha = 0
    # covenant region); replaced by the real tau after the first band solve
    tau_g = (
        np.zeros((ns, ctx.gvec.num_gvec), dtype=np.complex128) if mgga else None
    )
    # the start potential: f64 on the host like the reported energy's
    # (scf.finalize.potential), its XC the process's compiled program
    # (dft/xc._host_xc; counters.num_host_xc_traces counts new ones)
    counters["num_host_xc_traces"] += 0
    with obs_spans.span("scf.setup.potential",
                        xc="gga" if xc.is_gga else "lda", host_xc="compiled"):
        pot = generate_potential(ctx, rho_g, xc, mag_g, tau_g=tau_g)
    om_size = 0 if hub is None else ns * hub.num_hub_total * hub.num_hub_total
    nl_sizes = [] if hub is None else [
        ns * (2 * e["il"] + 1) * (2 * e["jl"] + 1) for e in hub.nonloc
    ]
    nl_size = sum(nl_sizes)
    # constrained-occupancy Lagrange multipliers join the mixing vector
    # (reference mixer_functions.cpp:275-347 mixes multipliers_constraints_
    # with the Hubbard matrix): the raw lambda += beta*(om - om_ref) map is
    # an unstable integrator on its own; Anderson/Broyden quasi-Newton
    # mixing is what finds the Lagrange saddle point.
    cons_size = om_size if (hub is not None and hub_om_cons is not None) else 0
    paw_size = 0 if paw is None else paw.dm_size()
    mixer = Mixer(
        cfg.mixer, ctx.gvec.glen2,
        num_components=2 if polarized else 1,
        extra_len=om_size + nl_size + cons_size + paw_size,
        omega=ctx.unit_cell.omega,
    )
    # constant device tables, uploaded once (not per iteration); the full-
    # precision projector stack feeds the density-matrix accumulation
    # independently of the wave-function working dtype
    # stored as a (re, im) real pair (real-boundary contract,
    # parallel/batched.py)
    if ctx.beta.num_beta_total:
        from sirius_tpu.parallel.batched import split_cplx as _sc

        _bre, _bim = _sc(np.asarray(ctx.beta.beta_gk))
        beta_dev = (jnp.asarray(_bre), jnp.asarray(_bim))
    else:
        beta_dev = None
    do_symmetrize = (
        p.use_symmetry and ctx.symmetry is not None and ctx.symmetry.num_ops > 1
    )
    if do_symmetrize:
        # the group's rotation tables, before the first host symmetrisation
        # would build half of them unspanned (span scf.setup.symmetry)
        symmetry_tables(ctx)

    ng = ctx.gvec.num_gvec

    def pack(r, m, o, onl, pdm, lam=None):
        parts = [r]
        if polarized:
            parts.append(m)
        if hub is not None:
            parts.append(o.ravel())
            for blk in onl or []:
                parts.append(blk.ravel())
            if cons_size:
                parts.append(np.ravel(lam))
        if paw is not None:
            parts.append(pdm.astype(np.complex128))
        return np.concatenate(parts) if len(parts) > 1 else r

    def unpack(x):
        r = x[:ng]
        m = x[ng : 2 * ng] if polarized else None
        o = None
        onl = None
        pdm = None
        lam = None
        if paw is not None:
            pdm = np.real(x[len(x) - paw_size :])
        end = len(x) - paw_size
        if hub is not None:
            start = end - om_size - nl_size - cons_size
            o = x[start : start + om_size].reshape(
                ns, hub.num_hub_total, hub.num_hub_total
            )
            onl = []
            off = start + om_size
            for e, sz in zip(hub.nonloc, nl_sizes):
                onl.append(
                    x[off : off + sz].reshape(ns, 2 * e["il"] + 1, 2 * e["jl"] + 1)
                )
                off += sz
            if cons_size:
                lam = x[off : off + cons_size].reshape(
                    ns, hub.num_hub_total, hub.num_hub_total
                )
        return r, m, o, onl, pdm, lam

    om_mixed = n0 if hub is not None else None
    om_nl_mixed = om_nl0 if hub is not None else None
    if cons_size:
        hub_lagrange = np.zeros(
            (ns, hub.num_hub_total, hub.num_hub_total), dtype=np.complex128
        )
    x_mix = pack(rho_g, mag_g, om_mixed, om_nl_mixed, paw_dm, hub_lagrange)

    evals = np.zeros((nk, ns, nb))
    rho_spin = None  # host paths: last accumulated per-spin density
    # the band solve behind one seam (dft/band_solve.py): the path is
    # decided here, once, and the loop does not know which one it holds
    band = band_solve.choose(
        ctx, cfg, devices, serial_bands=serial_bands, hub=hub, paw=paw,
        mgga=mgga, wf_dtype=wf_dtype)
    if psi is not None:
        band.load(psi)
    else:
        # full atomic-orbital block (nbig >= nb); rotated down to the lowest
        # nb Ritz vectors at the first band solve, once the screened D of
        # the initial potential exists (reference initialize_subspace)
        band.restart(_initial_subspace(ctx))
    mu, occ, entropy_sum = 0.0, jnp.zeros((nk, ns, nb)), 0.0
    etot_history, rms_history, mag_history = [], [], []
    e_prev, converged, rms, scf_correction = None, False, 0.0, 0.0
    num_iter_done = 0
    itsol = cfg.iterative_solver
    # --- performance-attribution spans (obs/spans.py): per-stage wall
    # clocks recorded alongside (not replacing) the cumulative profiler
    # tree, each annotated with the analytic flops/bytes of its stage so
    # the timeline reports achieved GFLOP/s and roofline headroom ---
    _span_fence = bool(getattr(cfg.control, "span_fence", False))
    try:
        _stage_costs = obs_costs.scf_stage_costs(
            nk, ns, nb, int(ctx.gkvec.ngk_max),
            int(ctx.beta.num_beta_total), tuple(ctx.fft_coarse.dims), ng,
            itsol.num_steps, box_fine=tuple(ctx.gvec.fft.dims),
            mix_history=int(cfg.mixer.max_history), aug=ctx.aug is not None)
    except Exception:
        _stage_costs = {}

    def _stage(stage, **attrs):
        # a live span of one stage, opened here; the caller closes it
        c = _stage_costs.get(stage)
        return obs_spans.open_span(
            stage, flops=c.flops if c else 0.0,
            bytes=c.bytes if c else 0.0, **attrs)

    def _density_stage(it):
        # the scf.density span, with what the solver's density_acc carries
        # through the cube's products booked beside it (0 and no field for
        # a solver off that route)
        rows, route = band.density_route()
        counters["num_density_rows"] += rows
        return _stage("scf.density", it=it + 1, **route)

    _it_span = None

    def _close_iteration():
        # the loop's head and its exit close the iteration that is open:
        # the loop has `continue` paths (recovery rollback, precision
        # switch) and a `break`. An iteration that left before its
        # bookkeeping is marked incomplete; a stage span still open
        # under it is closed first, marked unwound (obs/spans.py)
        nonlocal _it_span
        if _it_span is not None:
            if "path" in _it_span.attrs:
                _it_span.close()
            else:
                _it_span.close(incomplete=True)
            _it_span = None

    def _moment_attr():
        # the step's total moment, the scalar mag_history already holds
        return {"moment_ub": mag_history[-1]} if polarized else {}

    def _hbm_attr():
        # per-iteration HBM high-water sample (device memory_stats peak;
        # host RSS fallback on CPU) — attached to scf.iteration spans
        if not obs_metrics.enabled():
            return {}
        hw = obs_tracing.hbm_high_water()
        return {"hbm_peak_bytes": max(hw.values())} if hw else {}

    def _fence(tree):
        # best-effort sync for truthful attribution (span_fence decks only)
        try:
            jax.block_until_ready(tree)
        except Exception:
            pass
    # adaptive band-solve tolerance, tightened each iteration with the
    # density residual (reference schedule dft_ground_state.cpp:252-259);
    # a static bar leaves a locked-band noise floor in the density that can
    # sit just above density_tol and stall tight decks at num_dft_iter
    res_tol = initial_res_tol(itsol)
    it0 = 0
    warm_secants = None
    if guess_scf:
        # --- cross-run warm start of the MIXER, not just the density: the
        # successive differences of the donor's (x, f) history are secant
        # pairs of the SCF Jacobian, which a small geometry/volume
        # perturbation barely changes. Without them the warm density still
        # pays a full Anderson ramp-up (the model builds one pair per
        # iteration); with them the first mix() is already quasi-Newton.
        # Only DIFFERENCES transfer (Mixer.import_secants explains why
        # absolute pairs stall the child). The donor's final res_tol
        # replaces the loose start of the adaptive band-tolerance schedule
        # below — a warm density is past the regime the loose bar exists
        # for. A length mismatch (different G set / extras layout) drops
        # the hint silently: an optimization, never a correctness input. ---
        hx = np.asarray(guess_scf.get("mix_x", ()))
        hf = np.asarray(guess_scf.get("mix_f", ()))
        if (hx.ndim == 2 and hx.shape[0] >= 2 and hx.shape == hf.shape
                and hx.shape[1] == x_mix.size
                and np.all(np.isfinite(hx.view(np.float64)))
                and np.all(np.isfinite(hf.view(np.float64)))):
            warm_secants = (np.diff(hx.astype(np.complex128), axis=0),
                            np.diff(hf.astype(np.complex128), axis=0))
            mixer.import_secants(*warm_secants)
        hint_tol = guess_scf.get("res_tol")
        if hint_tol is not None and np.isfinite(hint_tol) and hint_tol > 0:
            res_tol = min(res_tol, float(hint_tol))
    if resume_scf is not None:
        # --- mid-SCF resume (control.autosave_every checkpoints): restore
        # the packed mixed vector, mixer history/backoff state, adaptive
        # tolerance, convergence histories and the iteration counter, then
        # rebuild everything derived (hub/PAW on-site state, potential).
        # With psi also restored above, the host path replays the exact
        # trajectory of the uninterrupted run. ---
        if mgga:
            raise NotImplementedError(
                "mid-SCF resume with mGGA (tau is not checkpointed)")
        x_mix = np.asarray(resume_scf["x_mix"])
        rho_g, mag_g, om_mixed, om_nl_mixed, paw_dm, lam_mixed = unpack(x_mix)
        if lam_mixed is not None:
            hub_lagrange = lam_mixed
        if hub is not None:
            um_local, um_nl, e_hub, _ = hubbard_potential_and_energy(
                hub, om_mixed, ctx.max_occupancy, om_nl=om_nl_mixed,
                lagrange=hub_lagrange if hub_cons_active else None,
                om_cons=hub_om_cons if hub_cons_active else None,
            )
            vhub = np.stack([
                u_matrix_for_k(hub, um_local, um_nl, ctx.gkvec.kpoints[ik])
                for ik in range(nk)
            ])
        if paw is not None:
            paw_res = paw_mod.compute_paw(paw, paw_dm, xc)
            e_paw_one_el = paw_mod.one_elec_energy(
                paw, paw_dm, paw_res["dij_atoms"])
        with profile("scf::potential"):
            pot = generate_potential(ctx, rho_g, xc, mag_g)
        mixer.import_history(resume_scf)
        mixer.beta = float(resume_scf.get("mix_beta", mixer.beta))
        mixer.kind = str(resume_scf.get("mix_kind", mixer.kind))
        res_tol = float(resume_scf.get("res_tol", res_tol))
        if "e_prev" in resume_scf:
            e_prev = float(resume_scf["e_prev"])
        etot_history = [float(v) for v in resume_scf.get("etot_history", [])]
        rms_history = [float(v) for v in resume_scf.get("rms_history", [])]
        mag_history = [float(v) for v in resume_scf.get("mag_history", [])]
        if "evals" in resume_scf:
            evals = np.asarray(resume_scf["evals"], dtype=np.float64)
        it0 = int(resume_scf.get("iteration", 0))
        num_iter_done = it0
        # honour an fp32 -> fp64 polish switch that fired before the save
        wf_dtype = (
            jnp.complex128
            if bool(resume_scf.get("wf_fp64", p.precision_wf == "fp64"))
            else jnp.complex64
        )
        if wf_dtype == jnp.complex128:
            runtime.refuse_64bit_on(_devs, f"resume file {resume} (wf_fp64)")

    # ---- fused device-resident iteration (dft/fused.py): density ->
    # mixer -> potential -> D/H-diag refresh as ONE compiled program with a
    # donated carry; per-iteration host traffic is a [NUM_SCALARS] vector.
    # One tail for both production band solves: the batched k-set solve
    # and the packed-real Gamma solve hand it the same five arrays (acc,
    # the density-matrix pair, ev, occ_w, the (re, im) band block).
    # control.device_scf = false keeps the host path below as the f64
    # reference/debug path (tests/test_fused_scf.py pins the two to ~1e-8
    # Ha; benchmark/make_refs.py computes its references with it). ----
    fused = None
    fused_carry = fused_out = fused_np = None
    if (
        band.feeds_fused
        and cfg.control.device_scf not in (False, "false", "off")
        and hub is None and paw is None and not mgga
        and mixer.kind in ("linear", "anderson")
        and not _cks.enabled()
    ):
        from sirius_tpu.dft.fused import (
            FusedScf,
            S_BXC, S_CHG, S_E1, S_E2, S_EHA, S_ENT, S_EVAL, S_EXC, S_FINITE,
            S_HERM, S_MAG, S_NEL, S_ORTHO, S_RMS, S_SYM, S_V0, S_VHA, S_VXC,
            fold_scalars,
        )

        if band.mesh is not None:
            # replicate the fused constants/state on the production mesh
            # ONCE: jit against mesh-sharded band-solve outputs would
            # otherwise reshard every uncommitted operand each iteration —
            # a hidden per-iteration transfer (caught by the
            # transfer-guard test in tests/test_fused_scf.py)
            from jax.sharding import NamedSharding, PartitionSpec

            _rep = NamedSharding(band.mesh, PartitionSpec())

            def _repl(t):
                return jax.tree_util.tree_map(
                    lambda a: jax.device_put(a, _rep), t
                )
        else:

            def _repl(t):
                return jax.tree_util.tree_map(
                    lambda a: band_solve.up(a, _devs[0]), t)

        def _fused_setup(x0, pot0, history=None, rebuild=True):
            # (re)build the fused program and/or its carry. The recovery
            # ladder calls this after a rollback: the donated carry of a
            # diverged step holds poisoned buffers, and a beta/kind change
            # needs a full rebuild because mixer.beta and mixer.kind are
            # constants of the step's trace. The program, its tables and the
            # scalars below are in the band solve's working precision
            # (wf_dtype), so the fp32 -> fp64 polish switch rebuilds too.
            nonlocal fused, fused_carry, fused_out, fused_np, fused_beta
            nonlocal fused_nel, fused_width, fused_occmax, fused_dm0
            if rebuild or fused is None:
                from sirius_tpu.ops.hamiltonian import real_dtype_of

                frdt = np.dtype(real_dtype_of(wf_dtype))
                fused_beta = None if beta_dev is None else _repl(tuple(
                    b if b.dtype == frdt else np.asarray(b, dtype=frdt)
                    for b in beta_dev))
                fused = FusedScf(ctx, xc, mixer, polarized, do_symmetrize,
                                 beta_dev=fused_beta, exec_cache=exec_cache,
                                 wf_dtype=wf_dtype, place=_repl)
                # pre-wrapped device scalars: python floats fed to jit are
                # implicit host->device transfers, which the fused loop
                # must not make
                fused_nel, fused_width, fused_occmax = _repl(tuple(
                    np.asarray(float(v), dtype=frdt)
                    for v in (nel, p.smearing_width, ctx.max_occupancy)))
                fused_dm0 = _repl(
                    (np.zeros((ns, 0, 0), frdt), np.zeros((ns, 0, 0), frdt))
                )
            fused_carry = _repl(fused.init_carry(x0, pot0, history=history))
            fused_out = fused_np = None

        def _fused_switch_precision():
            # fp32 -> fp64 polish: rebuild the fused program in the new
            # wf_dtype around the same mixed state (a one-off supervised
            # fetch, like the rollback snapshot); the last step's outputs
            # still feed the next band solve's one-time table rebuild
            nonlocal fused_out, fused_np
            x_sw, h_sw = fused.fetch_state(fused_carry, with_history=True)
            keep = (fused_out, fused_np)
            _fused_setup(x_sw, fused.fetch_potential(fused_carry),
                         history=h_sw or None)
            fused_out, fused_np = keep

        fused_beta = fused_nel = fused_width = fused_occmax = fused_dm0 = None
        # booked where _step_impl's body is traced (dft/fused.py); a job
        # that reuses the process's step reports the 0, not a missing key
        counters["num_fused_step_traces"] += 0
        _fused_setup(
            x_mix, pot,
            history=mixer.export_history() or None
            if resume_scf is not None else None,
        )

    # ---- SCF supervision & recovery (dft/recovery.py): the sentinels
    # below (non-finite fields, energy blow-up, RMS divergence) roll the
    # loop back to the last finite snapshot and escalate a backoff ladder
    # instead of raising a fatal FloatingPointError. ----
    sup = ScfSupervisor(
        cfg.control, mixer.beta, mixer.kind,
        deck_label=f"nk={nk} ns={ns} nb={nb} ng={ng}",
        density_tol=float(p.density_tol),
    )
    _snap_every = max(1, int(getattr(cfg.control, "snapshot_every", 5)))
    _autosave_every = int(getattr(cfg.control, "autosave_every", 0))
    if sup.enabled:
        # rollback target before any iteration ran: the initial guess
        sup.snapshot(-1, {"x_mix": np.array(x_mix), "res_tol": res_tol})

    def _recover(sentinel, detail=""):
        """Roll back to the supervisor's snapshot and apply one ladder
        rung. Raises ScfAbortError (with the structured diagnostic) when
        the ladder or the recovery budget is exhausted."""
        nonlocal x_mix, rho_g, mag_g, om_mixed, om_nl_mixed, paw_dm
        nonlocal hub_lagrange, um_local, um_nl, e_hub, vhub
        nonlocal paw_res, e_paw_one_el, pot, band
        nonlocal tau_g, fused, fused_carry, fused_out, fused_np
        nonlocal e_prev, res_tol
        if os.environ.get("SIRIUS_TPU_DUMP_DIVERGED"):
            np.savez(
                os.environ["SIRIUS_TPU_DUMP_DIVERGED"],
                rho_g=rho_g,
                mag_g=mag_g if mag_g is not None else np.zeros(1),
            )
        d = sup.recover(sentinel, it, detail=detail, state={
            "mixer_beta": mixer.beta, "mixer_kind": mixer.kind,
            "device_scf": fused is not None,
            **band_solve.oom_state(band, cfg),
        })
        if cfg.control.verbosity >= 1:
            logger.warning(
                "recovery at it=%d: sentinel '%s' -> rung %d "
                "(rollback to it=%d)",
                it + 1, sentinel, d.rung, sup.snap["it"] + 1)
        snap = sup.snap
        x_mix = np.array(snap["x_mix"])
        if d.flush_history:
            mixer.flush_history()
        if d.beta is not None:
            mixer.beta = d.beta
        if d.kind is not None:
            mixer.kind = d.kind
        res_tol = float(snap.get("res_tol", initial_res_tol(itsol)))
        e_prev = None
        rho_g, mag_g, om_mixed, om_nl_mixed, paw_dm, _lam = unpack(x_mix)
        if _lam is not None:
            hub_lagrange = _lam
        if hub is not None:
            um_local, um_nl, e_hub, _ = hubbard_potential_and_energy(
                hub, om_mixed, ctx.max_occupancy, om_nl=om_nl_mixed,
                lagrange=hub_lagrange if hub_cons_active else None,
                om_cons=hub_om_cons if hub_cons_active else None,
            )
            vhub = np.stack([
                u_matrix_for_k(hub, um_local, um_nl, ctx.gkvec.kpoints[ik])
                for ik in range(nk)
            ])
        if paw is not None:
            paw_res = paw_mod.compute_paw(paw, paw_dm, xc)
            e_paw_one_el = paw_mod.one_elec_energy(
                paw, paw_dm, paw_res["dij_atoms"])
        if mgga:
            # tau of the diverged wave functions is poisoned too; restart
            # from the tau = 0 bootstrap like the initial iteration
            tau_g = np.zeros((ns, ng), dtype=np.complex128)
        with profile("scf::potential"):
            pot = generate_potential(ctx, rho_g, xc, mag_g, tau_g=tau_g)
        # the OOM ladder may swap the band solve for the chunked projectors
        band = band_solve.degrade(band, d, cfg)
        # the diverged wave functions are part of the poisoned trajectory:
        # restart the band solve from a fresh LCAO subspace
        band.restart(_initial_subspace(ctx))
        if fused is not None:
            if d.disable_device or not band.feeds_fused:
                # rung 2: remaining iterations on the host path, which
                # re-validates every field per iteration (the chunked
                # projector path also runs under the host loop)
                fused = None
                fused_carry = fused_out = fused_np = None
            else:
                _fused_setup(
                    x_mix, pot,
                    rebuild=(d.beta is not None or d.kind is not None),
                )

    def _autosave(it):
        """Atomic mid-SCF checkpoint (io/checkpoint.py scf_state group):
        everything the resume path above needs to continue this run."""
        from sirius_tpu.io.checkpoint import save_state

        _as_span = obs_spans.open_span("scf.autosave", it=it + 1)
        path = cfg.control.autosave_path or default_autosave_path(
            cfg, base_dir)
        if fused is not None and fused_carry is not None:
            x_now, hist = fused.fetch_state(fused_carry, with_history=True)
            ev_h = np.asarray(ev_dev, dtype=np.float64)
        else:
            x_now = np.array(x_mix)
            hist = mixer.export_history()
            ev_h = np.asarray(evals)
        psi_h = band.host_psi()
        if psi_h is not None:
            psi_h = np.asarray(psi_h, dtype=np.complex128)
        r_s, m_s, _, _, pdm_s, _ = unpack(x_now)
        scf_state = {
            "x_mix": x_now,
            "iteration": it + 1,
            "res_tol": res_tol,
            "e_prev": e_prev,
            "mix_beta": mixer.beta,
            "mix_kind": mixer.kind,
            "wf_fp64": wf_dtype == jnp.complex128,
            "evals": ev_h,
            "etot_history": np.asarray(etot_history),
            "rms_history": np.asarray(rms_history),
            "mag_history": np.asarray(mag_history),
        }
        if hist:
            scf_state.update(hist)
        save_state(
            path, ctx, r_s, m_s, psi=psi_h, band_energies=ev_h,
            paw_dm=pdm_s, scf_state=scf_state,
            rotate_keep=int(getattr(cfg.control, "autosave_keep", 0)),
        )
        _AUTOSAVES.inc()
        obs_events.emit("autosave", it=it + 1, path=path,
                        fused=fused is not None)
        _as_span.close()
        # fault site: a preemption right after the autosave (soak test /
        # tests drive the resume path through this)
        faults.check("scf.autosave_kill", it)

    # ---- convergence forecasting + deadline feasibility (obs/forecast.py
    # via the supervisor): one scf_forecast event and two gauges per
    # iteration, plus a deadline_feasibility event whenever the
    # forecasted finish crosses control.deadline_ts in either direction.
    _fc_warnings = 0
    _fc_deadline_ok = None  # None until the first feasibility verdict
    _iter_wall: list[float] = []
    _numerics_probe = bool(getattr(cfg.control, "numerics_probe", False))
    _numerics_every = max(
        1, int(getattr(cfg.control, "numerics_probe_every", 10)))

    def _forecast_tick(it, dt, path):
        nonlocal _fc_warnings, _fc_deadline_ok
        if not (sup.enabled and sup.forecast_enabled):
            return
        _iter_wall.append(float(dt))
        # fault site: a deliberately wrong forecast — maximum warning with
        # no real divergence; drives the proactive-snapshot and deadline
        # paths and pins that a misfire alone never costs a recovery
        if faults.armed("scf.forecast_misfire", it):
            sup.inject_warning(1.0)
        snap = sup.forecast_snapshot()
        if snap is None:
            return
        warning = float(snap.get("warning") or 0.0)
        if warning >= sup.forecast_warning_threshold:
            _fc_warnings += 1
        total = snap.get("forecast_total")
        if total is not None:
            _FORECAST_ITERS.set(float(total))
        _FORECAST_WARNING.set(warning)
        obs_events.emit("scf_forecast", it=it + 1, path=path, **{
            k: snap.get(k) for k in (
                "decay_rate", "forecast_remaining", "forecast_total",
                "warning", "growth_streak")})
        deadline = float(getattr(cfg.control, "deadline_ts", 0.0) or 0.0)
        remaining = snap.get("forecast_remaining")
        if deadline > 0.0 and remaining is not None and _iter_wall:
            # median of the recent iteration walls: robust against the
            # compile-dominated first iteration
            tail = sorted(_iter_wall[-5:])
            per_it = tail[len(tail) // 2]
            eta = time.time() + per_it * float(remaining)
            ok = bool(eta <= deadline)
            if ok != _fc_deadline_ok:
                obs_events.emit(
                    "deadline_feasibility", it=it + 1, feasible=ok,
                    eta_ts=eta, deadline_ts=deadline,
                    forecast_remaining=remaining,
                    sec_per_iteration=per_it)
                _fc_deadline_ok = ok

    # ---- straggler watchdog (utils/devfail.py): per-iteration wall
    # against BOTH the run's own healthy-median baseline and the
    # obs/costs.py analytic model for scf.iteration. A slice degraded by
    # thermal throttling or a sick neighbor chip runs every iteration
    # slow; a sustained streak preempts the run at a snapshot boundary so
    # the serving layer can reschedule it on healthy hardware
    # (serve/scheduler.py treats StragglerPreempt as a preemption, never a
    # strike). control.straggler_detect "auto" keeps it OFF standalone —
    # the scheduler resolves it to on at job admission. ----
    _strag_on = getattr(cfg.control, "straggler_detect", "auto") in (
        True, "true", "on", "force")
    _strag_ratio = float(getattr(cfg.control, "straggler_ratio", 4.0))
    _strag_iters = max(1, int(getattr(cfg.control, "straggler_iters", 3)))
    _strag = {"healthy": [], "streak": 0, "fire": False, "delay": 0.0}
    _c_it = _stage_costs.get("scf.iteration")
    _strag_model_s = (
        _c_it.flops / (obs_costs.peak_gflops(_devs[0].device_kind) * 1e9)
        if _c_it else 0.0)

    def _straggler_tick(it, dt, path):
        """Feed one iteration wall clock to the straggler detector."""
        if not _strag_on or _strag["fire"]:
            return
        if it - it0 < 2:
            return  # compile-dominated warm-up walls are not evidence
        healthy = _strag["healthy"]
        if len(healthy) >= 3:
            tail = sorted(healthy[-12:])
            base = max(tail[len(tail) // 2], _strag_model_s)
            if dt > _strag_ratio * base:
                _strag["streak"] += 1
                if _strag["streak"] >= _strag_iters:
                    _strag["fire"] = True
                    obs_events.emit(
                        "straggler", it=it + 1, path=path, dt=dt,
                        baseline_s=base, model_s=_strag_model_s,
                        ratio=dt / base, streak=_strag["streak"])
                return
        _strag["streak"] = 0
        healthy.append(float(dt))

    def _straggler_preempt(it):
        """After the detector fired: force a snapshot unless this
        iteration already autosaved, then hand the run back to the
        scheduler as a preemption (resume elsewhere from the autosave)."""
        if not _strag["fire"]:
            return
        if not (_autosave_every and (it + 1) % _autosave_every == 0):
            _autosave(it)
        _STRAGGLER.inc()
        raise devfail.StragglerPreempt(
            f"straggler watchdog preempted the run at iteration {it + 1}: "
            f"sustained slow iterations on this slice")

    obs_events.emit(
        "run_manifest", nk=nk, ns=ns, nb=nb, ng=ng,
        num_atoms=ctx.unit_cell.num_atoms, device_scf=fused is not None,
        it0=it0, num_dft_iter=p.num_dft_iter, resumed=resume is not None,
        xc=list(p.xc_functionals), precision_wf=p.precision_wf,
    )
    # everything since run_scf entry (context/tables/initial guess/fused
    # compile trigger) is the setup span
    # ... and it says which mesh factorisation the job runs on, if any,
    # and how large the batched solve's one program is
    counters["num_kpoints_solved"] = nk
    _setup_span.close(
        fused=fused is not None,
        spin={"num_spins": ns, "num_mag_dims": int(ctx.num_mag_dims),
              "start_moment_ub": float(
                  np.sum(ctx.unit_cell.moments[:, 2]))},
        # what the process's table of steps answered this job's constants
        # (counters.num_fused_step_traces is what JAX then did)
        **({} if fused is None else {
            "fused_step": "reused" if fused.step_reused else "traced"}),
        **({"symmetry": {
            "num_ops": int(ctx.symmetry.num_ops),
            "kpoints_mesh": int(np.prod(p.ngridk)),
            "kpoints_irreducible": nk}} if do_symmetrize else {}),
        **({} if band.mesh is None else {"mesh": dict(band.mesh.shape)}),
        **band.plan(wf_dtype))
    _it_t0 = time.time()
    for it in range(it0, p.num_dft_iter):
        _close_iteration()
        obs_trace.tick(it + 1)
        _it_span = obs_spans.open_span("scf.iteration", it=it + 1)
        _it_t0 = time.time()
        # ---- injectable device faults at the jit-dispatch boundary
        # (utils/faults.py fire/armed; tools/chaos_serve.py device phases).
        # device.oom is classified (utils/devfail.py) and routed through
        # the OOM degradation ladder IN-RUN: the run rolls back to the
        # supervisor snapshot and continues on a smaller memory plan — no
        # job failure. device.lost is deliberately NOT caught here: a lost
        # chip takes the whole dispatch down, and only the serving layer
        # can rebuild a mesh from the surviving devices and resume from
        # the autosave. ----
        try:
            faults.fire("device.oom", it)
        except RuntimeError as _de:
            if devfail.classify(_de) != "oom":
                raise
            _recover("device_oom", detail=str(_de))
            continue
        faults.fire("device.lost", it)
        if _strag_on and faults.armed("device.straggler", it):
            # persistent slowdown from this iteration on — sized off the
            # run's own healthy walls so the detector's ratio bar is
            # crossed regardless of deck size
            _h = sorted(_strag["healthy"])
            _base = _h[len(_h) // 2] if _h else 0.1
            _strag["delay"] = max(0.45, (_strag_ratio + 2.0) * _base)
        if _strag["delay"]:
            time.sleep(_strag["delay"])
        # --- band solve per (k, spin) (warm start) ---
        if fused is None or fused_out is None:
            # host D/v0 from the host potential; once the fused step has
            # run, the refreshed D and v0 live on device (fused_out)
            _sp = _stage("scf.d_matrix", it=it + 1)
            d_by_spin = []
            for ispn in range(ns):
                if ctx.aug is not None:
                    vs_g = pot.veff_g + (pot.bz_g if ispn == 0 else -pot.bz_g) if polarized else pot.veff_g
                    d_by_spin.append(
                        d_operator(ctx.unit_cell, ctx.gvec, ctx.aug, vs_g,
                                   ctx.beta, phases=ctx.phases)
                    )
                else:
                    d_by_spin.append(ctx.beta.dion)
            if paw is not None:
                # add the on-site PAW Dij (from the mixed on-site density) to
                # the screened D before the band solve
                d_by_spin = paw_mod.add_dij_to_d(paw, paw_res["dij_atoms"], d_by_spin)
            v0 = float(np.real(pot.veff_g[0]))
            _sp.close()
            inputs = band_solve.Inputs(
                pot=pot, d_by_spin=d_by_spin, v0=v0, vhub=vhub)
        else:
            # only the leaves the solve reads: all of fused_out held here
            # would keep the previous step's residual and density-matrix
            # buffers alive through the next step
            inputs = band_solve.Inputs(
                fused_out={k: fused_out[k]
                           for k in ("veff_r_coarse", "dion", "h_diag")},
                v0=float(fused_np[S_V0]), vhub=vhub)
        # the span carries no cost until the solve has said what it ran:
        # the model's "scf.band_solve" is the cost of all num_steps steps
        _bs_span = obs_spans.open_span("scf.band_solve", it=it + 1,
                                       num_steps=itsol.num_steps)
        _bs_t0 = time.perf_counter()
        with profile("scf::band_solve"):
            out = band.solve(inputs, res_tol, wf_dtype,
                             tail_rdt=None if fused is None else fused.rdt)
        if _span_fence:
            # the host tails already fenced via np.asarray(ev); only a
            # device-resident solve still has compute in flight
            _fence(out)
        band.after_solve(_bs_t0, it)
        if (obs_metrics.enabled() and _stage_costs
                and (fused is None or _span_fence)):
            # the span's time is the solve's (a host tail has fetched its
            # values, a fenced one has waited for them): it carries the
            # cost of the steps that ran. On the device path without a
            # fence the span times a dispatch and the steps are still on
            # the device: no cost, so no utilization is made of a bound
            _c = band.last_cost(int(ctx.gkvec.ngk_max),
                                int(ctx.beta.num_beta_total),
                                tuple(ctx.fft_coarse.dims))
            _bs_span.flops, _bs_span.bytes = _c.flops, _c.bytes
        _bs_span.close()
        # --- band-solve supervision (dft/recovery.py): a stagnated or
        # blown-up solve is retried with a deeper subspace; the serial
        # debug path additionally falls back to dense diagonalization for
        # small |G+k| spheres (band_solve's rescue methods). Host tail
        # only — the fused loop's scalar record already carries an
        # all-finite eigenvalue sentinel, and checking rn here would add
        # per-iteration device->host traffic.
        if fused is None and sup.enabled:
            from sirius_tpu.solvers.davidson import residual_health

            rn_max, rn_ok = residual_health(
                out.rn, blowup=cfg.control.band_residual_blowup)
            if faults.armed("scf.band_stagnate", it):
                rn_ok = False
            rescued = None if rn_ok else band.rescue(inputs, out, res_tol)
            if rescued is not None:
                out = rescued
                if cfg.control.verbosity >= 1:
                    logger.warning(
                        "band-solve rescue at it=%d (max rnorm %.2e)",
                        it + 1, rn_max)
        if fused is not None:
            ev_dev = out.ev
        else:
            evals = out.ev
        if _cks.enabled():
            _cks.checksum("evals", evals)

        if fused is not None:
            # --- fused device-resident remainder of the iteration: fermi
            # search, density, mixing, potential and the D/h_diag refresh
            # all run on device; ONE scalar vector comes back ---
            with profile("scf::fused_step"):
                # sub-stage clocks: honest per-stage splits need span_fence
                # (each _fence is a sync, not a transfer — the transfer
                # guard of test_fused_no_host_transfers stays satisfied);
                # unfenced, dispatch latency is recorded per stage and the
                # queued compute lands in scf.readback below
                _sp = _stage("scf.occupations", it=it + 1)
                mu, occ, entropy_sum = find_fermi(
                    ev_dev, fused.kweights_dev, fused_nel, fused_width,
                    kind=p.smearing, max_occupancy=fused_occmax,
                )
                occ_w = occ * fused.kweights_dev[:, None, None]
                if _span_fence:
                    _fence(occ_w)
                _sp.close()
                _sp = _density_stage(it)
                from sirius_tpu.parallel.batched import density_matrix_kset

                acc = band.density_acc(occ_w)
                # fault site: NaN into the accumulated density (functional
                # device-side update; a no-op dict lookup when unarmed, so
                # the transfer-guard contract of this span is preserved)
                acc = faults.corrupt("scf.density", it, acc)
                if fused.has_aug and fused_beta is not None:
                    dm_re, dm_im = density_matrix_kset(
                        *fused_beta, out.pr, out.pi, occ_w
                    )
                else:
                    dm_re, dm_im = fused_dm0
                if _span_fence:
                    _fence((acc, dm_re, dm_im))
                _sp.close()
                _sp = _stage(
                    "scf.fused_step", it=it + 1, box_fill="gather",
                    box_fills=fused.box_fills, xc=fused.xc_kind,
                    sym_ops=fused.sym_ops, polarized=polarized)
                counters["num_tail_box_fills"] += fused.box_fills
                counters["num_sym_pw"] += fused.sym_pw
                counters["num_xc_gradient_transforms"] += (
                    fused.xc_gradient_transforms)
                fused_carry, fused_out = fused.step(
                    fused_carry, acc, dm_re, dm_im, ev_dev, occ_w,
                    entropy_sum, out.pr, out.pi,
                )
                if _span_fence:
                    _fence(fused_out)
                _sp.close()
            # the ONLY per-iteration device->host fetch
            _sp = _stage("scf.readback", it=it + 1)
            fused_np = fold_scalars(np.asarray(fused_out["scalars"]))
            _sp.close()
            if (not np.all(np.isfinite(fused_np))
                    or fused_np[S_FINITE] != 1.0):
                # non-finite fields on device: roll back and escalate
                # (dft/recovery.py) instead of losing the run
                _recover(
                    "device_nonfinite",
                    detail="non-finite scalars/fields from the "
                    "device-resident step",
                )
                continue
            rms = float(fused_np[S_RMS])
            eha_res = float(fused_np[S_EHA])
            dens_metric = eha_res if mixer.use_hartree else rms
            res_tol = schedule_res_tol(itsol, res_tol, dens_metric, nel,
                                       mixer.use_hartree)
            scf_correction = (
                float(fused_np[S_E2] - fused_np[S_E1])
                if p.use_scf_correction else 0.0
            )
            e_total = (
                float(fused_np[S_EVAL] - fused_np[S_VXC] - fused_np[S_BXC]
                      - 0.5 * fused_np[S_VHA] + fused_np[S_EXC])
                + ctx.e_ewald + scf_correction
            )
            if cfg.control.verification >= 1:
                nel_got = float(fused_np[S_NEL])
                if abs(nel_got - nel) > 1e-6 * max(1.0, nel):
                    import warnings

                    warnings.warn(
                        f"electron count from density {nel_got:.8f} != "
                        f"{nel:.8f}"
                    )
            etot_history.append(e_total + float(fused_np[S_ENT]))
            rms_history.append(rms)
            if polarized:
                mag_history.append(float(fused_np[S_MAG]))
            num_iter_done = it + 1
            _ITERATIONS.inc(path="fused")
            _it_dt = time.time() - _it_t0
            _ITER_SECONDS.observe(_it_dt)
            _RMS.set(rms)
            _ETOT.set(e_total)
            # the span runs on to the loop's head: snapshot, autosave and
            # the straggler check below are the iteration's too
            _it_span.set(path="fused", **_moment_attr(), **_hbm_attr())
            # numerics ledger: the invariants ride the existing [NUM_SCALARS]
            # readback (dft/fused.py) — naming them here costs no transfer
            ledger = obs_numerics.ledger_from_scalars(fused_np)
            obs_numerics.record_ledger(ledger, it + 1, "fused")
            obs_events.emit(
                "scf_iteration", it=it + 1, path="fused", rms=rms,
                e_total=e_total, dt=_it_dt,
                scalars=[float(v) for v in fused_np], ledger=ledger,
            )
            if cfg.control.verbosity >= 2:
                mg = f" mag={mag_history[-1]:+.4f}" if polarized else ""
                logger.info("it=%3d etot=%+.10f rms=%.3e%s",
                            it + 1, e_total, rms, mg)
            sentinel = sup.observe(it, rms, e_total)
            if sentinel is not None:
                _recover(sentinel)
                continue
            _forecast_tick(it, _it_dt, "fused")
            _straggler_tick(it, _it_dt, "fused")
            if sup.enabled and (it % _snap_every == 0
                                or sup.should_snapshot()):
                # rollback snapshot: fetch the mixed vector from the carry
                # OUTSIDE the fused profile span (an explicit supervised
                # transfer every snapshot_every iterations — plus whenever
                # the divergence early warning is raised, so a subsequent
                # rollback lands on the newest trusted iterate instead of
                # one up to snapshot_every iterations stale)
                x_snap, _ = fused.fetch_state(fused_carry)
                sup.snapshot(it, {
                    "x_mix": x_snap, "e_total": e_total,
                    "res_tol": res_tol,
                })
            de = abs(e_total - e_prev) if e_prev is not None else np.inf
            e_prev = e_total
            if (
                wf_dtype == jnp.complex64
                and cfg.settings.fp32_to_fp64_rms > 0
                and rms < cfg.settings.fp32_to_fp64_rms
            ):
                wf_dtype = jnp.complex128
                _fused_switch_precision()
                continue
            # autosave AFTER e_prev/precision bookkeeping: the saved state
            # must be exactly what the next iteration of an uninterrupted
            # run would start from
            if _autosave_every and (it + 1) % _autosave_every == 0:
                _autosave(it)
            if de < p.energy_tol and dens_metric < p.density_tol:
                converged = True
                break
            _straggler_preempt(it)
            continue

        # --- occupations ---
        # fault site: NaN into the band energies (detected with the other
        # non-finite fields after the density assembly below)
        evals = faults.corrupt("scf.evals", it, evals)
        _sp = _stage("scf.occupations", it=it + 1)
        mu, occ, entropy_sum = find_fermi(
            jnp.asarray(evals),
            jnp.asarray(ctx.kweights),
            nel,
            p.smearing_width,
            kind=p.smearing,
            max_occupancy=ctx.max_occupancy,
        )
        occ_np = np.asarray(occ)  # self-fencing host fetch
        _sp.close()

        # --- Hubbard occupation matrix (mixed jointly with the density) ---
        om_new = None
        om_nl_new = None
        if hub is not None:
            om_new, occ_T = occupation_matrix(
                ctx, hub, band.host_psi(), occ_np, ctx.max_occupancy
            )
            # Constrained-occupancy runs keep the RAW k-weighted om: the
            # stable dual-ascent drives the om to a target that is NOT
            # invariant under the crystal group (test30's eg off-diagonal
            # -0.351 cannot survive the symmetry average), so the om is
            # left unsymmetrized while a constraint is configured.
            if do_symmetrize and hub_om_cons is None:
                om_new, om_nl_new = symmetrize_occupation(
                    ctx, hub, om_new, occ_T
                )
            else:
                from sirius_tpu.ops.hubbard import nonlocal_from_occ_T

                om_nl_new = nonlocal_from_occ_T(hub, occ_T) if hub.nonloc else []
            # occupancy-constraint Lagrange multipliers (reference
            # calculate_constraints_and_error; RELEASES once converged)
            if hub_om_cons is not None:
                hub_lagrange, hub_cons_active = constraint_update(
                    hub, om_new, hub_lagrange, hub_om_cons, hub_cons_state
                )
            # one-electron term inside eval_sum: NEW occupancies against the
            # potential the band solve actually used (um_local/um_nl of the
            # previous mixing step; reference one_electron_energy_hubbard)
            e_hub_one_el = ctx.max_occupancy * (
                sum(
                    float(np.real(np.sum(om_new[ispn] * np.conj(um_local[ispn]))))
                    for ispn in range(ns)
                )
                + sum(
                    float(np.real(np.sum(o * np.conj(u))))
                    for o, u in zip(om_nl_new or [], um_nl)
                )
            )

        # --- density (per spin, then charge/magnetization assembly) ---
        _sp = _density_stage(it)
        occ_w = jnp.asarray(occ_np * ctx.kweights[:, None, None])
        with profile("scf::density"):
            from sirius_tpu.dft.density import density_from_coarse_acc

            # the solver's own coarse-box accumulator where it has one,
            # the host wave functions otherwise
            acc_h = band.density_acc(occ_w)
            if acc_h is None:
                rho_spin = generate_density_g(ctx, band.host_psi(), occ_np)
            else:
                rho_spin = density_from_coarse_acc(ctx, np.asarray(acc_h))
            if mgga:
                # same 1/Omega + coarse->fine mapping as the density; tau
                # transforms as a scalar field, so the reduced k-wedge sum
                # needs the same point-group symmetrization as rho
                tau_g = density_from_coarse_acc(
                    ctx, np.asarray(band.tau_acc(occ_w)))
                if do_symmetrize:
                    tau_g = np.stack(
                        [symmetrize_pw(ctx, t) for t in tau_g]
                    )
                # NOTE: the potential is built from the MIXED density but
                # the FRESH tau of the current wave functions (tau is
                # psi-derived and not part of the mixing vector); near
                # self-consistency the pair is consistent, and the SCAN
                # smoke test covers the transient
        dm_blocks_by_spin = []
        if ctx.aug is not None:
            from sirius_tpu.dft.density import symmetrize_density_matrix
            from sirius_tpu.parallel.batched import density_matrix_kset, split_cplx

            if out.pr is not None:
                ppair = (out.pr, out.pi)  # already device-resident
            else:
                ppair = split_cplx(np.asarray(band.host_psi()))
            dm_re, dm_im = density_matrix_kset(*beta_dev, *ppair, occ_w)
            from sirius_tpu.parallel.batched import join_cplx as _jc

            dm_by_spin = _jc(dm_re, dm_im)
            if do_symmetrize:
                dm_by_spin = symmetrize_density_matrix(ctx, dm_by_spin)
            for ispn in range(ns):
                dm_blocks = [
                    dm_by_spin[ispn, off : off + nbf, off : off + nbf]
                    for _, off, nbf in ctx.beta.atom_blocks(ctx.unit_cell)
                ]
                dm_blocks_by_spin.append(dm_blocks)
                rho_spin[ispn] += rho_aug_g(ctx.unit_cell, ctx.gvec, ctx.aug,
                                            dm_blocks, phases=ctx.phases)
        rho_new = rho_spin.sum(axis=0)
        mag_new = rho_spin[0] - rho_spin[1] if polarized else None
        if _cks.enabled():
            _cks.checksum("rho_new", rho_new)
        if cfg.control.verification >= 1:
            # electron-count audit (reference Density::check_num_electrons,
            # dft_ground_state.cpp:305-308)
            nel_got = float(np.real(rho_new[0]) * ctx.unit_cell.omega)
            if abs(nel_got - nel) > 1e-6 * max(1.0, nel):
                import warnings

                warnings.warn(
                    f"electron count from density {nel_got:.8f} != {nel:.8f}"
                )
        if do_symmetrize:
            rho_new = symmetrize_pw(ctx, rho_new)
            if polarized:
                mag_new = symmetrize_pw(ctx, mag_new, axial_z=True)
        paw_dm_new = (
            paw.dm_from_density_matrix(dm_by_spin) if paw is not None else None
        )
        # fault site: NaN into the freshly accumulated density (drives the
        # recovery-ladder tests without waiting for a real divergence)
        rho_new = faults.corrupt("scf.density", it, rho_new)
        x_new = pack(rho_new, mag_new, om_new, om_nl_new, paw_dm_new,
                     hub_lagrange)
        # the span extends past profile("scf::density") through augmentation,
        # symmetrization and packing — the full "new density" stage
        _sp.close()
        rho_resid_g = rho_new - rho_g  # output - input density (scf-corr force)
        if not np.all(np.isfinite(evals)) or not np.isfinite(
            np.sum(np.abs(x_new))
        ):
            bad = [
                name
                for name, a in [
                    ("evals", evals),
                    ("rho_new", rho_new),
                    ("mag_new", mag_new if polarized else np.zeros(1)),
                    ("om_new", om_new if hub is not None else np.zeros(1)),
                    ("om_nl_new", np.concatenate([np.ravel(o) for o in om_nl_new]) if (hub is not None and om_nl_new) else np.zeros(1)),
                    ("paw_dm_new", paw_dm_new if paw_dm_new is not None else np.zeros(1)),
                    ("lagrange", hub_lagrange if hub_lagrange is not None else np.zeros(1)),
                    ("veff_in", pot.veff_r_coarse),
                    ("vhub_in", vhub if vhub is not None else np.zeros(1)),
                    ("rho_in", rho_g),
                ]
                if not np.all(np.isfinite(np.asarray(a)))
            ]
            _recover("nonfinite_fields", detail=f"non-finite {bad}")
            continue
        _sp = _stage("scf.mixing", it=it + 1)
        rms = mixer.rms(x_mix, x_new)
        x_mix = mixer.mix(x_mix, x_new)
        # density criterion in the reference's metric: with use_hartree the
        # bar is the Hartree ENERGY of (mixed - new), not the rms
        # (dft_ground_state.cpp:251,353) — quadratic in the residual, so
        # testing the Hartree-metric rms against the same density_tol is a
        # far stricter (square-root) bar and stalls decks at 100 iterations
        eha_res = mixer.residual_hartree_energy(x_mix, x_new)
        dens_metric = (
            eha_res if (mixer.use_hartree and eha_res is not None) else rms
        )
        res_tol = schedule_res_tol(itsol, res_tol, dens_metric, nel,
                                   mixer.use_hartree and eha_res is not None)
        rho_g, mag_g, om_mixed, om_nl_mixed, paw_dm, lam_mixed = unpack(x_mix)
        _sp.close()
        if lam_mixed is not None:
            hub_lagrange = lam_mixed  # quasi-Newton-mixed multipliers
        if hub is not None:
            um_local, um_nl, e_hub, _ = hubbard_potential_and_energy(
                hub, om_mixed, ctx.max_occupancy, om_nl=om_nl_mixed,
                lagrange=hub_lagrange if hub_cons_active else None,
                om_cons=hub_om_cons if hub_cons_active else None,
            )
            vhub = np.stack([
                u_matrix_for_k(hub, um_local, um_nl, ctx.gkvec.kpoints[ik])
                for ik in range(nk)
            ])
        if paw is not None:
            # PAW on-site update from the mixed dm: potentials, Dij (used by
            # the next band solve) and energies (reference generates the PAW
            # potential from the mixed density, potential.generate)
            paw_res = paw_mod.compute_paw(paw, paw_dm, xc)
            e_paw_one_el = paw_mod.one_elec_energy(
                paw, paw_dm, paw_res["dij_atoms"]
            )

        # first-order (Harris-like) correction: E_pot[rho_out] under the new
        # vs old potential (reference dft_ground_state.cpp:245,320-322)
        def _epot(r_out, m_out, p_):
            e = float(np.real(np.vdot(r_out, p_.veff_g))) * ctx.unit_cell.omega
            if polarized and p_.bz_g is not None and m_out is not None:
                e += float(np.real(np.vdot(m_out, p_.bz_g))) * ctx.unit_cell.omega
            return e

        e1 = _epot(rho_new, mag_new, pot)

        # --- potential + energies ---
        _sp = _stage("scf.potential", it=it + 1)
        with profile("scf::potential"):
            pot = generate_potential(ctx, rho_g, xc, mag_g, tau_g=tau_g)
        _sp.close()
        # fault site: NaN into the generated effective potential
        pot.veff_r_coarse = faults.corrupt(
            "scf.potential", it, pot.veff_r_coarse)
        if not np.all(np.isfinite(np.asarray(pot.veff_r_coarse))):
            _recover(
                "potential_nonfinite",
                detail=f"potential non-finite from rho finite="
                f"{np.all(np.isfinite(rho_g))}, mag finite="
                f"{mag_g is None or np.all(np.isfinite(mag_g))}",
            )
            continue
        if _cks.enabled():
            _cks.checksum("veff", pot.veff_g)
        scf_correction = (
            _epot(rho_new, mag_new, pot) - e1 if p.use_scf_correction else 0.0
        )
        eval_sum = float(np.sum(ctx.kweights[:, None, None] * occ_np * evals))
        e = pot.energies
        e_total = (
            eval_sum - e["vxc"] - e["bxc"] - e.get("vtau_tau", 0.0)
            - 0.5 * e["vha"] + e["exc"] + ctx.e_ewald
            + scf_correction + (e_hub - e_hub_one_el if hub is not None else 0.0)
            + (paw_res["e_total"] - e_paw_one_el if paw is not None else 0.0)
        )
        # reference etot_history records the free energy (dft_ground_state
        # etot_hist; verified against verification/test23 and test01 outputs)
        etot_history.append(e_total + float(entropy_sum))
        rms_history.append(rms)
        if polarized:
            # per-iteration total moment (reference prints magnetisation
            # each SCF step); recorded from the OUTPUT density pre-mix
            mag_history.append(float(np.real(mag_new[0]) * ctx.unit_cell.omega))
        num_iter_done = it + 1
        _ITERATIONS.inc(path="host")
        _it_dt = time.time() - _it_t0
        _ITER_SECONDS.observe(_it_dt)
        _RMS.set(rms)
        _ETOT.set(e_total)
        # the span runs on to the loop's head: probe, snapshot and
        # autosave below are the iteration's too
        _it_span.set(path="host", **_moment_attr(), **_hbm_attr())
        # numpy twin of the fused on-device numerics ledger (obs/numerics.py)
        # — same invariants from the same operands, so the fused values can
        # be validated against this path (tests/test_fused_scf.py)
        ledger = None
        if out.pr is not None:
            _sym_resid = (
                float(np.max(np.abs(symmetrize_pw(ctx, rho_new) - rho_new)))
                if do_symmetrize else 0.0
            )
            ledger = obs_numerics.ledger_host(
                np.asarray(out.pr) + 1j * np.asarray(out.pi),
                np.asarray(ctx.beta.beta_gk)
                if ctx.beta.num_beta_total else None,
                ctx.beta.qmat, ctx.beta.dion,
                np.asarray(ctx.gkvec.mask, dtype=np.float64),
                x_mix, x_new, ctx.unit_cell.omega, sym_resid=_sym_resid,
            )
            obs_numerics.record_ledger(ledger, it + 1, "host")
        obs_events.emit(
            "scf_iteration", it=it + 1, path="host", rms=rms,
            e_total=e_total, dt=_it_dt,
            # host-path equivalent of the fused [NUM_SCALARS] scalar record
            scalars={"eval_sum": eval_sum, "vha": e["vha"], "vxc": e["vxc"],
                     "exc": e["exc"], "bxc": e["bxc"],
                     "entropy": float(entropy_sum),
                     "scf_correction": scf_correction},
            ledger=ledger,
        )
        if cfg.control.verbosity >= 2:
            # reference per-iteration SCF line (dft_ground_state verbosity 2)
            mg = f" mag={mag_history[-1]:+.4f}" if polarized else ""
            logger.info("it=%3d etot=%+.10f rms=%.3e%s",
                        it + 1, e_total, rms, mg)

        sentinel = sup.observe(it, rms, e_total)
        if sentinel is not None:
            _recover(sentinel)
            continue
        _forecast_tick(it, _it_dt, "host")
        _straggler_tick(it, _it_dt, "host")
        # in-loop precision-headroom probes (obs/numerics.py): shadow
        # re-execution of the post-band stages at degraded precision on
        # the current iterate, every numerics_probe_every iterations
        if (_numerics_probe and out.pr is not None
                and (it + 1) % _numerics_every == 0):
            _sp = _stage("scf.numerics_probe", it=it + 1)
            _stages = obs_numerics.probe_stages(
                ctx, xc, np.asarray(out.pr) + 1j * np.asarray(out.pi), occ_np,
                np.asarray(evals), rho_g, mag_g,
                mixer_beta=mixer.beta, smearing=p.smearing,
                smearing_width=float(p.smearing_width),
            )
            obs_numerics.emit_probe_events(_stages, it=it + 1)
            _sp.close()
        if sup.enabled:
            # host path: the snapshot is a cheap host copy — keep the last
            # finite post-mix state every iteration
            sup.snapshot(it, {
                "x_mix": np.array(x_mix), "e_total": e_total,
                "res_tol": res_tol,
            })
        de = abs(e_total - e_prev) if e_prev is not None else np.inf
        e_prev = e_total
        # fp32 -> fp64 polish switch (reference settings.fp32_to_fp64_rms);
        # when it fires, force at least one fp64 iteration before declaring
        # convergence so the final state is genuinely double precision
        if (
            wf_dtype == jnp.complex64
            and cfg.settings.fp32_to_fp64_rms > 0
            and rms < cfg.settings.fp32_to_fp64_rms
        ):
            wf_dtype = jnp.complex128
            continue
        # autosave AFTER e_prev/precision bookkeeping: the saved state must
        # be exactly what the next iteration of an uninterrupted run would
        # start from (resume-equality is asserted bit-exact on this path)
        if _autosave_every and (it + 1) % _autosave_every == 0:
            _autosave(it)
        if de < p.energy_tol and dens_metric < p.density_tol:
            converged = True
            break
        _straggler_preempt(it)

    _close_iteration()
    obs_trace.finish()
    # everything between the loop's end and the returned result
    _fin_span = obs_spans.open_span("scf.finalize")
    # the steps and chunks every band solve ran leave the device here, and
    # become the counters of the H applications and eigenproblems that ran
    band.book()
    counters["num_spin_channels"] = ns
    # of the context's position-independent table sets (the lattice's and
    # one an atom type), how many an earlier context of the process built
    counters["context_tables_reused"] = ctx.tables_reused
    # the atoms' phases on the fine G set: the tables the job's context
    # built, and the readers that took one instead of building their own
    counters["phase_table_builds"] = int(ctx.phases is not None)
    counters["phase_table_reads"] = ctx.phases.reads if ctx.phases else 0
    # read-only record of the path taken and of where each stage of the last
    # iteration ran and in which dtype, read off the arrays themselves
    placement = {
        "path": band.name_fused if fused is not None else band.name,
        "devices": [str(d) for d in _devs],
        "mesh": None if band.mesh is None else dict(band.mesh.shape),
    }
    if fused is not None and fused_out is not None:
        placement.update(
            band_solve=runtime.where(band.placed()),
            occupations=runtime.where(occ_w),
            density=runtime.where(acc),
            fused_step=runtime.where(fused_out["scalars"]),
            mixing=runtime.where(fused_carry.x_re),
            potential=runtime.where(fused_out["veff_r_coarse"]),
            psi_shard_devices=sorted(
                s.device.id for s in out.pr.addressable_shards),
        )
    elif num_iter_done > it0:
        placement.update(
            band_solve=runtime.where(band.placed()),
            occupations=runtime.where(occ),
            density=runtime.where(rho_spin),
            mixing=runtime.where(x_mix),
            potential=runtime.where(pot.veff_r_coarse),
        )
    # --- final report ---
    if fused is not None and fused_out is not None:
        # one-time exit fetch from the device-resident loop: mixed density,
        # D matrices and dm blocks for forces/stress, plus a host-side
        # potential regeneration so the report/checkpoint path below sees
        # the same PotentialResult fields it always has
        evals = np.asarray(ev_dev, dtype=np.float64)
        fin = fused.finalize(fused_carry, fused_out)
        rho_g = fin["rho_g"]
        mag_g = fin["mag_g"]
        d_by_spin = fin["d_by_spin"]
        rho_resid_g = fin["rho_resid_g"]
        dm_blocks_by_spin = fin["dm_blocks_by_spin"]
        # the reported energy's potential: f64 on the host, with a gradient
        # correction its seven more transforms; the functional and its
        # derivatives are one compiled program on the CPU (dft/xc._host_xc)
        with profile("scf::potential"), obs_spans.span(
                "scf.finalize.potential", xc=fused.xc_kind,
                host_xc="compiled"):
            pot = generate_potential(ctx, rho_g, xc, mag_g)
    psi = band.host_psi()
    if psi is None:
        # num_dft_iter == 0: no band solve ran, so the LCAO block was never
        # rotated; report its first nb rows for shape-valid output ONLY —
        # this truncation must not be persisted as a warm start
        psi = band.psi_big[:, :, :nb] if band.psi_big is not None else None
        keep_state = False
        save_to = None
    occ_np = np.asarray(occ)
    band_gap = _band_gap(evals, occ_np, ctx)
    rho_r = rho_real_space(ctx, rho_g)
    e = pot.energies
    eval_sum = float(np.sum(ctx.kweights[:, None, None] * occ_np * evals))
    e_total = (
        eval_sum - e["vxc"] - e["bxc"] - e.get("vtau_tau", 0.0)
            - 0.5 * e["vha"] + e["exc"] + ctx.e_ewald
        + scf_correction + (e_hub - e_hub_one_el if hub is not None else 0.0)
        + (paw_res["e_total"] - e_paw_one_el if paw is not None else 0.0)
    )
    result = {
        "converged": converged,
        "num_scf_iterations": num_iter_done,
        "gshard_devices": band.gshard_devices,
        "efermi": float(mu),
        "band_gap": band_gap,
        "rho_min": float(rho_r.min()),
        "etot_history": etot_history,
        "rms_history": rms_history,
        "mag_history": mag_history,
        # supervision record (dft/recovery.py): empty ladder_history means
        # the run never needed a rollback
        "recovery": {
            "recoveries": sup.recoveries,
            "rung": sup.rung,
            "ladder_history": list(sup.history),
        },
        "scf_time": time.time() - t0,
        "energy": {
            "total": e_total,
            "free": e_total + float(entropy_sum),
            "eval_sum": eval_sum,
            "kin": eval_sum - e["veff"] - e["bxc"] - e.get("vtau_tau", 0.0),
            "veff": e["veff"],
            "vha": e["vha"],
            "vxc": e["vxc"],
            "vloc": e["vloc"],
            "exc": e["exc"],
            "bxc": e["bxc"],
            "ewald": ctx.e_ewald,
            "entropy_sum": float(entropy_sum),
            "scf_correction": scf_correction,
            "hubbard": e_hub if hub is not None else 0.0,
            "hubbard_one_el": e_hub_one_el if hub is not None else 0.0,
            "paw_total_energy": paw_res["e_total"] if paw is not None else 0.0,
            "paw_one_elec": e_paw_one_el if paw is not None else 0.0,
        },
        "band_energies": evals.tolist(),
        "band_occupancies": occ_np.tolist(),
        "counters": dict(counters),
        "timers": timer_report(),
        "placement": placement,
    }
    # convergence-forecast summary (obs/forecast.py via the supervisor):
    # consumed by serve/scheduler.py (deadline triage) and campaigns
    _fc_snap = sup.forecast_snapshot()
    result["forecast"] = {
        "enabled": bool(sup.enabled and sup.forecast_enabled),
        "decay_rate": _fc_snap.get("decay_rate") if _fc_snap else None,
        "forecast_total": _fc_snap.get("forecast_total") if _fc_snap else None,
        "forecast_remaining": (
            _fc_snap.get("forecast_remaining") if _fc_snap else None),
        "warning": _fc_snap.get("warning") if _fc_snap else None,
        "warnings_total": _fc_warnings,
        "actual_iterations": num_iter_done,
    }
    # end-of-run precision-headroom probe on the final iterate (both
    # paths; the in-loop cadence above only covers the host path)
    if _numerics_probe and num_iter_done > 0 and psi is not None:
        _sp = _stage("scf.numerics_probe", it=num_iter_done)
        _stages = obs_numerics.probe_stages(
            ctx, xc, np.asarray(psi), occ_np, np.asarray(evals),
            rho_g, mag_g, mixer_beta=mixer.beta, smearing=p.smearing,
            smearing_width=float(p.smearing_width),
        )
        obs_numerics.emit_probe_events(_stages, it=num_iter_done)
        _sp.close()
        result["numerics"] = _stages
    _RUNS.inc(outcome="converged" if converged else "unconverged")
    # what one step of the loop's energy could resolve: the fused step's
    # summed terms arrive as two words each (core/hilo.py), the host tail's
    # as one float64
    obs_events.emit(
        "scf_done", converged=converged, iterations=num_iter_done,
        e_total=e_total, recoveries=sup.recoveries, wall_s=result["scf_time"],
        num_loc_op_applied=int(counters["num_loc_op_applied"]),
        num_fft_boxes=int(counters["num_fft_boxes"]),
        num_subspace_eigh=int(counters["num_subspace_eigh"]),
        num_complex_subspace_eigh=int(
            counters["num_complex_subspace_eigh"]),
        num_davidson_steps=int(counters["num_davidson_steps"]),
        num_tail_box_fills=int(counters["num_tail_box_fills"]),
        num_density_rows=int(counters["num_density_rows"]),
        num_sym_pw=int(counters["num_sym_pw"]),
        num_xc_gradient_transforms=int(
            counters["num_xc_gradient_transforms"]),
        num_fused_step_traces=int(counters["num_fused_step_traces"]),
        num_host_xc_traces=int(counters["num_host_xc_traces"]),
        num_spin_channels=int(counters["num_spin_channels"]),
        context_tables_reused=int(counters["context_tables_reused"]),
        energy_resolution_ha=abs(e_total) * pair_eps(
            fused.rdt if fused is not None else np.float64),
    )
    if hub is not None:
        result["_hubbard_v"] = vhub  # ndarray, consumed by the band-path task
    if keep_state:
        # in-memory state for warm starts across geometry steps; the "scf"
        # sub-dict (mixer history + final band tolerance) lets the NEXT run
        # warm-start the quasi-Newton model too, not just the density (fed
        # back through initial_state= or initial_guess=(rho, psi, scf))
        if fused is not None and fused_carry is not None:
            _, _hist = fused.fetch_state(fused_carry, with_history=True)
        else:
            _hist = mixer.export_history()
        result["_state"] = {
            "rho_g": np.asarray(rho_g),
            "mag_g": None if mag_g is None else np.asarray(mag_g),
            "psi": np.asarray(psi),
            "paw_dm": None if paw_dm is None else np.asarray(paw_dm),
            "scf": (dict(_hist, res_tol=float(res_tol)) if _hist else None),
        }
    if polarized:
        result["magnetisation"] = {
            "total": [0.0, 0.0, float(np.real(mag_g[0]) * ctx.unit_cell.omega)],
            "atoms": [
                [0.0, 0.0, float(mz)] for mz in atomic_moments(ctx, mag_g)
            ],
        }
    if cfg.control.print_forces and num_iter_done > 0:
        from sirius_tpu.dft.forces import total_forces

        fterms = total_forces(
            ctx, rho_g, pot.vxc_g, pot.veff_g, pot.bz_g, psi, occ_np, evals,
            d_by_spin, dm_blocks_by_spin, rho_resid_g=rho_resid_g,
        )
        if hub is not None:
            from sirius_tpu.dft.forces import forces_hubbard, symmetrize_forces

            if hub.nonloc or getattr(
                ctx.cfg.hubbard, "hubbard_subspace_method", "none"
            ) == "full_orthogonalization":
                # the inter-site +V occupancy derivative and the
                # full_orthogonalization O^{-1/2} derivative are not
                # implemented; adding the bare-phi local term on top of
                # orbitals that were actually O^{-1/2}-mixed would be
                # inconsistent — skip the Hubbard force entirely (the
                # reference computes forces only for the simple local
                # correction, hubbard_occupancies_derivatives.cpp)
                import warnings

                warnings.warn(
                    "Hubbard force term SKIPPED: +V / full_orthogonalization "
                    "occupancy derivatives are not implemented; reported "
                    "forces omit the Hubbard contribution"
                )
            else:
                fh = forces_hubbard(
                    ctx, hub, um_local, psi, occ_np, ctx.max_occupancy
                )
                fterms["hubbard"] = fh
                fterms["total"] = symmetrize_forces(ctx, fterms["total"] + fh)
        result["forces"] = fterms["total"].tolist()
    if cfg.control.print_stress and num_iter_done > 0:
        from sirius_tpu.dft.stress import StressCalculator

        if mgga:
            # StressCalculator evaluates the XC functional without tau and
            # the tau-operator stress term is not implemented: computing a
            # plausibly-sized wrong tensor silently is worse than refusing
            raise NotImplementedError("stress with mGGA is not implemented")
        calc = StressCalculator(ctx, xc)
        sterms = calc.compute(
            rho_g, mag_g, rho_r,
            rho_real_space(ctx, mag_g) if polarized else None,
            psi, occ_np, evals, d_by_spin,
            dm_blocks_by_spin=dm_blocks_by_spin if ctx.aug is not None else None,
            hub=hub,
        )
        result["stress"] = sterms["total"].tolist()
    if save_to:
        from sirius_tpu.io.checkpoint import save_state

        save_state(
            save_to, ctx, rho_g, mag_g, pot.veff_g, pot.bz_g,
            np.asarray(psi), evals, occ_np, paw_dm=paw_dm,
        )
    _fin_span.close()
    return result


def _band_gap(evals: np.ndarray, occ: np.ndarray, ctx: SimulationContext) -> float:
    tol = 1e-6 * ctx.max_occupancy
    occupied = evals[occ > ctx.max_occupancy - 1e-4]
    empty = evals[occ < tol]
    if len(occupied) == 0 or len(empty) == 0:
        return 0.0
    gap = float(empty.min() - occupied.max())
    # metallic if partial occupancies straddle
    partial = (occ > tol) & (occ < ctx.max_occupancy - 1e-4)
    if np.any(partial) and gap < 1e-8:
        return 0.0
    return max(gap, 0.0)


def run_scf_from_file(
    path: str, test_against: str | None = None, task: str = "ground_state_new"
) -> int:
    import os

    cfg = load_config(path)
    base_dir = os.path.dirname(os.path.abspath(path))
    state_file = os.path.join(base_dir, "sirius.h5")
    if cfg.parameters.electronic_structure_method == "full_potential_lapwlo":
        # FP-LAPW branch (reference dft_ground_state FP path); tasks other
        # than the ground state are PP-PW-only for now
        if task not in ("ground_state_new", "ground_state"):
            raise NotImplementedError(
                f"FP-LAPW task '{task}' not supported yet (ground state only)"
            )
        from sirius_tpu.lapw.scf_fp import run_scf_fp

        result = run_scf_fp(cfg, base_dir)
        out = {"ground_state": result, "task": task, "context": {}}
        with open("output.json", "w") as f:
            json.dump(out, f, indent=2, default=float)
        if test_against:
            with open(test_against) as f:
                refgs = json.load(f)["ground_state"]
            de = abs(refgs["energy"]["total"] - result["energy"]["total"])
            print(f"total energy difference: {de:.3e}")
            if de >= 1e-5:
                import sys as _sys

                print(
                    f"sirius-scf: test_against FAILED: |dE_total|={de:.3e} "
                    "(tol 1e-05)", file=_sys.stderr,
                )
                return 1
            return 0
        return 0
    ref = None
    if test_against:
        with open(test_against) as f:
            ref = json.load(f)["ground_state"]
        # a reference quantity we would silently not compute is a failed
        # comparison waiting to happen — switch the calculations on
        if "forces" in ref:
            cfg.control.print_forces = True
        if "stress" in ref:
            cfg.control.print_stress = True
    if task == "ground_state_relax":
        from sirius_tpu.dft.relax import relax_atoms

        rr = relax_atoms(cfg, base_dir)
        result = rr["ground_state"]
        result["relaxation"] = {k: rr[k] for k in ("converged", "num_steps", "history", "final_positions")}
    elif task == "ground_state_restart":
        # prefer a mid-SCF autosave (continues the interrupted run with the
        # full mixer/psi/tolerance state); fall back to the density-only
        # warm start from the converged state file
        from sirius_tpu.io.checkpoint import find_resumable

        auto = cfg.control.autosave_path or default_autosave_path(
            cfg, base_dir)
        resume_path = find_resumable(
            auto, keep=int(getattr(cfg.control, "autosave_keep", 0)))
        if resume_path is not None:
            result = run_scf(cfg, base_dir, resume=resume_path,
                             save_to=state_file)
        else:
            result = run_scf(cfg, base_dir, restart_from=state_file,
                             save_to=state_file)
    elif task == "ground_state_direct":
        from sirius_tpu.dft.direct_min import run_direct_min

        result = run_direct_min(cfg, base_dir)
    elif task == "k_point_path":
        from sirius_tpu.context import SimulationContext
        from sirius_tpu.dft.bands import band_path, sample_path
        from sirius_tpu.dft.xc import XCFunctional

        if XCFunctional(cfg.parameters.xc_functionals).is_mgga:
            # the saved state carries no tau and band_path applies the
            # tau-less operator; fail BEFORE the (long) SCF, not after
            raise NotImplementedError("k_point_path with mGGA")
        # vk defines the band path, NOT the SCF mesh (reference task
        # semantics: SCF on ngridk, then bands along vk)
        vk_path = list(cfg.parameters.vk)
        cfg.parameters.vk = []
        ctx = SimulationContext.create(cfg, base_dir)
        result = run_scf(cfg, base_dir, save_to=state_file, ctx=ctx)
        cfg.parameters.vk = vk_path  # restore: the echoed config must match
        from sirius_tpu.dft.potential import generate_potential
        from sirius_tpu.io.checkpoint import load_state
        from sirius_tpu.ops.augmentation import d_operator

        state = load_state(state_file, ctx)
        xc = XCFunctional(cfg.parameters.xc_functionals)
        pot = generate_potential(ctx, state["rho_g"], xc, state.get("mag_g"))
        # screened per-spin D (ultrasoft) — same operator the SCF solved with
        if ctx.aug is not None:
            d_full = np.stack([
                d_operator(
                    ctx.unit_cell, ctx.gvec, ctx.aug,
                    pot.veff_g + (0 if pot.bz_g is None else (pot.bz_g if ispn == 0 else -pot.bz_g)),
                    ctx.beta, phases=ctx.phases,
                )
                for ispn in range(ctx.num_spins)
            ])
        else:
            d_full = None
        vk = vk_path if vk_path else [[0, 0, 0], [0.5, 0, 0]]
        result["band_path"] = band_path(
            ctx, pot, sample_path(np.asarray(vk)), d_full=d_full,
            vhub=result.get("_hubbard_v"),
        )
    else:  # ground_state_new
        result = run_scf(cfg, base_dir, save_to=state_file)
    result.pop("_hubbard_v", None)  # ndarray, not JSON-serializable
    result.pop("_state", None)
    out = {
        "ground_state": result,
        "task": task,
        "config": cfg.to_dict(),
        "git_hash": "",
        "comm_world_size": 1,
    }
    summary = {"energy": result["energy"], "efermi": result["efermi"],
               "converged": result["converged"],
               "num_scf_iterations": result["num_scf_iterations"]}
    if "magnetisation" in result:
        summary["magnetisation"] = result["magnetisation"]
    print(json.dumps(summary, indent=2))
    with open("output.json", "w") as f:
        json.dump(out, f, indent=2)
    if ref is not None:
        ok = True
        fails = []
        de = abs(ref["energy"]["total"] - result["energy"]["total"])
        print(f"|dE_total| vs reference: {de:.3e}")
        if de >= 1e-5:
            ok = False
            fails.append(f"|dE_total|={de:.3e} (tol 1e-05)")
        for key, label, tol in (("forces", "|dF|_max", 1e-5), ("stress", "|dsigma|_max", 1e-5)):
            if key in ref:
                if key not in result:
                    print(f"{key}: present in reference but not computed -> FAIL")
                    ok = False
                    fails.append(f"{key} missing from result")
                    continue
                d = float(np.abs(np.asarray(ref[key]) - np.asarray(result[key])).max())
                print(f"{label} vs reference: {d:.3e}")
                if d >= tol:
                    ok = False
                    fails.append(f"{label}={d:.3e} (tol {tol:g})")
        print("TEST PASSED" if ok else "TEST FAILED")
        if not ok:
            # one-line machine-greppable diff summary on stderr: the serve
            # engine and CI use the exit code + this line as the probe
            import sys as _sys

            print(
                "sirius-scf: test_against FAILED: " + "; ".join(fails),
                file=_sys.stderr,
            )
            return 1
        return 0
    return 0
