"""Device-slice scheduler: concurrent SCF jobs over a partitioned mesh.

The global device list is split into ``num_slices`` contiguous slices;
one worker thread drains the queue per slice (thread-per-slice — XLA
execution releases the GIL, so slices genuinely overlap on CPU tests and
would on real accelerators). Each job runs through the normal run_scf
machinery — ScfSupervisor ladder, control.autosave_every checkpoints —
with a job-scoped autosave path, so a failed or preempted job is retried
and *resumed* from its newest valid autosave rather than restarted.

Failure classification:
  transient  -> requeue (up to job.max_retries) with exponential backoff
               (``job.not_before``, jittered, never past the deadline),
               resuming from autosave: SimulatedKill (injected
               preemption, class ``preempted``), ScfAbortError
               (supervisor ladder exhausted — a rollback snapshot may
               still converge from the autosave, class ``scf_abort``),
               CheckpointError (bad autosave: the resume path is cleared
               first, class ``bad_checkpoint``), OSError (class ``io``),
               plus watchdog hand-backs (class ``crash``/``hang``).
  device     -> backend errors classified by the utils/devfail.py
               taxonomy instead of falling into the permanent catch-all:
               ``oom`` retries with a degradation hint (the next attempt
               runs on a smaller memory plan — apply_oom_hint),
               ``device_lost`` shrinks the slice to its surviving
               devices and resumes from autosave on the smaller mesh,
               ``straggler`` (StragglerPreempt from run_scf's watchdog)
               parks the slice behind a cooldown so the retry lands on
               healthy hardware, ``transient`` plain-retries. All are
               preemption semantics: device evidence is against the
               hardware, never a poison strike against the deck.
  permanent  -> failed, never retried: UpfParseError and other
               ValueError/NotImplementedError/KeyError deck problems —
               re-running bad input cannot succeed — unclassifiable
               unexpected exceptions, and poison quarantine
               (serve/supervisor.py).

Workers are supervised (serve/supervisor.py): they heartbeat every poll
cycle, register the job they run, and are respawned by the watchdog when
they die or hang. Each attempt captures ``job._epoch`` at pickup; a
worker whose job was taken away by the watchdog discards its outcome
instead of clobbering the job's new life.

Campaign nodes (sirius_tpu.campaigns) ride the same path with three
extra steps: a ``handoff_in`` artifact is loaded into
``run_scf(initial_guess=)`` (degrading to a cold start on damage or
shape mismatch — campaigns/handoff.py), a top-level ``task: "relax"``
deck key dispatches dft/relax.py instead of a single SCF, and on DONE a
``handoff_out`` artifact is written *before* the terminal transition so
the journal's DONE record always implies a durable artifact for the
children. The ``campaign.node_fail`` fault site preempts a node attempt
before its SCF to drive the SKIPPED_UPSTREAM cascade in tests.
"""

from __future__ import annotations

import logging
import os
import random
import threading
import time

from sirius_tpu.obs import events as obs_events
from sirius_tpu.obs import metrics as obs_metrics
from sirius_tpu.obs import spans as obs_spans
from sirius_tpu.obs import tracing as obs_tracing
from sirius_tpu.obs.log import get_logger, job_context
from sirius_tpu.serve import cache as cache_mod
from sirius_tpu.serve.queue import Job, JobQueue, JobStatus
from sirius_tpu.serve.supervisor import SliceSupervisor
from sirius_tpu.utils import devfail
from sirius_tpu.utils import faults
from sirius_tpu.utils.profiler import counters

logger = get_logger("serve")

_RUN_SECONDS = obs_metrics.REGISTRY.histogram(
    "serve_job_run_seconds", "per-attempt SCF wall time by bucket warmth")
_RETRIES = obs_metrics.REGISTRY.counter(
    "serve_job_retries_total", "transient-failure retries by failure class")
_FAILURES = obs_metrics.REGISTRY.counter(
    "serve_job_failures_total", "terminal job failures")
_BACKOFF = obs_metrics.REGISTRY.histogram(
    "serve_backoff_seconds", "retry backoff delays by failure class")
_NODE_ITERS = obs_metrics.REGISTRY.counter(
    "campaign_node_scf_iterations_total",
    "SCF iterations spent on campaign nodes, by warm/cold handoff")
# same family run_scf updates mid-run (dft/scf.py); serve re-publishes the
# terminal forecast per slice so dashboards see it after the job finishes
_FORECAST_ITERS = obs_metrics.REGISTRY.gauge(
    "scf_forecast_iterations",
    "forecasted total SCF iterations to convergence (obs/forecast.py)")

# SimulationContext building for synthetic decks monkeypatches
# UnitCell.from_config (testing.py idiom); serialize every context build
# so concurrent workers never see each other's patch
_CTX_LOCK = threading.Lock()


def build_job_context(cfg, base_dir: str = "."):
    """SimulationContext for a deck Config.

    A ``synthetic`` extra section ({"ultrasoft": bool, "positions": [...],
    "supercell": n, "a": lattice const, "species": "si" | "dshell",
    "moments": [mx, my, mz] for all atoms or one such vector an atom})
    builds an in-memory test species (sirius_tpu.testing: the Si-like one,
    or the one with an open d-like shell) instead of reading species files
    — the species-file-free deck form used by tests and tools/loadgen.py.
    ``moments`` are the atoms' starting moments (they matter where
    ``num_mag_dims`` is 1); an unknown species or a moments array of the
    wrong length raises here. Everything else (cutoffs, k-mesh, control
    knobs incl. ngk_pad_quantum) comes from the normal config sections.

    Spanned here (``serve.context_build``), not at the call sites: the
    scheduler, the MD driver and a plain ``run_scf`` client all get it.

    The context shares its position-independent tables (G-vector sets,
    k-spheres, each species' tables on them) with every earlier context of
    the process on the same lattice, cutoffs, k-points and species content,
    read-only (`SimulationContext.create`); the species is compared by its
    arrays, so the fresh ``AtomType`` built here every job finds them.
    ``ctx.tables_reused`` says how many of the 1 + (atom types) table sets
    were found; the child spans
    ``context.lattice_tables``, ``context.species_tables`` (``hit``,
    ``bytes``) and ``context.positions`` split the build.
    """
    with obs_spans.span("serve.context_build"):
        return _build_job_context(cfg, base_dir)


def _build_job_context(cfg, base_dir: str):
    from sirius_tpu.context import SimulationContext

    syn = cfg.extra.get("synthetic") if isinstance(cfg.extra, dict) else None
    if not syn:
        with _CTX_LOCK:
            return SimulationContext.create(cfg, base_dir)

    from sirius_tpu.testing import context_of_cell, synthetic_cell

    uc = synthetic_cell(
        species=str(syn.get("species", "si")),
        ultrasoft=bool(syn.get("ultrasoft", True)),
        a=float(syn.get("a", 10.26)),
        positions=syn.get("positions"),
        supercell=int(syn.get("supercell", 1)),
        moments=syn.get("moments"),
    )
    with _CTX_LOCK:
        return context_of_cell(cfg, uc, base_dir)


class SliceScheduler:
    """Partition ``devices`` into ``num_slices`` and drain ``queue``."""

    def __init__(self, queue: JobQueue, exec_cache, num_slices: int = 1,
                 devices=None, autosave_every: int = 3,
                 autosave_keep: int = 2, verbose: bool = False,
                 poison_threshold: int = 2,
                 job_wall_time_budget: float | None = None,
                 watchdog_interval: float = 0.25,
                 backoff_base: float = 0.5, backoff_max: float = 30.0,
                 backoff_jitter: float = 0.1,
                 straggler_cooldown: float = 5.0):
        import jax

        self.queue = queue
        self.cache = exec_cache
        devices = list(devices) if devices is not None else jax.devices()
        num_slices = max(1, min(int(num_slices), len(devices)))
        per = len(devices) // num_slices
        self.slices = [
            devices[i * per:(i + 1) * per] for i in range(num_slices)
        ]
        # leftover devices join the last slice rather than idling
        self.slices[-1].extend(devices[num_slices * per:])
        self.autosave_every = int(autosave_every)
        self.autosave_keep = int(autosave_keep)
        self.verbose = verbose
        self.backoff_base = float(backoff_base)
        self.backoff_max = float(backoff_max)
        self.backoff_jitter = float(backoff_jitter)
        self.straggler_cooldown = float(straggler_cooldown)
        self.supervisor = SliceSupervisor(
            self, poison_threshold=poison_threshold,
            job_wall_time_budget=job_wall_time_budget,
            interval=watchdog_interval,
        )

    def start(self) -> None:
        self.supervisor.start()

    def join(self, timeout: float | None = None) -> None:
        self.supervisor.join(timeout)

    def stop_supervision(self) -> None:
        self.supervisor.stop()

    def _worker(self, idx: int, devs) -> None:
        sup = self.supervisor
        while True:
            sup.beat(idx)
            if not sup.slice_available(idx):
                # degradation cooldown (straggler): leave queued work to
                # the healthy slices until the deadline passes
                if self.queue.closed and len(self.queue) == 0:
                    return
                time.sleep(0.05)
                continue
            job = self.queue.pop(timeout=0.5)
            if job is None:
                if self.queue.closed and len(self.queue) == 0:
                    return
                continue
            epoch = job._epoch
            sup.note_job(idx, job, epoch)
            # a WorkerCrash (or any other BaseException) propagates past
            # note_idle: the thread dies with the job still registered,
            # which is exactly what the watchdog recovers from
            self._run_job(job, idx, devs, epoch)
            sup.note_idle(idx, job)

    def _run_job(self, job: Job, slice_idx: int, devs, epoch: int) -> None:
        job.attempts += 1
        if job.trace_id is None and job.handoff_in:
            # a job joining a DAG without engine.submit's assignment:
            # continue the trace stored in the parent's handoff artifact
            from sirius_tpu.campaigns import handoff as handoff_mod

            job.trace_id = handoff_mod.artifact_trace_id(
                job.handoff_in.get("path"))
        if job.trace_id is None:
            # direct queue users bypass engine.submit; give the job a
            # trace here so every attempt still has end-to-end identity
            job.trace_id = obs_tracing.new_trace_id()
        # every log line and obs event inside the attempt carries job.id,
        # and every span/event/exemplar the job's trace_id — across
        # worker threads, retries, and (via the journal) process restarts
        with obs_tracing.trace_context(job.trace_id), job_context(job.id):
            if faults.armed("serve.worker_crash", job.attempts - 1):
                raise faults.WorkerCrash(
                    f"fault serve.worker_crash (job {job.id} "
                    f"attempt {job.attempts})")
            if faults.armed("serve.job_hang", job.attempts - 1):
                self._hang(job, slice_idx, epoch)
                return
            # the job as this worker has it: pop to terminal transition
            # (or to the retry's requeue); the context build, the run
            # and the queue wait that ended at its start hang under it
            with obs_spans.span("serve.job", slice=slice_idx,
                                attempt=job.attempts) as job_span:
                self._run_job_inner(job, slice_idx, devs, epoch)
                job_span.set(status=str(getattr(job.status, "value",
                                                job.status)))

    def _hang(self, job: Job, slice_idx: int, epoch: int) -> None:
        """Simulate a wedged worker (serve.job_hang): park until the
        watchdog abandons the job (epoch bump) — never transition it."""
        job._transition(JobStatus.RUNNING, f"slice {slice_idx} (hung)")
        t0 = time.time()
        while job._epoch == epoch and time.time() - t0 < 120.0:
            time.sleep(0.02)
        logger.info("hung attempt of job %s unparked (%s)", job.id,
                    "abandoned" if job._epoch != epoch else "timed out")

    def _stale(self, job: Job, epoch: int) -> bool:
        """True when the watchdog took this job away mid-attempt: the
        outcome of the attempt must be discarded, not applied."""
        if job._epoch != epoch:
            logger.warning("discarding stale attempt outcome for job %s "
                           "(abandoned by the watchdog)", job.id)
            return True
        return False

    def _load_handoff(self, job: Job, ctx):
        """Load the parent artifact named by ``job.handoff_in`` into an
        ``initial_guess`` for run_scf.

        Degrades rather than fails: a missing/partial artifact or one
        whose shapes don't match this node's context gives a cold start
        (mode ``"missing"``/``"cold"``); a corrupt one (non-finite
        payload, campaign.handoff_corrupt fault site) is dropped with
        mode ``"corrupt_fallback"``. Only a usable (rho, psi) pair
        reaches run_scf, so the ValueError shape guard there — a
        permanent-failure class — can never fire on handoff data."""
        from sirius_tpu.campaigns import handoff as handoff_mod

        path = job.handoff_in.get("path")
        displaced = bool(job.handoff_in.get("displaced", True))
        guess = None
        try:
            guess = handoff_mod.load_guess(path, ctx, displaced=displaced)
            mode = "warm" if guess is not None else "missing"
        except handoff_mod.HandoffError as e:
            logger.warning("job %s: corrupt handoff artifact %s (%s); "
                           "falling back to a cold start", job.id, path, e)
            mode = "corrupt_fallback"
        obs_events.emit("campaign_handoff", job_id=job.id,
                        campaign_id=job.campaign_id, node_id=job.node_id,
                        mode=mode, displaced=displaced)
        return guess, mode

    def _run_job_inner(self, job: Job, slice_idx: int, devs,
                       epoch: int) -> None:
        import time as _time

        import jax

        from sirius_tpu.config.schema import load_config
        from sirius_tpu.dft.recovery import ScfAbortError
        from sirius_tpu.dft.scf import run_scf
        from sirius_tpu.io.checkpoint import CheckpointError
        from sirius_tpu.io.upf import UpfParseError
        from sirius_tpu.utils.devfail import StragglerPreempt
        from sirius_tpu.utils.faults import SimulatedKill

        cfg = None
        try:
            if job.campaign_id:
                # test/chaos hook: preempt a campaign node attempt before
                # any SCF work (retries, then SKIPPED_UPSTREAM cascade)
                faults.check("campaign.node_fail", job.attempts - 1)
            deck = dict(job.deck)
            task = deck.get("task") or "scf"
            if job.handoff_in and job.handoff_in.get("adopt_positions"):
                from sirius_tpu.campaigns import handoff as handoff_mod

                # run at the geometry the parent settled on (relax->SCF
                # chains); a missing artifact raises OSError = retryable
                deck = handoff_mod.adopt_positions(
                    deck, job.handoff_in["path"])
            cfg = load_config(deck)
            job._cfg = cfg  # watchdog retries refresh the resume path
            # serve defaults: job-scoped autosaves with rotation so every
            # job is resumable and none clobbers a neighbour's checkpoint
            if not cfg.control.autosave_tag and not cfg.control.autosave_path:
                cfg.control.autosave_tag = job.id
            if not cfg.control.autosave_every:
                cfg.control.autosave_every = self.autosave_every
            if not cfg.control.autosave_keep:
                cfg.control.autosave_keep = self.autosave_keep
            if job.deadline is not None and not cfg.control.deadline_ts:
                # forecast-driven deadline triage: run_scf emits
                # deadline_feasibility events against this bound as its
                # iterations-to-converge forecast evolves (obs/forecast.py)
                cfg.control.deadline_ts = float(job.deadline)
            if cfg.control.straggler_detect == "auto":
                # straggler watchdog on by default under serve only: a
                # slow slice preempts the run at a snapshot boundary and
                # the retry resumes on healthy hardware (dft/scf.py)
                cfg.control.straggler_detect = True
            if job.oom_degrade:
                # a previous attempt died of HBM exhaustion below the
                # in-run ladder's reach: start this one pre-degraded
                applied = devfail.apply_oom_hint(
                    cfg.control, job.oom_degrade)
                logger.warning(
                    "job %s retrying at OOM degradation level %d: %s",
                    job.id, job.oom_degrade, ",".join(applied))
            ctx = build_job_context(cfg, job.base_dir)
            key = cache_mod.bucket_key(cfg, ctx)
            warm = self.cache.note_job(key)
            job._transition(
                JobStatus.RUNNING if warm else JobStatus.COMPILING,
                f"slice {slice_idx}, bucket {'warm' if warm else 'cold'}",
            )
            if job.started_at is None:
                job.started_at = job.events[-1][0]
            job_span = obs_spans.current()  # serve.job; None: telemetry off
            if job.submitted_at is not None and job_span is not None:
                # measured from outside: submit -> this worker popping it
                # (where serve.job starts), a wait that began before any
                # worker had the job
                t_sub = int(job.submitted_at * 1e9)
                obs_spans.record(
                    "serve.queue_wait", start_unix_ns=t_sub,
                    end_unix_ns=max(t_sub, job_span.start_unix_ns),
                    slice=slice_idx, bucket="warm" if warm else "cold")
            guess = None
            handoff_mode = None
            if job.handoff_in:
                guess, handoff_mode = self._load_handoff(job, ctx)
            keep_state = bool(job.handoff_out)
            compiles0 = cache_mod.backend_compiles_this_thread()
            csec0 = obs_metrics.backend_compile_seconds_this_thread()
            t_run0 = _time.time()
            final_positions = None
            with obs_spans.span("serve.run", slice=slice_idx,
                                bucket="warm" if warm else "cold") as run_span:
                with jax.default_device(devs[0]):
                    if task == "relax":
                        from sirius_tpu.dft.relax import relax_atoms

                        relax_args = (
                            deck.get("relax")
                            if isinstance(deck.get("relax"), dict) else {})
                        rr = relax_atoms(
                            cfg, base_dir=job.base_dir,
                            max_steps=int(relax_args.get("max_steps", 30)),
                            force_tol=float(
                                relax_args.get("force_tol", 1e-4)),
                            ctx=ctx, exec_cache=self.cache, devices=devs,
                        )
                        gs = rr["ground_state"]
                        final_positions = rr["final_positions"]
                        result = {
                            "task": "relax",
                            "converged": rr["converged"],
                            "energy": gs["energy"],
                            "num_scf_iterations": sum(
                                h["scf_iterations"] for h in rr["history"]),
                            "forces": gs.get("forces"),
                            "_state": gs.get("_state"),
                            "relax": {
                                k: rr[k] for k in (
                                    "converged", "num_steps", "history",
                                    "final_positions")
                            },
                        }
                    else:
                        result = run_scf(
                            cfg, base_dir=job.base_dir, ctx=ctx,
                            exec_cache=self.cache, devices=devs,
                            resume=job.resume_path,
                            initial_guess=guess, keep_state=keep_state,
                        )
                compiled = (cache_mod.backend_compiles_this_thread()
                            - compiles0)
                # compile time attributed via the jax.monitoring listener's
                # per-thread accumulator: run_scf happened on THIS thread,
                # so the delta is exactly this job's XLA backend-compile
                # seconds. Fields of serve.run, not a span: compilation is
                # spread over the run and has no start of its own
                csec = (obs_metrics.backend_compile_seconds_this_thread()
                        - csec0)
                run_span.set(compile_s=csec, compiled_executables=compiled)
            _RUN_SECONDS.observe(_time.time() - t_run0,
                                 bucket="warm" if warm else "cold",
                                 slice=slice_idx)
            counters["serve.backend_compiles"] += compiled
            state = result.pop("_state", None)
            result["serve"] = {
                "job_id": job.id,
                "slice": slice_idx,
                "attempts": job.attempts,
                "bucket_warm": warm,
                "compiled_executables": compiled,
                "warm_start": guess is not None,
                "handoff": handoff_mode,
                "forecast": result.get("forecast"),
            }
            _fc = result.get("forecast") or {}
            if _fc.get("forecast_total") is not None:
                _FORECAST_ITERS.set(float(_fc["forecast_total"]),
                                    slice=str(slice_idx))
            if self._stale(job, epoch):
                return
            if job.handoff_out:
                from sirius_tpu.campaigns import handoff as handoff_mod

                # artifact before the terminal transition: a journaled
                # DONE record must imply a durable artifact, or a replay
                # could skip a node whose children have nothing to load
                handoff_mod.save_artifact(
                    job.handoff_out, ctx, result, state,
                    positions=final_positions)
            if job.campaign_id:
                _NODE_ITERS.inc(
                    int(result.get("num_scf_iterations") or 0),
                    warm="true" if guess is not None else "false")
            job.result = result
            job._transition(
                JobStatus.DONE,
                f"E={result['energy']['total']:.10f} "
                f"compiled={compiled}",
            )
        except StragglerPreempt as e:
            # before SimulatedKill: StragglerPreempt subclasses it. The
            # slice, not the deck, is slow — park it behind a cooldown so
            # the retry lands on healthy hardware; never a strike.
            if self._stale(job, epoch):
                return
            self.supervisor.degrade_slice(
                slice_idx, "straggler", cooldown=self.straggler_cooldown)
            self._retry(job, cfg, f"straggler preempt: {e}", "straggler")
        except SimulatedKill as e:
            if self._stale(job, epoch):
                return
            self._retry(job, cfg, f"preempted: {e}", "preempted")
        except CheckpointError as e:
            if self._stale(job, epoch):
                return
            # the autosave we tried to resume from is unusable: retry from
            # scratch rather than looping on the same bad file
            job.resume_path = None
            self._retry(job, cfg, f"bad checkpoint: {e}", "bad_checkpoint",
                        resume=False)
        except UpfParseError as e:
            if self._stale(job, epoch):
                return
            self._fail(job, f"UPF parse error: {e}", permanent=True)
        except (ValueError, NotImplementedError, KeyError) as e:
            if self._stale(job, epoch):
                return
            self._fail(job, f"bad deck: {type(e).__name__}: {e}",
                       permanent=True)
        except ScfAbortError as e:
            if self._stale(job, epoch):
                return
            if e.diagnostic.get("sentinel") == "device_oom":
                # the in-run OOM ladder ran out of rungs: retry under the
                # ``oom`` class with the same rungs pre-applied, so the
                # next attempt starts on the smaller memory plan instead
                # of re-climbing the ladder from scratch
                job.oom_degrade = min(job.oom_degrade + 1, 3)
                self._retry(job, cfg, f"scf aborted on device OOM: {e}",
                            "oom")
            else:
                self._retry(job, cfg, f"scf aborted: {e}", "scf_abort")
        except OSError as e:
            if self._stale(job, epoch):
                return
            self._retry(job, cfg, f"io error: {e}", "io")
        except Exception as e:  # a serving worker must outlive any job
            if self._stale(job, epoch):
                return
            cls = devfail.classify(e)
            if cls == "oom":
                # HBM exhaustion that unwound past run_scf's in-run ladder
                # (e.g. from inside a compiled program): retry with a
                # degradation hint so the next attempt starts on a
                # smaller memory plan (devfail.apply_oom_hint above)
                job.oom_degrade = min(job.oom_degrade + 1, 3)
                self._retry(job, cfg, f"device OOM: {e}", "oom")
            elif cls == "device_lost":
                # hardware evidence against the slice, not the job:
                # shrink the slice to its surviving devices and resume
                # from autosave on the smaller mesh — preemption
                # semantics, never a poison strike
                self.supervisor.degrade_slice(
                    slice_idx, "device_lost", drop_devices=1)
                self._retry(job, cfg, f"device lost: {e}", "device_lost")
            elif cls == "transient":
                self._retry(job, cfg, f"transient backend error: {e}",
                            "transient")
            else:
                self._fail(job, f"unexpected {type(e).__name__}: {e}",
                           permanent=True)

    def _backoff_delay(self, job: Job) -> float:
        """Exponential backoff with jitter, clamped so the retry can never
        be pushed past the job's deadline (a late answer is a wrong
        answer — better to retry sooner than to abort unrun)."""
        delay = min(self.backoff_max,
                    self.backoff_base * (2.0 ** max(0, job.attempts - 1)))
        delay *= 1.0 + self.backoff_jitter * random.random()
        if job.deadline is not None:
            delay = max(0.0, min(delay, job.deadline - time.time()))
        return delay

    def _retry(self, job: Job, cfg, detail: str, failure_class: str,
               resume: bool = True) -> None:
        from sirius_tpu.dft.scf import default_autosave_path
        from sirius_tpu.io.checkpoint import find_resumable

        if job.terminal:
            return  # quarantined/drained while the attempt unwound
        counters["serve.retries"] += 1
        # labeled by failure class, NOT job id: one series per job is
        # unbounded cardinality under real traffic
        _RETRIES.inc(failure_class=failure_class)
        if job.attempts > job.max_retries:
            self._fail(job, f"{detail} (retries exhausted)")
            return
        if resume and cfg is not None:
            auto = cfg.control.autosave_path or default_autosave_path(
                cfg, job.base_dir)
            job.resume_path = find_resumable(
                auto, keep=int(cfg.control.autosave_keep))
        delay = self._backoff_delay(job)
        job.not_before = time.time() + delay
        _BACKOFF.observe(delay, failure_class=failure_class)
        obs_events.emit("backoff", job_id=job.id, delay_s=delay,
                        attempt=job.attempts, failure_class=failure_class,
                        not_before=job.not_before)
        logger.log(
            logging.INFO if self.verbose else logging.DEBUG,
            "retrying %s in %.2fs: %s (resume=%s)", job.id, delay, detail,
            job.resume_path)
        self.queue.requeue(job, f"{detail} (backoff {delay:.2f}s)")

    def _watchdog_retry(self, job: Job, detail: str,
                        failure_class: str) -> None:
        """Supervisor entry point: hand a crashed/hung worker's job back
        to the queue with backoff, resuming from its newest autosave."""
        self._retry(job, job._cfg, detail, failure_class)

    def _fail(self, job: Job, detail: str, permanent: bool = False,
              quarantined: bool = False) -> None:
        job.error = detail
        job.permanent = permanent
        job.quarantined = quarantined
        counters["serve.failures"] += 1
        _FAILURES.inc(permanent=str(permanent).lower())
        logger.info("job %s failed: %s", job.id, detail)
        job._transition(JobStatus.FAILED, detail)

    def cleanup_autosaves(self, jobs) -> None:
        """Remove job-scoped autosave generations of terminal jobs.

        Rotation depth follows the engine's ``autosave_keep`` (probing a
        little past it, like io.checkpoint.find_resumable, in case keep
        was lowered between runs) so raised keep values don't leak files.
        Jobs drained into the journal keep their autosaves — they are the
        restart's resume points."""
        for job in jobs:
            if job.leave_in_journal or not job.terminal:
                continue
            tag = job.id
            base = os.path.join(job.base_dir, f"sirius_autosave.{tag}.h5")
            paths = [base] + [
                f"{base}.{i}" for i in range(1, max(self.autosave_keep, 1) + 1)
            ]
            i = max(self.autosave_keep, 1) + 1
            while os.path.exists(f"{base}.{i}") and i < 100:
                paths.append(f"{base}.{i}")
                i += 1
            for p in paths:
                if os.path.exists(p):
                    try:
                        os.remove(p)
                    except OSError:
                        pass
