"""ServeEngine: queue + executable cache + slice scheduler + durable job
journal, and the `sirius-serve` CLI.

Library use::

    eng = ServeEngine(num_slices=4, journal_path="jobs.journal")
    eng.start()
    job = eng.submit(deck_dict, priority=1)
    job.wait()
    eng.shutdown(mode="drain")
    print(eng.stats())

CLI use: ``sirius-serve deck1.json deck2.json ... [--slices N]`` runs the
decks to completion and prints a JSON stats report (the same shape
tools/loadgen.py writes to SERVE_BENCH.json).

Fault tolerance (ISSUE 8): with ``journal_path`` set, every accepted
submission and terminal transition is fsync'd to an append-only JSONL
write-ahead journal (serve/journal.py) *before* the engine acts on it. A
new engine pointed at the same journal replays the jobs that never
reached a terminal state, re-submitting them with ``resume_path`` aimed
at their job-scoped autosaves — a ``kill -9`` mid-campaign costs only
the SCF iterations since each job's last autosave. ``shutdown`` knows
``drain`` (stop admissions, finish in-flight, leave queued jobs in the
journal for the next process) from ``abort`` (queued jobs are terminally
aborted and journaled as such); the CLI maps SIGTERM to a drain and
exits 0. Slice workers are supervised with heartbeats, a watchdog, and
poison quarantine (serve/supervisor.py).

Fleet serving (ISSUE 19, sirius_tpu.fleet): ``store_dir`` (or
``fleet_dir``, which implies a shared ``<fleet_dir>/store``) arms
content-addressed dedup — an exact resubmission is answered from the
durable result store instantly with ``provenance: memo`` and the donor
run's trace id, and a duplicate of a job currently in flight attaches
to it as a *watcher*, so no canonical hash is ever computed twice
concurrently. ``fleet_dir`` additionally federates this engine with any
number of peer processes over one shared queue directory: a pull thread
leases pending jobs (fsync'd atomic claim + heartbeat renewal), and a
peer's SIGKILL expires its leases so this engine reclaims and resumes
its jobs from their shared autosaves, continuing the original trace
ids. ``fair_share``/``tenants`` switch the queue to per-tenant weighted
deficit-round-robin popping with per-tenant quotas (serve/queue.py).

Observability: ``metrics_port`` starts the obs HTTP endpoint
(``/metrics`` Prometheus text, ``/healthz`` JSON, ``/debug/trace`` to arm
a jax.profiler capture — obs/http.py) for the engine's lifetime, and
``events_path`` opens the JSONL event sink so every job transition and
SCF iteration is logged. ``metrics_snapshot()`` is the pull-style
equivalent for batch runs: the full registry plus engine stats as one
JSON-friendly dict (what loadgen embeds into SERVE_BENCH.json).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

from sirius_tpu import obs
from sirius_tpu.fleet.canon import deck_hash
from sirius_tpu.fleet.federation import FleetMember
from sirius_tpu.fleet.store import ResultStore
from sirius_tpu.obs import events as obs_events
from sirius_tpu.obs import metrics as obs_metrics
from sirius_tpu.obs import tracing as obs_tracing
from sirius_tpu.runtime import PLATFORMS, enable_compile_cache, select_platform
from sirius_tpu.serve import journal as journal_mod
from sirius_tpu.serve.cache import ExecutableCache
from sirius_tpu.serve.queue import Job, JobQueue, JobStatus
from sirius_tpu.serve.scheduler import SliceScheduler

_REPLAYS = obs_metrics.REGISTRY.counter(
    "serve_journal_replays_total", "jobs replayed from the journal")
_MEMO = obs_metrics.REGISTRY.counter(
    "fleet_memo_total",
    "content-addressed dedup outcomes (outcome=hit|miss|store)")
_WATCHERS = obs_metrics.REGISTRY.counter(
    "fleet_watcher_attaches_total",
    "duplicate submissions attached as watchers to an in-flight job")


def _percentile(xs, q: float) -> float:
    """Nearest-rank percentile of a non-empty list."""
    s = sorted(xs)
    i = min(len(s) - 1, max(0, int(round(q / 100.0 * (len(s) - 1)))))
    return s[i]


class ServeEngine:
    def __init__(self, num_slices: int = 1, devices=None,
                 cache_capacity: int = 32, autosave_every: int = 3,
                 autosave_keep: int = 2, workdir: str = ".",
                 verbose: bool = False, metrics_port: int | None = None,
                 events_path: str | None = None,
                 journal_path: str | None = None, queue_maxsize: int = 0,
                 poison_threshold: int = 2,
                 job_wall_time_budget: float | None = None,
                 watchdog_interval: float = 0.25,
                 backoff_base: float = 0.5, backoff_max: float = 30.0,
                 store_dir: str | None = None, dedup: bool | None = None,
                 fleet_dir: str | None = None, fleet_poll: float = 0.25,
                 lease_ttl: float = 6.0, engine_id: str | None = None,
                 fair_share: bool = False,
                 tenants: dict[str, dict] | None = None):
        self.queue = JobQueue(maxsize=queue_maxsize, fair_share=fair_share,
                              tenants=tenants)
        self.cache = ExecutableCache(capacity=cache_capacity)
        self.workdir = workdir
        os.makedirs(workdir, exist_ok=True)
        self.autosave_keep = int(autosave_keep)
        self.scheduler = SliceScheduler(
            self.queue, self.cache, num_slices=num_slices, devices=devices,
            autosave_every=autosave_every, autosave_keep=autosave_keep,
            verbose=verbose, poison_threshold=poison_threshold,
            job_wall_time_budget=job_wall_time_budget,
            watchdog_interval=watchdog_interval,
            backoff_base=backoff_base, backoff_max=backoff_max,
        )
        self._t0: float | None = None
        self._submitted: list[Job] = []
        self._shutdown = False
        self._obs_server = None
        # wait_all blocks on this condition; every job's terminal hook
        # notifies it, so completion latency is not quantized by polling
        self._done_cv = threading.Condition()
        if events_path:
            obs.configure_events(events_path)
        # content-addressed memo layer (sirius_tpu.fleet): a fleet dir
        # implies a fleet-wide shared store unless one is given
        if fleet_dir and store_dir is None:
            store_dir = os.path.join(fleet_dir, "store")
        self.store: ResultStore | None = (
            ResultStore(store_dir) if store_dir else None)
        self.dedup = (self.store is not None if dedup is None
                      else bool(dedup) and self.store is not None)
        # canonical hash -> the one Job computing it right now; duplicate
        # submissions attach to it as watchers instead of recomputing
        self._inflight: dict[str, Job] = {}
        self._inflight_lock = threading.Lock()
        self.dedup_lookups = 0
        self.memo_hits = 0
        self.watcher_attaches = 0
        self.fleet: FleetMember | None = None
        if fleet_dir:
            self.fleet = FleetMember(self, fleet_dir, poll=fleet_poll,
                                     lease_ttl=lease_ttl, owner=engine_id)
        self.journal: journal_mod.JobJournal | None = None
        self.replayed: list[Job] = []
        if journal_path:
            pending, jstats = journal_mod.replay(journal_path)
            self.journal = journal_mod.JobJournal(journal_path)
            self._journal_stats = jstats
            # campaign children replayed below may depend on parents that
            # settled in a previous process and so never re-enter the
            # queue: resolve those edges from the journal's terminal map
            self.queue.external_parent_status.update(
                jstats.get("terminal_status") or {})
            for rec in pending:
                self.replayed.append(self._replay_job(rec))
        if metrics_port is not None:
            from sirius_tpu.obs.http import ObsHttpServer
            self._obs_server = ObsHttpServer(
                port=metrics_port, health_fn=self._health,
                default_trace_dir=os.path.join(workdir, "trace_capture"),
            )

    def _notify_terminal(self, job: Job) -> None:
        """Job terminal hook: wake wait_all promptly."""
        with self._done_cv:
            self._done_cv.notify_all()

    # -- content-addressed dedup (sirius_tpu.fleet) ------------------------

    @staticmethod
    def _memo_result(rec: dict) -> dict:
        """A job result served from the store: the donor's physics plus
        a provenance trail back to the run that computed it."""
        res = {k: rec[k]
               for k in ("energy", "converged", "num_scf_iterations",
                         "forces", "stress", "task")
               if rec.get(k) is not None}
        res["provenance"] = "memo"
        res["donor_trace_id"] = rec.get("trace_id")
        res["donor_job_id"] = rec.get("job_id")
        return res

    def _try_dedup(self, job: Job) -> bool:
        """Answer ``job`` without computing: from the store (memo hit)
        or by attaching it as a watcher to the in-flight job for the
        same canonical hash. Returns False — after registering ``job``
        as the new in-flight leader — when a fresh compute is needed."""
        canon = job.canon_hash
        with self._inflight_lock:  # counters shared with FleetMember thread
            self.dedup_lookups += 1
        rec = self.store.get(canon) if self.store is not None else None
        if rec is not None:
            with self._inflight_lock:
                self.memo_hits += 1
            _MEMO.inc(outcome="hit")
            job.result = self._memo_result(rec)
            job.submitted_at = job.submitted_at or time.time()
            obs_events.emit("memo_hit", job_id=job.id, canon_hash=canon,
                            donor_trace_id=rec.get("trace_id"),
                            trace_id=job.trace_id)
            job._transition(
                JobStatus.DONE,
                f"memo hit {canon[:12]} (donor {rec.get('job_id')})")
            return True
        with self._inflight_lock:
            leader = self._inflight.get(canon)
            if leader is None or leader.terminal:
                self._inflight[canon] = job
                leader = None
        if leader is None:
            _MEMO.inc(outcome="miss")
            job.add_terminal_hook(self._store_result)
            job.add_terminal_hook(self._inflight_forget)
            return False
        with self._inflight_lock:
            self.watcher_attaches += 1
        _WATCHERS.inc()
        job.submitted_at = job.submitted_at or time.time()
        obs_events.emit("watcher_attach", job_id=job.id, leader=leader.id,
                        canon_hash=canon, trace_id=job.trace_id)
        # fires immediately if the leader settled in the check window
        # (add_terminal_hook's after-terminal contract), so the watcher
        # can never miss the answer
        leader.add_terminal_hook(self._make_watcher_settle(job))
        return True

    def _make_watcher_settle(self, watcher: Job):
        def settle(leader: Job) -> None:
            self._settle_watcher(watcher, leader)
        return settle

    def _settle_watcher(self, watcher: Job, leader: Job) -> None:
        """The leader for ``watcher``'s hash settled: copy its answer,
        or — if the leader died without one — promote the watcher to
        compute (or chain it onto an already-promoted sibling)."""
        if watcher.terminal:
            return
        if leader.status == JobStatus.DONE and leader.result:
            res = {k: v for k, v in leader.result.items() if k != "serve"}
            res.update(provenance="watcher",
                       donor_trace_id=leader.trace_id,
                       donor_job_id=leader.id)
            watcher.result = res
            watcher._transition(
                JobStatus.DONE, f"watcher served by {leader.id}")
            return
        with self._inflight_lock:
            cur = self._inflight.get(watcher.canon_hash)
            if cur is leader or cur is None or cur.terminal:
                self._inflight[watcher.canon_hash] = watcher
                cur = None
        if cur is not None:
            # a sibling watcher was promoted first: wait on it instead
            cur.add_terminal_hook(self._make_watcher_settle(watcher))
            return
        watcher.add_terminal_hook(self._store_result)
        watcher.add_terminal_hook(self._inflight_forget)
        if self.journal is not None:
            # the watcher is real work the engine owes now — make it
            # durable before queueing, like any fresh submission
            watcher.submitted_at = watcher.submitted_at or time.time()
            self.journal.record_submit(watcher)
            watcher.add_terminal_hook(self._journal_terminal)
        # the watcher already holds _notify_terminal from submit();
        # re-order it to fire last so the store/journal writes land
        # before any waiter resumes (see submit())
        if self._notify_terminal in watcher._terminal_hooks:
            watcher._terminal_hooks.remove(self._notify_terminal)
            watcher._terminal_hooks.append(self._notify_terminal)
        self.queue.requeue(
            watcher, f"promoted: leader {leader.id} {leader.status}")

    def _store_result(self, job: Job) -> None:
        """Job terminal hook: persist a freshly computed answer under
        its content address (never re-store memo/watcher copies)."""
        if (self.store is None or job.canon_hash is None
                or job.status != JobStatus.DONE or not job.result
                or job.result.get("provenance") in ("memo", "watcher")):
            return
        if self.store.put(job.canon_hash, job.result,
                          trace_id=job.trace_id, job_id=job.id):
            _MEMO.inc(outcome="store")
            obs_events.emit("memo_store", job_id=job.id,
                            canon_hash=job.canon_hash,
                            trace_id=job.trace_id)

    def _inflight_forget(self, job: Job) -> None:
        """Job terminal hook: stop routing duplicates to a settled
        leader (later exact submissions hit the store instead)."""
        if job.canon_hash is None:
            return
        with self._inflight_lock:
            if self._inflight.get(job.canon_hash) is job:
                del self._inflight[job.canon_hash]

    # -- fleet federation (sirius_tpu.fleet.federation) --------------------

    def _adopt_fleet_job(self, rec: dict) -> Job | None:
        """Admit a fleet job whose lease we just won into the local
        queue, resuming from its shared-work-dir autosave with its
        ORIGINAL trace id; store hits settle instantly as memo answers.
        Returns None when the engine can no longer take work (the
        member releases the lease). Fleet jobs are deliberately not
        written to the local journal — the fleet dir is their durable
        record."""
        if self._shutdown or self.queue.closed:
            return None
        job = Job(
            rec.get("deck") or {}, job_id=rec["job_id"],
            base_dir=self.fleet.dir.work_dir,
            priority=int(rec.get("priority") or 0),
            deadline=rec.get("deadline"),
            max_retries=int(rec.get("max_retries") or 2),
            wall_time_budget=rec.get("wall_time_budget"),
            trace_id=rec.get("trace_id"),
            tenant=rec.get("tenant") or "default",
            canon_hash=(rec.get("canon_hash") if self.dedup else None),
        )
        job.submitted_at = rec.get("ts") or time.time()
        self._submitted.append(job)
        # _notify_terminal last (see submit()): the store write must
        # land before any waiter resumes
        if job.canon_hash and self._try_dedup(job):
            job.add_terminal_hook(self._notify_terminal)
            return job
        job.add_terminal_hook(self._notify_terminal)
        job.resume_path = self._find_replay_autosave(job)
        self.queue.requeue(job, "fleet claim")
        return job

    def _abandon_fleet_job(self, job: Job) -> None:
        """Our lease on ``job`` was lost: some survivor owns it now.
        Bump the epoch so a still-running worker's late result is
        discarded, and keep the autosaves (``leave_in_journal``) for
        the new owner to resume from."""
        job._epoch += 1
        job.leave_in_journal = True
        job._transition(JobStatus.ABORTED, "fleet lease lost")

    # -- journal -----------------------------------------------------------

    def _journal_terminal(self, job: Job) -> None:
        """Job terminal hook: make the outcome durable. Drained jobs are
        deliberately left non-terminal so a restart re-runs them."""
        if self.journal is None or job.leave_in_journal:
            return
        self.journal.record_terminal(job)

    def _replay_job(self, rec: dict) -> Job:
        """Re-submit one non-terminal journal record, resuming from the
        newest valid generation of its job-scoped autosave."""
        job = Job(
            rec.get("deck") or {}, job_id=rec["job_id"],
            base_dir=rec.get("base_dir") or self.workdir,
            priority=int(rec.get("priority") or 0),
            deadline=rec.get("deadline"),
            max_retries=int(rec.get("max_retries") or 2),
            wall_time_budget=rec.get("wall_time_budget"),
            parents=rec.get("parents"),
            campaign_id=rec.get("campaign_id"),
            node_id=rec.get("node_id"),
            handoff_in=rec.get("handoff_in"),
            handoff_out=rec.get("handoff_out"),
            trace_id=rec.get("trace_id"),
            tenant=rec.get("tenant") or "default",
            canon_hash=(rec.get("canon_hash") if self.dedup else None),
        )
        job.resume_path = self._find_replay_autosave(job)
        job.add_terminal_hook(self._journal_terminal)
        job.submitted_at = rec.get("ts") or time.time()
        self._submitted.append(job)
        _REPLAYS.inc()
        obs_events.emit("journal_replay_job", job_id=job.id,
                        resume=job.resume_path)
        # replayed duplicates dedup like fresh ones: a store hit (or an
        # already-replayed leader for the same hash) settles this job
        # without a recompute, and the terminal record converges the
        # journal. _notify_terminal last (see submit()).
        if job.canon_hash and self._try_dedup(job):
            job.add_terminal_hook(self._notify_terminal)
            return job
        job.add_terminal_hook(self._notify_terminal)
        # requeue, not submit: the journal already admitted this work, so
        # it is exempt from the admission bound and not re-journaled
        self.queue.requeue(job, "journal replay")
        return job

    def _find_replay_autosave(self, job: Job) -> str | None:
        from sirius_tpu.io.checkpoint import CheckpointError, find_resumable

        ctl = {}
        if isinstance(job.deck, dict):
            ctl = job.deck.get("control") or {}
        # mirror the scheduler's serve defaults: explicit autosave_path
        # wins, then the (tag or job-id)-scoped rotation in base_dir
        base = ctl.get("autosave_path") or os.path.join(
            job.base_dir,
            f"sirius_autosave.{ctl.get('autosave_tag') or job.id}.h5")
        try:
            return find_resumable(base, keep=self.autosave_keep)
        except (CheckpointError, OSError):
            # only the two ways probing an autosave legitimately fails:
            # damaged/mismatched file or filesystem trouble — a cold
            # replay is the right degradation for both. Anything else
            # (incl. a device-class error) must surface, not be eaten.
            return None

    @property
    def num_slices(self) -> int:
        return len(self.scheduler.slices)

    def start(self) -> None:
        self._t0 = time.time()
        if self._obs_server is not None:
            self._obs_server.start()
        self.scheduler.start()
        if self.fleet is not None:
            self.fleet.start()

    @property
    def metrics_url(self) -> str | None:
        """Base URL of the obs endpoint (None when metrics_port unset)."""
        return self._obs_server.url if self._obs_server else None

    def _health(self) -> dict:
        return {
            "ok": not self._shutdown,
            "num_slices": self.num_slices,
            "queue_depth": len(self.queue),
            "jobs_submitted": len(self._submitted),
            "jobs_in_flight": sum(
                not j.terminal for j in self._submitted),
            "journal": self.journal.path if self.journal else None,
            "jobs_replayed": len(self.replayed),
            "dedup_memo_hits": self.memo_hits,
            "dedup_watcher_attaches": self.watcher_attaches,
            "fleet_owner": self.fleet.owner if self.fleet else None,
            "fleet_claimed": (self.fleet.claimed_ids()
                              if self.fleet else []),
            "uptime_s": (time.time() - self._t0) if self._t0 else 0.0,
        }

    def submit(self, deck: dict, job_id: str | None = None,
               priority: int = 0, deadline: float | None = None,
               base_dir: str | None = None, max_retries: int = 2,
               wall_time_budget: float | None = None,
               block: bool = False, timeout: float | None = None,
               parents: list[str] | None = None,
               campaign_id: str | None = None,
               node_id: str | None = None,
               handoff_in: dict | None = None,
               handoff_out: str | None = None,
               trace_id: str | None = None,
               tenant: str = "default") -> Job:
        """Admit a job. Raises QueueFullError when the queue is bounded
        and full (immediately, or after ``timeout`` with ``block=True``)
        or when ``tenant`` is over its queue quota.
        With a journal, the submission is durable before it is queued.
        With a result store (``store_dir``/``fleet_dir``), an exact
        resubmission — same canonical deck hash — is answered from the
        store instantly (``provenance: memo``), and a duplicate of a job
        currently in flight attaches to it as a watcher instead of
        recomputing; neither consumes queue capacity.
        ``parents``/``campaign_id``/``handoff_*`` attach the job to a
        campaign DAG (sirius_tpu.campaigns): it runs only after every
        parent is DONE, is skipped terminally when one fails, and routes
        the parent's converged state in as run_scf(initial_guess=)."""
        job = Job(
            deck, job_id=job_id, base_dir=base_dir or self.workdir,
            priority=priority, deadline=deadline, max_retries=max_retries,
            wall_time_budget=wall_time_budget,
            parents=parents, campaign_id=campaign_id, node_id=node_id,
            handoff_in=handoff_in, handoff_out=handoff_out,
            # trace identity BEFORE journaling: explicit id (campaigns) >
            # the caller's ambient trace > a fresh one — so replay after
            # SIGKILL continues the same end-to-end trace
            trace_id=(trace_id or obs_tracing.current_trace_id()
                      or obs_tracing.new_trace_id()),
            tenant=tenant,
            canon_hash=(deck_hash(deck) if self.dedup else None),
        )
        # _notify_terminal (which wakes wait_all) must be the LAST hook:
        # hooks fire in registration order, and a waiter resuming before
        # _store_result / _journal_terminal ran could resubmit the same
        # deck and miss the memo that is still being written
        if job.canon_hash and self._try_dedup(job):
            # answered from the store or attached to the in-flight
            # leader: no queue admission, no journal record — the engine
            # owes nothing a crash could lose
            job.add_terminal_hook(self._notify_terminal)
            self._submitted.append(job)
            return job
        if self.journal is not None:
            job.add_terminal_hook(self._journal_terminal)
            # write-ahead: journal first so a crash between journaling and
            # queueing re-runs the job (at-least-once) instead of losing it
            job.submitted_at = time.time()
            self.journal.record_submit(job)
        job.add_terminal_hook(self._notify_terminal)
        try:
            self.queue.submit(job, block=block, timeout=timeout)
        except Exception as e:
            # keep the journal consistent: the rejection is terminal (the
            # _on_terminal hook writes the terminal record)
            job.error = f"rejected: {e}"
            job._transition(JobStatus.ABORTED, job.error)
            raise
        self._submitted.append(job)
        return job

    def wait_all(self, timeout: float | None = None) -> bool:
        """Block until every submitted job is terminal. False on timeout.

        Condition-based, not polled: each job's terminal hook notifies
        ``_done_cv``, so a waiter wakes within the transition itself —
        campaign completion latency is not quantized by a poll interval.
        The pending set is re-evaluated on every wakeup, which also
        covers jobs submitted after the wait began."""
        deadline = None if timeout is None else time.time() + timeout
        with self._done_cv:
            while True:
                # status is set before the hook fires, so any job whose
                # notify we could have missed is already terminal here
                if all(j.terminal for j in self._submitted):
                    return True
                remaining = (None if deadline is None
                             else deadline - time.time())
                if remaining is not None and remaining <= 0:
                    return False
                self._done_cv.wait(remaining)

    def shutdown(self, wait: bool = True, cleanup: bool = True,
                 mode: str = "drain") -> None:
        """Stop the engine.

        ``mode="drain"``: stop admissions, let in-flight jobs finish, and
        hand queued-but-unstarted jobs back to the journal (terminal
        ABORTED in-process so ``wait_all`` returns, but left non-terminal
        on disk with their autosaves intact — the next engine on this
        journal re-runs them). ``mode="abort"``: queued jobs are
        terminally aborted, in the journal too."""
        if mode not in ("drain", "abort"):
            raise ValueError(f"shutdown mode must be drain|abort, not {mode!r}")
        self._shutdown = True
        if self.fleet is not None:
            # stop claiming and renewing first: our queued fleet jobs'
            # leases are released below, in-flight ones either finish
            # (terminal record written, fenced) or expire for survivors
            self.fleet.stop()
        self.queue.close()
        # "drain" keeps work durable for whoever resumes it — the local
        # journal or, for fleet jobs, the shared fleet dir
        leave = mode == "drain" and (self.journal is not None
                                     or self.fleet is not None)
        drained = self.queue.abort_pending(
            "drained for restart" if mode == "drain" else "abort shutdown",
            leave_in_journal=leave,
        )
        if drained:
            obs_events.emit("drain" if mode == "drain" else "abort",
                            jobs=[j.id for j in drained])
        if wait:
            self.scheduler.join(timeout=60.0)
        self.scheduler.stop_supervision()
        # deterministic close: nothing a dead/raced worker left behind may
        # stay QUEUED forever (wait_all would block on it)
        self.queue.abort_pending(
            "queue closed before worker pickup", leave_in_journal=leave)
        if cleanup:
            self.scheduler.cleanup_autosaves(self._submitted)
        if self.journal is not None:
            self.journal.close()
        if self._obs_server is not None:
            self._obs_server.stop()

    def stats(self) -> dict:
        done = [j for j in self._submitted if j.status == JobStatus.DONE]
        lat = [j.latency for j in done if j.latency is not None]
        wall = (time.time() - self._t0) if self._t0 else 0.0
        by_tenant: dict[str, list[Job]] = {}
        for j in self._submitted:
            by_tenant.setdefault(j.tenant, []).append(j)

        def _tenant_row(js: list[Job]) -> dict:
            tl = [j.latency for j in js
                  if j.status == JobStatus.DONE and j.latency is not None]
            return {
                "num_jobs": len(js),
                "num_done": sum(j.status == JobStatus.DONE for j in js),
                "p50_latency_s": _percentile(tl, 50) if tl else None,
                "p95_latency_s": _percentile(tl, 95) if tl else None,
            }

        return {
            "num_jobs": len(self._submitted),
            "num_done": len(done),
            "num_failed": sum(
                j.status == JobStatus.FAILED for j in self._submitted),
            "num_aborted": sum(
                j.status == JobStatus.ABORTED for j in self._submitted),
            "num_skipped_upstream": sum(
                j.status == JobStatus.SKIPPED_UPSTREAM
                for j in self._submitted),
            "num_quarantined": sum(
                j.quarantined for j in self._submitted),
            "num_replayed": len(self.replayed),
            "num_drained": sum(
                j.leave_in_journal for j in self._submitted),
            "num_slices": self.num_slices,
            "wall_s": wall,
            "jobs_per_min": (len(done) / wall * 60.0) if wall > 0 else 0.0,
            "p50_latency_s": _percentile(lat, 50) if lat else None,
            "p95_latency_s": _percentile(lat, 95) if lat else None,
            "cache": self.cache.stats(),
            "retries_total": sum(j.attempts - 1 for j in self._submitted),
            "tenants": {t: _tenant_row(js)
                        for t, js in sorted(by_tenant.items())},
            "fair_share": self.queue.fair_share,
            "dedup": {
                "enabled": self.dedup,
                "lookups": self.dedup_lookups,
                "memo_hits": self.memo_hits,
                "watcher_attaches": self.watcher_attaches,
                "hit_rate": ((self.memo_hits + self.watcher_attaches)
                             / self.dedup_lookups
                             if self.dedup_lookups else 0.0),
                "store": self.store.stats() if self.store else None,
            },
            "fleet": ({"owner": self.fleet.owner,
                       "claimed": self.fleet.claimed_ids()}
                      if self.fleet else None),
        }

    def metrics_snapshot(self) -> dict:
        """Full observability snapshot for batch runs: engine stats,
        compile counts, queue high-water, and the metrics registry
        (histograms with cumulative buckets) as JSON-friendly data."""
        obs.update_device_memory_gauges()
        return {
            "stats": self.stats(),
            "backend_compiles_total": obs.backend_compiles_total(),
            "queue_depth_high_water": self.queue.high_water,
            "registry": obs.REGISTRY.snapshot(),
        }


def main(argv: list[str] | None = None) -> int:
    p = argparse.ArgumentParser(
        prog="sirius-serve",
        description="multi-job SCF serving engine (sirius_tpu.serve)",
    )
    p.add_argument("decks", nargs="*",
                   help="JSON deck files (cli.py format); optional when "
                        "--fleet-dir supplies the work")
    p.add_argument("--slices", type=int, default=1,
                   help="device slices / concurrent jobs")
    p.add_argument("--fleet-dir", default=None,
                   help="shared fleet queue directory: lease jobs other "
                        "processes submitted, and serve until drained "
                        "(sirius_tpu.fleet.federation)")
    p.add_argument("--engine-id", default=None,
                   help="stable lease-owner id in the fleet dir "
                        "(default: host-pid-random)")
    p.add_argument("--lease-ttl", type=float, default=6.0,
                   help="fleet lease expiry in seconds; a SIGKILL'd "
                        "engine's jobs are reclaimed after this long")
    p.add_argument("--store-dir", default=None,
                   help="content-addressed result store for dedup "
                        "(defaults to <fleet-dir>/store in fleet mode)")
    p.add_argument("--no-dedup", action="store_true",
                   help="disable content-addressed dedup even with a "
                        "store configured")
    p.add_argument("--fair-share", action="store_true",
                   help="weighted deficit-round-robin popping across "
                        "tenants instead of global priority order")
    p.add_argument("--tenant", default="default",
                   help="tenant id for decks submitted by this CLI")
    p.add_argument("--repeat", type=int, default=1,
                   help="submit each deck N times (cache warm-up study)")
    p.add_argument("--priority", type=int, default=0)
    p.add_argument("--deadline", type=float, default=None,
                   help="per-job deadline in seconds from submission")
    p.add_argument("--timeout", type=float, default=3600.0,
                   help="overall wait bound in seconds")
    p.add_argument("--stats_out", default=None,
                   help="also write the stats JSON to this path")
    p.add_argument("--platform", default=None, choices=list(PLATFORMS))
    p.add_argument("--metrics-port", type=int, default=None,
                   help="serve /metrics + /healthz on this port "
                        "(0 = ephemeral; off when omitted)")
    p.add_argument("--events", default=None,
                   help="append JSONL observability events to this file")
    p.add_argument("--journal", default=None,
                   help="durable job journal (JSONL WAL); a restart with "
                        "the same path resumes unfinished jobs")
    p.add_argument("--queue-max", type=int, default=0,
                   help="bound the queue (0 = unbounded); full queues "
                        "reject submissions")
    p.add_argument("--budget", type=float, default=None,
                   help="per-attempt wall-time budget in seconds enforced "
                        "by the slice watchdog")
    p.add_argument("--poison-threshold", type=int, default=2,
                   help="worker-fatal strikes before a job is quarantined")
    p.add_argument("-v", "--verbose", action="count", default=0,
                   help="raise log level (-v info, -vv debug)")
    args = p.parse_args(argv)

    obs.setup_logging(args.verbose)

    if not args.decks and not args.fleet_dir:
        print("sirius-serve: nothing to do (no decks and no --fleet-dir)",
              file=sys.stderr)
        return 2
    for d in args.decks:
        if not os.path.isfile(d):
            print(f"sirius-serve: deck not found: {d}", file=sys.stderr)
            return 2

    select_platform(args.platform)
    enable_compile_cache()

    import signal
    import threading

    eng = ServeEngine(num_slices=args.slices, verbose=True,
                      metrics_port=args.metrics_port,
                      events_path=args.events,
                      journal_path=args.journal,
                      queue_maxsize=args.queue_max,
                      job_wall_time_budget=args.budget,
                      poison_threshold=args.poison_threshold,
                      store_dir=args.store_dir,
                      dedup=False if args.no_dedup else None,
                      fleet_dir=args.fleet_dir,
                      engine_id=args.engine_id,
                      lease_ttl=args.lease_ttl,
                      fair_share=args.fair_share)
    drain = threading.Event()

    def _on_sigterm(signum, frame):
        # graceful drain: stop accepting, finish in-flight, leave the
        # rest (journaled) for the next process, exit 0
        print("sirius-serve: SIGTERM — draining", file=sys.stderr)
        drain.set()
        eng.queue.close()

    try:
        signal.signal(signal.SIGTERM, _on_sigterm)
    except ValueError:
        pass  # not the main thread (embedded use)
    eng.start()
    if eng.metrics_url:
        print(f"sirius-serve: metrics at {eng.metrics_url}/metrics",
              file=sys.stderr)
    if eng.replayed:
        print(f"sirius-serve: replayed {len(eng.replayed)} unfinished "
              f"job(s) from {args.journal}", file=sys.stderr)
    for rep in range(args.repeat):
        for path in args.decks:
            with open(path) as f:
                deck = json.load(f)
            name = os.path.splitext(os.path.basename(path))[0]
            eng.submit(
                deck, job_id=f"{name}-{rep}", priority=args.priority,
                deadline=(time.time() + args.deadline
                          if args.deadline else None),
                base_dir=os.path.dirname(os.path.abspath(path)) or ".",
                wall_time_budget=args.budget,
                tenant=args.tenant,
            )
    bar = time.time() + args.timeout
    ok = False
    while not drain.is_set():
        ok = eng.wait_all(timeout=0.5)
        if args.fleet_dir:
            # fleet mode serves until the SHARED queue is drained, not
            # just our own submissions (other processes feed it)
            ok = ok and eng.fleet.dir.all_terminal()
        if ok or time.time() > bar:
            break
    stats_obs = eng.metrics_snapshot()
    eng.shutdown(wait=True, mode="drain")
    stats = eng.stats()
    stats["obs"] = {k: v for k, v in stats_obs.items() if k != "stats"}
    stats["jobs"] = [j.to_dict() for j in eng._submitted]
    print(json.dumps(stats, indent=2, default=float))
    if args.stats_out:
        with open(args.stats_out, "w") as f:
            json.dump(stats, f, indent=2, default=float)
    if drain.is_set():
        print(f"sirius-serve: drained ({stats['num_drained']} job(s) left "
              f"in the journal)", file=sys.stderr)
        return 0
    if not ok:
        print("sirius-serve: timed out waiting for jobs", file=sys.stderr)
        return 3
    return 1 if stats["num_failed"] or stats["num_aborted"] else 0


if __name__ == "__main__":
    raise SystemExit(main())
