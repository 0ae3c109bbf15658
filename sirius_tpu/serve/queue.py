"""Priority job queue with deadlines, admission control, retry backoff
and per-job lifecycle events.

Jobs carry the same JSON deck dict that cli.py consumes. Lifecycle:
queued -> compiling -> running -> done | failed | aborted; every
transition is appended to ``job.events`` as (timestamp, status, detail)
so a client can reconstruct what happened to its job. Higher ``priority``
pops first; among equal priorities the earlier ``deadline`` (then FIFO
order) wins. A job whose deadline has already passed when it reaches the
front is aborted instead of run — serving semantics: a late answer is a
wrong answer.

Fault-tolerance semantics (ISSUE 8):

- **Backoff.** ``job.not_before`` is an absolute wall-clock bar that
  ``pop()`` honors: a retried job sleeps *in the queue* (the worker is
  free to run other jobs) until its backoff expires. The scheduler
  clamps ``not_before`` to the job deadline, so backoff can never push a
  job past the point where it would be aborted unrun.
- **Admission control.** ``JobQueue(maxsize=N)`` bounds the number of
  queued entries; ``submit`` either rejects immediately with
  ``QueueFullError`` or, with ``block=True``, waits up to ``timeout``
  for space. ``requeue`` (retries, watchdog hand-backs, journal replays)
  bypasses the bound — work the engine already accepted is never
  rejected.
- **Deterministic close.** ``close()`` stops admissions; blocked
  ``pop()`` calls drain then return None. ``abort_pending()`` empties
  the heap and transitions every entry terminally — the engine calls it
  on ``drain``/``abort`` shutdown and again after the workers have
  exited, so a close racing a worker's exit can never strand a job in
  QUEUED with ``wait_all()`` blocked on it.
- **Terminal transitions are final.** ``Job._transition`` ignores any
  transition after done/failed/aborted — a hung worker abandoned by the
  watchdog cannot resurrect or clobber a job that was already requeued,
  quarantined, or drained.

Campaign DAG semantics (ISSUE 10):

- **Dependency-aware admission.** A job with ``parents`` becomes
  poppable only once every parent is terminal-DONE. ``pop()`` defers
  dependency-blocked entries exactly like backoff-deferred ones; a
  terminal transition on any job notifies ``_not_empty`` so a worker
  promptly re-scans the heap for newly-unblocked children.
- **Upstream-failure propagation.** A parent that ends failed, aborted
  or skipped transitions the child to the terminal
  ``SKIPPED_UPSTREAM`` status inside ``pop()`` — the cascade is lazy
  (evaluated when the child reaches the front) and transitive: a
  skipped parent skips its own children in turn.
- **External parents.** After a journal replay, a child's parent may
  have finished in a previous process and so never re-enters
  ``jobs``. ``external_parent_status`` (job_id -> terminal status,
  populated by the engine from the journal) resolves those edges; an
  unknown parent is treated as satisfied rather than deadlocking the
  child forever.

Multi-tenant fair share (ISSUE 19):

- **Tenant identity.** Every job carries a ``tenant`` id (defaulting to
  ``"default"``); ``set_tenant`` registers a weight and an optional
  per-tenant queue quota.
- **Per-tenant admission control.** A tenant at its ``max_queued``
  quota is rejected with ``QueueFullError`` naming the tenant — one
  tenant flooding the queue can exhaust its own quota but never the
  global bound for everyone else. Quota rejections are immediate
  (admission control is a per-tenant verdict, not a capacity wait);
  ``block=True`` only ever waits on the global bound.
- **Weighted deficit round robin.** With ``fair_share=True``, ``pop``
  picks among the front-runnable job of each tenant by deficit round
  robin: a round-robin pointer grants each tenant its weight in service
  quantum on arrival and keeps serving that tenant while it has at
  least one quantum banked, so a weight-2 tenant gets twice the pops of
  a weight-1 tenant under contention while an idle tenant banks
  nothing. Within a tenant the existing priority/deadline/FIFO order is
  untouched; with ``fair_share=False`` (the default) cross-tenant order
  is the existing global priority order, bit-for-bit.
"""

from __future__ import annotations

import heapq
import itertools
import threading
import time
import uuid

from sirius_tpu.obs import events as obs_events
from sirius_tpu.obs import metrics as obs_metrics
from sirius_tpu.obs.log import get_logger

logger = get_logger("serve")

_TRANSITIONS = obs_metrics.REGISTRY.counter(
    "serve_job_transitions_total", "job lifecycle transitions by status")
_STATE_SECONDS = obs_metrics.REGISTRY.histogram(
    "serve_job_state_seconds", "time spent in each job state")
_LATENCY = obs_metrics.REGISTRY.histogram(
    "serve_job_latency_seconds", "submit-to-terminal job latency")
_DEPTH = obs_metrics.REGISTRY.gauge(
    "serve_queue_depth", "jobs waiting in the queue")
_DEPTH_HW = obs_metrics.REGISTRY.gauge(
    "serve_queue_depth_high_water", "max queue depth seen this process")
_REJECTED = obs_metrics.REGISTRY.counter(
    "serve_queue_rejected_total", "submissions rejected by admission control")
_TENANT_DEPTH = obs_metrics.REGISTRY.gauge(
    "serve_tenant_queue_depth", "jobs waiting in the queue per tenant")


class QueueFullError(RuntimeError):
    """The bounded queue rejected a submission (admission control)."""


class JobStatus:
    QUEUED = "queued"
    COMPILING = "compiling"
    RUNNING = "running"
    DONE = "done"
    FAILED = "failed"
    ABORTED = "aborted"
    # terminal state of a campaign node whose upstream dependency ended
    # failed/aborted/skipped: the node never ran and never will
    SKIPPED_UPSTREAM = "skipped_upstream"


TERMINAL = (JobStatus.DONE, JobStatus.FAILED, JobStatus.ABORTED,
            JobStatus.SKIPPED_UPSTREAM)


class Job:
    """One SCF request: a deck dict plus scheduling metadata."""

    def __init__(self, deck: dict, job_id: str | None = None,
                 base_dir: str = ".", priority: int = 0,
                 deadline: float | None = None, max_retries: int = 2,
                 wall_time_budget: float | None = None,
                 parents: list[str] | None = None,
                 campaign_id: str | None = None,
                 node_id: str | None = None,
                 handoff_in: dict | None = None,
                 handoff_out: str | None = None,
                 trace_id: str | None = None,
                 tenant: str = "default",
                 canon_hash: str | None = None):
        # uuid, NOT id(self): default ids must be unique across the
        # engine *processes* of a fleet sharing one work directory —
        # id() is a heap address, reused within a process after GC and
        # trivially colliding between processes, which would cross-wire
        # job-scoped autosave files
        self.id = job_id or f"job-{uuid.uuid4().hex[:12]}"
        self.deck = deck
        self.base_dir = base_dir
        self.priority = int(priority)
        self.deadline = deadline  # absolute time.time() bar, None = none
        self.max_retries = int(max_retries)
        # per-attempt wall-time budget enforced by the supervisor watchdog
        # (None falls back to the scheduler default; 0/None = unbounded)
        self.wall_time_budget = wall_time_budget
        # campaign DAG metadata: this job is poppable only once every id
        # in ``parents`` is terminal-DONE; a failed parent skips it
        self.parents = list(parents) if parents else []
        self.campaign_id = campaign_id
        self.node_id = node_id
        # handoff_in: {"path", "displaced", "adopt_positions"} — load the
        # parent artifact at ``path`` as run_scf(initial_guess=);
        # handoff_out: artifact path this job writes on DONE
        self.handoff_in = dict(handoff_in) if handoff_in else None
        self.handoff_out = handoff_out
        # end-to-end trace identity (obs/tracing.py): assigned by the
        # engine before journaling so SIGKILL+replay keeps the same trace;
        # campaigns pass one id for the whole DAG
        self.trace_id = trace_id
        # fair-share identity: which tenant's quota/weight this job
        # counts against (ISSUE 19)
        self.tenant = tenant or "default"
        # content address of the deck (fleet/canon.py), set by the
        # engine when dedup is on: keys the result store and in-flight
        # watcher attachment
        self.canon_hash = canon_hash
        self.status = JobStatus.QUEUED
        self.events: list[tuple[float, str, str]] = []
        self.result: dict | None = None
        self.error: str | None = None
        self.permanent = False  # classified non-retryable (bad input)
        self.quarantined = False  # poisoned: killed/stalled its workers
        self.attempts = 0
        self.poison_strikes = 0  # watchdog strikes (crash/hang) against it
        # OOM degradation level (utils/devfail.py apply_oom_hint): bumped
        # by the scheduler when an attempt dies of HBM exhaustion below
        # the in-run ladder's reach; the next attempt starts pre-degraded
        self.oom_degrade = 0
        self.resume_path: str | None = None  # autosave to resume from
        self.not_before: float | None = None  # backoff bar honored by pop()
        self.submitted_at: float | None = None
        self.started_at: float | None = None
        self.finished_at: float | None = None
        # drained jobs are terminal in-process but deliberately left
        # non-terminal in the journal so a restart re-runs them
        self.leave_in_journal = False
        # bumped when the watchdog takes the job away from a worker;
        # workers capture the epoch at pickup and discard stale results
        self._epoch = 0
        self._cfg = None  # parsed Config cached by the scheduler (retries)
        # fired in order on the terminal transition (journal record,
        # queue dependency wakeup, engine wait_all notify, ...)
        self._terminal_hooks: list = []
        self._done = threading.Event()

    def add_terminal_hook(self, hook) -> None:
        """Register ``hook(job)`` to fire once on the terminal transition
        (idempotent: re-registering the same hook is a no-op). A hook
        added AFTER the job settled fires immediately — a watcher
        attaching to an in-flight leader must not miss the answer to a
        race it cannot see."""
        if hook in self._terminal_hooks:
            return
        self._terminal_hooks.append(hook)
        if self.status in TERMINAL:
            try:
                hook(self)
            except Exception:
                logger.exception(
                    "job %s late terminal hook failed", self.id)

    def _transition(self, status: str, detail: str = "") -> None:
        if self.status in TERMINAL:
            # final means final: an abandoned worker finishing late, or a
            # drain racing a retry, must not resurrect a settled job
            logger.debug("job %s: ignoring %s after terminal %s",
                         self.id, status, self.status)
            return
        now = time.time()
        if self.events:
            prev_t, prev_status, _ = self.events[-1]
            _STATE_SECONDS.observe(now - prev_t, state=prev_status)
        self.status = status
        self.events.append((now, status, detail))
        _TRANSITIONS.inc(status=status)
        extra = {"campaign_id": self.campaign_id} if self.campaign_id else {}
        if self.trace_id:
            extra["trace_id"] = self.trace_id
        obs_events.emit("job_transition", job_id=self.id, status=status,
                        detail=detail, attempt=self.attempts, **extra)
        if status in TERMINAL:
            self.finished_at = now
            if self.submitted_at is not None:
                _LATENCY.observe(now - self.submitted_at, outcome=status)
            for hook in list(self._terminal_hooks):
                try:
                    hook(self)
                except Exception as e:
                    # deliberately broad: the remaining hooks and
                    # _done.set() below MUST still run (a raising hook
                    # would strand wait_all() forever) — but a
                    # device-class error surfacing in a hook is hardware
                    # news, escalated instead of drowned in a traceback
                    from sirius_tpu.utils import devfail

                    cls = devfail.classify(e)
                    if cls in ("oom", "device_lost"):
                        logger.critical(
                            "job %s terminal hook hit a device-class "
                            "failure (%s): %s", self.id, cls, e)
                    else:
                        logger.exception(
                            "job %s terminal hook failed", self.id)
            self._done.set()

    def wait(self, timeout: float | None = None) -> bool:
        """Block until the job reaches a terminal status."""
        return self._done.wait(timeout)

    @property
    def terminal(self) -> bool:
        return self.status in TERMINAL

    @property
    def latency(self) -> float | None:
        """Submit-to-terminal wall time (the serving latency metric)."""
        if self.submitted_at is None or self.finished_at is None:
            return None
        return self.finished_at - self.submitted_at

    def to_dict(self) -> dict:
        return {
            "id": self.id,
            "status": self.status,
            "trace_id": self.trace_id,
            "tenant": self.tenant,
            "canon_hash": self.canon_hash,
            "campaign_id": self.campaign_id,
            "node_id": self.node_id,
            "parents": list(self.parents),
            "priority": self.priority,
            "attempts": self.attempts,
            "poison_strikes": self.poison_strikes,
            "oom_degrade": self.oom_degrade,
            "latency_s": self.latency,
            "error": self.error,
            "permanent": self.permanent,
            "quarantined": self.quarantined,
            "events": [
                {"t": t, "status": s, "detail": d} for t, s, d in self.events
            ],
            "result": self._result_summary(),
        }

    def _result_summary(self) -> dict | None:
        """What a stats row says of a finished job's physics: enough for
        chip_smoke.py to check energy, compile count and placement through
        the sirius-serve front door."""
        r = self.result
        if not isinstance(r, dict):
            return None
        return {
            "converged": r.get("converged"),
            "energy_total": (r.get("energy") or {}).get("total"),
            "num_scf_iterations": r.get("num_scf_iterations"),
            "compiled_executables": (r.get("serve") or {}).get(
                "compiled_executables"),
            "placement": r.get("placement"),
        }


class JobQueue:
    """Thread-safe priority queue (highest priority first, then earliest
    deadline, then submit order), with optional bounded admission."""

    def __init__(self, maxsize: int = 0, fair_share: bool = False,
                 tenants: dict[str, dict] | None = None):
        # reentrant: a terminal transition inside pop() (deadline abort,
        # upstream-skip propagation) fires hooks that may re-enter the
        # queue lock to wake dependency waiters
        self._lock = threading.RLock()
        self._not_empty = threading.Condition(self._lock)
        self._not_full = threading.Condition(self._lock)
        self._heap: list[tuple] = []
        self._seq = itertools.count()
        self._closed = False
        self.maxsize = int(maxsize)  # 0 = unbounded
        self.jobs: dict[str, Job] = {}
        # journal-replay edge resolution: terminal statuses of jobs that
        # finished in a previous process and are not in ``jobs``
        self.external_parent_status: dict[str, str] = {}
        self.high_water = 0
        # -- multi-tenant fair share (all guarded by self._lock) --------
        self.fair_share = bool(fair_share)
        # tenant -> {"weight": float, "max_queued": int|None}
        self._tenants: dict[str, dict] = {}
        self._queued_by_tenant: dict[str, int] = {}
        # DRR state: banked service quantum per tenant, the tenant the
        # pointer is currently spending on, and the last tenant the
        # pointer visited (ring position for the next advance)
        self._drr_deficit: dict[str, float] = {}
        self._drr_current: str | None = None
        self._drr_last: str | None = None
        for name, policy in (tenants or {}).items():
            if isinstance(policy, (int, float)):
                policy = {"weight": policy}  # bare-weight shorthand
            self.set_tenant(name, **dict(policy))

    def set_tenant(self, name: str, weight: float = 1.0,
                   max_queued: int | None = None) -> None:
        """Register (or update) a tenant's fair-share weight and queue
        quota. Unregistered tenants serve at weight 1 with no quota."""
        with self._lock:
            self._tenants[str(name)] = {
                "weight": max(float(weight), 1e-9),
                "max_queued": int(max_queued) if max_queued else None,
            }

    @property
    def closed(self) -> bool:
        """True once close() was called (no further admissions)."""
        return self._closed

    def _depth_changed_locked(self) -> None:
        depth = len(self._heap)
        if depth > self.high_water:
            self.high_water = depth
        _DEPTH.set(depth)
        _DEPTH_HW.max(depth)

    def _wake_on_terminal(self, job: Job) -> None:
        """Job terminal hook: a terminal transition may unblock
        dependency-deferred children, so re-wake every pop() waiter."""
        with self._lock:
            self._not_empty.notify_all()

    def _dep_state_locked(self, job: Job):
        """None when every parent is DONE (or unknown — resolved as
        satisfied so a half-replayed graph cannot deadlock); otherwise
        ``("wait"|"skip", parent_id, parent_status)``."""
        for pid in job.parents:
            parent = self.jobs.get(pid)
            status = (parent.status if parent is not None
                      else self.external_parent_status.get(pid))
            if status is None or status == JobStatus.DONE:
                continue
            if status in TERMINAL:
                return ("skip", pid, status)
            return ("wait", pid, status)
        return None

    def _tenant_count_locked(self, tenant: str, delta: int) -> None:
        n = self._queued_by_tenant.get(tenant, 0) + delta
        self._queued_by_tenant[tenant] = max(n, 0)
        _TENANT_DEPTH.set(max(n, 0), tenant=tenant)

    def _push_locked(self, job: Job) -> None:
        heapq.heappush(self._heap, (
            -job.priority,
            job.deadline if job.deadline is not None else float("inf"),
            next(self._seq),
            job,
        ))
        self._tenant_count_locked(job.tenant, +1)
        self._depth_changed_locked()
        self._not_empty.notify()

    def submit(self, job: Job, block: bool = False,
               timeout: float | None = None) -> Job:
        """Admit a new job. A bounded queue that is full rejects with
        QueueFullError immediately (``block=False``) or after waiting up
        to ``timeout`` seconds for space (``block=True``)."""
        bar = None if timeout is None else time.time() + timeout
        with self._not_empty:
            if self._closed:
                raise RuntimeError("queue is closed")
            # per-tenant quota first, and never blocking: the verdict is
            # about THIS tenant's backlog, which global space cannot fix
            policy = self._tenants.get(job.tenant)
            quota = policy.get("max_queued") if policy else None
            if quota and self._queued_by_tenant.get(job.tenant, 0) >= quota:
                _REJECTED.inc(mode="tenant")
                raise QueueFullError(
                    f"tenant {job.tenant!r} over quota "
                    f"({self._queued_by_tenant[job.tenant]}/{quota} queued)")
            while self.maxsize and len(self._heap) >= self.maxsize:
                if not block:
                    _REJECTED.inc(mode="immediate")
                    raise QueueFullError(
                        f"queue full ({len(self._heap)}/{self.maxsize})")
                remaining = None if bar is None else bar - time.time()
                if remaining is not None and remaining <= 0:
                    _REJECTED.inc(mode="timeout")
                    raise QueueFullError(
                        f"queue full ({len(self._heap)}/{self.maxsize}) "
                        f"after {timeout}s")
                self._not_full.wait(remaining)
                if self._closed:
                    raise RuntimeError("queue is closed")
            job.submitted_at = time.time()
            job.add_terminal_hook(self._wake_on_terminal)
            job._transition(JobStatus.QUEUED)
            self.jobs[job.id] = job
            self._push_locked(job)
        return job

    def requeue(self, job: Job, detail: str = "") -> None:
        """Put a transiently-failed job back (retry/resume/replay path).
        Exempt from the admission bound: this work was already accepted."""
        if job.terminal:
            return  # quarantined/drained while the retry was in flight
        with self._not_empty:
            if self._closed:
                job._transition(JobStatus.ABORTED, "queue closed")
                return
            job.add_terminal_hook(self._wake_on_terminal)
            job._transition(JobStatus.QUEUED, detail)
            self.jobs.setdefault(job.id, job)
            self._push_locked(job)

    def pop(self, timeout: float | None = None) -> Job | None:
        """Next runnable job; None on timeout or when closed and drained.
        Deadline-expired jobs are aborted here, never returned; jobs whose
        backoff bar (``not_before``) is still in the future stay queued.
        Dependency-blocked jobs (non-DONE parents) likewise stay queued
        until a parent's terminal transition wakes the waiters; a parent
        that ended failed/aborted/skipped terminally skips the child with
        ``SKIPPED_UPSTREAM`` instead of ever running it."""
        bar = None if timeout is None else time.time() + timeout
        with self._not_empty:
            while True:
                now = time.time()
                deferred: list[tuple] = []
                picked: Job | None = None
                next_ready: float | None = None
                # fair-share mode gathers the front-runnable entry of
                # EACH tenant (heap order within a tenant is preserved —
                # later same-tenant entries are deferred), then lets DRR
                # choose between tenants
                candidates: dict[str, tuple] = {}
                while self._heap:
                    entry = heapq.heappop(self._heap)
                    job = entry[3]
                    if (job.deadline is not None and now > job.deadline):
                        self._tenant_count_locked(job.tenant, -1)
                        self._depth_changed_locked()
                        self._not_full.notify()
                        job._transition(
                            JobStatus.ABORTED, "deadline expired in queue")
                        continue
                    if job.not_before is not None and job.not_before > now:
                        deferred.append(entry)
                        if next_ready is None or job.not_before < next_ready:
                            next_ready = job.not_before
                        continue
                    if job.parents:
                        dep = self._dep_state_locked(job)
                        if dep is not None:
                            state, pid, pstatus = dep
                            if state == "skip":
                                self._tenant_count_locked(job.tenant, -1)
                                self._depth_changed_locked()
                                self._not_full.notify()
                                job._transition(
                                    JobStatus.SKIPPED_UPSTREAM,
                                    f"parent {pid} {pstatus}")
                                continue
                            # parent still pending/running: stays queued
                            # until a terminal transition wakes us
                            deferred.append(entry)
                            continue
                    if not self.fair_share:
                        picked = job
                        break
                    if job.tenant in candidates:
                        deferred.append(entry)
                        continue
                    candidates[job.tenant] = entry
                if picked is None and candidates:
                    chosen = self._drr_pick_locked(candidates)
                    for tenant, entry in candidates.items():
                        if tenant == chosen:
                            picked = entry[3]
                        else:
                            deferred.append(entry)
                for entry in deferred:
                    heapq.heappush(self._heap, entry)
                if picked is not None:
                    self._tenant_count_locked(picked.tenant, -1)
                    self._depth_changed_locked()
                    self._not_full.notify()
                    return picked
                if self._closed and not self._heap:
                    return None
                # nothing runnable: wait for a submit, a backoff expiry,
                # or the caller's timeout — whichever comes first
                wait_until = bar
                if next_ready is not None:
                    wait_until = (next_ready if wait_until is None
                                  else min(wait_until, next_ready))
                if wait_until is None:
                    self._not_empty.wait()
                else:
                    remaining = wait_until - time.time()
                    expired = remaining <= 0 or not self._not_empty.wait(
                        remaining)
                    if expired and bar is not None and time.time() >= bar:
                        return None

    def _drr_pick_locked(self, candidates: dict[str, tuple]) -> str:
        """Weighted deficit round robin over the tenants that have a
        runnable job right now.

        The pointer grants a tenant ``weight`` service quantum when it
        ARRIVES there (not per pop) and keeps picking that tenant while
        it has >= 1 quantum banked, paying 1 per pop — so weight 2 vs 1
        yields a 2:1 pop ratio under sustained contention. Tenants with
        nothing runnable are dropped from the bank first: an idle tenant
        must not save up quantum and then starve everyone on return
        (classic DRR active-list semantics). Deficits are capped so
        fractional weights accumulate across visits without unbounded
        banking."""
        for tenant in list(self._drr_deficit):
            if tenant not in candidates:
                del self._drr_deficit[tenant]
        if self._drr_current not in candidates:
            self._drr_current = None
        ring = sorted(candidates)
        guard = 0
        while True:
            if self._drr_current is None:
                after = [t for t in ring if t > (self._drr_last or "")]
                tenant = after[0] if after else ring[0]
                self._drr_last = self._drr_current = tenant
                weight = (self._tenants.get(tenant) or {}).get("weight", 1.0)
                self._drr_deficit[tenant] = min(
                    self._drr_deficit.get(tenant, 0.0) + weight,
                    max(weight, 1.0) + 1.0)
            tenant = self._drr_current
            if self._drr_deficit.get(tenant, 0.0) >= 1.0:
                self._drr_deficit[tenant] -= 1.0
                return tenant
            self._drr_current = None
            guard += 1
            if guard > 1000 * len(ring):
                # unreachable with weights floored at 1e-9 in
                # set_tenant, but a scheduler must never spin forever
                logger.error("DRR failed to accumulate quantum; "
                             "falling back to first tenant")
                return ring[0]

    def abort_pending(self, detail: str,
                      leave_in_journal: bool = False) -> list[Job]:
        """Pop and terminally abort every queued entry (drain/abort
        shutdown, and the post-join safety net against close/worker-exit
        races). With ``leave_in_journal`` the jobs stay non-terminal in
        the engine journal so a restart re-runs them."""
        with self._not_empty:
            entries = self._heap
            self._heap = []
            for tenant in list(self._queued_by_tenant):
                self._tenant_count_locked(
                    tenant, -self._queued_by_tenant[tenant])
            self._depth_changed_locked()
            self._not_full.notify_all()
        out = []
        for entry in sorted(entries):
            job = entry[3]
            job.leave_in_journal = leave_in_journal
            job._transition(JobStatus.ABORTED, detail)
            out.append(job)
        return out

    def close(self) -> None:
        """Stop accepting work; blocked pop() calls drain then return
        None, blocked submit() calls fail."""
        with self._not_empty:
            self._closed = True
            self._not_empty.notify_all()
            self._not_full.notify_all()

    def __len__(self) -> int:
        with self._lock:
            return len(self._heap)
