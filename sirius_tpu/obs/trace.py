"""On-demand jax.profiler trace capture around SCF iterations.

A trace of the *whole* run is useless for long serve processes and
thousand-step MD — you want "the next N SCF iterations, starting now".
This singleton arms a capture (from ``control.trace_capture`` at run_scf
entry, or live from the serve ``/debug/trace?steps=N`` endpoint); the
SCF loop calls ``tick()`` at the top of every iteration and ``finish()``
when it leaves the loop. tick() starts the profiler at the first tick
after arming (the serve endpoint) or, with ``skip=1`` (run_scf entry),
at the job's second iteration: the first one holds the subspace
initialisation and whatever still compiles, and a capture is wanted of
the steady state. It stops after N more ticks and writes a
TensorBoard-readable directory (plugins/profile/<ts>/<host>.xplane.pb).

One clock with the program's spans (obs/spans.py). ``time.time_ns()`` is
read immediately before ``start_trace``: the xplane's events are
nanoseconds since the session's start, so ``event.start_ns +
session_start_unix_ns`` is Unix time (the xplane's own
``profile_start_time``, in its "Task Environment" plane, agrees to well
under a millisecond). Two spans are recorded on the job's trace id:
``trace.capture`` (start_trace returned -> stop asked for; attributes
``session_start_unix_ns``, ``steps``, ``first_iteration``, ``trace_dir``;
it only delimits: it is recorded at its end and is nobody's parent) and
``trace.stop`` (what stopping and writing cost: ``session_stop_s``,
``write_s``, ``xplane_bytes``). While
the capture is active every live span is mirrored as a
``jax.profiler.TraceAnnotation`` (spans.set_mirror), so xprof/Perfetto
show the program's spans above the device rows.

A third record, ``trace.scopes``, says where the device's time went in
the program's own names: the capture's file names a device operation by
the compiler's label, but it also holds the executables that ran, whose
instructions carry the ``jax.named_scope`` path that emitted them.
obs/device_scopes.py joins the two from the serialized trace the stop
has in hand (imported then, never at this module's import: with no
capture armed none of it runs) into ``by_scope`` ({path: {s, ops}},
seconds a union of intervals per device, a parent's holding its
children's), ``by_module``, ``busy_s``, ``steps``, ``devices``,
``unscoped_s`` / ``unscoped_top`` (what to name next), ``scopes_seen``,
``modules_without_hlo``, ``source`` and ``reduce_s``, what making the
table cost. It is recorded like ``trace.capture`` (an interval measured
here, nobody's parent) and kept as ``status()["last_scopes"]`` for the
operator of ``/debug/trace``.

Cheap: the profiler's Python tracer is off (it slows the host it
observes; the host tracer stays on for the annotations), and stopping
writes the ``.xplane.pb`` only. ``jax.profiler.stop_trace`` is
stop-and-export, which also converts the whole trace to a
``.trace.json.gz`` that no reader here needs: `sirius-trace export
--jax-trace-dir` reads the ``.xplane.pb``, and xprof/TensorBoard convert
it on load.

The SCF loop has several ``continue`` paths (recovery rollback, band
rescue), which is why bracketing start/stop around the loop body would
leak an open trace; counting at the loop head plus an unconditional
finish() after the loop is robust to all of them. A completed-dirs set
keeps ``control.trace_capture`` from re-arming on every MD step's
run_scf call — one trace per requested directory unless force=True
(the serve endpoint forces, with a fresh subdirectory per request).
"""

from __future__ import annotations

import os
import socket
import threading
import time

from sirius_tpu.obs import events, spans
from sirius_tpu.obs.log import get_logger

logger = get_logger("obs.trace")


class TraceCapture:
    def __init__(self):
        self._lock = threading.Lock()
        self._armed_dir: str | None = None
        self._remaining = 0
        self._skip = 0
        self._active = False
        self._done_dirs: set[str] = set()
        self._session: dict = {}
        self._iterations = 0
        self._last_scopes: dict | None = None

    def request(self, trace_dir: str, steps: int = 5, *,
                force: bool = False, skip: int = 0) -> bool:
        """Arm a capture of ``steps`` SCF iterations into ``trace_dir``,
        starting at the next iteration head after ``skip`` of them have
        passed. Returns False when already captured (and not forced) or
        a capture is in flight."""
        trace_dir = str(trace_dir)
        with self._lock:
            if self._active or self._armed_dir is not None:
                return False
            if trace_dir in self._done_dirs and not force:
                return False
            self._armed_dir = trace_dir
            self._remaining = max(1, int(steps))
            self._skip = max(0, int(skip))
        logger.info("trace capture armed: %d iterations -> %s",
                    self._remaining, trace_dir)
        return True

    def tick(self, iteration: int | None = None) -> None:
        """Call at the top of each SCF iteration (``iteration``: its
        1-based number, kept as the capture's ``first_iteration``)."""
        with self._lock:
            if self._armed_dir is not None and not self._active:
                if self._skip > 0:
                    self._skip -= 1
                    return
                target = self._armed_dir
                start = True
            elif self._active:
                self._remaining -= 1
                if self._remaining <= 0:
                    return self._stop_locked()
                self._iterations += 1
                return
            else:
                return
        if start:
            self._start(target, iteration)

    def finish(self) -> None:
        """Call after the SCF loop exits (converged, aborted, or
        exhausted) — closes a capture shorter than requested, and
        disarms one that never started (a loop that ended before the
        iteration it was to start at)."""
        with self._lock:
            if self._active:
                self._stop_locked()
            self._armed_dir = None
            self._skip = 0

    def status(self) -> dict:
        with self._lock:
            return {"active": self._active,
                    "armed_dir": self._armed_dir,
                    "remaining": self._remaining,
                    "completed": sorted(self._done_dirs),
                    "last_scopes": self._last_scopes}

    # -- internals (lock handling: _start runs unlocked because
    #    jax.profiler.start_trace can itself compile) ------------------

    def _start(self, trace_dir: str, iteration: int | None) -> None:
        try:
            os.makedirs(trace_dir, exist_ok=True)
            import jax

            opts = _profile_options()
            session_ns = time.time_ns()
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            started_ns = time.time_ns()
            spans.set_mirror(jax.profiler.TraceAnnotation)
        except Exception as exc:  # profiler unavailable on some builds
            logger.warning("trace capture failed to start: %s", exc)
            with self._lock:
                self._armed_dir = None
                self._remaining = 0
            return
        with self._lock:
            self._active = True
            self._iterations = 1  # the one whose head this is
            self._session = {
                "session_start_unix_ns": session_ns,
                "started_unix_ns": started_ns, "steps": self._remaining,
                "first_iteration": iteration, "trace_dir": trace_dir}
        events.emit("trace_capture", phase="start", trace_dir=trace_dir,
                    steps=self._remaining,
                    session_start_unix_ns=session_ns)

    def _stop_locked(self) -> None:
        # called with self._lock held
        trace_dir = self._armed_dir
        session, self._session = self._session, {}
        iterations = self._iterations
        self._active = False
        self._armed_dir = None
        self._remaining = 0
        if trace_dir is not None:
            self._done_dirs.add(trace_dir)

        def _stop():
            spans.set_mirror(None)
            started_ns = session.pop("started_unix_ns", None)
            if started_ns is not None:
                spans.record("trace.capture", start_unix_ns=started_ns,
                             end_unix_ns=time.time_ns(), **session)
            with spans.span("trace.stop", trace_dir=trace_dir) as sp:
                try:
                    fields, xspace = _stop_session(trace_dir)
                    sp.set(**fields)
                except Exception as exc:
                    logger.warning("trace capture failed to stop: %s", exc)
                    sp.set(error=type(exc).__name__)
                    return
            logger.info("trace capture written: %s", trace_dir)
            self._last_scopes = _record_scopes(xspace, iterations, trace_dir)
            events.emit("trace_capture", phase="stop", trace_dir=trace_dir,
                        ts_stop=time.time())
        # release before touching the profiler: stopping flushes to disk
        self._lock.release()
        try:
            _stop()
        finally:
            self._lock.acquire()


def _profile_options():
    """Python tracer off: it hooks every Python call of the host it is
    meant to observe. Host tracer at its default level: the mirrored
    annotations and the runtime's own events come through it."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return opts


def _stop_session(trace_dir: str) -> tuple:
    """Stop the profiler session and write its ``.xplane.pb`` where
    ``jax.profiler.stop_trace`` would, without the conversion to
    ``.trace.json.gz`` that comes with it. The session object is jax's
    own (``jax._src.profiler``); where this jax keeps it elsewhere, fall
    back to ``stop_trace``. Returns what `trace.stop` records (the
    seconds the session took to stop and hand over its data, the seconds
    and bytes of the write) and the serialized trace, None from the
    fallback."""
    import jax

    t0 = time.perf_counter()
    try:
        from jax._src import profiler as _jp

        state = _jp._profile_state
        session_stop = state.profile_session.stop
    except (ImportError, AttributeError):  # no session, or not kept there
        jax.profiler.stop_trace()  # writes the file; no trace in hand
        return {"session_stop_s": time.perf_counter() - t0}, None
    with state.lock:
        try:
            xspace = session_stop()
        finally:
            state.reset()
    t1 = time.perf_counter()
    run = os.path.join(trace_dir, "plugins", "profile",
                       time.strftime("%Y_%m_%d_%H_%M_%S"))
    os.makedirs(run, exist_ok=True)
    with open(os.path.join(run, socket.gethostname() + ".xplane.pb"),
              "wb") as f:
        f.write(xspace)
    return {"session_stop_s": t1 - t0, "write_s": time.perf_counter() - t1,
            "xplane_bytes": len(xspace)}, xspace


def _record_scopes(xspace: bytes, iterations: int, trace_dir: str):
    """The ``trace.scopes`` record of one capture (module docstring), from
    the serialized trace the stop just wrote; returns the table, None where
    it could not be made (the capture itself is on disk either way)."""
    if xspace is None:
        return None
    start_ns = time.time_ns()
    try:
        from sirius_tpu.obs import device_scopes

        table = device_scopes.table(xspace, steps=iterations)
    except Exception as exc:  # the table is a convenience of the capture
        logger.warning("trace.scopes not recorded: %s: %s",
                       type(exc).__name__, exc)
        return None
    spans.record("trace.scopes", start_unix_ns=start_ns,
                 end_unix_ns=time.time_ns(), trace_dir=trace_dir, **table)
    return table


CAPTURE = TraceCapture()
