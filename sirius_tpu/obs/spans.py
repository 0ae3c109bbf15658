"""Nestable wall-clock span timeline — the performance-attribution layer.

`utils/profiler.py` keeps the reference-style cumulative timer tree
(timers.json report); this module is the *event* view of the same
instants: every span is one record with identity (span_id), lineage
(parent_id via a contextvar, so nesting survives generators and
callbacks), monotonic start/duration, and optional analytic cost
annotations (GFLOP/s, roofline ceiling, MFU from obs/costs.py when the
producer attaches a flops/bytes estimate).

Three consumers, all fed on span close:

- the JSONL event sink (obs/events.py): one ``kind="span"`` record per
  completed span, carrying job_id/step from the logging context;
- the metrics registry: a ``perf_span_seconds`` histogram labelled by
  span name (the Prometheus-side view of the timeline);
- in-process `capture()` collectors: tools/bench_regress.py runs an SCF
  under `with capture() as cap:` and reads per-stage durations straight
  from `cap` without parsing the event log.

Device-bound spans and fencing: XLA dispatch is asynchronous, so a bare
host timer around `davidson_kset(...)` measures dispatch, not compute —
the wall time lands in whichever span first blocks (usually the scalar
readback). Durations still *sum* to the true wall time, but per-stage
attribution is skewed. Passing ``fence=`` (a jax pytree, or assigning
``sp.fence = out`` inside the block) makes ``__exit__`` call
``jax.block_until_ready`` on it first, charging the compute to the span
that launched it. run_scf wires this behind ``control.span_fence``
(default off: production never pays the sync; bench_regress turns it on
for truthful attribution).

One clock: every record carries ``start_unix_ns`` / ``end_unix_ns``,
integer Unix nanoseconds (``time.time_ns()``) read at open and at close
(after the fence), beside the float ``t0`` and the perf_counter
``dur_s``. obs/trace.py records the same clock just before the profiler
session starts, so ``start_unix_ns - session_start_unix_ns`` is a span's
place on the device trace's time axis. While such a capture is active
(and only then: `set_mirror`) every live span is also entered as a
``jax.profiler.TraceAnnotation`` of the same name, so the profiler's own
file shows the program's spans above the device rows.

Two forms. ``with span(name): ...`` where the block is short;
``sp = open_span(name)`` ... ``sp.close()`` where the work between is
too long to re-indent (run_scf's loop). Closing a span first closes
whatever was opened under it and is still open (a ``continue`` path, an
exception that jumped a ``close()``), marking those ``unwound``: nothing
stays open and the contextvar is restored.

When telemetry is disabled (``control.telemetry = false`` ->
obs.metrics.set_enabled(False)) every span is a no-op: ``__enter__``
returns after one flag test — no contextvar writes, no clock reads, no
records anywhere; ``open_span`` hands back one shared inert object.
"""

from __future__ import annotations

import contextlib
import contextvars
import functools
import itertools
import threading
import time

from sirius_tpu.obs import events as _events
from sirius_tpu.obs import metrics as _metrics
from sirius_tpu.obs import tracing as _tracing

# the innermost live span of this logical context (contextvar, not a
# thread-local stack: lineage must survive contextvars-aware frameworks
# and stays isolated per serve worker thread)
_parent: contextvars.ContextVar = contextvars.ContextVar(
    "sirius_tpu_span_parent", default=None)
_next_id = itertools.count(1)

_collectors_lock = threading.Lock()
_collectors: list["SpanCapture"] = []

# jax.profiler.TraceAnnotation while a profiler capture is active, else
# None (obs/trace.py sets and clears it; this module never imports jax
# at import time)
_mirror = None


def set_mirror(annotation) -> None:
    """Enter every live span as ``annotation(name)`` too (a context
    manager class: jax.profiler.TraceAnnotation), or stop doing so
    (None). Spans already open are not touched."""
    global _mirror
    _mirror = annotation


class SpanCapture:
    """In-process sink of finished span records (plain dicts)."""

    def __init__(self):
        self.records: list[dict] = []
        self._lock = threading.Lock()

    def add(self, rec: dict) -> None:
        with self._lock:
            self.records.append(rec)

    def by_name(self, name: str) -> list[dict]:
        with self._lock:
            return [r for r in self.records if r["name"] == name]

    def durations(self, name: str) -> list[float]:
        return [r["dur_s"] for r in self.by_name(name)]

    def names(self) -> set[str]:
        with self._lock:
            return {r["name"] for r in self.records}


@contextlib.contextmanager
def capture():
    """Collect every span finished anywhere in the process while the
    context is open (process-global, like the event sink — the producers
    span serve worker threads)."""
    cap = SpanCapture()
    with _collectors_lock:
        _collectors.append(cap)
    try:
        yield cap
    finally:
        with _collectors_lock:
            _collectors.remove(cap)


def _finish(rec: dict) -> None:
    _metrics.REGISTRY.histogram(
        "perf_span_seconds", "span-timeline durations by span name").observe(
            rec["dur_s"], span=rec["name"])
    _events.emit("span", **rec)
    with _collectors_lock:
        caps = list(_collectors)
    for cap in caps:
        cap.add(rec)


class span:
    """Context manager: ``with span("scf.density", flops=f) as sp: ...``

    ``fence``: jax pytree (or callable returning one) blocked on before
    the clock stops; assignable inside the block (``sp.fence = out``).
    ``flops``/``bytes``: analytic cost estimate for this span's work —
    when given, the record is annotated with achieved GFLOP/s, the
    roofline ceiling, and MFU against the shared peak table
    (obs/costs.py). Extra keyword arguments become record fields.
    """

    __slots__ = ("name", "attrs", "fence", "flops", "bytes", "span_id",
                 "parent_id", "depth", "dur_s", "_t0", "_t0_ns", "_up",
                 "_ann", "_token")

    def __init__(self, name: str, fence=None, flops: float = 0.0,
                 bytes: float = 0.0, **attrs):
        self.name = name
        self.fence = fence
        self.flops = flops
        self.bytes = bytes
        self.attrs = attrs
        self.dur_s = None

    def __enter__(self):
        if not _metrics.enabled():
            return self
        parent = _parent.get()
        self.span_id = next(_next_id)
        self.parent_id = parent.span_id if parent is not None else None
        self.depth = (parent.depth + 1) if parent is not None else 0
        self._up = parent
        self._token = _parent.set(self)
        self._ann = None
        mirror = _mirror
        if mirror is not None:
            try:
                ann = mirror(self.name)
                ann.__enter__()
                self._ann = ann
            except Exception:
                pass  # the mirror is a convenience of the capture
        self._t0_ns = time.time_ns()
        self._t0 = time.perf_counter()
        return self

    @property
    def start_unix_ns(self) -> int:
        """When the span was opened (live spans, telemetry on)."""
        return self._t0_ns

    def set(self, **attrs) -> "span":
        """More record fields, known only once the work is under way."""
        self.attrs.update(attrs)
        return self

    def close(self, **attrs) -> None:
        """The explicit form's ``__exit__``; ``attrs`` as in `set`."""
        self.attrs.update(attrs)
        self.__exit__(None, None, None)

    def __exit__(self, exc_type, exc, tb):
        if not hasattr(self, "_token"):
            return False  # telemetry was off at __enter__, or closed twice
        if _parent.get() is not self:
            _unwind(self)
        if self.fence is not None:
            try:
                import jax

                jax.block_until_ready(
                    self.fence() if callable(self.fence) else self.fence)
            except Exception:
                pass  # fencing is best-effort observability, never fatal
        self.dur_s = time.perf_counter() - self._t0
        end_ns = time.time_ns()
        if self._ann is not None:
            try:
                self._ann.__exit__(exc_type, exc, tb)
            except Exception:
                pass
        try:
            _parent.reset(self._token)
        except ValueError:
            pass  # closed from another context: nothing of ours to restore
        del self._token
        rec = {
            "name": self.name,
            "span_id": self.span_id,
            "parent_id": self.parent_id,
            "depth": self.depth,
            "t0": self._t0_ns * 1e-9,
            "dur_s": self.dur_s,
            "start_unix_ns": self._t0_ns,
            "end_unix_ns": end_ns,
            **_tracing.context_fields(),
        }
        if exc_type is not None:
            rec["error"] = exc_type.__name__
        if self.attrs:
            rec.update(self.attrs)
        if self.flops:
            from sirius_tpu.obs import costs as _costs

            rec.update(_costs.annotate_span(self.dur_s, self.flops,
                                            self.bytes))
        _finish(rec)
        return False


def _unwind(to: span) -> None:
    """Close, innermost first and marked ``unwound``, every span opened
    under ``to`` in this context and still open. Does nothing where
    ``to`` is not among the open spans of this context."""
    cur = _parent.get()
    chain = []
    while cur is not None and cur is not to:
        chain.append(cur)
        cur = cur._up
    if cur is None:
        return
    for sp in chain:
        sp.attrs["unwound"] = True
        sp.__exit__(None, None, None)


class _Off:
    """What `open_span` returns with telemetry off: nothing happens."""

    __slots__ = ()
    attrs: dict = {}
    fence = None

    def set(self, **attrs):
        return self

    def close(self, **attrs) -> None:
        return None

    def __setattr__(self, name, value) -> None:
        return None  # ``sp.fence = out`` on the inert span


_OFF = _Off()


def open_span(name: str, **span_kw):
    """The explicit form: a live span, opened now; the caller calls
    ``close()`` where the work ends. With telemetry off: one flag test."""
    if not _metrics.enabled():
        return _OFF
    return span(name, **span_kw).__enter__()


def record(name: str, dur_s: float | None = None, t0: float | None = None,
           flops: float = 0.0, bytes: float = 0.0,
           start_unix_ns: int | None = None, end_unix_ns: int | None = None,
           **attrs) -> None:
    """Record an interval that was measured from outside (the serve
    queue wait: a timestamp delta that began before any worker had the
    job; a profiler capture that starts under one span and stops under
    another). Give the start as ``start_unix_ns`` or ``t0`` (Unix
    seconds); without either it is taken as ``dur_s`` before now. Work
    of this process that can be bracketed is a live `span` instead.
    Lineage comes from the current contextvar like a live span."""
    if not _metrics.enabled():
        return
    if start_unix_ns is None:
        start_unix_ns = (int(float(t0) * 1e9) if t0 is not None
                         else time.time_ns() - int(float(dur_s) * 1e9))
    if end_unix_ns is None:
        end_unix_ns = start_unix_ns + int(float(dur_s) * 1e9)
    if dur_s is None:
        dur_s = (end_unix_ns - start_unix_ns) * 1e-9
    parent = _parent.get()
    rec = {
        "name": name,
        "span_id": next(_next_id),
        "parent_id": parent.span_id if parent is not None else None,
        "depth": (parent.depth + 1) if parent is not None else 0,
        "t0": start_unix_ns * 1e-9,
        "dur_s": float(dur_s),
        "start_unix_ns": int(start_unix_ns),
        "end_unix_ns": int(end_unix_ns),
        **_tracing.context_fields(),
    }
    if attrs:
        rec.update(attrs)
    if flops:
        from sirius_tpu.obs import costs as _costs

        rec.update(_costs.annotate_span(float(dur_s), flops, bytes))
    _finish(rec)


def spanned(name: str | None = None, **span_kw):
    """Decorator form: ``@spanned("md.extrapolate")`` (defaults to the
    function's qualified name)."""

    def deco(fn):
        label = name or fn.__qualname__

        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            with span(label, **span_kw):
                return fn(*args, **kwargs)

        return wrapper

    return deco


def current() -> "span | None":
    """The innermost live span of this context (None at top level)."""
    return _parent.get()
