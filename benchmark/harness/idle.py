"""Who owns the device's idle time, and a span's self time.

The program's spans (obs/spans.py) carry their start and end in Unix
nanoseconds; the profiler's operations are nanoseconds since its session's
start; the program's ``trace.capture`` span records when that was, on the
spans' clock (``session_start_unix_ns``), and delimits the part of the trace
in which the program ran under the profiler (start_trace returned -> stop
asked for). So every instant of the capture has an innermost open span on the
traced job's thread, and every idle interval of a device can be cut at the
span boundaries and charged to it.

``charge(record)`` does that from ``record["trace_raw"]`` (trace_reduce's
columns) and ``record["trace_job"]["spans"]``, mean over the chips used, and
leaves the table in ``record["notes"]``, which run.py prints as the ``notes``
event. It returns None where the program records no ``trace.capture`` (a
program older than the span tree): the metrics that read it are then left out
of the line.
"""

from __future__ import annotations

import statistics

import numpy as np

NO_SPAN = "(no span)"
CAPTURE = "trace.capture"


# ---- the span tree ---------------------------------------------------------

def _has_clock(span: dict) -> bool:
    return "start_unix_ns" in span and "end_unix_ns" in span


def children(spans: list) -> dict:
    """parent span_id -> its child records."""
    kids: dict = {}
    for r in spans:
        kids.setdefault(r.get("parent_id"), []).append(r)
    return kids


def self_seconds(span: dict, kids: list) -> float | None:
    """A span's seconds less the union of its children's, each child cut to
    the span (a wait recorded from outside may begin before its parent).
    None where the records carry no shared clock."""
    if not _has_clock(span) or not all(_has_clock(k) for k in kids):
        return None
    lo, hi = span["start_unix_ns"], span["end_unix_ns"]
    covered, edge = 0, lo
    for s, e in sorted((max(k["start_unix_ns"], lo), min(k["end_unix_ns"], hi))
                       for k in kids):
        if e > edge:
            covered += e - max(s, edge)
            edge = e
    return (hi - lo - covered) * 1e-9


def self_ms(record: dict, args: dict) -> float | None:
    """What the two self-time metrics read: per counted job the self time of
    its spans named ``args["span"]``, their median or sum (``args["over"]``);
    median over jobs. None where the program's spans form no tree (no span of
    that name has a child)."""
    per = []
    for j in record["jobs"]:
        if j.get("result") is None:
            continue
        kids = children(j.get("spans", []))
        own = [self_seconds(r, kids[r["span_id"]]) for r in j.get("spans", [])
               if r["name"] == args["span"] and r["span_id"] in kids]
        own = [s for s in own if s is not None]
        if own:
            per.append(args["scale"] * (statistics.median(own)
                                        if args["over"] == "median" else sum(own)))
    return statistics.median(per) if per else None


def _owner_segments(spans: list, zero: int, lo: float, hi: float):
    """Cut [lo, hi) (ns since the session's start) at the boundaries of
    ``spans``: (edges, paths), where paths[k] names the spans open in
    [edges[k], edges[k+1]) from the outermost to the innermost, () where
    none is. Spans of one thread nest, so the innermost of those open is the
    one that started last."""
    iv = []
    for r in spans:
        s, e = r["start_unix_ns"] - zero, r["end_unix_ns"] - zero
        if e > lo and s < hi:
            iv.append((float(max(s, lo)), float(min(e, hi)), r))
    edges = sorted({lo, hi, *(x for s, e, _ in iv for x in (s, e))})
    by_id = {r["span_id"]: r for _, _, r in iv}
    paths = []
    for a, b in zip(edges, edges[1:]):
        mid = 0.5 * (a + b)
        open_ = [(s, -e, r) for s, e, r in iv if s <= mid < e]
        if not open_:
            paths.append(())
            continue
        r = max(open_, key=lambda t: t[:2])[2]
        path = [r["name"]]
        while r.get("parent_id") in by_id:
            r = by_id[r["parent_id"]]
            path.append(r["name"])
        paths.append(tuple(reversed(path)))
    return np.asarray(edges), paths


# ---- the device's idle intervals -------------------------------------------

def idle_intervals(starts, ends, lo: float, hi: float):
    """The complement inside [lo, hi) of the union of [start, end)
    intervals: (gap starts, gap ends), sorted."""
    s = np.clip(np.asarray(starts, dtype=np.float64), lo, hi)
    e = np.clip(np.asarray(ends, dtype=np.float64), lo, hi)
    keep = e > s
    s, e = s[keep], e[keep]
    if s.size == 0:
        return np.array([lo]), np.array([hi])
    order = np.argsort(s, kind="stable")
    s, e = s[order], np.maximum.accumulate(e[order])
    g_s = np.concatenate(([lo], e))
    g_e = np.concatenate((s, [hi]))
    keep = g_e > g_s
    return g_s[keep], g_e[keep]


def _idle_before(g_s, g_e, cum, t):
    """Idle nanoseconds in [first gap, t) for each t."""
    i = np.searchsorted(g_s, t, side="right") - 1
    j = np.maximum(i, 0)
    part = np.minimum(t, g_e[j]) - g_s[j]
    return np.where(i >= 0, cum[j] + part, 0.0)


def _charge_device(g_s, g_e, edges):
    """Per segment of ``edges``: idle ns, number of gap pieces, longest."""
    nseg = len(edges) - 1
    if g_s.size == 0:
        return np.zeros(nseg), np.zeros(nseg, dtype=int), np.zeros(nseg)
    cum = np.concatenate(([0.0], np.cumsum(g_e - g_s)))
    before = _idle_before(g_s, g_e, cum, edges)
    first = np.searchsorted(g_e, edges[:-1], side="right")
    last = np.searchsorted(g_s, edges[1:], side="left")
    longest = np.zeros(nseg)
    for k in range(nseg):
        if last[k] > first[k]:
            a = np.maximum(g_s[first[k]:last[k]], edges[k])
            b = np.minimum(g_e[first[k]:last[k]], edges[k + 1])
            longest[k] = float(np.max(b - a))
    return np.diff(before), np.maximum(last - first, 0), longest


# ---- the table ---------------------------------------------------------------

def charge(record: dict) -> dict | None:
    """Charge each device's idle time inside the capture to the innermost
    open span of the traced job's thread. Returns (and caches in the record)
    {"capture_s", "iterations", "idle_in_capture_s", "idle_outside_capture_s",
    "by_name": {name: {"idle_s", "gaps", "longest_s"}}, "segments": [(path,
    idle_s)], "table": [[name, idle_s], ...] longest first, and what the
    profiler's own edges cost: "start_trace_s", "stop_s" (of it
    "session_stop_s", "write_s"), "xplane_bytes"};
    seconds and gap counts are means over the devices, the longest gap is the
    longest on any.
    """
    if "_idle_charge" in record:
        return record["_idle_charge"]
    record["_idle_charge"] = out = _charge(record)
    if out is not None:
        notes = record.setdefault("notes", {})
        notes["idle_gaps"] = out["table"]
        notes["idle_capture"] = {
            k: out[k] for k in (
                "capture_s", "iterations", "idle_in_capture_s",
                "idle_outside_capture_s", "owned_share", "start_trace_s",
                "stop_s", "session_stop_s", "write_s", "xplane_bytes")}
        notes["idle_detail"] = {
            name: [v["idle_s"], v["gaps"], v["longest_s"]]
            for name, v in out["by_name"].items()}
    return out


def _charge(record: dict) -> dict | None:
    raw, job = record.get("trace_raw"), record.get("trace_job")
    if not raw or not raw.get("devices") or not job:
        return None
    spans = [r for r in job.get("spans", []) if _has_clock(r)]
    cap = next((r for r in spans if r["name"] == CAPTURE
                and "session_start_unix_ns" in r), None)
    if cap is None:
        return None
    zero = int(cap["session_start_unix_ns"])
    lo, hi = float(cap["start_unix_ns"] - zero), float(cap["end_unix_ns"] - zero)
    if hi <= lo:
        return None
    # the traced job's thread: the one the capture was started and stopped on
    mine = [r for r in spans if r["name"] != CAPTURE
            and (r.get("pid"), r.get("thread")) == (cap.get("pid"),
                                                    cap.get("thread"))]
    edges, paths = _owner_segments(mine, zero, lo, hi)
    w_lo, w_hi = (float(x) for x in raw["window_ns"])
    w_lo, w_hi = min(w_lo, lo), max(w_hi, hi)

    dev = np.asarray(raw["dev"])
    start = np.asarray(raw["start_ns"], dtype=np.float64)
    end = start + np.asarray(raw["dur_ns"], dtype=np.float64)
    ndev = len(raw["devices"])
    nseg = len(paths)
    idle, gaps, longest = np.zeros(nseg), np.zeros(nseg), np.zeros(nseg)
    outside = 0.0
    for d in range(ndev):
        sel = dev == d
        g_s, g_e = idle_intervals(start[sel], end[sel], lo, hi)
        i_ns, n, lg = _charge_device(g_s, g_e, edges)
        idle += i_ns / ndev
        gaps += n / ndev
        longest = np.maximum(longest, lg)
        for a, b in ((w_lo, lo), (hi, w_hi)):  # the profiler's own edges
            if b > a:
                o_s, o_e = idle_intervals(start[sel], end[sel], a, b)
                outside += float(np.sum(o_e - o_s)) / ndev

    by_name: dict = {}
    segments = []
    for k, path in enumerate(paths):
        name = path[-1] if path else NO_SPAN
        row = by_name.setdefault(name, {"idle_s": 0.0, "gaps": 0.0,
                                        "longest_s": 0.0})
        row["idle_s"] += idle[k] * 1e-9
        row["gaps"] += float(gaps[k])
        row["longest_s"] = max(row["longest_s"], longest[k] * 1e-9)
        segments.append((path, idle[k] * 1e-9))
    table = sorted(([n, v["idle_s"]] for n, v in by_name.items()),
                   key=lambda kv: -kv[1])
    total = float(np.sum(idle)) * 1e-9
    unowned = by_name.get(NO_SPAN, {"idle_s": 0.0})["idle_s"]
    iterations = sum(1 for r in mine if r["name"] == "scf.iteration"
                     and lo <= r["start_unix_ns"] - zero
                     and r["end_unix_ns"] - zero <= hi)
    stop = next((r for r in spans if r["name"] == "trace.stop"), {})
    return {"capture_s": (hi - lo) * 1e-9, "iterations": iterations,
            "start_trace_s": lo * 1e-9, "stop_s": stop.get("dur_s"),
            "session_stop_s": stop.get("session_stop_s"),
            "write_s": stop.get("write_s"),
            "xplane_bytes": stop.get("xplane_bytes"),
            "idle_in_capture_s": total, "idle_outside_capture_s": outside * 1e-9,
            "owned_share": (1.0 - unowned / total) if total > 0 else None,
            "by_name": by_name, "segments": segments, "table": table}


def idle_under(charged: dict, names) -> float:
    """Idle seconds charged to spans of these names and to whatever was open
    under them."""
    names = set(names)
    return sum(s for path, s in charged["segments"] if names & set(path))


def idle_ms_per_iteration(record: dict, args: dict) -> float | None:
    """What the two idle metrics read: idle ms charged to ``args["spans"]``
    and below, per traced iteration."""
    charged = charge(record)
    if charged is None or not charged["iterations"]:
        return None
    return (float(args.get("scale", 1000.0)) * idle_under(charged, args["spans"])
            / charged["iterations"])
