"""Timeline export: the span/event JSONL merged into one Chrome-trace /
Perfetto JSON, plus the campaign critical-path analyzer.

The span timeline (obs/spans.py) and event sink (obs/events.py) already
record everything a distributed trace needs — identity (trace_id from
obs/tracing.py), physical placement (pid, thread), lineage (span_id /
parent_id), wall-clock intervals — but as JSONL, which no timeline UI
reads. This module folds them into the Chrome trace-event format
(https://ui.perfetto.dev loads it directly):

- one *process* per OS pid seen in the records (serve engine restarts
  across a SIGKILL show up as two processes sharing one trace_id —
  exactly the story the trace should tell);
- one *thread track* per worker thread (spans become "X" complete
  events, non-span events become "i" instants on the same track);
- one synthetic *campaign process* per campaign, with a track per DAG
  node spanning its RUNNING->terminal interval, and "s"/"f" flow arrows
  along the handoff edges;
- "C" counter tracks for the per-iteration HBM high-water samples that
  dft/scf.py attaches to scf.iteration spans, and for the numerics
  observatory: the SCF residual and on-device ledger invariants
  (scf_iteration events), the decay-rate/forecast/early-warning series
  (scf_forecast events) and the per-stage precision-headroom probe
  impacts (numerics_probe events) each render as counter series;
- optionally, the jax.profiler device traces merged in with offset pids
  — one track per device line, stitched under the same timeline. An
  obs/trace.py capture writes the ``.xplane.pb`` only; it is read here
  directly and put on the spans' clock (Unix time) by the session start
  the profiler records in it. A ``*.trace.json(.gz)`` beside it (a
  capture taken with ``jax.profiler.stop_trace``, or exported by
  xprof/TensorBoard from the ``.xplane.pb``) is merged as it is: the
  profiler's own format already IS Chrome JSON.

The critical-path analyzer reads the campaign DAG shape from the
``campaign_submit`` event (runner.py ships ``edges``), node intervals
from ``job_transition`` events, and SCF effort from ``scf_done``; it
reports the longest path, per-node slack (classic CPM es/ef/ls/lf), and
a warm-start savings estimate per handoff edge.

CLI (``sirius-trace``):

    sirius-trace export --events run/events.jsonl --out timeline.json
    sirius-trace validate timeline.json
    sirius-trace critical-path --events run/events.jsonl
"""

from __future__ import annotations

import argparse
import glob
import gzip
import json
import os
import sys

from sirius_tpu.obs import events as _events
from sirius_tpu.obs import spans as _spans

_US = 1_000_000  # chrome trace timestamps are microseconds


# ---------------------------------------------------------------------------
# chrome-trace building


def _tid_for(tid_map: dict, pid: int, thread: str) -> int:
    key = (pid, str(thread))
    if key not in tid_map:
        tid_map[key] = len([k for k in tid_map if k[0] == pid]) + 1
    return tid_map[key]


def build_chrome_trace(records: list[dict], trace_id: str | None = None,
                       campaign_id: str | None = None) -> dict:
    """Fold event-sink records into a Chrome trace-event document.

    trace_id: keep only records of that trace (None = all).
    campaign_id: restrict the synthetic campaign tracks (None = all
    campaigns present).
    """
    if trace_id is not None:
        records = [r for r in records if r.get("trace_id") == trace_id]
    ev: list[dict] = []
    tid_map: dict = {}
    pids_seen: set[int] = set()

    for r in records:
        kind = r.get("kind")
        pid = int(r.get("pid") or 0)
        thread = r.get("thread") or "main"
        if kind == "span":
            tid = _tid_for(tid_map, pid, thread)
            pids_seen.add(pid)
            args = {k: v for k, v in r.items()
                    if k not in ("kind", "name", "t0", "dur_s", "ts",
                                 "pid", "thread")}
            ev.append({
                "name": r.get("name", "span"), "ph": "X", "cat": "span",
                "ts": int(float(r["t0"]) * _US),
                "dur": max(1, int(float(r["dur_s"]) * _US)),
                "pid": pid, "tid": tid, "args": args,
            })
            if r.get("hbm_peak_bytes") is not None:
                ev.append({
                    "name": "hbm_peak_bytes", "ph": "C",
                    "ts": int((float(r["t0"]) + float(r["dur_s"])) * _US),
                    "pid": pid, "tid": tid,
                    "args": {"bytes": float(r["hbm_peak_bytes"])},
                })
        elif "ts" in r:
            tid = _tid_for(tid_map, pid, thread)
            pids_seen.add(pid)
            args = {k: v for k, v in r.items()
                    if k not in ("kind", "ts", "pid", "thread")}
            ev.append({
                "name": kind or "event", "ph": "i", "cat": "event",
                "ts": int(float(r["ts"]) * _US), "s": "t",
                "pid": pid, "tid": tid, "args": args,
            })
            # numerics observatory counter tracks (obs/numerics.py +
            # obs/forecast.py): residual, ledger invariants, forecast and
            # probe headroom render as Perfetto counter series next to
            # the hbm_peak_bytes track above
            cts = int(float(r["ts"]) * _US)
            if kind == "scf_iteration":
                if isinstance(r.get("rms"), (int, float)):
                    ev.append({"name": "scf_residual", "ph": "C",
                               "ts": cts, "pid": pid, "tid": tid,
                               "args": {"rms": float(r["rms"])}})
                led = r.get("ledger")
                if isinstance(led, dict) and led:
                    ev.append({
                        "name": "numerics_ledger", "ph": "C", "ts": cts,
                        "pid": pid, "tid": tid,
                        "args": {k: float(v) for k, v in led.items()
                                 if isinstance(v, (int, float))}})
            elif kind == "scf_forecast":
                fc = {k: float(r[k]) for k in
                      ("decay_rate", "forecast_remaining", "warning")
                      if isinstance(r.get(k), (int, float))}
                if fc:
                    ev.append({"name": "scf_forecast", "ph": "C",
                               "ts": cts, "pid": pid, "tid": tid,
                               "args": fc})
            elif kind == "numerics_probe":
                if isinstance(r.get("energy_impact_ha"), (int, float)):
                    series = f"{r.get('stage')}:{r.get('prec')}"
                    ev.append({
                        "name": "numerics_headroom", "ph": "C", "ts": cts,
                        "pid": pid, "tid": tid,
                        "args": {series: float(r["energy_impact_ha"])}})

    ev.extend(_campaign_tracks(records, campaign_id))

    # metadata: name the processes and thread tracks
    meta: list[dict] = []
    for pid in sorted(pids_seen):
        meta.append({"name": "process_name", "ph": "M", "pid": pid,
                     "args": {"name": f"sirius pid {pid}"}})
    for (pid, thread), tid in sorted(tid_map.items(), key=lambda x: x[1]):
        meta.append({"name": "thread_name", "ph": "M", "pid": pid,
                     "tid": tid, "args": {"name": thread}})
    return {"traceEvents": meta + ev, "displayTimeUnit": "ms"}


def _campaign_tracks(records: list[dict],
                     campaign_id: str | None = None) -> list[dict]:
    """Synthetic per-campaign process: one track per DAG node spanning its
    RUNNING->terminal interval, with flow arrows along handoff edges."""
    submits = [r for r in records if r.get("kind") == "campaign_submit"
               and (campaign_id is None
                    or r.get("campaign_id") == campaign_id)]
    out: list[dict] = []
    for ci, sub in enumerate(submits):
        cid = sub.get("campaign_id")
        edges = sub.get("edges") or {}
        nodes = sub.get("nodes") or sorted(edges)
        pid = 90000 + ci  # out of the way of real OS pids
        out.append({"name": "process_name", "ph": "M", "pid": pid,
                    "args": {"name": f"campaign {cid}"}})
        iv = _node_intervals(records, cid)
        tids = {n: i + 1 for i, n in enumerate(nodes)}
        for n, t in tids.items():
            out.append({"name": "thread_name", "ph": "M", "pid": pid,
                        "tid": t, "args": {"name": f"node {n}"}})
            span = iv.get(n)
            if span is None:
                continue
            out.append({
                "name": f"{cid}.{n}", "ph": "X", "cat": "campaign_node",
                "ts": int(span["start"] * _US),
                "dur": max(1, int((span["end"] - span["start"]) * _US)),
                "pid": pid, "tid": t,
                "args": {"status": span["status"], "campaign_id": cid,
                         "node_id": n},
            })
        flow = 0
        for child, parents in edges.items():
            for parent in parents or []:
                if parent not in iv or child not in iv:
                    continue
                flow += 1
                fid = f"{cid}:{parent}->{child}"
                out.append({"name": "handoff", "ph": "s", "cat": "handoff",
                            "id": fid, "ts": int(iv[parent]["end"] * _US),
                            "pid": pid, "tid": tids.get(parent, 0)})
                out.append({"name": "handoff", "ph": "f", "cat": "handoff",
                            "bp": "e", "id": fid,
                            "ts": int(iv[child]["start"] * _US),
                            "pid": pid, "tid": tids.get(child, 0)})
    return out


_TERMINAL = ("done", "failed", "aborted", "skipped_upstream")


def _node_intervals(records: list[dict], cid: str) -> dict:
    """{node_id: {queued, start, end, status}} from the job_transition
    events of one campaign. ``queued`` is the submit-time transition,
    ``start`` the first COMPILING/RUNNING transition (what the timeline
    track draws; falls back to ``queued`` for nodes that never ran),
    ``end`` the terminal transition. The critical-path analyzer needs
    both anchors: the scheduler does real per-node setup (deck parsing,
    context build) between queue pop and the COMPILING transition, so
    charging a node only start->end would leak that work out of the
    wall reconciliation, while charging queued->end would charge a
    child its parent's whole runtime."""
    raw: dict[str, dict] = {}
    for r in records:
        if r.get("kind") != "job_transition" or r.get("campaign_id") != cid:
            continue
        jid = str(r.get("job_id") or "")
        node = jid[len(cid) + 1:] if jid.startswith(f"{cid}.") else jid
        ts = float(r["ts"])
        status = r.get("status")
        e = raw.setdefault(node, {"queued": ts, "start": None, "end": ts,
                                  "status": status})
        if status in ("compiling", "running") and e["start"] is None:
            e["start"] = ts
        if e["status"] not in _TERMINAL:
            e["end"] = ts
            e["status"] = status
    for e in raw.values():
        if e["start"] is None:
            e["start"] = e["queued"]
    return raw


# ---------------------------------------------------------------------------
# jax.profiler merge (best-effort)


def _merge_xplane(doc: dict, path: str, pid_base: int) -> int:
    """One ``.xplane.pb`` as "X" events: a process per plane, a thread
    per line. Event times are nanoseconds since the session's start; the
    "Task Environment" plane's ``profile_start_time`` (Unix ns) puts
    them on the spans' clock. A device operation under one of the
    program's ``jax.named_scope`` names carries its scope path as
    ``args.scope``, through the reader that makes a capture's
    ``trace.scopes`` table (obs/device_scopes.py)."""
    import jax

    from sirius_tpu.obs import device_scopes

    with open(path, "rb") as f:
        data = f.read()
    pd = jax.profiler.ProfileData.from_serialized_xspace(data)
    try:
        scopes = device_scopes.event_scopes(data)
    except Exception:  # a file without the executables' text still merges
        scopes = {}
    env = pd.find_plane_with_name("Task Environment")
    start_ns = int(dict(env.stats).get("profile_start_time", 0)) if env else 0
    ev = doc.setdefault("traceEvents", [])
    merged = 0
    for i, plane in enumerate(pd.planes):
        pid = pid_base + i
        named = False
        for tid, line in enumerate(plane.lines, start=1):
            first = True
            for e in line.events:
                if first:
                    first = False
                    if not named:
                        named = True
                        ev.append({"name": "process_name", "ph": "M",
                                   "pid": pid, "tid": 0,
                                   "args": {"name": plane.name}})
                    ev.append({"name": "thread_name", "ph": "M", "pid": pid,
                               "tid": tid, "args": {"name": line.name}})
                rec = {
                    "name": e.name, "ph": "X", "cat": "xplane",
                    "ts": (start_ns + e.start_ns) / 1000.0,
                    "dur": max(e.duration_ns / 1000.0, 0.001),
                    "pid": pid, "tid": tid,
                }
                scope = scopes and scopes.get((
                    plane.name, device_scopes.instruction_name(e.name),
                    float(e.start_ns)))
                if scope:
                    rec["args"] = {"scope": scope}
                ev.append(rec)
                merged += 1
    return merged


def merge_jax_profiler_trace(doc: dict, trace_dir: str,
                             pid_offset: int = 100000) -> int:
    """Merge the profiler traces under ``trace_dir`` into ``doc`` with
    offset pids so device tracks sit next to the host tracks:
    ``*.trace.json[.gz]`` files where there are any, else the
    ``*.xplane.pb`` that obs/trace.py captures write. Returns the number
    of events merged; silently returns 0 when nothing usable is found."""
    merged = 0
    pats = ("**/*.trace.json.gz", "**/*.trace.json")
    files = []
    for p in pats:
        files.extend(glob.glob(os.path.join(trace_dir, p), recursive=True))
    for i, f in enumerate(sorted(files)):
        try:
            opener = gzip.open if f.endswith(".gz") else open
            with opener(f, "rt", encoding="utf-8") as fh:
                sub = json.load(fh)
            sub_ev = sub.get("traceEvents") or []
        except Exception:
            continue
        for e in sub_ev:
            if not isinstance(e, dict) or "ph" not in e:
                continue
            e = dict(e)
            e["pid"] = int(e.get("pid") or 0) + pid_offset + i * 1000
            doc.setdefault("traceEvents", []).append(e)
            merged += 1
    if not files:
        planes = sorted(glob.glob(os.path.join(trace_dir, "**/*.xplane.pb"),
                                  recursive=True))
        for i, f in enumerate(planes):
            try:
                merged += _merge_xplane(doc, f, pid_offset + i * 1000)
            except Exception:
                continue
    return merged


# ---------------------------------------------------------------------------
# validation (the CI trace-smoke gate)

_KNOWN_PH = {"B", "E", "X", "i", "I", "C", "M", "s", "t", "f", "b", "n",
             "e", "P", "N", "O", "D"}


def validate_chrome_trace(doc: dict) -> list[str]:
    """Structural validation against the Chrome trace-event format.
    Returns a list of problems — empty means loadable."""
    problems = []
    if not isinstance(doc, dict):
        return ["document is not an object"]
    ev = doc.get("traceEvents")
    if not isinstance(ev, list):
        return ["traceEvents missing or not a list"]
    if not ev:
        problems.append("traceEvents is empty")
    for i, e in enumerate(ev):
        where = f"traceEvents[{i}]"
        if not isinstance(e, dict):
            problems.append(f"{where}: not an object")
            continue
        ph = e.get("ph")
        if ph not in _KNOWN_PH:
            problems.append(f"{where}: unknown ph {ph!r}")
            continue
        if ph != "M":
            ts = e.get("ts")
            if not isinstance(ts, (int, float)):
                problems.append(f"{where}: ph={ph} without numeric ts")
        if ph == "X":
            dur = e.get("dur")
            if not isinstance(dur, (int, float)) or dur < 0:
                problems.append(f"{where}: X event without dur >= 0")
            if not e.get("name"):
                problems.append(f"{where}: X event without name")
        if ph == "M" and e.get("name") in ("process_name", "thread_name"):
            if not isinstance(e.get("args", {}).get("name"), str):
                problems.append(f"{where}: metadata without args.name")
        for key in ("pid", "tid"):
            if key in e and not isinstance(e[key], int):
                problems.append(f"{where}: {key} not an int")
    return problems


# ---------------------------------------------------------------------------
# campaign critical path


def campaign_critical_path(records: list[dict],
                           campaign_id: str | None = None) -> dict:
    """Longest path through a campaign DAG with per-node slack and a
    warm-start savings estimate.

    Classic CPM over node *durations* (RUNNING->terminal wall): earliest
    start/finish forward, latest start/finish backward, slack = ls - es.
    ``critical_path_s`` is the duration sum along the longest chain —
    on a serial chain it reconciles with the measured campaign wall
    (acceptance: within 5%)."""
    submits = [r for r in records if r.get("kind") == "campaign_submit"]
    if campaign_id is not None:
        submits = [r for r in submits
                   if r.get("campaign_id") == campaign_id]
    if not submits:
        raise ValueError(
            f"no campaign_submit event"
            + (f" for campaign {campaign_id!r}" if campaign_id else "")
            + " in the record stream")
    sub = submits[-1]
    cid = sub["campaign_id"]
    edges: dict = sub.get("edges") or {}
    nodes = list(sub.get("nodes") or sorted(edges))
    iv = _node_intervals(records, cid)
    present = [n for n in nodes if n in iv]
    parents = {n: [p for p in (edges.get(n) or []) if p in iv]
               for n in present}
    order, seen = [], set()

    def _visit(n, stack=()):
        if n in seen:
            return
        if n in stack:
            raise ValueError(f"cycle through {n}")
        for p in parents.get(n, []):
            _visit(p, stack + (n,))
        seen.add(n)
        order.append(n)

    for n in present:
        _visit(n)
    # effective node duration: ready -> terminal, where ready = submitted
    # AND every parent terminal. This charges the node the scheduler's
    # pre-COMPILING setup (queue pop, deck parse, context build) without
    # charging it the parents' runtime — the anchor the wall
    # reconciliation needs.
    dur = {}
    for n in order:
        ready = max((iv[p]["end"] for p in parents[n]),
                    default=iv[n]["queued"])
        ready = max(ready, iv[n]["queued"])
        dur[n] = max(0.0, iv[n]["end"] - ready)
    es, ef = {}, {}
    for n in order:
        es[n] = max((ef[p] for p in parents[n]), default=0.0)
        ef[n] = es[n] + dur[n]
    cp_total = max(ef.values(), default=0.0)
    children: dict = {n: [] for n in dur}
    for n in dur:
        for p in parents[n]:
            children[p].append(n)
    lf, ls = {}, {}
    for n in reversed(order):
        lf[n] = min((ls[c] for c in children[n]), default=cp_total)
        ls[n] = lf[n] - dur[n]
    slack = {n: max(0.0, ls[n] - es[n]) for n in dur}

    # walk the zero-slack chain from the last-finishing critical node
    path = []
    cur = max((n for n in dur if abs(ef[n] - cp_total) < 1e-9),
              key=lambda n: ef[n], default=None)
    while cur is not None:
        path.append(cur)
        cur = max((p for p in parents[cur]
                   if abs(ef[p] - es[path[-1]]) < 1e-9),
                  key=lambda p: ef[p], default=None)
    path.reverse()

    # measured wall: the finalize summary when present, else the span of
    # the node intervals
    walls = [r.get("wall_s") for r in records
             if r.get("kind") == "campaign_done"
             and r.get("campaign_id") == cid]
    if walls and walls[-1]:
        measured = float(walls[-1])
    elif dur:
        measured = (max(iv[n]["end"] for n in dur)
                    - min(iv[n]["queued"] for n in dur))
    else:
        measured = 0.0

    # per-node SCF effort + warm-start savings estimate: cold nodes set
    # the baseline iteration count; a warm node's shortfall against it is
    # the handoff's saving
    modes = {}
    for r in records:
        if r.get("kind") == "campaign_handoff" and r.get(
                "campaign_id") == cid:
            modes[str(r.get("node_id"))] = r.get("mode")
    iters = {}
    for r in records:
        if r.get("kind") != "scf_done":
            continue
        jid = str(r.get("job_id") or "")
        if jid.startswith(f"{cid}."):
            iters[jid[len(cid) + 1:]] = int(r.get("iterations") or 0)
    cold = [v for n, v in iters.items() if modes.get(n) != "warm"]
    baseline = (sorted(cold)[len(cold) // 2] if cold else None)
    savings = {}
    for n, m in modes.items():
        if m == "warm" and baseline is not None and n in iters:
            savings[n] = max(0, baseline - iters[n])

    return {
        "campaign_id": cid,
        "nodes": {
            n: {
                "dur_s": round(dur[n], 3),
                "es": round(es[n], 3), "ef": round(ef[n], 3),
                "slack_s": round(slack[n], 3),
                "critical": n in path,
                "status": iv[n]["status"],
                "scf_iterations": iters.get(n),
                "handoff_mode": modes.get(n),
            } for n in dur
        },
        "critical_path": path,
        "critical_path_s": round(cp_total, 3),
        "measured_wall_s": round(measured, 3),
        "cp_over_wall": round(cp_total / measured, 3) if measured else None,
        "warm_savings_iterations": savings,
        "warm_baseline_iterations": baseline,
        "trace_id": sub.get("trace_id"),
    }


# ---------------------------------------------------------------------------
# CLI


def export_timeline(events_path: str, out_path: str | None = None,
                    trace_id: str | None = None,
                    campaign_id: str | None = None,
                    jax_trace_dir: str | None = None) -> dict:
    """events JSONL -> Chrome trace document (written to out_path when
    given). The export itself is a ``trace.export`` span."""
    with _spans.span("trace.export") as sp:
        records = _events.read_events(events_path)
        doc = build_chrome_trace(records, trace_id=trace_id,
                                 campaign_id=campaign_id)
        merged = 0
        if jax_trace_dir:
            merged = merge_jax_profiler_trace(doc, jax_trace_dir)
        if out_path:
            with open(out_path, "w", encoding="utf-8") as fh:
                json.dump(doc, fh)
        sp.set(events=len(records), trace_events=len(doc["traceEvents"]),
               device_events=merged)
    return doc


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        prog="sirius-trace",
        description="export/validate Perfetto timelines and analyze "
                    "campaign critical paths from the obs event log")
    sub = ap.add_subparsers(dest="cmd", required=True)

    p = sub.add_parser("export", help="events JSONL -> Chrome trace JSON")
    p.add_argument("--events", required=True, help="events JSONL path")
    p.add_argument("--out", default="timeline.json")
    p.add_argument("--trace-id", default=None,
                   help="keep only this trace's records")
    p.add_argument("--campaign", default=None,
                   help="campaign id for the synthetic node tracks")
    p.add_argument("--jax-trace-dir", default=None,
                   help="merge the jax.profiler capture under this "
                        "directory: its *.trace.json(.gz) if it has any, "
                        "else its *.xplane.pb")

    p = sub.add_parser("validate",
                       help="check a file against the trace-event format")
    p.add_argument("file")

    p = sub.add_parser("critical-path",
                       help="campaign CPM report from the event log")
    p.add_argument("--events", required=True)
    p.add_argument("--campaign", default=None)
    p.add_argument("--json", action="store_true",
                   help="machine-readable output")

    args = ap.parse_args(argv)
    if args.cmd == "export":
        doc = export_timeline(args.events, out_path=args.out,
                              trace_id=args.trace_id,
                              campaign_id=args.campaign,
                              jax_trace_dir=args.jax_trace_dir)
        problems = validate_chrome_trace(doc)
        print(f"wrote {args.out}: {len(doc['traceEvents'])} events"
              + (f", {len(problems)} problems" if problems else ""))
        for pr in problems:
            print(f"  problem: {pr}", file=sys.stderr)
        return 1 if problems else 0
    if args.cmd == "validate":
        with open(args.file, encoding="utf-8") as fh:
            doc = json.load(fh)
        problems = validate_chrome_trace(doc)
        for pr in problems:
            print(f"problem: {pr}", file=sys.stderr)
        print(f"{args.file}: "
              + ("OK" if not problems else f"{len(problems)} problems"))
        return 1 if problems else 0
    if args.cmd == "critical-path":
        records = _events.read_events(args.events)
        rep = campaign_critical_path(records, campaign_id=args.campaign)
        if args.json:
            print(json.dumps(rep, indent=1))
            return 0
        print(f"campaign {rep['campaign_id']}  trace {rep['trace_id']}")
        print(f"critical path ({rep['critical_path_s']} s, wall "
              f"{rep['measured_wall_s']} s, ratio {rep['cp_over_wall']}):")
        print("  " + " -> ".join(rep["critical_path"]))
        print(f"{'node':<16}{'dur_s':>8}{'slack_s':>9}{'crit':>6}"
              f"{'iters':>7}  handoff")
        for n, d in sorted(rep["nodes"].items()):
            print(f"{n:<16}{d['dur_s']:>8.2f}{d['slack_s']:>9.2f}"
                  f"{'*' if d['critical'] else '':>6}"
                  f"{d['scf_iterations'] or '-':>7}  "
                  f"{d['handoff_mode'] or '-'}")
        if rep["warm_savings_iterations"]:
            tot = sum(rep["warm_savings_iterations"].values())
            print(f"warm-start savings: ~{tot} SCF iterations vs cold "
                  f"baseline {rep['warm_baseline_iterations']}")
        return 0
    return 2


if __name__ == "__main__":
    raise SystemExit(main())
