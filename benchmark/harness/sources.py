"""Readers of the layer metrics, one per source kind. A layer-metric file
(layer_metrics/<name>.json) names a kind and its arguments; kind ``python``
names a <name>.py beside it with one function ``read(record, args)``.

A reader takes the run's record and returns a number, or None where it finds
nothing to read: the harness then leaves the metric out of the line.

The record: {"jobs": [counted job records: result, spans, seconds, ...],
"trace": reduced trace (trace_reduce.reduce) or None, "trace_raw": the
trace's operations as columns, "trace_job": the traced job's record,
"memory_peak_bytes", "window_compiles", "config", "deck0", "device_kind",
"chips"}.
"""

from __future__ import annotations

import importlib.util
import os
import statistics


def _dig(obj, dotted: str):
    for key in dotted.split("."):
        if not isinstance(obj, dict) or key not in obj:
            return None
        obj = obj[key]
    return obj


def span_seconds(job: dict, names) -> float:
    names = (names,) if isinstance(names, str) else tuple(names)
    return sum(r["dur_s"] for r in job.get("spans", []) if r["name"] in names)


def _median(values):
    values = [v for v in values if v is not None]
    return statistics.median(values) if values else None


def _good(record):
    """The counted jobs that came back with a result."""
    return [j for j in record["jobs"] if j.get("result") is not None]


def result_field(record, args):
    """Median per job of one field of the result."""
    return _median(_dig(j["result"], args["field"]) for j in _good(record))


def span_median(record, args):
    """Median duration of every span of one name, over all counted jobs."""
    scale = float(args.get("scale", 1.0))
    durs = [r["dur_s"] for j in _good(record) for r in j.get("spans", [])
            if r["name"] == args["span"]]
    return scale * statistics.median(durs) if durs else None


def span_share(record, args):
    """Median per job of the named spans' seconds over the job's seconds,
    in per cent."""
    shares = []
    for j in _good(record):
        s = span_seconds(j, args["spans"])
        if s > 0:
            shares.append(100.0 * s / j["seconds"])
    return _median(shares)


def span_sum_per_iteration(record, args):
    """Median per job of the named spans' summed seconds over the job's SCF
    iterations."""
    scale = float(args.get("scale", 1.0))
    per = []
    for j in _good(record):
        iters = j["result"].get("num_scf_iterations") or 0
        s = span_seconds(j, args["spans"])
        if iters and s > 0:
            per.append(scale * s / iters)
    return _median(per)


def trace_idle(record, args):
    tr = record.get("trace")
    if not tr or tr.get("idle_share") is None or not tr["num_events"]:
        return None
    return 100.0 * tr["idle_share"]


def trace_scope_roofline(record, args):
    """Least time for the counted applications of a kernel over the device
    time of the operations under its scope, in per cent. The applications
    inside the traced iterations are the job's counter times the traced share
    of its iterations (every iteration applies H the same number of times)."""
    from benchmark.harness import costs, shapes, trace_reduce

    raw, job = record.get("trace_raw"), record.get("trace_job")
    if not raw or not job or job.get("result") is None:
        return None
    t_scope = trace_reduce.scope_seconds(raw, args["scope"])
    if t_scope <= 0:
        return None
    res = job["result"]
    rows = _dig(res, args["counter"])
    iters = res.get("num_scf_iterations")
    traced = min(int(record.get("trace_steps") or 0), int(iters or 0))
    if not rows or not iters or not traced:
        return None
    sh = shapes.of_deck(record["deck0"])
    rows_traced = rows * traced / iters / max(int(record.get("chips", 1)), 1)
    flops = costs.hpsi_flops(1, sh["ngk"], sh["nbeta"], sh["box"]) * rows_traced
    bytes_ = costs.hpsi_bytes(1, sh["ngk"], sh["nbeta"], sh["box"]) * rows_traced
    least = costs.roofline_seconds(
        flops, bytes_, costs.load_peaks(record["device_kind"]),
        args.get("precision", "highest"))
    record.setdefault("notes", {})[args["scope"]] = dict(
        least, scope_s=t_scope, rows_traced=rows_traced, **sh)
    return 100.0 * least["seconds"] / t_scope


def memory_peak(record, args):
    b = record.get("memory_peak_bytes")
    return b / float(args.get("divide", 1e9)) if b else None


def compile_count(record, args):
    return record.get("window_compiles")


def python(record, args, path=None):
    spec = importlib.util.spec_from_file_location(
        "layer_metric_" + os.path.basename(path)[:-3].replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(record, args)


KINDS = {f.__name__: f for f in (
    result_field, span_median, span_share, span_sum_per_iteration, trace_idle,
    trace_scope_roofline, memory_peak, compile_count, python)}


def read_metric(spec: dict, spec_dir: str, name: str, record: dict):
    kind = spec["kind"]
    if kind not in KINDS:
        raise KeyError(f"layer metric {name}: unknown source kind {kind!r}")
    args = spec.get("args", {})
    if kind == "python":
        return python(record, args, path=os.path.join(spec_dir, name + ".py"))
    return KINDS[kind](record, args)
