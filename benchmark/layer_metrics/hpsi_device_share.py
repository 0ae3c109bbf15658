"""Device time under one of the program's own scope names, from the traced
job's ``trace.scopes`` record (sirius_tpu/obs/device_scopes.py: every device
operation of the capture put to the ``jax.named_scope`` path that emitted it,
seconds a union of intervals per device). ``args``: ``scope`` (a key of the
record's ``by_scope``, e.g. ``davidson_hpsi/local_op``) or ``field`` (a
top-level number of the record, e.g. ``unscoped_s``), ``over`` (the record's
``busy_s`` or ``steps``) and ``scale``. Nothing where the program records no
such span (a program before PR 36), the scope did not run or the divisor is
absent. The four sibling metrics read through this file."""


def scopes_record(record):
    job = record.get("trace_job") or {}
    for r in job.get("spans") or []:
        if r.get("name") == "trace.scopes":
            return r
    return None


KEEP = ("busy_s", "steps", "devices", "by_scope", "by_module", "unscoped_s",
        "unscoped_top", "scopes_seen", "modules_without_hlo", "source",
        "num_ops", "reduce_s")


def read(record, args):
    rec = scopes_record(record)
    if rec is None:
        return None
    # the whole table rides in the run's ``notes`` event, beside
    # ``idle_capture``, which holds what stopping the capture cost
    record.setdefault("notes", {}).setdefault(
        "scopes", {k: rec.get(k) for k in KEEP})
    if "scope" in args:
        num = ((rec.get("by_scope") or {}).get(args["scope"]) or {}).get("s")
    else:
        num = rec.get(args["field"])
    over = rec.get(args["over"])
    if num is None or not over:
        return None
    return float(args.get("scale", 1.0)) * num / over
