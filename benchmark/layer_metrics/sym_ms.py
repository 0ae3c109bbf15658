"""Device milliseconds a traced iteration spends in the fused step's
symmetrisers: every path of the traced job's ``trace.scopes`` record whose
last name is one of ``args["leaves"]`` (``sym_pw`` under step_density,
step_vloc and step_ledger, ``sym_dm`` under step_density), summed, over the
record's ``steps``. Nothing where the program records no such table, no such
scope ran, or the capture has no device plane (the harness's own reduction
finds no "XLA Modules" line: the CPU backend of a rehearsal, whose host
events stand in for operations and are no device time)."""


def read(record, args):
    if not (record.get("trace") or {}).get("modules"):
        return None
    job = record.get("trace_job") or {}
    rec = next((r for r in job.get("spans") or []
                if r.get("name") == "trace.scopes"), None)
    if rec is None:
        return None
    seconds = [v.get("s") for path, v in (rec.get("by_scope") or {}).items()
               if path.rsplit("/", 1)[-1] in args["leaves"]]
    over = rec.get(args["over"])
    if not seconds or not over:
        return None
    return float(args.get("scale", 1.0)) * sum(seconds) / over
