"""Device milliseconds a traced iteration spends in one half of the band
solve's subspace eigensolver: every path of the traced job's ``trace.scopes``
record that ends in ``args["tail"]`` (``davidson_rr/eigh_reduce``: the
Householder reduction of a complex subspace matrix to a real tridiagonal one;
``davidson_rr/eigh_kernel``: the chip's real kernel and the back-transformation;
solvers/subspace_eigh.py), summed, over the record's ``steps``. The start's
``davidson_ortho/eigh_*`` is another path and not in it. Nothing where the
program records no such table, no such scope ran (a real subspace has no
reduction), or the capture has no device plane (the harness's own reduction
finds no "XLA Modules" line: the CPU backend of a rehearsal, whose host events
are no device time). ``eigh_kernel_ms`` reads through this file."""


def read(record, args):
    if not (record.get("trace") or {}).get("modules"):
        return None
    job = record.get("trace_job") or {}
    rec = next((r for r in job.get("spans") or []
                if r.get("name") == "trace.scopes"), None)
    if rec is None:
        return None
    tail = "/" + args["tail"]
    seconds = [v.get("s") for path, v in (rec.get("by_scope") or {}).items()
               if ("/" + path).endswith(tail)]
    over = rec.get(args["over"])
    if not seconds or not over:
        return None
    return float(args.get("scale", 1.0)) * sum(seconds) / over
