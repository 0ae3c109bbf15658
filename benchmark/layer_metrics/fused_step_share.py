"""Share of the capture's device busy time in the fused step's program: the
seconds of the jitted module whose name holds ``args["module"]`` (the trace's
"XLA Modules" line, trace_reduce.reduce's ``modules``, per device) over
``busy_s``, in per cent. Nothing where there is no trace, no busy time or no
such module."""


def read(record, args):
    tr = record.get("trace")
    if not tr or not tr.get("busy_s"):
        return None
    t = sum(s for name, s in tr.get("modules", []) if args["module"] in name)
    return 100.0 * t / tr["busy_s"] if t > 0 else None
