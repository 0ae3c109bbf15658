"""Per job: context building plus the program's scf.setup span, median."""

import statistics


def read(record, args):
    per = []
    for j in record["jobs"]:
        if j.get("result") is None:
            continue
        s = sum(r["dur_s"] for r in j.get("spans", [])
                if r["name"] in args["spans"])
        s += j.get("ctx_s") or 0.0  # the direct runner's own clock
        if s > 0:
            per.append(args["scale"] * s)
    return statistics.median(per) if per else None
