"""Per job, the slice's time that is neither run_scf nor context building.

Jobs of one slice run one after another. For each counted job but the first:
(finished_at - started_at) - (its scf.setup + scf.iteration spans)
+ (started_at - the previous job's finished_at) - (its context build, which
the scheduler does inside that gap). Median over jobs."""

import statistics


def read(record, args):
    jobs = sorted((j for j in record["jobs"]
                   if j.get("result") is not None and j.get("started_at")),
                  key=lambda j: j["started_at"])
    per = []
    for prev, j in zip(jobs, jobs[1:]):
        spans = j.get("spans", [])
        inside = sum(r["dur_s"] for r in spans if r["name"] in args["inside"])
        build = sum(r["dur_s"] for r in spans if r["name"] == args["build"])
        gap = j["started_at"] - prev["finished_at"]
        over = (j["finished_at"] - j["started_at"]) - inside + gap - build
        per.append(args["scale"] * over)
    return statistics.median(per) if per else None
