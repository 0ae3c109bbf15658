"""Band rows H was applied to per second of the fenced band solve: per job
the result's counter over the seconds of its scf.band_solve spans, median
over the counted jobs. Nothing where a job has no such counter or span."""

import statistics

from benchmark.harness import sources


def read(record, args):
    per = []
    for j in record["jobs"]:
        if j.get("result") is None:
            continue
        rows = sources._dig(j["result"], args["counter"])
        s = sources.span_seconds(j, args["spans"])
        if rows and s > 0:
            per.append(rows / s)
    return statistics.median(per) if per else None
