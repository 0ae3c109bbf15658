"""The window rule, the same in every cell: jobs start until ``seconds``
have passed; the jobs in flight at that moment are finished and counted.

One closed loop serves every mix: ``clients`` callers, each handing in its
next job when its last has come back. Job ``i`` (a counter shared by the
clients) is made by ``run_one(i)``, which blocks until the job's result is on
the host and returns its record. One client runs on the calling thread.
"""

from __future__ import annotations

import statistics
import threading
import time


def closed_loop(run_one, clients: int, seconds: float,
                clock=time.perf_counter) -> tuple[list, float]:
    """Returns (records in order of job index, t0). Each record gains
    ``index``, ``t_start`` and ``t_end`` (seconds since t0) and ``seconds``."""
    lock = threading.Lock()
    state = {"next": 0}
    records: list[dict] = []
    t0 = clock()

    def client():
        while True:
            with lock:
                if clock() - t0 >= seconds:
                    return
                i = state["next"]
                state["next"] += 1
            t_start = clock()
            try:
                rec = run_one(i)
            except Exception as e:  # a job that raised is a failed job
                rec = {"error": f"{type(e).__name__}: {e}"}
            t_end = clock()
            rec.update(index=i, t_start=t_start - t0, t_end=t_end - t0,
                       seconds=t_end - t_start)
            with lock:
                records.append(rec)

    if clients <= 1:
        client()
    else:
        threads = [threading.Thread(target=client, name=f"client{c}")
                   for c in range(clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    records.sort(key=lambda r: r["index"])
    return records, t0


def scf_s(records: list) -> float:
    """Median over the counted jobs of hand-off to converged result."""
    return statistics.median(r["seconds"] for r in records)


def jobs_per_min(records: list) -> float:
    """Jobs completed over the minutes from the window's start to the
    completion of the last counted job."""
    return 60.0 * len(records) / max(r["t_end"] for r in records)
