"""Share of the device's busy time spent in the operations whose names match
a pattern: for metrics of a kind of operation that the TPU trace names but
gives no scope (collectives, the subspace eigensolver).

Operations nest (a while loop and the operations of its body are both
events), so both sides are unions of intervals, per device: the matching
operations' over all operations'. The share is the mean over the devices.
"""

from __future__ import annotations

import re

from benchmark.harness import trace_reduce


def share_of_busy(raw: dict | None, pattern: str, top: int = 12):
    """(share in per cent, {operation name: summed seconds per device}) for
    the operations of ``raw`` (trace_reduce.read_xplane's columns) whose name
    matches ``pattern``, case ignored; (None, {}) where no operation ran."""
    if not raw or not raw.get("dev"):
        return None, {}
    rx = re.compile(pattern, re.IGNORECASE)
    hit = [bool(rx.search(n)) for n in raw["names"]]
    ndev = len(raw["devices"])
    every = [[] for _ in range(ndev)]
    mine = [[] for _ in range(ndev)]
    by_name = {}
    for i, j, s, d in zip(raw["dev"], raw["name"], raw["start_ns"],
                          raw["dur_ns"]):
        every[i].append((s, s + d))
        if hit[j]:
            mine[i].append((s, s + d))
            by_name[j] = by_name.get(j, 0.0) + d
    shares = []
    for m, e in zip(mine, every):
        busy = trace_reduce.union_ns(e)
        if busy > 0:
            shares.append(100.0 * trace_reduce.union_ns(m) / busy)
    if not shares:
        return None, {}
    ops = sorted(by_name.items(), key=lambda kv: -kv[1])[:top]
    return (sum(shares) / len(shares),
            {raw["names"][j]: t * 1e-9 / ndev for j, t in ops})


def read(record: dict, args: dict):
    """The layer-metric reader: ``args["pattern"]``; the matched operations
    go into the run's notes under ``args["note"]``."""
    share, ops = share_of_busy(record.get("trace_raw"), args["pattern"])
    if share is not None:
        record.setdefault("notes", {})[args["note"]] = {
            "share_pct": share, "ops_s_per_device": ops}
    return share
