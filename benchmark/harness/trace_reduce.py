"""From a profiler trace to the device's busy and idle time.

Two steps, so that the arithmetic can be checked on a small recorded trace:
``read_xplane`` turns the profiler's .xplane.pb into a plain list of device
operations, as columns (device, name, start, duration); ``reduce`` works only
on those. Busy time of a device is the union of the intervals in which an
operation ran on it; the idle share is 1 - busy / window, averaged over the
devices used.
"""

from __future__ import annotations

import glob
import os
import re

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def find_xplane(trace_dir: str) -> str | None:
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return files[-1] if files else None


_HINT = re.compile(r'(?:body|calls|to_apply)=%([\w.\-]+)|custom_call_target="([^"]+)"')


def short_name(name: str) -> str:
    """The TPU trace names an operation by its whole HLO instruction text
    (and carries no jax.named_scope path): keep the instruction's own name
    and, where it calls a computation, that computation's
    (``while.135 wide.EighJacobiSweeps_body.0``)."""
    head = name.split(" = ", 1)[0].lstrip("%")
    m = _HINT.search(name) if " = " in name else None
    hint = (m.group(1) or m.group(2)) if m else ""
    return (head + " " + hint).strip()[:120]


def read_xplane(path: str) -> dict:
    """The device operations of the profiler's file as columns:
    {"devices": [...], "names": [...], "dev": [i], "name": [j],
    "start_ns": [...], "dur_ns": [...], "window_ns": [start, end],
    "modules": {program: seconds}, "lines": {...}}.

    Device operations are the events of the "XLA Ops" line of each
    "/device:" plane; "XLA Modules" gives the time of each jitted program.
    Where there is no device plane (the CPU backend of a rehearsal) the host
    events that carry an ``hlo_op`` stat stand in, by ``device_ordinal``. The
    window is the span of all events of all planes: trace start to stop."""
    import jax

    pd = jax.profiler.ProfileData.from_file(path)
    devices, names, short, index_of = [], [], {}, {}
    dev, name, start, dur = [], [], [], []
    modules, lines_seen = {}, {}
    lo, hi = None, None
    have_device = any(p.name.startswith("/device:") for p in pd.planes)

    def add(device, full, s, d):
        i = index_of.get(device)
        if i is None:
            i = index_of[device] = len(devices)
            devices.append(device)
        j = short.get(full)
        if j is None:
            j = short[full] = len(names)
            names.append(short_name(full))
        dev.append(i)
        name.append(j)
        start.append(s)
        dur.append(d)

    for plane in pd.planes:
        is_dev = plane.name.startswith("/device:")
        for line in plane.lines:
            n = 0
            ops = is_dev and line.name == OPS_LINE
            mods = is_dev and line.name == MODULES_LINE
            host_ops = not have_device and not is_dev
            for ev in line.events:
                n += 1
                s, d = float(ev.start_ns), float(ev.duration_ns)
                lo = s if lo is None or s < lo else lo
                hi = s + d if hi is None or s + d > hi else hi
                if ops:
                    add(plane.name, ev.name, s, d)
                elif mods:
                    key = ev.name.split("(")[0]
                    modules[key] = modules.get(key, 0.0) + d * 1e-9
                elif host_ops:
                    stats = dict(ev.stats)
                    if "hlo_op" in stats:
                        add(f"host:{stats.get('device_ordinal', 0)}",
                            f"{stats.get('hlo_module', '')} {ev.name}", s, d)
            lines_seen[f"{plane.name}|{line.name}"] = n
    return {"devices": devices, "names": names, "dev": dev, "name": name,
            "start_ns": start, "dur_ns": dur,
            "window_ns": [lo or 0.0, hi or 0.0], "modules": modules,
            "lines": lines_seen}


def union_ns(intervals) -> float:
    """Length of the union of [start, end) intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def reduce(trace: dict, top: int = 10) -> dict:
    """busy_s (mean over devices), window_s, idle_share, per-device busy and
    the ``top`` operations by summed time. Operations nest (a while loop and
    the operations of its body are both events), so busy time is a union and
    the per-name sums overlap."""
    ndev = len(trace["devices"])
    window_ns = trace["window_ns"][1] - trace["window_ns"][0]
    by_dev = [[] for _ in range(ndev)]
    by_name = [0.0] * len(trace["names"])
    for i, j, s, d in zip(trace["dev"], trace["name"], trace["start_ns"],
                          trace["dur_ns"]):
        by_dev[i].append((s, s + d))
        by_name[j] += d
    busy = {trace["devices"][i]: union_ns(iv) * 1e-9
            for i, iv in enumerate(by_dev)}
    busy_s = sum(busy.values()) / ndev if ndev else 0.0
    window_s = window_ns * 1e-9
    ops = sorted(zip(trace["names"], by_name), key=lambda kv: -kv[1])[:top]
    modules = sorted(trace.get("modules", {}).items(), key=lambda kv: -kv[1])
    return {
        "busy_s": busy_s, "window_s": window_s,
        "idle_share": (1.0 - busy_s / window_s) if window_s > 0 else None,
        "busy_by_device_s": busy, "num_events": len(trace["dev"]),
        # per device, so that four chips do not count four times
        "device_ops": [[n, t * 1e-9 / max(ndev, 1)] for n, t in ops],
        "modules": [[n, t / max(ndev, 1)] for n, t in modules[:top]],
    }


def scope_seconds(trace: dict, scope: str) -> float:
    """Device time (mean over devices) of the operations whose name contains
    ``scope``."""
    hit = [scope in n for n in trace["names"]]
    t = sum(d for j, d in zip(trace["name"], trace["dur_ns"]) if hit[j])
    return t * 1e-9 / max(len(trace["devices"]), 1)
