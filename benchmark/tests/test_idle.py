"""harness/idle.py on a record small enough to add up in the head: two
devices, nested spans, a gap that straddles span boundaries, a gap under no
span, operations that nest and one that begins before the capture."""

import json

import pytest

from benchmark.harness import idle, sources, trace_reduce
from conftest import ROOT

Z = 1_000_000_000_000  # the session's start, Unix ns


def span(name, sid, parent, start, end, thread="w0", **more):
    return dict(name=name, span_id=sid, parent_id=parent, pid=1,
                thread=thread, start_unix_ns=Z + start, end_unix_ns=Z + end,
                t0=(Z + start) * 1e-9, dur_s=(end - start) * 1e-9, **more)


def record():
    spans = [
        span("scf.run", 1, None, 150, 2000),
        span("scf.iteration", 2, 1, 200, 1000, it=2),
        span("scf.band_solve", 3, 2, 200, 600),
        span("scf.density", 4, 2, 600, 900),
        span("other.thread", 9, None, 0, 2000, thread="w1"),
        span("trace.capture", 5, 1, 100, 1100, session_start_unix_ns=Z,
             steps=1, first_iteration=2),
        span("trace.stop", 6, 1, 1100, 1500),
    ]
    ops = {  # device -> [start, end) since the session's start
        0: [(0, 120), (300, 550), (310, 320), (650, 950), (1050, 1100)],
        1: [(100, 400), (500, 1100)],
    }
    dev, start, dur = [], [], []
    for d, ivs in ops.items():
        for s, e in ivs:
            dev.append(d), start.append(float(s)), dur.append(float(e - s))
    raw = {"devices": ["/device:TPU:0", "/device:TPU:1"], "names": ["op"],
           "dev": dev, "name": [0] * len(dev), "start_ns": start,
           "dur_ns": dur, "window_ns": [0.0, 1200.0]}
    return {"trace_raw": raw, "trace_job": {"spans": spans}, "jobs": []}, ops


def test_idle_intervals_are_the_complement_of_the_union():
    g_s, g_e = idle.idle_intervals([300, 310, 0, 650, 1050],
                                   [550, 320, 120, 950, 1100], 100, 1100)
    assert list(zip(g_s, g_e)) == [(120, 300), (550, 650), (950, 1050)]
    g_s, g_e = idle.idle_intervals([], [], 100, 1100)
    assert list(zip(g_s, g_e)) == [(100, 1100)]
    g_s, g_e = idle.idle_intervals([0], [2000], 100, 1100)
    assert g_s.size == 0


def test_every_idle_nanosecond_has_one_owner_and_the_innermost_span_wins():
    rec, ops = record()
    out = idle.charge(rec)
    ns = {name: v["idle_s"] * 1e9 for name, v in out["by_name"].items()}
    # device 0: [120,300) = 30 under no span, 50 scf.run, 100 band solve;
    # [550,650) = 50 band solve, 50 density; [950,1050) = 50 of the
    # iteration itself, 50 scf.run. device 1: [400,500) in the band solve.
    assert ns == pytest.approx({
        idle.NO_SPAN: 30 / 2, "scf.run": 100 / 2,
        "scf.band_solve": (150 + 100) / 2, "scf.density": 50 / 2,
        "scf.iteration": 50 / 2})
    assert "other.thread" not in ns and "trace.capture" not in ns
    # the totals are the idle time: the capture less each device's union
    busy = [trace_reduce.union_ns((max(s, 100), min(e, 1100))
                                  for s, e in iv) for iv in ops.values()]
    want = sum(1000 - b for b in busy) / 2
    assert out["idle_in_capture_s"] * 1e9 == pytest.approx(want) == 240
    assert sum(s for _, s in out["table"]) * 1e9 == pytest.approx(want)
    assert sum(s for _, s in out["segments"]) * 1e9 == pytest.approx(want)
    assert out["owned_share"] == pytest.approx(1 - 15 / 240)
    assert out["capture_s"] * 1e9 == pytest.approx(1000)
    assert out["iterations"] == 1
    # outside the capture: the profiler's edges, [0,100) and [1100,1200)
    assert out["idle_outside_capture_s"] * 1e9 == pytest.approx((100 + 200) / 2)


def test_gap_counts_longest_gap_and_the_table_for_the_notes_event():
    rec, _ = record()
    out = idle.charge(rec)
    by = out["by_name"]
    assert by["scf.band_solve"]["gaps"] == pytest.approx((2 + 1) / 2)
    assert by["scf.run"]["gaps"] == pytest.approx(2 / 2)
    assert by[idle.NO_SPAN]["gaps"] == pytest.approx(1 / 2)
    assert by["scf.band_solve"]["longest_s"] * 1e9 == pytest.approx(100)
    assert by["scf.run"]["longest_s"] * 1e9 == pytest.approx(50)
    names = [n for n, _ in out["table"]]
    assert names[0] == "scf.band_solve" and names[-1] == idle.NO_SPAN
    assert [s for _, s in out["table"]] == sorted(
        (s for _, s in out["table"]), reverse=True)
    assert rec["notes"]["idle_gaps"] == out["table"]
    assert rec["notes"]["idle_capture"]["iterations"] == 1
    assert idle.charge(rec) is out  # computed once for both metrics


def test_the_two_idle_metrics_read_the_charge_per_traced_iteration():
    rec, _ = record()
    mdir = ROOT + "/benchmark/layer_metrics"
    got = {}
    for name in ("idle_band_solve_ms", "idle_tail_ms"):
        with open(f"{mdir}/{name}.json") as f:
            got[name] = sources.read_metric(json.load(f), mdir, name, rec)
    assert got["idle_band_solve_ms"] == pytest.approx(125e-9 * 1e3)
    assert got["idle_tail_ms"] == pytest.approx(25e-9 * 1e3)


def test_a_program_without_the_capture_span_reads_nothing():
    rec, _ = record()
    rec["trace_job"]["spans"] = [
        {k: v for k, v in r.items() if not k.endswith("_unix_ns")}
        for r in rec["trace_job"]["spans"] if r["name"] != "trace.capture"]
    assert idle.charge(rec) is None and "notes" not in rec
    assert idle.idle_ms_per_iteration(rec, {"spans": ["scf.band_solve"]}) is None
    assert idle.charge({"trace_raw": None, "trace_job": None}) is None


def test_self_time_is_the_span_less_the_union_of_its_children_cut_to_it():
    parent = span("serve.job", 1, None, 0, 100)
    kids = [span("serve.queue_wait", 2, 1, -50, 10),  # began before it
            span("serve.context_build", 3, 1, 20, 40),
            span("serve.run", 4, 1, 30, 60)]          # overlaps the last
    assert idle.self_seconds(parent, kids) * 1e9 == pytest.approx(100 - 50)
    assert idle.self_seconds(parent, []) * 1e9 == pytest.approx(100)
    old = {k: v for k, v in parent.items() if not k.endswith("_unix_ns")}
    assert idle.self_seconds(old, []) is None
    assert set(idle.children([parent, *kids])) == {None, 1}


def test_self_time_metrics_on_hand_made_jobs():
    mdir = ROOT + "/benchmark/layer_metrics"

    def job(self_ns):
        its = []
        for i, extra in enumerate(self_ns):
            base = 1000 * i
            its += [span("scf.iteration", 10 + i, 1, base, base + 500 + extra),
                    span("scf.band_solve", 20 + i, 10 + i, base, base + 300),
                    span("scf.density", 30 + i, 10 + i, base + 300, base + 500)]
        sjob = [span("serve.job", 2, None, -100, 5000),
                span("serve.run", 1, 2, -40, 4990),
                span("serve.context_build", 3, 2, -90, -40)]
        return {"result": {}, "spans": sjob + its}

    rec = {"jobs": [job([10, 30, 20]), job([40, 40, 40]), {"result": None}]}
    got = {}
    for name in ("iter_self_ms", "serve_self_ms"):
        with open(f"{mdir}/{name}.json") as f:
            got[name] = sources.read_metric(json.load(f), mdir, name, rec)
    # per job the median iteration (20, 40), then the median over jobs
    assert got["iter_self_ms"] == pytest.approx(30e-9 * 1e3)
    assert got["serve_self_ms"] == pytest.approx(20e-9 * 1e3)
    # a program whose spans form no tree reports neither
    flat = {"jobs": [{"result": {}, "spans": [
        dict(name="scf.iteration", span_id=1, parent_id=None, dur_s=1.0)]}]}
    with open(f"{mdir}/iter_self_ms.json") as f:
        assert sources.read_metric(json.load(f), mdir, "iter_self_ms",
                                   flat) is None
