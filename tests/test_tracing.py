"""Distributed tracing (sirius_tpu/obs/tracing.py + timeline.py, ISSUE
11): trace-context propagation (mint/inherit, span + event + metric
exemplar stamping), the metric label-cardinality guard, trace continuity
across serve journal replay and campaign handoff, the Chrome-trace
export (``sirius-trace``), and the campaign critical-path analyzer's
reconciliation against the measured wall."""

import json
import os

import jax
import pytest

from sirius_tpu import obs
from sirius_tpu.obs import events as obs_events
from sirius_tpu.obs import metrics as obs_metrics
from sirius_tpu.obs import spans, timeline, tracing

requires_mesh = pytest.mark.skipif(
    len(jax.devices()) < 2, reason="needs >=2 CPU devices for a serve run")


@pytest.fixture(autouse=True)
def _clean_obs():
    obs.enable()
    yield
    obs.close_events()
    obs.enable()


# ---------------------------------------------------------------- context


def test_trace_context_mint_inherit_and_reset():
    assert tracing.current_trace_id() is None
    with tracing.trace_context() as tid:
        assert tid == tracing.current_trace_id()
        assert len(tid) == 16 and int(tid, 16) >= 0
        # inherit: ensure_trace keeps the ambient trace
        with tracing.ensure_trace() as tid2:
            assert tid2 == tid
        # explicit child context forks
        with tracing.trace_context("feedc0ffee123456"):
            assert tracing.current_trace_id() == "feedc0ffee123456"
        assert tracing.current_trace_id() == tid
    assert tracing.current_trace_id() is None
    # ensure_trace mints when there is nothing to inherit
    with tracing.ensure_trace() as tid3:
        assert tid3 is not None and tid3 != "feedc0ffee123456"
    assert tracing.current_trace_id() is None


def test_new_trace_ids_are_distinct():
    ids = {tracing.new_trace_id() for _ in range(64)}
    assert len(ids) == 64


def test_spans_carry_trace_pid_thread():
    with spans.capture() as cap:
        with tracing.trace_context() as tid:
            with spans.span("scf.iteration"):
                spans.record("scf.density", 0.1)
        with spans.span("scf.potential"):  # outside any trace
            pass
    recs = {r["name"]: r for r in cap.records}
    for name in ("scf.iteration", "scf.density"):
        assert recs[name]["trace_id"] == tid
        assert recs[name]["pid"] == os.getpid()
        assert isinstance(recs[name]["thread"], str)
    assert "trace_id" not in recs["scf.potential"]


def test_events_inherit_trace_unless_explicit(tmp_path):
    path = str(tmp_path / "events.jsonl")
    obs.configure_events(path)
    with tracing.trace_context() as tid:
        obs_events.emit("scf_done", converged=True)
        obs_events.emit("scf_done", converged=True, trace_id="override00000000")
    obs_events.emit("scf_done", converged=True)  # no ambient trace
    obs.close_events()
    evs = obs.read_events(path)
    assert evs[0]["trace_id"] == tid
    assert evs[1]["trace_id"] == "override00000000"
    assert "trace_id" not in evs[2]


def test_metric_exemplars_link_to_trace():
    # families of this test's own; REGISTRY.reset() here would also drop
    # the ones other modules registered at import and still write to
    # (test_obs.py then misses serve_queue_depth on the same worker)
    c = obs_metrics.REGISTRY.counter("tr_demo_total", "exemplar demo")
    h = obs_metrics.REGISTRY.histogram("tr_demo_seconds", "exemplar demo")
    c.inc(outcome="cold")  # before any trace: no exemplar
    with tracing.trace_context() as tid:
        c.inc(outcome="warm")
        h.observe(0.25, outcome="warm")
    snap = obs_metrics.REGISTRY.snapshot()
    by_outcome = {s["labels"]["outcome"]: s
                  for s in snap["tr_demo_total"]["samples"]}
    assert "exemplar" not in by_outcome["cold"]
    assert by_outcome["warm"]["exemplar"]["trace_id"] == tid
    hsamp = snap["tr_demo_seconds"]["samples"][0]
    assert hsamp["exemplar"]["trace_id"] == tid
    assert hsamp["exemplar"]["value"] == 0.25


# ------------------------------------------------- cardinality guard


def test_cardinality_guard_clips_to_overflow_child():
    prev = obs_metrics.set_max_labelsets(4)
    try:
        c = obs_metrics.REGISTRY.counter("tr_cardinality_total", "guard")
        for i in range(50):  # a per-job-id label: the exact bug the
            c.inc(job_id=f"job-{i}")  # guard exists to contain
        sets = c.labelsets()
        assert len(sets) <= 5  # 4 real children + the overflow child
        assert (("overflow", "true"),) in sets
        clipped = sum(c.value(**dict(k)) for k in sets
                      if k == (("overflow", "true"),))
        kept = sum(c.value(**dict(k)) for k in sets
                   if k != (("overflow", "true"),))
        assert kept + clipped == 50  # no increment is lost, only labels
        assert obs_metrics.cardinality_clips()["tr_cardinality_total"] >= 46
    finally:
        obs_metrics.set_max_labelsets(prev)


def test_audited_registries_stay_bounded_by_default():
    """Regression for the cardinality audit: the default cap is generous
    enough for every legitimate labelset in the tree (span names, status
    enums, slice indices) but small enough to contain an accidental
    per-job label."""
    assert 64 <= obs_metrics.max_labelsets() <= 1024


# ------------------------------------------------- md / scf front doors


def test_run_md_front_door_is_one_trace(monkeypatch):
    from sirius_tpu.md import driver as md_driver

    seen = []

    def fake_impl(*a, **kw):
        seen.append(tracing.current_trace_id())
        spans.record("md.scf", 0.01, step=0)
        spans.record("md.scf", 0.01, step=1)
        return {"ok": True}

    monkeypatch.setattr(md_driver, "_run_md_impl", fake_impl)
    with spans.capture() as cap:
        assert md_driver.run_md() == {"ok": True}
    assert seen[0] is not None
    tids = {r["trace_id"] for r in cap.by_name("md.scf")}
    assert tids == {seen[0]}  # every step span shares the trajectory trace
    # and an ambient trace is continued, not forked
    with tracing.trace_context("aaaabbbbccccdddd"):
        md_driver.run_md()
    assert seen[1] == "aaaabbbbccccdddd"


def test_run_scf_front_door_mints_or_inherits(monkeypatch):
    from sirius_tpu.dft import scf as scf_mod

    seen = []
    monkeypatch.setattr(
        scf_mod, "_run_scf_inner",
        lambda *a, **kw: seen.append(tracing.current_trace_id()) or {})
    assert scf_mod.run_scf({}) == {}
    assert seen[0] is not None  # standalone SCF mints its own trace
    with tracing.trace_context("1234567890abcdef"):
        scf_mod.run_scf({})
    assert seen[1] == "1234567890abcdef"  # serve/campaign trace is kept


# ------------------------------------------------- serve journal replay


def test_trace_survives_engine_restart_via_journal(tmp_path):
    """The trace id is assigned before the write-ahead journal record, so
    a SIGKILL + replay continues the SAME trace in the next process."""
    from sirius_tpu.serve.engine import ServeEngine

    jp = str(tmp_path / "jobs.journal")
    eng = ServeEngine(num_slices=1, workdir=str(tmp_path), journal_path=jp)
    job = eng.submit({"parameters": {}}, job_id="tr-1")
    tid = job.trace_id
    assert tid is not None and len(tid) == 16
    # workers never started -> drain leaves the job pending on disk
    eng.shutdown(wait=True, mode="drain")

    eng2 = ServeEngine(num_slices=1, workdir=str(tmp_path), journal_path=jp)
    assert [j.id for j in eng2.replayed] == ["tr-1"]
    assert eng2.replayed[0].trace_id == tid
    eng2.shutdown(wait=True, mode="abort")


def test_submit_inherits_ambient_trace(tmp_path):
    from sirius_tpu.serve.engine import ServeEngine

    eng = ServeEngine(num_slices=1, workdir=str(tmp_path))
    with tracing.trace_context() as tid:
        job = eng.submit({"parameters": {}}, job_id="tr-amb")
    assert job.trace_id == tid
    eng.shutdown(wait=True, mode="abort")


def test_artifact_trace_id_missing_file_is_none(tmp_path):
    from sirius_tpu.campaigns import handoff

    assert handoff.artifact_trace_id(str(tmp_path / "nope.npz")) is None
    assert handoff.artifact_trace_id(None) is None


# ------------------------------------------------- timeline unit


def _synthetic_campaign_records(gap_s=0.001):
    """A serial 3-node chain with near-zero scheduler gaps, plus spans."""
    t0, recs = 1000.0, []
    recs.append({"kind": "campaign_submit", "ts": t0, "campaign_id": "c1",
                 "trace_id": "ab" * 8, "nodes": ["a", "b", "c"],
                 "edges": {"a": [], "b": ["a"], "c": ["b"]}})
    start = t0
    for i, n in enumerate(["a", "b", "c"]):
        recs.append({"kind": "job_transition", "ts": t0, "campaign_id": "c1",
                     "job_id": f"c1.{n}", "status": "queued",
                     "pid": 7, "thread": "slice-0"})
        run = start + gap_s
        recs.append({"kind": "job_transition", "ts": run, "campaign_id": "c1",
                     "job_id": f"c1.{n}", "status": "running",
                     "pid": 7, "thread": "slice-0"})
        recs.append({"kind": "span", "name": "scf.iteration", "t0": run,
                     "dur_s": 8.0, "ts": run + 8.0, "pid": 7,
                     "thread": "slice-0", "trace_id": "ab" * 8,
                     "hbm_peak_bytes": 2.0e9})
        recs.append({"kind": "job_transition", "ts": run + 8.0,
                     "campaign_id": "c1", "job_id": f"c1.{n}",
                     "status": "done", "pid": 7, "thread": "slice-0"})
        recs.append({"kind": "scf_done", "ts": run + 8.0,
                     "job_id": f"c1.{n}", "converged": True,
                     "iterations": 20 if i == 0 else 11})
        if i > 0:
            recs.append({"kind": "campaign_handoff", "ts": run,
                         "campaign_id": "c1", "node_id": n, "mode": "warm"})
        start = run + 8.0
    recs.append({"kind": "campaign_done", "ts": start, "campaign_id": "c1",
                 "wall_s": start - t0})
    return recs


def test_chrome_trace_structure_and_validation():
    doc = timeline.build_chrome_trace(_synthetic_campaign_records())
    assert timeline.validate_chrome_trace(doc) == []
    ev = doc["traceEvents"]
    xs = [e for e in ev if e["ph"] == "X" and e.get("cat") == "span"]
    assert len(xs) == 3 and all(e["dur"] == 8_000_000 for e in xs)
    assert all(e["args"]["trace_id"] == "ab" * 8 for e in xs)
    # per-node campaign tracks in a synthetic process + flow arrows
    nodes = [e for e in ev if e.get("cat") == "campaign_node"]
    assert {e["args"]["node_id"] for e in nodes} == {"a", "b", "c"}
    flows = [e for e in ev if e["ph"] in ("s", "f")]
    assert len(flows) == 4  # two handoff edges, start+finish each
    counters = [e for e in ev if e["ph"] == "C"]
    assert counters and counters[0]["args"]["bytes"] == 2.0e9
    # process/thread metadata names both the OS pid and the campaign
    names = {e["args"]["name"] for e in ev if e["ph"] == "M"}
    assert "sirius pid 7" in names and "campaign c1" in names
    # broken documents are rejected with located problems
    assert timeline.validate_chrome_trace({"traceEvents": "x"})
    bad = {"traceEvents": [{"ph": "X", "name": "n", "pid": 1, "tid": 1}]}
    probs = timeline.validate_chrome_trace(bad)
    assert any("ts" in p for p in probs) and any("dur" in p for p in probs)


def test_numerics_counter_tracks_render_and_validate():
    """Numerics-observatory events (obs/numerics.py + obs/forecast.py)
    render as Perfetto counter series — residual + ledger invariants,
    forecast decay rate/warning, and per-stage probe headroom — and the
    resulting document clears validate_chrome_trace."""
    recs = [
        {"kind": "scf_iteration", "ts": 10.0, "pid": 7, "thread": "main",
         "it": 1, "rms": 1e-3, "e_total": -7.5,
         "ledger": {"ortho": 1e-15, "charge": 2e-13, "sym": 0.0,
                    "herm": 3e-16}},
        {"kind": "scf_forecast", "ts": 10.1, "pid": 7, "thread": "main",
         "it": 1, "path": "host", "decay_rate": 0.4,
         "forecast_remaining": 6, "forecast_total": 7, "warning": 0.0,
         "growth_streak": 0},
        {"kind": "numerics_probe", "ts": 10.2, "pid": 7, "thread": "main",
         "stage": "scf.mixing", "prec": "bf16", "energy_impact_ha": 3e-4,
         "rel_err": 1e-3, "clears": False},
    ]
    doc = timeline.build_chrome_trace(recs)
    assert timeline.validate_chrome_trace(doc) == []
    counters = {e["name"]: e for e in doc["traceEvents"] if e["ph"] == "C"}
    assert counters["scf_residual"]["args"] == {"rms": 1e-3}
    assert counters["numerics_ledger"]["args"]["charge"] == 2e-13
    assert set(counters["numerics_ledger"]["args"]) == {
        "ortho", "charge", "sym", "herm"}
    assert counters["scf_forecast"]["args"]["decay_rate"] == 0.4
    assert counters["scf_forecast"]["args"]["warning"] == 0.0
    assert counters["numerics_headroom"]["args"] == {"scf.mixing:bf16": 3e-4}
    # every numerics record still gets its instant marker alongside
    instants = [e for e in doc["traceEvents"] if e["ph"] == "i"]
    assert {e["name"] for e in instants} == {
        "scf_iteration", "scf_forecast", "numerics_probe"}


def test_trace_id_filter_selects_one_trace():
    recs = _synthetic_campaign_records()
    recs.append({"kind": "span", "name": "scf.iteration", "t0": 0.0,
                 "dur_s": 1.0, "ts": 1.0, "pid": 9, "thread": "other",
                 "trace_id": "ff" * 8})
    doc = timeline.build_chrome_trace(recs, trace_id="ff" * 8)
    xs = [e for e in doc["traceEvents"]
          if e["ph"] == "X" and e.get("cat") == "span"]
    assert len(xs) == 1 and xs[0]["pid"] == 9


def test_critical_path_serial_chain_reconciles():
    rep = timeline.campaign_critical_path(_synthetic_campaign_records())
    assert rep["critical_path"] == ["a", "b", "c"]
    # acceptance: duration sum along the chain within 5% of measured wall
    assert abs(rep["cp_over_wall"] - 1.0) <= 0.05
    assert all(d["slack_s"] == 0.0 and d["critical"]
               for d in rep["nodes"].values())
    # warm-start savings against the cold baseline (node a: 20 iters)
    assert rep["warm_baseline_iterations"] == 20
    assert rep["warm_savings_iterations"] == {"b": 9, "c": 9}
    assert rep["trace_id"] == "ab" * 8


def test_critical_path_diamond_has_slack():
    t0, recs = 50.0, []
    recs.append({"kind": "campaign_submit", "ts": t0, "campaign_id": "d1",
                 "nodes": ["root", "fast", "slow", "join"],
                 "edges": {"root": [], "fast": ["root"], "slow": ["root"],
                           "join": ["fast", "slow"]}})
    ivs = {"root": (t0, t0 + 4), "fast": (t0 + 4, t0 + 5),
           "slow": (t0 + 4, t0 + 14), "join": (t0 + 14, t0 + 16)}
    for n, (a, b) in ivs.items():
        for ts, st in ((a, "running"), (b, "done")):
            recs.append({"kind": "job_transition", "ts": ts,
                         "campaign_id": "d1", "job_id": f"d1.{n}",
                         "status": st})
    rep = timeline.campaign_critical_path(recs)
    assert rep["critical_path"] == ["root", "slow", "join"]
    assert rep["critical_path_s"] == 16.0
    assert rep["nodes"]["fast"]["slack_s"] == 9.0
    assert rep["nodes"]["slow"]["slack_s"] == 0.0


def test_cli_export_validate_critical_path(tmp_path, capsys):
    ev_path = str(tmp_path / "events.jsonl")
    with open(ev_path, "w", encoding="utf-8") as fh:
        for r in _synthetic_campaign_records():
            fh.write(json.dumps(r) + "\n")
    out = str(tmp_path / "timeline.json")
    assert timeline.main(["export", "--events", ev_path, "--out", out]) == 0
    with open(out, encoding="utf-8") as fh:
        doc = json.load(fh)
    assert timeline.validate_chrome_trace(doc) == []
    assert timeline.main(["validate", out]) == 0
    assert timeline.main(["critical-path", "--events", ev_path]) == 0
    assert "a -> b -> c" in capsys.readouterr().out
    # a corrupt document fails validation with rc 1
    with open(out, "w", encoding="utf-8") as fh:
        json.dump({"traceEvents": [{"ph": "??"}]}, fh)
    assert timeline.main(["validate", out]) == 1


def test_export_records_its_own_span(tmp_path):
    ev_path = str(tmp_path / "events.jsonl")
    with open(ev_path, "w", encoding="utf-8") as fh:
        fh.write(json.dumps({"kind": "scf_done", "ts": 1.0}) + "\n")
    with spans.capture() as cap:
        timeline.export_timeline(ev_path)
    rec = cap.by_name("trace.export")[0]
    assert rec["events"] == 1 and rec["trace_events"] >= 0


# ------------------------------------------------- telemetry off


def test_telemetry_off_spans_and_events_are_noops(tmp_path):
    obs.disable()
    try:
        with tracing.trace_context():  # tracing itself stays functional
            with spans.capture() as cap:
                with spans.span("scf.iteration"):
                    spans.record("scf.density", 0.1)
            obs_events.emit("scf_done", converged=True)
        assert cap.records == []
        assert not obs_events.configured()
        assert tracing.current_trace_id() is None
    finally:
        obs.enable()


# ------------------------------------------------- end-to-end (serve mesh)


@requires_mesh
def test_campaign_trace_end_to_end(tmp_path):
    """One campaign, one trace: every span of every node carries the DAG
    trace id; the handoff artifact carries it; the exported timeline
    validates; and the critical-path sum reconciles with the measured
    wall within the 5% acceptance bar."""
    from sirius_tpu.campaigns import handoff, runner
    from sirius_tpu.campaigns.spec import CampaignNode, CampaignSpec
    from sirius_tpu.serve.engine import ServeEngine
    from sirius_tpu.serve.queue import JobStatus
    from tests.test_serve import make_deck

    ev_path = str(tmp_path / "events.jsonl")
    spec = CampaignSpec(campaign_id="trc", kind="generic", nodes=[
        CampaignNode(node_id="n0", deck=make_deck()),
        CampaignNode(node_id="n1", deck=make_deck(), parents=["n0"],
                     warm_from="n0", displaced=False),
    ])
    eng = ServeEngine(num_slices=1, devices=jax.devices()[:2],
                      workdir=str(tmp_path), events_path=ev_path)
    eng.start()
    try:
        handle = runner.submit_campaign(eng, spec, workdir=str(tmp_path))
        assert eng.wait_all(timeout=900.0)
        summary = handle.finalize()
    finally:
        eng.shutdown(wait=True)
        obs.close_events()

    assert handle.jobs["n0"].status == JobStatus.DONE
    assert handle.jobs["n1"].status == JobStatus.DONE, handle.jobs["n1"].error
    tid = handle.jobs["n0"].trace_id
    assert tid and handle.jobs["n1"].trace_id == tid

    evs = obs.read_events(ev_path)
    span_recs = [e for e in evs if e["kind"] == "span"]
    assert span_recs, "no spans in the event log"
    # no orphans: every span emitted under the campaign carries its trace
    scf_spans = [e for e in span_recs if e["name"].startswith("scf.")]
    assert scf_spans and all(e.get("trace_id") == tid for e in scf_spans)
    # exactly-once: span ids never repeat in the log
    sids = [e["span_id"] for e in span_recs if "span_id" in e]
    assert len(sids) == len(set(sids))
    # journal-free continuity: the handoff artifact carries the trace
    art = handoff.artifact_path(str(tmp_path), "trc", "n0")
    assert handoff.artifact_trace_id(art) == tid
    # the warm child reproduces the parent energy (same geometry)
    assert summary is not None

    out = str(tmp_path / "timeline.json")
    assert timeline.main(["export", "--events", ev_path, "--out", out,
                          "--trace-id", tid]) == 0
    with open(out, encoding="utf-8") as fh:
        doc = json.load(fh)
    assert timeline.validate_chrome_trace(doc) == []
    xs = [e for e in doc["traceEvents"]
          if e["ph"] == "X" and e.get("cat") == "span"]
    assert xs, "exported timeline has no span tracks"
    assert any(e.get("cat") == "campaign_node" for e in doc["traceEvents"])

    rep = timeline.campaign_critical_path(evs, campaign_id="trc")
    assert rep["critical_path"] == ["n0", "n1"]
    assert rep["trace_id"] == tid
    # acceptance: node duration sum within 5% of the measured wall
    assert rep["cp_over_wall"] is not None
    assert abs(rep["cp_over_wall"] - 1.0) <= 0.05, rep
    assert rep["nodes"]["n1"]["handoff_mode"] == "warm"
