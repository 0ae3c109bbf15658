"""One job's span tree on one clock (obs/spans.py, obs/trace.py).

serve.job -> serve.queue_wait, serve.context_build, serve.run ->
scf.run -> scf.setup, scf.iteration x N -> the stage spans, scf.finalize;
every record carries its start and end in Unix nanoseconds, the profiler
capture records when its session started on the same clock, and while
it is active the spans are mirrored into the profiler's own file.
"""

import time

import pytest

from sirius_tpu import obs
from sirius_tpu.obs import spans
from sirius_tpu.obs.trace import CAPTURE

STAGES = {"scf.d_matrix", "scf.band_solve", "scf.occupations",
          "scf.density", "scf.fused_step", "scf.mixing", "scf.potential",
          "scf.readback", "scf.autosave", "scf.numerics_probe"}


def _deck(iters: int = 3, **control) -> dict:
    return {
        "parameters": {
            "gk_cutoff": 3.0, "pw_cutoff": 7.0, "ngridk": [1, 1, 1],
            "num_bands": 8, "use_symmetry": False,
            "xc_functionals": ["XC_LDA_X", "XC_LDA_C_PZ"],
            "smearing_width": 0.025, "num_dft_iter": iters,
            "density_tol": 1e-14, "energy_tol": 1e-16,  # exactly `iters`
        },
        "control": {"ngk_pad_quantum": 16, "telemetry": True, **control},
        "synthetic": {"ultrasoft": True},
    }


def _run(tmp_path, deck):
    from sirius_tpu.config.schema import load_config
    from sirius_tpu.dft.scf import run_scf
    from sirius_tpu.serve.scheduler import build_job_context

    cfg = load_config(deck)
    ctx = build_job_context(cfg, str(tmp_path))
    return run_scf(cfg, base_dir=str(tmp_path), ctx=ctx)


def _inside(child: dict, parent: dict) -> bool:
    return (parent["start_unix_ns"] <= child["start_unix_ns"]
            and child["end_unix_ns"] <= parent["end_unix_ns"])


def _check_tree(records: list) -> dict:
    """The SCF part of the tree; returns the records by span_id."""
    by_id = {r["span_id"]: r for r in records}
    (run,) = [r for r in records if r["name"] == "scf.run"]
    top = [r for r in records if r["parent_id"] == run["span_id"]]
    # recorded at close, so sort by start: setup, iterations, finalize
    top.sort(key=lambda r: r["start_unix_ns"])
    names = [r["name"] for r in top if not r["name"].startswith("trace.")]
    assert names[0] == "scf.setup" and names[-1] == "scf.finalize"
    assert set(names[1:-1]) == {"scf.iteration"}
    starts = [r["start_unix_ns"] for r in top]
    assert starts == sorted(starts)
    for a, b in zip(top, top[1:]):  # siblings follow one another
        if "trace.capture" not in (a["name"], b["name"]):
            assert a["end_unix_ns"] <= b["start_unix_ns"]
    for r in records:
        assert r["end_unix_ns"] >= r["start_unix_ns"]
        assert r["t0"] == pytest.approx(r["start_unix_ns"] * 1e-9, abs=1e-5)
        if r["name"] in STAGES and r["name"] != "scf.numerics_probe":
            parent = by_id[r["parent_id"]]
            assert parent["name"] == "scf.iteration", r["name"]
            assert parent["it"] == r["it"]
            assert parent["parent_id"] == run["span_id"]
        if r["parent_id"] in by_id and r["name"] != "trace.capture":
            assert _inside(r, by_id[r["parent_id"]]), r["name"]
    return by_id


@pytest.mark.parametrize("device_scf", ["auto", "off"])
def test_scf_span_tree(tmp_path, device_scf):
    with spans.capture() as cap:
        res = _run(tmp_path, _deck(3, device_scf=device_scf,
                                   autosave_every=2))
    assert res["num_scf_iterations"] == 3
    assert spans.current() is None
    recs = [r for r in cap.records if r["name"].startswith("scf.")]
    _check_tree(recs)
    its = sorted((r for r in recs if r["name"] == "scf.iteration"),
                 key=lambda r: r["it"])
    assert [r["it"] for r in its] == [1, 2, 3]
    path = "fused" if device_scf == "auto" else "host"
    assert all(r["path"] == path and "incomplete" not in r for r in its)
    kids = {r["name"] for r in recs if r["parent_id"] == its[1]["span_id"]}
    want = ({"scf.band_solve", "scf.occupations", "scf.density",
             "scf.fused_step", "scf.readback"} if path == "fused" else
            {"scf.d_matrix", "scf.band_solve", "scf.occupations",
             "scf.density", "scf.mixing", "scf.potential"})
    assert kids == want | {"scf.autosave"}  # autosave_every 2: iteration 2
    # a layer's self time: the iteration less its children, small and >= 0
    for it in its:
        inside = sum(r["dur_s"] for r in recs
                     if r["parent_id"] == it["span_id"])
        assert 0.0 <= it["dur_s"] - inside < 0.5 * it["dur_s"] + 0.05


@pytest.mark.faults
def test_continue_path_leaves_no_span_open(tmp_path):
    """A recovery rollback leaves the loop body by `continue`: the next
    iteration's head closes the one it left, marked, and nothing stays
    open."""
    from sirius_tpu.utils import faults

    faults.install([("scf.density", 1, "nan")])
    with spans.capture() as cap:
        res = _run(tmp_path, _deck(4, device_scf="off"))
    assert res["recovery"]["recoveries"] == 1
    assert spans.current() is None
    recs = [r for r in cap.records if r["name"].startswith("scf.")]
    _check_tree(recs)
    its = sorted((r for r in recs if r["name"] == "scf.iteration"),
                 key=lambda r: r["it"])
    assert [bool(r.get("incomplete")) for r in its] == [
        False, True, False, False]
    assert "path" not in its[1]  # it never reached its bookkeeping


@pytest.mark.faults
def test_raise_out_of_the_loop_unwinds_the_tree(tmp_path):
    from sirius_tpu.utils import faults

    faults.install([("scf.autosave_kill", 1, "raise")])
    with spans.capture() as cap, pytest.raises(faults.SimulatedKill):
        _run(tmp_path, _deck(4, device_scf="off", autosave_every=1))
    assert spans.current() is None
    by_name = {r["name"]: r for r in cap.records}
    assert by_name["scf.run"]["error"] == "SimulatedKill"
    left = [r for r in cap.records if r.get("unwound")]
    assert [r["name"] for r in left] == ["scf.iteration"]
    assert left[0]["it"] == 2 and _inside(left[0], by_name["scf.run"])
    assert "scf.finalize" not in by_name


def test_close_unwinds_children_and_restores_the_contextvar():
    with spans.capture() as cap:
        run = spans.open_span("scf.run")
        it = spans.open_span("scf.iteration", it=1)
        spans.open_span("scf.density")  # never closed by its opener
        it.close(incomplete=True)
        assert spans.current() is run
        it.close()  # closing twice records nothing
        it2 = spans.open_span("scf.iteration", it=2)
        assert it2.parent_id == run.span_id
        run.close()
        assert spans.current() is None
    names = [(r["name"], bool(r.get("unwound"))) for r in cap.records]
    assert names == [("scf.density", True), ("scf.iteration", False),
                     ("scf.iteration", True), ("scf.run", False)]
    stage, first = cap.records[0], cap.records[1]
    assert first["incomplete"] and _inside(stage, first)
    # a span that is not open in this context leaves the others alone
    a = spans.open_span("a")
    it.close()
    assert spans.current() is a
    a.close()


def test_record_is_for_intervals_measured_from_outside():
    with spans.capture() as cap:
        with spans.span("serve.job") as job:
            spans.record("serve.queue_wait", start_unix_ns=5_000_000_000,
                         end_unix_ns=7_500_000_000, slice=0)
            spans.record("x.backdated", 0.25)
            spans.record("x.from_t0", 2.0, t0=100.0)
    q, b, t, _ = cap.records
    assert q["parent_id"] == job.span_id and q["dur_s"] == 2.5
    assert q["t0"] == 5.0 and q["slice"] == 0
    assert b["end_unix_ns"] - b["start_unix_ns"] == 250_000_000
    assert abs(b["end_unix_ns"] - time.time_ns()) < 1_000_000_000
    assert (t["start_unix_ns"], t["end_unix_ns"]) == (
        100_000_000_000, 102_000_000_000)


def test_spans_off_cost_one_flag_test(monkeypatch):
    from sirius_tpu.obs import metrics

    calls = []
    monkeypatch.setattr(metrics, "enabled",
                        lambda: calls.append(1) and False)
    with spans.capture() as cap:
        sp = spans.open_span("scf.band_solve", flops=1.0, it=1)
        assert sp is spans.open_span("scf.density")  # one shared object
        assert calls == [1, 1]
        sp.fence = object()
        sp.set(path="host")
        sp.close(incomplete=True)
        assert sp.attrs == {} and sp.fence is None
        assert calls == [1, 1]  # set/close test nothing
        with spans.span("scf.mixing"):
            assert spans.current() is None
        assert calls == [1, 1, 1]
    assert cap.records == []


def test_no_trace_annotation_without_a_capture(monkeypatch):
    import jax

    entered = []

    class Annotation:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            entered.append(self.name)

        def __exit__(self, *exc):
            entered.append("/" + self.name)

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
    assert not CAPTURE.status()["active"]
    with spans.span("scf.iteration"):
        pass
    assert entered == []
    spans.set_mirror(Annotation)  # what obs/trace.py does while it captures
    try:
        with spans.span("scf.iteration"):
            with spans.span("scf.density"):
                pass
    finally:
        spans.set_mirror(None)
    assert entered == ["scf.iteration", "scf.density", "/scf.density",
                       "/scf.iteration"]
    with spans.span("scf.iteration"):
        pass
    assert len(entered) == 4


def _xplane(trace_dir):
    import jax

    (path,) = list(trace_dir.rglob("*.xplane.pb"))
    return path, jax.profiler.ProfileData.from_file(str(path))


def test_capture_armed_at_entry_is_steady_state_and_on_the_spans_clock(
        tmp_path):
    with spans.capture() as cap:
        res = _run(tmp_path, _deck(4, device_scf="off",
                                   trace_capture="tracedir",
                                   trace_capture_steps=2))
    assert res["num_scf_iterations"] == 4
    (capture,) = cap.by_name("trace.capture")
    (stop,) = cap.by_name("trace.stop")
    (run,) = cap.by_name("scf.run")
    its = {r["it"]: r for r in cap.by_name("scf.iteration")}
    assert capture["first_iteration"] == 2 and capture["steps"] == 2
    assert capture["trace_id"] == run["trace_id"] == stop["trace_id"]
    assert capture["trace_dir"].endswith("tracedir")
    # it delimits iterations 2 and 3 and is nobody's parent
    assert its[1]["end_unix_ns"] <= capture["session_start_unix_ns"]
    assert capture["session_start_unix_ns"] <= capture["start_unix_ns"]
    assert capture["start_unix_ns"] <= its[2]["start_unix_ns"]
    assert its[3]["end_unix_ns"] <= capture["end_unix_ns"]
    assert capture["end_unix_ns"] <= its[4]["start_unix_ns"]
    assert not [r for r in cap.records
                if r["parent_id"] == capture["span_id"]]
    assert capture["end_unix_ns"] <= stop["start_unix_ns"]
    assert stop["end_unix_ns"] <= its[4]["start_unix_ns"]
    assert stop["parent_id"] == run["span_id"]

    # the table of device seconds by the program's scope names, made from
    # the trace the stop wrote, on the job's trace id and nobody's parent
    (scopes,) = cap.by_name("trace.scopes")
    assert scopes["trace_id"] == run["trace_id"]
    assert stop["end_unix_ns"] <= scopes["start_unix_ns"]
    assert scopes["end_unix_ns"] <= its[4]["start_unix_ns"]
    assert not [r for r in cap.records
                if r["parent_id"] == scopes["span_id"]]
    assert scopes["steps"] == 2 and scopes["devices"] >= 1
    assert scopes["source"] == "xplane_hlo_proto"
    assert scopes["modules_without_hlo"] == []
    by_scope = scopes["by_scope"]
    for path in ("davidson_hpsi", "davidson_hpsi/local_op",
                 "davidson_hpsi/beta_proj", "davidson_rr",
                 "davidson_rr/eigh_kernel", "davidson_rotate"):
        assert 0 < by_scope[path]["s"] <= scopes["busy_s"], path
    assert by_scope["davidson_hpsi/local_op"]["s"] <= \
        by_scope["davidson_hpsi"]["s"]
    assert {"davidson_hpsi", "local_op", "davidson_rr"} <= \
        set(scopes["scopes_seen"])
    assert any("davidson" in m for m in scopes["by_module"])
    assert scopes["reduce_s"] == pytest.approx(scopes["dur_s"], abs=0.05)
    last = CAPTURE.status()["last_scopes"]
    assert last["by_scope"] == by_scope and last["busy_s"] == scopes["busy_s"]

    # only the .xplane.pb is written: no conversion to trace.json.gz
    path, pd = _xplane(tmp_path / "tracedir")
    assert stop["xplane_bytes"] == path.stat().st_size > 0
    assert not list((tmp_path / "tracedir").rglob("*.json*"))

    # the mirrored annotations, read back from the profiler's own file,
    # lie where the spans say: within 1 ms on the shared clock
    zero = capture["session_start_unix_ns"]
    found = {}
    for plane in pd.planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name in ("scf.iteration", "scf.band_solve",
                               "scf.potential"):
                    found.setdefault(ev.name, []).append(
                        (ev.start_ns, ev.duration_ns))
    assert len(found["scf.iteration"]) == 2  # iterations 2 and 3, not 1
    for name, evs in found.items():
        recs = sorted((r for r in cap.by_name(name) if r["it"] in (2, 3)),
                      key=lambda r: r["it"])
        assert len(evs) == len(recs)
        for (start, dur), rec in zip(sorted(evs), recs):
            assert abs(zero + start - rec["start_unix_ns"]) < 1e6, name
            assert abs(zero + start + dur - rec["end_unix_ns"]) < 1e6
    # and the profiler's own record of when its session started agrees
    env = pd.find_plane_with_name("Task Environment")
    assert abs(dict(env.stats)["profile_start_time"] - zero) < 1e6


def test_capture_unstarted_when_the_loop_ends_before_iteration_2(tmp_path):
    with spans.capture() as cap:
        res = _run(tmp_path, _deck(1, device_scf="off",
                                   trace_capture="tracedir"))
    assert res["num_scf_iterations"] == 1
    assert not cap.by_name("trace.capture") and not cap.by_name("trace.stop")
    st = CAPTURE.status()
    assert not st["active"] and st["armed_dir"] is None
    assert not list((tmp_path / "tracedir").rglob("*.xplane.pb"))
    # disarmed, not done: the same directory can be asked for again
    assert CAPTURE.request(str(tmp_path / "tracedir"), steps=1)
    CAPTURE.finish()


def test_forced_capture_starts_at_the_next_iteration(tmp_path):
    """The serve endpoint's request has no skip."""
    target = str(tmp_path / "forced")
    with spans.capture() as cap:
        assert CAPTURE.request(target, steps=1, force=True)
        CAPTURE.tick(7)
        assert CAPTURE.status()["active"]
        with spans.span("scf.iteration", it=7):
            pass
        CAPTURE.tick(8)
        assert not CAPTURE.status()["active"]
    (capture,) = cap.by_name("trace.capture")
    assert capture["first_iteration"] == 7 and capture["steps"] == 1
    assert cap.by_name("trace.stop")[0]["xplane_bytes"] > 0
    assert target in CAPTURE.status()["completed"]


def test_serve_job_owns_context_build_queue_wait_and_run(tmp_path):
    from sirius_tpu.serve.engine import ServeEngine

    eng = ServeEngine(num_slices=1, workdir=str(tmp_path))
    eng.start()
    try:
        with spans.capture() as cap:
            job = eng.submit(_deck(2, device_scf="off"))
            job.wait()
    finally:
        eng.shutdown(wait=True)
    assert job.status == "done"
    mine = [r for r in cap.records if r.get("trace_id") == job.trace_id]
    by_name = {}
    for r in mine:
        by_name.setdefault(r["name"], []).append(r)
    (sjob,) = by_name["serve.job"]
    assert sjob["parent_id"] is None and sjob["status"] == "done"
    for name in ("serve.context_build", "serve.queue_wait", "serve.run"):
        (r,) = by_name[name]
        assert r["parent_id"] == sjob["span_id"], name
    (wait,) = by_name["serve.queue_wait"]
    assert wait["end_unix_ns"] == sjob["start_unix_ns"]  # ends at the pop
    assert wait["start_unix_ns"] == int(job.submitted_at * 1e9)
    (build,), (run,) = by_name["serve.context_build"], by_name["serve.run"]
    assert _inside(build, sjob) and _inside(run, sjob)
    assert build["end_unix_ns"] <= run["start_unix_ns"]
    (scf_run,) = by_name["scf.run"]
    assert scf_run["parent_id"] == run["span_id"] and _inside(scf_run, run)
    # compile is fields of serve.run, not a span with an invented start
    assert "serve.compile" not in by_name
    assert run["compile_s"] >= 0.0 and run["compiled_executables"] >= 0
    assert len({r["thread"] for r in mine}) == 1
    _check_tree([r for r in mine if r["name"].startswith("scf.")])


def test_timeline_merges_the_xplane_on_the_spans_clock(tmp_path):
    from sirius_tpu.obs import timeline

    with spans.capture() as cap:
        _run(tmp_path, _deck(3, device_scf="off", events_path="ev.jsonl",
                             trace_capture="tracedir",
                             trace_capture_steps=1))
    obs.close_events()
    doc = timeline.export_timeline(
        str(tmp_path / "ev.jsonl"), jax_trace_dir=str(tmp_path / "tracedir"))
    assert timeline.validate_chrome_trace(doc) == []
    merged = [e for e in doc["traceEvents"] if e.get("cat") == "xplane"]
    assert merged
    (it2,) = [r for r in cap.by_name("scf.iteration") if r["it"] == 2]
    (mirror,) = [e for e in merged if e["name"] == "scf.iteration"]
    assert abs(mirror["ts"] * 1e3 - it2["start_unix_ns"]) < 1e6
    # device operations carry the scope path the capture's table put them to
    (scopes,) = cap.by_name("trace.scopes")
    labelled = [e["args"]["scope"] for e in merged if "args" in e]
    assert len(labelled) >= sum(  # and the loops under a scope, no leaves
        v["ops"] for p, v in scopes["by_scope"].items() if "/" not in p) > 0
    assert set(labelled) <= set(scopes["by_scope"])
