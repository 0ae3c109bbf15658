"""Span timeline (sirius_tpu/obs/spans.py): nesting/parent linkage,
decorator + externally-timed records, exactly-once JSONL emission through
a real 2-iteration SCF run, the >= 90% attribution acceptance bar, and
the zero-overhead no-op when control.telemetry is off."""

import json

import pytest

from sirius_tpu import obs
from sirius_tpu.obs import spans


@pytest.fixture(autouse=True)
def _clean_obs():
    obs.enable()
    yield
    obs.close_events()
    obs.enable()


# ---------------------------------------------------------------------------
# unit: lineage, decorator, record


def test_nesting_and_parent_linkage():
    with spans.capture() as cap:
        with spans.span("outer") as so:
            assert spans.current() is so
            with spans.span("inner") as si:
                assert spans.current() is si
                with spans.span("leaf"):
                    pass
        assert spans.current() is None
    recs = {r["name"]: r for r in cap.records}
    assert set(recs) == {"outer", "inner", "leaf"}
    assert recs["outer"]["parent_id"] is None and recs["outer"]["depth"] == 0
    assert recs["inner"]["parent_id"] == recs["outer"]["span_id"]
    assert recs["leaf"]["parent_id"] == recs["inner"]["span_id"]
    assert recs["leaf"]["depth"] == 2
    # children close before parents -> capture order is leaf-first
    assert [r["name"] for r in cap.records] == ["leaf", "inner", "outer"]
    assert all(r["dur_s"] >= 0 for r in cap.records)


def test_siblings_share_parent():
    with spans.capture() as cap:
        with spans.span("parent") as sp:
            with spans.span("a"):
                pass
            with spans.span("b"):
                pass
    a, b = cap.by_name("a")[0], cap.by_name("b")[0]
    assert a["parent_id"] == b["parent_id"] == cap.by_name("parent")[0]["span_id"]


def test_decorator_and_record_lineage():
    @spans.spanned("work.unit")
    def unit(x):
        return x + 1

    with spans.capture() as cap:
        with spans.span("parent"):
            assert unit(1) == 2
            spans.record("work.external", 0.25, detail="queue")
    u = cap.by_name("work.unit")[0]
    e = cap.by_name("work.external")[0]
    pid = cap.by_name("parent")[0]["span_id"]
    assert u["parent_id"] == pid and e["parent_id"] == pid
    assert e["dur_s"] == 0.25 and e["detail"] == "queue"


def test_exception_recorded_and_contextvar_restored():
    with spans.capture() as cap:
        with pytest.raises(ValueError):
            with spans.span("boom"):
                raise ValueError("x")
    assert cap.by_name("boom")[0]["error"] == "ValueError"
    assert spans.current() is None


def test_cost_annotations_on_span():
    with spans.capture() as cap:
        spans.record("annotated", 0.5, flops=1e9)
    r = cap.by_name("annotated")[0]
    assert r["gflops"] == pytest.approx(2.0)
    assert r["roofline_gflops"] > 0
    assert 0 <= r["mfu"] <= 1.0


def test_span_histogram_fed():
    from sirius_tpu.obs.metrics import REGISTRY

    with spans.span("histo.stage"):
        pass
    snap = REGISTRY.snapshot()
    fam = snap.get("perf_span_seconds")
    assert fam is not None
    assert any(s["labels"].get("span") == "histo.stage"
               for s in fam["samples"])


def test_fence_callable_and_pytree():
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp

    with spans.capture() as cap:
        with spans.span("fenced") as sp:
            sp.fence = jnp.ones(8) * 2.0
        with spans.span("fenced_callable", fence=lambda: jnp.zeros(4)):
            pass
        with spans.span("fenced_garbage", fence=object()):
            pass  # best-effort: junk fences never raise
    assert len(cap.records) == 3


# ---------------------------------------------------------------------------
# telemetry off: spans are no-ops


def test_disabled_spans_are_noop():
    obs.disable()
    try:
        with spans.capture() as cap:
            with spans.span("invisible") as sp:
                # no identity assigned, no contextvar write
                assert spans.current() is None
                assert not hasattr(sp, "span_id")
            spans.record("also.invisible", 1.0)
        assert cap.records == []
    finally:
        obs.enable()


def test_disabled_no_registry_samples():
    from sirius_tpu.obs.metrics import REGISTRY

    obs.disable()
    try:
        with spans.span("off.stage"):
            pass
        snap = REGISTRY.snapshot()
        fam = snap.get("perf_span_seconds", {"samples": []})
        assert not any(s["labels"].get("span") == "off.stage"
                       for s in fam["samples"])
    finally:
        obs.enable()


# ---------------------------------------------------------------------------
# integration: a real 2-iteration SCF run


def _span_deck(events_name: str, **control) -> dict:
    return {
        "parameters": {
            "gk_cutoff": 3.0,
            "pw_cutoff": 7.0,
            "ngridk": [1, 1, 1],
            "num_bands": 8,
            "use_symmetry": False,
            "xc_functionals": ["XC_LDA_X", "XC_LDA_C_PZ"],
            "smearing_width": 0.025,
            "num_dft_iter": 2,
            "density_tol": 1e-14,  # never converge early: exactly 2 its
            "energy_tol": 1e-16,
        },
        "control": {"ngk_pad_quantum": 16, "telemetry": True,
                    "events_path": events_name, **control},
        "synthetic": {"ultrasoft": True},
    }


def _run(tmp_path, deck):
    from sirius_tpu.config.schema import load_config
    from sirius_tpu.dft.scf import run_scf
    from sirius_tpu.serve.scheduler import build_job_context

    cfg = load_config(deck)
    ctx = build_job_context(cfg, str(tmp_path))
    return run_scf(cfg, base_dir=str(tmp_path), ctx=ctx)


@pytest.mark.parametrize("fence", [True, False])
def test_band_solve_span_carries_the_cost_of_the_steps_that_ran(tmp_path,
                                                                fence):
    """The band solve's trip count is dynamic (PR 37): the model's
    "scf.band_solve" is the cost of all num_steps steps, the span carries
    the cost of the steps its solve ran (fenced: its time is the solve's)
    or, on the device path without a fence, where it times a dispatch and
    the steps are still on the device, no cost and so no utilization."""
    from sirius_tpu.config.schema import load_config
    from sirius_tpu.dft.scf import run_scf
    from sirius_tpu.obs import costs
    from sirius_tpu.serve.scheduler import build_job_context

    deck = _span_deck("events.jsonl", span_fence=fence)
    deck["parameters"]["ngridk"] = [2, 2, 2]
    cfg = load_config(deck)
    ctx = build_job_context(cfg, str(tmp_path))
    with spans.capture() as cap:
        res = run_scf(cfg, base_dir=str(tmp_path), ctx=ctx)
    obs.close_events()
    assert res["placement"]["path"] == "batched+fused"
    assert res["counters"]["num_davidson_steps"] < 2 * 20
    bs = cap.by_name("scf.band_solve")
    assert len(bs) == 2 and all(r["num_steps"] == 20 for r in bs)
    if not fence:
        assert not any("flops" in r or "mfu" in r for r in bs)
        return
    shapes = (ctx.num_bands, int(ctx.gkvec.ngk_max),
              int(ctx.beta.num_beta_total), tuple(ctx.fft_coarse.dims))
    lanes = ctx.gkvec.num_kpoints * ctx.num_spins
    bound = lanes * costs.davidson_cost(*shapes, 20).flops
    least = lanes * costs.davidson_cost(*shapes, 1, chunks=1).flops
    for r in bs:
        assert least <= r["flops"] < bound and r["gflops"] > 0


def test_scf_spans_attribution_and_exactly_once_jsonl(tmp_path):
    with spans.capture() as cap:
        res = _run(tmp_path, _span_deck("events.jsonl", span_fence=True))
    obs.close_events()
    assert res["num_scf_iterations"] == 2

    # >= 5 distinct attributed stages, annotated with the cost model
    iters = cap.durations("scf.iteration")
    assert len(iters) == 2
    per_iter = [n for n in cap.names()
                if n.startswith("scf.")
                and n not in ("scf.iteration", "scf.setup", "scf.readback",
                              "scf.run", "scf.finalize")]
    assert len(per_iter) >= 5
    attributed = sum(sum(cap.durations(n)) for n in per_iter)
    assert attributed / sum(iters) >= 0.90
    bs = cap.by_name("scf.band_solve")[0]
    assert bs["gflops"] > 0 and bs["roofline_gflops"] > 0

    # exactly-once JSONL: one span event per captured record of each
    # SCF stage (the sink and the capture collector see the same closes)
    lines = [json.loads(ln) for ln in
             (tmp_path / "events.jsonl").read_text().splitlines()]
    span_events = [e for e in lines if e["kind"] == "span"]
    emitted = {}
    for e in span_events:
        emitted[e["name"]] = emitted.get(e["name"], 0) + 1
    assert emitted["scf.iteration"] == 2
    for n in per_iter:
        assert emitted[n] == len(cap.by_name(n)), n
    # every emitted stage span carries the span identity fields
    assert all("span_id" in e and "dur_s" in e for e in span_events)


def test_scf_spans_off_with_telemetry_disabled(tmp_path):
    with spans.capture() as cap:
        res = _run(tmp_path, _span_deck("events.jsonl", telemetry=False))
    obs.close_events()
    assert res["num_scf_iterations"] == 2
    # the deck's control.telemetry takes effect at run_scf entry: the
    # context the caller built before it is the one thing spanned (with its
    # children: the group search, the two table stages, the position stage
    # and the phase table inside it)
    assert [r["name"] for r in cap.records] == [
        "context.symmetry", "context.lattice_tables",
        "context.species_tables", "context.phases", "context.positions",
        "serve.context_build"]
