"""The reduction from device operations to busy and idle time, on a small
recorded trace (tests/data/trace_small.json: the first operations of a traced
run of si2-k444.scf on the v5e, cut by hand) and on intervals small enough to
add up in the head."""

import json
import os

import pytest

from benchmark.harness import trace_reduce

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def test_union_of_overlapping_and_nested_intervals():
    assert trace_reduce.union_ns([]) == 0.0
    # [0,10) and [5,12) overlap, [20,30) holds [22,25), [40,41) stands alone
    iv = [(20, 30), (0, 10), (5, 12), (22, 25), (40, 41)]
    assert trace_reduce.union_ns(iv) == 12 + 10 + 1


def test_idle_share_is_the_mean_over_devices():
    trace = {"window_ns": [0.0, 100.0],
             "devices": ["/device:TPU:0", "/device:TPU:1"], "names": ["a", "b"],
             "dev": [0, 0, 1], "name": [0, 1, 0],
             "start_ns": [0.0, 20.0, 50.0], "dur_ns": [30.0, 20.0, 10.0]}
    out = trace_reduce.reduce(trace)
    assert out["busy_by_device_s"] == {"/device:TPU:0": 40e-9,
                                       "/device:TPU:1": 10e-9}
    assert out["busy_s"] == pytest.approx(25e-9)
    assert out["idle_share"] == pytest.approx(0.75)
    # summed per name, per device: a = (30 + 10) / 2, b = 20 / 2
    assert out["device_ops"] == [["a", pytest.approx(20e-9)],
                                 ["b", pytest.approx(10e-9)]]


def test_recorded_trace_gives_the_hand_computed_idle_share():
    with open(os.path.join(DATA, "trace_small.json")) as f:
        doc = json.load(f)
    out = trace_reduce.reduce(doc["trace"])
    want = doc["hand_computed"]
    assert out["num_events"] == want["num_events"]
    assert out["busy_s"] == pytest.approx(want["busy_s"], rel=1e-12)
    assert out["window_s"] == pytest.approx(want["window_s"], rel=1e-12)
    assert out["idle_share"] == pytest.approx(want["idle_share"], rel=1e-9)
    assert trace_reduce.scope_seconds(doc["trace"], want["scope"]) == \
        pytest.approx(want["scope_s"], rel=1e-12)


def test_scope_roofline_reader_takes_a_scope_a_counter_and_shapes(monkeypatch):
    """The reader a later `hpsi_roofline` file would name: least time for the
    counted applications over the device time under a scope. No cell has it
    yet (the TPU trace carries no jax.named_scope path, PERF.md section 7)."""
    from benchmark.harness import costs, shapes, sources

    with open(os.path.join(DATA, "trace_small.json")) as f:
        doc = json.load(f)
    sh = {"nk": 64, "nb": 26, "ngk": 352, "nbeta": 36, "box": [24, 24, 24]}
    monkeypatch.setattr(shapes, "of_deck", lambda deck: sh)
    record = {"trace_raw": doc["trace"], "trace_steps": 5, "chips": 1,
              "deck0": {}, "device_kind": "TPU v5 lite",
              "trace_job": {"result": {"num_scf_iterations": 10,
                                       "counters": {"n": 2000}}}}
    args = {"scope": doc["hand_computed"]["scope"], "counter": "counters.n"}
    got = sources.trace_scope_roofline(record, args)
    rows = 2000 * 5 / 10
    least = costs.roofline_seconds(
        costs.hpsi_flops(1, 352, 36, sh["box"]) * rows,
        costs.hpsi_bytes(1, 352, 36, sh["box"]) * rows,
        costs.load_peaks("TPU v5 lite"))
    assert least["bound"] == "bytes"
    assert got == pytest.approx(
        100.0 * least["seconds"] / doc["hand_computed"]["scope_s"])
    assert sources.trace_scope_roofline(dict(record, trace_raw=None), args) is None
