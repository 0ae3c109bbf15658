"""The window rule on a fake clock: jobs start until the seconds have passed,
the job in flight is finished and counted, and the two end-to-end numbers
follow from the records."""

import pytest

from benchmark.harness import window


class FakeClock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t


def test_in_flight_job_is_counted():
    clock = FakeClock()
    durations = [4.0, 4.0, 5.0, 4.0]

    def run_one(i):
        clock.t += durations[i]
        return {"result": {}}

    records, t0 = window.closed_loop(run_one, 1, 10.0, clock)
    # jobs start at 0, 4, 8; the third is in flight at 10 and ends at 13
    assert t0 == 100.0
    assert [r["index"] for r in records] == [0, 1, 2]
    assert [r["t_start"] for r in records] == [0.0, 4.0, 8.0]
    assert records[-1]["t_end"] == 13.0
    assert window.scf_s(records) == 4.0
    assert window.jobs_per_min(records) == pytest.approx(60.0 * 3 / 13.0)


def test_a_job_that_raises_is_a_record_with_an_error():
    clock = FakeClock()

    def run_one(i):
        clock.t += 6.0
        if i == 1:
            raise RuntimeError("boom")
        return {"result": {}}

    records, _ = window.closed_loop(run_one, 1, 10.0, clock)
    assert len(records) == 2
    assert "boom" in records[1]["error"] and "result" not in records[1]


def test_two_clients_share_the_job_counter():
    seen = []

    def run_one(i):
        seen.append(i)
        return {"result": {}}

    calls = {"n": 0}

    def clock():  # every look at the clock moves it by 0.1 s
        calls["n"] += 1
        return 0.1 * calls["n"]

    records, _ = window.closed_loop(run_one, 2, 3.0, clock)
    assert sorted(seen) == list(range(len(records)))
    assert [r["index"] for r in records] == list(range(len(records)))
