"""The two ways a job reaches the system under test, chosen by the traffic
file's ``runner``: ``direct`` (load_config + build_job_context + run_scf, as
`sirius-scf` does) and ``engine`` (ServeEngine.submit until DONE, timed from
the client's side). Each ``run(deck)`` blocks until the result is on the host
and returns {"result", "spans", ...}; spans are the program's own records
(obs/spans.py), collected per job."""

from __future__ import annotations

import os
import shutil
import threading
import time


class DirectRunner:
    """Fresh Config and context per job, run_scf on the cell's devices."""

    def __init__(self, devices, traffic: dict, workdir: str):
        self.devices = devices

    def run(self, deck: dict) -> dict:
        import jax

        from sirius_tpu.config.schema import load_config
        from sirius_tpu.dft.scf import run_scf
        from sirius_tpu.obs import spans
        from sirius_tpu.serve.scheduler import build_job_context

        with spans.capture() as cap:
            t0 = time.perf_counter()
            cfg = load_config(deck)
            ctx = build_job_context(cfg, ".")
            ctx_s = time.perf_counter() - t0
            result = run_scf(cfg, ctx=ctx, devices=self.devices)
            jax.block_until_ready(jax.live_arrays())
        return {"result": result, "spans": list(cap.records), "ctx_s": ctx_s}

    def close(self):
        pass


class EngineRunner:
    """One ServeEngine on the cell's devices: no store, no fleet, no journal.
    ``run`` is called from the clients' threads."""

    def __init__(self, devices, traffic: dict, workdir: str):
        from sirius_tpu.obs import spans
        from sirius_tpu.serve import scheduler
        from sirius_tpu.serve.engine import ServeEngine

        self.workdir = workdir
        shutil.rmtree(workdir, ignore_errors=True)
        os.makedirs(workdir, exist_ok=True)
        # a span of the benchmark's own around the scheduler's call into
        # context building, which the program does not span itself
        self._scheduler = scheduler
        self._orig_build = scheduler.build_job_context

        def timed_build(cfg, base_dir="."):
            with spans.span("bench.build_job_context"):
                return self._orig_build(cfg, base_dir)

        scheduler.build_job_context = timed_build
        self._cap_cm = spans.capture()
        self._cap = self._cap_cm.__enter__()
        self._lock = threading.Lock()
        self._taken = 0
        self.engine = ServeEngine(
            num_slices=int(traffic.get("num_slices", 1)), devices=devices,
            workdir=workdir,
            autosave_every=int(traffic.get("autosave_every", 3)))
        self.engine.start()

    def run(self, deck: dict) -> dict:
        job = self.engine.submit(deck)
        job.wait()
        if job.status != "done":
            raise RuntimeError(f"job {job.id} ended {job.status}: {job.error}")
        with self._lock:
            mine = [r for r in self._cap.records
                    if r.get("trace_id") == job.trace_id]
        return {"result": job.result, "spans": mine,
                "started_at": job.started_at, "finished_at": job.finished_at}

    def close(self):
        self.engine.shutdown(wait=True)
        self._cap_cm.__exit__(None, None, None)
        self._scheduler.build_job_context = self._orig_build
        shutil.rmtree(self.workdir, ignore_errors=True)


RUNNERS = {"direct": DirectRunner, "engine": EngineRunner}
