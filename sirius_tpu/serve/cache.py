"""Executable cache: share jitted SCF programs across same-shape jobs.

Two levels:

- **Shape buckets** (`bucket_key`): every executable-relevant static shape
  of a deck — band/sphere/FFT/species dimensions plus the trace constants
  the fused step bakes in. Jobs in one bucket compile nothing after the
  first; `control.ngk_pad_quantum` rounds the |G+k| sphere up so decks
  with slightly different spheres coalesce.
- **Executables** (`get`): named jitted callables keyed by their full
  trace signature (dft/fused.py `_trace_signature`), LRU-evicted. For the
  fused step these are the engine's books only: the step is a program of
  the process (dft/fused.py `step_program`, found by the record of its
  trace constants, its tables program *inputs*), built once whoever calls
  run_scf, and `FusedScf` tells this cache which one it runs. A hit here
  says the engine has seen the signature before; whether JAX traced
  anything is `counters.num_fused_step_traces`.

Hit/miss counters are exported through utils/profiler.py (thread-local,
so each job's result reports its own), aggregated on the cache object
(cross-thread, what the engine's stats report), and mirrored into the
obs metrics registry for the /metrics endpoint. The jax.monitoring
backend-compile listener that "a cache hit means zero new executables"
is asserted against (tests/test_serve.py) now lives in obs/metrics.py,
where it also records trace/lowering duration histograms; the names
below stay as re-exports for existing callers.
"""

from __future__ import annotations

import threading
from collections import OrderedDict

from sirius_tpu.obs import metrics as obs_metrics
from sirius_tpu.obs.metrics import (  # noqa: F401  (back-compat re-exports)
    backend_compiles_this_thread,
    backend_compiles_total,
    install_jax_listeners as install_compile_listener,
)
from sirius_tpu.utils.profiler import counters


def bucket_key(cfg, ctx) -> tuple:
    """Shape bucket of a (config, context): every static dimension and
    trace constant that a jitted SCF program depends on. Two decks with
    equal keys run identical executables."""
    p = cfg.parameters
    uc = ctx.unit_cell
    return (
        ctx.gkvec.num_kpoints,
        ctx.num_spins,
        ctx.num_bands,
        ctx.gkvec.ngk_max,
        ctx.gvec.num_gvec,
        ctx.gvec_coarse.num_gvec,
        tuple(ctx.gvec.fft.dims),
        tuple(ctx.fft_coarse.dims),
        ctx.beta.num_beta_total,
        len(uc.atom_types),
        uc.num_atoms,
        0 if ctx.symmetry is None else ctx.symmetry.num_ops,
        round(float(uc.omega), 10),
        cfg.mixer.type,
        int(cfg.mixer.max_history),
        round(float(cfg.mixer.beta), 12),
        tuple(p.xc_functionals),
        ctx.num_mag_dims,
        p.precision_wf,
        str(cfg.control.device_scf),
    )


class ExecutableCache:
    """Thread-safe LRU of named jitted executables + bucket bookkeeping.

    capacity bounds the number of cached executables; evicting one drops
    this cache's reference to the jitted callable. The fused step's
    callable holds no FusedScf and no device array, and the process's own
    table (dft/fused.py) is what keeps it compiled.
    """

    def __init__(self, capacity: int = 32):
        self._lock = threading.RLock()
        self._exe: OrderedDict[tuple, object] = OrderedDict()
        self._buckets: dict[tuple, int] = {}
        self.capacity = int(capacity)
        self.hits = 0          # executable-level get() hits
        self.misses = 0
        self.job_hits = 0      # job/bucket-level (note_job)
        self.job_misses = 0
        install_compile_listener()
        self._m_exec = obs_metrics.REGISTRY.counter(
            "serve_cache_exec_total", "executable cache lookups")
        self._m_job = obs_metrics.REGISTRY.counter(
            "serve_cache_jobs_total", "job-level bucket lookups")

    # -- executable level ------------------------------------------------

    def get(self, sig: tuple, builder):
        """Return the cached executable for ``sig``, building (and
        caching) it with ``builder()`` on a miss."""
        with self._lock:
            if sig in self._exe:
                self._exe.move_to_end(sig)
                self.hits += 1
                counters["serve.cache.exec_hit"] += 1
                self._m_exec.inc(outcome="hit")
                return self._exe[sig]
            self.misses += 1
            counters["serve.cache.exec_miss"] += 1
            self._m_exec.inc(outcome="miss")
            exe = builder()
            self._exe[sig] = exe
            while len(self._exe) > self.capacity:
                self._exe.popitem(last=False)
                counters["serve.cache.evictions"] += 1
            return exe

    # -- job / bucket level ----------------------------------------------

    def note_job(self, key: tuple) -> bool:
        """Record a job landing in shape bucket ``key``; True when the
        bucket is warm (a previous job already compiled for it)."""
        with self._lock:
            warm = key in self._buckets
            self._buckets[key] = self._buckets.get(key, 0) + 1
            if warm:
                self.job_hits += 1
                counters["serve.cache.job_hit"] += 1
                self._m_job.inc(outcome="hit")
            else:
                self.job_misses += 1
                counters["serve.cache.job_miss"] += 1
                self._m_job.inc(outcome="miss")
            return warm

    def stats(self) -> dict:
        with self._lock:
            total = self.job_hits + self.job_misses
            return {
                "exec_hits": self.hits,
                "exec_misses": self.misses,
                "job_hits": self.job_hits,
                "job_misses": self.job_misses,
                "hit_rate": (self.job_hits / total) if total else 0.0,
                "num_buckets": len(self._buckets),
                "num_executables": len(self._exe),
                "backend_compiles": backend_compiles_total(),
            }
