"""Backend compiles, persistent-cache traffic and device memory, as
chip_smoke.py (PR 22) counts them: jax.monitoring listeners and
memory_stats()."""

from __future__ import annotations

BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class Counters:
    """Backend compiles and persistent-cache hits/misses since take()."""

    def __init__(self):
        from jax import monitoring

        self.n = {"compiles": 0, "compile_s": 0.0, "cache_hits": 0,
                  "cache_misses": 0}
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event, dt, **kw):
        if event == BACKEND_COMPILE_EVENT:
            self.n["compiles"] += 1
            self.n["compile_s"] += float(dt)

    def _event(self, event, **kw):
        if event.endswith("/cache_hits"):
            self.n["cache_hits"] += 1
        elif event.endswith("/cache_misses"):
            self.n["cache_misses"] += 1

    def take(self) -> dict:
        out = dict(self.n, compile_s=round(self.n["compile_s"], 3))
        for k in self.n:
            self.n[k] = 0
        return out


def peak_hbm(devices) -> int:
    """Peak bytes in use on the fullest of ``devices`` (0 where the backend
    reports none, as the CPU backend does)."""
    stats = [d.memory_stats() or {} for d in devices]
    return int(max((s.get("peak_bytes_in_use", 0) for s in stats), default=0))
