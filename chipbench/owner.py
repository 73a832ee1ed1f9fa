"""Helpers that run in the worker that owns the chip (the LLM replica,
the Train worker): only that process can count its compilations, read
its memory statistics or trace its device."""

from __future__ import annotations

import os

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class CompileCounter:
    """Counts every program this process compiles or loads from the
    persistent cache (both are a jit-cache miss: work that does not
    belong inside a measured window)."""

    def __init__(self, jax):
        from jax import monitoring

        self.count = 0
        monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_kw) -> None:
        if event == _COMPILE_EVENT:
            self.count += 1


def device_info(jax) -> dict:
    devices = jax.devices()
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "pid": os.getpid()}


def memory_peak_bytes(jax, count: int) -> int:
    """Peak on the fullest of the first ``count`` chips (0 where the
    backend keeps no statistics, as on the CPU)."""
    return max(((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                for d in jax.devices()[:count]), default=0)
