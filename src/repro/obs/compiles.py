"""Count XLA backend compiles through `jax.monitoring` (`repro.obs`).

jax reports the time of every backend compile — a persistent-cache load
included — as the duration event `/jax/core/compile/backend_compile_duration`.
`CompileCounter` listens for it: `count` is always on, and with a tracer
enabled each compile is also a `jax.compile` instant naming the function.
A session reads the count's per-step difference into
`StepReport.compiles`, so a recompile inside a training run shows as a
step that compiled.
"""
from __future__ import annotations

import threading

import jax.monitoring

from repro.obs import tracer as _tracer

BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class CompileCounter:
    """Registers a duration listener on construction; `close()`
    unregisters it."""

    def __init__(self):
        self.count = 0
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **kwargs) -> None:
        if event != BACKEND_COMPILE_EVENT:
            return
        with self._lock:
            self.count += 1
        _tracer.instant("jax.compile", cat="compile",
                        fun=str(kwargs.get("fun_name", "")),
                        seconds=duration)

    def close(self) -> None:
        jax.monitoring.unregister_event_duration_listener(self._on_event)
