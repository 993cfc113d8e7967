"""Counts XLA backend compiles and their seconds (jax.monitoring)."""
from __future__ import annotations

from typing import List


class CompileCounter:
    """Listens for the rest of the process's life; ``compile_s`` holds the
    seconds of every backend compile so far."""

    def __init__(self):
        import jax

        self.compile_s: List[float] = []
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _duration(self, event, secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s.append(secs)
