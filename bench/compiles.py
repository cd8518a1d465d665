"""Compile events, counted and stamped on the host clock.

A copy of the bring-up smoke's ``CompileClock`` (JAX's monitoring events for
tracing, lowering and backend compiling), kept here so that program changes
cannot move the yardstick, with the time of each backend compile recorded so
that compiles can be counted inside a window.
"""
from __future__ import annotations

import time
from typing import List

import jax

EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
          "/jax/core/compile/jaxpr_to_mlir_module_duration",
          "/jax/core/compile/backend_compile_duration")


class CompileClock:
    """Sums JAX's trace, lowering and backend-compile durations and stamps
    each backend compile with the host clock at its end."""

    def __init__(self):
        self.seconds = 0.0
        self.stamps: List[float] = []
        self.names: List[str] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, fun_name="", **_):
        if event in EVENTS:
            self.seconds += duration
            if event == EVENTS[-1]:
                self.stamps.append(time.perf_counter())
                self.names.append(str(fun_name))

    @property
    def compiles(self) -> int:
        return len(self.stamps)

    def between(self, t0: float, t1: float) -> List[str]:
        """Names of the programs compiled in (t0, t1]."""
        return [n for t, n in zip(self.stamps, self.names) if t0 < t <= t1]

    def close(self) -> None:
        jax.monitoring.unregister_event_duration_listener(self._on)
