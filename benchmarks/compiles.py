"""Counts JAX's compile requests, so a run can show none fell in its window."""

from __future__ import annotations

#: recorded once for every program JAX compiles or loads from its cache
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class CompileCounter:
    def __init__(self) -> None:
        from jax import monitoring

        self.count = 0
        self.seconds = 0.0
        monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, name: str, secs: float, **_) -> None:
        if name == COMPILE_EVENT:
            self.count += 1
            self.seconds += secs

    def reset(self) -> None:
        self.count, self.seconds = 0, 0.0
