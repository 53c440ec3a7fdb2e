"""Counts what JAX compiles, from ``jax.monitoring`` (copied from
``chip_smoke.py``'s ``CompileMeter``): a program whose backend compile
ran — or was loaded from the persistent cache — is one count. Read at
both ends of the measured window; the difference should be 0."""

from __future__ import annotations

import time


class CompileMeter:
    _BACKEND = "/jax/core/compile/backend_compile_duration"

    def __init__(self) -> None:
        import jax.monitoring as mon
        self.programs = 0
        self.seconds = 0.0
        self.cache_hits = 0
        self.at = []                 # time.monotonic() of each program
        mon.register_event_duration_secs_listener(self._duration)
        mon.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_kw) -> None:
        if event == self._BACKEND:
            self.programs += 1
            self.seconds += secs
            self.at.append(time.monotonic())

    def _event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
