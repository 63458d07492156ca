"""Counts XLA compiles through jax's own monitoring events (a copy of
``chip_smoke.CompileWatch``): the ground truth for 'nothing compiled in
the window', whatever the program's own accounting says."""
from __future__ import annotations

import collections


class CompileWatch:
    def __init__(self):
        import jax

        self.n = collections.Counter()
        self.compile_s = 0.0
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(self._on_dur)

    def _on_event(self, event, **_):
        if event.startswith("/jax/compilation_cache/"):
            self.n[event.rsplit("/", 1)[1]] += 1

    def _on_dur(self, event, secs, **_):
        # one event per executable built OR loaded from the disk cache
        if event == "/jax/core/compile/backend_compile_duration":
            self.n["compiles"] += 1
            self.compile_s += secs

    def mark(self) -> dict:
        out = {k: self.n[k] for k in ("compiles", "cache_hits",
                                      "cache_misses")}
        out["compile_s"] = self.compile_s
        return out

    @staticmethod
    def delta(a: dict, b: dict) -> dict:
        return {k: b[k] - a[k] for k in a}
