"""Counts what jax lowers and compiles (copied in idea from
`chip_smoke.CompileLog`; listed in PERF.md for a later PR to merge).

`jax.monitoring` reports every jaxpr lowered to a module (each is then
compiled, or read from the persistent cache: a cache hit) and the seconds
the backend spent compiling. A program lowered inside the measured window
is a compile in the window, whether or not the cache had it."""

import jax

LOWERED = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"


class CompileLog:
    def __init__(self):
        self.programs = self.cache_hits = 0
        self.compile_s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_time)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_time(self, event, duration, **_):
        if event == LOWERED:
            self.programs += 1
        elif event == BACKEND_COMPILE:
            self.compile_s += duration

    def _on_event(self, event, **_):
        if event == CACHE_HIT:
            self.cache_hits += 1

    def snapshot(self):
        return {"programs": self.programs, "cache_hits": self.cache_hits,
                "compile_s": self.compile_s}
