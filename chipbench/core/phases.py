"""Backend compile counter, through ``jax.monitoring`` (copied from the
program's chip smoke test), so a run can show that nothing compiles inside
its measured window."""
from __future__ import annotations


class CompileCounter:
    """Counts executables built (persistent-cache hits among them)."""

    def __init__(self):
        import jax
        self.compiles = 0
        self.cache_hits = 0

        def on_duration(event, _secs, **_kw):
            if event == "/jax/core/compile/backend_compile_duration":
                self.compiles += 1

        def on_event(event, **_kw):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)
