"""Counts the executables jax builds, through jax's own monitoring
events (a copy of `chip_smoke._CompileMeter`, PR 21). Each one is
either compiled by XLA or loaded from the persistent cache; both are
work that must not happen inside a measured window. Listeners cannot
be removed, so a process makes one meter."""


class CompileMeter:
    def __init__(self):
        import jax.monitoring

        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def _on_event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
