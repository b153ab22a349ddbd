"""The chip, the compile cache, and the compile clock."""

from __future__ import annotations


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def require_tpu(chips: int):
    """The first device, after checking that JAX has ``chips`` TPUs."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devices[0].platform!r});"
                     f" the benchmark runs on a TPU only")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found "
                     f"{len(devices)}")
    return devices[0]


def use_compile_cache() -> str:
    """The program's fixed persistent cache, with every compile kept:
    the search cells' compiles take under a second, below JAX's default
    threshold, and would otherwise be paid again by every run."""
    import jax
    from repro.launch.compile_cache import use_compile_cache as program_cache
    path = program_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def memory_peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest chip."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


class CompileClock:
    """Seconds JAX spent tracing, lowering and compiling (or fetching
    from the persistent cache), how many programs it traced, and how
    many compiles hit that cache."""

    _EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
               "/jax/core/compile/jaxpr_to_mlir_module_duration",
               "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.traces = 0
        self.cache_hits = 0

        def on_duration(event, secs, **_):
            if event in self._EVENTS:
                self.seconds += secs
            if event == self._EVENTS[0]:
                self.traces += 1

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def mark(self) -> tuple[float, int, int]:
        return self.seconds, self.traces, self.cache_hits

    def since(self, mark) -> dict:
        return {"compile_s": self.seconds - mark[0],
                "traces": self.traces - mark[1],
                "cache_hits": self.cache_hits - mark[2]}
