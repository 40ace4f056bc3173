"""What every cell runner needs from JAX."""

from __future__ import annotations

import jax


class CompileCounter:
    """Counts backend compilations (and persistent-cache fetches, which
    JAX reports through the same event): none may happen in the window."""

    def __init__(self):
        self.count = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1
            self.seconds += secs


def start_trace(trace_dir: str):
    """Profiler on, without the Python tracer (it slows the host most)."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
