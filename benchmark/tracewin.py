"""A short slice of the window inside jax.profiler's trace, with the
benchmark's own host spans (TraceAnnotation) on the trace's clock."""
from __future__ import annotations

import contextlib
import time


class _Window:
    overhead_s = 0.0
    t_begin = 0.0      # time.monotonic() just inside the trace
    t_end = 0.0

    @staticmethod
    def annotate(name: str):
        import jax

        return jax.profiler.TraceAnnotation(name)


@contextlib.contextmanager
def device_trace(trace_dir: str):
    """Trace what runs inside the `with`. `bench.window` brackets it on the
    host's line, so the reduction knows the window's extent."""
    import jax

    w = _Window()
    t0 = time.monotonic()
    # the host's Python call tracer is off: it costs far more than the
    # spans it adds are worth, and the benchmark's own spans are TraceMe's
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    w.t_begin = time.monotonic()
    try:
        with w.annotate("bench.window"):
            yield w
    finally:
        w.t_end = time.monotonic()
        jax.profiler.stop_trace()
        w.overhead_s = (w.t_begin - t0) + (time.monotonic() - w.t_end)
