"""The program's span stream in the metrics registry.

The per-thread stack of open spans lives here: profiler.RecordEvent pushes
and pops `(id, name)` on it, and ``innermost_span()`` names the innermost
open one. On that stream:

- **per-span totals.** The always-installed span sink (profiler/__init__.py
  puts it first): every RecordEvent end adds its duration to
  ``host_span_seconds_total{span}`` and one to ``host_span_calls_total{span}``,
  keyed by the span's base name (before ``:``; ``comm:bucket3`` counts under
  ``comm``). The flight recorder's ring evicts; these do not: a benchmark
  reader finds the set-up phases after the fact, and a /metrics scrape reads
  the pair as a sum and a count (a span's mean time).
- **the caller's time between the program's spans.** The same sink keeps
  each thread's end of its last top-level span (one opened with no other
  open); the next top-level span books the time since to
  ``host_outside_seconds_total{before}``, its own base name. `before=
  "model_init"` is what the caller did between the package's import and the
  model's construction (a TPU client's start-up, in the benchmark);
  `before="jit_step"` rises at the rate a training loop spends outside the
  program between its steps (input pipeline, waits).
- **JAX's compile events, by the span that caused them.** The listener the
  profiler registers with ``jax.monitoring`` books each event's seconds, as
  JAX reports them, to ``jit_compile_seconds_total{phase, span}``: `trace`,
  `lower`, `backend` (the compile, or on a persistent-cache hit its load)
  and `cache_load` (the part of `backend` that was a load), under the
  innermost open span or ``outside the program``. An event opened inside
  another (a jitted function traced inside one being traced, an eager op
  compiled while tracing) is the outer one's time and is not booked again.
  A compile under `jit_step.dispatch` is a step that was not its entry's
  first call and still compiled: the flight recorder notes its `fun_name`.

Pure stdlib like metrics.py: imported while the framework package is still
importing.
"""
from __future__ import annotations

import threading
from typing import Optional

from .metrics import get_registry

__all__ = ["on_span", "open_spans", "innermost_span", "on_compile_start",
           "on_compile_seconds", "OUTSIDE", "COMPILE_EVENTS"]

OUTSIDE = "outside the program"

# JAX's own event names -> the phase they are booked under
COMPILE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_load",
}
# the phases JAX also announces at their start (a scalar event of the same
# name): the ones that can hold one another
_OPENED = ("trace", "lower", "backend")
RECOMPILE_SPAN = "jit_step.dispatch"

_reg = get_registry()
_seconds = _reg.counter(
    "host_span_seconds_total", labels=("span",),
    help="seconds inside RecordEvent spans, by the span's base name")
_calls = _reg.counter(
    "host_span_calls_total", labels=("span",),
    help="RecordEvent spans ended, by the span's base name")
_outside = _reg.counter(
    "host_outside_seconds_total", labels=("before",),
    help="seconds on a thread between the end of a top-level RecordEvent "
         "and the start of the next, by the next one's base name")
_compile = _reg.counter(
    "jit_compile_seconds_total", labels=("phase", "span"),
    help="seconds JAX reports tracing, lowering and compiling (or loading "
         "from the persistent cache), by phase and the innermost open span")
_bound = {}     # base name -> (seconds child, calls child)
_gaps = {}      # base name of a top-level span -> its outside child
_tls = threading.local()


def open_spans() -> list:
    """The calling thread's open spans, outermost first: `[(id, name)]`."""
    s = getattr(_tls, "stack", None)
    if s is None:
        s = _tls.stack = []
    return s


def innermost_span() -> Optional[str]:
    """Base name of the innermost span open on the calling thread, or None."""
    s = getattr(_tls, "stack", None)
    return s[-1][1].split(":", 1)[0] if s else None


def on_span(name, start_ns, end_ns, tid):
    base = name.split(":", 1)[0]
    pair = _bound.get(base)
    if pair is None:
        pair = _bound[base] = (_seconds.labels(span=base),
                               _calls.labels(span=base))
    pair[0].value += (end_ns - start_ns) / 1e9
    pair[1].value += 1
    if getattr(_tls, "stack", None):
        return                      # nested: its parent is still open
    last = getattr(_tls, "last_end", None)
    if last is not None and start_ns > last:
        gap = _gaps.get(base)
        if gap is None:
            gap = _gaps[base] = _outside.labels(before=base)
        gap.value += (start_ns - last) / 1e9
    _tls.last_end = end_ns


def on_compile_start(event, value, **_):
    """JAX's scalar event at the start of a trace, lowering or compile."""
    if COMPILE_EVENTS.get(event) in _OPENED:
        _tls.compiling = getattr(_tls, "compiling", 0) + 1


def on_compile_seconds(event, secs, fun_name=None, **_):
    phase = COMPILE_EVENTS.get(event)
    if phase is None:
        return
    depth = getattr(_tls, "compiling", 0)
    if phase in _OPENED:
        _tls.compiling = max(depth - 1, 0)
        if depth > 1:
            return                  # inside another event: its time already
    elif depth > 1:
        return                      # a load inside another event's compile
    span = innermost_span() or OUTSIDE
    _compile.labels(phase=phase, span=span).value += float(secs)
    if span == RECOMPILE_SPAN and phase == "backend":
        from .flight_recorder import get_flight_recorder

        get_flight_recorder().note("recompile", str(fun_name),
                                   seconds=float(secs))
