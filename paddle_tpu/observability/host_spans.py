"""Per-span totals of the profiler's span stream, in the metrics registry.

The always-installed span sink (profiler/__init__.py puts it first):
every RecordEvent end adds its duration to
``host_span_seconds_total{span}`` and one to ``host_span_calls_total{span}``,
keyed by the span's base name (before ``:``; ``comm:bucket3`` counts under
``comm``). The flight recorder's ring evicts; these do not: a benchmark
reader finds the set-up phases after the fact, and a /metrics scrape reads
the pair as a sum and a count (a span's mean time; docs/ARCHITECTURE.md,
Observability).

Pure stdlib like metrics.py: imported while the framework package is still
importing.
"""
from __future__ import annotations

from .metrics import get_registry

__all__ = ["on_span"]

_reg = get_registry()
_seconds = _reg.counter(
    "host_span_seconds_total", labels=("span",),
    help="seconds inside RecordEvent spans, by the span's base name")
_calls = _reg.counter(
    "host_span_calls_total", labels=("span",),
    help="RecordEvent spans ended, by the span's base name")
_bound = {}     # base name -> (seconds child, calls child)


def on_span(name, start_ns, end_ns, tid):
    base = name.split(":", 1)[0]
    pair = _bound.get(base)
    if pair is None:
        pair = _bound[base] = (_seconds.labels(span=base),
                               _calls.labels(span=base))
    pair[0].value += (end_ns - start_ns) / 1e9
    pair[1].value += 1
