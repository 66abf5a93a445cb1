"""Per-step time breakdown from nested RecordEvent spans.

The profiler answers "which op is slow"; the StepTimer answers the scaling
question EQuARX-style papers start from: of one training step, how much is
data / forward / backward / optimizer / comm / checkpoint? It subscribes to
the profiler's span stream (every RecordEvent end, profiler active or not),
buckets spans into canonical phases by name, and closes a row per step().

    timer = StepTimer().start()
    for batch in loader:
        with RecordEvent("forward"): ...
        with RecordEvent("backward"): ...
        comm.sync(...)              # grad_comm emits its own "comm" span
        with RecordEvent("optimizer"): ...
        timer.step()
    timer.stop()
    timer.report()                  # formatted table; .steps for raw rows

Attribution is by span name (exact phase name, an alias like "fwd", or a
"phase:detail" prefix). Phase times are inclusive — if a phase span nests
inside another phase span the overlap is counted in both and `other` is
clamped at zero; the built-in instrumentation emits phases as siblings, so
in practice rows add up.
"""
from __future__ import annotations

import time
from typing import Dict, List, Optional, Sequence

__all__ = ["StepTimer", "PHASES", "phase_of", "format_breakdown"]

PHASES = ("data", "forward", "backward", "optimizer", "comm", "checkpoint")

_ALIASES = {
    "fwd": "forward",
    "bwd": "backward",
    "opt": "optimizer",
    "optimizer_step": "optimizer",
    "dataloader": "data",
    "all_reduce": "comm",
    "allreduce": "comm",
    "reduce_scatter": "comm",
    "all_gather": "comm",
    "grad_comm": "comm",
    "save": "checkpoint",
    "ckpt": "checkpoint",
}


def phase_of(name: str, phases: Sequence[str] = PHASES) -> Optional[str]:
    """Canonical phase for a span name, or None if it isn't a phase span."""
    base = name.split(":", 1)[0].split("/", 1)[0]
    if base in phases:
        return base
    alias = _ALIASES.get(base)
    return alias if alias in phases else None


class StepTimer:
    def __init__(self, phases: Sequence[str] = PHASES, registry=None):
        self.phases = tuple(phases)
        self.steps: List[dict] = []     # one closed row per step()
        self._current: Dict[str, float] = {}
        # this step's phase spans as the sink got them: (phase, start_ns,
        # end_ns) on the profiler's clock (time.monotonic_ns)
        self._spans: List[tuple] = []
        self._step_t0 = None
        self._active = False
        self._registry = registry       # optional MetricsRegistry mirror

    # ---------------------------------------------------------- lifecycle
    def start(self):
        from .. import profiler as _prof

        if not self._active:
            _prof.add_span_sink(self._on_span)
            self._active = True
        self._current = {}
        self._spans = []
        self._step_t0 = time.monotonic()
        return self

    def stop(self):
        from .. import profiler as _prof

        if self._active:
            _prof.remove_span_sink(self._on_span)
            self._active = False
        return self

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.stop()
        return False

    # -------------------------------------------------------------- spans
    def _on_span(self, name, start_ns, end_ns, tid):
        ph = phase_of(name, self.phases)
        if ph is not None:
            self._current[ph] = self._current.get(ph, 0.0) + \
                (end_ns - start_ns) / 1e9
            self._spans.append((ph, start_ns, end_ns))

    def step(self) -> dict:
        """Close the current step: record its phase row and reset."""
        now = time.monotonic()
        wall = now - self._step_t0 if self._step_t0 is not None else 0.0
        row = {ph: self._current.get(ph, 0.0) for ph in self.phases}
        row["total"] = wall
        row["other"] = max(0.0, wall - sum(self._current.values()))
        self.steps.append(row)
        if self._registry is not None:
            h = self._registry.histogram("step_time_seconds",
                                         help="wall time per training step")
            h.observe(wall)
        self._trace_step(now - wall, now, step_index=len(self.steps) - 1)
        self._current = {}
        self._spans = []
        self._step_t0 = now
        return row

    def _trace_step(self, t_start: float, t_end: float, step_index: int):
        """Mint a per-step trace so checkpoint/comm/optimizer phases share
        the timeline store (and /traces endpoint) with serve requests.
        Each phase span is the interval its RecordEvent had: the profiler
        and the tracer read one clock."""
        from .tracing import get_tracer

        tracer = get_tracer()
        ctx = tracer.start_trace("train_step", step=step_index)
        if ctx is None:
            return
        tracer.record_span(ctx, "step", t_start=t_start, t_end=t_end,
                           step=step_index)
        for ph, start_ns, end_ns in self._spans:
            tracer.record_span(ctx, ph, t_start=start_ns / 1e9,
                               t_end=end_ns / 1e9, step=step_index)

    # ------------------------------------------------------------ reports
    def breakdown(self) -> dict:
        """Aggregate over recorded steps: per-phase total/mean/share."""
        return aggregate_rows(self.steps, self.phases)

    def report(self) -> str:
        return format_breakdown(self.breakdown())


def aggregate_rows(rows: List[dict], phases: Sequence[str] = PHASES) -> dict:
    n = len(rows)
    total = sum(r.get("total", 0.0) for r in rows)
    out = {"steps": n, "total_seconds": total, "phases": {}}
    for ph in tuple(phases) + ("other",):
        tot = sum(r.get(ph, 0.0) for r in rows)
        out["phases"][ph] = {
            "seconds": tot,
            "mean_seconds": tot / n if n else 0.0,
            "share": tot / total if total else 0.0,
        }
    return out


def format_breakdown(agg: dict, extra: Optional[Dict[str, Dict]] = None) -> str:
    """Render an aggregate as the step-time-breakdown table.

    `extra` optionally maps phase -> {column: value} for joined columns
    (e.g. comm collectives/bytes from the metrics registry)."""
    lines = [f"{'phase':<12}{'total_ms':>12}{'ms/step':>12}{'share':>9}"]
    for ph, row in agg["phases"].items():
        line = (f"{ph:<12}{row['seconds'] * 1e3:>12.2f}"
                f"{row['mean_seconds'] * 1e3:>12.2f}"
                f"{row['share'] * 100:>8.1f}%")
        for k, v in (extra or {}).get(ph, {}).items():
            line += f"  {k}={v}"
        lines.append(line)
    per_step = (agg["total_seconds"] / agg["steps"] * 1e3
                if agg["steps"] else 0.0)
    lines.append(f"{'step total':<12}{agg['total_seconds'] * 1e3:>12.2f}"
                 f"{per_step:>12.2f}"
                 f"{100.0:>8.1f}%  ({agg['steps']} steps)")
    return "\n".join(lines)
