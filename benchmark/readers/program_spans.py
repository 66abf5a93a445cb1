"""The program's own host spans in the device trace: every
`profiler.RecordEvent` of the program is a host event `pt.<name>` on the
trace's clock (a `jax.profiler.TraceAnnotation`), so the train step's host
work can be laid against the chip's busy intervals
(`obs["trace"]["busy_by_chip"]`, `window_ns`; benchmark/trace_reduce.py).

Fields:
  train_step_host_ms      median over the traced steps of `pt.jit_step`
                          less its `pt.jit_step.dispatch` child: the Python
                          the program runs per step outside the runtime
  idle_ms_host_python     chip-idle ms per traced step in gaps of at least
                          MIN_GAP_NS whose middle lies in a `pt.jit_step`
                          but not in its `.dispatch`: the program's Python
                          on the critical path. The rest of the idle is
                          logged by the innermost `pt.*` span over the gap's
                          middle, or "outside the program" (the caller's
                          input pipeline).

None where the run has no device trace, and where the trace holds no
`pt.jit_step` (a program from before the spans existed).
"""
from __future__ import annotations

import statistics
from collections import defaultdict

from benchmark import trace_reduce

PREFIX = "pt."
STEP = PREFIX + "jit_step"
DISPATCH = STEP + ".dispatch"
MIN_GAP_NS = 1e6      # clock skew host/device is 0.05-0.35 ms (PERF.md 7)


def load_spans(path: str) -> list:
    """[(name, start_ns, end_ns, line)] of the `pt.*` host events."""
    import jax

    pd = jax.profiler.ProfileData.from_file(path)
    spans = []
    for plane in pd.planes:
        if trace_reduce.DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            where = (plane.name, line.name)
            spans.extend(
                (e.name, float(e.start_ns),
                 float(e.start_ns) + float(e.duration_ns), where)
                for e in line.events if e.name.startswith(PREFIX))
    return spans


def step_host_ms(spans: list, window_ns) -> list:
    """Per `pt.jit_step` inside the window: its duration less the
    `.dispatch` spans it holds on its own line, in ms."""
    lo, hi = window_ns
    out = []
    for name, s, e, where in spans:
        if name != STEP or s < lo or e > hi:
            continue
        inside = sum(e2 - s2 for n2, s2, e2, w2 in spans
                     if n2 == DISPATCH and w2 == where and s <= s2 < e)
        out.append((e - s - inside) / 1e6)
    return out


def idle_by_span(busy: list, spans: list, window_ns) -> dict:
    """Idle ns of one chip by the innermost `pt.*` span over each gap's
    middle: {"pt.jit_step.rebind": ns, ..., "outside the program": ns,
    "gaps under 1 ms": ns}."""
    lo, hi = window_ns
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    by = defaultdict(float)
    for i in range(0, len(edges), 2):
        s, e = edges[i], edges[i + 1]
        if e <= s:
            continue
        if e - s < MIN_GAP_NS:
            by["gaps under 1 ms"] += e - s
            continue
        mid = (s + e) / 2
        cover = [(b - a, name) for name, a, b, _ in spans if a <= mid < b]
        by[min(cover)[1] if cover else "outside the program"] += e - s
    return dict(by)


def host_python_ns(by: dict) -> float:
    return sum(ns for name, ns in by.items()
               if name.startswith(STEP) and name != DISPATCH)


def read(metric: dict, obs: dict):
    red = obs.get("trace")
    if not red:
        return None
    if "program_spans" not in obs:        # once per run, for both metrics
        obs["program_spans"] = load_spans(
            trace_reduce.find_xplane(obs["trace_dir"]))
    spans = obs["program_spans"]
    if not any(name == STEP for name, *_ in spans):
        return None
    log = obs.get("log") or (lambda *a: None)
    steps = obs.get("traced_steps") or 0
    field = metric["field"]
    if field == "train_step_host_ms":
        per_step = step_host_ms(spans, red["window_ns"])
        if not per_step:
            return None
        log(f"[spans] {len(per_step)} pt.jit_step in the window of "
            f"{red['window_s']:.4f} s: host ms a step median "
            f"{statistics.median(per_step):.3f} max {max(per_step):.3f}; "
            f"step time inside the profiler "
            f"{red['window_s'] / max(steps, 1) * 1e3:.3f} ms "
            f"({steps} steps)")
        return statistics.median(per_step)
    if field == "idle_ms_host_python":
        if not steps:
            return None
        chip = min(red["busy_by_chip"])
        by = idle_by_span(red["busy_by_chip"][chip], spans,
                          red["window_ns"])
        log("[spans] idle ms a step on chip %d by the program's innermost "
            "span: %s" % (chip, "; ".join(
                f"{name} {ns / 1e6 / steps:.3f}"
                for name, ns in sorted(by.items(), key=lambda kv: -kv[1]))
                or "none"))
        return host_python_ns(by) / 1e6 / steps
    raise KeyError(f"program_spans has no field {field!r}")
