"""paddle.profiler — host + device profiling.

Reference: the new-generation profiler (platform/profiler/ — HostTracer
CommonEvents into an event tree, chrome-trace output_logger.h) and the Python
facade python/paddle/profiler/. TPU device-side tracing is jax.profiler
(XPlane → TensorBoard); host events come from RecordEvent plus a per-op
dispatch hook in call_op (the operator.cc:1264 RecordEvent analog).

Events form a parent-linked span TREE (the HostTracer event-tree analog):
each RecordEvent carries an id and the id of the enclosing RecordEvent on
the same thread, so a chrome trace can reconstruct nesting instead of
guessing from time overlap. Every span end is also streamed to registered
span sinks (observability.StepTimer subscribes to build per-step phase
breakdowns), profiler active or not.

The first sink is always installed (observability/host_spans.py): per-span
totals and the caller's time between top-level spans, in the metrics
registry. Beside it, installed once at this module's import, a
``jax.monitoring`` listener books JAX's own compile events to the span open
when they fire (``innermost_span()``): set-up's compile seconds by the
phase of the program that caused them.

One span stream, one clock. RecordEvent is the program's one span type: it
also opens a ``jax.profiler.TraceAnnotation`` named ``pt.<name>``, so any
active jax.profiler session (this module's Profiler(targets=[TPU]), or a
caller's own start_trace) holds the program's spans beside the device's
operations, on the trace's clock. Every host stamp of the program comes
from ``now_ns()`` (time.monotonic_ns: the clock Tracer, FlightRecorder and
EventLog read), so no two stamps of one trace are on different clocks.
"""
from __future__ import annotations

import itertools
import json
import os
import threading
import time
from typing import Callable, List, Optional

import jax.monitoring
from jax.profiler import TraceAnnotation

from ..framework import autograd
from ..observability import host_spans as _host_spans
from ..observability.host_spans import innermost_span, open_spans as _stack

__all__ = [
    "Profiler", "RecordEvent", "ProfilerTarget", "ProfilerState",
    "make_scheduler", "export_chrome_tracing", "load_profiler_result",
    "SummaryView", "add_span_sink", "remove_span_sink", "now_ns",
    "record_span", "innermost_span",
]

# a RecordEvent named `x` is the host event `pt.x` in a jax.profiler trace
TRACE_PREFIX = "pt."

# the program's one host clock, in ns
now_ns = time.monotonic_ns


class ProfilerTarget:
    CPU = "cpu"
    GPU = "gpu"
    TPU = "tpu"
    CUSTOM_DEVICE = "custom_device"


class ProfilerState:
    CLOSED = 0
    READY = 1
    RECORD = 2
    RECORD_AND_RETURN = 3


class SummaryView:
    OpView = "op"
    KernelView = "kernel"
    OverView = "overview"


class _Event:
    __slots__ = ("name", "start_ns", "end_ns", "tid", "kind", "id",
                 "parent_id")

    def __init__(self, name, start_ns, end_ns, tid, kind="host", eid=None,
                 parent_id=None):
        self.name = name
        self.start_ns = start_ns
        self.end_ns = end_ns
        self.tid = tid
        self.kind = kind
        self.id = eid
        self.parent_id = parent_id


_collector_lock = threading.Lock()
_active_profiler: Optional["Profiler"] = None


def _log_profiler_fault(message: str):
    """Record a swallowed-by-design profiler fault to the event log (with
    traceback) instead of dropping it. Import is lazy and itself guarded:
    the profiler must stay usable even if observability is mid-teardown."""
    try:
        from ..observability.events import get_event_log

        import traceback as _tb
        get_event_log().warning("profiler", message,
                                error=_tb.format_exc(limit=4))
    except Exception:   # lint-ok: C003 last-resort guard; event log itself unavailable
        pass

# the per-thread stack of open RecordEvents, `(id, name)`, is
# host_spans.open_spans(): the parent linkage source
_event_ids = itertools.count(1)

# span sinks: called as sink(name, start_ns, end_ns, tid) on EVERY
# RecordEvent end, whether or not a profiler is recording
# (observability.StepTimer registers here). The first is always installed:
# the registry's per-span totals and gaps, which outlive the flight
# recorder's ring.
_span_sinks: List[Callable] = [_host_spans.on_span]


def add_span_sink(sink: Callable) -> Callable:
    _span_sinks.append(sink)
    return sink


def remove_span_sink(sink: Callable):
    try:
        _span_sinks.remove(sink)
    except ValueError:
        pass


def _current_span_id() -> Optional[int]:
    s = _stack()
    return s[-1][0] if s else None


def _on_compile_start(event, value, **kw):
    try:
        _host_spans.on_compile_start(event, value, **kw)
    except Exception:
        _log_profiler_fault(f"compile listener failed for {event!r}")


def _on_compile_seconds(event, secs, **kw):
    # JAX calls this inside its own compile: a fault here would fail the
    # user's jit call, so it is recorded instead (rule C003, as for sinks)
    try:
        _host_spans.on_compile_seconds(event, secs, **kw)
    except Exception:
        _log_profiler_fault(f"compile listener failed for {event!r}")


jax.monitoring.register_scalar_listener(_on_compile_start)
jax.monitoring.register_event_duration_secs_listener(_on_compile_seconds)


def _emit(name, start_ns, end_ns, eid, parent_id):
    """One finished span to the recording profiler and to every sink."""
    tid = threading.get_ident()
    prof = _active_profiler
    if prof is not None and prof._recording:
        prof._add(_Event(name, start_ns, end_ns, tid, "user", eid=eid,
                         parent_id=parent_id))
    for sink in _span_sinks:
        try:
            sink(name, start_ns, end_ns, tid)
        except Exception:
            # a broken sink must not sink the training loop — but the
            # fault is recorded, not swallowed (rule C003)
            _log_profiler_fault(f"span sink failed for {name!r}")


def record_span(name, start_ns, end_ns):
    """A span that already ended, from two `now_ns()` stamps: for work that
    starts before a RecordEvent can exist (the package's own import). It
    reaches the profiler and the sinks, not a device trace."""
    _emit(name, start_ns, end_ns, next(_event_ids), _current_span_id())


class RecordEvent:
    """RAII host-event marker (platform/profiler.cc RecordEvent analog).

    Usable as a context manager or with explicit begin()/end(). Nesting is
    tracked per thread: the event records the id of the RecordEvent it was
    opened inside, forming the span tree.
    """

    def __init__(self, name, event_type=None):
        self.name = name
        self._t0 = None
        self._id = None
        self._parent_id = None
        self._annotation = None

    def begin(self):
        self._id = next(_event_ids)
        self._parent_id = _current_span_id()
        _stack().append((self._id, self.name))
        self._annotation = TraceAnnotation(TRACE_PREFIX + self.name)
        self._annotation.__enter__()
        self._t0 = now_ns()

    def end(self):
        if self._t0 is None:
            return
        t1 = now_ns()
        self._annotation.__exit__(None, None, None)
        s = _stack()
        if s and s[-1][0] == self._id:
            s.pop()
        else:                      # misnested explicit begin()/end(): unwind
            for i, (eid, _) in enumerate(s):
                if eid == self._id:
                    del s[i:]
                    break
        _emit(self.name, self._t0, t1, self._id, self._parent_id)
        self._t0 = None

    def __enter__(self):
        self.begin()
        return self

    def __exit__(self, *exc):
        self.end()


def make_scheduler(*, closed=0, ready=0, record=1, repeat=0, skip_first=0):
    """Step-state schedule (parity: paddle.profiler.make_scheduler)."""
    cycle = closed + ready + record

    def schedule(step):
        if step < skip_first:
            return ProfilerState.CLOSED
        s = step - skip_first
        if repeat and s >= repeat * cycle:
            return ProfilerState.CLOSED
        pos = s % cycle
        if pos < closed:
            return ProfilerState.CLOSED
        if pos < closed + ready:
            return ProfilerState.READY
        if pos == cycle - 1:
            return ProfilerState.RECORD_AND_RETURN
        return ProfilerState.RECORD

    return schedule


def export_chrome_tracing(dir_name, worker_name=None):
    """on_trace_ready callback writing chrome://tracing JSON. Fires once per
    record cycle (Profiler.step sees RECORD_AND_RETURN end a cycle) and at
    stop(); each export names the file by the profiler's export count so a
    later cycle never overwrites an earlier one."""

    def handler(prof):
        os.makedirs(dir_name, exist_ok=True)
        name = worker_name or f"worker_{os.getpid()}"
        n = getattr(prof, "_export_count", 0)
        suffix = f".cycle{n}" if n else ""
        path = os.path.join(dir_name, f"{name}{suffix}.pt.trace.json")
        prof._export_chrome(path)
        return path

    return handler


def load_profiler_result(path):
    with open(path) as f:
        return json.load(f)


class Profiler:
    """paddle.profiler.Profiler.

    targets including ProfilerTarget.TPU additionally drive jax.profiler
    (XPlane trace for TensorBoard — the CUPTI DeviceTracer analog).
    """

    def __init__(self, targets=None, scheduler=None, on_trace_ready=None,
                 timer_only=False, record_shapes=False, profile_memory=False):
        self.targets = list(targets) if targets else [ProfilerTarget.CPU]
        if isinstance(scheduler, tuple):
            # paddle's (start, end) means record for steps in [start, end);
            # going through make_scheduler (rather than a bare lambda) keeps
            # RECORD_AND_RETURN at step end-1, so per-cycle export fires
            start, end = scheduler
            self.scheduler = make_scheduler(closed=start, ready=0,
                                            record=end - start, repeat=1)
        else:
            self.scheduler = scheduler  # callable or None (always record)
        self.on_trace_ready = on_trace_ready
        self.timer_only = timer_only
        self.events: List[_Event] = []
        self.step_num = 0
        self._recording = False
        self._prev_hook = None
        self._prev_active = None
        self._device_trace_dir = None
        self._step_t0 = None
        self._step_times: List[float] = []
        self._export_count = 0

    # -- collection ----------------------------------------------------------
    def _add(self, ev):
        with _collector_lock:
            self.events.append(ev)

    def _op_hook(self, name, t0, t1):
        # op events parent under the innermost open RecordEvent (the
        # operator.cc RecordEvent-inside-RecordEvent tree shape)
        self._add(_Event(name, t0, t1, threading.get_ident(), "op",
                         eid=next(_event_ids),
                         parent_id=_current_span_id()))

    def _state(self):
        if self.scheduler is None:
            return ProfilerState.RECORD
        return self.scheduler(self.step_num)

    # -- lifecycle -----------------------------------------------------------
    def start(self):
        global _active_profiler
        with _collector_lock:
            self._prev_active = _active_profiler
            _active_profiler = self
        self._recording = self._state() in (ProfilerState.RECORD,
                                            ProfilerState.RECORD_AND_RETURN)
        if not self.timer_only:
            self._prev_hook = autograd.set_op_profiler(
                self._op_hook if self._recording else None)
        if ProfilerTarget.TPU in self.targets and not self.timer_only:
            import tempfile

            import jax

            self._device_trace_dir = tempfile.mkdtemp(prefix="paddle_tpu_xplane_")
            # the host's Python call tracer is off: it costs far more than
            # its spans are worth, and the program's own are RecordEvents
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            try:
                jax.profiler.start_trace(self._device_trace_dir,
                                         profiler_options=options)
            except Exception:
                self._device_trace_dir = None
                _log_profiler_fault("device trace start failed")
        self._step_t0 = time.perf_counter()
        return self

    def stop(self):
        global _active_profiler
        if not self.timer_only:
            autograd.set_op_profiler(self._prev_hook)
        if self._device_trace_dir is not None:
            import jax

            try:
                jax.profiler.stop_trace()
            except Exception:
                _log_profiler_fault("device trace stop failed")
        # nested profilers: restore the enclosing one (hook restore above
        # pairs with this — a nested start/stop must leave the outer
        # profiler collecting exactly as before)
        with _collector_lock:
            _active_profiler, self._prev_active = self._prev_active, None
        self._recording = False
        if self.on_trace_ready is not None and \
                (self.events or self._export_count == 0):
            # skip only when per-cycle exports already flushed everything
            self.on_trace_ready(self)
            self._export_count += 1

    def step(self, num_samples=None):
        now = time.perf_counter()
        if self._step_t0 is not None:
            self._step_times.append(now - self._step_t0)
        self._step_t0 = now
        prev_state = self._state()   # state of the step that just finished
        self.step_num += 1
        state = self._state()
        was = self._recording
        self._recording = state in (ProfilerState.RECORD,
                                    ProfilerState.RECORD_AND_RETURN)
        if not self.timer_only and was != self._recording:
            autograd.set_op_profiler(self._op_hook if self._recording
                                     else None)
        if prev_state == ProfilerState.RECORD_AND_RETURN and \
                self.on_trace_ready is not None:
            # a record cycle just ended: hand the collected events out NOW
            # (per-cycle export), then clear for the next cycle; without a
            # handler events accumulate for summary()/export() at stop
            self.on_trace_ready(self)
            self._export_count += 1
            with _collector_lock:
                self.events = []

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.stop()

    # -- reporting -----------------------------------------------------------
    def span_tree(self):
        """Parent-linked event tree: list of root nodes, each
        {"event": _Event, "children": [...]} ordered by start time."""
        nodes = {ev.id: {"event": ev, "children": []}
                 for ev in self.events if ev.id is not None}
        roots = []
        for ev in sorted(self.events, key=lambda e: e.start_ns):
            if ev.id is None:
                continue
            parent = nodes.get(ev.parent_id)
            if parent is not None:
                parent["children"].append(nodes[ev.id])
            else:
                roots.append(nodes[ev.id])
        return roots

    def _export_chrome(self, path):
        events = []
        for ev in self.events:
            rec = {
                "ph": "X", "cat": ev.kind, "name": ev.name,
                "pid": os.getpid(), "tid": ev.tid,
                "ts": ev.start_ns / 1000.0,
                "dur": (ev.end_ns - ev.start_ns) / 1000.0,
            }
            if ev.id is not None:
                rec["args"] = {"id": ev.id, "parent_id": ev.parent_id}
            events.append(rec)
        with open(path, "w") as f:
            json.dump({"traceEvents": events,
                       "displayTimeUnit": "ms"}, f)
        return path

    def export(self, path, format="json"):
        if format == "json":
            return self._export_chrome(path)
        raise ValueError(f"unsupported export format {format!r}")

    def summary(self, sorted_by=None, op_detail=True, thread_sep=False,
                time_unit="ms"):
        """Aggregated per-op table (profiler_statistic analog)."""
        unit = {"s": 1e9, "ms": 1e6, "us": 1e3, "ns": 1.0}[time_unit]
        agg = {}
        for ev in self.events:
            d = agg.setdefault(ev.name, [0, 0.0, float("inf"), 0.0])
            dur = (ev.end_ns - ev.start_ns) / unit
            d[0] += 1
            d[1] += dur
            d[2] = min(d[2], dur)
            d[3] = max(d[3], dur)
        rows = sorted(agg.items(), key=lambda kv: -kv[1][1])
        lines = [f"{'Name':<40}{'Calls':>8}{'Total':>12}{'Min':>10}"
                 f"{'Max':>10}{'Avg':>10}  ({time_unit})"]
        for name, (cnt, tot, mn, mx) in rows:
            lines.append(f"{name[:39]:<40}{cnt:>8}{tot:>12.3f}{mn:>10.3f}"
                         f"{mx:>10.3f}{tot / max(cnt, 1):>10.3f}")
        if self._step_times:
            avg = sum(self._step_times) / len(self._step_times)
            lines.append(f"steps: {len(self._step_times)}, "
                         f"avg step time: {avg * 1e3:.3f} ms")
        table = "\n".join(lines)
        print(table)
        return table

    @property
    def device_trace_dir(self):
        """TensorBoard XPlane directory when TPU tracing was on."""
        return self._device_trace_dir
