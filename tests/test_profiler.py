"""Profiler tests (reference analogs: test_profiler.py, test_newprofiler.py)."""
import json
import os

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.profiler as profiler
from paddle_tpu.profiler import (
    Profiler, ProfilerState, ProfilerTarget, RecordEvent, export_chrome_tracing,
    make_scheduler,
)


def test_record_event_and_op_hook():
    net = nn.Linear(8, 8)
    x = paddle.to_tensor(np.ones((2, 8), "float32"))
    with Profiler(targets=[ProfilerTarget.CPU]) as prof:
        with RecordEvent("fwd"):
            y = net(x)
        (y ** 2).sum().backward()
    names = {e.name for e in prof.events}
    assert "fwd" in names
    assert any(n for n in names if n != "fwd")  # op-level events recorded


def test_chrome_trace_export(tmp_path):
    with Profiler() as prof:
        with RecordEvent("work"):
            paddle.to_tensor(np.ones(4, "float32")) * 2
    path = prof.export(str(tmp_path / "trace.json"))
    data = json.load(open(path))
    assert any(ev["name"] == "work" for ev in data["traceEvents"])
    assert all({"ph", "ts", "dur"} <= set(ev) for ev in data["traceEvents"])


def test_on_trace_ready_handler(tmp_path):
    handler = export_chrome_tracing(str(tmp_path / "profdir"))
    with Profiler(on_trace_ready=handler):
        with RecordEvent("e"):
            pass
    files = os.listdir(str(tmp_path / "profdir"))
    assert any(f.endswith(".pt.trace.json") for f in files)


def test_scheduler_states():
    sched = make_scheduler(closed=1, ready=1, record=2, repeat=1)
    states = [sched(i) for i in range(5)]
    assert states[0] == ProfilerState.CLOSED
    assert states[1] == ProfilerState.READY
    assert states[2] == ProfilerState.RECORD
    assert states[3] == ProfilerState.RECORD_AND_RETURN
    assert states[4] == ProfilerState.CLOSED


def test_tuple_scheduler_records_only_window():
    x = paddle.to_tensor(np.ones(4, "float32"))
    prof = Profiler(scheduler=(1, 3))
    prof.start()
    for step in range(4):
        x * 2  # one op per step
        prof.step()
    prof.stop()
    # step-0 op not recorded (state CLOSED at step 0), steps 1-2 recorded
    op_events = [e for e in prof.events if e.kind == "op"]
    assert len(op_events) == 2


def test_summary_table():
    with Profiler() as prof:
        with RecordEvent("alpha"):
            pass
    table = prof.summary()
    assert "alpha" in table
    assert "Calls" in table


def test_scheduler_skip_first():
    sched = make_scheduler(closed=0, ready=0, record=2, skip_first=3)
    assert [sched(i) for i in range(3)] == [ProfilerState.CLOSED] * 3
    assert sched(3) == ProfilerState.RECORD
    assert sched(4) == ProfilerState.RECORD_AND_RETURN
    assert sched(5) == ProfilerState.RECORD  # repeat=0: cycles forever


def test_scheduler_repeat_exhaustion():
    sched = make_scheduler(closed=1, ready=0, record=1, repeat=2)
    # two full cycles of (CLOSED, RECORD_AND_RETURN), then CLOSED forever
    assert [sched(i) for i in range(6)] == [
        ProfilerState.CLOSED, ProfilerState.RECORD_AND_RETURN,
        ProfilerState.CLOSED, ProfilerState.RECORD_AND_RETURN,
        ProfilerState.CLOSED, ProfilerState.CLOSED,
    ]


def test_scheduler_closed_ready_record_cycle():
    sched = make_scheduler(closed=2, ready=1, record=3, repeat=1,
                           skip_first=1)
    states = [sched(i) for i in range(8)]
    assert states == [
        ProfilerState.CLOSED,                 # skip_first
        ProfilerState.CLOSED, ProfilerState.CLOSED,   # closed=2
        ProfilerState.READY,                  # ready=1
        ProfilerState.RECORD, ProfilerState.RECORD,   # record
        ProfilerState.RECORD_AND_RETURN,      # last record slot
        ProfilerState.CLOSED,                 # repeat exhausted
    ]


def test_tuple_scheduler_yields_record_and_return():
    """ISSUE 3 satellite: the (start, end) tuple scheduler goes through
    make_scheduler (no dead-code lambda) and ends the window on
    RECORD_AND_RETURN so per-cycle export fires."""
    prof = Profiler(scheduler=(1, 3))
    states = [prof.scheduler(i) for i in range(4)]
    assert states == [
        ProfilerState.CLOSED, ProfilerState.RECORD,
        ProfilerState.RECORD_AND_RETURN, ProfilerState.CLOSED,
    ]


def test_step_fires_on_trace_ready_per_cycle(tmp_path):
    """ISSUE 3 satellite: when a record cycle ends (RECORD_AND_RETURN),
    on_trace_ready fires with that cycle's events, which are then cleared
    — per-cycle export, not only at stop()."""
    exports = []

    def handler(prof):
        exports.append([e.name for e in prof.events])

    x = paddle.to_tensor(np.ones(4, "float32"))
    prof = Profiler(scheduler=make_scheduler(closed=1, ready=0, record=1,
                                             repeat=2),
                    on_trace_ready=handler)
    prof.start()
    for step in range(4):
        with RecordEvent(f"user_{step}"):
            x * 2
        prof.step()
    prof.stop()
    # cycles end after steps 1 and 3; each export carries only ITS events
    assert len(exports) == 2
    assert any("user_1" == n for n in exports[0])
    assert not any("user_3" == n for n in exports[0])
    assert any("user_3" == n for n in exports[1])
    assert not any("user_1" == n for n in exports[1])


def test_export_chrome_tracing_per_cycle_files(tmp_path):
    handler = export_chrome_tracing(str(tmp_path))
    x = paddle.to_tensor(np.ones(4, "float32"))
    with Profiler(scheduler=(0, 1), on_trace_ready=handler) as prof:
        x * 2
        prof.step()
    files = sorted(os.listdir(tmp_path))
    assert len(files) == 1                 # cycle export; nothing new at stop
    assert files[0].endswith(".pt.trace.json")


def test_nested_profiler_restores_hook_and_active():
    """ISSUE 3 satellite: a nested Profiler start/stop must hand RecordEvent
    collection and the op hook back to the OUTER profiler, not to None."""
    from paddle_tpu.framework import autograd

    x = paddle.to_tensor(np.ones(4, "float32"))
    outer = Profiler().start()
    with RecordEvent("outer_before"):
        x * 2
    inner = Profiler().start()
    with RecordEvent("inner_only"):
        x * 2
    inner.stop()
    with RecordEvent("outer_after"):
        x * 2
    # outer's op hook is live again after inner.stop()
    assert autograd._op_profiler == outer._op_hook
    outer.stop()
    assert autograd._op_profiler is None
    outer_names = {e.name for e in outer.events}
    assert {"outer_before", "outer_after"} <= outer_names
    assert "inner_only" not in outer_names
    assert "inner_only" in {e.name for e in inner.events}


def test_span_tree_nesting():
    with Profiler() as prof:
        with RecordEvent("step"):
            with RecordEvent("forward"):
                with RecordEvent("attn"):
                    pass
            with RecordEvent("backward"):
                pass
        with RecordEvent("solo"):
            pass
    roots = prof.span_tree()
    by_name = {r["event"].name: r for r in roots}
    assert set(by_name) == {"step", "solo"}
    step = by_name["step"]
    kids = [c["event"].name for c in step["children"]]
    assert kids == ["forward", "backward"]
    fwd = step["children"][0]
    assert [c["event"].name for c in fwd["children"]] == ["attn"]
    # chrome export carries the linkage in args
    import json as _json
    import tempfile

    with tempfile.NamedTemporaryFile("r", suffix=".json") as f:
        prof.export(f.name)
        data = _json.load(open(f.name))
    ev = {e["name"]: e for e in data["traceEvents"]}
    assert ev["attn"]["args"]["parent_id"] == ev["forward"]["args"]["id"]
    assert ev["forward"]["args"]["parent_id"] == ev["step"]["args"]["id"]
    assert ev["step"]["args"]["parent_id"] is None


def test_op_events_parent_under_enclosing_span():
    x = paddle.to_tensor(np.ones(4, "float32"))
    with Profiler() as prof:
        with RecordEvent("fwd"):
            x * 2
    ops = [e for e in prof.events if e.kind == "op"]
    fwd = next(e for e in prof.events if e.name == "fwd")
    assert ops and all(o.parent_id == fwd.id for o in ops)


def test_nan_inf_flag_roundtrip():
    import jax

    paddle.set_flags({"FLAGS_check_nan_inf": True})
    assert jax.config.jax_debug_nans
    paddle.set_flags({"FLAGS_check_nan_inf": False})
    flags = paddle.get_flags(["FLAGS_check_nan_inf"])
    assert flags["FLAGS_check_nan_inf"] is False
    jax.config.update("jax_debug_nans", False)


# ---------------------------------------------------------------- ISSUE 25
# one span stream, one clock: RecordEvent in the jax.profiler trace, the
# registry's per-span totals, and the train step's host spans

def _host_events(trace_dir, prefix="pt."):
    """[(name, start_ns, end_ns, (plane, line))] of one jax.profiler trace."""
    import glob

    import jax

    (path,) = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    out = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for line in plane.lines:
            out.extend((e.name, e.start_ns, e.start_ns + e.duration_ns,
                        (plane.name, line.name))
                       for e in line.events if e.name.startswith(prefix))
    return out


def _span_tree_in_a_trace():
    import time

    with RecordEvent("outer"):
        with RecordEvent("inner:detail"):
            time.sleep(0.002)
        with RecordEvent("sibling"):
            time.sleep(0.001)


def _assert_nested(events):
    by = {name: (s, e, where) for name, s, e, where in events}
    assert set(by) == {"pt.outer", "pt.inner:detail", "pt.sibling"}
    o, i, s = by["pt.outer"], by["pt.inner:detail"], by["pt.sibling"]
    assert o[2] == i[2] == s[2]                     # one thread's line
    assert o[0] <= i[0] and i[1] <= s[0] and s[1] <= o[1]
    assert i[1] - i[0] >= 2e6


def test_record_event_tree_is_nested_in_a_jax_profiler_trace(tmp_path):
    import jax

    jax.profiler.start_trace(str(tmp_path))
    try:
        _span_tree_in_a_trace()
    finally:
        jax.profiler.stop_trace()
    _assert_nested(_host_events(str(tmp_path)))


def test_the_programs_own_profiler_writes_the_same_trace():
    """Profiler(targets=[TPU]) starts jax.profiler's trace with the Python
    call tracer off: its file holds the program's spans and no `$file:line
    function` event per Python call."""
    with Profiler(targets=[ProfilerTarget.TPU]) as prof:
        _span_tree_in_a_trace()
        sum(i * i for i in range(2000))             # Python calls to trace
    assert prof.device_trace_dir
    _assert_nested(_host_events(prof.device_trace_dir))
    assert not _host_events(prof.device_trace_dir, prefix="$")
    tree = prof.span_tree()                         # the host tree, as ever
    assert [n["event"].name for n in tree] == ["outer"]
    assert [c["event"].name for c in tree[0]["children"]] == \
        ["inner:detail", "sibling"]


def test_record_event_stamps_are_on_time_monotonic_ns():
    import time

    assert profiler.now_ns is time.monotonic_ns
    t0 = time.monotonic_ns()
    with Profiler() as prof:
        with RecordEvent("stamped"):
            pass
    t1 = time.monotonic_ns()
    (ev,) = [e for e in prof.events if e.name == "stamped"]
    assert t0 <= ev.start_ns <= ev.end_ns <= t1


def test_no_stamp_of_the_program_reads_another_clock():
    """The op hook, the futures' launch/start/end and RecordEvent are
    compared with each other: all come from profiler.now_ns."""
    package = os.path.dirname(os.path.abspath(paddle.__file__))
    found = []
    for d, _, files in os.walk(package):
        for f in files:
            if f.endswith(".py"):
                with open(os.path.join(d, f)) as fh:
                    if "perf_counter_ns" in fh.read():
                        found.append(os.path.join(d, f))
    assert found == []


def test_host_span_totals_count_calls_and_seconds_by_base_name():
    import time

    from paddle_tpu.observability import get_registry

    reg = get_registry()

    def read(family, span):
        fam = reg.get(family)
        return dict((lb["span"], c.value) for lb, c in fam.items()).get(
            span, 0)

    before = {(f, s): read(f, s)
              for f in ("host_span_calls_total", "host_span_seconds_total")
              for s in ("i25comm", "i25comm:bucket3", "i25other")}
    for i in range(3):
        with RecordEvent(f"i25comm:bucket{i}"):
            time.sleep(0.002)
    with RecordEvent("i25comm"):
        pass
    with RecordEvent("i25other"):
        pass

    def grew(family, span):
        return read(family, span) - before[(family, span)]

    assert grew("host_span_calls_total", "i25comm") == 4
    assert grew("host_span_calls_total", "i25other") == 1
    assert read("host_span_calls_total", "i25comm:bucket3") == 0
    assert 0.006 <= grew("host_span_seconds_total", "i25comm") < 0.5
    text = reg.to_prometheus()
    assert 'host_span_calls_total{span="i25comm"}' in text


def test_record_span_is_a_closed_span_for_the_profiler_and_the_sinks():
    seen = []
    sink = profiler.add_span_sink(lambda *a: seen.append(a))
    try:
        with Profiler() as prof:
            with RecordEvent("around"):
                t = profiler.now_ns()
                profiler.record_span("closed", t - 5_000_000, t)
    finally:
        profiler.remove_span_sink(sink)
    ev = {e.name: e for e in prof.events}
    assert ev["closed"].end_ns - ev["closed"].start_ns == 5_000_000
    assert ev["closed"].parent_id == ev["around"].id
    assert [a[0] for a in seen] == ["closed", "around"]


def test_the_packages_import_is_a_span():
    from paddle_tpu.observability import get_registry

    fam = get_registry().get("host_span_calls_total")
    calls = {lb["span"]: c.value for lb, c in fam.items()}
    # a registry reset (other tests do) zeroes it: present either way
    assert "import" in calls


def test_step_timer_traces_each_phase_at_its_own_interval():
    """The sink is handed start and end: the per-step trace's phase spans
    are the intervals the RecordEvents had, in order, not durations hung
    on the step's end."""
    import time

    from paddle_tpu.observability import StepTimer, get_tracer

    tracer = get_tracer()
    timer = StepTimer().start()
    with RecordEvent("forward"):
        time.sleep(0.003)
    with RecordEvent("backward"):
        time.sleep(0.002)
    with RecordEvent("optimizer"):
        time.sleep(0.001)
    row = timer.step()
    timer.stop()
    assert row["forward"] >= 0.003 and row["backward"] >= 0.002
    index = tracer.store.index()["traces"]
    tid = [t["trace_id"] for t in index if t["name"] == "train_step"][-1]
    spans = {s["name"]: s for s in tracer.store.get(tid)["spans"]}
    f, b, o, st = (spans[k] for k in ("forward", "backward", "optimizer",
                                      "step"))
    assert st["t_start"] <= f["t_start"] < f["t_end"] <= b["t_start"] \
        < b["t_end"] <= o["t_start"] < o["t_end"] <= st["t_end"]
    assert f["t_end"] - f["t_start"] >= 0.003


def _gpt_test_step():
    import paddle_tpu.optimizer as opt
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.models import (GPTForCausalLM, GPTPretrainingCriterion,
                                   gpt_presets)

    cfg = gpt_presets("gpt-test")
    model = GPTForCausalLM(cfg, seed=0)
    crit = GPTPretrainingCriterion()
    optim = opt.AdamW(learning_rate=1e-3, parameters=model.parameters())
    step = TrainStep(model, lambda lg, lb: crit(lg, lb), optim)
    ids = np.random.RandomState(0).randint(0, cfg.vocab_size, (2, 33))

    def call():
        return step(inputs=(paddle.to_tensor(ids[:, :-1], dtype="int64"),),
                    labels=(paddle.to_tensor(ids[:, 1:], dtype="int64"),))

    return step, call


def test_train_step_host_spans_over_three_calls():
    step, call = _gpt_test_step()
    with Profiler(timer_only=True) as prof:
        losses = [float(call()) for _ in range(3)]
    assert losses[2] < losses[0]
    spans = [e for e in prof.events if e.name.startswith("jit_step")]
    by_id = {e.id: e for e in spans}
    count = {}
    for e in spans:
        count[e.name] = count.get(e.name, 0) + 1
    assert count == {"jit_step": 3, "jit_step.args": 3, "jit_step.rebind": 3,
                     "jit_step.state_init": 1, "jit_step.build": 1,
                     "jit_step.place": 1, "jit_step.first_call": 1,
                     "jit_step.dispatch": 2}
    steps = [e for e in spans if e.name == "jit_step"]
    assert all(e.parent_id not in by_id for e in steps)
    for e in spans:
        if e.name == "jit_step":
            continue
        parent = by_id[e.parent_id]
        # state_init and build happen inside the argument assembly
        want = "jit_step.args" if e.name in (
            "jit_step.state_init", "jit_step.build") else "jit_step"
        assert parent.name == want, (e.name, parent.name)
        assert parent.start_ns <= e.start_ns and e.end_ns <= parent.end_ns
    first = steps[0].id
    firsts = {e.name for e in spans if e.parent_id == first}
    assert firsts == {"jit_step.args", "jit_step.place",
                      "jit_step.first_call", "jit_step.rebind"}


def test_train_step_makes_its_abstract_signature_once_per_entry():
    import jax

    step, call = _gpt_test_step()
    assert step._last_abstract is None and step.memory_analysis() is None
    call()
    first = step._last_abstract
    call()
    assert step._last_abstract is first             # not rebuilt per call
    leaves = jax.tree_util.tree_leaves(first)
    assert leaves and all(isinstance(v, jax.ShapeDtypeStruct)
                          for v in leaves)
    assert step.memory_analysis(record=False) is not None


def test_reimported_state_drops_the_abstract_signature():
    """Slots another writer left (set_state_dict, the eager path) may have
    other dtypes: the entry's next call makes its signature anew, and goes
    by `first_call` since jit may compile again."""
    step, call = _gpt_test_step()
    call()
    call()
    before = step._last_abstract
    step.optimizer.set_state_dict(step.optimizer.state_dict())
    with Profiler(timer_only=True) as prof:
        call()
        call()
    assert step._last_abstract is not None
    assert step._last_abstract is not before
    names = [e.name for e in prof.events if e.name.startswith("jit_step.")]
    assert names.count("jit_step.state_init") == 1
    assert names.count("jit_step.first_call") == 1
    assert names.count("jit_step.dispatch") == 1
    assert step.memory_analysis(record=False) is not None


def _scope_paths():
    """The names on each op's path in the lowered gpt-test step:
    `jit(pure_step)/transpose(jvp(attn))/dot_general` gives
    {jit, pure_step, transpose, jvp, attn, dot_general}."""
    import re

    step, call = _gpt_test_step()
    call()
    text = step._cache[step._last_ckey].lower(
        *step._last_abstract).as_text(debug_info=True)
    return [set(re.findall(r"[A-Za-z_]\w*", path)) for path in
            set(re.findall(r'loc\("(jit\(pure_step\)[^"]*)"', text))]


def test_the_lowered_train_step_names_its_layers():
    paths = _scope_paths()

    def some_path_holds(*names):
        return any(set(names) <= p for p in paths)

    for scope in ("attn", "mlp", "embed", "lm_head", "loss"):
        assert some_path_holds(scope), scope
        # the backward inherits the scope inside transpose(jvp(...))
        assert some_path_holds("transpose", "jvp", scope), scope
    assert some_path_holds("optimizer")
    assert not some_path_holds("optimizer", "jvp")
    assert not some_path_holds("attn", "mlp")       # siblings, not nested


# --------------------------------------------- compile events and gaps (PR 37)
def _child(family, **labels):
    from paddle_tpu.observability import get_registry

    fam = get_registry().get(family)
    for lb, c in (fam.items() if fam is not None else ()):
        if lb == labels:
            return c.value
    return 0.0


def _compiled(span, phases=("trace", "lower", "backend", "cache_load")):
    return {p: _child("jit_compile_seconds_total", phase=p, span=span)
            for p in phases}


def _grew(before, after):
    return {k: after[k] - before[k] for k in before}


def test_innermost_span_names_the_base_of_the_innermost_open_span():
    assert profiler.innermost_span() is None
    with RecordEvent("i37outer"):
        with RecordEvent("i37inner:bucket2"):
            assert profiler.innermost_span() == "i37inner"
        assert profiler.innermost_span() == "i37outer"
    assert profiler.innermost_span() is None


def test_a_jit_inside_a_span_books_trace_lower_and_backend_under_it():
    import jax
    import jax.numpy as jnp

    before = _compiled("i37x")
    with RecordEvent("i37x"):
        jax.jit(lambda x: jnp.sin(x) * 37)(jnp.ones(37))
    grew = _grew(before, _compiled("i37x"))
    assert grew["trace"] > 0 and grew["lower"] > 0 and grew["backend"] > 0
    assert grew["cache_load"] == 0      # no persistent cache in the tests


def test_a_compile_with_no_span_open_books_outside_the_program():
    import jax
    import jax.numpy as jnp

    outside = "outside the program"
    before = _compiled(outside, ("trace", "backend"))
    jax.jit(lambda x: jnp.cos(x) * 41)(jnp.ones(41))
    grew = _grew(before, _compiled(outside, ("trace", "backend")))
    assert grew["trace"] > 0 and grew["backend"] > 0


def test_a_trace_inside_a_trace_is_booked_once():
    """Tracing an outer jit traces the inner one (and its primitives) inside
    it: JAX reports each, and only the outermost is the wall's."""
    import jax
    import jax.numpy as jnp

    seen = []

    def listener(event, secs, fun_name=None, **_):
        if event == "/jax/core/compile/jaxpr_trace_duration":
            seen.append((fun_name, secs))

    inner = jax.jit(lambda x: jnp.tanh(x) * 43)

    @jax.jit
    def outer(x):
        return inner(x) + inner(2 * x)

    x = jnp.ones(43)
    jax.monitoring.register_event_duration_secs_listener(listener)
    try:
        before = _compiled("i37nest", ("trace",))
        with RecordEvent("i37nest"):
            outer(x)
        grew = _grew(before, _compiled("i37nest", ("trace",)))
    finally:
        jax.monitoring.unregister_event_duration_listener(listener)
    assert len(seen) > 1 and seen[-1][0] == "outer"
    assert grew["trace"] == pytest.approx(seen[-1][1], rel=1e-6)
    assert seen[-1][1] < sum(s for _, s in seen)


def test_a_warm_persistent_cache_books_cache_load(tmp_path):
    import jax
    import jax.numpy as jnp
    from jax._src import compilation_cache

    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    old = {k: getattr(jax.config, k) for k in keys}
    jax.config.update(keys[0], str(tmp_path))
    jax.config.update(keys[1], 0.0)
    jax.config.update(keys[2], -1)
    compilation_cache.reset_cache()
    try:
        def f(x):
            return jnp.exp(x) * 47

        jax.jit(f)(jnp.ones(47))           # compiles and writes the cache
        jax.clear_caches()
        before = _compiled("i37warm")
        with RecordEvent("i37warm"):
            jax.jit(f)(jnp.ones(47))       # traces, lowers, loads
        grew = _grew(before, _compiled("i37warm"))
    finally:
        for k, v in old.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()
    assert grew["trace"] > 0 and grew["lower"] > 0
    assert 0 < grew["cache_load"] <= grew["backend"]


def test_a_module_without_the_cache_mark_compiles_with_no_persistent_cache(
        request):
    """The run's shared compile cache (tests/conftest.py) is a marked
    module's alone: whatever module ran before on this worker, this one
    starts with none, so the `cache_load == 0` of
    test_a_jit_inside_a_span_books_trace_lower_and_backend_under_it holds
    by construction."""
    import jax

    assert request.node.get_closest_marker("shared_compile_cache") is None
    assert not jax.config.jax_compilation_cache_dir


def test_train_steps_first_call_books_under_first_call_and_later_none():
    step, call = _gpt_test_step()
    first = _compiled("jit_step.first_call")
    call()
    grew = _grew(first, _compiled("jit_step.first_call"))
    assert grew["trace"] > 0 and grew["lower"] > 0 and grew["backend"] > 0
    dispatch = _compiled("jit_step.dispatch")
    call()
    call()
    assert _compiled("jit_step.dispatch") == dispatch


def test_a_later_call_that_compiles_books_under_dispatch_and_is_noted():
    """A second input shape is a new entry: its first call. An input jax.jit
    tells apart that the entry's key does not (a weak type) recompiles on a
    later call: under `jit_step.dispatch`, the flight recorder naming it."""
    import jax.numpy as jnp
    from jax._src.lax.lax import _convert_element_type

    from paddle_tpu.observability import get_flight_recorder

    step, call = _gpt_test_step()
    call()
    ids = np.random.RandomState(1).randint(0, 100, (2, 17))
    first = _compiled("jit_step.first_call", ("backend",))
    step(inputs=(paddle.to_tensor(ids[:, :-1], dtype="int64"),),
         labels=(paddle.to_tensor(ids[:, 1:], dtype="int64"),))
    assert _grew(first, _compiled("jit_step.first_call",
                                  ("backend",)))["backend"] > 0

    weak = _convert_element_type(jnp.asarray(ids[:, :-1]), np.dtype("int32"),
                                 weak_type=True)
    rec = get_flight_recorder()
    noted = len(rec.entries(kind="recompile"))
    before = _compiled("jit_step.dispatch", ("trace", "backend"))
    step(inputs=(paddle.Tensor(weak, _internal=True),),
         labels=(paddle.to_tensor(ids[:, 1:], dtype="int64"),))
    grew = _grew(before, _compiled("jit_step.dispatch", ("trace", "backend")))
    assert grew["trace"] > 0 and grew["backend"] > 0
    new = rec.entries(kind="recompile")[noted:]
    assert [e["name"] for e in new] == ["jit(pure_step)"]
    assert new[0]["seconds"] > 0


def test_the_gap_before_a_top_level_span_is_booked_to_it_and_none_nested():
    import time

    def gap(name):
        return _child("host_outside_seconds_total", before=name)

    with RecordEvent("i37first"):
        pass
    time.sleep(0.02)
    with RecordEvent("i37second"):
        time.sleep(0.001)
        with RecordEvent("i37nested"):
            pass
        time.sleep(0.005)
        profiler.record_span("i37recorded", profiler.now_ns(),
                             profiler.now_ns())
    assert 0.02 <= gap("i37second") < 0.5
    assert gap("i37nested") == 0 and gap("i37recorded") == 0
    # a span that ended before the last top-level one ended books nothing
    t = profiler.now_ns()
    with RecordEvent("i37third"):
        pass
    profiler.record_span("i37late", t - 10**9, t)
    assert gap("i37late") == 0


def test_a_compile_listener_that_raises_is_recorded_not_propagated(
        monkeypatch):
    import jax
    import jax.numpy as jnp

    from paddle_tpu.observability import get_event_log
    from paddle_tpu.observability import host_spans

    def broken(*a, **k):
        raise RuntimeError("i37 broken listener")

    log = get_event_log()
    n = len(log.events(kind="profiler"))
    monkeypatch.setattr(host_spans, "on_compile_seconds", broken)
    monkeypatch.setattr(host_spans, "on_compile_start", broken)
    out = jax.jit(lambda x: x * 53)(jnp.ones(53))    # still compiles, runs
    assert float(out[0]) == 53.0
    faults = [r for r in log.events(kind="profiler")[n:]
              if "compile listener failed" in r.get("message", "")]
    assert faults and "i37 broken listener" in faults[0]["error"]
