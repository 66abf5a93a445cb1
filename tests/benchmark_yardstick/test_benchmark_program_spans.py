"""The three readers of what the PROGRAM names (scopes and kernel names in
the device trace, `pt.*` host spans, the registry's per-span seconds): on
hand-made events with known answers, and on a small trace of the program's
train step recorded on the chip."""
import json
import os

import pytest

from benchmark import manifest as mf, trace_reduce as tr, xplane_meta
from benchmark.readers import (program_phases, program_scopes,
                               program_spans)

MS = 1e6   # ns
FIXTURE = os.path.join(mf.BENCH_DIR, "fixtures", "tiny_scoped.xplane.pb")
CPU_FIXTURE_STEPS = 6   # record_scoped_fixture.py: 3 chunks of 2 steps


def _metric(name):
    with open(os.path.join(mf.BENCH_DIR, "layer_metrics",
                           name + ".json")) as f:
        return json.load(f)


SHARES = ["scope_time_share." + g for g in
          ("attn", "mlp", "embed", "lm_head_loss", "optimizer", "unscoped")]

# ------------------------------------------------------------ hand-made ops
FLASH_DQ = ("%flash_bwd_dq.3 = bf16[12,12,1024,64]{3,2,1,0:T(8,128)(2,1)} "
            "custom-call(bf16[12,12,1024,64]{3,2,1,0} %bitcast.4), "
            "custom_call_target=\"tpu_custom_call\"")
FLASH_FWD = FLASH_DQ.replace("flash_bwd_dq.3", "flash_fwd.1")
OPS = [   # (HLO text, tf_op, ms)
    (FLASH_FWD, "jit(pure_step)/jvp(attn)/flash_fwd/pallas_call:", 10),
    # outside a scope XLA names the call `transpose_jvp_flash_bwd_dq__`:
    # the name on the path still finds it
    (FLASH_DQ.replace("flash_bwd_dq.3", "transpose_jvp_flash_bwd_dq__.3"),
     "jit(pure_step)/transpose(jvp(attn))/flash_bwd_dq/pallas_call:", 20),
    ("%fusion.1 = bf16[8]{0} fusion(bf16[8]{0} %p)",
     "jit(pure_step)/transpose(jvp(mlp))/dot_general:", 15),
    ("%fusion.2 = f32[8]{0} fusion(f32[8]{0} %p)",
     "jit(pure_step)/jvp(embed)/jit(fwd)/gather:", 5),
    ("%fusion.3 = f32[8]{0} fusion(f32[8]{0} %p)",
     "jit(pure_step)/jvp(lm_head)/jit(fwd)/dot_general:", 8),
    ("%fusion.4 = f32[]{:T(128)} fusion(f32[8]{0} %p)",
     "jit(pure_step)/transpose(jvp(loss))/jit(fwd)/reduce_sum:", 4),
    ("%fusion.5 = f32[8]{0} fusion(f32[8]{0} %p)",
     "jit(pure_step)/optimizer/mul:", 6),
    ("%fusion.6 = f32[8]{0} fusion(f32[8]{0} %p)",
     "jit(pure_step)/clip/mul:", 1),
    # a scope no file of the family lists, and a jitted function and a
    # primitive that merely share a group's name: all unscoped
    ("%fusion.7 = f32[8]{0} fusion(f32[8]{0} %p)",
     "jit(pure_step)/jvp(final_norm)/jit(fwd)/mul:", 2),
    ("%fusion.8 = f32[8]{0} fusion(f32[8]{0} %p)",
     "jit(step)/jit(clip)/clamp:", 1),
    ("%fusion.9 = f32[8]{0} fusion(f32[8]{0} %p)",
     "jit(pure_step)/jit(loss)/jit(mlp)/embed:", 1),
    ("%copy.9 = f32[8]{0} copy(f32[8]{0} %p)", None, 3),   # no scope at all
    ("%w = (s32[]{:T(128)}) while((s32[]) %t), body=%b",
     "jit(pure_step)/jvp(attn)/while:", 50),               # a container
]


def _rows(ops=OPS, window_ms=100, groups=None):
    """Two steps' worth of the ops above back to back on one chip."""
    events, meta, t = [], {}, 0.0
    for text, tf_op, ms in ops:
        events.append((text, t, ms * MS))
        meta[text] = {"tf_op": tf_op, "source": "gpt.py:1"} if tf_op \
            else {"hlo_category": "copy"}
        if "while(" not in text:      # a container's body runs inside it
            t += ms * MS
    trace = {"devices": {0: {"ops": events, "async": []}}}
    return program_scopes.op_table(trace, {0: meta}, (0.0, window_ms * MS),
                                   groups or program_scopes.family())


def test_a_scope_is_a_whole_component_of_the_path():
    path = program_scopes.scope_path
    assert path("jit(pure_step)/transpose(jvp(attn))/dot_general:") == (
        "attn",)
    # a transform wraps the one component after it; the rest follow plain
    assert path("jit(pure_step)/transpose(jvp(model))/attn/flash_bwd_dq/"
                "pallas_call:") == ("model", "attn", "flash_bwd_dq")
    assert path("jit(pure_step)/jvp(loss)/jit(fwd)/jit(take_along_axis)/"
                "gather:") == ("loss",)
    assert path("jit(pure_step)/jvp(attn)/bsh,hcj->bscj/dot_general:") == (
        "attn", "bsh,hcj->bscj")
    # no scope: a jitted function's name, the primitive (wrapped by the
    # transform where no scope follows it), a longer name, nothing
    assert path("jit(step)/jit(clip)/clamp:") == ()
    assert path("jit(step)/transpose(jvp(pallas_call)):") == ()
    assert path("jit(pure_step)/jvp()/mul:") == ()
    assert path("jit(pure_step)/transpose(jvp(jit(fwd)))/jit(_var)/mul:") == ()
    assert path("jit(pure_step)/jvp(attnx)/attn:") == ("attnx",)
    assert path(None) == () and path("train_p[0]:") == ()


def test_the_family_is_the_files_that_list_scopes():
    groups = program_scopes.family()
    assert [name for name, _ in groups] == SHARES[:-1]
    assert dict(groups)["scope_time_share.lm_head_loss"] == {"lm_head",
                                                             "loss"}
    book = program_scopes.group_of
    assert book(("mlp", "attn"), groups) == "scope_time_share.attn"
    assert book(("loss", "embed"), groups) == "scope_time_share.embed"
    assert book(("final_norm",), groups) is None and book((), groups) is None


def test_a_new_scope_group_is_one_new_file(tmp_path):
    """A later model's group: its file alone takes its ops out of
    `unscoped`, and out of no group that comes before it."""
    import shutil

    for name in SHARES:
        shutil.copy(os.path.join(mf.BENCH_DIR, "layer_metrics",
                                 name + ".json"), tmp_path)
    norm = dict(_metric(SHARES[0]), name="scope_time_share.norm",
                field={"scopes": ["final_norm"], "order": 9})
    with open(tmp_path / "scope_time_share.norm.json", "w") as f:
        json.dump(norm, f)
    groups = program_scopes.family(str(tmp_path))
    assert groups[-1] == ("scope_time_share.norm", {"final_norm"})
    rows = _rows(groups=groups)
    ms = {n: sum(r["dur_ns"] for r in program_scopes.select(rows, m)) / MS
          for n, m in [(norm["name"], norm)] + [(n, _metric(n))
                                               for n in SHARES]}
    assert ms[norm["name"]] == 2 and ms["scope_time_share.unscoped"] == 5
    assert ms["scope_time_share.attn"] == 30
    # two groups that claim one place in the order: refused, not guessed
    with open(tmp_path / "scope_time_share.twin.json", "w") as f:
        json.dump(dict(norm, name="scope_time_share.twin"), f)
    with pytest.raises(ValueError, match="order"):
        program_scopes.family(str(tmp_path))


def test_each_op_is_booked_once_and_the_shares_add_up_to_busy():
    rows = _rows()
    assert len(rows) == len(OPS) - 1            # the container is left out
    want = {"attn": 30, "mlp": 15, "embed": 5, "lm_head_loss": 12,
            "optimizer": 7,
            "unscoped": 7}  # final_norm, jit(clip), jit(loss)..., the copy
    got = {}
    for name in SHARES:
        picked = program_scopes.select(rows, _metric(name))
        got[name.split(".")[1]] = sum(r["dur_ns"] for r in picked) / MS
    assert got == want
    assert sum(got.values()) == sum(ms for t, _, ms in OPS
                                    if "while(" not in t)


def test_kernels_are_found_by_the_names_the_program_gave_them():
    rows = _rows()
    fwd = program_scopes.select(rows, _metric("flash_fwd_ms_step"))
    bwd = program_scopes.select(rows, _metric("flash_bwd_ms_step"))
    assert [r["dur_ns"] / MS for r in fwd] == [10]
    assert [r["dur_ns"] / MS for r in bwd] == [20]
    assert all(r["mosaic"] for r in fwd + bwd)
    # a fusion that merely mentions the kernel is no kernel
    named_like = [("%fusion.8 = bf16[8]{0} fusion(bf16[8]{0} %flash_fwd.1)",
                   "jit(pure_step)/jvp(attn)/add:", 1)]
    assert program_scopes.select(
        _rows(named_like), _metric("flash_fwd_ms_step")) is None


def test_a_program_that_names_nothing_reports_nothing():
    """The parent commit's trace: ops, tf_op, and none of the scopes."""
    old = [(FLASH_DQ.replace("flash_bwd_dq.3", "jvp__.19"),
            "jit(step)/transpose(jvp(pallas_call)):", 20),
           ("%fusion.1 = bf16[8]{0} fusion(bf16[8]{0} %p)",
            "jit(step)/dot_general:", 15)]
    rows = _rows(old)
    for name in SHARES + ["flash_fwd_ms_step", "flash_bwd_ms_step"]:
        assert program_scopes.select(rows, _metric(name)) is None


def _obs(red, **more):
    lines = []
    return dict(trace=red, log=lambda *a: lines.append(" ".join(map(str, a))),
                **more), lines


def test_the_readers_return_none_without_a_device_trace():
    obs, _ = _obs(None, trace_dir="/nowhere", traced_steps=4)
    for name in SHARES + ["flash_fwd_ms_step", "flash_bwd_ms_step"]:
        assert program_scopes.read(_metric(name), obs) is None
    for name in ("train_step_host_ms", "idle_ms_step.host_python"):
        assert program_spans.read(_metric(name), obs) is None


# ---------------------------------------------------------- hand-made spans
def _spans():
    """Two steps on the host's line, window 0..100 ms.
    step 1: 0-10   args 0-2, dispatch 2-8, rebind 8-10
    step 2: 40-52  args 40-41, dispatch 41-49, rebind 49-52"""
    line = ("/host:CPU", "python3")
    return [("pt.jit_step", 0, 10 * MS, line),
            ("pt.jit_step.args", 0, 2 * MS, line),
            ("pt.jit_step.dispatch", 2 * MS, 8 * MS, line),
            ("pt.jit_step.rebind", 8 * MS, 10 * MS, line),
            ("pt.jit_step", 40 * MS, 52 * MS, line),
            ("pt.jit_step.args", 40 * MS, 41 * MS, line),
            ("pt.jit_step.dispatch", 41 * MS, 49 * MS, line),
            ("pt.jit_step.rebind", 49 * MS, 52 * MS, line),
            # another thread's dispatch is not this step's child
            ("pt.jit_step.dispatch", 3 * MS, 4 * MS, ("/host:CPU", "other"))]


def test_host_ms_is_the_step_less_its_dispatch():
    per_step = program_spans.step_host_ms(_spans(), (0, 100 * MS))
    assert per_step == [4.0, 4.0]
    # a step that straddles the window's edge is not a traced step
    assert program_spans.step_host_ms(_spans(), (5 * MS, 100 * MS)) == [4.0]


def test_idle_gaps_go_to_the_innermost_span_over_their_middle():
    # busy 0-4, 7-8.5, 10-44, 48-49.5, 50.2-60; idle: 4-7 (middle 5.5, in
    # dispatch), 8.5-10 (9.25, in rebind), 44-48 (46, in dispatch), 49.5-50.2
    # (under 1 ms), 60-100 (80, outside every pt.* span)
    busy = [[0, 4 * MS], [7 * MS, 8.5 * MS], [10 * MS, 44 * MS],
            [48 * MS, 49.5 * MS], [50.2 * MS, 60 * MS]]
    by = program_spans.idle_by_span(busy, _spans(), (0, 100 * MS))
    assert {k: round(v / MS, 3) for k, v in by.items()} == {
        "pt.jit_step.dispatch": 7.0, "pt.jit_step.rebind": 1.5,
        "gaps under 1 ms": 0.7, "outside the program": 40.0}
    assert program_spans.host_python_ns(by) == 1.5 * MS
    # a gap over the step's own self time (no child there) is host Python
    line = ("/host:CPU", "python3")
    by = program_spans.idle_by_span(
        [[0, 10 * MS], [13 * MS, 20 * MS]],
        [("pt.jit_step", 9 * MS, 15 * MS, line),
         ("pt.jit_step.dispatch", 13 * MS, 15 * MS, line)], (0, 20 * MS))
    assert by == {"pt.jit_step": 3 * MS}
    assert program_spans.host_python_ns(by) == 3 * MS


def test_the_span_reader_through_its_entry_point():
    red = {"window_ns": (0, 100 * MS), "window_s": 0.1, "chips": 1,
           "busy_by_chip": {0: [[0, 8.5 * MS], [10 * MS, 100 * MS]]}}
    obs, lines = _obs(red, trace_dir="/unused", traced_steps=2,
                      program_spans=_spans())
    assert program_spans.read(_metric("train_step_host_ms"), obs) == 4.0
    assert program_spans.read(_metric("idle_ms_step.host_python"),
                              obs) == 0.75
    text = "\n".join(lines)
    assert "step time inside the profiler 50.000 ms" in text
    assert "pt.jit_step.rebind 0.750" in text
    # a program from before the spans existed: bench.* only
    obs, _ = _obs(red, trace_dir="/unused", traced_steps=2,
                  program_spans=[])
    assert program_spans.read(_metric("train_step_host_ms"), obs) is None


# ------------------------------------------------------------------ phases
def test_set_up_phases_are_the_registrys_span_seconds():
    from paddle_tpu import profiler
    from paddle_tpu.observability import get_registry

    fam = get_registry().get("host_span_seconds_total")
    before = {lb["span"]: c.value for lb, c in fam.items()}
    t = profiler.now_ns()
    profiler.record_span("jit_step.build", t - int(2e9), t)
    profiler.record_span("jit_step.first_call", t - int(3e9), t)
    obs, lines = _obs(None)
    got = program_phases.read(_metric("setup_first_step_s"), obs)
    want = 5.0 + before.get("jit_step.build", 0.0) + before.get(
        "jit_step.first_call", 0.0)
    assert got == pytest.approx(want, abs=1e-6)
    assert "jit_step.build" in lines[0] and "jit_step.first_call" in lines[0]
    none = {"name": "x", "field": {"spans": ["no_such_span"]}}
    assert program_phases.read(none, obs) is None
    for name in ("setup_import_s", "setup_model_init_s",
                 "setup_state_init_s", "setup_first_step_s"):
        m = _metric(name)
        assert m["reader"] == "program_phases" and m["field"]["spans"]


# --------------------------------------------------------- the wire reader
def test_event_metadata_is_read_from_the_wire_format():
    old = os.path.join(mf.BENCH_DIR, "fixtures", "tiny_tpu.xplane.pb")
    planes = {p["name"]: p["event_metadata"]
              for p in xplane_meta.read_planes(old)}
    meta = planes["/device:TPU:0"]
    trace = tr.load(old)
    names = {e[0] for e in trace["devices"][0]["ops"]}
    assert names and names <= set(meta)         # joined by the event's name
    by_op = {tr.parse_op(n)["opcode"]: meta[n] for n in names}
    assert by_op["custom-call"]["tf_op"] == "jit(step)/pallas_call:"
    assert by_op["custom-call"]["source"].endswith("flash_attention.py:172")
    assert by_op["fusion"]["flops"] > 0
    assert "tf_op" not in by_op["copy-start"]   # some ops carry none


def test_a_tpu_plane_without_tf_op_fails_loudly(tmp_path, monkeypatch):
    old = os.path.join(mf.BENCH_DIR, "fixtures", "tiny_tpu.xplane.pb")
    real = xplane_meta.read_planes

    def stripped(path):
        return [{"name": p["name"], "event_metadata": {
            k: {s: v for s, v in st.items() if s != "tf_op"}
            for k, st in p["event_metadata"].items()}} for p in real(path)]

    monkeypatch.setattr(xplane_meta, "read_planes", stripped)
    with pytest.raises(RuntimeError, match="tf_op"):
        program_scopes.load_table(old, (0, 1e18))


# ------------------------------------------------- the trace from the chip
@pytest.fixture(scope="module")
def recorded():
    trace = tr.load(FIXTURE)
    red = tr.reduce(trace)
    obs, lines = _obs(red, trace_dir=os.path.dirname(FIXTURE),
                      traced_steps=CPU_FIXTURE_STEPS)
    obs["program_scopes"] = program_scopes.load_table(FIXTURE,
                                                      red["window_ns"])
    obs["program_spans"] = program_spans.load_spans(FIXTURE)
    return obs, lines, red


def test_recorded_scope_shares_add_up_to_the_busy_share(recorded):
    obs, _, red = recorded
    shares = {n: program_scopes.read(_metric(n), obs) for n in SHARES}
    assert all(v is not None and v >= 0 for v in shares.values()), shares
    busy = 100.0 * red["busy_s"] / red["window_s"]
    assert sum(shares.values()) == pytest.approx(busy, abs=0.5)
    # the blocks' scopes and the optimizer hold most of a step this small
    assert shares["scope_time_share.attn"] > 0
    assert shares["scope_time_share.mlp"] > 0
    assert shares["scope_time_share.optimizer"] > 0
    assert shares["scope_time_share.unscoped"] < 0.25 * busy


def test_recorded_kernel_names_select_what_the_target_finds(recorded):
    obs, _, red = recorded
    fwd = program_scopes.read(_metric("flash_fwd_ms_step"), obs)
    bwd = program_scopes.read(_metric("flash_bwd_ms_step"), obs)
    assert fwd > 0 and bwd > 0
    assert (fwd + bwd) * CPU_FIXTURE_STEPS / 1e3 == pytest.approx(
        red["mosaic_s"], rel=1e-6)
    calls = [r for r in obs["program_scopes"] if r["mosaic"]]
    assert len(calls) == 3 * 2 * CPU_FIXTURE_STEPS  # 3 kernels x 2 layers
    assert {tr.op_family(r["op"]["name"]) for r in calls} == {
        "flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"}
    assert all(r["group"] == "scope_time_share.attn" for r in calls)


def test_recorded_host_spans_lie_on_the_traces_clock(recorded):
    obs, lines, red = recorded
    lo, hi = red["window_ns"]
    steps = [s for s in obs["program_spans"] if s[0] == "pt.jit_step"]
    assert len(steps) == CPU_FIXTURE_STEPS
    assert all(lo <= s[1] and s[2] <= hi for s in steps)
    host = program_spans.read(_metric("train_step_host_ms"), obs)
    assert 0 < host < 50
    idle = program_spans.read(_metric("idle_ms_step.host_python"), obs)
    assert idle is not None and idle >= 0
    by = program_spans.idle_by_span(
        red["busy_by_chip"][0], obs["program_spans"], red["window_ns"])
    # the recorder sleeps 3 ms between chunks, outside every pt.* span
    assert by["outside the program"] >= 3 * 3 * MS
    total_idle = (red["window_s"] - red["busy_s"]) * 1e9
    assert sum(by.values()) == pytest.approx(total_idle, rel=1e-6)


def test_a_cpu_only_trace_gives_nothing(tmp_path):
    """No TPU plane: the harness's reduction is None, and so is every
    number under these names."""
    import jax

    from paddle_tpu.profiler import RecordEvent

    jax.profiler.start_trace(str(tmp_path))
    try:
        with RecordEvent("jit_step"):
            jax.block_until_ready(jax.numpy.ones(8) * 2)
    finally:
        jax.profiler.stop_trace()
    from benchmark import harness

    red = harness.reduce_trace(str(tmp_path), lambda *a: None)
    assert red is None
    obs, _ = _obs(red, trace_dir=str(tmp_path), traced_steps=1)
    for name in SHARES + ["flash_fwd_ms_step", "train_step_host_ms",
                          "idle_ms_step.host_python"]:
        m = _metric(name)
        assert mf.load_reader(mf.load_cell("gpt-125m.train-b12-s1024"),
                              m["reader"]).read(m, obs) is None
    # the spans are in the file all the same, for a trace that has a chip
    spans = program_spans.load_spans(tr.find_xplane(str(tmp_path)))
    assert [s[0] for s in spans] == ["pt.jit_step"]


def test_the_traced_cpu_cell_reports_the_phases_and_no_device_number(
        bench_root, fresh_mesh):
    import time

    from benchmark import harness

    from conftest import PRETEND_TPU

    result = harness.run_cell(
        "gpt-test.train", 2 ** 31 + 11, 3.0, True, time.monotonic(),
        root=bench_root, device=dict(PRETEND_TPU, count=1),
        log=lambda *a: None)
    got = set(result["metrics"])
    assert {"setup_import_s", "setup_model_init_s", "setup_state_init_s",
            "setup_first_step_s"} <= got
    assert not got & set(SHARES + ["flash_fwd_ms_step", "flash_bwd_ms_step",
                                   "train_step_host_ms",
                                   "idle_ms_step.host_python"])
    assert result["metrics"]["setup_first_step_s"]["value"] > 0
