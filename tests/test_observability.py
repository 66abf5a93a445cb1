"""Telemetry layer tests (ISSUE 3): MetricsRegistry / EventLog / StepTimer,
and the instrumentation sweep through dispatch, grad_comm, and robustness."""
import json
import os

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu.observability import (
    EventLog, MetricsRegistry, StepTimer, get_event_log, get_registry,
    phase_of,
)
from paddle_tpu.profiler import RecordEvent


# ------------------------------------------------------------- metrics core
def test_counter_gauge_histogram_basics():
    reg = MetricsRegistry()
    reg.counter("c").inc()
    reg.counter("c").inc(4)
    reg.gauge("g").set(2.5)
    reg.gauge("g").dec()
    h = reg.histogram("h", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    h.observe(5.0)
    snap = reg.snapshot()
    assert snap["c"] == 5
    assert snap["g"] == 1.5
    assert snap["h"]["count"] == 3
    assert snap["h"]["sum"] == pytest.approx(5.55)
    # cumulative bucket semantics: <=0.1 holds 1, <=1.0 holds 2
    assert snap["h"]["buckets"] == {"0.1": 1, "1.0": 2}
    assert snap["h"]["min"] == 0.05 and snap["h"]["max"] == 5.0


def test_labelled_counters_and_redeclare():
    reg = MetricsRegistry()
    fam = reg.counter("bytes", labels=("codec",))
    fam.labels(codec="bf16").inc(10)
    fam.labels(codec="int8").inc(1)
    # re-declaration returns the same family; kind clash raises
    assert reg.counter("bytes", labels=("codec",)) is fam
    with pytest.raises(ValueError):
        reg.gauge("bytes")
    with pytest.raises(ValueError):
        fam.labels(wrong="x")
    snap = reg.snapshot()
    assert snap["bytes"] == {"codec=bf16": 10, "codec=int8": 1}
    # bind() gives the raw child and survives reset() (reset in place)
    child = fam.bind(codec="bf16")
    reg.reset()
    child.inc(3)
    assert reg.snapshot()["bytes"]["codec=bf16"] == 3
    assert reg.snapshot()["bytes"]["codec=int8"] == 0


def test_prometheus_exposition_and_jsonl(tmp_path):
    reg = MetricsRegistry()
    reg.counter("reqs", help="requests").inc(7)
    reg.counter("by_op", labels=("op",)).labels(op="all_reduce").inc(2)
    reg.histogram("lat", buckets=(0.1, 1.0)).observe(0.5)
    text = reg.to_prometheus()
    assert "# HELP reqs requests" in text
    assert "# TYPE reqs counter" in text
    assert "reqs 7" in text
    assert 'by_op{op="all_reduce"} 2' in text
    assert 'lat_bucket{le="0.1"} 0' in text
    assert 'lat_bucket{le="1.0"} 1' in text
    assert 'lat_bucket{le="+Inf"} 1' in text
    assert "lat_count 1" in text
    p = tmp_path / "m.jsonl"
    reg.export_jsonl(str(p))
    reg.export_jsonl(str(p))
    lines = [json.loads(l) for l in open(p)]
    assert len(lines) == 2
    assert lines[0]["metrics"]["reqs"] == 7
    assert lines[0]["time"] <= lines[1]["time"]


# --------------------------------------------------------------- event log
def test_event_log_records_and_filters(tmp_path):
    log = EventLog(path=str(tmp_path / "ev.jsonl"), rank=3)
    log.info("checkpoint", "committed", step=5)
    log.warning("nan_guard", "trip", step=6)
    log.error("watchdog", "stall")
    with pytest.raises(ValueError):
        log.log("k", severity="fatal")
    assert len(log) == 3
    assert [e["kind"] for e in log.events(min_severity="warning")] == \
        ["nan_guard", "watchdog"]
    assert log.events(kind="checkpoint")[0]["step"] == 5
    recs = [json.loads(l) for l in open(tmp_path / "ev.jsonl")]
    assert len(recs) == 3
    assert all(r["rank"] == 3 for r in recs)
    # both clocks present; monotonic is non-decreasing across records
    assert all("time" in r and "mono" in r for r in recs)
    assert recs[0]["mono"] <= recs[1]["mono"] <= recs[2]["mono"]
    log.close()


def test_event_log_ring_bound_and_export(tmp_path):
    log = EventLog(max_memory=4)
    for i in range(7):
        log.info("k", i=i)
    assert len(log) == 4
    assert log.dropped == 3
    assert [e["i"] for e in log.tail(2)] == [5, 6]
    out = tmp_path / "dump.jsonl"
    log.export(str(out))
    assert len(open(out).read().splitlines()) == 4


# -------------------------------------------------------------- step timer
def test_step_timer_phase_attribution():
    assert phase_of("forward") == "forward"
    assert phase_of("comm:bucket0") == "comm"
    assert phase_of("fwd") == "forward"
    assert phase_of("matmul") is None
    t = StepTimer().start()
    try:
        with RecordEvent("forward"):
            pass
        with RecordEvent("comm"):
            pass
        row = t.step()
        with RecordEvent("backward"):
            pass
        row2 = t.step()
    finally:
        t.stop()
    assert row["forward"] > 0 and row["comm"] > 0 and row["backward"] == 0
    assert row2["backward"] > 0 and row2["forward"] == 0
    agg = t.breakdown()
    assert agg["steps"] == 2
    assert agg["phases"]["forward"]["seconds"] == pytest.approx(
        row["forward"])
    assert "forward" in t.report()
    # sinks are removed on stop: spans after stop() do not accumulate
    with RecordEvent("forward"):
        pass
    assert len(t.steps) == 2


# --------------------------------------------------- instrumentation sweep
def test_dispatch_and_trace_cache_counters():
    from paddle_tpu.framework.autograd import clear_op_cache

    reg = get_registry()
    x = paddle.to_tensor(np.ones(8, "float32"))
    clear_op_cache()  # deterministic hit/miss pattern below
    d0 = reg.counter("eager_dispatch_total").value
    h0 = reg.counter("trace_cache_hits_total").value
    m0 = reg.counter("trace_cache_misses_total").value
    u0 = reg.counter("trace_cache_uncacheable_total").value
    y = (x * 3.0).sum()
    y2 = (x * 3.0).sum()  # same mul again: a cache hit
    assert reg.counter("eager_dispatch_total").value - d0 == 4
    # mul: 1 miss then 1 hit; sum dispatches through a dynamic closure
    # (no cache key) so both runs count as uncacheable, not misses
    assert reg.counter("trace_cache_misses_total").value - m0 == 1
    assert reg.counter("trace_cache_hits_total").value - h0 == 1
    assert reg.counter("trace_cache_uncacheable_total").value - u0 == 2


def test_grad_comm_sync_records_metrics():
    from paddle_tpu.distributed import grad_comm
    from paddle_tpu.framework.tensor import Tensor

    reg = get_registry()
    lin = nn.Linear(16, 16)
    for p in lin.parameters():
        p.grad = Tensor(np.ones(p.shape, "float32"))
    cfg = grad_comm.GradCommConfig(codec="bf16")
    comm = grad_comm.GradCommunicator(cfg)
    fam_c = reg.counter("grad_comm_collectives_total",
                        labels=("codec", "path"))
    fam_b = reg.counter("grad_comm_bytes_total", labels=("codec", "path"))
    c0 = fam_c.labels(codec="bf16", path="eager").value
    b0 = fam_b.labels(codec="bf16", path="eager").value
    f0 = reg.histogram("grad_comm_bucket_fill_ratio").bind().count
    comm.sync(lin.parameters(), world=2)
    assert fam_c.labels(codec="bf16", path="eager").value - c0 == \
        comm.stats["collectives"] > 0
    assert fam_b.labels(codec="bf16", path="eager").value - b0 == \
        comm.stats["comm_bytes"] > 0
    # one fill-ratio observation per bucket
    assert reg.histogram("grad_comm_bucket_fill_ratio").bind().count - f0 \
        == comm.stats["n_buckets"]


def test_collective_issue_counter():
    from paddle_tpu.distributed import collective as coll

    reg = get_registry()
    fam = reg.counter("collectives_total", labels=("op",))
    n0 = fam.labels(op="all_reduce").value
    t = paddle.to_tensor(np.ones(4, "float32"))
    coll.all_reduce(t)
    coll.all_reduce(t)
    assert fam.labels(op="all_reduce").value - n0 == 2


def test_checkpoint_save_histogram_and_events(tmp_path):
    from paddle_tpu.robustness.checkpoint import CheckpointManager

    reg = get_registry()
    h = reg.histogram("checkpoint_save_seconds").bind()
    s0, n0 = reg.counter("checkpoint_saves_total").value, h.count
    log = get_event_log()
    log.clear()
    mgr = CheckpointManager(str(tmp_path / "ck"), keep_last_n=2)
    mgr.save({"w": np.ones(4)}, 1)
    mgr.save({"w": np.ones(4) * 2}, 2)
    assert reg.counter("checkpoint_saves_total").value - s0 == 2
    assert h.count - n0 == 2
    evs = log.events(kind="checkpoint")
    assert len(evs) == 2
    assert evs[-1]["step"] == 2 and evs[-1]["severity"] == "info"
    assert evs[-1]["seconds"] > 0
    # load timing lands in the load histogram
    l0 = reg.histogram("checkpoint_load_seconds").bind().count
    mgr.load_latest()
    assert reg.histogram("checkpoint_load_seconds").bind().count == l0 + 1


def test_checkpoint_corrupt_skip_counter(tmp_path):
    from paddle_tpu.robustness.checkpoint import (
        MANIFEST_NAME, CheckpointManager,
    )

    reg = get_registry()
    mgr = CheckpointManager(str(tmp_path / "ck"), keep_last_n=5)
    mgr.save({"w": 1}, 1)
    mgr.save({"w": 2}, 2)
    # tear the newest checkpoint's payload
    with open(os.path.join(mgr.step_path(2), "state.pdparams"), "wb") as f:
        f.write(b"torn")
    c0 = reg.counter("checkpoint_corrupt_skipped_total").value
    get_event_log().clear()
    state, step, _ = mgr.load_latest()
    assert step == 1
    assert reg.counter("checkpoint_corrupt_skipped_total").value == c0 + 1
    warn = get_event_log().events(kind="checkpoint", severity="warning")
    assert warn and warn[0]["step"] == 2


def test_checkpoint_retry_counter(tmp_path):
    from paddle_tpu.robustness.checkpoint import CheckpointManager
    from paddle_tpu.robustness.fault_injection import FaultyFS

    reg = get_registry()
    r0 = reg.counter("checkpoint_retries_total").value
    fs = FaultyFS(transient_oserrors=1)  # first write flakes once
    mgr = CheckpointManager(str(tmp_path / "ck"), fs=fs, retries=3,
                            backoff=0.001)
    mgr.save({"w": 1}, 1)
    assert reg.counter("checkpoint_retries_total").value > r0


def test_nan_guard_trip_metrics_and_events():
    from paddle_tpu.robustness.watchdog import NanGuard

    reg = get_registry()
    fam = reg.counter("nan_guard_trips_total", labels=("action",))
    t0 = fam.labels(action="skip_step").value
    get_event_log().clear()
    g = NanGuard(policy="skip_step", max_consecutive_bad=0)
    assert g.check(loss=1.0) == "ok"
    assert g.check(loss=float("nan")) == "skip_step"
    assert g.check(loss=1.0, scaler_skipped=True) == "ok"
    assert fam.labels(action="skip_step").value - t0 == 1
    evs = get_event_log().events(kind="nan_guard")
    assert len(evs) == 1 and evs[0]["severity"] == "warning"
    assert evs[0]["action"] == "skip_step"


def test_hang_detector_heartbeat_counter_and_event():
    import time as _time

    from paddle_tpu.robustness.watchdog import HangDetector

    reg = get_registry()
    b0 = reg.counter("watchdog_heartbeats_total").value
    h0 = reg.counter("watchdog_hangs_total").value
    get_event_log().clear()
    hits = []
    hd = HangDetector(timeout=0.05, poll_interval=0.01,
                      on_hang=lambda age: hits.append(age))
    with hd:
        hd.beat()
        deadline = _time.time() + 2.0
        while not hits and _time.time() < deadline:
            _time.sleep(0.01)
    assert hits, "hang never detected"
    assert reg.counter("watchdog_heartbeats_total").value - b0 >= 2
    assert reg.counter("watchdog_hangs_total").value - h0 == 1
    evs = get_event_log().events(kind="watchdog")
    assert evs and evs[0]["severity"] == "error"
    assert evs[0]["stall_age_seconds"] >= 0.05


# ------------------------------------------------- rpc-profiler flag wiring
def test_flags_enable_rpc_profiler_streams_collective_events():
    from paddle_tpu.framework import flags as flags_mod
    from paddle_tpu.observability import rpc_profiler_enabled

    flags_mod._compat_warned.discard("FLAGS_enable_rpc_profiler")
    get_event_log().clear()
    with pytest.warns(UserWarning, match="FLAGS_enable_rpc_profiler"):
        paddle.set_flags({"FLAGS_enable_rpc_profiler": True})
    try:
        assert rpc_profiler_enabled()
        t = paddle.to_tensor(np.ones(4, "float32"))
        paddle.distributed.all_reduce(t)
        evs = get_event_log().events(kind="collective")
        assert evs and evs[0]["op"] == "all_reduce"
        assert evs[0]["bytes"] == 16
    finally:
        paddle.set_flags({"FLAGS_enable_rpc_profiler": False})
    assert not rpc_profiler_enabled()
    get_event_log().clear()
    paddle.distributed.all_reduce(paddle.to_tensor(np.ones(4, "float32")))
    assert not get_event_log().events(kind="collective")


# ----------------------------------------------------------- hapi callback
def test_metrics_callback_via_fit(tmp_path):
    from paddle_tpu.hapi.callbacks import MetricsCallback
    from paddle_tpu.io import Dataset

    class DS(Dataset):
        def __len__(self):
            return 8

        def __getitem__(self, i):
            return (np.ones(4, "float32") * (i % 3),
                    np.array([1.0], "float32"))

    net = nn.Linear(4, 1)
    model = paddle.Model(net)
    model.prepare(
        optimizer=paddle.optimizer.SGD(learning_rate=0.01,
                                       parameters=net.parameters()),
        loss=lambda out, y: ((out - y) ** 2).mean(), jit_compile=False)
    mc = MetricsCallback(log_dir=str(tmp_path), freq=2)
    model.fit(DS(), batch_size=2, epochs=1, verbose=0, callbacks=[mc])
    lines = [json.loads(l)
             for l in open(tmp_path / "metrics.jsonl").read().splitlines()]
    assert len(lines) == 2           # 4 steps / freq 2
    rec = lines[-1]
    assert rec["step"] == 4
    bd = rec["step_breakdown"]
    assert bd["steps"] == 2
    # the eager train path tags forward/backward/optimizer spans; fit tags
    # the batch fetch as "data"
    for ph in ("forward", "backward", "optimizer", "data"):
        assert bd["phases"][ph]["seconds"] > 0, ph
    assert rec["metrics"]["eager_dispatch_total"] > 0
    assert mc.last_snapshot is rec or mc.last_snapshot == rec


# ============================================================ ISSUE 6 plane
# Distributed telemetry: cross-rank aggregation, flight recorder, memory
# accounting, live exposition, exposition-format fixes, quantiles.

def _emulate_ranks(n_ranks, perturb=None):
    """gather_fn factory: clone the local payload into an n-rank world
    (the single-process stand-in for the all_gather exchange, mirroring
    how chaos tests emulate ReplicaGuard's reduce_fn)."""
    import copy

    def gather(payload):
        outs = []
        for r in range(n_ranks):
            p = copy.deepcopy(payload)
            p["rank"] = r
            if perturb:
                perturb(r, p)
            outs.append(p)
        return outs

    return gather


# ------------------------------------------------------- exposition format
def test_prometheus_label_value_escaping_round_trip():
    """Satellite 1: backslash, double-quote, and newline in label values
    must be escaped per exposition format 0.0.4 — and survive a strict
    parse back to the original value."""
    from paddle_tpu.observability import parse_prometheus_text

    reg = MetricsRegistry()
    nasty = 'he said "hi"\\path\nline2'
    reg.counter("esc_total", labels=("msg",)).labels(msg=nasty).inc(2)
    text = reg.to_prometheus()
    assert '\\"hi\\"' in text and "\\\\path" in text and "\\n" in text
    # no raw newline may survive inside a sample line
    sample_lines = [l for l in text.splitlines() if l.startswith("esc_total")]
    assert len(sample_lines) == 1
    fams = parse_prometheus_text(text)
    (name, labels, value), = fams["esc_total"]["samples"]
    assert labels["msg"] == nasty
    assert value == 2.0


def test_prometheus_help_escaping():
    reg = MetricsRegistry()
    reg.counter("h_total", help="line1\nline2 \\ backslash").inc()
    text = reg.to_prometheus()
    help_lines = [l for l in text.splitlines() if l.startswith("# HELP")]
    assert help_lines == ["# HELP h_total line1\\nline2 \\\\ backslash"]


def test_strict_parser_rejects_malformed():
    from paddle_tpu.observability import parse_prometheus_text

    ok = parse_prometheus_text('a_total{x="1"} 3\n')
    assert ok["a_total"]["samples"] == [("a_total", {"x": "1"}, 3.0)]
    for bad in (
        'a_total{x=unquoted} 1\n',          # unquoted label value
        'a_total{x="v\\q"} 1\n',            # invalid escape
        'a_total{x="v"} notanumber\n',      # non-numeric value
        '# TYPE a_total counter\n# TYPE a_total gauge\na_total 1\n',  # re-TYPE
        'a_total{x="dangling\\"} 1 2 3\n',  # trailing junk
    ):
        with pytest.raises(ValueError):
            parse_prometheus_text(bad)


def test_histogram_exemplar_round_trip():
    """ISSUE 18: observe(value, exemplar=trace_id) pins the trace id to
    the tightest covering bucket; the exposition line carries an
    OpenMetrics-style `# {trace_id="..."} value` tail, the strict parser
    splits it back out, and plain samples stay 3-tuples throughout."""
    from paddle_tpu.observability import parse_prometheus_text

    reg = MetricsRegistry()
    h = reg.histogram("ex_lat_ms", buckets=(10.0, 100.0))
    h.observe(5.0, exemplar="t1-000001")
    h.observe(50.0, exemplar="t1-000002")
    h.observe(5000.0, exemplar="t1-000003")      # beyond last bound: +Inf
    assert h.get()["exemplars"] == {
        "10.0": {"value": 5.0, "trace_id": "t1-000001"},
        "100.0": {"value": 50.0, "trace_id": "t1-000002"},
        "+Inf": {"value": 5000.0, "trace_id": "t1-000003"},
    }
    # last-exemplar-wins per bucket; observes without exemplar keep it
    h.observe(7.0, exemplar="t1-000009")
    h.observe(8.0)
    assert h.get()["exemplars"]["10.0"]["trace_id"] == "t1-000009"

    text = reg.to_prometheus()
    tails = [l for l in text.splitlines() if " # {" in l]
    assert len(tails) == 3 and all("_bucket{" in l for l in tails)
    fams = parse_prometheus_text(text)          # STRICT parse still passes
    fam = fams["ex_lat_ms"]
    assert all(len(s) == 3 for s in fam["samples"])  # samples undisturbed
    by_le = {labels["le"]: (ex, v) for name, labels, ex, v
             in fam["exemplars"]}
    assert by_le["10.0"] == ({"trace_id": "t1-000009"}, 7.0)
    assert by_le["+Inf"] == ({"trace_id": "t1-000003"}, 5000.0)
    # exemplars are a render-layer detail: the cross-rank merge contract
    # (typed_snapshot) never carries them
    assert "exemplars" not in str(reg.typed_snapshot())


def test_redeclare_label_name_mismatch_raises():
    """Satellite 2: re-declaring an existing family with different label
    NAMES must raise instead of silently handing back a family whose
    .labels() rejects every increment."""
    reg = MetricsRegistry()
    fam = reg.counter("relabel_total", labels=("op",))
    assert reg.counter("relabel_total", labels=("op",)) is fam  # idempotent
    with pytest.raises(ValueError, match="labels"):
        reg.counter("relabel_total", labels=("op", "rank"))
    with pytest.raises(ValueError, match="labels"):
        reg.counter("relabel_total")  # unlabelled redeclare also a mismatch
    with pytest.raises(ValueError, match="registered as"):
        reg.gauge("relabel_total", labels=("op",))  # kind clash still first


# ---------------------------------------------------------------- quantiles
def test_histogram_quantiles():
    """Satellite 3: cumulative-bucket quantile estimation, surfaced as
    p50/p95/p99 in get()/snapshot."""
    reg = MetricsRegistry()
    h = reg.histogram("lat_s", buckets=(1.0, 2.0, 4.0, 8.0))
    assert h.quantile(0.5) is None  # empty
    for v in (0.5, 1.5, 3.0, 6.0):
        h.observe(v)
    # target=2 falls in the (1,2] bucket: lo=1, interpolates to exactly 2
    assert h.quantile(0.5) == pytest.approx(2.0)
    # the top quantiles land in the last populated bucket, clamped to the
    # observed max — never a value no observation ever had
    assert h.quantile(0.99) <= 6.0
    assert h.quantile(0.0) >= 0.5
    with pytest.raises(ValueError):
        h.quantile(1.5)
    snap = reg.snapshot()["lat_s"]
    for q in ("p50", "p95", "p99"):
        assert snap[q] is not None
    assert snap["p50"] <= snap["p95"] <= snap["p99"]


def test_histogram_quantile_all_beyond_last_bound():
    reg = MetricsRegistry()
    h = reg.histogram("big_s", buckets=(0.1,))
    h.observe(5.0)
    h.observe(7.0)
    # everything in the +Inf bucket: best estimate is the observed max
    assert h.quantile(0.9) == 7.0


# ----------------------------------------------------- cross-rank aggregation
def test_merge_typed_snapshots_rules():
    """Tentpole (a): counters sum, gauges min/max/mean, histogram buckets
    add element-wise; families missing on a rank merge over the ranks that
    have them."""
    from paddle_tpu.observability import merge_typed_snapshots

    regs = [MetricsRegistry() for _ in range(3)]
    for i, reg in enumerate(regs):
        reg.counter("c_total", labels=("op",)).labels(op="ar").inc(10 * (i + 1))
        reg.gauge("g").set(float(i))
        h = reg.histogram("h_s", buckets=(1.0, 2.0))
        h.observe(0.5 + i)  # 0.5, 1.5, 2.5
    regs[2].counter("only_r2_total").inc(7)

    merged = merge_typed_snapshots([r.typed_snapshot() for r in regs])
    assert merged["c_total"]["children"]["op=ar"] == 60
    g = merged["g"]["children"][""]
    assert g == {"min": 0.0, "max": 2.0, "mean": 1.0}
    h = merged["h_s"]["children"][""]
    assert h["count"] == 3 and h["sum"] == pytest.approx(4.5)
    assert h["bucket_counts"] == [1, 2]  # cumulative: <=1 holds 1, <=2 holds 2
    assert h["min"] == 0.5 and h["max"] == 2.5
    assert h["p50"] is not None
    # partial family: merged over the ranks that have it, count recorded
    assert merged["only_r2_total"]["children"][""] == 7
    assert merged["only_r2_total"]["ranks"] == 1


def test_merge_histogram_bound_mismatch_degrades():
    """Version-skewed bucket layouts must not throw inside telemetry —
    count/sum still merge, buckets drop."""
    from paddle_tpu.observability.aggregate import _merge_histogram

    a = {"bounds": [1.0], "bucket_counts": [1], "count": 1, "sum": 0.5,
         "min": 0.5, "max": 0.5}
    b = {"bounds": [2.0], "bucket_counts": [1], "count": 2, "sum": 3.0,
         "min": 1.0, "max": 2.0}
    m = _merge_histogram([a, b])
    assert m["count"] == 3 and m["sum"] == 3.5
    assert m["bounds"] == [] and m["bucket_counts"] == []


def test_aggregator_multirank_sum_and_skew():
    """Acceptance: rank-0 aggregate sums collectives_total across ranks and
    reports a nonzero step_time_skew under an induced straggler."""
    from paddle_tpu.observability import MetricsAggregator, note_step_time

    reg = MetricsRegistry()
    reg.counter("collectives_total", labels=("op",)).labels(
        op="all_reduce").inc(4)
    note_step_time(0.01)

    def straggle(rank, payload):
        payload["step_time"] = {"steps": 8, "mean_s": 0.01, "last_s": 0.01}
        if rank == 2:
            payload["step_time"]["mean_s"] = 0.02  # 2x straggler

    agg = MetricsAggregator(registry=reg, gather_fn=_emulate_ranks(4, straggle))
    rec = agg.aggregate()
    assert rec["ranks"] == [0, 1, 2, 3]
    fam = rec["metrics"]["collectives_total"]
    assert fam["children"]["op=all_reduce"] == 16  # 4 summed over 4 ranks
    assert rec["step_time_skew"] > 0
    assert rec["step_time"]["slowest_rank"] == 2
    assert agg.last is rec
    # the straggler gauge landed on the GLOBAL registry for scrapers
    assert get_registry().snapshot()["step_time_skew"] > 0


def test_aggregation_collective_timeout_degrades_not_raises():
    """Chaos variant: the aggregation exchange times out (PR-4 typed error)
    — training must continue on a degraded local-only record, with the
    failure counted, never an exception out of telemetry."""
    from paddle_tpu.framework.errors import CollectiveTimeoutError
    from paddle_tpu.observability import MetricsAggregator

    reg = MetricsRegistry()
    reg.counter("c_total").inc(3)

    def hang_gather(payload):
        raise CollectiveTimeoutError("all_gather timed out", op="all_gather",
                                     group=None, rank=0, attempt=3)

    fails0 = get_registry().snapshot().get(
        "telemetry_aggregation_failures_total", 0)
    agg = MetricsAggregator(registry=reg, gather_fn=hang_gather)
    rec = agg.aggregate()  # must NOT raise
    assert "CollectiveTimeoutError" in rec["degraded"]
    assert rec["metrics"]["c_total"]["children"][""] == 3  # local view kept
    assert agg.failures == 1
    assert get_registry().snapshot()[
        "telemetry_aggregation_failures_total"] == fails0 + 1
    # a later healthy round recovers cleanly
    agg.gather_fn = _emulate_ranks(2)
    assert "degraded" not in agg.aggregate()


def test_aggregated_to_plain_flattens_like_snapshot():
    from paddle_tpu.observability import merge_typed_snapshots
    from paddle_tpu.observability.aggregate import aggregated_to_plain

    regs = [MetricsRegistry() for _ in range(2)]
    for reg in regs:
        reg.counter("n_total", labels=("k",)).labels(k="a").inc(2)
        reg.gauge("same_g").set(5.0)
    plain = aggregated_to_plain(
        merge_typed_snapshots([r.typed_snapshot() for r in regs]))
    assert plain["n_total"] == {"k=a": 4}
    assert plain["same_g"] == 5.0  # agreeing gauge collapses to the value


# ------------------------------------------------------------ flight recorder
def test_flight_recorder_ring_and_dump(tmp_path):
    """Tentpole (b): bounded ring, span/event taps, postmortem dump."""
    from paddle_tpu.observability import FlightRecorder

    rec = FlightRecorder(capacity=4, dump_dir=str(tmp_path), rank=0)
    for i in range(7):
        rec.note("lane", f"e{i}", bucket=i)
    assert len(rec) == 4  # bounded: oldest evicted
    assert [e["name"] for e in rec.entries()] == ["e3", "e4", "e5", "e6"]
    assert [e["name"] for e in rec.entries(n=2)] == ["e5", "e6"]

    path = rec.dump("unit_test")
    assert path and os.path.exists(path)
    dump = json.load(open(path))
    assert dump["reason"] == "unit_test" and dump["rank"] == 0
    assert dump["n_entries"] == 4
    assert dump["entries"][-1]["name"] == "e6"
    assert rec.dumps[-1]["path"] == path

    # capacity 0 disables recording AND dumping
    off = FlightRecorder(capacity=0, dump_dir=str(tmp_path))
    off.note("lane", "x")
    assert len(off) == 0 and off.dump("nope") is None


def test_flight_recorder_auto_dump_budget(tmp_path):
    from paddle_tpu.observability import FlightRecorder
    from paddle_tpu.observability.flight_recorder import _MAX_AUTO_DUMPS

    rec = FlightRecorder(capacity=8, dump_dir=str(tmp_path), rank=0)
    rec.note("lane", "x")
    for _ in range(_MAX_AUTO_DUMPS):
        assert rec.dump("storm", auto=True) is not None
    assert rec.dump("storm", auto=True) is None  # budget spent
    assert rec.dump("manual") is not None        # manual dumps still allowed


def test_flight_recorder_taps_spans_and_events():
    """The global recorder sees RecordEvent closes and EventLog records
    without any explicit wiring at the call sites."""
    from paddle_tpu.observability import get_flight_recorder

    rec = get_flight_recorder()
    rec.clear()
    with RecordEvent("fr_test_span"):
        pass
    get_event_log().warning("fr_test", "something happened", detail=7)
    names = [(e["kind"], e["name"]) for e in rec.entries()]
    assert ("span", "fr_test_span") in names
    ev = next(e for e in rec.entries(kind="event")
              if e["name"] == "fr_test")
    assert ev["severity"] == "warning"
    assert ev["fields"]["detail"] == 7


def test_escalation_paths_dump_flight_recorder(tmp_path, monkeypatch):
    """Every escalation path must leave a postmortem: NanGuard trip,
    breaker, HangDetector escalate, collective-timeout exhaustion."""
    import paddle_tpu.observability.flight_recorder as fr_mod
    from paddle_tpu.framework.errors import CollectiveTimeoutError
    from paddle_tpu.robustness.fault_injection import ChaosGroup
    from paddle_tpu.robustness.watchdog import HangDetector, NanGuard
    import paddle_tpu.distributed.collective as coll
    from paddle_tpu.framework.tensor import Tensor

    reasons = []
    tmp_rec = fr_mod._install(fr_mod.FlightRecorder(capacity=64,
                                                    dump_dir=str(tmp_path),
                                                    rank=0))
    monkeypatch.setattr(fr_mod, "_recorder", tmp_rec)
    real_dump = fr_mod.FlightRecorder.dump

    def spy(self, reason, path=None, auto=False):
        reasons.append(str(reason))
        return real_dump(self, reason, path=path, auto=auto)

    monkeypatch.setattr(fr_mod.FlightRecorder, "dump", spy)

    try:
        # NanGuard skip_step trip
        NanGuard(policy="skip_step").check(float("nan"))
        assert any(r.startswith("nan_guard:") for r in reasons)

        # HangDetector escalate
        hd = HangDetector(timeout=60.0, on_hang=lambda age: None)
        hd.beat()
        hd.escalate("unit test")
        assert any(r.startswith("hang_escalated:") for r in reasons)

        # collective-timeout exhaustion (every attempt hangs past the
        # group timeout -> typed error + postmortem)
        g = ChaosGroup(plan={i: ("hang", 0.3) for i in range(1, 4)},
                       timeout=0.05)
        with pytest.raises(CollectiveTimeoutError):
            coll.all_reduce(Tensor(np.float32(1.0)), group=g)
        assert any(r.startswith("collective_timeout:") for r in reasons)
        # the dump actually landed on disk
        assert any(p.name.startswith("flightrec_rank0")
                   for p in tmp_path.iterdir())
    finally:
        fr_mod._uninstall(tmp_rec)  # the temp ring's sinks must not leak


# ------------------------------------------------------------------- memory
def test_memory_accounting_sample_and_gauges():
    from paddle_tpu.observability import memory as obs_mem

    t = paddle.to_tensor(np.ones((64, 64), np.float32))  # noqa: F841 live
    s = obs_mem.sample()
    assert s["live_tensor_bytes"] >= 64 * 64 * 4
    assert get_registry().snapshot()["live_tensor_bytes"] >= 64 * 64 * 4


def test_memory_record_compiled():
    from paddle_tpu.observability import memory as obs_mem

    analysis = {"argument_bytes": 100, "output_bytes": 50, "temp_bytes": 30,
                "alias_bytes": 40, "generated_code_bytes": 0,
                "peak_hbm_bytes": 140}
    got = obs_mem.record_compiled("unit_entry", analysis)
    assert got["peak_hbm_bytes"] == 140
    assert obs_mem.compiled_memory()["unit_entry"]["peak_hbm_bytes"] == 140
    g = get_registry().snapshot()["compiled_peak_hbm_bytes"]
    assert g["entry=unit_entry"] == 140


def test_train_step_memory_analysis_compiled_path():
    """Compiled-path accounting keyed by trace-cache entry: XLA's
    memory_analysis of the EXACT program the last call compiled."""
    import paddle_tpu.nn.functional as F
    import paddle_tpu.optimizer as optim
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.observability import memory as obs_mem

    paddle.seed(7)
    net = nn.Sequential(nn.Linear(8, 4), nn.Tanh(), nn.Linear(4, 1))
    opt = optim.SGD(learning_rate=0.1, parameters=net.parameters())
    step = TrainStep(net, lambda o, y: F.mse_loss(o, y), opt)
    assert step.memory_analysis() is None  # before the first call

    x = paddle.to_tensor(np.random.randn(4, 8).astype(np.float32))
    y = paddle.to_tensor(np.random.randn(4, 1).astype(np.float32))
    step(x, y)
    a = step.memory_analysis(entry="unit_train_step")
    assert a is not None
    assert a["peak_hbm_bytes"] == (a["argument_bytes"] + a["temp_bytes"]
                                   + a["output_bytes"] - a["alias_bytes"])
    assert a["peak_hbm_bytes"] > 0
    assert obs_mem.compiled_memory()["unit_train_step"]["peak_hbm_bytes"] \
        == a["peak_hbm_bytes"]


# --------------------------------------------------------------- exposition
def test_exposition_end_to_end_scrape(tmp_path):
    """Acceptance: /metrics round-trips through the strict parser
    (escaped label values included); /snapshot serves the rank-0
    aggregate; /events and /flightrecorder serve the rings."""
    import urllib.request

    from paddle_tpu.observability import (
        MetricsAggregator, TelemetryServer, parse_prometheus_text,
    )

    reg = MetricsRegistry()
    reg.counter("scrape_total", labels=("path",)).labels(
        path='weird "quoted"\\x').inc(3)
    reg.histogram("scrape_lat_s", buckets=(0.1, 1.0)).observe(0.5)
    agg = MetricsAggregator(registry=reg, gather_fn=_emulate_ranks(2))

    with TelemetryServer(port=0, registry=reg, aggregator=agg) as srv:
        assert srv.port  # ephemeral port bound
        text = urllib.request.urlopen(srv.url + "/metrics").read().decode()
        fams = parse_prometheus_text(text)  # STRICT: malformed would raise
        (_, labels, value), = fams["scrape_total"]["samples"]
        assert labels["path"] == 'weird "quoted"\\x' and value == 3.0
        assert fams["scrape_lat_s"]["type"] == "histogram"
        bucket_samples = [s for s in fams["scrape_lat_s"]["samples"]
                         if s[0] == "scrape_lat_s_bucket"]
        assert {s[1]["le"] for s in bucket_samples} == {"0.1", "1.0", "+Inf"}

        snap = json.load(urllib.request.urlopen(srv.url + "/snapshot"))
        assert snap["aggregated"] is True
        assert snap["ranks"] == [0, 1]
        assert snap["metrics"]["scrape_total"]["children"][
            'path=weird "quoted"\\x'] == 6  # summed over the 2 ranks
        local = json.load(
            urllib.request.urlopen(srv.url + "/snapshot?local=1"))
        assert local["aggregated"] is False

        get_event_log().info("scrape_test", "hello")
        evs = json.load(urllib.request.urlopen(srv.url + "/events?n=50"))
        assert any(e["kind"] == "scrape_test" for e in evs["events"])

        fr = json.load(urllib.request.urlopen(srv.url + "/flightrecorder"))
        assert fr["capacity"] > 0

        ok = urllib.request.urlopen(srv.url + "/healthz").read()
        assert ok == b"ok\n"
        with pytest.raises(urllib.error.HTTPError) as e:
            urllib.request.urlopen(srv.url + "/nope")
        assert e.value.code == 404
    # context exit stopped the server
    with pytest.raises(OSError):
        urllib.request.urlopen(f"http://127.0.0.1:{srv.port}/healthz",
                               timeout=0.5)


def test_healthz_verbose_and_404_list_dynamic_sections(tmp_path):
    """ISSUE 18: the /healthz?verbose path list and the 404 body are
    computed from the live section map — a section registered after the
    server started (how the serving runtime mounts /serving and /traces)
    appears in both, and disappears on unregister. The bare /healthz
    liveness probe body stays exactly "ok\\n"."""
    import urllib.request

    from paddle_tpu.observability import TelemetryServer
    from paddle_tpu.observability.exposition import (
        register_section, unregister_section,
    )

    reg = MetricsRegistry()
    with TelemetryServer(port=0, registry=reg) as srv:
        assert urllib.request.urlopen(srv.url + "/healthz").read() == b"ok\n"
        base = json.load(
            urllib.request.urlopen(srv.url + "/healthz?verbose=1"))
        assert base["status"] == "ok"
        assert "/metrics" in base["paths"] and "/healthz" in base["paths"]
        assert "/dyn" not in base["paths"]

        register_section("dyn", lambda: {"n": 7},
                         lambda sub: {"sub": sub} if sub == "x" else None)
        try:
            live = json.load(
                urllib.request.urlopen(srv.url + "/healthz?verbose=1"))
            assert "/dyn" in live["paths"]
            assert json.load(
                urllib.request.urlopen(srv.url + "/dyn")) == {"n": 7}
            assert json.load(
                urllib.request.urlopen(srv.url + "/dyn/x")) == {"sub": "x"}
            with pytest.raises(urllib.error.HTTPError) as e:
                urllib.request.urlopen(srv.url + "/dyn/nope")
            assert e.value.code == 404
            # the 404 body itself advertises the live paths
            with pytest.raises(urllib.error.HTTPError) as e:
                urllib.request.urlopen(srv.url + "/nope")
            body = json.loads(e.value.read())
            assert "/dyn" in body["paths"]
        finally:
            unregister_section("dyn")
        gone = json.load(
            urllib.request.urlopen(srv.url + "/healthz?verbose=1"))
        assert "/dyn" not in gone["paths"]


def test_start_exposition_flag_gated(monkeypatch):
    from paddle_tpu.framework import flags as flags_mod
    from paddle_tpu.observability import (
        get_telemetry_server, start_exposition, stop_exposition,
    )

    stop_exposition()
    # flag unset -> off, returns None so callers can wire unconditionally
    monkeypatch.setitem(flags_mod._FLAGS, "FLAGS_telemetry_http_port", 0)
    assert start_exposition() is None
    assert get_telemetry_server() is None
    try:
        srv = start_exposition(port=0)  # explicit port overrides the flag
        assert srv is not None and srv.port
        assert start_exposition(port=0) is srv  # idempotent
    finally:
        stop_exposition()


# ------------------------------------------------- hapi aggregation wiring
def test_metrics_callback_aggregates_and_samples_memory(tmp_path):
    """Model.fit with telemetry: each dump carries the cross-rank aggregate
    (emulated 2-rank world) + a memory sample; the skew gauge lands."""
    import paddle_tpu.optimizer as optim
    from paddle_tpu.hapi import Model
    from paddle_tpu.hapi.callbacks import MetricsCallback
    from paddle_tpu.observability import MetricsAggregator

    paddle.seed(0)
    net = nn.Sequential(nn.Linear(4, 8), nn.Tanh(), nn.Linear(8, 2))
    model = Model(net)
    model.prepare(optim.SGD(learning_rate=0.01,
                            parameters=net.parameters()),
                  nn.CrossEntropyLoss())
    rs = np.random.RandomState(0)
    data = [(rs.standard_normal(4).astype(np.float32),
             np.int64(rs.randint(2))) for _ in range(8)]

    agg = MetricsAggregator(gather_fn=_emulate_ranks(2))
    cb = MetricsCallback(log_dir=str(tmp_path), freq=4, aggregate=True,
                         aggregator=agg)
    model.fit(data, batch_size=2, epochs=1, verbose=0, callbacks=[cb],
              telemetry=agg)
    rec = cb.last_snapshot
    assert rec is not None
    assert rec["aggregated"]["ranks"] == [0, 1]
    assert "step_time_skew" in rec["aggregated"]
    assert rec["memory"]["live_tensor_bytes"] > 0
    # records serialized to JSONL despite non-JSON-native payloads
    lines = open(os.path.join(str(tmp_path), "metrics.jsonl")).readlines()
    assert lines and all(json.loads(l) for l in lines)


# --------------------------------------------------- strategy knob wiring
def test_fleet_strategy_telemetry_knobs():
    """DistributedStrategy.telemetry resizes the flight-recorder ring at
    fleet.init time (the exposition port stays flag-gated: 0 = off)."""
    from paddle_tpu.distributed import fleet
    from paddle_tpu.distributed import mesh as mesh_mod
    from paddle_tpu.observability import get_flight_recorder

    old_cap = get_flight_recorder().capacity
    old_state = dict(fleet._fleet_state)
    old_mesh = mesh_mod.get_mesh()
    strategy = fleet.DistributedStrategy()
    strategy.telemetry = True
    cfg = dict(strategy.telemetry_configs)
    cfg["flight_recorder_capacity"] = 512
    strategy.telemetry_configs = cfg
    try:
        fleet.init(is_collective=True, strategy=strategy)
        assert get_flight_recorder().capacity == 512
    finally:
        from paddle_tpu.observability import configure_flight_recorder

        configure_flight_recorder(capacity=old_cap)
        # a telemetry-opted fleet strategy must not leak into later tests
        # (Model.fit auto-inherits it)
        fleet._fleet_state.clear()
        fleet._fleet_state.update(old_state)
        # fleet.init SETS the global hybrid mesh; leaving it behind made
        # every later single-device Model.fit shard its small batches
        # over data=8 — the order-dependent TestRobustCheckpointCallback
        # tier-1 failures (PR 14's note, fixed + pinned in PR 15)
        mesh_mod.set_mesh(old_mesh)
