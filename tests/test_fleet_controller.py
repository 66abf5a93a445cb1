"""Goodput-maximizing elastic controller (ISSUE 17).

Contracts pinned here:
- ScalePolicy.decide is a PURE function of a FleetSignals snapshot, with
  the documented priority order (preemption > cooldown > straggler >
  serve overload > serve idle > grow) and cooldown hysteresis carried IN
  the snapshot; a recorded run replays to the bit-identical decision
  sequence.
- FleetController assembles honest signals (free-chip inventory math,
  quarantine accounting), actuates through duck-typed plants, logs every
  non-noop decision on the event plane and the
  fleet_decisions_total{action=} counter.
- GoodputLedger attributes every chip-second to exactly one account,
  refuses unknown accounts, and verify_conservation catches dropped time.
- Compile-aware watchdog grace: a replica reporting "compiling" gets
  max(timeout, compile_grace) as its deadline; a fake slow-compile
  replica survives a timeout that evicts a non-compiling control.
- Fault injection growth: FaultyFS targeted delay_on, and
  LateHeartbeatStore making one host's lease lapse (ElasticManager sees
  the member vanish, then recover when heartbeats resume).
- A controller run over the fleet plants of tools/chaos_train.py keeps
  zero lost requests, every preemption notice answered inside its grace,
  ledger conservation and replay, under each planted fault kind.
"""
import os
import sys
import time

import pytest

from paddle_tpu.distributed.fleet.elastic import (
    Decision, ElasticManager, FleetController, FleetSignals, GoodputLedger,
    LocalKVStore, ReactivePolicy, ScalePolicy, LEDGER_ACCOUNTS,
)
from paddle_tpu.observability import get_event_log
from paddle_tpu.observability.metrics import get_registry
from paddle_tpu.robustness.fault_injection import (
    FaultyFS, LateHeartbeatStore,
)
from paddle_tpu.robustness.watchdog import HangDetector

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _sig(**over):
    base = dict(clock=10.0, train_world=4, serve_replicas=2, total_chips=8,
                free_chips=0, spare_hosts=0, step_time_p99_ms=900.0,
                step_time_skew=0.02, serve_queue_depth=0,
                serve_latency_p99_ms=0.0, preempt_notice=False,
                preempt_grace_s=30.0)
    base.update(over)
    return FleetSignals(**base)


class _Train:
    """Duck-typed train plant that records actuations."""

    def __init__(self, world=4):
        self.world = world
        self.calls = []
        self.skew = 0.02
        self.preempt = False

    def spare_hosts(self):
        return 0

    def step_time_p99_ms(self):
        return 900.0

    def step_time_skew(self):
        return self.skew

    def preempt_pending(self):
        return self.preempt

    def preempt_grace_s(self):
        return 30.0

    def preempt_shrink(self):
        self.calls.append("preempt_shrink")
        self.world -= 1
        self.preempt = False

    def shed_straggler(self):
        self.calls.append("shed_straggler")
        self.world -= 1
        self.skew = 0.02

    def grow(self):
        self.calls.append("grow")
        self.world += 1

    def release_chip(self):
        self.calls.append("release_chip")
        self.world -= 1


class _Serve:
    def __init__(self, replicas=2):
        self.replicas = replicas
        self.calls = []
        self.queue_depth = 0
        self.p99 = 0.0

    def latency_p99_ms(self):
        return self.p99

    def scale_up(self):
        self.calls.append("scale_up")
        self.replicas += 1

    def scale_down(self):
        self.calls.append("scale_down")
        self.replicas -= 1


class TestScalePolicy:
    def test_preemption_outranks_everything_and_ignores_cooldown(self):
        p = ScalePolicy(cooldown_s=5.0)
        s = _sig(preempt_notice=True, step_time_skew=0.9,
                 serve_queue_depth=50, last_scale_clock=9.5)
        assert p.decide(s).action == "preempt_shrink"

    def test_preemption_respects_world_floor(self):
        p = ScalePolicy(min_train_world=4)
        s = _sig(preempt_notice=True)
        assert p.decide(s).action != "preempt_shrink"

    def test_cooldown_suppresses_non_preempt_actions(self):
        p = ScalePolicy(cooldown_s=5.0, skew_high=0.5)
        s = _sig(step_time_skew=0.9, last_scale_clock=8.0)  # 2s ago < 5s
        d = p.decide(s)
        assert d.action == "none" and d.reason == "cooldown"
        # outside the window the same signals shed the straggler
        assert p.decide(_sig(step_time_skew=0.9,
                             last_scale_clock=1.0)).action == "shed_straggler"

    def test_overload_prefers_free_chip_over_train_shrink(self):
        p = ScalePolicy(queue_high=6)
        over = _sig(serve_queue_depth=9, free_chips=1)
        assert p.decide(over).action == "serve_up"
        no_free = _sig(serve_queue_depth=9, free_chips=0)
        assert p.decide(no_free).action == "train_to_serve"

    def test_overload_by_latency_alone(self):
        p = ScalePolicy(serve_p99_high_ms=2500.0)
        s = _sig(serve_latency_p99_ms=4000.0, free_chips=1)
        assert p.decide(s).action == "serve_up"

    def test_overload_with_no_capacity_anywhere_is_none(self):
        p = ScalePolicy(min_train_world=4, max_serve_replicas=4)
        s = _sig(serve_queue_depth=50, free_chips=0, train_world=4)
        assert p.decide(s).action == "none"

    def test_serve_idle_hands_chip_to_training(self):
        p = ScalePolicy(queue_low=0)
        s = _sig(serve_queue_depth=0, serve_latency_p99_ms=0.0)
        assert p.decide(s).action == "serve_to_train"

    def test_serve_idle_at_train_ceiling_scales_down(self):
        p = ScalePolicy(max_train_world=4)
        s = _sig(serve_queue_depth=0, train_world=4)
        assert p.decide(s).action == "serve_down"

    def test_serve_idle_respects_replica_floor(self):
        p = ScalePolicy(min_serve_replicas=2, max_train_world=4)
        s = _sig(serve_replicas=2, serve_queue_depth=0, train_world=4)
        assert p.decide(s).action == "none"

    def test_spare_capacity_grows_train(self):
        p = ScalePolicy()
        assert p.decide(_sig(spare_hosts=1, serve_queue_depth=3)
                        ).action == "grow_train"
        # an overloaded serve keeps the spare chip available for serve_up
        d = p.decide(_sig(spare_hosts=1, free_chips=1, serve_queue_depth=9))
        assert d.action == "serve_up"

    def test_decide_is_pure_and_deterministic(self):
        p = ScalePolicy()
        s = _sig(serve_queue_depth=9, free_chips=1)
        before = dict(vars(p))
        d1, d2 = p.decide(s), p.decide(s)
        assert d1 == d2                      # frozen dataclass equality
        assert vars(p) == before             # no state mutated

    def test_reactive_policy_never_acts(self):
        p = ReactivePolicy()
        for s in (_sig(preempt_notice=True), _sig(serve_queue_depth=99),
                  _sig(step_time_skew=5.0), _sig(spare_hosts=3)):
            assert p.decide(s).action == "none"

    def test_decision_rejects_unknown_action(self):
        with pytest.raises(ValueError):
            Decision("explode", "nope", 0.0)


class TestGoodputLedger:
    def test_charge_and_conservation(self):
        led = GoodputLedger()
        led.charge("train_useful", 4, seconds=2.0)
        led.charge("save", 4)
        led.charge("idle", 1, seconds=3.0)
        assert led.chip_seconds == pytest.approx(15.0)
        assert led.verify_conservation(15.0)
        assert not led.verify_conservation(16.0)

    def test_unknown_account_refused(self):
        led = GoodputLedger()
        with pytest.raises(ValueError):
            led.charge("snacks", 1)
        with pytest.raises(ValueError):
            led.tokens("snacks", 1)

    def test_goodput_couples_tokens_and_availability(self):
        led = GoodputLedger()
        led.tokens("train", 900)
        led.tokens("serve", 100)
        assert led.availability == 1.0      # nothing submitted yet
        led.serve_submitted, led.serve_completed = 10, 5
        assert led.availability == 0.5
        assert led.goodput(10.0) == pytest.approx(1000 / 10.0 * 0.5)

    def test_summary_accounts_all_ledger_accounts(self):
        led = GoodputLedger()
        led.charge("serve_useful", 2)
        summ = led.summary()
        assert set(summ["accounts"]) == set(LEDGER_ACCOUNTS)
        assert summ["useful_fraction"] == pytest.approx(1.0)


class TestFleetController:
    def test_free_chip_inventory_math(self):
        ctrl = FleetController(ScalePolicy(), _Train(world=4),
                               _Serve(replicas=2), total_chips=8)
        assert ctrl.free_chips == 2
        ctrl.quarantined = 1
        assert ctrl.free_chips == 1
        s = ctrl.signals(clock=0.0)
        assert s.free_chips == 1 and s.train_world == 4 \
            and s.serve_replicas == 2

    def test_preempt_tick_actuates_and_records(self):
        train, serve = _Train(world=4), _Serve()
        train.preempt = True
        ctrl = FleetController(ScalePolicy(), train, serve, total_chips=8)
        get_event_log().clear()
        c0 = get_registry().counter(
            "fleet_decisions_total",
            labels=("action",)).labels(action="preempt_shrink").value
        d = ctrl.tick(0.0)
        assert d.action == "preempt_shrink"
        assert train.calls == ["preempt_shrink"] and train.world == 3
        assert len(ctrl.records) == 1
        assert get_registry().counter(
            "fleet_decisions_total",
            labels=("action",)).labels(action="preempt_shrink").value \
            == c0 + 1
        evs = get_event_log().events(kind="fleet")
        assert evs and evs[-1]["action"] == "preempt_shrink"
        assert ctrl.decision_log()[-1]["action"] == "preempt_shrink"

    def test_arbitration_moves_chips_both_ways(self):
        train, serve = _Train(world=5), _Serve(replicas=2)
        ctrl = FleetController(ScalePolicy(cooldown_s=0.0), train, serve,
                               total_chips=7)
        serve.queue_depth = 9
        assert ctrl.tick(0.0).action == "train_to_serve"
        assert train.world == 4 and serve.replicas == 3
        serve.queue_depth = 0
        assert ctrl.tick(1.0).action == "serve_to_train"
        assert train.world == 5 and serve.replicas == 2

    def test_straggler_shed_quarantines_the_chip(self):
        train = _Train(world=4)
        train.skew = 0.9
        ctrl = FleetController(ScalePolicy(), train, _Serve(),
                               total_chips=8)
        free0 = ctrl.free_chips
        assert ctrl.tick(0.0).action == "shed_straggler"
        # world shrank by one but the shed chip is quarantined, not free
        assert ctrl.quarantined == 1 and ctrl.free_chips == free0

    def test_hysteresis_clock_rides_in_the_snapshot(self):
        train = _Train(world=4)
        train.skew = 0.9
        ctrl = FleetController(ScalePolicy(cooldown_s=5.0), train,
                               _Serve(), total_chips=8)
        assert ctrl.tick(0.0).action == "shed_straggler"
        train.skew = 0.9            # still straggling
        d = ctrl.tick(2.0)          # inside the cooldown window
        assert d.action == "none" and d.reason == "cooldown"
        assert ctrl.records[-1][0].last_scale_clock == 0.0

    def test_recorded_run_replays_bit_identically(self):
        train, serve = _Train(world=5), _Serve(replicas=2)
        ctrl = FleetController(ScalePolicy(cooldown_s=2.0), train, serve,
                               total_chips=8)
        serve.queue_depth = 9
        ctrl.tick(0.0)
        ctrl.tick(1.0)
        serve.queue_depth = 0
        ctrl.tick(3.0)
        train.preempt = True
        ctrl.tick(4.0)
        assert len(ctrl.records) == 4
        assert ctrl.replay()        # pure decide() over frozen snapshots


class TestCompileAwareWatchdog:
    def test_effective_timeout_stretches_only_while_compiling(self):
        state = {"s": "compiling"}
        hd = HangDetector(timeout=0.5, state_fn=lambda: state["s"],
                          compile_grace=60.0)
        assert hd.effective_timeout() == 60.0
        state["s"] = "serving"
        assert hd.effective_timeout() == 0.5
        # a broken state_fn degrades to the plain timeout, never crashes
        hd2 = HangDetector(timeout=0.5, state_fn=lambda: 1 / 0,
                           compile_grace=60.0)
        assert hd2.effective_timeout() == 0.5
        hd3 = HangDetector(timeout=0.5)     # no state_fn: unchanged
        assert hd3.effective_timeout() == 0.5

    def test_slow_compile_survives_where_control_is_evicted(self):
        """A fake replica stuck in its first (compiling) step outlives a
        timeout that fires for an identical non-compiling control."""
        hangs = []
        hd = HangDetector(timeout=0.06, poll_interval=0.01,
                          on_hang=lambda age: hangs.append(age),
                          state_fn=lambda: "compiling", compile_grace=30.0)
        control_hangs = []
        ctrl = HangDetector(timeout=0.06, poll_interval=0.01,
                            on_hang=lambda age: control_hangs.append(age),
                            state_fn=lambda: "serving", compile_grace=30.0)
        with hd, ctrl:
            time.sleep(0.25)        # both heartbeats go stale
        assert hangs == []          # compiling: deadline stretched
        assert len(control_hangs) == 1

    def test_compile_finish_rearms_the_plain_deadline(self):
        state = {"s": "compiling"}
        hangs = []
        hd = HangDetector(timeout=0.05, poll_interval=0.01,
                          on_hang=lambda age: hangs.append(age),
                          state_fn=lambda: state["s"], compile_grace=30.0)
        with hd:
            time.sleep(0.12)
            assert hangs == []
            state["s"] = "serving"  # compile done, heartbeat still stale
            time.sleep(0.12)
        assert len(hangs) == 1


class TestFaultInjectionGrowth:
    def test_faultyfs_targeted_delay(self, tmp_path):
        fs = FaultyFS(delay_on={("write", 2): 0.08})
        p = str(tmp_path / "x.bin")
        with fs.open(p, "wb") as f:
            f.write(b"a")                   # write #1: no delay
            assert fs.delays == 0
            t0 = time.monotonic()
            f.write(b"b")                   # write #2: delayed
            assert time.monotonic() - t0 >= 0.08    # the injected sleep
        assert fs.delays == 1
        assert [e for e in fs.log if e[0] == "delay"] == \
            [("delay", "write#2")]

    def test_faultyfs_delay_on_rename_and_fsync(self, tmp_path):
        fs = FaultyFS(delay_on={("rename", 1): 0.05, ("fsync", 1): 0.05})
        src, dst = str(tmp_path / "a"), str(tmp_path / "b")
        with fs.open(src, "wb") as f:
            f.write(b"x")
            t0 = time.monotonic()
            fs.fsync(f)
            assert time.monotonic() - t0 >= 0.05
        t0 = time.monotonic()
        fs.replace(src, dst)
        assert time.monotonic() - t0 >= 0.05
        assert fs.delays == 2

    def test_late_heartbeat_drops_then_recovers(self):
        inner = LocalKVStore()
        st = LateHeartbeatStore(inner, host="b", drop_puts=2)
        a = ElasticManager("a", "1:4", store=st, job_id="hb", ttl=0.1)
        b = ElasticManager("b", "1:4", store=st, job_id="hb", ttl=0.1)
        a.register()
        b.register()                 # swallowed (drop 1)
        assert a.members() == ["a"]  # b's lease never landed
        b.register()                 # swallowed (drop 2)
        assert a.members() == ["a"]
        b.register()                 # injector exhausted: heartbeat heals
        assert sorted(a.members()) == ["a", "b"]
        assert st.dropped == 2
        # ...and with no further beats the healed lease expires again
        time.sleep(0.15)
        assert "b" not in a.members() and "a" not in a.members()

    def test_late_heartbeat_delay_forwards_after_sleep(self):
        st = LateHeartbeatStore(LocalKVStore(), host="b", delay_puts=1,
                                delay_s=0.05)
        b = ElasticManager("b", "1:4", store=st, job_id="hb2", ttl=5)
        t0 = time.monotonic()
        b.register()
        assert time.monotonic() - t0 >= 0.05
        assert st.delayed == 1
        assert b.members() == ["b"]  # late, but it landed

    def test_other_hosts_pass_straight_through(self):
        st = LateHeartbeatStore(LocalKVStore(), host="b", drop_puts=99)
        a = ElasticManager("a", "1:4", store=st, job_id="hb3", ttl=5)
        a.register()
        assert a.members() == ["a"] and st.dropped == 0


def _fleet_tool():
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import chaos_train
    finally:
        sys.path.pop(0)
    return chaos_train


_NO_FAULTS = dict(preemptions=[], capacity_adds=[], consolidations=[],
                  straggler={"start": 0, "until": 0, "skew": 0.8})

# fault kind -> what it plants in the recorded trace (tools/chaos_train.py
# record_fleet_trace; the recorded trace itself runs in
# test_distributed_ft.py::TestChaosTrainQuick)
_FLEET_FAULTS = {
    "control": {},
    # the emergency save costs one tick and the notice gives one
    "preempt_tight_grace": {"preemptions": [{"t": 3, "grace_ticks": 1}]},
    "preempt_twice": {"preemptions": [{"t": 20, "grace_ticks": 6},
                                      {"t": 34, "grace_ticks": 6}]},
    # a scale action at t=0 opens a 3-tick cooldown; the notice lands in it
    "preempt_in_cooldown": {"preemptions": [{"t": 1, "grace_ticks": 3}]},
    # busy replicas retired under the day's backlog: drain + re-admit
    "busy_consolidations": {"consolidations": [{"t": 30}, {"t": 36},
                                               {"t": 42}]},
    # eight arrivals a tick for ten ticks: the full queue refuses some
    "burst": {"burst": range(24, 34)},
}


class TestFleetRunInvariants:
    """What the controller owes under churn, asserted on its own run over
    the fleet plants of tools/chaos_train.py (a real ReplicaSet and a real
    ZeRO-3 job on the trace's virtual clock): no accepted request lost,
    every preemption notice answered by an emergency save committed inside
    its grace, every chip-second on exactly one account, the decisions
    replayable. All counts; nothing here reads a wall clock."""

    def _run(self, tmp_path, fault, mode="policy"):
        import logging

        ct = _fleet_tool()
        logging.getLogger("paddle_tpu").setLevel(logging.ERROR)
        trace = ct._load_fleet_trace()      # read anew from its file
        trace.update(_NO_FAULTS)
        plant = dict(_FLEET_FAULTS[fault])
        for t in plant.pop("burst", ()):
            trace["arrivals"][t] = [0, 1, 2, 3, 4, 5, 0, 1]
        trace.update(plant)
        return trace, ct._run_fleet_mode(trace, mode, str(tmp_path), seed=3)

    @pytest.mark.parametrize("fault", sorted(_FLEET_FAULTS))
    def test_policy_run_keeps_every_promise(self, tmp_path, fault):
        trace, run = self._run(tmp_path, fault)
        serve = run["serve"]
        # zero lost, zero unanswered: every accepted request completed,
        # and what was not accepted was refused at the door, not dropped
        assert serve["lost_requests"] == 0
        assert serve["accepted"] + serve["rejected"] == serve["submitted"]
        if fault == "burst":
            assert serve["rejected"] > 0
        if fault == "busy_consolidations":
            assert sum(ev["drained"] for ev in serve["scale_events"]) >= 1
        # every notice answered, its save committed before the deadline
        # and before the world switched (the resize that follows loads it)
        notices = trace["preemptions"]
        assert run["preempt_unanswered"] == 0
        records = run["preempt_records"]
        assert [r["notice_t"] for r in records] == [n["t"] for n in notices]
        for rec in records:
            assert rec["in_grace"] and rec["save_done_t"] <= rec["deadline_t"]
        actions = [d["action"] for d in run["decisions"]]
        assert actions.count("preempt_shrink") == len(notices)
        assert sum(1 for r in run["train_resizes"]
                   if r["reason"] == "preempt") == len(notices)
        # the ledger and the decision log
        assert run["conservation_ok"]
        assert run["decision_replay_ok"]

    def test_reactive_baseline_leaves_the_notice_unanswered(self, tmp_path):
        """The assertions above can fail: with no policy the same notices
        go unanswered and the job pays a crash-restart for each."""
        _, run = self._run(tmp_path, "preempt_twice", mode="reactive")
        assert run["preempt_unanswered"] == 2
        assert run["preempt_records"] == []
        assert run["serve"]["lost_requests"] == 0
        assert run["conservation_ok"]


# ---------------------------------------------------------------------------
# telemetry-derived signals (ISSUE 18)
# ---------------------------------------------------------------------------

class TestTelemetrySignals:
    """HistogramWindow windowed quantiles, SLO burn rate, and the
    SignalsAdapter serve-plant duck — the observe half of the loop."""

    def _reg(self):
        from paddle_tpu.observability.metrics import MetricsRegistry

        reg = MetricsRegistry()
        lat = reg.histogram("serve_request_latency_ms",
                            buckets=(100.0, 1000.0, 5000.0))
        ttft = reg.histogram("serve_ttft_ms", buckets=(50.0, 500.0))
        return reg, lat, ttft

    def test_window_quantile_sees_load_subside(self):
        from paddle_tpu.distributed.fleet.elastic import HistogramWindow

        reg, lat, _ = self._reg()
        w = HistogramWindow(lambda: reg.get(
            "serve_request_latency_ms").bind())
        for _ in range(50):
            lat.observe(4000.0)              # sustained slow burst
        w.sample(0.0)
        w.sample(10.0)                       # no new traffic since
        # cumulative life-to-date p99 stays huge; the WINDOW reads the
        # interval delta and reports the load gone
        assert lat.quantile(0.99) > 1000.0
        assert w.quantile(0.99, window_s=10.0) == 0.0
        for _ in range(20):
            lat.observe(50.0)                # fast traffic resumes
        w.sample(20.0)
        assert w.quantile(0.99, window_s=10.0) <= 100.0

    def test_window_single_sample_is_life_to_date(self):
        from paddle_tpu.distributed.fleet.elastic import HistogramWindow

        reg, lat, _ = self._reg()
        w = HistogramWindow(lambda: reg.get(
            "serve_request_latency_ms").bind())
        for _ in range(10):
            lat.observe(4000.0)
        w.sample(0.0)                        # only one snapshot yet
        assert w.quantile(0.5, window_s=10.0) > 1000.0

    def test_window_absent_family_is_quiet(self):
        from paddle_tpu.distributed.fleet.elastic import HistogramWindow

        w = HistogramWindow(lambda: None)
        w.sample(0.0)
        assert w.quantile(0.99, 10.0) == 0.0
        assert w.bad_fraction(100.0, 10.0) == 0.0

    def test_slo_burn_fast_and_slow_windows(self):
        from paddle_tpu.distributed.fleet.elastic import (
            HistogramWindow, SloBurnRate,
        )

        reg, lat, _ = self._reg()
        w = HistogramWindow(lambda: reg.get(
            "serve_request_latency_ms").bind())
        slo = SloBurnRate(w, budget_ms=1000.0, objective=0.9,
                          fast_window_s=5.0, slow_window_s=30.0)
        for _ in range(90):
            lat.observe(50.0)                # 90 good...
        for _ in range(10):
            lat.observe(4000.0)              # ...10 bad = exactly budget
        w.sample(0.0)
        fast, slow = slo.burn()
        assert fast == pytest.approx(1.0) and slow == pytest.approx(1.0)
        for _ in range(10):
            lat.observe(4000.0)              # all-bad recent interval
        w.sample(10.0)
        fast, _ = slo.burn()
        assert fast == pytest.approx(10.0)   # 100% bad / 10% budget
        with pytest.raises(ValueError):
            SloBurnRate(w, budget_ms=1.0, objective=1.0)

    def test_adapter_duck_and_snapshot(self):
        from paddle_tpu.distributed.fleet.elastic import SignalsAdapter

        reg, lat, ttft = self._reg()
        qd = reg.gauge("serve_queue_depth")
        qd.set(7)
        plant = _Serve(replicas=3)
        ad = SignalsAdapter(plant, registry=reg, window_s=10.0,
                            latency_budget_ms=1000.0, ttft_budget_ms=500.0)
        for _ in range(20):
            lat.observe(4000.0)
            ttft.observe(40.0)
        ad.observe(0.0)
        assert ad.replicas == 3              # actuation truth: the plant
        assert ad.queue_depth == 7           # telemetry, not the plant
        assert ad.latency_p99_ms() > 1000.0
        assert ad.ttft_p99_ms() <= 50.0
        fast, slow = ad.slo_burn()
        assert fast == pytest.approx(10.0)   # latency SLO dominates
        assert ad.heartbeat_age_max_s() == 0.0   # no ReplicaSet wired
        ad.scale_up()
        assert plant.calls == ["scale_up"] and ad.replicas == 4
        snap = ad.snapshot()
        assert snap["queue_depth"] == 7
        assert snap["slo_fast_burn"] == pytest.approx(10.0)

    def test_adapter_queue_depth_falls_back_to_plant(self):
        from paddle_tpu.distributed.fleet.elastic import SignalsAdapter
        from paddle_tpu.observability.metrics import MetricsRegistry

        plant = _Serve(replicas=2)
        plant.queue_depth = 4
        ad = SignalsAdapter(plant, registry=MetricsRegistry())
        assert ad.queue_depth == 4           # gauge family absent

    def test_controller_reads_adapter_signals(self):
        from paddle_tpu.distributed.fleet.elastic import SignalsAdapter

        reg, lat, ttft = self._reg()
        reg.gauge("serve_queue_depth").set(2)
        ad = SignalsAdapter(_Serve(replicas=2), registry=reg,
                            window_s=10.0, ttft_budget_ms=500.0)
        for _ in range(10):
            lat.observe(300.0)
            ttft.observe(900.0)              # TTFT SLO fully burning
        ctl = FleetController(ScalePolicy(), _Train(), ad, total_chips=8)
        s = ctl.signals(clock=5.0)           # ticks ad.observe(5.0) itself
        assert s.serve_queue_depth == 2
        assert s.serve_latency_p99_ms > 0.0
        # 900ms sits in the +Inf bucket: the window clamps to the last
        # finite bound (500) rather than inventing a per-interval max
        assert s.serve_ttft_p99_ms == pytest.approx(500.0)
        assert s.slo_fast_burn == pytest.approx(10.0)
        assert s.heartbeat_age_max_s == 0.0

    def test_policy_slo_burn_gate_is_opt_in(self):
        # default (None): burn alone never triggers overload — recorded
        # PR-17 decision sequences replay unchanged
        calm = _sig(slo_slow_burn=50.0, free_chips=1)
        assert ScalePolicy().decide(calm).action != "serve_up"
        armed = ScalePolicy(slo_burn_high=2.0)
        assert armed.decide(calm).action == "serve_up"
        assert armed.decide(
            _sig(slo_slow_burn=1.0, free_chips=1)).action != "serve_up"

    def test_real_artifact_signals_section_if_present(self):
        """Acceptance (ISSUE 18): the checked-in chaos artifact carries
        the adapter-driven run — decisions matching the probe run (or
        goodput within 0.9x), zero lost, replay intact."""
        import json

        path = os.path.join(REPO, "artifacts", "chaos_train.json")
        if not os.path.exists(path):
            pytest.skip("no checked-in chaos_train artifact")
        with open(path) as fh:
            fleet = json.load(fh)["fleet"]
        sa = fleet.get("signals_adapter")
        assert sa is not None, "artifact predates the signals adapter"
        assert sa["ok"] is True
        assert sa["decisions_match_probe"] or sa["goodput_vs_probe"] >= 0.9
        assert sa["lost_requests"] == 0 and sa["decision_replay_ok"]
        assert sa["snapshot"]["latency_p99_ms"] >= 0.0


# ---------------------------------------------------------------------------
# warm-boot actuation (ISSUE 19)
# ---------------------------------------------------------------------------

class _WarmServe(_Serve):
    """Serve plant with the ISSUE 19 surface: ``scale_up(warm=)``, a
    boot ledger, and ``warm_boot_counts()``."""

    def __init__(self, replicas=2, boot_mode="warm"):
        super().__init__(replicas)
        self.boot_mode = boot_mode
        self.last_boot = None
        self._counts = {"warm_boots": 0, "warm_boot_timeouts": 0}

    def scale_up(self, warm=False, reason="scale_up"):
        self.calls.append(f"scale_up(warm={warm})")
        self.replicas += 1
        if warm and self.boot_mode == "warm":
            self._counts["warm_boots"] += 1
            self.last_boot = {"mode": "warm", "outcome": "ok"}
        elif warm:
            self._counts["warm_boot_timeouts"] += 1
            self.last_boot = {"mode": "cold", "outcome": "ok"}

    def warm_boot_counts(self):
        return dict(self._counts)


class TestWarmBootActuation:
    def _overloaded(self, policy, serve):
        """world 5 + 2 replicas of 8 chips leaves one free; queue depth
        forces the overload branch so the next tick decides serve_up."""
        train = _Train(world=5)
        ctrl = FleetController(policy, train, serve, total_chips=8)
        serve.queue_depth = 9
        return ctrl

    def test_knob_off_actuates_cold(self):
        serve = _WarmServe()
        ctrl = self._overloaded(ScalePolicy(), serve)
        ctrl.tick(0.0)
        assert serve.calls == ["scale_up(warm=False)"]
        assert ctrl.actuations[-1]["outcome"] == "ok"

    def test_knob_on_actuates_warm_and_records_ok(self):
        serve = _WarmServe(boot_mode="warm")
        ctrl = self._overloaded(ScalePolicy(warm_boot=True), serve)
        ctrl.tick(0.0)
        assert serve.calls == ["scale_up(warm=True)"]
        assert ctrl.actuations[-1] == {
            "action": "serve_up", "clock": 0.0, "outcome": "ok"}

    def test_cold_fallback_recorded_as_warm_boot_timeout(self):
        serve = _WarmServe(boot_mode="cold")
        ctrl = self._overloaded(ScalePolicy(warm_boot=True), serve)
        ctrl.tick(0.0)
        assert serve.calls == ["scale_up(warm=True)"]
        assert ctrl.actuations[-1]["outcome"] == "warm_boot_timeout"

    def test_plant_without_warm_kwarg_falls_back(self):
        """PR-17 plants predate ``warm=`` — the controller degrades to
        the plain cold scale_up instead of crashing the actuation."""
        serve = _Serve()  # scale_up(self) only
        ctrl = self._overloaded(ScalePolicy(warm_boot=True), serve)
        ctrl.tick(0.0)
        assert serve.calls == ["scale_up"]
        assert serve.replicas == 3
        assert ctrl.actuations[-1]["outcome"] == "ok"

    def test_signals_stamp_warm_boot_counts(self):
        serve = _WarmServe(boot_mode="warm")
        ctrl = self._overloaded(ScalePolicy(warm_boot=True), serve)
        ctrl.tick(0.0)
        sig = ctrl.signals(1.0)
        assert sig.warm_boots == 1 and sig.warm_boot_timeouts == 0

    def test_plants_without_counts_hook_default_to_zero(self):
        train, serve = _Train(), _Serve()
        ctrl = FleetController(ScalePolicy(), train, serve, total_chips=8)
        sig = ctrl.signals(0.0)
        assert sig.warm_boots == 0 and sig.warm_boot_timeouts == 0

    def test_decide_never_reads_the_knob(self):
        """``warm_boot`` changes HOW serve_up actuates, never WHAT is
        decided — the same signal stream produces bit-identical decision
        sequences with the knob on and off (replay compatibility)."""
        sigs = [_sig(clock=t, serve_queue_depth=d, free_chips=1)
                for t, d in ((0.0, 9), (1.0, 9), (3.0, 0), (6.0, 9))]
        plain = ScalePolicy(cooldown_s=2.0)
        warm = ScalePolicy(cooldown_s=2.0, warm_boot=True)
        assert [plain.decide(s) for s in sigs] \
            == [warm.decide(s) for s in sigs]

    def test_old_signature_snapshots_replay_bit_identically(self):
        """PR-17 fleet traces predate the warm fields: FleetSignals
        defaults them, so a recorded run built from old-shape snapshot
        dicts re-decides bit-identically (acceptance: decision-record
        replay of PR-17 traces)."""
        import dataclasses

        old_shape = dict(clock=0.0, train_world=4, serve_replicas=2,
                         total_chips=8, free_chips=1, spare_hosts=0,
                         step_time_p99_ms=900.0, step_time_skew=0.02,
                         serve_queue_depth=9, serve_latency_p99_ms=0.0,
                         preempt_notice=False, preempt_grace_s=30.0)
        sig = FleetSignals(**old_shape)   # no warm fields in the record
        assert sig.warm_boots == 0 and sig.warm_boot_timeouts == 0
        policy = ScalePolicy(cooldown_s=2.0, warm_boot=True)
        want = policy.decide(sig)
        # round-trip through the serialized form a trace would carry
        rt = FleetSignals(**{k: v for k, v in
                             dataclasses.asdict(sig).items()
                             if k in old_shape})
        assert policy.decide(rt) == want

    def test_live_replay_with_warm_actuation(self):
        """A full recorded run with warm actuation on replays
        bit-identically — actuation outcomes live in ``actuations``,
        never inside the decision records replay() re-derives."""
        serve = _WarmServe(boot_mode="warm")
        ctrl = self._overloaded(ScalePolicy(cooldown_s=2.0,
                                            warm_boot=True), serve)
        ctrl.tick(0.0)
        serve.queue_depth = 0
        ctrl.tick(3.0)
        serve.queue_depth = 9
        ctrl.tick(6.0)
        assert ctrl.replay()
