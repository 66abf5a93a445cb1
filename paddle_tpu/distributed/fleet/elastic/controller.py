"""Goodput-maximizing elastic controller for the unified train+serve fleet.

PR 10 made any world size resumable and PR 14 made replica eviction
lossless, but until now every scale change in the repo was a *failure
response*: the reshard path ran after a crash, the drain path ran after a
watchdog eviction. This module closes ROADMAP item 2 by adding the
missing decision layer — a policy loop that watches the signals the repo
already emits and moves capacity *ahead* of failures:

  signal                          source
  ------------------------------  --------------------------------------
  preemption notice               robustness.preemption.PreemptionHandler
                                  (flag-file poll / SIGTERM latch)
  step-time p99 / straggler skew  observability step_time_skew gauge +
                                  aggregated step-time percentiles
  serve queue depth / tail ms     serving.scheduler serve_queue_depth
                                  gauge + replica latency percentiles
  spare capacity                  ElasticManager membership (TTL leases)

  decision                        actuation
  ------------------------------  --------------------------------------
  preempt_shrink                  timed emergency save + PR-10 reshard
                                  BEFORE the SIGTERM grace expires
  grow_train                      ElasticManager.wait_for_np + reshard up
  serve_up / serve_down           ReplicaSet.scale_up / scale_down
                                  (the PR-14 drain + re-admit path —
                                  zero dropped requests)
  train_to_serve / serve_to_train chip arbitration for diurnal traffic
  shed_straggler                  reshard the slow host out of the ring

Determinism contract: :meth:`ScalePolicy.decide` is a PURE function of a
:class:`FleetSignals` snapshot. All state a decision depends on —
including the hysteresis clock of the last scale action — rides IN the
snapshot, so a recorded signal sequence replays to the identical decision
sequence (tests/test_fleet_controller.py pins this). Every non-noop
decision is logged through the observability event plane and counted on
``fleet_decisions_total{action=}``.

The optimization target is goodput — useful tokens/s × availability —
accounted by :class:`GoodputLedger`: every chip-second of the fleet is
attributed to exactly one account (useful train tokens, useful serve
tokens, save/reshard/compile/drain overhead, recompute, or idle), so the
policy's value over the reactive baseline is a single number
(tools/chaos_train.py fleet phase, on a virtual clock).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional

__all__ = [
    "ACTIONS", "FleetSignals", "Decision", "ScalePolicy", "ReactivePolicy",
    "GoodputLedger", "FleetController", "LEDGER_ACCOUNTS",
]

# every action the policy may emit; "none" is the explicit no-op so the
# decision log is a total function of the tick sequence
ACTIONS = (
    "none",            # nothing to do (or hysteresis cooldown)
    "preempt_shrink",  # preemption notice: save + reshard before grace ends
    "shed_straggler",  # reshard a slow host out of the training ring
    "grow_train",      # spare capacity observed: reshard the world up
    "serve_up",        # serving overloaded, free chip available
    "serve_down",      # serving idle, no train demand for the chip
    "train_to_serve",  # serving overloaded, no free chip: take one from train
    "serve_to_train",  # serving idle: hand the chip to training
)


def _get_event_log():
    from ....observability.events import get_event_log

    return get_event_log()


def _m_decisions():
    from ....observability.metrics import get_registry

    return get_registry().counter(
        "fleet_decisions_total",
        help="elastic controller decisions actuated", labels=("action",))


@dataclass(frozen=True)
class FleetSignals:
    """One immutable snapshot of everything a decision may depend on.

    Frozen on purpose: ``ScalePolicy.decide`` takes nothing else, so
    pickling the snapshot sequence of a run is a complete replay script.
    ``last_scale_clock`` is the hysteresis state — it lives in the
    snapshot (stamped by whoever assembles it), NOT in the policy, so the
    policy object itself stays stateless.
    """

    clock: float                     # trace/virtual seconds, NOT wall time
    train_world: int
    serve_replicas: int
    total_chips: int
    free_chips: int = 0              # healthy chips assigned to neither side
    spare_hosts: int = 0             # registered members beyond the world
    step_time_p99_ms: float = 0.0
    step_time_skew: float = 0.0      # straggler gauge: (max-min)/mean step ms
    serve_queue_depth: int = 0
    serve_latency_p99_ms: float = 0.0
    preempt_notice: bool = False     # PreemptionHandler.requested (flag poll)
    preempt_grace_s: float = 0.0
    last_scale_clock: float = float("-inf")
    # telemetry-derived signals (ISSUE 18 SignalsAdapter). Defaulted so
    # snapshots recorded before the adapter existed still construct and
    # replay to the same decisions; a plant that doesn't expose them just
    # leaves the defaults.
    serve_ttft_p99_ms: float = 0.0   # windowed time-to-first-token tail
    slo_fast_burn: float = 0.0       # error-budget burn, fast window
    slo_slow_burn: float = 0.0       # error-budget burn, slow window
    heartbeat_age_max_s: float = 0.0  # oldest replica watchdog heartbeat
    # zero-cold-start plane (ISSUE 19). Defaulted for the same replay
    # reason: PR-17/18 snapshot sequences construct unchanged and decide
    # identically (nothing in ScalePolicy.decide reads these — they are
    # observability fields the decision records carry, stamped from the
    # ReplicaSet boot ledger via the warm_boot_counts duck-hook).
    warm_boots: int = 0              # cumulative warm boots completed ok
    warm_boot_timeouts: int = 0      # boots that fell back to cold


@dataclass(frozen=True)
class Decision:
    """One policy verdict for one tick."""

    action: str
    reason: str
    clock: float
    amount: int = 1

    def __post_init__(self):
        if self.action not in ACTIONS:
            raise ValueError(
                f"action must be one of {ACTIONS}, got {self.action!r}")


class ScalePolicy:
    """Deterministic goodput-maximizing scale policy.

    Priority order (first match wins):

    1. preemption notice  — the grace clock is already running; nothing
       outranks getting the emergency save + reshard done before it
       expires. Exempt from the cooldown for the same reason.
    2. straggler skew     — a slow host taxes every step of the whole
       ring; world−1 at full speed beats world at the straggler's pace.
    3. serve overload     — queue depth or tail latency over threshold:
       add a replica from the free pool, else take a chip from training
       (day traffic pays for itself in the availability term of goodput).
    4. serve idle         — replicas above the floor with an empty queue:
       hand chips back to training (night).
    5. spare capacity     — registered members beyond the world: grow.

    Rules 2-5 respect a cooldown of ``cooldown_s`` since
    ``signals.last_scale_clock`` so one burst of signal noise cannot
    thrash reshard/drain machinery whose cost the ledger charges.
    """

    def __init__(self, min_train_world: int = 1,
                 max_train_world: Optional[int] = None,
                 min_serve_replicas: int = 1,
                 max_serve_replicas: Optional[int] = None,
                 queue_high: int = 6, queue_low: int = 0,
                 serve_p99_high_ms: float = 2500.0,
                 skew_high: float = 0.5,
                 cooldown_s: float = 2.0,
                 slo_burn_high: Optional[float] = None,
                 warm_boot: bool = False):
        self.min_train_world = int(min_train_world)
        self.max_train_world = max_train_world
        self.min_serve_replicas = int(min_serve_replicas)
        self.max_serve_replicas = max_serve_replicas
        self.queue_high = int(queue_high)
        self.queue_low = int(queue_low)
        self.serve_p99_high_ms = float(serve_p99_high_ms)
        self.skew_high = float(skew_high)
        self.cooldown_s = float(cooldown_s)
        # SLO burn-rate trigger (ISSUE 18): OFF by default (None) so
        # decision sequences recorded before the burn signal existed
        # replay bit-identically; set (e.g. 1.0) to treat a slow-window
        # budget burn as serve overload alongside depth/latency.
        self.slo_burn_high = (None if slo_burn_high is None
                              else float(slo_burn_high))
        # zero-cold-start actuation (ISSUE 19): OFF by default so every
        # recorded decision sequence replays bit-identically (the knob
        # changes HOW serve_up/train_to_serve are actuated — warm standby
        # with readiness probe + boot budget — never WHAT is decided;
        # decide() does not read it).
        self.warm_boot = bool(warm_boot)

    # ------------------------------------------------------------ decide
    def decide(self, s: FleetSignals) -> Decision:
        """Pure: (signals) -> Decision. No reads of self beyond the
        constructor thresholds, no clocks, no RNG, no mutation."""
        train_can_shrink = s.train_world > self.min_train_world
        train_can_grow = (self.max_train_world is None
                          or s.train_world < self.max_train_world)
        serve_can_grow = (self.max_serve_replicas is None
                          or s.serve_replicas < self.max_serve_replicas)
        serve_can_shrink = s.serve_replicas > self.min_serve_replicas

        if s.preempt_notice and train_can_shrink:
            return Decision(
                "preempt_shrink", "preemption notice: emergency save + "
                "reshard inside the grace window", s.clock)

        if (s.clock - s.last_scale_clock) < self.cooldown_s:
            return Decision("none", "cooldown", s.clock)

        if s.step_time_skew >= self.skew_high and train_can_shrink:
            return Decision(
                "shed_straggler", "straggler skew over threshold: the ring "
                "is worth more without the slow host", s.clock)

        overloaded = (s.serve_queue_depth >= self.queue_high
                      or s.serve_latency_p99_ms >= self.serve_p99_high_ms
                      or (self.slo_burn_high is not None
                          and s.slo_slow_burn >= self.slo_burn_high))
        if overloaded and serve_can_grow:
            if s.free_chips > 0:
                return Decision(
                    "serve_up", "serving overloaded, free chip available",
                    s.clock)
            if train_can_shrink:
                return Decision(
                    "train_to_serve", "serving overloaded, no free chip: "
                    "arbitrating one away from training", s.clock)

        serve_idle = (s.serve_queue_depth <= self.queue_low
                      and s.serve_latency_p99_ms
                      < 0.5 * self.serve_p99_high_ms)
        if serve_idle and serve_can_shrink:
            if train_can_grow:
                return Decision(
                    "serve_to_train", "serving idle: handing the chip to "
                    "training", s.clock)
            return Decision(
                "serve_down", "serving idle above the replica floor",
                s.clock)

        if (s.free_chips > 0 or s.spare_hosts > 0) and train_can_grow \
                and not overloaded:
            return Decision(
                "grow_train", "spare capacity observed: growing the world",
                s.clock)

        return Decision("none", "steady state", s.clock)


class ReactivePolicy(ScalePolicy):
    """The pre-PR-17 baseline: never decides anything. Scale changes
    happen only as failure responses outside the policy (a crash after
    the grace window expires, a watchdog eviction) — exactly the repo's
    behavior before this controller existed. The fleet chaos phase runs
    the same trace under both policies; the goodput ratio between them is
    the controller's gated value."""

    def decide(self, s: FleetSignals) -> Decision:
        return Decision("none", "reactive baseline: failures only", s.clock)


# ---------------------------------------------------------------- ledger
LEDGER_ACCOUNTS = (
    "train_useful",  # chip-seconds advancing never-seen optimizer steps
    "serve_useful",  # chip-seconds a replica spent admitting/decoding
    "save",          # checkpoint commits (emergency or resize)
    "reshard",       # PR-10 shard-geometry transforms + rebuilds
    "compile",       # warm-up of a resized ring / freshly booted replica
    "drain",         # replica drain + preempted chip wind-down
    "recompute",     # replaying steps lost to a crash (reactive baseline)
    "idle",          # healthy chip, no work assigned
)


class GoodputLedger:
    """Chip-second accounting: every chip-second of the fleet horizon is
    attributed to exactly one of :data:`LEDGER_ACCOUNTS`.

    Goodput is the metric fleets buy — useful tokens per second times
    availability::

        goodput = (train_tokens + serve_tokens) / horizon_s * availability

    where availability is the serve completion fraction (completed /
    submitted) over the horizon. ``verify_conservation`` checks that the
    accounts sum to the chip-seconds that actually existed — an
    attribution that silently drops time would flatter any policy.
    """

    def __init__(self):
        self.accounts: Dict[str, float] = {a: 0.0 for a in LEDGER_ACCOUNTS}
        self.train_tokens = 0
        self.serve_tokens = 0
        self.serve_submitted = 0
        self.serve_completed = 0

    def charge(self, account: str, chips: float, seconds: float = 1.0):
        if account not in self.accounts:
            raise ValueError(
                f"account must be one of {LEDGER_ACCOUNTS}, got {account!r}")
        self.accounts[account] += float(chips) * float(seconds)

    def tokens(self, kind: str, n: int):
        if kind == "train":
            self.train_tokens += int(n)
        elif kind == "serve":
            self.serve_tokens += int(n)
        else:
            raise ValueError(f"kind must be train|serve, got {kind!r}")

    @property
    def chip_seconds(self) -> float:
        return sum(self.accounts.values())

    @property
    def availability(self) -> float:
        if self.serve_submitted == 0:
            return 1.0
        return self.serve_completed / self.serve_submitted

    def goodput(self, horizon_s: float) -> float:
        toks = self.train_tokens + self.serve_tokens
        return (toks / float(horizon_s)) * self.availability

    def verify_conservation(self, expected_chip_seconds: float,
                            tol: float = 1e-6) -> bool:
        return abs(self.chip_seconds - expected_chip_seconds) <= tol

    def summary(self) -> dict:
        total = self.chip_seconds or 1.0
        return {
            "accounts": {k: round(v, 3) for k, v in self.accounts.items()},
            "chip_seconds": round(self.chip_seconds, 3),
            "useful_fraction": round(
                (self.accounts["train_useful"]
                 + self.accounts["serve_useful"]) / total, 4),
            "train_tokens": self.train_tokens,
            "serve_tokens": self.serve_tokens,
            "serve_submitted": self.serve_submitted,
            "serve_completed": self.serve_completed,
            "availability": round(self.availability, 4),
        }


# ------------------------------------------------------------ controller
class FleetController:
    """Signal → decision → actuation loop over duck-typed plants.

    ``train`` must expose: ``world`` (int), ``step_time_p99_ms()``,
    ``step_time_skew()``, ``preempt_pending()`` (the flag-file poll),
    ``preempt_grace_s()``, and the actuators ``preempt_shrink()``,
    ``shed_straggler()``, ``grow()``, ``release_chip()``.

    ``serve`` must expose: ``replicas`` (int), ``queue_depth`` (int),
    ``latency_p99_ms()``, and the actuators ``scale_up()``,
    ``scale_down()`` (the PR-14 drain + re-admit path).

    The controller owns chip inventory (``total_chips`` −
    ``quarantined`` − assigned = free) and the hysteresis clock; the
    policy owns nothing. ``tick(clock)`` assembles the snapshot, asks the
    policy, actuates, and appends ``(signals, decision)`` to
    ``self.records`` — the replay log the determinism test re-decides
    from.
    """

    def __init__(self, policy: ScalePolicy, train, serve,
                 total_chips: int, ledger: Optional[GoodputLedger] = None):
        self.policy = policy
        self.train = train
        self.serve = serve
        self.total_chips = int(total_chips)
        self.quarantined = 0
        self.ledger = ledger or GoodputLedger()
        self.records: List[tuple] = []   # (FleetSignals, Decision)
        self.decisions: List[Decision] = []  # non-noop only
        # actuation OUTCOMES (ISSUE 19): what happened when a decision
        # ran — e.g. a warm serve_up that overran its boot budget records
        # outcome="warm_boot_timeout" here. Kept OUT of self.records so
        # replay stays a pure function of (signals, decision).
        self.actuations: List[dict] = []
        self._last_scale_clock = float("-inf")

    # ------------------------------------------------------------ signals
    @property
    def free_chips(self) -> int:
        return max(0, self.total_chips - self.quarantined
                   - self.train.world - self.serve.replicas)

    def signals(self, clock: float) -> FleetSignals:
        # a telemetry-backed serve plant (signals.SignalsAdapter) advances
        # its histogram windows on the decision clock; plants without the
        # hook (and without the optional signal methods below) are served
        # by the FleetSignals defaults
        observe = getattr(self.serve, "observe", None)
        if observe is not None:
            observe(float(clock))
        zero = lambda: 0.0  # noqa: E731 - duck default
        burn = getattr(self.serve, "slo_burn", None)
        fast_burn, slow_burn = burn() if burn is not None else (0.0, 0.0)
        counts = getattr(self.serve, "warm_boot_counts", None)
        boot_counts = counts() if counts is not None else {}
        return FleetSignals(
            clock=float(clock),
            train_world=int(self.train.world),
            serve_replicas=int(self.serve.replicas),
            total_chips=self.total_chips,
            free_chips=self.free_chips,
            spare_hosts=int(getattr(self.train, "spare_hosts", lambda: 0)()),
            step_time_p99_ms=float(self.train.step_time_p99_ms()),
            step_time_skew=float(self.train.step_time_skew()),
            serve_queue_depth=int(self.serve.queue_depth),
            serve_latency_p99_ms=float(self.serve.latency_p99_ms()),
            preempt_notice=bool(self.train.preempt_pending()),
            preempt_grace_s=float(self.train.preempt_grace_s()),
            last_scale_clock=self._last_scale_clock,
            serve_ttft_p99_ms=float(
                getattr(self.serve, "ttft_p99_ms", zero)()),
            slo_fast_burn=float(fast_burn),
            slo_slow_burn=float(slow_burn),
            heartbeat_age_max_s=float(
                getattr(self.serve, "heartbeat_age_max_s", zero)()),
            warm_boots=int(boot_counts.get("warm_boots", 0)),
            warm_boot_timeouts=int(
                boot_counts.get("warm_boot_timeouts", 0)),
        )

    # --------------------------------------------------------------- tick
    def tick(self, clock: float) -> Decision:
        s = self.signals(clock)
        d = self.policy.decide(s)
        self.records.append((s, d))
        if d.action != "none":
            self._actuate(d)
        return d

    def replay(self) -> bool:
        """Re-decide every recorded snapshot; True iff the decision
        sequence is bit-identical (the determinism contract)."""
        return all(self.policy.decide(s) == d for s, d in self.records)

    # ------------------------------------------------------------ actuate
    def _serve_scale_up(self):
        """serve_up/train_to_serve actuation. With the policy's
        ``warm_boot`` knob on, the replica boots as a warm standby
        (pre-compiled, readiness-probed, budget-bounded — ISSUE 19);
        plants without the ``warm=`` kwarg or a boot ledger fall back to
        the plain cold scale_up. Returns the boot outcome string."""
        if getattr(self.policy, "warm_boot", False):
            try:
                self.serve.scale_up(warm=True)
            except TypeError:  # plant predates the warm kwarg
                self.serve.scale_up()
                return "ok"
            boot = getattr(self.serve, "last_boot", None)
            if boot and boot.get("mode") == "cold":
                # warm path fell back: the PREVIOUS record is the timeout
                return "warm_boot_timeout"
            return "ok"
        self.serve.scale_up()
        return "ok"

    def _actuate(self, d: Decision):
        outcome = "ok"
        if d.action == "preempt_shrink":
            self.train.preempt_shrink()
        elif d.action == "shed_straggler":
            self.quarantined += 1   # the slow host is not free capacity
            self.train.shed_straggler()
        elif d.action == "grow_train":
            self.train.grow()
        elif d.action == "serve_up":
            outcome = self._serve_scale_up()
        elif d.action == "serve_down":
            self.serve.scale_down()
        elif d.action == "train_to_serve":
            self.train.release_chip()
            outcome = self._serve_scale_up()
        elif d.action == "serve_to_train":
            self.serve.scale_down()
            self.train.grow()
        else:  # pragma: no cover - Decision.__post_init__ guards this
            raise ValueError(f"unknown action {d.action!r}")
        self._last_scale_clock = d.clock
        self.decisions.append(d)
        self.actuations.append(
            {"action": d.action, "clock": d.clock, "outcome": outcome})
        _m_decisions().labels(action=d.action).inc()
        _get_event_log().info(
            "fleet", f"decision actuated: {d.action}", action=d.action,
            reason=d.reason, outcome=outcome, clock=round(d.clock, 3),
            train_world=int(self.train.world),
            serve_replicas=int(self.serve.replicas),
            free_chips=self.free_chips)

    # ----------------------------------------------------------- exposure
    def decision_log(self) -> List[dict]:
        return [{"action": d.action, "clock": d.clock, "reason": d.reason}
                for d in self.decisions]
