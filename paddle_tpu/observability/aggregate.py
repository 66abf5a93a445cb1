"""Cross-rank metric aggregation: per-kind reduction to a rank-0 view.

PR 3's registry is strictly process-local — on a 64-chip job there are 64
`collectives_total` counters and nobody sums them. This module makes the
job-wide view a first-class artifact:

- every rank snapshots its registry (``MetricsRegistry.typed_snapshot`` —
  the snapshot keeps each family's KIND so the merge applies the right
  rule) plus its recent step-time stats;
- the payloads are exchanged through the guarded collective layer
  (``distributed/collective.all_gather`` → ``execute_collective``), so the
  PR-4 machinery — group timeouts, transient retries, chaos injection —
  applies to the telemetry exchange exactly as it does to gradient
  traffic. Telemetry must never wedge training: an exchange that exhausts
  its retries degrades to a local-only aggregate, bumps
  ``telemetry_aggregation_failures_total``, and returns;
- rank 0 merges: **counters sum**, **gauges reduce to min/max/mean**,
  **histogram buckets add element-wise** (counts/sums add, min/max merge —
  quantiles of the merged histogram are the job-wide percentiles);
- the per-rank step-time spread is surfaced as the ``step_time_skew``
  straggler gauge: (max - min) / mean of the per-rank mean step seconds.
  A healthy SPMD job sits near 0; a straggling host shows up as a number,
  not as "rank 17 feels slow".

Emulated multi-rank (this repo's single-process test reality) plugs in via
``gather_fn`` exactly like ``ReplicaGuard.reduce_fn`` does.
"""
from __future__ import annotations

import json
import threading
import time
from collections import deque
from typing import Callable, List, Optional

from .events import get_event_log
from .metrics import get_registry

__all__ = [
    "MetricsAggregator", "merge_payloads", "merge_typed_snapshots",
    "note_step_time", "local_step_stats", "aggregated_to_plain",
]

# ---------------------------------------------------------------------------
# per-rank step-time tracker (fed by hapi's MetricsCallback / training loops;
# read into every aggregation payload so rank 0 can compute the skew gauge)
# ---------------------------------------------------------------------------

_STEP_WINDOW = 64
_step_times = deque(maxlen=_STEP_WINDOW)
_step_lock = threading.Lock()


def note_step_time(seconds: float):
    """Record one training step's wall seconds into the rank-local window
    the aggregation payload reports (bounded; O(1))."""
    with _step_lock:
        _step_times.append(float(seconds))


def local_step_stats() -> dict:
    with _step_lock:
        times = list(_step_times)
    if not times:
        return {"steps": 0, "mean_s": None, "last_s": None}
    return {"steps": len(times), "mean_s": sum(times) / len(times),
            "last_s": times[-1]}


# ---------------------------------------------------------------------------
# merge rules
# ---------------------------------------------------------------------------

def _merge_counter(values: List[float]):
    return sum(values)


def _merge_gauge(values: List[float]):
    vals = [float(v) for v in values]
    return {"min": min(vals), "max": max(vals),
            "mean": sum(vals) / len(vals)}


def _merge_histogram(states: List[dict]) -> dict:
    """Element-wise bucket addition. Ranks declare histograms from the same
    code, so bounds agree by construction; a mismatch (version skew during
    a rolling restart) falls back to count/sum-only so the merge never
    throws inside a telemetry path."""
    base = states[0]
    bounds = list(base["bounds"])
    if all(list(s["bounds"]) == bounds for s in states[1:]):
        bucket_counts = [sum(s["bucket_counts"][i] for s in states)
                         for i in range(len(bounds))]
    else:
        bounds, bucket_counts = [], []
    mins = [s["min"] for s in states if s["min"] is not None]
    maxs = [s["max"] for s in states if s["max"] is not None]
    count = sum(s["count"] for s in states)
    out = {
        "bounds": bounds,
        "bucket_counts": bucket_counts,
        "count": count,
        "sum": sum(s["sum"] for s in states),
        "min": min(mins) if mins else None,
        "max": max(maxs) if maxs else None,
    }
    out["mean"] = out["sum"] / count if count else 0.0
    if bounds and count:
        from .metrics import Histogram

        h = Histogram(buckets=bounds)
        h.bucket_counts = list(bucket_counts)
        h.count, h.sum = count, out["sum"]
        h.min, h.max = out["min"], out["max"]
        out["p50"] = h.quantile(0.5)
        out["p95"] = h.quantile(0.95)
        out["p99"] = h.quantile(0.99)
    return out


_MERGE = {"counter": _merge_counter, "gauge": _merge_gauge,
          "histogram": _merge_histogram}


def merge_typed_snapshots(snapshots: List[dict]) -> dict:
    """Merge per-rank `MetricsRegistry.typed_snapshot()` dicts under the
    per-kind reduction rules. Families/labels missing on some ranks merge
    over the ranks that have them (a late-joining rank must not zero the
    fleet's counters)."""
    merged = {}
    names = sorted({n for s in snapshots for n in s})
    for name in names:
        fams = [s[name] for s in snapshots if name in s]
        kind = fams[0]["kind"]
        rule = _MERGE[kind]
        child_keys = sorted({k for f in fams for k in f["children"]})
        children = {}
        for key in child_keys:
            vals = [f["children"][key] for f in fams if key in f["children"]]
            children[key] = rule(vals)
        merged[name] = {"kind": kind, "help": fams[0]["help"],
                        "labels": fams[0]["labels"], "ranks": len(fams),
                        "children": children}
    return merged


def _skew(step_stats: List[dict]) -> dict:
    means = [s["mean_s"] for s in step_stats if s.get("mean_s")]
    out = {"per_rank_mean_s": means}
    if len(means) >= 1 and sum(means):
        mean = sum(means) / len(means)
        out["skew"] = (max(means) - min(means)) / mean if mean else 0.0
        out["slowest_rank"] = max(range(len(means)), key=means.__getitem__)
    else:
        out["skew"] = 0.0
    return out


def merge_payloads(payloads: List[dict]) -> dict:
    """Merge full per-rank payloads ({"rank", "step_time", "metrics"})
    into the rank-0 aggregate record."""
    merged = {
        "time": time.time(),
        "ranks": sorted(p.get("rank", i) for i, p in enumerate(payloads)),
        "metrics": merge_typed_snapshots([p["metrics"] for p in payloads]),
        "step_time": _skew([p.get("step_time", {}) for p in payloads]),
    }
    return merged


def aggregated_to_plain(merged_metrics: dict) -> dict:
    """Flatten a merged typed snapshot back to the plain snapshot() shape
    (counters/gauges as values, histograms as stats dicts) so existing
    consumers read an aggregate exactly like a local snapshot. Labelled families keep their {label: value}
    sub-dicts; unlabelled collapse to the bare value."""
    out = {}
    for name, fam in merged_metrics.items():
        children = {}
        for key, v in fam["children"].items():
            if fam["kind"] == "gauge" and isinstance(v, dict):
                children[key] = v["mean"] if v["min"] == v["max"] else v
            else:
                children[key] = v
        out[name] = children.get("", children) if "" in children else children
    return out


# ---------------------------------------------------------------------------
# the aggregator
# ---------------------------------------------------------------------------

_m_aggs = get_registry().counter(
    "telemetry_aggregations_total",
    help="cross-rank metric aggregation rounds completed").bind()
_m_agg_fail = get_registry().counter(
    "telemetry_aggregation_failures_total",
    help="aggregation exchanges that degraded to local-only "
         "(collective timeout/transient exhaustion)").bind()
_m_skew = get_registry().gauge(
    "step_time_skew",
    help="(max - min) / mean of per-rank mean step seconds — straggler "
         "indicator, ~0 on a healthy job")


class MetricsAggregator:
    """Periodic cross-rank aggregation driver.

        agg = MetricsAggregator(group=telemetry_group)
        ...
        record = agg.aggregate()        # rank-0 merged view (or local-only
                                        # degraded record under faults)

    `gather_fn(payload_dict) -> [payload_dict, ...]` overrides the
    exchange — the chaos harness and single-process tests emulate an
    N-rank world with it (mirroring ReplicaGuard.reduce_fn). The default
    exchange serializes the payload to JSON bytes and all_gathers them
    through the guarded collective layer, so group timeouts / retries /
    chaos interposers apply to telemetry like any other traffic.

    `last` always holds the newest aggregate; `aggregate()` never raises
    out of a telemetry exchange — a fault degrades to the local view and
    counts on telemetry_aggregation_failures_total.
    """

    def __init__(self, group=None, gather_fn: Optional[Callable] = None,
                 registry=None):
        self.group = group
        self.gather_fn = gather_fn
        self.registry = registry or get_registry()
        self.last: Optional[dict] = None
        self.failures = 0

    # ---------------------------------------------------------- payloads
    def local_payload(self) -> dict:
        from ..distributed.env import get_rank

        return {"rank": int(get_rank()), "time": time.time(),
                "step_time": local_step_stats(),
                "metrics": self.registry.typed_snapshot()}

    def _default_gather(self, payload: dict) -> List[dict]:
        """JSON-bytes all_gather over the guarded collective layer."""
        import jax.numpy as jnp
        import numpy as np

        from ..distributed import collective as coll
        from ..framework.tensor import Tensor

        raw = json.dumps(payload).encode()
        buf = np.frombuffer(raw, dtype=np.uint8)
        outs = coll.all_gather([], Tensor(jnp.asarray(buf), _internal=True),
                               group=self.group)
        return [json.loads(bytes(np.asarray(o.numpy())).decode())
                for o in outs]

    # --------------------------------------------------------- aggregate
    def aggregate(self) -> dict:
        """One aggregation round. Returns the merged record; on exchange
        failure returns a `degraded: True` local-only record instead of
        raising (telemetry must never take training down with it)."""
        payload = self.local_payload()
        degraded = None
        try:
            gather = self.gather_fn or self._default_gather
            payloads = list(gather(payload)) or [payload]
        except Exception as e:  # CollectiveTimeoutError, transients, ...
            self.failures += 1
            _m_agg_fail.value += 1
            get_event_log().warning(
                "telemetry", "aggregation exchange failed; using local view",
                error=repr(e))
            payloads = [payload]
            degraded = repr(e)
        record = merge_payloads(payloads)
        if degraded is not None:
            record["degraded"] = degraded
        _m_aggs.value += 1
        _m_skew.set(round(record["step_time"].get("skew", 0.0), 6))
        record["step_time_skew"] = record["step_time"].get("skew", 0.0)
        self.last = record
        return record
