"""HBM / host memory accounting: the number every ROADMAP item gates on.

Every capacity decision in this codebase — does ZeRO-3 actually shrink the
resident set, does batch 256 fit v5e's 16 GiB, is the KV cache budget real
— reduces to "peak HBM bytes vs the roofline", and until now that number
existed only inside one-off AOT probes. This module makes it a metric:

- **eager path** — ``live_tensor_bytes()`` sums the bytes of every live
  ``jax.Array`` in the process (the eager dispatch path's working set:
  parameters, grads, activations still referenced). ``sample()`` reads it
  plus the PJRT allocator's view (``device.memory_stats()``: bytes_in_use
  / peak_bytes_in_use — TPU only; None on CPU) into the
  ``live_tensor_bytes`` / ``hbm_bytes_in_use`` / ``peak_hbm_bytes``
  gauges.
- **compiled path** — ``analyze_compiled()`` reads XLA's
  ``memory_analysis()`` off a compiled executable (argument + temp +
  output - aliased = the compiler's peak for one invocation) and
  ``record_compiled(entry, ...)`` keys it by trace-cache entry (the
  ``compiled_peak_hbm_bytes{entry=...}`` gauge), so every cached program's
  footprint is inspectable. ``jit.TrainStep.memory_analysis()`` rides
  this.

Everything degrades to None/{} rather than raising: memory accounting
must never be the thing that kills a job.
"""
from __future__ import annotations

import threading
from typing import Dict, Optional

from .events import get_event_log
from .metrics import get_registry

__all__ = [
    "live_tensor_bytes", "device_memory_stats", "sample",
    "LiveBytesWatermark", "sample_watermarks",
    "analyze_compiled", "record_compiled", "compiled_memory",
]

_m_live = get_registry().gauge(
    "live_tensor_bytes",
    help="bytes held by live jax arrays (eager-path working set)")
_m_in_use = get_registry().gauge(
    "hbm_bytes_in_use",
    help="device allocator bytes currently in use (PJRT memory_stats; "
         "0 where the backend reports none)")
_m_peak = get_registry().gauge(
    "peak_hbm_bytes",
    help="device allocator peak bytes in use (PJRT memory_stats; 0 where "
         "the backend reports none)")
_m_compiled = get_registry().gauge(
    "compiled_peak_hbm_bytes",
    help="XLA memory_analysis peak for a compiled program",
    labels=("entry",))

_compiled_lock = threading.Lock()
_compiled: Dict[str, dict] = {}     # entry key -> analysis dict


# ---------------------------------------------------------------------------
# live / allocator accounting (eager path)
# ---------------------------------------------------------------------------

def live_tensor_bytes() -> Optional[int]:
    """Total bytes of every live jax.Array in the process — the eager
    dispatch path's resident tensor set. None when jax (or the API) is
    unavailable."""
    try:
        import jax

        return int(sum(a.nbytes for a in jax.live_arrays()))
    except Exception:
        return None


def device_memory_stats(device=None) -> Optional[dict]:
    """PJRT allocator stats for one device ({bytes_in_use,
    peak_bytes_in_use, ...} on TPU; None on backends that don't report)."""
    try:
        import jax

        dev = device if device is not None else jax.local_devices()[0]
        stats = dev.memory_stats()
        return dict(stats) if stats else None
    except Exception:
        return None


def sample(registry=None) -> dict:
    """One accounting sample; updates the gauges and returns the reading.
    Cheap enough for a per-dump cadence (MetricsCallback), too expensive
    for per-op — live_arrays() walks every registered buffer."""
    live = live_tensor_bytes()
    stats = device_memory_stats()
    out = {"live_tensor_bytes": live}
    if live is not None:
        _m_live.set(int(live))
    if stats:
        out["bytes_in_use"] = int(stats.get("bytes_in_use", 0))
        out["peak_bytes_in_use"] = int(stats.get("peak_bytes_in_use", 0))
        _m_in_use.set(out["bytes_in_use"])
        _m_peak.set(out["peak_bytes_in_use"])
    return out


# ---------------------------------------------------------------------------
# live-bytes watermark (ZeRO-3 free-after-use proof, ISSUE 9)
# ---------------------------------------------------------------------------
# Deterministic, thread-free peak tracking: code that transitions tensor
# lifetimes (the stage-3 store's gather/free points) calls
# sample_watermarks() at each transition, so any active watermark sees the
# peak at exactly the moments live bytes can change. A poller would race
# the transitions and under-read the peak.

_watermark_lock = threading.Lock()
_active_watermarks = []


class LiveBytesWatermark:
    """Peak live-jax-bytes over a window.

        with LiveBytesWatermark() as wm:
            model(x)                   # stage-3 hooks sample at gather/free
        assert wm.delta <= 2 * bucket_bytes + slack

    ``baseline`` is the live-byte reading at entry, ``peak`` the maximum
    seen by any sample() during the window (entry and exit are sampled
    too), ``delta`` the watermark above baseline — for a sharded-at-rest
    model, the bytes the gathered full parameters (plus activations)
    transiently added."""

    def __init__(self):
        self.baseline = 0
        self.peak = 0
        self.n_samples = 0

    def sample(self):
        v = live_tensor_bytes()
        if v is not None:
            self.peak = max(self.peak, int(v))
            self.n_samples += 1
        return v

    @property
    def delta(self) -> int:
        return max(0, self.peak - self.baseline)

    def __enter__(self):
        self.baseline = int(live_tensor_bytes() or 0)
        self.peak = self.baseline
        self.n_samples = 0
        with _watermark_lock:
            _active_watermarks.append(self)
        return self

    def __exit__(self, *exc):
        with _watermark_lock:
            if self in _active_watermarks:
                _active_watermarks.remove(self)
        self.sample()
        return False


def sample_watermarks():
    """Feed every active LiveBytesWatermark one reading — called by code
    that just changed tensor lifetimes (stage-3 gather/free). Free when no
    watermark is active."""
    with _watermark_lock:
        if not _active_watermarks:
            return
        active = list(_active_watermarks)
    for wm in active:
        wm.sample()


# ---------------------------------------------------------------------------
# compiled-path accounting (XLA memory_analysis, keyed by cache entry)
# ---------------------------------------------------------------------------

def analyze_compiled(compiled) -> Optional[dict]:
    """XLA's memory analysis of one compiled executable. Peak =
    arguments + temps + outputs - aliased (donated buffers alias their
    outputs), the same accounting models/gpt.py's AOT estimator uses.
    None when the backend doesn't report."""
    try:
        mem = compiled.memory_analysis()
    except Exception:
        return None
    if mem is None:
        return None
    out = {
        "argument_bytes": int(mem.argument_size_in_bytes),
        "output_bytes": int(mem.output_size_in_bytes),
        "temp_bytes": int(mem.temp_size_in_bytes),
        "alias_bytes": int(mem.alias_size_in_bytes),
        "generated_code_bytes": int(mem.generated_code_size_in_bytes),
    }
    out["peak_hbm_bytes"] = (out["argument_bytes"] + out["temp_bytes"]
                             + out["output_bytes"] - out["alias_bytes"])
    return out


def record_compiled(entry: str, compiled_or_analysis) -> Optional[dict]:
    """Record one trace-cache entry's compiled-path footprint; `entry` is
    the cache key label (e.g. "train_step[...]"). Accepts either a
    compiled executable or an already-built analysis dict. Returns the
    analysis (None if unavailable)."""
    if isinstance(compiled_or_analysis, dict):
        analysis = dict(compiled_or_analysis)
    else:
        analysis = analyze_compiled(compiled_or_analysis)
    if analysis is None:
        return None
    with _compiled_lock:
        _compiled[str(entry)] = analysis
    try:
        _m_compiled.labels(entry=str(entry)).set(
            int(analysis["peak_hbm_bytes"]))
    except (KeyError, TypeError, ValueError) as e:
        # a malformed analysis dict must not break memory recording, but
        # the drop is visible in the event log (rule C003)
        get_event_log().warning("memory", "compiled-peak gauge not set",
                                entry=str(entry), error=repr(e))
    return analysis


def compiled_memory() -> Dict[str, dict]:
    """{entry: analysis} of every recorded compiled program."""
    with _compiled_lock:
        return {k: dict(v) for k, v in _compiled.items()}
