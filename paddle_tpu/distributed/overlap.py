"""Overlapped gradient communication: bucket-ready async all-reduce.

PR 1's `grad_comm.GradCommunicator.sync` runs as one serial phase after
backward finishes — on the step breakdown (observability.StepTimer) the comm
time is fully exposed, none hidden under backward compute. This module hides
it ("Automatic Cross-Replica Sharding of Weight Update in Data-Parallel
Training", arXiv:2004.13336; EQuARX, arXiv:2506.17615: quantized all-reduce
composes with async collectives):

- **Eager path** (`OverlappedGradCommunicator`): `prepare()` installs the
  autograd grad-ready hook (`framework.autograd.set_grad_ready_hook` — the
  Reducer's MarkVarReady analog). The moment the LAST grad of a bucket is
  deposited, the bucket's collective launches on a background
  `CollectiveLane` (one worker thread, FIFO — so collectives keep a total
  order per rank) while the rest of backward keeps running on the main
  thread. Every collective still goes through `collective.py` →
  `robustness/distributed_ft.execute_collective`, so group timeouts,
  retries, backoff, and chaos injection keep working unchanged. `flush()`
  (called by `sync()` / `apply_collective_grads`) is the step barrier: it
  launches any bucket whose grads appeared after backward (e.g.
  `find_unused_parameters` zero-fills), waits the lane out, surfaces the
  first error, and records the overlap telemetry. Results are BIT-IDENTICAL
  to the serial path: the flatten → encode → collective → decode → scatter
  pipeline is `GradCommunicator`'s own, per bucket, and buckets are
  independent (int8 error-feedback residuals are per bucket).
- **In-trace path** (`sync_async` / `BucketFuture`): inside a
  shard_map/pjit trace each bucket's psum/psum_scatter is issued as its own
  op and returned as a per-bucket future instead of being consumed at one
  barrier. XLA's latency-hiding scheduler is then free to overlap bucket
  k+1's collective with whatever consumes bucket k — the fused flat-buffer
  optimizer update (optimizer/fused.py) consumes the futures one by one for
  exactly this reason. The configured wire codec applies HERE TOO (ISSUE
  8): quantize -> psum-of-int -> dequantize is part of the compiled
  program, with error-feedback residuals threaded as carried state
  (`residuals=` in, `fut.residual` out — jit.TrainStep(grad_comm=) does
  the threading for a whole train step). Eagerly the same call returns
  already-resolved futures (jax dispatch is itself async).

Telemetry: per-bucket `comm_launch:bucket{i}` marker spans are emitted on
the MAIN thread inside backward (proof of launch-before-backward-end in the
step trace) and `comm:bucket{i}` spans on the lane thread carry the actual
transfer window; flush emits a `comm` span for the exposed wait. The
`grad_comm_overlap_efficiency` gauge is hidden_comm_time/total_comm_time of
the last flush.
"""
from __future__ import annotations

import threading
from collections import deque
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..framework import autograd as _autograd
from ..observability.flight_recorder import get_flight_recorder
from ..observability.metrics import get_registry as _get_registry
from ..profiler import now_ns
from .grad_comm import GradBucket, GradCommConfig, GradCommunicator

__all__ = [
    "BucketFuture", "CollectiveLane", "GatherFuture",
    "OverlappedGradCommunicator", "communicator_for",
]

_m_overlap_eff = _get_registry().gauge(
    "grad_comm_overlap_efficiency",
    help="hidden_comm_time / total_comm_time of the last overlapped sync")
_m_overlap_syncs = _get_registry().counter(
    "grad_comm_overlapped_syncs_total",
    help="gradient syncs that ran in bucket-ready overlapped mode").bind()
_m_early = _get_registry().counter(
    "grad_comm_buckets_launched_early_total",
    help="buckets whose collective launched before backward finished").bind()


def communicator_for(config: Optional[GradCommConfig] = None, group=None):
    """GradCommunicator (serial) or OverlappedGradCommunicator, per
    `config.overlap` — the one constructor call sites need."""
    config = config or GradCommConfig()
    cls = OverlappedGradCommunicator if config.overlap else GradCommunicator
    return cls(config, group=group)


class BucketFuture:
    """Handle for one in-flight (or in-trace) bucket reduction.

    Eager/overlapped: resolved by the CollectiveLane worker; `wait()` blocks.
    In-trace: holds the already-issued collective's lazy value; `wait()` is
    immediate (XLA owns the schedule).
    """

    __slots__ = ("bucket", "_value", "_error", "_done", "launch_ns",
                 "start_ns", "end_ns", "scatter", "residual")

    def __init__(self, bucket: GradBucket, value=None, resolved=False):
        self.bucket = bucket
        self._value = value
        self._error = None
        self._done = threading.Event()
        if resolved:
            self._done.set()
        self.launch_ns = None   # submit time (main thread, inside backward)
        self.start_ns = None    # lane-side work window
        self.end_ns = None
        # error-feedback residual of this bucket's encode (sync_async):
        # None for codecs without error feedback. In-trace this is the
        # carried-state output the caller must thread into the next step
        # (jit.TrainStep does); eagerly the communicator already kept it.
        self.residual = None

    def _resolve(self, value):
        self._value = value
        self._done.set()

    def _fail(self, err):
        self._error = err
        self._done.set()

    def done(self) -> bool:
        return self._done.is_set()

    def wait(self, timeout=None):
        """Block until resolved; returns the reduced flat buffer (raises
        the lane-side error, if any)."""
        if not self._done.wait(timeout):
            raise TimeoutError(
                f"bucket {self.bucket.index} collective did not complete "
                f"within {timeout}s")
        if self._error is not None:
            raise self._error
        return self._value

    result = wait

    def __repr__(self):
        state = ("error" if self._error is not None
                 else "done" if self.done() else "pending")
        return f"BucketFuture(bucket={self.bucket.index}, {state})"


class GatherFuture(BucketFuture):
    """Handle for one in-flight ZeRO-3 parameter-bucket all_gather — the
    second CollectiveLane client (distributed/sharding/stage3.py), running
    the grad lane's collective in the inverse direction: shards in, full
    flat parameter buffer out. Launch/start/end timestamps carry the
    prefetch-vs-exposed accounting exactly like a grad BucketFuture's."""

    __slots__ = ()


class CollectiveLane:
    """Background collective lane: one daemon worker draining a FIFO.

    One lane = one thread = a total order over the collectives it runs, the
    same property a dedicated comm stream gives NCCL — ranks launching
    buckets in the same (deterministic, bucket-completion) order cannot
    deadlock. The worker exits when idle and is respawned on demand, so an
    idle communicator holds no thread.
    """

    def __init__(self, name="grad-comm-lane"):
        self.name = name
        self._lock = threading.Lock()
        self._jobs = deque()
        self._thread: Optional[threading.Thread] = None

    def submit(self, fn) -> threading.Event:
        """Queue fn for FIFO execution; returns its completion event."""
        done = threading.Event()
        with self._lock:
            self._jobs.append((fn, done))
            if self._thread is None or not self._thread.is_alive():
                self._thread = threading.Thread(
                    target=self._run, daemon=True, name=self.name)
                self._thread.start()
        return done

    def _run(self):
        while True:
            with self._lock:
                if not self._jobs:
                    if self._thread is threading.current_thread():
                        self._thread = None
                    return
                fn, done = self._jobs.popleft()
            try:
                fn()
            finally:
                done.set()


class OverlappedGradCommunicator(GradCommunicator):
    """GradCommunicator whose buckets launch as backward produces them.

    Protocol (what `DataParallel` does when the strategy's
    ``grad_comm_configs["overlap"]`` is on):

        comm.prepare(params, world)      # before backward: install hooks
        loss.backward()                  # buckets launch as they complete
        comm.sync(params, world)         # == flush(): barrier + write-back

    `sync()` on a prepared step is the flush barrier; on an unprepared step
    it falls back to the serial path (still correct, nothing hidden), so
    call sites need no mode branching. Overlapped mode requires each grad's
    dtype to match its parameter's (true for this framework's eager tape;
    the hook checks and fails loudly otherwise rather than silently
    re-bucketing differently from the serial path).
    """

    def __init__(self, config: Optional[GradCommConfig] = None, group=None):
        super().__init__(config, group)
        self._lane = CollectiveLane()
        self._step = None            # per-backward state; None = not prepared
        self._prev_hook = None
        self.last_timeline: List[dict] = []

    # ------------------------------------------------------------- prepare
    def prepare(self, params, world: Optional[int] = None,
                use_reduce_scatter: bool = False):
        """Arm the next backward: build the bucket plan from the (reverse
        traversal order) parameter list and install the grad-ready hook.
        No-op (returns self) when world <= 1 or there is nothing to sync."""
        self.abandon()   # a re-arm must not leak the previous step's hook
        params = [p for p in params if not p.stop_gradient]
        if world is None:
            from .env import get_world_size

            world = get_world_size()
        if world <= 1 or not params:
            return self
        # grads don't exist yet: bucket on the param dtypes, which is what
        # the eager tape's cotangents carry (checked at hook time)
        dtypes = [np.dtype(p._value.dtype) for p in params]
        buckets = self.buckets_for(params, dtypes=dtypes)
        by_param: Dict[int, GradBucket] = {}
        for b in buckets:
            for pi in b.param_indices:
                by_param[id(params[pi])] = b
        self._step = {
            "params": params,
            "world": int(world),
            "use_reduce_scatter": bool(use_reduce_scatter),
            "buckets": buckets,
            "by_param": by_param,
            "remaining": {b.index: len(b.param_indices) for b in buckets},
            "futures": {},           # bucket index -> BucketFuture
            "dtype_error": None,
        }
        self.stats = {"codec": self.config.codec, "path": "eager",
                      "n_params": len(params), "n_buckets": len(buckets),
                      "collectives": 0, "comm_bytes": 0}
        self._prev_hook = _autograd.set_grad_ready_hook(self._on_grad_ready)
        return self

    # ---------------------------------------------------------- hook + lane
    def _on_grad_ready(self, tensor):
        st = self._step
        if st is None:
            return
        b = st["by_param"].get(id(tensor))
        if b is None:
            return
        grad = tensor.grad
        if grad is not None and np.dtype(grad._value.dtype) != b.dtype:
            # re-bucketing by grad dtype here would silently diverge from
            # the serial assignment (and the int8 residual keys) — refuse
            st["dtype_error"] = (
                f"overlapped grad sync: parameter {tensor.name!r} produced "
                f"a {grad._value.dtype} grad in a {b.dtype} bucket; "
                f"overlap requires grad dtype == param dtype (disable "
                f"grad_comm_configs['overlap'] for mixed-dtype grads)")
            return
        st["remaining"][b.index] -= 1
        if st["remaining"][b.index] == 0 and st["dtype_error"] is None:
            self._launch(b, st)

    def _launch(self, bucket: GradBucket, st):
        """Submit one completed bucket to the lane. Called on the thread
        that produced the last grad (inside backward for early launches,
        inside flush for stragglers)."""
        from ..profiler import RecordEvent

        fut = BucketFuture(bucket)
        fut.launch_ns = now_ns()
        st["futures"][bucket.index] = fut
        # zero-width marker in the MAIN thread's span stream: nests inside
        # the enclosing "backward" span, so the step trace proves the
        # launch happened before backward completed
        marker = RecordEvent(f"comm_launch:bucket{bucket.index}")
        marker.begin()
        marker.end()
        params, world = st["params"], st["world"]
        use_rs = st["use_reduce_scatter"]
        # flight-recorder lane entry (ISSUE 6): a hang postmortem must name
        # the bucket/group that launched and never completed
        flightrec = get_flight_recorder()
        group = repr(self.group) if self.group is not None else "world"
        flightrec.lane(f"comm_launch:bucket{bucket.index}",
                       bucket=bucket.index, group=group, phase="launch")

        def job():
            fut.start_ns = now_ns()
            flightrec.lane(f"comm:bucket{bucket.index}", bucket=bucket.index,
                           group=group, phase="start")
            try:
                with RecordEvent(f"comm:bucket{bucket.index}"):
                    flat = self._flatten_bucket(bucket, params)
                    reduced = self._sync_bucket(bucket, flat, world, use_rs)
                    self._scatter_bucket(bucket, params, reduced)
                    # realize the transfer inside the span so the recorded
                    # window is the work, not the async dispatch
                    v = params[bucket.param_indices[0]].grad._value
                    if hasattr(v, "block_until_ready"):
                        v.block_until_ready()
            except BaseException as e:  # surfaced by flush()
                fut._fail(e)
                flightrec.lane(f"comm:bucket{bucket.index}",
                               bucket=bucket.index, group=group,
                               phase="error", error=repr(e))
            else:
                fut._resolve(reduced)
                flightrec.lane(f"comm:bucket{bucket.index}",
                               bucket=bucket.index, group=group, phase="end")
            fut.end_ns = now_ns()

        self._lane.submit(job)

    def abandon(self):
        """Disarm without syncing: restore the hook and discard the step
        state (draining anything already launched). Needed before a
        backward whose grads must ACCUMULATE raw — e.g. the non-update
        micro-batches of gradient accumulation, where an early bucket
        launch would average partial grads the serial path never would."""
        st, self._step = self._step, None
        if st is None:
            return
        _autograd.set_grad_ready_hook(self._prev_hook)
        self._prev_hook = None
        for fut in st["futures"].values():
            fut._done.wait()

    # ----------------------------------------------------------------- sync
    def sync(self, params, world: Optional[int] = None,
             use_reduce_scatter: bool = False):
        """Prepared step → flush barrier; unprepared → serial fallback."""
        if self._step is None:
            return super().sync(params, world,
                                use_reduce_scatter=use_reduce_scatter)
        return self.flush()

    def flush(self):
        """Step barrier: launch stragglers, drain the lane, write back (the
        lane already scattered each bucket), account, and uninstall the
        hook. Raises the first lane-side error after the lane is drained."""
        from ..profiler import RecordEvent

        st, self._step = self._step, None
        _autograd.set_grad_ready_hook(self._prev_hook)
        self._prev_hook = None
        if st is None:
            return
        if st["dtype_error"]:
            # drain in-flight buckets before raising so no lane job is
            # left mutating grads behind the caller's back
            for fut in st["futures"].values():
                fut._done.wait()
            raise RuntimeError(st["dtype_error"])
        flush_t0 = now_ns()
        with RecordEvent("comm"):     # the EXPOSED comm window of this step
            # stragglers: buckets whose grads appeared outside backward
            # (zero-filled unused params, manual .grad writes) — or a
            # backward that never ran; launch them now, in bucket order
            for b in st["buckets"]:
                if b.index in st["futures"]:
                    continue
                if any(st["params"][pi].grad is None
                       for pi in b.param_indices):
                    raise RuntimeError(
                        f"overlapped grad sync: bucket {b.index} still has "
                        f"parameters with no gradient at flush time — "
                        f"DataParallel(find_unused_parameters=True) "
                        f"zero-fills them before the sync")
                self._launch(b, st)
            error = None
            for b in st["buckets"]:
                fut = st["futures"][b.index]
                fut._done.wait()
                if fut._error is not None and error is None:
                    error = fut._error
        if error is not None:
            raise error
        self._account(st, flush_t0)

    def _account(self, st, flush_t0):
        """Overlap telemetry for one flushed step: how much of the comm
        time ran under backward (before flush began) vs exposed after it."""
        timeline, total, hidden = [], 0.0, 0.0
        for b in st["buckets"]:
            fut = st["futures"][b.index]
            dur = max(0, (fut.end_ns or 0) - (fut.start_ns or 0))
            hid = max(0, min(fut.end_ns or 0, flush_t0)
                      - min(fut.start_ns or 0, flush_t0))
            total += dur
            hidden += hid
            timeline.append({
                "bucket": b.index,
                "launched_early": fut.launch_ns < flush_t0,
                "launch_ns": fut.launch_ns,
                "start_ns": fut.start_ns,
                "end_ns": fut.end_ns,
                "comm_s": dur / 1e9,
                "hidden_s": hid / 1e9,
            })
        self.last_timeline = timeline
        eff = hidden / total if total else 0.0
        early = sum(1 for row in timeline if row["launched_early"])
        self.stats.update({
            "overlapped": True,
            "hidden_comm_s": hidden / 1e9,
            "exposed_comm_s": (total - hidden) / 1e9,
            "overlap_efficiency": eff,
            "buckets_launched_early": early,
        })
        _m_overlap_syncs.value += 1
        _m_early.value += early
        _m_overlap_eff.set(round(eff, 6))
        self._record_metrics(st["buckets"])

    # ------------------------------------------------------------- in-trace
    def sync_async(self, params, world: Optional[int] = None,
                   use_reduce_scatter: bool = False,
                   residuals=None) -> List[BucketFuture]:
        """Issue every bucket's collective NOW and return per-bucket
        futures instead of blocking on one barrier.

        Inside a shard_map/pjit trace each bucket becomes its own
        psum/psum_scatter op whose result is consumed only when the
        caller's code touches that future — XLA's latency-hiding scheduler
        interleaves the collectives with compute between consumptions (the
        fused optimizer update consumes them bucket by bucket). Eagerly the
        futures resolve immediately. Write-back to `.grad` views happens
        per future via `scatter()`; callers that consume the flat buffer
        directly (optimizer/fused.py) skip the unflatten entirely.

        The configured codec is honored on BOTH paths — in-trace the
        quantize -> psum-of-int -> dequantize sequence is part of the
        compiled program, so XLA overlaps the (4x smaller) transfers.
        Error feedback in-trace is CARRIED STATE: pass the previous step's
        residuals as `residuals` ({bucket_index: fp32 flat}) and read each
        future's `.residual` back out (a tracer must never land in
        `self._residuals`); eagerly, omitting `residuals` keeps the
        communicator managing them host-side exactly as `sync()` does.
        """
        params = [p for p in params if p.grad is not None]
        if world is None:
            from .env import get_world_size

            world = get_world_size()
        self.stats = {"codec": self.config.codec, "path": "eager",
                      "n_params": len(params), "n_buckets": 0,
                      "collectives": 0, "comm_bytes": 0}
        if world <= 1 or not params:
            return []
        from .grad_comm import EF_CODECS

        dtypes = [np.dtype(p.grad._value.dtype) for p in params]
        buckets = self.buckets_for(params, dtypes=dtypes)
        self.stats["n_buckets"] = len(buckets)
        ef = self.config.error_feedback and self.config.codec in EF_CODECS
        futures = []
        path = "eager"
        for b in buckets:
            flat = self._flatten_bucket(b, params)
            if isinstance(flat, jax.core.Tracer):
                path = "traced"
            res_in = None
            if ef:
                res_in = (residuals.get(b.index) if residuals is not None
                          else self._residuals.get(b.index))
            reduced, new_res, wire_bytes, n_coll = self.reduce_bucket(
                b, flat, world, use_reduce_scatter=use_reduce_scatter,
                residual=res_in)
            if new_res is not None and residuals is None:
                if isinstance(new_res, jax.core.Tracer):
                    raise RuntimeError(
                        f"grad_comm codec {self.config.codec!r} with error "
                        f"feedback inside a trace needs the residuals "
                        f"threaded as carried state: call "
                        f"sync_async(residuals=...) and feed each "
                        f"future's .residual back next step (or use "
                        f"jit.TrainStep(grad_comm=...))")
                self._residuals[b.index] = new_res
            self.stats["collectives"] += n_coll
            self.stats["comm_bytes"] += wire_bytes
            fut = BucketFuture(b, value=reduced, resolved=True)
            fut.residual = new_res
            # bind write-back so callers can scatter lazily, per bucket
            fut.scatter = (lambda bb=b, rr=reduced:
                           self._scatter_bucket(bb, params, rr))
            futures.append(fut)
        self.stats["path"] = path
        self._record_metrics(buckets, path=path)
        return futures
