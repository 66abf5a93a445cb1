"""ZeRO stage-3: parameters sharded at rest, lane-prefetched all_gathers.

Reference: python/paddle/distributed/fleet/meta_parallel/sharding/
sharding_stage3.py — GroupShardedStage3 keeps every parameter as a 1/N
slice per rank and gathers the full tensor just in time for the layer that
needs it, freeing it again after use. "Automatic Cross-Replica Sharding of
Weight Update in Data-Parallel Training" (PAPERS.md) is the weight-update /
memory half of that design; this module is the parameter-side completion
for the eager path (the compiled path already gets stage-3 placement from
GSPMD `dist_spec` annotations — see `group_sharded_parallel`).

Lifetime discipline (one bucket of parameters at a time)::

    shard  --prefetch-->  inflight  --wait+scatter-->  gathered
      ^                                                   |
      +------------------- free (after use) --------------+

- **At rest** every parameter's full value is FREED: its ``_value`` is a
  :class:`FreedParamValue` placeholder (shape/dtype metadata only) and the
  only device-resident copy is this rank's 1/world shard of the flat
  bucket (`GradBucket` layout shared with grad_comm, so grad reduce_scatter
  shards and optimizer-update shards all line up element for element).
- **Prefetch** is the inverse of the PR-5 grad-ready hook: a forward
  PRE-hook on layer k enqueues the all_gather for layer k+1's bucket on a
  second :class:`~paddle_tpu.distributed.overlap.CollectiveLane` client
  ("zero3-gather-lane") so the wire time hides under layer k's compute;
  the FIRST bucket has nothing to hide under and is gathered synchronously.
- **Free after use**: a forward POST-hook frees a bucket the moment its
  last using layer finished, so at most ~2 buckets of full parameters
  (current + prefetched next) are ever resident — the watermark
  `observability.memory.LiveBytesWatermark` proves in tests.
- **Backward** needs no re-gather for hook-covered parameters: the eager
  tape's vjp pullbacks captured the forward-time values as residuals (the
  re-gather of the reference design, without the wire traffic). A
  parameter read OUTSIDE its owning layer's forward (e.g. a tied embedding
  consumed by the LM head) self-heals: the placeholder's ``__array__``
  triggers an exposed synchronous gather (counted on
  ``zero3_gathers_total{mode="fallback"}``) — declare such uses with
  :meth:`Stage3ParamShards.register_external_use` to get them prefetched.
- **Update** runs on the owned shard only:
  ``FusedFlatUpdater.step_sharded(..., param_store=store)`` consumes the
  reduce_scatter grad shard and commits the new parameter shard straight
  back here — the full parameter is never materialized for the update.

Gathers ride ``distributed.collective.all_gather``, so the PR-4 timeout /
retry / chaos machinery applies on the lane. In a single-process run
(tests, CPU emulation) the eager all_gather degenerates to a clone; the
store then keeps the peer ranks' shards HOST-side (numpy) and assembles
the full buffer from them — the device-resident set is still exactly this
rank's shard, which is what `live_tensor_bytes` measures, so the memory
claim stays honest under emulation.

Telemetry: `gather_launch:bucket{i}` marker spans on the MAIN thread (the
layer-order proof that the launch precedes the bucket's first use),
`gather:bucket{i}` spans on the lane thread, `gather_sync:bucket{i}` for
exposed synchronous gathers, flight-recorder lane entries for postmortems,
and the `zero3_*` gauge/counter families below.
"""
from __future__ import annotations

import contextlib
import threading
import time
from typing import Dict, List, Optional

import jax.numpy as jnp
import numpy as np

from .. import collective as _coll
from ..grad_comm import GradCommConfig, GradCommunicator
from ..overlap import CollectiveLane, GatherFuture
from ...framework.tensor import Tensor
from ...observability import memory as obs_memory
from ...observability.flight_recorder import get_flight_recorder
from ...observability.metrics import get_registry as _get_registry
from ...profiler import now_ns

__all__ = ["FreedParamValue", "Stage3ParamShards"]

SHARDED, INFLIGHT, GATHERED = "sharded", "inflight", "gathered"

# one process-wide dispatch materializer covers every store: the
# placeholder itself knows its store/bucket. Installed on the first
# shard_() so processes that never shard pay only autograd's None check.
_materializer_installed = [False]


def _materialize_dispatch_value(v):
    if type(v) is FreedParamValue:
        return v.materialize()
    return v


def _install_materializer():
    if not _materializer_installed[0]:
        from ...framework import autograd as _autograd

        _autograd.set_value_materializer(_materialize_dispatch_value)
        _materializer_installed[0] = True

_m_param_bytes = _get_registry().gauge(
    "zero3_param_bytes_per_rank",
    help="device-resident parameter bytes at rest under ZeRO-3 (this "
         "rank's shards)")
_m_resident = _get_registry().gauge(
    "zero3_gathered_buckets",
    help="parameter buckets currently materialized full (gathered)")
_m_exposed = _get_registry().gauge(
    "zero3_exposed_gather_ms",
    help="exposed (not hidden under compute) parameter-gather ms of the "
         "last forward pass")
_m_gathers = _get_registry().counter(
    "zero3_gathers_total",
    help="parameter-bucket all_gathers by launch mode",
    labels=("mode",))


class FreedParamValue:
    """Placeholder standing in for a freed (sharded-at-rest) parameter.

    Carries shape/dtype metadata so planning code keeps working (bucket
    assignment keys, `Tensor.shape`, grad-hook dtype checks); reading the
    DATA triggers the store's self-healing fallback gather — or a loud
    error naming the lifecycle contract when no store is attached.
    """

    __slots__ = ("shape", "dtype", "_store", "_bucket", "_pname")

    def __init__(self, shape, dtype, store=None, bucket=None, pname=""):
        self.shape = tuple(int(s) for s in shape)
        self.dtype = np.dtype(dtype)
        self._store = store
        self._bucket = bucket
        self._pname = pname

    @property
    def ndim(self):
        return len(self.shape)

    @property
    def size(self):
        return int(np.prod(self.shape)) if self.shape else 1

    @property
    def nbytes(self):
        return self.size * self.dtype.itemsize

    def materialize(self):
        """Exposed synchronous re-gather of the owning bucket; returns this
        parameter's full device value. The self-healing path for reads the
        forward hooks did not cover (autograd.set_value_materializer routes
        dispatched placeholders here)."""
        if self._store is None:
            raise RuntimeError(
                f"parameter {self._pname!r} is sharded at rest (ZeRO-3) and "
                f"its full value was freed after use; gather its bucket "
                f"before reading (Stage3ParamShards.ensure_gathered)")
        return self._store._fallback_read(self._bucket, self._pname,
                                          self.shape, self.dtype)

    def __array__(self, dtype=None, copy=None):
        arr = np.asarray(self.materialize())
        return arr.astype(dtype) if dtype is not None else arr

    def __repr__(self):
        return (f"FreedParamValue(shape={self.shape}, dtype={self.dtype}, "
                f"bucket={self._bucket})")


class Stage3ParamShards:
    """At-rest parameter shards + the gather/free lifecycle for one model.

    The bucket layout is the COMMUNICATOR's own (`buckets_for` on the
    trainable parameter list), so the grad reduce_scatter shard, the
    optimizer-update shard, and the at-rest parameter shard of bucket i
    are the same ``[rank*chunk, (rank+1)*chunk)`` slice of the same flat
    buffer. ``world`` is the sharding degree (the eager process world /
    sharding-group size); ``rank`` this process's slice.
    """

    def __init__(self, params, communicator: Optional[GradCommunicator] = None,
                 rank: Optional[int] = None, world: Optional[int] = None,
                 group=None, prefetch_ahead: int = 1,
                 free_after_use: bool = True,
                 config: Optional[GradCommConfig] = None):
        from ..env import get_rank, get_world_size

        self.params = [p for p in params if not p.stop_gradient]
        self.comm = communicator or GradCommunicator(config or
                                                     GradCommConfig())
        self.rank = get_rank() if rank is None else int(rank)
        self.world = get_world_size() if world is None else int(world)
        if self.world <= 1:
            raise ValueError(
                "Stage3ParamShards needs world > 1 — with one rank there is "
                "nothing to shard (group_sharded_parallel leaves the model "
                "unsharded in that case)")
        if not (0 <= self.rank < self.world):
            raise ValueError(f"rank {self.rank} outside world {self.world}")
        self.group = group if group is not None else self.comm.group
        self.prefetch_ahead = max(0, int(prefetch_ahead))
        self.free_after_use = bool(free_after_use)
        self.buckets = self.comm.buckets_for(self.params)
        self._by_param: Dict[int, int] = {}
        for b in self.buckets:
            for pi in b.param_indices:
                self._by_param[id(self.params[pi])] = b.index
        # second CollectiveLane client (the grad lane's inverse direction)
        self._lane = CollectiveLane("zero3-gather-lane")
        self._lock = threading.Lock()     # guards _state/_futures handoff
        # single-process emulation: the eager all_gather degenerates to a
        # clone, so peer shards are kept HOST-side (numpy) — device memory
        # still holds only this rank's shard
        n_coll = _coll._group_size(_coll._axes(self.group), self.group)
        self.emulated = n_coll < self.world
        self._shards: Dict[int, object] = {}         # bucket -> jnp shard
        self._peer_shards: Dict[int, Dict[int, np.ndarray]] = {}
        self._state: Dict[int, str] = {}
        self._futures: Dict[int, GatherFuture] = {}
        self._hook_handles: List = []
        self._layer_order: List = []       # [(layer, [bucket indices])]
        self._external: Dict[int, List] = {}    # id(layer) -> [params]
        self._uses_left: Dict[int, int] = {}
        self._pass_active = False
        self.exposed_gather_s = 0.0        # since last reset_exposed()
        self._pass_exposed_s = 0.0
        self.sharded = False
        self.stats: Dict[str, object] = {
            "world": self.world, "rank": self.rank,
            "n_buckets": len(self.buckets),
            "param_bytes_full": sum(b.nbytes for b in self.buckets),
        }

    # ------------------------------------------------------------- geometry
    def _chunk(self, bucket) -> int:
        return (bucket.size + (-bucket.size) % self.world) // self.world

    def param_bytes_per_rank(self) -> int:
        """Device-resident parameter bytes at rest (this rank's shards)."""
        return sum(self._chunk(b) * b.dtype.itemsize for b in self.buckets)

    def resident_buckets(self) -> List[int]:
        return [i for i, s in self._state.items() if s == GATHERED]

    # ------------------------------------------------------------- sharding
    def shard_(self):
        """Drop to at-rest state: keep 1/world of every bucket on device,
        free the full parameter values. Idempotent."""
        if self.sharded:
            return self
        _install_materializer()
        for b in self.buckets:
            flat = self._flatten_params(b)
            chunk = self._chunk(b)
            pad = chunk * self.world - b.size
            if pad:
                flat = jnp.concatenate(
                    [flat, jnp.zeros((pad,), flat.dtype)])
            # own shard is a fresh device buffer; the concatenated full
            # buffer dies with this scope
            self._shards[b.index] = flat[self.rank * chunk:
                                         (self.rank + 1) * chunk]
            if self.emulated:
                # np.array (copy): a zero-copy np.asarray view would pin
                # the device buffer and void the at-rest memory win
                self._peer_shards[b.index] = {
                    r: np.array(flat[r * chunk:(r + 1) * chunk])
                    for r in range(self.world) if r != self.rank}
            self._state[b.index] = SHARDED
            self._free_params(b)
        self.sharded = True
        _m_param_bytes.set(self.param_bytes_per_rank())
        _m_resident.set(0)
        obs_memory.sample_watermarks()
        return self

    def _flatten_params(self, bucket):
        if len(bucket.param_indices) == 1:
            return self.params[bucket.param_indices[0]]._value.reshape(-1)
        return jnp.concatenate([self.params[pi]._value.reshape(-1)
                                for pi in bucket.param_indices])

    def _free_params(self, bucket):
        for pi in bucket.param_indices:
            p = self.params[pi]
            p._value = FreedParamValue(
                p._value.shape, p._value.dtype, store=self,
                bucket=bucket.index, pname=p.name)

    # ------------------------------------------------------ gather lifecycle
    def prefetch_bucket(self, index: int):
        """Launch bucket `index`'s all_gather on the lane (the layer-ahead
        prefetch). No-op unless the bucket is at rest."""
        from ...profiler import RecordEvent

        with self._lock:
            if (not self.sharded or self._state.get(index) != SHARDED
                    or index in self._futures):
                return None
            fut = GatherFuture(self.buckets[index])
            fut.launch_ns = now_ns()
            self._futures[index] = fut
            self._state[index] = INFLIGHT
        # zero-width marker in the MAIN thread's span stream: the proof the
        # launch preceded the bucket's first forward use
        marker = RecordEvent(f"gather_launch:bucket{index}")
        marker.begin()
        marker.end()
        flightrec = get_flight_recorder()
        group = repr(self.group) if self.group is not None else "world"
        flightrec.lane(f"gather_launch:bucket{index}", bucket=index,
                       group=group, phase="launch")
        bucket = self.buckets[index]

        def job():
            fut.start_ns = now_ns()
            flightrec.lane(f"gather:bucket{index}", bucket=index,
                           group=group, phase="start")
            try:
                with RecordEvent(f"gather:bucket{index}"):
                    full = self._gather_full(bucket)
                    if hasattr(full, "block_until_ready"):
                        full.block_until_ready()
            except BaseException as e:   # surfaced at the wait
                fut._fail(e)
                flightrec.lane(f"gather:bucket{index}", bucket=index,
                               group=group, phase="error", error=repr(e))
            else:
                fut._resolve(full)
                flightrec.lane(f"gather:bucket{index}", bucket=index,
                               group=group, phase="end")
            fut.end_ns = now_ns()

        self._lane.submit(job)
        _m_gathers.labels(mode="prefetched").inc()
        return fut

    def ensure_gathered(self, index: int, _mode: str = "sync"):
        """Make bucket `index`'s full parameters resident (wait for the
        prefetch if one is in flight, else gather synchronously — fully
        exposed) and scatter them into the parameter views.

        The EXPOSED accounting covers the wait for the gathered data (the
        wire time forward actually blocks on — ~0 when the prefetch beat
        us here); the per-param scatter is compute-side materialization
        work both modes pay identically and is excluded."""
        from ...profiler import RecordEvent

        if self._state.get(index) == GATHERED:
            return
        t0 = time.perf_counter()
        fut = self._futures.get(index)
        if fut is not None:
            try:
                full = fut.wait()
            except BaseException:
                # a failed prefetch must not wedge the bucket INFLIGHT:
                # drop the future so a retry can gather fresh
                with self._lock:
                    self._futures.pop(index, None)
                    self._state[index] = SHARDED
                raise
        else:
            marker = RecordEvent(f"gather_launch:bucket{index}")
            marker.begin()
            marker.end()
            with RecordEvent(f"gather_sync:bucket{index}"):
                full = self._gather_full(self.buckets[index])
                if hasattr(full, "block_until_ready"):
                    full.block_until_ready()
            _m_gathers.labels(mode=_mode).inc()
        exposed = time.perf_counter() - t0
        self.exposed_gather_s += exposed
        self._pass_exposed_s += exposed
        # parameter mutation stays on the CALLING thread — the lane only
        # produces the flat buffer
        self._scatter_full(self.buckets[index], full)
        with self._lock:
            self._state[index] = GATHERED
            popped = self._futures.pop(index, None)
        # drop the flat gather buffer NOW (the scattered params are their
        # own buffers) so the watermark sees one bucket, not two
        if popped is not None:
            popped._value = None
        full = None
        _m_resident.set(len(self.resident_buckets()))
        obs_memory.sample_watermarks()

    def free_bucket(self, index: int):
        """Back to at-rest: drop the full parameter values of bucket
        `index` (the shard is the source of truth; forward never mutates
        parameters). Drains an in-flight prefetch first."""
        fut = self._futures.get(index)
        if fut is not None:
            fut._done.wait()
        with self._lock:
            self._futures.pop(index, None)
            self._state[index] = SHARDED
        self._free_params(self.buckets[index])
        _m_resident.set(len(self.resident_buckets()))
        obs_memory.sample_watermarks()

    def _gather_full(self, bucket):
        """All_gather this rank's shard into the padded full flat buffer.
        Rides the guarded collective layer (timeouts/retry/chaos apply);
        in single-process emulation the degenerate gather falls back to
        assembling from the host-side peer shards."""
        chunk = self._chunk(bucket)
        shard_t = Tensor(self._shards[bucket.index], _internal=True)
        gathered = _coll.all_gather(None, shard_t, group=self.group)
        full = gathered._value.reshape(-1)
        if int(full.shape[0]) == chunk * self.world:
            return full
        # emulation: the eager all_gather cloned the shard; peers are host.
        # Assemble on HOST and device_put ONCE — a device-side concatenate
        # would transiently hold parts + full (2 buckets) on top of the
        # previous bucket's scattered params, breaking the <= 2-bucket
        # residency the free-after-use discipline promises
        parts = [np.array(self._shards[bucket.index]) if r == self.rank
                 else self._peer_shards[bucket.index][r]
                 for r in range(self.world)]
        return jnp.asarray(np.concatenate(parts))

    def _scatter_full(self, bucket, full):
        for pi, off, n, shape in zip(bucket.param_indices, bucket.offsets,
                                     bucket.numels, bucket.shapes):
            p = self.params[pi]
            p._value = full[off:off + n].reshape(shape)

    def _fallback_read(self, index: int, pname: str, shape, dtype):
        """Self-healing path for a parameter read outside its layer's
        forward (FreedParamValue.materialize): exposed synchronous gather
        + scatter, returning this parameter's full device value. Counted
        (`mode="fallback"`) so undeclared external uses are visible in
        /metrics — declare them via register_external_use to prefetch."""
        self.ensure_gathered(index, _mode="fallback")
        b = self.buckets[index]
        for pi in b.param_indices:
            p = self.params[pi]
            if p.name == pname and tuple(p._value.shape) == tuple(shape):
                return p._value
        # name didn't resolve (unnamed params): fall back to the first
        # matching shape in the bucket
        for pi in b.param_indices:
            p = self.params[pi]
            if tuple(p._value.shape) == tuple(shape):
                return p._value
        raise RuntimeError(
            f"fallback gather of bucket {index} did not materialize a "
            f"parameter of shape {tuple(shape)} ({pname!r})")

    # ------------------------------------------------------- optimizer side
    def own_shard(self, index: int):
        """This rank's at-rest shard of bucket `index` (padded chunk)."""
        return self._shards[index]

    def peer_ranks(self) -> List[int]:
        return [r for r in range(self.world) if r != self.rank]

    def peer_shard(self, index: int, rank: int) -> np.ndarray:
        return self._peer_shards[index][rank]

    def commit_shard(self, index: int, new_shard):
        """Commit the optimizer's updated OWN shard (the at-rest value).
        Any gathered full copy of the bucket is now stale and is freed."""
        self._shards[index] = new_shard
        if self._state.get(index) == GATHERED:
            self.free_bucket(index)
        _m_param_bytes.set(self.param_bytes_per_rank())

    def commit_peer_shard(self, index: int, rank: int, new_shard):
        """Emulation only: the peer rank's updated shard (host-resident;
        np.array copies so no device buffer stays pinned)."""
        self._peer_shards[index][rank] = np.array(new_shard)

    # ------------------------------------------------------------ model side
    def register_external_use(self, layer, param):
        """Declare that `layer`'s forward reads `param` even though another
        layer owns it (tied weights). The bucket is then gathered by this
        layer's pre-hook instead of paying the fallback path."""
        self._external.setdefault(id(layer), []).append(param)

    def install_hooks(self, model, order=None):
        """Install the gather-ahead / free-after-use forward hooks.

        `order` (list of layers) defaults to registration order
        (pre-order traversal), which matches execution order for
        sequentially-built models; pass it explicitly when construction
        and execution order differ."""
        self.remove_hooks()
        if order is None:
            order = [l for _, l in model.named_sublayers(include_self=True)]
        param_ids = set(self._by_param)
        seq = []
        for layer in order:
            own = [p for p in layer._parameters.values()
                   if p is not None and id(p) in param_ids]
            own += [p for p in self._external.get(id(layer), [])
                    if id(p) in param_ids]
            if own:
                need = sorted({self._by_param[id(p)] for p in own})
                seq.append((layer, need))
        self._layer_order = seq
        # pass bracketing on the ROOT model (registered first/last so its
        # pre-hook runs before, and its post-hook after, any layer hook on
        # the same module): begin resets the per-pass use counts; end
        # frees leftovers and records the exposed-gather stats. Ending at
        # the last param-OWNING layer instead would free too early for a
        # root whose forward still reads a tied weight after its children.
        self._hook_handles.append(
            model.register_forward_pre_hook(self._pass_begin_hook))
        for k, (layer, _need) in enumerate(seq):
            self._hook_handles.append(
                layer.register_forward_pre_hook(self._make_pre_hook(k)))
            self._hook_handles.append(
                layer.register_forward_post_hook(self._make_post_hook(k)))
        self._hook_handles.append(
            model.register_forward_post_hook(self._pass_end_hook))
        return self

    def remove_hooks(self):
        for h in self._hook_handles:
            h.remove()
        self._hook_handles = []

    def _begin_pass(self):
        # self-heal a pass aborted by an exception: anything still
        # gathered from the previous attempt goes back to rest first
        for i in list(self.resident_buckets()):
            self.free_bucket(i)
        self._uses_left = {}
        for _layer, need in self._layer_order:
            for bi in need:
                self._uses_left[bi] = self._uses_left.get(bi, 0) + 1
        self._pass_exposed_s = 0.0
        self._pass_active = True

    def _end_pass(self):
        if self.free_after_use:
            for i in list(self.resident_buckets()):
                self.free_bucket(i)
        self._pass_active = False
        self.stats["exposed_gather_s_last_pass"] = self._pass_exposed_s
        _m_exposed.set(round(self._pass_exposed_s * 1e3, 6))

    def _pass_begin_hook(self, layer, inputs):
        if self.sharded:
            self._begin_pass()
        return None

    def _pass_end_hook(self, layer, inputs, outputs):
        if self.sharded and self._pass_active:
            self._end_pass()
        return None

    def _make_pre_hook(self, k: int):
        def hook(layer, inputs):
            if not self.sharded:
                return None
            from ...profiler import RecordEvent

            if not self._pass_active:
                # sublayer driven directly (no root call): self-arm
                self._begin_pass()
            marker = RecordEvent(f"zero3_prehook:layer{k}")
            marker.begin()
            marker.end()
            _layer, need = self._layer_order[k]
            for bi in need:
                self.ensure_gathered(bi)
            # the layer-ahead prefetch: enqueue the NEXT layers' buckets
            for j in range(k + 1, min(k + 1 + self.prefetch_ahead,
                                      len(self._layer_order))):
                for bi in self._layer_order[j][1]:
                    self.prefetch_bucket(bi)
            # marker: this layer's buckets are resident — its forward use
            # starts after this point (the span-ordering proof anchor)
            ready = RecordEvent(f"zero3_ready:layer{k}")
            ready.begin()
            ready.end()
            return None

        return hook

    def _make_post_hook(self, k: int):
        def hook(layer, inputs, outputs):
            if not self.sharded or not self._pass_active:
                return None
            _layer, need = self._layer_order[k]
            for bi in need:
                left = max(0, self._uses_left.get(bi, 0) - 1)
                self._uses_left[bi] = left
                if left == 0 and self.free_after_use:
                    self.free_bucket(bi)
            return None

        return hook

    @contextlib.contextmanager
    def materialize(self):
        """Temporarily gather EVERY bucket (full parameters resident) —
        for whole-model reads like `save_group_sharded_model`. Frees on
        all exits (analysis rule S001's contract)."""
        if not self.sharded:
            yield self
            return
        try:
            for b in self.buckets:
                self.ensure_gathered(b.index)
            yield self
        finally:
            for b in self.buckets:
                self.free_bucket(b.index)

    def unshard_(self):
        """Permanently leave stage-3: materialize the full parameters and
        drop the shards/hooks (the inverse of shard_())."""
        if not self.sharded:
            return self
        for b in self.buckets:
            self.ensure_gathered(b.index)
        self.remove_hooks()
        self.sharded = False
        self._shards.clear()
        self._peer_shards.clear()
        self._futures.clear()
        self._state.clear()
        _m_param_bytes.set(0)
        _m_resident.set(0)
        return self

    def reset_exposed(self):
        self.exposed_gather_s = 0.0

    # ------------------------------------------------------------ state io
    def state_dict(self) -> dict:
        """At-rest snapshot for sharded checkpoints: this rank's shards
        (plus the host-side peer shards under emulation) and the bucket
        key they were laid out under. Gathered copies are not saved — the
        shard is the source of truth."""
        out = {
            "bucket_key": self.comm._bucket_key,
            "rank": self.rank, "world": self.world,
            # unpadded bucket sizes: what reshard.py needs to strip the
            # world-N padding before re-chunking to a new world size
            "bucket_sizes": {int(b.index): int(b.size)
                             for b in self.buckets},
            "shards": {int(i): np.asarray(v)
                       for i, v in self._shards.items()},
        }
        if self.emulated:
            out["peer_shards"] = {
                int(i): {int(r): np.asarray(v) for r, v in peers.items()}
                for i, peers in self._peer_shards.items()}
        return out

    def load_state_dict(self, state: dict, allow_reshard: bool = False):
        """Restore a state_dict() snapshot into a freshly sharded store.
        The world size and bucket layout must match — a resume that
        re-bucketed differently would mis-slice every parameter. With
        ``allow_reshard=True`` a world-size drift triggers the elastic
        N→M transform (reshard.py) instead of refusing, provided the
        state carries the full shard set (the emulated peer-shard layout;
        a real per-rank state needs `CheckpointManager.load_sharded`,
        which joins every rank's file first)."""
        if int(state.get("world", self.world)) != self.world:
            if not allow_reshard:
                raise ValueError(
                    f"zero3 state world mismatch: checkpoint has "
                    f"{state.get('world')}, store runs {self.world}")
            from .reshard import reshard_zero3_states

            if not state.get("peer_shards"):
                raise ValueError(
                    f"zero3 state world mismatch (checkpoint "
                    f"{state.get('world')} vs live {self.world}) and this "
                    f"state holds only one rank's shards — reshard via "
                    f"CheckpointManager.load_sharded(allow_reshard=True), "
                    f"which joins all rank files")
            state = reshard_zero3_states([state], self.world)[0]
        key = state.get("bucket_key")
        if key is not None and self.comm._bucket_key is not None \
                and tuple(key) != tuple(self.comm._bucket_key):
            raise ValueError(
                "zero3 state bucket-key mismatch: the checkpointed bucket "
                "layout differs from this store's — resume with the same "
                "comm_buffer_size / parameter list")
        if not self.sharded:
            self.shard_()
        for i, v in (state.get("shards") or {}).items():
            self._shards[int(i)] = jnp.asarray(v)
        for i, peers in (state.get("peer_shards") or {}).items():
            self._peer_shards[int(i)] = {
                int(r): np.asarray(v) for r, v in peers.items()}
        # everything goes back to rest; stale gathered copies are freed
        for b in self.buckets:
            if self._state.get(b.index) == GATHERED:
                self.free_bucket(b.index)
        _m_param_bytes.set(self.param_bytes_per_rank())

    def meta_state(self) -> dict:
        """The layout fingerprint job_state carries (capture_job_state):
        enough to refuse a resume whose sharding geometry changed."""
        return {"world": self.world, "rank": self.rank,
                "n_buckets": len(self.buckets),
                "bucket_key": self.comm._bucket_key}

    def check_meta(self, meta: dict, allow_world_drift: bool = False):
        if int(meta.get("world", self.world)) != self.world:
            if not allow_world_drift:
                raise ValueError(
                    f"zero3 resume geometry mismatch: job_state world "
                    f"{meta.get('world')} vs live {self.world} — pass "
                    f"allow_reshard=True (restore_job_state) after "
                    f"resharding the shard payloads to accept the drift")
            # elastic resume across a world change: the shard payloads were
            # already resharded (reshard.py); the meta world is historical
            get_flight_recorder().note(
                "reshard", "world drift accepted on resume",
                from_world=int(meta.get("world", -1)), to_world=self.world)
        key = meta.get("bucket_key")
        if key is not None and self.comm._bucket_key is not None \
                and tuple(key) != tuple(self.comm._bucket_key):
            raise ValueError(
                "zero3 resume geometry mismatch: bucket layout changed "
                "between checkpoint and resume")

    def __repr__(self):
        return (f"Stage3ParamShards(rank={self.rank}/{self.world}, "
                f"buckets={len(self.buckets)}, sharded={self.sharded}, "
                f"resident={len(self.resident_buckets())})")
