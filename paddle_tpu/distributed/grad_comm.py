"""Bucketed + quantized gradient communication for data parallelism.

Reference: the C++ Reducer (imperative/reducer.cc) coalesces grads into
~`comm_buffer_size` MB groups and launches one allreduce per group instead of
one per parameter; meta_optimizers/fp16_allreduce_optimizer.py halves the wire
dtype. This module is both, plus an EQuARX-style int8 quantized all-reduce
codec (PAPERS.md): per-bucket abs-max scale (the `quantization/observers.py`
AbsMaxObserver rule), quantize -> sum -> dequantize, with an error-feedback
residual carried across steps so convergence is preserved.

TPU-native shape: buckets are flat jnp buffers and the collectives are the
`distributed/collective.py` functions, so the same codec runs eagerly (host
emulation for multi-process CPU testing) and inside shard_map/pjit traces
(lowering to XLA AllReduce / ReduceScatter over ICI).

Blockwise codecs (ISSUE 8, EQuARX): `int8_block` / `fp8_block` quantize with
one abs-max scale per `block_size` elements instead of one per bucket —
orders-of-magnitude tighter scales on a ~25MB bucket — and the per-block
scale vector rides a sum-typed exchange alongside the payload (a real packed
wire format fuses both into one transfer; there is NO scalar-MAX host round
trip). Every codec transform here is pure jnp (enforced by analysis rule
T002), so the exact same encode/decode bits run in the eager sync, on the
overlapped lane, and inside a compiled train step (`jit.TrainStep(grad_comm=)`
/ `overlap.sync_async`) where the error-feedback residual is threaded through
as carried state instead of host-side mutation.

Determinism contract: bucket assignment is a pure function of the parameter
traversal order and the grad dtypes/shapes — identical across SPMD ranks by
construction (all ranks enumerate the same model), so ranks always agree on
which collective carries which parameter.

Overlap: `DistributedStrategy.grad_comm_configs["overlap"] = True` (or
`GradCommConfig(overlap=True)`) swaps in
`overlap.OverlappedGradCommunicator` — each bucket's collective launches on
a background lane the moment backward produces its last gradient, instead
of all buckets running serially after backward; `sync()` becomes the flush
barrier. Values are bit-identical to the serial path (the codecs, error
feedback, and bucket assignment here are shared verbatim); only the wall
clock moves. See distributed/overlap.py.
"""
from __future__ import annotations

import math
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

# the collective module is bound by name (not function) so tests can
# monkeypatch coll.all_reduce / coll.reduce_scatter and be seen here
from . import collective as _coll
from .collective import ReduceOp
from ..framework.tensor import Tensor
from ..observability.metrics import get_registry as _get_registry

# wire-traffic telemetry (ISSUE 3 sweep; ISSUE 8 adds the `path` label):
# what sync() actually put on the wire, per codec AND per execution path
# (eager host sync vs inside a compiled step), plus how full the buckets
# ran — the counters a /metrics scrape sets against the step-time
# breakdown's comm row. The path label is the satellite fix: the traced
# path used to be indistinguishable from (and mis-accounted as) the eager
# one in /metrics.
_m_syncs = _get_registry().counter(
    "grad_comm_syncs_total", help="gradient sync rounds").bind()
_m_coll = _get_registry().counter(
    "grad_comm_collectives_total",
    help="collectives issued by bucketed grad sync",
    labels=("codec", "path"))
_m_bytes = _get_registry().counter(
    "grad_comm_bytes_total", help="wire bytes moved by grad sync",
    labels=("codec", "path"))
_m_fill = _get_registry().histogram(
    "grad_comm_bucket_fill_ratio",
    help="bucket bytes / bucket cap at sync time",
    buckets=(0.1, 0.25, 0.5, 0.75, 0.9, 1.0, 1.5))

__all__ = [
    "CODECS", "BLOCK_CODECS", "GradCommConfig", "GradBucket",
    "GradCommunicator", "build_buckets", "comm_plan",
    "config_from_strategy", "record_sync_metrics",
    "block_absmax", "block_scales", "block_encode", "block_decode",
    "block_residual", "scale_bytes", "traced_reduce_scatter_quantized",
]

CODECS = ("fp32", "bf16", "int8", "int8_block", "fp8_block")
# blockwise codecs: per-block abs-max scales, error feedback supported
BLOCK_CODECS = ("int8_block", "fp8_block")
# codecs that carry a cross-step error-feedback residual
EF_CODECS = ("int8",) + BLOCK_CODECS

# wire bytes per fp32 gradient element, by codec (int8 adds a 4-byte
# per-bucket scale; the blockwise codecs one fp32 scale per block_size
# elements — accounted separately)
_WIRE_ITEMSIZE = {"fp32": 4, "bf16": 2, "int8": 1, "int8_block": 1,
                  "fp8_block": 1}
# largest representable magnitude of the wire format (int8 symmetric /
# float8_e4m3fn max normal)
_QMAX = {"int8_block": 127.0, "fp8_block": 448.0}
_FP8_WIRE = jnp.float8_e4m3fn

_MB = 1024 * 1024


class GradCommConfig:
    """Gradient-communication knobs (DistributedStrategy.grad_comm_configs).

    codec:  'bf16' (default half-traffic wire format; exponent-safe on TPU),
            'fp32' (escape hatch, full-precision wire), 'int8' (quantized
            all-reduce, 4x less traffic than fp32, ONE abs-max scale per
            bucket shared via a scalar MAX exchange, error feedback on),
            'int8_block' / 'fp8_block' (EQuARX blockwise: one abs-max scale
            per `block_size` elements — far tighter than per-bucket on a
            ~25MB bucket — with the fp32 scale vector riding a sum-typed
            exchange next to the payload instead of a scalar MAX round
            trip; ~4x less traffic than fp32 plus 4/block_size overhead).
            fp8_block writes float8_e4m3fn on the wire (carried wider
            through the summation, like int8's int32 carrier).
    comm_buffer_size:        target bucket size in MB (reference DataParallel
                             kwarg of the same name).
    last_comm_buffer_size:   cap of the first-reduced bucket (the reference
                             keeps the last backward bucket small so its
                             collective can launch early).
    error_feedback:          carry the quantization residual across steps
                             (int8 and the blockwise codecs; no effect for
                             fp32/bf16). In a compiled step the residual is
                             carried state of the jitted function — see
                             jit.TrainStep(grad_comm=).
    overlap:                 launch each bucket's collective the moment its
                             last gradient is produced (bucket-ready async
                             sync, distributed/overlap.py) instead of one
                             serial phase after backward. Bit-identical to
                             the serial path; flush() is the step barrier.
    block_size:              elements per abs-max scale block for the
                             blockwise codecs (default 1024; one fp32 scale
                             per block = 4/block_size bytes/element of wire
                             overhead). Ignored by the other codecs.
    """

    def __init__(self, codec: str = "bf16", comm_buffer_size: float = 25,
                 last_comm_buffer_size: float = 1, error_feedback: bool = True,
                 overlap: bool = False, block_size: int = 1024):
        if codec not in CODECS:
            raise ValueError(
                f"unknown grad_comm codec {codec!r}; one of {CODECS}")
        for name, v in (("comm_buffer_size", comm_buffer_size),
                        ("last_comm_buffer_size", last_comm_buffer_size)):
            try:
                ok = float(v) > 0
            except (TypeError, ValueError):
                ok = False
            if not ok:
                raise ValueError(
                    f"{name} must be a positive number of MB, got {v!r}")
        if not isinstance(block_size, (int, np.integer)) or block_size <= 0:
            raise ValueError(
                f"block_size must be a positive int, got {block_size!r}")
        self.codec = codec
        self.comm_buffer_size = float(comm_buffer_size)
        self.last_comm_buffer_size = float(last_comm_buffer_size)
        self.error_feedback = bool(error_feedback)
        self.overlap = bool(overlap)
        self.block_size = int(block_size)

    def __repr__(self):
        return (f"GradCommConfig(codec={self.codec!r}, "
                f"comm_buffer_size={self.comm_buffer_size}, "
                f"last_comm_buffer_size={self.last_comm_buffer_size}, "
                f"error_feedback={self.error_feedback}, "
                f"overlap={self.overlap}, block_size={self.block_size})")


class GradBucket:
    """One dtype-homogeneous flat communication bucket."""

    __slots__ = ("index", "dtype", "param_indices", "shapes", "numels",
                 "offsets", "size")

    def __init__(self, index: int, dtype: np.dtype):
        self.index = index
        self.dtype = np.dtype(dtype)
        self.param_indices: List[int] = []   # positions in the param list
        self.shapes: List[tuple] = []
        self.numels: List[int] = []
        self.offsets: List[int] = []         # start offset of each param
        self.size = 0                        # total elements in the bucket

    def add(self, param_index: int, shape: Sequence[int]):
        n = int(np.prod(shape)) if len(shape) else 1
        self.param_indices.append(param_index)
        self.shapes.append(tuple(shape))
        self.numels.append(n)
        self.offsets.append(self.size)
        self.size += n

    @property
    def nbytes(self) -> int:
        return self.size * self.dtype.itemsize

    def signature(self) -> tuple:
        """Rank-agreement fingerprint: identical on every rank iff the
        assignment is deterministic (no ids, no addresses)."""
        return (self.index, str(self.dtype), tuple(self.param_indices),
                tuple(self.shapes), tuple(self.offsets), self.size)

    def __repr__(self):
        return (f"GradBucket(#{self.index}, dtype={self.dtype}, "
                f"params={len(self.param_indices)}, numel={self.size})")


def build_buckets(params, comm_buffer_size: float = 25,
                  last_comm_buffer_size: float = 1,
                  dtypes: Optional[Sequence] = None) -> List[GradBucket]:
    """Assign parameters to dtype-homogeneous flat buckets.

    Parameters are walked in REVERSE traversal order — the order backward
    produces grads — so the first bucket closes (and its collective could
    launch) earliest; its cap is `last_comm_buffer_size` MB, every later
    bucket's is `comm_buffer_size` MB (reference Reducer group semantics).
    `dtypes` optionally overrides the per-param bucketing dtype (grad dtype
    when known; defaults to the param dtype).
    """
    params = list(params)
    if dtypes is None:
        dtypes = [np.dtype(p._value.dtype) for p in params]
    order = list(range(len(params)))[::-1]
    buckets: List[GradBucket] = []
    open_by_dtype = {}
    for pi in order:
        dt = np.dtype(dtypes[pi])
        shape = tuple(params[pi]._value.shape)
        numel = int(np.prod(shape)) if shape else 1
        b = open_by_dtype.get(dt)
        if b is not None:
            # the earliest-closing bucket keeps the small cap so its
            # collective can launch before the rest of backward finishes
            cap_mb = (last_comm_buffer_size if b.index == 0
                      else comm_buffer_size)
            if b.size > 0 and (b.size + numel) * dt.itemsize > cap_mb * _MB:
                b = None
        if b is None:
            b = GradBucket(len(buckets), dt)
            buckets.append(b)
            open_by_dtype[dt] = b
        b.add(pi, shape)
    return buckets


# --------------------------------------------------------------------- codecs
# Pure jnp transforms so they run identically eagerly and in-trace. The int8
# pair is split around the collectives: encode needs the SHARED scale (max of
# the per-rank abs-max), decode needs the summed int payload.

def encode_bf16(flat):
    return flat.astype(jnp.bfloat16)


def decode_bf16(wire, dtype):
    return wire.astype(dtype)


def int8_scale(flat):
    """Per-bucket abs-max scale (AbsMaxObserver rule): one fp32 scalar."""
    return jnp.maximum(jnp.abs(flat).max(), 1e-12).astype(jnp.float32) / 127.0


def int8_encode(flat, scale):
    """Quantize with the (shared) scale -> int8 payload carried as int32 so
    the summation over ranks cannot overflow."""
    q = jnp.clip(jnp.round(flat.astype(jnp.float32) / scale), -127, 127)
    return q.astype(jnp.int8).astype(jnp.int32)


def int8_decode(q_sum, scale, world, dtype):
    """Dequantize the summed payload back to the grad dtype (AVG)."""
    return (q_sum.astype(jnp.float32) * scale / world).astype(dtype)


def int8_residual(flat, q, scale):
    """Error-feedback residual: what quantization dropped locally."""
    return flat.astype(jnp.float32) - q.astype(jnp.float32) * scale


# ----------------------------------------------------------- blockwise codecs
# EQuARX-style blockwise variants: one abs-max scale per `block_size`
# elements. The scale vector is SHARED by summing every rank's local
# per-block abs-max (a sum-typed exchange that a real packed wire format
# fuses into the payload transfer — no scalar MAX round trip); the summed
# abs-max upper-bounds every rank's, so each rank quantizes into range with
# the identical step and the summed integers dequantize consistently. The
# bound is looser than a true MAX by at most `world`x (≤ log2(world) bits of
# the 8/[fp8 mantissa]), which the per-block granularity more than buys back
# versus the per-bucket scale, and error feedback absorbs across steps.
# Every function here is pure jnp (analysis rule T002) so the same bits run
# eagerly and inside a compiled step.

def n_scale_blocks(numel: int, block_size: int) -> int:
    return -(-int(numel) // int(block_size))


def scale_bytes(numel: int, block_size: int) -> int:
    """Wire overhead of the per-block fp32 scale vector, in bytes."""
    return 4 * n_scale_blocks(numel, block_size)


def _as_blocks(flat, block_size: int):
    """(n_blocks, block_size) fp32 view of a flat buffer, zero-padded."""
    n = flat.shape[0]
    nb = n_scale_blocks(n, block_size)
    pad = nb * block_size - n
    flat = flat.astype(jnp.float32)
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), jnp.float32)])
    return flat.reshape(nb, block_size)


def block_absmax(flat, block_size: int):
    """Per-block abs-max of a flat buffer: the local half of the shared
    scale (fp32 vector of n_blocks entries)."""
    return jnp.abs(_as_blocks(flat, block_size)).max(axis=1)


def block_scales(absmax, codec: str):
    """Quantization step per block from the (summed-over-ranks) abs-max."""
    return jnp.maximum(absmax, 1e-12).astype(jnp.float32) / _QMAX[codec]


def block_encode(flat, scales, block_size: int, codec: str):
    """Blockwise quantize with the shared scales. int8_block returns the
    int8-valued payload carried as int32 (the summation over ranks must not
    wrap); fp8_block returns the float8_e4m3fn-valued payload carried as
    fp32 (same reason — fp8 addition would round away low bits)."""
    q = _as_blocks(flat, block_size) / scales[:, None]
    if codec == "int8_block":
        return jnp.clip(jnp.round(q), -127, 127).astype(jnp.int8) \
            .astype(jnp.int32)
    return q.astype(_FP8_WIRE).astype(jnp.float32)


def block_decode(q_sum, scales, world, dtype, numel: int):
    """Dequantize the summed blockwise payload back to the grad dtype
    (AVG over `world` replicas)."""
    vals = q_sum.astype(jnp.float32) * scales[:, None]
    return (vals.reshape(-1)[:numel] / world).astype(dtype)


def block_residual(flat, q, scales, numel: int):
    """Error-feedback residual of a blockwise encode: the local input minus
    its own dequantized wire value (no world averaging — local error)."""
    deq = (q.astype(jnp.float32) * scales[:, None]).reshape(-1)[:numel]
    return flat.astype(jnp.float32) - deq


def _block_kernel_ops():
    """Resolve the blockwise transform pair at a call site: the pallas TPU
    kernels (ops/pallas/codec.py, ISSUE 13) when FLAGS_kernel_autotune is
    on AND the compile target is TPU, else the pure-jnp reference pair
    above. The jnp pair stays the interpret-mode reference and the
    flag-off path — with the flag unset this returns the exact functions
    every pre-ISSUE-13 guarantee (traced wire bytes, crash→resume parity)
    was proven against. Payload bits are identical either way (the kernel
    equivalence tests pin it); only wall clock moves."""
    from ..framework.flags import flag

    if flag("FLAGS_kernel_autotune"):
        from ..ops.pallas import codec as _pallas_codec

        if _pallas_codec.use_tpu_kernels():
            return _pallas_codec.block_encode, _pallas_codec.block_decode
    return block_encode, block_decode


def traced_reduce_scatter_quantized(flat, axis, world: int,
                                    config: "GradCommConfig",
                                    residual=None):
    """EQuARX §RS, in-trace: blockwise-quantized reduce_scatter followed by
    a REQUANTIZED all_gather — both halves of the ring decomposition ship
    the 1-byte wire format, with each half's fp32 block scales riding its
    own payload. Must be called from inside a shard_map over `axis`.

    RS half: shared scales (summed per-block abs-max, like reduce_bucket),
    integer psum_scatter; each rank dequantizes only its OWNED shard with
    the matching scale slice (the window where a ZeRO-2 sharded optimizer
    consumes the shard). AG half: every rank requantizes its reduced shard
    with fresh LOCAL block scales — no exchange needed, the per-rank scale
    vector is gathered next to the payload — and all ranks decode each
    gathered shard with its sender's scales.

    Returns `(full, shard, new_residual, wire_bytes, collectives)` where
    `full` is the re-assembled reduced buffer (AVG), `shard` this rank's
    dequantized owned shard, and `new_residual` the RS-half error-feedback
    residual (None when `config.error_feedback` is off). The AG half's
    requantization error is not fed back — it never enters the optimizer
    state on the owning rank, matching EQuARX's error model."""
    codec = config.codec
    if codec not in BLOCK_CODECS:
        raise ValueError(
            f"traced_reduce_scatter_quantized needs a blockwise codec, "
            f"got {codec!r}")
    bs = config.block_size
    n = int(flat.shape[0])
    in_dtype = flat.dtype
    # pad so every rank's shard is a whole number of scale blocks
    chunk_blocks = n_scale_blocks(n_scale_blocks(n, world), bs)
    chunk = chunk_blocks * bs
    padded = world * chunk
    x = flat.astype(jnp.float32)
    if residual is not None:
        x = x + residual
    if padded > n:
        x = jnp.concatenate([x, jnp.zeros((padded - n,), jnp.float32)])
    enc, _dec = _block_kernel_ops()
    # ---- RS half: shared blockwise scales, integer payload psum_scatter
    absmax = jax.lax.psum(block_absmax(x, bs), axis)
    scales = block_scales(absmax, codec)
    q = enc(x, scales, bs, codec)
    new_res = None
    if config.error_feedback:
        new_res = block_residual(x[:n], q, scales, n)
    q_shard = jax.lax.psum_scatter(q.reshape(-1), axis,
                                   scatter_dimension=0, tiled=True)
    rank = jax.lax.axis_index(axis)
    shard_scales = jax.lax.dynamic_slice_in_dim(
        scales, rank * chunk_blocks, chunk_blocks)
    shard = (q_shard.reshape(chunk_blocks, bs).astype(jnp.float32)
             * shard_scales[:, None]).reshape(-1) / world
    # ---- AG half: requantize the reduced shard with LOCAL scales; the
    # per-rank scale vectors ride the gathered payload
    s2 = block_scales(block_absmax(shard, bs), codec)
    q2 = enc(shard, s2, bs, codec)
    gq = jax.lax.all_gather(q2.reshape(-1), axis, tiled=False)
    gs = jax.lax.all_gather(s2, axis, tiled=False)
    full = (gq.reshape(world, chunk_blocks, bs).astype(jnp.float32)
            * gs[:, :, None]).reshape(-1)[:n]
    wire_bytes = 2 * (padded * _WIRE_ITEMSIZE[codec]
                      + scale_bytes(padded, bs))
    return (full.astype(in_dtype), shard.astype(in_dtype), new_res,
            wire_bytes, 4)


def record_sync_metrics(codec: str, collectives: int, comm_bytes: int,
                        path: str):
    """One gradient-sync round into the process-global metric families —
    shared by the eager sync, the overlapped lane, and the compiled step
    (jit.TrainStep increments per executed step; trace-time python only
    runs once, so the traced path cannot count itself)."""
    _m_syncs.value += 1
    _m_coll.labels(codec=codec, path=path).inc(collectives)
    _m_bytes.labels(codec=codec, path=path).inc(comm_bytes)


class GradCommunicator:
    """Coalesced gradient synchronizer.

    sync() runs ONE collective per bucket (two for int8: a scalar MAX for the
    shared scale + the int payload sum; two for the blockwise codecs: the
    per-block scale-vector sum + the payload sum; two for the reduce-scatter
    mode) and writes the averaged gradients back through the original
    per-param views. Per-step wire accounting lives in `.stats`:
        {"codec", "path", "n_params", "n_buckets", "collectives",
         "comm_bytes"}
    where `path` is "eager" for a host-side sync and "traced" when the sync
    ran inside a jax trace, and `comm_bytes` is always the ACTUAL wire
    format's bytes (the traced path used to claim the codec's bytes
    unconditionally).
    """

    def __init__(self, config: Optional[GradCommConfig] = None, group=None):
        self.config = config or GradCommConfig()
        self.group = group
        self._buckets: Optional[List[GradBucket]] = None
        self._bucket_key = None
        self._residuals = {}          # bucket index -> fp32 flat residual
        self.stats = {"codec": self.config.codec, "path": "eager",
                      "n_params": 0, "n_buckets": 0, "collectives": 0,
                      "comm_bytes": 0}

    # ------------------------------------------------------------- planning
    def buckets_for(self, params, dtypes=None) -> List[GradBucket]:
        """Build (and cache) the bucket assignment for this param list."""
        key = tuple((tuple(p._value.shape), str(dt)) for p, dt in
                    zip(params, dtypes or [p._value.dtype for p in params]))
        if self._buckets is None or key != self._bucket_key:
            self._buckets = build_buckets(
                params, self.config.comm_buffer_size,
                self.config.last_comm_buffer_size, dtypes=dtypes)
            # drop error-feedback residuals only when the assignment really
            # changed — a fresh communicator whose residuals were just
            # load_state_dict'ed (resume) must keep them through its first
            # bucket build, or a restart silently changes convergence
            if key != self._bucket_key:
                self._residuals.clear()
            self._bucket_key = key
        return self._buckets

    # ------------------------------------------------------------ job state
    def state_dict(self) -> dict:
        """Resume-critical communicator state: the int8 error-feedback
        residuals (cross-step quantization error) keyed by bucket, plus the
        bucket key they belong to. Stored in the checkpoint's job_state
        entry (robustness/distributed_ft.capture_job_state) — without it a
        resumed int8 run silently diverges from the uninterrupted one."""
        return {
            "codec": self.config.codec,
            "error_feedback": self.config.error_feedback,
            "block_size": self.config.block_size,
            "bucket_key": self._bucket_key,
            "residuals": {int(i): np.asarray(r)
                          for i, r in self._residuals.items()},
        }

    def load_state_dict(self, state: dict):
        """Restore state_dict() output. The codec must match — feeding fp32
        residuals into a bf16 run (or dropping int8 residuals) would change
        convergence without any error surfacing."""
        if state.get("codec") != self.config.codec:
            raise ValueError(
                f"grad_comm state codec mismatch: checkpoint has "
                f"{state.get('codec')!r}, communicator runs "
                f"{self.config.codec!r} — resume with the same wire codec")
        ckpt_bs = state.get("block_size")
        if (self.config.codec in BLOCK_CODECS and ckpt_bs is not None
                and int(ckpt_bs) != self.config.block_size):
            raise ValueError(
                f"grad_comm state block_size mismatch: checkpoint has "
                f"{ckpt_bs}, communicator runs {self.config.block_size} — "
                f"a different scale granularity silently changes the "
                f"quantization the residuals were computed against")
        self._bucket_key = state.get("bucket_key")
        self._residuals = {int(i): jnp.asarray(r)
                           for i, r in (state.get("residuals") or {}).items()}

    # ----------------------------------------------------------------- sync
    def sync(self, params, world: Optional[int] = None,
             use_reduce_scatter: bool = False):
        """All-reduce (AVG) the `.grad` of every param, bucketed + encoded.

        `world` is the number of replicas the collective averages over
        (defaults to the process world size — the eager multi-process DP
        notion). With `use_reduce_scatter`, each bucket goes through the
        bandwidth-optimal reduce_scatter -> all_gather decomposition so each
        rank reduces only its own shard (the ZeRO stage-2 grad path).
        """
        from ..profiler import RecordEvent

        params = [p for p in params if p.grad is not None]
        if world is None:
            from .env import get_world_size

            world = get_world_size()
        self.stats = {"codec": self.config.codec, "path": "eager",
                      "n_params": len(params), "n_buckets": 0,
                      "collectives": 0, "comm_bytes": 0}
        if world <= 1 or not params:
            return
        dtypes = [np.dtype(p.grad._value.dtype) for p in params]
        buckets = self.buckets_for(params, dtypes=dtypes)
        self.stats["n_buckets"] = len(buckets)
        with RecordEvent("comm"):  # the step-time breakdown's comm phase
            for b in buckets:
                reduced = self._sync_bucket(
                    b, self._flatten_bucket(b, params), world,
                    use_reduce_scatter)
                self._scatter_bucket(b, params, reduced)
        self._record_metrics(buckets)

    @staticmethod
    def _flatten_bucket(bucket: GradBucket, params):
        """The bucket's grads as one flat wire buffer. Shared verbatim by
        the serial and overlapped paths — parity depends on both sides
        concatenating identically."""
        if len(bucket.param_indices) == 1:
            return params[bucket.param_indices[0]].grad._value.reshape(-1)
        return jnp.concatenate([params[pi].grad._value.reshape(-1)
                                for pi in bucket.param_indices])

    @staticmethod
    def _scatter_bucket(bucket: GradBucket, params, reduced):
        """Write a reduced flat buffer back through the original per-param
        grad views (inverse of _flatten_bucket)."""
        for pi, off, n, shape in zip(bucket.param_indices, bucket.offsets,
                                     bucket.numels, bucket.shapes):
            g = params[pi].grad
            g._value = reduced[off:off + n].reshape(shape).astype(
                g._value.dtype)

    def _record_metrics(self, buckets, path: str = "eager"):
        """Mirror this sync's stats into the process-global registry (and
        leave one sync summary in the flight-recorder ring)."""
        codec = self.config.codec
        record_sync_metrics(codec, self.stats["collectives"],
                            self.stats["comm_bytes"], path)
        from ..observability.flight_recorder import get_flight_recorder

        get_flight_recorder().note(
            "grad_comm", "sync", codec=codec, path=path,
            n_buckets=self.stats["n_buckets"],
            collectives=self.stats["collectives"],
            comm_bytes=self.stats["comm_bytes"])
        for b in buckets:
            cap_mb = (self.config.last_comm_buffer_size if b.index == 0
                      else self.config.comm_buffer_size)
            _m_fill.observe(b.nbytes / (cap_mb * _MB))

    def _sync_bucket(self, bucket: GradBucket, flat, world: int,
                     use_reduce_scatter: bool):
        """Host-managed form of `reduce_bucket`: the error-feedback
        residual comes from / returns to `self._residuals`, and the wire
        accounting lands in `self.stats`. This is the eager sync and
        overlapped-lane entry point; a TRACED caller with an
        error-feedback codec must use `reduce_bucket` directly (storing a
        tracer on self would leak it out of the trace) — sync_async and
        jit.TrainStep do."""
        ef = (self.config.error_feedback and self.config.codec in EF_CODECS)
        residual = self._residuals.get(bucket.index) if ef else None
        reduced, new_res, wire_bytes, n_coll = self.reduce_bucket(
            bucket, flat, world, use_reduce_scatter=use_reduce_scatter,
            residual=residual)
        if new_res is not None:
            if isinstance(new_res, jax.core.Tracer):
                raise RuntimeError(
                    f"grad_comm codec {self.config.codec!r} with error "
                    f"feedback cannot run via sync() inside a trace — the "
                    f"cross-step residual would leak a tracer into host "
                    f"state. Thread it as carried state instead: "
                    f"sync_async(residuals=...) or jit.TrainStep("
                    f"grad_comm=...)")
            self._residuals[bucket.index] = new_res
        self.stats["path"] = ("traced"
                              if isinstance(reduced, jax.core.Tracer)
                              else "eager")
        self.stats["collectives"] += n_coll
        self.stats["comm_bytes"] += wire_bytes
        return reduced

    def reduce_bucket_payload(self, bucket: GradBucket, flat, world: int,
                              residual=None):
        """Blockwise reduce that STOPS at the summed wire payload: returns
        ``(q_sum, scales, new_residual, wire_bytes, collectives)`` without
        dequantizing — the fused dequant+update kernel
        (ops/pallas/fused_update.fused_dequant_update_flat) consumes the
        payload directly, so the decoded gradient never materializes in
        HBM inside a compiled step (ISSUE 13 follow-on, wired by
        jit.TrainStep's ZeRO-2 grad_comm path). The encode half — shared
        scales from the summed per-block abs-max, error feedback, wire
        accounting — is the exact same math as :meth:`reduce_bucket`'s
        blockwise branch; only the decode moves into the kernel."""
        codec = self.config.codec
        if codec not in BLOCK_CODECS:
            raise ValueError(
                f"reduce_bucket_payload needs a blockwise codec, got "
                f"{codec!r}")
        bs = self.config.block_size
        ef = self.config.error_feedback
        if ef and residual is not None:
            flat = flat.astype(jnp.float32) + residual
        enc, _dec = _block_kernel_ops()
        am_t = Tensor(block_absmax(flat, bs), _internal=True)
        _coll.all_reduce(am_t, op=ReduceOp.SUM, group=self.group)
        scales = block_scales(am_t._value, codec)
        q = enc(flat, scales, bs, codec)
        new_res = block_residual(flat, q, scales, bucket.size) if ef \
            else None
        q_flat = q.reshape(-1)
        t = Tensor(q_flat, _internal=True)
        _coll.all_reduce(t, op=ReduceOp.SUM, group=self.group)
        q_sum = t._value.reshape(q.shape)
        wire_bytes = (bucket.size * _WIRE_ITEMSIZE[codec]
                      + scale_bytes(bucket.size, bs))
        return q_sum, scales, new_res, wire_bytes, 2

    def reduce_bucket(self, bucket: GradBucket, flat, world: int,
                      use_reduce_scatter: bool = False, residual=None):
        """Reduce ONE flat bucket under the configured codec — the pure
        core shared verbatim by the eager sync, the overlapped lane, and
        the traced paths (sync_async / jit.TrainStep's compiled step).

        `residual` is the incoming error-feedback residual (or None);
        returns `(reduced, new_residual, wire_bytes, collectives)` where
        `new_residual` is None for codecs without error feedback and
        `wire_bytes` counts the ACTUAL wire format (payload + any scale
        exchange, doubled for the reduce_scatter->all_gather mode)."""
        codec = self.config.codec
        ef = self.config.error_feedback and codec in EF_CODECS
        new_res = None
        if codec == "int8":
            if ef and residual is not None:
                flat = flat.astype(jnp.float32) + residual
            # share the scale: MAX over ranks makes every rank quantize with
            # the same step, so the summed ints dequantize consistently
            scale_t = Tensor(int8_scale(flat), _internal=True)
            _coll.all_reduce(scale_t, op=ReduceOp.MAX, group=self.group)
            scale = scale_t._value
            q = int8_encode(flat, scale)
            if ef:
                new_res = int8_residual(flat, q, scale)
            q_sum = self._reduce(q, ReduceOp.SUM, use_reduce_scatter, world)
            reduced = int8_decode(q_sum, scale, world, bucket.dtype)
            wire_bytes = bucket.size * _WIRE_ITEMSIZE["int8"] + 4
            n_coll = 2  # scalar scale exchange + payload
        elif codec in BLOCK_CODECS:
            if use_reduce_scatter and isinstance(flat, jax.core.Tracer):
                # in-trace ZeRO-2 path: the EQuARX §RS decomposition with
                # a requantized all_gather half (1-byte wire both ways)
                axes = _coll._axes(self.group)
                reduced, _shard, new_res, wire_bytes, n_coll = \
                    traced_reduce_scatter_quantized(
                        flat, axes if len(axes) > 1 else axes[0], world,
                        self.config,
                        residual=residual if ef else None)
                if not ef:
                    new_res = None
                return (reduced.astype(bucket.dtype), new_res, wire_bytes,
                        n_coll)
            bs = self.config.block_size
            if ef and residual is not None:
                flat = flat.astype(jnp.float32) + residual
            # blockwise shared scales: SUM the local per-block abs-max over
            # ranks (the vector rides a sum-typed exchange a packed wire
            # format fuses with the payload — no scalar MAX round trip);
            # the sum bounds every rank's abs-max, so all ranks quantize
            # with the identical per-block step
            enc, dec = _block_kernel_ops()
            am_t = Tensor(block_absmax(flat, bs), _internal=True)
            _coll.all_reduce(am_t, op=ReduceOp.SUM, group=self.group)
            scales = block_scales(am_t._value, codec)
            q = enc(flat, scales, bs, codec)
            if ef:
                new_res = block_residual(flat, q, scales, bucket.size)
            # the (n_blocks, block_size) payload rides the wire flat —
            # _reduce's reduce_scatter padding/reassembly is 1-D (this was
            # a latent eager ZeRO-2 x blockwise-codec crash; the traced RS
            # path above never hit it)
            q_sum = self._reduce(q.reshape(-1), ReduceOp.SUM,
                                 use_reduce_scatter, world).reshape(q.shape)
            reduced = dec(q_sum, scales, world, bucket.dtype,
                          bucket.size)
            wire_bytes = (bucket.size * _WIRE_ITEMSIZE[codec]
                          + scale_bytes(bucket.size, bs))
            n_coll = 2  # scale-vector exchange + payload
        elif codec == "bf16" and bucket.dtype.itemsize > 2:
            wire = encode_bf16(flat)
            reduced = decode_bf16(
                self._reduce(wire, ReduceOp.AVG, use_reduce_scatter, world),
                bucket.dtype)
            wire_bytes = bucket.size * _WIRE_ITEMSIZE["bf16"]
            n_coll = 1
        else:
            reduced = self._reduce(flat, ReduceOp.AVG, use_reduce_scatter,
                                   world)
            wire_bytes = bucket.size * flat.dtype.itemsize
            n_coll = 1
        if use_reduce_scatter:
            # the payload crosses the wire twice (reduce_scatter half +
            # all_gather half) and counts as two collectives
            payload = wire_bytes - (4 if codec == "int8" else 0) \
                - (scale_bytes(bucket.size, self.config.block_size)
                   if codec in BLOCK_CODECS else 0)
            wire_bytes += payload
            n_coll += 1
        return reduced, new_res, wire_bytes, n_coll

    def __repr__(self):
        return (f"GradCommunicator({self.config!r}, "
                f"buckets={len(self._buckets or [])})")

    def _reduce(self, wire_val, op, use_reduce_scatter: bool, world: int):
        if use_reduce_scatter:
            # each rank reduces only its own shard, then the shards are
            # re-assembled — the ring-allreduce decomposition, but the shard
            # is available between the two halves for sharded optimizers
            n = wire_val.shape[0]
            pad = (-n) % world
            if pad:
                wire_val = jnp.concatenate(
                    [wire_val, jnp.zeros((pad,), wire_val.dtype)])
            t = Tensor(wire_val, _internal=True)
            shard = _coll.reduce_scatter(t, op=op, group=self.group)
            full = _coll.all_gather(None, shard, group=self.group)
            return full._value.reshape(-1)[:n]
        t = Tensor(wire_val, _internal=True)
        _coll.all_reduce(t, op=op, group=self.group)
        return t._value


def config_from_strategy(strategy, comm_buffer_size: float = 25,
                         last_comm_buffer_size: float = 1,
                         default_codec: str = "fp32") -> GradCommConfig:
    """Resolve the wire codec from a DistributedStrategy: grad_comm_configs
    when the grad_comm toggle is on; else bf16 iff fp16_allreduce
    (fp16_allreduce_optimizer.py semantics); else `default_codec` — 'fp32'
    (the grads' own dtype, the seed DataParallel wire) for the DP path,
    'bf16' for the net-new sharded path. The buffer-size arguments are the
    caller's (e.g. DataParallel ctor) defaults, overridden by
    grad_comm_configs when active."""
    if strategy is not None and getattr(strategy, "grad_comm", False):
        gc = strategy.grad_comm_configs
        return GradCommConfig(
            codec=gc["codec"],
            comm_buffer_size=gc["comm_buffer_size_MB"],
            last_comm_buffer_size=gc["last_comm_buffer_size_MB"],
            error_feedback=gc["error_feedback"],
            overlap=gc.get("overlap", False),
            block_size=gc.get("block_size", 1024))
    codec = ("bf16" if strategy is not None
             and getattr(strategy, "fp16_allreduce", False)
             else default_codec)
    return GradCommConfig(codec=codec, comm_buffer_size=comm_buffer_size,
                          last_comm_buffer_size=last_comm_buffer_size)


# ---------------------------------------------------------------- planning
def comm_plan(params, config: Optional[GradCommConfig] = None,
              world: int = 2) -> dict:
    """Static wire-traffic plan for one gradient sync of `params`.

    Pure host-side accounting (no collectives run): how many collectives per
    step and how many bytes cross the wire under `config`, next to the
    un-bucketed per-parameter baseline. The communicators' own counters
    are held to it (tests/test_grad_comm.py,
    tests/test_quantized_collectives.py).
    """
    config = config or GradCommConfig()
    params = [p for p in params if not p.stop_gradient]
    buckets = build_buckets(params, config.comm_buffer_size,
                            config.last_comm_buffer_size)
    total_numel = sum(b.size for b in buckets)
    grad_bytes = sum(b.nbytes for b in buckets)
    per_elem = _WIRE_ITEMSIZE[config.codec]
    if config.codec == "bf16":
        # bf16 halves only wider-than-16-bit grads; bf16 grads ship as-is
        comm_bytes = sum(b.size * min(per_elem, b.dtype.itemsize)
                         for b in buckets)
    else:
        comm_bytes = total_numel * per_elem
    collectives = len(buckets)
    if config.codec == "int8":
        collectives *= 2                       # + scalar scale exchange
        comm_bytes += 4 * len(buckets)
    elif config.codec in BLOCK_CODECS:
        collectives *= 2                       # + per-block scale vector
        comm_bytes += sum(scale_bytes(b.size, config.block_size)
                          for b in buckets)
    return {
        "codec": config.codec,
        "world": int(world),
        "n_params": len(params),
        "n_buckets": len(buckets),
        "total_grad_numel": int(total_numel),
        "grad_bytes": int(grad_bytes),
        "collectives_per_step": int(collectives),
        "comm_bytes_per_step": int(comm_bytes),
        "per_param_collectives": len(params),
        "per_param_comm_bytes": int(grad_bytes),
        "bucket_bound": int(math.ceil(grad_bytes / _MB /
                                      config.comm_buffer_size)
                            + len({b.dtype for b in buckets}) + 1),
    }
