"""Recommendation-scale PS hot path: compiled dense step + async sharded
embedding pipeline (ISSUE 20).

The eager Wide&Deep path (`distributed_lookup_table` per step) dispatches
dozens of host ops and one PS round trip per mini-batch. This module
replaces it with the heter-PS recipe the reference fleet ran (dense on
accelerator, sparse on host), rebuilt on this repo's primitives; what it
gains on a chip is not measured (no cell runs it: ROADMAP B6):

* **PsTrainStep** — the dense hot loop as ONE jitted XLA program (the
  `jit.TrainStep` seam: FunctionalModule + optimizer.apply_gradients_tree
  + donated carried state, warm-keyed through `jit/artifact_cache` like
  PR 19): it consumes the pre-gathered embedding rows as a `[pad_rows,
  dim]` device array plus `[batch, slots]` int32 gather indices and emits
  the sparse row-gradients as an OUTPUT (the gather's transpose is a
  scatter-add, so duplicate ids inside a batch accumulate in-trace). No
  per-slot host round trip exists inside the step.

* **PsPipeline** — double-buffered async pull/push: while step *k* runs
  on-chip, a prefetch worker pulls step *k+1*'s unique keys (directly or
  through a `HeterCache`) and a push worker commits step *k−1*'s row
  grads; `FLAGS_ps_pipeline_depth` bounds the in-flight window (depth 1 =
  bit-identical serial reference). Exposed pull/push wait — the part the
  pipeline failed to hide — is measured per step (`PsPipeline.run`).

* **BusShardedClient / PsShardService** — embedding tables sharded across
  hosts by the splitmix64 key-hash, served by request/reply actors on the
  cross-host `MessageBus` (`fleet_executor.ServiceInterceptor`); pull and
  push payloads are quantized through the PR-8 `int8_block`/`fp8_block`
  blockwise codecs with a client-side error-feedback residual per table
  shard on the push wire. Failure model per PR 4: per-attempt timeout +
  exponential-backoff retry; a shard that exhausts retries is declared
  dead LOUDLY (typed `DeadShardError` naming the shard task/host, ERROR
  event, flight-recorder note) — `FLAGS_ps_degraded_ok` switches to a
  degraded mode that serves zeros for the dead shard's keys and
  drops-and-counts its pushes instead of failing the step.

Wire-byte accounting (`ps_pull_bytes_total{codec=}` /
`ps_push_bytes_total{codec=}`) counts what actually crosses the bus:
quantized payload + per-block fp32 scales + uint64 keys. Push retries are
at-least-once: a reply lost after the server applied the push re-applies
the merged gradient once — acceptable under downpour semantics, flagged
here because it is a real semantic of retried non-idempotent RPCs.
"""
from __future__ import annotations

import threading
import time
from collections import deque
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List, Optional, Sequence

import numpy as np

from ...framework.flags import flag
from ...observability.metrics import get_registry as _get_registry

__all__ = [
    "DeadShardError", "PsShardService", "BusShardedClient",
    "make_sharded_ps", "PsTrainStep", "PsPipeline", "encode_rows",
    "decode_rows", "wire_nbytes",
]

_m_pull_bytes = _get_registry().counter(
    "ps_pull_bytes_total", help="sharded PS pull payload bytes on the wire",
    labels=("codec",))
_m_push_bytes = _get_registry().counter(
    "ps_push_bytes_total", help="sharded PS push payload bytes on the wire",
    labels=("codec",))
_m_degraded = _get_registry().counter(
    "ps_degraded_ops_total",
    help="pull/push ops served degraded because a shard host is dead",
    labels=("shard",))
_m_steps = _get_registry().counter(
    "ps_pipeline_steps_total", help="compiled PS pipeline steps run").bind()

PS_WIRE_CODECS = ("fp32", "int8_block", "fp8_block")


class DeadShardError(RuntimeError):
    """A shard host exhausted its pull/push retries — the PR-4 fail-fast
    path. Carries the shard index and bus task id so a stall names the
    hung host."""

    def __init__(self, msg, shard=None, task_id=None, op=None):
        super().__init__(msg)
        self.shard = shard
        self.task_id = task_id
        self.op = op


# --------------------------------------------------------------------------
# blockwise wire codec (the PR-8 grad_comm transforms, packed for the TLV
# bus: int8 payloads travel as np.int8, fp8 as the uint8 bitcast)
# --------------------------------------------------------------------------

def _codec_block():
    return int(flag("FLAGS_ps_wire_block", 1024))


def _fp8_np_dtype():
    import jax.numpy as jnp

    return np.dtype(jnp.float8_e4m3fn)


def _np_blocks(flat: np.ndarray, bs: int) -> np.ndarray:
    """(n_blocks, bs) zero-padded view — grad_comm._as_blocks in numpy."""
    n = flat.size
    nb = -(-n // bs)
    pad = nb * bs - n
    if pad:
        flat = np.concatenate([flat, np.zeros(pad, np.float32)])
    return flat.reshape(nb, bs)


def encode_rows(rows: np.ndarray, codec: str, block: Optional[int] = None):
    """[n, dim] f32 -> (wire payload dict, EF residual [n, dim] or None).

    The quantization math is grad_comm's blockwise codec mirrored
    IEEE-op-for-op in numpy (abs-max/block, scale = max(absmax,1e-12)/QMAX,
    round-half-to-even to [-127,127] int8 / cast to float8_e4m3fn) — the
    parity test pins bit-equality against block_absmax/block_scales/
    block_encode, so the bits on this wire are the bits every PR-8
    guarantee was proven against. numpy on purpose: this runs on the
    pull/push worker threads for a DIFFERENT row count every step, and the
    jnp pair would mint one compiled program per distinct numel (measured:
    the int8 pipeline ran slower than eager before this)."""
    rows = np.asarray(rows, np.float32)
    if codec == "fp32":
        return {"codec": "fp32", "rows": rows}, None
    if codec not in PS_WIRE_CODECS:
        raise ValueError(f"unknown PS wire codec {codec!r}; "
                         f"one of {PS_WIRE_CODECS}")
    from ..grad_comm import _QMAX

    bs = int(block or _codec_block())
    numel = rows.size
    blocks = _np_blocks(rows.reshape(-1), bs)
    absmax = np.abs(blocks).max(axis=1)
    scales = (np.maximum(absmax, 1e-12) / _QMAX[codec]).astype(np.float32)
    q = blocks / scales[:, None]
    if codec == "int8_block":
        qv = np.clip(np.round(q), -127, 127).astype(np.int8)
        wire = qv
    else:  # fp8_block: the exact fp8 values, bitcast to uint8 for the TLV
        # (one rounding f32->f8E4M3FN, as XLA in jaxlib 0.9.0 does it)
        qv = q.astype(_fp8_np_dtype())
        wire = qv.view(np.uint8)
    # Only the first ``numel`` quantized elements travel — block padding
    # dequantizes to zeros, so the receiver reconstructs it for free.
    # (Measured: at block=1024 the padding alone pushed the int8 wire
    # from 0.296x to 0.304x of fp32.)
    payload = {"codec": codec, "q": wire.reshape(-1)[:numel], "s": scales,
               "shape": list(rows.shape), "block": bs}
    deq = (qv.astype(np.float32) * scales[:, None]).reshape(-1)[:numel]
    resid = (rows.reshape(-1) - deq).reshape(rows.shape)
    return payload, resid


def decode_rows(payload) -> np.ndarray:
    """Inverse of encode_rows, pure numpy (runs on shard-host threads)."""
    if payload["codec"] == "fp32":
        return np.asarray(payload["rows"], np.float32)
    n, dim = payload["shape"]
    q = np.asarray(payload["q"]).reshape(-1)
    if payload["codec"] == "fp8_block":
        q = q.view(_fp8_np_dtype())
    scales = np.asarray(payload["s"], np.float32)
    bs = int(payload["block"])
    pad = len(scales) * bs - q.size   # wire is truncated to numel
    if pad:
        q = np.concatenate([q, np.zeros(pad, q.dtype)])
    vals = q.astype(np.float32).reshape(len(scales), bs) * scales[:, None]
    return vals.reshape(-1)[:n * dim].reshape(n, dim)


def wire_nbytes(payload, keys: Optional[np.ndarray] = None) -> int:
    """Bytes this payload puts on the bus: quantized rows (or fp32 rows) +
    per-block scales + the uint64 key vector riding with it."""
    if payload["codec"] == "fp32":
        n = int(np.asarray(payload["rows"]).nbytes)
    else:
        n = int(payload["q"].nbytes + payload["s"].nbytes)
    if keys is not None:
        n += int(np.asarray(keys).nbytes)
    return n


# --------------------------------------------------------------------------
# sharded transport over the MessageBus
# --------------------------------------------------------------------------

def _shard_of(keys: np.ndarray, n: int) -> np.ndarray:
    """splitmix64-style mix -> shard index (the PsClient._route hash, so
    bus sharding and TCP sharding agree on key placement)."""
    keys = np.asarray(keys, np.uint64).reshape(-1)
    if n == 1:
        return np.zeros(keys.shape, np.int64)
    with np.errstate(over="ignore"):
        h = keys * np.uint64(0x9E3779B97F4A7C15)
        h ^= h >> np.uint64(30)
        h = h * np.uint64(0xBF58476D1CE4E5B9)
        h ^= h >> np.uint64(31)
    return (h % np.uint64(n)).astype(np.int64)


class PsShardService:
    """One shard host: a table backend behind a bus request/reply actor.

    The backend is any PS client duck (LocalPs by default) owning this
    shard's slice of every table. Pull requests name the codec they want
    the rows quantized with; push requests arrive quantized and are
    dequantized here before the backend's merged-gradient apply."""

    def __init__(self, bus, task_id: int, backend=None, name: str = ""):
        from .. import fleet_executor as fx
        from . import LocalPs

        self.backend = backend if backend is not None else LocalPs()
        self.task_id = int(task_id)
        self.name = name or f"shard@task{task_id}"
        self._node = fx.TaskNode(task_id=self.task_id, role="compute")
        self.interceptor = fx.ServiceInterceptor(self._node, bus, {
            "create_table": self._create_table,
            "pull": self._pull,
            "push": self._push,
            "assign": self._assign,
            "add": self._add,
            "table_size": self._table_size,
        })
        self.interceptor.start()

    def _create_table(self, table_id, dim, kw=None):
        self.backend.create_table(int(table_id), int(dim), **(kw or {}))
        return True

    def _pull(self, table_id, keys, codec="fp32"):
        rows = np.asarray(
            self.backend.pull(int(table_id), np.asarray(keys, np.uint64)),
            np.float32)
        payload, _ = encode_rows(rows, codec)
        return payload

    def _push(self, table_id, keys, payload, lr=-1.0):
        keys = np.asarray(keys, np.uint64)
        grads = decode_rows(payload)
        self.backend.push(int(table_id), keys, grads, lr=float(lr))
        return True

    def _assign(self, table_id, keys, values):
        self.backend.assign(int(table_id), np.asarray(keys, np.uint64),
                            np.asarray(values, np.float32))
        return True

    def _add(self, table_id, keys, deltas):
        self.backend.add(int(table_id), np.asarray(keys, np.uint64),
                         np.asarray(deltas, np.float32))
        return True

    def _table_size(self, table_id):
        return int(self.backend.table_size(int(table_id)))

    def stop(self):
        self.interceptor.stop()
        self.interceptor.join(timeout=5)


class BusShardedClient:
    """Key-hash sharded PS client over the MessageBus — the same
    pull/push/assign/add duck as LocalPs/PsClient, so `DevicePassCache`,
    `HeterCache`, and the communicators sit on it unchanged.

    Wire: pulls ask each owning shard for rows quantized with
    `FLAGS_ps_wire_codec`; pushes quantize per shard with an
    error-feedback residual kept per (table, shard) keyed by row id (the
    PR-8 EF discipline — what the wire rounded away this push is added
    back before the next quantize of the same rows), so the quantized
    push wire converges to the fp32-wire fixpoint instead of biasing it.
    The residual store grows with the touched vocabulary of this worker,
    the same bound as the tables themselves.

    Failure model (PR 4): each RPC gets `FLAGS_ps_pull_timeout_s` per
    attempt and `FLAGS_ps_pull_retries` retries with exponential backoff.
    Exhaustion marks the shard DEAD and either raises `DeadShardError`
    (default) or, under `FLAGS_ps_degraded_ok`, serves the shard's keys
    degraded (zero rows on pull, dropped-and-counted pushes) after one
    ERROR event naming the host."""

    def __init__(self, bus, shard_tasks: Sequence[int], client_task: int,
                 codec: Optional[str] = None,
                 timeout_s: Optional[float] = None,
                 retries: Optional[int] = None,
                 degraded_ok: Optional[bool] = None,
                 shard_names: Optional[Sequence[str]] = None):
        from .. import fleet_executor as fx

        self.bus = bus
        self.shard_tasks = [int(t) for t in shard_tasks]
        self.codec = codec if codec is not None \
            else str(flag("FLAGS_ps_wire_codec", "fp32"))
        if self.codec not in PS_WIRE_CODECS:
            raise ValueError(f"FLAGS_ps_wire_codec={self.codec!r}; "
                             f"one of {PS_WIRE_CODECS}")
        self.timeout_s = float(timeout_s if timeout_s is not None
                               else flag("FLAGS_ps_pull_timeout_s", 10.0))
        self.retries = int(retries if retries is not None
                           else flag("FLAGS_ps_pull_retries", 2))
        self.degraded_ok = bool(degraded_ok if degraded_ok is not None
                                else flag("FLAGS_ps_degraded_ok", False))
        self.shard_names = list(shard_names or
                                [f"task{t}" for t in self.shard_tasks])
        self._rpc = fx.BusRpcClient(bus, int(client_task))
        self._dims: Dict[int, int] = {}
        self._resid: Dict[tuple, Dict[int, np.ndarray]] = {}
        self._resid_lock = threading.Lock()
        self._dead: set = set()
        self._pool = ThreadPoolExecutor(
            max_workers=max(2, len(self.shard_tasks)))
        self.pull_bytes = 0   # plain mirrors of the wire counters, for
        self.push_bytes = 0   # tests/bench that want deltas without /metrics
        self.dropped_pushes = 0

    # ---- failure model -------------------------------------------------
    def _declare_dead(self, shard: int, op: str, err):
        from ...observability import get_event_log
        from ...observability.flight_recorder import get_flight_recorder

        first = shard not in self._dead
        self._dead.add(shard)
        if first:
            get_event_log().error(
                "ps_shard_dead", shard=int(shard),
                task_id=self.shard_tasks[shard],
                host=self.shard_names[shard], op=op, err=str(err))
            get_flight_recorder().note(
                "ps", "shard_dead", shard=int(shard),
                host=self.shard_names[shard], op=op)
        if not self.degraded_ok:
            raise DeadShardError(
                f"PS shard {shard} ({self.shard_names[shard]}, bus task "
                f"{self.shard_tasks[shard]}) dead after "
                f"{self.retries + 1} {op} attempts x {self.timeout_s}s: "
                f"{err}", shard=shard, task_id=self.shard_tasks[shard],
                op=op)

    def _call_shard(self, shard: int, op: str, **kw):
        """One RPC under the timeout/retry/backoff policy. Returns None
        when the shard is dead and degraded mode is on (callers fill in
        the degraded behavior)."""
        if shard in self._dead:
            _m_degraded.labels(shard=str(shard)).inc()
            if self.degraded_ok:
                return None
            raise DeadShardError(
                f"PS shard {shard} ({self.shard_names[shard]}) is dead",
                shard=shard, task_id=self.shard_tasks[shard], op=op)
        delay = 0.05
        last = None
        for _attempt in range(self.retries + 1):
            try:
                return self._rpc.call(self.shard_tasks[shard], op,
                                      timeout=self.timeout_s, **kw)
            except TimeoutError as e:
                last = e
                time.sleep(delay)
                delay *= 2
        self._declare_dead(shard, op, last)  # raises unless degraded_ok
        _m_degraded.labels(shard=str(shard)).inc()
        return None

    # ---- table admin ---------------------------------------------------
    def create_table(self, table_id, dim, **kw):
        self._dims[int(table_id)] = int(dim)
        for s in range(len(self.shard_tasks)):
            self._call_shard(s, "create_table", table_id=int(table_id),
                             dim=int(dim), kw=kw)

    def table_size(self, table_id):
        total = 0
        for s in range(len(self.shard_tasks)):
            n = self._call_shard(s, "table_size", table_id=int(table_id))
            total += int(n or 0)
        return total

    # ---- data plane ----------------------------------------------------
    def _route(self, keys):
        keys = np.asarray(keys, np.uint64).reshape(-1)
        shard = _shard_of(keys, len(self.shard_tasks))
        out = []
        for s in range(len(self.shard_tasks)):
            idx = np.nonzero(shard == s)[0]
            if idx.size:
                out.append((s, idx, keys[idx]))
        return out

    def pull(self, table_id, keys, create_if_missing=True):
        keys = np.asarray(keys, np.uint64).reshape(-1)
        dim = self._dims.get(int(table_id))
        out = None
        futs = [(s, idx, sk,
                 self._pool.submit(self._call_shard, s, "pull",
                                   table_id=int(table_id), keys=sk,
                                   codec=self.codec))
                for s, idx, sk in self._route(keys)]
        for s, idx, sk, fut in futs:
            payload = fut.result()
            if payload is None:          # dead shard, degraded: zero rows
                if dim is None:
                    raise DeadShardError(
                        f"degraded pull needs a known dim for table "
                        f"{table_id}; create_table through this client",
                        shard=s, task_id=self.shard_tasks[s], op="pull")
                rows = np.zeros((idx.size, dim), np.float32)
            else:
                nb = wire_nbytes(payload, sk)
                self.pull_bytes += nb
                _m_pull_bytes.labels(codec=self.codec).inc(nb)
                rows = decode_rows(payload)
            if out is None:
                out = np.empty((keys.size, rows.shape[1]), np.float32)
            out[idx] = rows
        return out if out is not None \
            else np.zeros((0, dim or 0), np.float32)

    def _push_one(self, table_id, s, sk, grads, lr):
        """Quantize one shard's merged grads (EF residual folded in and
        carried per (table, shard)) and push."""
        g = np.asarray(grads, np.float32)
        rkey = (int(table_id), int(s))
        if self.codec != "fp32":
            with self._resid_lock:
                res = self._resid.setdefault(rkey, {})
                for i, k in enumerate(sk.tolist()):
                    r = res.get(int(k))
                    if r is not None:
                        g = g.copy() if g is grads else g
                        g[i] = g[i] + r
        payload, new_res = encode_rows(g, self.codec)
        if new_res is not None:
            with self._resid_lock:
                res = self._resid.setdefault(rkey, {})
                for i, k in enumerate(sk.tolist()):
                    res[int(k)] = new_res[i]
        ok = self._call_shard(s, "push", table_id=int(table_id), keys=sk,
                              payload=payload, lr=float(lr))
        if ok is None:                    # dead shard, degraded: drop loud
            self.dropped_pushes += len(sk)
            return
        nb = wire_nbytes(payload, sk)
        self.push_bytes += nb
        _m_push_bytes.labels(codec=self.codec).inc(nb)

    def push(self, table_id, keys, grads, lr=-1.0):
        from .communicator import merge_sparse

        keys = np.asarray(keys, np.uint64).reshape(-1)
        grads = np.asarray(grads, np.float32).reshape(keys.size, -1)
        keys, grads = merge_sparse(keys, grads)  # duplicate ids SUM here
        futs = [self._pool.submit(self._push_one, table_id, s, sk,
                                  grads[idx], lr)
                for s, idx, sk in self._route(keys)]
        for f in futs:
            f.result()

    def assign(self, table_id, keys, values):
        keys = np.asarray(keys, np.uint64).reshape(-1)
        values = np.asarray(values, np.float32).reshape(keys.size, -1)
        for s, idx, sk in self._route(keys):
            self._call_shard(s, "assign", table_id=int(table_id), keys=sk,
                             values=values[idx])

    def add(self, table_id, keys, deltas):
        keys = np.asarray(keys, np.uint64).reshape(-1)
        deltas = np.asarray(deltas, np.float32).reshape(keys.size, -1)
        for s, idx, sk in self._route(keys):
            self._call_shard(s, "add", table_id=int(table_id), keys=sk,
                             deltas=deltas[idx])

    def close(self):
        self._pool.shutdown(wait=False)
        self._rpc.close()


def make_sharded_ps(n_shards: Optional[int] = None, bus=None,
                    base_task: int = 9000, codec: Optional[str] = None,
                    **client_kw):
    """Build an in-process sharded PS: one MessageBus, `n_shards`
    PsShardService actors (LocalPs backends), one BusShardedClient.
    Returns (client, services, bus). Cross-host deployments construct the
    same pieces per rank and wire bus.listen()/connect() instead."""
    from .. import fleet_executor as fx

    n = int(n_shards if n_shards is not None else flag("FLAGS_ps_shards", 1))
    bus = bus or fx.MessageBus(rank=0)
    services = [PsShardService(bus, base_task + i, name=f"shard{i}")
                for i in range(n)]
    client = BusShardedClient(
        bus, [s.task_id for s in services], client_task=base_task + n,
        codec=codec, shard_names=[s.name for s in services], **client_kw)
    return client, services, bus


# --------------------------------------------------------------------------
# the compiled dense step
# --------------------------------------------------------------------------

_step_warm: Dict[str, object] = {}   # process-global degraded artifact tier
_step_warm_lock = threading.Lock()


class PsTrainStep:
    """ONE-dispatch dense Wide&Deep step over pre-gathered rows.

    pure(train_p, frozen_p, bvals, opt_state, rows, slots, labels, key,
    lr) -> (loss, new_p, new_state, row_grads, new_b): embedding gather +
    dense forward/backward + dense optimizer update, with the sparse
    row-gradients EMITTED as an output for the pipeline's async push
    (CompiledPassStep keeps them in a device accumulator instead — that
    is the pass-scoped variant; this is the streaming one). jax's gather
    transpose is a scatter-add, so duplicate ids within a batch sum into
    their shared row — the classic PS last-write-win bug cannot happen
    in-trace.

    Shape contract: rows [pad_rows, dim] f32, slots [batch, n_slots]
    int32, labels [batch] f32 — all fixed, so one compiled program serves
    the whole run. The compiled fn is registered in the PR-19 artifact
    tier under cache_key((model fingerprint, geometry), ...): in-process
    re-instantiations warm-start, and where jax.export exists the disk
    tier persists across processes (`FLAGS_artifact_cache_dir`)."""

    def __init__(self, model, optimizer, loss_fn, dim: int, pad_rows: int):
        from ...jit.functional import FunctionalModule

        self.model = model
        self.optimizer = optimizer
        self.loss_fn = loss_fn
        self.dim = int(dim)
        self.pad_rows = int(pad_rows)
        self._fm = FunctionalModule(model)
        self._opt_state = None
        self._step_idx = 0
        self._jit = None
        self.cache_hit = False   # warm-map hit at build time (tests)

    def _fingerprint(self, batch: int, n_slots: int) -> str:
        shapes = ",".join(f"{tuple(p._value.shape)}" for p in self._fm.params)
        return (f"ps_step:{type(self.model).__name__}:"
                f"{type(self.optimizer).__name__}:{shapes}")

    def _build(self, batch: int, n_slots: int):
        import jax

        from ...jit.artifact_cache import cache_key

        key = cache_key(self._fingerprint(batch, n_slots),
                        (self.pad_rows, self.dim, batch, n_slots),
                        "float32")
        with _step_warm_lock:
            hit = _step_warm.get(key)
        if hit is not None:
            self._jit = hit
            self.cache_hit = True
            return
        fn = jax.jit(self._pure(), donate_argnums=(3, 4))
        self._register_artifact(key, fn)
        self._jit = fn

    def _register_artifact(self, key: str, fn):
        """PR-19 artifact tier: always the in-process warm map; the disk
        tier additionally persists under FLAGS_artifact_cache_dir."""
        with _step_warm_lock:
            _step_warm[key] = fn
        root = flag("FLAGS_artifact_cache_dir", "")
        if not root:
            return
        from ...jit.artifact_cache import ArtifactCache

        ArtifactCache(root).store(key, fn)

    def _pure(self):
        import jax
        import jax.numpy as jnp

        from ...framework import autograd
        from ...jit.functional import vals_to_tensors

        fm, opt, loss_fn = self._fm, self.optimizer, self.loss_fn

        def pure(train_p, frozen_p, bvals, opt_state, rows, slots, labels,
                 key, lr):
            def loss_of(tp, rv):
                emb = jnp.take(rv, slots, axis=0)
                flat = emb.reshape((slots.shape[0], -1))
                pv = fm.merge_values(list(tp), list(frozen_p))
                out_vals, new_b = fm.call(pv, list(bvals), key, (flat,),
                                          training=True)
                outs = vals_to_tensors(out_vals)
                with autograd.no_grad():
                    loss_t = loss_fn(outs, vals_to_tensors((labels,))[0])
                return loss_t._value.astype(jnp.float32), new_b

            (loss, new_b), (g_p, g_rows) = jax.value_and_grad(
                loss_of, argnums=(0, 1), has_aux=True)(tuple(train_p), rows)
            new_p, new_state = opt.apply_gradients_tree(
                list(train_p), list(g_p), opt_state, lr)
            return loss, new_p, new_state, g_rows, new_b

        return pure

    def __call__(self, rows, slots, labels):
        """rows: [pad_rows, dim] device array (donated — do not reuse);
        slots: [batch, n_slots] int32; labels: [batch] f32. Returns
        (loss, row_grads) as DEVICE values — no host sync here; the
        pipeline's push worker syncs row_grads off the main thread."""
        import jax
        import jax.numpy as jnp

        fm, opt = self._fm, self.optimizer
        slots = jnp.asarray(slots, jnp.int32)
        if self._jit is None:
            self._build(int(slots.shape[0]), int(slots.shape[1]))
        train_p, frozen_p = fm.split_values(fm.param_values())
        if self._opt_state is None:
            self._opt_state = opt.init_state_tree(train_p)
        lr = jnp.asarray(float(opt.get_lr()) if hasattr(opt, "get_lr")
                         else 0.001, jnp.float32)
        self._step_idx += 1
        (loss, new_p, self._opt_state, g_rows, new_b) = self._jit(
            tuple(train_p), tuple(frozen_p), fm.buffer_values(),
            self._opt_state, rows, slots, jnp.asarray(labels),
            jax.random.key(self._step_idx), lr)
        ti = 0
        for p, m in zip(fm.params, fm.trainable_mask):
            if m:
                p._value = new_p[ti]
                ti += 1
        fm.bind_buffers(new_b)
        _m_steps.inc()
        return loss, g_rows


# --------------------------------------------------------------------------
# the double-buffered driver
# --------------------------------------------------------------------------

class PsPipeline:
    """Async pull/push pipeline around a PsTrainStep.

    Timing diagram at depth 2 (one box per worker thread):

        pull worker : [pull 0][pull 1 ][pull 2 ]...
        main (chip) :         [step 0 ][step 1 ][step 2 ]...
        push worker :                  [push 0 ][push 1 ]...

    While step k computes, pull k+1 prefetches and push k-1 commits; the
    main thread only ever blocks on (a) pull k's future if the prefetch
    failed to hide it (measured: exposed_pull_ms), and (b) the push of
    step k-depth if the wire fell behind (exposed_push_ms). depth 1
    degenerates to pull -> step -> push, bit-identical to the serial
    reference — the parity anchor the tests pin.

    Rows source: `client` directly (every step pulls its unique keys), or
    through a `HeterCache` (`cache=`) for admission + LRU eviction +
    coalesced write-back — the sharded/quantized wire then only sees
    misses and evictions. Tracing: each run() is one trace with
    pull_launch / pull_wait / step / push_commit spans per step (the PR-18
    shape); a pull that dies names the hung shard host in its span and in
    the DeadShardError."""

    def __init__(self, client, table_id: int, step: PsTrainStep,
                 depth: Optional[int] = None, lr_sparse: float = 0.1,
                 cache=None, name: str = "ps_pass"):
        self.client = client
        self.table_id = int(table_id)
        self.step = step
        self.depth = max(1, int(depth if depth is not None
                                else flag("FLAGS_ps_pipeline_depth", 2)))
        self.lr_sparse = float(lr_sparse)
        self.cache = cache
        self.name = name
        self._pull_pool = ThreadPoolExecutor(max_workers=1)
        self._push_pool = ThreadPoolExecutor(max_workers=1)

    # ---- worker jobs ---------------------------------------------------
    def _pull_job(self, ids: np.ndarray):
        import jax.numpy as jnp

        uniq, inv = np.unique(
            np.asarray(ids, np.uint64).reshape(-1), return_inverse=True)
        if uniq.size > self.step.pad_rows:
            raise ValueError(
                f"batch touches {uniq.size} unique ids > pad_rows="
                f"{self.step.pad_rows}; raise pad_rows")
        if self.cache is not None:
            rows = self.cache.lookup(uniq)           # device [u, dim]
            pad = self.step.pad_rows - int(rows.shape[0])
            if pad:
                rows = jnp.concatenate(
                    [rows, jnp.zeros((pad, rows.shape[1]), rows.dtype)])
        else:
            rows_np = np.asarray(
                self.client.pull(self.table_id, uniq), np.float32)
            if rows_np.shape[0] < self.step.pad_rows:
                rows_np = np.pad(rows_np, ((0, self.step.pad_rows
                                            - rows_np.shape[0]), (0, 0)))
            rows = jnp.asarray(rows_np)
        slots = inv.astype(np.int32).reshape(np.shape(ids))
        return rows, uniq, slots

    def _push_job(self, ctx, k: int, uniq: np.ndarray, g_rows):
        from ...observability.tracing import get_tracer

        t0 = time.monotonic()
        g = np.asarray(g_rows)[:uniq.size]   # device->host sync, off-main
        nz = np.any(g != 0, axis=1)
        if nz.any():
            if self.cache is not None:
                self.cache.push_grads(uniq[nz], g[nz])
            else:
                self.client.push(self.table_id, uniq[nz], g[nz],
                                 lr=self.lr_sparse)
        get_tracer().record_span(ctx, "push_commit", t_start=t0, step=k,
                                 buf=k % self.depth, n_rows=int(nz.sum()))

    # ---- the drive loop ------------------------------------------------
    def run(self, batches) -> dict:
        """batches: sequence of (ids [batch, slots] uint64, labels
        [batch] f32). Returns throughput/latency stats; losses[] carries
        the per-step loss curve for convergence checks."""
        import jax

        from ...observability.tracing import get_tracer

        batches = list(batches)
        n = len(batches)
        if n == 0:
            return {"steps": 0, "examples_per_s": 0.0, "losses": []}
        tracer = get_tracer()
        ctx = tracer.start_trace(self.name, depth=self.depth,
                                 steps=n, codec=getattr(
                                     self.client, "codec", "local"))
        look = self.depth - 1
        pulls: Dict[int, object] = {}
        pushes: deque = deque()   # (k, future)
        losses: List[float] = []
        exposed_pull = exposed_push = step_s = 0.0

        def launch_pull(i):
            if i < n:
                t0 = time.monotonic()
                pulls[i] = self._pull_pool.submit(self._pull_job,
                                                  batches[i][0])
                tracer.record_span(ctx, "pull_launch", t_start=t0, step=i,
                                   buf=i % self.depth)

        t_run = time.perf_counter()
        for i in range(min(look + 1, n)):
            launch_pull(i)
        try:
            for k in range(n):
                # bound the push window: step k must not outrun push k-depth
                while len(pushes) >= self.depth:
                    pk, fut = pushes.popleft()
                    t0 = time.monotonic()
                    fut.result()
                    exposed_push += time.monotonic() - t0
                t0 = time.monotonic()
                try:
                    rows, uniq, slots = pulls.pop(k).result()
                except DeadShardError as e:
                    tracer.record_span(ctx, "pull_wait", t_start=t0, step=k,
                                       error="dead_shard", shard=e.shard,
                                       task_id=e.task_id)
                    raise
                wait = time.monotonic() - t0
                exposed_pull += wait
                tracer.record_span(ctx, "pull_wait", t_start=t0, step=k,
                                   buf=k % self.depth, n_uniq=int(uniq.size))
                if self.depth > 1:       # prefetch while step k computes
                    launch_pull(k + look + 1)
                t0 = time.monotonic()
                loss, g_rows = self.step(rows, slots, batches[k][1])
                loss = jax.block_until_ready(loss)
                step_s += time.monotonic() - t0
                tracer.record_span(ctx, "step", t_start=t0, step=k,
                                   buf=k % self.depth)
                losses.append(float(loss))
                pushes.append((k, self._push_pool.submit(
                    self._push_job, ctx, k, uniq, g_rows)))
                if self.depth == 1:      # serial mode: commit before next pull
                    t0 = time.monotonic()
                    pushes.popleft()[1].result()
                    exposed_push += time.monotonic() - t0
                    launch_pull(k + 1)
        finally:
            while pushes:
                pushes.popleft()[1].result()
            if self.cache is not None:
                self.cache.flush()
        wall = time.perf_counter() - t_run
        batch = int(np.shape(batches[0][0])[0])
        return {
            "steps": n, "wall_s": round(wall, 4),
            "examples_per_s": round(n * batch / wall, 1),
            "exposed_pull_ms": round(1000 * exposed_pull / n, 4),
            "exposed_push_ms": round(1000 * exposed_push / n, 4),
            "step_ms": round(1000 * step_s / n, 4),
            "losses": losses,
        }

    def close(self):
        self._pull_pool.shutdown(wait=False)
        self._push_pool.shutdown(wait=False)
