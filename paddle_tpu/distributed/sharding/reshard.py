"""Elastic resharding: transform a sharded checkpoint from world=N to M.

The PR-1/9 bucket layout (the weight-update-sharding layout of Xu et al.,
arXiv:2004.13336) makes every per-rank artifact — ZeRO-3 at-rest parameter
shards, `FusedFlatUpdater` shard slot buffers, reduce_scatter grad shards —
the same ``[rank*chunk, (rank+1)*chunk)`` slice of one flat per-bucket
buffer, where ``chunk = ceil(size / world)`` and the buffer is zero-padded
to ``world * chunk``. The shard geometry is therefore a pure function of
(bucket sizes, world): an N→M transform is mechanical —

    1. reconstruct each flat bucket HOST-side by concatenating the N rank
       shards and stripping the N-padding back to the true bucket size;
    2. re-pad to ``M * ceil(size / M)`` and slice M new rank shards.

For fp32 payloads (parameters, optimizer slot buffers) this is bit-exact:
the transform is a relabeling of the same bytes, so the result is
BIT-IDENTICAL to the gather→rewrap reference (materialize the full
parameters at N, shard them fresh at M) — tests/test_reshard.py pins it.

Error-feedback residuals (the int8/fp8 codecs' cross-step quantization
error) are NOT sharded — each rank carries a full-bucket-sized local
residual. Resharding policy: **sum per element across the old ranks, then
re-split 1/M to every new rank** (``new_r = Σ_old res / M``). What matters
for convergence is the TOTAL error mass re-injected at the next sync
(each rank adds its residual to its local gradient before encoding and
the encoded payloads are summed over ranks), and the policy preserves
that sum exactly: Σ_new new_r = Σ_old res. In single-process emulation the
world shares one communicator, so the single residual map passes through
unchanged (N_maps = M_maps = 1) and resumed training is bit-identical.

Entry points:

- :func:`reshard_payloads` — pure host transform over the per-rank
  payload dicts `save_group_sharded_checkpoint` writes.
- :func:`reshard_checkpoint` — load a sharded checkpoint at ``step`` from
  a :class:`~paddle_tpu.robustness.checkpoint.CheckpointManager`,
  transform, and commit the world-M checkpoint back at the same step
  (manifest-gated; the old-geometry checkpoint is replaced atomically).
  Counted on ``reshard_total{from_world,to_world}`` and timed into the
  ``reshard_ms`` gauge.
- `CheckpointManager.load_sharded(..., allow_reshard=True)` and
  `ElasticController`'s scale-restart path call in here so a drifted
  geometry triggers the transform instead of refusing the resume.

Both the emulated single-process layout (one shard file whose zero3 state
carries ``peer_shards``) and the real multi-file layout (one payload per
rank, own shards only) are supported; the output keeps the input's style.
"""
from __future__ import annotations

import copy
import time
from typing import Dict, List, Optional

import numpy as np

from ...framework.errors import CheckpointCorruptError
from ...observability import get_event_log
from ...observability.metrics import get_registry as _get_registry

__all__ = [
    "chunk_of", "rechunk_flat", "assemble_full_buckets",
    "reshard_zero3_states", "reshard_slot_states", "reshard_residual_maps",
    "reshard_payloads", "reshard_checkpoint",
]

# elastic-resharding telemetry: how often geometry-drifted resumes were
# transformed instead of refused, and what the transform costs — the
# numbers that decide whether preemption-tolerant shrink is cheap enough
# to run on every rank loss
_m_reshards = _get_registry().counter(
    "reshard_total",
    help="sharded checkpoints resharded to a new world size",
    labels=("from_world", "to_world"))
_m_reshard_ms = _get_registry().gauge(
    "reshard_ms", help="wall ms of the last N->M checkpoint reshard")


def chunk_of(size: int, world: int) -> int:
    """Per-rank chunk of a flat bucket: ceil(size / world) — the PR-1/9
    padding geometry every sharded artifact in this repo uses."""
    size, world = int(size), int(world)
    return (size + (-size) % world) // world


def rechunk_flat(full: np.ndarray, size: int, world: int) -> List[np.ndarray]:
    """Slice an unpadded flat buffer into `world` padded rank chunks."""
    full = np.asarray(full).reshape(-1)[:size]
    c = chunk_of(size, world)
    pad = c * world - size
    if pad:
        full = np.concatenate([full, np.zeros((pad,), full.dtype)])
    return [full[r * c:(r + 1) * c] for r in range(world)]


def _bucket_sizes_of(state: dict, what: str) -> Dict[int, int]:
    sizes = state.get("bucket_sizes")
    if not sizes:
        raise CheckpointCorruptError(
            f"{what} predates elastic resharding: it carries no "
            f"'bucket_sizes', so the N-padding cannot be stripped before "
            f"re-chunking — re-save the checkpoint with this version "
            f"before changing the world size")
    return {int(i): int(n) for i, n in sizes.items()}


def _is_emulated_zero3(states: List[dict]) -> bool:
    return len(states) == 1 and bool(states[0].get("peer_shards"))


def assemble_full_buckets(states: List[dict]) -> Dict[int, np.ndarray]:
    """Reconstruct every flat bucket (unpadded) from zero3 shard states —
    either one emulated state (own + peer shards) or one state per rank."""
    sizes = _bucket_sizes_of(states[0], "zero3 shard state")
    old_world = int(states[0]["world"])
    full = {}
    if _is_emulated_zero3(states):
        st = states[0]
        own_rank = int(st["rank"])
        for i, size in sizes.items():
            parts = []
            for r in range(old_world):
                if r == own_rank:
                    parts.append(np.asarray(st["shards"][i]))
                else:
                    parts.append(np.asarray(st["peer_shards"][i][r]))
            full[i] = np.concatenate(parts)[:size]
    else:
        if len(states) != old_world:
            raise CheckpointCorruptError(
                f"zero3 reshard needs every rank's shard state: world is "
                f"{old_world} but {len(states)} states were given")
        by_rank = {int(s["rank"]): s for s in states}
        for i, size in sizes.items():
            parts = [np.asarray(by_rank[r]["shards"][i])
                     for r in range(old_world)]
            full[i] = np.concatenate(parts)[:size]
    return full


def reshard_zero3_states(states: List[dict], new_world: int) -> List[dict]:
    """N→M transform of `Stage3ParamShards.state_dict()` snapshots.

    Input/output style match: one emulated state in (own + peer shards) →
    one emulated state out at world M; N real per-rank states in → M out.
    fp32-bit-exact: the flat bucket bytes are only re-sliced.
    """
    new_world = int(new_world)
    sizes = _bucket_sizes_of(states[0], "zero3 shard state")
    full = assemble_full_buckets(states)
    key = states[0].get("bucket_key")
    emulated = _is_emulated_zero3(states)

    chunks = {i: rechunk_flat(full[i], sizes[i], new_world) for i in full}
    if emulated:
        out = {
            "bucket_key": key, "rank": 0, "world": new_world,
            "bucket_sizes": dict(sizes),
            "shards": {i: chunks[i][0] for i in chunks},
            "peer_shards": {i: {r: chunks[i][r]
                                for r in range(1, new_world)}
                            for i in chunks},
        }
        return [out]
    return [{
        "bucket_key": key, "rank": r, "world": new_world,
        "bucket_sizes": dict(sizes),
        "shards": {i: chunks[i][r] for i in chunks},
    } for r in range(new_world)]


def _is_scalar_slot(v) -> bool:
    return np.shape(v) == ()


def reshard_slot_states(slot_states: List[dict], new_world: int,
                        old_world: Optional[int] = None) -> List[dict]:
    """N→M transform of `FusedFlatUpdater.shard_slots_state()` snapshots.

    Slot buffers (Adam moments etc.) are laid out exactly like the
    parameter shards, so the transform is the same strip-and-re-chunk;
    scalar slots (shared beta pows) are identical on every rank and are
    copied through. Emulated input (rank 0's ``own`` + ``peer`` entries)
    yields emulated output; N per-rank states yield M.
    """
    new_world = int(new_world)
    sizes = _bucket_sizes_of(slot_states[0], "fused shard-slot state")
    emulated = len(slot_states) == 1 and bool(slot_states[0].get("peer"))
    if old_world is None:
        if emulated:
            old_world = 1 + max((r for (_i, r) in slot_states[0]["peer"]),
                                default=0)
        else:
            old_world = len(slot_states)

    def slots_of(rank: int, bucket: int) -> Optional[dict]:
        if emulated:
            st = slot_states[0]
            if rank == 0:
                return (st.get("own") or {}).get(bucket)
            return (st.get("peer") or {}).get((bucket, rank))
        return (slot_states[rank].get("own") or {}).get(bucket)

    buckets = sorted(sizes)
    # join: full flat buffer per (bucket, slot key); scalars from rank 0
    joined: Dict[int, Dict[str, object]] = {}
    for i in buckets:
        ref = slots_of(0, i)
        if ref is None:
            continue  # bucket never stepped — no slots to transform
        out = {}
        for k, v in ref.items():
            if _is_scalar_slot(v):
                out[k] = v
            else:
                parts = []
                for r in range(old_world):
                    s = slots_of(r, i)
                    if s is None:
                        raise CheckpointCorruptError(
                            f"fused shard slots for bucket {i} missing on "
                            f"rank {r} — every rank of a stepped bucket "
                            f"must carry its slot shard")
                    parts.append(np.asarray(s[k]))
                out[k] = np.concatenate(parts)[:sizes[i]]
        joined[i] = out

    def chunked(i: int, r: int) -> dict:
        out = {}
        for k, v in joined[i].items():
            if _is_scalar_slot(v):
                out[k] = v
            else:
                out[k] = rechunk_flat(v, sizes[i], new_world)[r]
        return out

    if emulated:
        return [{
            "own": {i: chunked(i, 0) for i in joined},
            "peer": {(i, r): chunked(i, r)
                     for i in joined for r in range(1, new_world)},
            "bucket_sizes": dict(sizes),
        }]
    return [{
        "own": {i: chunked(i, r) for i in joined},
        "peer": {},
        "bucket_sizes": dict(sizes),
    } for r in range(new_world)]


def reshard_residual_maps(maps: List[dict], new_count: int) -> List[dict]:
    """Error-feedback residual policy: sum per element across the old
    ranks, then re-split 1/M to every new rank — preserves the total
    error mass the next sync re-injects (Σ_new = Σ_old). A single shared
    map (single-process emulation: one communicator for the whole world)
    passes through unchanged."""
    new_count = int(new_count)
    maps = [m or {} for m in maps]
    if len(maps) == 1 and new_count == 1:
        return [dict(maps[0])]
    keys = sorted({int(k) for m in maps for k in m})
    summed = {}
    for k in keys:
        parts = [np.asarray(m[k], dtype=np.float32) for m in maps if k in m]
        summed[k] = np.sum(parts, axis=0)
    return [{k: summed[k] / new_count for k in keys}
            for _ in range(new_count)]


def _reshard_job_state(js: dict, rank: int, new_world: int,
                       residuals: Optional[dict]) -> dict:
    js = copy.deepcopy(js)
    js["rank"] = int(rank)
    if "zero3" in js and isinstance(js["zero3"], dict):
        js["zero3"] = dict(js["zero3"], world=int(new_world), rank=int(rank))
    if residuals is not None and "grad_comm" in js:
        js["grad_comm"] = dict(js["grad_comm"], residuals=residuals)
    return js


def reshard_payloads(payloads: List[dict], new_world: int) -> List[dict]:
    """Transform the per-rank payload dicts of one sharded checkpoint
    (`save_group_sharded_checkpoint`'s layout: optional ``zero3`` /
    ``model`` / ``optimizer`` / ``fused_shard_slots`` / ``job_state``
    entries) from their current sharding world to ``new_world``.

    Emulated checkpoints (one payload whose zero3 state carries peer
    shards) come back as one payload; real N-payload checkpoints come
    back as ``new_world`` payloads. Replicated entries (``model``,
    ``optimizer``) are taken from rank 0; rank-local ``job_state`` is
    re-derived per new rank with the residual re-split policy applied.
    """
    new_world = int(new_world)
    if not payloads:
        raise ValueError("reshard_payloads needs at least one payload")
    z3_states = [p["zero3"] for p in payloads if "zero3" in p]
    emulated = bool(z3_states) and _is_emulated_zero3(z3_states)
    out_count = 1 if emulated else new_world

    new_z3 = (reshard_zero3_states(z3_states, new_world)
              if z3_states else None)
    slot_states = [p["fused_shard_slots"] for p in payloads
                   if "fused_shard_slots" in p]
    new_slots = (reshard_slot_states(slot_states, new_world)
                 if slot_states else None)

    job_states = [p.get("job_state") for p in payloads]
    have_js = [js for js in job_states if js is not None]
    new_res = None
    if have_js and not emulated:
        res_maps = [(js.get("grad_comm") or {}).get("residuals") or {}
                    for js in have_js]
        if any(res_maps):
            new_res = reshard_residual_maps(res_maps, out_count)

    out = []
    for r in range(out_count):
        p = {}
        if new_z3 is not None:
            p["zero3"] = new_z3[r]
        elif "model" in payloads[0]:
            p["model"] = copy.deepcopy(payloads[0]["model"])
        if "optimizer" in payloads[0]:
            p["optimizer"] = copy.deepcopy(payloads[0]["optimizer"])
        if new_slots is not None:
            p["fused_shard_slots"] = new_slots[r]
        if have_js:
            base = job_states[r] if r < len(job_states) and \
                job_states[r] is not None else have_js[0]
            p["job_state"] = _reshard_job_state(
                base, r, new_world,
                new_res[r] if new_res is not None else None)
        out.append(p)
    return out


def _sharding_world_of(payloads: List[dict], file_world: int) -> int:
    """The checkpoint's SHARDING world: the zero3 store's world when one
    is present (covers the emulated one-file layout), else the shard-file
    count."""
    for p in payloads:
        z3 = p.get("zero3")
        if isinstance(z3, dict) and "world" in z3:
            return int(z3["world"])
    return int(file_world)


def reshard_checkpoint(manager, step: int, new_world: int, metadata=None):
    """Load the sharded checkpoint at `step` from `manager`, transform it
    to ``new_world``, and commit the result back AT THE SAME STEP (the
    atomic manifest-gated commit replaces the old-geometry directory, so
    `load_latest` / `load_sharded` immediately see the new geometry).

    No-op (returns the manifest unchanged) when the geometry already
    matches. Raises CheckpointCorruptError when the step is missing,
    invalid, or not sharded. Returns the new manifest.
    """
    new_world = int(new_world)
    manifest = manager.validate(step)
    if manifest is None:
        raise CheckpointCorruptError(
            f"reshard: checkpoint step {step} under {manager.root!r} is "
            f"missing or fails validation")
    if not manifest.get("sharded"):
        raise CheckpointCorruptError(
            f"reshard: checkpoint step {step} is not sharded — an "
            f"unsharded checkpoint has no geometry to transform")
    file_world = int(manifest["world_size"])
    payloads = [manager.load(step, shard=r) for r in range(file_world)]
    from_world = _sharding_world_of(payloads, file_world)
    if from_world == new_world:
        return manifest
    t0 = time.perf_counter()
    new_payloads = reshard_payloads(payloads, new_world)
    meta = dict(manifest.get("metadata") or {})
    meta.update(dict(metadata or {}))
    meta["resharded_from"] = from_world
    meta["resharded_to"] = new_world
    for r, p in enumerate(new_payloads):
        manager.save_shard(p, step, r, len(new_payloads))
    manager.finalize_sharded(step, len(new_payloads), metadata=meta)
    ms = (time.perf_counter() - t0) * 1e3
    _m_reshards.labels(from_world=str(from_world),
                       to_world=str(new_world)).inc()
    _m_reshard_ms.set(round(ms, 3))
    get_event_log().info(
        "reshard", "sharded checkpoint resharded", step=int(step),
        from_world=from_world, to_world=new_world, ms=round(ms, 3),
        shard_files=len(new_payloads))
    return manager.validate(step)
