"""paddle.cost_model — program cost estimation.

Reference: python/paddle/cost_model/cost_model.py (CostModel.profile_measure
over ProfilerProtobuf) + framework/ir/cost_model.cc — per-op cost feeding
passes and the auto-parallel planner.

TPU-native: XLA already computes an analytical cost model for every compiled
executable; `cost_analysis()` surfaces flops/bytes/transcendentals straight
from the compiler, and wall-time comes from a measured replay. No hand-built
per-op cost tables to maintain — the numbers are the compiler's own.
"""
from __future__ import annotations

import math
import time
from typing import Dict, Optional, Sequence

import numpy as np

__all__ = ["CostModel", "comm_cost", "zero3_cost", "kernel_roofline",
           "pipeline_cost", "ps_pipeline_cost", "DEVICE_PEAKS",
           "TARGET_DEVICE_KIND", "device_peaks",
           "HOST_OFFLOAD_BANDWIDTH_BPS"]

# effective ICI bandwidth per chip for bandwidth-optimal collectives and the
# per-collective launch overhead — rough v5e figures; both overridable per
# call. They only rank alternatives (bucketed vs per-param, codec choices);
# absolute times come from measurement / the XLA cost analysis above.
ICI_BANDWIDTH_BPS = 9e10
COLLECTIVE_LATENCY_S = 5e-6

# THE peaks table: {substring of the PJRT device_kind: (peak bf16 FLOP/s,
# HBM bytes/s)}, per chip, from the vendor's published figures (v5e: Google
# Cloud "TPU v5e" — 197 TFLOP/s bf16, 819 GB/s). jit/aot.py's roofline
# estimate, the pipeline pricer and the autotuner's noise floor read it
# (the benchmark keeps its own copy in benchmark/peaks.json). A device that is not here has no published peak:
# device_peaks() raises, it does not assume one.
DEVICE_PEAKS = {
    "v5 lite": (1.97e14, 8.19e11),   # v5e
    "v5e": (1.97e14, 8.19e11),
    "v5p": (4.59e14, 2.77e12),
    "v4": (2.75e14, 1.2e12),
    "v6": (9.2e14, 1.6e12),
}
# the chip the planners price for when the caller names none (they run
# ahead of time, off the chip)
TARGET_DEVICE_KIND = "TPU v5 lite"


def device_peaks(device_kind: str) -> tuple:
    """(peak FLOP/s, HBM bytes/s) of one chip of this PJRT ``device_kind``
    (substring match, e.g. ``"TPU v5 lite"``); ValueError if unknown."""
    kind = (device_kind or "").lower()
    for sub, peaks in DEVICE_PEAKS.items():
        if sub in kind:
            return peaks
    raise ValueError(
        f"no published peaks for device kind {device_kind!r} "
        f"(cost_model.DEVICE_PEAKS knows {sorted(DEVICE_PEAKS)})")


def kernel_roofline(flops: float, bytes_accessed: float, device_kind: str,
                    peaks: Optional[tuple] = None) -> float:
    """Roofline LOWER BOUND on one kernel execution, in seconds.

    ``max(flops / peak_flops, bytes / peak_bandwidth)`` with the chip's
    peaks from :func:`device_peaks`. A measured time below this bound is
    physically impossible — the autotune harness (ops/pallas/autotune.py)
    rejects such measurements as noise instead of persisting them as
    winners. ``peaks`` overrides the table.
    """
    peak_flops, peak_bw = peaks if peaks is not None \
        else device_peaks(device_kind)
    return max(float(flops) / peak_flops, float(bytes_accessed) / peak_bw)

# wire bytes per fp32 gradient byte (grad_comm codecs); the blockwise
# codecs add one fp32 scale per block_size elements on top of the base
# 1-byte/element payload (priced separately below)
_CODEC_RATIO = {"fp32": 1.0, "bf16": 0.5, "int8": 0.25,
                "int8_block": 0.25, "fp8_block": 0.25}
_BLOCKWISE = ("int8_block", "fp8_block")


def comm_cost(grad_bytes: float, world: int, codec: str = "bf16",
              comm_buffer_size_MB: float = 25.0,
              collectives: Optional[int] = None,
              reduce_scatter_only: bool = False,
              bandwidth: float = ICI_BANDWIDTH_BPS,
              latency_s: float = COLLECTIVE_LATENCY_S,
              overlap: bool = False,
              backward_s: float = 0.0,
              block_size: int = 1024) -> dict:
    """Analytic gradient-sync cost for the grad_comm layer.

    A ring all-reduce moves 2*(n-1)/n of the wire bytes through each chip
    (reduce-scatter half + all-gather half); `reduce_scatter_only` models the
    ZeRO stage-2 path where each rank keeps just its shard. The latency term
    is what bucketing amortizes: un-bucketed per-param sync pays it once per
    parameter, bucketed sync once per ~comm_buffer_size_MB bucket. Quantized
    codecs scale the bandwidth term by their wire ratio (int8 adds its scalar
    scale exchange to the collective count).

    `overlap` models the bucket-ready async launch (distributed/overlap.py):
    every bucket except the LAST can hide under the tail of backward —
    bounded by `backward_s`, the compute window still running when the first
    bucket closes. The exposed time can never drop below the last bucket's
    own collective (it closes when backward ends, nothing left to hide
    under). Serial sync exposes everything. The returned
    `exposed_time_s` / `hidden_time_s` / `overlap_efficiency` carry the
    split; `time_s` stays the total comm work either way.

    Gather terms (ZeRO-3): this function prices the GRADIENT direction
    only. The parameter direction — per-bucket all_gathers of the at-rest
    shards (`distributed/sharding/stage3.py`), one (world-1)/world ring
    hop per bucket, prefetched a layer ahead so only the first bucket (and
    any gather outliving its compute window) stays exposed, plus the
    param-HBM-at-rest accounting — lives in :func:`zero3_cost`; compose
    the two for a full stage-3 step estimate.
    """
    try:
        ratio = _CODEC_RATIO[codec]
    except KeyError:
        raise ValueError(f"unknown codec {codec!r}; one of "
                         f"{sorted(_CODEC_RATIO)}") from None
    wire_bytes = float(grad_bytes) * ratio
    if codec in _BLOCKWISE:
        # one fp32 scale per block of fp32 elements: 4B per block_size
        # elements = grad_bytes / block_size of scale traffic
        wire_bytes += float(grad_bytes) / float(block_size)
    n_coll = collectives if collectives is not None else max(
        1, math.ceil(wire_bytes / (comm_buffer_size_MB * 1024 * 1024)))
    if codec in (("int8",) + _BLOCKWISE) and collectives is None:
        n_coll *= 2                      # + per-bucket scale exchange
    if world <= 1:
        return {"codec": codec, "world": int(world), "wire_bytes": 0,
                "collectives": 0, "bytes_through_chip": 0.0, "time_s": 0.0,
                "exposed_time_s": 0.0, "hidden_time_s": 0.0,
                "overlap_efficiency": 0.0}
    hops = (world - 1) / world if reduce_scatter_only else 2 * (world - 1) / world
    through = wire_bytes * hops
    time_s = n_coll * latency_s + through / bandwidth
    hidden = 0.0
    if overlap and n_coll > 0:
        per_coll = time_s / n_coll       # buckets are ~uniform by cap
        hideable = time_s - per_coll     # the last bucket is always exposed
        hidden = min(hideable, max(0.0, float(backward_s)))
    return {
        "codec": codec,
        "world": int(world),
        "wire_bytes": int(wire_bytes),
        "collectives": int(n_coll),
        "bytes_through_chip": through,
        "time_s": time_s,
        "exposed_time_s": time_s - hidden,
        "hidden_time_s": hidden,
        "overlap_efficiency": hidden / time_s if time_s else 0.0,
    }


def zero3_cost(param_bytes: float, world: int,
               comm_buffer_size_MB: float = 25.0,
               bandwidth: float = ICI_BANDWIDTH_BPS,
               latency_s: float = COLLECTIVE_LATENCY_S,
               forward_s: float = 0.0,
               prefetch: bool = True,
               regather_backward: bool = False) -> dict:
    """Analytic parameter-gather cost for ZeRO-3 at-rest sharding
    (distributed/sharding/stage3.py).

    At rest each rank holds `param_bytes / world` (`param_bytes_per_rank`
    — the HBM budget the sharding buys). Forward re-materializes the
    parameters one ~`comm_buffer_size_MB` bucket at a time via all_gather:
    a ring gather moves (world-1)/world of the bucket through each chip,
    plus the per-collective launch latency.

    Synchronous gathers expose everything (`exposed_gather_s_sync`). With
    `prefetch` (the layer-ahead launch on the CollectiveLane), bucket k+1's
    gather hides under layer k's compute: only the FIRST bucket (nothing
    runs before it) plus whatever gather work outlives the `forward_s`
    compute window stays exposed (`exposed_gather_s_prefetched`).

    `regather_backward` doubles the gather work for runtimes that free and
    re-gather for backward; the eager tape here keeps the forward-time
    values as vjp residuals, so the default is False.
    """
    if world <= 1:
        return {"world": int(world), "param_bytes": int(param_bytes),
                "param_bytes_per_rank": int(param_bytes), "n_buckets": 0,
                "gather_time_s": 0.0, "exposed_gather_s_sync": 0.0,
                "exposed_gather_s_prefetched": 0.0, "hidden_gather_s": 0.0}
    per_rank = int(math.ceil(param_bytes / world))
    n_buckets = max(1, math.ceil(
        param_bytes / (comm_buffer_size_MB * 1024 * 1024)))
    hops = (world - 1) / world
    t_bucket = latency_s + (param_bytes / n_buckets) * hops / bandwidth
    passes = 2 if regather_backward else 1
    total = passes * n_buckets * t_bucket
    exposed_sync = total
    if prefetch:
        # the first bucket of each pass is always exposed; the rest hide
        # under the compute window (bounded by forward_s per pass)
        hideable = total - passes * t_bucket
        hidden = min(hideable, max(0.0, float(forward_s)) * passes)
    else:
        hidden = 0.0
    return {
        "world": int(world),
        "param_bytes": int(param_bytes),
        "param_bytes_per_rank": per_rank,
        "n_buckets": int(n_buckets),
        "gather_time_s": total,
        "exposed_gather_s_sync": exposed_sync,
        "exposed_gather_s_prefetched": total - hidden,
        "hidden_gather_s": hidden,
    }


# effective host<->device (PCIe/DMA) bandwidth for the activation-offload
# tier — rough v5e figure, overridable per call; like ICI_BANDWIDTH_BPS it
# only ranks alternatives (remat vs offload), never predicts wall time
HOST_OFFLOAD_BANDWIDTH_BPS = 1.6e10

# per-layer activation policies the pipeline memory planner assigns
PIPELINE_POLICIES = ("none", "remat", "offload")


def pipeline_cost(*, pipe_degree: int, microbatches: int,
                  layers_per_stage: int,
                  activation_bytes_per_layer: float,
                  input_bytes_per_layer: float,
                  layer_flops: float,
                  policies: Optional[Sequence[str]] = None,
                  stash_offload: bool = False,
                  stash_slot_bytes: Optional[float] = None,
                  fixed_bytes: float = 0.0,
                  hbm_budget_bytes: Optional[float] = None,
                  device_kind: str = TARGET_DEVICE_KIND,
                  peaks: Optional[tuple] = None,
                  host_bandwidth_bps: float = HOST_OFFLOAD_BANDWIDTH_BPS,
                  ) -> dict:
    """Price ONE per-device 1F1B pipeline train step under an activation
    policy assignment — the pricer behind
    ``distributed/pipeline/memory_plan.plan_memory``.

    The segmented 1F1B schedule (distributed/pipeline/schedule.py) runs
    4M + 4P - 4 stage-work units per step against 4M useful ones, so the
    bubble fraction is (P-1)/(M+P-1) — the term a larger micro-batch count
    M buys down, and what this function prices against the activation
    memory M would otherwise cost (GPipe keeps O(M) residuals; 1F1B keeps
    an S = min(M, 2P-1)-slot input stash + one backward tick's residuals).

    Per-layer ``policies`` (length ``layers_per_stage``) govern what the
    backward tick's local VJP keeps resident:

      "none"     full layer internals stay (``activation_bytes_per_layer``)
                 — cheapest time, biggest memory;
      "remat"    jax.checkpoint per block: only the block INPUT persists
                 (``input_bytes_per_layer``); one extra layer-forward of
                 FLOPs per micro-batch;
      "offload"  remat + the saved block input lives in host memory: ~zero
                 device bytes at rest, the input crosses the host link
                 twice per micro-batch (priced at ``host_bandwidth_bps``).

    ``stash_offload`` moves the S-slot micro-batch input stash to the host
    tier the same way (2 crossings per micro-batch of one
    ``stash_slot_bytes`` slot; one slot stays transient on device).

    Returns a dict with the memory account (``activation_bytes_peak``,
    per-component breakdown), the time account (useful/recompute FLOPs,
    ``time_lower_bound_s`` from the device roofline plus the exposed host
    traffic), ``bubble_fraction``, and — when ``hbm_budget_bytes`` is given
    — ``fits`` plus a human-readable ``why`` naming the binding component.
    All byte inputs are PER-DEVICE (post tensor/sequence sharding).
    """
    P = int(pipe_degree)
    M = int(microbatches)
    L = int(layers_per_stage)
    if P < 1 or M < 1 or L < 1:
        raise ValueError(
            f"pipe_degree/microbatches/layers_per_stage must be >= 1, got "
            f"{P}/{M}/{L}")
    policies = list(policies if policies is not None else ["none"] * L)
    if len(policies) != L:
        raise ValueError(
            f"policies has {len(policies)} entries for {L} layers per stage")
    bad = [p for p in policies if p not in PIPELINE_POLICIES]
    if bad:
        raise ValueError(f"unknown policies {bad}; one of "
                         f"{PIPELINE_POLICIES}")
    if stash_slot_bytes is None:
        stash_slot_bytes = input_bytes_per_layer
    S = min(M, 2 * P - 1)
    bubble = (P - 1) / (M + P - 1)

    # ---- memory: stash + one backward tick's resident VJP residuals
    stash_dev = (stash_slot_bytes if stash_offload
                 else S * stash_slot_bytes)
    stash_host = S * stash_slot_bytes if stash_offload else 0.0
    resident = 0.0          # persists across the whole VJP
    transient = 0.0         # one layer's internals during its recompute
    host_bytes_per_mb = 0.0  # host-link crossings per micro-batch (one way)
    recompute_layers = 0
    for pol in policies:
        if pol == "none":
            resident += activation_bytes_per_layer
        elif pol == "remat":
            resident += input_bytes_per_layer
            transient = max(transient, activation_bytes_per_layer)
            recompute_layers += 1
        else:  # offload
            transient = max(transient, activation_bytes_per_layer
                            + input_bytes_per_layer)
            host_bytes_per_mb += 2.0 * input_bytes_per_layer
            recompute_layers += 1
    if stash_offload:
        host_bytes_per_mb += 2.0 * stash_slot_bytes
    act_peak = stash_dev + resident + transient
    peak = act_peak + float(fixed_bytes)

    # ---- time: device roofline on the schedule's work units + exposed
    # host traffic. Useful work = fwd + recompute(stage) + bwd = 4 units
    # per micro-batch per stage-layer; per-layer remat adds one more
    # layer-forward inside the VJP.
    stage_flops = L * float(layer_flops)
    useful_flops = 4.0 * M * stage_flops
    recompute_flops = M * recompute_layers * float(layer_flops)
    total_flops = (useful_flops + recompute_flops) / (1.0 - bubble)
    compute_s = kernel_roofline(total_flops, 0.0, device_kind, peaks)
    offload_s = M * host_bytes_per_mb / float(host_bandwidth_bps)
    out = {
        "pipe": P, "microbatches": M, "layers_per_stage": L,
        "stash_slots": S,
        "policies": list(policies),
        "stash_offload": bool(stash_offload),
        "bubble_fraction": bubble,
        "activation_bytes_peak": int(act_peak),
        "peak_bytes": int(peak),
        "stash_bytes_device": int(stash_dev),
        "stash_bytes_host": int(stash_host),
        "resident_residual_bytes": int(resident),
        "transient_residual_bytes": int(transient),
        "host_bytes_per_step": int(M * host_bytes_per_mb),
        "recompute_flops": recompute_flops,
        "total_flops": total_flops,
        "compute_lower_bound_s": compute_s,
        "offload_s": offload_s,
        "time_lower_bound_s": compute_s + offload_s,
    }
    if hbm_budget_bytes is not None:
        out["hbm_budget_bytes"] = int(hbm_budget_bytes)
        out["fits"] = peak <= hbm_budget_bytes
        binding = max(
            (("stash", stash_dev), ("residuals", resident + transient),
             ("fixed", float(fixed_bytes))), key=lambda kv: kv[1])[0]
        out["why"] = (
            f"peak {int(peak):,} B vs budget {int(hbm_budget_bytes):,} B "
            f"({'fits' if out['fits'] else 'OVER'}; binding component: "
            f"{binding}; bubble {bubble:.1%} at M={M}, P={P})")
    return out


_PS_WIRE_ELEM_BYTES = {"fp32": 4.0, "int8_block": 1.0, "fp8_block": 1.0}


def ps_pipeline_cost(*, batch: int, uniq_keys: int, dim: int,
                     step_s: float, depth: int = 2, codec: str = "fp32",
                     wire_block: int = 512,
                     wire_bandwidth_bps: float = 1e9,
                     rpc_latency_s: float = 2e-4) -> dict:
    """Price one steady-state step of the ISSUE-20 PS pipeline
    (distributed/ps/pipeline.py): a compiled dense step of ``step_s``
    overlapped at ``depth`` with the pull of the next batch's
    ``uniq_keys`` embedding rows and the push of the previous step's row
    grads, each ``uniq_keys * dim`` elements quantized per ``codec`` plus
    per-block fp32 scales and uint64 keys on the wire.

    depth 1 serializes pull -> step -> push; depth >= 2 hides wire time
    behind compute, so the steady-state step is max(step, pull, push) and
    the *exposed* remainders are what `PsPipeline.run` reports. The model
    only ranks codec/depth/capacity choices; absolute times are not
    measured (no cell runs the PS path)."""
    if codec not in _PS_WIRE_ELEM_BYTES:
        raise ValueError(f"unknown PS wire codec {codec!r}; one of "
                         f"{sorted(_PS_WIRE_ELEM_BYTES)}")
    u, d = int(uniq_keys), int(dim)
    numel = u * d
    scale_b = (0.0 if codec == "fp32"
               else 4.0 * math.ceil(numel / float(wire_block)))
    one_way = numel * _PS_WIRE_ELEM_BYTES[codec] + scale_b + 8.0 * u
    t_pull = one_way / float(wire_bandwidth_bps) + float(rpc_latency_s)
    t_push = one_way / float(wire_bandwidth_bps) + float(rpc_latency_s)
    if int(depth) <= 1:
        step_total = t_pull + float(step_s) + t_push
        exposed_pull, exposed_push = t_pull, t_push
    else:
        step_total = max(float(step_s), t_pull, t_push)
        exposed_pull = max(0.0, t_pull - float(step_s))
        exposed_push = max(0.0, t_push - float(step_s))
    return {
        "depth": int(depth), "codec": codec,
        "wire_bytes_per_step": int(2 * one_way),
        "pull_s": t_pull, "push_s": t_push, "step_s": float(step_s),
        "exposed_pull_s": exposed_pull, "exposed_push_s": exposed_push,
        "steady_step_s": step_total,
        "examples_per_s": int(batch) / step_total if step_total else 0.0,
        "wire_bound": step_total > float(step_s),
    }


class CostModel:
    def __init__(self):
        self._costs: Dict[str, dict] = {}

    def profile_measure(self, startup_program=None, main_program=None,
                        device="tpu", fetch_cost_list=("time",),
                        feed: Optional[dict] = None, fetch_list=None,
                        repeat: int = 5):
        """Compile main_program, read XLA's analytical cost, measure wall
        time over `repeat` replays. Returns
        {time_ms, flops, bytes_accessed, utilization_pct?}."""
        import jax

        from . import static

        exe = static.Executor()
        if startup_program is not None:
            exe.run(startup_program)
        main_program = main_program or static.default_main_program()
        feed = feed or {}

        # one run to build + compile the cached executable
        exe.run(main_program, feed=feed, fetch_list=fetch_list)
        t0 = time.perf_counter()
        for _ in range(repeat):
            res = exe.run(main_program, feed=feed, fetch_list=fetch_list)
        dt = (time.perf_counter() - t0) / repeat

        del res
        out = {"time_ms": dt * 1e3}
        out.update(self.static_cost(main_program, feed, fetch_list))
        self._costs["main"] = out
        return out

    def static_cost(self, program, feed=None, fetch_list=None) -> dict:
        """XLA analytical cost of the program's forward replay:
        flops / bytes accessed / transcendentals."""
        import jax
        import jax.numpy as jnp

        from . import static

        feed = feed or {}
        feed_names = [n for n in program.feeds if n in feed]
        feed_vids = [program.feeds[n] for n in feed_names]
        ext_ids = sorted(program.externals)

        def replay(ext_vals, feed_vals):
            env = dict(zip(ext_ids, ext_vals))
            env.update(zip(feed_vids, feed_vals))
            for rec in program.ops:
                ins = [env[s[1]] if s[0] == "var" else s[1]
                       for s in rec.arg_spec]
                o = rec.fn(*ins, **rec.kwargs)
                if rec.multi:
                    for oid, ov in zip(rec.out_ids, o):
                        env[oid] = ov
                else:
                    env[rec.out_ids[0]] = o
            if fetch_list:
                ids = static.Executor._fetch_ids(program, fetch_list)
                return tuple(env[ref] for kind, ref in ids if kind == "var")
            return tuple(env[rec.out_ids[0]] for rec in program.ops[-1:])

        ext_vals = [program.externals[v]._value for v in ext_ids]
        feed_vals = [jnp.asarray(np.asarray(feed[n])) for n in feed_names]
        compiled = jax.jit(replay).lower(ext_vals, feed_vals).compile()
        ca = compiled.cost_analysis() or {}
        return {
            "flops": float(ca.get("flops", 0.0)),
            "bytes_accessed": float(ca.get("bytes accessed", 0.0)),
            "transcendentals": float(ca.get("transcendentals", 0.0)),
        }

    comm_cost = staticmethod(comm_cost)
    zero3_cost = staticmethod(zero3_cost)
    pipeline_cost = staticmethod(pipeline_cost)

    def get_cost(self, key="main"):
        return self._costs.get(key)
