"""Runtime flag registry.

Reference: paddle/fluid/platform/flags.cc (48 PADDLE_DEFINE_EXPORTED gflags) +
python facade paddle.set_flags/get_flags (fluid/framework.py:6846,6870).
TPU-native: most CUDA allocator/cudnn flags are meaningless under PJRT; we keep
the facade, honour the ones with XLA analogs, and accept-and-store the rest so
user scripts keep running.
"""
from __future__ import annotations

import os
from typing import Any, Dict

_FLAGS: Dict[str, Any] = {
    # sanitizer-style checks (reference: FLAGS_check_nan_inf, operator.cc:1311)
    "FLAGS_check_nan_inf": False,
    "FLAGS_benchmark": False,
    # allocator knobs — stored for compat; PJRT owns HBM
    "FLAGS_allocator_strategy": "auto_growth",
    "FLAGS_fraction_of_gpu_memory_to_use": 0.92,
    "FLAGS_eager_delete_tensor_gb": 0.0,
    # determinism
    "FLAGS_cudnn_deterministic": False,
    # executor choice is moot (XLA is the executor) but kept
    "FLAGS_USE_STANDALONE_EXECUTOR": True,
    # eager-op jit cache
    "FLAGS_eager_jit_cache": True,
    # route DataLoader prefetch through the native C++ blocking queue
    # (cross-thread pickle transport; off by default — the in-process Python
    # queue hands batches over zero-copy)
    "FLAGS_use_native_dataloader_queue": False,
    # ---- reference flag tail with TPU analogs (flags.cc families) --------
    # verbosity: FLAGS_v maps onto the framework loggers' level (glog -v)
    "FLAGS_v": 0,
    # host allocator family — PJRT owns HBM; host-side fractions stored for
    # compat (fraction_of_cpu_memory_to_use etc.)
    "FLAGS_fraction_of_cpu_memory_to_use": 1.0,
    "FLAGS_initial_cpu_memory_in_mb": 500,
    "FLAGS_fast_eager_deletion_mode": True,
    "FLAGS_memory_fraction_of_eager_deletion": 1.0,
    "FLAGS_use_pinned_memory": True,
    # determinism family — stored for compat: the eager tape already
    # accumulates gradients in deterministic topological order, so
    # sort_sum_gradient has nothing extra to sort
    "FLAGS_sort_sum_gradient": False,
    "FLAGS_embedding_deterministic": False,
    # host threading — stored for compat (XLA sizes its own thread pool)
    "FLAGS_paddle_num_threads": 1,
    # PS communicator family — read as defaults by Communicator.create /
    # AsyncCommunicator (merge count, queue capacity, wait)
    "FLAGS_communicator_max_merge_var_num": 20,
    "FLAGS_communicator_send_queue_size": 20,
    "FLAGS_communicator_send_wait_times": 0.005,
    # AMP loss scaling floor (min_loss_scaling) — read by GradScaler
    "FLAGS_min_loss_scaling": 1.0,
    # profiler tail: FLAGS_enable_rpc_profiler is WIRED (reinterpreted) —
    # there is no RPC layer here (XLA/PJRT own the wire), so turning it on
    # streams per-collective / distributed-path events into
    # observability.get_event_log() instead (see _apply_rpc_profiler)
    "FLAGS_enable_rpc_profiler": False,
    "FLAGS_max_inplace_grad_add": 0,
    # default per-group timeout for eager collectives, in seconds (analog of
    # the reference's NCCL_BLOCKING_WAIT + new_group(timeout=) default).
    # 0 = disabled: collectives block forever, exactly the seed behavior.
    # Groups created while this is set inherit it (distributed/collective.py
    # new_group); robustness/distributed_ft.py enforces it on eager calls.
    "FLAGS_collective_timeout_s": 0.0,
    # ---- distributed telemetry plane (observability/, ISSUE 6) ----------
    # per-rank live telemetry HTTP endpoint (/metrics /snapshot /events
    # /flightrecorder). 0 = off; any port (use a base port + rank offset on
    # multi-process hosts) is bound by observability.start_exposition(),
    # which hapi's MetricsCallback calls on train begin.
    "FLAGS_telemetry_http_port": 0,
    # flight-recorder ring depth (entries). Read when the global recorder
    # is created (first telemetry/distributed import); 0 disables
    # recording. Reconfigure later with
    # observability.configure_flight_recorder().
    "FLAGS_flight_recorder_capacity": 4096,
    # postmortem dump directory; "" = <tmpdir>/paddle_tpu_flightrec
    "FLAGS_flight_recorder_dir": "",
    # ---- static analysis & sanitizers (analysis/, ISSUE 7) --------------
    # lock-order witness (analysis/lock_order.py): on = framework locks
    # created after the flag is set are wrapped so cross-lock acquisition
    # edges build a graph and ABBA-inversion cycles are reportable
    # (lock_order.get_graph().report()). tests/conftest.py installs it
    # BEFORE paddle_tpu imports when the env var is set, so module-level
    # locks are witnessed too.
    "FLAGS_lock_order_check": False,
    # host-sync sanitizer (analysis/host_sync.py, ISSUE 11): on = the
    # device→host sync points (np.asarray on jax arrays,
    # jax.block_until_ready, jax.device_get) are patched to record any
    # blocking sync that happens while a train-step span is open —
    # host_sync.report() names the offending source lines. Installed by
    # tests/conftest.py when the env var is set; zero overhead when off.
    "FLAGS_host_sync_check": False,
    # device selection handed to worker processes by distributed/launch
    # ("all" or a count) and read back by distributed/env.py. Declared
    # here (registry-drift rule R001) so env override and get_flags see it.
    "FLAGS_selected_tpus": "0",
    # ---- pallas kernel autotuner (ops/pallas/, ISSUE 13) ----------------
    # on = kernel dispatch (flash attention block shapes, quant_matmul
    # tiles, the fused dequant+update bucket tile, the blockwise codec
    # kernels) consults the tune cache (artifacts/kernel_tune_cache.json /
    # .cache/ runtime copy) for validated winners, and the fused-update /
    # codec pallas kernels replace their jnp compositions on TPU targets.
    # Off (default): every dispatch uses today's defaults — numerically
    # dot-for-dot the pre-ISSUE-13 behavior. Observability:
    # kernel_dispatch_total{kernel=,source=tuned|default|fallback}.
    "FLAGS_kernel_autotune": False,
    # ---- continuous-batching serving runtime (serving/, ISSUE 14) ------
    # tokens per paged-KV-cache block (the pool allocation granularity)
    "FLAGS_serving_block_tokens": 16,
    # max sequences decoded together per replica (the continuous batch)
    "FLAGS_serving_max_batch": 8,
    # request-queue admission depth: submits beyond this are REJECTED
    # (open-loop backpressure), counted serve_requests_total{outcome=}
    "FLAGS_serving_queue_depth": 256,
    # at-rest KV-cache codec: "fp32" (bit-exact) | "int8_block" |
    # "fp8_block" (grad_comm blockwise codecs; ~4x less KV HBM)
    "FLAGS_serving_kv_codec": "fp32",
    # per-replica watchdog: a scheduler tick stuck past this many seconds
    # evicts the replica (drain + re-admit its in-flight requests)
    "FLAGS_serving_watchdog_s": 30.0,
    # ---- prefix cache + speculative decode (serving/, ISSUE 16) --------
    # on (default): admission matches prompt prefixes against resident
    # refcounted KV blocks and prefills only the un-cached tail (shared
    # blocks are read-only; copy-on-write before any append; LRU over
    # refcount-0 blocks). Off: every prompt prefills from scratch
    # (pre-ISSUE-16 behavior). Counters:
    # serve_prefix_cache_{hit,miss}_tokens_total.
    "FLAGS_serving_prefix_cache": True,
    # draft tokens proposed per speculative decode step (engines built
    # with a draft_model; losslessly verified against the target —
    # gauge serve_spec_accepted_per_step)
    "FLAGS_serving_spec_k": 4,
    # ---- fleet elastic controller (ISSUE 17) ---------------------------
    # compile-aware watchdog grace: while a replica reports state
    # "compiling" (its first step traces+compiles under jit) the
    # per-replica watchdog deadline stretches to this many seconds, so a
    # cold compile is not evicted as a hang (the PR-14 bug class where a
    # 0.5s watchdog evicted the survivor for compiling)
    "FLAGS_serving_compile_grace_s": 120.0,
    # ---- request-scoped tracing (observability/tracing.py, ISSUE 18) ----
    # on (default): every ServeRequest admission mints a TraceContext and
    # lifecycle edges (queue wait, prefill, decode steps, eviction,
    # requeue, re-admission, retire) record spans into the bounded trace
    # store + the flight-recorder ring; latency/TTFT histogram
    # observations carry the trace id as an exemplar. Off: zero spans,
    # zero exemplars.
    "FLAGS_serving_tracing": True,
    # bounded per-request trace store: max retained traces (oldest
    # evicted) and max spans kept per trace (overflow counted, not kept)
    "FLAGS_trace_store_capacity": 256,
    "FLAGS_trace_max_spans": 256,
    # ---- zero-cold-start plane (jit/artifact_cache.py, ISSUE 19) -------
    # wall-clock budget for a WARM replica boot (standby pre-compiles
    # every shape bucket the set has executed before the old replica
    # drains). Exceeding it raises the typed ReplicaBootBudgetExceeded:
    # the standby is abandoned, the boot falls back to the cold path, and
    # the outcome is recorded replica_boots_total{mode=warm,
    # outcome=warm_boot_timeout} — a slow compile may cost the warm
    # handoff, never hang the fleet.
    "FLAGS_replica_boot_budget_s": 300.0,
    # root directory of the persistent compiled-artifact cache; "" =
    # in-process warm map only (no disk tier)
    "FLAGS_artifact_cache_dir": "",
    # ---- parameter-server hot path (distributed/ps/pipeline.py, ISSUE 20) --
    # in-flight window of the async pull/push pipeline: while step k runs,
    # up to depth-1 later batches may have pulls in flight and up to
    # depth-1 earlier batches may have pushes uncommitted. 1 = fully
    # serial (pull -> step -> push per batch, bit-identical to the
    # unpipelined reference); 2 = classic double buffering
    "FLAGS_ps_pipeline_depth": 2,
    # wire codec for sharded pull/push embedding payloads riding the
    # MessageBus: "fp32" (bit-exact) | "int8_block" | "fp8_block" (the
    # PR-8 blockwise codecs; ~4x less wire, error-feedback residual per
    # table shard on the push side)
    "FLAGS_ps_wire_codec": "fp32",
    # elements per abs-max scale block of the blockwise wire codecs (wider
    # than the collective default: embedding rows tolerate a coarser scale
    # and the fp32 scale vector is pure wire overhead on the PS hop)
    "FLAGS_ps_wire_block": 1024,
    # default shard-host count for make_sharded_ps() when none is given
    "FLAGS_ps_shards": 1,
    # per-attempt timeout for a sharded pull/push RPC, and how many times
    # it retries (exponential backoff) before the shard is declared dead
    "FLAGS_ps_pull_timeout_s": 10.0,
    "FLAGS_ps_pull_retries": 2,
    # behavior after a shard host is declared dead: False (default) =
    # raise the typed DeadShardError (fail fast, PR-4 failure model);
    # True = loud degraded mode — pulls return the table's init rows for
    # that shard's keys, pushes to it are dropped-and-counted
    # (ps_degraded_ops_total{shard=}), and an ERROR event names the host
    "FLAGS_ps_degraded_ok": False,
}

_compat_warned: set = set()


def _env_override():
    for k in list(_FLAGS):
        if k in os.environ:
            v = os.environ[k]
            cur = _FLAGS[k]
            if isinstance(cur, bool):
                _FLAGS[k] = v.lower() in ("1", "true", "yes")
            elif isinstance(cur, float):
                _FLAGS[k] = float(v)
            elif isinstance(cur, int):
                _FLAGS[k] = int(v)
            else:
                _FLAGS[k] = v
    if "FLAGS_v" in os.environ:  # env-set verbosity must also apply
        _apply_verbosity(int(_FLAGS["FLAGS_v"]))
    if "FLAGS_enable_rpc_profiler" in os.environ:  # env-set wiring too
        _apply_rpc_profiler(bool(_FLAGS["FLAGS_enable_rpc_profiler"]))
    if _FLAGS.get("FLAGS_lock_order_check"):
        _apply_lock_order_check()
    if _FLAGS.get("FLAGS_host_sync_check"):
        _apply_host_sync_check()


def set_flags(flags: Dict[str, Any]):
    """paddle.set_flags({'FLAGS_check_nan_inf': True})."""
    if not isinstance(flags, dict):
        raise TypeError("set_flags expects a dict")
    for k, v in flags.items():
        _FLAGS[k] = v
    if flags.get("FLAGS_check_nan_inf") or flags.get("FLAGS_cudnn_deterministic"):
        _apply_debug_flags()
    if "FLAGS_v" in flags:
        _apply_verbosity(int(flags["FLAGS_v"]))
    if "FLAGS_enable_rpc_profiler" in flags:
        _apply_rpc_profiler(bool(flags["FLAGS_enable_rpc_profiler"]))
    if flags.get("FLAGS_lock_order_check"):
        _apply_lock_order_check()
    if flags.get("FLAGS_host_sync_check"):
        _apply_host_sync_check()


def _apply_lock_order_check():
    """FLAGS_lock_order_check: install the lock-order witness. Locks
    created from here on are instrumented; for module-level locks set the
    env var instead so tests/conftest.py installs before paddle_tpu
    imports."""
    from ..analysis import lock_order

    lock_order.install()


def _apply_host_sync_check():
    """FLAGS_host_sync_check: install the host-sync sanitizer (patches
    np.asarray / jax.block_until_ready / jax.device_get + the step-span
    tracker). Idempotent; host_sync.uninstall() restores."""
    from ..analysis import host_sync

    host_sync.install()


def _apply_rpc_profiler(on: bool):
    """FLAGS_enable_rpc_profiler (reference: per-RPC spans in the fluid
    distributed/ps runtime). No RPC stack exists here, so the flag is
    REINTERPRETED rather than dropped: on = distributed collectives and ps
    pushes emit structured records into observability.get_event_log().
    A one-time compat warning spells out the reinterpretation."""
    import warnings

    from ..observability import enable_rpc_event_log

    if on and "FLAGS_enable_rpc_profiler" not in _compat_warned:
        _compat_warned.add("FLAGS_enable_rpc_profiler")
        warnings.warn(
            "flags.FLAGS_enable_rpc_profiler: there is no RPC layer on this "
            "stack (XLA/PJRT own the wire); the flag is reinterpreted — "
            "per-collective events now stream into "
            "paddle_tpu.observability.get_event_log()", stacklevel=3)
    enable_rpc_event_log(on)


def _apply_verbosity(v: int):
    """glog -v analog: raise framework logger verbosity (0 = warnings,
    1 = info, >=2 = debug)."""
    import logging

    level = (logging.WARNING if v <= 0
             else logging.INFO if v == 1 else logging.DEBUG)
    logging.getLogger("paddle_tpu").setLevel(level)


def get_flags(flags) -> Dict[str, Any]:
    if isinstance(flags, str):
        flags = [flags]
    return {k: _FLAGS.get(k) for k in flags}


def flag(name: str, default=None):
    return _FLAGS.get(name, default)


def _apply_debug_flags():
    import jax

    if _FLAGS.get("FLAGS_check_nan_inf"):
        jax.config.update("jax_debug_nans", True)


# applied at import so env-set flags (incl. FLAGS_v) take effect immediately
_env_override()
