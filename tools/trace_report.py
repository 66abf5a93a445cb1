"""Step-time-breakdown report: chrome trace × metrics snapshot (ISSUE 3).

Joins two artifacts the telemetry layer produces —

  1. a chrome trace exported by `paddle_tpu.profiler.Profiler` (the span
     tree: "step" spans delimit steps, phase spans fill them), and
  2. a `MetricsRegistry` snapshot (JSON; grad_comm / checkpoint / dispatch
     counters)

— into ONE report: per-phase wall time next to the matching counters, so
the comm row shows not just "x ms" but "x ms, N collectives, B bytes/step"
and the two accountings can be cross-checked against
`grad_comm.comm_plan`.

Usage:
    python tools/trace_report.py TRACE.json METRICS.json
    python tools/trace_report.py --demo [--codec bf16] [--steps 3]
        # runs a 3-step gpt-test training loop (eager tape + bucketed grad
        # sync at world=2 + a checkpoint save) under Profiler+StepTimer,
        # exports trace + snapshot to --out (default /tmp), then reports.

The demo's comm row must agree with `grad_comm.comm_plan` for the same
codec (collectives/step and bytes/step) — that agreement is the acceptance
check that the wall-time view and the counter view describe the same wire.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


# ----------------------------------------------------------------- joining
def metrics_extras(metrics: dict, steps: int) -> dict:
    """Per-phase extra columns pulled out of a registry snapshot."""
    extras = {}
    steps = max(int(steps), 1)

    colls = metrics.get("grad_comm_collectives_total") or {}
    byts = metrics.get("grad_comm_bytes_total") or {}
    if colls:
        # label keys look like "codec=bf16,path=eager" (the path label is
        # ISSUE 8's eager-vs-traced wire split)
        def labels(k):
            return dict(kv.split("=", 1) for kv in k.split(","))

        total_coll = sum(colls.values())
        total_bytes = sum(byts.values())
        extras["comm"] = {
            "collectives/step": round(total_coll / steps, 2),
            "bytes/step": int(round(total_bytes / steps)),
            "codec": "+".join(sorted({labels(k).get("codec", k)
                                      for k in colls})),
        }
        by_path = {}
        for k, v in byts.items():
            p = labels(k).get("path", "eager")
            by_path[p] = by_path.get(p, 0) + v
        if len(by_path) > 1 or "traced" in by_path:
            extras["comm"]["bytes/step by path"] = {
                p: int(round(v / steps)) for p, v in sorted(by_path.items())}
    saves = metrics.get("checkpoint_save_seconds") or {}
    if isinstance(saves, dict) and saves.get("count"):
        extras["checkpoint"] = {
            "saves": saves["count"],
            "mean_ms": round(saves["mean"] * 1e3, 2),
        }
    return extras


def cache_hit_rate(metrics: dict):
    hits = metrics.get("trace_cache_hits_total") or 0
    misses = metrics.get("trace_cache_misses_total") or 0
    return hits / (hits + misses) if (hits + misses) else None


def quantile_section(metrics: dict) -> list:
    """Step-time percentiles (ISSUE 6 satellite): the p50/p95/p99 the
    serving SLO loop consumes, straight from the step_time_seconds
    histogram snapshot."""
    h = metrics.get("step_time_seconds")
    if not isinstance(h, dict) or not h.get("count"):
        return []
    row = " ".join(f"{q}={h[q] * 1e3:.2f}ms" for q in ("p50", "p95", "p99")
                   if h.get(q) is not None)
    return [f"step-time percentiles ({h['count']} steps): {row}"] if row \
        else []


def memory_section(metrics: dict, memory: dict = None) -> list:
    """HBM/host accounting: live/allocator gauges from the snapshot plus
    the compiled-path peaks vs the recorded rooflines."""
    lines = []
    live = metrics.get("live_tensor_bytes")
    if live:
        lines.append(f"live tensor bytes: {int(live):,}")
    peak = metrics.get("peak_hbm_bytes")
    if peak:
        lines.append(f"allocator peak bytes: {int(peak):,}")
    compiled = (memory or {}).get("compiled") or {}
    comp_gauge = metrics.get("compiled_peak_hbm_bytes")
    if not compiled and isinstance(comp_gauge, dict):
        compiled = {k.split("=", 1)[1]: {"peak_hbm_bytes": v}
                    for k, v in comp_gauge.items()}
    for entry, rec in sorted(compiled.items()):
        lines.append(f"compiled peak [{entry}]: "
                     f"{int(rec['peak_hbm_bytes']):,} bytes")
    rooflines = (memory or {}).get("rooflines") or {}
    if rooflines:
        names = ", ".join(f"{k}={v / 2**30:.2f}GiB"
                          for k, v in sorted(rooflines.items()))
        lines.append(f"cost-model rooflines: {names}")
    if lines:
        lines.insert(0, "memory accounting")
    return lines


def cross_rank_section(aggregated: dict) -> list:
    """Rank-0 aggregate view: merged counter totals + the straggler gauge."""
    if not aggregated:
        return []
    lines = [f"cross-rank aggregate ({len(aggregated.get('ranks', []))} "
             f"ranks: {aggregated.get('ranks')})"]
    st = aggregated.get("step_time", {})
    if st.get("per_rank_mean_s"):
        per = " ".join(f"r{i}={v * 1e3:.1f}ms"
                       for i, v in enumerate(st["per_rank_mean_s"]))
        lines.append(f"  step_time_skew: {st.get('skew', 0.0):.3f}  ({per})")
    merged = aggregated.get("metrics", {})
    for name in ("collectives_total", "grad_comm_bytes_total",
                 "eager_dispatch_total"):
        fam = merged.get(name)
        if not fam:
            continue
        if fam["kind"] == "counter":
            total = sum(fam["children"].values())
            lines.append(f"  {name} (summed over ranks): {int(total):,}")
    if aggregated.get("degraded"):
        lines.append(f"  DEGRADED to local view: {aggregated['degraded']}")
    return lines


def build_report(trace: dict, metrics: dict, aggregated: dict = None,
                 memory: dict = None) -> str:
    from paddle_tpu.observability.step_timer import (
        breakdown_from_trace, format_breakdown,
    )

    agg = breakdown_from_trace(trace)
    lines = ["step-time breakdown (trace × metrics join)",
             format_breakdown(agg, extra=metrics_extras(metrics,
                                                        agg["steps"]))]
    hr = cache_hit_rate(metrics)
    if hr is not None:
        lines.append(f"trace-cache hit rate: {hr * 100:.1f}% "
                     f"({metrics.get('trace_cache_hits_total')} hits / "
                     f"{metrics.get('trace_cache_misses_total')} misses)")
    disp = metrics.get("eager_dispatch_total")
    if disp is not None:
        lines.append(f"eager dispatches: {disp}")
    lines += quantile_section(metrics)
    lines += memory_section(metrics, memory)
    lines += cross_rank_section(aggregated or metrics.get("_aggregated"))
    return "\n".join(lines)


def load_report(trace_path: str, metrics_path: str) -> str:
    with open(trace_path) as f:
        trace = json.load(f)
    with open(metrics_path) as f:
        metrics = json.load(f)
    # accept either a bare snapshot or an export_jsonl-style record
    if "metrics" in metrics and isinstance(metrics["metrics"], dict):
        metrics = metrics["metrics"]
    return build_report(trace, metrics)


# -------------------------------------------------------------------- demo
def run_demo(out_dir: str, steps: int = 3, codec: str = "bf16",
             world: int = 2):
    """3-step gpt-test eager training run, fully instrumented: Profiler
    trace (span tree), StepTimer rows, grad_comm counters at `world`,
    one checkpoint save. Returns (trace_path, metrics_path, report)."""
    import numpy as np

    import paddle_tpu as paddle
    import paddle_tpu.optimizer as opt
    from paddle_tpu.models import (
        GPTForCausalLM, GPTPretrainingCriterion, gpt_presets,
    )
    from paddle_tpu.observability import StepTimer, get_registry
    from paddle_tpu.profiler import Profiler, ProfilerTarget, RecordEvent
    from paddle_tpu.robustness.checkpoint import CheckpointManager
    from paddle_tpu.distributed import grad_comm

    os.makedirs(out_dir, exist_ok=True)
    reg = get_registry()
    reg.reset()

    cfg = gpt_presets("gpt-test")
    model = GPTForCausalLM(cfg, seed=0)
    crit = GPTPretrainingCriterion()
    optim = opt.AdamW(learning_rate=1e-4, parameters=model.parameters())
    comm = grad_comm.GradCommunicator(grad_comm.GradCommConfig(codec=codec))
    ckpt = CheckpointManager(os.path.join(out_dir, "ckpt"), keep_last_n=1)
    params = [p for p in model.parameters() if not p.stop_gradient]

    rs = np.random.RandomState(0)
    batch, seq = 2, 32

    timer = StepTimer(registry=reg)
    prof = Profiler(targets=[ProfilerTarget.CPU])
    step_seconds = []
    with prof, timer:
        for i in range(steps):
            t0 = time.perf_counter()
            with RecordEvent("step"):
                with RecordEvent("data"):
                    ids = paddle.to_tensor(
                        rs.randint(0, cfg.vocab_size, (batch, seq)),
                        dtype="int64")
                    labels = paddle.to_tensor(
                        rs.randint(0, cfg.vocab_size, (batch, seq)),
                        dtype="int64")
                with RecordEvent("forward"):
                    logits = model(ids)
                    loss = crit(logits, labels)
                with RecordEvent("backward"):
                    loss.backward()
                comm.sync(params, world=world)   # emits the "comm" span
                with RecordEvent("optimizer"):
                    optim.step()
                    optim.clear_grad()
                if i == steps - 1:               # emits "checkpoint" span
                    ckpt.save(model.state_dict(), i)
            prof.step()
            timer.step()
            step_seconds.append(time.perf_counter() - t0)
        ckpt.close()

    # distributed-plane sections (ISSUE 6): a memory-accounting sample and
    # one EMULATED 3-rank aggregation round — rank 1 is a 1.3x straggler,
    # so the report's skew line shows a nonzero step_time_skew the way a
    # real straggling host would
    from paddle_tpu.observability import (
        MetricsAggregator, memory as obs_memory, note_step_time,
    )

    for s in step_seconds:
        note_step_time(s)
    memory = obs_memory.memory_report()

    def _emulated_gather(payload, _ranks=3, _straggler=1.3):
        import copy

        outs = []
        for r in range(_ranks):
            p = copy.deepcopy(payload)
            p["rank"] = r
            mean = p["step_time"].get("mean_s") or 0.0
            if r == 1:
                p["step_time"]["mean_s"] = mean * _straggler
            outs.append(p)
        return outs

    aggregated = MetricsAggregator(gather_fn=_emulated_gather).aggregate()

    trace_path = os.path.join(out_dir, "trace.json")
    prof.export(trace_path)
    metrics_path = os.path.join(out_dir, "metrics.json")
    snapshot = reg.snapshot()
    with open(metrics_path, "w") as f:
        json.dump(snapshot, f, indent=1)

    with open(trace_path) as f:
        trace = json.load(f)
    report = build_report(trace, snapshot, aggregated=aggregated,
                          memory=memory)
    # cross-check: the comm row's counters must equal the communicator's
    # own per-step stats (the accounting grad_comm.comm_plan plans)
    per_step_coll = comm.stats["collectives"]
    per_step_bytes = comm.stats["comm_bytes"]
    report += (f"\ngrad_comm cross-check ({codec}, world={world}): "
               f"{per_step_coll} collectives/step, "
               f"{per_step_bytes} bytes/step")
    return trace_path, metrics_path, report


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("trace", nargs="?", help="chrome trace JSON")
    ap.add_argument("metrics", nargs="?", help="metrics snapshot JSON")
    ap.add_argument("--demo", action="store_true",
                    help="run the instrumented 3-step gpt-test loop first")
    ap.add_argument("--steps", type=int, default=3)
    ap.add_argument("--codec", default="bf16",
                    help="grad_comm codec for the demo (fp32|bf16|int8)")
    ap.add_argument("--out", default="/tmp/paddle_tpu_trace_report",
                    help="demo output directory")
    args = ap.parse_args(argv)

    if args.demo:
        trace_path, metrics_path, report = run_demo(
            args.out, steps=args.steps, codec=args.codec)
        print(f"# trace:   {trace_path}\n# metrics: {metrics_path}")
        print(report)
        return 0
    if not (args.trace and args.metrics):
        ap.error("TRACE and METRICS paths required (or --demo)")
    print(load_report(args.trace, args.metrics))
    return 0


if __name__ == "__main__":
    sys.exit(main())
