"""One run of one cell: device check, set-up clock, compile events, the
kind's driver, the per-layer readers, and the one result line.

The contract (the driver reads the LAST line of stdout as one JSON object):
  correct, attempted, failed, metrics{name: {value, unit}}, device{platform,
  kind, count, memory_peak_bytes [, busy_s, window_s]} [, breakdown],
  compared{name: [number, limit]}.
With --trace 0 the metrics are the cell's end-to-end metrics, with
--trace 1 its per-layer metrics. `compared` comes last: every number that
decided `correct` beside its limit, also the run's last lines on stderr.
"""
from __future__ import annotations

import json
import os
import sys
import time
from dataclasses import dataclass, field
from typing import Optional

from . import manifest as mf


class NotATpu(SystemExit):
    """JAX found no TPU, or fewer chips than the cell asks for."""


@dataclass
class Opts:
    seed: int
    seconds: float
    trace: bool
    trace_dir: Optional[str] = None   # where a traced run writes its trace
    log: object = None                # print-like, for earlier lines


@dataclass
class Record:
    """What a kind's driver hands back. The run is correct when
    `why_incorrect` is empty."""
    attempted: int
    failed: int
    end_to_end: dict                  # name -> value (without setup_s)
    t_window_start: float             # time.monotonic(): set-up ends here
    t_window_end: float               # ... and the measured work here
    obs: dict = field(default_factory=dict)   # raw material for readers
    why_incorrect: list = field(default_factory=list)
    # every number that decided `correct`: name -> (number, its limit)
    compared: dict = field(default_factory=dict)


class CompileLog:
    """What JAX itself reports about compilation (jax.monitoring), split at
    the window's start: programs compiled, seconds in tracing + lowering +
    backend compile (or cache retrieval), persistent-cache hits."""

    _DURATIONS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax

        self.events = []   # (t_monotonic, kind, seconds)
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, name, secs, **_):
        if name in self._DURATIONS:
            kind = "program" if name.endswith("backend_compile_duration") \
                else "trace"
            self.events.append((time.monotonic(), kind, float(secs)))

    def _event(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.events.append((time.monotonic(), "hit", 0.0))
        elif name == "/jax/compilation_cache/cache_misses":
            self.events.append((time.monotonic(), "miss", 0.0))

    def summary(self, t_split: float, t_end: float) -> dict:
        before = [e for e in self.events if e[0] < t_split]
        inside = [e for e in self.events if t_split <= e[0] <= t_end]
        return {
            "compile_s": sum(e[2] for e in before),
            "compile_cache_hits": sum(e[1] == "hit" for e in before),
            "compile_cache_misses": sum(e[1] == "miss" for e in before),
            "programs_in_setup": sum(e[1] == "program" for e in before),
            "compiles_in_window": sum(e[1] == "program" for e in inside),
        }


def find_device(chips: int) -> dict:
    """The device as JAX reports it; NotATpu unless it is `chips` TPUs."""
    import jax

    devices = jax.devices()
    found = {"platform": devices[0].platform, "kind": devices[0].device_kind,
             "count": len(devices)}
    if found["platform"] != "tpu" or found["count"] < chips:
        raise NotATpu(
            f"benchmark: the cell needs {chips} TPU chip(s) and JAX found "
            f"{found['kind']} x {found['count']} on platform "
            f"{found['platform']!r}. Nothing was run and no result is "
            f"printed: a CPU time is never a device number.")
    return found


def memory_peak_bytes(chips: int) -> int:
    """Peak bytes in use on the fullest of the cell's chips."""
    import jax

    peaks = []
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


def reduce_trace(trace_dir, log) -> Optional[dict]:
    """The traced slice's device trace, reduced once per run for every
    metric that reads it (benchmark/trace_reduce.py). None where there is
    no trace or no operation ran on a device in it."""
    from . import trace_reduce

    path = trace_reduce.find_xplane(trace_dir) if trace_dir else None
    if path is None:
        return None
    trace = trace_reduce.load(path)
    log(f"[trace] {path}: " + "; ".join(
        f"{p} {ls}" for p, ls in trace["lines"].items() if ls))
    red = trace_reduce.reduce(trace)
    if not red or red["busy_s"] <= 0:
        return None
    log(f"[trace] window {red['window_s']:.3f} s, busy {red['busy_s']:.3f} "
        f"s on {red['chips']} chip(s), collectives "
        f"{red['collective_s']:.3f} s ({red['collective_exposed_s']:.3f} "
        f"exposed), Mosaic {red['mosaic_s']:.3f} s in "
        f"{red['mosaic_calls']:.0f} calls per chip")
    return red


def per_layer_metrics(cell: mf.Cell, obs: dict, log=print) -> dict:
    """Each per-layer metric of the cell through its reader. A reader that
    finds nothing returns None and the metric is left out of the line."""
    out = {}
    readers = {}
    for metric in cell.per_layer:
        rname = metric["reader"]
        if rname not in readers:
            readers[rname] = mf.load_reader(cell, rname)
        value = readers[rname].read(metric, obs)
        if value is None:
            log(f"[per-layer] {metric['name']}: nothing to read")
            continue
        out[metric["name"]] = {"value": float(value), "unit": metric["unit"]}
    return out


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             t_process_start: float, root: str = mf.ROOT,
             device: Optional[dict] = None, log=None) -> dict:
    """Run one cell once and return the result object. `device` is given
    only by tests, which lift the TPU check; the command never passes it."""
    log = log or (lambda *a: print(*a, flush=True))
    cell = mf.load_cell(workload, root)
    checked = device is None
    if checked:
        device = find_device(cell.chips)
    compiles = CompileLog()
    trace_dir = None
    if trace:
        trace_dir = os.path.join(root, ".bench_trace", workload)
        import shutil

        shutil.rmtree(trace_dir, ignore_errors=True)
        os.makedirs(trace_dir, exist_ok=True)
    opts = Opts(seed=int(seed), seconds=float(seconds), trace=bool(trace),
                trace_dir=trace_dir, log=log)
    log(f"[cell] {cell.name}: config {cell.config_name}, traffic "
        f"{cell.traffic_name} (kind {cell.kind}), {cell.chips} chip(s), "
        f"seed {seed}, {seconds} s, trace {int(trace)}; device {device}")
    record: Record = mf.load_kind(cell).run(cell, opts)

    setup_s = record.t_window_start - t_process_start
    comp = compiles.summary(record.t_window_start, record.t_window_end)
    log(f"[compile] {comp}")
    if comp["compiles_in_window"]:
        record.why_incorrect.append(
            f"{comp['compiles_in_window']} program(s) compiled inside the "
            f"measured window: set-up did not warm every shape")
    if record.why_incorrect:
        log(f"[incorrect] {record.why_incorrect}")

    dev = dict(device)
    dev["memory_peak_bytes"] = memory_peak_bytes(cell.chips) if checked \
        else int(record.obs.get("memory_peak_bytes", 0))
    result = {"correct": not record.why_incorrect,
              "attempted": int(record.attempted),
              "failed": int(record.failed)}
    if not trace:
        values = dict(record.end_to_end)
        values["setup_s"] = setup_s
        result["metrics"] = {
            e["name"]: {"value": float(values[e["name"]]), "unit": e["unit"]}
            for e in cell.end_to_end}
    else:
        obs = dict(record.obs)
        tr = reduce_trace(obs.get("trace_dir"), log)
        obs.update(compile=comp, device=dev, log=log, trace=tr)
        result["metrics"] = per_layer_metrics(cell, obs, log)
        if tr:
            dev["busy_s"] = float(tr["busy_s"])
            dev["window_s"] = float(tr["window_s"])
            if tr.get("breakdown"):
                result["breakdown"] = tr["breakdown"]
    result["device"] = dev
    compared = dict(record.compared,
                    compiles_in_window=(comp["compiles_in_window"], 0))
    result["compared"] = {k: [float(v), float(lim)]
                          for k, (v, lim) in compared.items()}
    for k, (v, lim) in result["compared"].items():
        print(f"[compared] {k} {v:.6g} limit {lim:.6g}", file=sys.stderr,
              flush=True)
    return result


def main(argv, t_process_start: float) -> int:
    import argparse

    ap = argparse.ArgumentParser(prog="benchmark/run.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    seconds = args.seconds
    if seconds is None:
        seconds = float(mf.load_manifest()["run_seconds"])

    # the program under test must be there: in a directory that holds only
    # BENCHMARK.json and the benchmark's own files this fails, non-zero,
    # before any result is printed
    import paddle_tpu  # noqa: F401
    import jax

    # every program enters the persistent cache, the sub-second ones too
    # (jax's default threshold is 1 s of compile time); set before the
    # program's use_compile_cache() picks the directory
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    from paddle_tpu.jit.artifact_cache import use_compile_cache

    cache_dir = use_compile_cache()
    print(f"[cache] {cache_dir}", flush=True)
    result = run_cell(args.workload, args.seed, seconds, bool(args.trace),
                      t_process_start)
    sys.stdout.flush()
    print(json.dumps(result), flush=True)
    return 0
