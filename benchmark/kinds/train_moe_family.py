"""Traffic kind `train_moe_family`: a sparse-expert block's train step in
chunks of k steps, on one chip's share of an expert-parallel deployment,
for whichever family the cell's configuration names.

kinds/train_moe.py and kinds/train_gdn_moe.py are this loop bound to one
adapter each; here the adapter and the operation counts come from
`cell.config["family"]`: benchmark/program_<family>.py and
benchmark/flops_<family>.py, so the next family adds those two files and
no kind. What the kind asks of them, and nothing else:

  program_<family>: build_train(cell, seed) -> {"step", "model", "cfg"};
      assign_counts(model) -> [expert layers, router outputs] int64;
      check_step_program(built, log); check_against_reference(cell, model,
      seed, log) -> {"ok", "why", "compared", ...}.
  flops_<family>: train_flops_per_token(cfg, seq, held assignments a token
      an expert layer); experts_train_cost(rows, experts, hidden, width)
      of ONE expert layer; kernel_costs(cfg, batch, seq) -> {obs key:
      {"flops", "bytes"}} per step, for the cell's roofline metrics.

The window, the chunk rule and the rate are kinds/train.py's (its
`_segment`, `segment_wall`, `chunk_seconds`, imported, as the two older
kinds import them): see its docstring for the timing. The further check is
theirs too: the layers' assignment counters, read at the window's two
ends, must sum to steps x tokens x k x expert layers, or a token was
dropped.
"""
from __future__ import annotations

import importlib
import time

import numpy as np

from benchmark import stats, traffic_gen
from benchmark.harness import Record
from benchmark.kinds.train import _segment, chunk_seconds, segment_wall


def run(cell, opts) -> Record:
    import jax

    import paddle_tpu as paddle

    log = opts.log
    tr, cfgd = cell.traffic, cell.config
    batch, seq, k = tr["global_batch"], tr["seq"], tr["chunk_steps"]
    program = importlib.import_module("benchmark.program_" + cfgd["family"])
    flops = importlib.import_module("benchmark.flops_" + cfgd["family"])
    built = program.build_train(cell, opts.seed)
    step, model = built["step"], built["model"]
    why = []

    gen = traffic_gen.ZipfTokens(opts.seed, cfgd["vocab_size"],
                                 tr["tokens"]["exponent"])
    counter = {"n": 0}

    def step_fn():
        x, y = gen.batch(counter["n"], batch, seq)
        counter["n"] += 1
        loss = step(inputs=(paddle.to_tensor(x, dtype="int64"),),
                    labels=(paddle.to_tensor(y, dtype="int64"),))
        return loss._value

    def sync(v):
        jax.block_until_ready(v)

    for _ in range(tr["warm_steps"]):
        sync(step_fn())          # the first compiles, or finds the cache
    warm_steps = counter["n"]
    counts_start = program.assign_counts(model)

    # the window: the device is drained, nothing is in flight
    drop = tr["drop_chunks"]
    t_window = time.monotonic()
    t_stop = t_window + opts.seconds
    segments, traced = [], None
    if not opts.trace:
        segments.append(_segment(step_fn, sync, k, until=t_stop))
    else:
        from benchmark import tracewin

        n_tr = tr["trace_chunks"]
        first = _segment(step_fn, sync, k, n_chunks=drop + 4)
        segments.append(first)
        est = np.median(chunk_seconds(first, drop))
        with tracewin.device_trace(opts.trace_dir) as tw:
            traced = _segment(step_fn, sync, k, n_chunks=n_tr + 1,
                              annotate=tw.annotate)
        log(f"[trace] {n_tr + 1} chunks traced, chunk ~{est:.3f} s, "
            f"profiler start+stop {tw.overhead_s:.1f} s")
        if time.monotonic() < t_stop:
            segments.append(_segment(step_fn, sync, k, until=t_stop))
    t_window_end = time.monotonic()
    steps = counter["n"] - warm_steps
    # what training holds: the comparison below runs the float32 reference
    # in this process, and the process's peak then reads that
    window_peak = (jax.devices()[0].memory_stats() or {}).get(
        "peak_bytes_in_use")
    log(f"[train] peak bytes in use at the window's end, before the "
        f"comparison with the reference: {(window_peak or 0) / 1e9:.3f} GB")

    # correctness, part 1: no token dropped. Every one of the window's
    # tokens has k assignments in every expert layer's counters
    counted = program.assign_counts(model) - counts_start
    n_moe = counted.shape[0]
    top_k = cfgd["num_experts_per_tok"]
    want = steps * batch * seq * top_k * n_moe
    lo, hi = cfgd["experts_held"]
    held = counted[:, lo:hi]
    held_share = float(held.sum()) / max(float(counted.sum()), 1.0)
    log(f"[moe] {steps} steps: {int(counted.sum())} assignments counted in "
        f"{n_moe} expert layers, {want} = steps x tokens x {top_k} x layers "
        f"expected; to the {hi - lo} held experts {int(held.sum())} "
        f"({held_share:.4f} of all; uniform "
        f"{(hi - lo) / counted.shape[1]:.4f}); per layer max/mean of the "
        f"held {[round(float(r.max() / max(r.mean(), 1e-9)), 3) for r in held]}")
    if int(counted.sum()) != want:
        why.append(f"assignment counters sum to {int(counted.sum())}, not "
                   f"{want}: a token was dropped or counted twice")

    # part 2, AFTER the window: the compiled step holds the Mosaic kernels,
    # and the program agrees with the float32 reference, in the parts the
    # family's adapter names, at the weights the window left
    program.check_step_program(built, log)
    ref = program.check_against_reference(cell, model, opts.seed, log)
    if not ref["ok"]:
        why.append(ref["why"])
    compared = dict(ref["compared"],
                    assignments_off_expected=(abs(int(counted.sum()) - want),
                                              0))

    kept = [s for seg in segments for s in chunk_seconds(seg, drop)]
    losses = [float(l) for seg in segments for l in seg["losses"]]
    losses_all = losses + [float(l) for l in (traced or {"losses": []})[
        "losses"]]
    min_kept = tr["min_kept_chunks"] if not opts.trace else 3
    if len(kept) < min_kept:
        raise SystemExit(
            f"benchmark: only {len(kept)} kept chunks of {k} steps fit in "
            f"{opts.seconds} s; the cell needs {min_kept}. Run longer.")

    wall = sum(segment_wall(seg) for seg in segments)
    timed_steps = sum(seg["steps"] for seg in segments)
    end_to_end = {}
    if not opts.trace:
        tok_s_chip = stats.rate(timed_steps * batch * seq, wall) / cell.chips
        end_to_end["train_tok_s_chip"] = tok_s_chip
        log(f"[train] {timed_steps} steps = {timed_steps * batch * seq} "
            f"tokens in {wall:.4f} s of window: {tok_s_chip:.1f} "
            f"tokens/s/chip")
    log(f"[train] {len(kept)} kept chunks of {k}; chunk s: median "
        f"{np.median(kept):.4f} min {min(kept):.4f} max {max(kept):.4f}; "
        f"tokens/s/chip by the median chunk "
        f"{stats.chunk_rate(kept, batch * seq * k) / cell.chips:.1f}")
    log(f"[train] losses (each chunk's last step): "
        f"{[round(x, 4) for x in losses]}")

    # part 3: finite losses that fall
    bad = [x for x in losses_all if not np.isfinite(x)]
    compared["non_finite_losses"] = (len(bad), 0)
    if bad:
        why.append(f"{len(bad)} non-finite losses")
    if len(losses) >= 6:
        first3, last3 = np.median(losses[:3]), np.median(losses[-3:])
        compared["loss_last3_over_first3"] = (last3 / first3, 1.0)
        if not last3 < first3:
            why.append(f"loss did not fall: median of first three "
                       f"{first3:.4f}, of last three {last3:.4f}")

    # needed work, the routed experts by what the window counted
    tokens = max(steps * batch * seq, 1)
    held_per_token_layer = float(held.sum()) / tokens / n_moe
    expert_rows_layer_step = float(held.sum()) / max(steps, 1) / n_moe
    experts_cost = flops.experts_train_cost(
        expert_rows_layer_step, hi - lo, cfgd["hidden_size"],
        cfgd["moe_intermediate_size"])
    obs = {
        "chunk_seconds": kept, "chunk_steps": k,
        "tokens_per_step": batch * seq, "chips": cell.chips,
        "flops_per_token": flops.train_flops_per_token(
            cfgd, seq, held_per_token_layer),
        "trace_dir": opts.trace_dir if traced else None,
        "traced_steps": traced["steps"] if traced else 0,
        "moe_experts_cost": {key: v * n_moe
                             for key, v in experts_cost.items()},
        "moe_load_max_over_mean": float(
            held.sum(0).max() / max(held.sum(0).mean(), 1e-9)),
        "moe_held_share": 100.0 * held_share,
        "moe_held_load": held.tolist(),
        "hbm_window_peak_gb": window_peak / 1e9 if window_peak else None,
        "reference": ref,
    }
    # per step, for the rooflines of readers/named_ops.py
    obs.update(flops.kernel_costs(cfgd, batch, seq))
    return Record(attempted=steps, failed=len(bad) * k,
                  end_to_end=end_to_end,
                  t_window_start=t_window, t_window_end=t_window_end,
                  obs=obs, why_incorrect=why, compared=compared)
