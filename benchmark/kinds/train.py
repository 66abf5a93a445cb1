"""Traffic kind `train`: the GPT train step in chunks of k steps.

Built the way a user builds it (chip_smoke.py train_phase is the model):
GPTForCausalLM -> AdamW -> [group_sharded_parallel] -> jit.TrainStep, on one
chip or over the traffic file's mesh. Every step gets a fresh seeded batch.
Set-up is only that and the warm-up steps; the comparison with the
reference runs after the window, on the trained weights.

Timing. The window starts with the device drained (every warm-up step
was waited for) and runs whole chunks of k steps, one chunk always enqueued
ahead, until the clock passes --seconds; then it waits for what is in
flight. `train_tok_s_chip` is ALL the tokens stepped in the window over ALL
its wall, from the window's start to the last completion, per chip: a
stall anywhere in the window moves it.

Beside it, for the per-layer metrics: a chunk's time is the interval
between the completions (block_until_ready on the chunk's last loss) of
consecutive chunks, the first `drop_chunks` chunks of a segment and its
last are left out, `step_ms_p50` is the median chunk over k, and
`stall_share` is what the median chunk does not explain of the kept wall.

A traced run splits the window into three segments: chunks with the
profiler off, `trace_chunks` chunks inside jax.profiler's trace, chunks
with the profiler off again. Host-clock metrics come from the first and the
third only, so the profiler's own cost is not in them. A traced run
reports no end-to-end metric.
"""
from __future__ import annotations

import contextlib
import time

import numpy as np

from benchmark import flops, program_gpt, stats, traffic_gen
from benchmark.harness import Record


def _segment(step_fn, sync, k: int, until=None, n_chunks=None,
             annotate=None) -> dict:
    """Run chunks of k steps with one chunk enqueued ahead, until the clock
    passes `until` (time.monotonic) or `n_chunks` are done, then wait for
    what is in flight. Returns the start time, the completion times, every
    chunk's last loss and the steps made."""
    note = annotate or (lambda name: contextlib.nullcontext())
    t_start = time.monotonic()
    done_t, losses, pending = [], [], []

    def enqueue():
        with note("bench.enqueue_chunk"):
            last = None
            for _ in range(k):
                last = step_fn()
            pending.append(last)

    def complete():
        with note("bench.wait_chunk"):
            loss = pending.pop(0)
            sync(loss)
        done_t.append(time.monotonic())
        losses.append(loss)

    enqueue()
    while True:
        n_enqueued = len(done_t) + len(pending)
        if n_chunks is not None and n_enqueued >= n_chunks:
            break
        if until is not None and time.monotonic() >= until:
            break
        enqueue()
        complete()
    while pending:
        complete()
    return {"t_start": t_start, "done_t": done_t, "losses": losses,
            "steps": len(done_t) * k}


def segment_wall(seg: dict) -> float:
    """All of a segment's wall: its start to its last completion."""
    return seg["done_t"][-1] - seg["t_start"]


def chunk_seconds(seg: dict, drop: int) -> list:
    """Intervals between consecutive completions. Left out: the first
    `drop` chunks of the segment (ramp-up), and its LAST chunk, which
    completes with nothing enqueued behind it. The runtime lets only a
    couple of steps be in flight, so enqueueing the next chunk returns
    when the chunk before is long done and every completion is stamped
    about half a chunk late: the same lateness each time, so the intervals
    are whole chunks, except the last, which is stamped on time and comes
    out short (0.30 s against 0.61 s on the chip, PR 24)."""
    t = [seg["t_start"]] + list(seg["done_t"])
    secs = [b - a for a, b in zip(t[:-1], t[1:])]
    return secs[drop:-1]


def run(cell, opts) -> Record:
    import jax

    import paddle_tpu as paddle

    log = opts.log
    tr = cell.traffic
    batch, seq, k = tr["global_batch"], tr["seq"], tr["chunk_steps"]
    built = program_gpt.build_train(cell, opts.seed)
    step, model, cfg = built["step"], built["model"], built["cfg"]
    why = []

    gen = traffic_gen.ZipfTokens(opts.seed, cfg.vocab_size,
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

    # correctness, part 1, AFTER the window, so that set-up is only what a
    # training job pays: the compiled step holds the flash kernel, and the
    # program's forward agrees with the float32 reference on a seeded
    # sample, at the weights the window left
    program_gpt.check_step_program(built, log)
    ref = program_gpt.check_forward_loss(cell, model, cfg, opts.seed, log)
    if not ref["ok"]:
        why.append(ref["why"])
    compared = dict(ref["compared"])

    kept = [s for seg in segments for s in chunk_seconds(seg, drop)]
    losses = [float(l) for seg in segments for l in seg["losses"]]
    losses_all = losses + [float(l) for l in (traced or {"losses": []})[
        "losses"]]
    steps = counter["n"] - warm_steps
    min_kept = tr["min_kept_chunks"] if not opts.trace else 3
    if len(kept) < min_kept:
        raise SystemExit(
            f"benchmark: only {len(kept)} kept chunks of {k} steps fit in "
            f"{opts.seconds} s; the cell needs {min_kept}. Run longer.")

    # end to end: every token of the window over all of its wall. (A traced
    # run's window holds the profiler, so it reports no such rate.)
    wall = sum(segment_wall(seg) for seg in segments)
    end_to_end = {}
    if not opts.trace:
        tok_s_chip = stats.rate(steps * batch * seq, wall) / cell.chips
        end_to_end["train_tok_s_chip"] = tok_s_chip
        log(f"[train] {steps} steps = {steps * batch * seq} tokens in "
            f"{wall:.4f} s of window: {tok_s_chip:.1f} tokens/s/chip")
    log(f"[train] {len(kept)} kept chunks of {k}; chunk s: median "
        f"{np.median(kept):.4f} min {min(kept):.4f} max {max(kept):.4f}; "
        f"tokens/s/chip by the median chunk "
        f"{stats.chunk_rate(kept, batch * seq * k) / cell.chips:.1f}")
    log(f"[train] losses (each chunk's last step): "
        f"{[round(x, 4) for x in losses]}")

    # correctness, part 2: finite losses that fall
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

    cfgd = cell.config
    obs = {
        "chunk_seconds": kept, "chunk_steps": k,
        "tokens_per_step": batch * seq, "chips": cell.chips,
        "flops_per_token": flops.gpt_train_flops_per_token(cfgd, seq),
        "trace_dir": opts.trace_dir if traced else None,
        "traced_steps": traced["steps"] if traced else 0,
        "flash": {"batch": batch, "seq": seq,
                  "heads": cfgd["num_attention_heads"],
                  "head_dim": cfgd["head_dim"],
                  "layers": cfgd["num_hidden_layers"]},
        "reference": ref,
    }
    return Record(attempted=steps, failed=len(bad) * k,
                  end_to_end=end_to_end,
                  t_window_start=t_window, t_window_end=t_window_end,
                  obs=obs, why_incorrect=why, compared=compared)
