"""Benchmark: training-step throughput on the chip.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", "device"}.
vs_baseline = measured MFU / 0.40 (the BASELINE.md north-star MFU target;
the reference publishes no absolute numbers — BASELINE.md).

`BENCH_MODE` selects the BASELINE.md config:
    gpt (default) | resnet50 | bert | widedeep | eager

One process, on the chip or not at all: without a TPU, on an unknown device
kind (no published peak in cost_model.DEVICE_PEAKS) or on any error the
script exits non-zero and prints no result. This is not the round's
benchmark (ROADMAP A1 defines that); it is the old measurement entry point
with everything that could hide the device taken out.
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np


def _require_tpu() -> dict:
    """The device as JAX reports it, or exit: there is no CPU fallback."""
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(
            f"bench.py measures the chip and JAX found platform "
            f"{dev.platform!r} ({dev.device_kind}); nothing was measured")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}


def _peak_flops() -> float:
    import jax

    from paddle_tpu.cost_model import device_peaks

    return device_peaks(jax.devices()[0].device_kind)[0]


def measure_gpt() -> dict:
    import paddle_tpu as paddle
    import paddle_tpu.optimizer as opt
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.models import (
        GPTForCausalLM, GPTPretrainingCriterion, gpt_presets,
    )

    peak = _peak_flops()
    batch, seq, preset, dtype, steps = 8, 1024, "gpt-125m", "bfloat16", 10
    cfg = gpt_presets(preset, max_position_embeddings=seq, dtype=dtype)
    model = GPTForCausalLM(cfg, seed=0)
    crit = GPTPretrainingCriterion()
    optim = opt.AdamW(learning_rate=1e-4, parameters=model.parameters())
    step = TrainStep(model, lambda lg, lb: crit(lg, lb), optim)

    rs = np.random.RandomState(0)
    ids = paddle.to_tensor(rs.randint(0, cfg.vocab_size, (batch, seq)), dtype="int64")
    labels = paddle.to_tensor(rs.randint(0, cfg.vocab_size, (batch, seq)),
                              dtype="int64")

    def one_step():
        return step(inputs=(ids,), labels=(labels,))

    # warmup / compile (sync before starting the clock)
    for _ in range(3):
        loss = one_step()
        _ = float(loss)
    t0 = time.perf_counter()
    for _ in range(steps):
        loss = one_step()
    _ = float(loss)  # sync
    dt = time.perf_counter() - t0

    tokens_per_sec = batch * seq * steps / dt
    h, L, v = cfg.hidden_size, cfg.num_layers, cfg.vocab_size
    n_params = v * h + seq * h + L * 12 * h * h + 2 * h
    # fwd+bwd FLOPs/token: 6*N for matmuls + 6*L*s*h causal attention
    flops_per_token = 6 * n_params + 6 * L * seq * h
    mfu = tokens_per_sec * flops_per_token / peak

    print(f"# loss={float(loss):.4f} mfu={mfu:.3f} "
          f"step_ms={1000 * dt / steps:.1f}", file=sys.stderr)
    result = {
        "metric": f"gpt_{preset.split('-')[1]}_train_tokens_per_sec",
        "value": round(tokens_per_sec, 1),
        "unit": "tokens/s/chip",
        "vs_baseline": round(mfu / 0.40, 4),
    }
    result.update(_grad_comm_fields(model))
    result.update(_metrics_fields(model))
    result.update(_memory_fields(step))
    result.update(_kernel_fields(model, optim, cfg, batch, seq))
    return result


def _kernel_fields(model, optim, cfg, batch, seq) -> dict:
    """ISSUE 13 kernel-layer fields: `fused_update_ms` — wall time of one
    fused flat-bucket optimizer update over this model's buckets (the
    compiled inner loop the pallas dequant+update kernel owns on TPU;
    the jnp composition under the default flag-off dispatch) — and
    `flash_block`, the block shape flash-attention dispatch would run
    for this bench config (tuned/default/fallback source included, so
    the trajectory records WHICH tiles produced the number)."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.optimizer.fused import FusedFlatUpdater
    from paddle_tpu.ops.flash_attention import flash_block_choice

    fields = {}
    fused = FusedFlatUpdater(optim, model.parameters())
    lr = jnp.asarray(optim.get_lr(), jnp.float32)
    rs = np.random.RandomState(0)
    work = []  # [fn, p, g, slots] per bucket, compiled via _bucket_fn
    for b in fused.buckets:
        p = fused._flat_params(b)
        g = jnp.asarray(rs.randn(b.size), jnp.float32).astype(p.dtype)
        work.append([fused._bucket_fn(b), p, g,
                     fused._init_flat_slots(b)])

    def one_pass():
        outs = []
        for item in work:
            fn, p, g, slots = item
            new_p, new_s = fn(p, g, slots, lr)
            item[3] = new_s     # slots are donated in, fresh out
            outs.append(new_p)
        jax.block_until_ready(outs)

    one_pass()  # warmup / compile outside the clock
    times = []
    for _ in range(5):
        t0 = time.perf_counter()
        one_pass()
        times.append(time.perf_counter() - t0)
    fields["fused_update_ms"] = round(sorted(times)[2] * 1e3, 3)
    heads = getattr(cfg, "num_heads",
                    getattr(cfg, "num_attention_heads", None))
    if heads:
        d = cfg.hidden_size // heads
        fields["flash_block"] = flash_block_choice(
            (batch, seq, heads, d),
            dtype=getattr(cfg, "dtype", "float32"))
    return fields


def _memory_fields(step) -> dict:
    """Measured peak-HBM accounting for the bench step (ISSUE 6): the PJRT
    allocator's peak_bytes_in_use where the backend reports it, else XLA's
    memory_analysis of the exact compiled train step
    (TrainStep.memory_analysis — argument+temp+output-alias). Also records
    the live-tensor byte count so the eager working set is on the record."""
    from paddle_tpu.observability import memory as obs_mem

    fields = {}
    stats = obs_mem.device_memory_stats()
    analysis = step.memory_analysis()
    if stats and stats.get("peak_bytes_in_use"):
        fields["peak_hbm_bytes_measured"] = int(stats["peak_bytes_in_use"])
        fields["peak_hbm_source"] = "device_memory_stats"
    elif analysis is not None:
        fields["peak_hbm_bytes_measured"] = int(
            analysis["peak_hbm_bytes"])
        fields["peak_hbm_source"] = "xla_memory_analysis"
    if analysis is not None:
        fields["train_step_memory"] = {
            k: analysis[k] for k in ("argument_bytes", "temp_bytes",
                                     "output_bytes", "alias_bytes",
                                     "peak_hbm_bytes")}
    live = obs_mem.live_tensor_bytes()
    if live is not None:
        fields["live_tensor_bytes"] = int(live)
    return fields


def _metrics_fields(model) -> dict:
    """Observability snapshot for the bench record (ISSUE 3): trace-cache
    hit rate over this run's eager dispatches, plus a checkpoint
    save-duration histogram measured by one real atomic commit of the bench
    model's weights — so every BENCH_* file carries compile-cache and
    checkpoint telemetry next to the wall-clock number."""
    import shutil
    import tempfile

    from paddle_tpu.observability import get_registry
    from paddle_tpu.robustness.checkpoint import CheckpointManager

    d = tempfile.mkdtemp(prefix="bench_ckpt_")
    try:
        mgr = CheckpointManager(d, keep_last_n=1)
        mgr.save(model.state_dict(), 0)
        mgr.close()
    finally:
        shutil.rmtree(d, ignore_errors=True)
    snap = get_registry().snapshot()
    hits = snap.get("trace_cache_hits_total", 0)
    misses = snap.get("trace_cache_misses_total", 0)
    keep = {
        k: v for k, v in snap.items()
        if k.startswith(("trace_cache_", "eager_dispatch",
                         "grad_comm_", "checkpoint_save",
                         "collectives_total"))
    }
    keep["trace_cache_hit_rate"] = (
        round(hits / (hits + misses), 4) if (hits + misses) else None)
    return {"metrics": keep}


def _grad_comm_fields(model) -> dict:
    """DP gradient-traffic plan for this model under the default grad_comm
    settings: codec name + bytes/collectives per step, so the trajectory
    records the bucketing/quantization win next to the throughput number."""
    from paddle_tpu.distributed import grad_comm, overlap

    plan = grad_comm.comm_plan(model.parameters(),
                               grad_comm.GradCommConfig())
    fields = {
        "grad_codec": plan["codec"],
        "comm_bytes_per_step": plan["comm_bytes_per_step"],
        "comm_collectives_per_step": plan["collectives_per_step"],
        "per_param_comm_bytes": plan["per_param_comm_bytes"],
        # ISSUE 8: the COMPILED step's wire bytes under the default
        # codec — sync_async / TrainStep(grad_comm=) now apply the
        # codec in-trace, so the compiled path moves the plan's bytes
        # instead of raw fp32 (tools/grad_comm_bench.py's traced_*
        # columns measure the same number from a compiled shard_map
        # sync; tests pin their agreement)
        "comm_bytes_per_step_traced": plan["comm_bytes_per_step"],
    }
    # bucket-ready overlapped sync (ISSUE 5): measured on detached
    # fakes of this model's param shapes — how much of the comm work
    # hides under an emulated backward window vs the serial sync. The
    # small caps split this model into several buckets so the pipeline
    # has stages (the default 25MB cap is one bucket for small nets —
    # nothing to overlap); same config as tools/overlap_bench.py.
    rep = overlap.overlap_report(
        model.parameters(),
        grad_comm.GradCommConfig(comm_buffer_size=0.05,
                                 last_comm_buffer_size=0.01),
        world=2, compute_s=0.04)
    fields["overlap_efficiency"] = rep["overlap_efficiency"]
    fields["exposed_comm_ms"] = {
        "serial": rep["serial_exposed_comm_ms"],
        "overlapped": rep["overlapped_exposed_comm_ms"],
    }
    # ZeRO-3 parameter direction (ISSUE 9): exposed gather ms with the
    # layer-ahead prefetch + per-rank resident param bytes at rest,
    # measured on detached fakes of this model's param shapes
    # (distributed/sharding/stage3.py); tools/bench_gate.py gates both
    from paddle_tpu.distributed.sharding.stage3 import (
        zero3_gather_report,
    )

    z3 = zero3_gather_report(
        model.parameters(),
        grad_comm.GradCommConfig(comm_buffer_size=0.05,
                                 last_comm_buffer_size=0.01),
        world=2, compute_s=0.04)
    fields["zero3_exposed_gather_ms"] = z3["prefetch_exposed_gather_ms"]
    fields["zero3_param_bytes_per_rank"] = \
        z3["zero3_param_bytes_per_rank"]
    fields["zero3_gather"] = {
        "sync_exposed_ms": z3["sync_exposed_gather_ms"],
        "prefetched_exposed_ms": z3["prefetch_exposed_gather_ms"],
        "n_buckets": z3["n_buckets"],
        "param_bytes_full": z3["param_bytes_full"],
    }
    # elastic resharding + preemption (ISSUE 10): the N=4→M=2 shard
    # geometry transform on this model's shapes (host cost — the
    # transform IS host-side), bit-identity asserted in passing, and
    # one emergency preemption checkpoint commit of this model's
    # state — both gated by tools/bench_gate.py against the grace
    # window budget
    fields.update(_reshard_fields(model))
    return fields


def _reshard_fields(model) -> dict:
    """reshard_ms (N=4→M=2 zero3 transform on this model's shapes) and
    emergency_save_ms (one tagged preemption checkpoint commit)."""
    import shutil
    import tempfile

    from paddle_tpu.distributed import grad_comm
    from paddle_tpu.distributed.sharding.reshard import reshard_report
    from paddle_tpu.robustness.checkpoint import CheckpointManager
    from paddle_tpu.robustness.preemption import timed_emergency_save

    rep = reshard_report(
        model.parameters(),
        grad_comm.GradCommConfig(comm_buffer_size=0.05,
                                 last_comm_buffer_size=0.01),
        old_world=4, new_world=2)
    fields = {
        "reshard_ms": rep["reshard_ms"],
        "reshard": {k: rep[k] for k in
                    ("from_world", "to_world", "n_buckets",
                     "param_bytes_full", "bit_identical")},
    }
    d = tempfile.mkdtemp(prefix="bench_emergency_")
    try:
        mgr = CheckpointManager(d, keep_last_n=1)
        ms = timed_emergency_save(mgr, {"model": model.state_dict()}, 0)
        mgr.close()
    finally:
        shutil.rmtree(d, ignore_errors=True)
    fields["emergency_save_ms"] = round(ms, 3)
    return fields


def measure_resnet50() -> dict:
    """BASELINE.md config 2: ResNet-50 train step, samples/s/chip + MFU."""
    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    import paddle_tpu.optimizer as opt
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.vision.models import resnet50

    peak = _peak_flops()
    # batch 256: the TPU compiler ranks it well ahead of 64/128
    # (artifacts/resnet_aot_probe.json: est 2127 vs 1321 samples/s,
    # 9.5 GiB HBM — fits v5e's 16) and conv efficiency rises with
    # batch; round-5 measured 1758 at batch 64
    batch, img, steps = 256, 224, 8

    model = resnet50(num_classes=1000)
    optim = opt.Momentum(learning_rate=0.01, momentum=0.9,
                         parameters=model.parameters())
    step = TrainStep(model, lambda logits, y: F.cross_entropy(logits, y),
                     optim)

    rs = np.random.RandomState(0)
    x = paddle.to_tensor(rs.randn(batch, 3, img, img).astype("float32"))
    y = paddle.to_tensor(rs.randint(0, 1000, (batch,)), dtype="int64")

    from paddle_tpu.amp import auto_cast

    def one_step():
        with auto_cast(level="O2", dtype="bfloat16"):
            return step(inputs=(x,), labels=(y,))

    for _ in range(3):
        loss = one_step()
        _ = float(loss)
    t0 = time.perf_counter()
    for _ in range(steps):
        loss = one_step()
    _ = float(loss)
    dt = time.perf_counter() - t0

    samples_per_sec = batch * steps / dt
    # fwd FLOPs ~4.09 GF at 224^2 (conv-dominated -> scales with area);
    # train step ~= 3x fwd
    flops_per_sample = 3 * 4.09e9 * (img * img) / (224 * 224)
    mfu = samples_per_sec * flops_per_sample / peak
    print(f"# loss={float(loss):.4f} mfu={mfu:.3f} "
          f"step_ms={1000 * dt / steps:.1f}", file=sys.stderr)
    return {
        "metric": "resnet50_train_samples_per_sec",
        "value": round(samples_per_sec, 2),
        "unit": "samples/s/chip",
        "vs_baseline": round(mfu / 0.40, 4),
    }


def measure_bert() -> dict:
    """BASELINE.md config 3: BERT pretraining (MLM+NSP), samples/s/chip + MFU."""
    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    import paddle_tpu.optimizer as opt
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.models import BertForPretraining, bert_presets

    peak = _peak_flops()
    batch, seq, preset, steps = 16, 512, "bert-base", 10
    cfg = bert_presets(preset)
    model = BertForPretraining(cfg)
    optim = opt.AdamW(learning_rate=1e-4, parameters=model.parameters())

    # loss = MLM loss (model computes it over masked positions) + NSP CE
    step = TrainStep(
        model,
        lambda mlm_loss, nsp_logits, nsp_lbl:
            mlm_loss + F.cross_entropy(nsp_logits, nsp_lbl),
        optim)

    rs = np.random.RandomState(0)
    ids = rs.randint(0, cfg.vocab_size, (batch, seq))
    masked = rs.rand(batch, seq) < 0.15
    mlm = np.where(masked, ids, -1)
    ids_t = paddle.to_tensor(ids, dtype="int64")
    mlm_t = paddle.to_tensor(mlm, dtype="int64")
    nsp_t = paddle.to_tensor(rs.randint(0, 2, (batch,)), dtype="int64")

    from paddle_tpu.amp import auto_cast

    def one_step():
        with auto_cast(level="O2", dtype="bfloat16"):
            return step(inputs=(ids_t, None, None, None, mlm_t),
                        labels=(nsp_t,))

    for _ in range(3):
        loss = one_step()
        _ = float(loss)
    t0 = time.perf_counter()
    for _ in range(steps):
        loss = one_step()
    _ = float(loss)
    dt = time.perf_counter() - t0

    samples_per_sec = batch * steps / dt
    h, L, v = cfg.hidden_size, cfg.num_layers, cfg.vocab_size
    n_params = v * h + seq * h + 2 * h + L * 12 * h * h + 2 * h * h
    # bidirectional attention: 12*L*s*h per token fwd+bwd (no causal halving)
    flops_per_token = 6 * n_params + 12 * L * seq * h
    mfu = samples_per_sec * seq * flops_per_token / peak
    print(f"# loss={float(loss):.4f} mfu={mfu:.3f} "
          f"step_ms={1000 * dt / steps:.1f}", file=sys.stderr)
    return {
        "metric": "bert_train_samples_per_sec",
        "value": round(samples_per_sec, 2),
        "unit": "samples/s/chip",
        "vs_baseline": round(mfu / 0.40, 4),
    }


def measure_widedeep() -> dict:
    """BASELINE.md config 5: Wide&Deep over the PS, examples/s + AUC.

    vs_baseline here is the held-out AUC (the BASELINE row asks for AUC
    parity, not an MFU); the throughput is the headline value.
    """
    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    from paddle_tpu.distributed.ps import (
        LocalPs, TheOnePSRuntime, distributed_lookup_table,
    )
    from paddle_tpu.distributed.ps.communicator import AsyncCommunicator
    from paddle_tpu.metric import Auc

    batch, slots, steps, vocab = 512, 16, 60, 10000

    runtime = TheOnePSRuntime()
    ps = LocalPs()
    ps.create_table(0, dim=8, init_range=0.01, lr=0.1, optimizer="adagrad")
    runtime.client = ps
    runtime.communicator = AsyncCommunicator(ps)
    runtime.communicator.start()

    deep = paddle.nn.Sequential(
        paddle.nn.Linear(8 * slots, 64), paddle.nn.ReLU(),
        paddle.nn.Linear(64, 1))
    optim = paddle.optimizer.Adam(learning_rate=1e-3,
                                  parameters=deep.parameters())
    rs = np.random.RandomState(0)
    true_w = rs.randn(vocab)

    def make_batch(n):
        ids = rs.randint(0, vocab, (n, slots))
        labels = (true_w[ids].sum(1) > 0).astype("float32")
        return ids, labels

    # the heter pass path (PSGPUTrainer analog): the pass working set
    # lives on device, ONE compiled program per step (gather + dense
    # fwd/bwd + Adam + grad accumulation), merged PS push per pass —
    # vs the eager per-step lookup/push path this avoids the per-batch
    # host<->device row round-trip
    from paddle_tpu.distributed.ps.heter_cache import DevicePassCache
    from paddle_tpu.distributed.ps.heter_trainer import CompiledPassStep

    cache = DevicePassCache(ps, 0, lr=0.1)
    pass_step = CompiledPassStep(
        cache, deep, optim,
        lambda out, labels: F.binary_cross_entropy_with_logits(
            out[:, 0], labels),
        table_optimizer="adagrad", table_lr=0.1)
    steps_per_pass = 10

    # fixed slab size: shape-stable across passes, ONE compiled program
    pad_rows = vocab

    def run_pass(pass_batches):
        cache.begin_pass(
            np.concatenate([b[0].reshape(-1) for b in pass_batches]),
            pad_to=pad_rows)
        for b in pass_batches:
            loss = pass_step(cache, b)
        cache.end_pass(assign=True)  # device optimizer owns the update
        return loss

    loss = run_pass([make_batch(batch) for _ in range(2)])  # warm compile
    batches = [make_batch(batch) for _ in range(steps)]  # keep data-gen
    t0 = time.perf_counter()                             # out of the timer
    for i in range(0, steps, steps_per_pass):
        loss = run_pass(batches[i:i + steps_per_pass])
    _ = float(loss)
    dt = time.perf_counter() - t0
    examples_per_sec = batch * steps / dt

    # held-out AUC
    auc = Auc()
    ids, labels = make_batch(4096)
    with paddle.no_grad():
        rows = distributed_lookup_table(
            paddle.to_tensor(ids, dtype="int64"), table_id=0, lr=0.0)
        logit = deep(rows.reshape([4096, -1]))[:, 0]
        prob = F.sigmoid(logit).numpy()
    preds = np.stack([1.0 - prob, prob], axis=1)
    auc.update(preds, labels[:, None])
    auc_val = float(auc.accumulate())
    runtime.communicator.stop()

    print(f"# loss={float(loss):.4f} auc={auc_val:.4f} "
          f"table_rows={ps.table_size(0)}", file=sys.stderr)
    return {
        "metric": "wide_deep_ps_examples_per_sec",
        "value": round(examples_per_sec, 1),
        "unit": "examples/s",
        "vs_baseline": round(auc_val, 4),
    }


def measure_eager() -> dict:
    """Eager per-op dispatch latency (op-cache hit path) on the real chip.

    SURVEY §7 hard-part 1: eager op dispatch must stay usable on TPU.
    vs_baseline = 100us-target / measured (>=1 means each cached eager op
    dispatches in under 100us).
    """
    import paddle_tpu as paddle

    x = paddle.ones([256, 256])
    n = 200

    def chain(t, k):
        for _ in range(k):
            t = t * 1.0001 + 0.1
        return t

    _ = float(chain(x, 20).sum())  # warm the op-cache
    t0 = time.perf_counter()
    y = chain(x, n)
    _ = float(y.sum())
    dt = time.perf_counter() - t0
    us_per_op = dt / (2 * n) * 1e6  # each chain iteration is 2 ops (mul, add)

    # grad-enabled loop: dispatch + tape-node build + cached backward —
    # the eager TRAINING path (SURVEY §7 hard-part 1's real shape). Tiny
    # tensors so HOST overhead (the thing being measured) dominates compute.
    xs = paddle.ones([16, 16])
    w = paddle.ones([16, 16])
    w.stop_gradient = False
    k = 20

    def train_iter():
        t = xs
        for _ in range(k):
            t = t @ w
            t = t * 0.5
        loss = t.sum()
        loss.backward()
        g = w.grad
        w.clear_grad()
        return g

    _ = train_iter()  # warm fwd+bwd caches
    iters = max(1, n // (2 * k))
    t0 = time.perf_counter()
    for _ in range(iters):
        g = train_iter()
    _ = float(g.sum()._value if hasattr(g.sum(), "_value") else g.sum())
    dt_g = time.perf_counter() - t0
    # per iteration: 2k fwd dispatches + one tape walk of 2k+1 bwd nodes
    us_per_train_op = dt_g / (iters * 4 * k) * 1e6
    print(f"# eager {us_per_op:.1f} us/op (no-grad chain), "
          f"{us_per_train_op:.1f} us/op (fwd+bwd tape loop)",
          file=sys.stderr)
    return {
        "metric": "eager_op_dispatch_us",
        "value": round(us_per_op, 2),
        "unit": "us/op",
        "vs_baseline": round(100.0 / us_per_op, 4),
        "train_us_per_op": round(us_per_train_op, 2),
    }


MODES = {"gpt": measure_gpt, "resnet50": measure_resnet50,
         "bert": measure_bert, "widedeep": measure_widedeep,
         "eager": measure_eager}


def main():
    mode = os.environ.get("BENCH_MODE", "gpt")
    if mode not in MODES:
        raise SystemExit(f"unknown BENCH_MODE={mode!r}; one of {list(MODES)}")
    device = _require_tpu()
    from paddle_tpu.jit.artifact_cache import use_compile_cache

    use_compile_cache()
    result = MODES[mode]()
    result["device"] = device
    result["device_kind"] = device["kind"]   # tools/bench_gate.py's class key
    print(json.dumps(result))


if __name__ == "__main__":
    main()
