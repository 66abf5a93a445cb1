"""TPU-AOT estimates for the BASELINE throughput configs (2, 3, 4).

Every BASELINE.md row that asks for samples/sec+MFU gets a TPU-backend
artifact without a chip: the REAL TrainStep for each
config is AOT-compiled with the TPU compiler (jax.experimental
.topologies) at the bench shapes, recording per-device memory and a
labeled roofline step-time bound from the compiler's own cost counters.

Measurements come from the benchmark's cells on the chip
(BENCHMARK.json); these rows exist so every config has TPU-compiler
evidence, and so
regressions that only show up in TPU lowering (layout, fusion, kernel
choice) are visible without hardware.

Single-chip configs compile as pure data-parallel x8 over a v5e:2x4
topology (TrainStep needs a >1-device mesh to target the topology); the
per-chip program matches the single-chip bench shape plus a grad
all-reduce, so the bound is slightly conservative.

Usage: python tools/baseline_aot_estimates.py
Writes artifacts/baseline_aot_estimates.json.
"""
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from paddle_tpu.cost_model import device_peaks

V5E_PEAK_BF16 = device_peaks("TPU v5 lite")[0]


def main():
    import jax

    jax.config.update("jax_platforms", "cpu")

    import numpy as np
    from jax.sharding import PartitionSpec as P

    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    import paddle_tpu.optimizer as opt
    from paddle_tpu.amp import auto_cast
    from paddle_tpu.distributed import mesh as mesh_mod
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.jit.aot import (
        aot_compile_step, estimate_step_seconds, topology_mesh,
    )

    rs = np.random.RandomState(0)
    results = {}

    def run(name, build, per_chip_items, unit):
        """build() -> (step, inputs, labels, amp, flops_per_item) under no
        mesh; compiled DPx8 against the topology."""
        mesh_mod.set_mesh(None)
        t0 = time.time()
        try:
            step, inputs, labels, amp, flops_per_item = build()
            mesh_mod.set_mesh(topology_mesh("v5e:2x4", {"data": 8}))
            with auto_cast(enable=amp, level="O2", dtype="bfloat16"):
                cost = aot_compile_step(step, inputs, labels,
                                        want_cost=True)
        except Exception as e:
            results[name] = {"error": f"{type(e).__name__}: {str(e)[:300]}"}
            print(f"  {name}: FAILED {results[name]['error'][:100]}")
            return
        finally:
            mesh_mod.set_mesh(None)
        row = {"per_chip_batch_items": per_chip_items, "unit": unit,
               "peak_hbm_bytes": cost.get("peak_hbm_bytes"),
               "compile_seconds": round(time.time() - t0, 1),
               "note": "roofline = LOWER bound on step time; DPx8 proxy"}
        sec = estimate_step_seconds(cost)
        if sec:
            row["est_step_seconds"] = round(sec["seconds"], 6)
            row["est_signal"] = sec["signal"]
            row["est_items_per_sec_chip"] = round(
                per_chip_items / sec["seconds"], 1)
            if flops_per_item and sec["seconds"] > 0:
                row["est_mfu"] = round(
                    flops_per_item * per_chip_items / sec["seconds"]
                    / V5E_PEAK_BF16, 4)
        results[name] = row
        peak = (f"{row['peak_hbm_bytes']/2**30:.2f} GiB"
                if row["peak_hbm_bytes"] is not None else "?")
        print(f"  {name}: peak {peak}, "
              + (f"est {row['est_items_per_sec_chip']:.0f} {unit} "
                 f"({row['est_signal']})" if sec else "no estimate")
              + f" [{row['compile_seconds']:.0f}s]")

    # ---- config 2: ResNet-50, b=64 img=224, bf16 O2 (bench shapes) ----
    def build_resnet():
        from paddle_tpu.vision.models import resnet50

        model = resnet50(num_classes=1000)
        optim = opt.Momentum(learning_rate=0.01, momentum=0.9,
                             parameters=model.parameters())
        step = TrainStep(model, lambda lg, y: F.cross_entropy(lg, y),
                         optim, batch_spec=P("data"))
        b = 64 * 8
        x = paddle.to_tensor(rs.randn(b, 3, 224, 224).astype("float32"))
        y = paddle.to_tensor(rs.randint(0, 1000, (b,)), dtype="int64")
        return step, (x,), (y,), True, 3 * 4.09e9  # ~3x fwd FLOPs/sample

    run("resnet50_b64_224_bf16", build_resnet, 64, "samples/s/chip")

    # ---- config 3: BERT-base MLM+NSP, b=16 s=512, bf16 O2 ----
    def build_bert():
        from paddle_tpu.models import BertForPretraining, bert_presets

        cfg = bert_presets("bert-base")
        model = BertForPretraining(cfg)
        optim = opt.AdamW(learning_rate=1e-4,
                          parameters=model.parameters())
        step = TrainStep(
            model,
            lambda mlm_loss, nsp_logits, nsp_lbl:
                mlm_loss + F.cross_entropy(nsp_logits, nsp_lbl),
            optim, batch_spec=P("data"))
        b, s = 16 * 8, 512
        ids = rs.randint(0, cfg.vocab_size, (b, s))
        mlm = np.where(rs.rand(b, s) < 0.15, ids, -1)
        # same formula as bench.measure_bert: 6*params + bidirectional attn
        h, L, v = cfg.hidden_size, cfg.num_layers, cfg.vocab_size
        n_params = v * h + s * h + 2 * h + L * 12 * h * h + 2 * h * h
        flops_per_sample = (6 * n_params + 12 * L * s * h) * s
        return (step,
                (paddle.to_tensor(ids, dtype="int64"), None, None, None,
                 paddle.to_tensor(mlm, dtype="int64")),
                (paddle.to_tensor(rs.randint(0, 2, (b,)), dtype="int64"),),
                True, flops_per_sample)

    run("bert_base_b16_512_bf16", build_bert, 16, "samples/s/chip")

    # config 4 (GPT-1.3B) is covered by tools/gpt13b_aot_tpu.py and the
    # planner sweep; config 1 (MNIST) is a correctness milestone and
    # config 5 (Wide&Deep PS) is host-side — no AOT row applies.

    path = os.path.join(REPO, "artifacts", "baseline_aot_estimates.json")
    with open(path, "w") as f:
        json.dump(results, f, indent=1)
    print(f"wrote {path}")


if __name__ == "__main__":
    main()
