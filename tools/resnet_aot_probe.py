"""ResNet-50 batch-size sweep through the REAL TPU compiler (AOT).

The measured round-5 number (1758 samples/s at batch 64, MFU 0.109) is
far under the 0.40 target; the XLA-TPU compiler is reachable without a chip
via jax.experimental.topologies, so rank
candidate per-chip batch sizes by the compiler's own step-time estimate
and pick the bench config from evidence instead of guessing. Writes
artifacts/resnet_aot_probe.json (est_* fields: compiler/roofline
numbers, not measurements).
"""
from __future__ import annotations

import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def probe(batches=(64, 128, 256)):
    import jax

    jax.config.update("jax_platforms", "cpu")
    import numpy as np
    from jax.sharding import PartitionSpec as P

    import paddle_tpu.nn.functional as F
    import paddle_tpu.optimizer as opt
    from paddle_tpu.amp import auto_cast
    from paddle_tpu.distributed import mesh as mesh_mod
    from paddle_tpu.framework import target as target_mod
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.jit.aot import (
        aot_compile_step, estimate_step_seconds, topology_mesh,
    )
    from paddle_tpu.vision.models import resnet50

    model = resnet50(num_classes=1000)
    optim = opt.Momentum(learning_rate=0.01, momentum=0.9,
                         parameters=model.parameters())

    mesh = topology_mesh("v5e:2x4", {"data": 8})
    results = []
    prev = mesh_mod.get_mesh()
    try:
        with target_mod.force_target("tpu"):
            mesh_mod.set_mesh(mesh)
            for batch in batches:
                # np.zeros is calloc-backed: the arrays only template
                # shapes/dtypes for the abstract lowering
                x = np.zeros((batch * 8, 3, 224, 224), np.float32)
                y = np.zeros((batch * 8,), np.int64)
                step = TrainStep(
                    model, lambda lo, yy: F.cross_entropy(lo, yy), optim,
                    batch_spec=P("data"))
                with auto_cast(enable=True, level="O2", dtype="bfloat16"):
                    r = aot_compile_step(step, (x,), (y,), want_cost=True)
                est = estimate_step_seconds(r)
                rec = {
                    "per_chip_batch": batch,
                    "compile_seconds": r.get("compile_seconds"),
                    "est_step_seconds": est and round(est["seconds"], 6),
                    "est_signal": est and est["signal"],
                    "est_samples_per_sec_chip": est and round(
                        batch / est["seconds"], 1),
                    "peak_hbm_bytes": r.get("peak_hbm_bytes"),
                }
                results.append(rec)
                print(rec, flush=True)
    finally:
        mesh_mod.set_mesh(prev)
    return results


def main():
    out = {"config": "resnet50 train step, bf16 O2, DPx8 v5e proxy",
           "note": "est_* are compiler/roofline numbers, not measurements",
           "results": probe()}
    path = os.path.join(REPO, "artifacts", "resnet_aot_probe.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    print("saved", path)


if __name__ == "__main__":
    main()
