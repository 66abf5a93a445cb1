"""Extend the 1.3B mesh sweep with PIPELINE-parallel candidates.

tools/mesh_planner_13b.py sweeps (data, sharding, model) through the
abstract GSPMD estimator; the 1F1B pipeline path needs the real TrainStep
(gpt_1f1b_train_step + jit.aot.aot_compile_step), which materializes real
params/slots — fine on this host's RAM, heavier per candidate. This tool
AOT-compiles a small set of pipe-bearing candidates for GPT-1.3B on
v5e:8x8 and appends them to artifacts/mesh_plan_13b.json under
"ranked_pipe", so the planner artifact answers: does 1F1B pipelining beat
ZeRO+TP for BASELINE config 4?

All numbers are compiler estimates / roofline bounds, labeled est_*.

Usage: python tools/mesh_planner_13b_pipe.py [--candidates N]
"""
import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from paddle_tpu.cost_model import device_peaks  # noqa: E402

V5E_PEAK_BF16_FLOPS = device_peaks("TPU v5 lite")[0]

HBM_BUDGET = 16 * 2**30
GLOBAL_BATCH, SEQ, N_CHIPS = 64, 2048, 64

CANDIDATES = [
    {"data": 4, "sharding": 2, "pipe": 4, "model": 2},
    {"data": 2, "pipe": 8, "model": 4},
    {"data": 8, "pipe": 4, "model": 2},
]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--candidates", type=int, default=len(CANDIDATES))
    args = ap.parse_args()

    import jax

    jax.config.update("jax_platforms", "cpu")

    import numpy as np
    from jax.sharding import PartitionSpec as P

    import paddle_tpu as paddle
    import paddle_tpu.optimizer as opt
    from paddle_tpu.distributed import mesh as mesh_mod
    from paddle_tpu.distributed.sharding import group_sharded_parallel
    from paddle_tpu.jit.aot import (
        aot_compile_step, estimate_step_seconds, topology_mesh,
    )
    from paddle_tpu.models import (
        GPTForCausalLM, gpt_presets, gpt_1f1b_train_step,
    )

    rs = np.random.RandomState(0)
    rows = []
    for shape_map in CANDIDATES[:args.candidates]:
        label = "x".join(f"{a}{d}" for a, d in sorted(shape_map.items()))
        t0 = time.time()
        model = optim = step = None  # finally must survive early failures
        try:
            mesh_mod.set_mesh(None)
            # microbatch size (GLOBAL_BATCH / M) must divide by the batch
            # axes' degree, and M >= P for the schedule to fill; prefer
            # M = 4P (quarter-bubble) when the batch allows it
            bdeg = shape_map.get("data", 1) * shape_map.get("sharding", 1)
            pipe = shape_map.get("pipe", 1)
            mb = min(4 * pipe, GLOBAL_BATCH // bdeg)
            if mb < pipe:
                raise ValueError(
                    f"global batch {GLOBAL_BATCH} too small for pipe "
                    f"{pipe} x batch-degree {bdeg}")
            cfg = gpt_presets(
                "gpt-1.3b", mode="scan", dtype="bfloat16", recompute=True,
                use_flash_attention=True, pp_microbatches=mb)
            model = GPTForCausalLM(cfg, seed=0)
            optim = opt.AdamW(learning_rate=1e-4,
                              parameters=model.parameters())
            model, optim, _ = group_sharded_parallel(model, optim, "os_g")
            ids = paddle.to_tensor(
                rs.randint(0, cfg.vocab_size, (GLOBAL_BATCH, SEQ)),
                dtype="int64")
            lbl = paddle.to_tensor(
                rs.randint(0, cfg.vocab_size, (GLOBAL_BATCH, SEQ)),
                dtype="int64")
            mesh_mod.set_mesh(topology_mesh("v5e:8x8", shape_map))
            step = gpt_1f1b_train_step(
                model, optim, batch_spec=P(("data", "sharding")))
            cost = aot_compile_step(step, (ids,), (lbl,), want_cost=True)
        except Exception as e:
            rows.append({"mesh": shape_map,
                         "error": f"{type(e).__name__}: {str(e)[:300]}"})
            print(f"  {label}: FAILED {type(e).__name__}: {str(e)[:120]} "
                  f"[{time.time()-t0:.0f}s]")
            continue
        finally:
            mesh_mod.set_mesh(None)
            # release ~13 GB of host arrays per candidate — including the
            # TrainStep closure, which holds model+optimizer alive
            model = optim = step = None

        row = {"mesh": shape_map, **cost,
               "wall_seconds": round(time.time() - t0, 1),
               "schedule": "1F1B", "pp_microbatches": mb}
        if row.get("peak_hbm_bytes") is not None:
            row["fits_v5e_16gb"] = row["peak_hbm_bytes"] <= HBM_BUDGET
        sec = estimate_step_seconds(cost)
        if sec:
            row["est_step_seconds"] = round(sec["seconds"], 6)
            row["est_signal"] = sec["signal"]
            row["est_tokens_per_sec_chip"] = round(
                GLOBAL_BATCH * SEQ / N_CHIPS / sec["seconds"], 1)
            if cost.get("flops"):
                # same headline metric as the GSPMD ranked list
                row["est_mfu"] = round(
                    cost["flops"] / sec["seconds"] / V5E_PEAK_BF16_FLOPS, 4)
        peak = row.get("peak_hbm_bytes")
        print(f"  {label}: peak "
              + (f"{peak/2**30:.2f} GiB" if peak else "?")
              + (f", est step {row['est_step_seconds']*1e3:.1f} ms "
                 f"({row['est_signal']}), est "
                 f"{row['est_tokens_per_sec_chip']:.0f} tok/s/chip"
                 if sec else "")
              + f" [{row['wall_seconds']:.0f}s]")
        rows.append(row)

    path = os.path.join(REPO, "artifacts", "mesh_plan_13b.json")
    try:
        out = json.load(open(path))
    except (FileNotFoundError, json.JSONDecodeError):
        out = {}
    # same ranking contract as the sibling GSPMD sweep: errors last,
    # over-budget plans demoted — ranked_pipe[0] must actually FIT
    out["ranked_pipe"] = sorted(
        rows, key=lambda r: (
            2 if r.get("error") else 0 if r.get("fits_v5e_16gb") else 1,
            r.get("est_step_seconds") or float("inf")))
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    print(f"updated {path} (ranked_pipe: {len(rows)} rows)")


if __name__ == "__main__":
    main()
