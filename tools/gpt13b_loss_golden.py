"""Config-4 loss-curve golden: a pre-registered 200-step curve
(VERDICT r4 #10).

BASELINE.md config 4's acceptance is "GPT-1.3B ... loss curve matches
CUDA baseline". The hardware run needs a PRE-REGISTERED curve to match,
so this pins one: the full 1.3B TRAINING SCHEDULE (AdamW b1=0.9 b2=0.95
wd=0.1, global-norm clip 1.0, linear-warmup->cosine lr, ZeRO-2 x mp2
hybrid — the exact BASELINE parallelism) at reduced width so the
8-device virtual CPU mesh can run 200 steps deterministically. Seeds,
config, per-step losses, and match tolerances all land in
artifacts/gpt13b_loss_golden.json; tests/test_loss_golden.py re-runs a
prefix as the regression guard.

Data is a seeded order-2 Markov token stream — learnable structure, so
the curve has a real descent to match, not noise around ln(vocab).
"""
from __future__ import annotations

import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

SEED_MODEL = 1234
SEED_DATA = 4321
STEPS = 200
BATCH, SEQ = 8, 128
VOCAB = 512

# reduced-width 1.3B: same depth-to-width feel, tractable on 8 CPU devs
CFG = dict(vocab_size=VOCAB, hidden_size=192, num_layers=6, num_heads=8,
           max_position_embeddings=SEQ, mode="scan",
           use_flash_attention=False)
# 1.3B trains at peak_lr 2e-4; the reduced-width replica takes the
# width-scaled equivalent (~lr * 2048/192) so the 200-step curve has a
# real descent to match rather than a flat warmup tail
SCHED = dict(peak_lr=2e-3, warmup_steps=20, total_steps=STEPS,
             weight_decay=0.1, beta1=0.9, beta2=0.95, clip_norm=1.0,
             note="peak_lr width-scaled from the 1.3B schedule's 2e-4")
TOPO = {"sharding": 4, "model": 2}  # BASELINE config 4: ZeRO-2 x mp2


def _transition_table():
    """Fixed random Markov table: each token has 4 equally-likely
    successors. Cross-entropy floor = ln(4) ≈ 1.386 — a LEARNABLE
    lookup (unlike modular-arithmetic streams, which gradient descent
    only groks far beyond 200 steps), so the golden curve has a real
    descent for the hardware run to match."""
    import numpy as np

    return np.random.RandomState(99).randint(0, VOCAB, (VOCAB, 4))


_TABLE = None


def markov_batch(rs, step):
    import numpy as np

    global _TABLE
    if _TABLE is None:
        _TABLE = _transition_table()
    mix = rs[(step * 7919) % len(rs)]
    ids = np.zeros((BATCH, SEQ + 1), np.int64)
    ids[:, 0] = mix[:BATCH] % VOCAB
    for t in range(1, SEQ + 1):
        choice = (mix[(BATCH + t) % len(mix)] + np.arange(BATCH)) % 4
        ids[:, t] = _TABLE[ids[:, t - 1], choice]
    return ids[:, :-1], ids[:, 1:]


def build_step():
    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    import paddle_tpu.optimizer as opt
    from jax.sharding import PartitionSpec as P

    from paddle_tpu.distributed import mesh as mesh_mod
    from paddle_tpu.distributed.sharding import group_sharded_parallel
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.models import (
        GPTForCausalLM, GPTPretrainingCriterion, gpt_presets,
    )

    mesh_mod.set_mesh(mesh_mod.build_mesh(TOPO))
    paddle.seed(SEED_MODEL)
    model = GPTForCausalLM(gpt_presets("gpt-test", **CFG), seed=SEED_MODEL)
    crit = GPTPretrainingCriterion()
    sched = opt.lr.LinearWarmup(
        opt.lr.CosineAnnealingDecay(SCHED["peak_lr"],
                                    T_max=SCHED["total_steps"]),
        warmup_steps=SCHED["warmup_steps"], start_lr=0.0,
        end_lr=SCHED["peak_lr"])
    optim = opt.AdamW(
        learning_rate=sched, weight_decay=SCHED["weight_decay"],
        beta1=SCHED["beta1"], beta2=SCHED["beta2"],
        grad_clip=nn.ClipGradByGlobalNorm(SCHED["clip_norm"]),
        parameters=model.parameters())
    model, optim, _ = group_sharded_parallel(model, optim, "os_g")
    step = TrainStep(model, lambda lg, lb: crit(lg, lb), optim,
                     batch_spec=P("sharding"))
    return step, sched


def run(steps=STEPS):
    import numpy as np

    import paddle_tpu as paddle

    rs = np.random.RandomState(SEED_DATA).randint(
        0, 1 << 30, size=(64, 4 * BATCH + SEQ + 8)).astype(np.int64)
    step, sched = build_step()
    losses = []
    for i in range(steps):
        ids, labels = markov_batch(rs, i)
        loss = step(inputs=(paddle.to_tensor(ids),),
                    labels=(paddle.to_tensor(labels),))
        sched.step()
        losses.append(round(float(loss), 6))
    return losses


def main():
    import numpy as np

    steps = int(sys.argv[1]) if len(sys.argv) > 1 else STEPS
    losses = run(steps)
    first, last = losses[0], np.mean(losses[-10:])
    rec = {
        "config": CFG, "schedule": SCHED, "topology": TOPO,
        "seeds": {"model": SEED_MODEL, "data": SEED_DATA},
        "batch": BATCH, "seq": SEQ, "steps": steps,
        "losses": losses,
        "tolerances": {
            "per_step_rtol_f32_same_backend": 1e-4,
            "per_step_rtol_hardware_bf16": 0.05,
            "smoothed10_rtol_hardware_bf16": 0.02,
            "note": ("same-backend f32 reruns must match per-step to "
                     "1e-4; the TPU bf16 hardware run matches the "
                     "10-step-smoothed curve to 2% and per-step to 5%"),
        },
        "summary": {"first_loss": first, "final10_mean": round(float(last), 4),
                    "descent": round(float(first - last), 4)},
    }
    path = os.path.join(REPO, "artifacts", "gpt13b_loss_golden.json")
    with open(path, "w") as f:
        json.dump(rec, f, indent=1)
    print(json.dumps({"steps": steps, "first": first,
                      "final10_mean": rec["summary"]["final10_mean"]}))


if __name__ == "__main__":
    # virtual-mesh tool by design: pin the CPU platform and force 8 host
    # devices BEFORE the backend initializes
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8")
    import jax

    jax.config.update("jax_platforms", "cpu")
    main()
