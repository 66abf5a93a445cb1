"""Train GPT with the fused TrainStep — single chip or hybrid mesh.

    python examples/train_gpt.py                 # single device
    python examples/train_gpt.py --dp 2 --tp 2   # 4-device mesh (set
        XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu
        to try it without TPUs)
"""
import argparse

import numpy as np

import paddle_tpu as paddle
import paddle_tpu.optimizer as opt
from paddle_tpu.distributed import mesh as mesh_mod
from paddle_tpu.jit import TrainStep
from paddle_tpu.jit.artifact_cache import use_compile_cache
from paddle_tpu.models import GPTForCausalLM, GPTPretrainingCriterion, gpt_presets


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", default="gpt-test")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--dp", type=int, default=1)
    ap.add_argument("--tp", type=int, default=1)
    ap.add_argument("--pp", type=int, default=1)
    args = ap.parse_args()
    use_compile_cache()

    if args.dp * args.tp * args.pp > 1:
        import jax

        mesh_mod.set_mesh(mesh_mod.build_mesh(
            {"data": args.dp, "model": args.tp, "pipe": args.pp},
            devices=jax.devices()[: args.dp * args.tp * args.pp]))

    cfg = gpt_presets(args.preset, max_position_embeddings=args.seq,
                      mode="scan" if args.pp > 1 else "loop")
    model = GPTForCausalLM(cfg, seed=0)
    crit = GPTPretrainingCriterion()
    optim = opt.AdamW(learning_rate=3e-4, parameters=model.parameters())
    step = TrainStep(model, lambda lg, lb: crit(lg, lb), optim)

    rs = np.random.RandomState(0)
    for i in range(args.steps):
        ids = paddle.to_tensor(
            rs.randint(0, cfg.vocab_size, (args.batch, args.seq)), dtype="int64")
        loss = step(inputs=(ids,), labels=(ids,))
        if i % 5 == 0 or i == args.steps - 1:
            print(f"step {i}: loss {float(loss):.4f}")


if __name__ == "__main__":
    main()
