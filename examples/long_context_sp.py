"""Long-context training via sequence parallelism — ring vs Ulysses.

The sequence dim is sharded over the 'sep' mesh axis, so per-device
activation memory scales with s/P and the O(s^2) score matrix never lands
on one chip (ring: online-softmax k/v rotation; ulysses: all_to_all
head/seq swap). Both are net-new capability vs the reference (SURVEY §5).

    python examples/long_context_sp.py --scheme ring    --sep 4
    python examples/long_context_sp.py --scheme ulysses --sep 4

Try without TPUs:
    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
        python examples/long_context_sp.py --scheme ulysses --sep 4 --dp 2
"""
import argparse

import numpy as np

import paddle_tpu as paddle
import paddle_tpu.optimizer as opt
from jax.sharding import PartitionSpec as P
from paddle_tpu.distributed import mesh as mesh_mod
from paddle_tpu.jit import TrainStep
from paddle_tpu.models import (
    GPTForCausalLM, GPTPretrainingCriterion, gpt_presets,
)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--scheme", choices=("ring", "ulysses"), default="ring")
    ap.add_argument("--sep", type=int, default=4)
    ap.add_argument("--dp", type=int, default=1)
    ap.add_argument("--seq", type=int, default=512)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--steps", type=int, default=10)
    args = ap.parse_args()

    import jax

    topo = {"data": args.dp, "sep": args.sep}
    mesh_mod.set_mesh(mesh_mod.build_mesh(topo))
    print(f"mesh: {topo} over {len(jax.devices())} devices")

    cfg = gpt_presets(
        "gpt-test",
        max_position_embeddings=args.seq,
        use_ring_attention=args.scheme == "ring",
        use_ulysses_attention=args.scheme == "ulysses",
    )
    model = GPTForCausalLM(cfg, seed=0)
    crit = GPTPretrainingCriterion()
    optim = opt.AdamW(learning_rate=3e-4, parameters=model.parameters())
    step = TrainStep(model, lambda lg, lb: crit(lg, lb), optim,
                     batch_spec=P(("data",)))

    rs = np.random.RandomState(0)
    ids = paddle.to_tensor(
        rs.randint(0, cfg.vocab_size, (args.batch, args.seq)), dtype="int64")
    labels = paddle.to_tensor(
        rs.randint(0, cfg.vocab_size, (args.batch, args.seq)), dtype="int64")

    for i in range(args.steps):
        loss = step(inputs=(ids,), labels=(labels,))
        if i % 2 == 0 or i == args.steps - 1:
            print(f"step {i:3d}  scheme={args.scheme}  "
                  f"loss {float(loss):.4f}")
    print("done")


if __name__ == "__main__":
    main()
