"""Heter-PS pass training: the compiled fast path for CTR models.

The eager PS path (examples/wide_deep_ps.py) dispatches one host op per
layer per batch and round-trips embedding rows host<->device on every
lookup. The heter pass path (reference PSGPUTrainer, ps_gpu_wrapper.cc)
pulls each pass's working set into device memory once, trains with ONE
compiled XLA program per step (gather + dense fwd/bwd + Adam + device
adagrad on the embedding slab), and syncs values back at pass end.

    python examples/heter_pass_training.py
"""
import numpy as np

import paddle_tpu as paddle
import paddle_tpu.nn.functional as F
from paddle_tpu.distributed.ps import LocalPs
from paddle_tpu.distributed.ps.heter_cache import DevicePassCache
from paddle_tpu.distributed.ps.heter_trainer import CompiledPassStep

VOCAB, SLOTS, DIM, BATCH = 1000, 6, 8, 64


def main():
    ps = LocalPs()
    ps.create_table(0, dim=DIM, init_range=0.01, lr=0.1,
                    optimizer="adagrad")
    cache = DevicePassCache(ps, 0, lr=0.1)

    deep = paddle.nn.Sequential(
        paddle.nn.Linear(DIM * SLOTS, 32), paddle.nn.ReLU(),
        paddle.nn.Linear(32, 1))
    optim = paddle.optimizer.Adam(learning_rate=1e-3,
                                  parameters=deep.parameters())
    step = CompiledPassStep(
        cache, deep, optim,
        lambda out, labels: F.binary_cross_entropy_with_logits(
            out[:, 0], labels),
        table_optimizer="adagrad", table_lr=0.1)

    rs = np.random.RandomState(0)
    true_w = rs.randn(VOCAB)

    def batch():
        ids = rs.randint(0, VOCAB, (BATCH, SLOTS))
        return ids, (true_w[ids].sum(1) > 0).astype("float32")

    losses = []
    for p in range(5):  # 5 passes x 10 steps
        bs = [batch() for _ in range(10)]
        cache.begin_pass(np.concatenate([b[0].reshape(-1) for b in bs]),
                         pad_to=VOCAB)  # fixed slab: one compile, ever
        for b in bs:
            losses.append(float(step(cache, b).numpy()))
        cache.end_pass(assign=True)  # device adagrad owns the update
        print(f"pass {p}: loss {losses[-1]:.4f} "
              f"(pulls={cache.pulls} syncs={cache.pushes})")
    assert losses[-1] < losses[0]
    print(f"trained: {losses[0]:.4f} -> {losses[-1]:.4f}, "
          f"table rows {ps.table_size(0)}")


if __name__ == "__main__":
    main()
