"""Wide&Deep CTR training over the parameter server, two ways.

``--eager`` (the pre-ISSUE-20 path): per-step distributed_lookup_table
through the async communicator — a host pull + Tensor-autograd dense
step + host push for every batch. Simple, and roughly three orders of
magnitude under the accelerator roofline.

Default (ISSUE 20): the compiled hot path — paddle_tpu.models.WideDeep
under PsTrainStep (ONE jitted program per step, pre-gathered rows in /
row-grads out) driven by PsPipeline double buffering over a bus-sharded
PS, so step k computes while step k+1's unique keys prefetch and step
k-1's merged grads push. What that gains on a chip is not measured: no
cell of BENCHMARK.json runs the PS path yet.

    python examples/wide_deep_ps.py [--eager]
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

import paddle_tpu as paddle
from paddle_tpu.models import WideDeep, ctr_batches, wide_deep_loss

VOCAB, SLOTS, DIM, BATCH, STEPS = 1000, 6, 8, 64, 50


def main_eager():
    from paddle_tpu.distributed.ps import (
        LocalPs, TheOnePSRuntime, distributed_lookup_table)
    from paddle_tpu.distributed.ps.communicator import AsyncCommunicator

    runtime = TheOnePSRuntime()
    ps = LocalPs()
    ps.create_table(0, dim=DIM, init_range=0.01, lr=0.1,
                    optimizer="adagrad")
    runtime.client = ps
    runtime.communicator = AsyncCommunicator(ps)
    runtime.communicator.start()

    paddle.seed(0)
    model = WideDeep(SLOTS, DIM)
    optim = paddle.optimizer.Adam(learning_rate=1e-3,
                                  parameters=model.parameters())
    for step, (ids, labels) in enumerate(
            ctr_batches(STEPS, BATCH, SLOTS, VOCAB, alpha=1.1, seed=0)):
        rows = distributed_lookup_table(
            paddle.to_tensor(ids.astype(np.int64)), table_id=0, lr=0.1)
        logit = model(rows.reshape([BATCH, -1]))
        loss = wide_deep_loss(logit, paddle.to_tensor(labels))
        loss.backward()
        optim.step()
        optim.clear_grad()
        if step % 10 == 0:
            print(f"step {step}: loss {float(loss):.4f}  "
                  f"table rows {ps.table_size(0)}")
    runtime.communicator.stop()


def main_pipelined():
    from paddle_tpu.distributed.ps.pipeline import (
        PsPipeline, PsTrainStep, make_sharded_ps)

    client, services, bus = make_sharded_ps(2)
    try:
        client.create_table(0, DIM, init_range=0.01, optimizer="adagrad")
        paddle.seed(0)
        model = WideDeep(SLOTS, DIM)
        optim = paddle.optimizer.Adam(learning_rate=1e-3,
                                      parameters=model.parameters())
        step = PsTrainStep(model, optim, wide_deep_loss, dim=DIM,
                           pad_rows=512)
        pipe = PsPipeline(client, 0, step, depth=2, lr_sparse=0.1)
        batches = ctr_batches(STEPS, BATCH, SLOTS, VOCAB, alpha=1.1,
                              seed=0)
        stats = pipe.run(batches)
        pipe.close()
        for i in range(0, STEPS, 10):
            print(f"step {i}: loss {stats['losses'][i]:.4f}")
        print(f"{stats['examples_per_s']:.0f} examples/s, exposed pull "
              f"{stats['exposed_pull_ms']:.3f} ms / step "
              f"{stats['step_ms']:.3f} ms, table rows "
              f"{client.table_size(0)}")
    finally:
        client.close()
        for s in services:
            s.stop()
        bus.close()


if __name__ == "__main__":
    if "--eager" in sys.argv[1:]:
        main_eager()
    else:
        main_pipelined()
