"""AOT-validate the Wide&Deep compiled pass step for TPU.

The bench's widedeep mode was rewired to CompiledPassStep without a chip
to run it on; before chip time is spent, prove the exact program (gather + dense fwd/bwd + Adam + device adagrad at the
bench's TPU shapes) passes the REAL XLA-TPU compiler, and record its
memory/step estimates. Writes artifacts/widedeep_aot_probe.json.
"""
from __future__ import annotations

import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def main():
    import jax

    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    from paddle_tpu.distributed.ps import LocalPs
    from paddle_tpu.distributed.ps.heter_cache import DevicePassCache
    from paddle_tpu.distributed.ps.heter_trainer import CompiledPassStep
    from paddle_tpu.framework.target import force_target
    from paddle_tpu.jit.aot import estimate_step_seconds

    batch, slots, vocab, dim = 512, 16, 10000, 8  # the bench's TPU shapes

    ps = LocalPs()
    ps.create_table(0, dim=dim, init_range=0.01, lr=0.1,
                    optimizer="adagrad")
    cache = DevicePassCache(ps, 0, lr=0.1)
    deep = paddle.nn.Sequential(
        paddle.nn.Linear(dim * slots, 64), paddle.nn.ReLU(),
        paddle.nn.Linear(64, 1))
    optim = paddle.optimizer.Adam(learning_rate=1e-3,
                                  parameters=deep.parameters())
    step = CompiledPassStep(
        cache, deep, optim,
        lambda out, labels: F.binary_cross_entropy_with_logits(
            out[:, 0], labels),
        table_optimizer="adagrad", table_lr=0.1)
    step._build()

    fm, opt = step._fm, optim
    train_p, frozen_p = fm.split_values(fm.param_values())
    opt_state = opt.init_state_tree(train_p)

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x4")
    mesh1 = Mesh(np.asarray(topo.devices[:1]).reshape(1), ("x",))
    sh = NamedSharding(mesh1, P())
    SDS = jax.ShapeDtypeStruct

    def sds(v):
        return SDS(tuple(np.shape(v)), jnp.asarray(v).dtype, sharding=sh)

    args = (
        tuple(sds(v) for v in train_p),
        tuple(sds(v) for v in frozen_p),
        [sds(v) for v in fm.buffer_values()],
        [{k: sds(x) for k, x in s.items()} for s in opt_state],
        SDS((vocab, dim), jnp.float32, sharding=sh),   # rows slab
        SDS((vocab, dim), jnp.float32, sharding=sh),   # gacc/adagrad state
        SDS((batch, slots), jnp.int32, sharding=sh),   # slot indices
        SDS((batch,), jnp.float32, sharding=sh),       # labels
        sds(jax.random.key(0)),
        SDS((), jnp.float32, sharding=sh),             # lr
    )
    with force_target("tpu"):
        t0 = time.time()
        compiled = step._jit.lower(*args).compile()
        secs = round(time.time() - t0, 1)
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis() or {}
    est = estimate_step_seconds({
        "optimal_seconds": cost.get("optimal_seconds"),
        "flops": cost.get("flops"),
        "bytes_accessed": cost.get("bytes accessed",
                                   cost.get("bytes_accessed"))})
    out = {
        "config": f"widedeep compiled pass step, b{batch} slots{slots} "
                  f"vocab{vocab} dim{dim}, v5e single chip",
        "compile_seconds": secs,
        "peak_hbm_bytes": int(mem.temp_size_in_bytes
                              + mem.argument_size_in_bytes),
        "est_step_seconds": est and round(est["seconds"], 6),
        "est_signal": est and est["signal"],
        "est_examples_per_sec": est and round(batch / est["seconds"], 1),
        "note": "est_* are compiler/roofline numbers, not measurements",
    }
    path = os.path.join(REPO, "artifacts", "widedeep_aot_probe.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
