"""AOT-compile the FULL hybrid-parallel train step with the real TPU compiler.

Complements tools/gpt13b_aot_tpu.py (which covers the BASELINE config-4
GSPMD estimate): this validates that the framework's actual TrainStep —
the same object users drive, including ZeRO-2 slot sharding, Megatron TP,
the 1F1B pipeline schedule and ring-attention sequence parallelism — lowers
and compiles for REAL v5e topologies through jax.experimental.topologies,
with no TPU execution required. The CPU virtual-mesh dryrun proves the
sharded program is correct; this proves the TPU compiler accepts it and
reports its per-device memory.

Configs (mirroring __graft_entry__.dryrun_multichip):
  A  v5e:2x4  (8)  data2 x sharding2 x model2, GSPMD + ZeRO-2
  C  v5e:4x8  (32) data2 x sharding2 x pipe2 x model2 x sep2, ZeRO-2 +
                   1F1B + TP + ring-attention SP jointly

Writes artifacts/hybrid_aot_tpu.json. Runs with JAX_PLATFORMS=cpu — model
init arrays live on CPU; compilation targets the described TPU topology.
"""
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


from paddle_tpu.jit.aot import aot_compile_step, topology_mesh as topo_mesh


def build_config_a():
    """GSPMD ZeRO-2 + TP TrainStep on a v5e:2x4 topology mesh — shared by
    main() and tests/test_tpu_aot.py so the two can't drift.

    Model/optimizer/inputs are built with NO mesh (arrays on CPU): topology
    devices are non-addressable, so only the abstract lowering may see the
    mesh — device_put onto a described topology is impossible.
    """
    import numpy as np
    from jax.sharding import PartitionSpec as P

    import paddle_tpu as paddle
    import paddle_tpu.optimizer as opt
    from paddle_tpu.distributed import mesh as mesh_mod
    from paddle_tpu.distributed.sharding import group_sharded_parallel
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.models import (
        GPTForCausalLM, GPTPretrainingCriterion, gpt_presets,
    )

    rs = np.random.RandomState(0)
    crit = GPTPretrainingCriterion()
    mesh_mod.set_mesh(None)
    # flash ON at a shape the kernel accepts under this mesh: head_dim 128,
    # one head per 'model' shard, seq 128 (the O(s^2) fallback compiling
    # instead would go unnoticed with the kernel off)
    cfg = gpt_presets("gpt-test", mode="scan", hidden_size=256, num_heads=2)
    model = GPTForCausalLM(cfg, seed=0)
    optim = opt.AdamW(learning_rate=1e-4, parameters=model.parameters())
    model, optim, _ = group_sharded_parallel(model, optim, "os_g")
    step = TrainStep(model, lambda lg, lb: crit(lg, lb), optim,
                     batch_spec=P(("data", "sharding")))
    batch, seq = 16, 128
    ids = paddle.to_tensor(rs.randint(0, cfg.vocab_size, (batch, seq)),
                           dtype="int64")
    lbl = paddle.to_tensor(rs.randint(0, cfg.vocab_size, (batch, seq)),
                           dtype="int64")
    mesh_mod.set_mesh(
        topo_mesh("v5e:2x4", {"data": 2, "sharding": 2, "model": 2}))
    return step, (ids,), (lbl,)


def main():
    import jax

    jax.config.update("jax_platforms", "cpu")

    import numpy as np
    from jax.sharding import PartitionSpec as P

    import paddle_tpu as paddle
    import paddle_tpu.optimizer as opt
    from paddle_tpu.distributed import mesh as mesh_mod
    from paddle_tpu.distributed.sharding import group_sharded_parallel
    from paddle_tpu.models import (
        GPTForCausalLM, GPTPretrainingCriterion, gpt_presets,
        gpt_1f1b_train_step,
    )

    results = {}
    rs = np.random.RandomState(0)
    crit = GPTPretrainingCriterion()

    # ---- config A: GSPMD ZeRO-2 + TP on v5e:2x4 ----
    step, inputs, labels = build_config_a()
    r = aot_compile_step(step, inputs, labels)
    r["topology"], r["mesh"] = "v5e:2x4", {"data": 2, "sharding": 2,
                                           "model": 2}
    print("A (GSPMD ZeRO-2 + TP, v5e:2x4):", r)
    results["A_gspmd_zero2_tp"] = r

    # ---- config C: all five axes jointly on v5e:4x8 (1F1B + ring SP) ----
    mesh_mod.set_mesh(None)
    cfg_c = gpt_presets("gpt-test", mode="scan", use_flash_attention=False,
                        num_layers=4, pp_microbatches=4,
                        use_ring_attention=True)
    model = GPTForCausalLM(cfg_c, seed=0)
    optim = opt.AdamW(learning_rate=1e-4, parameters=model.parameters())
    model, optim, _ = group_sharded_parallel(model, optim, "os_g")
    batch = 32
    ids = paddle.to_tensor(rs.randint(0, cfg_c.vocab_size, (batch, 16)),
                           dtype="int64")
    lbl = paddle.to_tensor(rs.randint(0, cfg_c.vocab_size, (batch, 16)),
                           dtype="int64")
    # the 1F1B schedule reads the pipe degree at construction time, so the
    # step (unlike model/optim/inputs) is built under the topology mesh
    mesh_mod.set_mesh(topo_mesh("v5e:4x8", {"data": 2, "sharding": 2,
                                            "pipe": 2, "model": 2,
                                            "sep": 2}))
    step = gpt_1f1b_train_step(model, optim,
                               batch_spec=P(("data", "sharding")))
    r = aot_compile_step(step, (ids,), (lbl,))
    r["topology"] = "v5e:4x8"
    r["mesh"] = {"data": 2, "sharding": 2, "pipe": 2, "model": 2, "sep": 2}
    print("C (ZeRO-2 + 1F1B + TP + ring-SP, v5e:4x8):", r)
    results["C_joint_5axis_1f1b"] = r

    # ---- pallas kernels: first TPU-backend validation (tests run them in
    # CPU interpret mode; this proves the Mosaic lowering itself) ----
    import jax.numpy as jnp

    from paddle_tpu.jit.aot import (
        compile_for_one_chip, compile_pallas_flash_for_tpu,
    )
    from paddle_tpu.ops.quant_matmul import quant_matmul

    b, s, n, d = 8, 1024, 12, 64
    results["pallas_flash_fwd_bwd"] = {
        "compile_seconds": compile_pallas_flash_for_tpu(
            (b, s, n, d), grad=True),
        "shape": [b, s, n, d], "topology": "v5e (single chip)",
        "mosaic": True}
    print("pallas flash fwd+bwd TPU compile:",
          results["pallas_flash_fwd_bwd"])

    SDS = jax.ShapeDtypeStruct
    t0 = time.time()
    compile_for_one_chip(quant_matmul, SDS((512, 1024), jnp.bfloat16),
                         SDS((1024, 1024), jnp.int8),
                         SDS((1, 1024), jnp.float32))
    results["pallas_int8_matmul"] = {
        "compile_seconds": round(time.time() - t0, 1),
        "shape": [512, 1024, 1024], "topology": "v5e (single chip)",
        "mosaic": True}
    print("pallas int8 matmul TPU compile:", results["pallas_int8_matmul"])

    path = os.path.join(REPO, "artifacts", "hybrid_aot_tpu.json")
    with open(path, "w") as f:
        json.dump(results, f, indent=1)
    print(f"wrote {path}")


if __name__ == "__main__":
    main()
