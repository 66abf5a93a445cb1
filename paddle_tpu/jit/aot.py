"""Ahead-of-time compilation of TrainSteps for described TPU topologies.

Reference analog: the auto-parallel cost model + cluster description
(python/paddle/distributed/auto_parallel/cost_model.py, cluster.py) — the
reference predicts a distributed program's step time and memory with a
hand-written simulator because compiling for a CUDA cluster it doesn't
have is impossible. On TPU the roles invert: jax.experimental.topologies
describes any v5e/v4 slice, XLA-TPU compiles the REAL train step for it
(no hardware, no execution), and the compiler's own cost/memory analysis
replaces the simulator. Used by distributed.auto_parallel.planner (mesh
search) and tools/{gpt13b,hybrid}_aot_tpu.py (feasibility artifacts).

The one rule: topology devices are described, not addressable — build
models/optimizers/inputs with NO mesh active (arrays stay on CPU), then
set the topology mesh, then compile abstractly here.
"""
from __future__ import annotations

import time
from typing import Dict, Optional

from ..cost_model import TARGET_DEVICE_KIND, device_peaks

__all__ = ["aot_compile_step", "compile_for_one_chip", "topology_mesh",
           "estimate_step_seconds"]

def estimate_step_seconds(cost: Dict,
                          device_kind: str = TARGET_DEVICE_KIND,
                          ) -> Optional[Dict]:
    """Best available per-device step-time estimate from a cost dict.

    XLA-TPU's `optimal_seconds` is authoritative when positive, but goes
    negative (an unknown-cost sentinel accumulating) on larger programs
    with collectives. Fall back to a roofline bound from the compiler's
    own flops / bytes-accessed counters: max(compute-bound, HBM-bound).
    Returns {"seconds", "signal"} with signal "compiler" | "roofline",
    or None when neither is available. The roofline ignores ICI time, so
    it is a LOWER bound — fine for ranking same-model candidates, not an
    absolute throughput claim.
    """
    opt_s = cost.get("optimal_seconds")
    if opt_s is not None and opt_s > 0:
        return {"seconds": float(opt_s), "signal": "compiler"}
    fl, by = cost.get("flops"), cost.get("bytes_accessed")
    peak_flops, hbm_bw = device_peaks(device_kind)
    if fl and fl > 0:
        sec = fl / peak_flops
        if by and by > 0:
            sec = max(sec, by / hbm_bw)
        return {"seconds": float(sec), "signal": "roofline"}
    return None


def topology_mesh(name: str, shape_map: Dict[str, int]):
    """Mesh over a described TPU topology, e.g. ("v5e:2x4",
    {"data": 2, "model": 4}). Device order is raw topology order — fine
    for compile-time cost/memory analysis, which is order-invariant."""
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh

    topo = topologies.get_topology_desc(platform="tpu", topology_name=name)
    axes = tuple(shape_map)
    degs = tuple(shape_map[a] for a in axes)
    n = 1
    for d in degs:
        n *= d
    if len(topo.devices) != n:
        raise ValueError(f"{name} has {len(topo.devices)} chips, "
                         f"mesh {shape_map} wants {n}")
    return Mesh(np.asarray(topo.devices).reshape(degs), axes)


def compile_for_one_chip(fn, *avals, topology: str = "v5e:2x2"):
    """Compile ``fn`` at the ShapeDtypeStruct ``avals`` for ONE chip of a
    described TPU topology — Pallas kernels go through Mosaic, not the
    interpreter. Returns the compiled executable. The free pre-flight for
    anything that will run on a chip: no TPU, no execution."""
    import jax
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from ..framework.target import force_target

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name=topology)
    sh = NamedSharding(Mesh(np.asarray(topo.devices[:1]), ("x",)), P())
    args = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh)
            for a in avals]
    # force_target: this is a raw jax mesh, not the framework's ambient
    # mesh, so the pallas interpret gate needs the explicit pin
    with force_target("tpu"):
        return jax.jit(fn).lower(*args).compile()


def compile_pallas_flash_for_tpu(shape=(8, 1024, 12, 64), block_size=None,
                                 topology: str = "v5e:2x2",
                                 grad: bool = True) -> float:
    """Compile the pallas flash-attention kernel (Mosaic, not interpret)
    for one chip of a described TPU topology; returns compile seconds.
    Shared by tools/hybrid_aot_tpu.py and tests/test_tpu_aot.py so the
    validation recipe can't drift."""
    import jax
    import jax.numpy as jnp

    from ..ops.flash_attention import flash_attention_val

    def fwd(a, b, c):
        return flash_attention_val(a, b, c, block_size=block_size)

    fn = fwd
    if grad:
        fn = jax.grad(lambda a, b, c: jnp.sum(fwd(a, b, c).astype(
            jnp.float32)), argnums=(0, 1, 2))
    q = jax.ShapeDtypeStruct(tuple(shape), jnp.bfloat16)
    t0 = time.time()
    compile_for_one_chip(fn, q, q, q, topology=topology)
    return round(time.time() - t0, 1)


def aot_compile_step(step, inputs, labels, want_cost: bool = False) -> Dict:
    """Abstractly lower + compile a TrainStep for the ACTIVE mesh, exactly
    the way TrainStep.__call__ would run it (same pure function, same
    in/out shardings), but with ShapeDtypeStruct arguments — nothing
    executes, so the mesh may live on a described topology.

    Returns compile_seconds, mosaic_calls (Pallas custom calls in the
    lowered program — 0 means every kernel fell back to its jnp path) and
    XLA's memory analysis (argument/output/temp/alias/peak bytes, per
    device); with want_cost also the compiler's cost analysis
    (optimal_seconds = estimated step time, flops).
    """
    import jax

    # the argument tuple is TrainStep's own (_step_args): the arrays in it
    # stay on the host backend, only their shapes/dtypes are lowered
    jitted, args, _ = step._step_args(inputs, labels)
    lowered = jitted.lower(*jax.tree_util.tree_map(
        lambda v: jax.ShapeDtypeStruct(v.shape, v.dtype), args))
    t0 = time.time()
    compiled = lowered.compile()
    out: Dict = {"compile_seconds": round(time.time() - t0, 1),
                 "mosaic_calls": lowered.as_text().count("tpu_custom_call")}
    mem = compiled.memory_analysis()
    if mem is not None:
        out.update(
            argument_bytes=int(mem.argument_size_in_bytes),
            output_bytes=int(mem.output_size_in_bytes),
            temp_bytes=int(mem.temp_size_in_bytes),
            alias_bytes=int(mem.alias_size_in_bytes))
        out["peak_hbm_bytes"] = (out["argument_bytes"] + out["temp_bytes"]
                                 + out["output_bytes"] - out["alias_bytes"])
    if want_cost:
        out.update(cost_counters(compiled))
    return out


def cost_counters(compiled) -> Dict:
    """Raw compiler cost counters from a compiled executable, normalized
    to {optimal_seconds, flops, bytes_accessed} (keys present only when
    the backend reports them). estimate_step_seconds decides how far to
    trust them. Shared by aot_compile_step and models.gpt
    .gpt_hbm_estimate so the key mapping can't drift."""
    try:
        ca = compiled.cost_analysis()
    except Exception:  # backends without cost analysis
        ca = None
    out: Dict = {}
    if isinstance(ca, dict):
        for src, dst in (("optimal_seconds", "optimal_seconds"),
                         ("flops", "flops"),
                         ("bytes accessed", "bytes_accessed")):
            if ca.get(src) is not None:
                out[dst] = float(ca[src])
    return out
