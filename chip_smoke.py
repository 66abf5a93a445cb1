"""Does the main path still start on the chip?  `python chip_smoke.py`

Drives the system once through the entry points a user calls, at the full
width of gpt-125m (12 layers, h768, 12 heads, vocab 50,304, s1024, bf16,
weights random from a seed), in ONE process:

  device      JAX must report a TPU. There is no fallback: on any other
              platform the script exits non-zero and prints no result.
  train       GPTForCausalLM -> AdamW -> jit.TrainStep, batch 8 x seq 1024,
              one fixed batch: finite falling losses, every parameter and
              optimizer slot on the chip, the Mosaic flash-attention custom
              call in the step's program (models/gpt.py falls back to the
              O(s^2) einsum silently when the kernel's shape gate says no).
  serve       the same preset through serving.GPTDecodeModel ->
              ReplicaSet(n_replicas=1): greedy requests complete with their
              full token counts and leave no KV block behind; the serving
              forward agrees with the training forward on a small input.
  four chips  (>= 4 devices; otherwise one explicit "skipped" line) the train
              step over sharding2 x model2 with ZeRO-2: same first loss as
              one chip, parameters spread over four devices.

Every phase is a plain function that raises on failure. Stdout ends with two
lines: `[result] {...}` (jax version, cache directory, per-phase walls,
compile seconds by phase from the program's own counters, losses, tokens)
and then, last, the verdict and nothing else:
{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}.
"""
from __future__ import annotations

import json
import time

import numpy as np

# bf16 tolerances, stated once. Logits of the random-init model have
# sigma ~ 0.55 (sqrt(768) * 0.02); twelve bf16 residual blocks round each to
# ~1-2 % of that, and the two forwards differ in attention kernel and
# reduction order. The loss is a mean over 8,192 tokens, so its noise is far
# smaller; 5e-3 relative is ~0.05 at ln(50304) = 10.8.
LOGIT_ATOL = 0.1
FIRST_LOSS_RTOL = 5e-3


def find_device() -> dict:
    """The device as JAX reports it."""
    import jax

    devices = jax.devices()
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def _on_platform(arrays, platform: str) -> int:
    """Assert every array's every shard lives on `platform`; the count."""
    import jax

    leaves = jax.tree_util.tree_leaves(arrays)
    for a in leaves:
        wrong = {d for d in a.devices() if d.platform != platform}
        if wrong:
            raise AssertionError(
                f"array {a.shape} {a.dtype} lives on {wrong}, expected "
                f"platform {platform!r}")
    return len(leaves)


def train_phase(platform: str, preset: str = "gpt-125m", batch: int = 8,
                seq: int = 1024, dtype: str = "bfloat16", steps: int = 6,
                mesh_topology: dict | None = None) -> dict:
    """`steps` TrainStep calls on one fixed batch (the first one compiles)."""
    import jax
    from jax.sharding import PartitionSpec as P

    import paddle_tpu as paddle
    import paddle_tpu.optimizer as opt
    from paddle_tpu.distributed import mesh as mesh_mod
    from paddle_tpu.distributed.sharding import group_sharded_parallel
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.models import (
        GPTForCausalLM, GPTPretrainingCriterion, gpt_presets,
    )

    n_dev = int(np.prod(list(mesh_topology.values()))) if mesh_topology else 1
    mesh_mod.set_mesh(
        mesh_mod.build_mesh(mesh_topology, devices=jax.devices()[:n_dev])
        if mesh_topology else None)
    try:
        cfg = gpt_presets(preset, max_position_embeddings=seq, dtype=dtype)
        model = GPTForCausalLM(cfg, seed=0)
        crit = GPTPretrainingCriterion()
        optim = opt.AdamW(learning_rate=1e-4, parameters=model.parameters())
        if mesh_topology:
            model, optim, _ = group_sharded_parallel(model, optim, "os_g")
            step = TrainStep(model, lambda lg, lb: crit(lg, lb), optim,
                             batch_spec=P(("data", "sharding")))
        else:
            step = TrainStep(model, lambda lg, lb: crit(lg, lb), optim)
        rs = np.random.RandomState(0)
        ids = paddle.to_tensor(
            rs.randint(0, cfg.vocab_size, (batch, seq)), dtype="int64")
        labels = paddle.to_tensor(
            rs.randint(0, cfg.vocab_size, (batch, seq)), dtype="int64")

        losses, walls = [], []
        for _ in range(steps):
            t0 = time.perf_counter()
            losses.append(float(step(inputs=(ids,), labels=(labels,))))
            walls.append(time.perf_counter() - t0)   # float() waited

        if not np.all(np.isfinite(losses)):
            raise AssertionError(f"non-finite loss in {losses}")
        if not losses[-1] < losses[0]:
            raise AssertionError(f"loss did not fall: {losses}")
        params = [p._value for p in model.parameters()]
        n_state = _on_platform([params, step._slots], platform)
        # the program the step actually runs, lowered again at the same
        # abstract signature (tracing only — nothing recompiles)
        program = step._cache[step._last_ckey].lower(
            *step._last_abstract).as_text()
        mosaic_calls = program.count("tpu_custom_call")
        if platform == "tpu" and cfg.use_flash_attention \
                and mosaic_calls == 0:
            raise AssertionError(
                "no Mosaic custom call in the compiled train step: "
                "attention fell back to the O(s^2) einsum path at "
                f"batch {batch} x seq {seq}")
        out = {
            "preset": preset, "batch": batch, "seq": seq, "dtype": dtype,
            "layers": cfg.num_layers, "losses": [round(x, 4) for x in losses],
            # wall per call; main() reports what of it was compilation
            "call_s": [round(w, 3) for w in walls],
            "state_arrays_on_device": n_state,
            "mosaic_custom_calls": mosaic_calls,
        }
        if mesh_topology:
            # sharded placement, read off the arrays themselves
            spread = [len({s.device for s in v.addressable_shards})
                      for v in params]
            if min(spread) != n_dev:
                raise AssertionError(
                    f"a parameter is held by {min(spread)} device(s), "
                    f"expected {n_dev}: the mesh did not spread the model")
            split = sum(1 for v in params
                        if v.addressable_shards[0].data.shape != v.shape)
            slot_split = sum(
                1 for v in jax.tree_util.tree_leaves(step._slots)
                if v.ndim and v.addressable_shards[0].data.shape != v.shape)
            if not (split and slot_split):
                raise AssertionError(
                    f"nothing is actually partitioned: {split} parameters "
                    f"and {slot_split} optimizer slots hold a strict shard")
            out.update(mesh=mesh_topology, devices_per_param=n_dev,
                       params_partitioned=split,
                       slots_partitioned=slot_split)
        return out
    finally:
        mesh_mod.set_mesh(None)


def serve_phase(platform: str, preset: str = "gpt-125m", seq: int = 1024,
                dtype: str = "bfloat16",
                prompt_lens=(64, 160, 300, 512),
                new_tokens=(16, 24, 32, 20), n_blocks: int = 256) -> dict:
    """Two passes of len(prompt_lens) greedy requests through a one-replica
    ReplicaSet: the first compiles every shape bucket in traffic, the
    second (other tokens, same lengths) finds them compiled."""
    import paddle_tpu as paddle
    from paddle_tpu.models import GPTForCausalLM, gpt_presets
    from paddle_tpu.serving import GPTDecodeModel, ReplicaSet, ServeRequest

    cfg = gpt_presets(preset, max_position_embeddings=seq, dtype=dtype)
    model = GPTForCausalLM(cfg, seed=0)
    model.eval()
    dm = GPTDecodeModel(model)
    _on_platform(dm.params, platform)

    # the repo's own reference: serving re-implements the block, and its
    # teacher-forced logits must be the training forward's
    rs = np.random.RandomState(1)
    small = rs.randint(0, cfg.vocab_size, (1, min(64, seq)))
    got = dm.forced_logits(small).astype(np.float32)
    with paddle.no_grad():
        ref = np.asarray(model(paddle.to_tensor(small, dtype="int64"))
                         .astype("float32").numpy())
    if got.shape != (1, small.shape[1], cfg.vocab_size):
        raise AssertionError(f"serving logits have shape {got.shape}")
    if not np.all(np.isfinite(got)):
        raise AssertionError("non-finite serving logits")
    logit_err = float(np.max(np.abs(got - ref)))
    if logit_err > LOGIT_ATOL:
        raise AssertionError(
            f"serving and training forwards disagree: max |dlogit| "
            f"{logit_err:.4f} > {LOGIT_ATOL}")

    rset = ReplicaSet(dm, n_replicas=1, n_blocks=n_blocks, block_tokens=16,
                      max_batch=len(prompt_lens))
    passes = []
    with rset:
        for _ in ("cold", "warm"):
            reqs = [ServeRequest(prompt_ids=rs.randint(0, cfg.vocab_size,
                                                       (n,)),
                                 max_new_tokens=m)
                    for n, m in zip(prompt_lens, new_tokens)]
            t0 = time.perf_counter()
            for r in reqs:
                if not rset.submit(r):
                    raise AssertionError(f"{r.request_id} rejected")
            done = rset.wait([r.request_id for r in reqs], timeout=900)
            wall = time.perf_counter() - t0
            for r in reqs:
                res = done.get(r.request_id)
                if res is None or res.outcome != "completed":
                    raise AssertionError(
                        f"{r.request_id}: "
                        f"{res.outcome if res else 'no result'} "
                        f"(evictions: {rset.evictions})")
                if len(res.generated) != r.max_new_tokens:
                    raise AssertionError(
                        f"{r.request_id}: {len(res.generated)} of "
                        f"{r.max_new_tokens} tokens")
                if not all(0 <= t < cfg.vocab_size for t in res.generated):
                    raise AssertionError(f"{r.request_id}: token out of "
                                         f"vocabulary")
            passes.append({"wall_s": round(wall, 2),
                           "tokens": sum(len(done[r.request_id].generated)
                                         for r in reqs)})
        engine = rset.engines[0]
        leaked = engine.pool.blocks_in_use
        buckets = len(engine.seen_buckets())
    if rset.evictions:
        raise AssertionError(f"replica evicted: {rset.evictions}")
    if leaked:
        raise AssertionError(f"{leaked} KV blocks still in use")
    return {
        "preset": preset, "dtype": dtype, "requests": 2 * len(prompt_lens),
        "prompt_lens": list(prompt_lens), "new_tokens": list(new_tokens),
        "tokens_generated": sum(p["tokens"] for p in passes),
        "cold_pass_s": passes[0]["wall_s"], "warm_pass_s": passes[1]["wall_s"],
        "shape_buckets_compiled": buckets,
        "max_abs_logit_diff_vs_train_forward": round(logit_err, 5),
        "kv_blocks_in_use_after": leaked,
    }


def four_chip_phase(platform: str, one_chip: dict, **size) -> dict:
    """The one-chip train step again, over sharding2 x model2 with ZeRO-2."""
    out = train_phase(platform, mesh_topology={"sharding": 2, "model": 2},
                      **size)
    a, b = one_chip["losses"][0], out["losses"][0]
    if abs(a - b) > FIRST_LOSS_RTOL * abs(a):
        raise AssertionError(
            f"first loss on four chips {b} != one chip {a} "
            f"(rtol {FIRST_LOSS_RTOL})")
    out["first_loss_one_chip"] = a
    return out


def compile_seconds() -> dict:
    """The program's own compile seconds so far, by phase, over every span
    (`jit_compile_seconds_total`, observability/host_spans.py): tracing,
    lowering, backend compile or cache load, and the loads' part of it."""
    from paddle_tpu.observability import get_registry

    out = dict.fromkeys(("trace", "lower", "backend", "cache_load"), 0.0)
    family = get_registry().get("jit_compile_seconds_total")
    for labels, child in (family.items() if family is not None else ()):
        out[labels["phase"]] += child.value
    return out


def main() -> None:
    t_start = time.perf_counter()
    device = find_device()
    if device["platform"] != "tpu":
        raise SystemExit(
            f"chip_smoke: JAX found platform {device['platform']!r} "
            f"({device['kind']} x {device['count']}), not a TPU. "
            f"Nothing was run; there is no CPU fallback.")
    import jax
    import jaxlib

    from paddle_tpu.framework.flags import flag
    from paddle_tpu.jit.artifact_cache import use_compile_cache

    cache_dir = use_compile_cache()
    # the smoke runs the default kernel dispatch: with the flag off,
    # ops/pallas/autotune.lookup() returns before it opens any tune cache
    # (.cache/kernel_tune_cache.json or the committed artifacts/ copy), and
    # nothing here imports the analysis suite that reads .cache/static_ast.pkl
    if flag("FLAGS_kernel_autotune"):
        raise SystemExit("chip_smoke: FLAGS_kernel_autotune is on; the smoke "
                         "checks the default dispatch")
    print(f"[device] {device}  jax {jax.__version__}  cache {cache_dir}  "
          f"kernel tune cache: ignored (FLAGS_kernel_autotune off)",
          flush=True)

    phases = {}
    platform = device["platform"]
    todo = [("train", lambda: train_phase(platform)),
            ("serve", lambda: serve_phase(platform))]
    if device["count"] >= 4:
        todo.append(("four_chips",
                     lambda: four_chip_phase(platform, phases["train"])))
    for name, fn in todo:
        t0, mark = time.perf_counter(), compile_seconds()
        phases[name] = fn()
        phases[name]["wall_s"] = round(time.perf_counter() - t0, 2)
        phases[name]["compile_s"] = {
            k: round(v - mark[k], 2) for k, v in compile_seconds().items()}
        print(f"[{name}] {phases[name]}", flush=True)
    if device["count"] < 4:
        phases["four_chips"] = f"skipped: {device['count']} chips"
        print(f"[four_chips] {phases['four_chips']}", flush=True)

    print("[result] " + json.dumps({
        "jax": jax.__version__, "jaxlib": jaxlib.__version__,
        "compile_cache_dir": cache_dir, "kernel_autotune": False,
        "wall_s": round(time.perf_counter() - t_start, 2),
        "phases": phases,
    }), flush=True)
    # the verdict, last and alone: exactly these keys (find_device's three)
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
