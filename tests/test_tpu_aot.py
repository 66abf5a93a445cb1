"""The distributed train step and pallas kernels COMPILE for real TPU.

The CPU virtual-mesh suite proves the sharded programs are numerically
correct; these tests prove the TPU compiler (via jax.experimental
.topologies — ahead-of-time, no TPU execution) accepts them: the GSPMD
ZeRO-2 + TP TrainStep with the flash kernel on, on a described v5e:2x4,
and every Pallas family's Mosaic lowering at gpt-125m / gpt-1.3b widths on
one v5e chip. A regression here means "works on the CPU mesh, breaks on
TPU hardware" (tools/gpt13b_aot_tpu.py and tools/hybrid_aot_tpu.py carry
the full config matrix; this is the fast always-on subset).

Runs in subprocesses: the topology compile client is process-global state
the suite shouldn't inherit.
"""
import functools
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

PROBE = (
    "from jax.experimental import topologies; "
    "topologies.get_topology_desc(platform='tpu', topology_name='v5e:2x4')"
)

CHILD = r"""
import sys
sys.path.insert(0, %r)
sys.path.insert(0, %r + "/tools")
from hybrid_aot_tpu import aot_compile_step, build_config_a

step, inputs, labels = build_config_a()
r = aot_compile_step(step, inputs, labels)
assert r.get("peak_hbm_bytes", 0) > 0, r
# flash is ON in config A: the Mosaic custom calls (forward, backward) must
# be in the program, not the silent O(s^2) einsum fallback
assert r["mosaic_calls"] >= 2, r
print("TRAINSTEP-AOT-OK", r["compile_seconds"], r["mosaic_calls"])
""" % (REPO, REPO)

# One Mosaic compile per Pallas family at the widths the models publish:
# gpt-125m (h768, ffn 3072, 12 heads x 64, s1024) and gpt-1.3b (h2048,
# ffn 8192, 16 heads x 128, s2048). quantize_int8 is the regression this
# must catch: gridless, it put the whole weight in VMEM and was refused
# from 768x3072 up ("Scoped allocation with size 20.24M and limit 16.00M
# exceeded"), and with stochastic=True Mosaic rejected its uint32 ->
# float32 cast outright.
KERNELS_CHILD = r"""
import sys
sys.path.insert(0, %r)
import jax
import jax.numpy as jnp

from paddle_tpu.jit.aot import (compile_for_one_chip,
                                compile_pallas_flash_for_tpu)
from paddle_tpu.ops.pallas import codec, fused_update as fu
from paddle_tpu.ops.quant_matmul import quant_matmul, quantize_int8

SDS = jax.ShapeDtypeStruct
f32, bf16 = jnp.float32, jnp.bfloat16


def mosaic(fn, *avals):
    text = compile_for_one_chip(fn, *avals).as_text()
    assert "tpu_custom_call" in text, "kernel fell back to its jnp path"


# bf16 in, forward and the one backward kernel, at the blocks dispatch
# picks: the two widths above, then the benchmark cells' own shapes
# (gpt-125m.train-b12-s1024 and gpt-125m-ctx2048.train-b6-s2048), so a
# bf16 dot or a block Mosaic cannot lower (the backward's transposed
# scores, a tile or the whole-sequence dq accumulator over the VMEM limit
# the code computes) fails here and not on the chip
for shape in ((8, 1024, 12, 64), (4, 2048, 16, 128),
              (12, 1024, 12, 64), (6, 2048, 12, 64)):
    compile_pallas_flash_for_tpu(shape, grad=True)
print("FLASH-OK")

# the two kernels go by name in the compiled programs (a device trace's
# reader finds them so): forward alone, then forward + the backward call,
# which keeps the dkv kernel's name for the benchmark's readers.
# Inside a scope, as in the model's block: XLA names the call after the
# last name on its path, and directly under a transform that would be
# `jvp(flash_fwd)`
import re
from paddle_tpu.ops import flash_attention as fa
from paddle_tpu.ops.flash_attention import flash_attention_val


def attention(a, b, c):
    with jax.named_scope("attn"):
        return flash_attention_val(a, b, c)


def mosaic_names(fn):
    q = SDS((8, 1024, 12, 64), bf16)
    text = compile_for_one_chip(fn, q, q, q).as_text()
    return sorted(re.match(r"\s*%%?([A-Za-z_0-9]+)", line).group(1)
                  for line in text.splitlines()
                  if "custom-call(" in line and "tpu_custom_call" in line)


def all_grads(a, b, c):
    return jax.grad(
        lambda a, b, c: jnp.sum(flash_attention_val(a, b, c).astype(f32)),
        argnums=(0, 1, 2))(a, b, c)


assert mosaic_names(attention) == ["flash_fwd"]
names = mosaic_names(jax.grad(
    lambda a, b, c: jnp.sum(attention(a, b, c).astype(f32)),
    argnums=(0, 1, 2)))
assert names == ["flash_bwd_dkv", "flash_fwd"], names
print("FLASH-NAMES-OK")

# latent attention's two widths (q, k 192 and v 128 wide) at the shape of
# the cell kanana-2-30b-a3b-ep8.train-b1-s8192, forward and the backward
# kernel at the block dispatch picks: a 192-wide row that Mosaic cannot
# tile, or a dq accumulator over 8,192 rows of 192 that passes the VMEM
# limit the code computes, fails here
q, v = SDS((1, 8192, 32, 192), bf16), SDS((1, 8192, 32, 128), bf16)
text = compile_for_one_chip(all_grads, q, q, v).as_text()
assert text.count('custom_call_target="tpu_custom_call"') == 2, text[:2000]
# the longest sequence flash_attention_supported lets through, in fp32 (the
# dq block is then as large as the accumulator, twice): the limit the code
# computes there is one the compiler takes
s_max = fa._DQ_ACC_BYTES // (4 * 128)
assert fa.flash_attention_supported((1, s_max, 1, 128))
assert not fa.flash_attention_supported((1, 2 * s_max, 1, 128))
q = SDS((1, s_max, 1, 128), f32)
compile_for_one_chip(all_grads, q, q, q)
print("FLASH-MLA-OK")

# grouped heads 256 wide in bf16 at 8,192 tokens (16 query heads over 2
# key/value heads, the qwen3_next configuration's; and all of a call's
# query heads over one key/value head): a 512-byte row takes `_default_block`'s 1024, the largest tiles these kernels have
# (forward 0.5 MB of q and 1 MB of k, v a step, an 8.4 MB dq accumulator a
# head); k and v go in at their own head count, by the index maps
for n, n_kv in ((16, 2), (8, 1)):
    q, k = SDS((1, 8192, n, 256), bf16), SDS((1, 8192, n_kv, 256), bf16)
    assert fa.flash_attention_supported(q.shape)
    text = compile_for_one_chip(all_grads, q, k, k).as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2, \
        text[:2000]
    dq, dk, dv = jax.eval_shape(all_grads, q, k, k)
    assert dq.shape == q.shape and dk.shape == dv.shape == k.shape
# ... and 64 wide at two sequences of 8,192 tokens, 32 query heads over 8
# key/value heads: the lfm2_moe cell's attention layer (the narrowest head
# with groups, at the longest sequence the 64-wide kernels have seen)
q, k = SDS((2, 8192, 32, 64), bf16), SDS((2, 8192, 8, 64), bf16)
assert fa.flash_attention_supported(q.shape)
text = compile_for_one_chip(all_grads, q, k, k).as_text()
assert text.count('custom_call_target="tpu_custom_call"') == 2, text[:2000]
print("FLASH-GQA-OK")

# the chunked gated delta rule, forward and backward, at the qwen3_next
# cell's shape (8,192 tokens, 16 key heads serving 32 value heads, 128
# wide, v in bf16): compiled for a TPU the chunks follow one another in the
# two Pallas kernels gdn_chunk_fwd / gdn_chunk_bwd (eight heads a grid
# step; the backward's scoped VMEM is 16.1 MB by the compiler's count, over
# the 16 MB default, so the call sets its limit), the solve around them is
# XLA. What is held for
# the whole sequence is the inputs, T and one state a chunk: 1.16 GB of
# temporaries by the compiler's count (0.76 GB with the scan; the kernels
# hand back dT, dq and dk per value head and o in float32 as whole arrays,
# where the scan's backward made them a chunk at a time. On the chip the
# step's peak FELL, 7.115 -> 7.089 GB: PERF.md, PR 34).
# And at the shape and in the context of the benchmark's comparison (d):
# one value head a key head, v in float32, under
# default_matmul_precision("highest"), which the kernels' bfloat16 passes
# must not inherit (Mosaic: "Bad lhs type")
from paddle_tpu.observability import get_registry
from paddle_tpu.ops.gated_delta_rule import gated_delta_rule_chunked


def rule_grads(q, k, v, g, beta):
    return jax.grad(
        lambda *a: jnp.sum(gated_delta_rule_chunked(*a).astype(f32)),
        argnums=(0, 1, 2, 3, 4))(q, k, v, g, beta)


def rule_dispatched(source):
    return get_registry().snapshot().get("kernel_dispatch_total", {}).get(
        "kernel=gated_delta_rule,source=" + source, 0)


gb = SDS((1, 8192, 32), f32)
for n_key, values, precision, most in ((16, bf16, None, 1.2e9),
                                        (32, f32, "highest", 1.25e9)):
    qk = SDS((1, 8192, n_key, 128), f32)
    with jax.default_matmul_precision(precision):
        compiled = compile_for_one_chip(
            rule_grads, qk, qk, SDS((1, 8192, 32, 128), values), gb, gb)
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') >= 2, \
        text[:2000]
    assert "gdn_chunk_fwd" in text and "gdn_chunk_bwd" in text
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < most, temp
assert rule_dispatched("kernel") == 2 and rule_dispatched("scan") == 0
print("GDN-RULE-OK")

for k, n in ((768, 3072), (2048, 8192), (3072, 768), (8192, 2048)):
    for stochastic in (False, True):
        mosaic(lambda w: quantize_int8(w, stochastic=stochastic, seed=3),
               SDS((k, n), f32))
print("QUANTIZE-OK")

for m, k, n in ((8192, 768, 3072), (8192, 2048, 8192)):
    mosaic(quant_matmul, SDS((m, k), bf16), SDS((k, n), jnp.int8),
           SDS((1, n), f32))
print("QMM-OK")

n = 768 * 3072
for name, bs in (("int8_block", 256), ("fp8_block", 512)):
    nb = n // bs
    mosaic(lambda x, s: codec.block_encode(x, s, bs, name),
           SDS((n,), f32), SDS((nb,), f32))
    carrier = jnp.int32 if name == "int8_block" else f32
    mosaic(lambda q, s: codec.block_decode(q, s, 4, f32, n),
           SDS((nb, bs), carrier), SDS((nb,), f32))
print("CODEC-OK")

hyper = {"beta1": 0.9, "beta2": 0.999, "eps": 1e-8}
for n, dt in ((768 * 3072, f32), (768 * 50304, bf16)):   # 2.4M, 38.6M
    slots = {"moment1": SDS((n,), f32), "moment2": SDS((n,), f32),
             "beta1_pow": SDS((), f32), "beta2_pow": SDS((), f32)}

    def plain(p, g, m1, m2, b1, b2, lr):
        return fu.fused_update_flat(
            p, g, {"moment1": m1, "moment2": m2, "beta1_pow": b1,
                   "beta2_pow": b2}, lr, kind="adamw", hyper=hyper,
            wd=0.01)

    mosaic(plain, SDS((n,), dt), SDS((n,), f32), *slots.values(),
           SDS((), f32))
    bs = 512

    def dequant(p, q, s, m1, m2, b1, b2, lr):
        return fu.fused_dequant_update_flat(
            p, q, s, 4, {"moment1": m1, "moment2": m2, "beta1_pow": b1,
                         "beta2_pow": b2}, lr, kind="adamw", hyper=hyper,
            block_size=bs, wd=0.01)

    mosaic(dequant, SDS((n,), dt), SDS((n // bs, bs), jnp.int32),
           SDS((n // bs,), f32), *slots.values(), SDS((), f32))
print("FUSED-UPDATE-OK")
""" % (REPO,)


@functools.lru_cache(maxsize=None)
def _probe_failure():
    """None when the TPU compiler answers the topology probe, else why."""
    r = subprocess.run(
        [sys.executable, "-c", PROBE],
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=300)
    return None if r.returncode == 0 else (r.stderr or "").strip()[-300:]


def _run_child(code, timeout=1500):
    if _probe_failure() is not None:      # the only reason to skip
        pytest.skip("topology probe failed — libtpu absent: "
                    + _probe_failure())
    proc = subprocess.run(
        [sys.executable, "-c", code],
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=timeout)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return proc.stdout


def test_trainstep_with_flash_compiles_for_tpu():
    assert "TRAINSTEP-AOT-OK" in _run_child(CHILD)


def test_pallas_families_compile_by_mosaic():
    out = _run_child(KERNELS_CHILD)
    for tag in ("FLASH-OK", "FLASH-NAMES-OK", "FLASH-MLA-OK", "FLASH-GQA-OK",
                "GDN-RULE-OK", "QUANTIZE-OK",
                "QMM-OK", "CODEC-OK", "FUSED-UPDATE-OK"):
        assert tag in out, out[-2000:]


# One expert layer's recomputation and backward at the `qwen3_next` cell's
# shapes, as the models run it (the whole layer under jax.checkpoint): what
# the compiled program moves on the token side. The forward of a linear
# loss's gradient is dead, so what compiles is the recomputed forward and
# the backward. PR 36: the combine keeps its residuals and its backward on
# the sorted side, so ONE gather of T*k rows is left (the dispatch's
# backward; the recomputed combine's feeds nothing and is dropped), no
# float32 cotangent of T*k rows is made, and with the assignments k-major
# no array has k in its tiled minor two axes.
EXPERT_LAYER_CHILD = r"""
import re
import sys
sys.path.insert(0, %r)
import jax
import jax.numpy as jnp

from paddle_tpu.distributed import moe
from paddle_tpu.jit.aot import compile_for_one_chip

SDS = jax.ShapeDtypeStruct
f32, bf16 = jnp.float32, jnp.bfloat16
T, k, h, E, f = 8192, 10, 2048, 32, 512


def loss(x, weights, w_gate, w_up, w_down, chosen, cot):
    layer = jax.checkpoint(lambda x_, *w: x_ + moe.held_experts_ffn(
        x_, chosen, *w, 0))
    y = layer(x, weights, w_gate, w_up, w_down)
    return jnp.sum(y.astype(f32) * cot)


text = compile_for_one_chip(
    jax.grad(loss, argnums=(0, 1, 2, 3, 4)), SDS((T, h), bf16),
    SDS((T, k), f32), SDS((E, h, f), bf16), SDS((E, h, f), bf16),
    SDS((E, f, h), bf16), SDS((T, k), jnp.int32), SDS((T, h), f32)).as_text()
gathers = re.findall(
    r"= \w+\[%%d,%%d\]\S* gather\(.*op_name=\"([^\"]*)\"" %% (T * k, h), text)
assert len(gathers) == 1, gathers
# booked where the dispatch is; the combine's backward is the block loop
assert "/moe_dispatch/" in gathers[0], gathers
assert "/moe_combine/while/body/gather" in text
assert "rematted_computation/moe_combine" not in text
assert "f32[%%d,%%d]" %% (T * k, h) not in text
assert "[%%d,%%d,%%d]" %% (T, k, h) not in text
print("EXPERT-LAYER-OK")
""" % REPO


def test_the_expert_layer_backward_keeps_to_the_sorted_side():
    assert "EXPERT-LAYER-OK" in _run_child(EXPERT_LAYER_CHILD)


PLANNER_CHILD = r"""
import sys
sys.path.insert(0, %r)
import numpy as np
from jax.sharding import PartitionSpec as P

import paddle_tpu as paddle
import paddle_tpu.optimizer as opt
from paddle_tpu.jit import TrainStep
from paddle_tpu.models import (GPTForCausalLM, GPTPretrainingCriterion,
                               gpt_presets)
from paddle_tpu.distributed import mesh as mesh_mod
from paddle_tpu.distributed.auto_parallel.planner import (
    plan, enumerate_factorizations)

# pure-search unit: factor assignment honors caps, drops degree-1 axes
f = enumerate_factorizations(8, ("data", "model"), caps={"model": 4})
assert {tuple(sorted(c.items())) for c in f} == {
    (("data", 8),), (("data", 4), ("model", 2)),
    (("data", 2), ("model", 4))}, f

crit = GPTPretrainingCriterion()
rs = np.random.RandomState(0)

def builder(shape_map, activate_mesh):
    cfg = gpt_presets("gpt-test", mode="scan", use_flash_attention=False)
    model = GPTForCausalLM(cfg, seed=0)
    optim = opt.AdamW(learning_rate=1e-4, parameters=model.parameters())
    step = TrainStep(model, lambda lg, lb: crit(lg, lb), optim,
                     batch_spec=P(("data", "sharding")))
    ids = paddle.to_tensor(rs.randint(0, cfg.vocab_size, (16, 16)),
                           dtype="int64")
    lbl = paddle.to_tensor(rs.randint(0, cfg.vocab_size, (16, 16)),
                           dtype="int64")
    activate_mesh()
    return step, (ids,), (lbl,)

plans = plan(builder, 8, axes=("data", "model"), caps={"model": 4},
             verbose=False)
assert len(plans) == 3, plans
assert all(p.error is None for p in plans), plans
assert all(p.est_seconds and p.est_seconds > 0 for p in plans), plans
assert all(p.peak_hbm_bytes and p.fits for p in plans), plans
# sorted best-first by the estimate
secs = [p.est_seconds for p in plans]
assert secs == sorted(secs), plans
assert mesh_mod.get_mesh() is None  # planner restored ambient mesh
print("PLANNER-OK", plans[0].shape_map)
""" % (REPO,)


GPT13B_CHILD = r"""
import json, sys
sys.path.insert(0, %r)
sys.path.insert(0, %r + "/tools")
from gpt13b_aot_tpu import compile_config4

est = compile_config4()  # the exact configuration the artifact records
assert est.get("peak_hbm_bytes", 0) > 0, est
print("HBM13B_JSON:" + json.dumps(est))
""" % (REPO, REPO)


@pytest.mark.slow
def test_gpt13b_fits_v5e_by_the_real_tpu_compiler():
    """BASELINE config-4 feasibility pinned with the TPU backend, not the
    CPU proxy (tests/test_gpt13b_memory.py keeps the CPU guard): the full
    AdamW step (ZeRO-2 sharding32 x mp2, bf16 + remat + flash) must fit a
    v5e chip per XLA-TPU's own memory accounting. Artifact counterpart:
    artifacts/gpt13b_aot_tpu.json (2.55 GiB/device)."""
    out = _run_child(GPT13B_CHILD)
    est = None
    for line in out.splitlines():
        if line.startswith("HBM13B_JSON:"):
            est = json.loads(line[len("HBM13B_JSON:"):])
    assert est is not None, out[-1000:]
    peak_gib = est["peak_hbm_bytes"] / 2**30
    assert 1.0 <= peak_gib <= 16.0, est


def test_mesh_planner_ranks_with_tpu_compiler():
    """distributed.auto_parallel.planner: the reference's Planner+cost_model
    (auto_parallel/planner.py:829) redesigned with XLA-TPU AOT compilation
    as the cost model — candidates enumerate, compile, rank, mesh state
    restored."""
    assert "PLANNER-OK" in _run_child(PLANNER_CHILD)
