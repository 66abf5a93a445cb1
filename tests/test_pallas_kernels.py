"""Kernel-vs-reference property grid for the ISSUE 13 pallas kernels.

All four families in interpret mode (conftest's 8-device CPU platform):
fused dequant+update, blockwise codec, flash attention (independent
q/k blocks), quant_matmul (tuned tiles + deterministic seeds). The
equivalence contract under test: codec payload bits EXACT; fused update
within 1 ulp of the jnp composition per application (XLA fma-contraction
freedom between the two graph shapes — see ops/pallas/fused_update.py).
"""
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.optimizer as opt
from paddle_tpu.distributed import grad_comm as gc
from paddle_tpu.framework import flags
from paddle_tpu.ops.pallas import autotune as at
from paddle_tpu.ops.pallas import codec as pc
from paddle_tpu.ops.pallas import fused_update as fu

import jax
import jax.numpy as jnp


def assert_ulp(a, b, max_ulp=1, msg=""):
    """Elementwise ulp distance between two same-dtype float arrays."""
    a, b = np.asarray(a), np.asarray(b)
    assert a.dtype == b.dtype and a.shape == b.shape, (a.dtype, b.dtype)
    kind = {2: np.int16, 4: np.int32, 8: np.int64}[a.dtype.itemsize]
    ai, bi = a.view(kind), b.view(kind)
    # map sign-magnitude float ordering onto two's complement ints
    ai = np.where(ai < 0, np.array(-(2 ** (a.dtype.itemsize * 8 - 1)),
                                   kind) - ai, ai)
    bi = np.where(bi < 0, np.array(-(2 ** (a.dtype.itemsize * 8 - 1)),
                                   kind) - bi, bi)
    d = np.abs(ai.astype(np.int64) - bi.astype(np.int64))
    assert d.max() <= max_ulp, f"{msg} max ulp {d.max()} at {d.argmax()}"


def _optimizer(kind_name, params):
    mk = {
        "SGD": lambda: opt.SGD(learning_rate=1e-3, parameters=params),
        "Momentum": lambda: opt.Momentum(learning_rate=1e-3, momentum=0.9,
                                         use_nesterov=True,
                                         parameters=params),
        "Adam": lambda: opt.Adam(learning_rate=1e-3, parameters=params),
        "AdamW": lambda: opt.AdamW(learning_rate=1e-3, weight_decay=0.01,
                                   parameters=params),
    }
    return mk[kind_name]()


def _slots_for(o, n, seed):
    rs = np.random.RandomState(seed)
    slots = {}
    for k, v in o._init_slots(jnp.zeros((1,), jnp.float32)).items():
        if np.shape(v) == ():
            slots[k] = v
        elif k == "moment2":  # second moments are non-negative
            slots[k] = jnp.abs(jnp.asarray(rs.randn(n), jnp.float32)) * 0.01
        else:
            slots[k] = jnp.asarray(rs.randn(n), jnp.float32) * 0.01
    return slots


# --------------------------------------------------- fused update vs jnp

@pytest.mark.parametrize("kind_name", ["SGD", "Momentum", "Adam", "AdamW"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("n", [128, 1000, 12345])   # odd, non-row-aligned
def test_fused_update_matches_bucket_fn(kind_name, dtype, n):
    lin = nn.Linear(4, 4)
    o = _optimizer(kind_name, lin.parameters())
    kind, hyper = fu.rule_spec(o)
    wd = 0.01 if kind_name == "AdamW" else 0.0
    rs = np.random.RandomState(n)
    p = jnp.asarray(rs.randn(n), jnp.dtype(dtype))
    g = jnp.asarray(rs.randn(n), jnp.float32)
    slots = _slots_for(o, n, n + 1)
    lr = jnp.asarray(1e-3, jnp.float32)

    fused = jax.jit(lambda p, g, s, lr: fu.fused_update_flat(
        p, g, s, lr, kind=kind, hyper=hyper, lm=1.0, wd=wd))

    def ref(p, g, s, lr):          # FusedFlatUpdater._bucket_fn's body
        new_p, new_s = o._update(p, g.astype(p.dtype), s, lr, 1.0, wd)
        return new_p.astype(p.dtype), new_s

    pa, sa = fused(p, g, dict(slots), lr)
    pb, sb = jax.jit(ref)(p, g, dict(slots), lr)
    assert pa.dtype == pb.dtype == p.dtype
    if dtype == "float32":
        assert_ulp(pa, pb, 8, f"{kind_name} params")
        # fma freedom touches only isolated elements — the overwhelming
        # majority must be bit-equal
        eq = (np.asarray(pa) == np.asarray(pb)).mean()
        assert eq > 0.999, eq
    else:  # bf16 rounding collapses sub-ulp fma differences
        assert (np.asarray(pa.astype(jnp.float32))
                == np.asarray(pb.astype(jnp.float32))).all()
    assert set(sa) == set(sb)
    for k in sa:
        if np.shape(sa[k]) == ():
            assert float(sa[k]) == float(sb[k]), k
        else:
            # b*m + (1-b)*g cancels where the two terms nearly offset; an
            # fma on one side then moves the result by ~eps * |term|, which
            # is unbounded in ULPs of a near-zero moment (jax 0.9.0's
            # XLA:CPU: 21-79 ulp at one element, 0-3 elsewhere). So the
            # bound is absolute in the terms' scale (|(1-b)*g| < 0.5), not
            # an ulp count pinned to one compiler's contraction choices.
            np.testing.assert_allclose(
                np.asarray(sa[k]), np.asarray(sb[k]), rtol=1e-6,
                atol=0.5 * np.finfo(np.float32).eps,
                err_msg=f"{kind_name} slot {k}")


@pytest.mark.parametrize("codec", ["int8_block", "fp8_block"])
@pytest.mark.parametrize("with_residual", [False, True])
def test_fused_dequant_update_matches_decode_then_update(codec,
                                                         with_residual):
    n, bs, world = 5000, 1024, 2
    rs = np.random.RandomState(3)
    flat = jnp.asarray(rs.randn(n), jnp.float32)
    scales = gc.block_scales(gc.block_absmax(flat, bs), codec)
    q = gc.block_encode(flat, scales, bs, codec)
    residual = (jnp.asarray(rs.randn(n), jnp.float32) * 1e-3
                if with_residual else None)
    lin = nn.Linear(4, 4)
    o = _optimizer("Adam", lin.parameters())
    kind, hyper = fu.rule_spec(o)
    p = jnp.asarray(rs.randn(n), jnp.float32)
    slots = _slots_for(o, n, 4)
    lr = jnp.asarray(1e-3, jnp.float32)

    fused = jax.jit(lambda p, q, s, sl, lr: fu.fused_dequant_update_flat(
        p, q, s, world, sl, lr, kind=kind, hyper=hyper, block_size=bs,
        residual=residual))

    def ref(p, q, s, sl, lr):
        g = gc.block_decode(q, s, world, jnp.float32, n)
        if residual is not None:
            g = (g.astype(jnp.float32) + residual).astype(jnp.float32)
        new_p, new_s = o._update(p, g.astype(p.dtype), sl, lr, 1.0, 0.0)
        return new_p.astype(p.dtype), new_s

    pa, sa = fused(p, q, scales, dict(slots), lr)
    pb, sb = jax.jit(ref)(p, q, scales, dict(slots), lr)
    assert_ulp(pa, pb, 8, "dequant params")
    for k in ("moment1", "moment2"):
        assert_ulp(sa[k], sb[k], 8, k)


def test_fused_dequant_ragged_block_size_falls_back():
    n, bs = 1000, 96          # 96 % 128 != 0 -> jnp decode + fused update
    rs = np.random.RandomState(5)
    flat = jnp.asarray(rs.randn(n), jnp.float32)
    scales = gc.block_scales(gc.block_absmax(flat, bs), "int8_block")
    q = gc.block_encode(flat, scales, bs, "int8_block")
    lin = nn.Linear(4, 4)
    o = _optimizer("SGD", lin.parameters())
    kind, hyper = fu.rule_spec(o)
    p = jnp.asarray(rs.randn(n), jnp.float32)
    lr = jnp.asarray(1e-3, jnp.float32)
    pa, _ = fu.fused_dequant_update_flat(p, q, scales, 2, {}, lr,
                                         kind=kind, hyper=hyper,
                                         block_size=bs)
    g = gc.block_decode(q, scales, 2, jnp.float32, n)
    pb, _ = o._update(p, g, {}, lr, 1.0, 0.0)
    assert_ulp(pa, pb.astype(p.dtype), 8)


def test_fused_updater_use_kernel_step_parity():
    """FusedFlatUpdater(use_kernel=True) vs the jnp path: bit-identical
    first step, ulp-bounded trajectory (fma freedom compounds across
    steps but never grows past a few ulp)."""
    rs = np.random.RandomState(0)
    x = paddle.to_tensor(rs.randn(16, 32).astype("f4"))

    def run(use_kernel, steps):
        paddle.seed(0)
        net = nn.Sequential(nn.Linear(32, 64), nn.ReLU(), nn.Linear(64, 8))
        o = opt.AdamW(learning_rate=1e-3, weight_decay=0.01,
                      parameters=net.parameters())
        from paddle_tpu.optimizer.fused import FusedFlatUpdater

        fused = FusedFlatUpdater(o, net.parameters(),
                                 use_kernel=use_kernel)
        for _ in range(steps):
            net(x).sum().backward()
            fused.step()
            for p in net.parameters():
                p.clear_gradient()
        return [np.asarray(p._value) for p in net.parameters()]

    for a, b in zip(run(False, 1), run(True, 1)):
        assert (a == b).all()          # single step: bit-identical
    for a, b in zip(run(False, 3), run(True, 3)):
        assert_ulp(a, b, 16, "3-step trajectory")


def test_fused_updater_kernel_sharded_step_parity(monkeypatch):
    """step_sharded (ZeRO-2 shape) with the kernel path computes the
    same owned-shard update as the jnp path — the padded-shard geometry
    goes through the same fused kernel."""
    from paddle_tpu.distributed import collective as coll
    from paddle_tpu.framework.tensor import Tensor
    from paddle_tpu.optimizer.fused import FusedFlatUpdater

    rs = np.random.RandomState(1)

    def run(use_kernel):
        paddle.seed(1)
        net = nn.Sequential(nn.Linear(16, 32), nn.ReLU(), nn.Linear(32, 4))
        o = opt.Adam(learning_rate=1e-3, parameters=net.parameters())
        params = [p for p in net.parameters() if not p.stop_gradient]
        fused = FusedFlatUpdater(o, params, use_kernel=use_kernel)
        g_rs = np.random.RandomState(2)
        for p in params:
            p.grad = Tensor(g_rs.standard_normal(p.shape)
                            .astype(np.float32) * 1e-2)
        shards = []

        def fake_all_gather(tl, t, group=None, **kw):
            # capture this rank's updated shard; hand back a full-size
            # buffer so _scatter_params can proceed
            shards.append(np.asarray(t._value))
            return Tensor(np.concatenate([np.asarray(t._value)] * 2),
                          _internal=True)

        monkeypatch.setattr(coll, "all_gather", fake_all_gather)
        fused.step_sharded(rank=0, world=2)
        return shards

    for a, b in zip(run(False), run(True)):
        assert_ulp(a, b, 8)


# ------------------------------------------------------------ codec kernels

@pytest.mark.parametrize("codec", ["int8_block", "fp8_block"])
@pytest.mark.parametrize("n,bs", [(5000, 1024), (128, 128), (777, 128),
                                  (4096, 512)])
def test_codec_kernels_bit_identical(codec, n, bs):
    rs = np.random.RandomState(n + bs)
    flat = jnp.asarray(rs.randn(n), jnp.float32)
    scales = gc.block_scales(gc.block_absmax(flat, bs), codec)
    qa = pc.block_encode(flat, scales, bs, codec)
    qb = gc.block_encode(flat, scales, bs, codec)
    assert qa.dtype == qb.dtype and qa.shape == qb.shape
    assert (np.asarray(qa) == np.asarray(qb)).all()
    da = pc.block_decode(qa, scales, 2, jnp.float32, n)
    db = gc.block_decode(qb, scales, 2, jnp.float32, n)
    assert (np.asarray(da) == np.asarray(db)).all()


def test_codec_ragged_block_size_falls_back_to_jnp():
    n, bs = 500, 96
    rs = np.random.RandomState(9)
    flat = jnp.asarray(rs.randn(n), jnp.float32)
    scales = gc.block_scales(gc.block_absmax(flat, bs), "int8_block")
    qa = pc.block_encode(flat, scales, bs, "int8_block")
    qb = gc.block_encode(flat, scales, bs, "int8_block")
    assert (np.asarray(qa) == np.asarray(qb)).all()
    da = pc.block_decode(qa, scales, 4, jnp.bfloat16, n)
    db = gc.block_decode(qb, scales, 4, jnp.bfloat16, n)
    assert (np.asarray(da.astype(jnp.float32))
            == np.asarray(db.astype(jnp.float32))).all()


def test_codec_kernels_under_shard_map():
    """world>1 wrap: the codec kernels run inside shard_map (where the
    traced ZeRO-2 path uses them on TPU) without vma/partitioning
    crashes, and match the jnp pair per shard."""
    from paddle_tpu.distributed import mesh as mesh_mod

    mesh = mesh_mod.build_mesh({"data": 2}, devices=jax.devices()[:2])
    from jax.sharding import PartitionSpec as P

    n, bs = 2048, 128
    rs = np.random.RandomState(11)
    flat = jnp.asarray(rs.randn(2 * n), jnp.float32)

    def per_shard(x):
        scales = gc.block_scales(gc.block_absmax(x, bs), "int8_block")
        q = pc.block_encode(x, scales, bs, "int8_block")
        return pc.block_decode(q, scales, 1, jnp.float32, n)

    out = mesh_mod.compat_shard_map(per_shard, mesh, (P("data"),),
                                    P("data"))(flat)

    def per_shard_ref(x):
        scales = gc.block_scales(gc.block_absmax(x, bs), "int8_block")
        q = gc.block_encode(x, scales, bs, "int8_block")
        return gc.block_decode(q, scales, 1, jnp.float32, n)

    ref = mesh_mod.compat_shard_map(per_shard_ref, mesh, (P("data"),),
                                    P("data"))(flat)
    assert (np.asarray(out) == np.asarray(ref)).all()


def test_fused_update_under_shard_map():
    from paddle_tpu.distributed import mesh as mesh_mod
    from jax.sharding import PartitionSpec as P

    mesh = mesh_mod.build_mesh({"data": 2}, devices=jax.devices()[:2])
    n = 1024
    rs = np.random.RandomState(12)
    p = jnp.asarray(rs.randn(2 * n), jnp.float32)
    g = jnp.asarray(rs.randn(2 * n), jnp.float32)
    lr = jnp.asarray(1e-3, jnp.float32)

    def shard_update(p, g):
        return fu.fused_update_flat(p, g, {}, lr, kind="sgd", hyper={})[0]

    out = mesh_mod.compat_shard_map(shard_update, mesh,
                                    (P("data"), P("data")),
                                    P("data"))(p, g)
    ref = np.asarray(p) - 1e-3 * np.asarray(g)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-6,
                               atol=1e-7)


# --------------------------------------------------------- flash attention

def _ref_attn(q, k, v, causal):
    import math

    scale = 1.0 / math.sqrt(q.shape[-1])
    logits = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    if causal:
        ql, kl = logits.shape[-2], logits.shape[-1]
        mask = jnp.tril(jnp.ones((ql, kl), bool), k=kl - ql)
        logits = jnp.where(mask, logits, -1e30)
    p = jax.nn.softmax(logits, -1)
    return jnp.einsum("bhqk,bkhd->bqhd", p,
                      v.astype(jnp.float32)).astype(q.dtype)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("dtype,tol", [("float32", 1e-5),
                                       ("bfloat16", 2e-2)])
@pytest.mark.parametrize("s,bq,bk", [(96, 32, 16), (160, 16, 32),
                                     (128, 64, 32)])
def test_flash_independent_blocks_grid(causal, dtype, tol, s, bq, bk):
    from paddle_tpu.ops.flash_attention import flash_attention_val

    rs = np.random.RandomState(s + bq)
    mk = lambda: jnp.asarray(rs.randn(2, s, 2, 32), jnp.dtype(dtype))
    q, k, v = mk(), mk(), mk()
    out = flash_attention_val(q, k, v, causal=causal, block_q=bq,
                              block_k=bk)
    ref = _ref_attn(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


def test_flash_supported_independent_blocks():
    from paddle_tpu.ops.flash_attention import flash_attention_supported

    assert flash_attention_supported((2, 96, 4, 64), block_q=32,
                                     block_k=16)
    assert not flash_attention_supported((2, 96, 4, 64), block_q=64,
                                         block_k=32)   # 96 % 64 != 0
    assert not flash_attention_supported((2, 96, 4, 64), block_q=32,
                                         block_k=7)    # < 8
    assert flash_attention_supported((2, 128, 4, 64))  # ladder path


def test_flash_tuned_dispatch_consults_cache():
    """A cache entry with an asymmetric (block_q, block_k) winner is
    applied under the flag (and produces reference numerics); an entry
    that no longer divides the live seq len falls back to the ladder."""
    from paddle_tpu.ops.flash_attention import (flash_attention_val,
                                                flash_block_choice)

    rs = np.random.RandomState(7)
    q = jnp.asarray(rs.randn(1, 128, 2, 32), jnp.float32)
    c = at.TuneCache()
    c.put(at.cache_key("flash_attention", (1, 128, 2, 32),
                       "float32-causal"),
          {"block_q": 32, "block_k": 64})
    flags.set_flags({"FLAGS_kernel_autotune": True})
    try:
        at.reset_runtime_cache(c)
        choice = flash_block_choice((1, 128, 2, 32))
        assert choice == {"block_q": 32, "block_k": 64, "source": "tuned"}
        out = flash_attention_val(q, q, q, causal=True)
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(_ref_attn(q, q, q, True)),
                                   rtol=1e-5, atol=1e-5)
        # 96 shares the 128 bucket but 96 % 64 != 0 -> ladder fallback
        q96 = jnp.asarray(rs.randn(1, 96, 2, 32), jnp.float32)
        choice96 = flash_block_choice((1, 96, 2, 32))
        assert choice96["source"] == "fallback"
        out96 = flash_attention_val(q96, q96, q96, causal=True)
        np.testing.assert_allclose(
            np.asarray(out96), np.asarray(_ref_attn(q96, q96, q96, True)),
            rtol=1e-5, atol=1e-5)
    finally:
        flags.set_flags({"FLAGS_kernel_autotune": False})
        at.reset_runtime_cache()


# ------------------------------------------------------------- quant_matmul

def test_quant_matmul_tuned_tiles_dispatch():
    from paddle_tpu.ops.quant_matmul import quant_matmul, quantize_int8

    rs = np.random.RandomState(2)
    x = jnp.asarray(rs.randn(128, 256).astype("f4"))
    w = jnp.asarray(rs.randn(256, 128).astype("f4"))
    qw, s = quantize_int8(w)
    ref = np.asarray(x) @ (np.asarray(qw, np.float32) * np.asarray(s))
    c = at.TuneCache()
    c.put(at.cache_key("quant_matmul", (128, 256, 128), jnp.float32),
          {"block_m": 64, "block_n": 64, "block_k": 128})
    flags.set_flags({"FLAGS_kernel_autotune": True})
    try:
        at.reset_runtime_cache(c)
        out = quant_matmul(x, qw, s)
        np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-4,
                                   atol=1e-3)
    finally:
        flags.set_flags({"FLAGS_kernel_autotune": False})
        at.reset_runtime_cache()
    out_def = quant_matmul(x, qw, s)
    np.testing.assert_allclose(np.asarray(out_def), ref, rtol=1e-4,
                               atol=1e-3)


def test_quantize_int8_stochastic_deterministic():
    """Same seed -> same int8 bits, on every call (the pltpu.prng path
    this replaces was backend-dependent and had no CPU lowering at
    all); different seeds -> different roundings; error stays bounded
    by one quantization step."""
    from paddle_tpu.ops.quant_matmul import quantize_int8

    rs = np.random.RandomState(4)
    w = jnp.asarray(rs.randn(64, 128).astype("f4"))
    qa, sa = quantize_int8(w, stochastic=True, seed=42)
    qb, _ = quantize_int8(w, stochastic=True, seed=42)
    qc, _ = quantize_int8(w, stochastic=True, seed=43)
    assert (np.asarray(qa) == np.asarray(qb)).all()
    assert not (np.asarray(qa) == np.asarray(qc)).all()
    deq = np.asarray(qa, np.float32) * np.asarray(sa)
    err = np.abs(deq - np.asarray(w))
    assert (err <= np.asarray(sa) + 1e-6).all()
    # unbiased-ish: mean error well under half a step
    assert abs((deq - np.asarray(w)).mean()) < float(np.asarray(sa).mean())


def test_quantize_int8_column_tiles_keep_the_bits():
    """The kernel grids over column tiles (here 3, the last one ragged);
    the noise counter is the GLOBAL flat index, so the int8 bits equal an
    untiled numpy evaluation of the same hash."""
    from paddle_tpu.ops.quant_matmul import quantize_int8

    k, n, seed = 1024, 640, 42           # bn = 256 at k = 1024
    w = np.random.RandomState(5).randn(k, n).astype("f4")
    q, s = quantize_int8(jnp.asarray(w), stochastic=True, seed=seed)
    with np.errstate(over="ignore"):
        h = (np.arange(k * n, dtype=np.uint32).reshape(k, n)
             * np.uint32(2654435761)) ^ np.uint32(seed)
        h ^= h >> 16
        h *= np.uint32(0x85EBCA6B)
        h ^= h >> 13
        h *= np.uint32(0xC2B2AE35)
        h ^= h >> 16
    u = (h >> 8).astype(np.float32) * np.float32(1.0 / (1 << 24))
    scale = np.asarray(s)
    np.testing.assert_allclose(
        scale, np.abs(w).max(0, keepdims=True) / 127.0, rtol=1e-6)
    want = np.clip(np.floor(w / scale + u), -127, 127).astype(np.int8)
    np.testing.assert_array_equal(np.asarray(q), want)
    # plain rounding through the same tiles
    q0, _ = quantize_int8(jnp.asarray(w))
    np.testing.assert_array_equal(
        np.asarray(q0), np.clip(np.round(w / scale), -127, 127))


def test_stable_seed_is_process_stable():
    from paddle_tpu.ops.quant_matmul import stable_seed

    assert stable_seed("linear_0.w_0") == stable_seed("linear_0.w_0")
    assert stable_seed("linear_0.w_0") != stable_seed("linear_1.w_0")
    # pinned crc32 value: would catch a regression back to the salted
    # builtin hash() (different every process) without a subprocess
    assert stable_seed("linear_0.w_0") == 354945823


def test_int8_linear_deterministic_across_conversions():
    from paddle_tpu.quantization import Int8Linear

    paddle.seed(7)
    lin = nn.Linear(32, 16)
    a = Int8Linear(lin, stochastic=True)
    b = Int8Linear(lin, stochastic=True)
    assert (np.asarray(a.qweight._value)
            == np.asarray(b.qweight._value)).all()


# --------------------------------------------------- inference int8 opt-in

def test_predictor_int8_weights_opt_in(tmp_path):
    """Config.enable_int8_weights: imported-model weights go int8 at
    rest (halved bytes, deterministic seeds) with small output error vs
    the fp predictor."""
    from paddle_tpu import inference
    from test_interop_importer import (A_INT, FEED_MINIBATCH, FETCH_LIST,
                                       attr, block_desc, lod_tensor_stream,
                                       op_desc, program_desc, var_desc)

    rs = np.random.RandomState(6)
    w1 = rs.randn(16, 32).astype("f4")
    w2 = rs.randn(32, 4).astype("f4")
    vars_ = [
        var_desc("feed", type_id=FEED_MINIBATCH, persistable=True),
        var_desc("fetch", type_id=FETCH_LIST, persistable=True),
        var_desc("x", dims=(-1, 16)),
        var_desc("w1", dims=(16, 32), persistable=True),
        var_desc("w2", dims=(32, 4), persistable=True),
        var_desc("h0", dims=(-1, 32)), var_desc("h1", dims=(-1, 32)),
        var_desc("out", dims=(-1, 4)),
    ]
    mulattrs = [attr("x_num_col_dims", A_INT, 1),
                attr("y_num_col_dims", A_INT, 1)]
    ops = [
        op_desc("feed", [("X", ["feed"])], [("Out", ["x"])],
                [attr("col", A_INT, 0)]),
        op_desc("mul", [("X", ["x"]), ("Y", ["w1"])], [("Out", ["h0"])],
                mulattrs),
        op_desc("relu", [("X", ["h0"])], [("Out", ["h1"])]),
        op_desc("mul", [("X", ["h1"]), ("Y", ["w2"])], [("Out", ["out"])],
                mulattrs),
        op_desc("fetch", [("X", ["out"])], [("Out", ["fetch"])],
                [attr("col", A_INT, 0)]),
    ]
    (tmp_path / "__model__").write_bytes(
        program_desc([block_desc(0, vars_, ops)]))
    with open(tmp_path / "__params__", "wb") as f:
        for arr in (w1, w2):        # combined persistables, sorted names
            f.write(lod_tensor_stream(arr))

    xs = rs.randn(8, 16).astype("f4")
    pred = inference.create_predictor(inference.Config(str(tmp_path)))
    ref = pred.run([xs])[0]

    cfg8 = inference.Config(str(tmp_path))
    cfg8.enable_int8_weights()
    assert cfg8.int8_weights()
    pred8 = inference.create_predictor(cfg8)
    art = pred8._artifact
    assert set(art._int8_dtypes) == {"w1", "w2"}
    for name in art._int8_dtypes:
        q, s = art._params[name]
        assert q.dtype == jnp.int8
    out = pred8.run([xs])[0]
    rel = np.abs(out - ref).mean() / (np.abs(ref).mean() + 1e-9)
    assert rel < 0.05, rel
