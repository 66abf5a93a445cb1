"""Pallas flash attention: kernel numerics vs the einsum reference path.

Runs on the CPU interpret mode (conftest forces the 8-device CPU platform);
the same kernel compiles for TPU via Mosaic. Reference capability:
operators/fused/fused_attention_op.cu (fused CUDA attention fwd+bwd).
"""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops.flash_attention import (
    flash_attention_supported, flash_attention_val,
)


def ref_attn(q, k, v, causal=True):
    scale = 1.0 / math.sqrt(q.shape[-1])
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if causal:
        ql, kl = logits.shape[-2], logits.shape[-1]
        mask = jnp.tril(jnp.ones((ql, kl), bool), k=kl - ql)
        logits = jnp.where(mask, logits, -1e30)
    p = jax.nn.softmax(logits.astype(jnp.float32), -1).astype(v.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def _rand(b, s, n, d, seed=0):
    rs = np.random.RandomState(seed)
    mk = lambda: jnp.asarray(rs.randn(b, s, n, d), jnp.float32)
    return mk(), mk(), mk()


@pytest.mark.parametrize("causal", [True, False])
def test_forward_matches_reference(causal):
    q, k, v = _rand(2, 128, 4, 64)
    out = flash_attention_val(q, k, v, causal=causal, block_size=64)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(ref_attn(q, k, v, causal)),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_gradients_match_reference(causal):
    q, k, v = _rand(2, 64, 2, 32, seed=1)

    def f_flash(q, k, v):
        return jnp.sum(jnp.sin(
            flash_attention_val(q, k, v, causal=causal, block_size=32)))

    def f_ref(q, k, v):
        return jnp.sum(jnp.sin(ref_attn(q, k, v, causal)))

    g1 = jax.grad(f_flash, (0, 1, 2))(q, k, v)
    g2 = jax.grad(f_ref, (0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


def test_uneven_q_k_blocks():
    # block_q != block divisor of s exercises the diagonal masking path
    q, k, v = _rand(1, 96, 2, 32, seed=2)
    out = flash_attention_val(q, k, v, causal=True, block_size=32)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(ref_attn(q, k, v, True)),
                               rtol=1e-5, atol=1e-5)


def test_supported_shapes():
    assert flash_attention_supported((2, 128, 4, 64))
    assert flash_attention_supported((2, 96, 4, 64))   # 32-divisible
    assert not flash_attention_supported((2, 7, 4, 64))
    assert not flash_attention_supported((2, 128, 64))  # wrong rank


def test_public_api():
    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F

    rs = np.random.RandomState(3)
    q = paddle.to_tensor(rs.randn(2, 64, 2, 32).astype("float32"))
    q.stop_gradient = False
    out, sm = F.flash_attention(q, q, q, causal=True)
    assert sm is None
    assert tuple(out.shape) == (2, 64, 2, 32)
    out.sum().backward()
    assert q.grad is not None


def test_jit_under_mesh():
    # flash path with a mesh active must stay SPMD via shard_map
    from paddle_tpu.distributed import mesh as mesh_mod
    from paddle_tpu.models.gpt import _flash_sharded

    mesh = mesh_mod.build_mesh({"data": 2, "model": 2},
                               devices=jax.devices()[:4])
    prev = mesh_mod.get_mesh()
    mesh_mod.set_mesh(mesh)
    try:
        q, k, v = _rand(2, 64, 4, 32, seed=4)
        out = jax.jit(_flash_sharded)(q, k, v)
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(ref_attn(q, k, v, True)),
                                   rtol=1e-5, atol=1e-5)
    finally:
        mesh_mod.set_mesh(prev)


# ---------------------------------------------------------------------------
# operand dtype: the MXU takes the caller's dtype, the statistics stay fp32
# ---------------------------------------------------------------------------

# bf16 keeps 8 bits: one rounding is at most 2^-9 = 2e-3 of the value. The
# largest errors seen here are the result's own rounding (0.02 at |dv| = 5.5,
# 0.008 at |out| = 3.3), the roundings inside (the scaled q tile, p, ds)
# average out over the contraction. 1e-2 + 1e-2 |x| bounds them with room and
# is half the autotune family's validation tolerance, the ceiling (2e-2)
BF16_TOL = dict(rtol=1e-2, atol=1e-2)

# (head dim, block_q, block_k): both published head sizes, block_q != block_k
# both ways round, so the diagonal crosses blocks that are not square
DTYPE_CASES = [(64, 64, 32), (128, 32, 64)]


def _f32(x):
    return np.asarray(x.astype(jnp.float32))


def _loss(attn, q, k, v):
    return jnp.sum(jnp.sin(attn(q, k, v).astype(jnp.float32)))


def _flash_and_reference_grads(q, k, v, causal, bq, bk):
    """(out, dq, dk, dv) of the kernel on q, k, v as given, and of the
    reference on the same values widened to fp32: the reference sees the
    rounded inputs, so the comparison is of the arithmetic alone."""
    flash = lambda a, b, c: flash_attention_val(a, b, c, causal=causal,
                                                block_q=bq, block_k=bk)
    ref = lambda a, b, c: ref_attn(a, b, c, causal)
    wide = [x.astype(jnp.float32) for x in (q, k, v)]
    got = (flash(q, k, v),) + jax.grad(
        lambda *a: _loss(flash, *a), (0, 1, 2))(q, k, v)
    want = (ref(*wide),) + jax.grad(
        lambda *a: _loss(ref, *a), (0, 1, 2))(*wide)
    return got, want


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("d,bq,bk", DTYPE_CASES)
def test_bf16_forward_matches_fp32_reference(causal, d, bq, bk):
    q, k, v = (x.astype(jnp.bfloat16) for x in _rand(2, 128, 2, d, seed=5))
    out = flash_attention_val(q, k, v, causal=causal, block_q=bq, block_k=bk)
    assert out.dtype == jnp.bfloat16
    want = ref_attn(*(x.astype(jnp.float32) for x in (q, k, v)), causal)
    np.testing.assert_allclose(_f32(out), np.asarray(want), **BF16_TOL)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("d,bq,bk", DTYPE_CASES)
def test_bf16_gradients_match_fp32_reference(causal, d, bq, bk):
    q, k, v = (x.astype(jnp.bfloat16) for x in _rand(2, 128, 2, d, seed=6))
    got, want = _flash_and_reference_grads(q, k, v, causal, bq, bk)
    for name, a, b in zip(("out", "dq", "dk", "dv"), got, want):
        assert a.dtype == jnp.bfloat16, name
        np.testing.assert_allclose(_f32(a), np.asarray(b), err_msg=name,
                                   **BF16_TOL)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("d,bq,bk", DTYPE_CASES)
def test_fp32_inputs_keep_fp32_tolerances(causal, d, bq, bk):
    # fp32 callers get fp32 dots: the tolerances of the first two tests
    # above, at the head sizes and uneven blocks of the bf16 cases
    q, k, v = _rand(2, 128, 2, d, seed=7)
    got, want = _flash_and_reference_grads(q, k, v, causal, bq, bk)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]),
                               rtol=1e-5, atol=1e-5)
    for a, b in zip(got[1:], want[1:]):
        assert a.dtype == jnp.float32
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


def _eqns(jaxpr):
    """Every equation of a jaxpr, those of nested jaxprs (pl.when's cond
    branches, the kernel body of a pallas_call) included."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _eqns(inner)


def _grad_jaxpr(dtype, **blocks):
    """The jaxpr of all three gradients at q, k, v [1, 64, 2, 64]."""
    q = jnp.zeros((1, 64, 2, 64), dtype)
    grads = jax.grad(
        lambda a, b, c: jnp.sum(flash_attention_val(
            a, b, c, causal=True, **blocks).astype(jnp.float32)), (0, 1, 2))
    return jax.make_jaxpr(grads)(q, q, q).jaxpr


def _kernel_bodies(dtype):
    """The pallas_call equations of forward + backward, by name."""
    return {e.params["name"]: e
            for e in _eqns(_grad_jaxpr(dtype, block_q=32, block_k=16))
            if e.primitive.name == "pallas_call"}


@pytest.mark.parametrize("kernel,n_dots", [("flash_fwd", 2),
                                           ("flash_bwd_dkv", 5)])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_kernel_dots_take_the_input_dtype(kernel, n_dots, dtype):
    """No dot in a kernel body widens its operands: for bf16 inputs none
    takes a float32 operand, and every one accumulates in float32. The
    statistics (lse, delta) and the scratch accumulators, the backward's
    whole-sequence dq accumulator among them, are float32 whatever comes
    in."""
    call = _kernel_bodies(dtype)[kernel]
    dots = [e for e in _eqns(call.params["jaxpr"])
            if e.primitive.name == "dot_general"]
    assert len(dots) == n_dots
    for dot in dots:
        assert [v.aval.dtype for v in dot.invars] == [dtype, dtype], dot
        assert dot.params["preferred_element_type"] == jnp.float32
    refs = [v.aval for v in call.params["jaxpr"].invars]
    mapping = call.params["grid_mapping"]
    scratch = refs[len(refs) - mapping.num_scratch_operands:]
    assert scratch and all(r.dtype == jnp.float32 for r in scratch), scratch
    if kernel == "flash_bwd_dkv":
        # dq's accumulator holds every q block of the head: 64 rows here
        assert math.prod(scratch[0].shape) == 64 * 64, scratch[0]
    # lse and delta blocks: (BQ, 1) columns forward, (1, BQ) rows backward
    stats = [r for r in refs if 1 in r.shape[-2:]]
    assert stats and all(r.dtype == jnp.float32 for r in stats), stats


def test_backward_is_one_kernel_of_five_products():
    """s^T and dp^T are made once: the backward is ONE pallas_call, named
    as the benchmark's readers find it, with five dot_generals (s^T, dv,
    dp^T, dk, dq) where two kernels made seven."""
    bwd = [e for e in _eqns(_grad_jaxpr(jnp.bfloat16, block_q=16,
                                        block_k=32))
           if e.primitive.name == "pallas_call"
           and e.params["name"] != "flash_fwd"]
    assert [e.params["name"] for e in bwd] == ["flash_bwd_dkv"]
    assert sum(e.primitive.name == "dot_general"
               for e in _eqns(bwd[0].params["jaxpr"])) == 5


# dq accumulates across kv blocks (the OUTER grid axis) while q is the inner
# one: s = 256 in 4 x 8 and 8 x 4 blocks, so every q block's accumulator is
# revisited once per kv block, with block_q != block_k both ways round
MANY_BLOCKS = [(64, 32), (32, 64)]
WIDTHS = [(32, 32), (48, 32)]             # d_qk = d_v, and 192 / 128 in small


def _rand_qkv(s, d_qk, d_v, seed):
    rs = np.random.RandomState(seed)
    mk = lambda d: jnp.asarray(rs.randn(1, s, 2, d), jnp.float32)
    return mk(d_qk), mk(d_qk), mk(d_v)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("d_qk,d_v", WIDTHS)
@pytest.mark.parametrize("bq,bk", MANY_BLOCKS)
@pytest.mark.parametrize("dtype,tol", [
    (jnp.bfloat16, BF16_TOL), (jnp.float32, dict(rtol=1e-4, atol=1e-4))])
def test_gradients_over_many_blocks(dtype, tol, causal, d_qk, d_v, bq, bk):
    q, k, v = (x.astype(dtype) for x in _rand_qkv(256, d_qk, d_v, 8))
    got, want = _flash_and_reference_grads(q, k, v, causal, bq, bk)
    for name, a, b in zip(("out", "dq", "dk", "dv"), got, want):
        assert a.dtype == dtype and a.shape == b.shape, name
        np.testing.assert_allclose(_f32(a), np.asarray(b), err_msg=name,
                                   **tol)


def test_supported_up_to_the_dq_accumulators_budget():
    """The backward keeps a head's whole dq in VMEM: a sequence whose
    accumulator passes the budget is not supported, and callers take their
    other path."""
    from paddle_tpu.ops import flash_attention as fa

    assert flash_attention_supported((1, 8192, 32, 192))   # the 8k cell
    assert flash_attention_supported((6, 2048, 12, 64))
    longest = fa._DQ_ACC_BYTES // (4 * 192)      # s x d_qk float32
    assert flash_attention_supported((1, longest, 32, 192))
    assert not flash_attention_supported((1, 2 * longest, 32, 192))
    assert flash_attention_supported((1, 2 * longest, 32, 64))
    assert not flash_attention_supported((1, 4 * longest, 32, 64))
    assert not flash_attention_supported((1, 2 * longest, 32, 192),
                                         block_q=512, block_k=512)
