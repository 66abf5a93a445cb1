"""Flash attention as a Pallas TPU kernel (forward + backward).

Capability parity: the reference's fused CUDA attention
(paddle/fluid/operators/fused/fused_attention_op.cu, fmha_ref.h) — here
re-designed for the TPU memory hierarchy: the kv dimension is the innermost
grid axis, so k/v blocks stream HBM→VMEM with automatic double-buffering,
online-softmax state lives in VMEM scratch across grid steps and the [s, s]
score matrix never exists in HBM. Causal upper-triangle blocks are
predicated off with @pl.when.

Precision follows the caller's dtype, with no switch. The MXU's operands —
q (times the softmax scale, rounded once), k, v, dO, and the probabilities
p and ds where they enter the second matmuls — are in the dtype q, k, v
arrive in: bf16 callers get bf16 x bf16 dots, fp32 callers fp32 ones. What
is float32 whatever comes in: every dot's accumulation
(preferred_element_type), the scores and the mask, the softmax statistics
m, l, lse and delta, exp, dp - delta, and the three accumulators. (On the
chip this states what Mosaic did already: at default precision it feeds an
fp32 operand to the MXU as one bf16 pass, so widening the blocks first
bought no precision and cost no time — PERF.md, PR 26.)

Layout is [b, n, s, d] inside the kernels (head-major, contiguous (s, d)
tiles per grid cell); the public entry takes the model's [b, s, n, d] and
transposes (XLA fuses the transposes into the surrounding program).

q and k share one width d_qk and v has its own d_v (latent attention's
heads are 192 / 128 wide): the scores contract d_qk and are scaled by
1/sqrt(d_qk); o, dO, dv and the forward accumulator are d_v wide, dq and dk
d_qk wide. Nothing is padded to the wider of the two, in HBM or in VMEM.
With d_qk == d_v the kernels are the ones they were.

Backward uses the standard two-kernel flash decomposition:
  dq kernel:  grid (b, n, q_blocks, kv_blocks), dq accumulates in scratch
  dkv kernel: grid (b, n, kv_blocks, q_blocks), dk/dv accumulate in scratch,
              on the transposed score block (see _dkv_kernel)
with delta = rowsum(dO * O) precomputed outside (one fused elementwise pass).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = float(-1e30)
_LANES = 128  # m/l scratch lane width (min f32 tile is (8, 128))


def _pick_block(s: int, want: int) -> int:
    """The pre-tuner preference ladder: largest power-of-two block <= want
    that divides s. The FALLBACK when the tune cache has no validated
    winner for the shape (and the whole story when FLAGS_kernel_autotune
    is off)."""
    for b in (want, 1024, 512, 256, 128, 64, 32, 16, 8):
        if b <= want and s % b == 0 and b <= s:
            return b
    return 0


def _default_block(d: int, dtype, d_v: int = None) -> int:
    """Where the ladder starts when the caller names no block: 1024, or 512
    where a row of the tile (d elements of dtype, the wider of d_qk and
    d_v) is wider than 512 bytes.

    From the chip sweep of PR 26 over {256, 512, 1024} x {256, 512, 1024}
    (v5e, bf16, s1024 and s2048 at d=64, s2048 at d=128; PERF.md section
    6): the kernels' time goes with the number of grid steps and of score
    ROWS a step handles (the row max and sum, the (BQ, 1) statistics
    spread over the lanes), hardly with the block's area, so one (1024,
    1024) block beats the three of four (512, 512) blocks under the causal
    diagonal by 1.33-1.39x over forward + backward although it computes
    the masked quarter too. 2048 does not fit VMEM in the backward
    kernels, nor does 1024 with fp32 rows of d=256 (the TPU compiler,
    without the chip): hence the bound on the row's bytes.
    Latent attention's q, k 192 / v 128 wide in bf16 (a 384-byte row) stay
    at 1024 by the same rule and by PR 27's sweep at s=8192, 32 heads (ms a
    call, forward / dq + dkv, ten calls chained in one jit): 1024x1024
    8.2 / 22.5; 512x1024 9.7 / 23.8; 1024x512 13.5 / 24.1; 512x512 13.8 /
    25.3; 2048 runs out of VMEM in the dq kernel."""
    return 1024 if max(d, d_v or d) * jnp.dtype(dtype).itemsize <= 512 \
        else 512


def _tuned_blocks(shape, dtype, causal: bool, want, d_v: int = None):
    """(block_q, block_k) for a [b, s, n, d_qk] call (v `d_v` wide): the tuner cache's
    validated winner under FLAGS_kernel_autotune when it still fits the
    concrete sequence length, else the _pick_block ladder pair from
    ``want`` (None: from _default_block of the head size and dtype). The
    independent q/k blocks are the point — the cache may hold an
    asymmetric winner the ladder can never produce."""
    s = int(shape[1])
    want = want or _default_block(int(shape[3]), dtype, d_v)
    from .pallas import autotune as _at

    params = _at.lookup(
        "flash_attention", tuple(int(x) for x in shape),
        f"{jnp.dtype(dtype)}-{'causal' if causal else 'full'}")
    if params:
        bq = int(params.get("block_q", 0))
        bk = int(params.get("block_k", 0))
        if bq >= 8 and bk >= 8 and s % bq == 0 and s % bk == 0:
            return bq, bk, "tuned"
        # tuned entry no longer fits this concrete shape (bucket
        # collision): fall back loudly in the dispatch counter
        _at.count_dispatch("flash_attention", "fallback")
        blk = _pick_block(s, want)
        return blk, blk, "fallback"
    blk = _pick_block(s, want)
    return blk, blk, "default"


def flash_block_choice(shape, dtype="float32", causal=True,
                       block_size=None) -> dict:
    """What dispatch would run for this [b, s, n, d] call, so that a
    reading can say WHICH tiles produced it:
    {"block_q", "block_k", "source"}."""
    bq, bk, source = _tuned_blocks(tuple(shape), dtype, bool(causal),
                                   block_size)
    return {"block_q": int(bq), "block_k": int(bk), "source": source}


def flash_attention_supported(q_shape, block: int = 512,
                              block_q: int = None,
                              block_k: int = None) -> bool:
    """True if the kernel can handle this [b, s, n, d] shape. With
    explicit ``block_q``/``block_k`` the check honors the independent
    tiles (s must divide by BOTH); with neither, the ladder must find a
    block <= ``block``."""
    if len(q_shape) != 4:
        return False
    s = int(q_shape[1])
    if block_q is not None or block_k is not None:
        bq = int(block_q or block)
        bk = int(block_k or block)
        return (bq >= 8 and bk >= 8 and bq <= s and bk <= s
                and s % bq == 0 and s % bk == 0)
    return _pick_block(s, block) >= 8


def _interpret() -> bool:
    from ..framework.target import target_platform

    return target_platform() != "tpu"


def _causal_mask(s_blk, qi, ki, block_q, block_k, transposed=False):
    """s_blk is (BQ, BK), or (BK, BQ) when transposed."""
    q_pos = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, s_blk.shape, 1 if transposed else 0)
    k_pos = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, s_blk.shape, 0 if transposed else 1)
    return jnp.where(q_pos >= k_pos, s_blk, NEG_INF)


def _scaled(q, scale):
    """The q tile times the softmax scale, in q's own dtype: the product is
    made in fp32 and rounded once (exact where the scale is a power of two,
    as at d=64), so the MXU takes it as it takes k, v and dO."""
    return (q.astype(jnp.float32) * scale).astype(q.dtype)


# ---------------------------------------------------------------------------
# forward — grid (b, n, q_blocks, kv_blocks), kv innermost
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref,
                *, scale, causal, block_q, block_k):
    qi, ki = pl.program_id(2), pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # causal: the block computes only if some q_pos >= some k_pos
    run = (qi * block_q + block_q - 1 >= ki * block_k) if causal else True

    @pl.when(run)
    def _compute():
        q = _scaled(q_ref[0, 0, :, :], scale)                    # (BQ, d)
        kb = k_ref[0, 0, :, :]                                   # (BK, d)
        vb = v_ref[0, 0, :, :]
        s_blk = jax.lax.dot_general(
            q, kb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)                  # (BQ, BK)
        if causal:
            s_blk = _causal_mask(s_blk, qi, ki, block_q, block_k)
        m_prev = m_ref[:, :1]                                    # (BQ, 1)
        l_prev = l_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s_blk, -1, keepdims=True))
        p = jnp.exp(s_blk - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + jnp.sum(p, -1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(vb.dtype), vb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)                  # (BQ, d)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(ki == nk - 1)
    def _finish():
        l = jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[0, 0, :, :] = (acc_ref[...] / l).astype(o_ref.dtype)
        lse_ref[0, 0, :, :] = m_ref[:, :1] + jnp.log(l)


def _sds(shape, dtype, like):
    """Out ShapeDtypeStruct carrying `like`'s varying-mesh-axes set, so the
    pallas_call stays legal inside vma-tracked shard_map regions (the 1F1B
    pipeline, ring attention's manual block, the traced ZeRO-2
    reduce_scatter path)."""
    return jax.ShapeDtypeStruct(shape, dtype, vma=jax.typeof(like).vma)


def _fwd(q, k, v, causal, block_q, block_k):
    b, n, s, d = q.shape
    d_v = v.shape[-1]
    grid = (b, n, s // block_q, s // block_k)
    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=1.0 / math.sqrt(d),
                          causal=causal, block_q=block_q, block_k=block_k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda bi, hi, qi, ki: (bi, hi, ki, 0)),
            pl.BlockSpec((1, 1, block_k, d_v),
                         lambda bi, hi, qi, ki: (bi, hi, ki, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, d_v),
                         lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, block_q, 1),
                         lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
        ],
        out_shape=[
            _sds((b, n, s, d_v), q.dtype, q),
            _sds((b, n, s, 1), jnp.float32, q),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, d_v), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
        ],
        interpret=_interpret(),
        name="flash_fwd",
    )(q, k, v)
    return out, lse


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------

def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               acc_ref, *, scale, causal, block_q, block_k):
    qi, ki = pl.program_id(2), pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    run = (qi * block_q + block_q - 1 >= ki * block_k) if causal else True

    @pl.when(run)
    def _compute():
        q = _scaled(q_ref[0, 0, :, :], scale)                    # (BQ, d)
        kb = k_ref[0, 0, :, :]                                   # (BK, d)
        vb = v_ref[0, 0, :, :]
        do = do_ref[0, 0, :, :]
        lse = lse_ref[0, 0, :, :]                                # (BQ, 1)
        delta = delta_ref[0, 0, :, :]
        s_blk = jax.lax.dot_general(
            q, kb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        if causal:
            s_blk = _causal_mask(s_blk, qi, ki, block_q, block_k)
        p = jnp.exp(s_blk - lse)                                 # (BQ, BK)
        dp = jax.lax.dot_general(
            do, vb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        acc_ref[...] += jax.lax.dot_general(
            ds.astype(kb.dtype), kb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _finish():
        dq_ref[0, 0, :, :] = (acc_ref[...] * scale).astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_acc, dv_acc, *, scale, causal,
                block_q, block_k):
    """Works on the TRANSPOSED score block s^T = k q^T, (BK, BQ), so that
    dv += p^T dO and dk += ds^T q are plain row-by-column products and the
    row statistics come as (1, BQ) rows, spread down the sublanes. From an
    untransposed p both products contract dim 0 of both operands and lse
    and delta are (BQ, 1) columns spread over the lanes: 18-23 % slower at
    block 512 on the v5e, 2-10 % at 1024 (PERF.md, PR 26)."""
    ki, qi = pl.program_id(2), pl.program_id(3)
    nq = pl.num_programs(3)

    @pl.when(qi == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    run = (qi * block_q + block_q - 1 >= ki * block_k) if causal else True

    @pl.when(run)
    def _compute():
        q = _scaled(q_ref[0, 0, :, :], scale)                    # (BQ, d)
        kb = k_ref[0, 0, :, :]                                   # (BK, d)
        vb = v_ref[0, 0, :, :]
        do = do_ref[0, 0, :, :]
        lse = lse_ref[0, 0, 0, :, :]                             # (1, BQ)
        delta = delta_ref[0, 0, 0, :, :]
        st = jax.lax.dot_general(
            kb, q, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)                  # (BK, BQ)
        if causal:
            st = _causal_mask(st, qi, ki, block_q, block_k, transposed=True)
        pt = jnp.exp(st - lse)
        dv_acc[...] += jax.lax.dot_general(
            pt.astype(do.dtype), do, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)                  # (BK, d)
        dpt = jax.lax.dot_general(
            vb, do, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)                  # (BK, BQ)
        dst = pt * (dpt - delta)
        # q was pre-scaled, so dk already carries `scale`
        dk_acc[...] += jax.lax.dot_general(
            dst.astype(q.dtype), q, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)                  # (BK, d)

    @pl.when(qi == nq - 1)
    def _finish():
        dk_ref[0, 0, :, :] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0, 0, :, :] = dv_acc[...].astype(dv_ref.dtype)


def _bwd(q, k, v, out, lse, do, causal, block_q, block_k):
    b, n, s, d = q.shape
    d_v = v.shape[-1]
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1, keepdims=True)                      # (b, n, s, 1)

    def tile(rows, width, on_q, kv_major=False):
        """A (1, 1, rows, width) tile that follows the q block (`on_q`) or
        the kv block; `kv_major` for the grid whose third axis is kv."""
        if kv_major:
            return pl.BlockSpec((1, 1, rows, width),
                                lambda bi, hi, ki, qi: (
                                    bi, hi, qi if on_q else ki, 0))
        return pl.BlockSpec((1, 1, rows, width),
                            lambda bi, hi, qi, ki: (
                                bi, hi, qi if on_q else ki, 0))

    qb, dob = tile(block_q, d, True), tile(block_q, d_v, True)
    kb_, vb_ = tile(block_k, d, False), tile(block_k, d_v, False)
    rowb = pl.BlockSpec((1, 1, block_q, 1),
                        lambda bi, hi, qi, ki: (bi, hi, qi, 0))

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=1.0 / math.sqrt(d), causal=causal,
                          block_q=block_q, block_k=block_k),
        grid=(b, n, s // block_q, s // block_k),
        in_specs=[qb, kb_, vb_, dob, rowb, rowb],
        out_specs=qb,
        out_shape=_sds((b, n, s, d), q.dtype, q),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=_interpret(),
        name="flash_bwd_dq",
    )(q, k, v, do, lse, delta)

    # dkv: grid (b, n, kv_blocks, q_blocks) — q innermost. lse and delta go
    # in as one (1, BQ) row per q block: a block that spans its array's last
    # two dims whole is legal for every block_q
    qb2, dob2 = tile(block_q, d, True, True), tile(block_q, d_v, True, True)
    kb2, vb2 = tile(block_k, d, False, True), tile(block_k, d_v, False, True)
    rowb2 = pl.BlockSpec((1, 1, 1, 1, block_q),
                         lambda bi, hi, ki, qi: (bi, hi, qi, 0, 0))
    rows = (b, n, s // block_q, 1, block_q)
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=1.0 / math.sqrt(d),
                          causal=causal, block_q=block_q, block_k=block_k),
        grid=(b, n, s // block_k, s // block_q),
        in_specs=[qb2, kb2, vb2, dob2, rowb2, rowb2],
        out_specs=[kb2, vb2],
        out_shape=[_sds((b, n, s, d), k.dtype, k),
                   _sds((b, n, s, d_v), v.dtype, v)],
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d_v), jnp.float32)],
        interpret=_interpret(),
        name="flash_bwd_dkv",
    )(q, k, v, do, lse.reshape(rows), delta.reshape(rows))
    return dq, dk, dv


# ---------------------------------------------------------------------------
# custom-vjp wrapper, [b, n, s, d]
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash_bnsd(q, k, v, causal, block_q, block_k):
    out, _ = _fwd(q, k, v, causal, block_q, block_k)
    return out


def _flash_fwd_rule(q, k, v, causal, block_q, block_k):
    out, lse = _fwd(q, k, v, causal, block_q, block_k)
    return out, (q, k, v, out, lse)


def _flash_bwd_rule(causal, block_q, block_k, res, do):
    q, k, v, out, lse = res
    return _bwd(q, k, v, out, lse, do, causal, block_q, block_k)


_flash_bnsd.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def flash_attention_val(q, k, v, causal=True, block_size=None,
                        block_q=None, block_k=None):
    """Causal flash attention on q, k [b, s, n, d_qk] and v [b, s, n, d_v]
    → [b, s, n, d_v], the scores scaled by 1/sqrt(d_qk).

    Value-level (raw jax arrays); Tensor-level wrappers live in
    nn/functional/flash_attention.py. Fallback is the caller's job —
    check flash_attention_supported() first. Explicit ``block_q`` /
    ``block_k`` pin the tiles (both must divide s); otherwise dispatch
    consults the autotune cache under FLAGS_kernel_autotune and falls
    back to the ``_pick_block`` ladder from ``block_size`` (None: from
    ``_default_block`` of the head size and dtype).

    The result and the gradients have the inputs' dtype, and so have the
    operands of every matmul inside; accumulation and the softmax
    statistics are float32 for every input dtype (module docstring).
    """
    b, s, n, d = q.shape
    d_v = int(v.shape[-1])
    if k.shape != q.shape or v.shape[:3] != q.shape[:3]:
        raise ValueError(
            f"flash attention: q {q.shape} and k {k.shape} must agree, and "
            f"v {v.shape} with them in all but the head size")
    if block_q is not None or block_k is not None:
        other = block_size or _default_block(d, q.dtype, d_v)
        bq = int(block_q or other)
        bk = int(block_k or other)
        if not flash_attention_supported(q.shape, block_q=bq, block_k=bk):
            raise ValueError(
                f"flash attention: blocks ({bq}, {bk}) invalid for seq "
                f"len {s} (both must divide it and be >= 8)")
    else:
        bq, bk, _src = _tuned_blocks(q.shape, q.dtype, bool(causal),
                                     block_size, d_v)
        if bq < 8 or bk < 8:
            raise ValueError(
                f"flash attention: no valid block for seq len {s}")
    qt = jnp.transpose(q, (0, 2, 1, 3))
    kt = jnp.transpose(k, (0, 2, 1, 3))
    vt = jnp.transpose(v, (0, 2, 1, 3))
    out = _flash_bnsd(qt, kt, vt, bool(causal), bq, bk)
    return jnp.transpose(out, (0, 2, 1, 3))


def _mesh_flash_specs(shape):
    """(mesh_active, mesh, PartitionSpec) for running the kernel under the
    ambient framework mesh. mesh_active False → call directly (no mesh);
    True with spec None → a mesh IS active but the shape is unshardable
    (the kernel must NOT run — Mosaic custom calls cannot be
    auto-partitioned by GSPMD; under a mesh the kernel must go through
    shard_map with batch over the dp/ZeRO axes and heads over 'model')."""
    from ..distributed import mesh as mesh_mod
    from ..distributed.mesh import AXIS_DATA, AXIS_MODEL, AXIS_SHARD

    m = mesh_mod.get_mesh()
    if m is None or m.size <= 1:
        return False, None, None
    from jax.sharding import PartitionSpec as P

    b, s, n, d = shape
    batch_axes = tuple(a for a in (AXIS_DATA, AXIS_SHARD)
                       if a in m.axis_names and m.shape[a] > 1)
    head_ax = (AXIS_MODEL if AXIS_MODEL in m.axis_names
               and m.shape[AXIS_MODEL] > 1 else None)
    bdeg = 1
    for a in batch_axes:
        bdeg *= m.shape[a]
    ndeg = m.shape[head_ax] if head_ax else 1
    if b % bdeg or n % ndeg:
        return True, None, None  # unshardable shape under this mesh
    if not flash_attention_supported((b // bdeg, s, n // ndeg, d)):
        return True, None, None  # per-shard shape defeats the kernel
    return True, m, P(batch_axes or None, None, head_ax, None)


def flash_attention_sharded_ok(shape) -> bool:
    """Can flash_attention_val_auto run this [b, s, n, d] shape — on the
    ambient mesh if one is active, directly otherwise?"""
    active, mesh, _spec = _mesh_flash_specs(tuple(shape))
    if not active:
        return flash_attention_supported(tuple(shape))
    return mesh is not None


def flash_attention_val_auto(q, k, v, causal=True, block_size=None):
    """flash_attention_val that is safe under an active mesh: wraps the
    pallas call in shard_map with batch/head partitioning so GSPMD never
    sees an unpartitionable Mosaic call. Check flash_attention_sharded_ok
    first; raises ValueError (not an opaque Mosaic compile crash) when a
    mesh is active but the shape cannot be sharded onto it."""
    active, mesh, spec = _mesh_flash_specs(q.shape)
    if not active:
        return flash_attention_val(q, k, v, causal=causal,
                                   block_size=block_size)
    if mesh is None:
        raise ValueError(
            f"flash attention shape {tuple(q.shape)} cannot be sharded "
            f"onto the active mesh — batch/heads must divide the "
            f"data*sharding / model degrees (check "
            f"flash_attention_sharded_ok first)")
    fn = functools.partial(flash_attention_val, causal=causal,
                           block_size=block_size)
    from ..distributed import mesh as mesh_mod

    return mesh_mod.compat_shard_map(fn, mesh, (spec, spec, spec),
                                     spec)(q, k, v)
