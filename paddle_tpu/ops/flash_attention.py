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
m, l, lse and delta, exp, dp - delta, and the four accumulators. (On the
chip this states what Mosaic did already: at default precision it feeds an
fp32 operand to the MXU as one bf16 pass, so widening the blocks first
bought no precision and cost no time — PERF.md, PR 26.)

Layout is [b, n, s, d] inside the kernels (head-major, contiguous (s, d)
tiles per grid cell); the public entry takes the model's [b, s, n, d] and
transposes (XLA fuses the transposes into the surrounding program).

Grouped heads: k and v may have fewer heads than q (n_kv dividing n).
Query head h reads k / v head h // (n // n_kv) through the BlockSpecs'
index maps, forward and backward: no copy of k or v at n heads exists in
HBM. The backward writes dk and dv per QUERY head and the heads of a group
are summed outside the kernel, in float32 (the dq accumulator spans one
head's sequence, so a group's heads cannot share a kernel instance). With
n_kv == n the index maps and the kernels are the ones they were.

q and k share one width d_qk and v has its own d_v (latent attention's
heads are 192 / 128 wide): the scores contract d_qk and are scaled by
1/sqrt(d_qk); o, dO, dv and the forward accumulator are d_v wide, dq and dk
d_qk wide. Nothing is padded to the wider of the two, in HBM or in VMEM.
With d_qk == d_v the kernels are the ones they were.

Backward is ONE kernel, grid (b, n, kv_blocks, q_blocks) with q innermost,
on the transposed score block (see _bwd_kernel): s^T, p^T, dp^T and ds^T
are made once a block pair and feed all three gradients. dk / dv
accumulate in a scratch of one kv block; dq accumulates over the kv axis in
a float32 scratch that spans the head's whole sequence, which the grid's
order allows because it finishes one head before the next and a head's dq
is small against VMEM (6.3 MB at s = 8192, 192 wide; 8.4 MB at 256). The
VMEM limit
follows the shapes (_bwd_vmem_bytes) and flash_attention_supported refuses
a sequence whose accumulator would not fit (_DQ_ACC_BYTES). delta =
rowsum(dO * O) is precomputed outside (one fused elementwise pass). The
call is named flash_bwd_dkv, the name of the dkv kernel it grew from: the
benchmark's readers find the backward by it (ROADMAP C2k).
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
    the masked quarter too. 2048 does not fit the forward's VMEM, nor
    does 1024 with fp32 rows of d=256 (the TPU compiler, without the
    chip): hence the bound on the row's bytes.
    Latent attention's q, k 192 / v 128 wide in bf16 (a 384-byte row) stay
    at 1024 by the same rule and by PR 27's sweep at s=8192, 32 heads (ms a
    call, forward, ten calls chained in one jit): 1024x1024 8.2; 512x1024
    9.7; 1024x512 13.5; 512x512 13.8.
    The one backward kernel (PR 32) shares the pair. Its sweep on the v5e
    (ms a call, ten chained, block_q x block_k; b12's shape / b6's / 192 /
    128 wide at s=8192): 1024x1024 1.22 / 1.74 / 14.6; 512x1024 1.22 /
    1.77 / 15.0; 512x512 1.13 / 1.75 / 16.2; 256x1024 1.41 / 2.09 / 16.0;
    1024x2048 - / 2.12 / 15.2; 2048x2048, which its computed VMEM limit
    lets compile, - / 2.20 / 15.1. Inside the step 512x512 reads 0.3 ms
    under 1024x1024 at s=1024 and 1.0 ms over it at s=2048
    (`flash_bwd_ms_step`): one pair for both kernels and all shapes.
    Grouped heads 256 wide in bf16 (PR 33): a 512-byte row sits on the
    rule's edge and takes 1024, the largest tiles these kernels have (the
    TPU compiler takes forward and backward at 16 over 2 heads, s=8192,
    without the chip: tests/test_tpu_aot.py). What the chip said at 16
    query heads over 2 key/value heads, s=8192, inside the step of the
    qwen3_next cell (traced run, PR 33): the forward 3.84 and 3.99 ms a
    call, the one backward 8.68, together 50.7 % of their roofline
    (`flash_gqa_roofline`; 37 % at 192 / 128): the widest head is the
    kernels' best shape. Against the float32 reference there, through the
    group's sum of dk and dv: the layer's q, k, v weight gradients within
    0.7 % of their norms, as every bf16 matrix of the block. 512 was not
    measured at this width."""
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


# The backward keeps one head's whole dq in VMEM as float32 (_bwd_kernel),
# s x d_qk x 4 bytes. 24 MiB of it is s = 32,768 at 192 wide, 49,152 at 128,
# 98,304 at 64. The dq block lies beside it twice in the inputs' dtype:
# 72 MiB in fp32, 97 with the tiles and a block pair's float32
# intermediates (_bwd_vmem_bytes), three quarters of the v5e's 128 MiB; the
# TPU compiler takes that edge without the chip (tests/test_tpu_aot.py)
_DQ_ACC_BYTES = 24 << 20


def _dq_acc_fits(s: int, d: int) -> bool:
    return s * d * 4 <= _DQ_ACC_BYTES


def flash_attention_supported(q_shape, block: int = 512,
                              block_q: int = None,
                              block_k: int = None) -> bool:
    """True if the kernel can handle this [b, s, n, d] shape. With
    explicit ``block_q``/``block_k`` the check honors the independent
    tiles (s must divide by BOTH); with neither, the ladder must find a
    block <= ``block``. Never for a sequence whose float32 dq accumulator
    (the backward keeps a head's whole dq in VMEM) would pass
    _DQ_ACC_BYTES."""
    if len(q_shape) != 4:
        return False
    s = int(q_shape[1])
    if not _dq_acc_fits(s, int(q_shape[3])):
        return False
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


def _kv_head(n: int, n_kv: int):
    """The k / v head a query head reads, for the index maps: query head
    hi reads head hi // (n // n_kv). With as many k / v heads as query
    heads the map is the identity it always was."""
    group = n // n_kv
    return (lambda hi: hi) if group == 1 else (lambda hi: hi // group)


def _fwd(q, k, v, causal, block_q, block_k):
    b, n, s, d = q.shape
    d_v = v.shape[-1]
    kvh = _kv_head(n, k.shape[1])
    grid = (b, n, s // block_q, s // block_k)
    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=1.0 / math.sqrt(d),
                          causal=causal, block_q=block_q, block_k=block_k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda bi, hi, qi, ki: (bi, kvh(hi), ki, 0)),
            pl.BlockSpec((1, 1, block_k, d_v),
                         lambda bi, hi, qi, ki: (bi, kvh(hi), ki, 0)),
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

def _pad(n: int, to: int) -> int:
    return -(-n // to) * to


def _bwd_vmem_bytes(s, d, d_v, block_q, block_k, dtype) -> int:
    """The backward kernel's VMEM limit, from its shapes: the float32 dq^T
    accumulator over the head's whole sequence, the dq^T output block (the
    head's whole dq, double-buffered like every block), the q, k, v, dO,
    dk, dv tiles (double-buffered) with the float32 dk / dv accumulators,
    and s^T, p^T, dp^T, ds^T of one block pair in float32; 2 MiB for the
    (1, BQ) statistics rows and what else is small. A last dim takes whole
    128-lane tiles (192 takes 256). A bound, not the need: Mosaic keeps less of the intermediates at
    once (the TPU compiler, without the chip, still compiles the kernel
    under 5.8 MiB of the 22.6 at b12's shape, 17.5 of 36.1 at q, k 192 /
    v 128 wide and s = 8192, 37.1 of 97.1 at the longest fp32 sequence
    that flash_attention_supported lets through)."""
    item = jnp.dtype(dtype).itemsize
    wd, wv, wq = _pad(d, _LANES), _pad(d_v, _LANES), _pad(block_q, _LANES)
    dq = (s // block_q) * _pad(d, 16) * wq * (4 + 2 * item)
    tiles = 2 * item * (block_q + 2 * block_k) * (wd + wv)
    accs = 4 * block_k * (wd + wv)
    scores = 4 * 4 * block_k * wq
    return dq + tiles + accs + scores + (2 << 20)


def _bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dq_ref, dk_ref, dv_ref, dq_acc, dk_acc, dv_acc, *, scale,
                causal, block_q, block_k):
    """The whole backward of one (kv block, q block) pair: s^T, p^T, dp^T
    and ds^T are made once and feed dv += p^T dO, dk += ds^T q and
    dq^T[q block] += k^T ds^T: five products where a dq kernel and a dkv
    kernel made seven, and the mask, exp and dp - delta once.

    Works on the TRANSPOSED score block s^T = k q^T, (BK, BQ), so that the
    dv and dk products are plain row-by-column products and the row
    statistics come as (1, BQ) rows, spread down the sublanes. From an
    untransposed p both products contract dim 0 of both operands and lse
    and delta are (BQ, 1) columns spread over the lanes: 18-23 % slower at
    block 512 on the v5e, 2-10 % at 1024 (PERF.md, PR 26).

    dk and dv accumulate over the inner (q) axis in a scratch of one kv
    block. dq accumulates over the OUTER (kv) axis, so its float32
    accumulator holds every q block of the head, (nq, d, BQ): block qi is
    zeroed at ki == 0, added to in ascending ki, and written at
    ki == nk - 1 to the dq output, whose block is the head's whole dq^T
    and stays in VMEM over both inner axes. dq^T and not dq: inside the
    step the kernel reads 6-13 % less so on the v5e (PERF.md, finding
    19)."""
    ki, qi = pl.program_id(2), pl.program_id(3)
    nk, nq = pl.num_programs(2), pl.num_programs(3)

    @pl.when(qi == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    @pl.when(ki == 0)
    def _init_dq():
        dq_acc[qi] = jnp.zeros(dq_acc.shape[1:], dq_acc.dtype)

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
            preferred_element_type=jnp.float32)                  # (BK, d_v)
        dpt = jax.lax.dot_general(
            vb, do, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)                  # (BK, BQ)
        dst = (pt * (dpt - delta)).astype(q.dtype)
        # q was pre-scaled, so dk already carries `scale`
        dk_acc[...] += jax.lax.dot_general(
            dst, q, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)                  # (BK, d)
        # dq = ds k contracts dim 0 of ds^T. Made as dq^T = k^T ds^T, (d, BQ):
        # the transposed operand is then the small k tile and not the
        # (BK, BQ) one, and the accumulator is lane-dense at any d
        dq_acc[qi] += jax.lax.dot_general(
            kb, dst, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)                  # (d, BQ)

    @pl.when(qi == nq - 1)
    def _finish():
        dk_ref[0, 0, :, :] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0, 0, :, :] = dv_acc[...].astype(dv_ref.dtype)

    @pl.when(ki == nk - 1)
    def _finish_dq():
        dq_ref[0, 0, qi] = (dq_acc[qi] * scale).astype(dq_ref.dtype)


def _bwd(q, k, v, out, lse, do, causal, block_q, block_k):
    b, n, s, d = q.shape
    d_v = v.shape[-1]
    n_kv = k.shape[1]
    kvh = _kv_head(n, n_kv)
    nq = s // block_q
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)                                     # (b, n, s)

    def tile(rows, width, on_q, head=lambda hi: hi):
        """A (1, 1, rows, width) tile that follows the q block (`on_q`) or
        the kv block, of the head `head` maps the query head to."""
        return pl.BlockSpec((1, 1, rows, width),
                            lambda bi, hi, ki, qi: (
                                bi, head(hi), qi if on_q else ki, 0))

    qb, dob = tile(block_q, d, True), tile(block_q, d_v, True)
    kb, vb = tile(block_k, d, False, kvh), tile(block_k, d_v, False, kvh)
    # dk and dv leave the kernel per QUERY head (grouped heads: the heads
    # of one group are summed below); with one k / v head a query head the
    # tiles are the inputs' own
    dkb, dvb = tile(block_k, d, False), tile(block_k, d_v, False)
    # lse and delta go in as one (1, BQ) row per q block: a block that
    # spans its array's last two dims whole is legal for every block_q,
    # and so is the head's dq^T as (nq, d, BQ), which the kernel indexes
    # by q block on a leading dim
    rowb = pl.BlockSpec((1, 1, 1, 1, block_q),
                        lambda bi, hi, ki, qi: (bi, hi, qi, 0, 0))
    rows = (b, n, nq, 1, block_q)
    dqb = pl.BlockSpec((1, 1, nq, d, block_q),
                       lambda bi, hi, ki, qi: (bi, hi, 0, 0, 0))
    dq, dk, dv = pl.pallas_call(
        functools.partial(_bwd_kernel, scale=1.0 / math.sqrt(d),
                          causal=causal, block_q=block_q, block_k=block_k),
        grid=(b, n, s // block_k, nq),
        in_specs=[qb, kb, vb, dob, rowb, rowb],
        out_specs=[dqb, dkb, dvb],
        out_shape=[_sds((b, n, nq, d, block_q), q.dtype, q),
                   _sds((b, n, s, d), k.dtype, k),
                   _sds((b, n, s, d_v), v.dtype, v)],
        scratch_shapes=[pltpu.VMEM((nq, d, block_q), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d_v), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_bwd_vmem_bytes(s, d, d_v, block_q, block_k,
                                             q.dtype)),
        interpret=_interpret(),
        # the instruction's name is what the benchmark's readers find the
        # backward by (benchmark/layer_metrics/flash_bwd_ms_step.json)
        name="flash_bwd_dkv",
    )(q, k, v, do, lse.reshape(rows), delta.reshape(rows))
    if n_kv != n:
        # a k / v head's gradient is the sum over the query heads that read
        # it, made in float32 from the kernel's per-head parts
        def over_group(g):
            parts = g.reshape(b, n_kv, n // n_kv, s, g.shape[-1])
            return jnp.sum(parts.astype(jnp.float32), axis=2).astype(g.dtype)

        dk, dv = over_group(dk), over_group(dv)
    # dq left the kernel as (nq, d, BQ) a head: XLA folds this transpose
    # into the [b, n, s, d] -> [b, s, n, d] one that follows
    return jnp.swapaxes(dq, -1, -2).reshape(b, n, s, d), dk, dv


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
    """Causal flash attention on q [b, s, n, d_qk], k [b, s, n_kv, d_qk]
    and v [b, s, n_kv, d_v] → [b, s, n, d_v], the scores scaled by
    1/sqrt(d_qk). Grouped heads: n_kv divides n and query head h reads
    k / v head h // (n // n_kv), by the kernels' index maps: no copy of k
    or v at n heads exists in HBM.

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
    n_kv = int(k.shape[2]) if k.ndim == 4 else 0
    if k.shape != (b, s, n_kv, d) or v.shape[:3] != k.shape[:3]:
        raise ValueError(
            f"flash attention: q {q.shape} and k {k.shape} must agree in "
            f"all but the head count, and v {v.shape} with k in all but "
            f"the head size")
    if n_kv < 1 or n % n_kv:
        raise ValueError(
            f"flash attention: {n} query heads are no whole groups over "
            f"{n_kv} key/value heads")
    if not _dq_acc_fits(s, d):
        raise ValueError(
            f"flash attention: the backward keeps a head's dq in VMEM, and "
            f"{s} x {d} in float32 passes {_DQ_ACC_BYTES >> 20} MiB (check "
            f"flash_attention_supported first)")
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


def _mesh_flash_specs(shape, n_kv: int = None):
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
    if b % bdeg or n % ndeg or (n_kv or n) % ndeg:
        return True, None, None  # unshardable shape under this mesh
    if not flash_attention_supported((b // bdeg, s, n // ndeg, d)):
        return True, None, None  # per-shard shape defeats the kernel
    return True, m, P(batch_axes or None, None, head_ax, None)


def flash_attention_sharded_ok(shape, n_kv: int = None) -> bool:
    """Can flash_attention_val_auto run this [b, s, n, d] shape (k and v
    with `n_kv` heads; None: n) — on the ambient mesh if one is active,
    directly otherwise?"""
    n = int(shape[2]) if len(shape) == 4 else 0
    if n_kv is not None and (n_kv < 1 or n % n_kv):
        return False
    active, mesh, _spec = _mesh_flash_specs(tuple(shape), n_kv)
    if not active:
        return flash_attention_supported(tuple(shape))
    return mesh is not None


def flash_attention_val_auto(q, k, v, causal=True, block_size=None):
    """flash_attention_val that is safe under an active mesh: wraps the
    pallas call in shard_map with batch/head partitioning so GSPMD never
    sees an unpartitionable Mosaic call. Check flash_attention_sharded_ok
    first; raises ValueError (not an opaque Mosaic compile crash) when a
    mesh is active but the shape cannot be sharded onto it."""
    active, mesh, spec = _mesh_flash_specs(q.shape, int(k.shape[2]))
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
