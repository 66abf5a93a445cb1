"""Pallas int8 weight-only quantized matmul + quantize kernel.

Reference capability: the int8 kernels behind paddle's quantization
deployment (operators/fused/quant_dequant kernels, mkldnn int8 path).
TPU-native: weight-only int8 with per-output-channel scales — the memory-
bound serving case where halving weight bytes doubles effective HBM
bandwidth; the MXU consumes the dequantized tile from VMEM.

Determinism contract (ISSUE 13): ``quantize_int8`` is a pure function of
``(w, stochastic, seed)`` — the stochastic rounding derives its noise
from a counter-based integer hash of (element index, seed) computed with
plain uint32 arithmetic inside the kernel, so the SAME seed yields the
SAME int8 weights on every platform, in every process, on every call.
(The previous ``pltpu.prng_*`` path tied the bits to the backend and has
no interpret-mode lowering at all — stochastic quantization simply
crashed on CPU.)

Kernels:
  quantize_int8(w, seed=)     -> (int8 values, f32 per-col scales)
  quant_matmul(x, qw, scales) -> x @ dequant(qw)   (bf16/f32 in, f32 acc)

quant_matmul's m/n/k tiles are tuner-dispatched: family "quant_matmul"
in the autotune cache under FLAGS_kernel_autotune; explicit block_m/n/k
arguments pin them, and both fall back to the (256, 256, 512) defaults.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _interpret() -> bool:
    from ..framework.target import target_platform

    return target_platform() != "tpu"


# ---------------------------------------------------------------------------
# quantize: per-output-channel symmetric int8
# ---------------------------------------------------------------------------

def _hash_uniform(shape, seed_u32, col0, n_cols):
    """[0, 1) uniforms from a murmur3-finalizer hash of (element index,
    seed): pure uint32 arithmetic — identical bits under Mosaic, the
    interpreter, and XLA:CPU. The per-element counter is the GLOBAL flat
    index into the [k, n_cols] weight (this block starts at column
    `col0`), so the column tiling cannot change the noise."""
    idx = (jax.lax.broadcasted_iota(jnp.uint32, shape, 0)
           * jnp.uint32(n_cols)
           + jax.lax.broadcasted_iota(jnp.uint32, shape, 1)
           + col0.astype(jnp.uint32))
    h = idx * jnp.uint32(2654435761) ^ seed_u32
    h = h ^ (h >> 16)
    h = h * jnp.uint32(0x85EB_CA6B)
    h = h ^ (h >> 13)
    h = h * jnp.uint32(0xC2B2_AE35)
    h = h ^ (h >> 16)
    # via int32: Mosaic has no uint32 -> float32 cast, and the value is
    # < 2**24 so the bits are the same
    return ((h >> 8).astype(jnp.int32).astype(jnp.float32)
            * jnp.float32(1.0 / (1 << 24)))


def _quantize_kernel(seed_ref, w_ref, q_ref, s_ref, *, stochastic, n_cols):
    w = w_ref[...].astype(jnp.float32)
    amax = jnp.max(jnp.abs(w), axis=0, keepdims=True)          # per col
    scale = jnp.maximum(amax / 127.0, 1e-12)
    scaled = w / scale
    if stochastic:
        u = _hash_uniform(scaled.shape, seed_ref[0].astype(jnp.uint32),
                          pl.program_id(0) * w.shape[1], n_cols)
        # floor(x + u) rounds up with probability frac(x): unbiased
        q = jnp.clip(jnp.floor(scaled + u), -127, 127).astype(jnp.int8)
    else:
        q = jnp.clip(jnp.round(scaled), -127, 127).astype(jnp.int8)
    q_ref[...] = q
    s_ref[...] = scale


def quantize_int8(w, stochastic=False, seed=0):
    """[k, n] float weights → ([k, n] int8, [1, n] f32 scales).

    Deterministic: same (w, stochastic, seed) → bit-identical int8 on
    every platform and process (see module docstring)."""
    k, n = w.shape
    # scales are per column, so a (k, bn) block holds everything a column
    # needs. bn keeps the fp32 block near 1 MiB: the whole weight in VMEM
    # (the gridless form) is refused by Mosaic from 768x3072 up. At
    # k = 8192 even the narrowest (k, 128) block plus the rounding
    # temporaries needs 22 MiB, over the default 16 MiB scoped limit —
    # hence vmem_limit_bytes.
    bn = max(128, (1 << 20) // (4 * k) // 128 * 128)
    if n <= bn:
        bn = n
    q, s = pl.pallas_call(
        functools.partial(_quantize_kernel, stochastic=stochastic,
                          n_cols=n),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(pl.cdiv(n, bn),),
            in_specs=[pl.BlockSpec((k, bn), lambda j, seed: (0, j))],
            out_specs=[pl.BlockSpec((k, bn), lambda j, seed: (0, j)),
                       pl.BlockSpec((1, bn), lambda j, seed: (0, j))]),
        out_shape=[jax.ShapeDtypeStruct((k, n), jnp.int8),
                   jax.ShapeDtypeStruct((1, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=64 << 20),
        interpret=_interpret(),
    )(jnp.asarray([int(seed) & 0x7FFF_FFFF], jnp.int32), w)
    return q, s


def stable_seed(name: str, base: int = 0) -> int:
    """Process-stable seed for a named weight: crc32 (NOT the salted
    builtin ``hash``) so every process, rank, and run derives the same
    stochastic-rounding bits for the same parameter name."""
    import zlib

    return (int(base) + zlib.crc32(name.encode("utf-8"))) & 0x7FFF_FFFF


# ---------------------------------------------------------------------------
# quantized matmul: grid over (m, n) tiles, k streamed
# ---------------------------------------------------------------------------

def _qmm_kernel(x_ref, q_ref, s_ref, o_ref, acc_ref, *, n_k):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...].astype(jnp.float32)
    wq = q_ref[...].astype(jnp.float32)                        # dequant tile
    acc_ref[...] += jax.lax.dot(x, wq,
                                preferred_element_type=jnp.float32)

    @pl.when(ki == n_k - 1)
    def _finish():
        o_ref[...] = (acc_ref[...] * s_ref[...]).astype(o_ref.dtype)


_DEFAULT_TILES = (256, 256, 512)


def _tuned_tiles(m: int, n: int, k: int, dtype):
    """(block_m, block_n, block_k) from the tuner cache when the entry
    still tiles this concrete problem, else the defaults."""
    from .pallas import autotune as _at

    params = _at.lookup("quant_matmul", (m, k, n), jnp.dtype(dtype))
    if params:
        bm = int(params.get("block_m", 0))
        bn = int(params.get("block_n", 0))
        bk = int(params.get("block_k", 0))
        if bm > 0 and bn > 0 and bk > 0 \
                and m % min(bm, m) == 0 and n % min(bn, n) == 0 \
                and k % min(bk, k) == 0:
            return bm, bn, bk
        _at.count_dispatch("quant_matmul", "fallback")
    return _DEFAULT_TILES


def _tile(dim: int, block: int) -> int:
    """The largest tile <= block that divides dim and stays 128-aligned
    (k = 768 under the default block_k = 512 runs at 256); the whole dim
    when it is no larger than the block; 0 when nothing fits (ragged)."""
    if dim <= block:
        return dim
    t = math.gcd(dim, block)
    return t if t % 128 == 0 else 0


def quant_matmul(x, qw, scales, block_m=None, block_n=None, block_k=None,
                 out_dtype=None):
    """x [m, k] @ dequant(qw [k, n], scales [1, n]) -> [m, n].

    Explicit block_m/n/k pin the tiles; otherwise dispatch consults the
    autotune cache under FLAGS_kernel_autotune and falls back to the
    (256, 256, 512) defaults."""
    m, k = x.shape
    k2, n = qw.shape
    assert k == k2, (x.shape, qw.shape)
    if block_m is None and block_n is None and block_k is None:
        block_m, block_n, block_k = _tuned_tiles(m, n, k, x.dtype)
    else:
        block_m = block_m or _DEFAULT_TILES[0]
        block_n = block_n or _DEFAULT_TILES[1]
        block_k = block_k or _DEFAULT_TILES[2]
    bm, bn, bk = _tile(m, block_m), _tile(n, block_n), _tile(k, block_k)
    if not (bm and bn and bk):
        # ragged shapes: plain XLA dequant matmul (still weight-only int8 in
        # HBM — the bandwidth saving survives; only the tiling control is lost)
        out = x.astype(jnp.float32) @ (qw.astype(jnp.float32) * scales)
        return out.astype(out_dtype or x.dtype)
    n_k = k // bk
    out = pl.pallas_call(
        functools.partial(_qmm_kernel, n_k=n_k),
        grid=(m // bm, n // bn, n_k),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, ki: (i, ki)),
            pl.BlockSpec((bk, bn), lambda i, j, ki: (ki, j)),
            pl.BlockSpec((1, bn), lambda i, j, ki: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, ki: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype or x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=_interpret(),
    )(x, qw, scales)
    return out
