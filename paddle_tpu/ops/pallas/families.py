"""Tuner families for the pre-existing pallas kernels.

flash_attention and quant_matmul predate the autotuner (their kernels
live in ``ops/``); this module only teaches the harness their parameter
spaces and references. The fused_update and block_codec families register
themselves from their own kernel modules.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from . import autotune

__all__ = ["flash_candidate_blocks"]

_FLASH_BLOCKS = (1024, 512, 256, 128, 64, 32, 16, 8)


def flash_candidate_blocks(s: int):
    """Valid (block_q, block_k) pairs for sequence length ``s`` — every
    ladder block that divides s, combined independently (the satellite
    point: q and k tiles need not be equal; a long-seq kernel often wants
    a wide k tile against a narrow q tile)."""
    valid = [b for b in _FLASH_BLOCKS if b <= s and s % b == 0]
    return [(bq, bk) for bq in valid for bk in valid]


def _flash_reference(q, k, v, causal):
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


def _register_flash():
    from ..flash_attention import (_default_block, _pick_block,
                                   flash_attention_val)

    def candidates(q, k, v, causal):
        return [{"block_q": bq, "block_k": bk}
                for bq, bk in flash_candidate_blocks(int(q.shape[1]))]

    def default_params(q, k, v, causal):
        blk = _pick_block(int(q.shape[1]),
                          _default_block(int(q.shape[3]), q.dtype))
        return {"block_q": blk, "block_k": blk}

    def run(params, q, k, v, causal):
        return flash_attention_val(q, k, v, causal=causal,
                                   block_q=params["block_q"],
                                   block_k=params["block_k"])

    def cost(q, k, v, causal):
        b, s, n, d = q.shape
        flops = 4.0 * b * n * s * s * d * (0.5 if causal else 1.0)
        nbytes = 4.0 * b * s * n * d * q.dtype.itemsize
        return flops, nbytes

    autotune.register_family(autotune.KernelFamily(
        "flash_attention",
        candidates=candidates,
        default_params=default_params,
        run=run,
        reference=lambda q, k, v, causal: _flash_reference(q, k, v, causal),
        cost=cost,
        key_shape=lambda q, k, v, causal: tuple(int(x) for x in q.shape),
        key_dtype=lambda q, k, v, causal: (
            f"{q.dtype}-{'causal' if causal else 'full'}"),
        rtol=2e-2, atol=2e-2))   # bf16-wide tolerance; fp32 is ~1e-5


def _register_quant_matmul():
    from ..quant_matmul import quant_matmul

    tiles = (64, 128, 256, 512)

    def candidates(x, qw, scales):
        m, k = x.shape
        _, n = qw.shape
        return [{"block_m": bm, "block_n": bn, "block_k": bk}
                for bm in tiles if m % min(bm, m) == 0
                for bn in tiles if n % min(bn, n) == 0
                for bk in tiles if k % min(bk, k) == 0]

    def run(params, x, qw, scales):
        return quant_matmul(x, qw, scales, **params)

    def reference(x, qw, scales):
        return (x.astype(jnp.float32)
                @ (qw.astype(jnp.float32) * scales)).astype(x.dtype)

    def cost(x, qw, scales):
        m, k = x.shape
        _, n = qw.shape
        return 2.0 * m * n * k, (m * k * 4.0 + k * n * 1.0 + n * 4.0
                                 + m * n * 4.0)

    autotune.register_family(autotune.KernelFamily(
        "quant_matmul",
        candidates=candidates,
        default_params=lambda x, qw, scales: {
            "block_m": 256, "block_n": 256, "block_k": 512},
        run=run, reference=reference, cost=cost,
        key_shape=lambda x, qw, scales: (int(x.shape[0]), int(x.shape[1]),
                                         int(qw.shape[1])),
        key_dtype=lambda x, qw, scales: x.dtype,
        rtol=1e-4, atol=1e-3))


_register_flash()
_register_quant_matmul()
