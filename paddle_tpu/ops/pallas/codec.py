"""Blockwise quantize/dequantize codec kernels (pallas TPU).

The PR-8 EQuARX blockwise wire codecs (``grad_comm.block_encode`` /
``block_decode``) are pure jnp — correct everywhere, but on TPU the
encode's divide+round+clip+double-cast chain and the decode's
multiply+scale-broadcast each cost XLA a full HBM round trip over a
~25MB bucket between the collectives. These kernels run the same math as
one VMEM pass per direction; the pure-jnp pair stays the interpret-mode
reference (and the dispatch fallback), so every ZeRO-2/3 and
crash→resume parity guarantee keeps its bit-for-bit meaning:

  int8_block: bit-identical payload integers (round/clip on the same
      fp32 values);
  fp8_block:  bit-identical float8_e4m3fn wire values (same cast).

Dispatch: ``grad_comm._block_kernel_ops()`` selects this module only
under ``FLAGS_kernel_autotune`` when the compile target is TPU
(:func:`use_tpu_kernels`); ragged geometries (block_size not a multiple
of the 128-lane width) fall back to the jnp reference internally. The
row tile is the autotunable parameter (family ``"block_codec"``).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import autotune
from ..flash_attention import _sds

__all__ = ["use_tpu_kernels", "block_encode", "block_decode",
           "DEFAULT_TILE"]

_LANES = 128
DEFAULT_TILE = 8
_FP8_WIRE = jnp.float8_e4m3fn


def _interpret() -> bool:
    from ...framework.target import target_platform

    return target_platform() != "tpu"


def use_tpu_kernels() -> bool:
    """True when the compile target is TPU — the only platform where the
    Mosaic codec kernels beat the XLA-fused jnp pair."""
    from ...framework.target import target_platform

    return target_platform() == "tpu"


def _resolve_tile(nb: int, dtype, tile: Optional[int]) -> int:
    if tile is not None:
        return int(tile)
    params = autotune.lookup("block_codec", (int(nb),), dtype)
    if params:
        t = int(params.get("tile", 0))
        if t >= 1:
            return t
        autotune.count_dispatch("block_codec", "fallback")
    return DEFAULT_TILE


def _pad_rows(x, tile):
    nb = x.shape[0]
    tile = max(1, min(int(tile), nb))
    R = -(-nb // tile) * tile
    if R > nb:
        pad = jnp.zeros((R - nb,) + x.shape[1:], x.dtype)
        x = jnp.concatenate([x, pad])
    return x, R, tile


# ------------------------------------------------------------------- encode

def _encode_kernel(x_ref, s_ref, q_ref, *, codec):
    q = x_ref[...] / s_ref[...]
    if codec == "int8_block":
        q_ref[...] = jnp.clip(jnp.round(q), -127, 127).astype(jnp.int8) \
            .astype(jnp.int32)
    else:
        q_ref[...] = q.astype(_FP8_WIRE).astype(jnp.float32)


def block_encode(flat, scales, block_size: int, codec: str,
                 tile: Optional[int] = None):
    """Drop-in for ``grad_comm.block_encode`` (same signature, same
    payload bits): blockwise quantize with the shared scales as one VMEM
    pass. Ragged block sizes fall back to the jnp reference."""
    from ...distributed import grad_comm as _gc

    if block_size % _LANES or codec not in ("int8_block", "fp8_block"):
        return _gc.block_encode(flat, scales, block_size, codec)
    x = _gc._as_blocks(flat, block_size)                 # (nb, bs) fp32
    nb = int(x.shape[0])
    s = scales.astype(jnp.float32).reshape(nb, 1)
    tile = _resolve_tile(nb, jnp.int8 if codec == "int8_block"
                         else _FP8_WIRE, tile)
    x, R, tile = _pad_rows(x, tile)
    if s.shape[0] < R:
        # pad scales with ONES (not zeros): padded rows are all-zero
        # payload and a zero scale would make them 0/0 = NaN
        s = jnp.concatenate([s, jnp.ones((R - s.shape[0], 1), s.dtype)])
    out_dtype = jnp.int32 if codec == "int8_block" else jnp.float32
    bs = int(x.shape[1])
    q = pl.pallas_call(
        functools.partial(_encode_kernel, codec=codec),
        grid=(R // tile,),
        in_specs=[pl.BlockSpec((tile, bs), lambda i: (i, 0)),
                  pl.BlockSpec((tile, 1), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((tile, bs), lambda i: (i, 0)),
        out_shape=_sds((R, bs), out_dtype, flat),
        interpret=_interpret(),
    )(x, s)
    return q[:nb]


# ------------------------------------------------------------------- decode

def _decode_kernel(q_ref, s_ref, o_ref, *, world):
    vals = q_ref[...].astype(jnp.float32) * s_ref[...]
    o_ref[...] = (vals / world).astype(o_ref.dtype)


def block_decode(q_sum, scales, world: int, dtype, numel: int,
                 tile: Optional[int] = None):
    """Drop-in for ``grad_comm.block_decode``: dequantize the summed
    payload back to the grad dtype (AVG) in one VMEM pass."""
    from ...distributed import grad_comm as _gc

    nb, bs = int(q_sum.shape[0]), int(q_sum.shape[1])
    if bs % _LANES:
        return _gc.block_decode(q_sum, scales, world, dtype, numel)
    s = scales.astype(jnp.float32).reshape(nb, 1)
    tile = _resolve_tile(nb, jnp.dtype(dtype), tile)
    q, R, tile = _pad_rows(q_sum, tile)
    if s.shape[0] < R:
        s = jnp.concatenate([s, jnp.ones((R - s.shape[0], 1), s.dtype)])
    out = pl.pallas_call(
        functools.partial(_decode_kernel, world=world),
        grid=(R // tile,),
        in_specs=[pl.BlockSpec((tile, bs), lambda i: (i, 0)),
                  pl.BlockSpec((tile, 1), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((tile, bs), lambda i: (i, 0)),
        out_shape=_sds((R, bs), jnp.dtype(dtype), q_sum),
        interpret=_interpret(),
    )(q, s)
    return out[:nb].reshape(-1)[:numel]


# ----------------------------------------------------------- tuner family

def _register_family():
    def _ref(params_ignored, flat, scales, block_size, codec, world, numel):
        from ...distributed import grad_comm as _gc

        q = _gc.block_encode(flat, scales, block_size, codec)
        return q, _gc.block_decode(q, scales, world, jnp.float32, numel)

    def candidates(flat, scales, block_size, codec, world, numel):
        nb = int(scales.shape[0])
        return [{"tile": t} for t in (1, 2, 4, 8, 16, 32, 64)
                if t <= max(1, nb)]

    def run(params, flat, scales, block_size, codec, world, numel):
        q = block_encode(flat, scales, block_size, codec,
                         tile=params["tile"])
        return q, block_decode(q, scales, world, jnp.float32, numel,
                               tile=params["tile"])

    def cost(flat, scales, block_size, codec, world, numel):
        n = float(flat.shape[0])
        return 6 * n, (4 + 1 + 1 + 4) * n

    autotune.register_family(autotune.KernelFamily(
        "block_codec",
        candidates=candidates,
        default_params=lambda *a: {"tile": DEFAULT_TILE},
        run=run,
        reference=lambda *a: _ref(None, *a),
        cost=cost,
        key_shape=lambda flat, scales, *a: (int(scales.shape[0]),),
        key_dtype=lambda flat, scales, block_size, codec, *a: codec,
        rtol=0.0, atol=0.0))       # codec payloads must be bit-identical


_register_family()
