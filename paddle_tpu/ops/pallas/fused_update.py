"""Fused blockwise dequantize + optimizer-update pallas TPU kernel.

The ``FusedFlatUpdater`` inner loop today composes jnp: decode the summed
int8/fp8-block payload back to fp32 (``grad_comm.block_decode``), run the
optimizer's elementwise ``_update`` rule, write the new parameters — three
HBM round trips over the same ~25MB flat bucket. This kernel streams the
bucket once: payload + per-block scales (+ optional error-feedback
residual) + parameters + moment slots ride HBM→VMEM tile by tile, the
dequant and the Adam/AdamW/Momentum/SGD update run in VMEM, and the new
parameters and moments come out — one pass.

Equivalence contract (what the property tests pin): the kernel replicates
the EXACT op sequence of ``optimizer._update`` composed with
``FusedFlatUpdater._bucket_fn``'s casts — fp32 math, the same scalar
pre-reductions (``lr*lm``, ``1-beta_pow``) computed with the same jnp ops
outside the kernel — and the bf16 path reproduces its exact cast chain
(grad → param dtype → fp32). The dequant entry replicates
``block_decode``'s chain: ``q*scale → /world → bucket dtype → param
dtype → fp32``. Documented tolerance: dequantized payload values are
EXACT (same fp32 products); the fp32 update matches the jnp composition
bit-for-bit up to XLA's fma-contraction freedom — the two graph shapes
may contract isolated ``a*b ± c`` elements differently, and through
Adam's divide/sqrt chain that amplifies to **a few ulp on isolated
elements** (the tests pin ulp distance ≤ 8 across the whole property
grid with > 99.9% of elements exactly equal; bf16 rounding collapses
the difference entirely). With ``FLAGS_kernel_autotune`` unset this
module is never entered and the jnp path is byte-for-byte the
pre-ISSUE-13 one.

Layout: flat buckets fold to ``(rows, 128)`` lanes, zero-padded; the grid
walks row tiles of ``tile`` rows (the autotunable parameter, family
``"fused_update"``); per-block scales ride as a ``(rows, 1)`` column so
the scale traffic stays 1/128th of the payload. Interpret mode resolves
through the shared ``target_platform()`` seam (rule K001).
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import autotune
from ..flash_attention import _sds

__all__ = ["FUSED_RULES", "rule_spec", "fused_update_flat",
           "fused_dequant_update_flat", "bucket_update_fn",
           "DEFAULT_TILE"]

_LANES = 128
DEFAULT_TILE = 8          # rows per grid step — today's (pre-tuner) default

# optimizer class name -> fused kernel rule kind
FUSED_RULES = {"SGD": "sgd", "Momentum": "momentum", "Adam": "adam",
               "AdamW": "adamw"}


def _interpret() -> bool:
    from ...framework.target import target_platform

    return target_platform() != "tpu"


def rule_spec(optimizer) -> Optional[Tuple[str, dict]]:
    """(kind, hyper) when ``optimizer``'s update rule has a fused pallas
    form, else None (caller falls back to the jnp composition)."""
    kind = FUSED_RULES.get(type(optimizer).__name__)
    if kind is None:
        return None
    if kind == "sgd":
        return kind, {}
    if kind == "momentum":
        return kind, {"momentum": float(optimizer._momentum),
                      "nesterov": bool(optimizer._nesterov)}
    return kind, {"beta1": float(optimizer._beta1),
                  "beta2": float(optimizer._beta2),
                  "eps": float(optimizer._epsilon)}


def _slot_names(kind) -> Tuple[str, ...]:
    if kind == "momentum":
        return ("velocity",)
    if kind in ("adam", "adamw"):
        return ("moment1", "moment2")
    return ()


# ------------------------------------------------------------------ kernels

def _update_math(p, g, slot_vals, svec, *, kind, hyper, wd):
    """The shared in-VMEM update: mirrors optimizer._update line for line
    (same expression shapes and evaluation order — the bit-identity
    contract). ``svec`` carries the scalar pre-reductions. Returns
    (new_p_f32, [new_slot_arrays])."""
    if kind == "sgd":
        if wd:
            g = g + wd * p
        return p - svec[0] * g, []
    if kind == "momentum":
        mom = hyper["momentum"]
        if wd:
            g = g + wd * p
        v = mom * slot_vals[0] + g
        if hyper["nesterov"]:
            return p - svec[0] * (g + mom * v), [v]
        return p - svec[0] * v, [v]
    beta1, beta2, eps = hyper["beta1"], hyper["beta2"], hyper["eps"]
    if wd and kind == "adam":
        g = g + wd * p
    m1 = beta1 * slot_vals[0] + (1 - beta1) * g
    m2 = beta2 * slot_vals[1] + (1 - beta2) * g * g
    mhat = m1 / svec[1]
    vhat = m2 / svec[2]
    new_p = p - svec[0] * mhat / (jnp.sqrt(vhat) + eps)
    if wd and kind == "adamw":
        new_p = new_p - svec[0] * wd * p
    return new_p, [m1, m2]


def _plain_kernel(s_ref, g_ref, p_ref, *refs, kind, hyper, wd, n_slots):
    slot_refs, out_refs = refs[:n_slots], refs[n_slots:]
    p = p_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    svec = [s_ref[i] for i in range(s_ref.shape[0])]
    new_p, new_slots = _update_math(p, g, [r[...] for r in slot_refs],
                                    svec, kind=kind, hyper=hyper, wd=wd)
    out_refs[0][...] = new_p.astype(out_refs[0].dtype)
    for r, v in zip(out_refs[1:], new_slots):
        r[...] = v


def _dequant_kernel(s_ref, q_ref, srow_ref, *refs, kind, hyper, wd,
                    n_slots, world, bucket_dtype, has_residual):
    refs = list(refs)
    res_ref = refs.pop(0) if has_residual else None
    p_ref = refs[0]
    slot_refs = refs[1:1 + n_slots]
    out_refs = refs[1 + n_slots:]
    p = p_ref[...].astype(jnp.float32)
    # block_decode's chain: q*scale -> /world -> bucket dtype, then
    # _bucket_fn's grad->param-dtype cast, then _update's f32 lift
    vals = q_ref[...].astype(jnp.float32) * srow_ref[...]
    gdec = vals / world
    if res_ref is not None:
        gdec = gdec + res_ref[...]
    g = gdec.astype(bucket_dtype).astype(p_ref.dtype).astype(jnp.float32)
    svec = [s_ref[i] for i in range(s_ref.shape[0])]
    new_p, new_slots = _update_math(p, g, [r[...] for r in slot_refs],
                                    svec, kind=kind, hyper=hyper, wd=wd)
    out_refs[0][...] = new_p.astype(out_refs[0].dtype)
    for r, v in zip(out_refs[1:], new_slots):
        r[...] = v


def _resolve_tile(n: int, dtype, tile: Optional[int]) -> int:
    if tile is not None:
        return int(tile)
    params = autotune.lookup("fused_update", (int(n),), dtype)
    if params:
        t = int(params.get("tile", 0))
        if t >= 1:
            return t
        autotune.count_dispatch("fused_update", "fallback")
    return DEFAULT_TILE


def _scalar_prep(kind, hyper, slots, lr, lm):
    """The scalar pre-reductions, with the same jnp ops the reference
    update uses (bit-identity): lr*lm, and for adam the stepped beta
    powers and their 1-x denominators."""
    lr_lm = lr * lm
    if kind in ("adam", "adamw"):
        b1p = slots["beta1_pow"] * hyper["beta1"]
        b2p = slots["beta2_pow"] * hyper["beta2"]
        svec = jnp.stack([lr_lm, 1 - b1p, 1 - b2p]).astype(jnp.float32)
        return svec, {"beta1_pow": b1p, "beta2_pow": b2p}
    return jnp.reshape(lr_lm, (1,)).astype(jnp.float32), {}


def _geometry(n: int, tile: int):
    rows = max(1, -(-n // _LANES))
    tile = max(1, min(int(tile), rows))
    R = -(-rows // tile) * tile
    return rows, tile, R, R * _LANES - n


def _fold(x, R, fill=0):
    pad = R * _LANES - x.shape[0]
    if pad:
        x = jnp.concatenate([x, jnp.full((pad,), fill, x.dtype)])
    return x.reshape(R, _LANES)


def fused_update_flat(flat_p, flat_g, slots: Dict, lr, *, kind: str,
                      hyper: dict, lm: float = 1.0, wd: float = 0.0,
                      tile: Optional[int] = None):
    """One fused update over a flat bucket — the non-dequant entry,
    drop-in for ``FusedFlatUpdater._bucket_fn``'s jnp body. Returns
    ``(new_p, new_slots)`` with the update rule's exact math
    (bit-identical for fp32; bf16 reproduces the jnp cast chain)."""
    n = int(flat_p.shape[0])
    names = _slot_names(kind)
    _, tile, R, _ = _geometry(n, _resolve_tile(n, flat_p.dtype, tile))
    svec, scalar_slots = _scalar_prep(kind, hyper, slots, lr, lm)
    g = _fold(flat_g.astype(flat_p.dtype), R)     # _bucket_fn's cast
    p2 = _fold(flat_p, R)
    slot2 = [_fold(slots[nm], R) for nm in names]
    blk = pl.BlockSpec((tile, _LANES), lambda i: (i, 0))
    out = pl.pallas_call(
        functools.partial(_plain_kernel, kind=kind, hyper=hyper, wd=wd,
                          n_slots=len(names)),
        grid=(R // tile,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)]
        + [blk] * (2 + len(names)),
        out_specs=[blk] * (1 + len(names)),
        out_shape=[_sds((R, _LANES), flat_p.dtype, flat_p)]
        + [_sds((R, _LANES), jnp.float32, flat_p)] * len(names),
        interpret=_interpret(),
    )(svec, g, p2, *slot2)
    new_slots = {nm: o.reshape(-1)[:n] for nm, o in zip(names, out[1:])}
    new_slots.update(scalar_slots)
    return out[0].reshape(-1)[:n], new_slots


def fused_dequant_update_flat(flat_p, q, scales, world: int, slots: Dict,
                              lr, *, kind: str, hyper: dict,
                              block_size: int, bucket_dtype=None,
                              lm: float = 1.0, wd: float = 0.0,
                              residual=None, tile: Optional[int] = None):
    """Fused ``block_decode`` + update: the summed blockwise payload ``q``
    (``(n_blocks, block_size)`` int32/fp32 carrier) and the per-block fp32
    ``scales`` go in; the decoded-AVG gradient never materializes in HBM.
    ``residual`` (fp32, bucket length), when given, is added to the
    decoded gradient in fp32 before the bucket-dtype cast. Falls back to
    the jnp decode feeding :func:`fused_update_flat` when ``block_size``
    does not fold to whole 128-lane rows (ragged tiling)."""
    n = int(flat_p.shape[0])
    bucket_dtype = jnp.dtype(bucket_dtype or flat_p.dtype)
    if block_size % _LANES:
        from ...distributed.grad_comm import block_decode

        g = block_decode(q, scales, world, bucket_dtype, n)
        if residual is not None:
            g = (g.astype(jnp.float32) + residual).astype(bucket_dtype)
        return fused_update_flat(flat_p, g, slots, lr, kind=kind,
                                 hyper=hyper, lm=lm, wd=wd, tile=tile)
    names = _slot_names(kind)
    rows, tile, R, _ = _geometry(n, _resolve_tile(n, flat_p.dtype, tile))
    svec, scalar_slots = _scalar_prep(kind, hyper, slots, lr, lm)
    carrier = jnp.int32 if q.dtype == jnp.int32 else jnp.float32
    q2 = _fold(q.reshape(-1)[:n].astype(carrier), R)
    # one scale per 128-lane row: row i lives in block (i*128)//block_size
    row_idx = (jnp.arange(rows) * _LANES) // block_size
    srow = jnp.take(scales.astype(jnp.float32), row_idx)
    if R > rows:
        srow = jnp.concatenate(
            [srow, jnp.ones((R - rows,), jnp.float32)])
    srow = srow.reshape(R, 1)
    arrs = [q2, srow]
    specs = [pl.BlockSpec((tile, _LANES), lambda i: (i, 0)),
             pl.BlockSpec((tile, 1), lambda i: (i, 0))]
    if residual is not None:
        arrs.append(_fold(residual.astype(jnp.float32), R))
        specs.append(pl.BlockSpec((tile, _LANES), lambda i: (i, 0)))
    blk = pl.BlockSpec((tile, _LANES), lambda i: (i, 0))
    arrs.append(_fold(flat_p, R))
    arrs.extend(_fold(slots[nm], R) for nm in names)
    specs.extend([blk] * (1 + len(names)))
    out = pl.pallas_call(
        functools.partial(_dequant_kernel, kind=kind, hyper=hyper, wd=wd,
                          n_slots=len(names), world=int(world),
                          bucket_dtype=bucket_dtype,
                          has_residual=residual is not None),
        grid=(R // tile,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)] + specs,
        out_specs=[blk] * (1 + len(names)),
        out_shape=[_sds((R, _LANES), flat_p.dtype, flat_p)]
        + [_sds((R, _LANES), jnp.float32, flat_p)] * len(names),
        interpret=_interpret(),
    )(svec, *arrs)
    new_slots = {nm: o.reshape(-1)[:n] for nm, o in zip(names, out[1:])}
    new_slots.update(scalar_slots)
    return out[0].reshape(-1)[:n], new_slots


def bucket_update_fn(optimizer, lm: float, wd: float):
    """``f(flat_p, flat_g, slots, lr) -> (new_p, new_slots)`` routing
    ``FusedFlatUpdater._bucket_fn`` through the fused kernel, or None
    when the optimizer's rule has no fused form (caller keeps the jnp
    path). The returned f matches the jnp body's signature and output
    dtypes exactly, so the caller's ``jax.jit(..., donate_argnums=(2,))``
    wrapping is unchanged."""
    spec = rule_spec(optimizer)
    if spec is None:
        return None
    kind, hyper = spec

    def f(flat_p, flat_g, slots, lr):
        new_p, new_s = fused_update_flat(flat_p, flat_g, slots, lr,
                                         kind=kind, hyper=hyper, lm=lm,
                                         wd=wd)
        return new_p.astype(flat_p.dtype), new_s

    return f


# ----------------------------------------------------------- tuner family

def reference_update_flat(flat_p, flat_g, slots, lr, *, kind, hyper,
                          lm=1.0, wd=0.0):
    """The pure-jnp composition the kernel replaces — the interpret-mode
    validation reference, and what the equivalence tests compare
    against (it IS optimizer._update's math on a flat bucket)."""
    g = flat_g.astype(flat_p.dtype).astype(jnp.float32)
    p32 = flat_p.astype(jnp.float32)
    svec, scalar_slots = _scalar_prep(kind, hyper, slots, lr, lm)
    new_p, new_arrs = _update_math(
        p32, g, [slots[nm] for nm in _slot_names(kind)], svec,
        kind=kind, hyper=hyper, wd=wd)
    out = dict(zip(_slot_names(kind), new_arrs))
    out.update(scalar_slots)
    return new_p.astype(flat_p.dtype), out


def _register_family():
    def candidates(p, g, slots, lr, kind, hyper, lm, wd):
        rows = -(-int(p.shape[0]) // _LANES)
        return [{"tile": t} for t in (1, 2, 4, 8, 16, 32, 64, 128)
                if t <= max(1, rows)]

    def run(params, p, g, slots, lr, kind, hyper, lm, wd):
        return fused_update_flat(p, g, dict(slots), lr, kind=kind,
                                 hyper=hyper, lm=lm, wd=wd,
                                 tile=params["tile"])

    def reference(p, g, slots, lr, kind, hyper, lm, wd):
        return reference_update_flat(p, g, dict(slots), lr, kind=kind,
                                     hyper=hyper, lm=lm, wd=wd)

    def cost(p, g, slots, lr, kind, hyper, lm, wd):
        n = float(p.shape[0])
        n_arrays = 2 + 2 * len(_slot_names(kind)) + 1
        return 12 * n, n_arrays * n * 4

    autotune.register_family(autotune.KernelFamily(
        "fused_update",
        candidates=candidates,
        default_params=lambda *a: {"tile": DEFAULT_TILE},
        run=run, reference=reference, cost=cost,
        key_shape=lambda p, *a: (int(p.shape[0]),),
        key_dtype=lambda p, *a: p.dtype,
        rtol=1e-6, atol=1e-6))


_register_family()
