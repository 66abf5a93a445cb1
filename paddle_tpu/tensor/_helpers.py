"""Shared dispatch helpers for the functional kernel library.

Every public op is a thin wrapper calling ``op(fn, *tensor_args, **static_kw)``
where ``fn`` is a pure jax function — the pten-style functional kernel
(reference: paddle/pten/kernels/, kernel_registry.h:219). XLA does the fusion;
pallas kernels slot in as alternate ``fn`` bodies where needed.
"""
from __future__ import annotations

import math

import jax.numpy as jnp
import numpy as np
from jax._src.core import EvalTrace as _EvalTrace, trace_ctx as _trace_ctx

from ..framework.autograd import call_op as op  # noqa: F401
from ..framework.tensor import Tensor  # noqa: F401
from ..framework import dtype as dtype_mod


def val(x):
    return x._value if isinstance(x, Tensor) else x


# python-scalar → device-array cache: `x * 1.0001 + 0.1` style eager chains
# re-convert the same literals every op, and jnp.asarray + the weak-type
# convert_element_type bind dominate the cached-dispatch latency (profiled
# ~40% of the eager us/op; SURVEY §7 hard part 1). Arrays are immutable, so
# sharing one per (type, value, dtype) is sound. Dtype semantics are exactly
# the uncached paths': an explicit ref dtype, else floats take the (current)
# default dtype as a STRONG type — a weak-typed scalar would change jax
# promotion (e.g. f32-weak + bf16 → bf16) and silently shift numerics.
_scalar_cache: dict = {}


# Cached arrays must not escape into traces: jax lifts closure constants
# into compiled executables by identity, and a shared array reappearing
# across separately-compiled programs corrupts their buffer plans (observed
# as 'supplied N buffers but compiled program expected M' on executor
# replays). Trace-time conversion cost compiles away anyway. The probe is a
# private jax import, unguarded on purpose: if it moves, the package fails
# to import instead of silently treating every call as traced.
def _tracing() -> bool:
    return type(_trace_ctx.trace) is not _EvalTrace


def _scalar_array(x, dtype):
    if dtype is None and isinstance(x, float):
        dtype = dtype_mod.get_default_dtype()
    if _tracing():
        return jnp.asarray(np.asarray(x, dtype=dtype))
    # -0.0 == 0.0 hashes equal, so a plain value key would hand a cached
    # +0.0 array to a -0.0 request (flipping 1/x, copysign, atan2); carry
    # the sign of zero explicitly for floats
    if isinstance(x, float):
        key = (type(x), x, math.copysign(1.0, x), dtype)
    else:
        key = (type(x), x, dtype)
    arr = _scalar_cache.get(key)
    if arr is None:
        if len(_scalar_cache) > 4096:
            _scalar_cache.clear()
        arr = _scalar_cache[key] = jnp.asarray(np.asarray(x, dtype=dtype))
    return arr


def as_tensor(x, ref: Tensor | None = None):
    """Coerce python scalars / numpy to Tensor, matching ref dtype for scalars."""
    if isinstance(x, Tensor):
        return x
    if isinstance(x, (int, float, bool)):
        dtype = ref.dtype if ref is not None else None
        return Tensor(_scalar_array(x, dtype), _internal=True)
    return Tensor(x)


def normalize_axis(axis, ndim):
    if axis is None:
        return None
    if isinstance(axis, (list, tuple)):
        return tuple(a + ndim if a < 0 else a for a in axis)
    a = int(axis)
    return a + ndim if a < 0 else a


def convert_dtype(d):
    return dtype_mod.convert_dtype(d)
