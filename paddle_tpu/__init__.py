"""paddle_tpu — a TPU-native deep-learning framework with the PaddlePaddle API.

Built new against JAX/XLA (compute), pallas (custom kernels), pjit/GSPMD
(parallelism). The reference capability surface is documented in SURVEY.md; the
public namespace mirrors python/paddle/__init__.py of the reference.
"""
from __future__ import annotations

import time as _time
import warnings as _warnings

# the `import` span's first stamp (the profiler's clock; the profiler
# module does not exist yet)
_t_import = _time.monotonic_ns()

_warnings.filterwarnings("ignore", message=".*truncated to dtype.*")

__version__ = "0.1.0"

from .framework import (  # noqa: F401
    CPUPlace, CUDAPlace, Parameter, Place, TPUPlace, Tensor, bfloat16,
    complex64, complex128, device_count, enable_grad, float16, float32, float64,
    get_default_dtype, get_device, get_flags, grad, int8, int16, int32, int64,
    is_compiled_with_cuda, is_grad_enabled, no_grad, seed, set_default_dtype,
    set_device, set_flags, set_grad_enabled, to_tensor, uint8,
)
from .framework import bool  # noqa: F401,A004
from .framework.dtype import convert_dtype  # noqa: F401
from .framework.random import get_cuda_rng_state, set_cuda_rng_state  # noqa: F401

# the full functional namespace (paddle.add, paddle.matmul, ...)
from .tensor import *  # noqa: F401,F403
from .tensor import is_tensor  # noqa: F401

# static/dygraph mode switch: always-dygraph frontend; enable_static is honored
# by the paddle_tpu.static facade (jit-compiled programs)
_static_mode = [False]


def enable_static():
    _static_mode[0] = True


def disable_static():
    _static_mode[0] = False


def in_dynamic_mode():
    return not _static_mode[0]


def in_static_mode():
    return _static_mode[0]


def _import_submodules():
    """Bind every subpackage on the namespace. A submodule that fails to
    import raises here: swallowing it would silently drop e.g.
    paddle_tpu.distributed after a moved jax import."""
    import importlib

    mod_names = [
        "nn",
        "optimizer",
        "io",
        "metric",
        "amp",
        "jit",
        "static",
        "vision",
        "text",
        "distributed",
        "distribution",
        "autograd",
        "device",
        "hapi",
        "incubate",
        "onnx",
        "profiler",
        "sparse",
        "fft",
        "signal",
        "geometric",
        "hub",
        "cost_model",
        "inference",
        "interop",
        "observability",
        "robustness",
        "linalg",
        "regularizer",
        "callbacks",
        "sysconfig",
        "version",
    ]
    g = globals()
    for m in mod_names:
        g[m] = importlib.import_module(f".{m}", __name__)


_import_submodules()

# hoist frequently-used entry points
from .framework.io import load, save  # noqa: F401,E402
from .hapi.model import Model  # noqa: F401,E402
from .hapi.model_summary import flops, summary  # noqa: F401,E402


# ---------------------------------------------------------------- misc shims
from .distributed.parallel import DataParallel  # noqa: F401,E402
from .framework.device import XPUPlace  # noqa: F401,E402

dtype = _np_dtype = None
from .framework import dtype as _dtype_mod  # noqa: E402

dtype = _dtype_mod.DType if hasattr(_dtype_mod, "DType") else str


def iinfo(dtype_):
    """paddle.iinfo over numpy (reference: paddle.iinfo)."""
    import numpy as _np

    from .framework.dtype import dtype_name

    return _np.iinfo(_np.dtype(dtype_name(dtype_)))


def finfo(dtype_):
    import numpy as _np

    from .framework.dtype import dtype_name

    name = dtype_name(dtype_)
    if name == "bfloat16":
        import jax.numpy as _jnp

        class _BF16Info:
            bits = 16
            eps = float(_jnp.finfo(_jnp.bfloat16).eps)
            min = float(_jnp.finfo(_jnp.bfloat16).min)
            max = float(_jnp.finfo(_jnp.bfloat16).max)
            tiny = float(_jnp.finfo(_jnp.bfloat16).tiny)
            dtype = "bfloat16"

        return _BF16Info()
    return _np.finfo(_np.dtype(name))


def get_cudnn_version():
    """No CUDA on this stack (reference returns the cudnn build version)."""
    return None


def set_printoptions(precision=None, threshold=None, edgeitems=None,
                     sci_mode=None, linewidth=None):
    import numpy as _np

    kw = {}
    if precision is not None:
        kw["precision"] = precision
    if threshold is not None:
        kw["threshold"] = threshold
    if edgeitems is not None:
        kw["edgeitems"] = edgeitems
    if linewidth is not None:
        kw["linewidth"] = linewidth
    if sci_mode is not None:
        kw["suppress"] = not sci_mode
    _np.set_printoptions(**kw)


class LazyGuard:
    """reference: paddle.LazyGuard — defer parameter initialization. Init is
    already lazy-cheap here (numpy host init, no device traffic until use),
    so the guard is a no-op context for API parity."""

    def __enter__(self):
        return self

    def __exit__(self, *a):
        return False


def batch(reader, batch_size, drop_last=False):
    """paddle.batch (reference fluid reader decorator)."""
    def batched():
        buf = []
        for item in reader():
            buf.append(item)
            if len(buf) == batch_size:
                yield buf
                buf = []
        if buf and not drop_last:
            yield buf

    return batched

# ----------------------------------------------- reference top-level parity
from .framework.device import CPUPlace as _CPUPlace  # noqa: E402
from .framework.param_attr import ParamAttr  # noqa: F401,E402
from .framework.tensor import create_parameter  # noqa: F401,E402

CUDAPinnedPlace = _CPUPlace  # pinned host staging dissolves into PJRT
NPUPlace = XPUPlace  # NPU (Ascend) place alias: a non-TPU device tag


def check_shape(shape):
    """Validate a shape argument (reference: paddle.check_shape in
    fluid/layers/utils.py: ints or a 1-D integer tensor; -1 allowed once)."""
    from .framework.tensor import Tensor

    if isinstance(shape, Tensor):
        if len(shape.shape) != 1:
            raise ValueError("shape tensor must be 1-D")
        return
    dims = list(shape)
    # NB: builtins, not the shadowing paddle.sum
    if len([d for d in dims if int(d) == -1]) > 1:
        raise ValueError("only one dimension may be -1")
    for d in dims:
        if int(d) < -1:
            raise ValueError(f"invalid dimension {d}")


def disable_signal_handler():
    """Reference: paddle.disable_signal_handler — the C++ runtime installed
    SIGSEGV/SIGBUS handlers worth disabling when embedding; the TPU build
    installs none, so this is a supported no-op."""


def tolist(x):
    """paddle.tolist (reference: tensor/manipulation.py tolist)."""
    return x.tolist()


# everything above is the package's import: one closed span, a set-up phase
profiler.record_span("import", _t_import, profiler.now_ns())  # noqa: F821
