"""paddle_tpu.ops.pallas — the kernel-performance layer (ISSUE 13).

Three pieces aimed at raw per-chip math throughput:

- ``autotune.py`` — a CUDA-L2-spirit sweep harness over kernel tile
  parameters: validate every candidate against the jnp reference, time
  compiled execution on device (interpret-mode candidates are
  validated-only), sanity-bound timings against ``cost_model`` rooflines,
  persist winners per ``(kernel, shape_bucket, dtype, device_kind)`` in
  ``artifacts/kernel_tune_cache.json`` (+ a ``.cache/`` runtime copy)
  consulted at dispatch under ``FLAGS_kernel_autotune``.
- ``fused_update.py`` — fused blockwise dequantize + optimizer update
  over flat grad_comm buckets (the ``FusedFlatUpdater`` inner loop as
  one VMEM pass).
- ``codec.py`` — the PR-8 blockwise quantize/dequantize wire codecs as
  pallas kernels for TPU, pure-jnp pair kept as the interpret reference.

Importing this package registers all four tuner families (the two new
kernels plus flash_attention and quant_matmul via ``families.py``).
"""
from __future__ import annotations

from . import autotune  # noqa: F401
from . import codec  # noqa: F401
from . import families  # noqa: F401
from . import fused_update  # noqa: F401
from .autotune import (FAMILIES, TuneCache, autotune as autotune_sweep,
                       cache_key, count_dispatch, lookup, shape_bucket)
from .codec import block_decode, block_encode, use_tpu_kernels
from .fused_update import (bucket_update_fn, fused_dequant_update_flat,
                           fused_update_flat, rule_spec)

__all__ = [
    "FAMILIES", "TuneCache", "autotune", "autotune_sweep", "cache_key",
    "codec", "count_dispatch", "families", "fused_update", "lookup",
    "shape_bucket", "block_decode", "block_encode", "use_tpu_kernels",
    "bucket_update_fn", "fused_dequant_update_flat", "fused_update_flat",
    "rule_spec",
]
