"""Kernel autotune harness: sweep tile/block shapes, validate, persist.

The CUDA-L2 / tensor-core-autogen recipe (PAPERS.md) applied to our pallas
kernels: a kernel *family* exposes its tunable parameters (flash
attention's ``block_q``/``block_k``, quant_matmul's m/n/k tiles, the fused
dequant+update bucket tile, the blockwise codec row tile) and the harness

  1. enumerates candidate parameter sets for a concrete input,
  2. **validates every candidate against the jnp reference op** within the
     family's tolerance — an unvalidated candidate is never eligible, no
     matter how fast it times;
  3. times eligible candidates by compiled execution on the device.
     Interpret-mode candidates (CPU tier-1, AOT hosts) are
     validated-only and NEVER timed — interpreter wall time says nothing
     about Mosaic codegen. Tests inject a ``timer`` to exercise selection;
  4. sanity-bounds every measurement against the ``cost_model`` roofline
     (:func:`cost_model.kernel_roofline`): a time below the physical bound
     is measurement noise and is rejected, not persisted;
  5. persists the winner keyed ``(kernel, shape_bucket, dtype,
     device_kind)`` in a JSON cache — ``artifacts/kernel_tune_cache.json``
     is the committed copy, ``.cache/kernel_tune_cache.json`` the runtime
     one — that :func:`lookup` consults at dispatch under
     ``FLAGS_kernel_autotune``.

Dispatch contract (the flag-off inertness guarantee): with
``FLAGS_kernel_autotune`` unset, :func:`lookup` returns ``None`` without
touching any file and every kernel runs today's defaults — the numeric
behavior is dot-for-dot the pre-autotuner one. Cache miss falls back to
the defaults; a corrupt or version-drifted cache is discarded LOUDLY (a
``warnings.warn``) and counts as ``fallback`` in the
``kernel_dispatch_total{kernel=,source=tuned|default|fallback}`` counter.

Determinism: cache keys are pure functions of (kernel, shape bucket,
dtype, device kind) — no timestamps, no ids — and the JSON dump sorts its
keys, so save→load→save round-trips byte-identically offline.
"""
from __future__ import annotations

import json
import os
import warnings
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

from ...observability.metrics import get_registry as _get_registry

__all__ = [
    "CACHE_VERSION", "KernelFamily", "FAMILIES", "register_family",
    "TuneCache", "shape_bucket", "cache_key", "current_device_kind",
    "artifact_cache_path", "runtime_cache_path", "get_runtime_cache",
    "reset_runtime_cache", "lookup", "count_dispatch", "autotune",
]

CACHE_VERSION = 1

_m_dispatch = _get_registry().counter(
    "kernel_dispatch_total",
    help="kernel dispatch decisions by parameter source",
    labels=("kernel", "source"))


def count_dispatch(kernel: str, source: str):
    """One dispatch decision into the process-global counter. ``source``
    is 'tuned' (cache hit applied), 'default' (flag off or plain cache
    miss) or 'fallback' (flag on but the cache/tuned entry was unusable —
    corrupt file, version drift, or params invalid for the live shape)."""
    _m_dispatch.labels(kernel=kernel, source=source).inc()


# --------------------------------------------------------------------- keys

def _ceil_pow2(n: int) -> int:
    n = max(1, int(n))
    p = 1
    while p < n:
        p <<= 1
    return p


def shape_bucket(shape: Sequence[int]) -> Tuple[int, ...]:
    """Bucket a concrete shape: each dim rounds UP to the next power of
    two. Nearby shapes share a tuned entry (a 1000-element bucket reuses
    the 1024 winner) while the validation step still runs on the concrete
    shape, so a bucketed winner is never applied unvalidated at tune time
    and dispatch re-checks divisibility before applying it."""
    return tuple(_ceil_pow2(d) for d in shape)


def current_device_kind() -> str:
    """PJRT device kind of the default backend ('cpu' on the host
    fallback) — one half of the cache key."""
    import jax

    try:
        return str(jax.devices()[0].device_kind)
    except Exception:
        return "cpu"


def _dtype_str(dtype) -> str:
    """Canonical dtype spelling for the key ('float32', not a class
    repr); composite family strings ('float32-causal') pass through."""
    if isinstance(dtype, str):
        return dtype
    import numpy as np

    try:
        return np.dtype(dtype).name
    except TypeError:
        return str(dtype)


def cache_key(kernel: str, shape: Sequence[int], dtype,
              device_kind: Optional[str] = None) -> str:
    if device_kind is None:
        device_kind = current_device_kind()
    bucket = "x".join(str(d) for d in shape_bucket(shape))
    return f"{kernel}|{bucket}|{_dtype_str(dtype)}|{device_kind}"


# -------------------------------------------------------------------- cache

def _repo_root() -> str:
    # paddle_tpu/ops/pallas/autotune.py -> repo root three levels up
    return os.path.abspath(os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "..", "..", ".."))


def artifact_cache_path() -> str:
    return os.path.join(_repo_root(), "artifacts", "kernel_tune_cache.json")


def runtime_cache_path() -> str:
    return os.path.join(_repo_root(), ".cache", "kernel_tune_cache.json")


class TuneCache:
    """The persisted winner table: {key: {"params", "measured_ms",
    "default_ms", "validated"}}. ``ok`` is False when a load found a
    corrupt/version-drifted file (discarded loudly; dispatch then counts
    'fallback' instead of quietly serving garbage)."""

    def __init__(self, entries: Optional[Dict[str, dict]] = None,
                 ok: bool = True):
        self.entries: Dict[str, dict] = dict(entries or {})
        self.ok = ok

    @classmethod
    def load(cls, path: str) -> "TuneCache":
        """Load a cache file. Missing file -> empty cache (ok=True: an
        empty cache is a valid state). Corrupt JSON, wrong version, or a
        non-dict payload -> empty cache with ok=False plus a LOUD
        warning — a drifted cache must never silently pick kernels."""
        if not os.path.exists(path):
            return cls()
        try:
            with open(path, "r", encoding="utf-8") as f:
                data = json.load(f)
            if not isinstance(data, dict):
                raise ValueError("cache payload is not an object")
            if data.get("version") != CACHE_VERSION:
                raise ValueError(
                    f"cache version {data.get('version')!r} != "
                    f"{CACHE_VERSION}")
            entries = data.get("entries")
            if not isinstance(entries, dict):
                raise ValueError("cache has no entries object")
            return cls(entries)
        except (OSError, json.JSONDecodeError, ValueError, KeyError) as e:
            warnings.warn(
                f"kernel tune cache {path!r} discarded: {e} — dispatch "
                f"falls back to default kernel parameters", stacklevel=2)
            return cls(ok=False)

    def get(self, key: str) -> Optional[dict]:
        e = self.entries.get(key)
        return e if isinstance(e, dict) and "params" in e else None

    def put(self, key: str, params: dict, measured_ms: Optional[float] = None,
            default_ms: Optional[float] = None):
        entry = {"params": dict(params), "validated": True}
        if measured_ms is not None:
            entry["measured_ms"] = round(float(measured_ms), 6)
        if default_ms is not None:
            entry["default_ms"] = round(float(default_ms), 6)
        self.entries[key] = entry

    def dump(self) -> str:
        """Deterministic JSON: sorted keys, no timestamps — two dumps of
        the same entries are byte-identical (the offline round-trip
        contract)."""
        return json.dumps({"version": CACHE_VERSION,
                           "entries": self.entries},
                          sort_keys=True, indent=1) + "\n"

    def save(self, path: str):
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w", encoding="utf-8") as f:
            f.write(self.dump())
        os.replace(tmp, path)


_runtime_cache: Optional[TuneCache] = None


def get_runtime_cache(reload: bool = False) -> TuneCache:
    """The process-wide dispatch cache: the runtime ``.cache/`` copy when
    present, else the committed artifact. Loaded once (dispatch is on hot
    paths); ``reload=True`` / :func:`reset_runtime_cache` re-read."""
    global _runtime_cache
    if _runtime_cache is None or reload:
        path = runtime_cache_path()
        if not os.path.exists(path):
            path = artifact_cache_path()
        _runtime_cache = TuneCache.load(path)
    return _runtime_cache


def reset_runtime_cache(cache: Optional[TuneCache] = None):
    """Drop (or inject, for tests) the memoized dispatch cache."""
    global _runtime_cache
    _runtime_cache = cache


def lookup(kernel: str, shape: Sequence[int], dtype,
           device_kind: Optional[str] = None) -> Optional[dict]:
    """Dispatch-side consult: the tuned parameter dict for this call
    site, or None for "use today's defaults".

    Flag off -> None immediately (and counts 'default'): the entire
    autotuner is inert without ``FLAGS_kernel_autotune``. Flag on: a
    cache hit counts 'tuned' and returns a COPY of the params (callers
    may mutate); a miss counts 'default'; an unloadable cache counts
    'fallback'. Callers that find the tuned params invalid for the live
    shape (e.g. a block that no longer divides the sequence) must call
    :func:`count_dispatch(kernel, "fallback")` and use their defaults.
    """
    from ...framework.flags import flag

    if not flag("FLAGS_kernel_autotune"):
        count_dispatch(kernel, "default")
        return None
    cache = get_runtime_cache()
    if not cache.ok:
        count_dispatch(kernel, "fallback")
        return None
    entry = cache.get(cache_key(kernel, shape, dtype, device_kind))
    if entry is None:
        count_dispatch(kernel, "default")
        return None
    count_dispatch(kernel, "tuned")
    return dict(entry["params"])


# ----------------------------------------------------------------- families

class KernelFamily:
    """One tunable kernel family.

    candidates(*args) -> [param dict, ...] valid for these concrete args
    default_params(*args) -> the pre-autotuner dispatch choice
    run(params, *args) -> kernel output pytree (through the
        ``target_platform()`` interpret seam, like every dispatch site)
    reference(*args) -> jnp reference output pytree
    cost(*args) -> (flops, bytes_accessed) for the roofline bound
    key_shape(*args) -> the shape tuple the cache key buckets
    key_dtype(*args) -> the dtype half of the key
    rtol/atol: validation tolerance vs the reference
    """

    def __init__(self, name: str, *, candidates: Callable,
                 default_params: Callable, run: Callable,
                 reference: Callable, cost: Callable, key_shape: Callable,
                 key_dtype: Callable, rtol: float = 1e-5,
                 atol: float = 1e-5):
        self.name = name
        self.candidates = candidates
        self.default_params = default_params
        self.run = run
        self.reference = reference
        self.cost = cost
        self.key_shape = key_shape
        self.key_dtype = key_dtype
        self.rtol = rtol
        self.atol = atol


FAMILIES: Dict[str, KernelFamily] = {}


def register_family(family: KernelFamily) -> KernelFamily:
    FAMILIES[family.name] = family
    return family


def _leaves(x) -> List[Any]:
    import jax

    return jax.tree_util.tree_leaves(x)


def _validates(family: KernelFamily, out, ref) -> bool:
    import numpy as np

    a, b = _leaves(out), _leaves(ref)
    if len(a) != len(b):
        return False
    for xa, xb in zip(a, b):
        xa = np.asarray(xa, dtype=np.float64)
        xb = np.asarray(xb, dtype=np.float64)
        if xa.shape != xb.shape:
            return False
        if not np.allclose(xa, xb, rtol=family.rtol, atol=family.atol):
            return False
    return True


def _can_time_on_device() -> bool:
    """Real timing needs compiled (Mosaic) execution — only when the
    compile target is a live TPU. Interpret-mode timings are meaningless
    and the contract forbids them."""
    from ...framework.target import target_platform

    return target_platform() == "tpu"


def _device_timer(fn: Callable[[], Any], repeats: int) -> float:
    """Median-of-repeats wall seconds of ``fn`` with device sync."""
    import time

    import jax

    def once() -> float:
        t0 = time.perf_counter()
        out = fn()
        jax.block_until_ready(out)
        return time.perf_counter() - t0

    once()  # warmup / compile outside the clock
    return sorted(once() for _ in range(max(1, repeats)))[repeats // 2]


def autotune(kernel: str, *args, cache: Optional[TuneCache] = None,
             timer: Optional[Callable] = None, repeats: int = 5,
             persist: bool = True, device_kind: Optional[str] = None,
             cache_path: Optional[str] = None) -> dict:
    """Sweep one family over concrete inputs; returns the report dict.

    ``timer(params, fn)`` -> seconds overrides on-device measurement
    (tests inject deterministic timers; interpret-mode runs without a
    timer validate every candidate but select no winner). A winner is
    persisted only when it is validated, roofline-sane, and differs from
    the default parameters.
    """
    family = FAMILIES[kernel]
    if device_kind is None:
        device_kind = current_device_kind()
    ref = family.reference(*args)
    default = family.default_params(*args)
    can_time = timer is not None or _can_time_on_device()
    floor_s = None      # the noise floor exists only where something is timed
    if can_time:
        from ...cost_model import kernel_roofline

        floor_s = kernel_roofline(*family.cost(*args), device_kind)

    rows = []
    for params in family.candidates(*args):
        row = {"params": dict(params), "validated": False, "time_s": None,
               "rejected": None}
        rows.append(row)
        try:
            out = family.run(params, *args)
        except Exception as e:  # a candidate that fails to lower is just
            row["rejected"] = f"run failed: {type(e).__name__}"
            continue            # ineligible, not a harness error
        if not _validates(family, out, ref):
            row["rejected"] = "reference mismatch"
            continue
        row["validated"] = True
        if not can_time:
            continue            # interpret mode: validated-only, never timed
        if timer is not None:
            t = float(timer(params, lambda p=params: family.run(p, *args)))
        else:
            t = _device_timer(lambda p=params: family.run(p, *args), repeats)
        if t < floor_s:
            row["rejected"] = "below roofline (noise)"
            continue
        row["time_s"] = t

    timed = [r for r in rows if r["time_s"] is not None]
    winner = min(timed, key=lambda r: r["time_s"]) if timed else None
    default_row = next((r for r in rows if r["params"] == default), None)
    key = cache_key(kernel, family.key_shape(*args),
                    family.key_dtype(*args), device_kind)
    persisted = False
    if winner is not None and winner["params"] != default and persist:
        if cache is None:
            cache = get_runtime_cache()
        cache.put(key, winner["params"],
                  measured_ms=winner["time_s"] * 1e3,
                  default_ms=(default_row["time_s"] * 1e3
                              if default_row and default_row["time_s"]
                              else None))
        cache.save(cache_path or runtime_cache_path())
        reset_runtime_cache(cache)
        persisted = True
    return {
        "kernel": kernel,
        "key": key,
        "device_kind": device_kind,
        "roofline_floor_s": floor_s,
        "n_candidates": len(rows),
        "n_validated": sum(1 for r in rows if r["validated"]),
        "n_timed": len(timed),
        "n_rejected_roofline": sum(1 for r in rows
                                   if r["rejected"] == "below roofline "
                                                       "(noise)"),
        "default_params": default,
        "winner_params": dict(winner["params"]) if winner else None,
        "winner_ms": (winner["time_s"] * 1e3 if winner else None),
        "default_ms": (default_row["time_s"] * 1e3
                       if default_row and default_row["time_s"] else None),
        "persisted": persisted,
        "candidates": rows,
    }
