"""Persistent compiled-artifact cache (ISSUE 19, ROADMAP item 5).

Compile latency was the repo's last unmanaged failure mode: the serving
watchdog had to be sized above cold-compile time (PR 14) and
`compile_grace` state plumbing band-aided the same liability (PR 17).
This module is the root fix — serialized executables (``jax.export``) with
a validate-then-adopt cache discipline, keyed exactly like the PR-13
kernel tune cache:

    (program_fingerprint, shape_bucket, dtype, device_kind, world)

Validation discipline (the PR-13 ``TuneCache`` shape, upgraded to binary
payloads): every entry carries a content digest plus the producing
jax/jaxlib version. A corrupt, torn (``FaultyFS`` partial write),
version-drifted, or key-mismatched entry is discarded LOUDLY —
``warnings.warn`` + the ``artifact_cache_total{event=discard}`` counter —
and the caller falls back to recompiling; a poisoned entry can never
poison the process. Writes are atomic (tmp + fsync + rename through a
``LocalFS`` seam) so a crash mid-write leaves either the old entry or a
``.tmp`` orphan the loader never reads.

:func:`use_compile_cache` is the one place the process points XLA's OWN
persistent compilation cache at a directory.
"""
from __future__ import annotations

import base64
import hashlib
import json
import os
import warnings
from typing import Any, Dict, Optional

from jax import export as _export

from ..observability.metrics import get_registry as _get_registry

__all__ = [
    "CACHE_VERSION", "producer_id", "cache_key", "ArtifactCache",
    "export_compiled", "use_compile_cache",
]

CACHE_VERSION = 1

_m_events = _get_registry().counter(
    "artifact_cache_total",
    "persistent compiled-artifact cache events",
    labels=("event",))


def use_compile_cache() -> str:
    """Give this process a persistent XLA compilation cache and return its
    directory. Entry points (chip_smoke.py, benchmark/run.py, examples,
    the chip tools) call it before their first compile.

    ``JAX_COMPILATION_CACHE_DIR`` set: jax already honours it, so no
    directory is set in code — whoever placed the cache keeps control of
    it. Unset: ``<checkout>/.jax_cache``, a fixed path (the path is part
    of how a later run finds the entries; never a tmp name, pid or time).
    """
    import jax

    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    checkout = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    path = os.path.join(checkout, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def producer_id() -> str:
    """Identity of the producing toolchain; part of every entry. A cache
    entry from a different jax/jaxlib may deserialize into garbage (or a
    different calling convention), so drift discards the entry."""
    import jax

    try:
        import jaxlib

        jl = getattr(jaxlib, "__version__", "?")
    except Exception:  # pragma: no cover - jaxlib rides with jax
        jl = "?"
    return f"jax-{jax.__version__}|jaxlib-{jl}"


def _default_device_kind() -> str:
    import jax

    try:
        return jax.devices()[0].device_kind.replace(" ", "_")
    except Exception:  # pragma: no cover - uninitialized backend
        return "unknown"


def _default_world() -> int:
    import jax

    try:
        return int(jax.device_count())
    except Exception:  # pragma: no cover - uninitialized backend
        return 1


def cache_key(program_fingerprint: str, shape_bucket, dtype,
              device_kind: Optional[str] = None,
              world: Optional[int] = None) -> str:
    """The PR-13 kernel-cache key shape plus device_kind and world, which
    are ALWAYS part of the identity (defaulted from the live backend): an
    executable serialized for one device count is never adopted by
    another."""
    dk = device_kind if device_kind is not None else _default_device_kind()
    w = world if world is not None else _default_world()
    bucket = "x".join(str(b) for b in shape_bucket) \
        if isinstance(shape_bucket, (tuple, list)) else str(shape_bucket)
    return f"{program_fingerprint}|{bucket}|{dtype}|{dk}|w{int(w)}"


def export_compiled(fn, *example_args):
    """Serialize-capable export of ``fn`` at the example arguments'
    shapes/dtypes. Returns the ``Exported`` (``.serialize()`` →  bytes,
    ``.call(*args)`` executes)."""
    import jax

    return _export.export(jax.jit(fn))(*example_args)


class ArtifactCache:
    """Keyed persistent store of serialized compiled programs.

    ``store(key, exported)`` persists ``exported.serialize()`` under the
    key (and always registers the object on the in-process warm map);
    ``lookup(key)`` answers from the warm map first, then deserializes a
    validated on-disk entry.

    ``fs`` is the ``LocalFS`` syscall seam (robustness/checkpoint.py) so
    ``FaultyFS`` can tear writes at exactly the points a machine fails.
    """

    def __init__(self, root: str, fs=None):
        from ..robustness.checkpoint import LocalFS

        self.root = str(root)
        self.fs = fs if fs is not None else LocalFS()
        self.fs.makedirs(self.root)
        self._warm: Dict[str, Any] = {}
        self.hits = 0
        self.misses = 0
        self.discards = 0

    # ----------------------------------------------------------- internals
    def _path(self, key: str) -> str:
        name = hashlib.sha256(key.encode()).hexdigest()[:24]
        return os.path.join(self.root, f"art_{name}.json")

    def _discard(self, path: str, why: str):
        self.discards += 1
        _m_events.labels(event="discard").inc()
        warnings.warn(
            f"artifact cache entry discarded ({why}): {path} — falling "
            f"back to recompile", stacklevel=3)
        try:
            self.fs.remove(path)
        except OSError:
            pass

    # -------------------------------------------------------------- bytes
    def save_bytes(self, key: str, payload: bytes,
                   meta: Optional[dict] = None) -> Optional[str]:
        """Atomically persist one entry; None (never an exception) on
        I/O failure — the cache is an accelerator, not a dependency."""
        entry = {
            "version": CACHE_VERSION,
            "key": key,
            "producer": producer_id(),
            "digest": hashlib.sha256(payload).hexdigest(),
            "payload": base64.b64encode(payload).decode("ascii"),
            "meta": dict(meta or {}),
        }
        path = self._path(key)
        tmp = path + ".tmp"
        try:
            with self.fs.open(tmp, "wb") as f:
                f.write(json.dumps(entry, sort_keys=True).encode())
                self.fs.fsync(f)
            self.fs.replace(tmp, path)
        except OSError as e:
            warnings.warn(f"artifact cache save failed ({e!r}): {path} — "
                          f"entry not persisted", stacklevel=2)
            return None
        _m_events.labels(event="store").inc()
        return path

    def load_bytes(self, key: str) -> Optional[bytes]:
        """Validated read: a missing entry is a quiet miss; a corrupt /
        torn / version-drifted / key-mismatched entry is discarded loudly
        and reads as a miss (the caller recompiles)."""
        path = self._path(key)
        if not self.fs.exists(path):
            self.misses += 1
            _m_events.labels(event="miss").inc()
            return None
        try:
            with self.fs.open(path, "rb") as f:
                entry = json.loads(f.read().decode())
        except (OSError, ValueError, UnicodeDecodeError):
            self._discard(path, "unreadable/corrupt")
            return None
        if not isinstance(entry, dict) \
                or entry.get("version") != CACHE_VERSION:
            self._discard(path, f"version drift "
                                f"(entry {entry.get('version')!r}, "
                                f"cache {CACHE_VERSION})")
            return None
        if entry.get("producer") != producer_id():
            self._discard(path, f"producer drift "
                                f"(entry {entry.get('producer')!r}, "
                                f"running {producer_id()!r})")
            return None
        if entry.get("key") != key:
            self._discard(path, "key mismatch (hash collision or tamper)")
            return None
        try:
            payload = base64.b64decode(entry["payload"].encode("ascii"))
        except Exception:
            self._discard(path, "payload undecodable")
            return None
        if hashlib.sha256(payload).hexdigest() != entry.get("digest"):
            self._discard(path, "content digest mismatch (torn write?)")
            return None
        self.hits += 1
        _m_events.labels(event="hit").inc()
        return payload

    # ----------------------------------------------------------- programs
    def store(self, key: str, exported) -> bool:
        """Register a compiled program under ``key``. The in-process warm
        map always takes it; the disk tier additionally persists the
        serialized form when the object is serializable. True iff the
        entry was persisted to disk."""
        self._warm[key] = exported
        ser = getattr(exported, "serialize", None)
        if ser is None:
            return False
        try:
            payload = ser()
        except Exception as e:
            warnings.warn(f"artifact serialize failed ({e!r}) — entry "
                          f"kept in-process only", stacklevel=2)
            return False
        return self.save_bytes(key, payload) is not None

    def lookup(self, key: str):
        """The compiled program for ``key``: the in-process warm map
        first, then a validated deserialization of the disk entry (cached
        back into the warm map). None = recompile."""
        hit = self._warm.get(key)
        if hit is not None:
            self.hits += 1
            _m_events.labels(event="hit").inc()
            return hit
        payload = self.load_bytes(key)
        if payload is None:
            return None
        try:
            obj = _export.deserialize(bytearray(payload))
        except Exception as e:
            self._discard(self._path(key), f"deserialize failed ({e!r})")
            return None
        self._warm[key] = obj
        return obj

    def stats(self) -> dict:
        return {"root": self.root, "warm_entries": len(self._warm),
                "hits": self.hits, "misses": self.misses,
                "discards": self.discards}
