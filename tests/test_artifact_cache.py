"""Persistent compiled-artifact cache (ISSUE 19, ROADMAP item 5).

Contracts pinned here:
- round trip: export → serialize → store → (fresh cache) lookup →
  deserialize is BYTE-identical and the deserialized program computes
  the same results.
- validation discipline: corrupt, version-drifted, producer-drifted,
  key-mismatched and torn entries are discarded LOUDLY (warning +
  discard counter) and read as a miss — the caller recompiles; a
  poisoned entry can never poison the process.
- FaultyFS: a torn write or crashed rename leaves either the old entry
  or an orphan ``.tmp`` the loader never reads; transient write errors
  degrade to "not persisted", never an exception.
- an object that cannot serialize stays on the in-process warm map.
"""
import os

import numpy as np
import pytest

from paddle_tpu.jit.artifact_cache import (
    CACHE_VERSION, ArtifactCache, cache_key, export_compiled, producer_id,
)
from paddle_tpu.robustness.fault_injection import FaultyFS, InjectedCrash


# ---------------------------------------------------------------------------
# key
# ---------------------------------------------------------------------------

class TestKey:
    def test_key_separates_world_and_device(self):
        base = dict(program_fingerprint="fp", shape_bucket=(4, 16),
                    dtype="float32")
        k1 = cache_key(device_kind="cpu", world=1, **base)
        k2 = cache_key(device_kind="cpu", world=2, **base)
        k3 = cache_key(device_kind="TPU_v4", world=2, **base)
        assert len({k1, k2, k3}) == 3
        assert k1.endswith("|w1") and k2.endswith("|w2")
        assert "4x16" in k1

    def test_key_defaults_come_from_live_backend(self):
        import jax

        k = cache_key("fp", (2,), "int8")
        assert f"w{jax.device_count()}" in k

    def test_producer_id_names_both_toolchain_halves(self):
        assert "jax-" in producer_id() and "jaxlib-" in producer_id()


# ---------------------------------------------------------------------------
# round trip (real jax.export)
# ---------------------------------------------------------------------------

class TestRoundTrip:
    def test_byte_identical_round_trip_and_execution(self, tmp_path):
        import jax.numpy as jnp

        x = jnp.arange(8, dtype=jnp.float32)
        exported = export_compiled(lambda a: a * 2.0 + 1.0, x)
        want_bytes = bytes(exported.serialize())
        want = np.asarray(exported.call(x))

        key = cache_key("roundtrip", (8,), "float32")
        cache = ArtifactCache(str(tmp_path))
        assert cache.store(key, exported) is True

        # a FRESH cache (fresh process stand-in: empty warm map) answers
        # from disk with the exact bytes and a working program
        cold = ArtifactCache(str(tmp_path))
        assert cold.load_bytes(key) == want_bytes
        obj = cold.lookup(key)
        assert obj is not None
        np.testing.assert_array_equal(np.asarray(obj.call(x)), want)
        assert cold.stats()["hits"] >= 1

    def test_disk_miss_on_other_world_key(self, tmp_path):
        import jax.numpy as jnp

        x = jnp.arange(4, dtype=jnp.float32)
        exported = export_compiled(lambda a: a + 1.0, x)
        cache = ArtifactCache(str(tmp_path))
        cache.store(cache_key("fp", (4,), "float32", world=1), exported)
        cold = ArtifactCache(str(tmp_path))
        assert cold.lookup(
            cache_key("fp", (4,), "float32", world=2)) is None


# ---------------------------------------------------------------------------
# validation discipline (pure plumbing, runs everywhere)
# ---------------------------------------------------------------------------

class TestValidation:
    def _stored(self, tmp_path, key="k", payload=b"payload-bytes"):
        cache = ArtifactCache(str(tmp_path))
        path = cache.save_bytes(key, payload)
        assert path is not None
        return cache, path, payload

    def test_save_load_bytes_round_trip(self, tmp_path):
        cache, _, payload = self._stored(tmp_path)
        fresh = ArtifactCache(str(tmp_path))
        assert fresh.load_bytes("k") == payload

    def test_missing_entry_is_a_quiet_miss(self, tmp_path):
        cache = ArtifactCache(str(tmp_path))
        assert cache.load_bytes("absent") is None
        assert cache.misses == 1 and cache.discards == 0

    def test_corrupt_entry_discarded_loudly(self, tmp_path):
        cache, path, _ = self._stored(tmp_path)
        with open(path, "wb") as f:
            f.write(b"\x00not json\xff")
        with pytest.warns(UserWarning, match="discarded"):
            assert cache.load_bytes("k") is None
        assert cache.discards == 1
        assert not os.path.exists(path)  # quarantined, not retried forever

    def _rewrite(self, path, **patch):
        import json

        with open(path) as f:
            entry = json.load(f)
        entry.update(patch)
        with open(path, "w") as f:
            json.dump(entry, f)

    def test_version_drift_discarded_loudly(self, tmp_path):
        cache, path, _ = self._stored(tmp_path)
        self._rewrite(path, version=CACHE_VERSION + 1)
        with pytest.warns(UserWarning, match="version drift"):
            assert cache.load_bytes("k") is None

    def test_producer_drift_discarded_loudly(self, tmp_path):
        cache, path, _ = self._stored(tmp_path)
        self._rewrite(path, producer="jax-0.0.1|jaxlib-0.0.1")
        with pytest.warns(UserWarning, match="producer drift"):
            assert cache.load_bytes("k") is None

    def test_key_mismatch_discarded_loudly(self, tmp_path):
        cache, path, _ = self._stored(tmp_path)
        self._rewrite(path, key="some-other-key")
        with pytest.warns(UserWarning, match="key mismatch"):
            assert cache.load_bytes("k") is None

    def test_torn_payload_digest_discarded_loudly(self, tmp_path):
        import base64

        cache, path, payload = self._stored(tmp_path)
        torn = base64.b64encode(payload[: len(payload) // 2]).decode()
        self._rewrite(path, payload=torn)
        with pytest.warns(UserWarning, match="digest mismatch"):
            assert cache.load_bytes("k") is None


# ---------------------------------------------------------------------------
# FaultyFS: machine-shaped failures
# ---------------------------------------------------------------------------

class TestFaultyFS:
    def test_transient_write_error_degrades_to_not_persisted(
            self, tmp_path):
        cache = ArtifactCache(str(tmp_path),
                              fs=FaultyFS(transient_oserrors=1))
        with pytest.warns(UserWarning, match="not persisted"):
            assert cache.save_bytes("k", b"payload") is None
        # the cache stays usable; the next save lands
        assert cache.save_bytes("k", b"payload") is not None
        assert ArtifactCache(str(tmp_path)).load_bytes("k") == b"payload"

    def test_torn_write_leaves_no_visible_entry(self, tmp_path):
        """Power loss mid-write: the destination entry never appears
        (atomic tmp+rename), a fresh cache reads a quiet miss and the
        caller recompiles."""
        cache = ArtifactCache(str(tmp_path), fs=FaultyFS(partial_write_on=1))
        with pytest.raises(InjectedCrash):
            cache.save_bytes("k", b"payload-bytes")
        fresh = ArtifactCache(str(tmp_path))
        assert fresh.load_bytes("k") is None
        assert fresh.discards == 0  # a miss, not a poisoned read

    def test_crash_on_rename_leaves_only_tmp_orphan(self, tmp_path):
        cache = ArtifactCache(str(tmp_path), fs=FaultyFS(crash_on_rename=1))
        with pytest.raises(InjectedCrash):
            cache.save_bytes("k", b"payload-bytes")
        fresh = ArtifactCache(str(tmp_path))
        assert fresh.load_bytes("k") is None
        orphans = [p for p in os.listdir(tmp_path) if p.endswith(".tmp")]
        assert orphans, "the torn tmp file should remain for forensics"


# ---------------------------------------------------------------------------
# unserializable objects
# ---------------------------------------------------------------------------

class TestUnserializable:
    def test_unserializable_object_stays_in_process(self, tmp_path):
        class _Boom:
            def serialize(self):
                raise ValueError("not today")

        cache = ArtifactCache(str(tmp_path))
        obj = _Boom()
        with pytest.warns(UserWarning, match="kept in-process"):
            assert cache.store("k", obj) is False
        assert cache.lookup("k") is obj
