"""Autotune harness (ops/pallas/autotune.py): cache round-trip, shape
bucketing, corruption discard, flag-off inertness, winner selection.

Timing on CPU is forbidden by contract (interpret-mode candidates are
validated-only), so selection tests inject deterministic timers — the
same seam tools/kernel_bench.py --seed-cache uses.
"""
import json
import os

import numpy as np
import pytest

import paddle_tpu  # noqa: F401  (conftest platform setup)
from paddle_tpu.framework import flags
from paddle_tpu.ops import pallas as pk
from paddle_tpu.ops.pallas import autotune as at

import jax.numpy as jnp


@pytest.fixture
def flag_on():
    flags.set_flags({"FLAGS_kernel_autotune": True})
    at.reset_runtime_cache()
    try:
        yield
    finally:
        flags.set_flags({"FLAGS_kernel_autotune": False})
        at.reset_runtime_cache()


@pytest.fixture
def fresh_cache():
    at.reset_runtime_cache()
    yield
    at.reset_runtime_cache()


def _fused_args(n=1000, seed=0):
    rs = np.random.RandomState(seed)
    p = jnp.asarray(rs.randn(n), jnp.float32)
    g = jnp.asarray(rs.randn(n), jnp.float32)
    slots = {"moment1": jnp.zeros((n,), jnp.float32),
             "moment2": jnp.zeros((n,), jnp.float32),
             "beta1_pow": jnp.ones((), jnp.float32),
             "beta2_pow": jnp.ones((), jnp.float32)}
    lr = jnp.asarray(1e-3, jnp.float32)
    hyper = {"beta1": 0.9, "beta2": 0.999, "eps": 1e-8}
    return (p, g, slots, lr, "adamw", hyper, 1.0, 0.01)


# ------------------------------------------------------------- shape bucket

def test_shape_bucket_rounds_up_to_pow2():
    assert at.shape_bucket((1000,)) == (1024,)
    assert at.shape_bucket((1024,)) == (1024,)
    assert at.shape_bucket((2, 96, 4, 64)) == (2, 128, 4, 64)
    assert at.shape_bucket((1,)) == (1,)


def test_cache_key_deterministic_and_free_of_time():
    k1 = at.cache_key("f", (1000,), jnp.float32, "cpu")
    k2 = at.cache_key("f", (777,), jnp.float32, "cpu")
    assert k1 == "f|1024|float32|cpu"
    assert k1 == k2  # same bucket
    assert at.cache_key("f", (1025,), jnp.float32, "cpu") != k1


# ------------------------------------------------------------------- cache

def test_cache_round_trip_byte_identical(tmp_path):
    path = str(tmp_path / "cache.json")
    c = at.TuneCache()
    c.put("fused_update|1024|float32|cpu", {"tile": 32}, measured_ms=1.25,
          default_ms=2.5)
    c.put("flash_attention|2x128x4x64|float32-causal|cpu",
          {"block_q": 64, "block_k": 128})
    c.save(path)
    with open(path) as f:
        first = f.read()
    c2 = at.TuneCache.load(path)
    assert c2.ok
    assert c2.entries == c.entries
    c2.save(path)
    with open(path) as f:
        assert f.read() == first  # save→load→save byte-identical


def test_cache_corruption_discarded_loudly(tmp_path):
    path = str(tmp_path / "cache.json")
    with open(path, "w") as f:
        f.write("{ this is not json")
    with pytest.warns(UserWarning, match="discarded"):
        c = at.TuneCache.load(path)
    assert not c.ok and c.entries == {}


def test_cache_version_drift_discarded_loudly(tmp_path):
    path = str(tmp_path / "cache.json")
    with open(path, "w") as f:
        json.dump({"version": 999, "entries": {"k": {"params": {}}}}, f)
    with pytest.warns(UserWarning, match="version"):
        c = at.TuneCache.load(path)
    assert not c.ok and c.entries == {}


def test_cache_missing_file_is_valid_empty():
    c = at.TuneCache.load("/nonexistent/kernel_tune_cache.json")
    assert c.ok and c.entries == {}


# ---------------------------------------------------------------- dispatch

def test_lookup_inert_with_flag_off(fresh_cache):
    snap_before = _dispatch_count("fused_update", "tuned")
    assert at.lookup("fused_update", (1000,), jnp.float32) is None
    assert _dispatch_count("fused_update", "tuned") == snap_before


def _dispatch_count(kernel, source):
    from paddle_tpu.observability import get_registry

    fam = get_registry().get("kernel_dispatch_total")
    if fam is None:
        return 0
    snap = get_registry().snapshot().get("kernel_dispatch_total", {})
    if isinstance(snap, dict):
        return snap.get(f"kernel={kernel},source={source}", 0)
    return 0


def test_lookup_consults_injected_cache(flag_on):
    c = at.TuneCache()
    c.put(at.cache_key("fused_update", (1000,), jnp.float32),
          {"tile": 64})
    at.reset_runtime_cache(c)
    assert at.lookup("fused_update", (1000,), jnp.float32) == {"tile": 64}
    # different bucket -> miss -> default
    assert at.lookup("fused_update", (5000,), jnp.float32) is None


def test_lookup_counts_fallback_on_corrupt_cache(flag_on, tmp_path):
    path = str(tmp_path / "bad.json")
    with open(path, "w") as f:
        f.write("garbage")
    with pytest.warns(UserWarning):
        at.reset_runtime_cache(at.TuneCache.load(path))
    before = _dispatch_count("fused_update", "fallback")
    assert at.lookup("fused_update", (1000,), jnp.float32) is None
    assert _dispatch_count("fused_update", "fallback") == before + 1


def test_lookup_returns_copy(flag_on):
    c = at.TuneCache()
    key = at.cache_key("fused_update", (1000,), jnp.float32)
    c.put(key, {"tile": 64})
    at.reset_runtime_cache(c)
    got = at.lookup("fused_update", (1000,), jnp.float32)
    got["tile"] = 999
    assert at.lookup("fused_update", (1000,), jnp.float32) == {"tile": 64}


# ---------------------------------------------------------------- autotune

# sweeps with an injected timer name the chip whose published peaks bound
# the timings (cost_model.DEVICE_PEAKS has no entry for the CPU)
CHIP = "TPU v5 lite"


def test_sweep_selects_validated_non_default_winner(tmp_path, fresh_cache):
    """The acceptance sweep: an injected timer that prefers tile=32 makes
    the harness persist a validated non-default winner, and dispatch
    under the flag then serves it."""
    args = _fused_args()
    cache = at.TuneCache()
    path = str(tmp_path / "cache.json")

    def timer(params, fn):
        return 1.0 if params["tile"] == 4 else 2.0 + params["tile"] * 0.01

    rep = at.autotune("fused_update", *args, cache=cache, timer=timer,
                      cache_path=path, device_kind=CHIP)
    assert rep["winner_params"] == {"tile": 4}
    assert rep["winner_params"] != rep["default_params"]
    assert rep["n_validated"] == rep["n_candidates"] > 1
    assert rep["persisted"]
    reloaded = at.TuneCache.load(path)
    assert reloaded.get(rep["key"])["params"] == {"tile": 4}
    # dispatch consults it under the flag
    flags.set_flags({"FLAGS_kernel_autotune": True})
    try:
        at.reset_runtime_cache(reloaded)
        assert at.lookup("fused_update", (1000,), jnp.float32,
                         device_kind=CHIP) == {"tile": 4}
    finally:
        flags.set_flags({"FLAGS_kernel_autotune": False})
        at.reset_runtime_cache()


def test_sweep_rejects_below_roofline_timings(fresh_cache):
    """A timing that beats physics is noise: rejected, never persisted."""
    args = _fused_args()
    cache = at.TuneCache()

    def impossible_timer(params, fn):
        return 1e-30

    rep = at.autotune("fused_update", *args, cache=cache,
                      timer=impossible_timer, persist=True,
                      cache_path="/nonexistent/should/never/write.json",
                      device_kind=CHIP)
    assert rep["n_timed"] == 0
    assert rep["n_rejected_roofline"] == rep["n_validated"] > 0
    assert rep["winner_params"] is None and not rep["persisted"]


def test_sweep_interpret_mode_validates_but_never_times(fresh_cache):
    """No timer on CPU -> every candidate validated, none timed, no
    winner, nothing persisted (the interpret contract)."""
    args = _fused_args(n=500)
    rep = at.autotune("fused_update", *args,
                      cache=at.TuneCache(),
                      cache_path="/nonexistent/never.json")
    assert rep["n_validated"] == rep["n_candidates"] > 0
    assert rep["n_timed"] == 0
    assert rep["winner_params"] is None and not rep["persisted"]


def test_sweep_winner_equal_to_default_not_persisted(fresh_cache):
    args = _fused_args()
    cache = at.TuneCache()

    def timer(params, fn):
        from paddle_tpu.ops.pallas.fused_update import DEFAULT_TILE

        return 1.0 if params["tile"] == DEFAULT_TILE else 5.0

    rep = at.autotune("fused_update", *args, cache=cache, timer=timer,
                      cache_path="/nonexistent/never.json",
                      device_kind=CHIP)
    assert rep["winner_params"] == rep["default_params"]
    assert not rep["persisted"] and cache.entries == {}


def test_all_four_families_registered():
    for fam in ("flash_attention", "quant_matmul", "fused_update",
                "block_codec"):
        assert fam in pk.FAMILIES, sorted(pk.FAMILIES)


# ------------------------------------------------------ flag-off inertness

def test_flag_off_dispatch_sites_use_defaults(fresh_cache):
    """With FLAGS_kernel_autotune unset, every dispatch helper returns
    the pre-ISSUE-13 defaults even with a loaded cache sitting there."""
    c = at.TuneCache()
    for kernel, shape, dtype, params in [
            ("fused_update", (1000,), jnp.float32, {"tile": 64}),
            ("block_codec", (5,), jnp.dtype("float32"), {"tile": 64})]:
        c.put(at.cache_key(kernel, shape, dtype), params)
    at.reset_runtime_cache(c)

    from paddle_tpu.distributed import grad_comm as gc
    from paddle_tpu.ops.pallas import codec as pc
    from paddle_tpu.ops.pallas.fused_update import (DEFAULT_TILE,
                                                    _resolve_tile)

    assert _resolve_tile(1000, jnp.float32, None) == DEFAULT_TILE
    # the grad_comm codec seam resolves to the pure-jnp pair
    enc, dec = gc._block_kernel_ops()
    assert enc is gc.block_encode and dec is gc.block_decode
    assert pc._resolve_tile(5, jnp.float32, None) == pc.DEFAULT_TILE


def test_codec_seam_needs_tpu_target_even_with_flag(flag_on):
    """Flag on but CPU compile target: the codec seam still returns the
    jnp pair — the pallas codecs only engage for TPU lowering."""
    from paddle_tpu.distributed import grad_comm as gc

    enc, dec = gc._block_kernel_ops()
    assert enc is gc.block_encode and dec is gc.block_decode

    from paddle_tpu.framework.target import force_target

    with force_target("tpu"):
        enc2, dec2 = gc._block_kernel_ops()
    from paddle_tpu.ops.pallas import codec as pc

    assert enc2 is pc.block_encode and dec2 is pc.block_decode
