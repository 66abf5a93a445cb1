"""Pure-function units of the AOT planner stack (no compiles).

The compile-heavy halves live in tests/test_tpu_aot.py (libtpu-gated);
these pin the arithmetic that ranks candidates — wrong math here silently
reorders plans without any compile failing.
"""
import pytest

from paddle_tpu.distributed.auto_parallel.planner import (
    enumerate_factorizations,
)
from paddle_tpu.cost_model import device_peaks
from paddle_tpu.jit.aot import estimate_step_seconds

V5E_PEAK_BF16_FLOPS, V5E_HBM_BYTES_PER_S = device_peaks("TPU v5 lite")


class TestEnumerateFactorizations:
    def test_products_cover_exactly_n(self):
        for n in (8, 16, 64):
            for axes in (("data", "model"), ("data", "sharding", "model")):
                for cand in enumerate_factorizations(n, axes):
                    prod = 1
                    for d in cand.values():
                        prod *= d
                    assert prod == n, (n, cand)
                    assert all(d > 1 for d in cand.values()) or cand == {
                        axes[0]: 1}

    def test_no_duplicates(self):
        cands = enumerate_factorizations(64, ("a", "b", "c"))
        keys = [tuple(sorted(c.items())) for c in cands]
        assert len(keys) == len(set(keys))

    def test_caps_respected(self):
        for cand in enumerate_factorizations(64, ("data", "model"),
                                             caps={"model": 4}):
            assert cand.get("model", 1) <= 4

    def test_single_axis_degenerate(self):
        assert enumerate_factorizations(1, ("data",)) == [{"data": 1}]

    def test_non_power_of_two(self):
        cands = enumerate_factorizations(12, ("a", "b"))
        assert {"a": 12} in cands and {"a": 4, "b": 3} in cands

    def test_unsatisfiable_caps_raise(self):
        with pytest.raises(ValueError, match="no way to place"):
            enumerate_factorizations(8, ("model",), caps={"model": 4})


class TestEstimateStepSeconds:
    def test_trusts_positive_compiler_estimate(self):
        out = estimate_step_seconds(
            {"optimal_seconds": 0.01, "flops": 1e15, "bytes_accessed": 1e12})
        assert out == {"seconds": 0.01, "signal": "compiler"}

    def test_negative_sentinel_falls_back_to_roofline(self):
        fl, by = 1e12, 1e11
        out = estimate_step_seconds(
            {"optimal_seconds": -21.9, "flops": fl, "bytes_accessed": by})
        assert out["signal"] == "roofline"
        assert out["seconds"] == pytest.approx(
            max(fl / V5E_PEAK_BF16_FLOPS, by / V5E_HBM_BYTES_PER_S))

    def test_roofline_picks_binding_resource(self):
        # HBM-bound: huge bytes, tiny flops
        out = estimate_step_seconds({"flops": 1e9, "bytes_accessed": 1e12})
        assert out["seconds"] == pytest.approx(1e12 / V5E_HBM_BYTES_PER_S)
        # compute-bound: huge flops, tiny bytes
        out = estimate_step_seconds({"flops": 1e15, "bytes_accessed": 1e9})
        assert out["seconds"] == pytest.approx(1e15 / V5E_PEAK_BF16_FLOPS)

    def test_flops_only(self):
        out = estimate_step_seconds({"flops": 2e14})
        assert out["signal"] == "roofline"
        assert out["seconds"] == pytest.approx(2e14 / V5E_PEAK_BF16_FLOPS)

    def test_nothing_usable_returns_none(self):
        assert estimate_step_seconds({}) is None
        assert estimate_step_seconds({"optimal_seconds": -1.0}) is None
        assert estimate_step_seconds({"flops": 0.0}) is None

    def test_peaks_come_from_the_named_device(self):
        out = estimate_step_seconds({"flops": 2e14}, device_kind="TPU v4")
        assert out["seconds"] == pytest.approx(
            2e14 / device_peaks("TPU v4")[0])
        with pytest.raises(ValueError, match="no published peaks"):
            estimate_step_seconds({"flops": 2e14}, device_kind="cpu")


class TestRankKey:
    def test_compiler_signal_outranks_roofline(self):
        """A roofline estimate is a lower bound that ignores collective
        time; it must never outrank a compiler-signal plan on raw seconds
        (ADVICE r4)."""
        from paddle_tpu.distributed.auto_parallel.planner import (
            MeshPlan, rank_key,
        )

        fast_roofline = MeshPlan({"data": 8}, est_seconds=0.010,
                                 est_signal="roofline")
        slow_compiler = MeshPlan({"model": 8}, est_seconds=0.018,
                                 est_signal="compiler")
        plans = sorted([fast_roofline, slow_compiler], key=rank_key)
        assert plans[0] is slow_compiler

        # among same-signal plans, seconds still decide
        a = MeshPlan({"data": 8}, est_seconds=0.02, est_signal="compiler")
        b = MeshPlan({"model": 8}, est_seconds=0.01, est_signal="compiler")
        assert sorted([a, b], key=rank_key)[0] is b

        # errored / over-budget plans sink regardless of signal
        err = MeshPlan({"data": 8}, error="boom")
        nofit = MeshPlan({"data": 8}, est_seconds=0.001,
                         est_signal="compiler", fits=False)
        order = sorted([err, nofit, fast_roofline], key=rank_key)
        assert order[-1] is err and order[-2] is nofit
