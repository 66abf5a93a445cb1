"""Activation-memory planner + pipeline cost model (ISSUE 15).

Covers: the 1F1B bubble/memory arithmetic of cost_model.pipeline_cost,
plan_memory's cheapest-in-time search and its refusal path (priced
reason, never an XLA OOM), the gpt per-layer estimates, and the
acceptance geometry — a gpt config whose UNPIPELINED activation need
exceeds an emulated HBM budget is refused by the planner while the
pipelined plan fits.
"""
import numpy as np
import pytest

from paddle_tpu.cost_model import pipeline_cost
from paddle_tpu.distributed.pipeline import (
    MemoryPlan, gpt_activation_estimate, host_offload_supported,
    plan_memory,
)
from paddle_tpu.distributed.pipeline.memory_plan import plan_for_gpt

ACT, INP, FLOPS = 1e6, 1e5, 1e9


def cost(**kw):
    base = dict(pipe_degree=4, microbatches=8, layers_per_stage=2,
                activation_bytes_per_layer=ACT, input_bytes_per_layer=INP,
                layer_flops=FLOPS)
    base.update(kw)
    return pipeline_cost(**base)


class TestPipelineCost:
    def test_bubble_fraction_formula(self):
        for P, M in [(2, 2), (4, 8), (4, 1), (8, 64)]:
            c = cost(pipe_degree=P, microbatches=M)
            assert c["bubble_fraction"] == pytest.approx(
                (P - 1) / (M + P - 1))

    def test_bubble_monotone_down_in_microbatches(self):
        bubbles = [cost(microbatches=M)["bubble_fraction"]
                   for M in (1, 2, 4, 8, 32)]
        assert bubbles == sorted(bubbles, reverse=True)

    def test_stash_slots_bounded_by_depth(self):
        assert cost(microbatches=2)["stash_slots"] == 2        # M < 2P-1
        assert cost(microbatches=64)["stash_slots"] == 7       # 2P-1 cap

    def test_policy_memory_ordering(self):
        """none keeps full internals; remat keeps only the input (plus one
        transient recompute); offload keeps ~nothing device-resident."""
        none = cost(policies=["none", "none"])
        rem = cost(policies=["remat", "remat"])
        off = cost(policies=["offload", "offload"])
        assert none["activation_bytes_peak"] > rem["activation_bytes_peak"]
        assert rem["resident_residual_bytes"] == 2 * INP
        assert none["resident_residual_bytes"] == 2 * ACT
        assert off["resident_residual_bytes"] == 0
        # offload's host traffic is priced, remat's is not
        assert off["host_bytes_per_step"] > 0 and \
            rem["host_bytes_per_step"] == 0
        assert off["offload_s"] > 0.0

    def test_recompute_flops_accounting(self):
        none = cost(policies=["none", "none"])
        rem = cost(policies=["remat", "remat"])
        assert none["recompute_flops"] == 0
        assert rem["recompute_flops"] == pytest.approx(8 * 2 * FLOPS)
        assert rem["time_lower_bound_s"] > none["time_lower_bound_s"]

    def test_stash_offload_moves_stash_bytes(self):
        on = cost(stash_offload=True)
        off = cost(stash_offload=False)
        assert on["stash_bytes_device"] < off["stash_bytes_device"]
        assert on["stash_bytes_host"] == off["stash_bytes_device"]
        assert on["host_bytes_per_step"] > 0

    def test_budget_verdict_and_reason(self):
        c = cost(hbm_budget_bytes=1e4)
        assert c["fits"] is False and "OVER" in c["why"]
        c2 = cost(hbm_budget_bytes=1e12)
        assert c2["fits"] is True and "fits" in c2["why"]

    def test_validation(self):
        with pytest.raises(ValueError, match="policies"):
            cost(policies=["none"])
        with pytest.raises(ValueError, match="unknown"):
            cost(policies=["none", "bogus"])
        with pytest.raises(ValueError, match=">= 1"):
            cost(pipe_degree=0)


class TestPlanMemory:
    def kw(self, **over):
        base = dict(num_layers=8, pipe_degree=4, microbatches=8,
                    activation_bytes_per_layer=ACT,
                    input_bytes_per_layer=INP, layer_flops=FLOPS)
        base.update(over)
        return base

    def test_no_budget_all_none(self):
        p = plan_memory(**self.kw())
        assert p.feasible and p.policies == ("none", "none")
        assert not p.stash_offload

    def test_cheapest_fitting_assignment_wins(self):
        """A budget only full remat satisfies picks remat; a budget that
        admits all-none keeps it (remat costs time, never free)."""
        # all-none peak = 7*INP + 2*ACT = 2.7e6; full remat =
        # 7*INP + 2*INP + ACT (transient recompute) = 1.9e6
        tight = plan_memory(**self.kw(hbm_budget_bytes=2.0e6))
        assert tight.feasible and tight.policies == ("remat", "remat")
        roomy = plan_memory(**self.kw(hbm_budget_bytes=2.8e6))
        assert roomy.feasible and roomy.policies == ("none", "none")

    def test_infeasible_is_refused_with_priced_reason(self):
        p = plan_memory(**self.kw(hbm_budget_bytes=1e4))
        assert not p.feasible
        assert "no assignment fits" in p.reason and "B" in p.reason
        assert isinstance(p, MemoryPlan)

    def test_offload_gated_by_backend_support(self):
        """Without a distinct host space the planner must not claim
        offload bytes; with one (or forced) it may."""
        assert isinstance(host_offload_supported(), bool)
        # a budget only offload can satisfy (below remat's input floor)
        budget = INP + ACT + INP + 10   # stash slot + transient, ~no resident
        p = plan_memory(**self.kw(hbm_budget_bytes=budget,
                                  allow_offload=False))
        assert not p.feasible
        assert "host offload unavailable" in p.reason
        forced = plan_memory(**self.kw(hbm_budget_bytes=budget,
                                       allow_offload=True))
        assert forced.feasible
        assert forced.stash_offload or "offload" in forced.policies
        assert forced.stash_memory_kind in (None, "pinned_host")

    def test_layers_must_divide_stages(self):
        with pytest.raises(ValueError, match="divisible"):
            plan_memory(**self.kw(num_layers=7))


class TestGptEstimates:
    def test_estimate_scales_with_config_and_mesh(self):
        import jax

        from paddle_tpu.distributed import mesh as mesh_mod
        from paddle_tpu.models import gpt_presets

        cfg = gpt_presets("gpt-test", use_flash_attention=False)
        e1 = gpt_activation_estimate(cfg, 4, 32)
        e2 = gpt_activation_estimate(cfg, 8, 32)
        assert e2["activation_bytes_per_layer"] == pytest.approx(
            2 * e1["activation_bytes_per_layer"])
        assert e2["input_bytes_per_layer"] == pytest.approx(
            2 * e1["input_bytes_per_layer"])
        # flash drops the [n, s, s] softmax probs from the residual set
        cfg_f = gpt_presets("gpt-test", use_flash_attention=True)
        ef = gpt_activation_estimate(cfg_f, 4, 32)
        assert ef["activation_bytes_per_layer"] < \
            e1["activation_bytes_per_layer"]
        # a 'model'-axis mesh divides the sharded widths
        mesh = mesh_mod.build_mesh({"model": 2},
                                   devices=jax.devices()[:2])
        em = gpt_activation_estimate(cfg, 4, 32, mesh)
        assert em["activation_bytes_per_layer"] < \
            e1["activation_bytes_per_layer"]

    def test_acceptance_geometry_unpipelined_refused_pipelined_fits(self):
        """THE emulated-HBM acceptance shape: one budget, same model and
        global batch — the unpipelined (P=1, M=1, whole batch resident)
        plan is refused with the priced reason, the pipelined plan fits.
        tests/test_pipeline_train_step.py trains the fitting config and
        watermarks it; this pins the planner's side of the gate."""
        from paddle_tpu.models import gpt_presets

        cfg = gpt_presets("gpt-test", mode="scan",
                          use_flash_attention=False)
        B, s = 32, 64
        est = gpt_activation_estimate(cfg, B, s)
        # budget: comfortably fits the pipelined step, not the
        # unpipelined one (which keeps all L layers' residuals for the
        # whole batch even under full remat)
        budget = 6 * est["input_bytes_per_layer"] / (B // 8) * 8 \
            + 2 * est["activation_bytes_per_layer"] / (B // 4)
        unpiped = plan_for_gpt(cfg, pipe_degree=1, microbatches=1,
                               global_batch=B, seq=s,
                               hbm_budget_bytes=budget)
        piped = plan_for_gpt(cfg, pipe_degree=2, microbatches=8,
                             global_batch=B, seq=s,
                             hbm_budget_bytes=budget)
        assert not unpiped.feasible and "OVER" in unpiped.reason
        assert piped.feasible
        assert piped.activation_bytes_peak <= budget
        assert piped.bubble_fraction == pytest.approx(1 / 9)
