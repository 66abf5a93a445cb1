"""PipelineTrainStep — 1F1B composed into ONE compiled train step
(ISSUE 15 tentpole): schedule x quantized grad_comm x ZeRO-3 at-rest
stage params x memory planner, plus the emulated-HBM acceptance run.

Parity references: the unpipelined ``TrainStep(grad_accum_steps=M)`` has
the SAME arithmetic shape (per-micro-batch mean losses, forward-order
grad accumulation, identical optimizer path), so the composed step's
FIRST loss — same params, same forward — and the trajectory must track
within a few ulp. Bitwise equality across the two DIFFERENT XLA programs
is not in our control: the compiler may order reductions and contract
a*b+c chains differently per program (measured here: 1 ulp on the first
loss, 1-2 ulp on two tensors after one update), which is why every
assertion is a tight allclose rather than ==.
"""
import jax
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.optimizer as opt
from paddle_tpu.distributed import mesh as mesh_mod
from paddle_tpu.distributed.pipeline import (
    MemoryPlan, PipelineTrainStep, plan_memory,
)
from paddle_tpu.distributed.pipeline.train_step import MemoryPlanInfeasible
from paddle_tpu.jit import TrainStep
from paddle_tpu.models import GPTForCausalLM, gpt_presets
from paddle_tpu.models.gpt import GPTPretrainingCriterion

B, S = 8, 16
CFG_KW = dict(mode="scan", use_flash_attention=False)

rs = np.random.RandomState(3)
IDS = rs.randint(0, 128, (B, S))
LBL = rs.randint(0, 128, (B, S))


@pytest.fixture(autouse=True)
def _no_ambient_mesh(fresh_mesh):
    yield


def T(a):
    return paddle.to_tensor(a, dtype="int64")


def run_reference(M, steps=3, num_layers=2):
    """Unpipelined fp32 reference at equal global batch: the SAME
    micro-batched accumulation arithmetic, one device."""
    mesh_mod.set_mesh(None)
    cfg = gpt_presets("gpt-test", num_layers=num_layers, **CFG_KW)
    model = GPTForCausalLM(cfg, seed=0)
    crit = GPTPretrainingCriterion()
    optim = opt.AdamW(learning_rate=1e-3, parameters=model.parameters())
    step = TrainStep(model, lambda lg, lb: crit(lg, lb), optim,
                     grad_accum_steps=M)
    return [float(step(inputs=(T(IDS),), labels=(T(LBL),)))
            for _ in range(steps)]


def run_pipelined(topology, M, steps=3, num_layers=2, **step_kw):
    n = int(np.prod(list(topology.values())))
    mesh_mod.set_mesh(mesh_mod.build_mesh(topology,
                                          devices=jax.devices()[:n]))
    cfg = gpt_presets("gpt-test", num_layers=num_layers,
                      pp_microbatches=M, **CFG_KW)
    model = GPTForCausalLM(cfg, seed=0)
    optim = opt.AdamW(learning_rate=1e-3, parameters=model.parameters())
    step_kw.setdefault("memory_plan", None)
    step = PipelineTrainStep(model, optim, **step_kw)
    losses = [float(step(inputs=(T(IDS),), labels=(T(LBL),)))
              for _ in range(steps)]
    return losses, step, model


def assert_same_first_loss(got, ref):
    """The first loss is one forward over identical weights, but through
    two separately compiled programs: XLA may order the loss-mean reduction
    differently in each (jax 0.9.0's CPU backend does, by 1 ulp), so the
    bound is a few fp32 ulp instead of bit-equality pinned to one
    compiler."""
    np.testing.assert_allclose(got[0], ref[0],
                               rtol=4 * np.finfo(np.float32).eps)


class TestComposedParity:
    def test_fp32_first_loss_and_trajectory_ulp(self):
        M = 4
        ref = run_reference(M)
        pp, step, _ = run_pipelined({"pipe": 2}, M)
        assert_same_first_loss(pp, ref)
        np.testing.assert_allclose(pp, ref, rtol=2e-6)
        rep = step.report()
        assert rep["pipeline_bubble_pct"] == pytest.approx(20.0)
        assert rep["stash_slots"] == 3

    def test_fewer_microbatches_than_stages(self):
        # M=1 < P=2: deep bubble, exact math
        ref = run_reference(1, steps=2)
        pp, step, _ = run_pipelined({"pipe": 2}, 1, steps=2)
        assert_same_first_loss(pp, ref)
        np.testing.assert_allclose(pp, ref, rtol=2e-6)
        assert step.report()["pipeline_bubble_pct"] == pytest.approx(50.0)

    def test_many_more_microbatches_than_stages(self):
        # M=8 >> P=2: shallow bubble, stash capped at 2P-1
        ref = run_reference(8, steps=2)
        pp, step, _ = run_pipelined({"pipe": 2}, 8, steps=2)
        assert_same_first_loss(pp, ref)
        np.testing.assert_allclose(pp, ref, rtol=2e-6)
        rep = step.report()
        assert rep["stash_slots"] == 3
        assert rep["pipeline_bubble_pct"] == pytest.approx(100 / 9,
                                                           abs=1e-3)

    def test_data_parallel_composition(self):
        ref = run_reference(4)
        pp, _, _ = run_pipelined({"pipe": 2, "data": 2}, 4)
        assert_same_first_loss(pp, ref)
        np.testing.assert_allclose(pp, ref, rtol=2e-6)


class TestQuantizedGradComm:
    def test_int8_block_convergence_and_carried_residuals(self):
        """The codec reduces the data-axis wire INSIDE the schedule's
        body; error-feedback residuals ride the jitted step as carried
        state with per-ownership row counts."""
        fp, _, _ = run_pipelined({"pipe": 2, "data": 2}, 4, steps=4)
        qq, step, _ = run_pipelined({"pipe": 2, "data": 2}, 4, steps=4,
                                    grad_comm="int8_block")
        # convergence parity: quantized tracks fp32 closely on gpt-test
        assert_same_first_loss(qq, fp)
        np.testing.assert_allclose(qq, fp, rtol=5e-3)
        assert qq[-1] < qq[0]
        st = step.comm_stats
        assert st["path"] == "traced" and st["codec"] == "int8_block"
        assert st["world"] == 2
        # per-bucket residual stacking: replicated-param bucket has one
        # row per data rank; the pipe-owned block bucket one per
        # (pipe x data) rank
        res = step.grad_comm_communicator._residuals
        rows = sorted(np.asarray(r).shape[0] for r in res.values())
        assert rows == [2, 4]
        # resume surface: round-trips through state_dict
        sd = step.grad_comm_communicator.state_dict()
        assert sd["codec"] == "int8_block" and len(sd["residuals"]) == 2

    def test_fp32_codec_matches_plain_pmean_bitwise(self):
        """The fp32 'codec' is a plain AVG over the data axis — the
        composed step must equal the codec-less one bit for bit."""
        base, _, _ = run_pipelined({"pipe": 2, "data": 2}, 4, steps=3)
        fp, _, _ = run_pipelined({"pipe": 2, "data": 2}, 4, steps=3,
                                 grad_comm="fp32")
        assert base == fp


class TestZero3StageParams:
    def test_at_rest_layout_and_parity(self):
        """Block weights (and moments) rest sharded over
        ('pipe','sharding') on the layer dim — 1/(P*Z) of the stack per
        rank — while the loss trajectory tracks the unpipelined
        reference."""
        L = 4
        ref = run_reference(4, num_layers=L)
        zz, step, model = run_pipelined({"pipe": 2, "sharding": 2}, 4,
                                        num_layers=L,
                                        zero3_stage_params=True)
        assert_same_first_loss(zz, ref)
        np.testing.assert_allclose(zz, ref, rtol=2e-6)
        # at-rest placement: each rank's shard of the stacked qkv weight
        # holds L/(P*Z) = 1 layer
        qkv = model.gpt.decoder.qkv_w
        assert tuple(qkv.dist_spec)[0] == ("pipe", "sharding")
        shard_rows = {sh.data.shape[0]
                      for sh in qkv._value.addressable_shards}
        assert shard_rows == {L // 4}
        # optimizer moments follow the at-rest layout (the ZeRO-3 state
        # win): find qkv_w's slot entry and check its shards
        fm_params = [p for p, m in zip(step.fm.params,
                                       step.fm.trainable_mask) if m]
        qi = next(i for i, p in enumerate(fm_params) if p is qkv)
        m1 = step._slots[qi]["moment1"]
        assert {sh.data.shape[0] for sh in m1.addressable_shards} \
            == {L // 4}

    def test_zero3_with_quantized_comm(self):
        """All three composed: 1F1B x ZeRO-3 at rest x int8_block codec
        over the data axis."""
        L = 4
        # M=2: each 4-row micro-batch shards over data x sharding = 4
        ref = run_reference(2, num_layers=L, steps=3)
        qq, step, _ = run_pipelined(
            {"pipe": 2, "sharding": 2, "data": 2}, 2, num_layers=L,
            zero3_stage_params=True, grad_comm="int8_block")
        assert_same_first_loss(qq, ref)
        np.testing.assert_allclose(qq, ref, rtol=5e-3)
        assert step.comm_stats["world"] == 2   # data axis only


class TestMemoryPolicies:
    def test_remat_policy_matrix_watermark(self):
        """none / full-remat / planner-chosen via explicit MemoryPlan:
        all train to the same losses (remat changes memory, not math),
        and the compiled step's temp bytes order none >= remat."""
        import paddle_tpu.cost_model as cm

        temps, losses = {}, {}
        for name, policies in [("none", ("none",)),
                               ("remat", ("remat",))]:
            plan = plan_memory(
                num_layers=2, pipe_degree=2, microbatches=4,
                activation_bytes_per_layer=1e5,
                input_bytes_per_layer=1e4, layer_flops=1e6)
            plan = MemoryPlan(
                policies=policies, stash_offload=False,
                stash_memory_kind=None, pipe_degree=2, microbatches=4,
                feasible=True, reason="pinned by test", cost=plan.cost)
            ll, step, _ = run_pipelined({"pipe": 2}, 4, steps=2,
                                        memory_plan=plan)
            losses[name] = ll
            mem = step.memory_analysis(record=False)
            if mem is not None:
                temps[name] = mem["temp_bytes"]
        np.testing.assert_allclose(losses["none"], losses["remat"],
                                   rtol=2e-6)
        if len(temps) == 2:
            # at one gpt-test layer per stage remat frees less than XLA's
            # buffer packing moves between two compiles (jax 0.9.0: +8
            # bytes); same page of slack as the depth-not-M test below
            assert temps["remat"] <= temps["none"] + 4096

    @pytest.mark.xfail(
        raises=jax.errors.JaxRuntimeError, strict=False,
        reason="XLA:CPU in jaxlib 0.9.0: a jit with explicit out_shardings "
               "that contains a host-memory transfer fails the SPMD "
               "partitioner's RET_CHECK 'Side-effect HLO must have "
               "sharding' on the annotate_device_placement jax adds to the "
               "outputs; XLA:TPU compiles the same program")
    def test_offload_policy_lowering_parity(self):
        """Forced offload to the host memory space must not change the
        math."""
        plan_off = MemoryPlan(
            policies=("offload",), stash_offload=True,
            stash_memory_kind="pinned_host", pipe_degree=2,
            microbatches=4, feasible=True, reason="forced by test",
            cost={})
        base, _, _ = run_pipelined({"pipe": 2}, 4, steps=2)
        off, _, _ = run_pipelined({"pipe": 2}, 4, steps=2,
                                  memory_plan=plan_off)
        np.testing.assert_allclose(off, base, rtol=2e-6)

    def test_composed_step_temp_bytes_bounded_by_depth_not_m(self):
        """THE 1F1B memory claim, through the WHOLE composed step: at
        fixed micro-batch size, growing M leaves the compiled step's
        temp bytes ~flat once the stash saturates at 2P-1 slots."""
        def temp_bytes(M):
            mesh_mod.set_mesh(mesh_mod.build_mesh(
                {"pipe": 2}, devices=jax.devices()[:2]))
            cfg = gpt_presets("gpt-test", pp_microbatches=M, **CFG_KW)
            model = GPTForCausalLM(cfg, seed=0)
            optim = opt.AdamW(learning_rate=1e-3,
                              parameters=model.parameters())
            step = PipelineTrainStep(model, optim, memory_plan=None)
            ids = rs.randint(0, 128, (M * 2, S))
            step(inputs=(T(ids),), labels=(T(ids),))
            mem = step.memory_analysis(record=False)
            if mem is None:
                pytest.skip("backend exposes no memory analysis")
            return mem["temp_bytes"]

        t_sat = temp_bytes(3)      # S saturates at 2P-1 = 3
        t_big = temp_bytes(12)     # 4x the micro-batches, same mb size
        assert t_big <= t_sat + max(4096, int(0.05 * t_sat)), \
            (t_sat, t_big)


class TestPlannerGate:
    def test_infeasible_budget_refused_with_priced_reason(self):
        mesh_mod.set_mesh(mesh_mod.build_mesh(
            {"pipe": 2}, devices=jax.devices()[:2]))
        cfg = gpt_presets("gpt-test", pp_microbatches=4, **CFG_KW)
        model = GPTForCausalLM(cfg, seed=0)
        optim = opt.AdamW(learning_rate=1e-3,
                          parameters=model.parameters())
        step = PipelineTrainStep(model, optim, hbm_budget_bytes=1024)
        with pytest.raises(MemoryPlanInfeasible, match="no assignment"):
            step(inputs=(T(IDS),), labels=(T(LBL),))

    def test_planner_chosen_plan_trains_and_reports(self):
        """The emulated-HBM acceptance run: a budget the all-none plan
        busts but remat fits — the step plans, trains, reports the plan
        + bubble, and the first loss matches the unpipelined fp32
        reference at equal global batch."""
        from paddle_tpu.distributed.pipeline import (
            gpt_activation_estimate,
        )

        ref = run_reference(4, steps=2, num_layers=4)
        mesh_mod.set_mesh(mesh_mod.build_mesh(
            {"pipe": 2}, devices=jax.devices()[:2]))
        cfg = gpt_presets("gpt-test", num_layers=4, pp_microbatches=4,
                          **CFG_KW)
        est = gpt_activation_estimate(cfg, B // 4, S)
        # 2 layers per stage: between the full-remat peak
        # (stash + 2*inp + 1 transient act) and the all-none peak
        # (stash + 2 resident acts)
        budget = (3 * est["input_bytes_per_layer"]
                  + 2 * est["input_bytes_per_layer"]
                  + 1.5 * est["activation_bytes_per_layer"])
        model = GPTForCausalLM(cfg, seed=0)
        optim = opt.AdamW(learning_rate=1e-3,
                          parameters=model.parameters())
        step = PipelineTrainStep(model, optim, hbm_budget_bytes=budget)
        losses = [float(step(inputs=(T(IDS),), labels=(T(LBL),)))
                  for _ in range(2)]
        assert_same_first_loss(losses, ref)
        np.testing.assert_allclose(losses, ref, rtol=2e-6)
        plan = step.memory_plan
        assert plan is not None and plan.feasible
        assert "remat" in plan.policies
        assert plan.activation_bytes_peak <= budget
        rep = step.report()
        assert rep["memory_plan"]["feasible"]
        assert rep["pipeline_bubble_pct"] == pytest.approx(20.0)


class TestLiveBytesWatermark:
    def test_watermark_bounded_across_m(self):
        """LiveBytesWatermark over the composed step: the host-visible
        live-byte watermark is dominated by params/opt state and stays
        ~flat as M grows at fixed micro-batch size (the O(M) quantity —
        the global batch — enters only as the input arrays themselves);
        the in-program activation bound is pinned by
        test_composed_step_temp_bytes_bounded_by_depth_not_m."""
        from paddle_tpu.observability.memory import LiveBytesWatermark

        def watermark(M):
            mesh_mod.set_mesh(mesh_mod.build_mesh(
                {"pipe": 2}, devices=jax.devices()[:2]))
            cfg = gpt_presets("gpt-test", pp_microbatches=M, **CFG_KW)
            model = GPTForCausalLM(cfg, seed=0)
            optim = opt.AdamW(learning_rate=1e-3,
                              parameters=model.parameters())
            step = PipelineTrainStep(model, optim, memory_plan=None)
            ids = rs.randint(0, 128, (M * 2, S))
            step(inputs=(T(ids),), labels=(T(ids),))  # compile outside
            with LiveBytesWatermark() as wm:
                step(inputs=(T(ids),), labels=(T(ids),))
                wm.sample()
            batch_bytes = 2 * ids.size * 8
            return wm.delta, batch_bytes

        d1, b1 = watermark(3)
        d2, b2 = watermark(12)
        # growing M 4x adds only the batch arrays, not activations
        assert d2 - d1 <= (b2 - b1) + (1 << 20), (d1, d2, b1, b2)


def test_pipeline_metrics_exported():
    """The step exports its bubble and watermark gauges."""
    from paddle_tpu.observability.metrics import get_registry

    run_pipelined({"pipe": 2}, 4, steps=1)
    snap = get_registry().snapshot()
    assert snap["pipeline_bubble_pct"] == pytest.approx(20.0)
    assert snap["pipeline_microbatches"] == 4
    assert snap["pipeline_stash_slots"] == 3
