"""PipelineTrainStep — 1F1B as the loss+grad engine of ONE compiled step.

The seam this composes through existed since the 1F1B schedule landed
(``jit.TrainStep(grad_fn=)``) but nothing exercised it together with the
rest of the training stack. This class is that composition:

- the **1F1B schedule** (schedule.pipeline_1f1b) computes loss+grads
  inside the same compiled SPMD program that runs the optimizer update —
  activation memory bounded by pipeline depth, not micro-batch count;
- the **quantized grad_comm codecs** (PR 8) reduce the data-axis gradient
  wire in-trace *inside the schedule's shard_map body* (the ``grad_sync``
  seam), with per-rank error-feedback residuals carried in and out of the
  jitted step exactly like the unpipelined ``TrainStep(grad_comm=)`` path
  — checkpointable via ``grad_comm_communicator.state_dict()``;
- the **ZeRO-3 at-rest layout** (PR 9's open GSPMD follow-on): with
  ``zero3_stage_params=True`` the pipe-stacked block weights rest sharded
  over ('pipe', 'sharding') on the layer dim — 1/(P*Z) of the stack per
  rank, gathered per stage inside the body; the gather's AD transpose
  re-shards the grads, so the fp32 accumulators and optimizer moments
  stay 1/(P*Z) too;
- the **memory planner** (memory_plan.plan_memory) picks the per-layer
  remat/offload policies and the stash tier against an (emulated) HBM
  budget, and REFUSES an infeasible config with the priced reason before
  anything compiles.

Bubble accounting: the segmented schedule runs 4M + 4P - 4 stage-work
units per step against 4M useful ones — bubble = (P-1)/(M+P-1), exported
as the ``pipeline_bubble_pct`` gauge and by :meth:`report`.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ...jit import TrainStep
from ...observability.metrics import get_registry
from .. import mesh as mesh_mod
from .memory_plan import MemoryPlan, plan_for_gpt

__all__ = ["PipelineTrainStep", "MemoryPlanInfeasible"]

_m_bubble = get_registry().gauge(
    "pipeline_bubble_pct",
    help="analytic 1F1B bubble share of the composed train step, percent")
_m_micro = get_registry().gauge(
    "pipeline_microbatches", help="micro-batch count of the composed step")
_m_stash = get_registry().gauge(
    "pipeline_stash_slots",
    help="1F1B input-stash slots (min(M, 2P-1)) of the composed step")


class MemoryPlanInfeasible(RuntimeError):
    """The planner found no remat/offload assignment under the budget;
    the message carries the priced reason (plan.describe())."""

    def __init__(self, plan: MemoryPlan):
        super().__init__(plan.reason)
        self.plan = plan


class _LocalParam:
    """Shape/dtype shim for the bucket planner: a bucket plan over the
    PER-RANK shard shapes (what the shard_map body actually reduces)."""

    __slots__ = ("_value",)

    def __init__(self, shape, dtype):
        self._value = jax.ShapeDtypeStruct(tuple(shape), np.dtype(dtype))


def _local_shape(shape, spec, mesh):
    """Per-rank block shape of a global array under a PartitionSpec."""
    out = list(shape)
    for i, entry in enumerate(tuple(spec or ())):
        if entry is None:
            continue
        axes = entry if isinstance(entry, tuple) else (entry,)
        deg = 1
        for ax in axes:
            if ax in mesh.axis_names:
                deg *= int(mesh.shape[ax])
        out[i] = out[i] // deg
    return tuple(out)


class PipelineTrainStep(TrainStep):
    """One fused, compiled 1F1B-pipelined training step for scan-mode GPT.

        mesh_mod.set_mesh(build_mesh({"pipe": 4, "data": 2}))
        step = PipelineTrainStep(model, optimizer,
                                 grad_comm="int8_block",
                                 hbm_budget_bytes=2 << 30)
        loss = step(inputs=(ids,), labels=(lbls,))

    ``memory_plan``: "auto" (default) plans on the first call from the
    batch shape and ``hbm_budget_bytes`` (raising
    :class:`MemoryPlanInfeasible` with the priced reason when nothing
    fits); a :class:`MemoryPlan` pins an explicit plan; None defers to
    the model config's recompute/recompute_policy.
    """

    def __init__(self, model, optimizer, *, grad_comm=None,
                 memory_plan="auto", zero3_stage_params: bool = False,
                 hbm_budget_bytes: Optional[int] = None,
                 batch_spec=None, loss_fn=None):
        cfg = getattr(model, "config", None)
        if cfg is None or getattr(cfg, "mode", None) != "scan":
            raise ValueError(
                "PipelineTrainStep drives the scan-mode (pipe-stacked) "
                "GPT decoder; got a model without a scan-mode config")
        mesh = mesh_mod.get_mesh()
        if mesh is None or "pipe" not in mesh.axis_names \
                or int(mesh.shape["pipe"]) <= 1:
            raise ValueError(
                "PipelineTrainStep needs an active mesh with pipe "
                "degree > 1 (mesh_mod.set_mesh(build_mesh({'pipe': P, "
                "...})))")
        # the base ctor rejects grad_comm+grad_fn for the unpipelined DP
        # body; the pipeline grad_fn handles the codec reduction itself,
        # so attach grad_comm AFTER construction via the dedicated seam
        super().__init__(model, loss_fn, optimizer, batch_spec=batch_spec)
        if grad_comm is not None:
            from ..grad_comm import GradCommConfig, GradCommunicator

            if isinstance(grad_comm, str):
                grad_comm = GradCommConfig(codec=grad_comm)
            self._gc_comm = GradCommunicator(grad_comm)
        self._pipe_model = model
        self._pipe_cfg = cfg
        self._pipe_mesh = mesh
        self._plan_request = memory_plan
        self._zero3_request = bool(zero3_stage_params)
        self._hbm_budget = hbm_budget_bytes
        self.memory_plan: Optional[MemoryPlan] = (
            memory_plan if isinstance(memory_plan, MemoryPlan) else None)
        self._local_params = None          # bucket-plan shapes (per rank)
        self._gc_bucket_plan = None
        self._gc_bucket_axes = {}
        self._pipe_order = None
        self._pipe_specs = None
        self._prepared = False

    # ------------------------------------------------------ lazy assembly
    def _microbatches(self) -> int:
        return int(self._pipe_cfg.pp_microbatches
                   or self._pipe_mesh.shape["pipe"])

    def _prepare(self, inputs):
        """Build the memory plan + grad engine from the first batch's
        shape (the planner prices the actual micro-batch size)."""
        from ...models.gpt import gpt_1f1b_grad_fn

        mesh, cfg = self._pipe_mesh, self._pipe_cfg
        first = inputs[0]
        shape = getattr(first, "shape", None) or first._value.shape
        b, s = int(shape[0]), int(shape[1])
        M = self._microbatches()
        plan = self.memory_plan
        if plan is None and self._plan_request == "auto" \
                and self._hbm_budget is not None:
            plan = plan_for_gpt(
                cfg, pipe_degree=int(mesh.shape["pipe"]), microbatches=M,
                global_batch=b, seq=s,
                hbm_budget_bytes=self._hbm_budget, mesh=mesh)
            if not plan.feasible:
                raise MemoryPlanInfeasible(plan)
            self.memory_plan = plan

        # pass 1: the engine's layout (traversal order + at-rest specs) —
        # the bucket plan and residual shardings derive from it
        probe = gpt_1f1b_grad_fn(self._pipe_model, memory_plan=plan,
                                 zero3_stage_params=self._zero3_request)
        self._pipe_order = probe.order
        self._pipe_specs = probe.specs
        self._local_params = self._build_local_params()
        grad_sync, sync_specs = (None, ())
        if self._gc_comm is not None:
            grad_sync, sync_specs = self._build_grad_sync()
        if grad_sync is None:
            self.grad_fn = probe
        else:
            self.grad_fn = gpt_1f1b_grad_fn(
                self._pipe_model, memory_plan=plan,
                zero3_stage_params=self._zero3_request,
                grad_sync=grad_sync, sync_axes=("data",),
                sync_state_specs=sync_specs)
        if self.grad_fn.zero3_stage_params:
            # re-home the block weights (and thereby the grads, fp32
            # accumulators and optimizer moments) to the at-rest
            # ('pipe','sharding') layout — _shardings/_build read
            # dist_spec, so the whole compiled step agrees
            from ...models.gpt import _BLOCK_PARAMS

            dec = self._pipe_model.gpt.decoder
            for n in _BLOCK_PARAMS:
                getattr(dec, n).dist_spec = self.grad_fn.specs[n]
        P_deg = int(mesh.shape["pipe"])
        S = min(M, 2 * P_deg - 1)
        self._bubble_pct = 100.0 * (P_deg - 1) / (M + P_deg - 1)
        _m_bubble.set(self._bubble_pct)
        _m_micro.set(M)
        _m_stash.set(S)
        self._prepared = True

    def _build_local_params(self):
        """Per-rank shard shapes of every trainable param, in traversal
        order — what the in-body bucket plan is built over."""
        fm = self.fm
        mesh = self._pipe_mesh
        specs = self._pipe_specs
        order = self._pipe_order
        out = []
        ti = 0
        for p, m in zip(fm.params, fm.trainable_mask):
            if not m:
                continue
            spec = specs[order[ti]]
            out.append(_LocalParam(
                _local_shape(p._value.shape, spec, mesh), p._value.dtype))
            ti += 1
        return out

    # ------------------------------------------------- grad_comm plumbing
    def _gc_world(self, mesh):
        """The codec reduces over the DATA axis only: 'sharding' is either
        the ZeRO-3 at-rest dimension (owned, reduced by the gather's
        transpose) or handled by the schedule's default pmean."""
        if mesh is None or self._gc_comm is None:
            return (), 1
        if "data" in mesh.axis_names and mesh.shape["data"] > 1:
            return ("data",), int(mesh.shape["data"])
        return (), 1

    def _gc_buckets(self):
        """Bucket plan over the PER-RANK shard shapes, segregated by
        ownership signature: a flat bucket mixing a pipe-OWNED block
        grad (per-stage values) with a replicated embed/loss grad would
        make the whole bucket pipe-varying and break the replicated
        outputs' shard_map specs (and, on vma jax, their types). Params
        sharing a spec-axes set bucket together; indices renumber
        deterministically (same traversal on every rank)."""
        if self._gc_bucket_plan is not None:
            return self._gc_bucket_plan
        if self._local_params is None:
            raise RuntimeError("bucket plan requested before _prepare()")
        from ..grad_comm import build_buckets
        from .schedule import _spec_axes

        cfgc = self._gc_comm.config
        groups = {}
        for i, name in enumerate(self._pipe_order):
            key = tuple(sorted(_spec_axes(self._pipe_specs[name])))
            groups.setdefault(key, []).append(i)
        plan, plan_axes = [], {}
        for key in sorted(groups):
            idxs = groups[key]
            sub = [self._local_params[i] for i in idxs]
            for b in build_buckets(
                    sub, cfgc.comm_buffer_size, cfgc.last_comm_buffer_size,
                    dtypes=[np.dtype(p._value.dtype) for p in sub]):
                b.param_indices = [idxs[j] for j in b.param_indices]
                b.index = len(plan)
                plan.append(b)
                plan_axes[b.index] = frozenset(key)
        self._gc_bucket_plan = plan
        self._gc_bucket_axes = plan_axes
        return plan

    def _gc_res_layout(self, mesh):
        """Per-bucket residual stacking: a bucket of grads OWNED on some
        axes (the pipe-stacked block params; +'sharding' under ZeRO-3)
        has distinct values — and so a distinct quantization error — on
        every (owner x data) rank; a replicated-param bucket only differs
        per data rank. The residual spec mirrors exactly that, which is
        also what keeps the replicated grads' replication provable to
        shard_map after the error-feedback add."""
        out = []
        for b in self._gc_buckets():
            axes = tuple(ax for ax in mesh.axis_names
                         if (ax in self._gc_bucket_axes[b.index]
                             or ax == "data") and int(mesh.shape[ax]) > 1)
            rows = 1
            for ax in axes:
                rows *= int(mesh.shape[ax])
            out.append((rows, P(axes)))
        return out

    def _build_grad_sync(self):
        """The in-body quantized bucket reduction: flatten the per-rank
        grads bucket-wise, reduce each bucket with the configured codec
        over the data axis (the same ``reduce_bucket`` core every other
        path runs), thread the error-feedback residual rows through."""
        from .. import collective as _coll

        comm = self._gc_comm
        mesh = self._pipe_mesh
        axes, world = self._gc_world(mesh)
        if world <= 1:
            return None, ()
        if comm.group is None or tuple(comm.group.axes) != axes:
            comm.group = _coll.new_group(axes=axes)
        from ..grad_comm import EF_CODECS

        ef = (comm.config.error_feedback
              and comm.config.codec in EF_CODECS)
        order = self._pipe_order
        buckets = self._gc_buckets()

        def grad_sync(grads, state):
            flat_parts = [grads[k].reshape(-1) for k in order]
            new_state = list(state)
            for gi, b in enumerate(buckets):
                if len(b.param_indices) == 1:
                    flat = flat_parts[b.param_indices[0]]
                else:
                    flat = jnp.concatenate(
                        [flat_parts[pi] for pi in b.param_indices])
                residual = state[gi].reshape(-1) if ef else None
                reduced, nr, _w, _c = comm.reduce_bucket(
                    b, flat, world, residual=residual)
                if nr is not None:
                    new_state[gi] = nr.reshape(1, -1)
                for pi, off, n in zip(b.param_indices, b.offsets,
                                      b.numels):
                    flat_parts[pi] = reduced[off:off + n].astype(
                        flat_parts[pi].dtype)
            out = {k: fp.reshape(grads[k].shape)
                   for k, fp in zip(order, flat_parts)}
            return out, tuple(new_state)

        sync_specs = (tuple(spec for _rows, spec
                            in self._gc_res_layout(mesh))
                      if ef else ())
        return grad_sync, sync_specs

    # ------------------------------------------------------------- calls
    def __call__(self, inputs, labels=()):
        if not isinstance(inputs, (tuple, list)):
            inputs = (inputs,)
        if not self._prepared:
            self._prepare(inputs)
        return super().__call__(inputs, labels)

    def report(self) -> dict:
        """The pipeline's account of itself: analytic bubble %, schedule
        geometry, the planner verdict, and the grad_comm wire stats of the
        newest step."""
        mesh = self._pipe_mesh
        M = self._microbatches()
        P_deg = int(mesh.shape["pipe"])
        out = {
            "pipe_degree": P_deg,
            "microbatches": M,
            "stash_slots": min(M, 2 * P_deg - 1),
            "pipeline_bubble_pct": round(
                100.0 * (P_deg - 1) / (M + P_deg - 1), 3),
            "zero3_stage_params": bool(
                getattr(self.grad_fn, "zero3_stage_params", False)),
        }
        if self.memory_plan is not None:
            out["memory_plan"] = {
                "policies": list(self.memory_plan.policies),
                "stash_offload": self.memory_plan.stash_offload,
                "feasible": self.memory_plan.feasible,
                "activation_bytes_peak":
                    self.memory_plan.activation_bytes_peak,
                "reason": self.memory_plan.reason,
            }
        if self.comm_stats:
            out["grad_comm"] = dict(self.comm_stats)
        return out
