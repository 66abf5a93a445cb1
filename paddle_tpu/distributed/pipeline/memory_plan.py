"""Activation-memory planner for pipelined training (ISSUE 15).

The schedules bound activations structurally (1F1B: an S = min(M, 2P-1)
slot stash instead of GPipe's O(M) residuals); this module decides what
happens WITHIN that bound: which of a stage's layers keep their full VJP
residuals ("none"), which rematerialize from the block input ("remat"),
which push the saved input to the host tier ("offload"), and whether the
stash itself lives in host memory — all priced by
``cost_model.pipeline_cost`` against an (emulated) HBM budget, choosing
the cheapest-in-time assignment that fits.

The planner REFUSES infeasible configs with the priced reason instead of
letting XLA OOM deep inside a compile: ``plan_memory(...)`` returns a
``MemoryPlan`` whose ``feasible`` flag and ``reason`` string callers gate
on (``PipelineTrainStep`` raises the reason; bench prints it). The same
pricer with ``pipe_degree=1, microbatches=1`` prices the UNPIPELINED step
— how a too-big model is shown to not fit before the pipeline is brought
in (tests/test_memory_plan.py pins both directions).

Host offload is a memory-SPACE move, not an algorithm change: the offload
tier is the backend's "pinned_host" space (``jax.memory.Space.Host``), and
the planner selects it only where ``host_offload_supported()`` finds that
space beside a distinct device memory, or the caller forces
``allow_offload=True``.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

from ...cost_model import TARGET_DEVICE_KIND, pipeline_cost

__all__ = ["MemoryPlan", "plan_memory", "host_offload_supported",
           "gpt_activation_estimate", "plan_for_gpt"]


OFFLOAD_KIND = "pinned_host"   # the memory space the offload tier lowers to


def host_offload_supported() -> bool:
    """True when the backend exposes a host memory space DISTINCT from
    its default device memory ("pinned_host" next to "device") — the
    planner must not claim bytes it cannot move."""
    import jax

    dev = jax.devices()[0]
    kinds = {m.kind for m in dev.addressable_memories()}
    return OFFLOAD_KIND in kinds and dev.default_memory().kind != OFFLOAD_KIND


@dataclasses.dataclass(frozen=True)
class MemoryPlan:
    """One planner verdict: the per-layer policy vector for a stage, the
    stash placement, the priced cost account, and the feasibility gate."""

    policies: Tuple[str, ...]           # per layer of ONE stage
    stash_offload: bool
    stash_memory_kind: Optional[str]    # None = stash stays in HBM
    pipe_degree: int
    microbatches: int
    feasible: bool
    reason: str                         # priced explanation either way
    cost: dict                          # pipeline_cost(...) account

    @property
    def activation_bytes_peak(self) -> int:
        return int(self.cost.get("activation_bytes_peak", 0))

    @property
    def bubble_fraction(self) -> float:
        return float(self.cost.get("bubble_fraction", 0.0))

    def describe(self) -> str:
        pol = ",".join(self.policies)
        return (f"MemoryPlan(P={self.pipe_degree}, M={self.microbatches}, "
                f"policies=[{pol}], stash_offload={self.stash_offload}, "
                f"feasible={self.feasible}: {self.reason})")


def plan_memory(*, num_layers: int, pipe_degree: int, microbatches: int,
                activation_bytes_per_layer: float,
                input_bytes_per_layer: float,
                layer_flops: float,
                fixed_bytes: float = 0.0,
                hbm_budget_bytes: Optional[float] = None,
                device_kind: str = TARGET_DEVICE_KIND,
                allow_offload: Optional[bool] = None,
                host_bandwidth_bps: Optional[float] = None,
                ) -> MemoryPlan:
    """Choose the cheapest-in-time per-layer remat/offload assignment (and
    stash placement) that fits ``hbm_budget_bytes``.

    The stage's layers are homogeneous, so an assignment is fully
    described by (k_offload, k_remat): that many layers at "offload" /
    "remat", the rest "none" — the planner enumerates the O(L^2) frontier,
    prices each with ``cost_model.pipeline_cost`` (each offloaded input
    crosses the host link twice per micro-batch; each remat'd layer costs
    one extra layer-forward), and keeps the fitting assignment with the
    lowest ``time_lower_bound_s``. Without a budget the all-"none" plan
    wins by construction. Returns an INFEASIBLE plan (never raises) when
    even full offload is over budget — ``reason`` carries the priced gap.

    ``allow_offload`` defaults to :func:`host_offload_supported`.
    """
    L_total = int(num_layers)
    P = int(pipe_degree)
    if L_total % P:
        raise ValueError(
            f"num_layers={L_total} not divisible by pipe_degree={P}")
    L = L_total // P
    if allow_offload is None:
        allow_offload = host_offload_supported()
    kw = dict(pipe_degree=P, microbatches=int(microbatches),
              layers_per_stage=L,
              activation_bytes_per_layer=float(activation_bytes_per_layer),
              input_bytes_per_layer=float(input_bytes_per_layer),
              layer_flops=float(layer_flops),
              fixed_bytes=float(fixed_bytes),
              hbm_budget_bytes=hbm_budget_bytes,
              device_kind=device_kind)
    if host_bandwidth_bps is not None:
        kw["host_bandwidth_bps"] = float(host_bandwidth_bps)

    def price(k_off: int, k_rem: int, stash_off: bool) -> dict:
        pol = (["offload"] * k_off + ["remat"] * k_rem
               + ["none"] * (L - k_off - k_rem))
        return pipeline_cost(policies=pol, stash_offload=stash_off, **kw)

    def make(cost: dict, feasible: bool, reason: str) -> MemoryPlan:
        stash_off = bool(cost["stash_offload"])
        return MemoryPlan(
            policies=tuple(cost["policies"]),
            stash_offload=stash_off,
            stash_memory_kind=OFFLOAD_KIND if stash_off else None,
            pipe_degree=P, microbatches=int(microbatches),
            feasible=feasible, reason=reason, cost=cost)

    if hbm_budget_bytes is None:
        cost = price(0, 0, False)
        return make(cost, True, "no HBM budget given: all-\"none\" plan "
                                "(cheapest in time)")

    best = None
    stash_options = (False, True) if allow_offload else (False,)
    max_off = L if allow_offload else 0
    for stash_off in stash_options:
        for k_off in range(max_off + 1):
            for k_rem in range(L - k_off + 1):
                c = price(k_off, k_rem, stash_off)
                if not c["fits"]:
                    continue
                if best is None or (c["time_lower_bound_s"]
                                    < best["time_lower_bound_s"]):
                    best = c
    if best is not None:
        return make(best, True, best["why"])
    # nothing fits: report the priced gap of the most aggressive plan
    worst_case = price(max_off, L - max_off, bool(allow_offload and
                                                  stash_options[-1]))
    return make(worst_case, False,
                f"no assignment fits: even the most aggressive plan "
                f"({worst_case['why']})"
                + ("" if allow_offload else
                   "; host offload unavailable on this backend"))


# --------------------------------------------------------------- gpt glue

def gpt_activation_estimate(cfg, microbatch_size: int,
                            seq: Optional[int] = None,
                            mesh=None) -> dict:
    """Per-DEVICE activation byte/FLOP estimates for one gpt block on one
    micro-batch — the numbers ``plan_memory`` prices.

    ``activation_bytes_per_layer`` counts the VJP residuals one block keeps
    under policy "none": the block input, both LN outputs, qkv, the
    attention output, and the two MLP intermediates (~10h + 2f floats per
    token), plus the [n, s, s] softmax probabilities when the non-flash
    path runs. ``input_bytes_per_layer`` is the one [mb, s, h] block input
    "remat" keeps. Both divide by the tensor/sequence-parallel degrees the
    mesh actually shards over (the 'model' axis slices qkv/mlp widths,
    'sep' slices the sequence dim).
    """
    import numpy as np

    from ...framework import dtype as dtype_mod

    s = int(seq or cfg.max_position_embeddings)
    mb = int(microbatch_size)
    h, f, n = cfg.hidden_size, cfg.ffn, cfg.num_heads
    itemsize = np.dtype(dtype_mod.convert_dtype(cfg.dtype)).itemsize
    mp = sep = 1
    if mesh is not None:
        mp = int(mesh.shape.get("model", 1)) if "model" in mesh.axis_names \
            else 1
        sep = int(mesh.shape.get("sep", 1)) if "sep" in mesh.axis_names \
            else 1
    tok = mb * (s // sep)
    # widths sharded over 'model': qkv (3h), attn out (h), mlp (2f)
    act = tok * itemsize * (6 * h + (4 * h + 2 * f) / mp)
    flash = bool(cfg.use_flash_attention and cfg.attn_dropout == 0.0)
    if not flash:
        act += mb * (n / mp) * (s // sep) * s * 4      # fp32 softmax probs
    inp = tok * itemsize * h
    # ~6 matmuls of [tok, h]x[h, ~h..f]: 2*tok*(3h^2 + h^2 + 2*h*f) flops
    flops = 2.0 * tok * (4.0 * h * h + 2.0 * h * f) / mp \
        + 4.0 * mb * (n / mp) * (s // sep) * s * cfg.head_dim
    return {
        "activation_bytes_per_layer": float(act),
        "input_bytes_per_layer": float(inp),
        "layer_flops": float(flops),
    }


def plan_for_gpt(cfg, *, pipe_degree: int, microbatches: int,
                 global_batch: int, seq: Optional[int] = None,
                 hbm_budget_bytes: Optional[float] = None,
                 mesh=None, fixed_bytes: float = 0.0,
                 allow_offload: Optional[bool] = None,
                 device_kind: str = TARGET_DEVICE_KIND) -> MemoryPlan:
    """``plan_memory`` over a GPTConfig: derives the per-layer byte/FLOP
    estimates from the config and the mesh's sharding degrees, with the
    micro-batch size taken from ``global_batch / microbatches`` divided by
    the mesh's data-parallel degree (the per-device slice the schedule
    actually stashes)."""
    M = int(microbatches)
    if int(global_batch) % M:
        raise ValueError(
            f"global_batch={global_batch} not divisible by M={M}")
    mb = int(global_batch) // M
    if mesh is not None:
        for ax in ("data", "sharding"):
            if ax in mesh.axis_names:
                mb = max(1, mb // int(mesh.shape[ax]))
    est = gpt_activation_estimate(cfg, mb, seq, mesh)
    return plan_memory(
        num_layers=cfg.num_layers, pipe_degree=int(pipe_degree),
        microbatches=M, fixed_bytes=fixed_bytes,
        hbm_budget_bytes=hbm_budget_bytes,
        allow_offload=allow_offload, device_kind=device_kind, **est)
