"""SPMD pipeline parallelism — the real micro-batch schedules.

Reference capability: 1F1B with micro-batch overlap
(fleet/meta_parallel/pipeline_parallel.py:80-150 interleaving fwd/bwd,
pp_utils/p2p_communication.py:216-434 p2p send/recv between stage ranks,
static-graph SectionWorker paddle/fluid/framework/section_worker.cc:143-199).

TWO schedules, both collective-permute pipelines inside ONE SPMD program:

- `pipeline_spmd` — forward-only wave; training differentiates through it
  (GPipe fill-drain: AD keeps every micro-batch's residuals alive, O(M)
  activation memory). The simple/composable building block.
- `pipeline_1f1b` — the genuine 1F1B TRAIN step: forward and
  recompute-backward waves interleaved tick-by-tick with a
  min(M, 2P-1)-slot input stash, activation memory bounded by pipeline
  depth (the property the reference's schedule exists for). See its
  docstring for the wave arithmetic.

pipeline_spmd design notes:

- every pipe rank holds its stage's parameter slice (leading stacked-layer dim
  sharded over the 'pipe' mesh axis);
- micro-batches rotate through the stages with lax.ppermute: at step t, stage
  s computes micro-batch (t - s) — all stages busy in steady state, the same
  concurrency 1F1B achieves with p2p ranks;
- the loop runs M + P - 1 steps (bubble fraction (P-1)/(M+P-1), identical to
  GPipe fill/drain), with XLA overlapping each ppermute with the next step's
  compute (ICI transfer hides behind MXU work);
- backward is the TRANSPOSED pipeline: jax AD differentiates through scan +
  ppermute, yielding the reverse schedule for free — the part the reference
  spends p2p_communication.py hand-coding;
- inside the manual region tensor parallelism is explicit Megatron
  (column/row-sharded matmuls + psum over 'model') and sequence parallelism
  is the ring-attention body over 'sep' — the composition the reference
  builds from three separate communicator rings.
"""
from __future__ import annotations

from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from .. import mesh as mesh_mod


_MEMORY_SPACES = {"device": jax.memory.Space.Device,
                  "pinned_host": jax.memory.Space.Host}


def _to_memory_kind(v, kind: Optional[str]):
    """Transfer `v` to a named memory space inside the trace (no-op when
    kind is None). The stash's host-offload tier rides this:
    `kind="pinned_host"` keeps the S input slots out of device memory
    between their forward write and backward read, `kind="device"` brings
    one back."""
    if kind is None:
        return v
    return jax.device_put(v, _MEMORY_SPACES[kind])


def pipeline_spmd(
    stage_fn: Callable,
    params,
    x,
    *,
    mesh,
    param_specs,
    pipe_axis: str = "pipe",
    microbatches: Optional[int] = None,
    batch_axes: Sequence[str] = ("data", "sharding"),
    seq_axis: str = "sep",
):
    """Run `x` through a pipeline of P = mesh.shape[pipe_axis] stages.

    stage_fn(local_params, x_mb) -> y_mb applies ONE stage's layers (the
    caller scans its local layer slices). `params` is a tuple of stacked
    arrays whose leading dim is sharded over `pipe_axis` (param_specs gives
    each one's full PartitionSpec INCLUDING the leading pipe dim). x is the
    full global batch [b, ...]; it is split into `microbatches` equal
    micro-batches along dim 0 (default: the pipe degree, the minimum that
    fills the pipeline).
    """
    P_deg = int(mesh.shape[pipe_axis])
    M = int(microbatches or P_deg)
    b = x.shape[0]
    if b % M:
        raise ValueError(f"batch {b} not divisible by {M} micro-batches")
    mb = b // M
    x_mb = x.reshape(M, mb, *x.shape[1:])

    batch_tuple = tuple(a for a in batch_axes if a in mesh.axis_names) or None
    seq = seq_axis if seq_axis in mesh.axis_names else None
    # [M, mb, s, ...]: micro dim unsharded, batch over dp axes, seq over sp
    x_spec = P(None, batch_tuple, seq, *([None] * (x.ndim - 2)))

    def body(params_local, xl):
        stage = jax.lax.axis_index(pipe_axis)
        T = M + P_deg - 1
        perm = [(i, (i + 1) % P_deg) for i in range(P_deg)]
        state0 = jnp.zeros(xl.shape[1:], xl.dtype)
        out0 = jnp.zeros_like(xl)

        def step(carry, t):
            state, outs = carry
            # fill: stage 0 ingests micro-batch t (clipped during drain)
            fresh = jax.lax.dynamic_index_in_dim(
                xl, jnp.clip(t, 0, M - 1), 0, keepdims=False)
            state = jnp.where(stage == 0, fresh, state)
            y = stage_fn(params_local, state)
            # drain: micro-batch (t - P + 1) leaves the last stage at step t
            oi = t - (P_deg - 1)
            upd = jax.lax.dynamic_update_index_in_dim(
                outs, y.astype(outs.dtype), jnp.clip(oi, 0, M - 1), 0)
            outs = jnp.where(oi >= 0, upd, outs)
            # hand-off: stage s -> s+1 (wrap to 0 is overwritten by ingest)
            state = jax.lax.ppermute(y, pipe_axis, perm)
            return (state, outs), None

        (_, outs), _ = jax.lax.scan(step, (state0, out0), jnp.arange(T))
        # results live on the last stage; replicate over the pipe axis so the
        # (SPMD-replicated) head/loss can proceed on every rank
        outs = jnp.where(stage == P_deg - 1, outs, jnp.zeros_like(outs))
        return jax.lax.psum(outs, pipe_axis)

    out_mb = mesh_mod.compat_shard_map(
        body, mesh, (tuple(param_specs), x_spec), x_spec,
    )(tuple(params), x_mb)
    return out_mb.reshape(b, *x.shape[1:])


def _mb_spec(arr_ndim, batch_tuple, seq):
    """[M, mb, (seq), ...] PartitionSpec: micro dim unsharded, batch over the
    dp axes, (optional) sequence dim over sp."""
    dims = [None, batch_tuple]
    if arr_ndim >= 3:
        dims.append(seq)
    dims += [None] * (arr_ndim - len(dims))
    return P(*dims)


def _spec_axes(spec):
    """Set of mesh axis names appearing in a PartitionSpec."""
    out = set()
    for entry in (spec or ()):
        if entry is None:
            continue
        if isinstance(entry, (tuple, list)):
            out.update(entry)
        else:
            out.add(entry)
    return out


def pipeline_1f1b(
    embed_fn: Callable,
    stage_fn: Callable,
    loss_fn: Callable,
    params,
    x,
    labels,
    *,
    mesh,
    param_specs,
    pipe_axis: str = "pipe",
    microbatches: Optional[int] = None,
    batch_axes: Sequence[str] = ("data", "sharding"),
    seq_axis: str = "sep",
    natural_axes: Sequence[str] = ("model",),
    grad_sync: Optional[Callable] = None,
    sync_axes: Sequence[str] = (),
    sync_state: Sequence = (),
    sync_state_specs: Sequence = (),
    stash_memory_kind: Optional[str] = None,
):
    """Memory-bounded 1F1B pipeline TRAIN step: returns (loss, grads).

    Reference capability: the 1F1B schedule of
    fleet/meta_parallel/pipeline_parallel.py:80-150 (interleaved
    forward_backward_pipeline) and the static-graph SectionWorker
    (paddle/fluid/framework/section_worker.cc:143-199), whose point is that
    live activations are bounded by the pipeline depth P, not the
    micro-batch count M.

    TPU-native redesign — ONE SPMD scan over T = M + 2P - 1 lockstep ticks;
    the backward is hand-scheduled INSIDE the scan (no AD-of-scan residuals):

    - tick t, stage s forwards micro-batch  f = t - s            (wave down)
    - tick t, stage s backwards micro-batch b = t - (2P-1) + s   (wave up)
    - activations stashed per stage in a circular buffer of
      S = min(M, 2P-1) stage-INPUT slots — the O(P) 1F1B memory bound; the
      stage body is recomputed during the backward tick (the recompute policy
      the reference applies at scale anyway), so no other residual survives
      between ticks.
    - the backward tick takes jax.value_and_grad of a local objective
      `vdot(y, g_in)` (mid stages) or `loss_fn` (last stage, via lax.cond so
      the loss head only runs there), which yields d/d(params) and
      d/d(input) in one pass; input-grads ride the reverse ppermute.

    embed_fn(params, x_mb_raw) -> h   applied on stage 0 only (recomputed in
                                      that stage's backward ticks, so its
                                      param grads flow);
    stage_fn(params, h) -> h          one stage's blocks (P stages SPMD; pipe-
                                      stacked weights arrive pre-sliced);
    loss_fn(params, h, labels_mb) -> scalar mean loss of one micro-batch
                                      (applied on the last stage only).

    `params` is ONE pytree shared by all three fns — a weight used by both
    embed_fn and loss_fn (tied embedding) accumulates both contributions via
    the cross-stage psum. Grads are returned in float32, scaled to the mean
    over micro-batches; params sharded over `pipe_axis`/'model' stay sharded,
    everything else is reduced to replicated.

    Composition seams (ISSUE 15, consumed by PipelineTrainStep):

    - ``grad_sync(grads, state) -> (grads, new_state)`` replaces the default
      pmean over ``sync_axes`` (a subset of the batch axes): it runs INSIDE
      the shard_map body, after the pipe/sep reductions, with the grads
      still varying over ``sync_axes`` — the hook point where the quantized
      grad_comm bucket codecs reduce the data-axis wire in-trace.
      ``sync_state`` / ``sync_state_specs`` thread its carried state (the
      per-rank error-feedback residuals) through the body; the call then
      returns ``(loss, grads, *new_state)``.
    - ``stash_memory_kind`` places the S-slot input stash in a named memory
      space ("pinned_host" on TPU = the host-offload tier for the one
      per-stage activation buffer 1F1B keeps; None = HBM as before).
    """
    P_deg = int(mesh.shape[pipe_axis])
    M = int(microbatches or P_deg)
    b = x.shape[0]
    if b % M:
        raise ValueError(f"batch {b} not divisible by {M} micro-batches")
    mb = b // M
    x_mb = x.reshape(M, mb, *x.shape[1:])
    lbl_mb = labels.reshape(M, mb, *labels.shape[1:])
    S = min(M, 2 * P_deg - 1)
    T = M + 2 * P_deg - 2  # last tick index is T; loop runs T+1 ticks

    batch_tuple = tuple(a for a in batch_axes if a in mesh.axis_names) or None
    seq = seq_axis if seq_axis in mesh.axis_names else None
    x_spec = _mb_spec(x_mb.ndim, batch_tuple, seq)
    l_spec = _mb_spec(lbl_mb.ndim, batch_tuple, seq)
    mesh_axes = set(mesh.axis_names)
    # axes grad_sync reduces itself (in-trace codec collectives); the
    # default pmean skips them so the hook sees per-rank partial grads
    sync_set = (set(a for a in sync_axes if a in mesh_axes)
                if grad_sync is not None else set())
    # memory space a consumed stash slot returns to (None = no transfer)
    fetch_kind = "device" if stash_memory_kind is not None else None

    def body(params_in, xl, ll, *state):
        stage = jax.lax.axis_index(pipe_axis)
        is_first = stage == 0
        is_last = stage == P_deg - 1
        perm_fwd = [(i, (i + 1) % P_deg) for i in range(P_deg)]
        perm_bwd = [(i, (i - 1) % P_deg) for i in range(P_deg)]

        # Axes handled by vma-typed AD *inside* the per-tick VJP (the TP
        # axis: stage_fn's own psum points make JAX insert the correct
        # Megatron backward collectives there). Everything else is pre-cast
        # to device-varying BEFORE differentiation, for two reasons:
        # - the transpose of an implicit replicated->varying cast is a psum,
        #   and the VJP below runs under a lax.cond whose predicate differs
        #   across pipe ranks — a pipe-psum materializing inside those
        #   branches is a mismatched collective (observed as an XLA CPU
        #   AllReduce abort);
        # - for the batch axes it would all-reduce the full parameter grads
        #   every tick; per-rank partials reduced once after the scan ride a
        #   single collective instead.
        cast_axes = tuple(a for a in mesh.axis_names if a not in natural_axes)

        def to_varying(a, axes=cast_axes):
            have = set(jax.typeof(a).vma)
            need = tuple(ax for ax in axes if ax not in have)
            return jax.lax.pcast(a, need, to="varying") if need else a

        params_local = jax.tree.map(to_varying, params_in)

        # local activation template from the embed output
        h_tpl = jax.eval_shape(lambda p, r: embed_fn(p, r), params_local,
                               jax.eval_shape(lambda a: a[0], xl))
        h_zero = jnp.zeros(h_tpl.shape, h_tpl.dtype)

        def apply_in(p, raw, h_in):
            """Stage input: stage 0 embeds the raw micro-batch, others take
            the ppermuted activation. where() keeps it one trace; the unused
            branch's grads are zeroed by the select."""
            h_emb = embed_fn(p, raw)
            return jnp.where(is_first, h_emb, h_in)

        g0 = {
            "state": h_zero,
            "gstate": jnp.zeros(h_tpl.shape, jnp.float32),
            "stash": _to_memory_kind(
                jnp.zeros((S,) + tuple(h_tpl.shape), h_tpl.dtype),
                stash_memory_kind),
            "grads": jax.tree.map(
                lambda a: jnp.zeros(a.shape, jnp.float32), params_local),
            "loss": jnp.zeros((), jnp.float32),
        }

        def tick(carry, t, do_fwd=True, do_bwd=True):
            """One lockstep tick. do_fwd/do_bwd are PYTHON constants: the
            fill ticks (t < P) have globally no backward work and the
            drain ticks (t > M+P-2) no forward work, so the caller scans
            three specialized bodies — fwd-only fill, fwd+bwd steady,
            bwd-only drain — instead of paying both phases on all
            M+2P-1 ticks. That cuts schedule cost from 4(M+2P-1) to
            4(M+P-1)-ish work units, at or below GPipe fill-drain's,
            while keeping the O(P) stash (the test_pipeline_throughput
            tests count the units off the traced program)."""
            fwd_m = t - stage
            bwd_m = t - (2 * P_deg - 1 - stage)
            fwd_on = (fwd_m >= 0) & (fwd_m < M)
            bwd_on = (bwd_m >= 0) & (bwd_m < M)

            state_next = carry["state"]
            stash = carry["stash"]
            gstate_next = carry["gstate"]
            grads = carry["grads"]
            loss = carry["loss"]

            if do_fwd:
                # ---- forward: micro-batch fwd_m ----
                raw_f = jax.lax.dynamic_index_in_dim(
                    xl, jnp.clip(fwd_m, 0, M - 1), 0, keepdims=False)
                x_in = apply_in(params_local, raw_f, carry["state"])
                # offload tier: the slot VALUE crosses to the stash's
                # memory space before the update, so the S-slot buffer
                # never round-trips through device memory whole
                x_slot = _to_memory_kind(x_in.astype(carry["stash"].dtype),
                                         stash_memory_kind)
                stash = jnp.where(
                    fwd_on,
                    jax.lax.dynamic_update_index_in_dim(
                        carry["stash"], x_slot,
                        jnp.clip(fwd_m, 0, M - 1) % S, 0),
                    carry["stash"])
                y = stage_fn(params_local, x_in)
                state_next = jax.lax.ppermute(y.astype(h_tpl.dtype),
                                              pipe_axis, perm_fwd)

            if do_bwd:
                # ---- backward: micro-batch bwd_m (recompute + local VJP) ----
                raw_b = jax.lax.dynamic_index_in_dim(
                    xl, jnp.clip(bwd_m, 0, M - 1), 0, keepdims=False)
                lbl_b = jax.lax.dynamic_index_in_dim(
                    ll, jnp.clip(bwd_m, 0, M - 1), 0, keepdims=False)
                stash_x = jax.lax.dynamic_index_in_dim(
                    carry["stash"], jnp.clip(bwd_m, 0, M - 1) % S, 0,
                    keepdims=False)
                # offload tier: only the ONE slot being consumed returns
                # to device memory for the recompute
                stash_x = _to_memory_kind(stash_x, fetch_kind)

                def obj(p, h_stash, g_in):
                    xin = apply_in(p, raw_b, h_stash)
                    yb = stage_fn(p, xin)
                    return jax.lax.cond(
                        is_last,
                        lambda: loss_fn(p, yb, lbl_b).astype(jnp.float32),
                        lambda: jnp.vdot(yb.astype(jnp.float32), g_in),
                    )

                val, (dp, dx, _) = jax.value_and_grad(obj, argnums=(0, 1, 2))(
                    params_local, stash_x, carry["gstate"])
                grads = jax.tree.map(
                    lambda acc, g:
                        acc + jnp.where(bwd_on, g, 0.0).astype(acc.dtype),
                    carry["grads"], dp)
                loss = carry["loss"] + jnp.where(bwd_on & is_last, val, 0.0)
                gstate_next = jax.lax.ppermute(
                    jnp.where(bwd_on, dx.astype(jnp.float32), 0.0),
                    pipe_axis, perm_bwd)

            return {"state": state_next, "gstate": gstate_next,
                    "stash": stash, "grads": grads, "loss": loss}, None

        # lax.scan needs carry input and output vma types to agree; the
        # loop's fixed point depends on what stage_fn does (ppermute makes
        # values pipe-varying, a TP psum makes them model-replicated, the
        # sharded micro-batch data makes them batch-varying). Iterate
        # abstractly to the fixed point and pcast the zeros init up to it.
        for _ in range(len(mesh.axis_names) + 2):
            out_t = jax.eval_shape(lambda c: tick(c, jnp.int32(0))[0], g0)
            tgt = jax.tree.map(lambda o: frozenset(o.vma), out_t)
            cur = jax.tree.map(
                lambda a: frozenset(jax.typeof(a).vma), g0)
            if tgt == cur:
                break
            g0 = jax.tree.map(
                lambda a, o: to_varying(a, tuple(sorted(o))), g0, tgt)
        else:
            raise ValueError("1F1B carry vma types did not converge")

        # Three specialized segments (identical math to one full scan —
        # the skipped phase is exactly the one whose work every stage
        # masks to zero on those ticks):
        #   fill  t in [0, P-1]:        no stage has backward work yet
        #   steady t in [P, M+P-2]:     both waves live (M-1 ticks)
        #   drain t in [M+P-1, M+2P-2]: forward wave fully retired
        carry, _ = jax.lax.scan(
            lambda c, t: tick(c, t, do_bwd=False), g0, jnp.arange(P_deg))
        if M > 1:
            carry, _ = jax.lax.scan(
                tick, carry, jnp.arange(P_deg, M + P_deg - 1))
        final, _ = jax.lax.scan(
            lambda c, t: tick(c, t, do_fwd=False), carry,
            jnp.arange(M + P_deg - 1, T + 1))

        inv_m = np.float32(1.0 / M)

        def reduce_out(g, owned):
            """One cross-rank reduction per value: psum over pipe (only the
            owning stage produced a non-zero), pmean over every other
            still-varying axis the value is not intentionally sharded on."""
            if pipe_axis not in owned and pipe_axis in jax.typeof(g).vma:
                g = jax.lax.psum(g, pipe_axis)
            for ax in sorted(mesh_axes - owned - {pipe_axis}):
                if int(mesh.shape[ax]) > 1 and ax in jax.typeof(g).vma:
                    g = jax.lax.pmean(g, ax)
            return g

        loss = reduce_out(final["loss"] * inv_m, set())
        # grad_sync owns sync_set: the default reduction leaves those axes
        # varying (per-rank partial grads) for the hook's codec collectives
        grads = jax.tree.map(
            lambda g, spec: reduce_out(g * inv_m,
                                       _spec_axes(spec) | sync_set),
            final["grads"], param_specs)
        if grad_sync is not None:
            grads, new_state = grad_sync(grads, state)
            return (loss, grads) + tuple(new_state)
        return loss, grads

    # check_vma=True: with replication tracking on, the transpose of the TP
    # psum inside stage_fn is the (correct) identity pass-through — under
    # check_vma=False it would re-psum the already-replicated cotangent and
    # double every tensor-parallel gradient.
    in_specs = (param_specs, x_spec, l_spec) + tuple(sync_state_specs)
    out_specs = (P(), param_specs) + tuple(sync_state_specs)
    out = mesh_mod.compat_shard_map(
        body, mesh, in_specs, out_specs, check=True,
    )(params, x_mb, lbl_mb, *sync_state)
    if grad_sync is not None:
        return out[0], out[1], tuple(out[2:])
    return out
