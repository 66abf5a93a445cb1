"""paddle_tpu.jit — compiled execution.

Reference surface: paddle.jit.to_static / paddle.jit.save/load
(python/paddle/fluid/dygraph/jit.py, dygraph_to_static/). TPU-native: tracing
via the functional bridge + jax.jit; the ProgramDesc analog is the jaxpr/HLO
owned by XLA, and `TrainStep` fuses forward+backward+optimizer into ONE
compiled program — the fast path that replaces the reference's per-op executor
loop entirely.
"""
from __future__ import annotations

import functools
import os
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..framework import autograd, random as rng_mod
from ..framework.device import current_place
from ..framework.tensor import Tensor
from ..profiler import RecordEvent, now_ns
from .functional import FunctionalModule, tree_to_vals, vals_to_tensors


def _amp_fingerprint():
    """Hashable identity of the ambient AMP mode (None when off). The op
    allow/block lists are part of the identity: they are baked into the
    trace, so two policies must not share a cache entry."""
    from ..amp import amp_state

    st = amp_state()
    if st is None:
        return None
    return (st.get("level"), str(st.get("dtype")),
            frozenset(st.get("white") or ()), frozenset(st.get("black") or ()))


def _interleave_vals(mask, trk, frz):
    full, ti, fi = [], 0, 0
    for m in mask:
        if m:
            full.append(trk[ti])
            ti += 1
        else:
            full.append(frz[fi])
            fi += 1
    return full


def _abstract_key(vals):
    out = []
    for v in jax.tree_util.tree_leaves(vals):
        out.append((tuple(v.shape), str(v.dtype)) if hasattr(v, "shape") else repr(v))
    return tuple(out)


class StaticFunction:
    """@to_static product: shape-cached jitted forward.

    Inference calls run the cached executable. Calls needing grad register
    the whole compiled forward as ONE tape op whose forward AND vjp are
    jitted once per shape key (_grad_step_cached) — no per-call tracing.
    TrainStep still wins for full training loops because it fuses the
    optimizer update into the same program.
    """

    def __init__(self, layer_or_fn, input_spec=None):
        from ..nn import Layer

        if isinstance(layer_or_fn, Layer):
            self.layer = layer_or_fn
            self.fn = None
        else:
            self.layer = getattr(layer_or_fn, "__self__", None)
            self.fn = layer_or_fn
        self.fm = FunctionalModule(self.layer) if self.layer is not None else None
        self._cache: Dict[Any, Callable] = {}

    def _pure(self, training):
        fm = self.fm

        def pure(pvals, bvals, key, args, kwargs):
            fn = None
            if self.fn is not None:
                fn = lambda layer, *a, **k: self.fn.__func__(layer, *a, **k)  # noqa: E731
            return fm.call(pvals, bvals, key, args, kwargs, training=training, fn=fn)

        return pure

    def __call__(self, *args, **kwargs):
        if not _to_static_state["enabled"]:
            # conversion globally off: run the original code eagerly
            if self.fn is not None:
                if self.layer is not None and hasattr(self.fn, "__func__"):
                    return self.fn.__func__(self.layer, *args, **kwargs)
                return self.fn(*args, **kwargs)
            return self.layer.forward(*args, **kwargs)
        if self.fm is None:
            # plain function: jit directly with shape cache
            key = ("fn", _abstract_key(tree_to_vals(args)))
            if key not in self._cache:
                f = self.fn

                def pure(a, kw):
                    ta = vals_to_tensors(a)
                    tk = vals_to_tensors(kw)
                    with autograd.no_grad():
                        return tree_to_vals(f(*ta, **tk))

                self._cache[key] = jax.jit(pure)
            out = self._cache[key](tree_to_vals(args), tree_to_vals(kwargs))
            return vals_to_tensors(out)

        fm = self.fm
        training = self.layer.training
        arg_vals = tree_to_vals(args)
        kw_vals = tree_to_vals(kwargs)
        # grad needed for trainable params OR differentiable inputs (an
        # all-frozen feature extractor must still propagate dL/dx)
        input_needs_grad = any(
            isinstance(o, Tensor) and not o.stop_gradient
            and hasattr(o._value, "dtype")
            and jnp.issubdtype(o._value.dtype, jnp.inexact)
            for o in jax.tree_util.tree_flatten((args, kwargs))[0])
        need_grad = autograd.is_grad_enabled() and (
            any(fm.trainable_mask) or input_needs_grad)
        rng_key = rng_mod.next_key()

        # AMP is ambient python state read while tracing, so it must be part
        # of the cache identity: toggling auto_cast between same-shape calls
        # must not reuse a trace baked under the other mode
        ckey = (training, need_grad, _abstract_key(arg_vals),
                _abstract_key(kw_vals), _amp_fingerprint())
        if ckey not in self._cache:
            pure = self._pure(training)
            self._cache[ckey] = jax.jit(pure)
        jitted = self._cache[ckey]

        if not need_grad:
            out_vals, new_b = jitted(fm.param_values(), fm.buffer_values(), rng_key,
                                     arg_vals, kw_vals)
            fm.bind_buffers(new_b)
            return vals_to_tensors(out_vals)

        # grad path: whole compiled forward as one tape op over trainable params
        # + floating inputs
        bvals = fm.buffer_values()
        frozen = [v for v, m in zip(fm.param_values(), fm.trainable_mask) if not m]

        flat_args, args_treedef = jax.tree_util.tree_flatten((arg_vals, kw_vals))
        n_params = sum(fm.trainable_mask)

        tracked_tensors = [p for p, m in zip(fm.params, fm.trainable_mask) if m]
        # keep the ORIGINAL arg Tensors for tape linkage (a fresh wrapper
        # would sever the user's x from the grad graph and default to
        # stop_gradient=True, silently dropping input grads)
        flat_orig = jax.tree_util.tree_flatten((args, kwargs))[0]
        input_tensors = [
            o if isinstance(o, Tensor) else Tensor(v, _internal=True)
            for o, v in zip(flat_orig, flat_args)
        ]

        if autograd._op_recorder is None:
            # fast path (VERDICT r1 weak #5): jitted forward + jitted vjp
            # cached per shape key — NO per-call tracing. The tape GradNode
            # is wired directly, exactly as call_op would.
            return self._grad_step_cached(
                ckey, jitted, args_treedef, tracked_tensors, input_tensors,
                frozen, bvals, rng_key)

        out_struct = {}

        def op_fn(*tracked):
            full_p = _interleave_vals(fm.trainable_mask,
                                      list(tracked[:n_params]), frozen)
            a_vals, k_vals = jax.tree_util.tree_unflatten(
                args_treedef, list(tracked[n_params:])
            )
            out_vals, new_b = jitted(full_p, bvals, rng_key, a_vals, k_vals)
            flat_out, treedef = jax.tree_util.tree_flatten(out_vals)
            out_struct["treedef"] = treedef
            out_struct["n_out"] = len(flat_out)
            return tuple(flat_out) + tuple(new_b)

        res = autograd.call_op(op_fn, *tracked_tensors, *input_tensors,
                               op_name="to_static")
        if not isinstance(res, tuple):
            res = (res,)
        n_out = out_struct["n_out"]
        out_flat, buf_out = res[:n_out], res[n_out:]
        for b, t in zip(fm.buffers, buf_out):
            b._value = t._value
        out_vals = jax.tree_util.tree_unflatten(out_struct["treedef"], list(out_flat))
        return jax.tree_util.tree_map(
            lambda v: v if isinstance(v, Tensor) else Tensor(v, _internal=True),
            out_vals,
        )

    def _grad_step_cached(self, ckey, jitted, args_treedef, tracked_tensors,
                          input_tensors, frozen, bvals, rng_key):
        """Cached-jit grad dispatch: one jitted forward and one jitted vjp
        per (training, shapes) key. Replaces the per-call ``jax.vjp``
        re-trace of the whole model body with two compiled calls."""
        from ..amp import amp_cast_inputs, amp_state
        from ..framework.autograd import _is_floating

        fm = self.fm
        mask = fm.trainable_mask

        def _arr(v):
            return hasattr(v, "shape") and hasattr(v, "dtype")

        # AMP input casting, as call_op would apply (amp_auto_cast.cc
        # analog): tracked params + array input leaves are the op's tensor
        # args; python-scalar leaves pass through untouched (weak-typed)
        trk_vals = [t._value for t in tracked_tensors]
        leaf_vals = [t._value for t in input_tensors]
        if amp_state() is not None:
            n_trk = len(trk_vals)
            arr_pos = [i for i, v in enumerate(leaf_vals) if _arr(v)]
            cast = amp_cast_inputs(
                "to_static", trk_vals + [leaf_vals[i] for i in arr_pos])
            trk_vals = cast[:n_trk]
            for j, i in enumerate(arr_pos):
                leaf_vals[i] = cast[n_trk + j]
        trk_vals = tuple(trk_vals)
        leaf_vals = tuple(leaf_vals)
        # diff positions among input leaves (params always differentiate)
        diff_inputs = [
            i for i, t in enumerate(input_tensors)
            if not t.stop_gradient and _arr(t._value)
            and _is_floating(t._value)
        ]
        # key on post-cast dtypes + pytree structure (leaf shapes alone
        # can't distinguish two kwarg spellings with identical shapes);
        # python scalars are traced weak-typed, keyed by type only
        sig = tuple((tuple(v.shape), str(v.dtype)) if _arr(v)
                    else ("py", type(v).__name__)
                    for v in trk_vals + leaf_vals)
        gkey = ("gradjit", ckey, tuple(diff_inputs), sig, args_treedef)
        entry = self._cache.get(gkey)
        if entry is None:
            def run(trk, leaves, frz, bv, key):
                a_vals, k_vals = jax.tree_util.tree_unflatten(
                    args_treedef, list(leaves))
                # pytree output: the treedef is read off the first real call
                return jitted(_interleave_vals(mask, trk, frz),
                              list(bv), key, a_vals, k_vals)

            def bwd(trk, leaves, frz, bv, key, cots):
                def closure(trk_d, leaves_d):
                    merged = list(leaves)
                    for j, i in enumerate(diff_inputs):
                        merged[i] = leaves_d[j]
                    out_vals, new_b = run(trk_d, merged, frz, bv, key)
                    return (tuple(jax.tree_util.tree_leaves(out_vals)) +
                            tuple(new_b))

                _, vjp_fn = jax.vjp(
                    closure, tuple(trk),
                    tuple(leaves[i] for i in diff_inputs))
                g_trk, g_in = vjp_fn(tuple(cots))
                return tuple(g_trk) + tuple(g_in)

            entry = {"fwd": jax.jit(run), "bwd": jax.jit(bwd),
                     "bwd_raw": bwd}
            self._cache[gkey] = entry

        frz = tuple(frozen)
        bv = tuple(bvals)
        if autograd._op_profiler is not None:
            t0 = now_ns()
            out_vals_tree, new_b = entry["fwd"](trk_vals, leaf_vals, frz, bv,
                                                rng_key)
            autograd._op_profiler("to_static", t0, now_ns())
        else:
            out_vals_tree, new_b = entry["fwd"](trk_vals, leaf_vals, frz, bv,
                                                rng_key)
        flat_out, out_treedef = jax.tree_util.tree_flatten(out_vals_tree)
        for b, v in zip(fm.buffers, new_b):
            b._value = v

        bwd_jit = entry["bwd"]

        def vjp_fn(cots):
            cot_list = list(cots) if isinstance(cots, (tuple, list)) else [cots]
            if any(getattr(c, "dtype", None) == jax.dtypes.float0
                   for c in jax.tree_util.tree_leaves(cot_list)):
                # float0 (int-output) cotangents can't cross jit; rare —
                # run the same bwd body unjitted
                return entry["bwd_raw"](trk_vals, leaf_vals, frz, bv,
                                        rng_key, tuple(cot_list))
            return bwd_jit(trk_vals, leaf_vals, frz, bv, rng_key,
                           tuple(cot_list))

        all_outs = tuple(flat_out) + tuple(new_b)
        out_avals = [jax.ShapeDtypeStruct(o.shape, o.dtype) for o in all_outs]
        diff_tensors = list(tracked_tensors) + [input_tensors[i]
                                                for i in diff_inputs]
        node = autograd.GradNode(
            vjp_fn,
            [(t, t._grad_node, t._out_index) for t in diff_tensors],
            out_avals,
            True,
            name="to_static",
        )
        res = autograd._wrap_outputs(all_outs, node=node, op_name="to_static")
        out_flat = res[:len(flat_out)]
        out_vals = jax.tree_util.tree_unflatten(out_treedef, list(out_flat))
        return jax.tree_util.tree_map(
            lambda v: v if isinstance(v, Tensor) else Tensor(v, _internal=True),
            out_vals,
        )


def to_static(function=None, input_spec=None, build_strategy=None, backend=None):
    """paddle.jit.to_static decorator (fluid/dygraph/jit.py:to_static)."""

    def decorate(f):
        from ..nn import Layer
        from .dy2static import transform_function

        if isinstance(f, Layer):
            fwd = f.forward.__get__(f) if hasattr(f.forward, "__get__") \
                else f.forward
            f.forward = StaticFunction(transform_function(fwd))
            return f
        return StaticFunction(transform_function(f))

    if function is not None:
        return decorate(function)
    return decorate


def not_to_static(fn):
    fn._not_to_static = True
    return fn


class TrainStep:
    """One fused, compiled training step: forward + backward + optimizer.

    (loss computation included). The replacement for the reference's executor
    hot loop (§3.1) — everything lands in one XLA program; params/opt slots are
    donated so updates happen in place in HBM.

        step = TrainStep(model, loss_fn, optimizer)
        loss = step(inputs=(x,), labels=(y,))   # params updated in place
        # loss_fn is called as loss_fn(*model_outputs, *labels)

    `grad_comm` (a GradCommConfig or codec name) expresses the data-parallel
    gradient all-reduce EXPLICITLY inside the compiled program (ISSUE 8 /
    EQuARX): the forward+backward runs as explicit SPMD over the mesh's
    batch axes (shard_map), each grad bucket is quantized with the
    configured wire codec, psum'd as integers, and dequantized — all
    in-trace, so XLA's latency-hiding scheduler overlaps the (up to 4x
    smaller) transfers with compute. The cross-step error-feedback residual
    is CARRIED STATE of the jitted step: an in/out pytree threaded through
    every call, checkpointed via `grad_comm_communicator.state_dict()`
    (robustness/distributed_ft.capture_job_state(train_step=...)), so
    crash->resume stays bit-identical. Without a >1-replica batch axis the
    knob is inert and the step compiles exactly as before.
    """

    def __init__(self, model, loss_fn, optimizer, grad_accum_steps=1,
                 batch_spec=None, grad_fn=None, grad_comm=None):
        self.model = model
        self.loss_fn = loss_fn
        self.optimizer = optimizer
        self.fm = FunctionalModule(model)
        self.grad_accum = int(grad_accum_steps)
        # optional external loss+grad engine (e.g. the 1F1B pipeline
        # schedule): grad_fn(train_p, frozen_p, bvals, key, ins, lbls) ->
        # (loss, grads_in_train_p_order); optimizer update/clip/shardings
        # stay the standard path
        self.grad_fn = grad_fn
        # in-trace quantized gradient all-reduce (distributed/grad_comm.py
        # codecs); the communicator owns the bucket plan and the
        # error-feedback residual store between steps
        self._gc_comm = None
        self.comm_stats = None
        if grad_comm is not None:
            from ..distributed.grad_comm import (GradCommConfig,
                                                 GradCommunicator)

            if isinstance(grad_comm, str):
                grad_comm = GradCommConfig(codec=grad_comm)
            if self.grad_accum > 1 or (
                    grad_fn is not None
                    and not getattr(grad_fn, "handles_grad_comm", False)):
                raise ValueError(
                    "TrainStep(grad_comm=...) expresses the gradient "
                    "all-reduce explicitly in-trace; it supports the "
                    "plain fused step (grad_accum_steps == 1) or an "
                    "external grad_fn that marks handles_grad_comm (the "
                    "1F1B pipeline engine) — not this combination")
            self._gc_comm = GradCommunicator(grad_comm)
        self._cache: Dict[Any, Callable] = {}
        self._slots = None
        # True from a (re-)import of optimizer state until the next call
        self._fresh_state = False
        self._accum = None
        self._accum_count = 0
        # newest cache entry, and each entry's abstract call signature
        # (made at the entry's first call), kept so memory_analysis() can
        # AOT-lower the exact compiled program
        self._last_ckey = None
        self._abstract: Dict[Any, Any] = {}
        # distributed: PartitionSpec for data batches (defaults to sharding the
        # leading dim over the 'data' axis when a mesh is active)
        self._batch_spec = batch_spec

    def _mesh(self):
        from ..distributed import mesh as mesh_mod

        m = mesh_mod.get_mesh()
        if m is not None and m.size > 1:
            return m
        return None

    @property
    def _last_abstract(self):
        """The newest entry's arguments as ShapeDtypeStructs (None before
        its first call)."""
        return self._abstract.get(self._last_ckey)

    # ------------------------------------------- in-trace quantized comm
    @property
    def grad_comm_communicator(self):
        """The GradCommunicator carrying this step's in-trace error-feedback
        residuals (None without grad_comm=). Its state_dict()/
        load_state_dict() are the resume surface — capture_job_state
        (robustness/distributed_ft) accepts it as `reducer` (or this whole
        step as `train_step=`)."""
        return self._gc_comm

    def _gc_world(self, mesh):
        """(axes, world) of the in-trace gradient all-reduce: the mesh's
        >1-sized batch axes. world <= 1 leaves the codec path inert —
        a single replica has no wire to compress."""
        if mesh is None or self._gc_comm is None:
            return (), 1
        axes = tuple(ax for ax in ("data", "sharding")
                     if ax in mesh.axis_names and mesh.shape[ax] > 1)
        world = 1
        for ax in axes:
            world *= mesh.shape[ax]
        return axes, world

    def _gc_res_layout(self, mesh):
        """Per-bucket (rows, PartitionSpec) of the carried error-feedback
        residuals: each bucket's residual stacks one row per rank that
        quantizes its own distinct shard. Here every bucket reduces over
        the batch axes, so rows = the reducing world and the spec is the
        batch spec. PipelineTrainStep refines this per bucket — a bucket
        of pipe-OWNED grads has per-(pipe x data)-rank residuals, a
        replicated-param bucket per-data-rank only (a wider spec would
        re-vary the replicated grads and break the schedule's output
        replication)."""
        from jax.sharding import PartitionSpec as P

        from ..distributed import mesh as mesh_mod

        spec = mesh_mod.sanitize_spec(
            self._batch_spec or P(("data", "sharding")), mesh)
        world = self._gc_world(mesh)[1]
        return [(world, spec) for _ in self._gc_buckets()]

    def _gc_buckets(self):
        """Bucket plan over the trainable params (cached by the
        communicator; identical on every rank by construction)."""
        fm = self.fm
        train_params = [p for p, m in zip(fm.params, fm.trainable_mask)
                        if m]
        dtypes = [np.dtype(p._value.dtype) for p in train_params]
        return self._gc_comm.buckets_for(train_params, dtypes=dtypes)

    def _gc_error_feedback(self) -> bool:
        from ..distributed.grad_comm import EF_CODECS

        cfg = self._gc_comm.config
        return cfg.error_feedback and cfg.codec in EF_CODECS

    def _account_gc_step(self, buckets, world):
        """Per-EXECUTED-step wire accounting for the in-trace sync. The
        traced python runs once at compile time, so the compiled program
        cannot count itself — the wire bytes per step are static (bucket
        plan x codec), so each host-side call records one sync into the
        grad_comm metric families with path="traced"."""
        from ..distributed import grad_comm as gc_mod

        cfg = self._gc_comm.config
        comm_bytes = collectives = 0
        for b in buckets:
            if cfg.codec in gc_mod.BLOCK_CODECS:
                comm_bytes += (b.size * gc_mod._WIRE_ITEMSIZE[cfg.codec]
                               + gc_mod.scale_bytes(b.size, cfg.block_size))
                collectives += 2
            elif cfg.codec == "int8":
                comm_bytes += b.size * 1 + 4
                collectives += 2
            elif cfg.codec == "bf16" and b.dtype.itemsize > 2:
                comm_bytes += b.size * 2
                collectives += 1
            else:
                comm_bytes += b.nbytes
                collectives += 1
        gc_mod.record_sync_metrics(cfg.codec, collectives, comm_bytes,
                                   "traced")
        self.comm_stats = {"codec": cfg.codec, "path": "traced",
                           "world": int(world), "n_buckets": len(buckets),
                           "collectives": collectives,
                           "comm_bytes": comm_bytes}
        self._gc_comm.stats = dict(self.comm_stats)

    def _shardings(self, train_p_tensors, slots, in_vals, lbl_vals,
                   gc_res=()):
        """NamedShardings for (train_p, frozen_p, bvals, slots, gc_res,
        key, lr, ins, lbls)."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        m = self._mesh()
        from ..distributed import mesh as mesh_mod

        def pspec(p):
            spec = p.dist_spec if getattr(p, "dist_spec", None) is not None else P()
            return mesh_mod.sanitize_spec(spec, m)

        def ns(spec):
            return NamedSharding(m, spec)

        fm = self.fm
        train_params = [p for p, msk in zip(fm.params, fm.trainable_mask) if msk]
        frozen_params = [p for p, msk in zip(fm.params, fm.trainable_mask) if not msk]
        tp_sh = [ns(pspec(p)) for p in train_params]
        fp_sh = [ns(pspec(p)) for p in frozen_params]
        b_sh = [ns(P()) for _ in fm.buffers]
        # ZeRO stage-1/2 (group_sharded 'os'/'os_g'): slots of replicated
        # params still shard over the 'sharding' axis when the optimizer is
        # marked by group_sharded_parallel (distributed/sharding)
        slot_axis = getattr(self.optimizer, "_slot_shard_axis", None)
        slot_deg = m.shape[slot_axis] if (
            slot_axis and m is not None and slot_axis in m.axis_names) else 1

        def slot_spec(p, v):
            if getattr(v, "shape", ()) != tuple(p._value.shape):
                return P()
            from ..distributed.sharding import zero_slot_spec

            return zero_slot_spec(v.shape, pspec(p), slot_axis, slot_deg)

        slot_sh = []
        for p, s in zip(train_params, slots):
            slot_sh.append({k: ns(slot_spec(p, v)) for k, v in s.items()})
        bs = mesh_mod.sanitize_spec(self._batch_spec or P(("data", "sharding")), m)
        data_sh = jax.tree_util.tree_map(
            lambda v: ns(bs if getattr(v, "ndim", 0) >= 1 else P()), in_vals
        )
        lbl_sh = jax.tree_util.tree_map(
            lambda v: ns(bs if getattr(v, "ndim", 0) >= 1 else P()), lbl_vals
        )
        # error-feedback residuals are PER-RANK state (each replica's own
        # local quantization error), carried stacked on a leading world dim
        # and sharded per _gc_res_layout — declaring them replicated would
        # let a host round-trip (checkpoint!) collapse every rank's
        # residual onto rank 0's
        gc_sh = ([ns(spec) for (_r, spec) in self._gc_res_layout(m)]
                 if gc_res else [])
        return (tp_sh, fp_sh, b_sh, slot_sh, gc_sh, ns(P()), ns(P()),
                data_sh, lbl_sh), (ns(P()), tp_sh, b_sh, slot_sh)

    def _build(self):
        fm = self.fm
        opt = self.optimizer
        loss_fn = self.loss_fn
        mask = fm.trainable_mask
        clip_cfg = opt._clip_cfg()
        lr_mults = [
            float(getattr(p, "optimize_attr", {}).get("learning_rate", 1.0))
            for p, m in zip(fm.params, mask) if m
        ]
        wds = [opt._param_wd(p) for p, m in zip(fm.params, mask) if m]
        # keep updated params/opt-state pinned to their shardings in-trace
        mesh = self._mesh()
        param_sh = None
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            from ..distributed import mesh as mesh_mod

            param_sh = [
                NamedSharding(mesh, mesh_mod.sanitize_spec(
                    p.dist_spec if getattr(p, "dist_spec", None) is not None
                    else P(), mesh))
                for p, msk in zip(fm.params, mask) if msk
            ]

        split_params = fm.split_values
        merge_params = fm.merge_values

        accum = max(1, self.grad_accum)

        # ---- in-trace quantized gradient all-reduce (ISSUE 8 / EQuARX):
        # forward+backward as explicit SPMD over the batch axes so the
        # backward produces LOCAL grads, then quantize -> psum-of-int ->
        # dequantize per bucket inside the same trace. gc_step is the whole
        # replacement for the jax.value_and_grad branch below.
        gc_comm = self._gc_comm
        gc_axes, gc_world = self._gc_world(mesh)
        gc_on = gc_comm is not None and gc_world > 1
        gc_step = None
        gc_fused = None
        if gc_on and self.grad_fn is None:
            from jax.sharding import PartitionSpec as P

            from ..distributed import collective as _coll
            from ..distributed import mesh as mesh_lib
            from ..distributed.collective import ReduceOp as _ROp
            from ..framework.tensor import Tensor as _T

            gc_buckets = self._gc_buckets()
            gc_ef = self._gc_error_feedback()
            # ISSUE 13 follow-on: with the kernel flag on, a blockwise
            # codec, a fusable elementwise rule and uniform per-bucket
            # hyperparameters (no clip — it needs the decoded grads), the
            # compiled step keeps the SUMMED WIRE PAYLOAD and the fused
            # dequant+update kernel consumes it per flat bucket — the
            # decoded gradient never materializes in HBM. Flag off (or
            # any precondition missing): the jnp decode path below runs
            # byte-for-byte as before.
            from ..distributed.grad_comm import BLOCK_CODECS as _BLK
            from ..framework.flags import flag as _ka_flag

            if (_ka_flag("FLAGS_kernel_autotune")
                    and gc_comm.config.codec in _BLK
                    and clip_cfg is None and accum == 1):
                from ..ops.pallas import fused_update as _fu

                _spec = _fu.rule_spec(opt)
                if _spec is not None:
                    hypers = []
                    for b in gc_buckets:
                        lms = {lr_mults[pi] for pi in b.param_indices}
                        bwds = {wds[pi] for pi in b.param_indices}
                        if len(lms) > 1 or len(bwds) > 1:
                            hypers = None
                            break
                        hypers.append((lms.pop(), bwds.pop()))
                    if hypers is not None:
                        gc_fused = {"kind": _spec[0], "hyper": _spec[1],
                                    "bucket_hypers": hypers,
                                    "slot_names": _fu._slot_names(
                                        _spec[0])}
            if gc_comm.group is None or \
                    tuple(gc_comm.group.axes) != gc_axes:
                gc_comm.group = _coll.new_group(axes=gc_axes)
            gc_group = gc_comm.group
            bs_spec = mesh_lib.sanitize_spec(
                self._batch_spec or jax.sharding.PartitionSpec(
                    ("data", "sharding")), mesh)

            def _bspec(v):
                return bs_spec if getattr(v, "ndim", 0) >= 1 else P()

            def gc_step(train_p, frozen_p, bvals, gc_res, key, in_vals,
                        lbl_vals):
                in_specs_d = jax.tree_util.tree_map(_bspec, in_vals)
                lbl_specs = jax.tree_util.tree_map(_bspec, lbl_vals)

                def body(tp, fp, bv, res, k, ins, lbls):
                    def local_loss(tp_, bv_, ins_, lbls_, k_):
                        pv = merge_params(list(tp_), list(fp))
                        out_vals, new_b = fm.call(pv, list(bv_), k_, ins_,
                                                  training=True)
                        outs = vals_to_tensors(out_vals)
                        largs = (list(outs) if isinstance(outs,
                                                          (tuple, list))
                                 else [outs])
                        largs += list(vals_to_tensors(lbls_))
                        with autograd.no_grad(), jax.named_scope("loss"):
                            loss_t = loss_fn(*largs)
                        return (loss_t._value.astype(jnp.float32),
                                (new_b, out_vals))

                    (loss, (new_b, out_vals)), grads = jax.value_and_grad(
                        local_loss, has_aux=True)(tuple(tp), bv, ins,
                                                  lbls, k)
                    # shard-local mean loss -> global mean (equal shards)
                    lt = _T(loss, _internal=True)
                    _coll.all_reduce(lt, op=_ROp.AVG, group=gc_group)
                    loss = lt._value
                    # quantized bucket all-reduce with the error-feedback
                    # residual threaded through as carried state. Each
                    # residual is PER-RANK (this replica's own quantization
                    # error): carried stacked on a leading world dim and
                    # sharded over the batch axes, so the body sees its own
                    # (1, n) row — and a host round trip (checkpoint)
                    # preserves every rank's row instead of collapsing all
                    # onto rank 0's
                    grads = list(grads)
                    new_res = list(res)
                    payloads = []
                    for gi, b in enumerate(gc_buckets):
                        if len(b.param_indices) == 1:
                            flat = grads[b.param_indices[0]].reshape(-1)
                        else:
                            flat = jnp.concatenate(
                                [grads[pi].reshape(-1)
                                 for pi in b.param_indices])
                        residual = res[gi].reshape(-1) if gc_ef else None
                        if gc_fused is not None:
                            # keep the summed wire payload; the fused
                            # kernel dequantizes inside the update
                            q_sum, scales, nr, _w, _c = \
                                gc_comm.reduce_bucket_payload(
                                    b, flat, gc_world, residual=residual)
                            payloads.append((q_sum, scales))
                            if nr is not None:
                                new_res[gi] = nr.reshape(1, -1)
                            continue
                        reduced, nr, _w, _c = gc_comm.reduce_bucket(
                            b, flat, gc_world, residual=residual)
                        if nr is not None:
                            new_res[gi] = nr.reshape(1, -1)
                        for pi, off, n, shape in zip(
                                b.param_indices, b.offsets, b.numels,
                                b.shapes):
                            grads[pi] = reduced[off:off + n].reshape(
                                shape).astype(grads[pi].dtype)
                    if gc_fused is not None:
                        grads = tuple(payloads)
                    # clip AFTER the sync — global-gradient semantics,
                    # same as the implicit-psum path
                    if clip_cfg is not None:
                        with jax.named_scope("clip"):
                            grads = _apply_clip(grads, clip_cfg)
                    # floating buffers computed on the batch shard average
                    # back to one replicated value
                    rep_b = []
                    for v in new_b:
                        if hasattr(v, "dtype") and jnp.issubdtype(
                                v.dtype, jnp.inexact):
                            bt = _T(v, _internal=True)
                            _coll.all_reduce(bt, op=_ROp.AVG,
                                             group=gc_group)
                            v = bt._value
                        rep_b.append(v)
                    return (loss, out_vals, tuple(grads), tuple(rep_b),
                            tuple(new_res))

                f = mesh_lib.compat_shard_map(
                    body, mesh,
                    in_specs=(P(), P(), P(), bs_spec, P(), in_specs_d,
                              lbl_specs),
                    out_specs=(P(), bs_spec, P(), P(), bs_spec))
                loss, out_vals, grads, new_b, new_res = f(
                    tuple(train_p), tuple(frozen_p), tuple(bvals),
                    tuple(gc_res), key, in_vals, lbl_vals)
                # pin the (batch-sharded) outputs' sharding in-trace:
                # with out_shardings left to XLA, the donation aliaser
                # would otherwise pair a replicated donated param with a
                # same-global-shape sharded output and fail on the local
                # byte-size mismatch
                out_ns = jax.sharding.NamedSharding(mesh, bs_spec)
                out_vals = jax.tree_util.tree_map(
                    lambda v: (jax.lax.with_sharding_constraint(v, out_ns)
                               if getattr(v, "ndim", 0) >= 1 else v),
                    out_vals)
                return loss, out_vals, grads, new_b, new_res

        def _gc_fused_update(train_p, slots, payloads, lr):
            """Per-bucket fused dequant+optimizer-update: the summed
            blockwise payload feeds ops/pallas/fused_update directly on
            the flat bucket; per-param values and slots are views split
            back out (the same split the jnp path's scatter does), with
            the scalar slots (beta pows) shared bucket-wide — exact
            because every param steps with identical betas."""
            from ..ops.pallas.fused_update import fused_dequant_update_flat

            kind, hyper = gc_fused["kind"], gc_fused["hyper"]
            names = gc_fused["slot_names"]
            new_tp = list(train_p)
            new_slots = [dict(s) for s in slots]

            def cat(vals):
                return vals[0] if len(vals) == 1 else jnp.concatenate(vals)

            for b, (q_sum, scales), (lm, wd) in zip(
                    gc_buckets, payloads, gc_fused["bucket_hypers"]):
                flat_p = cat([train_p[pi].reshape(-1)
                              for pi in b.param_indices])
                first = slots[b.param_indices[0]]
                flat_slots = {
                    nm: cat([slots[pi][nm].reshape(-1)
                             for pi in b.param_indices]) for nm in names}
                for k2, v2 in first.items():
                    if k2 not in names:
                        flat_slots[k2] = v2      # scalar slots
                new_flat, new_s = fused_dequant_update_flat(
                    flat_p, q_sum, scales, gc_world, flat_slots, lr,
                    kind=kind, hyper=hyper,
                    block_size=gc_comm.config.block_size,
                    bucket_dtype=b.dtype, lm=lm, wd=wd)
                scalars = {k2: v2 for k2, v2 in new_s.items()
                           if k2 not in names}
                for pi, off, n, shape in zip(b.param_indices, b.offsets,
                                             b.numels, b.shapes):
                    np_ = new_flat[off:off + n].reshape(shape).astype(
                        train_p[pi].dtype)
                    sdict = {nm: new_s[nm][off:off + n].reshape(shape)
                             for nm in names}
                    sdict.update(scalars)
                    if param_sh is not None:
                        np_ = jax.lax.with_sharding_constraint(
                            np_, param_sh[pi])
                        sdict = {
                            k2: jax.lax.with_sharding_constraint(
                                v2, param_sh[pi])
                            if getattr(v2, "shape", ()) == tuple(shape)
                            else v2
                            for k2, v2 in sdict.items()}
                    new_tp[pi] = np_
                    new_slots[pi] = sdict
            return new_tp, new_slots

        def pure_step(train_p, frozen_p, bvals, slots, gc_res, key, lr,
                      in_vals, lbl_vals):
            def loss_of(tp, bv, ins, lbls, k):
                pv = merge_params(tp, frozen_p)
                out_vals, new_b = fm.call(pv, bv, k, ins, training=True)
                outs = vals_to_tensors(out_vals)
                largs = list(outs) if isinstance(outs, (tuple, list)) else [outs]
                largs += list(vals_to_tensors(lbls))
                # the device trace's scopes (jax.named_scope reaches each
                # op's name there): every model gets `loss`, `clip` and
                # `optimizer`, and names its own layers
                with autograd.no_grad(), jax.named_scope("loss"):
                    loss_t = loss_fn(*largs)
                return loss_t._value.astype(jnp.float32), (new_b, out_vals)

            new_gc_res = tuple(gc_res)
            if gc_step is not None:
                loss, out_vals, grads, new_b, new_gc_res = gc_step(
                    train_p, frozen_p, bvals, gc_res, key, in_vals,
                    lbl_vals)
                new_b = list(new_b)   # pytree parity with fm.call's output
                if gc_fused is not None:
                    # `grads` carries the per-bucket wire payloads; the
                    # fused kernel dequantizes inside the update
                    with jax.named_scope("optimizer"):
                        new_tp, new_slots = _gc_fused_update(
                            train_p, slots, grads, lr)
                    return (loss, new_tp, new_b, new_slots, new_gc_res,
                            out_vals)
            elif self.grad_fn is not None:
                if getattr(self.grad_fn, "handles_grad_comm", False) \
                        and gc_on:
                    # the grad engine (1F1B pipeline) runs the quantized
                    # reduction inside its own shard_map body and threads
                    # the error-feedback residuals as carried state
                    loss, grads, new_gc_res = self.grad_fn(
                        train_p, frozen_p, bvals, gc_res, key, in_vals,
                        lbl_vals)
                    new_gc_res = tuple(new_gc_res)
                else:
                    loss, grads = self.grad_fn(
                        train_p, frozen_p, bvals, key, in_vals, lbl_vals)
                loss = loss.astype(jnp.float32)
                new_b, out_vals = bvals, ()
            elif accum == 1:
                (loss, (new_b, out_vals)), grads = jax.value_and_grad(
                    loss_of, has_aux=True
                )(train_p, bvals, in_vals, lbl_vals, key)
            else:
                # micro-batch accumulation: split the leading batch dim into
                # `accum` chunks and scan, averaging grads — one optimizer
                # update per call (reference: GradientMergeOptimizer /
                # pipeline accumulate_steps)
                def reshape_micro(v):
                    return v.reshape((accum, v.shape[0] // accum) + v.shape[1:])

                m_ins = jax.tree_util.tree_map(reshape_micro, in_vals)
                m_lbls = jax.tree_util.tree_map(reshape_micro, lbl_vals)
                keys = jax.random.split(key, accum)

                def micro(carry, xs):
                    bv, gacc = carry
                    ins, lbls, k = xs
                    (l, (nb, ov)), g = jax.value_and_grad(loss_of, has_aux=True)(
                        train_p, bv, ins, lbls, k
                    )
                    gacc = jax.tree_util.tree_map(jnp.add, gacc, g)
                    return (nb, gacc), (l, ov)

                g0 = jax.tree_util.tree_map(
                    lambda v: jnp.zeros(v.shape, jnp.result_type(v, jnp.float32)),
                    list(train_p),
                )
                (new_b, gsum), (losses, outs_stacked) = jax.lax.scan(
                    micro, (bvals, g0), (m_ins, m_lbls, keys)
                )
                grads = jax.tree_util.tree_map(lambda g: g / accum, gsum)
                loss = jnp.mean(losses)
                out_vals = jax.tree_util.tree_map(
                    lambda v: v.reshape((v.shape[0] * v.shape[1],) + v.shape[2:]),
                    outs_stacked,
                )
            if clip_cfg is not None and gc_step is None:
                # the gc path already clipped inside the shard body
                with jax.named_scope("clip"):
                    grads = _apply_clip(grads, clip_cfg)
            new_tp, new_slots = [], []
            with jax.named_scope("optimizer"):
                for i, (pval, g, s, lm, wd) in enumerate(
                    zip(train_p, grads, slots, lr_mults, wds)
                ):
                    np_, ns_ = opt._update(pval, g.astype(pval.dtype), s, lr,
                                           lm, wd)
                    np_ = np_.astype(pval.dtype)
                    if param_sh is not None:
                        def pin(v, sh=param_sh[i]):
                            return jax.lax.with_sharding_constraint(v, sh)

                        np_ = pin(np_)
                        ns_ = {k: pin(v) if getattr(v, "shape", ())
                               == tuple(pval.shape) else v
                               for k, v in ns_.items()}
                    new_tp.append(np_)
                    new_slots.append(ns_)
            # donated-buffer outputs (params, slots, residuals) come BEFORE
            # out_vals: jax pairs donated inputs with outputs of equal
            # abstract shape in order, and a batch-sharded model output that
            # happens to share a donated param's global shape would steal
            # its alias slot and fail on the local byte-size mismatch
            return loss, new_tp, new_b, new_slots, new_gc_res, out_vals

        return pure_step

    def _compile(self, pure_step, slots, in_vals, lbl_vals, gc_res=()):
        if self._mesh() is None:
            return jax.jit(pure_step, donate_argnums=(0, 3, 4))
        in_sh, _ = self._shardings(None, slots, in_vals, lbl_vals, gc_res)
        # pin updated params/buffers/slots to their input shardings: without
        # this XLA may emit replicated outputs, silently undoing the ZeRO
        # memory profile (and paying an all-gather per step)
        tp_sh, b_sh, slot_sh, gc_sh = (in_sh[0], in_sh[2], in_sh[3],
                                       in_sh[4])
        out_sh = (None, list(tp_sh), list(b_sh),
                  [dict(d) for d in slot_sh], tuple(gc_sh), None)
        return jax.jit(pure_step, donate_argnums=(0, 3, 4),
                       in_shardings=in_sh, out_shardings=out_sh)

    def _step_args(self, inputs, labels):
        """(jitted step, its un-placed positional arguments, the in-trace
        grad-comm buckets or None). The ONE place the compiled step's
        signature is assembled: __call__ places and runs these arguments,
        jit.aot.aot_compile_step lowers the same tuple abstractly."""
        fm = self.fm
        if not isinstance(inputs, (tuple, list)):
            inputs = (inputs,)
        if not isinstance(labels, (tuple, list)):
            labels = (labels,)
        in_vals = tree_to_vals(tuple(inputs))
        lbl_vals = tree_to_vals(tuple(labels))
        opt = self.optimizer
        writer_is_self = getattr(opt, "_slot_writer_is",
                                 lambda s: False)(self)
        if self._slots is None or not writer_is_self:
            # (re-)import optimizer state: first call, OR newer state was
            # written by the eager path / set_state_dict / another
            # TrainStep since our last step (last-writer arbitration).
            # COPIED: this step donates its slot buffers, and donating an
            # array the optimizer still references would leave
            # optimizer._slots reading deleted memory.
            if self._slots is not None and getattr(
                    opt, "_slot_writer", None) not in (None, "eager"):
                # the newer writer is another compiled step: land its
                # slots in opt._slots first, then import
                opt._sync_from_compiled()

            def _carry(p, cur):
                s = opt._slots.get(id(p))
                if not s:
                    return cur if cur is not None else \
                        opt._init_slots(p._value)
                return {k: jnp.array(v, copy=True) for k, v in s.items()}

            train_params = [p for p, m in zip(fm.params, fm.trainable_mask)
                            if m]
            cur_slots = self._slots or [None] * len(train_params)
            with RecordEvent("jit_step.state_init"):
                self._slots = [_carry(p, cur)
                               for p, cur in zip(train_params, cur_slots)]
            self._fresh_state = True
            # imported slots may differ in dtype or structure from the ones
            # the entries were first called with: each entry's next call
            # makes its signature anew (and may compile again)
            self._abstract.clear()
        # in-trace grad-comm carried state: the per-bucket error-feedback
        # residuals ride in and out of the jitted step as an aux pytree
        _gc_axes, gc_world = self._gc_world(self._mesh())
        gc_on = self._gc_comm is not None and gc_world > 1
        gc_res, gc_buckets = [], None
        if gc_on:
            gc_buckets = self._gc_buckets()
            if self._gc_error_feedback():
                # (rows, bucket_size) per bucket: row r is rank r's OWN
                # error-feedback residual (sharded per _gc_res_layout by
                # _shardings; a checkpoint round trip keeps every row)
                layout = self._gc_res_layout(self._mesh())
                for b, (rows, _spec) in zip(gc_buckets, layout):
                    r = self._gc_comm._residuals.get(b.index)
                    gc_res.append(
                        jnp.zeros((rows, b.size), jnp.float32)
                        if r is None
                        else jnp.asarray(r, jnp.float32).reshape(
                            rows, b.size))
        ckey = (_abstract_key(in_vals), _abstract_key(lbl_vals))
        if ckey not in self._cache:
            with RecordEvent("jit_step.build"):
                self._cache[ckey] = self._compile(
                    self._build(), self._slots, in_vals, lbl_vals, gc_res
                )
        self._last_ckey = ckey
        pvals = fm.param_values()
        train_p = [v for v, m in zip(pvals, fm.trainable_mask) if m]
        frozen_p = [v for v, m in zip(pvals, fm.trainable_mask) if not m]
        lr = jnp.asarray(self.optimizer.get_lr(), jnp.float32)
        key = rng_mod.next_key()
        args = (train_p, frozen_p, fm.buffer_values(), self._slots, gc_res,
                key, lr, in_vals, lbl_vals)
        return self._cache[ckey], args, gc_buckets

    def __call__(self, inputs, labels=()):
        """One step. The host work is one `jit_step` span whose children
        name its parts (profiler.RecordEvent: in the registry's per-span
        totals always, in the device trace when a jax.profiler session is
        on)."""
        with RecordEvent("jit_step"):
            return self._call(inputs, labels)

    def _place(self, args):
        if self._mesh() is not None:
            # place every operand on its target sharding (no-op when already
            # there); jit-with-in_shardings rejects mismatched placements
            slots, gc_res, in_vals, lbl_vals = (args[3], args[4], args[7],
                                                args[8])
            in_sh, _ = self._shardings(None, slots, in_vals, lbl_vals,
                                       gc_res)
            return jax.tree_util.tree_map(jax.device_put, args, in_sh)
        # fresh parameters and slots are uncommitted arrays and the step
        # hands them back committed: run on them as they are and jit
        # compiles the same program again for the second call. Commit the
        # carried state once, here (744 leaves for gpt-125m — not per call;
        # key/lr/batch arrive the same way every call) — but only beside a
        # batch that sits on the current place: a batch a caller sharded
        # itself keeps deciding where the step runs.
        dev = current_place().jax_device
        if all(v.devices() == {dev}
               for v in jax.tree_util.tree_leaves(args[7:])):
            return jax.device_put(args[:5], dev) + args[5:]
        return args

    def _call(self, inputs, labels):
        fm = self.fm
        with RecordEvent("jit_step.args"):
            step, args, gc_buckets = self._step_args(inputs, labels)
        if self._mesh() is not None or self._fresh_state:
            with RecordEvent("jit_step.place"):
                args = self._place(args)
        self._fresh_state = False
        # an entry's first call (and its first with newly imported state)
        # makes its abstract signature, BEFORE the call: donated buffers
        # (params, slots) are deleted by the step, but memory_analysis()
        # only needs their shapes/dtypes
        first = self._last_ckey not in self._abstract
        if first:
            self._abstract[self._last_ckey] = jax.tree_util.tree_map(
                lambda v: jax.ShapeDtypeStruct(v.shape, v.dtype), args)
        # `dispatch` is where the host blocks once the runtime's steps in
        # flight are full; an entry's first call also traces, lowers and
        # compiles (or loads the cache), so it goes by another name
        with RecordEvent("jit_step.first_call" if first
                         else "jit_step.dispatch"):
            loss, new_tp, new_b, new_slots, new_gc_res, out_vals = \
                step(*args)
        with RecordEvent("jit_step.rebind"):
            ti = 0
            for p, m in zip(fm.params, fm.trainable_mask):
                if m:
                    p._value = new_tp[ti]
                    ti += 1
            fm.bind_buffers(new_b)
            self._slots = new_slots
            if gc_buckets is not None:
                for b, r in zip(gc_buckets, new_gc_res):
                    self._gc_comm._residuals[b.index] = r
                self._account_gc_step(gc_buckets,
                                      self._gc_world(self._mesh())[1])
            self.optimizer._accumulated_steps += 1
            mark = getattr(self.optimizer, "_mark_slot_writer", None)
            if mark is not None:
                mark(self)
            t = Tensor(loss, _internal=True)
            self.last_outputs = vals_to_tensors(out_vals)
        return t

    def memory_analysis(self, record=True, entry=None):
        """XLA's memory accounting for the newest compiled step: AOT-lower
        the cached program at the last call's abstract signature and read
        ``compiled.memory_analysis()`` (argument/temp/output/alias bytes +
        the derived ``peak_hbm_bytes``). When `record`, the result lands in
        observability.memory's compiled-path registry keyed by this trace-
        cache entry (the ``compiled_peak_hbm_bytes{entry=...}`` gauge).
        Returns None before the first call or when the backend doesn't
        report."""
        if self._last_ckey is None or self._last_ckey not in self._cache:
            return None
        try:
            compiled = self._cache[self._last_ckey].lower(
                *self._last_abstract).compile()
        except Exception:
            return None
        from ..observability import memory as obs_mem

        analysis = obs_mem.analyze_compiled(compiled)
        if analysis is not None and record:
            entry = entry or (
                f"train_step:{type(self.model).__name__}:"
                f"{abs(hash(self._last_ckey)) & 0xFFFFFF:06x}")
            obs_mem.record_compiled(entry, analysis)
            analysis = dict(analysis, entry=entry)
        return analysis


def _apply_clip(grads, cfg):
    kind, cval = cfg
    if kind == "global_norm":
        gsq = sum(jnp.sum(jnp.square(g.astype(jnp.float32))) for g in grads)
        gnorm = jnp.sqrt(gsq)
        scale = jnp.minimum(1.0, cval / jnp.maximum(gnorm, 1e-12))
        return [g * scale.astype(g.dtype) for g in grads]
    if kind == "norm":
        out = []
        for g in grads:
            n = jnp.sqrt(jnp.sum(jnp.square(g.astype(jnp.float32))))
            s = jnp.minimum(1.0, cval / jnp.maximum(n, 1e-12))
            out.append(g * s.astype(g.dtype))
        return out
    if kind == "value":
        lo, hi = cval
        return [jnp.clip(g, lo, hi) for g in grads]
    return grads


def save(layer, path, input_spec=None, **config):
    """paddle.jit.save — weights for reload; with input_spec, ALSO the
    deployable inference artifact (StableHLO triple, inference/io.py) that
    paddle_tpu.inference.create_predictor / static.load_inference_model can
    serve from a fresh process.

    Reference saves a translated ProgramDesc + params
    (fluid/dygraph/jit.py:save → __model__/.pdiparams for AnalysisPredictor).
    """
    import pickle

    from ..nn import Layer

    state = {}
    if isinstance(layer, Layer):
        state["state_dict"] = {
            k: np.asarray(v._value) for k, v in layer.state_dict().items()
        }
        state["class"] = type(layer).__name__
    with open(path + ".pdparams" if not path.endswith(".pdparams") else path, "wb") as f:
        pickle.dump(state, f)

    if input_spec and isinstance(layer, Layer):
        from ..inference.io import export_inference_artifact
        from .functional import FunctionalModule

        was_training = layer.training
        layer.eval()
        try:
            fm = FunctionalModule(layer)
            pvals = fm.param_values()
            bvals = fm.buffer_values()
            key = jax.random.key(0)
            feed_specs = []
            for i, spec in enumerate(input_spec):
                # None/-1 dims stay symbolic (shape-polymorphic export)
                shape = tuple(None if (d is None or (isinstance(d, int)
                                                     and d < 0))
                              else int(d) for d in spec.shape)
                name = getattr(spec, "name", None) or f"x{i}"
                feed_specs.append((name, shape, str(np.dtype(spec.dtype))))

            n_p = len(pvals)

            def fn(ws, fs):
                out, _ = fm.call(list(ws[:n_p]), list(ws[n_p:]), key,
                                 tuple(fs), training=False)
                return out

            export_inference_artifact(fn, list(pvals) + list(bvals),
                                      feed_specs, path)
        finally:
            if was_training:
                layer.train()


class TranslatedLayer:
    """Loaded inference artifact as a callable Layer-like (reference:
    fluid/dygraph/io.py TranslatedLayer returned by paddle.jit.load)."""

    def __init__(self, artifact, state=None):
        self._artifact = artifact
        self._state = state or {}
        self.training = False

    def __call__(self, *inputs):
        from ..framework.tensor import Tensor

        vals = [i._value if isinstance(i, Tensor) else np.asarray(i)
                for i in inputs]
        outs = self._artifact.run(vals)
        outs = [Tensor(o, _internal=True) for o in outs]
        return outs[0] if len(outs) == 1 else tuple(outs)

    def eval(self):
        self.training = False
        return self

    def train(self):
        raise RuntimeError(
            "a TranslatedLayer serves a compiled inference program; "
            "re-create the original Layer to continue training "
            "(reference limitation as well)")

    def state_dict(self):
        return dict(self._state)


def load(path, **config):
    """paddle.jit.load: with an inference artifact at `path` (written by
    jit.save(..., input_spec=...) or save_inference_model) returns a callable
    TranslatedLayer; otherwise returns the pickled weights dict."""
    import pickle

    if os.path.exists(path + ".pdmodel"):
        from ..inference.io import InferenceArtifact

        state = {}
        pp = path + ".pdparams"
        if os.path.exists(pp):
            with open(pp, "rb") as f:
                state = pickle.load(f).get("state_dict", {})
        return TranslatedLayer(InferenceArtifact.load(path), state)
    p = path + ".pdparams" if not path.endswith(".pdparams") else path
    with open(p, "rb") as f:
        return pickle.load(f)


_to_static_state = {"enabled": True, "code_level": -1, "verbosity": 0}


def enable_to_static(flag=True):
    """Globally toggle @to_static conversion (reference:
    ProgramTranslator.enable / paddle.jit.enable_to_static): when off,
    StaticFunction.__call__ runs the original eager code."""
    _to_static_state["enabled"] = bool(flag)


def set_code_level(level=100):
    """Reference: dygraph_to_static set_code_level — how much transformed
    code to log. Stored for parity; transformed source is available via
    dy2static.transform_function."""
    _to_static_state["code_level"] = int(level)


def set_verbosity(level=0):
    """Reference: dygraph_to_static logging verbosity knob."""
    _to_static_state["verbosity"] = int(level)


def ignore_module(modules):
    pass


class ProgramTranslator:
    """Singleton facade over the to_static machinery (reference:
    fluid/dygraph/dygraph_to_static/program_translator.py)."""

    _instance = None

    @classmethod
    def get_instance(cls):
        if cls._instance is None:
            cls._instance = cls()
        return cls._instance

    def enable(self, flag=True):
        enable_to_static(flag)

    @property
    def enable_to_static(self):
        return _to_static_state["enabled"]

    def get_code(self, fn):
        """Transformed source of a dygraph function (reference
        get_code)."""
        import inspect

        from .dy2static import transform_function

        return inspect.getsource(transform_function(fn))


class TracedLayer:
    """Trace-based dygraph→static capture (reference:
    fluid/dygraph/jit.py TracedLayer): TracedLayer.trace(layer, inputs)
    runs the layer once under tracing and returns (outputs, traced), where
    traced() replays the compiled program and save_inference_model emits
    the deployable artifact."""

    def __init__(self, layer, static_fn):
        self._layer = layer
        self._fn = static_fn

    @classmethod
    def trace(cls, layer, inputs):
        sf = StaticFunction(layer)
        outs = sf(*inputs)
        return outs, cls(layer, sf)

    def __call__(self, inputs):
        return self._fn(*inputs)

    def save_inference_model(self, path, feed=None, fetch=None, **kw):
        from ..framework.tensor import Tensor

        # re-derive an input spec from the last traced call's cache keys is
        # fragile; require explicit specs via feed, else save weights-only
        save(self._layer, path, input_spec=feed)
        return path
