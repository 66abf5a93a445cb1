"""Heter-PS pass trainer: the PSGPUTrainer drive loop over DevicePassCache.

Reference: PSGPUTrainer / HeterXpuTrainer (paddle/fluid/framework/
trainer.h:179,249) and ps_gpu_wrapper.cc BuildGPUTask: each training PASS
bulk-pulls its sparse working set into device memory, every in-pass lookup
is a device gather (no per-batch host-PS hop), and the merged gradients
push back once at pass end (downpour semantics: one optimizer step per
pass per key with the summed gradient).

TPU-native: DevicePassCache holds the rows as one jnp array; lookups fuse
into the jitted step as XLA gathers. heter_embedding() is the drop-in for
distributed_lookup_table inside the step — same Tensor-with-grad surface,
but backward scatter-adds into the device accumulator instead of a host
push per step.
"""
from __future__ import annotations

from typing import Callable, Sequence

import numpy as np

from .heter_cache import DevicePassCache

__all__ = ["HeterPassTrainer", "heter_embedding"]


def heter_embedding(cache, ids):
    """Cache-backed embedding lookup with gradient accumulation.

    Works over either cache tier: the pass-scoped DevicePassCache (rows
    pulled once by begin_pass) or the capacity-bounded HeterCache (LRU/LFU
    with batched faults). Forward: device gather. Backward: device
    scatter-add into the cache's grad accumulator — the host PS sees
    merged pushes at end_pass/flush/eviction, not one per step
    (ps_gpu_wrapper.cc push_sparse-at-EndPass semantics).
    """
    import jax
    import jax.numpy as jnp

    from ...framework import autograd
    from ...framework.tensor import Tensor

    ids_np = np.asarray(ids.numpy() if isinstance(ids, Tensor) else ids)
    if isinstance(cache, DevicePassCache):
        slot_idx = cache.slots(ids_np)  # one host translation per batch
        out_val = cache.lookup_slots(jnp.asarray(slot_idx))

        def backward(cot, dim):
            cache._push_slot_grads(slot_idx.reshape(-1),
                                   np.asarray(cot).reshape(-1, dim))
    else:  # HeterCache: faulting lookup; grads keyed by id
        out_val = cache.lookup(ids_np)

        def backward(cot, dim):
            cache.push_grads(ids_np.reshape(-1),
                             np.asarray(cot).reshape(-1, dim))

    out = Tensor(out_val, _internal=True)
    if autograd.is_grad_enabled():
        dim = out_val.shape[-1]

        def vjp_fn(cot):
            backward(cot, dim)
            return []

        node = autograd.GradNode(
            vjp_fn, [],
            [jax.ShapeDtypeStruct(out_val.shape, out_val.dtype)],
            multi_output=False, name="heter_embedding")
        out.stop_gradient = False
        out._grad_node = node
        out._out_index = 0
    return out


class HeterPassTrainer:
    """Drives train_from_dataset with the pass lifecycle of PSGPUTrainer.

    step_fn(cache, batch) runs one mini-batch (typically: heter_embedding
    lookups + dense forward/backward + dense optimizer step); the trainer
    owns BuildGPUTask (working-set union + ONE bulk pull) before the pass
    and the merged push after it.
    """

    def __init__(self, client, table_id: int, lr: float = -1.0,
                 sparse_slots: Sequence[int] = (0,)):
        self.cache = DevicePassCache(client, table_id, lr=lr)
        self.sparse_slots = tuple(sparse_slots)

    def _pass_ids(self, batches):
        return np.concatenate(
            [np.asarray(b[s], np.uint64).reshape(-1)
             for b in batches for s in self.sparse_slots])

    def train_from_dataset(self, dataset, step_fn: Callable, passes: int = 1,
                           pad_to=None):
        """One or more passes over `dataset`. Per pass: BuildGPUTask
        (materialize the pass, union its sparse ids, one bulk pull),
        per-batch device-gather steps, EndPass sync. Returns the last
        pass's step_fn outputs.

        The end-of-pass sync mode follows the step_fn: a CompiledPassStep
        with a device-side table optimizer writes VALUES back
        (assign=True) — its gacc holds optimizer state, which must never
        be pushed as a gradient; every other step_fn pushes the merged
        gradient (downpour)."""
        assign = bool(getattr(step_fn, "table_optimizer", None))
        outs = []
        for _ in range(int(passes)):
            batches = list(dataset.iterate())
            if not batches:
                return outs
            self.cache.begin_pass(self._pass_ids(batches), pad_to=pad_to)
            try:
                outs = [step_fn(self.cache, b) for b in batches]
            finally:
                self.cache.end_pass(assign=assign)
        return outs

    def infer_from_dataset(self, dataset, step_fn: Callable):
        """Evaluation twin: pull the working set, run step_fn per batch
        (no grads accumulate -> end_pass pushes nothing)."""
        batches = list(dataset.iterate())
        if not batches:
            return []
        self.cache.begin_pass(self._pass_ids(batches))
        try:
            return [step_fn(self.cache, b) for b in batches]
        finally:
            self.cache.end_pass()


class CompiledPassStep:
    """ONE-dispatch pass step: embedding gather + dense forward/backward
    + dense optimizer update + embedding-grad accumulation, compiled as a
    single XLA program.

    The eager heter_embedding path dispatches dozens of host ops per
    batch and round-trips the embedding rows host<->device every step.
    Here the pass cache's row slab, the grad accumulator, and the dense optimizer
    state all live on device across the whole pass (ps_gpu_wrapper.cc
    keeps them in GPU memory the same way); per-step host work is the
    vectorized id->slot translation plus an int32 upload.

        trainer = HeterPassTrainer(client, table_id=0, lr=0.1)
        step = CompiledPassStep(trainer.cache, deep_model, optimizer,
                                loss_fn)
        trainer.train_from_dataset(dataset, step, passes=1)

    loss_fn(output_tensor, labels_tensor) -> scalar Tensor.
    """

    def __init__(self, cache: DevicePassCache, model, optimizer, loss_fn,
                 table_optimizer=None, table_lr=0.1):
        """table_optimizer: None keeps downpour semantics (grads
        accumulate, merged push at end_pass); "adagrad"/"sgd" runs the
        embedding update ON DEVICE each step (ps_gpu_wrapper's device
        optimizer) — pair with cache.end_pass(assign=True)."""
        self.cache = cache
        self.model = model
        self.optimizer = optimizer
        self.loss_fn = loss_fn
        self.table_optimizer = table_optimizer
        self.table_lr = float(table_lr)
        from ...jit.functional import FunctionalModule

        self._fm = FunctionalModule(model)
        self._opt_state = None
        self._step_idx = 0
        self._jit = None

    def _build(self):
        import jax
        import jax.numpy as jnp

        from ...framework import autograd
        from ...jit.functional import vals_to_tensors

        fm, opt, loss_fn = self._fm, self.optimizer, self.loss_fn

        def pure(train_p, frozen_p, bvals, opt_state, rows, gacc, slots,
                 labels, key, lr):
            def loss_of(tp, rv):
                emb = jnp.take(rv, slots, axis=0)
                flat = emb.reshape((slots.shape[0], -1))
                pv = fm.merge_values(list(tp), list(frozen_p))
                out_vals, new_b = fm.call(pv, list(bvals), key, (flat,),
                                          training=True)
                outs = vals_to_tensors(out_vals)
                with autograd.no_grad():
                    loss_t = loss_fn(outs, vals_to_tensors((labels,))[0])
                return loss_t._value.astype(jnp.float32), new_b

            (loss, new_b), (g_p, g_rows) = jax.value_and_grad(
                loss_of, argnums=(0, 1), has_aux=True)(tuple(train_p), rows)
            new_p, new_state = opt.apply_gradients_tree(
                list(train_p), list(g_p), opt_state, lr)
            if self.table_optimizer is None:
                return loss, new_p, new_state, rows, gacc + g_rows, new_b
            # device-side embedding optimizer: the cached rows train
            # per step; end_pass(assign=True) writes values back
            if self.table_optimizer == "adagrad":
                gacc = gacc + g_rows * g_rows
                rows = rows - self.table_lr * g_rows / jnp.sqrt(gacc + 1e-8)
            else:  # sgd
                rows = rows - self.table_lr * g_rows
            return loss, new_p, new_state, rows, gacc, new_b

        self._jit = jax.jit(pure, donate_argnums=(3, 4, 5))

    def __call__(self, cache: DevicePassCache, batch):
        """batch: (ids, labels) numpy arrays. Returns the loss Tensor."""
        import jax.numpy as jnp

        from ...framework.tensor import Tensor

        ids, labels = batch[0], batch[1]
        fm, opt = self._fm, self.optimizer
        train_p, frozen_p = fm.split_values(fm.param_values())
        if self._jit is None:
            self._build()
        if self._opt_state is None:
            self._opt_state = opt.init_state_tree(train_p)
        slots = jnp.asarray(cache.slots(ids))
        lr = jnp.asarray(float(opt.get_lr()) if hasattr(opt, "get_lr")
                         else 0.001, jnp.float32)
        import jax

        self._step_idx += 1  # fresh dropout mask per step
        (loss, new_p, self._opt_state, cache._rows, cache._gacc,
         new_b) = self._jit(
            tuple(train_p), tuple(frozen_p), fm.buffer_values(),
            self._opt_state, cache._rows, cache._gacc, slots,
            jnp.asarray(labels), jax.random.key(self._step_idx), lr)
        # write updated dense params + buffers back into the live model
        ti = 0
        for p, m in zip(fm.params, fm.trainable_mask):
            if m:
                p._value = new_p[ti]
                ti += 1
        fm.bind_buffers(new_b)
        return Tensor(loss, _internal=True)
