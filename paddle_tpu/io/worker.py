"""Multiprocess DataLoader workers.

Reference: fluid/reader.py _DataLoaderIterMultiProcess + the C++ shared-mem
queue (paddle/fluid/imperative/data_loader.cc): worker PROCESSES fetch and
collate samples so a GIL-bound __getitem__ cannot starve the device input
pipeline; batches return over a pickle ring (mp.Queue) and are re-ordered by
batch index so iteration order is deterministic regardless of worker timing.

TPU framing: the consumer is an ICI-fed chip expecting a steady HBM feed; the
parent process only deserializes and device_puts, all decode work lives in
the workers. Workers use the 'spawn' start method — fork after the JAX
backend initializes is unsafe (runtime threads don't survive fork).
"""
from __future__ import annotations

import itertools
import multiprocessing as mp
import os
import queue as queue_mod
import traceback
from typing import Optional

_worker_info = None


class WorkerInfo:
    def __init__(self, id, num_workers, dataset, seed=0):
        self.id = id
        self.num_workers = num_workers
        self.dataset = dataset
        self.seed = seed

    def __repr__(self):
        return (f"WorkerInfo(id={self.id}, num_workers={self.num_workers})")


def get_worker_info() -> Optional[WorkerInfo]:
    """paddle.io.get_worker_info — non-None only inside a worker process."""
    return _worker_info


def _worker_loop(dataset, index_q, result_q, collate_fn, worker_id,
                 num_workers, init_fn, iterable, batch_size, drop_last):
    global _worker_info
    _worker_info = WorkerInfo(worker_id, num_workers, dataset)
    try:
        if init_fn is not None:
            init_fn(worker_id)
        if iterable:
            it = iter(dataset)
            while True:
                chunk = list(itertools.islice(it, batch_size))
                if not chunk or (len(chunk) < batch_size and drop_last):
                    break
                result_q.put(("data", None, collate_fn(chunk), None))
            result_q.put(("done", worker_id, None, None))
        else:
            while True:
                task = index_q.get()
                if task is None:
                    break
                epoch, bidx, indices = task
                try:
                    batch = collate_fn([dataset[i] for i in indices])
                    result_q.put(("data", (epoch, bidx), batch, None))
                except Exception:
                    result_q.put(("data", (epoch, bidx), None,
                                  traceback.format_exc()))
    except KeyboardInterrupt:
        pass
    except Exception:
        try:
            result_q.put(("fatal", worker_id, None, traceback.format_exc()))
        except (OSError, ValueError, BrokenPipeError):
            # the parent (and its queue) are already gone — there is no
            # channel left to report on; narrow so a genuinely different
            # fault in the put path still surfaces (rule C003)
            pass


class _WorkerPool:
    """Spawned worker processes + index/result queues (one pool per loader
    when persistent_workers, else per epoch)."""

    def __init__(self, loader):
        ctx = mp.get_context(
            os.environ.get("PADDLE_DATALOADER_START_METHOD", "spawn"))
        self.index_q = ctx.Queue()
        self.result_q = ctx.Queue()
        self.num_workers = loader.num_workers
        self.procs = []
        # Data decode is host work and the chip belongs to this process:
        # a worker must come up with JAX held to the CPU even when
        # JAX_PLATFORMS=tpu is exported. jax reads the variable when it is
        # imported — in a spawned child that happens while the arguments
        # unpickle, before _worker_loop runs — so it is ASSIGNED in the
        # environment the children inherit, not defaulted inside them.
        parent_platforms = os.environ.get("JAX_PLATFORMS")
        os.environ["JAX_PLATFORMS"] = "cpu"
        try:
            for wid in range(loader.num_workers):
                p = ctx.Process(
                    target=_worker_loop,
                    args=(loader.dataset, self.index_q, self.result_q,
                          loader.collate_fn, wid, loader.num_workers,
                          loader.worker_init_fn, loader._iterable_mode,
                          getattr(loader, "batch_size", 1),
                          getattr(loader, "drop_last", False)),
                    daemon=True)
                p.start()
                self.procs.append(p)
        finally:
            if parent_platforms is None:
                del os.environ["JAX_PLATFORMS"]
            else:
                os.environ["JAX_PLATFORMS"] = parent_platforms
        self.closed = False

    def shutdown(self):
        if self.closed:
            return
        self.closed = True
        for _ in self.procs:
            try:
                self.index_q.put(None)
            except (OSError, ValueError, BrokenPipeError):
                # a worker that crashed mid-epoch can leave the queue's
                # pipe closed; shutdown still proceeds to terminate() below
                pass
        for p in self.procs:
            p.join(timeout=5)
            if p.is_alive():
                p.terminate()


class MPMapIterator:
    """Ordered multiprocess iteration over a map-style dataset."""

    def __init__(self, loader, pool: _WorkerPool, epoch: int, to_tensors):
        self.loader = loader
        self.pool = pool
        self.epoch = epoch
        self.to_tensors = to_tensors
        self.batches = list(loader.batch_sampler)
        self.total = len(self.batches)
        self.dispatched = 0
        self.yielded = 0
        self.buffer = {}
        self.timeout = loader.timeout or 120
        # prime the pipeline
        depth = max(2, loader.prefetch_factor) * pool.num_workers
        for _ in range(min(depth, self.total)):
            self._dispatch()

    def _dispatch(self):
        if self.dispatched < self.total:
            self.pool.index_q.put(
                (self.epoch, self.dispatched, self.batches[self.dispatched]))
            self.dispatched += 1

    def __iter__(self):
        return self

    def __next__(self):
        if self.yielded >= self.total:
            raise StopIteration
        while self.yielded not in self.buffer:
            try:
                kind, tag, batch, err = self.pool.result_q.get(
                    timeout=self.timeout)
            except queue_mod.Empty:
                dead = [p.pid for p in self.pool.procs if not p.is_alive()]
                self.pool.shutdown()
                if dead:
                    raise RuntimeError(
                        f"DataLoader worker process(es) {dead} died "
                        f"without reporting an error — commonly the "
                        f"dataset class is not importable in a spawned "
                        f"worker (defined in a REPL/heredoc __main__), or "
                        f"the worker was OOM-killed")
                raise RuntimeError(
                    f"DataLoader worker timed out after {self.timeout}s "
                    f"with workers still alive — a slow __getitem__, or "
                    f"first-batch worker startup (spawned workers re-import "
                    f"the framework; see DataLoader docstring: "
                    f"persistent_workers=True amortizes it across epochs, "
                    f"PADDLE_DATALOADER_START_METHOD=forkserver halves it)")
            if kind == "fatal" or (err is not None):
                self.pool.shutdown()
                raise RuntimeError(f"DataLoader worker failed:\n{err}")
            epoch, bidx = tag
            if epoch != self.epoch:
                continue  # stale result from an abandoned epoch
            self.buffer[bidx] = batch
        out = self.buffer.pop(self.yielded)
        self.yielded += 1
        self._dispatch()
        return self.to_tensors(out)

    def close(self):
        if not self.loader.persistent_workers:
            self.pool.shutdown()


class MPIterableIterator:
    """Multiprocess iteration over an IterableDataset: every worker runs its
    own iterator (shard via get_worker_info, reference semantics); batches
    arrive unordered."""

    def __init__(self, loader, pool: _WorkerPool, to_tensors):
        self.pool = pool
        self.to_tensors = to_tensors
        self.done = 0
        self.timeout = loader.timeout or 120

    def __iter__(self):
        return self

    def __next__(self):
        while True:
            if self.done >= self.pool.num_workers:
                self.pool.shutdown()
                raise StopIteration
            try:
                kind, tag, batch, err = self.pool.result_q.get(
                    timeout=self.timeout)
            except queue_mod.Empty:
                self.pool.shutdown()
                raise RuntimeError(
                    f"DataLoader worker timed out after {self.timeout}s")
            if kind == "fatal" or err is not None:
                self.pool.shutdown()
                raise RuntimeError(f"DataLoader worker failed:\n{err}")
            if kind == "done":
                self.done += 1
                continue
            return self.to_tensors(batch)

    def close(self):
        self.pool.shutdown()
