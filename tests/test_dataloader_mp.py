"""Multiprocess DataLoader (io/worker.py).

Reference capability: fluid/reader.py _DataLoaderIterMultiProcess +
imperative/data_loader.cc — worker processes so a GIL-bound __getitem__
cannot starve the input pipeline. Datasets here are module-level (spawn
pickling).
"""
import os

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.io import DataLoader, Dataset, IterableDataset, get_worker_info


class PidDataset(Dataset):
    def __len__(self):
        return 16

    def __getitem__(self, i):
        return np.array([os.getpid(), i], dtype=np.int64)


class PlatformDataset(Dataset):
    """1 where the process fetching the item has JAX held to the CPU."""

    def __len__(self):
        return 4

    def __getitem__(self, i):
        import jax

        return np.array([jax.config.jax_platforms == "cpu"], dtype=np.int64)


class SquareDataset(Dataset):
    def __len__(self):
        return 32

    def __getitem__(self, i):
        return np.array([i * i], dtype=np.int64)


class FailingDataset(Dataset):
    def __len__(self):
        return 8

    def __getitem__(self, i):
        if i == 5:
            raise ValueError("boom at 5")
        return np.array([i])


class ShardedIterable(IterableDataset):
    def __iter__(self):
        info = get_worker_info()
        wid = info.id if info else 0
        nw = info.num_workers if info else 1
        for i in range(wid, 12, nw):
            yield np.array([i], dtype=np.int64)


def test_workers_are_real_processes():
    dl = DataLoader(PidDataset(), batch_size=4, num_workers=2)
    pids = set()
    for batch in dl:
        pids.update(int(p) for p in batch.numpy()[:, 0])
    assert os.getpid() not in pids  # fetched OUTSIDE the parent process
    assert len(pids) >= 1  # (on a multi-core box both workers participate;
    # this 1-core CI machine may drain everything through one)


def test_workers_stay_off_the_chip_their_parent_holds(monkeypatch):
    """One process per chip: with JAX_PLATFORMS=tpu exported, a worker
    must still come up on the CPU (and the parent's variable survives)."""
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    dl = DataLoader(PlatformDataset(), batch_size=2, num_workers=1)
    assert all(int(v) == 1 for b in dl for v in b.numpy()[:, 0])
    assert os.environ["JAX_PLATFORMS"] == "tpu"


def test_order_is_deterministic():
    dl = DataLoader(SquareDataset(), batch_size=4, num_workers=3)
    seen = np.concatenate([b.numpy()[:, 0] for b in dl])
    np.testing.assert_array_equal(seen, np.arange(32) ** 2)


def test_buffer_reader_stages_device_batches(monkeypatch):
    # use_buffer_reader=True (default; reference use_double_buffer): the
    # device put runs on a producer/stager THREAD so transfer overlaps
    # compute; with the flag off it runs on the consumer thread. Observe
    # the distinguishing behavior by recording which thread converts.
    import threading

    import paddle_tpu.io as io_mod
    from paddle_tpu.framework.tensor import Tensor

    real = io_mod._to_tensors
    seen_threads = []

    def spy(batch):
        seen_threads.append(threading.current_thread() is
                            threading.main_thread())
        return real(batch)

    monkeypatch.setattr(io_mod, "_to_tensors", spy)

    on = list(DataLoader(SquareDataset(), batch_size=8))
    assert seen_threads and not any(seen_threads), \
        "flag on: conversion must happen OFF the main thread"

    seen_threads.clear()
    off_loader = DataLoader(SquareDataset(), batch_size=8,
                            use_buffer_reader=False)
    off = list(off_loader)
    assert seen_threads and all(seen_threads), \
        "flag off: conversion must happen on the consumer thread"

    for a, b in zip(on, off):
        assert isinstance(a, Tensor) and isinstance(b, Tensor)
        np.testing.assert_array_equal(a.numpy(), b.numpy())


def test_buffer_reader_applies_to_worker_processes(monkeypatch):
    # the staging contract holds on the multiprocess path too (the batch
    # crosses the process boundary as host arrays; the parent's stager
    # thread owns the device put)
    import threading

    import paddle_tpu.io as io_mod
    from paddle_tpu.framework.tensor import Tensor

    real = io_mod._to_tensors
    on_main = []

    def spy(batch):
        on_main.append(threading.current_thread() is
                       threading.main_thread())
        return real(batch)

    monkeypatch.setattr(io_mod, "_to_tensors", spy)
    out = list(DataLoader(SquareDataset(), batch_size=8, num_workers=2))
    assert on_main and not any(on_main)
    assert all(isinstance(b, Tensor) for b in out)
    seen = np.sort(np.concatenate([b.numpy()[:, 0] for b in out]))
    np.testing.assert_array_equal(seen, np.sort(np.arange(32) ** 2))


def test_shuffle_follows_paddle_seed():
    # shuffle order is governed by paddle.seed, not global np.random:
    # unrelated np.random draws between runs must not change data order
    # (this was a real flake: suite-order-dependent hapi accuracies)
    import paddle_tpu as paddle

    def epoch_order():
        dl = DataLoader(SquareDataset(), batch_size=4, shuffle=True)
        return np.concatenate([b.numpy()[:, 0] for b in dl])

    paddle.seed(11)
    a = epoch_order()
    np.random.rand(1000)          # perturb the GLOBAL numpy stream
    paddle.seed(11)
    b = epoch_order()
    np.testing.assert_array_equal(a, b)
    paddle.seed(12)
    c = epoch_order()
    assert not np.array_equal(a, c)  # different seed, different order


def test_two_epochs_and_persistent_workers():
    dl = DataLoader(SquareDataset(), batch_size=8, num_workers=2,
                    persistent_workers=True)
    e1 = np.concatenate([b.numpy()[:, 0] for b in dl])
    e2 = np.concatenate([b.numpy()[:, 0] for b in dl])
    np.testing.assert_array_equal(e1, e2)
    dl._persistent_pool.shutdown()


def test_worker_error_propagates():
    dl = DataLoader(FailingDataset(), batch_size=2, num_workers=2)
    with pytest.raises(RuntimeError, match="boom at 5"):
        list(dl)


def test_iterable_dataset_shards_across_workers():
    dl = DataLoader(ShardedIterable(), batch_size=3, num_workers=2)
    seen = sorted(int(v) for b in dl for v in b.numpy()[:, 0])
    assert seen == list(range(12))


# ---------------------------------------------------------------------------
# fleet datasets (distributed/fleet/dataset.py)
# ---------------------------------------------------------------------------

def _write_slot_files(tmp_path, n_files=2, rows=6):
    paths = []
    v = 0
    for f in range(n_files):
        p = tmp_path / f"part-{f}.txt"
        lines = []
        for _ in range(rows):
            lines.append(f"{v} {v + 0.5}")
            v += 1
        p.write_text("\n".join(lines))
        paths.append(str(p))
    return paths


def test_inmemory_dataset_load_shuffle_iterate(tmp_path):
    from paddle_tpu.distributed import fleet

    ds = fleet.InMemoryDataset()
    ds.init(batch_size=4)
    ds.set_filelist(_write_slot_files(tmp_path))
    ds.load_into_memory()
    assert ds.get_memory_data_size() == 12
    first = [int(b[0][0, 0]) for b in ds.iterate()]
    ds.local_shuffle(seed=1)
    shuffled = [int(b[0][0, 0]) for b in ds.iterate()]
    assert first != shuffled  # order actually changed
    all_ids = sorted(int(r[0]) for r in ds._records)
    assert all_ids == list(range(12))
    ds.release_memory()
    assert ds.get_memory_data_size() == 0


def test_queue_dataset_streams(tmp_path):
    from paddle_tpu.distributed import fleet

    ds = fleet.QueueDataset()
    ds.init(batch_size=5)
    ds.set_filelist(_write_slot_files(tmp_path))
    batches = list(ds.iterate())
    assert [b[0].shape[0] for b in batches] == [5, 5, 2]
    with pytest.raises(NotImplementedError):
        ds.local_shuffle()


def test_pipe_command(tmp_path):
    from paddle_tpu.distributed import fleet

    p = tmp_path / "raw.txt"
    p.write_text("a,1\nb,2\n")
    ds = fleet.QueueDataset()
    ds.init(batch_size=2, pipe_command="cut -d, -f2")
    ds.set_filelist([str(p)])
    (batch,) = list(ds.iterate())
    np.testing.assert_array_equal(batch[0][:, 0], [1, 2])
