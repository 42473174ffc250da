"""Multiprocess DataLoader: order, samples made in worker processes,
worker-death detection.

Mirrors the reference's multiprocess dataloader capability
(/root/reference/python/paddle/fluid/dataloader/dataloader_iter.py:335,
paddle/fluid/imperative/data_loader.cc SIGCHLD handling).
"""

import os

import numpy as np
import pytest

from paddle_tpu.data import DataLoader, Dataset, IterableDataset
from paddle_tpu.data.worker import get_worker_info


class ArrayDataset(Dataset):
    def __init__(self, n=64, dim=512):
        self.x = np.arange(n * dim, dtype=np.float32).reshape(n, dim)

    def __getitem__(self, i):
        return self.x[i], np.int64(i)

    def __len__(self):
        return len(self.x)


class PidDataset(Dataset):
    """Each sample carries the pid of the process that produced it."""

    def __init__(self, n=32):
        self.n = n

    def __getitem__(self, i):
        return np.full((8,), float(i), np.float32), np.int64(os.getpid())

    def __len__(self):
        return self.n


class DyingDataset(Dataset):
    def __getitem__(self, i):
        if i == 5 and get_worker_info() is not None:
            os._exit(3)  # hard death: no exception, no cleanup
        return np.zeros((4,), np.float32)

    def __len__(self):
        return 16


class CountStream(IterableDataset):
    def __init__(self, n=40):
        self.n = n

    def __iter__(self):
        for i in range(self.n):
            yield np.full((4,), float(i), np.float32)


def test_mp_matches_single_process_order():
    ds = ArrayDataset(64)
    ref = [b for b in DataLoader(ds, batch_size=8, num_workers=0)]
    got = [b for b in DataLoader(ds, batch_size=8, num_workers=3)]
    assert len(ref) == len(got)
    for (rx, ri), (gx, gi) in zip(ref, got):
        np.testing.assert_array_equal(rx, gx)
        np.testing.assert_array_equal(ri, gi)


def test_mp_large_batches_ride_shared_memory():
    # 64 x 512 f32 = 128KiB per batch array > _SHM_MIN_BYTES: exercises the
    # shm encode/decode path end to end.
    ds = ArrayDataset(128, dim=512)
    batches = [b for b in DataLoader(ds, batch_size=64, num_workers=2)]
    assert batches[0][0].shape == (64, 512)
    np.testing.assert_array_equal(
        np.concatenate([b[0] for b in batches]), ds.x)


def test_mp_iterable_dataset_covers_stream():
    ds = CountStream(40)
    got = [b for b in DataLoader(ds, batch_size=4, num_workers=2)]
    # every sample appears exactly once across workers
    vals = sorted(float(v) for b in got for v in b[:, 0])
    assert vals == [float(v) for v in range(40)]
    # and the merged order is deterministic across runs
    again = [b for b in DataLoader(ds, batch_size=4, num_workers=2)]
    for a, b in zip(got, again):
        np.testing.assert_array_equal(a, b)


def test_mp_iterable_self_sharding_dataset():
    """Dataset that shards itself via get_worker_info (the reference's
    convention) runs with worker_auto_shard=False and must not be strided
    twice."""

    class SelfSharding(IterableDataset):
        def __iter__(self):
            info = get_worker_info()
            wid = info.id if info else 0
            n = info.num_workers if info else 1
            for i in range(wid, 40, n):
                yield np.full((4,), float(i), np.float32)

    got = [b for b in DataLoader(SelfSharding(), batch_size=4,
                                 num_workers=2, worker_auto_shard=False)]
    vals = sorted(float(v) for b in got for v in b[:, 0])
    assert vals == [float(v) for v in range(40)]


def test_mp_samples_come_from_several_worker_processes():
    """With ``num_workers=4`` every sample is produced in a process
    other than the parent, more than one worker produces samples, and
    the batches are the serial loader's. (What the workers buy in time
    is the box's business: a wall-clock ratio cannot be held on a
    shared one.)"""
    ds = PidDataset(n=32)
    serial = list(DataLoader(ds, batch_size=2, num_workers=0))
    mp = list(DataLoader(ds, batch_size=2, num_workers=4))
    assert len(mp) == len(serial) == 16
    for (x_mp, _), (x_serial, _) in zip(mp, serial):
        np.testing.assert_array_equal(x_mp, x_serial)
    assert {int(p) for _, pids in serial for p in pids} == {os.getpid()}
    workers = {int(p) for _, pids in mp for p in pids}
    assert os.getpid() not in workers
    assert len(workers) >= 2, workers


def test_mp_worker_death_raises():
    dl = DataLoader(DyingDataset(), batch_size=4, num_workers=2)
    with pytest.raises(RuntimeError, match="died unexpectedly"):
        for _ in dl:
            pass


def test_mp_worker_exception_propagates():
    class Bad(Dataset):
        def __getitem__(self, i):
            if i == 7:
                raise ValueError("bad record 7")
            return np.zeros((4,), np.float32)

        def __len__(self):
            return 16

    dl = DataLoader(Bad(), batch_size=4, num_workers=2)
    with pytest.raises(RuntimeError, match="bad record 7"):
        for _ in dl:
            pass


def test_mp_early_break_shuts_down_cleanly():
    ds = ArrayDataset(64)
    for epoch in range(3):
        for i, _ in enumerate(DataLoader(ds, batch_size=8, num_workers=2)):
            if i == 1:
                break  # generator close must reap workers, not leak them
