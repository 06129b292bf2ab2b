"""DataLoader tests: thread mode + multi-process shared-memory mode
(SURVEY §2b io row: multi-process workers + shm transport)."""
import os
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.io import DataLoader, Dataset


class NumpyDataset(Dataset):
    def __init__(self, n=64, shape=(8,)):
        self.data = np.arange(n * int(np.prod(shape)), dtype=np.float32)
        self.data = self.data.reshape((n,) + shape)

    def __getitem__(self, i):
        return self.data[i], np.int64(i)

    def __len__(self):
        return len(self.data)


class PythonHeavyDataset(Dataset):
    """GIL-bound __getitem__: pure-python arithmetic threads can't overlap.
    Each sample carries where and when it was made: [value, index, pid,
    start, end] with the times on the system-wide monotonic clock."""

    def __init__(self, n=48, iters=600000):
        self.n = n
        self.iters = iters

    def __getitem__(self, i):
        t0 = time.monotonic()
        acc = 0
        for k in range(self.iters):          # holds the GIL
            acc = (acc + i * k) % 1000003
        return np.array([float(acc), float(i), float(os.getpid()), t0,
                         time.monotonic()], np.float64)

    def __len__(self):
        return self.n


def test_mp_loader_values_and_order():
    ds = NumpyDataset(n=32)
    dl = DataLoader(ds, batch_size=8, num_workers=2, use_shared_memory=True)
    seen = []
    for xb, ib in dl:
        assert xb.shape == [8, 8]
        seen.extend(ib.numpy().tolist())
    assert seen == list(range(32))  # deterministic order despite 2 workers
    xb0 = next(iter(DataLoader(ds, batch_size=4, num_workers=2,
                               use_shared_memory=True)))[0]
    np.testing.assert_allclose(xb0.numpy(), ds.data[:4])


class DictDs(Dataset):
    """Dataset classes must be module-level: process workers receive the
    dataset by pickle (reference contract for multi-process loading)."""

    def __getitem__(self, i):
        return {"x": np.full((3,), float(i), np.float32), "i": i}

    def __len__(self):
        return 8


class BadDs(Dataset):
    def __getitem__(self, i):
        if i == 5:
            raise ValueError("bad sample")
        return np.zeros(2, np.float32)

    def __len__(self):
        return 8


def collate_sum(samples):
    import paddle_tpu as paddle
    xs = np.stack([s["x"] for s in samples])
    return paddle.to_tensor(xs.sum(axis=1))


def test_mp_loader_dict_samples_and_custom_collate():
    dl = DataLoader(DictDs(), batch_size=4, num_workers=2,
                    use_shared_memory=True)
    b = next(iter(dl))
    np.testing.assert_allclose(b["x"].numpy()[:, 0], [0, 1, 2, 3])
    assert b["i"].numpy().tolist() == [0, 1, 2, 3]

    # custom collate runs on the consumer over raw samples
    dl2 = DataLoader(DictDs(), batch_size=4, num_workers=2,
                     use_shared_memory=True, collate_fn=collate_sum)
    out = next(iter(dl2))
    np.testing.assert_allclose(out.numpy(), [0.0, 3.0, 6.0, 9.0])


def test_mp_loader_worker_error_propagates():
    dl = DataLoader(BadDs(), batch_size=4, num_workers=2,
                    use_shared_memory=True)
    with pytest.raises(RuntimeError, match="bad sample"):
        for _ in dl:
            pass


def test_mp_loader_abandoned_iterator_cleanup():
    ds = NumpyDataset(n=64)
    it = iter(DataLoader(ds, batch_size=4, num_workers=2,
                         use_shared_memory=True))
    next(it)  # consume one batch, abandon the rest
    it._shutdown()
    assert all(not w.is_alive() for w in it.workers)
    # a fresh epoch works after abandonment
    total = sum(1 for _ in DataLoader(ds, batch_size=4, num_workers=2,
                                      use_shared_memory=True))
    assert total == 16


def test_mp_loader_persistent_workers():
    ds = NumpyDataset(n=32)
    dl = DataLoader(ds, batch_size=8, num_workers=2, use_shared_memory=True,
                    persistent_workers=True)
    seen1 = [i for _, ib in dl for i in ib.numpy().tolist()]
    pids1 = [w.pid for w in dl._mp_pool.workers]
    assert all(w.is_alive() for w in dl._mp_pool.workers)  # survived epoch end
    seen2 = [i for _, ib in dl for i in ib.numpy().tolist()]
    pids2 = [w.pid for w in dl._mp_pool.workers]
    assert seen1 == seen2 == list(range(32))
    assert pids1 == pids2  # same worker processes reused
    dl._mp_pool.shutdown()


def test_mp_loader_persistent_abandoned_epoch_discarded():
    ds = NumpyDataset(n=64)
    dl = DataLoader(ds, batch_size=4, num_workers=2, use_shared_memory=True,
                    persistent_workers=True)
    it = iter(dl)
    next(it)  # abandon epoch 0 mid-flight
    del it
    seen = [i for _, ib in dl for i in ib.numpy().tolist()]
    assert seen == list(range(64))  # stale epoch-0 batches were discarded
    dl._mp_pool.shutdown()


@pytest.mark.skipif((os.cpu_count() or 1) < 2, reason=(
    "process-vs-thread speedup on GIL-bound work needs >1 CPU core; "
    "this host has 1 (thread and process modes both serialize here)"))
def test_mp_loader_beats_threads_on_python_heavy_dataset():
    """Process workers run GIL-bound samples at the same time, in processes
    of their own, and hand the batches back in order. Asserted from what
    each sample records (pid, start, end), not from a race of two wall
    clocks: that race lost whenever the machine was busy (six xdist
    workers), and a count of overlapping samples does not depend on load."""
    ds = PythonHeavyDataset()
    kw = dict(batch_size=8, num_workers=4)

    def run(loader):
        rows = np.concatenate([np.asarray(b.numpy()) for b in loader])
        return rows, len(rows) // 8

    threads, n1 = run(DataLoader(ds, use_shared_memory=False, **kw))
    procs, n2 = run(DataLoader(ds, use_shared_memory=True, **kw))
    assert n1 == n2 == 6
    # the process loader keeps the order whichever worker finished first,
    # and computes what the thread loader computes
    assert procs[:, 1].tolist() == list(range(48))
    threads = threads[np.argsort(threads[:, 1])]
    assert procs[:, :2].tolist() == threads[:, :2].tolist()
    # thread workers are the parent; process workers are several others
    assert set(threads[:, 2]) == {float(os.getpid())}
    pids = set(procs[:, 2])
    assert len(pids) >= 2 and float(os.getpid()) not in pids, pids
    # GIL-bound samples from different processes ran during each other
    overlapping = sum(
        1 for a in procs for b in procs
        if a[2] < b[2] and a[3] < b[4] and b[3] < a[4])
    assert overlapping > 0, "no two process workers ever ran at once"
