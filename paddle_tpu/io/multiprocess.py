"""Multi-process DataLoader workers with shared-memory batch transport.

Ref: python/paddle/io/dataloader/dataloader_iter.py (_DataLoaderIterMultiProcess)
+ the reference's shared-memory LoDTensor transport (core._convert_to_shared_
memory). TPU-native constraints shape the design:

- worker processes come from a **forkserver**: the server is fork+exec'd
  with a clean address space, so workers never inherit the parent's live
  jax/XLA threads or locks (plain `fork` after the TPU backend has
  initialized deadlocks in the child on inherited mutexes). The server
  imports the package once; each worker is then a cheap fork of that
  clean, warm process.
- workers run pure numpy (sample fetch + collate). Device Tensors are
  built on the consumer side, so host->HBM transfer stays in the parent.
  A chip belongs to one process, and that process is the parent: the
  server starts with ``JAX_PLATFORMS=cpu``, so nothing a dataset does in
  a worker can create an accelerator backend.
- batches cross the process boundary as multiprocessing.shared_memory
  segments (one per array leaf); only tiny (name, shape, dtype) metadata
  goes through the result queue. The consumer copies each leaf out of the
  segment exactly once (into the device buffer) and unlinks it.
- a reorder buffer keeps batch order deterministic regardless of which
  worker finishes first (reference behavior).

The thread-based path (io/__init__.py) remains the default for
numpy-collate datasets; process workers win when __getitem__ holds the GIL
(Python-heavy decode/augment), which is exactly the reference's use case
for multi-process loading. Dataset / worker_init_fn must be picklable
(same contract as the reference's multi-process mode).
"""
from __future__ import annotations

import multiprocessing as mp
import os
import queue as _queue
import sys
from multiprocessing import shared_memory

import numpy as np

_SENTINEL = "__stop__"


def _np_collate(batch):
    """default_collate, but producing numpy leaves only (no jax in
    workers)."""
    sample = batch[0]
    if isinstance(sample, np.ndarray):
        return np.stack(batch)
    if isinstance(sample, (int, float, np.number)):
        return np.asarray(batch)
    if isinstance(sample, (list, tuple)):
        return type(sample)(_np_collate(list(items)) for items in zip(*batch))
    if isinstance(sample, dict):
        return {k: _np_collate([d[k] for d in batch]) for k in sample}
    return batch


def _pack(x, shms):
    """numpy leaf -> shm descriptor (appending the segment to shms)."""
    if isinstance(x, np.ndarray) and x.nbytes > 0:
        x = np.ascontiguousarray(x)
        shm = shared_memory.SharedMemory(create=True, size=x.nbytes)
        dst = np.ndarray(x.shape, x.dtype, buffer=shm.buf)
        dst[...] = x
        del dst
        shms.append(shm)
        return ("shm", shm.name, x.shape, x.dtype.str)
    if isinstance(x, np.ndarray):
        return ("arr", x)
    if isinstance(x, (list, tuple)):
        return ("seq", type(x).__name__, [_pack(v, shms) for v in x])
    if isinstance(x, dict):
        return ("map", {k: _pack(v, shms) for k, v in x.items()})
    return ("val", x)


def _unpack(desc, wrap_leaf, owned):
    """shm descriptor -> pytree. wrap_leaf gets an OWNED (copied) ndarray;
    segments are recorded in `owned` for the caller to unlink."""
    kind = desc[0]
    if kind == "shm":
        _, name, shape, dtype = desc
        shm = shared_memory.SharedMemory(name=name)
        owned.append(shm)
        view = np.ndarray(shape, np.dtype(dtype), buffer=shm.buf)
        arr = view.copy()  # detach from the segment before it is unlinked
        del view
        return wrap_leaf(arr)
    if kind == "arr":
        return wrap_leaf(desc[1])
    if kind == "seq":
        _, tname, items = desc
        vals = [_unpack(v, wrap_leaf, owned) for v in items]
        return tuple(vals) if tname == "tuple" else vals
    if kind == "map":
        return {k: _unpack(v, wrap_leaf, owned) for k, v in desc[1].items()}
    return desc[1]


def _release(owned):
    for shm in owned:
        try:
            shm.close()
            shm.unlink()
        except Exception:
            pass


def _worker_loop(dataset, idx_q, out_q, collate_in_worker, worker_id,
                 worker_init_fn, seed):
    np.random.seed((seed + worker_id) % (2 ** 31))
    if worker_init_fn is not None:
        worker_init_fn(worker_id)
    while True:
        item = idx_q.get()
        if item == _SENTINEL:
            out_q.put(_SENTINEL)
            return
        epoch, batch_idx, idxs = item
        try:
            samples = [dataset[i] for i in idxs]
            payload = _np_collate(samples) if collate_in_worker else samples
            shms = []
            desc = _pack(payload, shms)
            out_q.put((epoch, batch_idx, desc, None))
            # segment ownership moves to the consumer, which unlinks after
            # copying out. The shared resource tracker (forkserver children
            # inherit the parent's) keeps the registration until then.
            for shm in shms:
                shm.close()
        except BaseException as e:  # surface dataset errors to the consumer
            out_q.put((epoch, batch_idx, None, f"{type(e).__name__}: {e}"))


_mp_ctx = None


def _get_ctx():
    """forkserver context, created once. The server process has a clean
    address space (fork+exec) and imports this package before serving, so
    worker forks are cheap and jax-state-free.

    The server inherits sys.path via PYTHONPATH (exported here for the
    ensure_running call): without it, paths added at runtime (pytest
    rootdir, site hooks) are invisible to the server, its preload fails
    silently, and every worker re-pays the full framework import. It
    also starts with JAX_PLATFORMS=cpu, read when its preload imports
    jax and inherited by every worker it forks: the chip stays the
    parent's whatever a dataset does."""
    global _mp_ctx
    if _mp_ctx is None:
        try:
            ctx = mp.get_context("forkserver")
            ctx.set_forkserver_preload(["paddle_tpu.io.multiprocess"])
            from multiprocessing import forkserver as _fs
            server_env = {"PYTHONPATH": os.pathsep.join(
                p for p in sys.path if p), "JAX_PLATFORMS": "cpu"}
            old = {k: os.environ.get(k) for k in server_env}
            os.environ.update(server_env)
            try:
                _fs._forkserver.ensure_running()
            finally:
                for k, v in old.items():
                    if v is None:
                        os.environ.pop(k, None)
                    else:
                        os.environ[k] = v
        except ValueError:  # platform without forkserver
            ctx = mp.get_context("spawn")
        _mp_ctx = ctx
    return _mp_ctx


class _WorkerPool:
    """Process-worker pool + queues. Owned by one iterator (non-persistent)
    or cached on the DataLoader across epochs (persistent_workers=True,
    reference semantics: worker start + module import cost paid once)."""

    def __init__(self, loader):
        from collections import deque
        ctx = _get_ctx()
        nw = loader.num_workers
        # indices are dispatched incrementally with an outstanding cap
        # (reference behavior): bounds idx-queue memory on huge datasets,
        # caps live shm segments, and means an abandoned epoch wastes at
        # most `cap` stale batches of worker time, not the whole epoch
        self.cap = max(2, loader.prefetch_factor * nw)
        self.idx_q = ctx.Queue()
        self.out_q = ctx.Queue(maxsize=self.cap)
        self.feed = deque()
        self.outstanding = 0
        seed = int.from_bytes(os.urandom(4), "little")
        self.workers = [
            ctx.Process(
                target=_worker_loop,
                args=(loader.dataset, self.idx_q, self.out_q,
                      loader.collate_fn is None, w,
                      getattr(loader, "worker_init_fn", None), seed),
                daemon=True)
            for w in range(nw)]
        for w in self.workers:
            w.start()
        self.epoch = -1
        self.closed = False

    def submit_epoch(self, batches):
        from collections import deque
        self.epoch += 1
        # un-dispatched remainder of an abandoned epoch is simply dropped
        self.feed = deque((self.epoch, i, b) for i, b in enumerate(batches))
        self._fill()
        return self.epoch

    def _fill(self):
        while self.feed and self.outstanding < self.cap:
            self.idx_q.put(self.feed.popleft())
            self.outstanding += 1

    def on_result(self):
        """One outstanding batch was received (any epoch); dispatch more."""
        self.outstanding -= 1
        self._fill()

    def alive(self):
        return any(w.is_alive() for w in self.workers)

    def drain(self, block=False):
        """Pop and free any queued results (stale epochs / shutdown)."""
        try:
            while True:
                item = self.out_q.get(timeout=0.2) if block \
                    else self.out_q.get_nowait()
                if item != _SENTINEL and item[2] is not None:
                    owned = []
                    _unpack(item[2], lambda a: None, owned)
                    _release(owned)
        except _queue.Empty:
            pass

    def shutdown(self):
        if self.closed:
            return
        self.closed = True
        # graceful first: sentinels let workers finish their current batch
        # and exit cleanly (no mid-_pack orphaned shm segments); drain keeps
        # the bounded out_q moving so blocked put()s can complete
        for _ in self.workers:
            self.idx_q.put(_SENTINEL)
        deadline = 10  # drain rounds of 0.2s each
        while deadline > 0 and any(w.is_alive() for w in self.workers):
            self.drain(block=True)
            deadline -= 1
        for w in self.workers:
            if w.is_alive():
                w.terminate()
        for w in self.workers:
            w.join(timeout=5)
        self.drain()  # anything flushed between drain and terminate

    def __del__(self):
        try:
            self.shutdown()
        except Exception:
            pass


class MultiprocessLoaderIter:
    """In-order iterator over process workers (see module docstring)."""

    def __init__(self, loader, pool=None):
        self.loader = loader
        self.collate = loader.collate_fn  # None => numpy collate in worker
        self.owns_pool = pool is None
        self.pool = pool if pool is not None else _WorkerPool(loader)
        batches = list(iter(loader.batch_sampler))
        self.n_batches = len(batches)
        self.epoch = self.pool.submit_epoch(batches)
        self.next_idx = 0
        self.buffer = {}
        self.done = False
        self.timeout = getattr(loader, "timeout", 0) or 0

    def __iter__(self):
        return self

    def _get_result(self):
        """out_q.get that can never hang forever: polls worker liveness and
        honors the loader's timeout (0 => only die when workers do)."""
        waited = 0.0
        while True:
            try:
                return self.pool.out_q.get(timeout=2.0)
            except _queue.Empty:
                waited += 2.0
                if self.timeout and waited >= self.timeout:
                    self._finish(kill=True)
                    raise RuntimeError(
                        f"DataLoader timed out after {self.timeout}s waiting "
                        "for a worker batch")
                if not self.pool.alive():
                    try:  # drain anything flushed between checks
                        return self.pool.out_q.get(timeout=1.0)
                    except _queue.Empty:
                        self._finish(kill=True)
                        raise RuntimeError(
                            "DataLoader workers exited unexpectedly "
                            "(killed or crashed without reporting)")

    def __next__(self):
        from ..tensor.tensor import Tensor
        if self.done or self.next_idx >= self.n_batches:
            self._finish()
            raise StopIteration
        while self.next_idx not in self.buffer:
            item = self._get_result()
            if item == _SENTINEL:  # a worker exited (shutdown elsewhere)
                self._finish(kill=True)
                raise RuntimeError("DataLoader worker pool was shut down")
            self.pool.on_result()  # frees a dispatch slot, feeds the next
            epoch, idx, desc, err = item
            if epoch != self.epoch:  # stale batch from an abandoned epoch
                if desc is not None:
                    owned = []
                    _unpack(desc, lambda a: None, owned)
                    _release(owned)
                continue
            self.buffer[idx] = (desc, err)
        desc, err = self.buffer.pop(self.next_idx)
        self.next_idx += 1
        if err is not None:
            self._finish(kill=True)
            raise RuntimeError(f"DataLoader worker failed: {err}")
        owned = []
        if self.collate is None:
            # worker already collated to numpy; leaves become Tensors here
            out = _unpack(desc, Tensor, owned)
        else:
            # custom collate runs on the consumer (jax-safe) over the raw
            # worker-fetched samples
            samples = _unpack(desc, lambda a: a, owned)
            out = self.collate(samples)
        _release(owned)
        return out

    def _finish(self, kill=False):
        if self.done:
            return
        self.done = True
        for desc, _err in self.buffer.values():
            if desc is not None:
                owned = []
                _unpack(desc, lambda a: None, owned)
                _release(owned)
        self.buffer.clear()
        if self.owns_pool or kill:
            self.pool.shutdown()
            if not self.owns_pool:  # persistent pool died: loader re-creates
                loader_pool = getattr(self.loader, "_mp_pool", None)
                if loader_pool is self.pool:
                    self.loader._mp_pool = None

    # legacy/test hook: shut everything down regardless of pool ownership
    def _shutdown(self):
        self._finish(kill=True)

    @property
    def workers(self):
        return self.pool.workers

    def __del__(self):
        try:
            self._finish()
        except Exception:
            pass
