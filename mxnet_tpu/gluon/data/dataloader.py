"""DataLoader (reference: python/mxnet/gluon/data/dataloader.py ~L400).

The reference uses multiprocessing workers passing NDArrays through POSIX
shared memory (cpu_shared_storage_manager.h).  This rebuild keeps both
transports:

- ``num_workers>0`` (default): PROCESS workers — batches cross back via
  ``multiprocessing.shared_memory`` (one copy into shm in the worker, one
  device_put out of it in the parent), matching the reference's shm
  design.  This is the path for GIL-bound python transforms.  Workers use
  the ``spawn`` start method (an initialized PjRt client does not survive
  fork) and pin themselves to the CPU backend — the input pipeline is
  host work by definition.  Dataset + batchify_fn must be picklable,
  and (standard ``spawn`` rule) a script creating a worker-backed
  DataLoader at module level needs an ``if __name__ == "__main__"``
  guard — children re-import ``__main__``.
- ``thread_pool=True``: the round-3 thread pool — zero transport cost,
  right when the heavy lifting already releases the GIL (libmxio, numpy).

``pin_memory`` is accepted and ignored: jax.device_put is the only
host->device path on TPU and stages through PjRt's own pinned buffers.
"""
from __future__ import annotations

import multiprocessing
import pickle
from concurrent.futures import ThreadPoolExecutor
from typing import Callable, List, Optional

import numpy as np

from ...base import MXNetError
from .dataset import Dataset
from .sampler import BatchSampler, RandomSampler, SequentialSampler

__all__ = ["DataLoader", "default_batchify_fn"]

# arrays at/above this size ride shared memory; smaller ones pickle
_SHM_MIN_BYTES = 1 << 15

_worker_state = None  # (dataset, batchify_fn) inside a worker process


def _worker_init(payload: bytes):
    # FIRST: pin the worker to the host backend.  A chip belongs to one
    # process, the parent's; a worker that inherited its platform would
    # try to claim the chip and fail or hang.
    import jax

    jax.config.update("jax_platforms", "cpu")
    global _worker_state
    _worker_state = pickle.loads(payload)


def _encode(obj, created=None):
    """Worker-side: batch pytree -> picklable tree with big ndarrays in
    POSIX shared memory (reference: cpu_shared storage, ~L60).  `created`
    collects segment names so a mid-batch failure (e.g. ENOSPC on the
    second array) can unlink what the batch already allocated."""
    from ...ndarray import NDArray

    if isinstance(obj, NDArray):
        obj = obj.asnumpy()
    if isinstance(obj, np.ndarray) and obj.nbytes >= _SHM_MIN_BYTES:
        from multiprocessing import shared_memory

        shm = shared_memory.SharedMemory(create=True, size=obj.nbytes)
        if created is not None:
            created.append(shm.name)
        np.ndarray(obj.shape, obj.dtype, buffer=shm.buf)[...] = obj
        name = shm.name
        shm.close()
        return ("shm", name, obj.shape, str(obj.dtype))
    if isinstance(obj, (list, tuple)):
        return ("seq", isinstance(obj, tuple),
                [_encode(o, created) for o in obj])
    return ("raw", obj)


def _decode(enc):
    """Parent-side: rebuild the batch; shm segments are copied into device
    buffers (nd.array) and unlinked immediately."""
    from ... import ndarray as nd

    kind = enc[0]
    if kind == "shm":
        from multiprocessing import shared_memory

        _, name, shape, dtype = enc
        shm = shared_memory.SharedMemory(name=name)
        try:
            # explicit heap copy BEFORE unlink: the CPU backend's
            # device_put aliases host numpy memory zero-copy, so handing
            # the shm view to nd.array and unmapping would leave the
            # device buffer pointing at freed pages
            arr = np.ndarray(shape, dtype, buffer=shm.buf).copy()
        finally:
            shm.close()
            shm.unlink()
        return nd.array(arr, dtype=arr.dtype)
    if kind == "seq":
        _, is_tuple, items = enc
        vals = [_decode(o) for o in items]
        return tuple(vals) if is_tuple else vals
    val = enc[1]
    if isinstance(val, np.ndarray):
        return nd.array(val, dtype=val.dtype)
    return val


def _free(enc):
    """Unlink an encoded batch's shm segments without decoding it."""
    if enc[0] == "shm":
        _unlink([enc[1]])
    elif enc[0] == "seq":
        for o in enc[2]:
            _free(o)


def _unlink(names):
    from multiprocessing import shared_memory

    for name in names:
        try:
            shm = shared_memory.SharedMemory(name=name)
            shm.close()
            shm.unlink()
        except FileNotFoundError:
            pass


def _worker_fn(indices):
    dataset, batchify_fn = _worker_state
    created = []
    try:
        return _encode(batchify_fn([dataset[i] for i in indices]), created)
    except BaseException:
        _unlink(created)  # don't leak this batch's finished segments
        raise


def default_batchify_fn(data):
    """Stack samples into a batch (reference: dataloader.py ~L130)."""
    from ... import ndarray as nd
    from ...ndarray import NDArray

    if isinstance(data[0], NDArray):
        return nd.stack(*data)
    if isinstance(data[0], tuple):
        data = zip(*data)
        return [default_batchify_fn(list(i)) for i in data]
    arr = np.asarray(data)
    return nd.array(arr, dtype=arr.dtype)


class DataLoader:
    def __init__(self, dataset: Dataset, batch_size: Optional[int] = None,
                 shuffle: bool = False, sampler=None, last_batch=None,
                 batch_sampler=None, batchify_fn: Optional[Callable] = None,
                 num_workers: int = 0, pin_memory: bool = False,
                 pin_device_id: int = 0, prefetch: Optional[int] = None,
                 thread_pool: bool = False, timeout: int = 120,
                 prefetch_to=None):
        self._dataset = dataset
        if batch_sampler is None:
            if batch_size is None:
                raise MXNetError(
                    "batch_size must be specified unless batch_sampler is")
            if sampler is None:
                sampler = (RandomSampler(len(dataset)) if shuffle
                           else SequentialSampler(len(dataset)))
            elif shuffle:
                raise MXNetError(
                    "shuffle must not be specified if sampler is specified")
            batch_sampler = BatchSampler(sampler, batch_size,
                                         last_batch or "keep")
        elif (batch_size is not None or shuffle or sampler is not None
              or last_batch is not None):
            raise MXNetError(
                "batch_size, shuffle, sampler and last_batch must not be "
                "specified if batch_sampler is specified.")
        self._batch_sampler = batch_sampler
        self._batchify_fn = batchify_fn or default_batchify_fn
        self._num_workers = max(0, num_workers)
        self._thread_pool = thread_pool
        self._timeout = timeout
        self._prefetch = max(0, prefetch if prefetch is not None
                             else 2 * self._num_workers)
        # device-side prefetch hook (docs/PERFORMANCE.md §Async pipeline):
        # a DataParallelStep here stages every yielded batch onto the
        # step's input shardings in a background thread, so step() skips
        # its own H2D transfer
        self._prefetch_to = prefetch_to
        self._pool = None  # lazy persistent process pool

    def _load(self, indices) -> object:
        return self._batchify_fn([self._dataset[i] for i in indices])

    def _get_pool(self):
        if self._pool is None:
            import multiprocessing as mp

            ctx = mp.get_context("spawn")
            payload = pickle.dumps((self._dataset, self._batchify_fn))
            self._pool = ctx.Pool(self._num_workers, initializer=_worker_init,
                                  initargs=(payload,))
        return self._pool

    def __iter__(self):
        if self._prefetch_to is None:
            return self._iter_batches()
        from ...io.io import stage_batches

        return stage_batches(self._iter_batches(), self._prefetch_to)

    def _iter_batches(self):
        if self._num_workers == 0:
            for batch in self._batch_sampler:
                yield self._load(batch)
            return
        if self._thread_pool:
            # thread pool with bounded prefetch (double buffering)
            with ThreadPoolExecutor(max_workers=self._num_workers) as pool:
                batches = iter(self._batch_sampler)
                futures = []
                try:
                    for _ in range(self._prefetch or self._num_workers):
                        futures.append(pool.submit(self._load, next(batches)))
                except StopIteration:
                    pass
                while futures:
                    fut = futures.pop(0)
                    try:
                        futures.append(pool.submit(self._load, next(batches)))
                    except StopIteration:
                        pass
                    yield fut.result()
            return
        # process workers + shared-memory transport (reference semantics)
        pool = self._get_pool()
        batches = iter(self._batch_sampler)
        pending = []
        try:
            try:
                for _ in range(self._prefetch or self._num_workers):
                    pending.append(
                        pool.apply_async(_worker_fn, (next(batches),)))
            except StopIteration:
                pass
            while pending:
                res = pending.pop(0)
                try:
                    pending.append(
                        pool.apply_async(_worker_fn, (next(batches),)))
                except StopIteration:
                    pass
                yield _decode(res.get(self._timeout))
        finally:
            # abandoned iteration (break/exception): prefetched batches
            # hold live /dev/shm segments — drain and unlink them or they
            # accumulate until ENOSPC.  A worker still stuck past two
            # timeouts is best-effort: warn with the leak's identity
            # instead of silently dropping it.
            for res in pending:
                for attempt in (1, 2):
                    try:
                        _free(res.get(self._timeout))
                        break
                    except multiprocessing.TimeoutError:
                        if attempt == 2:
                            import warnings

                            warnings.warn(
                                "DataLoader drain timed out; a prefetched "
                                "batch's shared-memory segments may leak "
                                "until process exit")
                    except Exception:
                        break  # worker raised: _worker_fn already unlinked

    def __del__(self):
        pool = getattr(self, "_pool", None)  # __init__ may have raised
        if pool is not None:
            pool.terminate()

    def __len__(self):
        return len(self._batch_sampler)
