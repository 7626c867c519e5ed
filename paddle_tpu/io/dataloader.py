"""Dataset / Sampler / DataLoader.

Parity: `python/paddle/fluid/reader.py:146` DataLoader +
`python/paddle/fluid/dataloader/` (dataset.py, batch_sampler.py, worker
processes with shared-mem mmap tensors). TPU-native differences: batches are
collated into numpy on host workers and transferred once per step (minimizing
host->HBM traffic); multi-process workers use the standard multiprocessing
pool rather than the reference's custom mmap allocator
(`memory/allocation/mmap_allocator.cc`) because JAX owns device transfer.
"""
import itertools
import queue
import threading

import numpy as np

from ..core.tensor import Tensor
from ..core.random import default_generator


class Dataset:
    def __getitem__(self, idx):
        raise NotImplementedError

    def __len__(self):
        raise NotImplementedError


class IterableDataset(Dataset):
    def __iter__(self):
        raise NotImplementedError

    def __getitem__(self, idx):
        raise RuntimeError("IterableDataset has no __getitem__")

    def __len__(self):
        raise RuntimeError("IterableDataset has no __len__")


class TensorDataset(Dataset):
    def __init__(self, tensors):
        self.tensors = tensors

    def __getitem__(self, idx):
        return tuple(np.asarray(t._value)[idx] if isinstance(t, Tensor)
                     else np.asarray(t)[idx] for t in self.tensors)

    def __len__(self):
        t = self.tensors[0]
        return t.shape[0] if isinstance(t, Tensor) else len(t)


class ComposeDataset(Dataset):
    def __init__(self, datasets):
        self.datasets = list(datasets)

    def __len__(self):
        return min(len(d) for d in self.datasets)

    def __getitem__(self, idx):
        out = []
        for d in self.datasets:
            item = d[idx]
            out.extend(item if isinstance(item, (list, tuple)) else [item])
        return tuple(out)


class ConcatDataset(Dataset):
    def __init__(self, datasets):
        self.datasets = list(datasets)
        self.cum = np.cumsum([len(d) for d in self.datasets])

    def __len__(self):
        return int(self.cum[-1])

    def __getitem__(self, idx):
        ds = int(np.searchsorted(self.cum, idx, side="right"))
        prev = 0 if ds == 0 else int(self.cum[ds - 1])
        return self.datasets[ds][idx - prev]


class ChainDataset(IterableDataset):
    def __init__(self, datasets):
        self.datasets = list(datasets)

    def __iter__(self):
        return itertools.chain(*self.datasets)


class Subset(Dataset):
    def __init__(self, dataset, indices):
        self.dataset = dataset
        self.indices = list(indices)

    def __getitem__(self, idx):
        return self.dataset[self.indices[idx]]

    def __len__(self):
        return len(self.indices)


def random_split(dataset, lengths, generator=None):
    total = sum(lengths)
    if total != len(dataset):
        raise ValueError("sum of lengths != dataset size")
    perm = np.random.permutation(len(dataset))
    out, off = [], 0
    for n in lengths:
        out.append(Subset(dataset, perm[off:off + n].tolist()))
        off += n
    return out


class Sampler:
    def __init__(self, data_source=None):
        self.data_source = data_source

    def __iter__(self):
        raise NotImplementedError


class SequenceSampler(Sampler):
    def __iter__(self):
        return iter(range(len(self.data_source)))

    def __len__(self):
        return len(self.data_source)


class RandomSampler(Sampler):
    def __init__(self, data_source, replacement=False, num_samples=None,
                 generator=None):
        super().__init__(data_source)
        self.replacement = replacement
        self._num_samples = num_samples

    @property
    def num_samples(self):
        return self._num_samples or len(self.data_source)

    def __iter__(self):
        n = len(self.data_source)
        if self.replacement:
            return iter(np.random.randint(0, n, self.num_samples).tolist())
        return iter(np.random.permutation(n)[:self.num_samples].tolist())

    def __len__(self):
        return self.num_samples


class WeightedRandomSampler(Sampler):
    def __init__(self, weights, num_samples, replacement=True):
        self.weights = np.asarray(weights, dtype=np.float64)
        self.num_samples = num_samples
        self.replacement = replacement

    def __iter__(self):
        p = self.weights / self.weights.sum()
        return iter(np.random.choice(len(self.weights), self.num_samples,
                                     replace=self.replacement, p=p).tolist())

    def __len__(self):
        return self.num_samples


class BatchSampler(Sampler):
    def __init__(self, dataset=None, sampler=None, shuffle=False,
                 batch_size=1, drop_last=False):
        self.batch_size = batch_size
        self.drop_last = drop_last
        if sampler is not None:
            self.sampler = sampler
        elif shuffle:
            self.sampler = RandomSampler(dataset)
        else:
            self.sampler = SequenceSampler(dataset)

    def __iter__(self):
        batch = []
        for idx in self.sampler:
            batch.append(idx)
            if len(batch) == self.batch_size:
                yield batch
                batch = []
        if batch and not self.drop_last:
            yield batch

    def __len__(self):
        n = len(self.sampler)
        if self.drop_last:
            return n // self.batch_size
        return (n + self.batch_size - 1) // self.batch_size


class DistributedBatchSampler(BatchSampler):
    """Per-rank sharding of the sample space (reference
    `python/paddle/io/DistributedBatchSampler`); on TPU used for per-host
    data feeding of a dp-sharded global batch."""

    def __init__(self, dataset, batch_size, num_replicas=None, rank=None,
                 shuffle=False, drop_last=False):
        from .. import distributed as dist
        self.dataset = dataset
        self.batch_size = batch_size
        self.nranks = num_replicas if num_replicas is not None else \
            dist.get_world_size()
        self.local_rank = rank if rank is not None else dist.get_rank()
        self.shuffle = shuffle
        self.drop_last = drop_last
        self.epoch = 0
        self.num_samples = int(np.ceil(len(dataset) / self.nranks))
        self.total_size = self.num_samples * self.nranks

    def __iter__(self):
        n = len(self.dataset)
        indices = np.arange(n)
        if self.shuffle:
            rng = np.random.RandomState(self.epoch)
            rng.shuffle(indices)
        # pad to be divisible
        pad = self.total_size - n
        if pad > 0:
            indices = np.concatenate([indices, indices[:pad]])
        indices = indices[self.local_rank::self.nranks]
        batch = []
        for idx in indices.tolist():
            batch.append(idx)
            if len(batch) == self.batch_size:
                yield batch
                batch = []
        if batch and not self.drop_last:
            yield batch

    def __len__(self):
        if self.drop_last:
            return self.num_samples // self.batch_size
        return (self.num_samples + self.batch_size - 1) // self.batch_size

    def set_epoch(self, epoch):
        self.epoch = epoch


def default_collate_fn(batch):
    sample = batch[0]
    if isinstance(sample, (list, tuple)):
        return type(sample)(default_collate_fn([b[i] for b in batch])
                            for i in range(len(sample)))
    if isinstance(sample, dict):
        return {k: default_collate_fn([b[k] for b in batch]) for k in sample}
    if isinstance(sample, Tensor):
        return Tensor(np.stack([np.asarray(b._value) for b in batch]))
    if isinstance(sample, np.ndarray):
        return Tensor(np.stack(batch))
    if isinstance(sample, (int, np.integer)):
        return Tensor(np.asarray(batch, dtype=np.int64))
    if isinstance(sample, (float, np.floating)):
        return Tensor(np.asarray(batch, dtype=np.float32))
    return batch


class DataLoader:
    """Iterates a Dataset into device Tensors.

    Map-style datasets with num_workers>0 run an asynchronous prefetch
    pipeline (io.prefetch): worker THREADS by default (the numpy decode
    path releases the GIL), or real worker PROCESSES over a fork-safe
    start method with shared-memory batch transport
    (``worker_mode="process"``, picklable dataset required). Batches are
    delivered in sampler order regardless of worker completion order,
    so the stream is deterministic in num_workers for a fixed seed.
    Iterable datasets use a background-thread prefetch pipeline (the
    reference's BufferedReader double-buffering,
    `operators/reader/buffered_reader.h:36`).

    DEPRECATED (PR 6): the old fork-context worker pool is gone —
    ``os.fork()`` under multithreaded JAX is a deadlock hazard
    (CPython's RuntimeWarning) — and ``worker_mode="fork"`` raises.
    The constructor surface is otherwise unchanged;
    ``use_shared_memory`` now gates the preallocated shared-memory slot
    transport of process workers (ignored for threads).

    BEHAVIOR CHANGE vs the fork pool: the default ``worker_mode="auto"``
    runs worker THREADS that share ONE dataset object (the fork workers
    each had a copy-on-write copy). A dataset with per-instance mutable
    state (its own RandomState, parser buffers, file handles) must pass
    ``worker_mode="process"`` to get per-worker copies back — thread
    workers calling ``__getitem__`` concurrently on such a dataset race.

    A ``persistent_workers`` loader supports ONE active iterator at a
    time (they share the worker pool): starting a new epoch drains and
    invalidates the previous iterator, whose ``next()`` then raises.

    For training loops, wrap the loader in
    ``io.prefetch_to_device(loader, sharding=...)`` to overlap the H2D
    transfer with compute and land each dp shard directly on its device.
    """

    def __init__(self, dataset, feed_list=None, places=None,
                 return_list=True, batch_sampler=None, batch_size=1,
                 shuffle=False, drop_last=False, collate_fn=None,
                 num_workers=0, use_buffer_reader=True, use_shared_memory=True,
                 prefetch_factor=2, timeout=0, worker_init_fn=None,
                 persistent_workers=False, worker_mode="auto"):
        self.dataset = dataset
        self.collate_fn = collate_fn or default_collate_fn
        self.num_workers = num_workers
        self.prefetch = max(2, prefetch_factor)
        self.timeout = timeout
        self.worker_init_fn = worker_init_fn
        self.use_shared_memory = use_shared_memory
        self.persistent_workers = persistent_workers
        self.worker_mode = worker_mode
        self.device_sharding = None   # set by prefetch.DeviceLoader/callers
        self._pool = None
        self._active_iter = None      # weakref: persistent-workers guard
        self._iterable_mode = isinstance(dataset, IterableDataset)
        if self._iterable_mode:
            self.batch_sampler = None
            self.batch_size = batch_size
            self.drop_last = drop_last
        elif batch_sampler is not None:
            self.batch_sampler = batch_sampler
        else:
            self.batch_sampler = BatchSampler(dataset, shuffle=shuffle,
                                              batch_size=batch_size,
                                              drop_last=drop_last)

    def __len__(self):
        if self._iterable_mode:
            raise TypeError("IterableDataset has no len()")
        return len(self.batch_sampler)

    def _batches(self):
        from .. import monitor
        if self._iterable_mode:
            batch = []
            for item in self.dataset:
                batch.append(item)
                if len(batch) == self.batch_size:
                    monitor.incr("io.batches")
                    yield self.collate_fn(batch)
                    batch = []
            if batch and not self.drop_last:
                monitor.incr("io.batches")
                yield self.collate_fn(batch)
            return
        for indices in self.batch_sampler:
            monitor.incr("io.batches")
            yield self.collate_fn([self.dataset[i] for i in indices])

    def __iter__(self):
        if self.num_workers == 0:
            return self._batches()
        if self._iterable_mode:
            return self._iterable_prefetch()
        from .prefetch import MultiWorkerIterator, make_pool
        if self.persistent_workers:
            # one ACTIVE iterator at a time: two iterators sharing the
            # persistent pool would steal each other's results off the
            # single result queue and deadlock — drain and invalidate
            # the previous one before feeding new jobs
            prev = self._active_iter() if self._active_iter else None
            if prev is not None:
                prev._invalidate()
        if self._pool is None or not self.persistent_workers:
            self._pool = make_pool(self)
        it = MultiWorkerIterator(self, self._pool)
        if self.persistent_workers:
            import weakref
            self._active_iter = weakref.ref(it)
        return it

    def _iterable_prefetch(self):
        """Iterable datasets: one background producer thread feeding a
        bounded queue (backpressure = prefetch depth), waits recorded
        for the flight recorder."""
        import time as _time
        from .prefetch import _WaitTracker
        q = queue.Queue(maxsize=self.prefetch)
        sentinel = object()
        err = []

        def producer():
            try:
                for b in self._batches():
                    q.put(b)
            except BaseException as e:
                err.append(e)
            finally:
                q.put(sentinel)

        t = threading.Thread(target=producer, daemon=True,
                             name="paddle-io-iterable-prefetch")
        t.start()
        wait = _WaitTracker()
        while True:
            t0 = _time.perf_counter()
            item = q.get()
            if item is sentinel:
                if err:
                    raise err[0]
                break
            wait.fetched(_time.perf_counter() - t0, q.qsize())
            yield item

    # -- hooks used by io.prefetch ---------------------------------------
    def _leaf_transfer(self, sharding=None):
        """Process-pool finalize hook: move one batch's ndarray leaves
        (views into a shared-memory slot) onto the device and block
        until the copy lands — the slot is recycled right after."""
        from .prefetch import _leaf_put
        import jax
        put = _leaf_put(sharding)
        # the CPU client zero-copy-aliases aligned host buffers instead
        # of copying them; a device array aliasing a recycled slot is a
        # use-after-unmap, so on host-resident backends the leaf must be
        # copied out first. Real accelerators DMA the bytes to HBM —
        # there the view-to-device_put path is the zero-copy win.
        aliases_host = jax.default_backend() == "cpu"

        def xfer(leaves):
            if aliases_host:
                leaves = [np.array(a) for a in leaves]
            out = [put(a) for a in leaves]
            if out:
                jax.block_until_ready(out)
            return out
        return xfer

    def _wrap_leaves(self, tree):
        """Wrap array leaves of a worker-collated batch into Tensors so
        process-worker output matches default_collate_fn's exactly."""
        import jax

        def wrap(node):
            if isinstance(node, (np.ndarray, jax.Array)):
                return Tensor(node)
            if isinstance(node, tuple):
                return tuple(wrap(x) for x in node)
            if isinstance(node, list):
                return [wrap(x) for x in node]
            if isinstance(node, dict):
                return {k: wrap(v) for k, v in node.items()}
            return node
        return wrap(tree)

    def shutdown(self):
        """Tear down persistent workers (idempotent)."""
        pool, self._pool = self._pool, None
        if pool is not None:
            pool.shutdown()

    def __del__(self):
        try:
            self.shutdown()
        except Exception:
            pass


def get_worker_info():
    """Inside a worker (thread or process): that worker's WorkerInfo
    (id, num_workers, seed, dataset); None in the main process."""
    from .prefetch import get_worker_info as _gwi
    return _gwi()
