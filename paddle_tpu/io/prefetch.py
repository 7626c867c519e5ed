"""Asynchronous prefetch-to-device input pipeline (the DataLoader engine).

Rebuilt from the fork-based worker pool (PR 6): `os.fork()` under a
multithreaded JAX runtime is a real deadlock hazard (CPython's own
RuntimeWarning says so), so no code path here ever forks the parent. Three
worker transports, chosen per loader:

- **thread** (default): N worker threads fetch + collate batches. The
  hot decode path is numpy (slice/copy/stack release the GIL), so
  threads scale for array-heavy transforms and cost nothing to spawn.
- **process** (``worker_mode="process"``/"spawn"/"forkserver"): real
  worker PROCESSES started via a fork-safe context (forkserver's server
  is exec'd, spawn is exec'd — neither calls `os.fork()` in the
  multithreaded parent). Batches come back through PREALLOCATED shared-
  memory slots: the worker collates samples straight into the slot
  buffer (zero-copy assembly — no per-batch pickle of array payloads),
  the parent maps numpy views onto the slot and moves them to the
  device, then recycles the slot. Slot count bounds the jobs in flight,
  so backpressure falls out of slot availability. Requires a picklable
  dataset; ``worker_mode="auto"`` falls back to threads when the
  dataset cannot be shipped.
- **num_workers=0**: synchronous in-caller iteration (unchanged).

On top of either transport, `DeviceLoader` / `prefetch_to_device()` is
the double-buffered device iterator: a background stage keeps `size`
batches device-resident (``jax.device_put`` with an explicit Sharding,
so a dp-sharded batch lands shard-by-shard on its devices with no
host-side gather/re-split) while step N's compute runs, and every
``next()`` records how long the consumer waited on input:

- ``io.input_wait_ms`` / ``io.queue_depth`` / ``io.input_bound_frac``
  monitor gauges (live on the PR-3 ``/metrics`` endpoint);
- the same three fields land first-class in the step-record JSONL via
  the telemetry recorder (sink.STEP_OPTIONAL_KEYS), so "host-bound vs
  chip-bound" is a number in the flight recorder, not a vibe.

Worker processes never touch an accelerator: they produce numpy only,
and never initialize a JAX backend (`JAX_PLATFORMS` is pinned to cpu in
the child before the dataset is even unpickled).
"""
import collections
import itertools
import os
import pickle
import queue as _queue
import threading
import time
import weakref

import numpy as np

__all__ = [
    "DeviceLoader", "prefetch_to_device", "WorkerInfo", "get_worker_info",
    "default_collate_numpy", "consume_step_input_stats",
]

# --------------------------------------------------------------------------
# worker identity (paddle.io.get_worker_info analog)
# --------------------------------------------------------------------------

class WorkerInfo:
    """Identity of the worker executing the current ``__getitem__`` /
    dataset iteration: ``id`` in [0, num_workers), ``num_workers``,
    ``seed`` (per-worker), ``dataset`` (this worker's copy)."""

    def __init__(self, id, num_workers, seed=None, dataset=None):  # noqa: A002
        self.id = int(id)
        self.num_workers = int(num_workers)
        self.seed = seed
        self.dataset = dataset

    def __repr__(self):
        return (f"WorkerInfo(id={self.id}, "
                f"num_workers={self.num_workers}, seed={self.seed})")


_PROC_WORKER_INFO = None            # set in worker processes
_THREAD_WORKER_INFO = threading.local()


def get_worker_info():
    """Inside a worker (thread or process): its WorkerInfo; None in the
    main process/thread."""
    info = getattr(_THREAD_WORKER_INFO, "info", None)
    if info is not None:
        return info
    return _PROC_WORKER_INFO


# --------------------------------------------------------------------------
# input-wait telemetry shared with the flight recorder
# --------------------------------------------------------------------------

_INPUT_LOCK = threading.Lock()
_INPUT_STATS = None                 # guarded by: _INPUT_LOCK — most recent batch-fetch stats
_INTERIOR = threading.local()       # set in pipeline-internal threads


def _note_input_stats(wait_ms, depth, frac):
    """Record the fetch stats of the batch about to be consumed. The
    telemetry recorder pops these at step close (consume_step_input_stats)
    so they land first-class in that step's JSONL record. ONE process-
    global slot — latest fetch wins — so a consumer interleaving loaders
    (e.g. an eval pass inside fit) must drop the stale value before its
    next recorded step (hapi drains after every eval pass)."""
    global _INPUT_STATS
    from .. import monitor
    monitor.set_gauge("io.input_wait_ms", wait_ms)
    monitor.set_gauge("io.queue_depth", depth)
    monitor.set_gauge("io.input_bound_frac", frac)
    with _INPUT_LOCK:
        _INPUT_STATS = {"input_wait_ms": round(float(wait_ms), 4),
                        "input_queue_depth": int(depth),
                        "input_bound_frac": round(float(frac), 4)}


def consume_step_input_stats():
    """Pop the most recent batch-fetch stats (one-shot; None when no
    loader delivered a batch since the last pop). Called by
    TelemetryRecorder.end_step so the fields describe THIS step's input
    wait, not a stale one."""
    global _INPUT_STATS
    with _INPUT_LOCK:
        stats, _INPUT_STATS = _INPUT_STATS, None
    return stats


class _WaitTracker:
    """Per-iterator input-wait accounting: instantaneous wait per fetch
    plus an EMA input-bound fraction (wait / (wait + compute))."""

    def __init__(self, alpha=0.25):
        self.alpha = alpha
        self.frac = 0.0
        self._last_return = None

    def fetched(self, wait_s, depth):
        now = time.perf_counter()
        busy_s = 0.0
        if self._last_return is not None:
            busy_s = max(0.0, now - self._last_return - wait_s)
        inst = wait_s / max(1e-9, wait_s + busy_s)
        self.frac += self.alpha * (inst - self.frac)
        self._last_return = now
        # only the CONSUMER-facing end of the pipeline reports: a host
        # iterator being drained by a DeviceLoader stage thread would
        # otherwise race its (large, background) waits into the same
        # one-shot slot and invert the host-bound signal
        if getattr(_INTERIOR, "on", False):
            return
        _note_input_stats(wait_s * 1000.0, depth, self.frac)


# --------------------------------------------------------------------------
# numpy-side collate (runs in workers; no jax, no Tensor construction)
# --------------------------------------------------------------------------

def default_collate_numpy(batch):
    """Structure-preserving collate to NUMPY (the worker-side half of
    io.default_collate_fn): nested tuples/lists/dicts of arrays/scalars
    become stacked ndarrays; the parent wraps array leaves into device
    Tensors. Tensor leaves are read out via np.asarray so workers never
    build device arrays."""
    sample = batch[0]
    if isinstance(sample, (list, tuple)):
        return type(sample)(default_collate_numpy([b[i] for b in batch])
                            for i in range(len(sample)))
    if isinstance(sample, dict):
        return {k: default_collate_numpy([b[k] for b in batch])
                for k in sample}
    if hasattr(sample, "_value"):       # core.tensor.Tensor, duck-typed
        return np.stack([np.asarray(b._value) for b in batch])
    if isinstance(sample, np.ndarray):
        return np.stack(batch)
    if isinstance(sample, (bool, np.bool_)):
        return np.asarray(batch, dtype=np.bool_)
    if isinstance(sample, (int, np.integer)):
        return np.asarray(batch, dtype=np.int64)
    if isinstance(sample, (float, np.floating)):
        return np.asarray(batch, dtype=np.float32)
    return batch


def _flatten_tree(tree):
    """Flatten a collated batch (nested tuple/list/dict) into
    (ndarray leaves, spec). The spec is a picklable skeleton with leaf
    indices where arrays were."""
    leaves = []

    def walk(node):
        if isinstance(node, np.ndarray):
            leaves.append(np.ascontiguousarray(node))
            return ("a", len(leaves) - 1)
        if isinstance(node, (list, tuple)):
            return ("t", type(node).__name__, [walk(x) for x in node])
        if isinstance(node, dict):
            return ("d", [(k, walk(v)) for k, v in node.items()])
        return ("o", node)

    return leaves, walk(tree)


def _unflatten_tree(spec, leaves):
    tag = spec[0]
    if tag == "a":
        return leaves[spec[1]]
    if tag == "t":
        seq = [_unflatten_tree(s, leaves) for s in spec[2]]
        return tuple(seq) if spec[1] == "tuple" else list(seq)
    if tag == "d":
        return {k: _unflatten_tree(s, leaves) for k, s in spec[1]}
    return spec[1]


# --------------------------------------------------------------------------
# process workers: fork-safe context + shared-memory slot transport
# --------------------------------------------------------------------------

def _fork_safe_context(worker_mode):
    """A multiprocessing context that never calls os.fork() in this
    (multithreaded, JAX-owning) process. forkserver preferred: its
    server process is exec'd clean and workers fork from THAT, so
    per-worker startup skips full interpreter boot."""
    import multiprocessing as mp
    methods = mp.get_all_start_methods()
    if worker_mode in ("spawn", "forkserver"):
        if worker_mode not in methods:
            raise ValueError(f"start method {worker_mode!r} unavailable "
                             f"(have {methods})")
        return mp.get_context(worker_mode)
    for m in ("forkserver", "spawn"):
        if m in methods:
            return mp.get_context(m)
    raise RuntimeError("no fork-safe multiprocessing start method available")


def _process_worker_main(ds_bytes, init_bytes, index_q, result_q, wid,
                         num_workers, seed):
    """Worker PROCESS body. Jobs: (seq, indices, slot_name, slot_size,
    mode); None is shutdown. Replies: (seq, slot_name, slot_payload,
    pickled_payload, err) — exactly one payload is non-None on success.

    mode 'arrays': collate to numpy here and write the leaves into the
    shared-memory slot (overflowing batches ship pickled; the parent
    grows the slot). mode 'samples': ship raw samples pickled — the
    parent runs the user's custom collate_fn, preserving its semantics
    and output types exactly.
    """
    # workers produce numpy only; an accidental jax import in dataset
    # code must never initialize an accelerator backend here — pin
    # UNCONDITIONALLY (the parent may export JAX_PLATFORMS=tpu, and a
    # worker contending for the chip is exactly the failure this
    # transport exists to prevent)
    os.environ["JAX_PLATFORMS"] = "cpu"
    global _PROC_WORKER_INFO
    dataset = pickle.loads(ds_bytes)
    worker_init_fn = pickle.loads(init_bytes) if init_bytes else None
    _PROC_WORKER_INFO = WorkerInfo(wid, num_workers, seed=seed,
                                   dataset=dataset)
    if worker_init_fn is not None:
        worker_init_fn(wid)
    from multiprocessing import shared_memory
    open_slots = {}
    try:
        while True:
            job = index_q.get()
            if job is None:
                break
            seq, indices, slot_name, slot_size, mode = job
            try:
                samples = [dataset[i] for i in indices]
                if mode == "samples":
                    result_q.put((seq, slot_name, None, samples, None))
                    continue
                leaves, spec = _flatten_tree(default_collate_numpy(samples))
                total = sum(a.nbytes for a in leaves)
                if slot_name is not None and total <= slot_size:
                    shm = open_slots.get(slot_name)
                    if shm is None:
                        shm = shared_memory.SharedMemory(name=slot_name)
                        open_slots[slot_name] = shm
                    metas, off = [], 0
                    for a in leaves:
                        dst = np.ndarray(a.shape, a.dtype,
                                         buffer=shm.buf, offset=off)
                        dst[...] = a      # zero-copy assembly into the slot
                        metas.append((a.shape, a.dtype.str, off))
                        off += a.nbytes
                    result_q.put((seq, slot_name, (spec, metas), None, None))
                else:
                    # slot too small (or shm off): pickled fallback; the
                    # parent records `total` and grows the slot for the
                    # next acquisition
                    result_q.put((seq, slot_name, None,
                                  (spec, leaves, total), None))
            except Exception as e:   # surface the error in the parent
                result_q.put((seq, slot_name, None, None,
                              f"{type(e).__name__}: {e}"))
    finally:
        for shm in open_slots.values():
            try:
                shm.close()
            except Exception:
                pass


class _SlotPool:
    """Parent-side pool of PREALLOCATED shared-memory batch buffers.

    Slot count == max jobs in flight (backpressure: no free slot, no new
    job). Slots grow geometrically when a batch overflows (the worker
    falls back to pickle for that one batch and reports the needed
    size); growth replaces the slot under a fresh name so a worker's
    stale handle can never alias a recycled buffer.
    """

    def __init__(self, n_slots, slot_bytes=1 << 16):
        from multiprocessing import shared_memory
        self._shm_mod = shared_memory
        self._slots = {}
        self._free = collections.deque()
        for _ in range(n_slots):
            shm = shared_memory.SharedMemory(create=True, size=slot_bytes)
            self._slots[shm.name] = shm
            self._free.append(shm.name)
        self._default_bytes = slot_bytes

    def acquire(self):
        """-> (name, size) or None when every slot is in flight."""
        if not self._free:
            return None
        name = self._free.popleft()
        return name, self._slots[name].size

    def release(self, name, min_bytes=None):
        if name not in self._slots:
            return
        if min_bytes is not None and min_bytes > self._slots[name].size:
            name = self._grow(name, min_bytes)
        self._free.append(name)

    def _grow(self, name, need):
        old = self._slots.pop(name)
        try:
            old.close()
            old.unlink()
        except Exception:
            pass
        size = max(int(need * 1.25), old.size * 2, self._default_bytes)
        shm = self._shm_mod.SharedMemory(create=True, size=size)
        self._slots[shm.name] = shm
        return shm.name

    def view(self, name, metas):
        shm = self._slots[name]
        return [np.ndarray(shape, np.dtype(dt), buffer=shm.buf, offset=off)
                for shape, dt, off in metas]

    def close(self):
        for shm in self._slots.values():
            try:
                shm.close()
                shm.unlink()
            except Exception:
                pass
        self._slots.clear()
        self._free.clear()


def _estimate_batch_bytes(loader, ds_bytes=None):
    """Initial shared-memory slot size: probe ONE sample and scale by
    the batch size, so the first real batches land in the slot instead
    of all paying the pickled-overflow slow path (a 19MB ResNet batch
    against a blind 64KB default would overflow every slot exactly
    once). Slots still grow geometrically on genuine overflow. The
    probe runs against a THROWAWAY pickled-roundtrip copy when
    available: dataset[0] may materialize lazy state (sample pools,
    file handles) that the parent-side object must not keep — the
    parent never serves samples, its workers do."""
    try:
        bs = getattr(loader.batch_sampler, "batch_size", 1) or 1
        dataset = pickle.loads(ds_bytes) if ds_bytes else loader.dataset
        leaves, _ = _flatten_tree(
            default_collate_numpy([dataset[0]]))
        per_sample = sum(a.nbytes for a in leaves)
        return max(1 << 16, int(per_sample * bs * 1.25))
    except Exception:
        return 1 << 16


def dataset_is_picklable(dataset):
    try:
        pickle.dumps(dataset)
        return True
    except Exception:
        return False


# --------------------------------------------------------------------------
# the two worker pools
# --------------------------------------------------------------------------

class _PoolBase:
    """Shared lifecycle: monotonic sequence numbers (unique across
    epochs under persistent_workers) and idempotent shutdown."""

    def __init__(self):
        self._seq = itertools.count()
        self._closed = False

    def next_seq(self):
        return next(self._seq)

    def shutdown(self):
        if self._closed:
            return
        self._closed = True
        self._shutdown_impl()

    def __del__(self):
        try:
            self.shutdown()
        except Exception:
            pass


class _ThreadPool(_PoolBase):
    """Worker THREADS: fetch + collate in-process. The collate runs the
    loader's real collate_fn, so output types match num_workers=0
    exactly; numpy decode work (slice/copy/stack) releases the GIL."""

    def __init__(self, loader):
        super().__init__()
        self.num_workers = loader.num_workers
        self._dataset = loader.dataset
        self._collate = loader.collate_fn
        self._init_fn = loader.worker_init_fn
        self._index_q = _queue.Queue()
        self.result_q = _queue.Queue()
        self._threads = []
        for wid in range(self.num_workers):
            t = threading.Thread(target=self._worker, args=(wid,),
                                 name=f"paddle-io-worker-{wid}", daemon=True)
            t.start()
            self._threads.append(t)

    def _worker(self, wid):
        # seed=wid matches the process-pool contract, so dataset code
        # keying augmentation off worker_info.seed behaves identically
        # across worker modes
        _THREAD_WORKER_INFO.info = WorkerInfo(wid, self.num_workers,
                                              seed=wid,
                                              dataset=self._dataset)
        if self._init_fn is not None:
            self._init_fn(wid)
        while True:
            job = self._index_q.get()
            if job is None:
                return
            seq, indices = job
            try:
                batch = self._collate([self._dataset[i] for i in indices])
                self.result_q.put((seq, batch, None))
            except Exception as e:
                self.result_q.put((seq, None, f"{type(e).__name__}: {e}"))

    def submit(self, seq, indices):
        self._index_q.put((seq, list(indices)))

    def finalize_batch(self, payload):
        return payload

    def reclaim(self, payload):
        """Drop an unconsumed result (no resources to recycle here)."""

    def workers_alive(self):
        return [t for t in self._threads if t.is_alive()]

    def _shutdown_impl(self):
        for _ in self._threads:
            self._index_q.put(None)
        for t in self._threads:
            t.join(timeout=5)
        self._threads = []


class _ProcessPool(_PoolBase):
    """Worker PROCESSES over a fork-safe start method with shared-memory
    slot transport (see module docstring). `finalize_batch` runs in the
    parent: map views onto the slot, hand them to the device stage, then
    recycle the slot."""

    def __init__(self, loader, mode, ds_bytes=None):
        super().__init__()
        self.num_workers = loader.num_workers
        self._collate = loader.collate_fn
        from .dataloader import default_collate_fn
        self._default_collate = loader.collate_fn is default_collate_fn
        self._use_shm = loader.use_shared_memory and self._default_collate
        self.mode = "arrays" if self._default_collate else "samples"
        n_slots = max(2, self.num_workers * loader.prefetch)
        self.capacity = n_slots
        if ds_bytes is None:
            ds_bytes = pickle.dumps(loader.dataset)
        self._slots = (_SlotPool(n_slots,
                                 slot_bytes=_estimate_batch_bytes(
                                     loader, ds_bytes))
                       if self._use_shm else None)
        ctx = _fork_safe_context(mode)
        self._index_q = ctx.Queue()
        self.result_q = ctx.Queue()
        init_bytes = (pickle.dumps(loader.worker_init_fn)
                      if loader.worker_init_fn is not None else b"")
        self._procs = []
        for wid in range(self.num_workers):
            p = ctx.Process(
                target=_process_worker_main,
                args=(ds_bytes, init_bytes, self._index_q, self.result_q,
                      wid, self.num_workers, wid),
                daemon=True)
            p.start()
            self._procs.append(p)

    def submit(self, seq, indices):
        slot_name, slot_size = None, 0
        if self._slots is not None:
            acq = self._slots.acquire()
            if acq is None:     # caller respects capacity; belt & braces
                raise RuntimeError("no free shared-memory slot")
            slot_name, slot_size = acq
        self._index_q.put((seq, list(indices), slot_name, slot_size,
                           self.mode))

    def finalize_batch(self, payload, to_device=None):
        """payload = (slot_name, slot_payload, pickled_payload). Returns
        the finished host/device batch. `to_device(leaves) -> leaves` is
        applied while the slot is still held (the device stage must
        consume the views before the buffer is recycled)."""
        slot_name, slot_payload, pickled = payload
        if slot_payload is not None:
            spec, metas = slot_payload
            leaves = self._slots.view(slot_name, metas)
            try:
                if to_device is not None:
                    leaves = to_device(leaves)
                else:
                    leaves = [np.array(a) for a in leaves]   # own the data
            finally:
                self._slots.release(slot_name)
            return _unflatten_tree(spec, leaves)
        if slot_name is not None and self._slots is not None:
            # the batch overflowed this slot: grow it for next time
            need = pickled[2] if isinstance(pickled, tuple) \
                and len(pickled) == 3 else None
            self._slots.release(slot_name, min_bytes=need)
        if self.mode == "samples":
            return self._collate(pickled)
        spec, leaves, _ = pickled
        if to_device is not None:
            leaves = to_device(leaves)
        return _unflatten_tree(spec, leaves)

    def reclaim(self, payload):
        """Release the shared-memory slot of an unconsumed result
        (abandoned epoch / worker error) so the next epoch's jobs can
        acquire it — without this, a persistent pool starves."""
        slot_name, slot_payload, pickled = payload
        if slot_name is not None and self._slots is not None:
            need = pickled[2] if isinstance(pickled, tuple) \
                and len(pickled) == 3 else None
            self._slots.release(slot_name, min_bytes=need)

    def workers_alive(self):
        return [p for p in self._procs if p.is_alive()]

    def _shutdown_impl(self):
        for _ in self._procs:
            try:
                self._index_q.put(None)
            except Exception:
                break
        deadline = time.monotonic() + 5
        for p in self._procs:
            p.join(timeout=max(0.1, deadline - time.monotonic()))
        for p in self._procs:
            if p.is_alive():
                p.terminate()
                p.join(timeout=5)
        for q in (self._index_q, self.result_q):
            try:
                q.close()
                q.join_thread()
            except Exception:
                pass
        self._procs = []
        if self._slots is not None:
            self._slots.close()
            self._slots = None


def make_pool(loader):
    """Resolve the loader's worker_mode to a live pool. 'auto' prefers
    threads (zero spawn cost, deadlock-proof); 'process' requires a
    picklable dataset and picks forkserver/spawn. 'fork' is rejected
    outright — that is the deadlock the rebuild removes."""
    mode = getattr(loader, "worker_mode", "auto") or "auto"
    if mode == "fork":
        raise ValueError(
            "worker_mode='fork' is not supported: os.fork() under a "
            "multithreaded JAX runtime deadlocks (CPython warns about "
            "it). Use 'process' (forkserver/spawn), "
            "'thread', or 'auto'.")
    if mode in ("process", "spawn", "forkserver"):
        try:     # pickle ONCE; the bytes ship to the workers as-is
            ds_bytes = pickle.dumps(loader.dataset)
        except Exception as e:
            raise ValueError(
                f"worker_mode={mode!r} needs a picklable dataset "
                "(spawn/forkserver workers receive it by pickle); use "
                f"worker_mode='thread' for closure-captured datasets "
                f"[{type(e).__name__}: {e}]") from e
        return _ProcessPool(loader, mode if mode != "process" else "auto",
                            ds_bytes=ds_bytes)
    if mode in ("auto", "thread"):
        return _ThreadPool(loader)
    raise ValueError(f"unknown worker_mode {mode!r}; expected one of "
                     "'auto', 'thread', 'process', 'spawn', 'forkserver'")


# --------------------------------------------------------------------------
# the multi-worker iterator (sampler order preserved, bounded in-flight)
# --------------------------------------------------------------------------

class MultiWorkerIterator:    # guarded by: none (single active iterator per pool — _invalidate poisons the old one before a new one may submit)
    """Drives a worker pool through one pass of the batch sampler.

    Index feeding has backpressure (jobs in flight <= pool capacity —
    for process pools that is the shared-memory slot count, for thread
    pools num_workers * prefetch), results are REORDERED to sampler
    order regardless of worker completion, and result waits poll worker
    liveness so a killed worker raises instead of hanging. Determinism:
    the sampler runs only in the parent, so for a fixed seed the batch
    stream is identical across num_workers and worker modes."""

    def __init__(self, loader, pool):
        self.loader = loader
        self.pool = pool
        # capture the target placement NOW: DeviceLoader announces it on
        # the loader only around iterator creation, so a later direct
        # iteration (or a second DeviceLoader with a different sharding)
        # can never inherit this iterator's placement
        self._device_sharding = getattr(loader, "device_sharding", None)
        self._stolen = False
        self._jobs = iter(loader.batch_sampler)
        self._n_jobs = len(loader.batch_sampler)
        self._base = None
        self._sent = 0
        self._done = 0
        self._exhausted = False
        self._lost = 0            # error replies consumed off-queue
        self._pending = {}
        self._limit = getattr(pool, "capacity",
                              max(2, pool.num_workers * loader.prefetch))
        self._wait = _WaitTracker()
        self._closed = False
        self._feed()

    def __iter__(self):
        return self

    def _feed(self):
        while not self._exhausted and self._sent - self._done < self._limit:
            try:
                indices = next(self._jobs)
            except StopIteration:
                self._exhausted = True
                return
            seq = self.pool.next_seq()
            if self._base is None:
                self._base = seq
            self.pool.submit(seq, indices)
            self._sent += 1

    def __next__(self):
        from .. import monitor
        if self._stolen:
            raise RuntimeError(
                "this DataLoader iterator was invalidated: a new iterator "
                "was started on the persistent_workers loader (one active "
                "iterator at a time — they share the worker pool)")
        if self._done >= self._n_jobs:
            self.close()
            raise StopIteration
        want = (self._base or 0) + self._done
        t0 = time.perf_counter()
        deadline = self.loader.timeout or None
        while want not in self._pending:
            try:
                seq, *payload = self.pool.result_q.get(
                    timeout=deadline or 5.0)
            except _queue.Empty:
                alive = self.pool.workers_alive()
                if len(alive) < self.pool.num_workers or deadline:
                    self.close()
                    raise RuntimeError(
                        f"DataLoader worker(s) died or timed out waiting "
                        f"{deadline or 5.0}s for batch "
                        f"{want - (self._base or 0)}") from None
                continue
            err = payload[-1]
            if err is not None:
                # the failed job's reply is consumed here: recycle its
                # slot and account it so close()'s drain doesn't wait
                # for a result that already arrived
                self.pool.reclaim(tuple(payload[:-1]))
                self._lost += 1
                self.close()
                raise RuntimeError(f"DataLoader worker failed: {err}")
            self._pending[seq] = payload[:-1]
            # depth counts batches ready beyond the one being awaited
        wait_s = time.perf_counter() - t0
        payload = self._pending.pop(want)
        self._done += 1
        # finalize BEFORE feeding: for process pools, finalize recycles
        # the shared-memory slot the next job needs
        batch = self._finalize(payload)
        self._feed()
        self._wait.fetched(wait_s, len(self._pending))
        monitor.incr("io.batches")
        if self._done >= self._n_jobs and not self.loader.persistent_workers:
            self.close()
        return batch

    def _finalize(self, payload):
        if isinstance(self.pool, _ProcessPool):
            if self.pool.mode == "samples":
                # custom collate_fn ran in the parent: its output types
                # must pass through untouched (exactly what num_workers
                # =0 and thread modes yield)
                return self.pool.finalize_batch(tuple(payload))
            out = self.pool.finalize_batch(
                tuple(payload),
                to_device=self.loader._leaf_transfer(self._device_sharding))
            return self.loader._wrap_leaves(out)
        return payload[0]

    def _invalidate(self):
        """Called when a NEW iterator is started on the persistent-
        workers loader this iterator was feeding: drain the in-flight
        jobs (their slots must recycle before the new iterator submits)
        and poison this one — two live iterators over the shared pool
        would steal each other's results and deadlock."""
        self.close()
        self._stolen = True

    def close(self):
        if self._closed:
            return
        self._closed = True
        if not self.loader.persistent_workers:
            self.pool.shutdown()
            if getattr(self.loader, "_pool", None) is self.pool:
                self.loader._pool = None
            return
        # persistent pool outlives this iterator: every in-flight job's
        # result must be drained and its shared-memory slot reclaimed,
        # or the next epoch's submits starve on slot acquisition (and
        # stale results poison the next iterator's reorder buffer)
        outstanding = (self._sent - self._done - self._lost
                       - len(self._pending))
        for payload in self._pending.values():
            self.pool.reclaim(tuple(payload))
        self._pending.clear()
        deadline = time.monotonic() + 10
        while outstanding > 0 and time.monotonic() < deadline:
            try:
                _seq, *payload = self.pool.result_q.get(timeout=0.5)
            except _queue.Empty:
                if len(self.pool.workers_alive()) < self.pool.num_workers:
                    break
                continue
            self.pool.reclaim(tuple(payload[:-1]))
            outstanding -= 1
        if outstanding > 0:
            # could not drain cleanly (dead worker / lost job): the pool
            # is poisoned — tear it down so the next epoch rebuilds
            self.pool.shutdown()
            if getattr(self.loader, "_pool", None) is self.pool:
                self.loader._pool = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


# --------------------------------------------------------------------------
# prefetch-to-device: the double-buffered device iterator
# --------------------------------------------------------------------------

def _resolve_sharding(sharding):
    """None | jax.sharding.Sharding | Mesh | callable(arr)->Sharding
    -> callable(arr)->Sharding-or-None."""
    if sharding is None:
        return lambda arr: None
    if callable(sharding) and not hasattr(sharding, "spec") \
            and type(sharding).__name__ != "Mesh":
        return sharding
    if type(sharding).__name__ == "Mesh":
        mesh = sharding

        def per_leaf(arr):
            from ..distributed import env
            return env.trim_batch_sharding(arr, env.batch_sharding(mesh),
                                           mesh)
        return per_leaf
    sh = sharding

    def fixed(arr):
        from ..distributed import env
        return env.trim_batch_sharding(arr, sh, getattr(sh, "mesh", None))
    return fixed


def _leaf_put(sharding):
    """-> put(value) -> device jax.Array for one array leaf, honoring
    the resolved per-leaf sharding and skipping the transfer entirely
    when the value is already equivalently placed (the no-redundant-h2d
    contract ShardedTrainStep relies on)."""
    import jax
    per_leaf = _resolve_sharding(sharding)

    def put(v):
        sh = per_leaf(v)
        if isinstance(v, jax.Array):
            cur = getattr(v, "sharding", None)
            if sh is None:
                return v
            try:
                if cur is not None and cur.is_equivalent_to(sh, v.ndim):
                    return v
            except Exception:
                pass
        return jax.device_put(v, sh) if sh is not None else jax.device_put(v)
    return put


def device_put_batch(batch, sharding=None):
    """Move every array leaf of a (possibly nested) host batch onto the
    device(s): ``jax.device_put`` with the resolved per-leaf Sharding —
    each dp shard lands directly on its device, no host-side gather or
    re-split. Tensor leaves come back as Tensors on fresh device values.
    Blocks until the transfers complete so callers may recycle the host
    buffers (shared-memory slots) immediately after return."""
    import jax
    from ..core.tensor import Tensor
    put = _leaf_put(sharding)

    def to_dev(x):
        if isinstance(x, Tensor):
            return Tensor(put(x._value), stop_gradient=x.stop_gradient)
        if isinstance(x, (np.ndarray, jax.Array)):
            return put(x)
        return x

    moved = jax.tree_util.tree_map(
        to_dev, batch, is_leaf=lambda x: isinstance(x, Tensor))
    arrs = [x._value if isinstance(x, Tensor) else x
            for x in jax.tree_util.tree_leaves(
                moved, is_leaf=lambda x: isinstance(x, Tensor))
            if isinstance(x, Tensor) or isinstance(x, jax.Array)]
    if arrs:
        jax.block_until_ready(arrs)
    return moved


class DeviceLoader:
    """Double-buffered device iterator over any host-batch iterable.

    A background stage thread pulls host batches and dispatches their
    H2D transfer (``jax.device_put`` with an explicit per-leaf Sharding
    when given), keeping up to ``size`` device-resident batches queued —
    step N's compute overlaps batch N+1's transfer. ``__next__`` yields
    batches whose array leaves are already jax Arrays placed per the
    sharding (TrainStep passes them through untouched;
    ShardedTrainStep's shard_batch recognizes the placement and skips
    its own device_put), and records input_wait_ms / queue depth /
    input-bound fraction into the monitor gauges and the telemetry
    step records.

    sharding: None (default device) | a jax Sharding (trimmed per leaf
    rank/divisibility) | a Mesh (dp/sp batch sharding from
    distributed.env) | callable(ndarray) -> Sharding.
    """

    def __init__(self, loader, sharding=None, size=2):
        self.loader = loader
        self.sharding = sharding
        self.size = max(1, int(size))

    def __len__(self):
        return len(self.loader)

    def __iter__(self):
        # tell a wrapped DataLoader the target placement BEFORE its
        # iterator spins up: process-pool finalize then device_puts the
        # shared-memory views straight to the right devices and the
        # stage's device_put_batch recognizes the placement (no second
        # reshard hop)
        if hasattr(self.loader, "device_sharding"):
            # scoped to iterator creation: MultiWorkerIterator captures
            # the placement in __init__, so the attribute resets before
            # anyone else iterates the loader
            self.loader.device_sharding = self.sharding
            try:
                host_iter = iter(self.loader)
            finally:
                self.loader.device_sharding = None
        else:
            host_iter = iter(self.loader)
        return _DeviceIterator(self, host_iter)


def _device_stage_main(host_iter, q, stop, sharding, errbox, sentinel):
    """Stage-thread body, deliberately a MODULE function: the thread
    must hold no reference to the _DeviceIterator, or an abandoned
    iterator (consumer broke out without close()) could never be
    garbage-collected and its finalizer — the only thing that stops
    this loop — would never run."""
    _INTERIOR.on = True     # host-iterator waits in this thread are
    # pipeline-internal, not the consumer's input wait
    try:
        for batch in host_iter:
            if stop.is_set():
                break
            batch = device_put_batch(batch, sharding)
            while not stop.is_set():
                try:
                    q.put(batch, timeout=0.25)
                    break
                except _queue.Full:
                    continue
    except BaseException as e:          # surfaced on the consumer side
        errbox.append(e)
    finally:
        while not stop.is_set():
            try:
                q.put(sentinel, timeout=0.25)
                break
            except _queue.Full:
                continue


class _DeviceIterator:
    _SENTINEL = object()

    def __init__(self, dl, host_iter):
        self._q = _queue.Queue(maxsize=dl.size)
        self._errbox = []
        self._finished = False
        self._stop = threading.Event()
        self._wait = _WaitTracker()
        self._thread = threading.Thread(
            target=_device_stage_main,
            args=(host_iter, self._q, self._stop, dl.sharding,
                  self._errbox, self._SENTINEL),
            name="paddle-io-device-stage", daemon=True)
        self._thread.start()
        weakref.finalize(self, self._stop.set)

    def __iter__(self):
        return self

    def __next__(self):
        if self._finished:
            raise StopIteration    # repeated next() must not block
        t0 = time.perf_counter()
        while True:
            try:
                item = self._q.get(timeout=0.25)
                break
            except _queue.Empty:
                # closed or stage thread gone with nothing queued: the
                # sentinel will never come — finish instead of hanging
                if self._stop.is_set() or not self._thread.is_alive():
                    self._finished = True
                    if self._errbox:
                        raise self._errbox.pop(0)
                    raise StopIteration from None
        wait_s = time.perf_counter() - t0
        if item is self._SENTINEL:
            self._finished = True
            self._stop.set()
            if self._errbox:
                raise self._errbox.pop(0)
            raise StopIteration
        self._wait.fetched(wait_s, self._q.qsize())
        return item

    def close(self):
        self._stop.set()
        # Drain-and-join until the stage thread is really gone. A single
        # drain raced the stage thread: it could already be inside
        # `q.put(batch, timeout=0.25)` when stop was set, so its put
        # succeeded AFTER our sweep and a device-resident batch stayed
        # pinned in the queue for the iterator's remaining lifetime.
        # Draining in a loop keeps the queue unblocked until the thread
        # observes stop and exits; the final sweep catches anything the
        # last put landed.
        t = self._thread
        while t.is_alive():
            self._drain()
            t.join(timeout=0.05)
        self._drain()

    def _drain(self):
        try:
            while True:
                self._q.get_nowait()
        except _queue.Empty:
            pass


def prefetch_to_device(loader, sharding=None, size=2):
    """Wrap `loader` (a DataLoader or any iterable of host batches) in a
    DeviceLoader: device-resident, double-buffered, wait-instrumented.
    The tf.data ``prefetch_to_device`` analog."""
    return DeviceLoader(loader, sharding=sharding, size=size)
