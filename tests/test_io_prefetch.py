"""PR-6 input-pipeline overhaul: the async prefetch loader
(paddle_tpu/io/prefetch.py + the rebuilt DataLoader), the
prefetch-to-device stage, the no-redundant-h2d hot-path contract, the
legacy constructor surface, and the triangle-grid sequential-flush
invariant (ADVICE.md round-5 debt; since the Kernel Doctor landed it
is asserted through KN501 rather than a source grep).
"""
import inspect
import threading
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.io import DataLoader, Dataset


class ArangeDataset(Dataset):
    """Deterministic map-style dataset: item i -> (f32 vector of i's,
    label i). Module-level and stateless so it pickles for fork-safe
    process workers (spawn/forkserver re-import this module)."""

    def __init__(self, n=64, dim=8):
        self.n = n
        self.dim = dim

    def __len__(self):
        return self.n

    def __getitem__(self, i):
        return (np.full((self.dim,), i, np.float32), np.int64(i))


class CountingDataset(ArangeDataset):
    """Counts fetched items via a class-level counter (thread workers
    share the instance, so the count sees every worker fetch)."""

    def __init__(self, n=64, dim=8):
        super().__init__(n, dim)
        self.fetched = 0
        self._lock = threading.Lock()

    def __getitem__(self, i):
        with self._lock:
            self.fetched += 1
        return super().__getitem__(i)


def _stream(loader):
    """Materialize the loader's full batch stream as numpy pairs."""
    out = []
    for bx, by in loader:
        out.append((np.asarray(bx.numpy()), np.asarray(by.numpy())))
    return out


def _assert_same_stream(a, b):
    assert len(a) == len(b)
    for (ax, ay), (bx, by) in zip(a, b):
        np.testing.assert_array_equal(ax, bx)
        np.testing.assert_array_equal(ay, by)


# ---------------------------------------------------------------------------
# determinism: same seed => same batch stream across worker counts/modes
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shuffle", [False, True])
def test_loader_deterministic_across_num_workers(shuffle):
    ds = ArangeDataset(48)
    streams = []
    for workers in (0, 2, 4):
        np.random.seed(123)   # RandomSampler draws from np.random
        loader = DataLoader(ds, batch_size=5, shuffle=shuffle,
                            num_workers=workers)
        streams.append(_stream(loader))
        loader.shutdown()
    _assert_same_stream(streams[0], streams[1])
    _assert_same_stream(streams[0], streams[2])
    # shuffle=True must actually permute (same seed, same permutation)
    if shuffle:
        first_labels = streams[0][0][1]
        assert not np.array_equal(first_labels, np.arange(5))


def test_process_workers_match_synchronous_stream():
    """Fork-safe PROCESS workers (spawn/forkserver + shared-memory slot
    transport) deliver the identical batch stream, in order."""
    ds = ArangeDataset(24)
    np.random.seed(7)
    ref = _stream(DataLoader(ds, batch_size=4, num_workers=0))
    np.random.seed(7)
    loader = DataLoader(ds, batch_size=4, num_workers=2,
                        worker_mode="process")
    got = _stream(loader)
    loader.shutdown()
    _assert_same_stream(ref, got)


def test_worker_mode_fork_rejected():
    """os.fork() under multithreaded JAX is the deadlock hazard the
    rebuild removed: asking for it is an error, not a warn."""
    with pytest.raises(ValueError, match="fork"):
        iter(DataLoader(ArangeDataset(8), batch_size=2, num_workers=2,
                        worker_mode="fork"))


def test_no_fork_start_method_reachable():
    """No code path in io.prefetch resolves to the 'fork' start method."""
    from paddle_tpu.io.prefetch import _fork_safe_context
    ctx = _fork_safe_context("auto")
    assert ctx.get_start_method() in ("forkserver", "spawn")
    # "fork" is rejected upstream (make_pool) before a context is ever
    # resolved; an unknown mode is an error, not a silent fallback
    with pytest.raises(ValueError, match="worker_mode"):
        iter(DataLoader(ArangeDataset(8), batch_size=2, num_workers=2,
                        worker_mode="nonsense"))


# ---------------------------------------------------------------------------
# backpressure + shutdown hygiene
# ---------------------------------------------------------------------------

def test_backpressure_bounds_prefetch():
    """Jobs in flight never exceed num_workers * prefetch_factor: a slow
    consumer must NOT let workers race through the whole epoch."""
    ds = CountingDataset(400, dim=4)
    batch = 4
    loader = DataLoader(ds, batch_size=batch, num_workers=2,
                        prefetch_factor=2)
    it = iter(loader)
    next(it)
    limit = 2 * loader.prefetch          # pool capacity, in batches
    time.sleep(0.3)                      # give eager workers rope
    # delivered (1) + in-flight (<= limit) batches, in items
    assert ds.fetched <= (limit + 1) * batch, \
        f"workers fetched {ds.fetched} items; backpressure broken"
    it.close()
    loader.shutdown()


def _io_worker_threads():
    return [t for t in threading.enumerate()
            if t.name.startswith("paddle-io-")]


def test_clean_shutdown_no_leaked_workers():
    before = len(_io_worker_threads())
    loader = DataLoader(ArangeDataset(30), batch_size=3, num_workers=3)
    for _ in loader:
        pass
    deadline = time.monotonic() + 5
    while len(_io_worker_threads()) > before and time.monotonic() < deadline:
        time.sleep(0.05)
    assert len(_io_worker_threads()) <= before, \
        f"leaked worker threads: {_io_worker_threads()}"


@pytest.mark.slow    # spawn/forkserver interpreter boots; ci.sh stage 6
def test_early_break_shutdown_and_process_pool_reaped():
    """Abandoning iteration mid-epoch (and shutdown()) must reap worker
    processes; no zombie children survive."""
    loader = DataLoader(ArangeDataset(64), batch_size=4, num_workers=2,
                        worker_mode="process")
    it = iter(loader)
    next(it)
    pool = loader._pool
    procs = list(pool._procs)
    assert procs and all(p.is_alive() for p in procs)
    it.close()
    loader.shutdown()
    for p in procs:
        p.join(timeout=5)
    assert not any(p.is_alive() for p in procs), "leaked worker processes"


@pytest.mark.slow    # spawn/forkserver interpreter boots; ci.sh stage 6
def test_persistent_process_pool_survives_early_break():
    """Abandoning an epoch mid-iteration must reclaim the in-flight
    shared-memory slots: the NEXT epoch over the same persistent pool
    has to deliver the full, correct stream (a leaked slot would starve
    submit() before the first batch)."""
    loader = DataLoader(ArangeDataset(32), batch_size=4, num_workers=2,
                        worker_mode="process", persistent_workers=True)
    it = iter(loader)
    next(it)
    it.close()                      # early break, jobs still in flight
    pool = loader._pool
    assert pool is not None and pool.workers_alive()
    np.random.seed(5)
    got = _stream(loader)           # fresh epoch over the SAME pool
    assert loader._pool is pool
    np.random.seed(5)
    ref = _stream(DataLoader(ArangeDataset(32), batch_size=4,
                             num_workers=0))
    _assert_same_stream(ref, got)
    loader.shutdown()


def test_abandoned_device_iterator_stage_thread_stops():
    """Dropping a DeviceLoader iterator WITHOUT close() must still stop
    the stage thread: the thread body holds no reference back to the
    iterator, so GC collects the abandoned iterator and its finalizer
    sets the stop event (a leaked stage thread would pin `size` device
    batches plus the whole host pipeline forever)."""
    import gc
    from paddle_tpu.io import prefetch_to_device
    loader = DataLoader(ArangeDataset(64), batch_size=4, num_workers=0)
    it = iter(prefetch_to_device(loader, size=2))
    next(it)                          # stage running, queue full
    th = it._thread
    del it
    gc.collect()
    th.join(timeout=5)
    assert not th.is_alive()


def test_device_iterator_close_joins_stage_and_leaves_queue_empty():
    """close() must not RACE the stage thread: a single queue sweep
    could run while the stage was already blocked inside
    `q.put(batch, timeout=0.25)` — its put then succeeded AFTER the
    sweep and a device batch stayed pinned in the queue forever.
    close() now drains until the stage thread has exited, so the queue
    is verifiably empty afterwards (repeated, to catch the timing)."""
    from paddle_tpu.io import prefetch_to_device
    for trial in range(8):
        loader = DataLoader(ArangeDataset(64), batch_size=4,
                            num_workers=0)
        it = iter(prefetch_to_device(loader, size=1))
        next(it)         # queue full, stage blocked in its next put
        it.close()
        assert not it._thread.is_alive()
        assert it._q.qsize() == 0, \
            f"trial {trial}: {it._q.qsize()} batch(es) left pinned"
        with pytest.raises(StopIteration):
            next(it)


def test_device_iterator_repeated_stop_and_post_close_next():
    """Iterator protocol: next() after exhaustion (or close) must raise
    StopIteration again, never block."""
    from paddle_tpu.io import prefetch_to_device
    loader = DataLoader(ArangeDataset(8), batch_size=4, num_workers=0)
    it = iter(prefetch_to_device(loader))
    list(it)
    with pytest.raises(StopIteration):
        next(it)
    it2 = iter(prefetch_to_device(
        DataLoader(ArangeDataset(8), batch_size=4, num_workers=0)))
    next(it2)
    it2.close()
    with pytest.raises(StopIteration):
        for _ in range(3):
            next(it2)


def test_persistent_concurrent_iterators_invalidated():
    """Two live iterators over one persistent_workers loader share the
    pool's single result queue and would steal each other's results
    (deadlock, not wrong data). Starting a new iterator must drain and
    invalidate the previous one: the stale handle raises immediately and
    the new iterator delivers the full, correct stream."""
    loader = DataLoader(ArangeDataset(24), batch_size=4, num_workers=2,
                        persistent_workers=True)
    it1 = iter(loader)
    next(it1)
    it2 = iter(loader)                  # invalidates it1, drains its jobs
    with pytest.raises(RuntimeError, match="invalidated"):
        next(it1)
    got = [(np.asarray(bx.numpy()), np.asarray(by.numpy()))
           for bx, by in it2]
    ref = _stream(DataLoader(ArangeDataset(24), batch_size=4,
                             num_workers=0))
    _assert_same_stream(ref, got)
    loader.shutdown()


def test_device_loader_sharding_scoped_to_iterator():
    """A DeviceLoader's sharding must not outlive its iterator: after
    training through prefetch_to_device(sharding=mesh), a DIRECT pass
    over the same loader yields default-placed (single-device) batches,
    not stale mesh-sharded ones."""
    import jax
    from paddle_tpu.distributed import env
    from paddle_tpu.io import prefetch_to_device

    mesh = env.build_mesh(dp=8)
    try:
        loader = DataLoader(ArangeDataset(16), batch_size=8, num_workers=2,
                            worker_mode="process", persistent_workers=True)
        for bx, _ in prefetch_to_device(loader, sharding=mesh):
            assert len(bx._value.sharding.device_set) == 8
        assert loader.device_sharding is None     # scoped, not sticky
        for bx, _ in loader:                      # direct host-side pass
            assert len(bx._value.sharding.device_set) == 1
    finally:
        loader.shutdown()
        env.clear_mesh()


def test_persistent_workers_survive_epochs():
    loader = DataLoader(ArangeDataset(12), batch_size=3, num_workers=2,
                        persistent_workers=True)
    s1 = _stream(loader)
    pool = loader._pool
    assert pool is not None and pool.workers_alive()
    s2 = _stream(loader)
    assert loader._pool is pool        # same pool, no respawn
    _assert_same_stream(s1, s2)
    loader.shutdown()


def test_worker_error_surfaces_not_hangs():
    class Broken(ArangeDataset):
        def __getitem__(self, i):
            if i == 7:
                raise RuntimeError("decode exploded")
            return super().__getitem__(i)

    loader = DataLoader(Broken(16), batch_size=4, num_workers=2)
    with pytest.raises(RuntimeError, match="decode exploded"):
        _stream(loader)


def test_get_worker_info_in_workers():
    from paddle_tpu.io import get_worker_info
    assert get_worker_info() is None   # main thread
    seen = []

    class Probe(ArangeDataset):
        def __getitem__(self, i):
            info = get_worker_info()
            seen.append(None if info is None else info.id)
            return super().__getitem__(i)

    for _ in DataLoader(Probe(12), batch_size=3, num_workers=2):
        pass
    assert seen and all(w in (0, 1) for w in seen)


# ---------------------------------------------------------------------------
# prefetch-to-device: double-buffered device iterator + telemetry taps
# ---------------------------------------------------------------------------

def test_prefetch_to_device_yields_device_resident_batches():
    import jax
    from paddle_tpu.io import prefetch_to_device
    from paddle_tpu.io.prefetch import consume_step_input_stats

    loader = DataLoader(ArangeDataset(20), batch_size=4, num_workers=0)
    consume_step_input_stats()           # drop stale state
    n = 0
    for bx, by in prefetch_to_device(loader, size=2):
        assert isinstance(bx._value, jax.Array)
        assert isinstance(by._value, jax.Array)
        n += 1
    assert n == 5
    # the device stage recorded this fetch for the flight recorder
    stats = consume_step_input_stats()
    assert stats is not None
    assert set(stats) == {"input_wait_ms", "input_queue_depth",
                          "input_bound_frac"}
    assert stats["input_wait_ms"] >= 0
    assert 0.0 <= stats["input_bound_frac"] <= 1.0
    assert consume_step_input_stats() is None      # one-shot pop


def test_input_stats_land_in_step_records_and_validate():
    """The loader taps ride the step-record schema end-to-end: recorder
    pops them at step close, sink validates them, /metrics gauges move."""
    from paddle_tpu import monitor, telemetry
    from paddle_tpu.io import prefetch_to_device
    from paddle_tpu.io.prefetch import consume_step_input_stats
    from paddle_tpu.telemetry.sink import validate_step_record

    consume_step_input_stats()
    loader = DataLoader(ArangeDataset(8), batch_size=4, num_workers=0)
    it = iter(prefetch_to_device(loader))
    next(it)
    rec = telemetry.make_step_record(step=0, step_ms=5.0, compile_ms=0.0,
                                     **(consume_step_input_stats() or {}))
    assert rec["input_wait_ms"] >= 0
    assert rec["input_queue_depth"] >= 0
    assert validate_step_record(rec) == []
    snap = monitor.snapshot()
    gauges = snap.get("gauges", snap)
    assert "io.input_wait_ms" in gauges
    assert "io.input_bound_frac" in gauges
    # a poisoned record must NOT validate
    bad = dict(rec, input_bound_frac=1.7)
    assert any("input_bound_frac" in p for p in validate_step_record(bad))


def test_device_loader_sharded_batches_with_mesh():
    """sharding=mesh lands each dp shard directly on its device (no
    host-side gather/re-split) and the spec trims for indivisible /
    lower-rank leaves."""
    import jax
    from paddle_tpu.distributed import env
    from paddle_tpu.io import prefetch_to_device

    mesh = env.build_mesh(dp=8)
    try:
        loader = DataLoader(ArangeDataset(32, dim=6), batch_size=8,
                            num_workers=0)
        for bx, by in prefetch_to_device(loader, sharding=mesh):
            assert isinstance(bx._value, jax.Array)
            spec = bx._value.sharding.spec
            assert tuple(spec)[:1] == ("dp",)
            assert len(bx._value.sharding.device_set) == 8
    finally:
        env.clear_mesh()


# ---------------------------------------------------------------------------
# no-redundant-h2d on the hot path (TrainStep / ShardedTrainStep)
# ---------------------------------------------------------------------------

def test_shard_batch_skips_device_put_for_resident_batches(monkeypatch):
    """A batch the input pipeline already placed with the dp sharding
    must pass through shard_batch WITHOUT a second device_put."""
    import jax
    from paddle_tpu.distributed import env
    from paddle_tpu.distributed.sharded_train import shard_batch

    mesh = env.build_mesh(dp=8)
    try:
        x = np.arange(16 * 4, dtype=np.float32).reshape(16, 4)
        first = shard_batch([x], mesh=mesh)
        assert len(first[0].sharding.device_set) == 8

        calls = []
        real_put = jax.device_put

        def counting_put(v, *a, **k):
            calls.append(type(v).__name__)
            return real_put(v, *a, **k)

        monkeypatch.setattr(jax, "device_put", counting_put)
        again = shard_batch(first, mesh=mesh)
        assert calls == [], f"redundant device_put on hot path: {calls}"
        assert again[0] is first[0]       # the very same buffer
    finally:
        env.clear_mesh()


def test_train_step_accepts_device_resident_batch_no_copy():
    """TrainStep's batch ingestion (jnp.asarray) must be identity for an
    already-device-resident jax.Array — no host round-trip, no copy."""
    import jax
    import jax.numpy as jnp

    x = jax.device_put(np.ones((4, 4), np.float32))
    assert jnp.asarray(x) is x
    # and the prefetch leaf-put recognizes equivalent placement
    from paddle_tpu.io.prefetch import _leaf_put
    put = _leaf_put(x.sharding)
    assert put(x) is x


# ---------------------------------------------------------------------------
# legacy surface locks
# ---------------------------------------------------------------------------

def test_dataloader_constructor_surface_locked():
    """The old constructor keywords must keep working verbatim (callers
    ported from the reference framework); new knobs only append."""
    params = list(inspect.signature(DataLoader.__init__).parameters)
    assert params == [
        "self", "dataset", "feed_list", "places", "return_list",
        "batch_sampler", "batch_size", "shuffle", "drop_last",
        "collate_fn", "num_workers", "use_buffer_reader",
        "use_shared_memory", "prefetch_factor", "timeout",
        "worker_init_fn", "persistent_workers", "worker_mode",
    ]
    # legacy kwargs accepted exactly as before
    loader = DataLoader(ArangeDataset(8), feed_list=None, places=None,
                        return_list=True, batch_size=2, shuffle=False,
                        drop_last=False, collate_fn=None, num_workers=0,
                        use_buffer_reader=True, use_shared_memory=True,
                        timeout=0, worker_init_fn=None,
                        persistent_workers=False)
    assert len(list(loader)) == 4


def test_reader_decorators_still_compose():
    """reader.py combinators (the pre-DataLoader legacy surface) keep
    working; multiprocess_reader degrades to chain without forking."""
    from paddle_tpu import reader

    def r1():
        return iter([1, 2, 3])

    def r2():
        return iter([4, 5])

    assert list(reader.buffered(r1, 2)()) == [1, 2, 3]
    assert list(reader.chain(r1, r2)()) == [1, 2, 3, 4, 5]
    assert list(reader.multiprocess_reader([r1, r2])()) == [1, 2, 3, 4, 5]
    assert list(reader.firstn(r1, 2)()) == [1, 2]


# ---------------------------------------------------------------------------
# ADVICE.md round-5 debt: the _flush_dq sequential-grid invariant —
# now checked as a PROPERTY (Kernel Doctor rule KN501) instead of the
# old source-grep: KN501 evaluates the output index_maps over the real
# grid, so it sees the revisits themselves, not the comment about them
# ---------------------------------------------------------------------------

def test_triangle_backward_grid_never_marked_parallel():
    """The merged triangle-grid backward walks live tiles column-major
    and flushes each dq window only in its diagonal column (_flush_dq);
    dk/dv scratch accumulates down columns. Both rely on Mosaic's
    DEFAULT sequential grid order. KN501 (analysis/kernel_lint) derives
    that property from the captured BlockSpecs: the tri kernels as
    shipped must pass, and a deliberately-parallelized copy of the SAME
    captured grid must fail — the invariant is machine-checked, not
    grepped."""
    import numpy as np
    from paddle_tpu.analysis import kernel_lint
    from paddle_tpu.ops.kernel_registry import get_kernel
    import paddle_tpu.ops.pallas_attention as pa

    for name in ("flash_bwd_merged_tri", "flash_fwd_tri"):
        reg = get_kernel(name)
        args, kwargs = reg.example(np.random.default_rng(0))
        caps, _ = kernel_lint.capture_kernels(
            reg.fn, args, kwargs, name=name)
        (cap,) = caps
        # as shipped: no dimension_semantics -> sequential -> clean
        assert cap.dimension_semantics is None
        assert kernel_lint.check_grid_races(cap) == []
        # the deliberately-parallelized copy: same kernel, same grid,
        # flat T axis marked parallel -> the flush invariant breaks
        bad = kernel_lint.check_grid_races(
            cap, semantics=("arbitrary", "parallel"))
        assert bad, f"{name}: parallelized T axis produced no KN501"
        assert all(f.rule_id == "KN501" for f in bad)
        assert any(name in f.location for f in bad)

    # the invariant's subject (and its machine-checked note) still
    # exists where we claim it does
    src = inspect.getsource(pa)
    assert "_flush_dq" in src
    assert "SEQUENTIAL-GRID INVARIANT" in src
    assert "KN501" in src
