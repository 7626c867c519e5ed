"""Continuous-batching serving engine (paddle_tpu/serving): block-pool
allocator, paged-vs-dense attention parity, engine-vs-run_generate
token parity (the numerics contract the CPU smoke gates), eviction
recompute, sampling independence and Config routing."""
import json

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.serving import (BlockPool, EngineConfig, PagedKVCache,
                                SamplingParams, ServingEngine)
from paddle_tpu.serving.kv_cache import NULL_BLOCK


def _small_gpt(seed=0):
    from paddle_tpu.models.gpt import GPTConfig, GPTForPretraining
    paddle.seed(seed)
    cfg = GPTConfig(vocab_size=512, hidden_size=128, num_layers=2,
                    num_heads=4, max_seq_len=128, dropout=0.0,
                    use_flash_attention=False)
    return GPTForPretraining(cfg)


def _refs(model, prompts, max_new, **kw):
    out = []
    for p in prompts:
        ids = paddle.to_tensor(np.asarray([p], np.int32))
        o, _ = model.generate(ids, max_new_tokens=max_new, **kw)
        out.append(np.asarray(o.numpy())[0, len(p):].tolist())
    return out


# ---------------------------------------------------------------------------
# block pool
# ---------------------------------------------------------------------------

class TestBlockPool:
    def test_alloc_free_roundtrip(self):
        pool = BlockPool(9)
        assert pool.capacity == 8 and pool.num_free == 8
        a = pool.alloc(3, owner="a")
        b = pool.alloc(2, owner="b")
        assert len(a) == 3 and len(b) == 2
        assert NULL_BLOCK not in a + b          # null block never handed out
        assert pool.num_used == 5
        assert pool.owner_of(a[0]) == "a"
        pool.free(a)
        assert pool.num_free == 6
        assert abs(pool.utilization() - 2 / 8) < 1e-9

    def test_exhaustion_makes_no_partial_allocation(self):
        pool = BlockPool(5)
        assert pool.alloc(3) is not None
        before = pool.num_free
        assert pool.alloc(2) is None            # only 1 left
        assert pool.num_free == before          # nothing leaked

    def test_double_free_and_foreign_free_raise(self):
        pool = BlockPool(4)
        blocks = pool.alloc(2)
        pool.free(blocks)
        with pytest.raises(ValueError):
            pool.free(blocks)
        with pytest.raises(ValueError):
            pool.free([NULL_BLOCK])

    def test_fragmentation_cannot_strand_capacity(self):
        """Paging point: after ANY interleaved alloc/free history, the
        pool can hand out exactly its free count — no placement
        constraint ever strands a free block."""
        pool = BlockPool(17)
        rs = np.random.RandomState(0)
        held = []
        for _ in range(200):
            if held and rs.rand() < 0.5:
                pool.free(held.pop(rs.randint(len(held))))
            else:
                got = pool.alloc(int(rs.randint(1, 4)))
                if got is not None:
                    held.append(got)
        free = pool.num_free
        if free:
            got = pool.alloc(free)              # every free block usable
            assert got is not None and len(got) == free

    def test_deterministic_under_seeded_schedule(self):
        def run():
            pool = BlockPool(33)
            rs = np.random.RandomState(7)
            held, trace = [], []
            for _ in range(300):
                if held and rs.rand() < 0.45:
                    blocks = held.pop(rs.randint(len(held)))
                    pool.free(blocks)
                    trace.append(("free", tuple(blocks)))
                else:
                    got = pool.alloc(int(rs.randint(1, 5)))
                    trace.append(("alloc", tuple(got or ())))
                    if got:
                        held.append(got)
            return trace
        assert run() == run()

    def test_blocks_for_tokens(self):
        assert PagedKVCache.blocks_for_tokens(1, 8) == 1
        assert PagedKVCache.blocks_for_tokens(8, 8) == 1
        assert PagedKVCache.blocks_for_tokens(9, 8) == 2


# ---------------------------------------------------------------------------
# paged attention parity
# ---------------------------------------------------------------------------

def test_paged_kernel_matches_gather_fallback():
    """The fused pallas paged kernel (interpret mode here) and the
    gather+dense fallback are the same attention."""
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas_decode import paged_decode_attention

    rs = np.random.RandomState(0)
    S, N, H, BS, NB, MB = 3, 4, 32, 8, 12, 4
    nh = N * H
    k_pages = jnp.asarray(rs.randn(NB, BS, nh), jnp.float32)
    v_pages = jnp.asarray(rs.randn(NB, BS, nh), jnp.float32)
    tables = jnp.asarray(
        [[3, 1, 0, 0], [2, 5, 7, 0], [4, 6, 8, 9]], jnp.int32)
    ctx = jnp.asarray([5, 13, 30], jnp.int32)
    q = jnp.asarray(rs.randn(S, 1, nh), jnp.float32)
    fb = paged_decode_attention(q, k_pages, v_pages, tables, ctx, N,
                                use_kernel=False)
    kn = paged_decode_attention(q, k_pages, v_pages, tables, ctx, N,
                                use_kernel=True)
    np.testing.assert_allclose(np.asarray(fb), np.asarray(kn),
                               atol=2e-5, rtol=2e-5)


def test_paged_matches_dense_decode_attention():
    """A contiguous block table must reproduce the DENSE decode
    attention (the run_generate cache path) exactly — paging is an
    indirection, not a different attention."""
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas_decode import (decode_attention,
                                              paged_decode_attention)

    rs = np.random.RandomState(1)
    S, N, H, BS, MB = 2, 4, 32, 8, 4
    nh, L = N * H, 32
    k = jnp.asarray(rs.randn(S, L, nh), jnp.float32)
    v = jnp.asarray(rs.randn(S, L, nh), jnp.float32)
    q = jnp.asarray(rs.randn(S, 1, nh), jnp.float32)
    off = jnp.asarray(17, jnp.int32)
    dense = decode_attention(q, k, v, off, N)
    # lay the same values out as pages with identity-ish tables
    k_pages = jnp.concatenate(
        [jnp.zeros((1, BS, nh), jnp.float32),
         k.reshape(S * MB, BS, nh)], axis=0)
    v_pages = jnp.concatenate(
        [jnp.zeros((1, BS, nh), jnp.float32),
         v.reshape(S * MB, BS, nh)], axis=0)
    tables = jnp.asarray(
        [[1 + s * MB + i for i in range(MB)] for s in range(S)],
        jnp.int32)
    ctx = jnp.full((S,), 17, jnp.int32)
    for use_kernel in (False, True):
        paged = paged_decode_attention(q, k_pages, v_pages, tables, ctx,
                                       N, use_kernel=use_kernel)
        np.testing.assert_allclose(np.asarray(paged), np.asarray(dense),
                                   atol=2e-5, rtol=2e-5)


def test_paged_decode_supported_gate():
    from paddle_tpu.ops.pallas_decode import paged_decode_supported
    assert paged_decode_supported(16, 768, 12)
    assert not paged_decode_supported(10, 768, 12)    # block % 8
    assert not paged_decode_supported(16, 769, 12)    # hidden % 128
    assert not paged_decode_supported(16, 768, 200)   # heads > 128


def _tiled_case(N, H, dtype, seed, nan_dead=False):
    """Slots whose contexts sit on every edge of the kernel's tiling
    (0, one page less and more a row, one tile less and more a row,
    the table's end), over at least three tiles; two slots share the
    physical pages of a cached prefix, one slot is idle (ctx 0, table
    all null), every table's tail is the null block. With `nan_dead`
    the null block and every page no live table entry reaches are NaN
    and the idle slot is left out (position 0 of its null page is live
    by the contract)."""
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas_decode import paged_decode_tile_rows

    bs = 16
    nh = N * H
    itemsize = jnp.dtype(dtype).itemsize
    # the table is a tile short of three and a page over, so that the
    # last tile is partial and the policy still picks the cell's tile
    rows = paged_decode_tile_rows(bs, nh, N, itemsize, 1 << 20)
    P = rows // bs
    mb = 2 * P + 2
    assert paged_decode_tile_rows(bs, nh, N, itemsize, mb) == rows
    T = P * bs
    ctxs = [0, bs - 1, bs, T - 1, T, T + 1, mb * bs - 1, 2 * T + 3]
    rs = np.random.RandomState(seed)
    tables, nxt = [], 1
    for c in ctxs:
        n = c // bs + 1
        tables.append(list(range(nxt, nxt + n)) + [NULL_BLOCK] * (mb - n))
        nxt += n
    # the last slot shares its first P + 1 pages with the one before it
    tables[-1][:P + 1] = tables[-2][:P + 1]
    if not nan_dead:
        ctxs.append(0)
        tables.append([NULL_BLOCK] * mb)
    nb = nxt + 3                        # and three pages nobody maps
    k = rs.randn(nb, bs, nh).astype(np.float32)
    v = rs.randn(nb, bs, nh).astype(np.float32)
    # copies: on the CPU jnp.asarray may alias the numpy buffer
    clean = (jnp.asarray(k.copy(), dtype), jnp.asarray(v.copy(), dtype))
    if nan_dead:
        live = {b for row, c in zip(tables, ctxs)
                for b in row[:c // bs + 1]}
        dead = [b for b in range(nb) if b not in live]
        assert NULL_BLOCK in dead and len(dead) >= 4
        k[dead] = np.nan
        v[dead] = np.nan
    q = jnp.asarray(rs.randn(len(ctxs), 1, nh), dtype)
    return (q, jnp.asarray(k, dtype), jnp.asarray(v, dtype),
            jnp.asarray(tables, jnp.int32), jnp.asarray(ctxs, jnp.int32),
            N), clean, rows


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("N,H", [(12, 64), (16, 128)])
def test_paged_kernel_parity_at_cell_widths(N, H, dtype):
    """The tiled kernel against gather+dense at the two serving cells'
    head shapes, on every edge of the tiling (see _tiled_case). float32
    arenas hold the kernel to float32 dots; in bf16 both paths round
    the probabilities to bf16 and differ by the accumulation order."""
    from paddle_tpu.ops.pallas_decode import paged_decode_attention

    args, _, rows = _tiled_case(N, H, dtype, seed=N)
    assert rows >= 128 and args[4].max() >= 2 * rows
    fb = paged_decode_attention(*args, use_kernel=False)
    kn = paged_decode_attention(*args, use_kernel=True)
    assert kn.dtype == args[0].dtype and kn.shape == args[0].shape
    tol = 2e-5 if dtype == "float32" else 2e-2
    np.testing.assert_allclose(np.asarray(kn, np.float32),
                               np.asarray(fb, np.float32),
                               atol=tol, rtol=tol)


@pytest.mark.parametrize("N,H", [(12, 64), (16, 128)])
def test_paged_kernel_never_reads_a_dead_page(N, H):
    """Only the live context: with the null block and every page that
    no context reaches full of NaN the kernel's result is finite and is
    the fallback's over clean pages. (The fallback gathers whole
    tables, so over the poisoned arenas every slot whose table has a
    null tail comes out NaN: the poison is there to be read.)"""
    from paddle_tpu.ops.pallas_decode import paged_decode_attention

    args, (k_clean, v_clean), _ = _tiled_case(N, H, "float32", seed=7,
                                              nan_dead=True)
    q, k_nan, v_nan, tables, ctx, n = args
    kn = np.asarray(paged_decode_attention(*args, use_kernel=True))
    assert np.isfinite(kn).all()
    fb = paged_decode_attention(q, k_clean, v_clean, tables, ctx, n,
                                use_kernel=False)
    np.testing.assert_allclose(kn, np.asarray(fb), atol=2e-5, rtol=2e-5)
    poisoned = np.asarray(paged_decode_attention(*args, use_kernel=False))
    assert np.isnan(poisoned[:6]).all()


@pytest.mark.parametrize("bs,hidden,n_heads,itemsize,mb", [
    (16, 768, 12, 2, 64),        # gpt3-125m.serve-chat
    (16, 2048, 16, 2, 128),      # gpt3-1.3b.serve-long
])
def test_paged_tile_policy_at_the_cells(bs, hidden, n_heads, itemsize, mb):
    """The tile policy at the serving cells' shapes: 128 rows or more,
    whole pages and whole 128-lane logits columns, under VMEM_BUDGET by
    the registry's own footprint model."""
    from paddle_tpu.ops.kernel_registry import VMEM_BUDGET
    from paddle_tpu.ops.pallas_decode import (_paged_footprint,
                                              paged_decode_supported,
                                              paged_decode_tile_rows)

    rows = paged_decode_tile_rows(bs, hidden, n_heads, itemsize, mb)
    assert 128 <= rows <= 512
    assert rows % bs == 0 and rows % 128 == 0
    assert _paged_footprint(rows, hidden, n_heads, itemsize) <= VMEM_BUDGET
    assert paged_decode_supported(bs, hidden, n_heads, itemsize, mb)


@pytest.mark.parametrize("bs,hidden,n_heads,itemsize,mb,want", [
    (8, 128, 4, 4, 4, 128),       # the tests' toy engines: one unit
    (16, 256, 8, 4, 3, 128),      # kernel_lint's example
    (16, 768, 12, 2, 12, 256),    # no longer than the table, rounded up
    (16, 5120, 40, 2, 128, 128),  # 13B: VMEM leaves one unit
    (48, 768, 12, 4, 64, 384),    # a page that does not divide 128
    (16, 12288, 96, 2, 128, 0),   # 175B wide: no tile fits
    (8, 768, 12, 2, 64, 0),       # half a packed bf16 sublane tile
])
def test_paged_tile_policy_adapts(bs, hidden, n_heads, itemsize, mb, want):
    """One policy for every shape: it shrinks to what the table and
    VMEM allow, and `paged_decode_supported` is the same answer."""
    from paddle_tpu.ops.pallas_decode import (paged_decode_supported,
                                              paged_decode_tile_rows)

    ok = paged_decode_supported(bs, hidden, n_heads, itemsize, mb)
    assert ok == (want > 0)
    if ok:
        assert paged_decode_tile_rows(
            bs, hidden, n_heads, itemsize, mb) == want


def test_paged_kv_rows_counts_whole_live_pages():
    """`kv_rows` of `serving_dispatch`: every page up to the one `ctx`
    lies in, none past it, whatever the tile."""
    from paddle_tpu.ops.pallas_decode import paged_decode_kv_rows

    assert paged_decode_kv_rows(np.asarray([0, 15, 16, 511, 512]), 16) \
        == 16 + 16 + 32 + 512 + 528
    assert paged_decode_kv_rows(np.zeros((32,), np.int32), 16) == 512


# ---------------------------------------------------------------------------
# engine correctness
# ---------------------------------------------------------------------------

def test_engine_token_parity_with_run_generate():
    """The tentpole contract: concurrent greedy streams through the
    batched engine == single-request run_generate, token for token."""
    model = _small_gpt()
    rs = np.random.RandomState(0)
    prompts = [rs.randint(0, 512, (n,)).tolist() for n in (7, 13, 3)]
    refs = _refs(model, prompts, 10)
    eng = ServingEngine(model, max_slots=4, block_size=8,
                        prefill_chunk=8, max_model_len=64)
    handles = [eng.submit(p, SamplingParams(max_new_tokens=10))
               for p in prompts]
    eng.run_until_idle(max_steps=2000)
    for h, ref in zip(handles, refs):
        assert h.output_tokens == ref
    # blocks + slots fully reclaimed
    assert eng.pool.num_used == 0
    assert eng.sched.num_running() == 0
    assert eng.kv_peak_utilization > 0


def test_engine_eos_parity():
    model = _small_gpt()
    rs = np.random.RandomState(0)
    p = rs.randint(0, 512, (10,)).tolist()
    ref = _refs(model, [p], 16)[0]
    eos = ref[4]
    ref_eos = _refs(model, [p], 16, eos_token_id=eos, pad_token_id=0)[0]
    eng = ServingEngine(model, max_slots=2, block_size=8,
                        prefill_chunk=8, max_model_len=64)
    h = eng.submit(p, SamplingParams(max_new_tokens=16, eos_token_id=eos))
    eng.run_until_idle(max_steps=2000)
    got = h.output_tokens
    assert got[-1] == eos
    assert got + [0] * (16 - len(got)) == ref_eos


@pytest.mark.slow
def test_eviction_reclaim_is_invisible_in_streams():
    """Over-admitted schedule: preemption MUST fire (pool smaller than
    the offered load) and recompute MUST reproduce the identical
    stream."""
    from paddle_tpu import monitor
    model = _small_gpt()
    rs = np.random.RandomState(0)
    prompts = [rs.randint(0, 512, (10,)).tolist() for _ in range(4)]
    refs = _refs(model, prompts, 24)
    before = monitor.get("serving.preemptions", 0)
    eng = ServingEngine(model, max_slots=4, block_size=8,
                        prefill_chunk=8, max_model_len=64,
                        num_blocks=11)
    handles = [eng.submit(p, SamplingParams(max_new_tokens=24))
               for p in prompts]
    eng.run_until_idle(max_steps=20000)
    assert monitor.get("serving.preemptions", 0) - before > 0
    for h, ref in zip(handles, refs):
        assert h.output_tokens == ref
    assert eng.pool.num_used == 0               # eviction reclaim clean


@pytest.mark.slow
def test_all_prefill_pool_exhaustion_cannot_deadlock():
    """Four admitted prompts whose prefills together exceed the pool:
    with nothing decoding, the oldest prefill must evict its way
    forward instead of every prefill waiting on everyone else."""
    model = _small_gpt()
    rs = np.random.RandomState(3)
    # 4 x 33-token prompts (5 blocks each at bs=8) vs an 11-block pool
    prompts = [rs.randint(0, 512, (33,)).tolist() for _ in range(4)]
    refs = _refs(model, prompts, 6)
    eng = ServingEngine(model, max_slots=4, block_size=8,
                        prefill_chunk=8, max_model_len=48,
                        num_blocks=11)
    handles = [eng.submit(p, SamplingParams(max_new_tokens=6))
               for p in prompts]
    steps = eng.run_until_idle(max_steps=20000)
    assert steps < 20000, "engine failed to drain (deadlock)"
    for h, ref in zip(handles, refs):
        assert h.output_tokens == ref
    assert eng.pool.num_used == 0


@pytest.mark.slow
def test_sampling_stream_independent_of_batch_composition():
    """Per-request fold_in keys: a seeded sampled stream must not
    change when other requests share the decode batch."""
    model = _small_gpt()
    rs = np.random.RandomState(0)
    prompts = [rs.randint(0, 512, (n,)).tolist() for n in (10, 6, 14)]
    eng = ServingEngine(model, max_slots=4, block_size=8,
                        prefill_chunk=8, max_model_len=64)
    sp = dict(max_new_tokens=8, decode_strategy="sampling", top_k=20,
              top_p=0.9, temperature=0.8, seed=42)
    h = eng.submit(prompts[1], SamplingParams(**sp))
    eng.run_until_idle(max_steps=2000)
    alone = h.output_tokens
    assert len(alone) == 8
    eng.submit(prompts[0], SamplingParams(max_new_tokens=6))
    eng.submit(prompts[2], SamplingParams(max_new_tokens=6))
    h2 = eng.submit(prompts[1], SamplingParams(**sp))
    eng.run_until_idle(max_steps=2000)
    assert h2.output_tokens == alone


@pytest.mark.slow
def test_wo8_engine_matches_quantized_run_generate():
    """weights='wo8' engine == quantize_for_decode + run_generate."""
    from paddle_tpu.quant import quantize_for_decode
    model_ref = _small_gpt()
    rs = np.random.RandomState(0)
    p = rs.randint(0, 512, (9,)).tolist()
    quantize_for_decode(model_ref)
    ref = _refs(model_ref, [p], 8)[0]
    model = _small_gpt()
    eng = ServingEngine(model, max_slots=2, block_size=8,
                        prefill_chunk=8, max_model_len=64,
                        weights="wo8")
    h = eng.submit(p, SamplingParams(max_new_tokens=8))
    eng.run_until_idle(max_steps=2000)
    assert h.output_tokens == ref


def test_submit_rejects_oversized_requests():
    model = _small_gpt()
    eng = ServingEngine(model, max_slots=2, block_size=8,
                        prefill_chunk=8, max_model_len=32)
    with pytest.raises(ValueError):
        eng.submit(list(range(20)), SamplingParams(max_new_tokens=20))
    with pytest.raises(ValueError):
        SamplingParams(decode_strategy="beam_search")


# ---------------------------------------------------------------------------
# scheduler unit behavior
# ---------------------------------------------------------------------------

def test_request_token_at_is_tokens_all_indexed():
    """What a decode step asks of every slot, without building the
    prompt + answer list each time."""
    from paddle_tpu.serving.scheduler import Request
    req = Request([5, 6, 7], SamplingParams(max_new_tokens=4),
                  np.zeros((2,), np.uint32))
    for tok in (8, 9):
        req.push_token(tok)
    assert [req.token_at(i) for i in range(5)] == req.tokens_all \
        == [5, 6, 7, 8, 9]
    with pytest.raises(IndexError):
        req.token_at(5)


def test_scheduler_preempts_youngest_and_requeues_front():
    from paddle_tpu.serving.scheduler import Request, Scheduler
    pool = BlockPool(7)                          # capacity 6
    sched = Scheduler(pool, block_size=8, max_slots=3, max_model_len=48)
    key = np.zeros((2,), np.uint32)
    reqs = [Request([1] * 8, SamplingParams(max_new_tokens=8), key)
            for _ in range(3)]
    for r in reqs:
        sched.submit(r)
    sched.admit()
    assert len(sched.prefilling) == 3
    # give each 2 blocks: pool exhausted
    for r in reqs:
        assert sched.ensure_blocks(r, 16, evict=False)
    assert pool.num_free == 0
    # oldest needs growth -> youngest must be evicted, requeued FRONT
    assert sched.ensure_blocks(reqs[0], 17, evict=True)
    assert reqs[2].state == "waiting"
    assert sched.waiting[0] is reqs[2]
    assert reqs[2].blocks == [] and reqs[2].n_prefilled == 0
    # prefill growth never evicts
    got = sched.ensure_blocks(reqs[1], 48, evict=False)
    assert got is False
    assert all(r.state != "waiting" for r in (reqs[0], reqs[1]))


def test_scheduler_admission_bounded_by_slots():
    from paddle_tpu.serving.scheduler import Request, Scheduler
    pool = BlockPool(64)
    sched = Scheduler(pool, block_size=8, max_slots=2, max_model_len=64)
    key = np.zeros((2,), np.uint32)
    for _ in range(5):
        sched.submit(Request([1, 2], SamplingParams(max_new_tokens=4),
                             key))
    sched.admit()
    assert len(sched.prefilling) == 2
    assert len(sched.waiting) == 3


# ---------------------------------------------------------------------------
# Config routing + quant helper
# ---------------------------------------------------------------------------

def test_engine_config_routes_inference_config():
    import warnings
    from paddle_tpu import inference
    cfg = inference.Config("x")
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        cfg.disable_gpu()
        cfg.enable_tensorrt_engine(
            precision_mode=inference.PrecisionType.Int8)
        cfg.enable_use_gpu(memory_pool_init_size_mb=64)
    # enable_use_gpu flipped the device back to accelerator + budget
    ec = EngineConfig.from_inference_config(cfg)
    assert ec.weights == "wo8" and ec.dtype == "bfloat16"
    assert ec.kv_memory_mb == 64
    assert ec.device is None                    # accelerator default
    import warnings as _w
    with _w.catch_warnings():
        _w.simplefilter("ignore")
        cfg.disable_gpu()
    ec = EngineConfig.from_inference_config(cfg)
    assert ec.device is not None and ec.device.platform == "cpu"
    # Float32 precision -> decode in the params' own dtype
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        cfg.enable_tensorrt_engine(
            precision_mode=inference.PrecisionType.Float32)
    assert EngineConfig.from_inference_config(cfg).dtype is None


def test_kv_memory_budget_sizes_pool():
    model = _small_gpt()
    # 2 layers * 2 arenas * 8 * 128 * 2B = 8 KiB per block (bf16)
    eng = ServingEngine(model, max_slots=2, block_size=8,
                        prefill_chunk=8, max_model_len=32,
                        kv_memory_mb=1)
    assert eng.pool.num_blocks == (1 * 2 ** 20) // (2 * 2 * 8 * 128 * 2)


def test_quantize_for_decode_idempotent_and_loud():
    from paddle_tpu import nn
    from paddle_tpu.quant import (WeightOnlyInt8Linear,
                                  quantize_for_decode)
    model = _small_gpt()
    n = quantize_for_decode(model)
    assert n == 8                               # 4 linears x 2 layers
    assert quantize_for_decode(model) == 0      # idempotent, not double
    with pytest.raises(ValueError):
        quantize_for_decode(nn.LayerNorm(8))    # nothing quantizable
    q = [m for m in model.sublayers()
         if isinstance(m, WeightOnlyInt8Linear)]
    assert len(q) == 8


@pytest.mark.slow
def test_step_error_fails_streams_and_loop_survives():
    """A PERMANENT step failure (a programming error — recompute-replay
    would hit the identical bug) must not strand open streams or kill
    the serve thread: in-flight requests FAIL with the error, the
    arenas rebuild, and the engine keeps serving. (Transient faults
    take the warm-restart path instead — test_serving_resilience.)"""
    from paddle_tpu import monitor
    model = _small_gpt()
    rs = np.random.RandomState(0)
    p = rs.randint(0, 512, (8,)).tolist()
    ref = _refs(model, [p], 5)[0]
    eng = ServingEngine(model, max_slots=2, block_size=8,
                        prefill_chunk=8, max_model_len=32)
    orig = eng._decode_greedy_jit
    before = monitor.get("serving.engine_errors", 0)

    def boom(*a, **k):
        raise ValueError("injected device failure")

    with eng:
        eng._decode_greedy_jit = boom
        h = eng.submit(p, SamplingParams(max_new_tokens=5))
        with pytest.raises(RuntimeError, match="injected"):
            list(h.tokens(timeout=60))
        assert h.finished
        assert monitor.get("serving.engine_errors", 0) > before
        assert eng.pool.num_used == 0           # state rebuilt clean
        eng._decode_greedy_jit = orig           # "device" recovers
        h2 = eng.submit(p, SamplingParams(max_new_tokens=5))
        assert h2.result(timeout=120) == ref


@pytest.mark.slow
def test_http_front_streams_and_scrapes():
    import urllib.request
    from paddle_tpu.serving import ServingHTTPServer
    model = _small_gpt()
    rs = np.random.RandomState(0)
    p = rs.randint(0, 512, (8,)).tolist()
    ref = _refs(model, [p], 6)[0]
    eng = ServingEngine(model, max_slots=2, block_size=8,
                        prefill_chunk=8, max_model_len=32)
    with eng, ServingHTTPServer(eng, port=0) as srv:
        body = json.dumps({"prompt": p, "max_new_tokens": 6,
                           "stream": True}).encode()
        r = urllib.request.urlopen(urllib.request.Request(
            srv.url + "/generate", data=body,
            headers={"Content-Type": "application/json"}), timeout=120)
        lines = [json.loads(ln) for ln in
                 r.read().decode().strip().splitlines()]
        assert [ln["token"] for ln in lines[:-1]] == ref
        assert lines[-1]["done"] and lines[-1]["tokens"] == ref
        m = urllib.request.urlopen(srv.url + "/metrics",
                                   timeout=30).read().decode()
        assert "paddle_tpu_serving_kv_block_utilization" in m
        # bad request -> 400, oversized -> 429
        import urllib.error
        with pytest.raises(urllib.error.HTTPError) as e:
            urllib.request.urlopen(urllib.request.Request(
                srv.url + "/generate", data=b"{}",
                headers={"Content-Type": "application/json"}),
                timeout=30)
        assert e.value.code == 400
