"""Prefix-sharing KV cache: refcounted copy-on-write block reuse
across requests + flash chunked prefill.

Covers the refcounted BlockPool (holder sets, cached parking,
write-safety predicate, leak reports naming every holder), the
block-granular PrefixIndex (full + partial matching capped below the
prompt length, LRU eviction over refcount-0 leaves, pinning, stale
binding tripwire), the engine integration (CoW fork on mid-block
divergence with streams bit-identical to cold-cache runs, preemption
and warm-restart recompute-replay over prefix hits, index flush on
arena rebuild and drain), the `flash_prefill_chunk` kernel's
registration and fallback parity, the enable_prefix_cache knob
routing, and telemetry fields + trace_check cross-rules.
"""
import os
import sys

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import monitor, telemetry
from paddle_tpu.models.gpt import GPTConfig, GPTForPretraining
from paddle_tpu.resilience.retry import tag_transient
from paddle_tpu.serving import (BlockLeakError, BlockPool, EngineConfig,
                                PrefixIndex, SamplingParams,
                                ServingEngine, StaleIndexError)

TOOLS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tools")


def _small_gpt(seed=0):
    paddle.seed(seed)
    cfg = GPTConfig(vocab_size=512, hidden_size=128, num_layers=2,
                    num_heads=4, max_seq_len=128, dropout=0.0,
                    use_flash_attention=False)
    return GPTForPretraining(cfg)


def _refs(model, prompts, max_new):
    out = []
    for p in prompts:
        ids = paddle.to_tensor(np.asarray([p], np.int32))
        full, _ = model.generate(ids, max_new_tokens=max_new)
        out.append(np.asarray(full.numpy())[0, len(p):].tolist())
    return out


# ---------------------------------------------------------------------------
# BlockPool refcounts / copy-on-write bookkeeping
# ---------------------------------------------------------------------------

class TestRefcountedPool:
    def test_alloc_incref_free_lifecycle(self):
        pool = BlockPool(9)
        a = pool.alloc(2, owner="a")
        assert pool.refcount(a[0]) == 1
        pool.incref(a, owner="b")
        assert pool.refcount(a[0]) == 2
        assert pool.num_shared == 2
        assert pool.holders_of(a[0]) == ("a", "b")
        pool.free(a, owner="a")               # drops a's reference only
        assert pool.refcount(a[0]) == 1
        assert pool.num_free == 6             # still held by b
        pool.free(a, owner="b")
        assert pool.num_free == 8
        pool.assert_quiesced()

    def test_free_of_shared_block_requires_owner(self):
        pool = BlockPool(4)
        blocks = pool.alloc(1, owner="a")
        pool.incref(blocks, owner="b")
        with pytest.raises(ValueError, match="explicit owner"):
            pool.free(blocks)
        with pytest.raises(ValueError, match="not a holder"):
            pool.free(blocks, owner="c")
        pool.free(blocks, owner="a")
        pool.free(blocks, owner="b")

    def test_incref_rejects_free_and_double_hold(self):
        pool = BlockPool(4)
        blocks = pool.alloc(1, owner="a")
        with pytest.raises(ValueError, match="already holds"):
            pool.incref(blocks, owner="a")
        pool.free(blocks, owner="a")
        with pytest.raises(ValueError, match="free/unallocated"):
            pool.incref(blocks, owner="b")

    def test_cached_block_parks_at_refcount_zero(self):
        pool = BlockPool(4)
        blocks = pool.alloc(1, owner="a")
        pool.mark_cached(blocks[0])
        pool.free(blocks, owner="a")
        # cached: off the free list, not a leak, not "used"
        assert pool.num_free == 2
        assert pool.num_used == 0
        assert pool.num_cached == 1
        pool.assert_quiesced()
        # a later request can reference the cached content again
        pool.incref(blocks, owner="b")
        assert pool.num_cached == 0 and pool.num_used == 1
        pool.free(blocks, owner="b")
        pool.release_cached(blocks[0])
        assert pool.num_free == 3

    def test_is_private_write_safety_predicate(self):
        pool = BlockPool(6)
        blocks = pool.alloc(1, owner="a")
        assert pool.is_private(blocks[0], "a")
        pool.incref(blocks, owner="b")
        assert not pool.is_private(blocks[0], "a")     # shared
        pool.free(blocks, owner="b")
        pool.mark_cached(blocks[0])
        assert not pool.is_private(blocks[0], "a")     # index can read it
        pool.free(blocks, owner="a")
        pool.release_cached(blocks[0])

    def test_owner_of_reports_holder_set(self):
        pool = BlockPool(6)
        blocks = pool.alloc(1, owner="a")
        assert pool.owner_of(blocks[0]) == "a"         # sole-owner compat
        pool.incref(blocks, owner="b")
        assert pool.owner_of(blocks[0]) == ("a", "b")  # the holder set
        pool.free(blocks, owner="a")
        pool.free(blocks, owner="b")
        assert pool.owner_of(blocks[0]) is None

    def test_assert_quiesced_names_every_holder_of_shared_block(self):
        pool = BlockPool(6)
        blocks = pool.alloc(1, owner="r1")
        pool.incref(blocks, owner="r2")
        with pytest.raises(BlockLeakError) as e:
            pool.assert_quiesced()
        msg = str(e.value)
        assert "r1" in msg and "r2" in msg and "refs>1" in msg
        pool.free(blocks, owner="r1")
        pool.free(blocks, owner="r2")
        pool.assert_quiesced()


# ---------------------------------------------------------------------------
# PrefixIndex: radix matching, LRU eviction, pinning, stale binding
# ---------------------------------------------------------------------------

class TestPrefixIndex:
    def _pool_index(self, num_blocks=17, bs=4):
        pool = BlockPool(num_blocks)
        return pool, PrefixIndex(bs, pool=pool)

    def test_match_full_partial_and_cap(self):
        pool, idx = self._pool_index()
        tokens = list(range(100, 108))                 # 8 tokens, bs=4
        blocks = pool.alloc(2, owner="a")              # 2 full chunks
        idx.insert(tokens, blocks, pool)
        # identical tokens: capped at len-1 = 7 -> 1 full + partial 3
        # (the fully-cached-prompt case that forces a CoW fork)
        got, n = idx.match(tokens, pool)
        assert got == blocks and n == 7
        # longer prompt with same prefix: both chunks match fully
        got, n = idx.match(tokens + [1, 2, 3], pool)
        assert got == blocks and n == 8
        # diverging inside the second chunk: partial on chunk 2
        div = tokens[:6] + [9, 9, 9, 9]
        got, n = idx.match(div, pool)
        assert got == blocks and n == 6
        # diverging inside the FIRST chunk: partial on chunk 1
        got, n = idx.match([100, 101, 0, 0, 0, 0], pool)
        assert got == blocks[:1] and n == 2
        # no overlap at all
        got, n = idx.match([7, 7, 7, 7, 7], pool)
        assert got == [] and n == 0

    def test_lru_eviction_over_refcount0_leaves(self):
        pool, idx = self._pool_index()
        a = pool.alloc(1, owner="a")
        b = pool.alloc(1, owner="b")
        idx.insert([1, 2, 3, 4], a, pool)
        idx.insert([5, 6, 7, 8], b, pool)
        pool.free(a, owner="a")
        pool.free(b, owner="b")
        # touch a AFTER b so b is the LRU leaf
        idx.match([1, 2, 3, 4, 0], pool)
        freed = idx.evict(1, pool)
        assert freed == 1
        got, n = idx.match([5, 6, 7, 8, 0], pool)      # b evicted
        assert n == 0
        got, n = idx.match([1, 2, 3, 4, 0], pool)      # a survives
        assert n == 4

    def test_shared_leaf_pinned_under_mid_decode_reader(self):
        """Evicting a leaf some request still references must be
        impossible: the refcount pins it."""
        pool, idx = self._pool_index()
        a = pool.alloc(1, owner="writer")
        idx.insert([1, 2, 3, 4], a, pool)
        pool.free(a, owner="writer")
        blocks, n = idx.match([1, 2, 3, 4, 9], pool)
        pool.incref(blocks, owner="reader")            # mid-decode reader
        assert idx.evict(5, pool) == 0                 # pinned: nothing freed
        got, n = idx.match([1, 2, 3, 4, 9], pool)
        assert n == 4                                  # still cached
        pool.free(blocks, owner="reader")
        assert idx.evict(5, pool) == 1                 # unpinned -> evictable

    def test_interior_nodes_never_evicted_before_leaves(self):
        pool, idx = self._pool_index()
        chain = pool.alloc(3, owner="a")
        idx.insert(list(range(12)), chain, pool)
        pool.free(chain, owner="a")
        assert idx.evict(1, pool) == 1                 # the deepest leaf
        got, n = idx.match(list(range(12)) + [99], pool)
        assert n == 8 and got == chain[:2]             # prefix chain intact

    def test_stale_binding_raises(self):
        pool, idx = self._pool_index()
        blocks = pool.alloc(1, owner="a")
        idx.insert([1, 2, 3, 4], blocks, pool)
        other = BlockPool(17)
        with pytest.raises(StaleIndexError):
            idx.match([1, 2, 3, 4, 5], other)
        with pytest.raises(StaleIndexError):
            idx.evict(1, other)
        pool.free(blocks, owner="a")

    def test_flush_releases_retained_blocks(self):
        pool, idx = self._pool_index()
        blocks = pool.alloc(2, owner="a")
        idx.insert(list(range(8)), blocks, pool)
        pool.free(blocks, owner="a")
        free_before = pool.num_free
        idx.flush()
        assert idx.num_blocks == 0
        assert pool.num_free == free_before + 2
        assert pool.num_cached == 0


# ---------------------------------------------------------------------------
# engine integration: CoW, replay, flush, knob
# ---------------------------------------------------------------------------

def _engine(model, **kw):
    base = dict(max_slots=4, block_size=8, prefill_chunk=8,
                max_model_len=64)
    base.update(kw)
    return ServingEngine(model, **base)


def test_cow_fork_mid_block_divergence_streams_identical():
    """Requests diverging mid-block share the common full blocks, the
    duplicate-prompt case partially shares (and forks) the tail block,
    and every stream is token-identical to both run_generate and a
    cold-cache engine."""
    model = _small_gpt()
    rs = np.random.RandomState(0)
    tpl = rs.randint(0, 512, (20,)).tolist()           # 2.5 blocks of 8
    prompts = [tpl + rs.randint(0, 512, (4,)).tolist() for _ in range(3)]
    prompts.append(list(prompts[0]))                   # exact duplicate
    refs = _refs(model, prompts, 8)

    # max_slots=2: admissions serialize, so later requests arrive at a
    # WARMED index (simultaneous admissions into an empty index are
    # legitimately all misses)
    cold = _engine(model, enable_prefix_cache=False, max_slots=2)
    hc = [cold.submit(p, SamplingParams(max_new_tokens=8))
          for p in prompts]
    cold.run_until_idle()

    forks_before = monitor.get("serving.prefix_cow_forks", 0)
    warm = _engine(model, max_slots=2)
    hw = [warm.submit(p, SamplingParams(max_new_tokens=8))
          for p in prompts]
    warm.run_until_idle()

    for i in range(len(prompts)):
        assert hc[i].output_tokens == refs[i]
        assert hw[i].output_tokens == refs[i]
    ps = warm.prefix_stats()
    assert ps["tokens_saved"] > 0 and 0 < ps["hit_rate"] <= 1
    # the duplicate prompt resumed INSIDE a shared block -> CoW fork
    assert monitor.get("serving.prefix_cow_forks", 0) > forks_before
    assert warm.pool.num_shared == 0                   # all terminal


def test_prefix_cache_off_bit_matches_run_generate():
    model = _small_gpt()
    rs = np.random.RandomState(1)
    prompts = [rs.randint(0, 512, (n,)).tolist() for n in (10, 10, 14)]
    refs = _refs(model, prompts, 8)
    eng = _engine(model, enable_prefix_cache=False)
    assert eng.prefix_index is None
    handles = [eng.submit(p, SamplingParams(max_new_tokens=8))
               for p in prompts]
    eng.run_until_idle()
    for h, ref in zip(handles, refs):
        assert h.output_tokens == ref
    ps = eng.prefix_stats()
    assert ps["tokens_offered"] == 0 and ps["tokens_saved"] == 0


def test_preemption_recompute_replay_over_prefix_hit():
    """An over-committed pool must preempt — and the evicted requests'
    replays ride their cached prefix blocks while still streaming
    token-identically to run_generate."""
    model = _small_gpt()
    rs = np.random.RandomState(2)
    tpl = rs.randint(0, 512, (16,)).tolist()
    prompts = [tpl + rs.randint(0, 512, (2 + i,)).tolist()
               for i in range(4)]
    refs = _refs(model, prompts, 16)
    before = monitor.get("serving.preemptions", 0)
    eng = _engine(model, num_blocks=13)    # far below the offered load
    handles = [eng.submit(p, SamplingParams(max_new_tokens=16))
               for p in prompts]
    eng.run_until_idle(max_steps=20000)
    assert monitor.get("serving.preemptions", 0) > before
    for h, ref in zip(handles, refs):
        assert h.output_tokens == ref
    assert eng.prefix_stats()["hits"] > 0


def test_warm_restart_replay_over_prefix_hit():
    """A transient step fault warm-restarts the engine: the index is
    flushed with the arenas, in-flight requests replay (re-matching
    whatever the survivors re-cache), and streams stay identical."""
    model = _small_gpt()
    rs = np.random.RandomState(3)
    tpl = rs.randint(0, 512, (16,)).tolist()
    prompts = [tpl + rs.randint(0, 512, (3,)).tolist() for _ in range(3)]
    refs = _refs(model, prompts, 8)
    eng = _engine(model, max_slots=2, restart_backoff_s=0.01)
    calls = {"n": 0}
    orig = eng._decode_greedy_jit

    def flaky(*a, **k):
        calls["n"] += 1
        if calls["n"] == 3:
            raise tag_transient(OSError(5, "injected transient fault"))
        return orig(*a, **k)

    eng._decode_greedy_jit = flaky
    with eng:
        handles = [eng.submit(p, SamplingParams(max_new_tokens=8))
                   for p in prompts]
        for h, ref in zip(handles, refs):
            assert h.result(timeout=180) == ref
    assert calls["n"] >= 3
    assert eng.prefix_index._pool is eng.pool          # rebound post-restart


def test_failover_readmission_rides_prefix_cache_token_identical():
    """The fleet router's failover replay lands as submit(replay_tokens
    =...) on a WARM replica: the replayed prompt re-matches the blocks
    the first admission cached there, and the spliced stream (replayed
    prefix + resumed decode) is token-identical to an uninterrupted
    run — the recompute-replay invariant, cross-engine."""
    model = _small_gpt()
    rs = np.random.RandomState(5)
    prompt = rs.randint(0, 512, (18,)).tolist()
    [ref] = _refs(model, [prompt], 12)
    eng = _engine(model, max_slots=2)
    # first admission: the 'replica that survives' serves this prompt
    # once, populating its radix index with the prompt's full blocks
    h0 = eng.submit(prompt, SamplingParams(max_new_tokens=12),
                    request_id="fo-orig")
    eng.run_until_idle()
    assert h0.output_tokens == ref
    hits_before = eng.prefix_stats()["hits"]
    # ... now a request that streamed 5 tokens on another replica
    # before it died fails over HERE, replaying what already reached
    # the client's wire
    replayed = ref[:5]
    h1 = eng.submit(prompt, SamplingParams(max_new_tokens=12),
                    request_id="fo-replay", replay_tokens=replayed)
    eng.run_until_idle()
    # only the NEW tokens stream (the replayed ones are already on the
    # client's wire); output_tokens carries the full spliced stream
    assert list(h1.tokens(timeout=5)) == ref[5:]
    assert h1.output_tokens == ref                  # the splice
    # the engine's own ledger counts ALL tokens, replayed included —
    # the quantity the router's splice proof checks
    assert h1.stats["n_tokens"] == len(ref)
    # the replay re-matched the first admission's cached blocks
    assert eng.prefix_stats()["hits"] > hits_before


def test_replay_tokens_validation():
    """submit() rejects replays that leave nothing to stream or that
    already terminated — a malformed failover must fail loudly at the
    door, not wedge a slot."""
    model = _small_gpt()
    eng = _engine(model)
    prompt = list(range(2, 14))
    with pytest.raises(ValueError, match="nothing left to stream"):
        eng.submit(prompt, SamplingParams(max_new_tokens=4),
                   replay_tokens=[1, 2, 3, 4])
    with pytest.raises(ValueError, match="eos_token_id"):
        eng.submit(prompt,
                   SamplingParams(max_new_tokens=8, eos_token_id=3),
                   replay_tokens=[1, 2, 3])


def test_stale_index_on_serve_loop_keeps_request_and_self_heals():
    """A stale index binding raises BEFORE the admission pop, so the
    request stays queued — and the background loop's warm restart
    (StaleIndexError classifies as infra) rebuilds + rebinds the
    index, after which the queued request serves normally instead of
    vanishing with its client blocked forever."""
    from paddle_tpu.serving import BlockPool
    model = _small_gpt()
    rs = np.random.RandomState(8)
    p = rs.randint(0, 512, (12,)).tolist()
    refs = _refs(model, [p, p + [1]], 4)
    eng = _engine(model, max_slots=2, restart_backoff_s=0.01)
    h0 = eng.submit(p, SamplingParams(max_new_tokens=4))
    eng.run_until_idle()
    assert h0.output_tokens == refs[0]
    # simulate the buggy rebuild: pool swapped, index left stale
    eng.pool = BlockPool(eng.pool.num_blocks)
    eng.sched.pool = eng.pool
    with eng:
        h1 = eng.submit(p + [1], SamplingParams(max_new_tokens=4))
        assert h1.result(timeout=180) == refs[1]
    assert monitor.get("serving.restarts", 0) >= 1
    assert eng.prefix_index._pool is eng.pool


def test_rebuild_arenas_flushes_and_rebinds_index():
    model = _small_gpt()
    rs = np.random.RandomState(4)
    p = rs.randint(0, 512, (16,)).tolist()
    eng = _engine(model)
    eng.submit(p, SamplingParams(max_new_tokens=2))
    eng.run_until_idle()
    assert eng.prefix_index.num_blocks > 0
    eng._rebuild_arenas()
    assert eng.prefix_index.num_blocks == 0
    assert eng.prefix_index._pool is eng.pool
    # and the rebuilt engine serves the same prompt cleanly (cold)
    h = eng.submit(p, SamplingParams(max_new_tokens=2))
    eng.run_until_idle()
    assert len(h.output_tokens) == 2


def test_drain_flushes_index_and_quiesce_reports_prefix_fields(tmp_path):
    model = _small_gpt()
    rs = np.random.RandomState(5)
    tpl = rs.randint(0, 512, (16,)).tolist()
    sink = telemetry.JsonlSink(str(tmp_path / "serving.jsonl"))
    eng = ServingEngine(model, sink=sink, max_slots=2, block_size=8,
                        prefill_chunk=8, max_model_len=64)
    for i in range(3):
        eng.submit(tpl + [i], SamplingParams(max_new_tokens=2))
    eng.run_until_idle()
    assert eng.drain()
    assert eng.prefix_index.num_blocks == 0
    assert eng.pool.num_cached == 0
    sink.close()
    from paddle_tpu.telemetry.sink import read_jsonl
    quiesce = [r for r in read_jsonl(str(tmp_path / "serving.jsonl"))
               if r.get("kind") == "serving"
               and r.get("event") == "quiesce"]
    assert quiesce
    q = quiesce[-1]
    assert q["prefix_blocks_shared"] == 0
    assert 0.0 <= q["prefix_hit_rate"] <= 1.0
    assert q["prefill_tokens_saved"] <= q["prefill_tokens_offered"]
    # the whole ledger passes the validator + cross-rules
    sys.path.insert(0, TOOLS)
    import trace_check
    problems, _ = trace_check.check_pair(str(tmp_path / "serving.jsonl"))
    assert problems == []


def test_prefix_gauges_live():
    model = _small_gpt()
    rs = np.random.RandomState(6)
    tpl = rs.randint(0, 512, (16,)).tolist()
    eng = _engine(model, max_slots=2)
    for i in range(3):
        eng.submit(tpl + [i], SamplingParams(max_new_tokens=2))
    eng.run_until_idle()
    assert monitor.get_gauge("serving.prefix_hit_rate", -1) >= 0
    assert monitor.get_gauge("serving.prefill_tokens_saved", -1) > 0
    assert monitor.get_gauge("serving.prefill_tokens_offered", -1) > 0
    assert monitor.get_gauge("serving.prefix_blocks_shared", -1) >= 0


def test_engine_config_knob_routing():
    from paddle_tpu import inference
    cfg = inference.Config("unused")
    import warnings as _w
    with _w.catch_warnings(record=True) as rec:
        _w.simplefilter("always")
        cfg.enable_prefix_cache(False)
    assert any("enable_prefix_cache" in str(r.message) for r in rec)
    ecfg = EngineConfig.from_inference_config(cfg)
    assert ecfg.enable_prefix_cache is False
    cfg.enable_prefix_cache(True)
    assert EngineConfig.from_inference_config(cfg).enable_prefix_cache


# ---------------------------------------------------------------------------
# flash_prefill_chunk kernel
# ---------------------------------------------------------------------------

class TestFlashPrefillKernel:
    def test_fallback_parity(self):
        from paddle_tpu.ops.pallas_decode import (_prefill_example,
                                                  flash_prefill_chunk)
        for seed in (0, 7):
            rng = np.random.default_rng(seed)
            args, kw = _prefill_example(rng)
            got = np.asarray(flash_prefill_chunk(*args, **kw),
                             dtype=np.float64)
            want = np.asarray(
                flash_prefill_chunk(*args, use_kernel=False),
                dtype=np.float64)
            assert got.shape == want.shape
            np.testing.assert_allclose(got, want, rtol=1e-3, atol=1e-3)

    def test_resume_offset_mid_block(self):
        """A prefix hit resumes prefill at a NON-block-aligned offset:
        the kernel and the fallback must agree there too."""
        from paddle_tpu.ops.pallas_decode import flash_prefill_chunk
        rng = np.random.default_rng(11)
        N, H, bs, C, mb = 4, 32, 16, 16, 3
        nh = N * H
        q = 0.1 * rng.standard_normal((1, C, nh)).astype(np.float32)
        kp = 0.1 * rng.standard_normal((mb + 2, bs, nh)).astype(np.float32)
        vp = 0.1 * rng.standard_normal((mb + 2, bs, nh)).astype(np.float32)
        table = np.arange(1, mb + 1, dtype=np.int32)
        for p0 in (0, 5, 13, 31):              # incl. mid-block resumes
            got = flash_prefill_chunk(q, kp, vp, table, np.int32(p0), N,
                                      use_kernel=True)
            want = flash_prefill_chunk(q, kp, vp, table, np.int32(p0), N,
                                       use_kernel=False)
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       rtol=1e-3, atol=1e-3)

    def test_supported_gate(self):
        from paddle_tpu.ops.pallas_decode import flash_prefill_supported
        assert flash_prefill_supported(16, 128, 768, 12, max_blocks=64)
        assert flash_prefill_supported(16, 128, 2048, 16, max_blocks=128)
        assert not flash_prefill_supported(6, 128, 768, 12,
                                           max_blocks=64)   # bs % 8
        assert not flash_prefill_supported(16, 12, 768, 12,
                                           max_blocks=64)   # chunk % 8
        assert not flash_prefill_supported(16, 128, 768, 7,
                                           max_blocks=64)   # nh % N
        # float32 pages are whole tiles from 8 rows on, bf16 from 16
        assert flash_prefill_supported(8, 8, 128, 4, 4, max_blocks=4)
        assert not flash_prefill_supported(8, 16, 128, 4, 2, max_blocks=4)
        # heads that do not tile the 128-lane columns: 96 lanes a head
        assert not flash_prefill_supported(16, 128, 1152, 12,
                                           max_blocks=64)

    @pytest.mark.parametrize("hidden,n_heads,mb,tiling", [
        (768, 12, 64, (768, 512)),        # gpt3-125m.serve-chat
        (2048, 16, 128, (2048, 256)),     # gpt3-1.3b.serve-long
        (5120, 40, 128, (1280, 512)),     # 13B: its columns in groups
        (2048, 16, 8, (2048, 128)),       # a table shorter than a tile
    ])
    def test_tile_policy_fits_vmem(self, hidden, n_heads, mb, tiling):
        """The gate is the tile policy's answer, and what the policy
        chooses lies within VMEM_BUDGET by the kernel's own footprint;
        a wide model gets a narrower group of head columns a step, not
        the fallback."""
        from paddle_tpu.ops.kernel_registry import VMEM_BUDGET
        from paddle_tpu.ops.pallas_decode import (_head_columns,
                                                  _prefill_footprint,
                                                  flash_prefill_supported,
                                                  flash_prefill_tiling)
        width, rows = flash_prefill_tiling(16, 128, hidden, n_heads, 2, mb)
        assert (width, rows) == tiling
        lanes, heads = _head_columns(hidden, n_heads)
        assert hidden % width == 0 and width % lanes == 0
        assert rows % 128 == 0 and rows % 16 == 0
        assert _prefill_footprint(rows, 128, width, lanes, heads, 2) \
            <= VMEM_BUDGET
        assert flash_prefill_supported(16, 128, hidden, n_heads, 2, mb)

    def test_gate_says_no_where_no_tile_fits(self):
        """A chunk so long that one column's statistics alone pass the
        budget: the gate answers False and the caller takes the
        fallback."""
        from paddle_tpu.ops.pallas_decode import (flash_prefill_supported,
                                                  flash_prefill_tiling)
        assert flash_prefill_tiling(16, 8192, 5120, 40, 2, 128) == (0, 0)
        assert not flash_prefill_supported(16, 8192, 5120, 40, 2, 128)

    def test_kv_rows_hand_count(self):
        from paddle_tpu.ops.pallas_decode import flash_prefill_kv_rows
        # whole pages up to the one the last real position lies in
        assert flash_prefill_kv_rows(0, 1, 16) == 16
        assert flash_prefill_kv_rows(0, 16, 16) == 16
        assert flash_prefill_kv_rows(0, 17, 16) == 32
        assert flash_prefill_kv_rows(576, 128, 16) == 704
        assert flash_prefill_kv_rows(1408, 5, 16) == 1424    # padded
        assert flash_prefill_kv_rows(13, 3, 16) == 16         # mid-page

    @pytest.mark.parametrize("H", [64, 128])
    @pytest.mark.parametrize("case,p0,n_real,dtype,split", [
        ("start", 0, 32, "float32", False),
        ("inside_a_tile", 40, 32, "float32", False),
        ("crosses_a_tile_edge", 112, 32, "float32", False),
        ("past_two_tiles", 300, 32, "float32", False),
        ("padded_chunk", 120, 11, "float32", False),
        ("bf16_arenas", 232, 32, "bfloat16", False),
        ("a_column_a_step", 200, 32, "float32", True),
    ])
    def test_tiles_over_the_live_context(self, monkeypatch, H, case, p0,
                                         n_real, dtype, split):
        """The kernel on 128-row tiles against gather+dense. The table
        past the chunk's last page holds out-of-range entries and every
        page the chunk does not reach holds NaN: a result that is
        finite and equal read nothing past the live context."""
        import jax.numpy as jnp
        from paddle_tpu.ops import pallas_decode as pd
        monkeypatch.setattr(pd, "_TILE_ROWS", 128)
        if split:
            monkeypatch.setattr(pd, "flash_prefill_tiling",
                                lambda *a: (max(H, 128), 128))
        pd.flash_prefill_chunk.clear_cache()
        rng = np.random.default_rng(p0 + H)
        N, bs, C, mb = 4, 16, 32, 24
        nh = N * H
        q = 0.3 * rng.standard_normal((1, C, nh)).astype(np.float32)
        kp = 0.3 * rng.standard_normal((mb + 3, bs, nh)).astype(np.float32)
        vp = 0.3 * rng.standard_normal((mb + 3, bs, nh)).astype(np.float32)
        table = rng.permutation(np.arange(1, mb + 3)).astype(np.int32)[:mb]
        n_live = (p0 + n_real - 1) // bs + 1
        dirty_table = table.copy()
        dirty_table[n_live:] = 1 << 20
        dead = np.setdiff1d(np.arange(mb + 3), table[:n_live])
        dirty_k, dirty_v = kp.copy(), vp.copy()
        dirty_k[dead] = np.nan
        dirty_v[dead] = np.nan
        cast = lambda a: jnp.asarray(a, dtype)
        try:
            got = pd.flash_prefill_chunk(
                cast(q), cast(dirty_k), cast(dirty_v), dirty_table,
                np.int32(p0), N, use_kernel=True, n_real=np.int32(n_real))
        finally:
            pd.flash_prefill_chunk.clear_cache()
        want = pd.flash_prefill_chunk(
            cast(q), cast(kp), cast(vp), table, np.int32(p0), N,
            use_kernel=False)
        got = np.asarray(got, np.float32)
        assert got.shape == (1, C, nh) and np.isfinite(got).all()
        tol = 2e-2 if dtype == "bfloat16" else 1e-4
        np.testing.assert_allclose(
            got[0, :n_real], np.asarray(want, np.float32)[0, :n_real],
            rtol=tol, atol=tol)

    def test_padded_last_chunk_through_the_engine(self):
        """The engine's own prefill step on its own arenas, a full
        chunk and then a padded one (n_real < C), with the kernel and
        through gather+dense: the same logits at the last real
        position, the same rows written in every block but the null
        block, which takes the padding rows."""
        import functools
        import jax
        model = _small_gpt()
        eng = ServingEngine(model, max_slots=2, block_size=8,
                            prefill_chunk=16, max_model_len=64,
                            dtype="float32")
        params = eng._param_vals()
        rs = np.random.RandomState(3)
        table = np.arange(1, eng.max_blocks_per_seq + 1, dtype=np.int32)
        k, v = eng.cache.k, eng.cache.v
        for p0, n_real in ((0, 16), (16, 5)):
            ids = np.zeros((1, 16), np.int32)
            ids[0, :n_real] = rs.randint(0, 512, (n_real,))
            outs = {uk: jax.jit(functools.partial(
                eng._prefill_logits, use_kernel=uk))(
                    params, k, v, ids, np.int32(p0), np.int32(n_real),
                    table) for uk in (True, False)}
            kernel, dense = (jax.tree_util.tree_leaves(outs[uk])
                             for uk in (True, False))
            assert kernel[0].shape == (1, 512)
            for got, want in zip(kernel, dense, strict=True):
                got, want = np.asarray(got), np.asarray(want)
                assert got.dtype == np.float32
                if got.ndim == 3:               # an arena: drop block 0
                    got, want = got[1:], want[1:]
                np.testing.assert_allclose(got, want, rtol=2e-4,
                                           atol=2e-4)
            _, k, v = outs[True]

    def test_registered_and_doctor_clean(self):
        from paddle_tpu.analysis.kernel_lint import lint_kernel
        from paddle_tpu.ops.kernel_registry import get_kernel
        reg = get_kernel("flash_prefill_chunk")
        assert reg.fallback is not None
        findings, info = lint_kernel(reg)
        assert findings == [], [str(f) for f in findings]
        assert info["has_fallback"]


# ---------------------------------------------------------------------------
# telemetry cross-rules
# ---------------------------------------------------------------------------

def test_trace_check_prefix_cross_rules():
    sys.path.insert(0, TOOLS)
    import trace_check
    from paddle_tpu.telemetry.sink import make_serving_record

    def check(recs):
        return trace_check.check_serving_records(recs, "mem")

    ok = [make_serving_record("quiesce", engine=1, kv_blocks_used=0,
                              counts={"admitted": 0, "finished": 0,
                                      "failed": 0, "cancelled": 0,
                                      "expired": 0},
                              prefix_blocks_shared=0,
                              prefix_hit_rate=0.5,
                              prefill_tokens_saved=10,
                              prefill_tokens_offered=20)]
    assert check(ok) == []
    bad_rate = [make_serving_record("admitted", rid=1, engine=1,
                                    prefix_hit_rate=1.5)]
    assert any("outside [0, 1]" in p for p in check(bad_rate))
    bad_saved = [make_serving_record("admitted", rid=1, engine=1,
                                     prefill_tokens_saved=30,
                                     prefill_tokens_offered=20)]
    assert any("saved" in p for p in check(bad_saved))
    shared = [make_serving_record("quiesce", engine=1, kv_blocks_used=0,
                                  counts={"admitted": 0, "finished": 0,
                                          "failed": 0, "cancelled": 0,
                                          "expired": 0},
                                  prefix_blocks_shared=2)]
    assert any("SHARED" in p for p in check(shared))
