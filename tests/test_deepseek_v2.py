"""DeepSeek-V2 through the serving engine at toy widths on the CPU:
the latent paged cache, the absorbed attention, the drop-free expert
layer and its share of an expert-parallel deployment, held to the plain
reference of benchmark/reference/deepseek_v2.py (float32, attention not
absorbed, no cache)."""
import functools
import math
import os
import sys

import numpy as np
import jax
import jax.numpy as jnp
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.drivers.serve_mla import seeded_program_model   # noqa: E402
from benchmark.reference import deepseek_v2 as ref             # noqa: E402
from paddle_tpu.moe.serving import (held_expert_ffn,           # noqa: E402
                                    route_group_limited)
from paddle_tpu.ops import pallas_mla, rotary                  # noqa: E402
from paddle_tpu.ops.kernel_registry import registered_kernels  # noqa: E402
from paddle_tpu.serving import (EngineConfig, SamplingParams,  # noqa: E402
                                ServingEngine)
from paddle_tpu.serving.kv_cache import (PagedKVCache, kv_kind,  # noqa: E402
                                         latent_kind)

ROPE = {"type": "yarn", "factor": 4, "original_max_position_embeddings": 16,
        "beta_fast": 32, "beta_slow": 1, "mscale": 0.707,
        "mscale_all_dim": 0.707}
TINY = {"vocab_size": 96, "hidden_size": 64, "num_layers": 3,
        "num_attention_heads": 4, "q_lora_rank": 32, "kv_lora_rank": 32,
        "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
        "intermediate_size": 128, "moe_intermediate_size": 32,
        "n_shared_experts": 2, "num_experts_per_tok": 3, "n_group": 4,
        "topk_group": 2, "routed_scaling_factor": 4.0,
        "first_k_dense_replace": 1, "rms_norm_eps": 1e-6,
        "rope_theta": 10000, "rope_scaling": ROPE,
        "initializer_range": 0.02, "router_experts": 16,
        "held_experts": (0, 8)}
SCALE = {"block_scale": 8.0}    # attention and routing with something to pick
SEED = 2 ** 31 + 11
# float32 program against the float32 reference: both round every
# product differently (the program absorbs W_uk into the query and sums
# the experts grouped), which at logits of order 1 leaves 1e-5; a
# bfloat16 program leaves 1e-2 and more
TOL = 2e-4


def engine(dtype=None, m=TINY, **kw):
    model = seeded_program_model(m, SEED, SCALE, 128, dtype="float32")
    return ServingEngine(model, config=EngineConfig(
        max_slots=3, block_size=16, prefill_chunk=32, max_model_len=128,
        dtype=dtype, **kw))


def served_logits(eng, prompt, n_new, use_kernel=None):
    """Logits of the positions len(prompt)-1 .. +n_new-1, taken from the
    engine's own compiled prefill and decode steps over its arenas,
    feeding the reference's greedy tokens. `use_kernel` True: through
    the Pallas kernels (in the interpreter here)."""
    prefill = jax.jit(functools.partial(eng._prefill_logits,
                                        use_kernel=use_kernel))
    decode = jax.jit(functools.partial(eng._decode_logits,
                                       use_kernel=use_kernel))
    C, bs = eng.cfg.prefill_chunk, eng.block_size
    mb = eng.max_blocks_per_seq
    table = np.zeros((mb,), np.int32)
    table[:] = np.arange(1, mb + 1)
    k, v = eng.cache.k, eng.cache.v
    params = eng._param_vals()
    out = []
    for p0 in range(0, len(prompt), C):
        n = min(C, len(prompt) - p0)
        ids = np.zeros((1, C), np.int32)
        ids[0, :n] = prompt[p0:p0 + n]
        last, k, v = prefill(params, k, v, ids, np.int32(p0), np.int32(n),
                             table)
    out.append(np.asarray(last[0]))
    S = eng.cfg.max_slots
    seq = list(prompt)
    for _ in range(n_new - 1):
        seq.append(int(np.argmax(out[-1])))
        toks = np.zeros((S,), np.int32)
        ctx = np.zeros((S,), np.int32)
        tables = np.zeros((S, mb), np.int32)
        toks[1], ctx[1], tables[1] = seq[-1], len(seq) - 1, table
        last, k, v = decode(params, k, v, toks, ctx, tables)
        out.append(np.asarray(last[1]))
    return np.stack(out), seq


@pytest.mark.parametrize("use_kernel", [None, True],
                         ids=["fallback", "kernels"])
def test_engine_through_latent_cache_matches_reference(use_kernel):
    """A prompt of one full chunk and a partly filled one (32 + 13): the
    last chunk's attention is handed n_real and the head reads its last
    real position."""
    rng = np.random.default_rng(3)
    prompt = rng.integers(1, TINY["vocab_size"], 45)
    got, seq = served_logits(engine(), prompt, 6, use_kernel)
    want = np.asarray(ref.full_logits(TINY, SEED, SCALE, np.asarray(seq)))
    assert np.abs(got - want[len(prompt) - 1:]).max() < TOL
    if use_kernel:
        return
    # the tolerance sees precision: the same run with the program in
    # bfloat16 (weights, activations and latent cache) must fail it
    low, seq_low = served_logits(engine(dtype="bfloat16"), prompt, 6)
    want_low = np.asarray(ref.full_logits(TINY, SEED, SCALE,
                                          np.asarray(seq_low)))
    assert np.abs(low - want_low[len(prompt) - 1:]).max() > 10 * TOL


def test_absorbed_decode_equals_dense_attention():
    """The model's own whole-sequence forward forms k and v a head; the
    engine's steps never do."""
    eng = engine()
    rng = np.random.default_rng(4)
    prompt = rng.integers(1, TINY["vocab_size"], 37)
    got, seq = served_logits(eng, prompt, 4)
    dense = np.asarray(eng.model(np.asarray(seq)[None])._value[0])
    assert np.abs(got - dense[len(prompt) - 1:]).max() < TOL


def _chunk_case(seed, p0, n_real, C=32, mb=8, bs=16, N=4, W=256):
    """A chunk at p0 over a table whose entries past the page of the
    last real position point at a block of NaN, and the same table with
    every page real (what the reference gathers)."""
    rng = np.random.default_rng(seed)
    pages = 0.1 * rng.standard_normal((mb + 2, bs, W)).astype(np.float32)
    pages[mb + 1] = np.nan
    q = 0.1 * rng.standard_normal((C, N, W)).astype(np.float32)
    whole = np.arange(1, mb + 1, dtype=np.int32)
    live = (p0 + n_real - 1) // bs + 1
    row = np.where(np.arange(mb) < live, whole, mb + 1).astype(np.int32)
    return q, pages, row, whole


@pytest.mark.parametrize("p0", [48, 53], ids=["aligned", "inside_a_block"])
@pytest.mark.parametrize("n_real", [1, 3, 4, 30, 32])
def test_chunk_kernel_works_on_its_real_positions_only(p0, n_real):
    """The real positions are the dense reference's, the padded ones
    exactly zero, and no page past the last real position's is read (a
    prefix hit resumes inside a block; groups of 4 positions a grid
    step, so 1, 3 and 30 end inside a group)."""
    rank, scale = 128, 0.125
    q, pages, row, whole = _chunk_case(n_real, p0, n_real)
    got = np.asarray(pallas_mla.mla_prefill_chunk(
        q, pages, row, np.int32(p0), rank, scale, use_kernel=True,
        n_real=np.int32(n_real)))
    positions = p0 + jnp.arange(q.shape[0], dtype=jnp.int32)
    want = np.asarray(pallas_mla._dense(
        q[None], pages[whole].reshape(1, -1, q.shape[-1]), positions[None],
        rank, scale)[0])
    reg = next(r for r in registered_kernels()
               if r.name == "mla_prefill_chunk")
    assert not np.isnan(got).any()
    np.testing.assert_allclose(got[:n_real], want[:n_real],
                               rtol=reg.tol[0], atol=reg.tol[1])
    assert not got[n_real:].any()
    # the gather+dense path keeps the same contract over a sound table
    dense = np.asarray(pallas_mla.mla_prefill_chunk(
        q, pages, whole, np.int32(p0), rank, scale, use_kernel=False,
        n_real=np.int32(n_real)))
    assert np.array_equal(dense[:n_real], want[:n_real])
    assert not dense[n_real:].any()


def test_a_full_chunk_is_the_chunk_without_n_real():
    q, pages, _, whole = _chunk_case(0, 53, 32)
    args = (q, pages, whole, np.int32(53), 128, 0.125)
    full = pallas_mla.mla_prefill_chunk(*args, use_kernel=True,
                                        n_real=np.int32(32))
    plain = pallas_mla.mla_prefill_chunk(*args, use_kernel=True)
    assert np.array_equal(np.asarray(full), np.asarray(plain))


def test_prefill_positions_real_and_padded_add_up():
    """Every chunk is dispatched at `prefill_chunk` positions; the two
    counters say how many of them were a prompt's."""
    rng = np.random.default_rng(9)
    prompts = [rng.integers(1, TINY["vocab_size"], n) for n in (64, 45, 10)]
    eng = engine(enable_prefix_cache=False)
    before = eng.metrics_snapshot()
    _streams(eng, prompts, n_new=2)
    after = eng.metrics_snapshot()

    def grew(name):
        return after.get("serving." + name, 0) - before.get(
            "serving." + name, 0)
    assert grew("prefill_chunks") == 2 + 2 + 1
    assert grew("prefill_positions_real") == 64 + 45 + 10
    assert grew("prefill_positions_padded") == (32 - 13) + (32 - 10)
    assert grew("prefill_positions_real") + grew("prefill_positions_padded") \
        == grew("prefill_chunks") * eng.cfg.prefill_chunk


def test_weight_reads_arrive_with_the_steps_counts():
    """`moe_weight_reads` rides the packed stats array to
    `serving.moe_weight_reads`; no program here has more tokens than a
    tile has rows, so every reached expert is read once."""
    rng = np.random.default_rng(10)
    prompts = [rng.integers(1, TINY["vocab_size"], n) for n in (40, 17)]
    eng = engine(enable_prefix_cache=False)
    before = eng.metrics_snapshot()
    _streams(eng, prompts, n_new=3)
    after = eng.metrics_snapshot()
    reads, reached = (after.get("serving." + n, 0) - before.get(
        "serving." + n, 0) for n in ("moe_weight_reads",
                                     "moe_experts_reached"))
    assert reads == reached > 0


def _streams(eng, prompts, n_new=6):
    hs = [eng.submit(p.astype(np.int32), SamplingParams(max_new_tokens=n_new))
          for p in prompts]
    eng.run_until_idle()
    return [h.result() for h in hs]


def test_prefix_cache_shares_latent_blocks_and_forks_on_write():
    rng = np.random.default_rng(5)
    head = rng.integers(1, TINY["vocab_size"], 40)    # 2.5 blocks shared
    prompts = [np.concatenate([head, rng.integers(1, 96, n)])
               for n in (9, 5, 13)]
    cold = engine(enable_prefix_cache=False)
    want = _streams(cold, prompts)
    warm = engine(enable_prefix_cache=True)
    first = _streams(warm, prompts[:1])
    rest = _streams(warm, prompts[1:])
    assert first + rest == want
    stats = warm.prefix_stats()
    assert stats["hits"] == 2 and stats["tokens_saved"] >= 2 * 32
    # a request that resumes inside a shared block writes a private copy
    inside = np.concatenate([prompts[0][:44], rng.integers(1, 96, 3)])
    assert _streams(warm, [inside]) == _streams(cold, [inside])
    assert warm.metrics_snapshot().get("serving.prefix_cow_forks", 0) >= 1
    assert warm.pool.num_used == 0 and cold.pool.num_used == 0


def test_a_burst_on_one_document_computes_it_once():
    """Admission is bounded by the slots, so a burst is admitted into
    an index that holds nothing yet; each request looks again before
    its first chunk and takes what the one ahead of it published."""
    rng = np.random.default_rng(8)
    doc = rng.integers(1, TINY["vocab_size"], 64)       # 4 blocks
    prompts = [np.concatenate([doc, rng.integers(1, 96, n)])
               for n in (5, 9, 7)]
    burst = engine(enable_prefix_cache=True)
    got = _streams(burst, prompts)
    assert got == _streams(engine(enable_prefix_cache=False), prompts)
    stats = burst.prefix_stats()
    assert stats["hits"] == 2 and stats["tokens_saved"] == 2 * 64
    assert stats["tokens_saved"] <= stats["tokens_offered"]
    assert burst.pool.num_used == 0


def test_fork_copies_every_arena_of_either_kind():
    kinds = (kv_kind(8), latent_kind(16))
    cache = PagedKVCache(kinds, 4, 2, dtype="float32")
    assert [a.shape for a in cache.arenas()] == [(4, 2, 8), (4, 2, 16),
                                                 (4, 2, 8)]
    assert cache.v[1] is None
    assert cache.nbytes == 4 * PagedKVCache.block_bytes(kinds, 2, "float32")
    eng = engine()
    k = tuple(a.at[2].set(7.0) for a in eng.cache.k)
    new_k, new_v = eng._fork_jit(k, eng.cache.v, np.int32(2), np.int32(5))
    assert all(float(a[5].min()) == 7.0 for a in new_k)
    assert all(a is None for a in new_v)
    eng.cache.swap(new_k, new_v)
    assert len(eng.cache.arenas()) == TINY["num_layers"]
    assert eng.cache.fresh().nbytes == eng.cache.nbytes


def test_the_shares_add_up():
    """Four chips' routed parts plus the shared experts counted once
    are the uncut expert layer."""
    m = dict(TINY, held_experts=(0, 16))
    whole = seeded_program_model(m, SEED, SCALE, 128, dtype="float32")
    layer = whole.blocks[1].moe
    x = jnp.asarray(np.random.default_rng(6).standard_normal((21, 64)),
                    jnp.float32)
    uncut, stats = layer.run(x)
    weights, experts = layer.route(x)
    live = jnp.ones((21,), bool)
    parts, held_pairs = 0.0, 0.0
    for first in (0, 4, 8, 12):
        part = seeded_program_model(dict(TINY, held_experts=(first, 4)),
                                    SEED, SCALE, 128, dtype="float32")
        ffn = part.blocks[1].moe
        y, st = held_expert_ffn(x, live, weights, experts, (first, 4),
                                ffn.experts_gate._value, ffn.experts_up._value,
                                ffn.experts_down._value)
        parts = parts + y
        held_pairs += float(st["moe_pairs_held"])
        # a share's experts are the uncut model's
        assert np.array_equal(np.asarray(ffn.experts_gate._value),
                              np.asarray(layer.experts_gate._value[first:first + 4]))
    total = layer.shared.run(x) + parts
    assert np.abs(np.asarray(total - uncut)).max() < 1e-5
    assert held_pairs == 21 * 3 == float(stats["moe_pairs_chosen"])
    # the experts a step reaches are those with a row: counted, not
    # expected (the roofline of the expert kernel reads their weights)
    reached = len(set(np.asarray(experts).ravel().tolist()))
    assert float(stats["moe_experts_reached"]) == reached <= 16
    # and the uncut layer is the reference's
    w = ref.layer_weights(m, SEED, 1, SCALE)
    y = ref._gated(x, w["moe.shared.gate"], w["moe.shared.up"],
                   w["moe.shared.down"], "f32")
    rw, re, _ = ref.route(m, x, w["moe.router"])
    for e in range(16):
        y = y + jnp.sum(jnp.where(re == e, rw, 0.0), -1)[:, None] \
            * ref._gated(x, w[f"moe.experts.{e}.gate"],
                         w[f"moe.experts.{e}.up"],
                         w[f"moe.experts.{e}.down"], "f32")
    assert np.abs(np.asarray(uncut - y)).max() < 1e-4


def test_rmsnorm_layer_is_the_reference_norm():
    import paddle_tpu as paddle
    x = np.random.default_rng(9).standard_normal((3, 5, 64)) \
        .astype(np.float32)
    gain = 1.0 + 0.1 * np.arange(64, dtype=np.float32)
    layer = paddle.nn.RMSNorm(64, epsilon=1e-6)
    layer.weight.set_value(gain)
    want = np.asarray(ref._rmsnorm(jnp.asarray(x), jnp.asarray(gain), 1e-6))
    assert np.abs(np.asarray(layer(paddle.to_tensor(x))._value)
                  - want).max() < 1e-5
    bare = paddle.nn.functional.rms_norm(paddle.to_tensor(x))
    assert np.allclose(np.mean(np.asarray(bare._value) ** 2, -1), 1.0,
                       atol=1e-4)


def test_group_limited_routing_by_hand():
    # 8 experts in 4 groups of 2; logits chosen so that group 1 holds
    # the best expert, group 3 the second-best group maximum, and group
    # 0 two good experts whose maximum is only third
    logits = np.array([[2.0, 1.9, 3.0, -5.0, 0.0, 0.1, 2.5, -4.0]],
                      np.float32)
    x = jnp.ones((1, 1), jnp.float32)
    w, e = route_group_limited(x, jnp.asarray(logits), n_group=4,
                               topk_group=2, k=3, scale=10.0)
    scores = np.exp(logits[0]) / np.exp(logits[0]).sum()
    # groups 1 and 3 are kept: experts 2, 3, 6, 7; top 3 of them
    assert list(np.asarray(e[0])) == [2, 6, 7]
    assert np.allclose(np.asarray(w[0]), 10.0 * scores[[2, 6, 7]],
                       rtol=1e-6)
    # not renormalised: the weights do not sum to the scale
    assert float(w.sum()) < 10.0


def test_reference_route_margin_by_hand():
    """By how much the reference's router decided what THIS share
    computes: the group boundary always, the expert boundary where one
    of the two experts at it is held here."""
    m = {"router_experts": 8, "n_group": 4, "topk_group": 2,
         "num_experts_per_tok": 2, "routed_scaling_factor": 1.0}
    router = jnp.eye(8, dtype=jnp.float32)
    # groups 0 and 1 are kept by far (2.95 against 0.5); experts 0 and 2
    # are chosen, expert 1 is left out by 0.05
    x = jnp.asarray([[3.0, 2.9, 2.95, 0.0, 0.5, 0.0, 0.2, 0.0]])
    _, e, here = ref.route(dict(m, held_experts=(0, 4)), x, router)
    _, _, away = ref.route(dict(m, held_experts=(4, 4)), x, router)
    assert sorted(np.asarray(e[0]).tolist()) == [0, 2]
    assert abs(float(here[0]) - 0.05) < 1e-5
    assert abs(float(away[0]) - 2.45) < 1e-5
    # the group boundary decides for every share
    x = jnp.asarray([[3.0, 0.0, 2.0, 0.0, 1.5, 0.0, 1.0, 0.0]])
    for held in ((0, 4), (4, 4)):
        _, _, margin = ref.route(dict(m, held_experts=held), x, router)
        assert abs(float(margin[0]) - 0.5) < 1e-5


def test_yarn_tables_against_the_closed_form():
    dim, base, factor, orig = 64, 10000.0, 40.0, 4096
    inv = rotary.yarn_inv_freq(dim, base, factor, orig, 32, 1)
    i = np.arange(dim // 2)
    plain = base ** (-2.0 * i / dim)
    turns = orig * plain / (2 * math.pi)    # rotations over the context
    low = math.floor(dim * math.log(orig / (32 * 2 * math.pi))
                     / (2 * math.log(base)))
    high = math.ceil(dim * math.log(orig / (1 * 2 * math.pi))
                     / (2 * math.log(base)))
    assert (low, high) == (10, 23)
    assert np.allclose(inv[:low + 1], plain[:low + 1])      # extrapolated
    assert np.allclose(inv[high:], plain[high:] / factor)   # interpolated
    assert np.all(turns[:low] > 32) and np.all(turns[high + 1:] < 1)
    mid = (low + high) // 2
    ramp = (mid - low) / (high - low)
    assert np.isclose(inv[mid], plain[mid] * ((1 - ramp) + ramp / factor))
    assert np.isclose(rotary.yarn_mscale(40, 0.707),
                      0.1 * 0.707 * math.log(40) + 1)
    # the program's and the reference's tables are the same numbers
    m = dict(TINY, qk_rope_head_dim=64, rope_scaling=dict(
        ROPE, factor=40, original_max_position_embeddings=4096))
    assert np.allclose(ref.yarn_inv_freq(m), inv)
    cos, sin = rotary.rotary_cos_sin(jnp.arange(50), inv)
    rcos, rsin = ref.rotary_tables(m, 50)
    assert np.allclose(cos, rcos, atol=1e-6) and np.allclose(sin, rsin,
                                                             atol=1e-6)
