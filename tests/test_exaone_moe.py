"""K-EXAONE through the serving engine at toy widths on the CPU (two
periods L L L G, window 8, 16 experts of which 4 are held; d 32): the
rings by request beside the paged K/V, the banded chunk attention and
decode over a ring, rotary grouped-query heads with QK-norm, the
sigmoid router, held to the plain reference of
benchmark/reference/exaone_moe.py (float32, the window a mask, no
cache)."""
import os
import sys

import numpy as np
import pytest
import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.drivers.serve_exaone import seeded_program_model  # noqa: E402
from benchmark.reference import exaone_moe as ref                # noqa: E402
from paddle_tpu import monitor                                   # noqa: E402
from paddle_tpu.moe.serving import route_sigmoid_topk            # noqa: E402
from paddle_tpu.ops import pallas_decode as pd                   # noqa: E402
from paddle_tpu.ops.rotary import (apply_rotary, rotary_cos_sin,  # noqa: E402
                                   yarn_inv_freq)
from paddle_tpu.serving import (EngineConfig, SamplingParams,    # noqa: E402
                                ServingEngine)
from paddle_tpu.serving.kv_cache import (PagedKVCache, kv_kind,  # noqa: E402
                                         window_kind)

W = 8
PERIOD = ["sliding_attention"] * 3 + ["full_attention"]
TINY = ref.sizes({
    "vocab_size": 96, "hidden_size": 32, "num_hidden_layers": 8,
    "num_layers": 8, "layer_types": PERIOD * 2,
    "mlp_layer_types": ["dense"] + ["sparse"] * 7,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 8,
    "sliding_window": W, "intermediate_size": 48,
    "moe_intermediate_size": 16, "num_experts": 4,
    "num_experts_per_tok": 4, "num_shared_experts": 1,
    "routed_scaling_factor": 2.5, "norm_topk_prob": True,
    "rms_norm_eps": 1e-5, "rope_parameters": {"rope_theta": 1000000},
    "initializer_range": 0.2,
    "deployment": {"router_experts": 16, "held_experts": [0, 4]}})
SCALE = {"block_scale": 1.0}
SEED = 2 ** 31 + 23
# float32 program against the float32 reference: the cached path sums
# in another order than the whole forward pass, which at logits of
# order 1 leaves 1e-5; a bfloat16 program leaves 1e-2 and more
TOL = 2e-4


def model(m=TINY):
    return seeded_program_model(m, SEED, SCALE, 128, dtype="float32")


def engine(chunk=16, slots=3, **kw):
    return ServingEngine(model(), config=EngineConfig(
        max_slots=slots, block_size=4, prefill_chunk=chunk,
        max_model_len=128, dtype=None, **kw))


def served_logits(eng, prompt, n_new, row=2, slot=1):
    """Logits of the positions len(prompt)-1 .. +n_new-1, taken from the
    engine's own compiled prefill and decode steps over its arenas,
    feeding the greedy tokens back."""
    C = eng.cfg.prefill_chunk
    mb = eng.max_blocks_per_seq
    table = np.arange(1, mb + 1, dtype=np.int32)
    k, v = eng.cache.k, eng.cache.v
    params = eng._param_vals()
    out = []
    for p0 in range(0, len(prompt), C):
        n = min(C, len(prompt) - p0)
        ids = np.zeros((1, C), np.int32)
        ids[0, :n] = prompt[p0:p0 + n]
        last, k, v = jax.jit(eng._prefill_logits)(
            params, k, v, ids, np.int32(p0), np.int32(n), table,
            np.int32(row))
    out.append(np.asarray(last[0]))
    S = eng.cfg.max_slots
    seq = list(prompt)
    for _ in range(n_new - 1):
        seq.append(int(np.argmax(out[-1])))
        toks = np.zeros((S,), np.int32)
        ctx = np.zeros((S,), np.int32)
        tables = np.zeros((S, mb), np.int32)
        rows = np.zeros((S,), np.int32)
        toks[slot], ctx[slot], tables[slot] = seq[-1], len(seq) - 1, table
        rows[slot] = row
        last, k, v = jax.jit(eng._decode_logits)(
            params, k, v, toks, ctx, tables, rows)
        out.append(np.asarray(last[slot]))
    return np.stack(out), seq, (k, v)


def reference_logits(seq, m=TINY):
    return np.asarray(ref.full_logits(m, SEED, SCALE, np.asarray(seq)))


# -- prefill in chunks, then decode, through ring and pages -------------

@pytest.mark.parametrize("n,chunk", [
    (5, 16),        # shorter than the window
    (W, 16),        # the window exactly
    (W + 1, 16),    # one more: the first key falls out
    (61, 16),       # many windows, chunks of two windows
    (37, 8),        # a chunk a window
    (45, 12),       # p0 = 12, 24, 36: no multiple of the window
    (30, 40),       # the whole prompt in one padded chunk
])
def test_chunked_prefill_then_decode_is_the_full_forward(n, chunk):
    rng = np.random.default_rng(n)
    prompt = rng.integers(1, TINY["vocab_size"], n)
    got, seq, _ = served_logits(engine(chunk=chunk), prompt, 2 * W + 3)
    assert np.abs(got - reference_logits(seq)[n - 1:]).max() < TOL


def _keys_of_layer_0(mdl, seq):
    """K (rotated) and V of the first layer, whose input is the
    embedding: [T, kv width] each."""
    attn = mdl.blocks[0].attn
    h = mdl.embed._value[np.asarray(seq)]
    _, k, v = attn.project(h, jnp.arange(len(seq), dtype=jnp.int32))
    return np.asarray(k), np.asarray(v)


@pytest.mark.parametrize("n,steps", [(5, 1), (13, 1), (29, 1), (29, 12)])
def test_ring_holds_exactly_the_last_window_positions(n, steps):
    """After the chunks, and after decode steps: ring row r is the
    newest position that is r modulo the window; rows no position has
    reached keep what they held."""
    rng = np.random.default_rng(100 + n)
    eng = engine(chunk=16)
    marked = tuple(jnp.full_like(a, 7.0) for a in eng.cache.k[:1]) \
        + eng.cache.k[1:]
    eng.cache.swap(marked, eng.cache.v)
    prompt = rng.integers(1, TINY["vocab_size"], n)
    _, seq, (k, v) = served_logits(eng, prompt, steps, row=2)
    cached = len(seq)   # every token of `seq` went through a step
    want_k, want_v = _keys_of_layer_0(eng.model, seq)
    for r in range(W):
        older = [p for p in range(cached) if p % W == r]
        if older:
            np.testing.assert_allclose(k[0][2, r], want_k[older[-1]],
                                       atol=1e-6)
            np.testing.assert_allclose(v[0][2, r], want_v[older[-1]],
                                       atol=1e-6)
        else:
            assert np.all(np.asarray(k[0][2, r]) == 7.0)
    # nobody else's ring was touched
    assert np.all(np.asarray(k[0][1]) == 7.0)


def test_a_reused_row_that_held_a_longer_request_changes_nothing():
    rng = np.random.default_rng(7)
    a = rng.integers(1, TINY["vocab_size"], 43)
    b = rng.integers(1, TINY["vocab_size"], 5)
    eng = engine()
    _, _, (k, v) = served_logits(eng, a, 4, row=2)
    eng.cache.swap(k, v)            # row 2 now holds a's last 8 positions
    assert float(jnp.abs(k[0][2]).min()) > 0
    got, seq, _ = served_logits(eng, b, 2 * W, row=2)
    assert np.abs(got - reference_logits(seq)[len(b) - 1:]).max() < TOL


# -- the kernels in interpret mode against their fallbacks --------------

@pytest.mark.parametrize("p0,n_real", [(0, 256), (0, 1), (100, 253),
                                       (333, 256), (384, 130)])
def test_window_chunk_kernel_is_its_fallback(p0, n_real):
    rng = np.random.default_rng(p0 + n_real)
    (q, k, v, rk, rv, row, _, N), kw = pd._window_example(rng)
    kw.update(n_real=np.int32(n_real))
    got = pd.window_prefill_chunk(q, k, v, rk, rv, row, np.int32(p0), N,
                                  **kw)
    want = pd.window_prefill_chunk(q, k, v, rk, rv, row, np.int32(p0), N,
                                   **dict(kw, use_kernel=False))
    assert np.abs(np.asarray(got - want)[:n_real]).max() < 2e-5


def test_window_chunk_is_the_banded_softmax_by_hand():
    """The fallback against the mask written out: the whole sequence's
    keys, query i sees j iff 0 <= i - j < window."""
    rng = np.random.default_rng(5)
    N, Nk, H, Wd, C, p0 = 4, 2, 8, 8, 16, 21
    T = p0 + C
    q, k, v = (rng.standard_normal((T, n * H)).astype(np.float32)
               for n in (N, Nk, Nk))
    ring_k = np.full((3, Wd, Nk * H), 9.0, np.float32)
    ring_v = np.full((3, Wd, Nk * H), 9.0, np.float32)
    for p in range(p0):             # position p lives in row p % window
        ring_k[1, p % Wd], ring_v[1, p % Wd] = k[p], v[p]
    got = pd.window_prefill_chunk(q[p0:], k[p0:], v[p0:], ring_k, ring_v,
                                  np.int32(1), np.int32(p0), N, kv_heads=Nk)
    q4 = q.reshape(T, Nk, N // Nk, H)
    s = np.einsum("tkgh,skh->kgts", q4, k.reshape(T, Nk, H)) * H ** -0.5
    behind = np.arange(T)[:, None] - np.arange(T)[None, :]
    s = np.where((behind >= 0) & (behind < Wd), s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    want = np.einsum("kgts,skh->tkgh", p, v.reshape(T, Nk, H))
    assert np.abs(np.asarray(got) - want.reshape(T, N * H)[p0:]).max() < 1e-5


def test_ring_positions_and_write_by_hand():
    assert list(pd.window_ring_positions(np.int32(0), 4)) == [-4, -3, -2, -1]
    assert list(pd.window_ring_positions(np.int32(6), 4)) == [4, 5, 2, 3]
    assert list(pd.window_ring_positions(np.int32(8), 4)) == [4, 5, 6, 7]
    ring = jnp.full((2, 4, 1), -1.0)
    chunk = jnp.arange(10, 16, dtype=jnp.float32)[:, None]  # positions 6..
    out = pd.window_ring_write(ring, 1, chunk, np.int32(6), np.int32(1))
    assert list(out[1, :, 0]) == [-1, -1, 10, -1]       # position 6 alone
    out = pd.window_ring_write(ring, 1, chunk, np.int32(6), np.int32(5))
    assert list(out[1, :, 0]) == [12, 13, 14, 11]       # positions 7..10
    assert list(out[0, :, 0]) == [-1] * 4


def test_decode_over_a_ring_kernel_is_its_fallback():
    """`paged_decode_attention` on rings: one `window`-row page a
    request, contexts clipped to the ring, under its own name."""
    rng = np.random.default_rng(9)
    N, Nk, H, Wd, S = 4, 2, 128, 128, 3
    q = 0.3 * rng.standard_normal((S, 1, N * H)).astype(np.float32)
    rk, rv = (0.3 * rng.standard_normal((S + 1, Wd, Nk * H))
              .astype(np.float32) for _ in range(2))
    rows = np.array([2, 0, 3], np.int32)
    ctx = np.array([700, 0, 41], np.int32)
    args = (q, rk, rv, rows[:, None], np.minimum(ctx, Wd - 1), N)
    kw = dict(kv_heads=Nk, name="paged_decode_window")
    got = pd.paged_decode_attention(*args, use_kernel=True, **kw)
    want = pd.paged_decode_attention(*args, use_kernel=False, **kw)
    assert np.abs(np.asarray(got - want)).max() < 2e-5
    # and the name reaches the kernel
    for name in ("paged_decode", "paged_decode_window"):
        text = str(jax.make_jaxpr(lambda *a: pd.paged_decode_attention(
            *a, N, use_kernel=True, kv_heads=Nk, name=name))(*args[:5]))
        assert ("paged_decode_window" in text) == (name != "paged_decode")


# -- the router, the shares, the rotation -------------------------------

def test_router_by_hand_and_the_bias_chooses_but_does_not_weigh():
    x = np.array([[1.0, 0.0], [0.0, 1.0]], np.float32)
    w = np.array([[2.0, 1.0, 0.9, -1.0], [0.0, 0.1, 0.2, 0.3]], np.float32)
    sig = lambda z: 1.0 / (1.0 + np.exp(-z))
    none = np.zeros((4,), np.float32)
    weights, experts = route_sigmoid_topk(x, w, none, 2, scale=2.5)
    assert [sorted(e) for e in np.asarray(experts)] == [[0, 1], [2, 3]]
    s = sig(np.array([2.0, 1.0]))
    np.testing.assert_allclose(np.sort(weights[0])[::-1], 2.5 * s / s.sum(),
                               rtol=1e-6)
    # a bias that lifts expert 2 over expert 1 for the first token
    bias = np.array([0.0, 0.0, 0.05, 0.0], np.float32)
    weights, experts = route_sigmoid_topk(x, w, bias, 2, scale=2.5)
    assert sorted(np.asarray(experts)[0]) == [0, 2]
    s = sig(np.array([2.0, 0.9]))       # the scores, without the bias
    np.testing.assert_allclose(np.sort(weights[0])[::-1], 2.5 * s / s.sum(),
                               rtol=1e-6)
    raw, _ = route_sigmoid_topk(x, w, bias, 2, scale=2.5, renorm=False)
    np.testing.assert_allclose(np.sort(raw[0])[::-1], 2.5 * s, rtol=1e-6)


def test_router_is_the_references_and_its_bias_moves_choices():
    rng = np.random.default_rng(3)
    x = rng.standard_normal((400, 32)).astype(np.float32)
    w = ref.layer_leaf(TINY, SEED, 1, 7)        # moe.router
    bias = ref.layer_leaf(TINY, SEED, 1, 8)     # moe.bias
    assert w.shape == (32, 16) and bias.shape == (16,)
    weights, experts = route_sigmoid_topk(x, w, bias, 4, 2.5)
    want_w, want_e, margin = ref.route(TINY, jnp.asarray(x), w, bias)
    sure = np.asarray(margin) > 1e-6
    assert np.array_equal(np.asarray(experts)[sure], np.asarray(want_e)[sure])
    np.testing.assert_allclose(np.asarray(weights)[sure],
                               np.asarray(want_w)[sure], atol=1e-6)
    _, plain = route_sigmoid_topk(x, w, 0 * bias, 4, 2.5)
    moved = np.mean([set(a) != set(b) for a, b in
                     zip(np.asarray(experts), np.asarray(plain))])
    assert 0.0 < moved < 0.5


def test_the_shares_add_up_to_the_uncut_layer():
    """The four shares' routed parts plus the shared expert once are the
    layer with all 16 experts, which is the reference's."""
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.standard_normal((24, 32)).astype(np.float32))
    whole = dict(TINY, held_experts=(0, 16))
    layer = model(whole).blocks[1].moe
    want, stats = layer.run(x)
    assert float(stats["moe_pairs_held"]) == 24 * 4
    shared = layer.shared.run(x)
    total = shared
    for first in range(0, 16, 4):
        part = model(dict(TINY, held_experts=(first, 4))).blocks[1].moe
        total = total + part.run(x)[0] - part.shared.run(x)
    assert np.abs(np.asarray(total - want)).max() < 1e-5
    # and the uncut model is the uncut reference
    seq = rng.integers(1, TINY["vocab_size"], 21)
    got = np.asarray(model(whole).forward(seq[None])._value)[0]
    assert np.abs(got - reference_logits(seq, whole)).max() < TOL


def test_rotary_grouped_query_heads_against_the_closed_form():
    rng = np.random.default_rng(6)
    T, N, H, theta = 5, 3, 8, 1e6
    x = rng.standard_normal((T, N, H)).astype(np.float32)
    pos = np.array([0, 1, 7, 130, 12000], np.int32)
    cos, sin = rotary_cos_sin(pos, yarn_inv_freq(H, theta))
    got = np.asarray(apply_rotary(x, cos[:, None], sin[:, None],
                                  interleaved=False))
    # dimension i turns with dimension i + H/2 by pos * theta^(-2i/H)
    z = x[..., :H // 2] + 1j * x[..., H // 2:]
    angle = pos[:, None] * theta ** (-np.arange(0, H, 2) / H)
    z = z * np.exp(1j * angle)[:, None, :]
    want = np.concatenate([z.real, z.imag], axis=-1)
    np.testing.assert_allclose(got, want, atol=2e-4)
    # scores depend on the distance only
    q = apply_rotary(x[:1], *(t[None] for t in rotary_cos_sin(
        np.array([40]), yarn_inv_freq(H, theta))), interleaved=False)
    k = apply_rotary(x[1:2], *(t[None] for t in rotary_cos_sin(
        np.array([33]), yarn_inv_freq(H, theta))), interleaved=False)
    q2 = apply_rotary(x[:1], *(t[None] for t in rotary_cos_sin(
        np.array([1007]), yarn_inv_freq(H, theta))), interleaved=False)
    k2 = apply_rotary(x[1:2], *(t[None] for t in rotary_cos_sin(
        np.array([1000]), yarn_inv_freq(H, theta))), interleaved=False)
    assert abs(float(jnp.sum(q * k) - jnp.sum(q2 * k2))) < 1e-3


# -- the cache kinds ----------------------------------------------------

def test_a_window_layer_costs_a_request_a_ring_and_a_block_nothing():
    """The published widths: 8 layers L L L G L L L G, K/V rows of 1,024
    bfloat16 numbers, a window of 128."""
    kinds = [window_kind(1024, 128)] * 3 + [kv_kind(1024)]
    kinds = kinds * 2
    assert PagedKVCache.block_bytes(kinds, 16, "bfloat16") == 16 * 8192
    assert kinds[0].request_bytes == 2 * 128 * 1024 * 2
    assert PagedKVCache.request_bytes(kinds) == 6 * 2 * 128 * 1024 * 2
    assert kinds[0].by_request and kinds[0].name == "window" \
        and not kinds[3].by_request
    eng = engine()
    assert [k.name for k in eng.cache_kinds] == ["window"] * 3 + ["kv"] \
        + ["window"] * 3 + ["kv"]
    assert eng.cache.k[0].shape == (eng.cfg.max_slots + 1, W, 16)
    assert eng.cache.k[3].shape[1:] == (4, 16)
    assert eng._block_bytes() == 2 * 2 * 16 * 4 * 4    # two paged layers
    assert eng.prefix_index is None and eng.rows.names == ("window",)


# -- through submit -----------------------------------------------------

def _streams(eng, prompts, n_new=6, stepwise=False):
    hs = [eng.submit(p.astype(np.int32), SamplingParams(max_new_tokens=n_new))
          for p in prompts]
    if stepwise:
        while eng.run_until_idle(max_steps=1):
            pass
    else:
        eng.run_until_idle()
    return [h.result() for h in hs]


def _greedy_reference(prompt, n_new):
    """The reference's greedy continuation. The sequence is padded to
    one length so that the reference compiles once: attention is causal,
    no position sees the padding behind it."""
    seq = list(prompt)
    for _ in range(n_new):
        padded = np.zeros((64,), np.int64)
        padded[:len(seq)] = seq
        seq.append(int(np.argmax(reference_logits(padded)[len(seq) - 1])))
    return seq[len(prompt):]


def test_streams_through_submit_match_reference_greedy():
    rng = np.random.default_rng(11)
    prompts = [rng.integers(1, TINY["vocab_size"], n) for n in (23, 9, 40, 17)]
    eng = engine(slots=3)
    before = {n: monitor.get("serving." + n)
              for n in ("window_rows_taken", "window_rows_released",
                        "state_rows_taken")}
    got = _streams(eng, prompts, 10)
    assert got == [_greedy_reference(p, 10) for p in prompts]
    assert eng.rows.num_live == 0 and eng.pool.num_used == 0
    eng.rows.assert_quiesced()
    grew = {n: monitor.get("serving." + n) - was
            for n, was in before.items()}
    assert grew == {"window_rows_taken": 4, "window_rows_released": 4,
                    "state_rows_taken": 0}
    assert monitor.get_gauge("serving.window_rows_live", -1) == 0


def test_step_in_flight_carries_the_rings():
    """The loop with one decode step in flight gives the streams of the
    loop that retires every step before the next."""
    rng = np.random.default_rng(12)
    prompts = [rng.integers(1, TINY["vocab_size"], n) for n in (21, 33, 12)]
    assert _streams(engine(), prompts, 12) \
        == _streams(engine(), prompts, 12, stepwise=True)


def test_preempt_and_replay_gives_the_same_stream():
    """A pool too small for both requests preempts the younger, which
    gives its ring back and replays from position 0."""
    rng = np.random.default_rng(14)
    prompts = [rng.integers(1, TINY["vocab_size"], n) for n in (30, 28)]
    want = [_greedy_reference(p, 14) for p in prompts]
    replays = monitor.get("serving.state_replays")
    eng = engine(slots=2, num_blocks=19)      # 18 blocks of 4: 72 positions
    got = _streams(eng, prompts, 14)
    assert eng.sched.preemptions > 0
    assert monitor.get("serving.state_replays") > replays
    assert got == want
    assert eng.rows.num_live == 0


def test_a_step_that_raises_replays_from_zero():
    """A ring that took in a dropped step's position has lost the oldest
    key of the position to compute again: the requests replay."""
    rng = np.random.default_rng(15)
    prompts = [rng.integers(1, TINY["vocab_size"], n) for n in (19, 26)]
    want = [_greedy_reference(p, 8) for p in prompts]
    eng = engine()
    hs = [eng.submit(p.astype(np.int32), SamplingParams(max_new_tokens=8))
          for p in prompts]
    for _ in range(5):
        eng.step()
    real = eng._fetch_flight

    def broken(flight):
        eng._fetch_flight = real
        raise RuntimeError("planted")
    eng._fetch_flight = broken
    with pytest.raises(RuntimeError):
        eng.step()
    assert eng.rows.num_live == 0 and len(eng.sched.waiting) == 2
    eng.run_until_idle()
    assert [h.result() for h in hs] == want


def test_dispatch_span_names_the_rings_and_their_rows():
    import paddle_tpu.serving.engine as engine_mod
    eng = engine(chunk=16)
    seen = []
    kept = engine_mod._span

    def spy(name, **kw):
        if name == "serving_dispatch":
            seen.append(kw)
        return kept(name, **kw)

    engine_mod._span = spy
    try:
        _streams(eng, [np.arange(1, 20)], 4)
    finally:
        engine_mod._span = kept
    assert seen and all(kw["cache_kind"] == "kv+window" for kw in seen)
    assert all(kw["state_rows"] == 0 for kw in seen)
    chunks = [kw for kw in seen if kw["family"] == "serving_prefill"]
    assert [(kw["p0"], kw["window_rows"], kw["window_kv_rows"])
            for kw in chunks] == [(0, 1, 0), (16, 1, W - 1)]
    decode = [kw for kw in seen if kw["family"] == "serving_decode"]
    assert decode and all(kw["window_rows"] == 1
                          and kw["window_kv_rows"] == W for kw in decode)
    # the paged layers' rows, as for every model: whole pages of 4
    assert decode[0]["kv_rows"] >= 20
