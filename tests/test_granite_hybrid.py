"""Granite-4.0-H through the serving engine at toy widths on the CPU
(pattern m, m, a, m; d 64): the request rows beside the paged K/V, the
chunked scan and the one-token state step, grouped-query heads in the
paged kernels, held to the plain reference of
benchmark/reference/granite_hybrid.py (float32, the recurrence itself,
no cache)."""
import os
import sys

import numpy as np
import pytest
import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.drivers.serve_hybrid import seeded_program_model  # noqa: E402
from benchmark.reference import granite_hybrid as ref            # noqa: E402
from paddle_tpu import monitor                                   # noqa: E402
from paddle_tpu.ops import pallas_decode as pd                   # noqa: E402
from paddle_tpu.ops import pallas_ssm as ssm                     # noqa: E402
from paddle_tpu.serving import (EngineConfig, SamplingParams,    # noqa: E402
                                ServingEngine)
from paddle_tpu.serving.kv_cache import (CacheKind, PagedKVCache,  # noqa: E402
                                         RowPool, kv_kind, state_kind)

TINY = ref.sizes({
    "vocab_size": 96, "hidden_size": 64, "num_hidden_layers": 4,
    "layer_types": ["mamba", "mamba", "attention", "mamba"],
    "num_attention_heads": 4, "num_key_value_heads": 2,
    "shared_intermediate_size": 96, "mamba_n_heads": 4, "mamba_d_head": 32,
    "mamba_d_state": 16, "mamba_d_conv": 4, "mamba_n_groups": 1,
    "mamba_expand": 2, "mamba_chunk_size": 8, "attention_multiplier": 0.0625,
    "embedding_multiplier": 12, "residual_multiplier": 0.22,
    "logits_scaling": 8, "rms_norm_eps": 1e-5, "initializer_range": 0.1})
SCALE = {"block_scale": 1.0}
SEED = 2 ** 31 + 17
# float32 program against the float32 reference: the chunked form sums
# in another order than the recurrence, which at logits of order 1
# leaves 1e-5; a bfloat16 program leaves 1e-2 and more
TOL = 2e-4


def engine(dtype=None, chunk=16, slots=3, **kw):
    model = seeded_program_model(TINY, SEED, SCALE, 128, dtype="float32")
    return ServingEngine(model, config=EngineConfig(
        max_slots=slots, block_size=8, prefill_chunk=chunk, max_model_len=128,
        dtype=dtype, **kw))


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


def reference_logits(seq):
    return np.asarray(ref.full_logits(TINY, SEED, SCALE, np.asarray(seq)))


# -- the chunked scan, the state step, the recurrence -------------------

def _recurrence(x, dt, a, b, c, s0):
    """The recurrence in float64 over the transposed state [N, H*P]."""
    H = dt.shape[1]
    P = x.shape[1] // H
    S = np.asarray(s0, np.float64).reshape(-1, H, P)
    ys = []
    for t in range(x.shape[0]):
        S = np.exp(dt[t] * a)[None, :, None] * S + b[t][:, None, None] \
            * (dt[t][:, None] * x[t].reshape(H, P))[None]
        ys.append(np.einsum("nhp,n->hp", S, c[t]).reshape(-1))
    return np.stack(ys), S.reshape(np.shape(s0))


@pytest.mark.parametrize("use_kernel", [False, True])
@pytest.mark.parametrize("piece", [128, 256])
def test_chunk_scan_is_the_recurrence(use_kernel, piece):
    args, _ = ssm._chunk_scan_example(np.random.default_rng(1))
    want_y, want_s = _recurrence(*(np.asarray(v, np.float64) for v in args))
    y, s = ssm.mamba2_chunk_scan(*args, piece=piece, use_kernel=use_kernel)
    assert np.abs(np.asarray(y)[:200] - want_y[:200]).max() < 2e-4
    assert np.abs(np.asarray(s) - want_s).max() < 2e-4


@pytest.mark.parametrize("split", [(5, 11), (8, 8), (13, 3), (1, 15)])
def test_chunk_scan_in_two_calls_passes_the_state_on(split):
    """A chunk split where the pieces do not divide it: the state after
    the first call starts the second."""
    rng = np.random.default_rng(2)
    C, H, P, N = 16, 4, 32, 16
    x = 0.5 * rng.standard_normal((C, H * P)).astype(np.float32)
    dt = rng.uniform(0.01, 0.3, (C, H)).astype(np.float32)
    a = -rng.uniform(1.0, 4.0, (H,)).astype(np.float32)
    b = rng.standard_normal((C, N)).astype(np.float32)
    c = rng.standard_normal((C, N)).astype(np.float32)
    s0 = np.zeros((N, H * P), np.float32)
    want_y, want_s = _recurrence(x, dt, a, b, c, s0)
    n = split[0]
    y1, s1 = ssm.mamba2_chunk_scan(x[:n], dt[:n], a, b[:n], c[:n], s0,
                                   piece=8)
    y2, s2 = ssm.mamba2_chunk_scan(x[n:], dt[n:], a, b[n:], c[n:], s1,
                                   piece=8)
    assert np.abs(np.concatenate([y1, y2]) - want_y).max() < 2e-4
    assert np.abs(np.asarray(s2) - want_s).max() < 2e-4


@pytest.mark.parametrize("use_kernel", [False, True])
def test_state_step_is_one_step_of_the_recurrence(use_kernel):
    args, _ = ssm._state_step_example(np.random.default_rng(3))
    state, rows, live, decay, dx, b, c = args
    new, y = ssm.mamba2_state_step(*args, use_kernel=use_kernel)
    new, y = np.asarray(new), np.asarray(y)
    for s, (row, alive) in enumerate(zip(rows, live)):
        want = state[row] * decay[s][None] + b[s][:, None] * dx[s][None]
        if not alive:
            assert row == 0 and not new[0].any()    # the null row: zeros
            continue
        assert np.abs(new[row] - want).max() < 1e-5
        assert np.abs(y[s] - (want * c[s][:, None]).sum(0)).max() < 1e-4
    untouched = [r for r in range(state.shape[0]) if r not in rows]
    assert (new[untouched] == state[untouched]).all()


def test_registry_holds_both_kernels_with_fallbacks():
    from paddle_tpu.ops.kernel_registry import registered_kernels
    reg = registered_kernels()
    for name in ("mamba2_state_step", "mamba2_chunk_scan"):
        assert name in reg and reg.get(name).fallback is not None


# -- grouped-query heads in the paged kernels ---------------------------

def _paged_case(rng, N, Nk, H, S=3, bs=16, mb=10):
    ctx = rng.integers(0, mb * bs - 1, size=S).astype(np.int32)
    tables = np.zeros((S, mb), np.int32)
    for s in range(S):
        for i in range(int(ctx[s]) // bs + 1):
            tables[s, i] = 1 + s * mb + i
    q = 0.3 * rng.standard_normal((S, 1, N * H)).astype(np.float32)
    kp = 0.3 * rng.standard_normal((S * mb + 1, bs, Nk * H)) \
        .astype(np.float32)
    vp = 0.3 * rng.standard_normal(kp.shape).astype(np.float32)
    return q, kp, vp, tables, ctx


def _idle_and_boundary_case(rng, N, Nk, H, bs=16, mb=40):
    """Five slots: two idle (context 0 on the null block 0, as the
    engine leaves a free slot), one whose context ends on the last row
    of the kernel's first tile, one that begins the second, one drawn."""
    rows = pd.paged_decode_tile_rows(bs, Nk * H, Nk, 4, mb, N // Nk)
    assert 0 < rows < mb * bs
    ctx = np.array([0, rows - 1, rows, rng.integers(1, mb * bs), 0],
                   np.int32)
    q, kp, vp, tables, _ = _paged_case(rng, N, Nk, H, S=5, bs=bs, mb=mb)
    tables[:] = 0
    for s in (1, 2, 3):
        tables[s, :int(ctx[s]) // bs + 1] = 1 + s * mb + np.arange(
            int(ctx[s]) // bs + 1)
    return q, kp, vp, tables, ctx


def test_paged_decode_head_rows_pack_the_members():
    """One row a query head, the block padded once to 16 sublanes: as
    many rows as before where a K/V head has one query head, and the
    grouped cells' 128 / 128 / 64 rows become 16 / 64 / 32."""
    # the GPT widths: one row a head, padded to 16, as before the packing
    for kv_heads, rows in ((12, 16), (16, 16), (32, 32), (40, 48),
                           (96, 96)):
        assert pd.paged_decode_head_rows(kv_heads) == rows
        assert pd.paged_decode_head_rows(kv_heads, 1) == rows
    assert pd.paged_decode_head_rows(2, 8) == 16       # longgen
    assert pd.paged_decode_head_rows(8, 8) == 64       # mixed
    assert pd.paged_decode_head_rows(8, 4) == 32       # many
    assert pd.paged_decode_head_rows(8, 3) == 32       # 24 rows + 8 pad
    assert pd.paged_decode_head_rows(1, 4) == 16


def test_paged_footprint_charges_the_packed_rows(monkeypatch):
    rows, hidden, it = 512, 512, 2
    packed = pd._paged_footprint(rows, hidden, 2, it, 8)
    asked = []
    monkeypatch.setattr(pd, "paged_decode_head_rows",
                        lambda k, g: asked.append((k, g)) or 128)
    padded = pd._paged_footprint(rows, hidden, 2, it, 8)
    assert asked == [(2, 8)]
    # each head row: its accumulator and two statistics, its logits,
    # probabilities and mask, its spread q and its product
    per_row = (hidden + 2 * pd._COLS) * 4 + (3 * rows + 2 * hidden) * 4
    assert padded - packed == (128 - 16) * per_row


def _dense_gqa(q, k, v, n_heads, kv_heads, scale, causal_from=None):
    """q [T, N*H] against k, v [L, Nk*H] by repeated K/V heads."""
    T, L = q.shape[0], k.shape[0]
    H = q.shape[1] // n_heads
    q = q.reshape(T, n_heads, H)
    k = np.repeat(k.reshape(L, kv_heads, H), n_heads // kv_heads, axis=1)
    v = np.repeat(v.reshape(L, kv_heads, H), n_heads // kv_heads, axis=1)
    s = np.einsum("tnh,lnh->ntl", q, k) * scale
    if causal_from is not None:
        s = np.where(np.arange(L)[None, None] <= causal_from
                     + np.arange(T)[None, :, None], s, -1e30)
    p = np.exp(s - s.max(-1, keepdims=True))
    p = p / p.sum(-1, keepdims=True)
    return np.einsum("ntl,lnh->tnh", p, v).reshape(T, n_heads * H)


@pytest.mark.parametrize("use_kernel", [False, True])
@pytest.mark.parametrize("heads", [
    (8, 2, 64), (16, 4, 32), (4, 1, 128),
    # the grouped cells' layouts (longgen, mixed, many) and one whose
    # 24 query heads leave 8 padding rows in the packed block; each with
    # idle slots and contexts that end on a tile's last row or begin one
    (16, 2, 256, "idle"), (64, 8, 128, "idle"), (32, 8, 64, "idle"),
    (24, 8, 32, "idle")])
def test_paged_decode_with_fewer_kv_heads(heads, use_kernel):
    N, Nk, H = heads[:3]
    if len(heads) == 3:
        q, kp, vp, tables, ctx = _paged_case(np.random.default_rng(4), N,
                                             Nk, H)
    else:
        q, kp, vp, tables, ctx = _idle_and_boundary_case(
            np.random.default_rng(7), N, Nk, H)
    got = np.asarray(pd.paged_decode_attention(
        q, kp, vp, tables, ctx, N, use_kernel=use_kernel, kv_heads=Nk,
        scale=0.07))
    for s in range(q.shape[0]):
        n = int(ctx[s]) + 1
        k = kp[tables[s]].reshape(-1, Nk * H)[:n]
        v = vp[tables[s]].reshape(-1, Nk * H)[:n]
        want = _dense_gqa(q[s], k, v, N, Nk, 0.07)
        assert np.abs(got[s] - want).max() < 1e-4


@pytest.mark.parametrize("use_kernel", [False, True])
@pytest.mark.parametrize("heads", [(8, 2, 64), (16, 4, 32), (4, 1, 128)])
def test_prefill_chunk_with_fewer_kv_heads(heads, use_kernel):
    N, Nk, H = heads
    rng = np.random.default_rng(5)
    _, kp, vp, _, _ = _paged_case(rng, N, Nk, H, S=1)
    C, p0, n_real = 32, 40, 20
    table = np.arange(1, 11, dtype=np.int32)
    q = 0.3 * rng.standard_normal((1, C, N * H)).astype(np.float32)
    got = np.asarray(pd.flash_prefill_chunk(
        q, kp, vp, table, np.int32(p0), N, use_kernel=use_kernel,
        n_real=np.int32(n_real), kv_heads=Nk, scale=0.07))[0]
    k = kp[table].reshape(-1, Nk * H)
    v = vp[table].reshape(-1, Nk * H)
    want = _dense_gqa(q[0], k, v, N, Nk, 0.07, causal_from=p0)
    assert np.abs(got[:n_real] - want[:n_real]).max() < 1e-4


@pytest.mark.parametrize("use_kernel", [False, True])
def test_as_many_kv_heads_is_bit_equal_to_leaving_them_out(use_kernel):
    """`kv_heads == n_heads` and the default scale are today's call."""
    N, H = 8, 32
    rng = np.random.default_rng(6)
    q, kp, vp, tables, ctx = _paged_case(rng, N, N, H)
    a = pd.paged_decode_attention(q, kp, vp, tables, ctx, N,
                                  use_kernel=use_kernel)
    b = pd.paged_decode_attention(q, kp, vp, tables, ctx, N,
                                  use_kernel=use_kernel, kv_heads=N,
                                  scale=H ** -0.5)
    assert (np.asarray(a) == np.asarray(b)).all()
    qc = 0.3 * rng.standard_normal((1, 32, N * H)).astype(np.float32)
    args = (qc, kp, vp, tables[0], np.int32(8), N)
    a = pd.flash_prefill_chunk(*args, use_kernel=use_kernel)
    b = pd.flash_prefill_chunk(*args, use_kernel=use_kernel, kv_heads=N,
                               scale=H ** -0.5)
    assert (np.asarray(a) == np.asarray(b)).all()


# -- the cache's two families -------------------------------------------

def test_cache_holds_pages_by_token_and_rows_by_request():
    state = state_kind(((3, 20), "float32"), ((16, 128), "float32"))
    kinds = (state, kv_kind(64), state)
    cache = PagedKVCache(kinds, 5, 8, dtype="float32", request_rows=3)
    assert cache.k[0].shape == (4, 3, 20) and cache.v[0].shape == (4, 16, 128)
    assert cache.k[1].shape == (5, 8, 64) and cache.v[1].shape == (5, 8, 64)
    assert PagedKVCache.block_bytes(kinds, 8, "float32") == 2 * 64 * 8 * 4
    assert PagedKVCache.request_bytes(kinds) == 2 * (60 + 2048) * 4
    assert cache.nbytes == 2 * 5 * 8 * 64 * 4 + 4 * 2 * (60 + 2048) * 4
    fresh = cache.fresh()
    assert [a.shape for a in fresh.arenas()] \
        == [a.shape for a in cache.arenas()]
    assert state.by_request and not kv_kind(64).by_request
    with pytest.raises(ValueError):
        CacheKind("both", widths=(8,), request_rows=(((2,), "float32"),))


def test_row_pool_hands_out_rows_above_the_null_row():
    pool = RowPool(3)
    rows = [pool.take(owner=i) for i in range(3)]
    assert sorted(rows) == [1, 2, 3] and pool.num_live == 3
    with pytest.raises(RuntimeError):
        pool.take()
    pool.give(rows[1])
    assert pool.take() == rows[1]       # last in, first out
    with pytest.raises(ValueError):
        pool.give(0)
    for r in rows:
        pool.give(r)
    pool.assert_quiesced()


# -- the engine ---------------------------------------------------------

@pytest.mark.parametrize("chunk,n_prompt", [(16, 45), (8, 29), (32, 21)])
def test_prefill_in_chunks_then_decode_matches_reference(chunk, n_prompt):
    """Chunks that do not divide the prompt (a padded last chunk) and a
    scan piece (8) that does not divide the chunk's real tokens."""
    rng = np.random.default_rng(7)
    prompt = rng.integers(1, TINY["vocab_size"], n_prompt)
    got, seq, _ = served_logits(engine(chunk=chunk), prompt, 6)
    want = reference_logits(seq)
    assert np.abs(got - want[len(prompt) - 1:]).max() < TOL


def test_tolerance_sees_a_bfloat16_program():
    rng = np.random.default_rng(7)
    prompt = rng.integers(1, TINY["vocab_size"], 45)
    low, seq, _ = served_logits(engine(dtype="bfloat16"), prompt, 6)
    assert np.abs(low - reference_logits(seq)[len(prompt) - 1:]).max() \
        > 10 * TOL


def test_whole_sequence_forward_matches_reference():
    rng = np.random.default_rng(8)
    seq = rng.integers(1, TINY["vocab_size"], 37)
    eng = engine()
    dense = np.asarray(eng.model(seq[None])._value[0])
    assert np.abs(dense - reference_logits(seq)).max() < TOL
    n = sum(int(np.prod(s)) for layer in range(4)
            for _, s, _ in ref.layer_leaves(TINY, layer)) + 96 * 64 + 64
    assert eng.model.num_parameters() == n


def test_padding_rows_leave_the_state_alone():
    """A last chunk of 5 real tokens among 16 leaves the state and the
    convolution's tail of a chunk of exactly those 5 tokens."""
    rng = np.random.default_rng(9)
    prompt = rng.integers(1, TINY["vocab_size"], 21)     # 16 + 5
    _, _, (k_pad, v_pad) = served_logits(engine(chunk=16), prompt, 1)
    model = engine().model
    mixer, x = model.blocks[0].mixer, None
    # the first Mamba layer alone: its state after 21 tokens from the
    # whole-sequence scan
    from paddle_tpu.nn.functional.norm import rms_norm_values
    h = model.embedded(jnp.asarray(prompt))
    x = rms_norm_values(h, model.blocks[0].norm1._value, 1e-5)
    z, xbc, dt = mixer._project(x)
    _, state, tail = mixer._scan(
        z, xbc, dt, jnp.zeros((3, xbc.shape[1])),
        jnp.zeros((16, 128)), 21)
    assert np.abs(np.asarray(v_pad[0][2]) - np.asarray(state)).max() < 1e-5
    assert np.abs(np.asarray(k_pad[0][2]) - np.asarray(tail)).max() < 1e-6
    assert np.abs(np.asarray(tail) - np.asarray(xbc[18:21])).max() == 0.0


def test_reused_row_starts_from_zero():
    """Two requests one after the other take the same row; the second's
    logits are those of a fresh engine."""
    rng = np.random.default_rng(10)
    a = rng.integers(1, TINY["vocab_size"], 30)
    b = rng.integers(1, TINY["vocab_size"], 19)
    eng = engine()
    _, _, (k, v) = served_logits(eng, a, 4, row=2)
    eng.cache.swap(k, v)            # row 2 now holds a's state
    assert float(jnp.abs(v[0][2]).max()) > 0
    got, seq, _ = served_logits(eng, b, 4, row=2)
    assert np.abs(got - reference_logits(seq)[len(b) - 1:]).max() < TOL


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
    seq = list(prompt)
    for _ in range(n_new):
        seq.append(int(np.argmax(reference_logits(seq)[-1])))
    return seq[len(prompt):]


def test_streams_through_submit_match_reference_greedy():
    rng = np.random.default_rng(11)
    prompts = [rng.integers(1, TINY["vocab_size"], n) for n in (23, 9, 40, 17)]
    eng = engine(slots=3)
    before = {n: monitor.get("serving." + n)
              for n in ("state_rows_taken", "state_rows_released")}
    got = _streams(eng, prompts)
    assert got == [_greedy_reference(p, 6) for p in prompts]
    assert eng.rows.num_live == 0 and eng.pool.num_used == 0
    eng.rows.assert_quiesced()
    for name, was in before.items():
        assert monitor.get("serving." + name) - was == 4
    assert monitor.get_gauge("serving.state_rows_live", -1) == 0


def test_step_in_flight_carries_the_state_arenas():
    """The loop with one decode step in flight gives the streams of the
    loop that retires every step before the next."""
    rng = np.random.default_rng(12)
    prompts = [rng.integers(1, TINY["vocab_size"], n) for n in (21, 33, 12)]
    assert _streams(engine(), prompts, 9) \
        == _streams(engine(), prompts, 9, stepwise=True)


def test_same_prefix_twice_is_right_and_nothing_is_looked_up():
    rng = np.random.default_rng(13)
    head = rng.integers(1, TINY["vocab_size"], 24)        # three blocks
    prompts = [np.concatenate([head, rng.integers(1, 96, n)])
               for n in (7, 11)]
    eng = engine(enable_prefix_cache=True)
    assert eng.prefix_index is None
    first = _streams(eng, prompts[:1])
    rest = _streams(eng, prompts[1:])
    assert first + rest == [_greedy_reference(p, 6) for p in prompts]
    stats = eng.prefix_stats()
    assert stats["lookups"] == 0 and stats["hits"] == 0 \
        and stats["blocks_cached"] == 0


def test_preempt_and_replay_gives_the_same_stream():
    """A pool too small for both requests preempts the younger, which
    gives its row back and replays from position 0."""
    rng = np.random.default_rng(14)
    prompts = [rng.integers(1, TINY["vocab_size"], n) for n in (30, 28)]
    want = [_greedy_reference(p, 14) for p in prompts]
    replays = monitor.get("serving.state_replays")
    eng = engine(slots=2, num_blocks=10)      # 9 blocks of 8: 72 positions
    got = _streams(eng, prompts, 14)
    assert eng.sched.preemptions > 0
    assert monitor.get("serving.state_replays") > replays
    assert got == want
    assert eng.rows.num_live == 0


def test_a_step_that_raises_replays_from_zero():
    """After a voided step a recurrent state must not see a position
    twice: the requests go back to the queue and replay."""
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
    replays = monitor.get("serving.state_replays")
    with pytest.raises(RuntimeError):
        eng.step()
    assert eng.rows.num_live == 0 and len(eng.sched.waiting) == 2
    # both had a state, and both states were thrown away
    assert monitor.get("serving.state_replays") - replays == 2
    eng.run_until_idle()
    assert [h.result() for h in hs] == want


def _reference_rows(ids):
    """{layer: the state [d_state, heads * head_dim] the reference keeps
    after `ids`}, as the program lays it out."""
    states = ref.final_states(TINY, SEED, SCALE, [np.asarray(ids)])
    return {layer: np.transpose(s[0], (2, 0, 1)).reshape(s[0].shape[2], -1)
            for layer, s in states.items()}


def test_request_rows_are_the_reference_state_after_the_tokens_taken_in():
    """A live request's rows, with a step in flight: the state after
    exactly the tokens the call names, in prefill and in decode; nothing
    for a request that waits or has finished."""
    rng = np.random.default_rng(16)
    prompts = [rng.integers(1, TINY["vocab_size"], n) for n in (37, 12)]
    eng = engine(chunk=16, slots=1)     # the second request waits
    hs = [eng.submit(p.astype(np.int32), SamplingParams(max_new_tokens=8))
          for p in prompts]
    assert eng.request_rows(hs[1]) is None
    seen = set()
    for _ in range(8):
        eng.step()
        got = eng.request_rows(hs[0])
        if got is None:
            break
        ids, rows = got
        seen.add(len(ids))
        assert list(ids) == (list(prompts[0]) + hs[0]._req.out_tokens)[
            :len(ids)]
        want = _reference_rows(ids)
        assert sorted(rows) == sorted(want) == [0, 1, 3]
        for layer, (tail, state) in rows.items():
            assert tail.shape == (3, TINY["conv_dim"])
            np.testing.assert_allclose(state, want[layer], atol=TOL)
    # a chunk boundary inside the prompt, its end, and decode steps
    assert {16, 32, 37, 38} <= seen
    eng.run_until_idle()
    assert eng.request_rows(hs[0]) is None
    assert [h.result() for h in hs] == [_greedy_reference(p, 8)
                                        for p in prompts]


def test_dispatch_span_names_the_state_rows():
    import paddle_tpu.serving.engine as engine_mod
    eng = engine()
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
    decode = [kw for kw in seen if kw["family"] == "serving_decode"]
    assert decode and all(kw["cache_kind"] == "kv+state" for kw in seen)
    assert all(kw["state_rows"] == 1 for kw in decode)
