"""Qwen3-Next through the serving engine at toy widths on the CPU (two
periods L L L F, 16 experts of which 4 are held; d 32): the delta-rule
states by request beside the paged K/V, the chunked form and the
one-token state step, gated attention with partial rotary, the
renormalised softmax router and the gated shared expert, held to the
plain reference of benchmark/reference/qwen3_next.py (float32, the
recurrence itself, no cache)."""
import os
import sys
from types import SimpleNamespace

import numpy as np
import pytest
import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.drivers.serve_qwen3next import (  # noqa: E402
    seeded_program_model)
from benchmark.reference import qwen3_next as ref                # noqa: E402
from paddle_tpu import monitor                                   # noqa: E402
from paddle_tpu.models.blocks import (gated_rms_norm,            # noqa: E402
                                      zero_centred_rms_norm)
from paddle_tpu.models.qwen3_next import ExpertLayer             # noqa: E402
from paddle_tpu.moe.serving import route_group_limited           # noqa: E402
from paddle_tpu.ops import pallas_gdn as gdn                     # noqa: E402
from paddle_tpu.ops.rotary import (apply_rotary, rotary_cos_sin,  # noqa: E402
                                   yarn_inv_freq)
from paddle_tpu.serving import (EngineConfig, SamplingParams,    # noqa: E402
                                ServingEngine)
from paddle_tpu.serving.kv_cache import (PagedKVCache, kv_kind,  # noqa: E402
                                         state_kind)

TINY = ref.sizes({
    "vocab_size": 96, "hidden_size": 32, "num_hidden_layers": 8,
    "num_layers": 8, "full_attention_interval": 4,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "partial_rotary_factor": 0.25, "rope_theta": 10000000,
    "linear_num_key_heads": 2, "linear_num_value_heads": 4,
    "linear_key_head_dim": 8, "linear_value_head_dim": 8,
    "linear_conv_kernel_dim": 4, "moe_intermediate_size": 16,
    "shared_expert_intermediate_size": 24, "num_experts_per_tok": 4,
    "norm_topk_prob": True, "rms_norm_eps": 1e-6, "initializer_range": 0.2,
    "deployment": {"router_experts": 16, "held_experts": [0, 4]}})
SCALE = {"block_scale": 1.0}
SEED = 2 ** 31 + 29
# float32 program against the float32 reference: the chunked form sums
# in another order than the recurrence, which at logits of order 1
# leaves 1e-5; a bfloat16 program leaves 1e-2 and more
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


# -- prefill in chunks, then decode, through state rows and pages ---------

@pytest.mark.parametrize("n,chunk", [
    (5, 16),        # one partial chunk
    (16, 16),       # one whole chunk
    (37, 16),       # two whole and a partial: the state carried twice
    (45, 12),       # chunks that are no multiple of 8
    (30, 40),       # the whole prompt in one padded chunk
])
def test_chunked_prefill_then_decode_is_the_full_forward(n, chunk):
    rng = np.random.default_rng(n)
    prompt = rng.integers(1, TINY["vocab_size"], n)
    got, seq, _ = served_logits(engine(chunk=chunk), prompt, 12)
    assert np.abs(got - reference_logits(seq)[n - 1:]).max() < TOL


def test_request_rows_are_the_references_states():
    """What a request keeps after its prompt and some decode steps: the
    convolution's last three inputs and the states the reference's
    recurrence reaches over the same tokens."""
    rng = np.random.default_rng(3)
    prompt = rng.integers(1, TINY["vocab_size"], 29)
    eng = engine(chunk=16)
    _, seq, (k, v) = served_logits(eng, prompt, 6, row=2)
    # every token of `seq` went through a step
    want = ref.final_states(TINY, SEED, SCALE, [np.asarray(seq)])
    linear = [i for i, t in enumerate(TINY["layer_types"])
              if t == "linear_attention"]
    assert sorted(want) == linear
    for layer in linear:
        got = np.asarray(v[layer][2])                   # [heads, K, V]
        assert np.abs(got - want[layer][0]).max() < 1e-4
        assert np.abs(np.asarray(v[layer][1])).max() == 0   # untouched row
        assert k[layer].shape == (4, 3, 2 * 16 + 32)


def test_a_reused_row_that_held_a_longer_request_changes_nothing():
    rng = np.random.default_rng(7)
    a = rng.integers(1, TINY["vocab_size"], 43)
    b = rng.integers(1, TINY["vocab_size"], 5)
    eng = engine()
    _, _, (k, v) = served_logits(eng, a, 4, row=2)
    eng.cache.swap(k, v)            # row 2 now holds a's state and tail
    assert float(jnp.abs(v[0][2]).max()) > 0
    got, seq, _ = served_logits(eng, b, 10, row=2)
    assert np.abs(got - reference_logits(seq)[len(b) - 1:]).max() < TOL


# -- the kernels against the recurrence ----------------------------------

def _recurrence(q, k, v, g, beta, s0):
    """The delta rule in float64, a token at a time."""
    r = g.shape[1] // k.shape[1]
    q = np.repeat(np.asarray(q, np.float64), r, 1)
    k = np.repeat(np.asarray(k, np.float64), r, 1)
    S, ys = np.asarray(s0, np.float64).copy(), []
    for t in range(g.shape[0]):
        S = S * np.exp(g[t])[:, None, None]
        u = np.einsum("hkv,hk->hv", S, k[t])
        S = S + k[t][:, :, None] * (beta[t][:, None] * (v[t] - u))[:, None]
        ys.append(np.einsum("hkv,hk->hv", S, q[t]))
    return np.stack(ys), S


def _sequence(rng, T, H=4, Hk=2, K=128, V=128):
    l2 = lambda x: x / np.sqrt((x * x).sum(-1, keepdims=True) + 1e-6)
    q = (l2(rng.standard_normal((T, Hk, K))) * K ** -0.5).astype(np.float32)
    k = l2(rng.standard_normal((T, Hk, K))).astype(np.float32)
    v = rng.standard_normal((T, H, V)).astype(np.float32)
    g = -rng.uniform(0.0, 0.3, (T, H)).astype(np.float32)
    beta = rng.uniform(0.1, 0.9, (T, H)).astype(np.float32)
    return q, k, v, g, beta


@pytest.mark.parametrize("use_kernel", [False, True])
def test_chunks_at_random_offsets_are_the_recurrence(use_kernel):
    """A sequence cut into chunks of 128 at a random start, the state
    carried from one chunk to the next, the last chunk partial (padding
    with g = beta = 0): outputs and final state are the recurrence's."""
    rng = np.random.default_rng(11)
    T, C = 300, 128
    q, k, v, g, beta = _sequence(rng, T)
    s0 = rng.standard_normal((4, 128, 128)).astype(np.float32)
    want_y, want_s = _recurrence(q, k, v, g, beta, s0)
    state, ys = s0, []
    for at in (0, 77, 205):         # chunks of 77, 128 and 95
        n = min(C, T - at, 128 if at else 77)
        pad = lambda a: np.concatenate([a[at:at + n], np.zeros(
            (C - n,) + a.shape[1:], a.dtype)])
        y, state = gdn.gdn_chunk(*(pad(a) for a in (q, k, v, g, beta)),
                                 state, np.int32(n), sub=64,
                                 use_kernel=use_kernel)
        ys.append(np.asarray(y)[:n])
    assert sum(len(y) for y in ys) == T
    assert np.abs(np.concatenate(ys) - want_y).max() < 2e-4
    assert np.abs(np.asarray(state) - want_s).max() < 2e-4


def test_dead_sub_chunks_write_zeros_and_keep_the_state():
    rng = np.random.default_rng(12)
    q, k, v, g, beta = _sequence(rng, 128)
    g[40:], beta[40:] = 0.0, 0.0
    s0 = np.zeros((4, 128, 128), np.float32)
    y, s = gdn.gdn_chunk(q, k, v, g, beta, s0, np.int32(40), sub=32,
                         use_kernel=True)
    want_y, want_s = _recurrence(q[:40], k[:40], v[:40], g[:40], beta[:40],
                                 s0)
    assert np.abs(np.asarray(y)[:40] - want_y).max() < 2e-4
    assert not np.asarray(y)[64:].any()
    assert np.abs(np.asarray(s) - want_s).max() < 2e-4


@pytest.mark.parametrize("use_kernel", [False, True])
def test_state_step_is_one_step_of_the_recurrence(use_kernel):
    args, _ = gdn._state_step_example(np.random.default_rng(3))
    state, rows, live, q, k, v, g, beta = args
    new, y = gdn.gdn_state_step(*args, use_kernel=use_kernel)
    new, y = np.asarray(new), np.asarray(y)
    for s, (row, alive) in enumerate(zip(rows, live)):
        if not alive:
            assert not y[s].any()
            continue
        want_y, want_s = _recurrence(q[s:s + 1], k[s:s + 1], v[s:s + 1],
                                     g[s:s + 1], beta[s:s + 1], state[row])
        assert np.abs(new[row] - want_s).max() < 1e-5
        assert np.abs(y[s] - want_y[0]).max() < 1e-5
    # a slot that holds no request writes nothing, the null row included
    untouched = [r for r in range(state.shape[0])
                 if r not in rows[live]]
    assert (new[untouched] == state[untouched]).all()


def test_dead_slots_before_any_live_one_leave_the_null_row():
    args, _ = gdn._state_step_example(np.random.default_rng(4))
    state, rows, live, *rest = args
    rows, live = np.asarray([0, 0, 3], np.int32), np.asarray(
        [False, False, True])
    for use_kernel in (False, True):
        new, _ = gdn.gdn_state_step(state, rows, live, *rest,
                                    use_kernel=use_kernel)
        assert (np.asarray(new)[[0, 1, 2, 4]] == state[[0, 1, 2, 4]]).all()


def test_registry_holds_both_kernels_with_fallbacks():
    from paddle_tpu.ops.kernel_registry import registered_kernels
    reg = registered_kernels()
    for name in ("gdn_state_step", "gdn_chunk"):
        assert name in reg and reg.get(name).fallback is not None


# -- the layers by hand ---------------------------------------------------

def test_partial_rotary_turns_the_first_dimensions_alone():
    rng = np.random.default_rng(6)
    T, N, H, r, theta = 5, 3, 16, 4, 1e7
    x = rng.standard_normal((T, N, H)).astype(np.float32)
    pos = np.array([0, 1, 7, 130, 12000], np.int32)
    cos, sin = rotary_cos_sin(pos, yarn_inv_freq(r, theta))
    got = np.asarray(apply_rotary(x, cos[:, None], sin[:, None],
                                  interleaved=False))
    # dimension i < r/2 turns with dimension i + r/2 by pos * theta^(-2i/r)
    z = x[..., :r // 2] + 1j * x[..., r // 2:r]
    angle = pos[:, None] * theta ** (-np.arange(0, r, 2) / r)
    z = z * np.exp(1j * angle)[:, None, :]
    np.testing.assert_allclose(got[..., :r], np.concatenate(
        [z.real, z.imag], axis=-1), atol=2e-4)
    assert (got[..., r:] == x[..., r:]).all()


def test_rotary_over_the_whole_head_is_unchanged():
    """Tables as wide as the head trace the program the DeepSeek-V2 and
    K-EXAONE calls traced before partial rotary."""
    def before(x, cos, sin, interleaved=True):
        f = x.astype(jnp.float32)
        if interleaved:
            f = jnp.concatenate([f[..., 0::2], f[..., 1::2]], axis=-1)
        half = f.shape[-1] // 2
        rotated = jnp.concatenate([-f[..., half:], f[..., :half]], axis=-1)
        return (f * cos + rotated * sin).astype(x.dtype)

    rng = np.random.default_rng(7)
    x = rng.standard_normal((4, 2, 8)).astype(np.float32)
    cos, sin = rotary_cos_sin(np.arange(4, dtype=np.int32),
                              yarn_inv_freq(8, 1e6))
    for interleaved in (False, True):
        trace = lambda fn: str(jax.make_jaxpr(
            lambda a, c, s: fn(a, c, s, interleaved))(
                x, cos[:, None], sin[:, None]))
        assert trace(apply_rotary) == trace(before)


def test_norms_by_hand():
    rng = np.random.default_rng(8)
    x = rng.standard_normal((3, 8)).astype(np.float32)
    w = 0.1 * rng.standard_normal((8,)).astype(np.float32)
    z = rng.standard_normal((3, 8)).astype(np.float32)
    rms = np.sqrt((x * x).mean(-1, keepdims=True) + 1e-6)
    np.testing.assert_allclose(zero_centred_rms_norm(x, w, 1e-6),
                               x / rms * (1 + w), rtol=1e-5)
    silu = z / (1 + np.exp(-z))
    # the norm first, then the gate: not RMSNorm(x * silu(z)) * w
    np.testing.assert_allclose(gated_rms_norm(x, z, 1 + w, 1e-6),
                               x / rms * (1 + w) * silu, rtol=1e-5)
    f = x * silu
    other = f / np.sqrt((f * f).mean(-1, keepdims=True) + 1e-6) * (1 + w)
    assert np.abs(np.asarray(gated_rms_norm(x, z, 1 + w, 1e-6))
                  - other).max() > 0.1


def test_output_gate_and_rotary_of_the_full_layer():
    """A full layer (q, k normed, then rotated over a quarter of a head;
    the output times sigmoid(gate) before W_o) is the reference's, and
    a gate of 0 halves the output."""
    rng = np.random.default_rng(9)
    attn = model().blocks[3].mixer
    x = jnp.asarray(rng.standard_normal((21, 32)).astype(np.float32))
    want = ref._attention(TINY, ref.layer_weights(TINY, SEED, 3, SCALE), x,
                          "f32")
    assert np.abs(np.asarray(attn.dense(x)) - np.asarray(want)).max() < 1e-5
    q, gate = attn.project(x, jnp.arange(21, dtype=jnp.int32))[:2]
    assert q.shape == gate.shape == (21, 4 * 16)
    o = jnp.asarray(rng.standard_normal((5, 64)).astype(np.float32))
    np.testing.assert_allclose(
        attn._out(o, jnp.zeros_like(o), x), 0.5 * np.asarray(o)
        @ np.asarray(attn.o._value), rtol=1e-5, atol=1e-6)


def route(x, w, k):
    """The program's router, top `k` of the columns of `w`."""
    stub = SimpleNamespace(
        c=SimpleNamespace(num_experts_per_tok=k, norm_topk_prob=True),
        router=SimpleNamespace(_value=jnp.asarray(w)))
    return ExpertLayer.route(stub, jnp.asarray(x))


def test_router_by_hand_and_the_default_does_not_renormalise():
    """The softmax over every expert, its top k renormalised
    (`norm_topk_prob`); DeepSeek-V2's router, which the experts share
    with it, keeps the chosen p as they are."""
    x = np.array([[1.0, 0.0], [0.0, 1.0]], np.float32)
    w = np.array([[2.0, 1.0, 0.9, -1.0], [0.0, 0.1, 0.2, 0.3]], np.float32)
    p = np.exp(x @ w)
    p /= p.sum(-1, keepdims=True)
    weights, experts = route(x, w, 2)
    assert experts.dtype == jnp.int32
    assert [sorted(e) for e in np.asarray(experts)] == [[0, 1], [2, 3]]
    top = np.sort(p[0])[::-1][:2]
    np.testing.assert_allclose(np.sort(weights[0])[::-1], top / top.sum(),
                               rtol=1e-6)
    raw, same = route_group_limited(x, w, 1, 1, 2)
    np.testing.assert_allclose(np.sort(raw[0])[::-1], top, rtol=1e-6)
    assert (np.asarray(same) == np.asarray(experts)).all()


def test_router_is_the_references():
    rng = np.random.default_rng(3)
    x = rng.standard_normal((400, 32)).astype(np.float32)
    leaves = [n for n, _, _ in ref.layer_leaves(TINY, 1)]
    w = ref.layer_leaf(TINY, SEED, 1, leaves.index("moe.router"))
    assert w.shape == (32, 16)
    layer = model().blocks[1].moe
    weights, experts = layer.route(jnp.asarray(x))
    want_w, want_e, margin = ref.route(TINY, jnp.asarray(x), w)
    sure = np.asarray(margin) > 1e-5
    assert sure.mean() > 0.9
    assert np.array_equal(np.asarray(experts)[sure], np.asarray(want_e)[sure])
    np.testing.assert_allclose(np.asarray(weights)[sure],
                               np.asarray(want_w)[sure], atol=1e-6)


def test_the_shares_add_up_to_the_uncut_layer():
    """The four shares' routed parts plus the gated shared expert once
    are the layer with all 16 experts, which is the reference's."""
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.standard_normal((24, 32)).astype(np.float32))
    whole = dict(TINY, held_experts=(0, 16))
    layer = model(whole).blocks[1].moe
    want, stats = layer.run(x)
    assert float(stats["moe_pairs_held"]) == 24 * 4
    gate = jax.nn.sigmoid(x @ layer.shared.shared_gate._value)
    shared = layer.shared.run(x)
    plain = super(type(layer.shared), layer.shared).run(x)
    assert np.abs(np.asarray(shared - gate * plain)).max() < 1e-6
    total = shared
    for first in range(0, 16, 4):
        part = model(dict(TINY, held_experts=(first, 4))).blocks[1].moe
        total = total + part.run(x)[0] - shared
    assert np.abs(np.asarray(total - want)).max() < 1e-5
    # and the uncut model is the uncut reference
    seq = rng.integers(1, TINY["vocab_size"], 21)
    got = np.asarray(model(whole).forward(seq[None])._value)[0]
    assert np.abs(got - reference_logits(seq, whole)).max() < TOL


# -- the cache kinds ------------------------------------------------------

def test_a_linear_layer_costs_a_request_a_state_and_a_block_nothing():
    """The published widths: 12 layers L L L F, K/V rows of 512
    bfloat16 numbers in the full layers, a float32 state of 32 x 128 x
    128 and a convolution tail of 3 x 8,192 in the linear ones."""
    linear = state_kind(((3, 8192), "bfloat16"), ((32, 128, 128), "float32"))
    kinds = ([linear] * 3 + [kv_kind(512)]) * 3
    assert PagedKVCache.block_bytes(kinds, 16, "bfloat16") == 16 * 6144
    assert linear.request_bytes == 3 * 8192 * 2 + 32 * 128 * 128 * 4 \
        == 2_146_304
    assert PagedKVCache.request_bytes(kinds) == 9 * 2_146_304
    eng = engine()
    assert [k.name for k in eng.cache_kinds] == (["state"] * 3 + ["kv"]) * 2
    assert eng.cache.v[0].shape == (eng.cfg.max_slots + 1, 4, 8, 8)
    # the states are float32 whatever the model's dtype, as the
    # configuration states
    from paddle_tpu.models.qwen3_next import GatedDeltaNet, Qwen3NextConfig
    c = Qwen3NextConfig(hidden_size=32, linear_num_key_heads=2,
                        linear_num_value_heads=4, linear_key_head_dim=8,
                        linear_value_head_dim=8, dtype="bfloat16")
    kind = GatedDeltaNet(lambda n, shape, k: jnp.zeros(shape), "",
                         c).cache_kind()
    assert [d for _, d in kind.request_rows] == [jnp.bfloat16, jnp.float32]
    assert eng.cache.k[0].shape == (eng.cfg.max_slots + 1, 3, 2 * 16 + 32)
    assert eng.prefix_index is None and eng.rows.names == ("state",)


# -- through submit -------------------------------------------------------

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
    one length so that the reference compiles once: the layers are
    causal, no position sees the padding behind it."""
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
              for n in ("state_rows_taken", "state_rows_released")}
    got = _streams(eng, prompts, 10)
    assert got == [_greedy_reference(p, 10) for p in prompts]
    assert eng.rows.num_live == 0 and eng.pool.num_used == 0
    eng.rows.assert_quiesced()
    grew = {n: monitor.get("serving." + n) - was
            for n, was in before.items()}
    assert grew == {"state_rows_taken": 4, "state_rows_released": 4}
    assert monitor.get_gauge("serving.state_rows_live", -1) == 0


def test_step_in_flight_carries_the_states():
    """The loop with one decode step in flight gives the streams of the
    loop that retires every step before the next."""
    rng = np.random.default_rng(12)
    prompts = [rng.integers(1, TINY["vocab_size"], n) for n in (21, 33, 12)]
    assert _streams(engine(), prompts, 12) \
        == _streams(engine(), prompts, 12, stepwise=True)


def test_preempt_and_replay_gives_the_same_stream():
    """A pool too small for both requests preempts the younger, which
    gives its row back and replays from position 0."""
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
    """A state that took in a dropped step's position must not see it
    twice: the requests replay from position 0."""
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


def test_request_rows_of_a_live_request_are_its_states():
    """`ServingEngine.request_rows` of a request in decode: the ids it
    has taken in and, in every linear layer, (tail, state) equal to the
    reference's state over those ids."""
    rng = np.random.default_rng(16)
    eng = engine()
    h = eng.submit(rng.integers(1, TINY["vocab_size"], 27).astype(np.int32),
                   SamplingParams(max_new_tokens=20))
    for _ in range(12):
        eng.step()
    ids, rows = eng.request_rows(h)
    assert len(ids) >= 27 and sorted(rows) == [0, 1, 2, 4, 5, 6]
    want = ref.final_states(TINY, SEED, SCALE, [ids])
    for layer, (tail, state) in rows.items():
        assert tail.shape == (3, 64) and state.shape == (4, 8, 8)
        assert np.abs(np.asarray(state) - want[layer][0]).max() < 1e-4
    eng.run_until_idle()


def test_dispatch_span_names_the_state_rows():
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
    assert seen and all(kw["cache_kind"] == "kv+state" for kw in seen)
    assert all(kw["state_rows"] >= 1 for kw in seen)
