"""Sequence-parallel attention tests on the 8-virtual-device mesh: ring and
Ulysses must match full (composed) attention in fwd and grads."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu import distributed as dist
from paddle_tpu.distributed import env as dist_env
from paddle_tpu.ops.attention import _composed_attention
from paddle_tpu.ops.ring_attention import (ring_attention_values,
                                           ulysses_attention_values)


@pytest.fixture(autouse=True)
def _clear_mesh():
    yield
    dist_env.clear_mesh()


def _qkv(b=2, s=32, n=4, h=8, seed=0):
    rs = np.random.RandomState(seed)
    mk = lambda: jnp.asarray(rs.randn(b, s, n, h), jnp.float32) * 0.4
    return mk(), mk(), mk()


@pytest.mark.parametrize("causal", [False, True])
def test_ring_matches_full(causal):
    mesh = dist.build_mesh(dp=2, sp=4)
    q, k, v = _qkv()
    out = ring_attention_values(q, k, v, causal=causal, mesh=mesh)
    ref = _composed_attention(q, k, v, causal=causal)
    assert np.allclose(np.asarray(out), np.asarray(ref), atol=1e-5), \
        np.abs(np.asarray(out) - np.asarray(ref)).max()


@pytest.mark.parametrize("causal", [False, True])
def test_ring_grads_match(causal):
    mesh = dist.build_mesh(sp=8)
    q, k, v = _qkv(b=1, s=16, n=2, h=4, seed=1)

    # jitted like every production caller: under jax 0.9.0 an eager
    # shard_map re-lowers the whole ring on each call (35 s here)
    g1 = jax.jit(jax.grad(lambda *a: jnp.sum(
        ring_attention_values(*a, causal=causal, mesh=mesh) ** 2),
        argnums=(0, 1, 2)))(q, k, v)
    g2 = jax.grad(lambda *a: jnp.sum(
        _composed_attention(*a, causal=causal) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        assert np.allclose(np.asarray(a), np.asarray(b), atol=1e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_matches_full(causal):
    mesh = dist.build_mesh(dp=2, sp=4)
    q, k, v = _qkv(n=4)
    out = ulysses_attention_values(q, k, v, causal=causal, mesh=mesh)
    ref = _composed_attention(q, k, v, causal=causal)
    assert np.allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_ulysses_head_divisibility_error():
    mesh = dist.build_mesh(sp=8)
    q, k, v = _qkv(n=4)  # 4 heads, sp=8
    with pytest.raises(ValueError, match="divisible"):
        ulysses_attention_values(q, k, v, mesh=mesh)


def test_gpt_with_ring_attention_trains():
    """Full GPT train step with sequence_parallel='ring' on a dp x sp mesh,
    loss parity with the same model on no mesh."""
    from paddle_tpu.models.gpt import GPTConfig, GPTForPretraining
    from paddle_tpu.nn import functional as F  # noqa: F401

    rs = np.random.RandomState(0)
    ids = rs.randint(0, 128, (4, 32))
    lbl = rs.randint(0, 128, (4, 32))

    def build(seq_par):
        paddle.seed(7)
        cfg = GPTConfig(vocab_size=128, hidden_size=32, num_layers=2,
                        num_heads=4, max_seq_len=32, dropout=0.0,
                        use_flash_attention=False,
                        sequence_parallel=seq_par)
        return GPTForPretraining(cfg)

    m_ref = build(None)
    loss_ref = m_ref.loss(paddle.to_tensor(ids, "int32"),
                          paddle.to_tensor(lbl, "int32")).item()

    mesh = dist.build_mesh(dp=2, sp=4)
    m = build("ring")
    dist.shard_model(m)
    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=m.parameters())
    step = dist.ShardedTrainStep(
        m, lambda a, b: m.loss(a, b), opt, zero_stage=1,
        seq_shard_batch=True)
    loss = step(paddle.to_tensor(ids, "int32"),
                paddle.to_tensor(lbl, "int32"))
    assert np.allclose(loss.item(), loss_ref, rtol=1e-4), \
        (loss.item(), loss_ref)
