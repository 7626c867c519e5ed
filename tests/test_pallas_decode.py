"""Fused decode-attention kernel (ops/pallas_decode.py): interpret-mode
correctness on CPU (the Mosaic lowering is checked on the chip by
chip_smoke.py's kernel leg). The model's cache-layout switch (flat for the fused
path, 4-D for composed) is covered via init_cache."""
import numpy as np
import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.ops.pallas_decode import decode_attention


def _ref(q4, k4, v4, off):
    B, _, N, H = q4.shape
    L = k4.shape[1]
    lg = np.einsum("bqnh,bknh->bnqk", q4, k4) / np.sqrt(H)
    mask = np.arange(L) <= off
    lg = np.where(mask[None, None, None, :], lg, -1e30)
    p = np.exp(lg - lg.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    return np.einsum("bnqk,bknh->bqnh", p, v4)


def test_decode_attention_matches_reference():
    rs = np.random.RandomState(0)
    for B, L, N, H, off in ((4, 256, 12, 64, 100), (2, 64, 2, 64, 0),
                            (1, 128, 16, 64, 127), (3, 512, 4, 128, 300)):
        q4 = rs.randn(B, 1, N, H).astype(np.float32)
        k4 = rs.randn(B, L, N, H).astype(np.float32)
        v4 = rs.randn(B, L, N, H).astype(np.float32)
        out = decode_attention(
            jnp.asarray(q4.reshape(B, 1, N * H)),
            jnp.asarray(k4.reshape(B, L, N * H)),
            jnp.asarray(v4.reshape(B, L, N * H)),
            jnp.asarray(off, jnp.int32), N)
        ref = _ref(q4, k4, v4, off).reshape(B, 1, N * H)
        np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-5,
                                   atol=2e-5)


def test_decode_attention_bf16_inputs():
    rs = np.random.RandomState(1)
    B, L, N, H = 2, 128, 12, 64
    q4 = rs.randn(B, 1, N, H).astype(np.float32)
    k4 = rs.randn(B, L, N, H).astype(np.float32)
    v4 = rs.randn(B, L, N, H).astype(np.float32)
    out = decode_attention(
        jnp.asarray(q4.reshape(B, 1, N * H), jnp.bfloat16),
        jnp.asarray(k4.reshape(B, L, N * H), jnp.bfloat16),
        jnp.asarray(v4.reshape(B, L, N * H), jnp.bfloat16),
        jnp.asarray(50, jnp.int32), N)
    ref = _ref(q4, k4, v4, 50).reshape(B, 1, N * H)
    rel = np.max(np.abs(np.asarray(out) - ref)) / (np.abs(ref).max()
                                                   + 1e-9)
    assert rel < 3e-2, rel


def test_init_cache_layout_follows_flag():
    """Cache layout must match the decode path: 4-D on CPU (composed),
    flat only when the fused kernel will actually run (TPU + dividing
    shapes) — a reshape between the carried buffer and either consumer
    copies the whole cache every step."""
    from paddle_tpu.models.gpt import GPTConfig, GPTForPretraining
    paddle.seed(0)
    cfg = GPTConfig(vocab_size=256, hidden_size=128, num_layers=2,
                    num_heads=2, max_seq_len=64, dropout=0.0,
                    use_flash_attention=False)
    m = GPTForPretraining(cfg)
    caches = m.gpt.init_cache(2, 64)
    expect_flat = jax.default_backend() == "tpu"
    for k, v in caches:
        if expect_flat:
            assert tuple(k.shape) == (2, 64, 128)
        else:
            assert tuple(k.shape) == (2, 64, 2, 64)


def test_generate_cache_key_includes_decode_flag():
    """Flipping the decode-attention flag must not reuse a trace built
    for the other cache layout."""
    from paddle_tpu.flags import set_flags, get_flag
    from paddle_tpu.models.gpt import GPTConfig, GPTForPretraining
    paddle.seed(0)
    cfg = GPTConfig(vocab_size=256, hidden_size=128, num_layers=2,
                    num_heads=2, max_seq_len=64, dropout=0.0,
                    use_flash_attention=False)
    m = GPTForPretraining(cfg)
    ids = paddle.to_tensor(
        np.random.RandomState(0).randint(0, 256, (2, 8)), "int32")
    old = get_flag("use_pallas_decode_attention")
    try:
        set_flags({"use_pallas_decode_attention": False})
        a, _ = m.generate(ids, max_new_tokens=4)
        set_flags({"use_pallas_decode_attention": True})
        b, _ = m.generate(ids, max_new_tokens=4)
        assert len(m._generate_cache) == 2    # distinct traces
        np.testing.assert_array_equal(a.numpy(), b.numpy())
    finally:
        set_flags({"use_pallas_decode_attention": old})


def test_supported_predicate_gates_tiling():
    from paddle_tpu.ops.pallas_decode import decode_attention_supported
    assert decode_attention_supported(256, 768, 12, 2)       # 125M decode
    assert decode_attention_supported(512, 768, 12, 2)
    assert not decode_attention_supported(255, 768, 12, 2)   # L % 8
    assert not decode_attention_supported(256, 760, 12, 2)   # nh % 128
    assert not decode_attention_supported(256, 768, 200, 2)  # heads cap
    # the kernel tiles L with online softmax (r5), so 13B dims and a
    # 4k-context 1.3B run fused now — the old whole-L VMEM gate is gone
    assert decode_attention_supported(256, 5120, 40, 2)
    assert decode_attention_supported(4096, 2048, 16, 2)
    assert decode_attention_supported(16384, 2048, 16, 2)


def test_decode_attention_tiled_long_cache():
    """Caches long enough to force nl > 1 L-tiles must match the dense
    reference (online-softmax accumulation across tiles), including when
    `off` leaves whole tail tiles fully masked."""
    from paddle_tpu.ops import pallas_decode as pd
    rs = np.random.RandomState(3)
    B, L, N, H = 2, 1024, 4, 64
    nh = N * H
    bl = pd._pick_bl(L, nh, 2)
    # shrink the budget so this shape genuinely tiles in interpret mode
    old = pd._VMEM_BUDGET
    pd._VMEM_BUDGET = pd._per_row_bytes(nh, 4) * 128
    pd._pick_bl.cache_clear()
    try:
        assert pd._pick_bl(L, nh, 4) < L   # really exercising tiling
        for off in (1023, 517, 40):        # full, mid-tile, first-tile
            q4 = rs.randn(B, 1, N, H).astype(np.float32)
            k4 = rs.randn(B, L, N, H).astype(np.float32)
            v4 = rs.randn(B, L, N, H).astype(np.float32)
            out = pd.decode_attention(
                jnp.asarray(q4.reshape(B, 1, nh)),
                jnp.asarray(k4.reshape(B, L, nh)),
                jnp.asarray(v4.reshape(B, L, nh)),
                jnp.asarray(off, jnp.int32), N)
            ref = _ref(q4, k4, v4, off).reshape(B, 1, nh)
            np.testing.assert_allclose(np.asarray(out), ref, rtol=3e-5,
                                       atol=3e-5)
    finally:
        pd._VMEM_BUDGET = old
        pd._pick_bl.cache_clear()
