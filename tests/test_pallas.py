"""Pallas flash-attention vs composed XLA attention (interpret mode on CPU).
The OpTest-style numeric parity pattern (`tests/unittests/op_test.py:274`):
kernel output and analytic grads vs a dense reference implementation."""
import math

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from paddle_tpu.ops.pallas_attention import flash_attention_fwd
from paddle_tpu.ops.attention import _composed_attention


def _ref(q, k, v, causal):
    return _composed_attention(q, k, v, causal=causal)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_forward(causal):
    rs = np.random.RandomState(0)
    b, s, n, h = 2, 256, 2, 64
    q = jnp.asarray(rs.randn(b, s, n, h), jnp.float32) * 0.3
    k = jnp.asarray(rs.randn(b, s, n, h), jnp.float32) * 0.3
    v = jnp.asarray(rs.randn(b, s, n, h), jnp.float32) * 0.3
    out = flash_attention_fwd(q, k, v, causal)
    ref = _ref(q, k, v, causal)
    assert np.allclose(np.asarray(out), np.asarray(ref), atol=2e-5), \
        np.abs(np.asarray(out) - np.asarray(ref)).max()


@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_grads(causal):
    rs = np.random.RandomState(1)
    b, s, n, h = 1, 256, 2, 64
    q = jnp.asarray(rs.randn(b, s, n, h), jnp.float32) * 0.3
    k = jnp.asarray(rs.randn(b, s, n, h), jnp.float32) * 0.3
    v = jnp.asarray(rs.randn(b, s, n, h), jnp.float32) * 0.3

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention_fwd(q, k, v, causal) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(_ref(q, k, v, causal) ** 2)

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g1, g2):
        assert np.allclose(np.asarray(a), np.asarray(b_), atol=5e-4), \
            np.abs(np.asarray(a) - np.asarray(b_)).max()


def test_flash_attention_cross_lengths():
    """kv longer than q (decode-with-prefix shape)."""
    rs = np.random.RandomState(2)
    b, sq, sk, n, h = 1, 128, 256, 2, 64
    q = jnp.asarray(rs.randn(b, sq, n, h), jnp.float32) * 0.3
    k = jnp.asarray(rs.randn(b, sk, n, h), jnp.float32) * 0.3
    v = jnp.asarray(rs.randn(b, sk, n, h), jnp.float32) * 0.3
    out = flash_attention_fwd(q, k, v, True)
    ref = _ref(q, k, v, causal=True)
    assert np.allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_non_block_multiple_seq():
    """Seq lengths that are multiples of 128 but not of the 512 default
    block must still tile exactly (regression: silent truncation)."""
    rs = np.random.RandomState(5)
    b, s, n, h = 1, 1152, 2, 64   # 1152 = 9 * 128
    q = jnp.asarray(rs.randn(b, s, n, h), jnp.float32) * 0.3
    k = jnp.asarray(rs.randn(b, s, n, h), jnp.float32) * 0.3
    v = jnp.asarray(rs.randn(b, s, n, h), jnp.float32) * 0.3
    out = flash_attention_fwd(q, k, v, True)
    ref = _ref(q, k, v, True)
    assert np.allclose(np.asarray(out), np.asarray(ref), atol=2e-5), \
        np.abs(np.asarray(out) - np.asarray(ref)).max()
    g1 = jax.grad(lambda *a: jnp.sum(flash_attention_fwd(*a, True) ** 2),
                  (0, 1, 2))(q, k, v)
    g2 = jax.grad(lambda *a: jnp.sum(_ref(*a, True) ** 2), (0, 1, 2))(q, k, v)
    for a, b_ in zip(g1, g2):
        assert np.allclose(np.asarray(a), np.asarray(b_), atol=5e-4)


def test_triangle_grid_backward_rect_blocks():
    """Causal grads with EXPLICIT block_q=128, block_k=512 (r = bk/bq = 4):
    exercises the column-major _tri_bwd_decode at r>1 and the per-column
    dq-flush path of the merged triangle-grid backward, which the default
    block policy never reaches at test sizes (ADVICE.md r5: r>1 is the
    production config for sq>8192 but had no coverage)."""
    rs = np.random.RandomState(7)
    b, s, n, h = 1, 1024, 2, 64
    q = jnp.asarray(rs.randn(b, s, n, h), jnp.float32) * 0.3
    k = jnp.asarray(rs.randn(b, s, n, h), jnp.float32) * 0.3
    v = jnp.asarray(rs.randn(b, s, n, h), jnp.float32) * 0.3

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention_fwd(
            q, k, v, True, None, 128, 512) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(_ref(q, k, v, True) ** 2)

    out = flash_attention_fwd(q, k, v, True, None, 128, 512)
    ref = _ref(q, k, v, True)
    assert np.allclose(np.asarray(out), np.asarray(ref), atol=2e-5), \
        np.abs(np.asarray(out) - np.asarray(ref)).max()
    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for name, a, b_ in zip("qkv", g1, g2):
        assert np.allclose(np.asarray(a), np.asarray(b_), atol=5e-4), \
            (name, np.abs(np.asarray(a) - np.asarray(b_)).max())


def test_triangle_grid_backward_long_context_default_blocks():
    """Causal grads with EXPLICIT block_q=512, block_k=1024 (r = bk/bq
    = 2): the EXACT block shape _resolve_blocks selects for the >=128k
    long-context backward (sq > 8192 clamps bq to 512, bk stays 1024)
    — the config GPTConfig.gpt3_1_3b_128k's local flash attention and
    the ringattn_128k bench run on TPU. The PR-1 parity test pins only
    bq=128/bk=512; this covers the long-context default so the r=2
    column-major decode and its dq flush can't regress unobserved
    (ADVICE.md r5 debt)."""
    rs = np.random.RandomState(11)
    b, s, n, h = 1, 2048, 2, 64    # 4 q-blocks x 2 k-blocks at r=2
    q = jnp.asarray(rs.randn(b, s, n, h), jnp.float32) * 0.3
    k = jnp.asarray(rs.randn(b, s, n, h), jnp.float32) * 0.3
    v = jnp.asarray(rs.randn(b, s, n, h), jnp.float32) * 0.3

    out = flash_attention_fwd(q, k, v, True, None, 512, 1024)
    ref = _ref(q, k, v, True)
    assert np.allclose(np.asarray(out), np.asarray(ref), atol=2e-5), \
        np.abs(np.asarray(out) - np.asarray(ref)).max()
    g1 = jax.grad(lambda *a: jnp.sum(flash_attention_fwd(
        *a, True, None, 512, 1024) ** 2), argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(lambda *a: jnp.sum(_ref(*a, True) ** 2),
                  argnums=(0, 1, 2))(q, k, v)
    for name, a, b_ in zip("qkv", g1, g2):
        assert np.allclose(np.asarray(a), np.asarray(b_), atol=5e-4), \
            (name, np.abs(np.asarray(a) - np.asarray(b_)).max())


def test_fused_add_layer_norm_matches_composed():
    """Pallas fused residual+LN (interpret on CPU via the composed-path
    equivalence + direct kernel run) matches LN(x+res) fwd and grads."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import pallas_layernorm as pln

    rs = np.random.RandomState(0)
    rows, d = 256, 128
    x = jnp.asarray(rs.randn(rows, d), jnp.float32)
    res = jnp.asarray(rs.randn(rows, d), jnp.float32)
    w = jnp.asarray(rs.rand(d) + 0.5, jnp.float32)
    b = jnp.asarray(rs.randn(d), jnp.float32)

    def composed(xx, rr, ww, bb):
        s = xx + rr
        mean = jnp.mean(s, -1, keepdims=True)
        var = jnp.mean((s - mean) ** 2, -1, keepdims=True)
        return (s - mean) * jax.lax.rsqrt(var + 1e-5) * ww + bb

    # interpret-mode run of the actual kernel
    from jax.experimental import pallas as pl
    import functools as ft
    out, ssum, rstd = pl.pallas_call(
        ft.partial(pln._fwd_kernel, eps=1e-5),
        grid=(1,),
        in_specs=[pl.BlockSpec((rows, d), lambda i: (i, 0)),
                  pl.BlockSpec((rows, d), lambda i: (i, 0)),
                  pl.BlockSpec((d,), lambda i: (0,)),
                  pl.BlockSpec((d,), lambda i: (0,))],
        out_specs=[pl.BlockSpec((rows, d), lambda i: (i, 0)),
                   pl.BlockSpec((rows, d), lambda i: (i, 0)),
                   pl.BlockSpec((rows, 1), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((rows, d), jnp.float32),
                   jax.ShapeDtypeStruct((rows, d), jnp.float32),
                   jax.ShapeDtypeStruct((rows, 1), jnp.float32)],
        interpret=True,
    )(x, res, w, b)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(composed(x, res, w, b)),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(ssum), np.asarray(x + res),
                               rtol=1e-6)

    # custom-vjp backward vs jax.grad of the composed fn (the vjp reuses
    # the saved sum, so run it against the composed loss directly)
    def loss_c(xx, rr, ww, bb):
        return jnp.sum(composed(xx, rr, ww, bb) ** 2)

    gc = jax.grad(loss_c, argnums=(0, 1, 2, 3))(x, res, w, b)
    out_c = composed(x, res, w, b)
    gd = 2 * out_c
    dx, dres, dw, db = pln._vjp_bwd(1e-5, (x + res, (1.0 / jnp.sqrt(
        jnp.var(x + res, -1, keepdims=True) + 1e-5)), w), gd)
    np.testing.assert_allclose(np.asarray(dx), np.asarray(gc[0]),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(dw), np.asarray(gc[2]),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(db), np.asarray(gc[3]),
                               rtol=2e-4, atol=2e-4)


def test_add_layer_norm_dispatcher_cpu_path():
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas_layernorm import add_layer_norm
    rs = np.random.RandomState(1)
    x = jnp.asarray(rs.randn(8, 16), jnp.float32)
    r = jnp.asarray(rs.randn(8, 16), jnp.float32)
    w = jnp.ones((16,), jnp.float32)
    b = jnp.zeros((16,), jnp.float32)
    out = add_layer_norm(x, r, w, b)        # CPU: composed path
    s = np.asarray(x + r)
    ref = (s - s.mean(-1, keepdims=True)) / np.sqrt(
        s.var(-1, keepdims=True) + 1e-5)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("sq,block_q,block_k,for_bwd,want", [
    (512, None, None, False, (1024, 1024)),
    (8192, None, None, True, (1024, 1024)),     # the backward's last full bq
    (16384, None, None, False, (1024, 1024)),   # the forward has no cap
    (16384, None, None, True, (512, 1024)),     # dq accumulator caps bq
    (16384, 2048, None, True, (2048, 1024)),    # an explicit block wins...
    (1024, None, 512, False, (1024, 512)),      # ...each on its own
])
def test_block_policy_is_a_table_of_the_shape(sq, block_q, block_k,
                                              for_bwd, want):
    from paddle_tpu.ops.pallas_attention import _resolve_blocks
    assert _resolve_blocks(sq, block_q, block_k, for_bwd) == want
