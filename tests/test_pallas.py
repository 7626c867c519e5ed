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


def _flat_case(seed, bn, s, h, dtype):
    """q, k, v and an upstream gradient in the kernels' flat [BN, S, H]
    layout, q pre-scaled as the callers hand it over."""
    rng = np.random.default_rng(seed)
    def mk(scale=0.3):
        return jnp.asarray(scale * rng.standard_normal((bn, s, h)), dtype)
    return mk(0.3 / math.sqrt(h) * 8), mk(), mk(), mk()


def _gap(a, b):
    return float(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)).max())


# (S, bq, bk, H, dtype): tiles of 512 and 1,024 reach the sub-blocked
# diagonal (c = 128: 4 and 8 row sub-blocks), a tile of 256 keeps the one
# masked body; bk > bq (r = 2, 4) is the backward's shape beyond 8,192
# positions, where a diagonal tile's rows start j * bq columns into its
# k tile (ADVICE.md r5: no test reached r > 1 at these tiles)
_TRI_CASES = [
    (2048, 1024, 1024, 64, jnp.float32),
    (2048, 1024, 1024, 64, jnp.bfloat16),      # gpt3-125m.train's tiles
    (2048, 1024, 1024, 128, jnp.float32),
    (2048, 1024, 1024, 128, jnp.bfloat16),     # GPT-3 1.3B's heads
    (2048, 512, 512, 64, jnp.float32),
    (2048, 512, 512, 128, jnp.bfloat16),
    (1024, 256, 256, 64, jnp.float32),         # the single masked body
    (2048, 512, 1024, 64, jnp.float32),        # r = 2
    (2048, 512, 1024, 128, jnp.bfloat16),
    (2048, 256, 1024, 64, jnp.float32),        # r = 4
    (3072, 512, 1024, 64, jnp.float32),        # r = 2, three columns
    (1152, 384, 384, 64, jnp.float32),         # three sub-blocks a tile
]
_TRI_PARAMS = [
    pytest.param(s, bq, bk, h, dt,
                 id=f"s{s}-bq{bq}-bk{bk}-h{h}-{jnp.dtype(dt).name}")
    for s, bq, bk, h, dt in _TRI_CASES]


# the forward's triangle grid takes square tiles only
@pytest.mark.parametrize(
    "s,bq,bk,h,dtype",
    [p for p in _TRI_PARAMS if p.values[1] == p.values[2]])
def test_tri_forward_matches_reference(s, bq, bk, h, dtype):
    """(out, lse) of the triangle-grid forward against the naive
    attention it tiles, diagonal tiles walked by sub-blocks."""
    from paddle_tpu.ops import pallas_attention as pa
    qr, kr, vr, _ = _flat_case(21, 2, s, h, dtype)
    out, lse = pa._flash_fwd_tri(qr, kr, vr, bq, bk, s // bq)
    ref_out, ref_lse = pa._ref_fwd_flat(qr, kr, vr, causal=True)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    assert out.dtype == qr.dtype and lse.shape == ref_lse.shape
    assert _gap(out, ref_out) < tol
    assert _gap(lse, ref_lse) < (2e-5 if dtype == jnp.float32 else 2e-2)


@pytest.mark.parametrize("s,bq,bk,h,dtype", _TRI_PARAMS)
def test_tri_backward_matches_reference(s, bq, bk, h, dtype):
    """(dq, dk, dv) of the triangle-grid merged backward from the saved
    lse / delta against the naive backward, r > 1 included."""
    from paddle_tpu.ops import pallas_attention as pa
    qr, kr, vr, gr = _flat_case(22, 2, s, h, dtype)
    out, lse = pa._ref_fwd_flat(qr, kr, vr, causal=True)
    delta = jnp.sum(gr.astype(jnp.float32) * out.astype(jnp.float32), -1)
    delta = jnp.broadcast_to(delta[:, None, :], (2, pa._SUB, s))
    got = pa._flash_bwd_merged_tri(qr, kr, vr, gr, lse, delta,
                                   bq, bk, s // bq)
    ref = pa._ref_bwd_flat(qr, kr, vr, gr, lse, delta, causal=True)
    for name, a, b_ in zip(("dq", "dk", "dv"), got, ref):
        scale = float(jnp.abs(b_.astype(jnp.float32)).max())
        tol = 2e-5 if dtype == jnp.float32 else 3e-2
        assert _gap(a, b_) < tol * max(scale, 1.0), (name, _gap(a, b_))


@pytest.mark.parametrize("c", [256, 512])
def test_tri_kernels_at_other_sub_block_sizes(monkeypatch, c):
    """The walk is right at any sub-block size the policy may come to
    choose, not only at the one it chooses today."""
    from paddle_tpu.ops import pallas_attention as pa
    monkeypatch.setattr(pa, "sub_block_rows", lambda bq, h, itemsize: c)
    s, bq, h = 2048, 1024, 64
    qr, kr, vr, gr = _flat_case(23, 1, s, h, jnp.float32)
    out, lse = pa._flash_fwd_tri(qr, kr, vr, bq, bq, s // bq)
    ref_out, ref_lse = pa._ref_fwd_flat(qr, kr, vr, causal=True)
    assert _gap(out, ref_out) < 2e-5 and _gap(lse, ref_lse) < 2e-5
    delta = jnp.sum(gr * ref_out, -1)
    delta = jnp.broadcast_to(delta[:, None, :], (1, pa._SUB, s))
    for bq_bwd in (1024, 512):            # r = 1 and r = 2
        got = pa._flash_bwd_merged_tri(qr, kr, vr, gr, ref_lse, delta,
                                       bq_bwd, 1024, s // bq_bwd)
        ref = pa._ref_bwd_flat(qr, kr, vr, gr, ref_lse, delta, causal=True)
        for name, a, b_ in zip(("dq", "dk", "dv"), got, ref):
            assert _gap(a, b_) < 2e-5, (bq_bwd, name, _gap(a, b_))


@pytest.mark.parametrize("sq,bq,bk,c,want", [
    (2048, 1024, 1024, 1024, 1.5),      # whole diagonal tiles (before PR 37)
    (2048, 1024, 1024, 512, 1.25),
    (2048, 1024, 1024, 256, 1.125),
    (2048, 1024, 1024, 128, 1.0625),    # gpt3-125m.train
    (8192, 1024, 1024, 256, 1.03125),   # the waste shrinks with the length
    (2048, 512, 512, 256, 1.125),
    (2048, 128, 128, 128, 1.0625),
    (2048, 512, 1024, 512, 1.25),       # r = 2: dead columns right of a
    (2048, 512, 1024, 256, 1.125),      # diagonal tile are not formed
])
def test_causal_work_ratio(sq, bq, bk, c, want):
    from paddle_tpu.ops.pallas_attention import causal_work_ratio
    assert causal_work_ratio(sq, bq, bk, c) == want


def test_causal_work_ratio_counts_what_the_kernels_compute():
    """The ratio's numerator, by brute force over the tiles: a row
    sub-block of a diagonal tile meets the columns up to its own last
    row's, whole tiles below the diagonal all of theirs."""
    from paddle_tpu.ops.pallas_attention import causal_work_ratio
    for sq, bq, bk, c in [(2048, 1024, 1024, 256), (4096, 512, 1024, 256),
                          (3072, 256, 1024, 256), (2048, 512, 512, 128)]:
        elems = 0
        for k0 in range(0, sq, bk):
            for q0 in range(k0, sq, bq):
                if q0 >= k0 + bk:
                    elems += bq * bk
                    continue
                for r0 in range(q0, q0 + bq, c):
                    elems += c * (r0 + c - k0)
        assert causal_work_ratio(sq, bq, bk, c) == elems / (sq * sq / 2)


@pytest.mark.parametrize("bq,h,itemsize,want", [
    (1024, 64, 2, 128),      # gpt3-125m.train: 8 row sub-blocks
    (1024, 128, 2, 128),     # GPT-3 1.3B's heads
    (1024, 64, 4, 128),
    (512, 64, 2, 128),       # the backward's tile beyond 8,192 positions
    (256, 128, 2, 256),      # 256 rows or fewer: the single masked body
    (128, 64, 4, 128),
    (384, 64, 2, 128),       # an explicit tile: 3 sub-blocks
])
def test_sub_block_rows_is_a_table_of_the_shape(bq, h, itemsize, want):
    from paddle_tpu.ops.pallas_attention import sub_block_rows
    c = sub_block_rows(bq, h, itemsize)
    assert c == want and bq % c == 0 and c % 128 == 0


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
