"""Blockwise (flash) attention as Pallas TPU kernels.

TPU-native replacement for the reference's fused attention CUDA kernels
(`operators/fused/fused_attention_op.cu`, `fmha_ref.h`), which materialize
the full O(s^2) probability matrix in HBM. Here the softmax is computed
online per [block_q, block_k] tile held in VMEM, so HBM traffic is O(s) and
the two matmuls per tile run back-to-back on the MXU.

Layout: inputs are paddle-convention [batch, seq, heads, head_dim] (BSNH);
kernels internally operate on [batch*heads, seq, head_dim]. Forward saves
the per-row logsumexp; backward recomputes probabilities per tile (the
standard flash-attention recomputation trade) with three Pallas kernels
(dkdv, dq) wired up through jax.custom_vjp so the eager tape's jax.vjp
flows through it unchanged.
"""
import functools
import math

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .kernel_registry import out_struct, register_kernel

DEFAULT_BLOCK_Q = None   # None -> per-shape policy (_resolve_blocks)
DEFAULT_BLOCK_K = None


def _resolve_blocks(sq, block_q, block_k, for_bwd=False):
    """Block policy, a function of the shape alone: bk=1024 at every
    shape (512..16384, D 64/128). Measured on the v5e under jax 0.9.0
    (PR 37, kernels alone, bf16, diagonal tiles walked by
    sub_block_rows; PERF.md section 6): at 144 x 2,048 x 64 tiles of
    1,024 read 2.30 ms forward / 3.69 backward, and tiles of 512, under
    a first form of the walk that read 2.66 / 4.04 at 1,024, 3.90 /
    4.72 (10 tiles a head instead of 3: their grid steps cost more than
    the masked scores they save); at D=128 tiles of 1,024 read 0.76 /
    1.11 ms at 64 x 2,048 and 2.29 / 3.99 at 16 x 8,192. The backward's
    whole-slice dq VMEM accumulator caps bq at 512 beyond sq=8192 (the
    constraint is governed by sq, not sk; that cap dates from jax
    0.4.37 and was not searched again: bq=512 under bk=1,024 reads 17.7
    ms at 16 x 16,384 x 64); the forward has no such working set and
    keeps bq=1024 everywhere. Explicit block args override."""
    if block_k is None:
        block_k = 1024
    if block_q is None:
        block_q = 512 if (for_bwd and sq > 8192) else 1024
    return block_q, block_k


def sub_block_rows(bq, h, itemsize):
    """Rows `c` of a row sub-block of a DIAGONAL tile of the triangle
    grids, a function of the shape alone: the tile's row sub-block i
    meets only the (i+1)*c key columns at or left of the diagonal, so
    the masked upper half of the tile is never formed. Tiles of 256
    rows or fewer keep one masked body (c == bq); larger ones walk
    sub-blocks of 128 rows — 8 at bq=1024, 36/64 of the tile's scores.
    Measured on the v5e (jax 0.9.0, bf16, PR 37; PERF.md section 6) at
    144 x 2,048 x 64 and at H=128 with S=2,048 and 8,192: 128 rows read
    faster than 256 and 512 at both widths, forward and backward, with
    the same compile time; H and the operand size did not change the
    answer, so they are taken and not used."""
    del h, itemsize
    c = 128
    if bq <= 256 or bq % c:
        return bq
    return c


def _tri_score_elems(sq, bq, bk, c):
    """Score elements a head's triangle grid computes over [sq, sq]
    (bk % bq == 0): whole [bq, bk] tiles below the diagonal, and in
    each of the sq/bq diagonal tiles the row sub-blocks of `c` rows
    against their live columns only."""
    nq, nk, r = sq // bq, sq // bk, bk // bq
    tiles = nk * nq - r * nk * (nk - 1) // 2
    diag = sum(c * (j * bq + (i + 1) * c)
               for j in range(r) for i in range(bq // c))
    return (tiles - nq) * bq * bk + nk * diag


def causal_work_ratio(sq, bq, bk, c):
    """Score elements the triangle-grid kernels compute over the
    sq*sq/2 the causal mask needs: how far the diagonal tiles' walk
    engages, from the tiling alone (at sq=2048, bq=bk=1024: 1.5 with
    c=1024, whole diagonal tiles; 1.25 at c=512; 1.125 at 256; 1.0625
    at 128). benchmark/work.py's flash_flops counts the needed half
    whatever this reads, so flash_attn_roofline moves against it."""
    return _tri_score_elems(sq, bq, bk, c) / (sq * sq / 2)


_LANES = 128  # stats buffers padded to a full lane register
_SUB = 8     # row-stats (lse/delta) replicated over 8 sublanes so their
             # [.., _SUB, bq] blocks satisfy the TPU (8, 128) tile minimum
_NEG_INF = -1e30


def _interpret():
    return jax.default_backend() != "tpu"


def _fit_block(block, dim):
    """Largest power-of-two block <= `block` that exactly tiles `dim`
    (callers guarantee dim % 128 == 0, so this terminates >= 128)."""
    b = min(block, dim)
    while dim % b:
        b //= 2
    return b


# ---------------------------------------------------------------------------
# triangle grids: for causal self-attention (offset == 0) the grid
# enumerates ONLY the lower-triangular live tiles through a 1D flat index,
# so dead tiles cost neither a grid step nor their block DMA (the
# rectangular grid's pl.when skip saves compute but still fetches blocks).
# Decodes are float-sqrt seeded and integer-corrected, so they are exact.
# ---------------------------------------------------------------------------

def _tri_fwd_decode(t):
    """Flat lower-triangle index -> (qi, ki) for bq == bk: row qi holds
    qi+1 tiles, cumulative C(q) = q(q+1)/2."""
    tf = t.astype(jnp.float32)
    qi = ((jnp.sqrt(8.0 * tf + 1.0) - 1.0) * 0.5).astype(jnp.int32)
    qi = jnp.where((qi + 1) * (qi + 2) // 2 <= t, qi + 1, qi)
    qi = jnp.where(qi * (qi + 1) // 2 > t, qi - 1, qi)
    ki = t - qi * (qi + 1) // 2
    return qi, ki


def _tri_bwd_decode(t, nq, r):
    """Flat index -> (ki, qj), column-major: column ki holds nq - r*ki
    q-tiles starting at qj = r*ki (r = bk // bq)."""
    def C(x):
        return x * nq - r * x * (x - 1) // 2
    tf = t.astype(jnp.float32)
    a = nq + 0.5 * r
    ki = ((a - jnp.sqrt(a * a - 2.0 * r * tf)) / r).astype(jnp.int32)
    ki = jnp.where(C(ki + 1) <= t, ki + 1, ki)
    ki = jnp.where(C(ki) > t, ki - 1, ki)
    qj = r * ki + (t - C(ki))
    return ki, qj


# ---------------------------------------------------------------------------
# kernel-registry references + examples (analysis/kernel_lint KN504):
# naive attention over the flat [BN, S, H] layout is the exact math the
# flash kernels tile; the doctor runs every registered kernel against
# it on randomized in-support shapes
# ---------------------------------------------------------------------------

def _ref_fwd_flat(qr, kr, vr, causal, offset=0):
    """Reference forward over pre-scaled flat inputs -> (out, lse)
    shaped exactly like the kernels' outputs."""
    f32 = jnp.float32
    s = jax.lax.dot_general(
        qr.astype(f32), kr.astype(f32),
        (((2,), (2,)), ((0,), (0,))))                 # [BN, sq, sk]
    sq, sk = qr.shape[1], kr.shape[1]
    if causal:
        mask = (jnp.arange(sq)[:, None] + offset) >= \
            jnp.arange(sk)[None, :]
        s = jnp.where(mask[None], s, _NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    out = jax.lax.dot_general(
        p / l, vr.astype(f32), (((2,), (1,)), ((0,), (0,))))
    lse = (m + jnp.log(l))[..., 0]                    # [BN, sq]
    lse = jnp.broadcast_to(lse[:, None, :],
                           (qr.shape[0], _SUB, qr.shape[1]))
    return out.astype(qr.dtype), lse


def _ref_bwd_flat(qr, kr, vr, gr, lse, delta, causal, offset=0):
    """Reference backward from the saved lse/delta -> (dq, dk, dv)
    flat, UN-scaled (mirrors the kernels; callers apply scale)."""
    f32 = jnp.float32
    s = jax.lax.dot_general(
        qr.astype(f32), kr.astype(f32),
        (((2,), (2,)), ((0,), (0,))))                 # [BN, sq, sk]
    p = jnp.exp(s - lse[:, 0, :, None])
    sq, sk = qr.shape[1], kr.shape[1]
    if causal:
        mask = (jnp.arange(sq)[:, None] + offset) >= \
            jnp.arange(sk)[None, :]
        p = jnp.where(mask[None], p, 0.0)
    d_row = delta[:, 0, :, None]                      # [BN, sq, 1]
    dv = jax.lax.dot_general(
        p, gr.astype(f32), (((1,), (1,)), ((0,), (0,))))   # [BN, sk, H]
    dp = jax.lax.dot_general(
        gr.astype(f32), vr.astype(f32),
        (((2,), (2,)), ((0,), (0,))))                 # [BN, sq, sk]
    ds = p * (dp - d_row)
    dk = jax.lax.dot_general(
        ds, qr.astype(f32), (((1,), (1,)), ((0,), (0,))))  # [BN, sk, H]
    dq = jax.lax.dot_general(
        ds, kr.astype(f32), (((2,), (1,)), ((0,), (0,))))  # [BN, sq, H]
    return (dq.astype(qr.dtype), dk.astype(kr.dtype),
            dv.astype(vr.dtype))


def _flat_example(rng, nq, bq=128, h=128, bn=2):
    sq = nq * bq
    mk = lambda: 0.08 * rng.standard_normal(  # noqa: E731
        (bn, sq, h)).astype(np.float32)
    return mk(), mk(), mk()


def _fwd_tri_example(rng):
    nq = int(rng.integers(2, 5))
    qr, kr, vr = _flat_example(rng, nq)
    return (qr, kr, vr, 128, 128, nq), {}


def _fwd_tri_fallback(qr, kr, vr, bq, bk, nq):
    return _ref_fwd_flat(qr, kr, vr, causal=True)


def _rect_4d_example(rng):
    """4-D example that stays OFF the triangle path (causal only with
    offset != 0), so the rectangular pallas_call site is the one
    captured."""
    b, n, h = 1, 2, 128
    sq = int(rng.choice([128, 256]))
    causal = bool(rng.integers(2))
    sk = sq + 128 if causal else sq
    mk = lambda s: 0.08 * rng.standard_normal(  # noqa: E731
        (b, s, n, h)).astype(np.float32)
    return mk(sq), mk(sk), mk(sk), causal, 1.0 / math.sqrt(h)


def _fwd_rect_example(rng):
    q, k, v, causal, scale = _rect_4d_example(rng)
    return (q, k, v, causal, scale, 128, 128), {}


def _fwd_rect_fallback(q, k, v, causal, scale, block_q, block_k):
    b, sq, n, h = q.shape
    sk = k.shape[1]
    qr = (q.transpose(0, 2, 1, 3).reshape(b * n, sq, h)) * scale
    kr = k.transpose(0, 2, 1, 3).reshape(b * n, sk, h)
    vr = v.transpose(0, 2, 1, 3).reshape(b * n, sk, h)
    return _ref_fwd_flat(qr, kr, vr, causal, sk - sq)


def _bwd_tri_example(rng):
    r = int(rng.integers(1, 3))
    nk = int(rng.integers(2, 4))
    bq = 128
    bk = bq * r
    nq = nk * r
    qr, kr, vr = _flat_example(rng, nq, bq=bq)
    out, lse = _ref_fwd_flat(qr, kr, vr, causal=True)
    # upstream gradient at the scale of the inputs, as in
    # _bwd_rect_example: the in-kernel f32 dots take one bf16 MXU pass
    # on the chip, whose absolute error scales with the operands (a
    # unit-scale gradient reads 9e-3 off at this tolerance's 2e-3)
    gr = 0.08 * rng.standard_normal(qr.shape).astype(np.float32)
    delta = jnp.sum(gr * out.astype(jnp.float32), axis=-1)
    delta = jnp.broadcast_to(delta[:, None, :],
                             (qr.shape[0], _SUB, qr.shape[1]))
    return (qr, kr, vr, gr, lse, delta, bq, bk, nq), {}


def _bwd_tri_fallback(qr, kr, vr, gr, lse, delta, bq, bk, nq):
    return _ref_bwd_flat(qr, kr, vr, gr, lse, delta, causal=True)


def _bwd_rect_example(rng):
    q, k, v, causal, scale = _rect_4d_example(rng)
    b, sq, n, h = q.shape
    out, lse = _fwd_rect_fallback(q, k, v, causal, scale, 128, 128)
    g = 0.08 * rng.standard_normal(q.shape).astype(np.float32)
    return (q, k, v, out, lse, g, causal, scale, 128, 128), {}


def _bwd_rect_fallback(q, k, v, out, lse, g, causal, scale,
                       block_q, block_k):
    b, sq, n, h = q.shape
    sk = k.shape[1]
    qr = (q.transpose(0, 2, 1, 3).reshape(b * n, sq, h)) * scale
    kr = k.transpose(0, 2, 1, 3).reshape(b * n, sk, h)
    vr = v.transpose(0, 2, 1, 3).reshape(b * n, sk, h)
    gr = g.transpose(0, 2, 1, 3).reshape(b * n, sq, h)
    delta = jnp.sum(gr.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)
    delta = jnp.broadcast_to(delta[:, None, :], (b * n, _SUB, sq))
    dq, dk, dv = _ref_bwd_flat(qr, kr, vr, gr, lse, delta, causal,
                               sk - sq)
    dq = dq * scale

    def unflatten(x, s):
        return x.reshape(b, n, s, h).transpose(0, 2, 1, 3)
    return unflatten(dq, sq), unflatten(dk, sk), unflatten(dv, sk)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                acc_sc, m_sc, l_sc, *, causal, bq, bk, nk, offset):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_sc[:] = jnp.zeros_like(acc_sc)
        m_sc[:] = jnp.full_like(m_sc, _NEG_INF)
        l_sc[:] = jnp.zeros_like(l_sc)

    qi = pl.program_id(1)

    def compute(masked):
        q = q_ref[0]                               # [bq, H] input dtype
        k = k_ref[0]                               # [bk, H]
        # bf16 inputs feed the MXU directly; accumulation stays f32
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)           # [bq, bk] f32
        if masked:
            rows = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) + qi * bq
            cols = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1) + ki * bk
            s = jnp.where(rows + offset >= cols, s, _NEG_INF)
        m_prev = m_sc[:, :1]                       # [bq, 1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)                     # [bq, bk] f32
        l_new = alpha * l_sc[:, :1] + jnp.sum(p, axis=-1, keepdims=True)
        v = v_ref[0]                               # [bk, H]
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)    # [bq, H]
        acc_sc[:] = acc_sc[:] * alpha + pv
        m_sc[:] = jnp.broadcast_to(m_new, m_sc.shape)
        l_sc[:] = jnp.broadcast_to(l_new, l_sc.shape)

    if causal:
        # three tile classes: above the band (skip entirely), crossing the
        # diagonal (mask), fully inside (no iota/compare/select VPU work)
        live = ki * bk <= (qi + 1) * bq - 1 + offset
        diag = (ki + 1) * bk - 1 > qi * bq + offset

        @pl.when(jnp.logical_and(live, diag))
        def _():
            compute(True)

        @pl.when(jnp.logical_and(live, jnp.logical_not(diag)))
        def _():
            compute(False)
    else:
        compute(False)

    @pl.when(ki == nk - 1)
    def _finalize():
        l = l_sc[:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_sc[:] / l_safe).astype(o_ref.dtype)
        lse = (m_sc[:, :1] + jnp.log(l_safe))[:, 0]          # [bq]
        lse_ref[0] = jnp.broadcast_to(lse[None, :], (_SUB, lse.shape[0]))


def _mask_square(x, fill):
    """Causal mask of a [c, c] square of scores (or probabilities) on
    the diagonal: local iotas, no tile offsets to add."""
    rows = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    return jnp.where(rows >= cols, x, fill)


def _mask_crossing(x, fill):
    """Causal mask of a [c, w] block whose LAST c columns are the square
    the diagonal crosses: columns left of it are live and are passed
    through without an iota or a select."""
    c, w = x.shape
    if w == c:
        return _mask_square(x, fill)
    return jnp.concatenate(
        [x[:, :w - c], _mask_square(x[:, w - c:], fill)], axis=1)


def _diagonal_walk(bq, c, off=0):
    """Row sub-blocks of a diagonal tile of bq rows that start `off`
    columns into its k tile: (rows, live) for each sub-block of c rows
    — sub-block i meets the first off + (i+1)*c key columns, the last
    c of which the diagonal crosses."""
    return [(slice(r0, r0 + c), off + r0 + c) for r0 in range(0, bq, c)]


def _fwd_kernel_tri(q_ref, k_ref, v_ref, o_ref, lse_ref,
                    acc_sc, m_sc, l_sc, *, bq, bk, c):
    """Triangle-grid causal forward (offset == 0, bq == bk): grid step t
    enumerates live tiles only; the diagonal tile (ki == qi) is the only
    one needing the mask, and it is also the row's finalize step. It
    walks its row sub-blocks of c rows (sub_block_rows), each against
    the key columns at or left of the diagonal alone."""
    t = pl.program_id(1)
    qi, ki = _tri_fwd_decode(t)

    @pl.when(ki == 0)
    def _init():
        acc_sc[:] = jnp.zeros_like(acc_sc)
        m_sc[:] = jnp.full_like(m_sc, _NEG_INF)
        l_sc[:] = jnp.zeros_like(l_sc)

    def compute(subs):
        """One online-softmax step of each (rows, blocks) of `subs`:
        `rows` a static slice of the q tile, `blocks` the (key columns,
        crossing) pieces its scores are formed in, a crossing piece
        being a square on the diagonal. Written phase by phase ACROSS
        the sub-blocks — every score product, then every maximum, every
        exponential, every value product, the stores — because Mosaic
        schedules near source order: sub-block by sub-block each short
        product waits for the reduction before it, and a diagonal tile
        walked so costs more than one computed whole (v5e, PR 37)."""
        scores = []
        for rows, blocks in subs:
            q = q_ref[0, rows, :]                  # [c, H] input dtype
            # bf16 inputs feed the MXU directly; accumulation stays f32
            ss = [jax.lax.dot_general(
                q, k_ref[0, cols, :], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) for cols, _ in blocks]
            scores.append([_mask_square(s_, _NEG_INF) if crossing else s_
                           for s_, (_, crossing) in zip(ss, blocks)])
        m_prev = [m_sc[rows, :1] for rows, _ in subs]          # [c, 1]
        m_new = [jnp.maximum(mp, functools.reduce(jnp.maximum, [
            jnp.max(s_, axis=-1, keepdims=True) for s_ in ss]))
            for mp, ss in zip(m_prev, scores)]
        alpha = [jnp.exp(mp - mn) for mp, mn in zip(m_prev, m_new)]
        probs = [[jnp.exp(s_ - mn) for s_ in ss]               # f32
                 for mn, ss in zip(m_new, scores)]
        l_new = [a * l_sc[rows, :1] + sum(
            jnp.sum(p, axis=-1, keepdims=True) for p in ps)
            for a, (rows, _), ps in zip(alpha, subs, probs)]
        pv = [sum(jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0, cols, :],
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
            for p, (cols, _) in zip(ps, blocks))               # [c, H]
            for ps, (_, blocks) in zip(probs, subs)]
        acc = [acc_sc[rows, :] * a + x
               for (rows, _), a, x in zip(subs, alpha, pv)]
        for (rows, _), acc_, mn, ln in zip(subs, acc, m_new, l_new):
            acc_sc[rows, :] = acc_
            m_sc[rows, :] = jnp.broadcast_to(mn, (mn.shape[0], _LANES))
            l_sc[rows, :] = jnp.broadcast_to(ln, (ln.shape[0], _LANES))

    @pl.when(ki == qi)
    def _():
        # a sub-block's live columns as the rectangle left of the
        # diagonal and the square on it: no score right of it is formed
        subs = []
        for rows, live in _diagonal_walk(bq, c):
            left = [(slice(0, live - c), False)] if live > c else []
            subs.append((rows, left + [(rows, True)]))
        compute(subs)

    @pl.when(ki < qi)
    def _():
        compute([(slice(0, bq), [(slice(0, bk), False)])])

    @pl.when(ki == qi)
    def _finalize():
        l = l_sc[:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_sc[:] / l_safe).astype(o_ref.dtype)
        lse = (m_sc[:, :1] + jnp.log(l_safe))[:, 0]          # [bq]
        lse_ref[0] = jnp.broadcast_to(lse[None, :], (_SUB, lse.shape[0]))


@register_kernel(
    "flash_fwd_tri", example=_fwd_tri_example,
    fallback=_fwd_tri_fallback, tol=(2e-3, 2e-3),
    notes="triangle-grid causal forward; flat T axis must stay "
          "sequential (KN501)")
def _flash_fwd_tri(qr, kr, vr, bq, bk, nq):
    bn, sq, h = qr.shape
    T = nq * (nq + 1) // 2
    c = sub_block_rows(bq, h, qr.dtype.itemsize)
    # the cost estimate below quotes what the grid runs, not the ~2x-
    # overstated dense square: the score elements the tiles compute
    # (diagonal tiles their live sub-blocks only) and the blocks the
    # live tiles fetch (frac of the full nq x nq square)
    elems = bn * _tri_score_elems(sq, bq, bk, c)
    frac = (nq + 1) / (2 * nq)

    def qmap(bn_, t):
        return (bn_, _tri_fwd_decode(t)[0], 0)

    def kmap(bn_, t):
        return (bn_, _tri_fwd_decode(t)[1], 0)

    def omap(bn_, t):
        return (bn_, _tri_fwd_decode(t)[0], 0)

    def lmap(bn_, t):
        return (bn_, 0, _tri_fwd_decode(t)[0])

    kernel = functools.partial(_fwd_kernel_tri, bq=bq, bk=bk, c=c)
    # SEQUENTIAL-GRID INVARIANT: the flat-index dimension (T) enumerates
    # live tiles in row-major order and the kernel's running softmax
    # state (acc/m/l scratch) carries across its steps; this dimension
    # must NEVER be marked parallel (dimension_semantics) — Mosaic's
    # default sequential execution is load-bearing. MACHINE-CHECKED:
    # Kernel Doctor rule KN501 (analysis/kernel_lint.py) evaluates the
    # output index_maps over the grid and fails any parallel-marked
    # axis whose steps revisit an output block (tests/test_io_prefetch
    # pins it; tools/kerneldoctor.py gates it in CI).
    out, lse = pl.pallas_call(
        kernel,
        name="flash_fwd_tri",
        grid=(bn, T),
        in_specs=[
            pl.BlockSpec((1, bq, h), qmap),
            pl.BlockSpec((1, bk, h), kmap),
            pl.BlockSpec((1, bk, h), kmap),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, h), omap),
            pl.BlockSpec((1, _SUB, bq), lmap),
        ],
        out_shape=[
            out_struct((bn, sq, h), qr.dtype, qr, kr, vr),
            out_struct((bn, _SUB, sq), jnp.float32, qr, kr, vr),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, h), jnp.float32),
            pltpu.VMEM((bq, _LANES), jnp.float32),
            pltpu.VMEM((bq, _LANES), jnp.float32),
        ],
        cost_estimate=pl.CostEstimate(
            # 4 flops/elem over the computed scores + pv, 1 exp/elem;
            # dense q/k/v/o traffic x the live-tile fraction
            flops=4 * elems * h,
            bytes_accessed=int((qr.size * 2 + kr.size + vr.size)
                               * qr.dtype.itemsize * frac),
            transcendentals=elems),
        interpret=_interpret(),
    )(qr, kr, vr)
    return out, lse


@register_kernel(
    "flash_fwd_rect", example=_fwd_rect_example,
    fallback=_fwd_rect_fallback, tol=(2e-3, 2e-3),
    notes="rectangular-grid forward (non-causal / offset cross-attn)")
def _flash_fwd(q, k, v, causal, scale, block_q, block_k):
    b, sq, n, h = q.shape
    sk = k.shape[1]
    bq = _fit_block(block_q, sq)
    bk = _fit_block(block_k, sk)
    nq, nk = sq // bq, sk // bk
    offset = sk - sq

    # scale folded into q once here instead of a [bq, bk] VPU pass per
    # tile inside the kernel (dq is un-scaled correspondingly in the vjp)
    qr = (q.transpose(0, 2, 1, 3).reshape(b * n, sq, h)) * scale
    kr = k.transpose(0, 2, 1, 3).reshape(b * n, sk, h)
    vr = v.transpose(0, 2, 1, 3).reshape(b * n, sk, h)

    if causal and offset == 0 and bq == bk and nq > 1:
        return _flash_fwd_tri(qr, kr, vr, bq, bk, nq)

    kernel = functools.partial(
        _fwd_kernel, causal=causal, bq=bq, bk=bk, nk=nk,
        offset=offset)
    out, lse = pl.pallas_call(
        kernel,
        name="flash_fwd_rect",
        grid=(b * n, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, h), lambda bn, i, j: (bn, i, 0)),
            pl.BlockSpec((1, bk, h), lambda bn, i, j: (bn, j, 0)),
            pl.BlockSpec((1, bk, h), lambda bn, i, j: (bn, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, h), lambda bn, i, j: (bn, i, 0)),
            pl.BlockSpec((1, _SUB, bq), lambda bn, i, j: (bn, 0, i)),
        ],
        out_shape=[
            out_struct((b * n, sq, h), q.dtype, qr, kr, vr),
            out_struct((b * n, _SUB, sq), jnp.float32, qr, kr, vr),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, h), jnp.float32),
            pltpu.VMEM((bq, _LANES), jnp.float32),
            pltpu.VMEM((bq, _LANES), jnp.float32),
        ],
        cost_estimate=pl.CostEstimate(
            flops=4 * b * n * sq * sk * h,
            bytes_accessed=(qr.size + kr.size + vr.size) * q.dtype.itemsize,
            transcendentals=b * n * sq * sk),
        interpret=_interpret(),
    )(qr, kr, vr)
    return out, lse  # [BN, S, H], [BN, _SUB, S]


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------

def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_sc, dv_sc,
                *, causal, bq, bk, nq, offset):
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_sc[:] = jnp.zeros_like(dk_sc)
        dv_sc[:] = jnp.zeros_like(dv_sc)

    ki = pl.program_id(1)

    def compute(masked):
        q = q_ref[0]                               # [bq, H] input dtype
        k = k_ref[0]                               # [bk, H]
        v = v_ref[0]
        do = do_ref[0]                             # [bq, H]
        lse = lse_ref[0][0][:, None]               # [bq, 1]
        delta = delta_ref[0][0][:, None]           # [bq, 1]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)           # [bq, bk]
        p = jnp.exp(s - lse)
        if masked:
            rows = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) + qi * bq
            cols = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1) + ki * bk
            p = jnp.where(rows + offset >= cols, p, 0.0)
        # dv += p^T do
        dv_sc[:] = dv_sc[:] + jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)    # [bq, bk]
        ds = p * (dp - delta)
        dk_sc[:] = dk_sc[:] + jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        live = (qi + 1) * bq - 1 + offset >= ki * bk
        diag = (ki + 1) * bk - 1 > qi * bq + offset

        @pl.when(jnp.logical_and(live, diag))
        def _():
            compute(True)

        @pl.when(jnp.logical_and(live, jnp.logical_not(diag)))
        def _():
            compute(False)
    else:
        compute(False)

    @pl.when(qi == nq - 1)
    def _finalize():
        dk_ref[0] = dk_sc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_sc[:].astype(dv_ref.dtype)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
               dq_ref, dq_sc, *, causal, bq, bk, nk, offset):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        dq_sc[:] = jnp.zeros_like(dq_sc)

    qi = pl.program_id(1)

    def compute(masked):
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0][0][:, None]
        delta = delta_ref[0][0][:, None]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        p = jnp.exp(s - lse)
        if masked:
            rows = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) + qi * bq
            cols = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1) + ki * bk
            p = jnp.where(rows + offset >= cols, p, 0.0)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        dq_sc[:] = dq_sc[:] + jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        live = ki * bk <= (qi + 1) * bq - 1 + offset
        diag = (ki + 1) * bk - 1 > qi * bq + offset

        @pl.when(jnp.logical_and(live, diag))
        def _():
            compute(True)

        @pl.when(jnp.logical_and(live, jnp.logical_not(diag)))
        def _():
            compute(False)
    else:
        compute(False)

    @pl.when(ki == nk - 1)
    def _finalize():
        dq_ref[0] = dq_sc[:].astype(dq_ref.dtype)


def _bwd_merged_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref,
                       delta_ref, dq_ref, dk_ref, dv_ref,
                       dk_sc, dv_sc, dq_sc,
                       *, causal, bq, bk, nq, nk, offset):
    """One pass over (k-tile outer, q-tile inner) producing all three
    gradients, so the s/p recomputation and the dp dot are shared —
    5 MXU dots per tile instead of the 7 the split dkv+dq kernels cost.
    dq accumulates in a whole-slice VMEM scratch ([sq, H] f32 — 256 KB at
    GPT bench shapes) and each dq block is flushed on the LAST k-tile."""
    qi = pl.program_id(2)
    ki = pl.program_id(1)

    @pl.when(qi == 0)
    def _init_kv():
        dk_sc[:] = jnp.zeros_like(dk_sc)
        dv_sc[:] = jnp.zeros_like(dv_sc)

    @pl.when(jnp.logical_and(ki == 0, qi == 0))
    def _init_dq():
        dq_sc[:] = jnp.zeros_like(dq_sc)

    def compute(masked):
        q = q_ref[0]                               # [bq, H]
        k = k_ref[0]                               # [bk, H]
        v = v_ref[0]
        do = do_ref[0]                             # [bq, H]
        lse = lse_ref[0][0][:, None]               # [bq, 1]
        delta = delta_ref[0][0][:, None]           # [bq, 1]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)           # [bq, bk]
        p = jnp.exp(s - lse)
        if masked:
            rows = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) + qi * bq
            cols = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1) + ki * bk
            p = jnp.where(rows + offset >= cols, p, 0.0)
        dv_sc[:] = dv_sc[:] + jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)    # [bq, bk]
        ds = (p * (dp - delta)).astype(q.dtype)
        dk_sc[:] = dk_sc[:] + jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        rows_sl = pl.ds(qi * bq, bq)
        dq_sc[rows_sl, :] = dq_sc[rows_sl, :] + jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        @pl.when((qi + 1) * bq - 1 + offset >= ki * bk)
        def _():
            compute(True)
    else:
        compute(False)

    @pl.when(qi == nq - 1)
    def _finalize_kv():
        dk_ref[0] = dk_sc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_sc[:].astype(dv_ref.dtype)

    # the dq output window moves every (inner) grid step, so Pallas
    # flushes a block per step regardless; writing the running partial on
    # every visit keeps those flushes DEFINED (never stale VMEM), and the
    # final visit (ki == nk-1) flushes the completed value last
    dq_ref[0] = dq_sc[pl.ds(qi * bq, bq), :].astype(dq_ref.dtype)


def _bwd_merged_kernel_tri(q_ref, k_ref, v_ref, do_ref, lse_ref,
                           delta_ref, dq_ref, dk_ref, dv_ref,
                           dk_sc, dv_sc, dq_sc,
                           *, bq, bk, nq, r, c):
    """Triangle-grid causal merged backward (offset == 0, bk % bq == 0):
    column-major over live tiles only. Same 5-dot body and whole-slice dq
    accumulator as _bwd_merged_kernel; the mask is applied only on the r
    diagonal-crossing tiles per column (qj // r == ki), which walk their
    row sub-blocks of c rows (sub_block_rows), each against the key
    columns at or left of the diagonal alone."""
    t = pl.program_id(1)
    ki, qj = _tri_bwd_decode(t, nq, r)

    @pl.when(qj == r * ki)
    def _init_kv():
        dk_sc[:] = jnp.zeros_like(dk_sc)
        dv_sc[:] = jnp.zeros_like(dv_sc)

    @pl.when(t == 0)
    def _init_dq():
        dq_sc[:] = jnp.zeros_like(dq_sc)

    def compute(subs, masked):
        """The five products of each (rows, live) of `subs`: `rows` a
        static slice of the q tile, against the k tile's first `live`
        rows; `masked` says the last len(rows) of those cross the
        diagonal. Phase by phase ACROSS the sub-blocks, as in
        _fwd_kernel_tri.compute (sub-block by sub-block reads 8% slower
        on the v5e), but one product a sub-block with the crossing
        square cut out and put back: in the forward's two pieces the
        backward read 2% slower. The phases stand in the order the
        whole tile (one sub-block) reads fastest in: both score-shaped
        products first read 0.7% slower there."""
        q = [q_ref[0, rows, :] for rows, _ in subs]            # [c, H]
        do = [do_ref[0, rows, :] for rows, _ in subs]          # [c, H]
        p = []
        for q_, (rows, live) in zip(q, subs):
            lse = lse_ref[0, 0, rows][:, None]                 # [c, 1]
            s = jax.lax.dot_general(
                q_, k_ref[0, :live, :], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)           # [c, live]
            p.append(jnp.exp(s - lse))
        if masked:
            p = [_mask_crossing(p_, 0.0) for p_ in p]
        dv = [jax.lax.dot_general(
            p_.astype(do_.dtype), do_, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) for p_, do_ in zip(p, do)]
        ds = []
        for p_, q_, do_, (rows, live) in zip(p, q, do, subs):
            delta = delta_ref[0, 0, rows][:, None]             # [c, 1]
            dp = jax.lax.dot_general(
                do_, v_ref[0, :live, :], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)           # [c, live]
            ds.append((p_ * (dp - delta)).astype(q_.dtype))
        dk = [jax.lax.dot_general(
            ds_, q_, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) for ds_, q_ in zip(ds, q)]
        dq = [jax.lax.dot_general(
            ds_, k_ref[0, :live, :], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
            for ds_, (_, live) in zip(ds, subs)]
        for (rows, live), dv_, dk_, dq_ in zip(subs, dv, dk, dq):
            dv_sc[:live, :] = dv_sc[:live, :] + dv_
            dk_sc[:live, :] = dk_sc[:live, :] + dk_
            rows_sl = pl.ds(qj * bq + rows.start, rows.stop - rows.start)
            dq_sc[rows_sl, :] = dq_sc[rows_sl, :] + dq_

    # a diagonal tile's q rows start j * bq columns into its k tile
    # (j = qj - r * ki < r, static under its own branch)
    for j in range(r):
        @pl.when(qj == r * ki + j)
        def _(j=j):
            compute(_diagonal_walk(bq, c, j * bq), True)

    @pl.when(qj // r > ki)
    def _():
        compute([(slice(0, bq), bk)], False)

    @pl.when(qj == nq - 1)
    def _finalize_kv():
        dk_ref[0] = dk_sc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_sc[:].astype(dv_ref.dtype)

    # dq windows are revisited across columns and flushed on every step;
    # only the LAST flush of a window must be the complete value, and the
    # final visit of window qj is in its diagonal column ki == qj // r
    # (the largest ki that visits qj). Intermediate flushes may carry
    # whatever is in the output buffer — they are overwritten in order.
    @pl.when(ki == qj // r)
    def _flush_dq():
        dq_ref[0] = dq_sc[pl.ds(qj * bq, bq), :].astype(dq_ref.dtype)


@register_kernel(
    "flash_bwd_merged_tri", example=_bwd_tri_example,
    fallback=_bwd_tri_fallback, tol=(2e-3, 2e-3),
    notes="triangle-grid merged backward; the _flush_dq sequential-grid"
          " invariant is the KN501 checked property")
def _flash_bwd_merged_tri(qr, kr, vr, gr, lse, delta, bq, bk, nq):
    bn, sq, h = qr.shape
    r = bk // bq
    nk = sq // bk
    T = nk * nq - r * nk * (nk - 1) // 2
    c = sub_block_rows(bq, h, qr.dtype.itemsize)
    # as in _flash_fwd_tri: the score elements the tiles compute, and
    # the live-tile fraction of the full nk x nq tile square (~(nq+1)/
    # (2*nq) at r=1) for the blocks they fetch
    elems = bn * _tri_score_elems(sq, bq, bk, c)
    frac = T / (nk * nq)

    def qmap(bn_, t):
        return (bn_, _tri_bwd_decode(t, nq, r)[1], 0)

    def kmap(bn_, t):
        return (bn_, _tri_bwd_decode(t, nq, r)[0], 0)

    def smap(bn_, t):
        return (bn_, 0, _tri_bwd_decode(t, nq, r)[1])

    kernel = functools.partial(
        _bwd_merged_kernel_tri, bq=bq, bk=bk, nq=nq, r=r, c=c)
    # SEQUENTIAL-GRID INVARIANT: the flat-index dimension (T) walks live
    # tiles column-major and the kernel relies on Mosaic's sequential
    # grid order twice — (a) dk/dv scratch accumulates down each column,
    # and (b) a dq output window is revisited across columns with its
    # COMPLETE value flushed only in the diagonal column (_flush_dq);
    # intermediate revisits DMA whatever the buffer holds and are
    # overwritten in order. Marking this grid dimension parallel
    # (dimension_semantics) would silently corrupt dq and dk/dv — never
    # do it. MACHINE-CHECKED: KN501 (analysis/kernel_lint.py) derives
    # exactly this property from the dq index_map's revisits, so a
    # parallel marking here fails the kerneldoctor CI gate by name.
    dq, dk, dv = pl.pallas_call(
        kernel,
        name="flash_bwd_merged_tri",
        grid=(bn, T),
        in_specs=[
            pl.BlockSpec((1, bq, h), qmap),   # q
            pl.BlockSpec((1, bk, h), kmap),   # k
            pl.BlockSpec((1, bk, h), kmap),   # v
            pl.BlockSpec((1, bq, h), qmap),   # do
            pl.BlockSpec((1, _SUB, bq), smap),  # lse
            pl.BlockSpec((1, _SUB, bq), smap),  # delta
        ],
        out_specs=[
            pl.BlockSpec((1, bq, h), qmap),
            pl.BlockSpec((1, bk, h), kmap),
            pl.BlockSpec((1, bk, h), kmap),
        ],
        out_shape=[
            out_struct((bn, sq, h), qr.dtype, qr, kr, vr, gr),
            out_struct((bn, sq, h), kr.dtype, qr, kr, vr, gr),
            out_struct((bn, sq, h), vr.dtype, qr, kr, vr, gr),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, h), jnp.float32),
            pltpu.VMEM((bk, h), jnp.float32),
            pltpu.VMEM((sq, h), jnp.float32),
        ],
        cost_estimate=pl.CostEstimate(
            # 5 MXU dots = 10 flops/elem over the computed scores, 1
            # exp/elem; q/do/lse/delta + k/v + dq/dk/dv traffic x frac
            flops=10 * elems * h,
            bytes_accessed=int((qr.size * 4 + kr.size * 4)
                               * qr.dtype.itemsize * frac),
            transcendentals=elems),
        interpret=_interpret(),
    )(qr, kr, vr, gr, lse, delta)
    return dq, dk, dv


# above ~this scratch footprint the whole-slice dq accumulator stops
# fitting comfortably next to the tile buffers; shrink bq first, then
# fall back to the split kernels
_MERGED_BWD_DQ_SCRATCH_LIMIT = 6 * 1024 * 1024
_MERGED_BWD_DQ_SCRATCH_LIMIT_SMALL_BQ = 9 * 1024 * 1024


@register_kernel(
    "flash_bwd_merged_rect", example=_bwd_rect_example,
    fallback=_bwd_rect_fallback, tol=(2e-3, 2e-3),
    notes="rectangular merged backward (whole-slice dq accumulator)")
def _flash_bwd_merged(q, k, v, out, lse, g, causal, scale, block_q, block_k):
    b, sq, n, h = q.shape
    sk = k.shape[1]
    bq = _fit_block(block_q, sq)
    bk = _fit_block(block_k, sk)
    nq, nk = sq // bq, sk // bk
    offset = sk - sq

    qr = (q.transpose(0, 2, 1, 3).reshape(b * n, sq, h)) * scale
    kr = k.transpose(0, 2, 1, 3).reshape(b * n, sk, h)
    vr = v.transpose(0, 2, 1, 3).reshape(b * n, sk, h)
    gr = g.transpose(0, 2, 1, 3).reshape(b * n, sq, h)
    delta = jnp.sum(gr.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    delta = jnp.broadcast_to(delta[:, None, :], (b * n, _SUB, sq))

    if causal and offset == 0 and bk % bq == 0 and nk > 1:
        dq, dk, dv = _flash_bwd_merged_tri(qr, kr, vr, gr, lse, delta,
                                           bq, bk, nq)
        dq = dq * scale

        def unflatten_tri(x, s):
            return x.reshape(b, n, s, h).transpose(0, 2, 1, 3)
        return (unflatten_tri(dq, sq), unflatten_tri(dk, sk),
                unflatten_tri(dv, sk))

    kernel = functools.partial(
        _bwd_merged_kernel, causal=causal, bq=bq, bk=bk,
        nq=nq, nk=nk, offset=offset)
    dq, dk, dv = pl.pallas_call(
        kernel,
        name="flash_bwd_merged_rect",
        grid=(b * n, nk, nq),
        in_specs=[
            pl.BlockSpec((1, bq, h), lambda bn, i, j: (bn, j, 0)),  # q
            pl.BlockSpec((1, bk, h), lambda bn, i, j: (bn, i, 0)),  # k
            pl.BlockSpec((1, bk, h), lambda bn, i, j: (bn, i, 0)),  # v
            pl.BlockSpec((1, bq, h), lambda bn, i, j: (bn, j, 0)),  # do
            pl.BlockSpec((1, _SUB, bq), lambda bn, i, j: (bn, 0, j)),
            pl.BlockSpec((1, _SUB, bq), lambda bn, i, j: (bn, 0, j)),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, h), lambda bn, i, j: (bn, j, 0)),
            pl.BlockSpec((1, bk, h), lambda bn, i, j: (bn, i, 0)),
            pl.BlockSpec((1, bk, h), lambda bn, i, j: (bn, i, 0)),
        ],
        out_shape=[
            out_struct((b * n, sq, h), q.dtype, qr, kr, vr, gr),
            out_struct((b * n, sk, h), k.dtype, qr, kr, vr, gr),
            out_struct((b * n, sk, h), v.dtype, qr, kr, vr, gr),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, h), jnp.float32),
            pltpu.VMEM((bk, h), jnp.float32),
            pltpu.VMEM((sq, h), jnp.float32),
        ],
        interpret=_interpret(),
    )(qr, kr, vr, gr, lse, delta)
    dq = dq * scale

    def unflatten(x, s):
        return x.reshape(b, n, s, h).transpose(0, 2, 1, 3)
    return unflatten(dq, sq), unflatten(dk, sk), unflatten(dv, sk)


@register_kernel(
    "flash_bwd_split", example=_bwd_rect_example,
    fallback=_bwd_rect_fallback, tol=(2e-3, 2e-3),
    notes="split dkv + dq backward (fallback above the dq-scratch cap)")
def _flash_bwd(q, k, v, out, lse, g, causal, scale, block_q, block_k):
    b, sq, n, h = q.shape
    sk = k.shape[1]
    bq = _fit_block(block_q, sq)
    bk = _fit_block(block_k, sk)
    nq, nk = sq // bq, sk // bk
    offset = sk - sq

    qr = (q.transpose(0, 2, 1, 3).reshape(b * n, sq, h)) * scale
    kr = k.transpose(0, 2, 1, 3).reshape(b * n, sk, h)
    vr = v.transpose(0, 2, 1, 3).reshape(b * n, sk, h)
    gr = g.transpose(0, 2, 1, 3).reshape(b * n, sq, h)

    # delta_i = rowsum(dO * O); elementwise, XLA fuses it
    delta = jnp.sum(gr.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    delta = jnp.broadcast_to(delta[:, None, :], (b * n, _SUB, sq))

    common_in = [
        pl.BlockSpec((1, bq, h), lambda bn, i, j: (bn, j, 0)),   # q by inner
        pl.BlockSpec((1, bk, h), lambda bn, i, j: (bn, i, 0)),   # k by outer
        pl.BlockSpec((1, bk, h), lambda bn, i, j: (bn, i, 0)),   # v by outer
        pl.BlockSpec((1, bq, h), lambda bn, i, j: (bn, j, 0)),   # do by inner
        pl.BlockSpec((1, _SUB, bq), lambda bn, i, j: (bn, 0, j)),  # lse
        pl.BlockSpec((1, _SUB, bq), lambda bn, i, j: (bn, 0, j)),  # delta
    ]
    dkv_kernel = functools.partial(
        _dkv_kernel, causal=causal, bq=bq, bk=bk, nq=nq,
        offset=offset)
    dk, dv = pl.pallas_call(
        dkv_kernel,
        name="flash_bwd_split_dkv",
        grid=(b * n, nk, nq),
        in_specs=common_in,
        out_specs=[
            pl.BlockSpec((1, bk, h), lambda bn, i, j: (bn, i, 0)),
            pl.BlockSpec((1, bk, h), lambda bn, i, j: (bn, i, 0)),
        ],
        out_shape=[
            out_struct((b * n, sk, h), k.dtype, qr, kr, vr, gr),
            out_struct((b * n, sk, h), v.dtype, qr, kr, vr, gr),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, h), jnp.float32),
            pltpu.VMEM((bk, h), jnp.float32),
        ],
        interpret=_interpret(),
    )(qr, kr, vr, gr, lse, delta)

    dq_kernel = functools.partial(
        _dq_kernel, causal=causal, bq=bq, bk=bk, nk=nk,
        offset=offset)
    dq = pl.pallas_call(
        dq_kernel,
        name="flash_bwd_split_dq",
        grid=(b * n, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, h), lambda bn, i, j: (bn, i, 0)),
            pl.BlockSpec((1, bk, h), lambda bn, i, j: (bn, j, 0)),
            pl.BlockSpec((1, bk, h), lambda bn, i, j: (bn, j, 0)),
            pl.BlockSpec((1, bq, h), lambda bn, i, j: (bn, i, 0)),
            pl.BlockSpec((1, _SUB, bq), lambda bn, i, j: (bn, 0, i)),
            pl.BlockSpec((1, _SUB, bq), lambda bn, i, j: (bn, 0, i)),
        ],
        out_specs=pl.BlockSpec((1, bq, h), lambda bn, i, j: (bn, i, 0)),
        out_shape=out_struct((b * n, sq, h), q.dtype, qr, kr, vr, gr),
        scratch_shapes=[pltpu.VMEM((bq, h), jnp.float32)],
        interpret=_interpret(),
    )(qr, kr, vr, gr, lse, delta)
    dq = dq * scale

    def unflatten(x, s):
        return x.reshape(b, n, s, h).transpose(0, 2, 1, 3)
    return unflatten(dq, sq), unflatten(dk, sk), unflatten(dv, sk)


# ---------------------------------------------------------------------------
# public custom-vjp entry
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention_fwd(q, k, v, causal=False, scale=None,
                        block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K):
    """q, k, v: [B, S, N, H] -> out [B, S, N, H]."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    b, sq, n, h = q.shape
    block_q, block_k = _resolve_blocks(q.shape[1], block_q, block_k)
    out, _ = _flash_fwd(q, k, v, causal, scale, block_q, block_k)
    return out.reshape(b, n, sq, h).transpose(0, 2, 1, 3)


def _vjp_fwd(q, k, v, causal, scale, block_q, block_k):
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    b, sq, n, h = q.shape
    block_q, block_k = _resolve_blocks(q.shape[1], block_q, block_k)
    out, lse = _flash_fwd(q, k, v, causal, scale, block_q, block_k)
    res = (q, k, v, out, lse)
    return out.reshape(b, n, sq, h).transpose(0, 2, 1, 3), res


def _vjp_bwd(causal, scale, block_q, block_k, res, g):
    q, k, v, out, lse = res
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    sq, h = q.shape[1], q.shape[3]
    explicit_bq = block_q is not None
    block_q, block_k = _resolve_blocks(q.shape[1], block_q, block_k,
                                       for_bwd=True)
    dq_scratch = sq * h * 4
    if dq_scratch <= _MERGED_BWD_DQ_SCRATCH_LIMIT:
        dq, dk, dv = _flash_bwd_merged(q, k, v, out, lse, g, causal, scale,
                                       block_q, block_k)
    elif dq_scratch <= _MERGED_BWD_DQ_SCRATCH_LIMIT_SMALL_BQ:
        # a [sq, 128] f32 dq accumulator (8 MB at 16k) still fits VMEM if
        # the [bq, bk] f32 tile temporaries shrink with it (measured r5);
        # an explicitly passed block_q overrides this clamp per contract
        bq_small = block_q if explicit_bq else min(block_q, 256)
        dq, dk, dv = _flash_bwd_merged(q, k, v, out, lse, g, causal, scale,
                                       bq_small, block_k)
    else:
        dq, dk, dv = _flash_bwd(q, k, v, out, lse, g, causal, scale,
                                block_q, block_k)
    return dq, dk, dv


flash_attention_fwd.defvjp(_vjp_fwd, _vjp_bwd)
