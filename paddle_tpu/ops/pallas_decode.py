"""Pallas decode-side attention kernels over the KV cache.

Three kernels, all inference-only (no vjp; training uses the
flash-attention kernels of ops/pallas_attention.py):

`paged_decode_attention` (q_len == 1 over the serving engine's paged
arenas) is memory bound: a step reads every live K and V row once and
does a few FLOP a byte, so what it can reach is the HBM bandwidth. Its
unit of work is a TILE of many cache pages (`paged_decode_tile_rows`:
128-512 rows), walked only over the tiles a slot's context reaches.
The arenas stay in HBM; a page of `[num_blocks, block_size, N*H]` is
one contiguous run of bytes, and the kernel copies a tile's live pages
itself (`pltpu.make_async_copy` through the scalar-prefetched block
table) into one of two VMEM buffers while it computes on the other, so
the fetch hides behind the arithmetic and no page past a context is
read. Heads sit on sublanes: q is spread once a slot into `[heads,
N*H]` rows, each holding one head's lanes and zeros elsewhere, so a
tile costs two MXU dots in the arenas' dtype, `qh . K^T` [heads, rows]
and `p @ V` [heads, N*H], every K and V element entering the MXU once,
with the softmax statistics in float32 carried across tiles. On the
v5e it runs at 55-90% of the HBM roofline at the serving cells' shapes
(PERF.md §6, PR 27).

`decode_attention` (q_len == 1 over a dense [B, L, N*H] cache, the
run_generate path) computes the whole masked attention for ALL heads of
one batch row in one program, in the [L, N*H] layout with two constant
0/1 matmuls for the per-head contractions —
    logits[l, n] = sum_h K[l, n*H+h] * q[n*H+h]   = K @ (S * q_col)
    pexp[l, nh]  = probs[l, head_of(nh)]          = probs @ E
with S [NH, 128] selecting each head's lanes into a column and
E [128, NH] expanding a head column back over its lanes. The cache
length is TILED: the grid is (B, nl) and the softmax accumulates online
across L-tiles (running per-head max/denominator in VMEM scratch, the
weighted-value accumulator rescaled by exp(m_prev - m_new) per tile),
as the reference's fused attention loops key tiles
(`paddle/fluid/operators/fused/fmha_ref.h`).

`flash_prefill_chunk` is the serving engine's chunked-prefill attention
over the same arenas, on the same tiles: one request's C queries at
positions p0..p0+C-1 against the pages its table row names, walked
only up to the chunk's last real position (`walk_tiles`, which all the
paged kernels share), causal by position. Heads are taken a 128-lane
COLUMN at a time (`_head_columns`): a 128-lane head is a column, two
64-lane heads share one, their queries stacked into 2C rows with the
other head's lanes zeroed, so a tile costs a column two MXU products in
the arenas' dtype, [Q, lanes] x [lanes, rows] and [Q, rows] x [rows,
lanes], with float32 accumulation and statistics. All columns run in
one grid step on whole-page copies where that fits VMEM, a group of
columns a step on lane-sliced copies where it does not
(`flash_prefill_tiling`). On the v5e a 128-token chunk takes 8-36 us a
layer at the serving cells' shapes, 35-43% of the HBM roofline from a
context of 700 rows on (PERF.md §6, PR 29).

Both paged kernels take grouped-query heads (`kv_heads`: the arenas are
`kv_heads * head_dim` wide and `n_heads // kv_heads` query heads read
each K/V head). The query heads are regrouped outside the kernel into
`group` rows over the arenas' lanes, member i of every K/V head side by
side; `paged_decode` packs every member of every K/V head into one
block of sublanes of the same two products (`paged_decode_head_rows`),
`flash_prefill_chunk` gives each member grid steps of its own. With
`kv_heads == n_heads` both are what they were.

Both paged kernels have a gather+dense fallback that reproduces the
composed einsum math of models/gpt._cached_attention bit for bit, so
CPU serving stays identical to run_generate; it is also the parity
reference the tests and chip_smoke.py hold the kernels to.
"""
import functools
import math

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .kernel_registry import (VMEM_BUDGET as _VMEM_BUDGET,
                              register_kernel, vmem_footprint)

_COLS = 128   # head-column padding (N <= 128 heads)
_SUB = 8      # scratch stat rows padded to the (8, 128) f32 tile minimum
# the most rows one paged-decode tile holds (see paged_decode_tile_rows)
_TILE_ROWS = 512
# the rows a prefill-chunk tile should have (see flash_prefill_tiling)
_PREFILL_ROWS = 256


def _interpret():
    return jax.default_backend() != "tpu"


def _per_row_bytes(hidden, itemsize):
    """KN502-projection bytes per K/V tile row, via the shared
    kernel_registry model (the Kernel Doctor's single source): the raw
    K and V rows are MOVING blocks (double-buffered), and the in-kernel
    f32 casts plus the three [row, COLS] f32 logits/probs/mask
    intermediates ride as temp bytes. Slightly stricter than the
    pre-registry hand formula (which charged the raw rows once and left
    double-buffering to the budget's headroom)."""
    return vmem_footprint(
        moving=[((1, hidden), itemsize)] * 2,
        temp_bytes=2 * hidden * 4 + _COLS * 12)


def decode_attention_supported(max_len, hidden, n_heads, itemsize=2):
    """Single source of truth for when the fused kernel may run —
    callers that pick the cache LAYOUT (GPTModel.init_cache) must use
    this so layout and kernel eligibility can never drift. Since the
    kernel tiles L with online-softmax accumulation (r5), the gate is
    only the TPU tiling constraints plus "one minimal 8-row tile fits
    the VMEM budget" (true for every real model: 13B's hidden 5120
    needs ~0.5 MB per 8 rows)."""
    if max_len % 8 or hidden % 128 or n_heads > _COLS:
        return False
    return _SUB * _per_row_bytes(hidden, itemsize) <= _VMEM_BUDGET


@functools.lru_cache(maxsize=64)
def _pick_bl(L, hidden, itemsize):
    """Largest multiple-of-8 divisor of L whose tile fits the VMEM
    budget (scan is at trace time only)."""
    per_row = _per_row_bytes(hidden, itemsize)
    cap = max(_SUB, min(L, _VMEM_BUDGET // per_row))
    bl = (cap // 8) * 8
    while bl > 8 and L % bl:
        bl -= 8
    return max(bl, 8)


@functools.lru_cache(maxsize=8)
def _seg_mats_np(n_heads, head_dim):
    # cache NUMPY constants: caching jnp arrays would capture a tracer
    # when first called under a trace and leak it into later traces
    nh = n_heads * head_dim
    s = np.zeros((nh, _COLS), np.float32)
    e = np.zeros((_COLS, nh), np.float32)
    for n in range(n_heads):
        s[n * head_dim:(n + 1) * head_dim, n] = 1.0
        e[n, n * head_dim:(n + 1) * head_dim] = 1.0
    return s, e


def _seg_mats(n_heads, head_dim):
    s, e = _seg_mats_np(n_heads, head_dim)
    return jnp.asarray(s), jnp.asarray(e)


def _kernel(q_ref, k_ref, v_ref, mask_ref, s_ref, e_ref, out_ref,
            m_sc, l_sc, acc_sc, *, scale, nl):
    # refs are 4-D blocks of the ORIGINAL [B, L, N, H] buffers (no
    # pre-reshape outside: a reshaped view fed to pallas_call inside the
    # decode while_loop forced a fresh copy of the whole cache per layer
    # per step — measured 16.8k -> 4.2k tok/s); the [L, N*H] collapse of
    # minor dims is layout-free in-kernel
    li = pl.program_id(1)

    @pl.when(li == 0)
    def _init():
        m_sc[:] = jnp.full_like(m_sc, -1e30)
        l_sc[:] = jnp.zeros_like(l_sc)
        acc_sc[:] = jnp.zeros_like(acc_sc)

    q = q_ref[0].astype(jnp.float32)                # [1, NH]
    k = k_ref[0].astype(jnp.float32)                # [BL, NH]
    v = v_ref[0].astype(jnp.float32)                # [BL, NH]
    s = s_ref[...]                                  # [NH, COLS]
    e = e_ref[...]                                  # [COLS, NH]
    # q into head columns: qs[nh, c] = q[nh] * S[nh, c]
    qs = s * q.T                                    # [NH, COLS]
    logits = jax.lax.dot_general(
        k, qs, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32) * scale  # [BL, COLS]
    logits = logits + mask_ref[...]                 # [BL, COLS] additive
    m_prev = m_sc[:1]                               # [1, COLS]
    m_cur = jnp.max(logits, axis=0, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    alpha = jnp.exp(m_prev - m_new)                 # [1, COLS]
    p = jnp.exp(logits - m_new)                     # [BL, COLS]
    l_new = alpha * l_sc[:1] + jnp.sum(p, axis=0, keepdims=True)
    pexp = jax.lax.dot_general(
        p, e, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)         # [BL, NH]
    # alpha per head column expanded over its lanes
    alpha_nh = jax.lax.dot_general(
        alpha, e, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)         # [1, NH]
    acc_sc[:1] = acc_sc[:1] * alpha_nh + jnp.sum(
        pexp * v, axis=0, keepdims=True)            # [1, NH]
    m_sc[:] = jnp.broadcast_to(m_new, m_sc.shape)
    l_sc[:] = jnp.broadcast_to(l_new, l_sc.shape)

    @pl.when(li == nl - 1)
    def _finalize():
        denom = l_sc[:1]                            # [1, COLS]
        denom = jnp.where(denom == 0.0, 1.0, denom)
        denom_nh = jax.lax.dot_general(
            denom, e, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)     # [1, NH]
        out_ref[0] = (acc_sc[:1] / denom_nh).reshape(out_ref.shape[1:])


def paged_decode_tile_rows(block_size, hidden, n_heads, itemsize,
                           max_blocks, group=1):
    """Rows of K and of V that one step of the paged decode kernel
    works on: the tile policy, a pure function of what the arguments'
    shapes show. 0 when no tile fits. `hidden` and `n_heads` are the
    arenas' (the K/V heads); `group` query heads read each.

    A tile is a whole number of cache pages and of 128-lane logits
    columns, so its rows are a multiple of lcm(block_size, 128). Among
    those it is the largest that (a) has at most `_TILE_ROWS` rows: on
    the v5e a step's fixed cost is paid for from 256 rows on, and a
    larger tile only computes more dead rows at a context's end, (b) is
    no longer than the longest context a table can hold, rounded up to
    one unit, and (c) fits `VMEM_BUDGET` with both of its buffers."""
    return tile_rows_within(
        block_size, max_blocks,
        lambda rows: _paged_footprint(rows, hidden, n_heads, itemsize,
                                      group))


def tile_rows_within(block_size, max_blocks, footprint):
    """The tile policy shared by the paged kernels (`paged_decode`, the
    latent kernels of ops/pallas_mla.py): the largest multiple of
    lcm(block_size, 128) rows, at most `_TILE_ROWS` and no longer than
    a table of `max_blocks` reaches, whose `footprint(rows)` fits
    `VMEM_BUDGET`; 0 when none does."""
    unit = math.lcm(int(block_size), _COLS)
    longest = -(-int(max_blocks) * int(block_size) // unit) * unit
    rows = min(max(unit, _TILE_ROWS // unit * unit), longest)
    while rows and footprint(rows) > _VMEM_BUDGET:
        rows -= unit
    return rows


def _packed_rows(itemsize):
    # sublanes of one packed tile: 8 rows of float32, 16 of bf16
    return _SUB * max(1, 4 // itemsize)


def paged_decode_head_rows(kv_heads, group=1):
    """Sublane rows of the paged decode kernel's two products and of its
    softmax: one row for each of a slot's `kv_heads * group` query
    heads, member i of K/V head n in row i * kv_heads + n, the block
    padded once to the 16 sublanes a packed bf16 tile has. A pure
    function of the shapes: rows past `kv_heads * group` are the padding
    every tile computes."""
    return -(-(kv_heads * group) // 16) * 16


def _paged_footprint(rows, hidden, n_heads, itemsize, group=1):
    """KN502 projection of the paged decode kernel at a tile of `rows`:
    K and V tiles in two buffers each (the kernel's own double buffer:
    scratch, so charged once a buffer), q and the output block moving
    with the slot, the per-head accumulator, and the [heads, rows] f32
    logits/probabilities plus the [heads, hidden] product as temps."""
    R = paged_decode_head_rows(n_heads, group)
    return vmem_footprint(
        moving=[((group, hidden), itemsize), ((group, hidden), 4)],
        scratch=[((2, rows, hidden), itemsize)] * 2
        + [((R, hidden), 4), ((R, _COLS), 4), ((R, _COLS), 4)],
        temp_bytes=(3 * R * rows + 2 * R * hidden) * 4)


def paged_decode_kv_rows(ctx_lens, block_size):
    """Rows of K (and of V) a `paged_decode_attention` call fetches a
    layer for these contexts: every page up to the one position `ctx`
    lies in, and no page past it."""
    ctx = np.asarray(ctx_lens)
    return int(((ctx // block_size + 1) * block_size).sum())


def walk_tiles(step, n_steps, last_pos, page_copies, buf_ref, compute,
               *, bs, rows):
    """What the paged kernels share (`paged_decode`,
    `flash_prefill_chunk`, the latent kernels of ops/pallas_mla.py):
    grid step `step` of `n_steps` works through the tiles of `rows`
    cache rows (`rows // bs` pages) up to the one that holds position
    `last_pos(step)`, calling `compute(t, buf)` on tile t once its live
    pages lie in the VMEM buffers' half `buf`. The copies of a tile are
    started before the tile ahead of it is computed (at a step's last
    tile: the next step's first), so a fetch hides behind arithmetic;
    `buf_ref` (SMEM) carries the half the next step starts on.

    `page_copies(s, i, buf, j)` gives the async copies that bring
    logical page i of step s into page j of half `buf`. The last tile's
    dead pages, whose table entries may hold anything, are neither
    fetched nor waited for. A loop, not P copies written out: a step's
    descriptors would be traced and lowered once a layer a program."""
    P = rows // bs

    def each_live_page(s, tile, buf, act):
        n_live = jnp.minimum(P, last_pos(s) // bs - tile * P + 1)

        def page(j, carry):
            for copy in page_copies(s, tile * P + j, buf, j):
                act(copy)
            return carry

        jax.lax.fori_loop(0, n_live, page, 0)

    def start(s, tile, buf):
        each_live_page(s, tile, buf, lambda c: c.start())

    @pl.when(step == 0)
    def _first():
        buf_ref[0] = 0
        start(0, 0, 0)

    buf0 = buf_ref[0]
    n_tiles = last_pos(step) // rows + 1

    def tile_step(t, carry):
        buf = (buf0 + t) % 2
        last = t + 1 == n_tiles

        @pl.when(jnp.logical_or(jnp.logical_not(last), step + 1 < n_steps))
        def _prefetch():
            start(jnp.where(last, step + 1, step),
                  jnp.where(last, 0, t + 1), 1 - buf)

        each_live_page(step, t, buf, lambda c: c.wait())
        compute(t, buf)
        return carry

    jax.lax.fori_loop(0, n_tiles, tile_step, 0)
    buf_ref[0] = (buf0 + n_tiles) % 2


def _paged_kernel(tab_ref, ctx_ref, q_ref, k_hbm, v_hbm, out_ref,
                  k_buf, v_buf, sems, buf_ref, m_sc, l_sc, acc_sc,
                  *, scale, bs, rows, n_heads, head_dim, group):
    """One grid step a SLOT; inside it `walk_tiles` over the tiles that
    the slot's context reaches, `ctx // rows + 1` of them. The arenas
    stay in HBM: a page is one contiguous run found through the
    scalar-prefetched table.

    Heads sit on sublanes: q becomes `qh` [R, N*H], row n holding head
    n's lanes and zeros elsewhere, once a slot. A tile's logits are
    `qh . K^T` [R, rows] and its weighted values `p @ V` [R, N*H], both
    in the arenas' dtype with float32 accumulation; row n of the
    product is head n's output on head n's lanes (the other lanes are
    never read). Softmax statistics are float32, one column a row.

    `n_heads` are the K/V heads. With `group` query heads to each, q
    arrives as [group, N*H], row i holding member i of every K/V head on
    that head's lanes, and the members are packed into one block of
    sublanes (`paged_decode_head_rows`): row i * n_heads + n is member i
    of K/V head n, and the rows past the last member own no lane."""
    b = pl.program_id(0)
    R = acc_sc.shape[0]
    # the rows a member takes: its K/V heads, or all R where it is alone
    Rk = n_heads if group > 1 else R
    nh = n_heads * head_dim
    ctx = ctx_ref[b]

    def page_copies(slot_b, i, buf, j):
        blk = tab_ref[slot_b, i]
        return (pltpu.make_async_copy(
                    k_hbm.at[blk], k_buf.at[buf, j], sems.at[0, buf]),
                pltpu.make_async_copy(
                    v_hbm.at[blk], v_buf.at[buf, j], sems.at[1, buf]))

    @pl.when(b == 0)
    def _zero():
        # p is exactly 0 on a dead row, and 0 * NaN is NaN: rows no copy
        # has written yet must hold numbers
        v_buf[...] = jnp.zeros_like(v_buf)

    m_sc[...] = jnp.full_like(m_sc, -1e30)
    l_sc[...] = jnp.zeros_like(l_sc)
    acc_sc[...] = jnp.zeros_like(acc_sc)
    row = jax.lax.broadcasted_iota(jnp.int32, (R, nh), 0)
    head = row % Rk
    lane = jax.lax.broadcasted_iota(jnp.int32, (R, nh), 1)
    own = jnp.logical_and(lane >= head * head_dim,
                          lane < (head + 1) * head_dim)
    if group * Rk < R:
        own = jnp.logical_and(own, row < group * Rk)
    q = q_ref[0].astype(jnp.float32)                      # [group, NH]
    if group > 1:
        member = row // Rk
        qg, q = q, jnp.broadcast_to(q[:1], (R, nh))
        for i in range(1, group):
            q = jnp.where(member == i, qg[i:i + 1], q)
    qh = jnp.where(own, q, 0.0).astype(k_buf.dtype)       # [R, NH]

    def compute(t, buf):
        logits = jax.lax.dot_general(
            qh, k_buf[buf].reshape(rows, nh), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # [R, rows]
        pos = t * rows + jax.lax.broadcasted_iota(
            jnp.int32, (R, rows), 1)
        logits = jnp.where(pos <= ctx, logits, -1e30)
        m_prev = m_sc[:, :1]                              # [R, 1]
        m_new = jnp.maximum(
            m_prev, jnp.max(logits, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(logits - m_new)                       # [R, rows]
        l_new = alpha * l_sc[:, :1] + jnp.sum(p, axis=1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(v_buf.dtype), v_buf[buf].reshape(rows, nh),
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)           # [R, NH]
        acc_sc[...] = acc_sc[...] * alpha + pv
        m_sc[...] = jnp.broadcast_to(m_new, m_sc.shape)
        l_sc[...] = jnp.broadcast_to(l_new, l_sc.shape)

    walk_tiles(b, pl.num_programs(0), lambda s: ctx_ref[s], page_copies,
               buf_ref, compute, bs=bs, rows=rows)
    # every slot's first tile holds position 0, so no denominator is 0
    # but those of the padding rows past the last head
    denom = jnp.where(l_sc[:, :1] == 0.0, 1.0, l_sc[:, :1])
    out = jnp.where(own, acc_sc[...] / denom, 0.0)
    for i in range(group):                                # [1, NH] each
        out_ref[0, i:i + 1] = jnp.sum(out[i * Rk:(i + 1) * Rk],
                                      axis=0, keepdims=True)


def paged_decode_supported(block_size, hidden, n_heads, itemsize=2,
                           max_blocks=_COLS, group=1):
    """Gate for the fused PAGED decode kernel (the block-pool serving
    cache, paddle_tpu/serving/kv_cache.py): a page is a whole number of
    the dtype's packed sublane tiles (8 rows of float32, 16 of bf16) so
    that a page copy lands tile-aligned, the lanes are whole, and the
    tile policy finds a tile that fits VMEM. `hidden` and `n_heads` are
    the arenas' (the K/V heads), `group` the query heads to each."""
    if block_size % _packed_rows(itemsize) or hidden % _COLS \
            or n_heads * group > _COLS:
        return False
    return paged_decode_tile_rows(
        block_size, hidden, n_heads, itemsize, max_blocks, group) > 0


def _paged_example(rng):
    """Randomized in-support paged config (kernel_lint KN504): distinct
    physical blocks per row, tails at the null block 0; a K/V head read
    by 1, 2, 4 or 8 query heads, whose rows the kernel packs."""
    H = 32
    nh = 128 * (1 if rng.integers(2) else 2)    # arenas 128 or 256 lanes
    Nk = nh // H
    N = Nk * int(rng.choice([1, 2, 4, 8]))
    bs = 16
    S = int(rng.choice([2, 3]))
    mb = int(rng.integers(2, 4))
    num_blocks = S * mb + 1
    ctx = rng.integers(0, mb * bs - 1, size=S).astype(np.int32)
    tables = np.zeros((S, mb), np.int32)
    for s in range(S):
        n_alloc = int(ctx[s]) // bs + 1
        for i in range(n_alloc):
            tables[s, i] = 1 + s * mb + i
    q = 0.1 * rng.standard_normal((S, 1, N * H)).astype(np.float32)
    kp = 0.1 * rng.standard_normal((num_blocks, bs, nh)).astype(np.float32)
    vp = 0.1 * rng.standard_normal((num_blocks, bs, nh)).astype(np.float32)
    return (q, kp, vp, tables, ctx, N), {"use_kernel": True, "kv_heads": Nk}


def _paged_fallback(q, k_pages, v_pages, block_tables, ctx_lens,
                    n_heads, use_kernel=None, **kw):
    # the in-function gather+dense path IS the declared exact fallback
    return paged_decode_attention(q, k_pages, v_pages, block_tables,
                                  ctx_lens, n_heads, use_kernel=False, **kw)


def _regroup(q, kv_heads, group):
    """q [A, T, N*H] with N = kv_heads * group -> [A, group, T,
    kv_heads*H]: row i holds member i of every K/V head's query heads,
    side by side on that K/V head's lanes."""
    A, T, nh = q.shape
    H = nh // (kv_heads * group)
    return jnp.transpose(q.reshape(A, T, kv_heads, group, H),
                         (0, 3, 1, 2, 4)).reshape(A, group, T, kv_heads * H)


def _ungroup(o, kv_heads, group):
    """The inverse of `_regroup`: [A, group, T, kv_heads*H] -> [A, T,
    N*H] with the query heads in their own order."""
    A, _, T, wk = o.shape
    H = wk // kv_heads
    return jnp.transpose(o.reshape(A, group, T, kv_heads, H),
                         (0, 2, 3, 1, 4)).reshape(A, T, group * wk)


@register_kernel(
    "paged_decode", example=_paged_example, fallback=_paged_fallback,
    tol=(1e-3, 1e-3),
    notes="one grid step a slot (sequential: the tile buffers and their "
          "in-flight copies pass from slot to slot); the arenas stay in "
          "HBM and the kernel copies the live pages of each tile itself "
          "through the scalar-prefetched table (KN505 covers the "
          "prefetch channel)")
# jitted on its own so that a model's layers, which all call it on the
# same shapes, share one trace and one lowering of the kernel: traced
# once a layer, the decode programs' 24 or 48 copies cost seconds of
# every start, compile cache or not
@functools.partial(jax.jit, static_argnames=("n_heads", "use_kernel",
                                             "kv_heads", "scale", "name"))
def paged_decode_attention(q, k_pages, v_pages, block_tables, ctx_lens,
                           n_heads, use_kernel=None, kv_heads=None,
                           scale=None, name="paged_decode"):
    """Decode attention (q_len == 1) over a PAGED KV cache.

    q [S, 1, N*H]; k_pages/v_pages [num_blocks, block_size, Nk*H] — the
    shared physical arenas, Nk = `kv_heads` (default N: as many K/V
    heads as query heads; fewer: query head n reads K/V head
    n // (N // Nk)); `scale` on the scores (default H ** -0.5);
    block_tables [S, max_blocks] int32 mapping
    each row's logical block i to a physical block (unallocated tail
    entries point at the reserved null block 0); ctx_lens [S] int32 —
    each row's current position (keys at logical positions 0..ctx are
    valid, matching `off` in `decode_attention`). `name` is the
    kernel's name in a device trace: a caller whose arenas are not the
    block pool's (a window layer's rings, one `window`-row page a
    request: `models/exaone_moe.py`) gives its own, so that the trace
    tells the two apart. Returns [S, 1, N*H] in q's dtype.

    Two paths, one contract:
    - fused Pallas kernel (TPU + `paged_decode_supported`): tiles of
      `paged_decode_tile_rows` rows stream through VMEM with online
      softmax, only over the pages each context reaches — the cache is
      never materialized contiguously and no dead page is read;
    - gather+dense fallback everywhere else: gather the physical
      blocks into a dense [S, L, N, H] view and run the SAME composed
      masked-attention math as models/gpt._cached_attention, so a CPU
      serving engine is token-for-token identical to `run_generate`.
    """
    S, one, nh = q.shape
    if one != 1:
        raise ValueError("paged_decode_attention is q_len==1 only")
    N = n_heads
    H = nh // N
    Nk = N if kv_heads is None else int(kv_heads)
    if N % Nk:
        raise ValueError(f"{N} query heads over {Nk} K/V heads")
    G, wk = N // Nk, Nk * H
    num_blocks, bs, _ = k_pages.shape
    mb = block_tables.shape[1]
    scale = 1.0 / float(np.sqrt(H)) if scale is None else float(scale)
    itemsize = k_pages.dtype.itemsize
    if use_kernel is None:
        use_kernel = (jax.default_backend() == "tpu"
                      and paged_decode_supported(bs, wk, Nk, itemsize, mb,
                                                 G))
    if not use_kernel:
        # gather+dense: EXACTLY the composed einsum path of
        # models/gpt._cached_attention (dtypes included) over the
        # gathered pages — bit-parity with the dense decode cache is
        # what makes the CPU serving smoke token-identical
        L = mb * bs
        k4 = k_pages[block_tables].reshape(S, L, Nk, H)
        v4 = v_pages[block_tables].reshape(S, L, Nk, H)
        if G > 1:       # each K/V head under the query heads that read it
            k4, v4 = jnp.repeat(k4, G, axis=2), jnp.repeat(v4, G, axis=2)
        q4 = q.reshape(S, 1, N, H)
        logits = jnp.einsum("bqnh,bknh->bnqk", q4, k4.astype(q.dtype),
                            preferred_element_type=jnp.float32) * scale
        key_pos = jnp.arange(L, dtype=jnp.int32)[None, None, None, :]
        logits = jnp.where(key_pos <= ctx_lens[:, None, None, None],
                           logits, -1e30)
        probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
        out = jnp.einsum("bnqk,bknh->bqnh", probs, v4.astype(q.dtype))
        return out.reshape(S, 1, nh)

    rows = paged_decode_tile_rows(bs, wk, Nk, itemsize, mb, G)
    if not rows:
        raise ValueError(
            f"paged_decode kernel: no tile of {bs}-row pages at width "
            f"{wk} fits VMEM (see paged_decode_supported)")
    R = paged_decode_head_rows(Nk, G)
    # [S, G, wk]: as it is where every query head has its own K/V head
    qg = _regroup(q, Nk, G)[:, :, 0] if G > 1 else q
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(S,),
        in_specs=[
            pl.BlockSpec((1, G, wk), lambda b, tab, ctx: (b, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, G, wk), lambda b, tab, ctx: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, rows // bs, bs, wk), k_pages.dtype),
            pltpu.VMEM((2, rows // bs, bs, wk), v_pages.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SMEM((1,), jnp.int32),
            pltpu.VMEM((R, _COLS), jnp.float32),
            pltpu.VMEM((R, _COLS), jnp.float32),
            pltpu.VMEM((R, wk), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_paged_kernel, scale=scale, bs=bs, rows=rows,
                          n_heads=Nk, head_dim=H, group=G),
        name=name,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, G, wk), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=_interpret(),
    )(block_tables.astype(jnp.int32), ctx_lens.astype(jnp.int32),
      qg, k_pages, v_pages)
    if G > 1:
        out = _ungroup(out[:, :, None], Nk, G)
    return out.astype(q.dtype)


def _head_columns(hidden, n_heads):
    """(lanes of a head COLUMN, heads in it): a head of 128 lanes or a
    multiple is a column of its own; narrower heads share one 128-lane
    column, 128 // H of them side by side. (0, 0) when the heads do not
    tile the lanes that way."""
    if n_heads <= 0 or hidden % n_heads:
        return 0, 0
    H = hidden // n_heads
    if H % _COLS == 0:
        return H, 1
    if _COLS % H or n_heads % (_COLS // H):
        return 0, 0
    return _COLS, _COLS // H


def _prefill_footprint(rows, chunk, width, lanes, heads, itemsize):
    """KN502 projection of the prefill-chunk kernel at a tile of `rows`
    over a group of `width` lanes (columns of `lanes` lanes holding
    `heads` heads each): K and V tiles in two buffers each (the
    kernel's own double buffer), q and the output block moving with
    the group, per column the accumulator and the two statistics over
    its `heads * chunk` query rows, and a column's [query rows, rows]
    f32 logits/probabilities/mask plus its spread q and product as
    temps."""
    Q, cols = heads * chunk, width // lanes
    return vmem_footprint(
        moving=[((chunk, width), itemsize)] * 2,
        scratch=[((2, rows, width), itemsize)] * 2
        + [((cols, Q, lanes), 4)] + [((cols, Q, _COLS), 4)] * 2,
        temp_bytes=(3 * Q * rows + 3 * Q * lanes) * 4)


def flash_prefill_tiling(block_size, chunk, hidden, n_heads, itemsize,
                         max_blocks):
    """(lanes a grid step of the prefill-chunk kernel works on, rows of
    its K and V tiles): the kernel's policy, a pure function of what
    the arguments' shapes show. (0, 0) when the heads do not tile the
    lanes or nothing fits.

    All heads a step where `tile_rows_within` finds them a tile of
    `_PREFILL_ROWS` rows or the table's whole reach: the page copies
    are then whole pages, one contiguous run each, and a tile's mask is
    built once for every head. A wider model splits its head columns
    into the fewest equal groups that get such a tile; where none does,
    the single column takes what fits."""
    lanes, heads = _head_columns(hidden, n_heads)
    if not lanes:
        return 0, 0
    cols = hidden // lanes
    want = min(_PREFILL_ROWS,
               tile_rows_within(block_size, max_blocks, lambda rows: 0))
    for per_step in (c for c in range(cols, 0, -1) if cols % c == 0):
        width = per_step * lanes
        rows = tile_rows_within(
            block_size, max_blocks, lambda r: _prefill_footprint(
                r, chunk, width, lanes, heads, itemsize))
        if rows >= want or (rows and per_step == 1):
            return width, rows
    return 0, 0


def flash_prefill_kv_rows(p0, n_real, block_size):
    """Rows of K (and of V) a `flash_prefill_chunk` call fetches a
    layer for the chunk whose `n_real` real positions start at `p0`:
    every page up to the one position `p0 + n_real - 1` lies in, and no
    page past it."""
    return ((int(p0) + int(n_real) - 1) // block_size + 1) * block_size


def _prefill_kernel(tab_ref, span_ref, q_ref, k_hbm, v_hbm, out_ref,
                    k_buf, v_buf, sems, buf_ref, m_sc, l_sc, acc_sc,
                    *, scale, bs, rows, lanes, heads, group):
    """One grid step a GROUP of head columns (all of them where they
    fit: `flash_prefill_tiling`); inside it `walk_tiles` over the tiles
    up to the chunk's last real position `span[1]`, each tile's live
    pages copied from the HBM arenas through the scalar-prefetched
    table row.

    A column is `lanes` lanes of `heads` heads. Its C queries become
    Q = heads * C rows, block h holding head h's lanes and zeros
    elsewhere, so that a tile costs a column two MXU products in the
    arenas' dtype, `q . K^T` [Q, rows] and `p @ V` [Q, lanes], with
    float32 accumulation and float32 softmax statistics a row; block h
    of the product is head h's output on head h's lanes. Causal by
    position: query row r stands at `p0 + r % C`, a padding query past
    the last real one at that one's position. Every tile is masked: a
    second body for the tiles wholly below `p0` measured no faster
    (PERF.md section 6, PR 29).

    With `group` query heads to a K/V head, q is [group, C, lanes of the
    arenas], member i of every K/V head on that head's lanes, and the
    grid runs the members of a group of columns one after another: step
    s works on member s % group of column group s // group, and fetches
    that group's K and V again."""
    g = pl.program_id(0)
    _, C, W = q_ref.shape
    Q, cols = heads * C, W // lanes
    p0, last = span_ref[0], span_ref[1]

    def page_copies(s, i, buf, j):
        blk = tab_ref[i]
        at = (blk,) if W == k_hbm.shape[2] else \
            (blk, slice(None),
             pl.ds(pl.multiple_of(s // group * W, _COLS), W))
        return (pltpu.make_async_copy(
                    k_hbm.at[at], k_buf.at[buf, j], sems.at[0, buf]),
                pltpu.make_async_copy(
                    v_hbm.at[at], v_buf.at[buf, j], sems.at[1, buf]))

    @pl.when(g == 0)
    def _zero():
        # p is exactly 0 on a dead row, and 0 * NaN is NaN: rows no copy
        # has written yet must hold numbers
        v_buf[...] = jnp.zeros_like(v_buf)

    m_sc[...] = jnp.full_like(m_sc, -1e30)
    l_sc[...] = jnp.zeros_like(l_sc)
    acc_sc[...] = jnp.zeros_like(acc_sc)
    qpos = jnp.minimum(last, p0 + jax.lax.broadcasted_iota(
        jnp.int32, (Q, rows), 0) % C)
    kcol = jax.lax.broadcasted_iota(jnp.int32, (Q, rows), 1)
    if heads > 1:
        own = jax.lax.broadcasted_iota(jnp.int32, (Q, lanes), 0) // C \
            == jax.lax.broadcasted_iota(jnp.int32, (Q, lanes), 1) \
            // (lanes // heads)

    def column(c):
        return pl.ds(c * lanes, lanes)

    def queries(c):
        q = q_ref[0, :, column(c)]                        # [C, lanes]
        if heads == 1:
            return q
        q = jnp.concatenate([q.astype(jnp.float32)] * heads, axis=0)
        return jnp.where(own, q, 0.0).astype(q_ref.dtype)  # [Q, lanes]

    def compute(t, buf):
        live = t * rows + kcol <= qpos      # once a tile, every column's
        for c in range(cols):
            k = k_buf[buf, :, :, column(c)].reshape(rows, lanes)
            v = v_buf[buf, :, :, column(c)].reshape(rows, lanes)
            s = jax.lax.dot_general(
                queries(c), k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale  # [Q, rows]
            s = jnp.where(live, s, -1e30)
            m_prev = m_sc[c, :, :1]                       # [Q, 1]
            m_new = jnp.maximum(
                m_prev, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)                        # [Q, rows]
            l_new = alpha * l_sc[c, :, :1] + jnp.sum(
                p, axis=1, keepdims=True)
            pv = jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)       # [Q, lanes]
            acc_sc[c] = acc_sc[c] * alpha + pv
            m_sc[c] = jnp.broadcast_to(m_new, m_sc.shape[1:])
            l_sc[c] = jnp.broadcast_to(l_new, l_sc.shape[1:])

    walk_tiles(g, pl.num_programs(0), lambda s: last, page_copies,
               buf_ref, compute, bs=bs, rows=rows)
    for c in range(cols):
        # the first tile holds position 0, which every query attends
        o = acc_sc[c] / l_sc[c, :, :1]                    # [Q, lanes]
        if heads > 1:
            o = jnp.where(own, o, 0.0)
            o = sum(o[h * C:(h + 1) * C] for h in range(heads))
        out_ref[0, :, column(c)] = o.astype(out_ref.dtype)


def flash_prefill_supported(block_size, chunk, hidden, n_heads,
                            itemsize=2, max_blocks=_COLS):
    """Gate for the fused flash prefill-chunk kernel: pages and the
    chunk are whole packed sublane tiles of the dtype (8 rows of
    float32, 16 of bf16), the heads tile the 128-lane columns
    (`_head_columns`), and the tile policy finds a tile that fits
    VMEM."""
    sub = _packed_rows(itemsize)
    if block_size % sub or chunk % sub or hidden % _COLS:
        return False
    return flash_prefill_tiling(block_size, chunk, hidden, n_heads,
                                itemsize, max_blocks)[1] > 0


def _prefill_example(rng):
    """Randomized in-support prefill-chunk config (kernel_lint KN504):
    a chunk resuming at a random offset over a small paged arena."""
    N, H = 4, 32
    nh = N * H
    bs = 16
    C = 16
    mb = int(rng.integers(2, 4))
    num_blocks = mb + 2
    p0 = np.int32(rng.integers(0, mb * bs - C + 1))
    table_row = np.arange(1, mb + 1, dtype=np.int32)
    q = 0.1 * rng.standard_normal((1, C, nh)).astype(np.float32)
    kp = 0.1 * rng.standard_normal((num_blocks, bs, nh)).astype(np.float32)
    vp = 0.1 * rng.standard_normal((num_blocks, bs, nh)).astype(np.float32)
    return (q, kp, vp, table_row, p0, N), {"use_kernel": True}


def _prefill_fallback(q, k_pages, v_pages, table_row, p0, n_heads,
                      use_kernel=None, **kw):
    # the in-function gather+dense path IS the declared exact fallback
    return flash_prefill_chunk(q, k_pages, v_pages, table_row, p0,
                               n_heads, use_kernel=False, **kw)


@register_kernel(
    "flash_prefill_chunk", example=_prefill_example,
    fallback=_prefill_fallback, tol=(1e-3, 1e-3),
    notes="one grid step a group of head columns (sequential: the tile "
          "buffers and their in-flight copies pass from group to "
          "group); the arenas stay in HBM and the kernel copies the "
          "live pages of each tile itself through the scalar-prefetched "
          "table row, up to the chunk's last real position")
# jitted on its own, as paged_decode_attention is: a model's layers
# share one trace and one lowering of the kernel
@functools.partial(jax.jit, static_argnames=("n_heads", "use_kernel",
                                             "kv_heads", "scale"))
def flash_prefill_chunk(q, k_pages, v_pages, table_row, p0, n_heads,
                        use_kernel=None, n_real=None, kv_heads=None,
                        scale=None):
    """Chunked-prefill attention over a PAGED KV cache.

    q [1, C, N*H] — the chunk's queries at positions p0..p0+C-1;
    k_pages/v_pages [num_blocks, block_size, Nk*H] — the shared
    physical arenas, already holding this chunk's own K/V (callers
    write before attending), Nk = `kv_heads` (default N; fewer: query
    head n reads K/V head n // (N // Nk)); `scale` on the scores
    (default H ** -0.5); table_row [max_blocks] int32 — ONE
    request's logical->physical block map; p0 scalar int32 — the
    chunk's first position (a TRACED scalar: prefix-cache hits resume
    prefill at arbitrary offsets without widening the compile-signature
    family); n_real scalar int32 — how many of the C positions are
    real (all of them when None): what the queries past them return is
    finite and means nothing. Returns [1, C, N*H] in q's dtype.

    Two paths, one contract:
    - fused Pallas kernel (TPU + `flash_prefill_supported`): tiles of
      `flash_prefill_tiling` rows stream through VMEM with online
      softmax, only over the pages up to position p0 + n_real - 1 —
      the [C, ctx] score matrix is never materialized, no page past
      that position is read and table entries past it may hold
      anything (Sarathi-style compute-dense prefill chunks over a
      paged arena);
    - gather+dense fallback everywhere else: gather the pages into a
      dense [1, L, N, H] view and run the SAME composed masked einsum
      math as models/gpt._cached_attention's prefill branch, so a CPU
      serving engine stays bit-identical to `run_generate`.
    """
    one, C, nh = q.shape
    if one != 1:
        raise ValueError("flash_prefill_chunk takes one request's chunk")
    N = n_heads
    H = nh // N
    Nk = N if kv_heads is None else int(kv_heads)
    if N % Nk:
        raise ValueError(f"{N} query heads over {Nk} K/V heads")
    G, wk = N // Nk, Nk * H
    num_blocks, bs, _ = k_pages.shape
    mb = table_row.shape[0]
    scale = 1.0 / float(np.sqrt(H)) if scale is None else float(scale)
    itemsize = k_pages.dtype.itemsize
    if use_kernel is None:
        use_kernel = (jax.default_backend() == "tpu"
                      and flash_prefill_supported(bs, C, wk, Nk, itemsize,
                                                  mb))
    if not use_kernel:
        # gather+dense: EXACTLY the composed einsum prefill math of
        # models/gpt._cached_attention over the gathered pages —
        # bit-parity with the dense path keeps CPU engine streams
        # token-identical to run_generate
        L = mb * bs
        k4 = k_pages[table_row].reshape(1, L, Nk, H)
        v4 = v_pages[table_row].reshape(1, L, Nk, H)
        if G > 1:       # each K/V head under the query heads that read it
            k4, v4 = jnp.repeat(k4, G, axis=2), jnp.repeat(v4, G, axis=2)
        logits = jnp.einsum("bqnh,bknh->bnqk", q.reshape(1, C, N, H),
                            k4.astype(q.dtype),
                            preferred_element_type=jnp.float32) * scale
        key_pos = jnp.arange(L, dtype=jnp.int32)[None, None, None, :]
        q_pos = (p0 + jnp.arange(C, dtype=jnp.int32))[None, None, :, None]
        logits = jnp.where(key_pos <= q_pos, logits, -1e30)
        probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
        out = jnp.einsum("bnqk,bknh->bqnh", probs, v4.astype(q.dtype))
        return out.reshape(1, C, nh)

    W, rows = flash_prefill_tiling(bs, C, wk, Nk, itemsize, mb)
    if not rows:
        raise ValueError(
            f"flash_prefill_chunk kernel: no tile of {bs}-row pages "
            f"under {Nk} heads of {H} lanes and a chunk of {C} fits VMEM "
            "(see flash_prefill_supported)")
    lanes, heads = _head_columns(wk, Nk)
    Q, cols = heads * C, W // lanes
    p0 = jnp.asarray(p0, jnp.int32)
    last = jnp.clip(p0 + (C if n_real is None else n_real) - 1,
                    0, mb * bs - 1)
    # [G, C, wk]: as it is where every query head has its own K/V head
    qg = _regroup(q, Nk, G)[0] if G > 1 else q
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(wk // W * G,),
        in_specs=[
            pl.BlockSpec((1, C, W), lambda s, tab, span: (s % G, 0, s // G)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, C, W),
                               lambda s, tab, span: (s % G, 0, s // G)),
        scratch_shapes=[
            pltpu.VMEM((2, rows // bs, bs, W), k_pages.dtype),
            pltpu.VMEM((2, rows // bs, bs, W), v_pages.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SMEM((1,), jnp.int32),
            pltpu.VMEM((cols, Q, _COLS), jnp.float32),
            pltpu.VMEM((cols, Q, _COLS), jnp.float32),
            pltpu.VMEM((cols, Q, lanes), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_prefill_kernel, scale=scale, bs=bs, rows=rows,
                          lanes=lanes, heads=heads, group=G),
        name="flash_prefill_chunk",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((G, C, wk), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=_interpret(),
    )(table_row.astype(jnp.int32), jnp.stack([p0, last]),
      qg.astype(k_pages.dtype), k_pages, v_pages)
    return _ungroup(out[None], Nk, G) if G > 1 else out


def window_ring_positions(p0, window):
    """The position each of a ring's `window` rows holds before a chunk
    that starts at `p0`: the largest p < p0 with p % window == row,
    negative where the request has written no such position yet (so at
    p0 == 0 every row, whatever its last owner left there)."""
    row = jnp.arange(window, dtype=jnp.int32)
    base = p0 // window * window
    return base + row - jnp.where(row < p0 - base, 0, window)


def window_ring_write(ring, row, chunk, p0, n_real):
    """`ring` [rows + 1, window, w] with request row `row` taking in the
    chunk's rows `chunk` [C, w] at positions p0.., the first `n_real`
    real: afterwards row r of the ring holds the largest position
    <= p0 + n_real - 1 that is r modulo `window`, from the chunk where
    that position is the chunk's, as it was where it is older."""
    window = ring.shape[1]
    r = jnp.arange(window, dtype=jnp.int32)
    last = p0 + n_real - 1
    pos = last - (last - r) % window
    new = jnp.where((pos >= p0)[:, None],
                    chunk[jnp.clip(pos - p0, 0, chunk.shape[0] - 1)]
                    .astype(ring.dtype), ring[row])
    return ring.at[row].set(new)


def _window_kernel(at_ref, q_ref, kp_ref, vp_ref, ko_ref, vo_ref, rk_ref,
                   rv_ref, out_ref, *, scale, window, head_dim, group):
    """Grid (K/V head g, query tile t of `window` rows). A query at
    chunk row i sees the keys at rows i - window + 1 .. i: tile t sees
    its own tile of the chunk's keys and the tile before it, which for
    tile 0 is the ring (the request's last `window` positions before the
    chunk). Two products a query head, `q . [K_prev | K_own]^T`
    [window, 2 window] and `p @ [V_prev | V_own]` [window, H], masked by
    position: visible iff the key's position is >= 0 and within
    `window` behind the query's, the query's own included."""
    t = pl.program_id(1)
    p0, n_real = at_ref[1], at_ref[2]
    W, H = window, head_dim

    @pl.when(t * W >= n_real)
    def _dead():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(t * W < n_real)
    def _live():
        first = t == 0
        kk = jnp.concatenate(
            [jnp.where(first, rk_ref[0], kp_ref[...]), ko_ref[...]], axis=0)
        vv = jnp.concatenate(
            [jnp.where(first, rv_ref[0], vp_ref[...]), vo_ref[...]], axis=0)
        col = jax.lax.broadcasted_iota(jnp.int32, (W, 2 * W), 1)
        base = p0 // W * W
        ring_pos = base + col - jnp.where(col < p0 - base, 0, W)
        kpos = jnp.where(jnp.logical_and(first, col < W), ring_pos,
                         p0 + (t - 1) * W + col)
        qpos = p0 + t * W + jax.lax.broadcasted_iota(
            jnp.int32, (W, 2 * W), 0)
        seen = jnp.logical_and(
            kpos >= 0, jnp.logical_and(kpos <= qpos, qpos - kpos < W))
        real = t * W + jax.lax.broadcasted_iota(
            jnp.int32, (W, H), 0) < n_real
        exact = jax.lax.Precision.HIGHEST \
            if q_ref.dtype == jnp.float32 else None
        for i in range(group):
            s = jax.lax.dot_general(
                q_ref[:, i * H:(i + 1) * H], kk, (((1,), (1,)), ((), ())),
                precision=exact,
                preferred_element_type=jnp.float32) * scale   # [W, 2W]
            s = jnp.where(seen, s, -1e30)
            p = jnp.exp(s - jnp.max(s, axis=1, keepdims=True))
            o = jax.lax.dot_general(
                p.astype(vv.dtype), vv, (((1,), (0,)), ((), ())),
                precision=exact,
                preferred_element_type=jnp.float32)           # [W, H]
            # a query sees its own key: no denominator is 0; the rows
            # past the last real position come out 0, as from a dead tile
            o = o / jnp.sum(p, axis=1, keepdims=True)
            out_ref[:, i * H:(i + 1) * H] = jnp.where(
                real, o, 0.0).astype(out_ref.dtype)


def window_prefill_supported(chunk, window, head_dim, itemsize=2):
    """Gate for the fused window prefill-chunk kernel: a query tile is
    `window` rows, so the chunk is a whole number of windows; a tile's
    scores are [window, 2 window] and a head a lane column, so both are
    whole 128-lane tiles."""
    return chunk % window == 0 and window % _COLS == 0 \
        and head_dim % _COLS == 0 and window % _packed_rows(itemsize) == 0


def _window_example(rng):
    """A chunk that resumes in the middle of a window over a ring that a
    longer request left full (kernel_lint KN504)."""
    N, Nk, H, W, C = 4, 2, 128, 128, 256
    f = lambda *shape: 0.3 * rng.standard_normal(shape).astype(np.float32)
    p0 = np.int32(rng.integers(0, 3 * W))
    n_real = np.int32(rng.integers(1, C + 1))
    return (f(C, N * H), f(C, Nk * H), f(C, Nk * H), f(3, W, Nk * H),
            f(3, W, Nk * H), np.int32(2), p0, N), \
        {"n_real": n_real, "kv_heads": Nk, "use_kernel": True}


def _window_fallback(q, k, v, ring_k, ring_v, row, p0, n_heads,
                     use_kernel=None, **kw):
    return window_prefill_chunk(q, k, v, ring_k, ring_v, row, p0, n_heads,
                                use_kernel=False, **kw)


@register_kernel(
    "window_prefill_chunk", example=_window_example,
    fallback=_window_fallback, tol=(2e-3, 2e-3),
    notes="sliding-window attention of one chunk: grid (K/V head, query "
          "tile of `window` rows), each tile against its own tile of the "
          "chunk's keys and the one before it, the request's ring for "
          "tile 0; rows past n_real come out 0 from either path")
@functools.partial(jax.jit, static_argnames=("n_heads", "use_kernel",
                                             "kv_heads", "scale"))
def window_prefill_chunk(q, k, v, ring_k, ring_v, row, p0, n_heads,
                         use_kernel=None, n_real=None, kv_heads=None,
                         scale=None):
    """Chunked-prefill attention of a layer that attends over its last
    `window` positions, the query's own included.

    q [C, N*H] — the chunk's queries at positions p0..p0+C-1; k, v
    [C, Nk*H] — the chunk's own keys and values, from the activations
    (keys already rotated, where the layer rotates); ring_k, ring_v
    [rows + 1, window, Nk*H] — the rings a request each
    (`kv_cache.window_kind`): row `row` holds this request's positions
    before p0, position p in ring row p % window, and `window` is read
    off their shape; which of its rows are valid follows from p0
    (`window_ring_positions`), so what the row's last owner left is
    never seen. Nk = `kv_heads` (default N); `scale` on the scores
    (default H ** -0.5); n_real: how many of the C positions are real
    (the rows past them come out 0). The rings are
    read, not written: the caller puts the chunk's rows in afterwards
    (`window_ring_write`). Returns [C, N*H] in q's dtype.

    The fused kernel (TPU + `window_prefill_supported`) never forms the
    [C, window + C] scores: a query tile of `window` rows meets two key
    tiles. Everywhere else the same masked softmax is composed over the
    ring and the chunk side by side."""
    C, nh = q.shape
    N = n_heads
    H = nh // N
    Nk = N if kv_heads is None else int(kv_heads)
    if N % Nk:
        raise ValueError(f"{N} query heads over {Nk} K/V heads")
    G = N // Nk
    W = ring_k.shape[1]
    scale = 1.0 / float(np.sqrt(H)) if scale is None else float(scale)
    p0 = jnp.asarray(p0, jnp.int32)
    n_real = jnp.asarray(C if n_real is None else n_real, jnp.int32)
    if use_kernel is None:
        use_kernel = (jax.default_backend() == "tpu"
                      and window_prefill_supported(C, W, H, q.dtype.itemsize))
    if not use_kernel:
        kk = jnp.concatenate([ring_k[row].astype(q.dtype), k], axis=0)
        vv = jnp.concatenate([ring_v[row].astype(q.dtype), v], axis=0)
        kpos = jnp.concatenate([window_ring_positions(p0, W),
                                p0 + jnp.arange(C, dtype=jnp.int32)])
        qpos = (p0 + jnp.arange(C, dtype=jnp.int32))[:, None]
        seen = (kpos >= 0) & (kpos <= qpos) & (qpos - kpos < W)
        exact = jax.lax.Precision.HIGHEST if q.dtype == jnp.float32 else None
        s = jnp.einsum("tkgh,skh->kgts", q.reshape(C, Nk, G, H),
                       kk.reshape(W + C, Nk, H), precision=exact,
                       preferred_element_type=jnp.float32) * scale
        probs = jax.nn.softmax(jnp.where(seen, s, -1e30), axis=-1)
        o = jnp.einsum("kgts,skh->tkgh", probs.astype(q.dtype),
                       vv.reshape(W + C, Nk, H), precision=exact,
                       preferred_element_type=jnp.float32)
        real = jnp.arange(C, dtype=jnp.int32)[:, None] < n_real
        return jnp.where(real, o.reshape(C, nh), 0.0).astype(q.dtype)

    if C % W:
        raise ValueError(f"window_prefill_chunk kernel: a chunk of {C} is "
                         f"no whole number of windows of {W}")
    prev = lambda g, t, at: (jnp.maximum(t - 1, 0), g)
    own = lambda g, t, at: (t, g)
    ring = lambda g, t, at: (at[0], 0, g)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(Nk, C // W),
        in_specs=[
            pl.BlockSpec((W, G * H), own),
            pl.BlockSpec((W, H), prev), pl.BlockSpec((W, H), prev),
            pl.BlockSpec((W, H), own), pl.BlockSpec((W, H), own),
            pl.BlockSpec((1, W, H), ring), pl.BlockSpec((1, W, H), ring),
        ],
        out_specs=pl.BlockSpec((W, G * H), own),
    )
    return pl.pallas_call(
        functools.partial(_window_kernel, scale=scale, window=W,
                          head_dim=H, group=G),
        name="window_prefill_chunk",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((C, nh), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=_interpret(),
    )(jnp.stack([jnp.asarray(row, jnp.int32), p0, n_real]),
      q, k.astype(q.dtype), v.astype(q.dtype), k.astype(q.dtype),
      v.astype(q.dtype), ring_k.astype(q.dtype), ring_v.astype(q.dtype))


def _decode_example(rng):
    N = int(rng.choice([4, 8]))
    H = 32
    nh = N * H
    B = int(rng.choice([1, 2]))
    L = int(rng.choice([16, 32]))
    off = np.int32(rng.integers(0, L))
    q = 0.1 * rng.standard_normal((B, 1, nh)).astype(np.float32)
    k = 0.1 * rng.standard_normal((B, L, nh)).astype(np.float32)
    v = 0.1 * rng.standard_normal((B, L, nh)).astype(np.float32)
    return (q, k, v, off, N), {}


def _decode_fallback(q, k_buf, v_buf, off, n_heads):
    """Dense masked attention in f32 — the composed einsum math of
    models/gpt._cached_attention, the kernel's exact reference."""
    B, _, nh = q.shape
    N, H = n_heads, nh // n_heads
    L = k_buf.shape[1]
    scale = 1.0 / float(np.sqrt(H))
    q4 = q.reshape(B, 1, N, H).astype(jnp.float32)
    k4 = k_buf.reshape(B, L, N, H).astype(jnp.float32)
    v4 = v_buf.reshape(B, L, N, H).astype(jnp.float32)
    logits = jnp.einsum("bqnh,bknh->bnqk", q4, k4) * scale
    key_pos = jnp.arange(L, dtype=jnp.int32)
    logits = logits + jnp.where(key_pos <= off, 0.0,
                                -1e30)[None, None, None, :]
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bnqk,bknh->bqnh", probs, v4)
    return out.reshape(B, 1, nh)


@register_kernel(
    "decode_fused", example=_decode_example, fallback=_decode_fallback,
    tol=(1e-3, 1e-3),
    notes="all-head fused decode step over the flat KV cache; online "
          "softmax across L tiles")
def decode_attention(q, k_buf, v_buf, off, n_heads):
    """q [B, 1, N*H]; k_buf/v_buf FLAT [B, L, N*H] (L multiple of 8,
    N*H multiple of 128, N <= 128); off scalar int32 — q's position
    (keys 0..off are valid). Returns [B, 1, N*H] f32 attention output;
    does NOT write the cache (callers update it first). The cache must
    be STORED flat: any reshape between the decode loop's carried
    buffer and pallas_call forces a full cache copy per layer per step
    (measured 16.8k -> 4.2k tok/s), and Mosaic cannot collapse 4-D
    blocks in-kernel."""
    B, one, nh = q.shape
    if one != 1:
        raise ValueError("decode_attention is q_len==1 only")
    N = n_heads
    H = nh // N
    L = k_buf.shape[1]
    scale = 1.0 / float(np.sqrt(H))
    sm, em = _seg_mats(N, H)
    key_pos = jnp.arange(L, dtype=jnp.int32)
    mask = jnp.where(key_pos <= off, 0.0, -1e30).astype(jnp.float32)
    mask = jnp.broadcast_to(mask[:, None], (L, _COLS))

    bl = _pick_bl(L, nh, k_buf.dtype.itemsize)
    nl = L // bl

    return pl.pallas_call(
        functools.partial(_kernel, scale=scale, nl=nl),
        name="decode_fused",
        grid=(B, nl),
        in_specs=[
            pl.BlockSpec((1, 1, nh), lambda b, l: (b, 0, 0)),
            pl.BlockSpec((1, bl, nh), lambda b, l: (b, l, 0)),
            pl.BlockSpec((1, bl, nh), lambda b, l: (b, l, 0)),
            pl.BlockSpec((bl, _COLS), lambda b, l: (l, 0)),
            pl.BlockSpec((nh, _COLS), lambda b, l: (0, 0)),
            pl.BlockSpec((_COLS, nh), lambda b, l: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, nh), lambda b, l: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, 1, nh), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((_SUB, _COLS), jnp.float32),
            pltpu.VMEM((_SUB, _COLS), jnp.float32),
            pltpu.VMEM((_SUB, nh), jnp.float32),
        ],
        interpret=_interpret(),
    )(q, k_buf, v_buf, mask, sm, em)
