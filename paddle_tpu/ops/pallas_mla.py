"""Attention over a LATENT paged cache (multi-head latent attention,
DeepSeek-V2, arXiv:2405.04434), in the absorbed form.

A latent arena `[num_blocks, block_size, W]` keeps one row a token: the
compressed K/V `c_kv` (its first `rank` numbers) and the rotary key
shared by all heads (the rest). With the up-projection of the keys
folded into the queries (`q_lat = q_nope . W_uk^T`), every head's
score against a cached token is one W-wide dot with that row, and the
weighted values are the weighted sum of the rows' first `rank`
numbers: the row is key and value at once and is shared by all heads,
so a slot's `[heads, W]` queries against a `[rows, W]` tile of the
arena is a plain MXU product. `k` and `v` a head are never formed from
the cache.

One kernel body serves both of the engine's steps, on the tile
machinery of `pallas_decode.paged_decode_attention` (tiles of many
pages walked only over the live context; the arena stays in HBM and
the kernel copies a tile's live pages itself, through the
scalar-prefetched block table, into one of two VMEM buffers while it
computes on the other):

`mla_paged_decode`   one grid step a SLOT: its `[heads, W]` queries
    over the slot's own context.
`mla_prefill_chunk`  one grid step a GROUP of `tq` consecutive chunk
    positions of one request: `[tq * heads, W]` queries, causal by
    position, over that request's context; the groups past the chunk's
    last real position (`n_real`) do nothing.

Both have a gather+dense fallback in the same absorbed arithmetic (the
CPU path, and what the tests and chip_smoke.py hold the kernel to).
"""
import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .kernel_registry import register_kernel, vmem_footprint
from .pallas_decode import (_COLS, _interpret, tile_rows_within,
                            walk_tiles)

__all__ = ["mla_paged_decode", "mla_prefill_chunk", "mla_supported",
           "mla_tile_rows"]

# chunk positions a grid step of the prefill kernel takes: 4 x 128 heads
# are 512 query rows against a 512-row tile
_CHUNK_GROUP = 4


def _lanes(width):
    return -(-width // _COLS) * _COLS


def _footprint(rows, width, rank, q_rows, itemsize):
    """KN502 projection at a tile of `rows`: the latent tile in two
    buffers (the kernel's own double buffer), q and the output moving
    with the grid step, the accumulator and the softmax statistics, and
    the [q_rows, rows] logits and probabilities as temps."""
    return vmem_footprint(
        moving=[((q_rows, _lanes(width)), itemsize),
                ((q_rows, rank), itemsize)],
        scratch=[((2, rows, _lanes(width)), itemsize),
                 ((q_rows, rank), 4), ((q_rows, _COLS), 4),
                 ((q_rows, _COLS), 4)],
        temp_bytes=(3 * q_rows * rows + q_rows * rank) * 4)


def mla_tile_rows(block_size, width, rank, q_rows, itemsize, max_blocks):
    """Rows of the latent arena one tile holds: `paged_decode`'s policy
    (whole pages, whole 128-lane logits columns, at most `_TILE_ROWS`,
    no longer than a table's reach, within VMEM). 0 when none fits."""
    return tile_rows_within(
        block_size, max_blocks,
        lambda rows: _footprint(rows, width, rank, q_rows, itemsize))


def mla_supported(block_size, width, rank, q_rows, itemsize=2,
                  max_blocks=_COLS):
    """Gate for the kernel: pages and query rows are whole packed
    sublane tiles of the dtype, the row and its value part are whole
    lanes (the kernel copies pages as they lie in HBM), and the tile
    policy finds a tile."""
    sub = 8 * max(1, 4 // itemsize)
    if block_size % sub or q_rows % sub or rank % _COLS or rank > width \
            or width % _COLS:
        return False
    return mla_tile_rows(block_size, width, rank, q_rows, itemsize,
                         max_blocks) > 0


def _mla_kernel(tab_ref, base_ref, q_ref, lat_hbm, out_ref,
                buf, sems, buf_ref, m_sc, l_sc, acc_sc,
                *, scale, bs, rows, n_heads, rank, chunk, reach):
    """Grid step g: `q_ref` [Q, W] holds the queries of Q // n_heads
    consecutive positions base[g], base[g] + 1, ... (heads minor) of
    the request whose table row is g (a decode slot) or 0 (`chunk`).
    `walk_tiles` takes it through the tiles up to the last position any
    of them attends, `reach` at most (the table's end).

    For a chunk `base_ref` holds, past the G bases, the chunk's last
    real position and the number of grid steps that hold a real one.
    Those steps attend up to that position at most, and their rows past
    it come out zero; the steps after them fetch nothing, compute
    nothing and write zeros."""
    g = pl.program_id(0)
    Q = q_ref.shape[1]
    tq = Q // n_heads
    if chunk:
        G = pl.num_programs(0)
        end, n_live = base_ref[G], base_ref[G + 1]

    def last_pos(step):
        last = base_ref[step] + tq - 1
        if chunk:
            last = jnp.minimum(last, end)
        return jnp.minimum(last, reach - 1)

    def page_copies(step, i, slot, j):
        blk = tab_ref[0 if chunk else step, i]
        return (pltpu.make_async_copy(
            lat_hbm.at[blk], buf.at[slot, j], sems.at[slot]),)

    @pl.when(g == 0)
    def _zero():
        # p is exactly 0 on a dead row, and 0 * NaN is NaN: rows no copy
        # has written yet must hold numbers
        buf[...] = jnp.zeros_like(buf)

    def attend():
        m_sc[...] = jnp.full_like(m_sc, -1e30)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)
        q = q_ref[0]                                      # [Q, W]
        qpos = base_ref[g] + jax.lax.broadcasted_iota(
            jnp.int32, (Q, rows), 0) // n_heads

        def compute(t, slot):
            tile = buf[slot].reshape(rows, buf.shape[-1])  # [rows, W]
            logits = jax.lax.dot_general(
                q, tile, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale  # [Q, rows]
            kpos = t * rows + jax.lax.broadcasted_iota(
                jnp.int32, (Q, rows), 1)
            logits = jnp.where(kpos <= qpos, logits, -1e30)
            m_prev = m_sc[:, :1]
            m_new = jnp.maximum(
                m_prev, jnp.max(logits, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(logits - m_new)                   # [Q, rows]
            l_new = alpha * l_sc[:, :1] + jnp.sum(p, axis=1, keepdims=True)
            pv = jax.lax.dot_general(
                p.astype(tile.dtype), tile[:, :rank],
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)       # [Q, rank]
            acc_sc[...] = acc_sc[...] * alpha + pv
            m_sc[...] = jnp.broadcast_to(m_new, m_sc.shape)
            l_sc[...] = jnp.broadcast_to(l_new, l_sc.shape)

        # the last step to walk starts no copy for a step that waits for
        # none
        walk_tiles(g, n_live if chunk else pl.num_programs(0),
                   last_pos, page_copies, buf_ref, compute, bs=bs,
                   rows=rows)
        # every first tile holds position 0, which every query attends
        out = acc_sc[...] / l_sc[:, :1]
        if chunk:
            out = jnp.where(qpos[:, :1] <= end, out, 0.0)
        out_ref[0] = out.astype(out_ref.dtype)

    def dead():
        out_ref[0] = jnp.zeros(out_ref.shape[1:], out_ref.dtype)

    if chunk:
        pl.when(g < n_live)(attend)
        pl.when(g >= n_live)(dead)
    else:
        attend()


def _plan(q, pages, tables, *, rank, scale, n_heads, chunk, name):
    """(kernel, pallas_call keywords) for q [G, Q, W] over tables
    [S or 1, max_blocks]; the operands are (tables, base, q, pages),
    base [G] for a decode step's slots and [G + 2] for a chunk (the
    groups' first positions, the last real position, the number of
    groups that hold a real one)."""
    G, Q, W = q.shape
    _, bs, _ = pages.shape
    mb = tables.shape[1]
    rows = mla_tile_rows(bs, W, rank, Q, pages.dtype.itemsize, mb)
    if not rows:
        raise ValueError(
            f"{name}: no tile of {bs}-row pages at width {W} under "
            f"{Q} query rows fits VMEM (see mla_supported)")
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(G,),
        in_specs=[
            # a group with no real position fetches no queries: it stays
            # on the block of the last one that has
            pl.BlockSpec((1, Q, W), (lambda g, tab, base: (
                jnp.minimum(g, base[G + 1] - 1), 0, 0)) if chunk
                else (lambda g, tab, base: (g, 0, 0))),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, Q, rank), lambda g, tab, base: (g, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, rows // bs, bs, W), pages.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SMEM((1,), jnp.int32),
            pltpu.VMEM((Q, _COLS), jnp.float32),
            pltpu.VMEM((Q, _COLS), jnp.float32),
            pltpu.VMEM((Q, rank), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _mla_kernel, scale=scale, bs=bs, rows=rows, n_heads=n_heads,
        rank=rank, chunk=chunk, reach=mb * bs)
    return kernel, dict(
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((G, Q, rank), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)))


def _dense(q, lat, qpos, rank, scale):
    """The absorbed attention on gathered rows: q [B, Tq, N, W], lat
    [B, L, W], qpos [B, Tq] -> [B, Tq, N, rank]. Scores and softmax in
    float32, the probabilities in the rows' dtype for the second
    product, as the kernel has them."""
    L = lat.shape[1]
    logits = jnp.einsum("bqnw,blw->bqnl", q.astype(lat.dtype), lat,
                        preferred_element_type=jnp.float32) * scale
    live = jnp.arange(L, dtype=jnp.int32)[None, None, None, :] \
        <= qpos[:, :, None, None]
    probs = jax.nn.softmax(jnp.where(live, logits, -1e30), axis=-1)
    out = jnp.einsum("bqnl,blr->bqnr", probs.astype(lat.dtype),
                     lat[..., :rank], preferred_element_type=jnp.float32)
    return out.astype(q.dtype)


def _decode_example(rng):
    """Randomized in-support config (kernel_lint KN504): distinct
    physical blocks a slot, tails at the null block 0."""
    N, W, rank, bs = 16, 256, 128, 16
    S = int(rng.choice([2, 3]))
    mb = int(rng.integers(2, 12))
    ctx = rng.integers(0, mb * bs - 1, size=S).astype(np.int32)
    tables = np.zeros((S, mb), np.int32)
    for s in range(S):
        for i in range(int(ctx[s]) // bs + 1):
            tables[s, i] = 1 + s * mb + i
    q = 0.1 * rng.standard_normal((S, N, W)).astype(np.float32)
    pages = 0.1 * rng.standard_normal((S * mb + 1, bs, W)) \
        .astype(np.float32)
    return (q, pages, tables, ctx, rank, 0.125), {"use_kernel": True}


def _decode_fallback(q, pages, tables, ctx, rank, scale, use_kernel=None):
    return mla_paged_decode(q, pages, tables, ctx, rank, scale,
                            use_kernel=False)


@register_kernel(
    "mla_paged_decode", example=_decode_example,
    fallback=_decode_fallback, tol=(1e-3, 1e-3),
    notes="absorbed latent attention, one grid step a slot (sequential: "
          "the tile buffers and their in-flight copies pass from slot "
          "to slot); the latent arena stays in HBM and the kernel "
          "copies the live pages of each tile itself through the "
          "scalar-prefetched table")
# jitted on its own so that a model's layers share one trace and one
# lowering of the kernel (as paged_decode_attention is)
@functools.partial(jax.jit, static_argnames=("rank", "scale", "use_kernel"))
def mla_paged_decode(q, pages, tables, ctx, rank, scale, use_kernel=None):
    """Decode attention over a latent paged cache. q [S, N, W]: every
    head's absorbed query ([q_nope . W_uk^T | rotary part]); pages
    [num_blocks, block_size, W]; tables [S, max_blocks] int32; ctx [S]
    int32, each slot's position (rows 0..ctx are attended). Returns
    [S, N, rank]: softmax(q . row) weighted rows' first `rank`
    numbers, in q's dtype."""
    S, N, W = q.shape
    _, bs, _ = pages.shape
    if use_kernel is None:
        use_kernel = (jax.default_backend() == "tpu" and mla_supported(
            bs, W, rank, N, pages.dtype.itemsize, tables.shape[1]))
    if not use_kernel:
        lat = pages[tables].reshape(S, -1, W)
        return _dense(q[:, None], lat, ctx[:, None], rank, scale)[:, 0]
    kernel, how = _plan(q, pages, tables, rank=rank, scale=scale, n_heads=N,
                        chunk=False, name="mla_paged_decode")
    return pl.pallas_call(kernel, name="mla_paged_decode",
                          interpret=_interpret(), **how)(
        tables.astype(jnp.int32), ctx.astype(jnp.int32),
        q.astype(pages.dtype), pages)


def _chunk_example(rng):
    N, W, rank, bs, C = 4, 256, 128, 16, 16
    mb = int(rng.integers(2, 12))
    p0 = np.int32(rng.integers(0, mb * bs - C + 1))
    table_row = np.arange(1, mb + 1, dtype=np.int32)
    q = 0.1 * rng.standard_normal((C, N, W)).astype(np.float32)
    pages = 0.1 * rng.standard_normal((mb + 2, bs, W)).astype(np.float32)
    return (q, pages, table_row, p0, rank, 0.125), {"use_kernel": True}


def _chunk_fallback(q, pages, table_row, p0, rank, scale, use_kernel=None):
    return mla_prefill_chunk(q, pages, table_row, p0, rank, scale,
                             use_kernel=False)


def _chunk_group(C, n_heads, itemsize):
    """Positions a grid step takes: the largest divisor of the chunk up
    to `_CHUNK_GROUP` whose query rows are whole sublane tiles."""
    sub = 8 * max(1, 4 // itemsize)
    for tq in range(min(_CHUNK_GROUP, C), 0, -1):
        if C % tq == 0 and (tq * n_heads) % sub == 0:
            return tq
    return 0


@register_kernel(
    "mla_prefill_chunk", example=_chunk_example,
    fallback=_chunk_fallback, tol=(1e-3, 1e-3),
    notes="absorbed latent attention of one prompt chunk, causal by "
          "position: mla_paged_decode's body, a group of chunk "
          "positions a grid step over one request's table")
@functools.partial(jax.jit, static_argnames=("rank", "scale", "use_kernel"))
def mla_prefill_chunk(q, pages, table_row, p0, rank, scale,
                      use_kernel=None, n_real=None):
    """Chunked-prefill attention over a latent paged cache that already
    holds the chunk's own rows. q [C, N, W]: the absorbed queries at
    positions p0..p0+C-1 (p0 a traced int32); table_row [max_blocks]
    int32, ONE request's block table; n_real (a traced int32, 1..C; all
    C when None): how many of the positions are real. Returns
    [C, N, rank], zero at the positions past the real ones.

    The kernel's grid is one step a group of positions whatever n_real
    is (one program for every length of chunk), but only the groups
    that hold a real position work, and only up to position
    p0 + n_real - 1: no page past the one it lies in is fetched, and
    table entries past it may hold anything (`flash_prefill_chunk`'s
    contract).

    It stays absorbed: at 8k of context a 512-token chunk costs 1.17
    TFLOP a layer this way against 0.62 with k and v a head expanded
    from the cached rows, but the expanded form writes and reads
    0.7 GB of k/v a layer a chunk that the absorbed form never forms,
    and shares no code with decode (PERF.md section 6, PR 28)."""
    C, N, W = q.shape
    _, bs, _ = pages.shape
    mb = table_row.shape[0]
    tq = _chunk_group(C, N, pages.dtype.itemsize)
    if use_kernel is None:
        use_kernel = (jax.default_backend() == "tpu" and tq > 0
                      and mla_supported(bs, W, rank, tq * N,
                                        pages.dtype.itemsize, mb))
    positions = p0 + jnp.arange(C, dtype=jnp.int32)
    n = jnp.clip(jnp.asarray(C if n_real is None else n_real, jnp.int32),
                 1, C)
    if not use_kernel:
        lat = pages[table_row].reshape(1, -1, W)
        out = _dense(q[None], lat, positions[None], rank, scale)[0]
        return jnp.where((positions < p0 + n)[:, None, None], out, 0)
    qg = q.reshape(C // tq, tq * N, W)
    kernel, how = _plan(qg, pages, table_row[None], rank=rank, scale=scale,
                        n_heads=N, chunk=True, name="mla_prefill_chunk")
    base = jnp.concatenate([
        positions[::tq], jnp.stack([p0 + n - 1, -(-n // tq)])])
    out = pl.pallas_call(kernel, name="mla_prefill_chunk",
                         interpret=_interpret(), **how)(
        table_row[None].astype(jnp.int32), base.astype(jnp.int32),
        qg.astype(pages.dtype), pages)
    return out.reshape(C, N, rank)
