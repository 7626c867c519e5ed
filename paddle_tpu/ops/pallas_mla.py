"""Attention over a LATENT paged cache (multi-head latent attention,
DeepSeek-V2, arXiv:2405.04434), in the absorbed form, and for a
prompt chunk with many live positions in the expanded form.

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

`mla_prefill_chunk_expanded` walks the same tiles for a chunk, but
forms each head's k = c_kv W_uk^T and v = c_kv W_uv from a tile's rows
in VMEM, a group of heads a grid step, and attends the chunk's
[q_nope | q_pe] queries with them: the up-projection of a cached row is
paid once a head and shared by every live position, and k and v never
reach HBM. `mla_expanded_pays` says which of the two prefill kernels a
chunk of so many live positions takes.
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
           "mla_tile_rows", "mla_prefill_chunk_expanded",
           "mla_expanded_pays"]

# chunk positions a grid step of the prefill kernel takes: 4 x 128 heads
# are 512 query rows against a 512-row tile
_CHUNK_GROUP = 4
# the expanded kernel: query rows it attends at a time (a chunk's rows
# past the block that holds its last real position compute nothing), the
# most heads a grid step forms k and v for, and the VMEM Mosaic may give
# it (a TPU v5e has 128 MiB)
_QUERY_BLOCK = 256
_HEAD_GROUP = 8
_EXPANDED_VMEM = 64 * 2 ** 20
# what an operation of the expanded kernel costs, in operations of the
# absorbed one: forming k and v, and attending (see mla_expanded_pays).
# Fitted on a TPU v5e by tools/mla_prefill_timing.py, which times both
# kernels alone at DeepSeek-V2's widths; on another chip the rule holds
# only as far as the two kernels keep these ratios, and that script
# fits them again there.
_FORM_COST = 0.78
_ATTEND_COST = 1.54


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

    A chunk takes this form where it has few live positions, by the
    rule of `mla_expanded_pays`: a (query,
    cached row, head) costs 2 (W + rank) operations here, 2,304 at
    DeepSeek-V2's widths, against 2 (nope + rope + v) = 640 in
    `mla_prefill_chunk_expanded`, which first pays 2 rank (nope + v) =
    262,144 a cached row a head for its k and v, shared by the chunk's
    live positions. By operations the expanded form would pay from 158
    live positions on and do half the work at a full chunk of 512; its
    softmax is not hidden under its products as this kernel's is, and
    on a TPU v5e it pays from 199 live positions (in blocks of 256: at
    199-256 and 308-512), at 512 in ~0.6 of this kernel's time."""
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


def mla_expanded_pays(n_real, heads, rank, nope, rope, v, chunk=None):
    """Whether a chunk of `chunk` positions (a whole query block when
    None), `n_real` of them live (an int or a traced int32), takes less
    time in the expanded form than in the absorbed
    one: the two kernels' operations, each cached row they attend
    counted once, weighed by what an operation of each kind costs.

    Absorbed, a (position, row, head) costs 2 (W + rank): a W-lane score
    against the row (W = rank + rope in whole 128-lane tiles) and a
    rank-wide weighted row. Expanded, a row costs 2 rank (nope + v) a
    head to form its k and v, shared by the chunk's positions, and a
    (position, row, head) 2 (nope + rope + v), for every row of the
    query blocks the kernel attends (`_QUERY_BLOCK` positions, or the
    whole of a shorter chunk) that hold a live one. At
    DeepSeek-V2's widths (rank 512, nope 128, rope 64, v 128) that is
    262,144 + 640 x (live positions rounded up to a block) against
    2,304 x n_real a row a head: by operations alone the expanded form
    would pay from 158 live positions on.

    It does not: the weights are measured (a TPU v5e, both kernels alone
    at that shape, `tools/mla_prefill_timing.py`). An operation of the
    absorbed kernel, which keeps the MXU busy, is the unit; forming k
    and v costs `_FORM_COST` of it, and the expanded attention, whose
    softmax takes as long as its two products, `_ATTEND_COST`. With
    blocks of 256 positions the expanded form pays at 199-256 and at
    308-512 live positions."""
    block = _QUERY_BLOCK if chunk is None else min(_QUERY_BLOCK, chunk)
    rows = (n_real + block - 1) // block * block
    absorbed = 2 * heads * n_real * (_lanes(rank + rope) + rank)
    expanded = 2 * heads * (_FORM_COST * rank * (nope + v)
                            + _ATTEND_COST * rows * (nope + rope + v))
    return expanded < absorbed


def _expanded_footprint(rows, hg, chunk, width, rank, nope, v, itemsize):
    """KN502 projection of the expanded kernel at a tile of `rows` and
    `hg` heads a grid step: the group's queries, weights and output
    moving with the step, the latent tile in two buffers, the group's
    accumulators, softmax statistics, k and v, and as temps the group's
    k^T (float32 and cast) and a query block's scores and
    probabilities."""
    D = nope + width - rank
    tq = min(_QUERY_BLOCK, chunk)
    return vmem_footprint(
        moving=[((chunk, hg * D), itemsize), ((hg, nope, rank), itemsize),
                ((hg, rank, v), itemsize), ((chunk, hg * v), itemsize)],
        scratch=[((2, rows, width), itemsize), ((hg, chunk, _COLS), 4),
                 ((hg, chunk, _COLS), 4), ((hg, chunk, v), 4),
                 ((hg, D, rows), itemsize), ((hg, rows, v), itemsize)],
        temp_bytes=hg * nope * rows * (4 + itemsize) + 3 * tq * rows * 4)


def _expanded_tiling(bs, max_blocks, chunk, heads, width, rank, nope, v,
                     itemsize):
    """(tile rows, heads a grid step) of the expanded kernel: the tile
    by the latent kernels' policy at one head a step, then the most
    heads, up to `_HEAD_GROUP`, that divide `heads`, keep the query and
    output blocks whole lanes and fit `_EXPANDED_VMEM`. A zero where
    nothing fits."""
    def fp(rows, hg):
        return _expanded_footprint(rows, hg, chunk, width, rank, nope, v,
                                   itemsize)
    rows = tile_rows_within(bs, max_blocks, lambda r: fp(r, 1))
    D = nope + width - rank
    for hg in range(min(_HEAD_GROUP, heads), 0, -1):
        lanes_whole = hg == heads or (hg * D % _COLS == 0
                                      and hg * v % _COLS == 0)
        if heads % hg == 0 and lanes_whole and rows \
                and fp(rows, hg) <= _EXPANDED_VMEM:
            return rows, hg
    return rows, 0


def _expanded_supported(bs, chunk, width, rank, nope, rope, v, heads,
                        itemsize, max_blocks):
    """Gate for the expanded kernel: pages, query blocks and the heads'
    k rows are whole packed sublane tiles, the row, its value part and
    each head's output are whole lanes, the rotary part fits the lanes
    past `rank`, and the tiling finds a tile and a head group."""
    sub = 8 * max(1, 4 // itemsize)
    tq = min(_QUERY_BLOCK, chunk)
    if bs % sub or chunk % tq or tq % sub or nope % sub or rank % _COLS \
            or width % _COLS or v % _COLS or rope > width - rank:
        return False
    return all(_expanded_tiling(bs, max_blocks, chunk, heads, width, rank,
                                nope, v, itemsize))


def _expanded_kernel(tab_ref, span_ref, q_ref, lat_hbm, wuk_ref, wuv_ref,
                     out_ref, buf, sems, buf_ref, m_sc, l_sc, acc_sc, k_sc,
                     v_sc, *, scale, bs, rows, rank, tq, reach):
    """Grid step g: the heads of group g, `wuk_ref` [hg, nope, rank] and
    `wuv_ref` [hg, rank, v], for every position of the chunk: `q_ref`
    [C, hg * D] holds each head's [q_nope | q_pe | 0] (D = nope + the
    row's lanes past `rank`) and `out_ref` [C, hg * v] its output;
    `span_ref` holds p0 and n_real.

    Every step walks the tiles up to the chunk's last real position. On
    a tile it forms the group's k^T (scaled) from the rows' c_kv beside
    their rotary keys, shared by the heads, into `k_sc` [hg, D, rows],
    and the heads' v into `v_sc` [hg, rows, v]; then it attends the
    blocks of `tq` query rows that hold a real position and reach the
    tile, all of the group's heads in one loop body, so that one head's
    softmax runs beside another's products. Only the blocks whose first
    position lies before the tile's last row are masked. Rows past the
    last real position come out zero; the blocks past its block compute
    nothing."""
    g = pl.program_id(0)
    hg, nope, _ = wuk_ref.shape
    C = q_ref.shape[0]
    D = q_ref.shape[1] // hg
    v_dim = wuv_ref.shape[2]
    W = buf.shape[-1]
    dt = buf.dtype
    p0, n_real = span_ref[0], span_ref[1]
    end = p0 + n_real - 1
    n_blocks = (n_real + tq - 1) // tq

    def last_pos(step):
        return jnp.minimum(end, reach - 1)

    def page_copies(step, i, slot, j):
        return (pltpu.make_async_copy(
            lat_hbm.at[tab_ref[0, i]], buf.at[slot, j], sems.at[slot]),)

    @pl.when(g == 0)
    def _zero():
        # p is exactly 0 on a dead row, and 0 * NaN is NaN: rows no copy
        # has written yet must hold numbers
        buf[...] = jnp.zeros_like(buf)

    m_sc[...] = jnp.full_like(m_sc, -1e30)
    l_sc[...] = jnp.zeros_like(l_sc)
    acc_sc[...] = jnp.zeros_like(acc_sc)

    def compute(t, slot):
        tile = buf[slot].reshape(rows, W)
        c = tile[:, :rank]
        kt = jax.lax.dot_general(
            wuk_ref[...].reshape(hg * nope, rank), c,
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        kt = (kt * scale).astype(dt)                      # [hg * nope, rows]
        pe = (tile[:, rank:].astype(jnp.float32) * scale).T.astype(dt)
        for h in range(hg):
            k_sc[h] = jnp.concatenate([kt[h * nope:(h + 1) * nope], pe], 0)
            v_sc[h] = jnp.dot(c, wuv_ref[h],
                              preferred_element_type=jnp.float32).astype(dt)
        # the blocks that reach the tile, and of them those that need
        # the causal mask: their first position lies before its last row
        first = jnp.maximum(t * rows - p0, 0) // tq
        split = jnp.minimum(jnp.maximum(
            (jnp.maximum((t + 1) * rows - 1 - p0, 0) + tq - 1) // tq,
            first), n_blocks)

        def attend(b, carry, masked=True):
            r0 = pl.multiple_of(b * tq, tq)
            if masked:
                live = t * rows + jax.lax.broadcasted_iota(
                    jnp.int32, (tq, rows), 1) <= p0 + r0 \
                    + jax.lax.broadcasted_iota(jnp.int32, (tq, rows), 0)
            for h in range(hg):
                s = jnp.dot(q_ref[pl.ds(r0, tq), h * D:(h + 1) * D], k_sc[h],
                            preferred_element_type=jnp.float32)  # [tq, rows]
                if masked:
                    s = jnp.where(live, s, -1e30)
                at = (h, pl.ds(r0, tq))
                m_prev = m_sc[at][:, :1]
                m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
                alpha = jnp.exp(m_prev - m_new)
                p = jnp.exp(s - m_new)
                l_new = alpha * l_sc[at][:, :1] + jnp.sum(p, axis=1,
                                                         keepdims=True)
                acc_sc[at] = acc_sc[at] * alpha + jnp.dot(
                    p.astype(dt), v_sc[h], preferred_element_type=jnp.float32)
                m_sc[at] = jnp.broadcast_to(m_new, (tq, _COLS))
                l_sc[at] = jnp.broadcast_to(l_new, (tq, _COLS))
            return carry

        jax.lax.fori_loop(first, split, attend, 0)
        jax.lax.fori_loop(split, n_blocks,
                          functools.partial(attend, masked=False), 0)

    walk_tiles(g, pl.num_programs(0), last_pos, page_copies, buf_ref,
               compute, bs=bs, rows=rows)
    qpos = p0 + jax.lax.broadcasted_iota(jnp.int32, (C, v_dim), 0)
    for h in range(hg):
        # every live row attends position 0, in the first tile
        out = acc_sc[h] / l_sc[h][:, :1]
        out_ref[:, h * v_dim:(h + 1) * v_dim] = jnp.where(
            qpos <= end, out, 0.0).astype(out_ref.dtype)


def _expanded_dense(q_nope, q_pe, lat, w_uk, w_uv, positions, scale):
    """The expanded attention on gathered rows lat [L, W], causal by
    `positions` [C] -> [C, H, v], rounded where the kernel rounds: the
    scaled k and v a head, and the probabilities, in the rows' dtype;
    the products summed, and scores and softmax taken, in float32."""
    rank, rope = w_uk.shape[-1], q_pe.shape[-1]

    def rounded(x):
        return x.astype(lat.dtype).astype(jnp.float32)
    c = rounded(lat[:, :rank])
    kt = rounded(jnp.einsum("hnc,lc->hnl", rounded(w_uk), c) * scale)
    pe = rounded(rounded(lat[:, rank:rank + rope]) * scale)
    v = rounded(jnp.einsum("lc,hcv->hlv", c, rounded(w_uv)))
    s = jnp.einsum("thn,hnl->thl", rounded(q_nope), kt) \
        + jnp.einsum("thr,lr->thl", rounded(q_pe), pe)
    live = jnp.arange(lat.shape[0], dtype=jnp.int32)[None, None, :] \
        <= positions[:, None, None]
    probs = jax.nn.softmax(jnp.where(live, s, -1e30), axis=-1)
    out = jnp.einsum("thl,hlv->thv", rounded(probs), v)
    return out.astype(q_nope.dtype)


def _expanded_example(rng):
    H, nope, rope, v, rank, W, bs, C = 2, 128, 64, 128, 128, 256, 16, 32
    mb = int(rng.integers(2, 12))
    p0 = np.int32(rng.integers(0, mb * bs - C + 1))
    table_row = np.arange(1, mb + 1, dtype=np.int32)

    def rand(*shape):
        return 0.1 * rng.standard_normal(shape).astype(np.float32)
    return (rand(C, H, nope), rand(C, H, rope), rand(mb + 2, bs, W),
            rand(H, nope, rank), rand(H, rank, v), table_row, p0,
            0.125), {"use_kernel": True}


def _expanded_fallback(q_nope, q_pe, pages, w_uk, w_uv, table_row, p0,
                       scale, use_kernel=None):
    return mla_prefill_chunk_expanded(q_nope, q_pe, pages, w_uk, w_uv,
                                      table_row, p0, scale,
                                      use_kernel=False)


@register_kernel(
    "mla_prefill_chunk_expanded", example=_expanded_example,
    fallback=_expanded_fallback, tol=(1e-3, 1e-3),
    notes="latent attention of one prompt chunk in the expanded form: a "
          "group of heads a grid step forms its k and v from each tile "
          "of cached rows in VMEM and attends the chunk's live query "
          "blocks, causal by position, over one request's table")
@functools.partial(jax.jit, static_argnames=("scale", "use_kernel"))
def mla_prefill_chunk_expanded(q_nope, q_pe, pages, w_uk, w_uv, table_row,
                               p0, scale, use_kernel=None, n_real=None):
    """Chunked-prefill attention over a latent paged cache that already
    holds the chunk's own rows, with k and v a head formed from the
    cached rows. q_nope [C, N, nope] and q_pe [C, N, rope] (rotated):
    the queries at positions p0..p0+C-1 (p0 a traced int32); pages
    [num_blocks, block_size, W], rows [c_kv | k_pe | 0]; w_uk
    [N, nope, rank] and w_uv [N, rank, v], the halves of kv_b_proj a
    head; table_row [max_blocks] int32, ONE request's block table;
    n_real (a traced int32, 1..C; all C when None). Returns [C, N, v]
    in q_nope's dtype, zero at the positions past the real ones.

    The kernel fetches what `mla_prefill_chunk` fetches (no page past
    the one position p0 + n_real - 1 lies in) once a group of heads,
    and forms k and v in VMEM: nothing of [L, N, nope + v] reaches
    HBM."""
    C, N, nope = q_nope.shape
    rope = q_pe.shape[-1]
    _, bs, W = pages.shape
    rank, v = w_uk.shape[-1], w_uv.shape[-1]
    mb = table_row.shape[0]
    itemsize = pages.dtype.itemsize
    if use_kernel is None:
        use_kernel = (jax.default_backend() == "tpu" and _expanded_supported(
            bs, C, W, rank, nope, rope, v, N, itemsize, mb))
    positions = p0 + jnp.arange(C, dtype=jnp.int32)
    n = jnp.clip(jnp.asarray(C if n_real is None else n_real, jnp.int32),
                 1, C)
    if not use_kernel:
        out = _expanded_dense(q_nope, q_pe, pages[table_row].reshape(-1, W),
                              w_uk, w_uv, positions, scale)
        return jnp.where((positions < p0 + n)[:, None, None], out, 0)
    rows, hg = _expanded_tiling(bs, mb, C, N, W, rank, nope, v, itemsize)
    if not hg:
        raise ValueError(
            f"mla_prefill_chunk_expanded: no tile of {bs}-row pages and "
            f"no group of its {N} heads fits VMEM (see _expanded_tiling)")
    dt = pages.dtype
    D = nope + W - rank
    q = jnp.concatenate(
        [q_nope.astype(dt), q_pe.astype(dt),
         jnp.zeros((C, N, W - rank - rope), dt)], -1).reshape(C, N * D)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(N // hg,),
        in_specs=[
            pl.BlockSpec((C, hg * D), lambda g, tab, span: (0, g)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((hg, nope, rank), lambda g, tab, span: (g, 0, 0)),
            pl.BlockSpec((hg, rank, v), lambda g, tab, span: (g, 0, 0)),
        ],
        out_specs=pl.BlockSpec((C, hg * v), lambda g, tab, span: (0, g)),
        scratch_shapes=[
            pltpu.VMEM((2, rows // bs, bs, W), dt),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SMEM((1,), jnp.int32),
            pltpu.VMEM((hg, C, _COLS), jnp.float32),
            pltpu.VMEM((hg, C, _COLS), jnp.float32),
            pltpu.VMEM((hg, C, v), jnp.float32),
            pltpu.VMEM((hg, D, rows), dt),
            pltpu.VMEM((hg, rows, v), dt),
        ],
    )
    kernel = functools.partial(
        _expanded_kernel, scale=scale, bs=bs, rows=rows, rank=rank,
        tq=min(_QUERY_BLOCK, C), reach=mb * bs)
    out = pl.pallas_call(
        kernel, name="mla_prefill_chunk_expanded", interpret=_interpret(),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((C, N * v), dt),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_EXPANDED_VMEM))(
        table_row[None].astype(jnp.int32),
        jnp.stack([p0, n]).astype(jnp.int32), q, pages,
        w_uk.astype(dt), w_uv.astype(dt))
    return out.reshape(C, N, v).astype(q_nope.dtype)

