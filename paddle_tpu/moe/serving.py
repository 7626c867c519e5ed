"""The drop-free expert layer of the serving step.

`moe/router.py` and `moe/layer.py` are the TRAINING layer (GShard top-k
into capacity buckets that drop what overflows). Serving must not drop:
here every token's chosen experts are computed, whatever the load.

    route_group_limited   softmax scores over ALL experts in float32,
                          the best `topk_group` of `n_group` groups by
                          their best expert, the top `k` experts among
                          those groups (DeepSeek-V2's
                          `group_limited_greedy`)
    route_sigmoid_topk    sigmoid scores in float32, the top `k` of
                          score + a learned bias, the chosen scores
                          renormalised to sum to 1 and scaled (the
                          bias chooses and does not weigh: DeepSeek-V3's
                          gate without its group limit)
    held_expert_ffn       the gated expert MLPs of the experts HELD
                          HERE, `held=(first, count)`, over the tokens
                          routed to them: the layer's share of an
                          expert-parallel deployment. What the other
                          chips hold is neither computed nor stood in
                          for; the caller adds this partial sum to the
                          shared expert's output.

The expert products are grouped by expert: the (token, expert) pairs
held here are ordered by expert, each expert's rows padded to whole
tiles of `tm` rows, and `moe_grouped_ffn` walks the tiles with each
tile's expert looked up through a scalar-prefetched map. A live tile
is ONE READ of its expert's weights (the width axis is the inner one,
so two tiles of one expert walk its matrices twice) and a dead tile
fetches nothing, so `expert_tile_rows` sizes the tile to hold the rows
of the busiest held expert, four times the mean, up to 128: a decode
batch gives a held expert 1 (DeepSeek-V2: 32 slots x top 6 of 160) to
4 (K-EXAONE: 64 x top 8 of 128) rows on average, a chunk of 512 tokens
19 and 32, and the busiest 1.8 and 3.8 times that. A larger tile
than that only pads: every held expert's rows end on a whole tile, and
the padding rows go through the products like any row. Up to ~240 rows
of bf16 the chip takes longer to read an expert's weights than to
multiply by them (197e12 FLOP/s over 819e9 B/s), so the layer is bound
by reading the held experts' weights, once each.

The layout of the tiles never reaches HBM: the kernel holds the
tokens' x and a float32 y [T, d] whole in VMEM, gathers each live
tile's real rows from x itself, and adds each pair's weighted output
into its token's row of y, which it writes once, in x's dtype. Where x
and y do not fit VMEM beside a tile, the rows are laid out
[n_tiles * tm, d] in HBM around the kernel instead. Off the TPU the
same rows go through `jax.lax.ragged_dot`.
"""
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..ops.kernel_registry import register_kernel, vmem_footprint

__all__ = ["route_group_limited", "route_sigmoid_topk", "held_expert_ffn",
           "moe_grouped_ffn", "group_by_expert", "expert_tile_rows"]

_F_TILE = 256               # columns of an expert's width a grid step takes
# what Mosaic may give the kernel, assuming a TPU v5e's 128 MiB of VMEM:
# x and y held whole beside a tile (52.7 MB at K-EXAONE's chunk of 512).
# Past it the kernel takes the layout path: at tiles of 16 rows that is a
# `prefill_chunk` over 2,144 tokens at K-EXAONE's widths (d 6,144,
# f 2,048), 2,656 at DeepSeek-V2's, 7,248 at Qwen3-Next's. A chip with
# less VMEM needs a smaller limit, or the fused kernel fails to compile.
_VMEM_LIMIT = 120 * 2 ** 20
# the most rows of a tile, and the rows of the busiest held expert over
# the mean that a tile allows for: the cells' routers give their busiest
# 1.8 (deepseek-v2.serve-docs) and 3.8 times (k-exaone-236b-a23b.serve-
# mixed) the mean (see expert_tile_rows)
_TILE_ROWS = 128
_LOAD = 4


def _interpret():
    return jax.default_backend() != "tpu"


def route_group_limited(x, w_gate, n_group, topk_group, k, scale=1.0):
    """x [T, d], w_gate [d, E] -> (weights [T, k] float32, experts
    [T, k] int32). Scores are a float32 softmax over all E experts; a
    group's score is its best expert's; experts outside the best
    `topk_group` groups score 0; the weights are the chosen experts'
    scores times `scale`, not renormalised."""
    T, E = x.shape[0], w_gate.shape[1]
    scores = jax.nn.softmax(
        jnp.dot(x, w_gate.astype(x.dtype),
                preferred_element_type=jnp.float32), axis=-1)
    group_best = scores.reshape(T, n_group, E // n_group).max(axis=-1)
    _, groups = jax.lax.top_k(group_best, topk_group)
    kept = jnp.zeros((T, n_group), bool).at[
        jnp.arange(T)[:, None], groups].set(True)
    masked = jnp.where(jnp.repeat(kept, E // n_group, axis=1), scores, 0.0)
    weights, experts = jax.lax.top_k(masked, k)
    return weights * scale, experts.astype(jnp.int32)


def route_sigmoid_topk(x, w_gate, bias, k, scale=1.0, renorm=True):
    """x [T, d], w_gate [d, E], bias [E] -> (weights [T, k] float32,
    experts [T, k] int32). Scores are a float32 sigmoid an expert; the
    chosen experts are the top `k` of score + bias; the weights are the
    chosen experts' SCORES (without the bias), divided by their sum
    (+ 1e-20) where `renorm`, times `scale`."""
    scores = jax.nn.sigmoid(
        jnp.dot(x, w_gate.astype(x.dtype),
                preferred_element_type=jnp.float32))
    _, experts = jax.lax.top_k(scores + bias.astype(jnp.float32), k)
    weights = jnp.take_along_axis(scores, experts, axis=1)
    if renorm:
        weights = weights / (weights.sum(axis=-1, keepdims=True) + 1e-20)
    return weights * scale, experts.astype(jnp.int32)


def group_by_expert(local, held, n_experts, tm, n_tiles):
    """Order (token, expert) pairs by expert into tiles of `tm` rows.

    local [P] int32: a pair's expert, counted from the first held one;
    held [P] bool: whether the pair is computed here. Returns
    dest [P] (the pair's row, n_tiles * tm where it is not held),
    tile_expert [n_tiles] (dead tiles repeat the last live tile's, so
    that they fetch nothing), tile_live [n_tiles] int32, and
    counts [n_experts]. Every expert's rows start on a tile."""
    onehot = jnp.logical_and(
        local[:, None] == jnp.arange(n_experts)[None, :], held[:, None])
    counts = onehot.sum(axis=0).astype(jnp.int32)
    rank = jnp.take_along_axis(
        jnp.cumsum(onehot, axis=0, dtype=jnp.int32) - 1,
        jnp.clip(local, 0, n_experts - 1)[:, None], axis=1)[:, 0]
    padded = -(-counts // tm) * tm
    ends = jnp.cumsum(padded)
    starts = ends - padded
    dest = jnp.where(held, starts[jnp.clip(local, 0, n_experts - 1)] + rank,
                     n_tiles * tm)
    tile_start = jnp.arange(n_tiles, dtype=jnp.int32) * tm
    tile_live = tile_start < ends[-1]
    tile_expert = jnp.minimum(
        jnp.searchsorted(ends, tile_start, side="right"), n_experts - 1)
    last_live = jnp.maximum(ends[-1] // tm - 1, 0)
    tile_expert = jnp.where(tile_live, tile_expert, tile_expert[last_live])
    return dest, tile_expert.astype(jnp.int32), \
        tile_live.astype(jnp.int32), counts


def _products(x, wg_ref, wu_ref, wd_ref, acc_ref):
    """acc += the tile's rows x through one width tile of its expert."""
    g = jnp.dot(x, wg_ref[0], preferred_element_type=jnp.float32)
    u = jnp.dot(x, wu_ref[0], preferred_element_type=jnp.float32)
    h = (g * jax.nn.sigmoid(g) * u).astype(x.dtype)
    acc_ref[...] += jnp.dot(h, wd_ref[0], preferred_element_type=jnp.float32)


def _fused_kernel(te_ref, tn_ref, tr_ref, col_ref, w_ref, tok_ref, x_ref,
                  wg_ref, wu_ref, wd_ref, o_ref, rows_ref, acc_ref, y_ref,
                  *, nj):
    """Grid (row tile i, width tile j) over x and y [T, d] held whole.
    At a live tile's first width step its `tn[i]` real rows are gathered
    from x (a one-hot product: exact, and rows past them come out 0); at
    its last, each real row's output, rounded to x's dtype, is weighted
    in float32 and added into its token's row of y. y is cast to x's
    dtype once, at the last grid step."""
    i, j = pl.program_id(0), pl.program_id(1)
    n = tn_ref[i]

    @pl.when(jnp.logical_and(i == 0, j == 0))
    def _zero():
        y_ref[...] = jnp.zeros_like(y_ref)

    @pl.when(n > 0)
    def _live():
        @pl.when(j == 0)
        def _rows_in():
            tm, T = col_ref.shape[0], x_ref.shape[0]
            onehot = col_ref[...] == jax.lax.broadcasted_iota(
                jnp.int32, (tm, T), 1)
            # one product a row, exact: a float32 x asks for every pass
            exact = (jax.lax.Precision.HIGHEST
                     if x_ref.dtype == jnp.float32 else None)
            rows_ref[...] = jnp.dot(
                onehot.astype(x_ref.dtype), x_ref[...], precision=exact,
                preferred_element_type=jnp.float32).astype(rows_ref.dtype)
            acc_ref[...] = jnp.zeros_like(acc_ref)

        _products(rows_ref[...], wg_ref, wu_ref, wd_ref, acc_ref)

        @pl.when(j == nj - 1)
        def _sum_out():
            acc_ref[...] = acc_ref[...].astype(o_ref.dtype).astype(
                jnp.float32) * w_ref[...]

            def add(r, carry):
                t = tok_ref[0, 0, r]
                y_ref[pl.ds(t, 1), :] += acc_ref[pl.ds(r, 1), :]
                return carry
            jax.lax.fori_loop(0, n, add, 0)

    @pl.when(jnp.logical_and(i == pl.num_programs(0) - 1, j == nj - 1))
    def _out():
        o_ref[...] = y_ref[...].astype(o_ref.dtype)


def _layout_kernel(te_ref, tl_ref, tr_ref, x_ref, wg_ref, wu_ref, wd_ref,
                   o_ref, acc_ref, *, nj):
    """Grid (row tile i, width tile j) over rows laid out by expert in
    HBM: tile i's rows through its expert, written to tile i of the
    output (zeros for a dead tile)."""
    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(tl_ref[i] != 0)
    def _live():
        _products(x_ref[...], wg_ref, wu_ref, wd_ref, acc_ref)

    @pl.when(j == nj - 1)
    def _store():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _ffn_example(rng):
    import numpy as np
    T, k, E, d, f, tm = 24, 2, 3, 128, 512, 16
    n_tiles = -(-T * k // tm) + E
    # experts -1 and E are held elsewhere; expert 1 gets no rows and
    # expert 0 more than a tile
    local = np.stack([rng.permutation(E + 2)[:k] - 1 for _ in range(T)])
    local[local == 1] = E
    local[:20, 1] = np.where(local[:20, 0] == 0, local[:20, 1], 0)
    held = np.logical_and(local >= 0, local < E).reshape(T * k)
    plan = group_by_expert(jnp.asarray(local.reshape(T * k)),
                           jnp.asarray(held), E, tm, n_tiles)
    dest, te, tl, counts = (np.asarray(a) for a in plan)
    w = np.where(held, rng.random(T * k), 0).astype(np.float32)
    x = 0.3 * rng.standard_normal((T, d)).astype(np.float32)
    ws = [0.1 * rng.standard_normal(s).astype(np.float32)
          for s in ((E, d, f), (E, d, f), (E, f, d))]
    return (x, w, dest, *ws, te, tl, counts, tm), {"use_kernel": True}


def _ffn_fallback(*args, use_kernel=None, **kw):
    return moe_grouped_ffn(*args, use_kernel=False, **kw)


def _kernel_supported(d, f, tm):
    return (jax.default_backend() == "tpu" and d % 128 == 0
            and f % 128 == 0 and tm % 16 == 0)


@register_kernel(
    "moe_grouped_ffn", example=_ffn_example, fallback=_ffn_fallback,
    tol=(2e-3, 2e-3),
    notes="gated expert MLPs over (token, expert) pairs grouped by "
          "expert: a tile's expert comes through scalar prefetch, the "
          "kernel gathers the tile's rows from x and adds each pair's "
          "weighted output into y, both held whole; the width axis "
          "carries the float32 sum and must stay sequential")
@functools.partial(jax.jit, static_argnames=("tm", "use_kernel"))
def moe_grouped_ffn(x, w, dest, wg, wu, wd, tile_expert, tile_live, counts,
                    tm, use_kernel=None):
    """y [T, d]: a token's sum over its pairs held here of the pair's
    weight times down(silu(gate(x)) * up(x)) through the pair's expert.

    x [T, d]; w, dest [T * k]: each pair's weight and its row as
    `group_by_expert` orders them (a pair not held here weighs 0 and
    lies at row n_tiles * tm); wg, wu [E, d, f], wd [E, f, d];
    tile_expert, tile_live [n_tiles] and counts [E] as
    `group_by_expert` gives them. A pair's output is rounded to x's
    dtype, then weighted and summed in float32; y is in x's dtype.

    Where x and y fit VMEM beside a tile (`_resident`, from the shapes)
    the kernel holds x and a float32 y whole there, gathers each live
    tile's rows itself and adds each pair's output into its token's row
    of y: nothing of the layout's size reaches HBM. Where they do not,
    the rows are gathered into the layout [n_tiles * tm, d] in HBM, the
    kernel writes its output there (dead tiles write zeros) and every
    pair is gathered back from it. Off the TPU the layout goes through
    `jax.lax.ragged_dot`."""
    T, d = x.shape
    f = wg.shape[2]
    k = dest.shape[0] // T
    n_tiles = tile_expert.shape[0]
    M = n_tiles * tm
    if use_kernel is None:
        use_kernel = _kernel_supported(d, f, tm)
    token = jnp.repeat(jnp.arange(T, dtype=jnp.int32), k)
    row_token = jnp.full((M,), -1, jnp.int32).at[dest].set(
        token, mode="drop")
    tf = _width_tile(f)
    nj = f // tf
    # a dead tile keeps the blocks the last live tile ended on (its
    # per-tile blocks, `tile_row`: the last live tile at or before each
    # tile, and its expert's last columns), so it fetches nothing
    tile_row = jax.lax.cummax(jnp.where(
        tile_live != 0, jnp.arange(n_tiles, dtype=jnp.int32), 0))

    def col(i, j, live):
        return jnp.where(live[i] != 0, j, nj - 1)

    weight_specs = [
        pl.BlockSpec((1, d, tf),
                     lambda i, j, te, lv, tr: (te[i], 0, col(i, j, lv))),
        pl.BlockSpec((1, d, tf),
                     lambda i, j, te, lv, tr: (te[i], 0, col(i, j, lv))),
        pl.BlockSpec((1, tf, d),
                     lambda i, j, te, lv, tr: (te[i], col(i, j, lv), 0))]
    params = pltpu.CompilerParams(
        dimension_semantics=("arbitrary", "arbitrary"),
        vmem_limit_bytes=_VMEM_LIMIT)
    if use_kernel and _resident(tm, T, d, f, x.dtype.itemsize):
        row_w = jnp.zeros((M,), jnp.float32).at[dest].set(w, mode="drop")
        tile_rows = (row_token >= 0).reshape(n_tiles, tm).sum(
            axis=1, dtype=jnp.int32)

        def per_tile(i, j, te, lv, tr):
            return tr[i], 0

        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(n_tiles, nj),
            in_specs=[
                pl.BlockSpec((tm, 1), per_tile),
                pl.BlockSpec((tm, 1), per_tile),
                pl.BlockSpec((1, 1, tm),
                             lambda i, j, te, lv, tr: (tr[i], 0, 0),
                             memory_space=pltpu.SMEM),
                pl.BlockSpec(memory_space=pltpu.VMEM),
                *weight_specs],
            out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
            scratch_shapes=[pltpu.VMEM((tm, d), x.dtype),
                            pltpu.VMEM((tm, d), jnp.float32),
                            pltpu.VMEM((T, d), jnp.float32)],
        )
        return pl.pallas_call(
            functools.partial(_fused_kernel, nj=nj),
            name="moe_grouped_ffn",
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((T, d), x.dtype),
            compiler_params=params,
            interpret=_interpret(),
        )(tile_expert, tile_rows, tile_row, row_token.reshape(M, 1),
          row_w.reshape(M, 1), row_token.reshape(n_tiles, 1, tm),
          x, wg, wu, wd)
    xs = x[jnp.maximum(row_token, 0)]
    if use_kernel:
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(n_tiles, nj),
            in_specs=[
                pl.BlockSpec((tm, d), lambda i, j, te, lv, tr: (tr[i], 0)),
                *weight_specs],
            out_specs=pl.BlockSpec((tm, d),
                                   lambda i, j, te, lv, tr: (i, 0)),
            scratch_shapes=[pltpu.VMEM((tm, d), jnp.float32)],
        )
        ys = pl.pallas_call(
            functools.partial(_layout_kernel, nj=nj),
            name="moe_grouped_ffn",
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((M, d), x.dtype),
            compiler_params=params,
            interpret=_interpret(),
        )(tile_expert, tile_live, tile_row, xs, wg, wu, wd)
    else:
        group_sizes = -(-counts // tm) * tm
        rdot = functools.partial(jax.lax.ragged_dot, group_sizes=group_sizes,
                                 preferred_element_type=jnp.float32)
        g, u = rdot(xs, wg.astype(x.dtype)), rdot(xs, wu.astype(x.dtype))
        h = (g * jax.nn.sigmoid(g) * u).astype(x.dtype)
        out = rdot(h, wd.astype(x.dtype)).astype(x.dtype)
        # rows past the last group are in no product: what ragged_dot
        # leaves there differs by platform
        grouped = jnp.arange(M) < jnp.sum(group_sizes)
        ys = jnp.where(grouped[:, None], out, 0)
    picked = ys[jnp.minimum(dest, M - 1)].astype(jnp.float32)
    # added one pair at a time, as the kernel adds them: a sum over the
    # pairs' axis may fold each product into its addition (an FMA)
    y = jnp.zeros((T, d), jnp.float32).at[token].add(picked * w[:, None])
    return y.astype(x.dtype)


def _width_tile(f):
    return next((t for t in (_F_TILE, 128) if f % t == 0), f)


def _ffn_footprint(tm, d, tf, itemsize, tokens=0):
    """VMEM of `moe_grouped_ffn` at tiles of `tm` rows: the three weight
    blocks in two buffers each, the float32 accumulator, and the
    [tm, tf] float32 gate, up and hidden products as temps. With
    `tokens` (the kernel gathers and sums itself) x and y [tokens, d]
    are held whole, y in float32 besides, the tile's gathered rows once,
    its rows' tokens and weights a lane column each in two buffers, and
    the gather's one-hot and float32 product are temps; without (the
    layout path) the tile's rows and its output block are in two
    buffers each."""
    weights = [((d, tf), itemsize)] * 3
    temps = 3 * tm * tf * 4
    if tokens:
        return vmem_footprint(
            moving=weights + [((tm, 128), 4)] * 2,
            resident=[((tokens, d), itemsize)] * 2,
            scratch=[((tm, d), itemsize), ((tm, d), 4), ((tokens, d), 4)],
            temp_bytes=temps + tm * tokens * itemsize + tm * d * 4)
    return vmem_footprint(moving=weights + [((tm, d), itemsize)] * 2,
                          scratch=[((tm, d), 4)], temp_bytes=temps)


def expert_tile_rows(tokens, k, n_experts, d, f, itemsize,
                     vmem_limit=_VMEM_LIMIT):
    """Rows of one tile of `moe_grouped_ffn`: the tile policy, a pure
    function of what the arguments' shapes show. `tokens` of the
    program each choose `k` of the `n_experts` the router scores.

    A tile is one read of its expert's weights, so it should hold all
    the rows the busiest held expert gets, and no more: every held
    expert's rows are padded to whole tiles. It is a whole number of
    packed bf16 row blocks (16 rows), and among those the smallest that
    holds `_LOAD` times the mean rows an expert (`tokens * k /
    n_experts`), unless that (a) is more rows than one expert can get
    in the program (a token chooses an expert once: `tokens`, rounded
    up to one unit), (b) has more than `_TILE_ROWS` rows: on the v5e a
    tile's products take as long as its weights' read at ~240 rows of
    bf16, and 128 is the largest power of two that leaves the kernel
    bound by the read, or (c) does not fit `vmem_limit` with every
    moving block in two buffers: beside x and y held whole where they
    fit with a tile of one unit (`_resident`), else on the layout
    path."""
    unit = 16

    def whole(rows):
        return -(-rows // unit) * unit

    rows = min(whole(-(-_LOAD * tokens * k // n_experts)), whole(tokens),
               _TILE_ROWS)
    tf = _width_tile(f)
    held_whole = tokens if _resident(unit, tokens, d, f, itemsize,
                                     vmem_limit) else 0
    while rows > unit and _ffn_footprint(rows, d, tf, itemsize,
                                         held_whole) > vmem_limit:
        rows -= unit
    return rows


def _resident(tm, tokens, d, f, itemsize, vmem_limit=None):
    """Whether `moe_grouped_ffn` holds x and y whole at tiles of `tm`
    rows: a choice the shapes make."""
    limit = _VMEM_LIMIT if vmem_limit is None else vmem_limit
    return _ffn_footprint(tm, d, _width_tile(f), itemsize, tokens) <= limit


def held_expert_ffn(x, live, weights, experts, held, wg, wu, wd,
                    use_kernel=None, n_experts=None):
    """The routed experts' weighted sum over the experts held here.

    x [T, d]; live [T] bool (padding tokens are routed nowhere);
    weights/experts [T, k] from the router; held=(first, count): wg, wu
    [count, d, f] and wd [count, f, d] are experts first..first+count-1;
    `n_experts` the experts the router chose among (the held ones where
    nobody says: it sizes the tiles, `expert_tile_rows`, and no result).
    The kernel gathers each tile's rows from x and adds each pair's
    weighted output into a float32 y held in VMEM, so nothing of the
    layout's size [n_tiles * tm, d] or [T * k, d] reaches HBM; where x
    and y do not fit VMEM beside a tile (`_resident`) it takes the
    layout through HBM instead.
    Returns (y [T, d], stats): y the sum over a token's chosen experts
    that are held here, stats the step's counts as float32 scalars —
    `moe_tokens_routed` (live tokens), `moe_pairs_held` (token-expert
    pairs computed here), `moe_pairs_chosen` (k a live token),
    `moe_load_max` and `moe_load_mean` (the busiest held expert's rows
    and the mean over the held experts), `moe_experts_reached` (held
    experts with at least one row: those whose weights the step reads),
    `moe_weight_reads` (live tiles, each of which reads its expert's
    weights: equal to the experts reached where every expert's rows fit
    one tile) and `moe_calls_fused` (1 where the kernel gathered and
    summed itself, 0 on the layout path or off the kernel)."""
    T, d = x.shape
    k = experts.shape[1]
    first, count = held
    f = wg.shape[2]
    tm = expert_tile_rows(T, k, n_experts or count, d, f, x.dtype.itemsize)
    if use_kernel is None:
        use_kernel = _kernel_supported(d, f, tm)
    n_tiles = -(-T * k // tm) + count
    local = (experts - first).reshape(T * k)
    here = jnp.logical_and(
        jnp.logical_and(local >= 0, local < count),
        jnp.repeat(live, k))
    dest, tile_expert, tile_live, counts = group_by_expert(
        local, here, count, tm, n_tiles)
    w = jnp.where(here, weights.reshape(T * k), 0.0)
    y = moe_grouped_ffn(x, w, dest, wg, wu, wd, tile_expert, tile_live,
                        counts, tm, use_kernel=use_kernel)
    n_live = live.sum().astype(jnp.float32)
    pairs = counts.sum().astype(jnp.float32)
    stats = {"moe_tokens_routed": n_live,
             "moe_pairs_chosen": n_live * k,
             "moe_pairs_held": pairs,
             "moe_load_max": counts.max().astype(jnp.float32),
             "moe_load_mean": pairs / count,
             "moe_experts_reached": (counts > 0).sum().astype(jnp.float32),
             "moe_weight_reads": tile_live.sum().astype(jnp.float32),
             "moe_calls_fused": jnp.float32(
                 use_kernel and _resident(tm, T, d, f, x.dtype.itemsize))}
    return y, stats
