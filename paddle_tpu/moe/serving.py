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
the padding is gathered and written like any row. Up to ~240 rows of
bf16 the chip takes longer to read an expert's weights than to multiply
by them (197e12 FLOP/s over 819e9 B/s), so the layer is bound by
reading the held experts' weights, once each. Off the TPU the same
rows go through `jax.lax.ragged_dot`.
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
_VMEM_LIMIT = 40 * 2 ** 20  # three weight blocks in two buffers each
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


def _ffn_kernel(te_ref, tl_ref, tr_ref, x_ref, wg_ref, wu_ref, wd_ref,
                o_ref, acc_ref, *, nj):
    """Grid (row tile i, width tile j): the tile's rows through columns
    j of its expert's gate and up matrices and rows j of its down
    matrix, summed over j in float32."""
    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(tl_ref[i] != 0)
    def _live():
        x = x_ref[...]
        g = jnp.dot(x, wg_ref[0], preferred_element_type=jnp.float32)
        u = jnp.dot(x, wu_ref[0], preferred_element_type=jnp.float32)
        h = (g * jax.nn.sigmoid(g) * u).astype(x.dtype)
        acc_ref[...] += jnp.dot(h, wd_ref[0],
                                preferred_element_type=jnp.float32)

    @pl.when(j == nj - 1)
    def _store():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _ffn_example(rng):
    import numpy as np
    E, d, f, tm, n_tiles = 3, 128, 256, 16, 5
    counts = np.array([16, 0, 20])
    sizes = -(-counts // tm) * tm
    te = np.array([0, 2, 2, 2, 2], np.int32)
    tl = np.array([1, 1, 1, 0, 0], np.int32)
    xs = 0.3 * rng.standard_normal((n_tiles * tm, d)).astype(np.float32)
    ws = [0.1 * rng.standard_normal(s).astype(np.float32)
          for s in ((E, d, f), (E, d, f), (E, f, d))]
    return (xs, *ws, te, tl, sizes.astype(np.int32), tm), \
        {"use_kernel": True}


def _ffn_fallback(xs, wg, wu, wd, tile_expert, tile_live, group_sizes, tm,
                  use_kernel=None):
    return moe_grouped_ffn(xs, wg, wu, wd, tile_expert, tile_live,
                           group_sizes, tm, use_kernel=False)


@register_kernel(
    "moe_grouped_ffn", example=_ffn_example, fallback=_ffn_fallback,
    tol=(2e-3, 2e-3),
    notes="gated expert MLP over rows grouped by expert: a tile's "
          "expert comes through scalar prefetch, the width axis carries "
          "the float32 sum and must stay sequential")
@functools.partial(jax.jit, static_argnames=("tm", "use_kernel"))
def moe_grouped_ffn(xs, wg, wu, wd, tile_expert, tile_live, group_sizes,
                    tm, use_kernel=None):
    """down(silu(gate(x)) * up(x)) of rows grouped by expert.

    xs [n_tiles * tm, d]: rows ordered by expert, every expert's rows
    starting on a tile; wg, wu [E, d, f], wd [E, f, d]; tile_expert /
    tile_live [n_tiles] as `group_by_expert` gives them; group_sizes
    [E]: each expert's rows padded to whole tiles (the fallback's
    `ragged_dot` groups). Rows of dead tiles come out 0. Returns
    [n_tiles * tm, d] in xs's dtype."""
    M, d = xs.shape
    E, _, f = wg.shape
    if use_kernel is None:
        use_kernel = (jax.default_backend() == "tpu" and d % 128 == 0
                      and f % 128 == 0 and tm % 16 == 0)
    if not use_kernel:
        rdot = functools.partial(jax.lax.ragged_dot, group_sizes=group_sizes,
                                 preferred_element_type=jnp.float32)
        g, u = rdot(xs, wg.astype(xs.dtype)), rdot(xs, wu.astype(xs.dtype))
        h = (g * jax.nn.sigmoid(g) * u).astype(xs.dtype)
        out = rdot(h, wd.astype(xs.dtype)).astype(xs.dtype)
        # rows past the last group are in no product: what ragged_dot
        # leaves there differs by platform
        grouped = jnp.arange(M) < jnp.sum(group_sizes)
        return jnp.where(grouped[:, None], out, 0)
    tf = _width_tile(f)
    nj = f // tf
    # a dead tile keeps the blocks the last live tile ended on, its rows
    # (`tile_row`: the last live tile at or before each tile) and its
    # expert's last columns, so it fetches nothing; it writes zeros
    tile_row = jax.lax.cummax(jnp.where(
        tile_live != 0, jnp.arange(M // tm, dtype=jnp.int32), 0))

    def col(i, j, tl):
        return jnp.where(tl[i] != 0, j, nj - 1)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(M // tm, nj),
        in_specs=[
            pl.BlockSpec((tm, d), lambda i, j, te, tl, tr: (tr[i], 0)),
            pl.BlockSpec((1, d, tf),
                         lambda i, j, te, tl, tr: (te[i], 0, col(i, j, tl))),
            pl.BlockSpec((1, d, tf),
                         lambda i, j, te, tl, tr: (te[i], 0, col(i, j, tl))),
            pl.BlockSpec((1, tf, d),
                         lambda i, j, te, tl, tr: (te[i], col(i, j, tl), 0)),
        ],
        out_specs=pl.BlockSpec((tm, d), lambda i, j, te, tl, tr: (i, 0)),
        scratch_shapes=[pltpu.VMEM((tm, d), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_ffn_kernel, nj=nj),
        name="moe_grouped_ffn",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((M, d), xs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=_interpret(),
    )(tile_expert, tile_live, tile_row, xs, wg, wu, wd)


def _width_tile(f):
    return next((t for t in (_F_TILE, 128) if f % t == 0), f)


def _ffn_footprint(tm, d, tf, itemsize):
    """VMEM of `moe_grouped_ffn` at tiles of `tm` rows: the three weight
    blocks, the rows and the output block in two buffers each, the
    float32 accumulator, and the [tm, tf] float32 gate, up and hidden
    products as temps."""
    return vmem_footprint(
        moving=[((d, tf), itemsize)] * 3 + [((tm, d), itemsize)] * 2,
        scratch=[((tm, d), 4)],
        temp_bytes=3 * tm * tf * 4)


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
    moving block in two buffers."""
    unit = 16

    def whole(rows):
        return -(-rows // unit) * unit

    rows = min(whole(-(-_LOAD * tokens * k // n_experts)), whole(tokens),
               _TILE_ROWS)
    tf = _width_tile(f)
    while rows > unit and _ffn_footprint(rows, d, tf, itemsize) > vmem_limit:
        rows -= unit
    return rows


def held_expert_ffn(x, live, weights, experts, held, wg, wu, wd,
                    use_kernel=None, n_experts=None):
    """The routed experts' weighted sum over the experts held here.

    x [T, d]; live [T] bool (padding tokens are routed nowhere);
    weights/experts [T, k] from the router; held=(first, count): wg, wu
    [count, d, f] and wd [count, f, d] are experts first..first+count-1;
    `n_experts` the experts the router chose among (the held ones where
    nobody says: it sizes the tiles, `expert_tile_rows`, and no result).
    Returns (y [T, d], stats): y the sum over a token's chosen experts
    that are held here, stats the step's counts as float32 scalars —
    `moe_tokens_routed` (live tokens), `moe_pairs_held` (token-expert
    pairs computed here), `moe_pairs_chosen` (k a live token),
    `moe_load_max` and `moe_load_mean` (the busiest held expert's rows
    and the mean over the held experts), `moe_experts_reached` (held
    experts with at least one row: those whose weights the step reads)
    and `moe_weight_reads` (live tiles, each of which reads its expert's
    weights: equal to the experts reached where every expert's rows fit
    one tile)."""
    T, d = x.shape
    k = experts.shape[1]
    first, count = held
    tm = expert_tile_rows(T, k, n_experts or count, d, wg.shape[2],
                          x.dtype.itemsize)
    n_tiles = -(-T * k // tm) + count
    local = (experts - first).reshape(T * k)
    here = jnp.logical_and(
        jnp.logical_and(local >= 0, local < count),
        jnp.repeat(live, k))
    dest, tile_expert, tile_live, counts = group_by_expert(
        local, here, count, tm, n_tiles)
    token = jnp.repeat(jnp.arange(T, dtype=jnp.int32), k)
    src = jnp.zeros((n_tiles * tm,), jnp.int32).at[dest].set(
        token, mode="drop")
    ys = moe_grouped_ffn(x[src], wg, wu, wd, tile_expert, tile_live,
                         -(-counts // tm) * tm, tm, use_kernel=use_kernel)
    w = jnp.where(here, weights.reshape(T * k), 0.0)
    picked = ys[jnp.minimum(dest, n_tiles * tm - 1)].astype(jnp.float32)
    y = (picked * w[:, None]).reshape(T, k, d).sum(axis=1).astype(x.dtype)
    n_live = live.sum().astype(jnp.float32)
    pairs = counts.sum().astype(jnp.float32)
    stats = {"moe_tokens_routed": n_live,
             "moe_pairs_chosen": n_live * k,
             "moe_pairs_held": pairs,
             "moe_load_max": counts.max().astype(jnp.float32),
             "moe_load_mean": pairs / count,
             "moe_experts_reached": (counts > 0).sum().astype(jnp.float32),
             "moe_weight_reads": tile_live.sum().astype(jnp.float32)}
    return y, stats
