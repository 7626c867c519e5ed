"""Pallas kernels of the Mamba-2 state-space layer (Dao & Gu 2024,
arXiv:2405.21060), inference only.

A head h keeps a state S[h] in R^{P x N} (P the head's channels, N the
state size) that one token moves by

    S_t[h] = exp(dt_t[h] A[h]) S_{t-1}[h] + dt_t[h] x_t[h] (x) B_t
    y_t[h] = S_t[h] C_t

with one B_t and C_t [N] for all heads. Both kernels hold the state
TRANSPOSED, `[N, channels]` with channels = heads * P: the channels lie
along the lanes, a head is P neighbouring lanes, and the sum over N that
gives y is an add of vector registers instead of a reduction inside each
(the [heads, P, N] layout would reduce over lanes 4,096 times a token a
layer). The arenas the serving engine keeps are `[rows, N, channels]`
float32.

`mamba2_state_step` is the decode step: one token for each of S slots,
each with its own row of the arena. It reads and writes the whole state
of every live row (2 MiB a row a layer at 64 heads x 64 x 128) for a few
FLOP a number, so what it can reach is the HBM bandwidth. One grid step
a (slot, channel tile): the row is found through the scalar-prefetched
`rows`, the arena is aliased to the output so that rows no slot names
are left as they are, and a slot that holds no request (`live` false)
writes zeros to the null row it names.

`mamba2_chunk_scan` is the chunked prefill of section 6 of the paper:
a chunk of one request in pieces of `piece` tokens. Inside a piece the
outputs are `Y = (L o C B^T) X` with `L[t, j] = exp(sum_{j<m<=t} dt_m A)`
for j <= t; the incoming state adds `exp(cum_t) C_t S`, and the state is
passed on as `exp(cum_last) S + B^T (w o X)`. Four products a piece, the
state in float32 in VMEM from piece to piece, the decays computed from
cumulative sums that XLA makes outside (float32: they are exponents). A
padding position carries dt = 0, which moves neither outputs nor state.

Each has a `jnp` fallback that is also its parity reference.
"""
import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .kernel_registry import VMEM_BUDGET, register_kernel, vmem_footprint

_LANES = 128
_HI = jax.lax.Precision.HIGHEST
# channels one grid step of the state step works on: 1 MiB of state at
# N = 128, in and out and double-buffered 4 MiB
_STEP_CHANNELS = 2048
# channels one grid step of the chunk scan works on
_SCAN_CHANNELS = 512


def _interpret():
    return jax.default_backend() != "tpu"


# ---------------------------------------------------------------------------
# the decode step
# ---------------------------------------------------------------------------

def state_step_tile(n_state, channels):
    """Channels a grid step of `mamba2_state_step` works on: the most,
    in whole 128-lane columns that divide `channels`, up to
    `_STEP_CHANNELS` and within `VMEM_BUDGET`; 0 when the shapes do not
    tile."""
    if channels % _LANES or n_state % 8:
        return 0
    tile = min(channels, _STEP_CHANNELS)
    while tile and (channels % tile or vmem_footprint(
            moving=[((n_state, tile), 4)] * 2 + [((1, tile), 4)] * 3,
            temp_bytes=3 * n_state * tile * 4) > VMEM_BUDGET):
        tile -= _LANES
    return tile


def state_step_supported(n_state, channels):
    return state_step_tile(n_state, channels) > 0


def _column(row):
    """[1, N] -> [N, 1]: the row laid along the sublanes, by a mask and
    a sum over lanes (N is small: a transpose of a sliver is no Mosaic
    op)."""
    n = row.shape[1]
    eye = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0) \
        == jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
    return jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)


def _state_step_kernel(rows_ref, live_ref, decay_ref, dx_ref, b_ref, c_ref,
                       state_ref, out_state_ref, y_ref):
    s = pl.program_id(0)
    b = _column(b_ref[0].astype(jnp.float32))               # [N, 1]
    c = _column(c_ref[0].astype(jnp.float32))
    new = state_ref[0] * decay_ref[0] + b * dx_ref[0]       # [N, tile]
    new = jnp.where(live_ref[s] > 0, new, 0.0)
    out_state_ref[0] = new
    y_ref[0] = jnp.sum(new * c, axis=0, keepdims=True)


def _state_step_jnp(state, rows, live, decay, dx, b, c):
    old = state[rows]                                       # [S, N, D]
    new = old * decay[:, None, :] \
        + b.astype(jnp.float32)[:, :, None] * dx[:, None, :]
    new = jnp.where(live[:, None, None], new, 0.0)
    y = jnp.sum(new * c.astype(jnp.float32)[:, :, None], axis=1)
    return state.at[rows].set(new), y


def _state_step_example(rng):
    S, N, D, R = 3, 16, 256, 5
    state = rng.standard_normal((R, N, D)).astype(np.float32)
    rows = np.asarray([2, 0, 4], np.int32)
    live = np.asarray([True, False, True])
    decay = rng.uniform(0.5, 1.0, (S, D)).astype(np.float32)
    dx = 0.1 * rng.standard_normal((S, D)).astype(np.float32)
    b = rng.standard_normal((S, N)).astype(np.float32)
    c = rng.standard_normal((S, N)).astype(np.float32)
    return (state, rows, live, decay, dx, b, c), {"use_kernel": True}


def _state_step_fallback(state, rows, live, decay, dx, b, c,
                         use_kernel=None):
    return mamba2_state_step(state, rows, live, decay, dx, b, c,
                             use_kernel=False)


@register_kernel(
    "mamba2_state_step", example=_state_step_example,
    fallback=_state_step_fallback, tol=(1e-5, 1e-5),
    notes="one grid step a (slot, channel tile); the state arena is "
          "aliased to the output and a slot's row found through the "
          "scalar-prefetched rows (KN505 covers the prefetch channel); "
          "slots that hold no request all name the null row and write "
          "zeros there, so the grid is sequential")
# jitted on its own: a model's layers share one trace and one lowering
@functools.partial(jax.jit, static_argnames=("use_kernel",))
def mamba2_state_step(state, rows, live, decay, dx, b, c, use_kernel=None):
    """One token for every slot.

    state [R, N, D] float32: the arena, D = heads * head_dim channels;
    rows [S] int32: each slot's row; live [S] bool; decay [S, D]
    float32: exp(dt A) of the channel's head; dx [S, D] float32:
    dt * x; b, c [S, N]. Returns (the arena with the slots' rows
    replaced by `decay * S + b (x) dx`, zeros where a slot is not live;
    y [S, D] float32 = the new state summed against c).
    """
    R, N, D = state.shape
    S = rows.shape[0]
    if use_kernel is None:
        use_kernel = jax.default_backend() == "tpu" \
            and state_step_supported(N, D)
    decay = decay.astype(jnp.float32)
    dx = dx.astype(jnp.float32)
    if not use_kernel:
        return _state_step_jnp(state, rows, live, decay, dx, b, c)
    tile = state_step_tile(N, D)
    if not tile:
        raise ValueError(f"mamba2_state_step kernel: a state of [{N}, {D}] "
                         "does not tile (see state_step_supported)")

    def by_slot(width):
        return pl.BlockSpec((1, 1, width), lambda s, j, rows, live: (s, 0, j))

    def whole(width):
        return pl.BlockSpec((1, 1, width), lambda s, j, rows, live: (s, 0, 0))

    arena = pl.BlockSpec((1, N, tile),
                         lambda s, j, rows, live: (rows[s], 0, j))
    new_state, y = pl.pallas_call(
        _state_step_kernel,
        name="mamba2_state_step",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(S, D // tile),
            in_specs=[by_slot(tile), by_slot(tile), whole(N), whole(N),
                      arena],
            out_specs=[arena, by_slot(tile)]),
        out_shape=[jax.ShapeDtypeStruct(state.shape, jnp.float32),
                   jax.ShapeDtypeStruct((S, 1, D), jnp.float32)],
        # operands count the two prefetched scalars: the arena is the 7th
        input_output_aliases={6: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=_interpret(),
    )(rows.astype(jnp.int32), live.astype(jnp.int32),
      decay[:, None], dx[:, None], b[:, None], c[:, None], state)
    return new_state, y[:, 0]


# ---------------------------------------------------------------------------
# the chunked scan
# ---------------------------------------------------------------------------

def chunk_scan_tile(chunk, piece, n_state, channels, head_dim):
    """Channels a grid step of `mamba2_chunk_scan` works on (whole
    128-lane columns of whole heads), 0 when the shapes do not tile."""
    if channels % _LANES or _LANES % head_dim or chunk % piece \
            or piece % _LANES or n_state % 8:
        return 0
    tile = min(channels, _SCAN_CHANNELS)
    while tile and channels % tile:
        tile -= _LANES
    return tile


def chunk_scan_supported(chunk, piece, n_state, channels, head_dim):
    return chunk_scan_tile(chunk, piece, n_state, channels, head_dim) > 0


def _chunk_scan_kernel(x_ref, dtc_ref, cumc_ref, dtr_ref, cumr_ref, b_ref,
                       c_ref, s0_ref, y_ref, s_out_ref, s_sc,
                       *, head_dim):
    """Grid step (g, k): channel tile g, piece k of the chunk (the
    inner axis: the state of tile g passes from piece to piece in
    `s_sc`). `dtc`/`cumc` [cols, L, hp] hold dt and the piece's
    cumulative dt*A of the tile's heads as columns, `dtr`/`cumr`
    [cols, hp, L] the same as rows: the decay matrix of a head needs
    both orientations."""
    k = pl.program_id(1)
    L, tile = x_ref.shape
    cols, hp = tile // _LANES, _LANES // head_dim

    @pl.when(k == 0)
    def _start():
        s_sc[...] = s0_ref[...]

    bm, cm = b_ref[...], c_ref[...]                         # [L, N]
    # float32 operands (the registry's example, the tests) want every
    # pass of the MXU; bfloat16 ones are a single pass anyway
    exact = _HI if x_ref.dtype == jnp.float32 else None
    # what position t reads of position j, before the decay: C_t . B_j
    cb = jax.lax.dot_general(cm, bm, (((1,), (1,)), ((), ())),
                             precision=exact,
                             preferred_element_type=jnp.float32)   # [L, L]
    causal = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0) \
        >= jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
    lane_head = jax.lax.broadcasted_iota(jnp.int32, (L, _LANES), 1) \
        // head_dim
    cm32, bm32 = cm.astype(jnp.float32), bm.astype(jnp.float32)
    for col in range(cols):
        at = pl.ds(col * _LANES, _LANES)
        x = x_ref[:, at]                                    # [L, 128]
        state = s_sc[:, at]                                 # [N, 128]
        y = jnp.zeros((L, _LANES), jnp.float32)
        # per lane: the decay since the piece began, the weight of a
        # position in the state passed on, the whole piece's decay
        since = jnp.zeros((L, _LANES), jnp.float32)
        weight = jnp.zeros((L, _LANES), jnp.float32)
        whole = jnp.zeros((1, _LANES), jnp.float32)
        for h in range(hp):
            cum_t = cumc_ref[col, :, h:h + 1]               # [L, 1]
            cum_j = cumr_ref[col, h:h + 1, :]               # [1, L]
            dt_j = dtr_ref[col, h:h + 1, :]
            decay = jnp.exp(jnp.where(causal, cum_t - cum_j, -1e30))
            m = (cb * decay * dt_j).astype(x.dtype)         # [L, L]
            mine = lane_head == h
            # a product as wide as the column costs the MXU what one as
            # wide as the head would
            y = y + jnp.where(mine, jax.lax.dot_general(
                m, x, (((1,), (0,)), ((), ())), precision=exact,
                preferred_element_type=jnp.float32), 0.0)
            last = cum_t[L - 1:L]                           # [1, 1]
            since = jnp.where(mine, jnp.exp(cum_t), since)
            weight = jnp.where(
                mine, jnp.exp(last - cum_t) * dtc_ref[col, :, h:h + 1],
                weight)
            whole = jnp.where(mine[:1], jnp.exp(last), whole)
        y = y + since * jax.lax.dot_general(
            cm32, state, (((1,), (0,)), ((), ())), precision=_HI,
            preferred_element_type=jnp.float32)
        y_ref[:, at] = y
        s_sc[:, at] = whole * state + jax.lax.dot_general(
            bm32, weight * x.astype(jnp.float32), (((0,), (0,)), ((), ())),
            precision=_HI, preferred_element_type=jnp.float32)

    @pl.when(k == pl.num_programs(1) - 1)
    def _end():
        s_out_ref[...] = s_sc[...]


def _chunk_scan_jnp(x, dt, a, b, c, state0, piece):
    """The chunked form in plain jnp: the kernel's own arithmetic, a
    piece at a time."""
    C, D = x.shape
    H = dt.shape[1]
    P = D // H
    xf = x.astype(jnp.float32).reshape(C, H, P)
    bf, cf = b.astype(jnp.float32), c.astype(jnp.float32)
    state = state0.reshape(-1, H, P)                        # [N, H, P]
    ys = []
    for p0 in range(0, C, piece):
        sl = slice(p0, min(p0 + piece, C))      # the last may be short
        dtp = dt[sl]                                        # [L, H]
        L = dtp.shape[0]
        cum = jnp.cumsum(dtp * a[None], axis=0)
        cb = jnp.einsum("tn,jn->tj", cf[sl], bf[sl], precision=_HI)
        causal = jnp.tril(jnp.ones((L, L), bool))
        decay = jnp.exp(jnp.where(causal[:, :, None],
                                  cum[:, None] - cum[None], -1e30))
        m = cb[:, :, None] * decay * dtp[None]              # [t, j, H]
        y = jnp.einsum("tjh,jhp->thp", m, xf[sl], precision=_HI)
        y = y + jnp.exp(cum)[:, :, None] * jnp.einsum(
            "tn,nhp->thp", cf[sl], state, precision=_HI)
        w = jnp.exp(cum[-1][None] - cum) * dtp              # [L, H]
        state = jnp.exp(cum[-1])[None, :, None] * state + jnp.einsum(
            "jn,jhp->nhp", bf[sl], w[:, :, None] * xf[sl], precision=_HI)
        ys.append(y.reshape(L, D))
    return jnp.concatenate(ys), state.reshape(state0.shape)


def _chunk_scan_example(rng):
    C, H, P, N, piece = 256, 4, 32, 16, 128
    x = 0.5 * rng.standard_normal((C, H * P)).astype(np.float32)
    dt = rng.uniform(0.01, 0.2, (C, H)).astype(np.float32)
    dt[200:] = 0.0                      # padding positions
    a = -rng.uniform(1.0, 4.0, (H,)).astype(np.float32)
    b = rng.standard_normal((C, N)).astype(np.float32)
    c = rng.standard_normal((C, N)).astype(np.float32)
    state0 = rng.standard_normal((N, H * P)).astype(np.float32)
    return (x, dt, a, b, c, state0), {"piece": piece, "use_kernel": True}


def _chunk_scan_fallback(x, dt, a, b, c, state0, piece=256,
                         use_kernel=None):
    return mamba2_chunk_scan(x, dt, a, b, c, state0, piece=piece,
                             use_kernel=False)


@register_kernel(
    "mamba2_chunk_scan", example=_chunk_scan_example,
    fallback=_chunk_scan_fallback, tol=(2e-4, 2e-4),
    notes="grid (channel tile, piece), the piece the inner axis: a "
          "tile's state passes from piece to piece in VMEM scratch and "
          "is written out after the last")
@functools.partial(jax.jit, static_argnames=("piece", "use_kernel"))
def mamba2_chunk_scan(x, dt, a, b, c, state0, piece=256, use_kernel=None):
    """A chunk of one request through the recurrence, in pieces.

    x [C, D]: the chunk's inputs, D = heads * head_dim channels;
    dt [C, H] float32: the step sizes after the softplus, 0 at padding
    positions; a [H] float32 (negative); b, c [C, N]; state0 [N, D]
    float32: the state before the chunk. Returns (y [C, D] float32,
    without the skip term D * x; the state after the chunk's last
    position with dt > 0).
    """
    C, D = x.shape
    H = dt.shape[1]
    N = state0.shape[0]
    P = D // H
    piece = min(int(piece), C)
    dt = dt.astype(jnp.float32)
    a = a.astype(jnp.float32)
    if use_kernel is None:
        use_kernel = jax.default_backend() == "tpu" \
            and chunk_scan_supported(C, piece, N, D, P)
    if not use_kernel:
        return _chunk_scan_jnp(x, dt, a, b, c, state0, piece)
    tile = chunk_scan_tile(C, piece, N, D, P)
    if not tile:
        raise ValueError(
            f"mamba2_chunk_scan kernel: a chunk of {C} in pieces of "
            f"{piece} over [{N}, {D}] with heads of {P} does not tile "
            "(see chunk_scan_supported)")
    cols, hp = tile // _LANES, _LANES // P
    # cumulative dt * A inside each piece, in float32: they are
    # exponents. Heads are grouped by the 128-lane column they share.
    cum = jnp.cumsum((dt * a[None]).reshape(C // piece, piece, H), axis=1) \
        .reshape(C, H)

    def as_columns(v):      # [C, H] -> [D // 128, C, hp]
        return jnp.transpose(v.reshape(C, D // _LANES, hp), (1, 0, 2))

    def as_rows(v):         # [C, H] -> [D // 128, hp, C]
        return jnp.transpose(v.reshape(C, D // _LANES, hp), (1, 2, 0))

    grid = (D // tile, C // piece)
    col_spec = pl.BlockSpec((cols, piece, hp), lambda g, k: (g, k, 0))
    row_spec = pl.BlockSpec((cols, hp, piece), lambda g, k: (g, 0, k))
    bc_spec = pl.BlockSpec((piece, N), lambda g, k: (k, 0))
    state_spec = pl.BlockSpec((N, tile), lambda g, k: (0, g))
    x_spec = pl.BlockSpec((piece, tile), lambda g, k: (k, g))
    bc = b.astype(x.dtype), c.astype(x.dtype)
    y, state = pl.pallas_call(
        functools.partial(_chunk_scan_kernel, head_dim=P),
        name="mamba2_chunk_scan",
        grid=grid,
        in_specs=[x_spec, col_spec, col_spec, row_spec, row_spec, bc_spec,
                  bc_spec, state_spec],
        out_specs=[x_spec, state_spec],
        out_shape=[jax.ShapeDtypeStruct((C, D), jnp.float32),
                   jax.ShapeDtypeStruct((N, D), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((N, tile), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=_interpret(),
    )(x, as_columns(dt), as_columns(cum), as_rows(dt), as_rows(cum),
      *bc, state0)
    return y, state
