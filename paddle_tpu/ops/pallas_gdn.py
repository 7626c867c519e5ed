"""Pallas kernels of the gated delta rule (Gated DeltaNet: Yang, Kautz &
Hatamizadeh 2024, arXiv:2412.06464), inference only.

A value head keeps a state S in R^{K x V} (key channels x value
channels, float32) that one token moves by

    S <- exp(g_t) S;   u = S^T k_t;   S <- S + k_t (beta_t (v_t - u))^T;
    o_t = S^T q_t

with g_t <= 0 the head's log decay and beta_t in (0, 1) its write
strength; q and k come L2-normalised (q also scaled), and value head j
reads key head j // (value heads / key heads). The arenas the serving
engine keeps are `[rows, value heads, K, V]` float32: K along the
sublanes and V along the lanes, so that u and o are sums over sublanes
(adds of vector registers) and the rank-1 write a broadcast product.

`gdn_state_step` is the decode step: one token for each of S slots, each
with its own row of the arena. Every live row's states are read once and
written once (2 MiB a row a layer at 32 heads of 128 x 128), for a few
operations a number: it is bound by HBM. One grid step a (head tile,
slot), the slot inner: the row comes through the scalar-prefetched
`rows`, the arena is aliased to the output so that rows no slot names
are left as they are, and a slot that holds no request names the row of
the last live slot before it, whose blocks are still in VMEM: it copies
nothing in and writes nothing back (where no live slot came before it
names the null row, which goes back as it came).

`gdn_chunk` is prefill: a chunk of one request in sub-chunks of `sub`
positions, the value heads of one key head a grid row (they share q and
k) and the sub-chunks inner, the states passing from one to the next in
VMEM. Inside a sub-chunk, with
G the cumulative sum of g from its start, E_tj = exp(G_t - G_j) for
j <= t (masked BEFORE the exponential: the upper triangle overflows),
L = (K K^T o E) below the diagonal and T = (I + diag(beta) L)^-1
diag(beta):

    D   = T (V - diag(exp G) K S0)
    O   = diag(exp G) Q S0 + (Q K^T o E) D
    S_C = exp(G_last) S0 + (diag(exp(G_last - G)) K)^T D

T's inverse is the product (I - B)(I + B^2)(I + B^4)... of the strictly
lower B, which the sub-chunk's length makes nilpotent. Every product is
float32 at every pass of the MXU. A padding position carries g = 0 and
beta = 0, which moves neither outputs nor state; sub-chunks past the
chunk's last real position fetch nothing and write zeros.

Each has a `jnp` fallback that is also its parity reference.
"""
import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .kernel_registry import VMEM_BUDGET, register_kernel, vmem_footprint

__all__ = ["gdn_state_step", "gdn_chunk", "state_step_heads",
           "state_step_supported", "chunk_supported"]

_LANES = 128
_HI = jax.lax.Precision.HIGHEST
# positions of a sub-chunk of `gdn_chunk`
SUB_CHUNK = 64


def _interpret():
    return jax.default_backend() != "tpu"


def _column(row):
    """[1, n] -> [n, 1]: the row laid along the sublanes, by a mask and
    a sum over lanes (a transpose of a sliver is no Mosaic op)."""
    n = row.shape[1]
    eye = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0) \
        == jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
    return jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)


# ---------------------------------------------------------------------------
# the decode step
# ---------------------------------------------------------------------------

def state_step_heads(n_heads, n_key_heads, dk, dv):
    """Value heads a grid step of `gdn_state_step` works on: the most
    that divide the heads, whose key heads fill whole sublane tiles, and
    whose states (in and out, double-buffered) fit `VMEM_BUDGET`; 0 when
    the shapes do not tile."""
    if dv % _LANES or dk % 8 or n_heads % n_key_heads:
        return 0
    ratio = n_heads // n_key_heads
    for heads in range(n_heads, 0, -1):
        keys = heads // ratio
        if n_heads % heads or heads % ratio:
            continue
        if not ((heads % 8 == 0 or heads == n_heads)
                and (keys % 8 == 0 or keys == n_key_heads)):
            continue
        if vmem_footprint(
                moving=[((heads, dk, dv), 4)] * 2 + [((heads, dv), 4)] * 2
                + [((keys, dk), 4)] * 2,
                temp_bytes=3 * dk * dv * 4) <= VMEM_BUDGET:
            return heads
    return 0


def state_step_supported(n_heads, n_key_heads, dk, dv):
    return state_step_heads(n_heads, n_key_heads, dk, dv) > 0


def _state_step_kernel(rows_ref, live_ref, q_ref, k_ref, v_ref, g_ref,
                       b_ref, state_ref, out_state_ref, y_ref, *, ratio):
    s = pl.program_id(1)
    heads = y_ref.shape[1]

    @pl.when(live_ref[s] > 0)
    def _live():
        for i in range(heads):
            kc = _column(k_ref[0, i // ratio:i // ratio + 1, :])  # [K, 1]
            qc = _column(q_ref[0, i // ratio:i // ratio + 1, :])
            st = state_ref[0, i] * jnp.exp(g_ref[0, 0, :, i:i + 1])
            u = jnp.sum(st * kc, axis=0, keepdims=True)            # [1, V]
            st = st + kc * (b_ref[0, 0, :, i:i + 1]
                            * (v_ref[0, i:i + 1, :] - u))
            out_state_ref[0, i] = st
            y_ref[0, i:i + 1, :] = jnp.sum(st * qc, axis=0, keepdims=True)

    @pl.when(live_ref[s] == 0)
    def _dead():
        y_ref[...] = jnp.zeros_like(y_ref)

        # the blocks are the last live slot's, already written: leave
        # them; before any live slot they are the null row's, which goes
        # back as it came
        @pl.when(rows_ref[s] == 0)
        def _null():
            out_state_ref[...] = state_ref[...]


def _state_step_jnp(state, rows, live, q, k, v, g, beta):
    ratio = v.shape[1] // k.shape[1]
    kk = jnp.repeat(k, ratio, axis=1)                       # [S, H, K]
    qq = jnp.repeat(q, ratio, axis=1)
    old = state[rows] * jnp.exp(g)[:, :, None, None]        # [S, H, K, V]
    u = jnp.einsum("shkv,shk->shv", old, kk, precision=_HI)
    new = old + kk[..., None] * (beta[..., None] * (v - u))[:, :, None, :]
    y = jnp.einsum("shkv,shk->shv", new, qq, precision=_HI)
    # a slot that holds no request writes nothing
    at = jnp.where(live, rows, state.shape[0])
    return state.at[at].set(new, mode="drop"), \
        jnp.where(live[:, None, None], y, 0.0)


def _state_step_example(rng):
    S, H, Hk, K, V, R = 3, 4, 2, 128, 128, 5
    state = rng.standard_normal((R, H, K, V)).astype(np.float32)
    rows = np.asarray([2, 0, 4], np.int32)
    live = np.asarray([True, False, True])
    q = _np_l2(rng.standard_normal((S, Hk, K))) * K ** -0.5
    k = _np_l2(rng.standard_normal((S, Hk, K)))
    v = rng.standard_normal((S, H, V)).astype(np.float32)
    g = -rng.uniform(0.0, 2.0, (S, H)).astype(np.float32)
    beta = rng.uniform(0.1, 0.9, (S, H)).astype(np.float32)
    return (state, rows, live, q, k, v, g, beta), {"use_kernel": True}


def _np_l2(x):
    return (x / np.sqrt((x * x).sum(-1, keepdims=True) + 1e-6)) \
        .astype(np.float32)


def _state_step_fallback(state, rows, live, q, k, v, g, beta,
                         use_kernel=None):
    return gdn_state_step(state, rows, live, q, k, v, g, beta,
                          use_kernel=False)


@register_kernel(
    "gdn_state_step", example=_state_step_example,
    fallback=_state_step_fallback, tol=(1e-5, 1e-5),
    notes="grid (head tile, slot), the slot inner: a slot's row comes "
          "through the scalar-prefetched rows and the arena is aliased "
          "to the output; a slot that holds no request names the last "
          "live slot's row, whose blocks are unchanged, so the grid is "
          "sequential")
# jitted on its own: a model's layers share one trace and one lowering
@functools.partial(jax.jit, static_argnames=("use_kernel",))
def gdn_state_step(state, rows, live, q, k, v, g, beta, use_kernel=None):
    """One token for every slot.

    state [R, H, K, V] float32: the arena; rows [S] int32: each slot's
    row; live [S] bool; q, k [S, Hk, K] (normalised, q scaled); v
    [S, H, V]; g [S, H] (log decay, <= 0); beta [S, H]. Returns (the
    arena with the live slots' rows replaced by their states after the
    token and every other row as it was; y [S, H, V] float32 = the new
    state read by q, zeros where a slot is not live).
    """
    R, H, K, V = state.shape
    S, Hk = k.shape[:2]
    q, k, v, g, beta = (a.astype(jnp.float32) for a in (q, k, v, g, beta))
    if use_kernel is None:
        use_kernel = jax.default_backend() == "tpu" \
            and state_step_supported(H, Hk, K, V)
    if not use_kernel:
        return _state_step_jnp(state, rows, live, q, k, v, g, beta)
    heads = state_step_heads(H, Hk, K, V)
    if not heads:
        raise ValueError(f"gdn_state_step kernel: {H} heads over {Hk} key "
                         f"heads of [{K}, {V}] do not tile (see "
                         "state_step_supported)")
    nt, keys = H // heads, heads * Hk // H
    live = live.astype(jnp.int32)
    # a dead slot names the row of the last live slot at or before it
    # (the null row where there is none): the same block as the step
    # before it, which the pipeline neither fetches nor writes back
    last = jax.lax.cummax(jnp.where(live > 0, jnp.arange(S), -1))
    rows = jnp.where(last >= 0, rows.astype(jnp.int32)[jnp.maximum(last, 0)],
                     0)

    def by_slot(*block):
        return pl.BlockSpec((1, *block),
                            lambda j, s, rows, live: (s, j) + (0,) * (
                                len(block) - 1))

    arena = pl.BlockSpec((1, heads, K, V),
                         lambda j, s, rows, live: (rows[s], j, 0, 0))
    per_head = lambda a: a.reshape(S, nt, 1, heads)
    new_state, y = pl.pallas_call(
        functools.partial(_state_step_kernel, ratio=H // Hk),
        name="gdn_state_step",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(nt, S),
            in_specs=[by_slot(keys, K), by_slot(keys, K), by_slot(heads, V),
                      by_slot(1, 1, heads), by_slot(1, 1, heads), arena],
            out_specs=[arena, by_slot(heads, V)]),
        out_shape=[jax.ShapeDtypeStruct(state.shape, jnp.float32),
                   jax.ShapeDtypeStruct((S, H, V), jnp.float32)],
        # operands count the two prefetched scalars: the arena is the 8th
        input_output_aliases={7: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=_interpret(),
    )(rows, live, q, k, v, per_head(g), per_head(beta), state)
    return new_state, y


# ---------------------------------------------------------------------------
# the chunked form
# ---------------------------------------------------------------------------

def chunk_supported(chunk, sub, dk, dv):
    return chunk % sub == 0 and sub % 8 == 0 and dk % _LANES == 0 \
        and dv % _LANES == 0


def _chunk_kernel(n_real_ref, q_ref, k_ref, v_ref, gam_ref, beta_ref, s0_ref,
                  y_ref, s_out_ref, s_sc):
    """Grid step (g, c): the value heads of key head g, sub-chunk c (the
    inner axis: the heads' states pass from sub-chunk to sub-chunk in
    `s_sc`). The heads share q, k and so K K^T and Q K^T; their own
    products are written head after head a phase at a time, so that the
    independent chains of products overlap."""
    c = pl.program_id(1)
    L, heads = q_ref.shape[0], s_sc.shape[0]
    V = s_sc.shape[2]

    @pl.when(c == 0)
    def _start():
        s_sc[...] = s0_ref[...]

    @pl.when(c * L < n_real_ref[0])
    def _live():
        q, k = q_ref[...], k_ref[...]                           # [L, K]
        rows = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
        dots = lambda a, b: jax.lax.dot_general(
            a, b, (((1,), (1,)), ((), ())), precision=_HI,
            preferred_element_type=jnp.float32)
        mm = lambda a, b: jnp.dot(a, b, precision=_HI,
                                  preferred_element_type=jnp.float32)
        kk, qk = dots(k, k), dots(q, k)                         # [L, L]
        hs = range(heads)
        gam = [gam_ref[h, 0] for h in hs]                       # [1, L]
        gam_c = [_column(x) for x in gam]                       # [L, 1]
        beta = [beta_ref[h, 0] for h in hs]
        # exp(G_t - G_j) where j <= t, masked before the exponential
        decay = [jnp.exp(jnp.where(rows >= cols, gam_c[h] - gam[h], -1e30))
                 for h in hs]
        b = [jnp.where(rows > cols, kk * decay[h], 0.0) * _column(beta[h])
             for h in hs]
        # (I + b)^-1 = (I - b)(I + b^2)(I + b^4)..., b^n = 0 from n = L
        eye = (rows == cols).astype(jnp.float32)
        inv, power, n = [eye - x for x in b], b, 2
        while n < L:
            power = [mm(x, x) for x in power]
            inv = [x + mm(x, p) for x, p in zip(inv, power)]
            n *= 2
        s = [s_sc[h] for h in hs]                               # [K, V]
        eg = [jnp.exp(x) for x in gam_c]                        # [L, 1]
        ks0 = [mm(k, x) for x in s]
        delta = [mm(inv[h] * beta[h], v_ref[:, h * V:(h + 1) * V]
                    - eg[h] * ks0[h]) for h in hs]              # [L, V]
        qs0 = [mm(q, x) for x in s]
        for h in hs:
            y_ref[:, h * V:(h + 1) * V] = eg[h] * qs0[h] \
                + mm(qk * decay[h], delta[h])
        last = [x[:, L - 1:L] for x in gam]                     # [1, 1]
        for h in hs:
            s_sc[h] = jnp.exp(last[h]) * s[h] + jax.lax.dot_general(
                k * jnp.exp(last[h] - gam_c[h]), delta[h],
                (((0,), (0,)), ((), ())), precision=_HI,
                preferred_element_type=jnp.float32)

    @pl.when(c * L >= n_real_ref[0])
    def _dead():
        y_ref[...] = jnp.zeros_like(y_ref)

    @pl.when(c == pl.num_programs(1) - 1)
    def _end():
        s_out_ref[...] = s_sc[...]


def _chunk_jnp(q, k, v, g, beta, state0, sub):
    """The chunked form in plain jnp, a sub-chunk at a time, with a
    triangular solve where the kernel multiplies by the inverse."""
    C, H = g.shape
    ratio = H // k.shape[1]
    qq = jnp.repeat(q, ratio, axis=1)                        # [C, H, K]
    kk = jnp.repeat(k, ratio, axis=1)
    state, ys = state0, []
    for c0 in range(0, C, sub):
        sl = slice(c0, min(c0 + sub, C))
        qs, ks, vs, bs = qq[sl], kk[sl], v[sl], beta[sl]
        L = bs.shape[0]
        gam = jnp.cumsum(g[sl], axis=0)                      # [L, H]
        lower = jnp.tril(jnp.ones((L, L), bool))
        diff = jnp.where(lower[:, :, None], gam[:, None] - gam[None], -1e30)
        decay = jnp.exp(diff)                                # [t, j, H]
        kkt = jnp.einsum("thk,jhk->tjh", ks, ks, precision=_HI)
        strict = jnp.tril(jnp.ones((L, L), bool), -1)[:, :, None]
        a = jnp.where(strict, kkt * decay, 0.0) * bs[:, None, :]
        eg = jnp.exp(gam)                                    # [L, H]
        rhs = bs[..., None] * (vs - eg[..., None] * jnp.einsum(
            "thk,hkv->thv", ks, state, precision=_HI))
        # (I + diag(beta) L) D = diag(beta) (V - diag(exp G) K S0), by head
        lhs = jnp.moveaxis(a, 2, 0) + jnp.eye(L)[None]
        delta = jax.lax.linalg.triangular_solve(
            lhs, jnp.moveaxis(rhs, 1, 0), left_side=True, lower=True,
            unit_diagonal=True)                              # [H, L, V]
        delta = jnp.moveaxis(delta, 0, 1)
        qkt = jnp.einsum("thk,jhk->tjh", qs, ks, precision=_HI) * decay
        ys.append(eg[..., None] * jnp.einsum("thk,hkv->thv", qs, state,
                                             precision=_HI)
                  + jnp.einsum("tjh,jhv->thv", qkt, delta, precision=_HI))
        w = jnp.exp(gam[-1][None] - gam)                     # [L, H]
        state = jnp.exp(gam[-1])[:, None, None] * state + jnp.einsum(
            "jhk,jhv->hkv", ks * w[..., None], delta, precision=_HI)
    return jnp.concatenate(ys), state


def _chunk_example(rng):
    C, H, Hk, K, V = 128, 4, 2, 128, 128
    q = _np_l2(rng.standard_normal((C, Hk, K))) * K ** -0.5
    k = _np_l2(rng.standard_normal((C, Hk, K)))
    v = rng.standard_normal((C, H, V)).astype(np.float32)
    g = -rng.uniform(0.0, 0.5, (C, H)).astype(np.float32)
    beta = rng.uniform(0.1, 0.9, (C, H)).astype(np.float32)
    g[100:], beta[100:] = 0.0, 0.0          # padding positions
    state0 = rng.standard_normal((H, K, V)).astype(np.float32)
    return (q, k, v, g, beta, state0, np.int32(100)), \
        {"sub": 64, "use_kernel": True}


def _chunk_fallback(q, k, v, g, beta, state0, n_real, sub=SUB_CHUNK,
                    use_kernel=None):
    return gdn_chunk(q, k, v, g, beta, state0, n_real, sub=sub,
                     use_kernel=False)


@register_kernel(
    "gdn_chunk", example=_chunk_example, fallback=_chunk_fallback,
    tol=(2e-4, 2e-4),
    notes="grid (key head, sub-chunk), the sub-chunk the inner axis: "
          "the heads' states pass from sub-chunk to sub-chunk in VMEM "
          "scratch and are written out after the last; sub-chunks past "
          "n_real (scalar-prefetched) keep the last live one's blocks")
@functools.partial(jax.jit, static_argnames=("sub", "use_kernel"))
def gdn_chunk(q, k, v, g, beta, state0, n_real, sub=SUB_CHUNK,
              use_kernel=None):
    """A chunk of one request through the delta rule, in sub-chunks.

    q, k [C, Hk, K] (normalised, q scaled); v [C, H, V]; g [C, H] (log
    decay) and beta [C, H], both 0 at padding positions; state0 [H, K, V]
    float32: the state before the chunk; n_real: the chunk's real
    positions (traced). Returns (y [C, H, V] float32, zeros past the
    last real sub-chunk with the kernel; the state after the chunk's
    last real position).
    """
    C, H = g.shape
    Hk, K = k.shape[1:]
    V = v.shape[2]
    sub = min(int(sub), C)
    q, k, v, g, beta = (a.astype(jnp.float32) for a in (q, k, v, g, beta))
    state0 = state0.astype(jnp.float32)
    if use_kernel is None:
        use_kernel = jax.default_backend() == "tpu" \
            and chunk_supported(C, sub, K, V)
    if not use_kernel:
        return _chunk_jnp(q, k, v, g, beta, state0, sub)
    if not chunk_supported(C, sub, K, V):
        raise ValueError(f"gdn_chunk kernel: a chunk of {C} in sub-chunks "
                         f"of {sub} over [{K}, {V}] does not tile (see "
                         "chunk_supported)")
    nc, ratio = C // sub, H // Hk
    # the cumulative log decay inside each sub-chunk, and beta, as one
    # row a (head, sub-chunk)
    gam = jnp.cumsum(g.reshape(nc, sub, H), axis=1)
    rows = lambda a: jnp.transpose(a.reshape(nc, sub, H), (2, 0, 1))[
        :, :, None, :]
    n_real = jnp.reshape(jnp.asarray(n_real, jnp.int32), (1,))

    def live(c, n):     # a dead sub-chunk keeps the last live one's blocks
        return jnp.minimum(c, jnp.maximum(n[0] - 1, 0) // sub)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(Hk, nc),
        in_specs=[
            pl.BlockSpec((sub, K), lambda kh, c, n: (live(c, n), kh)),
            pl.BlockSpec((sub, K), lambda kh, c, n: (live(c, n), kh)),
            pl.BlockSpec((sub, ratio * V),
                         lambda kh, c, n: (live(c, n), kh)),
            pl.BlockSpec((ratio, 1, 1, sub),
                         lambda kh, c, n: (kh, live(c, n), 0, 0)),
            pl.BlockSpec((ratio, 1, 1, sub),
                         lambda kh, c, n: (kh, live(c, n), 0, 0)),
            pl.BlockSpec((ratio, K, V), lambda kh, c, n: (kh, 0, 0)),
        ],
        out_specs=[pl.BlockSpec((sub, ratio * V), lambda kh, c, n: (c, kh)),
                   pl.BlockSpec((ratio, K, V), lambda kh, c, n: (kh, 0, 0))],
        scratch_shapes=[pltpu.VMEM((ratio, K, V), jnp.float32)])
    y, state = pl.pallas_call(
        _chunk_kernel,
        name="gdn_chunk",
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((C, H * V), jnp.float32),
                   jax.ShapeDtypeStruct((H, K, V), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=_interpret(),
    )(n_real, q.reshape(C, Hk * K), k.reshape(C, Hk * K), v.reshape(C, H * V),
      rows(gam), rows(beta), state0)
    return y.reshape(C, H, V), state
