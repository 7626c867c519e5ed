"""Plain reference of Granite 4.0-H (`granitemoehybrid` with no experts),
after the published `modeling_granitemoehybrid.py`: RMSNorm, Mamba-2
layers (Dao & Gu 2024, arXiv:2405.21060) among grouped-query attention
layers without positions, a gated MLP in every block, the four
multipliers, a tied head.

Straightforward `jax.numpy` in float32 with every contraction at
`Precision.HIGHEST`. The Mamba-2 layer is the RECURRENCE ITSELF, one
token at a time (`lax.scan`): no chunked form, no kernels, no cache, no
batching; attention repeats each K/V head under the query heads that
read it. One sequence at a time through one layer at a time, the layer's
weights drawn when it is reached and dropped after. It imports nothing
of the program under test. Weights are drawn here, leaf by leaf, from
the seed (`draw`); the benchmark's driver draws the same leaves for the
program under the same names.

Departures from the published code, each marked `# departs:` below:
  * a matrix is stored [in, out] and applied as x @ W (published:
    [out, in], x @ W^T), `shared_mlp.input_linear` as its two halves
    `mlp.gate | mlp.up`, and the convolution's taps as [taps, channels]
    (published: [channels, 1, taps]); with seeded weights these are
    relabellings;
  * the published module computes the Mamba-2 layer in the chunked form
    (or by fused kernels); the recurrence here is what both compute;
  * sequences are padded to one length so that one compiled program
    serves them all; attention and recurrence are causal, so no
    position sees the padding behind it.

A `prec` argument selects the arithmetic, so that the same code is the
low-precision control of the `correct` comparison:

  act     "f32" (reference) | "bf16" | "fp8": operands of every
          contraction (fp8: e4m3 under a per-tensor scale)
  state   None | "bf16": the recurrent state rounded to bfloat16 after
          every token, as a bfloat16 state arena would hold it
"""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
REFERENCE = {"act": "f32"}


# ---------------------------------------------------------------------------
# seeded weights, one leaf at a time
# ---------------------------------------------------------------------------

EMBED, FINAL_NORM = 1, 2                # tags of the leaves outside layers
_LAYER_TAG = 100                        # layer l is tagged 100 + l


def sizes(config):
    """The sizes this code runs: the configuration file's own keys."""
    m = dict(config)
    m["head_dim"] = m["hidden_size"] // m["num_attention_heads"]
    m["d_inner"] = m["mamba_n_heads"] * m["mamba_d_head"]
    m["conv_dim"] = m["d_inner"] + 2 * m["mamba_n_groups"] * m["mamba_d_state"]
    assert m["mamba_n_groups"] == 1
    assert m["d_inner"] == m["mamba_expand"] * m["hidden_size"]
    assert len(m["layer_types"]) == m["num_hidden_layers"]
    return m


def layer_leaves(m, layer):
    """(name, shape, kind) of a layer's leaves, in the order their keys
    are folded; the program holds them under `blocks.<layer>.<name>`.
    kind: "w" a matrix, "g" a gain, "conv" the convolution's taps and
    bias, "A_log", "dt_bias", "D"."""
    d, f = m["hidden_size"], m["shared_intermediate_size"]
    if m["layer_types"][layer] == "attention":
        H = m["head_dim"]
        q, kv = m["num_attention_heads"] * H, m["num_key_value_heads"] * H
        mixer = [("attn.q", (d, q), "w"), ("attn.k", (d, kv), "w"),
                 ("attn.v", (d, kv), "w"), ("attn.o", (q, d), "w")]
    else:
        di, cd, nh = m["d_inner"], m["conv_dim"], m["mamba_n_heads"]
        mixer = [("mamba.in_proj", (d, di + cd + nh), "w"),
                 ("mamba.conv_w", (m["mamba_d_conv"], cd), "conv"),
                 ("mamba.conv_b", (cd,), "conv"),
                 ("mamba.dt_bias", (nh,), "dt_bias"),
                 ("mamba.A_log", (nh,), "A_log"),
                 ("mamba.D", (nh,), "D"),
                 ("mamba.norm", (di,), "g"),
                 ("mamba.out_proj", (di, d), "w")]
    return [("norm1", (d,), "g")] + mixer + [
        ("norm2", (d,), "g"), ("mlp.gate", (d, f), "w"),
        ("mlp.up", (d, f), "w"), ("mlp.down", (f, d), "w")]


@functools.partial(jax.jit, static_argnames=("shape", "kind", "dtype",
                                             "taps", "tail"))
def _draw(key, tag, index, std, shape, kind, dtype, taps, tail, tail_scale):
    key = jax.random.fold_in(jax.random.fold_in(key, tag), index)
    if kind in ("w", "g"):
        x = std * jax.random.normal(key, shape, jnp.float32)
        if kind == "g":
            x = 1.0 + x
        if tail:        # the last `tail` columns scaled (in_proj's dt)
            x = x * jnp.where(jnp.arange(shape[-1]) >= shape[-1] - tail,
                              tail_scale, 1.0)
    elif kind == "D":
        x = jnp.ones(shape, jnp.float32)
    else:
        u = jax.random.uniform(key, shape, jnp.float32)
        if kind == "A_log":             # A uniform in [1, 16]
            x = jnp.log(1.0 + 15.0 * u)
        elif kind == "dt_bias":         # dt log-uniform in [0.001, 0.1]
            dt = jnp.exp(u * (math.log(0.1) - math.log(0.001))
                         + math.log(0.001))
            x = dt + jnp.log(-jnp.expm1(-dt))       # inverse softplus
        else:                           # "conv": +-1/sqrt(taps)
            x = (2.0 * u - 1.0) / math.sqrt(taps)
    return _bf16(x).astype(dtype)


def _bf16(x):
    """float32 `x` rounded to the numbers bfloat16 holds, still float32.
    Not `x.astype(bfloat16).astype(float32)`: on the TPU XLA drops such
    a pair of conversions inside one program (it may keep more precision
    than was asked for), and the rounding with it."""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def draw(seed, tag, index, shape, kind, std, dtype=jnp.float32, taps=4,
         tail=0, tail_scale=1.0):
    """One leaf from the seed, rounded to bfloat16 (the stated parameter
    dtype) and given back in `dtype`: N(0, std) for a matrix (its last
    `tail` columns times `tail_scale`), 1 + N(0, std) for a gain, and
    the Mamba-2 leaves as the paper's code draws them (`assumed` in the
    configuration file). One compiled program a shape."""
    return _draw(jax.random.PRNGKey(int(seed)), tag, index, std,
                 tuple(shape), kind, jnp.dtype(dtype), int(taps), int(tail),
                 tail_scale)


def stds(m, init=None):
    """{kind: std} of a leaf's draw. "w" a block matrix, "g" gains and
    the embedding. `init` is a cell's `init` (PERF.md section 2):
    `block_scale`, the factor on the blocks' matrices."""
    base = float(m["initializer_range"])
    return {"w": base * float((init or {}).get("block_scale", 1.0)),
            "g": base}


# The factor on `in_proj`'s last `mamba_n_heads` columns, which give dt
# (`assumed.dt_columns` in the configuration file): drawn like the rest
# of the matrix at `initializer_range` 0.1 and d = 2,048 they would add
# N(0, 4.5) to `dt_bias`, the step sizes would leave the range the
# initialiser draws them in and the recurrence would forget within a
# handful of tokens, so that nothing the state carries could be seen.
DT_COLUMNS = 0.1


def layer_leaf(m, seed, layer, j, init=None, dtype=jnp.float32):
    """Leaf `j` of `layer_leaves(m, layer)`."""
    name, shape, kind = layer_leaves(m, layer)[j]
    dt_cols = m["mamba_n_heads"] if name == "mamba.in_proj" else 0
    return draw(seed, _LAYER_TAG + layer, j, shape, kind,
                stds(m, init).get(kind, 0.0), dtype, m["mamba_d_conv"],
                tail=dt_cols, tail_scale=DT_COLUMNS)


def layer_weights(m, seed, layer, init=None, dtype=jnp.float32):
    return {name: layer_leaf(m, seed, layer, j, init, dtype)
            for j, (name, _, _) in enumerate(layer_leaves(m, layer))}


def outer_weights(m, seed, which, dtype=jnp.float32):
    """`which`: EMBED (also the head: tied) or FINAL_NORM."""
    d, V = m["hidden_size"], m["vocab_size"]
    shape, kind = {EMBED: ((V, d), "w"), FINAL_NORM: ((d,), "g")}[which]
    return draw(seed, which, 0, shape, kind, stds(m)["g"], dtype)


# ---------------------------------------------------------------------------
# arithmetic
# ---------------------------------------------------------------------------

def _fp8(x):
    """x rounded to e4m3 under a per-tensor scale."""
    top = float(jnp.finfo(jnp.float8_e4m3fn).max)
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _einsum(spec, a, b, act):
    if act == "f32":
        return jnp.einsum(spec, a, b, precision=HI)
    if act == "fp8":
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(spec, a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                      preferred_element_type=jnp.float32)


def _rmsnorm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * g


_HEADS_AT_ONCE = 8      # [heads, S, S] scores of all 32 need not fit


def _attention(m, w, x, act):
    """x [S, d] normed -> [S, d]; causal, no positions."""
    S = x.shape[0]
    N, Nk, H = m["num_attention_heads"], m["num_key_value_heads"], \
        m["head_dim"]
    # departs: matrices are [in, out]
    q = _einsum("sd,de->se", x, w["attn.q"], act).reshape(S, N, H)
    k = _einsum("sd,de->se", x, w["attn.k"], act).reshape(S, Nk, H)
    v = _einsum("sd,de->se", x, w["attn.v"], act).reshape(S, Nk, H)
    # query head i reads K/V head i // (N // Nk)
    k, v = jnp.repeat(k, N // Nk, axis=1), jnp.repeat(v, N // Nk, axis=1)
    causal = jnp.tril(jnp.ones((S, S), bool))

    def some_heads(args):
        qq, kk, vv = args                       # [S, heads, H] each
        scores = _einsum("tnh,snh->nts", qq, kk, act) \
            * m["attention_multiplier"]
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        return _einsum("nts,snh->tnh", probs, vv, act)

    n = min(_HEADS_AT_ONCE, N)
    groups = lambda a: jnp.moveaxis(a.reshape(S, N // n, n, H), 1, 0)
    o = jax.lax.map(some_heads, (groups(q), groups(k), groups(v)))
    o = jnp.moveaxis(o, 0, 1).reshape(S, N * H)
    return _einsum("se,ed->sd", o, w["attn.o"], act)


def _mamba(m, w, x, act, state_prec, n_real):
    """x [S, d] normed -> ([S, d], the state [heads, head_dim, d_state]
    after position n_real - 1): the recurrence from an empty state."""
    S = x.shape[0]
    di, cd, nh = m["d_inner"], m["conv_dim"], m["mamba_n_heads"]
    P, N, taps = m["mamba_d_head"], m["mamba_d_state"], m["mamba_d_conv"]
    zxbcdt = _einsum("sd,de->se", x, w["mamba.in_proj"], act)
    z, xbc, dt = zxbcdt[:, :di], zxbcdt[:, di:di + cd], zxbcdt[:, di + cd:]
    # causal depthwise convolution, zeros before the start
    # departs: taps are [taps, channels]
    padded = jnp.concatenate([jnp.zeros((taps - 1, cd)), xbc])
    xbc = jax.nn.silu(w["mamba.conv_b"] + sum(
        w["mamba.conv_w"][k] * padded[k:k + S] for k in range(taps)))
    xs = xbc[:, :di].reshape(S, nh, P)
    B, C = xbc[:, di:di + N], xbc[:, di + N:]
    dt = jax.nn.softplus(dt + w["mamba.dt_bias"])           # [S, nh]
    # departs: no step on the padding behind the sequence (decay 1,
    # nothing added), so that the scan ends on the state after the last
    # real token; no real position sees it
    dt = jnp.where(jnp.arange(S)[:, None] < n_real, dt, 0.0)
    A = -jnp.exp(w["mamba.A_log"])

    # departs: the recurrence itself, where the published module runs
    # the chunked form of the same sums
    def step(state, inp):
        x_t, b_t, c_t, dt_t = inp
        state = jnp.exp(dt_t * A)[:, None, None] * state \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[None, None, :]
        if state_prec == "bf16":
            state = _bf16(state)
        y = jnp.sum(state * c_t[None, None, :], axis=-1) \
            + w["mamba.D"][:, None] * x_t
        return state, y

    state, y = jax.lax.scan(step, jnp.zeros((nh, P, N)), (xs, B, C, dt))
    y = _rmsnorm(y.reshape(S, di) * jax.nn.silu(z), w["mamba.norm"],
                 m["rms_norm_eps"])
    return _einsum("se,ed->sd", y, w["mamba.out_proj"], act), state


class Layers:
    """The jitted block for one set of sizes `m` (`Layers.of(m)`: one
    instance a set of sizes, so that a second pass compiles nothing)."""
    _made = {}

    def __init__(self, m):
        self.m = m
        self._block = jax.jit(self._forward,
                              static_argnames=("kind", "act", "state"))

    @classmethod
    def of(cls, m):
        key = repr(sorted(m.items()))
        if key not in cls._made:
            cls._made[key] = cls(m)
        return cls._made[key]

    def _forward(self, w, h, n_real, kind, act, state):
        m = self.m
        eps, res = m["rms_norm_eps"], m["residual_multiplier"]
        x = _rmsnorm(h, w["norm1"], eps)
        if kind == "attention":
            mixed, kept = _attention(m, w, x, act), None
        else:
            mixed, kept = _mamba(m, w, x, act, state, n_real)
        h = h + res * mixed
        x = _rmsnorm(h, w["norm2"], eps)
        # departs: input_linear as its halves gate | up
        g = _einsum("sd,df->sf", x, w["mlp.gate"], act)
        y = _einsum("sf,fd->sd", jax.nn.silu(g)
                    * _einsum("sd,df->sf", x, w["mlp.up"], act),
                    w["mlp.down"], act)
        return h + res * y, kept

    def forward(self, w, h, layer, prec=REFERENCE, n_real=None):
        """One block on one sequence h [S, d], the first `n_real`
        positions of it real (all where None). Returns the block's
        output and what a Mamba-2 layer keeps of the sequence: the
        state after its last real token (None for an attention layer)."""
        return self._block(w, h, h.shape[0] if n_real is None else n_real,
                           kind=self.m["layer_types"][layer],
                           act=prec["act"], state=prec.get("state"))


def _logits(m, h, norm, embed, act):
    return _einsum("sd,vd->sv", _rmsnorm(h, norm, m["rms_norm_eps"]), embed,
                   act) / m["logits_scaling"]


@functools.partial(jax.jit, static_argnames=("eps", "scaling", "act"))
def _head(h, norm, embed, probes, eps, scaling, act):
    """h [count, d], probes [n, count] -> the best logit, its token,
    the probed tokens' logits [n, count]."""
    lg = _einsum("sd,vd->sv", _rmsnorm(h, norm, eps), embed, act) / scaling
    return (jnp.max(lg, axis=-1), jnp.argmax(lg, axis=-1).astype(jnp.int32),
            jnp.take_along_axis(lg, probes.T, axis=-1).T)


def embedded(m, embed, ids):
    return m["embedding_multiplier"] * embed[jnp.asarray(ids)]


def full_logits(m, seed, init, ids, prec=REFERENCE):
    """Logits [S, V] of one sequence (small sizes: the tests)."""
    layers = Layers.of(m)
    embed = outer_weights(m, seed, EMBED)
    h = embedded(m, embed, ids)
    for layer in range(m["num_hidden_layers"]):
        h, _ = layers.forward(layer_weights(m, seed, layer, init), h, layer,
                              prec)
    return _logits(m, h, outer_weights(m, seed, FINAL_NORM), embed,
                   prec["act"])


def _forward_all(m, seed, init, seqs, prec, length, log, keep_states):
    """The whole forward pass of each sequence of `seqs` (int arrays),
    padded to `length`: the final hidden rows [length, d] of each and,
    with `keep_states`, {layer: [the state after each sequence's last
    token]} over the Mamba-2 layers."""
    length = length or max(len(s) for s in seqs)
    # departs: padded to one length
    ids = [np.zeros((length,), np.int32) for _ in seqs]
    for row, s in zip(ids, seqs):
        row[:len(s)] = s
    embed = outer_weights(m, seed, EMBED)
    hs = [embedded(m, embed, row) for row in ids]
    layers, states = Layers.of(m), {}
    for layer in range(m["num_hidden_layers"]):
        w = layer_weights(m, seed, layer, init)
        kept = []
        for i, h in enumerate(hs):
            hs[i], state = layers.forward(w, h, layer, prec,
                                          n_real=len(seqs[i]))
            kept.append(state)
        jax.block_until_ready(hs)
        if keep_states and kept[0] is not None:
            states[layer] = [np.asarray(s) for s in kept]
        del w, kept
        if log:
            log(f"reference: layer {layer} done")
    return hs, embed, states


def position_logits(m, seed, init, seqs, spans, probes, prec=REFERENCE,
                    length=None, log=None):
    """At the positions `spans[i] = (first, count)` of sequence i:
    (the best logit, its token, the logits of each row of tokens in
    `probes[i]` [n, count])."""
    hs, embed, _ = _forward_all(m, seed, init, seqs, prec, length, log,
                                False)
    norm = outer_weights(m, seed, FINAL_NORM)
    out = []
    for h, (first, count), rows in zip(hs, spans, probes):
        out.append(tuple(np.asarray(x) for x in _head(
            h[first:first + count], norm, embed, jnp.asarray(np.stack(rows)),
            m["rms_norm_eps"], m["logits_scaling"], prec["act"])))
    return out


def final_states(m, seed, init, seqs, prec=REFERENCE, length=None,
                 log=None):
    """{layer: [state [heads, head_dim, d_state] after the last token
    of each sequence]} over the Mamba-2 layers: what a request that has
    taken in `seqs[i]` keeps there."""
    return _forward_all(m, seed, init, seqs, prec, length, log, True)[2]
