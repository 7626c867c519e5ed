"""Plain reference of K-EXAONE (`exaone_moe`,
https://huggingface.co/LGAI-EXAONE/K-EXAONE-236B-A23B), after the
family's published `modeling_exaone4.py` and, for the router, the form
its config keys name (DeepSeek-V3's gate): grouped-query attention with
an RMSNorm over each head of q and k, three sliding-window layers
(rotated, 128 keys with the query's own) to each full layer (no
positions), every norm on a sublayer's OUTPUT, a gated-MLP first layer
and expert layers after it (one shared expert plus the top 8 of 128 by
a sigmoid score and a correction bias), an untied head.

Straightforward `jax.numpy` in float32 with every contraction at
`Precision.HIGHEST`. No kernels, no cache, no ring (the window is a
mask, taken over the keys near a block of queries), no batching: one sequence at a time through one layer at a time,
the layer's weights drawn when it is reached and dropped after (an
expert layer is 3.0 GB in float32); attention a K/V head and a block of
queries at a time, so that 13k tokens fit. It imports nothing of the
program under test. Weights are drawn here, leaf by leaf, from the seed
(`draw`); the benchmark's driver draws the same leaves for the program
under the same names.

Departures from the published code, each marked `# departs:` below:
  * a matrix is stored [in, out] and applied as x @ W (published:
    [out, in], x @ W^T); with seeded weights this is a relabelling;
  * the model is one chip's SHARE of an eight-chip expert-parallel
    stage: the router scores all `router_experts`, the routed sum runs
    over the experts `held` here only, and embedding and head hold
    `vocab_size` rows of the published vocabulary. Nothing stands in
    for the other chips' part of the sum;
  * sequences are padded to one of a few lengths so that few compiled
    programs serve them all; causal attention never lets a position see
    the padding behind it;
  * the routed experts are a loop over the held experts with a 0/1 mask
    (published: a gather of each expert's tokens); the sum is the same;
  * the multi-token-prediction module is not built: it is no part of
    the forward pass.

A `prec` argument selects the arithmetic, so that the same code is the
low-precision control of the `correct` comparison:

  act     "f32" (reference) | "bf16" | "fp8": operands of every
          contraction (fp8: e4m3 under a per-tensor scale); the
          router's scores stay float32, as the configuration states
"""
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
REFERENCE = {"act": "f32"}


# ---------------------------------------------------------------------------
# seeded weights, one leaf at a time
# ---------------------------------------------------------------------------

EMBED, FINAL_NORM, HEAD = 1, 2, 3       # tags of the leaves outside layers
_LAYER_TAG = 100                        # layer l is tagged 100 + l
_EXPERT_LEAF = 1000                     # expert e's leaves: 1000 + 3e + 0..2

# The router's matrix is drawn at this factor of `initializer_range`
# (`assumed.router_draw`): its input is the residual stream itself, with
# no norm before it, whose rms grows to ~4 by the eighth layer; at the
# full std the sigmoid saturates and the float32 scores of the top
# experts lie 1e-6 apart. At 0.15 the scores' logits have a std of
# 0.4-0.9 over the layers held here.
ROUTER_SCALE = 0.15
# The std of the correction bias (`assumed.correction_bias_draw`): the
# eighth and ninth score lie ~0.005 apart, and a bias of this std
# changes the chosen set for 10-16% of the tokens.
BIAS_STD = 0.002


def sizes(config):
    """The sizes this code runs, from a configuration file: its keys
    (the share as run) with the router's width and the held experts of
    its `deployment`, the layers held here (the first `num_layers` of
    the published pattern) and the rotary base."""
    dep = config["deployment"]
    n = config["num_layers"]
    return dict(config, router_experts=dep["router_experts"],
                held_experts=tuple(dep["held_experts"]),
                layer_types=tuple(config["layer_types"][:n]),
                mlp_layer_types=tuple(config["mlp_layer_types"][:n]),
                rope_theta=float(config["rope_parameters"]["rope_theta"]))


def layer_leaves(m, layer):
    """(name, shape, kind) of a layer's leaves but its routed experts,
    in the order their keys are folded; the program holds them under
    `blocks.<layer>.<name>`. kind: "w" a block matrix, "g" a gain, "r"
    the router's matrix, "bias" its correction bias."""
    d, H = m["hidden_size"], m["head_dim"]
    q, kv = m["num_attention_heads"] * H, m["num_key_value_heads"] * H
    leaves = [("attn.q", (d, q), "w"), ("attn.k", (d, kv), "w"),
              ("attn.v", (d, kv), "w"), ("attn.q_norm", (H,), "g"),
              ("attn.k_norm", (H,), "g"), ("attn.o", (q, d), "w"),
              ("attn_norm", (d,), "g")]
    if m["mlp_layer_types"][layer] == "dense":
        f = m["intermediate_size"]
        leaves += [("mlp.gate", (d, f), "w"), ("mlp.up", (d, f), "w"),
                   ("mlp.down", (f, d), "w")]
    else:
        f = m["moe_intermediate_size"] * m["num_shared_experts"]
        leaves += [("moe.router", (d, m["router_experts"]), "r"),
                   ("moe.bias", (m["router_experts"],), "bias"),
                   ("moe.shared.gate", (d, f), "w"),
                   ("moe.shared.up", (d, f), "w"),
                   ("moe.shared.down", (f, d), "w")]
    return leaves + [("ffn_norm", (d,), "g")]


def expert_leaves(m):
    d, f = m["hidden_size"], m["moe_intermediate_size"]
    return [("gate", (d, f), "w"), ("up", (d, f), "w"), ("down", (f, d), "w")]


def _bf16(x):
    """float32 `x` rounded to the numbers bfloat16 holds, still float32.
    Not `x.astype(bfloat16).astype(float32)`: on the TPU XLA drops such
    a pair of conversions inside one program (PERF.md section 2)."""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


@functools.partial(jax.jit, static_argnames=("shape", "kind", "dtype"))
def _draw(key, tag, index, std, shape, kind, dtype):
    key = jax.random.fold_in(jax.random.fold_in(key, tag), index)
    x = std * jax.random.normal(key, shape, jnp.float32)
    if kind == "g":
        x = 1.0 + x
    return _bf16(x).astype(dtype)


def draw(seed, tag, index, shape, kind, std, dtype=jnp.float32):
    """One leaf from the seed: N(0, std) for a matrix or a bias,
    1 + N(0, std) for a gain, rounded to bfloat16 (the stated parameter
    dtype) and given back in `dtype`. One compiled program a shape."""
    return _draw(jax.random.PRNGKey(int(seed)), tag, index, std,
                 tuple(shape), kind, jnp.dtype(dtype))


def stds(m, init=None):
    """{kind: std} of a leaf's draw. `init` is a cell's `init` (PERF.md
    section 2): `block_scale`, the factor on the blocks' matrices."""
    base = float(m.get("initializer_range", 0.02))
    return {"w": base * float((init or {}).get("block_scale", 1.0)),
            "g": base, "r": base * ROUTER_SCALE, "bias": BIAS_STD}


def layer_leaf(m, seed, layer, j, init=None, dtype=jnp.float32):
    """Leaf `j` of `layer_leaves(m, layer)`."""
    _, shape, kind = layer_leaves(m, layer)[j]
    return draw(seed, _LAYER_TAG + layer, j, shape, kind,
                stds(m, init)[kind], dtype)


def expert_leaf(m, seed, layer, expert, j, init=None, dtype=jnp.float32):
    """Leaf `j` of `expert_leaves(m)` of routed expert `expert`, counted
    in the whole model: a share's experts are those the whole model
    would have."""
    _, shape, kind = expert_leaves(m)[j]
    return draw(seed, _LAYER_TAG + layer, _EXPERT_LEAF + 3 * expert + j,
                shape, kind, stds(m, init)[kind], dtype)


def layer_weights(m, seed, layer, init=None, dtype=jnp.float32):
    """{name: array} of one layer; the routed experts held here are
    `moe.experts.<e>.<gate|up|down>`."""
    out = {name: layer_leaf(m, seed, layer, j, init, dtype)
           for j, (name, _, _) in enumerate(layer_leaves(m, layer))}
    if m["mlp_layer_types"][layer] == "sparse":
        first, count = m["held_experts"]
        for e in range(first, first + count):
            for j, (name, _, _) in enumerate(expert_leaves(m)):
                out[f"moe.experts.{e}.{name}"] = expert_leaf(
                    m, seed, layer, e, j, init, dtype)
    return out


def outer_weights(m, seed, which, dtype=jnp.float32):
    """`which`: EMBED, FINAL_NORM or HEAD."""
    d, V = m["hidden_size"], m["vocab_size"]
    shape, kind = {EMBED: ((V, d), "w"), FINAL_NORM: ((d,), "g"),
                   HEAD: ((d, V), "w")}[which]
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


def rotary_tables(m, length):
    """cos, sin [length, head_dim], float32: each frequency twice, the
    halves side by side."""
    H = m["head_dim"]
    inv = m["rope_theta"] ** (-np.arange(0, H, 2, dtype=np.float64) / H)
    freqs = jnp.arange(length, dtype=jnp.float32)[:, None] \
        * jnp.asarray(inv, jnp.float32)[None, :]
    emb = jnp.concatenate([freqs, freqs], axis=-1)
    return jnp.cos(emb), jnp.sin(emb)


def _rotate(x, cos, sin):
    """apply_rotary_pos_emb of the published code (rotate-half)."""
    half = x.shape[-1] // 2
    return x * cos + jnp.concatenate([-x[..., half:], x[..., :half]],
                                     axis=-1) * sin


# ---------------------------------------------------------------------------
# one layer on one sequence
# ---------------------------------------------------------------------------

_QUERIES_AT_ONCE = 1024     # [group, queries, S] scores of 13k would not fit


def _attention(m, w, x, sliding, act):
    """x [S, d] -> [S, d]: no norm before it. Key j is visible to query
    i iff 0 <= i - j, and in a sliding layer i - j < sliding_window."""
    S = x.shape[0]
    N, Nk, H = m["num_attention_heads"], m["num_key_value_heads"], \
        m["head_dim"]
    eps = m["rms_norm_eps"]
    # departs: matrices are [in, out]
    q = _einsum("sd,de->se", x, w["attn.q"], act).reshape(S, N, H)
    k = _einsum("sd,de->se", x, w["attn.k"], act).reshape(S, Nk, H)
    v = _einsum("sd,de->se", x, w["attn.v"], act).reshape(S, Nk, H)
    q, k = _rmsnorm(q, w["attn.q_norm"], eps), \
        _rmsnorm(k, w["attn.k_norm"], eps)
    if sliding:     # a full layer has no positions
        cos, sin = rotary_tables(m, S)
        q, k = _rotate(q, cos[:, None], sin[:, None]), \
            _rotate(k, cos[:, None], sin[:, None])
    B = _QUERIES_AT_ONCE if S % _QUERIES_AT_ONCE == 0 else S
    # query head n reads K/V head n // (N // Nk): [Nk, blocks, B, G, H]
    qb = jnp.transpose(q.reshape(S // B, B, Nk, N // Nk, H), (2, 0, 1, 3, 4))
    # the keys a block of queries is held against: every key in a full
    # layer; in a sliding layer the block's own and the `back` before
    # them, which holds every key inside the window (the mask is the
    # same, over fewer keys that it would hide anyway)
    back = -(-(m["sliding_window"] - 1) // 8) * 8 if sliding else 0
    span = B + back if sliding else S

    def one_head(args):
        qs, kk, vv = args       # [blocks, B, G, H], [S, H], [S, H]
        if sliding:
            kk, vv = (jnp.concatenate([jnp.zeros((back, H)), a])
                      for a in (kk, vv))

        def one_block(a):
            qq, first = a
            start = first if sliding else 0     # in the padded keys
            kpos = start - back + jnp.arange(span)
            behind = (first + jnp.arange(B))[:, None] - kpos[None, :]
            seen = (behind >= 0) & (kpos >= 0)[None, :]
            if sliding:
                seen = seen & (behind < m["sliding_window"])
            ks = jax.lax.dynamic_slice(kk, (start, 0), (span, H))
            vs = jax.lax.dynamic_slice(vv, (start, 0), (span, H))
            # the group's heads as further query rows: [B * G, span]
            scores = _einsum("th,sh->ts", qq.reshape(-1, H), ks, act) \
                * H ** -0.5
            probs = jax.nn.softmax(jnp.where(
                jnp.repeat(seen, N // Nk, axis=0), scores, -jnp.inf), -1)
            return _einsum("ts,sh->th", probs, vs, act).reshape(qq.shape)
        return jax.lax.map(one_block, (qs, jnp.arange(S // B) * B))

    o = jax.lax.map(one_head, (qb, jnp.moveaxis(k, 1, 0),
                               jnp.moveaxis(v, 1, 0)))
    o = jnp.transpose(o, (1, 2, 0, 3, 4)).reshape(S, N * H)
    return _einsum("se,ed->sd", o, w["attn.o"], act)


def _gated(x, gate, up, down, act):
    g = _einsum("sd,df->sf", x, gate, act)
    return _einsum("sf,fd->sd", jax.nn.silu(g)
                   * _einsum("sd,df->sf", x, up, act), down, act)


def route(m, x, router, bias):
    """The gate the config's keys name (`scoring_func` sigmoid,
    `norm_topk_prob`, `routed_scaling_factor`, `n_group` = `topk_group`
    = 1: no group limit): (weights [S, k], experts [S, k], margin [S]).
    Scores in float32 whatever `act` is. The bias chooses and does not
    weigh.

    `margin` is not in the published code: by how much the choice that
    this share computes was decided, on score + bias: the eighth kept
    over the first left out, where one of the two is held here. A
    random router's eighth and ninth score lie closer than the rounding
    of the stated precision moves them; the comparison leaves a
    position out where they do (`drivers/serve_exaone.py`)."""
    k = m["num_experts_per_tok"]
    scores = jax.nn.sigmoid(jnp.einsum("sd,de->se", x, router,
                                       precision=HI))
    top, idx = jax.lax.top_k(scores + bias, k + 1)
    experts = idx[:, :k]
    weights = jnp.take_along_axis(scores, experts, axis=1)
    if m["norm_topk_prob"]:
        weights = weights / (weights.sum(axis=-1, keepdims=True) + 1e-20)
    first, count = m["held_experts"]
    here = (idx[:, k - 1:] >= first) & (idx[:, k - 1:] < first + count)
    margin = jnp.where(here.any(axis=-1), top[:, k - 1] - top[:, k],
                       jnp.inf)
    return weights * m["routed_scaling_factor"], experts, margin


class Layers:
    """The jitted pieces of a block for one set of sizes `m`
    (`Layers.of(m)`: one instance a set of sizes, so that a second pass
    compiles nothing)."""
    _made = {}

    def __init__(self, m):
        self.m = m
        self._first = jax.jit(self._attention_and_shared,
                              static_argnames=("sliding", "dense", "act"))
        self._expert = jax.jit(self._one_expert, static_argnames=("act",))
        self._last = jax.jit(self._normed_sum)

    @classmethod
    def of(cls, m):
        key = repr(sorted((k, repr(v)) for k, v in m.items()))
        if key not in cls._made:
            cls._made[key] = cls(m)
        return cls._made[key]

    def _attention_and_shared(self, w, h, sliding, dense, act):
        """h + norm(attention(h)), then the part of the feed-forward
        every token takes (the dense MLP, or the shared expert) and what
        the routed experts need: (h, y, weights, experts, margin)."""
        m = self.m
        eps = m["rms_norm_eps"]
        h = h + _rmsnorm(_attention(m, w, h, sliding, act), w["attn_norm"],
                         eps)
        if dense:
            return h, _gated(h, w["mlp.gate"], w["mlp.up"], w["mlp.down"],
                             act), None, None, None
        weights, experts, margin = route(m, h, w["moe.router"],
                                         w["moe.bias"])
        y = _gated(h, w["moe.shared.gate"], w["moe.shared.up"],
                   w["moe.shared.down"], act)
        return h, y, weights, experts, margin

    @staticmethod
    def _one_expert(x, gate, up, down, weight, act):
        return weight[:, None] * _gated(x, gate, up, down, act)

    def _normed_sum(self, h, y, g):
        return h + _rmsnorm(y, g, self.m["rms_norm_eps"])

    def forward(self, w, h, layer, prec=REFERENCE):
        """One block on one sequence h [S, d]: (h, the router's margin
        [S] at each position, None in a dense layer)."""
        m, act = self.m, prec["act"]
        dense = m["mlp_layer_types"][layer] == "dense"
        h, y, weights, experts, margin = self._first(
            {k: v for k, v in w.items() if ".experts." not in k}, h,
            sliding=m["layer_types"][layer] == "sliding_attention",
            dense=dense, act=act)
        if not dense:
            # departs: a loop over the experts held here with a mask;
            # the chosen experts that other chips hold are not in this
            # sum
            first, count = m["held_experts"]
            for e in range(first, first + count):
                weight = jnp.sum(jnp.where(experts == e, weights, 0.0),
                                 axis=-1)
                y = y + self._expert(
                    h, w[f"moe.experts.{e}.gate"], w[f"moe.experts.{e}.up"],
                    w[f"moe.experts.{e}.down"], weight, act=act)
        return self._last(h, y, w["ffn_norm"]), margin


@functools.partial(jax.jit, static_argnames=("eps", "act"))
def _head(h, norm, head, probes, eps, act):
    """h [count, d], probes [n, count] -> the best logit, its token,
    the probed tokens' logits [n, count]."""
    lg = _einsum("sd,dv->sv", _rmsnorm(h, norm, eps), head, act)
    return (jnp.max(lg, axis=-1), jnp.argmax(lg, axis=-1).astype(jnp.int32),
            jnp.take_along_axis(lg, probes.T, axis=-1).T)


def full_logits(m, seed, init, ids, prec=REFERENCE):
    """Logits [S, V] of one sequence (small sizes: the tests)."""
    layers = Layers.of(m)
    h = outer_weights(m, seed, EMBED)[jnp.asarray(ids)]
    for layer in range(m["num_layers"]):
        h, _ = layers.forward(layer_weights(m, seed, layer, init), h,
                              layer, prec)
    return _einsum("sd,dv->sv", _rmsnorm(
        h, outer_weights(m, seed, FINAL_NORM), m["rms_norm_eps"]),
        outer_weights(m, seed, HEAD), prec["act"])


def padded_length(n, length):
    """The length a sequence of `n` tokens is padded to: the first of
    2,048, 4,096, 8,192 and `length` that holds it."""
    return next((b for b in (2048, 4096, 8192) if n <= b < length), length)


def position_logits(m, seed, init, seqs, spans, probes, prec=REFERENCE,
                    length=None, log=None):
    """The whole forward pass of each sequence of `seqs` (int arrays),
    and at the positions `spans[i] = (first, count)` of sequence i:
    (the best logit, its token, the logits of each row of tokens in
    `probes[i]` [n, count], the smallest margin by which a router chose
    there over the expert layers)."""
    length = length or max(len(s) for s in seqs)
    # departs: padded to one of a few lengths
    ids = [np.zeros((padded_length(len(s), length),), np.int32)
           for s in seqs]
    for row, s in zip(ids, seqs):
        row[:len(s)] = s
    embed = outer_weights(m, seed, EMBED)
    hs = [embed[jnp.asarray(row)] for row in ids]
    del embed
    layers = Layers.of(m)
    margins = [jnp.full((len(row),), jnp.inf) for row in ids]
    t0 = time.perf_counter()
    for layer in range(m["num_layers"]):
        w = layer_weights(m, seed, layer, init)
        for i, h in enumerate(hs):
            hs[i], margin = layers.forward(w, h, layer, prec)
            if margin is not None:
                margins[i] = jnp.minimum(margins[i], margin)
        jax.block_until_ready(hs)
        del w
        if log:
            log(f"reference: layer {layer} done at "
                f"{time.perf_counter() - t0:.1f} s")
    norm = outer_weights(m, seed, FINAL_NORM)
    head = outer_weights(m, seed, HEAD)
    out = []
    for h, margin, (first, count), rows in zip(hs, margins, spans, probes):
        at = slice(first, first + count)
        # departs: the probed rows padded to a multiple of 256, so that
        # the head compiles for a few sizes and not for every answer's
        pad = -count % 256
        got = _head(jnp.pad(h[at], ((0, pad), (0, 0))), norm, head,
                    jnp.pad(jnp.asarray(np.stack(rows)), ((0, 0), (0, pad))),
                    m["rms_norm_eps"], prec["act"])
        out.append(tuple(np.asarray(x)[..., :count] for x in got)
                   + (np.asarray(margin[at]),))
    return out
