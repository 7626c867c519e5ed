"""Plain reference of DeepSeek-V2 (arXiv:2405.04434), after the published
`modeling_deepseek.py` of `deepseek-ai/DeepSeek-V2`: RMSNorm, multi-head
latent attention with a decoupled rotary key under YaRN scaling, a
gated-MLP first layer and expert layers (shared experts plus
group-limited top-k routed experts) after it, an untied head.

Straightforward `jax.numpy` in float32 with every contraction at
`Precision.HIGHEST`. Attention is NOT absorbed: keys and values a head
are formed from the compressed row by `kv_b`, as published. No kernels,
no cache, no batching: one sequence at a time through one layer at a
time, the layer's weights drawn when it is reached and dropped after
(an expert layer is 4.6 GB in float32). It imports nothing of the
program under test. Weights are drawn here, leaf by leaf, from the seed
(`draw`); the benchmark's driver draws the same leaves for the program.

Departures from the published code, each marked `# departs:` below:
  * a matrix is stored [in, out] and applied as x @ W (published:
    [out, in], x @ W^T); with seeded weights this is a relabelling;
  * the model is one chip's SHARE of a four-chip expert-parallel
    deployment: the router scores all `router_experts`, the routed sum
    runs over the experts `held` here only, and embedding and head hold
    `vocab_size` rows of the published vocabulary. Nothing stands in
    for the other chips' part of the sum;
  * sequences are padded to one length so that one compiled program
    serves them all; causal attention never lets a position see the
    padding behind it;
  * the routed experts are a loop over the held experts with a 0/1 mask
    (published: a gather of each expert's tokens); the sum is the same.

A `prec` argument selects the arithmetic, so that the same code is the
low-precision control of the `correct` comparison:

  act     "f32" (reference) | "bf16" | "fp8": operands of every
          contraction (fp8: e4m3 under a per-tensor scale); the
          router's scores stay float32, as the configuration states
  latent  None | "fp8": the cached row [c_kv | k_pe] rounded to e4m3 as
          an fp8 latent cache would hold it
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

EMBED, FINAL_NORM, HEAD = 1, 2, 3       # tags of the leaves outside layers
_LAYER_TAG = 100                        # layer l is tagged 100 + l
_EXPERT_LEAF = 1000                     # expert e's leaves: 1000 + 3e + 0..2


def attention_leaves(m):
    """(name, shape, kind) of a layer's attention and norms, in the
    order their keys are folded. kind: "w" a block matrix, "g" a gain."""
    d, H = m["hidden_size"], m["num_attention_heads"]
    qk = m["qk_nope_head_dim"] + m["qk_rope_head_dim"]
    rank, rope = m["kv_lora_rank"], m["qk_rope_head_dim"]
    return [("attn_norm", (d,), "g"),
            ("q_a", (d, m["q_lora_rank"]), "w"),
            ("q_a_norm", (m["q_lora_rank"],), "g"),
            ("q_b", (m["q_lora_rank"], H * qk), "w"),
            ("kv_a", (d, rank + rope), "w"),
            ("kv_a_norm", (rank,), "g"),
            ("kv_b", (rank, H * (m["qk_nope_head_dim"]
                                 + m["v_head_dim"])), "w"),
            ("o", (H * m["v_head_dim"], d), "w"),
            ("ffn_norm", (d,), "g")]


def ffn_leaves(m, layer):
    """The feed-forward leaves of a layer after the attention's: the
    dense gated MLP, or router and shared experts (the routed experts
    are drawn one by one, `expert_leaves`)."""
    d = m["hidden_size"]
    if layer < m["first_k_dense_replace"]:
        f = m["intermediate_size"]
        return [("mlp.gate", (d, f), "w"), ("mlp.up", (d, f), "w"),
                ("mlp.down", (f, d), "w")]
    f = m["moe_intermediate_size"] * m["n_shared_experts"]
    return [("moe.router", (d, m["router_experts"]), "w"),
            ("moe.shared.gate", (d, f), "w"), ("moe.shared.up", (d, f), "w"),
            ("moe.shared.down", (f, d), "w")]


def expert_leaves(m):
    d, f = m["hidden_size"], m["moe_intermediate_size"]
    return [("gate", (d, f), "w"), ("up", (d, f), "w"), ("down", (f, d), "w")]


@functools.partial(jax.jit, static_argnames=("shape", "kind", "dtype"))
def _draw(key, tag, index, std, shape, kind, dtype):
    key = jax.random.fold_in(jax.random.fold_in(key, tag), index)
    x = std * jax.random.normal(key, shape, jnp.float32)
    if kind == "g":
        x = 1.0 + x
    return x.astype(jnp.bfloat16).astype(dtype)


def draw(seed, tag, index, shape, kind, std, dtype=jnp.float32):
    """One leaf from the seed: N(0, std) for a matrix, 1 + N(0, std)
    for a gain, rounded to bfloat16 (the stated parameter dtype) and
    given back in `dtype`. One compiled program a shape."""
    return _draw(jax.random.PRNGKey(int(seed)), tag, index, std,
                 tuple(shape), kind, jnp.dtype(dtype))


def stds(m, init=None):
    """{kind: std} of a leaf's draw. "w" a block matrix, "g" gains,
    embedding and head. `init` is a cell's `init` (PERF.md section 2):
    `block_scale`, the factor on the blocks' matrices at which a random
    model's attention has something to attend to."""
    base = float(m.get("initializer_range", 0.02))
    return {"w": base * float((init or {}).get("block_scale", 1.0)),
            "g": base}


def layer_weights(m, seed, layer, init=None, dtype=jnp.float32):
    """{name: array} of one layer. The routed experts held here are
    `moe.experts.<e>.<gate|up|down>` under their index in the whole
    model, so a share's experts are those the whole model would have."""
    std = stds(m, init)
    tag, out = _LAYER_TAG + layer, {}
    for j, (name, shape, kind) in enumerate(
            attention_leaves(m) + ffn_leaves(m, layer)):
        out[name] = draw(seed, tag, j, shape, kind, std[kind], dtype)
    if layer >= m["first_k_dense_replace"]:
        first, count = m["held_experts"]
        for e in range(first, first + count):
            for j, (name, shape, kind) in enumerate(expert_leaves(m)):
                out[f"moe.experts.{e}.{name}"] = draw(
                    seed, tag, _EXPERT_LEAF + 3 * e + j, shape, kind,
                    std[kind], dtype)
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


def _yarn_mscale(scale, mscale):
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def yarn_inv_freq(m):
    """Inverse frequencies of the rotary pairs under the configuration's
    `rope_scaling` (DeepseekV2YarnRotaryEmbedding)."""
    dim, base = m["qk_rope_head_dim"], float(m["rope_theta"])
    rs = m["rope_scaling"]
    factor, orig = rs["factor"], rs["original_max_position_embeddings"]
    pos_freqs = base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    extra, inter = 1.0 / pos_freqs, 1.0 / (factor * pos_freqs)

    def correction_dim(rotations):
        return dim * math.log(orig / (rotations * 2 * math.pi)) \
            / (2 * math.log(base))
    low = max(math.floor(correction_dim(rs["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rs["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / (high - low), 0, 1)
    mask = 1.0 - ramp
    return inter * (1 - mask) + extra * mask


def rotary_tables(m, length):
    """cos, sin [length, rope_dim], float32."""
    rs = m["rope_scaling"]
    t = jnp.arange(length, dtype=jnp.float32)
    freqs = t[:, None] * jnp.asarray(yarn_inv_freq(m), jnp.float32)[None, :]
    emb = jnp.concatenate([freqs, freqs], axis=-1)
    scale = _yarn_mscale(rs["factor"], rs["mscale"]) \
        / _yarn_mscale(rs["factor"], rs["mscale_all_dim"])
    return jnp.cos(emb) * scale, jnp.sin(emb) * scale


def _rotate(x, cos, sin):
    """apply_rotary_pos_emb of the published code: the pairs
    (x0, x1), (x2, x3), ... are first moved to (x0, x2, ... | x1, x3,
    ...), then rotate-half."""
    x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)
    half = x.shape[-1] // 2
    return x * cos + jnp.concatenate([-x[..., half:], x[..., :half]],
                                     axis=-1) * sin


def softmax_scale(m):
    rs = m["rope_scaling"]
    mscale = _yarn_mscale(rs["factor"], rs["mscale_all_dim"])
    return (m["qk_nope_head_dim"] + m["qk_rope_head_dim"]) ** -0.5 \
        * mscale * mscale


# ---------------------------------------------------------------------------
# one layer on one sequence
# ---------------------------------------------------------------------------

_HEADS_AT_ONCE = 4      # the [heads, S, S] scores of all 128 would not fit


def _attention(m, w, x, cos, sin, act, latent):
    """x [S, d] normed -> [S, d]; causal over the S positions."""
    S = x.shape[0]
    H, nope, rope = m["num_attention_heads"], m["qk_nope_head_dim"], \
        m["qk_rope_head_dim"]
    rank, vd, eps = m["kv_lora_rank"], m["v_head_dim"], m["rms_norm_eps"]
    # departs: matrices are [in, out]
    q = _einsum("sd,de->se", _rmsnorm(_einsum("sd,de->se", x, w["q_a"], act),
                                      w["q_a_norm"], eps), w["q_b"], act)
    q = q.reshape(S, H, nope + rope)
    q_nope, q_pe = q[..., :nope], _rotate(q[..., nope:], cos[:, None],
                                          sin[:, None])
    kv = _einsum("sd,de->se", x, w["kv_a"], act)
    c_kv = _rmsnorm(kv[:, :rank], w["kv_a_norm"], eps)
    k_pe = _rotate(kv[:, rank:], cos, sin)
    if latent == "fp8":
        c_kv, k_pe = _fp8(c_kv), _fp8(k_pe)
    kvb = _einsum("sc,ce->se", c_kv, w["kv_b"], act).reshape(S, H, nope + vd)
    k_nope, v = kvb[..., :nope], kvb[..., nope:]
    causal = jnp.tril(jnp.ones((S, S), bool))
    scale = softmax_scale(m)

    def some_heads(args):
        qn, qp, kn, vv = args               # [S, heads, .] each
        scores = (_einsum("thn,shn->hts", qn, kn, act)
                  + _einsum("thr,sr->hts", qp, k_pe, act)) * scale
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        return _einsum("hts,shv->thv", probs, vv, act)

    n = min(_HEADS_AT_ONCE, H)
    groups = lambda a: jnp.moveaxis(
        a.reshape(S, H // n, n, a.shape[-1]), 1, 0)
    o = jax.lax.map(some_heads, tuple(groups(a)
                                      for a in (q_nope, q_pe, k_nope, v)))
    o = jnp.moveaxis(o, 0, 1).reshape(S, H * vd)
    return _einsum("se,ed->sd", o, w["o"], act)


def _gated(x, gate, up, down, act):
    g = _einsum("sd,df->sf", x, gate, act)
    return _einsum("sf,fd->sd", jax.nn.silu(g)
                   * _einsum("sd,df->sf", x, up, act), down, act)


def route(m, x, router):
    """MoEGate with `group_limited_greedy`: (weights [S, k], experts
    [S, k], margin [S]). Scores in float32 whatever `act` is.

    `margin` is not in the published code: by how much the choice that
    this share computes was decided, in logarithms of the scores. It is
    the smaller of ln(last group kept / first group left out) and, where
    one of the two is held here, ln(last expert kept / first expert
    left out). A random router's sixth and seventh score lie closer
    than the rounding of the stated precision moves them; the
    comparison leaves a position out where they do
    (`drivers/serve_mla.py`)."""
    S, E, G = x.shape[0], m["router_experts"], m["n_group"]
    kg, k = m["topk_group"], m["num_experts_per_tok"]
    scores = jax.nn.softmax(jnp.einsum("sd,de->se", x, router,
                                       precision=HI), axis=-1)
    group_scores = scores.reshape(S, G, E // G).max(axis=-1)
    group_top, group_idx = jax.lax.top_k(group_scores, min(kg + 1, G))
    group_mask = jnp.zeros((S, G)).at[jnp.arange(S)[:, None],
                                      group_idx[:, :kg]].set(1.0)
    score_mask = jnp.repeat(group_mask, E // G, axis=1) > 0
    top, idx = jax.lax.top_k(jnp.where(score_mask, scores, 0.0), k + 1)
    weights, experts = top[:, :k], idx[:, :k]
    ln = lambda a: jnp.log(jnp.maximum(a, 1e-30))
    first, count = m["held_experts"]
    here = (idx[:, k - 1:] >= first) & (idx[:, k - 1:] < first + count)
    margin = jnp.where(here.any(axis=-1), ln(top[:, k - 1]) - ln(top[:, k]),
                       jnp.inf)
    if kg < G:
        margin = jnp.minimum(margin, ln(group_top[:, kg - 1])
                             - ln(group_top[:, kg]))
    # norm_topk_prob is false: the weights are not renormalised
    return weights * m["routed_scaling_factor"], experts, margin


class Layers:
    """The jitted pieces of a block for one set of sizes `m`."""

    def __init__(self, m):
        self.m = m
        self._first = jax.jit(self._attention_and_shared,
                              static_argnames=("dense", "act", "latent"))
        self._expert = jax.jit(self._one_expert, static_argnames=("act",))

    def _attention_and_shared(self, w, h, dense, act, latent):
        """h + attention, then the part of the feed-forward every token
        takes (the dense MLP, or the shared experts) and what the
        routed experts need: (h, y, x_ffn, weights, experts, margin)."""
        m = self.m
        cos, sin = rotary_tables(m, h.shape[0])
        eps = m["rms_norm_eps"]
        h = h + _attention(m, w, _rmsnorm(h, w["attn_norm"], eps), cos, sin,
                           act, latent)
        x = _rmsnorm(h, w["ffn_norm"], eps)
        if dense:
            return h, _gated(x, w["mlp.gate"], w["mlp.up"], w["mlp.down"],
                             act), x, None, None, None
        weights, experts, margin = route(m, x, w["moe.router"])
        y = _gated(x, w["moe.shared.gate"], w["moe.shared.up"],
                   w["moe.shared.down"], act)
        return h, y, x, weights, experts, margin

    @staticmethod
    def _one_expert(x, gate, up, down, weight, act):
        return weight[:, None] * _gated(x, gate, up, down, act)

    def forward(self, w, h, layer, prec=REFERENCE):
        """One block on one sequence h [S, d]: (h, the router's margin
        [S] at each position, None in a dense layer)."""
        m, act = self.m, prec["act"]
        dense = layer < m["first_k_dense_replace"]
        h, y, x, weights, experts, margin = self._first(
            {k: v for k, v in w.items() if ".experts." not in k}, h,
            dense=dense, act=act, latent=prec.get("latent"))
        if not dense:
            # departs: a loop over the experts held here with a mask;
            # the chosen experts that other chips hold are not in this
            # sum
            first, count = m["held_experts"]
            for e in range(first, first + count):
                weight = jnp.sum(jnp.where(experts == e, weights, 0.0),
                                 axis=-1)
                y = y + self._expert(
                    x, w[f"moe.experts.{e}.gate"], w[f"moe.experts.{e}.up"],
                    w[f"moe.experts.{e}.down"], weight, act=act)
        return h + y, margin


@functools.partial(jax.jit, static_argnames=("eps", "act"))
def _head(h, norm, head, probes, eps, act):
    """h [count, d], probes [n, count] -> the best logit, its token,
    the probed tokens' logits [n, count]."""
    lg = _einsum("sd,dv->sv", _rmsnorm(h, norm, eps), head, act)
    return (jnp.max(lg, axis=-1), jnp.argmax(lg, axis=-1).astype(jnp.int32),
            jnp.take_along_axis(lg, probes.T, axis=-1).T)


def sizes(config):
    """The sizes this code runs, from a configuration file: its keys
    (the share as run) with the router's width and the held experts of
    its `deployment`."""
    dep = config["deployment"]
    return dict(config, router_experts=dep["router_experts"],
                held_experts=tuple(dep["held_experts"]))


def full_logits(m, seed, init, ids, prec=REFERENCE):
    """Logits [S, V] of one sequence (small sizes: the tests)."""
    layers = Layers(m)
    h = outer_weights(m, seed, EMBED)[jnp.asarray(ids)]
    for layer in range(m["num_layers"]):
        h, _ = layers.forward(layer_weights(m, seed, layer, init), h,
                              layer, prec)
    return _einsum("sd,dv->sv", _rmsnorm(
        h, outer_weights(m, seed, FINAL_NORM), m["rms_norm_eps"]),
        outer_weights(m, seed, HEAD), prec["act"])


def position_logits(m, seed, init, seqs, spans, probes, prec=REFERENCE,
                    length=None, log=None):
    """The whole forward pass of each sequence of `seqs` (int arrays),
    and at the positions `spans[i] = (first, count)` of sequence i:
    (the best logit, its token, the logits of each row of tokens in
    `probes[i]` [n, count], the smallest margin by which a router chose
    there over the expert layers). Sequences are padded to `length`."""
    length = length or max(len(s) for s in seqs)
    # departs: padded to one length
    ids = [np.zeros((length,), np.int32) for _ in seqs]
    for row, s in zip(ids, seqs):
        row[:len(s)] = s
    embed = outer_weights(m, seed, EMBED)
    hs = [embed[jnp.asarray(row)] for row in ids]
    del embed
    layers = Layers(m)
    margins = [jnp.full((length,), jnp.inf) for _ in seqs]
    for layer in range(m["num_layers"]):
        w = layer_weights(m, seed, layer, init)
        for i, h in enumerate(hs):
            hs[i], margin = layers.forward(w, h, layer, prec)
            if margin is not None:
                margins[i] = jnp.minimum(margins[i], margin)
        jax.block_until_ready(hs)
        del w
        if log:
            log(f"reference: layer {layer} done")
    norm = outer_weights(m, seed, FINAL_NORM)
    head = outer_weights(m, seed, HEAD)
    out = []
    for h, margin, (first, count), rows in zip(hs, margins, spans, probes):
        at = slice(first, first + count)
        out.append(tuple(np.asarray(x) for x in _head(
            h[at], norm, head, jnp.asarray(np.stack(rows)),
            m["rms_norm_eps"], prec["act"])) + (np.asarray(margin[at]),))
    return out
