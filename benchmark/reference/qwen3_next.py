"""Plain reference of Qwen3-Next (`qwen3_next`,
https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct), after the
published `modeling_qwen3_next.py`: zero-centred RMSNorm (gain 1 + w)
everywhere but the linear layers' gated norm; three gated delta-rule
linear-attention layers (Yang, Kautz & Hatamizadeh 2024,
arXiv:2412.06464) to each gated full-attention layer (QK-norm, rotary
over the first quarter of each head, an output gate); an expert layer in
every block (a softmax top-10 of 512 renormalised, and a shared expert
scaled by a sigmoid gate); an untied head.

Straightforward `jax.numpy` in float32 with every contraction at
`Precision.HIGHEST`. The delta-rule layer is the RECURRENCE ITSELF, one
token at a time (`lax.scan`): no chunked form, no kernels, no cache.
Full attention a K/V head and a block of 1,024 queries at a time. One
sequence at a time through one layer at a time, the layer's weights
drawn when it is reached and dropped after. It imports nothing of the
program under test. Weights are drawn here, leaf by leaf, from the seed
(`draw`); the benchmark's driver draws the same leaves for the program
under the same names.

Departures from the published code, each marked `# departs:` below:
  * a matrix is stored [in, out] and applied as x @ W (published:
    [out, in], x @ W^T), the convolution's taps as [taps, channels]
    (published: [channels, 1, taps]); with seeded weights these are
    relabellings;
  * the model is one chip's SHARE of an eight-chip expert-parallel
    stage: the router scores all `router_experts`, the routed sum runs
    over the experts `held` here only, and embedding and head hold
    `vocab_size` rows of the published vocabulary. Nothing stands in
    for the other chips' part of the sum;
  * the published module computes the delta rule in the chunked form
    (or by fused kernels); the recurrence here is what both compute;
  * sequences are padded to one of a few lengths so that few compiled
    programs serve them all; attention is causal and the recurrence
    takes no step on the padding, so no real position sees it;
  * the routed experts are a loop over the held experts with a 0/1 mask
    (published: a gather of each expert's tokens); the sum is the same;
  * the routing weights stay float32 (published: cast to the
    activations' dtype before the sum);
  * the multi-token-prediction module is not built: it is no part of
    the forward pass.

A `prec` argument selects the arithmetic, so that the same code is the
low-precision control of the `correct` comparison:

  act     "f32" (reference) | "bf16" | "fp8": operands of every
          contraction (fp8: e4m3 under a per-tensor scale); the
          router's scores stay float32, as the configuration states
  state   None | "bf16": the delta-rule state rounded to bfloat16 after
          every token, as a bfloat16 state arena would hold it
"""
import functools
import math
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


def sizes(config):
    """The sizes this code runs, from a configuration file: its keys
    (the share as run) with the router's width and the held experts of
    its `deployment`, the layers held here (the first `num_layers` of
    the published pattern: layer i is full where (i + 1) is a multiple
    of `full_attention_interval`) and the rotary width."""
    dep = config["deployment"]
    every = config["full_attention_interval"]
    return dict(config, router_experts=dep["router_experts"],
                held_experts=tuple(dep["held_experts"]),
                layer_types=tuple(
                    "full_attention" if (i + 1) % every == 0
                    else "linear_attention"
                    for i in range(config["num_layers"])),
                rotary_dim=int(config["head_dim"]
                               * config["partial_rotary_factor"]))


def layer_leaves(m, layer):
    """(name, shape, kind) of a layer's leaves but its routed experts,
    in the order their keys are folded; the program holds them under
    `blocks.<layer>.<name>`. kind: "w" a block matrix, "g" a gain, "zc"
    a zero-centred gain (the norm scales by 1 + it), "r" the router's
    matrix, "conv" the convolution's taps, "A_log", "dt_bias"."""
    d = m["hidden_size"]
    if m["layer_types"][layer] == "linear_attention":
        Hk, Hv = m["linear_num_key_heads"], m["linear_num_value_heads"]
        K, V = m["linear_key_head_dim"], m["linear_value_head_dim"]
        conv = 2 * Hk * K + Hv * V
        mixer = [("linear.in_proj_qkvz", (d, 2 * Hk * K + 2 * Hv * V), "w"),
                 ("linear.in_proj_ba", (d, 2 * Hv), "w"),
                 ("linear.conv_w", (m["linear_conv_kernel_dim"], conv),
                  "conv"),
                 ("linear.dt_bias", (Hv,), "dt_bias"),
                 ("linear.A_log", (Hv,), "A_log"),
                 ("linear.norm", (V,), "g"),
                 ("linear.out_proj", (Hv * V, d), "w")]
    else:
        N, Nk, H = m["num_attention_heads"], m["num_key_value_heads"], \
            m["head_dim"]
        mixer = [("attn.q", (d, N * 2 * H), "w"), ("attn.k", (d, Nk * H), "w"),
                 ("attn.v", (d, Nk * H), "w"), ("attn.q_norm", (H,), "zc"),
                 ("attn.k_norm", (H,), "zc"), ("attn.o", (N * H, d), "w")]
    f = m["shared_expert_intermediate_size"]
    return [("norm1", (d,), "zc")] + mixer + [
        ("norm2", (d,), "zc"),
        ("moe.router", (d, m["router_experts"]), "r"),
        ("moe.shared.gate", (d, f), "w"), ("moe.shared.up", (d, f), "w"),
        ("moe.shared.down", (f, d), "w"), ("moe.shared_gate", (d, 1), "w")]


def expert_leaves(m):
    d, f = m["hidden_size"], m["moe_intermediate_size"]
    return [("gate", (d, f), "w"), ("up", (d, f), "w"), ("down", (f, d), "w")]


def _bf16(x):
    """float32 `x` rounded to the numbers bfloat16 holds, still float32.
    Not `x.astype(bfloat16).astype(float32)`: on the TPU XLA drops such
    a pair of conversions inside one program (PERF.md section 2)."""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


@functools.partial(jax.jit, static_argnames=("shape", "kind", "dtype",
                                             "taps"))
def _draw(key, tag, index, std, shape, kind, dtype, taps):
    key = jax.random.fold_in(jax.random.fold_in(key, tag), index)
    if kind in ("w", "g", "zc", "r"):
        x = std * jax.random.normal(key, shape, jnp.float32)
        if kind == "g":
            x = 1.0 + x
    else:
        u = 1.0 - jax.random.uniform(key, shape, jnp.float32)   # (0, 1]
        if kind == "A_log":             # A uniform in (0, 16]
            x = jnp.log(16.0 * u)
        elif kind == "dt_bias":         # dt log-uniform in [0.001, 0.1]
            dt = jnp.exp(u * (math.log(0.1) - math.log(0.001))
                         + math.log(0.001))
            x = dt + jnp.log(-jnp.expm1(-dt))       # inverse softplus
        else:                           # "conv": +-1/sqrt(taps)
            x = (2.0 * u - 1.0) / math.sqrt(taps)
    return _bf16(x).astype(dtype)


def draw(seed, tag, index, shape, kind, std, dtype=jnp.float32, taps=4):
    """One leaf from the seed, rounded to bfloat16 (the stated parameter
    dtype) and given back in `dtype`: N(0, std) for a matrix, 1 + N(0,
    std) for a gain, N(0, std) for a zero-centred gain, and the linear
    layers' leaves as `assumed` in the configuration file says. One
    compiled program a shape."""
    return _draw(jax.random.PRNGKey(int(seed)), tag, index, std,
                 tuple(shape), kind, jnp.dtype(dtype), int(taps))


# The router's matrix is drawn at this factor of `initializer_range`
# (`assumed.router_draw`: 1.0, the published std).
ROUTER_SCALE = 1.0


def stds(m, init=None):
    """{kind: std} of a leaf's draw. `init` is a cell's `init` (PERF.md
    section 2): `block_scale`, the factor on the blocks' matrices."""
    base = float(m["initializer_range"])
    return {"w": base * float((init or {}).get("block_scale", 1.0)),
            "g": base, "zc": base, "r": base * ROUTER_SCALE}


def layer_leaf(m, seed, layer, j, init=None, dtype=jnp.float32):
    """Leaf `j` of `layer_leaves(m, layer)`."""
    _, shape, kind = layer_leaves(m, layer)[j]
    return draw(seed, _LAYER_TAG + layer, j, shape, kind,
                stds(m, init).get(kind, 0.0), dtype,
                m["linear_conv_kernel_dim"])


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
    first, count = m["held_experts"]
    for e in range(first, first + count):
        for j, (name, _, _) in enumerate(expert_leaves(m)):
            out[f"moe.experts.{e}.{name}"] = expert_leaf(
                m, seed, layer, e, j, init, dtype)
    return out


def outer_weights(m, seed, which, dtype=jnp.float32):
    """`which`: EMBED, FINAL_NORM (zero-centred) or HEAD."""
    d, V = m["hidden_size"], m["vocab_size"]
    shape, kind = {EMBED: ((V, d), "w"), FINAL_NORM: ((d,), "zc"),
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


def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _zc_norm(x, w, eps):
    """Qwen3NextRMSNorm: the gain is 1 + w."""
    return _rms(x, eps) * (1.0 + w)


def rotary_tables(m, length):
    """cos, sin [length, rotary_dim], float32: each frequency twice, the
    halves side by side, over the rotated dimensions alone."""
    r = m["rotary_dim"]
    inv = m["rope_theta"] ** (-np.arange(0, r, 2, dtype=np.float64) / r)
    freqs = jnp.arange(length, dtype=jnp.float32)[:, None] \
        * jnp.asarray(inv, jnp.float32)[None, :]
    emb = jnp.concatenate([freqs, freqs], axis=-1)
    return jnp.cos(emb), jnp.sin(emb)


def _rotate(x, cos, sin):
    """apply_rotary_pos_emb of the published code: rotate-half over the
    first cos.shape[-1] dimensions, the rest passed through."""
    r = cos.shape[-1]
    rot, rest = x[..., :r], x[..., r:]
    half = r // 2
    rot = rot * cos + jnp.concatenate([-rot[..., half:], rot[..., :half]],
                                      axis=-1) * sin
    return jnp.concatenate([rot, rest], axis=-1)


# ---------------------------------------------------------------------------
# the mixers on one sequence
# ---------------------------------------------------------------------------

_QUERIES_AT_ONCE = 1024     # [group, queries, S] scores of 20k would not fit


def _attention(m, w, x, act):
    """x [S, d] normed -> [S, d]: causal, gated."""
    S = x.shape[0]
    N, Nk, H = m["num_attention_heads"], m["num_key_value_heads"], \
        m["head_dim"]
    eps = m["rms_norm_eps"]
    # departs: matrices are [in, out]
    qg = _einsum("sd,de->se", x, w["attn.q"], act).reshape(S, N, 2 * H)
    q, gate = qg[..., :H], qg[..., H:].reshape(S, N * H)
    k = _einsum("sd,de->se", x, w["attn.k"], act).reshape(S, Nk, H)
    v = _einsum("sd,de->se", x, w["attn.v"], act).reshape(S, Nk, H)
    q, k = _zc_norm(q, w["attn.q_norm"], eps), _zc_norm(k, w["attn.k_norm"],
                                                        eps)
    cos, sin = rotary_tables(m, S)
    q, k = _rotate(q, cos[:, None], sin[:, None]), \
        _rotate(k, cos[:, None], sin[:, None])
    B = _QUERIES_AT_ONCE if S % _QUERIES_AT_ONCE == 0 else S
    # query head n reads K/V head n // (N // Nk): [Nk, blocks, B, G, H]
    qb = jnp.transpose(q.reshape(S // B, B, Nk, N // Nk, H), (2, 0, 1, 3, 4))

    def one_head(args):
        qs, kk, vv = args       # [blocks, B, G, H], [S, H], [S, H]

        def one_block(a):
            qq, first = a
            seen = (first + jnp.arange(B))[:, None] >= jnp.arange(S)[None, :]
            # the group's heads as further query rows: [B * G, S]
            scores = _einsum("th,sh->ts", qq.reshape(-1, H), kk, act) \
                * H ** -0.5
            probs = jax.nn.softmax(jnp.where(
                jnp.repeat(seen, N // Nk, axis=0), scores, -jnp.inf), -1)
            return _einsum("ts,sh->th", probs, vv, act).reshape(qq.shape)
        return jax.lax.map(one_block, (qs, jnp.arange(S // B) * B))

    o = jax.lax.map(one_head, (qb, jnp.moveaxis(k, 1, 0),
                               jnp.moveaxis(v, 1, 0)))
    o = jnp.transpose(o, (1, 2, 0, 3, 4)).reshape(S, N * H)
    return _einsum("se,ed->sd", o * jax.nn.sigmoid(gate), w["attn.o"], act)


def _l2(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def _linear(m, w, x, act, state_prec, n_real):
    """x [S, d] normed -> ([S, d], the states [heads, K, V] after
    position n_real - 1): the recurrence from an empty state."""
    S = x.shape[0]
    Hk, Hv = m["linear_num_key_heads"], m["linear_num_value_heads"]
    K, V = m["linear_key_head_dim"], m["linear_value_head_dim"]
    taps, r, kd = m["linear_conv_kernel_dim"], Hv // Hk, Hk * K
    # a key head's columns: [q K | k K | v r*V | z r*V]; [b r | a r]
    qkvz = _einsum("sd,de->se", x, w["linear.in_proj_qkvz"], act) \
        .reshape(S, Hk, -1)
    ba = _einsum("sd,de->se", x, w["linear.in_proj_ba"], act) \
        .reshape(S, Hk, 2 * r)
    z = qkvz[..., 2 * K + r * V:].reshape(S, Hv, V)
    b, a = ba[..., :r].reshape(S, Hv), ba[..., r:].reshape(S, Hv)
    mixed = jnp.concatenate([qkvz[..., :K].reshape(S, -1),
                             qkvz[..., K:2 * K].reshape(S, -1),
                             qkvz[..., 2 * K:2 * K + r * V].reshape(S, -1)],
                            axis=-1)
    # causal depthwise convolution, zeros before the start, no bias
    # departs: taps are [taps, channels]
    padded = jnp.concatenate([jnp.zeros((taps - 1, mixed.shape[1])), mixed])
    conv = jax.nn.silu(sum(w["linear.conv_w"][i] * padded[i:i + S]
                           for i in range(taps)))
    q = _l2(conv[:, :kd].reshape(S, Hk, K)) * K ** -0.5
    k = _l2(conv[:, kd:2 * kd].reshape(S, Hk, K))
    v = conv[:, 2 * kd:].reshape(S, Hv, V)
    # value head j reads key head j // r (repeat_interleave)
    q, k = jnp.repeat(q, r, axis=1), jnp.repeat(k, r, axis=1)
    beta = jax.nn.sigmoid(b)
    g = -jnp.exp(w["linear.A_log"]) * jax.nn.softplus(a + w["linear.dt_bias"])
    # departs: no step on the padding behind the sequence (no decay,
    # nothing written), so that the scan ends on the state after the
    # last real token; no real position sees it
    real = (jnp.arange(S) < n_real)[:, None]
    g, beta = jnp.where(real, g, 0.0), jnp.where(real, beta, 0.0)

    # departs: the recurrence itself, where the published module runs
    # the chunked form of the same sums
    def step(state, inp):
        q_t, k_t, v_t, g_t, b_t = inp                   # [H, K|V], [H]
        state = jnp.exp(g_t)[:, None, None] * state
        u = jnp.sum(state * k_t[:, :, None], axis=1)    # S^T k: [H, V]
        state = state + k_t[:, :, None] * (b_t[:, None] * (v_t - u))[:, None]
        if state_prec == "bf16":
            state = _bf16(state)
        return state, jnp.sum(state * q_t[:, :, None], axis=1)

    state, o = jax.lax.scan(step, jnp.zeros((Hv, K, V)), (q, k, v, g, beta))
    # Qwen3NextRMSNormGated: the norm first, then the gate
    y = _rms(o, m["rms_norm_eps"]) * w["linear.norm"] * jax.nn.silu(z)
    return _einsum("se,ed->sd", y.reshape(S, Hv * V), w["linear.out_proj"],
                   act), state


def _gated(x, gate, up, down, act):
    g = _einsum("sd,df->sf", x, gate, act)
    return _einsum("sf,fd->sd", jax.nn.silu(g)
                   * _einsum("sd,df->sf", x, up, act), down, act)


def route(m, x, router):
    """The softmax top-k of the published block (`norm_topk_prob`):
    (weights [S, k], experts [S, k], margin [S]). Scores in float32
    whatever `act` is.

    `margin` is not in the published code: by how much the choice that
    this share computes was decided, on the router's logits (the log of
    the softmax, up to a constant): the k-th kept over the first left
    out, where one of the two is held here. A random router's tenth and
    eleventh of 512 lie closer than the rounding of the stated precision
    moves them; the comparison leaves a position out where they do
    (`drivers/serve_qwen3next.py`)."""
    k = m["num_experts_per_tok"]
    logits = jnp.einsum("sd,de->se", x, router, precision=HI)
    scores = jax.nn.softmax(logits, axis=-1)
    top, idx = jax.lax.top_k(logits, k + 1)
    experts = idx[:, :k]
    weights = jnp.take_along_axis(scores, experts, axis=1)
    if m["norm_topk_prob"]:
        weights = weights / weights.sum(axis=-1, keepdims=True)
    first, count = m["held_experts"]
    here = (idx[:, k - 1:] >= first) & (idx[:, k - 1:] < first + count)
    margin = jnp.where(here.any(axis=-1), top[:, k - 1] - top[:, k], jnp.inf)
    return weights, experts, margin


class Layers:
    """The jitted pieces of a block for one set of sizes `m`
    (`Layers.of(m)`: one instance a set of sizes, so that a second pass
    compiles nothing)."""
    _made = {}

    def __init__(self, m):
        self.m = m
        self._first = jax.jit(self._mixer_and_shared,
                              static_argnames=("kind", "act", "state"))
        self._expert = jax.jit(self._one_expert, static_argnames=("act",))

    @classmethod
    def of(cls, m):
        key = repr(sorted((k, repr(v)) for k, v in m.items()))
        if key not in cls._made:
            cls._made[key] = cls(m)
        return cls._made[key]

    def _mixer_and_shared(self, w, h, n_real, kind, act, state):
        """h + mixer(norm1(h)), then what every token of the expert
        layer takes (the gated shared expert) and what the routed
        experts need: (h, x, y, weights, experts, margin, the state
        after the last real token or None)."""
        m = self.m
        eps = m["rms_norm_eps"]
        x = _zc_norm(h, w["norm1"], eps)
        if kind == "linear_attention":
            mixed, kept = _linear(m, w, x, act, state, n_real)
        else:
            mixed, kept = _attention(m, w, x, act), None
        h = h + mixed
        x = _zc_norm(h, w["norm2"], eps)
        weights, experts, margin = route(m, x, w["moe.router"])
        shared = _gated(x, w["moe.shared.gate"], w["moe.shared.up"],
                        w["moe.shared.down"], act)
        y = jax.nn.sigmoid(jnp.einsum("sd,do->so", x, w["moe.shared_gate"],
                                      precision=HI)) * shared
        return h, x, y, weights, experts, margin, kept

    @staticmethod
    def _one_expert(x, gate, up, down, weight, act):
        return weight[:, None] * _gated(x, gate, up, down, act)

    def forward(self, w, h, layer, prec=REFERENCE, n_real=None):
        """One block on one sequence h [S, d], the first `n_real`
        positions real (all where None): (h, the router's margin [S],
        the delta-rule states after the last real token or None)."""
        m, act = self.m, prec["act"]
        h, x, y, weights, experts, margin, kept = self._first(
            {k: v for k, v in w.items() if ".experts." not in k}, h,
            h.shape[0] if n_real is None else n_real,
            kind=m["layer_types"][layer], act=act, state=prec.get("state"))
        # departs: a loop over the experts held here with a mask; the
        # chosen experts that other chips hold are not in this sum
        first, count = m["held_experts"]
        for e in range(first, first + count):
            weight = jnp.sum(jnp.where(experts == e, weights, 0.0), axis=-1)
            y = y + self._expert(
                x, w[f"moe.experts.{e}.gate"], w[f"moe.experts.{e}.up"],
                w[f"moe.experts.{e}.down"], weight, act=act)
        return h + y, margin, kept


@functools.partial(jax.jit, static_argnames=("eps", "act"))
def _head(h, norm, head, probes, eps, act):
    """h [count, d], probes [n, count] -> the best logit, its token,
    the probed tokens' logits [n, count]."""
    lg = _einsum("sd,dv->sv", _zc_norm(h, norm, eps), head, act)
    return (jnp.max(lg, axis=-1), jnp.argmax(lg, axis=-1).astype(jnp.int32),
            jnp.take_along_axis(lg, probes.T, axis=-1).T)


def full_logits(m, seed, init, ids, prec=REFERENCE):
    """Logits [S, V] of one sequence (small sizes: the tests)."""
    layers = Layers.of(m)
    h = outer_weights(m, seed, EMBED)[jnp.asarray(ids)]
    for layer in range(m["num_layers"]):
        h, _, _ = layers.forward(layer_weights(m, seed, layer, init), h,
                                 layer, prec)
    return _einsum("sd,dv->sv", _zc_norm(
        h, outer_weights(m, seed, FINAL_NORM), m["rms_norm_eps"]),
        outer_weights(m, seed, HEAD), prec["act"])


def padded_length(n, length):
    """The length a sequence of `n` tokens is padded to: the first of
    4,096, 8,192 and `length` that holds it."""
    return next((b for b in (4096, 8192) if n <= b < length), length)


def _forward_all(m, seed, init, seqs, prec, length, log, keep_states):
    """The whole forward pass of each sequence of `seqs` (int arrays),
    each padded to `padded_length`: the final hidden rows of each, the
    smallest router margin at each position, and, with `keep_states`,
    {layer: [the state after each sequence's last token]} over the
    linear layers."""
    length = length or max(len(s) for s in seqs)
    # departs: padded to one of a few lengths
    ids = [np.zeros((padded_length(len(s), length),), np.int32)
           for s in seqs]
    for row, s in zip(ids, seqs):
        row[:len(s)] = s
    embed = outer_weights(m, seed, EMBED)
    hs = [embed[jnp.asarray(row)] for row in ids]
    del embed
    layers, states = Layers.of(m), {}
    margins = [jnp.full((len(row),), jnp.inf) for row in ids]
    t0 = time.perf_counter()
    for layer in range(m["num_layers"]):
        w = layer_weights(m, seed, layer, init)
        kept = []
        for i, h in enumerate(hs):
            hs[i], margin, state = layers.forward(w, h, layer, prec,
                                                  n_real=len(seqs[i]))
            margins[i] = jnp.minimum(margins[i], margin)
            kept.append(state)
        jax.block_until_ready(hs)
        if keep_states and kept[0] is not None:
            states[layer] = [np.asarray(s) for s in kept]
        del w, kept
        if log:
            log(f"reference: layer {layer} done at "
                f"{time.perf_counter() - t0:.1f} s")
    return hs, margins, states


def position_logits(m, seed, init, seqs, spans, probes, prec=REFERENCE,
                    length=None, log=None):
    """The whole forward pass of each sequence of `seqs` (int arrays),
    and at the positions `spans[i] = (first, count)` of sequence i:
    (the best logit, its token, the logits of each row of tokens in
    `probes[i]` [n, count], the smallest margin by which a router chose
    there over the layers)."""
    hs, margins, _ = _forward_all(m, seed, init, seqs, prec, length, log,
                                  False)
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


def final_states(m, seed, init, seqs, prec=REFERENCE, length=None,
                 log=None):
    """{layer: [state [heads, K, V] after the last token of each
    sequence]} over the linear layers: what a request that has taken in
    `seqs[i]` keeps there."""
    return _forward_all(m, seed, init, seqs, prec, length, log, True)[2]
