"""Qwen3-Next (`qwen3_next`): gated delta-rule linear-attention layers,
three to each gated full-attention layer, and an expert layer with a
gated shared expert in every block, for SERVING.

The layer equations follow the published `modeling_qwen3_next.py`; the
linear layer is the Gated DeltaNet of Yang, Kautz & Hatamizadeh 2024
(arXiv:2412.06464). Matrices are [in, out] and nothing has a bias.

  norms    "zero-centred" RMSNorm: x rsqrt(mean(x^2) + eps) (1 + w), w
           starting at 0, in float32 (the blocks' two norms, the final
           norm, q_norm and k_norm).
  block    h = h + Mixer(norm1(h));  h = h + MoE(norm2(h)).
  linear   [q k v z] per key head = x W_qkvz, [b a] per key head =
           x W_ba; [q (all heads) | k | v] through a causal depthwise
           convolution of `linear_conv_kernel_dim` taps and silu; q, k
           L2-normalised over a head, q times K^-1/2; beta = sigmoid(b),
           g = -exp(A_log) softplus(a + dt_bias) a value head; value
           head j reads key head j // (value heads / key heads). A value
           head's state S in R^{K x V}, a token:
               S <- exp(g) S;  S <- S + k (beta (v - S^T k))^T;  o = S^T q
           out = W_out (w * RMSNorm(o) * silu(z)) a value head: the norm
           FIRST, then the gate (Mamba-2 has them the other way round).
  full     q and a gate of `head_dim` each a query head (interleaved by
           head in W_q), k and v of `num_key_value_heads`; q_norm,
           k_norm over each head, then rotate-half over the first
           `partial_rotary_factor` of a head's dimensions; causal softmax
           at head_dim^-1/2; the output times sigmoid(gate), then W_o.
  experts  p = softmax(x W_r) in float32 over all `num_experts`, the
           top `num_experts_per_tok`, their weights p over the sum of
           the chosen p (`norm_topk_prob`); a shared expert scaled by
           sigmoid(x W_sg). A model built with `held=(first, count)`
           keeps that slice of the routed experts and computes its share
           of the sum: one chip of an expert-parallel deployment.

What a request keeps: in a linear layer the last `taps - 1` rows of the
convolution's input (the model's dtype) and the float32 states, rows by
request of the serving cache (`kv_cache.state_kind`), whatever the
request's length; decode is one step of the recurrence a slot
(`gdn_state_step`), prefill the chunked form (`gdn_chunk`) from the
request's stored state, zeros where the chunk starts at position 0, and
leaves the state after the chunk's last real token. In a full layer K
and V rows paged by token (`kv_cache.kv_kind`).

`Qwen3NextForCausalLM.served()` gives the serving engine its per-layer
protocol; `forward(ids)` is the same model on whole sequences. There is
no training path (the chunked form has no backward), and the published
multi-token-prediction module is not built (no part of the forward
pass).
"""
import jax
import jax.numpy as jnp

from ..core.tensor import Parameter, Tensor
from ..nn import Layer, LayerList
from ..ops.pallas_decode import flash_prefill_chunk, paged_decode_attention
from ..ops.pallas_gdn import SUB_CHUNK, gdn_chunk, gdn_state_step
from ..ops.rotary import apply_rotary, rotary_cos_sin, yarn_inv_freq
from ..core.scope import scope
from .blocks import (GatedMLP, HeldExperts, ServedDecoder, Weights,
                     default_make, gated_rms_norm, matmul,
                     zero_centred_rms_norm)

__all__ = ["Qwen3NextConfig", "Qwen3NextForCausalLM"]

_PERIOD = ("linear_attention",) * 3 + ("full_attention",)


class Qwen3NextConfig:
    """The published config's names. `layer_types` is the pattern of
    "linear_attention" and "full_attention"; its length is the depth.
    `num_experts` is what the router scores; `held=(first, count)` the
    slice of them this model keeps (default: all)."""

    def __init__(self, vocab_size=151936, hidden_size=2048,
                 layer_types=_PERIOD * 12, num_attention_heads=16,
                 num_key_value_heads=2, head_dim=256,
                 partial_rotary_factor=0.25, rope_theta=10000000.0,
                 linear_num_key_heads=16, linear_num_value_heads=32,
                 linear_key_head_dim=128, linear_value_head_dim=128,
                 linear_conv_kernel_dim=4, moe_intermediate_size=512,
                 shared_expert_intermediate_size=512, num_experts=512,
                 num_experts_per_tok=10, norm_topk_prob=True,
                 rms_norm_eps=1e-6, max_seq_len=262144,
                 initializer_range=0.02, dtype="bfloat16", held=None):
        if linear_num_value_heads % linear_num_key_heads:
            raise ValueError("linear_num_value_heads must be a multiple of "
                             "linear_num_key_heads")
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.layer_types = tuple(layer_types)
        self.num_layers = len(self.layer_types)
        self.num_attention_heads = num_attention_heads
        self.num_key_value_heads = num_key_value_heads
        self.head_dim = head_dim
        self.partial_rotary_factor = float(partial_rotary_factor)
        self.rope_theta = float(rope_theta)
        self.linear_num_key_heads = linear_num_key_heads
        self.linear_num_value_heads = linear_num_value_heads
        self.linear_key_head_dim = linear_key_head_dim
        self.linear_value_head_dim = linear_value_head_dim
        self.linear_conv_kernel_dim = linear_conv_kernel_dim
        self.moe_intermediate_size = moe_intermediate_size
        self.shared_expert_intermediate_size = shared_expert_intermediate_size
        self.num_experts = num_experts
        self.num_experts_per_tok = num_experts_per_tok
        self.norm_topk_prob = bool(norm_topk_prob)
        self.rms_norm_eps = rms_norm_eps
        self.max_seq_len = max_seq_len
        self.initializer_range = initializer_range
        self.dtype = dtype
        self.held = tuple(held) if held else (0, num_experts)

    @property
    def key_dim(self):
        return self.linear_num_key_heads * self.linear_key_head_dim

    @property
    def value_dim(self):
        return self.linear_num_value_heads * self.linear_value_head_dim

    @property
    def conv_dim(self):
        """Channels through the convolution: q, k and v."""
        return 2 * self.key_dim + self.value_dim

    @property
    def kv_width(self):
        """Lanes of a cached K (or V) row of a full layer."""
        return self.num_key_value_heads * self.head_dim

    @property
    def rotary_dim(self):
        return int(self.head_dim * self.partial_rotary_factor)


class GatedDeltaNet(Weights):
    """The delta-rule mixer over a request's row: the convolution's tail
    `[taps - 1, conv_dim]` in the model's dtype and the states
    `[value heads, K, V]` in float32."""

    layer = "linear"    # its half of a block in a device trace

    def __init__(self, make, prefix, c):
        super().__init__(make, prefix)
        d, H = c.hidden_size, c.linear_num_value_heads
        self.c = c
        self.in_proj_qkvz = self.param(
            "in_proj_qkvz", (d, 2 * c.key_dim + 2 * c.value_dim))
        self.in_proj_ba = self.param("in_proj_ba", (d, 2 * H))
        self.conv_w = self.param("conv_w", (c.linear_conv_kernel_dim,
                                            c.conv_dim), "conv")
        self.dt_bias = self.param("dt_bias", (H,), "dt_bias")
        self.A_log = self.param("A_log", (H,), "A_log")
        self.norm = self.param("norm", (c.linear_value_head_dim,), "g")
        self.out_proj = self.param("out_proj", (c.value_dim, d))

    def cache_kind(self):
        from ..serving.kv_cache import state_kind
        c = self.c
        return state_kind(
            ((c.linear_conv_kernel_dim - 1, c.conv_dim), c.dtype),
            ((c.linear_num_value_heads, c.linear_key_head_dim,
              c.linear_value_head_dim), "float32"))

    # -- the pieces both paths share ----------------------------------------
    def _project(self, x):
        """x [T, d] -> z [T, H, V], the convolution's input [T, conv_dim]
        ([q | k | v], each head after head), b and a [T, H]. W_qkvz holds
        [q 128 | k 128 | v ratio x 128 | z ratio x 128] a key head, W_ba
        [b ratio | a ratio]."""
        c = self.c
        T, Hk = x.shape[0], c.linear_num_key_heads
        K, V = c.linear_key_head_dim, c.linear_value_head_dim
        r = c.linear_num_value_heads // Hk
        qkvz = matmul(x, self.in_proj_qkvz._value).reshape(T, Hk, -1)
        q, k = qkvz[..., :K], qkvz[..., K:2 * K]
        v, z = qkvz[..., 2 * K:2 * K + r * V], qkvz[..., 2 * K + r * V:]
        ba = matmul(x, self.in_proj_ba._value).reshape(T, Hk, 2 * r)
        mixed = jnp.concatenate([q.reshape(T, -1), k.reshape(T, -1),
                                 v.reshape(T, -1)], axis=-1)
        return z.reshape(T, -1, V), mixed, ba[..., :r].reshape(T, -1), \
            ba[..., r:].reshape(T, -1)

    def _convolve(self, windows):
        """windows [taps, T, conv_dim]: tap k of every position -> q, k
        [T, Hk, K] (normalised, q scaled) and v [T, H, V], float32; the
        convolution itself in the input's dtype."""
        c = self.c
        T = windows.shape[1]
        w = self.conv_w._value.astype(jnp.float32)
        acc = sum(w[k] * windows[k].astype(jnp.float32)
                  for k in range(c.linear_conv_kernel_dim))
        out = jax.nn.silu(acc).astype(windows.dtype).astype(jnp.float32)
        K, kd = c.linear_key_head_dim, c.key_dim

        def l2(a):
            return a * jax.lax.rsqrt(jnp.sum(a * a, axis=-1, keepdims=True)
                                     + 1e-6)
        q = l2(out[:, :kd].reshape(T, -1, K)) * K ** -0.5
        k = l2(out[:, kd:2 * kd].reshape(T, -1, K))
        return q, k, out[:, 2 * kd:].reshape(T, -1, c.linear_value_head_dim)

    def _gates(self, b, a):
        """(g, beta) [T, H] float32: the log decay and the write
        strength."""
        g = -jnp.exp(self.A_log._value.astype(jnp.float32)) * jax.nn.softplus(
            a.astype(jnp.float32) + self.dt_bias._value.astype(jnp.float32))
        return g, jax.nn.sigmoid(b.astype(jnp.float32))

    def _finish(self, o, z, dtype):
        """The gated norm a value head and the output matrix; o [T, H, V]
        float32."""
        y = gated_rms_norm(o.astype(dtype), z, self.norm._value,
                           self.c.rms_norm_eps)
        return matmul(y.reshape(y.shape[0], -1), self.out_proj._value)

    # -- serving ------------------------------------------------------------
    def decode(self, x, pages, view):
        """One token a slot: x [S, d]."""
        tails, states = pages
        z, mixed, b, a = self._project(x)
        window = jnp.concatenate([tails[view.rows], mixed[:, None]], axis=1)
        q, k, v = self._convolve(jnp.moveaxis(window, 1, 0))
        tails = tails.at[view.rows].set(jnp.where(
            view.live[:, None, None], window[:, 1:], 0).astype(tails.dtype))
        g, beta = self._gates(b, a)
        states, o = gdn_state_step(states, view.rows, view.live, q, k, v, g,
                                   beta, use_kernel=view.use_kernel)
        return self._finish(o, z, x.dtype), (tails, states)

    def prefill(self, x, pages, view):
        """A chunk of one request: x [C, d], the first `n_real` rows
        real. Starts from the row's state, from zeros at position 0."""
        tails, states = pages
        first = view.p0 == 0
        tail = jnp.where(first, 0, tails[view.row]).astype(x.dtype)
        y, state, new_tail = self._chunk(
            x, tail, jnp.where(first, 0.0, states[view.row]), view.live,
            view.n_real, view.use_kernel)
        return y, (tails.at[view.row].set(new_tail.astype(tails.dtype)),
                   states.at[view.row].set(state))

    def _chunk(self, x, tail, state, live, n_real, use_kernel=None):
        """The chunk through convolution and recurrence -> (the mixer's
        output [C, d], the state after its last real token, the
        convolution's tail at row `n_real`)."""
        c = self.c
        T, taps = x.shape[0], c.linear_conv_kernel_dim
        z, mixed, b, a = self._project(x)
        seq = jnp.concatenate([tail, mixed], axis=0)    # [taps - 1 + T, .]
        q, k, v = self._convolve(jnp.stack(
            [seq[i:i + T] for i in range(taps)]))
        g, beta = self._gates(b, a)
        # a padding position neither decays nor writes the state
        g, beta = g * live[:, None], beta * live[:, None]
        o, state = gdn_chunk(q, k, v, g, beta, state, n_real, sub=SUB_CHUNK,
                             use_kernel=use_kernel)
        new_tail = jax.lax.dynamic_slice(seq, (n_real, 0),
                                         (taps - 1, seq.shape[1]))
        return self._finish(o, z, x.dtype), state, new_tail

    def dense(self, x):
        """One whole sequence x [T, d] from an empty state."""
        c = self.c
        T = x.shape[0]
        return self._chunk(
            x, jnp.zeros((c.linear_conv_kernel_dim - 1, c.conv_dim), x.dtype),
            jnp.zeros((c.linear_num_value_heads, c.linear_key_head_dim,
                       c.linear_value_head_dim), jnp.float32),
            jnp.ones((T,), bool), T)[0]


class GatedAttention(Weights):
    """Grouped-query attention with QK-norm, partial rotary and an
    output gate, over the paged K/V arenas."""

    layer = "attn"      # its half of a block in a device trace

    def __init__(self, make, prefix, c):
        super().__init__(make, prefix)
        d, H, N = c.hidden_size, c.head_dim, c.num_attention_heads
        self.c = c
        self.rotary_dim = c.rotary_dim
        self.q = self.param("q", (d, N * 2 * H))
        self.k = self.param("k", (d, c.kv_width))
        self.v = self.param("v", (d, c.kv_width))
        self.q_norm = self.param("q_norm", (H,), "zc")
        self.k_norm = self.param("k_norm", (H,), "zc")
        self.o = self.param("o", (N * H, d))

    def cache_kind(self):
        from ..serving.kv_cache import kv_kind
        return kv_kind(self.c.kv_width)

    def project(self, x, positions):
        """x [T, d] at `positions` [T] -> q [T, N*H], the gate [T, N*H],
        k and v [T, Nk*H]: q and k normed a head, then rotated over
        their first `rotary_dim` dimensions."""
        c = self.c
        T, H, eps = x.shape[0], c.head_dim, c.rms_norm_eps
        qg = matmul(x, self.q._value).reshape(T, -1, 2 * H)
        q = zero_centred_rms_norm(qg[..., :H], self.q_norm._value, eps)
        k = zero_centred_rms_norm(matmul(x, self.k._value).reshape(T, -1, H),
                                  self.k_norm._value, eps)
        # tables of `rotary_dim` turn that many dimensions of a head
        cos, sin = rotary_cos_sin(positions, yarn_inv_freq(self.rotary_dim,
                                                           c.rope_theta))
        q = apply_rotary(q, cos[:, None], sin[:, None], interleaved=False)
        k = apply_rotary(k, cos[:, None], sin[:, None], interleaved=False)
        return q.reshape(T, -1), qg[..., H:].reshape(T, -1), \
            k.reshape(T, -1), matmul(x, self.v._value)

    def _out(self, o, gate, x):
        o = o.astype(jnp.float32) * jax.nn.sigmoid(gate.astype(jnp.float32))
        return matmul(o.astype(x.dtype), self.o._value)

    def _kw(self, view):
        c = self.c
        return dict(use_kernel=view.use_kernel,
                    kv_heads=c.num_key_value_heads, scale=c.head_dim ** -0.5)

    def _write(self, pages, view, k, v):
        return (pages[0].at[view.blk, view.off].set(k.astype(pages[0].dtype)),
                pages[1].at[view.blk, view.off].set(v.astype(pages[1].dtype)))

    def decode(self, x, pages, view):
        """One token a slot: x [S, d] at positions `view.ctx`."""
        q, gate, k, v = self.project(x, view.ctx)
        kp, vp = self._write(pages, view, k, v)
        o = paged_decode_attention(q[:, None], kp, vp, view.tables, view.ctx,
                                   self.c.num_attention_heads,
                                   **self._kw(view))[:, 0]
        return self._out(o, gate, x), (kp, vp)

    def prefill(self, x, pages, view):
        """A chunk of one request: x [C, d], the first `n_real` rows
        real."""
        q, gate, k, v = self.project(x, view.positions)
        kp, vp = self._write(pages, view, k, v)
        o = flash_prefill_chunk(q[None], kp, vp, view.table_row, view.p0,
                                self.c.num_attention_heads,
                                n_real=view.n_real, **self._kw(view))[0]
        return self._out(o, gate, x), (kp, vp)

    def dense(self, x):
        """Causal attention of one whole sequence x [T, d], no cache."""
        c = self.c
        T, N, Nk, H = x.shape[0], c.num_attention_heads, \
            c.num_key_value_heads, c.head_dim
        q, gate, k, v = self.project(x, jnp.arange(T, dtype=jnp.int32))
        f32 = jnp.float32
        q = q.reshape(T, Nk, N // Nk, H).astype(f32)
        scores = jnp.einsum("tkgh,skh->kgts", q,
                            k.reshape(T, Nk, H).astype(f32)) * H ** -0.5
        probs = jax.nn.softmax(jnp.where(
            jnp.tril(jnp.ones((T, T), bool)), scores, -1e30), -1)
        o = jnp.einsum("kgts,skh->tkgh", probs.astype(x.dtype).astype(f32),
                       v.reshape(T, Nk, H).astype(f32)).astype(x.dtype)
        return self._out(o.reshape(T, N * H), gate, x)


class SharedExpert(GatedMLP):
    """The shared expert, its output scaled by sigmoid(x W_sg). Its
    leaves are the expert layer's `shared.{gate,up,down}` and
    `shared_gate` [d, 1]."""

    def __init__(self, make, prefix, d, width):
        super().__init__(make, prefix + "shared.", d, width)
        self.shared_gate = Parameter(make(prefix + "shared_gate", (d, 1),
                                          "w"), trainable=False)

    def run(self, x):
        shared = super().run(x)
        with scope("mlp"):
            return (jax.nn.sigmoid(jnp.dot(
                x, self.shared_gate._value.astype(x.dtype),
                preferred_element_type=jnp.float32))
                * shared.astype(jnp.float32)).astype(x.dtype)


class ExpertLayer(HeldExperts):
    """The gated shared expert plus this model's share of the routed
    experts, behind the softmax router."""

    def __init__(self, make, prefix, c):
        super().__init__(make, prefix)
        self.c = c
        d, f, count = c.hidden_size, c.moe_intermediate_size, c.held[1]
        self.router = self.param("router", (d, c.num_experts))
        self.shared = SharedExpert(make, prefix, d,
                                   c.shared_expert_intermediate_size)
        self.experts_gate = self.param("experts_gate", (count, d, f))
        self.experts_up = self.param("experts_up", (count, d, f))
        self.experts_down = self.param("experts_down", (count, f, d))

    def route(self, x):
        """The softmax over every expert in float32, its top k, their
        weights the chosen p over their sum where `norm_topk_prob`."""
        c = self.c
        p = jax.nn.softmax(jnp.dot(x, self.router._value.astype(x.dtype),
                                   preferred_element_type=jnp.float32), -1)
        weights, experts = jax.lax.top_k(p, c.num_experts_per_tok)
        if c.norm_topk_prob:
            weights = weights / weights.sum(-1, keepdims=True)
        return weights, experts.astype(jnp.int32)


class Qwen3NextBlock(Weights):
    def __init__(self, make, prefix, c, kind):
        super().__init__(make, prefix)
        d = c.hidden_size
        self.eps = c.rms_norm_eps
        self.norm1 = self.param("norm1", (d,), "zc")
        if kind == "linear_attention":
            self.mixer = GatedDeltaNet(make, prefix + "linear.", c)
        elif kind == "full_attention":
            self.mixer = GatedAttention(make, prefix + "attn.", c)
        else:
            raise ValueError(f"layer type {kind!r}")
        self.norm2 = self.param("norm2", (d,), "zc")
        self.moe = ExpertLayer(make, prefix + "moe.", c)

    def run(self, h, mix, live=None, use_kernel=None):
        """The block with `mix(x)` for the mixer, which returns its output
        and whatever else: (h, that, the expert layer's counts)."""
        with scope(self.mixer.layer):
            out, rest = mix(zero_centred_rms_norm(h, self.norm1._value,
                                                  self.eps))
            h = h + out.astype(h.dtype)
        with scope("experts"):
            y, stats = self.moe.run(
                zero_centred_rms_norm(h, self.norm2._value, self.eps), live,
                use_kernel)
            return h + y.astype(h.dtype), rest, stats


class _ServedBlock:
    """One block behind the engine's per-layer protocol: cache kind
    "state" (rows by request) in a linear layer, "kv" in a full one."""

    def __init__(self, block):
        self.block = block
        self.cache_kind = block.mixer.cache_kind()

    def decode(self, h, pages, view):
        return self.block.run(
            h, lambda x: self.block.mixer.decode(x, pages, view),
            view.live, view.use_kernel)

    def prefill(self, h, pages, view):
        return self.block.run(
            h, lambda x: self.block.mixer.prefill(x, pages, view),
            view.live, view.use_kernel)


def _default_make(config):
    """`blocks.default_make`, the zero-centred gains at 0, and the
    linear layers' leaves as the published module draws them: dt_bias
    1, A_log = log U(0, 16), the convolution's taps uniform in
    +-1/sqrt(taps) (the default of a depthwise Conv1d)."""
    from ..core.random import default_generator
    plain = default_make(config)
    dtype = jnp.dtype(config.dtype)

    def make(name, shape, kind):
        if kind in ("w", "g"):
            return plain(name, shape, kind)
        if kind == "zc":
            return jnp.zeros(shape, dtype)
        if kind == "dt_bias":
            return jnp.ones(shape, dtype)
        u = jax.random.uniform(default_generator().split(), shape,
                               jnp.float32)
        if kind == "A_log":
            x = jnp.log(16.0 * u + 1e-6)
        else:
            x = (2.0 * u - 1.0) * config.linear_conv_kernel_dim ** -0.5
        return x.astype(dtype)
    return make


class Qwen3NextForCausalLM(Layer):
    """`make(name, shape, kind)` supplies each parameter (a checkpoint
    loader, seeded weights drawn on the device): kind "w" a matrix, "g"
    a gain, "zc" a zero-centred gain, "conv" the convolution's taps,
    "dt_bias", "A_log"; by default they are random. Untied embedding and
    head."""

    def __init__(self, config, make=None):
        super().__init__()
        c = self.config = config
        make = make or _default_make(c)
        top = Weights(make, "")
        self.embed = top.param("embed", (c.vocab_size, c.hidden_size))
        self.blocks = LayerList([
            Qwen3NextBlock(make, f"blocks.{i}.", c, kind)
            for i, kind in enumerate(c.layer_types)])
        self.norm = top.param("norm", (c.hidden_size,), "zc")
        self.head = top.param("head", (c.hidden_size, c.vocab_size))

    def num_parameters(self):
        return sum(int(p._value.size) for p in self.parameters())

    def logits(self, h):
        """Final norm and the head, float32 logits."""
        hn = zero_centred_rms_norm(h, self.norm._value,
                                   self.config.rms_norm_eps)
        return jnp.dot(hn, self.head._value.astype(hn.dtype),
                       preferred_element_type=jnp.float32)

    def forward(self, input_ids):
        """Logits [b, s, V] of whole sequences. Inference only."""
        ids = input_ids._value if isinstance(input_ids, Tensor) \
            else jnp.asarray(input_ids)

        def one(row):
            h = self.embed._value[row]
            for block in self.blocks:
                h, _, _ = block.run(h, lambda x: (block.mixer.dense(x), None))
            return self.logits(h)
        return Tensor(jnp.stack([one(row) for row in ids]))

    def served(self):
        """This model behind the serving engine's per-layer protocol."""
        return ServedDecoder(self, [_ServedBlock(b) for b in self.blocks])
