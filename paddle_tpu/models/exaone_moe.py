"""K-EXAONE (`exaone_moe`): sliding-window attention layers among full
ones, grouped-query heads with QK-norm, and an expert layer behind a
sigmoid router, for SERVING.

The config's keys name the mechanisms; what they leave unsaid follows
the family's published code (EXAONE 4.0, `modeling_exaone4.py`) and, for
the router, the form its keys name (DeepSeek-V3's gate). Every norm is
RMSNorm and nothing has a bias but the router's correction.

  block    h = h + norm_a(Attn(h));  h = h + norm_f(FFN(h)): the norm is
           on a sublayer's OUTPUT, and nothing norms its input.
           FFN: a gated MLP in a "dense" layer, the expert layer in a
           "sparse" one (`mlp_layer_types`).
  attention  q of `num_attention_heads` heads, k and v of
           `num_key_value_heads`, all of `head_dim`; q and k pass an
           RMSNorm over each head (one gain of `head_dim` for all heads
           each). In a "sliding_attention" layer q and k are then
           rotated by position over the whole head (rotate-half,
           `rope_theta`), and key j is visible to query i iff
           0 <= i - j < `sliding_window`; a "full_attention" layer has
           no positions and sees every j <= i. Query head n reads K/V
           head n // (heads / kv heads); scores * head_dim ** -0.5.
  experts  s = sigmoid(x W_r) in float32 over all `num_experts`; the top
           `num_experts_per_tok` of s + bias are chosen; their weights
           are s (not s + bias) over the chosen sum, times
           `routed_scaling_factor`; plus one shared expert. No capacity
           and no drops (`moe/serving.py`). A model built with
           `held=(first, count)` keeps that slice of the routed experts
           and computes its share of the sum: one chip of an
           expert-parallel deployment.

What a request keeps: in a full layer K and V rows paged by token
(`kv_cache.kv_kind`); in a sliding layer a RING of `sliding_window` K
rows and as many V rows, by request (`kv_cache.window_kind`): position
p lives in ring row p % window, keys are cached rotated, and which rows
are valid follows from the positions a step works on, so a ring is
never cleared. Decode over a ring is `paged_decode_attention` on one
`window`-row page a request; a chunk attends over the ring and its own
keys in `window_prefill_chunk` and puts its last rows into the ring
afterwards.

`ExaoneMoeForCausalLM.served()` gives the serving engine its per-layer
protocol; `forward(ids)` is the same model on whole sequences with the
window as a mask. There is no training path, and the published
multi-token-prediction module is not built (it is no part of the
forward pass).
"""
import jax
import jax.numpy as jnp

from ..core.tensor import Tensor
from ..moe.serving import route_sigmoid_topk
from ..nn import Layer, LayerList
from ..nn.functional.norm import rms_norm_values
from ..ops.pallas_decode import (flash_prefill_chunk,
                                 paged_decode_attention,
                                 window_prefill_chunk, window_ring_write)
from ..ops.rotary import apply_rotary, rotary_cos_sin, yarn_inv_freq
from ..core.scope import scope
from .blocks import (GatedMLP, HeldExperts, ServedDecoder, Weights,
                     default_make, matmul)

__all__ = ["ExaoneMoeConfig", "ExaoneMoeForCausalLM"]

_PERIOD = ("sliding_attention",) * 3 + ("full_attention",)


class ExaoneMoeConfig:
    """The published config's names. `layer_types` and `mlp_layer_types`
    give each block's attention and feed-forward; their length is the
    depth. `num_experts` is what the router scores; `held=(first,
    count)` the slice of them this model keeps (default: all)."""

    def __init__(self, vocab_size=153600, hidden_size=6144,
                 layer_types=_PERIOD * 2,
                 mlp_layer_types=("dense",) + ("sparse",) * 7,
                 num_attention_heads=64, num_key_value_heads=8,
                 head_dim=128, sliding_window=128, intermediate_size=18432,
                 moe_intermediate_size=2048, num_experts=128,
                 num_experts_per_tok=8, num_shared_experts=1,
                 routed_scaling_factor=2.5, norm_topk_prob=True,
                 rms_norm_eps=1e-5, rope_theta=1000000.0,
                 max_seq_len=262144, initializer_range=0.02,
                 dtype="bfloat16", held=None):
        self.layer_types = tuple(layer_types)
        self.mlp_layer_types = tuple(mlp_layer_types)
        if len(self.layer_types) != len(self.mlp_layer_types):
            raise ValueError("layer_types and mlp_layer_types name the "
                             "same blocks")
        self.num_layers = len(self.layer_types)
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_attention_heads = num_attention_heads
        self.num_key_value_heads = num_key_value_heads
        self.head_dim = head_dim
        self.sliding_window = int(sliding_window)
        self.intermediate_size = intermediate_size
        self.moe_intermediate_size = moe_intermediate_size
        self.num_experts = num_experts
        self.num_experts_per_tok = num_experts_per_tok
        self.num_shared_experts = num_shared_experts
        self.routed_scaling_factor = float(routed_scaling_factor)
        self.norm_topk_prob = bool(norm_topk_prob)
        self.rms_norm_eps = rms_norm_eps
        self.rope_theta = float(rope_theta)
        self.max_seq_len = max_seq_len
        self.initializer_range = initializer_range
        self.dtype = dtype
        self.held = tuple(held) if held else (0, num_experts)

    @property
    def kv_width(self):
        """Lanes of a cached K (or V) row."""
        return self.num_key_value_heads * self.head_dim


class ExpertLayer(HeldExperts):
    """The shared expert plus this model's share of the routed experts,
    behind the sigmoid router."""

    def __init__(self, make, prefix, c):
        super().__init__(make, prefix)
        self.c = c
        self.router = self.param("router", (c.hidden_size, c.num_experts))
        self.bias = self.param("bias", (c.num_experts,), "bias")
        self.build_experts(c.hidden_size, c.moe_intermediate_size,
                           c.num_shared_experts)

    def route(self, x):
        c = self.c
        return route_sigmoid_topk(
            x, self.router._value, self.bias._value, c.num_experts_per_tok,
            c.routed_scaling_factor, renorm=c.norm_topk_prob)


class Attention(Weights):
    """Grouped-query attention with QK-norm; `sliding`: rotated, over
    the last `sliding_window` positions, cached in a ring a request."""

    def __init__(self, make, prefix, c, sliding):
        super().__init__(make, prefix)
        d, H = c.hidden_size, c.head_dim
        self.c, self.sliding = c, sliding
        self.rotates = sliding      # a full layer has no positions
        self.q = self.param("q", (d, c.num_attention_heads * H))
        self.k = self.param("k", (d, c.kv_width))
        self.v = self.param("v", (d, c.kv_width))
        self.q_norm = self.param("q_norm", (H,), "g")
        self.k_norm = self.param("k_norm", (H,), "g")
        self.o = self.param("o", (c.num_attention_heads * H, d))
        self._inv_freq = yarn_inv_freq(H, c.rope_theta)

    def cache_kind(self):
        from ..serving.kv_cache import kv_kind, window_kind
        c = self.c
        return window_kind(c.kv_width, c.sliding_window, c.dtype) \
            if self.sliding else kv_kind(c.kv_width)

    def project(self, x, positions):
        """x [T, d] at `positions` [T] -> q [T, N*H], k and v
        [T, Nk*H]: q and k normed a head and, in a sliding layer,
        rotated."""
        c = self.c
        T, H, eps = x.shape[0], c.head_dim, c.rms_norm_eps
        q = rms_norm_values(matmul(x, self.q._value).reshape(T, -1, H),
                            self.q_norm._value, eps)
        k = rms_norm_values(matmul(x, self.k._value).reshape(T, -1, H),
                            self.k_norm._value, eps)
        if self.rotates:
            cos, sin = rotary_cos_sin(positions, self._inv_freq)
            q = apply_rotary(q, cos[:, None], sin[:, None],
                             interleaved=False)
            k = apply_rotary(k, cos[:, None], sin[:, None],
                             interleaved=False)
        return q.reshape(T, -1), k.reshape(T, -1), matmul(x, self.v._value)

    def _kw(self, view):
        c = self.c
        return dict(use_kernel=view.use_kernel,
                    kv_heads=c.num_key_value_heads,
                    scale=c.head_dim ** -0.5)

    def _write(self, pages, at, k, v):
        return (pages[0].at[at].set(k.astype(pages[0].dtype)),
                pages[1].at[at].set(v.astype(pages[1].dtype)))

    def decode(self, x, pages, view):
        """One token a slot: x [S, d] at positions `view.ctx`."""
        c = self.c
        N, W = c.num_attention_heads, c.sliding_window
        q, k, v = self.project(x, view.ctx)
        if self.sliding:
            # the ring is a paged arena of one `window`-row page a
            # request; keys were rotated before they were cached and a
            # softmax does not care in what order it meets them
            at, tables = (view.rows, view.ctx % W), view.rows[:, None]
            ctx, name = jnp.minimum(view.ctx, W - 1), "paged_decode_window"
        else:
            at, tables = (view.blk, view.off), view.tables
            ctx, name = view.ctx, "paged_decode"
        kp, vp = self._write(pages, at, k, v)
        o = paged_decode_attention(q[:, None], kp, vp, tables, ctx, N,
                                   name=name, **self._kw(view))[:, 0]
        return matmul(o.astype(x.dtype), self.o._value), (kp, vp)

    def prefill(self, x, pages, view):
        """A chunk of one request: x [C, d], the first `n_real` rows
        real."""
        N = self.c.num_attention_heads
        q, k, v = self.project(x, view.positions)
        if self.sliding:
            # attends over the ring as it was and the chunk's own keys;
            # only then do the chunk's last real rows go into the ring
            o = window_prefill_chunk(
                q, k, v, pages[0], pages[1], view.row, view.p0, N,
                n_real=view.n_real, **self._kw(view))
            kp = window_ring_write(pages[0], view.row, k, view.p0,
                                   view.n_real)
            vp = window_ring_write(pages[1], view.row, v, view.p0,
                                   view.n_real)
        else:
            kp, vp = self._write(pages, (view.blk, view.off), k, v)
            o = flash_prefill_chunk(
                q[None], kp, vp, view.table_row, view.p0, N,
                n_real=view.n_real, **self._kw(view))[0]
        return matmul(o.astype(x.dtype), self.o._value), (kp, vp)

    def dense(self, x):
        """Attention of one whole sequence x [T, d], no cache: the
        window is a mask."""
        c = self.c
        T, N, Nk, H = x.shape[0], c.num_attention_heads, \
            c.num_key_value_heads, c.head_dim
        pos = jnp.arange(T, dtype=jnp.int32)
        q, k, v = self.project(x, pos)
        f32 = jnp.float32
        q = q.reshape(T, Nk, N // Nk, H).astype(f32)
        scores = jnp.einsum("tkgh,skh->kgts", q,
                            k.reshape(T, Nk, H).astype(f32)) * H ** -0.5
        behind = pos[:, None] - pos[None, :]
        seen = behind >= 0
        if self.sliding:
            seen = seen & (behind < c.sliding_window)
        probs = jax.nn.softmax(jnp.where(seen, scores, -1e30), -1)
        o = jnp.einsum("kgts,skh->tkgh", probs.astype(x.dtype).astype(f32),
                       v.reshape(T, Nk, H).astype(f32)).astype(x.dtype)
        return matmul(o.reshape(T, N * H), self.o._value)


class ExaoneMoeBlock(Weights):
    def __init__(self, make, prefix, c, attention, mlp):
        super().__init__(make, prefix)
        d = c.hidden_size
        if attention not in ("sliding_attention", "full_attention") \
                or mlp not in ("dense", "sparse"):
            raise ValueError(f"layer types {attention!r}, {mlp!r}")
        self.eps = c.rms_norm_eps
        self.attn = Attention(make, prefix + "attn.", c,
                              attention == "sliding_attention")
        self.attn_norm = self.param("attn_norm", (d,), "g")
        if mlp == "dense":
            self.mlp = GatedMLP(make, prefix + "mlp.", d,
                                c.intermediate_size)
        else:
            self.moe = ExpertLayer(make, prefix + "moe.", c)
        self.ffn_norm = self.param("ffn_norm", (d,), "g")

    def run(self, h, attend, live=None, use_kernel=None):
        """The block with `attend(h)` for the attention, which returns
        its output and whatever else: (h, that, the expert layer's
        counts or None)."""
        with scope("attn"):
            out, rest = attend(h)
            h = h + rms_norm_values(out, self.attn_norm._value, self.eps)
        sparse = hasattr(self, "moe")
        with scope("experts" if sparse else "mlp"):
            if sparse:
                y, stats = self.moe.run(h, live, use_kernel)
            else:
                y, stats = self.mlp.run(h), None
            return h + rms_norm_values(y, self.ffn_norm._value, self.eps), \
                rest, stats


class _ServedBlock:
    """One block behind the engine's per-layer protocol: cache kind
    "kv" in a full layer, "window" (rings by request) in a sliding
    one."""

    def __init__(self, block):
        self.block = block
        self.cache_kind = block.attn.cache_kind()

    def decode(self, h, pages, view):
        return self.block.run(
            h, lambda x: self.block.attn.decode(x, pages, view),
            view.live, view.use_kernel)

    def prefill(self, h, pages, view):
        return self.block.run(
            h, lambda x: self.block.attn.prefill(x, pages, view),
            view.live, view.use_kernel)


def _default_make(config):
    """`blocks.default_make`, and the router's correction bias at zero,
    where the published initialiser leaves it."""
    plain = default_make(config)

    def make(name, shape, kind):
        if kind == "bias":
            return jnp.zeros(shape, jnp.float32)
        return plain(name, shape, kind)
    return make


class ExaoneMoeForCausalLM(Layer):
    """`make(name, shape, kind)` supplies each parameter (a checkpoint
    loader, seeded weights drawn on the device): kind "w" a matrix, "g"
    a gain, "bias" a router's correction bias (float32); by default
    they are random. Untied embedding and head."""

    def __init__(self, config, make=None):
        super().__init__()
        c = self.config = config
        make = make or _default_make(c)
        top = Weights(make, "")
        self.embed = top.param("embed", (c.vocab_size, c.hidden_size))
        self.blocks = LayerList([
            ExaoneMoeBlock(make, f"blocks.{i}.", c, attention, mlp)
            for i, (attention, mlp) in enumerate(
                zip(c.layer_types, c.mlp_layer_types))])
        self.norm = top.param("norm", (c.hidden_size,), "g")
        self.head = top.param("head", (c.hidden_size, c.vocab_size))

    def num_parameters(self):
        return sum(int(p._value.size) for p in self.parameters())

    def logits(self, h):
        """Final norm and the head, float32 logits."""
        hn = rms_norm_values(h, self.norm._value, self.config.rms_norm_eps)
        return jnp.dot(hn, self.head._value.astype(hn.dtype),
                       preferred_element_type=jnp.float32)

    def forward(self, input_ids):
        """Logits [b, s, V] of whole sequences. Inference only."""
        ids = input_ids._value if isinstance(input_ids, Tensor) \
            else jnp.asarray(input_ids)

        def one(row):
            h = self.embed._value[row]
            for block in self.blocks:
                h, _, _ = block.run(
                    h, lambda x: (block.attn.dense(x), None))
            return self.logits(h)
        return Tensor(jnp.stack([one(row) for row in ids]))

    def served(self):
        """This model behind the serving engine's per-layer protocol."""
        return ServedDecoder(self, [_ServedBlock(b) for b in self.blocks])
