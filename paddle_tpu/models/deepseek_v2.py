"""DeepSeek-V2 (arXiv:2405.04434): multi-head latent attention and an
expert layer with shared experts, for SERVING.

The layer equations follow the published `modeling_deepseek.py`; every
norm is RMSNorm and nothing has a bias.

  block    h = x + MLA(norm1(x));  y = h + FFN(norm2(h))
           FFN: a gated MLP in the first `first_k_dense_replace`
           layers, the expert layer after.
  MLA      c_q = norm(x W_qa); q = c_q W_qb -> heads of [nope | rope];
           kv_a = x W_kva: c_kv = norm(kv_a[:rank]), k_pe = RoPE(rest),
           one rotary key shared by all heads. What is CACHED is the
           row [c_kv | k_pe], never k or v. With W_kvb split a head
           into W_uk [nope, rank] and W_uv [rank, v], attention runs
           ABSORBED: q_lat = q_nope W_uk, score = [q_lat | q_pe] . row,
           o = (softmax . c_kv) W_uv, then W_o. The parameters hold
           W_uk and W_uv, the two halves of the published kv_b_proj.
           A prompt chunk with many real positions forms k and v a head
           from the cached rows instead, in the kernel's VMEM
           (`ops/pallas_mla.mla_prefill_chunk_expanded`): the
           up-projection of a row is then shared by the chunk's
           positions.
  experts  float32 softmax over all routed experts, group-limited
           top-k, weights not renormalised and scaled, plus the shared
           experts; no capacity and no drops (`moe/serving.py`). A
           model built with `held=(first, count)` keeps that slice of
           the routed experts and computes its share of the sum: one
           chip of an expert-parallel deployment.

`DeepseekV2ForCausalLM.served()` gives the serving engine its per-layer
protocol (cache kind: latent); `forward(ids)` is the same model on a
whole sequence with k and v a head formed as published, which the
tests hold the absorbed path to. There is no training path.
"""

import jax
import jax.numpy as jnp

from ..core.tensor import Tensor
from ..moe.serving import route_group_limited
from ..nn import Layer, LayerList
from ..nn.functional.norm import rms_norm_values
from ..ops.pallas_mla import (mla_expanded_pays, mla_paged_decode,
                              mla_prefill_chunk, mla_prefill_chunk_expanded)
from ..core.scope import scope
from .blocks import (GatedMLP, HeldExperts, ServedDecoder, Weights,
                     default_make, matmul)
from ..ops.rotary import (apply_rotary, rotary_cos_sin, yarn_inv_freq,
                          yarn_mscale)

__all__ = ["DeepseekV2Config", "DeepseekV2ForCausalLM"]

_LANES = 128


class DeepseekV2Config:
    """The published config's names. `n_routed_experts` is what the
    router scores; `held=(first, count)` the slice of them this model
    keeps (default: all)."""

    def __init__(self, vocab_size=102400, hidden_size=5120, num_layers=60,
                 num_attention_heads=128, q_lora_rank=1536,
                 kv_lora_rank=512, qk_nope_head_dim=128,
                 qk_rope_head_dim=64, v_head_dim=128,
                 intermediate_size=12288, moe_intermediate_size=1536,
                 n_routed_experts=160, n_shared_experts=2,
                 num_experts_per_tok=6, n_group=8, topk_group=3,
                 routed_scaling_factor=16.0, first_k_dense_replace=1,
                 rms_norm_eps=1e-6, rope_theta=10000.0, rope_scaling=None,
                 max_seq_len=163840, initializer_range=0.02,
                 dtype="bfloat16", held=None):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_layers = num_layers
        self.num_attention_heads = num_attention_heads
        self.q_lora_rank = q_lora_rank
        self.kv_lora_rank = kv_lora_rank
        self.qk_nope_head_dim = qk_nope_head_dim
        self.qk_rope_head_dim = qk_rope_head_dim
        self.v_head_dim = v_head_dim
        self.intermediate_size = intermediate_size
        self.moe_intermediate_size = moe_intermediate_size
        self.n_routed_experts = n_routed_experts
        self.n_shared_experts = n_shared_experts
        self.num_experts_per_tok = num_experts_per_tok
        self.n_group = n_group
        self.topk_group = topk_group
        self.routed_scaling_factor = float(routed_scaling_factor)
        self.first_k_dense_replace = first_k_dense_replace
        self.rms_norm_eps = rms_norm_eps
        self.rope_theta = float(rope_theta)
        self.rope_scaling = dict(rope_scaling or {})
        self.max_seq_len = max_seq_len
        self.initializer_range = initializer_range
        self.dtype = dtype
        self.held = tuple(held) if held else (0, n_routed_experts)

    # -- what the rotary scaling comes to ---------------------------------
    def inv_freq(self):
        rs = self.rope_scaling
        return yarn_inv_freq(
            self.qk_rope_head_dim, self.rope_theta,
            factor=rs.get("factor", 1.0),
            original_len=rs.get("original_max_position_embeddings", 4096),
            beta_fast=rs.get("beta_fast", 32),
            beta_slow=rs.get("beta_slow", 1))

    def rotary_scale(self):
        """Factor on cos and sin: mscale over mscale_all_dim."""
        rs = self.rope_scaling
        factor = rs.get("factor", 1.0)
        return yarn_mscale(factor, rs.get("mscale", 1.0)) \
            / yarn_mscale(factor, rs.get("mscale_all_dim", 0.0))

    def softmax_scale(self):
        scale = (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5
        rs = self.rope_scaling
        if rs.get("mscale_all_dim"):
            m = yarn_mscale(rs.get("factor", 1.0), rs["mscale_all_dim"])
            scale *= m * m
        return scale

    @property
    def latent_width(self):
        """Lanes of a cached row: [c_kv | k_pe] padded to whole 128-lane
        tiles, which is how the device lays out a row of that width
        anyway (576 numbers take 640 lanes of HBM either way), and what
        lets the kernel copy pages as they lie."""
        width = self.kv_lora_rank + self.qk_rope_head_dim
        return -(-width // _LANES) * _LANES


def _einsum(spec, a, b):
    """A contraction batched over heads, summed in float32, out in a's
    dtype. The CPU runtime has no batched bfloat16 product with a
    float32 sum: off the TPU the operands are widened first, which
    gives the same numbers."""
    if jax.default_backend() != "tpu":
        out = jnp.einsum(spec, a.astype(jnp.float32), b.astype(jnp.float32))
    else:
        out = jnp.einsum(spec, a, b.astype(a.dtype),
                         preferred_element_type=jnp.float32)
    return out.astype(a.dtype)


class ExpertLayer(HeldExperts):
    """Shared experts plus this model's share of the routed experts,
    behind the group-limited softmax router."""

    def __init__(self, make, prefix, c):
        super().__init__(make, prefix)
        self.c = c
        self.router = self.param("router", (c.hidden_size,
                                            c.n_routed_experts))
        self.build_experts(c.hidden_size, c.moe_intermediate_size,
                           c.n_shared_experts)

    def route(self, x):
        c = self.c
        return route_group_limited(
            x, self.router._value, c.n_group, c.topk_group,
            c.num_experts_per_tok, c.routed_scaling_factor)


class MLAttention(Weights):
    def __init__(self, make, prefix, c):
        super().__init__(make, prefix)
        d, H = c.hidden_size, c.num_attention_heads
        self.c = c
        self.q_a = self.param("q_a", (d, c.q_lora_rank))
        self.q_a_norm = self.param("q_a_norm", (c.q_lora_rank,), "g")
        self.q_b = self.param(
            "q_b", (c.q_lora_rank,
                    H * (c.qk_nope_head_dim + c.qk_rope_head_dim)))
        self.kv_a = self.param(
            "kv_a", (d, c.kv_lora_rank + c.qk_rope_head_dim))
        self.kv_a_norm = self.param("kv_a_norm", (c.kv_lora_rank,), "g")
        # kv_b_proj [rank, H * (nope + v)] a head: its key half,
        # transposed for the absorbed query, and its value half
        self.w_uk = self.param(
            "w_uk", (H, c.qk_nope_head_dim, c.kv_lora_rank))
        self.w_uv = self.param("w_uv", (H, c.kv_lora_rank, c.v_head_dim))
        self.o = self.param("o", (H * c.v_head_dim, d))
        self._inv_freq = c.inv_freq()

    def project(self, x, positions):
        """x [T, d] (normed) at `positions` [T] -> q_nope [T, H, nope],
        q_pe [T, H, rope] (rotated), c_kv [T, rank] (normed), k_pe
        [T, rope] (rotated)."""
        c = self.c
        T, H = x.shape[0], c.num_attention_heads
        eps = c.rms_norm_eps
        cq = rms_norm_values(matmul(x, self.q_a._value),
                             self.q_a_norm._value, eps)
        q = matmul(cq, self.q_b._value).reshape(
            T, H, c.qk_nope_head_dim + c.qk_rope_head_dim)
        kv = matmul(x, self.kv_a._value)
        c_kv = rms_norm_values(kv[:, :c.kv_lora_rank],
                               self.kv_a_norm._value, eps)
        cos, sin = rotary_cos_sin(positions, self._inv_freq,
                                  c.rotary_scale())
        q_pe = apply_rotary(q[..., c.qk_nope_head_dim:], cos[:, None],
                            sin[:, None])
        k_pe = apply_rotary(kv[:, c.kv_lora_rank:], cos, sin)
        return q[..., :c.qk_nope_head_dim], q_pe, c_kv, k_pe

    def absorbed(self, x, positions):
        """(queries [T, H, W], cache rows [T, W]) of the absorbed form,
        W = `latent_width`: `absorb` and `latent_row` of what `project`
        gives."""
        q_nope, q_pe, c_kv, k_pe = self.project(x, positions)
        return self.absorb(q_nope, q_pe), self.latent_row(c_kv, k_pe)

    def _pad(self, a):
        """Zeros that take a's last axis to `latent_width` lanes
        beside the rank part."""
        c = self.c
        pad = c.latent_width - c.kv_lora_rank - c.qk_rope_head_dim
        return jnp.zeros(a.shape[:-1] + (pad,), a.dtype)

    def absorb(self, q_nope, q_pe):
        """[q_nope W_uk | q_pe | 0] [T, H, W]: the absorbed queries."""
        q_lat = _einsum("thn,hnc->thc", q_nope, self.w_uk._value)
        return jnp.concatenate([q_lat, q_pe, self._pad(q_pe)], -1)

    def latent_row(self, c_kv, k_pe):
        """[c_kv | k_pe | 0] [T, W]: the tokens' rows of the cache."""
        return jnp.concatenate([c_kv, k_pe, self._pad(k_pe)], -1)

    def output(self, o_lat):
        """o_lat [T, H, rank] (softmax-weighted c_kv) -> [T, d]."""
        return self.out(_einsum("thc,hcv->thv", o_lat, self.w_uv._value))

    def out(self, o):
        """The heads' outputs o [T, H, v] -> [T, d]."""
        return matmul(o.reshape(o.shape[0], -1), self.o._value)

    def dense(self, x, positions):
        """Causal attention of one whole sequence with k and v a head
        formed from c_kv, as published (not absorbed, no cache)."""
        c = self.c
        q_nope, q_pe, c_kv, k_pe = self.project(x, positions)
        k_nope = _einsum("sc,hnc->shn", c_kv, self.w_uk._value)
        v = _einsum("sc,hcv->shv", c_kv, self.w_uv._value)
        scores = (_einsum("thn,shn->hts", q_nope, k_nope)
                  + _einsum("thr,sr->hts", q_pe, k_pe)) \
            .astype(jnp.float32) * c.softmax_scale()
        causal = positions[None, :, None] >= positions[None, None, :]
        probs = jax.nn.softmax(jnp.where(causal, scores, -1e30), axis=-1)
        return self.out(_einsum("hts,shv->thv", probs.astype(x.dtype), v))


class DeepseekV2Block(Weights):
    def __init__(self, make, prefix, c, dense):
        super().__init__(make, prefix)
        d = c.hidden_size
        self.eps = c.rms_norm_eps
        self.attn_norm = self.param("attn_norm", (d,), "g")
        self.attn = MLAttention(make, prefix + "attn.", c)
        self.ffn_norm = self.param("ffn_norm", (d,), "g")
        if dense:
            self.mlp = GatedMLP(make, prefix + "mlp.", d,
                                c.intermediate_size)
        else:
            self.moe = ExpertLayer(make, prefix + "moe.", c)

    def norm1(self, x):
        return rms_norm_values(x, self.attn_norm._value, self.eps)

    def feed_forward(self, h, live=None, use_kernel=None):
        """h + FFN(norm2(h)) and the expert layer's counts (None for
        the dense layers)."""
        sparse = hasattr(self, "moe")
        with scope("experts" if sparse else "mlp"):
            y = rms_norm_values(h, self.ffn_norm._value, self.eps)
            if sparse:
                out, stats = self.moe.run(y, live, use_kernel)
                return h + out, stats
            return h + self.mlp.run(y), None


class _ServedBlock:
    """One block behind the engine's per-layer protocol, cache kind
    latent: the step's rows [c_kv | k_pe] go into the layer's one arena
    and the attention reads them back from it, absorbed in decode."""

    def __init__(self, block, c, first=False):
        from ..serving.kv_cache import latent_kind
        self.block, self.c, self.first = block, c, first
        self.cache_kind = latent_kind(c.latent_width)

    def _step(self, h, pages, view, positions, attend):
        block = self.block
        with scope("attn"):
            q, row = block.attn.absorbed(block.norm1(h), positions)
            lat = pages[0].at[view.blk, view.off].set(
                row.astype(pages[0].dtype))
            h = h + block.attn.output(attend(q, lat).astype(h.dtype))
        h, stats = block.feed_forward(h, view.live, view.use_kernel)
        return h, (lat, None), stats

    def decode(self, h, pages, view):
        c = self.c

        def attend(q, lat):
            return mla_paged_decode(
                q, lat, view.tables, view.ctx, c.kv_lora_rank,
                c.softmax_scale(), use_kernel=view.use_kernel)
        return self._step(h, pages, view, view.ctx, attend)

    def prefill(self, h, pages, view):
        """A chunk's rows go into the arena as decode's do. Its attention
        takes the expanded form where `mla_expanded_pays` for its real
        positions, else decode's absorbed one; one program serves every
        chunk length, so the choice is a `lax.cond` on the device, both
        branches compiled with the program. The first block counts the
        positions of a chunk that took the expanded form
        (`serving.mla_prefill_positions_expanded`)."""
        block, c = self.block, self.c
        attn = block.attn
        with scope("attn"):
            q_nope, q_pe, c_kv, k_pe = attn.project(block.norm1(h),
                                                    view.positions)
            lat = pages[0].at[view.blk, view.off].set(
                attn.latent_row(c_kv, k_pe).astype(pages[0].dtype))
            expanded = mla_expanded_pays(
                view.n_real, c.num_attention_heads, c.kv_lora_rank,
                c.qk_nope_head_dim, c.qk_rope_head_dim, c.v_head_dim,
                chunk=h.shape[0])

            def expanded_form():
                o = mla_prefill_chunk_expanded(
                    q_nope, q_pe, lat, attn.w_uk._value, attn.w_uv._value,
                    view.table_row, view.p0, c.softmax_scale(),
                    use_kernel=view.use_kernel, n_real=view.n_real)
                return attn.out(o.astype(h.dtype))

            def absorbed_form():
                o_lat = mla_prefill_chunk(
                    attn.absorb(q_nope, q_pe), lat, view.table_row, view.p0,
                    c.kv_lora_rank, c.softmax_scale(),
                    use_kernel=view.use_kernel, n_real=view.n_real)
                return attn.output(o_lat.astype(h.dtype))
            h = h + jax.lax.cond(expanded, expanded_form, absorbed_form)
        h, stats = block.feed_forward(h, view.live, view.use_kernel)
        if self.first:
            stats = dict(stats or {}, mla_prefill_positions_expanded=(
                jnp.where(expanded, view.n_real, 0)))
        return h, (lat, None), stats


class DeepseekV2ForCausalLM(Layer):
    """`make(name, shape, kind)` supplies each parameter (a checkpoint
    loader, seeded weights drawn on the device); by default they are
    random. Untied embedding and head."""

    def __init__(self, config, make=None):
        super().__init__()
        c = self.config = config
        make = make or default_make(c)
        top = Weights(make, "")
        self.embed = top.param("embed", (c.vocab_size, c.hidden_size))
        self.blocks = LayerList([
            DeepseekV2Block(make, f"blocks.{i}.", c,
                            dense=i < c.first_k_dense_replace)
            for i in range(c.num_layers)])
        self.norm = top.param("norm", (c.hidden_size,), "g")
        self.head = top.param("head", (c.hidden_size, c.vocab_size))

    def logits(self, h):
        """Final norm and the head, float32 logits."""
        hn = rms_norm_values(h, self.norm._value, self.config.rms_norm_eps)
        return jnp.dot(hn, self.head._value.astype(hn.dtype),
                       preferred_element_type=jnp.float32)

    def forward(self, input_ids):
        """Logits [b, s, V] of whole sequences, attention not absorbed.
        Inference only."""
        ids = input_ids._value if isinstance(input_ids, Tensor) \
            else jnp.asarray(input_ids)
        positions = jnp.arange(ids.shape[1], dtype=jnp.int32)

        def one(row):
            h = self.embed._value[row]
            for block in self.blocks:
                h = h + block.attn.dense(block.norm1(h), positions)
                h, _ = block.feed_forward(h)
            return self.logits(h)
        return Tensor(jnp.stack([one(row) for row in ids]))

    def served(self):
        """This model behind the serving engine's per-layer protocol."""
        return ServedDecoder(self, [_ServedBlock(b, self.config, i == 0)
                                    for i, b in enumerate(self.blocks)])
