"""GPT — the flagship pretraining model (capability config 5: GPT-3 1.3B/13B
3D-hybrid).

Reference analog: the fleet GPT examples driven by
`python/paddle/distributed/fleet/meta_parallel/parallel_layers/mp_layers.py`
(VocabParallelEmbedding/ColumnParallelLinear/RowParallelLinear) and
`pp_layers.py` (PipelineLayer). TPU-native design: the SAME model code serves
single-chip and 3D-parallel execution — parallelism is expressed as
per-parameter `PartitionSpec` tags (`mesh_axes` attribute) plus activation
sharding constraints, and GSPMD inserts the collectives the reference's
meta-optimizers used to splice in by program rewriting.

Sharding plan (Megatron-style, rides ICI):
  wte [vocab, d]            -> ("mp", None)       vocab-parallel embedding
  qkv/fc1 weight [d, 3d|4d] -> (None, "mp")       column-parallel
  proj/fc2 weight [*, d]    -> ("mp", None)       row-parallel
  activations [b, s, d]     -> ("dp", "sp", None) batch + sequence sharded
"""
import math

import numpy as np
import jax.numpy as jnp

from ..core.tensor import Tensor, apply
from ..nn import Layer, LayerList, Linear, LayerNorm, Dropout, Embedding
from ..nn import functional as F
from ..nn.initializer import Normal, Constant
from ..tensor.manipulation import reshape, transpose
from ..ops.attention import flash_attention
from ..core.scope import scope


class GPTConfig:
    def __init__(self, vocab_size=50304, hidden_size=768, num_layers=12,
                 num_heads=12, ffn_hidden_size=None, max_seq_len=1024,
                 dropout=0.0, attn_dropout=0.0, initializer_range=0.02,
                 use_flash_attention=True, sequence_parallel=None,
                 dtype="float32", remat=False):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_layers = num_layers
        self.num_heads = num_heads
        self.ffn_hidden_size = ffn_hidden_size or 4 * hidden_size
        self.max_seq_len = max_seq_len
        self.dropout = dropout
        self.attn_dropout = attn_dropout
        self.initializer_range = initializer_range
        self.use_flash_attention = use_flash_attention
        # None | "ring" | "ulysses": context parallelism over the sp axis
        self.sequence_parallel = sequence_parallel
        self.dtype = dtype
        # per-block rematerialization (reference RecomputeOptimizer /
        # recompute_interval): store only block INPUTS for the backward
        self.remat = remat

    @staticmethod
    def _preset(defaults, kw):
        return GPTConfig(**{**defaults, **kw})

    @staticmethod
    def gpt3_125m(**kw):
        return GPTConfig._preset(
            dict(hidden_size=768, num_layers=12, num_heads=12), kw)

    @staticmethod
    def gpt3_350m(**kw):
        return GPTConfig._preset(
            dict(hidden_size=1024, num_layers=24, num_heads=16), kw)

    @staticmethod
    def gpt3_1_3b(**kw):
        return GPTConfig._preset(
            dict(hidden_size=2048, num_layers=24, num_heads=16), kw)

    @staticmethod
    def gpt3_13b(**kw):
        return GPTConfig._preset(
            dict(hidden_size=5120, num_layers=40, num_heads=40), kw)

    @staticmethod
    def gpt3_1_3b_128k(**kw):
        """>=128k-context training preset: ring attention over the sp
        mesh axis (the production long-context path — HBM per chip is
        O(seq/sp)), per-block remat, flash attention for the local
        blocks. At this sequence length the flash backward resolves to
        block_q=512/block_k=1024 (ops/pallas_attention._resolve_blocks
        for sq > 8192) — the r=2 triangle-grid decode covered by the
        tests/test_pallas.py rect-block parity tests."""
        return GPTConfig._preset(
            dict(hidden_size=2048, num_layers=24, num_heads=16,
                 max_seq_len=131072, sequence_parallel="ring",
                 remat=True), kw)


def _tag(param, axes):
    """Attach a GSPMD partition tag consumed by distributed.shard_model /
    ShardedTrainStep."""
    if param is not None:
        param.mesh_axes = axes
    return param


class GPTAttention(Layer):
    def __init__(self, config):
        super().__init__()
        c = config
        self.num_heads = c.num_heads
        self.head_dim = c.hidden_size // c.num_heads
        self.hidden_size = c.hidden_size
        init = Normal(0.0, c.initializer_range)
        self.qkv_proj = Linear(c.hidden_size, 3 * c.hidden_size,
                               weight_attr=init)
        self.out_proj = Linear(c.hidden_size, c.hidden_size, weight_attr=init)
        _tag(self.qkv_proj.weight, (None, "mp"))
        _tag(self.qkv_proj.bias, ("mp",))
        _tag(self.out_proj.weight, ("mp", None))
        self.attn_dropout = c.attn_dropout
        self.use_flash = c.use_flash_attention
        self.sequence_parallel = c.sequence_parallel
        if c.sequence_parallel and c.attn_dropout > 0:
            import warnings
            warnings.warn(
                "attn_dropout is not applied on the sequence-parallel "
                "attention path (ring/ulysses); set attn_dropout=0 or "
                "sequence_parallel=None for identical regularization")

    def _sp_active(self):
        if not self.sequence_parallel:
            return False
        from ..distributed import env as dist_env
        mesh = dist_env.current_mesh()
        return (mesh is not None and "sp" in mesh.axis_names and
                mesh.shape["sp"] > 1)

    def project_qkv(self, x):
        """Shared q/k/v projection: [b, s, d] -> three [b, s, n, h]
        Tensors. Single source of truth for the qkv reshape/split so
        the serving engine's paged-cache step (paddle_tpu/serving)
        computes bit-identical projections to this module's forward."""
        b, s = x.shape[0], x.shape[1]
        qkv = self.qkv_proj(x)
        qkv = reshape(qkv, [b, s, 3, self.num_heads, self.head_dim])
        return qkv.unbind(axis=2)

    def forward(self, x, cache=None, offset=None):
        """cache: optional (k_buf, v_buf) Tensors of FIXED shape —
        FLAT [b, max_len, n*h] on the fused pallas decode path, 4-D
        [b, max_len, n, h] on the composed path (build them with
        GPTModel.init_cache, which owns the layout decision); offset:
        scalar int Tensor/int — how many cache positions are already
        filled. Fixed-size buffers + `lax.dynamic_update_slice` keep
        decode shapes static so XLA compiles the step once (the TPU
        answer to the reference's growing-concat decode caches,
        `fluid/layers/rnn.py:1583` dynamic_decode)."""
        b, s = x.shape[0], x.shape[1]
        q, k, v = self.project_qkv(x)
        if cache is not None:
            off = offset if isinstance(offset, Tensor) else \
                Tensor(jnp.asarray(0 if offset is None else offset,
                                   jnp.int32))
            out, k_buf, v_buf = apply(_cached_attention, q, k, v,
                                      cache[0], cache[1], off)
            out = reshape(out, [b, s, self.hidden_size])
            return self.out_proj(out), (k_buf, v_buf)
        if self._sp_active():
            from ..ops.ring_attention import ring_attention, ulysses_attention
            attn = ring_attention if self.sequence_parallel == "ring" \
                else ulysses_attention
            out = attn(q, k, v, causal=True)
        else:
            out = flash_attention(q, k, v, dropout=self.attn_dropout,
                                  causal=True, training=self.training,
                                  use_pallas=None if self.use_flash
                                  else False)
        out = reshape(out, [b, s, self.hidden_size])
        return self.out_proj(out)


def _cached_attention(q, k_new, v_new, k_buf, v_buf, off):
    """Incremental-decode attention on raw values: write k/v at `off`, attend
    q (s tokens at positions off..off+s) over the valid prefix via masking.
    O(max_len) per step — the standard KV-cache decode cost. The cache
    layout (see init_cache) picks the path: FLAT [b, L, n*h] buffers run
    the fused pallas kernel for q_len==1 steps; 4-D buffers run the
    composed einsums. Neither path reshapes the carried buffers."""
    import jax
    b, s, n, h = q.shape
    L = k_buf.shape[1]
    if k_buf.ndim == 3:
        k_buf = jax.lax.dynamic_update_slice(
            k_buf, k_new.reshape(b, s, n * h).astype(k_buf.dtype),
            (0, off, 0))
        v_buf = jax.lax.dynamic_update_slice(
            v_buf, v_new.reshape(b, s, n * h).astype(v_buf.dtype),
            (0, off, 0))
        if s == 1:
            # one fused kernel for the whole per-layer decode attention
            # (ops/pallas_decode.py): the einsum+mask+softmax+einsum
            # chain is the kernel-count bottleneck at serving batches
            from ..ops.pallas_decode import decode_attention
            out = decode_attention(q.reshape(b, 1, n * h), k_buf, v_buf,
                                   off, n).astype(q.dtype)
            return out.reshape(b, 1, n, h), k_buf, v_buf
        # prefill (s > 1) happens once per sequence: the reshape cost is
        # paid once, not per generated token
        k4 = k_buf.reshape(b, L, n, h)
        v4 = v_buf.reshape(b, L, n, h)
    else:
        k_buf = jax.lax.dynamic_update_slice(
            k_buf, k_new.astype(k_buf.dtype), (0, off, 0, 0))
        v_buf = jax.lax.dynamic_update_slice(
            v_buf, v_new.astype(v_buf.dtype), (0, off, 0, 0))
        k4, v4 = k_buf, v_buf
    scale = 1.0 / math.sqrt(h)
    logits = jnp.einsum("bqnh,bknh->bnqk", q, k4.astype(q.dtype),
                        preferred_element_type=jnp.float32) * scale
    key_pos = jnp.arange(L, dtype=jnp.int32)[None, None, None, :]
    q_pos = (off + jnp.arange(s, dtype=jnp.int32))[None, None, :, None]
    logits = jnp.where(key_pos <= q_pos, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    out = jnp.einsum("bnqk,bknh->bqnh", probs, v4.astype(q.dtype))
    return out, k_buf, v_buf


class GPTMLP(Layer):
    def __init__(self, config):
        super().__init__()
        c = config
        init = Normal(0.0, c.initializer_range)
        out_init = Normal(0.0, c.initializer_range / math.sqrt(2 * c.num_layers))
        self.fc1 = Linear(c.hidden_size, c.ffn_hidden_size, weight_attr=init)
        self.fc2 = Linear(c.ffn_hidden_size, c.hidden_size,
                          weight_attr=out_init)
        _tag(self.fc1.weight, (None, "mp"))
        _tag(self.fc1.bias, ("mp",))
        _tag(self.fc2.weight, ("mp", None))

    def forward(self, x):
        return self.fc2(F.gelu(self.fc1(x), approximate=True))


class GPTBlock(Layer):
    # FFN factory hook: the MoE family (paddle_tpu.moe.GPTMoEBlock)
    # swaps the dense MLP for the routed MoEFFN here instead of
    # re-stating the ln/attn/dropout plumbing
    mlp_cls = GPTMLP
    # the layer the second half of the block is in a device trace
    # (`telemetry.scope`); a norm and a residual add belong to the half
    # they feed
    ffn_scope = "mlp"

    def __init__(self, config):
        super().__init__()
        self.ln1 = LayerNorm(config.hidden_size)
        self.attn = GPTAttention(config)
        self.ln2 = LayerNorm(config.hidden_size)
        self.mlp = self.mlp_cls(config)
        self.dropout = Dropout(config.dropout)

    def forward(self, x, cache=None, offset=None):
        if cache is not None:
            with scope("attn"):
                a, new_cache = self.attn(self.ln1(x), cache=cache,
                                         offset=offset)
            return self.ffn_half(x, a), new_cache
        with scope("attn"):
            a = self.attn(self.ln1(x))
        return self.ffn_half(x, a)

    def ffn_half(self, x, a):
        """The block from its attention's output `a` on: x + a, the
        MLP of ln2 of that, and its residual."""
        with scope(self.ffn_scope):
            y, h = self._add_ln2(x, self.dropout(a))
            return h + self.dropout(self.mlp(y))

    def _add_ln2(self, x, delta):
        """The residual-add + ln2 site in one op: (ln2(x+delta), x+delta)."""
        return F.fused_add_layer_norm(x, delta, self.ln2.weight,
                                      self.ln2.bias, self.ln2._epsilon)


class GPTModel(Layer):
    # block factory hook: model families that swap the block (the MoE
    # family replaces the dense FFN, paddle_tpu.moe.GPTMoEModel) override
    # this instead of re-stating the embedding/ln_f plumbing
    block_cls = GPTBlock

    def __init__(self, config):
        super().__init__()
        self.config = config
        c = config
        init = Normal(0.0, c.initializer_range)
        self.wte = Embedding(c.vocab_size, c.hidden_size, weight_attr=init)
        self.wpe = Embedding(c.max_seq_len, c.hidden_size, weight_attr=init)
        _tag(self.wte.weight, ("mp", None))  # vocab-parallel
        self.drop = Dropout(c.dropout)
        self.blocks = LayerList([self.block_cls(c)
                                 for _ in range(c.num_layers)])
        self.ln_f = LayerNorm(c.hidden_size)

    def init_cache(self, batch_size, max_len, dtype=None):
        """Fixed-shape KV buffers, one (k, v) pair per block. Layout
        follows the decode-attention path: FLAT [b, max_len, n*h] when
        the fused pallas kernel will run (it needs reshape-free access
        to the loop-carried buffers — a reshaped view fed to
        pallas_call copies the whole cache per layer per step), 4-D
        [b, max_len, n, h] for the composed einsum path (which equally
        must not reshape per step). _cached_attention branches on
        ndim."""
        import jax as _jax
        from ..flags import get_flag
        from ..ops.pallas_decode import decode_attention_supported
        c = self.config
        dt = dtype or c.dtype
        flat = (get_flag("use_pallas_decode_attention")
                and _jax.default_backend() == "tpu"
                and decode_attention_supported(
                    max_len, c.hidden_size, c.num_heads,
                    jnp.dtype(dt).itemsize))
        if flat:
            shape = (batch_size, max_len, c.hidden_size)
        else:
            shape = (batch_size, max_len, c.num_heads,
                     c.hidden_size // c.num_heads)
        return [(Tensor(jnp.zeros(shape, dt)), Tensor(jnp.zeros(shape, dt)))
                for _ in self.blocks]

    def forward(self, input_ids, position_ids=None, caches=None, offset=None):
        b, s = input_ids.shape[0], input_ids.shape[1]
        if position_ids is None:
            if caches is not None and offset is not None:
                off = offset if isinstance(offset, Tensor) else \
                    Tensor(jnp.asarray(offset, jnp.int32))
                position_ids = apply(
                    lambda o: (o + jnp.arange(s, dtype=jnp.int32))[None, :],
                    off)
            else:
                position_ids = Tensor(jnp.arange(s, dtype=jnp.int32)[None, :])
        with scope("embed"):
            h = self.wte(input_ids) + self.wpe(position_ids)
            h = self.drop(h)
        h = _shard_activation(h)
        if caches is not None:
            new_caches = []
            for block, cache in zip(self.blocks, caches):
                h, nc = block(h, cache=cache, offset=offset)
                new_caches.append(nc)
            return self.final_norm(h), new_caches
        if self.config.remat:
            # jax.checkpoint per block: the backward recomputes the
            # block from its stored input — O(L) activation memory
            # (reference `backward.py:749` checkpoint segments /
            # `fleet/utils/recompute.py:63`)
            from ..distributed.recompute import recompute
            for block in self.blocks:
                h = recompute(block, h)
                h = _shard_activation(h)
            return self.final_norm(h)
        for block in self.blocks:
            h = block(h)
            h = _shard_activation(h)
        return self.final_norm(h)

    def final_norm(self, h):
        with scope("head"):
            return self.ln_f(h)


def _shard_activation(h):
    """Apply a [dp, sp, None] sharding constraint when a mesh is active —
    the GSPMD hook that keeps activations sequence-sharded between blocks."""
    from ..distributed import env as dist_env
    mesh = dist_env.current_mesh()
    if mesh is None:
        return h
    from jax.sharding import PartitionSpec as P
    import jax
    axes = [None, None, None]
    if "dp" in mesh.axis_names and mesh.shape["dp"] > 1:
        axes[0] = "dp"
    if "sp" in mesh.axis_names and mesh.shape["sp"] > 1:
        axes[1] = "sp"
    spec = P(*axes)
    return apply(lambda v: jax.lax.with_sharding_constraint(
        v, jax.sharding.NamedSharding(mesh, spec)), h)


class GPTForPretraining(Layer):
    """LM head tied to wte (the shared-embedding pattern whose cross-stage
    allreduce the reference handles at `pipeline_parallel.py:162`; with GSPMD
    the tied weight is just referenced twice and the compiler handles it)."""

    # model factory hook (see GPTModel.block_cls)
    model_cls = GPTModel

    def __init__(self, config):
        super().__init__()
        self.gpt = self.model_cls(config)
        self.config = config

    def forward(self, input_ids, position_ids=None, caches=None, offset=None):
        if caches is not None:
            h, new_caches = self.gpt(input_ids, position_ids, caches=caches,
                                     offset=offset)
            return self.lm_head(h), new_caches
        h = self.gpt(input_ids, position_ids)
        return self.lm_head(h)

    def lm_head(self, h):
        """Vocab projection of hidden states [b, s, d] over the tied
        wte table (quantized or not) -> logits Tensor. Factored out of
        forward so the serving engine's paged decode step projects
        logits through EXACTLY this code path (including the wo8
        int8-matvec dispatch) instead of a copy that could drift."""
        wte = self.gpt.wte
        if hasattr(wte, "wq"):
            # weight-only-int8 tied table (quant/wo8.py): the table is
            # row-padded to the pallas head block; logits slice back to
            # the true vocab
            V = wte.num_embeddings
            from ..core import autograd as _ag
            # the pallas kernel has no vjp: only take it when no grad
            # can flow (generate runs under no_grad; tuning paths with
            # a live tape keep the differentiable einsum)
            grad_live = _ag.grad_enabled() and not h.stop_gradient

            def head_q(hh, wq, ws):
                from ..amp import amp_state
                from ..ops.pallas_int8 import int8_matvec_preferred
                b, s, d = hh.shape
                if int8_matvec_preferred(b * s) and not grad_live:
                    # decode-sized rows: pallas int8 matvec streams the
                    # int8 tiles into VMEM (XLA won't fuse the
                    # int8->bf16 convert into a dot operand and instead
                    # materializes a dequantized [V, H] copy — measured
                    # slower than bf16 weights; ops/pallas_int8.py)
                    from ..ops.pallas_int8 import int8_matvec
                    out = int8_matvec(hh.reshape(b * s, d), wq, ws)
                    out = out.reshape(b, s, -1)[..., :V]
                    return out.astype(jnp.bfloat16) \
                        if amp_state().enabled else out
                cdt = jnp.bfloat16 if amp_state().enabled else hh.dtype
                out = jnp.einsum("bsd,vd->bsv", hh.astype(cdt),
                                 wq.astype(cdt),
                                 preferred_element_type=jnp.float32)
                out = out * ws.astype(jnp.float32)[None, None, :]
                out = out[..., :V]
                return out.astype(cdt) if amp_state().enabled else out
            with scope("head"):
                return apply(head_q, h, wte.wq, wte.w_scale)
        w = wte.weight
        from ..amp import maybe_cast_to_compute as _amp

        def head(hh, ww):
            # honor the AMP policy like F.linear does: the vocab projection
            # is the single largest matmul and must hit the MXU in bf16.
            # Accumulate in f32 but EMIT logits in the compute dtype — an
            # f32 [B,S,V] logits tensor is 3.3GB/write at 125M-bench scale
            # and every CE pass re-reads it (measured ~10GB/step of the
            # train step's HBM traffic); CE accumulates its log-sum-exp in
            # f32 regardless (amp black list), so bf16 logits cost ~1e-3
            # loss noise for ~2x less head+CE traffic
            hh, ww = _amp(hh, "matmul"), _amp(ww, "matmul")
            out = jnp.einsum("bsd,vd->bsv", hh, ww,
                             preferred_element_type=jnp.float32)
            # compute-dtype logits ONLY under amp (where CE's f32-
            # accumulating LSE is active); otherwise keep the f32
            # accumulator output so a hand-bf16 model still gets f32 CE
            from ..amp import amp_state
            return out.astype(hh.dtype) if amp_state().enabled else out
        with scope("head"):
            return apply(head, h, w)

    def served(self):
        """This model behind the serving engine's per-layer protocol."""
        return ServedGPT(self)

    def generate(self, input_ids, max_new_tokens=32, decode_strategy="greedy",
                 top_k=0, top_p=1.0, temperature=1.0, num_beams=1,
                 length_penalty=0.0, eos_token_id=None, pad_token_id=0,
                 seed=None, dtype="bfloat16"):
        """Autoregressive decoding with a static KV cache, compiled to a
        single XLA program (prefill + `lax.while_loop` decode). Analog of
        the reference's dynamic_decode/BeamSearchDecoder
        (`fluid/layers/rnn.py:866,1583`, `operators/beam_search_op.cc:1`).

        decode_strategy: "greedy" | "sampling" (top_k/top_p/temperature) |
        "beam_search" (num_beams, length_penalty).
        dtype: decode compute dtype ("bfloat16" default — ~2x tokens/sec,
        weight-bandwidth bound; dtype=None decodes in the params' dtype).
        Returns (ids Tensor [b, prompt+max_new], scores Tensor [b]).
        """
        from ..generation import run_generate
        return run_generate(
            self, input_ids, max_new_tokens=max_new_tokens,
            decode_strategy=decode_strategy, top_k=top_k, top_p=top_p,
            temperature=temperature, num_beams=num_beams,
            length_penalty=length_penalty, eos_token_id=eos_token_id,
            pad_token_id=pad_token_id, seed=seed, dtype=dtype)

    def loss(self, input_ids, labels, loss_mask=None):
        from ..flags import get_flag
        if get_flag("use_fused_ce"):
            # fused head+CE: the [B*S, V] logits tensor never exists —
            # measured ~16 GB/step of vocab-tensor HBM traffic on the
            # 125M bench collapses to chunk-sized working sets
            from ..ops.fused_ce import fused_linear_cross_entropy
            h = self.gpt(input_ids)
            w = self.gpt.wte.weight
            d = h.shape[-1]
            lbl = labels._value if isinstance(labels, Tensor) else \
                jnp.asarray(np.asarray(labels))
            flat_lbl = lbl.reshape(-1)

            from ..amp import maybe_cast_to_compute as _amp

            def fn(hh, ww):
                # same AMP policy as forward()'s head: the chunk dots must
                # run bf16 on the MXU; w stays full precision (the op
                # casts per chunk and returns f32-accumulated dW)
                hh = _amp(hh, "matmul")
                return fused_linear_cross_entropy(
                    hh.reshape(-1, d), ww, flat_lbl)

            # the head's product is inside the fused op: all `loss`
            with scope("loss"):
                return _reduce_loss(apply(fn, h, w), loss_mask)
        logits = self(input_ids)
        with scope("loss"):
            vocab = logits.shape[-1]
            flat_logits = reshape(logits, [-1, vocab])
            flat_labels = reshape(labels, [-1])
            return _reduce_loss(
                F.cross_entropy(flat_logits, flat_labels, reduction="none"),
                loss_mask)


def _reduce_loss(losses, loss_mask):
    if loss_mask is not None:
        m = reshape(loss_mask, [-1])
        return (losses * m).sum() / m.sum()
    return losses.mean()


class _ServedGPTBlock:
    """One GPTBlock behind the serving engine's per-layer protocol
    (serving/served.py), cache kind full K/V: the exact cache-branch
    math of GPTBlock.forward, with K/V written into the paged arenas
    and attention read from them."""

    def __init__(self, block, hidden, n_heads):
        from ..serving.kv_cache import kv_kind
        self.block, self.hidden, self.n_heads = block, hidden, n_heads
        self.cache_kind = kv_kind(hidden)

    def _step(self, h, pages, view, attend):
        block, nh = self.block, self.hidden
        with scope("attn"):
            y = block.ln1(h)
            q, k, v = block.attn.project_qkv(y)
            rows = view.blk.shape[0]
            kp = pages[0].at[view.blk, view.off].set(
                k._value.reshape(rows, nh).astype(pages[0].dtype))
            vp = pages[1].at[view.blk, view.off].set(
                v._value.reshape(rows, nh).astype(pages[1].dtype))
            out = attend(q._value, kp, vp)
            a = block.attn.out_proj(Tensor(out))
        return block.ffn_half(h, a), (kp, vp), None

    def decode(self, h, pages, view):
        from ..ops.pallas_decode import paged_decode_attention

        def attend(qv, kp, vp):
            return paged_decode_attention(
                qv.reshape(-1, 1, self.hidden), kp, vp, view.tables,
                view.ctx, self.n_heads, use_kernel=view.use_kernel)
        return self._step(h, pages, view, attend)

    def prefill(self, h, pages, view):
        from ..ops.pallas_decode import flash_prefill_chunk

        def attend(qv, kp, vp):
            # flash chunked prefill over the paged arena: the chunk's
            # queries attend to cached blocks via the block table with
            # in-kernel online softmax (TPU), never materializing the
            # full [chunk, ctx] score matrix; the gather+dense fallback
            # reproduces _cached_attention's composed einsum math
            # exactly, so CPU serving stays bit-identical to
            # run_generate
            return flash_prefill_chunk(
                qv.reshape(1, -1, self.hidden), kp, vp, view.table_row,
                view.p0, self.n_heads, use_kernel=view.use_kernel,
                n_real=view.n_real)
        return self._step(h, pages, view, attend)


class ServedGPT:
    """`GPTForPretraining` (quantized or not) as the serving engine
    reads a model: learned positions, one full-K/V layer a block, the
    tied head."""

    def __init__(self, model):
        c = model.config
        self.model, self.core = model, model.gpt
        self.max_seq_len, self.dtype = c.max_seq_len, c.dtype
        self.layers = [_ServedGPTBlock(b, c.hidden_size, c.num_heads)
                       for b in model.gpt.blocks]

    def embed(self, ids, positions):
        core = self.core
        return core.drop(core.wte(Tensor(ids)) + core.wpe(Tensor(positions)))

    def head(self, h, at=None):
        import jax
        hf = self.core.ln_f(h)
        if at is not None:
            hf = Tensor(jax.lax.dynamic_slice(
                hf._value, (0, at, 0), (hf.shape[0], 1, hf.shape[-1])))
        return self.model.lm_head(hf)._value


def gpt_tiny_config():
    """Small config for tests/dryrun."""
    return GPTConfig(vocab_size=256, hidden_size=64, num_layers=2,
                     num_heads=4, max_seq_len=128, dropout=0.0)
