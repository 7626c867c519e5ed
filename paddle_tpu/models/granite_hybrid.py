"""Granite 4.0-H (`granitemoehybrid`, no experts): Mamba-2 layers among
a few grouped-query attention layers, for SERVING.

The layer equations follow the published `modeling_granitemoehybrid.py`;
every norm is RMSNorm, nothing has a bias but the convolution, and there
are no positions of any kind (`position_embedding_type` "nope").

  model    h = embedding_multiplier * E[ids]; final norm;
           logits = h E^T / logits_scaling (tied).
  block    h = h + residual_multiplier * Mixer(norm1(h));
           h = h + residual_multiplier * MLP(norm2(h)), MLP gated
           (`shared_mlp`: its `input_linear` is held as its two halves).
  attention  q of `num_attention_heads` heads, k and v of
           `num_key_value_heads`; query head i reads K/V head
           i // (heads / kv heads); causal softmax of
           q.k * attention_multiplier (NOT head_dim ** -0.5).
  Mamba-2  [z | xBC | dt] = x W_in; xBC through a causal depthwise
           convolution of `mamba_d_conv` taps and silu, then split into
           x (heads of `mamba_d_head`), B and C (`mamba_d_state` each,
           one for all heads: `mamba_n_groups` 1). dt = softplus(dt +
           dt_bias), A = -exp(A_log). A head's state S in R^{P x N}:
           S_t = exp(dt A) S_{t-1} + dt x_t (x) B_t,
           y_t = S_t C_t + D x_t; y = RMSNorm(y * silu(z)) w; W_out.

What a request keeps in a Mamba-2 layer is the last `d_conv - 1` rows of
xBC (before the convolution) and S in float32: rows by request of the
serving cache (`kv_cache.state_kind`), whatever the request's length.
The state is held transposed, `[N, heads * P]` (ops/pallas_ssm.py).
Decode is one step of the recurrence a slot (`mamba2_state_step`);
prefill computes the same recurrence in the chunked form
(`mamba2_chunk_scan`) from the request's stored state, zeros where the
chunk starts at position 0, and leaves the state after the chunk's last
real token.

`GraniteHybridForCausalLM.served()` gives the serving engine its
per-layer protocol; `forward(ids)` is the same model on whole sequences
(the chunked scan from zeros, dense attention). There is no training
path: the scan has no backward.
"""
import jax
import jax.numpy as jnp

from ..core.tensor import Tensor
from ..nn import Layer, LayerList
from ..nn.functional.norm import rms_norm_values
from ..ops.pallas_decode import flash_prefill_chunk, paged_decode_attention
from ..ops.pallas_ssm import mamba2_chunk_scan, mamba2_state_step
from ..core.scope import scope
from .blocks import GatedMLP, ServedDecoder, Weights, default_make, matmul

__all__ = ["GraniteHybridConfig", "GraniteHybridForCausalLM"]


class GraniteHybridConfig:
    """The published config's names. `layer_types` is the pattern of
    "mamba" and "attention"; its length is the depth."""

    def __init__(self, vocab_size=100352, hidden_size=2048,
                 layer_types=("mamba",) * 5 + ("attention",),
                 num_attention_heads=32, num_key_value_heads=8,
                 shared_intermediate_size=8192, mamba_n_heads=64,
                 mamba_d_head=64, mamba_d_state=128, mamba_d_conv=4,
                 mamba_n_groups=1, mamba_expand=2, mamba_chunk_size=256,
                 attention_multiplier=0.015625, embedding_multiplier=12.0,
                 residual_multiplier=0.22, logits_scaling=8.0,
                 rms_norm_eps=1e-5, max_seq_len=131072,
                 initializer_range=0.1, dtype="bfloat16"):
        if mamba_n_groups != 1:
            raise ValueError("one B and one C for all heads "
                             "(mamba_n_groups 1) is what is implemented")
        if mamba_n_heads * mamba_d_head != mamba_expand * hidden_size:
            raise ValueError("mamba_n_heads * mamba_d_head must be "
                             "mamba_expand * hidden_size")
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.layer_types = tuple(layer_types)
        self.num_layers = len(self.layer_types)
        self.num_attention_heads = num_attention_heads
        self.num_key_value_heads = num_key_value_heads
        self.shared_intermediate_size = shared_intermediate_size
        self.mamba_n_heads = mamba_n_heads
        self.mamba_d_head = mamba_d_head
        self.mamba_d_state = mamba_d_state
        self.mamba_d_conv = mamba_d_conv
        self.mamba_chunk_size = mamba_chunk_size
        self.attention_multiplier = float(attention_multiplier)
        self.embedding_multiplier = float(embedding_multiplier)
        self.residual_multiplier = float(residual_multiplier)
        self.logits_scaling = float(logits_scaling)
        self.rms_norm_eps = rms_norm_eps
        self.max_seq_len = max_seq_len
        self.initializer_range = initializer_range
        self.dtype = dtype

    @property
    def head_dim(self):
        return self.hidden_size // self.num_attention_heads

    @property
    def d_inner(self):
        return self.mamba_n_heads * self.mamba_d_head

    @property
    def conv_dim(self):
        """Channels through the convolution: x, B and C."""
        return self.d_inner + 2 * self.mamba_d_state


class Attention(Weights):
    """Grouped-query attention over the paged K/V arenas, whose rows
    are `num_key_value_heads * head_dim` wide."""

    layer = "attn"      # its half of a block in a device trace

    def __init__(self, make, prefix, c):
        super().__init__(make, prefix)
        d, H = c.hidden_size, c.head_dim
        self.c = c
        self.q = self.param("q", (d, c.num_attention_heads * H))
        self.k = self.param("k", (d, c.num_key_value_heads * H))
        self.v = self.param("v", (d, c.num_key_value_heads * H))
        self.o = self.param("o", (c.num_attention_heads * H, d))

    def cache_kind(self):
        from ..serving.kv_cache import kv_kind
        return kv_kind(self.c.num_key_value_heads * self.c.head_dim)

    def _run(self, x, pages, view, attend):
        q = matmul(x, self.q._value)
        kp = pages[0].at[view.blk, view.off].set(
            matmul(x, self.k._value).astype(pages[0].dtype))
        vp = pages[1].at[view.blk, view.off].set(
            matmul(x, self.v._value).astype(pages[1].dtype))
        c = self.c
        o = attend(q, kp, vp, dict(
            use_kernel=view.use_kernel, kv_heads=c.num_key_value_heads,
            scale=c.attention_multiplier))
        return matmul(o.astype(x.dtype), self.o._value), (kp, vp)

    def decode(self, x, pages, view):
        def attend(q, kp, vp, kw):
            return paged_decode_attention(
                q[:, None], kp, vp, view.tables, view.ctx,
                self.c.num_attention_heads, **kw)[:, 0]
        return self._run(x, pages, view, attend)

    def prefill(self, x, pages, view):
        def attend(q, kp, vp, kw):
            return flash_prefill_chunk(
                q[None], kp, vp, view.table_row, view.p0,
                self.c.num_attention_heads, n_real=view.n_real, **kw)[0]
        return self._run(x, pages, view, attend)

    def dense(self, x):
        """Causal attention of one whole sequence x [T, d], no cache."""
        c = self.c
        T, N, Nk, H = x.shape[0], c.num_attention_heads, \
            c.num_key_value_heads, c.head_dim
        q = matmul(x, self.q._value).reshape(T, N, H)
        k = jnp.repeat(matmul(x, self.k._value).reshape(T, Nk, H),
                       N // Nk, axis=1)
        v = jnp.repeat(matmul(x, self.v._value).reshape(T, Nk, H),
                       N // Nk, axis=1)
        f32 = jnp.float32
        scores = jnp.einsum("tnh,snh->nts", q.astype(f32), k.astype(f32)) \
            * c.attention_multiplier
        probs = jax.nn.softmax(
            jnp.where(jnp.tril(jnp.ones((T, T), bool)), scores, -1e30), -1)
        o = jnp.einsum("nts,snh->tnh", probs.astype(x.dtype).astype(f32),
                       v.astype(f32)).astype(x.dtype)
        return matmul(o.reshape(T, N * H), self.o._value)


class Mamba2(Weights):
    """The Mamba-2 mixer over a request's row: the convolution's tail
    `[d_conv - 1, conv_dim]` in the model's dtype and the state
    `[d_state, d_inner]` in float32."""

    layer = "ssm"       # its half of a block in a device trace

    def __init__(self, make, prefix, c):
        super().__init__(make, prefix)
        d, di, H = c.hidden_size, c.d_inner, c.mamba_n_heads
        self.c = c
        self.in_proj = self.param("in_proj", (d, di + c.conv_dim + H))
        self.conv_w = self.param("conv_w", (c.mamba_d_conv, c.conv_dim),
                                 "conv")
        self.conv_b = self.param("conv_b", (c.conv_dim,), "conv")
        self.dt_bias = self.param("dt_bias", (H,), "dt_bias")
        self.A_log = self.param("A_log", (H,), "A_log")
        self.D = self.param("D", (H,), "D")
        self.norm = self.param("norm", (di,), "g")
        self.out_proj = self.param("out_proj", (di, d))

    def cache_kind(self):
        from ..serving.kv_cache import state_kind
        c = self.c
        return state_kind(
            ((c.mamba_d_conv - 1, c.conv_dim), c.dtype),
            ((c.mamba_d_state, c.d_inner), "float32"))

    # -- the pieces both paths share ----------------------------------------
    def _project(self, x):
        """x [T, d] -> z [T, d_inner], xBC [T, conv_dim], dt [T, H]
        (float32, after bias and softplus)."""
        c = self.c
        zxbcdt = matmul(x, self.in_proj._value)
        z = zxbcdt[:, :c.d_inner]
        xbc = zxbcdt[:, c.d_inner:c.d_inner + c.conv_dim]
        dt = jax.nn.softplus(
            zxbcdt[:, c.d_inner + c.conv_dim:].astype(jnp.float32)
            + self.dt_bias._value.astype(jnp.float32))
        return z, xbc, dt

    def _convolve(self, windows):
        """windows [d_conv, T, conv_dim]: tap k of every position ->
        x [T, d_inner], B and C [T, d_state], in the input's dtype."""
        c = self.c
        w = self.conv_w._value.astype(jnp.float32)
        acc = self.conv_b._value.astype(jnp.float32) + sum(
            w[k] * windows[k].astype(jnp.float32)
            for k in range(c.mamba_d_conv))
        out = jax.nn.silu(acc).astype(windows.dtype)
        return (out[:, :c.d_inner], out[:, c.d_inner:c.d_inner
                                        + c.mamba_d_state],
                out[:, c.d_inner + c.mamba_d_state:])

    def _a(self):
        return -jnp.exp(self.A_log._value.astype(jnp.float32))

    def _per_channel(self, v):
        """[T, H] -> [T, d_inner]: a head's number on each of its
        channels."""
        return jnp.repeat(v, self.c.mamba_d_head, axis=1)

    def _finish(self, y, xs, z):
        """The skip term, the gate, the norm and the output matrix;
        y [T, d_inner] float32."""
        y = y + self._per_channel(
            self.D._value.astype(jnp.float32)[None]) * xs.astype(jnp.float32)
        y = rms_norm_values(y * jax.nn.silu(z.astype(jnp.float32)),
                            self.norm._value, self.c.rms_norm_eps)
        return matmul(y.astype(xs.dtype), self.out_proj._value)

    # -- serving ------------------------------------------------------------
    def decode(self, x, pages, view):
        """One token a slot: x [S, d]."""
        tails, states = pages
        z, xbc, dt = self._project(x)
        window = jnp.concatenate([tails[view.rows], xbc[:, None]], axis=1)
        xs, b, c = self._convolve(jnp.moveaxis(window, 1, 0))
        tails = tails.at[view.rows].set(jnp.where(
            view.live[:, None, None], window[:, 1:], 0).astype(tails.dtype))
        states, y = mamba2_state_step(
            states, view.rows, view.live,
            self._per_channel(jnp.exp(dt * self._a()[None])),
            self._per_channel(dt) * xs.astype(jnp.float32), b, c,
            use_kernel=view.use_kernel)
        return self._finish(y, xs, z), (tails, states)

    def prefill(self, x, pages, view):
        """A chunk of one request: x [C, d], the first `n_real` rows
        real. Starts from the row's state, from zeros at position 0."""
        tails, states = pages
        first = view.p0 == 0
        z, xbc, dt = self._project(x)
        tail = jnp.where(first, 0, tails[view.row]).astype(xbc.dtype)
        y, state, new_tail = self._scan(
            z, xbc, dt * view.live[:, None], tail,
            jnp.where(first, 0.0, states[view.row]), view.n_real,
            view.use_kernel)
        return y, (tails.at[view.row].set(new_tail.astype(tails.dtype)),
                   states.at[view.row].set(state))

    def _scan(self, z, xbc, dt, tail, state, n_real, use_kernel=None):
        """The chunk through convolution and recurrence -> (the mixer's
        output [C, d], the state after it, the convolution's tail at
        row `n_real`)."""
        c = self.c
        T, taps = xbc.shape[0], c.mamba_d_conv
        seq = jnp.concatenate([tail, xbc], axis=0)      # [taps - 1 + T, .]
        xs, b, cc = self._convolve(jnp.stack(
            [seq[k:k + T] for k in range(taps)]))
        y, state = mamba2_chunk_scan(
            xs, dt, self._a(), b, cc, state, piece=c.mamba_chunk_size,
            use_kernel=use_kernel)
        # the last taps - 1 real rows of xBC: rows n_real .. of `seq`
        new_tail = jax.lax.dynamic_slice(
            seq, (n_real, 0), (taps - 1, seq.shape[1]))
        return self._finish(y, xs, z), state, new_tail

    def dense(self, x):
        """One whole sequence x [T, d] from an empty state."""
        c = self.c
        z, xbc, dt = self._project(x)
        return self._scan(
            z, xbc, dt, jnp.zeros((c.mamba_d_conv - 1, c.conv_dim),
                                  xbc.dtype),
            jnp.zeros((c.mamba_d_state, c.d_inner), jnp.float32),
            x.shape[0])[0]


class GraniteHybridBlock(Weights):
    def __init__(self, make, prefix, c, kind):
        super().__init__(make, prefix)
        d = c.hidden_size
        self.c = c
        self.norm1 = self.param("norm1", (d,), "g")
        if kind == "attention":
            self.mixer = Attention(make, prefix + "attn.", c)
        elif kind == "mamba":
            self.mixer = Mamba2(make, prefix + "mamba.", c)
        else:
            raise ValueError(f"layer type {kind!r}")
        self.norm2 = self.param("norm2", (d,), "g")
        self.mlp = GatedMLP(make, prefix + "mlp.", d,
                            c.shared_intermediate_size)

    def run(self, h, mix):
        """The block with `mix(x)` for the mixer: `mix` returns the
        mixer's output and whatever else, which is handed back."""
        c = self.c
        with scope(self.mixer.layer):
            out, rest = mix(rms_norm_values(h, self.norm1._value,
                                            c.rms_norm_eps))
            h = h + (c.residual_multiplier * out).astype(h.dtype)
        with scope("mlp"):
            y = self.mlp.run(rms_norm_values(h, self.norm2._value,
                                             c.rms_norm_eps))
            return h + (c.residual_multiplier * y).astype(h.dtype), rest


class _ServedBlock:
    """One block behind the engine's per-layer protocol: cache kind
    "kv" (grouped-query rows) in an attention layer, "state" (rows by
    request) in a Mamba-2 layer."""

    def __init__(self, block):
        self.block = block
        self.cache_kind = block.mixer.cache_kind()

    def decode(self, h, pages, view):
        h, pages = self.block.run(
            h, lambda x: self.block.mixer.decode(x, pages, view))
        return h, pages, None

    def prefill(self, h, pages, view):
        h, pages = self.block.run(
            h, lambda x: self.block.mixer.prefill(x, pages, view))
        return h, pages, None


class ServedGraniteHybrid(ServedDecoder):
    """`ServedDecoder` with the embedding's multiplier."""

    def embed(self, ids, positions):
        return self.model.embedded(ids.reshape(-1))


def _default_make(config):
    """`blocks.default_make`, and the Mamba-2 leaves as the paper's code
    draws them: A uniform in [1, 16], dt log-uniform in [0.001, 0.1]
    through the inverse softplus, D = 1, the convolution uniform in
    +-1/sqrt(d_conv)."""
    from ..core.random import default_generator
    plain = default_make(config)
    dtype = jnp.dtype(config.dtype)

    def make(name, shape, kind):
        if kind in ("w", "g"):
            return plain(name, shape, kind)
        if kind == "D":
            return jnp.ones(shape, dtype)
        u = jax.random.uniform(default_generator().split(), shape,
                               jnp.float32)
        if kind == "A_log":
            x = jnp.log(1.0 + 15.0 * u)
        elif kind == "dt_bias":
            dt = jnp.exp(u * (jnp.log(0.1) - jnp.log(0.001))
                         + jnp.log(0.001))
            x = dt + jnp.log(-jnp.expm1(-dt))
        else:
            x = (2.0 * u - 1.0) * config.mamba_d_conv ** -0.5
        return x.astype(dtype)
    return make


class GraniteHybridForCausalLM(Layer):
    """`make(name, shape, kind)` supplies each parameter (a checkpoint
    loader, seeded weights drawn on the device): kind "w" a matrix, "g"
    a gain, "conv" the convolution's taps and bias, "dt_bias", "A_log",
    "D"; by default they are random. Embedding and head are one
    matrix."""

    def __init__(self, config, make=None):
        super().__init__()
        c = self.config = config
        make = make or _default_make(c)
        top = Weights(make, "")
        self.embed = top.param("embed", (c.vocab_size, c.hidden_size))
        self.blocks = LayerList([
            GraniteHybridBlock(make, f"blocks.{i}.", c, kind)
            for i, kind in enumerate(c.layer_types)])
        self.norm = top.param("norm", (c.hidden_size,), "g")

    def num_parameters(self):
        return sum(int(p._value.size) for p in self.parameters())

    def embedded(self, ids):
        e = self.embed._value[ids]
        return (self.config.embedding_multiplier * e).astype(e.dtype)

    def logits(self, h):
        """Final norm and the tied head, float32 logits."""
        c = self.config
        hn = rms_norm_values(h, self.norm._value, c.rms_norm_eps)
        return jnp.einsum("...d,vd->...v", hn,
                          self.embed._value.astype(hn.dtype),
                          preferred_element_type=jnp.float32) \
            / c.logits_scaling

    def forward(self, input_ids):
        """Logits [b, s, V] of whole sequences. Inference only."""
        ids = input_ids._value if isinstance(input_ids, Tensor) \
            else jnp.asarray(input_ids)

        def one(row):
            h = self.embedded(row)
            for block in self.blocks:
                h, _ = block.run(h, lambda x: (block.mixer.dense(x), None))
            return self.logits(h)
        return Tensor(jnp.stack([one(row) for row in ids]))

    def served(self):
        """This model behind the serving engine's per-layer protocol."""
        return ServedGraniteHybrid(
            self, [_ServedBlock(b) for b in self.blocks])
