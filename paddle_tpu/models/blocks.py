"""What the served decoder models share (`deepseek_v2`, `granite_hybrid`,
`exaone_moe`, `qwen3_next`): parameters handed over leaf by leaf, the
matmul with a float32 sum, the gated MLP, the expert layer around a
model's own router, and the head behind the serving engine's protocol.
RMSNorm is `nn.functional.norm.rms_norm_values`; Qwen3-Next's two others
are here.
"""
import jax
import jax.numpy as jnp

from ..core.tensor import Parameter
from ..moe.serving import held_expert_ffn
from ..nn import Layer
from ..core.scope import scope

__all__ = ["GatedMLP", "HeldExperts", "ServedDecoder", "Weights",
           "default_make", "gated_rms_norm", "matmul",
           "zero_centred_rms_norm"]


class Weights(Layer):
    """A layer whose parameters come from `make(name, shape, kind)`:
    kind "w" a matrix, "g" a norm's gain (a model may add kinds of its
    own)."""

    def __init__(self, make, prefix):
        super().__init__()
        self._make, self._prefix = make, prefix

    def param(self, name, shape, kind="w"):
        return Parameter(self._make(self._prefix + name, tuple(shape), kind),
                         trainable=False)


def matmul(x, w):
    return jnp.dot(x, w.astype(x.dtype), preferred_element_type=jnp.float32) \
        .astype(x.dtype)


def zero_centred_rms_norm(x, w, eps):
    """RMSNorm whose gain is 1 + w (Qwen3-Next's: w starts at 0), all of
    it in float32, the input's dtype out."""
    f = x.astype(jnp.float32)
    f = f * jax.lax.rsqrt(jnp.mean(f * f, axis=-1, keepdims=True) + eps)
    return (f * (1.0 + w.astype(jnp.float32))).astype(x.dtype)


def gated_rms_norm(x, gate, w, eps):
    """w * RMSNorm(x), THEN times silu(gate) (Qwen3-Next's gated norm;
    Mamba-2's gates first and norms after): the norm's sums in float32
    and its result in the input's dtype before the gain, the gate in
    float32."""
    f = x.astype(jnp.float32)
    normed = (f * jax.lax.rsqrt(jnp.mean(f * f, axis=-1, keepdims=True)
                                + eps)).astype(x.dtype) * w.astype(x.dtype)
    return (normed.astype(jnp.float32)
            * jax.nn.silu(gate.astype(jnp.float32))).astype(x.dtype)


class GatedMLP(Weights):
    """down(silu(gate(x)) * up(x))."""

    def __init__(self, make, prefix, d, width):
        super().__init__(make, prefix)
        self.gate = self.param("gate", (d, width))
        self.up = self.param("up", (d, width))
        self.down = self.param("down", (width, d))

    def run(self, x):
        with scope("mlp"):
            g = matmul(x, self.gate._value)
            return matmul(jax.nn.silu(g) * matmul(x, self.up._value),
                          self.down._value)


class HeldExperts(Weights):
    """The shared experts (one gated MLP of their summed width) plus
    this model's share of the routed experts, `self.c.held = (first,
    count)` of its config. A subclass sets `self.c`, makes the router's
    parameters (`self.router` [d, E]: a column an expert it scores),
    then `build_experts`, and brings `route(x)` -> (weights [T, k],
    experts [T, k])."""

    def build_experts(self, d, f, n_shared):
        count = self.c.held[1]
        self.shared = GatedMLP(self._make, self._prefix + "shared.", d,
                               f * n_shared)
        self.experts_gate = self.param("experts_gate", (count, d, f))
        self.experts_up = self.param("experts_up", (count, d, f))
        self.experts_down = self.param("experts_down", (count, f, d))

    def run(self, x, live=None, use_kernel=None):
        """(shared(x) + the held experts' weighted sum, the step's
        routing counts)."""
        if live is None:
            live = jnp.ones((x.shape[0],), bool)
        with scope("experts"):
            weights, experts = self.route(x)
            routed, stats = held_expert_ffn(
                x, live, weights, experts, self.c.held,
                self.experts_gate._value, self.experts_up._value,
                self.experts_down._value, use_kernel=use_kernel,
                n_experts=self.router._value.shape[1])
            # the shared experts open `mlp` inside: the innermost owns
            return self.shared.run(x) + routed, stats


class ServedDecoder:
    """A decoder model as the serving engine reads it
    (serving/served.py): `layers` one served block a layer, the
    embedding a lookup, the head the model's `logits`. `h` is a plain
    array [tokens, d]: a decode step's slots or a chunk's positions."""

    def __init__(self, model, layers):
        c = model.config
        self.model = model
        self.max_seq_len, self.dtype = c.max_seq_len, c.dtype
        self.layers = layers

    def embed(self, ids, positions):
        return self.model.embed._value[ids.reshape(-1)]

    def head(self, h, at=None):
        if at is not None:
            h = jax.lax.dynamic_slice(h, (at, 0), (1, h.shape[1]))[None]
        else:
            h = h[:, None]
        return self.model.logits(h)


def default_make(config):
    """Parameters nobody handed over: N(0, initializer_range) matrices
    and unit gains, from the default generator, in the config's dtype."""
    from ..core.random import default_generator
    dtype = jnp.dtype(config.dtype)

    def make(name, shape, kind):
        if kind == "g":
            return jnp.ones(shape, dtype)
        return (config.initializer_range * jax.random.normal(
            default_generator().split(), shape, jnp.float32)).astype(dtype)
    return make
