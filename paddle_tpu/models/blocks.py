"""What the served decoder models share (`deepseek_v2`, `granite_hybrid`):
parameters handed over leaf by leaf, the matmul with a float32 sum, and
the gated MLP. RMSNorm is `nn.functional.norm.rms_norm_values`.
"""
import jax
import jax.numpy as jnp

from ..core.tensor import Parameter
from ..nn import Layer

__all__ = ["GatedMLP", "Weights", "default_make", "matmul"]


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


class GatedMLP(Weights):
    """down(silu(gate(x)) * up(x))."""

    def __init__(self, make, prefix, d, width):
        super().__init__(make, prefix)
        self.gate = self.param("gate", (d, width))
        self.up = self.param("up", (d, width))
        self.down = self.param("down", (width, d))

    def run(self, x):
        g = matmul(x, self.gate._value)
        return matmul(jax.nn.silu(g) * matmul(x, self.up._value),
                      self.down._value)


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
