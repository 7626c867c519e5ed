"""Rotary position embeddings with YaRN scaling, on raw values: one
rotary key shared by all heads (latent attention) or every head of
grouped-query q and k (`cos[:, None]`, `sin[:, None]` over the heads),
over a head's whole width or as many of its first dimensions as the
tables are wide.

The tables are never stored: `rotary_cos_sin` computes cos and sin of
the positions a step works on, in float32, from the inverse
frequencies, inside the compiled step.

YaRN (Peng et al. 2023, arXiv:2309.00071) as DeepSeek-V2 applies it:
dimensions whose wavelength fits the original context many times keep
their frequency (extrapolated), those that fit it less than once are
divided by `factor` (interpolated), and a linear ramp joins the two
between the dimensions that make `beta_fast` and `beta_slow` rotations
over the original context.
"""
import math

import numpy as np
import jax.numpy as jnp

__all__ = ["yarn_inv_freq", "yarn_mscale", "rotary_cos_sin",
           "apply_rotary"]


def yarn_mscale(factor, mscale):
    """YaRN's attention temperature: 0.1 * mscale * ln(factor) + 1."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def _correction_dim(rotations, dim, base, original_len):
    # the (fractional) pair index whose wavelength makes `rotations`
    # turns over the original context
    return dim * math.log(original_len / (rotations * 2 * math.pi)) \
        / (2 * math.log(base))


def yarn_inv_freq(dim, base=10000.0, factor=1.0, original_len=4096,
                  beta_fast=32, beta_slow=1):
    """Inverse frequencies [dim // 2], float64 numpy: plain rotary where
    factor <= 1, else the YaRN blend."""
    exponents = np.arange(0, dim, 2, dtype=np.float64) / dim
    extra = 1.0 / base ** exponents
    if factor <= 1:
        return extra
    inter = extra / factor
    low = max(math.floor(_correction_dim(beta_fast, dim, base,
                                         original_len)), 0)
    high = min(math.ceil(_correction_dim(beta_slow, dim, base,
                                         original_len)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    return inter * ramp + extra * (1.0 - ramp)


def rotary_cos_sin(positions, inv_freq, scale=1.0):
    """cos and sin [..., dim] (each frequency twice, halves side by
    side) of int positions [...], float32."""
    freqs = positions.astype(jnp.float32)[..., None] \
        * jnp.asarray(inv_freq, jnp.float32)
    emb = jnp.concatenate([freqs, freqs], axis=-1)
    return jnp.cos(emb) * scale, jnp.sin(emb) * scale


def apply_rotary(x, cos, sin, interleaved=True):
    """Rotate x [..., dim] by cos/sin broadcastable to it. With
    `interleaved` the pairs (x0, x1), (x2, x3), ... of the input are
    first moved to (x0, x2, ... | x1, x3, ...), as the DeepSeek-V2
    reference code does before its rotate-half; without it the input is
    taken as the two halves already (the plain rotate-half of the
    Llama/EXAONE code: dimension i turns with dimension i + dim/2).
    Tables narrower than x, cos/sin [..., r], turn the first r
    dimensions alone and pass the rest as they are (Qwen3-Next's partial
    rotary). Float32 arithmetic, the input's dtype out."""
    r = cos.shape[-1]
    if r < x.shape[-1]:
        return jnp.concatenate([
            apply_rotary(x[..., :r], cos, sin, interleaved), x[..., r:]],
            axis=-1)
    f = x.astype(jnp.float32)
    if interleaved:
        f = jnp.concatenate([f[..., 0::2], f[..., 1::2]], axis=-1)
    half = f.shape[-1] // 2
    rotated = jnp.concatenate([-f[..., half:], f[..., :half]], axis=-1)
    return (f * cos + rotated * sin).astype(x.dtype)
