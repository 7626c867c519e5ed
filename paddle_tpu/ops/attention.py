"""Attention kernels.

TPU-native replacement for the reference's fused attention
(`operators/fused/fused_attention_op.cu`, `fmha_ref.h` — full O(s^2)
materialization). Two paths:

- `flash_attention`: blockwise online-softmax Pallas kernel (paddle_tpu.ops.
  pallas_attention) when running on TPU with supported shapes/dtypes.
- composed XLA path: einsum + softmax + einsum; XLA fuses the chain and it is
  the fallback on CPU and for odd shapes.

Layout convention is paddle's: [batch, seq, heads, head_dim] (BSNH).
"""
import functools
import math

import jax
import jax.numpy as jnp

from ..core.tensor import Tensor, apply
from ..tensor._helpers import ensure_tensor


def _composed_attention(q, k, v, bias=None, causal=False, scale=None,
                        dropout_p=0.0, dropout_key=None):
    """q,k,v: [B, S, N, H] jax values."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    logits = jnp.einsum("bqnh,bknh->bnqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if causal:
        sq, sk = logits.shape[-2], logits.shape[-1]
        mask = jnp.tril(jnp.ones((sq, sk), dtype=jnp.bool_), k=sk - sq)
        logits = jnp.where(mask, logits, jnp.asarray(-1e30, logits.dtype))
    if bias is not None:
        logits = logits + bias.astype(logits.dtype)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    if dropout_p > 0.0 and dropout_key is not None:
        keep = jax.random.bernoulli(dropout_key, 1.0 - dropout_p, probs.shape)
        probs = jnp.where(keep, probs / (1.0 - dropout_p), 0.0)
    return jnp.einsum("bnqk,bknh->bqnh", probs, v)


def _use_pallas(q, force=None, k=None):
    """Kernel dispatch. The Pallas blockwise kernel (bf16 MXU dots, 512
    tiles) beats XLA's fused attention from s=1024 up on v5e (measured
    full-GPT step: 94ms vs 131ms at s=1024; 9x at s=8192 where composed
    materializes the O(s^2) probability tensor). Below that the composed
    path's single fusion wins on launch overhead."""
    from ..flags import get_flag
    if not get_flag("use_pallas_attention"):
        return False
    if jax.default_backend() != "tpu":
        return False
    b, s, n, h = q.shape
    shapes_ok = s % 128 == 0 and h in (64, 128, 256) and s >= 256
    if k is not None:
        # cross-attention / unpadded KV: the kernel's tiling contract needs
        # the KV sequence 128-aligned and at least one block long too
        sk = k.shape[1]
        shapes_ok = shapes_ok and sk % 128 == 0 and sk >= 256
    if force is not None:
        return force and shapes_ok
    return shapes_ok and s >= get_flag("pallas_attention_min_seq")


def _flash(q, k, v, causal):
    """The Pallas flash kernel, per device shard when a mesh is active.

    A Mosaic custom call has no partitioning rule: inside a
    GSPMD-partitioned step jax 0.9.0 refuses to lower it ("Mosaic
    kernels cannot be automatically partitioned. Please wrap the call
    in a shard_map") unless EVERY mesh axis is manual. Attention is
    independent over batch and heads, so under a multi-device mesh the
    kernel runs in a fully-manual shard_map that splits the batch over
    `dp` and the heads over `mp` — the axes the GPT sharding plan
    already splits them over — and sees every other axis replicated.
    An axis whose size does not divide the dimension is not split (the
    kernel then computes that dimension whole on each device)."""
    from ..distributed import env as dist_env
    from .pallas_attention import flash_attention_fwd
    mesh = dist_env.current_mesh()
    if mesh is None or mesh.size == 1:
        return flash_attention_fwd(q, k, v, causal=causal)

    def axis_for(name, dim):
        if mesh.shape[name] > 1 and dim % mesh.shape[name] == 0:
            return name
        return None

    from jax.sharding import PartitionSpec as P
    spec = P(axis_for("dp", q.shape[0]), None,
             axis_for("mp", q.shape[2]), None)
    shard = jax.shard_map(
        lambda a, b, c: flash_attention_fwd(a, b, c, causal=causal),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)
    return shard(q, k, v)


def flash_attention(query, key, value, dropout=0.0, causal=False,
                    return_softmax=False, fixed_seed_offset=None,
                    training=True, use_pallas=None, name=None):
    """paddle.nn.functional.flash_attention-compatible API.

    use_pallas: None = auto (Pallas blockwise kernel for long sequences,
    XLA fused attention otherwise), True/False = force."""
    query, key, value = (ensure_tensor(query), ensure_tensor(key),
                         ensure_tensor(value))
    dropout_key = None
    if dropout > 0.0 and training:
        from ..core.random import next_key
        dropout_key = next_key()

    def fn(q, k, v):
        if _use_pallas(q, use_pallas, k=k) and dropout == 0.0:
            return _flash(q, k, v, causal)
        return _composed_attention(q, k, v, causal=causal,
                                   dropout_p=dropout if training else 0.0,
                                   dropout_key=dropout_key)
    out = apply(fn, query, key, value)
    if return_softmax:
        return out, None
    return out


def scaled_dot_product_attention(query, key, value, attn_mask=None,
                                 dropout_p=0.0, is_causal=False,
                                 training=True, name=None):
    query, key, value = (ensure_tensor(query), ensure_tensor(key),
                         ensure_tensor(value))
    dropout_key = None
    if dropout_p > 0.0 and training:
        from ..core.random import next_key
        dropout_key = next_key()

    if attn_mask is None:
        def fn(q, k, v):
            if _use_pallas(q, k=k) and dropout_p == 0.0:
                return _flash(q, k, v, is_causal)
            return _composed_attention(
                q, k, v, causal=is_causal,
                dropout_p=dropout_p if training else 0.0,
                dropout_key=dropout_key)
        return apply(fn, query, key, value)

    attn_mask = ensure_tensor(attn_mask)

    def fn(q, k, v, m):
        if m.dtype == jnp.bool_:
            bias = jnp.where(m, 0.0, -1e30)
        else:
            bias = m
        return _composed_attention(q, k, v, bias=bias, causal=is_causal,
                                   dropout_p=dropout_p if training else 0.0,
                                   dropout_key=dropout_key)
    return apply(fn, query, key, value, attn_mask)
