"""Pallas int8-weight matmul for the weight-only-int8 LM head.

XLA does not fuse an int8->bf16 convert into a dot operand: the
quantized tied-head einsum materializes a dequantized [V, H] copy in
HBM every decode step, measured SLOWER than just reading bf16 weights
(10.8k vs 12.0k tok/s — see quant/wo8.py NOTE). This kernel does what
the fusion should: stream int8 weight tiles into VMEM (1 byte/weight
off HBM), convert + contract + scale in-register, emit [B, V] logits.

Inference-only (no vjp): the head's training path keeps the bf16
einsum. Row count B pads to the bf16 sublane minimum; V must divide by
the block (callers pad the table once at quantize time — see
WeightOnlyInt8Embedding.__init__; the consumer is GPTForPretraining's
head_q branch).
"""
import functools

import numpy as np
import jax
import jax.numpy as jnp

from .kernel_registry import register_kernel

_BLOCK_V = 1024
_MIN_ROWS = 16   # bf16 sublane minimum


def _interpret():
    return jax.default_backend() != "tpu"


def int8_matvec_preferred(rows):
    """Single source of truth for WHEN the pallas int8 head matvec
    beats the XLA einsum: decode-sized row counts on TPU (measured on
    v5e at the 125M head: pallas 11.1k tok/s vs einsum 10.8k vs bf16
    11.8k — see quant/wo8.py NOTE). Shared by the training model's
    quantized head branch (models/gpt.py head_q) and the serving
    engine's decode step, whose batch IS `rows` — a continuous-batching
    slot count above this bound should take the einsum instead."""
    return jax.default_backend() == "tpu" and rows <= 64


def _kernel(h_ref, wq_ref, s_ref, out_ref):
    hh = h_ref[...].astype(jnp.bfloat16)            # [Bp, D]
    w = wq_ref[...].astype(jnp.bfloat16)            # [bv, D]
    acc = jax.lax.dot_general(
        hh, w, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)         # [Bp, bv]
    out_ref[...] = acc * s_ref[...][None, :]


def _matvec_example(rng):
    B = int(rng.choice([1, 4, 32]))
    D = int(rng.choice([256, 512]))
    V = 2048
    h = rng.standard_normal((B, D)).astype(np.float32)
    wq = rng.integers(-127, 128, size=(V, D)).astype(np.int8)
    scale = (0.01 + rng.random(V)).astype(np.float32) * 0.01
    return (h, wq, scale), {}


def _matvec_fallback(h, wq, scale, block_v=_BLOCK_V):
    """Same bf16-cast contract+f32-accumulate math without the
    V-blocking (padding rows never reach the real output)."""
    hh = h.astype(jnp.bfloat16)
    w = wq.astype(jnp.bfloat16)
    acc = jax.lax.dot_general(
        hh, w, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    return acc * scale.astype(jnp.float32)[None, :]


@register_kernel(
    "int8_matvec", example=_matvec_example, fallback=_matvec_fallback,
    tol=(1e-4, 1e-4),
    notes="weight-only-int8 LM head matvec; int8 tiles dequantize "
          "in-register")
def int8_matvec(h, wq, scale, block_v=_BLOCK_V):
    """h [B, D] (any float dtype), wq int8 [V, D], scale f32 [V] ->
    [B, V] f32 logits (= h @ (wq * scale[:, None]).T without ever
    materializing the dequantized table)."""
    from jax.experimental import pallas as pl

    B, D = h.shape
    V = wq.shape[0]
    if V % block_v:
        raise ValueError(
            f"int8_matvec: V ({V}) must divide block_v ({block_v}); "
            "pad the table once at quantize time")
    Bp = ((max(_MIN_ROWS, B) + _MIN_ROWS - 1) // _MIN_ROWS) * _MIN_ROWS
    if Bp != B:
        h = jnp.concatenate(
            [h, jnp.zeros((Bp - B, D), h.dtype)], axis=0)
    out = pl.pallas_call(
        _kernel,
        name="int8_matvec",
        grid=(V // block_v,),
        in_specs=[
            pl.BlockSpec((Bp, D), lambda i: (0, 0)),
            pl.BlockSpec((block_v, D), lambda i: (i, 0)),
            pl.BlockSpec((block_v,), lambda i: (i,)),
        ],
        out_specs=pl.BlockSpec((Bp, block_v), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((Bp, V), jnp.float32),
        interpret=_interpret(),
    )(h, wq, scale.astype(jnp.float32))
    return out[:B]
