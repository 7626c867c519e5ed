"""Normalization functionals.

Parity: `python/paddle/nn/functional/norm.py` (reference kernels
`operators/batch_norm_op.cu`, `layer_norm_op.cu`, `group_norm_op.cu`,
`instance_norm_op.cu`). XLA fuses the reduce+scale+shift chains; layer_norm
is also provided as a Pallas kernel in `paddle_tpu.ops.pallas` for the
residual+dropout fusion cases.
"""
import jax
import jax.numpy as jnp

from ...core.tensor import Tensor, apply
from ...tensor._helpers import ensure_tensor


@jax.custom_vjp
def _scale_shift(x, w, b):
    """y = x * w + b applied in x's dtype (no f32 stream upcast), with a
    hand-written vjp whose PARAM-GRAD reductions accumulate in f32 — the
    automatic vjp of a bf16 multiply would sum the [B*S]-long bias/weight
    gradients in bf16 (~2 digits lost over 16k tokens)."""
    return x * w.astype(x.dtype) + b.astype(x.dtype)


def _scale_shift_fwd(x, w, b):
    return _scale_shift(x, w, b), (x, w)


def _scale_shift_bwd(res, g):
    x, w = res
    red = tuple(range(g.ndim - w.ndim))
    dx = g * w.astype(g.dtype)
    dw = jnp.sum(g * x, axis=red, dtype=jnp.float32).astype(w.dtype)
    db = jnp.sum(g, axis=red, dtype=jnp.float32).astype(w.dtype)
    return dx, dw, db


_scale_shift.defvjp(_scale_shift_fwd, _scale_shift_bwd)


def rms_norm_values(v, weight=None, epsilon=1e-06):
    """RMSNorm on raw values over the last axis: the mean square and the
    scaling in float32, the result back in the input's dtype before the
    gain (as the Llama/DeepSeek reference code has it)."""
    f = v.astype(jnp.float32)
    out = (f * jax.lax.rsqrt(jnp.mean(jnp.square(f), axis=-1, keepdims=True)
                             + epsilon)).astype(v.dtype)
    return out if weight is None else out * weight.astype(v.dtype)


def rms_norm(x, weight=None, epsilon=1e-06, name=None):
    """y = x / sqrt(mean(x^2) + eps) * weight over the last axis."""
    x = ensure_tensor(x)
    if weight is None:
        return apply(lambda v: rms_norm_values(v, None, epsilon), x)
    return apply(lambda v, w: rms_norm_values(v, w, epsilon), x,
                 ensure_tensor(weight))


def layer_norm(x, normalized_shape, weight=None, bias=None, epsilon=1e-05,
               name=None):
    x = ensure_tensor(x)
    if isinstance(normalized_shape, int):
        normalized_shape = [normalized_shape]
    naxes = tuple(range(-len(normalized_shape), 0))

    def fn(v, *wb):
        # statistics accumulate in the amp-list dtype for "layer_norm"
        # (f32 by default — black list; bf16 if the user white-lists it);
        # elementwise math stays in the input dtype so no f32 activation
        # copy is materialized (same bandwidth reasoning as batch_norm)
        from ...amp import amp_op_dtype
        acc = amp_op_dtype("layer_norm", jnp.float32)
        mean = jnp.mean(v, axis=naxes, keepdims=True, dtype=acc)
        d = v - mean.astype(v.dtype)
        var = jnp.mean(jnp.square(d), axis=naxes, keepdims=True,
                       dtype=acc)
        out = d * jax.lax.rsqrt(var + epsilon).astype(v.dtype)
        # scale/shift applied in the INPUT dtype: multiplying by the f32
        # params would upcast the whole [B,S,D] stream to f32 (measured
        # ~6.7GB/step of residual-stream traffic on the GPT bench);
        # _scale_shift's custom vjp keeps the param-grad reductions f32
        if weight is not None and bias is not None:
            return _scale_shift(out, wb[0], wb[1])
        i = 0
        if weight is not None:
            out = out * wb[i]        # f32 upcast: rare config, safe grads
            i += 1
        if bias is not None:
            out = out + wb[i]
        return out

    args = [x]
    if weight is not None:
        args.append(ensure_tensor(weight))
    if bias is not None:
        args.append(ensure_tensor(bias))
    return apply(fn, *args)


def batch_norm(x, running_mean, running_var, weight=None, bias=None,
               training=False, momentum=0.9, epsilon=1e-05,
               data_format="NCHW", use_global_stats=None, name=None):
    """When training, returns output computed from batch stats AND updates
    running stats in place on the provided tensors (dygraph semantics,
    reference `operators/batch_norm_op.cc`). Under `to_static` the buffer
    update is captured by the functional-state machinery in paddle_tpu.jit."""
    x = ensure_tensor(x)
    running_mean = ensure_tensor(running_mean)
    running_var = ensure_tensor(running_var)
    ch_axis = 1 if data_format[1] == "C" else x.ndim - 1
    red_axes = tuple(i for i in range(x.ndim) if i != ch_axis)
    bshape = [1] * x.ndim
    bshape[ch_axis] = -1

    use_batch_stats = training and not use_global_stats

    if use_batch_stats:
        def _stats(v):
            # f32-ACCUMULATING reductions straight off the (possibly bf16)
            # input: `v.astype(f32)` first would materialize a full f32
            # activation copy in HLO (measured: +14 GB/step traffic on
            # ResNet-50/64 — conv nets are bandwidth-bound on TPU). The
            # variance pass squares the CENTERED bf16 values, avoiding the
            # E[x^2]-E[x]^2 cancellation while keeping elementwise work in
            # the input dtype.
            mean = jnp.mean(v, axis=red_axes, dtype=jnp.float32)
            d = v - mean.astype(v.dtype).reshape(bshape)
            var = jnp.mean(jnp.square(d), axis=red_axes, dtype=jnp.float32)
            return mean, var

        # update running stats in place with (stop-gradient) batch stats;
        # tracer-safe under jit via the functional-state capture in paddle_tpu.jit
        bmean, bvar = _stats(x._value)
        running_mean._value = (momentum * running_mean._value.astype(jnp.float32)
                               + (1 - momentum) * bmean).astype(running_mean._value.dtype)
        running_var._value = (momentum * running_var._value.astype(jnp.float32)
                              + (1 - momentum) * bvar).astype(running_var._value.dtype)

        def fn(v, *wb):
            # batch stats recomputed inside so grads flow through mean/var.
            # The normalize is FOLDED into one per-channel multiply-add in
            # the INPUT dtype: out = v*a + c with a = w*rsqrt(var+eps),
            # c = b - mean*a — so every activation-sized tensor (and the
            # vjp's saved residuals) stays bf16 under AMP.
            mean, var = _stats(v)
            a = jax.lax.rsqrt(var + epsilon)
            i = 0
            if weight is not None:
                a = a * wb[i]
                i += 1
            c = -mean * a
            if bias is not None:
                c = c + wb[i]
            return v * a.reshape(bshape).astype(v.dtype) + \
                c.reshape(bshape).astype(v.dtype)
    else:
        mean_c, var_c = running_mean._value, running_var._value

        def fn(v, *wb):
            a = jax.lax.rsqrt(var_c.astype(jnp.float32) + epsilon)
            i = 0
            if weight is not None:
                a = a * wb[i]
                i += 1
            c = -mean_c.astype(jnp.float32) * a
            if bias is not None:
                c = c + wb[i]
            return v * a.reshape(bshape).astype(v.dtype) + \
                c.reshape(bshape).astype(v.dtype)

    args = [x]
    if weight is not None:
        args.append(ensure_tensor(weight))
    if bias is not None:
        args.append(ensure_tensor(bias))
    return apply(fn, *args)


def instance_norm(x, running_mean=None, running_var=None, weight=None,
                  bias=None, use_input_stats=True, momentum=0.9, eps=1e-05,
                  data_format="NCHW", name=None):
    x = ensure_tensor(x)
    ch_axis = 1 if data_format[1] == "C" else x.ndim - 1
    red_axes = tuple(i for i in range(2, x.ndim)) if ch_axis == 1 else \
        tuple(i for i in range(1, x.ndim - 1))
    bshape = [1] * x.ndim
    bshape[ch_axis] = -1

    def fn(v, *wb):
        mean = jnp.mean(v, axis=red_axes, keepdims=True)
        var = jnp.var(v, axis=red_axes, keepdims=True)
        out = (v - mean) * jnp.power(var + eps, -0.5)
        i = 0
        if weight is not None:
            out = out * wb[i].reshape(bshape)
            i += 1
        if bias is not None:
            out = out + wb[i].reshape(bshape)
        return out

    args = [x]
    if weight is not None:
        args.append(ensure_tensor(weight))
    if bias is not None:
        args.append(ensure_tensor(bias))
    return apply(fn, *args)


def group_norm(x, num_groups, epsilon=1e-05, weight=None, bias=None,
               data_format="NCHW", name=None):
    x = ensure_tensor(x)
    channel_last = data_format[-1] == "C"

    def fn(v, *wb):
        if channel_last:
            v = jnp.moveaxis(v, -1, 1)
        n, c = v.shape[0], v.shape[1]
        spatial = v.shape[2:]
        g = v.reshape(n, num_groups, c // num_groups, *spatial)
        axes = tuple(range(2, g.ndim))
        mean = jnp.mean(g, axis=axes, keepdims=True)
        var = jnp.var(g, axis=axes, keepdims=True)
        out = ((g - mean) * jnp.power(var + epsilon, -0.5)).reshape(v.shape)
        bshape = [1, c] + [1] * len(spatial)
        i = 0
        if weight is not None:
            out = out * wb[i].reshape(bshape)
            i += 1
        if bias is not None:
            out = out + wb[i].reshape(bshape)
        if channel_last:
            out = jnp.moveaxis(out, 1, -1)
        return out

    args = [x]
    if weight is not None:
        args.append(ensure_tensor(weight))
    if bias is not None:
        args.append(ensure_tensor(bias))
    return apply(fn, *args)


def fused_add_layer_norm(x, residual, weight, bias, epsilon=1e-05,
                         name=None):
    """(LayerNorm(x + residual), x + residual) — the pre-LN transformer
    residual site in one op. Dispatches to the Pallas pair kernel
    (`ops/pallas_layernorm.py`, measured 1.69x the composed XLA lowering
    on v5e at GPT bench shapes) when `use_pallas_layernorm` is on and
    shapes divide; composed XLA with identical f32-moment numerics
    otherwise. Reference analog: the fused_bias_dropout_residual_
    layer_norm op family / skip_layernorm_fuse_pass.cc."""
    x = ensure_tensor(x)
    residual = ensure_tensor(residual)
    weight = ensure_tensor(weight)
    bias = ensure_tensor(bias)

    def fn(v, r, w, b):
        from ...flags import get_flag
        from ...ops.pallas_layernorm import (fused_add_layer_norm_pair,
                                             _BLOCK_ROWS)
        lead = v.shape[:-1]
        d = v.shape[-1]
        rows = 1
        for n in lead:
            rows *= int(n)
        if (get_flag("use_pallas_layernorm") and rows % _BLOCK_ROWS == 0
                and d % 128 == 0 and jax.default_backend() == "tpu"):
            out2, carry2 = fused_add_layer_norm_pair(
                v.reshape(-1, d), r.reshape(-1, d), w, b, epsilon)
            return out2.reshape(*lead, d), carry2.reshape(*lead, d)
        # composed path: same bandwidth discipline as layer_norm above —
        # f32 moments, elementwise math and scale/shift in input dtype
        # (no f32 copy of the [.., d] stream is materialized)
        h = v + r
        from ...amp import amp_op_dtype
        acc = amp_op_dtype("layer_norm", jnp.float32)
        mean = jnp.mean(h, axis=-1, keepdims=True, dtype=acc)
        dlt = h - mean.astype(h.dtype)
        var = jnp.mean(jnp.square(dlt), axis=-1, keepdims=True, dtype=acc)
        out = dlt * jax.lax.rsqrt(var + epsilon).astype(h.dtype)
        return _scale_shift(out, w, b), h

    return apply(fn, x, residual, weight, bias)


def local_response_norm(x, size, alpha=1e-4, beta=0.75, k=1.0,
                        data_format="NCHW", name=None):
    x = ensure_tensor(x)

    def fn(v):
        sq = jnp.square(v)
        ch_axis = 1 if data_format[1] == "C" else v.ndim - 1
        c = v.shape[ch_axis]
        half = size // 2
        pads = [(0, 0)] * v.ndim
        pads[ch_axis] = (half, size - half - 1)
        padded = jnp.pad(sq, pads)
        acc = jnp.zeros_like(v)
        for i in range(size):
            sl = [slice(None)] * v.ndim
            sl[ch_axis] = slice(i, i + c)
            acc = acc + padded[tuple(sl)]
        return v / jnp.power(k + alpha * acc, beta)
    return apply(fn, x)
