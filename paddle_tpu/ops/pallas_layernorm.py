"""Pallas fused residual-add + LayerNorm (forward + custom VJP).

Reference analog: `operators/fused/fused_bias_dropout_residual_layer_
norm_op` family / `skip_layernorm_fuse_pass.cc` — the reference fuses
residual+LN into one CUDA kernel because its op-by-op executor would
otherwise materialize the sum. Under XLA the elementwise add DOES fuse
into the LN reduction already, so this kernel's win is narrower:
one VMEM pass computes the sum, the two reduction moments, and the
normalized output without re-reading HBM, and the saved residual-sum
for backward is produced in the same pass (XLA keeps sum + rstd + mean
as three kernels on some shapes).

Dispatch policy mirrors `ops/fused_ce.py`: OFF by default
(`use_pallas=False`) until measured faster on real hardware at the
caller's shape — the composed XLA path is already good; flip per-call
or via `paddle_tpu.set_flags({"use_pallas_layernorm": True})`.

Shapes: x, residual [rows, d] (callers flatten leading dims), weight/
bias [d]; d should be a multiple of 128 for clean lanes (padding
otherwise — handled by the caller check).
"""
import functools

import numpy as np
import jax
import jax.numpy as jnp

from .kernel_registry import register_kernel

_BLOCK_ROWS = 256


def _interpret():
    # escape hatch: off-TPU the kernels run in pallas interpret mode so
    # CPU CI keeps covering them (same probe as ops/pallas_attention.py)
    return jax.default_backend() != "tpu"


def _fwd_kernel(x_ref, res_ref, w_ref, b_ref, out_ref, sum_ref, rstd_ref,
                *, eps):
    xs = x_ref[...].astype(jnp.float32)
    rs = res_ref[...].astype(jnp.float32)
    s = xs + rs
    mean = jnp.mean(s, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(s - mean), axis=-1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    norm = (s - mean) * rstd
    out = norm * w_ref[...].astype(jnp.float32) + b_ref[...].astype(
        jnp.float32)
    out_ref[...] = out.astype(out_ref.dtype)
    sum_ref[...] = s.astype(sum_ref.dtype)
    rstd_ref[...] = jnp.broadcast_to(rstd, rstd_ref.shape).astype(
        rstd_ref.dtype)


def _ln_example(rng):
    rows = int(rng.choice([128, 256, 512]))
    d = int(rng.choice([128, 256]))
    x = rng.standard_normal((rows, d)).astype(np.float32)
    res = rng.standard_normal((rows, d)).astype(np.float32)
    w = rng.standard_normal((d,)).astype(np.float32)
    b = rng.standard_normal((d,)).astype(np.float32)
    return (x, res, w, b, 1e-5), {}


def _ln_ref(x, residual, weight, bias, eps):
    xs = x.astype(jnp.float32)
    rs = residual.astype(jnp.float32)
    s = xs + rs
    mean = jnp.mean(s, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(s - mean), axis=-1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    out = ((s - mean) * rstd * weight.astype(jnp.float32)
           + bias.astype(jnp.float32))
    return out.astype(x.dtype), s, rstd


def _ln_fwd_fallback(x, residual, weight, bias, eps):
    return _ln_ref(x, residual, weight, bias, eps)


def _ln_primal_fallback(x, residual, weight, bias, eps=1e-5):
    return _ln_ref(x, residual, weight, bias, eps)[0]


@register_kernel(
    "layernorm_fwd_saved", example=_ln_example, fallback=_ln_fwd_fallback,
    tol=(1e-4, 1e-5),
    notes="3-output forward (out + residual sum + rstd) for the vjp")
def _fwd(x, residual, weight, bias, eps):
    from jax.experimental import pallas as pl
    rows, d = x.shape
    if rows > _BLOCK_ROWS and rows % _BLOCK_ROWS:
        raise ValueError(
            f"fused_add_layer_norm: rows ({rows}) must divide by "
            f"{_BLOCK_ROWS} (trailing rows would be left unwritten); "
            "use add_layer_norm, whose dispatcher guards this")
    grid = (max(1, rows // _BLOCK_ROWS),)
    br = min(_BLOCK_ROWS, rows)
    out, s, rstd = pl.pallas_call(
        functools.partial(_fwd_kernel, eps=eps),
        name="layernorm_fwd_saved",
        grid=grid,
        in_specs=[
            pl.BlockSpec((br, d), lambda i: (i, 0)),
            pl.BlockSpec((br, d), lambda i: (i, 0)),
            pl.BlockSpec((d,), lambda i: (0,)),
            pl.BlockSpec((d,), lambda i: (0,)),
        ],
        out_specs=[
            pl.BlockSpec((br, d), lambda i: (i, 0)),
            pl.BlockSpec((br, d), lambda i: (i, 0)),
            pl.BlockSpec((br, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rows, d), x.dtype),
            jax.ShapeDtypeStruct((rows, d), jnp.float32),
            jax.ShapeDtypeStruct((rows, 1), jnp.float32),
        ],
        interpret=_interpret(),
    )(x, residual, weight, bias)
    return out, s, rstd


def _fwd_only_kernel(x_ref, res_ref, w_ref, b_ref, out_ref, *, eps):
    xs = x_ref[...].astype(jnp.float32)
    rs = res_ref[...].astype(jnp.float32)
    s = xs + rs
    mean = jnp.mean(s, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(s - mean), axis=-1, keepdims=True)
    out = ((s - mean) * jax.lax.rsqrt(var + eps)
           * w_ref[...].astype(jnp.float32)
           + b_ref[...].astype(jnp.float32))
    out_ref[...] = out.astype(out_ref.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
@register_kernel(
    "layernorm_fused", example=_ln_example, fallback=_ln_primal_fallback,
    tol=(1e-4, 1e-5),
    notes="output-only primal kernel (pallas outputs cannot be DCE'd)")
def fused_add_layer_norm(x, residual, weight, bias, eps=1e-5):
    """LayerNorm(x + residual) * weight + bias, one VMEM pass. The
    primal (inference) path runs an output-only kernel — pallas outputs
    cannot be DCE'd, so the 3-output forward is reserved for the vjp."""
    from jax.experimental import pallas as pl
    rows, d = x.shape
    if rows > _BLOCK_ROWS and rows % _BLOCK_ROWS:
        raise ValueError(
            f"fused_add_layer_norm: rows ({rows}) must divide by "
            f"{_BLOCK_ROWS}; use add_layer_norm")
    grid = (max(1, rows // _BLOCK_ROWS),)
    br = min(_BLOCK_ROWS, rows)
    return pl.pallas_call(
        functools.partial(_fwd_only_kernel, eps=eps),
        name="layernorm_fused",
        grid=grid,
        in_specs=[
            pl.BlockSpec((br, d), lambda i: (i, 0)),
            pl.BlockSpec((br, d), lambda i: (i, 0)),
            pl.BlockSpec((d,), lambda i: (0,)),
            pl.BlockSpec((d,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((br, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, d), x.dtype),
        interpret=_interpret(),
    )(x, residual, weight, bias)


def _vjp_fwd(x, residual, weight, bias, eps):
    out, s, rstd = _fwd(x, residual, weight, bias, eps)
    return out, (s, rstd, weight)


def _vjp_bwd(eps, saved, g):
    s, rstd, weight = saved
    g32 = g.astype(jnp.float32)
    w32 = weight.astype(jnp.float32)
    mean = jnp.mean(s, axis=-1, keepdims=True)
    norm = (s - mean) * rstd
    d_norm = g32 * w32
    d = s.shape[-1]
    # standard LN backward over the saved residual sum
    ds = (d_norm - jnp.mean(d_norm, axis=-1, keepdims=True)
          - norm * jnp.mean(d_norm * norm, axis=-1, keepdims=True)) * rstd
    dw = jnp.sum(g32 * norm, axis=0)
    db = jnp.sum(g32, axis=0)
    dx = ds.astype(g.dtype)
    return dx, dx, dw.astype(weight.dtype), db.astype(weight.dtype)


fused_add_layer_norm.defvjp(_vjp_fwd, _vjp_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def fused_add_layer_norm_pair(x, residual, weight, bias, eps=1e-5):
    """(LayerNorm(x + residual) * weight + bias, x + residual) in one
    VMEM pass. The second output is the residual CARRY the pre-LN
    transformer block threads to the next add — the 3-output forward
    already produces the sum for backward, so returning it is free."""
    out, s, _ = _fwd(x, residual, weight, bias, eps)
    return out, s.astype(x.dtype)


def _pair_vjp_fwd(x, residual, weight, bias, eps):
    out, s, rstd = _fwd(x, residual, weight, bias, eps)
    return (out, s.astype(x.dtype)), (s, rstd, weight)


def _pair_vjp_bwd(eps, saved, gs):
    g_out, g_sum = gs
    s, rstd, weight = saved
    g32 = g_out.astype(jnp.float32)
    w32 = weight.astype(jnp.float32)
    mean = jnp.mean(s, axis=-1, keepdims=True)
    norm = (s - mean) * rstd
    d_norm = g32 * w32
    ds = (d_norm - jnp.mean(d_norm, axis=-1, keepdims=True)
          - norm * jnp.mean(d_norm * norm, axis=-1, keepdims=True)) * rstd
    # the carry cotangent flows straight into the sum
    ds = ds + g_sum.astype(jnp.float32)
    dw = jnp.sum(g32 * norm, axis=0)
    db = jnp.sum(g32, axis=0)
    dx = ds.astype(g_out.dtype)
    return dx, dx, dw.astype(weight.dtype), db.astype(weight.dtype)


fused_add_layer_norm_pair.defvjp(_pair_vjp_fwd, _pair_vjp_bwd)


def add_layer_norm(x, residual, weight, bias, eps=1e-5, use_pallas=None):
    """Dispatching wrapper: composed XLA path by default; the Pallas
    kernel when requested (flag `use_pallas_layernorm` or use_pallas=
    True) AND the shape divides cleanly on a TPU backend."""
    if use_pallas is None:
        from ..flags import get_flag
        use_pallas = bool(get_flag("use_pallas_layernorm"))
    rows_ok = (x.ndim == 2 and x.shape[0] % _BLOCK_ROWS == 0
               and x.shape[-1] % 128 == 0)
    if use_pallas and rows_ok and jax.default_backend() == "tpu":
        return fused_add_layer_norm(x, residual, weight, bias, eps)
    # fp32 moments exactly like the kernel: flipping the flag must not
    # change numerics beyond kernel-level tolerance
    s = x.astype(jnp.float32) + residual.astype(jnp.float32)
    mean = jnp.mean(s, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(s - mean), axis=-1, keepdims=True)
    out = ((s - mean) * jax.lax.rsqrt(var + eps)
           * weight.astype(jnp.float32) + bias.astype(jnp.float32))
    return out.astype(x.dtype)
