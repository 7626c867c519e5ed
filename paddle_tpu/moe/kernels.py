"""Fused Pallas dispatch/combine kernels for the MoE layer.

The legacy layer (`distributed/moe.py`) realizes dispatch and combine as
einsums against dense [n, E, C] masks — O(n*E*C*d) MXU work for what is
logically a permutation. Here both sides are index-driven Pallas
programs over the router's slot maps (`router.route_top_k`):

  - **dispatch** = `moe_gather(tokens, slot_token)`: one program gathers
    token rows into their [E*C, d] expert buckets, zero-filling empty
    slots — the rows stream HBM->VMEM once, O(E*C*d);
  - **combine** = `moe_combine(expert_rows, comb_slot, comb_w)`: one
    program accumulates each token's k weighted expert rows in f32 —
    O(n*k*d), no [n, E, C] combine tensor ever exists.

Slot maps ride the scalar-prefetch channel (`PrefetchScalarGridSpec`) so
the index arithmetic happens in SMEM while the row DMA streams; the
sentinel (index == n_rows) masks to zero in-kernel. `d % 128 == 0` is
required on TPU (lane tiling); `moe_kernel_supported` is the single
eligibility gate, and callers fall back to the pure-jnp forms below —
`gather_fallback` / `combine_fallback` — which are the SAME index math
via `jnp.take(mode="fill")`, so kernel and fallback are numerically
interchangeable (pinned by tests/test_moe.py parity).

Backward: both ops carry a custom_vjp whose backward is the index-form
jnp math (gather^T = scatter-add, combine^T = gather + row-dot) — exact,
and shared by both forward paths so the two can never diverge in grads.
"""
import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..ops.kernel_registry import (fits_vmem, out_struct,
                                   register_kernel)

__all__ = ["moe_gather", "moe_combine", "gather_fallback",
           "combine_fallback", "moe_kernel_supported"]

_BLOCK_ROWS = 128


def _interpret():
    return jax.default_backend() != "tpu"


def moe_kernel_supported(d, dtype=jnp.float32, n_src=None):
    """Single eligibility gate for the fused path: the row width must
    tile the 128-lane registers, the dtype must be a native vector
    type, and — because the kernels keep the whole SOURCE array
    VMEM-resident (rows are gathered by dynamic index, so no block
    partition of src is possible without HBM streaming — a follow-up)
    — the src bytes plus a double-buffered output block must fit the
    per-core budget. The bound is the Kernel Doctor's KN502 projection
    (ops/kernel_registry.vmem_footprint: src is a RESIDENT block, the
    output block MOVES), so the HBM-streaming follow-up changes one
    place. Callers (auto mode) fall back to the exact jnp forms
    otherwise."""
    if d % 128 or jnp.dtype(dtype) not in (jnp.dtype(jnp.float32),
                                           jnp.dtype(jnp.bfloat16)):
        return False
    if n_src is not None:
        # projected at 4 bytes/element whatever the dtype: a bf16 source
        # runs through the kernels widened to f32 (_widened)
        if not fits_vmem(moving=[((_BLOCK_ROWS, d), 4)],
                         resident=[((n_src, d), 4)]):
            return False
    return True


def _widened(kernel, src, *rest):
    """Run `kernel` on an f32 copy of a bf16 source and narrow the
    result. The kernels fetch ONE source row per step at a dynamic
    sublane index; bf16 packs two rows per sublane and Mosaic refuses
    the load ("cannot statically prove that index in dimension 0 is a
    multiple of 8", vector.load of vector<1xdxbf16>, jax 0.9.0 / libtpu
    0.0.34). bf16 -> f32 -> bf16 is exact for the gather, and the
    combine already accumulates in f32 and rounds once at the end."""
    return kernel(src.astype(jnp.float32), *rest).astype(src.dtype)


def _auto_use_kernel(src):
    """The auto gate (use_kernel=None): a TPU, a supported shape, and a
    single-device program. Under a multi-device mesh the kernels are
    gated OFF: the MoE layer's expert-parallel region is manual over
    `ep` only and leaves dp/mp to GSPMD, and jax 0.9.0 refuses to lower
    a Mosaic call anywhere but a region manual over EVERY mesh axis —
    "NotImplementedError: Mosaic kernels cannot be automatically
    partitioned. Please wrap the call in a shard_map." The exact jnp
    forms partition fine; a fully-manual MoE region is the repair."""
    from ..distributed import env
    mesh = env.current_mesh()
    return (jax.default_backend() == "tpu"
            and (mesh is None or mesh.size == 1)
            and moe_kernel_supported(src.shape[-1], src.dtype,
                                     n_src=src.shape[0]))


def _pad_to(x, mult, fill):
    r = (-x.shape[0]) % mult
    if r:
        x = jnp.concatenate(
            [x, jnp.full((r,) + x.shape[1:], fill, x.dtype)])
    return x


def _for_each_row(rows, body):
    """Run `body(i)` for i in [0, rows) inside a kernel. A while_loop,
    not a fori_loop: a static-bound fori_loop traces to `scan`, and
    when the kernel sits under a shard_map the Pallas interpreter
    (jax 0.9.0) re-evaluates that scan with varying operands against a
    body typed without them ("Scan carry input and output got
    mismatched varying manual axes"); `while` carries no such check
    and Mosaic lowers the counted form to the same scf.for."""
    def step(i):
        body(i)
        return i + 1

    jax.lax.while_loop(lambda i: i < rows, step, jnp.int32(0))


# ---------------------------------------------------------------------------
# dispatch: row gather with sentinel zero-fill
# ---------------------------------------------------------------------------

def _gather_kernel(idx_ref, src_ref, out_ref, *, rows, n_src):
    base = pl.program_id(0) * rows

    def body(i):
        t = idx_ref[base + i]
        valid = (t < n_src).astype(src_ref.dtype)
        safe = jnp.where(t < n_src, t, 0)
        row = src_ref[pl.ds(safe, 1), :]
        out_ref[pl.ds(i, 1), :] = row * valid

    _for_each_row(rows, body)


def _gather_example(rng):
    d = int(rng.choice([128, 256]))
    n_src = int(rng.integers(16, 64))
    m = int(rng.integers(10, 150))
    src = rng.standard_normal((n_src, d)).astype(np.float32)
    idx = rng.integers(0, n_src + 1, size=m).astype(np.int32)  # incl sentinel
    return (src, idx), {}


@register_kernel(
    "moe_gather", example=_gather_example,
    # late-bound: gather_fallback is defined below (same index math
    # via jnp.take(mode="fill"), pinned exact)
    fallback=lambda src, idx: gather_fallback(src, idx),
    tol=(1e-6, 1e-6),
    notes="dispatch row-gather with sentinel zero-fill; slot map rides "
          "the scalar-prefetch channel")
def _gather_pallas(src, idx):
    if src.dtype != jnp.float32:
        return _widened(_gather_pallas, src, idx)
    n_src, d = src.shape
    n_out = idx.shape[0]
    rows = _BLOCK_ROWS
    idx_p = _pad_to(idx.astype(jnp.int32), rows, n_src)
    n_pad = idx_p.shape[0]
    grid = (n_pad // rows,)
    out = pl.pallas_call(
        functools.partial(_gather_kernel, rows=rows, n_src=n_src),
        name="moe_gather",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[pl.BlockSpec((n_src, d), lambda b, *_: (0, 0))],
            out_specs=pl.BlockSpec((rows, d),
                                   lambda b, *_: (b, 0)),
        ),
        out_shape=out_struct((n_pad, d), src.dtype, src, idx),
        # the per-row VMEM loop reads each src row at most once per
        # output row; cost == one src stream + one out stream
        cost_estimate=pl.CostEstimate(
            flops=0, transcendentals=0,
            bytes_accessed=(n_src + 2 * n_pad) * d * src.dtype.itemsize),
        interpret=_interpret(),
    )(idx_p, src)
    return out[:n_out]


def gather_fallback(src, idx):
    """Pure-jnp dispatch: out[i] = src[idx[i]], zeros past the end
    (the sentinel). Identical index math to the kernel."""
    return jnp.take(src, idx, axis=0, mode="fill", fill_value=0)


def _gather_impl(use_kernel, src, idx):
    if use_kernel is None:
        use_kernel = _auto_use_kernel(src)
    if use_kernel:
        return _gather_pallas(src, idx)
    return gather_fallback(src, idx)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def moe_gather(src, idx, use_kernel=None):
    """Dispatch gather with sentinel zero-fill. src [n, d], idx [m]
    int32 in [0, n] (n == empty) -> [m, d]. use_kernel: True (Pallas),
    False (jnp fallback), None (auto: TPU + supported)."""
    return _gather_impl(use_kernel, src, idx)


def _gather_fwd(src, idx, use_kernel):
    # src rides the residuals for its shape/dtype only — bwd never
    # reads its values, so DCE drops the dependency
    return _gather_impl(use_kernel, src, idx), (src, idx)


def _gather_bwd(use_kernel, res, g):
    src, idx = res
    # gather^T: scatter-add rows back; sentinel rows drop out of range
    dsrc = jnp.zeros(src.shape, jnp.float32).at[idx].add(
        g.astype(jnp.float32), mode="drop")
    return dsrc.astype(src.dtype), None


moe_gather.defvjp(_gather_fwd, _gather_bwd)


# ---------------------------------------------------------------------------
# combine: k-way weighted row gather, f32 accumulation
# ---------------------------------------------------------------------------

def _combine_kernel(idx_ref, w_ref, src_ref, out_ref, *, rows, k, n_src):
    base = pl.program_id(0) * rows

    def body(i):
        acc = jnp.zeros((1, out_ref.shape[-1]), jnp.float32)
        for s in range(k):          # k is static and small (1/2)
            t = idx_ref[(base + i) * k + s]
            w = w_ref[(base + i) * k + s]
            valid = (t < n_src).astype(jnp.float32)
            safe = jnp.where(t < n_src, t, 0)
            row = src_ref[pl.ds(safe, 1), :].astype(jnp.float32)
            acc = acc + (w * valid) * row
        out_ref[pl.ds(i, 1), :] = acc.astype(out_ref.dtype)

    _for_each_row(rows, body)


def _combine_example(rng):
    d = int(rng.choice([128, 256]))
    k = int(rng.choice([1, 2]))
    m = int(rng.integers(12, 48))
    n = int(rng.integers(10, 150))
    src = rng.standard_normal((m, d)).astype(np.float32)
    idx = rng.integers(0, m + 1, size=(n, k)).astype(np.int32)
    w = rng.random((n, k)).astype(np.float32)
    return (src, idx, w), {}


@register_kernel(
    "moe_combine", example=_combine_example,
    fallback=lambda src, idx, w: combine_fallback(src, idx, w),
    tol=(1e-5, 1e-5),
    notes="k-way weighted gather, f32 accumulation in slot order")
def _combine_pallas(src, idx, w):
    if src.dtype != jnp.float32:
        return _widened(_combine_pallas, src, idx, w)
    n_src, d = src.shape
    n, k = idx.shape
    rows = _BLOCK_ROWS
    pad = (-n) % rows
    idx_p = _pad_to(idx.astype(jnp.int32), rows, n_src)
    w_p = _pad_to(w.astype(jnp.float32), rows, 0.0)
    n_pad = n + pad
    grid = (n_pad // rows,)
    out = pl.pallas_call(
        functools.partial(_combine_kernel, rows=rows, k=k,
                          n_src=n_src),
        name="moe_combine",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[pl.BlockSpec((n_src, d), lambda b, *_: (0, 0))],
            out_specs=pl.BlockSpec((rows, d),
                                   lambda b, *_: (b, 0)),
        ),
        out_shape=out_struct((n_pad, d), src.dtype, src, idx, w),
        cost_estimate=pl.CostEstimate(
            flops=2 * n_pad * k * d, transcendentals=0,
            bytes_accessed=(n_src + (k + 1) * n_pad) * d
            * src.dtype.itemsize),
        interpret=_interpret(),
    )(idx_p.reshape(-1), w_p.reshape(-1), src)
    return out[:n]


def combine_fallback(src, idx, w):
    """Pure-jnp combine: out[i] = sum_s w[i,s] * src[idx[i,s]] with the
    sentinel zero-filled, f32 accumulation like the kernel."""
    gathered = jnp.take(src, idx, axis=0, mode="fill",
                        fill_value=0).astype(jnp.float32)  # [n, k, d]
    out = jnp.sum(w.astype(jnp.float32)[..., None] * gathered, axis=1)
    return out.astype(src.dtype)


def _combine_impl(use_kernel, src, idx, w):
    if use_kernel is None:
        use_kernel = _auto_use_kernel(src)
    if use_kernel:
        return _combine_pallas(src, idx, w)
    return combine_fallback(src, idx, w)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def moe_combine(src, idx, w, use_kernel=None):
    """Weighted combine. src [m, d], idx [n, k] int32 in [0, m]
    (m == dropped), w [n, k] -> [n, d]. use_kernel as in moe_gather."""
    return _combine_impl(use_kernel, src, idx, w)


def _combine_fwd(src, idx, w, use_kernel):
    return _combine_impl(use_kernel, src, idx, w), (src, idx, w)


def _combine_bwd(use_kernel, res, g):
    src, idx, w = res
    g32 = g.astype(jnp.float32)
    n, k = idx.shape
    # combine^T wrt src: scatter-add w[i,s] * g[i] at idx[i,s]
    contrib = (w.astype(jnp.float32)[..., None] * g32[:, None, :])
    dsrc = jnp.zeros(src.shape, jnp.float32).at[
        idx.reshape(-1)].add(contrib.reshape(n * k, -1), mode="drop")
    # combine^T wrt w: dot of g[i] with the gathered row
    gathered = jnp.take(src, idx, axis=0, mode="fill",
                        fill_value=0).astype(jnp.float32)
    dw = jnp.sum(gathered * g32[:, None, :], axis=-1)
    return dsrc.astype(src.dtype), None, dw.astype(w.dtype)


moe_combine.defvjp(_combine_fwd, _combine_bwd)
