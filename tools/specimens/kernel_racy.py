"""Broken-kernel specimen: a RACY GRID (kerneldoctor --selfcheck).

A row-reduction kernel that accumulates partial sums into its output
block across the inner grid axis — the flash-attention accumulation
pattern — but marks BOTH grid axes `parallel` via dimension_semantics.
Under Mosaic's parallel execution the inner axis' revisits of one
output window flush in undefined order, silently corrupting the sums;
under the default sequential order (and in interpret mode) the kernel
is numerically correct, which is exactly why the defect needs a STATIC
check: no differential test on a sequential backend can see it.

The Kernel Doctor must catch this by name: KN501 evaluates the output
BlockSpec index_map over the grid, sees axis 1's points write
overlapping output blocks, and fails the parallel marking.
"""
import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.ops.kernel_registry import KernelRegistry, register_kernel

SPECIMENS = KernelRegistry()

_ROWS, _COLS, _NB = 16, 128, 4


def _kernel(x_ref, o_ref):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] += x_ref[...]


def _example(rng):
    x = rng.standard_normal((2 * _ROWS, _NB * _COLS)).astype(np.float32)
    return (x,), {}


def _fallback(x):
    r, c = x.shape
    return x.reshape(r, _NB, _COLS).sum(axis=1)


@register_kernel("specimen_racy_grid", example=_example,
                 fallback=_fallback, tol=(1e-4, 1e-4),
                 registry=SPECIMENS,
                 notes="deliberately parallel-marked accumulation axis")
def racy_row_reduce(x):
    """sum of the _NB column blocks of x — the inner grid axis j
    revisits each output window, so it MUST be sequential; the
    dimension_semantics below wrongly parallelize it."""
    r, c = x.shape
    return pl.pallas_call(
        _kernel,
        grid=(r // _ROWS, _NB),
        in_specs=[pl.BlockSpec((_ROWS, _COLS), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((_ROWS, _COLS), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((r, _COLS), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=jax.default_backend() != "tpu",
    )(x)
