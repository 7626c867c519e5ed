"""Pallas kernel registry + shared VMEM-projection math.

Every `pallas_call` site in the tree registers itself here with
`@register_kernel`, declaring the canonical example inputs that drive
the call and (when one exists) the exact fallback it must agree with.
The registry is what makes the kernel level statically checkable at
all: the Kernel Doctor (`paddle_tpu/analysis/kernel_lint.py`) walks it
and, per call site, derives grid races (KN501), VMEM footprints
(KN502), CostEstimate honesty (KN503), fallback parity (KN504) and
grid-spec sanity (KN505) — and `analysis/astlint.py` FW405 fails any
`pallas_call` under `paddle_tpu/` whose enclosing function is NOT
decorated, so a new kernel cannot dodge the checks by simply not
registering.

This module is dependency-light on purpose (jax/numpy only): the ops
modules import it for both the decorator and the VMEM budget/footprint
helpers, and `analysis/kernel_lint.py` imports it for the registry —
the layering runs one way (ops -> registry <- analysis).

VMEM model (single source; `moe_kernel_supported` and
`paged_decode_supported` delegate here): one grid program must hold

    2 x (every block whose index moves across the grid)   [double buffer]
  + 1 x (every block whose index is constant)             [fetched once]
  + 1 x (every scratch buffer)
  + temp_bytes                                  [in-kernel casts/temps]

under `VMEM_BUDGET` — the same conservative 10 MiB (of the ~16 MiB/core
on v5e) the decode and MoE gates have always used, leaving headroom for
the compiler's own temporaries.
"""
import functools
import re

import jax
import jax.numpy as jnp

__all__ = [
    "VMEM_BUDGET", "block_bytes", "vmem_footprint", "fits_vmem",
    "out_struct", "mosaic_custom_calls",
    "KernelRegistry", "PallasKernel", "register_kernel",
    "registered_kernels", "get_kernel", "KERNELS",
]

# conservative per-core VMEM budget (v5e has ~16 MiB/core; headroom for
# double-buffering slop and compiler temps) — formerly duplicated as
# `_VMEM_BUDGET` in ops/pallas_decode.py and moe/kernels.py
VMEM_BUDGET = 10 * 2 ** 20


def block_bytes(shape, dtype):
    """Bytes of one [shape] buffer of `dtype` (a dtype-like or an int
    itemsize)."""
    itemsize = dtype if isinstance(dtype, int) else jnp.dtype(dtype).itemsize
    n = 1
    for d in shape:
        n *= int(d)
    return n * int(itemsize)


def vmem_footprint(moving=(), resident=(), scratch=(), temp_bytes=0):
    """Projected VMEM bytes of one grid program (the KN502 model).

    `moving`: (shape, dtype) pairs whose block index changes across the
    grid — double-buffered (x2) so the next block's DMA overlaps
    compute. `resident`: pairs whose index_map is constant — fetched
    once, held (x1). `scratch`: pairs allocated once per core (x1).
    `temp_bytes`: in-kernel intermediates the blocks don't show (f32
    casts of low-precision inputs, logits/probs buffers).
    """
    total = int(temp_bytes)
    for shape, dtype in moving:
        total += 2 * block_bytes(shape, dtype)
    for shape, dtype in resident:
        total += block_bytes(shape, dtype)
    for shape, dtype in scratch:
        total += block_bytes(shape, dtype)
    return total


def fits_vmem(moving=(), resident=(), scratch=(), temp_bytes=0,
              budget=VMEM_BUDGET):
    """True when the projected footprint fits the per-core budget."""
    return vmem_footprint(moving, resident, scratch, temp_bytes) <= budget


def out_struct(shape, dtype, *operands):
    """One `pallas_call` out_shape entry, varying over the manual mesh
    axes its `operands` vary over. Inside a `jax.shard_map` (the MoE ep
    region, the mesh-aware flash attention) the varying-axes check
    refuses an output whose `vma` is unset; outside one the set is
    empty and this is a plain ShapeDtypeStruct."""
    vma = frozenset().union(*(jax.typeof(x).vma for x in operands))
    return jax.ShapeDtypeStruct(shape, dtype, vma=vma)


def mosaic_custom_calls(hlo_text):
    """The Mosaic kernels a compiled program (`compiled.as_text()`)
    really contains: one (pallas_call name, operand shapes) pair per
    `tpu_custom_call`, in program order. Every in-tree pallas_call is
    named after its registry entry, so this is how chip_smoke.py tells
    that a kernel ran as a kernel (the platform gates can only say it
    was asked for) and on which per-device shapes."""
    calls = []
    for line in hlo_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        # ".../paged_decode/pallas_call", or wrapped by the transforms
        # it was traced under: ".../transpose(jvp(flash_bwd_split_dq))/..."
        name = re.search(r'op_name="[^"]*?([\w.]+)\)*/pallas_call', line)
        operands = re.search(
            r"operand_layout_constraints=\{(.*?)\}, \w+=", line)
        shapes = re.findall(r"\w+\[[\d,]*\]", operands.group(1)) \
            if operands else []
        calls.append((name.group(1) if name else "?", tuple(shapes)))
    return calls


class PallasKernel:
    """One registered pallas_call site.

    `fn` is the enclosing function (it calls `pl.pallas_call` when
    invoked — possibly more than once, e.g. the split flash backward);
    `example(rng)` returns (args, kwargs) for a small canonical
    in-support invocation the Kernel Doctor can capture, trace and run
    under interpret mode on any backend; `fallback`, when declared, is
    an exact reference with the SAME signature whose outputs the KN504
    differential harness compares against within `tol = (rtol, atol)`.
    """

    __slots__ = ("name", "fn", "example", "fallback", "tol", "notes")

    def __init__(self, name, fn, example, fallback=None, tol=(1e-4, 1e-4),
                 notes=""):
        self.name = str(name)
        self.fn = fn
        self.example = example
        self.fallback = fallback
        self.tol = tuple(tol)
        self.notes = str(notes)

    @property
    def module(self):
        return getattr(self.fn, "__module__", "?")

    @property
    def fn_name(self):
        return getattr(self.fn, "__name__", "?")

    def __repr__(self):
        return (f"PallasKernel({self.name!r}, {self.module}.{self.fn_name}"
                f"{', fallback' if self.fallback else ''})")


class KernelRegistry:
    """Ordered name -> PallasKernel map. The module-level `KERNELS`
    instance is the in-tree registry; specimens and tests build their
    own scoped instances (``register_kernel(..., registry=mine)``)."""

    def __init__(self):
        self._kernels = {}

    def add(self, kernel):
        if kernel.name in self._kernels:
            raise ValueError(
                f"kernel {kernel.name!r} registered twice "
                f"({self._kernels[kernel.name].module} and "
                f"{kernel.module})")
        self._kernels[kernel.name] = kernel
        return kernel

    def get(self, name):
        return self._kernels[name]

    def names(self):
        return list(self._kernels)

    def __iter__(self):
        return iter(self._kernels.values())

    def __len__(self):
        return len(self._kernels)

    def __contains__(self, name):
        return name in self._kernels


KERNELS = KernelRegistry()


def register_kernel(name, example, fallback=None, tol=(1e-4, 1e-4),
                    notes="", registry=None):
    """Decorator registering a pallas_call-containing function.

    Returns the function UNCHANGED (no wrapper — registration must not
    perturb the hot path), so it stacks safely under `jax.custom_vjp`.
    `analysis/astlint.py` recognizes the decorator by name: a
    `pallas_call` inside an undecorated function is an FW405 finding.
    """
    reg = KERNELS if registry is None else registry

    def deco(fn):
        reg.add(PallasKernel(name, fn, example, fallback=fallback,
                             tol=tol, notes=notes))
        return fn
    return deco


@functools.lru_cache(maxsize=1)
def _load_inventory():
    # import every in-tree kernel module so its @register_kernel
    # decorators run; lru_cache keeps this a one-time side effect
    from . import pallas_attention  # noqa: F401
    from . import pallas_decode  # noqa: F401
    from . import pallas_int8  # noqa: F401
    from . import pallas_layernorm  # noqa: F401
    from . import pallas_mla  # noqa: F401
    from . import pallas_ssm  # noqa: F401
    from ..moe import kernels  # noqa: F401
    from ..moe import serving  # noqa: F401
    return True


def registered_kernels():
    """The in-tree registry, fully populated (imports every kernel
    module on first call)."""
    _load_inventory()
    return KERNELS


def get_kernel(name):
    return registered_kernels().get(name)
