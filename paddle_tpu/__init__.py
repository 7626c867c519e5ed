"""paddle_tpu — a TPU-native deep learning framework.

Brand-new framework with the capabilities of the reference PaddlePaddle fork
(`/root/reference`), redesigned TPU-first: a single eager API whose autograd
tape records `jax.vjp` closures, so the same code runs eagerly (dygraph
analog) or traces under `paddle_tpu.jit.to_static` into one fused XLA program
(static-graph analog). Distribution is GSPMD sharding over a
`jax.sharding.Mesh` instead of NCCL program rewriting.
"""
__version__ = "0.1.0"

from .core.dtype import (  # noqa: F401
    bool, uint8, int8, int16, int32, int64, float16, bfloat16, float32,
    float64, complex64, complex128, set_default_dtype, get_default_dtype,
)
from .core.tensor import Tensor, Parameter, to_tensor  # noqa: F401
from .core.autograd import no_grad, enable_grad, set_grad_enabled, grad  # noqa: F401
from .core.random import seed, get_rng_state_tracker  # noqa: F401

from .tensor import *  # noqa: F401,F403
from .tensor import add_n  # noqa: F401

from . import tensor  # noqa: F401
from . import nn  # noqa: F401
from . import optimizer  # noqa: F401
from . import io  # noqa: F401
from . import jit  # noqa: F401
from . import amp  # noqa: F401
from . import metric  # noqa: F401
from . import framework  # noqa: F401
from . import device  # noqa: F401
from . import autograd  # noqa: F401
from . import utils  # noqa: F401
from . import enforce  # noqa: F401
from . import monitor  # noqa: F401
from . import cost_model  # noqa: F401
from . import telemetry  # noqa: F401
from . import resilience  # noqa: F401

from .framework import CPUPlace, TPUPlace, CUDAPlace, get_flags, set_flags  # noqa: F401
from .device import set_device, get_device, is_compiled_with_cuda  # noqa: F401
from .io.serialization import save, load  # noqa: F401

# heavier subpackages are imported lazily to keep import cost low
_LAZY = ("distributed", "vision", "text", "hapi", "profiler", "inference",
         "ops", "incubate", "static", "onnx", "fleet")


def __getattr__(name):
    if name in _LAZY:
        import importlib
        mod = importlib.import_module(f".{name}", __name__)
        globals()[name] = mod
        return mod
    if name == "Model":
        from .hapi.model import Model
        return Model
    if name == "DataParallel":
        from .distributed.parallel import DataParallel
        return DataParallel
    if name == "summary":
        from .hapi.summary import summary
        return summary
    if name == "flops":
        from .hapi.flops import flops
        return flops
    if name == "flops_compiled":
        from .hapi.flops import flops_compiled
        return flops_compiled
    if name == "callbacks":
        from .hapi import callbacks
        return callbacks
    raise AttributeError(f"module 'paddle_tpu' has no attribute {name!r}")


def disable_static(place=None):
    """No-op: paddle_tpu is always 'dygraph' (eager-traceable)."""


def enable_static():
    import warnings
    warnings.warn("paddle_tpu has no separate static mode; use "
                  "paddle_tpu.jit.to_static to compile", stacklevel=2)


def in_dynamic_mode():
    return True


def is_grad_enabled():
    from .core import autograd
    return autograd.grad_enabled()


# ---------------------------------------------------------------------------
# top-level API-parity shims (reference python/paddle/__init__.py surface)
# ---------------------------------------------------------------------------
from .nn import ParamAttr  # noqa: F401,E402
from . import fft  # noqa: F401,E402

VarBase = Tensor                       # 1.x alias
full_version = __version__
commit = "paddle-tpu-native"


def create_parameter(shape, dtype="float32", name=None, attr=None,
                     is_bias=False, default_initializer=None):
    """Standalone parameter factory (reference
    `fluid/layers/tensor.py create_parameter`)."""
    from .nn.layer.layers import Layer
    if attr is None and name is not None:
        attr = ParamAttr(name=name)
    helper = Layer()
    return helper.create_parameter(list(shape), attr=attr, dtype=dtype,
                                   is_bias=is_bias,
                                   default_initializer=default_initializer)


def batch(reader, batch_size, drop_last=False):
    """paddle.batch reader decorator (reference `fluid/../batch.py`)."""
    def batched():
        buf = []
        for item in reader():
            buf.append(item)
            if len(buf) == batch_size:
                yield buf
                buf = []
        if buf and not drop_last:
            yield buf
    return batched


def rank(input):  # noqa: A002
    """Number of dimensions, as a 0-d int Tensor (fluid.layers.rank)."""
    import numpy as _np
    n = input.ndim if hasattr(input, "ndim") else _np.asarray(input).ndim
    return Tensor(_np.asarray(n))


def set_printoptions(precision=None, threshold=None, edgeitems=None,
                     sci_mode=None, linewidth=None):
    """Tensor repr prints via numpy, so numpy's printoptions state is
    the single source of truth — just forward."""
    import numpy as _np
    kw = {}
    if precision is not None:
        kw["precision"] = precision
    if threshold is not None:
        kw["threshold"] = threshold
    if edgeitems is not None:
        kw["edgeitems"] = edgeitems
    if linewidth is not None:
        kw["linewidth"] = linewidth
    if sci_mode is not None:
        kw["suppress"] = not sci_mode
    _np.set_printoptions(**kw)


def enable_dygraph(place=None):
    """No-op: always eager."""


def disable_dygraph():
    import warnings
    warnings.warn("paddle_tpu has no static mode; use jit.to_static",
                  stacklevel=2)


def in_dygraph_mode():
    return True


def disable_signal_handler():
    """No-op (the reference unhooks its C++ fault handlers)."""


def is_compiled_with_xpu():
    return False


def is_compiled_with_npu():
    return False


def is_compiled_with_rocm():
    return False


def get_cuda_rng_state():
    """CUDA-API-parity shim: returns the framework RNG state."""
    from .core.random import default_generator
    return [default_generator().get_state()]


def set_cuda_rng_state(state_list):
    from .core.random import default_generator
    if state_list:
        default_generator().set_state(state_list[0])


# Place shims for API parity — framework.py owns the canonical aliases
from .framework import CUDAPinnedPlace, XPUPlace, NPUPlace  # noqa: F401,E402


def get_cudnn_version():
    return None                         # no cudnn in an XLA/TPU build


def check_shape(shape, op_name="check_shape",
                expected_shape_type=(list, tuple),
                expected_element_type=(int,),
                expected_tensor_dtype=("int32", "int64")):
    """Reference creation-op shape validation
    (`fluid/data_feeder.py:142`). A Tensor-valued shape is accepted when
    its dtype is in expected_tensor_dtype (the dynamic-shape program
    case); `all` must be the builtin — the tensor reduction op shadows
    it in this namespace."""
    import builtins
    import numpy as _np
    from .enforce import enforce
    from .core.tensor import Tensor
    if isinstance(shape, Tensor):
        enforce(str(shape.dtype).rsplit(".", 1)[-1] in expected_tensor_dtype,
                f"Tensor shape dtype must be one of "
                f"{expected_tensor_dtype}, got {shape.dtype}", op=op_name)
        return shape
    enforce(isinstance(shape, tuple(t for t in expected_shape_type
                                    if isinstance(t, type))),
            f"shape must be {expected_shape_type}, got {type(shape)}",
            op=op_name)
    shape = list(shape)
    ok = builtins.all(
        isinstance(s, tuple(expected_element_type) + (_np.integer,))
        and not isinstance(s, builtins.bool) for s in shape)
    enforce(ok, f"shape must be ints, got {shape}", op=op_name)
    return shape


def monkey_patch_math_varbase():
    """No-op: Tensor methods are registered at import time."""


def monkey_patch_variable():
    """No-op: there is no static Variable to patch."""


from .core import dtype  # noqa: F401,E402


from . import hub  # noqa: F401  (local-source hub + md5 weight loading)
from . import distribution  # noqa: F401
from . import sysconfig  # noqa: F401
from . import reader  # noqa: F401
from . import compat  # noqa: F401
from . import regularizer  # noqa: F401
from . import fluid  # noqa: F401  (legacy namespace shim)
