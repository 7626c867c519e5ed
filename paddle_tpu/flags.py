"""Runtime flag registry.

TPU-native analog of the reference's exported gflags + runtime get/set
(`paddle/fluid/platform/flags.cc:48` PADDLE_DEFINE_EXPORTED_*,
`paddle/fluid/pybind/global_value_getter_setter.cc`): one central registry of
typed, documented runtime switches, initialized from `FLAGS_<name>`
environment variables at import and mutable at runtime via
`paddle_tpu.set_flags`. Components read flags at use time through
`get_flag()`, so changes take effect immediately.

Only flags that actually do something here are registered — there is no
allocator/cudnn machinery to toggle (XLA owns both); compat names from the
reference that map to no-ops are intentionally NOT accepted, so a silently
ignored setting can't masquerade as tuning.
"""
import os
import threading

__all__ = ["set_flags", "get_flags", "get_flag"]


class _Flag:
    __slots__ = ("name", "value", "type", "help")

    def __init__(self, name, default, type_, help_):
        self.name = name
        self.value = default
        self.type = type_
        self.help = help_


_lock = threading.Lock()
_registry = {}


def _register(name, default, type_, help_):
    _registry[name] = _Flag(name, default, type_, help_)


def _coerce(flag, value):
    if flag.type is bool:
        if isinstance(value, str):
            return value.strip().lower() in ("1", "true", "yes", "on")
        return bool(value)
    return flag.type(value)


# ---------------------------------------------------------------------------
# the registry. Reference analogs noted per flag.
# ---------------------------------------------------------------------------
_register(
    "check_nan_inf", False, bool,
    "Assert every eager op output is finite (raises naming the op), and make "
    "TrainStep/ShardedTrainStep run a jitted finite check on loss and grads "
    "each step. Analog of FLAGS_check_nan_inf "
    "(`framework/details/nan_inf_utils_detail.cc:1`).")
_register(
    "benchmark", False, bool,
    "Synchronize (block_until_ready) after every eager op so timings "
    "attribute to the right op. Analog of FLAGS_benchmark (`flags.cc`).")
_register(
    "pallas_attention_min_seq", 512, int,
    "Sequence length at which attention dispatch switches from the composed "
    "XLA path to the Pallas blockwise kernel. XLA won at <=256, Pallas "
    "1.77x at 512, 2.6x at 1024, 3.0x at 2048: measured on an earlier "
    "revision (jax 0.4.37), not since.")
_register(
    "use_pallas_decode_attention", True, bool,
    "Use the fused Pallas decode-attention kernel (ops/pallas_decode.py)"
    " for q_len==1 KV-cache attention when shapes qualify (TPU, cache "
    "len %8==0, n_heads*head_dim %128==0). One kernel per layer instead "
    "of the einsum+mask+softmax+einsum chain; 91 vs 117 us per call at "
    "B=64/L=256, measured on an earlier revision (jax 0.4.37), not "
    "since.")
_register(
    "use_fused_ce", False, bool,
    "Use the chunked fused projection+cross-entropy for LM losses "
    "(ops/fused_ce.py): the full-vocab logits tensor is never "
    "materialized; backward recomputes chunk logits (flash-style). "
    "Off falls back to logits + F.cross_entropy.")
_register(
    "use_pallas_attention", True, bool,
    "Master switch for the Pallas flash-attention kernel; off forces the "
    "composed XLA attention everywhere.")
_register(
    "io_prefetch_capacity", 8, int,
    "Staging-slot count for the native C++ record loader "
    "(csrc/ptio.cc pool size).")
_register(
    "check_nan_inf_level", 0, int,
    "0: raise on non-finite. 1: print a warning and continue. Analog of the "
    "reference's FLAGS_check_nan_inf_level granularity.")


def _init_from_env():
    for name, flag in _registry.items():
        env = os.environ.get("FLAGS_" + name)
        if env is not None:
            try:
                flag.value = _coerce(flag, env)
            except (TypeError, ValueError):
                raise ValueError(
                    f"FLAGS_{name}={env!r} is not a valid {flag.type.__name__}")


_init_from_env()


def set_flags(flags):
    """paddle.set_flags analog: update registered runtime flags.

    Raises on unknown names — an unknown flag silently accepted would be a
    no-op pretending to work.
    """
    if not isinstance(flags, dict):
        raise TypeError("set_flags expects a dict of {name: value}")
    with _lock:
        for name, value in flags.items():
            key = name[6:] if name.startswith("FLAGS_") else name
            flag = _registry.get(key)
            if flag is None:
                raise ValueError(
                    f"unknown flag {name!r}; known: {sorted(_registry)}")
            flag.value = _coerce(flag, value)


def get_flags(flags=None):
    """paddle.get_flags analog: read one, several, or all flags."""
    if flags is None:
        names = sorted(_registry)
    elif isinstance(flags, str):
        names = [flags]
    else:
        names = list(flags)
    out = {}
    for name in names:
        key = name[6:] if name.startswith("FLAGS_") else name
        flag = _registry.get(key)
        if flag is None:
            raise ValueError(
                f"unknown flag {name!r}; known: {sorted(_registry)}")
        out[name] = flag.value
    return out


def get_flag(name):
    """Fast single-flag read for hot paths."""
    return _registry[name].value


def flag_docs():
    """name -> help text, for documentation/tooling."""
    return {name: f.help for name, f in sorted(_registry.items())}
