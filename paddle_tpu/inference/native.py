"""ctypes driver for the native C++ serving runner (csrc/predictor.cc).

Reference analog: the Python face of the C inference API
(`paddle/fluid/inference/capi_exp/pd_inference_api.h` — the reference
ships C and Go embeddings of AnalysisPredictor; here the embedding
surface is one C ABI with this thin ctypes client over it). The runner
itself links no Python: this module exists for tests and for Python
hosts that want the out-of-process-style engine in-process.

Usage:
    pred = NativePredictor(artifact_base, plugin_path)
    outs = pred.run([np_array, ...])        # list of np arrays
"""
import ctypes
import os

import numpy as np

_DTYPES = {
    "f32": np.float32, "f64": np.float64, "f16": np.float16,
    "s8": np.int8, "s16": np.int16, "s32": np.int32, "s64": np.int64,
    "u8": np.uint8, "u16": np.uint16, "u32": np.uint32, "u64": np.uint64,
    "pred": np.bool_,
}


def _bf16():
    import ml_dtypes
    return ml_dtypes.bfloat16


def _runner_lib():
    from ..utils.native_build import native_lib_path
    return native_lib_path("ptpredictor", source="predictor.cc",
                           extra_flags=["-ldl"])


def default_plugin_path():
    """The PJRT plugin .so to load: an explicit
    PJRT_PLUGIN_LIBRARY_PATH wins, then the installed `libtpu`
    package's library; tests pass the mock explicitly."""
    env = os.environ.get("PJRT_PLUGIN_LIBRARY_PATH")
    if env:
        return env
    try:
        import libtpu
    except ImportError:
        raise FileNotFoundError(
            "no PJRT plugin found: the libtpu package is not installed; "
            "set PJRT_PLUGIN_LIBRARY_PATH") from None
    return libtpu.get_library_path()


class NativePredictor:
    def __init__(self, artifact_base, plugin_path=None):
        lib_path = _runner_lib()
        self._lib = ctypes.CDLL(str(lib_path))
        self._lib.ptp_create.restype = ctypes.c_void_p
        self._lib.ptp_create.argtypes = [
            ctypes.c_char_p, ctypes.c_char_p, ctypes.c_char_p,
            ctypes.c_int]
        self._lib.ptp_io_dtype.restype = ctypes.c_char_p
        self._lib.ptp_io_dtype.argtypes = [ctypes.c_void_p, ctypes.c_int,
                                           ctypes.c_int]
        self._lib.ptp_io_bytes.restype = ctypes.c_int64
        self._lib.ptp_io_bytes.argtypes = [ctypes.c_void_p, ctypes.c_int,
                                           ctypes.c_int]
        self._lib.ptp_io_rank.argtypes = [ctypes.c_void_p, ctypes.c_int,
                                          ctypes.c_int]
        self._lib.ptp_io_shape.argtypes = [
            ctypes.c_void_p, ctypes.c_int, ctypes.c_int,
            ctypes.POINTER(ctypes.c_int64)]
        self._lib.ptp_num_inputs.argtypes = [ctypes.c_void_p]
        self._lib.ptp_num_outputs.argtypes = [ctypes.c_void_p]
        self._lib.ptp_run.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_void_p),
            ctypes.POINTER(ctypes.c_void_p), ctypes.c_char_p,
            ctypes.c_int]
        self._lib.ptp_destroy.argtypes = [ctypes.c_void_p]

        plugin = plugin_path or default_plugin_path()
        err = ctypes.create_string_buffer(2048)
        self._h = self._lib.ptp_create(
            str(artifact_base).encode(), str(plugin).encode(), err,
            len(err))
        if not self._h:
            raise RuntimeError(
                f"native predictor create failed: "
                f"{err.value.decode(errors='replace')}")

    def _spec(self, is_input, i):
        rank = self._lib.ptp_io_rank(self._h, is_input, i)
        dims = (ctypes.c_int64 * max(rank, 1))()
        if rank > 0:
            self._lib.ptp_io_shape(self._h, is_input, i, dims)
        code = self._lib.ptp_io_dtype(self._h, is_input, i).decode()
        dt = _bf16() if code == "bf16" else _DTYPES[code]
        return tuple(dims[:rank]), np.dtype(dt)

    @property
    def input_specs(self):
        n = self._lib.ptp_num_inputs(self._h)
        return [self._spec(1, i) for i in range(n)]

    @property
    def output_specs(self):
        n = self._lib.ptp_num_outputs(self._h)
        return [self._spec(0, i) for i in range(n)]

    def run(self, inputs):
        ispecs = self.input_specs
        if len(inputs) != len(ispecs):
            raise ValueError(
                f"expected {len(ispecs)} inputs, got {len(inputs)}")
        arrs = []
        for a, (shape, dt) in zip(inputs, ispecs):
            a = np.ascontiguousarray(np.asarray(a), dtype=dt)
            if tuple(a.shape) != shape:
                raise ValueError(
                    f"input shape {a.shape} != exported {shape} (the "
                    "native runner serves static shapes)")
            arrs.append(a)
        outs = [np.empty(shape, dt) for shape, dt in self.output_specs]
        in_ptrs = (ctypes.c_void_p * len(arrs))(
            *[a.ctypes.data_as(ctypes.c_void_p).value for a in arrs])
        out_ptrs = (ctypes.c_void_p * len(outs))(
            *[o.ctypes.data_as(ctypes.c_void_p).value for o in outs])
        err = ctypes.create_string_buffer(2048)
        rc = self._lib.ptp_run(self._h, in_ptrs, out_ptrs, err, len(err))
        if rc != 0:
            raise RuntimeError(
                f"native predictor run failed rc={rc}: "
                f"{err.value.decode(errors='replace')}")
        return outs

    def close(self):
        if getattr(self, "_h", None):
            self._lib.ptp_destroy(self._h)
            self._h = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass
