"""Native C++ PJRT serving runner (csrc/predictor.cc).

Hermetic tier: the mock identity plugin (csrc/pjrt_mock_plugin.cc)
proves artifact loading, signature parsing, buffer marshaling, the
PJRT call sequence, and error surfaces — the reference-test analog of
running against `ps_local_client.cc` instead of the brpc service.
Hardware tier (opt-in, PT_NATIVE_TPU_TEST=1): compiles the real
exported StableHLO through the installed libtpu and compares numerics
with the in-process Python predictor.
"""
import os

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn
from paddle_tpu.inference.native import NativePredictor
from paddle_tpu.utils.native_build import native_lib_path


def _mock_plugin():
    return native_lib_path("pjrt_mock", source="pjrt_mock_plugin.cc",
                           extra_flags=["-ldl"])


def _write_artifact(base, sig_lines, code=b"MOCK-IDENTITY"):
    with open(base + ".mlir", "wb") as f:
        f.write(code)
    with open(base + ".sig", "w") as f:
        f.write("version 1\n" + "\n".join(sig_lines) + "\n")


def test_mock_identity_roundtrip(tmp_path):
    base = str(tmp_path / "m")
    _write_artifact(base, ["input x0 f32 2,3", "input x1 s32 4",
                           "output out0 f32 2,3", "output out1 s32 4"])
    pred = NativePredictor(base, _mock_plugin())
    assert pred.input_specs == [((2, 3), np.dtype(np.float32)),
                                ((4,), np.dtype(np.int32))]
    a = np.arange(6, dtype=np.float32).reshape(2, 3) * 1.5
    b = np.array([9, -7, 5, 3], np.int32)
    o0, o1 = pred.run([a, b])
    np.testing.assert_array_equal(o0, a)
    np.testing.assert_array_equal(o1, b)
    # ZeroCopy contract: caller buffers, repeated runs
    o0b, _ = pred.run([a * 2, b])
    np.testing.assert_array_equal(o0b, a * 2)
    pred.close()


def test_mock_bf16_and_scalar(tmp_path):
    import ml_dtypes
    base = str(tmp_path / "m")
    _write_artifact(base, ["input x0 bf16 8", "output out0 bf16 8"])
    pred = NativePredictor(base, _mock_plugin())
    a = np.arange(8, dtype=ml_dtypes.bfloat16)
    (o,) = pred.run([a])
    np.testing.assert_array_equal(o.view(np.uint16), a.view(np.uint16))
    pred.close()


def test_shape_mismatch_and_input_count_errors(tmp_path):
    base = str(tmp_path / "m")
    _write_artifact(base, ["input x0 f32 2,3", "output out0 f32 2,3"])
    pred = NativePredictor(base, _mock_plugin())
    with pytest.raises(ValueError, match="static shapes"):
        pred.run([np.zeros((3, 2), np.float32)])
    with pytest.raises(ValueError, match="expected 1 inputs"):
        pred.run([np.zeros((2, 3), np.float32)] * 2)
    pred.close()


def test_compile_error_surfaces_plugin_message(tmp_path):
    base = str(tmp_path / "m")
    _write_artifact(base, ["input x0 f32 2", "output out0 f32 2"],
                    code=b"NOT-A-PROGRAM")
    with pytest.raises(RuntimeError, match="MOCK-IDENTITY"):
        NativePredictor(base, _mock_plugin())


def test_missing_artifact_and_dynamic_dims(tmp_path):
    base = str(tmp_path / "absent")
    with pytest.raises(RuntimeError, match=r"\.mlir"):
        NativePredictor(base, _mock_plugin())
    base2 = str(tmp_path / "dyn")
    _write_artifact(base2, ["input x0 f32 -1,3", "output out0 f32 -1,3"])
    with pytest.raises(RuntimeError, match="static shapes"):
        NativePredictor(base2, _mock_plugin())


def test_export_writes_native_sidecars(tmp_path):
    """save_inference_model emits the portable .mlir bytecode + .sig the
    C runner consumes; the sig matches the exported shapes/dtypes."""
    from paddle_tpu.inference.export import save_inference_model
    from paddle_tpu.jit import InputSpec
    paddle.seed(0)
    net = nn.Sequential(nn.Linear(4, 8), nn.ReLU(), nn.Linear(8, 2))
    base = str(tmp_path / "lin")
    save_inference_model(base, net,
                         input_spec=[InputSpec([3, 4], "float32")])
    blob = open(base + ".mlir", "rb").read()
    assert blob[:4] == b"ML\xefR"        # StableHLO bytecode magic
    sig = open(base + ".sig").read().splitlines()
    assert "input x0 f32 3,4" in sig
    assert "output out0 f32 3,2" in sig


def test_smoke_binary_runs_against_mock(tmp_path):
    """The pure-C++ demo binary (no Python linked) serves the artifact
    through the same C ABI."""
    import subprocess
    smoke = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "..", "csrc", "build", "predictor_smoke")
    if not os.path.exists(smoke):
        pytest.skip("predictor_smoke not built (run cmake in csrc)")
    base = str(tmp_path / "m")
    _write_artifact(base, ["input x0 f32 2,2", "output out0 f32 2,2"])
    out = subprocess.run([smoke, base, str(_mock_plugin())],
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert "OK" in out.stdout and "output 0" in out.stdout


@pytest.mark.skipif(os.environ.get("PT_NATIVE_TPU_TEST") != "1",
                    reason="needs a TPU host (set PT_NATIVE_TPU_TEST=1)")
def test_real_plugin_matches_python_predictor(tmp_path):
    """LeNet served through the real PJRT plugin with no Python in the
    engine path; outputs match the in-process Python predictor."""
    from paddle_tpu.inference.export import (save_inference_model,
                                             load_inference_model)
    from paddle_tpu.inference.native import default_plugin_path
    from paddle_tpu.jit import InputSpec
    from paddle_tpu.vision.models import LeNet
    paddle.seed(0)
    net = LeNet(num_classes=10)
    net.eval()
    base = str(tmp_path / "lenet")
    save_inference_model(base, net,
                         input_spec=[InputSpec([2, 1, 28, 28],
                                               "float32")])
    x = np.random.RandomState(0).randn(2, 1, 28, 28).astype(np.float32)
    ref = load_inference_model(base)(paddle.to_tensor(x))
    ref = ref[0].numpy() if isinstance(ref, list) else ref.numpy()
    pred = NativePredictor(base, default_plugin_path())
    (out,) = pred.run([x])
    np.testing.assert_allclose(out, ref, rtol=2e-3, atol=2e-3)
    pred.close()


def test_go_api_roundtrip(tmp_path):
    """Go serving wrapper (csrc/goapi/, reference goapi/lib.go analog):
    build the mock plugin + libptp + an identity artifact, then drive
    the cgo wrapper's own round-trip test. Gated on a go toolchain."""
    import shutil
    import subprocess
    go = shutil.which("go")
    if go is None:
        pytest.skip("go toolchain not installed")
    base = str(tmp_path / "m")
    _write_artifact(base, ["input x0 f32 2,3", "output out0 f32 2,3"])
    plugin = _mock_plugin()
    libptp = native_lib_path("ptpredictor", source="predictor.cc",
                             extra_flags=["-ldl"])
    import pathlib
    goapi = str(pathlib.Path(__file__).resolve().parent.parent
                / "csrc" / "goapi")
    env = dict(os.environ, PTP_ARTIFACT=base, PTP_PLUGIN=plugin,
               PTP_LIB=libptp)
    r = subprocess.run([go, "test", "-count=1", "./..."], cwd=goapi,
                       env=env, capture_output=True, text=True,
                       timeout=300)
    assert r.returncode == 0, r.stdout + r.stderr
