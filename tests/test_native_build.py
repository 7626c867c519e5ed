"""native_lib_path under several processes at once.

The driver's tier-1 run starts six xdist workers that all import the
native-IO tests at collection, on a checkout whose csrc/build/ does not
exist yet: every worker builds the same library at the same time.
"""
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NATIVE_BUILD = os.path.join(REPO, "paddle_tpu", "utils", "native_build.py")

# one builder: loads native_build.py by path (no paddle_tpu, no JAX),
# points it at the scratch csrc, waits for the start signal so all six
# reach g++ together, then builds and dlopens the result
_CHILD = r"""
import ctypes, importlib.util, os, sys, time
native_build_py, csrc, name, go = sys.argv[1:5]
spec = importlib.util.spec_from_file_location("_native_build", native_build_py)
nb = importlib.util.module_from_spec(spec)
spec.loader.exec_module(nb)
nb.repo_csrc = lambda: csrc
while not os.path.exists(go):
    time.sleep(0.005)
path = nb.native_lib_path(name)
assert path == os.path.join(csrc, "build", "lib" + name + ".so"), path
ctypes.CDLL(path)
"""


@pytest.mark.skipif(shutil.which("g++") is None, reason="no g++ on PATH")
@pytest.mark.parametrize("name", ["ptio", "pskv", "kvstore"])
def test_six_processes_build_one_library(name, tmp_path):
    csrc = tmp_path / "csrc"
    csrc.mkdir()
    src_csrc = os.path.join(REPO, "csrc")
    os.symlink(os.path.join(src_csrc, f"{name}.cc"), csrc / f"{name}.cc")
    os.symlink(os.path.join(src_csrc, "third_party"), csrc / "third_party")
    go = tmp_path / "go"
    procs = [subprocess.Popen(
        [sys.executable, "-c", _CHILD, NATIVE_BUILD, str(csrc), name, str(go)],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
        for _ in range(6)]
    go.write_text("")
    outs = [p.communicate(timeout=300)[0] for p in procs]
    assert [p.returncode for p in procs] == [0] * 6, "\n".join(outs)
    # every temporary name was renamed or removed
    assert os.listdir(csrc / "build") == [f"lib{name}.so"]
