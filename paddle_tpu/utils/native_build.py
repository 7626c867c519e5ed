"""Native library resolution/build shared by the ctypes runtimes.

Resolution order (reference analog: the prebuilt-vs-source duality of
`cmake/operators.cmake` op libraries):
  1. `paddle_tpu/_native/lib<name>.so` — prebuilt by `setup.py` /
     `cmake -S csrc` for installed packages;
  2. `csrc/build/lib<name>.so` next to the source checkout — built (and
     mtime-rebuilt) on demand with g++, so a dev tree needs no build step.
"""
import os
import subprocess
import tempfile
import threading

_lock = threading.Lock()

_FLAGS = ["-O3", "-std=c++17", "-shared", "-fPIC", "-pthread"]


def repo_csrc():
    return os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), "csrc")


def native_lib_path(name, source=None, extra_flags=()):
    """Absolute path to lib<name>.so, building from csrc on demand.
    `source` overrides the default `<name>.cc`; `extra_flags` appends
    compile/link flags (e.g. -ldl, -I... for the PJRT-based runner)."""
    pkg_native = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "_native", f"lib{name}.so")
    src = os.path.join(repo_csrc(), source or f"{name}.cc")
    if os.path.exists(pkg_native) and (
            not os.path.exists(src) or
            os.path.getmtime(pkg_native) >= os.path.getmtime(src)):
        return pkg_native
    if not os.path.exists(src):
        raise FileNotFoundError(
            f"native library {name!r}: neither a prebuilt "
            f"{pkg_native} nor source {src} exists")
    out_dir = os.path.join(repo_csrc(), "build")
    so = os.path.join(out_dir, f"lib{name}.so")
    with _lock:
        if (not os.path.exists(so) or
                os.path.getmtime(so) < os.path.getmtime(src)):
            os.makedirs(out_dir, exist_ok=True)
            inc = os.path.join(repo_csrc(), "third_party")
            # _lock orders this process's threads only: other processes
            # (xdist workers, loader children) build at the same time, so
            # each links into a name of its own and the rename publishes
            # a whole library whichever finishes last.
            fd, tmp = tempfile.mkstemp(prefix=f"lib{name}.", suffix=".so.tmp",
                                       dir=out_dir)
            os.close(fd)
            try:
                subprocess.run(["g++", *_FLAGS, f"-I{inc}", src,
                                "-o", tmp, *extra_flags],
                               check=True, capture_output=True)
                os.chmod(tmp, 0o755)    # mkstemp made it 0600
                os.replace(tmp, so)
            finally:
                if os.path.exists(tmp):
                    os.unlink(tmp)
    return so
