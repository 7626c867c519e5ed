"""The benchmark's own tests: run by hand with `pytest benchmark/tests`
(they are not part of tests/). Everything runs on the CPU at toy sizes;
nothing here prints or asserts a device metric."""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
