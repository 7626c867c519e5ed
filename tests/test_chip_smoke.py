"""chip_smoke.py must not rot between chip runs: every leg's body runs
here at the debug mode's toy sizes (kernels in the Pallas interpreter,
compiled-text checks skipped), the script refuses the CPU, and the
compile-cache helper places the cache where it says."""
import os
import sys

import jax
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import chip_smoke  # noqa: E402
from paddle_tpu import compile_cache  # noqa: E402


@pytest.fixture
def tiny(monkeypatch):
    monkeypatch.setattr(chip_smoke, "TINY", True)
    return chip_smoke.sizes()


@pytest.mark.parametrize("leg", ["train", "serve", "kernels", "four_chip"])
def test_leg_runs_at_toy_sizes(tiny, leg):
    out = getattr(chip_smoke, "leg_" + leg)(tiny)
    assert isinstance(out, dict), out       # four_chip: 8 virtual devices


def test_four_chip_leg_reports_when_not_run(tiny, monkeypatch):
    monkeypatch.setattr(jax, "device_count", lambda: 1)
    assert chip_smoke.leg_four_chip(tiny) == "not run: 1 device(s), needs 4"


def test_refuses_anything_but_a_tpu(capsys):
    assert not chip_smoke.TINY
    assert chip_smoke.main() == 2
    out = capsys.readouterr()
    assert '"ok"' not in out.out and "not 'tpu'" in out.err


def test_compile_cache_placement(monkeypatch):
    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
        assert compile_cache.enable() == "/some/dir"
        assert jax.config.jax_compilation_cache_dir == was   # left alone
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        checkout = os.path.dirname(os.path.abspath(chip_smoke.__file__))
        first = compile_cache.enable()
        assert first == compile_cache.enable() \
            == os.path.join(checkout, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == first
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
