"""Every registered Pallas kernel against its declared fallback, one
case each, in the interpreter (the KN504 check of analysis/kernel_lint
at one seed). The chip's own pass over the same registry is
chip_smoke.py's kernel leg; this is the CPU half, run in tier-1."""
import pytest

from paddle_tpu.analysis.kernel_lint import check_fallback_parity
from paddle_tpu.ops.kernel_registry import get_kernel, registered_kernels


@pytest.mark.parametrize("name", sorted(registered_kernels().names()))
def test_kernel_matches_its_fallback(name):
    findings = check_fallback_parity(get_kernel(name), seeds=(0,))
    assert findings == [], [f.message for f in findings]
