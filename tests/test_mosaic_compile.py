"""The serving path's Pallas kernels compiled by Mosaic, for a v5e that
is described and not attached (libtpu's compile-only client), at the
widths the benchmark's cells run. What interpret mode cannot see — an
unaligned slice, a relayout Mosaic refuses, too much VMEM — fails here
and costs no chip time. Nothing runs: numerics are the interpret-mode
tests' and chip_smoke.py's.

One file, and the topology only inside a fixture: a process keeps
libtpu once it has loaded it, and the suite runs under several
workers."""
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from paddle_tpu.ops import pallas_decode


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def mosaic(monkeypatch):
    """The kernels decide between Mosaic and the interpreter by the
    default backend, which is the CPU here."""
    monkeypatch.setattr(pallas_decode, "_interpret", lambda: False)
    # the entry is jitted: no trace made under the other answer may be
    # found again, by these tests or after them
    pallas_decode.paged_decode_attention.clear_cache()
    yield
    pallas_decode.paged_decode_attention.clear_cache()


@pytest.mark.parametrize("N,H,mb,dtype,rows", [
    (12, 64, 64, jnp.bfloat16, 512),      # gpt3-125m.serve-chat
    (16, 128, 128, jnp.bfloat16, 512),    # gpt3-1.3b.serve-long
    (40, 128, 128, jnp.bfloat16, 128),    # 13B wide: three rows of heads
    (4, 32, 3, jnp.float32, 128),         # the registry's example
])
def test_paged_decode_compiles_for_v5e(one_chip, mosaic, N, H, mb, dtype,
                                       rows):
    nh, bs, S, nb = N * H, 16, 32, 4 * mb
    assert pallas_decode.paged_decode_tile_rows(
        bs, nh, N, jnp.dtype(dtype).itemsize, mb) == rows

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    def attend(q, k, v, tables, ctx):
        return pallas_decode.paged_decode_attention(
            q, k, v, tables, ctx, N, use_kernel=True)

    compiled = jax.jit(attend).trace(
        sds((S, 1, nh), dtype), sds((nb, bs, nh), dtype),
        sds((nb, bs, nh), dtype), sds((S, mb), jnp.int32),
        sds((S,), jnp.int32)).lower(lowering_platforms=("tpu",)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "paged_decode" in text
