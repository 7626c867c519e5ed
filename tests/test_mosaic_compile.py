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
    jitted = (pallas_decode.paged_decode_attention,
              pallas_decode.flash_prefill_chunk,
              pallas_decode.window_prefill_chunk)
    monkeypatch.setattr(pallas_decode, "_interpret", lambda: False)
    # the entries are jitted: no trace made under the other answer may
    # be found again, by these tests or after them
    for fn in jitted:
        fn.clear_cache()
    yield
    for fn in jitted:
        fn.clear_cache()


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


@pytest.mark.parametrize("N,H,mb,C,dtype,tiling", [
    (12, 64, 64, 128, jnp.bfloat16, (768, 512)),     # gpt3-125m.serve-chat
    (16, 128, 128, 128, jnp.bfloat16, (2048, 256)),  # gpt3-1.3b.serve-long
    (40, 128, 128, 128, jnp.bfloat16, (1280, 512)),  # 13B: groups of columns
    (4, 32, 3, 16, jnp.float32, (128, 128)),         # the registry's example
])
def test_flash_prefill_chunk_compiles_for_v5e(one_chip, mosaic, N, H, mb, C,
                                              dtype, tiling):
    """The prefill-chunk kernel at the cells' widths: pairs of 64-lane
    heads in a column and whole 128-lane heads, all columns a grid step
    on whole-page copies; at 13B's width a group of columns a step on
    lane-sliced copies."""
    nh, bs, nb = N * H, 16, 4 * mb
    assert pallas_decode.flash_prefill_tiling(
        bs, C, nh, N, jnp.dtype(dtype).itemsize, mb) == tiling

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    def attend(q, k, v, table_row, p0, n_real):
        return pallas_decode.flash_prefill_chunk(
            q, k, v, table_row, p0, N, use_kernel=True, n_real=n_real)

    text = jax.jit(attend).trace(
        sds((1, C, nh), dtype), sds((nb, bs, nh), dtype),
        sds((nb, bs, nh), dtype), sds((mb,), jnp.int32),
        sds((), jnp.int32), sds((), jnp.int32)).lower(
            lowering_platforms=("tpu",)).compile().as_text()
    assert "tpu_custom_call" in text and "flash_prefill_chunk" in text


@pytest.fixture
def mosaic_mla(monkeypatch):
    from paddle_tpu.moe import serving as moe_serving
    from paddle_tpu.ops import pallas_mla
    jitted = (pallas_mla.mla_paged_decode, pallas_mla.mla_prefill_chunk,
              moe_serving.moe_grouped_ffn)
    monkeypatch.setattr(pallas_mla, "_interpret", lambda: False)
    monkeypatch.setattr(moe_serving, "_interpret", lambda: False)
    for fn in jitted:
        fn.clear_cache()
    yield pallas_mla, moe_serving
    for fn in jitted:
        fn.clear_cache()


@pytest.mark.parametrize("step", ["decode", "chunk"])
def test_latent_attention_compiles_for_v5e(one_chip, mosaic_mla, step):
    """deepseek-v2.serve-docs: 128 heads over 640-lane rows (576
    numbers), 512 of them the value, tables of 576 blocks; a chunk of
    512 with its number of real positions traced (one program for every
    length of question)."""
    pallas_mla, _ = mosaic_mla
    N, W, rank, bs, mb, nb = 128, 640, 512, 16, 576, 2048
    bf16 = jnp.bfloat16

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    if step == "decode":
        assert pallas_mla.mla_tile_rows(bs, W, rank, N, 2, mb) == 512
        fn = lambda q, a, t, c: pallas_mla.mla_paged_decode(
            q, a, t, c, rank, 0.11, use_kernel=True)
        args = (sds((32, N, W), bf16), sds((nb, bs, W), bf16),
                sds((32, mb), jnp.int32), sds((32,), jnp.int32))
    else:
        fn = lambda q, a, t, p0, n_real: pallas_mla.mla_prefill_chunk(
            q, a, t, p0, rank, 0.11, use_kernel=True, n_real=n_real)
        args = (sds((512, N, W), bf16), sds((nb, bs, W), bf16),
                sds((mb,), jnp.int32), sds((), jnp.int32),
                sds((), jnp.int32))
    text = jax.jit(fn).trace(*args).lower(
        lowering_platforms=("tpu",)).compile().as_text()
    assert "tpu_custom_call" in text
    assert ("mla_paged_decode" if step == "decode"
            else "mla_prefill_chunk") in text


def _no_layout(text, n_tiles, rows, tokens, k, d):
    """The compiled program holds no array of the layout's rows
    [n_tiles * rows, d] nor of one row a pair [tokens * k, d]: the
    kernel gathers and sums in VMEM."""
    for n in (n_tiles * rows, tokens * k):
        assert f"[{n},{d}]" not in text, n


@pytest.mark.parametrize("tokens,rows", [(32, 16), (512, 80)])
def test_grouped_expert_ffn_compiles_for_v5e(one_chip, mosaic_mla, tokens,
                                             rows):
    """The held experts' products at DeepSeek-V2's widths (the cell's 40
    experts held of the router's 160), for a decode batch and for a
    chunk, at the tile `expert_tile_rows` picks for each: Mosaic holds
    the kernel to its VMEM limit, x and y held whole, here and not
    first on the chip."""
    _, moe_serving = mosaic_mla
    d, f, E = 5120, 1536, 40
    bf16 = jnp.bfloat16
    assert moe_serving.expert_tile_rows(tokens, 6, 160, d, f, 2) == rows
    assert moe_serving._resident(rows, tokens, d, f, 2)

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    def fn(x, live, w, e, wg, wu, wd):
        return moe_serving.held_expert_ffn(x, live, w, e, (0, E), wg, wu,
                                           wd, use_kernel=True,
                                           n_experts=160)[0]

    text = jax.jit(fn).trace(
        sds((tokens, d), bf16), sds((tokens,), jnp.bool_),
        sds((tokens, 6), jnp.float32), sds((tokens, 6), jnp.int32),
        sds((E, d, f), bf16), sds((E, d, f), bf16),
        sds((E, f, d), bf16)).lower(
            lowering_platforms=("tpu",)).compile().as_text()
    assert "tpu_custom_call" in text and "moe_grouped_ffn" in text
    _no_layout(text, -(-tokens * 6 // rows) + E, rows, tokens, 6, d)


@pytest.mark.parametrize("name", ["mla_paged_decode", "mla_prefill_chunk",
                                  "moe_grouped_ffn"])
def test_registry_example_compiles_for_v5e(one_chip, mosaic_mla, name):
    """chip_smoke.py's kernel leg runs every registered kernel's own
    example through Mosaic; a latent row that is not whole lanes passed
    the interpreter and failed there."""
    import numpy as np
    from paddle_tpu.ops.kernel_registry import registered_kernels
    reg = next(r for r in registered_kernels() if r.name == name)
    args, kwargs = reg.example(np.random.default_rng(0))
    arrays = [i for i, a in enumerate(args) if isinstance(a, np.ndarray)]

    def fn(*xs):
        full = list(args)
        for i, x in zip(arrays, xs):
            full[i] = x
        return reg.fn(*full, **kwargs)

    text = jax.jit(fn).trace(*[
        jax.ShapeDtypeStruct(args[i].shape, args[i].dtype, sharding=one_chip)
        for i in arrays]).lower(lowering_platforms=("tpu",)).compile() \
        .as_text()
    assert "tpu_custom_call" in text and name in text


# -- granite-4.0-h-micro.serve-many -------------------------------------

@pytest.mark.parametrize("step", ["decode", "chunk"])
def test_grouped_query_attention_compiles_for_v5e(one_chip, mosaic, step):
    """32 query heads over 8 K/V heads of 64: arenas 512 lanes wide,
    tables of 160 blocks, 64 slots, chunks of 512."""
    N, Nk, H, bs, mb, nb = 32, 8, 64, 16, 160, 2048
    bf16 = jnp.bfloat16

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    kw = dict(use_kernel=True, kv_heads=Nk, scale=0.015625)
    pages = sds((nb, bs, Nk * H), bf16)
    if step == "decode":
        assert pallas_decode.paged_decode_tile_rows(
            bs, Nk * H, Nk, 2, mb, N // Nk) == 512
        fn = lambda q, k, v, t, c: pallas_decode.paged_decode_attention(
            q, k, v, t, c, N, **kw)
        args = (sds((64, 1, N * H), bf16), pages, pages,
                sds((64, mb), jnp.int32), sds((64,), jnp.int32))
    else:
        assert pallas_decode.flash_prefill_tiling(
            bs, 512, Nk * H, Nk, 2, mb)[1] > 0
        fn = lambda q, k, v, t, p0, n: pallas_decode.flash_prefill_chunk(
            q, k, v, t, p0, N, n_real=n, **kw)
        args = (sds((1, 512, N * H), bf16), pages, pages,
                sds((mb,), jnp.int32), sds((), jnp.int32),
                sds((), jnp.int32))
    text = jax.jit(fn).trace(*args).lower(
        lowering_platforms=("tpu",)).compile().as_text()
    assert "tpu_custom_call" in text
    assert ("paged_decode" if step == "decode"
            else "flash_prefill_chunk") in text


@pytest.fixture
def mosaic_ssm(monkeypatch):
    from paddle_tpu.ops import pallas_ssm
    jitted = (pallas_ssm.mamba2_state_step, pallas_ssm.mamba2_chunk_scan)
    monkeypatch.setattr(pallas_ssm, "_interpret", lambda: False)
    for fn in jitted:
        fn.clear_cache()
    yield pallas_ssm
    for fn in jitted:
        fn.clear_cache()


@pytest.mark.parametrize("kernel", ["mamba2_state_step",
                                    "mamba2_chunk_scan"])
def test_mamba2_kernels_compile_for_v5e(one_chip, mosaic_ssm, kernel):
    """64 heads of 64 over a state of 128: 65 rows of [128, 4096]
    float32, 64 slots; a chunk of 512 in pieces of 256."""
    ssm = mosaic_ssm
    N, D, H, S, C = 128, 4096, 64, 64, 512
    f32, bf16 = jnp.float32, jnp.bfloat16

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    if kernel == "mamba2_state_step":
        assert ssm.state_step_tile(N, D) == 2048
        fn = lambda *a: ssm.mamba2_state_step(*a, use_kernel=True)
        args = (sds((S + 1, N, D), f32), sds((S,), jnp.int32),
                sds((S,), jnp.bool_), sds((S, D), f32), sds((S, D), f32),
                sds((S, N), bf16), sds((S, N), bf16))
    else:
        assert ssm.chunk_scan_tile(C, 256, N, D, D // H) == 512
        fn = lambda *a: ssm.mamba2_chunk_scan(*a, piece=256,
                                              use_kernel=True)
        args = (sds((C, D), bf16), sds((C, H), f32), sds((H,), f32),
                sds((C, N), bf16), sds((C, N), bf16), sds((N, D), f32))
    text = jax.jit(fn).trace(*args).lower(
        lowering_platforms=("tpu",)).compile().as_text()
    assert "tpu_custom_call" in text and kernel in text


@pytest.mark.parametrize("name", ["mamba2_state_step", "mamba2_chunk_scan"])
def test_ssm_registry_example_compiles_for_v5e(one_chip, mosaic_ssm, name):
    import numpy as np
    from paddle_tpu.ops.kernel_registry import registered_kernels
    reg = next(r for r in registered_kernels() if r.name == name)
    args, kwargs = reg.example(np.random.default_rng(0))

    def fn(*xs):
        return reg.fn(*xs, **kwargs)

    text = jax.jit(fn).trace(*[
        jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
        for a in args]).lower(lowering_platforms=("tpu",)).compile() \
        .as_text()
    assert "tpu_custom_call" in text and name in text


# -- k-exaone-236b-a23b.serve-mixed -------------------------------------

@pytest.mark.parametrize("step", ["decode", "chunk", "ring_decode",
                                  "ring_chunk", "ring_chunk_example"])
def test_window_and_full_attention_compile_for_v5e(one_chip, mosaic, step):
    """64 query heads over 8 K/V heads of 128: queries 8,192 lanes wide,
    arenas 1,024. A full layer's tables hold 832 blocks of 16 (13,312
    positions); a window layer's ring is one 128-row page a request, 65
    of them; 64 slots, chunks of 512."""
    N, Nk, H, W, bs, mb, nb = 64, 8, 128, 128, 16, 832, 16384
    S, C, bf16, i32 = 64, 512, jnp.bfloat16, jnp.int32

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    kw = dict(use_kernel=True, kv_heads=Nk, scale=H ** -0.5)
    pages, rings = sds((nb, bs, Nk * H), bf16), sds((S + 1, W, Nk * H), bf16)
    name = {"decode": "paged_decode", "chunk": "flash_prefill_chunk",
            "ring_decode": "paged_decode_window"}.get(
                step, "window_prefill_chunk")
    if step == "decode":
        assert pallas_decode.paged_decode_supported(bs, Nk * H, Nk, 2, mb,
                                                    N // Nk)
        assert pallas_decode.paged_decode_tile_rows(
            bs, Nk * H, Nk, 2, mb, N // Nk) == 512
        fn = lambda q, k, v, t, c: pallas_decode.paged_decode_attention(
            q, k, v, t, c, N, **kw)
        args = (sds((S, 1, N * H), bf16), pages, pages, sds((S, mb), i32),
                sds((S,), i32))
    elif step == "chunk":
        assert pallas_decode.flash_prefill_supported(bs, C, Nk * H, Nk, 2,
                                                     mb)
        fn = lambda q, k, v, t, p0, n: pallas_decode.flash_prefill_chunk(
            q, k, v, t, p0, N, n_real=n, **kw)
        args = (sds((1, C, N * H), bf16), pages, pages, sds((mb,), i32),
                sds((), i32), sds((), i32))
    elif step == "ring_decode":
        assert pallas_decode.paged_decode_tile_rows(
            W, Nk * H, Nk, 2, 1, N // Nk) == W
        fn = lambda q, k, v, rows, c: pallas_decode.paged_decode_attention(
            q, k, v, rows[:, None], jnp.minimum(c, W - 1), N,
            name="paged_decode_window", **kw)
        args = (sds((S, 1, N * H), bf16), rings, rings, sds((S,), i32),
                sds((S,), i32))
    elif step == "ring_chunk":
        assert pallas_decode.window_prefill_supported(C, W, H, 2)
        fn = lambda q, k, v, rk, rv, row, p0, n: \
            pallas_decode.window_prefill_chunk(q, k, v, rk, rv, row, p0, N,
                                               n_real=n, **kw)
        args = (sds((C, N * H), bf16), sds((C, Nk * H), bf16),
                sds((C, Nk * H), bf16), rings, rings, sds((), i32),
                sds((), i32), sds((), i32))
    else:   # the registry's own example, float32, as chip_smoke.py runs it
        import numpy as np
        from paddle_tpu.ops.kernel_registry import registered_kernels
        reg = next(r for r in registered_kernels() if r.name == name)
        ex, exkw = reg.example(np.random.default_rng(0))
        fn = lambda *xs: reg.fn(*xs, ex[7], **exkw)
        args = tuple(sds(np.shape(a), np.asarray(a).dtype) for a in ex[:7])
    text = jax.jit(fn).trace(*args).lower(
        lowering_platforms=("tpu",)).compile().as_text()
    assert "tpu_custom_call" in text and name in text


@pytest.mark.parametrize("tokens,rows", [(64, 16), (512, 128)])
def test_exaone_expert_ffn_compiles_for_v5e(one_chip, mosaic_mla, tokens,
                                            rows):
    """The held experts' products at K-EXAONE's widths (6,144 x 2,048,
    weight blocks of 3.1 MB; the cell's 16 experts held of the router's
    128), for a decode batch and for a chunk, at the tile
    `expert_tile_rows` picks for each (52.7 MB at 128 rows beside the
    chunk's x and y: the limit is the most of the v5e's 128 MiB)."""
    _, moe_serving = mosaic_mla
    d, f, E, k = 6144, 2048, 16, 8
    bf16 = jnp.bfloat16
    assert moe_serving.expert_tile_rows(tokens, k, 128, d, f, 2) == rows
    assert moe_serving._resident(rows, tokens, d, f, 2)

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    def fn(x, live, w, e, wg, wu, wd):
        return moe_serving.held_expert_ffn(x, live, w, e, (0, E), wg, wu,
                                           wd, use_kernel=True,
                                           n_experts=128)[0]

    text = jax.jit(fn).trace(
        sds((tokens, d), bf16), sds((tokens,), jnp.bool_),
        sds((tokens, k), jnp.float32), sds((tokens, k), jnp.int32),
        sds((E, d, f), bf16), sds((E, d, f), bf16),
        sds((E, f, d), bf16)).lower(
            lowering_platforms=("tpu",)).compile().as_text()
    assert "tpu_custom_call" in text and "moe_grouped_ffn" in text
    _no_layout(text, -(-tokens * k // rows) + E, rows, tokens, k, d)


@pytest.fixture
def mosaic_flash(monkeypatch):
    from paddle_tpu.ops import pallas_attention
    monkeypatch.setattr(pallas_attention, "_interpret", lambda: False)
    return pallas_attention


@pytest.mark.parametrize("bn,S,H,bq,bk,dtype", [
    (144, 2048, 64, 1024, 1024, jnp.bfloat16),   # gpt3-125m.train
    (64, 2048, 128, 1024, 1024, jnp.bfloat16),   # GPT-3 1.3B's heads
    (8, 2048, 64, 1024, 1024, jnp.float32),
    (8, 16384, 64, 512, 1024, jnp.bfloat16),     # r = 2: beyond 8,192
    (8, 16384, 128, 256, 1024, jnp.bfloat16),    # r = 4: the small-bq clamp
])
def test_causal_flash_tri_kernels_compile_for_v5e(one_chip, mosaic_flash,
                                                  bn, S, H, bq, bk, dtype):
    """The training kernels' triangle grids with the diagonal tiles
    walked by row sub-blocks: static slices of the tile's refs at
    multiples of sub_block_rows, the crossing square cut out of the
    scores and put back, at the tiles _resolve_blocks and _vjp_bwd
    choose for these lengths."""
    pa = mosaic_flash
    nq = S // bq

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    x, stat = sds((bn, S, H), dtype), sds((bn, 8, S), jnp.float32)
    if bq == bk:
        text = jax.jit(
            lambda q, k, v: pa._flash_fwd_tri(q, k, v, bq, bk, nq)).trace(
                x, x, x).lower(lowering_platforms=("tpu",)).compile(
                ).as_text()
        assert "tpu_custom_call" in text and "flash_fwd_tri" in text
    text = jax.jit(
        lambda q, k, v, g, l, d: pa._flash_bwd_merged_tri(
            q, k, v, g, l, d, bq, bk, nq)).trace(
                x, x, x, x, stat, stat).lower(
                    lowering_platforms=("tpu",)).compile().as_text()
    assert "tpu_custom_call" in text and "flash_bwd_merged_tri" in text


# -- qwen3-next-80b-a3b.serve-longgen -------------------------------------

@pytest.fixture
def mosaic_gdn(monkeypatch):
    from paddle_tpu.ops import pallas_gdn
    jitted = (pallas_gdn.gdn_state_step, pallas_gdn.gdn_chunk)
    monkeypatch.setattr(pallas_gdn, "_interpret", lambda: False)
    for fn in jitted:
        fn.clear_cache()
    yield pallas_gdn
    for fn in jitted:
        fn.clear_cache()


@pytest.mark.parametrize("kernel", ["gdn_state_step", "gdn_chunk"])
def test_gdn_kernels_compile_for_v5e(one_chip, mosaic_gdn, kernel):
    """32 value heads over 16 key heads of 128 x 128: 129 rows of
    [32, 128, 128] float32 (2 MiB a row), 128 slots, all 32 heads a
    grid step; a chunk of 512 in sub-chunks of 64."""
    gdn = mosaic_gdn
    S, H, Hk, K, V, C = 128, 32, 16, 128, 128, 512
    f32, i32 = jnp.float32, jnp.int32

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    if kernel == "gdn_state_step":
        assert gdn.state_step_heads(H, Hk, K, V) == 32
        fn = lambda *a: gdn.gdn_state_step(*a, use_kernel=True)
        args = (sds((S + 1, H, K, V), f32), sds((S,), i32),
                sds((S,), jnp.bool_), sds((S, Hk, K), f32),
                sds((S, Hk, K), f32), sds((S, H, V), f32), sds((S, H), f32),
                sds((S, H), f32))
    else:
        assert gdn.chunk_supported(C, 64, K, V)
        fn = lambda *a: gdn.gdn_chunk(*a, sub=64, use_kernel=True)
        args = (sds((C, Hk, K), f32), sds((C, Hk, K), f32),
                sds((C, H, V), f32), sds((C, H), f32), sds((C, H), f32),
                sds((H, K, V), f32), sds((), i32))
    text = jax.jit(fn).trace(*args).lower(
        lowering_platforms=("tpu",)).compile().as_text()
    assert "tpu_custom_call" in text and kernel in text


@pytest.mark.parametrize("name", ["gdn_state_step", "gdn_chunk"])
def test_gdn_registry_example_compiles_for_v5e(one_chip, mosaic_gdn, name):
    import numpy as np
    from paddle_tpu.ops.kernel_registry import registered_kernels
    reg = next(r for r in registered_kernels() if r.name == name)
    args, kwargs = reg.example(np.random.default_rng(0))

    def fn(*xs):
        return reg.fn(*xs, **kwargs)

    text = jax.jit(fn).trace(*[
        jax.ShapeDtypeStruct(np.shape(a), np.asarray(a).dtype,
                             sharding=one_chip)
        for a in args]).lower(lowering_platforms=("tpu",)).compile() \
        .as_text()
    assert "tpu_custom_call" in text and name in text


@pytest.mark.parametrize("step", ["decode", "chunk"])
def test_gated_attention_heads_compile_for_v5e(one_chip, mosaic, step):
    """16 query heads over 2 K/V heads of 256 (group 8): queries 4,096
    lanes wide, arenas 512; tables of 1,280 blocks of 16 (20,480
    positions), 128 slots, chunks of 512."""
    N, Nk, H, bs, mb, nb = 16, 2, 256, 16, 1280, 32768
    S, C, bf16, i32 = 128, 512, jnp.bfloat16, jnp.int32

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    kw = dict(use_kernel=True, kv_heads=Nk, scale=H ** -0.5)
    pages = sds((nb, bs, Nk * H), bf16)
    if step == "decode":
        assert pallas_decode.paged_decode_supported(bs, Nk * H, Nk, 2, mb,
                                                    N // Nk)
        fn = lambda q, k, v, t, c: pallas_decode.paged_decode_attention(
            q, k, v, t, c, N, **kw)
        args = (sds((S, 1, N * H), bf16), pages, pages, sds((S, mb), i32),
                sds((S,), i32))
    else:
        assert pallas_decode.flash_prefill_supported(bs, C, Nk * H, Nk, 2,
                                                     mb)
        fn = lambda q, k, v, t, p0, n: pallas_decode.flash_prefill_chunk(
            q, k, v, t, p0, N, n_real=n, **kw)
        args = (sds((1, C, N * H), bf16), pages, pages, sds((mb,), i32),
                sds((), i32), sds((), i32))
    text = jax.jit(fn).trace(*args).lower(
        lowering_platforms=("tpu",)).compile().as_text()
    assert "tpu_custom_call" in text
    assert ("paged_decode" if step == "decode"
            else "flash_prefill_chunk") in text


@pytest.mark.parametrize("N,Nk,H", [(24, 8, 32), (12, 4, 64), (8, 2, 64)])
def test_packed_query_rows_compile_for_v5e(one_chip, mosaic, N, Nk, H):
    """Grouped query heads packed into a block of sublanes that they do
    not fill: 24, 12 and 8 live rows of 32, 16 and 16; in the last two
    a member's rows are a slice that starts off the 8-row float32
    tile."""
    S, bs, mb, bf16, i32 = 8, 16, 64, jnp.bfloat16, jnp.int32
    assert pallas_decode.paged_decode_head_rows(Nk, N // Nk) > N

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    pages = sds((4 * mb, bs, Nk * H), bf16)
    fn = lambda q, k, v, t, c: pallas_decode.paged_decode_attention(
        q, k, v, t, c, N, use_kernel=True, kv_heads=Nk)
    text = jax.jit(fn).trace(
        sds((S, 1, N * H), bf16), pages, pages, sds((S, mb), i32),
        sds((S,), i32)).lower(lowering_platforms=("tpu",)).compile() \
        .as_text()
    assert "tpu_custom_call" in text and "paged_decode" in text


@pytest.mark.parametrize("tokens,rows", [(128, 16), (512, 48)])
def test_qwen3next_expert_ffn_compiles_for_v5e(one_chip, mosaic_mla, tokens,
                                               rows):
    """The held experts' products at Qwen3-Next's widths (2,048 x 512:
    two width tiles of 256; the cell's 64 experts held of the router's
    512), for a decode batch and for a chunk, at the tile
    `expert_tile_rows` picks for each: no bf16[8208,2048] at the
    chunk's."""
    _, moe_serving = mosaic_mla
    d, f, E, k = 2048, 512, 64, 10
    bf16 = jnp.bfloat16
    assert moe_serving.expert_tile_rows(tokens, k, 512, d, f, 2) == rows
    assert moe_serving._resident(rows, tokens, d, f, 2)

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    def fn(x, live, w, e, wg, wu, wd):
        return moe_serving.held_expert_ffn(x, live, w, e, (0, E), wg, wu,
                                           wd, use_kernel=True,
                                           n_experts=512)[0]

    text = jax.jit(fn).trace(
        sds((tokens, d), bf16), sds((tokens,), jnp.bool_),
        sds((tokens, k), jnp.float32), sds((tokens, k), jnp.int32),
        sds((E, d, f), bf16), sds((E, d, f), bf16),
        sds((E, f, d), bf16)).lower(
            lowering_platforms=("tpu",)).compile().as_text()
    assert "tpu_custom_call" in text and "moe_grouped_ffn" in text
    _no_layout(text, -(-tokens * k // rows) + E, rows, tokens, k, d)
