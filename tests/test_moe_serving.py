"""`moe/serving.py`'s tile policy and the grouped expert products at every
tile the policy can return, through the Pallas interpreter, against a
plain float32 reference."""
import numpy as np
import pytest

import jax.numpy as jnp

from paddle_tpu.moe import serving as moe_serving

D, F, K, HELD = 128, 512, 4, (2, 5)
TILES = (16, 32, 48, 64, 80, 96, 112, 128)
VMEM_LIMIT = moe_serving._VMEM_LIMIT


def _load(tm):
    """192 tokens (the last 7 padding) x top 4 of 12 experts, 2..6 held
    here: expert 2 gets 150 rows (more than any tile), expert 3 exactly
    `tm`, expert 4 none, 5 and 6 a few; the other choices fall on
    experts held elsewhere (0, 1, 7..11), on both sides of the held."""
    T, n_live = 192, 185
    rs = np.random.default_rng(5)
    elsewhere = np.array([0, 1, 7, 8, 9, 10, 11])
    experts = np.stack([rs.permutation(elsewhere)[:K] for _ in range(T)])
    experts[rs.permutation(n_live)[:150], 0] = 2
    experts[rs.permutation(n_live)[:tm], 1] = 3
    experts[rs.permutation(n_live)[:9], 2] = 5
    experts[rs.permutation(n_live)[:13], 3] = 6
    experts[n_live:, 0] = 2         # padding tokens: routed, and not live
    x = rs.standard_normal((T, D)).astype(np.float32)
    weights = rs.random((T, K)).astype(np.float32)
    ws = [(s * rs.standard_normal(shape)).astype(np.float32)
          for s, shape in ((0.1, (HELD[1], D, F)), (0.1, (HELD[1], D, F)),
                           (0.05, (HELD[1], F, D)))]
    return x, np.arange(T) < n_live, weights, experts.astype(np.int32), ws


def _reference(x, live, weights, experts, ws):
    wg, wu, wd = (w.astype(np.float64) for w in ws)
    y = np.zeros(x.shape, np.float64)
    counts = np.zeros((HELD[1],), int)
    for t in np.flatnonzero(live):
        for j in range(K):
            e = experts[t, j] - HELD[0]
            if 0 <= e < HELD[1]:
                g, u = x[t] @ wg[e], x[t] @ wu[e]
                y[t] += weights[t, j] * ((g / (1 + np.exp(-g)) * u) @ wd[e])
                counts[e] += 1
    return y, counts


def _at_most_two_held(load):
    """`load` with each token's held choices past its first two moved to
    experts held elsewhere that it has not chosen: no token's y sums
    more than two pairs, so the order of the sum cannot show."""
    x, live, weights, experts, ws = load
    experts = experts.copy()
    elsewhere = [0, 1, 7, 8, 9, 10, 11]
    for t in range(experts.shape[0]):
        held = np.flatnonzero((experts[t] >= HELD[0])
                              & (experts[t] < HELD[0] + HELD[1]))
        free = [e for e in elsewhere if e not in experts[t]]
        for j in held[2:]:
            experts[t, j] = free.pop()
    return x, live, weights, experts, ws


def _run(monkeypatch, tm, load, use_kernel, path="fused"):
    """held_expert_ffn at tiles of `tm` rows; `path` "layout" leaves no
    VMEM for x and y to be held whole (the shapes choose the path, when
    `moe_grouped_ffn` is traced: no trace outlives the limit it read)."""
    monkeypatch.setattr(moe_serving, "expert_tile_rows",
                        lambda *a, **kw: tm)
    monkeypatch.setattr(moe_serving, "_VMEM_LIMIT",
                        0 if path == "layout" else VMEM_LIMIT)
    traced = set()
    for name in ("_fused_kernel", "_layout_kernel"):
        def body(*a, real=getattr(moe_serving, name), name=name, **kw):
            traced.add(name)
            return real(*a, **kw)
        monkeypatch.setattr(moe_serving, name, body)
    x, live, weights, experts, ws = load
    moe_serving.moe_grouped_ffn.clear_cache()
    try:
        y, stats = moe_serving.held_expert_ffn(
            jnp.asarray(x), jnp.asarray(live), jnp.asarray(weights),
            jnp.asarray(experts), HELD, *(jnp.asarray(w) for w in ws),
            use_kernel=use_kernel)
    finally:
        moe_serving.moe_grouped_ffn.clear_cache()
    assert traced == ({f"_{path}_kernel"} if use_kernel else set())
    return np.asarray(y), {n: float(v) for n, v in stats.items()}


@pytest.mark.parametrize("path", ["fused", "layout"])
@pytest.mark.parametrize("tm", TILES)
def test_held_expert_ffn_at_every_tile_the_rule_can_return(monkeypatch, tm,
                                                           path):
    """Both paths of the kernel (through the interpreter): the fused one
    gathers its rows and sums its pairs itself, the layout one goes
    through [n_tiles * tm, d] in HBM."""
    load = _load(tm)
    want, counts = _reference(*load)
    assert counts[0] == 150 > max(TILES) and counts[1] == tm \
        and counts[2] == 0 and 0 < counts[3] < counts[4] < 16
    got, stats = _run(monkeypatch, tm, load, use_kernel=True, path=path)
    assert np.abs(got - want).max() < 2e-4
    assert not got[~load[1]].any()
    assert stats["moe_calls_fused"] == (path == "fused")
    # a row's result does not depend on the tile it lies in
    other, _ = _run(monkeypatch, 16 if tm != 16 else 128, load,
                    use_kernel=True, path=path)
    assert np.array_equal(got, other)
    # a live tile is one read of its expert's weights
    assert stats["moe_weight_reads"] == np.ceil(counts / tm).sum()
    assert stats["moe_experts_reached"] == 4
    assert stats["moe_weight_reads"] == 4 + (150 - 1) // tm
    assert stats["moe_pairs_held"] == counts.sum()
    assert stats["moe_load_max"] == 150


@pytest.mark.parametrize("tm", [16, 128])
def test_the_fused_path_gives_the_layout_paths_bits(monkeypatch, tm):
    """Where no token has more than two pairs held here, a token's y is
    0 + a + b in either order: the kernel that sums in VMEM gives the
    layout path's bits, and both count the same step."""
    load = _at_most_two_held(_load(tm))
    held = (load[3] >= HELD[0]) & (load[3] < HELD[0] + HELD[1])
    assert held[load[1]].sum(axis=1).max() == 2
    fused, st_fused = _run(monkeypatch, tm, load, use_kernel=True)
    layout, st_layout = _run(monkeypatch, tm, load, use_kernel=True,
                             path="layout")
    assert np.array_equal(fused, layout)
    assert np.abs(fused - _reference(*load)[0]).max() < 2e-4
    assert st_fused.pop("moe_calls_fused") == 1.0
    assert st_layout.pop("moe_calls_fused") == 0.0
    assert st_fused == st_layout


@pytest.mark.parametrize("tm", TILES)
def test_one_weight_read_an_expert_where_its_rows_fit_a_tile(monkeypatch,
                                                             tm):
    """No expert has more rows than the tile (the busiest exactly a
    tile): every reached expert is read once."""
    x, live, weights, experts, ws = _load(tm)
    experts[experts == 2] = 0
    got, stats = _run(monkeypatch, tm, (x, live, weights, experts, ws),
                      use_kernel=False)
    want, counts = _reference(x, live, weights, experts, ws)
    assert counts.max() <= tm and counts[1] == tm
    assert stats["moe_weight_reads"] == stats["moe_experts_reached"] == 3
    assert np.abs(got - want).max() < 2e-4


@pytest.mark.parametrize("tokens,k,n_experts,d,f,rows", [
    # k-exaone-236b-a23b.serve-mixed: a chunk gives an expert 32 rows on
    # average, the decode batch of 64 slots 4
    (512, 8, 128, 6144, 2048, 128), (64, 8, 128, 6144, 2048, 16),
    # deepseek-v2.serve-docs: a chunk 19.2, the 32 slots 1.2
    (512, 6, 160, 5120, 1536, 80), (32, 6, 160, 5120, 1536, 16),
    # no more rows than an expert can get; at most 128
    (1, 8, 8, 6144, 2048, 16), (21, 3, 3, 128, 256, 32),
    (100, 4, 4, 128, 256, 112), (4096, 2, 64, 128, 256, 128),
    # whole units of 16 rows: 4 x 11 pairs over 2 experts = 22 -> 32
    (11, 1, 2, 128, 256, 16), (24, 1, 4, 128, 256, 32),
])
def test_expert_tile_rows_at_the_cells_shapes(tokens, k, n_experts, d, f,
                                              rows):
    assert moe_serving.expert_tile_rows(tokens, k, n_experts, d, f,
                                        2) == rows


def test_expert_tile_rows_follows_the_vmem_limit():
    rule = moe_serving.expert_tile_rows
    tf = moe_serving._width_tile(2048)
    foot = lambda tm: moe_serving._ffn_footprint(tm, 6144, tf, 2, 512)
    layout = lambda tm: moe_serving._ffn_footprint(tm, 6144, tf, 2)
    # 18.9 MB of weight blocks and the 512 tokens' x and y held whole
    # (x and y in bf16, y's float32 sum): 44.0 MB, then 68 KB a row:
    # 52.7 MB at 128 rows
    assert foot(0) == 3 * 2 * 6144 * 256 * 2 + 512 * 6144 * (2 + 2 + 4)
    assert 52e6 < foot(128) < 53e6 < moe_serving._VMEM_LIMIT
    # a tighter limit takes the largest multiple of 16 that fits
    got = rule(512, 8, 128, 6144, 2048, 2, vmem_limit=48 * 2 ** 20)
    assert got == 80 and foot(got) <= 48 * 2 ** 20 < foot(got + 16)
    # one that leaves no room for x and y beside a tile of 16 rows sizes
    # the layout path's tile: rows and output blocks in two buffers
    got = rule(512, 8, 128, 6144, 2048, 2, vmem_limit=24 * 2 ** 20)
    assert foot(16) > 24 * 2 ** 20
    assert got == 80 and layout(got) <= 24 * 2 ** 20 < layout(got + 16)
    # float32 operands double every block
    assert rule(512, 8, 128, 6144, 2048, 4, vmem_limit=40 * 2 ** 20) \
        < 128 == rule(512, 8, 128, 6144, 2048, 2, vmem_limit=40 * 2 ** 20)
    assert rule(512, 8, 128, 6144, 2048, 2, vmem_limit=1) == 16


def test_the_tile_follows_the_routers_width(monkeypatch):
    """`held_expert_ffn` sizes its tiles by the experts the router chose
    among (`HeldExperts.run` hands them over; the held ones where nobody
    says), and the result does not depend on it."""
    x, live, weights, experts, ws = _load(16)
    args = (jnp.asarray(x), jnp.asarray(live), jnp.asarray(weights),
            jnp.asarray(experts), HELD, *(jnp.asarray(w) for w in ws))
    seen, real = [], moe_serving.moe_grouped_ffn

    def spy(*a, **kw):
        seen.append(a[9])
        return real(*a, **kw)

    monkeypatch.setattr(moe_serving, "moe_grouped_ffn", spy)
    y_held, _ = moe_serving.held_expert_ffn(*args, use_kernel=False)
    y_48, st = moe_serving.held_expert_ffn(*args, use_kernel=False,
                                           n_experts=48)
    # 192 tokens x top 4: 154 rows an expert of 5, 16 an expert of 48
    assert seen == [128, 4 * 16]
    assert np.abs(np.asarray(y_held) - np.asarray(y_48)).max() < 1e-6
    assert st["moe_weight_reads"] == 4 + 149 // 64
