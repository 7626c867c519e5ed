"""chip_smoke.py — does the system still start on the chip?

One process, started cold, drives the two main paths through the entry
points a user calls, at the full published width of GPT-3 125M
(12 layers, d=768, 12 heads of 64, vocab 50304), and checks what comes
out by the repo's own means:

  train     paddle.jit.TrainStep (AdamW, bf16 autocast) at 24 x 1024:
            finite falling loss, the flash kernels in the compiled
            step, loss parity with the composed attention path
  serve     serving.ServingEngine (16 slots, 16-token pages, wo8)
            answers seeded requests: token counts, a quiesced pool,
            paged_decode / flash_prefill_chunk in the compiled steps,
            logit parity with the gather+dense path
  kernels   every registered Pallas kernel compiled by Mosaic against
            its declared fallback, then the production tile shapes the
            two legs above do not reach (the DeepSeek-V2, Granite and
            K-EXAONE cells': latent attention in the absorbed and the
            expanded form, expert products, the
            Mamba-2 step and scan, a window layer's chunk and its
            decode over rings, 64/8 heads of 128 to 13k)
  four_chip dist.ShardedTrainStep on a dp=2 x mp=2 mesh when there are
            four devices; reported as not run otherwise

    python chip_smoke.py

It refuses any platform but `tpu`, exits non-zero the moment a leg
fails, and on a pass prints as its last line
`{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}`.
Times it prints along the way are not results.

To debug the script itself where there is no chip, set
CHIP_SMOKE_DEBUG=tiny: toy sizes, kernels in the Pallas interpreter,
the compiled-text checks skipped. Such a run cannot pass — it ends
with `NOT A CHIP RUN` and exit code 3 and prints no result line.
"""
import collections
import functools
import gc
import json
import os
import sys
import time
import traceback
import warnings

import numpy as np

TINY = os.environ.get("CHIP_SMOKE_DEBUG") == "tiny"

BF16_TOL = 3e-2     # the repo's bf16 tolerance (.claude/skills/verify)

Sizes = collections.namedtuple(
    "Sizes", "model batch seq parity_batch train_steps engine prompt_lens "
             "tail_lens flash_seq decode_len")


def sizes():
    """The full GPT-3 125M sizes, or the toy ones of the debug mode."""
    from paddle_tpu.models.gpt import GPTConfig
    if not TINY:
        return Sizes(
            model=GPTConfig.gpt3_125m(max_seq_len=1024, dropout=0.0),
            batch=24, seq=1024,
            # the composed attention path needs 19.3 GB at 24 x 1024
            # (XLA's own figure), so the parity run takes 8 rows
            parity_batch=8, train_steps=4,
            engine=dict(max_slots=16, block_size=16, prefill_chunk=128,
                        max_model_len=512, weights="wo8"),
            prompt_lens=(37, 150, 260, 16), tail_lens=(30, 20, 90),
            flash_seq=2048, decode_len=1024)
    return Sizes(
        model=GPTConfig(vocab_size=512, hidden_size=128, num_layers=1,
                        num_heads=2, max_seq_len=256, dropout=0.0),
        batch=4, seq=256, parity_batch=2, train_steps=3,
        engine=dict(max_slots=4, block_size=16, prefill_chunk=32,
                    max_model_len=128, weights="wo8"),
        prompt_lens=(21, 40, 70, 16), tail_lens=(10, 8, 30),
        flash_seq=256, decode_len=32)


def check(ok, what):
    """A leg's assertion: says what was checked, raises when it failed."""
    print(f"  [{'ok' if ok else 'FAIL'}] {what}", flush=True)
    if not ok:
        raise AssertionError(what)


def max_err(got, want):
    """Largest absolute difference, relative to the reference's scale
    (at least 1), over matching pytrees."""
    import jax
    worst = 0.0
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want), strict=True):
        g = np.asarray(g, np.float64)
        w = np.asarray(w, np.float64)
        if g.shape != w.shape:
            raise AssertionError(f"shape {g.shape} vs reference {w.shape}")
        worst = max(worst, float(np.max(np.abs(g - w)))
                    / max(1.0, float(np.max(np.abs(w)))))
    return worst


def kernels_in(obs, family):
    """Counter of the Mosaic kernels in the programs the compile
    observatory built for `family` (by pallas_call name), plus the
    distinct operand shapes seen per kernel."""
    from paddle_tpu.ops.kernel_registry import mosaic_custom_calls
    names, shapes = collections.Counter(), {}
    programs = [c for f, c in obs.compiled_programs() if f.startswith(family)]
    if not programs:
        raise AssertionError(f"no compiled program of family {family!r}")
    for compiled in programs:
        for name, operands in mosaic_custom_calls(compiled.as_text()):
            names[name] += 1
            shapes.setdefault(name, set()).add(operands)
    return names, shapes


# ---------------------------------------------------------------------------
# train leg
# ---------------------------------------------------------------------------

def build_gpt_train_step(cfg, amp_on=True):
    """The GPT trainer the train leg checks: seeded model, AdamW, bf16
    autocast, one fused TrainStep. Returns (model, step)."""
    import paddle_tpu as paddle
    from paddle_tpu import amp, optimizer
    from paddle_tpu.models.gpt import GPTForPretraining

    paddle.seed(0)
    model = GPTForPretraining(cfg)
    opt = optimizer.AdamW(learning_rate=1e-4, weight_decay=0.01,
                          parameters=model.parameters())

    def loss_fn(ids, labels):
        with amp.auto_cast(enable=amp_on, dtype="bfloat16"):
            return model.loss(ids, labels)

    return model, paddle.jit.TrainStep(model, loss_fn, opt)


def seeded_token_batch(vocab_size, batch, seq):
    """Fixed (ids, labels) int32 batch from seed 0."""
    import paddle_tpu as paddle
    rs = np.random.RandomState(0)
    return tuple(
        paddle.to_tensor(rs.randint(0, vocab_size, (batch, seq)), "int32")
        for _ in range(2))


def leg_train(sz):
    from paddle_tpu import telemetry
    from paddle_tpu.flags import get_flag, set_flags
    from paddle_tpu.telemetry.mfu import flops_drift, model_flops_per_token

    cfg = sz.model
    with telemetry.CompileObservatory(action="record") as obs:
        model, step = build_gpt_train_step(cfg)
        ids, lbl = seeded_token_batch(cfg.vocab_size, sz.batch, sz.seq)
        losses = [float(step(ids, lbl).item())
                  for _ in range(sz.train_steps)]
    print(f"  losses at {sz.batch} x {sz.seq}: {losses}")
    check(all(np.isfinite(losses)), "every loss is finite")
    check(losses[-1] < losses[0], "loss falls over the steps")
    n_params = sum(int(np.prod(p.shape)) for p in model.parameters())
    rec = [r for r in obs.records if r.get("cost")][-1]
    analytic = model_flops_per_token(
        n_params, cfg.num_layers, cfg.hidden_size, sz.seq) \
        * sz.batch * sz.seq
    print(f"  cost analysis of the compiled step: {rec['cost']}; drift "
          f"from the analytic 6N+12LHS count: "
          f"{flops_drift(rec['cost']['flops'], analytic):+.3f}")
    if not TINY:
        names, shapes = kernels_in(obs, "TrainStep")
        print(f"  Mosaic kernels in the compiled step: {dict(names)} "
              f"on {shapes}")
        fwd = sum(n for k, n in names.items() if k.startswith("flash_fwd"))
        bwd = sum(n for k, n in names.items() if k.startswith("flash_bwd"))
        check(fwd == cfg.num_layers and bwd >= cfg.num_layers,
              f"a flash forward and backward kernel per layer "
              f"({fwd} fwd, {bwd} bwd, {cfg.num_layers} layers) — the "
              "step did not give way to _composed_attention")
    del model, step
    gc.collect()

    # the first steps against the composed attention path: the second
    # and third see the kernels' gradients
    ids, lbl = seeded_token_batch(cfg.vocab_size, sz.parity_batch, sz.seq)
    pallas_was = get_flag("use_pallas_attention")
    runs = {}
    try:
        for use_pallas in (True, False):
            set_flags({"use_pallas_attention": use_pallas})
            model, step = build_gpt_train_step(cfg)
            runs[use_pallas] = [float(step(ids, lbl).item())
                                for _ in range(3)]
            del model, step
            gc.collect()
    finally:
        set_flags({"use_pallas_attention": pallas_was})
    diff = max(abs(a - b) for a, b in zip(runs[True], runs[False]))
    print(f"  {sz.parity_batch} x {sz.seq}: flash {runs[True]} "
          f"composed {runs[False]}")
    check(diff <= BF16_TOL,
          f"first three losses within {BF16_TOL} of the composed "
          f"attention path (max diff {diff:.2e})")
    return {"losses": losses, "first_loss": losses[0]}


# ---------------------------------------------------------------------------
# serve leg
# ---------------------------------------------------------------------------

def leg_serve(sz):
    import jax
    import paddle_tpu as paddle
    from paddle_tpu import telemetry
    from paddle_tpu.models.gpt import GPTForPretraining
    from paddle_tpu.ops.pallas_decode import (flash_prefill_supported,
                                              paged_decode_supported)
    from paddle_tpu.serving import (EngineConfig, SamplingParams,
                                    ServingEngine)

    cfg = sz.model
    paddle.seed(0)
    model = GPTForPretraining(cfg)
    ecfg = EngineConfig(**sz.engine)
    mb = -(-ecfg.max_model_len // ecfg.block_size)
    check(paged_decode_supported(ecfg.block_size, cfg.hidden_size,
                                 cfg.num_heads, max_blocks=mb)
          and flash_prefill_supported(ecfg.block_size, ecfg.prefill_chunk,
                                      cfg.hidden_size, cfg.num_heads,
                                      max_blocks=mb),
          "the paged decode and flash prefill gates admit this engine")
    rs = np.random.RandomState(0)

    def tokens(n):
        return rs.randint(0, cfg.vocab_size, (n,)).tolist()

    shared = tokens(3 * ecfg.block_size)        # a 3-block common prefix
    tails = [shared + tokens(n) for n in sz.tail_lens]
    first_wave = [tokens(n) for n in sz.prompt_lens] + tails[:1]
    second_wave = tails[1:]
    with warnings.catch_warnings(record=True) as caught, \
            telemetry.CompileObservatory(action="record") as obs:
        warnings.simplefilter("always")
        engine = ServingEngine(model, config=ecfg)
        handles = []
        for wave in (first_wave, second_wave):
            for i, prompt in enumerate(wave):
                want = 5 + 3 * i
                handles.append((want, engine.submit(
                    prompt, SamplingParams(max_new_tokens=want))))
            engine.run_until_idle()
    got = [(want, len(h.output_tokens), h.status) for want, h in handles]
    print(f"  (requested, produced, status) per request: {got}")
    check(all(w == n and s == "finished" for w, n, s in got),
          "every request finished with the requested token count")
    engine.pool.assert_quiesced()
    check(True, "the block pool quiesced")
    stats = engine.prefix_stats()
    check(stats["hits"] >= 1,
          f"the shared prefix was served from the cache ({stats})")
    donated = [str(w.message) for w in caught if "onat" in str(w.message)]
    check(not donated, f"no donation warning ({donated[:1]})")
    if not TINY:
        L = cfg.num_layers
        for family, kernel in (("serving_prefill", "flash_prefill_chunk"),
                               ("serving_decode", "paged_decode")):
            names, shapes = kernels_in(obs, family)
            print(f"  Mosaic kernels in {family}: {dict(names)} on "
                  f"{shapes}")
            # weights="wo8" quantizes the linears only, so the tied head
            # stays a bf16 matmul; int8_matvec is held against its
            # fallback at this model's head shape in the kernel leg
            check(names[kernel] == L,
                  f"{family} runs {kernel} as a kernel in every layer")

    # one engine step with the kernels against the same step through
    # gather+dense, on the engine's own arenas: two prefill chunks of a
    # fresh prompt, then a decode batch of unequal context lengths
    S, C = ecfg.max_slots, ecfg.prefill_chunk
    mb = engine.max_blocks_per_seq
    params = engine._param_vals()
    k, v = engine.cache.k, engine.cache.v
    table = np.arange(1, mb + 1, dtype=np.int32)
    step_err = {}
    for p0 in (0, C):
        ids = rs.randint(0, cfg.vocab_size, (1, C)).astype(np.int32)
        outs = {uk: jax.jit(functools.partial(
            engine._prefill_logits, use_kernel=uk))(
                params, k, v, ids, np.int32(p0), np.int32(C), table)
            for uk in (True, False)}
        step_err[f"prefill@{p0}"] = max_err(outs[True][0], outs[False][0])
        _, k, v = outs[True]
    ctx = (2 * C - 1 - (C // S) * np.arange(S)).astype(np.int32)
    toks = rs.randint(0, cfg.vocab_size, (S,)).astype(np.int32)
    tables = np.tile(table, (S, 1))
    outs = {uk: jax.jit(functools.partial(
        engine._decode_logits, use_kernel=uk))(
            params, k, v, toks, ctx, tables) for uk in (True, False)}
    step_err["decode"] = max_err(outs[True][0], outs[False][0])
    check(all(np.isfinite(np.asarray(o[0], np.float32)).all()
              for o in outs.values()), "decode logits are finite")
    check(max(step_err.values()) <= BF16_TOL,
          f"last-position logits with the kernels within {BF16_TOL} of "
          f"gather+dense on the same step ({step_err})")
    return {"requests": len(handles), "prefix_hits": stats["hits"]}


# ---------------------------------------------------------------------------
# kernel leg
# ---------------------------------------------------------------------------

def _production_cases(sz):
    """(kernel names it must contain, label, fn, reference, args): the
    production tile shapes the train and serve legs do not reach, in
    bf16, each against the kernel's declared fallback (the flash
    kernels against _composed_attention and its jax.vjp)."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.moe import kernels as moe
    from paddle_tpu.ops import pallas_attention as pa
    from paddle_tpu.ops import pallas_decode as pd
    from paddle_tpu.ops.attention import _composed_attention

    rs = np.random.RandomState(0)
    bf16 = jnp.bfloat16

    def rand(shape, dtype=bf16, scale=0.3):
        return jnp.asarray(rs.randn(*shape) * scale, dtype)

    cases = []
    s = sz.flash_seq
    bq = min(1024, s // 2)      # two q tiles: the triangle grids engage

    def attn_with_grads(attn):
        def run(q, k, v, w):
            out, vjp = jax.vjp(attn, q, k, v)
            return out, vjp(w)
        return run

    for d, causal, kernels in (
            (128, True, ("flash_fwd_tri", "flash_bwd_merged_tri")),
            (128, False, ("flash_fwd_rect", "flash_bwd_merged_rect")),
            (64, True, ("flash_fwd_tri", "flash_bwd_merged_tri"))):
        q, k, v, w = (rand((2, s, 4, d)) for _ in range(4))
        cases.append((
            kernels, f"flash fwd+bwd s={s} D={d} causal={causal}",
            attn_with_grads(lambda q, k, v, c=causal: pa.flash_attention_fwd(
                q, k, v, c, None, bq, bq)),
            attn_with_grads(lambda q, k, v, c=causal: _composed_attention(
                q, k, v, causal=c)),
            (q, k, v, w)))

    # the split backward is what the vjp picks above the merged
    # kernel's dq-scratch cap; here it is called directly at s, D=128
    q, k, v, w = (rand((2, s, 4, 128)) for _ in range(4))
    scale = 1.0 / np.sqrt(128.0)

    def split_bwd(q, k, v, w):
        out, lse = pa._flash_fwd(q, k, v, True, scale, bq, bq)
        return pa._flash_bwd(q, k, v, out, lse, w, True, scale, bq, bq)

    def split_ref(q, k, v, w):
        _, vjp = jax.vjp(lambda a, b, c: _composed_attention(
            a, b, c, causal=True), q, k, v)
        return vjp(w)

    cases.append((("flash_bwd_split_dkv", "flash_bwd_split_dq"),
                  f"flash_bwd_split s={s} D=128", split_bwd, split_ref,
                  (q, k, v, w)))

    cfg = sz.model
    nh, n = cfg.hidden_size, cfg.num_heads
    L = sz.decode_len
    q = rand((8, 1, nh))
    kb, vb = rand((8, L, nh)), rand((8, L, nh))
    off = np.int32(L - L // 4 - 1)
    cases.append((("decode_fused",), f"decode_fused B=8 L={L} hidden={nh}",
                  lambda q, k, v: pd.decode_attention(q, k, v, off, n),
                  lambda q, k, v: pd._decode_fallback(q, k, v, off, n),
                  (q, kb, vb)))

    # paged decode at both serving cells' widths: contexts on every
    # edge of the kernel's tiling, scattered pages, null tails, one
    # idle slot, the last two slots sharing a cached prefix's pages
    for N, H, mb in ((2, 64, 8),) if TINY else ((12, 64, 64),
                                                 (16, 128, 128)):
        pbs, hid = 16, N * H
        T = pd.paged_decode_tile_rows(pbs, hid, N, 2, mb)
        ctxs = sorted({0, pbs - 1, pbs, T - 1, T, T + 1, 2 * T + 3,
                       mb * pbs - 1} & set(range(mb * pbs)))
        ctxs += [int(c) for c in rs.randint(1, mb * pbs, 14 - len(ctxs))]
        ctxs += [mb * pbs // 2 + 5] * 2
        pages = rs.permutation(np.arange(
            1, sum(c // pbs + 1 for c in ctxs) + 1))
        tabs, used = np.zeros((len(ctxs) + 1, mb), np.int32), 0
        for i, c in enumerate(ctxs):
            tabs[i, :c // pbs + 1] = pages[used:used + c // pbs + 1]
            used += c // pbs + 1
        tabs[-2, :mb // 4] = tabs[-3, :mb // 4]
        ctxs = np.asarray(ctxs + [0], np.int32)     # + the idle slot
        arena = (len(pages) + 1, pbs, hid)
        cases.append((
            ("paged_decode",),
            f"paged_decode {N}x{H} mb={mb} tile={T} rows",
            lambda q, k, v, t, c, N=N: pd.paged_decode_attention(
                q, k, v, t, c, N, use_kernel=True),
            lambda q, k, v, t, c, N=N: pd.paged_decode_attention(
                q, k, v, t, c, N, use_kernel=False),
            (rand((len(ctxs), 1, hid)), rand(arena), rand(arena),
             tabs, ctxs)))

        # a prompt chunk over one request's scattered pages at the same
        # widths: from position 0, inside a tile, across a tile's edge,
        # at the table's end, and a padded chunk; the table past the
        # chunk's last page points past the arena
        C = 32 if TINY else 128
        _, T = pd.flash_prefill_tiling(pbs, C, hid, N, 2, mb)
        for p0, n_real in ((0, C), (T // 2 - 8, C), (T - C // 2, C),
                           (mb * pbs - C, C), (T + 24, C // 2 - 3)):
            if p0 < 0 or p0 + C > mb * pbs:
                continue
            row = np.full((mb,), arena[0] + 7, np.int32)
            n_alloc = (p0 + n_real - 1) // pbs + 1
            row[:n_alloc] = pages[:n_alloc]
            cases.append((
                ("flash_prefill_chunk",),
                f"flash_prefill_chunk {N}x{H} mb={mb} tile={T} rows "
                f"p0={p0} n_real={n_real}",
                lambda q, k, v, t, p0=p0, n=n_real, N=N:
                    pd.flash_prefill_chunk(
                        q, k, v, t, np.int32(p0), N, use_kernel=True,
                        n_real=np.int32(n))[:, :n],
                lambda q, k, v, t, p0=p0, n=n_real, N=N, live=n_alloc:
                    pd.flash_prefill_chunk(
                        q, k, v, jnp.where(jnp.arange(t.shape[0]) < live,
                                           t, 0),
                        np.int32(p0), N, use_kernel=False)[:, :n],
                (rand((1, C, hid)), rand(arena), rand(arena), row)))

    # latent attention at the DeepSeek-V2 cell's shapes (128 heads over
    # 640-lane rows, 512 of them the value): decode contexts on every
    # edge of the tiling with scattered pages and an idle slot, and a
    # prompt chunk at three offsets over one request's table and a
    # partly filled one
    from paddle_tpu.moe import serving as moes
    from paddle_tpu.ops import pallas_mla as pm
    N, W, rank, pbs = (4, 128, 128, 16) if TINY else (128, 640, 512, 16)
    mb = 8 if TINY else 576
    T = pm.mla_tile_rows(pbs, W, rank, N, 2, mb)
    ctxs = sorted({0, pbs - 1, pbs, T - 1, T, T + 1, 2 * T + 3,
                   mb * pbs - 1} & set(range(mb * pbs)))
    ctxs += [int(c) for c in rs.randint(1, mb * pbs, 12 - len(ctxs))]
    pages = rs.permutation(np.arange(
        1, sum(c // pbs + 1 for c in ctxs) + 1))
    tabs, used = np.zeros((len(ctxs) + 1, mb), np.int32), 0
    for i, c in enumerate(ctxs):
        tabs[i, :c // pbs + 1] = pages[used:used + c // pbs + 1]
        used += c // pbs + 1
    ctxs = np.asarray(ctxs + [0], np.int32)         # + the idle slot
    arena = rand((len(pages) + 1, pbs, W))
    cases.append((
        ("mla_paged_decode",),
        f"mla_paged_decode {N}x{W} rank={rank} mb={mb} tile={T} rows",
        lambda q, a, t, c: pm.mla_paged_decode(q, a, t, c, rank, 0.11,
                                               use_kernel=True),
        lambda q, a, t, c: pm.mla_paged_decode(q, a, t, c, rank, 0.11,
                                               use_kernel=False),
        (rand((len(ctxs), N, W)), arena, tabs, ctxs)))
    C = 32 if TINY else 512
    mid = (mb * pbs // 2) // pbs * pbs
    # the last: a question's chunk that resumes inside a block, its
    # table past the last real position's page pointing outside the arena
    for p0, n_real in ((0, C), (C + 16, C), (mid, C),
                       (mid + 24, C // 2 - 3)):
        row = np.full((mb,), arena.shape[0] + 7, np.int32)
        n_alloc = (p0 + n_real - 1) // pbs + 1
        row[:n_alloc] = pages[:n_alloc]
        cases.append((
            ("mla_prefill_chunk",),
            f"mla_prefill_chunk C={C} p0={p0} n_real={n_real}",
            lambda q, a, t, p0=p0, n=n_real: pm.mla_prefill_chunk(
                q, a, t, np.int32(p0), rank, 0.11, use_kernel=True,
                n_real=np.int32(n)),
            lambda q, a, t, p0=p0, n=n_real, live=n_alloc:
                pm.mla_prefill_chunk(
                    q, a, jnp.where(jnp.arange(t.shape[0]) < live, t, 0),
                    np.int32(p0), rank, 0.11, use_kernel=False,
                    n_real=np.int32(n)),
            (rand((C, N, W)), arena, row)))
    # the expanded form of such chunks (k and v a head formed in VMEM):
    # a whole chunk at the start and in the middle of a table, one of
    # as many real positions as it takes to pay and one past the first
    # query block, each resuming inside a block
    nope, rope, v_dim = 128, 64, 128
    W_x, rank_x = (256, 128) if TINY else (W, rank)
    arena_x = rand((len(pages) + 1, pbs, W_x)) if TINY else arena
    pays = next((n for n in range(1, C + 1) if pm.mla_expanded_pays(
        n, N, rank_x, nope, rope, v_dim, C)), C)
    w_uk, w_uv = rand((N, nope, rank_x)), rand((N, rank_x, v_dim))
    for p0, n_real in ((0, C), (mid, C), (mid + 24, pays),
                       (C + 8, C // 2 + 5)):
        row = np.full((mb,), arena_x.shape[0] + 7, np.int32)
        n_alloc = (p0 + n_real - 1) // pbs + 1
        row[:n_alloc] = pages[:n_alloc]
        cases.append((
            ("mla_prefill_chunk_expanded",),
            f"mla_prefill_chunk_expanded C={C} p0={p0} n_real={n_real}",
            lambda qn, qp, a, t, p0=p0, n=n_real:
                pm.mla_prefill_chunk_expanded(
                    qn, qp, a, w_uk, w_uv, t, np.int32(p0), 0.11,
                    use_kernel=True, n_real=np.int32(n)),
            lambda qn, qp, a, t, p0=p0, n=n_real, live=n_alloc:
                pm.mla_prefill_chunk_expanded(
                    qn, qp, a, w_uk, w_uv,
                    jnp.where(jnp.arange(t.shape[0]) < live, t, 0),
                    np.int32(p0), 0.11, use_kernel=False,
                    n_real=np.int32(n)),
            (rand((C, N, nope)), rand((C, N, rope)), arena_x, row)))

    # the grouped expert products at the cell's widths, 8 held experts:
    # a decode batch's tokens and a chunk's, routed unevenly (one expert
    # idle), padding tokens routed nowhere, at the tiles the cell runs
    # (the router scores 160); then the chunk under a skewed load: one
    # expert with 200 of its rows, more than the largest tile
    d_e, f_e, E = (128, 128, 4) if TINY else (5120, 1536, 8)
    wg, wu = rand((E, d_e, f_e), scale=0.02), rand((E, d_e, f_e), scale=0.02)
    wd = rand((E, f_e, d_e), scale=0.02)

    def skewed(ex, busy, rows):
        """`rows` of the tokens choose expert `busy` (once each)."""
        take = rs.permutation(ex.shape[0])[:rows]
        ex[ex == busy] = -1
        ex[take, 0] = busy
        return ex

    C_e = 64 if TINY else 512
    for tokens, skew in ((32, 0), (C_e, 0), (C_e, 40 if TINY else 200)):
        xt = rand((tokens, d_e), scale=1.0)
        ex = rs.randint(-2, E + 3, (tokens, 6)).astype(np.int32)
        ex[ex == 1] = 3                             # expert 1 idle
        if skew:
            ex = skewed(ex, 2, skew)
        wts = jnp.asarray(rs.rand(tokens, 6), jnp.float32)
        live = jnp.arange(tokens) < tokens - 3
        cases.append((
            ("moe_grouped_ffn",),
            f"moe_grouped_ffn {tokens} tokens d={d_e} f={f_e} E={E}"
            + (f" one expert with {skew} rows" if skew else ""),
            lambda x, l, w, e, a, b, c: moes.held_expert_ffn(
                x, l, w, e, (0, E), a, b, c, use_kernel=True,
                n_experts=160)[0],
            lambda x, l, w, e, a, b, c: moes.held_expert_ffn(
                x, l, w, e, (0, E), a, b, c, use_kernel=False,
                n_experts=160)[0],
            (xt, live, wts, jnp.asarray(ex), wg, wu, wd)))

    # the Granite-4.0-H cell's shapes: grouped-query heads (32 over 8 of
    # 64) in both paged kernels with contexts on every edge of the
    # tiling, the state step over 64 slots (idle ones among them, rows
    # out of order) and the chunked scan over a padded chunk of 512
    from paddle_tpu.ops import pallas_ssm as ps
    N, Nk, H, pbs = (8, 2, 64, 16) if TINY else (32, 8, 64, 16)
    mb, wk = (8 if TINY else 160), Nk * H
    gq = dict(kv_heads=Nk, scale=1.0 / 64)
    T = pd.paged_decode_tile_rows(pbs, wk, Nk, 2, mb, N // Nk)
    ctxs = sorted({0, pbs - 1, pbs, T - 1, T, T + 1, 2 * T + 3,
                   mb * pbs - 1} & set(range(mb * pbs)))
    ctxs += [int(c) for c in rs.randint(1, mb * pbs, 12 - len(ctxs))]
    pages = rs.permutation(np.arange(
        1, sum(c // pbs + 1 for c in ctxs) + 1))
    tabs, used = np.zeros((len(ctxs) + 1, mb), np.int32), 0
    for i, c in enumerate(ctxs):
        tabs[i, :c // pbs + 1] = pages[used:used + c // pbs + 1]
        used += c // pbs + 1
    ctxs = np.asarray(ctxs + [0], np.int32)         # + the idle slot
    arena = (len(pages) + 1, pbs, wk)
    cases.append((
        ("paged_decode",),
        f"paged_decode {N} heads over {Nk}x{H} mb={mb} tile={T} rows",
        lambda q, k, v, t, c: pd.paged_decode_attention(
            q, k, v, t, c, N, use_kernel=True, **gq),
        lambda q, k, v, t, c: pd.paged_decode_attention(
            q, k, v, t, c, N, use_kernel=False, **gq),
        (rand((len(ctxs), 1, N * H)), rand(arena), rand(arena), tabs,
         ctxs)))
    C = 32 if TINY else 512
    for p0, n_real in ((0, C), (C, C), (mb * pbs - C, C), (C + 24, C // 2 - 3)):
        row = np.zeros((mb,), np.int32)
        n_alloc = (p0 + n_real - 1) // pbs + 1
        row[:n_alloc] = pages[:n_alloc]
        cases.append((
            ("flash_prefill_chunk",),
            f"flash_prefill_chunk {N} heads over {Nk}x{H} C={C} p0={p0} "
            f"n_real={n_real}",
            lambda q, k, v, t, p0=p0, n=n_real: pd.flash_prefill_chunk(
                q, k, v, t, np.int32(p0), N, use_kernel=True,
                n_real=np.int32(n), **gq)[:, :n],
            lambda q, k, v, t, p0=p0, n=n_real: pd.flash_prefill_chunk(
                q, k, v, t, np.int32(p0), N, use_kernel=False, **gq)[:, :n],
            (rand((1, C, N * H)), rand(arena), rand(arena), row)))
    Ns, D, Hm, S = (16, 256, 8, 6) if TINY else (128, 4096, 64, 64)
    f32 = jnp.float32
    rows = rs.permutation(np.arange(1, S + 1)).astype(np.int32)
    live = np.ones((S,), bool)
    live[[1, S - 2]] = False
    rows[~live] = 0
    cases.append((
        ("mamba2_state_step",),
        f"mamba2_state_step {S} slots over [{Ns}, {D}]",
        lambda *a: ps.mamba2_state_step(*a, use_kernel=True),
        lambda *a: ps.mamba2_state_step(*a, use_kernel=False),
        (rand((S + 1, Ns, D), f32, 1.0), rows, live,
         jnp.asarray(rs.uniform(0.5, 1.0, (S, D)), f32),
         rand((S, D), f32), rand((S, Ns)), rand((S, Ns)))))
    Cs, piece = (256, 128) if TINY else (512, 256)
    dt = rs.uniform(0.001, 0.1, (Cs, Hm)).astype(np.float32)
    dt[Cs - 37:] = 0.0                              # padding positions
    cases.append((
        ("mamba2_chunk_scan",),
        f"mamba2_chunk_scan C={Cs} in pieces of {piece} over [{Ns}, {D}]",
        lambda *a: ps.mamba2_chunk_scan(*a, piece=piece, use_kernel=True),
        lambda *a: ps.mamba2_chunk_scan(*a, piece=piece, use_kernel=False),
        (rand((Cs, D)), dt, -jnp.asarray(rs.uniform(1, 16, (Hm,)), f32),
         rand((Cs, Ns)), rand((Cs, Ns)), rand((Ns, D), f32, 1.0))))

    # the K-EXAONE cell's shapes: 64 query heads over 8 K/V heads of 128
    # (queries 8,192 lanes wide, arenas 1,024). A window layer: the
    # banded chunk over a request's ring (from an empty ring, from the
    # middle of a window, deep in a request; one real position, a part,
    # all) and decode over 64 rings as one 128-row page a request, under
    # its own name. A full layer: both paged kernels with contexts to
    # 13k. The expert products at 6,144 x 2,048 over 16 held experts,
    # the chunk also under the skewed load
    def exaone_cases():
        # a scope of its own: the lambdas of the blocks above read
        # their N, H, gq ... when they are called
        out = []
        N, Nk, H, Wd = (4, 2, 128, 128) if TINY else (64, 8, 128, 128)
        C, S, mb, pbs = (256, 6, 24, 16) if TINY else (512, 64, 832, 16)
        wk = Nk * H
        gq = dict(kv_heads=Nk, scale=H ** -0.5)
        rk, rv = rand((S + 1, Wd, wk)), rand((S + 1, Wd, wk))
        for p0, n_real in ((0, C), (0, 1), (100, 253), (4632, C), (4632, 253)):
            out.append((
                ("window_prefill_chunk",),
                f"window_prefill_chunk {N} heads over {Nk}x{H} window={Wd} "
                f"C={C} p0={p0} n_real={n_real}",
                lambda q, k, v, a, b, p0=p0, n=n_real: pd.window_prefill_chunk(
                    q, k, v, a, b, np.int32(S - 1), np.int32(p0), N,
                    n_real=np.int32(n), use_kernel=True, **gq)[:n],
                lambda q, k, v, a, b, p0=p0, n=n_real: pd.window_prefill_chunk(
                    q, k, v, a, b, np.int32(S - 1), np.int32(p0), N,
                    n_real=np.int32(n), use_kernel=False, **gq)[:n],
                (rand((C, N * H)), rand((C, wk)), rand((C, wk)), rk, rv)))
        rows = rs.permutation(np.arange(1, S + 1)).astype(np.int32)
        ctxs = rs.randint(1, 13000, (S,)).astype(np.int32)
        ctxs[:5] = (0, 5, Wd - 1, Wd, 4 * Wd + 3)
        rows[0] = 0                                     # the idle slot
        out.append((
            ("paged_decode_window",),
            f"paged_decode_window {N} heads over {Nk}x{H}, {S} rings of {Wd}",
            lambda q, a, b, r, c: pd.paged_decode_attention(
                q, a, b, r[:, None], jnp.minimum(c, Wd - 1), N,
                name="paged_decode_window", use_kernel=True, **gq),
            lambda q, a, b, r, c: pd.paged_decode_attention(
                q, a, b, r[:, None], jnp.minimum(c, Wd - 1), N,
                use_kernel=False, **gq),
            (rand((S, 1, N * H)), rk, rv, rows, ctxs)))
        T = pd.paged_decode_tile_rows(pbs, wk, Nk, 2, mb, N // Nk)
        ctxs = sorted({0, pbs - 1, T - 1, T, 2 * T + 3, mb * pbs - 1}
                      & set(range(mb * pbs)))
        ctxs += [int(c) for c in rs.randint(1, mb * pbs, 9 - len(ctxs))]
        pages = rs.permutation(np.arange(
            1, sum(c // pbs + 1 for c in ctxs) + 1))
        tabs, used = np.zeros((len(ctxs) + 1, mb), np.int32), 0
        for i, c in enumerate(ctxs):
            tabs[i, :c // pbs + 1] = pages[used:used + c // pbs + 1]
            used += c // pbs + 1
        ctxs = np.asarray(ctxs + [0], np.int32)         # + the idle slot
        arena = (len(pages) + 1, pbs, wk)
        out.append((
            ("paged_decode",),
            f"paged_decode {N} heads over {Nk}x{H} mb={mb} tile={T} rows",
            lambda q, k, v, t, c: pd.paged_decode_attention(
                q, k, v, t, c, N, use_kernel=True, **gq),
            lambda q, k, v, t, c: pd.paged_decode_attention(
                q, k, v, t, c, N, use_kernel=False, **gq),
            (rand((len(ctxs), 1, N * H)), rand(arena), rand(arena), tabs,
             ctxs)))
        Cf = 32 if TINY else 512
        for p0, n_real in ((0, Cf), (mb * pbs - Cf, Cf),
                           (Cf + 24, Cf // 2 - 3)):
            row = np.zeros((mb,), np.int32)
            n_alloc = (p0 + n_real - 1) // pbs + 1
            row[:n_alloc] = pages[:n_alloc]
            out.append((
                ("flash_prefill_chunk",),
                f"flash_prefill_chunk {N} heads over {Nk}x{H} C={Cf} p0={p0} "
                f"n_real={n_real}",
                lambda q, k, v, t, p0=p0, n=n_real: pd.flash_prefill_chunk(
                    q, k, v, t, np.int32(p0), N, use_kernel=True,
                    n_real=np.int32(n), **gq)[:, :n],
                lambda q, k, v, t, p0=p0, n=n_real: pd.flash_prefill_chunk(
                    q, k, v, t, np.int32(p0), N, use_kernel=False,
                    **gq)[:, :n],
                (rand((1, Cf, N * H)), rand(arena), rand(arena), row)))
        d_x, f_x, E_x = (128, 128, 4) if TINY else (6144, 2048, 16)
        wg, wu = rand((E_x, d_x, f_x), scale=0.02), \
            rand((E_x, d_x, f_x), scale=0.02)
        wd = rand((E_x, f_x, d_x), scale=0.02)
        C_x = 128 if TINY else 512
        for tokens, skew in ((64, 0), (C_x, 0), (C_x, 100 if TINY else 200)):
            xt = rand((tokens, d_x), scale=1.0)
            ex = rs.randint(-4, E_x + 5, (tokens, 8)).astype(np.int32)
            ex[ex == 1] = 3                             # expert 1 idle
            if skew:
                ex = skewed(ex, 2, skew)
            wts = jnp.asarray(rs.rand(tokens, 8), jnp.float32)
            live = jnp.arange(tokens) < tokens - 3
            out.append((
                ("moe_grouped_ffn",),
                f"moe_grouped_ffn {tokens} tokens d={d_x} f={f_x} E={E_x}"
                + (f" one expert with {skew} rows" if skew else ""),
                lambda x, l, w, e, a, b, c: moes.held_expert_ffn(
                    x, l, w, e, (0, E_x), a, b, c, use_kernel=True,
                    n_experts=128)[0],
                lambda x, l, w, e, a, b, c: moes.held_expert_ffn(
                    x, l, w, e, (0, E_x), a, b, c, use_kernel=False,
                    n_experts=128)[0],
                (xt, live, wts, jnp.asarray(ex), wg, wu, wd)))
        return out

    cases.extend(exaone_cases())

    from paddle_tpu.ops import pallas_int8 as p8
    vocab = -(-cfg.vocab_size // p8._BLOCK_V) * p8._BLOCK_V   # row-padded
    hq = rand((16, nh), scale=1.0)
    wq = jnp.asarray(rs.randint(-127, 128, (vocab, nh)), jnp.int8)
    ws = jnp.asarray(0.01 * (0.01 + rs.rand(vocab)), jnp.float32)
    cases.append((("int8_matvec",), f"int8_matvec 16x{nh} over V={vocab}",
                  p8.int8_matvec, p8._matvec_fallback, (hq, wq, ws)))

    # the largest source the MoE gate admits at this width, in bf16
    # (bisected over multiples of 8; the debug mode stops at 64 rows)
    lo, hi = 8, 64 if TINY else 1 << 20
    while hi - lo > 8:
        mid = (lo + hi) // 16 * 8
        if moe.moe_kernel_supported(nh, bf16, n_src=mid):
            lo = mid
        else:
            hi = mid
    n_src = lo
    src = rand((n_src, nh), scale=1.0)
    idx = jnp.asarray(rs.randint(0, n_src + 1, (2 * n_src,)), jnp.int32)
    cases.append((("moe_gather",), f"moe_gather bf16 d={nh} n_src={n_src}",
                  moe._gather_pallas, moe.gather_fallback, (src, idx)))
    idx2 = jnp.asarray(rs.randint(0, n_src + 1, (1024, 2)), jnp.int32)
    w2 = jnp.asarray(rs.rand(1024, 2), jnp.float32)
    cases.append((("moe_combine",), f"moe_combine bf16 d={nh} n_src={n_src}",
                  moe._combine_pallas, moe.combine_fallback,
                  (src, idx2, w2)))
    return cases


def leg_kernels(sz):
    import jax
    from paddle_tpu.analysis.kernel_lint import check_fallback_parity
    from paddle_tpu.ops.kernel_registry import (mosaic_custom_calls,
                                                registered_kernels)

    registry = registered_kernels()
    if not TINY:
        interpreted = sorted({k.module for k in registry
                              if sys.modules[k.module]._interpret()})
        check(not interpreted,
              f"no kernel module is in interpret mode ({interpreted})")
    failed = []

    # every registered kernel at its registered example against its
    # declared fallback at its own tolerance (the debug mode takes the
    # first three: tier-1's tests/test_kernel_parity.py already checks
    # every registration in the interpreter)
    for kern in list(registry)[:3] if TINY else registry:
        errs = []
        findings = check_fallback_parity(
            kern, seeds=(0,) if TINY else (0, 1), errors=errs)
        print(f"  [{'FAIL' if findings else 'ok'}] {kern.name} vs its "
              f"fallback at rtol/atol {kern.tol}: max abs error "
              f"{max(errs, default=float('nan')):.2e}"
              + "".join(f"\n      {f.message}" for f in findings),
              flush=True)
        if findings:
            failed.append(kern.name)

    seen = set()
    for kernels, label, fn, ref, args in _production_cases(sz):
        compiled = jax.jit(fn).lower(*args).compile()
        got = compiled(*args)
        if not TINY:
            names = {n for n, _ in mosaic_custom_calls(compiled.as_text())}
            seen |= names
            if not set(kernels) <= names:
                print(f"  [FAIL] {label}: compiled {sorted(names)}, "
                      f"expected {kernels}")
                failed.append(label)
                continue
        err = max_err(got, jax.jit(ref)(*args))
        ok = err <= BF16_TOL
        print(f"  [{'ok' if ok else 'FAIL'}] {label}: max error "
              f"{err:.2e} vs {BF16_TOL}", flush=True)
        if not ok:
            failed.append(label)
    check(not failed, f"every kernel agrees with its fallback ({failed})")
    return {"registered": len(registry), "production_kernels": sorted(seen)}


# ---------------------------------------------------------------------------
# four-chip leg
# ---------------------------------------------------------------------------

def leg_four_chip(sz):
    import re
    import jax
    import paddle_tpu as paddle
    from paddle_tpu import amp, optimizer, telemetry
    from paddle_tpu import distributed as dist
    from paddle_tpu.distributed import env
    from paddle_tpu.models.gpt import GPTForPretraining

    n_dev = jax.device_count()
    if n_dev < 4:
        return f"not run: {n_dev} device(s), needs 4"

    cfg = sz.model
    batch = 2 * sz.batch
    ids, lbl = seeded_token_batch(cfg.vocab_size, batch, sz.seq)

    # one-chip reference for the first-step loss: forward only, in
    # halves (equal token counts, so the mean of the halves' mean
    # losses is the batch's), before any mesh exists
    paddle.seed(0)
    ref_model = GPTForPretraining(cfg)
    ref_loss_fn = paddle.jit.to_static(ref_model.loss)
    halves = []
    with amp.auto_cast(enable=True, dtype="bfloat16"):
        for part in (slice(0, sz.batch), slice(sz.batch, batch)):
            halves.append(float(ref_loss_fn(
                paddle.to_tensor(np.asarray(ids._value)[part]),
                paddle.to_tensor(np.asarray(lbl._value)[part])).item()))
    ref_loss = float(np.mean(halves))
    del ref_model, ref_loss_fn
    gc.collect()

    mesh = dist.build_mesh(dp=2, mp=2, devices=jax.devices()[:4])
    try:
        paddle.seed(0)
        model = GPTForPretraining(cfg)
        opt = optimizer.AdamW(learning_rate=1e-4, weight_decay=0.01,
                              parameters=model.parameters())
        dist.shard_model(model, mesh)

        def loss_fn(i, l):
            with amp.auto_cast(enable=True, dtype="bfloat16"):
                return model.loss(i, l)

        with telemetry.CompileObservatory(action="record") as obs:
            step = dist.ShardedTrainStep(model, loss_fn, opt, zero_stage=1)
            losses = [float(step(ids, lbl).item()) for _ in range(3)]
        print(f"  losses at {batch} x {sz.seq} on dp=2 x mp=2: {losses}; "
              f"one-chip first-step reference {ref_loss} ({halves})")
        check(all(np.isfinite(losses)) and losses[-1] < losses[0],
              "finite, falling loss")
        check(abs(losses[0] - ref_loss) <= BF16_TOL,
              f"first-step loss within {BF16_TOL} of the one-chip "
              f"forward on the same seed and batch "
              f"(diff {abs(losses[0] - ref_loss):.2e})")

        # layout: every parameter and optimizer state lies over the
        # devices its spec says, in shards of the spec's shape
        wrong = []
        split = collections.Counter()
        for name, p in zip(step.param_names, step.params):
            want = step._param_sharding(p)
            placed = [("param", p._value, want)] + [
                (slot, val, step._state_sharding(p))
                for slot, val in opt._states[id(p)].items()
                if np.shape(val) == tuple(p._value.shape)]
            for what, val, sh in placed:
                shard_shape = sh.shard_shape(tuple(val.shape))
                ok = (val.sharding.is_equivalent_to(sh, val.ndim)
                      and len(val.sharding.device_set) == 4
                      and all(s.data.shape == shard_shape
                              for s in val.addressable_shards))
                split[what, shard_shape != tuple(val.shape)] += 1
                if not ok:
                    wrong.append((name, what, str(val.sharding)))
        print(f"  (array, is split) counts: {dict(split)}")
        check(not wrong, f"parameters and optimizer states are laid out "
                         f"as their specs say ({wrong[:3]})")
        check(split["param", True] > 0 and split["moment1", True]
              > split["param", True],
              "tagged parameters are split over mp, and ZeRO-1 splits "
              "more of the optimizer state (over dp) than of the "
              "parameters")
        stats = [d.memory_stats() for d in jax.devices()[:4]]
        if all(stats):
            used = [s["bytes_in_use"] for s in stats]
            print(f"  bytes_in_use per device: {used}")
            check(max(used) <= 2 * min(used),
                  "bytes_in_use is of the same order on all four devices")
        else:
            check(TINY, "the backend reports memory_stats()")

        if not TINY:
            names, shapes = kernels_in(obs, "ShardedTrainStep")
            print(f"  Mosaic kernels in the sharded step: {dict(names)} "
                  f"on {shapes}")
            n, h = cfg.num_heads, cfg.hidden_size // cfg.num_heads
            per_device = f"bf16[{batch // 2 * n // 2},{sz.seq},{h}]"
            flash = [o for k, ops in shapes.items() for o in ops
                     if k.startswith("flash")]
            check(flash and all(o[0] == per_device for o in flash),
                  f"the attention kernels work on the per-device shard "
                  f"{per_device} (batch/dp x heads/mp), not the global "
                  f"bf16[{batch * n},{sz.seq},{h}]")
            text = "".join(c.as_text() for f, c in obs.compiled_programs()
                           if f.startswith("ShardedTrainStep"))
            n_ar = len(re.findall(r" all-reduce(-start)?\(", text))
            check(n_ar > 0, f"the compiled step all-reduces ({n_ar} "
                            "all-reduce ops)")
    finally:
        env.clear_mesh()
    return {"losses": losses, "ref_loss": ref_loss}


# ---------------------------------------------------------------------------

def main():
    import jax
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    print(f"jax {jax.__version__}  default_backend "
          f"{jax.default_backend()}  device {device}", flush=True)
    if TINY:
        print("CHIP_SMOKE_DEBUG=tiny: toy sizes, interpreted kernels — "
              "this run cannot pass")
    else:
        if dev.platform != "tpu" or jax.default_backend() != "tpu":
            print(f"chip_smoke: platform is {dev.platform!r}, not 'tpu' — "
                  "nothing was run", file=sys.stderr)
            return 2
        from paddle_tpu.telemetry.mfu import device_peak_flops
        device_peak_flops()     # raises for a kind with no peak row

    from paddle_tpu import compile_cache
    cache_dir = compile_cache.enable()
    cache = compile_cache.CacheCounter()
    held = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    print(f"compile cache: {cache_dir} ({held} entries from earlier runs)")

    sz = sizes()
    for name, leg in (("train", leg_train), ("serve", leg_serve),
                      ("kernels", leg_kernels),
                      ("four_chip", leg_four_chip)):
        print(f"LEG {name}", flush=True)
        t0 = time.perf_counter()
        try:
            out = leg(sz)
        except Exception:
            traceback.print_exc()
            print(f"LEG {name}: FAIL after "
                  f"{time.perf_counter() - t0:.0f}s", flush=True)
            return 1
        if isinstance(out, dict):
            out = (f"PASS ({time.perf_counter() - t0:.0f}s, not a result) "
                   f"{out}")
        print(f"LEG {name}: {out}", flush=True)
    print(f"compile cache: {cache.hits} hits, {cache.misses} misses in "
          f"{cache_dir}" + (" — an earlier run's programs were reused"
                            if held and cache.hits else ""))
    if TINY:
        print("NOT A CHIP RUN")
        return 3
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
