"""Serving driver of the DeepSeek-V2 configuration: `drivers.serve`'s
window, latency, schedule and `served_logit_gap` arithmetic, with what
that driver takes from GPT brought here: the seeded weights (drawn on
the device leaf by leaf in bfloat16, the reference's own leaves), the
model builder, the work counts of `work_mla.py`, the expert layer's
counters, and the check through `reference/deepseek_v2.py`.

The engine's dispatch arguments are laid out as for GPT (tokens,
contexts and tables of a decode step at 3, 4, 5; a chunk's ids, first
position and real tokens at 3, 4, 5), so the taps' `_detail` is the
base driver's.
"""
import time

import jax.numpy as jnp
import numpy as np

from benchmark import harness, schedule, work_mla
from benchmark.drivers import serve
from benchmark.reference import deepseek_v2 as ref


def program_config(m, max_seq_len, dtype):
    """The program's `DeepseekV2Config` at the sizes `m`."""
    from paddle_tpu.models.deepseek_v2 import DeepseekV2Config
    keys = ("vocab_size", "hidden_size", "num_layers",
            "num_attention_heads", "q_lora_rank", "kv_lora_rank",
            "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
            "intermediate_size", "moe_intermediate_size",
            "n_shared_experts", "num_experts_per_tok", "n_group",
            "topk_group", "routed_scaling_factor", "first_k_dense_replace",
            "rms_norm_eps", "rope_theta", "rope_scaling",
            "initializer_range")
    return DeepseekV2Config(
        **{k: m[k] for k in keys if k in m},
        n_routed_experts=m["router_experts"], held=m["held_experts"],
        max_seq_len=max_seq_len, dtype=dtype)


def seeded_program_model(m, seed, init, max_seq_len,
                         dtype="bfloat16"):
    """The program's model with every parameter drawn from the seed as
    the reference draws it: the same leaves, rounded to bfloat16. The
    program holds `kv_b` as its two halves a head and a layer's routed
    experts stacked; both are rearrangements of the reference's
    leaves."""
    from paddle_tpu.models.deepseek_v2 import DeepseekV2ForCausalLM
    dt = jnp.dtype(dtype)
    std = ref.stds(m, init)
    H, nope, v = m["num_attention_heads"], m["qk_nope_head_dim"], \
        m["v_head_dim"]
    rank = m["kv_lora_rank"]
    outer = {"embed": ref.EMBED, "norm": ref.FINAL_NORM, "head": ref.HEAD}

    def make(name, shape, kind):
        if name in outer:
            out = ref.outer_weights(m, seed, outer[name], dt)
        else:
            _, layer, leaf = name.split(".", 2)
            out = layer_leaf(int(layer), leaf.replace("attn.", "", 1)
                             if leaf.startswith("attn.") else leaf)
        if tuple(out.shape) != tuple(shape):
            raise SystemExit(f"weight shape mismatch at {name}: "
                             f"{out.shape} for {shape}")
        return out

    def layer_leaf(layer, leaf):
        tag = ref._LAYER_TAG + layer
        table = ref.attention_leaves(m) + ref.ffn_leaves(m, layer)
        index = {n: (j, s, k) for j, (n, s, k) in enumerate(table)}

        def plain(n):
            j, shape, kind = index[n]
            return ref.draw(seed, tag, j, shape, kind, std[kind], dt)
        if leaf in ("w_uk", "w_uv"):
            kvb = plain("kv_b").reshape(rank, H, nope + v)
            return jnp.transpose(kvb[:, :, :nope], (1, 2, 0)) \
                if leaf == "w_uk" else jnp.transpose(kvb[:, :, nope:],
                                                     (1, 0, 2))
        if leaf.startswith("moe.experts_"):
            j, (_, shape, kind) = next(
                (j, row) for j, row in enumerate(ref.expert_leaves(m))
                if row[0] == leaf.split("experts_")[1])
            first, count = m["held_experts"]
            return jnp.stack([ref.draw(
                seed, tag, ref._EXPERT_LEAF + 3 * e + j, shape, kind,
                std[kind], dt) for e in range(first, first + count)])
        return plain(leaf)

    return DeepseekV2ForCausalLM(
        program_config(m, max_seq_len, dtype), make=make)


MOE_COUNTERS = ("moe_tokens_routed", "moe_pairs_chosen", "moe_pairs_held",
                "moe_load_max", "moe_load_mean", "moe_experts_reached")


class Driver(serve.Driver):
    def __init__(self, spec, seed, seconds, devices, log=print, trace=False):
        self.spec, self.seed, self.seconds = spec, int(seed), float(seconds)
        self.devices, self.log, self.trace = devices, log, bool(trace)
        self.m = ref.sizes(spec.config)
        self.init = dict(spec.cell.get("init", {}))
        self.closing = False
        self.drains = spec.traffic.get("at_close", "drain") == "drain"
        self.window_over = False
        self.steps, self.dispatches = [], []
        self.tap_fault = None
        self.recs = []
        self.moe_seen = []      # (end of a step, experts reached, pairs
                                # held) as counted so far, traced runs

    # -- set-up -------------------------------------------------------------
    def build_model(self, max_seq_len, dtype):
        return seeded_program_model(self.m, self.seed, self.init,
                                    max_seq_len, dtype=dtype)

    def setup(self):
        t0 = time.perf_counter()
        from paddle_tpu.serving import (EngineConfig, SamplingParams,
                                        ServingEngine)
        t_import = time.perf_counter()
        ecfg = dict(self.spec.cell["engine"])
        prec = self.spec.config["precision"]
        if ecfg.get("weights") != prec["serve_weights"]:
            raise SystemExit(f"cell serves weights={ecfg.get('weights')!r}, "
                             "the configuration states "
                             f"{prec['serve_weights']!r}")
        vocab = self.m["vocab_size"]
        model = self.build_model(int(ecfg["max_model_len"]), prec["params"])
        t_weights = time.perf_counter()
        self.engine = ServingEngine(model, config=EngineConfig(
            **dict(ecfg, dtype=prec["params"])))
        self.model = model
        t_engine = time.perf_counter()

        sched = schedule.build_schedule(self.spec.traffic, self.seconds)
        prompts = schedule.materialize(sched, self.spec.traffic, self.seed,
                                       vocab)
        self.recs = [serve.Rec(i, r["due_s"], prompts[i], r["output_len"])
                     for i, r in enumerate(sched)]
        self.params = [SamplingParams(max_new_tokens=r.want)
                       for r in self.recs]
        if self.trace:
            self._tap()
        self.engine.start()
        # warm the cell's shapes, as the base driver does: a prompt of
        # two chunks (prefill and decode), then one that shares a block
        # and a half with it, so the copy-on-write fork is compiled too
        rng = np.random.default_rng([self.seed, 13])
        bs, chunk = int(ecfg["block_size"]), int(ecfg["prefill_chunk"])
        head = rng.integers(1, vocab, bs + bs // 2)
        for tail in (chunk + 8, 8):
            prompt = np.concatenate([head, rng.integers(1, vocab, tail)])
            self.engine.submit(prompt.astype(np.int32), SamplingParams(
                max_new_tokens=4)).result(timeout=1100)
        self.base = self._counters()
        t_warm = time.perf_counter()
        self.log(f"schedule: {len(self.recs)} requests due in "
                 f"{self.seconds:g} s; {sum(r.want for r in self.recs)} "
                 "output tokens asked")
        return {"import_s": t_import - t0, "weights_s": t_weights - t_import,
                "engine_build_s": t_engine - t_weights,
                "warm_up_s": t_warm - t_engine}

    def _tap(self):
        """The base driver's taps, and after every step what the expert
        layers have counted so far: the roofline of their kernel takes
        its bytes from the experts the traced steps reached."""
        super()._tap()
        from paddle_tpu import monitor
        step = self.engine.step

        def counted_step():
            out = step()
            self.moe_seen.append(
                (time.perf_counter(),
                 monitor.get("serving.moe_experts_reached"),
                 monitor.get("serving.moe_pairs_held")))
            return out

        self.engine.step = counted_step

    def _counters(self):
        out = super()._counters()
        snap = self.engine.metrics_snapshot()
        for name in MOE_COUNTERS:
            out[name] = snap.get("serving." + name, 0.0)
        return out

    def _reduce(self, t0, t_close, t_end, after, tracer):
        out = super()._reduce(t0, t_close, t_end, after, tracer)
        moe = {n: after[n] - self.base[n] for n in MOE_COUNTERS}
        counters = out["records"]["counters"]
        if moe["moe_pairs_chosen"]:
            counters["moe_held_share"] = 100.0 * moe["moe_pairs_held"] \
                / moe["moe_pairs_chosen"]
        if moe["moe_load_mean"]:
            counters["expert_load_max_over_mean"] = moe["moe_load_max"] \
                / moe["moe_load_mean"]
        self.log(f"expert layer over the window: {moe}")
        return out

    def _traced_records(self, records, tracer):
        """Step times, occupancy and the work of the steps, from the
        taps of a traced run (the base driver's, with this model's
        counts)."""
        m = self.m
        slots = int(self.spec.cell["engine"]["max_slots"])
        t_a, t_b = self.t0_perf, self.t0_perf + self.seconds
        steps = [(a, b) for a, b in self.steps if t_a <= a and b <= t_b]
        dispatches = [x for x in self.dispatches if t_a <= x[1] <= t_b]
        if not steps or len(dispatches) < 2:
            return
        records["clock"]["engine_step_ms"] = harness.median(
            [(b - a) * 1e3 for a, b in steps])
        occ, flops, in_trace_ctx, expected = [], 0.0, [], 0.0
        layers = m["num_layers"] - m["first_k_dense_replace"]
        on = tracer.t_on if tracer and tracer.t_on else None
        off = tracer.t_off if tracer and tracer.t_off else None
        for family, t, detail in dispatches:
            if family.startswith("serving_decode"):
                ctx = detail[detail > 0]
                occ.append(100.0 * len(ctx) / slots)
                flops += sum(work_mla.decode_token_flops(c + 1, m)
                             for c in ctx)
                tokens = len(ctx)
                if on is not None and on <= t <= off:
                    in_trace_ctx.extend(int(c) + 1 for c in ctx)
            elif family == "serving_prefill":
                p0, tokens = detail
                flops += work_mla.prefill_chunk_flops(p0, tokens, m,
                                                      last_chunk=False)
            else:
                continue
            if on is not None and on <= t <= off:
                expected += layers * work_mla.experts_touched(tokens, m)
        records["counters"]["decode_occupancy"] = harness.median(occ) \
            if occ else None
        records["work"]["serve_step"] = {"flops_per_s": flops / self.seconds}
        if in_trace_ctx:
            records["work"]["mla_paged_decode"] = {
                "bytes": work_mla.decode_attention_bytes(in_trace_ctx, m),
                "flops": work_mla.decode_attention_flops(in_trace_ctx, m)}
        # the counts as they stood at the last step that ended before
        # the trace went on, and before it went off
        before = [x for x in self.moe_seen if on is not None and x[0] <= on]
        within = [x for x in self.moe_seen if on is not None and x[0] <= off]
        if before and within and within[-1][1] > before[-1][1]:
            reached = within[-1][1] - before[-1][1]
            pairs = within[-1][2] - before[-1][2]
            f, b = work_mla.expert_work(pairs, reached, m)
            records["work"]["moe_grouped_ffn"] = {"bytes": b, "flops": f}
            self.log(f"traced: {reached:.0f} experts reached (had the "
                     f"dispatches' tokens been routed uniformly: "
                     f"{expected:.0f}) and {pairs:.0f} pairs held in "
                     f"{len(within) - len(before)} steps")

    # -- correct ------------------------------------------------------------
    def gaps(self, controls=None):
        """As the base driver's, through this configuration's reference:
        the whole forward pass of each sampled request's prompt and
        answer, and how far the served tokens' logits lie under the
        reference's best. Two things differ, both because the routed
        experts are at their full scale and a random router's sixth and
        seventh score lie closer than bfloat16 rounding moves them, so
        that one expert chosen otherwise moves a logit by whole units:

        * a served token is compared only where every router of the
          reference chose by at least `check.route_margin`
          (`reference.route`); `left_out` is the share of served tokens
          that were not, and it is compared too, so that the rule cannot
          empty the comparison;
        * `served` is the gap that `check.within_share` of the compared
          tokens stay within, not the widest: a choice can still differ
          beyond the margin, at a token in a hundred, and that is no
          fault of the arithmetic.

        `controls` is {name: prec}: the tokens the reference puts first
        in each lower precision, held to the same rule at the same
        positions."""
        controls = controls or {}
        sample = self.sample()
        seqs = [np.concatenate([r.prompt, np.asarray(r.tokens, np.int32)])
                for r in sample]
        spans = [(len(r.prompt) - 1, len(r.tokens)) for r in sample]
        length = int(self.spec.cell["engine"]["max_model_len"])
        args = (self.m, self.seed, self.init, seqs, spans)
        probes = [[np.asarray(r.tokens, np.int32)] for r in sample]
        for prec in controls.values():
            low = ref.position_logits(*args, probes, prec=prec, length=length)
            for rows, (_, first, _, _) in zip(probes, low):
                rows.append(first)
        check = self.spec.cell["check"]
        rows = ref.position_logits(*args, probes, length=length, log=self.log)
        at = np.concatenate([margin >= float(check["route_margin"])
                             for _, _, _, margin in rows])
        below = np.concatenate([best[None] - probed
                                for best, _, probed, _ in rows], axis=1)

        def within(gaps):
            return float(np.percentile(gaps, 100.0 * check["within_share"])) \
                if len(gaps) else 0.0
        out = {"served": within(below[0, at]), "tokens": int(at.size),
               "compared": int(at.sum()),
               "left_out": 1.0 - float(at.mean()) if at.size else 1.0,
               "flipped": int(np.sum(below[0, at] > 0)),
               "widest": float(below[0, at].max()) if at.any() else 0.0,
               "widest_of_all": float(below[0].max()),
               "controls": {n: {"gap": within(row[at]),
                                "flipped": int(np.sum(row[at] > 0))}
                            for n, row in zip(controls, below[1:])}}
        self.log(f"served tokens under the reference's best: {out}")
        # a token each: what tools/calibrate_docs.py keeps, so that
        # another margin or share can be read off without another run
        out["margin"] = np.concatenate([row[3] for row in rows])
        out["below"] = below
        return out

    def check(self):
        if not self.sample():
            return [{"name": "finished_requests", "value": 1.0,
                     "limit": 0.0}]
        g = self.gaps()
        self.log(f"compared {g['compared']} of {g['tokens']} served tokens "
                 f"of {len(self.sample())} requests with the reference; "
                 f"{g['flipped']} are not the reference's first")
        return self.compared(g["served"], g["left_out"])

    def compared(self, gap, left_out):
        """The rows `correct` is decided on; the controls of
        tools/calibrate_docs.py go through them too."""
        limits = self.spec.cell["limits"]
        name = "served_logit_gap_p%d" % round(
            100 * self.spec.cell["check"]["within_share"])
        return [{"name": name, "value": gap, "limit": limits[name]},
                {"name": "route_left_out", "value": left_out,
                 "limit": limits["route_left_out"]}]
