"""Serving driver of the K-EXAONE configuration: `drivers.serve`'s
window, latency and schedule, with what that driver takes from GPT
brought here: the seeded weights (drawn on the device leaf by leaf in
bfloat16, the reference's own leaves under the names the program holds
them by), the model builder, the work counts of `work_exaone.py`, the
expert layer's and the rings' counters, and the check through
`reference/exaone_moe.py`.

`correct` is decided as in the DeepSeek-V2 cell (`drivers/serve_mla.py`),
because a random sigmoid top-8 router is as chaotic as that one: a
served token is compared only where every router of the reference chose
by at least `check.route_margin`, `served_logit_gap_p<share>` is the gap
that `check.within_share` of the compared tokens stay within, and
`route_left_out`, the share of served tokens not compared, is held to a
limit of its own so that the rule cannot empty the comparison.

A model that keeps rows by request hands its dispatches one argument
more than GPT's (the slots' rows after a decode step's twelve, the
request's row after a chunk's thirteen); the taps' `_detail` reads that
layout and refuses any other.
"""
import time

import jax.numpy as jnp
import numpy as np

from benchmark import harness, schedule, work_exaone
from benchmark.drivers import serve
from benchmark.reference import exaone_moe as ref

CONFIG_KEYS = ("vocab_size", "hidden_size", "layer_types",
               "mlp_layer_types", "num_attention_heads",
               "num_key_value_heads", "head_dim", "sliding_window",
               "intermediate_size", "moe_intermediate_size",
               "num_experts_per_tok", "num_shared_experts",
               "routed_scaling_factor", "norm_topk_prob", "rms_norm_eps",
               "rope_theta", "initializer_range")


def program_config(m, max_seq_len, dtype):
    """The program's `ExaoneMoeConfig` at the sizes `m`."""
    from paddle_tpu.models.exaone_moe import ExaoneMoeConfig
    return ExaoneMoeConfig(
        **{k: m[k] for k in CONFIG_KEYS}, num_experts=m["router_experts"],
        held=m["held_experts"], max_seq_len=max_seq_len, dtype=dtype)


def seeded_program_model(m, seed, init, max_seq_len, dtype="bfloat16"):
    """The program's model with every parameter drawn from the seed as
    the reference draws it: the same leaves under the same names,
    rounded to bfloat16. The program holds a layer's routed experts
    stacked, which is a rearrangement of the reference's leaves."""
    from paddle_tpu.models.exaone_moe import ExaoneMoeForCausalLM
    dt = jnp.dtype(dtype)
    outer = {"embed": ref.EMBED, "norm": ref.FINAL_NORM, "head": ref.HEAD}
    stacked = {"moe.experts_" + n: j
               for j, (n, _, _) in enumerate(ref.expert_leaves(m))}
    tables = {}

    def make(name, shape, kind):
        if name in outer:
            out = ref.outer_weights(m, seed, outer[name], dt)
        else:
            _, layer, leaf = name.split(".", 2)
            layer = int(layer)
            if leaf in stacked:
                first, count = m["held_experts"]
                out = jnp.stack([
                    ref.expert_leaf(m, seed, layer, e, stacked[leaf], init,
                                    dt) for e in range(first, first + count)])
            else:
                if layer not in tables:
                    tables[layer] = {n: j for j, (n, _, _) in enumerate(
                        ref.layer_leaves(m, layer))}
                out = ref.layer_leaf(m, seed, layer, tables[layer][leaf],
                                     init, dt)
        if tuple(out.shape) != tuple(shape):
            raise SystemExit(f"weight shape mismatch at {name}: "
                             f"{out.shape} for {shape}")
        return out

    return ExaoneMoeForCausalLM(
        program_config(m, max_seq_len, dtype), make=make)


MOE_COUNTERS = ("moe_tokens_routed", "moe_pairs_chosen", "moe_pairs_held",
                "moe_load_max", "moe_load_mean", "moe_experts_reached")
ROW_COUNTERS = ("window_rows_taken", "window_rows_released", "state_replays")
LONG_TOKENS = 6000      # "long": a hundredth of it is a window layer's reach
LONG_REQUESTS = 3       # of them among the sampled, where as many finished


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
        self.seen = []      # (end of a step, experts reached, pairs held,
                            # rings live) as counted so far, traced runs

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
        self.log(f"program parameters: {model.num_parameters()}")
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
        # warm the cell's shapes: a prompt of two chunks (a whole one and
        # a part) and a few decode steps, then a second request, whose
        # first decode step changes the batch with a step in flight.
        # There is no prefix cache for this model and so no fork
        rng = np.random.default_rng([self.seed, 13])
        chunk = int(ecfg["prefill_chunk"])
        for tail in (chunk + 8, 8):
            prompt = rng.integers(1, vocab, tail)
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
        layers have counted so far (the roofline of their kernel takes
        its bytes from the experts the traced steps reached) and how
        many rings are live."""
        super()._tap()
        from paddle_tpu import monitor
        step = self.engine.step

        def counted_step():
            out = step()
            self.seen.append(
                (time.perf_counter(),
                 monitor.get("serving.moe_experts_reached"),
                 monitor.get("serving.moe_pairs_held"),
                 monitor.get_gauge("serving.window_rows_live")))
            return out

        self.engine.step = counted_step

    def _detail(self, family, args):
        """As the base driver's, for dispatches that carry request rows
        as their last argument."""
        slots = int(self.spec.cell["engine"]["max_slots"])
        if family in ("serving_decode", "serving_decode_sampling"):
            if len(args) != 13:
                raise TypeError(f"13 arguments expected, got {len(args)}")
            rows = np.asarray(args[12])
            if rows.dtype != np.int32 or rows.shape != (slots,) \
                    or rows.min() < 0 or rows.max() > slots:
                raise TypeError(f"request rows int32[{slots}] in "
                                f"0..{slots} expected at 12")
            return super()._detail(family, args[:12])
        if family == "serving_prefill":
            if len(args) != 14:
                raise TypeError(f"14 arguments expected, got {len(args)}")
            row = np.asarray(args[13])
            if row.dtype != np.int32 or row.shape != () \
                    or not 1 <= int(row) <= slots:
                raise TypeError(f"a request row in 1..{slots} expected "
                                "at 13")
            return super()._detail(family, args[:13])
        return super()._detail(family, args)

    def _counters(self):
        out = super()._counters()
        snap = self.engine.metrics_snapshot()
        for name in MOE_COUNTERS + ROW_COUNTERS:
            out[name] = snap.get("serving." + name, 0.0)
        return out

    def _reduce(self, t0, t_close, t_end, after, tracer):
        out = super()._reduce(t0, t_close, t_end, after, tracer)
        moe = {n: after[n] - self.base[n] for n in MOE_COUNTERS}
        rows = {n: after[n] - self.base[n] for n in ROW_COUNTERS}
        counters = out["records"]["counters"]
        if moe["moe_pairs_chosen"]:
            counters["moe_held_share"] = 100.0 * moe["moe_pairs_held"] \
                / moe["moe_pairs_chosen"]
        if moe["moe_load_mean"]:
            counters["expert_load_max_over_mean"] = moe["moe_load_max"] \
                / moe["moe_load_mean"]
        counters.update(rows)
        self.log(f"expert layer over the window: {moe}")
        self.log(f"rings over the window: {rows}")
        return out

    def _traced_records(self, records, tracer):
        """Step times, occupancy, the share of rings live and the work
        of the steps, from the taps of a traced run (the base driver's,
        with this model's counts)."""
        m = self.m
        slots = int(self.spec.cell["engine"]["max_slots"])
        t_a, t_b = self.t0_perf, self.t0_perf + self.seconds
        steps = [(a, b) for a, b in self.steps if t_a <= a and b <= t_b]
        dispatches = [x for x in self.dispatches if t_a <= x[1] <= t_b]
        if not steps or len(dispatches) < 2:
            return
        records["clock"]["engine_step_ms"] = harness.median(
            [(b - a) * 1e3 for a, b in steps])
        live = [x[3] for x in self.seen if t_a <= x[0] <= t_b]
        if live:
            records["counters"]["window_rows_live_share"] = \
                100.0 * harness.median(live) / slots
        occ, flops, expected = [], 0.0, 0.0
        traced_ctx, traced_chunks = [], []
        on = tracer.t_on if tracer and tracer.t_on else None
        off = tracer.t_off if tracer and tracer.t_off else None
        for family, t, detail in dispatches:
            in_trace = on is not None and on <= t <= off
            if family.startswith("serving_decode"):
                ctx = detail[detail > 0]
                occ.append(100.0 * len(ctx) / slots)
                flops += sum(work_exaone.decode_token_flops(c + 1, m)
                             for c in ctx)
                tokens = len(ctx)
                if in_trace:
                    traced_ctx.extend(int(c) + 1 for c in ctx)
            elif family == "serving_prefill":
                p0, tokens = detail
                flops += work_exaone.prefill_chunk_flops(
                    p0, tokens, m, last_chunk=False)
                if in_trace:
                    traced_chunks.append((p0, tokens))
            else:
                continue
            if in_trace:
                expected += work_exaone.sparse_layers(m) \
                    * work_exaone.experts_touched(tokens, m)
        records["counters"]["decode_occupancy"] = harness.median(occ) \
            if occ else None
        work = records["work"]
        work["serve_step"] = {"flops_per_s": flops / self.seconds}
        if traced_ctx:
            work["paged_decode"] = {
                "bytes": work_exaone.decode_attention_bytes(traced_ctx, m),
                "flops": work_exaone.decode_attention_flops(traced_ctx, m)}
            work["paged_decode_window"] = {
                "bytes": work_exaone.window_decode_bytes(traced_ctx, m),
                "flops": work_exaone.window_decode_flops(traced_ctx, m)}
        if traced_chunks:
            work["window_prefill_chunk"] = {
                "bytes": sum(work_exaone.window_prefill_bytes(p0, n, m)
                             for p0, n in traced_chunks),
                "flops": sum(work_exaone.window_prefill_flops(p0, n, m)
                             for p0, n in traced_chunks)}
        # the counts as they stood at the last step that ended before
        # the trace went on, and before it went off
        before = [x for x in self.seen if on is not None and x[0] <= on]
        within = [x for x in self.seen if on is not None and x[0] <= off]
        if before and within and within[-1][1] > before[-1][1]:
            reached = within[-1][1] - before[-1][1]
            pairs = within[-1][2] - before[-1][2]
            f, b = work_exaone.expert_work(pairs, reached, m)
            work["moe_grouped_ffn"] = {"bytes": b, "flops": f}
            self.log(f"traced: {reached:.0f} experts reached (had the "
                     f"dispatches' tokens been routed uniformly: "
                     f"{expected:.0f}) and {pairs:.0f} pairs held in "
                     f"{len(within) - len(before)} steps")

    # -- correct ------------------------------------------------------------
    def sample(self):
        """The finished requests `correct` looks at: the longest, then
        long ones (over `LONG_TOKENS` tokens, where a window layer sees
        a hundredth of what a full layer does) until `LONG_REQUESTS` are
        in, and the rest drawn from the seed."""
        done = [r for r in self.recs if r.state == "finished"
                and len(r.tokens) == r.want]
        if not done:
            return []
        k = int(self.spec.cell["check_requests"])
        size = lambda r: len(r.prompt) + r.want
        done.sort(key=lambda r: (-size(r), r.idx))
        rng = np.random.default_rng([self.seed, 17])
        long_ = [r for r in done if size(r) > LONG_TOKENS]
        picked = done[:1]
        rest = [r for r in long_ if r is not done[0]]
        for i in rng.permutation(len(rest))[:max(0, LONG_REQUESTS - 1)]:
            picked.append(rest[i])
        rest = [r for r in done if r not in picked]
        for i in rng.permutation(len(rest))[:max(0, k - len(picked))]:
            picked.append(rest[i])
        return picked[:k]

    def gaps(self, controls=None):
        """As `drivers/serve_mla.py`'s, through this configuration's
        reference: the whole forward pass of each sampled request's
        prompt and answer, and how far the served tokens' logits lie
        under the reference's best, over the tokens whose reference
        routers all chose by at least `check.route_margin`. `controls`
        is {name: prec}: the tokens the reference puts first in each
        lower precision, held to the same rule at the same positions."""
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
               "lengths": [len(s) for s in seqs],
               "controls": {n: {"gap": within(row[at]),
                                "flipped": int(np.sum(row[at] > 0))}
                            for n, row in zip(controls, below[1:])}}
        self.log(f"served tokens under the reference's best: {out}")
        # a token each: what tools/calibrate_mixed.py keeps, so that
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
                 f"of {len(self.sample())} requests of {g['lengths']} "
                 f"tokens with the reference; {g['flipped']} are not the "
                 "reference's first")
        return self.compared(g["served"], g["left_out"])

    def compared(self, gap, left_out):
        """The rows `correct` is decided on; the controls and faults of
        tools/calibrate_mixed.py go through them too."""
        limits = self.spec.cell["limits"]
        name = "served_logit_gap_p%d" % round(
            100 * self.spec.cell["check"]["within_share"])
        return [{"name": name, "value": gap, "limit": limits[name]},
                {"name": "route_left_out", "value": left_out,
                 "limit": limits["route_left_out"]}]
