"""Serving driver of the Qwen3-Next configuration: `drivers.serve`'s
window, latency and schedule, with what that driver takes from GPT
brought here: the seeded weights (drawn on the device leaf by leaf in
bfloat16, the reference's own leaves under the names the program holds
them by), the model's construction, the work counts of
`work_qwen3next.py`, the expert layer's and the request rows' counters,
and the check through `reference/qwen3_next.py`.

`correct` is decided on four numbers. The first two are the K-EXAONE
cell's (`drivers/serve_exaone.py`), because a random softmax router over
512 experts has near-ties as DeepSeek-V2's does: a served token is
compared only where every router of the reference chose by at least
`check.route_margin`, `served_logit_gap_p<share>` is the gap that
`check.within_share` of the compared tokens stay within, and
`route_left_out`, the share of served tokens not compared, is held to a
limit of its own. The third is the Granite cell's `state_gap`
(`drivers/serve_hybrid.py`): the float32 delta-rule states of requests
still live when the engine is stopped, against the states the
reference's recurrence reaches over the same tokens, worst layer. The
fourth, `state_gap_first`, reads the same states in the first linear
layer alone, worst value head: its input is the embedding, so the
rounding of the bfloat16 activations that every layer after it passes on
is small there, and a state held in bfloat16 stands out of it.

A model that keeps rows by request hands its dispatches one argument
more than GPT's (the slots' rows after a decode step's twelve, the
request's row after a chunk's thirteen); the taps' `_detail` (the
K-EXAONE driver's) reads that layout and refuses any other.
"""
import time

import jax.numpy as jnp
import numpy as np

from benchmark import harness, schedule, work_qwen3next as work
from benchmark.drivers import serve, serve_exaone
from benchmark.reference import qwen3_next as ref

CONFIG_KEYS = ("vocab_size", "hidden_size", "layer_types",
               "num_attention_heads", "num_key_value_heads", "head_dim",
               "partial_rotary_factor", "rope_theta",
               "linear_num_key_heads", "linear_num_value_heads",
               "linear_key_head_dim", "linear_value_head_dim",
               "linear_conv_kernel_dim", "moe_intermediate_size",
               "shared_expert_intermediate_size", "num_experts_per_tok",
               "norm_topk_prob", "rms_norm_eps", "initializer_range")


def program_config(m, max_seq_len, dtype):
    """The program's `Qwen3NextConfig` at the sizes `m`."""
    from paddle_tpu.models.qwen3_next import Qwen3NextConfig
    return Qwen3NextConfig(
        **{k: m[k] for k in CONFIG_KEYS}, num_experts=m["router_experts"],
        held=m["held_experts"], max_seq_len=max_seq_len, dtype=dtype)


def seeded_program_model(m, seed, init, max_seq_len, dtype="bfloat16"):
    """The program's model with every parameter drawn from the seed as
    the reference draws it: the same leaves under the same names,
    rounded to bfloat16. The program holds a layer's routed experts
    stacked, which is a rearrangement of the reference's leaves."""
    from paddle_tpu.models.qwen3_next import Qwen3NextForCausalLM
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

    return Qwen3NextForCausalLM(
        program_config(m, max_seq_len, dtype), make=make)


MOE_COUNTERS = serve_exaone.MOE_COUNTERS
ROW_COUNTERS = ("state_rows_taken", "state_rows_released", "state_replays")
STATE_REQUESTS = 4      # live requests whose rows `state_gap` reads


class Driver(serve_exaone.Driver):
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
                            # request rows live) so far, traced runs
        self.live = []          # (ids, {layer: state}) at the stop
        self.want = None        # the reference's states over those ids

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
        # first decode step changes the batch with a step in flight;
        # then the program that copies a live request's rows, which the
        # check runs at the stop. There is no prefix cache for this
        # model and so no fork
        rng = np.random.default_rng([self.seed, 13])
        chunk = int(ecfg["prefill_chunk"])
        for tail in (chunk + 8, 8):
            prompt = rng.integers(1, vocab, tail)
            self.engine.submit(prompt.astype(np.int32), SamplingParams(
                max_new_tokens=4)).result(timeout=1100)
        h = self.engine.submit(rng.integers(1, vocab, 8).astype(np.int32),
                               SamplingParams(max_new_tokens=48))
        next(h.tokens(timeout=1100))
        if self.engine.request_rows(h) is None:
            raise SystemExit("benchmark: the warm-up request finished "
                             "before its rows were read")
        h.result(timeout=1100)
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
        layers have counted so far and how many request rows are
        live."""
        serve.Driver._tap(self)
        from paddle_tpu import monitor
        step = self.engine.step

        def counted_step():
            out = step()
            self.seen.append(
                (time.perf_counter(),
                 monitor.get("serving.moe_experts_reached"),
                 monitor.get("serving.moe_pairs_held"),
                 monitor.get_gauge("serving.state_rows_live")))
            return out

        self.engine.step = counted_step

    # -- the measured window ------------------------------------------------
    def window(self, tracer):
        """The base driver's window; where it stops the engine, the rows
        of some requests still live are copied first."""
        stop = self.engine.stop

        def stop_with_rows():
            self.live = self._live_rows()
            return stop()

        self.engine.stop = stop_with_rows
        return super().window(tracer)

    def _live_rows(self):
        """(ids, {layer: state}) of `STATE_REQUESTS` requests that hold
        a row, drawn from the seed. The rounding of a delta-rule state
        does not add up with its tokens (the write pulls S^T k back
        toward v), so the longest request is not sought out. A request
        that waits for its turn holds no row and is passed over."""
        live = [r for r in self.recs if r.handle is not None
                and r.state == "new"]
        rng = np.random.default_rng([self.seed, 19])
        out = []
        while live and len(out) < STATE_REQUESTS:
            rec = live.pop(int(rng.integers(len(live))))
            got = self.engine.request_rows(rec.handle)
            if got is not None:
                ids, rows = got
                # a linear layer keeps (convolution tail, state)
                out.append((ids, {l: np.asarray(r[1])
                                  for l, r in rows.items()}))
        return out

    def _counters(self):
        out = serve.Driver._counters(self)
        snap = self.engine.metrics_snapshot()
        for name in MOE_COUNTERS + ROW_COUNTERS:
            out[name] = snap.get("serving." + name, 0.0)
        return out

    def _reduce(self, t0, t_close, t_end, after, tracer):
        out = serve.Driver._reduce(self, t0, t_close, t_end, after, tracer)
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
        self.log(f"request rows over the window: {rows}")
        return out

    def _traced_records(self, records, tracer):
        """Step times, occupancy, the share of request rows live and the
        work of the steps, from the taps of a traced run (the base
        driver's, with this model's counts)."""
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
            records["counters"]["state_rows_live_share"] = \
                100.0 * harness.median(live) / slots
        occ, flops, expected = [], 0.0, 0.0
        traced_ctx, traced_rows, traced_chunks = [], 0, []
        on = tracer.t_on if tracer and tracer.t_on else None
        off = tracer.t_off if tracer and tracer.t_off else None
        for family, t, detail in dispatches:
            in_trace = on is not None and on <= t <= off
            if family.startswith("serving_decode"):
                ctx = detail[detail > 0]
                occ.append(100.0 * len(ctx) / slots)
                flops += sum(work.decode_token_flops(c + 1, m) for c in ctx)
                tokens = len(ctx)
                if in_trace:
                    traced_ctx.extend(int(c) + 1 for c in ctx)
                    traced_rows += len(ctx)
            elif family == "serving_prefill":
                p0, tokens = detail
                flops += work.prefill_chunk_flops(p0, tokens, m,
                                                  last_chunk=False)
                if in_trace:
                    traced_chunks.append(tokens)
            else:
                continue
            if in_trace:
                expected += m["num_layers"] * work.experts_touched(tokens, m)
        records["counters"]["decode_occupancy"] = harness.median(occ) \
            if occ else None
        out = records["work"]
        out["serve_step"] = {"flops_per_s": flops / self.seconds}
        if traced_ctx:
            out["paged_decode"] = {
                "bytes": work.decode_attention_bytes(traced_ctx, m),
                "flops": work.decode_attention_flops(traced_ctx, m)}
            out["gdn_state_step"] = {
                "bytes": work.state_step_bytes(traced_rows, m),
                "flops": work.state_step_flops(traced_rows, m)}
        if traced_chunks:
            out["gdn_chunk"] = {
                "bytes": sum(work.chunk_bytes(n, m) for n in traced_chunks),
                "flops": sum(work.chunk_flops(n, m) for n in traced_chunks)}
        # the counts as they stood at the last step that ended before
        # the trace went on, and before it went off
        before = [x for x in self.seen if on is not None and x[0] <= on]
        within = [x for x in self.seen if on is not None and x[0] <= off]
        if before and within and within[-1][1] > before[-1][1]:
            reached = within[-1][1] - before[-1][1]
            pairs = within[-1][2] - before[-1][2]
            f, b = work.expert_work(pairs, reached, m)
            out["moe_grouped_ffn"] = {"bytes": b, "flops": f}
            self.log(f"traced: {reached:.0f} experts reached (had the "
                     f"dispatches' tokens been routed uniformly: "
                     f"{expected:.0f}) and {pairs:.0f} pairs held in "
                     f"{len(within) - len(before)} steps")

    # -- correct ------------------------------------------------------------
    def gaps(self, controls=None):
        """As the K-EXAONE driver's, through this configuration's
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
        # a token each: what tools/calibrate_longgen.py keeps, so that
        # another margin or share can be read off without another run
        out["margin"] = np.concatenate([row[3] for row in rows])
        out["below"] = below
        return out

    def state_gaps(self, control=None):
        """The rows copied at the stop against the states the reference
        reaches over the same tokens: `worst`, the largest distance of a
        layer's states from the reference's as a share of the
        reference's norm, over the sampled requests and the linear
        layers; `worst_head` the same by value head; `first` the worst
        value head of the first linear layer; `by_layer` [request,
        layer] and `by_head` [request, layer, head] behind them. `control` is a prec: the states the reference reaches in
        that lower precision take the program's place."""
        args = (self.m, self.seed, self.init, [ids for ids, _ in self.live])
        length = int(self.spec.cell["engine"]["max_model_len"])
        if self.want is None:
            self.want = ref.final_states(*args, length=length)
        low = ref.final_states(*args, prec=control, length=length) \
            if control else None
        layers = sorted(self.want)
        heads = self.m["linear_num_value_heads"]
        dist = np.zeros((len(self.live), len(layers), heads))
        norm = np.zeros_like(dist)
        for i, (_, rows) in enumerate(self.live):
            for j, layer in enumerate(layers):
                # the program holds [heads, K, V], as the reference does
                got = low[layer][i] if low else np.asarray(rows[layer],
                                                           np.float32)
                r = self.want[layer][i]
                dist[i, j] = np.sum(np.square(got - r), axis=(1, 2))
                norm[i, j] = np.sum(np.square(r), axis=(1, 2))
        by_layer = np.sqrt(dist.sum(-1) / np.maximum(norm.sum(-1), 1e-30))
        by_head = np.sqrt(dist / np.maximum(norm, 1e-30))
        out = {"worst": float(by_layer.max()),
               "worst_head": float(by_head.max()),
               "first": float(by_head[:, 0].max()),
               "tokens": [len(ids) for ids, _ in self.live],
               "by_layer": by_layer, "by_head": by_head}
        self.log(f"request rows against the reference's states: worst "
                 f"layer {out['worst']:.6g}, worst head "
                 f"{out['worst_head']:.6g}, first layer's worst head "
                 f"{out['first']:.6g} over {len(self.live)} live "
                 f"requests of {out['tokens']} tokens")
        return out

    def check(self):
        if not self.sample():
            return [{"name": "finished_requests", "value": 1.0,
                     "limit": 0.0}]
        if not self.live:
            return [{"name": "live_requests", "value": 1.0, "limit": 0.0}]
        g = self.gaps()
        self.log(f"compared {g['compared']} of {g['tokens']} served tokens "
                 f"of {len(self.sample())} requests of {g['lengths']} "
                 f"tokens with the reference; {g['flipped']} are not the "
                 "reference's first")
        return self.compared(g["served"], g["left_out"], self.state_gaps())

    def compared(self, gap, left_out, states):
        """The rows `correct` is decided on, `states` what `state_gaps`
        gives; the controls and faults of tools/calibrate_longgen.py go
        through them too."""
        limits = self.spec.cell["limits"]
        name = "served_logit_gap_p%d" % round(
            100 * self.spec.cell["check"]["within_share"])
        return [{"name": name, "value": gap, "limit": limits[name]},
                {"name": "route_left_out", "value": left_out,
                 "limit": limits["route_left_out"]},
                {"name": "state_gap", "value": states["worst"],
                 "limit": limits["state_gap"]},
                {"name": "state_gap_first", "value": states["first"],
                 "limit": limits["state_gap_first"]}]
