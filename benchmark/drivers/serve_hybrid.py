"""Serving driver of the Granite-4.0-H configuration: `drivers.serve`'s
window, latency, schedule and `served_logit_gap` arithmetic, with what
that driver takes from GPT brought here: the seeded weights (drawn on
the device leaf by leaf in bfloat16, the reference's own leaves under
the names the program holds them by), the model builder, the work counts
of `work_hybrid.py`, the request rows' counters, and the check through
`reference/granite_hybrid.py`: the GPT cells' `served_logit_gap`, and
`state_gap`, which holds the recurrent state itself (the rows of
requests still live when the engine is stopped, against the state the
reference's recurrence reaches over the same tokens), because rounding
the state alone moves no served token far enough for the first to see.

A model that keeps rows by request hands its dispatches one argument
more than GPT's (the slots' rows after a decode step's twelve, the
request's row after a chunk's thirteen); the taps' `_detail` reads that
layout and refuses any other.
"""
import time

import jax.numpy as jnp
import numpy as np

from benchmark import harness, schedule, work_hybrid
from benchmark.drivers import serve
from benchmark.reference import granite_hybrid as ref

CONFIG_KEYS = ("vocab_size", "hidden_size", "layer_types",
               "num_attention_heads", "num_key_value_heads",
               "shared_intermediate_size", "mamba_n_heads", "mamba_d_head",
               "mamba_d_state", "mamba_d_conv", "mamba_n_groups",
               "mamba_expand", "mamba_chunk_size", "attention_multiplier",
               "embedding_multiplier", "residual_multiplier",
               "logits_scaling", "rms_norm_eps", "initializer_range")


def program_config(m, max_seq_len, dtype):
    """The program's `GraniteHybridConfig` at the sizes `m`."""
    from paddle_tpu.models.granite_hybrid import GraniteHybridConfig
    return GraniteHybridConfig(**{k: m[k] for k in CONFIG_KEYS},
                               max_seq_len=max_seq_len, dtype=dtype)


def seeded_program_model(m, seed, init, max_seq_len, dtype="bfloat16"):
    """The program's model with every parameter drawn from the seed as
    the reference draws it: the same leaves under the same names,
    rounded to bfloat16."""
    from paddle_tpu.models.granite_hybrid import GraniteHybridForCausalLM
    dt = jnp.dtype(dtype)
    outer = {"embed": ref.EMBED, "norm": ref.FINAL_NORM}
    tables = {}

    def make(name, shape, kind):
        if name in outer:
            out = ref.outer_weights(m, seed, outer[name], dt)
        else:
            _, layer, leaf = name.split(".", 2)
            layer = int(layer)
            if layer not in tables:
                tables[layer] = {n: (j, k) for j, (n, _, k) in
                                 enumerate(ref.layer_leaves(m, layer))}
            j, leaf_kind = tables[layer][leaf]
            if leaf_kind != kind:
                raise SystemExit(f"weight kind mismatch at {name}: "
                                 f"{kind!r} for {leaf_kind!r}")
            out = ref.layer_leaf(m, seed, layer, j, init, dt)
        if tuple(out.shape) != tuple(shape):
            raise SystemExit(f"weight shape mismatch at {name}: "
                             f"{out.shape} for {shape}")
        return out

    return GraniteHybridForCausalLM(
        program_config(m, max_seq_len, dtype), make=make)


ROW_COUNTERS = ("state_rows_taken", "state_rows_released", "state_replays")
STATE_REQUESTS = 4      # live requests whose rows `state_gap` reads


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
        self.rows_live = []     # the gauge after each step, traced runs
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
        # first decode step changes the batch with a step in flight.
        # There is no prefix cache for this model and so no fork
        rng = np.random.default_rng([self.seed, 13])
        chunk = int(ecfg["prefill_chunk"])
        for tail in (chunk + 8, 8):
            prompt = rng.integers(1, vocab, tail)
            self.engine.submit(prompt.astype(np.int32), SamplingParams(
                max_new_tokens=4)).result(timeout=1100)
        # and the program that copies a live request's rows, which the
        # check runs at the stop: inside what the harness counts as the
        # window's compiles
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
        """The base driver's taps, and after every step how many request
        rows are live."""
        super()._tap()
        from paddle_tpu import monitor
        step = self.engine.step

        def counted_step():
            out = step()
            self.rows_live.append((time.perf_counter(), monitor.get_gauge(
                "serving.state_rows_live")))
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
        a row: the one that has come farthest (a state's rounding adds
        up with its tokens), and the rest drawn from the seed. A request
        that waits for its turn holds no row and is passed over."""
        live = [r for r in self.recs if r.handle is not None
                and r.state == "new"]
        live.sort(key=lambda r: -(len(r.prompt) + len(r.tokens)))
        rng = np.random.default_rng([self.seed, 19])
        out = []
        while live and len(out) < STATE_REQUESTS:
            rec = live.pop(int(rng.integers(len(live))) if out else 0)
            got = self.engine.request_rows(rec.handle)
            if got is not None:
                ids, rows = got
                # a Mamba-2 layer keeps (convolution tail, state)
                out.append((ids, {l: np.asarray(r[1])
                                  for l, r in rows.items()}))
        return out

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
        for name in ROW_COUNTERS:
            out[name] = snap.get("serving." + name, 0.0)
        return out

    def _reduce(self, t0, t_close, t_end, after, tracer):
        out = super()._reduce(t0, t_close, t_end, after, tracer)
        rows = {n: after[n] - self.base[n] for n in ROW_COUNTERS}
        self.log(f"request rows over the window: {rows}")
        out["records"]["counters"].update(rows)
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
        live = [n for t, n in self.rows_live if t_a <= t <= t_b]
        if live:
            records["counters"]["state_rows_live_share"] = \
                100.0 * harness.median(live) / slots
        occ, flops = [], 0.0
        traced_ctx, traced_rows, traced_chunks = [], 0, []
        on = tracer.t_on if tracer and tracer.t_on else None
        off = tracer.t_off if tracer and tracer.t_off else None
        for family, t, detail in dispatches:
            in_trace = on is not None and on <= t <= off
            if family.startswith("serving_decode"):
                ctx = detail[detail > 0]
                occ.append(100.0 * len(ctx) / slots)
                flops += sum(work_hybrid.decode_token_flops(c + 1, m)
                             for c in ctx)
                if in_trace:
                    traced_ctx.extend(int(c) + 1 for c in ctx)
                    traced_rows += len(ctx)
            elif family == "serving_prefill":
                p0, n_real = detail
                flops += work_hybrid.prefill_chunk_flops(
                    p0, n_real, m, last_chunk=False)
                if in_trace:
                    traced_chunks.append(n_real)
        records["counters"]["decode_occupancy"] = harness.median(occ) \
            if occ else None
        records["work"]["serve_step"] = {"flops_per_s": flops / self.seconds}
        if traced_ctx:
            records["work"]["paged_decode"] = {
                "bytes": work_hybrid.decode_attention_bytes(traced_ctx, m),
                "flops": work_hybrid.decode_attention_flops(traced_ctx, m)}
            records["work"]["mamba2_state_step"] = {
                "bytes": work_hybrid.state_step_bytes(traced_rows, m),
                "flops": work_hybrid.state_step_flops(traced_rows, m)}
        if traced_chunks:
            records["work"]["mamba2_chunk_scan"] = {
                "bytes": sum(work_hybrid.chunk_scan_bytes(n, m)
                             for n in traced_chunks),
                "flops": sum(work_hybrid.chunk_scan_flops(n, m)
                             for n in traced_chunks)}

    # -- correct ------------------------------------------------------------
    def gaps(self, controls=None):
        """As the base driver's, through this configuration's reference:
        the whole forward pass of each sampled request's prompt and
        answer, and `served`, the widest gap by which a served token's
        logit lies under the reference's best. `controls` is
        {name: prec}: the tokens the reference puts first in each lower
        precision, held to the same rule at the same positions."""
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
            for rows, (_, first, _) in zip(probes, low):
                rows.append(first)
        rows = ref.position_logits(*args, probes, length=length, log=self.log)
        below = np.concatenate([best[None] - probed
                                for best, _, probed in rows], axis=1)
        out = {"served": float(below[0].max()), "tokens": int(below.shape[1]),
               "flipped": int(np.sum(below[0] > 0)),
               "controls": {n: {"gap": float(row.max()),
                                "flipped": int(np.sum(row > 0))}
                            for n, row in zip(controls, below[1:])}}
        self.log(f"served tokens under the reference's best: {out}")
        return out

    def state_gaps(self, control=None):
        """The rows copied at the stop against the states the reference
        reaches over the same tokens: `worst`, the largest distance of a
        layer's state from the reference's as a share of the
        reference's norm, over the sampled requests and the Mamba-2
        layers; `worst_head` the same by head; `by_layer`
        [request, layer] and `by_head` [request, layer, head] behind
        them (tools/calibrate_many.py writes them out). `control` is a
        prec: the states the reference reaches in that lower precision
        take the program's place."""
        m = self.m
        nh, P, N = m["mamba_n_heads"], m["mamba_d_head"], m["mamba_d_state"]
        args = (m, self.seed, self.init, [ids for ids, _ in self.live])
        length = int(self.spec.cell["engine"]["max_model_len"])
        if self.want is None:
            self.want = ref.final_states(*args, length=length)
        low = ref.final_states(*args, prec=control, length=length) \
            if control else None
        layers = sorted(self.want)
        dist = np.zeros((len(self.live), len(layers), nh))
        norm = np.zeros_like(dist)
        for i, (_, rows) in enumerate(self.live):
            for j, layer in enumerate(layers):
                # the program holds [d_state, heads * head_dim]
                got = low[layer][i] if low else np.asarray(
                    rows[layer], np.float32).reshape(N, nh, P) \
                    .transpose(1, 2, 0)
                r = self.want[layer][i]
                dist[i, j] = np.sum(np.square(got - r), axis=(1, 2))
                norm[i, j] = np.sum(np.square(r), axis=(1, 2))
        by_layer = np.sqrt(dist.sum(-1) / np.maximum(norm.sum(-1), 1e-30))
        by_head = np.sqrt(dist / np.maximum(norm, 1e-30))
        out = {"worst": float(by_layer.max()),
               "worst_head": float(by_head.max()),
               "tokens": [len(ids) for ids, _ in self.live],
               "by_layer": by_layer, "by_head": by_head}
        self.log(f"request rows against the reference's states: worst "
                 f"layer {out['worst']:.6g}, worst head "
                 f"{out['worst_head']:.6g} over {len(self.live)} live "
                 f"requests of {out['tokens']} tokens")
        return out

    def check(self):
        if not self.sample():
            return [{"name": "finished_requests", "value": 1.0,
                     "limit": 0.0}]
        if not self.live:
            return [{"name": "live_requests", "value": 1.0, "limit": 0.0}]
        g = self.gaps()
        self.log(f"compared {g['tokens']} served tokens of "
                 f"{len(self.sample())} requests with the reference; "
                 f"{g['flipped']} are not the reference's first")
        return self.compared(g["served"], self.state_gaps()["worst"])

    def compared(self, gap, state_gap):
        """The rows `correct` is decided on; the controls and faults of
        tools/calibrate_many.py go through it too."""
        limits = self.spec.cell["limits"]
        return [{"name": "served_logit_gap", "value": gap,
                 "limit": limits["served_logit_gap"]},
                {"name": "state_gap", "value": state_gap,
                 "limit": limits["state_gap"]}]
