"""Serving driver: `ServingEngine.start()` with requests entering by
`submit()` from one client thread on a fixed schedule (open loop), or
all at once when the window opens (a backlog).

Everything a request needs (token array, `SamplingParams`) is built in
set-up; at a due time the submitter only calls `submit` and hands the
stream to a consumer thread, which stamps every token on the harness's
own clock as it is delivered. Latencies are timed from the moment a
request was DUE, so a stalled system or a late generator shows.
"""
import gc
import threading
import time

import numpy as np

from benchmark import correct, harness, latency, schedule, weights, work


class Rec:
    """One request of the schedule, and what the clients saw of it."""
    __slots__ = ("idx", "due_s", "prompt", "want", "submitted", "times",
                 "tokens", "state", "error", "handle")

    def __init__(self, idx, due_s, prompt, want):
        self.idx, self.due_s, self.prompt, self.want = idx, due_s, prompt, want
        self.submitted = None
        self.times, self.tokens = [], []
        self.state, self.error, self.handle = "new", None, None


class Driver:
    def __init__(self, spec, seed, seconds, devices, log=print, trace=False):
        self.spec, self.seed, self.seconds = spec, int(seed), float(seconds)
        self.devices, self.log, self.trace = devices, log, bool(trace)
        self.dims = weights.sizes(spec.config)
        self.block_scale = weights.block_scale_of(spec.cell)
        self.closing = False        # the harness is stopping the engine
        self.drains = spec.traffic.get("at_close", "drain") == "drain"
        self.window_over = False    # nothing is submitted past the close
        self.steps = []         # (start, end) of engine steps, traced runs
        self.dispatches = []    # (family, start, detail), traced runs
        self.tap_fault = None   # a dispatch the taps could not read
        self.recs = []

    # -- set-up -------------------------------------------------------------
    def setup(self):
        t0 = time.perf_counter()
        from paddle_tpu.serving import (EngineConfig, SamplingParams,
                                        ServingEngine)
        t_import = time.perf_counter()
        L, d, heads, ffn, vocab, npos = self.dims
        ecfg = dict(self.spec.cell["engine"])
        want = self.spec.config["precision"]["serve_weights"]
        if ecfg.get("weights") != want:
            raise SystemExit(f"cell serves weights={ecfg.get('weights')!r}, "
                             f"the configuration states {want!r}")
        model = weights.seeded_program_model(self.spec.config, self.seed,
                                             self.block_scale)
        t_weights = time.perf_counter()
        self.engine = ServingEngine(model, config=EngineConfig(**ecfg))
        self.model = model
        t_engine = time.perf_counter()

        sched = schedule.build_schedule(self.spec.traffic, self.seconds)
        prompts = schedule.materialize(sched, self.spec.traffic, self.seed,
                                       vocab)
        self.recs = [Rec(i, r["due_s"], prompts[i], r["output_len"])
                     for i, r in enumerate(sched)]
        self.params = [SamplingParams(max_new_tokens=r.want)
                       for r in self.recs]
        if self.trace:
            self._tap()
        self.engine.start()
        # warm the cell's shapes: a prompt of two chunks (prefill and
        # decode), then one that shares a block and a half with it, so
        # the copy-on-write fork is compiled too
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

    def _counters(self):
        snap = self.engine.metrics_snapshot()
        ps = self.engine.prefix_stats()
        return {"decode_steps": snap.get("serving.decode_steps", 0),
                "prefill_chunks": snap.get("serving.prefill_chunks", 0),
                "preemptions": snap.get("serving.preemptions", 0),
                "cow_forks": snap.get("serving.prefix_cow_forks", 0),
                "tokens_saved": ps["tokens_saved"],
                "tokens_offered": ps["tokens_offered"],
                "blocks_live": self.engine.pool.num_used,
                "blocks_cached": ps["blocks_cached"],
                "blocks": self.engine.pool.capacity}

    def _tap(self):
        """Traced runs only: spans around the harness's calls into the
        engine's step and its device dispatches, and the shapes each
        dispatch worked on. The dispatch arguments are the engine's
        private layout: one that is not as `_detail` expects it stops
        the run with no result, so a reordered argument cannot move a
        per-layer metric unseen."""
        eng = self.engine
        step, dispatch = eng.step, eng._dispatch

        def timed_step():
            t = time.perf_counter()
            with harness.annotate("engine_step"):
                out = step()
            self.steps.append((t, time.perf_counter()))
            return out

        def timed_dispatch(family, jitted, args):
            t = time.perf_counter()
            try:
                detail = self._detail(family, args)
            except Exception as e:     # noqa: BLE001 - kept for the window
                self.tap_fault = self.tap_fault or f"{family}: {e!r}"
                detail = None
            self.dispatches.append((family, t, detail))
            with harness.annotate(family):
                return dispatch(family, jitted, args)

        eng.step, eng._dispatch = timed_step, timed_dispatch

    def _detail(self, family, args):
        """What a dispatch worked on: the context of every slot of a
        decode step, (first position, real tokens) of a prefill chunk."""
        ecfg = self.spec.cell["engine"]
        slots, chunk = int(ecfg["max_slots"]), int(ecfg["prefill_chunk"])
        length = int(ecfg["max_model_len"])

        def int32(x, shape):
            x = np.asarray(x)
            if x.dtype != np.int32 or x.shape != shape:
                raise TypeError(f"int32{list(shape)} expected, got "
                                f"{x.dtype}{list(x.shape)}")
            return x

        if family in ("serving_decode", "serving_decode_sampling"):
            if len(args) != 12:
                raise TypeError(f"12 arguments expected, got {len(args)}")
            int32(args[3], (slots,))                    # tokens
            ctx = int32(args[4], (slots,)).copy()       # context per slot
            tables = np.asarray(args[5])
            if tables.ndim != 2 or tables.shape[0] != slots:
                raise TypeError(f"block tables [{slots}, n] expected at 5")
            if ctx.min() < 0 or ctx.max() >= length:
                raise ValueError(f"contexts outside 0..{length}")
            return ctx
        if family == "serving_prefill":
            if len(args) != 13:
                raise TypeError(f"13 arguments expected, got {len(args)}")
            int32(args[3], (1, chunk))                  # the chunk's ids
            p0, n_real = int(int32(args[4], ())), int(int32(args[5], ()))
            if not (0 <= p0 and 1 <= n_real <= chunk
                    and p0 + n_real <= length):
                raise ValueError(f"chunk at {p0} of {n_real} tokens")
            return p0, n_real
        if family == "serving_fork":
            return None
        raise ValueError("a dispatch family the taps do not know")

    # -- the measured window ------------------------------------------------
    def _consume(self, rec):
        try:
            for tok in rec.handle.tokens():
                rec.times.append(time.monotonic())
                rec.tokens.append(tok)
            rec.state = "finished"
        except Exception as e:      # noqa: BLE001 - a client sees any error
            if self.closing:
                rec.state = "cut"       # stopped by the harness at close
            else:
                rec.state, rec.error = "failed", repr(e)

    def _submit_all(self, t0, consumers):
        for rec, params in zip(self.recs, self.params):
            wait = t0 + rec.due_s - time.monotonic()
            if wait > 0:
                time.sleep(wait)
            if self.window_over and not self.drains:
                return      # a backlog is cut at the close; a schedule
                            # is submitted to its last request
            rec.submitted = time.monotonic()
            try:
                rec.handle = self.engine.submit(rec.prompt, params)
            except Exception as e:  # noqa: BLE001 - shed or refused: failed
                rec.state, rec.error = "failed", repr(e)
                continue
            th = threading.Thread(target=self._consume, args=(rec,),
                                  daemon=True)
            th.start()
            consumers.append(th)

    def window(self, tracer):
        traffic = self.spec.traffic
        consumers = []
        self.t0_perf = time.perf_counter()
        t0 = time.monotonic()
        submitter = threading.Thread(target=self._submit_all,
                                     args=(t0, consumers), daemon=True)
        submitter.start()
        while True:
            elapsed = time.monotonic() - t0
            if elapsed >= self.seconds:
                break
            if tracer is not None:
                tracer.poll(elapsed)
            time.sleep(min(0.05, self.seconds - elapsed))
        t_close = t0 + self.seconds
        self.window_over = True
        if tracer is not None:
            tracer.poll(self.seconds, force_stop=True)
        submitter.join(timeout=30)
        after = self._counters()
        limit = time.monotonic() + float(traffic["drain_s"]
                                         if self.drains
                                         else traffic["check_wait_s"])
        if self.drains:
            # everything due gets its answer: wait for each
            for th in list(consumers):
                th.join(timeout=max(0.0, limit - time.monotonic()))
        else:
            # a backlog is cut at the close; only wait until enough
            # requests have finished for `correct` to look at
            need = int(self.spec.cell["check_requests"])
            while time.monotonic() < limit and sum(
                    r.state == "finished" for r in self.recs) < need:
                time.sleep(0.05)
        self.closing = True
        t_end = time.monotonic()
        self.engine.stop()
        for th in list(consumers):
            th.join(timeout=30)
        return self._reduce(t0, t_close, t_end, after, tracer)

    def _reduce(self, t0, t_close, t_end, after, tracer):
        recs = [r for r in self.recs if r.submitted is not None
                or r.state == "failed"]
        failed = [r for r in recs if r.state == "failed"]
        rows = [{"due": t0 + r.due_s, "submitted": r.submitted,
                 "times": r.times, "finished": r.state == "finished",
                 "failed": r.state == "failed",
                 "cut": r.state == "cut" and not self.drains}
                for r in recs]
        delivered = latency.delivered_in_window(rows, t_close)
        ttft, tpot, late = latency.request_latencies(rows, t_end)
        tail = latency.tails(ttft, tpot)
        finished = [r for r in recs if r.state == "finished"]
        beyond = tail["beyond_p95"]
        self.log(f"requests: {len(recs)} submitted, {len(finished)} "
                 f"finished, {len(failed)} failed, "
                 f"{sum(r.state == 'cut' for r in recs)} cut at the close; "
                 f"{beyond} lie beyond the 95th percentile")
        self.log(f"tokens delivered in the window: {delivered}; generator "
                 f"lateness p50/p99/max ms: "
                 f"{harness.percentile(late, 50):.3f}/"
                 f"{harness.percentile(late, 99):.3f}/{max(late):.3f}")
        if failed:
            self.log(f"first failure: {failed[0].error}")
        e2e = {"serve_tokens_per_s": delivered / self.seconds,
               "ttft_p95_ms": tail["ttft_p95_ms"],
               "tpot_p95_ms": tail["tpot_p95_ms"]}
        base = self.base
        offered = after["tokens_offered"] - base["tokens_offered"]
        saved = after["tokens_saved"] - base["tokens_saved"]
        counters = {"decode_steps": after["decode_steps"]
                    - base["decode_steps"],
                    "prefill_chunks": after["prefill_chunks"]
                    - base["prefill_chunks"],
                    "preemptions": after["preemptions"] - base["preemptions"],
                    "cow_forks": after["cow_forks"] - base["cow_forks"]}
        if offered:
            counters["prefix_saved_share"] = 100.0 * saved / offered
        self.log(f"engine counters over the window: {counters}")
        self.log(f"K/V arena at the close: {after['blocks_live']} blocks "
                 f"live + {after['blocks_cached']} kept by the prefix cache "
                 f"of {after['blocks']}")
        clock = {"gen_late_p99_ms": harness.percentile(late, 99),
                 "ttft_p95_ms": tail["ttft_p95_ms"],
                 "tpot_p95_ms": tail["tpot_p95_ms"],
                 "ttft_p50_ms": tail["ttft_p50_ms"],
                 "tpot_p50_ms": tail["tpot_p50_ms"]}
        records = {"clock": clock, "counters": counters, "work": {},
                   "host_spans": ("engine_step", "serving_prefill",
                                  "serving_decode", "serving_fork")}
        self.taps_sound()
        if self.steps:
            self._traced_records(records, tracer)
        return {"end_to_end": e2e, "attempted": len(recs),
                "failed": len(failed), "seconds": self.seconds,
                "records": records}

    def taps_sound(self):
        if self.tap_fault:
            raise SystemExit("benchmark: the engine's dispatch is not as "
                             "the serving taps read it, no per-layer "
                             f"metric can be trusted: {self.tap_fault}")

    def _traced_records(self, records, tracer):
        """Step times, occupancy and the work of the steps, from the
        taps of a traced run."""
        L, d, heads, ffn, vocab, npos = self.dims
        slots = int(self.spec.cell["engine"]["max_slots"])
        t_a, t_b = self.t0_perf, self.t0_perf + self.seconds
        steps = [(a, b) for a, b in self.steps if t_a <= a and b <= t_b]
        dispatches = [x for x in self.dispatches if t_a <= x[1] <= t_b]
        if not steps or len(dispatches) < 2:
            return
        records["clock"]["engine_step_ms"] = harness.median(
            [(b - a) * 1e3 for a, b in steps])
        occ, flops = [], 0.0
        in_trace_ctx = []
        on = tracer.t_on if tracer and tracer.t_on else None
        off = tracer.t_off if tracer and tracer.t_off else None
        for family, t, detail in dispatches:
            if family.startswith("serving_decode"):
                ctx = detail[detail > 0]
                occ.append(100.0 * len(ctx) / slots)
                flops += sum(work.decode_token_flops(c + 1, L, d, ffn, vocab)
                             for c in ctx)
                if on is not None and on <= t <= off:
                    in_trace_ctx.extend(int(c) + 1 for c in ctx)
            elif family == "serving_prefill":
                p0, n_real = detail
                flops += work.prefill_chunk_flops(p0, n_real, L, d, ffn,
                                                  vocab, last_chunk=False)
        records["counters"]["decode_occupancy"] = harness.median(occ) \
            if occ else None
        records["work"]["serve_step"] = {"flops_per_s": flops / self.seconds}
        if in_trace_ctx:
            records["work"]["paged_decode"] = {
                "bytes": work.decode_attention_bytes(in_trace_ctx, L, d),
                "flops": work.decode_attention_flops(in_trace_ctx, L, d)}

    def release(self):
        for r in self.recs:         # a handle holds the engine
            r.handle = None
        self.engine = self.model = self.params = None
        gc.collect()

    # -- correct ------------------------------------------------------------
    def sample(self):
        """The finished requests `correct` looks at: the longest, and the
        rest drawn from the seed."""
        done = [r for r in self.recs if r.state == "finished"
                and len(r.tokens) == r.want]
        if not done:
            return []
        k = int(self.spec.cell["check_requests"])
        longest = max(done, key=lambda r: len(r.prompt) + r.want)
        rest = [r for r in done if r is not longest]
        rng = np.random.default_rng([self.seed, 17])
        picks = rng.permutation(len(rest))[:max(0, k - 1)]
        return [longest] + [rest[i] for i in picks]

    def gaps(self, prec_low=None):
        """Over the sample: `served`, the widest gap of a served token
        under the reference's best; `flipped`, how many served tokens
        are not the reference's first; `tokens`; and with `prec_low`
        the same two of the token that the lower precision puts first at
        the same positions (`control`, `control_flipped`)."""
        ref = harness.reference_of(self.spec.config)
        import jax.numpy as jnp
        L, d, heads, ffn, vocab, npos = self.dims
        stated = dict(ref.REFERENCE,
                      wbits=8 if self.spec.config["precision"]
                      ["serve_weights"] == "wo8" else None)
        raw = weights.make_weights(self.spec.config, self.seed, stacked=True,
                                   block_scale=self.block_scale)
        params = ref.prepare(raw, stated)
        low = ref.prepare(raw, prec_low) if prec_low else None
        del raw
        length = int(self.spec.cell["engine"]["max_model_len"])
        out = {"served": 0.0, "control": 0.0, "tokens": 0, "flipped": 0,
               "control_flipped": 0}
        for r in self.sample():
            ids = np.zeros((1, length), np.int32)
            seq = np.concatenate([r.prompt, np.asarray(r.tokens, np.int32)])
            ids[0, :len(seq)] = seq
            nxt = np.roll(ids[0], -1)
            probe = nxt
            if low is not None:
                _, probe, _, _ = ref.position_logits(
                    low, jnp.asarray(ids), jnp.asarray(nxt),
                    jnp.asarray(nxt), heads, prec_low["act"],
                    kv=prec_low.get("kv"))
            best, _, picked, probed = (np.asarray(x) for x in
                                       ref.position_logits(
                params, jnp.asarray(ids), jnp.asarray(nxt),
                jnp.asarray(probe), heads, "f32"))
            first, count = len(r.prompt) - 1, len(r.tokens)
            at = slice(first, first + count)
            out["served"] = max(out["served"], correct.token_gap(
                best, picked, first, count))
            out["control"] = max(out["control"], correct.token_gap(
                best, probed, first, count))
            out["flipped"] += int(np.sum(best[at] > picked[at]))
            out["control_flipped"] += int(np.sum(best[at] > probed[at]))
            out["tokens"] += count
        return out

    def check(self):
        if not self.sample():
            return [{"name": "finished_requests", "value": 1.0,
                     "limit": 0.0}]
        g = self.gaps()
        self.log(f"compared {g['tokens']} served tokens of "
                 f"{len(self.sample())} requests with the reference; "
                 f"{g['flipped']} are not the reference's first")
        return self.compared(g["served"])

    def compared(self, gap):
        """The row `correct` is decided on; the controls of
        tools/calibrate.py go through it too."""
        return [{"name": "served_logit_gap", "value": gap,
                 "limit": self.spec.cell["limits"]["served_logit_gap"]}]
