"""Training driver: one compiled step object, built once, driven from
the seed through its first three steps in set-up (they are the warm-up
and what `correct` compares), then handed to the measured window.

The cell's file chooses the entry (`jit.TrainStep` on one chip), the
batch and the sequence length; the traffic file the pool of host
batches. Every step gets a fresh host batch, so the host-to-device copy
is inside the window.
"""
import gc
import time


from benchmark import correct, harness, schedule, weights, work


class Driver:
    def __init__(self, spec, seed, seconds, devices, log=print, trace=False):
        self.spec, self.seed, self.seconds = spec, int(seed), float(seconds)
        self.devices, self.log = devices, log
        self.dims = weights.sizes(spec.config)
        self.batch = int(spec.cell["batch"])
        self.seq = int(spec.cell["seq_len"])
        self.readings = None

    # -- set-up -------------------------------------------------------------
    def setup(self):
        import jax
        import jax.numpy as jnp
        t0 = time.perf_counter()
        import paddle_tpu as paddle
        from paddle_tpu import amp, optimizer
        ref = harness.reference_of(self.spec.config)
        t_import = time.perf_counter()
        L, d, heads, ffn, vocab, npos = self.dims
        cell = self.spec.cell
        if cell.get("entry", "jit.TrainStep") != "jit.TrainStep":
            raise SystemExit(f"train driver: unknown entry {cell['entry']!r}")
        model = weights.seeded_program_model(self.spec.config, self.seed)
        h = ref.ADAMW
        opt = optimizer.AdamW(
            learning_rate=h["lr"], beta1=h["beta1"], beta2=h["beta2"],
            epsilon=h["eps"], weight_decay=h["weight_decay"],
            parameters=model.parameters())
        autocast = self.spec.config["precision"]["compute"] == "bfloat16"

        def loss_fn(ids, labels):
            with amp.auto_cast(enable=autocast, dtype="bfloat16"):
                return model.loss(ids, labels)

        self.step = paddle.jit.TrainStep(model, loss_fn, opt)
        self.model, self.opt = model, opt
        self.pool = schedule.token_batches(
            self.seed, vocab, self.batch, self.seq,
            int(self.spec.traffic["batch_pool"]))
        t_weights = time.perf_counter()

        # the first three steps, through the window's own call and feed
        names = [n for n, _ in model.named_parameters()]
        params = [p for _, p in model.named_parameters()]

        def norms(arrays):
            """{leaf: norm} of program-named arrays, q/k/v apart."""
            named = ref.split_leaves(dict(zip(names, arrays)))
            return {k: float(v) for k, v in jax.jit(lambda t: {
                k: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                for k, x in t.items()})(named).items()}

        losses = [float(self.step(*self.pool[0]).item())]
        # the first gradient as the optimizer got it: moment1 after one
        # step is (1 - beta1) x gradient
        grad_norm = {k: v / (1.0 - h["beta1"]) for k, v in norms(
            [opt._states[id(p)]["moment1"] for p in params]).items()}
        for i in (1, 2):
            losses.append(float(self.step(*self.pool[i]).item()))
        start = weights.make_weights(self.spec.config, self.seed)
        change = norms(jax.jit(lambda a, b: [x - y for x, y in zip(a, b)])(
            [p._value for p in params], [start[n] for n in names]))
        self.readings = {"losses": losses, "grad_norm": grad_norm,
                         "change_norm": change}
        del start, change
        self.n_params = weights.count_params(self.spec.config)
        t_warm = time.perf_counter()
        self.log(f"first losses {losses}")
        return {"import_s": t_import - t0, "weights_s": t_weights - t_import,
                "warm_steps_s": t_warm - t_weights}

    # -- the measured window ------------------------------------------------
    def window(self, tracer):
        step, pool = self.step, self.pool
        done, pending, n = [], None, 0
        t0 = time.perf_counter()
        while True:
            if tracer is not None:
                tracer.poll(time.perf_counter() - t0)
            with harness.annotate("train_step_dispatch"):
                loss = step(*pool[(3 + n) % len(pool)])
            n += 1
            if pending is not None:
                pending._value.block_until_ready()
                done.append(time.perf_counter())
                if done[-1] - t0 >= self.seconds:
                    break
            pending = loss
        loss._value.block_until_ready()
        done.append(time.perf_counter())
        if tracer is not None:
            tracer.poll(done[-1] - t0, force_stop=True)
        elapsed = done[-1] - t0
        tokens = n * self.batch * self.seq
        stall = tracer.stall_s if tracer is not None else 0.0
        rate = tokens / (elapsed - stall)
        # a step's time, read over pairs of steps (each reading spans
        # two steps, so the host clock's half millisecond is small)
        pairs = [(done[i + 2] - done[i]) / 2.0 * 1e3
                 for i in range(0, len(done) - 2)]
        if tracer is not None and tracer.t_on is not None:
            pairs = [p for i, p in enumerate(pairs)
                     if not (done[i] <= tracer.t_off + tracer.stall_s
                             and done[i + 2] >= tracer.t_on - tracer.stall_s)]
        L, d, heads, ffn, vocab, npos = self.dims
        self.log(f"{n} steps of {self.batch} x {self.seq} in "
                 f"{elapsed:.3f} s (profiler stall {stall:.3f} s); last "
                 f"loss {float(loss.item()):.4f}")
        step_s = (elapsed - stall) / n
        records = {
            "clock": {"train_step_p50_ms": harness.median(pairs)
                      if pairs else step_s * 1e3},
            "work": {
                "train_step": {
                    "flops_per_s": work.train_flops_per_token(
                        self.n_params, L, d, self.seq) * rate},
                "flash_attn": {
                    "flops_per_unit": work.flash_flops(
                        self.batch, self.seq, L, d),
                    "events_per_unit": L}},
            "host_spans": ("train_step_dispatch",)}
        return {"end_to_end": {"train_tokens_per_s": rate},
                "attempted": n, "failed": 0, "seconds": elapsed - stall,
                "records": records}

    def release(self):
        self.step = self.model = self.opt = None
        gc.collect()

    # -- correct ------------------------------------------------------------
    def reference_readings(self, prec=None, batches=None):
        ref = harness.reference_of(self.spec.config)
        params = weights.make_weights(self.spec.config, self.seed,
                                      stacked=True)
        prec = prec or ref.REFERENCE
        out = ref.train_steps(
            ref.prepare(params, prec), batches or self.pool[:3],
            self.dims[2], prec, int(self.spec.cell["reference_rows"]))
        del params
        gc.collect()
        return out

    def check(self):
        want = self.reference_readings()
        self.log(f"reference losses {want['losses']}; gaps (not compared) "
                 f"{correct.loss_gaps(self.readings, want)}")
        return correct.train_rows(self.readings, want,
                                  self.spec.cell["limits"])
