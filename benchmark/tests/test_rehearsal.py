"""The rest of a run, driven on the CPU at a toy size (the look for a
chip is skipped; `run.py` itself refuses a machine without a TPU): each
toy cell comes out correct, and comes out NOT correct with the timed
path broken underneath, once for each fault the cell can have, and with
the reference in the lower precision put in the program's place.

The toy cells live under benchmark/fixtures/tiny and share every line
of harness, driver, generator and comparison with the real ones.
"""
import time

import pytest

from benchmark import correct, harness, schedule


def _spec(cell):
    bench = harness.load_json(harness.HERE, "fixtures", "tiny",
                              "BENCHMARK.json")
    return harness.Spec(cell, bench=bench)


def _run(cell, seed=2 ** 31 + 5, seconds=2.0):
    import jax
    return harness.execute(_spec(cell), seed, seconds, False, time.time(),
                           jax.devices()[:1], log=lambda m: None)


@pytest.mark.parametrize("cell", ["gpt-tiny.train", "gpt-tiny.serve-chat",
                                  "gpt-tiny.serve-long"])
def test_sound_run_is_correct(cell):
    res = _run(cell)
    assert res["correct"] is True, res["compared"]
    assert res["failed"] == 0 and res["attempted"] > 0
    names = {r["name"] for r in _spec(cell).metric_rows("end_to_end")}
    assert set(res["metrics"]) == names and "setup_s" in names
    assert list(res)[-1] == "compared"      # comes last in the line


def test_run_py_refuses_a_machine_without_a_tpu():
    import subprocess
    import sys
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "gpt3-125m.train",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=harness.ROOT, capture_output=True, text=True,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"})
    assert p.returncode != 0
    assert "{" not in p.stdout              # no result line


# -- faults planted under the timed path ------------------------------------

def test_train_state_left_unchanged_is_not_correct(monkeypatch):
    """A step that returns its state unchanged: the loss is computed,
    the parameters and the optimizer state are put back."""
    import jax.numpy as jnp
    from paddle_tpu import jit
    real = jit.TrainStep._run_step

    def stuck(self, *batch):
        params = [jnp.array(p._value) for p in self.params]
        states = [{k: jnp.array(v) for k, v in
                   self.optimizer._states[id(p)].items()}
                  for p in self.params]
        loss = real(self, *batch)
        for p, v, s in zip(self.params, params, states):
            p._value = v
            self.optimizer._states[id(p)] = s
        return loss

    monkeypatch.setattr(jit.TrainStep, "_run_step", stuck)
    res = _run("gpt-tiny.train")
    assert res["correct"] is False
    # nothing moved: the gap of norms reads 1 by the comparison's measure
    assert res["compared"]["grad_norm_gap"]["value"] == pytest.approx(1.0)
    assert res["compared"]["change_norm_gap"]["value"] == pytest.approx(1.0)


def test_train_half_of_the_batch_left_out_is_not_correct(monkeypatch):
    """Half of the rows dropped, the mean taken over the rest."""
    from paddle_tpu import jit
    real = jit.TrainStep._run_step
    monkeypatch.setattr(
        jit.TrainStep, "_run_step",
        lambda self, ids, lbl: real(self, ids[:len(ids) // 2],
                                    lbl[:len(lbl) // 2]))
    res = _run("gpt-tiny.train")
    assert res["correct"] is False, res["compared"]


@pytest.mark.parametrize("cell", ["gpt-tiny.serve-chat",
                                  "gpt-tiny.serve-long"])
def test_serve_token_altered_where_produced_is_not_correct(cell,
                                                           monkeypatch):
    """Every seventh token a request produces is replaced by its
    neighbour in the vocabulary before it is streamed (and fed back)."""
    from paddle_tpu.serving import scheduler
    real = scheduler.Request.push_token

    def altered(self, tok, now=None):
        if len(self.out_tokens) % 7 == 3:
            tok = (int(tok) + 1) % 512
        return real(self, tok, now=now)

    monkeypatch.setattr(scheduler.Request, "push_token", altered)
    res = _run(cell)
    assert res["correct"] is False, res["compared"]


# -- the control: the reference in the lower precision -----------------------

def test_train_control_in_fp8_is_not_correct():
    """The reference computed in fp8 operands, put in the program's
    place, fails one of the cell's numbers at the toy cell's limits."""
    import jax
    from benchmark.drivers.train import Driver
    spec = _spec("gpt-tiny.train")
    d = Driver(spec, 41, 0, jax.devices()[:1], log=lambda m: None)
    d.pool = schedule.token_batches(41, d.dims[4], d.batch, d.seq, 3)
    ref = d.reference_readings()
    low = d.reference_readings(prec={"act": "fp8", "wbits": None})
    rows = correct.train_rows(low, ref, spec.cell["limits"])
    assert any(r["value"] > r["limit"] for r in rows), rows
    same = correct.train_rows(ref, ref, spec.cell["limits"])
    assert all(r["value"] == 0 for r in same)


def test_serve_control_in_lower_precision_is_not_correct():
    """At the positions of the served tokens, the token that fp8
    operands put first lies further under the reference's best than
    the toy cell's limit allows."""
    import jax
    from benchmark.drivers.serve import Driver
    spec = _spec("gpt-tiny.serve-chat")
    d = Driver(spec, 43, 2.0, jax.devices()[:1], log=lambda m: None)
    d.setup()
    d.window(None)
    d.release()
    limit = spec.cell["limits"]["served_logit_gap"]
    low8 = d.gaps(prec_low={"act": "fp8", "wbits": 8})
    assert low8["tokens"] > 20 and low8["served"] <= limit
    assert low8["control"] > limit, (low8, limit)


# -- the taps of a traced run ------------------------------------------------

def test_taps_stop_the_run_on_a_dispatch_they_cannot_read():
    """The per-layer metrics of a serving cell read the engine's private
    dispatch arguments: a traced run goes through the taps, and one
    whose arguments are not laid out as the taps expect ends with no
    result instead of a moved metric."""
    import jax
    import numpy as np
    from benchmark.drivers.serve import Driver
    spec = _spec("gpt-tiny.serve-long")
    d = Driver(spec, 47, 1.0, jax.devices()[:1], log=lambda m: None,
               trace=True)
    d.setup()
    seen = []
    real = d._detail
    d._detail = lambda family, args: seen.append((family, args)) or \
        real(family, args)
    m = d.window(None)
    assert d.tap_fault is None and m["records"]["counters"][
        "decode_occupancy"] > 0
    # every clock or counter key that a serving metric file reads is
    # among what a traced run records
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    for row in bench["per_layer"]:
        meta = harness.load_json(harness.HERE, "metrics",
                                 row["name"] + ".json")
        if not row["name"].endswith((".chat", ".long")):
            continue
        if meta["reader"] == "clock":
            assert meta["args"]["key"] in m["records"]["clock"], row["name"]
        elif meta["reader"] == "counter":
            assert meta["args"]["key"] in m["records"]["counters"], \
                row["name"]
    family, args = next(x for x in seen if x[0] == "serving_decode")
    assert (real(family, args) == np.asarray(args[4])).all()
    swapped = list(args)
    swapped[3], swapped[4] = args[4], np.full_like(args[3], 500)
    for bad in (tuple(swapped), args[:-1],
                args[:4] + (np.asarray(args[4], np.int64),) + args[5:]):
        with pytest.raises((TypeError, ValueError)):
            real(family, bad)
    with pytest.raises(ValueError):
        real("serving_decode_v2", args)
    d.tap_fault = "serving_decode: planted"
    with pytest.raises(SystemExit):
        d.taps_sound()
