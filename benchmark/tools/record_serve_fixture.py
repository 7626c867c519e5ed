"""Record the small serving trace kept under benchmark/fixtures/ for the
tests of the `program_span` reader: a toy engine (two layers at GPT-3
125M's width, eight slots) serving eight short requests through
`start()`/`submit()` on the chip, with the harness's spans around
`step` and `_dispatch` as the serving driver puts them in a traced run,
so the program's spans lie beside the device's ops and beside the spans
the reader must leave out.

    python3 benchmark/tools/record_serve_fixture.py <out.xplane.pb.gz>
"""
import gzip
import os
import shutil
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

LIMIT = 512 * 1024      # bytes the fixture may take in the repository
SLOTS = 8               # the test divides `slots` by this


def main(out):
    import jax
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu.models.gpt import GPTConfig, GPTForPretraining
    from paddle_tpu.serving import (EngineConfig, SamplingParams,
                                    ServingEngine)
    from benchmark import harness, trace_reduce
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("record_serve_fixture: needs a TPU")
    paddle.seed(0)
    model = GPTForPretraining(GPTConfig(
        vocab_size=2048, hidden_size=768, num_layers=2, num_heads=12,
        max_seq_len=1024, dropout=0.0))
    eng = ServingEngine(model, config=EngineConfig(
        max_slots=SLOTS, block_size=16, prefill_chunk=128, weights="wo8",
        max_model_len=512, kv_memory_mb=64))
    step, dispatch = eng.step, eng._dispatch

    def timed_step():
        with harness.annotate("engine_step"):
            return step()

    def timed_dispatch(family, jitted, args):
        with harness.annotate(family):
            return dispatch(family, jitted, args)

    eng.step, eng._dispatch = timed_step, timed_dispatch
    rs = np.random.RandomState(0)
    head = rs.randint(1, 2048, 24)
    prompts = [np.concatenate([head, rs.randint(1, 2048, n)]).astype(
        np.int32) for n in rs.randint(30, 200, 8)]
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    tmp = tempfile.mkdtemp(dir=os.path.join(ROOT, "chiprun_out"))
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    eng.start()
    try:
        for p in prompts[:2]:       # compile outside the trace
            eng.submit(p, SamplingParams(max_new_tokens=4)).result(
                timeout=600)
        jax.profiler.start_trace(tmp, profiler_options=options)
        handles = [eng.submit(p, SamplingParams(max_new_tokens=8))
                   for p in prompts]
        for h in handles:
            h.result(timeout=600)
        jax.profiler.stop_trace()
    finally:
        eng.stop()
    src = trace_reduce.find_xplane(tmp)
    with open(src, "rb") as f, gzip.open(out, "wb") as g:
        shutil.copyfileobj(f, g)
    shutil.rmtree(tmp)
    size = os.path.getsize(out)
    print(out, size, "bytes")
    if size >= LIMIT:
        raise SystemExit(f"record_serve_fixture: {size} bytes, over {LIMIT}")


if __name__ == "__main__":
    main(sys.argv[1])
