"""Record the small device trace kept under benchmark/fixtures/ for the
trace-reduction tests: two steps of a toy GPT under `jit.TrainStep`
with the flash kernels in them, on the chip.

    python3 benchmark/tools/record_fixture.py <out.xplane.pb.gz>
"""
import gzip
import os
import shutil
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main(out):
    import jax
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu import amp, optimizer
    from paddle_tpu.models.gpt import GPTConfig, GPTForPretraining
    from benchmark import harness, trace_reduce
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("record_fixture: needs a TPU")
    model = GPTForPretraining(GPTConfig(
        vocab_size=1024, hidden_size=256, num_layers=2, num_heads=4,
        max_seq_len=1024, dropout=0.0))
    opt = optimizer.AdamW(learning_rate=1e-4, parameters=model.parameters())

    def loss_fn(ids, labels):
        with amp.auto_cast(enable=True, dtype="bfloat16"):
            return model.loss(ids, labels)

    step = paddle.jit.TrainStep(model, loss_fn, opt)
    rs = np.random.RandomState(0)
    batch = [rs.randint(0, 1024, (4, 1024)).astype(np.int32)
             for _ in range(2)]
    step(*batch).item()
    tmp = tempfile.mkdtemp(dir=os.path.join(ROOT, "chiprun_out"))
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(tmp, profiler_options=options)
    for _ in range(2):
        with harness.annotate("train_step_dispatch"):
            loss = step(*batch)
        loss.item()
    jax.profiler.stop_trace()
    src = trace_reduce.find_xplane(tmp)
    with open(src, "rb") as f, gzip.open(out, "wb") as g:
        shutil.copyfileobj(f, g)
    shutil.rmtree(tmp)
    print(out, os.path.getsize(out), "bytes")


if __name__ == "__main__":
    main(sys.argv[1])
