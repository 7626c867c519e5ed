"""The trace reduction, on a hand-written trace whose numbers can be
worked out on paper, and on a small trace recorded on the v5e
(benchmark/fixtures/v5e_train_steps.xplane.pb.gz: two steps of a toy
GPT under `jit.TrainStep` with the flash kernels in them)."""
import gzip
import os

import pytest

from benchmark import harness, trace_reduce as tr

US = 1_000_000      # picoseconds in a microsecond


def _event(mid, start_us, dur_us):
    return (f"events {{ metadata_id: {mid} offset_ps: {start_us * US} "
            f"duration_ps: {dur_us * US} }}")


def _plane(name, line, names, events):
    meta = "\n".join(
        f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}'
        for i, n in names.items())
    return (f'planes {{ name: "{name}" lines {{ name: "{line}" '
            f'timestamp_ns: 0 {" ".join(events)} }} {meta} }}')


@pytest.fixture(scope="module")
def synthetic():
    """One device, microseconds:
         0-100  fusion.1            100-150 idle
       150-250  %paged_decode.7 = ...(long HLO text)
       250-400  all-reduce.3        300-350 fusion.2 (under the all-reduce)
       400-500  idle                500-600 flash_prefill_chunk.2
    Host spans: engine_step 90-260, serving_decode 140-160,
                engine_step 390-520."""
    from jax.profiler import ProfileData
    names = {1: "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop",
             2: "%paged_decode.7 = f32[32,1,768]{2,1,0} custom-call(...)",
             3: "%all-reduce.3 = f32[8]{0} all-reduce(f32[8]{0} %x)",
             4: "%fusion.2 = f32[8]{0} fusion(f32[8]{0} %q), kind=kLoop",
             5: "%flash_prefill_chunk.2 = bf16[1,128,768] custom-call(...)"}
    dev = _plane("/device:TPU:0", "XLA Ops", names, [
        _event(1, 0, 100), _event(2, 150, 100), _event(3, 250, 150),
        _event(4, 300, 50), _event(5, 500, 100)])
    host = _plane("/host:CPU", "python3",
                  {1: "engine_step", 2: "serving_decode", 3: "noise"},
                  [_event(1, 90, 170), _event(2, 140, 20),
                   _event(1, 390, 130), _event(3, 0, 600)])
    data = ProfileData.from_text_proto(dev + "\n" + host)
    return tr.from_profile(data, host_names=("engine_step",
                                             "serving_decode"))


def test_busy_and_idle_by_hand(synthetic):
    # busy: 0-100, 150-400 (the all-reduce covers fusion.2), 500-600
    assert tr.busy_seconds(synthetic) == pytest.approx(450e-6)
    assert tr.window_of(synthetic) == (0, 600_000)


def test_kernel_time_by_name_pattern(synthetic):
    assert tr.kernel_seconds(synthetic, "paged_decode") == \
        pytest.approx(100e-6)
    assert tr.kernel_seconds(synthetic, "^fusion") == pytest.approx(150e-6)
    assert tr.kernel_seconds(synthetic, "flash_(fwd|bwd)") is None
    assert tr.op_name("%paged_decode.7 = f32[1] custom-call()") == \
        "paged_decode.7"
    assert tr.base_name("paged_decode.7") == "paged_decode"
    top = dict(tr.top_ops(synthetic))
    assert top["all-reduce"] == pytest.approx(150e-6)
    assert top["fusion"] == pytest.approx(150e-6)


def test_idle_gaps_named_by_the_host_span(synthetic):
    gaps = dict(tr.idle_gaps(synthetic, tr.window_of(synthetic)))
    # 100-150: midpoint 125 lies in engine_step 90-260 only;
    # 400-500: midpoint 450 lies in engine_step 390-520
    assert gaps == {"engine_step": pytest.approx(150e-6)}
    narrow = dict(tr.idle_gaps(synthetic, (140_000, 160_000)))
    # 140-150 idle: midpoint 145 lies in serving_decode, the innermost
    assert narrow == {"serving_decode": pytest.approx(10e-6)}


def test_interval_arithmetic():
    assert tr.union([(5, 7), (0, 2), (1, 3)]) == [[0, 3], [5, 7]]
    assert tr.subtract([[0, 10]], [[2, 3], [5, 12]]) == [[0, 2], [3, 5]]
    assert tr.subtract([[0, 10]], []) == [[0, 10]]


RECORDED = os.path.join(harness.HERE, "fixtures",
                        "v5e_train_steps.xplane.pb.gz")


@pytest.mark.skipif(not os.path.exists(RECORDED),
                    reason="no recorded trace in the fixtures")
def test_recorded_v5e_trace(tmp_path):
    path = tmp_path / "t.xplane.pb"
    with gzip.open(RECORDED) as f:
        path.write_bytes(f.read())
    t = tr.load(str(path), host_names=("train_step_dispatch",))
    assert list(t.devices) == ["/device:TPU:0"]
    start, end = tr.window_of(t)
    busy = tr.busy_seconds(t)
    assert 0 < busy <= (end - start) * 1e-9
    flash = tr.kernel_seconds(t, "flash_(fwd|bwd)")
    fwd = tr.kernel_seconds(t, "flash_fwd")
    assert 0 < fwd < flash < busy
    assert any(n == "train_step_dispatch" for n, _, _ in t.host)
    idle = sum(s for _, s in tr.idle_gaps(t, (start, end), k=100))
    assert idle == pytest.approx((end - start) * 1e-9 - busy, rel=1e-6)
