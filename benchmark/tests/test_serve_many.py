"""The Granite-4.0-H cell's own files, on the CPU: a toy cell of the new
driver through the whole harness (sound: correct; a fault planted in the
program, or the reference in the lower precision put in the program's
place: not correct), the work counts by hand, the taps' reading of the
dispatches, and the configuration, traffic and cell files against the
catalog row and the issue."""
import json
import time

import numpy as np
import pytest

from benchmark import harness, work_hybrid
from benchmark.reference import granite_hybrid as ref


def _spec():
    bench = harness.load_json(harness.HERE, "fixtures", "tiny_hybrid",
                              "BENCHMARK.json")
    return harness.Spec("granite-tiny.serve-many", bench=bench)


@pytest.fixture(scope="module")
def toy_run():
    """One run of the toy cell, and its driver kept for the controls."""
    import importlib
    import jax
    kept = {}
    mod = importlib.import_module("benchmark.drivers.serve_hybrid")
    real = mod.Driver

    class Kept(real):
        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            kept["driver"] = self
    mod.Driver = Kept
    try:
        res = harness.execute(_spec(), 2 ** 31 + 5, 2.0, False, time.time(),
                              jax.devices()[:1], log=lambda m: None)
    finally:
        mod.Driver = real
    return res, kept["driver"]


def test_sound_toy_run_is_correct(toy_run):
    res, driver = toy_run
    assert res["correct"] is True, res["compared"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    assert list(res)[-1] == "compared"
    assert set(res["compared"]) == {"served_logit_gap", "state_gap"}
    # the rows of requests still live at the stop were read
    assert 1 <= len(driver.live) <= 4
    # every request took a row and gave it back; nothing was looked up
    # in a prefix index
    assert driver.base["tokens_offered"] == 0


CONTROLS = {"fp8_operands": {"act": "fp8"}}


@pytest.fixture(scope="module")
def toy_gaps(toy_run):
    return toy_run[1].gaps(CONTROLS)


def _correct(rows):
    return all(r["value"] <= r["limit"] for r in rows)


def test_fp8_operands_control_is_not_correct(toy_run, toy_gaps):
    """The tokens the reference puts first when computed with fp8
    operands, judged by the run's own comparison at the cell's limits."""
    _, driver = toy_run
    g = toy_gaps
    sound = driver.state_gaps()["worst"]
    assert not _correct(driver.compared(
        g["controls"]["fp8_operands"]["gap"], sound)), g
    assert _correct(driver.compared(g["served"], sound)), g


def test_bfloat16_state_control_fails_on_the_state_alone(toy_run, toy_gaps):
    """The reference with its state alone rounded to bfloat16 after
    every token, in the program's place: `state_gap` is over its limit
    whatever the served tokens read."""
    _, driver = toy_run
    low = driver.state_gaps({"act": "f32", "state": "bf16"})
    rows = driver.compared(toy_gaps["served"], low["worst"])
    assert [r["value"] <= r["limit"] for r in rows] == [True, False], rows
    assert low["by_head"].shape == (len(driver.live), 3, 4)


@pytest.mark.parametrize("fault", ["state_dropped", "row_kept",
                                   "bf16_state"])
def test_planted_state_fault_is_not_correct(fault):
    """A fault in the request rows alone (the state not carried from one
    chunk to the next; a row not cleared on reuse; the state held in
    bfloat16), planted in the program as tools/calibrate_many.py plants
    it on the chip, comes out not correct through the run's own
    comparison."""
    import jax
    from benchmark.drivers.serve_hybrid import Driver
    from benchmark.tools.calibrate_many import planted
    d = planted(Driver, fault)(_spec(), 2 ** 31 + 6, 2.0,
                               jax.devices()[:1], log=lambda m: None)
    d.setup()
    d.window(None)
    d.release()
    rows = {r["name"]: r for r in d.check()}
    assert set(rows) == {"served_logit_gap", "state_gap"}
    # each is seen in the state itself, well over the limit (the
    # bfloat16 state by nothing else: it moves no served token here)
    assert rows["state_gap"]["value"] > 10 * rows["state_gap"]["limit"], \
        rows


def test_taps_read_the_rows_and_refuse_another_layout(toy_run):
    _, driver = toy_run
    slots, chunk, mb = 4, 16, 16
    i32 = lambda *shape: np.zeros(shape, np.int32)
    decode = [None] * 3 + [i32(slots), np.asarray([5, 0, 9, 0], np.int32),
                           i32(slots, mb)] + [None] * 6
    rows = np.asarray([2, 0, 1, 0], np.int32)
    got = driver._detail("serving_decode", tuple(decode) + (rows,))
    assert list(got) == [5, 0, 9, 0]
    with pytest.raises(TypeError):
        driver._detail("serving_decode", tuple(decode))     # no rows
    with pytest.raises(TypeError):
        driver._detail("serving_decode", tuple(decode) + (rows + 7,))
    prefill = [None] * 3 + [i32(1, chunk), np.int32(16), np.int32(9)] \
        + [None] * 7
    assert driver._detail("serving_prefill",
                          tuple(prefill) + (np.int32(3),)) == (16, 9)
    with pytest.raises(TypeError):
        driver._detail("serving_prefill", tuple(prefill) + (np.int32(0),))
    with pytest.raises(TypeError):
        driver._detail("serving_prefill", tuple(prefill))


CONFIG = harness.load_json(harness.HERE, "configs",
                           "granite-4.0-h-micro.json")
M = ref.sizes(CONFIG)


def test_work_counts_by_hand():
    # ISSUE 32's table: a Mamba-2 mixer's matrices 2048 x 8,512 and
    # 4096 x 2048, attention 10.49M, the gated MLP 50.33M, the head
    # 205.5M; 36 Mamba-2 layers and 4 attention layers
    assert (work_hybrid.mamba_layers(M), work_hybrid.attention_layers(M)) \
        == (36, 4)
    assert work_hybrid.mamba_params(M) == 2048 * 8512 + 4096 * 2048 \
        == 25_821_184
    assert work_hybrid.attention_params(M) \
        == 2 * 2048 * 2048 + 2 * 2048 * 512 == 10_485_760
    assert work_hybrid.mlp_params(M) == 3 * 2048 * 8192 == 50_331_648
    blocks, head = work_hybrid.matmul_params(M)
    assert head == 2048 * 100_352
    assert blocks == 36 * 25_821_184 + 4 * 10_485_760 + 40 * 50_331_648
    # a request's state: 128 x 4,096 float32 = 2 MiB a layer, read and
    # written by a decode step: 64 rows x 36 layers x 4 MiB = 9.66 GB
    assert work_hybrid.state_numbers(M) * 4 == 2 * 2 ** 20
    assert work_hybrid.state_step_bytes(64, M) == 64 * 36 * 4 * 2 ** 20
    assert 9.6e9 < work_hybrid.state_step_bytes(64, M) < 9.7e9
    assert work_hybrid.state_step_flops(64, M) \
        == 5 * 64 * 128 * 4096 * 36
    # under one operation a byte
    assert work_hybrid.state_step_flops(1, M) \
        < work_hybrid.state_step_bytes(1, M)
    # a chunk of 300 tokens is a piece of 256 and one of 44
    half = lambda l: l * (l + 1) // 2
    assert work_hybrid.chunk_scan_flops(300, M) == 36 * sum(
        2 * half(l) * 128 + 2 * half(l) * 4096 + 4 * l * 128 * 4096
        for l in (256, 44))
    assert work_hybrid.chunk_scan_bytes(512, M) == 36 * (
        512 * (4096 + 256) * 2 + 512 * 4096 * 4 + 2 * 2 * 2 ** 20)
    # K and V rows of 8 heads x 64 in 4 layers: 8 KB a token
    assert work_hybrid.decode_attention_bytes([1], M) == 8192
    assert work_hybrid.decode_attention_bytes([100, 28], M) == 128 * 8192
    assert work_hybrid.decode_attention_flops([1000], M) \
        == 1000 * 4 * 2048 * 4
    assert work_hybrid.decode_token_flops(500, M) == 2 * (blocks + head) \
        + 5 * 128 * 4096 * 36 + 500 * 4 * 2048 * 4
    attended = 512 * 512 + half(512)
    assert work_hybrid.prefill_chunk_flops(512, 512, M, last_chunk=True) \
        == 2 * blocks * 512 + work_hybrid.chunk_scan_flops(512, M) \
        + attended * 4 * 2048 * 4 + 2 * head


def test_parameter_count_is_the_issues():
    """3.19B: 36 x 76.19M + 4 x 60.82M + 205.5M, from the leaves the
    reference draws (the program's own count is logged by the run)."""
    def layer(i):
        return sum(int(np.prod(shape))
                   for _, shape, _ in ref.layer_leaves(M, i))
    assert layer(0) == 76_182_976 and layer(5) == 60_821_504
    total = sum(layer(i) for i in range(40)) + 100_352 * 2048 + 2048
    assert total == 36 * 76_182_976 + 4 * 60_821_504 + 205_522_944
    assert 3.18e9 < total < 3.20e9


def test_configuration_file_holds_the_catalog_row_whole():
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(line) for line in f]
    row = next((r for r in rows if r["name"] == "granite-4.0-h-micro"), None)
    if row is None:
        pytest.skip("no catalog beside the guides")
    assert CONFIG["source"] == row["source_url"]
    for key, value in row["config"].items():
        assert CONFIG[key] == value, key
    assert CONFIG["reduced"] == [] and CONFIG["reference"] == "granite_hybrid"
    assert len(CONFIG["layer_types"]) == 40 and CONFIG["vocab_size"] == 100352
    assert [i for i, t in enumerate(CONFIG["layer_types"])
            if t == "attention"] == [5, 15, 25, 35]
    assert set(CONFIG["assumed"]) == {"state_dtype", "initializer_range",
                                      "mamba_draws", "dt_columns",
                                      "matrix_layout"}


def test_traffic_and_cell_are_as_the_issue_names_them():
    t = harness.load_json(harness.HERE, "traffic", "serve-many.json")
    assert t["prompt_len"] == {"dist": "lognormal", "median": 384,
                               "sigma": 0.6, "min": 128, "max": 1536}
    assert t["output_len"] == {"dist": "lognormal", "median": 256,
                               "sigma": 0.6, "min": 64, "max": 768}
    assert "shared_prefix" not in t
    assert t["arrivals"]["kind"] == "poisson" and t["at_close"] == "stop"
    assert t["check_wait_s"] == 60 and t["driver"] == "serve_hybrid"
    assert t["trace"] == {"start_share": 0.5, "seconds": 3.0}
    assert "knee" in t["rate_from"]
    cell = harness.load_json(harness.HERE, "cells",
                             "granite-4.0-h-micro.serve-many.json")
    assert cell["engine"] == {"max_slots": 64, "block_size": 16,
                              "prefill_chunk": 512, "weights": "native",
                              "max_model_len": 2560, "kv_memory_mb": 1536}
    assert cell["check_requests"] == 12
    assert set(cell["limits"]) == {"served_logit_gap", "state_gap"}
    assert set(cell["init"]) == {"block_scale"}
    # every prompt and answer of the mix fits the model length
    assert t["prompt_len"]["max"] + t["output_len"]["max"] \
        <= cell["engine"]["max_model_len"]


def test_every_metric_of_the_cell_has_its_file_and_row():
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    cell = "granite-4.0-h-micro.serve-many"
    mine = {r["name"] for r in bench["per_layer"]
            if cell in r.get("workloads", ())}
    assert mine == {
        "ssm_step_roofline.many", "ssm_step_time_share.many",
        "ssm_scan_roofline.many", "ssm_scan_time_share.many",
        "paged_decode_roofline.many", "paged_decode_time_share.many",
        "prefill_chunk_time_share.many", "serve_step_mfu.many",
        "engine_step_ms.many", "decode_occupancy.many",
        "state_rows_live_share.many", "ttft_p50_ms.many",
        "tpot_p50_ms.many", "compiles_in_window", "cache_misses_warm"}
    e2e = {r["name"] for r in bench["end_to_end"]
           if cell in r.get("workloads", (cell,))}
    assert e2e == {"serve_tokens_per_s", "setup_s"}
