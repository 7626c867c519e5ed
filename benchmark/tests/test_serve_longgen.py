"""The Qwen3-Next cell's own files, on the CPU: a toy cell of the new
driver through the whole harness (sound: correct; every fault planted in
the program, and the reference in each lower precision put in the
program's place: not correct), the work counts by hand, the taps'
reading of the dispatches, and the configuration, traffic and cell files
against the published configuration and the deployment they state."""
import time

import numpy as np
import pytest

from benchmark import harness, work_qwen3next as work
from benchmark.reference import qwen3_next as ref
from benchmark.tools import calibrate_longgen

CELL = "qwen3-next-80b-a3b.serve-longgen"


def _spec():
    bench = harness.load_json(harness.HERE, "fixtures", "tiny_longgen",
                              "BENCHMARK.json")
    return harness.Spec("qwen3next-tiny.serve-longgen", bench=bench)


@pytest.fixture(scope="module")
def toy_run():
    """One run of the toy cell, and its driver kept for the controls."""
    import importlib
    import jax
    kept = {}
    mod = importlib.import_module("benchmark.drivers.serve_qwen3next")
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


def _correct(rows):
    return all(r["value"] <= r["limit"] for r in rows)


def test_sound_toy_run_is_correct(toy_run):
    res, driver = toy_run
    assert res["correct"] is True, res["compared"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    assert list(res)[-1] == "compared"
    assert set(res["compared"]) == {"served_logit_gap_p100",
                                    "route_left_out", "state_gap",
                                    "state_gap_first"}
    # the rows of requests still live at the stop were read, and
    # nothing was looked up in a prefix index
    assert 1 <= len(driver.live) <= 4
    assert driver.base["tokens_offered"] == 0


CONTROLS = calibrate_longgen.CONTROLS


@pytest.fixture(scope="module")
def toy_gaps(toy_run):
    return toy_run[1].gaps({n: CONTROLS[n] for n in ("fp8_operands",)})


def test_fp8_operands_control_is_not_correct(toy_run, toy_gaps):
    """The tokens the reference puts first when computed with fp8
    operands, judged by the run's own comparison at the cell's limits."""
    _, driver = toy_run
    g = toy_gaps
    sound = driver.state_gaps()
    assert not _correct(driver.compared(
        g["controls"]["fp8_operands"]["gap"], g["left_out"], sound)), g
    assert _correct(driver.compared(g["served"], g["left_out"], sound)), g


def test_bfloat16_state_control_fails_on_the_state(toy_run, toy_gaps):
    """The reference at the stated precision with its state rounded to
    bfloat16 after every token, in the program's place: both state
    numbers are over their limits whatever the served tokens read."""
    _, driver = toy_run
    assert CONTROLS["bf16_state"] == {"act": "bf16", "state": "bf16"}
    low = driver.state_gaps(CONTROLS["bf16_state"])
    rows = driver.compared(toy_gaps["served"], toy_gaps["left_out"], low)
    assert [r["value"] <= r["limit"] for r in rows] \
        == [True, True, False, False], rows
    assert low["first"] == low["by_head"][:, 0].max()
    # six linear layers of four value heads in the toy
    assert low["by_head"].shape == (len(driver.live), 6, 4)


@pytest.mark.parametrize("fault", calibrate_longgen.FAULTS)
def test_planted_fault_is_not_correct(fault):
    """The gated norm in Mamba-2's order, the attention's output gate
    dropped, rotary over the whole head, the state not carried from one
    chunk to the next, the shared expert's gate dropped: each planted in
    the program as tools/calibrate_longgen.py plants it on the chip,
    each not correct through the run's own comparison."""
    import jax
    from benchmark.drivers.serve_qwen3next import Driver
    d = calibrate_longgen.planted(Driver, fault)(
        _spec(), 2 ** 31 + 6, 2.0, jax.devices()[:1], log=lambda m: None)
    d.setup()
    d.window(None)
    d.release()
    rows = d.check()
    assert not _correct(rows), rows
    assert {r["name"] for r in rows} == {"served_logit_gap_p100",
                                         "route_left_out", "state_gap",
                                         "state_gap_first"}


def test_taps_read_the_dispatches_with_rows():
    """The taps' `_detail` takes a decode dispatch of 13 arguments and a
    chunk's of 14, the rows last, and refuses GPT's layout."""
    from benchmark.drivers.serve_qwen3next import Driver
    d = Driver(_spec(), 1, 2.0, None, log=lambda m: None)
    S, C = 4, 16
    i32 = lambda *shape: np.zeros(shape, np.int32)
    decode = [None] * 3 + [i32(S), np.array([5, 0, 9, 0], np.int32),
                           i32(S, 32)] + [None] * 6 + [
        np.array([2, 0, 1, 0], np.int32)]
    assert list(d._detail("serving_decode", decode)) == [5, 0, 9, 0]
    with pytest.raises(TypeError):
        d._detail("serving_decode", decode[:12])
    chunk = [None] * 3 + [i32(1, C), np.int32(16), np.int32(7)] \
        + [None] * 7 + [np.int32(2)]
    assert d._detail("serving_prefill", chunk) == (16, 7)
    with pytest.raises(TypeError):
        d._detail("serving_prefill", chunk[:13])


CFG = harness.load_json(harness.HERE, "configs", "qwen3-next-80b-a3b.json")
M = ref.sizes(CFG)


def test_work_counts_by_hand():
    # by hand: a linear mixer's matrices 33,685,504 (of its
    # 33,718,464 parameters), a full mixer's 27,262,976, an expert
    # 3,145,728, 1.25 of them held a token; 9 linear and 3 full layers
    assert (work.linear_layers(M), work.full_layers(M)) == (9, 3)
    assert work.linear_params(M) == 2048 * 12288 + 2048 * 64 \
        + 4096 * 2048 == 33_685_504
    assert work.attention_params(M) == 2048 * 8192 + 2 * 2048 * 512 \
        + 4096 * 2048 == 27_262_976
    assert work.expert_params(M) == 3_145_728
    assert work.held_per_token(M) == 1.25
    blocks, head = work.matmul_params(M)
    assert head == 2048 * 18992
    assert blocks == 9 * 33_685_504 + 3 * 27_262_976 + 12 * (
        2048 * 512 + 3_145_728 + 2048 + 1.25 * 3_145_728)
    # a request's state: 32 x 128 x 128 float32 = 2 MiB a layer, read
    # and written by a decode step: 128 rows x 9 layers x 4 MiB = 4.83 GB
    # (and 33 KB of q, k, v, gates and output a row a layer)
    assert work.state_numbers(M) * 4 == 2 * 2 ** 20
    assert work.state_step_bytes(128, M) == 128 * 9 * (
        4 * 2 ** 20 + (2 * 2048 + 2 * 4096 + 64) * 4)
    assert 4.8e9 < work.state_step_bytes(128, M) < 4.9e9
    assert work.state_step_flops(1, M) == 7 * 2 ** 19 * 9
    assert work.state_step_flops(1, M) < work.state_step_bytes(1, M)
    # a chunk of 100 is a sub-chunk of 64 and one of 36
    one = lambda l: (2 * (l * (l - 1) // 2) * 256 + 2 * (l * (l + 1) // 2)
                     * 256 + 6 * l * 128 * 128 + 128 * 128)
    assert work.chunk_flops(100, M) == (one(64) + one(36)) * 32 * 9
    assert work.chunk_bytes(512, M) == 9 * (
        512 * (2 * 2048 + 2 * 4096 + 64) * 4 + 2 * 2 * 2 ** 20)
    # K and V rows of 2 heads x 256 in 3 layers: 6,144 B a token
    assert work.decode_attention_bytes([1], M) == 6144
    assert work.decode_attention_flops([1000], M) \
        == 1000 * 4 * 16 * 256 * 3
    assert work.decode_token_flops(500, M) == 2 * (blocks + head) \
        + work.state_step_flops(1, M) + 500 * 4 * 4096 * 3
    attended = 512 * 512 + 512 * 513 // 2
    assert work.prefill_chunk_flops(512, 512, M, last_chunk=True) \
        == 2 * blocks * 512 + work.chunk_flops(512, M) \
        + attended * 4 * 4096 * 3 + 2 * head
    flops, bytes_ = work.expert_work(64, 16, M)
    assert flops == 2 * 64 * 3_145_728 and bytes_ == 16 * 3_145_728 * 2
    # a decode batch of 128 reaches ~58.8 of the 64 held experts
    assert 58.5 < work.experts_touched(128, M) < 59.0


def test_parameter_count_by_hand():
    """2,929,374,400: 9 linear layers of 239,245,504, 3 full layers of
    232,790,528, an eighth of the vocabulary twice and the final norm,
    from the leaves the reference draws (the program's own count is
    logged by the run and held by tests/test_qwen3_next.py)."""
    def layer(i):
        return sum(int(np.prod(s)) for _, s, _ in ref.layer_leaves(M, i)) \
            + 64 * sum(int(np.prod(s)) for _, s, _ in ref.expert_leaves(M))
    assert layer(0) == 239_245_504 and layer(3) == 232_790_528
    total = sum(layer(i) for i in range(12)) + 2 * 18992 * 2048 + 2048
    assert total == 2_929_374_400


# the published config.json's numbers, as the configuration file must
# keep them (all but the keys it lists in `reduced`)
PUBLISHED = {
    "decoder_sparse_step": 1, "full_attention_interval": 4, "head_dim": 256,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 5120,
    "linear_conv_kernel_dim": 4, "linear_key_head_dim": 128,
    "linear_num_key_heads": 16, "linear_num_value_heads": 32,
    "linear_value_head_dim": 128, "max_position_embeddings": 262144,
    "mlp_only_layers": [], "model_type": "qwen3_next",
    "moe_intermediate_size": 512, "norm_topk_prob": True,
    "num_attention_heads": 16, "num_experts": 512, "num_experts_per_tok": 10,
    "num_hidden_layers": 48, "num_key_value_heads": 2,
    "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-06,
    "rope_scaling": None, "rope_theta": 10000000,
    "shared_expert_intermediate_size": 512, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936}


def test_configuration_file_keeps_every_published_number():
    assert CFG["source"] == ("https://huggingface.co/Qwen/Qwen3-Next-80B-"
                             "A3B-Instruct/blob/main/config.json")
    changed = {"num_experts": 64, "vocab_size": 18992}
    for key, value in PUBLISHED.items():
        assert CFG[key] == changed.get(key, value), key


def test_configuration_states_the_cut():
    assert CFG["reduced"] == ["num_layers", "num_experts", "vocab_size"]
    assert CFG["num_layers"] == 12 and CFG["num_hidden_layers"] == 48
    assert CFG["published"] == {"num_hidden_layers": 48, "num_experts": 512,
                                "vocab_size": 151936}
    dep = CFG["deployment"]
    assert (dep["stages"], dep["chips_a_layer"], dep["router_experts"],
            dep["held_experts"], dep["vocab_rows"]) \
        == (4, 8, 512, [0, 64], [0, 18992])
    # the floors: three whole periods, 64 >= 8 experts, an eighth of the
    # vocabulary
    assert M["layer_types"] == (("linear_attention",) * 3
                                + ("full_attention",)) * 3
    assert CFG["vocab_size"] * 8 == 151936
    assert M["rotary_dim"] == 64 and M["rope_theta"] == 1e7
    assert set(CFG["assumed"]) >= {
        "initializer_range", "qkvz_layout", "q_gate_layout",
        "gated_norm_order", "router_draw", "linear_draws"}
    assert "multi_token_prediction" in CFG["left_out"]
    assert CFG["precision"]["params"] == "bfloat16"


def test_traffic_and_cell_as_stated():
    t = harness.load_json(harness.HERE, "traffic", "serve-longgen.json")
    assert t["prompt_len"] == {"dist": "lognormal", "median": 2048,
                               "sigma": 1.0, "min": 256, "max": 16384}
    assert t["output_len"] == {"dist": "lognormal", "median": 1024,
                               "sigma": 0.7, "min": 128, "max": 4096}
    assert t["arrivals"]["kind"] == "poisson" and t["at_close"] == "stop"
    assert t["driver"] == "serve_qwen3next" and "shared_prefix" not in t
    assert "knee" in t["rate_from"]
    cell = harness.load_json(harness.HERE, "cells", CELL + ".json")
    eng = cell["engine"]
    assert (eng["max_slots"], eng["block_size"], eng["prefill_chunk"],
            eng["weights"], eng["max_model_len"]) \
        == (128, 16, 512, "native", 20480)
    assert set(cell["limits"]) == {"served_logit_gap_p95",
                                   "route_left_out", "state_gap",
                                   "state_gap_first"}
    # every prompt and answer of the mix fits the model length
    assert t["prompt_len"]["max"] + t["output_len"]["max"] \
        <= eng["max_model_len"]
    assert len(cell["why"]) <= 200 and "2.5" in cell["why"]


def test_every_metric_of_the_cell_has_its_file_and_row():
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    mine = {r["name"] for r in bench["per_layer"]
            if CELL in r.get("workloads", ())}
    own = {"gdn_step_roofline", "gdn_step_time_share", "gdn_chunk_roofline",
           "gdn_chunk_time_share", "serve_step_mfu", "paged_decode_roofline",
           "paged_decode_time_share", "prefill_chunk_time_share",
           "moe_experts_roofline", "moe_experts_time_share",
           "moe_held_share", "expert_load_max_over_mean",
           "state_rows_live_share", "decode_occupancy", "engine_step_ms",
           "ttft_p50_ms", "tpot_p50_ms"}
    scopes = {"embed", "attn", "mlp", "experts", "head", "sample",
              "unscoped", "xla_own", "prefill_program", "linear"}
    assert mine == {n + ".longgen" for n in own} \
        | {"device_share." + s for s in scopes} \
        | {"compiles_in_window", "cache_misses_warm"}
    e2e = {r["name"] for r in bench["end_to_end"]
           if CELL in r.get("workloads", (CELL,))}
    assert e2e == {"serve_tokens_per_s", "setup_s"}
    for name in mine:
        meta = harness.load_json(harness.HERE, "metrics", name + ".json")
        assert meta["moves"] == "serve_tokens_per_s" or name in (
            "compiles_in_window", "cache_misses_warm")
    kernels = {n: harness.load_json(harness.HERE, "metrics", n + ".json")
               for n in ("gdn_step_roofline.longgen",
                         "gdn_chunk_roofline.longgen")}
    assert {m["args"]["work"] for m in kernels.values()} \
        == {"gdn_state_step", "gdn_chunk"}
