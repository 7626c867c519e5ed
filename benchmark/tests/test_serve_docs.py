"""The DeepSeek-V2 cell's own files, on the CPU: a toy cell of the new
driver through the whole harness (sound: correct; the reference in the
lower precision put in the program's place: not correct), the work
counts by hand, and the configuration file against the contract."""
import time

import pytest

from benchmark import harness, work_mla
from benchmark.reference import deepseek_v2 as ref


def _spec():
    bench = harness.load_json(harness.HERE, "fixtures", "tiny_mla",
                              "BENCHMARK.json")
    return harness.Spec("deepseek-tiny.serve-docs", bench=bench)


@pytest.fixture(scope="module")
def toy_run():
    """One run of the toy cell, and its driver kept for the control."""
    import importlib
    import jax
    kept = {}
    mod = importlib.import_module("benchmark.drivers.serve_mla")
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
    # the run went through the latent cache and counted its experts
    assert driver.base["blocks"] > 0


CONTROLS = {"fp8_operands": {"act": "fp8"},
            "fp8_latent": {"act": "f32", "latent": "fp8"}}


@pytest.fixture(scope="module")
def toy_gaps(toy_run):
    return toy_run[1].gaps(CONTROLS)


@pytest.mark.parametrize("name", list(CONTROLS))
def test_lower_precision_control_is_not_correct(toy_run, toy_gaps, name):
    """The tokens the reference puts first when computed in fp8, judged
    by the run's own comparison at the cell's limits."""
    _, driver = toy_run
    g = toy_gaps
    rows = driver.compared(g["controls"][name]["gap"], g["left_out"])
    assert not all(r["value"] <= r["limit"] for r in rows), g
    assert all(r["value"] <= r["limit"]
               for r in driver.compared(g["served"], g["left_out"])), g


def test_route_margin_leaves_out_what_it_says(toy_run, toy_gaps):
    """Tokens whose routing the reference decided by less than the
    cell's `check.route_margin` are not compared, the share left out is
    compared itself, and a margin that empties the comparison fails."""
    _, driver = toy_run
    g = toy_gaps
    assert 0 < g["compared"] <= g["tokens"]
    assert abs(g["left_out"] - (1.0 - g["compared"] / g["tokens"])) < 1e-12
    assert g["served"] <= g["widest"] <= g["widest_of_all"]
    # the gap that a share of the compared tokens stay within: the
    # fp8 control's median lies under its widest
    assert g["controls"]["fp8_operands"]["gap"] == g["below"][1].max()
    cell = driver.spec.cell
    kept = dict(cell["check"])
    try:
        cell["check"]["within_share"] = 0.5
        half = driver.gaps({"fp8_operands": CONTROLS["fp8_operands"]})
        import numpy as np
        assert half["controls"]["fp8_operands"]["gap"] == float(np.median(
            half["below"][1][half["margin"] >= kept["route_margin"]]))
        assert half["controls"]["fp8_operands"]["gap"] < g["below"][1].max()
        cell["check"].update(kept, route_margin=1e9)
        none = driver.gaps()
    finally:
        cell["check"].update(kept)
    assert none["compared"] == 0 and none["served"] == 0.0
    assert not all(r["value"] <= r["limit"] for r in
                   driver.compared(none["served"], none["left_out"]))


@pytest.mark.parametrize("fault", ["zero_routed", "shifted_held"])
def test_planted_expert_fault_is_not_correct(fault):
    """A fault in the routed sum alone (the grouped products give
    zeros; the layer is told the wrong first expert), planted in the
    program as tools/calibrate_docs.py plants it on the chip, comes out
    not correct through the run's own comparison."""
    import jax
    from benchmark.drivers.serve_mla import Driver
    from benchmark.tools.calibrate_docs import planted
    d = planted(Driver, fault)(_spec(), 2 ** 31 + 6, 2.0,
                               jax.devices()[:1], log=lambda m: None)
    d.setup()
    d.window(None)
    d.release()
    rows = d.check()
    assert not all(r["value"] <= r["limit"] for r in rows), rows
    assert {r["name"] for r in rows} == {"served_logit_gap_p100",
                                         "route_left_out"}


M = ref.sizes(harness.load_json(harness.HERE, "configs", "deepseek-v2.json"))


def test_work_counts_by_hand():
    # ISSUE 28's table: attention 149.2M, an expert 23.6M, 1.5 of them
    # held a token, 1,152 B and 2 x 128 x 1,088 FLOP a cached row a layer
    assert work_mla.attention_params(M) == (
        5120 * 1536 + 1536 * 128 * 192 + 5120 * 576 + 512 * 128 * 256
        + 16384 * 5120) == 149_225_472
    assert work_mla.expert_params(M) == 3 * 5120 * 1536 == 23_592_960
    assert work_mla.held_per_token(M) == 1.5
    blocks, head = work_mla.matmul_params(M)
    assert head == 5120 * 25600
    assert blocks == 5 * 149_225_472 + 3 * 5120 * 12288 + 4 * (
        5120 * 160 + 2 * 23_592_960 + 1.5 * 23_592_960)
    assert work_mla.decode_attention_bytes([100, 28], M) == 128 * 1152 * 5
    assert work_mla.decode_attention_flops([1000], M) \
        == 1000 * 2 * 128 * 1088 * 5
    assert work_mla.decode_token_flops(8192, M) \
        == 2 * (blocks + head) + 8192 * 2 * 128 * 1088 * 5
    # a 512-token chunk at 8k: about 1.17 TFLOP of absorbed attention a
    # layer, and the block matmuls of 512 tokens
    chunk = work_mla.prefill_chunk_flops(8192, 512, M, last_chunk=True)
    attended = 512 * 8192 + 512 * 513 // 2
    assert chunk == 2 * blocks * 512 + attended * 278_528 * 5 + 2 * head
    assert 1.1e12 < attended * 278_528 < 1.3e12
    # the expert kernel's work is what the program counted: pairs
    # through three matrices, each reached expert's weights read once;
    # 32 decode tokens routed uniformly would reach 28 of the 40
    flops, bytes_ = work_mla.expert_work(48, 28, M)
    assert flops == 2 * 48 * 23_592_960 and bytes_ == 28 * 23_592_960 * 2
    assert abs(work_mla.experts_touched(32, M)
               - 40 * (1 - (39 / 40) ** 48)) < 1e-9
    assert 27 < work_mla.experts_touched(32, M) < 29


def test_configuration_file_keeps_the_published_numbers():
    cfg = harness.load_json(harness.HERE, "configs", "deepseek-v2.json")
    published = {"hidden_size": 5120, "intermediate_size": 12288,
                 "moe_intermediate_size": 1536, "kv_lora_rank": 512,
                 "q_lora_rank": 1536, "qk_nope_head_dim": 128,
                 "qk_rope_head_dim": 64, "v_head_dim": 128,
                 "num_attention_heads": 128, "num_experts_per_tok": 6,
                 "n_group": 8, "topk_group": 3, "n_shared_experts": 2,
                 "routed_scaling_factor": 16, "num_hidden_layers": 60,
                 "first_k_dense_replace": 1, "max_position_embeddings": 163840}
    for key, value in published.items():
        assert cfg[key] == value and M[key] == value, key
    assert "model" not in cfg       # one copy, where the contract reads
    assert cfg["reduced"] == ["num_layers", "n_routed_experts", "vocab_size"]
    assert (cfg["num_layers"], cfg["n_routed_experts"], cfg["vocab_size"]) \
        == (5, 40, 25600)
    assert cfg["published"] == {"num_hidden_layers": 60,
                                "n_routed_experts": 160, "vocab_size": 102400}
    # the router still scores all 160 in 8 groups; 40 are held
    assert M["router_experts"] == 160 and M["held_experts"] == (0, 40)
    assert cfg["rope_scaling"]["factor"] == 40


def test_traffic_is_as_the_issue_names_it():
    t = harness.load_json(harness.HERE, "traffic", "serve-docs.json")
    assert t["shared_prefix"] == {"count": 24, "len": 8192,
                                  "popularity": "1/rank"}
    assert t["prompt_len"] == {"dist": "uniform", "min": 8224, "max": 8704}
    assert t["output_len"] == {"dist": "lognormal", "median": 128,
                               "sigma": 0.6, "min": 32, "max": 384}
    assert t["arrivals"]["kind"] == "poisson" and t["at_close"] == "stop"
    assert t["schedule_seed"] == 20251001 and t["driver"] == "serve_mla"
    cell = harness.load_json(harness.HERE, "cells",
                             "deepseek-v2.serve-docs.json")
    assert cell["engine"] == {"max_slots": 32, "block_size": 16,
                              "prefill_chunk": 512, "weights": "native",
                              "max_model_len": 9216, "kv_memory_mb": 2048}
