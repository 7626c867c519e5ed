import pytest

from benchmark import weights, work


def test_flash_step_by_hand():
    """One training step of 2 rows x 1,024 tokens, 3 layers, d=256.
    Forward QK^T and PV: 2 matmuls x 2 flops x B x S x S x d
      = 4 * 2 * 1024 * 1024 * 256 = 2,147,483,648 per layer.
    Forward + backward = 3.5 x; causal = half; 3 layers."""
    per_layer_fwd = 4 * 2 * 1024 * 1024 * 256
    assert per_layer_fwd == 2_147_483_648
    want = per_layer_fwd * 3.5 * 0.5 * 3
    assert work.flash_flops(2, 1024, 3, 256) == pytest.approx(want)
    assert work.flash_flops(2, 1024, 3, 256, causal=False,
                            backward=False) == per_layer_fwd * 3


def test_paged_decode_step_by_hand():
    """Three slots at contexts 100, 200, 300; 2 layers, d=64, bf16.
    Bytes: 600 positions x K and V x 2 layers x 64 x 2 B = 307,200.
    Flops: 600 x 4 x 64 x 2 = 307,200 (QK^T and PV, 2 flops each)."""
    assert work.decode_attention_bytes([100, 200, 300], 2, 64) == 307_200
    assert work.decode_attention_flops([100, 200, 300], 2, 64) == 307_200
    share, bound = work.roofline_share(307_200, 307_200, 1e-6, 197e12, 819e9)
    assert bound == "memory"
    assert share == pytest.approx(100 * (307_200 / 819e9) / 1e-6)


def test_train_flops_per_token_is_6n_plus_attention():
    assert work.train_flops_per_token(1000, 2, 8, 16) == 6000 + 12 * 2 * 8 * 16


def test_serving_token_work():
    blocks, head = work.matmul_params(2, 8, 32, 100)
    assert blocks == 2 * (8 * 24 + 64 + 2 * 8 * 32) and head == 800
    assert work.decode_token_flops(10, 2, 8, 32, 100) == \
        2 * (blocks + head) + 4 * 10 * 8 * 2
    # a chunk of 3 tokens at positions 5, 6, 7 attends 6 + 7 + 8 keys
    assert work.prefill_chunk_flops(5, 3, 2, 8, 32, 100, True) == \
        2 * blocks * 3 + 4 * 21 * 8 * 2 + 2 * head


def test_parameter_count_of_the_published_sizes():
    from benchmark import harness
    small = harness.load_json(harness.HERE, "configs", "gpt3-125m.json")
    xl = harness.load_json(harness.HERE, "configs", "gpt3-1.3b.json")
    assert 124e6 < weights.count_params(small) < 126e6
    assert 1.3e9 < weights.count_params(xl) < 1.33e9
