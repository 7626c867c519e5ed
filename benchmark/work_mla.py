"""Operations and bytes of the DeepSeek-V2 serving step (multi-head
latent attention, absorbed; an expert layer with shared experts), from
shapes and from the traffic, as `work.py` counts GPT's. `m` is the
reference's `sizes(config)`: the share as run.

By symmetry a token's `num_experts_per_tok` chosen experts fall on the
held experts in proportion to their number, so a token has
k * held / router_experts of them here (1.5 for 40 of 160 at k = 6).
The whole step's count takes that expectation; the expert kernel's own
work (`expert_work`) is what the program counted in the run.
"""


def held_per_token(m):
    return m["num_experts_per_tok"] * m["held_experts"][1] \
        / m["router_experts"]


def attention_params(m):
    """q_a, q_b, kv_a, the two halves of kv_b (absorbed: one on the
    query, one on the output), and o."""
    d, H = m["hidden_size"], m["num_attention_heads"]
    qk = m["qk_nope_head_dim"] + m["qk_rope_head_dim"]
    rank = m["kv_lora_rank"]
    return (d * m["q_lora_rank"] + m["q_lora_rank"] * H * qk
            + d * (rank + m["qk_rope_head_dim"])
            + rank * H * (m["qk_nope_head_dim"] + m["v_head_dim"])
            + H * m["v_head_dim"] * d)


def expert_params(m):
    return 3 * m["hidden_size"] * m["moe_intermediate_size"]


def matmul_params(m):
    """(block matmul parameters a token multiplies by, head
    parameters). The embedding lookup is no matmul."""
    d = m["hidden_size"]
    dense = m["first_k_dense_replace"]
    moe = m["num_layers"] - dense
    blocks = m["num_layers"] * attention_params(m) \
        + dense * 3 * d * m["intermediate_size"] \
        + moe * (d * m["router_experts"]
                 + m["n_shared_experts"] * expert_params(m)
                 + held_per_token(m) * expert_params(m))
    return blocks, d * m["vocab_size"]


def row_numbers(m):
    """Numbers of a cached row: c_kv and the shared rotary key."""
    return m["kv_lora_rank"] + m["qk_rope_head_dim"]


def attention_flops_per_row(m):
    """One query token against one cached row, all heads, a layer: the
    score (a dot as wide as the row) and the weighted value (as wide as
    c_kv)."""
    return 2 * m["num_attention_heads"] * (row_numbers(m)
                                           + m["kv_lora_rank"])


def decode_attention_flops(context_lens, m):
    return sum(int(c) for c in context_lens) * attention_flops_per_row(m) \
        * m["num_layers"]


def decode_attention_bytes(context_lens, m, bytes_per=2):
    """Every cached row of every decoding slot once, in every layer."""
    return sum(int(c) for c in context_lens) * row_numbers(m) * bytes_per \
        * m["num_layers"]


def decode_token_flops(ctx, m):
    blocks, head = matmul_params(m)
    return 2 * (blocks + head) + int(ctx) * attention_flops_per_row(m) \
        * m["num_layers"]


def prefill_chunk_flops(p0, n_real, m, last_chunk):
    """n_real prompt tokens at positions p0..: the block matmuls for
    each, causal attention over what precedes each, the head once where
    the chunk ends the prompt."""
    blocks, head = matmul_params(m)
    attended = n_real * p0 + n_real * (n_real + 1) // 2
    return (2 * blocks * n_real
            + attended * attention_flops_per_row(m) * m["num_layers"]
            + (2 * head if last_chunk else 0))


def experts_touched(tokens, m):
    """Held experts that `tokens` tokens reach in a layer if each of
    the tokens * held_per_token pairs fell on one of the held experts
    uniformly: what to expect of the program's own count
    (`serving.moe_experts_reached`), which is what the roofline takes."""
    held = m["held_experts"][1]
    pairs = tokens * held_per_token(m)
    return held * (1.0 - (1.0 - 1.0 / held) ** pairs)


def expert_work(pairs, reached, m, bytes_per=2):
    """(flops, bytes) of the routed experts' products as the program
    counted them: `pairs` token-expert pairs through three matrices,
    and the weights of the `reached` experts (summed over steps and
    layers: those with at least one row) read once each."""
    return (2 * pairs * expert_params(m),
            reached * expert_params(m) * bytes_per)
