"""Operations and bytes of the Qwen3-Next serving step (gated delta-rule
linear layers, three to each gated full-attention layer; an expert layer
with a gated shared expert in every block), from shapes and from the
traffic, as `work.py` counts GPT's: whatever implements them. `m` is the
reference's `sizes(config)`: the share as run.

By symmetry a token's `num_experts_per_tok` chosen experts fall on the
held experts in proportion to their number, so a token has
k * held / router_experts of them here (1.25 for 64 of 512 at k = 10).
The whole step's count takes that expectation; the expert kernel's own
work (`expert_work`) is what the program counted in the run.

A linear layer keeps a float32 state of `heads x K x V` numbers a
request. A decode step reads and writes all of it for every live
request, with 7 operations a number (the decay, S^T k, the rank-1
write, S^T q), under one a byte. The chunked form, for a sub-chunk of l
real positions a head: K K^T below the diagonal and Q K^T on and below
it, the triangular solve for the chunk's writes D, the incoming state
against K and Q, the outputs from D, and the state passed on.
"""

SUB_CHUNK = 64      # positions of a sub-chunk of `gdn_chunk`


def linear_layers(m):
    return sum(1 for t in m["layer_types"] if t == "linear_attention")


def full_layers(m):
    return sum(1 for t in m["layer_types"] if t == "full_attention")


def held_per_token(m):
    return m["num_experts_per_tok"] * m["held_experts"][1] \
        / m["router_experts"]


def _heads(m):
    return (m["linear_num_key_heads"], m["linear_num_value_heads"],
            m["linear_key_head_dim"], m["linear_value_head_dim"])


def linear_params(m):
    """in_proj_qkvz, in_proj_ba and out_proj: what a token multiplies
    by in a linear mixer."""
    d = m["hidden_size"]
    Hk, Hv, K, V = _heads(m)
    return d * (2 * Hk * K + 2 * Hv * V) + d * 2 * Hv + Hv * V * d


def attention_params(m):
    d, H = m["hidden_size"], m["head_dim"]
    N, Nk = m["num_attention_heads"], m["num_key_value_heads"]
    return d * N * 2 * H + 2 * d * Nk * H + N * H * d


def expert_params(m):
    return 3 * m["hidden_size"] * m["moe_intermediate_size"]


def shared_params(m):
    """The shared expert and its gate."""
    d = m["hidden_size"]
    return 3 * d * m["shared_expert_intermediate_size"] + d


def matmul_params(m):
    """(block matmul parameters a token multiplies by, head
    parameters). The embedding lookup is no matmul."""
    d = m["hidden_size"]
    blocks = linear_layers(m) * linear_params(m) \
        + full_layers(m) * attention_params(m) \
        + m["num_layers"] * (d * m["router_experts"] + shared_params(m)
                             + held_per_token(m) * expert_params(m))
    return blocks, d * m["vocab_size"]


def state_numbers(m):
    """Numbers of one request's state in one linear layer."""
    _, Hv, K, V = _heads(m)
    return Hv * K * V


def state_step_flops(rows, m):
    return 7 * int(rows) * state_numbers(m) * linear_layers(m)


def state_step_bytes(rows, m, state_bytes=4, act_bytes=4):
    """Every live row's state read and written once, and its q, k, v,
    gates and output, in every linear layer."""
    Hk, Hv, K, V = _heads(m)
    per_row = 2 * state_numbers(m) * state_bytes \
        + (2 * Hk * K + 2 * Hv * V + 2 * Hv) * act_bytes
    return int(rows) * per_row * linear_layers(m)


def _subs(n_real):
    return [min(SUB_CHUNK, n_real - at) for at in range(0, n_real, SUB_CHUNK)]


def chunk_flops(n_real, m):
    """A chunk of `n_real` tokens through every linear layer."""
    _, Hv, K, V = _heads(m)
    total = 0
    for l in _subs(int(n_real)):
        below, on = l * (l - 1) // 2, l * (l + 1) // 2
        total += (2 * below * K             # K K^T below the diagonal
                  + 2 * below * V           # the triangular solve for D
                  + 2 * on * K              # Q K^T on and below it
                  + 2 * on * V              # (Q K^T o E) D
                  + 3 * 2 * l * K * V       # K S0, Q S0, K^T D
                  + K * V)                  # the decay of the state
    return total * Hv * linear_layers(m)


def chunk_bytes(n_real, m, act_bytes=4, state_bytes=4):
    """q, k, v, the gates in and the output out, the state in and out,
    a linear layer."""
    n = int(n_real)
    Hk, Hv, K, V = _heads(m)
    per_layer = n * (2 * Hk * K + 2 * Hv * V + 2 * Hv) * act_bytes \
        + 2 * state_numbers(m) * state_bytes
    return per_layer * linear_layers(m)


def kv_row_numbers(m):
    """Numbers of a cached K (or V) row of a full layer."""
    return m["num_key_value_heads"] * m["head_dim"]


def attention_flops_per_row(m):
    """One query token against one cached position, all query heads, a
    full layer: the score and the weighted value."""
    return 4 * m["num_attention_heads"] * m["head_dim"]


def decode_attention_flops(context_lens, m):
    return sum(int(c) for c in context_lens) * attention_flops_per_row(m) \
        * full_layers(m)


def decode_attention_bytes(context_lens, m, kv_bytes=2):
    """K and V of every context position of every decoding slot, in
    every full layer."""
    return sum(int(c) for c in context_lens) * 2 * kv_row_numbers(m) \
        * kv_bytes * full_layers(m)


def decode_token_flops(ctx, m):
    blocks, head = matmul_params(m)
    return 2 * (blocks + head) + state_step_flops(1, m) \
        + int(ctx) * attention_flops_per_row(m) * full_layers(m)


def prefill_chunk_flops(p0, n_real, m, last_chunk):
    """n_real prompt tokens at positions p0..: the block matmuls for
    each, the chunked delta rule, causal attention over what precedes
    each in the full layers, the head once where the chunk ends the
    prompt."""
    blocks, head = matmul_params(m)
    attended = n_real * p0 + n_real * (n_real + 1) // 2
    return (2 * blocks * n_real + chunk_flops(n_real, m)
            + attended * attention_flops_per_row(m) * full_layers(m)
            + (2 * head if last_chunk else 0))


def experts_touched(tokens, m):
    """Held experts that `tokens` tokens reach in a layer if each of
    the tokens * held_per_token pairs fell on one of the held experts
    uniformly: what to expect of the program's own count."""
    held = m["held_experts"][1]
    pairs = tokens * held_per_token(m)
    return held * (1.0 - (1.0 - 1.0 / held) ** pairs)


def expert_work(pairs, reached, m, bytes_per=2):
    """(flops, bytes) of the routed experts' products as the program
    counted them: `pairs` token-expert pairs through three matrices,
    and the weights of the `reached` experts (summed over steps and
    layers) read once each."""
    return (2 * pairs * expert_params(m),
            reached * expert_params(m) * bytes_per)
