"""Operations and bytes of the K-EXAONE serving step (grouped-query
attention, three window layers to each full one; an expert layer behind
a sigmoid router with one shared expert), from shapes and from the
traffic, as `work.py` counts GPT's: whatever implements them. `m` is the
reference's `sizes(config)`: the share as run.

By symmetry a token's `num_experts_per_tok` chosen experts fall on the
held experts in proportion to their number, so a token has
k * held / router_experts of them here (1 for 16 of 128 at k = 8). The
whole step's count takes that expectation; the expert kernel's own work
(`expert_work`) is what the program counted in the run.

A full layer attends over every cached position of a request, a window
layer over at most `sliding_window` of them (the query's own included),
whatever the request's length.
"""


def full_layers(m):
    return sum(1 for t in m["layer_types"] if t == "full_attention")


def window_layers(m):
    return sum(1 for t in m["layer_types"] if t == "sliding_attention")


def sparse_layers(m):
    return sum(1 for t in m["mlp_layer_types"] if t == "sparse")


def held_per_token(m):
    return m["num_experts_per_tok"] * m["held_experts"][1] \
        / m["router_experts"]


def attention_params(m):
    d, H = m["hidden_size"], m["head_dim"]
    q, kv = m["num_attention_heads"] * H, m["num_key_value_heads"] * H
    return d * q + 2 * d * kv + q * d


def expert_params(m):
    return 3 * m["hidden_size"] * m["moe_intermediate_size"]


def matmul_params(m):
    """(block matmul parameters a token multiplies by, head
    parameters). The embedding lookup is no matmul."""
    d = m["hidden_size"]
    moe = sparse_layers(m)
    blocks = m["num_layers"] * attention_params(m) \
        + (m["num_layers"] - moe) * 3 * d * m["intermediate_size"] \
        + moe * (d * m["router_experts"]
                 + m["num_shared_experts"] * expert_params(m)
                 + held_per_token(m) * expert_params(m))
    return blocks, d * m["vocab_size"]


def kv_row_numbers(m):
    """Numbers of a cached K (or V) row."""
    return m["num_key_value_heads"] * m["head_dim"]


def attention_flops_per_row(m):
    """One query token against one visible cached position, all query
    heads, a layer: the score and the weighted value."""
    return 4 * m["num_attention_heads"] * m["head_dim"]


def window_rows(ctx, m):
    """Positions a window layer's query at context `ctx` (that many
    tokens cached, its own among them) attends over."""
    return min(int(ctx), m["sliding_window"])


def decode_attention_flops(context_lens, m):
    """The full layers' decode attention over these contexts."""
    return sum(int(c) for c in context_lens) * attention_flops_per_row(m) \
        * full_layers(m)


def decode_attention_bytes(context_lens, m, kv_bytes=2):
    """K and V of every context position of every decoding slot, in
    every full layer."""
    return sum(int(c) for c in context_lens) * 2 * kv_row_numbers(m) \
        * kv_bytes * full_layers(m)


def window_decode_flops(context_lens, m):
    return sum(window_rows(c, m) for c in context_lens) \
        * attention_flops_per_row(m) * window_layers(m)


def window_decode_bytes(context_lens, m, kv_bytes=2):
    """K and V of the ring rows a decoding slot attends over
    (`window_kv_rows` of the dispatch span), in every window layer."""
    return sum(window_rows(c, m) for c in context_lens) * 2 \
        * kv_row_numbers(m) * kv_bytes * window_layers(m)


def _window_attended(p0, n_real, m):
    """Query-key pairs of a chunk's `n_real` queries at p0.. in one
    window layer."""
    W = m["sliding_window"]
    return sum(min(p0 + i + 1, W) for i in range(int(n_real)))


def window_prefill_flops(p0, n_real, m):
    return _window_attended(p0, n_real, m) * attention_flops_per_row(m) \
        * window_layers(m)


def window_prefill_bytes(p0, n_real, m, act_bytes=2):
    """q in and the output out, the chunk's own K and V and the ring
    rows before it, a window layer."""
    q = m["num_attention_heads"] * m["head_dim"]
    ring = min(int(p0), m["sliding_window"] - 1)
    return (2 * int(n_real) * q
            + 2 * (int(n_real) + ring) * kv_row_numbers(m)) \
        * act_bytes * window_layers(m)


def decode_token_flops(ctx, m):
    blocks, head = matmul_params(m)
    return 2 * (blocks + head) + attention_flops_per_row(m) * (
        int(ctx) * full_layers(m) + window_rows(ctx, m) * window_layers(m))


def prefill_chunk_flops(p0, n_real, m, last_chunk):
    """n_real prompt tokens at positions p0..: the block matmuls for
    each, causal attention over what precedes each (all of it in a full
    layer, the window in a window layer), the head once where the chunk
    ends the prompt."""
    blocks, head = matmul_params(m)
    attended = n_real * p0 + n_real * (n_real + 1) // 2
    return (2 * blocks * n_real
            + attention_flops_per_row(m) * (
                attended * full_layers(m)
                + _window_attended(p0, n_real, m) * window_layers(m))
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
