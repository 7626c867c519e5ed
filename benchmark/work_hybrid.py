"""Operations and bytes of the Granite-4.0-H serving step (Mamba-2 layers
among grouped-query attention layers, a gated MLP in every block), from
shapes and from the traffic, as `work.py` counts GPT's: whatever
implements them. `m` is the reference's `sizes(config)`.

The recurrent state of a request is `d_state x d_inner` float32 numbers
a Mamba-2 layer. A decode step reads and writes all of it for every live
request; the recurrence needs 5 operations a number (decay times state,
B times dt*x, their sum; times C, summed), under one a byte. The chunked
scan needs, for a piece of l tokens, the causal half of C B^T and of its
product with X, the incoming state against C and the piece against B.
"""


def mamba_layers(m):
    return sum(1 for t in m["layer_types"] if t == "mamba")


def attention_layers(m):
    return sum(1 for t in m["layer_types"] if t == "attention")


def mamba_params(m):
    """in_proj and out_proj: what a token multiplies by in the mixer."""
    d, di = m["hidden_size"], m["d_inner"]
    return d * (di + m["conv_dim"] + m["mamba_n_heads"]) + di * d


def attention_params(m):
    d = m["hidden_size"]
    q = m["num_attention_heads"] * m["head_dim"]
    kv = m["num_key_value_heads"] * m["head_dim"]
    return d * q + 2 * d * kv + q * d


def mlp_params(m):
    return 3 * m["hidden_size"] * m["shared_intermediate_size"]


def matmul_params(m):
    """(block matmul parameters a token multiplies by, head
    parameters). The embedding lookup is no matmul; the head is the
    embedding matrix again (tied)."""
    blocks = mamba_layers(m) * mamba_params(m) \
        + attention_layers(m) * attention_params(m) \
        + m["num_hidden_layers"] * mlp_params(m)
    return blocks, m["hidden_size"] * m["vocab_size"]


def state_numbers(m):
    """Numbers of one request's state in one Mamba-2 layer."""
    return m["mamba_d_state"] * m["d_inner"]


def state_step_flops(rows, m):
    return 5 * int(rows) * state_numbers(m) * mamba_layers(m)


def state_step_bytes(rows, m, state_bytes=4):
    """Every live row's state read and written once, in every layer."""
    return 2 * int(rows) * state_numbers(m) * state_bytes * mamba_layers(m)


def _pieces(n_real, m):
    L = m["mamba_chunk_size"]
    return [min(L, n_real - at) for at in range(0, n_real, L)]


def chunk_scan_flops(n_real, m):
    """A chunk of `n_real` tokens through every Mamba-2 layer."""
    N, di = m["mamba_d_state"], m["d_inner"]
    total = 0
    for l in _pieces(int(n_real), m):
        half = l * (l + 1) // 2
        total += 2 * half * N + 2 * half * di + 2 * 2 * l * N * di
    return total * mamba_layers(m)


def chunk_scan_bytes(n_real, m, act_bytes=2, state_bytes=4):
    """x, B and C in, y out, the state in and out, a layer."""
    n = int(n_real)
    per_layer = n * (m["d_inner"] + 2 * m["mamba_d_state"]) * act_bytes \
        + n * m["d_inner"] * 4 + 2 * state_numbers(m) * state_bytes
    return per_layer * mamba_layers(m)


def kv_row_numbers(m):
    """Numbers of a cached K (or V) row in an attention layer."""
    return m["num_key_value_heads"] * m["head_dim"]


def attention_flops_per_row(m):
    """One query token against one cached position, all query heads, a
    layer: the score and the weighted value."""
    return 4 * m["num_attention_heads"] * m["head_dim"]


def decode_attention_flops(context_lens, m):
    return sum(int(c) for c in context_lens) * attention_flops_per_row(m) \
        * attention_layers(m)


def decode_attention_bytes(context_lens, m, kv_bytes=2):
    """K and V of every context position of every decoding slot, in
    every attention layer, at the K/V heads' width."""
    return sum(int(c) for c in context_lens) * 2 * kv_row_numbers(m) \
        * kv_bytes * attention_layers(m)


def decode_token_flops(ctx, m):
    blocks, head = matmul_params(m)
    return 2 * (blocks + head) + state_step_flops(1, m) \
        + int(ctx) * attention_flops_per_row(m) * attention_layers(m)


def prefill_chunk_flops(p0, n_real, m, last_chunk):
    """n_real prompt tokens at positions p0..: the block matmuls for
    each, the scan, causal attention over what precedes each, the head
    once where the chunk ends the prompt."""
    blocks, head = matmul_params(m)
    attended = n_real * p0 + n_real * (n_real + 1) // 2
    return (2 * blocks * n_real + chunk_scan_flops(n_real, m)
            + attended * attention_flops_per_row(m) * attention_layers(m)
            + (2 * head if last_chunk else 0))
