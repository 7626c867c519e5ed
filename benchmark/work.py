"""Operations and bytes that the algorithm needs, from shapes and from
the traffic — never from a kernel's grid or from what the compiler
emitted. Recomputation is not counted.
"""


def matmul_params(L, d, ffn, vocab):
    """(block matmul parameters, head parameters): what a token's
    forward multiplies by. The embedding lookups are not matmuls."""
    return L * (d * 3 * d + d * d + 2 * d * ffn), vocab * d


def train_flops_per_token(n_params, L, d, seq_len):
    """6N + 12·L·d·S: forward and backward over N parameters plus the
    attention score and value contractions at the full (not the causal
    half) sequence, the convention of the PaLM appendix and of
    `telemetry/mfu.py:model_flops_per_token`."""
    return 6 * n_params + 12 * L * d * seq_len


def flash_flops(batch, seq_len, L, d, causal=True, backward=True):
    """Attention kernels of one training step. Forward: QK^T and PV,
    2·2·B·S²·d per layer; backward: dQ, dK, dV and the recomputed
    scores are what the algorithm needs, 2.5x the forward (FlashAttention
    2, section 3.1 counts 5 matmuls against 2). Causal masks half."""
    fwd = 4 * batch * seq_len * seq_len * d * L
    total = fwd * (3.5 if backward else 1.0)
    return total * (0.5 if causal else 1.0)


def decode_attention_bytes(context_lens, L, d, kv_bytes=2):
    """Bytes a decode step has to read: K and V of every context
    position of every decoding slot, in every layer."""
    return sum(int(c) for c in context_lens) * 2 * L * d * kv_bytes


def decode_attention_flops(context_lens, L, d):
    return sum(int(c) for c in context_lens) * 4 * d * L


def decode_token_flops(ctx, L, d, ffn, vocab):
    blocks, head = matmul_params(L, d, ffn, vocab)
    return 2 * (blocks + head) + 4 * int(ctx) * d * L


def prefill_chunk_flops(p0, n_real, L, d, ffn, vocab, last_chunk):
    """A chunk of n_real prompt tokens at positions p0..: the block
    matmuls for each, causal attention over what precedes each, and the
    head once where the chunk ends the prompt."""
    blocks, head = matmul_params(L, d, ffn, vocab)
    attended = n_real * p0 + n_real * (n_real + 1) // 2
    return (2 * blocks * n_real + 4 * attended * d * L
            + (2 * head if last_chunk else 0))


def roofline_share(flops, bytes_, seconds, peak_flops, peak_bytes):
    """(share in %, which bound): the least time the chip could take
    over the time taken."""
    t_f, t_b = flops / peak_flops, bytes_ / peak_bytes
    return 100.0 * max(t_f, t_b) / seconds, \
        "compute" if t_f >= t_b else "memory"
