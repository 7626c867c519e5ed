"""paddle_tpu.ops — performance kernels (Pallas + fused XLA paths).

Analog of the reference's `operators/fused/` directory
(`fused_attention_op.cu`, `fmha_ref.h`, `fused_transformer_op.cu`), rebuilt
as Pallas TPU kernels + XLA-fused compositions.
"""
from .attention import scaled_dot_product_attention, flash_attention  # noqa: F401
