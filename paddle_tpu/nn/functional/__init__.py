"""paddle_tpu.nn.functional — mirrors `python/paddle/nn/functional/`."""
from .activation import *  # noqa: F401,F403
from .common import *  # noqa: F401,F403
from .conv import (  # noqa: F401
    conv1d, conv2d, conv3d, conv1d_transpose, conv2d_transpose,
    conv3d_transpose,
)
from .norm import (  # noqa: F401
    layer_norm, batch_norm, instance_norm, group_norm, local_response_norm,
    fused_add_layer_norm, rms_norm,
)
from .pooling import (  # noqa: F401
    max_pool1d, max_pool2d, max_pool3d, avg_pool1d, avg_pool2d, avg_pool3d,
    adaptive_avg_pool1d, adaptive_avg_pool2d, adaptive_avg_pool3d,
    adaptive_max_pool1d, adaptive_max_pool2d, adaptive_max_pool3d,
    max_unpool2d,
)
from ...tensor.manipulation import diag_embed  # noqa: F401
from .loss import (  # noqa: F401
    cross_entropy, softmax_with_cross_entropy, nll_loss, mse_loss, l1_loss,
    smooth_l1_loss, binary_cross_entropy, binary_cross_entropy_with_logits,
    kl_div, margin_ranking_loss, hinge_embedding_loss, cosine_embedding_loss,
    triplet_margin_loss, square_error_cost, log_loss, sigmoid_focal_loss,
    dice_loss, hsigmoid_loss, margin_cross_entropy,
    ctc_loss, npair_loss,
)
from .vision import (  # noqa: F401
    pixel_shuffle, pixel_unshuffle, channel_shuffle, affine_grid, grid_sample,
    temporal_shift,
)


def sequence_mask(x, maxlen=None, dtype="int64", name=None):
    """Reference `operators/sequence_ops/sequence_mask_op.cc` — mask[i, j] =
    j < x[i]."""
    import jax.numpy as jnp
    from ...core.tensor import Tensor
    from ...core.dtype import convert_dtype
    from ...tensor._helpers import ensure_tensor
    x = ensure_tensor(x)
    v = x._value
    if maxlen is None:
        import numpy as np
        maxlen = int(np.asarray(v).max())
    elif isinstance(maxlen, Tensor):
        maxlen = int(maxlen.item())
    mask = jnp.arange(maxlen) < v[..., None]
    return Tensor(mask.astype(convert_dtype(dtype)))


def scaled_dot_product_attention(query, key, value, attn_mask=None,
                                 dropout_p=0.0, is_causal=False,
                                 training=True, name=None):
    """Fused attention entry point. Uses the Pallas flash-attention kernel on
    TPU when shapes allow (paddle_tpu.ops.flash_attention), else the XLA
    composed path. Layout: [batch, seqlen, num_heads, head_dim] (paddle
    convention)."""
    from ...ops.attention import scaled_dot_product_attention as sdpa
    return sdpa(query, key, value, attn_mask=attn_mask, dropout_p=dropout_p,
                is_causal=is_causal, training=training)
