"""Weight-only int8 — the LLM decode bandwidth lever.

Autoregressive decode is WEIGHT-bandwidth-bound (each generated token
re-reads every matmul weight; activations are tiny), so storing Linear
weights as int8 + per-output-channel scales halves the HBM bytes per
step while activations and accumulation stay bf16/f32 — unlike the
act+weight Int8Linear path (`ptq.py`), no activation calibration is
needed and there is no activation-quantization error.

Reference analog: `contrib/slim` weight-quantize utilities
(`post_training_quantization.py` weight_quantize path); the
serving-world name for this recipe is "weight-only int8" (W8A16).

Usage:
    model = GPTForPretraining(cfg)
    model.set_state_dict(...)                  # trained weights
    quantize_weights_int8(model)               # in-place Linear swap
    out, _ = model.generate(ids, max_new_tokens=...)
"""
import numpy as np
import jax.numpy as jnp

from .. import nn
from ..core.tensor import Tensor, apply

__all__ = ["WeightOnlyInt8Linear", "WeightOnlyInt8Embedding",
           "quantize_weights_int8", "quantize_for_decode",
           "channelwise_int8"]


def channelwise_int8(w, bits=8):
    """Per-OUTPUT-channel symmetric int8: returns (wq int8, scale f32)
    with w ~= wq * scale. Shared by the weight-only path here and the
    act+weight Int8Linear in ptq.py."""
    qmax = 2.0 ** (bits - 1) - 1
    scale = np.maximum(np.max(np.abs(w), axis=0), 1e-8) / qmax   # [out]
    wq = np.clip(np.round(w / scale), -qmax, qmax).astype(np.int8)
    return wq, scale.astype(np.float32)


class WeightOnlyInt8Linear(nn.Layer):
    """Drop-in Linear replacement: w int8 [in, out] + f32 scale [out];
    forward dequantizes IN VMEM after the 1-byte-per-weight HBM read
    (the cast + scale fuse into the matmul's epilogue under XLA).
    wq/w_scale are persistable BUFFERS so state_dict round-trips the
    quantized weights (save-after-quantize serving flow)."""

    def __init__(self, layer, bits=8):
        super().__init__()
        wq, ws = channelwise_int8(layer.weight.numpy(), bits)
        self.register_buffer("w_scale", Tensor(jnp.asarray(ws)),
                             persistable=True)
        self.register_buffer("wq", Tensor(jnp.asarray(wq)),
                             persistable=True)
        self.bias = layer.bias

    def forward(self, x):
        def fn(xv, wq, ws, *maybe_bias):
            # int8 -> activation dtype in VMEM; bf16 MXU matmul; scale
            # per out-channel in the epilogue
            out = jnp.matmul(xv, wq.astype(xv.dtype))
            out = out * ws.astype(xv.dtype)
            if maybe_bias:
                out = out + maybe_bias[0].astype(out.dtype)
            return out
        args = (x, self.wq, self.w_scale) + (
            (self.bias,) if self.bias is not None else ())
        return apply(fn, *args)


class WeightOnlyInt8Embedding(nn.Layer):
    """Embedding with int8 rows + per-ROW f32 scales. One quantization
    serves BOTH uses of a tied LM-head table: the lookup dequantizes the
    gathered rows, and the vocab projection's out-channels ARE the rows,
    so the head matmul reads the same int8 table and applies the scale
    in its epilogue (see GPTForPretraining.forward's quantized branch —
    scaling AFTER the contraction avoids materializing a dequantized
    [V, H] temp)."""

    @property
    def _HEAD_BLOCK(self):
        # single source of truth: the pad target IS the kernel block
        from ..ops.pallas_int8 import _BLOCK_V
        return _BLOCK_V

    def __init__(self, layer, bits=8):
        super().__init__()
        w = layer.weight.numpy()                     # [V, H]
        wq_t, ws = channelwise_int8(w.T, bits)       # per-ROW of w
        wq, V = wq_t.T, w.shape[0]
        # pad rows to the pallas head-kernel block once at quantize
        # time (scale 0 on pad rows; head consumers slice to true V)
        pad = (-V) % self._HEAD_BLOCK
        if pad:
            wq = np.concatenate(
                [wq, np.zeros((pad, w.shape[1]), np.int8)], axis=0)
            ws = np.concatenate([ws, np.zeros((pad,), np.float32)])
        self.num_embeddings = V
        self.register_buffer("wq", Tensor(jnp.asarray(wq)),
                             persistable=True)       # int8 [Vp, H]
        self.register_buffer("w_scale", Tensor(jnp.asarray(ws)),
                             persistable=True)       # f32 [Vp]
        self._padding_idx = getattr(layer, "_padding_idx", None)

    def forward(self, x):
        pad = self._padding_idx
        n_real = self.num_embeddings

        def fn(ids, wq, ws):
            # dequantize into the SCALE's dtype: generation's
            # _cast_params casts the float scale buffer to the decode
            # compute dtype (bf16), so the rows enter the stack in the
            # same dtype an unquantized embedding would — emitting f32
            # here would silently downgrade the whole bf16 decode
            # clip to the TRUE vocab (not the padded table): an
            # out-of-range id must keep mapping to the last real row,
            # not to a zero-scale pad row
            ids = jnp.clip(ids, 0, n_real - 1)
            rows = wq[ids].astype(ws.dtype) * ws[ids][..., None]
            if pad is not None:
                # F.embedding masks the padding row at LOOKUP time (the
                # stored row can drift); mirror it
                rows = jnp.where((ids == pad)[..., None],
                                 jnp.zeros((), rows.dtype), rows)
            return rows
        from ..core.tensor import apply as _apply
        from ..tensor._helpers import ensure_tensor
        return _apply(fn, ensure_tensor(x), self.wq, self.w_scale)


def _holds_wo8(layer):
    for child in layer._sub_layers.values():
        if isinstance(child, (WeightOnlyInt8Linear, WeightOnlyInt8Embedding)):
            return True
        if _holds_wo8(child):
            return True
    return False


def quantize_for_decode(model, bits=8, min_features=0):
    """THE weight-only-int8 entry for decode consumers — the serving
    engine's `weights="wo8"` mode and every other decode caller share
    this one implementation. Thin discipline over `quantize_weights_int8`:

    - idempotent: an already-quantized model is a no-op (returns 0),
      so an engine built over a pre-quantized checkpoint doesn't
      double-quantize (which would quantize the int8 *scales*);
    - loud: a model with NOTHING to quantize raises instead of
      silently serving fp weights under a "wo8" label.

    Returns the number of swapped layers."""
    if _holds_wo8(model):
        return 0
    swapped = quantize_weights_int8(model, bits=bits,
                                    min_features=min_features)
    if swapped == 0:
        raise ValueError(
            "quantize_for_decode: model holds no quantizable nn.Linear "
            "layers — refusing to serve full-precision weights as wo8")
    return swapped


def quantize_weights_int8(layer, bits=8, min_features=0,
                          embeddings=False):
    """Walk the layer tree replacing every nn.Linear with a
    WeightOnlyInt8Linear in place (norms are untouched). With
    embeddings=True, nn.Embedding tables are also quantized per-row —
    including a tied LM-head table, whose vocab projection then reads
    int8 (GPT's head path detects the quantized wte). NOTE measured on
    v5e (GPT-125M decode, bf16 11.8k tok/s, linears-only 15.9-18.8k):
    embeddings=True is SLOWER than bf16 for the head even through the
    dedicated pallas int8 matvec (11.1k; the XLA einsum materializes a
    dequantized [V, H] copy and is worse still at 10.8k) — at decode
    sizes the per-step kernel overhead eats the 39MB-vs-77MB read
    saving. Default False; memory-constrained serving may still want
    the ~2x smaller table, and the pallas head is its best-known path
    (ops/pallas_int8.py). min_features skips small
    projections whose bandwidth doesn't matter. Returns the count of
    swapped layers."""
    swapped = 0
    for name, child in list(layer._sub_layers.items()):
        if isinstance(child, nn.Linear):
            w = child.weight
            if min(w.shape) >= min_features:
                layer._sub_layers[name] = WeightOnlyInt8Linear(child, bits)
                swapped += 1
        elif embeddings and isinstance(child, nn.Embedding):
            if min(child.weight.shape) >= min_features:
                layer._sub_layers[name] = WeightOnlyInt8Embedding(child,
                                                                  bits)
                swapped += 1
        else:
            swapped += quantize_weights_int8(child, bits, min_features,
                                             embeddings)
    return swapped
