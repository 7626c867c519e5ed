"""FLOPs estimation (reference `python/paddle/hapi/dynamic_flops.py`):
per-layer multiply-add counts via hooked dry-run forward."""
import numpy as np

from ..core.tensor import Tensor
from ..core import autograd


def _linear_flops(layer, inp, out):
    return int(np.prod(inp.shape)) * layer.weight.shape[-1]


def _conv_flops(layer, inp, out):
    kh_kw_cin = int(np.prod(layer.weight.shape[1:]))
    return int(np.prod(out.shape)) * kh_kw_cin


def flops(net, input_size, custom_ops=None, print_detail=False):
    """Total forward multiply-accumulate count for one input of
    `input_size`."""
    from ..nn.layer.layers import Layer
    from ..nn import Linear, Conv2D

    custom_ops = custom_ops or {}
    total = [0]
    hooks = []

    def make_hook(layer):
        def hook(lyr, inp, out):
            inp0 = inp[0] if isinstance(inp, (list, tuple)) else inp
            out0 = out[0] if isinstance(out, (list, tuple)) else out
            fn = custom_ops.get(type(lyr))
            if fn is not None:
                total[0] += int(fn(lyr, inp0, out0))
            elif isinstance(lyr, Linear):
                total[0] += _linear_flops(lyr, inp0, out0)
            elif isinstance(lyr, Conv2D):
                total[0] += _conv_flops(lyr, inp0, out0)
        return hook

    for _, layer in net.named_sublayers():
        if not list(layer.children()):
            hooks.append(layer.register_forward_post_hook(make_hook(layer)))

    shape = tuple(1 if d in (None, -1) else int(d) for d in input_size)
    x = Tensor(np.random.rand(*shape).astype(np.float32))
    was_training = net.training
    net.eval()
    try:
        with autograd.no_grad():
            net(x)
    finally:
        if was_training:
            net.train()
        for h in hooks:
            h.remove()
    if print_detail:
        print(f"Total FLOPs (MACs): {total[0]:,}")
    return total[0]


def flops_compiled(net_or_fn, input_spec, backprop=False, net=None):
    """EXACT cost-model feedback from the compiled program: lower the
    forward (or the full backward when backprop=True) through XLA and
    read the compiler's own cost analysis — flops and bytes accessed.
    This is the feedback loop the hook-based estimate above cannot give
    (fusion, rematerialization, and backward costs are all invisible to
    layer hooks). Returns {"flops": float, "bytes_accessed": float}.

    backprop=True differentiates w.r.t. the inputs AND the model
    parameters (pass `net` when net_or_fn is a plain function closing
    over a Layer; when net_or_fn IS a Layer its own parameters are
    used) — otherwise the dL/dW contractions, about half of real
    backward cost, would be invisible closure constants.

    input_spec: list of example arrays / Tensors / (shape, dtype).
    """
    import jax
    import jax.numpy as jnp
    from ..nn.layer.layers import Layer
    from ..jit import bind_tensors

    examples = []
    for spec in input_spec:
        if isinstance(spec, Tensor):
            examples.append(spec._value)
        elif isinstance(spec, tuple) and len(spec) == 2 and \
                isinstance(spec[0], (list, tuple)):
            examples.append(jnp.zeros(spec[0], spec[1]))
        else:
            examples.append(jnp.asarray(np.asarray(spec)))

    layer = net if net is not None else (
        net_or_fn if isinstance(net_or_fn, Layer) else None)
    params = list(layer.parameters()) if layer is not None else []
    param_vals = [p._value for p in params]

    def fwd(pvals, *vals):
        with autograd.no_grad(), bind_tensors(params, pvals):
            out = net_or_fn(*[Tensor(v) for v in vals])
        outs = out if isinstance(out, (tuple, list)) else [out]
        return sum(jnp.sum(o._value.astype(jnp.float32)) for o in outs)

    if backprop:
        fn = jax.grad(fwd, argnums=tuple(range(1 + len(examples))))
    else:
        fn = fwd
    comp = jax.jit(fn).lower(param_vals, *examples).compile()
    from ..cost_model import _cost_analysis
    ca = _cost_analysis(comp)
    return {"flops": float(ca.get("flops", 0.0)),
            "bytes_accessed": float(ca.get("bytes accessed", 0.0))}
