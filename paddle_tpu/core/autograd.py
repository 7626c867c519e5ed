"""Define-by-run autograd engine on JAX.

TPU-native replacement for the reference's imperative autograd
(`/root/reference/paddle/fluid/imperative/basic_engine.cc:39,251,379` BasicEngine
and `tracer.cc:146,235` grad-node recording). Instead of recording OpBase grad
nodes that later dispatch CUDA kernels, every eager op records a `jax.vjp`
closure on a thread-local tape; `Tensor.backward()` walks the tape in reverse
creation order (the tape is already topologically sorted, so no dep-counting
pass like PrepareDeps is needed) and accumulates cotangents.

The key TPU design win: all of this machinery runs at *trace time* under
`jax.jit`, so a whole train step (forward + backward + optimizer update)
compiles to a single fused XLA program — the reference needed a second world
(static graph + append_backward, `python/paddle/fluid/backward.py:1390`) to get
that; here eager and compiled are one code path.
"""
import contextlib
import threading

import jax
import jax.numpy as jnp
from jax.dtypes import float0
# JAX's name stack (what `jax.named_scope` extends) has no public reader
from jax._src import source_info_util as _names


class _AutogradState(threading.local):
    def __init__(self):
        self.grad_enabled = True
        self.nodes = []  # the tape, in op-creation (topological) order


_state = _AutogradState()


def grad_enabled():
    return _state.grad_enabled


@contextlib.contextmanager
def no_grad():
    """Analog of paddle.no_grad / dygraph no_grad (`fluid/dygraph/base.py`)."""
    prev = _state.grad_enabled
    _state.grad_enabled = False
    try:
        yield
    finally:
        _state.grad_enabled = prev


@contextlib.contextmanager
def enable_grad():
    prev = _state.grad_enabled
    _state.grad_enabled = True
    try:
        yield
    finally:
        _state.grad_enabled = prev


def set_grad_enabled(mode):
    prev = _state.grad_enabled
    _state.grad_enabled = not not mode
    return prev


class Node:
    """One recorded op: inputs, outputs, and its reverse rule.

    Analog of `imperative::OpBase` + GradOpNode (`imperative/op_base.h`) with
    the grad kernel replaced by a jax.vjp closure.

    Gradient routing is keyed by each tensor's `_key` — a fresh object per
    *value*, not per Tensor object — captured at record time. In-place ops
    (`__setitem__`, `increment`, `reshape_`) give the mutated tensor a fresh
    key, so cotangents for the pre- and post-mutation values route to the
    right producers (the reference tracks the same hazard with
    `TensorInplaceVersion`, `framework/tensor.h:77`).

    A node also keeps the name stack its forward was recorded under
    (`telemetry.scope`'s `pt.<layer>` among it): `jax.vjp` runs under
    the forward's scope, but the pull-back is called later, outside it,
    and its ops, two thirds of a train step's matmuls, would bear no
    layer's name. `reopened` puts the walk back under that stack.
    """

    __slots__ = ("inputs", "outputs", "vjp_fn", "multi_output",
                 "in_keys", "out_keys", "in_had_producer", "out_avals",
                 "scope")

    def __init__(self, inputs, outputs, vjp_fn, multi_output):
        self.inputs = inputs          # tuple[Tensor]
        self.outputs = outputs        # tuple[Tensor]
        self.vjp_fn = vjp_fn
        self.multi_output = multi_output
        self.in_keys = tuple(t._key for t in inputs)
        self.out_keys = tuple(o._key for o in outputs)
        self.in_had_producer = tuple(t._has_producer for t in inputs)
        # record-time output avals: a later in-place mutation (reshape_) can
        # change o._value's shape, but zero-cotangent fill must match the
        # shape this node actually produced
        self.out_avals = tuple((o._value.shape, o._value.dtype)
                               for o in outputs)
        self.scope = _names.current_name_stack()


_NO_SCOPE = contextlib.nullcontext()


def reopened(node):
    """The name stack `node` was recorded under, around its share of a
    reverse walk: the pull-back (a `custom_vjp`'s backward rule is
    traced by it) and the sums of its cotangents. Outside any scope,
    as all of eager mode is unless the caller opened one, nothing is
    entered."""
    return _names.set_name_stack(node.scope) if node.scope.stack \
        else _NO_SCOPE


def record(node):
    _state.nodes.append(node)
    for o in node.outputs:
        o._has_producer = True


def tape_size():
    return len(_state.nodes)


def current_tape():
    return _state.nodes


def truncate_tape(size):
    """Drop nodes recorded after `size` (a tape_size() snapshot)."""
    del _state.nodes[size:]


@contextlib.contextmanager
def fresh_tape():
    """Push a fresh tape (used when tracing a compiled step so recorded nodes
    never leak between trace-time and eager graphs)."""
    prev = _state.nodes
    _state.nodes = []
    try:
        yield
    finally:
        _state.nodes = prev


def clear_tape():
    _state.nodes.clear()


def _cotangent(node, pending):
    """The cotangent of `node`'s outputs as its pull-back takes it,
    popped from `pending`: zeros for an output nobody consumed."""
    cots = []
    for (shape, dtype), k in zip(node.out_avals, node.out_keys):
        c = pending.pop(k, None)
        if c is None:
            c = jnp.zeros(shape, dtype)
        elif c.dtype != dtype:
            # accumulation across mixed-dtype consumers promotes
            # (bf16 + f32 -> f32); jax.vjp requires the cotangent in
            # the output's own dtype
            c = c.astype(dtype)
        cots.append(c)
    return tuple(cots) if node.multi_output else cots[0]


def backward(tensor, grad=None, retain_graph=False):
    """Reverse-mode over the tape. Analog of BasicEngine::Execute
    (`imperative/basic_engine.cc:379`) + GradientAccumulator summation
    (`gradient_accumulator.cc`)."""
    backward_multi([tensor], [grad], retain_graph)


def backward_multi(tensors, grads=None, retain_graph=False):
    """One reverse walk with every root's cotangent seeded up front —
    shared subgraphs run each node's vjp once, not once per root
    (paddle.autograd.backward semantics)."""
    from .tensor import Tensor

    if grads is None:
        grads = [None] * len(tensors)

    # pending cotangents for non-leaf values, keyed by tape key (per-value
    # identity — survives in-place mutation of the Tensor object)
    pending = {}
    for tensor, grad in zip(tensors, grads):
        if grad is None:
            seed = jnp.ones_like(tensor._value)
        elif isinstance(grad, Tensor):
            seed = grad._value
        else:
            seed = jnp.asarray(grad, dtype=tensor._value.dtype)
        prev = pending.get(tensor._key)
        pending[tensor._key] = seed if prev is None else prev + seed
        if tensor._retain_grad or not tensor._has_producer:
            if not tensor.stop_gradient:
                tensor._accumulate_grad(seed)

    for node in reversed(_state.nodes):
        if not any(k in pending for k in node.out_keys):
            continue
        with reopened(node):
            in_grads = node.vjp_fn(_cotangent(node, pending))
            for inp, key, had_producer, g in zip(
                    node.inputs, node.in_keys, node.in_had_producer,
                    in_grads):
                if inp.stop_gradient or g.dtype == float0:
                    continue
                if had_producer:
                    prev = pending.get(key)
                    pending[key] = g if prev is None else prev + g
                    if inp._retain_grad:
                        inp._accumulate_grad(g)
                else:
                    # leaf: accumulate into .grad (paddle accumulates
                    # across backward() calls until clear_grad,
                    # varbase_patch_methods.py)
                    inp._accumulate_grad(g)

    if not retain_graph:
        clear_tape()


def grad(outputs, inputs, grad_outputs=None, retain_graph=False,
         create_graph=False, only_inputs=True, allow_unused=True,
         no_grad_vars=None):
    """Analog of paddle.grad (`imperative/partial_grad_engine.cc`,
    signature parity with `fluid/dygraph/base.py` grad): grads of
    outputs w.r.t. an explicit input list, without touching .grad
    fields. only_inputs=False is unsupported in the reference too;
    no_grad_vars blocks gradient flow through the listed tensors."""
    from .tensor import Tensor

    if not only_inputs:
        raise AssertionError(
            "only_inputs=False is not supported (the reference's "
            "partial-grad engine asserts the same)")
    if isinstance(no_grad_vars, Tensor):
        no_grad_vars = [no_grad_vars]
    blocked = {id(t) for t in (no_grad_vars or [])}
    if isinstance(outputs, Tensor):
        outputs = [outputs]
    if isinstance(inputs, Tensor):
        inputs = [inputs]
    if grad_outputs is None:
        grad_outputs = [None] * len(outputs)
    elif isinstance(grad_outputs, Tensor):
        grad_outputs = [grad_outputs]

    pending = {}
    for o, g in zip(outputs, grad_outputs):
        seed = jnp.ones_like(o._value) if g is None else (
            g._value if isinstance(g, Tensor) else jnp.asarray(g))
        prev = pending.get(o._key)
        pending[o._key] = seed if prev is None else prev + seed

    # wanted is keyed by Tensor OBJECT identity: grads are w.r.t. the input
    # tensor as the graph consumed it, even if it was mutated in-place after
    # the forward pass
    wanted = {id(t): i for i, t in enumerate(inputs)}
    results = [None] * len(inputs)

    def _stash(obj_id, g):
        i = wanted.get(obj_id)
        if i is not None:
            results[i] = g if results[i] is None else results[i] + g

    for o in outputs:
        if id(o) in wanted:
            _stash(id(o), pending[o._key])

    for node in reversed(_state.nodes):
        if not any(k in pending for k in node.out_keys):
            continue
        with reopened(node):
            in_grads = node.vjp_fn(_cotangent(node, pending))
            for inp, key, had_producer, g in zip(
                    node.inputs, node.in_keys, node.in_had_producer,
                    in_grads):
                if inp.stop_gradient or g.dtype == float0:
                    continue
                if id(inp) in blocked:
                    continue  # no_grad_vars: gradient does not flow through
                if had_producer:
                    prev = pending.get(key)
                    pending[key] = g if prev is None else prev + g
                _stash(id(inp), g)

    if not retain_graph:
        clear_tape()

    out = []
    for i, t in enumerate(inputs):
        if results[i] is None:
            if not allow_unused:
                raise RuntimeError(f"input {i} unused in the graph")
            out.append(None)
        else:
            out.append(Tensor(results[i], stop_gradient=True))
    return out
