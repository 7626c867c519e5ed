"""`telemetry.scope`: the program's names for its device work.

`telemetry.span` times the host and exists while the program runs. A
scope names device work and exists only while a program is TRACED: it
is `jax.named_scope` and nothing else, changes no op, and costs a
compiled program nothing when it runs. Every op traced inside
`with scope("mlp"):` carries `pt.mlp` in its name stack, which the
compiler keeps in the op's metadata (`op_name`) and the profiler in the
op's event (`tf_op`), so a device trace can give each op's time to the
model layer that asked for it (benchmark/readers/device_scope.py). The
innermost `pt.` name is the op's owner; the `pt.` prefix tells a scope
from a function's name in a name stack (`jit(_take)`).

A scope is opened once, where a layer is entered, not around single
ops; a pre-norm and a residual add belong to the half-block they feed,
the final norm to `head`. The backward of what was traced under a
scope bears the same name: the tape (`autograd.Node.scope`,
`autograd.reopened`) puts a node's pull-back under the name stack its
forward was recorded in, so no model and no op knows of it.

The definition lives here, below the models (which may not import
`telemetry`: tests/test_layering.py); `paddle_tpu.telemetry.scope` is
this function.
"""
import jax

# the whole vocabulary: the layers of a model as a device trace's
# readers know them
SCOPES = frozenset((
    "embed",        # the embedding lookup (and learned positions)
    "attn",         # projections, rotary, cache writes, the kernel call
    "mlp",          # dense and gated MLPs, the shared experts
    "experts",      # router, grouping, moe_grouped_ffn
    "ssm",          # a Mamba-2 layer: projections, convolution, scan
    "linear",       # a delta-rule layer: projections, convolution, the
                    # kernel call, the gated norm
    "head",         # the final norm and the vocabulary projection
    "loss",         # cross-entropy and its reduction
    "optimizer",    # clip, the AdamW update, the casts back
    "sample",       # the serving step's token selection
    "cast",         # the serving step's cast of its parameters
))


def scope(name):
    """`with scope(name):` around the code of one model layer; `name`
    is one of `SCOPES` (anything else raises, when the program is
    traced). Use it as a context manager, not as a decorator: one
    decorator's context would be shared by every thread that traces."""
    if name not in SCOPES:
        raise ValueError(f"telemetry.scope: {name!r} is not one of "
                         f"{sorted(SCOPES)}")
    return jax.named_scope("pt." + name)
