"""`telemetry.scope` where the device's work is traced: every served
model's decode, greedy decode and prefill programs and the GPT train
step are compiled on the CPU at a toy size and read back from the
compiled HLO's `op_name` metadata, which is what a device trace keeps of
an op (`tf_op`). Every product, kernel call and sort has an owner among
`telemetry.SCOPES`; the backward bears its forward's name (the tape
re-opens it, core/autograd.py); the serving programs have names; and a
scope changes an op's metadata and nothing else."""
import contextlib
import re
import sys

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu import amp, optimizer, telemetry
from paddle_tpu.core import autograd
from paddle_tpu.core import scope as scope_mod
from paddle_tpu.serving import EngineConfig, ServingEngine

OWNED = ("dot", "convolution", "custom-call", "sort")
LINE = re.compile(r"^\s*(?:ROOT )?%?[\w.\-]+ = .*? ([a-z][\w\-]*)\(")


def ops_of(hlo):
    """[(opcode, op_name or None)] of every instruction of the text."""
    out = []
    for line in hlo.splitlines():
        m = LINE.match(line)
        if m:
            name = re.search(r'op_name="([^"]*)"', line)
            out.append((m.group(1), name.group(1) if name else None))
    return out


def owner(op_name):
    """The innermost `pt.` component of a name stack, or None."""
    found = re.findall(r"(?:^|/|\()pt\.(\w+)", op_name or "")
    return found[-1] if found else None


def stripped(hlo):
    """The text with what a scope may change taken out: every op's
    metadata and the module's tables of files, functions, locations and
    stack frames that the metadata points into."""
    hlo = re.sub(r"(?ms)^(FileNames|FunctionNames|FileLocations|"
                 r"StackFrames)\n.*?\n\n", "", hlo)
    return re.sub(r",? ?metadata=\{[^}]*\}", "", hlo)


# -- the served models at toy sizes -------------------------------------------

def _gpt():
    from paddle_tpu.models.gpt import GPTConfig, GPTForPretraining
    return GPTForPretraining(GPTConfig(
        vocab_size=256, hidden_size=64, num_layers=2, num_heads=4,
        max_seq_len=128, dropout=0.0))


def _deepseek():
    from paddle_tpu.models.deepseek_v2 import (DeepseekV2Config,
                                               DeepseekV2ForCausalLM)
    return DeepseekV2ForCausalLM(DeepseekV2Config(
        vocab_size=96, hidden_size=64, num_layers=2, num_attention_heads=4,
        q_lora_rank=32, kv_lora_rank=32, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, intermediate_size=128,
        moe_intermediate_size=32, n_routed_experts=16, n_shared_experts=2,
        num_experts_per_tok=3, n_group=4, topk_group=2,
        first_k_dense_replace=1, max_seq_len=128, dtype="float32",
        held=(0, 8)))


def _granite():
    from paddle_tpu.models.granite_hybrid import (GraniteHybridConfig,
                                                  GraniteHybridForCausalLM)
    return GraniteHybridForCausalLM(GraniteHybridConfig(
        vocab_size=96, hidden_size=32, layer_types=("mamba", "attention"),
        num_attention_heads=4, num_key_value_heads=2,
        shared_intermediate_size=64, mamba_n_heads=4, mamba_d_head=16,
        mamba_d_state=16, mamba_chunk_size=8, max_seq_len=128,
        dtype="float32"))


def _exaone():
    from paddle_tpu.models.exaone_moe import (ExaoneMoeConfig,
                                              ExaoneMoeForCausalLM)
    return ExaoneMoeForCausalLM(ExaoneMoeConfig(
        vocab_size=96, hidden_size=32,
        layer_types=("sliding_attention", "full_attention"),
        mlp_layer_types=("dense", "sparse"), num_attention_heads=4,
        num_key_value_heads=2, head_dim=8, sliding_window=8,
        intermediate_size=48, moe_intermediate_size=16, num_experts=8,
        num_experts_per_tok=2, max_seq_len=128, dtype="float32",
        held=(0, 4)))


def _qwen3_next():
    from paddle_tpu.models.qwen3_next import (Qwen3NextConfig,
                                              Qwen3NextForCausalLM)
    return Qwen3NextForCausalLM(Qwen3NextConfig(
        vocab_size=96, hidden_size=32,
        layer_types=("linear_attention", "full_attention"),
        num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        linear_num_key_heads=2, linear_num_value_heads=4,
        linear_key_head_dim=8, linear_value_head_dim=8,
        moe_intermediate_size=16, shared_expert_intermediate_size=16,
        num_experts=8, num_experts_per_tok=2, max_seq_len=128,
        dtype="float32", held=(0, 4)))


# model: (builder, engine options, the scopes its programs should hold
# beside embed / attn / mlp / head / sample)
SERVED = {
    "gpt": (_gpt, {"dtype": None}, set()),
    "gpt-wo8": (_gpt, {"weights": "wo8"}, {"cast"}),
    "deepseek-v2": (_deepseek, {"dtype": None}, {"experts"}),
    "granite-hybrid": (_granite, {"dtype": None}, {"ssm"}),
    "exaone-moe": (_exaone, {"dtype": None}, {"experts"}),
    "qwen3-next": (_qwen3_next, {"dtype": None}, {"experts", "linear"}),
}
PROGRAMS = ("decode", "decode_greedy", "prefill")
EVERYWHERE = {"embed", "attn", "mlp", "head", "sample"}


def _engine(name):
    build, options, _ = SERVED[name]
    paddle.seed(0)
    return ServingEngine(build(), config=EngineConfig(
        max_slots=2, block_size=8, prefill_chunk=16, max_model_len=64,
        **options))


def _traced(eng, program):
    """A serving program traced over arguments shaped as the engine's
    own dispatch shapes them."""
    S, mb = eng.cfg.max_slots, eng.max_blocks_per_seq
    head = (eng._param_vals(), eng.cache.k, eng.cache.v)
    if program == "prefill":
        args = head + (
            np.zeros((1, eng.cfg.prefill_chunk), np.int32), np.int32(0),
            np.int32(5), np.zeros((mb,), np.int32),
            np.zeros((2,), np.uint32), np.int32(0), np.float32(1),
            np.int32(0), np.float32(1), np.bool_(True))
        args += (np.int32(1),) if eng.rows else ()
        return eng._prefill_jit.trace(*args)
    args = head + (
        np.zeros((S,), np.int32), np.zeros((S,), np.int32),
        np.zeros((S, mb), np.int32), np.zeros((S, 2), np.uint32),
        np.zeros((S,), np.int32), np.ones((S,), np.float32),
        np.zeros((S,), np.int32), np.ones((S,), np.float32),
        np.ones((S,), np.bool_))
    args += (np.zeros((S,), np.int32),) if eng.rows else ()
    jitted = eng._decode_jit if program == "decode" \
        else eng._decode_greedy_jit
    return jitted.trace(*args)


def eqns_of(jaxpr, outer=""):
    """[(primitive, name stack)] of every equation of a jaxpr and of the
    jaxprs inside it, each stack read from the outermost program in."""
    from jax._src import core
    out = []
    for eqn in jaxpr.eqns:
        stack = f"{outer}/{eqn.source_info.name_stack}"
        out.append((eqn.primitive.name, stack))
        for sub in core.jaxprs_in_params(eqn.params):
            out += eqns_of(sub, stack)
    return out


# what must have an owner: products, convolutions, sorts and kernel calls
OWNED_PRIMS = ("dot_general", "ragged_dot_general", "conv_general_dilated",
               "sort", "top_k", "pallas_call", "cumsum", "argmax")


@pytest.fixture(scope="module")
def compiled():
    """{(model, program): (module name, the traced program's equations,
    optimized HLO text)}, each traced and compiled once."""
    kept, engines = {}, {}

    def get(name, program):
        if (name, program) not in kept:
            if name not in engines:
                engines[name] = _engine(name)
            traced = _traced(engines[name], program)
            text = traced.lower().compile().as_text()
            kept[name, program] = (
                re.search(r"HloModule (\S+?),", text).group(1),
                eqns_of(traced.jaxpr.jaxpr), text)
        return kept[name, program]
    return get


@pytest.mark.parametrize("program", PROGRAMS)
@pytest.mark.parametrize("name", sorted(SERVED))
def test_served_program_names_its_layers(compiled, name, program):
    module, eqns, text = compiled(name, program)
    assert module == f"jit_{program}_fn"
    # in the program as it was traced, nothing of weight lacks an owner
    assert {p for p, _ in eqns} & set(OWNED_PRIMS[:2])
    unowned = [(p, s) for p, s in eqns if p in OWNED_PRIMS and not owner(s)]
    assert not unowned, unowned[:5]
    found = {owner(s) for _, s in eqns} - {None}
    assert found <= telemetry.SCOPES
    assert found == EVERYWHERE | SERVED[name][2], found
    # what has no scope is the step's own arithmetic on its block table
    # and positions (a floor division, a remainder, a clip, a gather of
    # one row): some fifty small integer ops
    bare = [p for p, s in eqns if not owner(s)]
    assert len(bare) < 80, bare
    # and the compiler keeps the names: every product, kernel call and
    # sort of the optimized HLO that has a name at all (the CPU's
    # rewrites of a product drop it) has an owner in it
    ops = ops_of(text)
    named = [n for op, n in ops if op in OWNED and n]
    assert named and all(owner(n) for n in named), \
        [n for n in named if not owner(n)][:5]
    assert {owner(n) for _, n in ops} - {None} == found


def test_sampling_sorts_and_greedy_does_not(compiled):
    for name in ("gpt", "exaone-moe"):
        sorts = [n for op, n in ops_of(compiled(name, "decode")[2])
                 if op == "sort"]
        assert sorts and all(owner(n) == "sample" for n in sorts)
        assert not [1 for op, _ in ops_of(
            compiled(name, "decode_greedy")[2]) if op == "sort"]


# -- the train step -----------------------------------------------------------

def _train_step_text():
    paddle.seed(0)
    model = _gpt()
    opt = optimizer.AdamW(learning_rate=1e-4, parameters=model.parameters())

    def loss_fn(ids, labels):
        with amp.auto_cast(enable=True, dtype="bfloat16"):
            return model.loss(ids, labels)

    step = paddle.jit.TrainStep(model, loss_fn, opt)
    rs = np.random.RandomState(0)
    batch = [jnp.asarray(rs.randint(0, 256, (2, 128)).astype(np.int32))
             for _ in range(2)]
    args = ([p._value for p in step.params],
            [opt._states[id(p)] for p in step.params],
            [b._value for b in step.buffers],
            jnp.asarray(1e-4, jnp.float32), jax.random.PRNGKey(0), batch)
    return step._make_step().lower(*args).compile().as_text()


@pytest.fixture(scope="module")
def train_ops():
    text = _train_step_text()
    return text, ops_of(text)


def test_train_step_module_and_owners(train_ops):
    text, ops = train_ops
    assert re.search(r"HloModule (\S+?),", text).group(1) == "jit_step"
    unowned = [(op, n) for op, n in ops if op in OWNED and not owner(n)]
    assert not unowned, unowned[:5]
    found = {owner(n) for _, n in ops} - {None}
    assert found == {"embed", "attn", "mlp", "head", "loss", "optimizer"}


@pytest.mark.parametrize("layer", ["attn", "mlp", "head"])
def test_backward_products_bear_their_forwards_scope(train_ops, layer):
    """Two of a linear layer's three products are its backward's: they
    lie under the forward's scope and under `transpose(`."""
    dots = [n for op, n in train_ops[1] if op == "dot" and owner(n) == layer]
    back = [n for n in dots if "transpose(" in n]
    assert back and len(back) == 2 * (len(dots) - len(back)), dots


@pytest.mark.parametrize("layer", ["embed", "loss"])
def test_backward_of_embedding_and_loss_is_attributed(train_ops, layer):
    assert [n for _, n in train_ops[1]
            if owner(n) == layer and "transpose(" in n]


def test_no_backward_op_lacks_a_scope(train_ops):
    bare = [n for _, n in train_ops[1]
            if n and "transpose(" in n and not owner(n)]
    assert not bare, bare[:5]


def test_the_update_is_the_optimizers(train_ops):
    names = [n for _, n in train_ops[1] if owner(n) == "optimizer"]
    assert len(names) > 20
    assert not [n for n in names if "transpose(" in n]
    # nothing of the update lies outside it: the moments are read and
    # written under `pt.optimizer` alone
    assert not [n for op, n in train_ops[1]
                if op != "parameter" and n and "moment" in n
                and owner(n) != "optimizer"]


def test_a_custom_vjps_backward_rule_is_traced_under_the_forwards_scope():
    """The flash kernels are a `custom_vjp`: its backward rule runs when
    the tape's pull-back is called, outside the model's `with` (the
    kernels themselves run on the chip alone: the recorded trace in
    benchmark/tests/test_device_scope.py holds `flash_bwd` under
    `pt.attn`)."""
    from paddle_tpu.core.tensor import apply

    @jax.custom_vjp
    def kernel(x):
        return jnp.sin(x)
    kernel.defvjp(lambda x: (jnp.sin(x), x),
                  lambda x, g: (g * jnp.cos(x),))

    def grads(x):
        with autograd.fresh_tape():
            x = paddle.to_tensor(x, stop_gradient=False)
            with telemetry.scope("attn"):
                out = apply(kernel, x)
            autograd.backward(out.sum())
            return x.grad._value
    eqns = eqns_of(jax.make_jaxpr(grads)(jnp.ones((4,), jnp.float32)).jaxpr)
    rule = [s for p, s in eqns if p == "cos"]
    assert rule and all(owner(s) == "attn" for s in rule), eqns


# -- the primitive ------------------------------------------------------------

def test_scope_is_a_named_scope_and_nothing_else():
    assert telemetry.scope is scope_mod.scope
    assert len(telemetry.SCOPES) == 11
    with telemetry.scope("mlp"):
        assert str(jax._src.source_info_util.current_name_stack()) == \
            "pt.mlp"


@pytest.mark.parametrize("name", ["mlps", "", "pt.mlp", "MLP"])
def test_an_unknown_scope_raises_when_traced(name):
    def f(x):
        with telemetry.scope(name):
            return x + 1
    with pytest.raises(ValueError, match="telemetry.scope"):
        jax.jit(f).lower(1.0)


def test_a_node_keeps_its_scope_and_eager_mode_enters_nothing():
    from paddle_tpu import nn
    layer = nn.Linear(4, 4)
    x = paddle.to_tensor(np.ones((2, 4), np.float32), stop_gradient=False)
    with autograd.fresh_tape():
        layer(x).sum()
        assert all(not n.scope.stack for n in autograd.current_tape())
        assert autograd.reopened(autograd.current_tape()[0]) \
            is autograd._NO_SCOPE
    with autograd.fresh_tape():
        with telemetry.scope("mlp"):
            layer(x)
        assert all(str(n.scope) == "pt.mlp"
                   for n in autograd.current_tape())


# -- a scope changes metadata and nothing else --------------------------------

@contextlib.contextmanager
def no_scopes():
    """`telemetry.scope` as a null context, everywhere it is opened."""
    real = scope_mod.scope

    def null(name):
        return contextlib.nullcontext()
    with pytest.MonkeyPatch.context() as patch:
        for mod in list(sys.modules.values()):
            if getattr(mod, "__name__", "").startswith("paddle_tpu"):
                for attr in ("scope", "_scope"):
                    if getattr(mod, attr, None) is real:
                        patch.setattr(mod, attr, null)
        yield


@pytest.mark.parametrize("name,program", [
    ("gpt-wo8", "decode"), ("gpt", "prefill"),
    ("deepseek-v2", "decode_greedy"), ("granite-hybrid", "decode_greedy"),
    ("granite-hybrid", "prefill"), ("exaone-moe", "decode"),
    ("exaone-moe", "prefill")])
def test_serving_hlo_is_the_same_without_scopes(compiled, name, program):
    scoped = compiled(name, program)[2]
    with no_scopes():
        bare = _traced(_engine(name), program).lower().compile().as_text()
    assert "pt.attn" in scoped and not re.search(r"\bpt\.[a-z]+/", bare)
    assert stripped(bare) == stripped(scoped)


def test_train_hlo_is_the_same_without_scopes(train_ops):
    with no_scopes():
        bare = _train_step_text()
    assert "pt.optimizer" in train_ops[0] and "pt.optimizer" not in bare
    assert stripped(bare) == stripped(train_ops[0])
