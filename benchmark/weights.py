"""Seeded GPT weights, made on the device in one jitted call.

The benchmark owns the weights: the program under test is handed them
(by parameter name) and the plain reference draws the same values again
from the same seed, so neither takes anything the other made. Names are
those of `GPTForPretraining.named_parameters()`.
"""
import functools
import math

import jax
import jax.numpy as jnp

# per-layer leaves in a fixed order: (name, shape as a function of the
# sizes, kind). kind: "w" matrix N(0, scale x std), "w2" residual-scaled
# matrix, "b" bias N(0, std), "g" layernorm gain 1 + N(0, std). `scale`
# is a cell's `init.block_scale` (1 where it gives none): at GPT-2's
# 0.02 a random model's positions collapse onto one direction and its
# greedy stream repeats one token with a wide margin, which no
# arithmetic can flip; a serving cell widens its blocks' matrices until
# the top two logits lie close, so that `served_logit_gap` sees the
# precision of activations and K/V (PERF.md section 2)
_LAYER_LEAVES = (
    ("ln1.weight", lambda d, f: (d,), "g"),
    ("ln1.bias", lambda d, f: (d,), "b"),
    ("attn.qkv_proj.weight", lambda d, f: (d, 3 * d), "w"),
    ("attn.qkv_proj.bias", lambda d, f: (3 * d,), "b"),
    ("attn.out_proj.weight", lambda d, f: (d, d), "w"),
    ("attn.out_proj.bias", lambda d, f: (d,), "b"),
    ("ln2.weight", lambda d, f: (d,), "g"),
    ("ln2.bias", lambda d, f: (d,), "b"),
    ("mlp.fc1.weight", lambda d, f: (d, f), "w"),
    ("mlp.fc1.bias", lambda d, f: (f,), "b"),
    ("mlp.fc2.weight", lambda d, f: (f, d), "w2"),
    ("mlp.fc2.bias", lambda d, f: (d,), "b"),
)
LAYER_LEAF_NAMES = tuple(n for n, _, _ in _LAYER_LEAVES)


def sizes(config):
    """(L, d, heads, ffn, vocab, positions) of a configuration file."""
    m = config["model"]
    return (m["num_layers"], m["hidden_size"], m["num_heads"],
            m["ffn_hidden_size"], m["vocab_size"], m["max_seq_len"])


def _draw(key, shape, kind, std, n_layers, scale=1.0):
    x = jax.random.normal(key, shape, jnp.float32)
    if kind == "g":
        return 1.0 + std * x
    if kind == "w2":
        return (scale * std / math.sqrt(2 * n_layers)) * x
    if kind == "w":
        return (scale * std) * x
    return std * x


def _make(key, dims, std, stacked, block_scale):
    L, d, _, f, vocab, npos = dims
    out = {
        "gpt.wte.weight": _draw(jax.random.fold_in(key, 1), (vocab, d),
                                "w", std, L),
        "gpt.wpe.weight": _draw(jax.random.fold_in(key, 2), (npos, d),
                                "w", std, L),
        "gpt.ln_f.weight": _draw(jax.random.fold_in(key, 3), (d,), "g",
                                 std, L),
        "gpt.ln_f.bias": _draw(jax.random.fold_in(key, 4), (d,), "b",
                               std, L),
    }
    for j, (name, shape, kind) in enumerate(_LAYER_LEAVES):
        leaves = [_draw(jax.random.fold_in(jax.random.fold_in(key, 100 + l),
                                           j), shape(d, f), kind, std, L,
                        block_scale)
                  for l in range(L)]
        if stacked:
            out["blocks." + name] = jnp.stack(leaves)
        else:
            for l, leaf in enumerate(leaves):
                out[f"gpt.blocks.{l}.{name}"] = leaf
    return out


def block_scale_of(cell):
    return float(cell.get("init", {}).get("block_scale", 1.0))


def make_weights(config, seed, stacked=False, block_scale=1.0):
    """All parameters of the configuration from `seed`, float32, in one
    jitted call. stacked=False: one entry per program parameter name;
    stacked=True: the per-layer leaves stacked as `blocks.<leaf>`
    [L, ...] (the reference's layout). Both layouts hold the same
    values."""
    dims = sizes(config)
    std = float(config["model"].get("initializer_range", 0.02))
    fn = jax.jit(functools.partial(_make, dims=dims, std=std,
                                   stacked=stacked,
                                   block_scale=float(block_scale)))
    return fn(jax.random.PRNGKey(int(seed)))


def count_params(config):
    L, d, _, f, vocab, npos = sizes(config)
    per_layer = sum(math.prod(shape(d, f)) for _, shape, _ in _LAYER_LEAVES)
    return vocab * d + npos * d + 2 * d + L * per_layer


def seeded_program_model(config, seed, block_scale=1.0):
    """The program's `GPTForPretraining` at the configuration's sizes,
    with every parameter replaced by the seeded weights."""
    from paddle_tpu.models.gpt import GPTConfig, GPTForPretraining
    L, d, heads, ffn, vocab, npos = sizes(config)
    model = GPTForPretraining(GPTConfig(
        vocab_size=vocab, hidden_size=d, num_layers=L, num_heads=heads,
        ffn_hidden_size=ffn, max_seq_len=npos, dropout=0.0))
    w = make_weights(config, seed, block_scale=block_scale)
    for name, p in model.named_parameters():
        if tuple(p.shape) != tuple(w[name].shape):
            raise SystemExit(f"weight shape mismatch at {name}")
        p._value = w.pop(name)
    if w:
        raise SystemExit(f"weights nobody took: {sorted(w)[:3]}")
    return model
