"""Plain reference of the GPT-3 architecture (Brown et al. 2020, the
GPT-2 block): learned positions, pre-layernorm blocks, fused q/k/v
projection, causal softmax attention, tanh-GELU MLP, tied output head.

Straightforward `jax.numpy` in float32 with every contraction at
`Precision.HIGHEST`; no kernels, no cache, no batching tricks. It
imports nothing of the program under test and takes nothing the program
made: weights come from `benchmark/weights.py` (stacked layout), the
weight-only int8 quantization the serving configurations state is done
here again from those float32 weights.

Departures from the paper, both as the program's `GPTConfig` has them:
the vocabulary is padded to 50,304 and the loss is the plain mean of
the per-position cross-entropy against the labels given (the traffic
draws labels independently, nothing is shifted).

A `prec` argument selects the arithmetic, so the same code also serves
as the low-precision control of the `correct` comparison:

  act   "f32" (reference) | "bf16" | "fp8"   operands of every
        contraction (fp8: e4m3 operands forward and the e5m2 incoming
        gradient backward, each under a per-tensor scale)
  wbits None | 8 | 4   per-output-channel symmetric weight-only
        quantization of the four linears of each block
  kv    None | "fp8"   keys and values rounded to e4m3 as an fp8 cache
        would hold them, everything else as `act` says (serving only)
"""
import functools
import math

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
REFERENCE = {"act": "f32", "wbits": None}

ADAMW = {"lr": 1e-4, "beta1": 0.9, "beta2": 0.999, "eps": 1e-8,
         "weight_decay": 0.01}

_LINEARS = ("attn.qkv_proj.weight", "attn.out_proj.weight",
            "mlp.fc1.weight", "mlp.fc2.weight")


# ---------------------------------------------------------------------------
# arithmetic
# ---------------------------------------------------------------------------

def _fp8(x, dtype):
    """x rounded to an fp8 format under a per-tensor scale."""
    top = float(jnp.finfo(dtype).max)
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
    return ((x / s).astype(dtype).astype(jnp.float32) * s).astype(
        jnp.bfloat16)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _fp8_einsum(spec, a, b):
    """The fp8 recipe of mixed-precision training: operands in e4m3
    forward, the incoming gradient in e5m2 backward, products
    accumulated in float32."""
    return jnp.einsum(spec, _fp8(a, jnp.float8_e4m3fn),
                      _fp8(b, jnp.float8_e4m3fn),
                      preferred_element_type=jnp.float32)


def _fp8_fwd(spec, a, b):
    qa, qb = _fp8(a, jnp.float8_e4m3fn), _fp8(b, jnp.float8_e4m3fn)
    return jnp.einsum(spec, qa, qb,
                      preferred_element_type=jnp.float32), (qa, qb)


def _fp8_bwd(spec, res, g):
    _, vjp = jax.vjp(lambda x, y: jnp.einsum(
        spec, x, y, preferred_element_type=jnp.float32), *res)
    da, db = vjp(_fp8(g, jnp.float8_e5m2).astype(jnp.float32))
    return da.astype(jnp.float32), db.astype(jnp.float32)


_fp8_einsum.defvjp(_fp8_fwd, _fp8_bwd)


def _einsum(spec, a, b, act):
    if act == "fp8":
        return _fp8_einsum(spec, a.astype(jnp.float32),
                           b.astype(jnp.float32))
    if act == "bf16":
        a, b = a.astype(jnp.bfloat16), b.astype(jnp.bfloat16)
    elif act != "f32":
        raise ValueError(f"unknown activation precision {act!r}")
    return jnp.einsum(spec, a, b, precision=HI if act == "f32" else None,
                      preferred_element_type=jnp.float32)


def quantize_channelwise(w, bits):
    """Symmetric per-output-channel weight quantization of [..., in, out]
    (the scale is over the `in` axis); returns the dequantized float32
    weights, what a weight-only scheme multiplies by."""
    qmax = 2.0 ** (bits - 1) - 1
    scale = jnp.maximum(jnp.max(jnp.abs(w), axis=-2, keepdims=True),
                        1e-8) / qmax
    return jnp.clip(jnp.round(w / scale), -qmax, qmax) * scale


def prepare(params, prec):
    """The weights as the configuration serves them: the four linears
    of each block quantized when `wbits` says so."""
    if not prec.get("wbits"):
        return params
    out = dict(params)
    for name in _LINEARS:
        out["blocks." + name] = quantize_channelwise(
            params["blocks." + name], prec["wbits"])
    return out


def _layernorm(x, g, b, eps=1e-5):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * g + b


def _block(h, p, n_heads, act, kv=None):
    b, s, d = h.shape
    hd = d // n_heads
    y = _layernorm(h, p["ln1.weight"], p["ln1.bias"])
    qkv = _einsum("bsd,de->bse", y, p["attn.qkv_proj.weight"], act) \
        + p["attn.qkv_proj.bias"]
    q, k, v = jnp.moveaxis(qkv.reshape(b, s, 3, n_heads, hd), 2, 0)
    if kv == "fp8":     # keys and values as an fp8 cache would hold them
        k, v = _fp8(k, jnp.float8_e4m3fn), _fp8(v, jnp.float8_e4m3fn)
    elif kv is not None:
        raise ValueError(f"unknown K/V precision {kv!r}")
    scores = _einsum("bqnh,bknh->bnqk", q, k, act) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -1e30), axis=-1)
    a = _einsum("bnqk,bknh->bqnh", probs, v, act).reshape(b, s, d)
    h = h + _einsum("bsd,de->bse", a, p["attn.out_proj.weight"], act) \
        + p["attn.out_proj.bias"]
    y = _layernorm(h, p["ln2.weight"], p["ln2.bias"])
    u = _einsum("bsd,df->bsf", y, p["mlp.fc1.weight"], act) \
        + p["mlp.fc1.bias"]
    u = jax.nn.gelu(u, approximate=True)
    return h + _einsum("bsf,fd->bsd", u, p["mlp.fc2.weight"], act) \
        + p["mlp.fc2.bias"]


def hidden(params, ids, n_heads, act, remat=False, kv=None):
    """Final-layernorm hidden states [B, S, d] of token ids [B, S]."""
    s = ids.shape[1]
    h = params["gpt.wte.weight"][ids] + params["gpt.wpe.weight"][:s][None]
    blocks = {k[len("blocks."):]: v for k, v in params.items()
              if k.startswith("blocks.")}
    body = functools.partial(_block, n_heads=n_heads, act=act, kv=kv)
    if remat:
        body = jax.checkpoint(body)
    h, _ = jax.lax.scan(lambda c, p: (body(c, p), None), h, blocks)
    return _layernorm(h, params["gpt.ln_f.weight"], params["gpt.ln_f.bias"])


def logits_of(params, hf, act):
    return _einsum("bsd,vd->bsv", hf, params["gpt.wte.weight"], act)


# ---------------------------------------------------------------------------
# training: loss, gradients, AdamW
# ---------------------------------------------------------------------------

def _loss_sum(params, ids, labels, n_heads, act):
    lg = logits_of(params, hidden(params, ids, n_heads, act, remat=True),
                   act)
    lse = jax.nn.logsumexp(lg, axis=-1)
    picked = jnp.take_along_axis(lg, labels[..., None], axis=-1)[..., 0]
    return jnp.sum(lse - picked)


@functools.partial(jax.jit, static_argnames=("n_heads", "act"))
def _block_grad(params, ids, labels, n_heads, act):
    return jax.value_and_grad(_loss_sum)(params, ids, labels, n_heads, act)


@jax.jit
def _add(a, b):
    return jax.tree_util.tree_map(jnp.add, a, b)


@jax.jit
def _sub(a, b):
    return jax.tree_util.tree_map(jnp.subtract, a, b)


@jax.jit
def _scaled(tree, scale):
    return jax.tree_util.tree_map(lambda x: x * scale, tree)


@jax.jit
def _zeros(tree):
    return jax.tree_util.tree_map(jnp.zeros_like, tree)


def loss_and_grad(params, ids, labels, n_heads, prec, rows_per_block):
    """Mean cross-entropy over all positions of [B, S] and its gradient,
    computed in blocks of rows so that it fits beside nothing else."""
    total, grads, n = 0.0, None, ids.shape[0] * ids.shape[1]
    for r0 in range(0, ids.shape[0], rows_per_block):
        rows = slice(r0, r0 + rows_per_block)
        l, g = _block_grad(params, jnp.asarray(ids[rows]),
                           jnp.asarray(labels[rows]), n_heads, prec["act"])
        total = total + l
        grads = g if grads is None else _add(grads, g)
    return total / n, _scaled(grads, jnp.float32(1.0 / n))


@jax.jit
def _adamw(params, grads, m, v, t):
    h = ADAMW
    b1, b2 = h["beta1"], h["beta2"]
    out_p, out_m, out_v = {}, {}, {}
    for k, p in params.items():
        g = grads[k]
        mk = b1 * m[k] + (1 - b1) * g
        vk = b2 * v[k] + (1 - b2) * jnp.square(g)
        mhat = mk / (1 - b1 ** t)
        vhat = vk / (1 - b2 ** t)
        p = p * (1.0 - h["lr"] * h["weight_decay"])
        out_p[k] = p - h["lr"] * mhat / (jnp.sqrt(vhat) + h["eps"])
        out_m[k], out_v[k] = mk, vk
    return out_p, out_m, out_v


def split_leaves(named):
    """{name: array} with every fused q/k/v projection (last axis 3d)
    given as its three parts `<name>.q`, `.k`, `.v`: they are three
    parameters of the architecture, and the key's bias has no gradient
    under softmax, which a norm over the fused leaf would hide."""
    out = {}
    for name, x in named.items():
        if "qkv_proj" in name:
            for part, piece in zip("qkv", jnp.split(x, 3, axis=-1)):
                out[f"{name}.{part}"] = piece
        else:
            out[name] = x
    return out


@jax.jit
def leaf_norms(tree):
    """Euclidean norm of every leaf, named as the program names its
    parameters (q/k/v apart, see split_leaves): a stacked
    `blocks.<leaf>` [L, ...] gives L norms."""
    out = {}
    for k, x in split_leaves(tree).items():
        if k.startswith("blocks."):
            n = jnp.sqrt(jnp.sum(jnp.square(x.reshape(x.shape[0], -1)),
                                 axis=1))
            for l in range(x.shape[0]):
                out[f"gpt.blocks.{l}.{k[len('blocks.'):]}"] = n[l]
        else:
            out[k] = jnp.sqrt(jnp.sum(jnp.square(x)))
    return out


def train_steps(params, batches, n_heads, prec, rows_per_block):
    """Follow AdamW through `batches` (a list of (ids, labels) host
    arrays) from `params` (stacked layout). Returns the losses, the
    per-leaf norm of the first gradient and the per-leaf norm of the
    parameters' change after the last step."""
    start = params
    m, v = _zeros(params), _zeros(params)
    losses, first = [], None
    for t, (ids, labels) in enumerate(batches, start=1):
        loss, grads = loss_and_grad(params, ids, labels, n_heads, prec,
                                    rows_per_block)
        if first is None:
            first = leaf_norms(grads)
        params, m, v = _adamw(params, grads, m, v, float(t))
        losses.append(float(loss))
    change = leaf_norms(_sub(params, start))
    return {"losses": losses,
            "grad_norm": {k: float(x) for k, x in first.items()},
            "change_norm": {k: float(x) for k, x in change.items()}}


# ---------------------------------------------------------------------------
# serving: per-position logits of a prompt with its served tokens
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("n_heads", "act", "kv"))
def position_logits(params, ids, probe_a, probe_b, n_heads, act, kv=None):
    """For ids [1, S]: at every position the best logit, its token, and
    the logits of the two probe tokens [S] each."""
    lg = logits_of(params, hidden(params, ids, n_heads, act, kv=kv),
                   act)[0]
    take = lambda t: jnp.take_along_axis(lg, t[:, None], axis=-1)[:, 0]
    return (jnp.max(lg, axis=-1), jnp.argmax(lg, axis=-1).astype(jnp.int32),
            take(probe_a), take(probe_b))
