"""Plain reference for the `gpt2-medium` configuration: a pre-LN decoder-only
transformer's forward pass, next-token cross-entropy, gradients and SGD with
momentum, in straightforward jax.numpy. float32 at `highest` matmul precision.

Independent of atomo_tpu: it imports nothing of the program and takes from it
neither weights nor tables. Weights come from `init_params` (the benchmark
installs the same arrays into the program before its first step); the names
of the leaves are the "/"-joined paths of the program's parameter tree, which
is all the two share.

Departures from the published GPT-2 medium, which the program's
models/transformer.py makes and the reference follows (configs/gpt2-medium.json
lists them under `assumed`): no biases on linears or LayerNorms, an untied
output head, LayerNorm epsilon 1e-6, tanh-approximated GELU.

`mode` selects the arithmetic. "float32" is the reference proper. "float8" is
the control of "How correct is decided": every matmul operand is rounded to
float8's precision on the way in and every cotangent on the way back
(reference/float8.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmarks.reference.float8 import fp8 as _fp8

HI = jax.lax.Precision.HIGHEST
LN_EPS = 1e-6
INIT_STD = 0.02


def param_shapes(cfg: dict) -> dict[str, tuple[int, ...]]:
    d, v = cfg["n_embd"], cfg["vocab_size"]
    shapes = {
        "tok_emb/embedding": (v, d),
        "pos_emb/embedding": (cfg["n_positions"], d),
        "ln_f/scale": (d,),
        "head/kernel": (d, v),
    }
    for i in range(cfg["n_layer"]):
        b = f"block{i}/"
        shapes[b + "ln1/scale"] = (d,)
        shapes[b + "MultiHeadAttention_0/qkv/kernel"] = (d, 3 * d)
        shapes[b + "MultiHeadAttention_0/proj/kernel"] = (d, d)
        shapes[b + "ln2/scale"] = (d,)
        shapes[b + "up/kernel"] = (d, 4 * d)
        shapes[b + "down/kernel"] = (4 * d, d)
    return shapes


def init_params(cfg: dict, seed: int, out_shardings=None) -> dict[str, jax.Array]:
    """All leaves on the device in one jitted call from the seed, float32:
    N(0, 0.02) for embeddings and kernels, ones for LayerNorm scales."""
    shapes = param_shapes(cfg)
    names = sorted(shapes)

    def make(key):
        out = {}
        for i, name in enumerate(names):
            if name.endswith("/scale"):
                out[name] = jnp.ones(shapes[name], jnp.float32)
            else:
                out[name] = INIT_STD * jax.random.normal(
                    jax.random.fold_in(key, i), shapes[name], jnp.float32
                )
        return out

    return jax.jit(make, out_shardings=out_shardings)(
        jax.random.PRNGKey(seed % (2**31 - 1))
    )


def _matmul(mode):
    if mode == "float32":
        return lambda a, b, spec: jnp.einsum(spec, a, b, precision=HI)
    if mode == "float8":
        return lambda a, b, spec: jnp.einsum(spec, _fp8(a), _fp8(b), precision=HI)
    raise ValueError(f"unknown reference mode {mode!r}")


def _layer_norm(x, scale):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + LN_EPS) * scale


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(0.7978845608028654 * (x + 0.044715 * x**3)))


def _block(x, p, n_head, mm):
    b, s, d = x.shape
    hd = d // n_head
    y = _layer_norm(x, p["ln1/scale"])
    qkv = mm(y, p["MultiHeadAttention_0/qkv/kernel"], "bsd,de->bse")
    q, k, v = (
        t.reshape(b, s, n_head, hd).transpose(0, 2, 1, 3)
        for t in jnp.split(qkv, 3, axis=-1)
    )
    scores = mm(q, k, "bhqd,bhkd->bhqk") / jnp.sqrt(jnp.float32(hd))
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    out = mm(probs, v, "bhqk,bhkd->bhqd").transpose(0, 2, 1, 3).reshape(b, s, d)
    x = x + mm(out, p["MultiHeadAttention_0/proj/kernel"], "bsd,de->bse")
    y = _layer_norm(x, p["ln2/scale"])
    y = _gelu_tanh(mm(y, p["up/kernel"], "bsd,de->bse"))
    return x + mm(y, p["down/kernel"], "bse,ed->bsd")


def loss(params: dict, tokens, cfg: dict, mode: str = "float32"):
    """Mean next-token cross-entropy of int32 `tokens` (B, S). Each block is
    rematerialised in the backward pass, so the 24 layers fit beside the
    weights in float32."""
    mm = _matmul(mode)
    s = tokens.shape[1]
    x = params["tok_emb/embedding"][tokens] + params["pos_emb/embedding"][:s][None]
    for i in range(cfg["n_layer"]):
        prefix = f"block{i}/"
        p = {k[len(prefix):]: v for k, v in params.items() if k.startswith(prefix)}
        x = jax.checkpoint(functools.partial(_block, n_head=cfg["n_head"], mm=mm))(x, p)
    x = _layer_norm(x, params["ln_f/scale"])
    logits = mm(x[:, :-1], params["head/kernel"], "bsd,dv->bsv")
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)
    return -jnp.mean(picked)


def leaf_norms(tree: dict) -> dict[str, jax.Array]:
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32)))) for k, v in tree.items()}


def train_steps(params: dict, batches, cfg: dict, mode: str = "float32", flags: dict | None = None,
                draws: int = 0):
    """Follow `len(batches)` optimizer steps from `params`. Returns each
    step's loss, the per-leaf norm of the first gradient, and the per-leaf
    norm of the parameters' change over all the steps. SGD with momentum as
    optax states it: trace = g + momentum * trace, p -= lr * trace. `flags`
    are the cell's flags of the lm command; this reference follows no codec, so `draws`, which picks a codec's
    stream of random numbers, changes nothing."""
    if (flags or {}).get("--code", "sgd") != "sgd":
        raise ValueError(f"this reference follows --code sgd only, not {flags['--code']!r}")
    lr, mu = cfg["lr"], cfg["momentum"]

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def step(p, trace, tokens):
        value, g = jax.value_and_grad(loss)(p, tokens, cfg, mode)
        trace = {k: g[k] + mu * trace[k] for k in g}
        p = {k: p[k] - lr * trace[k] for k in p}
        return p, trace, value, leaf_norms(g)

    start = params
    p = jax.tree_util.tree_map(jnp.copy, params)
    trace = jax.tree_util.tree_map(jnp.zeros_like, params)
    losses, grad1 = [], None
    for tokens in batches:
        p, trace, value, gnorm = step(p, trace, jnp.asarray(tokens))
        losses.append(float(value))
        if grad1 is None:
            grad1 = {k: float(v) for k, v in gnorm.items()}
    change = jax.jit(lambda a, b: leaf_norms({k: a[k] - b[k] for k in a}))(p, start)
    return {
        "losses": losses,
        "grad1_norms": grad1,
        "change_norms": {k: float(v) for k, v in change.items()},
    }


CONTROLS = ("float8",)  # the nearest precision below the configuration's bfloat16


def example_batches(cfg: dict, seed: int, calls: int, rows: int):
    """Token batches of the kind the lm command feeds (arithmetic progressions
    with random start and stride), for tests and for reading the control where
    no program ran: a copy of cmd_lm's `_synth` rule."""
    import numpy as np

    rng = np.random.default_rng(seed)
    out = []
    for _ in range(calls):
        starts = rng.integers(0, cfg["vocab_size"], size=(rows, 1))
        strides = rng.integers(1, 4, size=(rows, 1))
        out.append(
            ((starts + strides * np.arange(cfg["n_positions"])) % cfg["vocab_size"]).astype(np.int32)
        )
    return out
