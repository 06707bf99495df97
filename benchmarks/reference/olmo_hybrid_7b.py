"""Plain reference for the `olmo-hybrid-7b` configuration: a decoder of
gated-delta-rule linear-attention layers beside full-attention layers
(`layer_types`), its next-token cross-entropy, gradients and SGD with
momentum, in straightforward jax.numpy. float32 at `highest` matmul precision.

Independent of atomo_tpu: it imports nothing of the program and takes from it
neither weights nor tables. Weights come from `init_params` (the benchmark
installs the same arrays into the program before its first step); the names
of the leaves are the "/"-joined paths of the program's parameter tree, which
is all the two share.

The layers, from the published `config.json` (configs/olmo-hybrid-7b.json
lists under `assumed` what that file does not say):

- block: h = x + RMSNorm(Mixer(x)); y = h + RMSNorm(W_down(silu(W_gate h) * (W_up h)));
  a last RMSNorm before the head; no positional embedding; no biases.
- full attention: q, k, v from one matrix, RMSNorm over the whole projected q
  and k, causal softmax at 1/sqrt(head size), no rotary embedding.
- linear attention (the gated delta rule, arXiv:2412.06464): q, k, v each
  through a causal depthwise convolution over time and SiLU; q and k
  L2-normalised per head, q scaled by 1/sqrt(key size);
  beta = 2 sigmoid(W_b x), alpha = exp(-exp(A_log) softplus(W_a x + dt_bias));
  per head a state S of (value size, key size),
      S_t = alpha_t S_{t-1} (I - beta_t k_t k_t^T) + beta_t v_t k_t^T,  o_t = S_t q_t,
  run here **token by token** (`delta_rule_recurrent`), which is the
  definition; the output is W_o concat_heads(RMSNorm(o_t) * silu(z_t)).

So that three steps fit the chip beside float32 weights and momentum (12 B a
parameter at 929 M parameters), the gradient is taken stage by stage
(embedding, each block, head), each stage's vjp from the stage's input, and a
stage's leaves are updated as soon as their gradient is whole: it never exists
all at once. Inside a block the full layer's attention runs in blocks of
queries and the recurrence keeps its state at every 64th token only and
recomputes between.

`mode` selects the arithmetic. "float32" is the reference proper. "float8" is
the control of "How correct is decided": every matmul operand, and q, k and v
on their way into the recurrence, is rounded to float8's precision and every
cotangent on the way back (reference/float8.py).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmarks.reference.float8 import fp8 as _fp8

HI = jax.lax.Precision.HIGHEST
INIT_STD = 0.02
L2_EPS = 1e-6
QUERY_BLOCK = 512  # queries per block of the full layer's attention
KEPT_EVERY = 64  # tokens between the states the recurrence keeps for its backward pass
MIXER_OF = {"linear_attention": "linear", "full_attention": "full"}


def layer_kinds(cfg: dict) -> list[str]:
    return [MIXER_OF[t] for t in cfg["layer_types"][: cfg["num_hidden_layers"]]]


def _sizes(cfg: dict):
    heads = cfg["num_attention_heads"]
    if not heads == cfg["linear_num_key_heads"] == cfg["linear_num_value_heads"]:
        raise ValueError("this reference takes one head count for the full and the linear layers")
    return (cfg["hidden_size"], cfg["intermediate_size"], heads,
            cfg["linear_key_head_dim"], cfg["linear_value_head_dim"])


def param_shapes(cfg: dict) -> dict[str, tuple[int, ...]]:
    d, f, h, dk, dv = _sizes(cfg)
    v, w = cfg["vocab_size"], cfg["linear_conv_kernel_dim"]
    shapes = {"tok_emb/embedding": (v, d), "ln_f/scale": (d,), "head/kernel": (d, v)}
    for i, kind in enumerate(layer_kinds(cfg)):
        b = f"block{i}/"
        for name, shape in (("ln1/scale", (d,)), ("ln2/scale", (d,)), ("gate/kernel", (d, f)),
                            ("up/kernel", (d, f)), ("down/kernel", (f, d))):
            shapes[b + name] = shape
        if kind == "full":
            m = b + "MultiHeadAttention_0/"
            shapes.update({m + "qkv/kernel": (d, 3 * d), m + "q_norm/scale": (d,),
                           m + "k_norm/scale": (d,), m + "proj/kernel": (d, d)})
        else:
            m = b + "GatedDeltaNet_0/"
            shapes.update({
                m + "q/kernel": (d, h * dk), m + "k/kernel": (d, h * dk),
                m + "v/kernel": (d, h * dv), m + "z/kernel": (d, h * dv),
                m + "a/kernel": (d, h), m + "b/kernel": (d, h),
                m + "A_log": (h,), m + "dt_bias": (h,),
                m + "q_conv": (w, 1, h * dk), m + "k_conv": (w, 1, h * dk),
                m + "v_conv": (w, 1, h * dv),
                m + "o_norm/scale": (dv,), m + "o/kernel": (h * dv, d),
            })
    return shapes


def init_params(cfg: dict, seed: int, out_shardings=None) -> dict[str, jax.Array]:
    """All leaves on the device in one jitted call from the seed, float32:
    N(0, 0.02) for embeddings and kernels, ones for norm scales,
    A_log = log U(1, 16), dt_bias = softplus^-1(dt) with dt log-uniform on
    (0.001, 0.1) (so alpha starts between exp(-1.6) and 0.999), convolution
    taps U(-1/2, 1/2) (one over the root of the 4 taps)."""
    shapes = param_shapes(cfg)
    names = sorted(shapes)

    def make(key):
        out = {}
        for i, name in enumerate(names):
            k, shape = jax.random.fold_in(key, i), shapes[name]
            if name.endswith("/scale"):
                out[name] = jnp.ones(shape, jnp.float32)
            elif name.endswith("/A_log"):
                out[name] = jnp.log(jax.random.uniform(k, shape, jnp.float32, 1.0, 16.0))
            elif name.endswith("/dt_bias"):
                dt = jnp.exp(jax.random.uniform(k, shape, jnp.float32, math.log(1e-3), math.log(1e-1)))
                out[name] = dt + jnp.log(-jnp.expm1(-dt))
            elif name.endswith("_conv"):
                out[name] = jax.random.uniform(k, shape, jnp.float32, -0.5, 0.5)
            else:
                out[name] = INIT_STD * jax.random.normal(k, shape, jnp.float32)
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


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


def _causal_conv(x, kernel):
    """Depthwise over time, left-padded: the output at t sees t-3..t."""
    return jax.lax.conv_general_dilated(
        x, kernel, window_strides=(1,), padding=[(kernel.shape[0] - 1, 0)],
        dimension_numbers=("NWC", "WIO", "NWC"), feature_group_count=x.shape[-1], precision=HI,
    )


def delta_rule_recurrent(q, k, v, alpha, beta):
    """The gated delta rule token by token. q, k (B, S, H, dk), v
    (B, S, H, dv), alpha and beta (B, S, H); returns o (B, S, H, dv). The
    state is kept at every KEPT_EVERY-th token and recomputed between."""
    b, s, h, dk = q.shape
    dv, seg = v.shape[-1], math.gcd(s, KEPT_EVERY)

    def token(state, x):
        q_t, k_t, v_t, a_t, b_t = x
        sk = jnp.einsum("bhvk,bhk->bhv", state, k_t, precision=HI)
        write = b_t[..., None, None] * (v_t - a_t[..., None] * sk)[..., :, None] * k_t[..., None, :]
        state = a_t[..., None, None] * state + write
        return state, jnp.einsum("bhvk,bhk->bhv", state, q_t, precision=HI)

    @jax.checkpoint
    def segment(state, xs):
        return jax.lax.scan(token, state, xs)

    by_time = lambda x: jnp.moveaxis(x, 1, 0).reshape(s // seg, seg, *x.shape[:1], *x.shape[2:])  # noqa: E731
    state0 = jnp.zeros((b, h, dv, dk), jnp.float32)
    _, o = jax.lax.scan(segment, state0, tuple(by_time(x) for x in (q, k, v, alpha, beta)))
    return jnp.moveaxis(o.reshape(s, b, h, dv), 0, 1)


def _linear_attention(x, p, cfg, mm, mode):
    _, _, h, dk, dv = _sizes(cfg)
    b, s, _ = x.shape
    proj = lambda name: mm(x, p[name + "/kernel"], "bsd,de->bse")  # noqa: E731
    q, k, v = (jax.nn.silu(_causal_conv(proj(n), p[n + "_conv"])) for n in "qkv")
    q, k, v = q.reshape(b, s, h, dk), k.reshape(b, s, h, dk), v.reshape(b, s, h, dv)
    unit = lambda t: t * jax.lax.rsqrt(jnp.sum(jnp.square(t), axis=-1, keepdims=True) + L2_EPS)  # noqa: E731
    q, k = unit(q) / math.sqrt(dk), unit(k)
    beta = 2.0 * jax.nn.sigmoid(proj("b"))  # `linear_allow_neg_eigval`: beta in (0, 2)
    alpha = jnp.exp(-jnp.exp(p["A_log"]) * jax.nn.softplus(proj("a") + p["dt_bias"]))
    if mode == "float8":
        q, k, v = _fp8(q), _fp8(k), _fp8(v)
    o = delta_rule_recurrent(q, k, v, alpha, beta)
    o = _rms_norm(o, p["o_norm/scale"], cfg["rms_norm_eps"]) * jax.nn.silu(proj("z").reshape(b, s, h, dv))
    return mm(o.reshape(b, s, h * dv), p["o/kernel"], "bse,ed->bsd")


def _full_attention(x, p, cfg, mm):
    b, s, d = x.shape
    h = cfg["num_attention_heads"]
    hd, eps = d // h, cfg["rms_norm_eps"]
    q, k, v = jnp.split(mm(x, p["qkv/kernel"], "bsd,de->bse"), 3, axis=-1)
    q, k = _rms_norm(q, p["q_norm/scale"], eps), _rms_norm(k, p["k_norm/scale"], eps)
    q, k, v = (t.reshape(b, s, h, hd).transpose(0, 2, 1, 3) for t in (q, k, v))
    blk = math.gcd(s, QUERY_BLOCK)

    @jax.checkpoint
    def attend(args):
        q_blk, first = args  # (B, H, blk, hd), the block's first position
        scores = mm(q_blk, k, "bhqd,bhkd->bhqk") / math.sqrt(hd)
        causal = (first + jnp.arange(blk))[:, None] >= jnp.arange(s)[None, :]
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        return mm(probs, v, "bhqk,bhkd->bhqd")

    q_blocks = jnp.moveaxis(q.reshape(b, h, s // blk, blk, hd), 2, 0)
    out = jax.lax.map(attend, (q_blocks, jnp.arange(0, s, blk)))
    out = jnp.moveaxis(out, 0, 2).reshape(b, h, s, hd).transpose(0, 2, 1, 3).reshape(b, s, d)
    return mm(out, p["proj/kernel"], "bsd,de->bse")


@jax.default_matmul_precision("highest")  # on a TPU float32 products run in bfloat16 passes otherwise
def _block(p, x, kind, cfg, mode):
    mm, eps = _matmul(mode), cfg["rms_norm_eps"]
    sub = lambda prefix: {k[len(prefix):]: v for k, v in p.items() if k.startswith(prefix)}  # noqa: E731
    if kind == "full":
        mixed = _full_attention(x, sub("MultiHeadAttention_0/"), cfg, mm)
    else:
        mixed = _linear_attention(x, sub("GatedDeltaNet_0/"), cfg, mm, mode)
    x = x + _rms_norm(mixed, p["ln1/scale"], eps)
    y = jax.nn.silu(mm(x, p["gate/kernel"], "bsd,df->bsf")) * mm(x, p["up/kernel"], "bsd,df->bsf")
    return x + _rms_norm(mm(y, p["down/kernel"], "bsf,fd->bsd"), p["ln2/scale"], eps)


@jax.default_matmul_precision("highest")
def _head_loss(p, x, tokens, cfg, mode):
    """Mean next-token cross-entropy of int32 `tokens` (B, S) from the last block's output."""
    x = _rms_norm(x, p["ln_f/scale"], cfg["rms_norm_eps"])
    logits = _matmul(mode)(x[:, :-1], p["head/kernel"], "bsd,dv->bsv")
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1))


def leaf_norms(tree: dict) -> dict[str, jax.Array]:
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32)))) for k, v in tree.items()}


def backward_by_stage(params: dict, tokens, cfg: dict, mode: str = "float32"):
    """The loss, then each stage's gradient as soon as it is whole, last
    stage first: yields the loss (a scalar), then ({leaf: gradient} of the
    head, of each block from the last to the first, of the embedding). The
    caller may update or drop a stage's leaves before asking for the next."""
    kinds = layer_kinds(cfg)
    of = lambda prefix: {k: v for k, v in params.items() if k.startswith(prefix)}  # noqa: E731
    strip = lambda tree, prefix: {k[len(prefix):]: v for k, v in tree.items()}  # noqa: E731
    block = {kind: jax.jit(functools.partial(_block, kind=kind, cfg=cfg, mode=mode))
             for kind in set(kinds)}

    @jax.jit
    def head(p, x):
        value, (g, gx) = jax.value_and_grad(_head_loss, argnums=(0, 1))(p, x, tokens, cfg, mode)
        return value, g, gx

    @functools.partial(jax.jit, static_argnums=(3,), donate_argnums=(2,))
    def block_vjp(p, x, gx, kind):
        _, pull = jax.vjp(functools.partial(_block, kind=kind, cfg=cfg, mode=mode), p, x)
        return pull(gx)

    xs = [params["tok_emb/embedding"][tokens]]
    for i, kind in enumerate(kinds[:-1]):
        xs.append(block[kind](strip(of(f"block{i}/"), f"block{i}/"), xs[-1]))
    last = len(kinds) - 1
    # the last block's output feeds the head alone: made here, dropped after the head's vjp
    top = block[kinds[last]](strip(of(f"block{last}/"), f"block{last}/"), xs[-1])
    value, g, gx = head({k: params[k] for k in ("ln_f/scale", "head/kernel")}, top)
    del top
    yield value
    yield g
    for i in range(last, -1, -1):
        prefix = f"block{i}/"
        g, gx = block_vjp(strip(of(prefix), prefix), xs.pop(), gx, kinds[i])
        yield {prefix + k: v for k, v in g.items()}
    rows = params["tok_emb/embedding"].shape
    yield {"tok_emb/embedding": jax.jit(lambda gx: jnp.zeros(rows, gx.dtype).at[tokens].add(gx))(gx)}


def loss_and_grads(params: dict, tokens, cfg: dict, mode: str = "float32"):
    """The loss and the whole gradient, for tests at sizes where it fits."""
    stages = backward_by_stage(params, jnp.asarray(tokens), cfg, mode)
    value, grads = next(stages), {}
    for stage in stages:
        grads.update(stage)
    return value, grads


def train_steps(params: dict, batches, cfg: dict, mode: str = "float32", flags: dict | None = None,
                draws: int = 0):
    """Follow `len(batches)` optimizer steps from `params`. Returns each
    step's loss, the per-leaf norm of the first gradient, and the per-leaf
    norm of the parameters' change over all the steps. SGD with momentum as
    optax states it: trace = g + momentum * trace, p -= lr * trace. `flags`
    are the cell's flags of the lm command; this reference follows no codec,
    so `draws`, which picks a codec's stream of random numbers, changes nothing."""
    if (flags or {}).get("--code", "sgd") != "sgd":
        raise ValueError(f"this reference follows --code sgd only, not {flags['--code']!r}")
    lr, mu = cfg["lr"], cfg["momentum"]

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def sgd(p, trace, g):
        norms = leaf_norms(g)
        trace = {k: g[k] + mu * trace[k] for k in g}
        return {k: p[k] - lr * trace[k] for k in p}, trace, norms

    start = params
    p = jax.tree_util.tree_map(jnp.copy, params)
    trace = jax.tree_util.tree_map(jnp.zeros_like, params)
    losses, grad1 = [], None
    for tokens in batches:
        stages = backward_by_stage(p, jnp.asarray(tokens), cfg, mode)
        losses.append(float(next(stages)))
        norms = {}
        for g in stages:
            names = list(g)
            new_p, new_trace, stage_norms = sgd({k: p[k] for k in names}, {k: trace[k] for k in names}, g)
            p.update(new_p), trace.update(new_trace), norms.update(stage_norms)
        if grad1 is None:
            grad1 = {k: float(v) for k, v in norms.items()}
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
            ((starts + strides * np.arange(cfg["seq_len"])) % cfg["vocab_size"]).astype(np.int32)
        )
    return out
