"""Plain reference for the `mellum2-12b-a2.5b` configuration: a decoder of
grouped-query attention with rotary positions, a window in some layers and
YaRN-scaled full attention in the others, over softmax-routed experts; its
loss, gradients and SGD with momentum, in straightforward jax.numpy. float32
at `highest` matmul precision.

Independent of atomo_tpu: it imports nothing of the program and takes from it
neither weights nor tables. Weights come from `init_params` (the benchmark
installs the same arrays into the program before its first step); the names
of the leaves are the "/"-joined paths of the program's parameter tree, which
is all the two share.

The layers, from the published `config.json` (configs/mellum2-12b-a2.5b.json
lists under `assumed` what that file does not say); d the hidden size, no
biases, RMSNorm with eps `rms_norm_eps`:

- block l: h = x + Attn_l(RMSNorm(x)); y = h + Experts(RMSNorm(h)); a last
  RMSNorm before the head; embedding and head untied.
- Attn_l: [q | k | v] = u W_qkv (one leaf, W_q | W_k | W_v side by side),
  q as (S, `num_attention_heads`, `head_dim`), k and v as
  (S, `num_key_value_heads`, `head_dim`). q and k are rotated over the whole
  head, pair j = (x_j, x_{j + D/2}), by the layer kind's rule in
  `rope_parameters`: `sliding_attention` layers by position * b^(-2j/D),
  `full_attention` layers by YaRN's frequencies (below) with cos and sin
  multiplied by `attention_factor`. Query head i reads key/value head
  i // (heads / key-value heads): here k and v are repeated to every query
  head. Scores q.k / sqrt(D) under an explicit (S, S) mask: key t for query
  p where 0 <= p - t, and p - t < `sliding_window` in a sliding layer;
  softmax, o = P v, out = concat(o) W_o.
- YaRN (`factor` s, `original_max_position_embeddings` L0, `beta_fast`,
  `beta_slow`): c(r) = D ln(L0 / (2 pi r)) / (2 ln b); low = floor(c(beta_fast)),
  high = ceil(c(beta_slow)), both clipped to [0, D-1];
  ramp_j = clip((j - low) / (high - low), 0, 1);
  inv_freq_j = theta_j (1 - ramp_j) + theta_j / s ramp_j, theta_j = b^(-2j/D).
- Experts: s = softmax(u W_r) over all `routed_experts_total` outputs; the
  `num_experts_per_tok` largest are chosen; w_e = s_e / sum of the chosen s;
  y = sum over the chosen e of w_e down_e(silu(gate_e u) * up_e u). No shared
  expert, no bias, no scale, no auxiliary loss. **This chip's share**: of the
  router's experts the `num_experts` from `first_expert_held` are held; the
  sum runs over the chosen experts that are held, and what the absent ones
  would add is left out. Here every held expert is applied to **every** row
  and its result multiplied by the row's weight for it, zero where it was not
  chosen: no sort, no gather, no grouped product.

So that three steps at 2 x 8192 tokens fit the chip beside float32 weights
and momentum, the gradient is taken stage by stage (the head over slices of
the rows, each block, the embedding), each stage's vjp from the stage's
input, and a stage's leaves are updated as soon as their gradient is whole.
Attention runs in blocks of queries.

`mode` selects the arithmetic. "float32" is the reference proper. "float8" is
the control of "How correct is decided": every matmul operand but the
router's, which the configuration states in float32, is rounded to float8's
precision and every cotangent on the way back (reference/float8.py).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmarks.reference.float8 import fp8 as _fp8

HI = jax.lax.Precision.HIGHEST
INIT_STD = 0.02
QUERY_BLOCK = 512  # queries per block of the attention
HEAD_ROWS = 4096  # positions of a sequence per slice of the head
EXPERTS_AT_ONCE = 4  # held experts whose results over all rows are alive together


def layer_kinds(cfg: dict) -> list[str]:
    return list(cfg["layer_types"][: cfg["num_hidden_layers"]])


def param_shapes(cfg: dict) -> dict[str, tuple[int, ...]]:
    d, h, hk, dh = (cfg[k] for k in ("hidden_size", "num_attention_heads", "num_key_value_heads", "head_dim"))
    fe, v = cfg["moe_intermediate_size"], cfg["vocab_size"]
    total, held = cfg["routed_experts_total"], cfg["num_experts"]
    if set(cfg["mlp_layer_types"][: cfg["num_hidden_layers"]]) != {"sparse"}:
        raise ValueError("this reference follows layers of routed experts alone")
    shapes = {"tok_emb/embedding": (v, d), "ln_f/scale": (d,), "head/kernel": (d, v)}
    for i in range(cfg["num_hidden_layers"]):
        b = f"block{i}/"
        shapes.update({
            b + "ln1/scale": (d,), b + "ln2/scale": (d,),
            b + "MultiHeadAttention_0/qkv/kernel": (d, (h + 2 * hk) * dh),
            b + "MultiHeadAttention_0/proj/kernel": (h * dh, d),
            b + "moe/router": (d, total),
            b + "moe/gate": (held, d, fe), b + "moe/up": (held, d, fe), b + "moe/down": (held, fe, d),
        })
    return shapes


def init_params(cfg: dict, seed: int, out_shardings=None) -> dict[str, jax.Array]:
    """All leaves on the device in one jitted call from the seed, float32:
    N(0, 0.02) for embeddings, kernels, the router and the experts, ones for
    norm scales."""
    shapes = param_shapes(cfg)
    names = sorted(shapes)

    def make(key):
        out = {}
        for i, name in enumerate(names):
            if name.endswith("/scale"):
                out[name] = jnp.ones(shapes[name], jnp.float32)
            else:
                out[name] = INIT_STD * jax.random.normal(jax.random.fold_in(key, i), shapes[name], jnp.float32)
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


def yarn_ramp_bounds(rule: dict, dim: int) -> tuple[int, int]:
    base, length = float(rule["rope_theta"]), rule["original_max_position_embeddings"]
    pair = lambda turns: dim * math.log(length / (2 * math.pi * turns)) / (2 * math.log(base))  # noqa: E731
    return max(math.floor(pair(rule["beta_fast"])), 0), min(math.ceil(pair(rule["beta_slow"])), dim - 1)


def inverse_frequencies(rule: dict, dim: int):
    """(the dim / 2 pairs' frequencies, the factor on cos and sin) of one of
    `rope_parameters`' rules."""
    pairs = jnp.arange(dim // 2, dtype=jnp.float32)
    theta = float(rule["rope_theta"]) ** (-2.0 * pairs / dim)
    if rule["rope_type"] == "default":
        return theta, 1.0
    if rule["rope_type"] != "yarn":
        raise ValueError(f"unknown rope_type {rule['rope_type']!r}")
    low, high = yarn_ramp_bounds(rule, dim)
    ramp = jnp.clip((pairs - low) / max(high - low, 1e-3), 0.0, 1.0)
    factor = rule.get("attention_factor") or 0.1 * math.log(rule["factor"]) + 1.0
    return theta * (1.0 - ramp) + theta / rule["factor"] * ramp, factor


def rotate(x, rule: dict):
    """x (B, S, heads, D) with the position on axis 1: the pair (x_j, x_{j+D/2})
    turned by position * inv_freq_j, times the rule's factor."""
    half = x.shape[-1] // 2
    freq, factor = inverse_frequencies(rule, x.shape[-1])
    angle = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freq[None, :]
    cos, sin = (factor * f(angle)[None, :, None, :] for f in (jnp.cos, jnp.sin))
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _attention(u, p, kind, cfg, mm):
    h, hk, dh = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    b, s, _ = u.shape
    qkv = mm(u, p["qkv/kernel"], "bsd,de->bse")
    q = qkv[..., : h * dh].reshape(b, s, h, dh)
    k = qkv[..., h * dh : (h + hk) * dh].reshape(b, s, hk, dh)
    v = qkv[..., (h + hk) * dh :].reshape(b, s, hk, dh)
    rule = cfg["rope_parameters"][kind]
    q, k = rotate(q, rule), rotate(k, rule)
    # every query head gets its own copy of the key/value head it reads
    k, v = (jnp.repeat(t, h // hk, axis=2).transpose(0, 2, 1, 3) for t in (k, v))
    q = q.transpose(0, 2, 1, 3)
    window = cfg["sliding_window"] if kind == "sliding_attention" else s
    blk = math.gcd(s, QUERY_BLOCK)

    @jax.checkpoint
    def attend(args):
        q_blk, first = args  # (B, H, blk, D), the block's first position
        scores = mm(q_blk, k, "bhqd,bhkd->bhqk") / math.sqrt(dh)
        behind = (first + jnp.arange(blk))[:, None] - jnp.arange(s)[None, :]
        seen = (behind >= 0) & (behind < window)
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return mm(probs, v, "bhqk,bhkd->bhqd")

    q_blocks = jnp.moveaxis(q.reshape(b, h, s // blk, blk, dh), 2, 0)
    out = jax.lax.map(attend, (q_blocks, jnp.arange(0, s, blk)))
    out = jnp.moveaxis(out, 0, 2).reshape(b, h, s, dh).transpose(0, 2, 1, 3).reshape(b, s, h * dh)
    return mm(out, p["proj/kernel"], "bse,ed->bsd")


def _gated_ffn(u, gate, up, down, mm):
    return mm(jax.nn.silu(mm(u, gate, "...d,df->...f")) * mm(u, up, "...d,df->...f"), down, "...f,fd->...d")


def route(u, router, cfg):
    """(chosen experts (..., k), their weights (..., k)) in float32, whatever
    the mode of the rest."""
    scores = jax.nn.softmax(jnp.einsum("...d,de->...e", u, router, precision=HI), axis=-1)
    _, chosen = jax.lax.top_k(jax.lax.stop_gradient(scores), cfg["num_experts_per_tok"])
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    return chosen, picked / picked.sum(-1, keepdims=True)


def routed_experts(u, p, cfg, mm, first=None):
    """The held experts' part of the layer: each applied to every row, times
    the row's weight for it. And how many of the rows' choices fell on a held
    expert: the assignments the layer computed, none of which may be lost.
    `first` is the first expert held, the configuration's where not given."""
    chosen, weights = route(u, p["router"], cfg)
    first = cfg["first_expert_held"] if first is None else first
    count = p["gate"].shape[0]
    held = jnp.sum((chosen >= first) & (chosen < first + count), dtype=jnp.int32)

    def one(args):
        index, gate, up, down = args
        weight = jnp.sum(jnp.where(chosen == first + index, weights, 0.0), axis=-1)
        return weight[..., None] * _gated_ffn(u, gate, up, down, mm)

    # a few experts at a time: all 16 results of 2 x 8192 rows at once are 2.4 GB
    y = 0.0
    for lo in range(0, count, EXPERTS_AT_ONCE):
        some = slice(lo, lo + EXPERTS_AT_ONCE)
        y = y + jax.lax.map(
            jax.checkpoint(one), (jnp.arange(count)[some], p["gate"][some], p["up"][some], p["down"][some])
        ).sum(axis=0)
    return y, held


@jax.default_matmul_precision("highest")  # on a TPU float32 products run in bfloat16 passes otherwise
def _block(p, x, kind, cfg, mode):
    mm, eps = _matmul(mode), cfg["rms_norm_eps"]
    sub = lambda prefix: {k[len(prefix):]: v for k, v in p.items() if k.startswith(prefix)}  # noqa: E731
    x = x + _attention(_rms_norm(x, p["ln1/scale"], eps), sub("MultiHeadAttention_0/"), kind, cfg, mm)
    y, held = routed_experts(_rms_norm(x, p["ln2/scale"], eps), sub("moe/"), cfg, mm)
    return x + y, held


@jax.default_matmul_precision("highest")
def _head_loss_sum(p, x, targets, cfg, mode):
    """Summed cross-entropy of int32 `targets` (B, S') from x (B, S', d)
    through the last norm and the head."""
    x = _rms_norm(x, p["ln_f/scale"], cfg["rms_norm_eps"])
    logits = _matmul(mode)(x, p["head/kernel"], "bsd,dv->bsv")
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, targets[..., None], axis=-1))


def leaf_norms(tree: dict) -> dict[str, jax.Array]:
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32)))) for k, v in tree.items()}


def backward_by_stage(params: dict, tokens, cfg: dict, mode: str = "float32"):
    """The loss, then each stage's gradient as soon as it is whole: yields the
    loss (a scalar), the assignments to held experts that the blocks computed
    (a count), then {leaf: gradient} of the last norm and the head, each block
    from the last to the first, and the embedding. The caller may update or
    drop a stage's leaves before asking for the next."""
    kinds = layer_kinds(cfg)
    of = lambda prefix: {k[len(prefix):]: v for k, v in params.items() if k.startswith(prefix)}  # noqa: E731
    run = functools.partial(_block, cfg=cfg, mode=mode)
    block = {kind: jax.jit(functools.partial(run, kind=kind)) for kind in set(kinds)}

    @jax.jit
    def head(p, x, targets):
        return jax.value_and_grad(
            lambda p, x: _head_loss_sum(p, x, targets, cfg, mode), argnums=(0, 1))(p, x)

    @functools.partial(jax.jit, static_argnums=(3,), donate_argnums=(2,))
    def block_vjp(p, x, gx, kind):
        _, pull, _ = jax.vjp(functools.partial(run, kind=kind), p, x, has_aux=True)
        return pull(gx)

    table = params["tok_emb/embedding"]
    xs, held = [table[tokens]], 0
    for i, kind in enumerate(kinds):
        x, rows = block[kind](of(f"block{i}/"), xs[-1])
        xs.append(x)
        held = held + rows
    z = xs.pop()
    # the head over slices of the positions: the logits of 2 x 8192 rows over
    # 24,576 ids, their log-softmax and its cotangent would be 4.8 GB at once
    p_head = {k: params[k] for k in ("ln_f/scale", "head/kernel")}
    count = tokens.shape[0] * (tokens.shape[1] - 1)  # the last position predicts nothing
    value, g_head, gzs = 0.0, None, []
    for lo in range(0, tokens.shape[1] - 1, HEAD_ROWS):
        hi = min(lo + HEAD_ROWS, tokens.shape[1] - 1)
        part, (g, gz) = head(p_head, z[:, lo:hi], tokens[:, lo + 1 : hi + 1])
        value = value + part
        g_head = g if g_head is None else jax.tree_util.tree_map(jnp.add, g_head, g)
        gzs.append(gz)
    gx = jnp.pad(jnp.concatenate(gzs, axis=1), ((0, 0), (0, 1), (0, 0))) / count
    del z, gzs
    yield value / count
    yield held
    yield {k: v / count for k, v in g_head.items()}
    for i, kind in reversed(list(enumerate(kinds))):
        g, gx = block_vjp(of(f"block{i}/"), xs.pop(), gx, kind)
        yield {f"block{i}/" + k: v for k, v in g.items()}
    yield {"tok_emb/embedding": jax.jit(lambda gx: jnp.zeros(table.shape, gx.dtype).at[tokens].add(gx))(gx)}


def loss_and_grads(params: dict, tokens, cfg: dict, mode: str = "float32"):
    """The loss and the whole gradient, for tests at sizes where it fits."""
    stages = backward_by_stage(params, jnp.asarray(tokens), cfg, mode)
    value, _held, grads = next(stages), next(stages), {}
    for stage in stages:
        grads.update(stage)
    return value, grads


def train_steps(params: dict, batches, cfg: dict, mode: str = "float32", flags: dict | None = None,
                draws: int = 0):
    """Follow `len(batches)` optimizer steps from `params`. Returns each
    step's loss and its count of assignments to held experts (`held_rows`:
    what the program's `moe_held_row_bytes` counts in bytes; the harness
    compares no counter but `msg_bytes` yet), the per-leaf norm of the first
    gradient, and the per-leaf norm of the parameters' change over all the
    steps. SGD with momentum as optax states it: trace = g + momentum * trace,
    p -= lr * trace. `flags` are the cell's flags of the lm command; this
    reference follows no codec, so `draws`, which picks a codec's stream of
    random numbers, changes nothing."""
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
    losses, held_rows, grad1 = [], [], None
    for tokens in batches:
        stages = backward_by_stage(p, jnp.asarray(tokens), cfg, mode)
        losses.append(float(next(stages)))
        held_rows.append(int(next(stages)))
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
        "held_rows": held_rows,
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
