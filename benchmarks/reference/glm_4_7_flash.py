"""Plain reference for the `glm-4.7-flash` configuration: a decoder of
multi-head latent attention over a sigmoid-routed expert layer, with a
multi-token-prediction module, its loss, gradients and SGD with momentum, in
straightforward jax.numpy. float32 at `highest` matmul precision.

Independent of atomo_tpu: it imports nothing of the program and takes from it
neither weights nor tables. Weights come from `init_params` (the benchmark
installs the same arrays into the program before its first step); the names
of the leaves are the "/"-joined paths of the program's parameter tree, which
is all the two share.

The layers, from the published `config.json` (configs/glm-4.7-flash.json
lists under `assumed` what that file does not say); d the hidden size, no
biases, RMSNorm with eps `rms_norm_eps`:

- block: h = x + MLA(RMSNorm(x)); y = h + F(RMSNorm(h)); F the dense gated
  FFN down(silu(gate u) * up u) in the first `first_k_dense_replace` layers
  and the expert layer in every other; a last RMSNorm before the head.
- MLA, per token and head: c_q = RMSNorm(u W_qa), q = c_q W_qb split into
  q_nope | q_pe; [c_kv | k_pe] = u W_kva, [k_nope | v] = RMSNorm(c_kv) W_kvb,
  k_pe shared by the heads; q_pe and k_pe rotated by position (theta
  `rope_theta`, pairs (j, j + 32) of the 64, no scaling); scores
  [q_nope | rot q_pe] . [k_nope | rot k_pe] / sqrt(256), causal softmax,
  o = P v, out = concat(o) W_o.
- expert layer: s = sigmoid(u W_r); the `num_experts_per_tok` largest of
  s + b are chosen (b the selection bias: in the choice only, no gradient);
  w_e = `routed_scaling_factor` * s_e / (sum of the chosen s + 1e-20);
  F(u) = Shared(u) + sum over the chosen e of w_e Expert_e(u), each a gated
  FFN of `moe_intermediate_size`. **This chip's share**: of the router's
  `routed_experts_total` experts the `n_routed_experts` from
  `first_expert_held` are held; the sum runs over the chosen experts that are
  held, and what the absent ones would add is left out. Here every held
  expert is applied to **every** row and its result multiplied by the row's
  weight for it, zero where it was not chosen: no sort, no gather, no
  grouped product.
- multi-token prediction (DeepSeek-V3 report, section 2.2, depth 1): with
  z_t the last block's output before the last norm,
  m_t = [RMSNorm(Emb(x_{t+1})) | RMSNorm(z_t)] W_eh for t < S-1, one more
  expert block on m (positions 0 .. S-2), its own last RMSNorm, the shared
  head; it predicts x_{t+2}. loss = CE_main + `mtp_loss_weight` * CE_mtp, each
  a mean over its own positions (S-1 and S-2 a sequence).

So that three steps fit the chip beside float32 weights and momentum, the
gradient is taken stage by stage (the two heads, the prediction module, each
block, the embedding), each stage's vjp from the stage's input, and a stage's
leaves are updated as soon as their gradient is whole. The head and the
embedding are used twice, so their gradients are whole last. Attention runs
in blocks of queries.

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
BIAS_RANGE = 0.1  # the selection bias is drawn from U(-0.1, 0.1), so that it changes choices
QUERY_BLOCK = 512  # queries per block of the attention


def _sizes(cfg: dict):
    return (cfg["hidden_size"], cfg["num_attention_heads"], cfg["q_lora_rank"], cfg["kv_lora_rank"],
            cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"])


def block_names(cfg: dict) -> list[tuple[str, bool]]:
    """(prefix, whether it has the expert layer) of every block, the
    prediction module's last."""
    dense = cfg["first_k_dense_replace"]
    out = [(f"block{i}/", i >= dense) for i in range(cfg["num_hidden_layers"])]
    return out + [("mtp_block/", True)] * cfg["num_nextn_predict_layers"]


def param_shapes(cfg: dict) -> dict[str, tuple[int, ...]]:
    d, h, rq, rkv, nope, rope, dv = _sizes(cfg)
    f, fe, v = cfg["intermediate_size"], cfg["moe_intermediate_size"], cfg["vocab_size"]
    total, held, shared = cfg["routed_experts_total"], cfg["n_routed_experts"], cfg["n_shared_experts"]
    if cfg["num_nextn_predict_layers"] not in (0, 1):
        raise ValueError("this reference follows one prediction module or none")
    shapes = {"tok_emb/embedding": (v, d), "ln_f/scale": (d,), "head/kernel": (d, v)}
    for b, experts in block_names(cfg):
        shapes.update({
            b + "ln1/scale": (d,), b + "ln2/scale": (d,),
            b + "mla/q_a/kernel": (d, rq), b + "mla/q_a_norm/scale": (rq,),
            b + "mla/q_b/kernel": (rq, h * (nope + rope)),
            b + "mla/kv_a/kernel": (d, rkv + rope), b + "mla/kv_a_norm/scale": (rkv,),
            b + "mla/kv_b/kernel": (rkv, h * (nope + dv)), b + "mla/o/kernel": (h * dv, d),
        })
        if not experts:
            shapes.update({b + "gate/kernel": (d, f), b + "up/kernel": (d, f), b + "down/kernel": (f, d)})
            continue
        shapes.update({
            b + "moe/router": (d, total), b + "moe/route_bias": (total,),
            b + "moe/gate": (held, d, fe), b + "moe/up": (held, d, fe), b + "moe/down": (held, fe, d),
        })
        if shared:
            shapes.update({b + "shared_gate/kernel": (d, shared * fe), b + "shared_up/kernel": (d, shared * fe),
                           b + "shared_down/kernel": (shared * fe, d)})
    if cfg["num_nextn_predict_layers"]:
        shapes.update({"mtp_enorm/scale": (d,), "mtp_hnorm/scale": (d,), "mtp_norm/scale": (d,),
                       "mtp_proj/kernel": (2 * d, d)})
    return shapes


def init_params(cfg: dict, seed: int, out_shardings=None) -> dict[str, jax.Array]:
    """All leaves on the device in one jitted call from the seed, float32:
    N(0, 0.02) for embeddings, kernels, the router and the experts, ones for
    norm scales, U(-0.1, 0.1) for the selection bias."""
    shapes = param_shapes(cfg)
    names = sorted(shapes)

    def make(key):
        out = {}
        for i, name in enumerate(names):
            k, shape = jax.random.fold_in(key, i), shapes[name]
            if name.endswith("/scale"):
                out[name] = jnp.ones(shape, jnp.float32)
            elif name.endswith("/route_bias"):
                out[name] = jax.random.uniform(k, shape, jnp.float32, -BIAS_RANGE, BIAS_RANGE)
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


def rotate(x, theta: float):
    """x (B, S, ..., D) with the position on axis 1: the pair (x_j, x_{j+D/2})
    turned by position * theta^(-2j/D)."""
    half = x.shape[-1] // 2
    freq = theta ** (-2.0 * jnp.arange(half, dtype=jnp.float32) / x.shape[-1])
    angle = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freq[None, :]
    angle = angle.reshape(1, x.shape[1], *([1] * (x.ndim - 3)), half)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * jnp.cos(angle) - b * jnp.sin(angle),
                            b * jnp.cos(angle) + a * jnp.sin(angle)], axis=-1)


def _latent_attention(u, p, cfg, mm):
    d, h, _, rkv, nope, rope, dv = _sizes(cfg)
    b, s, _ = u.shape
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    c_q = _rms_norm(mm(u, p["q_a/kernel"], "bsd,dr->bsr"), p["q_a_norm/scale"], eps)
    q = mm(c_q, p["q_b/kernel"], "bsr,re->bse").reshape(b, s, h, nope + rope)
    latent = mm(u, p["kv_a/kernel"], "bsd,dr->bsr")
    c_kv = _rms_norm(latent[..., :rkv], p["kv_a_norm/scale"], eps)
    kv = mm(c_kv, p["kv_b/kernel"], "bsr,re->bse").reshape(b, s, h, nope + dv)
    k_pe = rotate(latent[..., rkv:], theta)  # one vector for all heads
    q = jnp.concatenate([q[..., :nope], rotate(q[..., nope:], theta)], axis=-1)
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(k_pe[:, :, None, :], (b, s, h, rope))], axis=-1)
    q, k, v = (t.transpose(0, 2, 1, 3) for t in (q, k, kv[..., nope:]))
    blk = math.gcd(s, QUERY_BLOCK)

    @jax.checkpoint
    def attend(args):
        q_blk, first = args  # (B, H, blk, 256), the block's first position
        scores = mm(q_blk, k, "bhqd,bhkd->bhqk") / math.sqrt(nope + rope)
        causal = (first + jnp.arange(blk))[:, None] >= jnp.arange(s)[None, :]
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        return mm(probs, v, "bhqk,bhkd->bhqd")

    q_blocks = jnp.moveaxis(q.reshape(b, h, s // blk, blk, nope + rope), 2, 0)
    out = jax.lax.map(attend, (q_blocks, jnp.arange(0, s, blk)))
    out = jnp.moveaxis(out, 0, 2).reshape(b, h, s, dv).transpose(0, 2, 1, 3).reshape(b, s, h * dv)
    return mm(out, p["o/kernel"], "bse,ed->bsd")


def _gated_ffn(u, gate, up, down, mm):
    return mm(jax.nn.silu(mm(u, gate, "...d,df->...f")) * mm(u, up, "...d,df->...f"), down, "...f,fd->...d")


def route(u, router, bias, cfg):
    """(chosen experts (..., k), their weights (..., k)) in float32, whatever
    the mode of the rest."""
    scores = jax.nn.sigmoid(jnp.einsum("...d,de->...e", u, router, precision=HI))
    _, chosen = jax.lax.top_k(jax.lax.stop_gradient(scores + bias), cfg["num_experts_per_tok"])
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    return chosen, cfg["routed_scaling_factor"] * picked / (picked.sum(-1, keepdims=True) + 1e-20)


def routed_experts(u, p, cfg, mm):
    """The held experts' part of the layer: each applied to every row, times
    the row's weight for it. And how many of the rows' choices fell on a held
    expert: the assignments the layer computed, none of which may be lost."""
    chosen, weights = route(u, p["router"], p["route_bias"], cfg)
    first, count = cfg["first_expert_held"], p["gate"].shape[0]
    held = jnp.sum((chosen >= first) & (chosen < first + count), dtype=jnp.int32)

    def one(args):
        index, gate, up, down = args
        weight = jnp.sum(jnp.where(chosen == first + index, weights, 0.0), axis=-1)
        return weight[..., None] * _gated_ffn(u, gate, up, down, mm)

    parts = jax.lax.map(jax.checkpoint(one), (jnp.arange(count), p["gate"], p["up"], p["down"]))
    return parts.sum(axis=0), held


@jax.default_matmul_precision("highest")  # on a TPU float32 products run in bfloat16 passes otherwise
def _block(p, x, experts, cfg, mode):
    mm, eps = _matmul(mode), cfg["rms_norm_eps"]
    sub = lambda prefix: {k[len(prefix):]: v for k, v in p.items() if k.startswith(prefix)}  # noqa: E731
    x = x + _latent_attention(_rms_norm(x, p["ln1/scale"], eps), sub("mla/"), cfg, mm)
    u = _rms_norm(x, p["ln2/scale"], eps)
    if not experts:
        return x + _gated_ffn(u, p["gate/kernel"], p["up/kernel"], p["down/kernel"], mm), jnp.int32(0)
    y, held = routed_experts(u, sub("moe/"), cfg, mm)
    if cfg["n_shared_experts"]:
        y = y + _gated_ffn(u, p["shared_gate/kernel"], p["shared_up/kernel"], p["shared_down/kernel"], mm)
    return x + y, held


@jax.default_matmul_precision("highest")
def _head_loss(p, x, targets, cfg, mode, norm):
    """Mean cross-entropy of int32 `targets` (B, S') from x (B, S', d)
    through the last norm named `norm` and the head."""
    x = _rms_norm(x, p[norm], cfg["rms_norm_eps"])
    logits = _matmul(mode)(x, p["head/kernel"], "bsd,dv->bsv")
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, targets[..., None], axis=-1))


@jax.default_matmul_precision("highest")
def _mtp_input(p, embedded_next, z, cfg, mode):
    eps = cfg["rms_norm_eps"]
    both = jnp.concatenate([_rms_norm(embedded_next, p["mtp_enorm/scale"], eps),
                            _rms_norm(z, p["mtp_hnorm/scale"], eps)], axis=-1)
    return _matmul(mode)(both, p["mtp_proj/kernel"], "bse,ed->bsd")


def leaf_norms(tree: dict) -> dict[str, jax.Array]:
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32)))) for k, v in tree.items()}


def backward_by_stage(params: dict, tokens, cfg: dict, mode: str = "float32"):
    """The loss, then each stage's gradient as soon as it is whole: yields the
    loss (a scalar), the assignments to held experts that the expert blocks
    computed (a count), then {leaf: gradient} of the prediction module's norm,
    its block, its input's leaves, the last norm, each block from the last to
    the first, and at the end the head and the embedding, which are used
    twice. The caller may update or drop a stage's leaves before asking for
    the next."""
    blocks = block_names(cfg)[: cfg["num_hidden_layers"]]
    mtp = bool(cfg["num_nextn_predict_layers"])
    weight = cfg["mtp_loss_weight"] if mtp else 0.0
    of = lambda prefix: {k[len(prefix):]: v for k, v in params.items() if k.startswith(prefix)}  # noqa: E731
    run = functools.partial(_block, cfg=cfg, mode=mode)
    block = {kind: jax.jit(functools.partial(run, experts=kind)) for kind in (False, True)}

    @functools.partial(jax.jit, static_argnums=(3, 4))
    def head(p, x, targets, norm, scale):
        value, (g, gx) = jax.value_and_grad(
            lambda p, x: scale * _head_loss(p, x, targets, cfg, mode, norm), argnums=(0, 1))(p, x)
        return value, g, gx

    @functools.partial(jax.jit, static_argnums=(3,), donate_argnums=(2,))
    def block_vjp(p, x, gx, experts):
        _, pull, _ = jax.vjp(functools.partial(run, experts=experts), p, x, has_aux=True)
        return pull(gx)

    table = params["tok_emb/embedding"]
    xs, held = [table[tokens]], 0
    for prefix, experts in blocks:
        x, rows = block[experts](of(prefix), xs[-1])
        xs.append(x)
        held = held + rows
    z = xs.pop()
    value, g_main, gz = head({k: params[k] for k in ("ln_f/scale", "head/kernel")}, z[:, :-1],
                             tokens[:, 1:], "ln_f/scale", 1.0)
    gz = jnp.pad(gz, ((0, 0), (0, 1), (0, 0)))  # the last position predicts nothing
    g_head, g_rows = g_main.pop("head/kernel"), None
    if mtp:
        names = ("mtp_enorm/scale", "mtp_hnorm/scale", "mtp_proj/kernel")
        p_in = {k: params[k] for k in names}
        into = jax.jit(functools.partial(_mtp_input, cfg=cfg, mode=mode))
        m = into(p_in, table[tokens[:, 1:]], z[:, :-1])
        top, rows = block[True](of("mtp_block/"), m)
        held = held + rows
        second, g_mtp, gx = head({k: params[k] for k in ("mtp_norm/scale", "head/kernel")}, top[:, :-1],
                                 tokens[:, 2:], "mtp_norm/scale", weight)
        del top
        value = value + second
        g_head = g_head + g_mtp.pop("head/kernel")
    yield value
    yield held
    yield g_main
    if mtp:
        yield g_mtp
        g, gx = block_vjp(of("mtp_block/"), m, jnp.pad(gx, ((0, 0), (0, 1), (0, 0))), True)
        yield {"mtp_block/" + k: v for k, v in g.items()}
        _, pull = jax.vjp(functools.partial(_mtp_input, cfg=cfg, mode=mode), p_in, table[tokens[:, 1:]], z[:, :-1])
        g, g_rows, gz_mtp = pull(gx)
        yield g
        gz = gz + jnp.pad(gz_mtp, ((0, 0), (0, 1), (0, 0)))
    del z
    gx = gz
    for prefix, experts in reversed(blocks):
        g, gx = block_vjp(of(prefix), xs.pop(), gx, experts)
        yield {prefix + k: v for k, v in g.items()}

    @jax.jit
    def embedding(gx, g_rows):
        g = jnp.zeros(table.shape, gx.dtype).at[tokens].add(gx)
        return g if g_rows is None else g.at[tokens[:, 1:]].add(g_rows)

    yield {"tok_emb/embedding": embedding(gx, g_rows), "head/kernel": g_head}


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
    steps. SGD with momentum as
    optax states it: trace = g + momentum * trace, p -= lr * trace. The
    selection bias has a zero gradient and so stays. `flags` are the cell's
    flags of the lm command; this reference follows no codec, so `draws`,
    which picks a codec's stream of random numbers, changes nothing."""
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
