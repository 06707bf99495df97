"""Expert parallelism: switch-style MoE transformer over an 'ep' mesh axis.

This switch top-1 layer with a fixed capacity is another model than `lm --block
glm`'s expert layer (models/moe.py: top-k, no dropped token, a shared expert).

The reference has no MoE and no model sharding of any kind (SURVEY.md §2.1);
this module adds the third model-sharding axis next to tp and sp. Design:

  ep — experts are sharded over the axis (E/n per chip); every token is
       routed to ONE expert (switch top-1 routing) and rides TWO
       ``all_to_all`` collectives per MoE layer (dispatch + return), the
       canonical expert-parallel pattern on the ICI torus. The ep axis also
       carries batch shards (each (dp, ep) chip computes its own tokens), so
       ep doubles as intra-replica data parallelism.
  dp — batch replica groups exchanging ATOMO-compressed gradients via
       parallel.lm.compressed_dp_update, composing gradient compression
       with expert sharding (each chip compresses its own expert slices).

Static shapes throughout: routing uses a fixed per-chip capacity C per
expert; overflow tokens are dropped (their MLP contribution is zero and the
residual stream carries them — standard switch semantics). The dispatch and
combine tensors are one-hot einsum operands, so the whole layer is three
matmuls + two collectives — MXU-shaped, no gathers.

Gradient discipline (cf. parallel.tp's derivation): the MoE forward crosses
NO psum — only all_to_all, whose transpose is the inverse all_to_all and
exchanges exact cotangents. With the local objective defined as
sum(local ce)/T_replica, expert-leaf grads arrive exact (each chip's expert
slices accumulate cotangents from every chip's tokens through the a2a
transpose) and replicated-leaf grads are shard-partials that one psum over
ep completes. No n-scaling anywhere.
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, PartitionSpec as P

from atomo_tpu.mesh.collectives import all_to_all_tiled
from atomo_tpu.parallel.common import (
    attention_sublayer,
    dense_init as _dense_init,
    layernorm,
    complete_model_axis_grads,
    make_state_specs,
    shard_state,
    shard_tokens_with_spec,
)
from atomo_tpu.parallel.compile import compile_step
from atomo_tpu.parallel.lm import DpExchange, dp_exchange_tail
from atomo_tpu.training.trainer import TrainState, cast_params

# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def init_moe_lm_params(key, cfg: dict) -> Any:
    """Param tree for the MoE LM. ``cfg`` keys: vocab_size, max_len, width,
    depth, num_heads, num_experts, mlp_ratio (default 4)."""
    w = cfg["width"]
    e = cfg["num_experts"]
    f = cfg.get("mlp_ratio", 4) * w
    h, d = cfg["num_heads"], w // cfg["num_heads"]
    keys = iter(jax.random.split(key, 4 + 6 * cfg["depth"]))
    params = {
        "tok_emb": {"embedding": jax.random.normal(next(keys), (cfg["vocab_size"], w)) / math.sqrt(w)},
        "pos_emb": {"embedding": jax.random.normal(next(keys), (cfg["max_len"], w)) / math.sqrt(w)},
        "ln_f": {"scale": jnp.ones((w,), jnp.float32)},
        "head": {"kernel": _dense_init(next(keys), (w, cfg["vocab_size"]))},
    }
    for i in range(cfg["depth"]):
        params[f"block{i}"] = {
            "ln1": {"scale": jnp.ones((w,), jnp.float32)},
            "qkv": {"kernel": _dense_init(next(keys), (w, 3 * h * d))},
            "proj": {"kernel": _dense_init(next(keys), (h * d, w))},
            "ln2": {"scale": jnp.ones((w,), jnp.float32)},
            "router": {"kernel": _dense_init(next(keys), (w, e))},
            # experts stacked on a leading E axis, contracted axis is axis 1
            "up": {"kernel": _dense_init(next(keys), (e, w, f), in_axis=1)},
            "down": {"kernel": _dense_init(next(keys), (e, f, w), in_axis=1)},
        }
    return params


def moe_param_specs(params: Any, ep_axis: str = "ep") -> Any:
    """Experts sharded on their leading E axis; everything else replicated
    (the router must be replicated — every chip routes its own tokens)."""

    def spec(path, leaf) -> P:
        names = [getattr(p, "key", getattr(p, "name", "")) for p in path]
        if "up" in names or "down" in names:
            return P(ep_axis, None, None)
        return P()

    return jax.tree_util.tree_map_with_path(spec, params)


# shared spec/shard scaffolding (parallel.common), under moe's public names
make_moe_state_specs = make_state_specs
shard_moe_state = shard_state


def create_moe_lm_state(
    mesh: Mesh, cfg: dict, optimizer, rng, *, ep_axis: str = "ep"
) -> tuple[TrainState, TrainState]:
    n_ep = mesh.shape[ep_axis]
    if cfg["num_experts"] % n_ep:
        raise ValueError(
            f"num_experts {cfg['num_experts']} not divisible by ep={n_ep}"
        )
    params = init_moe_lm_params(rng, cfg)
    state = TrainState(
        step=jnp.zeros((), jnp.int32),
        params=params,
        batch_stats={},
        opt_state=optimizer.init(params),
    )
    specs = make_moe_state_specs(state, moe_param_specs(params, ep_axis))
    return shard_moe_state(mesh, state, specs), specs


# ---------------------------------------------------------------------------
# the MoE layer
# ---------------------------------------------------------------------------


def moe_mlp(
    moe_params: Any,
    x: jax.Array,
    *,
    capacity: int,
    ep_axis: str | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Switch top-1 MoE MLP on local tokens x (T, W) -> (out (T, W), aux).

    ``moe_params``: {router: (W, E), up: (E|E/n, W, F), down: (E|E/n, F, W)}
    — with ``ep_axis`` set the expert kernels are the LOCAL E/n slices and
    the layer runs inside shard_map, moving token slots with two tiled
    all_to_all collectives; with ``ep_axis=None`` all E experts are local
    (the single-device oracle path, same routing/capacity semantics).

    ``capacity`` C is the per-(chip, expert) slot budget: of this chip's T
    tokens, the first C routed to an expert are processed, the rest are
    dropped (zero MLP output; residual carries them). ``aux`` is the switch
    load-balancing loss E * sum_e f_e * p_e over local tokens.
    """
    t, w = x.shape
    logits = x @ moe_params["router"]["kernel"]  # (T, E)
    n_experts_global = logits.shape[-1]
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    expert = jnp.argmax(probs, axis=-1)  # (T,)
    gate = jnp.take_along_axis(probs, expert[:, None], axis=-1)[:, 0]
    onehot = jax.nn.one_hot(expert, n_experts_global, dtype=jnp.float32)
    # position of each token in its expert's local slot queue
    pos = jnp.einsum("te,te->t", jnp.cumsum(onehot, axis=0) - 1.0, onehot)
    keep = pos < capacity
    dispatch = onehot * keep[:, None]  # (T, E)
    slot = jax.nn.one_hot(pos.astype(jnp.int32), capacity, dtype=jnp.float32)
    d3 = dispatch[:, :, None] * slot[:, None, :]  # (T, E, C)
    combine = d3 * gate[:, None, None]

    # dispatch/combine ride x's dtype so bf16 compute keeps the expert
    # matmuls AND both all_to_all collectives in bf16 (routing math above
    # stays f32); the one-hot structure is exact in any float dtype
    inputs = jnp.einsum("tw,tec->ecw", x, d3.astype(x.dtype))  # (E, C, W)
    if ep_axis is not None:
        # dispatch collective: every chip keeps E/n expert rows and receives
        # the matching C-slot blocks from all n chips -> (E/n, n*C, W)
        # (mesh.collectives.all_to_all_tiled — the shuffle
        # utils.comm_model.moe_all_to_all_wire_bytes prices)
        inputs = all_to_all_tiled(
            inputs, ep_axis, split_axis=0, concat_axis=1
        )
    h = jax.nn.gelu(jnp.einsum("esw,ewf->esf", inputs, moe_params["up"]["kernel"]))
    y = jnp.einsum("esf,efw->esw", h, moe_params["down"]["kernel"])
    if ep_axis is not None:
        # return collective: slots travel back to the chips that own the
        # tokens -> (E, C, W) in this chip's original slot layout
        y = all_to_all_tiled(y, ep_axis, split_axis=1, concat_axis=0)
    out = jnp.einsum("ecw,tec->tw", y, combine.astype(x.dtype))

    # switch aux loss: fraction routed x mean router prob, over local tokens
    f_e = jnp.mean(onehot, axis=0)
    p_e = jnp.mean(probs, axis=0)
    aux = n_experts_global * jnp.sum(f_e * p_e)
    return out.astype(x.dtype), aux


# ---------------------------------------------------------------------------
# MoE LM forward (stock attention blocks + MoE MLP)
# ---------------------------------------------------------------------------


def moe_lm_forward(
    params: Any,
    tokens: jax.Array,
    cfg: dict,
    *,
    capacity: int,
    ep_axis: str | None = None,
) -> tuple[jax.Array, jax.Array]:
    """(B, S) int tokens -> (logits (B, S, V), mean aux loss). Attention is
    local (full sequences per chip); only the MoE MLP crosses chips."""
    b, s = tokens.shape
    x = params["tok_emb"]["embedding"][tokens]
    x = x + params["pos_emb"]["embedding"][jnp.arange(s)][None]
    aux_total = 0.0
    for i in range(cfg["depth"]):
        p = params[f"block{i}"]
        x = attention_sublayer(p, x, cfg["num_heads"])
        y = layernorm(x, p["ln2"]["scale"])
        moe_out, aux = moe_mlp(
            p, y.reshape(b * s, -1), capacity=capacity, ep_axis=ep_axis
        )
        aux_total = aux_total + aux
        x = x + moe_out.reshape(b, s, -1)
    x = layernorm(x, params["ln_f"]["scale"])
    return x @ params["head"]["kernel"], aux_total / cfg["depth"]


# ---------------------------------------------------------------------------
# the dp x ep train step
# ---------------------------------------------------------------------------


def make_moe_lm_train_step(
    cfg: dict,
    optimizer,
    mesh: Mesh,
    state_specs: TrainState,
    codec=None,
    *,
    dp_axis: str = "dp",
    ep_axis: str = "ep",
    capacity_factor: float = 1.25,
    aux_weight: float = 0.01,
    compute_dtype=None,
    aggregate: str = "gather",
    exchange: DpExchange | None = None,
    oracle_parts: bool = False,
):
    """Jitted (state, key, tokens) -> (state, metrics): switch-MoE LM with
    experts sharded over ep and ATOMO-compressed gradient exchange over dp.

    tokens (B, S) are sharded over BOTH dp and ep on the batch axis (ep
    chips are intra-replica data shards). The per-chip expert capacity is
    ceil(capacity_factor * T_local / E).
    """
    n_dp = mesh.shape[dp_axis]
    n_ep = mesh.shape[ep_axis]
    param_specs = state_specs.params

    def grads_fn(state: TrainState, key, tokens):
        b_local, s = tokens.shape
        t_local = b_local * s
        capacity = max(1, math.ceil(capacity_factor * t_local / cfg["num_experts"]))
        my_dp = jax.lax.axis_index(dp_axis)
        k_codec = jax.random.fold_in(jax.random.fold_in(key, state.step), my_dp)

        def loss_fn(params):
            if compute_dtype is not None:
                # bf16 MXU compute, f32 master state (training.trainer
                # contract); router softmax and CE stay f32 internally
                params = cast_params(params, compute_dtype)
            logits, aux = moe_lm_forward(
                params, tokens, cfg, capacity=capacity, ep_axis=ep_axis
            )
            logits = logits.astype(jnp.float32)
            ce = optax.softmax_cross_entropy_with_integer_labels(
                logits[:, :-1], tokens[:, 1:]
            )
            # sum/T_replica (not local mean): the ep shards of one replica
            # partition the replica's tokens, so per-shard objectives SUM to
            # the replica mean and the psum below completes replicated-leaf
            # grads with no n-scaling (module docstring)
            n_valid = n_ep * ce.size
            # aux scaled by ce.size so after /n_valid it contributes
            # aux_weight * (mean aux over ep shards) — commensurate with the
            # mean-CE term instead of vanishing with batch size
            return (jnp.sum(ce) + aux_weight * aux * ce.size) / n_valid

        loss, grads = jax.value_and_grad(loss_fn)(state.params)
        # replicated leaves: psum over ep sums the shard-partials into the
        # replica gradient; expert leaves arrive exact via the a2a transpose
        # (no divide_by: the loss path crosses no psum — module docstring)
        grads = complete_model_axis_grads(grads, param_specs, ep_axis)
        replica_loss = jax.lax.psum(loss, ep_axis)
        return k_codec, grads, replica_loss

    def spmd_step(state: TrainState, key, tokens):
        k_codec, grads, replica_loss = grads_fn(state, key, tokens)
        return dp_exchange_tail(
            optimizer, codec, state, k_codec, grads, replica_loss,
            dp_axis=dp_axis, n_dp=n_dp, aggregate=aggregate,
            exchange=exchange,
        )

    if exchange is not None and exchange.overlap == "delayed":
        from atomo_tpu.parallel.lm import make_delayed_model_axis_step

        return make_delayed_model_axis_step(
            grads_fn, optimizer, codec, mesh,
            dp_axis=dp_axis, n_dp=n_dp, exchange=exchange,
            state_specs=state_specs,
            token_spec=P((dp_axis, ep_axis), None),
            oracle_parts=oracle_parts,
        )

    return compile_step(
        spmd_step,
        mesh,
        in_specs=(state_specs, P(), P((dp_axis, ep_axis), None)),
        out_specs=(state_specs, P()),
        donate_argnums=(0,),
    )


def shard_moe_tokens(
    mesh: Mesh, tokens, dp_axis: str = "dp", ep_axis: str = "ep"
):
    return shard_tokens_with_spec(mesh, tokens, P((dp_axis, ep_axis), None))
