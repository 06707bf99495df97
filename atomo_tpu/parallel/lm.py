"""Long-context LM training: dp×sp SPMD with compressed gradient exchange.

The capability composition the reference cannot express (DP-only, CV-only —
SURVEY.md §2.1): a 2-D mesh where

  dp — batch replicas exchanging ATOMO-compressed gradients (all_gather of
       codec payloads, identical decode+mean on every chip — exactly the
       replicated-PS semantics of parallel.replicated)
  sp — the sequence dimension of each replica's batch, attended over with
       exact ring attention (parallel.ring), gradients dense-psum'd: the sp
       reduction *forms* one replica's gradient, so it is intra-replica and
       not part of the compressed inter-replica exchange.

Loss is the exact global next-token cross-entropy: shard-boundary targets
are fetched from the ring neighbor with ppermute, and the final position of
the last shard is masked, so sharded and unsharded training compute the same
scalar.
"""

from __future__ import annotations

import dataclasses
import operator
from functools import partial


import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from atomo_tpu.codecs import (
    decode_mean_tree,
    decode_tree,
    encode_tree,
    encode_tree_streamed,
    tree_nbytes,
)
from atomo_tpu.mesh.collectives import ppermute_ring
from atomo_tpu.parallel.common import plan_layer_buckets
from atomo_tpu.parallel.compile import compile_step
from atomo_tpu.parallel.ring import ATTENTION_IMPLS
from atomo_tpu.training.trainer import TrainState, cast_params
from atomo_tpu.utils.tracing import named_phase


def sp_boundary_targets_and_mask(tokens, sp_axis: str, n_sp: int):
    """Boundary-exact next-token targets for a sequence-sharded batch:
    each shard's last target is the FIRST token of the next shard
    (ppermute), and the global final position (last shard's last column)
    is masked out. Returns (targets, valid) of shape (B, S_local) — the
    contract shared by the dp x sp and dp x tp x sp loss functions, so
    sharded and unsharded training compute the same scalar CE."""
    # one ring hop (mesh.collectives.ring_perm — the SAME rotation every
    # ring schedule uses): shard i's first column arrives at shard i-1
    nxt = ppermute_ring(tokens[:, :1], sp_axis, n_sp)
    targets = jnp.concatenate([tokens[:, 1:], nxt], axis=1)
    valid = jnp.ones(targets.shape, jnp.float32)
    is_last = (jax.lax.axis_index(sp_axis) == n_sp - 1).astype(jnp.float32)
    valid = valid.at[:, -1].set(1.0 - is_last)
    return targets, valid


def compressed_dp_update(
    optimizer,
    codec,
    state: TrainState,
    k_codec,
    grads,
    loss,
    *,
    dp_axis: str,
    n_dp: int,
    aggregate: str = "gather",
):
    """The shared per-shard tail of every compressed-DP train step: encode
    this replica's (already-completed) gradient, all_gather payloads over
    dp, decode+mean identically everywhere, apply the optimizer — or dense
    pmean when ``codec`` is None. Returns (new_state, metrics). Used by the
    dp x sp (make_lm_train_step) and dp x tp (parallel.tp) steps; gradients
    may be model-sharded on other mesh axes — each shard exchanges its own
    slice over dp, so compression composes with model sharding.

    ``aggregate="psum"`` with a codec keeps the encode->decode round trip
    (the quantization-noise semantics) but exchanges DENSE gradients with a
    pmean — the mode ``--aggregate auto`` picks on fast ICI, where the
    factor gather's codec tax loses to the wire saving
    (utils/comm_model.choose_aggregate)."""
    dense_bytes = tree_nbytes(grads)
    # the same phase scopes as compressed_dp_exchange: `report timeline`
    # reads this tail's programs too
    if codec is None:
        with named_phase("exchange"):
            mean_grads = jax.lax.pmean(grads, dp_axis)
        msg_bytes = dense_bytes
    elif aggregate == "psum":
        with named_phase("encode"):
            payloads, _ = encode_tree(codec, k_codec, grads)
        with named_phase("decode"):
            decoded = decode_tree(codec, payloads, grads)
        with named_phase("exchange"):
            mean_grads = jax.lax.pmean(decoded, dp_axis)
        msg_bytes = dense_bytes  # the wire truly carries dense bytes here
    elif aggregate == "gather":
        with named_phase("encode"):
            payloads, stats = encode_tree(codec, k_codec, grads)
        msg_bytes = stats.payload_bytes
        with named_phase("exchange"):
            gathered = jax.lax.all_gather(payloads, dp_axis)
        # fused decode_mean where the codec provides it (SVD: one
        # (m, N·k)@(N·k, n) matmul), vmap-decode + mean otherwise
        with named_phase("decode_mean"):
            mean_grads = decode_mean_tree(codec, gathered, grads, n_dp)
    else:
        raise ValueError(f"unknown aggregate mode {aggregate!r}")

    with named_phase("update"):
        updates, new_opt = optimizer.update(mean_grads, state.opt_state, state.params)
        new_params = optax.apply_updates(state.params, updates)
    metrics = {
        "loss": jax.lax.pmean(loss, dp_axis),
        # float32, not int32: byte counts are static Python ints at trace
        # time and a >=2 GiB per-shard gradient (the large-model regime tp
        # exists for) would overflow int32 at jit time
        "msg_bytes": jnp.asarray(msg_bytes, jnp.float32),
        "dense_bytes": jnp.asarray(dense_bytes, jnp.float32),
    }
    new_state = TrainState(
        step=state.step + 1,
        params=new_params,
        batch_stats=state.batch_stats,
        opt_state=new_opt,
    )
    return new_state, metrics


@dataclasses.dataclass(frozen=True)
class DpExchange:
    """The data-parallel gradient-exchange recipe of a model-axis step —
    the knob vector of the compressed stack, carried as ONE static value.

    Passing ``exchange=`` to a model-axis step builder routes its dp tail
    through :func:`compressed_dp_exchange` (the scoped, full-stack tail:
    ring aggregation, stream-encode buckets, per-leaf budget codecs all
    compose); ``exchange=None`` keeps the legacy
    :func:`compressed_dp_update` tail byte-for-byte. The fields mirror the
    replicated family's knob names (``utils.comm_model.candidate_name``
    algebra), so a controller candidate maps onto this dataclass
    field-for-field.

    ``overlap="delayed"`` threads the replicated loop's consume-next-step
    carry through the step (:func:`delayed_dp_exchange`): the dp exchange
    consumes the PREVIOUS step's encoded payload while this step's
    backward (and, on dp-pp, the pipeline's drain ticks) runs, so the
    exposed exchange time drops to ``max(0, exchange - compute_tail)``.
    ``overlap="off"`` (the default) is byte-identical HLO to a DpExchange
    that predates the field (tested).
    """

    aggregate: str = "gather"  # gather | psum | ring
    ring_bucket_size: int = 0
    stream_encode: bool = False
    stream_bucket_bytes: int = 4 << 20
    overlap: str = "off"  # off | delayed

    def __post_init__(self):
        if self.aggregate not in ("gather", "psum", "ring"):
            raise ValueError(
                f"unknown aggregate mode {self.aggregate!r}; the model-axis "
                "dp exchange ships gather | psum | ring"
            )
        if self.overlap not in ("off", "delayed"):
            raise ValueError(
                f"unknown overlap mode {self.overlap!r}; the model-axis dp "
                "exchange ships off | delayed"
            )
        if self.overlap == "delayed" and self.aggregate == "psum":
            raise ValueError(
                "overlap='delayed' carries an ENCODED payload between "
                "steps; the dense psum exchange has no payload to carry — "
                "use aggregate='gather' or 'ring'"
            )


def compressed_dp_exchange(
    optimizer,
    codec,
    state: TrainState,
    k_codec,
    grads,
    loss,
    *,
    dp_axis: str,
    n_dp: int,
    exchange: DpExchange,
):
    """The full-stack dp tail of the model-axis steps: the same contract as
    :func:`compressed_dp_update` (encode this shard's completed gradient,
    exchange over dp, decode+mean identically everywhere, apply the
    optimizer) with the rest of the compressed stack composed in —

      * ``named_phase`` scopes (``encode`` / ``exchange`` / ``decode_mean``
        / ``ring_exchange_decode``) label the traced regions, so ``report
        timeline`` finds the same anchors in every model-axis program
        family that it finds in the replicated family;
      * ``aggregate="ring"`` streams payload chunks around the dp ring
        (:func:`atomo_tpu.parallel.replicated._ring_stream_mean` — the
        same canonical staged mean, so replicas stay bit-equal);
      * ``stream_encode`` encodes per layer bucket
        (:func:`atomo_tpu.parallel.common.plan_layer_buckets` — payloads
        bit-identical to the monolithic encode, dataflow overlappable);
      * per-leaf budget codecs (``--budget-alloc variance``'s PerLeafCodec)
        flow through ``encode_tree``'s per-leaf resolution untouched.

    Gradients may be model-sharded on other mesh axes: each shard
    exchanges its own completed slice over dp, exactly as the legacy tail.
    """
    dense_bytes = tree_nbytes(grads)
    agg = exchange.aggregate
    if codec is None:
        if agg == "ring":
            raise ValueError(
                "aggregate='ring' needs a codec: the ring streams encoded "
                "payload chunks; a dense ring would just be a slower pmean"
            )
        with named_phase("exchange"):
            mean_grads = jax.lax.pmean(grads, dp_axis)
        msg_bytes = dense_bytes
    elif agg == "psum":
        with named_phase("encode"):
            payloads, _ = encode_tree(codec, k_codec, grads)
            decoded = decode_tree(codec, payloads, grads)
        with named_phase("exchange"):
            mean_grads = jax.lax.pmean(decoded, dp_axis)
        msg_bytes = dense_bytes  # the wire truly carries dense bytes here
    else:
        # stream_encode: per-layer-bucket encode (reverse-topological
        # plan, global-leaf-index keys) — bit-identical payloads whose
        # dataflow lets each bucket's encode run under backprop of the
        # layers feeding the next bucket; off keeps the monolithic call
        # byte-for-byte (the replicated family's exact idiom)
        lplan = (
            plan_layer_buckets(grads, exchange.stream_bucket_bytes)
            if exchange.stream_encode
            else None
        )
        with named_phase("encode"):
            if exchange.stream_encode:
                payloads, stats = encode_tree_streamed(
                    codec, k_codec, grads, lplan
                )
            else:
                payloads, stats = encode_tree(codec, k_codec, grads)
        msg_bytes = stats.payload_bytes
        if agg == "gather":
            with named_phase("exchange"):
                gathered = jax.lax.all_gather(payloads, dp_axis)
            with named_phase("decode_mean"):
                mean_grads = decode_mean_tree(codec, gathered, grads, n_dp)
        else:  # ring
            # lazy: replicated.py does not import this module, but a
            # module-level import here would cycle the other way around
            # through parallel/__init__
            from atomo_tpu.parallel.replicated import (
                _ring_stream_mean,
                _ring_stream_mean_layered,
            )

            my = jax.lax.axis_index(dp_axis)
            with named_phase("ring_exchange_decode"):
                if exchange.stream_encode:
                    mean_grads, _ = _ring_stream_mean_layered(
                        codec, payloads, grads, lplan,
                        axis=dp_axis, n_dev=n_dp, my=my, n_contrib=n_dp,
                        bucket_size=exchange.ring_bucket_size,
                    )
                else:
                    mean_grads, _ = _ring_stream_mean(
                        codec, payloads, grads,
                        axis=dp_axis, n_dev=n_dp, my=my, n_contrib=n_dp,
                        bucket_size=exchange.ring_bucket_size,
                    )

    with named_phase("update"):
        updates, new_opt = optimizer.update(mean_grads, state.opt_state, state.params)
        new_params = optax.apply_updates(state.params, updates)
    metrics = {
        "loss": jax.lax.pmean(loss, dp_axis),
        # float32, not int32 — same overflow rationale as the legacy tail
        "msg_bytes": jnp.asarray(msg_bytes, jnp.float32),
        "dense_bytes": jnp.asarray(dense_bytes, jnp.float32),
    }
    new_state = TrainState(
        step=state.step + 1,
        params=new_params,
        batch_stats=state.batch_stats,
        opt_state=new_opt,
    )
    return new_state, metrics


def dp_exchange_tail(
    optimizer, codec, state, k_codec, grads, loss, *,
    dp_axis: str, n_dp: int, aggregate: str, exchange=None,
):
    """Dispatch one model-axis step's dp tail: the legacy
    :func:`compressed_dp_update` when ``exchange`` is None (byte-for-byte
    the pre-refactor program), :func:`compressed_dp_exchange` when the
    caller hands a :class:`DpExchange` (``exchange.aggregate`` wins over
    the legacy ``aggregate`` string — one source of truth per path)."""
    if exchange is None:
        return compressed_dp_update(
            optimizer, codec, state, k_codec, grads, loss,
            dp_axis=dp_axis, n_dp=n_dp, aggregate=aggregate,
        )
    return compressed_dp_exchange(
        optimizer, codec, state, k_codec, grads, loss,
        dp_axis=dp_axis, n_dp=n_dp, exchange=exchange,
    )


# ---------------------------------------------------------------------------
# delayed overlap for the model-axis steps: the replicated loop's
# consume-next-step carry (parallel.replicated.OverlapCarry/DelayedState)
# generalized to every dp x {sp,tp,ep,pp} layout
# ---------------------------------------------------------------------------


def _delayed_produce_payload(codec, k_codec, grads, exchange: DpExchange):
    """PRODUCE half of the delayed exchange: encode THIS step's completed
    gradient under the same ``encode`` anchor (and the same stream-encode
    restructure) as the blocking tail — the payload at step t is
    bit-identical to what blocking mode would have put on the wire at
    step t (same ``k_codec`` fold, same plan). Returns the carry-shaped
    payload (leading per-device axis of length 1) and the byte stats."""
    with named_phase("encode"):
        if exchange.stream_encode:
            payloads, stats = encode_tree_streamed(
                codec, k_codec, grads,
                plan_layer_buckets(grads, exchange.stream_bucket_bytes),
            )
        else:
            payloads, stats = encode_tree(codec, k_codec, grads)
    payload_x = jax.tree_util.tree_map(lambda a: a[None], payloads)
    return payload_x, stats


def _delayed_consume(
    optimizer, codec, train, prev_payload, valid, *,
    dp_axis: str, n_dp: int, exchange: DpExchange,
):
    """CONSUME half: exchange -> decode-mean -> optimizer update on the
    PREVIOUS step's payload, computed from STEP-START values only. The
    ``optimization_barrier`` pins that boundary (the replicated loop's
    exact idiom): the chain is dataflow-independent of this step's
    forward/backward — which is the overlap — and the separately-jitted
    oracle's apply program compiles to the same arithmetic (bit-for-bit,
    tested). Stream-encode restructures the PRODUCE side only; payloads
    are bit-identical to the monolithic encode, so the consume side
    stays monolithic (the replicated family's documented choice).

    Step 0 consumes nothing (``valid=0``): params/opt state hold and
    ``metrics["skipped"]`` is 1 — the stale-by-one schedule's defined
    start."""
    from atomo_tpu.training.resilience import select_state

    params, opt_state, prev_payload, valid = jax.lax.optimization_barrier(
        (train.params, train.opt_state, prev_payload, valid)
    )
    if exchange.aggregate == "gather":
        with named_phase("exchange"):
            gathered = jax.lax.all_gather(prev_payload, dp_axis)
        with named_phase("decode_mean"):
            mean_grads = decode_mean_tree(codec, gathered, params, n_dp)
    else:  # ring — the same canonical staged mean as the blocking tail
        from atomo_tpu.parallel.replicated import _ring_stream_mean

        my = jax.lax.axis_index(dp_axis)
        with named_phase("ring_exchange_decode"):
            mean_grads, _ = _ring_stream_mean(
                codec, prev_payload, params,
                axis=dp_axis, n_dev=n_dp, my=my, n_contrib=n_dp,
                bucket_size=exchange.ring_bucket_size,
            )
    with named_phase("update"):
        updates, new_opt = optimizer.update(mean_grads, opt_state, params)
        new_params = optax.apply_updates(params, updates)
    consume_ok = valid > 0  # step 0: nothing in flight -> full skip
    new_params = select_state(consume_ok, new_params, params)
    new_opt = select_state(consume_ok, new_opt, opt_state)
    new_train = TrainState(
        step=train.step + 1,
        params=new_params,
        batch_stats=train.batch_stats,
        opt_state=new_opt,
    )
    return new_train, {"skipped": 1.0 - consume_ok.astype(jnp.float32)}


def delayed_dp_exchange(
    optimizer, codec, train, carry, k_codec, grads, loss, *,
    dp_axis: str, n_dp: int, exchange: DpExchange,
):
    """The fused delayed dp tail of a model-axis step: produce this
    step's payload (:func:`_delayed_produce_payload`), consume the
    carried one (:func:`_delayed_consume`), return
    ``(new_train, new_carry, metrics)``. The carry holds the ENCODED
    payload on purpose (the :class:`~atomo_tpu.parallel.replicated.
    OverlapCarry` contract): the consume chain reads only step-start
    values, so the scheduler can run the exchange+decode underneath this
    step's forward/backward — and, on dp-pp, underneath the pipeline's
    drain ticks."""
    from atomo_tpu.parallel.replicated import OverlapCarry

    payload_x, stats = _delayed_produce_payload(codec, k_codec, grads, exchange)
    prev_payload = jax.tree_util.tree_map(
        lambda a: jnp.squeeze(a, 0), carry.payload
    )
    new_train, am = _delayed_consume(
        optimizer, codec, train, prev_payload, carry.valid,
        dp_axis=dp_axis, n_dp=n_dp, exchange=exchange,
    )
    metrics = {
        "loss": jax.lax.pmean(loss, dp_axis),
        "msg_bytes": jnp.asarray(stats.payload_bytes, jnp.float32),
        "dense_bytes": jnp.asarray(tree_nbytes(grads), jnp.float32),
        **am,
    }
    new_carry = OverlapCarry(
        payload=payload_x, ok=carry.ok, valid=jnp.float32(1.0)
    )
    return new_train, new_carry, metrics


def model_axis_carry_specs(mesh: Mesh):
    """The carry's PartitionSpec tree on a model-axis mesh: the leading
    per-device axis sharded over ALL mesh axes (every device owns the one
    row holding its own encoded slice — uniform across layouts because
    each shard encodes its model-sharded gradient locally), the scalar
    ``valid`` replicated."""
    from atomo_tpu.parallel.replicated import OverlapCarry

    axes = tuple(mesh.axis_names)
    return OverlapCarry(payload=P(axes), ok=P(axes), valid=P())


def place_model_axis_carry(mesh: Mesh, carry):
    """Place a host-side carry onto the mesh (fresh init, ``--resume``
    and the reshard drain all MUST place identically, or a restored
    trajectory drifts from an uninterrupted one — the replicated
    ``_place_carry`` contract on the model-axis sharding)."""
    from atomo_tpu.parallel.replicated import OverlapCarry

    sh = NamedSharding(mesh, P(tuple(mesh.axis_names)))
    return OverlapCarry(
        payload=jax.tree_util.tree_map(
            lambda a: jax.device_put(jnp.asarray(a), sh), carry.payload
        ),
        ok=jax.device_put(jnp.asarray(carry.ok), sh),
        valid=jax.device_put(
            jnp.asarray(carry.valid), NamedSharding(mesh, P())
        ),
    )


def init_model_axis_delayed_state(mesh: Mesh, state, codec):
    """Wrap a (possibly model-sharded) LM train state into the fresh
    :class:`~atomo_tpu.parallel.replicated.DelayedState` a delayed
    model-axis step consumes: zero payload rows shaped by eval_shape of
    the codec's encode over each device's LOCAL param-shard shapes (the
    gradient the device will encode), all-healthy flags, ``valid=0``."""
    from atomo_tpu.parallel.replicated import DelayedState, OverlapCarry

    n_total = 1
    for a in mesh.axis_names:
        n_total *= mesh.shape[a]

    def local_sds(leaf):
        return jax.ShapeDtypeStruct(
            tuple(leaf.sharding.shard_shape(leaf.shape)), leaf.dtype
        )

    local = jax.tree_util.tree_map(local_sds, state.params)
    shapes = jax.eval_shape(
        lambda p: encode_tree(codec, jax.random.PRNGKey(0), p)[0], local
    )
    payload = jax.tree_util.tree_map(
        lambda s: jnp.zeros((n_total,) + tuple(s.shape), s.dtype), shapes
    )
    carry = OverlapCarry(
        payload=payload,
        ok=jnp.ones((n_total,), jnp.float32),
        valid=jnp.float32(0.0),
    )
    return DelayedState(
        train=state, carry=place_model_axis_carry(mesh, carry)
    )


def make_delayed_model_axis_step(
    grads_fn, optimizer, codec, mesh: Mesh, *,
    dp_axis: str, n_dp: int, exchange: DpExchange,
    state_specs, token_spec, oracle_parts: bool = False,
):
    """Compile the delayed variant of a model-axis family: ``grads_fn``
    is the family's forward/backward closure — ``(train, key, tokens) ->
    (k_codec, grads, loss)`` with grads COMPLETED over the model axes —
    and this wrapper threads the stale-by-one carry around its dp tail.
    The jitted step is ``(DelayedState, key, tokens) -> (DelayedState,
    metrics)`` with the carry sharded per :func:`model_axis_carry_specs`.

    ``oracle_parts=True`` returns ``{"produce", "apply"}`` instead: the
    SAME closures, separately jitted — the two-program eager oracle
    the tests drive host-side to prove the fused program's stale-by-one
    schedule bit-exact (the replicated family's ``_oracle_parts``
    precedent)."""
    if codec is None:
        raise ValueError(
            "overlap='delayed' needs a codec: the carry holds encoded "
            "payloads (a dense delayed exchange has nothing to carry)"
        )
    from atomo_tpu.parallel.replicated import DelayedState

    sspec = state_specs if state_specs is not None else P()
    carry_spec = model_axis_carry_specs(mesh)
    axes_p = carry_spec.payload

    if oracle_parts:

        def produce_prog(train, key, tokens):
            k_codec, grads, loss = grads_fn(train, key, tokens)
            payload_x, stats = _delayed_produce_payload(
                codec, k_codec, grads, exchange
            )
            pm = {
                "loss": jax.lax.pmean(loss, dp_axis),
                "msg_bytes": jnp.asarray(stats.payload_bytes, jnp.float32),
                "dense_bytes": jnp.asarray(tree_nbytes(grads), jnp.float32),
            }
            return payload_x, pm

        def apply_prog(train, payload_x, valid):
            prev = jax.tree_util.tree_map(
                lambda a: jnp.squeeze(a, 0), payload_x
            )
            return _delayed_consume(
                optimizer, codec, train, prev, valid,
                dp_axis=dp_axis, n_dp=n_dp, exchange=exchange,
            )

        produce_j = compile_step(
            produce_prog, mesh,
            in_specs=(sspec, P(), token_spec),
            out_specs=(axes_p, P()),
            check_vma=False,
        )
        apply_j = compile_step(
            apply_prog, mesh,
            in_specs=(sspec, axes_p, P()),
            out_specs=(sspec, P()),
            check_vma=False,
        )
        return {"produce": produce_j, "apply": apply_j}

    def spmd_delayed(d, key, tokens):
        k_codec, grads, loss = grads_fn(d.train, key, tokens)
        new_train, new_carry, metrics = delayed_dp_exchange(
            optimizer, codec, d.train, d.carry, k_codec, grads, loss,
            dp_axis=dp_axis, n_dp=n_dp, exchange=exchange,
        )
        return DelayedState(train=new_train, carry=new_carry), metrics

    d_spec = DelayedState(train=sspec, carry=carry_spec)
    return compile_step(
        spmd_delayed, mesh,
        in_specs=(d_spec, P(), token_spec),
        out_specs=(d_spec, P()),
        donate_argnums=(0,),
        check_vma=False,
    )


# The collections a layer sows a count into, each with how the step joins it:
# over the layers of one replica, then over the dp replicas. The layer chooses
# the collection; no name is read here.
SOWN_COUNTS = {
    # from shapes as the layer is traced (`lin_state_bytes`,
    # `attn_score_bytes`): constants, alike on every replica, and joined
    # by `+` so that their sum stays a constant of the step's text
    "counters": (operator.add, None),
    # from the data as the step runs (the expert layer's rows): each
    # replica's own, so their sum and their most
    "counts": (operator.add, jax.lax.psum),
    "counts_max": (jnp.maximum, jax.lax.pmax),
}


def keep_float32(cast, master, names):
    """``cast`` with the leaves named in ``names`` taken from ``master``."""
    return jax.tree_util.tree_map_with_path(
        lambda path, low, full: full if path[-1].key in names else low, cast, master
    )


def make_lm_train_step(
    lm_config: dict,
    optimizer,
    mesh: Mesh,
    codec=None,
    *,
    dp_axis: str = "dp",
    sp_axis: str = "sp",
    attn_impl: str = "ring",
    compute_dtype=None,
    aggregate: str = "gather",
    exchange: DpExchange | None = None,
    oracle_parts: bool = False,
):
    """Jitted (state, key, tokens) -> (state, metrics) with tokens (B, S)
    sharded batch-over-dp and sequence-over-sp. ``lm_config`` are
    TransformerLM kwargs (attention_fn is injected here). ``attn_impl``
    selects the sequence-parallel strategy: "ring" (ppermute K/V rotation,
    O(S/n) memory) or "ulysses" (two all_to_all collectives, blockwise
    local attention on H/n heads — see parallel.ring.ulysses_attention)."""
    if attn_impl not in ATTENTION_IMPLS:
        raise ValueError(
            f"unknown attn_impl {attn_impl!r}; expected one of "
            f"{sorted(ATTENTION_IMPLS)}"
        )
    # lazy: models.transformer imports parallel.ring, so a module-level
    # import here would cycle through parallel/__init__ (which exports tp,
    # which imports this module)
    from atomo_tpu.models.moe import FLOAT32_LEAVES
    from atomo_tpu.models.transformer import TransformerLM

    n_sp = mesh.shape[sp_axis]
    n_dp = mesh.shape[dp_axis]
    latent_moe = lm_config.get("latent_moe")
    if n_sp > 1 and latent_moe is not None:
        raise ValueError(
            f"the `mla` layers' prediction module reads each token's successor "
            f"in its own shard: {sp_axis}={n_sp} needs latent_moe unset"
        )
    experts = lm_config.get("experts")
    if n_sp > 1 and experts is not None:
        raise ValueError(
            f"the `experts` FFN sorts all of a replica's tokens, which the {sp_axis!r} "
            f"ring holds in shards: {sp_axis}={n_sp} needs experts unset"
        )
    if n_sp > 1 and (lm_config.get("kv_heads") or "window" in lm_config.get("layer_pattern", ())
                     or lm_config.get("positions") == "rotary"):
        raise ValueError(
            f"a window, grouped key/value heads and rotary positions are the "
            f"one-device attention core's: {sp_axis}={n_sp} needs kv_heads and rope "
            "unset and no `window` layer in layer_pattern"
        )
    if n_sp > 1 and "linear" in lm_config.get("layer_pattern", ()):
        raise ValueError(
            f"a linear-attention layer carries its state along the sequence, "
            f"which the {sp_axis!r} ring does not pass on: {sp_axis}={n_sp} "
            "needs every layer of layer_pattern to be 'full'"
        )

    def grads_and_counters(state: TrainState, key, tokens):
        model = TransformerLM(
            **lm_config,
            attention_fn=partial(
                ATTENTION_IMPLS[attn_impl], axis_name=sp_axis,
                axis_size=n_sp, causal=True,
            ),
        )
        my_dp = jax.lax.axis_index(dp_axis)
        k_codec = jax.random.fold_in(
            jax.random.fold_in(key, state.step), my_dp
        )

        def loss_fn(params):
            if compute_dtype is not None:
                # bf16 MXU compute, f32 master state; token ids are integer
                # inputs, so only the params need the cast
                master, params = params, cast_params(params, compute_dtype)
                if latent_moe is not None or experts is not None:  # the gate computes in float32
                    params = keep_float32(params, master, FLOAT32_LEAVES)
            s_local = tokens.shape[1]
            # what the layers count as they are traced or run: SOWN_COUNTS
            logits, sown = model.apply(
                {"params": params},
                tokens,
                train=True,
                pos_offset=jax.lax.axis_index(sp_axis) * s_local,
                mutable=list(SOWN_COUNTS),
            )
            mtp_logits = None
            if latent_moe is not None and latent_moe.mtp_depth:
                logits, mtp_logits = logits
            if compute_dtype is not None:
                logits = logits.astype(jnp.float32)
            targets, valid = sp_boundary_targets_and_mask(tokens, sp_axis, n_sp)
            ce = optax.softmax_cross_entropy_with_integer_labels(logits, targets)
            total = jax.lax.psum(jnp.sum(valid), sp_axis)
            loss = jax.lax.psum(jnp.sum(ce * valid), sp_axis) / total
            if mtp_logits is not None:
                # the prediction module's logits at t are of token t+2: a mean
                # of its own over the S-2 positions that have one
                ce = optax.softmax_cross_entropy_with_integer_labels(
                    mtp_logits.astype(jnp.float32), jnp.roll(tokens, -2, axis=1)
                )
                loss = loss + latent_moe.mtp_weight * jnp.mean(ce[:, :-2])
            return loss, sown

        with named_phase("forward_backward"):
            (loss, sown), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                state.params
            )
        # sp-PMEAN completes THIS replica's gradient (intra-replica, dense).
        # Mean, not sum: under shard_map the transpose of the loss psum is
        # itself a psum, so each shard's per-shard grads already carry an
        # n_sp factor (the replicated seed is summed across shards); summing
        # them again would scale the gradient by n_sp — a silent effective-LR
        # inflation verified empirically (tests/test_ring.py oracle parity).
        grads = jax.lax.pmean(grads, sp_axis)
        counters: dict = {}
        for collection, (join, over_dp) in SOWN_COUNTS.items():
            joined: dict = {}
            for path, value in jax.tree_util.tree_leaves_with_path(sown.get(collection, {})):
                name = path[-2].key  # .../<module>/<counter>/<index in its tuple>
                joined[name] = join(joined.get(name, 0.0), value)
            if over_dp is not None:
                joined = {name: over_dp(value, dp_axis) for name, value in joined.items()}
            counters.update(joined)
        return k_codec, grads, loss, counters

    def grads_fn(state: TrainState, key, tokens):
        # the delayed builder's closure, shared with tp/pp/ep: no counters
        return grads_and_counters(state, key, tokens)[:3]

    def spmd_step(state: TrainState, key, tokens):
        k_codec, grads, loss, counters = grads_and_counters(state, key, tokens)
        new_state, metrics = dp_exchange_tail(
            optimizer, codec, state, k_codec, grads, loss,
            dp_axis=dp_axis, n_dp=n_dp, aggregate=aggregate,
            exchange=exchange,
        )
        return new_state, {**metrics, **counters}

    if exchange is not None and exchange.overlap == "delayed":
        return make_delayed_model_axis_step(
            grads_fn, optimizer, codec, mesh,
            dp_axis=dp_axis, n_dp=n_dp, exchange=exchange,
            state_specs=None, token_spec=P(dp_axis, sp_axis),
            oracle_parts=oracle_parts,
        )

    # the ONE compile path (parallel.compile): construction byte-identical
    # to the hand-rolled jax.jit(jax.shard_map(...)) stack this builder
    # used to assemble inline (tested per program family)
    return compile_step(
        spmd_step,
        mesh,
        in_specs=(P(), P(), P(dp_axis, sp_axis)),
        out_specs=(P(), P()),
        donate_argnums=(0,),
    )


def shard_tokens(mesh: Mesh, tokens, dp_axis: str = "dp", sp_axis: str = "sp"):
    return jax.device_put(
        jnp.asarray(tokens), NamedSharding(mesh, P(dp_axis, sp_axis))
    )
