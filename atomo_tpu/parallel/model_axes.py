"""ONE front-end over the LM model-axis program families.

Every LM parallelism layout — pure dp, dp x sp (ring/Ulysses), dp x tp
(Megatron), dp x ep (switch-MoE), dp x pp (GPipe), and the 3-D
dp x tp x sp composition — used to be wired up ad hoc at each call site
(``cli.cmd_lm``'s per-layout elif ladder, each test's private setup).
This module is the single resolution of a :class:`~atomo_tpu.mesh.spec.
MeshSpec` model-axis layout to a runnable program:

  * the mesh comes from ``spec.build()`` (the same axes tuples the legacy
    call sites handed ``make_mesh`` — same mesh, same compiled program);
  * the step comes from the layout's builder, compiled through
    :func:`atomo_tpu.parallel.compile.compile_step` (the one compile
    path), with the dp gradient exchange routed through the compressed
    stack when the caller hands a
    :class:`~atomo_tpu.parallel.lm.DpExchange`;
  * state/specs/token-sharding come bundled, so a driver (CLI, benchmark,
    test) asks for a layout by name instead of re-deriving the recipe.

The legacy builders stay importable and bit-identical — this is a
front-end, not a fork: ``build_model_axis_program("dp-tp", ...)`` returns
exactly ``make_tp_lm_train_step``'s program.
"""

from __future__ import annotations

from typing import Any, Callable, NamedTuple, Optional

import jax

from atomo_tpu.mesh.spec import LAYOUT_MODEL_AXES, MeshSpec
from atomo_tpu.parallel.lm import DpExchange
from atomo_tpu.training.trainer import TrainState

__all__ = [
    "LAYOUT_MODEL_AXES",
    "ModelAxisProgram",
    "build_model_axis_program",
]


class ModelAxisProgram(NamedTuple):
    """A runnable model-axis LM program: everything a driver needs."""

    spec: MeshSpec
    mesh: Any
    state: TrainState
    state_specs: Optional[TrainState]  # None for the replicated layouts
    step: Callable  # jitted (state, key, tokens) -> (state, metrics)
    shard_tokens: Callable  # host (B, S) array -> device-sharded tokens


def build_model_axis_program(
    spec: MeshSpec,
    lm_config: dict,
    optimizer,
    rng,
    codec=None,
    *,
    attn_impl: str = "ring",
    num_microbatches: int = 2,
    capacity_factor: float = 1.25,
    aux_weight: float = 0.01,
    compute_dtype=None,
    aggregate: str = "gather",
    exchange: Optional[DpExchange] = None,
    devices=None,
    oracle_parts: bool = False,
) -> ModelAxisProgram:
    """Resolve a model-axis layout to its (mesh, state, specs, step,
    shard) bundle.

    ``spec`` comes from :meth:`MeshSpec.from_layout`; the dispatch key is
    ``spec.layout_name()`` (raises for shapes outside the LM grammar).
    ``exchange=None`` keeps each family's legacy dp tail byte-for-byte;
    a :class:`DpExchange` routes it through the full compressed stack
    (ring aggregation, stream-encode, per-leaf budget codecs).

    ``exchange.overlap == "delayed"`` threads the stale-by-one carry:
    ``state`` comes back as a :class:`~atomo_tpu.parallel.replicated.
    DelayedState` (``.params``/``.step`` read through, so driver loops
    are unchanged) and ``step`` consumes/returns it; ``state_specs``
    still describes the TRAIN half (checkpoint placement, reshard).
    ``oracle_parts=True`` (delayed only) swaps ``step`` for the
    ``{"produce", "apply"}`` two-program oracle the parity tests drive.
    Sizing errors (head/vocab/depth/expert divisibility) surface as the
    builders' ValueErrors, untranslated.
    """
    layout = spec.layout_name()
    mesh = spec.build(devices)
    delayed = exchange is not None and exchange.overlap == "delayed"
    kw = dict(
        compute_dtype=compute_dtype, aggregate=aggregate, exchange=exchange
    )
    if delayed:
        kw["oracle_parts"] = oracle_parts

    def finish(state, specs, step, shard_fn) -> ModelAxisProgram:
        if delayed:
            from atomo_tpu.parallel.lm import init_model_axis_delayed_state

            state = init_model_axis_delayed_state(mesh, state, codec)
        return ModelAxisProgram(spec, mesh, state, specs, step, shard_fn)

    if layout in ("dp", "dp-sp"):
        from atomo_tpu.models.transformer import TransformerLM
        from atomo_tpu.parallel.lm import make_lm_train_step, shard_tokens
        from atomo_tpu.parallel.replicated import replicate_state
        from atomo_tpu.training import create_state

        sample = jax.numpy.zeros((1, lm_config["max_len"]), jax.numpy.int32)
        state = create_state(TransformerLM(**lm_config), optimizer, rng, sample)
        state = replicate_state(mesh, state)
        step = make_lm_train_step(
            lm_config, optimizer, mesh, codec, attn_impl=attn_impl, **kw
        )
        return finish(state, None, step, lambda t: shard_tokens(mesh, t))

    if layout == "dp-tp":
        from atomo_tpu.parallel.tp import (
            create_tp_lm_state, make_tp_lm_train_step, shard_tp_tokens,
        )

        state, specs = create_tp_lm_state(mesh, lm_config, optimizer, rng)
        step = make_tp_lm_train_step(
            lm_config, optimizer, mesh, specs, codec, **kw
        )
        return finish(state, specs, step, lambda t: shard_tp_tokens(mesh, t))

    if layout == "dp-tp-sp":
        from atomo_tpu.parallel.tp import (
            create_tp_lm_state, make_tp_sp_lm_train_step,
        )
        from atomo_tpu.parallel.common import shard_tokens_with_spec
        from jax.sharding import PartitionSpec as P

        state, specs = create_tp_lm_state(mesh, lm_config, optimizer, rng)
        step = make_tp_sp_lm_train_step(
            lm_config, optimizer, mesh, specs, codec,
            attn_impl=attn_impl, **kw
        )
        return finish(
            state, specs, step,
            lambda t: shard_tokens_with_spec(mesh, t, P("dp", "sp")),
        )

    if layout == "dp-ep":
        from atomo_tpu.parallel.moe import (
            create_moe_lm_state, make_moe_lm_train_step, shard_moe_tokens,
        )

        state, specs = create_moe_lm_state(mesh, lm_config, optimizer, rng)
        step = make_moe_lm_train_step(
            lm_config, optimizer, mesh, specs, codec,
            capacity_factor=capacity_factor, aux_weight=aux_weight, **kw
        )
        return finish(state, specs, step, lambda t: shard_moe_tokens(mesh, t))

    if layout == "dp-pp":
        from atomo_tpu.parallel.pp import (
            create_pp_lm_state, make_pp_lm_train_step, shard_pp_tokens,
        )

        state, specs = create_pp_lm_state(mesh, lm_config, optimizer, rng)
        step = make_pp_lm_train_step(
            lm_config, optimizer, mesh, specs, codec,
            num_microbatches=num_microbatches, **kw
        )
        return finish(state, specs, step, lambda t: shard_pp_tokens(mesh, t))

    raise ValueError(  # pragma: no cover - layout_name() guards this
        f"unhandled layout {layout!r}"
    )
