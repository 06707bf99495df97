"""Cross-replica sharded weight update (Xu et al., 2004.13336).

The replicated program keeps N copies of everything: params, momentum/Adam
buffers, and the weight-update computation all exist once per chip. ZeRO-1
(:func:`atomo_tpu.parallel.replicated.zero1_state`) sharded the optimizer
STATE and the update computation over the dp axis but kept the master
params replicated — each chip still persists the full dense model between
steps. This module finishes the move, per the paper's recipe:

  * **sharded-persistent master weights** — the flat padded parameter
    vector lives sharded over the data axes; each chip persistently holds
    its 1/N slice and nothing else. The dense model never persists
    per-chip: it is materialized TRANSIENTLY inside the step (one tiled
    all_gather) for forward/backward and discarded.
  * **sharded update computation** — the optimizer update runs on the
    (grad-slice, master-slice, opt-slice) triple, exactly the ZeRO-1
    sliced update; ZeRO-1 is now the degenerate "shard state only" point
    of this family.
  * **bit-identity** — the all_gather of exact slices reassembles the
    replicated params byte-for-byte, the PRNG folds from the same step
    counter, and the update is slice-invariant (probed at setup, same as
    ZeRO-1), so sharded-update trajectories are bit-identical to
    replicated ones per codec (tested per codec in tests/test_mesh.py).

Per-chip persistent state, P params / N chips (f32, momentum-SGD):
replicated 8P bytes; zero1 4P + 4P/N; sharded-update 8P/N — read from
the actual device buffers by tests/test_mesh.py
(``test_per_chip_persistent_state_shrinks_by_world_size``).

The carry is ordinary: a :class:`ShardedUpdateState` is a pytree of plain
arrays, so it rides ``lax.scan`` (superstep), checkpoints (``device_get``
gathers slices to full host arrays — restore re-shards), and the
``--overlap delayed`` :class:`~atomo_tpu.parallel.replicated
.OverlapCarry` unchanged — which is what dissolves the historical
``zero1 x delayed x supervision`` dead end: the in-flight payload is just
another sharded carry leaf next to the master slices.
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import flax.struct
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P


@flax.struct.dataclass
class ShardedUpdateState:
    """The sharded-persistent train state: ``master`` is the flat padded
    parameter vector sharded over the data axes ((n_shards * chunk,)
    global, one chunk per chip); ``opt_state`` holds the optimizer
    buffers on the same flat layout (the ZeRO-1 layout); ``batch_stats``
    and ``step`` stay replicated.

    ``params`` is a PLACEMENT VIEW ONLY (the master vector, for fencing /
    block_until_ready in loop plumbing that touches ``.params`` of any
    state family) — it is NOT the parameter pytree; materialize that with
    :meth:`ShardedUpdateSpecs.materialize_host` or in-graph via the tiled
    all_gather the train step performs."""

    step: Any
    master: Any
    batch_stats: Any
    opt_state: Any

    @property
    def params(self):
        return self.master


class ShardedUpdateSpecs:
    """Static build artifact of :func:`sharded_update_state`: the flat
    layout (chunk length, true size, unravel closure), the data axes the
    master shards over, and the PartitionSpec trees the one compile path
    (:func:`atomo_tpu.parallel.compile.compile_step`) annotates the pjit
    boundary with. One instance per run — the step builder closes over
    it, so there is exactly one layout definition the dynamic slices and
    the state allocations can agree on (the ZeRO-1 ONE-definition rule,
    inherited)."""

    def __init__(self, *, axes, n_shards, chunk, d_flat, unravel,
                 opt_specs):
        self.axes: tuple[str, ...] = tuple(axes)
        self.n_shards: int = n_shards
        self.chunk: int = chunk
        self.d_flat: int = d_flat
        self.unravel: Callable = unravel
        self.opt_specs = opt_specs

    @property
    def gather_axes(self):
        """The axis argument collectives take: the bare name on a flat
        mesh, the (outer, inner) tuple on a two-tier one."""
        return self.axes[0] if len(self.axes) == 1 else self.axes

    @property
    def master_spec(self):
        return P(self.axes)

    def state_spec(self) -> ShardedUpdateState:
        """The TrainState-of-PartitionSpecs the compile path consumes."""
        return ShardedUpdateState(
            step=P(), master=P(self.axes), batch_stats=P(),
            opt_state=self.opt_specs,
        )

    def materialize_host(self, master) -> Any:
        """Gather the master vector to host and unravel the parameter
        pytree — the eval/checkpoint-template view. ``master`` may be the
        global sharded array or an already-host array."""
        flat = jnp.asarray(jax.device_get(master))
        return self.unravel(flat[: self.d_flat])


def chunk_len(flat_size: int, n_shards: int) -> int:
    """Per-chip slice length of the flat sharded buffers. ONE definition
    shared by the allocations here and the train step's dynamic slices
    (:mod:`atomo_tpu.parallel.replicated` delegates its ZeRO-1 chunk to
    this), or every momentum slice silently misaligns with its parameter
    slice."""
    return -(-flat_size // n_shards)


def check_slice_invariant(optimizer, n_shards: int, dtype) -> None:
    """Validity probe for every sharded-update family (ZeRO-1 and full
    sharded-update alike): updating a SLICE of the flat param vector must
    equal the slice of the full-vector update — true for elementwise
    transforms (sgd momentum, adam, weight decay, per-element clipping)
    but silently FALSE for globally-mixing ones (e.g.
    optax.clip_by_global_norm, whose norm would be taken per-slice).
    Run the optimizer on a tiny vector, sliced and unsliced, at setup
    time; raise on divergence rather than train subtly wrong. The probe
    sweeps gradient SCALES (1, 1e4, 1e-4) because threshold-gated mixing
    only activates at some magnitudes."""
    probe_n = 8 * n_shards
    pk, gk = jax.random.split(jax.random.PRNGKey(17))
    p_full = jax.random.normal(pk, (probe_n,), dtype)
    g_base = jax.random.normal(gk, (probe_n,), dtype)
    chunk = probe_n // n_shards
    for scale in (1.0, 1e4, 1e-4):
        g_full = g_base * scale
        u_full, _ = optimizer.update(g_full, optimizer.init(p_full), p_full)
        parts = []
        for i in range(n_shards):
            p_i = p_full[i * chunk:(i + 1) * chunk]
            g_i = g_full[i * chunk:(i + 1) * chunk]
            u_i, _ = optimizer.update(g_i, optimizer.init(p_i), p_i)
            parts.append(u_i)
        ref = jnp.concatenate(parts)
        tol = 1e-5 * float(jnp.max(jnp.abs(u_full))) + 1e-12
        if not jnp.allclose(u_full, ref, rtol=1e-5, atol=tol):
            raise ValueError(
                "sharded update: this optimizer's update is not "
                f"slice-invariant (at gradient scale {scale:g}, a sliced "
                "update differs from the slice of the full update — e.g. "
                "a global-norm clip in the chain). Sharding the update "
                "would train silently wrong; use the replicated optimizer "
                "path or an elementwise chain (sgd/momentum/adam/wd)."
            )


def _flat_axes(mesh, axis) -> tuple[tuple[str, ...], int]:
    axes = (axis,) if isinstance(axis, str) else tuple(axis)
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return axes, n


def flat_opt_state(mesh, optimizer, *, chunk, n_shards, axes, dtype):
    """ONE construction of the flat sharded optimizer state (the ZeRO-1
    layout, shared by ``zero1_state`` and :func:`sharded_update_state`):
    init on a per-chip zero chunk, tile vector buffers to one
    ``(n_shards * chunk,)`` global sharded over ``axes``, keep scalar
    leaves (counts) replicated. Returns ``(opt_global, opt_specs)`` —
    the placed state and its PartitionSpec tree."""
    local = optimizer.init(jnp.zeros((chunk,), dtype))

    def glob(leaf):
        leaf = jnp.asarray(leaf)
        if leaf.ndim == 0:  # counts etc.: replicated scalars
            return jax.device_put(leaf, NamedSharding(mesh, P()))
        # identical zero-init per shard; stored as one (n*chunk,) global
        return jax.device_put(
            jnp.tile(leaf, n_shards), NamedSharding(mesh, P(axes))
        )

    opt_global = jax.tree_util.tree_map(glob, local)
    opt_specs = jax.tree_util.tree_map(
        lambda l: P(axes) if jnp.asarray(l).ndim else P(), local
    )
    return opt_global, opt_specs


def sharded_update_state(
    mesh, state, optimizer, axis="dp"
) -> tuple[ShardedUpdateState, ShardedUpdateSpecs]:
    """Build the sharded-persistent state from a host/replicated
    ``TrainState``: ravel the params flat, pad to a multiple of the shard
    count, place the padded vector sharded over ``axis`` (a name, or the
    ("dp", "ici") tuple on a two-tier mesh), and init the optimizer on
    the flat layout exactly as ZeRO-1 does. Returns ``(state, specs)``;
    pass ``sharded_update=specs`` to ``make_distributed_train_step``.

    Degenerate meshes are first-class: on 1 device the chunk is the whole
    (padded) vector and the program is the replicated one with an
    identity all_gather."""
    from jax.flatten_util import ravel_pytree

    axes, n = _flat_axes(mesh, axis)
    flat, unravel = ravel_pytree(jax.device_get(state.params))
    check_slice_invariant(optimizer, n, flat.dtype)
    chunk = chunk_len(flat.size, n)
    pad = chunk * n - flat.size
    master = jnp.pad(flat, (0, pad))
    opt_global, opt_specs = flat_opt_state(
        mesh, optimizer, chunk=chunk, n_shards=n, axes=axes,
        dtype=flat.dtype,
    )
    specs = ShardedUpdateSpecs(
        axes=axes, n_shards=n, chunk=chunk, d_flat=flat.size,
        unravel=unravel, opt_specs=opt_specs,
    )
    new_state = ShardedUpdateState(
        step=jax.device_put(
            jnp.asarray(state.step), NamedSharding(mesh, P())
        ),
        master=jax.device_put(master, NamedSharding(mesh, P(axes))),
        batch_stats=jax.device_put(
            jax.device_get(state.batch_stats), NamedSharding(mesh, P())
        ),
        opt_state=opt_global,
    )
    return new_state, specs


def place_sharded_update(
    mesh, host_state: ShardedUpdateState, specs: ShardedUpdateSpecs
) -> ShardedUpdateState:
    """Place a host-side :class:`ShardedUpdateState` (a checkpoint
    restore, a reshard source) onto ``mesh`` with the layout ``specs``
    describe — resume and fresh init MUST place identically or a
    restored trajectory drifts from an uninterrupted one."""
    def put(tree, spec):
        sh = NamedSharding(mesh, spec)
        return jax.tree_util.tree_map(
            lambda a: jax.device_put(jnp.asarray(a), sh), tree
        )

    return ShardedUpdateState(
        step=put(host_state.step, P()),
        master=put(host_state.master, specs.master_spec),
        batch_stats=put(host_state.batch_stats, P()),
        opt_state=jax.tree_util.tree_map(
            lambda a, sp: jax.device_put(
                jnp.asarray(a), NamedSharding(mesh, sp)
            ),
            host_state.opt_state,
            specs.opt_specs,
        ),
    )


def sharded_state_from_params(
    mesh, params, batch_stats, step, optimizer, axis="dp"
) -> tuple[ShardedUpdateState, ShardedUpdateSpecs]:
    """Rebuild a fresh-momentum sharded state from bare (params,
    batch_stats, step) — the layout-mismatch resume fallback (a
    replicated checkpoint restored into a sharded-update run, or a
    reshaped mesh): params carry over, the optimizer state re-initializes
    sharded, and the caller warns out loud exactly like the ZeRO-1
    fallback."""
    from atomo_tpu.training.trainer import TrainState

    state = TrainState(
        step=jnp.asarray(step, jnp.int32), params=params,
        batch_stats=batch_stats, opt_state=None,
    )
    return sharded_update_state(mesh, state, optimizer, axis=axis)
