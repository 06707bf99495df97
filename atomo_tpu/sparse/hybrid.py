"""Per-layer hybrid exchange plan: sparse rows vs the existing dense path.

Parallax's (1808.02621) core observation, restated for this codebase: the
right exchange representation is a PER-LAYER decision, not a per-run one.
An embedding table's gradient is row-sparse (density ~ batch x slots /
rows), so shipping (row, value) pairs beats any dense representation by
~1/density; the dense tower's gradients are fully dense, so the existing
compressed gather/ring path stays optimal. SparCML (1802.08021) supplies
the selection rule: switch representations where the sparse form's bytes
cross the dense form's — the same density-crossover arithmetic
``topology/schedule`` already applies to its outer psum fallback, here
applied per leaf at plan time.

The planner is PURE: a function of (leaf shapes, measured densities,
worst-case row bounds, the dense path's per-leaf payload bytes) to a
:class:`HybridPlan`. Nothing is traced; the plan is a trace-time constant
the step builder bakes in (the stream-encode bucket-plan precedent). The
crossover is stated as a formula in every assignment's reason line so the
decision is auditable, not vibes:

    sparse  iff  B·(c·s + 4) + 4  <  P_codec(leaf)
    i.e.    b = B/R  <  D* = P_codec / (R·(c·s + 4))

with R rows, c columns, s value itemsize, B = min(R, worst-case touched
rows) the static budget, b the budgeted density and D* the SparCML
crossover density. MEASURED density (nnz rows / R on a probe gradient)
rides along for observability — the byte-split meta record and the
``report`` verb's consistency checks — but the ASSIGNMENT keys off the
worst-case budget, because losslessness must hold for every step, not
the average one.
"""

from __future__ import annotations

import dataclasses

from atomo_tpu.sparse.rowcodec import RowCodec, row_payload_bytes

# parameter-path substrings that mark a leaf as a lookup table whose
# per-step row support is bounded by batch x slots (a lookup touches at
# most one row per (sample, slot)); stated name-matching, not magic
TABLE_NAME_HINTS = ("table", "embedding")


@dataclasses.dataclass(frozen=True)
class LeafAssignment:
    """One leaf's exchange decision + the numbers that justify it."""

    index: int  # canonical flatten-order leaf index
    name: str  # jax.tree_util.keystr path
    shape: tuple
    kind: str  # "sparse" | "dense"
    density: float  # measured nnz-row fraction (1.0 for non-2-D leaves)
    row_budget: int  # static worst-case rows (0 for dense-assigned)
    dense_bytes: int
    codec_payload_bytes: int  # the dense path's wire bytes for this leaf
    payload_bytes: int  # the ASSIGNED path's wire bytes
    reason: str


@dataclasses.dataclass(frozen=True)
class HybridPlan:
    """The per-leaf partition ``make_distributed_train_step(hybrid=...)``
    executes. ``dense_idxs`` is ascending, so the dense-assigned encode
    (``encode_leaf_subset`` with GLOBAL leaf keys) produces payloads
    bit-identical to the all-dense run's for those leaves — the
    all-dense-assignment bit-parity contract rests on this ordering."""

    assignments: tuple

    @property
    def sparse_idxs(self) -> tuple:
        return tuple(
            a.index for a in self.assignments if a.kind == "sparse"
        )

    @property
    def dense_idxs(self) -> tuple:
        return tuple(a.index for a in self.assignments if a.kind == "dense")

    @property
    def n_leaves(self) -> int:
        return len(self.assignments)

    @property
    def any_sparse(self) -> bool:
        return any(a.kind == "sparse" for a in self.assignments)

    def row_codec(self, index: int) -> RowCodec:
        a = self.assignments[index]
        if a.kind != "sparse":
            raise ValueError(f"leaf {index} ({a.name}) is dense-assigned")
        return RowCodec(max_rows=a.row_budget)

    def payload_bytes(self) -> int:
        """Total wire bytes per replica under this plan — the honest
        ``msg_bytes`` the step reports and the comm model prices."""
        return int(sum(a.payload_bytes for a in self.assignments))

    def leaf_budgets(self) -> list:
        """Per-leaf ``(dense_bytes, payload_bytes)`` pairs in canonical
        leaf order — comm_model's per-leaf pricing input
        (``leaf_budget_totals``), so the +sparse autopilot candidates and
        the executed program sum the SAME numbers."""
        return [
            (int(a.dense_bytes), int(a.payload_bytes))
            for a in self.assignments
        ]

    def describe(self) -> str:
        s = self.sparse_idxs
        return (
            f"hybrid plan: {len(s)}/{self.n_leaves} leaves sparse-row, "
            f"{self.payload_bytes() / 1e6:.3f} MB/replica on the wire vs "
            f"{sum(a.codec_payload_bytes for a in self.assignments) / 1e6:.3f}"
            " MB all-dense-assigned"
        )


def measured_densities(grads) -> list:
    """Per-leaf nnz-row fraction of a (host or device) gradient tree, in
    canonical flatten order; non-2-D leaves report 1.0 (never
    sparse-assignable). Pure numpy — call it on a PROBE gradient
    (``probe_gradient``), never inside the traced step."""
    import jax
    import numpy as np

    out = []
    for leaf in jax.tree_util.tree_leaves(grads):
        a = np.asarray(leaf)
        if a.ndim != 2 or a.shape[0] == 0:
            out.append(1.0)
            continue
        nnz = int(np.count_nonzero(np.any(a != 0, axis=1)))
        out.append(nnz / a.shape[0])
    return out


def probe_gradient(model, images, labels):
    """One backward pass over a fixed batch — the measured-density probe.
    Deterministic given the batch (fixed dropout key); jitted once, then
    thrown away. Callers must feed a batch that does NOT advance the
    training stream's shuffle RNG (slice ``train_iter.images`` directly —
    the --aggregate auto code-review precedent)."""
    import jax
    import jax.numpy as jnp

    from atomo_tpu.training.trainer import cross_entropy_loss

    def loss_fn(params):
        out = model.apply(
            {"params": params}, jnp.asarray(images), train=True,
            rngs={"dropout": jax.random.PRNGKey(0)}, mutable=[],
        )
        logits = out[0] if isinstance(out, tuple) else out
        return cross_entropy_loss(logits, jnp.asarray(labels))

    params = model.init(
        {"params": jax.random.PRNGKey(0), "dropout": jax.random.PRNGKey(0)},
        jnp.asarray(images), train=False,
    )["params"]
    return jax.device_get(jax.jit(jax.grad(loss_fn))(params))


def infer_row_bounds(
    params, batch_per_chip: int, slots: int, hints=TABLE_NAME_HINTS
) -> list:
    """Per-leaf worst-case touched-row bound, canonical flatten order.

    A 2-D leaf whose parameter path names a lookup table (``hints``
    substring match — stated, auditable) is touched on at most
    ``batch_per_chip x slots`` rows per step: each (sample, slot) lookup
    contributes one row to the scatter-add backward. Every other leaf
    gets ``None`` — no provable bound, never sparse-assignable. The bound
    is what makes the lossless claim a THEOREM about the workload rather
    than an observation about probe batches."""
    import jax

    flat, _ = jax.tree_util.tree_flatten_with_path(params)
    out = []
    cap = max(int(batch_per_chip), 1) * max(int(slots), 1)
    for path, leaf in flat:
        name = jax.tree_util.keystr(path).lower()
        if len(getattr(leaf, "shape", ())) == 2 and any(
            h in name for h in hints
        ):
            out.append(min(int(leaf.shape[0]), cap))
        else:
            out.append(None)
    return out


def _codec_leaf_payload_bytes(codec, leaf, index=None) -> int:
    """The dense path's wire bytes for one leaf (static, via eval_shape —
    nothing materializes). ``codec=None`` would be a dense psum wire; the
    hybrid step requires a codec, so this prices the compressed gather.
    A per-leaf wrapper (``budget.PerLeafCodec`` — no whole-tensor encode
    by design) resolves through ``codec_for(index)``, so the planner can
    price a budget-allocated dense path (the joint ``+sp+ab``
    controller candidates)."""
    import jax
    import jax.numpy as jnp

    from atomo_tpu.codecs.base import payload_nbytes

    if index is not None and hasattr(codec, "codec_for"):
        codec = codec.codec_for(index)

    shape = jax.eval_shape(
        lambda: codec.encode(
            jax.random.PRNGKey(0),
            jnp.zeros(tuple(leaf.shape), leaf.dtype),
        )
    )
    return int(payload_nbytes(shape))


def plan_hybrid(
    codec,
    grads_like,
    densities,
    row_bounds,
) -> HybridPlan:
    """The pure per-leaf partitioner (module docstring formula).

    ``grads_like``: a tree of arrays OR ShapeDtypeStructs (shapes only —
    eval_shape output works); ``densities``/``row_bounds``: canonical-
    order lists from :func:`measured_densities` / :func:`infer_row_bounds`
    (``row_bounds[i] is None`` = no provable bound = dense). Same inputs,
    same plan — deterministic, trace-free."""
    import jax
    import numpy as np

    flat, _ = jax.tree_util.tree_flatten_with_path(grads_like)
    if not (len(flat) == len(densities) == len(row_bounds)):
        raise ValueError(
            f"plan_hybrid: {len(flat)} leaves vs {len(densities)} "
            f"densities vs {len(row_bounds)} row bounds — all three must "
            "come from the same tree in canonical order"
        )
    entries = []
    for i, (path, leaf) in enumerate(flat):
        name = jax.tree_util.keystr(path)
        shape = tuple(int(d) for d in leaf.shape)
        itemsize = np.dtype(leaf.dtype).itemsize
        dense_b = int(np.prod(shape or (1,))) * itemsize
        codec_b = _codec_leaf_payload_bytes(codec, leaf, index=i)
        bound = row_bounds[i]
        d = float(densities[i])
        if bound is not None and len(shape) == 2 and shape[0] > 0:
            r, c = shape
            budget = min(int(bound), r)
            sparse_b = row_payload_bytes(budget, c, itemsize)
            b_density = budget / r
            d_star = codec_b / (r * (c * itemsize + 4))
            if sparse_b < codec_b:
                entries.append(LeafAssignment(
                    index=i, name=name, shape=shape, kind="sparse",
                    density=d, row_budget=budget, dense_bytes=dense_b,
                    codec_payload_bytes=codec_b, payload_bytes=sparse_b,
                    reason=(
                        f"sparse: B={budget} rows x ({c}x{itemsize}+4) B "
                        f"= {sparse_b} B < {codec_b} B dense-path payload "
                        f"(SparCML crossover: budget density b=B/R="
                        f"{b_density:.4g} < D*=P/(R(c*s+4))={d_star:.4g}; "
                        f"measured density {d:.4g})"
                    ),
                ))
                continue
            entries.append(LeafAssignment(
                index=i, name=name, shape=shape, kind="dense",
                density=d, row_budget=0, dense_bytes=dense_b,
                codec_payload_bytes=codec_b, payload_bytes=codec_b,
                reason=(
                    f"dense: B={budget} rows would cost {sparse_b} B >= "
                    f"{codec_b} B dense-path payload (budget density "
                    f"b={b_density:.4g} >= crossover D*={d_star:.4g})"
                ),
            ))
            continue
        entries.append(LeafAssignment(
            index=i, name=name, shape=shape, kind="dense",
            density=d, row_budget=0, dense_bytes=dense_b,
            codec_payload_bytes=codec_b, payload_bytes=codec_b,
            reason="dense: no provable per-step row bound (not a table "
                   "leaf) — sparse rows would be lossy, rejected",
        ))
    return HybridPlan(assignments=tuple(entries))


def plan_for_model(
    codec,
    model,
    images,
    labels,
    batch_per_chip: int,
    slots: int,
) -> HybridPlan:
    """Convenience composition the CLI and the tests share: probe gradient ->
    measured densities + inferred bounds -> :func:`plan_hybrid`."""
    grads = probe_gradient(model, images, labels)
    return plan_hybrid(
        codec,
        grads,
        measured_densities(grads),
        infer_row_bounds(grads, batch_per_chip, slots),
    )
