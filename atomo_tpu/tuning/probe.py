"""Shared measured-probe runner — the autopilot's measurement half.

One timing discipline for every short measured probe in the tuning
package: warm the compiled program, then
time a dispatch loop ended by a device->host scalar fetch
(utils.tracing.fence_tree — a fence on every backend that also
returns the value for the finiteness check), best-of-N
against shared-host contention. Every completed row is ALSO written to a
JSON artifact atomically as it lands (:class:`ProbeLadder`), so a killed
or timed-out tune leaves parseable partial evidence
(utils.tracing.write_json_atomic's tmp+rename contract).

Probes are TRAJECTORY-NEUTRAL by construction: they run on synthetic
batches drawn from their own PRNG keys and on states initialized from
their own seeds, never touching the training data iterator's shuffle RNG
or the run's model-init seed — which is what lets ``--auto tune`` hand
the chosen config to the normal train path bit-identically to launching
that config statically (the PR-7 acceptance contract).
"""

from __future__ import annotations

import math
import time
from typing import Optional

from atomo_tpu.utils.tracing import write_json_atomic


class ProbeLadder:
    """Rows-as-they-complete artifact recorder (atomic partial JSON).

    ``artifact_path=None`` disables writing (rows still accumulate for
    the caller). The document shape:
    ``{"kind": ..., "meta": {...}, "rows": [...], "complete": bool}``.
    Write failures warn and never crash the run being tuned — evidence is
    best-effort, training is not.
    """

    def __init__(
        self, artifact_path: Optional[str] = None, kind: str = "probe",
        meta: Optional[dict] = None, log_fn=print,
    ):
        self.artifact_path = artifact_path
        self.doc = {
            "kind": kind,
            "meta": dict(meta or {}),
            "rows": [],
            "complete": False,
        }
        self.log_fn = log_fn

    @property
    def rows(self) -> list[dict]:
        return self.doc["rows"]

    def _write(self) -> None:
        if not self.artifact_path:
            return
        try:
            write_json_atomic(self.artifact_path, self.doc)
        except OSError as exc:
            self.log_fn(f"probe artifact write failed: {exc}")

    def record(self, row: dict) -> dict:
        self.doc["rows"].append(row)
        self._write()
        return row

    def finish(self, **extra) -> dict:
        self.doc.update(extra)
        self.doc["complete"] = True
        self._write()
        return self.doc


def model_init_fn(model, sample):
    """The deterministic param-init closure every byte-budget consumer
    shares (the CLI's ``--aggregate auto`` resolution, the autopilot,
    scripts/scenario_table.py): fixed PRNGKey(0)
    for params/dropout over a zeros ``sample``, params extracted. ONE
    definition so the byte budgets those surfaces compute can never
    silently diverge. Meant for jax.eval_shape — never materializes."""
    import jax

    def init():
        return model.init(
            {"params": jax.random.PRNGKey(0),
             "dropout": jax.random.PRNGKey(0)},
            sample, train=False,
        )["params"]

    return init


def leaf_byte_budgets(codec, init_fn) -> list:
    """Per-leaf ``(dense_bytes, payload_bytes)`` pairs in canonical
    flatten order, at zero cost via jax.eval_shape — the per-leaf form of
    the byte budget (PR-12): :func:`byte_budget` is now its sum through
    ``comm_model.leaf_budget_totals``, so the whole-tree scalars and any
    per-leaf consumer (the hybrid planner's pricing, the +sp autopilot
    candidates) read the SAME accounting. ``codec=None`` (dense
    training) reports payload 0 per leaf."""
    import jax

    from atomo_tpu.codecs import encode_tree, payload_nbytes, tree_nbytes

    if codec is None:
        leaves = jax.tree_util.tree_leaves(jax.eval_shape(init_fn))
        return [(tree_nbytes([l]), 0) for l in leaves]

    def shapes():
        params = init_fn()
        payload, _ = encode_tree(codec, jax.random.PRNGKey(0), params)
        return params, payload

    grads_s, payload_s = jax.eval_shape(shapes)
    g_leaves, treedef = jax.tree_util.tree_flatten(grads_s)
    p_leaves = treedef.flatten_up_to(payload_s)
    return [
        (tree_nbytes([g]), payload_nbytes(p))
        for g, p in zip(g_leaves, p_leaves)
    ]


def byte_budget(codec, init_fn) -> tuple[int, int]:
    """(dense_bytes, payload_bytes) of one gradient exchange — the sum of
    :func:`leaf_byte_budgets` through the one honest accounting function
    (``comm_model.leaf_budget_totals``). Report shape unchanged: the one
    implementation behind the CLI's ``--aggregate auto`` resolution and
    the autopilot's prediction context; build ``init_fn`` with
    :func:`model_init_fn`."""
    from atomo_tpu.utils.comm_model import leaf_budget_totals

    d, p = leaf_budget_totals(leaf_byte_budgets(codec, init_fn))
    return int(d), int(p)


def fenced_seconds_per_call(
    call, *, reps: int, warmup: int = 2, best_of: int = 1
) -> tuple[float, bool]:
    """Best-of-``best_of`` mean seconds per ``call()`` over ``reps``-call
    dispatch loops, each fenced by a scalar fetch of the last call's
    output. Returns ``(seconds, sync_ok)`` — ``sync_ok`` False when the
    fence scalar came back non-finite (the measurement is then invalid,
    reported, never silently trusted)."""
    from atomo_tpu.utils.tracing import fence_tree

    out = None
    for _ in range(max(warmup, 1)):
        out = call()
    sync = fence_tree(out)  # drain warmup + compile
    best = float("inf")
    for _ in range(max(best_of, 1)):
        t0 = time.perf_counter()
        for _ in range(max(reps, 1)):
            out = call()
        sync = fence_tree(out)
        best = min(best, (time.perf_counter() - t0) / max(reps, 1))
    return best, bool(math.isfinite(sync))


def synthetic_batch(key, batch: int, sample_shape, num_classes: int):
    """A probe batch from the probe's OWN key — never the training
    stream (trajectory neutrality, module docstring)."""
    import jax
    import jax.numpy as jnp

    ki, kl = jax.random.split(key)
    images = jax.random.uniform(
        ki, (batch,) + tuple(sample_shape), jnp.float32
    )
    labels = jax.random.randint(kl, (batch,), 0, num_classes)
    return images, labels


def probe_candidate(
    cand: dict,
    *,
    model,
    optimizer,
    codec,
    n_dev: int,
    sample_shape,
    num_classes: int,
    batch: int,
    seed: int = 0,
    steps: int = 3,
    reps: int = 2,
    warmup: int = 2,
    num_aggregate: int = 0,
    zero1: bool = False,
    grad_accum: int = 1,
    compute_dtype=None,
    ring_bucket_size: int = 65536,
    dcn_ways: int = 0,
    hybrid=None,
    error_feedback: bool = False,
) -> dict:
    """Measure one candidate knob vector: build the REAL step program the
    train path would run (same builders, same knobs — zero1 / grad_accum
    / compute_dtype / num_aggregate ride along because they change the
    program's speed; guard/chaos/remedy stay off, they are correctness
    machinery, not a performance knob) and time it with the fence
    discipline. Returns the probe row (measured ms/step per OPTIMIZER
    step — a superstep-K program's one dispatch covers K of them).

    Hierarchical candidates (``aggregate='hierarchical'`` + a ``plan``
    knob) probe on the two-tier mesh ``(dp=dcn_ways, ici=n_dev/dcn_ways)``
    through the same builder the train path uses (inner_axis='ici',
    topology plan attached) — the probes `--auto tune` was missing on
    ``--dcn-ways`` meshes.

    ``hybrid`` (sparse.hybrid.HybridPlan) is attached to the built step
    only for ``+sp`` candidates (``cand["sparse_rows"] == "on"``) — the
    probe then times the REAL per-layer hybrid exchange the train path
    would dispatch. The probe batch stays the synthetic float batch;
    row-id workloads read it as low row ids, which under-exercises the
    power-law tail but prices the program structure honestly (the
    lossless budget is static, so the timing is shape-faithful).

    ``error_feedback=True`` probes the residual-carry step (EF state
    wrapped via ``init_ef_state`` after replication) — the ISSUE-17
    satellite. The caller (``tune(error_feedback=True)``) is responsible
    for narrowing the candidate space to the flat blocking programs EF
    composes with; this function just builds what it is asked to and
    lets the step builder's conflict matrix reject the rest loudly."""
    import jax
    import jax.numpy as jnp

    k = max(int(cand.get("superstep", 1)), 1)
    key = jax.random.PRNGKey(seed + 7)
    images, labels = synthetic_batch(
        jax.random.PRNGKey(seed + 11), batch, sample_shape, num_classes
    )

    if n_dev <= 1:
        if error_feedback:
            raise ValueError(
                "error-feedback probes need a multi-device mesh — EF "
                "corrects the lossy EXCHANGE, and a single device has "
                "no exchange to correct"
            )
        from atomo_tpu.training import create_state, make_train_step

        state = create_state(
            model, optimizer, jax.random.PRNGKey(seed), images
        )
        step = make_train_step(
            model, optimizer, codec=codec, compute_dtype=compute_dtype,
            superstep=k,
        )
        if k > 1:
            im = jnp.broadcast_to(images, (k,) + images.shape)
            lb = jnp.broadcast_to(labels, (k,) + labels.shape)
        else:
            im, lb = images, labels
        box = {"st": state}

        def call():
            box["st"], m = step(box["st"], key, im, lb)
            box["m"] = m
            return m["loss"]

    else:
        from atomo_tpu.parallel import (
            init_delayed_state,
            make_distributed_train_step,
            make_mesh,
            replicate_state,
            shard_batch,
        )
        from atomo_tpu.parallel.replicated import shard_superbatch
        from atomo_tpu.training import create_state

        agg = cand.get("aggregate", "gather")
        overlap = cand.get("overlap", "off")
        plan = None
        inner_axis = None
        batch_axes = "dp"
        if agg == "hierarchical":
            from atomo_tpu.topology.schedule import plan_from_name

            kw = int(dcn_ways)
            if not (1 < kw <= n_dev) or n_dev % kw:
                raise ValueError(
                    f"hierarchical candidate needs dcn_ways dividing "
                    f"n_dev; got dcn_ways={kw}, n_dev={n_dev}"
                )
            mesh = make_mesh(
                n_dev, axes=(("dp", kw), ("ici", n_dev // kw))
            )
            plan = plan_from_name(cand.get("plan", "legacy"))
            inner_axis = "ici"
            batch_axes = ("dp", "ici")
        else:
            mesh = make_mesh(n_dev)
        state = create_state(
            model, optimizer, jax.random.PRNGKey(seed), images
        )
        zero1_specs = None
        if zero1:
            from atomo_tpu.parallel.replicated import zero1_state

            state, zero1_specs = zero1_state(
                mesh, state, optimizer, axis=batch_axes
            )
        else:
            state = replicate_state(mesh, state)
        step = make_distributed_train_step(
            model, optimizer, mesh, codec, aggregate=agg,
            num_aggregate=num_aggregate if agg in ("gather", "ring") else 0,
            compute_dtype=compute_dtype, zero1_specs=zero1_specs,
            grad_accum=grad_accum, superstep=k, overlap=overlap,
            ring_bucket_size=cand.get("ring_bucket_size", ring_bucket_size),
            stream_encode=cand.get("stream_encode") == "on",
            stream_bucket_bytes=int(
                cand.get("stream_bucket_bytes", 4 << 20)
            ),
            inner_axis=inner_axis, plan=plan,
            hybrid=hybrid if cand.get("sparse_rows") == "on" else None,
            error_feedback=error_feedback,
        )
        if error_feedback:
            from atomo_tpu.parallel.replicated import init_ef_state

            state = init_ef_state(mesh, state)
        if overlap == "delayed":
            state = init_delayed_state(mesh, state, codec)
        if k > 1:
            im_k = jnp.broadcast_to(images, (k,) + images.shape)
            lb_k = jnp.broadcast_to(labels, (k,) + labels.shape)
            im, lb = shard_superbatch(mesh, im_k, lb_k, axis=batch_axes)
        else:
            im, lb = shard_batch(mesh, images, labels, axis=batch_axes)
        box = {"st": state}

        def call():
            box["st"], m = step(box["st"], key, im, lb)
            box["m"] = m
            return m["loss"]

    t0 = time.perf_counter()
    per_call, sync_ok = fenced_seconds_per_call(
        call, reps=steps, warmup=warmup, best_of=max(reps, 1)
    )
    row = {
        **{kk: v for kk, v in cand.items()},
        "measured_ms_per_step": round(per_call / k * 1e3, 4),
        "probe_wall_s": round(time.perf_counter() - t0, 3),
        "sync_ok": sync_ok,
        "probed": True,
    }
    m = box.get("m")
    if m is not None and "msg_bytes" in m:
        # the executed program's OWN byte accounting (per-chip message on
        # the scarcest fabric + dense gradient size) — what
        # tests/test_topology.py compares the byte budget against
        import numpy as np

        row["measured_msg_bytes"] = int(
            np.ravel(jax.device_get(m["msg_bytes"]))[-1]
        )
        row["measured_dense_bytes"] = int(
            np.ravel(jax.device_get(m["dense_bytes"]))[-1]
        )
    return row


def probe_batch_size(batch: int, n_dev: int) -> int:
    """The probe's batch: the run's batch rounded down to a mesh multiple
    (floored at one sample per device) so shard_batch always accepts it."""
    if n_dev <= 1:
        return max(int(batch), 1)
    return max((int(batch) // n_dev) * n_dev, n_dev)
