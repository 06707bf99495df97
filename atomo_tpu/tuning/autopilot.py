"""Performance autopilot — ``--auto tune``: probe-driven config selection.

The framework exposes ~7 orthogonal performance knobs (codec+rank,
``--aggregate``, ``--superstep K``, ``--overlap``, ``--stream-encode``,
``--zero1``, ring bucket size) and an honest comm model — but a user
gets static defaults,
and the PR-4 measured result (the delayed-overlap win is load-dependent
skew absorption) proves the best config is not static. This module closes
the loop, SparCML/Parallax-style (pick the representation/collective per
density and fabric, per model — PAPERS.md):

  1. PREDICT: ``comm_model.enumerate_candidates`` +
     ``rank_candidates`` turn (model byte sizes, N, fabric) into a ranked
     candidate list of knob vectors. Predictions use stated anchors; they
     only decide which candidates are WORTH measuring.
  2. PROBE: the top of the ladder is measured for real
     (tuning.probe.probe_candidate — the same step builders the train
     path uses, fenced timing, rows written atomically as they land).
     Compile cost is amortized by the persistent compile cache
     (utils/compile_cache.py): the winner's program is already warm in
     the cache when training starts.
  3. DECIDE: :func:`choose_winner` — a PURE function of the probe rows,
     so the same artifact always names the same winner (tested). The
     decision, every candidate's predicted-vs-measured ms/step, and the
     reason the winner won land in ``tune_decision.json``.
  4. HONESTY: each probe is checked against its prediction
     (``comm_model.calibration_warning``); a >2x disagreement is logged
     with both numbers instead of silently trusted.
  5. RE-TUNE (rung 0.5 of the resilience ladder): the train loops feed a
     per-step wall-time series to :class:`OnlineRetuner`; sustained
     step-time drift (resilience.drift_update — frozen-baseline EMA with
     patience) arms a re-probe that runs at the next checkpoint boundary
     and logs its decision to ``incidents.jsonl``. The online knob space
     is deliberately the gather<->ring pair: the two aggregation
     OPERATORS are bit-identical (the PR-3 contract), so a mid-run switch
     stays within the documented cross-program fusion-drift class instead
     of changing the estimator.

Trajectory contract: probes never touch the training data iterator or
the run's init seed (tuning.probe docstring), so the tuned run's
trajectory is bit-identical to launching the chosen config statically —
asserted by a subprocess drill in tests/test_autopilot.py.
"""

from __future__ import annotations

import math
import os
import time
from typing import Callable, Optional, Sequence

TUNE_DECISION_NAME = "tune_decision.json"


def _num(row, key) -> float:
    v = row.get(key)
    try:
        v = float(v)
    except (TypeError, ValueError):
        return math.inf
    return v if math.isfinite(v) and v > 0 else math.inf


def _valid_measure(row) -> float:
    """A row's measured ms/step, or +inf when the measurement is not
    trustworthy (not probed, fence scalar came back non-finite, or the
    number itself is garbage). The ONE validity rule choose_winner and
    _why share — a sync-invalid number must never decide or be quoted
    as 'measured'."""
    if not row.get("probed") or not row.get("sync_ok", True):
        return math.inf
    return _num(row, "measured_ms_per_step")


def choose_winner(rows: Sequence[dict]) -> Optional[dict]:
    """The decision: min measured ms/step over the validly-probed rows
    (``probed`` true, ``sync_ok`` not false, finite measurement); ties
    break by predicted ms/step then candidate name. When no row was
    validly probed the prediction decides ALONE (ties by name) —
    sync-invalid measurements are classified untrustworthy and must not
    sneak back in through the fallback. A PURE deterministic function of
    the rows — same probe artifact, same winner, regardless of row
    order. None only for an empty list."""
    measured = [r for r in rows if _valid_measure(r) < math.inf]
    if measured:
        return min(
            measured,
            key=lambda r: (
                _valid_measure(r),
                _num(r, "predicted_ms_per_step"),
                str(r.get("name", "")),
            ),
        )
    if not rows:
        return None
    return min(
        rows,
        key=lambda r: (
            _num(r, "predicted_ms_per_step"),
            str(r.get("name", "")),
        ),
    )


def winner_knobs(row: dict) -> dict:
    """The knob vector a decision row pins (the fields the CLI applies and
    the static-equivalent command must pass)."""
    return {
        k: row[k]
        for k in ("aggregate", "overlap", "superstep", "ring_bucket_size",
                  "plan", "stream_encode", "stream_bucket_bytes",
                  "sparse_rows", "budget_alloc", "quorum", "staleness",
                  "error_feedback")
        if k in row
    }


def _why(rows: list[dict], winner: dict) -> str:
    ranked = sorted(
        rows,
        key=lambda r: (
            _valid_measure(r) == math.inf,
            _valid_measure(r),
            _num(r, "predicted_ms_per_step"),
            str(r.get("name", "")),
        ),
    )
    runner = next(
        (r for r in ranked if r["name"] != winner["name"]), None
    )
    bits = [f"{winner['name']} wins"]
    if _valid_measure(winner) < math.inf:
        bits.append(
            f"measured {winner['measured_ms_per_step']} ms/step "
            f"(predicted {winner.get('predicted_ms_per_step')})"
        )
    else:
        bits.append(
            f"by prediction alone ({winner.get('predicted_ms_per_step')} "
            "ms/step; no valid probe measurements)"
        )
    if runner is not None:
        r_valid = _valid_measure(runner) < math.inf
        bits.append(
            f"runner-up {runner['name']} at "
            f"{runner['measured_ms_per_step'] if r_valid else runner.get('predicted_ms_per_step')}"
            f" ms/step{' (measured)' if r_valid else ' (predicted)'}"
        )
    pred_first = min(
        rows,
        key=lambda r: (
            r.get("predicted_ms_per_step") or math.inf,
            str(r.get("name", "")),
        ),
    )
    bits.append(
        "predicted order held"
        if pred_first["name"] == winner["name"]
        else f"predicted order did NOT hold (model ranked "
        f"{pred_first['name']} first) — see calibration fields"
    )
    return "; ".join(bits)


def tune(
    *,
    model,
    optimizer,
    codec,
    model_init_fn: Callable,
    n_dev: int,
    sample_shape,
    num_classes: int,
    batch: int,
    fabric: str = "auto",
    seed: int = 0,
    artifact_path: Optional[str] = None,
    allow_ring: bool = True,
    allow_psum: bool = True,
    allow_overlap: bool = True,
    allow_stream: bool = False,
    stream_bucket_bytes: int = 4 << 20,
    stream_buckets: int = 0,
    allow_sparse: bool = False,
    hybrid=None,
    allow_budget: bool = False,
    budget_leaf_budgets=None,
    budget_codec=None,
    allow_quorum: bool = False,
    quorum_q: int = 0,
    quorum_staleness_options=(1, 2),
    quorum_delays=None,
    superstep_options=(1, 8),
    bucket_options=(65536,),
    dcn_ways: int = 0,
    plan_names=None,
    probe_top: int = 4,
    probe_steps: int = 3,
    probe_reps: int = 2,
    num_aggregate: int = 0,
    zero1: bool = False,
    partition: str = "replicated",
    grad_accum: int = 1,
    compute_dtype=None,
    codec_tax_s: Optional[float] = None,
    ring_bucket_size: int = 65536,
    context: Optional[dict] = None,
    fabric_probe: Optional[dict] = None,
    error_feedback: bool = False,
    extra_candidates: Optional[Sequence[dict]] = None,
    candidate_filter: Optional[Callable[[dict], bool]] = None,
    kind: str = "tune_decision",
    codec_for_candidate: Optional[Callable[[dict], object]] = None,
    hybrid_for_candidate: Optional[Callable[[dict], object]] = None,
    mesh_spec=None,
    log_fn=print,
) -> dict:
    """Run the startup autopilot; returns the finished decision document
    (also written atomically to ``artifact_path`` when given). Raises
    ValueError on an unresolvable ``fabric`` — the caller owns the exit.

    ``dcn_ways`` > 1 declares a two-tier mesh: the candidate space gains
    one hierarchical candidate per topology.schedule plan (``plan_names``
    narrows them), priced per tier by the :class:`TwoTierFabric` resolved
    from ``fabric`` and probed on the forced ``(dp=K, ici=n/K)`` mesh by
    the shared runner — the hierarchical/DCN probes the autopilot used to
    refuse. Flat candidates are then priced at the OUTER tier's bandwidth
    (the slowest link on their gradient path). The chosen plan lands in
    the decision artifact's winner knobs.

    ``allow_sparse`` + ``hybrid`` (a sparse.hybrid.HybridPlan with at
    least one sparse-assigned leaf) add a ``+sp`` variant of every plain
    blocking gather/ring candidate, priced from the plan's per-leaf wire
    bytes (``comm_model.leaf_budget_totals`` — the same sums the
    executed program reports) and probed through the SAME step builder
    with the plan attached.

    ``allow_budget`` + ``budget_codec`` (a ``budget.PerLeafCodec`` built
    from the run's solved allocation) + ``budget_leaf_budgets`` (its
    per-leaf pairs, ``budget.allocation_leaf_budgets``) add a ``+ab``
    variant of every plain blocking gather/ring candidate: priced from
    the allocation's clamped per-leaf sums and probed through the SAME
    step builder with the WRAPPED codec swapped in — the measured ladder
    decides whether the adaptive split beats the uniform one on this
    deployment, and the winner's ``budget_alloc`` knob records it.

    ``allow_quorum`` + ``quorum_q`` >= 1 add the ``+qK`` bounded-
    staleness variants (one per bound in ``quorum_staleness_options``)
    of every plain blocking gather/ring candidate, PRICED by the
    expected exposed straggler wait (``quorum_delays`` — the chaos
    ``slow@`` table's per-replica lag vector; blocking candidates pay
    its max, quorum candidates its Q-th order statistic,
    ``comm_model.quorum_exposed_wait_s``) but never PROBED: the probe
    harness runs straggler-free, so a measured quorum probe would omit
    exactly the wait the candidate exists to absorb — the rows carry
    the prediction and say why (``probe_note``).

    ``fabric_probe`` (the ``fabric_probe.json`` document) is required
    when ``fabric == "measured"``: the ONE parsers resolve the token
    from it, so every candidate — flat and hierarchical — is priced
    from the measured mesh, and the decision artifact's meta records
    the measured per-tier GB/s (``meta.fabric_tiers``) so the report's
    cross-artifact check can audit decision against probe.

    ``error_feedback=True`` tunes the residual-carry runs (ISSUE-17
    satellite): the candidate space is NARROWED to the flat blocking
    programs EF composes with (overlap/sparse/quorum/hierarchical off,
    ``num_aggregate`` forced 0 — the same conflict matrix the step
    builder enforces loudly), every probe builds the EF step, and every
    row + the meta carry ``error_feedback: "on"`` plus the BIAS CONTRACT
    note: EF changes the estimator (residuals accumulate, gradients are
    no longer unbiased per step), so its measured ms/step is comparable
    to non-EF rows on wall-clock ONLY — never on steps-to-accuracy.

    CONTROLLER HOOKS (tentpole; defaults reproduce the legacy autopilot
    bit-identically): ``extra_candidates`` appends caller-built joint
    candidates (each may carry its own per-leaf ``leaf_budgets``
    override, which ``predict_step_s`` prices FIRST) to the enumerated
    space before ranking; ``candidate_filter`` restricts the merged
    space (the controller's degeneracy subspaces); ``kind`` names the
    artifact document;
    ``codec_for_candidate(cand)`` / ``hybrid_for_candidate(cand)``
    override how the probe loop resolves the codec / hybrid plan per
    candidate — the default is the legacy pair (budget-wrapped codec for
    ``+ab`` rows, the one hybrid plan for ``+sp`` rows).
    """
    import jax

    from atomo_tpu.tuning.probe import (
        ProbeLadder,
        byte_budget,
        probe_batch_size,
        probe_candidate,
    )
    from atomo_tpu.utils.comm_model import (
        DISPATCH_ANCHOR_S,
        calibration_warning,
        enumerate_candidates,
        rank_candidates,
        resolve_fabric,
    )

    t_start = time.perf_counter()
    if error_feedback:
        # EF's conflict matrix (parallel.replicated rejects these at
        # build time): narrow the space HERE so the ladder never wastes
        # probes on programs the builder would refuse
        if zero1:
            raise ValueError(
                "error feedback shards residuals per replica; zero1's "
                "sharded optimizer state conflicts — run EF without "
                "--zero1 (the step builder rejects the pair)"
            )
        if allow_overlap or allow_sparse or allow_quorum or (
            int(dcn_ways) > 1 or int(num_aggregate) > 0
        ):
            log_fn(
                "Autopilot: --error-feedback narrows the candidate "
                "space to flat blocking programs (overlap/sparse/"
                "quorum/hierarchical/num-aggregate excluded — the EF "
                "conflict matrix)"
            )
        allow_overlap = False
        allow_sparse = False
        allow_quorum = False
        dcn_ways = 0
        num_aggregate = 0
    fabric2 = None
    two_tier = int(dcn_ways) > 1 and n_dev > 1 and n_dev % int(dcn_ways) == 0
    if two_tier:
        from atomo_tpu.topology.fabric import resolve_two_tier

        fabric2 = resolve_two_tier(
            fabric, dcn_ways=int(dcn_ways), n_dev=n_dev,
            n_proc=jax.process_count(), measured=fabric_probe,
        )
        # flat candidates cross the slow tier end to end: price them at
        # the OUTER bandwidth, not a blended scalar
        bw = fabric2.outer_bw
    else:
        try:
            bw = resolve_fabric(
                fabric, n_proc=jax.process_count(), measured=fabric_probe
            )
        except ValueError:
            # a two-tier <inner>:<outer> fabric string with a flat
            # candidate space (e.g. the CLI excluded the hierarchical
            # candidates for densify/num-aggregate, or dcn_ways does not
            # divide the mesh): flat candidates cross the slow tier end
            # to end, so price them at the OUTER token — do not reject a
            # valid two-tier string with the single-scalar usage line
            if ":" not in fabric:
                raise
            outer_tok = fabric.rpartition(":")[2]
            bw = resolve_fabric(
                outer_tok, n_proc=jax.process_count(),
                measured=fabric_probe,
            )
            log_fn(
                f"Autopilot: two-tier --fabric {fabric!r} with a flat "
                "candidate space; pricing flat candidates at the outer "
                f"tier ({outer_tok})"
            )
    dense_b, payload_b = byte_budget(codec, model_init_fn)
    backend = jax.default_backend()
    dispatch_s = DISPATCH_ANCHOR_S.get(backend, 5e-4)
    cands = enumerate_candidates(
        has_codec=codec is not None,
        ways=n_dev,
        allow_ring=allow_ring,
        allow_psum=allow_psum,
        allow_overlap=allow_overlap,
        allow_stream=allow_stream,
        stream_bucket_bytes=stream_bucket_bytes,
        stream_buckets=stream_buckets,
        allow_sparse=bool(allow_sparse and hybrid is not None),
        sparse_leaf_budgets=(
            hybrid.leaf_budgets() if hybrid is not None else None
        ),
        allow_budget=bool(allow_budget and budget_codec is not None),
        budget_leaf_budgets=budget_leaf_budgets,
        allow_quorum=bool(allow_quorum),
        quorum_q=int(quorum_q),
        quorum_staleness_options=quorum_staleness_options,
        superstep_options=superstep_options,
        bucket_options=bucket_options,
        dcn_ways=int(dcn_ways) if two_tier else 0,
        plan_names=plan_names,
    )
    if extra_candidates:
        # the controller's joint candidates ride the SAME ranked ladder
        # as the enumerated space — one predict_step_s ordering decides
        # who gets probed, not four independent winners
        cands = list(cands) + [dict(c) for c in extra_candidates]
    if candidate_filter is not None:
        # the controller's subspace restriction (degeneracy tests pin
        # each legacy decider's winner when the search is confined to
        # that decider's knob axes)
        cands = [c for c in cands if candidate_filter(c)]
    ranked = rank_candidates(
        cands,
        dense_bytes=dense_b,
        payload_bytes=payload_b,
        ways=n_dev,
        fabric_bw=bw,
        tax_s=codec_tax_s,
        dispatch_s=dispatch_s,
        fabric2=fabric2,
        # prices the +sp candidates from the plan's per-leaf pairs —
        # held ONCE here rather than copied into every candidate row
        sparse_leaf_budgets=(
            hybrid.leaf_budgets() if hybrid is not None else None
        ),
        # prices the +ab candidates from the allocation's per-leaf
        # pairs — held once here, like the sparse budgets above
        budget_leaf_budgets=budget_leaf_budgets,
        # the straggler-exposure term: blocking candidates pay the max
        # delay, +qK candidates the Q-th order statistic
        quorum_delays=quorum_delays,
    )
    from atomo_tpu.mesh import MeshSpec

    pb = probe_batch_size(batch, n_dev)
    meta = {
        "backend": backend,
        "n_devices": n_dev,
        # the PROBED mesh's named-axis shape (insertion-ordered dict):
        # decision_reusable compares it on resume — an n_devices-only
        # check cannot tell dp4 from dp2 x ici2, which are different
        # program families. A caller-supplied mesh_spec (the model-axis
        # layouts: dp2 x tp2 etc.) wins over the data-axes-only
        # reconstruction, so the record names tp/pp/ep/sp too.
        "mesh_axes": (
            mesh_spec.shape_dict()
            if mesh_spec is not None
            else MeshSpec.from_world(
                n_dev, dcn_ways if two_tier else 0
            ).shape_dict()
        ),
        # the weight-update partition the run trains with (recorded for
        # the audit trail; candidates are partition-agnostic because
        # partition families are trajectory-compatible per codec)
        "partition": partition,
        "fabric": fabric,
        "fabric_gbps_per_chip": round(bw / 1e9, 3),
        # a measured fabric's per-tier GB/s, copied from the probe doc
        # so report's fabric_probe_consistent check can audit this
        # decision against the artifact it was priced from
        **(
            {
                "fabric_tiers": {
                    t["label"]: t["bandwidth_gbps"]
                    for t in fabric_probe.get("tiers", [])
                    if t.get("bandwidth_gbps")
                }
            }
            if fabric == "measured" and fabric_probe is not None
            else {}
        ),
        **(
            {
                "dcn_ways": int(dcn_ways),
                "two_tier_fabric": fabric2.describe(),
            }
            if fabric2 is not None
            else {}
        ),
        "dense_mb": round(dense_b / 1e6, 3),
        "payload_mb": round(payload_b / 1e6, 3),
        "batch": pb,
        "probe": {
            "steps": probe_steps,
            "reps": probe_reps,
            "top": probe_top,
        },
        # the bias contract (tune() docstring): EF rows compare on
        # wall-clock only — the estimator changed, so steps-to-accuracy
        # is a different experiment
        **({"error_feedback": "on"} if error_feedback else {}),
        **(context or {}),
    }
    ladder = ProbeLadder(
        artifact_path, kind=kind, meta=meta, log_fn=log_fn
    )
    ef_note = (
        "error feedback changes the comparison basis: residual carry "
        "makes the per-step gradient biased, so this row's ms/step is "
        "comparable to non-EF rows on wall-clock only"
    )
    n_probe = max(1, min(int(probe_top), len(ranked)))
    for i, cand in enumerate(ranked):
        # per-candidate leaf_budgets overrides are a PRICING input (the
        # controller's joint candidates) — already consumed by the
        # ranker; keep them out of the recorded rows and the knob vector
        pub = {k: v for k, v in cand.items() if k != "leaf_budgets"}
        if error_feedback:
            pub["error_feedback"] = "on"
        if cand.get("quorum"):
            # priced, never probed (tune() docstring): the probe harness
            # runs straggler-free, so a measured quorum probe would omit
            # exactly the exposed wait the candidate exists to absorb
            ladder.record({
                **pub,
                "probed": False,
                "probe_note": (
                    "quorum candidates are priced by expected exposed "
                    "wait, not probed — the straggler-free probe harness "
                    "cannot measure the wait they absorb"
                ),
            })
            continue
        if cand.get("model_axes"):
            # priced, never probed (the quorum precedent): the probe
            # harness builds replicated-family programs, not model-axis
            # LM steps; these rows are priced from the wire model plus
            # the layout's pre-priced axis-collective floor
            # (model_comm_s / pipeline_bubble_s); their byte accounting is
            # held by tests/test_model_axes.py, their time is not measured
            ladder.record({
                **pub,
                "probed": False,
                "probe_note": (
                    "model-axis lm candidates are priced (dp wire + "
                    "axis-collective floor), not probed — the probe "
                    "harness builds replicated-family programs; "
                    "no on-chip measurement on record"
                ),
            })
            continue
        if i >= n_probe:
            ladder.record({**pub, "probed": False})
            continue
        knobs = {
            k: v
            for k, v in cand.items()
            if k in ("aggregate", "overlap", "superstep",
                     "ring_bucket_size", "plan", "name",
                     "stream_encode", "stream_bucket_bytes",
                     "sparse_rows", "budget_alloc")
        }
        if codec_for_candidate is not None:
            run_codec = codec_for_candidate(cand)
        else:
            # +ab candidates probe the REAL program the run would
            # dispatch: the per-leaf wrapped codec swaps in
            run_codec = (
                budget_codec
                if knobs.get("budget_alloc") == "variance"
                else codec
            )
        run_hybrid = (
            hybrid_for_candidate(cand)
            if hybrid_for_candidate is not None
            else hybrid
        )
        try:
            row = probe_candidate(
                knobs,
                model=model,
                optimizer=optimizer,
                codec=run_codec,
                n_dev=n_dev,
                sample_shape=sample_shape,
                num_classes=num_classes,
                batch=pb,
                seed=seed,
                steps=probe_steps,
                reps=probe_reps,
                num_aggregate=num_aggregate,
                zero1=zero1,
                grad_accum=grad_accum,
                compute_dtype=compute_dtype,
                dcn_ways=int(dcn_ways) if two_tier else 0,
                # the fallback for candidates that carry no explicit
                # ring_bucket_size knob (the hierarchical plans' ring
                # tiers): probe at the value the run will execute with,
                # not the builder default
                ring_bucket_size=ring_bucket_size,
                hybrid=run_hybrid,
                error_feedback=error_feedback,
            )
        except Exception as exc:  # noqa: BLE001 — one candidate failing
            # to compile/execute (OOM, a backend quirk) must not abort the
            # whole tune: record the failure, keep climbing the ladder
            # (the default config and eventual winner may be fine)
            row = {
                **pub,
                "probed": False,
                "probe_error": f"{type(exc).__name__}: {str(exc)[:200]}",
            }
            ladder.record(row)
            log_fn(
                f"Autopilot probe [{i + 1}/{n_probe}] {cand['name']} "
                f"FAILED ({row['probe_error']}); candidate dropped from "
                "the measured pool"
            )
            continue
        row["predicted_ms_per_step"] = cand["predicted_ms_per_step"]
        if error_feedback:
            row["error_feedback"] = "on"
            row["probe_note"] = ef_note
        warn = calibration_warning(
            cand["predicted_ms_per_step"] / 1e3,
            row["measured_ms_per_step"] / 1e3,
            label=cand["name"],
        )
        row["calibration"] = warn
        if warn:
            log_fn(f"Autopilot: {warn}")
        ladder.record(row)
        log_fn(
            f"Autopilot probe [{i + 1}/{n_probe}] {cand['name']}: "
            f"measured {row['measured_ms_per_step']} ms/step "
            f"(predicted {cand['predicted_ms_per_step']})"
        )
    winner = choose_winner(ladder.rows)
    why = _why(ladder.rows, winner) if winner is not None else "no candidates"
    doc = ladder.finish(
        winner=None if winner is None else {
            "name": winner["name"],
            "knobs": winner_knobs(winner),
            "measured_ms_per_step": winner.get("measured_ms_per_step"),
            "predicted_ms_per_step": winner.get("predicted_ms_per_step"),
        },
        why=why,
        tune_wall_s=round(time.perf_counter() - t_start, 3),
    )
    log_fn(f"Autopilot decision: {why}")
    if artifact_path:
        log_fn(f"Autopilot: decision artifact -> {artifact_path}")
    return doc


def decision_path(train_dir: str) -> str:
    return os.path.join(train_dir, TUNE_DECISION_NAME)


def decision_reusable(
    doc, *, n_dev: int, mesh_axes: Optional[dict] = None,
    quorum: Optional[int] = None, staleness: Optional[int] = None,
    fleet_roster: Optional[str] = None,
) -> tuple[bool, str]:
    """Can a ``--resume`` reuse this recorded tune decision?

    A resumed run must NOT re-probe (probe timings vary run to run, and a
    different winner could try to resume checkpoints written by a
    different program family) — but reuse has a validity condition the
    unconditional PR-7 path missed: the decision is a function of the
    WORLD SIZE (``meta.n_devices``). After an elastic shrink/grow (or a
    manual relaunch at a different ``--n-devices``) the recorded winner
    may be sized for a mesh that no longer exists — a ring plan for N
    chips, a superstep/bucket point picked from N-way probe timings — so
    a mismatch re-tunes instead of silently applying a stale config.

    ``mesh_axes`` (the resuming run's named-axis shape,
    ``MeshSpec.shape_dict()``) tightens the check to the MESH SHAPE: once
    dp x ici axes exist, ``n_devices`` alone cannot tell ``dp4`` from
    ``dp2 x ici2`` — a hierarchical winner probed on the two-tier mesh
    is not valid for the flat one (and vice versa), so a recorded
    ``meta.mesh_axes`` that differs refuses reuse. Artifacts that
    predate the mesh record fall back to the n_devices check (said in
    the reason, never silently).

    ``quorum``/``staleness`` (the resuming run's bounded-staleness
    knobs; None/0 = quorum off) must match what the recorded winner
    pinned: a decision priced under one (Q, K) means something else
    under another — the same refusal family as the arrival artifact's
    meta check (quorum.rig), applied to the tune decision.

    ``fleet_roster`` (the resuming run's host roster hash,
    ``fleet.control.current_roster_hash``; None = no fleet evidence)
    refuses reuse when the HOST ROSTER changed at the same device
    count: two swapped hosts or one replaced machine keep ``n_devices``
    and ``mesh_axes`` identical while moving data placement and stream
    splits, which only the roster fingerprint sees. Artifacts that
    predate the fleet record fall back to the device-count/mesh checks
    (said in the reason, never silently).

    Returns ``(reusable, reason)``; the reason is logged either way and
    lands in incidents.jsonl on the re-tune path. A PURE function of the
    document (tested), like choose_winner."""
    if not doc or not doc.get("complete"):
        return False, "decision artifact is missing or incomplete"
    if not ((doc.get("winner") or {}).get("knobs")):
        return False, "decision artifact names no winner"
    knobs = (doc.get("winner") or {}).get("knobs") or {}
    rec_q = knobs.get("quorum") or None
    rec_k = knobs.get("staleness") or None
    run_q = int(quorum) if quorum else None
    run_k = int(staleness) if staleness else None
    # run_k None with a real run_q = "any K" (the resume site under
    # --auto tune knows the chaos-derived Q but K was the ladder's pick)
    if rec_q != run_q or (
        rec_q is not None and run_k is not None and rec_k != run_k
    ):
        return False, (
            f"decision pinned quorum={rec_q} staleness={rec_k} but this "
            f"run sets quorum={run_q} staleness={run_k} — a winner "
            "priced under one (Q, K) is invalid under another; "
            "re-tuning"
        )
    rec = (doc.get("meta") or {}).get("n_devices")
    if rec != n_dev:
        return False, (
            f"decision was tuned for n_devices={rec} but this run has "
            f"{n_dev} (elastic shrink/grow or a manual resize) — the "
            "recorded winner may be invalid for this world; re-tuning"
        )
    meta = doc.get("meta") or {}
    fleet_note = ""
    if fleet_roster is not None:
        rec_fleet = meta.get("fleet_roster_hash")
        if rec_fleet is None:
            fleet_note = (
                "; artifact predates the fleet roster record, so the "
                "host-roster check falls back to device count alone"
            )
        elif rec_fleet != fleet_roster:
            return False, (
                f"decision was tuned on fleet roster {rec_fleet} but "
                f"this run's roster hashes to {fleet_roster} (same "
                "device count, different hosts — data placement and "
                "stream splits are roster facts); re-tuning"
            )
    if mesh_axes is not None:
        rec_axes = meta.get("mesh_axes")
        reconstructed = False
        if rec_axes is None:
            # legacy artifact: reconstruct the probed shape from the
            # recorded dcn_ways (two-tier artifacts have carried it
            # since the topology PR) — a legacy hierarchical decision
            # must not be silently applied to a flat mesh of the same
            # device count
            from atomo_tpu.mesh import MeshSpec

            try:
                rec_axes = MeshSpec.from_world(
                    rec, int(meta.get("dcn_ways") or 0)
                ).shape_dict()
                reconstructed = True
            except (TypeError, ValueError):
                rec_axes = None
        if rec_axes is None:
            return True, (
                f"recorded decision matches this world size ({n_dev}); "
                "artifact predates the mesh_axes record, so the shape "
                "check falls back to n_devices only" + fleet_note
            )
        src = (
            " (reconstructed from the legacy artifact's dcn_ways)"
            if reconstructed
            else ""
        )
        if dict(rec_axes) != dict(mesh_axes):
            return False, (
                f"decision was tuned on mesh {rec_axes}{src} but this "
                f"run's mesh is {mesh_axes} (same device count, "
                "different axis shape — different program family); "
                "re-tuning"
            )
        return True, (
            f"recorded decision matches this mesh shape ({mesh_axes})"
            + src + fleet_note
        )
    return True, (
        f"recorded decision matches this world size ({n_dev})"
        + fleet_note
    )


class OnlineRetuner:
    """Rung 0.5 of the resilience ladder: step-time drift -> re-probe.

    The train loops feed per-step wall seconds to :meth:`observe` (the
    same sequential-fold contract as the divergence detector: one value
    at a time or a block's worth — identical decisions for any
    partition). A sustained excursion past the
    :class:`~atomo_tpu.training.resilience.DriftConfig` threshold arms a
    PENDING re-probe; the loop executes it at the next checkpoint
    boundary via :meth:`maybe_retune`, which measures the candidate
    modes with ``probe_fn`` and logs the decision — switch or keep — to
    ``incidents.jsonl``.

    The online knob space is the gather<->ring aggregation pair ONLY:
    their operators are bit-identical (PR-3 contract), so a switch keeps
    the estimator and stays within the documented cross-program
    fusion-drift class (~1e-8, the scan-vs-standalone family) — the
    incident record says when one happened. Heavier knobs (codec,
    overlap, superstep) are startup-tune territory: changing them mid-run
    would change the program family the run's determinism contracts are
    stated over. ``probe_fn=None`` is the observe-only mode (the
    single-host loop): drift is still detected and logged as an incident,
    but nothing is switched — a single device has no exchange to re-pick.

    DRIFT BLAME (the fabric-observatory lift): a step-time alarm has two
    root-cause families — the FABRIC moved (a contended link, a changed
    route) or the PROGRAM did (a different phase balance, a remedy, a
    slow host). With ``fabric_probe_fn`` armed (the CLI wires it for
    ``--fabric measured`` runs, whose startup probe is the baseline),
    :meth:`maybe_retune` re-runs the cheap fabric probe and every
    ``perf_drift`` retune incident carries a ``blame`` record quoting
    BOTH numbers: the step-time pair (frozen baseline vs the observed
    excursion) and, per tier, baseline-vs-measured GB/s. Verdict
    ``fabric`` (any tier moved past ``obs.fabric.FABRIC_MOVED_RATIO``)
    additionally invokes ``on_fabric_moved`` so the caller re-prices —
    the CLI rewrites ``fabric_probe.json`` with the fresh measurement;
    verdict ``program`` leaves the re-probe of candidates (already this
    method's job) as the response. Without a fabric baseline the blame
    record says so (``basis``) instead of guessing.
    """

    def __init__(
        self,
        probe_fn: Optional[Callable[[str], float]] = None,
        modes: Sequence[str] = ("gather", "ring"),
        drift=None,
        margin: float = 1.05,
        incidents=None,
        fabric_probe_fn: Optional[Callable[[], dict]] = None,
        fabric_baseline: Optional[dict] = None,
        on_fabric_moved: Optional[Callable[[dict], None]] = None,
        log_fn=print,
    ):
        from atomo_tpu.training.resilience import DriftConfig, DriftState

        self.probe_fn = probe_fn
        self.modes = tuple(modes)
        self.cfg = drift if drift is not None else DriftConfig()
        self.state = DriftState()
        self.margin = float(margin)
        self.incidents = incidents
        self.fabric_probe_fn = fabric_probe_fn
        self.fabric_baseline = dict(fabric_baseline or {})
        self.on_fabric_moved = on_fabric_moved
        self.log_fn = log_fn
        self.pending: Optional[str] = None
        self._alarm_ms: Optional[dict] = None
        self.retunes = 0
        self.switches = 0

    def bind(self, incidents=None, log_fn=None) -> "OnlineRetuner":
        """Late-bind the loop-owned incident log / logger (the CLI builds
        the retuner before the loop builds its IncidentLog)."""
        if incidents is not None:
            self.incidents = incidents
        if log_fn is not None:
            self.log_fn = log_fn
        return self

    def observe(self, dts) -> Optional[str]:
        """Fold per-step wall seconds (scalar or a block's series); arms
        the pending re-probe on a drift alarm. Returns the alarm reason
        when one fired (already-pending blocks re-arming noise)."""
        from atomo_tpu.training.resilience import drift_scan

        self.state, alarm = drift_scan(self.cfg, self.state, dts)
        if alarm is not None and self.pending is None:
            self.pending = alarm
            # the blame record's program-side pair: the frozen baseline
            # vs the excursion that fired the alarm (the last observed
            # share — representative of the sustained run, the detector
            # requires `patience` of them above ratio x baseline)
            try:
                last = [float(d) for d in (
                    dts if hasattr(dts, "__iter__") else [dts]
                )]
                obs = next(
                    (d for d in reversed(last)
                     if math.isfinite(d) and d > 0), None,
                )
            except (TypeError, ValueError):
                obs = None
            self._alarm_ms = {
                "baseline": round(self.state.mean * 1e3, 3),
                "observed": (
                    round(obs * 1e3, 3) if obs is not None
                    else round(self.state.mean * 1e3, 3)
                ),
            }
            self.log_fn(
                f"Autopilot: sustained step-time drift detected "
                f"(baseline {self.state.mean * 1e3:.1f} ms/step); "
                "re-probe scheduled for the next checkpoint boundary"
            )
            return alarm
        return None

    def _blame(self) -> dict:
        """The drift-blame record (class docstring): re-run the cheap
        fabric probe and quote BOTH number pairs — per-tier
        baseline-vs-measured GB/s and the baseline-vs-observed step ms.
        Verdict ``fabric`` when any tier moved past
        ``obs.fabric.FABRIC_MOVED_RATIO`` either way (the re-price hook
        ``on_fabric_moved`` then fires); ``program`` otherwise — the
        candidate re-probe is the response. A failed or unavailable
        fabric probe is stated in ``basis``, never guessed around."""
        blame: dict = {
            "verdict": "program",
            "step_ms": dict(
                self._alarm_ms
                or {"baseline": round(self.state.mean * 1e3, 3),
                    "observed": None}
            ),
        }
        if self.fabric_probe_fn is None or not self.fabric_baseline:
            blame["basis"] = (
                "no fabric baseline (run --fabric measured to arm "
                "fabric blame); program blamed by default — the "
                "candidate re-probe decides the response"
            )
            return blame
        try:
            probe_doc = self.fabric_probe_fn()
        except Exception as exc:  # noqa: BLE001 — blame must not kill training
            blame["basis"] = (
                f"fabric re-probe failed ({type(exc).__name__}: "
                f"{str(exc)[:120]}); program blamed by default"
            )
            return blame
        from atomo_tpu.obs.fabric import (
            FABRIC_MOVED_RATIO,
            measured_bandwidths,
        )

        tiers = {}
        moved = False
        for label, bw in sorted(measured_bandwidths(probe_doc).items()):
            base = self.fabric_baseline.get(label)
            row = {"measured_gbps": round(bw / 1e9, 4)}
            if base and base > 0:
                ratio = bw / float(base)
                row["baseline_gbps"] = round(float(base) / 1e9, 4)
                row["ratio"] = round(ratio, 4)
                if not (
                    1.0 / FABRIC_MOVED_RATIO <= ratio <= FABRIC_MOVED_RATIO
                ):
                    moved = True
            tiers[label] = row
        blame["fabric"] = tiers
        blame["basis"] = (
            f"per-tier re-probe vs the startup baseline "
            f"(moved = ratio outside 1/{FABRIC_MOVED_RATIO}x.."
            f"{FABRIC_MOVED_RATIO}x)"
        )
        if moved:
            blame["verdict"] = "fabric"
            self.log_fn(
                "Autopilot: drift blame = FABRIC (per-tier GB/s moved "
                f"past {FABRIC_MOVED_RATIO}x: "
                + ", ".join(
                    f"{lbl} {r.get('baseline_gbps')}->"
                    f"{r.get('measured_gbps')}"
                    for lbl, r in tiers.items()
                )
                + "); re-pricing from the fresh probe"
            )
            # re-price: the new measurement replaces the stale baseline
            # for the NEXT alarm, and the caller persists it (the CLI
            # rewrites fabric_probe.json so resumes and reports read
            # the fabric that actually exists now)
            self.fabric_baseline = measured_bandwidths(probe_doc)
            if self.on_fabric_moved is not None:
                try:
                    self.on_fabric_moved(probe_doc)
                except Exception as exc:  # noqa: BLE001
                    self.log_fn(
                        f"Autopilot: fabric re-price hook failed: {exc}"
                    )
        else:
            self.log_fn(
                "Autopilot: drift blame = PROGRAM (fabric within "
                f"{FABRIC_MOVED_RATIO}x of baseline per tier); the "
                "candidate re-probe decides"
            )
        return blame

    def maybe_retune(self, step: int, current_mode: str) -> Optional[str]:
        """Execute the pending re-probe (call at a checkpoint boundary).
        Returns the new aggregation mode when the probe says switch, else
        None. Every outcome is one incident record; the drift baseline
        restarts either way (the world just changed — relearn it)."""
        from atomo_tpu.training.resilience import DriftState

        if self.pending is None:
            return None
        reason, self.pending = self.pending, None
        self.retunes += 1
        self.state = DriftState()
        blame = self._blame()
        if self.probe_fn is None or current_mode not in self.modes:
            # observe-only (single-host, or a mode outside the safe online
            # pair, e.g. psum/hierarchical): record the drift, keep config
            if self.incidents is not None:
                self.incidents.append(
                    "perf_drift",
                    action="observed",
                    step=step,
                    reason=reason,
                    mode=current_mode,
                    blame=blame,
                )
            self.log_fn(
                f"Autopilot: step-time drift at step {step} recorded; "
                f"no online knob to re-pick for mode {current_mode!r}"
            )
            return None
        measured = {}
        for m in self.modes:
            try:
                measured[m] = float(self.probe_fn(m))
            except Exception as exc:  # a failed probe must not kill training
                self.log_fn(f"Autopilot: re-probe of {m!r} failed: {exc}")
        finite = {
            m: v for m, v in measured.items()
            if math.isfinite(v) and v > 0
        }
        new_mode = None
        if finite:
            best = min(finite, key=lambda m: (finite[m], m))
            cur = finite.get(current_mode)
            if (
                best != current_mode
                and cur is not None
                and finite[best] * self.margin < cur
            ):
                new_mode = best
        action = f"retune->{new_mode}" if new_mode else "retune_keep"
        if self.incidents is not None:
            self.incidents.append(
                "perf_drift",
                action=action,
                step=step,
                reason=reason,
                mode=current_mode,
                measured_ms={
                    m: round(v, 4) for m, v in measured.items()
                },
                blame=blame,
            )
        if new_mode:
            self.switches += 1
            self.log_fn(
                f"Autopilot: re-tune at step {step}: aggregate "
                f"{current_mode} -> {new_mode} "
                f"({finite[new_mode]:.2f} vs {finite[current_mode]:.2f} "
                "ms/step; operators bit-identical, program family change "
                "logged)"
            )
        else:
            self.log_fn(
                f"Autopilot: re-tune at step {step} keeps aggregate "
                f"{current_mode} (measured "
                + ", ".join(f"{m}={v:.2f}" for m, v in measured.items())
                + " ms/step)"
            )
        return new_mode
