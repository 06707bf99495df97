"""The joint solve — one priced decision over every knob.

The repo grew four independent deciders, each already pure and tested:
the autopilot's probe ladder (``tuning.autopilot.tune``), the
water-filling allocation (``budget.allocator.solve_allocation``), the
per-layer hybrid crossover (``sparse.hybrid.plan_hybrid``) and the
two-tier plan ranking (``topology.schedule.choose_plan``). Each picked
its own winner; the cross terms (+sp+ab, +ab under delayed overlap /
stream encode / hierarchical plans / quorum) were never priced, so
"four local optima" stood in for one joint one.

:func:`solve_controller` composes the pure solvers as SUBROUTINES of
one structured search instead of four independent winners:

  1. The allocation is solved once (the caller's budget context — the
     same ``solve_allocation`` output the legacy ``--budget-alloc``
     path trains with), the hybrid plan once under the base codec and
     once under the budget-wrapped codec (the ``+sp+ab`` repricing).
  2. ``space.joint_candidates`` builds the cross terms, each carrying
     its own per-leaf wire override where needed; they merge into the
     autopilot's enumerated space and ONE ``predict_step_s`` ranking
     orders everything.
  3. Only the shortlist is probed, through the existing harness — the
     engine IS ``tune()`` (kind="controller_decision"), so timing
     discipline, row schema, calibration warnings, and
     partial-artifact atomicity are inherited, not reimplemented.
  4. The artifact meta carries the solved allocation and hybrid
     assignment (``controller.artifact`` docstring), so ONE document
     is the resume source of truth under refuse-on-mismatch.

Degeneracy (tested): restricting the search to one decider's knob axes
(``deciders={"autopilot"}`` etc.) reproduces that decider's winner
bit-identically — the controller is a superset of the legacy paths,
not a fifth opinion. For topology the identity is analytic:
``choose_plan`` ranks plans by ``predict_plan_step_s`` at the same
dispatch/superstep point the candidate ranking uses, and the name
tie-break embeds the plan name, so the hierarchical candidates' order
equals the plan ranking's.
"""

from __future__ import annotations

from typing import Optional

from atomo_tpu.controller.space import (
    DECIDERS,
    candidate_predicate,
    joint_candidates,
    lm_axis_candidates,
    normalize_deciders,
)


def pack_kernel_record(codec) -> dict:
    """The pack-kernel pricing record (qsgd_kernels graduation drill):
    which encode path ``pack_kernel=None`` resolves to on THIS backend,
    and the measured-win table the resolution read — auditable in the
    artifact, so a future real-TPU win visibly flips the selection."""
    import jax

    from atomo_tpu.ops.qsgd_kernels import (
        PACK_KERNEL_MEASURED_WINS,
        is_tpu,
        pack_kernel_default,
    )

    has_knob = hasattr(codec, "pack_kernel")
    kind = jax.devices()[0].device_kind
    rec = {
        "codec_has_knob": bool(has_knob),
        "device_kind": kind,
        "on_tpu": is_tpu(),
        "measured_wins": {
            tag: dict(v) for tag, v in sorted(
                PACK_KERNEL_MEASURED_WINS.items()
            )
        },
    }
    if has_knob:
        pinned = getattr(codec, "pack_kernel", None)
        rec["selected"] = bool(
            pinned if pinned is not None else pack_kernel_default()
        )
        rec["source"] = (
            "pinned by the codec" if pinned is not None
            else "resolved from the measured-win table"
        )
    return rec


def solve_controller(
    *,
    model,
    optimizer,
    codec,
    model_init_fn,
    n_dev: int,
    sample_shape,
    num_classes: int,
    batch: int,
    deciders=None,
    fabric: str = "auto",
    seed: int = 0,
    artifact_path: Optional[str] = None,
    budget_ctx: Optional[dict] = None,
    hybrid=None,
    hybrid_inputs: Optional[dict] = None,
    allow_ring: bool = True,
    allow_psum: bool = True,
    allow_overlap: bool = True,
    allow_stream: bool = False,
    stream_bucket_bytes: int = 4 << 20,
    stream_buckets: int = 0,
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
    mesh_spec=None,
    lm_codec_tag: str = "",
    lm_model_comm_s: float = 0.0,
    lm_pipeline_bubble_s: float = 0.0,
    log_fn=print,
) -> dict:
    """One joint solve (module docstring); returns the finished decision
    document, written atomically to ``artifact_path`` when given.

    ``budget_ctx`` is the CLI's budget context dict (``base_codec``,
    wrapped ``codec``, ``spectra``, ``alloc``, ``doc``,
    ``leaf_budgets``) — present iff the budget decider has an
    allocation to offer. ``hybrid`` is the base-codec
    :class:`~atomo_tpu.sparse.hybrid.HybridPlan`; ``hybrid_inputs``
    (``grads_like`` / ``densities`` / ``row_bounds``, the
    ``plan_hybrid`` argument triple) additionally enables the
    ``+sp+ab`` cross term by re-planning under the wrapped codec —
    without it the cross term is skipped and the log says so (scoped
    honestly, never guessed).

    ``mesh_spec`` (a :class:`~atomo_tpu.mesh.spec.MeshSpec`) records the
    run's FULL named-axis shape in ``meta.mesh_axes`` (so
    ``decision_reusable``/``controller_reusable`` refuse a model-axis
    shape mismatch on resume, not just a device-count change); when it
    carries live model axes the space additionally gains the layout's
    ``lm[...]`` candidates (:func:`~atomo_tpu.controller.space.
    lm_axis_candidates`) — priced from the dp wire plus the
    ``lm_model_comm_s`` / ``lm_pipeline_bubble_s`` axis-collective
    floor, never probed (the quorum precedent: the probe harness builds
    replicated-family programs). ``lm_codec_tag`` names the codec in
    those rows (``lm[tp2]+qsgd8+...``)."""
    from atomo_tpu.tuning.autopilot import tune

    d = normalize_deciders(deciders)
    have_budget = "budget" in d and bool(budget_ctx)
    have_sparse = "hybrid" in d and hybrid is not None
    two_tier = (
        "topology" in d
        and int(dcn_ways) > 1
        and n_dev > 1
        and n_dev % int(dcn_ways) == 0
    )
    budget_codec = (budget_ctx or {}).get("codec")
    budget_lb = (budget_ctx or {}).get("leaf_budgets")
    alloc = (budget_ctx or {}).get("alloc")

    hybrid_ab = None
    if have_budget and have_sparse and not error_feedback:
        if hybrid_inputs:
            from atomo_tpu.sparse.hybrid import plan_hybrid

            hybrid_ab = plan_hybrid(
                budget_codec,
                hybrid_inputs["grads_like"],
                hybrid_inputs["densities"],
                hybrid_inputs["row_bounds"],
            )
            log_fn(
                "Controller: re-planned the hybrid crossover under the "
                f"allocated codec for +sp+ab ({hybrid_ab.describe()})"
            )
        else:
            log_fn(
                "Controller: +sp+ab cross term skipped — no "
                "hybrid_inputs to re-plan the crossover under the "
                "allocated codec (the base-codec +sp and uniform +ab "
                "candidates still compete)"
            )

    extra = joint_candidates(
        deciders=d,
        allow_ring=allow_ring,
        ring_bucket_size=ring_bucket_size,
        have_budget=have_budget and not error_feedback,
        have_sparse=have_sparse,
        sparse_ab_leaf_budgets=(
            hybrid_ab.leaf_budgets() if hybrid_ab is not None else None
        ),
        allow_overlap=allow_overlap,
        allow_stream=allow_stream,
        stream_bucket_bytes=stream_bucket_bytes,
        stream_buckets=stream_buckets,
        two_tier=two_tier,
        plan_names=plan_names,
        allow_quorum=allow_quorum,
        quorum_q=quorum_q,
        quorum_staleness_options=quorum_staleness_options,
    )
    lm_axes = (
        dict(mesh_spec.model_axes)
        if mesh_spec is not None
        and any(s > 1 for _, s in mesh_spec.model_axes)
        else None
    )
    if lm_axes and not error_feedback:
        lm_rows = lm_axis_candidates(
            model_axes=lm_axes,
            codec_tag=lm_codec_tag,
            allow_ring=allow_ring,
            ring_bucket_size=ring_bucket_size,
            allow_stream=allow_stream,
            stream_bucket_bytes=stream_bucket_bytes,
            allow_overlap=allow_overlap,
            have_budget=have_budget,
            model_comm_s=lm_model_comm_s,
            pipeline_bubble_s=lm_pipeline_bubble_s,
        )
        extra = list(extra) + lm_rows
        log_fn(
            f"Controller: + {len(lm_rows)} model-axis lm candidates for "
            f"{mesh_spec.describe()} (priced, never probed — the probe "
            "harness builds replicated-family programs)"
        )
    # EF keeps the budget dial (the wrapped codec composes with residual
    # carry) but tune() narrows everything else; the joint cross terms
    # above are exactly the programs EF rejects, so they are not built
    if error_feedback and have_budget:
        log_fn(
            "Controller: --error-feedback keeps the +ab axis and drops "
            "the overlap/stream/hier/quorum cross terms (EF conflict "
            "matrix)"
        )

    def hybrid_for_candidate(cand):
        if (
            cand.get("sparse_rows") == "on"
            and cand.get("budget_alloc") == "variance"
        ):
            return hybrid_ab
        return hybrid

    meta_sections: dict = {
        "controller": {
            "deciders": sorted(d),
            "supersedes": ["tune_decision.json", "budget_alloc.json"],
            "pack_kernel": pack_kernel_record(codec),
            # the model-axis layout this decision was solved FOR (None =
            # pure data layout): report cross-checks it against the
            # run's metrics.jsonl, and the full shape also lands in
            # meta.mesh_axes via tune(mesh_spec=) for the resume refusal
            **(
                {
                    "model_axes": lm_axes,
                    "layout": mesh_spec.layout_name(),
                }
                if lm_axes
                else {}
            ),
        },
    }
    if have_budget and alloc is not None:
        meta_sections["allocation"] = {
            "epoch": int(alloc.epoch),
            "mode": alloc.mode,
            "ks": [int(k) for k in alloc.ks],
            "budget_bytes": int(alloc.budget_bytes),
            "payload_bytes": int(alloc.payload_bytes),
            "predicted_variance": float(alloc.predicted_variance),
        }
    if have_sparse:
        meta_sections["hybrid"] = {
            "assignments": [
                {
                    "index": int(a.index),
                    "name": a.name,
                    "kind": a.kind,
                    "row_budget": int(a.row_budget),
                    "dense_bytes": int(a.dense_bytes),
                    "payload_bytes": int(a.payload_bytes),
                }
                for a in hybrid.assignments
            ],
            "payload_bytes": int(hybrid.payload_bytes()),
        }
        if hybrid_ab is not None:
            meta_sections["hybrid"]["ab_assignments"] = [
                {
                    "index": int(a.index),
                    "kind": a.kind,
                    "payload_bytes": int(a.payload_bytes),
                }
                for a in hybrid_ab.assignments
            ]

    doc = tune(
        model=model,
        optimizer=optimizer,
        codec=codec,
        model_init_fn=model_init_fn,
        n_dev=n_dev,
        sample_shape=sample_shape,
        num_classes=num_classes,
        batch=batch,
        fabric=fabric,
        seed=seed,
        artifact_path=artifact_path,
        allow_ring=allow_ring and "autopilot" in d,
        allow_psum=allow_psum and "autopilot" in d,
        allow_overlap=allow_overlap and "autopilot" in d,
        allow_stream=allow_stream and "autopilot" in d,
        stream_bucket_bytes=stream_bucket_bytes,
        stream_buckets=stream_buckets,
        allow_sparse=have_sparse,
        hybrid=hybrid,
        allow_budget=have_budget,
        budget_leaf_budgets=budget_lb if have_budget else None,
        budget_codec=budget_codec if have_budget else None,
        allow_quorum=allow_quorum and "autopilot" in d,
        quorum_q=quorum_q,
        quorum_staleness_options=quorum_staleness_options,
        quorum_delays=quorum_delays,
        superstep_options=(
            superstep_options if "autopilot" in d else (1,)
        ),
        bucket_options=bucket_options,
        dcn_ways=int(dcn_ways) if two_tier else 0,
        plan_names=plan_names,
        probe_top=probe_top,
        probe_steps=probe_steps,
        probe_reps=probe_reps,
        num_aggregate=num_aggregate,
        zero1=zero1,
        partition=partition,
        grad_accum=grad_accum,
        compute_dtype=compute_dtype,
        codec_tax_s=codec_tax_s,
        ring_bucket_size=ring_bucket_size,
        context={**meta_sections, **(context or {})},
        fabric_probe=fabric_probe,
        error_feedback=error_feedback,
        extra_candidates=extra,
        candidate_filter=candidate_predicate(d),
        kind="controller_decision",
        hybrid_for_candidate=hybrid_for_candidate,
        mesh_spec=mesh_spec,
        log_fn=log_fn,
    )
    return doc
