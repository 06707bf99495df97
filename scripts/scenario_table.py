#!/usr/bin/env python
"""Generate the README's per-scenario recommended-config tables.

Model-only (comm_model.recommend_for_scenario): real byte budgets from
jax.eval_shape on the CPU backend (cheap — no training, no device work)
+ the stated anchors (utils/comm_model.py — unverified figures from
before this round, no on-chip measurement on record) scaled by gradient
size. Deterministic, so the table is reproducible by anyone:
`python scripts/scenario_table.py`. It orders candidates; it is not a
speed statement.

Usage: python scripts/scenario_table.py [--ways N] [--from-probe PATH]
"""

from __future__ import annotations

import argparse
import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

SCENARIOS = {
    # network -> (input shape, codecs to compare)
    "lenet": ((28, 28, 1), ("dense", "qsgd8", "svd3")),
    "resnet18": ((32, 32, 3), ("dense", "qsgd8", "svd3")),
}


def _budgets(network: str, shape) -> dict:
    import jax.numpy as jnp

    from atomo_tpu.codecs import QsgdCodec, SvdCodec
    from atomo_tpu.models import get_model
    from atomo_tpu.tuning.probe import byte_budget, model_init_fn

    model = get_model(network, 10)
    sample = jnp.zeros((1,) + tuple(shape), jnp.float32)
    init_fn = model_init_fn(model, sample)
    codec_objs = {
        "dense": None,
        "qsgd8": QsgdCodec(bits=8, bucket_size=512),
        "svd3": SvdCodec(rank=3),
    }
    return {
        name: byte_budget(codec_objs[name], init_fn)
        for name in SCENARIOS[network][1]
    }


def model_only_recs(ways: int, dcn_ways: int = 2,
                    allow_stream: bool = False,
                    fabric_probe: dict | None = None) -> dict:
    """{network: {fabric: recommendation}} from the stated anchors.

    Besides the three single-fabric columns, each network gets a TWO-TIER
    row (``ici:dcn 2-tier``): the topology planner's best plan per codec
    over a ``(dcn_ways x ways/dcn_ways)`` mesh
    (topology.schedule.recommend_two_tier — the same row shape, so one
    renderer serves both). Caveats, stated: the two-tier numbers use the
    SAME size-scaled single-chip anchors as the flat rows plus the
    fabric module's per-hop latency estimates; they order plans, they do
    not promise wall-clock (not measured on a chip).

    ``fabric_probe`` (``--from-probe``: a ``fabric_probe.json``
    document) replaces the preset fabric columns with the PROBED tiers
    (``measured_<label>`` columns at the measured per-chip GB/s), and
    the two-tier row prices from the probe's measured bandwidths AND
    latencies (obs.fabric.measured_two_tier) — the table then describes
    the mesh that was measured, not the mesh the presets assert."""
    from atomo_tpu.topology.fabric import resolve_two_tier
    from atomo_tpu.topology.schedule import recommend_two_tier
    from atomo_tpu.utils.comm_model import (
        FABRICS,
        estimate_codec_tax_s,
        estimate_compute_s,
        recommend_for_scenario,
    )

    fabric_cols = dict(FABRICS)
    probe_fabric2 = None
    if fabric_probe is not None:
        from atomo_tpu.obs.fabric import measured_bandwidths

        bws = measured_bandwidths(fabric_probe)
        if not bws:
            raise SystemExit(
                "--from-probe: the artifact carries no usable tier "
                "measurement"
            )
        fabric_cols = {
            f"measured_{label}": bw for label, bw in bws.items()
        }
        if (
            {"ici", "dcn"} <= set(bws)
            and 1 < dcn_ways <= ways
            and ways % dcn_ways == 0
        ):
            from atomo_tpu.obs.fabric import measured_two_tier

            probe_fabric2 = measured_two_tier(
                fabric_probe, dcn_ways=dcn_ways, n_dev=ways
            )
    recs = {}
    for net, (shape, _names) in SCENARIOS.items():
        budgets = _budgets(net, shape)
        dense_b = budgets["dense"][0]
        compute_ms = estimate_compute_s(dense_b) * 1e3
        tax_ms = estimate_codec_tax_s(dense_b) * 1e3
        measured = {
            name: compute_ms + (0.0 if name == "dense" else tax_ms)
            for name in budgets
        }
        recs[net] = {
            label: recommend_for_scenario(
                codec_budgets=budgets,
                measured_ms=measured,
                ways=ways,
                fabric_bw=bw,
                allow_stream=allow_stream,
            )
            for label, bw in sorted(fabric_cols.items())
        }
        if 1 < dcn_ways <= ways and ways % dcn_ways == 0:
            fabric2 = probe_fabric2 or resolve_two_tier(
                "auto", dcn_ways=dcn_ways, n_dev=ways
            )
            tier_label = (
                f"measured 2-tier (K={dcn_ways})" if probe_fabric2
                else f"ici:dcn 2-tier (K={dcn_ways})"
            )
            recs[net][tier_label] = recommend_two_tier(
                codec_budgets=budgets,
                measured_ms=measured,
                fabric=fabric2,
            )
    return recs


def sparse_recs(ways: int) -> dict:
    """``--sparse``: the embedding x zipf scenario rows — the flat codec
    recommendations PLUS the per-layer hybrid sparse-row candidate
    (``+sp``), priced from the real hybrid plan's per-leaf wire bytes
    (comm_model.leaf_budget_totals — the sums the executed program
    reports, tests/test_sparse.py's wire-match). Opt-in so the published
    historical table is stable by default; model-only ordering with the
    same stated anchors as the flat rows."""
    import jax.numpy as jnp

    from atomo_tpu.codecs import DenseCodec, QsgdCodec
    from atomo_tpu.data.zipf import zipf_dataset
    from atomo_tpu.models import EmbeddingTower
    from atomo_tpu.sparse import plan_for_model
    from atomo_tpu.tuning.probe import byte_budget, model_init_fn
    from atomo_tpu.utils.comm_model import (
        FABRICS,
        enumerate_candidates,
        estimate_codec_tax_s,
        estimate_compute_s,
        rank_candidates,
        recommend_for_scenario,
    )

    model = EmbeddingTower(num_classes=10)
    batch = 32
    ds = zipf_dataset(True, size=batch, seed=0)
    init_fn = model_init_fn(model, jnp.zeros((1, 8), jnp.float32))
    budgets = {
        "dense": byte_budget(None, init_fn),
        "qsgd8": byte_budget(QsgdCodec(bits=8, bucket_size=512), init_fn),
    }
    dense_b = budgets["dense"][0]
    compute_ms = estimate_compute_s(dense_b) * 1e3
    tax_ms = estimate_codec_tax_s(dense_b) * 1e3
    measured = {"dense": compute_ms, "qsgd8": compute_ms + tax_ms}
    # the hybrid plan: rows for the table, uncompressed DenseCodec
    # payloads for the tower (no codec tax — stated)
    plan = plan_for_model(
        DenseCodec(), model, ds.images, ds.labels,
        batch_per_chip=max(batch // ways, 1), slots=8,
    )
    out = {}
    for label, bw in sorted(FABRICS.items()):
        rec = recommend_for_scenario(
            codec_budgets=budgets, measured_ms=measured, ways=ways,
            fabric_bw=bw,
        )
        sp = [
            c for c in enumerate_candidates(
                has_codec=True, ways=ways, allow_overlap=False,
                allow_sparse=True,
                sparse_leaf_budgets=plan.leaf_budgets(),
            )
            if c.get("sparse_rows") == "on"
        ] if plan.any_sparse else []
        if sp:  # ways <= 1 enumerates no exchange candidates at all
            top = rank_candidates(
                sp, dense_bytes=dense_b,
                payload_bytes=plan.payload_bytes(), ways=ways,
                fabric_bw=bw, compute_s=compute_ms / 1e3, tax_s=0.0,
                # the per-leaf pairs the executed program sums — the
                # one-honest-accounting invariant, not the scalar
                # fallback that merely coincides with it today
                sparse_leaf_budgets=plan.leaf_budgets(),
            )[0]
            rec["ranked"].append({
                "code": "hybrid_rows",
                "candidate": top["name"],
                "predicted_ms_per_step": top["predicted_ms_per_step"],
                "measured_1chip_ms": None,
                "codec_tax_ms": 0.0,
            })
            rec["ranked"].sort(
                key=lambda r: (r["predicted_ms_per_step"], r["code"])
            )
            rec["winner"] = rec["ranked"][0]
        out[label] = rec
    return {"embedding(zipf)": out}


def adaptive_recs(ways: int) -> dict:
    """``--adaptive``: the lenet scenario re-ranked with the adaptive
    variance-budget candidate (``+ab``) in the space — the svd3 codec's
    per-layer allocation solved from a PROBE gradient over a fixed
    synthetic batch (deterministic: fixed keys, no data files), priced
    from the allocation's clamped per-leaf pairs
    (``budget.allocation_leaf_budgets`` — the same sums the wrapped
    codec's executed program reports, tests/test_budget.py's wire-match).
    Opt-in so the published historical table is stable; the +ab
    wire at the default budget EQUALS the uniform wire (the solver
    spends the same total), so the predicted ms/step ties the flat svd3
    candidate and the column's value is the variance split it buys."""
    import jax
    import jax.numpy as jnp

    from atomo_tpu.budget import (
        allocation_leaf_budgets,
        measure_spectra,
        solve_allocation,
    )
    from atomo_tpu.codecs import SvdCodec
    from atomo_tpu.models import get_model
    from atomo_tpu.sparse.hybrid import probe_gradient
    from atomo_tpu.utils.comm_model import (
        FABRICS,
        enumerate_candidates,
        estimate_codec_tax_s,
        estimate_compute_s,
        leaf_budget_totals,
        rank_candidates,
    )

    model = get_model("lenet", 10)
    codec = SvdCodec(rank=3)
    images = jax.random.uniform(
        jax.random.PRNGKey(0), (16, 28, 28, 1), jnp.float32
    )
    labels = jax.random.randint(jax.random.PRNGKey(1), (16,), 0, 10)
    import numpy as np

    spectra = measure_spectra(
        codec, probe_gradient(model, np.asarray(images), np.asarray(labels))
    )
    alloc = solve_allocation(codec, spectra, mode="variance")
    lb = allocation_leaf_budgets(codec, spectra, alloc.ks)
    dense_b, payload_b = leaf_budget_totals(lb)
    compute_ms = estimate_compute_s(dense_b) * 1e3
    tax_ms = estimate_codec_tax_s(dense_b) * 1e3
    out = {}
    for label, bw in sorted(FABRICS.items()):
        ab = [
            c for c in enumerate_candidates(
                has_codec=True, ways=ways, allow_overlap=False,
                allow_budget=True, budget_leaf_budgets=lb,
            )
            if c.get("budget_alloc") == "variance"
        ]
        ranked = [
            {
                "code": "svd3+ab",
                "candidate": c["name"],
                "predicted_ms_per_step": c["predicted_ms_per_step"],
                "measured_1chip_ms": None,
                "codec_tax_ms": round(tax_ms, 3),
            }
            for c in rank_candidates(
                ab, dense_bytes=dense_b, payload_bytes=payload_b,
                ways=ways, fabric_bw=bw, compute_s=compute_ms / 1e3,
                tax_s=tax_ms / 1e3, budget_leaf_budgets=lb,
            )
        ]
        out[label] = {"winner": ranked[0], "ranked": ranked}
    return {"lenet (adaptive budget)": out}


def lm_recs(ways: int, tp: int = 2) -> dict:
    """``--lm``: the model-axis LM scenario column — the dp x tp
    TransformerLM (width 32, depth 2, dp2 x tp2) with the controller's
    ``lm[tp2]+...`` candidates, priced exactly the way
    ``controller.solve`` prices them: the dp exchange over the tp-LOCAL
    gradient shard (each tp shard exchanges its own slice — the same
    per-leaf accounting tests/test_model_axes.py's byte-match pins to
    the executed program) plus the layout's pre-priced axis-collective
    floor (``comm_model.tp_psum_wire_bytes`` over the fabric). The
    candidate space includes the ``+delayed`` stale-by-one rows
    (``overlap`` column: the exchange priced as ``max(0, chain -
    compute - bubble)`` hidden behind the NEXT step's compute). Opt-in
    so the published historical table is stable; model-only ordering."""
    import jax
    import jax.numpy as jnp

    from atomo_tpu.codecs import QsgdCodec
    from atomo_tpu.controller.space import lm_axis_candidates
    from atomo_tpu.models.transformer import TransformerLM
    from atomo_tpu.parallel.tp import lm_params_to_tp, tp_param_specs
    from atomo_tpu.utils.comm_model import (
        FABRICS,
        codec_leaf_payload_bytes,
        estimate_codec_tax_s,
        estimate_compute_s,
        rank_candidates,
        tp_psum_wire_bytes,
    )

    cfg = dict(vocab_size=64, max_len=16, width=32, depth=2, num_heads=4)
    batch, seq = 8, cfg["max_len"]
    model = TransformerLM(**cfg)
    lm_shapes = jax.eval_shape(
        lambda: model.init(
            jax.random.PRNGKey(0), jnp.zeros((1, seq), jnp.int32)
        )["params"]
    )
    # the tp re-layout + its shard slicing, abstractly (eval_shape):
    # local leaf shapes are what the dp exchange actually encodes
    tp_shapes = jax.eval_shape(
        lambda p: lm_params_to_tp(p, cfg["num_heads"]), lm_shapes
    )
    specs = tp_param_specs(tp_shapes, "tp")

    def local(shape, spec):
        return tuple(
            d // tp if i < len(spec) and spec[i] == "tp" else d
            for i, d in enumerate(shape)
        )

    leaves = [
        local(l.shape, s)
        for l, s in zip(
            jax.tree_util.tree_leaves(tp_shapes),
            jax.tree_util.tree_leaves(
                specs, is_leaf=lambda x: not isinstance(x, (dict, list))
            ),
        )
    ]
    codec = QsgdCodec(bits=8, bucket_size=512)
    dense_b = float(sum(4 * int(jnp.prod(jnp.array(s))) for s in leaves))
    payload_b = float(
        sum(codec_leaf_payload_bytes(codec, s) for s in leaves)
    )
    compute_ms = estimate_compute_s(dense_b) * 1e3
    tax_ms = estimate_codec_tax_s(dense_b) * 1e3
    act_bytes = 4.0 * batch * seq * cfg["width"]
    n_dp = max(ways // tp, 1)
    out = {}
    for label, bw in sorted(FABRICS.items()):
        cands = lm_axis_candidates(
            model_axes={"tp": tp}, codec_tag="qsgd8",
            model_comm_s=tp_psum_wire_bytes(act_bytes, tp, cfg["depth"])
            / bw,
        )
        ranked = [
            {
                "code": "qsgd8",
                "candidate": c["name"],
                "overlap": c.get("overlap", "off"),
                "predicted_ms_per_step": c["predicted_ms_per_step"],
                "measured_1chip_ms": None,
                "codec_tax_ms": round(tax_ms, 3),
            }
            for c in rank_candidates(
                cands, dense_bytes=dense_b, payload_bytes=payload_b,
                ways=n_dp, fabric_bw=bw, compute_s=compute_ms / 1e3,
                tax_s=tax_ms / 1e3,
            )
        ]
        out[label] = {"winner": ranked[0], "ranked": ranked}
    return {f"lm dp{n_dp}xtp{tp}": out}


def render(recs: dict, ways: int, source: str) -> str:
    lines = [
        f"| scenario | fabric | recommended config | predicted ms/step "
        f"| runner-up |",
        "|---|---|---|---|---|",
    ]
    for net in sorted(recs):
        for fabric in sorted(recs[net]):
            r = recs[net][fabric]
            w = r["winner"]
            runner = next(
                (x for x in r["ranked"]
                 if (x["code"], x["candidate"])
                 != (w["code"], w["candidate"])),
                None,
            )
            runner_s = (
                f"`{runner['code']}` {runner['candidate']} "
                f"({runner['predicted_ms_per_step']})"
                if runner else "—"
            )
            lines.append(
                f"| {net} x {ways} ways | {fabric} | `{w['code']}` "
                f"{w['candidate']} | {w['predicted_ms_per_step']} | "
                f"{runner_s} |"
            )
    lines.append("")
    lines.append(f"<!-- generated by scripts/scenario_table.py ({source}) -->")
    return "\n".join(lines)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--ways", type=int, default=8,
                    help="modeled mesh width for the fabric term")
    ap.add_argument("--dcn-ways", type=int, default=2,
                    help="slow-fabric groups for the two-tier column "
                         "(0 disables it; must divide --ways)")
    ap.add_argument("--stream", action="store_true", default=False,
                    help="include --stream-encode on (+se) candidates in "
                         "the model-only recommendation space: encode's "
                         "predicted exposure drops to its pipeline tail "
                         "(comm_model.stream_exposed_encode_s). Off by "
                         "default so the published table's historical "
                         "candidate space is stable")
    ap.add_argument("--adaptive", action="store_true", default=False,
                    help="add the lenet scenario re-ranked with the "
                         "adaptive variance-budget (+ab) candidates, "
                         "priced from a real allocation's clamped "
                         "per-leaf wire bytes. Off by default so the "
                         "published table's historical rows are stable")
    ap.add_argument("--sparse", action="store_true", default=False,
                    help="add the embedding x zipf scenario with the "
                         "per-layer hybrid sparse-row (+sp) candidate, "
                         "priced from the real plan's per-leaf wire "
                         "bytes. Off by default so the published table's "
                         "historical rows are stable")
    ap.add_argument("--lm", action="store_true", default=False,
                    help="add the model-axis LM scenario (dp x tp2 "
                         "TransformerLM) with the controller's lm[tp2] "
                         "candidates — +delayed stale-by-one rows "
                         "included — priced over the tp-LOCAL gradient "
                         "shard + the tp psum floor. Off by default so "
                         "the published table's historical rows are "
                         "stable")
    ap.add_argument("--from-probe", type=str, default="",
                    help="price the fabric columns from a "
                         "fabric_probe.json artifact (--fabric measured "
                         "runs write one): measured_<tier> columns at "
                         "the probed per-chip GB/s, and the two-tier "
                         "row from the probed bandwidths AND latencies")
    args = ap.parse_args()
    fabric_probe = None
    if args.from_probe:
        with open(args.from_probe) as f:
            fabric_probe = json.load(f)
    recs = model_only_recs(args.ways, dcn_ways=args.dcn_ways,
                           allow_stream=args.stream,
                           fabric_probe=fabric_probe)
    if args.sparse:
        recs.update(sparse_recs(args.ways))
    if args.adaptive:
        recs.update(adaptive_recs(args.ways))
    if args.lm:
        recs.update(lm_recs(args.ways))
    source = (
        f"measured fabric, {args.from_probe} (compute/tax anchors stay "
        "the stated model-only estimates)"
        if fabric_probe is not None
        else "model-only anchors, unverified figures from before this round; "
             "2-tier rows: topology planner over the same anchors + "
             "stated latency estimates — ordering only, not measured "
             "on a chip"
    )
    print(render(recs, args.ways, source))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
