"""Topology subsystem (PR-8 tentpole): two-tier fabric model, plan-space
scheduling, boundary re-encoding, and planned-schedule execution.

Contracts being pinned:

  * The LEGACY hierarchical plan (psum+gather) is bit-identical to the
    pre-topology ``--aggregate hierarchical`` program — the plan space
    contains today's program as one point.
  * Every planned schedule's aggregation OPERATOR is bit-identical to
    the canonical unfused decode-order oracle in SPMD form
    (topology.execute.two_level_canonical_mean — gather + fused=False at
    every compressed tier; the PR-3 ring-vs-gather precedent, per tier).
  * The boundary RE-ENCODE (fresh outer-keyed draw over the inner
    estimate) is unbiased by composition: a Monte-Carlo expectation test
    per compressing codec shows the two-level mean estimates the true
    global mean.
  * The planner is a pure deterministic function of (bytes, fabric);
    the fabric parser extends resolve_fabric's one-parser grammar; the
    autopilot's candidate space gains hierarchical plans exactly on
    multi-tier meshes.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from atomo_tpu.codecs import DenseCodec, QsgdCodec, SvdCodec
from atomo_tpu.parallel.mesh import make_mesh
from atomo_tpu.topology import (
    LEGACY_PLAN,
    PLAN_NAMES,
    AggregationPlan,
    TwoTierFabric,
    choose_plan,
    enumerate_plans,
    plan_from_name,
    plan_wire_bytes,
    planned_two_level_mean,
    predict_plan_step_s,
    resolve_two_tier,
    two_level_mean_host,
)
from atomo_tpu.topology.execute import inner_codec_key, outer_codec_key
from atomo_tpu.topology.schedule import dense_outer_wins
from atomo_tpu.utils.comm_model import (
    candidate_name,
    enumerate_candidates,
    predict_step_s,
    rank_candidates,
)

CODECS = {
    "qsgd": QsgdCodec(bits=2, bucket_size=128),
    "svd": SvdCodec(rank=2),
}


def _leaves_equal(a, b):
    return all(
        np.array_equal(np.asarray(x), np.asarray(y))
        for x, y in zip(jax.tree_util.tree_leaves(a),
                        jax.tree_util.tree_leaves(b))
    )


# ------------------------------------------------- fabric + plan grammar


def test_plan_space_and_names():
    assert LEGACY_PLAN == AggregationPlan("psum", "gather")
    assert LEGACY_PLAN.is_legacy and LEGACY_PLAN.reencodes
    assert plan_from_name("legacy") == LEGACY_PLAN
    for name in PLAN_NAMES:
        assert plan_from_name(name).name == name
    assert not plan_from_name("cring+psum").reencodes  # dense outer
    with pytest.raises(ValueError, match="psum\\+psum"):
        AggregationPlan("psum", "psum")
    with pytest.raises(ValueError, match="unknown plan"):
        plan_from_name("garbage")
    with pytest.raises(ValueError, match="inner"):
        AggregationPlan("mystery", "gather")
    assert [p.name for p in enumerate_plans()] == list(PLAN_NAMES)
    assert [p.name for p in enumerate_plans(["cring+ring"])] == ["cring+ring"]


def test_resolve_two_tier_parsing():
    """Every tier token rides resolve_fabric's grammar; auto = ici inner
    + dcn outer; a single token names the OUTER (slowest-link) tier."""
    f = resolve_two_tier("auto", dcn_ways=2, n_dev=8)
    assert (f.inner_bw, f.outer_bw) == (45e9, 6.25e9)
    assert (f.inner_ways, f.outer_ways) == (4, 2)
    f = resolve_two_tier("eth10g", dcn_ways=4, n_dev=8)
    assert (f.inner_bw, f.outer_bw) == (45e9, 1.25e9)
    f = resolve_two_tier("45:1.25", dcn_ways=2, n_dev=4)
    assert (f.inner_bw, f.outer_bw) == (45e9, 1.25e9)
    assert "45.00 GB/s" in f.describe() and "outer 2x" in f.describe()
    with pytest.raises(ValueError, match="fabric"):
        resolve_two_tier("warp-drive", dcn_ways=2, n_dev=4)
    with pytest.raises(ValueError, match="fabric"):
        resolve_two_tier("ici:", dcn_ways=2, n_dev=4)
    with pytest.raises(ValueError, match="dcn_ways"):
        resolve_two_tier("auto", dcn_ways=3, n_dev=8)  # does not divide
    with pytest.raises(ValueError, match="dcn_ways"):
        resolve_two_tier("auto", dcn_ways=1, n_dev=8)
    # latency floor is charged per hop
    assert f.tier_time_s(0, "outer", 3) == pytest.approx(
        3 * f.outer_latency_s
    )


def test_planner_deterministic_and_per_tier():
    """choose_plan is pure (same inputs -> same plan) and its reason line
    quotes BOTH tiers' bytes/bandwidth — the advisory a blended scalar
    cannot state."""
    fab = resolve_two_tier("auto", dcn_ways=2, n_dev=8)
    a = choose_plan(dense_bytes=44.7e6, payload_bytes=0.6e6, fabric=fab)
    b = choose_plan(dense_bytes=44.7e6, payload_bytes=0.6e6, fabric=fab)
    assert a == b
    plan, why = a
    assert plan.name in PLAN_NAMES
    assert "inner tier" in why and "outer tier" in why
    assert fab.inner_label in why and fab.outer_label in why
    # every plan is priced; ordering respects the per-tier model
    costs = {
        p.name: predict_plan_step_s(
            p, dense_bytes=44.7e6, payload_bytes=0.6e6, fabric=fab
        )
        for p in enumerate_plans()
    }
    assert costs[plan.name] == min(costs.values())


def test_density_switch_picks_dense_outer():
    """SparCML representation switching: once the boundary payload has
    outgrown the dense crossover at K outer ways, the planner's pick
    ships the slow tier DENSE (an outer-psum plan)."""
    fab = resolve_two_tier("auto", dcn_ways=2, n_dev=8)
    assert dense_outer_wins(5e6, 1e6, 2)
    assert not dense_outer_wins(0.1e6, 44.7e6, 2)
    plan, why = choose_plan(
        dense_bytes=1e6, payload_bytes=5e6, fabric=fab
    )
    assert plan.outer == "psum"
    assert "representation switch" in why
    # per-tier wire accounting matches the comm-model formulas
    w = plan_wire_bytes(
        plan, dense_bytes=1e6, payload_bytes=5e6, fabric=fab
    )
    assert w["outer_bytes"] == 2.0 * 1e6 * (2 - 1) / 2


def test_enumerate_candidates_gains_plans_on_multitier():
    """The autopilot exclusion lift: dcn_ways>1 adds one hierarchical
    candidate per plan; flat meshes and dense codes are unchanged."""
    flat = enumerate_candidates(has_codec=True, ways=8)
    assert not any(c.get("aggregate") == "hierarchical" for c in flat)
    two = enumerate_candidates(
        has_codec=True, ways=8, dcn_ways=2, superstep_options=(1,)
    )
    hier = [c for c in two if c.get("aggregate") == "hierarchical"]
    assert [c["plan"] for c in hier] == list(PLAN_NAMES)
    assert all(c["overlap"] == "off" for c in hier)
    assert hier[0]["name"] == "hier[psum+gather]+off+k1"
    assert candidate_name(hier[0]) == hier[0]["name"]
    # flat candidates unchanged by the extension
    assert [c for c in two if c.get("aggregate") != "hierarchical"] == [
        c for c in enumerate_candidates(
            has_codec=True, ways=8, superstep_options=(1,)
        )
    ]
    # dense code / non-dividing ways / flat: no plans
    assert not any(
        c.get("aggregate") == "hierarchical"
        for c in enumerate_candidates(has_codec=False, ways=8, dcn_ways=2)
    )
    assert not any(
        c.get("aggregate") == "hierarchical"
        for c in enumerate_candidates(has_codec=True, ways=8, dcn_ways=3)
    )
    # plan_names narrows the space
    only = enumerate_candidates(
        has_codec=True, ways=8, dcn_ways=2, superstep_options=(1,),
        plan_names=("cring+ring",),
    )
    assert [c["plan"] for c in only if "plan" in c] == ["cring+ring"]


def test_predict_hierarchical_needs_fabric2_and_ranks():
    cand = {"aggregate": "hierarchical", "plan": "psum+gather",
            "superstep": 1, "name": "hier[psum+gather]+off+k1"}
    with pytest.raises(ValueError, match="fabric2"):
        predict_step_s(
            cand, dense_bytes=1e6, payload_bytes=1e5, ways=8,
            fabric_bw=6.25e9,
        )
    fab = resolve_two_tier("auto", dcn_ways=2, n_dev=8)
    cands = enumerate_candidates(
        has_codec=True, ways=8, dcn_ways=2, superstep_options=(1,)
    )
    ranked = rank_candidates(
        cands, dense_bytes=44.7e6, payload_bytes=0.6e6, ways=8,
        fabric_bw=fab.outer_bw, fabric2=fab,
    )
    assert len(ranked) == len(cands)
    assert all("predicted_ms_per_step" in r for r in ranked)
    # deterministic: same call, same order
    again = rank_candidates(
        cands, dense_bytes=44.7e6, payload_bytes=0.6e6, ways=8,
        fabric_bw=fab.outer_bw, fabric2=fab,
    )
    assert [r["name"] for r in ranked] == [r["name"] for r in again]


# ---------------------------------------- operator bit-parity per plan


def _fake_grads(c, key):
    kr = jax.random.fold_in(key, c)
    return {
        "conv": jax.random.normal(jax.random.fold_in(kr, 0), (5, 5, 1, 8)),
        "bias": jax.random.normal(jax.random.fold_in(kr, 1), (8,)),
        "fc": jax.random.normal(jax.random.fold_in(kr, 2), (33, 17)),
    }


def _plan_parity(codec, pname, n_outer=2, n_inner=2, bucket_size=256):
    """The executed two-level operator (planned_two_level_mean, outer
    gather forced to the canonical unfused decode order) against the
    canonical decode-order oracle in SPMD form (two_level_canonical_mean),
    SPMD program against SPMD program, over the same per-chip gradients
    and keys: True when every leaf is bit-identical."""
    from jax.sharding import PartitionSpec as P

    from atomo_tpu.topology.execute import two_level_canonical_mean

    mesh = make_mesh(
        n_outer * n_inner, axes=(("dcn", n_outer), ("ici", n_inner))
    )
    axis, inner_axis = mesh.axis_names
    plan = plan_from_name(pname)
    key = jax.random.PRNGKey(3)
    step_key = jax.random.PRNGKey(11)
    grads_by_chip = [
        jax.device_get(_fake_grads(c, key)) for c in range(n_outer * n_inner)
    ]

    def make_fn(canonical):
        def fn(x):
            o = jax.lax.axis_index(axis)
            my = o * n_inner + jax.lax.axis_index(inner_axis)
            grads = jax.lax.switch(
                my, [lambda c=c: grads_by_chip[c]
                     for c in range(len(grads_by_chip))],
            )
            ki = inner_codec_key(step_key, my)
            ko = outer_codec_key(step_key, o)
            kw = dict(axis=axis, inner_axis=inner_axis,
                      n_inner=n_inner, n_outer=n_outer)
            if canonical:
                return two_level_canonical_mean(
                    codec, plan, grads, ki, ko, **kw
                )
            mean, _, _, _ = planned_two_level_mean(
                codec, plan, grads, ki, ko, **kw,
                ring_bucket_size=bucket_size, unfused_decode=True,
            )
            return mean

        return fn

    def run(fn):
        return jax.jit(jax.shard_map(
            fn, mesh=mesh, in_specs=(P((axis, inner_axis)),), out_specs=P(),
            check_vma=False,
        ))(jnp.zeros((n_outer * n_inner,)))

    return _leaves_equal(
        jax.device_get(run(make_fn(False))), jax.device_get(run(make_fn(True)))
    )


# tier-1 keeps the uint32-packed family across the whole plan space and
# the factor family on the re-encoding plans; the remaining combinations
# ride the slow lane (each parametrization is two small 4-device
# compiles)
@pytest.mark.parametrize(
    "cname,pname",
    [("qsgd", p) for p in PLAN_NAMES]
    + [("svd", "psum+ring")]
    + [
        pytest.param("svd", p, marks=pytest.mark.slow)
        for p in ("cring+gather", "psum+gather", "cring+ring", "cring+psum")
    ],
)
def test_planned_operator_bit_identical_to_canonical(cname, pname):
    """The tentpole contract, per plan: the executed two-level operator
    computes the EXACT bits of the canonical unfused decode-order oracle
    (SPMD form) over the same per-chip gradients and keys."""
    assert _plan_parity(CODECS[cname], pname), (
        f"{cname}/{pname}: planned operator diverged from canonical"
    )


# ------------------------------------------- boundary-re-encode math


@pytest.mark.parametrize("cname", ["svd", "qsgd"])
def test_boundary_reencode_unbiased_monte_carlo(cname):
    """E over key draws of the re-encoded two-level mean == the true
    global mean (composition of unbiased estimators with independent
    inner/outer streams). The MC average over hundreds of draws must
    shrink the single-draw error by well over the ~sqrt(K) the CLT
    promises for an unbiased estimator — a biased boundary would leave a
    floor the averaging cannot remove."""
    codec = CODECS[cname]
    n_outer = n_inner = 2
    gkey = jax.random.PRNGKey(0)
    grads_by_chip = [
        {"m": jax.random.normal(jax.random.fold_in(gkey, c), (8, 6))}
        for c in range(n_outer * n_inner)
    ]
    true_mean = np.mean(
        [np.asarray(g["m"]) for g in grads_by_chip], axis=0
    )
    plan = plan_from_name("cring+ring")  # both stages compress

    def estimate(step_key):
        return two_level_mean_host(
            codec, plan, grads_by_chip, step_key,
            n_outer=n_outer, n_inner=n_inner,
        )["m"]

    keys = jax.random.split(jax.random.PRNGKey(42), 512)
    draws = jax.vmap(estimate)(keys)
    est = np.mean(np.asarray(draws), axis=0)
    err_single = float(np.max(np.abs(np.asarray(draws[0]) - true_mean)))
    err_mc = float(np.max(np.abs(est - true_mean)))
    scale = float(np.max(np.abs(true_mean)))
    # the MC mean must approach the true mean (no bias floor) and beat
    # the single draw decisively
    assert err_mc < 0.12 * scale, (err_mc, scale)
    assert err_mc < 0.35 * max(err_single, 1e-9), (err_mc, err_single)


# ------------------------------------ legacy bit-identity + full steps


def _hier_setup(n_outer=2, n_inner=2, batch=8):
    from atomo_tpu.models import get_model
    from atomo_tpu.parallel.replicated import replicate_state, shard_batch
    from atomo_tpu.training import create_state, make_optimizer

    mesh = make_mesh(
        n_outer * n_inner, axes=(("dp", n_outer), ("ici", n_inner))
    )
    model = get_model("lenet", 10)
    opt = make_optimizer("sgd", lr=0.05, momentum=0.9)
    images = jax.random.normal(jax.random.PRNGKey(1), (batch, 28, 28, 1))
    labels = jax.random.randint(jax.random.PRNGKey(2), (batch,), 0, 10)
    state0 = create_state(model, opt, jax.random.PRNGKey(0), images)
    si, sl = shard_batch(mesh, images, labels, axis=("dp", "ici"))
    return mesh, model, opt, state0, si, sl


def _run_hier(mesh, model, opt, state0, si, sl, nsteps=2, **kw):
    from atomo_tpu.parallel.replicated import (
        make_distributed_train_step,
        replicate_state,
    )

    st = replicate_state(mesh, jax.tree_util.tree_map(jnp.array, state0))
    step = make_distributed_train_step(
        model, opt, mesh, aggregate="hierarchical", inner_axis="ici", **kw
    )
    m = None
    for _ in range(nsteps):
        st, m = step(st, jax.random.PRNGKey(5), si, sl)
    return st, jax.device_get(m)


@pytest.mark.slow  # ~8 s of hierarchical compiles on 1 core — full-suite
# only; the legacy pin is a frozen contract, not an active code path
def test_legacy_plan_bit_identical_to_pre_topology_program():
    """plan=LEGACY_PLAN routes through the frozen inline path: the
    trajectory is bit-for-bit the plan=None (pre-topology) one."""
    setup = _hier_setup()
    codec = QsgdCodec(bits=2, bucket_size=128)
    a, ma = _run_hier(*setup, codec=codec)
    b, mb = _run_hier(*setup, codec=codec, plan=LEGACY_PLAN)
    assert _leaves_equal(a.params, b.params)
    assert _leaves_equal(a.opt_state, b.opt_state)
    assert float(ma["msg_bytes"]) == float(mb["msg_bytes"])


def test_planned_step_trains_and_replicas_identical():
    """A non-legacy plan (cring+ring: both tiers compressed, boundary
    re-encode in between) drives a real train step: finite loss, slow-
    fabric msg_bytes below dense, and the replicated-PS invariant holds
    bit-level across all four chips."""
    setup = _hier_setup()
    codec = QsgdCodec(bits=2, bucket_size=128)
    st, m = _run_hier(
        *setup, codec=codec, plan=plan_from_name("cring+ring")
    )
    assert np.isfinite(float(m["loss"]))
    assert float(m["msg_bytes"]) < float(m["dense_bytes"])
    for leaf in jax.tree_util.tree_leaves(st.params):
        shards = [np.asarray(s.data) for s in leaf.addressable_shards]
        for s in shards[1:]:
            np.testing.assert_array_equal(shards[0], s)


def test_plan_requires_hierarchical_aggregate():
    from atomo_tpu.models import get_model
    from atomo_tpu.parallel.replicated import make_distributed_train_step
    from atomo_tpu.training import make_optimizer

    mesh = make_mesh(4)
    with pytest.raises(ValueError, match="hierarchical"):
        make_distributed_train_step(
            get_model("lenet", 10), make_optimizer("sgd", lr=0.1), mesh,
            SvdCodec(rank=2), aggregate="gather",
            plan=plan_from_name("cring+ring"),
        )


@pytest.mark.slow
def test_planned_dense_outer_equals_flat_mean_for_dense_codec():
    """Sanity telescope: with the identity codec, the cring+psum plan
    (identity 'compression' inner ring, dense outer) must equal the flat
    global pmean to float tolerance — the schedule changes the route,
    not the estimator."""
    from atomo_tpu.models import get_model
    from atomo_tpu.parallel.replicated import (
        make_distributed_train_step,
        replicate_state,
        shard_batch,
    )
    from atomo_tpu.training import create_state, make_optimizer

    mesh4 = make_mesh(4)
    model = get_model("lenet", 10)
    opt = make_optimizer("sgd", lr=0.05, momentum=0.9)
    images = jax.random.normal(jax.random.PRNGKey(1), (8, 28, 28, 1))
    labels = jax.random.randint(jax.random.PRNGKey(2), (8,), 0, 10)
    state0 = create_state(model, opt, jax.random.PRNGKey(0), images)

    flat = replicate_state(mesh4, jax.tree_util.tree_map(jnp.array, state0))
    fstep = make_distributed_train_step(model, opt, mesh4, None)
    fsi, fsl = shard_batch(mesh4, images, labels)
    flat, _ = fstep(flat, jax.random.PRNGKey(9), fsi, fsl)

    setup = _hier_setup()
    h, _ = _run_hier(
        *setup[:4], *setup[4:], nsteps=1, codec=DenseCodec(),
        plan=plan_from_name("cring+psum"),
    )
    for a, b in zip(jax.tree_util.tree_leaves(jax.device_get(flat).params),
                    jax.tree_util.tree_leaves(h.params)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-5
        )


@pytest.mark.slow
def test_planned_guard_masks_poisoned_group():
    """Guard composition on a planned schedule: a NaN confined to chip 0
    poisons exactly its inner GROUP (the drop unit), the surviving group
    carries the step (dropped=1, skipped=0), and params stay finite."""
    from atomo_tpu.training.resilience import GuardConfig
    from atomo_tpu.utils.chaos import ChaosConfig, ChaosInjector

    setup = _hier_setup()
    codec = QsgdCodec(bits=2, bucket_size=128)
    st, m = _run_hier(
        *setup, nsteps=1, codec=codec,
        plan=plan_from_name("psum+ring"),
        guard=GuardConfig(),
        chaos=ChaosInjector(ChaosConfig.from_spec("nan@1")),
    )
    assert float(m["dropped"]) == 1.0 and float(m["skipped"]) == 0.0
    assert np.isfinite(float(m["loss"]))
    for leaf in jax.tree_util.tree_leaves(st.params):
        assert np.all(np.isfinite(np.asarray(leaf)))


@pytest.mark.slow
def test_planned_composes_with_superstep_and_zero1():
    """cring+gather under a K=2 superstep scan with ZeRO-1 sharded
    optimizer state: the composition surface the plan space inherits
    from the legacy hierarchical path."""
    from atomo_tpu.models import get_model
    from atomo_tpu.parallel.replicated import (
        make_distributed_train_step,
        shard_superbatch,
        zero1_state,
    )
    from atomo_tpu.training import create_state, make_optimizer

    mesh = make_mesh(4, axes=(("dp", 2), ("ici", 2)))
    model = get_model("lenet", 10)
    opt = make_optimizer("sgd", lr=0.05, momentum=0.9)
    images = jax.random.normal(jax.random.PRNGKey(1), (8, 28, 28, 1))
    labels = jax.random.randint(jax.random.PRNGKey(2), (8,), 0, 10)
    state0 = create_state(model, opt, jax.random.PRNGKey(0), images)
    z_state, specs = zero1_state(mesh, state0, opt, axis=("dp", "ici"))
    step = make_distributed_train_step(
        model, opt, mesh, QsgdCodec(bits=2, bucket_size=128),
        aggregate="hierarchical", inner_axis="ici",
        plan=plan_from_name("cring+gather"),
        zero1_specs=specs, superstep=2,
    )
    im = jnp.stack([images, images])
    lb = jnp.stack([labels, labels])
    si, sl = shard_superbatch(mesh, im, lb, axis=("dp", "ici"))
    st, m = step(z_state, jax.random.PRNGKey(5), si, sl)
    assert np.all(np.isfinite(np.asarray(m["loss"])))
    leaf = jax.tree_util.tree_leaves(st.params)[0]
    shards = [np.asarray(s.data) for s in leaf.addressable_shards]
    for s in shards[1:]:
        np.testing.assert_array_equal(shards[0], s)


# ------------------------------------------------ probe + tune + CLI


def test_probe_candidate_runs_hierarchical_plan():
    """The shared probe runner builds the REAL two-tier step for a
    hierarchical candidate and returns a fenced measurement plus the
    program's own byte accounting."""
    from atomo_tpu.models import get_model
    from atomo_tpu.training import make_optimizer
    from atomo_tpu.tuning.probe import (
        byte_budget,
        model_init_fn,
        probe_candidate,
    )

    model = get_model("lenet", 10)
    codec = QsgdCodec(bits=8, bucket_size=512)
    row = probe_candidate(
        {"aggregate": "hierarchical", "plan": "psum+ring",
         "overlap": "off", "superstep": 1, "name": "hier[psum+ring]"},
        model=model,
        optimizer=make_optimizer("sgd", lr=0.01, momentum=0.9),
        codec=codec,
        n_dev=4, sample_shape=(28, 28, 1), num_classes=10, batch=8,
        steps=2, reps=1, dcn_ways=2,
    )
    assert row["probed"] and row["sync_ok"]
    assert row["measured_ms_per_step"] > 0
    assert 0 < row["measured_msg_bytes"] < row["measured_dense_bytes"]
    # the bytes the comm model prices each tier from (plan_wire_bytes over
    # the eval_shape budget) are the executed program's own: the dense
    # gradient on the inner psum, the encoded payload on the outer ring
    dense_b, payload_b = byte_budget(
        codec, model_init_fn(model, jnp.zeros((1, 28, 28, 1)))
    )
    assert row["measured_dense_bytes"] == dense_b
    assert row["measured_msg_bytes"] == payload_b
    with pytest.raises(ValueError, match="dcn_ways"):
        probe_candidate(
            {"aggregate": "hierarchical", "plan": "psum+ring",
             "superstep": 1, "name": "x"},
            model=get_model("lenet", 10),
            optimizer=make_optimizer("sgd", lr=0.01),
            codec=QsgdCodec(bits=8, bucket_size=512),
            n_dev=4, sample_shape=(28, 28, 1), num_classes=10, batch=8,
            dcn_ways=3,
        )


@pytest.mark.slow
def test_tune_records_hierarchical_plan_in_decision(tmp_path):
    """The lifted exclusion end to end: tune() on a dcn_ways=2 mesh with
    a bandwidth-starved outer tier probes hierarchical candidates and the
    decision artifact's winner carries its plan knob."""
    import json

    from atomo_tpu.models import get_model
    from atomo_tpu.training import make_optimizer
    from atomo_tpu.tuning.autopilot import tune
    from atomo_tpu.tuning.probe import model_init_fn

    model = get_model("lenet", 10)
    sample = jnp.zeros((1, 28, 28, 1), jnp.float32)
    path = str(tmp_path / "decision.json")
    doc = tune(
        model=model,
        optimizer=make_optimizer("sgd", lr=0.01, momentum=0.9),
        codec=QsgdCodec(bits=8, bucket_size=512),
        model_init_fn=model_init_fn(model, sample),
        n_dev=4, sample_shape=(28, 28, 1), num_classes=10, batch=8,
        fabric="ici:0.05", dcn_ways=2,
        plan_names=("psum+gather", "cring+ring"),
        allow_psum=False, allow_overlap=False, allow_ring=False,
        superstep_options=(1,), probe_top=2, probe_steps=2, probe_reps=1,
        artifact_path=path, log_fn=lambda *_: None,
    )
    hier_probed = [
        r for r in doc["rows"]
        if r.get("probed") and r.get("aggregate") == "hierarchical"
    ]
    assert hier_probed, doc["rows"]
    assert doc["meta"]["dcn_ways"] == 2
    assert "0.05" in doc["meta"]["two_tier_fabric"]
    win = doc["winner"]["knobs"]
    if win.get("aggregate") == "hierarchical":
        assert win.get("plan") in ("psum+gather", "cring+ring")
    on_disk = json.load(open(path))
    assert on_disk["winner"] == doc["winner"]


def test_tune_flat_space_accepts_two_tier_fabric_string(tmp_path):
    """A two-tier <inner>:<outer> --fabric string must not abort a tune
    whose candidate space ended up flat (densify/num-aggregate exclusions
    zero dcn_ways): flat candidates are priced at the OUTER token, out
    loud, instead of dying on the single-scalar usage line."""
    from atomo_tpu.models import get_model
    from atomo_tpu.training import make_optimizer
    from atomo_tpu.tuning.autopilot import tune
    from atomo_tpu.tuning.probe import model_init_fn

    model = get_model("lenet", 10)
    sample = jnp.zeros((1, 28, 28, 1), jnp.float32)
    logs = []
    doc = tune(
        model=model,
        optimizer=make_optimizer("sgd", lr=0.01, momentum=0.9),
        codec=QsgdCodec(bits=8, bucket_size=512),
        model_init_fn=model_init_fn(model, sample),
        n_dev=1, sample_shape=(28, 28, 1), num_classes=10, batch=8,
        fabric="ici:0.05", dcn_ways=0,
        superstep_options=(1,), probe_top=1, probe_steps=1, probe_reps=1,
        log_fn=logs.append,
    )
    assert doc["complete"] and doc["winner"] is not None
    assert any("outer tier" in str(line) for line in logs)
    # a garbage OUTER token still fails with the fabric usage line
    with pytest.raises(ValueError, match="fabric"):
        tune(
            model=model,
            optimizer=make_optimizer("sgd", lr=0.01, momentum=0.9),
            codec=QsgdCodec(bits=8, bucket_size=512),
            model_init_fn=model_init_fn(model, sample),
            n_dev=1, sample_shape=(28, 28, 1), num_classes=10, batch=8,
            fabric="ici:warp", dcn_ways=0,
            superstep_options=(1,), probe_top=1, probe_steps=1,
            probe_reps=1, log_fn=lambda *_: None,
        )


def test_cli_plan_flag_validation():
    from atomo_tpu.cli import main

    base = ["train", "--network", "LeNet", "--synthetic", "--n-devices",
            "4", "--max-steps", "1", "--code", "svd"]
    with pytest.raises(SystemExit, match="unknown"):
        main(base + ["--aggregate", "hierarchical", "--dcn-ways", "2",
                     "--plan", "warp+drive"])
    with pytest.raises(SystemExit, match="hierarchical"):
        main(base + ["--aggregate", "gather", "--plan", "cring+ring"])
    with pytest.raises(SystemExit, match="pinned"):
        main(base + ["--auto", "tune", "--train-dir", "/tmp/x",
                     "--plan", "cring+ring"])
    with pytest.raises(SystemExit, match="delayed"):
        main(base + ["--overlap", "delayed", "--plan", "cring+ring"])
    # a pinned plan must never be silently dropped: dense code means
    # --aggregate auto can never resolve hierarchical, so the run
    # refuses with the reason instead of training a flat exchange
    with pytest.raises(SystemExit, match="resolved to"):
        main([
            "train", "--network", "LeNet", "--synthetic", "--n-devices",
            "4", "--max-steps", "1", "--code", "sgd",
            "--plan", "cring+ring",
        ])


@pytest.mark.slow
def test_cli_planned_hierarchical_end_to_end(capsys, tmp_path):
    """--aggregate hierarchical --plan cring+ring drives a planned
    schedule from the train subcommand on the forced (2x2) mesh."""
    from atomo_tpu.cli import main

    rc = main([
        "train", "--network", "LeNet", "--dataset", "MNIST", "--synthetic",
        "--train-dir", str(tmp_path), "--batch-size", "8",
        "--max-steps", "2", "--log-interval", "2", "--eval-freq", "0",
        "--n-devices", "4", "--momentum", "0.0", "--code", "qsgd",
        "--quantization-level", "8", "--aggregate", "hierarchical",
        "--dcn-ways", "2", "--plan", "cring+ring",
    ])
    assert rc == 0
    out = capsys.readouterr().out
    assert "Topology plan: cring+ring" in out
    assert "Worker: 0, Step: 2" in out


def test_cli_auto_aggregate_two_tier_advisory(capsys):
    """Satellite 1: on a --dcn-ways mesh the advisory quotes PER-TIER
    numbers (both fabrics by name and bandwidth) and names the planned
    schedule — not one blended bandwidth."""
    import argparse

    from atomo_tpu.cli import _resolve_auto_aggregate
    from atomo_tpu.models import get_model
    from atomo_tpu.tuning.probe import model_init_fn

    args = argparse.Namespace(
        fabric="auto", codec_tax_ms=None, dcn_ways=2
    )
    model = get_model("lenet", 10)
    init = model_init_fn(model, jnp.zeros((1, 28, 28, 1), jnp.float32))
    lines = []
    mode = _resolve_auto_aggregate(
        args, SvdCodec(rank=2), init, 4, log=lines.append
    )
    assert mode == "hierarchical"
    assert args._auto_plan in PLAN_NAMES
    line = lines[0]
    assert "inner 2x ici @ 45.00 GB/s" in line
    assert "outer 2x dcn @ 6.25 GB/s" in line
    assert "inner tier moves" in line and "outer tier moves" in line
    # an explicit --plan overrides the planner: the advisory must price
    # the PINNED plan (not announce a selection that will not run) and
    # must not stash a competing _auto_plan
    args2 = argparse.Namespace(
        fabric="auto", codec_tax_ms=None, dcn_ways=2, plan="cring+ring"
    )
    lines2 = []
    mode = _resolve_auto_aggregate(
        args2, SvdCodec(rank=2), init, 4, log=lines2.append
    )
    assert mode == "hierarchical"
    assert not hasattr(args2, "_auto_plan")
    assert "plan cring+ring" in lines2[0]
    assert "pinned by --plan" in lines2[0]
    assert "psum+gather" not in lines2[0]
