"""Sparse gradient exchange (PR-12, ``--sparse-rows``).

Contracts being pinned (sparse/rowcodec, sparse/hybrid,
parallel/replicated's ``hybrid=`` knob, data/zipf, comm_model's per-leaf
pricing, obs quality/report columns):

  * The row codec is LOSSLESS bit for bit within its static budget —
    round trip, duplicate-row collisions summing exactly, padding as an
    IEEE-exact identity, overflow counted (never hidden).
  * The sparse aggregation operator is bit-identical to the canonical
    dense exchange — the gather vmap-decode + mean form AND the
    ring-staged form (RowCodec riding ``_ring_stream_mean`` unchanged).
  * The hybrid plan is pure/deterministic, states the SparCML crossover
    as a formula in its reason lines, and its per-leaf budgets sum to
    the wire bytes the executed step reports.
  * ``hybrid=None`` is byte-identical lowered HLO; all-dense
    assignments are bit-identical trajectories (gather and ring); full
    GATHER trajectories bit-match all-dense under the lossless codec;
    ring's fused form tracks to the documented fusion-drift class.
  * The conflict matrix rejects sparse x {psum-degenerate, hierarchical
    boundary re-encode, delayed overlap, stream-encode, guard/elastic,
    num_aggregate} with reasons — builder AND argv preflight.
  * The zipf sampler is seeded-deterministic and rides BatchIterator's
    rng_signature / resume-replay conventions unchanged.
  * comm_model: ONE per-leaf accounting function behind the whole-tree
    scalars and the +sp candidates; quality meta density columns and
    the report verb's quality_density_valid check.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from atomo_tpu.codecs import DenseCodec, QsgdCodec, decode_mean_tree
from atomo_tpu.data import BatchIterator, SPECS, zipf_dataset
from atomo_tpu.data.zipf import zipf_spec
from atomo_tpu.models import EmbeddingTower, get_model
from atomo_tpu.parallel import (
    make_distributed_train_step,
    make_mesh,
    replicate_state,
    shard_batch,
)
from atomo_tpu.parallel.replicated import _hybrid_mean, _ring_stream_mean
from atomo_tpu.sparse import (
    HybridPlan,
    RowCodec,
    infer_row_bounds,
    measured_densities,
    plan_for_model,
    plan_hybrid,
    probe_gradient,
    row_payload_bytes,
)
from atomo_tpu.training import create_state, make_optimizer, snapshot_state

N_DEV = 4
BATCH = 32
SLOTS = 8


def _eq(a, b):
    return all(
        np.array_equal(np.asarray(x), np.asarray(y))
        for x, y in zip(jax.tree_util.tree_leaves(a),
                        jax.tree_util.tree_leaves(b))
    )


def _setup():
    mesh = make_mesh(N_DEV)
    model = get_model("embedding", 10)
    opt = make_optimizer("sgd", lr=0.05, momentum=0.9)
    ds = zipf_dataset(True, size=4 * BATCH, seed=0)
    host0 = snapshot_state(
        create_state(model, opt, jax.random.PRNGKey(0),
                     jnp.asarray(ds.images[:BATCH]))
    )
    return mesh, model, opt, host0, ds


def _run(step, mesh, host0, ds, n=3, init=None):
    st = init if init is not None else replicate_state(
        mesh, jax.tree_util.tree_map(jnp.asarray, host0)
    )
    key = jax.random.PRNGKey(1)
    m = None
    for i in range(n):
        si, sl = shard_batch(
            mesh,
            ds.images[i * BATCH:(i + 1) * BATCH],
            ds.labels[i * BATCH:(i + 1) * BATCH],
        )
        st, m = step(st, key, si, sl)
    return jax.device_get(st), jax.device_get(m)


def _plan(codec, model, ds, batch_per_chip=BATCH // N_DEV):
    return plan_for_model(
        codec, model, ds.images[:BATCH], ds.labels[:BATCH],
        batch_per_chip=batch_per_chip, slots=SLOTS,
    )


# --------------------------------------------------------------- zipf data


def test_zipf_dataset_deterministic_and_spec_lockstep():
    a = zipf_dataset(True, size=128, seed=3)
    b = zipf_dataset(True, size=128, seed=3)
    c = zipf_dataset(True, size=128, seed=4)
    assert np.array_equal(a.images, b.images)
    assert np.array_equal(a.labels, b.labels)
    assert not np.array_equal(a.images, c.images)
    assert a.images.dtype == np.float32 and a.images.shape == (128, SLOTS)
    # ids are exact integers in float32 and labels derive from row 0
    assert np.array_equal(a.images, np.round(a.images))
    assert np.array_equal(
        a.labels, (a.images[:, 0].astype(np.int64) % 10).astype(np.int32)
    )
    # the datasets.py literal spec stays in lockstep with data/zipf.py
    assert SPECS["zipf"] == zipf_spec()
    # train/test draw from offset seeds
    t = zipf_dataset(False, size=128, seed=3)
    assert not np.array_equal(a.images, t.images)
    with pytest.raises(ValueError, match="2\\^24"):
        zipf_dataset(True, rows=(1 << 24) + 1)


def test_zipf_rides_batch_iterator_signature_and_replay():
    """The satellite contract: the new workload's stream fingerprints and
    replays through the UNCHANGED BatchIterator machinery — elastic
    shard maps (rng_signature) and rollback replay (restream) covered."""
    ds = zipf_dataset(True, size=64, seed=5)
    it1 = BatchIterator(ds, 16, seed=9)
    it2 = BatchIterator(zipf_dataset(True, size=64, seed=5), 16, seed=9)
    assert it1.rng_signature() == it2.rng_signature()
    snap = it1.snapshot_rng()
    s1 = it1.forever()
    consumed = [next(s1) for _ in range(5)]
    # fingerprints diverge once the shuffle RNG advances
    assert it1.rng_signature() != it2.rng_signature()
    # restream replays the post-skip suffix bit-identically
    r = it1.restream(snap, skip=3)
    for want, got in zip(consumed[3:], [next(r) for _ in range(2)]):
        assert np.array_equal(want[0], got[0])
        assert np.array_equal(want[1], got[1])


def test_zipf_is_power_law_sparse():
    ds = zipf_dataset(True, size=1024, seed=0)
    ids = ds.images.astype(np.int64)
    # hot head: row 0 appears far more often than a uniform draw would
    assert (ids == 0).mean() > 10.0 / 4096
    # per-batch distinct rows far below the table size (the density the
    # hybrid plan measures)
    distinct = len(np.unique(ids[:BATCH]))
    assert distinct <= BATCH * SLOTS < 4096


# --------------------------------------------------------------- row codec


def test_rowcodec_lossless_roundtrip_and_padding_identity():
    rc = RowCodec(max_rows=16)
    r = np.random.default_rng(0)
    g = np.zeros((200, 6), np.float32)
    g[[3, 7, 50, 199]] = r.standard_normal((4, 6)).astype(np.float32)
    p = jax.jit(lambda x: rc.encode(jax.random.PRNGKey(0), x))(
        jnp.asarray(g)
    )
    assert int(p.overflow) == 0
    d = jax.jit(lambda q: rc.decode(q, (200, 6)))(p)
    assert np.array_equal(np.asarray(d), g)  # bit-for-bit, zeros included
    # padding slots point at row 0 with zero values — row 0's decode is
    # untouched even though every padding slot scatter-adds there
    assert np.asarray(p.rows).shape == (16,)
    assert np.array_equal(np.asarray(d)[0], g[0])
    # wire bytes match the stated formula
    from atomo_tpu.codecs import payload_nbytes

    assert payload_nbytes(p) == row_payload_bytes(16, 6)


def test_rowcodec_overflow_counted_never_hidden():
    rc = RowCodec(max_rows=2)
    g = np.zeros((10, 3), np.float32)
    g[[1, 4, 7]] = 1.0
    p = rc.encode(jax.random.PRNGKey(0), jnp.asarray(g))
    assert int(p.overflow) == 1  # three nonzero rows, budget two
    # the kept rows are the FIRST nonzero rows in ascending order
    assert sorted(np.asarray(p.rows).tolist()) == [1, 4]


def test_rowcodec_rejects_non_2d():
    with pytest.raises(ValueError, match="2-D"):
        RowCodec(max_rows=4).encode(
            jax.random.PRNGKey(0), jnp.zeros((8,))
        )


def test_rowcodec_duplicate_rows_across_replicas_sum_exactly():
    """The collision drill: replicas touching the SAME row sum exactly —
    per-replica decode is exact, so the cross-replica mean is the dense
    mean bit for bit."""
    rc = RowCodec(max_rows=8)
    r = np.random.default_rng(1)
    dense = []
    payloads = []
    for c in range(N_DEV):
        g = np.zeros((64, 4), np.float32)
        rows = [0, 3, 5 + c]  # row 0 and 3 collide on every replica
        g[rows] = r.standard_normal((len(rows), 4)).astype(np.float32)
        dense.append(g)
        payloads.append(rc.encode(jax.random.PRNGKey(c), jnp.asarray(g)))
    stack = jax.tree_util.tree_map(lambda *a: jnp.stack(a), *payloads)
    dec = jax.vmap(lambda q: rc.decode(q, (64, 4)))(stack)
    got = jnp.mean(dec, axis=0)
    want = jnp.mean(jnp.stack([jnp.asarray(g) for g in dense]), axis=0)
    assert np.array_equal(np.asarray(got), np.asarray(want))


# ------------------------------------- operator parity (gather + ring form)


def test_sparse_mean_bit_equals_canonical_dense_exchange():
    """The acceptance drill, gather form: for row-sparse gradients the
    row exchange's mean is bit-identical to the canonical dense exchange
    (vmap-decode + mean over DenseCodec payloads) — same arithmetic over
    exactly-decoded values."""
    mesh = make_mesh(N_DEV)
    rc = RowCodec(max_rows=8)
    r = np.random.default_rng(2)
    grads = []
    for c in range(N_DEV):
        g = np.zeros((64, 4), np.float32)
        g[r.integers(0, 64, 6)] = r.standard_normal((6, 4))
        grads.append(jnp.asarray(g))
    gx = jnp.stack(grads)

    def sm(fn, in_specs, out_specs):
        return jax.jit(jax.shard_map(
            fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
            check_vma=False,
        ))

    def via_rows(gx_):
        g = gx_[0]
        p = rc.encode(jax.random.PRNGKey(0), g)
        gathered = jax.lax.all_gather(p, "dp")
        dec = jax.vmap(lambda q: rc.decode(q, (64, 4)))(gathered)
        return jnp.mean(dec, axis=0)

    def via_dense(gx_):
        g = gx_[0]
        dc = DenseCodec()
        p = dc.encode(jax.random.PRNGKey(0), g)
        gathered = jax.lax.all_gather(p, "dp")
        return decode_mean_tree(
            dc, [gathered], [g], N_DEV, fused=False
        )[0]

    a = sm(via_rows, (P("dp"),), P())(gx)
    b = sm(via_dense, (P("dp"),), P())(gx)
    assert np.array_equal(np.asarray(a), np.asarray(b))


def test_rowcodec_rides_ring_staged_form_bit_exact():
    """The ring-staged form of the lossless drill: RowCodec IS a Codec,
    so it rides ``_ring_stream_mean`` unchanged — and the staged
    canonical-order mean bit-matches the gather form over the same
    payloads."""
    mesh = make_mesh(N_DEV)
    rc = RowCodec(max_rows=8)
    r = np.random.default_rng(3)
    grads = []
    for c in range(N_DEV):
        g = np.zeros((96, 5), np.float32)
        g[r.integers(0, 96, 7)] = r.standard_normal((7, 5))
        grads.append(jnp.asarray(g))
    gx = jnp.stack(grads)

    def sm(fn, in_specs, out_specs):
        return jax.jit(jax.shard_map(
            fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
            check_vma=False,
        ))

    def via_ring(gx_):
        my = jax.lax.axis_index("dp")
        g = gx_[0]
        p = rc.encode(jax.random.PRNGKey(0), g)
        mean, _ = _ring_stream_mean(
            rc, [p], [g], axis="dp", n_dev=N_DEV, my=my,
            n_contrib=N_DEV, bucket_size=65536,
        )
        return mean[0]

    def via_gather(gx_):
        g = gx_[0]
        p = rc.encode(jax.random.PRNGKey(0), g)
        gathered = jax.lax.all_gather(p, "dp")
        return decode_mean_tree(
            rc, [gathered], [g], N_DEV, fused=False
        )[0]

    a = sm(via_ring, (P("dp"),), P())(gx)
    b = sm(via_gather, (P("dp"),), P())(gx)
    # both equal the raw dense mean too (losslessness end to end)
    want = jnp.mean(gx, axis=0)
    assert np.array_equal(np.asarray(a), np.asarray(b))
    assert np.array_equal(np.asarray(a), np.asarray(want))


# ------------------------------------------------------------- hybrid plan


def test_plan_hybrid_pure_deterministic_and_crossover_stated():
    _, model, opt, host0, ds = _setup()
    codec = DenseCodec()
    p1 = _plan(codec, model, ds)
    p2 = _plan(codec, model, ds)
    assert p1 == p2  # pure function of the same inputs
    assert p1.any_sparse and list(p1.sparse_idxs) == [4]
    table = p1.assignments[4]
    assert table.kind == "sparse"
    assert table.row_budget == (BATCH // N_DEV) * SLOTS
    assert 0.0 < table.density < 1.0
    # the SparCML crossover is stated as a formula with numbers
    assert "SparCML crossover" in table.reason
    assert f"B={table.row_budget}" in table.reason
    # dense leaves carry their reason too
    assert all(
        "dense" in a.reason for a in p1.assignments if a.kind == "dense"
    )
    # per-leaf budgets sum to the plan's wire total
    from atomo_tpu.utils.comm_model import leaf_budget_totals

    d, p = leaf_budget_totals(p1.leaf_budgets())
    assert int(p) == p1.payload_bytes()
    assert table.payload_bytes == row_payload_bytes(table.row_budget, 16)


def test_plan_hybrid_assigns_dense_when_budget_crosses():
    """A budget at the table size prices sparse above dense — the
    crossover flips the assignment (the formula, exercised)."""
    _, model, opt, host0, ds = _setup()
    codec = DenseCodec()
    grads = probe_gradient(model, ds.images[:8], ds.labels[:8])
    dens = measured_densities(grads)
    bounds = infer_row_bounds(grads, batch_per_chip=1 << 20, slots=SLOTS)
    assert bounds[4] == 4096  # clamped to the table rows
    plan = plan_hybrid(codec, grads, dens, bounds)
    assert plan.assignments[4].kind == "dense"
    assert not plan.any_sparse


def test_plan_hybrid_input_mismatch_rejected():
    _, model, opt, host0, ds = _setup()
    grads = probe_gradient(model, ds.images[:8], ds.labels[:8])
    with pytest.raises(ValueError, match="canonical order"):
        plan_hybrid(DenseCodec(), grads, [1.0], [None])


def test_infer_row_bounds_name_matching():
    _, model, opt, host0, ds = _setup()
    bounds = infer_row_bounds(host0.params, batch_per_chip=8, slots=SLOTS)
    # only the 2-D table leaf gets a bound; dense tower leaves get None
    assert bounds[4] == 8 * SLOTS
    assert all(b is None for b in bounds[:4])


def test_measured_densities_canonical_order():
    g = {
        "a": np.zeros((10, 3), np.float32),
        "b": np.ones((4,), np.float32),
    }
    g["a"][2] = 1.0
    d = measured_densities(g)
    assert d == [pytest.approx(0.1), 1.0]


# -------------------------------------------------- step-level contracts


def test_hybrid_off_is_byte_identical_and_adds_no_ops():
    mesh, model, opt, host0, ds = _setup()
    codec = QsgdCodec(bits=8, bucket_size=128)
    key = jax.random.PRNGKey(1)
    si, sl = shard_batch(mesh, ds.images[:BATCH], ds.labels[:BATCH])
    st = replicate_state(mesh, jax.tree_util.tree_map(jnp.asarray, host0))
    s_def = make_distributed_train_step(model, opt, mesh, codec,
                                        aggregate="gather")
    s_off = make_distributed_train_step(model, opt, mesh, codec,
                                        aggregate="gather", hybrid=None)
    a = s_def.lower(st, key, si, sl).as_text()
    b = s_off.lower(st, key, si, sl).as_text()
    assert a == b  # the knob-off contract, byte for byte
    plan = _plan(codec, model, ds)
    s_on = make_distributed_train_step(model, opt, mesh, codec,
                                       aggregate="gather", hybrid=plan)
    c = s_on.lower(st, key, si, sl).as_text()
    assert c != a  # armed actually restructures the exchange


def test_hybrid_gather_trajectory_bit_matches_all_dense():
    """The trajectory-level lossless contract:
    with the lossless DenseCodec on the tower, hybrid-vs-off gather
    trajectories are bit-identical — the row path changed the wire, not
    one bit of arithmetic."""
    mesh, model, opt, host0, ds = _setup()
    codec = DenseCodec()
    plan = _plan(codec, model, ds)
    off = make_distributed_train_step(model, opt, mesh, codec,
                                      aggregate="gather")
    on = make_distributed_train_step(model, opt, mesh, codec,
                                     aggregate="gather", hybrid=plan)
    a, ma = _run(off, mesh, host0, ds)
    b, mb = _run(on, mesh, host0, ds)
    assert _eq(a.params, b.params)
    assert _eq(a.opt_state, b.opt_state)
    # and the wire shrank, reported honestly
    assert float(mb["msg_bytes"]) == plan.payload_bytes()
    assert float(mb["msg_bytes"]) < float(ma["msg_bytes"])
    assert float(mb["dense_bytes"]) == float(ma["dense_bytes"])


def test_hybrid_ring_tracks_all_dense_to_fusion_drift():
    """Ring + sparse assignment restructures the flat segmentation, so
    the fused step tracks all-dense to the documented fusion-drift class
    (~1e-8 allclose) while the standalone operator is bit-exact
    (test_hybrid_mean_operator_bit_exact_vs_full_ring)."""
    mesh, model, opt, host0, ds = _setup()
    codec = DenseCodec()
    plan = _plan(codec, model, ds)
    off = make_distributed_train_step(model, opt, mesh, codec,
                                      aggregate="ring")
    on = make_distributed_train_step(model, opt, mesh, codec,
                                     aggregate="ring", hybrid=plan)
    a, _ = _run(off, mesh, host0, ds)
    b, _ = _run(on, mesh, host0, ds)
    assert all(
        np.allclose(np.asarray(x), np.asarray(y), atol=1e-6)
        for x, y in zip(jax.tree_util.tree_leaves(a.params),
                        jax.tree_util.tree_leaves(b.params))
    )


def test_hybrid_mean_operator_bit_exact_vs_full_ring():
    """Standalone aggregation operator: hybrid (ring for the dense
    sub-list, rows for the table) equals the full-tree ring bit for bit
    — exact decode makes the restructuring invisible at operator level."""
    mesh, model, opt, host0, ds = _setup()
    codec = DenseCodec()
    plan = _plan(codec, model, ds)
    from atomo_tpu.codecs import encode_tree

    leaves, treedef = jax.tree_util.tree_flatten(host0.params)
    r = np.random.default_rng(4)
    chips = []
    for c in range(N_DEV):
        out = []
        for i, l in enumerate(leaves):
            a = np.zeros(l.shape, np.float32)
            if i in plan.sparse_idxs:
                a[r.integers(0, l.shape[0], 20)] = r.standard_normal(
                    (20, l.shape[1])
                )
            else:
                a = r.standard_normal(l.shape).astype(np.float32)
            out.append(jnp.asarray(a))
        chips.append(jax.tree_util.tree_unflatten(treedef, out))
    gx = jax.tree_util.tree_map(lambda *a: jnp.stack(a), *chips)

    def sm(fn):
        return jax.jit(jax.shard_map(
            fn, mesh=mesh, in_specs=(P("dp"),), out_specs=P(),
            check_vma=False,
        ))

    def full_ring(gx_):
        my = jax.lax.axis_index("dp")
        g = jax.tree_util.tree_map(lambda a: a[0], gx_)
        p, _ = encode_tree(codec, jax.random.PRNGKey(0), g)
        mean, _ = _ring_stream_mean(
            codec, p, g, axis="dp", n_dev=N_DEV, my=my,
            n_contrib=N_DEV, bucket_size=65536,
        )
        return mean

    def hyb(gx_):
        my = jax.lax.axis_index("dp")
        g = jax.tree_util.tree_map(lambda a: a[0], gx_)
        mean, _, _, _ = _hybrid_mean(
            codec, plan, g, jax.random.PRNGKey(0), axis="dp",
            n_dev=N_DEV, my=my, aggregate="ring",
            ring_bucket_size=65536, unfused_decode=False,
            track_quality=False,
        )
        return mean

    assert _eq(jax.device_get(sm(full_ring)(gx)),
               jax.device_get(sm(hyb)(gx)))


@pytest.mark.parametrize("agg", ["gather", "ring"])
def test_all_dense_assignment_bit_matches_hybrid_off(agg):
    """The hybrid-off contract for lossy codecs: an all-dense plan keeps
    the global-leaf-key encode and the full leaf list, so trajectories
    bit-match ``hybrid=None`` even under qsgd."""
    mesh, model, opt, host0, ds = _setup()
    codec = QsgdCodec(bits=8, bucket_size=128)
    grads = probe_gradient(model, ds.images[:8], ds.labels[:8])
    plan = plan_hybrid(
        codec, grads, measured_densities(grads),
        [None] * len(jax.tree_util.tree_leaves(grads)),
    )
    assert not plan.any_sparse
    off = make_distributed_train_step(model, opt, mesh, codec,
                                      aggregate=agg)
    on = make_distributed_train_step(model, opt, mesh, codec,
                                     aggregate=agg, hybrid=plan)
    a, _ = _run(off, mesh, host0, ds)
    b, _ = _run(on, mesh, host0, ds)
    assert _eq(a.params, b.params)


def test_hybrid_composes_with_zero1_and_superstep():
    from atomo_tpu.parallel import shard_superbatch
    from atomo_tpu.parallel.replicated import zero1_state

    mesh, model, opt, host0, ds = _setup()
    codec = DenseCodec()
    plan = _plan(codec, model, ds)
    # zero1: the sliced update consumes the same mean — bit parity holds
    z0, specs0 = zero1_state(
        mesh, replicate_state(
            mesh, jax.tree_util.tree_map(jnp.asarray, host0)
        ), opt,
    )
    off = make_distributed_train_step(model, opt, mesh, codec,
                                      aggregate="gather",
                                      zero1_specs=specs0)
    a, _ = _run(off, mesh, host0, ds, init=z0)
    z1, specs1 = zero1_state(
        mesh, replicate_state(
            mesh, jax.tree_util.tree_map(jnp.asarray, host0)
        ), opt,
    )
    on = make_distributed_train_step(model, opt, mesh, codec,
                                     aggregate="gather",
                                     zero1_specs=specs1, hybrid=plan)
    b, _ = _run(on, mesh, host0, ds, init=z1)
    assert _eq(a.params, b.params)
    # superstep: the scan family runs and stays finite with the plan
    key = jax.random.PRNGKey(1)
    im = np.stack([ds.images[:BATCH], ds.images[BATCH:2 * BATCH]])
    lb = np.stack([ds.labels[:BATCH], ds.labels[BATCH:2 * BATCH]])
    bi, bl = shard_superbatch(mesh, im, lb)
    s_off = make_distributed_train_step(model, opt, mesh, codec,
                                        aggregate="gather", superstep=2)
    s_on = make_distributed_train_step(model, opt, mesh, codec,
                                       aggregate="gather", superstep=2,
                                       hybrid=plan)
    sa, _ = s_off(replicate_state(
        mesh, jax.tree_util.tree_map(jnp.asarray, host0)), key, bi, bl)
    sb, _ = s_on(replicate_state(
        mesh, jax.tree_util.tree_map(jnp.asarray, host0)), key, bi, bl)
    assert _eq(jax.device_get(sa).params, jax.device_get(sb).params)


def test_hybrid_quality_probe_reads_zero_on_sparse_layers():
    mesh, model, opt, host0, ds = _setup()
    codec = QsgdCodec(bits=8, bucket_size=128)
    plan = _plan(codec, model, ds)
    step = make_distributed_train_step(model, opt, mesh, codec,
                                       aggregate="gather", hybrid=plan,
                                       track_quality=True)
    _, m = _run(step, mesh, host0, ds, n=2)
    q = np.asarray(m["q_err2"])
    assert q.shape == (plan.n_leaves,)
    for i in plan.sparse_idxs:
        assert q[i] == 0.0  # lossless, observed live
    assert any(q[i] > 0 for i in plan.dense_idxs)  # qsgd is lossy
    # the budget audit column: zero dropped rows on the real workload
    assert float(m["row_overflow"]) == 0.0


# --------------------------------------------------------- conflict matrix


def test_builder_conflict_matrix():
    mesh, model, opt, host0, ds = _setup()
    codec = QsgdCodec(bits=8, bucket_size=128)
    plan = _plan(codec, model, ds)
    from atomo_tpu.training import GuardConfig

    with pytest.raises(ValueError, match="degenerates"):
        make_distributed_train_step(model, opt, mesh, codec,
                                    aggregate="psum", hybrid=plan)
    with pytest.raises(ValueError, match="per-leaf payload path"):
        make_distributed_train_step(model, opt, mesh, None, hybrid=plan)
    with pytest.raises(ValueError, match="delayed"):
        make_distributed_train_step(model, opt, mesh, codec,
                                    aggregate="gather",
                                    overlap="delayed", hybrid=plan)
    with pytest.raises(ValueError, match="assignment-aware"):
        make_distributed_train_step(model, opt, mesh, codec,
                                    aggregate="ring", stream_encode=True,
                                    hybrid=plan)
    with pytest.raises(ValueError, match="skip-and-rescale"):
        make_distributed_train_step(model, opt, mesh, codec,
                                    aggregate="gather",
                                    guard=GuardConfig(max_grad_norm=0.0),
                                    hybrid=plan)
    with pytest.raises(ValueError, match="num_aggregate"):
        make_distributed_train_step(model, opt, mesh, codec,
                                    aggregate="gather", num_aggregate=2,
                                    hybrid=plan)
    mesh2 = make_mesh(4, axes=(("dp", 2), ("ici", 2)))
    with pytest.raises(ValueError, match="row-aware"):
        make_distributed_train_step(model, opt, mesh2, codec,
                                    aggregate="hierarchical",
                                    inner_axis="ici", hybrid=plan)


def test_preflight_conflict_matrix():
    from atomo_tpu.cli import _argv_preflight, build_parser

    p = build_parser()
    train = p._subparsers._group_actions[0].choices["train"]
    base = ["--sparse-rows", "on", "--code", "qsgd", "--n-devices", "4",
            "--aggregate", "gather"]
    _argv_preflight(train.parse_args(base))  # the good config passes
    rejects = [
        (["--sparse-rows", "on", "--code", "qsgd", "--n-devices", "1"],
         "multi-device"),
        (["--sparse-rows", "on", "--code", "qsgd", "--n-devices", "4",
          "--aggregate", "psum"], "degenerates"),
        (["--sparse-rows", "on", "--code", "qsgd", "--n-devices", "4",
          "--aggregate", "hierarchical"], "re-encode"),
        (["--sparse-rows", "on", "--code", "qsgd", "--n-devices", "4",
          "--plan", "legacy"], "re-encode"),
        (base + ["--overlap", "delayed"], "delayed"),
        (base + ["--stream-encode", "on"], "assignment-aware"),
        (base + ["--grad-guard"], "skip-and-rescale"),
        (base + ["--num-aggregate", "2"], "num-aggregate"),
        (["--sparse-rows", "on", "--code", "qsgd", "--n-devices", "4",
          "--auto", "tune", "--train-dir", "/tmp/x"], "pinned"),
        (["--sparse-rows", "auto", "--code", "sgd", "--n-devices", "4",
          "--auto", "tune", "--train-dir", "/tmp/x"], "compressing"),
    ]
    for argv, frag in rejects:
        with pytest.raises(SystemExit) as ei:
            _argv_preflight(train.parse_args(argv))
        assert frag in str(ei.value), (argv, str(ei.value))


# ----------------------------------------------------- comm model pricing


def test_leaf_budget_totals_is_the_one_accounting():
    from atomo_tpu.tuning.probe import (
        byte_budget,
        leaf_byte_budgets,
        model_init_fn,
    )
    from atomo_tpu.utils.comm_model import leaf_budget_totals

    model = get_model("embedding", 10)
    init = model_init_fn(model, jnp.zeros((1, SLOTS), jnp.float32))
    codec = QsgdCodec(bits=8, bucket_size=128)
    lbs = leaf_byte_budgets(codec, init)
    assert len(lbs) == 5
    assert byte_budget(codec, init) == tuple(
        int(x) for x in leaf_budget_totals(lbs)
    )
    d, p = byte_budget(None, init)
    assert p == 0 and d == byte_budget(codec, init)[0]


def test_sparse_candidates_enumerated_priced_and_pinned():
    from atomo_tpu.tuning.autopilot import winner_knobs
    from atomo_tpu.utils.comm_model import (
        enumerate_candidates,
        predict_step_s,
    )

    lb = [[1 << 20, 1 << 20], [1 << 22, 1 << 14]]
    base = enumerate_candidates(has_codec=True, ways=4)
    withsp = enumerate_candidates(
        has_codec=True, ways=4, allow_sparse=True, sparse_leaf_budgets=lb
    )
    names = {c["name"] for c in withsp}
    assert {c["name"] for c in base} < names
    assert any("+sp+" in n for n in names)
    # sparse candidates exist only for the plain blocking gather/ring
    for c in withsp:
        if c.get("sparse_rows") == "on":
            assert c["aggregate"] in ("gather", "ring")
            assert c["overlap"] == "off"
            assert c.get("stream_encode") != "on"
    kw = dict(dense_bytes=5 << 20, payload_bytes=5 << 20, ways=4,
              fabric_bw=1.25e9, tax_s=2e-3)
    off = {"aggregate": "gather", "overlap": "off", "superstep": 1}
    sp = {**off, "sparse_rows": "on", "leaf_budgets": lb}
    # the +sp candidate's wire comes from ITS per-leaf sum — cheaper
    assert predict_step_s(sp, **kw) < predict_step_s(off, **kw)
    # candidates carry only the flag; the per-leaf pairs are supplied
    # ONCE at ranking time (no duplication into the decision artifact)
    assert all("leaf_budgets" not in c for c in withsp)
    sp_flag = {**off, "sparse_rows": "on"}
    assert predict_step_s(
        sp_flag, **kw, sparse_leaf_budgets=lb
    ) == predict_step_s(sp, **kw)
    # winner knobs carry the sparse field so the CLI can apply it
    k = winner_knobs({**sp, "name": "x", "probed": True})
    assert k["sparse_rows"] == "on"
    # disabled without budgets
    none = enumerate_candidates(has_codec=True, ways=4, allow_sparse=True)
    assert not any(c.get("sparse_rows") == "on" for c in none)


# --------------------------------------------------- obs meta + report


def test_quality_meta_density_columns_and_report_check():
    from atomo_tpu.obs.quality import quality_meta
    from atomo_tpu.obs.report import _check_quality_density

    _, model, opt, host0, ds = _setup()
    codec = QsgdCodec(bits=8, bucket_size=128)
    plan = _plan(codec, model, ds)
    meta = quality_meta(codec, host0.params, hybrid=plan)
    tab = [l for l in meta["layers"] if "table" in l["name"]][0]
    assert tab["assignment"] == "sparse"
    assert 0.0 <= tab["density"] <= 1.0
    assert tab["row_budget"] == plan.assignments[4].row_budget
    assert tab["payload_bytes"] < tab["dense_bytes"]
    # the meta's total reflects the ASSIGNED exchange
    assert meta["payload_bytes"] == plan.payload_bytes()
    # plain meta (no hybrid) carries no density columns
    plain = quality_meta(codec, host0.params)
    assert all("density" not in l for l in plain["layers"])
    with pytest.raises(ValueError, match="must match"):
        quality_meta(codec, {"one": jnp.zeros((2, 2))}, hybrid=plan)
    # the report check: valid meta passes, corrupted density fails,
    # non-sparse metas skip
    ok = _check_quality_density([meta])
    assert ok["ok"] and not ok["skipped"]
    bad = {**meta, "layers": [dict(tab, density=1.5)]}
    assert not _check_quality_density([bad])["ok"]
    fat = dict(tab, payload_bytes=tab["dense_bytes"] + 1)
    assert not _check_quality_density(
        [{**meta, "layers": [fat]}]
    )["ok"]
    assert _check_quality_density([plain])["skipped"]


def test_embedding_model_fits_zipf():
    """The workload is trainable: loss drops over a short single-device
    run (the synthetic_dataset 'models can actually fit it' rule)."""
    from atomo_tpu.training import make_train_step

    model = get_model("embedding", 10)
    opt = make_optimizer("sgd", lr=0.1, momentum=0.9)
    ds = zipf_dataset(True, size=512, seed=0)
    st = create_state(model, opt, jax.random.PRNGKey(0),
                      jnp.asarray(ds.images[:64]))
    step = make_train_step(model, opt)
    key = jax.random.PRNGKey(2)
    losses = []
    for e in range(6):
        for i in range(8):
            im = jnp.asarray(ds.images[i * 64:(i + 1) * 64])
            lb = jnp.asarray(ds.labels[i * 64:(i + 1) * 64])
            st, m = step(st, key, im, lb)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0]
