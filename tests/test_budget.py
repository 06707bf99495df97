"""Adaptive variance-budget codecs + error feedback (PR-15 tentpole).

Contracts pinned here (atomo_tpu/budget + parallel/replicated EfState):

  * The water-filling solver is PURE and deterministic: same spectra and
    budget -> same allocation, always.
  * Degenerate-point identities: the per-leaf wrapper at UNIFORM ranks
    is byte-for-byte today's fixed-budget codec (bit-identical payloads,
    identical wire bytes); an unbounded budget drives every layer into
    the codec's exact dense fallback — ``--on-diverge densify``'s remedy
    as the dial's spend-everything limit.
  * The allocator's predicted per-leaf byte sums equal the executed
    encode's to the byte (the wire-match gate), under
    jit, the superstep scan and the streamed per-bucket encode — the
    per-leaf ranks are STATIC trace-time values.
  * budget_alloc.json round-trips; reuse refuses codec/leaf mismatches;
    the checkpoint-boundary retuner re-allocates out loud (artifact
    epoch + budget_realloc incident quoting both predicted variances).
  * Error feedback (EfState): step 1 equals the plain program bitwise
    (zero residual); the single-step estimator is BIASED (the stated
    contract) while the telescoping identity applied + residual ==
    sum(gradients) holds; the residual carry survives
    kill->restart->resume bit-exactly; unproven compositions are
    rejected by the builder, the loop and the CLI preflight.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from atomo_tpu.budget import (
    Allocation,
    BudgetRetuner,
    PerLeafCodec,
    alloc_reusable,
    allocation_leaf_budgets,
    allocation_meta,
    budgeted_codec,
    latest_epoch,
    measure_spectra,
    new_alloc_doc,
    read_alloc,
    solve_allocation,
    spectra_from_qerr2,
    uniform_ks,
    write_alloc,
)
from atomo_tpu.codecs import (
    DensePayload,
    SvdCodec,
    decode_mean_tree,
    decode_tree,
    encode_tree,
    encode_tree_streamed,
    payload_nbytes,
)
from atomo_tpu.data import BatchIterator, SPECS, synthetic_dataset
from atomo_tpu.models import get_model
from atomo_tpu.parallel import (
    EfState,
    init_ef_state,
    make_distributed_train_step,
    make_mesh,
    replicate_state,
    shard_batch,
)
from atomo_tpu.parallel.common import plan_layer_buckets
from atomo_tpu.training import create_state, make_optimizer


def _eq(a, b) -> bool:
    return all(
        np.array_equal(np.asarray(x), np.asarray(y))
        for x, y in zip(
            jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
        )
    )


def _grad_tree(key=0):
    k = jax.random.PRNGKey(key)
    return {
        "conv": jax.random.normal(k, (5, 5, 10, 20)),
        "fc": jax.random.normal(jax.random.fold_in(k, 1), (320, 50)) * 3.0,
        "bias": jax.random.normal(jax.random.fold_in(k, 2), (10,)),
        "fc2": jax.random.normal(jax.random.fold_in(k, 3), (50, 10)),
    }


CODEC = SvdCodec(rank=3)


# --------------------------------------------------------------- solver


def test_solver_pure_deterministic():
    spectra = measure_spectra(CODEC, _grad_tree())
    a1 = solve_allocation(CODEC, spectra, mode="variance")
    a2 = solve_allocation(CODEC, spectra, mode="variance")
    assert a1 == a2
    assert a1.payload_bytes <= a1.budget_bytes
    for l in spectra:
        assert 1 <= a1.ks[l.index] <= max(l.r_full, l.base_k)


def test_solver_respects_explicit_budget():
    spectra = measure_spectra(CODEC, _grad_tree())
    uni = solve_allocation(CODEC, spectra, mode="uniform")
    tight = solve_allocation(
        CODEC, spectra, budget_bytes=uni.payload_bytes * 3 // 4,
        mode="variance",
    )
    assert tight.payload_bytes <= uni.payload_bytes * 3 // 4
    rich = solve_allocation(
        CODEC, spectra, budget_bytes=uni.payload_bytes * 2,
        mode="variance",
    )
    # more budget never hurts the predicted variance
    assert rich.predicted_variance <= uni.predicted_variance + 1e-9


def test_uniform_degenerate_point_is_today_byte_for_byte():
    grads = _grad_tree()
    spectra = measure_spectra(CODEC, grads)
    wrapped = budgeted_codec(CODEC, uniform_ks(spectra))
    key = jax.random.PRNGKey(7)
    p0, s0 = encode_tree(CODEC, key, grads)
    p1, s1 = encode_tree(wrapped, key, grads)
    assert s0.payload_bytes == s1.payload_bytes
    assert _eq(p0, p1)
    # and decode agrees bitwise too
    assert _eq(decode_tree(CODEC, p0, grads), decode_tree(wrapped, p1, grads))


def test_spend_everything_point_is_densify():
    grads = _grad_tree()
    spectra = measure_spectra(CODEC, grads)
    big = solve_allocation(
        CODEC, spectra, budget_bytes=10**12, mode="variance"
    )
    wrapped = budgeted_codec(CODEC, big.ks)
    payloads, stats = encode_tree(wrapped, jax.random.PRNGKey(0), grads)
    # every leaf crossed into the codec's exact dense fallback: the
    # payload IS the gradient (the densify remedy, reached as the
    # budget dial's limit) and the wire equals dense
    assert stats.payload_bytes == stats.dense_bytes
    for p in jax.tree_util.tree_leaves(
        payloads, is_leaf=lambda x: isinstance(x, DensePayload)
    ):
        assert isinstance(p, DensePayload)
    decoded = decode_tree(wrapped, payloads, grads)
    for d, g in zip(
        jax.tree_util.tree_leaves(decoded),
        jax.tree_util.tree_leaves(grads),
    ):
        np.testing.assert_array_equal(np.asarray(d), np.asarray(g))


def test_wire_match_predicted_equals_executed():
    grads = _grad_tree()
    spectra = measure_spectra(CODEC, grads)
    alloc = solve_allocation(CODEC, spectra, mode="variance")
    wrapped = budgeted_codec(CODEC, alloc.ks)
    _, stats = encode_tree(wrapped, jax.random.PRNGKey(0), grads)
    assert stats.payload_bytes == alloc.payload_bytes
    # and the per-leaf pairs sum to the same number (the +ab pricing)
    assert sum(p for _, p in allocation_leaf_budgets(
        CODEC, spectra, alloc.ks
    )) == alloc.payload_bytes


def test_per_leaf_static_shapes_jit_and_stream():
    """The allocation's ranks are static per-leaf values: the wrapped
    encode traces under jit, and the streamed per-bucket encode is
    bit-identical to the monolithic one for any bucket size (the
    global-leaf-index key + codec dispatch discipline)."""
    grads = _grad_tree()
    spectra = measure_spectra(CODEC, grads)
    alloc = solve_allocation(CODEC, spectra, mode="variance")
    wrapped = budgeted_codec(CODEC, alloc.ks)
    key = jax.random.PRNGKey(3)
    p_ref, _ = encode_tree(wrapped, key, grads)
    p_jit = jax.jit(
        lambda k, g: encode_tree(wrapped, k, g)[0]
    )(key, grads)
    assert _eq(p_ref, p_jit)
    for bucket_bytes in (1 << 12, 1 << 14, 0):
        plan = plan_layer_buckets(grads, bucket_bytes)
        p_s, _ = encode_tree_streamed(wrapped, key, grads, plan)
        assert _eq(p_ref, p_s)


def test_decode_mean_tree_per_leaf_dispatch():
    """Gathered per-replica payloads of a per-leaf wrapped codec decode
    to the same mean as the per-replica decode + mean oracle."""
    grads = _grad_tree()
    spectra = measure_spectra(CODEC, grads)
    alloc = solve_allocation(CODEC, spectra, mode="variance")
    wrapped = budgeted_codec(CODEC, alloc.ks)
    n = 4
    payloads = [
        encode_tree(wrapped, jax.random.PRNGKey(100 + r), grads)[0]
        for r in range(n)
    ]
    gathered = jax.tree_util.tree_map(
        lambda *a: jnp.stack(a), *payloads
    )
    fused = decode_mean_tree(wrapped, gathered, grads, n, fused=False)
    oracle = jax.tree_util.tree_map(
        lambda *a: jnp.mean(jnp.stack(a), axis=0),
        *[decode_tree(wrapped, p, grads) for p in payloads],
    )
    assert _eq(fused, oracle)


def test_subset_reindexes_for_partial_leaf_lists():
    grads = _grad_tree()
    spectra = measure_spectra(CODEC, grads)
    alloc = solve_allocation(CODEC, spectra, mode="variance")
    wrapped = budgeted_codec(CODEC, alloc.ks)
    sub = wrapped.subset((2, 0))
    assert isinstance(sub, PerLeafCodec)
    assert sub.codec_for(0) == wrapped.codec_for(2)
    assert sub.codec_for(1) == wrapped.codec_for(0)
    with pytest.raises(IndexError):
        wrapped.codec_for(99)


def test_spectra_fold_from_qerr2():
    spectra = measure_spectra(CODEC, _grad_tree())
    ks = uniform_ks(spectra)
    q = [2.0] * len(spectra)
    fresh = spectra_from_qerr2(spectra, q, ks)
    for old, new in zip(spectra, fresh):
        if old.adaptive:
            assert new.a == pytest.approx(2.0 * ks[old.index])
        else:
            assert new.a == old.a
    # a gap (None / non-finite) keeps the prior A — not a sample
    q2 = [None, float("nan")] + [1.0] * (len(spectra) - 2)
    fresh2 = spectra_from_qerr2(spectra, q2, ks)
    assert fresh2[0].a == spectra[0].a
    assert fresh2[1].a == spectra[1].a


def test_spectra_fold_keeps_prior_a_at_dense_fallback():
    """A leaf currently shipped via the exact dense fallback reads
    q_err2 == 0 because the wire is exact, not because its spectrum
    vanished: with the codec passed (the retuner's call), the fold must
    keep the prior A so a re-solve cannot strip the leaf 'for free'
    and oscillate at every boundary (code-review finding)."""
    spectra = measure_spectra(CODEC, _grad_tree())
    target = next(l for l in spectra if l.adaptive and l.a > 0)
    # rank the target into its dense fallback (full rank always crosses
    # it under the near-square matricization)
    ks = list(uniform_ks(spectra))
    ks[target.index] = target.r_full
    q = [0.0] * len(spectra)  # the exact wire's observed error
    folded = spectra_from_qerr2(spectra, q, ks, codec=CODEC)
    assert folded[target.index].a == target.a  # prior kept
    # without the codec (no fallback knowledge) the raw law applies
    raw = spectra_from_qerr2(spectra, q, ks)
    assert raw[target.index].a == 0.0


# ------------------------------------------------------------- artifact


def test_artifact_roundtrip_and_reuse(tmp_path):
    grads = _grad_tree()
    spectra = measure_spectra(CODEC, grads)
    alloc = solve_allocation(CODEC, spectra, mode="variance")
    doc = new_alloc_doc(CODEC, spectra, alloc)
    write_alloc(str(tmp_path), doc)
    back = read_alloc(str(tmp_path))
    assert back == json.loads(json.dumps(doc))
    ok, why = alloc_reusable(
        back, codec_name=CODEC.name, n_leaves=len(spectra)
    )
    assert ok, why
    ep = latest_epoch(back)
    assert tuple(ep["ks"]) == alloc.ks
    # refusals: wrong codec, wrong leaf count, missing doc
    ok, why = alloc_reusable(back, codec_name="qsgd", n_leaves=len(spectra))
    assert not ok and "codec" in why
    ok, why = alloc_reusable(back, codec_name=CODEC.name, n_leaves=99)
    assert not ok and "leaves" in why
    ok, _ = alloc_reusable(None, codec_name=CODEC.name, n_leaves=1)
    assert not ok
    # the recorder meta's per-layer sum equals the artifact's
    meta = allocation_meta(ep)
    assert sum(l["payload_bytes"] for l in meta["layers"]) == \
        ep["payload_bytes"]


def test_retuner_reallocates_on_drifted_spectra(tmp_path):
    """Feed the retuner a recorded q_err2 series whose per-layer means
    contradict the startup spectra: the boundary re-solve must move the
    allocation, append an artifact epoch, and land a budget_realloc
    incident quoting predicted variance both ways."""
    from atomo_tpu.utils.tracing import IncidentLog

    grads = _grad_tree()
    spectra = measure_spectra(CODEC, grads)
    alloc = solve_allocation(CODEC, spectra, mode="variance")
    doc = new_alloc_doc(CODEC, spectra, alloc)
    write_alloc(str(tmp_path), doc)
    # fabricate the recorded stream: the leaf the startup allocation
    # fed LEAST suddenly carries all the error mass — the re-solve must
    # move atoms toward it
    n = len(spectra)
    target = min(
        (
            l for l in spectra
            if l.adaptive and alloc.ks[l.index] < l.r_full
        ),
        key=lambda l: (alloc.ks[l.index], l.index),
    ).index
    qrow = [0.0] * n
    qrow[target] = 1e6
    with open(os.path.join(str(tmp_path), "metrics.jsonl"), "w") as f:
        for s in range(1, 11):
            f.write(json.dumps(
                {"kind": "step", "step": s, "q_err2": qrow}
            ) + "\n")
    incidents = IncidentLog.for_train_dir(str(tmp_path))
    logs = []
    rt = BudgetRetuner(
        train_dir=str(tmp_path), base_codec=CODEC, spectra=spectra,
        alloc=alloc, doc=doc, incidents=incidents, log_fn=logs.append,
    )
    new_codec = rt.maybe_realloc(10)
    assert new_codec is not None
    assert new_codec.ks[target] > alloc.ks[target]
    back = read_alloc(str(tmp_path))
    assert len(back["epochs"]) == 2
    assert back["epochs"][1]["start_step"] == 10
    recs = IncidentLog.read(
        os.path.join(str(tmp_path), "incidents.jsonl")
    )
    rec = [r for r in recs if r.get("cause") == "budget_realloc"][-1]
    assert rec["action"] == "realloc->epoch1"
    assert rec["predicted_variance_old"] > rec["predicted_variance_new"]
    assert rec["ks_old"] != rec["ks_new"]


def test_retuner_keeps_without_signal_or_gain(tmp_path):
    from atomo_tpu.utils.tracing import IncidentLog

    grads = _grad_tree()
    spectra = measure_spectra(CODEC, grads)
    alloc = solve_allocation(CODEC, spectra, mode="variance")
    doc = new_alloc_doc(CODEC, spectra, alloc)
    write_alloc(str(tmp_path), doc)
    incidents = IncidentLog.for_train_dir(str(tmp_path))
    rt = BudgetRetuner(
        train_dir=str(tmp_path), base_codec=CODEC, spectra=spectra,
        alloc=alloc, doc=doc, incidents=incidents, log_fn=lambda *_: None,
    )
    # no recorded q series at all: not even a decision (no incident)
    assert rt.maybe_realloc(10) is None
    assert not [
        r for r in IncidentLog.read(
            os.path.join(str(tmp_path), "incidents.jsonl")
        )
        if r.get("cause") == "budget_realloc"
    ]
    # a consistent series (q == A/k of the startup spectra): keep, with
    # the decision on the record
    n = len(spectra)
    qrow = [
        (l.a / alloc.ks[l.index]) if l.adaptive else 0.0
        for l in spectra
    ]
    assert len(qrow) == n
    with open(os.path.join(str(tmp_path), "metrics.jsonl"), "w") as f:
        for s in range(1, 11):
            f.write(json.dumps(
                {"kind": "step", "step": s, "q_err2": qrow}
            ) + "\n")
    assert rt.maybe_realloc(10) is None
    kept = [
        r for r in IncidentLog.read(
            os.path.join(str(tmp_path), "incidents.jsonl")
        )
        if r.get("cause") == "budget_realloc"
    ]
    assert kept and kept[-1]["action"] == "keep"


def test_budget_alloc_consistent_report_check(tmp_path):
    from atomo_tpu.obs.report import build_report

    grads = _grad_tree()
    spectra = measure_spectra(CODEC, grads)
    alloc = solve_allocation(CODEC, spectra, mode="variance")
    doc = new_alloc_doc(CODEC, spectra, alloc)
    write_alloc(str(tmp_path), doc)
    meta = allocation_meta(latest_epoch(doc))
    with open(os.path.join(str(tmp_path), "metrics.jsonl"), "w") as f:
        f.write(json.dumps({"kind": "meta", **meta}) + "\n")
        for s in range(1, 4):
            f.write(json.dumps(
                {"kind": "step", "step": s, "loss": 1.0,
                 "budget_epoch": 0}
            ) + "\n")
    rep = build_report(str(tmp_path))
    chk = next(
        c for c in rep["checks"] if c["name"] == "budget_alloc_consistent"
    )
    assert chk["ok"] and not chk["skipped"], chk
    # a record claiming a never-recorded epoch fails the check
    with open(os.path.join(str(tmp_path), "metrics.jsonl"), "a") as f:
        f.write(json.dumps(
            {"kind": "step", "step": 4, "loss": 1.0, "budget_epoch": 7}
        ) + "\n")
    rep = build_report(str(tmp_path))
    chk = next(
        c for c in rep["checks"] if c["name"] == "budget_alloc_consistent"
    )
    assert not chk["ok"]


def test_report_check_skipped_without_budget(tmp_path):
    from atomo_tpu.obs.report import build_report

    rep = build_report(str(tmp_path))
    chk = next(
        c for c in rep["checks"] if c["name"] == "budget_alloc_consistent"
    )
    assert chk["ok"] and chk["skipped"]


# ------------------------------------------------------- error feedback


MESH4 = None


def _mesh4():
    global MESH4
    if MESH4 is None:
        MESH4 = make_mesh(4)
    return MESH4


def _setup_step(codec, **kw):
    mesh = _mesh4()
    model = get_model("lenet", 10)
    opt = make_optimizer("sgd", lr=0.05, momentum=0.9)
    images = jax.random.uniform(jax.random.PRNGKey(1), (16, 28, 28, 1))
    labels = jax.random.randint(jax.random.PRNGKey(2), (16,), 0, 10)
    host0 = jax.device_get(
        create_state(model, opt, jax.random.PRNGKey(0), images)
    )
    step = make_distributed_train_step(model, opt, mesh, codec, **kw)
    si, sl = shard_batch(mesh, images, labels)

    def fresh():
        return replicate_state(
            mesh, jax.tree_util.tree_map(jnp.asarray, host0)
        )

    return step, fresh, si, sl


TOPK = SvdCodec(rank=2, sample="topk")


@pytest.mark.slow
def test_ef_step1_equals_plain_bitwise():
    """Zero residual: the first EF step IS the plain step, bit for bit —
    the honest-start contract on _zero_ef_residual_host."""
    key = jax.random.PRNGKey(0)
    step_p, fresh, si, sl = _setup_step(TOPK, aggregate="gather")
    step_e, _, _, _ = _setup_step(
        TOPK, aggregate="gather", error_feedback=True
    )
    sp, _ = step_p(fresh(), key, si, sl)
    se, me = step_e(init_ef_state(_mesh4(), fresh()), key, si, sl)
    assert isinstance(se, EfState)
    assert _eq(jax.device_get(sp.params), jax.device_get(se.params))
    assert float(me["ef_res_norm"]) > 0  # topk is lossy: residual exists


@pytest.mark.slow
def test_ef_superstep_partition_invariance():
    """The residual rides the scan carry: two K=2 blocks equal one K=4
    block bit-for-bit — the PR-2 partition invariance WITHIN the scan
    family, EF carry included (scan-vs-standalone keeps its documented
    last-mantissa fusion-drift class, so K=1 is not the oracle here)."""
    from atomo_tpu.parallel import shard_superbatch

    key = jax.random.PRNGKey(0)
    mesh = _mesh4()
    model = get_model("lenet", 10)
    opt = make_optimizer("sgd", lr=0.05, momentum=0.9)
    images = jax.random.uniform(jax.random.PRNGKey(1), (16, 28, 28, 1))
    labels = jax.random.randint(jax.random.PRNGKey(2), (16,), 0, 10)
    host0 = jax.device_get(
        create_state(model, opt, jax.random.PRNGKey(0), images)
    )

    def run_blocks(block_k, n_blocks):
        step = make_distributed_train_step(
            model, opt, mesh, TOPK, aggregate="gather",
            error_feedback=True, superstep=block_k,
        )
        st = init_ef_state(mesh, replicate_state(
            mesh, jax.tree_util.tree_map(jnp.asarray, host0)
        ))
        imk = jnp.broadcast_to(images, (block_k,) + images.shape)
        lbk = jnp.broadcast_to(labels, (block_k,) + labels.shape)
        sik, slk = shard_superbatch(mesh, imk, lbk)
        for _ in range(n_blocks):
            st, _ = step(st, key, sik, slk)
        return st

    a = run_blocks(2, 2)
    b = run_blocks(4, 1)
    assert _eq(jax.device_get(a.params), jax.device_get(b.params))
    assert _eq(jax.device_get(a.residual), jax.device_get(b.residual))


def test_ef_bias_contract_and_telescoping():
    """The stated EF math at codec level: decode(encode(.)) is BIASED
    for the topk contraction (E != g — here deterministic, so one draw
    shows it), while the telescoping identity holds exactly: the sum of
    applied estimates plus the in-flight residual equals the sum of the
    true gradients fed in."""
    g = jax.random.normal(jax.random.PRNGKey(0), (64, 32))
    codec = SvdCodec(rank=2, sample="topk")
    one = codec.decode(
        codec.encode(jax.random.PRNGKey(1), g), tuple(g.shape)
    )
    assert float(jnp.max(jnp.abs(one - g))) > 1e-3  # biased: not g
    e = jnp.zeros_like(g)
    applied = jnp.zeros_like(g)
    fed_total = jnp.zeros_like(g)
    for t in range(6):
        gt = jax.random.normal(jax.random.fold_in(
            jax.random.PRNGKey(2), t
        ), g.shape) * 0.1
        fed = gt + e
        d = codec.decode(
            codec.encode(jax.random.PRNGKey(3), fed), tuple(g.shape)
        )
        e = fed - d
        applied = applied + d
        fed_total = fed_total + gt
    np.testing.assert_allclose(
        np.asarray(applied + e), np.asarray(fed_total), rtol=1e-4,
        atol=1e-5,
    )
    # bounded, not compounding: the residual stays the size of one
    # step's compression error, far below the accumulated gradient mass
    assert float(jnp.linalg.norm(e)) < float(jnp.linalg.norm(fed_total))


@pytest.mark.slow
def test_ef_kill_restart_resume_bit_exact(tmp_path):
    """The EF residual rides checkpoints: run to 4 with saves, resume to
    6 — final params bit-identical to the uninterrupted run (the
    ISSUE-15 EF carry drill)."""
    from atomo_tpu.parallel import distributed_train_loop

    mesh = _mesh4()
    model = get_model("lenet", 10)
    opt = make_optimizer("sgd", lr=0.05, momentum=0.9)

    def make_iter():
        return BatchIterator(
            synthetic_dataset(SPECS["mnist"], True, size=64), 16, seed=0
        )

    oracle = distributed_train_loop(
        model, opt, mesh, make_iter(), codec=TOPK, aggregate="gather",
        error_feedback=True, max_steps=6, log_every=0, eval_freq=0,
        seed=0,
    )
    assert isinstance(oracle, EfState)
    distributed_train_loop(
        model, opt, mesh, make_iter(), codec=TOPK, aggregate="gather",
        error_feedback=True, max_steps=4, log_every=0, eval_freq=0,
        seed=0, train_dir=str(tmp_path), save_freq=2,
    )
    logs = []
    resumed = distributed_train_loop(
        model, opt, mesh, make_iter(), codec=TOPK, aggregate="gather",
        error_feedback=True, max_steps=6, log_every=0, eval_freq=0,
        seed=0, train_dir=str(tmp_path), resume=True, log_fn=logs.append,
    )
    assert any("Resumed" in l and "step 4" in l for l in logs), logs
    assert _eq(
        jax.device_get(resumed.params), jax.device_get(oracle.params)
    )
    assert _eq(
        jax.device_get(resumed.residual), jax.device_get(oracle.residual)
    )


@pytest.mark.slow
def test_ef_resume_of_plain_checkpoint_rezeros_residual(tmp_path, recwarn):
    from atomo_tpu.parallel import distributed_train_loop

    mesh = _mesh4()
    model = get_model("lenet", 10)
    opt = make_optimizer("sgd", lr=0.05, momentum=0.9)

    def make_iter():
        return BatchIterator(
            synthetic_dataset(SPECS["mnist"], True, size=64), 16, seed=0
        )

    distributed_train_loop(
        model, opt, mesh, make_iter(), codec=TOPK, aggregate="gather",
        max_steps=2, log_every=0, eval_freq=0, seed=0,
        train_dir=str(tmp_path), save_freq=2,
    )
    resumed = distributed_train_loop(
        model, opt, mesh, make_iter(), codec=TOPK, aggregate="gather",
        error_feedback=True, max_steps=4, log_every=0, eval_freq=0,
        seed=0, train_dir=str(tmp_path), resume=True,
    )
    assert isinstance(resumed, EfState)
    assert any(
        "no residual carry" in str(w.message) for w in recwarn.list
    )


def test_ef_builder_conflict_matrix():
    mesh = _mesh4()
    model = get_model("lenet", 10)
    opt = make_optimizer("sgd", lr=0.05)
    from atomo_tpu.training import GuardConfig

    with pytest.raises(ValueError, match="dense training has no residual"):
        make_distributed_train_step(
            model, opt, mesh, None, error_feedback=True
        )
    with pytest.raises(ValueError, match="delayed"):
        make_distributed_train_step(
            model, opt, mesh, TOPK, aggregate="gather",
            overlap="delayed", error_feedback=True,
        )
    with pytest.raises(ValueError, match="guard"):
        make_distributed_train_step(
            model, opt, mesh, TOPK, aggregate="gather",
            guard=GuardConfig(), error_feedback=True,
        )
    with pytest.raises(ValueError, match="num_aggregate"):
        make_distributed_train_step(
            model, opt, mesh, TOPK, aggregate="gather",
            num_aggregate=2, error_feedback=True,
        )


def test_cli_preflight_rejects():
    from atomo_tpu.cli import _argv_preflight, build_parser

    parser = build_parser()

    def pf(argv):
        args = parser.parse_args(["train"] + argv)
        _argv_preflight(args)

    # budget conflicts
    with pytest.raises(SystemExit, match="budget-bytes"):
        pf(["--budget-bytes", "1000"])
    # qsgd bit allocation is a STATED law (B/(2^b-1)^2) — accepted now;
    # terngrad's max-norm scale + sigma clip is not, and stays rejected
    pf(["--budget-alloc", "variance", "--code", "qsgd"])
    with pytest.raises(SystemExit, match="terngrad"):
        pf(["--budget-alloc", "variance", "--code", "terngrad"])
    with pytest.raises(SystemExit, match="fixed_k"):
        pf(["--budget-alloc", "variance", "--code", "svd",
            "--sample", "topk"])
    with pytest.raises(SystemExit, match="no budget to allocate"):
        pf(["--budget-alloc", "variance", "--code", "sgd"])
    with pytest.raises(SystemExit, match="on-diverge"):
        pf(["--budget-alloc", "variance", "--code", "svd",
            "--obs-quality", "--obs-record", "--train-dir", "/tmp/x",
            "--on-diverge", "skip", "--save-freq", "2"])
    # error-feedback conflicts
    with pytest.raises(SystemExit, match="has none"):
        pf(["--error-feedback", "--code", "sgd"])
    with pytest.raises(SystemExit, match="multi-device"):
        pf(["--error-feedback", "--code", "svd", "--n-devices", "1"])
    with pytest.raises(SystemExit, match="delayed"):
        pf(["--error-feedback", "--code", "svd", "--n-devices", "4",
            "--overlap", "delayed", "--aggregate", "gather"])
    with pytest.raises(SystemExit, match="guard"):
        pf(["--error-feedback", "--code", "svd", "--n-devices", "4",
            "--grad-guard"])
    # EF x autopilot is now a probed composition (the tuner narrows its
    # space to the EF-compatible candidates) — accepted, not rejected
    pf(["--error-feedback", "--code", "svd", "--sample", "topk",
        "--n-devices", "4", "--auto", "tune", "--train-dir", "/tmp/x"])
    # the contraction-pairing warning, not a reject
    with pytest.warns(UserWarning, match="CONTRACTION"):
        pf(["--error-feedback", "--code", "svd", "--n-devices", "4"])


def test_pack_kernel_default_consults_decision_record(monkeypatch):
    """The use_pallas precedent as a mechanism (ISSUE-15 satellite):
    pack_kernel=None is the jnp oracle everywhere today (no measured win
    on record), flips default-ON exactly when a TPU device kind gains a
    recorded win, and never flips off-TPU."""
    from atomo_tpu.codecs import QsgdCodec
    from atomo_tpu.ops import qsgd_kernels as qk

    assert qk.pack_kernel_default() is False  # CPU suite: always jnp
    assert QsgdCodec(bits=2)._pack_kernel() is False
    assert QsgdCodec(bits=2, pack_kernel=True)._pack_kernel() is True
    # a recorded win flips the default on matching TPU hardware...
    monkeypatch.setitem(
        qk.PACK_KERNEL_MEASURED_WINS, "v5e",
        {"win": True, "evidence": "synthetic-test-entry"},
    )
    monkeypatch.setattr(qk, "is_tpu", lambda: True)

    class FakeDev:
        device_kind = "TPU v5e"

    monkeypatch.setattr(
        qk.jax, "devices", lambda *a, **k: [FakeDev()]
    )
    assert qk.pack_kernel_default() is True
    # ...but never on a kind without a recorded win
    FakeDev.device_kind = "TPU v4"
    assert qk.pack_kernel_default() is False
    # and never off-TPU, win or no win (the automatic jnp fallback)
    monkeypatch.setattr(qk, "is_tpu", lambda: False)
    FakeDev.device_kind = "TPU v5e"
    assert qk.pack_kernel_default() is False


# ------------------------------------------------- qsgd bit allocation
# The second water-filling target (same solver, different law): the
# knob is the leaf's bit width b, the stated law is E q_err2 =
# B_l / (2^b - 1)^2 with B_l = (1/6) sum_buckets n_b s_b^2, and the
# pricing is the codec's own analytic leaf_payload_bytes.


def test_qsgd_analytic_payload_matches_executed_across_knobs():
    from atomo_tpu.codecs import QsgdCodec

    grads = _grad_tree()
    leaves = jax.tree_util.tree_leaves(grads)
    for bits in (1, 2, 4, 8, 16):
        for bucket in (64, 512):
            qc = QsgdCodec(bits=bits, bucket_size=bucket)
            _, stats = encode_tree(qc, jax.random.PRNGKey(0), grads)
            assert stats.payload_bytes == sum(
                qc.leaf_payload_bytes(tuple(l.shape)) for l in leaves
            ), (bits, bucket)


def test_qsgd_bit_allocation_wire_match_predicted_equals_executed():
    from atomo_tpu.budget.allocator import MAX_BITS
    from atomo_tpu.codecs import QsgdCodec

    qc = QsgdCodec(bits=4, bucket_size=256)
    grads = _grad_tree()
    spectra = measure_spectra(qc, grads)
    alloc = solve_allocation(qc, spectra, mode="variance")
    assert all(1 <= b <= MAX_BITS for b in alloc.ks)
    wrapped = budgeted_codec(qc, alloc.ks)
    _, stats = encode_tree(wrapped, jax.random.PRNGKey(0), grads)
    assert stats.payload_bytes == alloc.payload_bytes
    # the per-leaf pairs the +ab candidates price with sum to the same
    assert sum(p for _, p in allocation_leaf_budgets(
        qc, spectra, alloc.ks
    )) == alloc.payload_bytes
    # and the recorded prediction is the stated bit law at those knobs
    from atomo_tpu.budget import predicted_variance

    assert alloc.predicted_variance == pytest.approx(
        predicted_variance(spectra, alloc.ks, codec=qc)
    )


def test_qsgd_uniform_point_is_configured_bits_byte_for_byte():
    from atomo_tpu.codecs import QsgdCodec

    qc = QsgdCodec(bits=2, bucket_size=512)
    grads = _grad_tree()
    spectra = measure_spectra(qc, grads)
    assert uniform_ks(spectra) == (2, 2, 2, 2)
    wrapped = budgeted_codec(qc, uniform_ks(spectra))
    key = jax.random.PRNGKey(11)
    p0, s0 = encode_tree(qc, key, grads)
    p1, s1 = encode_tree(wrapped, key, grads)
    assert s0.payload_bytes == s1.payload_bytes
    assert _eq(p0, p1)
    assert _eq(decode_tree(qc, p0, grads), decode_tree(wrapped, p1, grads))


def test_qsgd_bit_solver_pure_and_monotone():
    from atomo_tpu.codecs import QsgdCodec

    qc = QsgdCodec(bits=4, bucket_size=256)
    spectra = measure_spectra(qc, _grad_tree())
    a1 = solve_allocation(qc, spectra, mode="variance")
    a2 = solve_allocation(qc, spectra, mode="variance")
    assert a1 == a2
    uni = solve_allocation(qc, spectra, mode="uniform")
    rich = solve_allocation(
        qc, spectra, budget_bytes=uni.payload_bytes * 2, mode="variance"
    )
    assert rich.predicted_variance <= uni.predicted_variance + 1e-9
    tight = solve_allocation(
        qc, spectra, budget_bytes=uni.payload_bytes * 3 // 4,
        mode="variance",
    )
    assert tight.payload_bytes <= uni.payload_bytes * 3 // 4


def test_qsgd_terngrad_scheme_refused():
    from atomo_tpu.codecs import QsgdCodec

    tern = QsgdCodec(bits=1, scheme="terngrad")
    with pytest.raises(ValueError, match="terngrad"):
        measure_spectra(tern, _grad_tree())
