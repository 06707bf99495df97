"""Backward-interleaved layer-streamed encode (PR-10, ``--stream-encode``).

Contracts being pinned (parallel/common.plan_layer_buckets,
codecs/base.encode_tree_streamed, parallel/replicated's stream_encode
knob, utils/comm_model's pipeline accounting):

  * The bucket plan is deterministic, reverse-topological, size-bounded,
    and covers every leaf exactly once — a pure function of leaf shapes.
  * The plan is a LAYOUT knob, never a semantics knob: per-leaf codec
    keys fold from the GLOBAL leaf index, so streamed payloads are
    bit-identical to the monolithic encode for ANY bucket size, per
    codec — and the fused streamed program equals the eager per-bucket
    oracle (each bucket encoded standalone in its own jitted program,
    results concatenated) bit-for-bit.
  * ``stream_encode=False`` IS the prior program byte-for-byte (lowered
    HLO text identical to a default-args build).
  * Full trajectories are bit-identical across {off, any bucket size}
    for gather and ring, composing with superstep / ZeRO-1 / guard+chaos
    / delayed overlap / num_aggregate.
  * The per-bucket ring (_ring_stream_mean_layered) keeps the PR-3
    aggregation-operator contract: bit-identical to gather's canonical
    (unfused) decode order.
  * The conflict matrix rejects stream x {dense, psum, hierarchical,
    plan, single-device} with the stated reasons.
  * comm_model: exposed encode becomes the pipeline tail
    (stream_exposed_encode_s), overlap_report states it, +se candidates
    enter the autopilot space with a reduced predicted encode term.
  * The Pallas bucketed pack/unpack kernels behind the bucket boundary
    are bit-identical to the jnp pack_bucketed/unpack_bucketed oracle
    (interpreter mode), and the codec's pack_kernel wiring produces the
    same wire bytes either way.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from atomo_tpu.codecs import (
    DenseCodec,
    QsgdCodec,
    SvdCodec,
    decode_mean_tree,
    encode_leaf_subset,
    encode_tree,
    encode_tree_streamed,
    terngrad,
)
from atomo_tpu.models import get_model
from atomo_tpu.parallel import (
    init_delayed_state,
    make_distributed_train_step,
    make_mesh,
    replicate_state,
    shard_batch,
    shard_superbatch,
)
from atomo_tpu.parallel.common import plan_layer_buckets
from atomo_tpu.training import (
    GuardConfig,
    create_state,
    make_optimizer,
    snapshot_state,
)
from atomo_tpu.utils.chaos import ChaosConfig, ChaosInjector

QSGD = QsgdCodec(bits=4, bucket_size=128)

CODECS = {
    "qsgd": QSGD,
    "terngrad": terngrad(bucket_size=128),
    "svd": SvdCodec(rank=3),
    "svd_budget": SvdCodec(rank=2, sample="bernoulli_budget"),
    "dense": DenseCodec(),
}


def _setup(n_dev=2, batch=8):
    mesh = make_mesh(n_dev)
    model = get_model("lenet", 10)
    opt = make_optimizer("sgd", lr=0.01, momentum=0.9)
    r = np.random.default_rng(0)
    batches = [
        (r.standard_normal((batch, 28, 28, 1)).astype(np.float32),
         r.integers(0, 10, batch).astype(np.int32))
        for _ in range(3)
    ]
    host0 = snapshot_state(
        create_state(model, opt, jax.random.PRNGKey(0),
                     jnp.asarray(batches[0][0]))
    )
    return mesh, model, opt, host0, batches


def _fresh(mesh, host0):
    return replicate_state(mesh, jax.tree_util.tree_map(jnp.asarray, host0))


def _eq(a, b):
    return all(
        np.array_equal(np.asarray(x), np.asarray(y))
        for x, y in zip(jax.tree_util.tree_leaves(a),
                        jax.tree_util.tree_leaves(b))
    )


def _grads_like(params, seed=3):
    return jax.tree_util.tree_map(
        lambda a: jax.random.normal(
            jax.random.PRNGKey(seed), a.shape, jnp.float32
        ),
        params,
    )


def _run(step, st, batches, mesh, key, n=3):
    m = None
    for im, lb in batches[:n]:
        si, sl = shard_batch(mesh, im, lb)
        st, m = step(st, key, si, sl)
    return jax.device_get(st), jax.device_get(m)


# ------------------------------------------------------------ bucket plan


def test_plan_is_deterministic_reverse_topological_and_covers():
    _, model, opt, host0, _ = _setup()
    grads = _grads_like(host0.params)
    leaves = jax.tree_util.tree_leaves(grads)
    for bb in (0, 1 << 12, 1 << 16, 1 << 30):
        p1 = plan_layer_buckets(grads, bb)
        p2 = plan_layer_buckets(grads, bb)
        assert p1 == p2  # pure function of shapes
        flat = [i for bucket in p1.buckets for i in bucket]
        assert sorted(flat) == list(range(len(leaves)))  # exactly once
        # reverse-topological: bucket 0 holds the LAST leaves (backward's
        # first-finished gradients); indices never increase across walk
        assert flat == sorted(flat, reverse=True)
        if bb > 0:
            for bucket in p1.buckets:
                nb = sum(
                    int(leaves[i].size) * leaves[i].dtype.itemsize
                    for i in bucket
                )
                # size bound, except a single oversized leaf
                assert nb <= bb or len(bucket) == 1
    assert plan_layer_buckets(grads, 0).n_buckets == 1
    # one bucket per leaf at a tiny bound
    assert plan_layer_buckets(grads, 1).n_buckets == len(leaves)


# -------------------------------------------- operator-level bit parity


@pytest.mark.parametrize(
    "name",
    [
        # terngrad/svd_budget/svd re-prove the same bucket-split parity
        # over pricier encoders (~39 s on 1 core) — full-suite only (same
        # split test_ring_operator_bit_identical_to_gather uses); qsgd
        # keeps the parity witnessed in the smoke set
        pytest.param(n, marks=pytest.mark.slow)
        if n in ("terngrad", "svd_budget", "svd")
        else n
        for n in sorted(CODECS)
    ],
)
def test_streamed_encode_bit_equals_monolithic_any_bucket_size(name):
    """Partition invariance at the operator level: the plan never changes
    a single payload bit, per codec, for any bucket size."""
    _, model, opt, host0, _ = _setup()
    codec = CODECS[name]
    grads = _grads_like(host0.params)
    key = jax.random.PRNGKey(7)
    mono = jax.jit(lambda g: encode_tree(codec, key, g)[0])(grads)
    for bb in (0, 1 << 12, 1 << 16):
        plan = plan_layer_buckets(grads, bb)
        stream = jax.jit(
            lambda g, plan=plan: encode_tree_streamed(codec, key, g, plan)[0]
        )(grads)
        assert _eq(mono, stream), (name, bb)


@pytest.mark.parametrize(
    "name",
    ["qsgd", pytest.param("svd", marks=pytest.mark.slow)],
)
def test_fused_streamed_program_bit_equals_eager_bucket_oracle(name):
    """The PR acceptance oracle: encode each bucket STANDALONE (its own
    jitted program), concatenate — bit-equal to the one fused streamed
    program (and therefore to the monolithic encode)."""
    _, model, opt, host0, _ = _setup()
    codec = CODECS[name]
    grads = _grads_like(host0.params)
    leaves, treedef = jax.tree_util.tree_flatten(grads)
    key = jax.random.PRNGKey(7)
    plan = plan_layer_buckets(grads, 1 << 12)
    assert plan.n_buckets > 1
    fused = jax.jit(
        lambda g: encode_tree_streamed(codec, key, g, plan)[0]
    )(grads)
    eager = [None] * plan.n_leaves
    for idxs in plan.buckets:
        prog = jax.jit(
            lambda g, idxs=idxs: encode_leaf_subset(
                codec, key, jax.tree_util.tree_flatten(g)[0], list(idxs)
            )
        )
        for j, p in zip(idxs, prog(grads)):
            eager[j] = p
    assert _eq(fused, jax.tree_util.tree_unflatten(treedef, eager))


def test_streamed_plan_rejects_mismatched_tree():
    _, model, opt, host0, _ = _setup()
    grads = _grads_like(host0.params)
    plan = plan_layer_buckets({"a": jnp.zeros((3,))}, 0)
    with pytest.raises(ValueError, match="same structure"):
        encode_tree_streamed(QSGD, jax.random.PRNGKey(0), grads, plan)


# ------------------------------------------------- off-mode byte identity


def test_stream_off_is_byte_identical_to_default_build():
    mesh, model, opt, host0, batches = _setup()
    key = jax.random.PRNGKey(1)
    si, sl = shard_batch(mesh, *batches[0])
    s_def = make_distributed_train_step(model, opt, mesh, QSGD,
                                        aggregate="ring")
    s_off = make_distributed_train_step(model, opt, mesh, QSGD,
                                        aggregate="ring",
                                        stream_encode=False,
                                        stream_bucket_bytes=123)
    st = _fresh(mesh, host0)
    a = s_def.lower(st, key, si, sl).as_text()
    b = s_off.lower(st, key, si, sl).as_text()
    assert a == b  # the frozen-program contract, literally byte-for-byte


# --------------------------------------------- trajectory-level parity


@pytest.mark.parametrize(
    "agg",
    ["gather", pytest.param("ring", marks=pytest.mark.slow)],
)
def test_streamed_trajectory_bit_identical_for_any_bucket_size(agg):
    """The acceptance criterion: off and every streamed bucket size give
    bit-identical params after a multi-step trajectory."""
    mesh, model, opt, host0, batches = _setup()
    key = jax.random.PRNGKey(1)
    off = make_distributed_train_step(model, opt, mesh, QSGD, aggregate=agg)
    ref, _ = _run(off, _fresh(mesh, host0), batches, mesh, key)
    for bb in (0, 1 << 12, 1 << 16):
        on = make_distributed_train_step(
            model, opt, mesh, QSGD, aggregate=agg,
            stream_encode=True, stream_bucket_bytes=bb,
        )
        got, m = _run(on, _fresh(mesh, host0), batches, mesh, key)
        assert _eq(ref.params, got.params), (agg, bb)
        assert _eq(ref.opt_state, got.opt_state), (agg, bb)
        assert np.isfinite(float(m["loss"]))


def test_streamed_ring_operator_matches_gather_canonical_decode():
    """The PR-3 contract extended: the per-bucket layered ring is
    bit-identical to gather's canonical (unfused) decode-mean over the
    same per-chip payloads."""
    from jax.sharding import PartitionSpec as P

    from atomo_tpu.parallel.replicated import _ring_stream_mean_layered

    n_dev = 4
    mesh, model, opt, host0, _ = _setup(n_dev=n_dev)
    codec = SvdCodec(rank=2)  # the codec whose fused path reassociates
    grads = _grads_like(host0.params)
    key = jax.random.PRNGKey(5)
    plan = plan_layer_buckets(grads, 1 << 12)
    assert plan.n_buckets > 1

    def sm(fn, in_specs, out_specs):
        return jax.jit(jax.shard_map(
            fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
            check_vma=False,
        ))

    def enc(g):
        my = jax.lax.axis_index("dp")
        p, _ = encode_tree(codec, jax.random.fold_in(key, my), g)
        return jax.tree_util.tree_map(lambda a: a[None], p)

    payloads_x = sm(enc, (P(),), P("dp"))(grads)
    gathered = sm(
        lambda px: jax.lax.all_gather(
            jax.tree_util.tree_map(lambda a: a[0], px), "dp"
        ),
        (P("dp"),), P(),
    )(payloads_x)
    mean_g = sm(
        lambda gth: decode_mean_tree(codec, gth, grads, n_dev, fused=False),
        (P(),), P(),
    )(gathered)

    def ring_layered(px):
        my = jax.lax.axis_index("dp")
        local = jax.tree_util.tree_map(lambda a: a[0], px)
        mean, _ = _ring_stream_mean_layered(
            codec, local, grads, plan, axis="dp", n_dev=n_dev, my=my,
            n_contrib=n_dev, bucket_size=65536,
        )
        return mean

    mean_r = sm(ring_layered, (P("dp"),), P())(payloads_x)
    assert _eq(jax.device_get(mean_g), jax.device_get(mean_r))


# ------------------------------------------------------------ composition


@pytest.mark.slow  # ~11 s of scan-family compiles on 1 core — full-suite
# only; the operator- and trajectory-level parities above keep stream
# coverage in the smoke set
def test_streamed_superstep_matches_off_within_scan_family():
    """stream x superstep: within the scan family (the PR-2 contract's
    bitwise domain — scan-vs-standalone is the documented fusion-drift
    class), the streamed K-block bit-matches the off-mode K-block for
    any bucket size."""
    mesh, model, opt, host0, batches = _setup()
    key = jax.random.PRNGKey(1)
    im = np.stack([batches[0][0], batches[1][0]])
    lb = np.stack([batches[0][1], batches[1][1]])
    bi, bl = shard_superbatch(mesh, im, lb)
    off = make_distributed_train_step(model, opt, mesh, QSGD,
                                      aggregate="ring", superstep=2)
    ref, _ = off(_fresh(mesh, host0), key, bi, bl)
    ref = jax.device_get(ref)
    for bb in (0, 1 << 12):
        on = make_distributed_train_step(
            model, opt, mesh, QSGD, aggregate="ring", superstep=2,
            stream_encode=True, stream_bucket_bytes=bb,
        )
        got, _ = on(_fresh(mesh, host0), key, bi, bl)
        got = jax.device_get(got)
        assert _eq(ref.params, got.params), bb


@pytest.mark.slow  # ~18 s on 1 core — full-suite only; guard x stream
# parity is also held by the chaos drills in test_resilience
def test_streamed_guard_chaos_matches_off():
    """stream x guard x chaos: a spiked replica is masked identically —
    per-bucket ok rotation changes no verdict and no bit."""
    mesh, model, opt, host0, batches = _setup()
    key = jax.random.PRNGKey(1)
    guard = GuardConfig(max_grad_norm=0.0)

    def chaos():
        return ChaosInjector(ChaosConfig.from_spec("nan@2:0"))

    for agg in ("gather", "ring"):
        off = make_distributed_train_step(
            model, opt, mesh, QSGD, aggregate=agg, guard=guard,
            chaos=chaos(),
        )
        on = make_distributed_train_step(
            model, opt, mesh, QSGD, aggregate=agg, guard=guard,
            chaos=chaos(), stream_encode=True, stream_bucket_bytes=1 << 12,
        )
        a, ma = _run(off, _fresh(mesh, host0), batches, mesh, key)
        b, mb = _run(on, _fresh(mesh, host0), batches, mesh, key)
        assert _eq(a.params, b.params), agg
        assert float(ma["dropped"]) == float(mb["dropped"])


@pytest.mark.slow  # ~14 s on 1 core — full-suite only; zero1 is superseded
# by --partition sharded-update (PR 14), whose stream parity stays in tier-1
def test_streamed_zero1_num_aggregate_match_off():
    from atomo_tpu.parallel.replicated import zero1_state

    mesh, model, opt, host0, batches = _setup()
    key = jax.random.PRNGKey(1)
    # zero1
    z0, specs = zero1_state(mesh, _fresh(mesh, host0), opt)
    off = make_distributed_train_step(model, opt, mesh, QSGD,
                                      aggregate="ring", zero1_specs=specs)
    a, _ = _run(off, z0, batches, mesh, key)
    z1, specs1 = zero1_state(mesh, _fresh(mesh, host0), opt)
    on = make_distributed_train_step(
        model, opt, mesh, QSGD, aggregate="ring", zero1_specs=specs1,
        stream_encode=True, stream_bucket_bytes=1 << 12,
    )
    b, _ = _run(on, z1, batches, mesh, key)
    assert _eq(a.params, b.params)
    # num_aggregate subset rotation
    off = make_distributed_train_step(model, opt, mesh, QSGD,
                                      aggregate="gather", num_aggregate=1)
    on = make_distributed_train_step(
        model, opt, mesh, QSGD, aggregate="gather", num_aggregate=1,
        stream_encode=True, stream_bucket_bytes=1 << 12,
    )
    a, _ = _run(off, _fresh(mesh, host0), batches, mesh, key)
    b, _ = _run(on, _fresh(mesh, host0), batches, mesh, key)
    assert _eq(a.params, b.params)


@pytest.mark.parametrize(
    "agg",
    ["gather", pytest.param("ring", marks=pytest.mark.slow)],
)
def test_streamed_delayed_overlap_matches_off(agg):
    """stream x delayed: the produce-side encode streams; trajectories
    bit-match the monolithic delayed program (skipped step 0 included)."""
    mesh, model, opt, host0, batches = _setup()
    key = jax.random.PRNGKey(1)
    off = make_distributed_train_step(model, opt, mesh, QSGD,
                                      aggregate=agg, overlap="delayed")
    on = make_distributed_train_step(
        model, opt, mesh, QSGD, aggregate=agg, overlap="delayed",
        stream_encode=True, stream_bucket_bytes=1 << 12,
    )
    a, ma = _run(off, init_delayed_state(mesh, _fresh(mesh, host0), QSGD),
                 batches, mesh, key)
    b, mb = _run(on, init_delayed_state(mesh, _fresh(mesh, host0), QSGD),
                 batches, mesh, key)
    assert _eq(a.train.params, b.train.params)
    assert _eq(a.carry.payload, b.carry.payload)
    assert float(ma["skipped"]) == float(mb["skipped"])


# --------------------------------------------------------- conflict matrix


def test_builder_rejects_stream_without_codec_or_flat_compressed():
    mesh, model, opt, host0, _ = _setup()
    with pytest.raises(ValueError, match="stream_encode"):
        make_distributed_train_step(model, opt, mesh, None,
                                    stream_encode=True)
    with pytest.raises(ValueError, match="stream_encode"):
        make_distributed_train_step(model, opt, mesh, QSGD,
                                    aggregate="psum", stream_encode=True)


def test_builder_rejects_stream_hierarchical():
    mesh2 = make_mesh(4, axes=(("dp", 2), ("ici", 2)))
    model = get_model("lenet", 10)
    opt = make_optimizer("sgd", lr=0.01, momentum=0.9)
    with pytest.raises(ValueError, match="bucket-aware"):
        make_distributed_train_step(
            model, opt, mesh2, QSGD, aggregate="hierarchical",
            inner_axis="ici", stream_encode=True,
        )


def test_preflight_conflict_matrix():
    from atomo_tpu.cli import _argv_preflight, build_parser

    p = build_parser()
    train = p._subparsers._group_actions[0].choices["train"]
    # the good config passes
    _argv_preflight(train.parse_args(
        ["--stream-encode", "on", "--code", "qsgd", "--n-devices", "4",
         "--aggregate", "ring"]
    ))
    rejects = [
        (["--stream-encode", "on", "--code", "sgd", "--n-devices", "4"],
         "compressing"),
        (["--stream-encode", "on", "--code", "qsgd", "--n-devices", "1"],
         "multi-device"),
        (["--stream-encode", "on", "--code", "qsgd", "--n-devices", "4",
          "--aggregate", "psum"], "psum"),
        (["--stream-encode", "on", "--code", "qsgd", "--n-devices", "4",
          "--aggregate", "hierarchical"], "bucket-aware"),
        (["--stream-encode", "on", "--code", "qsgd", "--n-devices", "4",
          "--aggregate", "hierarchical", "--plan", "legacy"],
         "bucket-aware"),
        (["--stream-encode", "on", "--code", "qsgd", "--n-devices", "4",
          "--auto", "tune", "--train-dir", "/tmp/x"], "pinned"),
    ]
    for argv, frag in rejects:
        with pytest.raises(SystemExit) as ei:
            _argv_preflight(train.parse_args(argv))
        assert frag in str(ei.value), (argv, str(ei.value))


def test_svd_mode_alias_maps_and_conflicts():
    from atomo_tpu.cli import _build_common, build_parser

    p = build_parser()
    train = p._subparsers._group_actions[0].choices["train"]
    args = train.parse_args(
        ["--synthetic", "--dataset", "mnist", "--network", "lenet",
         "--code", "svd", "--svd-rank", "2", "--svd-mode", "randomized"]
    )
    _, _, codec, _, _, _ = _build_common(args)
    assert codec.algorithm == "randomized"
    args = train.parse_args(
        ["--synthetic", "--dataset", "mnist", "--network", "lenet",
         "--code", "svd", "--svd-rank", "2", "--svd-mode", "randomized",
         "--svd-algo", "exact"]
    )
    with pytest.raises(SystemExit, match="disagree"):
        _build_common(args)


@pytest.mark.slow  # ~11 s of randomized-SVD compiles on 1 core —
# full-suite only
def test_svd_randomized_mode_streams_bit_identically():
    """The satellite pair: --svd-mode randomized under streamed encode —
    the sketched estimator follows the same global-leaf-key contract."""
    mesh, model, opt, host0, batches = _setup()
    key = jax.random.PRNGKey(1)
    codec = SvdCodec(rank=2, algorithm="randomized")
    off = make_distributed_train_step(model, opt, mesh, codec,
                                      aggregate="gather")
    on = make_distributed_train_step(
        model, opt, mesh, codec, aggregate="gather",
        stream_encode=True, stream_bucket_bytes=1 << 12,
    )
    a, _ = _run(off, _fresh(mesh, host0), batches, mesh, key, n=2)
    b, _ = _run(on, _fresh(mesh, host0), batches, mesh, key, n=2)
    assert _eq(a.params, b.params)


# ------------------------------------------------------------- comm model


def test_comm_model_stream_pipeline_accounting():
    from atomo_tpu.utils.comm_model import (
        overlap_report,
        stream_bucket_count,
        stream_exposed_encode_s,
    )

    assert stream_exposed_encode_s(0.010, 5) == pytest.approx(0.002)
    assert stream_exposed_encode_s(0.010, 1) == pytest.approx(0.010)
    assert stream_bucket_count(10e6, 4e6) == 3
    assert stream_bucket_count(10e6, 0) == 1
    base = dict(dense_bytes=44.7e6, payload_bytes=1e6, ways=8,
                fabric_bw=6.25e9, compute_s=6.5e-3)
    r_off = overlap_report(**base, encode_s=2e-3)
    r_on = overlap_report(**base, encode_s=2e-3, stream_encode=True,
                          stream_buckets=4)
    assert r_off["encode_exposed_ms"] == pytest.approx(2.0)
    assert r_on["encode_exposed_ms"] == pytest.approx(0.5)
    assert r_on["encode_hidden_ms"] == pytest.approx(1.5)
    assert r_on["delayed_step_ms"] < r_off["delayed_step_ms"]
    # default args keep the historical report shape (encode absent = 0)
    r_legacy = overlap_report(**base)
    assert r_legacy["encode_ms"] == 0.0
    assert r_legacy["blocking_step_ms"] == pytest.approx(
        r_legacy["compute_ms"] + r_legacy["comm_chain_ms"], abs=0.01
    )


def test_enumerate_candidates_stream_variants_and_prediction():
    from atomo_tpu.utils.comm_model import (
        enumerate_candidates,
        predict_step_s,
    )

    base = enumerate_candidates(has_codec=True, ways=4)
    withse = enumerate_candidates(has_codec=True, ways=4, allow_stream=True)
    names = {c["name"] for c in withse}
    assert {c["name"] for c in base} < names
    assert any("+se+" in n for n in names)
    off = {"aggregate": "gather", "overlap": "off", "superstep": 1}
    on = {**off, "stream_encode": "on", "stream_bucket_bytes": 4 << 20}
    kw = dict(dense_bytes=44.7e6, payload_bytes=1e6, ways=4,
              fabric_bw=6.25e9, tax_s=4e-3)
    # streamed encode's predicted step strictly drops (the encode tail)
    assert predict_step_s(on, **kw) < predict_step_s(off, **kw)
    # the REAL plan's bucket count (stream_buckets) beats the byte-ratio
    # estimate: a 1-bucket real plan predicts NO hiding — exactly off's
    # step — where the ~12-bucket byte estimate would promise most of it
    honest = {**on, "stream_buckets": 1}
    assert predict_step_s(honest, **kw) == pytest.approx(
        predict_step_s(off, **kw)
    )
    assert predict_step_s(honest, **kw) > predict_step_s(on, **kw)
    # and enumerate attaches it when the caller supplies the real count
    attached = enumerate_candidates(
        has_codec=True, ways=4, allow_stream=True, stream_buckets=3
    )
    assert all(
        c.get("stream_buckets") == 3
        for c in attached if c.get("stream_encode") == "on"
    )


def test_winner_knobs_carry_stream_fields():
    from atomo_tpu.tuning.autopilot import winner_knobs

    row = {"aggregate": "ring", "overlap": "off", "superstep": 1,
           "stream_encode": "on", "stream_bucket_bytes": 1 << 20,
           "name": "x", "probed": True}
    k = winner_knobs(row)
    assert k["stream_encode"] == "on"
    assert k["stream_bucket_bytes"] == 1 << 20


# --------------------------------------------- pallas bucket-boundary pack


def test_pallas_pack_unpack_bucketed_matches_jnp_oracle():
    from atomo_tpu.codecs.qsgd import (
        pack_bucketed,
        padded_bucket,
        unpack_bucketed,
    )
    from atomo_tpu.ops.qsgd_kernels import (
        pallas_pack_bucketed,
        pallas_unpack_bucketed,
    )

    r = np.random.default_rng(0)
    for bits in (1, 2, 4, 8):
        for nb in (3, 9):
            bp = padded_bucket(128, bits)
            codes = jnp.asarray(
                r.integers(0, 1 << (bits + 1), (nb, bp)), jnp.uint32
            )
            w_j = pack_bucketed(codes, bits)
            w_p = pallas_pack_bucketed(codes, bits=bits, interpret=True)
            assert np.array_equal(np.asarray(w_j), np.asarray(w_p)), bits
            c_p = pallas_unpack_bucketed(w_j, bits=bits, interpret=True)
            assert np.array_equal(
                np.asarray(unpack_bucketed(w_j, bits)), np.asarray(c_p)
            ), bits


def test_qsgd_pack_kernel_wire_identical():
    """The codec's pack_kernel wiring: forced kernel vs jnp produce the
    same payload bits and decode identically (the default None = jnp — the
    use_pallas precedent: no kernel auto-selects without a measured
    hardware win — so auto == jnp everywhere)."""
    r = np.random.default_rng(1)
    g = jnp.asarray(r.standard_normal(3000), jnp.float32)
    key = jax.random.PRNGKey(2)
    jnp_c = QsgdCodec(bits=4, bucket_size=128, pack_kernel=False)
    ker_c = QsgdCodec(bits=4, bucket_size=128, pack_kernel=True)
    auto_c = QsgdCodec(bits=4, bucket_size=128)
    pa, pb, pc = (c.encode(key, g) for c in (jnp_c, ker_c, auto_c))
    assert np.array_equal(np.asarray(pa.words), np.asarray(pb.words))
    assert np.array_equal(np.asarray(pa.words), np.asarray(pc.words))
    assert np.array_equal(np.asarray(pa.scales), np.asarray(pb.scales))
    da = jnp_c.decode(pa, (3000,))
    db = ker_c.decode(pa, (3000,))
    assert np.array_equal(np.asarray(da), np.asarray(db))
