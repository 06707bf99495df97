"""Elastic world size (PR 9): membership epochs, shrink-and-continue,
deterministic re-admission.

Fast tier: membership records + log, the die@S:R chaos grammar, the
absence tracker's fold semantics, the surviving-roster mean's bit-parity
contract per codec (acceptance test c), the supervisor's membership
triage (no restart-budget charge), preflight rejects, the stale
tune-decision fix, and the guarded step's ok_bits metric.

Slow tier (subprocess drills, the acceptance criteria): (a) a die@S →
shrink run matches a fresh ``--n-devices N-1`` run resumed from the same
healthy checkpoint leaf-wise bit-exact; (b) shrink → re-grow completes
with membership epochs 0→1→2 recorded in order in incidents.jsonl and no
restart-budget slot consumed.
"""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from atomo_tpu.elastic import (
    AbsenceTracker,
    ElasticConfig,
    MembershipChange,
    MembershipEpoch,
    MembershipLog,
    apply_world_to_argv,
    membership_path,
    survivor_decode_mean,
)

_HERE = os.path.dirname(os.path.abspath(__file__))
_REPO_ROOT = os.path.dirname(_HERE)


# ---------------- membership records ----------------


def test_membership_epoch_roundtrip():
    rec = MembershipEpoch(
        epoch=1, world_size=3, roster=(0, 2, 3), start_step=4,
        reason="shrink", dead=(1,),
        shard_map={"kind": "contiguous", "batch_size": 12, "skip": 4},
    )
    back = MembershipEpoch.from_dict(json.loads(json.dumps(rec.to_dict())))
    assert back == rec


def test_membership_epoch_validates():
    with pytest.raises(ValueError, match="roster length"):
        MembershipEpoch(epoch=0, world_size=3, roster=(0, 1))
    with pytest.raises(ValueError, match=">= 1"):
        MembershipEpoch(epoch=0, world_size=0, roster=())


def test_membership_log_appends_atomically_and_reloads(tmp_path):
    d = str(tmp_path)
    log = MembershipLog.load(d)
    assert log.latest() is None and log.full_world == 0
    log.append(MembershipEpoch(epoch=0, world_size=4, roster=(0, 1, 2, 3)))
    log.append(
        MembershipEpoch(
            epoch=1, world_size=3, roster=(0, 2, 3), start_step=4,
            reason="shrink", dead=(1,),
        )
    )
    # contiguity: epochs are a strict counter, not free-form
    with pytest.raises(ValueError, match="contiguous"):
        log.append(MembershipEpoch(epoch=3, world_size=4, roster=(0, 1, 2, 3)))
    again = MembershipLog.load(d)
    assert [e.epoch for e in again.epochs] == [0, 1]
    assert again.full_world == 4  # the ORIGINAL world, not the latest
    assert again.latest().reason == "shrink"
    assert os.path.exists(membership_path(d))


def test_membership_log_tolerates_garbage_file(tmp_path):
    with open(membership_path(str(tmp_path)), "w") as f:
        f.write('{"torn')
    with pytest.warns(UserWarning, match="unreadable"):
        log = MembershipLog.load(str(tmp_path))
    assert log.latest() is None


def test_apply_world_to_argv():
    assert apply_world_to_argv(
        ["train", "--n-devices", "4", "--seed", "1"], 3
    ) == ["train", "--n-devices", "3", "--seed", "1"]
    assert apply_world_to_argv(["train", "--n-devices=4"], 3) == [
        "train", "--n-devices=3"
    ]
    # absent flag is appended: "all visible" must be pinned explicitly
    assert apply_world_to_argv(["train", "--seed", "1"], 3) == [
        "train", "--seed", "1", "--n-devices", "3"
    ]


# ---------------- die@S:R chaos grammar ----------------


def test_die_spec_parses_and_validates():
    from atomo_tpu.utils.chaos import ChaosConfig

    cfg = ChaosConfig.from_spec("die@3:1,nan@7")
    assert cfg.die_faults == ((3, 1),)
    assert cfg.enabled()
    assert ChaosConfig.from_spec("die@5").die_faults == ((5, 0),)
    with pytest.raises(ValueError, match="replica must be >= 0"):
        ChaosConfig.from_spec("die@3:-1")
    with pytest.raises(ValueError, match="bad chaos token"):
        ChaosConfig.from_spec("die@x")


def test_die_injection_is_persistent_epoch_keyed_and_generation_proof():
    from atomo_tpu.utils.chaos import ChaosConfig, ChaosInjector

    cfg = ChaosConfig.from_spec("die@3:1")
    inj = ChaosInjector(cfg, membership_epoch=0)
    g = {"w": jnp.ones((4,))}

    def hit(injector, step, replica):
        out = injector.inject_grads(g, jnp.int32(step), replica=jnp.int32(replica))
        return bool(np.any(~np.isfinite(np.asarray(out["w"]))))

    assert not hit(inj, 2, 1)  # before S
    assert hit(inj, 3, 1)  # from S...
    assert hit(inj, 9, 1)  # ...ONWARD (persistent, unlike nan@S)
    assert not hit(inj, 3, 0)  # only the targeted replica
    # survives doctor generation bumps (a dead host stays dead)
    assert hit(inj.with_generation(2), 5, 1)
    # disarmed past membership epoch 0 (the re-admitted member is healthy)
    assert not hit(ChaosInjector(cfg, membership_epoch=1), 5, 1)


def test_die_injector_reads_epoch_env(monkeypatch):
    from atomo_tpu.utils.chaos import ChaosConfig, ChaosInjector
    from atomo_tpu.utils.tracing import MEMBERSHIP_EPOCH_ENV

    monkeypatch.setenv(MEMBERSHIP_EPOCH_ENV, "2")
    inj = ChaosInjector(ChaosConfig.from_spec("die@1:0"))
    assert inj.membership_epoch == 2
    assert inj.with_generation(1).membership_epoch == 2


# ---------------- absence tracker ----------------


def test_absence_tracker_patience_and_flapping():
    t = AbsenceTracker(world_size=4, patience=3)
    full = 0b1111
    dead1 = 0b1101  # replica 1 absent
    assert t.observe(full) == set()
    assert t.observe(dead1) == set()
    assert t.observe(dead1) == set()
    assert t.observe(dead1) == {1}  # third consecutive miss
    assert t.observe(dead1) == set()  # reported once, stays pending upstream
    # a flapping replica (recovers before patience) never triggers
    t2 = AbsenceTracker(world_size=4, patience=3)
    for bits in (dead1, dead1, full, dead1, dead1, full):
        assert t2.observe(bits) == set()


def test_absence_tracker_partition_invariance():
    series = [15, 13, 13, 13, 5, 5, 5, 5]
    t_flat = AbsenceTracker(4, patience=3)
    flat = []
    for i, v in enumerate(series):
        flat += [(i, s) for s in sorted(t_flat.observe(v))]
    t_blocks = AbsenceTracker(4, patience=3)
    blocked = []
    base = 0
    for blk in (series[:3], series[3:4], series[4:]):
        blocked += [
            (base + i, s)
            for i, s in t_blocks.observe_series(np.asarray(blk))
        ]
        base += len(blk)
    # same events at the same absolute indices for ANY block partition
    assert flat == blocked == [(3, 1), (6, 3)]


# ---------------- acceptance (c): surviving-roster operator parity -----


@pytest.mark.parametrize(
    "name",
    ["qsgd", "terngrad", "svd", "svd_budget"],
)
def test_survivor_mean_bit_identical_to_surviving_roster_canonical(name):
    """The masked-absent-replica operator must be BIT-identical to the
    surviving-roster canonical mean — the roster-order fold over the
    survivors' per-replica decodes alone (what a genuinely shrunken
    world computes) — per codec, with the ring's staged form pinned to
    the same fold; and within the documented last-mantissa reassociation
    drift of the unpinned decode_mean_tree reduction."""
    from atomo_tpu.codecs import (
        QsgdCodec,
        SvdCodec,
        decode_mean_tree,
        decode_tree,
        encode_tree,
    )
    from atomo_tpu.elastic.shrink import roster_fold_sum

    codec = {
        "qsgd": QsgdCodec(bits=2, bucket_size=128),
        "terngrad": QsgdCodec(bits=1, bucket_size=128, scheme="terngrad"),
        "svd": SvdCodec(rank=2),
        "svd_budget": SvdCodec(rank=2, sample="bernoulli_budget"),
    }[name]
    key = jax.random.PRNGKey(7)
    tree = {
        "conv": jax.random.normal(jax.random.fold_in(key, 1), (6, 10)),
        "fc": jax.random.normal(jax.random.fold_in(key, 2), (12, 8)),
    }
    n, dead = 4, 1
    payloads = [
        encode_tree(codec, jax.random.fold_in(key, 100 + r), tree)[0]
        for r in range(n)
    ]
    gathered = jax.tree_util.tree_map(lambda *a: jnp.stack(a), *payloads)
    okg = jnp.asarray([1.0, 0.0, 1.0, 1.0])

    got = survivor_decode_mean(codec, gathered, okg, tree)

    # the canonical surviving-roster mean: per-replica decode of the
    # SURVIVORS alone, roster-order fold, one division — the (N-1)-row
    # operator the shrunken world runs
    decoded = [decode_tree(codec, p, tree) for p in payloads]
    want = jax.tree_util.tree_map(
        lambda *rows: roster_fold_sum(
            jnp.stack([r for i, r in enumerate(rows) if i != dead])
        ) / jnp.float32(n - 1),
        *decoded,
    )
    for g, w in zip(
        jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)
    ):
        assert np.array_equal(np.asarray(g), np.asarray(w)), name

    # the ring-staged form: flat rows at canonical source index, dead row
    # zeroed, the SAME pinned fold — bitwise equal to the survivors-only
    # fold (what the in-step survivor_exact ring segment computes)
    from jax.flatten_util import ravel_pytree

    rows = jnp.stack([ravel_pytree(d)[0] for d in decoded])
    ring_got = roster_fold_sum(rows.at[dead].set(0.0)) / jnp.float32(n - 1)
    ring_want = roster_fold_sum(
        jnp.delete(rows, dead, axis=0)
    ) / jnp.float32(n - 1)
    assert np.array_equal(np.asarray(ring_got), np.asarray(ring_want)), name

    # tie to the existing canonical operator family: the unpinned XLA
    # reduction agrees to the documented reassociation-drift class
    loose = decode_mean_tree(
        codec,
        jax.tree_util.tree_map(
            lambda *a: jnp.stack(a), *[p for i, p in enumerate(payloads) if i != dead]
        ),
        tree, n - 1, fused=False,
    )
    for g, w in zip(
        jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(loose)
    ):
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(w), rtol=1e-6, atol=1e-6
        )


def test_survivor_mean_all_healthy_is_the_full_roster_fold():
    """kept == N: the elastic operator is exactly the pinned full-roster
    fold mean (and agrees with the unpinned decode-mean to the
    reassociation-drift class) — the healthy prefix of an elastic run is
    the ordinary mean, in the pinned-order program family."""
    from atomo_tpu.codecs import QsgdCodec, decode_mean_tree, decode_tree, encode_tree
    from atomo_tpu.elastic.shrink import roster_fold_sum

    codec = QsgdCodec(bits=4, bucket_size=64)
    key = jax.random.PRNGKey(3)
    tree = {"w": jax.random.normal(key, (9, 7))}
    payloads = [
        encode_tree(codec, jax.random.fold_in(key, r), tree)[0]
        for r in range(4)
    ]
    gathered = jax.tree_util.tree_map(lambda *a: jnp.stack(a), *payloads)
    got = survivor_decode_mean(codec, gathered, jnp.ones((4,)), tree)
    decoded = [decode_tree(codec, p, tree) for p in payloads]
    want = jax.tree_util.tree_map(
        lambda *rows: roster_fold_sum(jnp.stack(rows)) / jnp.float32(4),
        *decoded,
    )
    for g, w in zip(
        jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)
    ):
        assert np.array_equal(np.asarray(g), np.asarray(w))
    loose = decode_mean_tree(codec, gathered, tree, 4, fused=False)
    for g, w in zip(
        jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(loose)
    ):
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(w), rtol=1e-6, atol=1e-6
        )


# ---------------- guarded step: ok_bits + survivor_exact ----------------


def test_guarded_step_reports_ok_bits_and_survives_die(tmp_path):
    from atomo_tpu.codecs import QsgdCodec
    from atomo_tpu.models import get_model
    from atomo_tpu.parallel import make_mesh
    from atomo_tpu.parallel.replicated import (
        make_distributed_train_step,
        replicate_state,
        shard_batch,
    )
    from atomo_tpu.training import GuardConfig, create_state, make_optimizer
    from atomo_tpu.utils.chaos import ChaosConfig, ChaosInjector

    mesh = make_mesh(4)
    model = get_model("lenet", 10)
    opt = make_optimizer("sgd", lr=0.05, momentum=0.9)
    images = np.random.RandomState(0).rand(8, 28, 28, 1).astype(np.float32)
    labels = np.arange(8, dtype=np.int32) % 10
    state = replicate_state(
        mesh, create_state(model, opt, jax.random.PRNGKey(0), jnp.asarray(images))
    )
    chaos = ChaosInjector(ChaosConfig.from_spec("die@2:1"), membership_epoch=0)
    step = make_distributed_train_step(
        model, opt, mesh, QsgdCodec(bits=2, bucket_size=128),
        aggregate="gather", guard=GuardConfig(), chaos=chaos,
        track_ok_bits=True, survivor_exact=True,
    )
    key = jax.random.PRNGKey(1)
    si, sl = shard_batch(mesh, images, labels)
    bits, dropped, losses = [], [], []
    for _ in range(3):
        si, sl = shard_batch(mesh, images, labels)
        state, m = step(state, key, si, sl)
        bits.append(int(float(m["ok_bits"])))
        dropped.append(float(m["dropped"]))
        losses.append(float(m["loss"]))
    assert bits == [0b1111, 0b1101, 0b1101]  # replica 1 gone from step 2 ON
    assert dropped == [0.0, 1.0, 1.0]
    assert all(np.isfinite(losses))  # healthy-only metrics stay finite
    for leaf in jax.tree_util.tree_leaves(jax.device_get(state.params)):
        assert np.all(np.isfinite(leaf))


def test_track_ok_bits_requires_guard():
    from atomo_tpu.models import get_model
    from atomo_tpu.parallel import make_mesh
    from atomo_tpu.parallel.replicated import make_distributed_train_step
    from atomo_tpu.training import make_optimizer

    with pytest.raises(ValueError, match="track_ok_bits"):
        make_distributed_train_step(
            get_model("lenet", 10), make_optimizer("sgd", lr=0.1),
            make_mesh(2), None, aggregate="psum", track_ok_bits=True,
        )


# ---------------- coordinator ----------------


def _mk_coord(tmp_path, n_dev=4, batch=12, patience=2, readmit_at=0,
              max_steps=100, incidents=None):
    from atomo_tpu.elastic.coordinator import ElasticCoordinator

    return ElasticCoordinator(
        ElasticConfig(patience=patience, readmit_at=readmit_at),
        str(tmp_path), n_dev=n_dev, batch_size=batch, max_steps=max_steps,
        incidents=incidents, log_fn=lambda s: None,
    )


def test_coordinator_shrink_grow_cycle(tmp_path):
    from atomo_tpu.utils.tracing import IncidentLog

    inc = IncidentLog(str(tmp_path / "incidents.jsonl"))
    c = _mk_coord(tmp_path, incidents=inc)
    c.adopt(0, rng_crc=123)
    c.observe(3, {"ok_bits": 13.0})
    c.observe(4, {"ok_bits": 13.0})  # patience 2 -> replica 1 pending
    with pytest.raises(MembershipChange) as ei:
        c.maybe_transition(4)
    assert ei.value.kind == "shrink" and ei.value.world_size == 3
    log = MembershipLog.load(str(tmp_path))
    assert [(e.epoch, e.world_size) for e in log.epochs] == [(0, 4), (1, 3)]
    assert log.latest().dead == (1,)
    assert log.latest().roster == (0, 2, 3)
    assert log.latest().shard_map["per_replica"] == 4
    # EVERY epoch (including planned transitions) pins the run-start
    # stream fingerprint its shard-map derivation replays from
    assert log.epochs[0].shard_map["rng_crc"] == 123
    assert log.epochs[1].shard_map["rng_crc"] == 123

    # the restarted shrunken world adopts epoch 1 without a new record...
    c2 = _mk_coord(tmp_path, n_dev=3, readmit_at=6, incidents=inc)
    c2.adopt(4, rng_crc=123)
    assert len(MembershipLog.load(str(tmp_path)).epochs) == 2
    # ...and re-grows to the FULL roster at the first boundary past
    # readmit_at
    c2.observe(5, {"ok_bits": 7.0})
    c2.maybe_transition(5)  # readmit_at not reached: no raise
    with pytest.raises(MembershipChange) as eg:
        c2.maybe_transition(6)
    assert eg.value.kind == "grow" and eg.value.world_size == 4
    log = MembershipLog.load(str(tmp_path))
    assert [(e.epoch, e.world_size) for e in log.epochs] == [
        (0, 4), (1, 3), (2, 4)
    ]
    assert log.epochs[2].shard_map["rng_crc"] == 123
    recs = IncidentLog.read(str(tmp_path / "incidents.jsonl"))
    mem = [r for r in recs if r["cause"] == "membership"]
    assert [(r["action"], r["epoch"]) for r in mem] == [
        ("begin", 0), ("shrink", 1), ("grow", 2)
    ]


def test_coordinator_carries_unviable_shrink(tmp_path):
    from atomo_tpu.utils.tracing import IncidentLog

    inc = IncidentLog(str(tmp_path / "incidents.jsonl"))
    # batch 10 over 3 survivors does not divide: carry, don't shrink
    c = _mk_coord(tmp_path, n_dev=4, batch=10, incidents=inc)
    c.adopt(0)
    c.observe(1, {"ok_bits": np.asarray([13.0, 13.0])})  # (K,) block form
    c.maybe_transition(2)  # no raise
    assert len(MembershipLog.load(str(tmp_path)).epochs) == 1
    recs = IncidentLog.read(str(tmp_path / "incidents.jsonl"))
    assert any(
        r["cause"] == "membership" and r["action"] == "carry"
        and "does not divide" in r["reason"]
        for r in recs
    )


def test_coordinator_never_shrinks_below_two(tmp_path):
    """A shrink to 1 survivor would hand the supervisor a child that
    dies on its own '--elastic needs a multi-device mesh' preflight
    (rc=2 -> give-up): carry instead."""
    from atomo_tpu.utils.tracing import IncidentLog

    inc = IncidentLog(str(tmp_path / "incidents.jsonl"))
    c = _mk_coord(tmp_path, n_dev=2, batch=12, incidents=inc)
    c.adopt(0)
    c.observe(1, {"ok_bits": np.asarray([1.0, 1.0])})  # replica 1 absent
    c.maybe_transition(2)  # must NOT raise
    assert len(MembershipLog.load(str(tmp_path)).epochs) == 1
    recs = IncidentLog.read(str(tmp_path / "incidents.jsonl"))
    assert any(
        r.get("action") == "carry" and "multi-device" in r["reason"]
        for r in recs
    )


def test_coordinator_regrow_budget_bounds_flapping(tmp_path):
    """A member that dies AGAIN after re-admission must not cycle
    shrink/grow forever: automatic re-grows are capped (counted as grow
    epochs in membership.json, so the cap survives restarts)."""
    from atomo_tpu.utils.tracing import IncidentLog

    inc = IncidentLog(str(tmp_path / "incidents.jsonl"))
    log = MembershipLog.load(str(tmp_path))
    log.append(MembershipEpoch(epoch=0, world_size=4, roster=(0, 1, 2, 3)))
    log.append(MembershipEpoch(
        epoch=1, world_size=3, roster=(0, 2, 3), start_step=4,
        reason="shrink", dead=(1,),
    ))
    log.append(MembershipEpoch(
        epoch=2, world_size=4, roster=(0, 1, 2, 3), start_step=6,
        reason="grow",
    ))
    log.append(MembershipEpoch(
        epoch=3, world_size=3, roster=(0, 2, 3), start_step=8,
        reason="shrink", dead=(1,),
    ))
    c = _mk_coord(tmp_path, n_dev=3, readmit_at=6, incidents=inc)
    c.adopt(8)
    c.maybe_transition(10)  # past readmit_at, below strength: NO raise
    assert len(MembershipLog.load(str(tmp_path)).epochs) == 4
    recs = IncidentLog.read(str(tmp_path / "incidents.jsonl"))
    assert any(
        r.get("action") == "regrow_budget_spent" and r.get("regrows") == 1
        for r in recs
    )


def test_coordinator_warns_on_epoch_env_mismatch(tmp_path, monkeypatch):
    """The supervisor's epoch env is what die@ keys on; a stale value
    must be called out at adopt, not silently accepted."""
    from atomo_tpu.utils.tracing import MEMBERSHIP_EPOCH_ENV, IncidentLog

    inc = IncidentLog(str(tmp_path / "incidents.jsonl"))
    logs = []
    from atomo_tpu.elastic.coordinator import ElasticCoordinator

    c0 = ElasticCoordinator(
        ElasticConfig(patience=2), str(tmp_path), n_dev=4, batch_size=12,
        incidents=inc, log_fn=logs.append,
    )
    monkeypatch.setenv(MEMBERSHIP_EPOCH_ENV, "5")
    c0.adopt(0)  # adopted epoch is 0, env says 5
    assert any("WARNING" in l and "disagrees" in l for l in logs)
    recs = IncidentLog.read(str(tmp_path / "incidents.jsonl"))
    assert any(
        r.get("action") == "epoch_env_mismatch" and r.get("env_epoch") == 5
        for r in recs
    )


def test_die_range_checks_skipped_past_epoch0(monkeypatch):
    """The re-exec'd shrunken child inherits the ORIGINAL die@S:R spec
    with a rewritten --n-devices; since die@ is disarmed past epoch 0,
    the range/guard validation must not kill the planned reshape."""
    from atomo_tpu.cli import _argv_preflight, build_parser
    from atomo_tpu.utils.tracing import MEMBERSHIP_EPOCH_ENV

    argv = [
        "train", "--synthetic", "--train-dir", "/tmp/x", "--save-freq",
        "2", "--grad-guard", "--elastic", "--batch-size", "12",
        "--n-devices", "3", "--chaos", "die@3:3",
    ]
    args = build_parser().parse_args(argv)
    with pytest.raises(SystemExit, match="would never fire"):
        _argv_preflight(args)  # epoch 0: replica 3 of a 3-world rejects
    monkeypatch.setenv(MEMBERSHIP_EPOCH_ENV, "1")
    _argv_preflight(args)  # the shrunken child: die disarmed, passes


def test_coordinator_records_operator_resize(tmp_path):
    c = _mk_coord(tmp_path, n_dev=4)
    c.adopt(0)
    c2 = _mk_coord(tmp_path, n_dev=2)  # manual relaunch at another world
    c2.adopt(10)
    log = MembershipLog.load(str(tmp_path))
    assert log.latest().reason == "operator_resize"
    assert log.latest().world_size == 2


def test_coordinator_suppresses_transition_at_run_end(tmp_path):
    c = _mk_coord(tmp_path, max_steps=6)
    c.adopt(0)
    c.observe(1, {"ok_bits": 13.0})
    c.observe(2, {"ok_bits": 13.0})
    c.maybe_transition(6)  # at max_steps: a reshape would buy nothing


# ---------------- supervisor triage ----------------

_FAKE_CHILD = """
import json, os, sys

train_dir = sys.argv[1]
argv = sys.argv[2:]
nd = argv[argv.index("--n-devices") + 1]
epoch_env = os.environ.get("ATOMO_MEMBERSHIP_EPOCH", "")
sys.path.insert(0, {root!r})
from atomo_tpu.elastic.membership import MembershipEpoch, MembershipLog

log = MembershipLog.load(train_dir)
if nd == "4":
    log.append(MembershipEpoch(epoch=0, world_size=4, roster=(0, 1, 2, 3)))
    log.append(MembershipEpoch(
        epoch=1, world_size=3, roster=(0, 2, 3), start_step=2,
        reason="shrink", dead=(1,),
    ))
    sys.exit(29)
assert nd == "3", nd
assert epoch_env == "1", epoch_env
assert "--resume" in argv, argv
sys.exit(0)
"""


def test_run_supervised_membership_restart_spares_budget(tmp_path):
    """rc=29 with a newer membership plan: the supervisor rewrites
    --n-devices, exports the epoch env, appends --resume, and restarts
    even with a ZERO crash budget — a planned reshape is not a crash."""
    from atomo_tpu.training.resilience import run_supervised
    from atomo_tpu.utils.tracing import IncidentLog

    child = tmp_path / "child.py"
    child.write_text(_FAKE_CHILD.format(root=_REPO_ROOT))
    rc = run_supervised(
        [sys.executable, str(child), str(tmp_path), "--n-devices", "4"],
        max_restarts=0,  # zero crash budget: only the reshape path passes
        train_dir=str(tmp_path),
        sleep=lambda s: None,
        log_fn=lambda s: None,
    )
    assert rc == 0
    recs = IncidentLog.read(str(tmp_path / "incidents.jsonl"))
    causes = [r["cause"] for r in recs]
    assert causes == ["membership_change", "clean_exit"]
    assert recs[0]["action"] == "reshape->3"
    assert recs[0]["epoch"] == 1 and recs[0]["world"] == 3


def test_run_supervised_stale_membership_plan_is_a_crash(tmp_path):
    """rc=29 without a (new) plan on disk must be triaged as a crash —
    the runaway-reshape guard."""
    from atomo_tpu.training.resilience import run_supervised
    from atomo_tpu.utils.tracing import IncidentLog

    child = tmp_path / "child.py"
    child.write_text("import sys; sys.exit(29)\n")
    rc = run_supervised(
        [sys.executable, str(child)],
        max_restarts=0,
        train_dir=str(tmp_path),
        sleep=lambda s: None,
        log_fn=lambda s: None,
    )
    assert rc == 29
    recs = IncidentLog.read(str(tmp_path / "incidents.jsonl"))
    assert recs[-1]["cause"] == "budget_exhausted"


# ---------------- CLI preflight ----------------


def _main(*extra):
    from atomo_tpu.cli import main

    return main([
        "train", "--synthetic", "--dataset", "mnist", "--network", "lenet",
        "--batch-size", "8", "--max-steps", "2", "--train-dir", "/tmp/x",
        "--save-freq", "2", *extra,
    ])


@pytest.mark.parametrize(
    "extra, match",
    [
        (("--elastic", "--n-devices", "4"), "--grad-guard"),
        (("--elastic", "--grad-guard", "--n-devices", "1"), "multi-device"),
        (
            ("--elastic", "--grad-guard", "--n-devices", "4", "--zero1"),
            "--zero1",
        ),
        (
            ("--elastic", "--grad-guard", "--n-devices", "4",
             "--code", "qsgd", "--overlap", "delayed"),
            "delayed",
        ),
        (
            ("--elastic", "--grad-guard", "--n-devices", "4",
             "--code", "qsgd", "--aggregate", "hierarchical"),
            "flat-mesh",
        ),
        (
            ("--elastic", "--grad-guard", "--n-devices", "4",
             "--elastic-patience", "0"),
            "must be >= 1",
        ),
        (("--readmit-at", "5", "--n-devices", "4"), "--elastic"),
        (
            ("--chaos", "die@3:1", "--n-devices", "4"),
            "skip-and-rescale",
        ),
        (
            ("--chaos", "die@3:1", "--grad-guard", "--n-devices", "1"),
            "surviving replicas",
        ),
        (
            ("--chaos", "die@3:7", "--grad-guard", "--n-devices", "4"),
            "would never fire",
        ),
    ],
)
def test_elastic_preflight_rejects(extra, match):
    with pytest.raises(SystemExit, match=match):
        _main(*extra)


def test_elastic_preflight_rejects_without_cadence():
    from atomo_tpu.cli import main

    with pytest.raises(SystemExit, match="checkpoint cadence"):
        main([
            "train", "--synthetic", "--train-dir", "/tmp/x", "--elastic",
            "--grad-guard", "--n-devices", "4", "--save-freq", "0",
            "--eval-freq", "0",
        ])


# ---------------- stale tune-decision reuse ----------------


def test_decision_reusable_world_size_gate():
    from atomo_tpu.tuning.autopilot import decision_reusable

    doc = {
        "complete": True,
        "meta": {"n_devices": 4},
        "winner": {"name": "x", "knobs": {"aggregate": "ring"}},
    }
    ok, why = decision_reusable(doc, n_dev=4)
    assert ok, why
    ok, why = decision_reusable(doc, n_dev=3)
    assert not ok and "n_devices=4" in why and "3" in why
    ok, _ = decision_reusable({"complete": False}, n_dev=4)
    assert not ok
    ok, _ = decision_reusable(None, n_dev=4)
    assert not ok
    # a pre-PR-9 artifact without the recorded world is NOT trusted
    legacy = {"complete": True, "winner": {"name": "x", "knobs": {"a": 1}}}
    ok, _ = decision_reusable(legacy, n_dev=4)
    assert not ok


# ---------------- incident-log folding (satellite f) ----------------


def test_incident_log_summarize_and_torn_membership_record(tmp_path):
    from atomo_tpu.utils.tracing import IncidentLog

    path = str(tmp_path / "incidents.jsonl")
    log = IncidentLog(path)
    log.append("membership", action="begin", step=0, epoch=0, world=4)
    log.append(
        "membership", action="shrink", step=4, epoch=1, world=3, dead=[1]
    )
    log.append(
        "membership_change", action="reshape->3", attempt=0, rc=29,
        epoch=1, world=3,
    )
    with open(path, "a") as f:
        f.write('{"cause": "membership", "action": "grow", "ep')  # torn
    recs = IncidentLog.read(path)
    assert len(recs) == 3  # the torn line is skipped, the rest parse
    s = IncidentLog.summarize(path)
    assert "epoch=1" in s and "world=3" in s and "rc=29" in s
    assert "-> shrink" in s and "-> reshape->3" in s


# ---------------- pipeline fingerprint ----------------


def test_rng_signature_deterministic_and_consumption_sensitive():
    from atomo_tpu.data import SPECS, BatchIterator, synthetic_dataset

    ds = synthetic_dataset(SPECS["mnist"], True, size=64)
    a = BatchIterator(ds, 8, seed=5)
    b = BatchIterator(ds, 8, seed=5)
    assert a.rng_signature() == b.rng_signature()
    next(iter(a.epoch()))  # consume a shuffle draw
    assert a.rng_signature() != b.rng_signature()
    assert BatchIterator(ds, 8, seed=6).rng_signature() != b.rng_signature()


# ---------------- slow subprocess drills (acceptance a + b) -----------


def _cli_elastic(train_dir, *extra, timeout=300):
    env = {
        **os.environ,
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
        "PYTHONPATH": _REPO_ROOT + os.pathsep + os.environ.get("PYTHONPATH", ""),
    }
    # cache-cold (the conftest default, restated): shared-cache re-execs
    # across world sizes corrupted executions on the CPU backend
    # (measured); the drills prove semantics, not compile amortization
    env["JAX_ENABLE_COMPILATION_CACHE"] = "false"
    cmd = [
        sys.executable, "-m", "atomo_tpu.cli", "train",
        "--synthetic", "--dataset", "mnist", "--network", "lenet",
        "--batch-size", "12", "--eval-freq", "0", "--save-freq", "2",
        "--log-interval", "1", "--code", "qsgd", "--quantization-level",
        "8", "--aggregate", "gather", "--grad-guard", "--elastic",
        "--elastic-patience", "2", "--train-dir", str(train_dir), *extra,
    ]
    return subprocess.run(
        cmd, env=env, capture_output=True, text=True, timeout=timeout,
        cwd=_REPO_ROOT,
    )


def _leaves(train_dir, step):
    from atomo_tpu.training.checkpoint import _read_state_dict

    return jax.tree_util.tree_leaves(_read_state_dict(str(train_dir), step))


@pytest.mark.slow
def test_die_shrink_matches_fresh_small_world_bit_exact(tmp_path):
    """Acceptance (a): the shrunken epoch of a die@S drill is leaf-wise
    BIT-exact with a fresh --n-devices N-1 run resumed from the same
    healthy checkpoint (same stream skip, same roster, same program).

    Pinned to ``--elastic-reshard reexec``: this drill proves the
    supervisor re-exec protocol specifically (the recorded fallback
    path); the live in-process primary path has its own witness in
    test_live_reshard_shrink_matches_fresh_small_world_bit_exact."""
    d1 = tmp_path / "drill"
    p = _cli_elastic(
        d1, "--n-devices", "4", "--max-steps", "10",
        "--chaos", "die@3:1", "--max-restarts", "1",
        "--restart-backoff", "0.05", "--elastic-reshard", "reexec",
    )
    assert p.returncode == 0, (p.stdout[-2000:], p.stderr[-2000:])
    log = MembershipLog.load(str(d1))
    assert [(e.epoch, e.world_size) for e in log.epochs] == [(0, 4), (1, 3)]
    shrink_step = log.epochs[1].start_step

    # fresh leg: same checkpoint + membership history AS OF the shrink,
    # run at N-1 from the start, no chaos, unsupervised
    d2 = tmp_path / "fresh"
    d2.mkdir()
    import shutil

    shutil.copy(d1 / f"model_step_{shrink_step}", d2)
    fresh_log = MembershipLog.load(str(d2))
    for e in log.epochs:  # epochs 0..1: the history the shrink leg saw
        fresh_log.append(e)
    p2 = _cli_elastic(
        d2, "--n-devices", "3", "--max-steps", "10", "--resume"
    )
    assert p2.returncode == 0, (p2.stdout[-2000:], p2.stderr[-2000:])
    assert f"Resumed from {d2} at step {shrink_step}" in p2.stdout

    for s in range(shrink_step + 2, 11, 2):  # every shared checkpoint
        la, lb = _leaves(d1, s), _leaves(d2, s)
        assert len(la) == len(lb)
        for x, y in zip(la, lb):
            assert np.array_equal(np.asarray(x), np.asarray(y)), s


@pytest.mark.slow
def test_die_shrink_regrow_records_epochs_in_order(tmp_path):
    """Acceptance (b): die@S -> shrink -> re-grow completes, membership
    epochs 0 -> 1 -> 2 land in incidents.jsonl in order, the final step
    count matches the uninterrupted run, and no crash-restart budget was
    consumed."""
    from atomo_tpu.training.checkpoint import latest_valid_step
    from atomo_tpu.utils.tracing import IncidentLog

    d = tmp_path / "drill"
    # pinned to reexec: the asserted membership_change incident stream
    # (world [3, 4]) only exists on the supervisor re-exec path
    p = _cli_elastic(
        d, "--n-devices", "4", "--max-steps", "12",
        "--chaos", "die@3:1", "--readmit-at", "6",
        "--max-restarts", "1", "--restart-backoff", "0.05",
        "--elastic-reshard", "reexec",
    )
    assert p.returncode == 0, (p.stdout[-2000:], p.stderr[-2000:])
    assert latest_valid_step(str(d)) == 12  # same step count as a clean run
    log = MembershipLog.load(str(d))
    assert [(e.epoch, e.world_size, e.reason) for e in log.epochs] == [
        (0, 4, "init"), (1, 3, "shrink"), (2, 4, "grow")
    ]
    recs = IncidentLog.read(str(d / "incidents.jsonl"))
    mem = [r for r in recs if r["cause"] == "membership"]
    assert [r["epoch"] for r in mem] == [0, 1, 2]
    assert [r["action"] for r in mem] == ["begin", "shrink", "grow"]
    reshapes = [r for r in recs if r["cause"] == "membership_change"]
    assert [r["world"] for r in reshapes] == [3, 4]
    # the whole cycle was planned reshapes: no crash, no budget spent
    assert not any(
        r["cause"] in ("crash", "budget_exhausted") for r in recs
    )
    assert recs[-1]["cause"] == "clean_exit"


# ---------------- live reshard drills (the zero-downtime primary path)


def test_live_reshard_shrink_matches_fresh_small_world_bit_exact(tmp_path):
    """THE tentpole witness: under the default ``--elastic-reshard
    live`` a die@ shrink reshapes IN PROCESS — rc=0, ONE process, no
    re-exec — and the continued trajectory is leaf-wise BIT-exact with
    a fresh --n-devices N-1 run resumed from the shrink checkpoint."""
    from atomo_tpu.utils.tracing import IncidentLog

    d1 = tmp_path / "drill"
    p = _cli_elastic(
        d1, "--n-devices", "4", "--max-steps", "10",
        "--chaos", "die@3:1",
    )
    assert p.returncode == 0, (p.stdout[-2000:], p.stderr[-2000:])
    assert "Elastic: LIVE shrink 4 -> 3" in p.stdout
    # no supervisor fallback: the whole run was one process
    assert "falling back to the re-exec protocol" not in p.stdout
    log = MembershipLog.load(str(d1))
    assert [(e.epoch, e.world_size) for e in log.epochs] == [(0, 4), (1, 3)]
    shrink_step = log.epochs[1].start_step
    recs = IncidentLog.read(str(d1 / "incidents.jsonl"))
    mem = [r for r in recs if r["cause"] == "membership"]
    assert [r["action"] for r in mem] == ["begin", "shrink"]
    assert mem[1]["reshard"] == "live"
    # the re-exec protocol's incident never fired
    assert not any(r["cause"] == "membership_change" for r in recs)

    d2 = tmp_path / "fresh"
    d2.mkdir()
    import shutil

    shutil.copy(d1 / f"model_step_{shrink_step}", d2)
    fresh_log = MembershipLog.load(str(d2))
    for e in log.epochs:
        fresh_log.append(e)
    p2 = _cli_elastic(
        d2, "--n-devices", "3", "--max-steps", "10", "--resume"
    )
    assert p2.returncode == 0, (p2.stdout[-2000:], p2.stderr[-2000:])
    for s in range(shrink_step + 2, 11, 2):
        la, lb = _leaves(d1, s), _leaves(d2, s)
        assert len(la) == len(lb)
        for x, y in zip(la, lb):
            assert np.array_equal(np.asarray(x), np.asarray(y)), s


@pytest.mark.slow
def test_live_reshard_refusal_records_fallback_and_reexecs(tmp_path):
    """When the live path cannot hold its determinism contract (the
    fused superstep's block feed is world-shaped) the coordinator
    REFUSES out loud — a ``reshard_fallback`` incident quoting why —
    and the supervisor re-exec protocol runs exactly as before."""
    from atomo_tpu.utils.tracing import IncidentLog

    d = tmp_path / "drill"
    p = _cli_elastic(
        d, "--n-devices", "4", "--max-steps", "10",
        "--chaos", "die@3:1", "--superstep", "2",
        "--max-restarts", "1", "--restart-backoff", "0.05",
    )
    assert p.returncode == 0, (p.stdout[-2000:], p.stderr[-2000:])
    assert "falling back to the re-exec protocol" in p.stdout
    log = MembershipLog.load(str(d))
    assert [(e.epoch, e.world_size) for e in log.epochs] == [(0, 4), (1, 3)]
    recs = IncidentLog.read(str(d / "incidents.jsonl"))
    fb = [r for r in recs if r.get("action") == "reshard_fallback"]
    assert len(fb) == 1 and "superstep" in fb[0]["reason"]
    # the fallback ran the full re-exec protocol, recorded as ever
    assert any(r["cause"] == "membership_change" for r in recs)


@pytest.mark.slow
def test_live_reshard_then_crash_restart_resumes_at_new_world(tmp_path):
    """Satellite: a live reshape advances the membership epoch WITHOUT
    rc=29, so a LATER crash must restart at the membership.json world,
    not the stale launch world — the supervisor's crash path re-derives
    --n-devices from the recorded epoch, and the replay is bit-exact
    with the uninterrupted live drill."""
    d1 = tmp_path / "drill"
    p = _cli_elastic(
        d1, "--n-devices", "4", "--max-steps", "10",
        "--chaos", "die@3:1",
    )
    assert p.returncode == 0, (p.stdout[-2000:], p.stderr[-2000:])

    d2 = tmp_path / "crashed"
    p2 = _cli_elastic(
        d2, "--n-devices", "4", "--max-steps", "10",
        "--chaos", "die@3:1,kill@7", "--max-restarts", "1",
        "--restart-backoff", "0.05",
    )
    assert p2.returncode == 0, (p2.stdout[-2000:], p2.stderr[-2000:])
    assert "Elastic: LIVE shrink 4 -> 3" in p2.stdout
    # the crash path re-derived the world from membership.json (the
    # live reshape advanced the epoch without an rc=29 exit)
    assert "reshaped before the crash; restarting with --n-devices 3" \
        in p2.stdout
    log = MembershipLog.load(str(d2))
    assert [(e.epoch, e.world_size) for e in log.epochs] == [(0, 4), (1, 3)]
    for s in (8, 10):
        la, lb = _leaves(d1, s), _leaves(d2, s)
        assert len(la) == len(lb)
        for x, y in zip(la, lb):
            assert np.array_equal(np.asarray(x), np.asarray(y)), s
