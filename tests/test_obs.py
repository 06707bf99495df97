"""Flight recorder, estimator-quality probes, and the run report (PR 11).

Contracts being pinned:

  * metrics.jsonl schema: one kind="step" record per training step with
    loss / step_ms / wire bytes / guard columns / context (aggregate,
    membership epoch, generation) and the rolling calibration column.
  * Superstep share-partition invariance: the same step series recorded
    as one block or as per-step records produces identical step/loss
    columns and the same total wall (the PR-9 per-step-shares precedent).
  * Torn-line tolerance: a SIGKILL-torn tail is skipped on read and the
    file stays appendable (the IncidentLog discipline).
  * Rollback/resume prune: checkpoint.prune_after and
    FlightRecorder.prune_past cut the metric timeline in lockstep with
    the checkpoint timeline.
  * The worker-line sink: stdout stays byte-identical to the captured
    golden line with the recorder disarmed, and armed it feeds stdout
    and metrics.jsonl from the SAME record.
  * --obs-quality off => byte-identical lowered HLO (the stream-encode
    precedent); on => bit-identical trajectories (probes only ADD
    metric outputs) and per-layer error columns with the documented
    semantics (dense codec => exactly zero error).
  * report: joins metrics + incidents + membership + tune_decision into
    a consistent timeline; each consistency check fires on the
    violation it documents; the supervised die@3:1 drill's artifacts
    pass all checks end to end (slow tier).
"""

import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from atomo_tpu.codecs import DenseCodec, QsgdCodec, encode_tree
from atomo_tpu.models import get_model
from atomo_tpu.obs.quality import quality_meta, quality_probe
from atomo_tpu.obs.recorder import (
    FlightRecorder,
    emit_worker_line,
    metrics_path,
    prune_metrics_after,
)
from atomo_tpu.obs.report import build_report, summarize_report
from atomo_tpu.parallel import (
    make_distributed_train_step,
    make_mesh,
    replicate_state,
    shard_batch,
)
from atomo_tpu.training import create_state, make_optimizer, snapshot_state
from atomo_tpu.training.trainer import make_train_step
from atomo_tpu.utils.metrics import StepMetrics

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

QSGD = QsgdCodec(bits=4, bucket_size=128)


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


# ------------------------------------------------------------- recorder


def test_recorder_step_schema_and_calibration(tmp_path):
    rec = FlightRecorder.for_train_dir(str(tmp_path), predicted_ms=2.0)
    rec.set_context(aggregate="gather")
    rec.record_block(
        1,
        {"loss": 2.5, "msg_bytes": 1024.0, "skipped": 0.0, "dropped": 0.0},
        wall_s=0.004,
        generation=0,
    )
    recs = FlightRecorder.read(metrics_path(str(tmp_path)))
    assert len(recs) == 1
    r = recs[0]
    assert r["kind"] == "step" and r["step"] == 1
    assert r["loss"] == 2.5 and r["msg_bytes"] == 1024.0
    assert r["step_ms"] == pytest.approx(4.0)
    assert r["aggregate"] == "gather" and r["epoch"] == 0
    assert r["generation"] == 0
    # calibration column: measured/predicted EMA (first sample = ratio)
    assert r["predicted_ms"] == 2.0
    assert r["calib"] == pytest.approx(2.0)


def test_recorder_block_series_and_quality_columns(tmp_path):
    rec = FlightRecorder.for_train_dir(str(tmp_path))
    m = {
        "loss": np.array([1.0, 2.0, 3.0]),
        "skipped": np.array([0.0, 1.0, 0.0]),
        "q_rel": np.arange(6.0).reshape(3, 2),
    }
    out = rec.record_block(5, m, wall_s=0.03)
    assert [r["step"] for r in out] == [5, 6, 7]
    assert [r["loss"] for r in out] == [1.0, 2.0, 3.0]
    assert out[1]["skipped"] == 1.0
    assert out[2]["q_rel"] == [4.0, 5.0]
    # the block wall lands as K equal per-step shares
    assert all(r["step_ms"] == pytest.approx(10.0) for r in out)


def test_share_partition_invariance(tmp_path):
    """The same per-step series recorded as ONE block or as K single
    records produces identical step/loss/q columns and the same total
    wall — a superstep block size is a layout knob for the timeline too."""
    losses = [1.0, 2.0, 3.0, 4.0]
    qs = np.arange(8.0).reshape(4, 2)
    a = FlightRecorder.for_train_dir(str(tmp_path / "block"))
    a.record_block(
        1, {"loss": np.asarray(losses), "q_rel": qs}, wall_s=0.04
    )
    b = FlightRecorder.for_train_dir(str(tmp_path / "steps"))
    for i, l in enumerate(losses):
        b.record_block(
            1 + i, {"loss": l, "q_rel": qs[i]}, wall_s=0.01
        )

    def strip(path):
        return [
            {k: v for k, v in r.items() if k != "ts"}
            for r in FlightRecorder.read_steps(metrics_path(path))
        ]

    ra, rb = strip(str(tmp_path / "block")), strip(str(tmp_path / "steps"))
    assert ra == rb


def test_torn_line_skipped_and_file_stays_appendable(tmp_path):
    rec = FlightRecorder.for_train_dir(str(tmp_path))
    rec.record_block(1, {"loss": 1.0})
    with open(rec.path, "a") as f:
        f.write('{"kind": "step", "step": 2, "los')  # SIGKILL mid-write
    assert [r["step"] for r in FlightRecorder.read_steps(rec.path)] == [1]
    rec.record_block(2, {"loss": 2.0})
    recs = FlightRecorder.read_steps(rec.path)
    # the torn fragment merged into record 2's line is dropped with it —
    # what survives must PARSE, and appends keep working
    assert all(isinstance(r["step"], int) for r in recs)
    rec.record_block(3, {"loss": 3.0})
    assert FlightRecorder.read_steps(rec.path)[-1]["step"] == 3


def test_nonfinite_metrics_serialize_as_null(tmp_path):
    """A diverged step's NaN loss must not make metrics.jsonl invalid
    JSON (json.dumps would emit the non-standard NaN token): non-finite
    floats land as null, and every line strict-parses."""
    rec = FlightRecorder.for_train_dir(str(tmp_path))
    rec.record_block(
        1,
        {"loss": float("nan"), "grad_norm": float("inf"),
         "q_rel": np.array([1.0, float("nan")])},
    )
    raw = open(rec.path).read()
    assert "NaN" not in raw and "Infinity" not in raw

    def strict(s):
        return json.loads(
            s, parse_constant=lambda c: pytest.fail(f"non-strict {c}")
        )

    r = strict(raw.strip())
    assert r["loss"] is None and r["grad_norm"] is None
    assert r["q_rel"] == [1.0, None]


def test_write_meta_is_idempotent_per_what(tmp_path):
    """A supervisor restart re-arms the recorder against the same file
    (prune_past keeps meta lines): re-writing the same meta must not
    accumulate one duplicate per attempt."""
    rec = FlightRecorder.for_train_dir(str(tmp_path))
    rec.write_meta({"what": "obs_quality", "n_layers": 2})
    rec2 = FlightRecorder.for_train_dir(str(tmp_path))  # the restart
    rec2.write_meta({"what": "obs_quality", "n_layers": 2})
    metas = [
        r for r in FlightRecorder.read(rec.path) if r["kind"] == "meta"
    ]
    assert len(metas) == 1


def test_calibration_column_gated_on_this_runs_tune(tmp_path):
    """A stale tune_decision.json left by some OTHER run must not
    fabricate a calibration series: without --auto tune the recorder
    gets no prediction and the column is absent."""
    from atomo_tpu.utils.tracing import write_json_atomic

    from atomo_tpu.cli import main

    write_json_atomic(
        str(tmp_path / "tune_decision.json"),
        {"complete": True,
         "winner": {"name": "x", "predicted_ms_per_step": 0.3,
                    "knobs": {}}},
    )
    rc = main([
        "train", "--synthetic", "--dataset", "mnist", "--network", "lenet",
        "--batch-size", "8", "--max-steps", "2", "--eval-freq", "0",
        "--log-interval", "0", "--n-devices", "1", "--code", "qsgd",
        "--quantization-level", "8", "--train-dir", str(tmp_path),
        "--obs-record", "--momentum", "0.0",
    ])
    assert rc == 0
    steps = FlightRecorder.read_steps(metrics_path(str(tmp_path)))
    assert steps and all(
        "predicted_ms" not in r and "calib" not in r for r in steps
    )


def test_prune_cuts_step_and_log_records_keeps_meta(tmp_path):
    rec = FlightRecorder.for_train_dir(str(tmp_path))
    rec.write_meta({"what": "obs_quality", "n_layers": 2})
    for s in range(1, 9):
        rec.record_block(s, {"loss": float(s)})
    emit_worker_line(rec, StepMetrics(step=8), log_fn=lambda _: None)
    removed = prune_metrics_after(str(tmp_path), 5)
    assert removed == 4  # steps 6,7,8 + the step-8 log record
    recs = FlightRecorder.read(metrics_path(str(tmp_path)))
    assert [r.get("kind") for r in recs][0] == "meta"  # meta survives
    assert max(r["step"] for r in recs if "step" in r) == 5


def test_checkpoint_prune_after_prunes_metrics_in_lockstep(tmp_path):
    from atomo_tpu.training.checkpoint import prune_after

    rec = FlightRecorder.for_train_dir(str(tmp_path))
    for s in range(1, 7):
        rec.record_block(s, {"loss": float(s)})
    prune_after(str(tmp_path), 3)  # no checkpoints exist — metrics still cut
    assert [
        r["step"] for r in FlightRecorder.read_steps(rec.path)
    ] == [1, 2, 3]


def test_prune_past_resume_hook(tmp_path):
    rec = FlightRecorder.for_train_dir(str(tmp_path))
    for s in range(1, 6):
        rec.record_block(s, {"loss": float(s)})
    assert rec.prune_past(2) == 3
    rec.record_block(3, {"loss": 3.5})  # the replayed step re-records
    assert [
        r["step"] for r in FlightRecorder.read_steps(rec.path)
    ] == [1, 2, 3]


# ------------------------------------------------- the worker-line sink

# captured golden line (byte-for-byte the reference worker format the
# tuning parser regexes) — the sink must not change a single character
_GOLDEN = (
    "Worker: 0, Step: 12, Epoch: 1 [384/10000 (4%)], Loss: 2.3456, "
    "Time Cost: 0.1234, Comp: 0.0000, Encode:  0.0000, Comm:  0.0000, "
    "Msg(MB):  0.5547, Prec@1:  12.5000, Prec@5:  50.0000"
)


def _golden_rec():
    return StepMetrics(
        rank=0, step=12, epoch=1, samples_seen=384, dataset_size=10000,
        loss=2.3456, time_cost=0.1234, comp_dur=0.0, encode_dur=0.0,
        comm_dur=0.0, msg_bytes=581632, prec1=12.5, prec5=50.0,
    )


def test_worker_line_sink_disarmed_is_byte_identical():
    lines = []
    emit_worker_line(None, _golden_rec(), log_fn=lines.append)
    assert lines == [_GOLDEN]


def test_worker_line_sink_armed_feeds_both_from_one_record(tmp_path):
    rec = FlightRecorder.for_train_dir(str(tmp_path))
    rec.set_context(aggregate="ring")
    lines = []
    emit_worker_line(rec, _golden_rec(), log_fn=lines.append)
    assert lines == [_GOLDEN]  # stdout unchanged by arming
    logged = [
        r for r in FlightRecorder.read(rec.path) if r["kind"] == "log"
    ]
    assert len(logged) == 1
    assert logged[0]["step"] == 12 and logged[0]["loss"] == 2.3456
    assert logged[0]["msg_bytes"] == 581632
    assert logged[0]["aggregate"] == "ring"
    # StepMetrics' DATASET epoch must not be overwritten by the
    # membership context (the field-collision guard)
    assert logged[0]["epoch"] == 1


# ------------------------------------------------------ quality probes


def test_quality_probe_dense_codec_is_exactly_zero():
    grads = {
        "a": jnp.arange(12.0).reshape(3, 4),
        "b": jnp.ones((5,)) * 0.3,
    }
    payloads, _ = encode_tree(DenseCodec(), jax.random.PRNGKey(0), grads)
    qm = jax.jit(lambda p, g: quality_probe(DenseCodec(), p, g))(
        payloads, grads
    )
    assert qm["q_err2"].shape == (2,)
    assert np.array_equal(np.asarray(qm["q_err2"]), np.zeros(2))
    assert np.array_equal(np.asarray(qm["q_rel"]), np.zeros(2))


def test_quality_probe_qsgd_error_and_rel_relation():
    key = jax.random.PRNGKey(1)
    grads = {
        "w": jax.random.normal(key, (16, 8)),
        "b": jax.random.normal(jax.random.fold_in(key, 1), (8,)),
    }
    payloads, _ = encode_tree(QSGD, jax.random.PRNGKey(2), grads)
    qm = jax.jit(lambda p, g: quality_probe(QSGD, p, g))(payloads, grads)
    err2 = np.asarray(qm["q_err2"])
    rel = np.asarray(qm["q_rel"])
    assert err2.shape == (2,) and (err2 > 0).all()  # lossy codec
    g2 = np.array([
        float(jnp.sum(g.astype(jnp.float32) ** 2))
        for g in jax.tree_util.tree_leaves(grads)
    ])
    np.testing.assert_allclose(rel, err2 / g2, rtol=1e-5)


def test_quality_meta_matches_encode_accounting():
    _, model, opt, host0, _ = _setup()
    meta = quality_meta(QSGD, host0.params)
    _, stats = encode_tree(
        QSGD, jax.random.PRNGKey(0),
        jax.tree_util.tree_map(jnp.asarray, host0.params),
    )
    assert meta["payload_bytes"] == stats.payload_bytes
    assert meta["dense_bytes"] == stats.dense_bytes
    assert meta["n_layers"] == len(meta["layers"])
    assert all(
        l["name"] and l["payload_bytes"] > 0 for l in meta["layers"]
    )


# ------------------------------------- off-mode HLO / on-mode bit parity


def test_quality_off_is_byte_identical_single_host():
    _, model, opt, host0, batches = _setup(n_dev=1)
    key = jax.random.PRNGKey(1)
    im = jnp.asarray(batches[0][0])
    lb = jnp.asarray(batches[0][1])
    st = jax.tree_util.tree_map(jnp.asarray, host0)
    s_def = make_train_step(model, opt, codec=QSGD)
    s_off = make_train_step(model, opt, codec=QSGD, track_quality=False)
    a = s_def.lower(st, key, im, lb).as_text()
    b = s_off.lower(st, key, im, lb).as_text()
    assert a == b


def test_quality_off_is_byte_identical_distributed():
    mesh, model, opt, host0, batches = _setup()
    key = jax.random.PRNGKey(1)
    si, sl = shard_batch(mesh, *batches[0])
    st = _fresh(mesh, host0)
    s_def = make_distributed_train_step(model, opt, mesh, QSGD,
                                        aggregate="gather")
    s_off = make_distributed_train_step(model, opt, mesh, QSGD,
                                        aggregate="gather",
                                        track_quality=False)
    a = s_def.lower(st, key, si, sl).as_text()
    b = s_off.lower(st, key, si, sl).as_text()
    assert a == b


@pytest.mark.parametrize(
    "agg",
    [
        "gather",
        # ring re-proves the same armed-vs-off identity over the pricier
        # exchange (~6 s on 1 core) — full-suite only; gather keeps the
        # probes-only-ADD contract witnessed in the smoke set
        pytest.param("ring", marks=pytest.mark.slow),
    ],
)
def test_quality_on_trajectory_bit_identical(agg):
    """Arming the probes only ADDS metric outputs: params after a short
    trajectory are bit-identical armed vs off, and the armed metrics
    carry per-layer columns of the right shape."""
    mesh, model, opt, host0, batches = _setup()
    key = jax.random.PRNGKey(1)
    off = make_distributed_train_step(model, opt, mesh, QSGD, aggregate=agg)
    on = make_distributed_train_step(model, opt, mesh, QSGD, aggregate=agg,
                                     track_quality=True)
    st_a, st_b = _fresh(mesh, host0), _fresh(mesh, host0)
    m_on = None
    for im, lb in batches[:2]:
        si, sl = shard_batch(mesh, im, lb)
        st_a, _ = off(st_a, key, si, sl)
        st_b, m_on = on(st_b, key, si, sl)
    pa = jax.device_get(st_a.params)
    pb = jax.device_get(st_b.params)
    for x, y in zip(jax.tree_util.tree_leaves(pa),
                    jax.tree_util.tree_leaves(pb)):
        assert np.array_equal(np.asarray(x), np.asarray(y))
    n_leaves = len(jax.tree_util.tree_leaves(host0.params))
    assert np.asarray(m_on["q_err2"]).shape == (n_leaves,)
    assert np.isfinite(np.asarray(m_on["q_rel"])).all()


def test_quality_conflict_matrix():
    mesh, model, opt, _, _ = _setup()
    with pytest.raises(ValueError, match="estimator"):
        make_distributed_train_step(model, opt, mesh, None,
                                    track_quality=True)
    with pytest.raises(ValueError, match="delayed"):
        make_distributed_train_step(model, opt, mesh, QSGD,
                                    overlap="delayed", track_quality=True)
    with pytest.raises(ValueError, match="estimator"):
        make_train_step(model, opt, codec=None, track_quality=True)


# ------------------------------------------------------------- report


def _write_jsonl(path, recs):
    with open(path, "w") as f:
        for r in recs:
            f.write(json.dumps(r) + "\n")


def _mk_run(tmp_path, *, steps, incidents=(), membership=None):
    rec = FlightRecorder.for_train_dir(str(tmp_path))
    rec._append_lines(steps)
    if incidents:
        _write_jsonl(str(tmp_path / "incidents.jsonl"), list(incidents))
    if membership is not None:
        from atomo_tpu.utils.tracing import write_json_atomic

        write_json_atomic(str(tmp_path / "membership.json"), membership)


def _steps(rng, aggregate="gather", epoch=0):
    return [
        {"kind": "step", "step": s, "loss": 2.0, "aggregate": aggregate,
         "epoch": epoch}
        for s in rng
    ]


def test_report_consistent_run(tmp_path):
    _mk_run(
        tmp_path,
        steps=_steps(range(1, 9)),
        incidents=[{"ts": 1.0, "cause": "clean_exit", "action": "done"}],
    )
    doc = build_report(str(tmp_path))
    assert doc["consistent"] is True
    assert doc["summary"]["steps_recorded"] == 8
    segs = [e for e in doc["timeline"] if e["kind"] == "metrics"]
    assert len(segs) == 1
    assert segs[0]["first_step"] == 1 and segs[0]["last_step"] == 8
    assert "consistency: OK" in summarize_report(doc)


def test_report_metrics_monotone_catches_surviving_tail(tmp_path):
    # a rollback whose prune failed: steps regress in file order
    _mk_run(
        tmp_path,
        steps=_steps(range(1, 7)) + _steps(range(4, 9)),
        incidents=[{
            "ts": 1.0, "cause": "divergence", "action": "rollback+skip",
            "step": 6, "target": 3,
        }],
    )
    doc = build_report(str(tmp_path))
    checks = {c["name"]: c for c in doc["checks"]}
    assert checks["metrics_monotone"]["ok"] is False
    assert doc["consistent"] is False
    assert "FAILED" in summarize_report(doc)


def test_report_membership_checks(tmp_path):
    membership = {
        "kind": "membership", "full_world": 4,
        "epochs": [
            {"epoch": 0, "world_size": 4, "roster": [0, 1, 2, 3],
             "start_step": 0, "reason": "init", "dead": []},
            {"epoch": 1, "world_size": 3, "roster": [0, 2, 3],
             "start_step": 4, "reason": "shrink", "dead": [1]},
        ],
    }
    incidents = [
        {"ts": 1.0, "cause": "membership", "action": "begin", "step": 0,
         "epoch": 0, "world": 4},
        {"ts": 2.0, "cause": "membership", "action": "shrink", "step": 4,
         "epoch": 1, "world": 3},
    ]
    steps = _steps(range(1, 5), epoch=0) + _steps(range(5, 9), epoch=1)
    _mk_run(tmp_path, steps=steps, incidents=incidents,
            membership=membership)
    doc = build_report(str(tmp_path))
    checks = {c["name"]: c for c in doc["checks"]}
    assert checks["membership_incidents_agree"]["ok"] is True
    assert not checks["membership_incidents_agree"]["skipped"]
    assert checks["membership_column_agrees"]["ok"] is True

    # now break both: drop the shrink incident, mis-stamp one record
    bad = tmp_path / "bad"
    bad.mkdir()
    _mk_run(
        bad,
        steps=_steps(range(1, 5), epoch=0) + _steps(range(5, 9), epoch=0),
        incidents=incidents[:1],
        membership=membership,
    )
    doc2 = build_report(str(bad))
    checks2 = {c["name"]: c for c in doc2["checks"]}
    assert checks2["membership_incidents_agree"]["ok"] is False
    assert checks2["membership_column_agrees"]["ok"] is False


def test_report_retune_column_check(tmp_path):
    incidents = [{
        "ts": 1.0, "cause": "perf_drift", "action": "retune->ring",
        "step": 4, "mode": "gather",
    }]
    ok_steps = _steps(range(1, 5), aggregate="gather") + _steps(
        range(5, 9), aggregate="ring"
    )
    _mk_run(tmp_path, steps=ok_steps, incidents=incidents)
    doc = build_report(str(tmp_path))
    checks = {c["name"]: c for c in doc["checks"]}
    assert checks["retunes_visible"]["ok"] is True
    assert not checks["retunes_visible"]["skipped"]

    bad = tmp_path / "bad"
    bad.mkdir()
    _mk_run(bad, steps=_steps(range(1, 9), aggregate="gather"),
            incidents=incidents)
    doc2 = build_report(str(bad))
    checks2 = {c["name"]: c for c in doc2["checks"]}
    assert checks2["retunes_visible"]["ok"] is False


def test_report_cli_verb(tmp_path):
    from atomo_tpu.cli import main

    _mk_run(tmp_path, steps=_steps(range(1, 4)))
    rc = main(["report", "--train-dir", str(tmp_path)])
    assert rc == 0
    doc = json.load(open(tmp_path / "run_report.json"))
    assert doc["kind"] == "run_report" and doc["consistent"] is True
    # --strict surfaces inconsistency as rc=3
    _mk_run(tmp_path, steps=_steps(range(1, 4)) + _steps(range(2, 5)))
    assert main(["report", "--train-dir", str(tmp_path),
                 "--strict"]) == 3


def test_report_missing_dir_is_config_error(tmp_path):
    from atomo_tpu.cli import main

    with pytest.raises(SystemExit, match="does not exist"):
        main(["report", "--train-dir", str(tmp_path / "nope")])


# ------------------------------------------------ end-to-end (in-process)


def test_cli_obs_run_records_and_reports(tmp_path):
    """The whole path through the CLI: a 4-device run with recorder +
    quality armed leaves a parsing metrics.jsonl whose records carry the
    per-layer columns, and the report verb finds it consistent."""
    from atomo_tpu.cli import main

    rc = main([
        "train", "--synthetic", "--dataset", "mnist", "--network", "lenet",
        "--batch-size", "8", "--max-steps", "4", "--eval-freq", "0",
        "--save-freq", "2", "--log-interval", "2", "--n-devices", "4",
        "--code", "qsgd", "--quantization-level", "8",
        "--aggregate", "gather", "--train-dir", str(tmp_path),
        "--obs-record", "--obs-quality", "--momentum", "0.0",
    ])
    assert rc == 0
    steps = FlightRecorder.read_steps(metrics_path(str(tmp_path)))
    assert [r["step"] for r in steps] == [1, 2, 3, 4]
    for r in steps:
        assert r["aggregate"] == "gather"
        assert r["step_ms"] > 0
        assert len(r["q_rel"]) == len(r["q_err2"]) > 0
    metas = [
        r for r in FlightRecorder.read(metrics_path(str(tmp_path)))
        if r["kind"] == "meta"
    ]
    assert len(metas) == 1 and metas[0]["what"] == "obs_quality"
    assert len(metas[0]["layers"]) == len(steps[0]["q_rel"])
    assert main(["report", "--train-dir", str(tmp_path),
                 "--strict"]) == 0


def test_cli_obs_quality_rejects_dense_code(tmp_path):
    from atomo_tpu.cli import main

    with pytest.raises(SystemExit, match="no estimator"):
        main([
            "train", "--synthetic", "--dataset", "mnist", "--network",
            "lenet", "--batch-size", "8", "--max-steps", "1",
            "--n-devices", "1", "--train-dir", str(tmp_path),
            "--obs-quality",
        ])


# --------------------------------------------- the supervised die@ drill


def _cli_obs_drill(train_dir, *extra, timeout=240):
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=4",
        JAX_ENABLE_COMPILATION_CACHE="false",  # (the conftest default)
    )
    cmd = [
        sys.executable, "-m", "atomo_tpu.cli", "train",
        "--synthetic", "--dataset", "mnist", "--network", "lenet",
        "--batch-size", "12", "--eval-freq", "0", "--save-freq", "2",
        "--log-interval", "1", "--code", "qsgd", "--quantization-level",
        "8", "--aggregate", "gather", "--grad-guard", "--elastic",
        "--elastic-patience", "2", "--train-dir", str(train_dir),
        "--obs-record", *extra,
    ]
    return subprocess.run(
        cmd, env=env, capture_output=True, text=True, timeout=timeout,
        cwd=_REPO_ROOT,
    )


@pytest.mark.slow
def test_supervised_die_drill_report_is_consistent(tmp_path):
    """The acceptance drill: a supervised die@3:1 elastic run with the
    recorder armed yields a metrics.jsonl + report whose timeline agrees
    with incidents.jsonl and membership.json under the report's own
    consistency checks — membership checks RAN (not skipped) and the
    epoch column tracks the reshape."""
    d = tmp_path / "drill"
    p = _cli_obs_drill(
        d, "--n-devices", "4", "--max-steps", "8",
        "--chaos", "die@3:1", "--max-restarts", "1",
        "--restart-backoff", "0.05",
    )
    assert p.returncode == 0, (p.stdout[-2000:], p.stderr[-2000:])
    doc = build_report(str(d))
    checks = {c["name"]: c for c in doc["checks"]}
    assert doc["consistent"], checks
    for name in ("membership_incidents_agree", "membership_column_agrees",
                 "metrics_monotone"):
        assert not checks[name]["skipped"], name
        assert checks[name]["ok"], checks[name]
    steps = FlightRecorder.read_steps(metrics_path(str(d)))
    assert [r["step"] for r in steps] == list(range(1, 9))
    epochs = sorted({r["epoch"] for r in steps})
    assert epochs == [0, 1]  # the shrink is visible in the step stream
    membership = [
        e for e in doc["timeline"] if e["kind"] == "membership"
    ]
    assert [m["epoch"] for m in membership] == [0, 1]
    # the report verb round-trips through the CLI too
    rc = subprocess.run(
        [sys.executable, "-m", "atomo_tpu.cli", "report", "--train-dir",
         str(d), "--strict"],
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=120, cwd=_REPO_ROOT,
    )
    assert rc.returncode == 0, rc.stdout[-2000:]
    assert "membership epoch 1: world 3" in rc.stdout


@pytest.mark.slow
def test_sigkill_mid_run_leaves_parseable_metrics(tmp_path):
    """SIGKILL the training process mid-run: metrics.jsonl must parse
    (torn tail skipped) and the report must still build."""
    d = tmp_path / "killed"
    env = dict(
        os.environ, JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=4",
    )
    proc = subprocess.Popen(
        [
            sys.executable, "-m", "atomo_tpu.cli", "train",
            "--synthetic", "--dataset", "mnist", "--network", "lenet",
            "--batch-size", "8", "--max-steps", "500", "--eval-freq", "0",
            "--save-freq", "50", "--log-interval", "1", "--n-devices", "4",
            "--code", "qsgd", "--quantization-level", "8",
            "--aggregate", "gather", "--train-dir", str(d),
            "--obs-record",
        ],
        env=env, cwd=_REPO_ROOT,
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
    )
    path = metrics_path(str(d))
    try:
        for _ in range(120):
            if os.path.exists(path) and len(
                FlightRecorder.read_steps(path)
            ) >= 3:
                break
            time.sleep(1)
        else:
            pytest.fail("recorder produced no records before the kill")
    finally:
        proc.kill()
        proc.wait()
    steps = FlightRecorder.read_steps(path)
    assert steps and all("loss" in r for r in steps)
    doc = build_report(str(d))
    checks = {c["name"]: c for c in doc["checks"]}
    assert checks["metrics_monotone"]["ok"], checks
