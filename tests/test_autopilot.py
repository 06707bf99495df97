"""Performance autopilot (PR 7): predictor ranking sanity, decision
determinism, preflight pinned-knob rejection, calibration honesty, the
step-time drift detector, the online re-tuner's protocol, the LR grid's
artifact, and the acceptance drill — a ``--auto tune`` run on the forced
4-device CPU mesh whose trajectory is bit-identical to launching the
chosen config statically (subprocess, slow-marked)."""

import json
import math
import os
import subprocess
import sys

import numpy as np
import pytest

from atomo_tpu.training.resilience import (
    DriftConfig,
    DriftState,
    drift_scan,
    drift_update,
)
from atomo_tpu.tuning.autopilot import OnlineRetuner, choose_winner, winner_knobs
from atomo_tpu.utils.comm_model import (
    calibration_warning,
    candidate_name,
    choose_aggregate,
    enumerate_candidates,
    predict_step_s,
    rank_candidates,
    recommend_for_scenario,
    resolve_fabric,
)

_HERE = os.path.dirname(os.path.abspath(__file__))
_REPO_ROOT = os.path.dirname(_HERE)


# ---------------------------------------------------------------- predictor


def test_enumerate_candidates_respects_conflict_matrix():
    # single device: only the superstep knob exists
    one = enumerate_candidates(has_codec=True, ways=1)
    assert all("aggregate" not in c for c in one)
    assert {c["superstep"] for c in one} == {1, 8}
    # dense code: psum only, never delayed
    dense = enumerate_candidates(has_codec=False, ways=4)
    assert {c["aggregate"] for c in dense} == {"psum"}
    assert all(c["overlap"] == "off" for c in dense)
    # compressed multi-device: delayed exists only for gather/ring
    full = enumerate_candidates(has_codec=True, ways=4)
    assert all(
        c["aggregate"] in ("gather", "ring")
        for c in full if c["overlap"] == "delayed"
    )
    # the allow_* narrowing used for densify/zero1/num-aggregate configs
    no_delayed = enumerate_candidates(
        has_codec=True, ways=4, allow_overlap=False, allow_psum=False
    )
    assert all(c["overlap"] == "off" for c in no_delayed)
    assert all(c["aggregate"] != "psum" for c in no_delayed)
    # names are unique (they are the artifact's candidate identity)
    names = [c["name"] for c in full]
    assert len(names) == len(set(names))


def test_predictor_ranking_agrees_with_choose_aggregate():
    """The blocking candidates' predicted order must agree with the
    established ``choose_aggregate`` wire-byte logic in both regimes: the
    gather-wins region (N < 2x byte reduction) and the psum-wins region
    (N past it)."""
    dense_b, ways = 44.7e6, 4
    for payload_b, expect in ((1.0e6, "gather"), (30.0e6, "psum")):
        mode, _ = choose_aggregate(
            has_codec=True, dense_bytes=dense_b, payload_bytes=payload_b,
            ways=ways, fabric_bw=1.25e9, tax_s=2.5e-3,
        )
        assert mode.split("+")[0] in (expect, "ring"), mode
        cands = [
            c for c in enumerate_candidates(has_codec=True, ways=ways)
            if c["overlap"] == "off" and c["superstep"] == 1
            and c["aggregate"] in ("gather", "psum")
        ]
        ranked = rank_candidates(
            cands, dense_bytes=dense_b, payload_bytes=payload_b,
            ways=ways, fabric_bw=1.25e9, tax_s=2.5e-3, compute_s=5e-3,
        )
        assert ranked[0]["aggregate"] == expect, (payload_b, ranked)


def test_predictor_overlap_hides_chain_and_superstep_amortizes():
    ctx = dict(
        dense_bytes=44.7e6, payload_bytes=1e6, ways=4, fabric_bw=1.25e9,
        compute_s=10e-3, tax_s=2e-3,
    )
    blocking = predict_step_s(
        {"aggregate": "gather", "overlap": "off", "superstep": 1}, **ctx
    )
    delayed = predict_step_s(
        {"aggregate": "gather", "overlap": "delayed", "superstep": 1}, **ctx
    )
    # the chain fits under 10 ms of compute: delayed = compute + encode
    assert delayed < blocking
    assert delayed == pytest.approx(10e-3 + 1e-3)
    k1 = predict_step_s(
        {"aggregate": "gather", "overlap": "off", "superstep": 1},
        dispatch_s=3e-3, **ctx,
    )
    k8 = predict_step_s(
        {"aggregate": "gather", "overlap": "off", "superstep": 8},
        dispatch_s=3e-3, **ctx,
    )
    assert k1 - k8 == pytest.approx(3e-3 * 7 / 8)


def test_resolve_fabric_contract():
    assert resolve_fabric("ici") == 45e9
    assert resolve_fabric("auto", n_proc=1) == 45e9
    assert resolve_fabric("auto", n_proc=2) == 6.25e9
    assert resolve_fabric("2.5") == pytest.approx(2.5e9)
    for bad in ("nope", "-1", "inf", "nan", ""):
        with pytest.raises(ValueError):
            resolve_fabric(bad)


def test_calibration_warning_is_two_sided_and_bounded():
    assert calibration_warning(10e-3, 15e-3) is None  # 1.5x: fine
    up = calibration_warning(10e-3, 25e-3, "slow")
    down = calibration_warning(25e-3, 10e-3, "fast")
    assert up and "25.00 ms/step" in up and "10.00 ms/step" in up
    assert down and "2.5x" in down
    assert calibration_warning(0.0, 10e-3) is None  # nothing to compare
    assert calibration_warning(10e-3, float("nan")) is None


def test_recommend_for_scenario_is_pure_and_uses_measured_tax():
    budgets = {"dense": (44.7e6, 0), "qsgd8": (44.7e6, 15.1e6),
               "svd3": (44.7e6, 0.95e6)}
    measured = {"dense": 6.5, "qsgd8": 9.0, "svd3": 9.0}
    a = recommend_for_scenario(
        codec_budgets=budgets, measured_ms=measured, ways=8,
        fabric_bw=1.25e9,
    )
    b = recommend_for_scenario(
        codec_budgets=dict(reversed(list(budgets.items()))),
        measured_ms=measured, ways=8, fabric_bw=1.25e9,
    )
    assert a == b  # pure + order-independent
    # measured tax = measured codec step - measured dense step
    svd = next(r for r in a["ranked"] if r["code"] == "svd3")
    assert svd["codec_tax_ms"] == pytest.approx(2.5)
    with pytest.raises(ValueError, match="dense"):
        recommend_for_scenario(
            codec_budgets=budgets, measured_ms={"qsgd8": 9.0}, ways=8,
            fabric_bw=1.25e9,
        )


# ----------------------------------------------------------- decision layer


def _rows():
    return [
        {"name": "gather+off+k1", "aggregate": "gather", "overlap": "off",
         "superstep": 1, "probed": True, "sync_ok": True,
         "predicted_ms_per_step": 11.0, "measured_ms_per_step": 14.0},
        {"name": "ring+off+k1+b65536", "aggregate": "ring",
         "overlap": "off", "superstep": 1, "ring_bucket_size": 65536,
         "probed": True, "sync_ok": True,
         "predicted_ms_per_step": 12.0, "measured_ms_per_step": 13.0},
        {"name": "psum+off+k8", "aggregate": "psum", "overlap": "off",
         "superstep": 8, "probed": False,
         "predicted_ms_per_step": 9.0},
    ]


def test_choose_winner_is_deterministic_and_order_independent():
    rows = _rows()
    w1 = choose_winner(rows)
    w2 = choose_winner(list(reversed(rows)))
    assert w1["name"] == w2["name"] == "ring+off+k1+b65536"
    # same artifact re-read from JSON round-trip => same winner
    again = choose_winner(json.loads(json.dumps(rows)))
    assert again["name"] == w1["name"]
    assert winner_knobs(w1) == {
        "aggregate": "ring", "overlap": "off", "superstep": 1,
        "ring_bucket_size": 65536,
    }


def test_choose_winner_measured_beats_predicted_and_falls_back():
    rows = _rows()
    # an unprobed 9.0-predicted row must NOT beat a measured 13.0 row
    assert choose_winner(rows)["name"] == "ring+off+k1+b65536"
    # no valid measurement anywhere -> prediction decides
    for r in rows:
        r.pop("measured_ms_per_step", None)
        r["probed"] = False
    assert choose_winner(rows)["name"] == "psum+off+k8"
    # a non-finite measurement is not a measurement
    rows = _rows()
    rows[1]["measured_ms_per_step"] = float("nan")
    assert choose_winner(rows)["name"] == "gather+off+k1"
    # sync_ok=False rows are excluded from the measured pool
    rows = _rows()
    rows[1]["sync_ok"] = False
    assert choose_winner(rows)["name"] == "gather+off+k1"
    # ...and when EVERY probe is sync-invalid, the prediction decides —
    # an invalid measurement must not sneak back in via the fallback
    rows = _rows()
    for r in rows:
        r["sync_ok"] = False
    assert choose_winner(rows)["name"] == "psum+off+k8"
    assert choose_winner([]) is None


def test_tune_survives_a_failing_candidate_probe(monkeypatch, tmp_path):
    """One candidate OOMing/failing to compile must not abort the tune:
    the failure is recorded as a row and the ladder continues to a
    winner (review finding)."""
    import atomo_tpu.tuning.autopilot as ap

    calls = {"n": 0}

    def fake_probe(cand, **kw):
        calls["n"] += 1
        if calls["n"] == 1:
            raise RuntimeError("XlaRuntimeError: out of memory")
        return {
            **cand, "probed": True, "sync_ok": True,
            "measured_ms_per_step": 10.0 + calls["n"],
            "probe_wall_s": 0.1,
        }

    monkeypatch.setattr("atomo_tpu.tuning.probe.probe_candidate",
                        fake_probe)
    import jax.numpy as jnp

    from atomo_tpu.codecs import QsgdCodec
    from atomo_tpu.models import get_model
    from atomo_tpu.training import make_optimizer
    from atomo_tpu.tuning.probe import model_init_fn

    model = get_model("lenet", 10)
    doc = ap.tune(
        model=model,
        optimizer=make_optimizer("sgd", lr=0.01, momentum=0.9),
        codec=QsgdCodec(bits=8, bucket_size=512),
        model_init_fn=model_init_fn(
            model, jnp.zeros((1, 28, 28, 1), jnp.float32)
        ),
        n_dev=4, sample_shape=(28, 28, 1), num_classes=10, batch=8,
        artifact_path=str(tmp_path / "td.json"),
        probe_top=3, probe_steps=1, probe_reps=1,
        log_fn=lambda *_: None,
    )
    failed = [r for r in doc["rows"] if r.get("probe_error")]
    assert len(failed) == 1 and "out of memory" in failed[0]["probe_error"]
    assert doc["complete"] is True
    assert doc["winner"]["name"] not in {failed[0]["name"]}
    assert doc["winner"]["measured_ms_per_step"] is not None


def test_candidate_name_round_trip():
    c = {"aggregate": "ring", "overlap": "delayed", "superstep": 8,
         "ring_bucket_size": 1024}
    assert candidate_name(c) == "ring+delayed+k8+b1024"
    assert candidate_name({"superstep": 1}) == "k1"


# ------------------------------------------------------------ drift detector


def test_drift_detector_alarms_on_sustained_drift_only():
    cfg = DriftConfig(window=8, ratio=1.5, patience=3, min_history=4)
    st = DriftState()
    for _ in range(10):
        st, a = drift_update(cfg, st, 0.010)
        assert a is None
    # a single spike is noise
    st, a = drift_update(cfg, st, 0.030)
    assert a is None
    st, a = drift_update(cfg, st, 0.010)
    assert a is None and st.hot == 0
    # sustained 2x drift fires after `patience` consecutive observations
    alarms = []
    for _ in range(3):
        st, a = drift_update(cfg, st, 0.022)
        alarms.append(a)
    assert alarms == [None, None, "step_time_drift"]


def test_drift_baseline_frozen_while_hot():
    cfg = DriftConfig(window=8, ratio=1.5, patience=50, min_history=2)
    st = DriftState()
    for _ in range(5):
        st, _ = drift_update(cfg, st, 0.010)
    base = st.mean
    for _ in range(20):
        st, _ = drift_update(cfg, st, 0.050)
    # the drifting series must NOT be absorbed into its own baseline
    assert st.mean == base
    assert st.hot == 20


def test_drift_baseline_sheds_compile_inflated_seed_fast():
    """The first observation of a cold run is compile-dominated (can be
    1000x a steady step). The floor-tracking baseline must shed it within
    ~a dozen steps so genuine drift early in training still alarms
    (review finding: a symmetric window-32 EMA needed ~130 steps, during
    which real 2x drift was silently absorbed)."""
    cfg = DriftConfig(window=32, ratio=1.5, patience=3, min_history=8)
    st = DriftState()
    st, _ = drift_update(cfg, st, 20.0)  # the compile step
    for _ in range(14):
        st, _ = drift_update(cfg, st, 0.010)
    assert st.mean < 0.015  # baseline recovered to ~the steady floor
    alarms = []
    for _ in range(3):
        st, a = drift_update(cfg, st, 0.025)  # genuine sustained 2.5x
        alarms.append(a)
    assert alarms[-1] == "step_time_drift"


def test_drift_scan_matches_sequential_fold_and_skips_garbage():
    cfg = DriftConfig(window=8, ratio=1.5, patience=3, min_history=2)
    series = [0.01] * 6 + [float("nan"), -1.0] + [0.03] * 3
    st_seq = DriftState()
    last = None
    for x in series:
        st_seq, a = drift_update(cfg, st_seq, x)
        last = a or last
    st_blk, a_blk = drift_scan(cfg, DriftState(), series)
    assert st_blk == st_seq
    assert a_blk == last == "step_time_drift"


def test_drift_config_validation():
    with pytest.raises(ValueError):
        DriftConfig(window=1)
    with pytest.raises(ValueError):
        DriftConfig(ratio=1.0)
    with pytest.raises(ValueError):
        DriftConfig(patience=0)


# ------------------------------------------------------------ online retuner


class _Log:
    def __init__(self):
        self.records = []

    def append(self, cause, **kw):
        self.records.append({"cause": cause, **kw})


def _drifted(tuner):
    """Feed a clean baseline then a sustained excursion."""
    for _ in range(10):
        tuner.observe(0.010)
    for _ in range(tuner.cfg.patience):
        tuner.observe(0.030)


def test_retuner_switches_at_boundary_and_logs_incident():
    log = _Log()
    probes = {"gather": 20.0, "ring": 12.0}
    tuner = OnlineRetuner(
        probe_fn=probes.__getitem__,
        drift=DriftConfig(window=8, ratio=1.5, patience=3, min_history=4),
        incidents=log, log_fn=lambda *_: None,
    )
    assert tuner.maybe_retune(5, "gather") is None  # nothing pending
    _drifted(tuner)
    assert tuner.pending == "step_time_drift"
    new = tuner.maybe_retune(10, "gather")
    assert new == "ring"
    assert tuner.pending is None
    rec = log.records[-1]
    assert rec["cause"] == "perf_drift" and rec["action"] == "retune->ring"
    assert rec["step"] == 10 and rec["mode"] == "gather"
    assert set(rec["measured_ms"]) == {"gather", "ring"}
    # the drift baseline restarts after a decision
    assert tuner.state == DriftState()


def test_retuner_keeps_config_within_margin_and_observe_only_mode():
    log = _Log()
    # 3% apart: inside the 5% switch margin -> keep
    tuner = OnlineRetuner(
        probe_fn={"gather": 10.0, "ring": 9.7}.__getitem__,
        drift=DriftConfig(window=8, ratio=1.5, patience=3, min_history=4),
        incidents=log, log_fn=lambda *_: None,
    )
    _drifted(tuner)
    assert tuner.maybe_retune(10, "gather") is None
    assert log.records[-1]["action"] == "retune_keep"
    # observe-only (no probe_fn): drift recorded, config kept
    log2 = _Log()
    t2 = OnlineRetuner(
        probe_fn=None,
        drift=DriftConfig(window=8, ratio=1.5, patience=3, min_history=4),
        incidents=log2, log_fn=lambda *_: None,
    )
    _drifted(t2)
    assert t2.maybe_retune(8, "local") is None
    assert log2.records[-1]["action"] == "observed"
    # a mode outside the bit-identical pair is never switched
    log3 = _Log()
    t3 = OnlineRetuner(
        probe_fn=lambda m: 1.0,
        drift=DriftConfig(window=8, ratio=1.5, patience=3, min_history=4),
        incidents=log3, log_fn=lambda *_: None,
    )
    _drifted(t3)
    assert t3.maybe_retune(8, "psum") is None
    assert log3.records[-1]["action"] == "observed"


def test_retune_defers_while_rollback_remedy_active():
    """The rig reports an open remedy window so the loop's re-probe can
    defer: a default rebuild mid-rewarm/densify would silently drop the
    doctor's remedy from the program (review finding)."""
    from atomo_tpu.training.resilience import (
        DetectorConfig,
        DivergeConfig,
        DivergenceDoctor,
        RecoveryRig,
    )

    def _rig(remedy):
        cfg = DivergeConfig(
            remedy=remedy, detector=DetectorConfig(window=4),
            max_rollbacks=2,
        )
        return RecoveryRig(
            DivergenceDoctor(cfg, train_dir=None, log_fn=lambda *_: None),
            cfg,
            reload_state=lambda t: "state",
            restream=lambda t: iter(()),
            build_step=lambda *a, **k: "step_fn",
        )

    rig = _rig("rewarm")
    assert not rig.remedy_active(3)  # nothing rolled back yet
    rig.rollback(5, "loss_zscore")  # target 0 (no train_dir), window 4
    assert rig.remedy_active(0) and rig.remedy_active(3)
    assert not rig.remedy_active(4)  # ramp saturated: rebuild is identity

    rig = _rig("densify")
    rig.rollback(5, "loss_zscore")
    assert rig.remedy_active(3) and rig.densify_until == 4
    assert rig.maybe_end_densify(4) == "step_fn"
    assert not rig.remedy_active(3)  # window closed, densify cleared

    rig = _rig("skip")
    rig.rollback(5, "loss_zscore")
    assert not rig.remedy_active(1)  # skip changes nothing in the program


def test_retuner_survives_probe_failure():
    log = _Log()

    def bad_probe(mode):
        raise RuntimeError("mesh on fire")

    tuner = OnlineRetuner(
        probe_fn=bad_probe,
        drift=DriftConfig(window=8, ratio=1.5, patience=3, min_history=4),
        incidents=log, log_fn=lambda *_: None,
    )
    _drifted(tuner)
    assert tuner.maybe_retune(10, "gather") is None  # keep, don't crash
    assert log.records[-1]["action"] == "retune_keep"


# ----------------------------------------------------------- CLI preflight


def _preflight(argv):
    from atomo_tpu.cli import _argv_preflight, build_parser

    parser = build_parser()
    sub = next(
        a for a in parser._actions if hasattr(a, "choices") and a.choices
    )
    return _argv_preflight(sub.choices["train"].parse_args(argv))


@pytest.mark.parametrize(
    "pinned",
    [
        ["--aggregate", "ring"],
        ["--overlap", "delayed", "--code", "svd", "--n-devices", "4"],
        ["--superstep", "4"],
    ],
)
def test_preflight_rejects_auto_tune_with_pinned_knobs(pinned):
    with pytest.raises(SystemExit, match="pin"):
        _preflight(["--auto", "tune", "--train-dir", "d"] + pinned)


def test_preflight_auto_tune_other_conflicts_and_acceptance():
    with pytest.raises(SystemExit, match="train-dir"):
        _preflight(["--auto", "tune", "--train-dir", ""])
    # the clean form passes preflight (superstep 0 = auto is not a pin)
    assert _preflight(["--auto", "tune", "--train-dir", "d"]) is None
    assert _preflight(
        ["--auto", "tune", "--train-dir", "d", "--code", "qsgd",
         "--n-devices", "4", "--zero1"]
    ) is None
    # ring bucket size is a bit-identical LAYOUT knob: pinning it composes
    # with --auto tune (the ring candidates probe the pinned packing)
    assert _preflight(
        ["--auto", "tune", "--train-dir", "d",
         "--ring-bucket-size", "1024"]
    ) is None
    pinned_buckets = enumerate_candidates(
        has_codec=True, ways=4, bucket_options=(1024,)
    )
    assert {
        c["ring_bucket_size"]
        for c in pinned_buckets if c["aggregate"] == "ring"
    } == {1024}


# ------------------------------------------------------- grid-search artifact


def test_grid_search_writes_partial_json_artifact(tmp_path, capsys):
    from atomo_tpu.cli import main

    art = tmp_path / "grid.json"
    rc = main([
        "tune", "--synthetic", "--dataset", "mnist", "--network", "LeNet",
        "--batch-size", "8", "--tuning-steps", "2", "--window", "2",
        "--grid", "0.1,0.01", "--train-dir", str(tmp_path),
        "--artifact", str(art), "--eval-freq", "0",
    ])
    assert rc == 0
    out = capsys.readouterr().out
    assert "best lr:" in out  # the regex-parsed log contract is intact
    doc = json.loads(art.read_text())
    assert doc["kind"] == "lr_grid" and doc["complete"] is True
    assert [r["lr"] for r in doc["rows"]] == [0.1, 0.01]
    for r in doc["rows"]:
        assert r["mean_loss"] is None or math.isfinite(r["mean_loss"])
        assert r["wall_s"] > 0
    assert doc["best"]["lr"] in (0.1, 0.01)
    # printed scores and artifact rows agree (one contract, two surfaces)
    for r in doc["rows"]:
        if r["mean_loss"] is not None:
            assert f"lr {r['lr']:g}: mean loss {r['mean_loss']:.4f}" in out


# ----------------------------------------------- acceptance drill (slow)


def _run_cli(argv, timeout=420):
    env = {
        **os.environ,
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
        "PYTHONPATH": _REPO_ROOT + os.pathsep + os.environ.get(
            "PYTHONPATH", ""
        ),
    }
    return subprocess.run(
        [sys.executable, "-m", "atomo_tpu.cli"] + argv,
        env=env, capture_output=True, text=True, timeout=timeout,
    )


@pytest.mark.slow
def test_auto_tune_trajectory_bit_identical_to_static(tmp_path):
    """The PR-7 acceptance drill: on the forced 4-dev CPU mesh,
    ``--auto tune`` probes, writes a complete tune_decision.json with
    predicted-vs-measured ms/step for every candidate, and the
    subsequent trajectory is bit-identical to launching the chosen
    config statically."""
    import jax
    import jax.numpy as jnp

    tuned = tmp_path / "tuned"
    static = tmp_path / "static"
    common = [
        "train", "--synthetic", "--dataset", "mnist", "--network",
        "LeNet", "--batch-size", "8", "--max-steps", "4", "--eval-freq",
        "0", "--save-freq", "2", "--log-interval", "1", "--n-devices",
        "4", "--code", "qsgd", "--quantization-level", "8", "--seed", "3",
    ]
    p = _run_cli(common + [
        "--train-dir", str(tuned), "--auto", "tune", "--tune-steps", "2",
        "--tune-reps", "1", "--tune-top", "2",
    ])
    assert p.returncode == 0, p.stderr[-3000:]
    doc = json.loads((tuned / "tune_decision.json").read_text())
    assert doc["complete"] is True
    win = doc["winner"]
    assert win and win["name"] and win["knobs"], doc
    # every candidate row carries a prediction; probed ones a measurement
    for r in doc["rows"]:
        assert isinstance(r.get("predicted_ms_per_step"), (int, float)), r
        if r.get("probed"):
            assert isinstance(r.get("measured_ms_per_step"), (int, float)), r
    # determinism: the artifact's rows re-decide to the same winner
    from atomo_tpu.tuning.autopilot import choose_winner as cw

    assert cw(doc["rows"])["name"] == win["name"]

    # the static equivalent: the winner's knobs as explicit flags
    knobs = win["knobs"]
    static_args = common + ["--train-dir", str(static)]
    if "aggregate" in knobs:
        static_args += ["--aggregate", knobs["aggregate"]]
    if knobs.get("overlap", "off") != "off":
        static_args += ["--overlap", knobs["overlap"]]
    static_args += ["--superstep", str(knobs.get("superstep", 1))]
    if "ring_bucket_size" in knobs:
        static_args += ["--ring-bucket-size",
                        str(knobs["ring_bucket_size"])]
    p2 = _run_cli(static_args)
    assert p2.returncode == 0, p2.stderr[-3000:]

    # final checkpoints must match BIT FOR BIT (params, opt state, BN
    # stats, and — when the winner is delayed — the in-flight payload)
    from atomo_tpu.codecs import QsgdCodec
    from atomo_tpu.models import get_model
    from atomo_tpu.training import create_state, make_optimizer
    from atomo_tpu.training.checkpoint import load_checkpoint

    model = get_model("lenet", 10)
    opt = make_optimizer(
        "sgd", lr=0.01, lr_shrinkage=0.95, shrinkage_freq=50, momentum=0.5
    )
    tpl = jax.device_get(create_state(
        model, opt, jax.random.PRNGKey(3), jnp.zeros((8, 28, 28, 1))
    ))
    if knobs.get("overlap") == "delayed":
        from atomo_tpu.parallel.replicated import (
            DelayedState,
            _zero_carry_host,
        )

        tpl = DelayedState(
            train=tpl,
            carry=_zero_carry_host(
                QsgdCodec(bits=8, bucket_size=512), tpl.params, 4
            ),
        )
    a = load_checkpoint(str(tuned), tpl, step=4)
    b = load_checkpoint(str(static), tpl, step=4)
    la = jax.tree_util.tree_leaves(a)
    lb = jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    assert all(
        np.array_equal(np.asarray(x), np.asarray(y))
        for x, y in zip(la, lb)
    ), "tuned trajectory is not bit-identical to the static equivalent"

    # a resumed tuned run (the supervised-restart path) must reuse the
    # recorded decision instead of re-probing: probe timings vary, and a
    # different winner could not resume this program family's checkpoints
    p3 = _run_cli(common + [
        "--train-dir", str(tuned), "--auto", "tune", "--tune-steps", "2",
        "--tune-reps", "1", "--tune-top", "2", "--max-steps", "6",
        "--resume",
    ])
    assert p3.returncode == 0, p3.stderr[-3000:]
    assert "resuming with the recorded decision" in p3.stdout
    assert "Autopilot probe [" not in p3.stdout  # no re-probe happened
    assert f"--auto tune -> {win['name']}" in p3.stdout


@pytest.mark.slow
def test_distributed_loop_retunes_on_injected_drift(tmp_path):
    """Loop wiring: a tuner whose drift detector is primed to fire sees
    the re-probe executed at the next checkpoint boundary, the incident
    logged, and the step program rebuilt onto the probed-better mode."""
    import jax

    from atomo_tpu.codecs import QsgdCodec
    from atomo_tpu.data import BatchIterator, SPECS, synthetic_dataset
    from atomo_tpu.models import get_model
    from atomo_tpu.parallel import distributed_train_loop, make_mesh
    from atomo_tpu.training import make_optimizer
    from atomo_tpu.utils.tracing import IncidentLog

    model = get_model("lenet", 10)
    opt = make_optimizer("sgd", lr=0.01, momentum=0.9)
    ds = synthetic_dataset(SPECS["mnist"], True, size=64)
    it = BatchIterator(ds, 8, seed=0)
    mesh = make_mesh(4)
    # a probe that always says ring is faster, and a PRE-ARMED pending
    # alarm (real wall-times FALL after the compile head, so a genuine
    # drift cannot be staged in a 6-step run — the detector math itself
    # is covered by the pure-fold tests above): the loop must execute
    # the re-probe at the first save boundary and flip gather -> ring
    tuner = OnlineRetuner(
        probe_fn={"gather": 50.0, "ring": 1.0}.__getitem__,
    )
    tuner.pending = "step_time_drift"
    distributed_train_loop(
        model, opt, mesh, it,
        codec=QsgdCodec(bits=8, bucket_size=512), aggregate="gather",
        max_steps=6, eval_freq=0, save_freq=2, seed=0,
        train_dir=str(tmp_path), log_fn=lambda *_: None, tuner=tuner,
    )
    recs = IncidentLog.read(str(tmp_path / "incidents.jsonl"))
    drift = [r for r in recs if r["cause"] == "perf_drift"]
    assert drift, recs
    assert drift[0]["action"] == "retune->ring"
    assert drift[0]["step"] % 2 == 0  # snapped to the save cadence
    assert tuner.switches == 1


def test_tune_error_feedback_probes_narrowed_space(monkeypatch, tmp_path):
    """EF x autopilot (ISSUE-17 satellite): --error-feedback runs ARE
    tunable — the ladder narrows to the flat blocking programs EF
    composes with, every probe builds the EF step, and the bias
    contract is recorded (rows + meta carry error_feedback="on"; probed
    rows carry the wall-clock-only probe_note)."""
    import atomo_tpu.tuning.autopilot as ap

    seen_ef = []

    def fake_probe(cand, **kw):
        seen_ef.append(kw.get("error_feedback"))
        return {
            **cand, "probed": True, "sync_ok": True,
            "measured_ms_per_step": 10.0 + len(cand["name"]),
            "probe_wall_s": 0.1,
        }

    monkeypatch.setattr("atomo_tpu.tuning.probe.probe_candidate",
                        fake_probe)
    import jax.numpy as jnp

    from atomo_tpu.codecs import QsgdCodec
    from atomo_tpu.models import get_model
    from atomo_tpu.training import make_optimizer
    from atomo_tpu.tuning.probe import model_init_fn

    model = get_model("lenet", 10)
    narrowed = []
    common = dict(
        model=model,
        optimizer=make_optimizer("sgd", lr=0.01, momentum=0.9),
        codec=QsgdCodec(bits=8, bucket_size=512),
        model_init_fn=model_init_fn(
            model, jnp.zeros((1, 28, 28, 1), jnp.float32)
        ),
        n_dev=4, sample_shape=(28, 28, 1), num_classes=10, batch=8,
        probe_top=3, probe_steps=1, probe_reps=1,
    )
    doc = ap.tune(
        artifact_path=str(tmp_path / "td.json"),
        error_feedback=True,
        # ask for everything EF conflicts with: the tuner must narrow
        # out loud, not build programs the step builder would refuse
        allow_overlap=True, allow_stream=True,
        allow_quorum=True, quorum_q=3,
        log_fn=narrowed.append,
        **common,
    )
    assert any("narrows the candidate space" in str(m) for m in narrowed)
    assert doc["complete"] is True
    assert doc["meta"]["error_feedback"] == "on"
    assert seen_ef and all(v is True for v in seen_ef)
    for r in doc["rows"]:
        assert r["error_feedback"] == "on"
        assert r["overlap"] == "off"
        # stream encode composes with the residual carry and stays in;
        # the conflict-matrix axes are out
        assert "+q" not in r["name"] and "+sp" not in r["name"]
        assert "hier[" not in r["name"]
        if r.get("probed"):
            assert "wall-clock only" in r["probe_note"]
    assert doc["winner"]["knobs"]["error_feedback"] == "on"
    # zero1's sharded optimizer state conflicts with the residual carry
    with pytest.raises(ValueError, match="zero1"):
        ap.tune(artifact_path=str(tmp_path / "td2.json"),
                error_feedback=True, zero1=True,
                log_fn=lambda *_: None, **common)
