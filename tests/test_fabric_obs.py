"""Fabric observatory (PR 13): the measured fabric probe, ``--fabric
measured`` resolution through the ONE parsers, the per-tier calibration
column, drift blame, the trace-based ``report timeline`` verb, and the
named_phase scope anchors it keys on. Runs on the forced 4-device CPU
mesh (conftest)."""

import json
import math
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from atomo_tpu.obs.fabric import (
    FABRIC_MOVED_RATIO,
    QUICK_SIZES,
    ensure_fabric_probe,
    measured_bandwidths,
    measured_outer_bw,
    measured_two_tier,
    predicted_tier_ms,
    probe_fabric,
    probe_path,
    read_fabric_probe,
    write_fabric_probe,
)

N_DEV = 4


def _quick_doc(**kw):
    kw.setdefault("n_dev", N_DEV)
    kw.setdefault("sizes", QUICK_SIZES)
    kw.setdefault("reps", 1)
    kw.setdefault("best_of", 1)
    kw.setdefault("log_fn", lambda *a, **k: None)
    return probe_fabric(**kw)


def _fake_doc(tiers):
    """A synthetic probe document: {label: (gbps, lat_us)}."""
    return {
        "kind": "fabric_probe",
        "meta": {"backend": "cpu", "n_devices": N_DEV, "dcn_ways": 0,
                 "reps": 1},
        "tiers": [
            {"label": lbl, "axis": "dp", "ways": N_DEV,
             "bandwidth_gbps": g, "latency_us": lat,
             "allgather_gbps": g, "rows": []}
            for lbl, (g, lat) in tiers.items()
        ],
        "complete": True,
    }


# ------------------------------------------------------------- the probe


def test_probe_flat_mesh_measures_one_tier():
    doc = _quick_doc()
    assert doc["complete"] is True
    assert [t["label"] for t in doc["tiers"]] == ["ici"]
    t = doc["tiers"][0]
    assert t["ways"] == N_DEV and t["bandwidth_gbps"] > 0
    assert t["latency_us"] >= 0 and t["allgather_gbps"] > 0
    # every ladder row is recorded with its fence verdict
    assert all(
        r["bytes"] > 0 and r["ppermute_ms"] > 0 and r["sync_ok"]
        for r in t["rows"]
    )
    assert doc["meta"]["n_devices"] == N_DEV
    assert doc["meta"]["dcn_ways"] == 0


def test_probe_two_tier_measures_both_axes():
    doc = _quick_doc(dcn_ways=2)
    labels = {t["label"]: t for t in doc["tiers"]}
    assert set(labels) == {"ici", "dcn"}
    assert labels["ici"]["axis"] == "ici" and labels["ici"]["ways"] == 2
    assert labels["dcn"]["axis"] == "dp" and labels["dcn"]["ways"] == 2
    assert all(t["bandwidth_gbps"] > 0 for t in doc["tiers"])
    bws = measured_bandwidths(doc)
    assert measured_outer_bw(doc) == min(bws.values())


def test_probe_rejects_single_device():
    with pytest.raises(ValueError, match="multi-device"):
        probe_fabric(n_dev=1)


def test_ensure_probe_writes_and_reuses(tmp_path, monkeypatch):
    calls = []
    import atomo_tpu.obs.fabric as fab

    real = fab.probe_fabric

    def counting(**kw):
        calls.append(kw)
        return real(**{**kw, "sizes": QUICK_SIZES, "reps": 1,
                       "best_of": 1})

    monkeypatch.setattr(fab, "probe_fabric", counting)
    d = str(tmp_path)
    doc = ensure_fabric_probe(d, n_dev=N_DEV, log_fn=lambda *a: None)
    assert os.path.exists(probe_path(d)) and len(calls) == 1
    assert read_fabric_probe(d)["complete"] is True
    # a resume reuses the recorded measurement for the SAME mesh shape
    doc2 = ensure_fabric_probe(
        d, n_dev=N_DEV, reuse=True, log_fn=lambda *a: None
    )
    assert len(calls) == 1 and doc2["meta"] == doc["meta"]
    # ... but never a measurement of a topology that no longer exists
    ensure_fabric_probe(d, n_dev=2, reuse=True, log_fn=lambda *a: None)
    assert len(calls) == 2
    assert read_fabric_probe(d)["meta"]["n_devices"] == 2


# ---------------------------------------------- the ONE-parser resolution


def test_resolve_fabric_measured_and_reject_messages():
    from atomo_tpu.utils.comm_model import resolve_fabric

    doc = _fake_doc({"ici": (40.0, 2.0), "dcn": (5.0, 20.0)})
    # measured = the SLOWEST tier (the historical scalar convention)
    assert resolve_fabric("measured", measured=doc) == 5.0e9
    with pytest.raises(ValueError, match="fabric_probe.json"):
        resolve_fabric("measured")
    # the reject usage line quotes every accepted form (PR-13 doc fix):
    # measured AND the two-tier grammar pointer
    with pytest.raises(ValueError, match="measured") as e1:
        resolve_fabric("nonsense")
    assert "inner" in str(e1.value) and "outer" in str(e1.value)
    with pytest.raises(ValueError, match="resolve_two_tier"):
        resolve_fabric("ici:dcn")


def test_resolve_two_tier_measured_uses_measured_latencies():
    from atomo_tpu.topology.fabric import resolve_two_tier

    doc = _fake_doc({"ici": (40.0, 2.0), "dcn": (5.0, 20.0)})
    f2 = resolve_two_tier("measured", dcn_ways=2, n_dev=4, measured=doc)
    assert f2.inner_bw == 40.0e9 and f2.outer_bw == 5.0e9
    assert f2.inner_latency_s == pytest.approx(2.0e-6)
    assert f2.outer_latency_s == pytest.approx(20.0e-6)
    assert f2.inner_label == "measured_ici"
    assert f2.outer_label == "measured_dcn"
    with pytest.raises(ValueError, match="fabric_probe.json"):
        resolve_two_tier("measured", dcn_ways=2, n_dev=4)
    # a flat probe (no dcn tier) cannot serve a two-tier mesh
    with pytest.raises(ValueError, match="both ici and dcn"):
        resolve_two_tier(
            "measured", dcn_ways=2, n_dev=4,
            measured=_fake_doc({"ici": (40.0, 2.0)}),
        )
    # a measured TOKEN inside <inner>:<outer> resolves per tier too
    f3 = resolve_two_tier("45:measured", dcn_ways=2, n_dev=4, measured=doc)
    assert f3.inner_bw == 45e9 and f3.outer_bw == 5.0e9


def test_tune_records_measured_tiers_in_meta(tmp_path):
    """A measured-priced tune decision carries the per-tier GB/s in its
    meta — the cross-artifact check's join key."""
    from atomo_tpu.models import get_model
    from atomo_tpu.training import make_optimizer
    from atomo_tpu.tuning.autopilot import tune
    from atomo_tpu.tuning.probe import model_init_fn

    doc = _fake_doc({"ici": (40.0, 2.0), "dcn": (5.0, 20.0)})
    model = get_model("lenet", 10)
    out = tune(
        model=model,
        optimizer=make_optimizer("sgd", lr=0.01, momentum=0.9),
        codec=None,
        model_init_fn=model_init_fn(
            model, jnp.zeros((1, 28, 28, 1), jnp.float32)
        ),
        n_dev=1,
        sample_shape=(28, 28, 1),
        num_classes=10,
        batch=4,
        fabric="measured",
        fabric_probe=doc,
        probe_top=1,
        probe_steps=1,
        probe_reps=1,
        log_fn=lambda *a: None,
    )
    meta = out["meta"]
    assert meta["fabric"] == "measured"
    assert meta["fabric_tiers"] == {"ici": 40.0, "dcn": 5.0}
    assert meta["fabric_gbps_per_chip"] == 5.0


# ------------------------------------------ per-tier calibration column


def test_predicted_tier_ms_flat_and_hierarchical():
    from atomo_tpu.topology.fabric import resolve_two_tier
    from atomo_tpu.utils.comm_model import ring_allgather_wire_bytes

    t = predicted_tier_ms(
        aggregate="gather", dense_bytes=1e6, payload_bytes=1e5,
        ways=4, fabric_bw=1e9, fabric_label="ici",
    )
    want = ring_allgather_wire_bytes(1e5, 4) / 1e9 * 1e3
    assert t == {"ici": pytest.approx(want, rel=1e-3)}
    f2 = resolve_two_tier("auto", dcn_ways=2, n_dev=4)
    t2 = predicted_tier_ms(
        aggregate="hierarchical", dense_bytes=1e6, payload_bytes=1e5,
        ways=4, fabric2=f2, plan_name="legacy",
    )
    assert set(t2) == {f2.inner_label, f2.outer_label}
    assert all(v > 0 for v in t2.values())
    # no bandwidth -> no column, never a made-up one
    assert predicted_tier_ms(
        aggregate="gather", dense_bytes=1e6, payload_bytes=1e5, ways=4,
    ) == {}


def test_recorder_emits_calib_tiers(tmp_path):
    from atomo_tpu.obs.recorder import FlightRecorder

    rec = FlightRecorder(
        str(tmp_path / "metrics.jsonl"),
        predicted_ms=10.0,
        predicted_tier_ms={"ici": 4.0},
    )
    # measured == predicted: both columns sit at 1.0
    rows = rec.record_block(1, {"loss": np.float32(1.0)}, wall_s=0.010)
    assert rows[0]["calib"] == pytest.approx(1.0, abs=1e-3)
    assert rows[0]["calib_tiers"]["ici"] == pytest.approx(1.0, abs=1e-3)
    # a +3 ms residual attributed entirely to the 4 ms tier -> 7/4
    rec2 = FlightRecorder(
        str(tmp_path / "m2.jsonl"),
        predicted_ms=10.0,
        predicted_tier_ms={"ici": 4.0},
    )
    rows = rec2.record_block(1, {"loss": np.float32(1.0)}, wall_s=0.013)
    assert rows[0]["calib_tiers"]["ici"] == pytest.approx(7.0 / 4.0,
                                                         abs=1e-3)
    # no tier decomposition -> no column (the disarmed shape unchanged)
    rec3 = FlightRecorder(str(tmp_path / "m3.jsonl"), predicted_ms=10.0)
    rows = rec3.record_block(1, {"loss": np.float32(1.0)}, wall_s=0.010)
    assert "calib_tiers" not in rows[0]


# ------------------------------------------------------------ drift blame


def _fire_alarm(tuner):
    """Feed the drift detector a clean baseline then a sustained 3x
    excursion until the alarm arms the pending re-probe."""
    tuner.observe([0.010] * tuner.cfg.min_history)
    for _ in range(tuner.cfg.patience + 2):
        tuner.observe(0.030)
        if tuner.pending:
            return
    raise AssertionError("drift alarm never fired")


def test_blame_program_when_fabric_steady(tmp_path):
    from atomo_tpu.tuning.autopilot import OnlineRetuner
    from atomo_tpu.utils.tracing import IncidentLog

    log = IncidentLog(str(tmp_path / "incidents.jsonl"))
    steady = _fake_doc({"ici": (10.0, 2.0)})
    tuner = OnlineRetuner(
        probe_fn=lambda mode: 10.0,
        incidents=log,
        fabric_probe_fn=lambda: steady,
        fabric_baseline=measured_bandwidths(steady),
        log_fn=lambda *a: None,
    )
    _fire_alarm(tuner)
    tuner.maybe_retune(40, "gather")
    recs = IncidentLog.read(log.path)
    r = [x for x in recs if x["cause"] == "perf_drift"][-1]
    assert r["action"].startswith("retune")
    blame = r["blame"]
    assert blame["verdict"] == "program"
    assert blame["step_ms"]["baseline"] > 0
    assert blame["step_ms"]["observed"] > blame["step_ms"]["baseline"]
    assert blame["fabric"]["ici"]["ratio"] == pytest.approx(1.0)


def test_blame_fabric_when_bandwidth_moved(tmp_path):
    from atomo_tpu.tuning.autopilot import OnlineRetuner
    from atomo_tpu.utils.tracing import IncidentLog

    log = IncidentLog(str(tmp_path / "incidents.jsonl"))
    base = _fake_doc({"ici": (10.0, 2.0)})
    slowed = _fake_doc({"ici": (10.0 / (FABRIC_MOVED_RATIO + 0.5), 2.0)})
    repriced = []
    tuner = OnlineRetuner(
        probe_fn=lambda mode: 10.0,
        incidents=log,
        fabric_probe_fn=lambda: slowed,
        fabric_baseline=measured_bandwidths(base),
        on_fabric_moved=repriced.append,
        log_fn=lambda *a: None,
    )
    _fire_alarm(tuner)
    tuner.maybe_retune(40, "gather")
    r = [x for x in IncidentLog.read(log.path)
         if x["cause"] == "perf_drift"][-1]
    blame = r["blame"]
    assert blame["verdict"] == "fabric"
    tier = blame["fabric"]["ici"]
    assert tier["baseline_gbps"] == 10.0
    assert tier["measured_gbps"] < 10.0 / FABRIC_MOVED_RATIO
    # the re-price hook fired with the fresh probe, and the NEXT alarm
    # compares against the new baseline (no permanent blame loop)
    assert repriced == [slowed]
    assert tuner.fabric_baseline == measured_bandwidths(slowed)


def test_blame_without_probe_states_basis(tmp_path):
    from atomo_tpu.tuning.autopilot import OnlineRetuner
    from atomo_tpu.utils.tracing import IncidentLog

    log = IncidentLog(str(tmp_path / "incidents.jsonl"))
    tuner = OnlineRetuner(
        probe_fn=lambda mode: 10.0, incidents=log, log_fn=lambda *a: None
    )
    _fire_alarm(tuner)
    tuner.maybe_retune(40, "gather")
    r = [x for x in IncidentLog.read(log.path)
         if x["cause"] == "perf_drift"][-1]
    assert r["blame"]["verdict"] == "program"
    assert "no fabric baseline" in r["blame"]["basis"]


# ------------------------------------------------- report cross-artifact


def test_report_fabric_probe_check(tmp_path):
    from atomo_tpu.obs.report import _check_fabric_probe

    doc = _fake_doc({"ici": (40.0, 2.0)})
    tune = {"meta": {"fabric": "measured", "fabric_tiers": {"ici": 40.0}}}
    assert _check_fabric_probe(tune, doc)["ok"]
    # a preset-priced decision has nothing to cross-check
    assert _check_fabric_probe({"meta": {"fabric": "ici"}}, doc)["skipped"]
    # measured-priced but the artifact vanished / disagrees / incomplete
    assert not _check_fabric_probe(tune, None)["ok"]
    bad = _fake_doc({"ici": (99.0, 2.0)})
    c = _check_fabric_probe(tune, bad)
    assert not c["ok"] and "rewritten" in c["detail"]
    incomplete = dict(doc, complete=False)
    assert not _check_fabric_probe(tune, incomplete)["ok"]
    c2 = _check_fabric_probe(
        {"meta": {"fabric": "measured",
                  "fabric_tiers": {"dcn": 5.0}}}, doc,
    )
    assert not c2["ok"] and "probe artifact measured" in c2["detail"]


def test_report_drift_blame_check():
    from atomo_tpu.obs.report import _check_drift_blame

    assert _check_drift_blame([])["skipped"]
    good = [{
        "cause": "perf_drift", "action": "retune_keep", "step": 40,
        "blame": {"verdict": "program",
                  "step_ms": {"baseline": 10.0, "observed": 31.2}},
    }]
    assert _check_drift_blame(good)["ok"]
    naked = [{"cause": "perf_drift", "action": "retune->ring", "step": 4}]
    c = _check_drift_blame(naked)
    assert not c["ok"] and "no blame verdict" in c["detail"]
    unquantified = [{
        "cause": "perf_drift", "action": "retune->ring", "step": 4,
        "blame": {"verdict": "fabric",
                  "step_ms": {"baseline": 10.0, "observed": 30.0},
                  "fabric": {"ici": {"measured_gbps": 1.0}}},
    }]
    c2 = _check_drift_blame(unquantified)
    assert not c2["ok"] and "per-tier" in c2["detail"]
    # drift observations that never triggered a retune are exempt
    assert _check_drift_blame(
        [{"cause": "perf_drift", "action": "observed"}]
    )["skipped"]


def test_report_verb_checks_include_fabric(tmp_path):
    """The new checks ride build_report: a dir with a measured-priced
    decision and a matching probe is consistent; deleting the probe
    flips fabric_probe_consistent and --strict exits 3."""
    from atomo_tpu.obs.report import build_report
    from atomo_tpu.utils.tracing import write_json_atomic

    d = str(tmp_path)
    write_fabric_probe(d, _fake_doc({"ici": (40.0, 2.0)}))
    write_json_atomic(
        os.path.join(d, "tune_decision.json"),
        {"complete": True,
         "meta": {"fabric": "measured", "fabric_tiers": {"ici": 40.0}},
         "winner": {"name": "k1", "knobs": {"superstep": 1}},
         "rows": []},
    )
    doc = build_report(d)
    names = {c["name"]: c for c in doc["checks"]}
    assert names["fabric_probe_consistent"]["ok"]
    assert not names["fabric_probe_consistent"]["skipped"]
    assert names["drift_blame_present"]["skipped"]
    assert doc["sources"]["fabric_probe_json"] is True
    os.remove(probe_path(d))
    doc2 = build_report(d)
    assert doc2["consistent"] is False
    from atomo_tpu.cli import main

    with pytest.raises(SystemExit):
        main(["report", "--train-dir", d + "/nope"])
    assert main(["report", "--train-dir", d]) == 0
    assert main(["report", "--train-dir", d, "--strict"]) == 3


# ------------------------------------------------------- named_phase HLO


QSGD = None


def _qsgd():
    global QSGD
    if QSGD is None:
        from atomo_tpu.codecs import QsgdCodec

        QSGD = QsgdCodec(bits=8, bucket_size=512)
    return QSGD


@pytest.mark.parametrize(
    "mode",
    ["gather", "ring", "stream", "sharded_gather", "sharded_ring"],
)
def test_named_phase_scopes_survive_into_compiled_hlo(mode):
    """The timeline verb keys on the named_phase scopes inside the fused
    distributed step; a refactor that drops them would silently blind it.
    Assert the anchors appear in the compiled HLO's op metadata for the
    gather, ring, and stream-encode programs — AND for the pjit-compiled
    sharded-update programs (the mesh-subsystem compile path must not
    silently drop the timeline's anchors; it additionally plants its own
    materialize_params / sharded_update scopes)."""
    from atomo_tpu.mesh import sharded_update_state
    from atomo_tpu.models import get_model
    from atomo_tpu.parallel import (
        make_distributed_train_step,
        make_mesh,
        replicate_state,
        shard_batch,
    )
    from atomo_tpu.training import create_state, make_optimizer

    mesh = make_mesh(N_DEV)
    model = get_model("lenet", 10)
    opt = make_optimizer("sgd", lr=0.01, momentum=0.9)
    images = jnp.zeros((8, 28, 28, 1), jnp.float32)
    labels = jnp.zeros((8,), jnp.int32)
    host = create_state(model, opt, jax.random.PRNGKey(0), images)
    sharded = mode.startswith("sharded_")
    if sharded:
        state, su = sharded_update_state(mesh, jax.device_get(host), opt)
    else:
        state, su = replicate_state(mesh, host), None
    step = make_distributed_train_step(
        model, opt, mesh, _qsgd(),
        aggregate="ring" if mode.endswith("ring") else "gather",
        stream_encode=mode == "stream",
        stream_bucket_bytes=1 << 16,
        sharded_update=su,
    )
    si, sl = shard_batch(mesh, images, labels)
    txt = step.lower(
        state, jax.random.PRNGKey(1), si, sl
    ).compile().as_text()
    assert "encode" in txt, mode
    if mode.endswith("ring"):
        assert "ring_exchange_decode" in txt
    else:
        assert "exchange" in txt and "decode_mean" in txt
    if sharded:
        assert "materialize_params" in txt, mode
        assert "sharded_update" in txt, mode


# --------------------------------------------------------- the timeline


def _traced_step(tmp_path, n_loops=6):
    """Capture a real xplane trace of a small jitted fn carrying the
    named_phase scopes (big enough that its device wall is measurable)."""
    from atomo_tpu.utils.tracing import named_phase, profile

    def f(x):
        with named_phase("encode"):
            y = x @ x
            for _ in range(n_loops):
                y = y @ x
        with named_phase("exchange"):
            z = jnp.sum(y, axis=0)
        with named_phase("decode_mean"):
            w = z / x.shape[0]
        return jnp.sum(w)

    jf = jax.jit(f)
    x = jnp.ones((512, 512), jnp.float32)
    float(jf(x))  # compile outside the trace
    prof = str(tmp_path / "trace")
    with profile(prof):
        for _ in range(2):
            float(jf(x))
    return prof


def test_timeline_parses_phases_from_a_real_trace(tmp_path):
    from atomo_tpu.obs.timeline import build_timeline

    prof = _traced_step(tmp_path)
    doc = build_timeline(prof)
    assert doc["trace"] and doc["module"]
    names = {c["name"]: c for c in doc["checks"]}
    assert names["timeline_phases_present"]["ok"]
    assert names["timeline_joins_metrics"]["skipped"]  # no train_dir
    assert doc["spans"], doc
    busy = {p: sum(s["phases"][p]["busy_ms"] for s in doc["spans"])
            for p in ("encode", "exchange", "decode")}
    assert busy["encode"] > 0  # the matmul chain dominates
    for s in doc["spans"]:
        for p in ("encode", "exchange", "decode"):
            ph = s["phases"][p]
            assert ph["exposed_ms"] >= 0 and ph["hidden_ms"] >= 0
            assert ph["busy_ms"] >= ph["exposed_ms"] + ph["hidden_ms"] - 1e-6


def test_timeline_join_passes_and_fails_on_fixture(tmp_path):
    """The join check must PASS against an honest metrics stream and
    FAIL on a violated fixture (missing steps; a host wall too small to
    contain the device span)."""
    from atomo_tpu.obs.recorder import metrics_path
    from atomo_tpu.obs.timeline import build_timeline

    prof = _traced_step(tmp_path)
    base = build_timeline(prof)
    max_wall = max(s["wall_ms"] for s in base["spans"])

    def write_metrics(d, steps, step_ms):
        os.makedirs(d, exist_ok=True)
        with open(metrics_path(d), "w") as f:
            f.write(json.dumps({
                "kind": "meta", "what": "profile_window",
                "first_step": 1, "last_step": 2, "profile_dir": prof,
            }) + "\n")
            for s in steps:
                f.write(json.dumps({
                    "kind": "step", "step": s, "ts": 0.0,
                    "loss": 1.0, "step_ms": step_ms,
                }) + "\n")

    # honest: the window's host wall generously contains the device span
    good = str(tmp_path / "good")
    write_metrics(good, [1, 2], step_ms=max_wall * 2)
    doc = build_timeline(prof, good)
    names = {c["name"]: c for c in doc["checks"]}
    assert names["timeline_joins_metrics"]["ok"], names
    assert doc["joined_steps"] == [1, 2]

    # violated fixture A: a recorded window step was never recorded
    holey = str(tmp_path / "holey")
    write_metrics(holey, [1], step_ms=max_wall * 2)
    doc_a = build_timeline(prof, holey)
    c = {x["name"]: x for x in doc_a["checks"]}["timeline_joins_metrics"]
    assert not c["ok"] and "missing" in c["detail"]
    assert doc_a["consistent"] is False

    # violated fixture B: the metrics claim steps far faster than the
    # device span the trace shows — they describe a different run
    fast = str(tmp_path / "fast")
    write_metrics(fast, [1, 2], step_ms=1e-4)
    doc_b = build_timeline(prof, fast)
    c = {x["name"]: x for x in doc_b["checks"]}["timeline_joins_metrics"]
    if max_wall > 1.5 * 2e-4 + 1.0:  # the guard band, stated in the check
        assert not c["ok"] and "EXCEEDS" in c["detail"]


def test_timeline_missing_trace_and_scopeless_trace(tmp_path):
    from atomo_tpu.obs.timeline import build_timeline
    from atomo_tpu.utils.tracing import profile

    doc = build_timeline(str(tmp_path / "nothing"))
    assert doc["consistent"] is False
    assert doc["checks"][0]["name"] == "timeline_trace_found"
    # a trace with no named_phase anchors is called out, not mis-read
    prof = str(tmp_path / "plain")
    jf = jax.jit(lambda x: jnp.sum(x * x))
    float(jf(jnp.ones(64)))
    with profile(prof):
        float(jf(jnp.ones(64)))
    doc2 = build_timeline(prof)
    assert doc2["consistent"] is False
    bad = [c for c in doc2["checks"] if not c["ok"]]
    assert bad and bad[0]["name"] == "timeline_phases_present"


def test_segmentation_anchors_on_one_device_line():
    """A multi-device trace carries every instruction once per DEVICE
    LINE per dispatch; segmentation must anchor on one reference line,
    not over-split each dispatch into per-device fragments (review
    finding)."""
    from atomo_tpu.obs.timeline import _segment_executions

    events = []
    for d in range(2):  # two dispatches
        base = d * 100.0
        for line in ("dev0", "dev1"):
            off = 0.1 if line == "dev1" else 0.0
            for i, op in enumerate(("a", "b", "c")):
                t = base + i * 1.0 + off
                events.append({
                    "name": op, "line": ("p", line),
                    "start_us": t, "end_us": t + 0.5,
                })
    events.sort(key=lambda e: e["start_us"])
    execs = _segment_executions(events)
    assert len(execs) == 2
    # each dispatch holds BOTH devices' events (6 = 3 ops x 2 lines)
    assert [len(ex) for ex in execs] == [6, 6]


def test_fabric_check_tolerates_recorded_reprice():
    """The drift-blame flow legitimately rewrites fabric_probe.json when
    the fabric moved; the cross-artifact check must accept a number
    mismatch that a fabric-verdict incident explains — and still fail an
    unexplained one (review finding)."""
    from atomo_tpu.obs.report import _check_fabric_probe

    tune = {"meta": {"fabric": "measured", "fabric_tiers": {"ici": 40.0}}}
    rewritten = _fake_doc({"ici": (20.0, 2.0)})
    moved = [{
        "cause": "perf_drift", "action": "retune_keep",
        "blame": {"verdict": "fabric",
                  "step_ms": {"baseline": 10.0, "observed": 30.0},
                  "fabric": {"ici": {"baseline_gbps": 40.0,
                                     "measured_gbps": 20.0,
                                     "ratio": 0.5}}},
    }]
    ok = _check_fabric_probe(tune, rewritten, moved)
    assert ok["ok"] and "re-price" in ok["detail"]
    assert not _check_fabric_probe(tune, rewritten, [])["ok"]


def test_measured_two_tier_degenerate_inner():
    """dcn_ways == n_dev: every inner group is one chip, so the probe
    records only the dcn tier — the resolution must accept the shape
    its own grammar accepts instead of dead-ending (review finding)."""
    doc = _fake_doc({"dcn": (5.0, 20.0)})
    f2 = measured_two_tier(doc, dcn_ways=4, n_dev=4)
    assert f2.inner_ways == 1 and f2.outer_ways == 4
    assert f2.outer_bw == 5.0e9


def test_ensure_probe_reuse_normalizes_nondividing_dcn(tmp_path,
                                                      monkeypatch):
    """A non-dividing --dcn-ways probes flat (meta.dcn_ways=0); a resume
    with the same flags must reuse that artifact, not re-probe forever
    on a mismatch that is not one (review finding)."""
    calls = []
    import atomo_tpu.obs.fabric as fab

    real = fab.probe_fabric

    def counting(**kw):
        calls.append(kw)
        return real(**{**kw, "sizes": QUICK_SIZES, "reps": 1,
                       "best_of": 1})

    monkeypatch.setattr(fab, "probe_fabric", counting)
    d = str(tmp_path)
    ensure_fabric_probe(d, n_dev=N_DEV, dcn_ways=3,
                        log_fn=lambda *a: None)
    assert read_fabric_probe(d)["meta"]["dcn_ways"] == 0
    ensure_fabric_probe(d, n_dev=N_DEV, dcn_ways=3, reuse=True,
                        log_fn=lambda *a: None)
    assert len(calls) == 1


def test_phase_of_classification():
    from atomo_tpu.obs.timeline import phase_of

    assert phase_of("jit(f)/jit(main)/encode/mul") == "encode"
    assert phase_of("jit(f)/transpose/decode_mean/dot") == "decode"
    assert phase_of("jit(f)/ring_exchange_decode/ppermute") == "exchange"
    assert phase_of("jit(f)/delayed_exchange/all_gather") == "exchange"
    assert phase_of("jit(f)/hybrid_exchange/all_gather") == "exchange"
    assert phase_of("jit(f)/dense/add") == "compute"
    assert phase_of(None) == "compute"


# --------------------------------------------- CLI wiring + deprecation


def test_preflight_rejects_measured_without_train_dir():
    from atomo_tpu.cli import main

    with pytest.raises(SystemExit, match="fabric_probe.json"):
        main(["train", "--fabric", "measured", "--train-dir", "",
              "--synthetic", "--n-devices", "4"])
    with pytest.raises(SystemExit, match="multi-device"):
        main(["train", "--fabric", "measured", "--train-dir", "x",
              "--synthetic", "--n-devices", "1"])


def test_fabric_measured_trains_bit_identical_to_pinned(tmp_path):
    """PRICING ONLY, through the real CLI: a ``--fabric measured`` run
    (both tiers probed at start-up, the artifact left complete) and a
    ``--fabric ici`` run with the same resolved knobs leave bit-identical
    checkpoints — the probe must not perturb the trajectory."""
    from atomo_tpu.cli import main
    from atomo_tpu.models import get_model
    from atomo_tpu.training import create_state, make_optimizer
    from atomo_tpu.training.checkpoint import load_checkpoint

    steps, batch = 2, 8
    common = [
        "train", "--synthetic", "--dataset", "mnist", "--network", "lenet",
        "--batch-size", str(batch), "--max-steps", str(steps),
        "--eval-freq", "0", "--save-freq", str(steps),
        "--log-interval", "0", "--n-devices", str(N_DEV), "--code", "qsgd",
        "--quantization-level", "8", "--aggregate", "gather",
        "--seed", "3", "--momentum", "0.5",
    ]
    d_meas, d_pin = str(tmp_path / "measured"), str(tmp_path / "pinned")
    assert main(common + ["--train-dir", d_meas, "--fabric", "measured",
                          "--dcn-ways", "2"]) == 0
    assert main(common + ["--train-dir", d_pin, "--fabric", "ici"]) == 0
    doc = read_fabric_probe(d_meas)
    assert doc and doc["complete"]
    assert {t["label"] for t in doc["tiers"]} == {"ici", "dcn"}
    tpl = jax.device_get(create_state(
        get_model("lenet", 10), make_optimizer("sgd", lr=0.01, momentum=0.5),
        jax.random.PRNGKey(3), jnp.zeros((batch, 28, 28, 1)),
    ))
    a = jax.tree_util.tree_leaves(load_checkpoint(d_meas, tpl, step=steps))
    b = jax.tree_util.tree_leaves(load_checkpoint(d_pin, tpl, step=steps))
    assert len(a) == len(b) and all(
        np.array_equal(np.asarray(x), np.asarray(y)) for x, y in zip(a, b)
    )


def test_report_timeline_verb_requires_a_trace(tmp_path):
    from atomo_tpu.cli import main

    with pytest.raises(SystemExit, match="profile dir"):
        main(["report", "timeline", "--train-dir", str(tmp_path)])


# ----------------------------------------------- scenario table + lint


def test_scenario_table_from_probe(tmp_path):
    import subprocess
    import sys

    doc = _fake_doc({"ici": (40.0, 2.0), "dcn": (5.0, 20.0)})
    path = tmp_path / "fabric_probe.json"
    path.write_text(json.dumps(doc))
    p = subprocess.run(
        [sys.executable, "scripts/scenario_table.py", "--ways", "8",
         "--from-probe", str(path)],
        capture_output=True, text=True, timeout=300,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    assert p.returncode == 0, p.stderr[-2000:]
    assert "measured_ici" in p.stdout and "measured_dcn" in p.stdout
    assert "measured 2-tier" in p.stdout
    assert "measured fabric" in p.stdout  # the source caveat line


def test_artifact_lint_covers_the_probe_writer(tmp_path):
    """scripts/check_artifact_discipline.py scans the whole package, so
    the new artifact writer is covered BY CONSTRUCTION — prove it: the
    shipped module is in the target set and clean, and a json.dump
    smuggled into it would be flagged."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "check_artifact_discipline",
        os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "scripts", "check_artifact_discipline.py",
        ),
    )
    lint = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(lint)
    assert lint.collect_violations() == []
    rel = os.path.join("atomo_tpu", "obs", "fabric.py")
    bad = tmp_path / "fabric.py"
    bad.write_text(
        "import json\n"
        "def write_fabric_probe(train_dir, doc):\n"
        "    with open(train_dir + '/fabric_probe.json', 'w') as f:\n"
        "        json.dump(doc, f)\n"
    )
    out = lint.scan_file(str(bad), rel)
    assert out and "write_json_atomic" in out[0]
