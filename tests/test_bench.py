"""bench.py parent-side logic: ladder order, aggregate emission, one child
per config with no retry and no fallback, and an exit code that says when
a config produced no row. The measurement side is exercised on hardware;
these pin the orchestration a caller depends on."""

import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import bench  # noqa: E402


def test_ladder_runs_headline_config_first(monkeypatch, capsys):
    """A caller records the LAST stdout line; config 2 (the headline)
    must run first so a ladder cut short mid-way still leaves a config-2
    aggregate."""
    order = []

    def fake_bench_one(c, no_baseline):
        order.append(c)
        return {"metric": f"m{c}", "value": float(c), "measurement_valid": True}

    monkeypatch.setattr(bench, "_bench_one", fake_bench_one)
    monkeypatch.setattr(bench, "_write_artifact", lambda: None)
    monkeypatch.setattr(sys, "argv", ["bench.py"])
    assert bench.main() == 0
    assert order == [2, 1, 3, 4, 5, 6, 7, 8, 9, 10, 11, 12, 13, 14, 15, 16,
                     17, 18, 19, 20, 21]

    lines = [
        json.loads(ln)
        for ln in capsys.readouterr().out.splitlines()
        if ln.strip().startswith("{")
    ]
    # every aggregate line is config-2-based, and the last one is complete
    aggs = [ln for ln in lines if "configs" in ln]
    assert aggs and all(a["metric"] == "m2" for a in aggs)
    assert aggs[-1]["configs_complete"] is True
    assert [c["metric"] for c in aggs[-1]["configs"]] == [
        "m1", "m2", "m3", "m4", "m5", "m6", "m7", "m8", "m9", "m10",
        "m11", "m12", "m13", "m14", "m15", "m16", "m17", "m18", "m19",
        "m20", "m21"
    ]
    # an aggregate exists right after the FIRST config completes
    assert "configs" in lines[1]
    assert lines[1]["configs_complete"] is False


def test_mark_invalid_appends_reasons():
    row = {"measurement_valid": True}
    bench._mark_invalid(row, "first")
    bench._mark_invalid(row, "second")
    assert row["measurement_valid"] is False
    assert row["invalid_reason"] == "first; second"


def test_failed_child_is_an_error_row_not_a_cpu_row(monkeypatch):
    """No retry ladder and no CPU fallback: a TPU-measuring config whose
    child fails yields ONE child, launched with no platform override, and
    an error row that carries the child's reason — never a CPU number
    under the device metric's name."""
    calls = []

    def fake_run_child(tail, env, timeout_s=None):
        calls.append((tail, env))
        return None, "rc=3: bench: config 1 measures the TPU, and JAX found platform 'cpu'"

    monkeypatch.setattr(bench, "_run_child", fake_run_child)
    row = bench._bench_one(1, no_baseline=True)
    assert len(calls) == 1
    assert "JAX_PLATFORMS" not in calls[0][1]
    assert "ATOMO_BENCH_FAST" not in calls[0][1]
    assert row["value"] is None and row["measurement_valid"] is False
    assert row["platform"] is None
    assert "found platform 'cpu'" in row["error"]


def test_non_cpu_mesh_config_exits_nonzero_without_tpu(monkeypatch, capsys):
    """A config that is not force_cpu_mesh and finds no TPU exits non-zero
    and prints why — in the child (this suite's backend is the CPU) and,
    through the error row, in the parent."""
    import argparse

    rc = bench.child_main(argparse.Namespace(config=1, no_baseline=True))
    cap = capsys.readouterr()
    assert rc == bench.NO_TPU_EXIT_CODE != 0
    assert "measures the TPU" in cap.err and "'cpu'" in cap.err
    assert not [ln for ln in cap.out.splitlines() if ln.startswith("{")]

    monkeypatch.setattr(
        bench, "_run_child",
        lambda tail, env, timeout_s=None: (None, "rc=3: " + cap.err.strip()),
    )
    monkeypatch.setattr(bench, "_write_artifact", lambda: None)
    monkeypatch.setattr(sys, "argv", ["bench.py", "--config", "1"])
    assert bench.main() != 0
    cap = capsys.readouterr()
    row = json.loads(cap.out.strip().splitlines()[-1])
    assert row["value"] is None and "measures the TPU" in row["error"]
    assert "failed" in cap.err


def test_ladder_deadline_truncates_honestly(monkeypatch):
    """A ladder with no global budget can run past its caller's window and
    truncate the final aggregate mid-write. With the deadline exhausted,
    _bench_one must emit an honest deadline row — no children, no
    timeout."""
    def boom(*a, **k):
        raise AssertionError("no child may be spawned past the deadline")

    monkeypatch.setattr(bench, "_run_child", boom)
    monkeypatch.setattr(bench, "_DEADLINE", bench.time.monotonic() + 1.0)
    row = bench._bench_one(3, no_baseline=True)
    assert row["measurement_valid"] is False
    assert "deadline" in row["invalid_reason"]
    assert row["metric"] == bench.CONFIGS[3]["metric"]


def test_child_timeout_clamped_to_deadline(monkeypatch):
    """With some budget left but less than the child default, the one
    child's timeout must be clamped to the remaining window."""
    seen = []

    def fake_run_child(tail, env, timeout_s=None):
        seen.append(timeout_s)
        return {"metric": "m", "value": 1.0, "measurement_valid": True,
                "platform": "tpu", "error": None}, ""

    monkeypatch.setattr(bench, "_run_child", fake_run_child)
    monkeypatch.setattr(bench, "_DEADLINE", bench.time.monotonic() + 200.0)
    row = bench._bench_one(1, no_baseline=True)
    assert row["value"] == 1.0
    assert seen and all(t <= 200 for t in seen), seen


def test_comm_model_attached_is_json_safe():
    """The comm model rows embedded in bench output must serialize with
    strict JSON (no Infinity tokens — code-review r4 finding)."""
    from atomo_tpu.utils.comm_model import crossover_report

    rep = crossover_report(44.7e6, 0.62e6, dense_step_s=9.0e-3,
                           svd_step_s=6.5e-3)  # tax clamps to 0 -> inf case
    text = json.dumps(rep, allow_nan=False)  # raises on inf/nan
    assert "any_bandwidth" in text


def test_artifact_rows_written_atomically_as_they_complete(
    monkeypatch, tmp_path, capsys
):
    """PR-3 evidence hardening: every ladder row lands in the JSON artifact
    atomically AS IT COMPLETES — a caller's rc=124 mid-ladder leaves a
    parseable artifact holding every finished row."""
    art = tmp_path / "partial.json"
    monkeypatch.setenv("ATOMO_BENCH_ARTIFACT", str(art))
    seen_when_row3_ran = {}

    def fake_bench_one(c, no_baseline):
        if c == 3 and art.exists():
            # the artifact must already hold the EARLIER rows (2, 1) —
            # i.e. writes happen per row, not at ladder end
            seen_when_row3_ran["rows"] = [
                r["metric"] for r in json.loads(art.read_text())["rows"]
            ]
        return {"metric": f"m{c}", "value": float(c),
                "measurement_valid": True}

    monkeypatch.setattr(bench, "_bench_one", fake_bench_one)
    monkeypatch.setattr(sys, "argv", ["bench.py"])
    assert bench.main() == 0
    assert seen_when_row3_ran.get("rows") == ["m2", "m1"]
    doc = json.loads(art.read_text())
    assert doc["complete"] is True
    assert [r["metric"] for r in doc["rows"]] == [
        "m2", "m1", "m3", "m4", "m5", "m6", "m7", "m8", "m9", "m10",
        "m11", "m12", "m13", "m14", "m15", "m16", "m17", "m18", "m19",
        "m20", "m21"
    ]
    # atomicity: no torn temp file left behind
    assert not list(tmp_path.glob("*.tmp.*"))


def test_artifact_write_failure_is_nonfatal(monkeypatch, tmp_path, capsys):
    """A read-only artifact location must not kill the bench (stdout JSON
    is the driver contract; the artifact is best-effort extra evidence)."""
    monkeypatch.setenv(
        "ATOMO_BENCH_ARTIFACT", str(tmp_path / ("no" * 40) / ("x" * 300))
    )
    monkeypatch.setattr(
        bench, "_bench_one",
        lambda c, nb: {"metric": f"m{c}", "value": 1.0,
                       "measurement_valid": True},
    )
    monkeypatch.setattr(sys, "argv", ["bench.py", "--config", "7"])
    assert bench.main() == 0
    out = capsys.readouterr().out
    assert json.loads(out.strip().splitlines()[-1])["metric"] == "m7"


def test_failed_child_error_carries_rc_and_stderr_tail(monkeypatch):
    """A child that dies without a JSON row must explain itself: its exit
    code and stderr tail travel into the error the row (and the artifact)
    records."""
    class FakeProc:
        returncode = 3
        stdout = ""
        stderr = "warming up\nbench: config 2 measures the TPU, and JAX found platform 'cpu'\n"

    monkeypatch.setattr(bench.subprocess, "run", lambda *a, **k: FakeProc())
    parsed, err = bench._run_child(["--config", "2"], {}, timeout_s=30)
    assert parsed is None
    assert err.startswith("rc=3: ") and "measures the TPU" in err


def test_ring_vs_gather_config_forces_cpu_mesh(monkeypatch):
    """Config 8 must run as ONE child on a forced multi-device CPU mesh."""
    seen = []

    def fake_run_child(tail, env, timeout_s=None):
        seen.append(env)
        return {"metric": "ring_vs_gather_dispatch", "value": 5.0,
                "measurement_valid": True, "platform": "cpu"}, ""

    monkeypatch.setattr(bench, "_run_child", fake_run_child)
    monkeypatch.setattr(bench, "_DEADLINE", bench.time.monotonic() + 900.0)
    row = bench._bench_one(8, no_baseline=True)
    assert row["measurement_valid"] is True
    assert len(seen) == 1
    assert seen[0]["JAX_PLATFORMS"] == "cpu"
    assert "--xla_force_host_platform_device_count=4" in seen[0]["XLA_FLAGS"]


def test_overlap_config_forces_cpu_mesh(monkeypatch):
    """Config 9 (overlap_vs_blocking) rides the same forced-CPU-mesh path
    as config 8: ONE child."""
    seen = []

    def fake_run_child(tail, env, timeout_s=None):
        seen.append((tail, env))
        return {"metric": "overlap_vs_blocking", "value": 5.0,
                "measurement_valid": True, "platform": "cpu"}, ""

    monkeypatch.setattr(bench, "_run_child", fake_run_child)
    monkeypatch.setattr(bench, "_DEADLINE", bench.time.monotonic() + 900.0)
    row = bench._bench_one(9, no_baseline=True)
    assert row["measurement_valid"] is True
    assert len(seen) == 1
    assert seen[0][1]["JAX_PLATFORMS"] == "cpu"
    assert "--xla_force_host_platform_device_count=4" in seen[0][1]["XLA_FLAGS"]


def test_sharded_update_config_forces_cpu_mesh(monkeypatch):
    """Config 15 (sharded_update_memory) rides the same forced-CPU-mesh
    path as configs 8-14: ONE child, no TPU attempts, no fast-mode
    fallback — the memory comparison needs the real 4-shard layout."""
    seen = []

    def fake_run_child(tail, env, timeout_s=None):
        seen.append(env)
        return {"metric": "sharded_update_memory", "value": 5.0,
                "measurement_valid": True, "platform": "cpu"}, ""

    monkeypatch.setattr(bench, "_run_child", fake_run_child)
    monkeypatch.setattr(bench, "_DEADLINE", bench.time.monotonic() + 900.0)
    row = bench._bench_one(15, no_baseline=True)
    assert row["measurement_valid"] is True
    assert len(seen) == 1
    assert seen[0]["JAX_PLATFORMS"] == "cpu"
    assert "--xla_force_host_platform_device_count=4" in seen[0]["XLA_FLAGS"]


def test_adaptive_budget_config_forces_cpu_mesh(monkeypatch):
    """Config 16 (adaptive_budget_pareto) rides the same forced-CPU-mesh
    path as configs 8-15: ONE child, no TPU attempts, no fast-mode
    fallback — the equal-wire Pareto compare needs the real 4-replica
    exchange."""
    seen = []

    def fake_run_child(tail, env, timeout_s=None):
        seen.append(env)
        return {"metric": "adaptive_budget_pareto", "value": 5.0,
                "measurement_valid": True, "platform": "cpu"}, ""

    monkeypatch.setattr(bench, "_run_child", fake_run_child)
    monkeypatch.setattr(bench, "_DEADLINE", bench.time.monotonic() + 900.0)
    row = bench._bench_one(16, no_baseline=True)
    assert row["measurement_valid"] is True
    assert len(seen) == 1
    assert seen[0]["JAX_PLATFORMS"] == "cpu"
    assert "--xla_force_host_platform_device_count=4" in seen[0]["XLA_FLAGS"]


def test_quorum_config_forces_cpu_mesh(monkeypatch):
    """Config 17 (quorum_straggler_absorption) rides the same forced-
    CPU-mesh path as configs 8-16: ONE child, no TPU attempts, no
    fast-mode fallback — the absorption compare needs a real 4-replica
    exchange with one slowed member."""
    seen = []

    def fake_run_child(tail, env, timeout_s=None):
        seen.append(env)
        return {"metric": "quorum_straggler_absorption", "value": 5.0,
                "measurement_valid": True, "platform": "cpu"}, ""

    monkeypatch.setattr(bench, "_run_child", fake_run_child)
    monkeypatch.setattr(bench, "_DEADLINE", bench.time.monotonic() + 900.0)
    row = bench._bench_one(17, no_baseline=True)
    assert row["measurement_valid"] is True
    assert len(seen) == 1
    assert seen[0]["JAX_PLATFORMS"] == "cpu"
    assert "--xla_force_host_platform_device_count=4" in seen[0]["XLA_FLAGS"]


def test_controller_config_forces_cpu_mesh(monkeypatch):
    """Config 18 (controller_joint_decision) rides the same forced-
    CPU-mesh path as configs 8-17: ONE child, no TPU attempts — the
    joint-vs-single compare needs a real 4-replica exchange."""
    seen = []

    def fake_run_child(tail, env, timeout_s=None):
        seen.append(env)
        return {"metric": "controller_joint_decision", "value": 5.0,
                "measurement_valid": True, "platform": "cpu"}, ""

    monkeypatch.setattr(bench, "_run_child", fake_run_child)
    monkeypatch.setattr(bench, "_DEADLINE", bench.time.monotonic() + 900.0)
    row = bench._bench_one(18, no_baseline=True)
    assert row["measurement_valid"] is True
    assert len(seen) == 1
    assert seen[0]["JAX_PLATFORMS"] == "cpu"
    assert "--xla_force_host_platform_device_count=4" in seen[0]["XLA_FLAGS"]


def test_lm_compressed_dp_wire_config_forces_cpu_mesh(monkeypatch):
    """Config 19 (lm_compressed_dp_wire) rides the same forced-CPU-mesh
    path as configs 8-18: ONE child, no TPU attempts — the dp2xtp2
    layout needs the real 4-device mesh."""
    seen = []

    def fake_run_child(tail, env, timeout_s=None):
        seen.append(env)
        return {"metric": "lm_compressed_dp_wire", "value": 5.0,
                "measurement_valid": True, "platform": "cpu"}, ""

    monkeypatch.setattr(bench, "_run_child", fake_run_child)
    monkeypatch.setattr(bench, "_DEADLINE", bench.time.monotonic() + 900.0)
    row = bench._bench_one(19, no_baseline=True)
    assert row["measurement_valid"] is True
    assert len(seen) == 1
    assert seen[0]["JAX_PLATFORMS"] == "cpu"
    assert "--xla_force_host_platform_device_count=4" in seen[0]["XLA_FLAGS"]


def test_lm_delayed_overlap_config_forces_cpu_mesh(monkeypatch):
    """Config 20 (lm_delayed_overlap) rides the same forced-CPU-mesh
    path as configs 8-19: ONE child, no TPU attempts — the dp2xpp2
    stale-by-one schedule needs the real 4-device mesh."""
    seen = []

    def fake_run_child(tail, env, timeout_s=None):
        seen.append(env)
        return {"metric": "lm_delayed_overlap", "value": 5.0,
                "measurement_valid": True, "platform": "cpu"}, ""

    monkeypatch.setattr(bench, "_run_child", fake_run_child)
    monkeypatch.setattr(bench, "_DEADLINE", bench.time.monotonic() + 900.0)
    row = bench._bench_one(20, no_baseline=True)
    assert row["measurement_valid"] is True
    assert len(seen) == 1
    assert seen[0]["JAX_PLATFORMS"] == "cpu"
    assert "--xla_force_host_platform_device_count=4" in seen[0]["XLA_FLAGS"]


def test_two_tier_config_forces_cpu_mesh(monkeypatch):
    """Config 11 (two_tier_matrix) rides the same forced-CPU-mesh path as
    configs 8-10: ONE child, no TPU attempts, no fast-mode fallback."""
    seen = []

    def fake_run_child(tail, env, timeout_s=None):
        seen.append((tail, env))
        return {"metric": "two_tier_matrix", "value": 5.0,
                "measurement_valid": True, "platform": "cpu"}, ""

    monkeypatch.setattr(bench, "_run_child", fake_run_child)
    monkeypatch.setattr(bench, "_DEADLINE", bench.time.monotonic() + 900.0)
    row = bench._bench_one(11, no_baseline=True)
    assert row["measurement_valid"] is True
    assert len(seen) == 1
    assert seen[0][1]["JAX_PLATFORMS"] == "cpu"
    assert "--xla_force_host_platform_device_count=4" in seen[0][1]["XLA_FLAGS"]


def test_env_parse_falls_back_on_garbage(monkeypatch, capsys):
    """A typo'd caller env (ATOMO_BENCH_STEPS=oops) must degrade to the
    default with a logged warning, not crash the ladder before any row is
    produced."""
    monkeypatch.setenv("ATOMO_BENCH_STEPS", "oops")
    assert bench._env_int("ATOMO_BENCH_STEPS", 3) == 3
    monkeypatch.setenv("ATOMO_BENCH_STEPS", "8.5")  # int parse, float given
    assert bench._env_int("ATOMO_BENCH_STEPS", 0) == 0
    monkeypatch.setenv("ATOMO_BENCH_DEADLINE_S", "soon")
    assert bench._env_float("ATOMO_BENCH_DEADLINE_S", 840.0) == 840.0
    err = capsys.readouterr().err
    assert "ATOMO_BENCH_STEPS" in err and "ignoring" in err
    # valid values still parse
    monkeypatch.setenv("ATOMO_BENCH_STEPS", "1")
    assert bench._env_int("ATOMO_BENCH_STEPS", 3) == 1


def test_ladder_exit_code_reflects_failed_rows(monkeypatch, capsys):
    """The ladder no longer returns 0 whatever happened: one config with
    an error row makes the whole invocation exit non-zero, while every
    row — the failed one included — still reaches stdout and the final
    aggregate is complete."""
    def fake_bench_one(c, no_baseline):
        if c == 3:
            return {"metric": "m3", "value": None, "measurement_valid": False,
                    "error": "rc=3: no TPU"}
        return {"metric": f"m{c}", "value": float(c),
                "measurement_valid": True, "error": None}

    monkeypatch.setattr(bench, "_bench_one", fake_bench_one)
    monkeypatch.setattr(bench, "_write_artifact", lambda: None)
    monkeypatch.setattr(sys, "argv", ["bench.py"])
    assert bench.main() == 1
    cap = capsys.readouterr()
    last = json.loads(cap.out.strip().splitlines()[-1])
    assert last["configs_complete"] is True
    assert [c["error"] for c in last["configs"] if c["metric"] == "m3"] == [
        "rc=3: no TPU"
    ]
    assert "m3 failed: rc=3: no TPU" in cap.err
