"""utils.tracing coverage: the profiler trace capture (``profile``) and
the ``--profile-dir`` CLI flag — the trace-capture surface had zero tests
(PR-11 satellite). Runs on the forced CPU mesh (conftest)."""

import os

import jax
import jax.numpy as jnp
import pytest

from atomo_tpu.utils.tracing import (
    IncidentLog,
    format_incident,
    profile,
    read_jsonl,
    span,
    spans,
)


def _files_under(root):
    return [
        os.path.join(b, f)
        for b, _, fs in os.walk(root)
        for f in fs
    ]


def test_profile_captures_a_device_trace(tmp_path):
    """profile(dir) must leave a loadable jax.profiler trace — the only
    honest way to see phase cost inside a fused program."""
    f = jax.jit(lambda x: jnp.sum(x * x))
    with profile(str(tmp_path)):
        float(f(jnp.arange(64.0)))
    files = _files_under(tmp_path)
    assert files, "no trace files written"
    assert any("xplane" in f or "trace" in f for f in files), files


def test_profile_stops_trace_on_error(tmp_path):
    """The trace must be closed even when the profiled block raises —
    a leaked open trace would crash the next capture."""
    with pytest.raises(RuntimeError, match="boom"):
        with profile(str(tmp_path)):
            raise RuntimeError("boom")
    # a second capture works: the previous one was stopped
    with profile(str(tmp_path)):
        float(jax.jit(jnp.sum)(jnp.ones(4)))
    assert _files_under(tmp_path)


@pytest.mark.slow  # full end-to-end CLI training under the profiler (~14 s
# on 1 core) — full-suite only; test_fabric_obs's timeline test keeps
# trace-production coverage in the smoke set
def test_cli_profile_dir_flag_produces_trace(tmp_path, capsys):
    """The --profile-dir trace flag end to end: a short distributed run
    announces the profiled window and leaves trace files."""
    from atomo_tpu.cli import main

    prof = tmp_path / "trace"
    rc = main([
        "train", "--synthetic", "--dataset", "mnist", "--network", "lenet",
        "--batch-size", "8", "--max-steps", "4", "--eval-freq", "0",
        "--log-interval", "0", "--n-devices", "2", "--code", "qsgd",
        "--quantization-level", "8", "--aggregate", "gather",
        "--train-dir", str(tmp_path / "run"), "--momentum", "0.0",
        "--profile-dir", str(prof),
    ])
    assert rc == 0
    out = capsys.readouterr().out
    assert "Profiling steps" in out
    assert _files_under(prof), "no profiler trace written by --profile-dir"


def test_span_and_read_jsonl_and_format_incident(tmp_path):
    with span("load"):
        pass
    assert spans()[-1][0] == "load" and spans()[-1][4] >= spans()[-1][3]
    log = IncidentLog(str(tmp_path / "i.jsonl"))
    log.append("membership", action="shrink", step=4, epoch=1, world=3)
    recs = read_jsonl(str(tmp_path / "i.jsonl"))
    assert len(recs) == 1
    line = format_incident(recs[0])
    # the PR-9 special cases live in the SHARED formatter now
    assert "epoch=1" in line and "world=3" in line and "-> shrink" in line
    assert IncidentLog.summarize(str(tmp_path / "i.jsonl")).count(line) == 1
