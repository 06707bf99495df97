"""Auxiliary subsystem tests: tracing spans, health monitor, launch helpers,
optimizer schedule parity."""

import time

import jax
import numpy as np
import pytest

from atomo_tpu.parallel.launch import HealthMonitor, global_mesh, initialize
from atomo_tpu.training import make_optimizer, stepwise_shrink
from atomo_tpu.utils.tracing import clear, span, spans


def test_span_records_into_the_ring():
    clear()
    with span("io", 7):
        time.sleep(0.01)
    (name, step, parent, t0, t1), = spans()
    assert (name, step, parent) == ("io", 7, None) and t1 - t0 >= 0.01


def test_span_is_safe_anywhere():
    """Outside any loop and any profiler session, with or without a step:
    the annotation half is a flag check, the ring half always records."""
    clear()
    with span("region"):
        with span("inner", 2):
            pass
    assert [(r[0], r[1], r[2]) for r in spans()] == [("inner", 2, "region"), ("region", None, None)]


def test_health_monitor_raises_after_silence():
    hm = HealthMonitor(timeout=0.01)
    hm.beat(3)
    time.sleep(0.05)
    with pytest.raises(RuntimeError, match="step 3"):
        hm.check()
    hm.beat(4)
    hm.check()  # fresh beat passes


def test_initialize_single_host_is_noop():
    initialize()  # no coordinator configured -> no-op


def test_initialize_env_var_path(monkeypatch):
    """The pod bootstrap: launch_pod.sh exports JAX_COORDINATOR_ADDRESS /
    JAX_NUM_PROCESSES / JAX_PROCESS_ID; initialize() must forward them to
    jax.distributed.initialize (VERDICT r1 next-round #5)."""
    calls = {}

    def fake_init(coordinator_address=None, num_processes=None, process_id=None):
        calls.update(
            coordinator_address=coordinator_address,
            num_processes=num_processes,
            process_id=process_id,
        )

    monkeypatch.setattr(jax.distributed, "initialize", fake_init)
    monkeypatch.setenv("JAX_COORDINATOR_ADDRESS", "10.0.0.1:1234")
    monkeypatch.setenv("JAX_NUM_PROCESSES", "4")
    monkeypatch.setenv("JAX_PROCESS_ID", "2")
    initialize()
    assert calls == dict(
        coordinator_address="10.0.0.1:1234", num_processes=4, process_id=2
    )


def test_watchdog_fires_on_stalled_step():
    """A stalled training step (no beat within timeout) must raise the
    alarm via the watchdog thread — the monitored-loop contract."""
    from atomo_tpu.parallel.launch import HealthWatchdog

    failures = []
    hm = HealthMonitor(timeout=0.05)
    wd = HealthWatchdog(hm, interval=0.01, on_failure=failures.append).start()
    try:
        hm.beat(1)
        time.sleep(0.2)  # the "stall"
    finally:
        wd.stop()
    assert failures and "step 1" in str(failures[0])


def test_watchdog_quiet_while_beating():
    from atomo_tpu.parallel.launch import HealthWatchdog

    failures = []
    hm = HealthMonitor(timeout=0.2)
    wd = HealthWatchdog(hm, interval=0.01, on_failure=failures.append).start()
    try:
        for s in range(10):
            hm.beat(s)
            time.sleep(0.01)
    finally:
        wd.stop()
    assert not failures


@pytest.mark.slow
def test_distributed_loop_beats_monitor():
    """distributed_train_loop with health_timeout armed completes a short
    run and tears the watchdog down cleanly (production wiring check)."""
    from atomo_tpu.codecs import SvdCodec
    from atomo_tpu.data import BatchIterator, SPECS, synthetic_dataset
    from atomo_tpu.models import get_model
    from atomo_tpu.parallel import distributed_train_loop, make_mesh
    from atomo_tpu.training import make_optimizer

    ds = synthetic_dataset(SPECS["mnist"], True)
    it = BatchIterator(ds, 16, seed=0)
    lines = []
    distributed_train_loop(
        get_model("lenet", 10),
        make_optimizer("sgd", lr=0.01),
        make_mesh(4),
        it,
        codec=SvdCodec(rank=2),
        max_steps=3,
        log_fn=lines.append,
        health_timeout=60.0,
    )
    assert any("Step: 3" in l for l in lines)


def test_global_mesh_spans_devices():
    mesh = global_mesh()
    assert mesh.devices.size == len(jax.devices())


@pytest.mark.slow
def test_profile_dir_captures_trace(tmp_path):
    """--profile-dir must produce a jax.profiler trace of steady-state steps
    (the fused-program observability story, utils/tracing docstring)."""
    from atomo_tpu.codecs import SvdCodec
    from atomo_tpu.data import BatchIterator, SPECS, synthetic_dataset
    from atomo_tpu.models import get_model
    from atomo_tpu.parallel import distributed_train_loop, make_mesh
    from atomo_tpu.training import make_optimizer

    ds = synthetic_dataset(SPECS["mnist"], True, size=64)
    lines = []
    distributed_train_loop(
        get_model("lenet", 10),
        make_optimizer("sgd", lr=0.01),
        make_mesh(2),
        BatchIterator(ds, 8, seed=0),
        codec=SvdCodec(rank=2),
        max_steps=4,
        log_fn=lines.append,
        profile_dir=str(tmp_path),
        profile_steps=2,
    )
    assert any("Profiling steps 2..3" in l for l in lines)
    trace_files = [
        f for _, _, fs in __import__("os").walk(tmp_path) for f in fs
    ]
    assert trace_files, "no profiler trace written"


def test_lr_schedule_parity():
    """lr = base * 0.95^(step//50) — sync_replicas_master_nn.py:106-107,232-234."""
    sched = stepwise_shrink(0.01, 0.95, 50)
    assert float(sched(0)) == pytest.approx(0.01)
    assert float(sched(49)) == pytest.approx(0.01)
    assert float(sched(50)) == pytest.approx(0.01 * 0.95)
    assert float(sched(250)) == pytest.approx(0.01 * 0.95**5)


def test_adam_amsgrad_variants_build():
    import optax

    for kwargs in (
        dict(name="adam"),
        dict(name="adam", amsgrad=True),
        dict(name="adam", weight_decay=1e-4),
        dict(name="sgd", momentum=0.9, nesterov=True, weight_decay=5e-4),
    ):
        opt = make_optimizer(**kwargs)
        assert isinstance(opt, optax.GradientTransformation)


def test_initialize_retries_transient_failure(monkeypatch):
    """The restart race: the coordinator is not listening yet on the first
    attempt; initialize() must back off and retry instead of dying (and
    must reset jax's half-initialized distributed state between tries)."""
    calls = []

    def flaky(**kw):
        calls.append(kw)
        if len(calls) == 1:
            raise RuntimeError("connect timed out")

    monkeypatch.setattr(jax.distributed, "initialize", flaky)
    monkeypatch.setenv("JAX_COORDINATOR_ADDRESS", "10.0.0.1:1234")
    monkeypatch.setenv("JAX_NUM_PROCESSES", "4")
    monkeypatch.setenv("JAX_PROCESS_ID", "2")
    initialize(backoff=0.01)
    assert len(calls) == 2
    assert calls[1]["coordinator_address"] == "10.0.0.1:1234"


def test_fence_tree_returns_finite_scalar_and_fences():
    """PR-4: the shared device->host fence used by every phase timer —
    returns the fetched float (finiteness is the caller's validity
    check) and works on pytrees and bare arrays alike."""
    from atomo_tpu.utils.tracing import fence_tree

    v = fence_tree({"a": jax.numpy.arange(4.0), "b": jax.numpy.ones((2, 2))})
    assert v == 6.0
    assert fence_tree(jax.numpy.full((3,), float("nan"))) != fence_tree(
        jax.numpy.zeros((3,))
    )  # NaN propagates out where validity checks can see it
