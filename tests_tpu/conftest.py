"""On-device TPU tests (separate from tests/, whose conftest forces the CPU
platform). Run them where a TPU is attached — through the chip tool:

    python -m pytest tests_tpu/ -v

Without a TPU this directory does not skip, it ERRORS: a run that was sent
to the chip and found none must not read as a pass (pytest exits 0 on an
all-skipped session). Likewise the Pallas interpreter must be off — these
tests exist to prove Mosaic compiles the kernels for the device.

What the directory covers that the CPU suite cannot: the Mosaic-only code
paths (on-core PRNG, u32 casts, vector-layout reshapes) have no CPU
lowering, so only a test that jit-compiles them on real hardware can catch
their compile regressions; and the step programs, which the CPU suite
proves for semantics, lowering through XLA:TPU.
"""

import os

import pytest


def pytest_sessionstart(session):
    import jax

    from atomo_tpu.ops.qsgd_kernels import INTERPRET_ENV

    devs = jax.devices()  # a backend that cannot come up raises here
    if devs[0].platform != "tpu":
        raise pytest.UsageError(
            f"tests_tpu/ needs a TPU; JAX found platform "
            f"{devs[0].platform!r} ({devs[0].device_kind}). Run it through "
            "the chip tool (the CPU suite is tests/)."
        )
    if os.environ.get(INTERPRET_ENV):
        raise pytest.UsageError(
            f"{INTERPRET_ENV} is set: tests_tpu/ must compile its Pallas "
            "kernels with Mosaic, not interpret them"
        )


def _device_text() -> str:
    import jax

    devs = jax.devices()
    return f"{devs[0].device_kind} x{len(devs)} ({devs[0].platform})"


def pytest_report_header(config):
    return f"device: {_device_text()}"


@pytest.fixture(autouse=True)
def _say_what_it_ran_on(capsys):
    """Every case prints the device it ran on, past pytest's capture, so a
    log of passes names its hardware line by line."""
    with capsys.disabled():
        print(f" [device: {_device_text()}]", end=" ", flush=True)
    yield
