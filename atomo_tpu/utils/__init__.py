"""Shared utilities: metrics, logging, tracing."""

from atomo_tpu.utils.metrics import (  # noqa: F401
    StepMetrics,
    Timer,
    accuracy,
)
