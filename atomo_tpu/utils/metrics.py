"""Metrics, structured per-step records, and reference-parity log lines.

The reference's observability *is* its print format: the worker line
(src/distributed_worker.py:255-258) is regex-parsed by the tuning harness
(src/tiny_tuning_parser.py:17-19), and `accuracy` (prec@k) is duplicated in
four files (SURVEY.md §5.5). Here: one accuracy implementation, a structured
``StepMetrics`` record (the machine-readable source of truth), and a
formatter emitting the reference's exact worker line shape so
existing log-scraping tooling keeps working.
"""

from __future__ import annotations

import dataclasses
import json
import time
from typing import Sequence

import jax
import jax.numpy as jnp


def accuracy(logits: jax.Array, labels: jax.Array, topk: Sequence[int] = (1, 5)):
    """prec@k percentages — single implementation of the reference's
    4x-duplicated `accuracy` (e.g. src/distributed_worker.py:42-56)."""
    k_max = max(topk)
    k_max = min(k_max, logits.shape[-1])
    _, pred = jax.lax.top_k(logits, k_max)
    correct = pred == labels[:, None]
    out = []
    for k in topk:
        k_eff = min(k, logits.shape[-1])
        out.append(jnp.mean(jnp.any(correct[:, :k_eff], axis=1)) * 100.0)
    return out


@dataclasses.dataclass
class StepMetrics:
    """One training step's record (the reference log line, structured)."""

    rank: int = 0
    step: int = 0
    epoch: int = 0
    samples_seen: int = 0
    dataset_size: int = 0
    loss: float = 0.0
    time_cost: float = 0.0
    comp_dur: float = 0.0
    encode_dur: float = 0.0
    comm_dur: float = 0.0
    msg_bytes: int = 0
    prec1: float = 0.0
    prec5: float = 0.0

    def worker_line(self) -> str:
        """The reference worker print format, byte-compatible with the
        tuning parser's regex (tiny_tuning_parser.py:17-19)."""
        pct = 100.0 * self.samples_seen / max(self.dataset_size, 1)
        return (
            "Worker: {}, Step: {}, Epoch: {} [{}/{} ({:.0f}%)], Loss: {:.4f}, "
            "Time Cost: {:.4f}, Comp: {:.4f}, Encode: {: .4f}, Comm: {: .4f}, "
            "Msg(MB): {: .4f}, Prec@1: {: .4f}, Prec@5: {: .4f}".format(
                self.rank,
                self.step,
                self.epoch,
                self.samples_seen,
                self.dataset_size,
                pct,
                self.loss,
                self.time_cost,
                self.comp_dur,
                self.encode_dur,
                self.comm_dur,
                self.msg_bytes / (1024.0**2),
                self.prec1,
                self.prec5,
            )
        )

    def json_line(self) -> str:
        return json.dumps(dataclasses.asdict(self))


class Timer:
    """Wall-clock lap timer behind the worker line's ``Time Cost`` field.

    Note: under jit a lap measures *dispatch+block* time; per-phase device
    time comes from jax.profiler traces (atomo_tpu.utils.tracing).
    """

    def __init__(self):
        self.t0 = time.time()

    def lap(self) -> float:
        now = time.time()
        dt = now - self.t0
        self.t0 = now
        return dt
