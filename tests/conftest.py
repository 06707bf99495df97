"""Test harness: simulate an 8-device TPU mesh on CPU.

Multi-chip hardware is not available in CI; all mesh/sharding tests run on
XLA's host platform with 8 virtual devices (SURVEY.md §4 'Implication for the
new framework'). Env vars must be set before jax is first imported.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# The suite runs cache-cold. On the CPU backend an executable loaded from
# the persistent compile cache was measured not bit-faithful to a fresh
# compile (48 bit-parity tests failed warm-cache, and re-exec'd children
# of different world sizes sharing one cache dir corrupted executions), so
# JAX's own switch is thrown here, in the environment, where the suite's
# child processes inherit it. Compile amortization is the entry points'
# default (utils/compile_cache.py), never tier-1's.
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
# Pallas kernels run in the TPU-semantics interpreter only on request
# (ops/qsgd_kernels.interpret_requested); this suite is the requester.
os.environ["ATOMO_PALLAS_INTERPRET"] = "1"

import jax  # noqa: E402

jax.config.update("jax_enable_x64", False)

import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: heavy multi-device compile/parity/convergence tests (VERDICT "
        'r3 #8b). Default run includes them; -m "not slow" is the tier-1 '
        "smoke selection, budgeted under ~13 min on 1 core. Budget "
        "discipline: when a parametrized parity family grows past its "
        "budget, mark the pricier variants slow but keep >=1 tier-1 witness "
        "per contract (see test_ring_aggregate/test_models for the "
        "pattern). The real-CIFAR convergence test additionally gates on "
        "ATOMO_RUN_SLOW=1.",
    )
    config.addinivalue_line(
        "markers",
        "perf: wall-clock performance sweeps (superstep dispatch "
        "amortization etc.). Opt-in only — they measure time, not "
        "correctness, and are meaningless on a contended 1-core CI box: "
        "additionally gate on ATOMO_RUN_PERF=1. Correctness-equivalence "
        "superstep tests are NOT marked perf and stay in tier-1.",
    )


@pytest.fixture
def rng():
    return jax.random.PRNGKey(0)
