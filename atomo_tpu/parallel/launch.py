"""Multi-host launch — the TPU-native replacement for mpirun + hostfiles.

Reference behavior: L0 cluster tools provision EC2 nodes and write a hostfile
(tools/pytorch_ec2.py:656), then `mpirun -n <P+1> --hostfile hosts_address`
forks one Python process per rank (src/run_pytorch.sh:1). On TPU pods the
runtime already starts one process per host; what remains is distributed
initialization and building a global mesh whose ICI-adjacent axes stay inside
a slice while DCN connects slices.

``initialize()`` wraps jax.distributed.initialize (no-op on a single host),
``global_mesh()`` builds a mesh over *all* processes' devices, and
``HealthMonitor`` is the failure-detection hook the reference lacks entirely
(a dead MPI worker hangs its master's waitany forever — SURVEY.md §5.3;
here a missed heartbeat raises on the host so the job scheduler can restart
from the last checkpoint).
"""

from __future__ import annotations

import os
import threading
import time
from typing import Callable, Optional, Sequence

import jax

from atomo_tpu.parallel.mesh import make_mesh


def initialize(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
    attempts: int = 3,
    backoff: float = 1.0,
    init_timeout: Optional[float] = None,
) -> None:
    """Initialize the multi-host runtime.

    Single-process (one host, any number of local devices): no-op.
    Multi-process: wires jax.distributed so jax.devices() spans all hosts.
    Arguments default from the standard env (JAX_COORDINATOR_ADDRESS etc.)
    or the TPU metadata the runtime provides.

    The coordinator handshake is the classic restart race: after a failure
    the workers come back before the coordinator is listening. ``attempts``
    > 1 retries the initialize with exponential backoff (``backoff`` base
    seconds) on connection-flavored failures instead of dying into the
    scheduler's next restart round.

    ``init_timeout`` bounds each handshake attempt (seconds;
    ``initialization_timeout``). The fleet re-form
    path needs this: a member waiting at the rendezvous for a peer that
    will never arrive must fail into a recorded incident, not sit in the
    default 300 s barrier.
    """
    coordinator_address = coordinator_address or os.environ.get("JAX_COORDINATOR_ADDRESS")
    if num_processes is None:
        env = os.environ.get("JAX_NUM_PROCESSES")
        num_processes = int(env) if env else None
    if process_id is None:
        env = os.environ.get("JAX_PROCESS_ID")
        process_id = int(env) if env else None
    if coordinator_address is None and num_processes in (None, 1):
        return  # single host
    if jax.distributed.is_initialized():
        return  # idempotent no-op — the retry below must never shut down
        # a HEALTHY coordinator connection
    from atomo_tpu.training.resilience import with_retries

    def _attempt(**kw):
        try:
            jax.distributed.initialize(**kw)
        except (RuntimeError, ConnectionError, OSError):
            # jax 0.9.0 sets global_state.service and .client BEFORE
            # client.connect(), so a connect that raises leaves
            # half-initialized state and every further initialize() dies
            # on the "should only be called once" guard. Reset it so the
            # retry can actually connect. (A connect that runs into its
            # DEADLINE does not raise under 0.9.0 — the runtime client
            # terminates the process, and the scheduler's restart is the
            # retry.)
            from jax._src.distributed import global_state

            if global_state.service is not None:
                global_state.service.shutdown()
            global_state.client = None
            global_state.service = None
            global_state.preemption_sync_manager = None
            raise

    kw = dict(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )
    if init_timeout is not None:
        kw["initialization_timeout"] = max(1, int(init_timeout))
    with_retries(
        _attempt,
        attempts=max(attempts, 1),
        base_delay=backoff,
        exceptions=(RuntimeError, ConnectionError, OSError),
        on_retry=lambda i, exc: print(
            f"jax.distributed.initialize failed (attempt {i}): {exc}; "
            "retrying",
            flush=True,
        ),
    )(**kw)


def global_mesh(axes: Sequence[tuple[str, int]] = ()) -> "jax.sharding.Mesh":
    """Mesh over every device across all processes. With multi-slice
    topologies put the fastest-varying (ICI) axis last so collectives ride
    ICI within a slice and only the outer axis crosses DCN."""
    return make_mesh(axes=tuple(axes), devices=jax.devices())


def device_roster(n: int = 0) -> list[dict]:
    """JSON-able description of the first ``n`` visible devices (0 = all):
    id, platform, owning process. The elastic membership layer attaches
    this to epoch records so a post-mortem can name the PHYSICAL members
    behind the logical roster slots — on a real fleet "replica 1 left"
    means a specific chip on a specific host, and the incident should say
    which."""
    devs = jax.devices()
    if n:
        devs = devs[:n]
    return [
        {
            "id": int(d.id),
            "platform": str(getattr(d, "platform", "unknown")),
            "process": int(getattr(d, "process_index", 0)),
        }
        for d in devs
    ]


class HealthMonitor:
    """Step-heartbeat failure detector (capability the reference lacks).

    Call ``beat(step)`` after every completed step; ``check()`` raises
    ``RuntimeError`` if no beat arrived within ``timeout`` seconds — e.g.
    from a watchdog thread or the eval loop. Pair with checkpoint/resume for
    restart-based elasticity: SPMD jobs fail as a unit (an XLA collective
    with a dead participant times out), so recovery = restart from the last
    ``model_step_N``.
    """

    def __init__(self, timeout: float = 300.0):
        self.timeout = timeout
        self._last = time.monotonic()
        self._last_step = -1

    def beat(self, step: int) -> None:
        self._last = time.monotonic()
        self._last_step = step

    def check(self) -> None:
        silent = time.monotonic() - self._last
        if silent > self.timeout:
            raise RuntimeError(
                f"no training heartbeat for {silent:.0f}s "
                f"(last completed step {self._last_step}); "
                "restart from the latest checkpoint"
            )


_EXIT_GRACE_S = 30.0


def _default_failure(exc: RuntimeError) -> None:
    """Kill the job: print the diagnosis, give the main thread one graceful
    chance (KeyboardInterrupt at its next bytecode), and hard-exit after a
    grace period. The hard exit matters: a main thread hung inside a C++
    XLA collective never executes another bytecode, so interrupt_main alone
    would reproduce the reference's hung-forever waitany (SURVEY.md §5.3).
    os._exit lets the scheduler see a dead process and restart from the
    last checkpoint."""
    import _thread
    import sys

    print(f"HealthWatchdog: {exc}", file=sys.stderr, flush=True)
    _thread.interrupt_main()
    time.sleep(_EXIT_GRACE_S)
    print(
        f"HealthWatchdog: main thread did not exit within {_EXIT_GRACE_S}s "
        "of interrupt (hung collective?); hard-exiting for scheduler restart",
        file=sys.stderr, flush=True,
    )
    os._exit(13)


class HealthWatchdog:
    """Background thread that polls a :class:`HealthMonitor`.

    The production wiring (VERDICT r1 next-round #5): the distributed train
    loop ``beat()``s the monitor after every completed step; this thread
    calls ``check()`` every ``interval`` seconds and invokes ``on_failure``
    (default: print + interrupt the main thread) when the heartbeat stops —
    the failure detection the reference lacks entirely (a dead MPI worker
    hangs its master's waitany forever, SURVEY.md §5.3).
    """

    def __init__(
        self,
        monitor: HealthMonitor,
        interval: float = 10.0,
        on_failure: Optional[Callable[[RuntimeError], None]] = None,
    ):
        self.monitor = monitor
        self.interval = interval
        self.on_failure = on_failure or _default_failure
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    def start(self) -> "HealthWatchdog":
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()
        return self

    def _run(self) -> None:
        while not self._stop.wait(self.interval):
            try:
                self.monitor.check()
            except RuntimeError as exc:
                self.on_failure(exc)
                return

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5.0)
