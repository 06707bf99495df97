"""Tracing / profiling — the reference's manual time.time() spans, upgraded.

Reference behavior (SURVEY.md §5.1): workers print per-step Comp/Encode/Comm
durations measured with time.time() (src/distributed_worker.py:216-258), the
master prints Gather/Decode (src/sync_replicas_master_nn.py:197-221), and the
log line is the metrics API. Under XLA those phases fuse into one compiled
program, so wall-clock phase spans are replaced by:

  * ``span(name, step)``  — the ONE host-span primitive: a
                            jax.profiler TraceAnnotation (so the span sits
                            on the device trace's clock whenever a profiler
                            session runs) plus one flat record on
                            time.perf_counter in a bounded in-process ring
                            (``spans()``), always on. The loops' vocabulary
                            (block / step > feed_take, dispatch, feed_start
                            > stack, put, next_batch, fetch, boundary) is
                            the constants below.
  * ``listen()``          — set-up on the same ring: JAX's own
                            ``jax.monitoring`` reports of each trace,
                            lowering and backend compile, and each program
                            the persistent cache did not hold, become
                            records beside the spans (``compile_records()``
                            names the function of each).
  * ``named_phase(name)`` — jax.named_scope: the device-side half, labels
                            the ops traced under it inside the compiled
                            step.
  * ``profile(dir)``      — a jax.profiler trace capturing device timelines
                            (the honest way to see encode/decode cost inside
                            the fused step).
  * ``IncidentLog``       — the robustness stack's machine-readable
                            post-mortem artifact (train_dir/incidents.jsonl):
                            every divergence alarm, rollback, retried host
                            op, supervised restart, and give-up lands here
                            as one JSON line, so "what happened to this
                            run" is a file read, not a log archaeology dig.
"""

from __future__ import annotations

import collections
import contextlib
import json
import os
import threading
import time
from typing import Iterator, Optional

# Supervisor protocol: training.resilience.run_supervised sets this on each
# child to the 0-based run attempt index; utils.chaos keys crashloop@M on
# it. Defined in this stdlib-only module because utils cannot import
# training, and sharing one name keeps setter and reader from drifting.
ATTEMPT_ENV = "ATOMO_RUN_ATTEMPT"
# Elastic-membership protocol (same placement rationale): the supervisor
# sets this on children re-exec'd across a membership transition to the
# new epoch id; utils.chaos keys die@S:R on it (a dead member's fault
# fires only at epoch 0 — the re-admitted member comes back healthy) and
# the elastic coordinator cross-checks it against membership.json.
MEMBERSHIP_EPOCH_ENV = "ATOMO_MEMBERSHIP_EPOCH"

# The span vocabulary of the training loops (PERF.md §3). A parent span is
# one iteration: a superstep block, or one step of a per-step loop; its
# children are the boundaries where the host's work happens, and nothing
# finer. Every loop takes these names, so one reader serves them all.
BLOCK = "block"  # parent: one iteration of the superstep loop (K optimizer steps)
STEP = "step"  # parent: one iteration of a per-step loop
FEED_TAKE = "feed_take"  # taking the block the feed staged
DISPATCH = "dispatch"  # the jitted step's call, until it returns to the host
FEED_START = "feed_start"  # staging the next block; children STACK and PUT
STACK = "stack"  # stacking K host batches into one block
PUT = "put"  # jnp.asarray + device_put of the block (returns once enqueued)
NEXT_BATCH = "next_batch"  # producing and placing one batch (per-step loops)
FETCH = "fetch"  # the host waiting on the device, and the result coming back
BOUNDARY = "boundary"  # everything after the fetch: recorder, doctor, log, eval, save
# cmd_lm keeps one step in flight: its parent span, FETCH and BOUNDARY carry
# the step they report, NEXT_BATCH and DISPATCH the step they launch, which is
# the one after unless the iteration drained
PARENT_SPANS = (BLOCK, STEP)
# Set-up. A span around building a loop's initial state, and JAX's compile
# phases as records with parent None (never an iteration's child): `step` is
# that of the innermost span open when the phase ended, None before the loop
INIT_STATE = "init_state"  # training.create_state: the initial state, its eager compiles included
JAX_TRACE = "jax_trace"  # a function traced to a jaxpr (nested jits nest)
JAX_LOWER = "jax_lower"  # a jaxpr lowered to an MLIR module
JAX_COMPILE = "jax_compile"  # a backend compile, or a load from the persistent cache
JAX_CACHE_MISS = "jax_cache_miss"  # zero length: a program the persistent cache did not hold
SETUP_RECORDS = (INIT_STATE, JAX_TRACE, JAX_LOWER, JAX_COMPILE, JAX_CACHE_MISS)
_JAX_PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": JAX_TRACE,
    "/jax/core/compile/jaxpr_to_mlir_module_duration": JAX_LOWER,
    "/jax/core/compile/backend_compile_duration": JAX_COMPILE,
}
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_CACHE_MISS = "/jax/compilation_cache/cache_misses"

# (name, step, parent, t0, t1) on time.perf_counter; str/int/float only, so
# the garbage collector untracks each record on its first pass and the ring
# adds nothing to walk. A few thousand iterations of at most 8 spans, and
# set-up's few thousand compile phases.
RING_RECORDS = 32768
_ring: collections.deque = collections.deque(maxlen=RING_RECORDS)
_fun_names: collections.deque = collections.deque(maxlen=RING_RECORDS)  # (t1, fun_name) of JAX's records
_open: list = []  # (name, step) of the spans now open; the loops run on one host thread
_profiler = None  # jax.profiler once a span has looked for it; False where there is none
_listening = False
_totals = {"hits": 0, "misses": 0, "compile_s": 0.0}  # the process's, whatever cleared the ring
_totals_lock = threading.Lock()  # JAX may compile on several threads at once


def _annotation(name: str, step):
    global _profiler
    if _profiler is None:
        try:
            import jax.profiler as _profiler
        except Exception:  # no jax: the ring alone
            _profiler = False
    if not _profiler:
        return contextlib.nullcontext()
    if step is None:
        return _profiler.TraceAnnotation(name)
    if name in PARENT_SPANS:  # XProf's step tools key on this one
        return _profiler.StepTraceAnnotation(name, step_num=step)
    return _profiler.TraceAnnotation(name, step=step)


class span:
    """Host span ``name`` of iteration ``step`` (the optimizer step the
    iteration's dispatch ends on, in ``cmd_lm`` the step it reports; a child
    without one takes its parent's),
    recorded on two clocks at once: as a jax.profiler annotation, which
    costs a flag check while no profiler session runs and lands on the
    device trace's clock while one does, and as one flat record in the
    module's bounded ring on time.perf_counter (:func:`spans`). There is
    no switch: two clock reads and one append per span. The record is
    written on the way out whatever the body raised (a BaseException
    too: the benchmark closes its window by raising through the log
    line), and the exception goes on."""

    __slots__ = ("name", "step", "_parent", "_annotation", "_t0")

    def __init__(self, name: str, step: Optional[int] = None):
        self.name, self.step = name, step

    def __enter__(self):
        parent, parent_step = _open[-1] if _open else (None, None)
        if self.step is None:
            self.step = parent_step
        self._parent = parent
        self._annotation = _annotation(self.name, self.step)
        self._annotation.__enter__()
        _open.append((self.name, self.step))
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        t1 = time.perf_counter()
        try:
            self._annotation.__exit__(exc_type, exc, tb)
        finally:
            _open.pop()
            _ring.append((self.name, self.step, self._parent, self._t0, t1))
        return False


def spans() -> list[tuple]:
    """The ring's records, oldest first: ``(name, step, parent, t0, t1)``
    with the times on time.perf_counter. A child closes before its parent,
    so it comes first."""
    return list(_ring)


def clear() -> None:
    """Empty the ring (``cli.main``'s entry, tests)."""
    _ring.clear()
    _fun_names.clear()


def clear_iterations() -> None:
    """Drop every record but set-up's (:data:`SETUP_RECORDS`): a loop's
    start, so that the ring holds this loop's iterations after the set-up
    that led to them."""
    kept = [rec for rec in _ring if rec[0] in SETUP_RECORDS]
    _ring.clear()
    _ring.extend(kept)


def _innermost_step():
    try:  # a compile on another thread may meet a span closing
        return _open[-1][1]
    except IndexError:
        return None


def _on_time_span(event, start_time, end_time, fun_name="", **_):
    """JAX calls this as a phase exits, with its bounds on time.time():
    the record keeps the length and takes its end from the ring's clock."""
    name = _JAX_PHASES.get(event)
    if name is None:
        return
    t1 = time.perf_counter()
    if name == JAX_COMPILE:
        with _totals_lock:
            _totals["compile_s"] += end_time - start_time
    _ring.append((name, _innermost_step(), None, t1 - (end_time - start_time), t1))
    _fun_names.append((t1, fun_name))


def _on_event(event, **_):
    if event == _CACHE_MISS:  # reported inside the compile whose program it wrote
        t = time.perf_counter()
        _ring.append((JAX_CACHE_MISS, _innermost_step(), None, t, t))
    elif event != _CACHE_HIT:
        return
    with _totals_lock:
        _totals["misses" if event == _CACHE_MISS else "hits"] += 1


def listen() -> None:
    """Register the process's one pair of ``jax.monitoring`` listeners:
    each trace, lowering and backend compile JAX reports becomes a ring
    record (:data:`JAX_TRACE`, :data:`JAX_LOWER`, :data:`JAX_COMPILE`),
    each persistent-cache miss a zero-length :data:`JAX_CACHE_MISS`, and
    :func:`compile_totals` counts. Idempotent. They run only when JAX
    compiles something, never on a call that finds its program."""
    global _listening
    if _listening:
        return
    import jax.monitoring

    jax.monitoring.register_event_time_span_listener(_on_time_span)
    jax.monitoring.register_event_listener(_on_event)
    _listening = True


def compile_records() -> list[tuple]:
    """JAX's records in the ring, oldest first, with the function JAX named
    for each: ``(name, step, t0, t1, fun_name)``. A miss has no name of its
    own (None): it lies inside the ``jax_compile`` record of its program."""
    names = dict(_fun_names)
    return [
        (name, step, t0, t1, names.get(t1) if name != JAX_CACHE_MISS else None)
        for name, step, _, t0, t1 in spans()
        if name in SETUP_RECORDS and name != INIT_STATE
    ]


def compile_totals() -> dict:
    """Since :func:`listen`: persistent-cache ``hits`` and ``misses``, and
    ``compile_s``, the seconds of backend compiles (cache loads
    included)."""
    with _totals_lock:
        return dict(_totals)


@contextlib.contextmanager
def named_phase(name: str) -> Iterator[None]:
    """Name a TRACED region (jax.named_scope): unlike :func:`span`,
    which marks host wall-time, this labels the ops traced
    under it so the phase survives INTO the compiled program — XLA HLO op
    names and jax.profiler device timelines show ``encode``/``exchange``/
    ``decode_mean``/``ring_exchange_decode`` regions inside the fused step,
    which is the only place the fused step's phase costs are visible
    (host spans cannot cut a single XLA program). Planted in the
    aggregation paths (parallel/replicated.py, parallel/lm.py), around
    ``forward_backward`` / ``encode`` / ``decode`` / ``update`` in the
    single-device step (training/trainer.py) and the lm step, and around
    ``attention`` (parallel/ring.py), ``linear_attention`` with
    ``delta_chunk`` and ``delta_scan`` inside it
    (models/linear_attention.py) and ``ffn`` around the gated FFN
    (models/transformer.py); read by ``report timeline``
    (obs/timeline.py PHASE_OF_SCOPE). Metadata only: the compiled program
    does not change. No-op when jax lacks named_scope.

    The scope ACQUISITION alone is guarded; the body's ``yield`` stays
    outside any try/except — a bare ``except: yield`` would swallow
    exceptions contextlib throws INTO the generator and re-raise them as
    an opaque "generator didn't stop after throw()", masking real
    trace-time errors (codec misconfig, shape mismatch) in the hot step.
    """
    scope = None
    try:
        import jax

        scope = jax.named_scope(name)
    except Exception:
        scope = None
    if scope is None:
        yield
    else:
        with scope:
            yield


def fence_tree(tree) -> float:
    """Device->host scalar fetch on one leaf of ``tree`` — an execution
    fence on every backend: the host cannot hold the value before the
    program that computes it has run. ``jax.block_until_ready`` waits for
    the same work (tests_tpu/test_fence_tpu.py checks that the two agree
    on the chip); this one is kept because it also RETURNS the fetched
    float, so callers validate finiteness with the same call. One program
    runs at a time per device, so fencing any output of a program fences
    the whole program. The autopilot's probe runner (tuning/probe.py) is
    its caller."""
    import jax
    import jax.numpy as jnp

    leaf = jax.tree_util.tree_leaves(tree)[0]
    return float(jnp.sum(leaf).astype(jnp.float32))


@contextlib.contextmanager
def profile(log_dir: str) -> Iterator[None]:
    """Capture a jax.profiler trace (TensorBoard-loadable) around a block.
    Python's own tracer is off: it slows the host's work inside the
    capture (a dispatch of GPT-2 medium read 4.8 ms under it on the v5e),
    and the loops' spans (:class:`span`) already name the host side."""
    import jax.profiler

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=options)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


PROFILE_STEPS = 3  # steps a per-step loop captures under --profile-dir


class ProfileWindow:
    """``--profile-dir`` for the one-device loops: one :func:`profile`
    capture of a few steady-state iterations, opened and closed by the
    loop at the iterations ``distributed_train_loop`` captures (steps
    start+2..start+4 of a per-step loop, the second block of a superstep
    loop), with its log line and its ``profile_window`` record (the
    artifact-side join key of ``report timeline``). Without a directory
    every method returns at once."""

    def __init__(self, profile_dir: Optional[str], log_fn=print, recorder=None):
        self.profile_dir, self.log_fn, self.recorder = profile_dir, log_fn, recorder
        self.last_step: Optional[int] = None  # of the open window
        self._ctx = None
        self._done = False

    def ends_at(self, step: int) -> bool:
        """True while a capture is open whose last step ``step`` reaches:
        the caller fences that step's dispatch (and launches no later one
        first, so the capture ends on a whole step), then calls
        :meth:`close`."""
        return self._ctx is not None and step >= self.last_step

    def open(self, first_step: int, last_step: int, what: str = "steps") -> None:
        if not self.profile_dir or self._done or self._ctx is not None:
            return
        self._ctx = profile(self.profile_dir)
        self._ctx.__enter__()
        self.last_step = last_step
        self.log_fn(
            f"Profiling {what} {first_step}..{last_step} -> {self.profile_dir}"
        )
        if self.recorder is not None:
            self.recorder.write_meta({
                "what": "profile_window",
                "first_step": first_step,
                "last_step": last_step,
                "profile_dir": self.profile_dir,
            })

    def close(self) -> None:
        """Stop the capture; the caller has fenced the window's last
        dispatch, so the trace holds all of it."""
        if self._ctx is not None:
            ctx, self._ctx, self._done = self._ctx, None, True
            ctx.__exit__(None, None, None)


def write_json_atomic(path: str, obj) -> None:
    """Write ``obj`` as JSON via tmp + ``os.replace`` — readers never see a
    torn file, even under SIGKILL mid-write (atomic on POSIX). The ONE
    artifact-writing discipline shared by the autopilot's
    ``tune_decision.json`` and the LR grid's ``lr_grid.json``, so every
    evidence file survives the failures the robustness stack drills.
    Raises OSError to the caller — artifact criticality (best-effort vs
    must-land) is a per-call-site policy."""
    d = os.path.dirname(path)
    if d:
        os.makedirs(d, exist_ok=True)
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(obj, f, indent=1)
    os.replace(tmp, path)


INCIDENT_LOG_NAME = "incidents.jsonl"


def read_jsonl(path: str) -> list[dict]:
    """Tolerant JSONL reader — the ONE parse discipline for every
    append-only evidence stream (incidents.jsonl and the flight
    recorder's metrics.jsonl): a missing file is an empty history, and
    torn trailing lines (a write interrupted by SIGKILL) are skipped —
    the artifact must stay readable after exactly the failures it
    documents."""
    if not os.path.exists(path):
        return []
    out = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except json.JSONDecodeError:
                continue
            if isinstance(rec, dict):
                out.append(rec)
    return out


def format_incident(r: dict) -> str:
    """One incident record as one human post-mortem line. The ONE
    formatter shared by :meth:`IncidentLog.summarize` and the flight
    recorder's run report (obs/report.py) — the PR-9 epoch/world/rc
    special-cases used to live only inside summarize and would have
    drifted the moment a second surface printed incidents."""
    bits = [f"+{r.get('uptime_s', 0.0):.1f}s", r.get("cause", "?")]
    if "step" in r:
        bits.append(f"step={r['step']}")
    if "target" in r:
        bits.append(f"target={r['target']}")
    if "attempt" in r:
        bits.append(f"attempt={r['attempt']}")
    # membership / elastic-triage context (PR-9): the epoch and world
    # size ARE the record for a membership line — dropping them would
    # reduce a reshape to an unexplained "-> shrink"
    if "epoch" in r:
        bits.append(f"epoch={r['epoch']}")
    if "world" in r:
        bits.append(f"world={r['world']}")
    if "rc" in r:
        bits.append(f"rc={r['rc']}")
    if r.get("action"):
        bits.append(f"-> {r['action']}")
    return " ".join(bits)


class IncidentLog:
    """Append-only JSONL incident stream (the post-mortem artifact).

    Schema — every record carries:
      ts        unix seconds at append time
      uptime_s  seconds since this writer process opened the log
      cause     what happened ("divergence", "crash", "retry",
                "clean_exit", "budget_exhausted", ...)
      action    what was done about it ("rollback", "restart", "give_up",
                "done", "retry", ...)
    plus the optional context fields ``step`` (trainer step), ``target``
    (rollback target step), ``attempt`` (supervised restart index), and any
    extra keyword detail the caller provides.

    Each record is ONE ``write()`` of one newline-terminated line in append
    mode, so concurrent writers (the trainer process and its supervisor)
    interleave at line granularity on POSIX — the file always parses.
    """

    def __init__(self, path: str):
        self.path = path
        self._t0 = time.time()
        parent = os.path.dirname(path)
        if parent:
            os.makedirs(parent, exist_ok=True)

    @classmethod
    def for_train_dir(cls, train_dir: str) -> "IncidentLog":
        return cls(os.path.join(train_dir, INCIDENT_LOG_NAME))

    def append(
        self,
        cause: str,
        *,
        action: str = "",
        step: Optional[int] = None,
        target: Optional[int] = None,
        attempt: Optional[int] = None,
        **detail,
    ) -> dict:
        now = time.time()
        rec = {
            "ts": round(now, 3),
            "uptime_s": round(now - self._t0, 3),
            "cause": cause,
            "action": action,
        }
        if step is not None:
            rec["step"] = int(step)
        if target is not None:
            rec["target"] = int(target)
        if attempt is not None:
            rec["attempt"] = int(attempt)
        rec.update(detail)
        try:
            with open(self.path, "a") as f:
                f.write(json.dumps(rec) + "\n")
        except OSError as exc:
            # best-effort: incidents are often recorded exactly when the
            # filesystem is misbehaving (e.g. inside with_retries' except
            # handler for a failed checkpoint save) — the post-mortem
            # artifact must never crash the run it documents
            import warnings

            warnings.warn(f"incident log append failed: {exc}")
        return rec

    @staticmethod
    def read(path: str) -> list[dict]:
        """Parse an incidents.jsonl; missing file = no incidents. Torn
        trailing lines (a write interrupted by a kill) are skipped — the
        log must stay readable after exactly the failures it documents
        (the shared :func:`read_jsonl` discipline)."""
        return read_jsonl(path)

    @staticmethod
    def summarize(path: str) -> str:
        """Human post-mortem: one line per incident, oldest first
        (:func:`format_incident` — shared with the obs run report)."""
        recs = IncidentLog.read(path)
        if not recs:
            return f"no incidents recorded in {path!r}"
        lines = [f"incident log {path} ({len(recs)} records):"]
        for r in recs:
            lines.append("  " + format_incident(r))
        return "\n".join(lines)
