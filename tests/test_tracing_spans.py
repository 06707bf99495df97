"""The host-span primitive (utils.tracing.span), the vocabulary each loop
emits, set-up in the same ring (JAX's compile phases, init_state), the
device scopes planted in the step programs, and --profile-dir on the
one-device loops. Tiny CPU runs; no time is asserted."""

import collections
import os
import subprocess
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from atomo_tpu.utils import tracing
from atomo_tpu.utils.tracing import (
    BLOCK,
    BOUNDARY,
    DISPATCH,
    FEED_START,
    FEED_TAKE,
    FETCH,
    INIT_STATE,
    JAX_CACHE_MISS,
    JAX_COMPILE,
    JAX_LOWER,
    JAX_TRACE,
    NEXT_BATCH,
    PUT,
    SETUP_RECORDS,
    STACK,
    STEP,
    span,
    spans,
)

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))  # benchmarks.trace, the reducer's union

TRAIN = [
    "train", "--network", "LeNet", "--dataset", "MNIST", "--synthetic",
    "--batch-size", "8", "--eval-freq", "0", "--save-freq", "0",
    "--n-devices", "1", "--code", "svd", "--svd-rank", "2", "--train-dir", "",
]
LM = [
    "lm", "--layout", "dp", "--vocab-size", "16", "--seq-len", "8",
    "--width", "16", "--depth", "2", "--num-heads", "2", "--batch-size", "4",
    "--n-devices", "1", "--code", "sgd", "--aggregate", "psum",
]


# ------------------------------------------------------------ the primitive


def test_span_records_flat_tuples_with_parent_and_inherited_step():
    tracing.clear()
    with span(BLOCK, 8):
        with span(FEED_START):
            with span(PUT):
                pass
        with span(FETCH, 8):
            pass
    recs = spans()
    assert [r[0] for r in recs] == [PUT, FEED_START, FETCH, BLOCK]  # a child closes first
    by = {r[0]: r for r in recs}
    assert by[PUT][2] == FEED_START and by[FEED_START][2] == BLOCK and by[BLOCK][2] is None
    assert {r[1] for r in recs} == {8}  # one identifier for the whole iteration
    for rec in recs:
        assert type(rec) is tuple and len(rec) == 5
        assert all(type(x) in (str, int, float, type(None)) for x in rec)
        assert rec[4] >= rec[3]
    assert by[BLOCK][3] <= by[PUT][3] and by[FETCH][4] <= by[BLOCK][4]


class _Closed(BaseException):
    """What the benchmark raises through the log line to close its window."""


@pytest.mark.parametrize("error", [ValueError, KeyboardInterrupt, _Closed])
def test_span_records_and_reraises_what_its_body_raises(error):
    tracing.clear()
    with pytest.raises(error):
        with span(STEP, 3):
            with span(BOUNDARY):
                raise error("out of the log line")
    assert [(r[0], r[1], r[2]) for r in spans()] == [(BOUNDARY, 3, STEP), (STEP, 3, None)]
    with span(STEP, 4):  # the stack of open spans was unwound
        pass
    assert spans()[-1][:3] == (STEP, 4, None)


def test_ring_is_bounded_and_clear_empties_it():
    tracing.clear()
    for i in range(tracing.RING_RECORDS + 100):
        with span(DISPATCH, i):
            pass
    recs = spans()
    assert len(recs) == tracing.RING_RECORDS
    assert recs[0][1] == 100 and recs[-1][1] == tracing.RING_RECORDS + 99  # the oldest fell out
    tracing.clear()
    assert spans() == []


def test_span_is_safe_with_no_profiler_and_has_no_switch(monkeypatch):
    """Without jax.profiler the ring alone records; with it and no session
    the annotation is a flag check. Nothing turns the ring off."""
    monkeypatch.setattr(tracing, "_profiler", False)
    tracing.clear()
    with span(STEP, 1):
        with span(FETCH):
            pass
    assert [r[0] for r in spans()] == [FETCH, STEP]
    monkeypatch.setattr(tracing, "_profiler", None)  # found again on the next span
    with span(STEP, 2):
        pass
    assert tracing._profiler is jax.profiler and spans()[-1][:2] == (STEP, 2)
    assert not hasattr(tracing, "annotate")  # one host-span primitive
    assert not [k for k in os.environ if "SPAN" in k.upper() and "ATOMO" in k.upper()]


def test_spans_reach_a_profiler_session_under_their_names(tmp_path):
    """With a session on, the same spans are events of the host planes, on
    the trace's clock: what benchmarks/trace.py and `report timeline` read."""
    from jax.profiler import ProfileData

    from atomo_tpu.obs.timeline import latest_trace
    from atomo_tpu.utils.tracing import profile

    jf = jax.jit(lambda x: jnp.sum(x * x))
    float(jf(jnp.ones(64)))
    with profile(str(tmp_path)):
        for i in (5, 6):
            with span(STEP, i):
                with span(DISPATCH):
                    out = jf(jnp.ones(64))
                with span(FETCH):
                    float(out)
    found = collections.Counter()
    steps = set()
    for plane in ProfileData.from_file(latest_trace(str(tmp_path))).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in (STEP, DISPATCH, FETCH):
                        found[ev.name] += 1
                        stats = dict(ev.stats)
                        steps.add(stats.get("step_num", stats.get("step")))
    assert found == {STEP: 2, DISPATCH: 2, FETCH: 2} and steps == {5, 6}


# ------------------------------------------------------ what each loop emits


def _iterations(recs, parent):
    """{step: Counter(child name)} of the iterations under `parent`."""
    out = {}
    for name, step, par, _, _ in recs:
        if name == parent:
            out.setdefault(step, collections.Counter())
        elif par == parent:
            out.setdefault(step, collections.Counter())[name] += 1
    return out


def test_superstep_loop_emits_block_spans(capsys):
    from atomo_tpu.cli import main

    assert main(TRAIN + ["--superstep", "2", "--max-steps", "6", "--log-interval", "1"]) == 0
    recs = spans()
    its = _iterations(recs, BLOCK)
    assert sorted(its) == [2, 4, 6]  # the step each block's dispatch ends on
    for step, kids in its.items():
        assert kids == {FEED_TAKE: 1, DISPATCH: 1, FEED_START: 1, FETCH: 1, BOUNDARY: 1}, step
    feed = _iterations(recs, FEED_START)
    assert feed[2] == {STACK: 1, PUT: 1} and feed[4] == {STACK: 1, PUT: 1}
    assert not feed[6]  # nothing left to stage behind the last block
    assert not [r for r in recs if r[0] == STEP]
    assert "Worker: 0, Step: 6" in capsys.readouterr().out


def test_per_step_train_loop_emits_step_spans_and_fetches_only_when_due():
    from atomo_tpu.cli import main

    assert main(TRAIN + ["--superstep", "1", "--max-steps", "4", "--log-interval", "2"]) == 0
    its = _iterations(spans(), STEP)
    assert sorted(its) == [1, 2, 3, 4]
    for step, kids in its.items():
        due = {FETCH: 1} if step % 2 == 0 else {}
        assert kids == {NEXT_BATCH: 1, DISPATCH: 1, BOUNDARY: 1, **due}, step
    assert not [r for r in spans() if r[0] == BLOCK]


def test_lm_loop_emits_step_spans():
    """One of each span for every step, under that step's number: `fetch` and
    `boundary` in the step's own iteration, `next_batch` and `dispatch` in
    the iteration before, ahead of its fetch (one step in flight), but for
    the first step's and the second's: the first drains."""
    from atomo_tpu.cli import main

    assert main(LM + ["--max-steps", "5", "--log-interval", "1"]) == 0
    its = _iterations(spans(), STEP)
    assert sorted(its) == [1, 2, 3, 4, 5]
    for step, kids in its.items():
        assert kids == {NEXT_BATCH: 1, DISPATCH: 1, FETCH: 1, BOUNDARY: 1}, step
    by_step = {r[1]: r for r in spans() if r[0] == FETCH}
    ends = [by_step[i][4] for i in (1, 2, 3, 4, 5)]
    assert ends == sorted(ends)  # a fence-free step counter: fenced stamps in step order
    launched = {r[1]: r[3] for r in spans() if r[0] == DISPATCH}
    assert by_step[1][4] <= launched[2]  # the first step drains: the placement line reads its state
    for i in (2, 3, 4):
        assert launched[i + 1] < by_step[i][3], i  # asked for once the next step is out
    parents = {r[1]: r for r in spans() if r[0] == STEP}
    for i in (2, 3, 4):  # the launch of step i+1 lies in the iteration that reports step i
        assert parents[i][3] <= launched[i + 1] < parents[i][4], i


# ------------------------------------------------------------------ set-up


JAX_PHASES = (JAX_TRACE, JAX_LOWER, JAX_COMPILE)


def test_a_jitted_functions_first_call_leaves_one_record_of_each_phase():
    """Inside the `dispatch` that made them, on perf_counter, with parent
    None (never an iteration's child) and the step of the innermost span;
    the second call finds its program and leaves nothing."""
    def first_call(x):
        return jax.lax.sin(x)

    tracing.listen()
    x = jnp.ones(16)
    step_fn = jax.jit(first_call)
    tracing.clear()
    before = time.perf_counter()
    with span(STEP, 7):
        with span(DISPATCH):
            step_fn(x).block_until_ready()
    after = time.perf_counter()
    recs = spans()
    mine = [r for r in tracing.compile_records() if r[4] in ("first_call", "jit(first_call)")]
    assert [(r[0], r[4]) for r in mine] == [
        (JAX_TRACE, "first_call"), (JAX_LOWER, "jit(first_call)"), (JAX_COMPILE, "jit(first_call)"),
    ]
    dispatch = next(r for r in recs if r[0] == DISPATCH)
    for rec in recs:
        if rec[0] in JAX_PHASES:
            assert rec[1] == 7 and rec[2] is None, rec
            assert before <= dispatch[3] <= rec[3] <= rec[4] <= dispatch[4] <= after, rec
    assert [r[:3] for r in recs[-2:]] == [(DISPATCH, 7, STEP), (STEP, 7, None)]
    tracing.clear()
    step_fn(x).block_until_ready()
    assert spans() == []


def test_a_nested_jit_nests_its_records_and_the_reducer_counts_them_once():
    from benchmarks.trace import union_len

    inner = jax.jit(lambda x: jax.lax.cos(x))

    def outer_fn(x):
        return inner(x) + jax.lax.sin(x)

    tracing.listen()
    x = jnp.ones(16)
    outer = jax.jit(outer_fn)
    tracing.clear()
    outer(x).block_until_ready()
    traces = {r[4]: r for r in tracing.compile_records() if r[0] == JAX_TRACE}
    outer_rec, inner_rec = traces["outer_fn"], traces["<lambda>"]
    assert outer_rec[2] <= inner_rec[2] <= inner_rec[3] <= outer_rec[3]
    intervals = [(r[2], r[3]) for r in tracing.compile_records() if r[0] == JAX_TRACE]
    assert union_len(intervals) == pytest.approx(outer_rec[3] - outer_rec[2], rel=1e-9)
    assert sum(t1 - t0 for t0, t1 in intervals) > union_len(intervals)


def test_create_state_leaves_an_init_state_span_around_its_compiles():
    from atomo_tpu.models import get_model
    from atomo_tpu.training import create_state, make_optimizer

    tracing.listen()
    rng, sample = jax.random.PRNGKey(0), jnp.zeros((1, 28, 28, 1))
    tracing.clear()
    create_state(get_model("LeNet", 10), make_optimizer("sgd", lr=0.01, momentum=0.0), rng, sample)
    recs = spans()
    (init,) = [r for r in recs if r[0] == INIT_STATE]
    assert init[1:3] == (None, None)  # set-up: no step, no parent
    assert [r for r in recs if r[0] in JAX_PHASES]
    for rec in recs:
        assert rec[0] in SETUP_RECORDS and init[3] <= rec[3] <= rec[4] <= init[4], rec


@pytest.mark.parametrize("argv,parent", [
    (LM + ["--max-steps", "3", "--log-interval", "1"], STEP),
    (TRAIN + ["--superstep", "2", "--max-steps", "4", "--log-interval", "1"], BLOCK),
])
def test_after_cli_main_the_ring_holds_its_set_up_and_its_iterations_and_nothing_earlier(argv, parent):
    from atomo_tpu.cli import main

    assert main(LM + ["--max-steps", "2", "--log-interval", "1"]) == 0
    started = time.perf_counter()
    assert main(argv) == 0
    recs = spans()
    assert min(r[3] for r in recs) >= started  # nothing of the earlier call
    names = collections.Counter(r[0] for r in recs)
    assert names[INIT_STATE] == 1 and all(names[n] for n in JAX_PHASES)
    (init,) = [r for r in recs if r[0] == INIT_STATE]
    iterations = [r for r in recs if r[0] == parent and r[2] is None]
    assert len(iterations) == 2 + (parent == STEP) and init[4] <= min(r[3] for r in iterations)
    assert all(r[2] is None for r in recs if r[0] in SETUP_RECORDS)  # no iteration's child
    assert len(recs) < tracing.RING_RECORDS  # nothing of set-up fell out


def test_a_loop_entered_without_cli_main_keeps_set_up_and_drops_earlier_iterations():
    from atomo_tpu.cli import main

    assert main(LM + ["--max-steps", "2", "--log-interval", "1"]) == 0
    ran = spans()
    with span(STEP, 99):  # an earlier loop's iteration, left in the ring
        pass
    tracing.clear_iterations()
    assert spans() == [r for r in ran if r[0] in SETUP_RECORDS] and spans()


def _misses_in_a_fresh_process(cache_dir):
    code = (
        "import jax, jax.numpy as jnp\n"
        "from atomo_tpu.utils import tracing\n"
        "from atomo_tpu.utils.compile_cache import enable_compile_cache\n"
        "enable_compile_cache(log_fn=lambda m: None)\n"
        "jax.jit(lambda a: jnp.cos(a) * 3)(jnp.arange(32.0)).block_until_ready()\n"
        "names = [r[0] for r in tracing.spans()]\n"
        "print(names.count('jax_cache_miss'), names.count('jax_compile'),"
        " tracing.compile_totals()['misses'])\n"
    )
    env = {**os.environ, "JAX_COMPILATION_CACHE_DIR": str(cache_dir),
           "JAX_ENABLE_COMPILATION_CACHE": "true", "JAX_PLATFORMS": "cpu", "PYTHONPATH": str(ROOT)}
    done = subprocess.run([sys.executable, "-c", code], env=env, capture_output=True, text=True,
                          timeout=300)
    assert done.returncode == 0, done.stderr[-2000:]
    return [int(n) for n in done.stdout.split()[-3:]]


def test_a_cold_process_records_its_cache_misses_and_a_warm_one_none(tmp_path):
    """In subprocesses: within one process JAX's in-memory cache would
    hide the second compile from the persistent one."""
    misses, compiles, counted = _misses_in_a_fresh_process(tmp_path / "cache")
    assert misses >= 1 and compiles >= misses and counted == misses
    misses, compiles, counted = _misses_in_a_fresh_process(tmp_path / "cache")
    assert misses == 0 and counted == 0 and compiles >= 1  # every program loaded


# ------------------------------------------------------------ device scopes


def _scopes(lowered):
    """The scope names that open an op's name-stack path or follow a `/` in
    it (inside a scan body the path starts at the scope)."""
    import re

    return set(re.findall(r'["/(]([a-z_]+)[/)]', lowered.as_text(debug_info=True)))


@pytest.mark.parametrize("superstep", [1, 2])
@pytest.mark.parametrize("code", ["sgd", "svd"])
def test_train_step_lowers_with_its_scopes(code, superstep):
    from atomo_tpu.codecs import get_codec
    from atomo_tpu.models import get_model
    from atomo_tpu.training import create_state, make_optimizer
    from atomo_tpu.training.trainer import make_train_step

    model = get_model("LeNet", 10)
    optimizer = make_optimizer("sgd", lr=0.01, momentum=0.0)
    codec = get_codec("svd", svd_rank=2) if code == "svd" else None
    step = make_train_step(model, optimizer, codec=codec, superstep=superstep)
    lead = (superstep, 4) if superstep > 1 else (4,)
    state = create_state(model, optimizer, jax.random.PRNGKey(0), jnp.zeros((1, 28, 28, 1)))
    text = _scopes(step.lower(
        state, jax.random.PRNGKey(1), jnp.zeros((*lead, 28, 28, 1)), jnp.zeros(lead, jnp.int32)
    ))
    want = ["forward_backward", "update"] + (["encode", "decode"] if codec else [])
    assert set(want) <= text, want
    if codec is None:
        assert not {"encode", "decode"} & text


def test_dense_lm_step_lowers_with_its_scopes():
    from atomo_tpu.mesh.spec import MeshSpec
    from atomo_tpu.parallel.model_axes import build_model_axis_program
    from atomo_tpu.training import make_optimizer

    cfg = dict(vocab_size=16, max_len=8, width=16, depth=1, num_heads=2)
    prog = build_model_axis_program(
        MeshSpec.from_layout("dp", 1, 1), cfg, make_optimizer("sgd", lr=0.01, momentum=0.9),
        jax.random.PRNGKey(0), None, aggregate="psum",
    )
    tokens = prog.shard_tokens(jnp.zeros((2, 8), jnp.int32))
    text = _scopes(prog.step.lower(prog.state, jax.random.PRNGKey(1), tokens))
    assert {"forward_backward", "update", "exchange", "attention"} <= text


# ----------------------------------------------- --profile-dir, one device


def _trace_files(path):
    return [f for _, _, files in os.walk(path) for f in files if f.endswith(".xplane.pb")]


@pytest.mark.parametrize("loop,argv,line", [
    ("superstep", TRAIN + ["--superstep", "2", "--max-steps", "6", "--log-interval", "2"],
     "Profiling superstep block 3..4 -> "),
    ("per-step", TRAIN + ["--superstep", "1", "--max-steps", "5", "--log-interval", "1"],
     "Profiling steps 2..4 -> "),
    ("lm", LM + ["--max-steps", "5", "--log-interval", "1"], "Profiling steps 2..4 -> "),
])
def test_profile_dir_is_honoured_by_the_one_device_loops(loop, argv, line, tmp_path, capsys):
    from atomo_tpu.cli import main

    prof = tmp_path / "trace"
    assert main(argv + ["--profile-dir", str(prof)]) == 0
    assert line + str(prof) in capsys.readouterr().out
    assert len(_trace_files(prof)) == 1, loop
