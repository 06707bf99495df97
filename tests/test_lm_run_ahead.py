"""`cmd_lm` keeps one step in flight: iteration j launches step j+1, then
fetches the loss of step j. What a run prints, records and saves is what
the synchronous loop gave (`_synchronous_lm`, a frozen copy of `cmd_lm` as
PR 31 left it), but for `Time Cost:`; the span ring shows
which iterations ran ahead and which drained. Tiny CPU runs; no time is
asserted."""

import json
import math
import re
import sys
import time

import jax
import pytest

from atomo_tpu import cli
from atomo_tpu.obs.timeline import ran_ahead, summarize_timeline
from atomo_tpu.utils import tracing
from atomo_tpu.utils.tracing import BOUNDARY, DISPATCH, FETCH, NEXT_BATCH, STEP, spans

LM = [
    "lm", "--layout", "dp", "--vocab-size", "16", "--seq-len", "8",
    "--width", "16", "--depth", "2", "--num-heads", "2", "--batch-size", "4",
    "--n-devices", "1", "--code", "sgd", "--aggregate", "psum",
]


def _synchronous_lm(argv):
    """`cmd_lm` as PR 31 left it, for what these runs use of it (`--layout
    dp` on one device, `--code sgd`, synthetic tokens): its set-up, and its
    loop statement for statement: launch, fetch the same step's loss, then
    that step's boundary work. Never edited with the program."""
    import numpy as np
    import optax

    from atomo_tpu.mesh.spec import MeshSpec
    from atomo_tpu.models.transformer import TransformerLM
    from atomo_tpu.obs import FlightRecorder
    from atomo_tpu.parallel.mesh import device_line, placement_line
    from atomo_tpu.parallel.mesh import replicated
    from atomo_tpu.parallel.model_axes import build_model_axis_program
    from atomo_tpu.training import make_optimizer
    from atomo_tpu.training.checkpoint import latest_step, load_checkpoint, save_checkpoint

    args = cli.build_parser().parse_args(argv)
    assert (args.layout, args.n_devices, args.code, args.aggregate) == ("dp", 1, "sgd", "psum")
    optimizer = make_optimizer(
        args.optimizer, lr=args.lr, lr_shrinkage=args.lr_shrinkage,
        shrinkage_freq=args.shrinkage_freq, momentum=args.momentum,
        nesterov=args.nesterov, weight_decay=args.weight_decay,
    )
    cfg = dict(
        vocab_size=args.vocab_size, max_len=args.seq_len, width=args.width,
        depth=args.depth, num_heads=args.num_heads,
    )
    key = jax.random.PRNGKey(args.seed)
    spec = MeshSpec.from_layout("dp", 1, 1)
    prog = build_model_axis_program(
        spec, cfg, optimizer, key, None, attn_impl=args.attn_impl,
        num_microbatches=args.microbatches, compute_dtype=None, aggregate="psum", exchange=None,
    )
    mesh, state, step, shard = prog.mesh, prog.state, prog.step, prog.shard_tokens
    assert prog.state_specs is None
    print(device_line(mesh), flush=True)
    rng = np.random.default_rng(args.seed)

    def _synth(r, n):
        starts = r.integers(0, args.vocab_size, size=(n, 1))
        strides = r.integers(1, 4, size=(n, 1))
        return ((starts + strides * np.arange(args.seq_len)) % args.vocab_size).astype(np.int32)

    eval_tokens = (
        _synth(np.random.default_rng(args.seed + 10_000), args.batch_size) if args.eval_freq else None
    )

    def eval_ppl(state):
        toks = jax.numpy.asarray(eval_tokens[: args.batch_size])
        logits = TransformerLM(**cfg).apply({"params": jax.device_get(state.params)}, toks)
        return float(
            optax.softmax_cross_entropy_with_integer_labels(logits[:, :-1], toks[:, 1:]).mean()
        )

    start = 0
    if args.train_dir and args.resume and latest_step(args.train_dir) is not None:
        state = jax.device_put(
            load_checkpoint(args.train_dir, jax.device_get(state)), replicated(mesh)
        )
        start = int(state.step)
        print(f"Resumed from {args.train_dir} at step {start}", flush=True)
    recorder = None
    if args.train_dir:
        recorder = FlightRecorder.for_train_dir(args.train_dir)
        if start:
            recorder.prune_past(start)
        recorder.set_context(aggregate="psum")
        recorder.write_meta({
            "what": "model_axes", "layout": "dp", "mesh_axes": spec.shape_dict(), "exchange": None,
        })
    save_freq = args.save_freq
    for i in range(start + 1, args.max_steps + 1):
        t0 = time.time()
        batch = shard(_synth(rng, args.batch_size))
        state, metrics = step(state, jax.random.fold_in(key, i), batch)
        loss = float(metrics["loss"])
        if i == start + 1:
            print(placement_line(state, batch), flush=True)
        if recorder is not None:
            recorder.record_block(i, jax.device_get(metrics), wall_s=time.time() - t0)
        if i % args.log_interval == 0 or i == args.max_steps:
            print(
                f"LM: Step: {i}, Layout: dp({spec.describe()}), "
                f"Loss: {loss:.4f}, PPL: {math.exp(min(loss, 30.0)):.2f}, "
                f"Time Cost: {time.time() - t0:.4f}, "
                f"Msg(MB): {float(metrics['msg_bytes']) / 1e6:.4f}, "
                f"Dense(MB): {float(metrics['dense_bytes']) / 1e6:.4f}",
                flush=True,
            )
        if args.eval_freq and i % args.eval_freq == 0:
            vl = eval_ppl(state)
            print(
                f"LM Validation: Step: {i}, Loss: {vl:.4f}, PPL: {math.exp(min(vl, 30.0)):.2f}",
                flush=True,
            )
        if args.train_dir and ((save_freq and i % save_freq == 0) or i == args.max_steps):
            save_checkpoint(args.train_dir, state, compress=args.compress)
    return 0


def _what_a_run_leaves(train_dir, out):
    """Its lines without their times, its records without theirs, and every
    checkpoint's bytes."""
    lines = [
        re.sub(r"Time Cost: [^,]+, ", "", line).replace(str(train_dir), "<dir>")
        for line in out.splitlines()
        if line.startswith(("LM", "Placement", "Resumed"))
    ]
    records = [
        {k: v for k, v in json.loads(line).items() if k not in ("ts", "step_ms")}
        for line in (train_dir / "metrics.jsonl").read_text().splitlines()
    ]
    saved = {p.name: p.read_bytes() for p in sorted(train_dir.glob("model_step_*"))}
    return lines, records, saved


CASES = {
    "plain": (["--max-steps", "6", "--log-interval", "1"], None),
    "eval": (["--max-steps", "6", "--log-interval", "1", "--eval-freq", "2"], None),
    "save": (["--max-steps", "7", "--log-interval", "1", "--save-freq", "3"], None),
    "log3": (["--max-steps", "7", "--log-interval", "3"], None),
    "all": (["--max-steps", "8", "--log-interval", "3", "--eval-freq", "4", "--save-freq", "2"], None),
    "resume": (
        ["--max-steps", "4", "--log-interval", "1", "--save-freq", "2"],
        ["--max-steps", "9", "--log-interval", "2", "--save-freq", "3", "--eval-freq", "3",
         "--resume"],
    ),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_a_run_leaves_what_the_synchronous_loop_left(case, tmp_path, capsys):
    first, then = CASES[case]
    left = {}
    for side, run in (("ahead", cli.main), ("synchronous", _synchronous_lm)):
        train_dir = tmp_path / side
        for flags in filter(None, (first, then)):
            assert run(LM + flags + ["--train-dir", str(train_dir)]) == 0
        left[side] = _what_a_run_leaves(train_dir, capsys.readouterr().out)
    lines, records, saved = left["ahead"]
    assert lines == left["synchronous"][0]
    assert records == left["synchronous"][1]
    assert saved == left["synchronous"][2]
    last = int((then or first)[1])
    assert f"model_step_{last}" in saved and any(l.startswith(f"LM: Step: {last},") for l in lines)
    assert [r["step"] for r in records if r["kind"] == "step"] == list(range(1, last + 1))


def test_a_checkpoint_at_step_k_holds_the_state_after_step_k(tmp_path):
    """Step 2's save happens with nothing launched after it: it is the file a
    run that ends at step 2 leaves, not the state one step on."""
    long, short = tmp_path / "long", tmp_path / "short"
    assert cli.main(LM + ["--max-steps", "5", "--save-freq", "2", "--train-dir", str(long)]) == 0
    assert cli.main(LM + ["--max-steps", "2", "--train-dir", str(short)]) == 0
    assert (long / "model_step_2").read_bytes() == (short / "model_step_2").read_bytes()
    assert (long / "model_step_2").read_bytes() != (long / "model_step_4").read_bytes()


def _iterations(recs):
    """{step of the iteration: [(name, step) of its children, in time order]}"""
    parents = [r for r in recs if r[0] == STEP]
    out = {p[1]: [] for p in parents}
    for name, step, _, t0, t1 in sorted((r for r in recs if r[2] == STEP), key=lambda r: r[3]):
        (inside,) = [p for p in parents if p[3] <= t0 and t1 <= p[4]]
        out[inside[1]].append((name, step))
    return out


def test_the_ring_shows_which_iterations_ran_ahead_and_which_drained(tmp_path):
    """Steps 1 (first), 4 and 8 (evaluation), 6 (save) and 10 (last) drain;
    every other iteration launches the next step before it asks for its own
    loss, and the one after a drain launches two."""
    assert cli.main(LM + [
        "--max-steps", "10", "--log-interval", "1", "--eval-freq", "4", "--save-freq", "6",
        "--train-dir", str(tmp_path),
    ]) == 0
    its = _iterations(spans())
    assert sorted(its) == list(range(1, 11))
    drains = {1, 4, 6, 8, 10}
    for j, kids in its.items():
        own = [] if j - 1 not in drains and j > 1 else [(NEXT_BATCH, j), (DISPATCH, j)]
        ahead = [] if j in drains else [(NEXT_BATCH, j + 1), (DISPATCH, j + 1)]
        assert kids == own + ahead + [(FETCH, j), (BOUNDARY, j)], j
    fetch = {r[1]: r for r in spans() if r[0] == FETCH}
    dispatch = {r[1]: r for r in spans() if r[0] == DISPATCH}
    assert sorted(fetch) == sorted(dispatch) == list(range(1, 11))  # one of each a step
    for j in range(1, 10):
        if j in drains:
            assert fetch[j][4] <= dispatch[j + 1][3], j  # its state was read: nothing queued
        else:
            assert dispatch[j + 1][3] < fetch[j][3], j  # the next launch is out first
    ends = [fetch[j][4] for j in range(1, 11)]
    assert ends == sorted(ends)  # the fenced stamps come in step order


class _Closed(BaseException):
    """What the benchmark raises through the log line to close its window."""


class _ClosesAt:
    """A stdout that raises when the line of step `step` appears."""

    def __init__(self, step):
        self.mark, self.text = f"LM: Step: {step},", ""

    def write(self, text):
        self.text += text
        if self.mark in self.text:
            raise _Closed
        return len(text)

    def flush(self):
        pass


def test_a_base_exception_out_of_print_leaves_the_loop_at_once(monkeypatch):
    monkeypatch.setattr(sys, "stdout", _ClosesAt(3))
    with pytest.raises(_Closed):
        cli.main(LM + ["--max-steps", "50", "--log-interval", "1"])
    recs = spans()
    assert recs[-1][:3] == (STEP, 3, None)  # raised from step 3's line, in the iteration of step 3
    assert recs[-2][:3] == (BOUNDARY, 3, STEP)
    assert max(r[1] for r in recs if r[0] == DISPATCH) == 4  # nothing launched after it
    assert max(r[1] for r in recs if r[0] == FETCH) == 3  # and step 4 was not waited for
    with tracing.span(STEP, 5):  # the stack of open spans was unwound
        pass
    assert spans()[-1][:3] == (STEP, 5, None)


@pytest.mark.parametrize("steps,interval,want", [(7, 5, [5, 7]), (6, 3, [3, 6]), (1, 4, [1])])
def test_the_last_steps_line_is_printed(steps, interval, want, capsys):
    assert cli.main(LM + ["--max-steps", str(steps), "--log-interval", str(interval)]) == 0
    out = capsys.readouterr().out
    assert [int(n) for n in re.findall(r"^LM: Step: (\d+),", out, re.M)] == want


def test_the_profile_window_closes_on_the_loss_of_its_last_step(tmp_path, capsys):
    """Steps 2..4 are captured whole: step 4 drains, so the capture stops
    with its loss in hand and step 5 not yet launched."""
    prof = tmp_path / "trace"
    assert cli.main(LM + ["--max-steps", "7", "--log-interval", "1", "--profile-dir", str(prof)]) == 0
    assert f"Profiling steps 2..4 -> {prof}" in capsys.readouterr().out
    fetch = {r[1]: r for r in spans() if r[0] == FETCH}
    dispatch = {r[1]: r for r in spans() if r[0] == DISPATCH}
    assert fetch[4][4] <= dispatch[5][3]
    assert dispatch[4][3] < fetch[3][3] and dispatch[6][3] < fetch[5][3]


def test_report_timeline_counts_the_iterations_that_ran_ahead():
    """The ring of a run of 6 steps, as `report timeline` holds the trace's
    host spans: iterations 2 to 5 launched the next step before they asked
    for their own loss; the first and the last drained."""
    assert cli.main(LM + ["--max-steps", "6", "--log-interval", "1"]) == 0
    host = [
        {"name": name, "step": step, "start_us": 1e6 * t0, "end_us": 1e6 * t1}
        for name, step, _, t0, t1 in spans()
    ]
    assert ran_ahead(host) == (4, 6)
    doc = {"host_spans": [], "ran_ahead": list(ran_ahead(host)), "consistent": True}
    assert "  ran ahead: 4 of 6 iterations" in summarize_timeline(doc).splitlines()
    assert "ran ahead" not in summarize_timeline({"ran_ahead": [0, 0], "consistent": True})
