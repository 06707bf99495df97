"""CLI surface tests: flag parity with src/distributed_nn.py:31-82, subcommand
dispatch, end-to-end smoke train, tuning parser contract."""

import time
import warnings

import pytest

from atomo_tpu.cli import build_parser, main
from atomo_tpu.tuning import DEFAULT_GRID, parse_worker_lines


REFERENCE_FLAGS = [
    # every flag the reference CLI accepts (distributed_nn.py:31-82)
    "--batch-size", "--test-batch-size", "--max-steps", "--epochs", "--lr",
    "--momentum", "--lr-shrinkage", "--no-cuda", "--seed", "--log-interval",
    "--network", "--code", "--bucket-size", "--dataset", "--comm-type",
    "--num-aggregate", "--eval-freq", "--train-dir", "--compress",
    "--enable-gpu", "--svd-rank", "--quantization-level",
]


def test_reference_flag_parity():
    parser = build_parser()
    sub = next(
        a for a in parser._actions if hasattr(a, "choices") and a.choices
    )
    train = sub.choices["train"]
    known = {s for a in train._actions for s in a.option_strings}
    missing = [f for f in REFERENCE_FLAGS if f not in known]
    assert not missing, f"reference flags missing from CLI: {missing}"


def test_bare_flags_behave_like_train(tmp_path):
    """`python -m atomo_tpu --network LeNet ...` == reference invocation."""
    rc = main([
        "--network", "LeNet", "--dataset", "MNIST", "--synthetic",
        "--batch-size", "8", "--max-steps", "2", "--eval-freq", "0",
        "--log-interval", "0", "--train-dir", str(tmp_path), "--n-devices", "1",
        "--momentum", "0.0",
    ])
    assert rc == 0


@pytest.mark.slow
def test_train_svd_smoke_with_checkpoint(tmp_path):
    rc = main([
        "train", "--network", "LeNet", "--dataset", "MNIST", "--synthetic",
        "--batch-size", "8", "--max-steps", "2", "--eval-freq", "2",
        "--save-freq", "2", "--log-interval", "0",
        "--train-dir", str(tmp_path), "--n-devices", "1",
        "--code", "svd", "--svd-rank", "2", "--momentum", "0.0",
    ])
    assert rc == 0
    assert (tmp_path / "model_step_2").exists()  # reference naming


def test_evaluate_subcommand(tmp_path):
    main([
        "train", "--network", "LeNet", "--dataset", "MNIST", "--synthetic",
        "--batch-size", "8", "--max-steps", "2", "--save-freq", "2",
        "--eval-freq", "0", "--log-interval", "0",
        "--train-dir", str(tmp_path), "--n-devices", "1", "--momentum", "0.0",
    ])
    rc = main([
        "evaluate", "--network", "LeNet", "--dataset", "MNIST", "--synthetic",
        "--test-batch-size", "32", "--model-dir", str(tmp_path),
        "--max-polls", "1", "--stop-when-idle", "--momentum", "0.0",
    ])
    assert rc == 0


def test_dead_flags_warn_not_crash(tmp_path):
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        rc = main([
            "train", "--network", "LeNet", "--dataset", "MNIST", "--synthetic",
            "--batch-size", "8", "--max-steps", "1", "--eval-freq", "0",
            "--log-interval", "0", "--train-dir", str(tmp_path),
            "--n-devices", "1", "--momentum", "0.0",
            "--comm-type", "Isend", "--num-aggregate", "3", "--enable-gpu",
        ])
    assert rc == 0
    text = " ".join(str(x.message) for x in w)
    assert "comm-type" in text and "num-aggregate" in text


def test_unknown_network_errors():
    with pytest.raises(ValueError):
        main([
            "train", "--network", "NopeNet", "--dataset", "MNIST",
            "--synthetic", "--max-steps", "1", "--n-devices", "1",
        ])


def test_tuning_parser_contract():
    """The regex must parse StepMetrics.worker_line output — the contract the
    reference's tiny_tuning_parser.py:17-19 relies on."""
    from atomo_tpu.utils.metrics import StepMetrics

    line = StepMetrics(
        rank=1, step=42, epoch=3, samples_seen=128, dataset_size=1000,
        loss=1.2345, time_cost=0.5, msg_bytes=1 << 20, prec1=55.0, prec5=90.0,
    ).worker_line()
    losses = parse_worker_lines(line, step=42)
    assert losses == [1.2345]
    assert parse_worker_lines(line, step=41) == []


def test_default_grid_matches_reference():
    # tune.sh:7 sweeps 2^-7 .. 2^-1
    assert DEFAULT_GRID == [2.0**-k for k in range(7, 0, -1)]


def test_tune_subcommand_smoke(capsys):
    rc = main([
        "tune", "--network", "LeNet", "--dataset", "MNIST", "--synthetic",
        "--batch-size", "8", "--grid", "0.1,0.01", "--tuning-steps", "3",
        "--window", "2", "--n-devices", "1", "--momentum", "0.0",
    ])
    assert rc == 0
    out = capsys.readouterr().out
    assert "best lr:" in out


@pytest.mark.parametrize(
    "layout,extra",
    [
        ("dp", []),
        ("dp-sp", ["--ways", "2", "--attn-impl", "ring"]),
        ("dp-sp", ["--ways", "2", "--attn-impl", "ulysses"]),
        ("dp-sp", ["--ways", "2", "--attn-impl", "ulysses-flash"]),
        ("dp-tp", ["--ways", "2"]),
        ("dp-tp", ["--ways", "2", "--bf16"]),
        ("dp-ep", ["--ways", "2", "--num-experts", "4"]),
        ("dp-pp", ["--ways", "2", "--microbatches", "2"]),
    ],
)
@pytest.mark.slow
def test_lm_subcommand_all_layouts(layout, extra, capsys):
    """Every parallelism layout is drivable end-to-end from the CLI on the
    8-device CPU mesh and prints the LM log line with a finite loss."""
    rc = main([
        "lm", "--layout", layout, "--vocab-size", "16", "--seq-len", "8",
        "--width", "16", "--depth", "2", "--num-heads", "2",
        "--batch-size", "8", "--max-steps", "2", "--log-interval", "1",
        "--n-devices", "4", "--code", "svd", "--svd-rank", "2",
        "--aggregate", "gather",  # pin the compressed wire the Msg assert checks
        *extra,
    ])
    assert rc == 0
    out = capsys.readouterr().out
    assert f"Layout: {layout}" in out
    import re

    losses = [float(m) for m in re.findall(r"Loss: ([0-9.]+)", out)]
    assert losses and all(l == l for l in losses)
    msgs = [float(m) for m in re.findall(r"Msg\(MB\): ([0-9.]+)", out)]
    dense = [float(m) for m in re.findall(r"Dense\(MB\): ([0-9.]+)", out)]
    assert msgs[-1] < dense[-1]  # svd codec actually compresses


def test_lm_subcommand_rejects_bad_ways():
    with pytest.raises(SystemExit):
        main(["lm", "--layout", "dp-tp", "--ways", "3", "--n-devices", "4"])


@pytest.mark.slow
def test_lm_data_file_byte_corpus(tmp_path, capsys):
    """--data-file trains on raw bytes of a real file (vocab 256)."""
    corpus = tmp_path / "corpus.txt"
    corpus.write_bytes((b"the quick brown fox jumps over the lazy dog. " * 40))
    rc = main([
        "lm", "--layout", "dp", "--data-file", str(corpus),
        "--vocab-size", "256", "--seq-len", "8", "--width", "16",
        "--depth", "1", "--num-heads", "2", "--batch-size", "8",
        "--max-steps", "2", "--log-interval", "1", "--n-devices", "2",
        "--code", "svd", "--svd-rank", "2", "--eval-freq", "2",
    ])
    assert rc == 0
    out = capsys.readouterr().out
    assert "PPL:" in out
    # --eval-freq with --data-file: held-out chunks (last 10%) validate
    assert "LM Validation: Step: 2" in out


def test_lm_data_file_rejects_small_vocab(tmp_path):
    corpus = tmp_path / "c.bin"
    corpus.write_bytes(b"x" * 1000)
    with pytest.raises(SystemExit, match="vocab-size"):
        main([
            "lm", "--data-file", str(corpus), "--vocab-size", "16",
            "--seq-len", "8", "--n-devices", "2",
        ])


@pytest.mark.slow
def test_train_zero1_multidevice(tmp_path, capsys):
    rc = main([
        "train", "--network", "LeNet", "--dataset", "MNIST", "--synthetic",
        "--batch-size", "8", "--max-steps", "2", "--eval-freq", "0",
        "--log-interval", "1", "--train-dir", str(tmp_path),
        "--n-devices", "4", "--code", "svd", "--svd-rank", "2",
        "--momentum", "0.9", "--zero1",
    ])
    assert rc == 0
    assert "Step: 2" in capsys.readouterr().out


@pytest.mark.slow
def test_lm_checkpoint_resume_sharded_layout(tmp_path, capsys):
    """lm --train-dir/--resume round-trips a MODEL-SHARDED (dp-tp) state:
    the checkpoint gathers from sharded buffers and restores onto the mesh
    shardings via load_sharded_checkpoint's shard_state path."""
    common = [
        "lm", "--layout", "dp-tp", "--ways", "2", "--vocab-size", "16",
        "--seq-len", "8", "--width", "16", "--depth", "1", "--num-heads", "2",
        "--batch-size", "8", "--log-interval", "1", "--n-devices", "4",
        "--code", "svd", "--svd-rank", "2", "--train-dir", str(tmp_path),
    ]
    assert main([*common, "--max-steps", "2"]) == 0
    assert (tmp_path / "model_step_2").exists()
    assert main([*common, "--max-steps", "4", "--resume"]) == 0
    out = capsys.readouterr().out
    assert "Resumed from" in out and "Step: 4" in out
    assert (tmp_path / "model_step_4").exists()


@pytest.mark.parametrize(
    "layout,extra",
    [
        ("dp", []),
        ("dp-tp", ["--ways", "2"]),
        ("dp-ep", ["--ways", "2", "--num-experts", "4"]),
        ("dp-pp", ["--ways", "2", "--microbatches", "2"]),
    ],
)
@pytest.mark.slow
def test_lm_eval_freq_prints_validation(layout, extra, capsys):
    """--eval-freq prints a held-out validation line for every layout via
    its single-device oracle forward on the gathered params."""
    rc = main([
        "lm", "--layout", layout, "--vocab-size", "16", "--seq-len", "8",
        "--width", "16", "--depth", "2", "--num-heads", "2",
        "--batch-size", "8", "--max-steps", "2", "--log-interval", "2",
        "--n-devices", "4", "--code", "svd", "--svd-rank", "2",
        "--eval-freq", "2", *extra,
    ])
    assert rc == 0
    out = capsys.readouterr().out
    assert "LM Validation: Step: 2" in out
    import re

    vls = [float(m) for m in re.findall(r"Validation: Step: 2, Loss: ([0-9.]+)", out)]
    assert vls and all(v == v for v in vls)
    if layout == "dp-ep":
        # ADVICE r3 #5: dp-ep also reports CE under the TRAINING per-chip
        # drop regime (chunked forward at the training capacity)
        m = re.search(r"Loss@TrainCap: ([0-9.]+) \(C=(\d+)\)", out)
        assert m, "dp-ep validation must include the train-capacity CE"
        assert float(m.group(1)) == float(m.group(1))  # finite
        # C must be the per-chip budget: ceil(1.25 * (8/4)*8 / 4) = 5
        assert int(m.group(2)) == 5


def test_overlap_flag_surface():
    """PR-4: the --overlap flag parses with its two modes and defaults to
    off (the byte-for-byte blocking program)."""
    parser = build_parser()
    sub = next(
        a for a in parser._actions if hasattr(a, "choices") and a.choices
    )
    train = sub.choices["train"]
    act = next(a for a in train._actions if "--overlap" in a.option_strings)
    assert act.default == "off"
    assert sorted(act.choices) == ["delayed", "off"]
    args = train.parse_args(["--overlap", "delayed"])
    assert args.overlap == "delayed"
    with pytest.raises(SystemExit):
        train.parse_args(["--overlap", "eager"])


# ---------------- PR 5: divergence-doctor / supervisor flags ----------------


def test_on_diverge_flag_validation():
    # densify needs a compressing codec
    with pytest.raises(SystemExit):
        main([
            "train", "--synthetic", "--n-devices", "1", "--max-steps", "1",
            "--code", "sgd", "--on-diverge", "densify",
            "--train-dir", "/tmp/nonexistent-unused",
        ])
    # densify cannot compose with the delayed overlap
    with pytest.raises(SystemExit):
        main([
            "train", "--synthetic", "--n-devices", "2", "--max-steps", "1",
            "--code", "qsgd", "--aggregate", "gather",
            "--overlap", "delayed", "--on-diverge", "densify",
            "--train-dir", "/tmp/nonexistent-unused",
        ])
    # densify cannot compose with hierarchical aggregation (hierarchical
    # needs a codec, so without this guard the conflict surfaced as an
    # uncaught ValueError at ROLLBACK time, after the timeline was pruned)
    with pytest.raises(SystemExit):
        main([
            "train", "--synthetic", "--n-devices", "2", "--max-steps", "1",
            "--code", "qsgd", "--aggregate", "hierarchical",
            "--on-diverge", "densify",
            "--train-dir", "/tmp/nonexistent-unused",
        ])
    # a config conflict must fail fast in the supervisor PARENT (argv-level
    # pre-flight), not re-exec children through the whole restart budget;
    # under supervision the old path took >= 2 backoffs before giving up
    for typo in (
        ["--code", "sgd", "--on-diverge", "densify"],
        ["--superstep", "-1"],
        ["--code", "qsgd", "--overlap", "delayed", "--aggregate", "psum"],
        ["--chaos", "frob@3"],
    ):
        t0 = time.monotonic()
        with pytest.raises(SystemExit):
            main([
                "train", "--synthetic", "--n-devices", "1", "--max-steps",
                "1", "--max-restarts", "5", "--restart-backoff", "30",
                "--train-dir", "/tmp/nonexistent-unused", *typo,
            ])
        assert time.monotonic() - t0 < 10  # no re-exec, no backoff sleeps


def test_on_diverge_preflight_symmetry():
    """_argv_preflight mirrors the in-run conflict gate: multi-device-only
    features are claimed only when the mesh can be multi-device, and every
    argv-knowable conflict (num-aggregate, retention-vs-window) fails fast
    in the supervisor parent instead of burning the restart budget."""
    from atomo_tpu.cli import _argv_preflight, build_parser

    parser = build_parser()
    sub = next(
        a for a in parser._actions if hasattr(a, "choices") and a.choices
    )
    train = sub.choices["train"]

    def preflight(*argv):
        _argv_preflight(train.parse_args(
            ["--synthetic", "--train-dir", "/tmp/unused", *argv]
        ))

    # argv-knowable densify x num-aggregate conflict: caught pre-exec
    with pytest.raises(SystemExit) as ei:
        preflight("--code", "qsgd", "--on-diverge", "densify",
                  "--num-aggregate", "2", "--n-devices", "2")
    assert "num-aggregate" in str(ei.value)
    # zero1 is multi-device-only: claimed on a mesh, ignored at n-devices 1
    with pytest.raises(SystemExit) as ei:
        preflight("--code", "qsgd", "--on-diverge", "skip",
                  "--zero1", "--n-devices", "4")
    assert "zero1" in str(ei.value)
    # --n-devices 1 disables the multi-device features: the in-run check
    # passes None for them, and preflight must not reject what it accepts
    preflight("--code", "qsgd", "--on-diverge", "densify",
              "--num-aggregate", "2", "--n-devices", "1")
    preflight("--code", "qsgd", "--on-diverge", "densify",
              "--aggregate", "hierarchical", "--n-devices", "1")
    preflight("--code", "qsgd", "--on-diverge", "skip",
              "--zero1", "--n-devices", "1")
    # keep-last-K retention shorter than the healthy-tag window
    with pytest.raises(SystemExit) as ei:
        preflight("--code", "sgd", "--on-diverge", "skip", "--n-devices",
                  "1", "--keep-ckpts", "1", "--save-freq", "2",
                  "--diverge-window", "16")
    assert "keep-ckpts" in str(ei.value)
    # supervised restarts append --resume, and a --zero1 run cannot resume
    # the delayed in-flight payload: every restart would fail instantly
    with pytest.raises(SystemExit) as ei:
        preflight("--code", "qsgd", "--overlap", "delayed", "--zero1",
                  "--n-devices", "4", "--max-restarts", "2")
    assert "zero1" in str(ei.value)
    # with checkpointing disabled (--train-dir "") resume is a no-op, so
    # supervised fresh restarts of a zero1+delayed run are fine
    _argv_preflight(train.parse_args(
        ["--synthetic", "--train-dir", "", "--code", "qsgd", "--overlap",
         "delayed", "--zero1", "--n-devices", "4", "--max-restarts", "2"]
    ))
    # a typo'd chaos spec is argv-knowable: caught before any re-exec
    with pytest.raises(SystemExit) as ei:
        preflight("--chaos", "frob@3")
    assert "frob" in str(ei.value)
    # checkpointing disabled: the doctor could never roll back to anything
    with pytest.raises(SystemExit) as ei:
        preflight("--on-diverge", "skip", "--save-freq", "0",
                  "--eval-freq", "0")
    assert "cadence" in str(ei.value)
    # --n-devices 0 (= all visible) is ambiguous from argv: preflight must
    # NOT claim multi-device features for it (a 1-device host accepts
    # these configs) — the in-run check rejects cheaply via rc=2 on a mesh
    preflight("--code", "qsgd", "--on-diverge", "skip", "--zero1",
              "--n-devices", "0")
    preflight("--code", "qsgd", "--on-diverge", "densify",
              "--num-aggregate", "2", "--n-devices", "0")
    # degenerate detector knobs are argv-knowable too: they must fail in
    # the supervisor parent, not as a ValueError in every jax-booted child
    with pytest.raises(SystemExit) as ei:
        preflight("--on-diverge", "skip", "--diverge-window", "1")
    assert "window" in str(ei.value)
    with pytest.raises(SystemExit) as ei:
        preflight("--on-diverge", "skip", "--diverge-patience", "0")
    assert "patience" in str(ei.value)


def test_preflight_validates_env_chaos_spec(monkeypatch):
    """Supervised children inherit ATOMO_CHAOS, so a typo'd env spec would
    burn the restart budget exactly like a typo'd --chaos flag; preflight
    must validate it when no flag overrides it."""
    from atomo_tpu.cli import _argv_preflight, build_parser

    parser = build_parser()
    sub = next(
        a for a in parser._actions if hasattr(a, "choices") and a.choices
    )
    train = sub.choices["train"]
    args = train.parse_args(["--synthetic", "--train-dir", "/tmp/unused"])

    monkeypatch.setenv("ATOMO_CHAOS", "frob@3")
    with pytest.raises(SystemExit) as ei:
        _argv_preflight(args)
    assert "frob" in str(ei.value)
    # a valid env spec passes, and an explicit --chaos flag wins (the env
    # is ignored in-run when the flag is set, so only the flag is checked)
    monkeypatch.setenv("ATOMO_CHAOS", "nan@2")
    _argv_preflight(args)
    monkeypatch.setenv("ATOMO_CHAOS", "frob@3")
    args2 = train.parse_args(
        ["--synthetic", "--train-dir", "/tmp/unused", "--chaos", "nan@2"]
    )
    _argv_preflight(args2)


def test_on_diverge_smoke_train(tmp_path):
    """A sane short run with the doctor armed: trains to completion with
    no rollback, writes healthy tags once the window clears."""
    rc = main([
        "train", "--synthetic", "--dataset", "MNIST", "--network", "LeNet",
        "--batch-size", "8", "--max-steps", "6", "--eval-freq", "0",
        "--save-freq", "2", "--log-interval", "0", "--n-devices", "1",
        "--train-dir", str(tmp_path), "--on-diverge", "skip",
        "--diverge-window", "2",
    ])
    assert rc == 0
    from atomo_tpu.training import latest_healthy_step

    # saves at 2/4/6; window 2 cleared past step 2 and 4 by step 6
    assert latest_healthy_step(str(tmp_path)) >= 2
