"""The benchmark's arithmetic, on inputs small enough to count by hand."""

import json
import math
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]


def _data():
    from benchmarks.run import Data

    return Data(ROOT / "BENCHMARK.json")


def _ctx(stamps, window=None, cut=None, **more):
    return {"stamps": stamps, "window": window or (0, len(stamps) - 1), "slice": cut,
            "process_start": 0.0, "compiles": [], "counters": {}, "peak_bytes": [], **more}


def _steady(n=21, dt=0.1, t0=50.0, per_line=1):
    return [(t0 + i * dt, 10 + i * per_line, 1.0) for i in range(n)]


def _reduce(name, ctx, **args):
    return _data().module("reducers", name).reduce(ctx, **args)


# ---- the window's arithmetic -------------------------------------------------

def test_step_ms_is_the_window_over_its_steps():
    assert _reduce("window_step_ms", _ctx(_steady())) == pytest.approx(100.0)


def test_a_superstep_line_stands_for_its_steps():
    assert _reduce("window_step_ms", _ctx(_steady(dt=0.8, per_line=8))) == pytest.approx(100.0)
    assert _reduce("stamp_stat", _ctx(_steady(dt=0.8, per_line=8)), stat="median") == pytest.approx(100.0)


def test_a_stall_moves_step_ms_and_the_slowest_and_leaves_the_median():
    stalled = [(t + (1.0 if i >= 10 else 0.0), n, loss) for i, (t, n, loss) in enumerate(_steady())]
    assert _reduce("window_step_ms", _ctx(stalled)) == pytest.approx(150.0)
    assert _reduce("stamp_stat", _ctx(stalled), stat="max") == pytest.approx(1100.0)
    assert _reduce("stamp_stat", _ctx(stalled), stat="median") == pytest.approx(100.0)


def test_step_times_leave_out_the_profiled_slice():
    stamps = [(t + (5.0 if i >= 8 else 0.0), n, loss) for i, (t, n, loss) in enumerate(_steady())]
    assert _reduce("stamp_stat", _ctx(stamps, cut=[6, 9]), stat="max") == pytest.approx(100.0)
    assert _reduce("stamp_stat", _ctx(stamps), stat="max") == pytest.approx(5100.0)


def test_setup_s_runs_from_process_start_to_the_first_stamp_of_the_window():
    assert _reduce("setup_s", _ctx(_steady(), window=(3, 20))) == pytest.approx(50.3)


def test_compiles_are_counted_inside_the_window_only():
    ctx = _ctx(_steady(), compiles=[(49.0, 1.0), (50.5, 0.2), (51.0, 0.1), (60.0, 3.0)])
    assert _reduce("compiles_in_window", ctx) == 2.0


def test_peak_reducer_returns_nothing_when_there_is_nothing_to_read():
    assert _reduce("peak_hbm_gib", _ctx(_steady())) is None
    assert _reduce("peak_hbm_gib", _ctx(_steady(), peak_bytes=[2**30, 3 * 2**30])) == 3.0


def test_stamper_opens_after_the_checked_lines_and_closes_on_the_clock(monkeypatch):
    import io
    import re

    from benchmarks import run

    clock = iter(x * 0.5 for x in range(1000))
    monkeypatch.setattr(run.time, "perf_counter", lambda: next(clock))
    stamper = run.Stamper(re.compile(r"^S (\d+) (\S+)"), io.StringIO(), seconds=2.0, skip_lines=4)
    with pytest.raises(run.WindowClosed):
        for i in range(1, 100):
            stamper.write(f"S {i} {'nan' if i == 7 else '1.5'}\nnot a step\n")
    # the clock ticks once per line, so a step and its other line take a second
    assert (stamper.first, stamper.last) == (4, 6)
    assert [n for _, n, _ in stamper.stamps] == list(range(1, 8))
    assert math.isnan(stamper.stamps[6][2])


# ---- FLOPs from shapes ---------------------------------------------------------

def test_transformer_flops_against_a_hand_count():
    from benchmarks.flops import transformer

    cfg = {"n_embd": 8, "n_layer": 2, "vocab_size": 32}
    # per layer: 3 tokens x (qkv 8x24 + proj 8x8 + up 8x32 + down 32x8) multiply-adds
    matmuls = 2 * 3 * (8 * 24 + 8 * 8 + 8 * 32 + 32 * 8)
    # causal attention: 3 queries see 1 + 2 + 3 = 6 keys, two matmuls of width 8
    attention = 2 * 2 * 6 * 8
    head = 2 * 2 * 8 * 32  # two predicting positions
    assert transformer.forward_flops(cfg, batch=1, seq=3) == 2 * (matmuls + attention) + head
    flags = {"--batch-size": "5", "--seq-len": "3"}
    assert transformer.train_flops_per_step(cfg, flags) == 3 * 5 * transformer.forward_flops(cfg, 1, 3)


def test_gpt2_medium_needs_about_six_flops_per_parameter_and_token():
    from benchmarks.flops import transformer

    cfg = json.loads((ROOT / "benchmarks/configs/gpt2-medium.json").read_text())
    flops = transformer.train_flops_per_step(cfg, {"--batch-size": "4", "--seq-len": "1024"})
    dense = 6 * (24 * 12 * 1024**2 + 1024 * 50257) * 4096
    assert dense < flops < 1.2 * dense


def test_resnet_block_flops_against_a_hand_count():
    from benchmarks.flops import resnet_cifar

    # a 3x3 convolution onto a 4x4 map, 2 -> 5 channels: 16 outputs x 9 taps x 2 x 5 multiply-adds
    assert resnet_cifar.conv_flops(4, 4, 3, 2, 5) == 2 * 16 * 9 * 2 * 5
    # a block that keeps its shape has two 3x3 convolutions and no shortcut convolution
    assert resnet_cifar.basic_block_flops(8, 4, 4, 1) == 2 * (2 * 64 * 9 * 4 * 4)
    # one that halves the map and doubles the planes adds the 1x1 shortcut, all on the 4x4 map
    strided = 2 * 16 * 9 * 4 * 8 + 2 * 16 * 9 * 8 * 8 + 2 * 16 * 1 * 4 * 8
    assert resnet_cifar.basic_block_flops(8, 4, 8, 2) == strided


def test_resnet18_step_is_three_forward_passes_of_about_half_a_gigaflop_per_image():
    from benchmarks.flops import resnet_cifar

    cfg = json.loads((ROOT / "benchmarks/configs/resnet18-cifar10.json").read_text())
    forward = resnet_cifar.forward_flops_per_image(cfg)
    assert 1.10e9 < forward < 1.12e9  # the CIFAR ResNet-18's 0.56 G multiply-adds
    assert resnet_cifar.train_flops_per_step(cfg, {"--batch-size": "7"}) == 3 * 7 * forward


def test_a_counter_in_mib_is_the_steps_own_count_or_nothing():
    assert _reduce("counter_mib", _ctx(_steady(), counters={"msg_bytes": 622124.0}),
                   counter="msg_bytes") == pytest.approx(0.5933, abs=5e-5)
    assert _reduce("counter_mib", _ctx(_steady()), counter="msg_bytes") is None


def test_mfu_is_flops_over_median_time_chips_and_peak():
    ctx = _ctx(_steady(), flops_per_step=197e12 * 0.1 * 0.25, cell={"chips": 1},
               peaks={"bf16_flops_per_s": 197e12})
    assert _reduce("step_mfu_pct", ctx) == pytest.approx(25.0)
    assert _reduce("step_mfu_pct", {**ctx, "cell": {"chips": 4}}) == pytest.approx(6.25)
    assert _reduce("step_mfu_pct", {**ctx, "peaks": None}) is None


# ---- peaks ---------------------------------------------------------------------

def test_peaks_table_names_the_v5e_with_its_source():
    peaks = json.loads((ROOT / "benchmarks/peaks.json").read_text())
    v5e = peaks["TPU v5 lite"]
    assert v5e["bf16_flops_per_s"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["ici_bits_per_s"] == 1600e9 and "TPU v5e" in v5e["source"]


def test_an_unknown_device_kind_is_refused(monkeypatch):
    import types

    import jax

    from benchmarks import run

    fake = types.SimpleNamespace(platform="tpu", device_kind="TPU v9 imaginary")
    monkeypatch.setattr(jax, "devices", lambda *a: [fake])
    args = run.parse(["--workload", CELLS[0], "--seed", "1", "--seconds", "1"])
    with pytest.raises(SystemExit, match="peaks.json has no device_kind"):
        run.run_cell(args)


def test_a_cell_without_its_chips_fails_and_prints_no_result():
    from benchmarks import run

    args = run.parse(["--workload", CELLS[0], "--seed", "1", "--seconds", "1"])
    with pytest.raises(SystemExit, match="needs .* TPU chip"):
        run.run_cell(args)  # the test suite runs on the CPU


# ---- the cells' argv -----------------------------------------------------------

@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("rehearse", [False, True], ids=["real", "tiny"])
def test_argv_of_a_cell_parses_under_the_programs_parser(cell, rehearse):
    from atomo_tpu.cli import build_parser
    from benchmarks import run

    data = _data()
    entry = data.cell(cell)
    config, traffic = data.config(entry["config"]), data.json("traffic", entry["traffic"])
    if rehearse:
        config, traffic = run.tiny(config, traffic)
    argv, flags = run.program_argv(config, traffic, seed=2**31 + 12345)
    args = build_parser().parse_args(argv)
    assert 0 <= args.seed < 2**31 and args.n_devices == entry["chips"]
    assert not getattr(args, "train_dir", "") and not args.eval_freq
    assert all("{" not in str(v) for v in flags.values())


# ---- the comparison ------------------------------------------------------------

def _side(scale=1.0, losses=(2.0, 1.9, 1.8)):
    leaves = {"a": 1.0, "b": 2.0, "c": 4.0, "dead": 1e-6}
    return {"losses": list(losses), "grad1_norms": {k: v * scale for k, v in leaves.items()},
            "change_norms": {k: 0.01 * v * scale for k, v in leaves.items()}}


def test_equal_sides_read_zero_and_pass():
    from benchmarks import check

    numbers = check.training_numbers(_side(), _side())
    assert {k: v["value"] for k, v in numbers.items()} == {"loss_gap": 0, "grad1_gap": 0, "change_gap": 0}
    assert check.judge(numbers, {"loss_gap": 0.0, "grad1_gap": 0.0, "change_gap": 0.0})[0]


def test_gaps_are_taken_by_the_worst_leaf_against_the_larger_of_leaf_and_median():
    from benchmarks import check

    prog = _side()
    prog["grad1_norms"]["c"] = 4.4  # 10% of its own norm
    prog["grad1_norms"]["dead"] = 0.1  # huge against itself, 1/15 of the median leaf (1.5)
    numbers = check.training_numbers(prog, _side())
    assert numbers["grad1_gap"]["at"] == "c" and numbers["grad1_gap"]["value"] == pytest.approx(0.1)


def test_a_leaf_with_no_gradient_is_left_out_of_the_change():
    from benchmarks import check

    prog = _side()
    prog["change_norms"]["dead"] = 1.0
    assert check.training_numbers(prog, _side())["change_gap"]["value"] == 0
    prog["change_norms"]["b"] = 0.0  # a leaf the program left unmoved reads 1
    assert check.training_numbers(prog, _side())["change_gap"]["value"] == pytest.approx(1.0)


def test_under_a_randomised_codec_the_change_is_held_whole_and_by_the_median_leaf():
    from benchmarks import check

    ref = {**_side(), "change_stat": "total", "losses_followed": 1, "msg_bytes": 100}
    prog = {**_side(losses=(2.0, 5.0, 9.0)), "msg_bytes": 100}  # later losses carry the draws
    prog["change_norms"]["c"] = 0.02  # one leaf off by half: the worst swings, the median stays
    numbers = check.training_numbers(prog, ref)
    assert numbers["loss_gap"]["value"] == 0 and numbers["msg_bytes_gap"]["value"] == 0
    assert numbers["median_leaf_change_gap"]["value"] == 0
    whole = math.sqrt(1 + 4 + 16) * 0.01
    assert numbers["change_gap"]["value"] == pytest.approx((whole - math.sqrt(1 + 4 + 4) * 0.01) / whole)
    prog["change_norms"] = {k: 2 * v for k, v in ref["change_norms"].items()}  # every leaf doubled
    numbers = check.training_numbers(prog, ref)
    assert numbers["median_leaf_change_gap"]["value"] == pytest.approx(1.0)
    assert numbers["change_gap"]["value"] == pytest.approx(1.0)


@pytest.mark.parametrize("reference,code,ok", [
    ("resnet18_cifar10", "sgd", True), ("resnet18_cifar10", "svd", True),
    ("resnet18_cifar10", "qsgd", False), ("gpt2_medium", "svd", False),
])
def test_a_reference_follows_the_cells_code_or_refuses_it(reference, code, ok):
    data = _data()
    module = data.module("reference", reference)
    config = next(c for c in BENCH["configs"]
                  if data.config(c["name"])["reference"] == reference)["name"]
    from benchmarks.run import tiny

    cfg, _ = tiny(data.config(config), {"flags": {}})
    batches = module.example_batches(cfg, seed=4, calls=1, rows=4)
    if not ok:
        with pytest.raises(ValueError):
            module.train_steps(module.init_params(cfg, 4), batches, cfg, flags={"--code": code})
        return
    got = module.train_steps(module.init_params(cfg, 4), batches, cfg, flags={"--code": code})
    coded = code == "svd"
    assert got.get("change_stat", "worst_leaf") == ("total" if coded else "worst_leaf")
    assert got["losses_followed"] == (1 if coded else len(got["losses"]))
    assert (got["msg_bytes"] is not None) == coded


def test_step_idle_is_the_part_of_a_step_outside_the_slice_the_device_does_not_fill(recorded):
    stamps = _steady(n=41, dt=0.2, per_line=8)  # 25 ms a step outside the slice
    ctx = _ctx(stamps, cut=(10, 20), trace=recorded)
    busy = _reduce("device_trace", ctx, what="busy")
    assert _reduce("step_idle_pct", ctx) == pytest.approx(100 * (1 - busy / 25.0))
    assert _reduce("step_idle_pct", _ctx(stamps, trace=None)) is None


@pytest.mark.parametrize("bad", [math.nan, math.inf])
def test_a_loss_that_is_not_finite_is_not_correct(bad):
    from benchmarks import check

    numbers = check.training_numbers(_side(losses=(2.0, bad, 1.8)), _side())
    assert not check.judge(numbers, {"loss_gap": 0.5, "grad1_gap": 0.5, "change_gap": 0.5})[0]


def test_a_number_without_a_limit_is_an_error_not_a_pass():
    from benchmarks import check

    with pytest.raises(KeyError):
        check.judge(check.training_numbers(_side(), _side()), {"loss_gap": 0.1})


# ---- intervals and the device trace ----------------------------------------------

FIXTURE = ROOT / "tests/benchmark/fixtures/tpu_v5e_tiny_trace.json"


@pytest.fixture(scope="module")
def recorded():
    return json.loads(FIXTURE.read_text())


def test_union_and_gaps_on_intervals_counted_by_hand():
    from benchmarks import trace as T

    ivs = [(0, 10), (5, 12), (20, 30), (30, 31), (50, 55)]
    assert T.union_len(ivs) == 12 + 11 + 5
    assert T.union_len([]) == 0.0
    assert T.merged(ivs) == [[0, 12], [20, 31], [50, 55]]
    assert T.gaps(ivs, 0, 60) == [(12, 20), (31, 50), (55, 60)]
    assert T.gaps(ivs, 6, 25) == [(12, 20)]


def test_recorded_trace_holds_five_executions_of_the_step_program(recorded):
    from benchmarks import trace as T

    device = T.fullest_device(recorded)
    assert T.step_module(device).startswith("jit_spmd_step(")
    runs = T.step_runs(device)
    assert len(runs) == 5 and all(25_000 < e - s < 26_000 for s, e in runs)


@pytest.mark.parametrize("what,expected", [
    ("busy", 88.203e-3 / 4),  # 88.203 us of operations over four executions, in ms
    ("gap", (4415.811 + 4500.672 + 4231.371 + 4133.109) / 4 / 1e3),
    ("idle", 100 * (1 - 88.203 / 17383.851)),
])
def test_device_trace_reducer_on_the_recorded_trace(recorded, what, expected):
    ctx = _ctx(_steady(), trace=recorded)
    assert _reduce("device_trace", ctx, what=what) == pytest.approx(expected, rel=1e-6)


def test_a_superstep_execution_is_divided_among_its_steps(recorded):
    ctx = _ctx(_steady(per_line=8), trace=recorded)
    assert _reduce("device_trace", ctx, what="busy") == pytest.approx(88.203e-3 / 4 / 8, rel=1e-6)
    assert _reduce("device_trace", ctx, what="gap") == pytest.approx(4.32024075, rel=1e-6)


def test_trace_reducers_return_nothing_without_a_trace_or_with_one_execution(recorded):
    assert _reduce("device_trace", _ctx(_steady(), trace=None), what="busy") is None
    name, device = next(iter(recorded["devices"].items()))
    first = min(s for n, s, _ in device["modules"] if n.startswith("jit_spmd_step"))
    one = {"devices": {name: {"ops": device["ops"],
                              "modules": [m for m in device["modules"] if m[1] <= first]}}, "host": []}
    assert _reduce("device_trace", _ctx(_steady(), trace=one), what="idle") is None


def test_busy_window_and_breakdown_of_the_recorded_trace(recorded):
    from benchmarks import breakdown as B

    busy_s, window_s = B.busy_and_window(recorded)
    assert busy_s == pytest.approx(88.203e-6) and window_s == pytest.approx(17383.851e-6)
    got = B.breakdown(recorded)
    assert len(got["device_ops"]) == 10 and len(got["idle_gaps"]) <= 10
    assert got["device_ops"][0][0].startswith("copy-done:f32[32]")
    # the device waits while the host fetches the loss: the lm loop syncs every step
    assert got["idle_gaps"][0][0].startswith("_array.py") and got["idle_gaps"][0][1] > 0.009
    assert sum(v for _, v in got["idle_gaps"]) == pytest.approx(window_s - busy_s, rel=1e-3)


def test_breakdown_label_is_the_instruction_and_the_largest_array_it_writes():
    from benchmarks import breakdown as B

    line = ("%fusion.3621 = (f32[4,16,1024]{2,1,0:T(8,128)S(1)}, f32[4,16,1024,1024]{2,3,1,0}) "
            "fusion(f32[4,16,1024,1024]{2,3,1,0:T(8,128)} %get-tuple-element.4330), kind=kOutput")
    assert B._label(line) == "fusion:f32[4,16,1024,1024]"
    assert B._label("%copy-done = u32[2]{0:T(128)S(1)} copy-done((u32[2]{0}, u32[2]{0}) %x)") == "copy-done:u32[2]"


def test_device_peak_adds_the_programs_reservation_to_what_the_window_held():
    from benchmarks import run

    stats = {"peak_bytes_in_use": 4898011136, "peak_bytes_reserved": 12079955968}
    assert run.device_peak(stats, 3248281088) == 3248281088 + 12079955968
    assert run.device_peak(stats, 0) == 12079955968
    assert run.device_peak({"peak_bytes_in_use": 5, "peak_bytes_reserved": 1}, 2) == 5
    assert run.device_peak(None, 0) is None and run.device_peak({}, 0) is None
