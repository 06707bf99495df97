"""--aggregate auto: the measured comm-cost model picks the exchange mode
per deployment and always says why (VERDICT r4 next-round #3). The
reference never had this choice — one PS, one 10 GbE fabric
(src/distributed_worker.py:330-335); this framework has three exchange
modes and the crossover physics to pick between them
(artifacts/COMM_CROSSOVER.md)."""

import re

import pytest

from atomo_tpu.cli import main
from atomo_tpu.utils.comm_model import (
    FABRICS,
    choose_aggregate,
    estimate_codec_tax_s,
)

# the config-2 regime (an unverified anchor from before this round):
# ResNet-18 dense gradient 44.7 MB, svd3 byte reduction 71.8x, codec tax
# ~2.5 ms
R18 = dict(dense_bytes=44.7e6, payload_bytes=44.7e6 / 71.8)


def test_no_codec_is_psum():
    mode, why = choose_aggregate(
        has_codec=False, dense_bytes=0, payload_bytes=0, ways=8,
        fabric_bw=FABRICS["ici"],
    )
    assert mode == "psum" and "no compressing codec" in why


def test_single_device_is_psum():
    mode, why = choose_aggregate(
        has_codec=True, ways=1, fabric_bw=FABRICS["ici"], **R18
    )
    assert mode == "psum" and "single device" in why


def test_cross_host_is_hierarchical():
    mode, why = choose_aggregate(
        has_codec=True, ways=16, fabric_bw=FABRICS["dcn"], cross_host=True,
        **R18,
    )
    assert mode == "hierarchical" and "crosses hosts" in why


def test_wire_bytes_decide_with_a_codec_and_ici_carries_the_advisory():
    """With a codec BOTH modes pay the encode->decode round trip, so the
    tax cancels and wire bytes decide: gather at 8 ways on any fabric. The
    fabric decides the ADVISORY: on 45 GB/s ICI the ~1.6 ms wire saving is
    below the ~2.5 ms codec tax (the measured single-chip truth — the
    printed line must say compression is costing wall-clock); on the
    reference's 10 GbE regime the ~59 ms saving dwarfs it (no note)."""
    kw = dict(has_codec=True, ways=8, **R18)
    mode_ici, why_ici = choose_aggregate(fabric_bw=FABRICS["ici"], **kw)
    mode_eth, why_eth = choose_aggregate(fabric_bw=FABRICS["eth10g"], **kw)
    assert mode_ici == "gather" and "NOTE" in why_ici
    assert "--code sgd" in why_ici  # the advisory names the faster config
    assert mode_eth == "gather" and "NOTE" not in why_eth


def test_buffer_outgrowing_dense_picks_ring():
    """PR-3: within the compression-wins region, once the gathered buffer
    N*P would exceed the dense gradient D (N >= byte reduction, here
    ~71.8), auto upgrades gather to the ring stream — same payloads, no
    O(N) buffer, decode overlapped — and says so with the byte numbers."""
    mode, why = choose_aggregate(
        has_codec=True, ways=100, fabric_bw=FABRICS["ici"], **R18
    )
    assert mode == "ring"
    assert "ppermute" in why and "buffer" in why
    # below the reduction the buffer is small: plain gather, unchanged
    mode, _ = choose_aggregate(
        has_codec=True, ways=64, fabric_bw=FABRICS["ici"], **R18
    )
    assert mode == "gather"
    # callers without the ring step (lm layouts) opt out
    mode, why = choose_aggregate(
        has_codec=True, ways=100, fabric_bw=FABRICS["ici"], allow_ring=False,
        **R18,
    )
    assert mode == "gather"


def test_past_twice_reduction_ways_is_psum():
    """Compression stops paying at N >= 2x byte reduction (gather traffic
    P*(N-1) crosses the saturating dense all-reduce 2D(N-1)/N): at 200
    ways on a 71.8x codec, dense psum wins regardless of fabric."""
    mode, why = choose_aggregate(
        has_codec=True, ways=200, fabric_bw=FABRICS["eth10g"], **R18
    )
    assert mode == "psum" and "2x reduction" in why


def test_explicit_tax_drives_the_advisory():
    """--codec-tax-ms is live: a near-zero measured tax removes the ICI
    advisory; a huge one adds it even on Ethernet. The MODE never flips on
    tax (both modes pay it — wire bytes decide)."""
    kw = dict(has_codec=True, ways=8, **R18)
    mode, why = choose_aggregate(fabric_bw=FABRICS["ici"], tax_s=1e-6, **kw)
    assert mode == "gather" and "NOTE" not in why
    mode, why = choose_aggregate(fabric_bw=FABRICS["eth10g"], tax_s=1.0, **kw)
    assert mode == "gather" and "NOTE" in why


def test_tax_estimate_scales_with_gradient_size():
    assert estimate_codec_tax_s(44.7e6) == pytest.approx(2.5e-3, rel=1e-6)
    assert estimate_codec_tax_s(44.7e6 / 10) == pytest.approx(2.5e-4, rel=1e-6)


@pytest.mark.slow
def test_train_cli_auto_selects_and_prints(tmp_path, capsys):
    """`train` defaults to --aggregate auto: with a codec the wire-bytes
    rule picks gather and, on the (single-host -> ici) default fabric, the
    printed justification carries the measured-truth advisory that the
    codec itself is costing wall-clock here. A forced --aggregate psum
    still runs and its worker line reports the honest DENSE wire bytes."""
    base = [
        "train", "--network", "LeNet", "--dataset", "MNIST", "--synthetic",
        "--train-dir", str(tmp_path), "--batch-size", "8",
        "--max-steps", "1", "--eval-freq", "0", "--log-interval", "1",
        "--n-devices", "4", "--code", "svd", "--svd-rank", "2",
        "--momentum", "0.0",
    ]
    assert main(base) == 0
    out = capsys.readouterr().out
    m = re.search(r"--aggregate auto -> (\w+) \((.*)\)", out)
    assert m, f"auto selection line missing from: {out!r}"
    assert m.group(1) == "gather"
    assert "NOTE" in m.group(2) and "--code sgd" in m.group(2)
    msg_gather = [float(x) for x in re.findall(r"Msg\(MB\):\s+([0-9.]+)", out)]

    assert main([*base, "--aggregate", "psum"]) == 0
    out = capsys.readouterr().out
    assert "--aggregate auto" not in out  # explicit mode: no resolver line
    msg_psum = [float(x) for x in re.findall(r"Msg\(MB\):\s+([0-9.]+)", out)]
    assert msg_psum and msg_gather
    # factors on the wire vs the psum mode's honest dense bytes
    assert msg_gather[-1] < 0.5 * msg_psum[-1]


@pytest.mark.slow
def test_lm_cli_auto_selects_and_prints(capsys):
    rc = main([
        "lm", "--layout", "dp", "--vocab-size", "16", "--seq-len", "8",
        "--width", "16", "--depth", "1", "--num-heads", "2",
        "--batch-size", "8", "--max-steps", "1", "--log-interval", "1",
        "--n-devices", "4", "--code", "svd", "--svd-rank", "2",
        "--fabric", "eth10g",
    ])
    assert rc == 0
    out = capsys.readouterr().out
    m = re.search(r"--aggregate auto -> (\w+)", out)
    assert m and m.group(1) == "gather"


def test_bad_fabric_is_a_clean_error(tmp_path):
    with pytest.raises(SystemExit, match="fabric"):
        main([
            "train", "--network", "LeNet", "--dataset", "MNIST",
            "--synthetic", "--train-dir", str(tmp_path),
            "--batch-size", "8", "--max-steps", "1", "--n-devices", "4",
            "--code", "svd", "--fabric", "warp-drive",
        ])


def test_psum_mode_reports_dense_wire_bytes():
    """Wire honesty regression: with a codec but psum aggregation the
    exchange moves DENSE gradients, and msg_bytes must say so (the codec's
    payload size is not this mode's message size)."""
    import jax
    import numpy as np

    from atomo_tpu.codecs import SvdCodec
    from atomo_tpu.models import get_model
    from atomo_tpu.parallel.mesh import make_mesh
    from atomo_tpu.parallel.replicated import (
        make_distributed_train_step,
        replicate_state,
        shard_batch,
    )
    from atomo_tpu.training import create_state, make_optimizer

    mesh = make_mesh(4)
    model = get_model("lenet", 10)
    opt = make_optimizer("sgd", lr=0.05)
    images = jax.random.normal(jax.random.PRNGKey(1), (8, 28, 28, 1))
    labels = jax.random.randint(jax.random.PRNGKey(2), (8,), 0, 10)
    state = replicate_state(mesh, create_state(model, opt, jax.random.PRNGKey(0), images))
    step = make_distributed_train_step(
        model, opt, mesh, SvdCodec(rank=2), aggregate="psum"
    )
    si, sl = shard_batch(mesh, images, labels)
    _, metrics = step(state, jax.random.PRNGKey(3), si, sl)
    assert float(metrics["msg_bytes"]) == float(metrics["dense_bytes"])
    assert np.isfinite(float(metrics["loss"]))


@pytest.mark.slow  # compiles a full LM step to observe a warning (~12 s on
# 1 core) — full-suite only
def test_lm_flooring_rank_warns(capsys):
    """VERDICT r4 weak #8: the measured flooring configuration (rank 3 at
    width 64, artifacts/LM_CONVERGENCE.md) can no longer run silently."""
    import warnings as _warnings

    with _warnings.catch_warnings(record=True) as w:
        _warnings.simplefilter("always")
        rc = main([
            "lm", "--layout", "dp", "--vocab-size", "16", "--seq-len", "8",
            "--width", "64", "--depth", "1", "--num-heads", "2",
            "--batch-size", "4", "--max-steps", "1", "--log-interval", "1",
            "--n-devices", "2", "--code", "svd", "--svd-rank", "3",
        ])
    assert rc == 0
    text = " ".join(str(x.message) for x in w)
    assert "floor" in text and "--svd-rank 3" in text


@pytest.mark.slow  # two LM-width compiles (~8 s on 1 core) — full-suite
# only
def test_lm_rank_auto_scales_with_width(capsys):
    """--svd-rank 0 (the default) resolves to the width-scaled rank and
    prints the policy line: width 64 -> the verified rank 6."""
    rc = main([
        "lm", "--layout", "dp", "--vocab-size", "16", "--seq-len", "8",
        "--width", "64", "--depth", "1", "--num-heads", "2",
        "--batch-size", "4", "--max-steps", "1", "--log-interval", "1",
        "--n-devices", "2", "--code", "svd",
    ])
    assert rc == 0
    out = capsys.readouterr().out
    assert "--svd-rank auto -> 6" in out


@pytest.mark.slow
def test_auto_spelling_trains_identically_to_explicit(tmp_path, capsys):
    """Seed-level reproducibility across spellings (code-review r5): the
    auto resolver must not consume training RNG, so `--aggregate auto`
    (resolving to gather) and `--aggregate gather` with the same seed
    produce the SAME step-1 loss on the same data order."""
    def run(mode):
        args = [
            "train", "--network", "LeNet", "--dataset", "MNIST",
            "--synthetic", "--train-dir", str(tmp_path / mode),
            "--batch-size", "8", "--max-steps", "1", "--eval-freq", "0",
            "--log-interval", "1", "--n-devices", "4", "--code", "svd",
            "--svd-rank", "2", "--momentum", "0.0", "--seed", "7",
            "--aggregate", mode,
        ]
        assert main(args) == 0
        out = capsys.readouterr().out
        m = re.search(r"Loss: ([0-9.]+)", out)
        assert m, out
        return m.group(1)

    assert run("auto") == run("gather")


def test_lm_gate_ablation_foil_resolution():
    """The LM gate's foil must stay discriminating (ADVICE r4 + code-review
    r5): no-probes converges toward the production codec as rank grows
    (measured: w128 rank-12 no-probes ratio 1.141, under the 1.15 bound),
    so 'auto' swaps to the floor-rank foil above the default rank, and the
    degenerate rank<=3 floor-rank combination is rejected outright."""
    import importlib.util
    import os

    spec = importlib.util.spec_from_file_location(
        "lm_gate_script",
        os.path.join(
            os.path.dirname(__file__), "..", "scripts",
            "lm_convergence_artifact.py",
        ),
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)

    assert mod.resolve_ablation("auto", 6, 6) == "noprobes"
    assert mod.resolve_ablation("auto", 12, 6) == "floor-rank"
    assert mod.resolve_ablation("noprobes", 12, 6) == "noprobes"
    with pytest.raises(ValueError, match="floor-rank"):
        mod.resolve_ablation("floor-rank", 3, 6)
    with pytest.raises(ValueError, match="floor-rank"):
        mod.resolve_ablation("floor-rank", 2, 6)
