"""Produce the LM convergence-parity artifact: compressed vs dense training
of the transformer LM on a dp mesh.

The CV artifact (scripts/convergence_artifact.py) proves the codec on
ResNet gradient spectra; this one proves it on TRANSFORMER gradients — the
matrices the tp/sp/pp/ep superset axes actually train. Three runs of the
dp-parallel LM step (parallel/lm.py with sp=1), identical data/seeds:
dense pmean, SVD rank-3 gather, and the deliberately-biased no-probes
ablation that must FAIL the gate (round-4 hardening, VERDICT r3 #6 —
plus token noise so the loss floor stays off zero and the gate can
discriminate). Writes artifacts/LM_CONVERGENCE.json + .md with the loss
curves, the final-window loss ratios, and the measured byte reduction.

Data: deterministic synthetic streams in the lm CLI's style (arithmetic
progressions with random starts/strides — learnable structure, reproducible
from this script's fixed seed; stride range differs from the CLI's).

Usage: python scripts/lm_convergence_artifact.py [--steps 300] [--out artifacts]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# The recipe is calibrated at a 4-way dp mesh (batch 32); on a 1-device CPU
# the batch silently shrinks to 8 and the gate numbers mean nothing. Force
# the virtual device count BEFORE jax import unconditionally — the flag
# only affects the HOST platform, so it is inert on a real TPU run — and
# hard-fail after backend init if fewer than 4 devices resolved anyway.
_fl = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _fl:
    os.environ["XLA_FLAGS"] = (
        _fl + " --xla_force_host_platform_device_count=4"
    ).strip()


# the measured flooring rank: rank 3 floors a width-64 LM at 1.39x dense CE
# (sweep 2026-07-30) and lands out-of-bound (1.178) at width 128 — the
# configuration the width-scaled policy exists to prevent, and therefore the
# foil for policy-rank gate runs
FLOOR_RANK = 3


def resolve_ablation(choice: str, rank: int, default_rank: int) -> str:
    """Pick the gate's foil. The no-probes sketch converges toward the
    production codec as rank grows (measured: w128 rank-12 no-probes ratio
    1.141, under the 1.15 bound), so above-default ranks foil against the
    measured flooring rank instead. Raises on the degenerate
    rank<=FLOOR_RANK floor-rank combination (the foil IS that rank)."""
    if choice == "auto":
        choice = "floor-rank" if rank > default_rank else "noprobes"
    if choice == "floor-rank" and rank <= FLOOR_RANK:
        raise ValueError(
            f"--ablation floor-rank needs --rank > {FLOOR_RANK}: the foil "
            f"IS rank {FLOOR_RANK}, so the gate could never discriminate"
        )
    return choice


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=800)
    ap.add_argument("--out", type=str, default="artifacts")
    ap.add_argument("--ratio-bound", type=float, default=1.15,
                    help="bound sized to DISCRIMINATE at this recipe "
                         "(sweep 2026-07-30, lr 0.05, 800 steps: production "
                         "rank-6 ratio 1.07, no-probes ablation 1.20 — a "
                         "1.25 bound would pass both)")
    ap.add_argument("--rank", type=int, default=6,
                    help="codec rank. NOT the CV default 3: on this "
                         "width-64 LM, rank 3 measurably FLOORS the loss "
                         "(1.39x dense CE at 800 steps, sweep 2026-07-30) "
                         "— atom-sampling variance scales with the "
                         "spectrum kept vs matrix width, so small models "
                         "need proportionally higher rank; rank 6 restores "
                         "parity at ~5x byte reduction")
    ap.add_argument("--width", type=int, default=64,
                    help="transformer width. Non-default widths validate "
                         "the width-scaled rank policy (cli lm --svd-rank "
                         "0: rank = ceil(width*6/64)) at a second measured "
                         "point; outputs are then suffixed _w{width}")
    ap.add_argument("--token-noise", type=float, default=0.1,
                    help="fraction of stream tokens randomized: keeps the "
                         "loss floor off zero so the gate can discriminate "
                         "(VERDICT r3 weak #5)")
    ap.add_argument("--ablation", choices=["auto", "noprobes", "floor-rank"],
                    default="auto",
                    help="which deliberately-broken codec must FAIL the "
                         "gate. 'noprobes' (pure sketch) biases hard at "
                         "low rank but converges toward the production "
                         "codec as rank grows (measured: w128 rank 12 "
                         "no-probes ratio 1.141 — under a 1.15 bound), so "
                         "'auto' selects 'floor-rank' — the rank-3 "
                         "configuration the width policy exists to prevent "
                         "(measured 1.39x floor at w64) — once rank "
                         "exceeds the default, and 'noprobes' otherwise")
    args = ap.parse_args()
    default_rank = ap.get_default("rank")
    try:
        args.ablation = resolve_ablation(args.ablation, args.rank, default_rank)
    except ValueError as e:
        ap.error(str(e))

    import jax
    import jax.numpy as jnp
    import numpy as np

    from atomo_tpu.codecs import SvdCodec
    from atomo_tpu.models.transformer import TransformerLM
    from atomo_tpu.parallel.lm import make_lm_train_step, shard_tokens
    from atomo_tpu.parallel.mesh import make_mesh
    from atomo_tpu.parallel.replicated import replicate_state
    from atomo_tpu.training import create_state, make_optimizer

    n_dev = min(4, len(jax.devices()))
    if n_dev < 4:
        raise SystemExit(
            f"only {n_dev} device(s) resolved; the gate's bound/rank are "
            "calibrated at the 4-way batch-32 recipe — running at a smaller "
            "batch would score against the wrong calibration (set "
            "XLA_FLAGS=--xla_force_host_platform_device_count=4 on CPU)"
        )
    cfg = dict(
        vocab_size=64, max_len=64, width=args.width, depth=2, num_heads=4
    )
    batch, seq = 8 * n_dev, 64
    mesh = make_mesh(n_dev, axes=(("dp", n_dev), ("sp", 1)))
    # lr 0.05: at lr 0.1+momentum this width-64 LM sits on the stability
    # edge and the codec's sampling noise tips it into late-training loss
    # creep (measured: rank-6 svd descends to 1.19 by step 400 then climbs
    # back to 1.49 by 800) — the gate would then measure noise-amplified
    # instability, not estimator parity. Dense converges fine either way.
    opt = make_optimizer("sgd", lr=0.05, momentum=0.9)

    rng = np.random.default_rng(0)

    def batch_tokens():
        starts = rng.integers(0, cfg["vocab_size"], size=(batch, 1))
        strides = rng.integers(1, 5, size=(batch, 1))
        toks = (starts + strides * np.arange(seq)) % cfg["vocab_size"]
        if args.token_noise > 0:
            # symmetric token noise: an irreducible CE floor, so parity is
            # judged mid-descent rather than at a saturated zero floor
            flip = rng.random(toks.shape) < args.token_noise
            toks = np.where(
                flip, rng.integers(0, cfg["vocab_size"], size=toks.shape), toks
            )
        return toks.astype(np.int32)

    batches = [batch_tokens() for _ in range(args.steps)]

    # deliberately-broken ablation: must FAIL the gate the production codec
    # passes, or the gate proves nothing (VERDICT r3 next-round #6)
    if args.ablation == "noprobes":
        ablation_codec = SvdCodec(rank=args.rank, residual_probes=0)
        ablation_label = f"rank-{args.rank} NO probes (pure sketch)"
    else:  # floor-rank: the configuration the width-scaled policy prevents
        ablation_codec = SvdCodec(rank=FLOOR_RANK)
        ablation_label = f"rank-{FLOOR_RANK} (measured flooring rank)"

    curves, bytes_info = {}, {}
    for tag, codec in (
        ("dense", None),
        ("svd", SvdCodec(rank=args.rank)),
        ("svd_ablation", ablation_codec),
    ):
        lm = TransformerLM(**cfg)
        state = create_state(
            lm, opt, jax.random.PRNGKey(0), jnp.zeros((1, seq), jnp.int32)
        )
        state = replicate_state(mesh, state)
        step = make_lm_train_step(cfg, opt, mesh, codec)
        losses = []
        t0 = time.time()
        for i, toks in enumerate(batches):
            state, m = step(
                state, jax.random.PRNGKey(1000 + i), shard_tokens(mesh, toks)
            )
            losses.append(float(m["loss"]))
        curves[tag] = losses
        bytes_info[tag] = dict(
            msg_bytes=float(m["msg_bytes"]), dense_bytes=float(m["dense_bytes"])
        )
        print(
            f"{tag}: final {losses[-1]:.4f} "
            f"({time.time() - t0:.1f}s, {len(losses)} steps)",
            flush=True,
        )

    w = max(args.steps // 10, 1)
    final_dense = float(np.mean(curves["dense"][-w:]))
    final_svd = float(np.mean(curves["svd"][-w:]))
    final_broken = float(np.mean(curves["svd_ablation"][-w:]))
    ratio = final_svd / max(final_dense, 1e-9)
    ratio_broken = final_broken / max(final_dense, 1e-9)
    reduction = bytes_info["svd"]["dense_bytes"] / max(
        bytes_info["svd"]["msg_bytes"], 1.0
    )
    # parity alone is not enough: both runs must have actually converged
    # (sibling artifact's guard — a broken step would give ratio ~1.0)
    converged = (
        final_dense < curves["dense"][0] * 0.5
        and final_svd < curves["svd"][0] * 0.5
    )
    discriminates = bool(
        ratio < args.ratio_bound and ratio_broken >= args.ratio_bound
    )
    # the verdict requires all three: parity, real convergence, AND a gate
    # that provably fails the biased ablation (ADVICE r4: a non-discriminating
    # gate must not report PASS)
    ok = ratio < args.ratio_bound and converged and discriminates

    os.makedirs(args.out, exist_ok=True)
    payload = dict(
        model="TransformerLM", config=cfg, batch=batch, seq_len=seq,
        n_devices=n_dev, steps=args.steps, optimizer="sgd lr=0.05 m=0.9",
        platform=jax.devices()[0].platform,
        device=jax.devices()[0].device_kind,
        final_window=w, final_loss_dense=final_dense,
        rank=args.rank, final_loss_svd=final_svd, ratio=ratio,
        ablation=args.ablation, ablation_label=ablation_label,
        final_loss_svd_ablation=final_broken, ratio_ablation=ratio_broken,
        gate_discriminates=discriminates, token_noise=args.token_noise,
        ratio_bound=args.ratio_bound, byte_reduction=reduction,
        bytes=bytes_info, converged=converged, passes=ok, curves=curves,
    )
    sfx = "" if args.width == 64 else f"_w{args.width}"
    if args.rank != default_rank:
        sfx += f"_r{args.rank}"
    if args.ablation != "noprobes":
        # distinct foils are distinct experiments; never overwrite one
        # ablation's artifact with another's
        sfx += "_floorabl"
    with open(os.path.join(args.out, f"LM_CONVERGENCE{sfx}.json"), "w") as f:
        json.dump(payload, f)
    with open(os.path.join(args.out, f"LM_CONVERGENCE{sfx}.md"), "w") as f:
        f.write(
            f"# LM convergence parity: SVD rank-{args.rank} vs dense\n\n"
            f"TransformerLM ({cfg['depth']}x{cfg['width']}, vocab "
            f"{cfg['vocab_size']}), batch {batch}, seq {seq}, {n_dev}-way dp "
            f"mesh on {payload['device']}; {args.steps} steps, synthetic "
            "arithmetic-progression streams (deterministic).\n\n"
            f"| run | final loss (last {w} mean) |\n|---|---|\n"
            f"| dense pmean | {final_dense:.4f} |\n"
            f"| svd rank-{args.rank} gather | {final_svd:.4f} |\n"
            f"| svd {ablation_label} (biased ablation) | {final_broken:.4f} |\n\n"
            f"ratio {ratio:.3f} (bound {args.ratio_bound}; ablation ratio "
            f"{ratio_broken:.3f} must be >= bound — gate discriminates: "
            f"{discriminates}), both runs "
            f"converged: {converged} — {'PASS' if ok else 'FAIL'}; byte "
            f"reduction {reduction:.1f}x per step per chip "
            f"(svd {bytes_info['svd']['msg_bytes']:.0f} B vs dense "
            f"{bytes_info['svd']['dense_bytes']:.0f} B).\n"
        )
    print(
        f"ratio={ratio:.3f} ablation_ratio={ratio_broken:.3f} "
        f"bound={args.ratio_bound} discriminates={discriminates} "
        f"byte_reduction={reduction:.1f}x -> {'PASS' if ok else 'FAIL'}"
    )
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
