"""Produce the ResNet-18 convergence-parity artifact (VERDICT r2 #6, r3 #6).

Round-3's version saturated: easy synthetic data drove both curves to a
~zero loss floor where ratio ≈ 1 is unfalsifiable — a biased codec could
pass. Round-4 hardening (VERDICT r3 next-round #6):

  * **label noise** (default 20%) keeps the loss floor well above zero and
    the accuracy ceiling well below 100%, so codec-induced degradation has
    somewhere to show;
  * **accuracy-vs-step curves** are recorded alongside loss, with a stated
    accuracy target standing in for BASELINE.md's unmeasurable 93%
    (no CIFAR-10 in this env): dense prec@1 must reach ``--acc-target``
    PERCENT (default 60 — accuracy metrics are on the 0-100 scale — at 500
    steps under 20% noise) and svd must land within ``--acc-gap``
    (default 5) percentage points of dense;
  * a **broken-codec ablation** runs the same gate: the pure-sketch
    no-residual-probes codec (its estimator discards the spectral tail —
    biased, the exact failure class the probes exist to fix) must FAIL
    the gate the production codec passes. A gate both pass would prove
    nothing; ``gate_discriminates`` in the JSON records this.

Runs the reference's canonical recipe (src/run_pytorch.sh:1-20: ResNet-18 /
CIFAR-10, batch 128, lr 0.01, momentum 0, svd-rank 3) three ways — dense,
default SVD codec, no-probes ablation — on whatever accelerator jax
resolves (the TPU where one is attached; JAX_PLATFORMS=cpu reproduces on CPU).

Data: real CIFAR-10 from ./data when present; otherwise the deterministic
synthetic fallback (documented in the artifact's "dataset" field) — class
structure is synthetic, but the gradient spectra exercising the codec are
real ResNet-18 gradients either way, and the label noise applies to both.

Usage: python scripts/convergence_artifact.py [--steps 500] [--out artifacts]
       [--network resnet18] [--label-noise 0.2] [--acc-target 60]
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=500)
    ap.add_argument("--tail", type=int, default=50, help="final-window size")
    ap.add_argument("--out", type=str, default="artifacts")
    ap.add_argument("--network", type=str, default="resnet18")
    ap.add_argument("--label-noise", type=float, default=0.2,
                    help="fraction of train labels randomized (keeps the "
                         "loss floor off zero so the gate can discriminate)")
    ap.add_argument("--acc-target", type=float, default=60.0,
                    help="dense prec@1 (percent) the recipe must reach (the "
                         "stand-in for BASELINE.md's 93%% — no real CIFAR-10 "
                         "here)")
    ap.add_argument("--acc-gap", type=float, default=5.0,
                    help="max dense-svd prec@1 gap (percentage points)")
    ap.add_argument("--ratio-tol", type=float, default=1.25,
                    help="max svd/dense final-loss ratio to pass")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from atomo_tpu.codecs import SvdCodec
    from atomo_tpu.data import SPECS, BatchIterator, synthetic_dataset
    from atomo_tpu.models import get_model
    from atomo_tpu.training import create_state, make_optimizer, make_train_step

    dataset = "cifar10"
    try:
        from atomo_tpu.data import load_dataset

        ds = load_dataset("cifar10", "./data", train=True, synthetic_fallback=False)
        dataset_kind = "real"
    except Exception:
        ds = synthetic_dataset(SPECS["cifar10"], True, size=2048)
        dataset_kind = "synthetic-fallback"

    if args.label_noise > 0:
        # deterministic symmetric label noise: the same corrupted label set
        # for every run, so the comparison stays paired
        rng_np = np.random.RandomState(7)
        labels = ds.labels.copy()
        flip = rng_np.rand(labels.shape[0]) < args.label_noise
        labels[flip] = rng_np.randint(
            0, ds.spec.num_classes, size=int(flip.sum())
        ).astype(labels.dtype)
        ds = dataclasses.replace(ds, labels=labels)

    model = get_model(args.network, 10)
    dev = jax.devices()[0]

    def run(codec):
        opt = make_optimizer("sgd", lr=0.01, momentum=0.0)
        it = BatchIterator(ds, 128, seed=0)
        images, _ = next(iter(it.epoch()))
        state = create_state(model, opt, jax.random.PRNGKey(0), jnp.asarray(images))
        step = make_train_step(model, opt, codec=codec)
        key = jax.random.PRNGKey(1)
        stream = it.forever()
        losses, accs = [], []
        t0 = time.perf_counter()
        for _ in range(args.steps):
            im, lb = next(stream)
            state, m = step(state, key, jnp.asarray(im), jnp.asarray(lb))
            losses.append(float(m["loss"]))  # device->host sync every step
            accs.append(float(m["prec1"]))
        return losses, accs, time.perf_counter() - t0, int(m["msg_bytes"])

    codec = SvdCodec(rank=3)
    broken = SvdCodec(rank=3, residual_probes=0)  # pure sketch: biased
    runs = {}
    for tag, c in (("dense", None), ("svd3", codec), ("svd3_noprobes", broken)):
        print(f"running {tag} ...", flush=True)
        losses, accs, wall, msg = run(c)
        runs[tag] = dict(losses=losses, accs=accs, wall_s=round(wall, 1),
                         msg_bytes=msg)

    tail = args.tail

    def final(tag, key):
        return float(np.mean(runs[tag][key][-tail:]))

    def gate(tag):
        """The pass/fail contract, applied identically to the production
        codec and the ablation."""
        ratio = final(tag, "losses") / max(final("dense", "losses"), 1e-8)
        gap = final("dense", "accs") - final(tag, "accs")
        return {
            "final_loss": final(tag, "losses"),
            "final_prec1": final(tag, "accs"),
            "loss_ratio_vs_dense": round(ratio, 4),
            "prec1_gap_vs_dense": round(gap, 4),
            "ratio_ok": bool(ratio < args.ratio_tol),
            "acc_ok": bool(gap <= args.acc_gap),
            "passed": bool(ratio < args.ratio_tol and gap <= args.acc_gap),
        }

    dense_reached_target = bool(final("dense", "accs") >= args.acc_target)
    g_svd = gate("svd3")
    g_broken = gate("svd3_noprobes")
    # the gate only carries evidence if the production codec passes it AND
    # the deliberately-biased ablation fails it
    discriminates = bool(g_svd["passed"] and not g_broken["passed"])
    passed = bool(dense_reached_target and g_svd["passed"])

    os.makedirs(args.out, exist_ok=True)
    record = {
        "recipe": f"{args.network}/cifar10 batch=128 lr=0.01 momentum=0 "
                  f"svd_rank=3 label_noise={args.label_noise}",
        "reference": "src/run_pytorch.sh:1-20; oracle methodology src/nn_ops.py:123-169",
        "dataset": dataset,
        "dataset_kind": dataset_kind,
        "platform": dev.platform,
        "device": dev.device_kind,
        "steps": args.steps,
        "codec": {
            "name": "svd", "rank": codec.rank, "sample": codec.sample,
            "algorithm": codec.algorithm,
            "residual_probes": codec.residual_probes,
            "power_iters": codec.power_iters,
            "wire_dtype": codec.wire_dtype,
        },
        "acc_target_dense": args.acc_target,
        "acc_gap_tol": args.acc_gap,
        "ratio_tol": args.ratio_tol,
        "dense": {"final_loss": final("dense", "losses"),
                  "final_prec1": final("dense", "accs"),
                  "reached_acc_target": dense_reached_target},
        "svd3": g_svd,
        "svd3_noprobes_ablation": g_broken,
        "gate_discriminates": discriminates,
        "assertion_passed": passed,
        "wall_s": {t: runs[t]["wall_s"] for t in runs},
        "msg_bytes_per_step": runs["svd3"]["msg_bytes"],
        "curves": {
            t: {"losses": [round(x, 5) for x in runs[t]["losses"]],
                "prec1": [round(x, 5) for x in runs[t]["accs"]]}
            for t in runs
        },
    }
    jpath = os.path.join(args.out, "CONVERGENCE.json")
    with open(jpath, "w") as f:
        json.dump(record, f, indent=1)

    def sparkline(xs, buckets=40, log=True):
        # log10 scale for losses (exponential decay); linear for accuracy
        blocks = " .:-=+*#%@"
        chunk = max(1, len(xs) // buckets)
        means = []
        for i in range(0, len(xs), chunk):
            v = float(np.mean(xs[i : i + chunk]))
            means.append(float(np.log10(max(v, 1e-8))) if log else v)
        lo, hi = min(means), max(means)
        span = max(hi - lo, 1e-9)
        return "".join(blocks[int((x - lo) / span * (len(blocks) - 1))] for x in means)

    with open(os.path.join(args.out, "CONVERGENCE.md"), "w") as f:
        rows = "\n".join(
            "| {} | {:.4f} | {:.4f} | {} |".format(
                t, final(t, "losses"), final(t, "accs"), runs[t]["wall_s"]
            )
            for t in runs
        )
        f.write(
            f"""# {args.network} convergence parity — hardened gate ({dataset_kind} {dataset}, {dev.device_kind})

Canonical recipe (reference `src/run_pytorch.sh:1-20`) + **{args.label_noise:.0%}
label noise** so neither loss nor accuracy saturates (VERDICT r3 weak #5:
the round-3 artifact's zero-floor ratio was nearly unfalsifiable). Gate:
dense prec@1 >= {args.acc_target} (the stand-in for BASELINE's 93% — no real
CIFAR-10 in this env), svd within {args.acc_gap:.0f} points and loss ratio
< {args.ratio_tol}. The **no-probes ablation** (pure sketch, biased — it
discards the spectral tail) must FAIL the same gate.

| run | final loss (last {tail}) | final prec@1 | wall s ({args.steps} steps) |
|---|---|---|---|
{rows}

* svd3 gate: ratio {g_svd['loss_ratio_vs_dense']}, acc gap {g_svd['prec1_gap_vs_dense']:.3f}
  -> **{"PASSED" if g_svd['passed'] else "FAILED"}**
* no-probes ablation: ratio {g_broken['loss_ratio_vs_dense']}, acc gap {g_broken['prec1_gap_vs_dense']:.3f}
  -> **{"PASSED (gate too weak!)" if g_broken['passed'] else "FAILED (as it must)"}**
* gate discriminates: **{discriminates}** · overall: **{"PASSED" if passed else "FAILED"}**

Loss curves (log scale, high→low):

    dense    {sparkline(runs['dense']['losses'])}
    svd3     {sparkline(runs['svd3']['losses'])}
    noprobes {sparkline(runs['svd3_noprobes']['losses'])}

prec@1 curves (linear, low→high):

    dense    {sparkline(runs['dense']['accs'], log=False)}
    svd3     {sparkline(runs['svd3']['accs'], log=False)}
    noprobes {sparkline(runs['svd3_noprobes']['accs'], log=False)}

Full curves in `CONVERGENCE.json`.
"""
        )
    print(json.dumps({k: v for k, v in record.items() if k != "curves"}, indent=1))
    return 0 if passed else 1


if __name__ == "__main__":
    raise SystemExit(main())
