"""Attribute the SVD encode tax, phase by phase (VERDICT r4 next-round #2).

An unverified record from before this round had ResNet-18 / CIFAR-10 / svd
rank 3 at +2.5 ms over dense on a v5e chip. The ledger since holds the
codec at 0.8 ms of a step at batch 1250 (`resnet18-1chip-svd3` 61.693 ms
against `resnet18-1chip-dense` 60.902; ledger, PR 30). This script times
on whatever backend jax resolves (the host platform unless run on the
chip; no on-chip run of it is on record, ROADMAP S9) and produces the
breakdown that decides what (if anything) is left to optimize:

  encode_full       encode_tree on the real ResNet-18 gradient pytree (the
                    production path: bucketed vmap, auto algorithm)
  encode_<algo>     the same with the decomposition forced to gram /
                    randomized (and optionally exact, the known-slow oracle)
  resize_only       reshape-to-near-square cost alone (memory movement)
  decode_mean_8     fused decode-mean of 8 gathered payloads (the decode
                    half of the gather exchange at the canonical 8 ways)
  bucket table      per-shape-bucket encode cost (count x shape -> ms), the
                    data a further batching optimization would need

Timing discipline: each phase runs STEPS times under one lax.scan
dispatch with every payload leaf kept live, fenced by a device->host
scalar fetch, best-of-3 (per-call host dispatch would otherwise swamp
millisecond-scale phases).

Writes <out>/ENCODE_PROFILE.json + .md. Reference hot spot being
attributed: the per-layer numpy SVD at src/codings/svd.py:95.

Usage: python scripts/encode_profile.py [--out artifacts]
       [--steps 30] [--network resnet18] [--include-exact]
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", type=str, default="artifacts")
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--network", type=str, default="resnet18")
    ap.add_argument("--batch", type=int, default=128)
    ap.add_argument("--rank", type=int, default=3)
    ap.add_argument("--include-exact", action="store_true", default=False,
                    help="also time algorithm='exact' (QDWH — ~120 ms/step "
                         "on v5e, round-2 measurement; off by default so "
                         "the profile itself stays fast)")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from atomo_tpu.codecs import SvdCodec, encode_tree
    from atomo_tpu.codecs.svd import resize_to_2d
    from atomo_tpu.models import get_model
    from atomo_tpu.training import create_state, make_optimizer

    dev = jax.devices()[0]
    steps = args.steps

    # real gradient pytree, per the canonical recipe
    model = get_model(args.network, 10)
    opt = make_optimizer("sgd", lr=0.01, momentum=0.9)
    rng = jax.random.PRNGKey(0)
    images = jax.random.uniform(rng, (args.batch, 32, 32, 3), jnp.float32)
    labels = jax.random.randint(rng, (args.batch,), 0, 10)
    state = create_state(model, opt, rng, images)

    def _loss(p):
        variables = {"params": p}
        if jax.tree_util.tree_leaves(state.batch_stats):
            variables["batch_stats"] = state.batch_stats
        out = model.apply(variables, images, train=False)
        return jnp.mean((out - jax.nn.one_hot(labels, out.shape[-1])) ** 2)

    grads = jax.jit(jax.grad(_loss))(state.params)
    key = jax.random.PRNGKey(1)

    def _consume(tree):
        """Keep EVERY leaf live (uint leaves would otherwise be DCE'd)."""
        tot = jnp.float32(0)
        for l in jax.tree_util.tree_leaves(tree):
            if jnp.issubdtype(l.dtype, jnp.floating):
                tot = tot + jnp.vdot(l, l) * 1e-20
            else:
                tot = tot + jnp.sum(l.astype(jnp.float32)) * 1e-30
        return tot

    def timed(fn, *fn_args) -> float:
        """ms per call: scan-fenced best-of-3."""

        @jax.jit
        def many(k, a):
            def body(acc, i):
                out = fn(jax.random.fold_in(k, i), a, acc)
                return _consume(out), None

            acc, _ = jax.lax.scan(body, jnp.float32(0), jnp.arange(steps))
            return acc

        sync = float(many(key, fn_args))  # compile + warm
        if not math.isfinite(sync):
            raise RuntimeError(f"sync scalar not finite: {sync}")
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            sync = float(many(key, fn_args))
            best = min(best, (time.perf_counter() - t0) / steps)
            if not math.isfinite(sync):
                raise RuntimeError(f"sync scalar not finite: {sync}")
        return best * 1e3

    results: dict = {}

    def jitter(tree, acc):
        # serialize scan iterations without changing magnitudes
        return jax.tree_util.tree_map(lambda a: a + acc * 1e-30, tree)

    # phase: resize only
    def resize_phase(k, a, acc):
        (g,) = a
        return [resize_to_2d(leaf)[0] for leaf in jax.tree_util.tree_leaves(jitter(g, acc))]

    results["resize_only_ms"] = timed(resize_phase, grads)

    # phase: full encode per algorithm
    algos = ["auto", "gram", "randomized"] + (
        ["exact"] if args.include_exact else []
    )
    for algo in algos:
        codec = SvdCodec(rank=args.rank, algorithm=algo)

        def enc_phase(k, a, acc, c=codec):
            (g,) = a
            payload, _ = encode_tree(c, k, jitter(g, acc))
            return payload

        tag = "encode_full_ms" if algo == "auto" else f"encode_{algo}_ms"
        try:
            results[tag] = timed(enc_phase, grads)
        except Exception as exc:  # noqa: BLE001 - report, don't die
            results[tag] = None
            results[tag + "_error"] = str(exc)[:200]

    # phase: fused decode-mean of 8 gathered payloads
    from atomo_tpu.codecs import decode_mean_tree

    codec = SvdCodec(rank=args.rank)
    payloads = jax.jit(lambda k, g: encode_tree(codec, k, g)[0])(key, grads)
    gathered = jax.tree_util.tree_map(
        lambda a: jnp.stack([a] * 8), payloads
    )

    def dec_phase(k, a, acc):
        (gath, g) = a
        gath = jitter(gath, acc)
        return decode_mean_tree(codec, gath, g, 8)

    results["decode_mean_8_ms"] = timed(dec_phase, gathered, grads)

    # per-bucket encode table: where inside encode_full the time goes
    leaves = jax.tree_util.tree_leaves(grads)
    buckets: dict = {}
    for leaf in leaves:
        buckets.setdefault((tuple(leaf.shape), str(leaf.dtype)), []).append(leaf)
    table = []
    for (shape, dtype), group in sorted(
        buckets.items(), key=lambda kv: -kv[1][0].size * len(kv[1])
    ):
        stacked = jnp.stack(group)
        n = len(group)

        def bucket_phase(k, a, acc, n=n):
            (st,) = a
            keys = jax.vmap(lambda i: jax.random.fold_in(k, i))(jnp.arange(n))
            return jax.vmap(codec.encode)(keys, jitter(st, acc))

        try:
            ms = timed(bucket_phase, stacked)
        except Exception as exc:  # noqa: BLE001
            ms = None
        table.append(
            dict(shape=list(shape), count=n, dtype=dtype,
                 ms_per_step=None if ms is None else round(ms, 4))
        )
    results["buckets"] = table

    results.update(
        platform=dev.platform, device=dev.device_kind, steps=steps,
        network=args.network, rank=args.rank,
        codec_defaults=repr(codec), timing="scan-fenced best-of-3",
    )
    for k in list(results):
        if isinstance(results[k], float):
            results[k] = round(results[k], 4)

    os.makedirs(args.out, exist_ok=True)
    with open(os.path.join(args.out, "ENCODE_PROFILE.json"), "w") as f:
        json.dump(results, f, indent=1)
    lines = [
        "# SVD encode-tax breakdown",
        "",
        f"{args.network} rank-{args.rank} gradients on {dev.device_kind} "
        f"({dev.platform}); {steps}-step scan-fenced best-of-3. "
        "Reference hot spot: per-layer numpy SVD, "
        "src/codings/svd.py:95.",
        "",
        "| phase | ms/step |",
        "|---|---|",
    ]
    for tag in (
        "resize_only_ms", "encode_full_ms", "encode_gram_ms",
        "encode_randomized_ms", "encode_exact_ms", "decode_mean_8_ms",
    ):
        if tag in results:
            lines.append(f"| {tag} | {results[tag]} |")
    lines += ["", "## Per-bucket encode cost", "",
              "| shape | count | ms/step |", "|---|---|---|"]
    for row in table:
        lines.append(
            f"| {tuple(row['shape'])} | {row['count']} | {row['ms_per_step']} |"
        )
    with open(os.path.join(args.out, "ENCODE_PROFILE.md"), "w") as f:
        f.write("\n".join(lines) + "\n")
    print(json.dumps({k: v for k, v in results.items() if k != "buckets"}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
