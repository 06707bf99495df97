"""Measured comm-bound comparison: gather-of-factors vs dense-psum.

VERDICT r3 next-round #1b: on one TPU chip there is no inter-chip link, so
the byte win (57-72x) can never show up as time. Here the bytes genuinely
move: an 8-device mesh (XLA host platform, one buffer per virtual device)
exchanges a real ResNet-18 gradient pytree, and the dense all-reduce must
push ~8x44.7 MB through the host's memory system while the factor
all-gather pushes ~8x0.6 MB. Three jitted SPMD programs are timed
(scan-fenced, best-of-N):

  psum_dense    pmean of the dense gradient tree over 'dp'   (the --code
                sgd baseline wire path)
  encode_only   per-chip SVD encode of the tree, no exchange (isolates the
                codec tax this host pays)
  svd_full      encode -> all_gather(payloads) -> fused decode_mean (the
                complete ATOMO exchange, atomo_tpu.parallel.replicated
                gather mode)

plus the end-to-end distributed train step (fwd/bwd included) both ways.
The exchange-phase comparison is svd_full - encode_only vs psum_dense:
bytes-on-wire becoming time. Results land in artifacts/COMM_CROSSOVER.json
and feed the analytic crossover tables (atomo_tpu/utils/comm_model.py)
printed alongside.

Caveats (honest): this script times on the host platform. The host
'fabric' is one machine's memory system shared by all 8 virtual devices —
absolute times are not TPU ICI/DCN times, are not speed numbers, and no
on-chip measurement of the exchange is on record (ROADMAP S6, S9); the
compute side runs on ~1 core. What transfers to hardware is the
*byte-proportionality* of the exchange phase, which is the quantity the
analytic model parameterizes with real fabric bandwidths.

Usage: python scripts/comm_crossover.py [--reps 3] [--rounds 3]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("JAX_PLATFORMS", "cpu")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from atomo_tpu.codecs import (  # noqa: E402
    SvdCodec,
    decode_mean_tree,
    encode_tree,
    tree_nbytes,
)
from atomo_tpu.models import get_model  # noqa: E402
from atomo_tpu.parallel.mesh import make_mesh  # noqa: E402
from atomo_tpu.parallel.replicated import (  # noqa: E402
    make_distributed_train_step,
    replicate_state,
    shard_batch,
)
from atomo_tpu.training import create_state, make_optimizer  # noqa: E402
from atomo_tpu.utils.comm_model import crossover_report  # noqa: E402

ART = os.path.join(os.path.dirname(__file__), os.pardir, "artifacts")


def timed(fn, *args, reps: int, rounds: int) -> float:
    """Best-of-rounds seconds per rep; fn is jitted and already compiled
    by the caller (one warm call). Scalar fetch fences each round."""
    best = float("inf")
    for _ in range(rounds):
        t0 = time.perf_counter()
        out = fn(*args)
        float(out)  # device->host scalar: the fence
        best = min(best, (time.perf_counter() - t0) / reps)
    return best


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--rounds", type=int, default=3)
    ap.add_argument("--steps", type=int, default=2, help="full-step reps")
    args = ap.parse_args()

    mesh = make_mesh(8)
    n_dev = 8
    model = get_model("resnet18", 10)
    opt = make_optimizer("sgd", lr=0.01, momentum=0.9)
    rng = jax.random.PRNGKey(0)
    images = jax.random.uniform(rng, (32, 32, 32, 3), jnp.float32)
    state = create_state(model, opt, rng, images)
    grads = jax.tree_util.tree_map(
        lambda p: jax.random.normal(rng, p.shape, jnp.float32), state.params
    )
    codec = SvdCodec(rank=3)
    dense_bytes = tree_nbytes(grads)

    # payload bytes (static, trace-time accounting)
    _, stats = encode_tree(codec, rng, grads)
    payload_bytes = stats.payload_bytes

    reps = args.reps

    def scan_reps(body_one):
        """reps iterations under one dispatch, serialized via a scalar
        carry folded into the input so XLA cannot batch or elide them."""

        def prog(g):
            def body(acc, _):
                out = body_one(
                    jax.tree_util.tree_map(lambda a: a + acc * 1e-30, g)
                )
                return jnp.float32(out), None

            acc, _ = jax.lax.scan(body, jnp.float32(0), None, length=reps)
            return acc

        return prog

    my = lambda: jax.lax.axis_index("dp")  # noqa: E731

    def psum_dense_one(g):
        # per-chip distinct values (defeat replication shortcuts), then the
        # dense wire path: pmean of the full gradient tree
        g = jax.tree_util.tree_map(
            lambda a: a * (1.0 + 1e-6 * my()), g
        )
        mean = jax.lax.pmean(g, "dp")
        return sum(jnp.vdot(l, l) for l in jax.tree_util.tree_leaves(mean)) * 1e-20

    def encode_only_one(g):
        g = jax.tree_util.tree_map(lambda a: a * (1.0 + 1e-6 * my()), g)
        key = jax.random.fold_in(jax.random.PRNGKey(1), my())
        payloads, _ = encode_tree(codec, key, g)
        return (
            sum(
                jnp.vdot(l, l)
                for l in jax.tree_util.tree_leaves(payloads)
                if jnp.issubdtype(l.dtype, jnp.floating)
            )
            * 1e-20
        )

    def svd_full_one(g):
        g = jax.tree_util.tree_map(lambda a: a * (1.0 + 1e-6 * my()), g)
        key = jax.random.fold_in(jax.random.PRNGKey(1), my())
        payloads, _ = encode_tree(codec, key, g)
        gathered = jax.lax.all_gather(payloads, "dp")
        mean = decode_mean_tree(codec, gathered, g, n_dev)
        return sum(jnp.vdot(l, l) for l in jax.tree_util.tree_leaves(mean)) * 1e-20

    results = {}
    for tag, body in (
        ("psum_dense", psum_dense_one),
        ("encode_only", encode_only_one),
        ("svd_full", svd_full_one),
    ):
        prog = jax.jit(
            jax.shard_map(
                scan_reps(body), mesh=mesh, in_specs=(P(),), out_specs=P(),
                check_vma=False,
            )
        )
        float(prog(grads))  # compile + warm
        results[f"{tag}_ms"] = round(
            timed(prog, grads, reps=reps, rounds=args.rounds) * 1e3, 2
        )
        print(f"{tag}: {results[f'{tag}_ms']} ms", flush=True)

    exchange_svd = results["svd_full_ms"] - results["encode_only_ms"]
    if exchange_svd > 0:
        results["exchange_svd_ms"] = round(exchange_svd, 2)
        results["exchange_speedup"] = round(
            results["psum_dense_ms"] / exchange_svd, 2
        )
    else:
        # two independently-minimized noisy timings can invert; an
        # "exchange phase" below zero is a measurement artifact, not a
        # number — flag it rather than report a garbage speedup
        results["exchange_svd_ms"] = None
        results["exchange_speedup"] = None
        results["exchange_note"] = (
            f"svd_full best-of ({results['svd_full_ms']}) landed under "
            f"encode_only best-of ({results['encode_only_ms']}); timing "
            "noise — rerun with more --rounds/--reps"
        )

    # end-to-end step: fwd/bwd + exchange + update, both wire paths
    step_rows = {}
    for tag, cdc, agg in (
        ("dense_psum", None, "psum"),
        ("svd_gather", codec, "gather"),
    ):
        st = replicate_state(mesh, create_state(model, opt, rng, images))
        step = make_distributed_train_step(model, opt, mesh, cdc, aggregate=agg)
        si, sl = shard_batch(
            mesh, images, jax.random.randint(rng, (32,), 0, 10)
        )
        key = jax.random.PRNGKey(2)
        st, m = step(st, key, si, sl)
        float(m["loss"])  # compile + warm
        best = float("inf")
        for _ in range(args.rounds):
            t0 = time.perf_counter()
            for _ in range(args.steps):
                st, m = step(st, key, si, sl)
            float(m["loss"])
            best = min(best, (time.perf_counter() - t0) / args.steps)
        step_rows[f"step_{tag}_ms"] = round(best * 1e3, 2)
        print(f"step_{tag}: {step_rows[f'step_{tag}_ms']} ms", flush=True)
    results.update(step_rows)
    results["step_speedup"] = round(
        results["step_dense_psum_ms"] / results["step_svd_gather_ms"], 3
    )

    out = {
        "setup": {
            "mesh": "8-device host-platform 'dp' mesh (one buffer per "
            "virtual device; single machine)",
            "model": "resnet18 (11.17M params)",
            "dense_bytes": dense_bytes,
            "payload_bytes": payload_bytes,
            "byte_reduction": round(dense_bytes / payload_bytes, 2),
            "reps": reps,
            "rounds": args.rounds,
            "timing": "scan-fenced best-of-rounds",
        },
        "measured": results,
        # analytic model seeded with the config-2 anchors (dense 6.50 ms,
        # svd3 9.01 ms — unverified figures from before this round)
        "model_onchip_config2": crossover_report(
            dense_bytes, payload_bytes, 6.50e-3, 9.01e-3
        ),
    }
    os.makedirs(ART, exist_ok=True)
    path = os.path.join(ART, "COMM_CROSSOVER.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps({"wrote": os.path.abspath(path), **results}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
