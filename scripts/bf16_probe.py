"""bf16-vs-f32 localization probe (VERDICT r3 weak #2: an unverified
record from before this round had the --bf16 step SLOWER than f32 on the
v5e — 7.78-7.91 vs 6.50 ms; no ledger line holds it — which inverts the
MXU's native-bf16 advantage; this script finds where the time goes).

Five scan-fenced timings on whatever backend jax resolves: it times on the
host platform unless run on the chip, and only a chip run is probative for
the MXU question. No on-chip run of it is on record (ROADMAP S9, S10):

  matmul_f32 / matmul_bf16   pure (4096x4096)@(4096x4096) — the MXU sanity
                             anchor: bf16 MUST win here or the timing
                             itself is miscounting
  resnet_f32 / resnet_bf16   the full ResNet-18 train step, f32 and bf16
  convnet_f32 / convnet_bf16 the same ResNet-18 trunk with BatchNorm
                             REMOVED (GroupNorm-free plain conv stack):
                             if the bf16 regression disappears here, the
                             cost is BN's bf16 statistics path, not convs

Prints one JSON line with all numbers + the implied suspect.

Usage: python scripts/bf16_probe.py [--steps 20]
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=20)
    args = ap.parse_args()

    import flax.linen as nn
    import jax
    import jax.numpy as jnp

    from atomo_tpu.models import get_model
    from atomo_tpu.training import create_state, make_optimizer, make_train_step

    dev = jax.devices()[0]
    steps = args.steps
    out = {"platform": dev.platform, "device": dev.device_kind, "steps": steps}

    def timed_scan(fn, *xs):
        """best-of-3 ms per iteration of `steps` scanned calls, scalar-fenced."""

        @jax.jit
        def many(*ys):
            def body(acc, _):
                r = fn(*[y + acc * 1e-30 for y in ys])
                return jnp.float32(jnp.sum(r) * 1e-20), None

            acc, _ = jax.lax.scan(body, jnp.float32(0), None, length=steps)
            return acc

        s = float(many(*xs))  # compile + warm
        if not math.isfinite(s):
            raise RuntimeError("sync scalar not finite")
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            float(many(*xs))
            best = min(best, (time.perf_counter() - t0) / steps)
        return round(best * 1e3, 3)

    # 1) MXU anchor
    for dt, tag in ((jnp.float32, "matmul_f32_ms"), (jnp.bfloat16, "matmul_bf16_ms")):
        a = jax.random.normal(jax.random.PRNGKey(0), (4096, 4096), dt)
        b = jax.random.normal(jax.random.PRNGKey(1), (4096, 4096), dt)
        out[tag] = timed_scan(
            lambda x, y: jnp.matmul(x, y).astype(jnp.float32), a, b
        )
        print(json.dumps({**out, "partial": True}), flush=True)

    # 2) the full ResNet-18 train step, both precisions
    model = get_model("resnet18", 10)
    opt = make_optimizer("sgd", lr=0.01, momentum=0.9)
    rng = jax.random.PRNGKey(0)
    images = jax.random.uniform(rng, (128, 32, 32, 3), jnp.float32)
    labels = jax.random.randint(rng, (128,), 0, 10)

    def step_ms(compute_dtype):
        state = create_state(model, opt, rng, images)
        step = make_train_step(model, opt, compute_dtype=compute_dtype)
        key = jax.random.PRNGKey(1)

        @jax.jit
        def many(s0):
            def body(s, _):
                s, m = step(s, key, images, labels)
                return s, m["loss"]

            s_out, losses = jax.lax.scan(body, s0, None, length=steps)
            return losses[-1]

        float(many(state))
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            float(many(state))
            best = min(best, (time.perf_counter() - t0) / steps)
        return round(best * 1e3, 3)

    out["resnet_f32_ms"] = step_ms(None)
    print(json.dumps({**out, "partial": True}), flush=True)
    out["resnet_bf16_ms"] = step_ms(jnp.bfloat16)
    print(json.dumps({**out, "partial": True}), flush=True)

    # 3) BN isolation: the same trunk shape with no BatchNorm at all
    class PlainConvNet(nn.Module):
        @nn.compact
        def __call__(self, x, train=True):
            widths = (64, 64, 64, 128, 128, 256, 256, 512, 512)
            strides = (1, 1, 1, 2, 1, 2, 1, 2, 1)
            for w, s in zip(widths, strides):
                x = nn.Conv(w, (3, 3), strides=(s, s), use_bias=False)(x)
                x = nn.relu(x)
            x = x.mean(axis=(1, 2))
            return nn.Dense(10)(x)

    def conv_ms(dtype):
        net = PlainConvNet()
        params = net.init(rng, images)["params"]
        if dtype is not None:
            params_c = jax.tree_util.tree_map(
                lambda a: a.astype(dtype), params
            )
            im = images.astype(dtype)
        else:
            params_c, im = params, images

        def fwd_bwd(p, x):
            def loss(pp):
                lg = net.apply({"params": pp}, x)
                return jnp.mean(lg.astype(jnp.float32) ** 2)

            l, g = jax.value_and_grad(loss)(p)
            return l + sum(
                jnp.sum(a.astype(jnp.float32) ** 2) * 1e-20
                for a in jax.tree_util.tree_leaves(g)
            )

        return timed_scan(lambda x: fwd_bwd(params_c, x), im)

    out["convnet_f32_ms"] = conv_ms(None)
    print(json.dumps({**out, "partial": True}), flush=True)
    out["convnet_bf16_ms"] = conv_ms(jnp.bfloat16)

    mm_ok = out["matmul_bf16_ms"] < out["matmul_f32_ms"]
    conv_gain = out["convnet_f32_ms"] / max(out["convnet_bf16_ms"], 1e-9)
    resnet_gain = out["resnet_f32_ms"] / max(out["resnet_bf16_ms"], 1e-9)
    if not mm_ok:
        suspect = "backend: even the pure MXU matmul shows no bf16 win"
    elif conv_gain > 1.05 and resnet_gain < 1.0:
        suspect = (
            "BatchNorm: plain convs gain from bf16 but the BN'd train step "
            "loses — bf16 statistics/cast chain in BN is the regression"
        )
    elif conv_gain < 1.05:
        suspect = (
            "convolutions at CIFAR shapes: XLA already runs the f32 convs "
            "on bf16 MXU passes, so --bf16 only adds cast overhead"
        )
    else:
        suspect = "none: bf16 wins end-to-end on this session"
    out["suspect"] = suspect
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
