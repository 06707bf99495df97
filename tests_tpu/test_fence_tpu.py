"""Do ``jax.block_until_ready`` and the scalar-fetch fence agree on the
stock TPU backend?

utils.tracing.fence_tree fetches one scalar to the host. Both are waits
for the same device work, so over a 30-step dependent loop of the
compressed ResNet-18 step they must measure the same wall time. This is
a check of the FENCE, not a speed number: the two times are printed and
compared with each other only.
"""

import time

import jax
import jax.numpy as jnp
import numpy as np

from atomo_tpu.codecs import SvdCodec
from atomo_tpu.models import get_model
from atomo_tpu.training import create_state, make_optimizer, make_train_step
from atomo_tpu.utils.tracing import fence_tree

STEPS = 30


def test_block_until_ready_agrees_with_scalar_fence(capsys):
    model = get_model("resnet18", 10)
    opt = make_optimizer("sgd", lr=0.01, momentum=0.0)
    rng = jax.random.PRNGKey(0)
    images = jax.random.uniform(rng, (128, 32, 32, 3), jnp.float32)
    labels = jax.random.randint(rng, (128,), 0, 10)
    state = create_state(model, opt, rng, images)
    step = make_train_step(model, opt, codec=SvdCodec(rank=3))
    key = jax.random.PRNGKey(1)
    for _ in range(3):  # compile + warm
        state, m = step(state, key, images, labels)
    fence_tree(m["loss"])

    def loop(fence):
        nonlocal state
        t0 = time.perf_counter()
        for _ in range(STEPS):
            state, m = step(state, key, images, labels)
        t_enqueued = time.perf_counter() - t0
        fence(m)
        return t_enqueued, time.perf_counter() - t0

    rows = {"block_until_ready": [], "scalar_fetch": []}
    for _ in range(3):
        rows["block_until_ready"].append(
            loop(lambda m: jax.block_until_ready(m["loss"]))
        )
        rows["scalar_fetch"].append(loop(lambda m: fence_tree(m["loss"])))
    with capsys.disabled():
        for name, r in rows.items():
            print(
                f"\n[fence] {name}: {STEPS}-step loop wall "
                + ", ".join(f"{t * 1e3:.1f}" for _, t in r)
                + " ms (enqueue alone "
                + ", ".join(f"{e * 1e3:.1f}" for e, _ in r) + " ms)"
            )
    bur = float(np.median([t for _, t in rows["block_until_ready"]]))
    fetch = float(np.median([t for _, t in rows["scalar_fetch"]]))
    # both waited for the device: neither may return at enqueue time
    assert abs(bur - fetch) <= 0.15 * max(bur, fetch), (bur, fetch)
