"""Worker for the real 2-process jax.distributed smoke test.

Launched (never imported) by tests/test_multiprocess.py: two copies of this
script form a 2-process jax.distributed job on localhost, each contributing
2 virtual CPU devices to a global 4-device 'dp' mesh, and run ONE compressed
SPMD training step end-to-end. This executes the code CI could previously
only monkeypatch (VERDICT r2 next-round #5):

  * launch.initialize()'s env path actually calling
    jax.distributed.initialize (replaces the reference's mpirun rank
    dispatch, src/distributed_nn.py:86-88,243-259);
  * shard_batch's jax.make_array_from_process_local_data branch
    (parallel/replicated.py) — each process feeds only its local shard;
  * the gather-aggregate step with cross-process collectives.

Prints one `RESULT {json}` line; the parent asserts both processes agree
bit-for-bit on the post-step state (replicated-PS equivalence, SURVEY.md §7
hard-part 4).

A CPU drill by construction (it forces the CPU platform below): two JAX
processes on one host cannot share a chip, and nothing assigns chips
between them.
"""

import json
import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=2"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

from atomo_tpu.parallel import launch  # noqa: E402
from atomo_tpu.utils.chaos import ChaosInjector  # noqa: E402

# simulated process death (kill@1) BEFORE the distributed handshake, so the
# fault-tolerance drill can kill real workers without deadlocking the peer
# in a collective (tests/test_fault_tolerance.py)
_chaos = ChaosInjector.from_env()
if _chaos is not None:
    _chaos.maybe_die(1)

launch.initialize()  # env path: JAX_COORDINATOR_ADDRESS / _NUM_PROCESSES / _ID

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from atomo_tpu.codecs import SvdCodec  # noqa: E402
from atomo_tpu.models import get_model  # noqa: E402
from atomo_tpu.parallel.launch import global_mesh  # noqa: E402
from atomo_tpu.parallel.replicated import (  # noqa: E402
    make_distributed_train_step,
    replicate_state,
    shard_batch,
)
from atomo_tpu.training import create_state, make_optimizer  # noqa: E402


def _params_sha256(params) -> str:
    import hashlib

    h = hashlib.sha256()
    for leaf in jax.tree_util.tree_leaves(params):
        h.update(np.asarray(jax.device_get(leaf)).tobytes())
    return h.hexdigest()


def main_lm() -> None:
    """dp x sp LM mode (ATOMO_MP_MODE=lm): the SEQUENCE axis spans the two
    processes (mesh rows = sp = process index), so ring attention's K/V
    ppermutes and the boundary-target fetch cross a REAL process boundary
    every step — the multi-host long-context claim, actually executed. The
    dp pair (and its compressed gather) lives inside each process."""
    from atomo_tpu.models.transformer import TransformerLM
    from atomo_tpu.parallel.lm import make_lm_train_step

    pid = jax.process_index()
    mesh = global_mesh((("sp", 2), ("dp", 2)))  # sp major: rows = processes
    cfg = dict(vocab_size=16, max_len=16, width=16, depth=1, num_heads=2)
    opt = make_optimizer("sgd", lr=0.05, momentum=0.9)
    sample = jnp.zeros((2, 16), jnp.int32)
    state = replicate_state(
        mesh, create_state(TransformerLM(**cfg), opt, jax.random.PRNGKey(0), sample)
    )
    step = make_lm_train_step(cfg, opt, mesh, SvdCodec(rank=2))

    # both processes generate the SAME global batch (seed is shared); each
    # contributes its own half of every sequence (its sp shard)
    full = np.random.RandomState(42).randint(0, 16, (4, 16)).astype(np.int32)
    local_toks = full[:, pid * 8 : (pid + 1) * 8]
    from jax.sharding import NamedSharding, PartitionSpec as P

    toks = jax.make_array_from_process_local_data(
        NamedSharding(mesh, P("dp", "sp")), local_toks
    )
    assert toks.shape == (4, 16), toks.shape
    state, metrics = step(state, jax.random.PRNGKey(1), toks)
    print(
        "RESULT "
        + json.dumps(
            {
                "pid": int(pid),
                "loss": float(metrics["loss"]),
                "msg_bytes": int(metrics["msg_bytes"]),
                "params_sha256": _params_sha256(state.params),
            }
        ),
        flush=True,
    )


def main() -> None:
    assert jax.process_count() == 2, f"process_count={jax.process_count()}"
    assert len(jax.devices()) == 4, f"global devices={len(jax.devices())}"
    if os.environ.get("ATOMO_MP_MODE") == "lm":
        main_lm()
        return
    pid = jax.process_index()

    mesh = global_mesh((("dp", 4),))
    model = get_model("lenet", 10)
    opt = make_optimizer("sgd", lr=0.01, momentum=0.0)
    rng = jax.random.PRNGKey(0)
    sample = jnp.zeros((4, 28, 28, 1), jnp.float32)
    state = replicate_state(mesh, create_state(model, opt, rng, sample))
    step = make_distributed_train_step(
        model, opt, mesh, codec=SvdCodec(rank=2), aggregate="gather"
    )

    # each process feeds its OWN local shard (2 local devices x 2 samples),
    # independently generated — the reference's workers also shuffle
    # independently (src/distributed_nn.py:93-207)
    local_im = np.random.RandomState(pid).rand(4, 28, 28, 1).astype(np.float32)
    local_lb = np.random.RandomState(100 + pid).randint(0, 10, (4,)).astype(np.int32)
    gi, gl = shard_batch(mesh, local_im, local_lb)
    assert gi.shape[0] == 8, gi.shape  # global batch = both processes' shards

    state, metrics = step(state, jax.random.PRNGKey(1), gi, gl)
    # ATOMO_MP_DUMP: process 0 saves the post-step param leaves so the
    # parent test can compare them leaf-wise against its single-process
    # oracle (a summary scalar would absorb compensating divergences)
    dump_path = os.environ.get("ATOMO_MP_DUMP", "")
    if dump_path and pid == 0:
        np.savez(
            dump_path,
            *[np.asarray(jax.device_get(l))
              for l in jax.tree_util.tree_leaves(state.params)],
        )
    # fingerprint the post-step replicated params: a cryptographic hash of
    # the raw bytes — an L1-sum scalar would absorb sub-rounding or
    # compensating divergences and defeat the bit-for-bit claim
    print(
        "RESULT "
        + json.dumps(
            {
                "pid": int(pid),
                "loss": float(metrics["loss"]),
                "msg_bytes": int(metrics["msg_bytes"]),
                "params_sha256": _params_sha256(state.params),
                "dump_path": dump_path or None,
            }
        ),
        flush=True,
    )


if __name__ == "__main__":
    main()
