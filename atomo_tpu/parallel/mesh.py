"""Device mesh construction — the TPU-native replacement for the reference's
MPI world (mpirun -n <P+1> --hostfile, src/run_pytorch.sh:1).

The reference topology is 1 master + N workers over TCP
(src/distributed_nn.py:243-259). SPMD has no master: every chip runs the
same compiled program; the 'parameter server' is the replicated update.
Axis taxonomy (forward-looking — the reference is DP-only, SURVEY.md §2.1):

  dp  data parallelism (the reference's workers)           — first-class
  sp  sequence/context parallelism (ring/Ulysses)          — atomo_tpu.parallel.ring
  tp  tensor parallelism (Megatron-style sharded blocks)   — atomo_tpu.parallel.tp
  ep  expert parallelism (switch-MoE, a2a dispatch)        — atomo_tpu.parallel.moe
  pp  pipeline parallelism (GPipe microbatch schedule)     — atomo_tpu.parallel.pp
"""

from __future__ import annotations

import json
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def make_mesh(
    n_devices: Optional[int] = None,
    axes: Sequence[tuple[str, int]] = (),
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """Build a mesh.

    Default: 1-D ('dp', n) over all visible devices. Pass ``axes`` as
    [('dp', 4), ('sp', 2)] for multi-axis layouts; sizes must multiply to
    the device count.
    """
    devs = list(devices if devices is not None else jax.devices())
    if n_devices is not None:
        devs = devs[:n_devices]
    if not axes:
        axes = (("dp", len(devs)),)
    names = tuple(a for a, _ in axes)
    sizes = tuple(s for _, s in axes)
    if int(np.prod(sizes)) != len(devs):
        raise ValueError(f"mesh axes {axes} need {np.prod(sizes)} devices, have {len(devs)}")
    arr = np.asarray(devs).reshape(sizes)
    return Mesh(arr, names)


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def batch_sharded(mesh: Mesh, axis: str = "dp") -> NamedSharding:
    return NamedSharding(mesh, P(axis))


def device_line(mesh: Optional[Mesh] = None) -> str:
    """The start-up line ``train`` and ``lm`` print: the device as JAX
    reports it and the mesh the run lays over it (``{}`` for the
    single-device loop). One JSON object after the prefix — chip_smoke.py
    reads it, and fails unless the platform is ``tpu``."""
    devs = jax.devices()
    return "Device: " + json.dumps({
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
        "mesh": {} if mesh is None else {k: int(v) for k, v in mesh.shape.items()},
    })


def shard_devices(tree) -> dict:
    """id -> device, for every device holding an addressable shard of a
    ``jax.Array`` leaf of ``tree``."""
    return {
        s.device.id: s.device
        for leaf in jax.tree_util.tree_leaves(tree)
        if isinstance(leaf, jax.Array)
        for s in leaf.addressable_shards
    }


def placement_line(state, batch) -> str:
    """Where a multi-device run's work actually sits after its first
    dispatch: the ids of the devices holding shards of the training state
    and of the batch, and the bytes in use on each state device as the
    runtime counts them (null where the backend keeps no count — the CPU)."""
    holders = shard_devices(state)
    in_use = {}
    for i, dev in sorted(holders.items()):
        stats = dev.memory_stats()
        in_use[str(i)] = None if stats is None else int(stats["bytes_in_use"])
    return "Placement: " + json.dumps({
        "state_devices": sorted(holders),
        "batch_devices": sorted(shard_devices(batch)),
        "bytes_in_use": in_use,
    })
