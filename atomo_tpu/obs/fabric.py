"""Measured fabric — per-tier bandwidth/latency probed on the real mesh.

Every prediction in the system (autopilot candidate ranking, the topology
planner's per-tier reason lines, the sparse hybrid crossover, the flight
recorder's calibration column) is priced from NAMED fabric presets
(``utils/comm_model.FABRICS``), i.e. from what the operator asserts the
wire is, not what it measures as. ROADMAP open item 2 says it out loud:
"*measure* the fabric instead of naming it". This module is that probe:

  * :func:`probe_fabric` runs fenced ``ppermute`` / ``all_gather``
    ladders over a size sweep on the real mesh (the probe fence
    discipline — warm, dispatch loop, device->host scalar fence,
    best-of-reps via ``tuning.probe.fenced_seconds_per_call``), one
    ladder per tier: the flat mesh's single fabric, or — when
    ``dcn_ways > 1`` — the ici and dcn axes probed SEPARATELY on the
    same ``(dp=K, ici=n/K)`` mesh the hierarchical schedules execute on.
    Per tier it fits per-chip effective ring bandwidth from the ppermute
    size slope and per-hop latency from the small-size intercept, with
    the all_gather ladder recorded as a cross-check.
  * The result is written ATOMICALLY to ``train_dir/fabric_probe.json``
    (``write_json_atomic`` — the one artifact discipline), so a killed
    run leaves parseable evidence and a ``--resume`` reuses the
    measurement instead of re-probing.
  * ``--fabric measured`` resolves from the artifact: the ONE fabric
    parsers (``comm_model.resolve_fabric`` and
    ``topology.fabric.resolve_two_tier``) accept the probe document via
    their ``measured=`` parameter, so ``predict_step_s``,
    ``choose_plan``, the hybrid crossover, and ``enumerate_candidates``
    all price from measurement through the same grammar every other
    fabric value uses.

SEMANTICS CONTRACT (the PR-6 probe-isolation precedent): the fabric
value is a PRICING input, never a semantics input. The probe runs on
deterministic ``jnp``-built buffers — it never touches the training data
iterator's shuffle RNG or the run's init seed — so ``--fabric measured``
trains bit-identical to the same resolved knobs under a pinned scalar
fabric (drilled through the CLI by tests/test_fabric_obs.py).

The probe also arms DRIFT BLAME (tuning.autopilot.OnlineRetuner): when a
step-time drift alarm fires, the retuner re-runs the cheap
:func:`quick_probe` and the ``perf_drift`` incident records whether the
FABRIC moved (per-tier baseline-vs-measured GB/s quoted; the artifact is
re-written so later pricing reads the new numbers) or the PROGRAM did
(the candidate re-probe decides), with both numbers quoted either way.

On the forced multi-device CPU mesh the "fabric" is host memcpy
bandwidth — recorded honestly (``meta.backend``), exactly like every
other CPU-mesh evidence row; the probe's value there is that the whole
measure->resolve->price loop is exercised end to end.
"""

from __future__ import annotations

import os
import time
from typing import Optional

FABRIC_PROBE_NAME = "fabric_probe.json"

# probe size sweep (bytes per chip per hop): small sizes expose the
# per-hop latency floor, large ones the bandwidth asymptote
DEFAULT_SIZES = (1 << 12, 1 << 16, 1 << 20, 1 << 23)
# the drift-blame re-probe: two points are enough for the slope, and the
# alarm path must stay cheap (it runs inside a checkpoint boundary)
QUICK_SIZES = (1 << 12, 1 << 20)
# per-tier bandwidth ratio past which drift blame says the FABRIC moved
FABRIC_MOVED_RATIO = 1.5


def probe_path(train_dir: str) -> str:
    return os.path.join(train_dir, FABRIC_PROBE_NAME)


def read_fabric_probe(train_dir: str) -> Optional[dict]:
    """The recorded probe document, or None when absent/unparseable
    (a torn or missing artifact is "no measurement", never a crash)."""
    import json

    try:
        with open(probe_path(train_dir)) as f:
            doc = json.load(f)
    except (OSError, ValueError):
        return None
    return doc if isinstance(doc, dict) else None


def measured_bandwidths(doc: dict) -> dict:
    """``{tier label: per-chip bandwidth bytes/s}`` from a probe doc —
    the shape the ONE fabric parsers consume via ``measured=``."""
    out = {}
    for tier in (doc or {}).get("tiers", []):
        bw = tier.get("bandwidth_gbps")
        if isinstance(bw, (int, float)) and bw > 0:
            out[str(tier.get("label"))] = float(bw) * 1e9
    return out


def measured_outer_bw(doc: dict) -> float:
    """The SLOWEST measured tier's bandwidth (bytes/s) — the historical
    single-scalar meaning of a fabric value (the slowest link on the
    gradient path). Raises ValueError on an artifact with no usable
    tier, with the re-probe instruction in the message."""
    bws = measured_bandwidths(doc)
    if not bws:
        raise ValueError(
            "fabric_probe.json carries no usable tier measurement — "
            "delete it and re-run with --fabric measured to re-probe"
        )
    return min(bws.values())


def measured_two_tier(doc: dict, *, dcn_ways: int, n_dev: int):
    """A :class:`~atomo_tpu.topology.fabric.TwoTierFabric` built from
    the probe artifact — measured bandwidths AND measured per-hop
    latencies per tier (the preset anchors replaced by numbers from this
    mesh). Needs a probe that measured both tiers (``--dcn-ways`` was
    set when it ran)."""
    from atomo_tpu.topology.fabric import TwoTierFabric

    k = int(dcn_ways)
    tiers = {str(t.get("label")): t for t in (doc or {}).get("tiers", [])}
    if "ici" not in tiers and int(n_dev) // k == 1 and "dcn" in tiers:
        # dcn_ways == n_dev: every inner group is one chip — the inner
        # tier has no hops to probe (probe_fabric skips a 1-wide axis)
        # and its bandwidth prices zero bytes, so the dcn measurement
        # stands in rather than rejecting a shape resolve_two_tier's own
        # grammar accepts
        tiers = dict(tiers, ici=tiers["dcn"])
    if "ici" not in tiers or "dcn" not in tiers:
        raise ValueError(
            "--fabric measured on a two-tier mesh needs a probe artifact "
            "with both ici and dcn tiers (found: "
            f"{sorted(tiers) or 'none'}); delete fabric_probe.json and "
            "re-run with --dcn-ways set so both axes are probed"
        )

    def _bw(t):
        return float(t["bandwidth_gbps"]) * 1e9

    def _lat(t, default):
        v = t.get("latency_us")
        return float(v) / 1e6 if isinstance(v, (int, float)) else default

    from atomo_tpu.topology.fabric import (
        DCN_HOP_LATENCY_S,
        ICI_HOP_LATENCY_S,
    )

    return TwoTierFabric(
        inner_bw=_bw(tiers["ici"]),
        outer_bw=_bw(tiers["dcn"]),
        inner_ways=int(n_dev) // k,
        outer_ways=k,
        inner_latency_s=_lat(tiers["ici"], ICI_HOP_LATENCY_S),
        outer_latency_s=_lat(tiers["dcn"], DCN_HOP_LATENCY_S),
        inner_label="measured_ici",
        outer_label="measured_dcn",
    )


# ------------------------------------------------------------------ probe


def _ladder(mesh, axis: str, sizes, *, reps: int, warmup: int,
            best_of: int) -> list[dict]:
    """One tier's measured rows: fenced seconds for a single ppermute
    ring hop and a full all_gather of an S-byte per-chip buffer, per
    size. The buffers are deterministic ``jnp`` constants — no PRNG, no
    data-iterator contact (the probe-isolation contract)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from atomo_tpu.tuning.probe import fenced_seconds_per_call

    names = tuple(mesh.axis_names)
    ways = int(mesh.shape[axis])
    total = 1
    for n in names:
        total *= int(mesh.shape[n])
    perm = [(i, (i + 1) % ways) for i in range(ways)]
    rows = []
    for size in sizes:
        n_elem = max(int(size) // 4, 1)  # f32 elements per chip

        def hop(x):
            y = jax.lax.ppermute(x, axis, perm)
            # per-device scalar keeps the collective live under DCE and
            # the fence fetch O(1)
            return jnp.sum(y).reshape(1, 1)

        def gather(x):
            g = jax.lax.all_gather(x, axis)
            return jnp.sum(g).reshape(1, 1)

        buf = jnp.ones((total, n_elem), jnp.float32)

        def timed(fn):
            sm = jax.jit(jax.shard_map(
                fn, mesh=mesh, in_specs=P(names), out_specs=P(names),
                check_vma=False,
            ))
            secs, sync_ok = fenced_seconds_per_call(
                lambda: sm(buf), reps=reps, warmup=warmup, best_of=best_of
            )
            return secs, sync_ok

        t_pp, ok_pp = timed(hop)
        t_ag, ok_ag = timed(gather)
        rows.append({
            "bytes": int(size),
            "ppermute_ms": round(t_pp * 1e3, 6),
            "allgather_ms": round(t_ag * 1e3, 6),
            "sync_ok": bool(ok_pp and ok_ag),
        })
    return rows


def _fit_tier(rows: list[dict], ways: int) -> dict:
    """Bandwidth from the ppermute size slope, per-hop latency from the
    small-size intercept (t(S) = lat + S/bw — a stated two-point fit,
    not a regression), all_gather bandwidth as the recorded cross-check.
    Rows whose fence came back non-finite are excluded from the fit."""
    ok = [r for r in rows if r.get("sync_ok", True)]
    out = {"bandwidth_gbps": None, "latency_us": None,
           "allgather_gbps": None}
    if not ok:
        return out
    lo, hi = min(ok, key=lambda r: r["bytes"]), max(
        ok, key=lambda r: r["bytes"]
    )
    t_lo, t_hi = lo["ppermute_ms"] / 1e3, hi["ppermute_ms"] / 1e3
    if hi["bytes"] > lo["bytes"] and t_hi > t_lo:
        bw = (hi["bytes"] - lo["bytes"]) / (t_hi - t_lo)
    elif t_hi > 0:
        bw = hi["bytes"] / t_hi  # degenerate sweep: asymptote only
    else:
        return out
    out["bandwidth_gbps"] = round(bw / 1e9, 4)
    out["latency_us"] = round(max(t_lo - lo["bytes"] / bw, 0.0) * 1e6, 3)
    t_ag = hi["allgather_ms"] / 1e3
    if t_ag > 0 and ways > 1:
        out["allgather_gbps"] = round(
            hi["bytes"] * (ways - 1) / t_ag / 1e9, 4
        )
    return out


def probe_fabric(
    *,
    n_dev: int,
    dcn_ways: int = 0,
    sizes=DEFAULT_SIZES,
    reps: int = 3,
    warmup: int = 1,
    best_of: int = 2,
    log_fn=print,
) -> dict:
    """Measure the mesh's fabric per tier (module docstring). Flat mesh:
    one tier labeled ``ici`` (the convention for "the fabric connecting
    this mesh's chips"). ``dcn_ways > 1``: the ``(dp=K, ici=n/K)``
    two-tier mesh with the ici and dcn axes probed separately. Returns
    the probe document; writing it is the caller's move
    (:func:`ensure_fabric_probe` pairs it with the artifact path)."""
    import jax

    from atomo_tpu.parallel import make_mesh

    t0 = time.perf_counter()
    n = int(n_dev)
    if n < 2:
        raise ValueError(
            "--fabric measured needs a multi-device mesh: a single "
            "device has no inter-chip fabric to measure"
        )
    k = int(dcn_ways)
    two_tier = k > 1 and n % k == 0 and k <= n
    tiers = []
    if two_tier:
        mesh = make_mesh(n, axes=(("dp", k), ("ici", n // k)))
        for label, axis in (("ici", "ici"), ("dcn", "dp")):
            ways = int(mesh.shape[axis])
            if ways < 2:
                continue  # a 1-wide axis has no hops to time
            rows = _ladder(mesh, axis, sizes, reps=reps, warmup=warmup,
                           best_of=best_of)
            tiers.append({
                "label": label, "axis": axis, "ways": ways,
                **_fit_tier(rows, ways), "rows": rows,
            })
    else:
        mesh = make_mesh(n)
        rows = _ladder(mesh, "dp", sizes, reps=reps, warmup=warmup,
                       best_of=best_of)
        tiers.append({
            "label": "ici", "axis": "dp", "ways": n,
            **_fit_tier(rows, n), "rows": rows,
        })
    doc = {
        "kind": "fabric_probe",
        "meta": {
            "backend": jax.default_backend(),
            "n_devices": n,
            "dcn_ways": k if two_tier else 0,
            "sizes_bytes": [int(s) for s in sizes],
            "reps": int(reps),
            "best_of": int(best_of),
            "probe_wall_s": round(time.perf_counter() - t0, 3),
        },
        "tiers": tiers,
        "complete": all(
            t.get("bandwidth_gbps") for t in tiers
        ) and bool(tiers),
    }
    for t in tiers:
        log_fn(
            f"Fabric probe: {t['label']} ({t['ways']} ways) measured "
            f"{t['bandwidth_gbps']} GB/s/chip, {t['latency_us']} us/hop "
            f"(all_gather cross-check {t['allgather_gbps']} GB/s)"
        )
    return doc


def write_fabric_probe(train_dir: str, doc: dict) -> str:
    """Atomic artifact write (the one discipline — write_json_atomic)."""
    from atomo_tpu.utils.tracing import write_json_atomic

    path = probe_path(train_dir)
    write_json_atomic(path, doc)
    return path


def ensure_fabric_probe(
    train_dir: str,
    *,
    n_dev: int,
    dcn_ways: int = 0,
    reuse: bool = False,
    log_fn=print,
) -> dict:
    """The CLI's ``--fabric measured`` startup hook: reuse a complete
    recorded probe when ``reuse`` (a ``--resume`` must not re-measure —
    the resumed pricing should match the original run's), else probe the
    mesh and write ``train_dir/fabric_probe.json``. A recorded probe for
    a DIFFERENT mesh shape is never reused — the measurement describes a
    topology that no longer exists (the decision_reusable precedent)."""
    # normalize the requested shape the same way probe_fabric will
    # record it (a non-dividing or degenerate dcn_ways probes flat with
    # meta.dcn_ways=0) — otherwise a --resume of such a run would
    # re-probe forever on a mismatch that is not one
    k = int(dcn_ways)
    k_norm = k if (1 < k <= int(n_dev) and int(n_dev) % k == 0) else 0
    if reuse:
        doc = read_fabric_probe(train_dir)
        if doc and doc.get("complete"):
            meta = doc.get("meta") or {}
            if (
                meta.get("n_devices") == int(n_dev)
                and int(meta.get("dcn_ways") or 0) == k_norm
            ):
                log_fn(
                    f"Fabric probe: reusing {probe_path(train_dir)} "
                    "(delete the file to re-measure)"
                )
                return doc
            log_fn(
                "Fabric probe: NOT reusing the recorded artifact (it "
                f"measured n_devices={meta.get('n_devices')}, "
                f"dcn_ways={meta.get('dcn_ways')} — this run has "
                f"{n_dev}/{dcn_ways}); re-probing"
            )
    doc = probe_fabric(n_dev=n_dev, dcn_ways=dcn_ways, log_fn=log_fn)
    path = write_fabric_probe(train_dir, doc)
    log_fn(f"Fabric probe: artifact -> {path}")
    return doc


def quick_probe(*, n_dev: int, dcn_ways: int = 0, log_fn=print) -> dict:
    """The drift-blame re-probe: the same ladder at two sizes, one rep —
    cheap enough for a checkpoint boundary, accurate enough to answer
    "did the fabric move by >1.5x", which is the only question blame
    asks of it."""
    return probe_fabric(
        n_dev=n_dev, dcn_ways=dcn_ways, sizes=QUICK_SIZES, reps=1,
        warmup=1, best_of=1, log_fn=log_fn,
    )


# ------------------------------------------------- per-tier prediction


def predicted_tier_ms(
    *,
    aggregate: str,
    dense_bytes: float,
    payload_bytes: float,
    ways: int,
    fabric_bw: Optional[float] = None,
    fabric_label: str = "fabric",
    fabric2=None,
    plan_name: Optional[str] = None,
) -> dict:
    """``{tier label: predicted comm ms}`` — the per-tier decomposition
    of the winner's predicted step time that the flight recorder's
    per-tier calibration column tracks against. Flat aggregates cross
    one fabric end to end (one tier, the wire formula per mode);
    hierarchical plans decompose over both tiers via
    ``topology.schedule.plan_wire_bytes``. Returns {} when the context
    cannot be priced (no bandwidth) — an absent column, never a made-up
    one."""
    from atomo_tpu.utils.comm_model import (
        ring_allgather_wire_bytes,
        ring_allreduce_wire_bytes,
        ring_stream_wire_bytes,
    )

    ways = int(ways)
    if ways <= 1:
        return {}
    if aggregate == "hierarchical" and fabric2 is not None:
        from atomo_tpu.topology.schedule import (
            plan_from_name,
            plan_wire_bytes,
        )

        wires = plan_wire_bytes(
            plan_from_name(plan_name or "legacy"),
            dense_bytes=dense_bytes,
            payload_bytes=payload_bytes,
            fabric=fabric2,
        )
        return {
            fabric2.inner_label: round(fabric2.tier_time_s(
                wires["inner_bytes"], "inner", wires["inner_hops"]
            ) * 1e3, 4),
            fabric2.outer_label: round(fabric2.tier_time_s(
                wires["outer_bytes"], "outer", wires["outer_hops"]
            ) * 1e3, 4),
        }
    if not fabric_bw or fabric_bw <= 0:
        return {}
    if aggregate == "psum" or not payload_bytes:
        wire = ring_allreduce_wire_bytes(dense_bytes, ways)
    elif aggregate == "ring":
        wire = ring_stream_wire_bytes(payload_bytes, dense_bytes, ways)
    else:
        wire = ring_allgather_wire_bytes(payload_bytes, ways)
    return {fabric_label: round(wire / float(fabric_bw) * 1e3, 4)}
