"""Fleet launcher — real multi-process formation + the lease drill loop.

Two layers, deliberately separable:

  * **Collective formation** (:func:`form_fleet` / :func:`reform_fleet`)
    wires :func:`atomo_tpu.parallel.launch.initialize` — the retrying
    jax.distributed handshake — so a real 2-process run FORMS, and
    re-forms at a new world after a membership transition. The re-form
    coordinator address is DERIVED (base port + membership epoch), so
    every surviving member computes the same rendezvous without any
    side channel: the epoch record in ``membership.json`` *is* the
    agreement.
  * **The lease loop** (:func:`run_fleet_member`) drives one host's
    :class:`~atomo_tpu.fleet.control.FleetController` round by round —
    heartbeat, observe, reconcile, maybe_transition — with the chaos
    hooks applied at the layer they model: ``hostdie@`` exits the
    process, ``slowlink@`` delays the lease renewal, ``partition@``
    cuts this host off the store entirely (no writes, no reads — the
    colocation fence, see control.py).

    The lease loop needs NO cross-process collectives, so it runs —
    and is drilled 2-process — on runtimes whose CPU backend cannot
    execute a multiprocess psum (where the collective smoke in
    tests/test_multiprocess.py must skip). Collective formation is
    attempted when a coordinator address is given and every failure is
    RECORDED (``fleet_form``/``fleet_reform`` incidents), never fatal
    to the control plane: losing the collective runtime is exactly the
    situation the control plane exists to survive.

``python -m atomo_tpu.fleet.launcher`` runs one member and prints one
``RESULT {json}`` line (the tests/_mp_worker.py convention).

A CPU drill: several members on one host are several JAX processes, a chip
belongs to one process at a time, and nothing here assigns chips between
them. It has only ever run on the CPU backend and stays off chip_smoke.py.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from typing import Optional

from atomo_tpu.fleet.control import (
    FleetConfig,
    FleetController,
    roster_hash,
)
from atomo_tpu.utils.chaos import ChaosInjector


def _reform_address(base: str, epoch: int) -> str:
    """Deterministic per-epoch rendezvous: base ``host:port`` with the
    membership epoch added to the port — every member of the new roster
    derives the same address from the epoch record alone."""
    host, _, port = base.rpartition(":")
    return f"{host}:{int(port) + int(epoch)}"


def _collective_up() -> bool:
    """Is a jax.distributed client currently formed in this process?"""
    try:
        from jax._src.distributed import global_state as _gs

        return getattr(_gs, "client", None) is not None
    except ImportError:
        return False


def _shutdown_bounded(timeout: float) -> bool:
    """``jax.distributed.shutdown()`` with a watchdog: the shutdown is a
    CLUSTER-WIDE BARRIER on this runtime — every member of the old
    collective must call it, and a one-sided call blocks until the peers
    arrive (or the service declares the barrier failed and the error
    poller hard-kills the process). Run it in a thread and give it
    ``timeout`` seconds; returns True when the barrier completed. On
    False the old client is left abandoned — the caller must NOT
    re-initialize in this process (the stale barrier state aborts it)
    and records the re-form as deferred to the next process generation
    instead."""
    import threading

    import jax

    done = threading.Event()

    def _sd():
        try:
            jax.distributed.shutdown()
        except Exception:  # noqa: BLE001 — judged by the event, not the raise
            pass
        done.set()

    th = threading.Thread(target=_sd, daemon=True)
    th.start()
    th.join(max(0.1, float(timeout)))
    return done.is_set()


def stand_down_collective(ctrl: FleetController, timeout: float) -> bool:
    """The EXCLUDED host's half of a re-form: join the old collective's
    shutdown barrier so the survivors' shutdown completes. A store
    partition fences the lease store, not TCP — the excluded host can
    still reach the coordination service, and doing so is what lets the
    surviving roster re-form without tearing the process down. Recorded
    either way (``fleet_stand_down``); a barrier that never completes
    (the peer really died) is abandoned after ``timeout`` and said so."""
    completed = _shutdown_bounded(timeout)
    ctrl.incidents.append(
        "fleet_stand_down",
        action="collective_released" if completed else "release_timeout",
        host=ctrl.host_id,
        epoch=ctrl.epoch.epoch if ctrl.epoch else None,
    )
    ctrl.log_fn(
        f"Fleet: host {ctrl.host_id} "
        + ("released the old collective (stood down)"
           if completed else
           "could not release the old collective within "
           f"{timeout:.0f}s; abandoned")
    )
    return completed


def form_fleet(
    ctrl: FleetController,
    coordinator: str,
    num_processes: int,
    process_id: int,
    *,
    attempts: int = 3,
    backoff: float = 0.5,
    init_timeout: float = 15.0,
) -> bool:
    """Initial collective formation via the retrying handshake
    (:func:`parallel.launch.initialize` — restart-race tolerant). A
    failure is an incident, not an exception: the lease loop runs
    either way."""
    try:
        from atomo_tpu.parallel import launch

        launch.initialize(
            coordinator_address=coordinator,
            num_processes=num_processes,
            process_id=process_id,
            attempts=attempts,
            backoff=backoff,
            init_timeout=init_timeout,
        )
    except Exception as exc:  # noqa: BLE001 — recorded, never fatal here
        ctrl.incidents.append(
            "fleet_form",
            action="form_failed",
            host=ctrl.host_id,
            world=num_processes,
            error=str(exc)[:300],
        )
        ctrl.log_fn(f"Fleet: collective formation failed ({exc}); "
                    "continuing lease-only")
        return False
    ctrl.incidents.append(
        "fleet_form",
        action="formed",
        host=ctrl.host_id,
        world=num_processes,
        coordinator=coordinator,
    )
    return True


def reform_fleet(
    ctrl: FleetController,
    base_coordinator: str,
    *,
    init_timeout: float = 15.0,
) -> bool:
    """Re-form the collective runtime on the CURRENT epoch's roster:
    release the old handshake (the shutdown BARRIER — every old member,
    including the host the new roster excludes, joins it via
    :func:`stand_down_collective`) and re-initialize at the
    epoch-derived address with ranks = roster order. Called by every
    member that adopts (or appends) a roster-changing epoch; the
    blocking initialize is the rendezvous barrier — the leader waits
    there for a healed host that is still reconciling.

    When the old collective cannot be released within ``init_timeout``
    (the excluded peer really died, so the barrier never completes),
    the re-form is DEFERRED: recorded as a ``fleet_reform`` incident
    with ``action="deferred"`` and left for the next process generation
    — re-initializing over an abandoned shutdown barrier hard-aborts
    the process on this runtime, which would take the control plane
    down with it."""
    rec = ctrl.epoch
    if rec is None or ctrl.host_id not in rec.roster:
        return False
    addr = _reform_address(base_coordinator, rec.epoch)
    rank = list(rec.roster).index(ctrl.host_id)
    if _collective_up() and not _shutdown_bounded(init_timeout):
        ctrl.incidents.append(
            "fleet_reform",
            action="deferred",
            host=ctrl.host_id,
            epoch=rec.epoch,
            world=rec.world_size,
            reason=(
                "old collective's shutdown barrier did not complete "
                f"within {init_timeout:.0f}s (a dead peer never joins "
                "it); collective re-form deferred to the next process "
                "generation — the lease control plane continues"
            ),
        )
        ctrl.log_fn(
            f"Fleet: re-form at epoch {rec.epoch} deferred (old "
            "collective not released); continuing lease-only"
        )
        return False
    try:
        from atomo_tpu.parallel import launch

        launch.initialize(
            coordinator_address=addr,
            num_processes=rec.world_size,
            process_id=rank,
            attempts=3,
            backoff=0.5,
            init_timeout=init_timeout,
        )
    except Exception as exc:  # noqa: BLE001 — recorded, never fatal
        ctrl.incidents.append(
            "fleet_reform",
            action="reform_failed",
            host=ctrl.host_id,
            epoch=rec.epoch,
            world=rec.world_size,
            error=str(exc)[:300],
        )
        ctrl.log_fn(
            f"Fleet: re-form at epoch {rec.epoch} failed ({exc}); "
            "continuing lease-only"
        )
        return False
    ctrl.incidents.append(
        "fleet_reform",
        action="reformed",
        host=ctrl.host_id,
        epoch=rec.epoch,
        world=rec.world_size,
        rank=rank,
        coordinator=addr,
    )
    ctrl.log_fn(
        f"Fleet: re-formed at epoch {rec.epoch} "
        f"(world {rec.world_size}, rank {rank})"
    )
    return True


def run_fleet_member(
    train_dir: str,
    host_id: int,
    n_hosts: int,
    *,
    cfg: Optional[FleetConfig] = None,
    rounds: int = 40,
    chaos: Optional[ChaosInjector] = None,
    coordinator: Optional[str] = None,
    stop_epoch: int = 0,
    max_seconds: float = 45.0,
    log_fn=print,
) -> dict:
    """Drive one host through ``rounds`` heartbeat rounds. Returns a
    JSON-able summary. ``stop_epoch`` > 0 ends the drill early once
    this host is a member of an epoch >= it (the drills know their
    target epoch; production would loop forever). ``max_seconds`` is a
    wall guard so a wedged drill fails visibly instead of hanging its
    parent."""
    cfg = cfg or FleetConfig()
    ctrl = FleetController(cfg, train_dir, host_id, n_hosts, log_fn=log_fn)
    formed = False
    reforms = 0
    if coordinator:
        formed = form_fleet(
            ctrl, coordinator, n_hosts, host_id,
            init_timeout=cfg.init_timeout_s,
        )
    ctrl.adopt()
    if chaos is not None and ctrl.epoch is not None:
        chaos.membership_epoch = ctrl.epoch.epoch
    t0 = time.monotonic()
    rounds_run = 0
    cut_rounds = 0
    was_cut = False
    for r in range(1, int(rounds) + 1):
        if time.monotonic() - t0 > max_seconds:
            ctrl.log_fn(
                f"Fleet: host {host_id} drill wall guard hit after "
                f"{r - 1} rounds"
            )
            break
        if chaos is not None:
            chaos.maybe_hostdie(r, host_id)
            if chaos.store_partitioned(r, host_id):
                # cut off the store: no lease renewal, no reads, no
                # evidence rows — the other side sees exactly what a
                # real partition shows it (a lease that stopped)
                cut_rounds += 1
                was_cut = True
                time.sleep(cfg.period_s)
                continue
            if was_cut:
                # back on the store: say so in my own stream (the
                # observer side already recorded lease_stale; this is
                # the healed side's half of the story)
                was_cut = False
                ctrl.incidents.append(
                    "fleet_partition",
                    action="healed",
                    host=ctrl.host_id,
                    round=r,
                    cut_rounds=cut_rounds,
                )
                ctrl.log_fn(
                    f"Fleet: host {host_id} back on the store after "
                    f"{cut_rounds} cut round(s)"
                )
            lag = chaos.slowlink_delay(r, host_id)
            if lag:
                time.sleep(lag)
        before = ctrl.epoch.epoch if ctrl.epoch else -1
        ctrl.heartbeat(step=r)
        ctrl.observe()
        status = ctrl.reconcile()
        if status == "excluded" and coordinator and _collective_up():
            # the excluded host's duty to the survivors: join the old
            # collective's shutdown barrier so THEIR re-form completes
            stand_down_collective(ctrl, cfg.init_timeout_s)
        rec = ctrl.maybe_transition(step=r)
        ctrl.record_metrics(step=r, status=status)
        rounds_run = r
        if ctrl.epoch is not None and ctrl.epoch.epoch != before:
            if chaos is not None:
                # epoch-keyed faults disarm once this host has moved on
                # (the die@ rule at host granularity)
                chaos.membership_epoch = ctrl.epoch.epoch
            if coordinator and ctrl.host_id in ctrl.epoch.roster:
                reforms += int(reform_fleet(
                    ctrl, coordinator,
                    init_timeout=cfg.init_timeout_s,
                ))
        if (
            stop_epoch
            and ctrl.epoch is not None
            and ctrl.epoch.epoch >= stop_epoch
            and ctrl.host_id in ctrl.epoch.roster
        ):
            ctrl.record_metrics(step=r, status="done")
            break
        time.sleep(cfg.period_s)
        _ = rec
    final = ctrl.epoch
    return {
        "host": int(host_id),
        "rounds_run": int(rounds_run),
        "cut_rounds": int(cut_rounds),
        "formed": bool(formed),
        "reforms": int(reforms),
        "epoch": int(final.epoch) if final else None,
        "world": int(final.world_size) if final else None,
        "roster": list(final.roster) if final else [],
        "roster_hash": roster_hash(final.roster) if final else None,
        "member": bool(final and host_id in final.roster),
    }


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        prog="python -m atomo_tpu.fleet.launcher",
        description="Run one fleet member's lease loop (drill driver).",
    )
    p.add_argument("--train-dir", required=True)
    p.add_argument("--host-id", type=int, required=True)
    p.add_argument("--n-hosts", type=int, required=True)
    p.add_argument("--rounds", type=int, default=40)
    p.add_argument("--period", type=float, default=0.05)
    p.add_argument("--patience", type=int, default=3)
    p.add_argument("--max-regrows", type=int, default=1)
    p.add_argument("--stop-epoch", type=int, default=0)
    p.add_argument("--max-seconds", type=float, default=45.0)
    p.add_argument("--init-timeout", type=float, default=15.0,
                   help="seconds to bound each collective handshake and "
                        "the re-form shutdown barrier")
    p.add_argument("--coordinator", default="",
                   help="host:port — attempt real jax.distributed "
                        "formation/re-formation (lease-only when empty)")
    p.add_argument("--chaos", default="",
                   help="chaos spec (hostdie@/slowlink@/partition@ ...)")
    args = p.parse_args(argv)
    cfg = FleetConfig(
        patience=args.patience,
        period_s=args.period,
        max_regrows=args.max_regrows,
        init_timeout_s=args.init_timeout,
    )
    chaos = None
    if args.chaos:
        from atomo_tpu.utils.chaos import ChaosConfig

        chaos = ChaosInjector(ChaosConfig.from_spec(args.chaos))
    summary = run_fleet_member(
        args.train_dir,
        args.host_id,
        args.n_hosts,
        cfg=cfg,
        rounds=args.rounds,
        chaos=chaos,
        coordinator=args.coordinator or None,
        stop_epoch=args.stop_epoch,
        max_seconds=args.max_seconds,
    )
    print("RESULT " + json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
