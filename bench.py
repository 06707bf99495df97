"""Headline benchmark: compressed training step on the local accelerator.

Canonical recipe (reference src/run_pytorch.sh:1-20): ResNet-18, CIFAR-10,
batch 128, SVD sparsification at rank 3. This bench times our jitted train
step (forward + backward + encode + decode + momentum-SGD update, one XLA
program) and compares against a reference-equivalent pipeline measured on
this host's CPU: a torch ResNet-18 fwd/bwd plus the reference's per-layer
numpy-SVD encode/decode hot path (src/distributed_worker.py:229-246 +
src/codings/svd.py:79-178 semantics) — the same work the reference's
m4.2xlarge CPU workers do each step.

Process design: the measurement runs in a CHILD subprocess and the parent
never initializes jax — a chip belongs to one process at a time, so the
parent stays off the backend and runs its children one after another. A
config that measures the TPU and finds none FAILS: its child exits
non-zero and says why, the parent records an error row (never a CPU row
under the same metric name) and exits non-zero. The `force_cpu_mesh`
configs are semantics drills that always run on a forced CPU mesh and say
so in their rows.

Timing discipline: JAX dispatch is asynchronous, so timing a dispatch loop
without a fence measures enqueue latency, not execution. Every timed loop
here ends with a device→host SCALAR fetch (`float(metrics["loss"])`): the
step chain is sequentially dependent, so the scalar of step N forces
execution of all N steps, and the fetched value doubles as the finiteness
check. `measurement_valid` is emitted alongside: false (with
`invalid_reason`) whenever the sync scalar is non-finite or a computed MFU
falls outside (0, 1).

By default the WHOLE ladder runs (the five BASELINE.md configs plus the LM
config 6, the shipped-loop superstep config 7, and the forced-CPU-mesh
semantics compares: ring-vs-gather config 8, overlap-vs-blocking
config 9, the autopilot scenario matrix config 10, the two-tier plan
matrix config 11, the stream-encode exposure config 12, the sparse-wire
config 13, the fabric-probe calibration config 14, the sharded-update
memory config 15, the adaptive-budget Pareto config 16, the quorum
straggler-absorption config 17, and the controller joint-decision
config 18): one JSON row per config
as it completes, then ONE final aggregate line — the headline config-2 row
with a "configs" list embedding every row (VERDICT r2 next-round #4; the
driver parses the last line). The parent enforces a global wall-clock
budget (ATOMO_BENCH_DEADLINE_S, default 840 s — under the driver's 870 s
cap): child timeouts are clamped to the remaining budget and configs that
cannot start emit an honest deadline row, so the final aggregate line is
always complete.

  {"metric": ..., "value": <ms/step>, "unit": "ms/step",
   "vs_baseline": <baseline_s / ours_s or null>,    # TIME ratio only
   "baseline": "torch-cpu-refpipe" | "none",
   "byte_reduction": <dense_bytes / payload_bytes>, # the bytes win
   "mfu": <fraction of peak or null>, "flops_per_step": ...,
   "peak_tflops": ..., "platform": ..., "device": ...,
   "chips_measured": 1, "measurement_valid": true|false,
   "timing": "warm-cache-scalar-sync", "error": null | "...",
   "configs": [...five rows...]}                    # aggregate line only

`vs_baseline` is strictly a step-time ratio (>1 = we are faster); the bytes
win is reported separately in `byte_reduction` and is never substituted
into the time field (round-1 ADVICE finding).

Usage: python bench.py [--config N | --all] [--no-baseline]
"""

from __future__ import annotations

import argparse
import json
import math
import os
import subprocess
import sys
import time

WARMUP = 3
STEPS = 30  # steps between scalar fetches: amortizes the one host round trip
REPS = 3  # best-of-N timing repeats
CHILD_TIMEOUT_S = 2400
NO_TPU_EXIT_CODE = 3  # a TPU-measuring child that found another platform

# BASELINE.md config ladder. `ways` is the reference cluster width the config
# models; payload bytes/chip/step do not depend on it, and step time is
# measured on the locally available chip (the driver validates multi-chip
# sharding separately via __graft_entry__.dryrun_multichip).
CONFIGS = {
    1: dict(metric="lenet_mnist_qsgd_step_time", network="lenet",
            input=(28, 28, 1), batch=128, code="qsgd", ways=1,
            dense_compare=True),
    2: dict(metric="resnet18_cifar10_svd3_step_time", network="resnet18",
            input=(32, 32, 3), batch=128, code="svd", rank=3, ways=8,
            torch_baseline=True, dense_compare=True, qsgd_compare=True,
            bf16_compare=True, attn_compare=True, wire_compare=True),
    3: dict(metric="vgg11_cifar10_svd5_step_time", network="vgg11",
            input=(32, 32, 3), batch=128, code="svd", rank=5, ways=16,
            dense_compare=True),
    4: dict(metric="resnet50_cifar10_svd3_ckpt_step_time", network="resnet50",
            input=(32, 32, 3), batch=128, code="svd", rank=3, ways=32,
            ckpt=True, dense_compare=True),
    5: dict(metric="resnet110_cifar10_svd3_budget_step_time", network="resnet110",
            input=(32, 32, 3), batch=128, code="svd_budget", rank=3, ways=64,
            dense_compare=True),
    # Config 6 (VERDICT r4 next-round #9): the high-MFU operating point.
    # The CIFAR ladder is HBM-bound with single-digit MFU ceilings by
    # physics (artifacts/ROOFLINE.md); this one is matmul-dominated —
    # TransformerLM width 512, bf16 MXU compute, 16k tokens/step — so the
    # framework demonstrates a high-MFU regime and the codec's behavior
    # there. rank 48 = the width-scaled policy (ceil(512*6/64), the
    # verified rank/width ratio — artifacts/LM_CONVERGENCE.md). No
    # reference analogue (CV-only): baseline "none".
    6: dict(metric="transformer_lm_w512_svd48_step_time", kind="lm",
            width=512, depth=8, num_heads=8, vocab=8192, seq=512, batch=32,
            code="svd", rank=48, bf16=True, ways=8, dense_compare=True),
    # Config 7 (PR-2 superstep tentpole): loop_as_shipped — times the
    # ACTUAL train_loop (host machinery, data feed, metric fetch, watchdog
    # hooks included) at --superstep 1 vs K, from the loop's own log-line
    # timestamps. The other rows' scan-fenced device times deliberately
    # exclude host dispatch; this row is where the per-dispatch host cost
    # shows up or is amortized away (not measured on the stock TPU
    # backend yet). Baseline "none".
    7: dict(metric="train_loop_superstep_step_time", kind="loop",
            network="lenet", dataset="mnist", batch=64, superstep=8, ways=1),
    # Config 8 (PR-3 ring tentpole): ring-vs-gather aggregation compare on
    # a multi-device mesh. A semantics drill, so this row always runs on a
    # forced 4-virtual-device CPU mesh (platform recorded honestly): it is a SEMANTICS + dispatch + phase
    # micro-compare (encode / exchange / decode programs timed separately,
    # aggregation-operator bit parity asserted in-row), not a chip-speed
    # claim. Baseline "none".
    8: dict(metric="ring_vs_gather_dispatch", kind="ringcmp",
            network="lenet", batch=32, n_dev=4, ways=4, force_cpu_mesh=True),
    # Config 9 (PR-4 overlap tentpole): --overlap delayed vs blocking on
    # the forced 4-device CPU mesh. Fenced full-step times for both modes
    # per codec, per-phase compute/encode/exchange/decode programs so the
    # exchange+decode chain that delayed takes off the critical path is
    # visible with numbers (comm_model.overlap_* turns them into
    # hidden/exposed ms), and the two-program eager-oracle bit parity
    # asserted in-row. Like config 8 this is a semantics + schedule
    # micro-compare, not a chip-speed claim. Baseline "none".
    9: dict(metric="overlap_vs_blocking", kind="overlapcmp",
            network="lenet", batch=16, n_dev=4, ways=4, force_cpu_mesh=True),
    # Config 10 (PR-7 autopilot tentpole): scenario_matrix — the sweep
    # that regression-gates the autopilot's choices the way configs 8-9
    # gated ring and overlap. {lenet, resnet18} x {1, 4 devices} x
    # {dense, qsgd8, svd3} on the forced CPU mesh: fenced ms/step + byte
    # reduction per cell (the shared tuning.probe runner — the same code
    # path `--auto tune` measures with), the gather-vs-ring aggregation-
    # operator bit-parity assert for every compressed multi-device cell
    # (the invariant that keeps the online re-tuner's switch trajectory-
    # safe), and per-fabric recommended configs from measured anchors +
    # the comm model (comm_model.recommend_for_scenario — the README's
    # recommended-config tables read from this row). Baseline "none";
    # fast mode keeps the lenet cells only, and a per-config cell budget
    # (ATOMO_SCENARIO_BUDGET_S) skips-and-records instead of overrunning.
    10: dict(metric="scenario_matrix", kind="scenarios", batch=8, n_dev=4,
             ways=4, force_cpu_mesh=True),
    # Config 11 (PR-8 topology tentpole): two_tier_matrix — planned
    # hierarchical schedules on the forced (2x2) CPU mesh (dp=2 slow-
    # fabric groups x ici=2 fast chips). Per plan: fenced measured
    # ms/step through the SAME probe runner `--auto tune` uses, the
    # two-tier comm model's predicted step time + PER-TIER predicted
    # wire bytes vs the executed program's own byte accounting
    # (measured_msg_bytes / runtime encode stats), and the per-plan
    # aggregation-operator bit-parity assert against the canonical
    # unfused decode-order oracle (topology.execute.two_level_mean_host)
    # — the invariant that makes every plan trajectory-safe. Also runs a
    # mini `tune()` with dcn_ways=2 so the row carries a probed decision
    # artifact naming hierarchical candidates. Semantics + model-honesty
    # evidence, not a chip-speed claim (CPU "fabric" has no tiers; the
    # step-time calibration field says how far the model is). Baseline
    # "none"; fast mode keeps two plans and a two-plan tune space.
    11: dict(metric="two_tier_matrix", kind="twotier", batch=8, n_dev=4,
             ways=4, dcn_ways=2, force_cpu_mesh=True),
    # Config 12 (PR-10 stream-encode tentpole): stream_encode_exposure —
    # the backward-interleaved layer-streamed encode on the forced 4-dev
    # CPU mesh. Per-phase encode exposed-vs-hidden ms: the monolithic
    # encode program vs the per-bucket streamed one, with the pipeline
    # accounting comm_model.stream_exposed_encode_s states (only the
    # last bucket's tail stays on the critical path), full fenced step
    # times for --stream-encode off vs on (ring — the mode whose first
    # hops also pipeline), and the in-row bit-parity asserts: streamed
    # payloads == monolithic payloads and the streamed step's params ==
    # the off step's, bit for bit (the layout-knob contract). Semantics +
    # schedule micro-compare like configs 8-9, not a chip-speed claim;
    # headline TPU rows stay measurement_valid: false per ROADMAP — this
    # CPU-mesh evidence is the bar. Baseline "none".
    12: dict(metric="stream_encode_exposure", kind="streamenc",
             network="lenet", batch=16, n_dev=4, ways=4,
             stream_bucket_bytes=1 << 18, force_cpu_mesh=True),
    # Config 13 (PR-12 sparse tentpole): sparse_vs_dense_wire — the
    # per-layer hybrid sparse-row exchange on the power-law embedding
    # workload, forced 4-device CPU mesh. Per-layer wire bytes of the
    # hybrid plan vs the comm model's per-leaf pricing with an in-row
    # match gate (the executed step's own msg_bytes must equal the
    # plan's leaf-budget sum EXACTLY — both are static accounting over
    # the same per-leaf formula), the hybrid-vs-all-dense bit-parity
    # assert under gather (the lossless-row contract at trajectory
    # level; the row codec's overflow counter gated at 0), and fenced
    # measured ms/step for both modes plus the measured wire-bytes
    # reduction (the headline number: rows vs dense on a Zipf batch).
    # Semantics + byte-honesty evidence like configs 8-12, not a
    # chip-speed claim. Baseline "none".
    13: dict(metric="sparse_vs_dense_wire", kind="sparsewire", batch=32,
             n_dev=4, ways=4, emb_rows=4096, emb_dim=16, zipf_slots=8,
             force_cpu_mesh=True),
    # Config 14 (fabric-observatory tentpole): fabric_probe_calibration —
    # the measured-fabric loop end to end on the forced 4-device CPU
    # mesh (dcn_ways=2 so BOTH tiers land). Three gates in one row: (1)
    # the probe runs and leaves a COMPLETE fabric_probe.json (per-tier
    # bandwidth + per-hop latency, fenced ppermute/all_gather ladders);
    # (2) the measured-vs-preset ratio is recorded per tier (on CPU the
    # "fabric" is host memcpy — the ratio is honesty bookkeeping, not a
    # chip claim); (3) the PRICING-ONLY contract: a `--fabric measured`
    # run and a `--fabric ici` run with identical resolved knobs train
    # BIT-IDENTICAL (in-row parity assert gating validity — the startup
    # probe must not perturb the trajectory, the PR-6 probe-isolation
    # precedent). Semantics + model-honesty evidence like configs 8-13,
    # not a chip-speed claim. Baseline "none".
    14: dict(metric="fabric_probe_calibration", kind="fabricprobe",
             network="lenet", batch=8, n_dev=4, ways=4, dcn_ways=2,
             force_cpu_mesh=True),
    # Config 15 (PR-14 mesh tentpole): sharded_update_memory — the
    # cross-replica sharded weight update (Xu et al. 2004.13336) vs
    # zero1 vs replicated on the forced 4-device CPU mesh. Per
    # partition: MEASURED per-chip persistent state bytes (params/master
    # + optimizer buffers summed over chip 0's actual device shards —
    # the paper's memory claim read off the buffers, not asserted) and
    # fenced ms/step through the same scalar-fetch fence as configs
    # 8-13, with the in-row BIT-PARITY gate: all three partitions train
    # the identical trajectory (canonical decode order, qsgd gather), so
    # the memory rows describe the same program family, not three
    # different runs. Semantics + memory-honesty evidence, not a
    # chip-speed claim; headline TPU rows stay measurement_valid: false
    # per ROADMAP. Baseline "none".
    15: dict(metric="sharded_update_memory", kind="shardedupd",
             network="lenet", batch=16, n_dev=4, ways=4,
             force_cpu_mesh=True),
    # Config 16 (PR-15 adaptive-budget tentpole): adaptive_budget_pareto
    # — ATOMO's variance-minimizing byte allocation (1806.04090) vs the
    # uniform fixed-rank budget at EQUAL total wire bytes, on the forced
    # 4-device CPU mesh over the power-law embedding workload (the
    # spectra-heterogeneous case where allocation matters; lenet's
    # near-homogeneous spectra make uniform ~optimal already — measured,
    # recorded in the row note). Gates, the configs 8-15 discipline:
    # (1) WIRE-MATCH — the executed step's msg_bytes equals the
    # allocator's predicted per-leaf sum EXACTLY (both static clamped
    # accounting), and the variance allocation's wire never exceeds
    # uniform's; (2) the UNIFORM DEGENERATE IDENTITY — the per-leaf
    # wrapper at uniform ranks lowers to byte-identical HLO and steps to
    # bit-identical params vs the plain codec (--budget-alloc uniform ==
    # today, by construction); (3) PARETO — measured mean estimator
    # variance (the in-graph q_err2 probes, the quantity the allocation
    # provably minimizes) AND seed-ensemble mean loss both <= uniform's
    # at <= uniform wire; (4) the RESUME DRILL — a run rebuilt from the
    # JSON-round-tripped budget_alloc epoch replays bit-exact against
    # the uninterrupted one. Semantics + byte/variance-honesty evidence,
    # not a chip-speed claim. Baseline "none".
    16: dict(metric="adaptive_budget_pareto", kind="adaptivebudget",
             batch=32, n_dev=4, ways=4, emb_rows=1024, emb_dim=16,
             zipf_slots=8, svd_rank=3, force_cpu_mesh=True),
    # Config 17 (PR-16 quorum tentpole): quorum_straggler_absorption —
    # bounded-staleness quorum aggregation vs blocking under ONE chaos-
    # slowed replica (slow@S:R:SEC) on the forced 4-device CPU mesh.
    # Measured fenced ms/step for the blocking step (which pays the
    # straggler's host sleep every exchange, the maybe_sleep_replica
    # discipline the shipped loop uses) vs the quorum step driven by a
    # LIVE QuorumRig (Q=3 of 4, K=1: the slow replica's payload rides
    # the carry one step stale, exposed wait 0) — at EQUAL wire, gated
    # in-row (msg_bytes identical; the quorum knob changes when payloads
    # are consumed, never how many bytes move). Then the REPLAY gate:
    # a second run rebuilt from the recorded arrival_schedule.jsonl via
    # --replay-arrivals semantics must land bit-identical params (the
    # honest-convergence contract: the absorbed straggler trajectory is
    # replayable, not a race). Semantics + schedule micro-compare like
    # configs 8-16, not a chip-speed claim. Baseline "none".
    17: dict(metric="quorum_straggler_absorption", kind="quorum",
             network="lenet", batch=32, n_dev=4, ways=4, slow_ms=60,
             force_cpu_mesh=True),
    # Config 18 (PR-17 controller tentpole): controller_joint_decision —
    # the global controller's JOINT priced decision space (aggregate x
    # topology plan x codec budget x sparse crossover x stream/overlap
    # x superstep) vs each legacy single-decider search run standalone
    # (autopilot-only, budget-only, hybrid-only, topology-only), on the
    # forced 4-device CPU mesh over the power-law embedding workload
    # (the config-16 spectra-heterogeneous case, where every knob has
    # signal). Gates, the configs 8-17 discipline: (1) SUPERSET
    # PRICING — the joint ladder's best predict_step_s is <= every
    # single decider's best (deterministic: the restricted subspaces
    # are subsets of the joint space by construction, checked per
    # decider); (2) NOT-SLOWER — the joint winner's probe-measured
    # ms/step is no slower than the best standalone winner's (same
    # fenced probe harness, stated tolerance for CPU probe noise;
    # trivially equal when both searches pick the same program);
    # (3) PIN BIT-PARITY — the winner program rebuilt from the
    # controller_decision.json knob vector ON DISK steps bit-identical
    # params at identical msg_bytes (equal wire in-row) vs the same
    # knobs passed as pinned literals — the artifact IS the program;
    # (4) the RESUME DRILL — T steps + controller_reusable + rebuild
    # from the re-read artifact + T more steps replays bit-exact
    # against the uninterrupted 2T-step run. Semantics + decision-
    # honesty evidence, not a chip-speed claim. Baseline "none".
    18: dict(metric="controller_joint_decision", kind="controller",
             batch=32, n_dev=4, ways=4, emb_rows=1024, emb_dim=16,
             zipf_slots=8, svd_rank=3, dcn_ways=2, force_cpu_mesh=True),
    # Config 19 (PR-18 model-axes tentpole): lm_compressed_dp_wire — the
    # compressed dp gradient exchange on a MODEL-AXIS layout (dp2 x tp2
    # TransformerLM, the one-mesh-path compile), forced 4-device CPU
    # mesh. The headline: qsgd8 vs dense dp wire at equal loss on the
    # tp-sharded LM — each tp shard exchanges its own gradient slice
    # over dp, so compression composes with tensor parallelism. Gates,
    # the configs 8-18 discipline: (1) BYTE-MATCH — the executed step's
    # per-shard msg_bytes equals the comm model's per-leaf payload sum
    # priced over the tp-LOCAL shard shapes EXACTLY (both static
    # accounting over codec_leaf_payload_bytes); (2) DEGENERACY
    # BIT-PARITY — the scoped full-stack exchange (DpExchange, the path
    # the controller's lm[...] candidates compile to) steps bit-identical
    # params at identical msg_bytes vs the legacy compressed_dp_update
    # tail (exchange=None) — the tentpole's "legacy builders reproduced
    # as degenerate points" contract, asserted in-row on the real mesh;
    # (3) WIRE REDUCTION — compressed dp bytes strictly below dense;
    # (4) the SEED ENSEMBLE — mean final loss under qsgd8 no worse than
    # dense within the stated tolerance, seeds x steps recorded per row.
    # Semantics + byte-honesty evidence like configs 8-18, not a
    # chip-speed claim. Baseline "none".
    19: dict(metric="lm_compressed_dp_wire", kind="lmwire",
             width=32, depth=2, num_heads=4, vocab=64, seq=16, batch=8,
             n_dev=4, tp=2, ways=2, force_cpu_mesh=True),
    # Config 20 (PR-19 delayed-overlap tentpole): lm_delayed_overlap —
    # the stale-by-one compressed dp exchange on a MODEL-AXIS layout
    # (dp2 x pp2 TransformerLM: the layout whose drain-tick bubble the
    # pricing credits as overlap headroom), forced 4-device CPU mesh.
    # The headline: delayed vs blocking fenced ms/step at EQUAL wire —
    # the exchange+decode chain leaves the critical path, the bytes do
    # not change. Gates, the configs 8-19 discipline: (1) OFF-MODE HLO
    # BYTE IDENTITY — DpExchange(overlap="off") lowers to byte-identical
    # HLO vs the overlap-less DpExchange (the carry threading cost
    # nothing when off); (2) ORACLE BIT-PARITY — the fused delayed
    # program steps bit-identical params AND carry payload vs the
    # host-driven two-program produce/apply oracle (oracle_parts=True)
    # running the same stale-by-one schedule (the replicated family's
    # _oracle_parts drill, generalized — the replicated loop itself is
    # CV-only and cannot host the LM, so the oracle IS the schedule
    # contract); (3) EQUAL WIRE — delayed msg_bytes == blocking
    # msg_bytes, same codec, same payload; (4) the RESUME DRILL — T
    # steps + save_checkpoint (the carry is a sharded leaf of the
    # checkpointed DelayedState) + fresh rebuild + load + place + T more
    # steps replays bit-exact (params and carry) against the
    # uninterrupted 2T-step run. Semantics + schedule-honesty evidence
    # like configs 8-19, not a chip-speed claim (CPU dispatch cannot
    # show the overlap win; overlap_report's modelled numbers ride in
    # the row, bubble_hidden_ms included). Baseline "none".
    20: dict(metric="lm_delayed_overlap", kind="lmdelayed",
             width=32, depth=2, num_heads=2, vocab=64, seq=16, batch=8,
             n_dev=4, pp=2, ways=2, microbatches=2, force_cpu_mesh=True,
             # the resume drill compares TWO executables of the SAME HLO
             # (the uninterrupted program vs the restarted rebuild); this
             # backend's persistent-cache round-trip is not bit-faithful
             # (the warm-cache parity hazard tests/conftest.py records),
             # so the child runs with JAX_ENABLE_COMPILATION_CACHE=false
             no_compile_cache=True),
    # Config 21 (PR-20 fleet tentpole): fleet_control_plane — the host-
    # level control plane drilled with REAL processes, not virtual
    # devices. Two gates, both in-row: (1) the 2-PROCESS DRILL — two
    # fleet.launcher processes form a fleet over one shared train_dir,
    # partition@ cuts host 1 off the lease store, the leader's transition
    # function shrinks around the stale lease, heal re-admits it
    # (epoch 0 -> 1 -> 2), and `report --fleet --strict` over the
    # resulting artifacts must exit 0 (every host's epochs consistent
    # with membership.json, every lease gap explained by a recorded
    # incident) — the drill is gated on the report's own checks, not on
    # ad-hoc assertions; (2) the RESUME DRILL — a live in-process die@
    # shrink (the zero-downtime reshard primary path: params + momentum
    # re-sliced, NO rc=29 re-exec) followed by kill@ -> supervisor
    # restart -> resume mid-epoch replays leaf-wise BIT-exact
    # checkpoints against the uninterrupted live run (the supervisor
    # re-derives --n-devices from membership.json because the live
    # reshape advanced the epoch without exiting). `value` is the
    # 2-process drill's wall seconds. Semantics + control-plane-honesty
    # evidence like configs 8-20, not a chip-speed claim: a CPU drill (its
    # processes force the CPU platform; several JAX processes cannot
    # share a chip and nothing assigns chips between them). Baseline
    # "none". no_compile_cache: the resume drill compares executables
    # across process generations (the same warm-cache parity hazard as
    # config 20).
    21: dict(metric="fleet_control_plane", kind="fleet",
             n_hosts=2, rounds=400, period_s=0.05, patience=4,
             stop_epoch=2, n_dev=4, force_cpu_mesh=True,
             no_compile_cache=True),
}

# Peak dense matmul throughput per chip (bf16 MXU passes — what XLA uses for
# f32 convs/matmuls by default on TPU), for the MFU denominator.
_PEAK_TFLOPS = [
    ("v6", 918.0), ("v5p", 459.0), ("v5 lite", 197.0), ("v5e", 197.0),
    ("v5litepod", 197.0), ("v4", 275.0), ("v3", 123.0), ("v2", 45.0),
]


def _peak_tflops(device_kind: str):
    kind = device_kind.lower()
    for tag, tf in _PEAK_TFLOPS:
        if tag in kind:
            return tf
    return None


# --------------------------------------------------------------------- child


def _env_int(name: str, default: int) -> int:
    """``int(os.environ[name])`` with a logged fallback: a typo in the
    caller's env must degrade to the default and still produce a bench
    row, never crash the ladder."""
    raw = os.environ.get(name)
    if raw is None or raw == "":
        return default
    try:
        return int(raw)
    except ValueError:
        print(
            f"bench: ignoring {name}={raw!r} (not an int); using {default}",
            file=sys.stderr, flush=True,
        )
        return default


def _env_float(name: str, default: float) -> float:
    """Float twin of :func:`_env_int` (same fallback-not-crash contract)."""
    raw = os.environ.get(name)
    if raw is None or raw == "":
        return default
    try:
        return float(raw)
    except ValueError:
        print(
            f"bench: ignoring {name}={raw!r} (not a number); using {default}",
            file=sys.stderr, flush=True,
        )
        return default


def _mark_invalid(row: dict, reason: str) -> None:
    """Fail a bench row, APPENDING to (never overwriting) earlier reasons
    (VERDICT r2 weak #2 discipline, shared by every invalidation site)."""
    row["measurement_valid"] = False
    prior = row.get("invalid_reason")
    row["invalid_reason"] = f"{prior}; {reason}" if prior else reason


def _flops_per_step(step_fn, *args):
    """XLA's own FLOP estimate for the compiled step program."""
    try:
        compiled = step_fn.lower(*args).compile()
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0]
        flops = float(ca.get("flops", 0.0))
        return flops if flops > 0 else None
    except Exception:
        return None


def measure_lm(cfg: dict) -> dict:
    """Config-6 measurement: single-chip TransformerLM step (fwd + bwd +
    encode + decode + update in one XLA program via parallel.lm's step on
    a 1-device mesh), scan-fenced exactly like the CV path."""
    import jax
    import jax.numpy as jnp

    from atomo_tpu.codecs import get_codec
    from atomo_tpu.models.transformer import TransformerLM
    from atomo_tpu.parallel.lm import make_lm_train_step, shard_tokens
    from atomo_tpu.parallel.mesh import make_mesh
    from atomo_tpu.parallel.replicated import replicate_state
    from atomo_tpu.training import create_state, make_optimizer

    lm_cfg = dict(
        vocab_size=cfg["vocab"], max_len=cfg["seq"], width=cfg["width"],
        depth=cfg["depth"], num_heads=cfg["num_heads"],
    )
    opt = make_optimizer("sgd", lr=0.01, momentum=0.9)
    mesh = make_mesh(1, axes=(("dp", 1), ("sp", 1)))
    key = jax.random.PRNGKey(0)
    sample = jnp.zeros((1, cfg["seq"]), jnp.int32)
    state0 = create_state(TransformerLM(**lm_cfg), opt, key, sample)
    codec = get_codec(cfg["code"], svd_rank=cfg["rank"], quantization_level=4)
    compute_dtype = jnp.bfloat16 if cfg.get("bf16") else None
    tokens = shard_tokens(
        mesh,
        jax.random.randint(
            jax.random.PRNGKey(1), (cfg["batch"], cfg["seq"]), 0,
            cfg["vocab"], dtype=jnp.int32,
        ),
    )

    def timed_lm(step_fn, st):
        """Same discipline as the CV `timed`: scan the steps under one
        dispatch, fence with a scalar fetch, best-of-3."""

        @jax.jit
        def multi(s0, k, toks):
            def body(s, _):
                s, m = step_fn(s, k, toks)
                return s, m["loss"]

            s_out, losses = jax.lax.scan(body, s0, None, length=STEPS)
            return s_out, losses[-1]

        for _ in range(WARMUP):
            st, m = step_fn(st, key, tokens)
        float(m["loss"])
        # dispatch loop (one dispatch per step, scalar-fenced at the end):
        # device time plus per-dispatch host cost, emitted for
        # transparency like the CV path's dispatch_ms_per_step
        t0 = time.perf_counter()
        for _ in range(STEPS):
            st, m = step_fn(st, key, tokens)
        float(m["loss"])
        disp_dt = (time.perf_counter() - t0) / STEPS
        st, last = multi(st, key, tokens)
        float(last)
        dt, sync = float("inf"), float("nan")
        for _ in range(REPS):
            t0 = time.perf_counter()
            st, last = multi(st, key, tokens)
            sync = float(last)
            dt = min(dt, (time.perf_counter() - t0) / STEPS)
        return dt, disp_dt, st, m, sync

    def _fresh(s):
        # deep copy: the step donates its state, and on CPU device_put can
        # alias state0's buffers — a donated alias would delete them out
        # from under the dense_compare's second replicate_state
        return jax.tree_util.tree_map(jnp.array, s)

    step = make_lm_train_step(
        lm_cfg, opt, mesh, codec, compute_dtype=compute_dtype
    )
    state = replicate_state(mesh, _fresh(state0))
    flops = _flops_per_step(step, state, key, tokens)
    dt, disp_dt, state, metrics, sync = timed_lm(step, state)

    dense = int(metrics["dense_bytes"]) if metrics else 0
    msg = int(metrics["msg_bytes"]) if metrics else 1
    dev = jax.devices()[0]
    peak = _peak_tflops(dev.device_kind) if dev.platform == "tpu" else None
    mfu = (flops / dt / (peak * 1e12)) if (flops and peak) else None
    tokens_per_step = cfg["batch"] * cfg["seq"]

    valid, invalid_reason = True, None
    if not math.isfinite(sync):
        valid, invalid_reason = False, f"sync scalar not finite: {sync}"
    elif mfu is not None and not (0.0 < mfu < 1.0):
        valid, invalid_reason = False, f"mfu {mfu:.3f} outside (0, 1)"

    out = dict(
        metric=cfg["metric"],
        value=round(dt * 1e3, 3),
        unit="ms/step",
        config=dict(
            kind="lm", **lm_cfg, batch=cfg["batch"], code=cfg["code"],
            rank=cfg["rank"], bf16=bool(cfg.get("bf16")), warmup=WARMUP,
            steps=STEPS, codec_defaults=repr(codec),
        ),
        byte_reduction=round(dense / max(msg, 1), 2),
        mfu=round(mfu, 4) if mfu is not None else None,
        flops_per_step=flops,
        peak_tflops=peak,
        tokens_per_step=tokens_per_step,
        tokens_per_sec=round(tokens_per_step / dt, 1),
        platform=dev.platform,
        device=dev.device_kind,
        ways=cfg.get("ways", 1),
        dispatch_ms_per_step=round(disp_dt * 1e3, 3),
        chips_measured=1,
        measurement_valid=valid,
        invalid_reason=invalid_reason,
        timing="scan-fenced",
    )
    if cfg.get("dense_compare"):
        dense_step = make_lm_train_step(
            lm_cfg, opt, mesh, None, compute_dtype=compute_dtype
        )
        ddt, _, _, _, dsync = timed_lm(
            dense_step, replicate_state(mesh, _fresh(state0))
        )
        out["dense_ms_per_step"] = round(ddt * 1e3, 3)
        if not math.isfinite(dsync):
            _mark_invalid(out, f"dense sync scalar not finite: {dsync}")
        else:
            from atomo_tpu.utils.comm_model import crossover_report

            out["comm_model"] = crossover_report(
                dense_bytes=dense, payload_bytes=msg,
                dense_step_s=ddt, svd_step_s=dt,
            )
    return out


def measure_loop(cfg: dict) -> dict:
    """Config-7: the SHIPPED train_loop timed end-to-end at --superstep 1
    vs K, from its own log-line timestamps (the steady tail; the compiling
    head is discarded). Includes everything the scan-fenced rows exclude:
    per-step host dispatch, data feed, metric fetch, log formatting. The
    ratio ``dispatch_amortization`` is the superstep tentpole's win; it is
    near 1 where dispatch is cheap against the step and grows with the
    per-dispatch host cost."""
    import jax
    import numpy as np

    from atomo_tpu.data import SPECS, BatchIterator, synthetic_dataset
    from atomo_tpu.models import get_model
    from atomo_tpu.training import make_optimizer, train_loop

    fast = os.environ.get("ATOMO_BENCH_FAST") == "1"
    k = int(cfg["superstep"])
    warm_blocks, steady_blocks = (1, 2) if fast else (2, 8)
    n_steps = (warm_blocks + steady_blocks) * k  # same step count for both

    def timed_loop(loop_call, superstep: int) -> float:
        """Run ``loop_call(model, opt, it, superstep, log_fn)`` — one of
        the two shipped loops — and return median steady-tail ms/step from
        its Worker-line timestamps. ONE copy of the timing protocol so the
        single-host and distributed amortization numbers stay comparable."""
        model = get_model(cfg["network"], 10)
        opt = make_optimizer("sgd", lr=0.01, momentum=0.9)
        ds = synthetic_dataset(SPECS[cfg["dataset"]], True, size=cfg["batch"] * 2)
        it = BatchIterator(ds, cfg["batch"], seed=0)
        stamps = []

        def log(line, _t=time.perf_counter):
            if line.startswith("Worker:"):
                stamps.append(_t())

        loop_call(model, opt, it, superstep, log)
        if len(stamps) < 3:
            return float("nan")
        deltas = np.diff(np.asarray(stamps))
        # steady tail only: the head is dominated by jit compilation
        tail = deltas[len(deltas) // 2 :]
        return float(np.median(tail)) / superstep * 1e3

    def single_host(model, opt, it, superstep, log):
        train_loop(
            model, opt, it, max_steps=n_steps, log_every=superstep,
            log_fn=log, superstep=superstep, eval_freq=0,
        )

    ms_k1 = timed_loop(single_host, 1)
    ms_k = timed_loop(single_host, k)
    dev = jax.devices()[0]
    valid = (
        math.isfinite(ms_k1) and math.isfinite(ms_k) and ms_k1 > 0 and ms_k > 0
    )
    out = dict(
        metric=cfg["metric"],
        value=round(ms_k, 3) if math.isfinite(ms_k) else None,
        unit="ms/step",
        config=dict(
            kind="loop", network=cfg["network"], dataset=cfg["dataset"],
            batch=cfg["batch"], superstep=k, steps=n_steps,
            warm_blocks=warm_blocks,
        ),
        loop_k1_ms_per_step=round(ms_k1, 3) if math.isfinite(ms_k1) else None,
        superstep=k,
        dispatch_amortization=round(ms_k1 / ms_k, 2) if valid else None,
        byte_reduction=None,
        mfu=None,
        flops_per_step=None,
        peak_tflops=None,
        platform=dev.platform,
        device=dev.device_kind,
        ways=cfg.get("ways", 1),
        chips_measured=1,
        measurement_valid=valid,
        invalid_reason=None if valid else "loop timing produced no finite ms/step",
        timing="shipped-loop-wallclock",
    )
    # the distributed loop, same protocol, when a mesh is available (the
    # single local chip cannot form one; fast mode skips the extra compiles)
    if len(jax.devices()) >= 2 and not fast:
        from atomo_tpu.codecs import QsgdCodec
        from atomo_tpu.parallel import distributed_train_loop, make_mesh

        mesh = make_mesh(2)

        def distributed(model, opt, it, superstep, log):
            distributed_train_loop(
                model, opt, mesh, it, max_steps=n_steps,
                codec=QsgdCodec(bits=4, bucket_size=512), aggregate="gather",
                log_every=superstep, log_fn=log, superstep=superstep,
            )

        d1, dk = timed_loop(distributed, 1), timed_loop(distributed, k)
        out["dist_loop_k1_ms_per_step"] = (
            round(d1, 3) if math.isfinite(d1) else None
        )
        out["dist_loop_ms_per_step"] = round(dk, 3) if math.isfinite(dk) else None
        if math.isfinite(d1) and math.isfinite(dk) and dk > 0:
            out["dist_dispatch_amortization"] = round(d1 / dk, 2)
    else:
        out["dist_loop_skipped"] = (
            "fast mode" if fast else "single local device: no mesh to form"
        )
    return out


def measure_ring_compare(cfg: dict) -> dict:
    """Config-8: ring vs gather aggregation on a multi-device mesh.

    Times the full distributed step in both modes (dispatch-loop, scalar-
    fenced) plus the SEPARATELY-JITTED phase programs — encode, gather's
    exchange (all_gather) and decode (decode_mean), and ring's fused
    exchange+decode rotation (one program BY DESIGN: the overlap is the
    tentpole; a host-visible boundary between them would un-fuse it) — and
    asserts the aggregation-operator bit-parity contract in-row
    (tests/test_ring_aggregate.py is the oracle; this row is the per-round
    evidence the artifact carries)."""
    import numpy as np

    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from atomo_tpu.codecs import QsgdCodec, decode_mean_tree, encode_tree
    from atomo_tpu.models import get_model
    from atomo_tpu.parallel import (
        make_distributed_train_step,
        make_mesh,
        replicate_state,
        shard_batch,
    )
    from atomo_tpu.parallel.replicated import _ring_stream_mean
    from atomo_tpu.training import create_state, make_optimizer

    dev = jax.devices()[0]
    n_dev = min(int(cfg.get("n_dev", 4)), len(jax.devices()))
    base = dict(
        metric=cfg["metric"], unit="ms/step", value=None,
        byte_reduction=None, mfu=None, flops_per_step=None,
        peak_tflops=None, platform=dev.platform, device=dev.device_kind,
        ways=n_dev, chips_measured=n_dev,
        timing="dispatch-loop-scalar-fenced",
        config=dict(kind="ringcmp", network=cfg["network"],
                    batch=cfg["batch"], n_dev=n_dev, code="qsgd-4bit"),
        note=("semantics + dispatch + phase micro-compare on a "
              f"{n_dev}-device {dev.platform} mesh; not a chip-speed row"),
    )
    if n_dev < 2:
        base.update(measurement_valid=False,
                    invalid_reason="single device: no mesh to compare on")
        return base

    mesh = make_mesh(n_dev)
    model = get_model(cfg["network"], 10)
    opt = make_optimizer("sgd", lr=0.01, momentum=0.9)
    rng = jax.random.PRNGKey(0)
    images = jax.random.uniform(rng, (cfg["batch"], 28, 28, 1), jnp.float32)
    labels = jax.random.randint(rng, (cfg["batch"],), 0, 10)
    state0 = create_state(model, opt, rng, images)
    codec = QsgdCodec(bits=4, bucket_size=512)
    key = jax.random.PRNGKey(1)
    si, sl = shard_batch(mesh, images, labels)
    # rep-count override honored ONLY in fast mode — same env discipline
    # as child_main's STEPS/WARMUP/REPS guard (a stray var must not
    # silently change the normal protocol)
    reps = 10
    if os.environ.get("ATOMO_BENCH_FAST") == "1":
        reps = _env_int("ATOMO_BENCH_STEPS", reps)

    from atomo_tpu.utils.tracing import fence_tree as fence

    def timed_calls(fn, *args):
        out = fn(*args)
        s = fence(out)  # compile + warm
        t0 = time.perf_counter()
        for _ in range(reps):
            out = fn(*args)
        s = fence(out)
        dt = (time.perf_counter() - t0) / reps
        if not math.isfinite(s):
            raise RuntimeError("fence scalar not finite")
        return dt, out

    out = dict(base, measurement_valid=True, invalid_reason=None)
    try:
        # --- full steps, both modes (fresh deep-copied states: donation)
        def fresh():
            return replicate_state(
                mesh, jax.tree_util.tree_map(jnp.array, state0)
            )

        step_times = {}
        stepped = {}
        for mode in ("gather", "ring"):
            step = make_distributed_train_step(
                model, opt, mesh, codec, aggregate=mode
            )
            st = fresh()
            for _ in range(3):  # warm: compile + settle the program
                st, m = step(st, key, si, sl)
                if not math.isfinite(float(m["loss"])):
                    raise RuntimeError(f"{mode} loss not finite")
            # dispatch loop over the warm program
            t0 = time.perf_counter()
            for _ in range(reps):
                st, m = step(st, key, si, sl)
            float(m["loss"])
            step_times[mode] = (time.perf_counter() - t0) / reps
            stepped[mode] = jax.device_get(st)
        out["value"] = round(step_times["ring"] * 1e3, 3)
        out["gather_ms_per_step"] = round(step_times["gather"] * 1e3, 3)
        out["ring_vs_gather_step_ratio"] = round(
            step_times["gather"] / step_times["ring"], 3
        )
        out["step_param_maxdiff"] = float(max(
            np.max(np.abs(np.asarray(a) - np.asarray(b)))
            for a, b in zip(
                jax.tree_util.tree_leaves(stepped["gather"].params),
                jax.tree_util.tree_leaves(stepped["ring"].params),
            )
        ))

        # --- phase programs over a fixed gradient-shaped tree
        grads = jax.tree_util.tree_map(
            lambda a: jax.random.normal(
                jax.random.PRNGKey(7), a.shape, jnp.float32
            ),
            jax.device_get(state0).params,
        )

        def sm(fn, in_specs, out_specs):
            return jax.jit(jax.shard_map(
                fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                check_vma=False,
            ))

        def enc(g):
            my = jax.lax.axis_index("dp")
            p, _ = encode_tree(codec, jax.random.fold_in(key, my), g)
            return jax.tree_util.tree_map(lambda a: a[None], p)

        enc_fn = sm(enc, (P(),), P("dp"))
        dt_enc, payloads_x = timed_calls(enc_fn, grads)
        out["encode_ms"] = round(dt_enc * 1e3, 3)

        def gx(px):
            local = jax.tree_util.tree_map(lambda a: a[0], px)
            return jax.lax.all_gather(local, "dp")

        gx_fn = sm(gx, (P("dp"),), P())
        dt_gx, gathered = timed_calls(gx_fn, payloads_x)
        out["gather_exchange_ms"] = round(dt_gx * 1e3, 3)

        dec_fn = sm(
            lambda gth: decode_mean_tree(codec, gth, grads, n_dev),
            (P(),), P(),
        )
        dt_dec, mean_g = timed_calls(dec_fn, gathered)
        out["gather_decode_ms"] = round(dt_dec * 1e3, 3)

        def ring_exdec(px):
            my = jax.lax.axis_index("dp")
            local = jax.tree_util.tree_map(lambda a: a[0], px)
            # bucket_size matches the full step's default packing layout,
            # so the phase timing decomposes the program the step runs
            mean, _ = _ring_stream_mean(
                codec, local, grads, axis="dp", n_dev=n_dev, my=my,
                n_contrib=n_dev, bucket_size=65536,
            )
            return mean

        ring_fn = sm(ring_exdec, (P("dp"),), P())
        dt_ring, mean_r = timed_calls(ring_fn, payloads_x)
        out["ring_exchange_decode_ms"] = round(dt_ring * 1e3, 3)
        out["aggregation_bit_parity"] = bool(all(
            np.array_equal(np.asarray(a), np.asarray(b))
            for a, b in zip(
                jax.tree_util.tree_leaves(jax.device_get(mean_g)),
                jax.tree_util.tree_leaves(jax.device_get(mean_r)),
            )
        ))
        if not out["aggregation_bit_parity"]:
            _mark_invalid(
                out,
                "ring aggregation operator is NOT bit-identical to "
                "gather's decode-mean (the PR-3 contract)",
            )
    except Exception as exc:  # noqa: BLE001 — a failed compare is a failed row
        _mark_invalid(out, f"ring compare failed: {str(exc)[:200]}")
    return out


def measure_overlap_compare(cfg: dict) -> dict:
    """Config-9: ``--overlap delayed`` vs blocking on a multi-device mesh.

    Per codec: the fenced full-step time of the blocking (gather) step and
    the delayed step, best-of-REPS dispatch loops. Plus the per-phase
    compute / encode / exchange / decode programs (the same split config 8
    times) so the exchange+decode chain the delayed schedule takes off the
    critical path is visible with numbers — comm_model.overlap_* turns
    them into the hidden/exposed ms the row reports. The two-program eager
    oracle is driven in-row for 3 steps and its bit parity with the fused
    delayed program asserted (tests/test_overlap.py is the full oracle;
    this is the per-round evidence). Semantics + schedule micro-compare on
    the forced CPU mesh — not a chip-speed claim."""
    import numpy as np

    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from atomo_tpu.codecs import QsgdCodec, SvdCodec, decode_mean_tree, encode_tree
    from atomo_tpu.models import get_model
    from atomo_tpu.parallel import (
        init_delayed_state,
        make_delayed_oracle_steps,
        make_distributed_train_step,
        make_mesh,
        replicate_state,
        shard_batch,
    )
    from atomo_tpu.parallel.replicated import _zero_carry_host
    from atomo_tpu.training import create_state, make_optimizer
    from atomo_tpu.utils.comm_model import (
        overlap_exposed_comm_s,
        overlap_hidden_comm_s,
    )
    from atomo_tpu.utils.tracing import fence_tree as fence

    fast = os.environ.get("ATOMO_BENCH_FAST") == "1"
    dev = jax.devices()[0]
    n_dev = min(int(cfg.get("n_dev", 4)), len(jax.devices()))
    base = dict(
        metric=cfg["metric"], unit="ms/step", value=None,
        byte_reduction=None, mfu=None, flops_per_step=None,
        peak_tflops=None, platform=dev.platform, device=dev.device_kind,
        ways=n_dev, chips_measured=n_dev,
        timing="dispatch-loop-scalar-fenced",
        config=dict(kind="overlapcmp", network=cfg["network"],
                    batch=cfg["batch"], n_dev=n_dev),
        note=("semantics + schedule micro-compare of --overlap delayed vs "
              f"blocking on a {n_dev}-device {dev.platform} mesh; not a "
              "chip-speed row"),
    )
    if n_dev < 2:
        base.update(measurement_valid=False,
                    invalid_reason="single device: no exchange to overlap")
        return base

    mesh = make_mesh(n_dev)
    model = get_model(cfg["network"], 10)
    opt = make_optimizer("sgd", lr=0.01, momentum=0.9)
    rng = jax.random.PRNGKey(0)
    images = jax.random.uniform(rng, (cfg["batch"], 28, 28, 1), jnp.float32)
    labels = jax.random.randint(rng, (cfg["batch"],), 0, 10)
    state0 = create_state(model, opt, rng, images)
    host0 = jax.device_get(state0)
    key = jax.random.PRNGKey(1)
    si, sl = shard_batch(mesh, images, labels)
    reps = 20
    if fast:
        reps = _env_int("ATOMO_BENCH_STEPS", reps)
    best_of = 1 if fast else 3
    # qsgd 8-bit at this batch is the measured operating point where the
    # exchange+decode chain is a visible slice of the step; svd rank 2 is
    # the factor-payload family ("at least one compressed codec" evidence
    # wants two shots). Fast mode keeps only the first.
    codecs = {"qsgd8": QsgdCodec(bits=8, bucket_size=512)}
    if not fast:
        codecs["svd2"] = SvdCodec(rank=2)

    def fresh_train():
        return replicate_state(
            mesh, jax.tree_util.tree_map(jnp.asarray, host0)
        )

    out = dict(base, measurement_valid=True, invalid_reason=None)
    try:
        per_codec = {}
        delayed_steps = {}  # reused by the oracle section (jit caches by
        # function identity — rebuilding the same program re-traces it)
        for name, codec in codecs.items():
            blocking = make_distributed_train_step(
                model, opt, mesh, codec, aggregate="gather"
            )
            delayed = make_distributed_train_step(
                model, opt, mesh, codec, aggregate="gather", overlap="delayed"
            )
            delayed_steps[name] = delayed

            def time_fn(step, mk_state):
                st = mk_state()
                m = None
                for _ in range(3):
                    st, m = step(st, key, si, sl)
                s = fence(m["loss"])
                if not math.isfinite(s):
                    raise RuntimeError(f"{name} warmup loss not finite")
                best = float("inf")
                for _ in range(best_of):
                    t0 = time.perf_counter()
                    for _ in range(reps):
                        st, m = step(st, key, si, sl)
                    s = fence(m["loss"])
                    best = min(best, (time.perf_counter() - t0) / reps)
                    if not math.isfinite(s):
                        raise RuntimeError(f"{name} fence scalar not finite")
                return best

            t_block = time_fn(blocking, fresh_train)
            t_delay = time_fn(
                delayed,
                lambda: init_delayed_state(mesh, fresh_train(), codec),
            )
            per_codec[name] = {
                "blocking_ms_per_step": round(t_block * 1e3, 3),
                "delayed_ms_per_step": round(t_delay * 1e3, 3),
                "overlap_speedup": round(t_block / t_delay, 4),
                "overlap_win": bool(t_delay < t_block),
            }
        out["codecs"] = per_codec
        wins = [n for n, r in per_codec.items() if r["overlap_win"]]
        out["overlap_win_codecs"] = wins
        # headline value: the delayed step of the winning codec (first
        # codec when none wins — the row then says so instead of hiding it)
        head = wins[0] if wins else next(iter(per_codec))
        out["value"] = per_codec[head]["delayed_ms_per_step"]
        out["blocking_ms_per_step"] = per_codec[head]["blocking_ms_per_step"]
        out["headline_codec"] = head
        if not wins:
            _mark_invalid(
                out,
                "delayed step not strictly below blocking for any codec "
                "on this run (contended host or overlap-free backend)",
            )

        # --- per-phase evidence (qsgd8): the chain delayed hides is
        # exchange+decode; encode consumes THIS step's gradient and stays
        codec = codecs["qsgd8"]
        grads = jax.tree_util.tree_map(
            lambda a: jax.random.normal(
                jax.random.PRNGKey(7), a.shape, jnp.float32
            ),
            host0.params,
        )

        def sm(fn, in_specs, out_specs):
            return jax.jit(jax.shard_map(
                fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                check_vma=False,
            ))

        def timed_calls(fn, *args):
            o = fn(*args)
            s = fence(o)
            best = float("inf")
            for _ in range(best_of):
                t0 = time.perf_counter()
                for _ in range(reps):
                    o = fn(*args)
                s = fence(o)
                best = min(best, (time.perf_counter() - t0) / reps)
            if not math.isfinite(s):
                raise RuntimeError("phase fence scalar not finite")
            return best, o

        from atomo_tpu.training.trainer import cross_entropy_loss

        def comp(params, stats, im, lb):
            def loss_fn(p):
                variables = {"params": p}
                if jax.tree_util.tree_leaves(stats):
                    variables["batch_stats"] = stats
                out_ = model.apply(
                    variables, im, train=True,
                    rngs={"dropout": jax.random.PRNGKey(0)},
                    mutable=["batch_stats"]
                    if jax.tree_util.tree_leaves(stats) else [],
                )
                return cross_entropy_loss(out_[0], lb)

            g = jax.grad(loss_fn)(params)
            return jax.tree_util.tree_map(lambda a: a[None], g)

        comp_fn = sm(comp, (P(), P(), P("dp"), P("dp")), P("dp"))
        dt_comp, _ = timed_calls(comp_fn, host0.params, host0.batch_stats,
                                 si, sl)

        def enc(g):
            my = jax.lax.axis_index("dp")
            p, _ = encode_tree(codec, jax.random.fold_in(key, my), g)
            return jax.tree_util.tree_map(lambda a: a[None], p)

        enc_fn = sm(enc, (P(),), P("dp"))
        dt_enc, payloads_x = timed_calls(enc_fn, grads)

        def gx(px):
            local = jax.tree_util.tree_map(lambda a: a[0], px)
            return jax.lax.all_gather(local, "dp")

        gx_fn = sm(gx, (P("dp"),), P())
        dt_gx, gathered = timed_calls(gx_fn, payloads_x)

        dec_fn = sm(
            lambda gth: decode_mean_tree(codec, gth, grads, n_dev),
            (P(),), P(),
        )
        dt_dec, _ = timed_calls(dec_fn, gathered)

        chain_s = dt_gx + dt_dec
        out["phases"] = {
            "compute_ms": round(dt_comp * 1e3, 3),
            "encode_ms": round(dt_enc * 1e3, 3),
            "exchange_ms": round(dt_gx * 1e3, 3),
            "decode_ms": round(dt_dec * 1e3, 3),
            "offloadable_chain_ms": round(chain_s * 1e3, 3),
            "hidden_ms": round(
                overlap_hidden_comm_s(chain_s, dt_comp) * 1e3, 3
            ),
            "exposed_ms": round(
                overlap_exposed_comm_s(chain_s, dt_comp) * 1e3, 3
            ),
            "note": ("delayed takes exchange+decode off the critical path "
                     "(hides min(chain, compute)); encode consumes this "
                     "step's gradient and stays on it"),
        }

        # --- two-program eager-oracle bit parity over 3 steps (qsgd8)
        delayed = delayed_steps["qsgd8"]  # the warm program from the loop
        oracle = make_delayed_oracle_steps(
            model, opt, mesh, codec, aggregate="gather"
        )
        d = init_delayed_state(mesh, fresh_train(), codec)
        st = fresh_train()
        carry = _zero_carry_host(codec, host0.params, n_dev)
        px, okx, valid = carry.payload, carry.ok, carry.valid
        parity = True
        for _ in range(3):
            d, _m = delayed(d, key, si, sl)
            npx, nok, stats_x, _pm = oracle["produce"](st, key, si, sl)
            st, _am = oracle["apply"](st, px, okx, valid, stats_x, nok)
            px, okx, valid = npx, nok, jnp.float32(1.0)
            parity &= all(
                np.array_equal(np.asarray(a), np.asarray(b))
                for a, b in zip(
                    jax.tree_util.tree_leaves(jax.device_get(d.train.params)),
                    jax.tree_util.tree_leaves(jax.device_get(st.params)),
                )
            )
        out["overlap_oracle_bit_parity"] = bool(parity)
        if not parity:
            _mark_invalid(
                out,
                "delayed fused program is NOT bit-identical to the "
                "two-program eager oracle (the PR-4 contract)",
            )
    except Exception as exc:  # noqa: BLE001 — a failed compare is a failed row
        _mark_invalid(out, f"overlap compare failed: {str(exc)[:200]}")
    return out


def measure_stream_encode(cfg: dict) -> dict:
    """Config-12: ``--stream-encode`` exposed-encode evidence on the
    forced multi-device CPU mesh.

    Three layers of evidence in one row: (1) the per-phase encode
    programs — monolithic ``encode_tree`` vs the per-layer-bucket
    ``encode_tree_streamed`` — timed with the fence discipline, and the
    exposed-encode ms each schedule leaves on the critical path per the
    comm model's pipeline accounting (monolithic: all of it; streamed:
    the last bucket's tail, ``stream_exposed_encode_s``); (2) fenced
    full-step times for ``--stream-encode`` off vs on under ring
    aggregation (the mode whose first ppermute hops pipeline too);
    (3) the in-row bit-parity asserts that make the knob trajectory-safe:
    streamed payloads are bit-identical to monolithic payloads, and the
    streamed step's params bit-match the off step's after the timed
    dispatch loop. A semantics + schedule micro-compare (configs 8-9
    class), not a chip-speed claim."""
    import numpy as np

    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from atomo_tpu.codecs import (
        QsgdCodec,
        encode_tree,
        encode_tree_streamed,
    )
    from atomo_tpu.models import get_model
    from atomo_tpu.parallel import (
        make_distributed_train_step,
        make_mesh,
        replicate_state,
        shard_batch,
    )
    from atomo_tpu.parallel.common import plan_layer_buckets
    from atomo_tpu.training import create_state, make_optimizer
    from atomo_tpu.training.trainer import cross_entropy_loss
    from atomo_tpu.utils.comm_model import stream_exposed_encode_s
    from atomo_tpu.utils.tracing import fence_tree as fence

    fast = os.environ.get("ATOMO_BENCH_FAST") == "1"
    dev = jax.devices()[0]
    n_dev = min(int(cfg.get("n_dev", 4)), len(jax.devices()))
    sb = int(cfg.get("stream_bucket_bytes", 1 << 18))
    base = dict(
        metric=cfg["metric"], unit="ms/step", value=None,
        byte_reduction=None, mfu=None, flops_per_step=None,
        peak_tflops=None, platform=dev.platform, device=dev.device_kind,
        ways=n_dev, chips_measured=n_dev,
        timing="dispatch-loop-scalar-fenced",
        config=dict(kind="streamenc", network=cfg["network"],
                    batch=cfg["batch"], n_dev=n_dev,
                    stream_bucket_bytes=sb),
        note=("semantics + schedule micro-compare of --stream-encode on "
              f"vs off on a {n_dev}-device {dev.platform} mesh; not a "
              "chip-speed row"),
    )
    if n_dev < 2:
        base.update(measurement_valid=False,
                    invalid_reason="single device: no exchange whose "
                                   "encode is on the critical path")
        return base

    mesh = make_mesh(n_dev)
    model = get_model(cfg["network"], 10)
    opt = make_optimizer("sgd", lr=0.01, momentum=0.9)
    rng = jax.random.PRNGKey(0)
    images = jax.random.uniform(rng, (cfg["batch"], 28, 28, 1), jnp.float32)
    labels = jax.random.randint(rng, (cfg["batch"],), 0, 10)
    state0 = create_state(model, opt, rng, images)
    host0 = jax.device_get(state0)
    key = jax.random.PRNGKey(1)
    si, sl = shard_batch(mesh, images, labels)
    codec = QsgdCodec(bits=8, bucket_size=512)
    reps = 20
    if fast:
        reps = _env_int("ATOMO_BENCH_STEPS", reps)
    best_of = 1 if fast else 3

    def fresh():
        return replicate_state(
            mesh, jax.tree_util.tree_map(jnp.asarray, host0)
        )

    out = dict(base, measurement_valid=True, invalid_reason=None)
    try:
        # --- full steps, ring aggregation, stream off vs on ------------
        step_times = {}
        stepped = {}
        for label, stream in (("off", False), ("stream", True)):
            step = make_distributed_train_step(
                model, opt, mesh, codec, aggregate="ring",
                stream_encode=stream, stream_bucket_bytes=sb,
            )
            st = fresh()
            m = None
            for _ in range(3):
                st, m = step(st, key, si, sl)
            s = fence(m["loss"])
            if not math.isfinite(s):
                raise RuntimeError(f"{label} warmup loss not finite")
            best = float("inf")
            for _ in range(best_of):
                t0 = time.perf_counter()
                for _ in range(reps):
                    st, m = step(st, key, si, sl)
                s = fence(m["loss"])
                best = min(best, (time.perf_counter() - t0) / reps)
                if not math.isfinite(s):
                    raise RuntimeError(f"{label} fence scalar not finite")
            step_times[label] = best
            stepped[label] = jax.device_get(st)
        out["value"] = round(step_times["stream"] * 1e3, 3)
        out["off_ms_per_step"] = round(step_times["off"] * 1e3, 3)
        # config 9's overlap_speedup convention: >1 = streaming is faster
        out["stream_speedup"] = round(
            step_times["off"] / step_times["stream"], 3
        )
        # the layout-knob contract, full-trajectory form: after identical
        # dispatch loops the two programs hold identical bits
        out["step_param_bit_parity"] = bool(all(
            np.array_equal(np.asarray(a), np.asarray(b))
            for a, b in zip(
                jax.tree_util.tree_leaves(stepped["off"].params),
                jax.tree_util.tree_leaves(stepped["stream"].params),
            )
        ))
        if not out["step_param_bit_parity"]:
            _mark_invalid(
                out,
                "streamed step params are NOT bit-identical to the off "
                "step's (the stream-encode layout-knob contract)",
            )

        # --- per-phase encode programs over a fixed gradient tree ------
        grads = jax.tree_util.tree_map(
            lambda a: jax.random.normal(
                jax.random.PRNGKey(7), a.shape, jnp.float32
            ),
            host0.params,
        )
        plan = plan_layer_buckets(grads, sb)
        n_buckets = plan.n_buckets

        def sm(fn, in_specs, out_specs):
            return jax.jit(jax.shard_map(
                fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                check_vma=False,
            ))

        def timed_calls(fn, *args):
            o = fn(*args)
            s = fence(o)
            best = float("inf")
            for _ in range(best_of):
                t0 = time.perf_counter()
                for _ in range(reps):
                    o = fn(*args)
                s = fence(o)
                best = min(best, (time.perf_counter() - t0) / reps)
            if not math.isfinite(s):
                raise RuntimeError("phase fence scalar not finite")
            return best, o

        def comp(params, stats, im, lb):
            def loss_fn(p):
                variables = {"params": p}
                if jax.tree_util.tree_leaves(stats):
                    variables["batch_stats"] = stats
                out_ = model.apply(
                    variables, im, train=True,
                    rngs={"dropout": jax.random.PRNGKey(0)},
                    mutable=["batch_stats"]
                    if jax.tree_util.tree_leaves(stats) else [],
                )
                return cross_entropy_loss(out_[0], lb)

            g = jax.grad(loss_fn)(params)
            return jax.tree_util.tree_map(lambda a: a[None], g)

        comp_fn = sm(comp, (P(), P(), P("dp"), P("dp")), P("dp"))
        dt_comp, _ = timed_calls(comp_fn, host0.params, host0.batch_stats,
                                 si, sl)

        def enc_mono(g):
            my = jax.lax.axis_index("dp")
            p, _ = encode_tree(codec, jax.random.fold_in(key, my), g)
            return jax.tree_util.tree_map(lambda a: a[None], p)

        def enc_stream(g):
            my = jax.lax.axis_index("dp")
            p, _ = encode_tree_streamed(
                codec, jax.random.fold_in(key, my), g, plan
            )
            return jax.tree_util.tree_map(lambda a: a[None], p)

        dt_mono, p_mono = timed_calls(sm(enc_mono, (P(),), P("dp")), grads)
        dt_stream, p_stream = timed_calls(
            sm(enc_stream, (P(),), P("dp")), grads
        )
        out["payload_bit_parity"] = bool(all(
            np.array_equal(np.asarray(a), np.asarray(b))
            for a, b in zip(
                jax.tree_util.tree_leaves(jax.device_get(p_mono)),
                jax.tree_util.tree_leaves(jax.device_get(p_stream)),
            )
        ))
        if not out["payload_bit_parity"]:
            _mark_invalid(
                out,
                "streamed payloads are NOT bit-identical to the "
                "monolithic encode (the global-leaf-key contract)",
            )
        exposed_off = dt_mono  # monolithic: the whole encode is the tail
        exposed_stream = stream_exposed_encode_s(dt_stream, n_buckets)
        out["phases"] = {
            "compute_ms": round(dt_comp * 1e3, 3),
            "encode_monolithic_ms": round(dt_mono * 1e3, 3),
            "encode_streamed_ms": round(dt_stream * 1e3, 3),
            "n_buckets": n_buckets,
            "encode_exposed_off_ms": round(exposed_off * 1e3, 3),
            "encode_exposed_stream_ms": round(exposed_stream * 1e3, 3),
            "encode_hidden_stream_ms": round(
                (dt_stream - exposed_stream) * 1e3, 3
            ),
            "note": ("pipeline accounting: streamed encode's buckets run "
                     "under backprop of the layers feeding the next "
                     "bucket; only the last bucket's tail (~encode/"
                     "n_buckets, uniform model) stays exposed — "
                     "comm_model.stream_exposed_encode_s. HONESTY: the "
                     "exposed/hidden split is MODEL arithmetic over "
                     "measured standalone phase times (it can only fail "
                     "if streaming made encode >= n_buckets x slower); "
                     "the end-to-end MEASURED overlap signal is the "
                     "full-step stream_speedup above"),
        }
        out["exposed_encode_reduced"] = bool(exposed_stream < exposed_off)
        if not out["exposed_encode_reduced"]:
            _mark_invalid(
                out,
                "streamed exposed-encode tail not below the monolithic "
                "exposed encode (single-bucket plan or a degenerate "
                "timing)",
            )
    except Exception as exc:  # noqa: BLE001 — a failed compare is a failed row
        _mark_invalid(out, f"stream-encode compare failed: {str(exc)[:200]}")
    return out


def measure_sparse_wire(cfg: dict) -> dict:
    """Config-13: per-layer hybrid sparse-row exchange evidence on the
    forced multi-device CPU mesh over the power-law embedding workload.

    Three gates in one row (the configs 8-12 discipline): (1) the
    WIRE-MATCH gate — the hybrid step's own ``msg_bytes`` accounting must
    equal the plan's per-leaf sum (``comm_model.leaf_budget_totals`` over
    ``HybridPlan.leaf_budgets``) exactly, so the comm model's +sp pricing
    and the executed program can never drift; (2) the BIT-PARITY gate —
    hybrid-vs-all-dense trajectories bit-identical under gather (the
    lossless row contract at trajectory level), with the row codec's
    overflow counter asserted 0 on real Zipf gradients; (3) fenced
    measured ms/step for both modes + the measured wire reduction (the
    headline: rows vs dense payloads on a power-law batch). A semantics
    + byte-honesty micro-compare, not a chip-speed claim."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    from atomo_tpu.codecs import DenseCodec
    from atomo_tpu.data.zipf import zipf_dataset
    from atomo_tpu.models import EmbeddingTower
    from atomo_tpu.parallel import (
        make_distributed_train_step,
        make_mesh,
        replicate_state,
        shard_batch,
    )
    from atomo_tpu.sparse import plan_for_model
    from atomo_tpu.training import create_state, make_optimizer
    from atomo_tpu.utils.tracing import fence_tree as fence

    fast = os.environ.get("ATOMO_BENCH_FAST") == "1"
    dev = jax.devices()[0]
    n_dev = min(int(cfg.get("n_dev", 4)), len(jax.devices()))
    batch = int(cfg.get("batch", 32))
    slots = int(cfg.get("zipf_slots", 8))
    base = dict(
        metric=cfg["metric"], unit="ms/step", value=None,
        byte_reduction=None, mfu=None, flops_per_step=None,
        peak_tflops=None, platform=dev.platform, device=dev.device_kind,
        ways=n_dev, chips_measured=n_dev,
        timing="dispatch-loop-scalar-fenced",
        config=dict(kind="sparsewire", batch=batch, n_dev=n_dev,
                    emb_rows=int(cfg.get("emb_rows", 4096)),
                    emb_dim=int(cfg.get("emb_dim", 16)),
                    zipf_slots=slots),
        note=(f"per-layer hybrid sparse-row exchange vs all-dense on a "
              f"{n_dev}-device {dev.platform} mesh, power-law embedding "
              "workload; byte-honesty + semantics row, not a chip-speed "
              "claim"),
    )
    if n_dev < 2:
        base.update(measurement_valid=False,
                    invalid_reason="single device: no exchange to save "
                                   "wire on")
        return base

    mesh = make_mesh(n_dev)
    model = EmbeddingTower(
        num_classes=10, rows=int(cfg.get("emb_rows", 4096)),
        dim=int(cfg.get("emb_dim", 16)),
    )
    opt = make_optimizer("sgd", lr=0.01, momentum=0.9)
    ds = zipf_dataset(
        True, rows=int(cfg.get("emb_rows", 4096)), slots=slots,
        size=max(batch * 2, 64), seed=0,
    )
    images = jnp.asarray(ds.images[:batch])
    labels = jnp.asarray(ds.labels[:batch])
    codec = DenseCodec()
    plan = plan_for_model(
        codec, model, ds.images[:batch], ds.labels[:batch],
        batch_per_chip=max(batch // n_dev, 1), slots=slots,
    )
    state0 = create_state(model, opt, jax.random.PRNGKey(0), images)
    host0 = jax.device_get(state0)
    key = jax.random.PRNGKey(1)
    si, sl = shard_batch(mesh, images, labels)
    reps = 20
    if fast:
        reps = _env_int("ATOMO_BENCH_STEPS", reps)
    best_of = 1 if fast else 3

    out = dict(base, measurement_valid=True, invalid_reason=None)
    out["hybrid_plan"] = {
        "n_leaves": plan.n_leaves,
        "sparse_leaves": list(plan.sparse_idxs),
        "per_layer": [
            {
                "name": a.name, "assignment": a.kind,
                "density": round(float(a.density), 6),
                "dense_bytes": int(a.dense_bytes),
                "payload_bytes": int(a.payload_bytes),
                **({"row_budget": int(a.row_budget)}
                   if a.kind == "sparse" else {}),
            }
            for a in plan.assignments
        ],
    }
    try:
        if not plan.any_sparse:
            raise RuntimeError("planner assigned no sparse leaf")
        # --- overflow gate: the lossless budget holds on real Zipf
        # gradients (per-chip shard of the batch) --------------------
        from atomo_tpu.sparse import probe_gradient

        per_chip = max(batch // n_dev, 1)
        max_overflow = 0
        for c in range(n_dev):
            g = probe_gradient(
                model, ds.images[c * per_chip:(c + 1) * per_chip],
                ds.labels[c * per_chip:(c + 1) * per_chip],
            )
            leaves = jax.tree_util.tree_leaves(g)
            for i in plan.sparse_idxs:
                p = plan.row_codec(i).encode(
                    jax.random.PRNGKey(0), jnp.asarray(leaves[i])
                )
                max_overflow = max(max_overflow, int(p.overflow))
        out["row_overflow"] = max_overflow
        if max_overflow:
            _mark_invalid(
                out,
                f"row budget overflowed by {max_overflow} rows — the "
                "lossless bound was violated",
            )

        # --- fenced full steps, hybrid off vs on, gather ------------
        step_times = {}
        stepped = {}
        msg_bytes = {}
        for label, hyb in (("alldense", None), ("hybrid", plan)):
            step = make_distributed_train_step(
                model, opt, mesh, codec, aggregate="gather", hybrid=hyb,
            )
            st = replicate_state(
                mesh, jax.tree_util.tree_map(jnp.asarray, host0)
            )
            m = None
            for _ in range(3):
                st, m = step(st, key, si, sl)
            s = fence(m["loss"])
            if not math.isfinite(s):
                raise RuntimeError(f"{label} warmup loss not finite")
            best = float("inf")
            for _ in range(best_of):
                t0 = time.perf_counter()
                for _ in range(reps):
                    st, m = step(st, key, si, sl)
                s = fence(m["loss"])
                best = min(best, (time.perf_counter() - t0) / reps)
                if not math.isfinite(s):
                    raise RuntimeError(f"{label} fence scalar not finite")
            step_times[label] = best
            stepped[label] = jax.device_get(st)
            msg_bytes[label] = int(
                np.ravel(jax.device_get(m["msg_bytes"]))[-1]
            )
        out["value"] = round(step_times["hybrid"] * 1e3, 3)
        out["alldense_ms_per_step"] = round(
            step_times["alldense"] * 1e3, 3
        )
        out["hybrid_wire_bytes"] = msg_bytes["hybrid"]
        out["alldense_wire_bytes"] = msg_bytes["alldense"]
        out["wire_reduction"] = round(
            msg_bytes["alldense"] / max(msg_bytes["hybrid"], 1), 3
        )
        # gate 1: the executed program's own byte accounting equals the
        # plan's per-leaf sum exactly (both static — no tolerance)
        out["wire_bytes_match"] = bool(
            msg_bytes["hybrid"] == plan.payload_bytes()
        )
        if not out["wire_bytes_match"]:
            _mark_invalid(
                out,
                f"executed msg_bytes {msg_bytes['hybrid']} != plan's "
                f"per-leaf sum {plan.payload_bytes()} — the comm model "
                "and the program disagree about a byte",
            )
        if msg_bytes["hybrid"] >= msg_bytes["alldense"]:
            _mark_invalid(
                out,
                "hybrid wire not below all-dense wire — no measured "
                "reduction on the power-law workload",
            )
        # gate 2: hybrid-vs-all-dense bit parity (gather — the
        # trajectory-level lossless contract; ring's fused-step drift
        # class is documented in parallel.replicated._hybrid_mean)
        out["hybrid_bit_parity"] = bool(all(
            np.array_equal(np.asarray(a), np.asarray(b))
            for a, b in zip(
                jax.tree_util.tree_leaves(stepped["alldense"].params),
                jax.tree_util.tree_leaves(stepped["hybrid"].params),
            )
        ))
        if not out["hybrid_bit_parity"]:
            _mark_invalid(
                out,
                "hybrid step params are NOT bit-identical to the "
                "all-dense step's (the lossless row-exchange contract)",
            )
    except Exception as exc:  # noqa: BLE001 — a failed compare is a failed row
        _mark_invalid(out, f"sparse-wire compare failed: {str(exc)[:200]}")
    return out


def measure_adaptive_budget(cfg: dict) -> dict:
    """Config-16: adaptive variance-budget allocation vs the uniform
    fixed-rank budget at equal total wire bytes (see CONFIGS[16] for the
    full gate contract)."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    from atomo_tpu.budget import (
        allocation_leaf_budgets,
        budgeted_codec,
        latest_epoch,
        measure_spectra,
        new_alloc_doc,
        solve_allocation,
        uniform_ks,
    )
    from atomo_tpu.codecs import SvdCodec
    from atomo_tpu.data.zipf import zipf_dataset
    from atomo_tpu.models import EmbeddingTower
    from atomo_tpu.parallel import (
        make_distributed_train_step,
        make_mesh,
        replicate_state,
        shard_batch,
    )
    from atomo_tpu.sparse.hybrid import probe_gradient
    from atomo_tpu.training import create_state, make_optimizer

    fast = os.environ.get("ATOMO_BENCH_FAST") == "1"
    dev = jax.devices()[0]
    n_dev = min(int(cfg.get("n_dev", 4)), len(jax.devices()))
    batch = int(cfg.get("batch", 32))
    rank = int(cfg.get("svd_rank", 3))
    base = dict(
        metric=cfg["metric"], unit="ms/step", value=None,
        byte_reduction=None, mfu=None, flops_per_step=None,
        peak_tflops=None, platform=dev.platform, device=dev.device_kind,
        ways=n_dev, chips_measured=n_dev,
        timing="dispatch-loop-scalar-fenced",
        config=dict(kind="adaptivebudget", batch=batch, n_dev=n_dev,
                    emb_rows=int(cfg.get("emb_rows", 1024)),
                    emb_dim=int(cfg.get("emb_dim", 16)),
                    zipf_slots=int(cfg.get("zipf_slots", 8)),
                    svd_rank=rank),
        note=(f"ATOMO water-filling byte allocation vs uniform fixed "
              f"rank at equal wire on a {n_dev}-device {dev.platform} "
              "mesh, power-law embedding workload (spectra-heterogeneous"
              " — lenet's near-homogeneous spectra make uniform "
              "~optimal, measured); byte/variance-honesty row, not a "
              "chip-speed claim"),
    )
    if n_dev < 2:
        base.update(measurement_valid=False,
                    invalid_reason="single device: no exchange budget "
                                   "to allocate")
        return base

    mesh = make_mesh(n_dev)
    model = EmbeddingTower(
        num_classes=10, rows=int(cfg.get("emb_rows", 1024)),
        dim=int(cfg.get("emb_dim", 16)),
    )
    opt = make_optimizer("sgd", lr=0.1, momentum=0.5)
    ds = zipf_dataset(
        True, rows=int(cfg.get("emb_rows", 1024)),
        slots=int(cfg.get("zipf_slots", 8)),
        size=max(batch * 8, 256), seed=0,
    )
    codec = SvdCodec(rank=rank)
    out = dict(base, measurement_valid=True, invalid_reason=None)
    try:
        spectra = measure_spectra(
            codec,
            probe_gradient(model, ds.images[:batch], ds.labels[:batch]),
        )
        alloc_u = solve_allocation(codec, spectra, mode="uniform")
        alloc_v = solve_allocation(codec, spectra, mode="variance")
        out["allocation"] = {
            "uniform_ks": [int(k) for k in alloc_u.ks],
            "variance_ks": [int(k) for k in alloc_v.ks],
            "budget_bytes": int(alloc_v.budget_bytes),
            "uniform_payload_bytes": int(alloc_u.payload_bytes),
            "variance_payload_bytes": int(alloc_v.payload_bytes),
            "predicted_variance_uniform": round(
                alloc_u.predicted_variance, 6
            ),
            "predicted_variance_variance": round(
                alloc_v.predicted_variance, 6
            ),
            "per_layer": [
                {"name": l.name, "k_uniform": int(alloc_u.ks[l.index]),
                 "k_variance": int(alloc_v.ks[l.index])}
                for l in spectra
            ],
        }
        if tuple(alloc_v.ks) == tuple(alloc_u.ks):
            _mark_invalid(
                out,
                "the solver returned the uniform point — no adaptive "
                "signal on this workload, nothing to compare",
            )
            return out
        wrapped_u = budgeted_codec(codec, uniform_ks(spectra))
        wrapped_v = budgeted_codec(codec, alloc_v.ks)

        steps_per = 40
        seeds = 2 if fast else 5
        if fast:
            steps_per = max(_env_int("ATOMO_BENCH_STEPS", 10), 4)
        n = len(ds.images)

        def batch_at(i):
            s0 = (i * batch) % (n - batch)
            return shard_batch(
                mesh, jnp.asarray(ds.images[s0:s0 + batch]),
                jnp.asarray(ds.labels[s0:s0 + batch]),
            )

        def run(codec_run, seed, T, step=None, state=None, quality=True):
            if step is None:
                step = make_distributed_train_step(
                    model, opt, mesh, codec_run, aggregate="gather",
                    track_quality=quality,
                )
            st = state if state is not None else replicate_state(
                mesh, create_state(
                    model, opt, jax.random.PRNGKey(seed),
                    jnp.asarray(ds.images[:batch]),
                )
            )
            key = jax.random.PRNGKey(seed + 100)
            losses, q_sum, msg = [], 0.0, None
            for i in range(T):
                si, sl = batch_at(i)
                st, m = step(st, key, si, sl)
                losses.append(float(m["loss"]))
                if quality:
                    q_sum += float(jnp.sum(m["q_err2"]))
                msg = m
            return st, losses, q_sum / max(T, 1), int(
                np.ravel(jax.device_get(msg["msg_bytes"]))[-1]
            ), step

        # --- gate 2: the uniform degenerate identity -----------------
        plain_step = make_distributed_train_step(
            model, opt, mesh, codec, aggregate="gather"
        )
        wrapped_u_step = make_distributed_train_step(
            model, opt, mesh, wrapped_u, aggregate="gather"
        )
        st0 = create_state(
            model, opt, jax.random.PRNGKey(0),
            jnp.asarray(ds.images[:batch]),
        )
        host0 = jax.device_get(st0)
        si0, sl0 = batch_at(0)
        key0 = jax.random.PRNGKey(100)
        h_plain = plain_step.lower(
            replicate_state(
                mesh, jax.tree_util.tree_map(jnp.asarray, host0)
            ), key0, si0, sl0,
        ).as_text()
        h_wrap = wrapped_u_step.lower(
            replicate_state(
                mesh, jax.tree_util.tree_map(jnp.asarray, host0)
            ), key0, si0, sl0,
        ).as_text()
        out["uniform_hlo_identical"] = bool(h_plain == h_wrap)
        if not out["uniform_hlo_identical"]:
            _mark_invalid(
                out,
                "per-leaf wrapper at uniform ranks does NOT lower to "
                "byte-identical HLO vs the plain codec — the "
                "--budget-alloc uniform degenerate-point contract broke",
            )
        sp, _ = plain_step(
            replicate_state(
                mesh, jax.tree_util.tree_map(jnp.asarray, host0)
            ), key0, si0, sl0,
        ), None
        sw, _ = wrapped_u_step(
            replicate_state(
                mesh, jax.tree_util.tree_map(jnp.asarray, host0)
            ), key0, si0, sl0,
        ), None
        out["uniform_bit_parity"] = bool(all(
            np.array_equal(np.asarray(a), np.asarray(b))
            for a, b in zip(
                jax.tree_util.tree_leaves(jax.device_get(sp[0].params)),
                jax.tree_util.tree_leaves(jax.device_get(sw[0].params)),
            )
        ))
        if not out["uniform_bit_parity"]:
            _mark_invalid(
                out,
                "uniform-wrapped step params are NOT bit-identical to "
                "the plain codec step's",
            )

        # --- gates 1 + 3: wire match + the Pareto ensemble -----------
        t0 = time.perf_counter()
        stats = {}
        for lbl, c in (("uniform", wrapped_u), ("variance", wrapped_v)):
            L, Q, wire, step = [], [], None, None
            for s in range(seeds):
                _, losses, q, msg_b, step = run(
                    c, s, steps_per, step=step
                )
                L.append(float(np.mean(losses[-max(steps_per // 4, 2):])))
                Q.append(q)
                wire = msg_b
            stats[lbl] = dict(
                mean_loss=float(np.mean(L)),
                per_seed_loss=[round(x, 6) for x in L],
                mean_q_err2=float(np.mean(Q)),
                wire_bytes=wire,
            )
        out["uniform_row"] = stats["uniform"]
        out["variance_row"] = stats["variance"]
        out["value"] = round(
            (time.perf_counter() - t0) / (2 * seeds * steps_per) * 1e3, 3
        )
        out["wire_bytes_match"] = bool(
            stats["variance"]["wire_bytes"] == alloc_v.payload_bytes
            and stats["uniform"]["wire_bytes"] == alloc_u.payload_bytes
        )
        if not out["wire_bytes_match"]:
            _mark_invalid(
                out,
                f"executed msg_bytes (u={stats['uniform']['wire_bytes']}"
                f", v={stats['variance']['wire_bytes']}) != allocator's "
                f"predicted sums (u={alloc_u.payload_bytes}, "
                f"v={alloc_v.payload_bytes}) — the allocation and the "
                "program disagree about a byte",
            )
        if stats["variance"]["wire_bytes"] > stats["uniform"]["wire_bytes"]:
            _mark_invalid(
                out,
                "variance allocation moved MORE wire than uniform — not "
                "an equal-byte comparison",
            )
        out["measured_variance_reduction"] = round(
            1.0 - stats["variance"]["mean_q_err2"]
            / max(stats["uniform"]["mean_q_err2"], 1e-30), 4
        )
        if stats["variance"]["mean_q_err2"] > stats["uniform"]["mean_q_err2"]:
            _mark_invalid(
                out,
                "measured estimator variance (q_err2) NOT reduced by "
                "the variance allocation — the solver's own objective "
                "failed on real gradients",
            )
        out["pareto_loss_ok"] = bool(
            stats["variance"]["mean_loss"] <= stats["uniform"]["mean_loss"]
        )
        if not out["pareto_loss_ok"]:
            _mark_invalid(
                out,
                "seed-ensemble mean loss "
                f"{stats['variance']['mean_loss']:.6f} (variance) > "
                f"{stats['uniform']['mean_loss']:.6f} (uniform) at equal "
                "wire — no Pareto win on this recipe",
            )

        # --- gate 4: the resume-from-allocation drill ----------------
        doc = new_alloc_doc(codec, spectra, alloc_v)
        doc_rt = json.loads(json.dumps(doc))  # the artifact round trip
        ks_rt = tuple(int(k) for k in latest_epoch(doc_rt)["ks"])
        t1 = max(steps_per // 2, 2)
        t2 = max(steps_per - t1, 2)
        step_v = make_distributed_train_step(
            model, opt, mesh, wrapped_v, aggregate="gather"
        )
        st_cont, _, _, _, _ = run(
            wrapped_v, 0, t1 + t2, step=step_v, quality=False
        )
        st_half, _, _, _, _ = run(
            wrapped_v, 0, t1, step=step_v, quality=False
        )
        # "restart": rebuild the codec and the step from the recorded
        # artifact alone, resume from the snapshot
        step_rt = make_distributed_train_step(
            model, opt, mesh, budgeted_codec(codec, ks_rt),
            aggregate="gather",
        )
        st_res = replicate_state(mesh, jax.device_get(st_half))
        key0 = jax.random.PRNGKey(100)
        for i in range(t1, t1 + t2):
            si, sl = batch_at(i)
            st_res, _ = step_rt(st_res, key0, si, sl)
        out["resume_bit_exact"] = bool(all(
            np.array_equal(np.asarray(a), np.asarray(b))
            for a, b in zip(
                jax.tree_util.tree_leaves(jax.device_get(st_cont.params)),
                jax.tree_util.tree_leaves(jax.device_get(st_res.params)),
            )
        ))
        if not out["resume_bit_exact"]:
            _mark_invalid(
                out,
                "resume-from-allocation drill NOT bit-exact: the "
                "JSON-round-tripped budget_alloc epoch rebuilt a "
                "different program",
            )
        # the headline byte context: the codec's reduction vs dense
        dense_b = sum(l.dense_bytes for l in spectra)
        out["byte_reduction"] = round(
            dense_b / max(stats["variance"]["wire_bytes"], 1), 3
        )
    except Exception as exc:  # noqa: BLE001 — a failed compare is a failed row
        _mark_invalid(
            out, f"adaptive-budget compare failed: {str(exc)[:200]}"
        )
    return out


def gather_vs_ring_parity(mesh, codec, grads, key, n_dev: int,
                          bucket_size: int = 65536) -> bool:
    """The PR-3 aggregation-operator contract, as one reusable check:
    gather's CANONICAL decode-mean (``decode_mean_tree(fused=False)`` —
    the fused SVD matmul reassociates, a documented ~1e-6 drift, not a
    parity break) must be BIT-identical to ring's streamed fold over the
    same per-chip payloads. tests/test_ring_aggregate.py is the full
    oracle; this is the in-row bench evidence — config 10 calls it per
    compressed multi-device cell (config 8's inline variant additionally
    times each phase program, which is why it keeps its own copy of the
    construction). The invariant is what makes the autopilot's online
    gather<->ring re-tune trajectory-safe."""
    import numpy as np

    import jax
    from jax.sharding import PartitionSpec as P

    from atomo_tpu.codecs import decode_mean_tree, encode_tree
    from atomo_tpu.parallel.replicated import _ring_stream_mean

    def sm(fn, in_specs, out_specs):
        return jax.jit(jax.shard_map(
            fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
            check_vma=False,
        ))

    def enc(g):
        my = jax.lax.axis_index("dp")
        p, _ = encode_tree(codec, jax.random.fold_in(key, my), g)
        return jax.tree_util.tree_map(lambda a: a[None], p)

    payloads_x = sm(enc, (P(),), P("dp"))(grads)
    gathered = sm(
        lambda px: jax.lax.all_gather(
            jax.tree_util.tree_map(lambda a: a[0], px), "dp"
        ),
        (P("dp"),), P(),
    )(payloads_x)
    mean_g = sm(
        lambda gth: decode_mean_tree(codec, gth, grads, n_dev,
                                     fused=False),
        (P(),), P(),
    )(gathered)

    def ring_xdec(px):
        my = jax.lax.axis_index("dp")
        local = jax.tree_util.tree_map(lambda a: a[0], px)
        mean, _ = _ring_stream_mean(
            codec, local, grads, axis="dp", n_dev=n_dev, my=my,
            n_contrib=n_dev, bucket_size=bucket_size,
        )
        return mean

    mean_r = sm(ring_xdec, (P("dp"),), P())(payloads_x)
    return bool(all(
        np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(
            jax.tree_util.tree_leaves(jax.device_get(mean_g)),
            jax.tree_util.tree_leaves(jax.device_get(mean_r)),
        )
    ))


def measure_fabric_probe(cfg: dict) -> dict:
    """Config-14: the measured-fabric loop on the forced multi-device
    CPU mesh (ladder comment on the config entry). The bit-parity drill
    runs the REAL CLI path twice — ``--fabric measured`` (startup probe,
    artifact, measured pricing) vs ``--fabric ici`` (preset pricing) —
    with identical resolved knobs, and asserts the final checkpoints
    equal bit for bit: the fabric value is a PRICING input, never a
    semantics input, and the probe's device work leaves the trajectory
    untouched."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    from atomo_tpu.obs.fabric import (
        QUICK_SIZES,
        probe_fabric,
        read_fabric_probe,
    )
    from atomo_tpu.utils.comm_model import FABRICS

    fast = os.environ.get("ATOMO_BENCH_FAST") == "1"
    dev = jax.devices()[0]
    n_dev = min(int(cfg.get("n_dev", 4)), len(jax.devices()))
    dcn_ways = int(cfg.get("dcn_ways", 2))
    base = dict(
        metric=cfg["metric"], unit="GB/s per chip", value=None,
        byte_reduction=None, mfu=None, flops_per_step=None,
        peak_tflops=None, platform=dev.platform, device=dev.device_kind,
        ways=n_dev, chips_measured=n_dev,
        timing="dispatch-loop-scalar-fenced",
        config=dict(kind="fabricprobe", n_dev=n_dev, dcn_ways=dcn_ways,
                    batch=int(cfg.get("batch", 8))),
        note=(f"measured per-tier fabric on a {n_dev}-device "
              f"{dev.platform} mesh (dcn_ways={dcn_ways}); on CPU the "
              "'fabric' is host memcpy — calibration bookkeeping plus "
              "the pricing-only bit-parity gate, not a chip-speed claim"),
    )
    if n_dev < 2:
        base.update(measurement_valid=False,
                    invalid_reason="single device: no fabric to measure")
        return base
    out = dict(base, measurement_valid=True, invalid_reason=None)
    try:
        # --- gate 1: the probe itself -------------------------------
        doc = probe_fabric(
            n_dev=n_dev, dcn_ways=dcn_ways,
            sizes=QUICK_SIZES if fast else (1 << 12, 1 << 16, 1 << 20),
            reps=1 if fast else 3, best_of=1 if fast else 2,
        )
        out["fabric_probe"] = {
            "complete": doc.get("complete"),
            "tiers": [
                {k: t[k] for k in ("label", "axis", "ways",
                                   "bandwidth_gbps", "latency_us",
                                   "allgather_gbps")}
                for t in doc.get("tiers", [])
            ],
            "probe_wall_s": (doc.get("meta") or {}).get("probe_wall_s"),
        }
        if not doc.get("complete"):
            _mark_invalid(out, "fabric probe artifact incomplete")
        tiers = {t["label"]: t for t in doc.get("tiers", [])}
        if set(tiers) != {"ici", "dcn"}:
            _mark_invalid(
                out, f"expected ici+dcn tiers, probed {sorted(tiers)}"
            )
        # --- gate 2: measured-vs-preset calibration ratio ------------
        out["measured_vs_preset"] = {
            lbl: round(
                float(t["bandwidth_gbps"]) * 1e9 / FABRICS[lbl], 4
            )
            for lbl, t in tiers.items()
            if lbl in FABRICS and t.get("bandwidth_gbps")
        }
        slow = min(
            (t["bandwidth_gbps"] for t in tiers.values()
             if t.get("bandwidth_gbps")),
            default=None,
        )
        out["value"] = slow  # headline: the slowest measured tier

        # --- gate 3: pricing-only bit parity through the REAL CLI ----
        import shutil
        import tempfile

        from atomo_tpu.cli import main as cli_main

        tmp = tempfile.mkdtemp(prefix="bench_c14_")
        try:
            steps = 2 if fast else 4
            common = [
                "train", "--synthetic", "--dataset", "mnist",
                "--network", "lenet", "--batch-size",
                str(int(cfg.get("batch", 8))), "--max-steps", str(steps),
                "--eval-freq", "0", "--save-freq", str(steps),
                "--log-interval", "0", "--n-devices", str(n_dev),
                "--code", "qsgd", "--quantization-level", "8",
                "--aggregate", "gather", "--seed", "3",
                "--momentum", "0.5",
            ]
            d_meas = os.path.join(tmp, "measured")
            d_pin = os.path.join(tmp, "pinned")
            rc_a = cli_main(common + ["--train-dir", d_meas,
                                      "--fabric", "measured",
                                      "--dcn-ways", str(dcn_ways)])
            rc_b = cli_main(common + ["--train-dir", d_pin,
                                      "--fabric", "ici"])
            if rc_a != 0 or rc_b != 0:
                raise RuntimeError(
                    f"parity drill runs exited rc={rc_a}/{rc_b}"
                )
            art = read_fabric_probe(d_meas)
            out["run_artifact_complete"] = bool(art and art.get("complete"))
            if not out["run_artifact_complete"]:
                _mark_invalid(
                    out, "--fabric measured run left no complete "
                    "fabric_probe.json"
                )
            from atomo_tpu.models import get_model
            from atomo_tpu.training import create_state, make_optimizer
            from atomo_tpu.training.checkpoint import load_checkpoint

            model = get_model("lenet", 10)
            opt = make_optimizer("sgd", lr=0.01, lr_shrinkage=0.95,
                                 shrinkage_freq=50, momentum=0.5)
            tpl = jax.device_get(create_state(
                model, opt, jax.random.PRNGKey(3),
                jnp.zeros((int(cfg.get("batch", 8)), 28, 28, 1)),
            ))
            a = load_checkpoint(d_meas, tpl, step=steps)
            b = load_checkpoint(d_pin, tpl, step=steps)
            la = jax.tree_util.tree_leaves(a)
            lb = jax.tree_util.tree_leaves(b)
            out["fabric_parity"] = bool(
                len(la) == len(lb)
                and all(
                    np.array_equal(np.asarray(x), np.asarray(y))
                    for x, y in zip(la, lb)
                )
            )
            if not out["fabric_parity"]:
                _mark_invalid(
                    out,
                    "measured-priced and preset-priced runs with "
                    "identical resolved knobs are NOT bit-identical — "
                    "the fabric leaked into semantics",
                )
        finally:
            shutil.rmtree(tmp, ignore_errors=True)
    except Exception as exc:  # noqa: BLE001 — a failed drill is a failed row
        _mark_invalid(out, f"fabric probe drill failed: {str(exc)[:200]}")
    return out


def measure_sharded_update_memory(cfg: dict) -> dict:
    """Config-15: replicated vs zero1 vs sharded-update on the forced
    multi-device CPU mesh (see CONFIGS[15] for the full row contract).

    Per partition the row records MEASURED per-chip persistent state
    bytes — params/master + optimizer buffers summed over chip 0's
    actual addressable device shards — plus fenced ms/step; the in-row
    ``bit_parity`` gate asserts all three partitions trained the
    identical trajectory (qsgd gather, the canonical decode order), so
    the memory columns describe one program family. ``value`` is the
    sharded-update ms/step; the headline memory number is
    ``state_bytes_reduction`` (replicated / sharded per-chip bytes)."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    from atomo_tpu.codecs import QsgdCodec
    from atomo_tpu.mesh import sharded_update_state
    from atomo_tpu.models import get_model
    from atomo_tpu.parallel import (
        make_distributed_train_step,
        make_mesh,
        replicate_state,
        shard_batch,
    )
    from atomo_tpu.parallel.replicated import zero1_state
    from atomo_tpu.training import create_state, make_optimizer

    fast = os.environ.get("ATOMO_BENCH_FAST") == "1"
    dev = jax.devices()[0]
    n_dev = min(int(cfg.get("n_dev", 4)), len(jax.devices()))
    batch = int(cfg.get("batch", 16))
    base = dict(
        metric=cfg["metric"], unit="ms/step", value=None,
        byte_reduction=None, mfu=None, flops_per_step=None,
        peak_tflops=None, platform=dev.platform, device=dev.device_kind,
        ways=n_dev, chips_measured=n_dev,
        timing="dispatch-loop-scalar-fenced",
        config=dict(kind="shardedupd", network=cfg.get("network", "lenet"),
                    batch=batch, n_dev=n_dev),
        note=(f"cross-replica sharded weight update (2004.13336) vs "
              f"zero1 vs replicated on a {n_dev}-device {dev.platform} "
              "mesh; measured per-chip state bytes + in-row bit parity; "
              "not a chip-speed claim"),
    )
    if n_dev < 2:
        base.update(measurement_valid=False,
                    invalid_reason="single device: nothing to shard the "
                                   "update over")
        return base

    mesh = make_mesh(n_dev)
    model = get_model(cfg.get("network", "lenet"), 10)
    opt = make_optimizer("sgd", lr=0.01, momentum=0.9)
    r = np.random.default_rng(0)
    images = jnp.asarray(
        r.standard_normal((batch, 28, 28, 1)).astype(np.float32)
    )
    labels = jnp.asarray(r.integers(0, 10, batch).astype(np.int32))
    codec = QsgdCodec(bits=8, bucket_size=512)
    host0 = jax.device_get(
        create_state(model, opt, jax.random.PRNGKey(0), images)
    )
    si, sl = shard_batch(mesh, images, labels)
    key = jax.random.PRNGKey(1)
    steps = _env_int("ATOMO_BENCH_STEPS", 3 if fast else 10)
    reps = 1 if fast else 3

    def chip0_bytes(tree) -> int:
        dev0 = jax.devices()[0]
        total = 0
        for leaf in jax.tree_util.tree_leaves(tree):
            for s in leaf.addressable_shards:
                if s.device == dev0:
                    total += (
                        int(np.prod(s.data.shape)) * s.data.dtype.itemsize
                    )
        return total

    def run(partition: str):
        if partition == "sharded_update":
            st, su = sharded_update_state(mesh, host0, opt)
            step = make_distributed_train_step(
                model, opt, mesh, codec, aggregate="gather",
                sharded_update=su,
            )
            persistent = lambda s: (s.master, s.opt_state)  # noqa: E731
        elif partition == "zero1":
            st, zs = zero1_state(mesh, host0, opt)
            step = make_distributed_train_step(
                model, opt, mesh, codec, aggregate="gather",
                zero1_specs=zs,
            )
            persistent = lambda s: (s.params, s.opt_state)  # noqa: E731
            su = None
        else:
            st = replicate_state(mesh, host0)
            step = make_distributed_train_step(
                model, opt, mesh, codec, aggregate="gather"
            )
            persistent = lambda s: (s.params, s.opt_state)  # noqa: E731
            su = None
        state_bytes = chip0_bytes(persistent(st))
        st, m = step(st, key, si, sl)  # compile + warm
        float(m["loss"])
        dt = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            for _ in range(steps):
                st, m = step(st, key, si, sl)
            float(m["loss"])  # the fence
            dt = min(dt, (time.perf_counter() - t0) / steps)
        params = (
            su.materialize_host(st.master)
            if partition == "sharded_update"
            else jax.device_get(st.params)
        )
        return dt, state_bytes, params

    out = dict(base, measurement_valid=True, invalid_reason=None)
    try:
        results = {}
        for part in ("replicated", "zero1", "sharded_update"):
            dt, sb, params = run(part)
            results[part] = (dt, sb, params)
            out[f"{part}_ms_per_step"] = round(dt * 1e3, 3)
            out[f"{part}_state_bytes_per_chip"] = sb
        ref = jax.tree_util.tree_leaves(results["replicated"][2])
        parity = all(
            all(
                np.array_equal(np.asarray(a), np.asarray(b))
                for a, b in zip(
                    ref, jax.tree_util.tree_leaves(results[p][2])
                )
            )
            for p in ("zero1", "sharded_update")
        )
        out["bit_parity"] = bool(parity)
        out["value"] = out["sharded_update_ms_per_step"]
        rep_b = results["replicated"][1]
        z_b = results["zero1"][1]
        s_b = results["sharded_update"][1]
        out["state_bytes_reduction"] = round(rep_b / max(s_b, 1), 3)
        if not parity:
            _mark_invalid(
                out,
                "partitions are NOT bit-identical on the canonical "
                "decode order — the sharded update leaked into semantics",
            )
        elif not (s_b < z_b < rep_b):
            _mark_invalid(
                out,
                f"per-chip state bytes not strictly decreasing "
                f"(replicated {rep_b} / zero1 {z_b} / sharded {s_b}) — "
                "the memory claim did not materialize on the buffers",
            )
    except Exception as exc:  # noqa: BLE001 — a failed drill is a failed row
        _mark_invalid(out, f"sharded-update drill failed: {str(exc)[:200]}")
    return out


def measure_quorum_absorption(cfg: dict) -> dict:
    """Config-17: bounded-staleness quorum vs blocking under one chaos-
    slowed replica (see CONFIGS[17] for the full row contract).

    ``value`` is the quorum step's fenced ms/step with the live rig
    consuming arrivals; ``blocking_ms_per_step`` pays the straggler's
    host sleep every exchange. The two in-row gates:
    ``equal_wire`` (identical msg_bytes — the quorum knob never changes
    how many bytes move) and ``replay_bit_parity`` (a second run driven
    by the recorded arrival schedule lands bit-identical params)."""
    import shutil
    import tempfile

    import numpy as np

    import jax
    import jax.numpy as jnp

    from atomo_tpu.codecs import QsgdCodec
    from atomo_tpu.models import get_model
    from atomo_tpu.parallel import (
        make_distributed_train_step,
        make_mesh,
        replicate_state,
        shard_batch,
    )
    from atomo_tpu.parallel.replicated import init_quorum_state
    from atomo_tpu.quorum import QuorumConfig
    from atomo_tpu.quorum.artifact import read_schedule, schedule_path
    from atomo_tpu.quorum.rig import QuorumRig
    from atomo_tpu.training import create_state, make_optimizer
    from atomo_tpu.utils.chaos import ChaosConfig, ChaosInjector

    fast = os.environ.get("ATOMO_BENCH_FAST") == "1"
    dev = jax.devices()[0]
    n_dev = min(int(cfg.get("n_dev", 4)), len(jax.devices()))
    batch = int(cfg.get("batch", 32))
    slow_s = float(cfg.get("slow_ms", 60)) / 1e3
    base = dict(
        metric=cfg["metric"], unit="ms/step", value=None,
        byte_reduction=None, mfu=None, flops_per_step=None,
        peak_tflops=None, platform=dev.platform, device=dev.device_kind,
        ways=n_dev, chips_measured=n_dev,
        timing="dispatch-loop-scalar-fenced",
        config=dict(kind="quorum", network=cfg.get("network", "lenet"),
                    batch=batch, n_dev=n_dev,
                    slow_ms=float(cfg.get("slow_ms", 60)),
                    quorum=n_dev - 1, staleness=1),
        note=(f"bounded-staleness quorum (Q={n_dev - 1} of {n_dev}, K=1) "
              f"vs blocking under one slow@ replica on a {n_dev}-device "
              f"{dev.platform} mesh; equal-wire + replay-parity gates "
              "in-row; not a chip-speed claim"),
    )
    if n_dev < 2:
        base.update(measurement_valid=False,
                    invalid_reason="single device: no exchange to quorum on")
        return base

    mesh = make_mesh(n_dev)
    model = get_model(cfg.get("network", "lenet"), 10)
    opt = make_optimizer("sgd", lr=0.01, momentum=0.9)
    r = np.random.default_rng(0)
    images = jnp.asarray(
        r.standard_normal((batch, 28, 28, 1)).astype(np.float32)
    )
    labels = jnp.asarray(r.integers(0, 10, batch).astype(np.int32))
    codec = QsgdCodec(bits=8, bucket_size=512)
    host0 = jax.device_get(
        create_state(model, opt, jax.random.PRNGKey(0), images)
    )
    si, sl = shard_batch(mesh, images, labels)
    key = jax.random.PRNGKey(1)
    steps = _env_int("ATOMO_BENCH_STEPS", 3 if fast else 10)
    # period == the straggler's lag, so its payload rides the carry ONE
    # step stale (never dropped) and the exposed quorum wait is zero
    qcfg = QuorumConfig(n_dev - 1, staleness=1, period_s=slow_s)
    chaos_spec = f"slow@1:1:{slow_s}"

    def fresh():
        return replicate_state(
            mesh, jax.tree_util.tree_map(jnp.asarray, host0)
        )

    out = dict(base, measurement_valid=True, invalid_reason=None)
    work = tempfile.mkdtemp(prefix="bench_quorum_")
    try:
        # --- blocking: the exchange waits for the slowed replica -------
        blocking = make_distributed_train_step(
            model, opt, mesh, codec, aggregate="gather"
        )
        st = fresh()
        st, m = blocking(st, key, si, sl)  # compile + warm (no sleep)
        if not math.isfinite(float(m["loss"])):
            raise RuntimeError("blocking warmup loss not finite")
        block_bytes = int(m["msg_bytes"])
        chaos = ChaosInjector(ChaosConfig.from_spec(chaos_spec))
        t0 = time.perf_counter()
        for s in range(1, steps + 1):
            chaos.maybe_sleep_replica(s, n_dev)
            st, m = blocking(st, key, si, sl)
        float(m["loss"])  # the fence
        t_block = (time.perf_counter() - t0) / steps

        # --- quorum, live rig: the straggler rides the carry -----------
        q_step = make_distributed_train_step(
            model, opt, mesh, codec, aggregate="gather", quorum=qcfg
        )

        def run_quorum(train_dir, replay=None):
            rig = QuorumRig(
                qcfg, n_dev=n_dev, train_dir=train_dir,
                chaos=None if replay else ChaosInjector(
                    ChaosConfig.from_spec(chaos_spec)
                ),
                replay_path=replay, log_fn=lambda *_: None,
            )
            qst = init_quorum_state(mesh, fresh(), codec, qcfg.staleness)
            m = None
            t0 = time.perf_counter()
            for s in range(1, steps + 1):
                arr = jnp.asarray(rig.begin_step(s))
                qst, m = q_step(qst, key, si, sl, arr)
            float(m["loss"])  # the fence
            dt = (time.perf_counter() - t0) / steps
            return dt, jax.device_get(qst), m

        # compile + warm the quorum program OFF the clock (throwaway
        # state; the measured runs below start fresh)
        _warm = init_quorum_state(mesh, fresh(), codec, qcfg.staleness)
        _warm, wm = q_step(_warm, key, si, sl,
                           jnp.zeros((n_dev,), jnp.int32))
        if not math.isfinite(float(wm["loss"])):
            raise RuntimeError("quorum warmup loss not finite")

        d_live = os.path.join(work, "live")
        t_quorum, live, qm = run_quorum(d_live)
        out["value"] = round(t_quorum * 1e3, 3)
        out["blocking_ms_per_step"] = round(t_block * 1e3, 3)
        out["straggler_absorption_speedup"] = round(t_block / t_quorum, 3)
        out["quorum_kept"] = int(qm["quorum_kept"])
        out["stale_dropped"] = int(qm["stale_dropped"])
        # equal wire: the quorum step ships the same payload bytes
        out["msg_bytes"] = int(qm["msg_bytes"])
        out["equal_wire"] = bool(int(qm["msg_bytes"]) == block_bytes)
        if not out["equal_wire"]:
            _mark_invalid(
                out,
                f"quorum step moved {int(qm['msg_bytes'])} B vs blocking "
                f"{block_bytes} B — the equal-wire contract broke",
            )
        if t_quorum >= t_block:
            _mark_invalid(
                out,
                "quorum step not below blocking despite the straggler "
                "sleep (contended host)",
            )

        # --- replay gate: rebuild the run from the recorded schedule ---
        _, arr_live = read_schedule(schedule_path(d_live))
        out["schedule_steps_recorded"] = len(arr_live)
        d_rep = os.path.join(work, "replay")
        _, replayed, _ = run_quorum(d_rep, replay=schedule_path(d_live))
        out["replay_bit_parity"] = bool(all(
            np.array_equal(np.asarray(a), np.asarray(b))
            for a, b in zip(
                jax.tree_util.tree_leaves(live.train.params),
                jax.tree_util.tree_leaves(replayed.train.params),
            )
        ))
        if not out["replay_bit_parity"]:
            _mark_invalid(
                out,
                "replayed arrival schedule did NOT reproduce the live "
                "params bit-for-bit (the PR-16 replay contract)",
            )
    except Exception as exc:  # noqa: BLE001 — a failed drill is a failed row
        _mark_invalid(out, f"quorum drill failed: {str(exc)[:200]}")
    finally:
        shutil.rmtree(work, ignore_errors=True)
    return out


def measure_controller_joint(cfg: dict) -> dict:
    """Config-18: the global controller's joint decision space vs each
    legacy single-decider search (see CONFIGS[18] for the full row
    contract).

    ``value`` is the joint winner's probe-measured ms/step. The four
    in-row gates: ``superset_pricing`` (joint best predicted <= every
    standalone best predicted), ``joint_not_slower`` (measured, stated
    tolerance), ``pin_bit_parity`` + ``pin_equal_wire`` (the winner
    rebuilt from controller_decision.json on disk == the same knobs as
    pinned literals, bit-identical params at identical msg_bytes), and
    ``resume_bit_parity`` (kill->controller_reusable->rebuild replays
    bit-exact against the uninterrupted run)."""
    import shutil
    import tempfile

    import numpy as np

    import jax
    import jax.numpy as jnp

    from atomo_tpu.budget import (
        allocation_leaf_budgets,
        budgeted_codec,
        measure_spectra,
        new_alloc_doc,
        solve_allocation,
    )
    from atomo_tpu.codecs import SvdCodec
    from atomo_tpu.controller import (
        controller_path,
        controller_reusable,
        read_controller,
        solve_controller,
    )
    from atomo_tpu.data.zipf import zipf_dataset
    from atomo_tpu.models import EmbeddingTower
    from atomo_tpu.parallel import (
        init_delayed_state,
        make_distributed_train_step,
        make_mesh,
        replicate_state,
        shard_batch,
    )
    from atomo_tpu.parallel.replicated import shard_superbatch
    from atomo_tpu.sparse.hybrid import (
        infer_row_bounds,
        measured_densities,
        plan_hybrid,
        probe_gradient,
    )
    from atomo_tpu.training import create_state, make_optimizer
    from atomo_tpu.tuning.probe import model_init_fn

    fast = os.environ.get("ATOMO_BENCH_FAST") == "1"
    dev = jax.devices()[0]
    n_dev = min(int(cfg.get("n_dev", 4)), len(jax.devices()))
    batch = int(cfg.get("batch", 32))
    rank = int(cfg.get("svd_rank", 3))
    dcn_ways = int(cfg.get("dcn_ways", 2))
    base = dict(
        metric=cfg["metric"], unit="ms/step", value=None,
        byte_reduction=None, mfu=None, flops_per_step=None,
        peak_tflops=None, platform=dev.platform, device=dev.device_kind,
        ways=n_dev, chips_measured=n_dev,
        timing="dispatch-loop-scalar-fenced",
        config=dict(kind="controller", batch=batch, n_dev=n_dev,
                    emb_rows=int(cfg.get("emb_rows", 1024)),
                    emb_dim=int(cfg.get("emb_dim", 16)),
                    zipf_slots=int(cfg.get("zipf_slots", 8)),
                    svd_rank=rank, dcn_ways=dcn_ways),
        note=(f"joint controller decision vs the four standalone "
              f"deciders at matched inputs on a {n_dev}-device "
              f"{dev.platform} mesh, power-law embedding workload; "
              "superset-pricing / not-slower / artifact-pin bit-parity "
              "/ resume evidence, not a chip-speed claim"),
    )
    if n_dev < 2:
        base.update(measurement_valid=False,
                    invalid_reason="single device: no exchange, nothing "
                                   "for a controller to decide")
        return base
    if dcn_ways < 2 or n_dev % dcn_ways:
        base.update(measurement_valid=False,
                    invalid_reason=f"dcn_ways={dcn_ways} does not "
                                   f"divide n_dev={n_dev}")
        return base

    model = EmbeddingTower(
        num_classes=10, rows=int(cfg.get("emb_rows", 1024)),
        dim=int(cfg.get("emb_dim", 16)),
    )
    opt = make_optimizer("sgd", lr=0.1, momentum=0.5)
    ds = zipf_dataset(
        True, rows=int(cfg.get("emb_rows", 1024)),
        slots=int(cfg.get("zipf_slots", 8)),
        size=max(batch * 8, 256), seed=0,
    )
    codec = SvdCodec(rank=rank)
    out = dict(base, measurement_valid=True, invalid_reason=None)
    work = tempfile.mkdtemp(prefix="atomo-bench-controller-")
    try:
        # ---- shared decider inputs (the CLI's preflight work) --------
        grads = probe_gradient(
            model, ds.images[:batch], ds.labels[:batch]
        )
        spectra = measure_spectra(codec, grads)
        alloc = solve_allocation(codec, spectra, mode="variance")
        budget_ctx = {
            "base_codec": codec,
            "codec": budgeted_codec(codec, alloc.ks),
            "spectra": spectra,
            "alloc": alloc,
            "doc": new_alloc_doc(codec, spectra, alloc),
            "leaf_budgets": allocation_leaf_budgets(
                codec, spectra, alloc.ks
            ),
        }
        st_probe = create_state(
            model, opt, jax.random.PRNGKey(0),
            jnp.asarray(ds.images[:batch]),
        )
        densities = measured_densities(grads)
        row_bounds = infer_row_bounds(
            st_probe.params, batch // n_dev,
            int(cfg.get("zipf_slots", 8)),
        )
        plan = plan_hybrid(codec, grads, densities, row_bounds)
        out["hybrid_any_sparse"] = bool(plan.any_sparse)
        hybrid_inputs = {
            "grads_like": grads, "densities": densities,
            "row_bounds": row_bounds,
        }

        common = dict(
            model=model, optimizer=opt, codec=codec,
            model_init_fn=model_init_fn(
                model, jnp.asarray(ds.images[:1])
            ),
            n_dev=n_dev, sample_shape=tuple(ds.images.shape[1:]),
            num_classes=10, batch=batch, seed=0,
            probe_steps=2 if fast else 3, probe_reps=1 if fast else 2,
            log_fn=lambda *a, **k: None,
        )
        joint = solve_controller(
            deciders=None, budget_ctx=budget_ctx, hybrid=plan,
            hybrid_inputs=hybrid_inputs, dcn_ways=dcn_ways,
            allow_stream=True, probe_top=2 if fast else 4,
            artifact_path=controller_path(work), **common,
        )
        singles = {
            "autopilot": solve_controller(
                deciders={"autopilot"}, allow_stream=True,
                probe_top=1, **common,
            ),
            "budget": solve_controller(
                deciders={"budget"}, budget_ctx=budget_ctx,
                probe_top=1, **common,
            ),
            "hybrid": solve_controller(
                deciders={"hybrid"}, hybrid=plan,
                probe_top=1, **common,
            ),
            "topology": solve_controller(
                deciders={"topology"}, dcn_ways=dcn_ways,
                probe_top=1, **common,
            ),
        }
        if not (joint.get("winner") or {}).get("knobs"):
            _mark_invalid(out, "joint solve produced no winner")
            return out

        def _best_predicted(doc):
            vals = [
                float(r["predicted_ms_per_step"]) for r in doc["rows"]
                if r.get("predicted_ms_per_step") is not None
            ]
            return min(vals) if vals else float("inf")

        # gate 1: SUPERSET PRICING — deterministic, per decider
        jbest = _best_predicted(joint)
        out["superset_pricing"] = {
            name: bool(jbest <= _best_predicted(doc) + 1e-9)
            for name, doc in singles.items()
        }
        out["joint_winner"] = dict(joint["winner"])
        out["single_winners"] = {
            name: (doc.get("winner") or {"name": None})
            for name, doc in singles.items()
        }
        if not all(out["superset_pricing"].values()):
            _mark_invalid(
                out,
                "joint ladder priced WORSE than a restricted subspace "
                "— the controller is not a superset of the legacy "
                f"deciders here: {out['superset_pricing']}",
            )
            return out

        # gate 2: NOT-SLOWER — same fenced probe harness both sides;
        # 1.25x tolerance for CPU probe noise (stated, in-row), and
        # trivially equal when both searches picked the same program
        singles_ms = {
            name: (doc.get("winner") or {}).get("measured_ms_per_step")
            for name, doc in singles.items()
        }
        best_single = min(
            (v for v in singles_ms.values() if v is not None),
            default=None,
        )
        joint_ms = joint["winner"].get("measured_ms_per_step")
        out["value"] = joint_ms
        out["best_single_ms_per_step"] = best_single
        same_prog = joint["winner"]["name"] in {
            (doc.get("winner") or {}).get("name")
            for doc in singles.values()
        }
        out["joint_not_slower"] = bool(
            same_prog
            or (joint_ms is not None and best_single is not None
                and joint_ms <= best_single * 1.25)
        )
        if not out["joint_not_slower"]:
            _mark_invalid(
                out,
                f"joint winner measured {joint_ms} ms/step, slower "
                f"than the best standalone decider ({best_single} "
                "ms/step) beyond the stated 1.25x probe-noise "
                "tolerance",
            )
            return out

        # ---- the winner program, rebuilt from knobs -----------------
        # mirrors tuning.probe.probe_candidate's multi-device builder
        # (the REAL train-path builders) + the controller's per-
        # candidate codec/hybrid resolution (+ab swaps in the wrapped
        # codec; +sp+ab re-plans the crossover under it)
        def build(knobs):
            agg = knobs.get("aggregate", "gather")
            overlap = knobs.get("overlap", "off")
            k = max(int(knobs.get("superstep", 1)), 1)
            plan_t, inner_axis, batch_axes = None, None, "dp"
            if agg == "hierarchical":
                from atomo_tpu.topology.schedule import plan_from_name

                mesh = make_mesh(
                    n_dev,
                    axes=(("dp", dcn_ways), ("ici", n_dev // dcn_ways)),
                )
                plan_t = plan_from_name(knobs.get("plan", "legacy"))
                inner_axis, batch_axes = "ici", ("dp", "ici")
            else:
                mesh = make_mesh(n_dev)
            ab = knobs.get("budget_alloc") == "variance"
            codec_run = budget_ctx["codec"] if ab else codec
            hybrid_run = None
            if knobs.get("sparse_rows") == "on":
                hybrid_run = (
                    plan_hybrid(budget_ctx["codec"], grads, densities,
                                row_bounds)
                    if ab else plan
                )
            st = replicate_state(mesh, create_state(
                model, opt, jax.random.PRNGKey(42),
                jnp.asarray(ds.images[:batch]),
            ))
            step = make_distributed_train_step(
                model, opt, mesh, codec_run, aggregate=agg,
                superstep=k, overlap=overlap,
                ring_bucket_size=int(
                    knobs.get("ring_bucket_size", 65536)
                ),
                stream_encode=knobs.get("stream_encode") == "on",
                stream_bucket_bytes=int(
                    knobs.get("stream_bucket_bytes", 4 << 20)
                ),
                inner_axis=inner_axis, plan=plan_t, hybrid=hybrid_run,
            )
            if overlap == "delayed":
                st = init_delayed_state(mesh, st, codec_run)
            return step, st, mesh, k, batch_axes

        n = len(ds.images)

        def run(prog, T, st=None, start=0):
            step, st0, mesh, k, bax = prog
            st = st0 if st is None else st
            m = None
            for i in range(start, start + T):
                s0 = (i * batch) % (n - batch)
                im = jnp.asarray(ds.images[s0:s0 + batch])
                lb = jnp.asarray(ds.labels[s0:s0 + batch])
                if k > 1:
                    im = jnp.broadcast_to(im, (k,) + im.shape)
                    lb = jnp.broadcast_to(lb, (k,) + lb.shape)
                    im, lb = shard_superbatch(mesh, im, lb, axis=bax)
                else:
                    im, lb = shard_batch(mesh, im, lb, axis=bax)
                st, m = step(
                    st, jax.random.fold_in(jax.random.PRNGKey(5), i),
                    im, lb,
                )
            leaves = [
                np.asarray(jax.device_get(l))
                for l in jax.tree_util.tree_leaves(st.params)
            ]
            msg = (
                int(np.ravel(jax.device_get(m["msg_bytes"]))[-1])
                if m is not None and "msg_bytes" in m else None
            )
            return st, leaves, msg

        T = 2 if fast else 4

        # gate 3: PIN BIT-PARITY at equal wire — the knob vector read
        # back from controller_decision.json ON DISK vs the same knobs
        # as pinned Python literals, through the same builder
        ctl = read_controller(work)
        artifact_knobs = dict((ctl.get("winner") or {}).get("knobs"))
        pinned_knobs = {
            str(kk): (vv if isinstance(vv, (int, float)) else str(vv))
            for kk, vv in sorted(artifact_knobs.items())
        }
        _, leaves_a, msg_a = run(build(artifact_knobs), T)
        _, leaves_b, msg_b = run(build(pinned_knobs), T)
        out["pin_bit_parity"] = bool(
            len(leaves_a) == len(leaves_b)
            and all(
                np.array_equal(x, y)
                for x, y in zip(leaves_a, leaves_b)
            )
        )
        out["pin_equal_wire"] = bool(msg_a == msg_b)
        out["winner_msg_bytes"] = msg_a
        if not (out["pin_bit_parity"] and out["pin_equal_wire"]):
            _mark_invalid(
                out,
                "winner program rebuilt from the decision artifact did "
                "NOT match the pinned-literals run bit-for-bit at "
                f"equal wire (parity={out['pin_bit_parity']}, "
                f"msg_bytes {msg_a} vs {msg_b})",
            )
            return out

        # gate 4: RESUME DRILL — T steps, controller_reusable on the
        # re-read artifact, rebuild, T more; vs 2T uninterrupted
        _, leaves_full, _ = run(build(artifact_knobs), 2 * T)
        prog_1 = build(artifact_knobs)
        st_mid, _, _ = run(prog_1, T)
        reread = read_controller(work)
        ok, reason = controller_reusable(reread, n_dev=n_dev)
        out["resume_reusable"] = bool(ok)
        if not ok:
            _mark_invalid(
                out,
                f"controller_reusable refused its own artifact on the "
                f"same mesh: {reason}",
            )
            return out
        prog_2 = build(dict(reread["winner"]["knobs"]))
        _, leaves_res, _ = run(prog_2, T, st=st_mid, start=T)
        out["resume_bit_parity"] = bool(
            len(leaves_full) == len(leaves_res)
            and all(
                np.array_equal(x, y)
                for x, y in zip(leaves_full, leaves_res)
            )
        )
        if not out["resume_bit_parity"]:
            _mark_invalid(
                out,
                "resume-from-artifact run did NOT replay the "
                "uninterrupted run bit-for-bit (the one-artifact "
                "resume contract)",
            )
    except Exception as exc:  # noqa: BLE001 — a failed drill is a failed row
        _mark_invalid(out, f"controller drill failed: {str(exc)[:200]}")
    finally:
        shutil.rmtree(work, ignore_errors=True)
    return out


def measure_lm_wire(cfg: dict) -> dict:
    """Config-19: compressed vs dense dp gradient exchange on the dp2xtp2
    model-axis LM layout (see CONFIGS[19] for the full row contract).

    ``value`` is the compressed (qsgd8, scoped DpExchange gather) step's
    fenced ms/step; the gates are byte-honesty and degeneracy, not speed:
    per-shard msg_bytes == the per-leaf payload sum priced over the
    tp-local shapes, scoped-vs-legacy bit parity, wire strictly below
    dense, and the seed-ensemble loss-no-worse check."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    from atomo_tpu.codecs import QsgdCodec
    from atomo_tpu.mesh.spec import MeshSpec
    from atomo_tpu.parallel.lm import DpExchange
    from atomo_tpu.parallel.model_axes import build_model_axis_program
    from atomo_tpu.training import make_optimizer
    from atomo_tpu.utils.comm_model import codec_leaf_payload_bytes

    fast = os.environ.get("ATOMO_BENCH_FAST") == "1"
    dev = jax.devices()[0]
    n_dev = min(int(cfg.get("n_dev", 4)), len(jax.devices()))
    tp = int(cfg.get("tp", 2))
    batch = int(cfg.get("batch", 8))
    lm_cfg = dict(
        vocab_size=cfg["vocab"], max_len=cfg["seq"], width=cfg["width"],
        depth=cfg["depth"], num_heads=cfg["num_heads"],
    )
    base = dict(
        metric=cfg["metric"], unit="ms/step", value=None,
        byte_reduction=None, mfu=None, flops_per_step=None,
        peak_tflops=None, platform=dev.platform, device=dev.device_kind,
        ways=n_dev // tp, chips_measured=n_dev,
        timing="dispatch-loop-scalar-fenced",
        config=dict(kind="lmwire", **lm_cfg, batch=batch, n_dev=n_dev,
                    tp=tp, layout="dp-tp", code="qsgd", bits=8),
        note=(f"compressed dp exchange on the dp{n_dev // tp}xtp{tp} LM "
              f"layout, {n_dev}-device {dev.platform} mesh; byte-match + "
              "degeneracy-parity + ensemble-loss gates in-row; not a "
              "chip-speed claim"),
    )
    if n_dev < 4 or n_dev % tp:
        base.update(
            measurement_valid=False,
            invalid_reason=f"need a dp x tp mesh (tp={tp}), have {n_dev} "
                           "devices",
        )
        return base

    spec = MeshSpec.from_layout("dp-tp", n_dev, tp)
    n_dp = n_dev // tp
    opt = make_optimizer("sgd", lr=0.01, momentum=0.9)
    codec = QsgdCodec(bits=8, bucket_size=512)
    key = jax.random.PRNGKey(1)
    toks_host = np.random.default_rng(0).integers(
        0, cfg["vocab"], size=(batch, cfg["seq"])
    ).astype(np.int32)
    steps = _env_int("ATOMO_BENCH_STEPS", 3 if fast else 10)
    seeds = 2 if fast else 3
    ens_steps = 4 if fast else 10

    def build(seed, run_codec, exchange):
        return build_model_axis_program(
            spec, lm_cfg, opt, jax.random.PRNGKey(seed), run_codec,
            exchange=exchange,
        )

    out = dict(base, measurement_valid=True, invalid_reason=None)
    try:
        # ONE compiled step per mode (jit caches on shapes; later seeds
        # re-init state only)
        prog_q = build(0, codec, DpExchange(aggregate="gather"))
        prog_leg = build(0, codec, None)
        prog_d = build(0, None, None)
        toks = prog_q.shard_tokens(toks_host)

        # --- gate 2: scoped full-stack tail == legacy tail, bit for bit
        sq, sl = prog_q.state, prog_leg.state
        mq = ml = None
        for s in range(3):
            sq, mq = prog_q.step(sq, key, toks)
            sl, ml = prog_leg.step(sl, key, toks)
        parity = all(
            np.array_equal(np.asarray(a), np.asarray(b))
            for a, b in zip(
                jax.tree_util.tree_leaves(jax.device_get(sq.params)),
                jax.tree_util.tree_leaves(jax.device_get(sl.params)),
            )
        ) and float(mq["msg_bytes"]) == float(ml["msg_bytes"])
        out["degeneracy_bit_parity"] = bool(parity)
        if not parity:
            _mark_invalid(
                out,
                "scoped DpExchange step diverged from the legacy "
                "compressed_dp_update tail (the degenerate-point contract)",
            )

        # --- gate 1: executed bytes == priced per-leaf sum over the
        # tp-LOCAL shard shapes (both static accounting)
        msg = int(float(mq["msg_bytes"]))
        dense = int(float(mq["dense_bytes"]))
        predicted = sum(
            codec_leaf_payload_bytes(
                codec, leaf.sharding.shard_shape(leaf.shape)
            )
            for leaf in jax.tree_util.tree_leaves(sq.params)
        )
        out["msg_bytes"] = msg
        out["dense_bytes"] = dense
        out["predicted_msg_bytes"] = int(predicted)
        out["byte_match"] = bool(predicted == msg)
        if not out["byte_match"]:
            _mark_invalid(
                out,
                f"executed msg_bytes {msg} != predicted per-leaf sum "
                f"{predicted} over the tp-local shapes",
            )
        # --- gate 3: the headline wire reduction
        out["byte_reduction"] = round(dense / max(msg, 1), 2)
        if msg >= dense:
            _mark_invalid(
                out, f"compressed wire {msg} B not below dense {dense} B"
            )

        # --- fenced ms/step, compressed vs dense dp wire --------------
        def timed(step_fn, st):
            st, m = step_fn(st, key, toks)  # warm (compile done above
            float(m["loss"])                # for prog_q; dense compiles)
            t0 = time.perf_counter()
            for _ in range(steps):
                st, m = step_fn(st, key, toks)
            float(m["loss"])  # the fence
            return (time.perf_counter() - t0) / steps

        out["value"] = round(timed(prog_q.step, build(1, codec,
                             DpExchange(aggregate="gather")).state) * 1e3, 3)
        out["dense_ms_per_step"] = round(
            timed(prog_d.step, build(1, None, None).state) * 1e3, 3
        )

        # --- gate 4: seed-ensemble mean final loss, qsgd8 vs dense ----
        def ensemble(step_fn, builder_codec, builder_ex):
            L = []
            for s in range(seeds):
                st = build(10 + s, builder_codec, builder_ex).state
                m = None
                for _ in range(ens_steps):
                    st, m = step_fn(st, jax.random.PRNGKey(10 + s), toks)
                L.append(float(m["loss"]))
            return L

        lq = ensemble(prog_q.step, codec, DpExchange(aggregate="gather"))
        ld = ensemble(prog_d.step, None, None)
        out["ensemble"] = dict(
            seeds=seeds, steps=ens_steps,
            qsgd_mean_loss=round(float(np.mean(lq)), 6),
            dense_mean_loss=round(float(np.mean(ld)), 6),
            per_seed_qsgd=[round(x, 6) for x in lq],
            per_seed_dense=[round(x, 6) for x in ld],
            tolerance=0.02,
        )
        worse = float(np.mean(lq)) - float(np.mean(ld))
        out["loss_no_worse"] = bool(
            worse <= 0.02 * abs(float(np.mean(ld)))
        )
        if not out["loss_no_worse"]:
            _mark_invalid(
                out,
                f"seed-ensemble qsgd8 mean loss {np.mean(lq):.6f} worse "
                f"than dense {np.mean(ld):.6f} beyond the 2% tolerance",
            )
    except Exception as exc:  # noqa: BLE001 — a failed drill is a failed row
        _mark_invalid(out, f"lm wire drill failed: {str(exc)[:200]}")
    return out


def measure_lm_delayed_overlap(cfg: dict) -> dict:
    """Config-20: delayed-overlap vs blocking compressed dp exchange on
    the dp2xpp2 model-axis LM layout (see CONFIGS[20] for the full row
    contract).

    ``value`` is the delayed step's fenced ms/step; the gates are
    schedule honesty, not speed: off-mode HLO byte identity, fused-vs-
    oracle bit parity (params AND carry payload), equal wire, and the
    bit-exact carry resume drill."""
    import tempfile

    import numpy as np

    import jax
    import jax.numpy as jnp

    from atomo_tpu.codecs import QsgdCodec
    from atomo_tpu.mesh.spec import MeshSpec
    from atomo_tpu.parallel.lm import DpExchange, place_model_axis_carry
    from atomo_tpu.parallel.model_axes import build_model_axis_program
    from atomo_tpu.parallel.replicated import DelayedState
    from atomo_tpu.training import make_optimizer
    from atomo_tpu.training.checkpoint import load_checkpoint, save_checkpoint
    from atomo_tpu.utils.comm_model import overlap_report

    fast = os.environ.get("ATOMO_BENCH_FAST") == "1"
    dev = jax.devices()[0]
    n_dev = min(int(cfg.get("n_dev", 4)), len(jax.devices()))
    pp = int(cfg.get("pp", 2))
    batch = int(cfg.get("batch", 8))
    micro = int(cfg.get("microbatches", 2))
    lm_cfg = dict(
        vocab_size=cfg["vocab"], max_len=cfg["seq"], width=cfg["width"],
        depth=cfg["depth"], num_heads=cfg["num_heads"],
    )
    base = dict(
        metric=cfg["metric"], unit="ms/step", value=None,
        byte_reduction=None, mfu=None, flops_per_step=None,
        peak_tflops=None, platform=dev.platform, device=dev.device_kind,
        ways=n_dev // pp, chips_measured=n_dev,
        timing="dispatch-loop-scalar-fenced",
        config=dict(kind="lmdelayed", **lm_cfg, batch=batch, n_dev=n_dev,
                    pp=pp, microbatches=micro, layout="dp-pp",
                    code="qsgd", bits=8, overlap="delayed"),
        note=(f"stale-by-one dp exchange on the dp{n_dev // pp}xpp{pp} LM "
              f"layout, {n_dev}-device {dev.platform} mesh; off-HLO-"
              "identity + oracle-parity + equal-wire + carry-resume gates "
              "in-row; not a chip-speed claim"),
    )
    if n_dev < 4 or n_dev % pp:
        base.update(
            measurement_valid=False,
            invalid_reason=f"need a dp x pp mesh (pp={pp}), have {n_dev} "
                           "devices",
        )
        return base

    spec = MeshSpec.from_layout("dp-pp", n_dev, pp)
    n_dp = n_dev // pp
    opt = make_optimizer("sgd", lr=0.01, momentum=0.9)
    codec = QsgdCodec(bits=8, bucket_size=512)
    key = jax.random.PRNGKey(1)
    toks_host = np.random.default_rng(0).integers(
        0, cfg["vocab"], size=(batch, cfg["seq"])
    ).astype(np.int32)
    steps = _env_int("ATOMO_BENCH_STEPS", 3 if fast else 10)
    T = 3  # resume-drill half-length

    def build(seed, exchange, **kw):
        return build_model_axis_program(
            spec, lm_cfg, opt, jax.random.PRNGKey(seed), codec,
            exchange=exchange, num_microbatches=micro, **kw
        )

    ex_delayed = DpExchange(aggregate="gather", overlap="delayed")
    out = dict(base, measurement_valid=True, invalid_reason=None)
    try:
        prog_d = build(0, ex_delayed)
        prog_b = build(0, DpExchange(aggregate="gather"))
        toks = prog_d.shard_tokens(toks_host)

        # --- gate 1: off-mode HLO byte identity (the carry threading
        # costs NOTHING when overlap is off)
        prog_off = build(0, DpExchange(aggregate="gather", overlap="off"))
        h_plain = prog_b.step.lower(prog_b.state, key, toks).as_text()
        h_off = prog_off.step.lower(prog_off.state, key, toks).as_text()
        out["off_hlo_byte_identical"] = bool(h_plain == h_off)
        if not out["off_hlo_byte_identical"]:
            _mark_invalid(
                out,
                "overlap='off' program lowered different HLO than the "
                "overlap-less DpExchange (the off-mode identity contract)",
            )

        # --- gate 2: fused delayed program == host-driven produce/apply
        # oracle over the same stale-by-one schedule, bit for bit
        oracle = build(0, ex_delayed, oracle_parts=True)
        st = prog_d.state
        md = None
        for i in range(2 * T):
            st, md = prog_d.step(st, jax.random.fold_in(key, i), toks)
        train = oracle.state.train
        payload = oracle.state.carry.payload
        valid = oracle.state.carry.valid
        for i in range(2 * T):
            k_i = jax.random.fold_in(key, i)
            new_payload, _ = oracle.step["produce"](train, k_i, toks)
            train, _ = oracle.step["apply"](train, payload, valid)
            payload, valid = new_payload, jnp.float32(1.0)

        def bit_eq(a, b):
            return all(
                np.array_equal(np.asarray(x), np.asarray(y))
                for x, y in zip(
                    jax.tree_util.tree_leaves(jax.device_get(a)),
                    jax.tree_util.tree_leaves(jax.device_get(b)),
                )
            )

        parity = bit_eq(st.train.params, train.params) and bit_eq(
            st.carry.payload, payload
        )
        out["oracle_bit_parity"] = bool(parity)
        if not parity:
            _mark_invalid(
                out,
                "fused delayed program diverged from the produce/apply "
                "oracle (params or carry payload)",
            )

        # --- gate 3: equal wire — delayed moves the SAME payload bytes
        sb, mb = prog_b.state, None
        for i in range(2):
            sb, mb = prog_b.step(sb, jax.random.fold_in(key, i), toks)
        msg_d = int(float(md["msg_bytes"]))
        msg_b = int(float(mb["msg_bytes"]))
        out["msg_bytes"] = msg_d
        out["dense_bytes"] = int(float(md["dense_bytes"]))
        out["equal_wire"] = bool(msg_d == msg_b)
        if not out["equal_wire"]:
            _mark_invalid(
                out,
                f"delayed msg_bytes {msg_d} != blocking msg_bytes {msg_b} "
                "(same codec, same payload — the equal-wire contract)",
            )
        out["byte_reduction"] = round(
            out["dense_bytes"] / max(msg_d, 1), 2
        )

        # --- gate 4: kill->restart->resume of the carry, bit-exact.
        # Deterministic per-step tokens (the CLI's host data stream is
        # stateful, so the drill drives the program directly)
        st_a = build(7, ex_delayed).state
        for i in range(2 * T):
            st_a, _ = prog_d.step(st_a, jax.random.fold_in(key, i), toks)
        st_b = build(7, ex_delayed).state
        for i in range(T):
            st_b, _ = prog_d.step(st_b, jax.random.fold_in(key, i), toks)
        with tempfile.TemporaryDirectory() as tmp:
            save_checkpoint(tmp, st_b)
            fresh = build(7, ex_delayed)  # the restarted process
            host = load_checkpoint(tmp, jax.device_get(fresh.state))
        from jax.sharding import NamedSharding

        train_r = jax.tree_util.tree_map(
            lambda leaf, sp: jax.device_put(
                leaf, NamedSharding(fresh.mesh, sp)
            ),
            host.train, fresh.state_specs,
        )
        st_r = DelayedState(
            train=train_r,
            carry=place_model_axis_carry(fresh.mesh, host.carry),
        )
        for i in range(T, 2 * T):
            st_r, _ = fresh.step(st_r, jax.random.fold_in(key, i), toks)
        resumed = bit_eq(st_a.train.params, st_r.train.params) and bit_eq(
            st_a.carry.payload, st_r.carry.payload
        )
        out["resume_bit_exact"] = bool(resumed)
        if not resumed:
            _mark_invalid(
                out,
                "kill->restart->resume diverged from the uninterrupted "
                "run (params or carry payload)",
            )

        # --- fenced ms/step, delayed vs blocking (equal wire) ---------
        def timed(step_fn, st0):
            st0, m = step_fn(st0, key, toks)  # warm
            float(m["loss"])
            t0 = time.perf_counter()
            for _ in range(steps):
                st0, m = step_fn(st0, key, toks)
            float(m["loss"])  # the fence
            return (time.perf_counter() - t0) / steps

        out["value"] = round(timed(prog_d.step, build(1, ex_delayed).state) * 1e3, 3)
        out["blocking_ms_per_step"] = round(
            timed(prog_b.step, build(1, DpExchange(aggregate="gather")).state)
            * 1e3, 3
        )
        # the modelled account the controller prices from (CPU dispatch
        # cannot show the overlap win; the model states what a real
        # fabric buys, bubble credit included)
        out["overlap_model"] = overlap_report(
            dense_bytes=float(out["dense_bytes"]),
            payload_bytes=float(msg_d),
            ways=n_dp,
            fabric_bw=1e9,
            compute_s=out["blocking_ms_per_step"] / 1e3,
            pipeline_stages=pp,
            pipeline_microbatches=micro,
        )
    except Exception as exc:  # noqa: BLE001 — a failed drill is a failed row
        _mark_invalid(out, f"lm delayed drill failed: {str(exc)[:200]}")
    return out


def measure_scenarios(cfg: dict) -> dict:
    """Config-10: the scenario matrix (autopilot regression gate).

    Every cell is measured by the SAME probe runner ``--auto tune`` uses
    (tuning.probe.probe_candidate — real step builders, fenced dispatch
    loops), so a bench regression here is a regression in exactly the
    numbers the autopilot decides from. The compressed 4-device cells
    additionally assert the gather-vs-ring aggregation-operator bit
    parity in-row; the per-network recommendations combine the matrix's
    own measured single-chip anchors with the comm model's fabric term
    (comm_model.recommend_for_scenario)."""
    import jax
    import jax.numpy as jnp

    from atomo_tpu.codecs import QsgdCodec, SvdCodec
    from atomo_tpu.models import get_model
    from atomo_tpu.parallel import make_mesh
    from atomo_tpu.training import create_state, make_optimizer
    from atomo_tpu.tuning.probe import (
        byte_budget,
        model_init_fn,
        probe_candidate,
    )
    from atomo_tpu.utils.comm_model import (
        FABRICS,
        recommend_for_scenario,
    )

    fast = os.environ.get("ATOMO_BENCH_FAST") == "1"
    dev = jax.devices()[0]
    n_mesh = min(int(cfg.get("n_dev", 4)), len(jax.devices()))
    batch = int(cfg.get("batch", 8))
    steps = _env_int("ATOMO_BENCH_STEPS", 3 if fast else 5)
    reps = 1 if fast else 2
    budget_s = _env_float("ATOMO_SCENARIO_BUDGET_S", 300.0)
    t0_all = time.perf_counter()

    networks = {"lenet": (28, 28, 1)}
    if not fast:
        # a resnet18 cell costs multi-minute CPU compiles; fast mode
        # (bench_smoke.sh's short drills) keeps the lenet cells only
        networks["resnet18"] = (32, 32, 3)

    def codecs():
        return {
            "dense": None,
            "qsgd8": QsgdCodec(bits=8, bucket_size=512),
            "svd3": SvdCodec(rank=3),
        }

    base = dict(
        metric=cfg["metric"], unit="ms/step", value=None,
        vs_baseline=None, baseline="none", byte_reduction=None, mfu=None,
        flops_per_step=None, peak_tflops=None, platform=dev.platform,
        device=dev.device_kind, ways=n_mesh, chips_measured=n_mesh,
        timing="dispatch-loop-scalar-fenced",
        config=dict(kind="scenarios", batch=batch, n_dev=n_mesh,
                    steps=steps, networks=sorted(networks),
                    codecs=sorted(codecs())),
        note=(f"autopilot regression matrix on a {n_mesh}-device "
              f"{dev.platform} mesh; semantics + probe-runner evidence, "
              "not a chip-speed row"),
    )
    if n_mesh < 2:
        base.update(measurement_valid=False,
                    invalid_reason="single device: no mesh for the matrix")
        return base

    out = dict(base, measurement_valid=True, invalid_reason=None)
    cells, skipped = [], []
    parities_ok = True
    budgets_by_net = {}
    measured_1dev = {}
    try:
        for net, shape in networks.items():
            opt = make_optimizer("sgd", lr=0.01, momentum=0.9)
            model = get_model(net, 10)
            rng = jax.random.PRNGKey(0)
            sample = jnp.zeros((1,) + shape, jnp.float32)
            _init_params = model_init_fn(model, sample)
            budgets_by_net[net] = {}
            measured_1dev[net] = {}
            for cname, codec in codecs().items():
                db, pb = byte_budget(codec, _init_params)
                budgets_by_net[net][cname] = (db, pb)
                for nd in (1, n_mesh):
                    if time.perf_counter() - t0_all > budget_s:
                        skipped.append(f"{net}/{nd}dev/{cname}")
                        continue
                    cand = {"superstep": 1}
                    if nd > 1:
                        cand.update(aggregate="gather", overlap="off")
                    row = probe_candidate(
                        cand, model=model, optimizer=opt, codec=codec,
                        n_dev=nd, sample_shape=shape, num_classes=10,
                        batch=batch, steps=steps, reps=reps,
                    )
                    cell = {
                        "network": net, "n_dev": nd, "code": cname,
                        "ms_per_step": row["measured_ms_per_step"],
                        "sync_ok": row["sync_ok"],
                        "byte_reduction": (
                            round(db / pb, 2) if pb else None
                        ),
                    }
                    if not row["sync_ok"]:
                        _mark_invalid(
                            out, f"cell {net}/{nd}dev/{cname}: fence "
                            "scalar not finite",
                        )
                    if nd == 1:
                        measured_1dev[net][cname] = (
                            row["measured_ms_per_step"]
                        )
                    if nd > 1 and codec is not None:
                        # the autopilot-safety invariant: gather's
                        # decode-mean and ring's streamed fold must be
                        # BIT-identical (PR-3 contract) — what makes a
                        # mid-run gather<->ring re-tune trajectory-safe
                        params = jax.device_get(
                            create_state(model, opt, rng,
                                         jnp.zeros((batch,) + shape))
                        ).params
                        grads = jax.tree_util.tree_map(
                            lambda a: jax.random.normal(
                                jax.random.PRNGKey(7), a.shape,
                                jnp.float32,
                            ),
                            params,
                        )
                        parity = gather_vs_ring_parity(
                            make_mesh(nd), codec, grads,
                            jax.random.PRNGKey(1), nd,
                        )
                        cell["aggregation_bit_parity"] = parity
                        parities_ok &= parity
                        if not parity:
                            _mark_invalid(
                                out,
                                f"cell {net}/{nd}dev/{cname}: ring "
                                "aggregation operator is NOT bit-"
                                "identical to gather's decode-mean "
                                "(the PR-3 contract the autopilot's "
                                "re-tune relies on)",
                            )
                    cells.append(cell)
        out["cells"] = cells
        out["skipped_cells"] = skipped
        out["aggregation_bit_parity"] = parities_ok
        # per-(network, fabric) recommended configs from the matrix's own
        # measured single-chip anchors + the analytic fabric term
        recs = {}
        for net, anchors in measured_1dev.items():
            if "dense" not in anchors:
                continue
            recs[net] = {}
            for label, bw in sorted(FABRICS.items()):
                recs[net][label] = recommend_for_scenario(
                    codec_budgets=budgets_by_net[net],
                    measured_ms=anchors,
                    ways=n_mesh,
                    fabric_bw=bw,
                )
        out["recommendations"] = recs
        head = next(
            (c for c in cells
             if c["network"] == "lenet" and c["n_dev"] == n_mesh
             and c["code"] == "qsgd8"),
            cells[0] if cells else None,
        )
        if head is not None:
            out["value"] = head["ms_per_step"]
            out["byte_reduction"] = head["byte_reduction"]
        if not cells:
            _mark_invalid(out, "no cells completed inside the budget")
    except Exception as exc:  # noqa: BLE001 — a failed matrix is a failed row
        _mark_invalid(out, f"scenario matrix failed: {str(exc)[:200]}")
    return out


def two_tier_parity(mesh, codec, plan, grads_by_chip, step_key,
                    n_outer: int, n_inner: int,
                    bucket_size: int = 65536) -> bool:
    """Per-plan twin of :func:`gather_vs_ring_parity`: the executed
    two-level operator (topology.execute.planned_two_level_mean, outer
    gather forced to the canonical unfused decode order) must be
    BIT-identical to the canonical decode-order oracle in SPMD form
    (two_level_canonical_mean: gather + unfused decode at every
    compressed tier — the ring-vs-gather precedent, SPMD program against
    SPMD program) over the same per-chip gradients and keys.
    tests/test_topology.py is the full oracle; this is config 11's
    in-row evidence."""
    import numpy as np

    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from atomo_tpu.topology.execute import (
        inner_codec_key,
        outer_codec_key,
        planned_two_level_mean,
        two_level_canonical_mean,
    )

    axis, inner_axis = mesh.axis_names[0], mesh.axis_names[1]

    def make_fn(canonical):
        def fn(x):
            o = jax.lax.axis_index(axis)
            my = o * n_inner + jax.lax.axis_index(inner_axis)
            grads = jax.lax.switch(
                my,
                [lambda c=c: grads_by_chip[c]
                 for c in range(len(grads_by_chip))],
            )
            ki = inner_codec_key(step_key, my)
            ko = outer_codec_key(step_key, o)
            if canonical:
                return two_level_canonical_mean(
                    codec, plan, grads, ki, ko,
                    axis=axis, inner_axis=inner_axis,
                    n_inner=n_inner, n_outer=n_outer,
                )
            mean, _, _, _ = planned_two_level_mean(
                codec, plan, grads, ki, ko,
                axis=axis, inner_axis=inner_axis,
                n_inner=n_inner, n_outer=n_outer,
                ring_bucket_size=bucket_size, unfused_decode=True,
            )
            return mean

        return fn

    def run(fn):
        return jax.jit(jax.shard_map(
            fn, mesh=mesh, in_specs=(P((axis, inner_axis)),), out_specs=P(),
            check_vma=False,
        ))(jnp.zeros((n_outer * n_inner,)))

    got = run(make_fn(False))
    want = run(make_fn(True))
    return bool(all(
        np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(
            jax.tree_util.tree_leaves(jax.device_get(got)),
            jax.tree_util.tree_leaves(jax.device_get(want)),
        )
    ))


def measure_two_tier(cfg: dict) -> dict:
    """Config-11: the two-tier topology matrix (plan-space evidence).

    Every plan is measured by the SAME probe runner ``--auto tune`` uses
    (tuning.probe.probe_candidate with ``dcn_ways`` — real two-tier step
    builders, fenced dispatch loops). The row records, per plan: measured
    vs predicted ms/step (the two-tier comm model, calibration warning
    attached when they disagree >2x — on a CPU mesh they will, the row
    says so instead of hiding it), PER-TIER predicted wire bytes vs the
    executed program's own byte accounting, and the bit-parity assert
    against the canonical decode-order oracle. A mini ``tune()`` with
    ``dcn_ways`` lands a probed decision artifact naming hierarchical
    candidates in-row."""
    import jax
    import jax.numpy as jnp

    from atomo_tpu.codecs import QsgdCodec, encode_tree
    from atomo_tpu.models import get_model
    from atomo_tpu.parallel import make_mesh
    from atomo_tpu.topology.fabric import resolve_two_tier
    from atomo_tpu.topology.schedule import (
        PLAN_NAMES,
        plan_from_name,
        plan_wire_bytes,
        predict_plan_step_s,
    )
    from atomo_tpu.training import create_state, make_optimizer
    from atomo_tpu.tuning.autopilot import tune as autopilot_tune
    from atomo_tpu.tuning.probe import (
        byte_budget,
        model_init_fn,
        probe_candidate,
    )
    from atomo_tpu.utils.comm_model import (
        calibration_warning,
        ring_allgather_wire_bytes,
        ring_allreduce_wire_bytes,
        ring_stream_wire_bytes,
    )

    fast = os.environ.get("ATOMO_BENCH_FAST") == "1"
    dev = jax.devices()[0]
    n_mesh = min(int(cfg.get("n_dev", 4)), len(jax.devices()))
    k_dcn = int(cfg.get("dcn_ways", 2))
    batch = int(cfg.get("batch", 8))
    steps = _env_int("ATOMO_BENCH_STEPS", 3 if fast else 5)
    reps = 1 if fast else 2
    shape = (28, 28, 1)
    plans = ("psum+gather", "cring+ring") if fast else PLAN_NAMES

    base = dict(
        metric=cfg["metric"], unit="ms/step", value=None,
        vs_baseline=None, baseline="none", byte_reduction=None, mfu=None,
        flops_per_step=None, peak_tflops=None, platform=dev.platform,
        device=dev.device_kind, ways=n_mesh, chips_measured=n_mesh,
        timing="dispatch-loop-scalar-fenced",
        config=dict(kind="twotier", batch=batch, n_dev=n_mesh,
                    dcn_ways=k_dcn, steps=steps, plans=list(plans)),
        note=(f"planned two-level schedules on a forced ({k_dcn}x"
              f"{n_mesh // max(k_dcn, 1)}) {dev.platform} mesh; semantics "
              "+ per-tier model-honesty evidence, not a chip-speed row "
              "(a CPU mesh has no real tiers — the calibration fields "
              "say how far the analytic model is here)"),
    )
    if n_mesh < 4 or k_dcn < 2 or n_mesh % k_dcn:
        base.update(
            measurement_valid=False,
            invalid_reason=f"need a (dcn x ici) mesh; have {n_mesh} devices",
        )
        return base

    out = dict(base, measurement_valid=True, invalid_reason=None)
    n_inner = n_mesh // k_dcn
    fabric2 = resolve_two_tier("auto", dcn_ways=k_dcn, n_dev=n_mesh)
    out["fabric"] = fabric2.describe()
    try:
        model = get_model("lenet", 10)
        opt = make_optimizer("sgd", lr=0.01, momentum=0.9)
        codec = QsgdCodec(bits=8, bucket_size=512)
        sample = jnp.zeros((1,) + shape, jnp.float32)
        dense_b, payload_b = byte_budget(codec, model_init_fn(model, sample))
        out["byte_reduction"] = round(dense_b / payload_b, 2)

        # real per-chip gradient trees for the parity oracle + the
        # runtime byte accounting (shaped like the params, distinct data)
        params = jax.device_get(
            create_state(model, opt, jax.random.PRNGKey(0),
                         jnp.zeros((batch,) + shape)).params
        )
        grads_by_chip = [
            jax.tree_util.tree_map(
                lambda a, c=c: jax.random.normal(
                    jax.random.fold_in(jax.random.PRNGKey(7), c),
                    a.shape, jnp.float32,
                ),
                params,
            )
            for c in range(n_mesh)
        ]
        # payload accounting over the REAL gradient trees (vs the byte
        # budget's model-init eval_shape) — the "measured" side of the
        # inner-tier byte comparison
        from atomo_tpu.codecs import tree_nbytes as _tree_nbytes

        payload_rt = _tree_nbytes(jax.eval_shape(
            lambda g: encode_tree(codec, jax.random.PRNGKey(1), g)[0],
            grads_by_chip[0],
        ))
        mesh2 = make_mesh(n_mesh, axes=(("dcn", k_dcn), ("ici", n_inner)))
        step_key = jax.random.PRNGKey(11)

        rows = []
        parities_ok = True
        for pname in plans:
            plan = plan_from_name(pname)
            cand = {
                "aggregate": "hierarchical", "plan": pname,
                "overlap": "off", "superstep": 1, "name": f"hier[{pname}]",
            }
            probe = probe_candidate(
                cand, model=model, optimizer=opt, codec=codec,
                n_dev=n_mesh, sample_shape=shape, num_classes=10,
                batch=batch, steps=steps, reps=reps, dcn_ways=k_dcn,
            )
            pred_s = predict_plan_step_s(
                plan, dense_bytes=dense_b, payload_bytes=payload_b,
                fabric=fabric2,
            )
            wires = plan_wire_bytes(
                plan, dense_bytes=dense_b, payload_bytes=payload_b,
                fabric=fabric2,
            )
            # measured per-tier wire bytes: the same honest-accounting
            # formulas applied to the EXECUTED program's byte accounting
            # (its msg_bytes metric on the slow tier; the runtime encode
            # stats on the fast tier) — must agree with the eval_shape
            # prediction or the model is lying about this program
            msg_meas = probe.get("measured_msg_bytes")
            dense_meas = probe.get("measured_dense_bytes", dense_b)
            if plan.inner == "psum":
                inner_meas = ring_allreduce_wire_bytes(dense_meas, n_inner)
            else:
                inner_meas = ring_stream_wire_bytes(
                    payload_rt, dense_meas, n_inner
                )
            if plan.outer == "gather":
                outer_meas = ring_allgather_wire_bytes(msg_meas, k_dcn)
            elif plan.outer == "ring":
                outer_meas = ring_stream_wire_bytes(
                    msg_meas, dense_meas, k_dcn
                )
            else:  # dense fallback: msg_bytes IS the dense gradient
                outer_meas = ring_allreduce_wire_bytes(msg_meas, k_dcn)
            tiers = {
                "inner": {
                    "predicted_mb": round(wires["inner_bytes"] / 1e6, 4),
                    "measured_mb": round(inner_meas / 1e6, 4),
                    "predicted_ms": round(
                        fabric2.tier_time_s(
                            wires["inner_bytes"], "inner",
                            wires["inner_hops"],
                        ) * 1e3, 4,
                    ),
                },
                "outer": {
                    "predicted_mb": round(wires["outer_bytes"] / 1e6, 4),
                    "measured_mb": round(outer_meas / 1e6, 4),
                    "predicted_ms": round(
                        fabric2.tier_time_s(
                            wires["outer_bytes"], "outer",
                            wires["outer_hops"],
                        ) * 1e3, 4,
                    ),
                },
            }
            bytes_match = (
                abs(tiers["inner"]["predicted_mb"]
                    - tiers["inner"]["measured_mb"]) < 1e-3
                and abs(tiers["outer"]["predicted_mb"]
                        - tiers["outer"]["measured_mb"]) < 1e-3
            )
            if not bytes_match:
                _mark_invalid(
                    out,
                    f"plan {pname}: comm-model per-tier wire bytes "
                    "disagree with the executed program's accounting",
                )
            parity = two_tier_parity(
                mesh2, codec, plan, grads_by_chip, step_key,
                n_outer=k_dcn, n_inner=n_inner,
            )
            parities_ok &= parity
            if not parity:
                _mark_invalid(
                    out,
                    f"plan {pname}: executed operator is NOT bit-identical "
                    "to the canonical decode-order oracle",
                )
            if not probe.get("sync_ok", True):
                _mark_invalid(
                    out, f"plan {pname}: fence scalar not finite"
                )
            rows.append({
                "plan": pname,
                "ms_per_step": probe["measured_ms_per_step"],
                "predicted_ms_per_step": round(pred_s * 1e3, 4),
                "calibration": calibration_warning(
                    pred_s, probe["measured_ms_per_step"] / 1e3,
                    label=f"plan {pname}",
                ),
                "tiers": tiers,
                "tier_bytes_match": bytes_match,
                "aggregation_bit_parity": parity,
                "sync_ok": probe.get("sync_ok"),
            })
        out["plans"] = rows
        out["aggregation_bit_parity"] = parities_ok
        legacy = next((r for r in rows if r["plan"] == "psum+gather"), None)
        if legacy is not None:
            out["value"] = legacy["ms_per_step"]

        # the probed autopilot decision on the same two-tier mesh: a very
        # slow outer fabric makes the hierarchical candidates the
        # predicted front-runners, so the probed set names them
        tune_doc = autopilot_tune(
            model=model, optimizer=opt, codec=codec,
            model_init_fn=model_init_fn(model, sample), n_dev=n_mesh,
            sample_shape=shape, num_classes=10, batch=batch,
            fabric="ici:0.05", dcn_ways=k_dcn,
            plan_names=plans if fast else None,
            allow_psum=False, allow_overlap=False, allow_ring=False,
            superstep_options=(1,), probe_top=2, probe_steps=steps,
            probe_reps=1, log_fn=lambda m: print(m, file=sys.stderr),
        )
        probed = [r["name"] for r in tune_doc["rows"] if r.get("probed")]
        hier_probed = [n for n in probed if n.startswith("hier[")]
        out["tune_decision"] = {
            "winner": tune_doc.get("winner"),
            "why": tune_doc.get("why"),
            "probed": probed,
            "hierarchical_probed": hier_probed,
        }
        if not hier_probed:
            _mark_invalid(
                out, "mini-tune probed no hierarchical candidate"
            )
    except Exception as exc:  # noqa: BLE001 — a failed matrix is a failed row
        _mark_invalid(out, f"two-tier matrix failed: {str(exc)[:200]}")
    return out


def measure_fleet(cfg: dict) -> dict:
    """Config-21: the host-level fleet control plane drilled with real
    processes (see CONFIGS[21] for the full row contract).

    ``value`` is the 2-process form→partition→shrink→heal→regrow drill's
    wall seconds. The two in-row gates: ``fleet_report_strict_ok``
    (``report --fleet --strict`` rc=0 over the drill's train_dir) and
    ``resume_bit_exact`` (live die@ shrink + kill→restart→resume replays
    bit-identical checkpoints vs the uninterrupted live run)."""
    import concurrent.futures
    import shutil
    import tempfile

    import numpy as np

    n_hosts = int(cfg.get("n_hosts", 2))
    rounds = int(cfg.get("rounds", 400))
    period = float(cfg.get("period_s", 0.05))
    patience = int(cfg.get("patience", 4))
    stop_epoch = int(cfg.get("stop_epoch", 2))
    chaos = "partition@3:0-1:0.8"
    base = dict(
        metric=cfg["metric"], unit="s", value=None,
        byte_reduction=None, mfu=None, flops_per_step=None,
        peak_tflops=None, platform="host", device="processes",
        ways=n_hosts, chips_measured=0,
        timing="wall-clock-2-process-drill",
        config=dict(kind="fleet", n_hosts=n_hosts, rounds=rounds,
                    period_s=period, patience=patience,
                    stop_epoch=stop_epoch, chaos=chaos),
        note=(f"host-level control plane: {n_hosts} REAL processes form "
              "a fleet over one shared train_dir, partition@ cuts host 1 "
              "off the lease store, the leader shrinks, heal re-admits "
              "(epoch 0->1->2); gated on `report --fleet --strict` rc=0 "
              "and a bit-exact live-reshard kill->restart->resume drill "
              "in-row; semantics evidence, not a chip-speed claim"),
    )
    repo = os.path.dirname(os.path.abspath(__file__))
    env = {
        **os.environ,
        "JAX_PLATFORMS": "cpu",
        "PYTHONPATH": repo + os.pathsep + os.environ.get("PYTHONPATH", ""),
    }
    # the resume drill crosses process generations; the shared compile
    # cache's round-trip is not bit-faithful on the CPU backend (measured
    # — the config-20 caveat), so the children run cache-cold
    env["JAX_ENABLE_COMPILATION_CACHE"] = "false"

    work = tempfile.mkdtemp(prefix="atomo_fleet_bench_")
    try:
        # ---- gate 1: the 2-process lease drill, report-gated ----
        d = os.path.join(work, "fleet")
        t0 = time.perf_counter()
        procs = [
            subprocess.Popen(
                [sys.executable, "-m", "atomo_tpu.fleet.launcher",
                 "--train-dir", d, "--host-id", str(i),
                 "--n-hosts", str(n_hosts), "--rounds", str(rounds),
                 "--period", str(period), "--patience", str(patience),
                 "--stop-epoch", str(stop_epoch), "--max-seconds", "60",
                 "--chaos", chaos],
                env=env, cwd=repo, stdout=subprocess.PIPE,
                stderr=subprocess.PIPE, text=True)
            for i in range(n_hosts)
        ]
        results = {}
        # drain concurrently: a full stderr pipe on the not-yet-drained
        # member would wedge a sequential communicate()
        with concurrent.futures.ThreadPoolExecutor(n_hosts) as pool:
            outs = list(pool.map(lambda p: p.communicate(timeout=120),
                                 procs))
        for p, (out, err) in zip(procs, outs):
            if p.returncode != 0:
                base.update(measurement_valid=False,
                            invalid_reason="fleet member process failed",
                            error=err[-2000:])
                return base
            for line in out.splitlines():
                if line.startswith("RESULT "):
                    r = json.loads(line[len("RESULT "):])
                    results[r["host"]] = r
        drill_s = time.perf_counter() - t0
        if sorted(results) != list(range(n_hosts)):
            base.update(measurement_valid=False,
                        invalid_reason="missing RESULT line from a member")
            return base
        full_cycle = all(
            r["member"] and r["epoch"] == stop_epoch
            and r["world"] == n_hosts for r in results.values()
        )
        rep = subprocess.run(
            [sys.executable, "-m", "atomo_tpu.cli", "report",
             "--train-dir", d, "--fleet", "--strict"],
            env=env, cwd=repo, capture_output=True, text=True,
            timeout=120,
        )
        report_ok = rep.returncode == 0 and "consistency: OK" in rep.stdout

        # ---- gate 2: live reshard + kill->restart->resume, bit-exact ----
        train = [
            sys.executable, "-m", "atomo_tpu.cli", "train",
            "--synthetic", "--dataset", "mnist", "--network", "lenet",
            "--batch-size", "12", "--eval-freq", "0", "--save-freq", "2",
            "--log-interval", "1", "--code", "qsgd",
            "--quantization-level", "8", "--aggregate", "gather",
            "--grad-guard", "--elastic", "--elastic-patience", "2",
            "--n-devices", "4", "--max-steps", "10",
        ]
        tenv = dict(
            env, XLA_FLAGS="--xla_force_host_platform_device_count=4"
        )
        d1 = os.path.join(work, "live")
        p1 = subprocess.run(
            train + ["--train-dir", d1, "--chaos", "die@3:1"],
            env=tenv, cwd=repo, capture_output=True, text=True,
            timeout=300,
        )
        d2 = os.path.join(work, "crashed")
        p2 = subprocess.run(
            train + ["--train-dir", d2, "--chaos", "die@3:1,kill@7",
                     "--max-restarts", "1", "--restart-backoff", "0.05"],
            env=tenv, cwd=repo, capture_output=True, text=True,
            timeout=300,
        )
        resume_ok = (
            p1.returncode == 0 and p2.returncode == 0
            and "Elastic: LIVE shrink 4 -> 3" in p1.stdout
            and "reshaped before the crash; restarting with --n-devices 3"
            in p2.stdout
        )
        if resume_ok:
            from atomo_tpu.training.checkpoint import _read_state_dict

            import jax as _jax

            for s in (8, 10):
                la = _jax.tree_util.tree_leaves(_read_state_dict(d1, s))
                lb = _jax.tree_util.tree_leaves(_read_state_dict(d2, s))
                if len(la) != len(lb) or not all(
                    np.array_equal(np.asarray(x), np.asarray(y))
                    for x, y in zip(la, lb)
                ):
                    resume_ok = False

        base.update(
            value=round(drill_s, 3),
            vs_baseline=None, baseline="none",
            fleet_full_cycle=full_cycle,
            fleet_report_strict_ok=report_ok,
            fleet_cut_rounds=int(results[n_hosts - 1].get("cut_rounds", 0)),
            resume_bit_exact=resume_ok,
            measurement_valid=bool(full_cycle and report_ok and resume_ok),
        )
        if not base["measurement_valid"]:
            failed = [name for name, ok in [
                ("full_cycle", full_cycle), ("report_strict", report_ok),
                ("resume_bit_exact", resume_ok)] if not ok]
            base["invalid_reason"] = f"gate(s) failed: {', '.join(failed)}"
        return base
    finally:
        shutil.rmtree(work, ignore_errors=True)


def measure_ours(cfg: dict) -> dict:
    import jax
    import jax.numpy as jnp

    from atomo_tpu.codecs import get_codec
    from atomo_tpu.models import get_model
    from atomo_tpu.training import create_state, make_optimizer, make_train_step

    if cfg.get("kind") == "lm":
        return measure_lm(cfg)
    if cfg.get("kind") == "loop":
        return measure_loop(cfg)
    if cfg.get("kind") == "ringcmp":
        return measure_ring_compare(cfg)
    if cfg.get("kind") == "overlapcmp":
        return measure_overlap_compare(cfg)
    if cfg.get("kind") == "scenarios":
        return measure_scenarios(cfg)
    if cfg.get("kind") == "twotier":
        return measure_two_tier(cfg)
    if cfg.get("kind") == "streamenc":
        return measure_stream_encode(cfg)
    if cfg.get("kind") == "sparsewire":
        return measure_sparse_wire(cfg)
    if cfg.get("kind") == "fabricprobe":
        return measure_fabric_probe(cfg)
    if cfg.get("kind") == "adaptivebudget":
        return measure_adaptive_budget(cfg)
    if cfg.get("kind") == "shardedupd":
        return measure_sharded_update_memory(cfg)
    if cfg.get("kind") == "quorum":
        return measure_quorum_absorption(cfg)
    if cfg.get("kind") == "controller":
        return measure_controller_joint(cfg)
    if cfg.get("kind") == "lmwire":
        return measure_lm_wire(cfg)
    if cfg.get("kind") == "lmdelayed":
        return measure_lm_delayed_overlap(cfg)
    if cfg.get("kind") == "fleet":
        return measure_fleet(cfg)

    model = get_model(cfg["network"], 10)
    opt = make_optimizer("sgd", lr=0.01, momentum=0.9)
    rng = jax.random.PRNGKey(0)
    h, w, c = cfg["input"]
    images = jax.random.uniform(rng, (cfg["batch"], h, w, c), jnp.float32)
    labels = jax.random.randint(rng, (cfg["batch"],), 0, 10)
    state = create_state(model, opt, rng, images)
    codec = get_codec(cfg["code"], svd_rank=cfg.get("rank", 3),
                      quantization_level=4)
    step = make_train_step(model, opt, codec=codec)
    key = jax.random.PRNGKey(1)

    flops = _flops_per_step(step, state, key, images, labels)
    # Sanity anchor for `flops` (XLA cost_analysis): batch-128 CIFAR
    # ResNet-18 is ~0.56 GFLOP/sample forward, fwd+bwd ≈ 3x -> ~2.2e11
    # FLOPs/step analytically; cost_analysis should land within ~2x of that
    # (it counts the whole program incl. encode/decode).

    def timed(step_fn, st):
        """ms/step with a forced device->host sync (utils.tracing.fence_tree's
        discipline: a scalar fetch from the final step's metrics is a fence
        on every backend and doubles as the finiteness check; the
        sequential state dependency makes it transitively fence all STEPS
        steps).

        Two measurements:
          * scanned — STEPS steps under ONE lax.scan dispatch, the
            idiomatic jitted-training-loop shape. This is pure device time
            and the headline `value`.
          * dispatch loop — one dispatch per step: device time plus the
            per-dispatch host cost (not measured on the stock TPU backend
            yet); emitted as `dispatch_ms_per_step` for transparency.
        """

        @jax.jit
        def multi(s0, k, im, lb):
            def body(s, _):
                s, m = step_fn(s, k, im, lb)
                return s, m["loss"]
            s_out, losses = jax.lax.scan(body, s0, None, length=STEPS)
            return s_out, losses[-1]

        for _ in range(WARMUP):
            st, m = step_fn(st, key, images, labels)
        float(m["loss"])  # drain warmup + per-step compile
        t0 = time.perf_counter()
        for _ in range(STEPS):
            st, m = step_fn(st, key, images, labels)
        disp_sync = float(m["loss"])  # the fence
        disp_dt = (time.perf_counter() - t0) / STEPS

        st, last = multi(st, key, images, labels)
        float(last)  # compile + warm the scanned program
        # best-of-3: this chip is shared — contention inflates individual
        # runs ~5x (measured: the same 33 MB elementwise op at 0.28 ms and
        # 1.41 ms minutes apart); the MIN is the standard contention-robust
        # estimator of true device time
        dt, scan_sync = float("inf"), float("nan")
        for _ in range(REPS):
            t0 = time.perf_counter()
            st, last = multi(st, key, images, labels)
            scan_sync = float(last)  # one dispatch fences all STEPS steps
            dt = min(dt, (time.perf_counter() - t0) / STEPS)

        sync = scan_sync if math.isfinite(disp_sync) else disp_sync
        return dt, disp_dt, st, m, sync

    dt, disp_dt, state, metrics, sync = timed(step, state)

    dense = sum(
        l.size * l.dtype.itemsize for l in jax.tree_util.tree_leaves(state.params)
    )
    reduction = dense / max(int(metrics["msg_bytes"]), 1)

    # isolate the ENCODE phase (VERDICT r3 next-round #3: "encode_ms
    # printed per config"): time encode_tree alone on a real gradient
    # pytree, scan-fenced like everything else.
    encode_ms = None
    try:
        from atomo_tpu.codecs import encode_tree

        def _loss(p):
            variables = {"params": p}
            if jax.tree_util.tree_leaves(state.batch_stats):
                variables["batch_stats"] = state.batch_stats
            out_ = model.apply(variables, images, train=False)
            return jnp.mean(
                (out_ - jax.nn.one_hot(labels, out_.shape[-1])) ** 2
            )

        grads = jax.jit(jax.grad(_loss))(state.params)

        @jax.jit
        def enc_many(k, g):
            def body(acc, i):
                gg = jax.tree_util.tree_map(lambda a: a + acc * 1e-30, g)
                p, _ = encode_tree(codec, jax.random.fold_in(k, i), gg)
                # EVERY leaf must stay live: summing only floating leaves
                # would let XLA dead-code-eliminate the uint32 bit-packing
                # that IS the bulk of a QSGD encode (review r4 finding)
                tot = jnp.float32(0)
                for l in jax.tree_util.tree_leaves(p):
                    if jnp.issubdtype(l.dtype, jnp.floating):
                        tot = tot + jnp.vdot(l, l) * 1e-20
                    else:
                        tot = tot + jnp.sum(l.astype(jnp.float32)) * 1e-30
                return tot, None

            acc, _ = jax.lax.scan(body, jnp.float32(0), jnp.arange(STEPS))
            return acc

        float(enc_many(key, grads))  # compile + warm
        best = float("inf")
        for _ in range(REPS):
            t0 = time.perf_counter()
            esync = float(enc_many(key, grads))
            best = min(best, (time.perf_counter() - t0) / STEPS)
            if not math.isfinite(esync):
                raise RuntimeError("encode sync scalar not finite")
        encode_ms = round(best * 1e3, 3)
    except Exception:
        encode_ms = None  # reported as absent, never fabricated

    dev = jax.devices()[0]
    peak = _peak_tflops(dev.device_kind) if dev.platform == "tpu" else None
    mfu = (flops / dt / (peak * 1e12)) if (flops and peak) else None

    valid, invalid_reason = True, None
    if not math.isfinite(sync):
        valid, invalid_reason = False, f"sync scalar not finite: {sync}"
    elif mfu is not None and not (0.0 < mfu < 1.0):
        # >100% of peak is physically impossible; it means the timing loop
        # did not actually fence execution (the r2 failure mode)
        valid, invalid_reason = False, f"mfu {mfu:.3f} outside (0, 1)"

    out = dict(
        metric=cfg["metric"],
        value=round(dt * 1e3, 3),
        unit="ms/step",
        # the EXACT measurement recipe, so rows from different sessions
        # are comparable or visibly not (VERDICT r3 weak #1: config 3's
        # two same-round dense baselines disagreed 4.7x with no recorded
        # config to reconcile them against)
        config=dict(
            network=cfg["network"], input=list(cfg["input"]),
            batch=cfg["batch"], code=cfg["code"], rank=cfg.get("rank"),
            warmup=WARMUP, steps=STEPS, augment=False,
            codec_defaults=repr(codec),
        ),
        byte_reduction=round(reduction, 2),
        mfu=round(mfu, 4) if mfu is not None else None,
        flops_per_step=flops,
        peak_tflops=peak,
        platform=dev.platform,
        device=dev.device_kind,
        ways=cfg.get("ways", 1),
        encode_ms_per_step=encode_ms,
        dispatch_ms_per_step=round(disp_dt * 1e3, 3),
        chips_measured=1,  # step time measured on the one locally attached
        # chip; `ways` is only the reference cluster width this config models
        measurement_valid=valid,
        invalid_reason=invalid_reason,
        timing="scan-fenced",  # value = device time of a scanned step loop
    )

    if cfg.get("attn_compare") and dev.platform == "tpu":
        attn_res = _flash_attention_compare()
        out.update(attn_res)
        if "attn_flash_error" in attn_res:
            # same discipline as the QSGD compare: a Mosaic compile failure
            # of an advertised production path fails the metric
            _mark_invalid(
                out,
                "flash attention pallas path failed: "
                + attn_res["attn_flash_error"],
            )
        elif "attn_jnp_error" in attn_res:
            # symmetric discipline (ADVICE r3 #3): a dead oracle leaves
            # attn_flash_ms with no comparison baseline — flag it so the
            # speedup claim can't be read from a one-sided result
            _mark_invalid(
                out,
                "flash attention jnp baseline failed (flash timing has no "
                "comparison): " + attn_res["attn_jnp_error"],
            )

    if cfg.get("qsgd_compare") and dev.platform == "tpu":
        cmp_res = _qsgd_encode_compare()
        out.update(cmp_res)
        if "qsgd_encode_error" in cmp_res:
            # a compile failure of the advertised opt-in kernel path is a
            # FAILED metric, not a footnote (VERDICT r2 weak #2)
            _mark_invalid(
                out,
                "QSGD pallas kernel path failed: " + cmp_res["qsgd_encode_error"],
            )


    if cfg.get("wire_compare"):
        # bf16 factors on the wire (stochastic rounding, unbiased): halves
        # payload bytes AND shrinks the decode contraction (VERDICT r3
        # next-round #3's dtype lever)
        import dataclasses as _dc

        wire_codec = _dc.replace(codec, wire_dtype="bfloat16")
        wire_step = make_train_step(model, opt, codec=wire_codec)
        wdt, _, _, wm, wsync = timed(
            wire_step, create_state(model, opt, rng, images)
        )
        out["bf16wire_ms_per_step"] = round(wdt * 1e3, 3)
        out["bf16wire_byte_reduction"] = round(
            dense / max(int(wm["msg_bytes"]), 1), 2
        )
        if not math.isfinite(wsync):
            _mark_invalid(out, f"bf16wire sync scalar not finite: {wsync}")

    if cfg.get("bf16_compare"):
        # the TPU-native mixed-precision mode (no reference analogue): same
        # codec, bf16 fwd/bwd on the MXU, f32 master state
        bf16_step = make_train_step(model, opt, codec=codec,
                                    compute_dtype=jnp.bfloat16)
        bdt, _, _, _, bsync = timed(bf16_step, create_state(model, opt, rng, images))
        out["bf16_ms_per_step"] = round(bdt * 1e3, 3)
        if not math.isfinite(bsync):
            _mark_invalid(out, f"bf16 sync scalar not finite: {bsync}")

    if cfg.get("dense_compare"):
        dense_step = make_train_step(model, opt, codec=None)
        ddt, _, _, _, dsync = timed(dense_step, create_state(model, opt, rng, images))
        out["dense_ms_per_step"] = round(ddt * 1e3, 3)
        if not math.isfinite(dsync):  # same validity discipline as the headline
            _mark_invalid(out, f"dense sync scalar not finite: {dsync}")
        else:
            # The comm-cost model (VERDICT r3 next-round #1a): single-chip
            # times say compression LOSES (the codec tax has no wire to
            # pay for); this attaches the quantity that decides deployment
            # — implied sync-step time at N ways over a given fabric, and
            # the crossover bandwidth. Assumptions: utils/comm_model.py.
            from atomo_tpu.utils.comm_model import crossover_report

            out["comm_model"] = crossover_report(
                dense_bytes=dense,
                payload_bytes=int(metrics["msg_bytes"]),
                dense_step_s=ddt,
                svd_step_s=dt,
            )

    if cfg.get("ckpt"):
        import tempfile

        from atomo_tpu.training.checkpoint import save_checkpoint

        host_state = jax.device_get(state)
        with tempfile.TemporaryDirectory() as td:
            t0 = time.perf_counter()
            save_checkpoint(td, host_state, 1, compress=True)
            out["ckpt_save_ms"] = round((time.perf_counter() - t0) * 1e3, 1)
            out["ckpt_bytes"] = sum(
                os.path.getsize(os.path.join(dp, f))
                for dp, _, fs in os.walk(td) for f in fs
            )

    return out


def _flash_attention_compare() -> dict:
    """Fused-Pallas flash attention vs the jnp blockwise oracle on an
    LM-sized causal forward (TPU only; same per-path try discipline as the
    QSGD compare). Shapes: (B=4, H=8, S=2048, D=64) f32 — ~4.3 GFLOP of
    attention per call."""
    import jax
    import jax.numpy as jnp

    from atomo_tpu.ops.attention_kernels import flash_attention
    from atomo_tpu.parallel.ring import blockwise_attention

    b, h, sq, d = 4, 8, 2048, 64
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q, k, v = (jax.random.normal(kk, (b, h, sq, d), jnp.float32) for kk in ks)
    reps = 10
    res = {}
    impls = {
        "flash": lambda q, k, v: flash_attention(
            q, k, v, causal=True, interpret=False
        ),
        "jnp": lambda q, k, v: blockwise_attention(q, k, v, causal=True),
    }
    for tag, fn in impls.items():
        try:

            @jax.jit
            def many(q, k, v, f=fn):
                def body(acc, i):
                    o = f(q + acc * 1e-9, k, v)  # serialize iterations
                    # consume EVERY output element: a single-position fetch
                    # would let XLA prune most of the jnp oracle's work
                    # while the opaque Pallas call runs in full
                    return jnp.float32(jnp.sum(o) * 1e-9), None

                acc, _ = jax.lax.scan(
                    body, jnp.float32(0), jnp.arange(reps)
                )
                return acc

            float(many(q, k, v))  # compile + warm
            best = float("inf")
            for _ in range(REPS):
                t0 = time.perf_counter()
                sync = float(many(q, k, v))
                best = min(best, (time.perf_counter() - t0) / reps)
                if not math.isfinite(sync):
                    raise RuntimeError(f"{tag} attention scalar not finite")
            res[f"attn_{tag}_ms"] = round(best * 1e3, 3)
        except Exception as exc:  # noqa: BLE001
            if tag == "flash":
                res["attn_flash_error"] = str(exc)[:200]
            else:
                res["attn_jnp_error"] = str(exc)[:200]
    return res


def _qsgd_encode_compare() -> dict:
    """Fused-Pallas vs jnp QSGD encode on a ResNet-18-sized flat gradient
    (TPU only): the kernels are the production path there, and this is the
    evidence (VERDICT r1 next-round #2). Each path is timed in its OWN
    try-block so a pallas compile failure cannot eat the jnp timing, and
    the caller escalates `qsgd_encode_error` to a failed metric (r2 weak
    #2 — r2's shared try demoted a production compile error to a footnote
    and lost the surviving path's number)."""
    import jax
    import jax.numpy as jnp

    from atomo_tpu.codecs import QsgdCodec

    n = 1 << 23  # ~8.4M f32 values ≈ a ResNet-18 gradient, flattened
    g = jax.random.normal(jax.random.PRNGKey(3), (n,), jnp.float32)
    key = jax.random.PRNGKey(4)
    reps = 30
    res = {}
    for tag, up in (("jnp", False), ("pallas", True)):
        try:
            codec = QsgdCodec(bits=4, use_pallas=up)

            # scan the encodes under ONE dispatch so per-call host
            # dispatch cost stays out of a milliseconds-scale encode time
            @jax.jit
            def many(k, x, c=codec):
                def body(acc, i):
                    p = c.encode(jax.random.fold_in(k, i), x)
                    # consume outputs so no encode is dead-code-eliminated
                    return acc + p.scales[0] + jnp.float32(p.words[0, 0] & 1), None
                acc, _ = jax.lax.scan(body, jnp.float32(0), jnp.arange(reps))
                return acc

            float(many(key, g))  # compile + warm
            best = float("inf")
            for _ in range(REPS):  # best-of-N (shared-chip contention)
                t0 = time.perf_counter()
                sync = float(many(key, g))  # one dispatch, scalar fence
                best = min(best, (time.perf_counter() - t0) / reps)
                if not math.isfinite(sync):
                    raise RuntimeError(f"{tag} encode sync scalar not finite: {sync}")
            res[f"qsgd_encode_{tag}_ms"] = round(best * 1e3, 3)
        except Exception as exc:
            if up:  # the production path on TPU — escalated by the caller
                res["qsgd_encode_error"] = str(exc)[:200]
            else:
                res["qsgd_encode_jnp_error"] = str(exc)[:200]
    return res


# ----------------------------------------------------------- torch baseline


def _torch_resnet18(num_classes: int = 10):
    """Standard CIFAR ResNet-18 (BasicBlock [2,2,2,2]) in plain torch."""
    import torch.nn as tnn

    class BasicBlock(tnn.Module):
        def __init__(self, cin, cout, stride=1):
            super().__init__()
            self.c1 = tnn.Conv2d(cin, cout, 3, stride, 1, bias=False)
            self.b1 = tnn.BatchNorm2d(cout)
            self.c2 = tnn.Conv2d(cout, cout, 3, 1, 1, bias=False)
            self.b2 = tnn.BatchNorm2d(cout)
            self.short = None
            if stride != 1 or cin != cout:
                self.short = tnn.Sequential(
                    tnn.Conv2d(cin, cout, 1, stride, bias=False), tnn.BatchNorm2d(cout)
                )
            self.relu = tnn.ReLU(inplace=True)

        def forward(self, x):
            out = self.relu(self.b1(self.c1(x)))
            out = self.b2(self.c2(out))
            out = out + (self.short(x) if self.short else x)
            return self.relu(out)

    class Net(tnn.Module):
        def __init__(self):
            super().__init__()
            layers = [
                tnn.Conv2d(3, 64, 3, 1, 1, bias=False),
                tnn.BatchNorm2d(64),
                tnn.ReLU(inplace=True),
            ]
            cin = 64
            for cout, stride in ((64, 1), (64, 1), (128, 2), (128, 1),
                                 (256, 2), (256, 1), (512, 2), (512, 1)):
                layers.append(BasicBlock(cin, cout, stride))
                cin = cout
            self.features = tnn.Sequential(*layers)
            self.pool = tnn.AdaptiveAvgPool2d(1)
            self.fc = tnn.Linear(512, num_classes)

        def forward(self, x):
            x = self.pool(self.features(x)).flatten(1)
            return self.fc(x)

    return Net()


def _numpy_svd_encode_decode(grad, rank: int):
    """The reference worker's per-layer encode/decode cost model:
    reshape-to-2d -> LA.svd -> keep `rank` atoms -> U @ diag(s) @ Vt."""
    import numpy as np

    g = grad
    if g.ndim <= 1:
        n = g.size
        g = np.resize(g, (max(n // 2, 1), 2 if n >= 2 else 1))
    elif g.ndim > 2:
        a, b = g.shape[0], g.shape[1]
        rest = int(np.prod(g.shape[2:]))
        m = a * b
        g = g.reshape((m // 2, 2 * rest) if m % 2 == 0 else (m, rest))
    u, s, vt = np.linalg.svd(g, full_matrices=False)
    k = min(rank, s.size)
    return (u[:, :k] * s[:k]) @ vt[:k, :]


def measure_reference_cpu(batch: int, rank: int) -> tuple[float, str]:
    """(seconds/step, protocol) of the reference-equivalent worker pipeline
    on CPU; protocol is "2-step-mean" or, when a single step already runs
    past 300s, "1-cold-step" (the warmup probe IS the measurement)."""
    import numpy as np
    import torch
    import torch.nn.functional as F

    # cap threads at the actually-usable core count: this box exposes many
    # CPUs but schedules ~1; forcing 4 threads oversubscribes and SLOWS the
    # baseline (observed 12+ CPU-minutes for 3 steps)
    usable = (
        len(os.sched_getaffinity(0))
        if hasattr(os, "sched_getaffinity")  # Linux-only API
        else (os.cpu_count() or 1)
    )
    torch.set_num_threads(min(torch.get_num_threads(), usable))
    net = _torch_resnet18()
    x = torch.rand(batch, 3, 32, 32)
    y = torch.randint(0, 10, (batch,))

    def one_step():
        net.zero_grad()
        loss = F.cross_entropy(net(x), y)
        loss.backward()
        for p in net.parameters():
            _numpy_svd_encode_decode(p.grad.numpy().astype(np.float32), rank)

    t0 = time.perf_counter()
    one_step()  # warmup doubles as a cost probe
    warm = time.perf_counter() - t0
    if warm > 300:
        # on a 1-core host a single reference step can run for many minutes;
        # at that scale the warmup IS the measurement (the comparison is
        # off by orders of magnitude either way) and burning 2 more steps
        # only risks the child timeout. The protocol marker travels into
        # the JSON so the cold-step inflation is visible to consumers.
        return warm, "1-cold-step"
    n = 2
    t0 = time.perf_counter()
    for _ in range(n):
        one_step()
    return (time.perf_counter() - t0) / n, "2-step-mean"


def child_main(args) -> int:
    import jax

    from atomo_tpu.utils.compile_cache import enable_compile_cache

    # the shared compile-cache rule (utils/compile_cache.py): measured step
    # times are unaffected (warmup runs either way), only the compile
    # wall-time ahead of them shrinks. Logged to stderr so the stdout JSON
    # contract stays clean.
    enable_compile_cache(log_fn=lambda m: print(m, file=sys.stderr, flush=True))
    number = args.config if args.config is not None else 2
    cfg = dict(CONFIGS[number])
    dev = jax.devices()[0]
    if not cfg.get("force_cpu_mesh") and dev.platform != "tpu":
        # no fallback: a CPU time is never written under a device metric
        print(
            f"bench: config {number} ({cfg['metric']}) measures the TPU, and "
            f"JAX found platform {dev.platform!r} ({dev.device_kind}); "
            "refusing to measure anything else under that metric's name",
            file=sys.stderr, flush=True,
        )
        return NO_TPU_EXIT_CODE
    out = measure_ours(cfg)
    # flush an intermediate row before the (slow, host-CPU) torch baseline:
    # if the baseline is killed by the parent's timeout, the accelerator
    # measurement above still reaches the parent (it parses the LAST line)
    print(json.dumps({**out, "vs_baseline": None, "baseline": "pending", "error": None}), flush=True)
    if cfg.get("torch_baseline") and not args.no_baseline:
        try:
            base_s, proto = measure_reference_cpu(cfg["batch"], cfg.get("rank", 3))
            out["vs_baseline"] = round(base_s / (out["value"] / 1e3), 3)
            out["baseline"] = "torch-cpu-refpipe"
            # protocol travels WITH the ratio: "1-cold-step" means the
            # numerator is a single unwarmed reference step (lazy torch
            # init included) and the ratio is not comparable with
            # "2-step-mean" rows
            out["vs_baseline_protocol"] = proto
        except Exception:
            out["vs_baseline"] = None
            out["baseline"] = "none"
    else:
        out["vs_baseline"] = None
        out["baseline"] = "none"
    out["error"] = None
    print(json.dumps(out))
    return 0


# -------------------------------------------------------------------- parent

# Ladder wall-clock deadline (seconds, ATOMO_BENCH_DEADLINE_S; set by main
# from invocation start). Every config checks the remaining budget, child
# timeouts are clamped to it, and configs that cannot start emit an honest
# deadline row — so the LAST line is always a complete aggregate even when
# the sum of the configs would outrun the caller's own time limit.
_DEADLINE = None


def _remaining() -> float:
    return float("inf") if _DEADLINE is None else _DEADLINE - time.monotonic()


def _deadline_row(cfg: dict) -> dict:
    return dict(
        metric=cfg["metric"], value=None, unit="ms/step", vs_baseline=None,
        baseline="none", byte_reduction=None, mfu=None, platform=None,
        device=None, chips_measured=1, measurement_valid=False,
        invalid_reason="ladder deadline exhausted before this config ran",
        error="ladder deadline exhausted (ATOMO_BENCH_DEADLINE_S)",
    )


def _run_child(
    argv_tail: list[str], env_extra: dict, timeout_s: int = CHILD_TIMEOUT_S
) -> tuple[dict | None, str]:
    cmd = [sys.executable, "-u", os.path.abspath(__file__), "--child"] + argv_tail
    env = {**os.environ, **env_extra}
    try:
        p = subprocess.run(
            cmd, capture_output=True, text=True, env=env, timeout=timeout_s
        )
        stdout = p.stdout or ""
        rc = p.returncode
        stderr = p.stderr or ""
    except subprocess.TimeoutExpired as e:
        # salvage any intermediate JSON the child already flushed
        stdout = (e.stdout or b"")
        if isinstance(stdout, bytes):
            stdout = stdout.decode(errors="replace")
        rc, stderr = -1, f"child timed out after {timeout_s}s"
    for line in reversed(stdout.splitlines()):
        line = line.strip()
        if line.startswith("{"):
            try:
                return json.loads(line), ""
            except json.JSONDecodeError:
                continue
    tail = (stderr or stdout or "").strip().splitlines()[-8:]
    return None, f"rc={rc}: " + " | ".join(tail)


# ------------------------------------------------------ partial artifact
# Every completed ladder row is ALSO written to a JSON artifact file
# ATOMICALLY (tmp + os.replace) as it lands, so a driver timeout (rc=124,
# SIGKILL) mid-ladder leaves a parseable artifact with every finished row
# — failed rows carry the child's stderr tail in "error", so partial
# evidence survives and explains itself. Disable with
# ATOMO_BENCH_ARTIFACT="" (e.g. for pure-stdout consumers).
_ARTIFACT: dict = {"rows": [], "complete": False}


def _artifact_path() -> str:
    return os.environ.get(
        "ATOMO_BENCH_ARTIFACT", os.path.join("artifacts", "bench_partial.json")
    )


def _write_artifact() -> None:
    path = _artifact_path()
    if not path:
        return
    try:
        # atomic tmp+rename (utils.tracing.write_json_atomic — the one
        # artifact discipline shared with the autopilot's decision file
        # and the LR grid): readers never see a torn file
        from atomo_tpu.utils.tracing import write_json_atomic

        write_json_atomic(path, _ARTIFACT)
    except OSError as exc:
        print(f"bench artifact write failed: {exc}", file=sys.stderr)


def _record_row(row: dict) -> None:
    _ARTIFACT["rows"].append(row)
    _write_artifact()


def _bench_one(config: int, no_baseline: bool) -> dict:
    """ONE child per config, on the device the config names: the TPU, or
    the forced CPU mesh for the `force_cpu_mesh` drills. No retry and no
    fallback — a child that fails yields an error row carrying its exit
    code and stderr tail."""
    cfg = CONFIGS[config]
    if _remaining() < 45:
        # not enough budget to even start a child: report the truncation
        # honestly instead of eating the caller's timeout
        return _deadline_row(cfg)
    tail = ["--config", str(config)]
    child_env = {}
    if cfg.get("force_cpu_mesh"):
        # a multi-device SEMANTICS/dispatch drill — always a forced
        # virtual-device CPU mesh (platform is recorded in the row);
        # baseline is "none" by design for these rows
        flags = (os.environ.get("XLA_FLAGS", "")
                 + " --xla_force_host_platform_device_count="
                 + str(cfg.get("n_dev", 4))).strip()
        child_env = {"JAX_PLATFORMS": "cpu", "XLA_FLAGS": flags}
        if cfg.get("no_compile_cache"):
            child_env["JAX_ENABLE_COMPILATION_CACHE"] = "false"
        tail.append("--no-baseline")
    elif no_baseline:
        tail.append("--no-baseline")
    parsed, err = _run_child(
        tail, child_env,
        timeout_s=int(min(CHILD_TIMEOUT_S, max(45, _remaining() - 10))),
    )
    if parsed is not None:
        return parsed
    return dict(
        metric=cfg["metric"], value=None, unit="ms/step", vs_baseline=None,
        baseline="none", byte_reduction=None, mfu=None, platform=None,
        device=None, chips_measured=0, measurement_valid=False,
        invalid_reason="no measurement produced",
        error=err,
    )


def _failed(row: dict) -> bool:
    """A row with an `error` is a config that did not run to a result
    (child failure, no TPU, deadline); the ladder's exit code says so."""
    if row.get("error"):
        print(f"bench: {row['metric']} failed: {row['error']}",
              file=sys.stderr, flush=True)
        return True
    return False


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", type=int, default=None, choices=sorted(CONFIGS),
                    help="run ONE ladder config (default: the whole ladder)")
    ap.add_argument("--all", action="store_true", help="(default behavior)")
    ap.add_argument("--no-baseline", action="store_true")
    ap.add_argument("--child", action="store_true", help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.child:
        return child_main(args)
    global _DEADLINE
    _DEADLINE = time.monotonic() + _env_float("ATOMO_BENCH_DEADLINE_S", 840.0)
    if args.config is not None and args.all:
        ap.error("--config and --all are mutually exclusive")
    _ARTIFACT.update(rows=[], complete=False)  # fresh run
    if args.config is not None:
        row = _bench_one(args.config, args.no_baseline)
        _record_row(row)
        _ARTIFACT["complete"] = True
        _write_artifact()
        print(json.dumps(row))
        return 1 if _failed(row) else 0
    _write_artifact()  # the artifact exists BEFORE any (slow) config
    # default: the whole ladder — one row per config as it completes, then
    # an aggregate headline line (config 2's fields + all rows so far under
    # "configs"). The HEADLINE config runs FIRST: if the ladder is cut
    # short, the caller's last-line parse still gets a config-2 aggregate
    # instead of whichever row happened to finish. The aggregate re-emits
    # after every later config.
    rows = {}
    n_failed = 0
    for c in [2] + [k for k in sorted(CONFIGS) if k != 2]:
        rows[c] = _bench_one(c, args.no_baseline)
        n_failed += _failed(rows[c])
        _record_row(rows[c])  # atomic: partial results survive rc=124
        print(json.dumps(rows[c]), flush=True)
        if 2 in rows:
            headline = dict(rows[2])
            headline["configs"] = [rows[k] for k in sorted(rows)]
            headline["configs_complete"] = len(rows) == len(CONFIGS)
            print(json.dumps(headline), flush=True)
    _ARTIFACT["complete"] = True
    _write_artifact()
    return 1 if n_failed else 0


if __name__ == "__main__":
    raise SystemExit(main())
