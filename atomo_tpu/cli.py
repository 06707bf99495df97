"""Command-line interface — the reference's entry-point surface, TPU-native.

Parity target: the argparse block of src/distributed_nn.py:31-82 (every flag
accepted, same names/defaults where meaningful) so the reference's job
scripts (src/run_pytorch.sh, src/tune.sh, src/evaluate_pytorch.sh) translate
mechanically. Deviations are honest:

  --comm-type     accepted, ignored with a warning — it is "a fake parameter"
                  in the reference too (README.md:111).
  --no-cuda /
  --enable-gpu    accepted, ignored — device selection belongs to JAX/XLA.
  --num-aggregate the reference stores this flag but always waits for all
                  workers (sync_replicas_master_nn.py:113,124; SURVEY.md
                  §2.1). Here it gets the partial-aggregation semantics it
                  advertises: with compressed gather aggregation on a multi-
                  device mesh, only a rotating K-of-N replica subset is
                  averaged each step. Unset = aggregate all (the reference's
                  actual behavior); inapplicable combinations warn.
  --compress      in the reference this flag is stored but never read in the
                  step path (SURVEY.md §5.6); here it controls lossless
                  checkpoint compression via the C++ native codec.
  --epochs        the reference calls it "somehow redundant" (README.md:115);
                  training length is --max-steps, epochs only caps it.

Subcommands:
  train      single-host or mesh-distributed training (rank dispatch in the
             reference, distributed_nn.py:243-259, collapses to --n-devices)
  evaluate   checkpoint-polling evaluator (src/distributed_evaluator.py)
  tune       LR grid search (src/tune.sh + src/tiny_tuning_parser.py)
  lm         LM training over any parallelism layout — dp, dp-sp (ring or
             Ulysses), dp-tp (Megatron), dp-ep (switch-MoE), dp-pp (GPipe),
             dp-tp-sp (3-D) — all compiled through the one mesh path with
             the compressed dp exchange; no reference analogue (DP-only,
             CV-only)

`python -m atomo_tpu.cli <flags>` with no subcommand behaves like `train`,
matching `python distributed_nn.py <flags>`.
"""

from __future__ import annotations

import argparse
import sys
import warnings

# --code values that mean "no compression, dense psum aggregation"; must
# match the aliases get_codec maps to DenseCodec (codecs/__init__.py)
DENSE_CODES = ("sgd", "dense", "none")


def _add_fit_args(parser: argparse.ArgumentParser) -> None:
    """Reference flag surface (distributed_nn.py:31-82) + TPU-native extras."""
    g = parser.add_argument_group("reference-parity flags")
    g.add_argument("--batch-size", type=int, default=128, metavar="N")
    g.add_argument("--test-batch-size", type=int, default=1000, metavar="N")
    g.add_argument("--max-steps", type=int, default=10000, metavar="N")
    g.add_argument("--epochs", type=int, default=100, metavar="N")
    g.add_argument("--lr", type=float, default=0.01, metavar="LR")
    g.add_argument("--momentum", type=float, default=0.5, metavar="M")
    g.add_argument("--lr-shrinkage", type=float, default=0.95, metavar="M")
    g.add_argument("--no-cuda", action="store_true", default=False)
    g.add_argument("--seed", type=int, default=1, metavar="S")
    g.add_argument("--log-interval", type=int, default=10, metavar="N")
    g.add_argument("--network", type=str, default="LeNet", metavar="N")
    g.add_argument("--code", type=str, default="sgd",
                   help="codec: sgd | svd | qsgd | terngrad")
    g.add_argument("--bucket-size", type=int, default=512)
    g.add_argument("--dataset", type=str, default="MNIST", metavar="N")
    g.add_argument("--comm-type", type=str, default="Bcast", metavar="N")
    g.add_argument("--num-aggregate", type=int, default=None, metavar="N",
                   help="aggregate only K replicas per step (rotating subset; "
                        "gather mode). The reference stores this flag but "
                        "always aggregates all workers; unset = all.")
    g.add_argument("--eval-freq", type=int, default=50, metavar="N")
    g.add_argument("--train-dir", type=str, default="output/models/", metavar="N")
    g.add_argument("--compress", action="store_true", default=False,
                   help="lossless-compress checkpoints (C++ native codec)")
    g.add_argument("--enable-gpu", action="store_true", default=False)
    g.add_argument("--svd-rank", type=int, default=0)
    g.add_argument("--quantization-level", type=int, default=4)

    t = parser.add_argument_group("tpu-native flags")
    t.add_argument("--n-devices", type=int, default=0,
                   help="devices in the dp mesh; 0 = all visible, 1 = single-host")
    t.add_argument("--auto", type=str, default="off",
                   choices=["off", "tune", "controller"],
                   help="controller = the GLOBAL controller: one priced "
                        "decision space over every knob (aggregate / "
                        "overlap / superstep / ring bucket / stream "
                        "buckets / topology plan / per-leaf rank-or-bit "
                        "allocation / sparse-row hybrid / quorum), the "
                        "pure legacy solvers composed as subroutines of "
                        "one predict-ranked enumeration, only the "
                        "shortlist probed, one decision artifact "
                        "(train_dir/controller_decision.json) "
                        "superseding tune_decision.json + "
                        "budget_alloc.json as the resume source of "
                        "truth, and one online re-solve loop "
                        "(controller_redecide incidents). "
                        "tune = performance autopilot: predict a ranked "
                        "candidate list of knob vectors (aggregate / "
                        "overlap / stream-encode / superstep / ring "
                        "bucket) from the comm "
                        "model, run a short measured probe ladder over the "
                        "top candidates at startup (amortized by "
                        "the persistent compile cache), pick the winner, write every "
                        "candidate's predicted-vs-measured ms/step to "
                        "train_dir/tune_decision.json, and train with the "
                        "chosen config — bit-identical to launching it "
                        "statically. Arms the online re-tuner: sustained "
                        "step-time drift re-probes gather-vs-ring at the "
                        "next checkpoint boundary (the bit-identical-"
                        "operator pair) and logs the decision to "
                        "incidents.jsonl. Conflicts with explicitly pinned "
                        "knobs (--aggregate/--overlap/--superstep) — pin "
                        "or tune, not both; an explicit --ring-bucket-size "
                        "is honored (bit-identical layout knob: the ring "
                        "candidates probe that value instead of exploring "
                        "the default and single-bucket packings)")
    t.add_argument("--tune-steps", type=int, default=3, metavar="N",
                   help="autopilot: steps per timed probe dispatch loop")
    t.add_argument("--tune-reps", type=int, default=2, metavar="N",
                   help="autopilot: best-of-N probe repeats (shared-host "
                        "contention estimator)")
    t.add_argument("--tune-top", type=int, default=4, metavar="N",
                   help="autopilot: how many top-ranked candidates get a "
                        "measured probe (the rest are recorded "
                        "predicted-only in the decision artifact)")
    t.add_argument("--aggregate", type=str, default="auto",
                   choices=["auto", "gather", "ring", "psum", "hierarchical"],
                   help="gradient exchange mode: gather = factor all_gather "
                        "(compressed wire), ring = the streamed form of "
                        "gather (payloads rotate via ppermute, each hop's "
                        "decode overlaps the next transfer, no O(N) "
                        "gathered buffer — see --ring-bucket-size), psum = "
                        "dense all-reduce, hierarchical = dense psum over "
                        "the fast fabric (ICI) then factor all_gather over "
                        "the slow one (DCN) — see --dcn-ways. auto "
                        "(default) picks per deployment from the measured "
                        "comm-cost model and prints why "
                        "(utils/comm_model.choose_aggregate, "
                        "artifacts/COMM_CROSSOVER.md)")
    t.add_argument("--overlap", type=str, default="off",
                   choices=["off", "delayed"],
                   help="delayed = stale-by-one overlapped aggregation: at "
                        "step t each chip computes and encodes grads_t "
                        "while the optimizer applies the step-(t-1) "
                        "decoded mean, so the gather/ring exchange and the "
                        "decode run underneath fwd/bwd+update and leave "
                        "the critical path (needs a compressing --code and "
                        "--aggregate gather|ring on a multi-device mesh). "
                        "Step 0 applies a zero (skipped) update; the guard "
                        "health flag travels with the delayed payload; "
                        "checkpoints carry the in-flight payload so resume "
                        "is exact. off (default) = the blocking program, "
                        "byte-for-byte as before")
    t.add_argument("--stream-encode", type=str, default="off",
                   choices=["off", "on"],
                   help="on = backward-interleaved layer-streamed encode: "
                        "the gradient tree is partitioned DDP-style into "
                        "size-bounded layer buckets (--stream-bucket-mb, "
                        "reverse-topological so the last-computed layers "
                        "form the first-ready buckets) and each bucket's "
                        "encode — and, under --aggregate ring, its first "
                        "ppermute hops — depends only on that bucket's "
                        "gradients, so encode runs under backprop and the "
                        "wire starts before backward finishes. The bucket "
                        "plan is a layout knob: payloads and trajectories "
                        "are bit-identical to off for any bucket size "
                        "(per-leaf codec keys fold from the global leaf "
                        "index). Needs a compressing --code with "
                        "--aggregate gather|ring on a multi-device mesh; "
                        "composes with --superstep/--zero1/--grad-guard/"
                        "--overlap delayed. off (default) = the monolithic "
                        "encode, byte-for-byte as before")
    t.add_argument("--stream-bucket-mb", type=float, default=4.0,
                   metavar="MB",
                   help="--stream-encode: dense megabytes per layer bucket "
                        "(<= 0 packs the whole tree into one bucket — "
                        "stream off's dataflow with stream on's code path). "
                        "Any value is bit-identical (layout only; tested); "
                        "smaller buckets pipeline finer at more dispatches")
    t.add_argument("--sparse-rows", type=str, default="off",
                   choices=["off", "auto", "on"],
                   help="per-layer sparse-row hybrid exchange (sparse/): "
                        "lookup-table leaves whose lossless (row, value) "
                        "payload beats the dense path's bytes move as rows "
                        "(the SparCML density crossover, stated per layer "
                        "in the plan's reason lines); every other leaf "
                        "keeps the existing gather/ring exchange. auto = "
                        "plan from a probe gradient and use it when any "
                        "leaf is sparse-assignable (with --auto tune, the "
                        "+sp candidates decide); on = require it. Needs a "
                        "multi-device flat gather/ring exchange (row-id "
                        "workloads: --dataset zipf --network embedding); "
                        "rejects psum/hierarchical/delayed/stream-encode/"
                        "guard/num-aggregate — the conflict matrix says "
                        "why. off (default) is byte-identical program text")
    t.add_argument("--emb-rows", type=int, default=4096, metavar="R",
                   help="--network embedding: lookup-table rows (must "
                        "match the --dataset zipf id range; <= 2^24 so "
                        "float32 batches carry ids exactly)")
    t.add_argument("--emb-dim", type=int, default=16, metavar="D",
                   help="--network embedding: embedding dimension")
    t.add_argument("--zipf-slots", type=int, default=8, metavar="S",
                   help="--dataset zipf: lookups per sample (bounds the "
                        "lossless row budget: batch/chip x slots)")
    t.add_argument("--zipf-alpha", type=float, default=1.1, metavar="A",
                   help="--dataset zipf: power-law exponent of the row "
                        "access distribution (p_i ~ 1/i^A)")
    t.add_argument("--ring-bucket-size", type=int, default=65536, metavar="N",
                   help="ring aggregation: elements per packed rotation "
                        "bucket (parallel.common.pack_tree_buckets) — every "
                        "same-dtype payload leaf rides one ppermute per hop "
                        "regardless of model depth; <= 0 packs each dtype "
                        "into a single unpadded bucket. Any value produces "
                        "bit-identical results (layout only; tested)")
    t.add_argument("--fabric", type=str, default="auto", metavar="F",
                   help="fabric every prediction is priced from "
                        "(--aggregate auto's advisory, the autopilot, the "
                        "topology planner): auto (ici single-host, dcn "
                        "multi-host) | ici | dcn | eth10g | a per-chip "
                        "GB/s number | <inner>:<outer> (two-tier) | "
                        "measured — a startup probe times fenced "
                        "ppermute/all_gather ladders per tier on the real "
                        "mesh, records train_dir/fabric_probe.json, and "
                        "every prediction prices from it. PRICING ONLY: "
                        "the resolved knobs being equal, measured trains "
                        "bit-identical to any pinned fabric "
                        "(tests/test_fabric_obs.py)")
    t.add_argument("--codec-tax-ms", type=float, default=None, metavar="MS",
                   help="measured single-chip codec tax for --aggregate "
                        "auto's advisory; default scales the ResNet-18 "
                        "anchor (an unverified figure from before this "
                        "round, utils/comm_model.py) by gradient size")
    t.add_argument("--dcn-ways", type=int, default=0, metavar="K",
                   help="hierarchical aggregation: number of SLOW-fabric "
                        "(outer/DCN) groups; the n-devices mesh becomes "
                        "(dp=K) x (ici=n/K). 0 = infer from "
                        "jax.process_count() (one group per host), "
                        "falling back to 2 on a single process. With "
                        "--dcn-ways > 1, --aggregate auto plans over the "
                        "two-tier fabric and --auto tune probes "
                        "hierarchical candidates")
    t.add_argument("--plan", type=str, default="auto",
                   help="two-level schedule for hierarchical aggregation "
                        "(topology.schedule): auto = the cost-driven "
                        "planner when --aggregate auto resolved "
                        "hierarchical, the legacy plan when you pinned "
                        "--aggregate hierarchical yourself (today's exact "
                        "program); legacy = dense psum over ICI + one "
                        "factor gather over DCN; or an explicit "
                        "inner+outer pair from {psum,cring}+{gather,ring,"
                        "psum}, e.g. cring+ring — inner dense-psum or "
                        "compressed-ring, boundary re-encode, outer "
                        "re-encoded gather/ring or SparCML dense fallback")
    t.add_argument("--sample", type=str, default="fixed_k",
                   choices=["fixed_k", "bernoulli_budget", "bernoulli", "topk"],
                   help="SVD atom sampling mode (bernoulli_budget = reference "
                        "Bernoulli keep semantics in a static rank+slack payload)")
    t.add_argument("--svd-algo", type=str, default="auto",
                   choices=["auto", "exact", "gram", "randomized"],
                   help="auto = Halko sketch for large matrices, gram "
                        "(full spectrum via eigh of the small-side Gram — "
                        "no iterative QDWH program) for small ones; "
                        "exact/gram/randomized force one algorithm "
                        "everywhere (exact Jacobi costs ~120 ms/step on "
                        "ResNet-18/v5e — VERDICT r2 #3)")
    t.add_argument("--svd-mode", type=str, default="auto",
                   choices=["auto", "exact", "randomized"],
                   help="SVD decomposition mode (alias surface over "
                        "--svd-algo; the two must agree when both are "
                        "pinned): randomized = the Halko range-finder "
                        "sketch at EVERY size (measured 9.7 vs 130 ms/step "
                        "exact for svd3 on ResNet-18/v5e — the operating "
                        "point streamed per-bucket encode makes dominant), "
                        "exact = the LAPACK-style oracle, auto (default) = "
                        "sketch for large matrices, Gram-eigh for small")
    t.add_argument("--svd-wire", type=str, default="float32",
                   choices=["float32", "bfloat16"],
                   help="factor dtype on the wire: bfloat16 halves u/vt "
                        "bytes via stochastic rounding (E[wire] == factor, "
                        "so the codec stays unbiased); coeffs stay f32")
    t.add_argument("--budget-alloc", type=str, default="uniform",
                   choices=["uniform", "variance"],
                   help="per-layer byte allocation (atomo_tpu.budget): "
                        "uniform (default) = today's fixed --svd-rank on "
                        "every layer, byte-identical HLO to the pre-budget "
                        "programs; variance = solve ATOMO's water-filling "
                        "allocation — measure per-layer gradient spectra "
                        "from a startup probe, distribute the global wire "
                        "budget to minimize total estimator variance, "
                        "record it in train_dir/budget_alloc.json (reused "
                        "on --resume; re-solved at checkpoint boundaries "
                        "from the recorded q_err2 series when "
                        "--obs-quality --obs-record are armed). Needs "
                        "--code svd --sample fixed_k (the stated variance "
                        "law A/k)")
    t.add_argument("--budget-bytes", type=float, default=0.0, metavar="B",
                   help="global wire-byte budget per replica for "
                        "--budget-alloc variance (bytes; 0 = spend exactly "
                        "the uniform allocation's total, the "
                        "equal-wire-bytes comparison). Large enough "
                        "and every layer reaches "
                        "the exact dense fallback — the --on-diverge "
                        "densify remedy as the dial's spend-everything "
                        "limit")
    t.add_argument("--error-feedback", action="store_true", default=False,
                   help="accumulate each replica's compression residual "
                        "and feed it into the next step's encode "
                        "(e' = (g+e) - decode(encode(g+e)); the residual "
                        "rides the step carry and checkpoints like the "
                        "overlap payload). BIAS CONTRACT: EF trades the "
                        "codec's unbiasedness invariant for lower "
                        "variance — intended pairing is the deterministic "
                        "contraction sampler (--sample topk), whose bias "
                        "the carry compensates (the standard EF "
                        "guarantee); with the unbiased random samplers "
                        "the residual is unbounded (measured divergent) "
                        "and the CLI warns. Rejected for compositions "
                        "whose carry semantics are unproven: delayed "
                        "overlap, hierarchical re-encode, guard/elastic, "
                        "sparse rows, num-aggregate, zero1/sharded-update")
    t.add_argument("--optimizer", type=str, default="sgd", choices=["sgd", "adam"])
    t.add_argument("--weight-decay", type=float, default=0.0)
    t.add_argument("--nesterov", action="store_true", default=False)
    t.add_argument("--adam-beta1", type=float, default=0.9,
                   help="Adam b1 (reference src/optim/adam.py betas default)")
    t.add_argument("--adam-beta2", type=float, default=0.999)
    t.add_argument("--adam-eps", type=float, default=1e-8)
    t.add_argument("--amsgrad", action="store_true", default=False,
                   help="AMSGrad variant (reference src/optim/adam.py:37-94)")
    t.add_argument("--health-timeout", type=float, default=0.0,
                   help="arm the step-heartbeat watchdog: interrupt the job "
                        "if no step completes within this many seconds "
                        "(0 = off); recovery = restart from last checkpoint")
    t.add_argument("--grad-guard", action="store_true", default=False,
                   help="anomaly-guarded stepping: screen each replica's "
                        "raw gradient for non-finite values, drop anomalous "
                        "contributions and re-scale the surviving average "
                        "by n/kept (valid because the codecs are unbiased); "
                        "a step with no survivors is skipped")
    t.add_argument("--max-grad-norm", type=float, default=0.0, metavar="L2",
                   help="with the guard: also drop contributions whose "
                        "global L2 norm exceeds this (0 = finiteness only). "
                        "A screen, not clipping — implies --grad-guard")
    t.add_argument("--keep-ckpts", type=int, default=0, metavar="K",
                   help="retain only the newest K model_step_N checkpoints "
                        "(0 = keep all)")
    t.add_argument("--chaos", type=str, default="", metavar="SPEC",
                   help="fault-injection spec for drills, e.g. "
                        "'nan@3,kill@6,truncate@4,spike@5:3,crashloop@2,"
                        "die@5:1,slow@4:2:0.3' (die@S:R = replica R stops "
                        "contributing from step S onward — the elastic "
                        "membership drill; needs --grad-guard and a "
                        "multi-device mesh; slow@S:R:SEC = replica R "
                        "delivers every payload SEC seconds late from "
                        "step S onward — the persistent-straggler drill "
                        "--quorum absorbs; see utils/chaos.py); defaults "
                        "to the ATOMO_CHAOS env var")
    t.add_argument("--quorum", type=str, default="off", metavar="Q",
                   help="bounded-staleness quorum aggregation: each step "
                        "consumes whatever payloads have ARRIVED (a "
                        "straggler's payload rides a staleness ring, "
                        "bounded at --staleness steps stale, then dropped "
                        "+ counted) and waits only until Q of the N "
                        "replicas are present — the surviving mean is "
                        "rescaled by the exact unbiased n/kept argument "
                        "the guard uses. The per-step arrival schedule "
                        "is recorded to train-dir/arrival_schedule.jsonl "
                        "so --replay-arrivals replays the trajectory "
                        "bit-exact. Needs a compressing --code, "
                        "--aggregate gather|ring and a multi-device "
                        "mesh; conflicts with --overlap delayed, "
                        "hierarchical plans, --sparse-rows, "
                        "--stream-encode, --error-feedback, --elastic, "
                        "--zero1/--partition sharded-update, "
                        "--num-aggregate, --superstep > 1, "
                        "--obs-quality. off (default) = blocking "
                        "aggregation, byte-identical HLO to a build "
                        "without the flag")
    t.add_argument("--staleness", type=int, default=1, metavar="K",
                   help="with --quorum: the staleness bound — a payload "
                        "may be consumed at most K steps late; one that "
                        "would exceed K is DROPPED (one "
                        "staleness_exceeded incident each, never a "
                        "silent stale apply)")
    t.add_argument("--quorum-period-ms", type=float, default=100.0,
                   metavar="MS",
                   help="with --quorum: the modelled step period used to "
                        "convert a chaos slow@S:R:SEC straggler's lag "
                        "into whole steps (lag = ceil(SEC/period))")
    t.add_argument("--replay-arrivals", type=str, default="",
                   metavar="PATH",
                   help="with --quorum: replay a recorded "
                        "arrival_schedule.jsonl instead of deriving (and "
                        "waiting out) a live schedule — the trajectory "
                        "is bit-identical to the recorded run's; refuses "
                        "a schedule recorded under different "
                        "Q/K/N/period knobs")
    t.add_argument("--elastic", action="store_true", default=False,
                   help="elastic world size: track membership epochs in "
                        "train-dir/membership.json, carry a persistently "
                        "guard-masked replica as an unbiased "
                        "survivors-only mean (needs --grad-guard), and at "
                        "the next checkpoint boundary SHRINK the world to "
                        "the surviving roster — by default LIVE, in "
                        "process (state/mesh/step program reshaped at "
                        "the boundary, no exit; see --elastic-reshard); "
                        "when the loop cannot reshape in place, exit "
                        "code 29 tells the --max-restarts supervisor to "
                        "re-exec with --n-devices N-1 (a planned "
                        "reshape, never charged against the restart "
                        "budget) and re-shard the data stream "
                        "deterministically. "
                        "Bit-exact per membership epoch: the shrunken leg "
                        "matches a fresh --n-devices N-1 run resumed "
                        "from the same checkpoint (tested). Flat "
                        "gather/ring/psum meshes only; conflicts with "
                        "--zero1, --overlap delayed, --aggregate "
                        "hierarchical")
    t.add_argument("--elastic-reshard", choices=("live", "reexec"),
                   default="live",
                   help="how a committed membership epoch reshapes the "
                        "run. live (default): re-place the replicated "
                        "state on the new-world mesh in process "
                        "(mesh.reshard.reshard_replicated) — zero "
                        "downtime, bit-exact vs a fresh new-world build "
                        "resumed from the boundary checkpoint; re-exec "
                        "(rc=29) remains the RECORDED fallback "
                        "(reshard_fallback incident quotes why). "
                        "reexec: always exit rc=29 and let the "
                        "supervisor relaunch (the historical path)")
    t.add_argument("--elastic-patience", type=int, default=6, metavar="N",
                   help="consecutive guard-masked steps before a replica "
                        "is declared absent (one masked step is a "
                        "transient screen hit, not a dead member)")
    t.add_argument("--readmit-at", type=int, default=0, metavar="S",
                   help="with --elastic: once past step S, a "
                        "below-strength world re-grows to the full "
                        "roster at the next checkpoint boundary "
                        "(restart from the newest checkpoint, shard map "
                        "re-derived; membership epoch bumped). 0 = no "
                        "automatic re-admission. At most ONE automatic "
                        "re-grow per job (counted in membership.json): a "
                        "member that dies again after re-admission stays "
                        "out — re-grow by hand")
    t.add_argument("--on-diverge", type=str, default="off",
                   choices=["off", "skip", "rewarm", "densify"],
                   help="arm the divergence doctor: a windowed robust "
                        "z-score over the per-step loss series (plus guard "
                        "skip-rate and grad-norm trend counters) detects "
                        "divergence the per-step screen cannot see; on "
                        "alarm the run rolls back to the newest HEALTHY "
                        "checkpoint, replays the data stream, and applies "
                        "this remedy: skip = replay unchanged (transient-"
                        "fault model), rewarm = LR re-warmup ramp over the "
                        "detector window, densify = temporary dense "
                        "(uncompressed) aggregation for the window — valid "
                        "because every codec is an unbiased estimator of "
                        "the same mean. off (default) = detector disarmed")
    t.add_argument("--diverge-window", type=int, default=16, metavar="W",
                   help="divergence-detector window: EMA span, healthy-"
                        "tag clearance, and remedy duration (steps)")
    t.add_argument("--diverge-zmax", type=float, default=6.0, metavar="Z",
                   help="robust z-score threshold for the loss series")
    t.add_argument("--diverge-patience", type=int, default=3, metavar="N",
                   help="consecutive above-threshold steps before the "
                        "alarm fires (one bad batch is noise; a sustained "
                        "excursion is divergence)")
    t.add_argument("--diverge-min-history", type=int, default=8,
                   metavar="N",
                   help="warmup steps before z/skip/trend alarms arm")
    t.add_argument("--max-rollbacks", type=int, default=2, metavar="N",
                   help="in-process rollback budget; exhaustion exits with "
                        "the rollback-requested code (23) so a supervisor "
                        "can prune to the last healthy checkpoint and "
                        "restart")
    t.add_argument("--max-restarts", type=int, default=0, metavar="N",
                   help="supervise this run: re-exec the same command "
                        "under a crash-loop budget of N restarts with "
                        "jittered exponential backoff, resuming from the "
                        "last checkpoint; decisions land in "
                        "train_dir/incidents.jsonl (0 = unsupervised)")
    t.add_argument("--restart-backoff", type=float, default=1.0,
                   metavar="SEC",
                   help="supervisor backoff base seconds (decorrelated "
                        "jitter, capped at 30x)")
    t.add_argument("--superstep", type=int, default=0, metavar="K",
                   help="fuse K optimizer steps into ONE device dispatch "
                        "(lax.scan) with device-resident (K, batch, ...) "
                        "data blocks and one metric fetch per block — "
                        "amortizes per-dispatch host cost (README "
                        "'Performance'; its size on the TPU is not "
                        "measured yet). "
                        "Log/eval/checkpoint cadence, watchdog beats and "
                        "chaos kill/sleep snap to block boundaries; "
                        "trajectories are bit-identical across K (resume "
                        "works at any step, boundary or not). 0 (default) "
                        "= auto: 8 on TPU, 1 elsewhere; 1 = the per-step "
                        "loop exactly as before")
    t.add_argument("--obs-record", action="store_true", default=False,
                   help="arm the flight recorder: one JSON line per "
                        "training step appended to train-dir/"
                        "metrics.jsonl (loss, step wall ms, guard "
                        "verdicts, wire bytes, the aggregate mode in "
                        "effect, membership epoch, chaos generation, "
                        "drift state, rolling predicted-vs-measured "
                        "calibration), pruned in lockstep with the "
                        "checkpoint timeline on rollback/resume. Off "
                        "(default): zero new device ops, byte-identical "
                        "programs and stdout. Read it back with the "
                        "`report` verb")
    t.add_argument("--obs-quality", action="store_true", default=False,
                   help="in-graph estimator-quality probes: per-layer "
                        "||decode(encode(g))-g||^2 and relative variance "
                        "proxy inside the fused step (the ATOMO "
                        "estimator's variance, observable at last — the "
                        "feed for adaptive variance budgets). Needs a "
                        "compressing --code with flat gather/ring/psum "
                        "aggregation; off = byte-identical programs, on "
                        "= bit-identical trajectories (the probe only "
                        "adds metric outputs). Costs one extra decode + "
                        "one f32 reduction per layer per step")
    t.add_argument("--profile-dir", type=str, default="",
                   help="capture a jax.profiler device trace of a few "
                        "steady-state steps into this dir (TensorBoard/XProf "
                        "loadable) — phase cost inside the fused program")
    t.add_argument("--grad-accum", type=int, default=1, metavar="K",
                   help="accumulate gradients over K microbatches per chip "
                        "before the single encode/exchange: activation "
                        "memory shrinks to one microbatch at fixed "
                        "--batch-size; raise --batch-size K-fold to convert "
                        "that into a K-fold per-sample comm reduction")
    t.add_argument("--zero1", action="store_true", default=False,
                   help="ZeRO-1 optimizer-state sharding: each dp chip "
                        "holds 1/n of the flat momentum/Adam buffers, "
                        "updates its slice, and one all_gather reassembles "
                        "the replicated params (multi-device mesh only). "
                        "Alias for --partition zero1")
    t.add_argument("--partition", type=str, default="replicated",
                   choices=["replicated", "zero1", "sharded-update"],
                   help="weight-update partitioning (the mesh subsystem's "
                        "knob): 'replicated' keeps params+optimizer state "
                        "on every chip; 'zero1' shards the optimizer "
                        "state only; 'sharded-update' (Xu et al. "
                        "2004.13336) shards master weights AND optimizer "
                        "state AND the update computation over the data "
                        "axes — per-chip persistent state drops to 1/n, "
                        "the dense model exists only transiently inside "
                        "the step, trajectories stay bit-identical to "
                        "replicated per codec (canonical decode order), "
                        "and — unlike zero1 — checkpoints carry the "
                        "--overlap delayed in-flight payload, so "
                        "supervised restarts resume bit-exact")
    t.add_argument("--bf16", action="store_true", default=False,
                   help="mixed precision: forward/backward compute in "
                        "bfloat16 on the MXU (master params, optimizer "
                        "state, gradients, loss, and BN stats stay f32). A "
                        "TPU-native speed mode with no reference analogue "
                        "(the all-f32 CPU-torch pipeline); codecs consume "
                        "the f32 gradients, so wire formats are unchanged")
    t.add_argument("--shrinkage-freq", type=int, default=50,
                   help="steps between lr shrink (reference hardcodes 50)")
    t.add_argument("--data-root", type=str, default="./data")
    t.add_argument("--synthetic", action="store_true", default=False,
                   help="force the synthetic dataset (offline smoke runs)")
    t.add_argument("--no-augment", action="store_true", default=False)
    t.add_argument("--save-freq", type=int, default=0,
                   help="checkpoint every N steps (0 = only at eval-freq)")
    t.add_argument("--resume", action="store_true", default=False)


def _warn_dead_flags(args: argparse.Namespace) -> None:
    if args.comm_type != "Bcast":
        warnings.warn(
            "--comm-type is accepted for parity but ignored (it is a fake "
            "parameter in the reference too, README.md:111)"
        )
    if args.num_aggregate is not None and (
        args.aggregate not in ("gather", "ring", "auto")
        or args.code.lower() in DENSE_CODES
    ):
        warnings.warn(
            "--num-aggregate only applies to compressed gather/ring "
            "aggregation (a dense psum cannot subset replicas); ignoring it "
            "— note the reference ignores it always "
            "(sync_replicas_master_nn.py:113,124)"
        )
    if args.enable_gpu or args.no_cuda:
        warnings.warn("--enable-gpu/--no-cuda are ignored: device selection is JAX's")


def _num_classes(dataset: str) -> int:
    from atomo_tpu.data import SPECS, canonical_name

    return SPECS[canonical_name(dataset)].num_classes


def _build_common(args: argparse.Namespace, need_train: bool = True):
    from atomo_tpu.codecs import get_codec
    from atomo_tpu.data import BatchIterator, load_dataset, synthetic_dataset, SPECS, canonical_name
    from atomo_tpu.models import get_model
    from atomo_tpu.training import make_optimizer

    from atomo_tpu.training.resilience import with_retries

    # dataset IO (downloads / NFS reads) is the classic transient failure:
    # bounded backoff instead of dying on the first blip
    load_dataset = with_retries(load_dataset, exceptions=(OSError,))

    name = canonical_name(args.dataset)

    def _zipf_ds(train: bool):
        # the zipf workload is synthetic by design and parameterized by
        # the CLI's table knobs — built directly so rows/slots/alpha
        # stay consistent with the embedding model below
        from atomo_tpu.data.zipf import zipf_dataset

        return zipf_dataset(
            train,
            rows=getattr(args, "emb_rows", 4096),
            slots=getattr(args, "zipf_slots", 8),
            alpha=getattr(args, "zipf_alpha", 1.1),
            seed=args.seed,
        )

    train_iter = None
    if need_train:  # the evaluator never touches the train split
        if name == "zipf":
            train_ds = _zipf_ds(True)
        elif args.synthetic:
            train_ds = synthetic_dataset(SPECS[name], True)
        else:
            train_ds = load_dataset(name, args.data_root, train=True)
        # data_seed may differ per host (multi-process shuffling); args.seed
        # itself must not — it also seeds model init and the SPMD step key
        train_iter = BatchIterator(
            train_ds, args.batch_size, seed=getattr(args, "data_seed", args.seed)
        )
    if name == "zipf":
        test_ds = _zipf_ds(False)
    elif args.synthetic:
        test_ds = synthetic_dataset(SPECS[name], False)
    else:
        test_ds = load_dataset(name, args.data_root, train=False)
    test_iter = BatchIterator(
        test_ds, args.test_batch_size, shuffle=False, drop_last=False, seed=args.seed
    )
    if args.network.lower() == "embedding":
        # table sizes are CLI knobs (the zipf id range must match them);
        # the registry's fixed-size entries serve everything else
        from atomo_tpu.models import EmbeddingTower

        model = EmbeddingTower(
            num_classes=_num_classes(args.dataset),
            rows=getattr(args, "emb_rows", 4096),
            dim=getattr(args, "emb_dim", 16),
        )
    else:
        model = get_model(args.network, _num_classes(args.dataset))
    optimizer = make_optimizer(
        args.optimizer,
        lr=args.lr,
        lr_shrinkage=args.lr_shrinkage,
        shrinkage_freq=args.shrinkage_freq,
        momentum=args.momentum,
        nesterov=args.nesterov,
        weight_decay=args.weight_decay,
        beta1=getattr(args, "adam_beta1", 0.9),
        beta2=getattr(args, "adam_beta2", 0.999),
        eps=getattr(args, "adam_eps", 1e-8),
        amsgrad=getattr(args, "amsgrad", False),
    )
    svd_rank = args.svd_rank
    if svd_rank == 0 and args.sample != "bernoulli":
        # reference semantics: rank 0 selects the p_i = s_i/s_0 Bernoulli
        # mode (svd.py:54-56), which only exists for --sample bernoulli;
        # for the static-shape samplers rank 0 would mean full rank
        # (payload > dense), so fall back to the canonical rank 3.
        if args.code.lower() == "svd":
            warnings.warn(
                "--svd-rank 0 maps to the reference's rank-0 mode only with "
                "--sample bernoulli; using rank 3 for the fixed-budget sampler"
            )
        svd_rank = 3
    # --svd-mode is the coarse mode surface over --svd-algo (exact |
    # randomized | auto); both pinned and disagreeing is a config error,
    # not a silent precedence
    svd_algo = getattr(args, "svd_algo", "auto")
    svd_mode = getattr(args, "svd_mode", "auto")
    if svd_mode != "auto":
        if svd_algo not in ("auto", svd_mode):
            raise SystemExit(
                f"--svd-mode {svd_mode} and --svd-algo {svd_algo} disagree "
                "(they select the same decomposition knob); pin one"
            )
        svd_algo = svd_mode
    codec = get_codec(
        args.code,
        svd_rank=svd_rank,
        quantization_level=args.quantization_level,
        bucket_size=args.bucket_size,
        sample=args.sample,
        algorithm=svd_algo,
        wire_dtype=getattr(args, "svd_wire", "float32"),
    )
    if args.code.lower() in DENSE_CODES:
        codec = None  # dense path: plain psum aggregation
    return model, optimizer, codec, train_iter, test_iter, name


def _codec_byte_budget(codec, model_init_fn) -> tuple[int, int]:
    """(dense_bytes, payload_bytes) for one gradient exchange, computed at
    zero cost with jax.eval_shape — now one implementation shared with
    the autopilot (tuning.probe.byte_budget)."""
    from atomo_tpu.tuning.probe import byte_budget

    return byte_budget(codec, model_init_fn)


def _resolve_auto_aggregate(
    args, codec, model_init_fn, n_dev, *, allow_hierarchical=True,
    allow_ring=True, log=print,
) -> str:
    """``--aggregate auto`` (VERDICT r4 #3): pick the exchange mode from
    the measured comm-cost model and always say why in one line.

    On a two-tier mesh (``--dcn-ways`` > 1 or multi-host) the advisory
    quotes PER-TIER numbers from :class:`TwoTierFabric` — a single
    blended bandwidth would price ICI hops at DCN speed — and runs the
    topology planner; the chosen plan is stashed on ``args._auto_plan``
    for the caller to execute."""
    import jax

    from atomo_tpu.utils.comm_model import choose_aggregate, resolve_fabric

    n_proc = jax.process_count()
    dcn_ways = getattr(args, "dcn_ways", 0)
    cross_host = (n_proc > 1 or dcn_ways > 1) and allow_hierarchical
    dense_b = payload_b = 0
    if codec is not None:
        dense_b, payload_b = _codec_byte_budget(codec, model_init_fn)
    if cross_host and codec is not None:
        # two-tier: per-tier advisory + planner, not a blended scalar
        from atomo_tpu.topology.fabric import resolve_two_tier
        from atomo_tpu.topology.schedule import choose_plan

        k = dcn_ways or max(n_proc, 2)
        try:
            fabric2 = resolve_two_tier(
                args.fabric, dcn_ways=k, n_dev=n_dev, n_proc=n_proc,
                measured=getattr(args, "_fabric_probe", None),
            )
        except ValueError as exc:
            raise SystemExit(str(exc)) from None
        # an explicit --plan wins the precedence chain, so the advisory
        # must price THAT plan (printing the planner's own pick here
        # would announce a schedule that will not run); restricting the
        # plan space to the pinned name keeps the per-tier numbers while
        # skipping the selection
        pinned = getattr(args, "plan", "auto")
        pinned_names = None
        suffix = ""
        if pinned != "auto":
            from atomo_tpu.topology.schedule import plan_from_name

            pinned_names = (plan_from_name(pinned).name,)
            suffix = " — pinned by --plan, planner selection skipped"
        plan, plan_reason = choose_plan(
            dense_bytes=dense_b,
            payload_bytes=payload_b,
            fabric=fabric2,
            tax_s=(
                None if args.codec_tax_ms is None
                else args.codec_tax_ms / 1e3
            ),
            plan_names=pinned_names,
        )
        if pinned == "auto":
            args._auto_plan = plan.name
        log(
            f"--aggregate auto -> hierarchical ({fabric2.describe()}; "
            f"{plan_reason}{suffix})"
        )
        return "hierarchical"
    try:
        bw = resolve_fabric(
            args.fabric, n_proc=n_proc,
            measured=getattr(args, "_fabric_probe", None),
        )
    except ValueError as exc:
        raise SystemExit(str(exc)) from None
    mode, reason = choose_aggregate(
        has_codec=codec is not None,
        dense_bytes=dense_b,
        payload_bytes=payload_b,
        ways=n_dev,
        fabric_bw=bw,
        tax_s=None if args.codec_tax_ms is None else args.codec_tax_ms / 1e3,
        cross_host=cross_host,
        allow_ring=allow_ring,
    )
    log(f"--aggregate auto -> {mode} ({reason})")
    return mode


def _diverged_exit(exc: Exception) -> int:
    """Map a DivergenceError (in-process rollback budget spent) to the
    rollback-requested exit code the run-level supervisor triages."""
    from atomo_tpu.training.resilience import ROLLBACK_EXIT_CODE

    print(
        f"Divergence doctor gave up: {exc}; diverged checkpoint tail "
        f"pruned to the last healthy step, exiting rc={ROLLBACK_EXIT_CODE} "
        "(rollback-requested — a supervisor restarts from there, and an "
        "unsupervised --resume lands there too)",
        flush=True,
    )
    return ROLLBACK_EXIT_CODE


def _membership_exit(exc: Exception) -> int:
    """Map a MembershipChange (elastic epoch boundary) to the exit code
    the run-level supervisor triages as a planned reshape (re-exec at the
    recorded world size, no restart budget charged)."""
    from atomo_tpu.training.resilience import MEMBERSHIP_EXIT_CODE

    print(
        f"Elastic membership boundary: {exc}; exiting "
        f"rc={MEMBERSHIP_EXIT_CODE} (membership-change — a supervisor "
        "re-execs at the recorded world size; unsupervised runs restart "
        f"manually with --n-devices {exc.world_size} --resume)",
        flush=True,
    )
    return MEMBERSHIP_EXIT_CODE


def _partition(args: argparse.Namespace) -> str:
    """Resolve the weight-update partition knob to one of
    {'replicated', 'zero1', 'sharded_update'} — ``--zero1`` is the legacy
    alias for ``--partition zero1`` and conflicts with the full
    sharded-update (which supersedes it as the shard-state-only
    degenerate point)."""
    p = getattr(args, "partition", "replicated").replace("-", "_")
    if getattr(args, "zero1", False):
        if p == "sharded_update":
            raise SystemExit(
                "--zero1 conflicts with --partition sharded-update: "
                "ZeRO-1 is the sharded update's shard-state-only "
                "degenerate point — pass one of the two"
            )
        p = "zero1"
    return p


def _quorum_q(args: argparse.Namespace):
    """Parse ``--quorum``: None for 'off', else the validated Q floor.
    One grammar for preflight and the run (a typo'd value must fail
    before the supervisor re-exec, like every other argv-knowable
    reject)."""
    q = getattr(args, "quorum", "off")
    if q in ("off", "", None):
        return None
    try:
        v = int(q)
    except (TypeError, ValueError):
        raise SystemExit(
            f"--quorum {q!r}: expected 'off' or a positive integer "
            "(the number of replicas a step waits for)"
        )
    if v < 1:
        raise SystemExit(
            f"--quorum {v}: must be >= 1 (a step has to consume at "
            "least one arrival)"
        )
    return v


def _argv_preflight(args: argparse.Namespace) -> None:
    """Deterministic config conflicts knowable from argv alone, checked
    BEFORE the supervisor re-exec (and before the jax backend initializes
    — the supervisor parent never calls jax.devices(), so the chip stays
    free for its child): a typo'd flag must fail fast with its reason, not burn
    the restart budget as a chain of "crash" incidents. Conflicts that
    need the resolved device count or the built codec are (re-)checked in
    the run itself."""
    partition = _partition(args)  # raises on the --zero1 conflict
    if partition == "sharded_update":
        # the sharded-update compatibility matrix, argv-knowable half
        # (the loop re-checks with the resolved mesh)
        if getattr(args, "elastic", False):
            raise SystemExit(
                "--elastic runs the replicated update for now (the live "
                "reshape path, mesh.reshard.reshard_replicated, moves "
                "the replicated layout; the sharded-update master "
                "shards are world-shaped — "
                "mesh.reshard.reshard_sharded_update exists but the "
                "elastic loop does not drive it); drop --partition "
                "sharded-update"
            )
        if args.on_diverge != "off":
            raise SystemExit(
                "--on-diverge rollback rebuilds replicated templates "
                "and cannot re-thread the sharded master layout yet; "
                "drop --partition sharded-update or --on-diverge"
            )
        if getattr(args, "sparse_rows", "off") != "off":
            raise SystemExit(
                "--partition sharded-update does not compose with "
                "--sparse-rows yet (the row exchange is untested "
                "against the flat master layout)"
            )
    if args.superstep < 0:
        raise SystemExit(
            f"--superstep {args.superstep}: must be >= 1 (or 0 for the "
            "per-backend auto default)"
        )
    if getattr(args, "auto", "off") in ("tune", "controller"):
        # pin or tune, not both: a knob whose value differs from its
        # auto/default sentinel was pinned by the user, and silently
        # overriding an explicit choice is worse than refusing. (Values,
        # not argv, define "pinned": re-passing a default is a no-op.)
        # The controller inherits the whole matrix — it picks a SUPERSET
        # of the autopilot's knobs.
        pinned = []
        if args.aggregate != "auto":
            pinned.append(f"--aggregate {args.aggregate}")
        if args.overlap != "off":
            pinned.append(f"--overlap {args.overlap}")
        if getattr(args, "stream_encode", "off") != "off":
            pinned.append(f"--stream-encode {args.stream_encode}")
        if getattr(args, "sparse_rows", "off") == "on":
            # "auto" is the explore sentinel (the +sp candidates decide);
            # "on" is a pinned knob like any other
            pinned.append(f"--sparse-rows {args.sparse_rows}")
        if args.superstep != 0:
            pinned.append(f"--superstep {args.superstep}")
        if getattr(args, "plan", "auto") != "auto":
            pinned.append(f"--plan {args.plan}")
        if getattr(args, "quorum", "off") != "off":
            # quorum is a pinned knob like --overlap: the autopilot's
            # +qK candidates explore it only when it is NOT pinned
            pinned.append(f"--quorum {args.quorum}")
        if pinned:
            raise SystemExit(
                f"--auto {args.auto} picks the performance knobs itself "
                f"and conflicts with the pinned {', '.join(pinned)}; drop "
                "the pinned flag(s) to let it choose, or drop "
                f"--auto {args.auto} to keep your explicit config"
            )
        if not args.train_dir:
            raise SystemExit(
                f"--auto {args.auto} needs a --train-dir: the decision "
                "artifact and the online re-tuner's incident log live "
                "there"
            )
    if getattr(args, "fabric", "auto") == "measured":
        # argv-knowable half of the measured-fabric contract; the
        # resolved device count is re-checked in cmd_train
        if not args.train_dir:
            raise SystemExit(
                "--fabric measured records the startup probe in "
                "train_dir/fabric_probe.json and needs a --train-dir"
            )
        if args.n_devices == 1:
            raise SystemExit(
                "--fabric measured needs a multi-device mesh: a single "
                "device has no inter-chip fabric to measure"
            )
    plan_flag = getattr(args, "plan", "auto")
    if plan_flag not in ("auto", "legacy"):
        from atomo_tpu.topology.schedule import plan_from_name

        try:
            # pure-python plan-name grammar: a typo'd --plan must fail
            # here, not in every re-exec'd jax-booted child
            plan_from_name(plan_flag)
        except ValueError as exc:
            raise SystemExit(str(exc))
    if plan_flag != "auto" and args.aggregate not in (
        "auto", "hierarchical"
    ):
        raise SystemExit(
            f"--plan {plan_flag} selects a two-level hierarchical "
            f"schedule and cannot compose with --aggregate "
            f"{args.aggregate}; use --aggregate hierarchical (or auto on "
            "a --dcn-ways mesh)"
        )
    if args.overlap == "delayed":
        if args.code.lower() in DENSE_CODES:
            raise SystemExit(
                "--overlap delayed needs a compressing --code (the mode "
                "overlaps the encoded exchange+decode; dense training has "
                "no delayed form)"
            )
        if args.n_devices == 1:
            raise SystemExit(
                "--overlap delayed needs a multi-device mesh: single-device "
                "training has no exchange to take off the critical path"
            )
        if args.aggregate in ("psum", "hierarchical"):
            raise SystemExit(
                f"--overlap delayed does not compose with --aggregate "
                f"{args.aggregate} (only the compressed flat gather/ring "
                "exchanges have a delayed form; no two-level topology "
                "plan — legacy or re-encoded — does)"
            )
        if plan_flag != "auto":
            raise SystemExit(
                f"--overlap delayed does not compose with --plan "
                f"{plan_flag}: no two-level topology plan — legacy or "
                "re-encoded — has a delayed form; drop one"
            )
        if (
            _partition(args) == "zero1"
            and args.max_restarts > 0
            and args.train_dir
        ):
            # the LEGACY dead end, kept on the legacy path only: the new
            # sharded path (--partition sharded-update) checkpoints the
            # in-flight payload as a sharded carry leaf and resumes
            # bit-exact (drilled: tests/test_mesh.py kill->restart drill)
            raise SystemExit(
                "--max-restarts with --zero1 --overlap delayed cannot work: "
                "supervised restarts resume from checkpoints, and a "
                "--zero1 run cannot resume the delayed in-flight payload "
                "(the legacy sharded optimizer template cannot carry it) "
                "— every restart would fail instantly and burn the "
                "budget; drop one of the three, or switch to --partition "
                "sharded-update, whose checkpoints hold the payload as a "
                "sharded carry leaf and resume bit-exact"
            )
    if getattr(args, "stream_encode", "off") == "on":
        if args.code.lower() in DENSE_CODES:
            raise SystemExit(
                "--stream-encode needs a compressing --code (the mode "
                "pipelines the per-bucket ENCODE under backprop; dense "
                "training has no encode to stream)"
            )
        if args.n_devices == 1:
            raise SystemExit(
                "--stream-encode needs a multi-device mesh: single-device "
                "training has no exchange whose encode is on the critical "
                "path"
            )
        if args.aggregate in ("psum", "hierarchical"):
            raise SystemExit(
                f"--stream-encode does not compose with --aggregate "
                f"{args.aggregate}: psum ships dense gradients (no encode "
                "to stream), and the hierarchical boundary re-encode is "
                "not bucket-aware yet — the honest reject until it is; "
                "use --aggregate gather or ring"
            )
        if plan_flag != "auto":
            raise SystemExit(
                f"--stream-encode does not compose with --plan "
                f"{plan_flag}: the two-level topology schedules re-encode "
                "at the fabric boundary, which is not bucket-aware yet; "
                "drop one"
            )
    if getattr(args, "sparse_rows", "off") != "off":
        if args.n_devices == 1 and args.sparse_rows == "on":
            # "auto" degrades gracefully in cmd_train (single device ->
            # all-dense, out loud); only the pinned "on" is a hard
            # config error here
            raise SystemExit(
                "--sparse-rows needs a multi-device mesh: single-device "
                "training has no exchange to save wire on"
            )
        if args.aggregate == "psum":
            raise SystemExit(
                "--sparse-rows does not compose with --aggregate psum: "
                "the row payloads would ride a full dense all-reduce "
                "wire, so the sparse exchange degenerates (the SparCML "
                "crossover can never pay); use --aggregate gather or ring"
            )
        if args.aggregate == "hierarchical" or plan_flag != "auto":
            raise SystemExit(
                "--sparse-rows does not compose with hierarchical "
                "aggregation (--aggregate hierarchical / --plan): the "
                "boundary re-encode composes a second estimator per "
                "layer and is not row-aware yet — rejected honestly"
            )
        if args.overlap == "delayed":
            raise SystemExit(
                "--sparse-rows does not compose with --overlap delayed: "
                "the carried payload's shapes are assignment-specific "
                "and the consume chain is not row-aware yet"
            )
        if getattr(args, "stream_encode", "off") == "on":
            raise SystemExit(
                "--sparse-rows does not compose with --stream-encode: "
                "the layer-bucket encode pipeline is not "
                "assignment-aware yet; drop one"
            )
        if (
            args.grad_guard or args.max_grad_norm > 0
            or getattr(args, "elastic", False)
        ):
            raise SystemExit(
                "--sparse-rows does not compose with the gradient guard "
                "(--grad-guard / --max-grad-norm) or --elastic: the row "
                "exchange has no skip-and-rescale masking yet — run the "
                "guard all-dense"
            )
        if args.num_aggregate is not None:
            raise SystemExit(
                "--sparse-rows does not compose with --num-aggregate: "
                "the rotating replica subset is not wired into the row "
                "exchange"
            )
        if (
            getattr(args, "auto", "off") in ("tune", "controller")
            and args.code.lower() in DENSE_CODES
        ):
            raise SystemExit(
                "--auto tune with --sparse-rows needs a compressing "
                "--code: with --code sgd the dense-assigned leaves' only "
                "exchange is the plain dense wire, so there is no "
                "candidate space for the +sp variants to compete in — "
                "pick a compressing --code or drop --auto tune"
            )
    if getattr(args, "obs_record", False) and not args.train_dir:
        raise SystemExit(
            "--obs-record appends per-step telemetry to "
            "train-dir/metrics.jsonl and needs a --train-dir"
        )
    if getattr(args, "obs_quality", False):
        if args.code.lower() in DENSE_CODES:
            raise SystemExit(
                "--obs-quality probes the codec's estimator error; dense "
                "training (--code sgd) has no estimator to probe"
            )
        if args.overlap == "delayed":
            raise SystemExit(
                "--obs-quality does not compose with --overlap delayed: "
                "the carried payload describes the PREVIOUS step, so a "
                "per-step per-layer error column would be off by one — "
                "rejected honestly rather than silently mis-attributed"
            )
        if args.aggregate == "hierarchical" or plan_flag != "auto":
            raise SystemExit(
                "--obs-quality needs flat gather/ring/psum aggregation: "
                "the hierarchical boundary re-encode composes two "
                "estimators per layer and is not probe-aware yet"
            )
    if (
        getattr(args, "budget_bytes", 0.0)
        and getattr(args, "budget_alloc", "uniform") != "variance"
    ):
        raise SystemExit(
            "--budget-bytes sizes the variance allocation's global wire "
            "budget and needs --budget-alloc variance (uniform spends "
            "the fixed --svd-rank budget per layer by definition)"
        )
    if getattr(args, "budget_alloc", "uniform") == "variance":
        # the adaptive-budget conflict matrix, argv-knowable half: the
        # water-filling solver implements the fixed_k variance law
        # V(k) = A/k — every other pairing is rejected honestly until
        # its law is stated too (allocator module docstring)
        if args.code.lower() in DENSE_CODES:
            raise SystemExit(
                "--budget-alloc variance allocates a compressing codec's "
                "per-layer budget; dense training has no budget to "
                "allocate"
            )
        if args.code.lower() not in ("svd", "qsgd"):
            raise SystemExit(
                f"--budget-alloc variance needs --code svd (the fixed_k "
                "rank law A/k) or --code qsgd (the bit law "
                f"B/(2^b-1)^2); per-layer allocation for {args.code!r} "
                "is the same machinery with a different pricing/"
                "variance pair and is not stated yet — rejected "
                "honestly (terngrad's max-norm scale + sigma clip "
                "included)"
            )
        if args.code.lower() == "svd" and args.sample != "fixed_k":
            raise SystemExit(
                f"--budget-alloc variance with --code svd needs "
                f"--sample fixed_k (the stated variance law is the "
                f"with-replacement sampler's A/k; --sample "
                f"{args.sample} has a different law)"
            )
        if args.aggregate == "hierarchical" or plan_flag != "auto":
            raise SystemExit(
                "--budget-alloc variance needs flat gather/ring/psum "
                "aggregation: the hierarchical boundary re-encode is not "
                "allocation-aware yet"
            )
        if getattr(args, "sparse_rows", "off") != "off" and (
            getattr(args, "auto", "off") != "controller"
        ):
            raise SystemExit(
                "--budget-alloc variance with --sparse-rows is a JOINT "
                "decision: the hybrid planner must re-price its dense "
                "sub-list under the allocated per-leaf codec, and the "
                "two single deciders each assume the other's knob is at "
                "its default. --auto controller prices and probes "
                "exactly that cross term (the +sp+ab candidates) — use "
                "it; the static pairing stays rejected"
            )
        if (
            args.on_diverge != "off"
            and getattr(args, "obs_quality", False)
            and getattr(args, "obs_record", False)
        ):
            raise SystemExit(
                "--budget-alloc variance with --obs-quality --obs-record "
                "arms online re-allocation at checkpoint boundaries, "
                "which cannot compose with --on-diverge: a rollback "
                "would replay pre-reallocation steps under the "
                "post-reallocation program — drop --on-diverge, or "
                "freeze the allocation by dropping --obs-record or "
                "--obs-quality"
            )
    if getattr(args, "error_feedback", False):
        # the EfState bias-contract conflict matrix, argv-knowable half
        # (parallel.replicated re-checks in the builder and the loop)
        if args.code.lower() in DENSE_CODES:
            raise SystemExit(
                "--error-feedback accumulates the codec's compression "
                "residual; dense training (--code sgd) has none"
            )
        if args.n_devices == 1:
            raise SystemExit(
                "--error-feedback needs a multi-device mesh: the "
                "residual compensates the exchanged estimator's error, "
                "and single-device training has no exchange"
            )
        if args.overlap == "delayed":
            raise SystemExit(
                "--error-feedback does not compose with --overlap "
                "delayed: the stale carry's residual semantics are "
                "unproven — rejected honestly"
            )
        if args.aggregate == "hierarchical" or plan_flag != "auto":
            raise SystemExit(
                "--error-feedback needs flat gather/ring/psum "
                "aggregation: the hierarchical boundary re-encode's "
                "unbiased-by-composition argument does not survive the "
                "EF bias"
            )
        if getattr(args, "sparse_rows", "off") != "off":
            raise SystemExit(
                "--error-feedback does not compose with --sparse-rows "
                "(the mixed per-leaf residual carry is untested)"
            )
        if args.num_aggregate is not None:
            raise SystemExit(
                "--error-feedback does not compose with --num-aggregate: "
                "an unconsumed encode's residual would be mis-attributed"
            )
        if (
            args.grad_guard or args.max_grad_norm > 0
            or getattr(args, "elastic", False)
        ):
            raise SystemExit(
                "--error-feedback does not compose with the gradient "
                "guard (--grad-guard / --max-grad-norm) or --elastic: "
                "skip-and-rescale rests on the unbiasedness EF trades "
                "away"
            )
        if args.on_diverge != "off":
            raise SystemExit(
                "--error-feedback does not compose with --on-diverge: "
                "the rollback reload does not rebuild the residual "
                "template yet"
            )
        if _partition(args) != "replicated":
            raise SystemExit(
                "--error-feedback does not compose with --zero1 / "
                "--partition sharded-update yet: the residual carry is "
                "untested against the sharded state templates"
            )
        # --auto tune/controller DOES compose with EF now (ISSUE-17
        # satellite): the probe harness builds the residual-carry step,
        # the candidate space narrows to the flat blocking programs EF
        # supports (tune() applies the same matrix as the rejects
        # above), and every probed row carries the bias contract in
        # its record plus a probe_note naming the changed comparison
        # basis.
        if not (args.code.lower() == "svd" and args.sample == "topk"):
            # svd+topk is the one contraction estimator in the registry;
            # every other compressing code (svd random samplers, qsgd,
            # terngrad — unbiased stochastic quantizers) carries the
            # same random-walk residual risk the bias contract states
            warnings.warn(
                "--error-feedback pairs with a CONTRACTION compressor "
                "(--code svd --sample topk): the unbiased random "
                "estimators make the residual a random walk (measured "
                "divergent on the LeNet recipe); proceeding, but "
                "svd+topk is the supported pairing"
            )
    import os

    q_val = _quorum_q(args)  # raises on a malformed --quorum value
    if q_val is None:
        if getattr(args, "replay_arrivals", ""):
            raise SystemExit(
                "--replay-arrivals replays a recorded quorum arrival "
                "schedule and needs --quorum"
            )
    else:
        # the quorum compatibility matrix, argv-knowable half (the loop
        # and the step builder re-check with the resolved mesh/codec):
        # quorum rides the payload gather/ring exchange and feeds a
        # fresh host-derived arrival vector every step, so everything
        # that re-shapes the exchange, carries cross-step payload state,
        # or fuses steps is rejected with its reason
        if args.staleness < 1:
            raise SystemExit(
                f"--staleness {args.staleness}: must be >= 1 (0 would "
                "mean blocking aggregation — drop --quorum instead)"
            )
        if getattr(args, "quorum_period_ms", 100.0) <= 0:
            raise SystemExit(
                f"--quorum-period-ms {args.quorum_period_ms}: must be "
                "> 0 (it converts a straggler's seconds of lag into "
                "whole steps)"
            )
        if args.code.lower() in DENSE_CODES:
            raise SystemExit(
                "--quorum rides the encoded payload exchange (the "
                "staleness ring carries payloads, not dense gradients); "
                "pick a compressing --code"
            )
        if args.n_devices == 1:
            raise SystemExit(
                "--quorum needs a multi-device mesh: a single device "
                "has no stragglers to absorb"
            )
        if args.aggregate in ("psum", "hierarchical"):
            raise SystemExit(
                f"--quorum does not compose with --aggregate "
                f"{args.aggregate}: only the flat payload gather/ring "
                "exchanges carry the staleness ring; psum ships dense "
                "gradients and the hierarchical boundary re-encode is "
                "not arrival-aware"
            )
        if getattr(args, "plan", "auto") != "auto":
            raise SystemExit(
                "--quorum does not compose with --plan: the two-level "
                "topology schedules are not arrival-aware; drop one"
            )
        if args.overlap == "delayed":
            raise SystemExit(
                "--quorum does not compose with --overlap delayed: "
                "both modes carry cross-step payload state, and "
                "composing the delayed carry with the staleness ring "
                "would double-count a step of lag — the quorum carry "
                "IS the bounded generalization of the delayed one"
            )
        if getattr(args, "stream_encode", "off") == "on":
            raise SystemExit(
                "--quorum does not compose with --stream-encode: the "
                "bucket-streamed encode is not staleness-ring-aware yet"
            )
        if getattr(args, "sparse_rows", "off") != "off":
            raise SystemExit(
                "--quorum does not compose with --sparse-rows: the "
                "row payloads' shapes are assignment-specific and the "
                "staleness ring is not row-aware yet"
            )
        if getattr(args, "error_feedback", False):
            raise SystemExit(
                "--quorum does not compose with --error-feedback: a "
                "dropped stale payload's residual would be "
                "mis-attributed — rejected honestly"
            )
        if getattr(args, "elastic", False):
            raise SystemExit(
                "--quorum does not compose with --elastic: membership "
                "tracks replicas that LEFT, the staleness ring carries "
                "replicas that are LATE — one absorption mechanism at "
                "a time"
            )
        if _partition(args) != "replicated":
            raise SystemExit(
                "--quorum does not compose with --zero1 / --partition "
                "sharded-update yet: the staleness-ring carry is "
                "untested against the sharded state templates"
            )
        if args.num_aggregate is not None:
            raise SystemExit(
                "--quorum does not compose with --num-aggregate: the "
                "arrival schedule already decides which replicas "
                "contribute each step"
            )
        if args.superstep > 1:
            raise SystemExit(
                f"--superstep {args.superstep} does not compose with "
                "--quorum: the host feeds a fresh arrival vector every "
                "step, which a fused K-step scan cannot consume"
            )
        if getattr(args, "obs_quality", False):
            raise SystemExit(
                "--quorum does not compose with --obs-quality: a stale "
                "payload's per-layer error column would describe an "
                "earlier step's gradient — rejected honestly rather "
                "than silently mis-attributed"
            )
        if args.on_diverge != "off":
            raise SystemExit(
                "--quorum does not compose with --on-diverge: the "
                "rollback reload does not rebuild the staleness-ring "
                "template yet"
            )
        if getattr(args, "replay_arrivals", "") and not os.path.exists(
            args.replay_arrivals
        ):
            raise SystemExit(
                f"--replay-arrivals {args.replay_arrivals!r}: no such "
                "file"
            )
    chaos_specs = [args.chaos] if args.chaos else []
    if not args.chaos and os.environ.get("ATOMO_CHAOS"):
        # the flagless path: supervised children inherit the env, so a
        # typo'd env spec would burn the budget exactly like a typo'd flag
        chaos_specs.append(os.environ["ATOMO_CHAOS"])
    for spec in chaos_specs:
        from atomo_tpu.utils.chaos import ChaosConfig

        try:
            _chaos_cfg = ChaosConfig.from_spec(spec)
        except ValueError as exc:
            # deterministic from argv/env: a typo'd fault spec must not
            # re-exec jax-booting children through the whole restart budget
            raise SystemExit(str(exc))
        from atomo_tpu.utils.tracing import MEMBERSHIP_EPOCH_ENV

        _epoch0 = int(os.environ.get(MEMBERSHIP_EPOCH_ENV, "0") or 0) == 0
        if _chaos_cfg.die_faults and _epoch0:
            # die@ fires only at membership epoch 0: past a reshape the
            # fault is disarmed, and validating its replica index against
            # the NEW (shrunken) world would kill the supervisor's own
            # re-exec'd child with rc=2 mid-reshape — so every die check
            # applies to epoch-0 children only.
            # die@ models a member the GUARD carries: without the screen
            # the persistent NaN poisons every replica's mean on step S
            # and the drill proves nothing — deterministic, so fail here
            if not (args.grad_guard or args.max_grad_norm > 0):
                raise SystemExit(
                    "chaos die@S:R models a replica that stops "
                    "contributing and is carried by the guard's "
                    "skip-and-rescale; arm --grad-guard (or "
                    "--max-grad-norm)"
                )
            if args.n_devices == 1:
                raise SystemExit(
                    "chaos die@S:R targets one replica of a multi-device "
                    "mesh; single-device training has no surviving "
                    "replicas to continue on"
                )
            if args.n_devices >= 2:
                # a typo'd replica index would silently inject NOTHING
                # and the drill would "pass" having proven nothing —
                # argv-knowable for an explicit mesh, so fail fast here
                # (--n-devices 0 defers to the in-run check)
                bad = [
                    r for _, r in _chaos_cfg.die_faults
                    if r >= args.n_devices
                ]
                if bad:
                    raise SystemExit(
                        f"chaos die@S:R targets replica(s) {sorted(bad)} "
                        f"outside the {args.n_devices}-device mesh "
                        "(replicas are 0-based); the fault would never "
                        "fire and the drill would prove nothing"
                    )
        if _chaos_cfg.slow_replica_faults and _epoch0:
            # slow@'s die@-style preflight: a typo'd replica index would
            # silently straggle NOTHING and the drill would "pass"
            # having proven nothing — argv-knowable for an explicit mesh
            if args.n_devices == 1:
                raise SystemExit(
                    "chaos slow@S:R:SEC delays one replica of a "
                    "multi-device mesh; single-device training has no "
                    "exchange for a straggler to hold up"
                )
            if args.n_devices >= 2:
                bad = [
                    r for _, r, _ in _chaos_cfg.slow_replica_faults
                    if r >= args.n_devices
                ]
                if bad:
                    raise SystemExit(
                        f"chaos slow@S:R:SEC targets replica(s) "
                        f"{sorted(bad)} outside the "
                        f"{args.n_devices}-device mesh (replicas are "
                        "0-based); the fault would never fire and the "
                        "drill would prove nothing"
                    )
    if getattr(args, "readmit_at", 0) and not getattr(args, "elastic", False):
        raise SystemExit(
            "--readmit-at re-admits a shrunken world's member and needs "
            "--elastic"
        )
    if getattr(args, "elastic", False):
        # the elastic compatibility matrix, argv-knowable half (the loop
        # re-checks with the resolved mesh): every reject here is
        # deterministic and must not burn the restart budget
        if not args.train_dir:
            raise SystemExit(
                "--elastic needs a --train-dir: membership.json and the "
                "shrink/grow restarts resume from checkpoints"
            )
        if not (args.grad_guard or args.max_grad_norm > 0):
            raise SystemExit(
                "--elastic needs --grad-guard: a dead member is carried "
                "by the guard's skip-and-rescale until the shrink boundary"
            )
        if not (args.save_freq or args.eval_freq):
            raise SystemExit(
                "--elastic needs a checkpoint cadence (--save-freq or "
                "--eval-freq > 0): membership transitions happen at "
                "checkpoint boundaries"
            )
        if args.n_devices == 1:
            raise SystemExit(
                "--elastic needs a multi-device mesh: a single device "
                "has no surviving roster to shrink to"
            )
        if args.zero1:
            raise SystemExit(
                "--elastic cannot compose with --zero1 (the sharded "
                "optimizer layout is world-size-specific; a shrink "
                "restart could not resume it)"
            )
        if args.overlap == "delayed":
            raise SystemExit(
                "--elastic cannot compose with --overlap delayed (the "
                "in-flight carry is shaped by the world size; a shrink "
                "restart could not resume it)"
            )
        if args.aggregate == "hierarchical" or plan_flag != "auto":
            raise SystemExit(
                "--elastic is flat-mesh only (gather/ring/psum): "
                "hierarchical schedules drop whole inner groups, while "
                "membership tracks single replicas — drop --aggregate "
                "hierarchical / --plan"
            )
        if args.elastic_patience < 1:
            raise SystemExit(
                f"--elastic-patience {args.elastic_patience}: must be >= 1"
            )
    if args.on_diverge != "off":
        from atomo_tpu.training.resilience import (
            DetectorConfig,
            diverge_conflict,
        )

        try:
            # pure-python knob validation (window >= 2, patience >= 1, ...):
            # degenerate detector knobs are argv-knowable and must fail here,
            # not as a ValueError in every re-exec'd jax-booted child
            DetectorConfig(
                window=args.diverge_window,
                zmax=args.diverge_zmax,
                patience=args.diverge_patience,
                min_history=args.diverge_min_history,
            )
        except ValueError as exc:
            raise SystemExit(str(exc))

        # mirror the in-run check's n_dev>1 gating as far as argv allows:
        # multi-device features are claimed only for an explicit mesh
        # (>= 2). --n-devices 0 (= all visible) is ambiguous without
        # booting jax — on a 1-device host an aggressive claim would
        # falsely reject configs the run accepts — so it defers to the
        # in-run check, which is cheap now that deterministic in-run
        # rejects exit CONFIG_EXIT_CODE and a supervisor gives up at once
        multi = args.n_devices >= 2
        reason = diverge_conflict(
            args.on_diverge,
            train_dir=args.train_dir,
            codec=None if args.code.lower() in DENSE_CODES else args.code,
            aggregate=args.aggregate if multi else None,
            overlap=args.overlap,
            zero1=_partition(args) == "zero1" and multi,
            num_aggregate=args.num_aggregate if multi else None,
            keep_ckpts=args.keep_ckpts,
            # the loops save every `save_freq or eval_freq` steps — check
            # the cadence they will actually run with
            save_freq=args.save_freq or args.eval_freq,
            window=args.diverge_window,
        )
        if reason:
            raise SystemExit(reason)


def _stream_bucket_bytes(args) -> int:
    """--stream-bucket-mb -> bytes (<= 0 means the single-bucket plan)."""
    mb = float(getattr(args, "stream_bucket_mb", 4.0))
    return int(mb * (1 << 20)) if mb > 0 else 0


def _real_stream_buckets(model_init_fn, bucket_bytes: int) -> int:
    """The REAL layer-bucket count of the stream-encode plan this model
    would execute — leaf shapes via jax.eval_shape (free, nothing
    materializes), then the same planner the step builder runs. Prices
    the autopilot's +se candidates' encode tail honestly where the
    byte-ratio estimate cannot (a single oversized leaf is ONE bucket,
    not dense/bucket_bytes of them)."""
    import jax

    from atomo_tpu.parallel.common import plan_layer_buckets

    return plan_layer_buckets(
        jax.eval_shape(model_init_fn), bucket_bytes
    ).n_buckets


def _run_autopilot(args, model, optimizer, codec, train_iter, n_dev,
                   save_freq, sparse_plan=None, budget_ctx=None,
                   hybrid_inputs=None):
    """``--auto tune`` / ``--auto controller``: run the startup probe
    ladder, apply the winning knob vector onto ``args`` (aggregate /
    overlap / ring bucket) and return ``(superstep, tuner)`` — the
    chosen fused-block size plus the armed online retuner (or None when
    there is no checkpoint cadence to snap a re-probe to). The decision
    artifact lands in ``train_dir/tune_decision.json``; the subsequent
    training trajectory is bit-identical to launching the chosen config
    statically (probes never touch the data iterator or the run's init
    seed).

    Under ``--auto controller`` the solve is the JOINT one
    (:func:`atomo_tpu.controller.solve_controller` — the legacy deciders
    composed inside one priced enumeration), the artifact is
    ``controller_decision.json`` (legacy artifacts still resume, with a
    stated fallback), and the returned tuner is a
    :class:`~atomo_tpu.controller.ControllerRetuner` so every online
    change lands as one ``controller_redecide`` incident.
    ``hybrid_inputs`` (the ``plan_hybrid`` argument triple) enables the
    controller's ``+sp+ab`` cross term."""
    import jax
    import jax.numpy as jnp

    from atomo_tpu.tuning.autopilot import (
        OnlineRetuner,
        decision_path,
        tune,
    )
    from atomo_tpu.tuning.probe import (
        model_init_fn,
        probe_batch_size,
        probe_candidate,
    )

    is_ctl = getattr(args, "auto", "off") == "controller"
    tag = "Controller" if is_ctl else "Autopilot"
    if jax.process_count() > 1:
        raise SystemExit(
            f"--auto {args.auto} is single-host for now (probe meshes "
            "are built over this host's devices; a multi-host probe "
            "would need every process in the dispatch loop); pick knobs "
            "explicitly on multi-host meshes — hierarchical plans ARE "
            "probed on single-host --dcn-ways meshes"
        )
    dcn_ways = 0
    if getattr(args, "dcn_ways", 0) > 1 and n_dev > 1:
        # a forced two-tier mesh: the candidate space gains one
        # hierarchical candidate per topology plan, probed on the
        # (dp=K, ici=n/K) mesh the train path would run
        dcn_ways = args.dcn_ways
        if n_dev % dcn_ways or not 1 < dcn_ways <= n_dev:
            raise SystemExit(
                f"--dcn-ways {dcn_ways} must divide --n-devices {n_dev} "
                "(outer slow-fabric groups x inner fast-fabric chips)"
            )
    sample_shape = tuple(train_iter.images.shape[1:])
    sample = jnp.zeros((1,) + sample_shape, jnp.float32)
    num_classes = _num_classes(args.dataset)
    _init_params = model_init_fn(model, sample)
    partition = _partition(args)
    zero1 = partition == "zero1" and n_dev > 1
    k_agg = 0
    if (
        args.num_aggregate is not None
        and n_dev > 1
        and 0 < args.num_aggregate < n_dev
    ):
        k_agg = args.num_aggregate
    # the candidate space must stay conflict-free by construction (the
    # enumerate_candidates contract): a hierarchical winner would be
    # rejected by the in-run densify matrix AFTER the whole probe ladder
    # ran, and would silently drop a requested --num-aggregate subset
    # (replica subsetting exists only in flat gather/ring) — narrow the
    # space up front, out loud, exactly like allow_overlap below
    if dcn_ways and args.on_diverge == "densify":
        print(
            f"{tag}: excluding hierarchical candidates (--on-diverge "
            "densify cannot compose with a two-level schedule — the "
            "dense fallback aggregates with a flat psum)",
            flush=True,
        )
        dcn_ways = 0
    if dcn_ways and k_agg:
        print(
            f"{tag}: excluding hierarchical candidates "
            "(--num-aggregate subsets replicas only in flat gather/ring)",
            flush=True,
        )
        dcn_ways = 0
    if dcn_ways and getattr(args, "elastic", False):
        print(
            f"{tag}: excluding hierarchical candidates (--elastic is "
            "flat-mesh only — membership tracks single replicas, not "
            "inner groups)",
            flush=True,
        )
        dcn_ways = 0
    if dcn_ways and getattr(args, "obs_quality", False):
        print(
            f"{tag}: excluding hierarchical candidates (--obs-quality "
            "probes flat exchanges only — the boundary re-encode is not "
            "probe-aware)",
            flush=True,
        )
        dcn_ways = 0
    # the +qK quorum variants: explored only when a chaos slow@ fault
    # actually straggles a replica of this mesh — priced by expected
    # exposed wait from the fault's per-replica delays (the probe
    # harness is straggler-free, so +qK is never probed; see tune())
    slow_faults = ()
    if args.chaos:
        from atomo_tpu.utils.chaos import ChaosConfig

        slow_faults = ChaosConfig.from_spec(args.chaos).slow_replica_faults
    allow_quorum = bool(slow_faults) and codec is not None and n_dev > 1
    quorum_q = 0
    quorum_delays = None
    if allow_quorum:
        per_rep = [0.0] * n_dev
        for _, r, sec in slow_faults:
            if r < n_dev:
                per_rep[r] = max(per_rep[r], float(sec))
        quorum_delays = per_rep
        slowed = len({r for _, r, _ in slow_faults if r < n_dev})
        # quorum = everyone who is NOT persistently slowed (floor 1):
        # the Q that absorbs exactly the injected stragglers
        quorum_q = max(1, n_dev - slowed)
    from atomo_tpu.fleet.control import current_roster_hash as _frh

    # stamped into every new decision artifact (and checked on resume):
    # the host roster the decision was produced under — device count and
    # mesh shape cannot tell two swapped hosts apart
    fleet_hash = _frh(args.train_dir)
    doc = None
    if args.resume:
        # a resumed run (including a supervised restart's appended
        # --resume) must NOT re-probe: probe timings vary run to run, and
        # a different winner would try to resume checkpoints written by a
        # different program family (e.g. delayed payload vs blocking).
        # The decision artifact IS the stable choice — reuse it, but ONLY
        # when it was tuned for THIS world size: after an elastic
        # shrink/grow the recorded winner (a ring plan sized for N, a
        # superstep point picked from N-way timings) may be invalid for
        # N-1 (decision_reusable), so a mismatch re-tunes out loud.
        import json as _json

        from atomo_tpu.tuning.autopilot import decision_reusable

        if is_ctl:
            # one resume source of truth: controller_decision.json,
            # with the STATED legacy fallback (load_resume_decision logs
            # it) so pre-controller train_dirs keep resuming
            from atomo_tpu.controller import (
                controller_path,
                controller_reusable,
                load_resume_decision,
            )

            prior, source = load_resume_decision(args.train_dir)
            path = (
                controller_path(args.train_dir)
                if source == "controller"
                else decision_path(args.train_dir)
            )
            check = (
                controller_reusable
                if source == "controller"
                else decision_reusable
            )
        else:
            path = decision_path(args.train_dir)
            try:
                with open(path) as f:
                    prior = _json.load(f)
            except (OSError, ValueError):
                prior = None
            check = decision_reusable
        from atomo_tpu.fleet.control import current_roster_hash
        from atomo_tpu.mesh import MeshSpec

        reusable, why = check(
            prior, n_dev=n_dev,
            mesh_axes=MeshSpec.from_world(n_dev, dcn_ways).shape_dict(),
            # the chaos-derived Q this run would explore (staleness=None:
            # K was the recorded ladder's pick, any value is consistent)
            quorum=quorum_q if allow_quorum else None,
            # the host-roster fingerprint: a replaced/swapped host keeps
            # n_devices AND mesh_axes identical — only the fleet record
            # (hosts/ leases, host-granularity membership epochs) sees it
            fleet_roster=current_roster_hash(args.train_dir),
        )
        if reusable:
            doc = prior
            print(
                f"{tag}: resuming with the recorded decision from "
                f"{path} (no re-probe; delete the file to re-tune)",
                flush=True,
            )
        elif prior is not None:
            print(f"{tag}: NOT reusing {path}: {why}", flush=True)
            if args.train_dir:
                from atomo_tpu.utils.tracing import IncidentLog

                IncidentLog.for_train_dir(args.train_dir).append(
                    "controller_decision" if is_ctl else "tune_decision",
                    action="retune",
                    reason=why,
                    n_devices=n_dev,
                )
    # delayed is excluded from the candidate space whenever a later stage
    # could not accept it: densify's dense fallback has no delayed form,
    # a zero1 run cannot resume the in-flight payload (PR-5 matrix), an
    # elastic shrink restart cannot resume the world-size-shaped carry,
    # and the --obs-quality probes reject the stale-by-one payload
    allow_overlap = (
        codec is not None and n_dev > 1
        and args.on_diverge != "densify" and not zero1
        and not getattr(args, "elastic", False)
        and not getattr(args, "obs_quality", False)
    )
    compute_dtype = jnp.bfloat16 if args.bf16 else None
    _ef = bool(getattr(args, "error_feedback", False))
    try:
        if doc is None and is_ctl:
            # the JOINT solve: the legacy deciders composed as
            # subroutines of one predict_step_s-ranked enumeration; the
            # shared knobs below are the SAME values the tune() branch
            # passes, so restricting the controller to one decider's
            # axes reproduces that decider's winner (degeneracy tests)
            from atomo_tpu.controller import (
                controller_path,
                solve_controller,
            )

            doc = solve_controller(
                model=model, optimizer=optimizer, codec=codec,
                model_init_fn=_init_params, n_dev=n_dev,
                sample_shape=sample_shape, num_classes=num_classes,
                batch=args.batch_size, fabric=args.fabric,
                seed=args.seed,
                artifact_path=controller_path(args.train_dir),
                budget_ctx=budget_ctx if n_dev > 1 else None,
                hybrid=(
                    sparse_plan
                    if getattr(args, "sparse_rows", "off") == "auto"
                    else None
                ),
                hybrid_inputs=hybrid_inputs,
                allow_psum=args.num_aggregate is None,
                allow_overlap=allow_overlap,
                allow_stream=codec is not None and n_dev > 1,
                stream_bucket_bytes=_stream_bucket_bytes(args),
                stream_buckets=_real_stream_buckets(
                    _init_params, _stream_bucket_bytes(args)
                ),
                allow_quorum=allow_quorum,
                quorum_q=quorum_q,
                quorum_delays=quorum_delays,
                superstep_options=(1, 8),
                bucket_options=(
                    (args.ring_bucket_size,)
                    if args.ring_bucket_size != 65536 else (65536, 0)
                ),
                dcn_ways=dcn_ways,
                probe_top=args.tune_top, probe_steps=args.tune_steps,
                probe_reps=args.tune_reps,
                num_aggregate=k_agg, zero1=zero1, partition=partition,
                grad_accum=args.grad_accum,
                compute_dtype=compute_dtype,
                codec_tax_s=(
                    None if args.codec_tax_ms is None
                    else args.codec_tax_ms / 1e3
                ),
                ring_bucket_size=args.ring_bucket_size,
                fabric_probe=getattr(args, "_fabric_probe", None),
                error_feedback=_ef,
                context={
                    "network": args.network, "dataset": args.dataset,
                    "code": args.code, "seed": args.seed,
                    **(
                        {"fleet_roster_hash": fleet_hash}
                        if fleet_hash else {}
                    ),
                },
            )
        doc = doc if doc is not None else tune(
            model=model, optimizer=optimizer, codec=codec,
            model_init_fn=_init_params, n_dev=n_dev,
            sample_shape=sample_shape, num_classes=num_classes,
            batch=args.batch_size, fabric=args.fabric, seed=args.seed,
            artifact_path=decision_path(args.train_dir),
            allow_psum=args.num_aggregate is None,
            allow_overlap=allow_overlap,
            # stream-encode candidates are trajectory-neutral layout/
            # schedule points (bit-identical payloads), so they are safe
            # for every compressed flat-exchange deployment; the REAL
            # plan's bucket count (from the gradient tree's shapes, free
            # via eval_shape) prices their encode tail — the byte-ratio
            # estimate overstates granularity when one leaf exceeds the
            # bound (an LM embedding)
            allow_stream=codec is not None and n_dev > 1,
            # the +sp hybrid variants: explored only under --sparse-rows
            # auto with a plan that actually sparse-assigns something
            # (preflight rejected the pinned "on" and the dense-code
            # case); priced from the plan's per-leaf wire bytes and
            # probed with the plan attached to the real step builder
            allow_sparse=(
                sparse_plan is not None
                and getattr(args, "sparse_rows", "off") == "auto"
            ),
            hybrid=sparse_plan,
            # the +ab adaptive-budget variants: explored when
            # --budget-alloc variance armed an allocation — priced from
            # its clamped per-leaf pairs and probed with the wrapped
            # codec swapped into the real step builder; the measured
            # winner's budget_alloc knob decides (applied below)
            allow_budget=budget_ctx is not None and n_dev > 1,
            budget_leaf_budgets=(
                budget_ctx["leaf_budgets"] if budget_ctx else None
            ),
            budget_codec=budget_ctx["codec"] if budget_ctx else None,
            # the +qK bounded-staleness variants (priced, never probed)
            allow_quorum=allow_quorum,
            quorum_q=quorum_q,
            quorum_delays=quorum_delays,
            stream_bucket_bytes=_stream_bucket_bytes(args),
            stream_buckets=_real_stream_buckets(
                _init_params, _stream_bucket_bytes(args)
            ),
            superstep_options=(1, 8),
            # an explicit --ring-bucket-size pins the ring candidates'
            # packing (any value is bit-identical — layout only); the
            # default explores the two packings that differ in dispatch
            # granularity (default buckets vs one unpadded bucket/dtype)
            bucket_options=(
                (args.ring_bucket_size,)
                if args.ring_bucket_size != 65536 else (65536, 0)
            ),
            dcn_ways=dcn_ways,
            probe_top=args.tune_top, probe_steps=args.tune_steps,
            probe_reps=args.tune_reps,
            num_aggregate=k_agg, zero1=zero1, partition=partition,
            grad_accum=args.grad_accum,
            compute_dtype=compute_dtype,
            codec_tax_s=(
                None if args.codec_tax_ms is None
                else args.codec_tax_ms / 1e3
            ),
            # hierarchical candidates carry no per-candidate bucket knob;
            # their ring tiers must be probed at the value the run will
            # execute with (bit-identical layout knob, but the measured
            # ms/step must describe the dispatched packing)
            ring_bucket_size=args.ring_bucket_size,
            # --fabric measured: the startup probe's document — every
            # candidate priced from the measured mesh, and the decision
            # meta records the per-tier GB/s for the report's
            # cross-artifact check
            fabric_probe=getattr(args, "_fabric_probe", None),
            # --error-feedback narrows the space inside tune() (EF
            # conflict matrix) and marks every probed row's comparison
            # basis — probed as a candidate, not rejected up front
            error_feedback=_ef,
            context={
                "network": args.network, "dataset": args.dataset,
                "code": args.code, "seed": args.seed,
                **(
                    {"fleet_roster_hash": fleet_hash}
                    if fleet_hash else {}
                ),
            },
        )
    except ValueError as exc:  # unresolvable --fabric
        raise SystemExit(str(exc)) from None
    win = doc.get("winner") or {}
    knobs = win.get("knobs") or {}
    if not knobs:
        if is_ctl:
            from atomo_tpu.controller import controller_path as _cpath

            art = _cpath(args.train_dir)
        else:
            art = decision_path(args.train_dir)
        raise SystemExit(
            f"--auto {args.auto} produced no viable candidate (see "
            f"{art})"
        )
    if n_dev > 1:
        args.aggregate = knobs.get("aggregate", "gather")
    args.overlap = knobs.get("overlap", "off")
    args.stream_encode = knobs.get("stream_encode", "off")
    if "stream_bucket_bytes" in knobs:
        # the run must execute the bucket plan the winner was PROBED with
        # (today the candidates carry _stream_bucket_bytes(args) back, so
        # this is an identity — but a replayed decision artifact or a
        # future multi-size candidate sweep must not silently diverge)
        args.stream_bucket_mb = float(knobs["stream_bucket_bytes"]) / (1 << 20)
    if knobs.get("plan"):
        # a hierarchical winner carries its topology plan; cmd_train's
        # hierarchical block executes it (highest plan precedence)
        args._tuned_plan = knobs["plan"]
    args.ring_bucket_size = int(
        knobs.get("ring_bucket_size", args.ring_bucket_size)
    )
    # a +sp winner pins the hybrid plan on; cmd_train applies it
    args._tuned_sparse = knobs.get("sparse_rows", "off")
    # a +ab winner pins the adaptive allocation on; cmd_train applies it
    args._tuned_budget = knobs.get("budget_alloc", "off")
    if knobs.get("quorum"):
        # a +qK winner arms the quorum exactly like an explicit flag;
        # cmd_train builds the QuorumConfig from args after this returns
        args.quorum = str(int(knobs["quorum"]))
        args.staleness = int(knobs.get("staleness", 1))
    superstep = max(int(knobs.get("superstep", 1)), 1)
    print(
        f"--auto {args.auto} -> {win.get('name')} ({doc.get('why')})",
        flush=True,
    )
    # a joint +sp+ab winner executes the hybrid plan RE-PLANNED under
    # the budget-wrapped codec (the crossover moves when per-leaf wire
    # bytes move) — the same deterministic plan_hybrid the controller
    # priced; cmd_train applies it via _tuned_hybrid_ab
    run_hybrid = sparse_plan
    if (
        is_ctl and budget_ctx is not None and hybrid_inputs
        and knobs.get("sparse_rows") == "on"
        and knobs.get("budget_alloc") == "variance"
    ):
        from atomo_tpu.sparse.hybrid import plan_hybrid

        run_hybrid = plan_hybrid(
            budget_ctx["codec"],
            hybrid_inputs["grads_like"],
            hybrid_inputs["densities"],
            hybrid_inputs["row_bounds"],
        )
        args._tuned_hybrid_ab = run_hybrid

    # online re-tune (rung 0.5): needs a checkpoint cadence to snap the
    # re-probe to. The re-pickable knob is the gather<->ring pair (the
    # bit-identical aggregation operators); every other deployment stays
    # observe-only — drift is still detected and logged.
    if not (save_freq and args.train_dir):
        return superstep, None
    probe_fn = None
    if (
        n_dev > 1 and codec is not None
        and args.aggregate in ("gather", "ring")
    ):
        base = dict(knobs)
        # a +ab winner's gather<->ring re-probe must time the wrapped-
        # codec program the run actually dispatches
        run_codec = (
            budget_ctx["codec"]
            if budget_ctx is not None
            and knobs.get("budget_alloc") == "variance"
            else codec
        )

        def probe_fn(mode, _base=base, _codec=run_codec):
            from atomo_tpu.utils.comm_model import candidate_name

            cand = {**_base, "aggregate": mode}
            cand["name"] = candidate_name(cand)
            row = probe_candidate(
                cand, model=model, optimizer=optimizer, codec=_codec,
                n_dev=n_dev, sample_shape=sample_shape,
                num_classes=num_classes,
                batch=probe_batch_size(args.batch_size, n_dev),
                seed=args.seed, steps=args.tune_steps, reps=1,
                num_aggregate=k_agg, zero1=zero1,
                grad_accum=args.grad_accum, compute_dtype=compute_dtype,
                ring_bucket_size=args.ring_bucket_size,
                # a +sp winner's gather<->ring re-probe must time the
                # hybrid program the run actually dispatches (the
                # +sp+ab re-planned one under the controller)
                hybrid=run_hybrid,
                error_feedback=_ef,
            )
            return row["measured_ms_per_step"]

    # drift blame (the fabric observatory): armed when this run measured
    # its fabric at startup — the startup probe is the baseline, the
    # cheap re-probe runs at the alarm, and a fabric verdict re-writes
    # fabric_probe.json so later pricing (and a resume) reads the fabric
    # that exists NOW, not the one that existed at launch
    fabric_kw = {}
    probe_doc = getattr(args, "_fabric_probe", None)
    if probe_doc is not None and n_dev > 1:
        from atomo_tpu.obs.fabric import (
            measured_bandwidths,
            quick_probe,
            write_fabric_probe,
        )

        probe_k = int((probe_doc.get("meta") or {}).get("dcn_ways") or 0)

        def fabric_probe_fn(_n=n_dev, _k=probe_k):
            return quick_probe(n_dev=_n, dcn_ways=_k)

        def on_fabric_moved(doc, _dir=args.train_dir):
            path = write_fabric_probe(_dir, doc)
            print(
                f"{tag}: fabric moved — {path} re-written from the "
                "re-probe (meta.reps says it was the quick ladder)",
                flush=True,
            )

        fabric_kw = dict(
            fabric_probe_fn=fabric_probe_fn,
            fabric_baseline=measured_bandwidths(probe_doc),
            on_fabric_moved=on_fabric_moved,
        )
    inner = OnlineRetuner(probe_fn=probe_fn, **fabric_kw)
    if is_ctl:
        # one re-solve loop: the drift retuner (and, when cmd_train arms
        # it, the budget retuner) composed behind one object — every
        # applied change is one controller_redecide incident quoting the
        # old/new knob vector (the ISSUE-17 online half)
        from atomo_tpu.controller import ControllerRetuner

        return superstep, ControllerRetuner(
            tuner=inner, knobs=dict(knobs)
        )
    return superstep, inner


def _recorder_tier_ms(args, n_dev, model, train_iter, codec):
    """{tier label: predicted comm ms} for the flight recorder's
    per-tier calibration column (obs.fabric.predicted_tier_ms): the
    autopilot winner's predicted step decomposed over the fabric tiers
    its exchange crosses — one tier for the flat aggregates, both for a
    hierarchical winner. Returns None when the context cannot be priced
    (single device, unresolved aggregate) — the column is then absent,
    never invented."""
    import jax
    import jax.numpy as jnp

    from atomo_tpu.obs.fabric import (
        measured_bandwidths,
        predicted_tier_ms,
    )
    from atomo_tpu.tuning.probe import byte_budget, model_init_fn
    from atomo_tpu.utils.comm_model import FABRICS, resolve_fabric

    if n_dev <= 1:
        return None
    agg = args.aggregate
    if agg not in ("gather", "ring", "psum", "hierarchical"):
        return None
    probe_doc = getattr(args, "_fabric_probe", None)
    sample = jnp.zeros(
        (1,) + tuple(train_iter.images.shape[1:]), jnp.float32
    )
    dense_b, payload_b = byte_budget(codec, model_init_fn(model, sample))
    n_proc = jax.process_count()
    if agg == "hierarchical":
        from atomo_tpu.topology.fabric import resolve_two_tier

        k = args.dcn_ways or max(n_proc, 2)
        if not (1 < k <= n_dev) or n_dev % k:
            return None
        fabric2 = resolve_two_tier(
            args.fabric, dcn_ways=k, n_dev=n_dev, n_proc=n_proc,
            measured=probe_doc,
        )
        plan_name = (
            getattr(args, "_tuned_plan", None)
            or (args.plan if args.plan != "auto" else None)
            or getattr(args, "_auto_plan", None)
            or "legacy"
        )
        return predicted_tier_ms(
            aggregate=agg, dense_bytes=dense_b, payload_bytes=payload_b,
            ways=n_dev, fabric2=fabric2, plan_name=plan_name,
        )
    fabric_tok = args.fabric
    try:
        bw = resolve_fabric(fabric_tok, n_proc=n_proc, measured=probe_doc)
    except ValueError:
        # a two-tier <inner>:<outer> string with a FLAT winner: the flat
        # exchange crosses the slow tier end to end, so price at the
        # OUTER token — the same fallback tune() applied when it priced
        # this very winner (the column must mirror the pricing path)
        if ":" not in fabric_tok:
            raise
        fabric_tok = fabric_tok.rpartition(":")[2]
        bw = resolve_fabric(fabric_tok, n_proc=n_proc, measured=probe_doc)
    if fabric_tok == "measured" and probe_doc is not None:
        bws = measured_bandwidths(probe_doc)
        label = "measured_" + min(bws, key=bws.get)
    elif fabric_tok == "auto":
        label = "dcn" if n_proc > 1 else "ici"
    elif fabric_tok in FABRICS:
        label = fabric_tok
    else:
        label = "fabric"
    return predicted_tier_ms(
        aggregate=agg, dense_bytes=dense_b, payload_bytes=payload_b,
        ways=n_dev, fabric_bw=bw, fabric_label=label,
    )


def cmd_train(args: argparse.Namespace) -> int:
    import os

    import jax
    import jax.numpy as jnp

    from atomo_tpu.parallel import launch
    from atomo_tpu.training.resilience import (
        SUPERVISED_ENV,
        DivergenceError,
        run_supervised,
    )

    _argv_preflight(args)

    if args.max_restarts > 0 and os.environ.get(SUPERVISED_ENV) != "1":
        # run-level supervision: re-exec this exact command as a child
        # under the crash-loop budget; the child sees SUPERVISED_ENV and
        # trains directly. Restarts get --resume appended.
        argv = getattr(args, "_argv", None)
        if argv is None:
            warnings.warn(
                "--max-restarts needs the CLI entrypoint's argv to re-exec "
                "itself; running unsupervised (call atomo_tpu.cli.main, or "
                "use scripts/supervise.py around your own command)"
            )
        else:
            if not args.train_dir:
                # legitimate (fresh restarts are the only supervised mode
                # for zero1+delayed) but easy to hit by accident
                warnings.warn(
                    "--max-restarts with --train-dir '': checkpointing is "
                    "off, so every restart retrains from step 0 and no "
                    "incidents.jsonl is written"
                )
            return run_supervised(
                [sys.executable, "-m", "atomo_tpu.cli"] + list(argv),
                max_restarts=args.max_restarts,
                backoff_base=args.restart_backoff,
                backoff_max=args.restart_backoff * 30,
                train_dir=args.train_dir,
                # no checkpoint dir -> nothing to resume: appending
                # --resume would deterministically kill every restart of
                # the zero1+delayed fresh-restart mode (the loop rejects
                # resuming the payload-less template) — mirror
                # scripts/supervise.py's guard
                resume_flag="--resume" if args.train_dir else None,
            )

    _warn_dead_flags(args)
    if args.bf16:
        # an unverified record from before this round (one v5e chip, never
        # reproduced on the stock TPU backend) had bf16 run the CIFAR CNN
        # ladder SLOWER than f32 (7.78-7.91 vs 6.50 ms/step on ResNet-18);
        # the cause was never found (ROADMAP S10). Warn rather than refuse:
        # the mode is correct, and matmul-dominated models (the lm
        # subcommand) are where it is expected to pay.
        warnings.warn(
            "--bf16 ran slower than f32 for the CIFAR-class CNN recipes in "
            "an unverified v5e record from before this round (7.8 vs 6.5 "
            "ms/step; not re-measured); it is expected to pay on "
            "matmul-dominated models (lm). Proceeding."
        )
    # Multi-host: form ONE jax.distributed world before any mesh/backend use
    # (replaces the reference's mpirun rank dispatch,
    # src/distributed_nn.py:86-88,243-259). No-op on a single host.
    launch.initialize()
    n_proc = jax.process_count()
    if n_proc > 1:
        if args.batch_size % n_proc:
            raise SystemExit(
                f"--batch-size {args.batch_size} must be divisible by the "
                f"{n_proc} participating hosts"
            )
        # each host feeds its local slice of the global batch, shuffled with
        # an independent DATA stream (the reference's workers also shuffle
        # independently, src/distributed_nn.py:93-207). Only the data seed
        # is offset: model init and the step key must stay identical across
        # processes or the "replicated" state would silently diverge.
        args.batch_size //= n_proc
        args.data_seed = args.seed + jax.process_index()
    model, optimizer, codec, train_iter, test_iter, ds_name = _build_common(args)
    augment = ds_name.startswith("cifar") and not args.no_augment
    n_train = len(train_iter.dataset)
    steps_per_epoch = max(n_train // args.batch_size, 1)
    max_steps = min(args.max_steps, args.epochs * steps_per_epoch)
    save_freq = args.save_freq or args.eval_freq

    guard = None
    if args.grad_guard or args.max_grad_norm > 0:
        from atomo_tpu.training.resilience import GuardConfig

        guard = GuardConfig(max_grad_norm=args.max_grad_norm)
    chaos = None
    if args.chaos:
        from atomo_tpu.utils.chaos import ChaosConfig, ChaosInjector

        chaos = ChaosInjector(ChaosConfig.from_spec(args.chaos))
    # (no --chaos: the train loops read ATOMO_CHAOS from the env)

    superstep = args.superstep  # < 0 already rejected by _argv_preflight
    if superstep == 0:
        # backend default: per-dispatch host cost is what superstepping
        # buys back. K=8 on TPU is a choice from before this round, not a
        # measurement on the stock backend (ROADMAP S7); the CPU default
        # stays K=1, the per-step loop exactly as before
        superstep = 8 if jax.default_backend() == "tpu" else 1
    n_dev = args.n_devices or len(jax.devices())
    if (
        chaos is not None and chaos.config.die_faults
        and not chaos.membership_epoch  # disarmed past a reshape
    ):
        # the argv-ambiguous half of the preflight range check
        # (--n-devices 0 = all visible needs the resolved count)
        bad = [r for _, r in chaos.config.die_faults if r >= n_dev]
        if bad or n_dev <= 1:
            raise SystemExit(
                f"chaos die@S:R targets replica(s) "
                f"{sorted(r for _, r in chaos.config.die_faults)} but this "
                f"run resolved to a {n_dev}-device mesh (replicas are "
                "0-based); the fault would never fire"
            )
    if (
        chaos is not None and chaos.config.slow_replica_faults
        and not chaos.membership_epoch
    ):
        # the argv-ambiguous half of the slow@ preflight range check
        # (--n-devices 0 = all visible needs the resolved count)
        bad = [
            r for _, r, _ in chaos.config.slow_replica_faults if r >= n_dev
        ]
        if bad or n_dev <= 1:
            raise SystemExit(
                f"chaos slow@S:R:SEC targets replica(s) "
                f"{sorted(r for _, r, _ in chaos.config.slow_replica_faults)} "
                f"but this run resolved to a {n_dev}-device mesh (replicas "
                "are 0-based); the fault would never fire"
            )
    if _quorum_q(args) is not None:
        # the argv-ambiguous half of the quorum preflight mesh checks
        if n_dev <= 1:
            raise SystemExit(
                "--quorum waits for Q of N replica payloads: this run "
                "resolved to 1 device, so there is no exchange to quorum on"
            )
        if _quorum_q(args) > n_dev:
            raise SystemExit(
                f"--quorum {_quorum_q(args)} exceeds the resolved "
                f"{n_dev}-replica mesh: a quorum larger than the world "
                "can never be met"
            )
    if args.fabric == "measured":
        # the startup fabric probe (obs.fabric): measure per-tier
        # bandwidth/latency on the real mesh BEFORE anything prices a
        # prediction from the fabric (the hybrid planner's crossover,
        # --aggregate auto, the autopilot ladder). The probe draws its
        # buffers from jnp constants — never the data iterator or the
        # init seed — so the trajectory is bit-identical to a pinned
        # fabric with the same resolved knobs (the PR-6 probe-isolation
        # precedent, drilled by tests/test_fabric_obs.py).
        from atomo_tpu.obs.fabric import ensure_fabric_probe

        if n_dev <= 1:
            # the argv-ambiguous half (--n-devices 0 on a 1-device host)
            raise SystemExit(
                "--fabric measured needs a multi-device mesh: this host "
                "resolved to 1 device, so there is no inter-chip fabric "
                "to measure"
            )
        try:
            args._fabric_probe = ensure_fabric_probe(
                args.train_dir,
                n_dev=n_dev,
                dcn_ways=getattr(args, "dcn_ways", 0),
                reuse=args.resume,
            )
        except ValueError as exc:
            raise SystemExit(str(exc)) from None
    sparse_plan = None
    hybrid_inputs = None  # plan_hybrid's argument triple (controller +sp+ab)
    if args.sparse_rows != "off":
        if n_dev <= 1:
            # the argv-ambiguous half of the preflight mesh check
            if args.sparse_rows == "on":
                raise SystemExit(
                    "--sparse-rows needs a multi-device mesh: this host "
                    "resolved to 1 device, so there is no exchange to "
                    "save wire on"
                )
            print(
                "--sparse-rows auto: single device, no exchange — "
                "running dense",
                flush=True,
            )
        elif train_iter.images.ndim != 2:
            msg = (
                "--sparse-rows: this workload's batches are not row-id "
                "shaped, so no leaf has a provable per-step row bound "
                "(row-id workloads: --dataset zipf --network embedding)"
            )
            if args.sparse_rows == "on":
                raise SystemExit(msg + "; drop --sparse-rows")
            print(msg + " — running all-dense", flush=True)
        else:
            # plan from a probe gradient over a DIRECT slice of the
            # training arrays (never epoch(): pulling a batch would
            # advance the shuffle RNG — the --aggregate auto precedent)
            from atomo_tpu.codecs import DenseCodec
            from atomo_tpu.sparse.hybrid import (
                infer_row_bounds,
                measured_densities,
                plan_hybrid,
                probe_gradient,
            )

            plan_codec = codec if codec is not None else DenseCodec()
            probe_n = min(max(args.batch_size, 8), len(train_iter.images))
            # plan_for_model's composition, inlined so the measured
            # triple survives: the controller re-plans the crossover
            # under the budget-wrapped codec (+sp+ab) from the SAME
            # probe inputs — deterministic, one probe gradient
            _grads = probe_gradient(
                model,
                train_iter.images[:probe_n], train_iter.labels[:probe_n],
            )
            _densities = measured_densities(_grads)
            _row_bounds = infer_row_bounds(
                _grads, max(args.batch_size // n_dev, 1),
                int(train_iter.images.shape[1]),
            )
            plan = plan_hybrid(plan_codec, _grads, _densities, _row_bounds)
            if args.auto == "controller":
                hybrid_inputs = {
                    "grads_like": _grads,
                    "densities": _densities,
                    "row_bounds": _row_bounds,
                }
            if plan.any_sparse:
                sparse_plan = plan
                print(plan.describe(), flush=True)
                for a in plan.assignments:
                    print(f"  [{a.index}] {a.name}: {a.reason}", flush=True)
            elif args.sparse_rows == "on":
                for a in plan.assignments:
                    print(f"  [{a.index}] {a.name}: {a.reason}", flush=True)
                raise SystemExit(
                    "--sparse-rows on: the hybrid planner assigned no "
                    "leaf sparse for this model/codec/batch (per-leaf "
                    "reasons above); drop --sparse-rows or shrink the "
                    "dense path's payload"
                )
            else:
                print(
                    "--sparse-rows auto: the planner assigned no leaf "
                    "sparse — running all-dense",
                    flush=True,
                )
    budget_ctx = None  # --budget-alloc variance: allocation + wrapped codec
    if args.budget_alloc == "variance":
        from atomo_tpu.budget import (
            Allocation,
            alloc_reusable,
            allocation_leaf_budgets,
            budgeted_codec,
            latest_epoch,
            measure_spectra,
            new_alloc_doc,
            read_alloc,
            solve_allocation,
            write_alloc,
        )
        from atomo_tpu.sparse.hybrid import probe_gradient

        # spectra from a probe gradient over a DIRECT slice of the
        # training arrays (never epoch(): pulling a batch would advance
        # the shuffle RNG — the sparse-rows/--aggregate auto precedent)
        probe_n = min(max(args.batch_size, 8), len(train_iter.images))
        spectra = measure_spectra(
            codec,
            probe_gradient(
                model, train_iter.images[:probe_n],
                train_iter.labels[:probe_n],
            ),
        )
        budget_b = int(args.budget_bytes) if args.budget_bytes > 0 else None
        alloc = None
        doc = None
        if args.resume and args.train_dir:
            # the determinism contract: a resume replays bit-exact from
            # the RECORDED allocation artifact — never a fresh probe
            # solve (the tune_decision.json reuse precedent)
            prior = read_alloc(args.train_dir)
            ok_reuse, why = alloc_reusable(
                prior, codec_name=codec.name, n_leaves=len(spectra)
            )
            if ok_reuse:
                ep = latest_epoch(prior)
                alloc = Allocation(
                    mode=str(ep.get("mode", "variance")),
                    ks=tuple(int(k) for k in ep["ks"]),
                    payload_bytes=int(ep["payload_bytes"]),
                    budget_bytes=int(
                        ep.get("budget_bytes", prior["budget_bytes"])
                    ),
                    predicted_variance=float(
                        ep.get("predicted_variance", 0.0)
                    ),
                    epoch=int(ep["epoch"]),
                )
                doc = prior
                print(f"Budget: {why} (budget_alloc.json)", flush=True)
            elif prior is not None:
                print(f"Budget: NOT reusing budget_alloc.json: {why}",
                      flush=True)
        if alloc is None:
            alloc = solve_allocation(
                codec, spectra, budget_bytes=budget_b, mode="variance"
            )
            doc = new_alloc_doc(codec, spectra, alloc)
            if args.train_dir:
                path = write_alloc(args.train_dir, doc)
                print(f"Budget: allocation artifact -> {path}", flush=True)
        wrapped = budgeted_codec(codec, alloc.ks)
        print(alloc.describe(), flush=True)
        for l in spectra:
            print(
                f"  [{l.index}] {l.name}: k={alloc.ks[l.index]}"
                + ("" if l.adaptive else " (dense at any rank — fixed)"),
                flush=True,
            )
        budget_ctx = {
            "base_codec": codec,
            "codec": wrapped,
            "spectra": spectra,
            "alloc": alloc,
            "doc": doc,
            "leaf_budgets": allocation_leaf_budgets(
                codec, spectra, alloc.ks
            ),
        }
        if args.auto not in ("tune", "controller"):
            # pinned variance mode: the wrapped codec IS the run's codec
            # (under --auto tune/controller the +ab candidates compete
            # and the measured winner decides below)
            codec = wrapped
    tuner = None
    if args.auto in ("tune", "controller"):
        superstep, tuner = _run_autopilot(args, model, optimizer, codec,
                                          train_iter, n_dev, save_freq,
                                          sparse_plan=sparse_plan,
                                          budget_ctx=budget_ctx,
                                          hybrid_inputs=hybrid_inputs)
        if budget_ctx is not None:
            if getattr(args, "_tuned_budget", "off") == "variance":
                codec = budget_ctx["codec"]
                print(
                    "Budget: +ab winner — training with the adaptive "
                    "allocation",
                    flush=True,
                )
            else:
                budget_ctx = None  # measured loser: uniform stays, out loud
                print(
                    "Budget: the measured ladder kept the uniform "
                    "allocation (+ab lost or was not probed); "
                    "--budget-alloc variance stands down",
                    flush=True,
                )
    hybrid_plan = None
    if sparse_plan is not None:
        if args.auto in ("tune", "controller"):
            # the +sp candidates competed in the probe ladder; the
            # winner's knob decides (measured, not assumed). A joint
            # +sp+ab winner executes the crossover re-planned under the
            # budget-wrapped codec (_run_autopilot recorded it)
            if getattr(args, "_tuned_sparse", "off") == "on":
                hybrid_plan = (
                    getattr(args, "_tuned_hybrid_ab", None) or sparse_plan
                )
        else:
            hybrid_plan = sparse_plan
        if hybrid_plan is not None and codec is None:
            # --code sgd: the dense-assigned leaves ride the payload
            # gather/ring as uncompressed DenseCodec payloads (the
            # hybrid's "existing dense exchange"), priced honestly
            from atomo_tpu.codecs import DenseCodec

            codec = DenseCodec()
    diverge = None
    if args.on_diverge != "off":
        from atomo_tpu.training.resilience import (
            DetectorConfig,
            DivergeConfig,
            diverge_conflict,
        )

        # multi-device-only features are "off" for the single-device loop
        reason = diverge_conflict(
            args.on_diverge,
            train_dir=args.train_dir,
            codec=codec,
            aggregate=args.aggregate if n_dev > 1 else None,
            overlap=args.overlap,
            zero1=_partition(args) == "zero1" and n_dev > 1,
            num_aggregate=args.num_aggregate if n_dev > 1 else None,
            keep_ckpts=args.keep_ckpts,
            save_freq=save_freq,
            window=args.diverge_window,
        )
        if reason:
            raise SystemExit(reason)
        diverge = DivergeConfig(
            remedy=args.on_diverge,
            detector=DetectorConfig(
                window=args.diverge_window,
                zmax=args.diverge_zmax,
                patience=args.diverge_patience,
                min_history=args.diverge_min_history,
            ),
            max_rollbacks=args.max_rollbacks,
        )
    if args.overlap == "delayed" and n_dev <= 1:
        # the argv-knowable delayed-mode conflicts were rejected by
        # _argv_preflight; this one needs the resolved device count
        # (--n-devices 0 = all visible)
        raise SystemExit(
            "--overlap delayed needs a multi-device mesh: single-device "
            "training has no exchange to take off the critical path"
        )
    if args.stream_encode == "on" and n_dev <= 1:
        # same resolved-count half of the preflight check as delayed's
        raise SystemExit(
            "--stream-encode needs a multi-device mesh: single-device "
            "training has no exchange whose encode is on the critical path"
        )
    if args.error_feedback and n_dev <= 1:
        # same resolved-count half of the preflight check
        raise SystemExit(
            "--error-feedback needs a multi-device mesh: this host "
            "resolved to 1 device, so there is no exchanged estimator "
            "whose error the residual would compensate"
        )
    elastic_cfg = None
    if args.elastic:
        if n_dev <= 1:
            # the argv-ambiguous case (--n-devices 0 on a 1-device host)
            raise SystemExit(
                "--elastic needs a multi-device mesh: this host resolved "
                "to 1 device, so there is no surviving roster to shrink to"
            )
        from atomo_tpu.elastic import ElasticConfig

        elastic_cfg = ElasticConfig(
            patience=args.elastic_patience,
            readmit_at=args.readmit_at,
            reshard=getattr(args, "elastic_reshard", "live"),
        )
    quorum_cfg = None
    if _quorum_q(args) is not None:
        # built AFTER the autopilot block so a tuned +qK winner's knobs
        # (applied onto args) arm the quorum exactly like an explicit flag
        from atomo_tpu.quorum import QuorumConfig

        if superstep > 1:
            # argv superstep>1 was rejected by _argv_preflight; this is
            # the backend default (8 on tpu) resolving over an armed
            # quorum — arrivals change per step, so steps cannot fuse
            print(
                "Quorum: per-step arrival consumption cannot run under a "
                "fused superstep scan; forcing --superstep 1",
                flush=True,
            )
            superstep = 1
        quorum_cfg = QuorumConfig(
            _quorum_q(args),
            staleness=args.staleness,
            period_s=args.quorum_period_ms / 1e3,
        )
    recorder = None
    if args.obs_record:
        from atomo_tpu.obs.recorder import (
            FlightRecorder,
            resolve_predicted_ms,
        )

        # built AFTER the autopilot so the calibration column can anchor
        # on the winner's predicted ms/step (tune_decision.json). Gated
        # on THIS run having tuned (--auto tune — a fresh probe, or a
        # decision_reusable-vetted resume): a stale decision file left in
        # the dir by some earlier differently-configured run must not
        # fabricate a calibration series for a program it never priced
        pred_ms = (
            resolve_predicted_ms(args.train_dir)
            if args.auto in ("tune", "controller")
            else None
        )
        tier_ms = None
        if pred_ms is not None:
            # the per-tier calibration column's reference: the winner's
            # predicted comm decomposed over the fabric tiers it crosses.
            # Best-effort observability — an unpriceable context drops
            # the column, never the run
            try:
                tier_ms = _recorder_tier_ms(args, n_dev, model, train_iter,
                                            codec)
            except Exception as exc:  # noqa: BLE001
                warnings.warn(
                    f"per-tier calibration column disabled ({exc})"
                )
        recorder = FlightRecorder.for_train_dir(
            args.train_dir,
            predicted_ms=pred_ms,
            predicted_tier_ms=tier_ms,
        )
    budget_tuner = None
    if budget_ctx is not None:
        from atomo_tpu.budget import allocation_meta, latest_epoch

        if recorder is not None:
            # the per-layer budget columns in metrics.jsonl: one meta
            # line per allocation epoch + the budget_epoch context
            # column on every step record (report's
            # budget_alloc_consistent check audits both against
            # budget_alloc.json)
            ep = latest_epoch(budget_ctx["doc"])
            recorder.write_meta(allocation_meta(ep))
            recorder.set_context(budget_epoch=int(ep["epoch"]))
        if (
            n_dev > 1
            and args.obs_quality and args.obs_record
            and recorder is not None
            and args.train_dir and save_freq
            and args.on_diverge == "off"
        ):
            # online re-allocation: armed only when its signal (the
            # recorded q_err2 series) actually lands on disk — a
            # frozen allocation otherwise, said here
            from atomo_tpu.budget import BudgetRetuner

            budget_tuner = BudgetRetuner(
                train_dir=args.train_dir,
                base_codec=budget_ctx["base_codec"],
                spectra=budget_ctx["spectra"],
                alloc=budget_ctx["alloc"],
                doc=budget_ctx["doc"],
            )
            print(
                "Budget: online re-allocation armed (q_err2-fed re-solve "
                "at checkpoint boundaries; decisions land in "
                "incidents.jsonl as budget_realloc)",
                flush=True,
            )
            if args.auto == "controller" and tuner is not None:
                # ONE re-solve loop: fold the budget reactor into the
                # ControllerRetuner so drift and allocation re-decisions
                # share one knob vector and one controller_redecide
                # incident stream (the loop sees a single object as
                # both tuner= and budget_tuner=)
                tuner.budget_tuner = budget_tuner
                budget_tuner = tuner
                print(
                    "Controller: online re-solve loop armed (drift + "
                    "allocation reactors composed; applied changes land "
                    "as controller_redecide)",
                    flush=True,
                )
        else:
            print(
                "Budget: allocation frozen for this run"
                + (
                    ""
                    if args.obs_quality and args.obs_record
                    else " (arm --obs-quality --obs-record with a "
                         "checkpoint cadence to re-solve at boundaries)"
                ),
                flush=True,
            )
    if n_dev > 1:
        from atomo_tpu.parallel import distributed_train_loop, make_mesh

        if args.aggregate == "auto" and hybrid_plan is not None:
            # the hybrid plan's wire bytes decide — the dense-path byte
            # budget would mis-price the exchange --sparse-rows actually
            # dispatches; and the row payloads need the payload path, so
            # a psum/hierarchical pick falls back to gather out loud
            from atomo_tpu.utils.comm_model import (
                choose_aggregate,
                resolve_fabric,
            )

            try:
                bw = resolve_fabric(
                    args.fabric, n_proc=jax.process_count(),
                    measured=getattr(args, "_fabric_probe", None),
                )
            except ValueError as exc:
                raise SystemExit(str(exc)) from None
            mode, reason = choose_aggregate(
                has_codec=True,
                dense_bytes=sum(
                    a.dense_bytes for a in hybrid_plan.assignments
                ),
                payload_bytes=hybrid_plan.payload_bytes(),
                ways=n_dev,
                fabric_bw=bw,
                tax_s=(
                    None if args.codec_tax_ms is None
                    else args.codec_tax_ms / 1e3
                ),
            )
            if mode not in ("gather", "ring"):
                reason = (
                    f"{mode} pick overridden — the sparse-row exchange "
                    f"needs the payload path ({reason})"
                )
                mode = "gather"
            print(
                f"--aggregate auto -> {mode} (sparse-row hybrid plan: "
                f"{reason})",
                flush=True,
            )
            args.aggregate = mode
        if args.aggregate == "auto":
            # shape only — do NOT pull a batch: epoch() advances the
            # iterator's persistent shuffle RNG, which would change the
            # training data order vs an explicit --aggregate run with the
            # same seed (code-review r5 finding)
            sample = jnp.zeros(
                (1,) + tuple(train_iter.images.shape[1:]), jnp.float32
            )
            from atomo_tpu.tuning.probe import model_init_fn

            _init_params = model_init_fn(model, sample)
            args.aggregate = _resolve_auto_aggregate(
                args, codec, _init_params, n_dev,
                allow_hierarchical=(
                    args.overlap != "delayed" and not args.elastic
                ),
            )
            if args.overlap == "delayed" and args.aggregate not in (
                "gather", "ring",
            ):
                raise SystemExit(
                    "--overlap delayed: --aggregate auto resolved to "
                    f"{args.aggregate!r} for this byte budget; pass "
                    "--aggregate gather or ring explicitly to keep the "
                    "overlapped schedule, or drop --overlap"
                )
            if args.stream_encode == "on" and args.aggregate not in (
                "gather", "ring",
            ):
                raise SystemExit(
                    "--stream-encode: --aggregate auto resolved to "
                    f"{args.aggregate!r} for this deployment; pass "
                    "--aggregate gather or ring explicitly to keep the "
                    "bucket-streamed encode, or drop --stream-encode"
                )
            if args.obs_quality and args.aggregate == "hierarchical":
                raise SystemExit(
                    "--obs-quality: --aggregate auto resolved to "
                    "hierarchical for this deployment (the boundary "
                    "re-encode is not probe-aware); pass --aggregate "
                    "gather or ring explicitly to keep the quality "
                    "probes, or drop --obs-quality"
                )
            if (
                args.num_aggregate is not None
                and codec is not None
                and args.aggregate not in ("gather", "ring")
            ):
                warnings.warn(
                    "--num-aggregate only applies to gather/ring "
                    f"aggregation; --aggregate auto resolved to "
                    f"{args.aggregate!r} — pass --aggregate gather "
                    "explicitly to subset replicas"
                )
            if args.plan != "auto" and args.aggregate != "hierarchical":
                # an explicitly pinned plan must never be silently
                # dropped (the --overlap delayed auto-resolution
                # precedent): auto only goes hierarchical on a two-tier
                # deployment with a codec
                raise SystemExit(
                    f"--plan {args.plan}: --aggregate auto resolved to "
                    f"{args.aggregate!r} for this deployment (a planned "
                    "two-level schedule needs a compressing --code and a "
                    "--dcn-ways/multi-host mesh); pass --aggregate "
                    "hierarchical explicitly to force it, or drop --plan"
                )
        inner_axis = None
        plan = None
        if args.aggregate == "hierarchical":
            k = args.dcn_ways or max(jax.process_count(), 2)
            if codec is None:
                raise SystemExit(
                    "--aggregate hierarchical needs a compressing --code "
                    "(the point is factors on the slow fabric; use "
                    "--aggregate psum for dense)"
                )
            if n_dev % k or not 1 < k <= n_dev:
                raise SystemExit(
                    f"--dcn-ways {k} must divide --n-devices {n_dev} "
                    "(outer slow-fabric groups x inner fast-fabric chips)"
                )
            mesh = make_mesh(n_dev, axes=(("dp", k), ("ici", n_dev // k)))
            inner_axis = "ici"
            # plan precedence: autopilot winner > explicit --plan >
            # auto-resolution's planner choice > legacy (None). A
            # user-pinned --aggregate hierarchical under --plan auto
            # falls through to legacy (args._auto_plan is only set when
            # the auto-resolution ran the planner), so today's exact
            # program stays the default for explicit hierarchical; the
            # legacy plan is byte-identical to the pre-topology path
            pname = (
                getattr(args, "_tuned_plan", None)
                or (args.plan if args.plan != "auto" else None)
                or getattr(args, "_auto_plan", None)
            )
            if pname and pname != "legacy":
                from atomo_tpu.topology.schedule import plan_from_name

                plan = plan_from_name(pname)
                print(f"Topology plan: {plan.name}", flush=True)
        else:
            mesh = make_mesh(n_dev)
        k_agg = 0
        if (
            args.num_aggregate is not None
            and args.aggregate in ("gather", "ring")
            and codec is not None
        ):
            k_agg = args.num_aggregate
            if not 0 < k_agg < n_dev:
                warnings.warn(
                    f"--num-aggregate {k_agg} is outside (0, {n_dev}) for this "
                    f"{n_dev}-device mesh; aggregating all replicas"
                )
                k_agg = 0
        from atomo_tpu.elastic.membership import MembershipChange
        from atomo_tpu.parallel.mesh import device_line

        print(device_line(mesh), flush=True)
        try:
            distributed_train_loop(
                model, optimizer, mesh, train_iter, test_iter,
                codec=codec, aggregate=args.aggregate, augment=augment,
                num_aggregate=k_agg,
                zero1=_partition(args) == "zero1",
                sharded_update=_partition(args) == "sharded_update",
                grad_accum=args.grad_accum, inner_axis=inner_axis,
                max_steps=max_steps, eval_freq=args.eval_freq, seed=args.seed,
                train_dir=args.train_dir, save_freq=save_freq, resume=args.resume,
                compress_ckpt=args.compress, log_every=args.log_interval,
                health_timeout=args.health_timeout,
                guard=guard, chaos=chaos, keep_ckpts=args.keep_ckpts,
                profile_dir=args.profile_dir or None,
                compute_dtype=jnp.bfloat16 if args.bf16 else None,
                superstep=superstep,
                ring_bucket_size=args.ring_bucket_size,
                overlap=args.overlap,
                stream_encode=args.stream_encode == "on",
                stream_bucket_bytes=_stream_bucket_bytes(args),
                diverge=diverge,
                tuner=tuner,
                plan=plan,
                elastic=elastic_cfg,
                track_quality=args.obs_quality,
                recorder=recorder,
                hybrid=hybrid_plan,
                error_feedback=args.error_feedback,
                budget_tuner=budget_tuner,
                quorum=quorum_cfg,
                quorum_replay=args.replay_arrivals or None,
            )
        except DivergenceError as exc:
            return _diverged_exit(exc)
        except MembershipChange as exc:
            return _membership_exit(exc)
    else:
        from atomo_tpu.training import train_loop

        if args.num_aggregate is not None:
            warnings.warn(
                "--num-aggregate needs a multi-device mesh; single-device "
                "training has no replicas to subset — ignoring it"
            )
        if args.zero1:
            warnings.warn(
                "--zero1 needs a multi-device mesh; single-device training "
                "has no dp axis to shard the optimizer state over — "
                "ignoring it"
            )
        if args.plan != "auto":
            warnings.warn(
                "--plan selects a two-level schedule over a multi-device "
                "mesh; single-device training has no tiers to schedule — "
                "ignoring it"
            )
        if args.grad_accum > 1:
            warnings.warn(
                "--grad-accum is only wired into the multi-device step; "
                "single-device training ignores it"
            )
        if _partition(args) != "replicated":
            warnings.warn(
                f"--partition {_partition(args)} is wired into the "
                "distributed loop; the single-device path trains the "
                "replicated update (the --zero1 precedent — there is "
                "nothing to shard a 1-chip update over)"
            )
        from atomo_tpu.parallel.mesh import device_line

        print(device_line(), flush=True)
        try:
            train_loop(
                model, optimizer, train_iter, test_iter,
                codec=codec, augment=augment, max_steps=max_steps,
                eval_freq=args.eval_freq, seed=args.seed,
                train_dir=args.train_dir, save_freq=save_freq, resume=args.resume,
                compress_ckpt=args.compress, log_every=args.log_interval,
                compute_dtype=jnp.bfloat16 if args.bf16 else None,
                guard=guard, chaos=chaos, health_timeout=args.health_timeout,
                keep_ckpts=args.keep_ckpts, superstep=superstep,
                diverge=diverge, tuner=tuner,
                track_quality=args.obs_quality,
                recorder=recorder,
                profile_dir=args.profile_dir or None,
            )
        except DivergenceError as exc:
            return _diverged_exit(exc)
    return 0


def _latent_moe_sizes(args: argparse.Namespace, pattern: tuple):
    """`lm --block glm`'s sizes as the model takes them, or a one-line refusal."""
    from atomo_tpu.models.moe import LatentMoeSizes

    if args.block != "glm" or set(pattern) != {"mla"}:
        raise SystemExit(
            "--layer-pattern: an mla layer comes with --block glm, whose every layer is one"
        )
    sizes = ("q_rank", "kv_rank", "nope_dim", "rope_dim", "value_dim",
             "routed_experts", "expert_width")
    missing = [f"--{name.replace('_', '-')}" for name in sizes if getattr(args, name) <= 0]
    if missing:
        raise SystemExit(f"--block glm needs its sizes: {' '.join(missing)}")
    try:
        return LatentMoeSizes(
            q_rank=args.q_rank, kv_rank=args.kv_rank, nope_dim=args.nope_dim,
            rope_dim=args.rope_dim, value_dim=args.value_dim, rope_theta=args.rope_theta,
            experts=args.routed_experts, experts_held=args.experts_held,
            first_expert=args.first_expert, per_token=args.experts_per_token,
            expert_width=args.expert_width, shared_experts=args.shared_experts,
            dense_layers=args.dense_layers, route_scale=args.route_scale,
            mtp_depth=args.mtp_depth, mtp_weight=args.mtp_weight, scoring=args.router,
        )
    except ValueError as e:
        raise SystemExit(f"--block glm: {e}") from None


def _expert_sizes(args: argparse.Namespace):
    """The `experts` FFN's sizes (`lm --block mellum`), or a one-line refusal."""
    from atomo_tpu.models.moe import ExpertSizes

    missing = [f"--{name.replace('_', '-')}" for name in ("routed_experts", "expert_width")
               if getattr(args, name) <= 0]
    if missing:
        raise SystemExit(f"--block {args.block} needs its experts' sizes: {' '.join(missing)}")
    if args.shared_experts or args.dense_layers:
        raise SystemExit(
            f"--block {args.block}: every layer is routed experts alone; say "
            "--shared-experts 0 --dense-layers 0 (a shared expert and leading dense "
            "layers are --block glm's)"
        )
    try:
        return ExpertSizes(
            expert_width=args.expert_width, experts=args.routed_experts,
            experts_held=args.experts_held, first_expert=args.first_expert,
            per_token=args.experts_per_token, scoring=args.router, route_scale=args.route_scale,
        )
    except ValueError as e:
        raise SystemExit(f"--block {args.block}: {e}") from None


def _rope_rules(args: argparse.Namespace, pattern: tuple) -> tuple:
    """((mixer kind, Rotary), ...) for the attention kinds of `pattern`:
    --rope-theta for all, with YaRN's record on the `full` layers where
    --yarn-factor is given (a window layer never reaches past its window, so
    its frequencies stay as trained)."""
    from atomo_tpu.models.rotary import Rotary, Yarn

    yarn = None
    if args.yarn_factor:
        if args.yarn_factor <= 1 or args.yarn_original_len <= 0:
            raise SystemExit("--yarn-factor scales past --yarn-original-len: a factor above 1 and a length")
        yarn = Yarn(args.yarn_factor, args.yarn_original_len, args.yarn_beta_fast,
                    args.yarn_beta_slow, args.yarn_attention_factor)
    kinds = dict.fromkeys(kind for kind in pattern if kind in ("full", "window"))
    return tuple((kind, Rotary(args.rope_theta, yarn if kind == "full" else None)) for kind in kinds)


def _lm_block_config(args: argparse.Namespace) -> dict:
    """The TransformerLM fields that `lm --block`, `--layer-pattern` and the
    sizes beside them set; empty for GPT-2's block, so that the layouts whose
    blocks are written by hand see the configuration they always saw."""
    from atomo_tpu.models.linear_attention import CHUNK
    from atomo_tpu.models.transformer import BLOCK_RECIPES, MIXERS

    pattern = tuple(kind.strip() for kind in args.layer_pattern.split(","))
    for kind in pattern:
        if kind not in MIXERS:
            raise SystemExit(
                f"--layer-pattern: unknown layer kind {kind!r}; expected a "
                f"comma-separated list of {' | '.join(MIXERS)}"
            )
    block = dict(BLOCK_RECIPES[args.block])
    if args.ffn_width:
        block["ffn_width"] = args.ffn_width
    if args.remat != "none":
        block["remat"] = args.remat
    if pattern != ("full",):
        block["layer_pattern"] = pattern
    if "mla" in block.get("layer_pattern", ()):
        block["latent_moe"] = _latent_moe_sizes(args, block["layer_pattern"])
    if block.get("ffn") == "experts":
        block["experts"] = _expert_sizes(args)
    if block.get("positions") == "rotary":
        block["rope"] = _rope_rules(args, pattern)
    for size in ("kv_heads", "head_dim"):
        if getattr(args, size):
            block[size] = getattr(args, size)
    if args.kv_heads and args.num_heads % args.kv_heads:
        raise SystemExit(f"--kv-heads {args.kv_heads} does not divide --num-heads {args.num_heads}")
    if "window" in pattern:
        if args.window <= 0:
            raise SystemExit("--layer-pattern with a window layer needs --window")
        block["window"] = args.window
    if "linear" in pattern:
        if args.linear_key_dim <= 0 or args.linear_value_dim <= 0:
            raise SystemExit(
                "--layer-pattern with a linear layer needs its head sizes: "
                "--linear-key-dim and --linear-value-dim"
            )
        if args.seq_len % CHUNK:
            raise SystemExit(
                f"--seq-len {args.seq_len} is no multiple of {CHUNK}: the "
                "linear layers of --layer-pattern run in whole chunks"
            )
        block.update(
            linear_key_dim=args.linear_key_dim,
            linear_value_dim=args.linear_value_dim,
            linear_conv_width=args.linear_conv_width,
        )
    if block and args.layout != "dp":
        flag = ("--block" if args.block != "gpt2" else
                "--ffn-width" if args.ffn_width else
                "--remat" if args.remat != "none" else
                "--kv-heads" if args.kv_heads else
                "--head-dim" if args.head_dim else "--layer-pattern")
        raise SystemExit(
            f"{flag} needs --layout dp: --layout {args.layout} writes GPT-2's "
            "block by hand (tp, ep, pp) or rings the sequence (sp), which a "
            "linear layer's state, a window and grouped key/value heads do not cross"
        )
    return block


def cmd_lm(args: argparse.Namespace) -> int:
    """Long-context / model-sharded LM training: every parallelism layout
    the framework supports, drivable from the CLI (no reference analogue —
    the reference is DP-only and CV-only, SURVEY.md §2.1/§5.7).

    --layout picks the mesh composition (the ``MeshSpec.from_layout``
    grammar); --ways sizes the model axis:
      dp        pure compressed data parallelism
      dp-sp     sequence parallelism (ring/Ulysses attention, --attn-impl)
      dp-tp     Megatron tensor parallelism
      dp-ep     switch-MoE expert parallelism
      dp-pp     GPipe pipeline parallelism
      dp-tp-sp  3-D tensor x sequence (--ways sizes tp, --sp-ways sizes sp)

    Every layout compiles through the ONE mesh path
    (``parallel.model_axes.build_model_axis_program``): the dp gradient
    exchange rides the compressed stack (gather/psum/ring,
    --stream-encode), the model-axis collectives ride
    ``mesh.collectives`` so the comm model can price them.
    """
    import jax
    import numpy as np

    from atomo_tpu.codecs import get_codec
    from atomo_tpu.parallel import launch
    from atomo_tpu.training import make_optimizer

    launch.initialize()
    n_dev = args.n_devices or len(jax.devices())
    layout = args.layout
    if layout == "dp" and args.ways != 2:  # 2 is the argparse default
        warnings.warn(
            f"--ways {args.ways} only applies to layouts with a model axis; "
            "--layout dp is pure data parallelism — ignoring it"
        )
    if args.sp_ways != 2 and layout != "dp-tp-sp":  # 2 is the default
        warnings.warn(
            "--sp-ways only applies to --layout dp-tp-sp (the 2-D layouts "
            "size their one model axis with --ways); ignoring it"
        )
    if layout == "dp-tp-sp":
        ways_arg = (args.ways, args.sp_ways)
        ways = args.ways * args.sp_ways
    else:
        ways = 1 if layout == "dp" else args.ways
        ways_arg = ways
    if n_dev % ways:
        raise SystemExit(f"--ways {ways} does not divide {n_dev} devices")
    dp = n_dev // ways
    if args.batch_size % n_dev and layout == "dp-ep":
        raise SystemExit(
            f"--batch-size {args.batch_size} must divide over all "
            f"{n_dev} chips for dp-ep"
        )
    if args.batch_size % dp:
        raise SystemExit(f"--batch-size {args.batch_size} not divisible by dp={dp}")

    # Width-aware rank policy (VERDICT r4 weak #8): rank 3 measurably
    # FLOORS a width-64 LM at 1.39x dense CE while rank 6 passes the
    # convergence gate (artifacts/LM_CONVERGENCE.md) — transformer matrix
    # width sets the rank budget. Default (0) scales rank to preserve the
    # verified 6/64 rank/width operating point; an explicit below-floor
    # rank runs, but never silently.
    svd_rank = args.svd_rank
    if args.code.lower().startswith("svd"):  # svd AND svd_budget: rank 0
        # would mean full-rank payloads / empty Bernoulli keep-sets
        # ceil(width * 6/64): the verified ratio, exact at the anchor
        rank_floor = max(2, -(-args.width * 6 // 64))
        if svd_rank <= 0:
            svd_rank = rank_floor
            print(
                f"--svd-rank auto -> {svd_rank} for width {args.width} "
                "(anchored at the verified rank-6/width-64 operating "
                "point, artifacts/LM_CONVERGENCE.md)"
            )
        elif svd_rank < rank_floor:
            warnings.warn(
                f"--svd-rank {svd_rank} is below the width-scaled floor "
                f"{rank_floor} for --width {args.width}: rank 3 floors a "
                "width-64 LM at 1.39x dense CE "
                "(artifacts/LM_CONVERGENCE.md) — expect a loss floor; use "
                "--svd-rank 0 for the width-scaled default"
            )
    codec = None
    if args.code.lower() not in DENSE_CODES:
        codec = get_codec(
            args.code,
            svd_rank=svd_rank,
            quantization_level=args.quantization_level,
            bucket_size=args.bucket_size,
            sample=getattr(args, "sample", "fixed_k"),
            algorithm=getattr(args, "svd_algo", "auto"),
            wire_dtype=getattr(args, "svd_wire", "float32"),
        )
    optimizer = make_optimizer(
        args.optimizer, lr=args.lr, lr_shrinkage=args.lr_shrinkage,
        shrinkage_freq=args.shrinkage_freq, momentum=args.momentum,
        nesterov=args.nesterov, weight_decay=args.weight_decay,
    )
    # validate --data-file BEFORE the expensive layout setup: it depends
    # only on argv and the file
    raw = None
    if args.data_file:
        if args.vocab_size < 256:
            raise SystemExit(
                f"--data-file tokenizes raw bytes: --vocab-size "
                f"{args.vocab_size} < 256 cannot embed them"
            )
        try:
            with open(args.data_file, "rb") as f:
                raw = np.frombuffer(f.read(), dtype=np.uint8)
        except OSError as e:
            raise SystemExit(f"--data-file: {e}") from None
        if len(raw) // args.seq_len < args.batch_size:
            raise SystemExit(
                f"--data-file holds only {len(raw) // args.seq_len} "
                f"sequences of length {args.seq_len}; need at least "
                f"--batch-size {args.batch_size}"
            )

    cfg = dict(
        vocab_size=args.vocab_size, max_len=args.seq_len, width=args.width,
        depth=args.depth, num_heads=args.num_heads,
    )
    cfg.update(_lm_block_config(args))  # nothing, for GPT-2's block
    key = jax.random.PRNGKey(args.seed)
    compute_dtype = jax.numpy.bfloat16 if args.bf16 else None

    aggregate = args.aggregate
    if aggregate == "ring" and codec is None:
        raise SystemExit(
            "--aggregate ring streams CODEC payloads around the dp axis; "
            "a dense code has no payloads to rotate — use psum (or pick a "
            "compressing --code)"
        )
    if args.stream_encode and codec is None:
        warnings.warn(
            "--stream-encode interleaves CODEC encode with the exchange; "
            "a dense code has nothing to encode — ignoring it"
        )
    if args.overlap == "delayed":
        # the model-axis delayed preflight — same contract the replicated
        # train path enforces, phrased for the lm surface
        if codec is None:
            raise SystemExit(
                "--overlap delayed carries the ENCODED payload between "
                "steps; a dense --code has no payload to carry — pick a "
                "compressing --code, or drop --overlap"
            )
        if dp <= 1:
            raise SystemExit(
                f"--overlap delayed needs a multi-replica dp axis; "
                f"--layout {layout} at {n_dev} devices resolves to dp=1 — "
                "no dp exchange to take off the critical path"
            )
        if aggregate == "psum":
            raise SystemExit(
                "--overlap delayed does not compose with --aggregate "
                "psum: the dense all-reduce has no encoded payload to "
                "carry between steps — use gather or ring"
            )
    if aggregate == "auto":
        # The lm dp exchange now prices the FULL axis-layout space the
        # replicated path ships — gather vs psum vs ring over the dp axis
        # of any model-axis layout (DpExchange routes all three through
        # the one compressed stack). Hierarchical alone stays out, for a
        # structural reason (controller.space.MODEL_AXIS_REJECTS
        # ["hierarchical"], the same reason every reject in that space
        # states): the model axes — sp/tp/ep/pp — own the second mesh
        # dimension, so there is no free inner data axis for a two-level
        # schedule to reduce over. Byte budget from the unsharded LM
        # (tp/ep/pp shard both sides of the ratio equally —
        # decision-equivalent heuristic)
        from atomo_tpu.models.transformer import TransformerLM as _LM
        from atomo_tpu.tuning.probe import model_init_fn

        sample = jax.numpy.zeros((1, args.seq_len), jax.numpy.int32)
        _init_params = model_init_fn(_LM(**cfg), sample)
        aggregate = _resolve_auto_aggregate(
            args, codec, _init_params, dp, allow_hierarchical=False,
        )
        if args.overlap == "delayed" and aggregate not in ("gather", "ring"):
            raise SystemExit(
                "--overlap delayed: --aggregate auto resolved to "
                f"{aggregate!r} for this byte budget; pass --aggregate "
                "gather or ring explicitly to keep the overlapped "
                "schedule, or drop --overlap"
            )
    # ring / stream-encode / delayed run through the DpExchange tail (the
    # compressed-stack route); the plain gather/psum knobs keep
    # exchange=None — the legacy tail, byte-for-byte (the degeneracy
    # contract tests/test_model_axes.py pins)
    exchange = None
    if args.stream_encode and codec is not None and aggregate == "psum":
        warnings.warn(
            "--stream-encode interleaves encode with the FACTOR exchange "
            "(gather/ring); psum moves the dense decoded tree — ignoring it"
        )
    elif (
        aggregate == "ring"
        or (args.stream_encode and codec is not None)
        or args.overlap == "delayed"
    ):
        from atomo_tpu.parallel.lm import DpExchange

        exchange = DpExchange(
            aggregate=aggregate,
            ring_bucket_size=args.ring_bucket_size,
            stream_encode=bool(args.stream_encode and codec is not None),
            stream_bucket_bytes=args.stream_bucket_bytes,
            overlap=args.overlap,
        )

    # layout-inapplicable flags: warn, don't silently ignore (the train
    # subcommand's _warn_dead_flags precedent)
    defaults = {"attn_impl": "ring", "num_experts": 8, "microbatches": 2}
    applicable = {
        "attn_impl": ("dp-sp", "dp-tp-sp"),
        "num_experts": ("dp-ep",),
        "microbatches": ("dp-pp",),
    }
    for flag, default in defaults.items():
        if getattr(args, flag) != default and layout not in applicable[flag]:
            raise_for = "/".join(applicable[flag])
            warnings.warn(
                f"--{flag.replace('_', '-')} only applies to layout "
                f"{raise_for}; ignored for --layout {layout}"
            )

    # layout preflight the builders cannot phrase as one-liners (they see
    # shapes, not flags): keep the flag-named messages here
    sp_size = ways if layout == "dp-sp" else (
        args.sp_ways if layout == "dp-tp-sp" else 1
    )
    if args.seq_len % sp_size:
        raise SystemExit(
            f"--seq-len must be divisible by sp ways={sp_size}"
        )
    if layout == "dp-ep":
        cfg["num_experts"] = args.num_experts
    if layout == "dp-pp":
        if args.depth % ways:
            raise SystemExit(
                f"--depth {args.depth} must be divisible by pp ways={ways}"
            )
        if (args.batch_size // dp) % args.microbatches:
            raise SystemExit(
                f"per-replica batch {args.batch_size // dp} not divisible "
                f"by --microbatches {args.microbatches}"
            )

    # the ONE compile path: every layout resolves through MeshSpec +
    # build_model_axis_program — same axes tuples, same builders, same
    # compiled programs as the old per-layout ladder (bit-parity pinned
    # by tests/test_model_axes.py)
    from atomo_tpu.mesh.spec import MeshSpec
    from atomo_tpu.parallel.model_axes import build_model_axis_program

    try:
        spec = MeshSpec.from_layout(layout, n_dev, ways_arg)
        prog = build_model_axis_program(
            spec, cfg, optimizer, key, codec,
            attn_impl=args.attn_impl,
            num_microbatches=args.microbatches,
            compute_dtype=compute_dtype,
            aggregate=aggregate,
            exchange=exchange,
        )
    except ValueError as e:  # sizing errors -> clean one-liner
        raise SystemExit(str(e)) from None
    mesh, state, specs = prog.mesh, prog.state, prog.state_specs
    step, shard = prog.step, prog.shard_tokens
    from atomo_tpu.parallel.mesh import device_line, placement_line

    print(device_line(mesh), flush=True)

    rng = np.random.default_rng(args.seed)

    def _synth(r, n):
        starts = r.integers(0, args.vocab_size, size=(n, 1))
        strides = r.integers(1, 4, size=(n, 1))
        return (
            (starts + strides * np.arange(args.seq_len)) % args.vocab_size
        ).astype(np.int32)

    if raw is not None:
        # byte-level corpus: the file's raw bytes are the token stream,
        # chunked into seq_len windows (validated above); the LAST 10% of
        # chunks are held out for --eval-freq validation
        n_seq = len(raw) // args.seq_len
        chunks = raw[: n_seq * args.seq_len].reshape(n_seq, args.seq_len)
        n_hold = max(1, n_seq // 10) if args.eval_freq else 0
        train_chunks = chunks[: n_seq - n_hold] if n_hold else chunks
        eval_tokens = chunks[n_seq - n_hold :].astype(np.int32) if n_hold else None
        if len(train_chunks) < args.batch_size:
            raise SystemExit(
                f"--data-file leaves only {len(train_chunks)} training "
                f"sequences after the --eval-freq holdout ({n_hold}); need "
                f"at least --batch-size {args.batch_size}"
            )

        def next_batch():
            idx = rng.integers(0, len(train_chunks), size=args.batch_size)
            return shard(train_chunks[idx].astype(np.int32))

    else:
        # deterministic learnable token streams: arithmetic progressions
        # with random starts/strides (the LM data analogue of --synthetic);
        # eval uses an independent stream of the same distribution
        eval_tokens = (
            _synth(np.random.default_rng(args.seed + 10_000), args.batch_size)
            if args.eval_freq
            else None
        )

        def next_batch():
            return shard(_synth(rng, args.batch_size))

    def eval_ppl(state) -> tuple[float, str]:
        """Held-out mean CE via the layout's SINGLE-DEVICE oracle forward on
        the gathered params — uniform across layouts, no extra jitted
        program (eval batches are small). Returns (ce, extra) where
        ``extra`` is a layout-specific suffix for the log line (dp-ep also
        reports CE under the TRAINING per-chip capacity so the train and
        validation series are commensurable — ADVICE r3 #5)."""
        import optax as _optax

        extra_note = ""
        toks = jax.numpy.asarray(eval_tokens[: args.batch_size])
        params = jax.device_get(state.params)
        if layout == "dp-tp":
            from atomo_tpu.models.transformer import TransformerLM
            from atomo_tpu.parallel.tp import tp_params_to_lm

            logits = TransformerLM(**cfg).apply(
                {"params": tp_params_to_lm(params, cfg["num_heads"])}, toks
            )
        elif layout == "dp-ep":
            import math as _math

            from atomo_tpu.parallel.moe import moe_lm_forward

            # capacity over the tokens actually in THIS forward (the whole
            # eval batch runs on one "chip"), not the per-chip training
            # count — a smaller budget would drop extra tokens and bias
            # the reported loss upward
            t_eval = toks.shape[0] * args.seq_len
            capp = max(
                1, _math.ceil(1.25 * t_eval / cfg["num_experts"])
            )
            logits, _ = moe_lm_forward(params, toks, cfg, capacity=capp)
            # ALSO evaluate under the TRAINING per-chip drop regime (the
            # same ceil(1.25*T_local/E) budget make_moe_lm_train_step
            # uses), so validation can be read against the training loss
            # series without a capacity mismatch (ADVICE r3 #5). The
            # regime only matches if the forward sees per-CHIP-sized
            # batches: routing the whole eval batch at the per-chip
            # capacity would be dp*ep times harsher than training, so
            # chunk the batch into training-sized shards and average.
            n_chips = dp * ways
            chunk_b = max(1, args.batch_size // n_chips)
            t_local = chunk_b * args.seq_len
            cap_train = max(1, _math.ceil(1.25 * t_local / cfg["num_experts"]))
            ces = []
            n_full = (toks.shape[0] // chunk_b) * chunk_b
            for i0 in range(0, n_full, chunk_b):
                lg_t, _ = moe_lm_forward(
                    params, toks[i0 : i0 + chunk_b], cfg, capacity=cap_train
                )
                ces.append(
                    float(
                        _optax.softmax_cross_entropy_with_integer_labels(
                            lg_t[:, :-1], toks[i0 : i0 + chunk_b, 1:]
                        ).mean()
                    )
                )
            if ces:
                ce_t = sum(ces) / len(ces)
                extra_note = f", Loss@TrainCap: {ce_t:.4f} (C={cap_train})"
        elif layout == "dp-pp":
            from atomo_tpu.parallel.pp import pp_lm_forward_reference

            logits = pp_lm_forward_reference(params, toks, cfg)
        else:
            from atomo_tpu.models.transformer import TransformerLM

            logits = TransformerLM(**cfg).apply({"params": params}, toks)
            if isinstance(logits, tuple):  # beside the prediction module's
                logits = logits[0]
        ce = float(
            _optax.softmax_cross_entropy_with_integer_labels(
                logits[:, :-1], toks[:, 1:]
            ).mean()
        )
        return ce, extra_note

    import collections
    import math
    import time

    start = 0
    if args.train_dir and args.resume:
        from atomo_tpu.training.checkpoint import (
            latest_step,
            load_checkpoint,
            load_sharded_checkpoint,
        )
        from atomo_tpu.parallel.mesh import replicated as _replicated

        if latest_step(args.train_dir) is not None:
            from atomo_tpu.parallel.replicated import DelayedState as _DS

            template = jax.device_get(state)
            if isinstance(state, _DS):
                # --overlap delayed: the carry (the in-flight encoded
                # payload + its valid flag) is PART of the checkpointed
                # state, so a kill->restart->resume continues the exact
                # stale-by-one schedule — load the full DelayedState host
                # tree, then place each half: train per the layout's
                # specs, carry on its all-axes row sharding
                from jax.sharding import NamedSharding

                from atomo_tpu.parallel.lm import place_model_axis_carry

                host = load_checkpoint(args.train_dir, template)
                if specs is None:
                    train = jax.device_put(host.train, _replicated(mesh))
                else:
                    train = jax.tree_util.tree_map(
                        lambda leaf, sp: jax.device_put(
                            leaf, NamedSharding(mesh, sp)
                        ),
                        host.train, specs,
                    )
                state = _DS(
                    train=train,
                    carry=place_model_axis_carry(mesh, host.carry),
                )
            elif specs is None:
                state = jax.device_put(
                    load_checkpoint(args.train_dir, template),
                    _replicated(mesh),
                )
            else:
                state = load_sharded_checkpoint(
                    args.train_dir, template, mesh, specs
                )
            start = int(state.step)
            print(f"Resumed from {args.train_dir} at step {start}", flush=True)

    recorder = None
    if args.train_dir:
        # flight-record the lm run so `report` can cross-check the
        # RECORDED axis layout against what actually executed (a resumed
        # run on a reshaped mesh contradicts its own metrics.jsonl)
        from atomo_tpu.obs import FlightRecorder

        recorder = FlightRecorder.for_train_dir(args.train_dir)
        if start:
            recorder.prune_past(start)
        recorder.set_context(aggregate=aggregate)
        recorder.write_meta({
            "what": "model_axes",
            "layout": layout,
            "mesh_axes": spec.shape_dict(),
            "exchange": (
                None if exchange is None else {
                    "aggregate": exchange.aggregate,
                    "stream_encode": exchange.stream_encode,
                    "overlap": exchange.overlap,
                }
            ),
        })

    from atomo_tpu.utils.tracing import (
        BOUNDARY,
        DISPATCH,
        FETCH,
        NEXT_BATCH,
        PROFILE_STEPS,
        STEP,
        ProfileWindow,
        clear_iterations,
        span,
    )

    # The loop keeps ONE step in flight: iteration j launches step j+1 and
    # only then fetches the loss of step j and does step j's boundary work
    # (recorder, log line), so the device has its next execution queued when
    # one ends and the log runs one step behind the device. `state` is
    # donated into a launch, so an iteration whose own state is read DRAINS
    # instead, it fetches its loss with nothing launched after it: the first
    # (the placement line), one with an evaluation or a save due, the last,
    # and the last of the --profile-dir window; the iteration after a drain
    # launches two. The `step` span, its `fetch` and its `boundary` carry
    # the step they report, `next_batch` and `dispatch` the step they launch.
    # `Time Cost:` is the wall time from the loss before to this one: what a
    # step costs. An exception out of the loop (the benchmark closes its
    # window by raising through `print`) waits for nothing. The loop stays
    # in this function: moved into one of its own, the step's first call and
    # the jits after it lowered a second slower on the v5e's host (PERF.md
    # §6, PR 32), and `setup_s` is an end-to-end metric.
    prof = ProfileWindow(args.profile_dir or None, print, recorder)
    clear_iterations()  # the ring holds set-up and this loop's iterations
    launched = start  # the last step handed to the device
    in_flight = collections.deque()  # metrics of the steps launched and not yet reported
    arrived = time.time()  # when the last loss came back
    for j in range(start + 1, args.max_steps + 1):
        with span(STEP, j):
            if j == start + 2:  # step 1 is dominated by compilation
                prof.open(j, j + PROFILE_STEPS - 1)
            eval_due = args.eval_freq and j % args.eval_freq == 0
            save_due = args.train_dir and (
                (args.save_freq and j % args.save_freq == 0) or j == args.max_steps
            )
            window_ends = prof.ends_at(j)
            drain = j == start + 1 or eval_due or save_due or window_ends
            # launch through step j, and one step more unless j's state is read
            while launched < min(j + (not drain), args.max_steps):
                launched += 1
                with span(NEXT_BATCH, launched):
                    batch = next_batch()
                with span(DISPATCH, launched):
                    state, metrics = step(state, jax.random.fold_in(key, launched), batch)
                in_flight.append(metrics)
            metrics = in_flight.popleft()
            with span(FETCH):
                loss = float(metrics["loss"])  # waits for step j, and for no later one
            now = time.time()
            wall, arrived = now - arrived, now  # what a step costs: loss to loss
            if window_ends:
                prof.close()
            with span(BOUNDARY):
                if j == start + 1:
                    print(placement_line(state, batch), flush=True)
                if recorder is not None:
                    recorder.record_block(j, jax.device_get(metrics), wall_s=wall)
                if j % args.log_interval == 0 or j == args.max_steps:
                    print(
                        f"LM: Step: {j}, Layout: {layout}({spec.describe()}), "
                        f"Loss: {loss:.4f}, PPL: {math.exp(min(loss, 30.0)):.2f}, "
                        f"Time Cost: {wall:.4f}, "
                        f"Msg(MB): {float(metrics['msg_bytes']) / 1e6:.4f}, "
                        f"Dense(MB): {float(metrics['dense_bytes']) / 1e6:.4f}",
                        flush=True,
                    )
                if eval_due:  # drained: `state` is the state after step j
                    vl, vl_extra = eval_ppl(state)
                    print(
                        f"LM Validation: Step: {j}, Loss: {vl:.4f}, "
                        f"PPL: {math.exp(min(vl, 30.0)):.2f}" + vl_extra,
                        flush=True,
                    )
                if save_due:
                    from atomo_tpu.training.checkpoint import save_checkpoint

                    save_checkpoint(args.train_dir, state, compress=args.compress)
    prof.close()  # a run shorter than the profiled window
    return 0


def cmd_evaluate(args: argparse.Namespace) -> int:
    from atomo_tpu.parallel.mesh import device_line
    from atomo_tpu.training.evaluator import CheckpointEvaluator

    print(device_line(), flush=True)
    model, optimizer, _, _, test_iter, _ = _build_common(args, need_train=False)
    ev = CheckpointEvaluator(
        model, optimizer, test_iter, args.model_dir or args.train_dir,
        poll_interval=args.poll_interval,
    )
    ev.run(max_polls=args.max_polls or None, stop_when_idle=args.stop_when_idle)
    return 0


def cmd_report(args: argparse.Namespace) -> int:
    """``report``: join the run's artifacts — metrics.jsonl (flight
    recorder) + incidents.jsonl + membership.json + tune_decision.json +
    fabric_probe.json — into one time-ordered run_report.json with
    cross-artifact consistency checks, and print the human post-mortem.
    "What happened to this run" as one command.

    ``report timeline``: the trace-based phase timeline — parse the
    newest ``--profile-dir`` trace into per-step encode/exchange/decode/
    compute spans (the ``named_phase`` scopes inside the fused step),
    join them against metrics.jsonl, and write
    ``train_dir/timeline_report.json``. It observes the REAL
    fused/superstep/stream-encode/hybrid programs.

    Both verbs are pure host-side file reads: no jax, no devices, safe
    on a box that cannot reach the accelerator."""
    import os

    from atomo_tpu.utils.tracing import write_json_atomic

    if getattr(args, "what", "run") == "timeline":
        from atomo_tpu.obs.timeline import (
            TIMELINE_REPORT_NAME,
            build_timeline,
            summarize_timeline,
        )

        prof = args.profile_dir
        if not prof and args.train_dir:
            # convention fallback: a trace captured into the train dir
            prof = os.path.join(args.train_dir, "trace")
        if not prof or not os.path.isdir(prof):
            raise SystemExit(
                f"report timeline: profile dir {prof!r} does not exist — "
                "run training with --profile-dir DIR to capture a trace, "
                "then report timeline --profile-dir DIR"
            )
        train_dir = (
            args.train_dir
            if args.train_dir and os.path.isdir(args.train_dir)
            else None
        )
        doc = build_timeline(prof, train_dir)
        if train_dir:
            out = os.path.join(train_dir, TIMELINE_REPORT_NAME)
            write_json_atomic(out, doc)
            print(summarize_timeline(doc), flush=True)
            print(f"timeline report -> {out}", flush=True)
        else:
            print(summarize_timeline(doc), flush=True)
        if args.strict and not doc["consistent"]:
            return 3
        return 0

    from atomo_tpu.obs.report import (
        build_report,
        report_path,
        summarize_report,
    )

    if not args.train_dir or not os.path.isdir(args.train_dir):
        raise SystemExit(
            f"report: train dir {args.train_dir!r} does not exist"
        )
    if getattr(args, "fleet", False):
        from atomo_tpu.obs.report import (
            build_fleet_report,
            fleet_report_path,
            summarize_fleet_report,
        )

        doc = build_fleet_report(args.train_dir)
        write_json_atomic(fleet_report_path(args.train_dir), doc)
        print(summarize_fleet_report(doc), flush=True)
        print(
            f"fleet report -> {fleet_report_path(args.train_dir)}",
            flush=True,
        )
        if args.strict and not doc["consistent"]:
            return 3
        return 0
    doc = build_report(args.train_dir)
    write_json_atomic(report_path(args.train_dir), doc)
    print(summarize_report(doc), flush=True)
    print(f"run report -> {report_path(args.train_dir)}", flush=True)
    if args.strict and not doc["consistent"]:
        return 3
    return 0


def cmd_tune(args: argparse.Namespace) -> int:
    import os

    from atomo_tpu.tuning import grid_search

    # JSON artifact beside the regex-parsed log contract (the printed
    # lines below stay the machine-readable surface they always were):
    # default train_dir/lr_grid.json, --artifact overrides, '' disables
    artifact = args.artifact
    if artifact is None:
        artifact = (
            os.path.join(args.train_dir, "lr_grid.json")
            if args.train_dir else ""
        )
    results = grid_search(args, artifact_path=artifact or None)
    best = min(results, key=lambda r: r.mean_loss)
    for r in results:
        print(f"lr {r.lr:g}: mean loss {r.mean_loss:.4f} over final {r.window} steps")
    print(f"best lr: {best.lr:g} (mean loss {best.mean_loss:.4f})")
    if artifact:
        print(f"lr grid artifact -> {artifact}")
    return 0


def build_parser() -> argparse.ArgumentParser:
    parser = argparse.ArgumentParser(
        prog="atomo_tpu",
        description="TPU-native communication-efficient distributed SGD (ATOMO capabilities)",
    )
    sub = parser.add_subparsers(dest="command")

    p_train = sub.add_parser("train", help="train a model (single-host or mesh)")
    _add_fit_args(p_train)
    p_train.set_defaults(fn=cmd_train)

    p_eval = sub.add_parser("evaluate", help="poll a checkpoint dir and evaluate")
    _add_fit_args(p_eval)
    p_eval.add_argument("--model-dir", type=str, default="", metavar="N",
                        help="checkpoint dir (defaults to --train-dir)")
    p_eval.add_argument("--poll-interval", type=float, default=10.0)
    p_eval.add_argument("--max-polls", type=int, default=0, help="0 = forever")
    p_eval.add_argument("--stop-when-idle", action="store_true", default=False)
    p_eval.set_defaults(fn=cmd_evaluate)

    p_lm = sub.add_parser(
        "lm",
        help="LM training over any parallelism layout "
             "(dp/sp/tp/ep/pp/tp-sp), compressed dp exchange throughout",
    )
    p_lm.add_argument("--layout", type=str, default="dp",
                      choices=["dp", "dp-sp", "dp-tp", "dp-ep", "dp-pp",
                               "dp-tp-sp"],
                      help="dp-ep is parallel/moe.py's switch top-1 block with "
                           "dropped tokens, another model than --block glm's "
                           "expert layer")
    p_lm.add_argument("--ways", type=int, default=2, metavar="N",
                      help="model-axis size (sp/tp/ep/pp shards; the tp "
                           "size for dp-tp-sp)")
    p_lm.add_argument("--sp-ways", type=int, default=2, metavar="N",
                      help="sp size for --layout dp-tp-sp (sequence shards "
                           "inside each tp group)")
    p_lm.add_argument("--attn-impl", type=str, default="ring",
                      choices=["ring", "ulysses", "ulysses-flash"],
                      help="dp-sp sequence-parallel strategy; ulysses-flash "
                           "uses the fused Pallas local attention")
    p_lm.add_argument("--data-file", type=str, default="",
                      help="byte-level text corpus (raw bytes = tokens, "
                           "needs --vocab-size >= 256); default: synthetic "
                           "deterministic token streams")
    p_lm.add_argument("--vocab-size", type=int, default=256)
    p_lm.add_argument("--seq-len", type=int, default=128)
    p_lm.add_argument("--width", type=int, default=128)
    p_lm.add_argument("--depth", type=int, default=4)
    p_lm.add_argument("--num-heads", type=int, default=4)
    p_lm.add_argument("--block", type=str, default="gpt2",
                      choices=["gpt2", "olmo", "glm", "mellum"],
                      help="the block's recipe (models/transformer.py "
                           "BLOCK_RECIPES): gpt2 = pre-LayerNorm, learned "
                           "positions, GELU MLP; olmo = RMSNorm on each "
                           "sublayer's output, q/k norm, SiLU-gated FFN, no "
                           "positional embedding; glm = pre-RMSNorm, latent "
                           "attention with rotary keys, a dense gated FFN in "
                           "the leading layers and sigmoid-routed experts "
                           "beside a shared one after (models/moe.py), sized "
                           "by the flags from --q-rank to --mtp-weight; mellum "
                           "= pre-RMSNorm, rotary positions in every attention "
                           "layer (--rope-theta, --yarn-*), routed experts "
                           "alone in place of the FFN (--router softmax, "
                           "--routed-experts ... --expert-width, --shared-experts "
                           "0 --dense-layers 0). --layout dp only")
    p_lm.add_argument("--layer-pattern", type=str, default="full",
                      metavar="KIND[,KIND...]",
                      help="mixer of each layer, repeated over --depth: full "
                           "(softmax attention) | window (the same over the last "
                           "--window positions) | linear (gated delta rule, "
                           "models/linear_attention.py), e.g. "
                           "linear,linear,linear,full; mla is --block glm's")
    p_lm.add_argument("--kv-heads", type=int, default=0, metavar="N",
                      help="key/value heads of the full and window layers: "
                           "query head i reads head i // (--num-heads / N) "
                           "(0 = --num-heads)")
    p_lm.add_argument("--head-dim", type=int, default=0, metavar="N",
                      help="size of an attention head (0 = --width / --num-heads)")
    p_lm.add_argument("--window", type=int, default=0, metavar="N",
                      help="a window layer's query sees the N positions up to its own")
    p_lm.add_argument("--router", type=str, default="sigmoid", choices=["sigmoid", "softmax"],
                      help="the routed experts' scores: sigmoid = one a token and "
                           "expert, a selection bias in the choice (--block glm's); "
                           "softmax = over all the router's outputs, the chosen "
                           "renormalised, no bias (--block mellum's)")
    for flag, kind, default, text in (
        ("--yarn-factor", float, 0.0, "YaRN's length factor on the full layers' rotation (0 = no scaling)"),
        ("--yarn-original-len", int, 0, "positions the unscaled frequencies were trained at"),
        ("--yarn-beta-fast", float, 32.0, "turns within that length above which a pair keeps its frequency"),
        ("--yarn-beta-slow", float, 1.0, "turns below which a pair's frequency is divided by the factor"),
        ("--yarn-attention-factor", float, 0.0, "factor on cos and sin (0 = 0.1 ln(factor) + 1)"),
    ):
        p_lm.add_argument(flag, type=kind, default=default, metavar="N",
                          help=f"--block mellum: {text}")
    p_lm.add_argument("--ffn-width", type=int, default=0, metavar="N",
                      help="hidden width of the FFN (0 = 4 x --width)")
    p_lm.add_argument("--linear-key-dim", type=int, default=0, metavar="N",
                      help="per-head key (and query) size of the linear layers")
    p_lm.add_argument("--linear-value-dim", type=int, default=0, metavar="N",
                      help="per-head value size of the linear layers")
    p_lm.add_argument("--linear-conv-width", type=int, default=4, metavar="W",
                      help="taps of the causal depthwise convolution on the "
                           "linear layers' q, k and v")
    p_lm.add_argument("--remat", type=str, default="none",
                      choices=["none", "dots"],
                      help="dots = each block keeps its matmuls against "
                           "weights for the backward pass and rebuilds the "
                           "rest there (less memory, about a forward pass of "
                           "the cheap operations more)")
    for flag, kind, default, text in (
        ("--q-rank", int, 0, "width of the queries' latent"),
        ("--kv-rank", int, 0, "width of the keys' and values' latent"),
        ("--nope-dim", int, 0, "per-head query/key size without position"),
        ("--rope-dim", int, 0, "per-head query/key size that is rotated"),
        ("--value-dim", int, 0, "per-head value size"),
        ("--rope-theta", float, 10000.0, "base of the rotary frequencies"),
        ("--routed-experts", int, 0, "the router's outputs"),
        ("--experts-held", int, 0, "experts this chip holds of them (0 = all)"),
        ("--first-expert", int, 0, "the first expert held"),
        ("--experts-per-token", int, 4, "experts a token is routed to"),
        ("--expert-width", int, 0, "hidden width of one expert"),
        ("--shared-experts", int, 1, "experts every token goes through"),
        ("--dense-layers", int, 1, "leading layers with the dense FFN"),
        ("--route-scale", float, 1.0, "factor on the normalised routing weights"),
        ("--mtp-depth", int, 0, "multi-token-prediction modules (0 | 1)"),
        ("--mtp-weight", float, 0.3, "weight of the prediction module's loss"),
    ):
        p_lm.add_argument(flag, type=kind, default=default, metavar="N",
                          help=f"--block glm (and mellum, its experts and --rope-theta): {text}")
    p_lm.add_argument("--num-experts", type=int, default=8,
                      help="--layout dp-ep: experts of parallel/moe.py's switch "
                           "top-1 layer, another model than --block glm's expert layer")
    p_lm.add_argument("--microbatches", type=int, default=2)
    p_lm.add_argument("--batch-size", type=int, default=8)
    p_lm.add_argument("--max-steps", type=int, default=50)
    p_lm.add_argument("--log-interval", type=int, default=10)
    p_lm.add_argument("--n-devices", type=int, default=0, help="0 = all")
    p_lm.add_argument("--seed", type=int, default=0)
    p_lm.add_argument("--lr", type=float, default=0.1)
    p_lm.add_argument("--momentum", type=float, default=0.9)
    p_lm.add_argument("--nesterov", action="store_true", default=False)
    p_lm.add_argument("--weight-decay", type=float, default=0.0)
    p_lm.add_argument("--lr-shrinkage", type=float, default=1.0)
    p_lm.add_argument("--shrinkage-freq", type=int, default=50)
    p_lm.add_argument("--optimizer", type=str, default="sgd")
    p_lm.add_argument("--code", type=str, default="svd")
    p_lm.add_argument("--bf16", action="store_true", default=False,
                      help="bfloat16 forward/backward, f32 master state")
    p_lm.add_argument("--eval-freq", type=int, default=0,
                      help="validation PPL every N steps on held-out data "
                           "(last 10%% of --data-file chunks, or a fresh "
                           "synthetic stream); 0 = off. Runs the layout's "
                           "single-device oracle forward on the gathered "
                           "params")
    p_lm.add_argument("--train-dir", type=str, default="",
                      help="checkpoint dir (model_step_N naming); empty = "
                           "no checkpoints")
    p_lm.add_argument("--save-freq", type=int, default=0,
                      help="checkpoint every N steps (0 = only at the end)")
    p_lm.add_argument("--resume", action="store_true", default=False,
                      help="resume from the latest checkpoint in --train-dir "
                           "(model-sharded states restore onto their mesh "
                           "shardings)")
    p_lm.add_argument("--compress", action="store_true", default=False,
                      help="lossless-compress checkpoints (C++ native codec)")
    p_lm.add_argument("--svd-rank", type=int, default=0,
                      help="0 (default) = width-scaled auto rank; an "
                           "explicit rank below the width floor warns "
                           "(artifacts/LM_CONVERGENCE.md)")
    p_lm.add_argument("--aggregate", type=str, default="auto",
                      choices=["auto", "gather", "psum", "ring"],
                      help="dp gradient exchange: factor all_gather vs "
                           "dense all-reduce vs streamed ring (the "
                           "compressed stack's DpExchange route); auto "
                           "picks from the comm-cost model and prints why")
    p_lm.add_argument("--ring-bucket-size", type=int, default=0,
                      metavar="B",
                      help="--aggregate ring payload bucket elements "
                           "(0 = unbucketed)")
    p_lm.add_argument("--stream-encode", action="store_true", default=False,
                      help="interleave per-layer encode with the factor "
                           "exchange (gather/ring; the replicated path's "
                           "stream-encode, now on the model-axis layouts)")
    p_lm.add_argument("--stream-bucket-bytes", type=int, default=4 << 20,
                      metavar="B",
                      help="layer-bucket coalescing bound for "
                           "--stream-encode")
    p_lm.add_argument("--overlap", type=str, default="off",
                      choices=["off", "delayed"],
                      help="delayed = stale-by-one overlapped dp exchange "
                           "on the model-axis layouts: each step applies "
                           "the PREVIOUS step's encoded payload, so the "
                           "gather/ring exchange+decode runs underneath "
                           "this step's fwd/bwd (and, on dp-pp, the "
                           "pipeline's drain-tick bubble — "
                           "comm_model.overlap_report's bubble_hidden_ms "
                           "term). Needs a compressing --code and "
                           "--aggregate gather/ring; step 0 skips (carry "
                           "starts empty)")
    p_lm.add_argument("--fabric", type=str, default="auto", metavar="F",
                      help="fabric for --aggregate auto's advisory line: "
                           "auto | ici | dcn | eth10g | a per-chip GB/s "
                           "number")
    p_lm.add_argument("--codec-tax-ms", type=float, default=None,
                      metavar="MS",
                      help="measured single-chip codec tax for --aggregate "
                           "auto (default: size-scaled measured anchor)")
    p_lm.add_argument("--sample", type=str, default="fixed_k",
                      choices=["fixed_k", "bernoulli_budget", "bernoulli",
                               "topk"])
    p_lm.add_argument("--svd-algo", type=str, default="auto",
                      choices=["auto", "exact", "gram", "randomized"])
    p_lm.add_argument("--svd-wire", type=str, default="float32",
                      choices=["float32", "bfloat16"],
                      help="bfloat16 = stochastically-rounded factors on "
                           "the wire (unbiased, ~half the payload bytes)")
    p_lm.add_argument("--quantization-level", type=int, default=2)
    p_lm.add_argument("--bucket-size", type=int, default=512)
    p_lm.add_argument("--profile-dir", type=str, default="",
                      help="capture a jax.profiler device trace of steps "
                           "start+2..start+4 into this dir: what `report "
                           "timeline --profile-dir` reads (the train "
                           "subcommand's flag)")
    p_lm.set_defaults(fn=cmd_lm)

    p_rep = sub.add_parser(
        "report",
        help="join metrics.jsonl + incidents.jsonl + membership.json + "
             "tune_decision.json + fabric_probe.json into run_report.json "
             "and print the post-mortem timeline (cross-artifact "
             "consistency checks); `report timeline` parses a "
             "--profile-dir trace into per-step phase spans instead",
    )
    p_rep.add_argument("what", nargs="?", default="run",
                       choices=["run", "timeline"],
                       help="run (default): the cross-artifact run "
                            "report; timeline: per-step encode/exchange/"
                            "decode/compute spans from a --profile-dir "
                            "trace, joined against metrics.jsonl")
    p_rep.add_argument("--train-dir", type=str, default="output/models/",
                       metavar="N", help="the run's artifact directory")
    p_rep.add_argument("--profile-dir", type=str, default="",
                       metavar="DIR",
                       help="for `report timeline`: the jax profiler "
                            "trace directory a training run captured "
                            "with --profile-dir (default: "
                            "train-dir/trace)")
    p_rep.add_argument("--fleet", action="store_true", default=False,
                       help="build the FLEET report instead: glob every "
                            "per-host lease/metrics/incident stream "
                            "under train-dir/hosts/ plus the shared "
                            "membership.json into one timeline "
                            "(fleet_report.json) with cross-host checks "
                            "(fleet_membership_consistent, "
                            "fleet_lease_gap_explained)")
    p_rep.add_argument("--strict", action="store_true", default=False,
                       help="exit rc=3 when a consistency check fails "
                            "(default: report and exit 0 — the report "
                            "itself is the product)")
    p_rep.set_defaults(fn=cmd_report)

    p_tune = sub.add_parser("tune", help="LR grid search (src/tune.sh parity)")
    _add_fit_args(p_tune)
    p_tune.add_argument("--grid", type=str, default="",
                        help="comma-separated LRs; default 2^-7..2^-1 (tune.sh:7)")
    p_tune.add_argument("--tuning-steps", type=int, default=100,
                        help="steps per LR (tune.sh max_tuning_step)")
    p_tune.add_argument("--window", type=int, default=10,
                        help="final steps averaged for the score")
    p_tune.add_argument("--artifact", type=str, default=None,
                        help="JSON artifact path for the grid results "
                             "(atomic tmp+rename, partial rows survive a "
                             "kill); default train_dir/lr_grid.json, '' "
                             "disables")
    p_tune.set_defaults(fn=cmd_tune)

    return parser


def main(argv=None) -> int:
    from atomo_tpu.utils.compile_cache import enable_compile_cache
    from atomo_tpu.utils.tracing import clear as clear_spans

    clear_spans()  # the ring holds this call's set-up and its loop's iterations
    # jax.config only: nothing ahead of the sub-command body may initialise
    # a backend (a supervising parent must leave the chip to its child).
    # Logged to stderr so verbs with a machine-readable stdout (report
    # --json consumers, shell pipelines) stay clean.
    enable_compile_cache(log_fn=lambda m: print(m, file=sys.stderr, flush=True))
    argv = list(sys.argv[1:] if argv is None else argv)
    known = {"train", "evaluate", "tune", "lm", "report", "-h", "--help"}
    if argv and argv[0] not in known:
        argv = ["train"] + argv  # bare flags behave like the reference CLI
    elif not argv:
        argv = ["train", "--help"]
    parser = build_parser()
    args = parser.parse_args(argv)
    args._argv = argv  # the supervisor re-execs this exact command
    return args.fn(args)


def cli_entry() -> int:
    """Process entry (python -m atomo_tpu / atomo_tpu.cli): every
    message-carrying SystemExit in this CLI is a deterministic config
    reject (preflight and subcommand validation alike), so convert it to
    CONFIG_EXIT_CODE here — a supervising parent, ours or the generic
    scripts/supervise.py, then gives up at once instead of retrying an
    identical failure. In-process callers of :func:`main` (tests) keep
    the raising behavior with the message attached."""
    try:
        return main()
    except SystemExit as exc:
        if isinstance(exc.code, str):
            from atomo_tpu.training.resilience import CONFIG_EXIT_CODE

            print(exc.code, file=sys.stderr, flush=True)
            return CONFIG_EXIT_CODE
        raise


if __name__ == "__main__":
    raise SystemExit(cli_entry())
