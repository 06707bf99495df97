#!/usr/bin/env bash
# Bench smoke (~8 min): prove the bench entrypoint still emits parseable
# evidence without burning the full-ladder window. Nineteen checks:
#
#   1. the no-fallback contract: config 7 measures the TPU, so on the
#      CPU backend it must exit NON-zero, say why on stderr, and leave
#      an error row (never a CPU number) on stdout and in the artifact.
#   2. config 8 (ring-vs-gather dispatch micro-compare, forced 4-device
#      CPU mesh) — the last-line JSON contract, the partial-artifact
#      file the row must also land in, per-phase encode/exchange/decode
#      timings present and the aggregation-operator bit-parity contract
#      holding in-row.
#   3. config 9 (overlap-vs-blocking, forced 4-device CPU mesh) — both
#      modes' fenced step times present per codec, the per-phase
#      compute/encode/exchange/decode + hidden/exposed fields present,
#      and the two-program eager-oracle bit parity holds in-row (the
#      speedup itself is timing and may lose to a contended host; the
#      row says so honestly and the smoke does not gate on it).
#   4. the kill contract: SIGKILL a full-ladder run mid-flight; the JSON
#      artifact must still parse with whatever rows completed (rc=124
#      resilience).
#
#   5. the supervisor contract (<60 s, CPU): a crashloop@2 chaos run
#      under --max-restarts 2 must exit 0 on the third attempt and
#      leave a parseable incidents.jsonl (2 crash records + the clean
#      exit) — the PR-5 escalation ladder's run-level rung.
#
#   6. the autopilot contract (<60 s, forced 4-device CPU mesh): a
#      --auto tune run must probe, train, exit 0, and leave a
#      tune_decision.json that parses, names a winner, and records
#      predicted AND measured ms/step for every probed candidate —
#      the PR-7 probe-driven config selection.
#
#   7. the topology contract (<60 s, forced (2x2) CPU mesh): bench
#      config 11 runs planned hierarchical schedules through the probe
#      runner and must exit 0 with the in-row per-plan operator
#      bit-parity assert TRUE, per-tier predicted-vs-measured wire
#      bytes matching, and a probed mini-tune decision naming
#      hierarchical candidates — the PR-8 two-tier plan space.
#
#   8. the elastic contract (<60 s, forced 4-device CPU mesh): a chaos
#      die@3:1 run under --elastic must carry the dead replica masked,
#      then shrink to 3 devices at a checkpoint boundary LIVE — the
#      fleet PR's in-process reshape default: ONE process start to
#      finish, no rc=29 re-exec, no membership_change incident, no
#      restart-budget slot — finish at the same step count as an
#      uninterrupted run, and leave a parseable incidents.jsonl with
#      membership records (reshard="live" on the shrink epoch) plus a
#      membership.json epoch history. (No compile cache here: the
#      re-exec fallback shares cache dirs across different-world
#      children, which corrupted executions on the CPU backend —
#      measured.)
#
#   9. the stream-encode contract (<60 s, forced 4-device CPU mesh):
#      bench config 12 must exit 0 with the per-phase encode
#      exposed-vs-hidden fields present, the streamed exposed-encode
#      tail REDUCED vs --stream-encode off in the same row, and both
#      in-row bit-parity asserts (payloads and step params) TRUE — the
#      PR-10 backward-interleaved layer-streamed encode.
#
#  10. the observability contract (<60 s, forced 4-device CPU mesh): a
#      run with the flight recorder AND the estimator-quality probes
#      armed (--obs-record --obs-quality) must exit 0, leave a
#      metrics.jsonl that parses with per-step records carrying the
#      per-layer quality columns and the aggregate-mode column, and the
#      `report` CLI verb must join metrics + incidents into a
#      run_report.json whose consistency checks all pass — the PR-11
#      flight recorder.
#
#  11. the sparse-exchange contract (<60 s, forced 4-device CPU mesh):
#      bench config 13 runs the per-layer hybrid sparse-row exchange on
#      the power-law embedding workload and must exit 0 with the in-row
#      wire-match gate TRUE (the executed step's msg_bytes equals the
#      plan's per-leaf comm-model sum exactly), the hybrid-vs-all-dense
#      bit-parity assert TRUE, zero row-budget overflow, and a measured
#      wire-bytes reduction > 1 — the PR-12 sparse gradient exchange.
#
#  12. the measured-fabric contract (<60 s, forced 4-device CPU mesh):
#      bench config 14 must leave a complete two-tier fabric_probe.json,
#      record measured-vs-preset ratios per tier, and hold the
#      pricing-only parity gate — the PR-13 fabric observatory.
#
#  13. the sharded-update contract (<60 s, forced 4-device CPU mesh):
#      bench config 15 runs replicated vs zero1 vs sharded-update and
#      must exit 0 with the in-row bit-parity gate TRUE (one trajectory,
#      three partitions), strictly decreasing measured per-chip state
#      bytes, and a recorded memory reduction — the PR-14 mesh
#      subsystem's cross-replica sharded weight update (2004.13336).
#
#  14. the adaptive-budget contract (<60 s, forced 4-device CPU mesh):
#      bench config 16 runs ATOMO's variance-minimizing byte allocation
#      vs the uniform fixed-rank budget on the power-law embedding
#      workload and must exit 0 with the exact wire-match gate TRUE
#      (executed msg_bytes == the allocator's predicted per-leaf sum,
#      variance wire <= uniform wire), the uniform degenerate identity
#      (byte-identical HLO + bit-identical params vs the plain codec),
#      a measured estimator-variance reduction, the seed-ensemble loss
#      Pareto gate, and the bit-exact resume-from-allocation drill —
#      the PR-15 adaptive variance-budget codecs.
#
#  15. the quorum contract (<60 s, forced 4-device CPU mesh): bench
#      config 17 runs bounded-staleness quorum aggregation (Q=3 of 4,
#      K=1) vs blocking under one chaos-slowed replica and must exit 0
#      with the equal-wire gate TRUE (identical msg_bytes — the knob
#      changes when payloads are consumed, never how many bytes move),
#      the recorded arrival schedule replayed to bit-identical params,
#      zero staleness drops, and a measured absorption speedup > 1 —
#      the PR-16 quorum aggregation.
#
#  16. the controller contract (<60 s, forced 4-device CPU mesh): bench
#      config 18 runs the global controller's JOINT priced decision
#      space against each legacy single-decider search standalone
#      (autopilot / budget / hybrid / topology) and must exit 0 with
#      the superset-pricing gate TRUE for all four (the joint ladder's
#      best predicted ms/step <= every restricted subspace's best),
#      the joint winner measured no slower than the best standalone
#      winner, the winner program rebuilt from the on-disk
#      controller_decision.json bit-identical at equal wire vs the
#      same knobs as pinned literals, and the kill->controller_reusable
#      ->rebuild resume drill bit-exact — the PR-17 global controller.
#
#  17. the model-axis wire contract (<60 s, forced 4-device CPU mesh):
#      bench config 19 runs the compressed dp gradient exchange on the
#      dp2 x tp2 TransformerLM layout (the one-mesh-path compile) and
#      must exit 0 with the byte-match gate TRUE (executed per-shard
#      msg_bytes == the per-leaf payload sum priced over the tp-LOCAL
#      shard shapes, to the byte), the scoped DpExchange tail stepping
#      bit-identical to the legacy compressed_dp_update tail (the
#      degenerate-point contract), compressed wire strictly below
#      dense, and the seed-ensemble loss no worse than dense within
#      tolerance — the PR-18 model-axes compile path.
#
#  18. the delayed-overlap contract (<60 s, forced 4-device CPU mesh):
#      bench config 20 runs the stale-by-one compressed dp exchange on
#      the dp2 x pp2 TransformerLM layout and must exit 0 with the
#      off-mode HLO byte-identity gate TRUE (overlap="off" lowers the
#      exact blocking program), the fused delayed program bit-identical
#      (params AND carry payload) to the host-driven produce/apply
#      oracle over the same stale-by-one schedule, delayed msg_bytes
#      equal to blocking msg_bytes (equal wire), and the carry resume
#      drill bit-exact (save -> fresh rebuild -> load -> place -> replay
#      vs the uninterrupted run) — the PR-19 delayed-overlap tentpole.
#
#  19. the fleet contract (<60 s, NO collectives, any backend): two REAL
#      fleet.launcher processes form a fleet over one shared train_dir,
#      partition@ cuts host 1 off the lease store, the leader's
#      transition function shrinks around the stale lease, heal
#      re-admits it (membership epoch 0 -> 1 -> 2, full world back),
#      and `report --fleet --strict` over the resulting per-host
#      artifacts must exit 0 — the fleet-PR host-level control plane,
#      gated on the report's own cross-host consistency checks.
#
# Wired next to scripts/tier1.sh: tier1 proves correctness, this proves
# the bench entrypoint. Usage: scripts/bench_smoke.sh (from anywhere).
cd "$(dirname "$0")/.." || exit 2
set -o pipefail
art=$(mktemp -d)
trap 'rm -rf "$art"' EXIT
# Cache-cold by default, like tier-1 (the CPU backend's persistent-cache
# round-trip is not bit-faithful and the drills assert bit parity). The
# checks that want compile amortization switch JAX's cache back on and
# point JAX_COMPILATION_CACHE_DIR at a throw-away directory — the program
# itself never names a cache dir when that variable is set.
export JAX_ENABLE_COMPILATION_CACHE=false

# --- 1: config 7 without a TPU fails out loud -----------------------------
out=$(timeout -k 5 90 env JAX_PLATFORMS=cpu ATOMO_BENCH_DEADLINE_S=240 \
      ATOMO_BENCH_ARTIFACT="$art/c7.json" \
      python bench.py --config 7 --no-baseline 2>"$art/c7.err")
rc=$?
if [ $rc -eq 0 ] || [ $rc -ge 124 ]; then
  echo "bench_smoke FAIL: config 7 on the CPU backend exited rc=$rc" \
       "(want a plain non-zero: no TPU, no row)"
  exit 1
fi
printf '%s\n' "$out" > "$art/c7.out"
python - "$art/c7.out" "$art/c7.json" "$art/c7.err" <<'EOF'
import json, sys

lines = [l for l in open(sys.argv[1]) if l.strip().startswith("{")]
assert lines, "bench_smoke FAIL: no JSON emitted"
row = json.loads(lines[-1])  # a caller parses the LAST line
assert row["metric"] == "train_loop_superstep_step_time", row
assert row["value"] is None and row["platform"] is None, row
assert row["measurement_valid"] is False, row
assert "measures the TPU" in (row["error"] or ""), row
doc = json.load(open(sys.argv[2]))  # the atomic partial artifact
assert doc["complete"] is True and len(doc["rows"]) == 1, doc
assert doc["rows"][0]["error"] == row["error"]
assert "failed" in open(sys.argv[3]).read()
print("bench_smoke OK[1/19]: config 7 without a TPU exits non-zero with "
      "an error row, no CPU number under the device metric")
EOF
[ $? -ne 0 ] && exit 1

# --- 2: config 8, ring-vs-gather micro-compare ---------------------------
out=$(timeout -k 5 150 env ATOMO_BENCH_FAST=1 ATOMO_BENCH_STEPS=3 \
      ATOMO_BENCH_DEADLINE_S=240 \
      ATOMO_BENCH_ARTIFACT="$art/c8.json" \
      python bench.py --config 8 --no-baseline 2>/dev/null)
rc=$?
if [ $rc -ne 0 ]; then
  echo "bench_smoke FAIL: config 8 exited rc=$rc (timeout or crash)"
  exit 1
fi
printf '%s\n' "$out" > "$art/c8.out"
python - "$art/c8.out" "$art/c8.json" <<'EOF'
import json, sys

lines = [l for l in open(sys.argv[1]) if l.strip().startswith("{")]
assert lines, "bench_smoke FAIL: config 8 emitted no JSON"
row = json.loads(lines[-1])  # a caller parses the LAST line
missing = [k for k in
           ("metric", "value", "unit", "measurement_valid", "platform",
            "error") if k not in row]
assert not missing, f"bench_smoke FAIL: missing keys {missing}: {row}"
assert row["metric"] == "ring_vs_gather_dispatch", row
doc = json.load(open(sys.argv[2]))  # the atomic partial artifact
assert doc["complete"] is True and len(doc["rows"]) == 1, doc
assert doc["rows"][0]["metric"] == row["metric"]
assert row["measurement_valid"], row.get("invalid_reason")
for k in ("encode_ms", "gather_exchange_ms", "gather_decode_ms",
          "ring_exchange_decode_ms", "gather_ms_per_step"):
    assert isinstance(row.get(k), (int, float)), f"missing phase field {k}: {row}"
assert row["aggregation_bit_parity"] is True, row
print(f"bench_smoke OK[2/19]: ring {row['value']} vs gather "
      f"{row['gather_ms_per_step']} ms/step; phases enc={row['encode_ms']} "
      f"gx={row['gather_exchange_ms']} gdec={row['gather_decode_ms']} "
      f"ring_xdec={row['ring_exchange_decode_ms']} ms; bit_parity=True")
EOF
[ $? -ne 0 ] && exit 1

# --- 3: config 9, overlap-vs-blocking contract ---------------------------
out=$(timeout -k 5 360 env ATOMO_BENCH_FAST=1 ATOMO_BENCH_STEPS=4 \
      ATOMO_BENCH_DEADLINE_S=340 \
      ATOMO_BENCH_ARTIFACT="$art/c9.json" \
      python bench.py --config 9 --no-baseline 2>/dev/null)
rc=$?
if [ $rc -ne 0 ]; then
  echo "bench_smoke FAIL: config 9 exited rc=$rc (timeout or crash)"
  exit 1
fi
printf '%s\n' "$out" > "$art/c9.out"
python - "$art/c9.out" <<'EOF'
import json, sys

lines = [l for l in open(sys.argv[1]) if l.strip().startswith("{")]
assert lines, "bench_smoke FAIL: config 9 emitted no JSON"
row = json.loads(lines[-1])
assert row["metric"] == "overlap_vs_blocking", row
# the oracle contract is semantics, not timing: it must hold even on a
# contended host (a failed assert here is a real regression)
assert row.get("overlap_oracle_bit_parity") is True, row
cods = row.get("codecs") or {}
assert "qsgd8" in cods, row
for k in ("blocking_ms_per_step", "delayed_ms_per_step", "overlap_speedup"):
    assert isinstance(cods["qsgd8"].get(k), (int, float)), (k, row)
ph = row.get("phases") or {}
for k in ("compute_ms", "encode_ms", "exchange_ms", "decode_ms",
          "hidden_ms", "exposed_ms"):
    assert isinstance(ph.get(k), (int, float)), (k, row)
win = row.get("overlap_win_codecs")
print(f"bench_smoke OK[3/19]: delayed {cods['qsgd8']['delayed_ms_per_step']} "
      f"vs blocking {cods['qsgd8']['blocking_ms_per_step']} ms/step "
      f"(speedup {cods['qsgd8']['overlap_speedup']}, win_codecs={win}); "
      f"phases comp={ph['compute_ms']} enc={ph['encode_ms']} "
      f"gx={ph['exchange_ms']} dec={ph['decode_ms']} "
      f"hidden={ph['hidden_ms']} exposed={ph['exposed_ms']} ms; "
      f"oracle_bit_parity=True")
EOF
[ $? -ne 0 ] && exit 1

# --- 4: kill mid-ladder, artifact still parses ---------------------------
env JAX_PLATFORMS=cpu ATOMO_BENCH_FAST=1 \
    ATOMO_BENCH_DEADLINE_S=600 ATOMO_BENCH_ARTIFACT="$art/killed.json" \
    python bench.py --all --no-baseline >/dev/null 2>&1 &
pid=$!
# wait for the FIRST atomic write (ladder start) before killing — a fixed
# sleep races bench startup on a loaded host and fails spuriously
for _ in $(seq 1 60); do
  [ -f "$art/killed.json" ] && break
  sleep 1
done
sleep 2  # let it get a little further into the ladder before the kill
kill -9 "$pid" 2>/dev/null
wait "$pid" 2>/dev/null
python - "$art/killed.json" <<'EOF'
import json, sys

doc = json.load(open(sys.argv[1]))  # must parse despite the SIGKILL
assert doc["complete"] is False
assert isinstance(doc["rows"], list)  # completed rows (possibly none yet)
print(f"bench_smoke OK[4/19]: killed ladder left a parseable artifact "
      f"({len(doc['rows'])} completed rows)")
EOF

[ $? -ne 0 ] && exit 1

# --- 5: supervisor crashloop budget drill --------------------------------
sup="$art/sup"
out=$(timeout -k 5 60 env JAX_PLATFORMS=cpu JAX_ENABLE_COMPILATION_CACHE=true JAX_COMPILATION_CACHE_DIR="$art/xla" \
      python -m atomo_tpu.cli train --synthetic --dataset mnist \
      --network lenet --batch-size 8 --max-steps 3 --eval-freq 2 \
      --log-interval 1 --n-devices 1 --train-dir "$sup" \
      --chaos crashloop@2 --max-restarts 2 --restart-backoff 0.05 2>&1)
rc=$?
if [ $rc -ne 0 ]; then
  echo "bench_smoke FAIL: supervisor drill exited rc=$rc"
  printf '%s\n' "$out" | tail -5
  exit 1
fi
python - "$sup/incidents.jsonl" <<'EOF'
import json, sys

recs = [json.loads(l) for l in open(sys.argv[1]) if l.strip()]
causes = [r["cause"] for r in recs]
assert causes == ["crash", "crash", "clean_exit"], causes
assert recs[-1]["action"] == "done" and recs[-1]["attempt"] == 2, recs[-1]
assert all(r["backoff_s"] > 0 for r in recs[:2]), recs
print(f"bench_smoke OK[5/19]: crashloop@2 recovered on attempt 2 under "
      f"budget; incident log parses ({len(recs)} records)")
EOF
[ $? -ne 0 ] && exit 1

# --- 6: autopilot probe ladder + decision artifact -----------------------
tune="$art/tune"
out=$(timeout -k 5 60 env JAX_PLATFORMS=cpu JAX_ENABLE_COMPILATION_CACHE=true JAX_COMPILATION_CACHE_DIR="$art/xla" \
      XLA_FLAGS="--xla_force_host_platform_device_count=4" \
      python -m atomo_tpu.cli train --synthetic --dataset mnist \
      --network lenet --batch-size 8 --max-steps 2 --eval-freq 0 \
      --save-freq 2 --log-interval 1 --n-devices 4 --code qsgd \
      --quantization-level 8 --train-dir "$tune" \
      --auto tune --tune-steps 2 --tune-reps 1 --tune-top 2 2>&1)
rc=$?
if [ $rc -ne 0 ]; then
  echo "bench_smoke FAIL: --auto tune exited rc=$rc"
  printf '%s\n' "$out" | tail -5
  exit 1
fi
python - "$tune/tune_decision.json" <<'EOF'
import json, sys

doc = json.load(open(sys.argv[1]))
assert doc["complete"] is True, doc
win = doc.get("winner") or {}
assert win.get("name") and win.get("knobs"), f"no winner named: {win}"
probed = [r for r in doc["rows"] if r.get("probed")]
assert probed, "no candidate was measured"
for r in probed:
    assert isinstance(r.get("measured_ms_per_step"), (int, float)), r
    assert isinstance(r.get("predicted_ms_per_step"), (int, float)), r
assert doc.get("why"), doc
print(f"bench_smoke OK[6/19]: --auto tune picked {win['name']} "
      f"({win.get('measured_ms_per_step')} ms/step measured, "
      f"{len(probed)}/{len(doc['rows'])} candidates probed); "
      "decision artifact parses")
EOF
[ $? -ne 0 ] && exit 1

# --- 7: config 11, two-tier planned-schedule contract --------------------
out=$(timeout -k 5 150 env ATOMO_BENCH_FAST=1 ATOMO_BENCH_STEPS=3 \
      ATOMO_BENCH_DEADLINE_S=340 \
      ATOMO_BENCH_ARTIFACT="$art/c11.json" \
      python bench.py --config 11 --no-baseline 2>/dev/null)
rc=$?
if [ $rc -ne 0 ]; then
  echo "bench_smoke FAIL: config 11 exited rc=$rc (timeout or crash)"
  exit 1
fi
printf '%s\n' "$out" > "$art/c11.out"
python - "$art/c11.out" <<'EOF'
import json, sys

lines = [l for l in open(sys.argv[1]) if l.strip().startswith("{")]
assert lines, "bench_smoke FAIL: config 11 emitted no JSON"
row = json.loads(lines[-1])
assert row["metric"] == "two_tier_matrix", row
assert row["measurement_valid"], row.get("invalid_reason")
# the planned-schedule semantics contract: every probed plan's operator
# is bit-identical to the canonical decode-order oracle, and the comm
# model's per-tier wire bytes agree with the executed program's own
# byte accounting
assert row["aggregation_bit_parity"] is True, row
plans = row.get("plans") or []
assert plans, row
for p in plans:
    assert p["aggregation_bit_parity"] is True, p
    assert p["tier_bytes_match"] is True, p
    for tier in ("inner", "outer"):
        t = p["tiers"][tier]
        assert isinstance(t.get("predicted_mb"), (int, float)), p
        assert isinstance(t.get("measured_mb"), (int, float)), p
    assert isinstance(p.get("ms_per_step"), (int, float)), p
    assert isinstance(p.get("predicted_ms_per_step"), (int, float)), p
td = row.get("tune_decision") or {}
assert td.get("hierarchical_probed"), row
print(f"bench_smoke OK[7/19]: two-tier plans "
      f"{[p['plan'] for p in plans]} measured with per-tier "
      "predicted-vs-measured bytes matching, per-plan bit_parity=True; "
      f"mini-tune probed {td['hierarchical_probed']} "
      f"(winner {(td.get('winner') or {}).get('name')})")
EOF
[ $? -ne 0 ] && exit 1

# --- 8: elastic shrink-and-continue drill (LIVE reshard default) ---------
# since the fleet PR the default membership boundary is the in-process
# live reshape (params + momentum re-sliced, NO rc=29 re-exec): ONE
# process start to finish, no membership_change incident, reshard="live"
# stamped on the shrink epoch's membership record
el="$art/elastic"
out=$(timeout -k 5 60 env JAX_PLATFORMS=cpu \
      XLA_FLAGS="--xla_force_host_platform_device_count=4" \
      python -m atomo_tpu.cli train --synthetic --dataset mnist \
      --network lenet --batch-size 12 --max-steps 8 --eval-freq 0 \
      --save-freq 2 --log-interval 1 --n-devices 4 --code qsgd \
      --quantization-level 8 --aggregate gather --grad-guard --elastic \
      --elastic-patience 2 --chaos die@3:1 --max-restarts 1 \
      --restart-backoff 0.05 --train-dir "$el" 2>&1)
rc=$?
if [ $rc -ne 0 ]; then
  echo "bench_smoke FAIL: elastic die@3:1 drill exited rc=$rc"
  printf '%s\n' "$out" | tail -5
  exit 1
fi
case "$out" in
  *"Elastic: LIVE shrink 4 -> 3"*) : ;;
  *) echo "bench_smoke FAIL: live shrink log line missing"
     printf '%s\n' "$out" | tail -5; exit 1 ;;
esac
python - "$el" <<'EOF'
import json, os, sys

d = sys.argv[1]
# membership epoch history: 0 (world 4) -> 1 (world 3, member 1 left)
mem = json.load(open(os.path.join(d, "membership.json")))
worlds = [(e["epoch"], e["world_size"], e["reason"]) for e in mem["epochs"]]
assert worlds == [(0, 4, "init"), (1, 3, "shrink")], worlds
assert mem["epochs"][1]["dead"] == [1], mem["epochs"][1]
# incidents.jsonl parses and carries the membership records; the reshape
# was a planned IN-PROCESS transition — no crash, no budget slot burned,
# and no membership_change (that incident belongs to the re-exec
# fallback protocol, which must NOT have run)
recs = [json.loads(l) for l in open(os.path.join(d, "incidents.jsonl"))]
memrec = [r for r in recs if r["cause"] == "membership"]
assert len(memrec) >= 1, recs
assert [r["action"] for r in memrec] == ["begin", "shrink"], memrec
assert memrec[1]["reshard"] == "live", memrec
assert not any(r["cause"] == "membership_change" for r in recs), recs
assert not any(r.get("action") == "reshard_fallback" for r in recs), recs
assert not any(r["cause"] in ("crash", "budget_exhausted") for r in recs), recs
assert recs[-1]["cause"] == "clean_exit", recs
# final step count matches the uninterrupted run (max-steps 8)
sys.path.insert(0, ".")
from atomo_tpu.training.checkpoint import latest_valid_step

assert latest_valid_step(d) == 8, latest_valid_step(d)
print("bench_smoke OK[8/19]: die@3:1 shrank 4 -> 3 LIVE in-process "
      "(no re-exec, restart budget untouched), finished at "
      f"step {latest_valid_step(d)} with membership epochs "
      f"{[w[0] for w in worlds]} recorded")
EOF
[ $? -ne 0 ] && exit 1

# --- 9: config 12, stream-encode exposure contract -----------------------
out=$(timeout -k 5 120 env ATOMO_BENCH_FAST=1 ATOMO_BENCH_STEPS=3 \
      ATOMO_BENCH_DEADLINE_S=110 \
      ATOMO_BENCH_ARTIFACT="$art/c12.json" \
      python bench.py --config 12 --no-baseline 2>/dev/null)
rc=$?
if [ $rc -ne 0 ]; then
  echo "bench_smoke FAIL: config 12 exited rc=$rc (timeout or crash)"
  exit 1
fi
printf '%s\n' "$out" > "$art/c12.out"
python - "$art/c12.out" <<'EOF9'
import json, sys

lines = [l for l in open(sys.argv[1]) if l.strip().startswith("{")]
assert lines, "bench_smoke FAIL: config 12 emitted no JSON"
row = json.loads(lines[-1])
assert row["metric"] == "stream_encode_exposure", row
assert row["measurement_valid"], row.get("invalid_reason")
# the layout-knob contracts are semantics, not timing: they must hold
# even on a contended host
assert row["payload_bit_parity"] is True, row
assert row["step_param_bit_parity"] is True, row
assert row["exposed_encode_reduced"] is True, row
ph = row.get("phases") or {}
for k in ("compute_ms", "encode_monolithic_ms", "encode_streamed_ms",
          "encode_exposed_off_ms", "encode_exposed_stream_ms",
          "encode_hidden_stream_ms"):
    assert isinstance(ph.get(k), (int, float)), (k, row)
assert int(ph.get("n_buckets", 0)) > 1, row
print(f"bench_smoke OK[9/19]: stream {row['value']} vs off "
      f"{row['off_ms_per_step']} ms/step; exposed encode "
      f"{ph['encode_exposed_stream_ms']} (stream, {ph['n_buckets']} "
      f"buckets) vs {ph['encode_exposed_off_ms']} (off) ms; "
      f"payload+param bit_parity=True")
EOF9
[ $? -ne 0 ] && exit 1

# --- 10: flight recorder + quality probes + report verb ------------------
obsd="$art/obs"
out=$(timeout -k 5 60 env JAX_PLATFORMS=cpu JAX_ENABLE_COMPILATION_CACHE=true JAX_COMPILATION_CACHE_DIR="$art/xla" \
      XLA_FLAGS="--xla_force_host_platform_device_count=4" \
      python -m atomo_tpu.cli train --synthetic --dataset mnist \
      --network lenet --batch-size 8 --max-steps 6 --eval-freq 0 \
      --save-freq 2 --log-interval 2 --n-devices 4 --code qsgd \
      --quantization-level 8 --aggregate gather --train-dir "$obsd" \
      --obs-record --obs-quality 2>&1)
rc=$?
if [ $rc -ne 0 ]; then
  echo "bench_smoke FAIL: obs-record run exited rc=$rc"
  printf '%s\n' "$out" | tail -5
  exit 1
fi
rep=$(timeout -k 5 30 env JAX_PLATFORMS=cpu \
      python -m atomo_tpu.cli report --train-dir "$obsd" --strict 2>&1)
rc=$?
if [ $rc -ne 0 ]; then
  echo "bench_smoke FAIL: report verb exited rc=$rc"
  printf '%s\n' "$rep" | tail -8
  exit 1
fi
python - "$obsd" <<'EOF'
import json, os, sys

d = sys.argv[1]
recs = [json.loads(l) for l in open(os.path.join(d, "metrics.jsonl"))]
steps = [r for r in recs if r.get("kind") == "step"]
assert [r["step"] for r in steps] == list(range(1, 7)), steps
for r in steps:
    assert r["aggregate"] == "gather" and r["step_ms"] > 0, r
    assert len(r["q_rel"]) == len(r["q_err2"]) > 0, r
metas = [r for r in recs if r.get("kind") == "meta"]
assert len(metas) == 1 and metas[0]["what"] == "obs_quality", metas
assert len(metas[0]["layers"]) == len(steps[0]["q_rel"]), metas
doc = json.load(open(os.path.join(d, "run_report.json")))
assert doc["consistent"] is True, doc["checks"]
ran = [c["name"] for c in doc["checks"] if not c["skipped"]]
segs = [e for e in doc["timeline"] if e["kind"] == "metrics"]
assert segs and segs[0]["first_step"] == 1 and segs[-1]["last_step"] == 6
print("bench_smoke OK[10/19]: recorder+quality run left "
      f"{len(steps)} step records ({len(steps[0]['q_rel'])}-layer "
      "quality columns), report verb joined a consistent timeline "
      f"(checks ran: {ran})")
EOF
[ $? -ne 0 ] && exit 1

# --- 11: config 13, sparse-vs-dense wire contract ------------------------
out=$(timeout -k 5 120 env ATOMO_BENCH_FAST=1 ATOMO_BENCH_STEPS=3 \
      ATOMO_BENCH_DEADLINE_S=110 \
      ATOMO_BENCH_ARTIFACT="$art/c13.json" \
      python bench.py --config 13 --no-baseline 2>/dev/null)
rc=$?
if [ $rc -ne 0 ]; then
  echo "bench_smoke FAIL: config 13 exited rc=$rc (timeout or crash)"
  exit 1
fi
printf '%s\n' "$out" > "$art/c13.out"
python - "$art/c13.out" <<'EOF11'
import json, sys

lines = [l for l in open(sys.argv[1]) if l.strip().startswith("{")]
assert lines, "bench_smoke FAIL: config 13 emitted no JSON"
row = json.loads(lines[-1])
assert row["metric"] == "sparse_vs_dense_wire", row
assert row["measurement_valid"], row.get("invalid_reason")
# byte-honesty + lossless contracts are semantics, not timing: they
# must hold even on a contended host
assert row["wire_bytes_match"] is True, row
assert row["hybrid_bit_parity"] is True, row
assert row["row_overflow"] == 0, row
assert row["hybrid_wire_bytes"] < row["alldense_wire_bytes"], row
assert row["wire_reduction"] > 1, row
plan = row.get("hybrid_plan") or {}
layers = plan.get("per_layer") or []
assert plan.get("sparse_leaves"), row
for l in layers:
    assert 0.0 <= l["density"] <= 1.0, l
    if l["assignment"] == "sparse":
        assert l["payload_bytes"] < l["dense_bytes"], l
print(f"bench_smoke OK[11/19]: hybrid {row['hybrid_wire_bytes']} B vs "
      f"all-dense {row['alldense_wire_bytes']} B on the wire "
      f"({row['wire_reduction']}x reduction, "
      f"{len(plan['sparse_leaves'])}/{plan['n_leaves']} leaves sparse); "
      f"{row['value']} vs {row['alldense_ms_per_step']} ms/step; "
      "wire_match+bit_parity=True, overflow=0")
EOF11
[ $? -ne 0 ] && exit 1

# --- 12: config 14, fabric probe + measured-fabric parity contract ------
out=$(timeout -k 5 120 env ATOMO_BENCH_FAST=1 ATOMO_BENCH_STEPS=3 \
      ATOMO_BENCH_DEADLINE_S=110 \
      JAX_ENABLE_COMPILATION_CACHE=true JAX_COMPILATION_CACHE_DIR="$art/xla" \
      ATOMO_BENCH_ARTIFACT="$art/c14.json" \
      python bench.py --config 14 --no-baseline 2>/dev/null)
rc=$?
if [ $rc -ne 0 ]; then
  echo "bench_smoke FAIL: config 14 exited rc=$rc (timeout or crash)"
  exit 1
fi
printf '%s\n' "$out" > "$art/c14.out"
python - "$art/c14.out" <<'EOF12'
import json, sys

lines = [l for l in open(sys.argv[1]) if l.strip().startswith("{")]
assert lines, "bench_smoke FAIL: config 14 emitted no JSON"
row = json.loads(lines[-1])
assert row["metric"] == "fabric_probe_calibration", row
assert row["measurement_valid"], row.get("invalid_reason")
probe = row.get("fabric_probe") or {}
assert probe.get("complete") is True, row
tiers = {t["label"]: t for t in probe.get("tiers", [])}
assert set(tiers) == {"ici", "dcn"}, tiers
for t in tiers.values():
    assert t["bandwidth_gbps"] and t["bandwidth_gbps"] > 0, t
    assert isinstance(t["latency_us"], (int, float)), t
ratios = row.get("measured_vs_preset") or {}
assert set(ratios) == {"ici", "dcn"} and all(
    r > 0 for r in ratios.values()
), ratios
# the pricing-only contract is semantics, not timing: it must hold
# even on a contended host
assert row["fabric_parity"] is True, row
assert row["run_artifact_complete"] is True, row
print(f"bench_smoke OK[12/19]: probed ici {tiers['ici']['bandwidth_gbps']} "
      f"/ dcn {tiers['dcn']['bandwidth_gbps']} GB/s/chip "
      f"({tiers['ici']['latency_us']} / {tiers['dcn']['latency_us']} "
      "us/hop); measured-vs-preset ratios recorded; measured-priced vs "
      "preset-priced runs bit-identical (fabric_parity=True)")
EOF12
[ $? -ne 0 ] && exit 1

# --- 13: config 15, sharded-update memory + bit-parity contract ----------
out=$(timeout -k 5 120 env ATOMO_BENCH_FAST=1 ATOMO_BENCH_STEPS=3 \
      ATOMO_BENCH_DEADLINE_S=110 \
      JAX_ENABLE_COMPILATION_CACHE=true JAX_COMPILATION_CACHE_DIR="$art/xla" \
      ATOMO_BENCH_ARTIFACT="$art/c15.json" \
      python bench.py --config 15 --no-baseline 2>/dev/null)
rc=$?
if [ $rc -ne 0 ]; then
  echo "bench_smoke FAIL: config 15 exited rc=$rc (timeout or crash)"
  exit 1
fi
printf '%s\n' "$out" > "$art/c15.out"
python - "$art/c15.out" <<'EOF13'
import json, sys

lines = [l for l in open(sys.argv[1]) if l.strip().startswith("{")]
assert lines, "bench_smoke FAIL: config 15 emitted no JSON"
row = json.loads(lines[-1])
assert row["metric"] == "sharded_update_memory", row
assert row["measurement_valid"], row.get("invalid_reason")
# the in-row bit-parity gate: all three partitions trained the SAME
# trajectory (canonical decode order), so the memory columns describe
# one program family
assert row["bit_parity"] is True, row
rep = row["replicated_state_bytes_per_chip"]
z1 = row["zero1_state_bytes_per_chip"]
shd = row["sharded_update_state_bytes_per_chip"]
# the 2004.13336 memory claim, read off the actual device buffers:
# strictly decreasing per-chip persistent state
assert shd < z1 < rep, (rep, z1, shd)
assert row["state_bytes_reduction"] > 1.5, row
for part in ("replicated", "zero1", "sharded_update"):
    assert row[f"{part}_ms_per_step"] > 0, row
print(f"bench_smoke OK[13/19]: per-chip state {rep} -> {z1} (zero1) -> "
      f"{shd} B (sharded-update, {row['state_bytes_reduction']}x); "
      f"ms/step {row['replicated_ms_per_step']} / "
      f"{row['zero1_ms_per_step']} / {row['sharded_update_ms_per_step']}; "
      "bit_parity=True")
EOF13
[ $? -ne 0 ] && exit 1

# --- 14: config 16, adaptive-budget Pareto + wire-match contract ---------
out=$(timeout -k 5 120 env ATOMO_BENCH_FAST=1 ATOMO_BENCH_STEPS=10 \
      ATOMO_BENCH_DEADLINE_S=110 \
      ATOMO_BENCH_ARTIFACT="$art/c16.json" \
      python bench.py --config 16 --no-baseline 2>/dev/null)
rc=$?
if [ $rc -ne 0 ]; then
  echo "bench_smoke FAIL: config 16 exited rc=$rc (timeout or crash)"
  exit 1
fi
printf '%s\n' "$out" > "$art/c16.out"
python - "$art/c16.out" <<'EOF14'
import json, sys

lines = [l for l in open(sys.argv[1]) if l.strip().startswith("{")]
assert lines, "bench_smoke FAIL: config 16 emitted no JSON"
row = json.loads(lines[-1])
assert row["metric"] == "adaptive_budget_pareto", row
assert row["measurement_valid"], row.get("invalid_reason")
# gate 1: the exact wire match — allocator prediction == executed bytes
assert row["wire_bytes_match"] is True, row
alloc = row["allocation"]
assert alloc["variance_payload_bytes"] <= alloc["uniform_payload_bytes"], alloc
assert alloc["variance_ks"] != alloc["uniform_ks"], alloc
# gate 2: the uniform degenerate identity (--budget-alloc uniform == today)
assert row["uniform_hlo_identical"] is True, row
assert row["uniform_bit_parity"] is True, row
# gate 3: the Pareto — measured estimator variance AND ensemble loss
assert row["measured_variance_reduction"] > 0, row
assert row["pareto_loss_ok"] is True, row
# gate 4: bit-exact resume from the recorded allocation artifact
assert row["resume_bit_exact"] is True, row
print(f"bench_smoke OK[14/19]: variance alloc {alloc['variance_ks']} vs "
      f"uniform {alloc['uniform_ks']} at "
      f"{row['variance_row']['wire_bytes']} <= "
      f"{row['uniform_row']['wire_bytes']} B wire; measured q_err2 "
      f"-{row['measured_variance_reduction']:.1%}, ensemble loss "
      f"{row['variance_row']['mean_loss']:.4f} <= "
      f"{row['uniform_row']['mean_loss']:.4f}; uniform HLO identical; "
      "resume bit-exact")
EOF14
[ $? -ne 0 ] && exit 1

# --- 15: config 17, quorum straggler-absorption contract -----------------
out=$(timeout -k 5 120 env ATOMO_BENCH_FAST=1 ATOMO_BENCH_STEPS=5 \
      ATOMO_BENCH_DEADLINE_S=110 \
      JAX_ENABLE_COMPILATION_CACHE=true JAX_COMPILATION_CACHE_DIR="$art/xla" \
      ATOMO_BENCH_ARTIFACT="$art/c17.json" \
      python bench.py --config 17 --no-baseline 2>/dev/null)
rc=$?
if [ $rc -ne 0 ]; then
  echo "bench_smoke FAIL: config 17 exited rc=$rc (timeout or crash)"
  exit 1
fi
printf '%s\n' "$out" > "$art/c17.out"
python - "$art/c17.out" <<'EOF15'
import json, sys

lines = [l for l in open(sys.argv[1]) if l.strip().startswith("{")]
assert lines, "bench_smoke FAIL: config 17 emitted no JSON"
row = json.loads(lines[-1])
assert row["metric"] == "quorum_straggler_absorption", row
assert row["measurement_valid"], row.get("invalid_reason")
# the equal-wire gate: the quorum knob changes WHEN payloads are
# consumed, never how many bytes move
assert row["equal_wire"] is True, row
# the replay gate is semantics, not timing: a run rebuilt from the
# recorded arrival schedule must land bit-identical params even on a
# contended host
assert row["replay_bit_parity"] is True, row
assert row["schedule_steps_recorded"] > 0, row
# the absorption itself: blocking pays the slow replica's sleep every
# exchange, the quorum step does not (measurement_valid above already
# gates quorum < blocking)
assert row["straggler_absorption_speedup"] > 1, row
assert row["stale_dropped"] == 0, row
print(f"bench_smoke OK[15/19]: quorum {row['value']} vs blocking "
      f"{row['blocking_ms_per_step']} ms/step under one slow@ replica "
      f"({row['straggler_absorption_speedup']}x absorbed) at equal wire "
      f"({row['msg_bytes']} B); {row['schedule_steps_recorded']}-step "
      "arrival schedule replayed bit-exact")
EOF15
[ $? -ne 0 ] && exit 1

# --- 16: config 18, global-controller joint-decision contract ------------
# NOTE: the joint_not_slower gate compares two measured probes under a
# 1.25x noise tolerance — on a contended 1-core box the accumulated load
# of the 15 prior checks can push it over. If ONLY this check fails,
# re-run checks 16-19 in isolation before treating it as a regression.
out=$(timeout -k 5 120 env ATOMO_BENCH_FAST=1 \
      ATOMO_BENCH_DEADLINE_S=110 \
      JAX_ENABLE_COMPILATION_CACHE=true JAX_COMPILATION_CACHE_DIR="$art/xla" \
      ATOMO_BENCH_ARTIFACT="$art/c18.json" \
      python bench.py --config 18 --no-baseline 2>/dev/null)
rc=$?
if [ $rc -ne 0 ]; then
  echo "bench_smoke FAIL: config 18 exited rc=$rc (timeout or crash)"
  exit 1
fi
printf '%s\n' "$out" > "$art/c18.out"
python - "$art/c18.out" <<'EOF16'
import json, sys

lines = [l for l in open(sys.argv[1]) if l.strip().startswith("{")]
assert lines, "bench_smoke FAIL: config 18 emitted no JSON"
row = json.loads(lines[-1])
assert row["metric"] == "controller_joint_decision", row
assert row["measurement_valid"], row.get("invalid_reason")
# superset pricing: the restricted subspaces are subsets of the joint
# space, so the joint ladder can never price worse — per decider
sup = row["superset_pricing"]
assert set(sup) == {"autopilot", "budget", "hybrid", "topology"}, row
assert all(sup.values()), row
# the joint winner is probe-confirmed and no slower than the best
# standalone winner (measurement_valid above already gates the stated
# probe-noise tolerance)
assert row["joint_not_slower"] is True, row
assert row["joint_winner"]["measured_ms_per_step"] is not None, row
# the artifact IS the program: rebuilt from controller_decision.json
# on disk == the same knobs as pinned literals, bit-for-bit at equal
# wire, and the resume drill replays bit-exact
assert row["pin_bit_parity"] is True, row
assert row["pin_equal_wire"] is True, row
assert row["resume_reusable"] is True, row
assert row["resume_bit_parity"] is True, row
print(f"bench_smoke OK[16/19]: controller picked "
      f"{row['joint_winner']['name']} "
      f"({row['value']} ms/step vs best standalone "
      f"{row['best_single_ms_per_step']}); artifact-pin bit-exact at "
      f"equal wire ({row['winner_msg_bytes']} B); resume bit-exact")
EOF16
[ $? -ne 0 ] && exit 1

# --- 17: config 19, model-axis compressed-dp-wire contract ---------------
out=$(timeout -k 5 120 env ATOMO_BENCH_FAST=1 ATOMO_BENCH_STEPS=3 \
      ATOMO_BENCH_DEADLINE_S=110 \
      JAX_ENABLE_COMPILATION_CACHE=true JAX_COMPILATION_CACHE_DIR="$art/xla" \
      ATOMO_BENCH_ARTIFACT="$art/c19.json" \
      python bench.py --config 19 --no-baseline 2>/dev/null)
rc=$?
if [ $rc -ne 0 ]; then
  echo "bench_smoke FAIL: config 19 exited rc=$rc (timeout or crash)"
  exit 1
fi
printf '%s\n' "$out" > "$art/c19.out"
python - "$art/c19.out" <<'EOF17'
import json, sys

lines = [l for l in open(sys.argv[1]) if l.strip().startswith("{")]
assert lines, "bench_smoke FAIL: config 19 emitted no JSON"
row = json.loads(lines[-1])
assert row["metric"] == "lm_compressed_dp_wire", row
assert row["measurement_valid"], row.get("invalid_reason")
# byte honesty: executed per-shard msg_bytes == the per-leaf payload
# sum priced over the tp-LOCAL shard shapes, to the byte
assert row["byte_match"] is True, row
assert row["predicted_msg_bytes"] == row["msg_bytes"], row
# the degenerate-point contract: the scoped full-stack DpExchange tail
# steps bit-identical to the legacy compressed_dp_update tail
assert row["degeneracy_bit_parity"] is True, row
# the headline: compressed dp wire strictly below dense on the tp layout
assert row["byte_reduction"] > 1, row
# and the seed ensemble says the wire saving is not bought with loss
assert row["loss_no_worse"] is True, row
print(f"bench_smoke OK[17/19]: dp2xtp2 LM compressed dp wire "
      f"{row['msg_bytes']} B vs dense {row['dense_bytes']} B "
      f"({row['byte_reduction']}x), predicted == executed to the byte; "
      f"scoped-vs-legacy bit-exact; ensemble loss "
      f"{row['ensemble']['qsgd_mean_loss']} vs dense "
      f"{row['ensemble']['dense_mean_loss']}")
EOF17
[ $? -ne 0 ] && exit 1

# --- 18: config 20, delayed-overlap model-axis contract ------------------
# NO compile cache here: the resume drill compares two executables of
# the SAME HLO (uninterrupted vs restarted rebuild), and this backend's
# persistent-cache round-trip is not bit-faithful (the warm-cache
# parity hazard tests/conftest.py records) — measured as a
# deterministic resume-drill divergence with any cache dir set.
# bench.py switches the cache off for the config-20 child too
# (CONFIGS[20]["no_compile_cache"]), so this is belt and suspenders.
out=$(timeout -k 5 120 env ATOMO_BENCH_FAST=1 ATOMO_BENCH_STEPS=3 \
      ATOMO_BENCH_DEADLINE_S=110 \
      ATOMO_BENCH_ARTIFACT="$art/c20.json" \
      python bench.py --config 20 --no-baseline 2>/dev/null)
rc=$?
if [ $rc -ne 0 ]; then
  echo "bench_smoke FAIL: config 20 exited rc=$rc (timeout or crash)"
  exit 1
fi
printf '%s\n' "$out" > "$art/c20.out"
python - "$art/c20.out" <<'EOF18'
import json, sys

lines = [l for l in open(sys.argv[1]) if l.strip().startswith("{")]
assert lines, "bench_smoke FAIL: config 20 emitted no JSON"
row = json.loads(lines[-1])
assert row["metric"] == "lm_delayed_overlap", row
assert row["measurement_valid"], row.get("invalid_reason")
# the off-mode identity contract: threading the carry costs nothing off
assert row["off_hlo_byte_identical"] is True, row
# the schedule contract: fused delayed == host-driven produce/apply
# oracle, params AND carry payload, bit for bit
assert row["oracle_bit_parity"] is True, row
# equal wire: delayed moves the same payload bytes as blocking
assert row["equal_wire"] is True, row
# the carry is a durable sharded leaf: kill->restart->resume bit-exact
assert row["resume_bit_exact"] is True, row
# the modelled account rides in-row, bubble credit included
assert "bubble_hidden_ms" in row["overlap_model"], row
print(f"bench_smoke OK[18/19]: dp2xpp2 LM delayed overlap "
      f"{row['value']} ms/step vs blocking "
      f"{row['blocking_ms_per_step']} ms/step at equal wire "
      f"({row['msg_bytes']} B); off-HLO identical, oracle + resume "
      f"bit-exact")
EOF18
[ $? -ne 0 ] && exit 1

# --- 19: fleet control plane, 2 REAL processes ---------------------------
# form -> partition@ cuts host 1 off the lease store -> the leader's
# transition function shrinks around the stale lease -> heal re-admits
# (epoch 0 -> 1 -> 2, full world back). No collectives, no coordinator:
# leases over the shared train_dir are the only channel, so this runs on
# ANY backend. The gate is the fleet report's own cross-host checks:
# `report --fleet --strict` must exit 0 (every host's recorded epochs
# consistent with membership.json, every lease gap explained by a
# recorded incident).
fl="$art/fleet"
for i in 0 1; do
  timeout -k 5 60 env JAX_PLATFORMS=cpu \
      python -m atomo_tpu.fleet.launcher --train-dir "$fl" \
      --host-id "$i" --n-hosts 2 --rounds 400 --period 0.05 \
      --patience 4 --stop-epoch 2 --max-seconds 50 \
      --chaos partition@3:0-1:0.8 > "$art/fleet_host$i.out" 2>&1 &
  eval "fpid$i=$!"
done
wait "$fpid0"; rc0=$?
wait "$fpid1"; rc1=$?
if [ $rc0 -ne 0 ] || [ $rc1 -ne 0 ]; then
  echo "bench_smoke FAIL: fleet member exited rc0=$rc0 rc1=$rc1"
  tail -5 "$art/fleet_host0.out" "$art/fleet_host1.out"
  exit 1
fi
rep=$(timeout -k 5 60 env JAX_PLATFORMS=cpu \
      python -m atomo_tpu.cli report --train-dir "$fl" --fleet --strict 2>&1)
rc=$?
if [ $rc -ne 0 ]; then
  echo "bench_smoke FAIL: report --fleet --strict exited rc=$rc"
  printf '%s\n' "$rep" | tail -10
  exit 1
fi
python - "$art/fleet_host0.out" "$art/fleet_host1.out" <<'EOF19'
import json, sys

rs = {}
for path in sys.argv[1:]:
    for line in open(path):
        if line.startswith("RESULT "):
            r = json.loads(line[len("RESULT "):])
            rs[r["host"]] = r
assert sorted(rs) == [0, 1], f"missing RESULT lines: {sorted(rs)}"
for r in rs.values():
    # full cycle: back to membership at full world after shrink + regrow
    assert r["member"] and r["epoch"] == 2 and r["world"] == 2, r
assert rs[0]["roster_hash"] == rs[1]["roster_hash"], rs
assert rs[1]["cut_rounds"] > 0, rs[1]  # the partition really cut it
print("bench_smoke OK[19/19]: 2-process fleet drill "
      "form->partition->shrink->heal->regrow (epoch 0->1->2, "
      f"host 1 cut {rs[1]['cut_rounds']} rounds), "
      "report --fleet --strict rc=0")
EOF19
[ $? -ne 0 ] && exit 1

echo "bench_smoke: all 19 checks passed"
