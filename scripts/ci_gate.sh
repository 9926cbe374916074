#!/usr/bin/env bash
# CI bench-regression gate (ROADMAP open item; docs/OBSERVABILITY.md §3):
# compare a candidate bench JSON against a baseline — by default the
# newest BENCH_r*.json in the repo root that actually RESOLVES the gate
# keys AND carries no TPU-probe failure (driver rounds whose bench run
# died at the TPU probe leave wrapper JSONs with truncated failure tails
# and, since PR 6, a structured `probe_error` field in the bench object;
# gating against the former would SKIP every key and silently pass any
# regression, and the latter's value is a CPU fallback that would poison
# the baseline — both are skipped with a logged reason) — and exit 2 on
# regression past the threshold, so the driver's round loop can fail
# fast on a perf-regressing change. Exits 1 if no baseline qualifies.
#
# Usage:
#   scripts/ci_gate.sh <candidate.json> [baseline.json]
#   THRESHOLD=0.15 KEYS='value,-t_dispatch_ms' scripts/ci_gate.sh cand.json
#
# Environment:
#   THRESHOLD  allowed relative regression (default 0.10)
#   KEYS       comma-separated gate keys; '-' prefix = lower-is-better
#              (default: value — the headline learner-steps/sec ratio —
#              plus the transfer-scheduler latency pins: ingest_ship_ms
#              and the transfer p95 tails, docs/TRANSFER.md; plus the
#              numerical-health pin -guardrail_rollbacks, which arms once
#              a BENCH_GUARDRAILS=1 bench becomes the baseline — a
#              candidate that skips updates or rolls back where the
#              baseline did not is a correctness regression, not noise;
#              plus the serving latency pins -serve_p95_ms and
#              -serve_queue_depth_p95 (docs/SERVING.md), which SKIP
#              against pre-serve baselines and arm automatically once a
#              BENCH_SERVE=1 bench becomes the baseline — the same
#              arm-on-first-capture pattern as the transfer p95 keys;
#              plus the higher-is-better devactor_rows_per_s throughput
#              pin (docs/DEVICE_ACTORS.md), which SKIPs against
#              pre-devactor baselines and arms once a BENCH_DEVACTOR=1
#              bench becomes the baseline;
#              plus the lower-is-better replay_ingest_bytes_per_row pin
#              (docs/REPLAY_SHARDING.md), which SKIPs against
#              pre-sharded-replay baselines and arms once a
#              BENCH_SHARDED_REPLAY=1 bench becomes the baseline — a
#              candidate whose sharded placement lands MORE bytes per
#              ingested row than the baseline's is a placement
#              regression, not noise;
#              plus the higher-is-better fused_steps_per_s throughput
#              pin (docs/FUSED_BEAT.md), which SKIPs against pre-fused
#              baselines and arms once a BENCH_FUSED=1 bench becomes
#              the baseline — the fused megastep regressing toward the
#              dispatch-per-phase rate is a fusion regression, not noise;
#              plus the higher-is-better superstep_steps_per_s pin
#              (docs/FUSED_BEAT.md §superstep), which SKIPs against
#              pre-superstep baselines and arms once a BENCH_SUPERSTEP=1
#              bench becomes the baseline — the compile-once fori_loop
#              dispatch regressing toward the per-beat dispatch rate is
#              an amortization regression, not noise;
#              plus the tensor-parallel pins (docs/MESH.md): the
#              lower-is-better tp_param_bytes_per_device placement fact
#              (a candidate whose TP placement holds MORE state bytes
#              per device than the baseline's is a rule-table
#              regression) and the higher-is-better tp_steps_per_s rate,
#              both of which SKIP against pre-TP baselines and arm once
#              a BENCH_TP=1 bench becomes the baseline;
#              plus the lower-is-better front_wire_p95_ms network-front
#              pin (docs/SERVING.md 'Network front'), which SKIPs
#              against pre-front baselines and arms once a socket-
#              transport serve bench becomes the baseline — the wire
#              round-trip tail regressing past threshold means the
#              ingress path (framing, QoS admit, version routing) got
#              slower, not the policy math.
#              Keys the BASELINE lacks are SKIPped, so old BENCH_r*.json
#              baselines gate on value alone and the new pins arm
#              automatically once a newer bench becomes the baseline; a
#              key the CANDIDATE drops while the baseline has it FAILS.)
#
# Flags:
#   --lint     run scripts/lint_gate.sh (the invariant lint engine,
#              docs/ANALYSIS.md) as a pre-step before the bench-key
#              comparison: unsuppressed findings exit 2 without touching
#              a single bench JSON. SKIPs (exit 0) when the analysis
#              package is absent — old baselines predate the linter.
#   --programs run scripts/proganalyze_gate.sh (the Layer-2 program-
#              contract analyzer, docs/ANALYSIS.md) as a pre-step:
#              donation-aliasing / collective-order / host-callback
#              findings exit 2 before any bench JSON is read. Same SKIP
#              semantics when analysis/programs.py is absent.
#   --elastic  run scripts/elastic_smoke.sh (the elastic-pod smoke,
#              docs/RESILIENCE.md) as a pre-step: slice digest/quarantine
#              drills and the {1,2,4}^2 N->M replay reshard matrix run on
#              CPU before any bench JSON is read (ELASTIC_FULL=1 adds the
#              slow 2-process shrink/grow drill).
#   --obs      run scripts/obs_smoke.sh (the telemetry-plane smoke,
#              docs/OBSERVABILITY.md §4) as a pre-step: health state
#              machine, /metrics + /healthz + /trace ingress, straggler
#              detection, merge-trace, and the schema-drift pin run on
#              CPU before any bench JSON is read (OBS_FULL=1 adds the
#              slow 2-process scrape/peer-loss/merge drill).
#   --supervise  run scripts/supervisor_smoke.sh (the pod-supervisor
#              smoke, docs/OPERATIONS.md runbook): exit-code contract,
#              breaker/backoff/prober units, and the scripted-children
#              shrink->grow cycle on CPU before any bench JSON is read
#              (SUPERVISE_FULL=1 adds the slow supervised 2-process
#              kill -> auto-shrink -> auto-grow gloo drill).
#   --serve-front  run scripts/serve_front_smoke.sh (the network-front
#              smoke, docs/SERVING.md 'Network front'): wire framing +
#              typed errors, QoS shed ordering, canary promote/rollback,
#              SAC serve-head parity, and a 1s closed-loop socket bench
#              before any bench JSON is read (SKIPs on pre-front trees;
#              FRONT_FULL=1 adds the slow end-to-end train drill). All
#              flags compose: `ci_gate.sh --lint --programs --obs
#              cand.json`.
set -euo pipefail

repo_root="$(cd "$(dirname "$0")/.." && pwd)"
while :; do
    case "${1:-}" in
        --lint) "$repo_root/scripts/lint_gate.sh"; shift ;;
        --programs) "$repo_root/scripts/proganalyze_gate.sh"; shift ;;
        --elastic) "$repo_root/scripts/elastic_smoke.sh"; shift ;;
        --obs) "$repo_root/scripts/obs_smoke.sh"; shift ;;
        --supervise) "$repo_root/scripts/supervisor_smoke.sh"; shift ;;
        --serve-front) "$repo_root/scripts/serve_front_smoke.sh"; shift ;;
        *) break ;;
    esac
done
candidate="${1:?usage: ci_gate.sh [--lint] [--programs] [--elastic] [--obs] [--supervise] [--serve-front] <candidate.json> [baseline.json]}"
baseline="${2:-}"
keys="${KEYS:-value,-ingest_ship_ms,-transfer_ingest_p95,-transfer_prefetch_p95,-transfer_d2h_p95,-guardrail_rollbacks,-serve_p95_ms,-serve_queue_depth_p95,devactor_rows_per_s,-replay_ingest_bytes_per_row,fused_steps_per_s,superstep_steps_per_s,-tp_param_bytes_per_device,tp_steps_per_s,-front_wire_p95_ms}"

# Pick (or validate) the baseline: it must resolve at least one gate key,
# else the gate would be a silent no-op (every key SKIPped = GATE PASS).
baseline="$(
    GATE_KEYS="$keys" GATE_BASELINE="$baseline" \
    python - "$repo_root" <<'PY'
import glob, os, sys

sys.path.insert(0, sys.argv[1])
from distributed_ddpg_tpu.tools.runs import _lookup, load_bench

keys = [k.lstrip("-") for k in os.environ["GATE_KEYS"].split(",") if k]


def usable(path, why=None):
    def skip(reason):
        print(f"ci_gate: skipping {path}: {reason}", file=sys.stderr)
        if why is not None:
            why.append(reason)
        return False

    try:
        obj = load_bench(path)
    except Exception as e:
        return skip(f"unreadable ({e!r})")
    if obj.get("probe_error"):
        # A probe-failure run's numbers are a CPU fallback (bench.py
        # records the failure as this structured field): gating future
        # candidates against it would poison the baseline.
        return skip("TPU-probe failure recorded (probe_error)")
    if not any(
        isinstance(_lookup(obj, k), (int, float))
        and not isinstance(_lookup(obj, k), bool)
        for k in keys
    ):
        # Typically a driver wrapper whose tail is a truncated failure
        # dump instead of a bench object.
        return skip(f"resolves none of the gate keys {keys} (failure tail "
                    "or no bench object)")
    return True


explicit = os.environ["GATE_BASELINE"]
if explicit:
    why = []
    if not usable(explicit, why):
        print(
            f"ci_gate: explicit baseline {explicit} unusable "
            f"({'; '.join(why)}) — the gate would silently pass; refusing",
            file=sys.stderr,
        )
        sys.exit(1)
    print(explicit)
    sys.exit(0)

# BENCH_r<NN>.json: zero-padded rounds, so lexicographic sort is round order.
for path in sorted(glob.glob(os.path.join(sys.argv[1], "BENCH_r*.json")),
                   reverse=True):
    if usable(path):
        print(path)
        sys.exit(0)
print(
    f"ci_gate: no BENCH_r*.json in {sys.argv[1]} qualifies as a baseline "
    f"(gate keys {keys})", file=sys.stderr,
)
sys.exit(1)
PY
)"

echo "ci_gate: baseline=$baseline candidate=$candidate" \
     "threshold=${THRESHOLD:-0.10} keys=$keys"
exec python -m distributed_ddpg_tpu.tools.runs gate \
    "$baseline" "$candidate" \
    --threshold "${THRESHOLD:-0.10}" \
    --keys "$keys"
