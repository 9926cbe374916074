#!/bin/bash
# Staleness-knob sweep (SURVEY.md §7 hard-part (b); docs/EVIDENCE.md §4):
# HalfCheetah-v4, 16 actors, 300k env steps, seed 0, varying the
# learner-rate cap (grad steps per env step). ratio 1 both sides is the
# reference's sync semantics; 0 is free-running async (the learner runs as
# fast as the device allows). Watchdog on: a wedged device must fail the
# run loudly (exit 70), not eat the sweep.
set -u
cd "$(dirname "$0")/.."
COMMON="--backend=jax_tpu --env_id=HalfCheetah-v4 --num_actors=16
        --total_env_steps=300000 --seed=0 --eval_every=30000
        --eval_episodes=3 --watchdog_s=300"
FAILED=0
run() { # name, extra flags...
  local name="$1"; shift
  echo "=== staleness sweep: $name $*"
  # Fresh artifact per attempt: the metrics sink appends, so a rerun after
  # a failed/partial run would interleave two step sequences in the JSONL
  # that docs/EVIDENCE.md cites.
  rm -f "runs/r3_staleness_${name}.jsonl"
  local rc=0
  python -m distributed_ddpg_tpu.train $COMMON "$@" \
    --log_path="runs/r3_staleness_${name}.jsonl" || rc=$?
  if [ "$rc" -ne 0 ]; then
    echo "=== staleness sweep: $name FAILED (rc=$rc)" >&2
    FAILED=$((FAILED + 1))   # keep sweeping — later points still have value
  fi
}
# Optional row selector ($1): run ONE row, so the sweep can be drained
# as per-row resumable stages (each landed row is durable evidence).
ONLY="${1:-}"
case "$ONLY" in
  ""|ratio1|ratio4|ratio16|free) ;;
  *) echo "unknown sweep row: $ONLY (rows: ratio1 ratio4 ratio16 free)" >&2
     exit 2 ;;  # a typo'd selector must NOT fall through to SWEEP_DONE
esac
want() { [ -z "$ONLY" ] || [ "$ONLY" = "$1" ]; }
want ratio1  && run ratio1  --max_learn_ratio=1 --max_ingest_ratio=1
want ratio4  && run ratio4  --max_learn_ratio=4
want ratio16 && run ratio16 --max_learn_ratio=16
want free    && run free
if [ "$FAILED" -gt 0 ]; then
  echo "SWEEP_INCOMPLETE: $FAILED run(s) failed" >&2
  exit 1
fi
echo SWEEP_DONE
