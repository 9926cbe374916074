#!/bin/bash
# CPU-platform staleness sweep (docs/EVIDENCE.md §4). The TPU sweep
# (staleness_sweep.sh) needs the chip; this variant produces the same
# SCIENTIFIC content — free-running degrades return, which is why
# max_learn_ratio exists — on the 1-core host by slowing env production
# (config.actor_throttle_s) until the learner can saturate the caps.
# Topology matches the §4 table (HalfCheetah-v4, 16 actors, seed 0);
# budget is reduced to 100k env steps so four runs fit in ~2h of 1-core
# wall clock. Records carry platform:"cpu" — these rows are the trend
# evidence, not chip measurements.
set -u
cd "$(dirname "$0")/.."
# The CPU is asked for explicitly: without this the trainer refuses to
# run anywhere but the chip (train.require_platform).
export JAX_PLATFORMS=cpu
COMMON="--backend=jax_tpu --env_id=HalfCheetah-v4 --num_actors=16
        --total_env_steps=100000 --seed=0 --eval_every=20000
        --eval_episodes=3 --watchdog_s=600 --actor_throttle_s=0.25"
FAILED=0
run() { # name, extra flags...
  local name="$1"; shift
  echo "=== staleness sweep (cpu): $name $*"
  rm -f "runs/r4_staleness_cpu_${name}.jsonl"
  local rc=0
  python -m distributed_ddpg_tpu.train $COMMON "$@" \
    --log_path="runs/r4_staleness_cpu_${name}.jsonl" || rc=$?
  if [ "$rc" -ne 0 ]; then
    echo "=== staleness sweep (cpu): $name FAILED (rc=$rc)" >&2
    FAILED=$((FAILED + 1))
  fi
}
run ratio1  --max_learn_ratio=1 --max_ingest_ratio=1
run ratio4  --max_learn_ratio=4
run ratio16 --max_learn_ratio=16
run free
if [ "$FAILED" -gt 0 ]; then
  echo "SWEEP_INCOMPLETE: $FAILED run(s) failed" >&2
  exit 1
fi
echo SWEEP_DONE
