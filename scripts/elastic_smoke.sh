#!/usr/bin/env bash
# Elastic-pod smoke (docs/RESILIENCE.md shrink/grow state machine;
# docs/REPLAY_SHARDING.md all-writer slices): drives the CPU-only
# coverage for the N->M replay reshard path and the slice fault drills —
# the digest/quarantine layer in test_chaos.py, the {1,2,4}^2 reshard
# matrix in test_replay_sharding.py, and (with ELASTIC_FULL=1) the slow
# 2-process kill-one -> survivor-shrinks -> rejoin-grows pod drill in
# test_pod.py.
#
# Environment:
#   ELASTIC_FULL=1  also run the slow 2-process shrink/grow drill
#                   (spawns real processes; minutes, not seconds).
set -euo pipefail

repo_root="$(cd "$(dirname "$0")/.." && pwd)"
cd "$repo_root"

echo "elastic_smoke: slice faults + reshard matrix (CPU)"
JAX_PLATFORMS=cpu python -m pytest -q -p no:cacheprovider \
    -m 'not slow' -k 'slice or reshard' \
    tests/test_chaos.py tests/test_replay_sharding.py

if [[ "${ELASTIC_FULL:-0}" == "1" ]]; then
    echo "elastic_smoke: 2-process shrink/grow drill (slow)"
    JAX_PLATFORMS=cpu python -m pytest -q -p no:cacheprovider \
        -m slow -k 'elastic' tests/test_pod.py
fi
echo "elastic_smoke: PASS"
