#!/usr/bin/env bash
# Pod-supervisor smoke (supervisor/; docs/OPERATIONS.md "Pod supervisor
# runbook"; docs/RESILIENCE.md exit-code matrix): drives the CPU-only
# coverage for the autonomous shrink/grow orchestration — the typed
# exit-code contract, generation classifier, crash-loop breaker, numeric
# refusal, rejoin-prober damping, and the scripted-children full
# shrink -> probe-gated grow -> success cycle in test_supervisor.py,
# plus the pod:<proc>:exit@<beat>:<code> injection grammar in
# test_faults.py. With SUPERVISE_FULL=1 it adds the slow gloo
# acceptance drill: a real 2-process podtrain pod, kill one child ->
# auto-shrink to a degraded singleton -> the prober sees the lost slot
# healthy again -> auto-grow back to 2 -> clean completion, zero
# operator actions (the known gloo SIGABRT infra flake retries inside
# the test, docs/RESILIENCE.md).
#
# Environment:
#   SUPERVISE_FULL=1  also run the slow 2-process supervised drill
#                     (spawns real training processes; minutes).
set -euo pipefail

repo_root="$(cd "$(dirname "$0")/.." && pwd)"
cd "$repo_root"

echo "supervisor_smoke: exit contract + supervisor units (CPU)"
JAX_PLATFORMS=cpu python -m pytest -q -p no:cacheprovider \
    -m 'not slow' tests/test_supervisor.py
JAX_PLATFORMS=cpu python -m pytest -q -p no:cacheprovider \
    -m 'not slow' -k 'exit' tests/test_faults.py

if [[ "${SUPERVISE_FULL:-0}" == "1" ]]; then
    echo "supervisor_smoke: supervised 2-process shrink/grow drill (slow)"
    JAX_PLATFORMS=cpu python -m pytest -q -p no:cacheprovider \
        -m slow tests/test_supervisor.py
fi
echo "supervisor_smoke: PASS"
