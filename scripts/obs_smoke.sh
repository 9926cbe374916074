#!/usr/bin/env bash
# Telemetry-plane smoke (docs/OBSERVABILITY.md §4): drives the CPU-only
# coverage for the obs/ subsystem — the health state machine, the
# Prometheus /metrics + /healthz + /trace ingress, straggler detection,
# the clock-aligned merge-trace fuser, and the schema-drift test that
# pins the docs tables to the emitted key set. With OBS_FULL=1 it also
# runs the slow 2-process pod drill: scrape /metrics live, inject a
# faults.py peer loss, assert /healthz flips healthy->degraded on the
# survivor, and validate the merged two-host Perfetto timeline.
#
# Environment:
#   OBS_FULL=1  also run the slow 2-process ingress/peer-loss/merge drill
#               (spawns real processes; minutes, not seconds).
set -euo pipefail

repo_root="$(cd "$(dirname "$0")/.." && pwd)"
cd "$repo_root"

echo "obs_smoke: telemetry plane unit coverage (CPU)"
JAX_PLATFORMS=cpu python -m pytest -q -p no:cacheprovider \
    -m 'not slow' tests/test_obs.py

if [[ "${OBS_FULL:-0}" == "1" ]]; then
    echo "obs_smoke: 2-process ingress + peer-loss + merge-trace drill (slow)"
    JAX_PLATFORMS=cpu python -m pytest -q -p no:cacheprovider \
        -m slow tests/test_obs.py
fi
echo "obs_smoke: PASS"
