#!/usr/bin/env bash
# Program-contract gate (docs/ANALYSIS.md "Layer 2"): trace every
# registered hot jitted program (jax.make_jaxpr + .lower(), never a
# compile or execution) and exit 2 on any finding — unaliasable
# donation, collective-order drift vs tests/golden_programs/, beat-group
# divergence, host-callback leak, or a static recompile-hazard. The
# dynamic twin of scripts/lint_gate.sh.
#
# SKIP semantics: a checkout without the program analyzer (old baselines
# the driver replays) exits 0 with a logged SKIP — absence of the
# analyzer must not read as a finding.
#
# Usage:
#   scripts/proganalyze_gate.sh [extra tools.proganalyze args...]
# Environment:
#   PROGRAM_JSON  report JSON path (default:
#                 <repo>/runs/program_findings.json); pretty-print it
#                 with `python -m distributed_ddpg_tpu.tools.runs
#                 programs <file>` on a gate box.
set -euo pipefail

repo_root="$(cd "$(dirname "$0")/.." && pwd)"
json="${PROGRAM_JSON:-$repo_root/runs/program_findings.json}"

if [ ! -f "$repo_root/distributed_ddpg_tpu/analysis/programs.py" ]; then
    echo "proganalyze_gate: SKIP — program analyzer absent (pre-layer-2 baseline)" >&2
    exit 0
fi

cd "$repo_root"
rc=0
python -m distributed_ddpg_tpu.tools.proganalyze --json "$json" "$@" || rc=$?
if [ "$rc" -ne 0 ]; then
    echo "proganalyze_gate: report JSON at $json — render the digest with:" >&2
    echo "  python -m distributed_ddpg_tpu.tools.runs programs $json" >&2
fi
exit "$rc"
