#!/usr/bin/env bash
# Invariant lint gate (docs/ANALYSIS.md): run the stdlib-ast rule engine
# over the package and exit 2 on any unsuppressed finding. Pure stdlib
# (no jax import), finishes in < 5 s on any CI box, so it runs before
# anything that compiles.
#
# SKIP semantics: a checkout without the analysis package (old baselines
# the driver replays) exits 0 with a logged SKIP — absence of the linter
# must not read as a finding.
#
# Usage:
#   scripts/lint_gate.sh [extra tools.lint args...]
# Environment:
#   LINT_JSON  findings JSON path (default: <repo>/runs/lint_findings.json);
#              pretty-print it with `python -m distributed_ddpg_tpu.tools.runs
#              lint <file>` on a gate box.
set -euo pipefail

repo_root="$(cd "$(dirname "$0")/.." && pwd)"
json="${LINT_JSON:-$repo_root/runs/lint_findings.json}"

if [ ! -f "$repo_root/distributed_ddpg_tpu/analysis/engine.py" ]; then
    echo "lint_gate: SKIP — analysis package absent (pre-lint baseline)" >&2
    exit 0
fi

cd "$repo_root"
rc=0
python -m distributed_ddpg_tpu.tools.lint --json "$json" "$@" || rc=$?
if [ "$rc" -ne 0 ]; then
    echo "lint_gate: findings JSON at $json — render the digest with:" >&2
    echo "  python -m distributed_ddpg_tpu.tools.runs lint $json" >&2
fi
exit "$rc"
