#!/usr/bin/env bash
# Serving-front smoke (docs/SERVING.md 'Network front'): drives the
# CPU-only coverage for serve/front/ — the wire framing + typed error
# contract, per-tenant QoS shed ordering, versioned snapshots with
# canary promote / gated rollback, the SAC serve head's per-client
# sampling parity, and the chaos drills (accept-stall, frame-corrupt,
# canary-regress) — then proves the closed loop by running
# tools.serve_bench --transport socket against a real TCP front. SKIPs
# (exit 0) when the front package is absent, so the gate composes with
# pre-front baselines (the elastic/obs smoke pattern).
#
# Environment:
#   FRONT_FULL=1  also run the slow end-to-end train drill (spawns a
#                 real training run with the front armed).
set -euo pipefail

repo_root="$(cd "$(dirname "$0")/.." && pwd)"
cd "$repo_root"

if [[ ! -f distributed_ddpg_tpu/serve/front/__init__.py ]]; then
    echo "serve_front_smoke: SKIP (serve/front/ absent — pre-front tree)"
    exit 0
fi

echo "serve_front_smoke: network-front unit coverage (CPU)"
JAX_PLATFORMS=cpu python -m pytest -q -p no:cacheprovider \
    -m 'not slow' tests/test_serve_front.py

echo "serve_front_smoke: closed-loop socket bench (1s)"
JAX_PLATFORMS=cpu python -m distributed_ddpg_tpu.tools.serve_bench \
    --transport socket --clients 2 --duration_s 1 --hidden 32,32 \
    | python -c '
import json, sys
d = json.load(sys.stdin)
assert d["served_rps"] > 0, f"socket front served nothing: {d}"
assert d["front_requests"] > 0, f"front_requests missing: {d}"
rps, p95 = d["served_rps"], d["wire_p95_ms"]
print(f"serve_front_smoke: served_rps={rps} wire_p95_ms={p95}")
'

if [[ "${FRONT_FULL:-0}" == "1" ]]; then
    echo "serve_front_smoke: end-to-end train drill (slow)"
    JAX_PLATFORMS=cpu python -m pytest -q -p no:cacheprovider \
        -m slow tests/test_serve_front.py
fi
echo "serve_front_smoke: PASS"
