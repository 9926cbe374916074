"""TPU test tier (VERDICT.md round-2 Missing #5 / Next #5): tests that
compile NATIVELY on the attached TPU. Each case runs in a subprocess
(tests/tpu_child.py) because conftest.py pins this process's JAX to the
virtual CPU platform — the very pin that made the round-2 megakernel
failure invisible to the suite — and because a chip belongs to one process
at a time: this parent never touches it, the children take it in turn.

Run on the chip:  python -m pytest tests/test_tpu.py -q
The tier skips only when the environment asked for the CPU
(JAX_PLATFORMS=cpu, as the tier-1 command sets) or TPU_TIER=skip.
Anywhere else a chip that cannot be reached is a FAILURE, not a skip.
"""

import json
import os
import subprocess
import sys

import pytest

# Also `slow`: every case is a subprocess that initialises the chip and
# compiles on it, so the tier-1 selection (-m 'not slow') leaves them out;
# naming this file on the command line (no -m filter) runs them.
pytestmark = [pytest.mark.tpu, pytest.mark.slow]

CHILD = os.path.join(os.path.dirname(__file__), "tpu_child.py")


def _run_child(case: str, timeout: float = 600) -> dict:
    # The child inherits the environment's platform choice (the fixture
    # already skipped if that was the CPU). Surgically remove only the
    # conftest-injected virtual-device token from XLA_FLAGS — any
    # operator-supplied flags must reach the child unchanged.
    env = dict(os.environ)
    if "XLA_FLAGS" in env:
        kept = [
            tok
            for tok in env["XLA_FLAGS"].split()
            if "xla_force_host_platform_device_count" not in tok
        ]
        if kept:
            env["XLA_FLAGS"] = " ".join(kept)
        else:
            del env["XLA_FLAGS"]
    env["JAX_TRACEBACK_FILTERING"] = "off"
    proc = subprocess.run(
        [sys.executable, CHILD, case],
        capture_output=True,
        text=True,
        timeout=timeout,
        env=env,
    )
    if proc.returncode != 0:
        tail = (proc.stderr or proc.stdout or "").strip().splitlines()[-15:]
        raise AssertionError(f"{case} child failed:\n" + "\n".join(tail))
    for line in reversed(proc.stdout.strip().splitlines()):
        if line.startswith("{"):
            return json.loads(line)
    raise AssertionError(f"{case} child printed no JSON: {proc.stdout!r}")


@pytest.fixture(scope="session")
def tpu():
    if os.environ.get("TPU_TIER", "") == "skip":
        pytest.skip("TPU tier bypassed (TPU_TIER=skip)")
    if os.environ.get("JAX_PLATFORMS", "").split(",")[0] == "cpu":
        pytest.skip("the environment asked for the CPU (JAX_PLATFORMS=cpu)")
    # Nobody asked for the CPU, so the chip is expected: a probe that
    # fails, hangs past its bound (90s covers a cold init with margin) or
    # resolves to another platform FAILS the tier.
    probe = _run_child("probe", timeout=90)
    assert probe.get("is_tpu"), f"JAX did not resolve to the TPU: {probe}"
    return probe


def test_fused_kernel_native_parity(tpu):
    """The pallas megakernel must COMPILE under real Mosaic (not interpret
    mode) and match the XLA scan path on the same chunk."""
    out = _run_child("fused_parity")
    assert out["ok"]


def test_fused_kernel_native_parity_c51(tpu):
    """The D4PG (C51) kernel branch — in-kernel categorical projection and
    closed-form cotangents — must compile under real Mosaic and match the
    scan path."""
    out = _run_child("fused_parity_c51")
    assert out["ok"]


def test_fused_kernel_native_parity_bf16(tpu):
    """The bf16 kernel (MXU-rate dots, f32 accumulate) must compile under
    real Mosaic and track the bf16 scan path within rounding."""
    out = _run_child("fused_parity_bf16")
    assert out["ok"]


def test_fused_kernel_native_parity_td3(tpu):
    """The TD3 kernel branch — twin member groups, streamed smoothing
    noise, pl.when-delayed updates — must compile under real Mosaic and
    match the scan path."""
    out = _run_child("fused_parity_td3")
    assert out["ok"]


def test_fused_kernel_native_parity_sac(tpu):
    """The SAC kernel branch — Gaussian-head lane split, streamed sampling
    normals, squash log-prob backward, scalar temperature Adam — must
    compile under real Mosaic and match the scan path."""
    out = _run_child("fused_parity_sac")
    assert out["ok"]


def test_device_replay_ingest_and_sample_chunk(tpu):
    """Real h2d DeviceReplay ingest + the production run_sample_chunk
    dispatch; fused_chunk='auto' must select the megakernel on real TPU
    (otherwise the flagship path is not being tested)."""
    out = _run_child("sample_chunk")
    assert out["ok"]
    assert out["fused_chunk_active"], "megakernel not selected on real TPU"
    # The native capture must carry the ingest breakdown (ROADMAP item:
    # CPU sweeps had it, TPU captures dropped it) — these are the fields
    # BENCH comparisons and tools.runs read.
    assert out["ingest_ship_calls"] >= 1
    assert out["ingest_rows_per_sec"] > 0
