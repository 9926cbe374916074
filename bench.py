"""Benchmark harness (SURVEY.md §7 step 8; BASELINE.md).

Measures the metric from BASELINE.json:2 — learner grad-steps/sec at
HalfCheetah-v4 scale (obs 17, act 6, 2x256 MLPs, batch 64, 16-actor
data pipeline simulated by a pre-filled replay) — for:

  - baseline: the `--backend native` pure-numpy CPU learner, which IS the
    reference baseline (the reference publishes no numbers, BASELINE.md;
    its learner is CPU TF on the same algorithm/shapes), and
  - jax_tpu: the sharded learner on the attached accelerator(s), fed by the
    device-resident replay (sampling fused into the scanned chunk), with
    actor ingest modeled at the 16-actor MuJoCo rate and INCLUDED in the
    measured loop — the honest end-to-end learner rate, not bare FLOPs.

Prints ONE JSON line:
  {"metric": ..., "value": <jax steps/s>, "unit": "grad_steps/s",
   "vs_baseline": <jax / native>, "mfu": ..., "scaling": {...}, ...}

Robustness (the round-1 failure mode, VERDICT.md Missing #1): every
measurement runs in its OWN subprocess with a hard timeout, so a hung or
Unavailable accelerator backend can neither crash nor stall the harness.
This orchestrator process never imports JAX: a chip belongs to one
process at a time, and a parent that initialised the TPU would make every
phase fail. The backend is probed ONCE up front (BENCH_PROBE_ATTEMPTS
opts back into a retry-with-backoff loop); on probe failure the harness
records the bounded, structured "probe_error" and marks the
accelerator-dependent sections "skipped". There is no fallback: a run
with no accelerator result prints no `value` and exits non-zero. The CPU
is measured only when asked for explicitly (JAX_PLATFORMS=cpu), and the
record then says "platform": "cpu". Probe failures never become `errors`
rows — stacked probe self-dump tails make the artifact useless as a gate
baseline, and ci_gate.sh already skips anything carrying probe_error.

Env overrides: JAX_PLATFORMS / BENCH_PLATFORM set the accelerator phase's
platform (smoke-testing); BENCH_SECONDS scales measurement length;
BENCH_SCALING=0 skips the virtual-device scaling curve; BENCH_CHUNK
overrides the learner chunk length for the accelerator phase;
BENCH_INGEST_ASYNC=0 / BENCH_INGEST_COALESCE=1 fall back to the seed's
serial inline replay ingest for A/B runs (docs/INGEST.md); BENCH_SERVE=1
adds the serve-path measurement (served throughput + p50/p95 with a
per-worker act() A/B at each client count — docs/SERVING.md);
BENCH_DEVACTOR=1 adds the device-actor rollout A/B (on-device vectorized
rollouts vs the host-pool path at equal env count E, rows/s curve over E
— docs/DEVICE_ACTORS.md; BENCH_DEVACTOR_ENVS overrides the E list);
BENCH_SHARDED_REPLAY=1 adds the sharded vs replicated device-replay A/B
(measured ingest bytes/row + per-device storage bytes + chunk rate on the
8 virtual devices — docs/REPLAY_SHARDING.md; BENCH_SHARDED_ROWS overrides
the ingest volume); BENCH_TP=1 adds the tensor-parallel vs replicated
learner A/B at widened hidden dims (per-device param+opt bytes /
model_axis, the docs/MESH.md headline; BENCH_TP_HIDDEN / BENCH_TP_AXES
override the width and the model-axis list); BENCH_FUSED=1 adds the fused-megastep vs
dispatch-per-phase A/B (one jitted beat vs three programs per iteration,
guarded and unguarded, grad-steps/s + rows/s over E —
docs/FUSED_BEAT.md; BENCH_FUSED_ENVS overrides the E list. The legacy
BENCH_FUSED=off value keeps its phase_jax meaning: megakernel disable);
BENCH_SUPERSTEP=1 adds the compile-once multi-beat superstep A/B (one
`lax.fori_loop` dispatch of B fused beats vs B per-beat dispatches at
equal total work, B over BENCH_SUPERSTEP_BEATS, default 1,4,16 — the
per-dispatch host overhead amortized /B is the signal; docs/FUSED_BEAT.md
§superstep. CPU rows are noise-prone and flagged for the native-TPU
verification backlog).
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

import numpy as np

OBS_DIM, ACT_DIM = 17, 6
HIDDEN = (256, 256)
BATCH = 64
NATIVE_STEPS = 400

# Peak bf16/f32 matmul throughput per chip, for the MFU estimate. Keyed by
# substring of jax Device.device_kind (lowercased). Sources: public TPU
# spec sheets; f32 for generations without bf16-only MXU paths is the same
# MXU number. CPU has no meaningful peak -> no MFU reported.
_PEAK_FLOPS = [
    ("v6e", 918e12), ("v6 lite", 918e12),
    ("v5p", 459e12),
    ("v5e", 197e12), ("v5 lite", 197e12), ("v5litepod", 197e12),
    ("v4", 275e12),
    ("v3", 123e12),
    ("v2", 46e12),
]


def flops_per_grad_step(obs: int, act: int, hidden, batch: int) -> float:
    """Analytic matmul FLOPs of one DDPG grad step (models/mlp.py shapes;
    action inserted at critic layer 1). fwd = 2*B*sum(in*out); one grad
    step does: critic TD update (target-actor fwd + target-critic fwd +
    critic fwd + critic bwd ~ 2 fwd) and actor DPG update (actor fwd +
    critic fwd + bwd through both ~ 2 fwd each) => 4*F_actor + 7*F_critic.
    Elementwise (Adam/Polyak/activations) excluded — MXU-irrelevant."""
    h = list(hidden)
    actor_dims = list(zip([obs] + h, h + [act]))
    critic_ins = [obs] + [h[0] + act] + h[1:]
    critic_dims = list(zip(critic_ins, h + [1]))
    f_a = 2.0 * batch * sum(i * o for i, o in actor_dims)
    f_c = 2.0 * batch * sum(i * o for i, o in critic_dims)
    return 4.0 * f_a + 7.0 * f_c


def _peak_flops(device_kind: str):
    kind = device_kind.lower()
    for key, peak in _PEAK_FLOPS:
        if key in kind:
            return peak
    return None


def _config():
    from distributed_ddpg_tpu.config import DDPGConfig

    return DDPGConfig(
        env_id="HalfCheetah-v4",
        actor_hidden=HIDDEN,
        critic_hidden=HIDDEN,
        batch_size=BATCH,
        num_actors=16,
        replay_capacity=200_000,
        # BENCH_GUARDRAILS=1: measure with the numerical-health probe
        # armed (guardrails.py — forces the scan path, so A/B it against
        # a default run to see the probe's cost; the guardrail_* counters
        # then ride the bench JSON and ci_gate.sh's -guardrail_rollbacks
        # key arms against them). Default off: the headline number stays
        # the megakernel path.
        guardrails=os.environ.get("BENCH_GUARDRAILS", "0") == "1",
    )


def _fill_replay(config, n=100_000):
    from distributed_ddpg_tpu.replay import UniformReplay

    replay = UniformReplay(config.replay_capacity, OBS_DIM, ACT_DIM, seed=0)
    rng = np.random.default_rng(0)
    bs = 10_000
    for _ in range(n // bs):
        replay.add_batch(
            rng.standard_normal((bs, OBS_DIM)).astype(np.float32),
            rng.uniform(-1, 1, (bs, ACT_DIM)).astype(np.float32),
            rng.standard_normal(bs).astype(np.float32),
            np.full(bs, 0.99, np.float32),
            rng.standard_normal((bs, OBS_DIM)).astype(np.float32),
        )
    return replay


# --------------------------------------------------------------------------
# Phases. Each runs in its own subprocess (see _run_phase) and prints one
# JSON line as its LAST stdout line.
# --------------------------------------------------------------------------

def _assert_platform() -> None:
    """Phase bootstrap: stack dumps on demand, and the trainer's own
    platform rule — a phase that resolves to the CPU without having been
    asked to (JAX_PLATFORMS=cpu) raises instead of measuring it."""
    from distributed_ddpg_tpu.train import _enable_faulthandler, require_platform

    _enable_faulthandler()
    require_platform()


def phase_native() -> dict:
    """CPU-native numpy learner — the baseline. Runs under JAX_PLATFORMS=cpu
    (set by the orchestrator) so accelerator health is irrelevant here."""
    _assert_platform()
    from distributed_ddpg_tpu.learner import init_train_state
    from distributed_ddpg_tpu.native_backend import NativeLearner

    config = _config()
    replay = _fill_replay(config)
    state = init_train_state(config, OBS_DIM, ACT_DIM, seed=0)
    learner = NativeLearner(config, state, action_scale=1.0)
    for _ in range(20):  # warmup (BLAS thread pools etc.)
        learner.step(replay.sample(BATCH))
    t0 = time.perf_counter()
    for _ in range(NATIVE_STEPS):
        learner.step(replay.sample(BATCH))
    rate = NATIVE_STEPS / (time.perf_counter() - t0)
    return {"native_rate": rate}


def _measure_jax(config, replay, seconds: float, mesh=None, chunk=None) -> dict:
    """Steady-state learner rate on the device-resident replay path
    (replay/device.py): sampling is fused into the scanned chunk, and the
    only h2d traffic is the actor ingest stream, modeled at the 16-actor
    MuJoCo rate (~8k transitions/sec) and INCLUDED in the measured loop.

    chunk=None measures the PRODUCTION steps-per-dispatch — the same
    resolve_learner_chunk value train_jax runs — so the headline number and
    the trainer are the same program (VERDICT.md round-2 Weak #3)."""
    import jax

    from distributed_ddpg_tpu.parallel.learner import (
        ShardedLearner,
        resolve_learner_chunk,
    )
    from distributed_ddpg_tpu.replay.device import DeviceReplay
    from distributed_ddpg_tpu.types import pack_batch_np

    if chunk is None:
        chunk = resolve_learner_chunk(config)
    learner = ShardedLearner(
        config, OBS_DIM, ACT_DIM, action_scale=1.0, chunk_size=chunk, mesh=mesh
    )
    # Production ingest pipeline (docs/INGEST.md + docs/TRANSFER.md):
    # coalesced host-ring staging + the unified transfer scheduler
    # (adaptive coalesce, pooled staging buffers), exactly what train_jax
    # runs. BENCH_INGEST_ASYNC=0 / BENCH_INGEST_COALESCE=1 /
    # BENCH_TRANSFER_SCHED=0 recover the seed's serial inline shipping
    # (or the PR-1 private-shipper pipeline) for A/B measurements.
    sched = None
    if os.environ.get("BENCH_TRANSFER_SCHED", "1") == "1":
        from distributed_ddpg_tpu.transfer import TransferScheduler

        sched = TransferScheduler().start()
    device_replay = DeviceReplay(
        config.replay_capacity, OBS_DIM, ACT_DIM, mesh=learner.mesh,
        block_size=4096,
        async_ship=os.environ.get("BENCH_INGEST_ASYNC", "1") == "1",
        max_coalesce=int(os.environ.get("BENCH_INGEST_COALESCE",
                                        str(config.ingest_coalesce))),
        scheduler=sched,
        adaptive_coalesce=(
            sched is not None and config.ingest_coalesce_adaptive
        ),
        host_pool=sched is not None and config.transfer_host_pool,
    )
    learner.transfer = sched
    # Initial fill mirroring the host replay contents (warm buffer).
    idx = np.arange(len(replay))
    device_replay.add_packed(pack_batch_np(replay.gather(idx)))
    device_replay.drain_pending()  # warm fill fully landed before timing
    device_replay.ingest_snapshot()  # reset: measure only the loop's ingest

    rng = np.random.default_rng(1)
    ingest_rows = rng.standard_normal((4096, device_replay.width)).astype(np.float32)
    actor_rate = 8_000.0  # transitions/sec from 16 MuJoCo actors

    # Warmup: compile + first dispatch.
    out = learner.run_sample_chunk(device_replay)
    _ = float(out.metrics["critic_loss"])  # sync

    # PhaseTimers (metrics.py): same bracket train_jax uses, so bench
    # records carry the identical t_dispatch_ms/t_ingest_ms means PLUS
    # the reservoir tails (p50/p95/max) — an 8-device ingest regression
    # once hid behind a healthy mean.
    from distributed_ddpg_tpu.metrics import PhaseTimers

    phases = PhaseTimers()
    steps = 0
    ingested = 0.0
    dispatches = 0
    t0 = time.perf_counter()
    deadline = t0 + seconds
    while time.perf_counter() < deadline:
        with phases.phase("dispatch"):
            out = learner.run_sample_chunk(device_replay)
        dispatches += 1
        steps += chunk
        # Ship actor blocks at the modeled ingest rate.
        with phases.phase("ingest"):
            due = (time.perf_counter() - t0) * actor_rate
            while ingested + 4096 <= due:
                device_replay.add_packed(ingest_rows)
                ingested += 4096
    _ = float(out.metrics["critic_loss"])  # sync on the last chunk
    elapsed = time.perf_counter() - t0
    rate = steps / elapsed
    ingest = device_replay.ingest_snapshot()
    transfer_fields = {}
    if sched is not None:
        transfer_fields = {
            **sched.snapshot(), **device_replay.transfer_snapshot(),
        }
    phase_fields = phases.snapshot()
    # Numerical health (BENCH_GUARDRAILS=1): the probe's cumulative
    # counters for the measured loop. guardrail_rollbacks is 0 by
    # construction here (bench runs the learner loop, not the repair
    # loop) — its presence arms ci_gate.sh's -guardrail_rollbacks key, so
    # a future bench that DOES skip/roll back fails the gate loudly.
    guard_fields = {}
    if learner.guard_enabled:
        h = learner.poll_health() or {}
        guard_fields = {
            "guardrail_rollbacks": 0,
            "guardrail_skipped_updates": h.get("skipped", 0),
            "guardrail_nonfinite_steps": h.get("nonfinite", 0),
            "guardrail_loss_spikes": h.get("spikes", 0),
        }
    device_replay.close()
    if sched is not None:
        sched.close()

    dev = jax.devices()[0]
    n_dev = learner.mesh.size
    result = {
        "rate": rate,
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "n_devices": n_dev,
        "per_device_rate": rate / n_dev,
        "chunk": chunk,
        "global_batch": learner.global_batch,
        "fused_chunk_active": learner.fused_chunk_active,
        # Per-phase breakdown (SURVEY.md §5): mean + p50/p95/max chunk
        # dispatch(+compute backpressure) time vs actor-ingest h2d time
        # per loop iteration (PhaseTimers reservoir, metrics.py).
        # t_ingest_ms is the CALLER-VISIBLE (learner critical path) cost;
        # the ingest_* fields (metrics.IngestStats) describe what the
        # pipeline did off-path: rows/sec landed, blocks coalesced per
        # device call, producer stall on backpressure, queue depth.
        **phase_fields,
        **ingest,
        # Transfer-scheduler breakdown (docs/TRANSFER.md): per-class
        # dispatches/bytes/tails + the adaptive-coalesce trajectory.
        **transfer_fields,
        # Numerical health (BENCH_GUARDRAILS=1 only).
        **guard_fields,
    }
    peak = _peak_flops(dev.device_kind)
    if peak is not None:
        # FLOPs per grad step scale with the GLOBAL batch (per-device draws
        # under scale_batch_with_data), not the config batch.
        result["mfu"] = rate * flops_per_grad_step(
            OBS_DIM, ACT_DIM, HIDDEN, learner.global_batch
        ) / (peak * n_dev)
    return result


def phase_probe() -> dict:
    """Cheap accelerator-backend health check: initialize the platform and
    run one tiny op. Keeps the expensive bench phase off dead backends."""
    if os.environ.get("BENCH_SELFTEST_HANG") == "1":
        # Diagnostics selftest: wedge before device init so the phase
        # deadline's faulthandler dump fires — verifies a real backend wedge
        # produces a stack in tpu_error instead of a bare "timeout".
        # lint: ok(timeout-discipline): this sleep IS the injected hang —
        # the phase deadline kills it; there is no deadline semantics here
        time.sleep(3600)
    import jax

    _assert_platform()
    import jax.numpy as jnp

    dev = jax.devices()[0]
    val = float(jnp.ones(8).sum())
    return {"platform": dev.platform, "device_kind": dev.device_kind,
            "n_devices": len(jax.devices()), "ok": val == 8.0}


def phase_jax() -> dict:
    """Accelerator (or JAX_PLATFORMS-forced) measurement over the FULL local
    mesh (config data_axis=-1: all attached devices data-parallel). Runs
    the program the learner selects; a failure is this phase's failure."""
    _assert_platform()
    seconds = float(os.environ.get("BENCH_SECONDS", "20"))
    config = _config()
    if os.environ.get("BENCH_FUSED", "") == "off":
        config = config.replace(fused_chunk="off")
    if os.environ.get("BENCH_CHUNK", ""):
        # Chunk-length experiments (per-chunk dispatch overhead amortizes
        # with K): override the resolved learner chunk for this phase only.
        config = config.replace(learner_chunk=int(os.environ["BENCH_CHUNK"]))
    return _measure_jax(config, _fill_replay(config), seconds)


def phase_ingest() -> dict:
    """Fast CPU ingest microbenchmark (tier-1 smoke: tests/
    test_ingest_pipeline.py runs it in-process): a tiny learner + the
    production coalesced/async ingest pipeline on a 1-device mesh, short
    enough for CI but exercising the same _measure_jax path the headline
    and scaling numbers use. Asserting on its JSON keys makes an ingest
    observability regression (or a pipeline exception) a test failure
    instead of a surprise in the next round bench."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    from distributed_ddpg_tpu.parallel import mesh as mesh_lib

    seconds = float(os.environ.get("BENCH_SECONDS", "2"))
    config = _config().replace(
        actor_hidden=(32, 32), critic_hidden=(32, 32),
        replay_capacity=65_536, fused_chunk="off",
    )
    replay = _fill_replay(config, n=20_000)
    mesh = mesh_lib.make_mesh(data_axis=1, devices=jax.devices()[:1])
    r = _measure_jax(config, replay, seconds, mesh=mesh, chunk=8)
    return {
        "ingest_bench": {
            k: r[k]
            for k in (
                "rate", "t_dispatch_ms", "t_dispatch_p95",
                "t_ingest_ms", "t_ingest_p95",
                "ingest_rows_per_sec", "ingest_ship_calls",
                "ingest_coalesce_mean", "ingest_stall_ms",
                "ingest_ship_ms", "ingest_queue_rows",
            )
            if k in r
        },
        # Transfer-scheduler smoke fields (docs/TRANSFER.md): present and
        # self-consistent whenever the scheduler ran (the default).
        "transfer_bench": {
            k: v for k, v in r.items() if k.startswith("transfer_")
        },
    }


def phase_scaling() -> dict:
    """Data-parallel scaling curves on N virtual CPU devices (the multi-chip
    stand-in this 1-chip environment allows). The orchestrator sets
    xla_force_host_platform_device_count=8. Absolute CPU rates are
    meaningless — the curves' SHAPE is the signal. Two curves
    (VERDICT.md round-2 Missing #4 / Weak #7):

      scaled_batch (production default): batch_size is per-device, global
        batch grows with the mesh — aggregate row throughput must grow.
      fixed_global_batch: round-2 semantics (64 rows sliced across N
        devices) — kept to show WHY it regresses (collective latency per
        ever-smaller shard), with the per-phase breakdown to prove it.
    """
    import jax

    jax.config.update("jax_platforms", "cpu")
    from distributed_ddpg_tpu.parallel import mesh as mesh_lib

    seconds = float(os.environ.get("BENCH_SECONDS", "3"))
    replay = _fill_replay(_config(), n=40_000)
    curves = {}
    for label, scaled in (("scaled_batch", True), ("fixed_global_batch", False)):
        config = _config().replace(
            fused_chunk="off", scale_batch_with_data=scaled
        )
        curve = {}
        for n in (1, 2, 4, 8):
            if n > len(jax.devices()):
                break
            mesh = mesh_lib.make_mesh(data_axis=n, devices=jax.devices()[:n])
            r = _measure_jax(config, replay, seconds, mesh=mesh, chunk=100)
            curve[str(n)] = {
                "grad_steps_per_sec": round(r["rate"], 1),
                "global_batch": r["global_batch"],
                "rows_per_sec": round(r["rate"] * r["global_batch"], 1),
                "t_dispatch_ms": r["t_dispatch_ms"],
                # Tails: an 8-device ingest regression was once
                # invisible in these means — p95 puts it in the curve.
                "t_dispatch_p95": r.get("t_dispatch_p95", 0.0),
                "t_ingest_ms": r["t_ingest_ms"],
                "t_ingest_p95": r.get("t_ingest_p95", 0.0),
                "ingest_rows_per_sec": r["ingest_rows_per_sec"],
                "ingest_coalesce_mean": r["ingest_coalesce_mean"],
                "ingest_stall_ms": r["ingest_stall_ms"],
                # Transfer-scheduler tails ride the curve so a per-mesh
                # scheduler regression shows up where that ingest
                # regression once hid.
                "transfer_ingest_p95": r.get("transfer_ingest_p95", 0.0),
                "transfer_coalesce_cap": r.get("transfer_coalesce_cap", 0),
            }
        curves[label] = curve
    return {"scaling_cpu_virtual": curves}


def phase_study() -> dict:
    """Megakernel-vs-scan study (BENCH_STUDY=1): steps/s and MFU at the
    production chunk for batch {64, 256, 1024}, both paths. Justifies the
    production defaults (fused_chunk='auto', chunk 800, batch 64) from
    measurement instead of lore."""
    import jax

    _assert_platform()
    # The platform this phase ACTUALLY measured on (not the orchestrator
    # probe's view).
    measured_platform = jax.devices()[0].platform
    seconds = float(os.environ.get("BENCH_SECONDS", "6"))
    base = _config()
    grid = [
        (f"b{b}_{'fused' if m == 'auto' else 'scan'}",
         base.replace(batch_size=b, fused_chunk=m))
        for b in (64, 256, 1024)
        for m in ("auto", "off")
    ] + [
        # Round-4 kernel envelope extensions at the flagship batch: D4PG
        # (C51 in-kernel) and bf16 (MXU-rate dots) vs their scan paths.
        (f"{tag}_{'fused' if m == 'auto' else 'scan'}",
         base.replace(fused_chunk=m, **kw))
        for tag, kw in (
            ("d4pg", dict(distributional=True, num_atoms=51,
                          v_min=-150.0, v_max=150.0)),
            ("bf16", dict(compute_dtype="bfloat16")),
        )
        for m in ("auto", "off")
    ] + [
        (f"td3_{'fused' if m == 'auto' else 'scan'}",
         base.replace(fused_chunk=m, twin_critic=True,
                      policy_delay=2, target_noise=0.2))
        for m in ("auto", "off")
    ] + [
        (f"sac_{'fused' if m == 'auto' else 'scan'}",
         base.replace(fused_chunk=m, sac=True))
        for m in ("auto", "off")
    ]
    # BENCH_STUDY_FILTER=<prefix>[,<prefix>...] narrows the grid to one
    # or a few fused/scan pairs instead of the 12-point monolith.
    filt = [p for p in os.environ.get("BENCH_STUDY_FILTER", "").split(",") if p]
    if filt:
        grid = [kv for kv in grid if any(kv[0].startswith(p) for p in filt)]
    points = {}
    for key, config in grid:
        # Per-point isolation: one failing point (e.g. the kernel at a
        # batch far outside its tuned envelope) must not discard the
        # rest of the grid.
        try:
            replay = _fill_replay(config, n=40_000)
            r = _measure_jax(config, replay, seconds)
            points[key] = {
                "grad_steps_per_sec": round(r["rate"], 1),
                "fused_chunk_active": r["fused_chunk_active"],
                **({"mfu": round(r["mfu"], 5)} if "mfu" in r else {}),
            }
        except Exception as e:
            points[key] = {"error": repr(e)[:300]}
    return {"study": points, "study_platform": measured_platform}


def phase_serve() -> dict:
    """Serve-path measurement (BENCH_SERVE=1; docs/SERVING.md): served
    throughput + latency tails from the dynamic batcher at the production
    net shapes, with the per-worker local act() A/B at each client count
    — the serving analogue of the virtual-device scaling curves. CPU-only
    (the serving stack's dispatch machinery is host-side either way), so
    it does not depend on the accelerator. The headline serve_p95_ms /
    serve_queue_depth_p95 land at the top level of the bench JSON, arming
    scripts/ci_gate.sh's lower-is-better serve keys once a serve-carrying
    BENCH becomes the baseline."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    from distributed_ddpg_tpu.tools.serve_bench import run_serve_bench

    seconds = float(os.environ.get("BENCH_SECONDS", "2"))
    curve = {}
    for n in (1, 2, 4, 8):
        r = run_serve_bench(
            clients=n, duration_s=seconds, obs_dim=OBS_DIM, act_dim=ACT_DIM,
            hidden=HIDDEN, max_batch=32, max_latency_ms=5.0,
        )
        curve[str(n)] = {
            "served_rps": r["served_rps"],
            "local_act_rps": r["local_act_rps"],      # the A/B row
            "served_vs_local": r.get("served_vs_local", 0.0),
            "serve_p50_ms": r["serve_p50_ms"],
            "serve_p95_ms": r["serve_p95_ms"],
            "serve_fill_mean": r["serve_fill_mean"],
            "serve_queue_depth_p95": r["serve_queue_depth_p95"],
            "client_sheds": r["client_sheds"],
        }
    head = curve[str(max(int(k) for k in curve))]
    return {
        "serve_scaling": curve,
        "serve_rps": head["served_rps"],
        "serve_p50_ms": head["serve_p50_ms"],
        "serve_p95_ms": head["serve_p95_ms"],
        "serve_queue_depth_p95": head["serve_queue_depth_p95"],
    }


def phase_devactor() -> dict:
    """Device-actor vs host-pool rollout A/B (BENCH_DEVACTOR=1;
    docs/DEVICE_ACTORS.md): transition rows/s at equal env count E for

      devactor  — actors/device_pool.py: ONE jitted lax.scan chunk steps E
                  vmapped JaxPendulum envs (policy mu(s) + per-env OU noise
                  on device) and scatters rows into DeviceReplay's HBM
                  ring with a donated insert — zero host bytes per row;
      host      — the host-pool path modeled tightly: numpy policy act
                  over the E-batch (one GEMM — FLATTERING the real pool,
                  which acts per worker at B=1), numpy OU noise, E builtin
                  Pendulum envs stepped in Python, rows packed and shipped
                  host->HBM through add_packed (staging ring + coalesced
                  insert — the real ingest pipeline).

    CPU-only and accelerator-independent. The headline devactor_rows_per_s
    lands at the top level of the bench JSON, arming scripts/ci_gate.sh's
    higher-is-better devactor_rows_per_s key once a BENCH_DEVACTOR=1 bench
    becomes the baseline."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    from distributed_ddpg_tpu.actors.device_pool import DeviceActorPool
    from distributed_ddpg_tpu.actors.policy import (
        NumpyPolicy,
        flatten_params,
        param_layout,
    )
    from distributed_ddpg_tpu.config import DDPGConfig
    from distributed_ddpg_tpu.envs.pendulum import Pendulum
    from distributed_ddpg_tpu.learner import init_train_state
    from distributed_ddpg_tpu.ops.noise import OUNoise
    from distributed_ddpg_tpu.parallel import mesh as mesh_lib
    from distributed_ddpg_tpu.replay.device import DeviceReplay
    from distributed_ddpg_tpu.types import pack_batch_np

    seconds = float(os.environ.get("BENCH_SECONDS", "2"))
    env_counts = [
        int(x)
        for x in os.environ.get("BENCH_DEVACTOR_ENVS", "64,256,1024").split(",")
        if x
    ]
    chunk = int(os.environ.get("BENCH_DEVACTOR_CHUNK", "16"))
    mesh = mesh_lib.make_mesh(
        data_axis=1, model_axis=1, devices=jax.devices()[:1]
    )
    curve = {}
    for E in env_counts:
        cfg = DDPGConfig(
            env_id="Pendulum-v1",
            actor_backend="device",
            num_actors=0,
            device_actor_envs=E,
            device_actor_chunk=chunk,
            actor_hidden=HIDDEN,
            critic_hidden=HIDDEN,
            replay_capacity=max(65_536, 4 * E * chunk),
        )
        pool = DeviceActorPool(cfg, mesh=mesh)
        state = init_train_state(cfg, pool.obs_dim, pool.act_dim, seed=0)
        from jax.sharding import NamedSharding, PartitionSpec as P

        params = jax.device_put(
            state.actor_params,
            jax.tree.map(lambda _: NamedSharding(mesh, P()),
                         state.actor_params),
        )
        pool.set_params(params)
        replay = DeviceReplay(
            cfg.replay_capacity, pool.obs_dim, pool.act_dim, mesh=mesh,
            block_size=1024, async_ship=False,
        )
        pool.run_chunk(replay)  # warmup: rollout + insert compile
        jax.block_until_ready(replay.storage)
        t0 = time.perf_counter()
        rows = 0
        while time.perf_counter() - t0 < seconds:
            rows += pool.run_chunk(replay)
        jax.block_until_ready(replay.storage)  # dispatched != landed
        dev_rate = rows / (time.perf_counter() - t0)

        # Host-pool reference at the same E (docstring: deliberately
        # flattered — batched act, no process/transport overhead).
        layout = param_layout(pool.obs_dim, pool.act_dim, HIDDEN)
        policy = NumpyPolicy(
            layout, pool.action_scale, pool.action_offset
        )
        policy.load_flat(flatten_params(jax.device_get(state.actor_params)))
        envs = [Pendulum(seed=i) for i in range(E)]
        obs = np.stack([e.reset(seed=i)[0] for i, e in enumerate(envs)])
        ou = OUNoise((E, pool.act_dim), cfg.ou_theta, cfg.ou_sigma, seed=1)
        host_replay = DeviceReplay(
            cfg.replay_capacity, pool.obs_dim, pool.act_dim, mesh=mesh,
            block_size=1024, async_ship=False,
        )
        low, high = pool.env.action_low, pool.env.action_high
        t0 = time.perf_counter()
        host_rows = 0
        pend = {k: [] for k in ("obs", "action", "reward", "discount",
                                "next_obs")}
        while time.perf_counter() - t0 < seconds:
            actions = np.clip(
                policy(obs) + ou() * pool.action_scale, low, high
            ).astype(np.float32)
            nxt = np.empty_like(obs)
            rewards = np.empty(E, np.float32)
            for i, e in enumerate(envs):
                o, r, term, trunc, _ = e.step(actions[i])
                rewards[i] = r
                if term or trunc:
                    o, _ = e.reset()
                    ou.state[i] = 0.0
                nxt[i] = o
            pend["obs"].append(obs.copy())
            pend["action"].append(actions)
            pend["reward"].append(rewards)
            pend["discount"].append(np.full(E, cfg.gamma, np.float32))
            pend["next_obs"].append(nxt.copy())
            host_rows += E
            obs = nxt
            if host_rows % (1024 * 4) < E:
                host_replay.add_packed(pack_batch_np(
                    {k: np.concatenate(v) for k, v in pend.items()}
                ))
                pend = {k: [] for k in pend}
        if pend["obs"]:
            host_replay.add_packed(pack_batch_np(
                {k: np.concatenate(v) for k, v in pend.items()}
            ))
        host_replay.drain_pending()
        host_rate = host_rows / (time.perf_counter() - t0)
        replay.close()
        host_replay.close()
        curve[str(E)] = {
            "devactor_rows_per_s": round(dev_rate, 1),
            "host_rows_per_s": round(host_rate, 1),
            "devactor_vs_host": round(dev_rate / max(host_rate, 1e-9), 2),
            "chunk": chunk,
        }
    head = curve[str(max(int(k) for k in curve))]
    return {
        "devactor_scaling": curve,
        "devactor_rows_per_s": head["devactor_rows_per_s"],
        "devactor_host_rows_per_s": head["host_rows_per_s"],
        "devactor_vs_host": head["devactor_vs_host"],
    }


def phase_sharded_replay() -> dict:
    """Sharded vs replicated device-replay A/B (BENCH_SHARDED_REPLAY=1;
    docs/REPLAY_SHARDING.md) on the 8 virtual CPU devices: the same
    ingest stream through both placements, reporting

      replay_ingest_bytes_per_row  MEASURED h2d bytes landed per ingested
                                   row (sum over device copies — the
                                   1/N-ingest claim; lower-is-better
                                   ci_gate key)
      replay_device_storage_bytes  storage bytes ONE device holds (the
                                   N×-aggregate-capacity claim at fixed
                                   per-device HBM)
      grad_steps_per_sec           fused-sampling chunk rate per mode
                                   (the shard-exchange gather's cost,
                                   visible next to the byte win)

    plus the derived replay_capacity_ratio (replicated device bytes /
    sharded device bytes ~= N) and replay_ingest_bytes_ratio at top level.
    Absolute CPU rates are meaningless; the BYTE ratios are the signal —
    they are placement facts, not timing."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    import numpy as np

    from distributed_ddpg_tpu.config import DDPGConfig
    from distributed_ddpg_tpu.parallel import mesh as mesh_lib
    from distributed_ddpg_tpu.parallel.learner import ShardedLearner
    from distributed_ddpg_tpu.replay.device import DeviceReplay
    from distributed_ddpg_tpu.types import pack_batch_np

    seconds = float(os.environ.get("BENCH_SECONDS", "2"))
    mesh = mesh_lib.make_mesh(-1, 1)
    n_dev = mesh.shape["data"]
    rows_total = int(os.environ.get("BENCH_SHARDED_ROWS", "32768"))
    capacity = max(65_536, rows_total)
    cfg = DDPGConfig(
        actor_hidden=(32, 32), critic_hidden=(32, 32), batch_size=64,
        fused_chunk="off", replay_capacity=capacity,
    )
    rng = np.random.default_rng(0)
    block = pack_batch_np({
        "obs": rng.standard_normal((4096, OBS_DIM)).astype(np.float32),
        "action": rng.uniform(-1, 1, (4096, ACT_DIM)).astype(np.float32),
        "reward": rng.standard_normal(4096).astype(np.float32),
        "discount": np.full(4096, 0.99, np.float32),
        "next_obs": rng.standard_normal((4096, OBS_DIM)).astype(np.float32),
        "weight": np.ones(4096, np.float32),
    })
    modes = {}
    for mode in ("replicated", "sharded"):
        replay = DeviceReplay(
            capacity, OBS_DIM, ACT_DIM, mesh=mesh, block_size=1024,
            async_ship=False, replay_sharding=mode,
        )
        t0 = time.perf_counter()
        shipped = 0
        while shipped < rows_total:
            replay.add_packed(block)
            shipped += len(block)
        replay.drain_pending()
        ingest_s = time.perf_counter() - t0
        snap = replay.ingest_snapshot()
        lrn = ShardedLearner(
            cfg.replace(replay_sharding=mode), OBS_DIM, ACT_DIM,
            action_scale=1.0, mesh=mesh, chunk_size=32,
            replay_sharding=mode,
        )
        lrn.run_sample_chunk(replay)  # compile
        t0 = time.perf_counter()
        steps = 0
        while time.perf_counter() - t0 < seconds:
            out = lrn.run_sample_chunk(replay)
            steps += 32
        jax.block_until_ready(out.td_errors)
        rate = steps / (time.perf_counter() - t0)
        modes[mode] = {
            "replay_ingest_bytes_per_row": snap["replay_ingest_bytes_per_row"],
            "replay_device_storage_bytes": snap["replay_device_storage_bytes"],
            "replay_shard_count": snap["replay_shard_count"],
            "replay_shard_fill_min": snap["replay_shard_fill_min"],
            "replay_shard_fill_max": snap["replay_shard_fill_max"],
            "replay_exchange_ms_p95": snap["replay_exchange_ms_p95"],
            "ingest_rows_per_s": round(shipped / ingest_s, 1),
            "grad_steps_per_sec": round(rate, 1),
        }
        replay.close()
    repl, shard = modes["replicated"], modes["sharded"]
    return {
        "sharded_replay": {**modes, "n_devices": n_dev},
        # Top-level gate keys (scripts/ci_gate.sh): the sharded placement's
        # measured bytes/row (lower-is-better) and the capacity ratio.
        "replay_ingest_bytes_per_row": shard["replay_ingest_bytes_per_row"],
        "replay_ingest_bytes_ratio": round(
            repl["replay_ingest_bytes_per_row"]
            / max(shard["replay_ingest_bytes_per_row"], 1e-9), 2
        ),
        "replay_capacity_ratio": round(
            repl["replay_device_storage_bytes"]
            / max(shard["replay_device_storage_bytes"], 1), 2
        ),
    }


def phase_tp() -> dict:
    """Tensor-parallel vs replicated learner A/B (BENCH_TP=1;
    docs/MESH.md) on the 8 virtual CPU devices at WIDENED hidden dims
    (BENCH_TP_HIDDEN, default 1024 — the seed's 256-wide MLPs are too
    small for TP to matter; the wide nets model the distributional value
    heads / pixel encoders the 2D mesh exists for). Per model_axis in
    BENCH_TP_AXES (default 1,2):

      tp_param_bytes_per_device  MEASURED TrainState bytes (params +
                                 targets + both Adam states) resident on
                                 ONE device — the HBM headline, expected
                                 ~/model_axis for rule-sharded layers
                                 (lower-is-better ci_gate key at the
                                 largest axis)
      tp_steps_per_s             fused-sampling chunk rate (higher-is-
                                 better ci_gate key; CPU rates are load-
                                 noisy — the BYTES ratio is the placement
                                 fact, the rate key catches collapses)

    plus tp_param_bytes_ratio (replicated device bytes / TP device
    bytes) and a tp_parity_max_abs_diff pin: the TP arm's end state vs
    the model_axis=1 oracle after identical chunks (same data axis, same
    draws — the tests/test_partition.py contract re-measured at width).
    Global batch is held fixed (scale_batch_with_data=False) so both
    arms do identical algorithmic work."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    import numpy as np

    from distributed_ddpg_tpu.config import DDPGConfig
    from distributed_ddpg_tpu.parallel import mesh as mesh_lib
    from distributed_ddpg_tpu.parallel.learner import ShardedLearner
    from distributed_ddpg_tpu.replay.device import DeviceReplay
    from distributed_ddpg_tpu.types import pack_batch_np

    seconds = float(os.environ.get("BENCH_SECONDS", "2"))
    hidden = int(os.environ.get("BENCH_TP_HIDDEN", "1024"))
    axes = [
        int(x) for x in os.environ.get("BENCH_TP_AXES", "1,2").split(",")
        if x
    ]
    batch = int(os.environ.get("BENCH_TP_BATCH", "64"))
    chunk = int(os.environ.get("BENCH_TP_CHUNK", "8"))
    # Fixed data axis = the smallest the axis list allows, so every arm
    # draws the identical sample stream (the placement-invariant PRNG,
    # parallel/mesh.py) and end states are comparable.
    n_dev = len(jax.devices())
    data_axis = n_dev // max(axes)
    rng = np.random.default_rng(0)
    rows = pack_batch_np({
        "obs": rng.standard_normal((4096, OBS_DIM)).astype(np.float32),
        "action": rng.uniform(-1, 1, (4096, ACT_DIM)).astype(np.float32),
        "reward": rng.standard_normal(4096).astype(np.float32),
        "discount": np.full(4096, 0.99, np.float32),
        "next_obs": rng.standard_normal((4096, OBS_DIM)).astype(np.float32),
        "weight": np.ones(4096, np.float32),
    })

    def device_bytes(state) -> int:
        dev = jax.devices()[0]
        total = 0
        for leaf in jax.tree.leaves(state):
            for s in leaf.addressable_shards:
                if s.device == dev:
                    total += s.data.nbytes
        return total

    curve = {}
    states = {}
    for m in axes:
        cfg = DDPGConfig(
            actor_hidden=(hidden, hidden), critic_hidden=(hidden, hidden),
            batch_size=batch, model_axis=m, fused_chunk="off",
            scale_batch_with_data=False, replay_capacity=8192,
        )
        mesh = mesh_lib.make_mesh(
            data_axis, m, devices=jax.devices()[: data_axis * m]
        )
        lrn = ShardedLearner(
            cfg, OBS_DIM, ACT_DIM, action_scale=1.0, mesh=mesh,
            chunk_size=chunk,
        )
        replay = DeviceReplay(
            8192, OBS_DIM, ACT_DIM, mesh=mesh, block_size=1024,
            async_ship=False,
        )
        replay.add_packed(rows)
        replay.drain_pending()
        lrn.run_sample_chunk(replay)  # compile + 1 parity chunk
        out = lrn.run_sample_chunk(replay)  # parity chunk 2
        jax.block_until_ready(out.td_errors)
        states[m] = jax.device_get(lrn.state)
        t0 = time.perf_counter()
        steps = 0
        while time.perf_counter() - t0 < seconds:
            out = lrn.run_sample_chunk(replay)
            steps += chunk
        jax.block_until_ready(out.td_errors)
        rate = steps / (time.perf_counter() - t0)
        curve[str(m)] = {
            "tp_param_bytes_per_device": device_bytes(lrn.state),
            "tp_steps_per_s": round(rate, 1),
        }
        replay.close()
    head = max(axes)
    tp_bytes = curve[str(head)]["tp_param_bytes_per_device"]
    result = {
        "tp": {**curve, "hidden": hidden, "data_axis": data_axis,
               "n_devices": n_dev},
        # Top-level gate keys (scripts/ci_gate.sh): per-device state
        # bytes at the largest TP degree (lower-is-better) and its chunk
        # rate (higher-is-better).
        "tp_param_bytes_per_device": tp_bytes,
        "tp_steps_per_s": curve[str(head)]["tp_steps_per_s"],
    }
    if "1" in curve and head != 1:
        # The replicated/TP ratio and the oracle parity exist ONLY when
        # the model_axis=1 arm actually ran (BENCH_TP_AXES includes 1):
        # a fallback denominator would report ratio 1.0 — 'TP buys
        # nothing' — and an unmeasured parity would read as bit-exact.
        result["tp_param_bytes_ratio"] = round(
            curve["1"]["tp_param_bytes_per_device"] / max(tp_bytes, 1), 2
        )
    if 1 in states and head != 1:
        # Present ONLY when the model_axis=1 oracle arm actually ran
        # (BENCH_TP_AXES includes 1): an unmeasured parity must be
        # absent, not a 0.0 that reads as bit-exact.
        result["tp_parity_max_abs_diff"] = max(
            float(np.max(np.abs(np.asarray(a) - np.asarray(b))))
            for a, b in zip(
                jax.tree.leaves(states[1]), jax.tree.leaves(states[head])
            )
        )
    return result


def phase_fused() -> dict:
    """Fused-megastep vs dispatch-per-phase A/B (BENCH_FUSED=1;
    docs/FUSED_BEAT.md): grad-steps/s and rollout rows/s at equal E and
    equal per-iteration work (K learner steps + K_env * E rows) for

      fused     — parallel/megastep.py: rollout + ring scatter + sample +
                  K learner updates as ONE jitted donated-carry program
                  per beat (zero host round-trips inside the beat);
      dispatch  — the current loop body: learner sample-chunk dispatch,
                  param pointer swap, standalone rollout dispatch,
                  donated insert — three device programs + host Python
                  between them.

    Both arms run guarded (the PR-7 probe threaded through) and
    unguarded, so the bench pins BOTH acceptance claims: fused >=
    dispatch-per-phase at equal E/K, and guarded fused within ~10% of
    unguarded fused. CPU-only and accelerator-independent; nets kept small so
    per-dispatch host overhead (what fusing removes) is visible next to
    compute, but the batch kept at 256 (BENCH_FUSED_BATCH): the probe's
    per-step cost is O(params) (tree-select + finite checks) while the
    step itself is O(params x batch), so a tiny-batch CPU microbench is
    probe-dominated in a way no production chunk is (measured: guarded/
    unguarded 0.72 at batch 64 vs 0.98 at batch 256 on this box). The
    headline fused_steps_per_s lands at the top level, arming
    scripts/ci_gate.sh's higher-is-better fused key once a BENCH_FUSED=1
    bench becomes the baseline."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    from distributed_ddpg_tpu.actors.device_pool import DeviceActorPool
    from distributed_ddpg_tpu.config import DDPGConfig
    from distributed_ddpg_tpu.parallel import mesh as mesh_lib
    from distributed_ddpg_tpu.parallel.learner import ShardedLearner
    from distributed_ddpg_tpu.parallel.megastep import FusedMegastep
    from distributed_ddpg_tpu.replay.device import DeviceReplay

    seconds = float(os.environ.get("BENCH_SECONDS", "2"))
    env_counts = [
        int(x)
        for x in os.environ.get("BENCH_FUSED_ENVS", "64,256,1024").split(",")
        if x
    ]
    k_env = int(os.environ.get("BENCH_FUSED_CHUNK", "4"))
    # k_learn=4 keeps the per-iteration dispatch overhead (what fusing
    # removes) a visible fraction of the beat on CPU; production chunks
    # amortize further (resolve_learner_chunk), which only shrinks the
    # unfused arm's advantage-free overhead — the A/B is conservative.
    k_learn = int(os.environ.get("BENCH_FUSED_LEARN", "4"))
    batch = int(os.environ.get("BENCH_FUSED_BATCH", "256"))
    mesh = mesh_lib.make_mesh(
        data_axis=1, model_axis=1, devices=jax.devices()[:1]
    )

    def build(cfg):
        pool = DeviceActorPool(cfg, mesh=mesh)
        learner = ShardedLearner(
            cfg, pool.obs_dim, pool.act_dim, pool.action_scale,
            action_offset=pool.action_offset, mesh=mesh,
            chunk_size=k_learn,
        )
        replay = DeviceReplay(
            cfg.replay_capacity, pool.obs_dim, pool.act_dim, mesh=mesh,
            block_size=1024, async_ship=False,
        )
        pool.set_params(learner.state.actor_params)
        while len(replay) < cfg.batch_size:
            pool.run_chunk(replay)
        return learner, pool, replay

    def window(step_fn, window_s):
        t0 = time.perf_counter()
        iters = 0
        while time.perf_counter() - t0 < window_s:
            out = step_fn()
            iters += 1
        jax.block_until_ready(out.td_errors)
        return iters * k_learn / (time.perf_counter() - t0)

    curve = {}
    for E in env_counts:
        row = {"k_env": k_env, "k_learn": k_learn}
        # ALL FOUR arms (fused/dispatch x unguarded/guarded) are built and
        # compiled up front, then measured in ROUND-ROBIN best-of-N
        # windows. Sequential per-arm measurement hands whichever arm drew
        # the quiet/warm slice a phantom win — observed 1.6x swings
        # BETWEEN identical reruns on an idle box when the guarded arms
        # ran minutes after the unguarded ones (allocator/cache state
        # drifts across the intervening builds and compiles). Interleaving
        # puts every arm under the same machine state within each round;
        # the max over rounds then approximates the steady rate for all
        # four — the tails-over-means discipline ci_gate uses.
        arms = {}
        for guard in (False, True):
            tag = "guarded" if guard else "unguarded"
            cfg = DDPGConfig(
                env_id="Pendulum-v1",
                actor_backend="device",
                num_actors=0,
                device_actor_envs=E,
                device_actor_chunk=k_env,
                learner_chunk=k_learn,
                actor_hidden=(64, 64),
                critic_hidden=(64, 64),
                batch_size=batch,
                replay_capacity=max(65_536, 4 * E * k_env),
                guardrails=guard,
                fused_chunk="off",
                fused_beat="on",
            )
            learner_f, pool_f, replay_f = build(cfg)
            ms = FusedMegastep(cfg, learner_f, pool_f, replay_f)
            ms.run_beat()  # compile
            jax.block_until_ready(replay_f.storage)

            learner_d, pool_d, replay_d = build(cfg)

            def disp_iter(L=learner_d, pool=pool_d, replay=replay_d):
                out = L.run_sample_chunk(replay)
                pool.set_params(L.state.actor_params)
                pool.run_chunk(replay)
                return out

            disp_iter()  # compile
            jax.block_until_ready(replay_d.storage)
            arms[(tag, "fused")] = (ms.run_beat, replay_f)
            arms[(tag, "dispatch")] = (disp_iter, replay_d)

        repeats = int(os.environ.get("BENCH_FUSED_REPEATS", "3"))
        window_s = max(seconds / repeats, 0.5)
        rates = {k: 0.0 for k in arms}
        for _ in range(repeats):
            for k, (step_fn, _replay) in arms.items():
                rates[k] = max(rates[k], window(step_fn, window_s))
        for _step_fn, replay in arms.values():
            replay.close()
        for tag in ("unguarded", "guarded"):
            fused_rate = rates[(tag, "fused")]
            disp_rate = rates[(tag, "dispatch")]
            row[tag] = {
                "fused_steps_per_s": round(fused_rate, 1),
                "dispatch_steps_per_s": round(disp_rate, 1),
                "fused_vs_dispatch": round(
                    fused_rate / max(disp_rate, 1e-9), 3
                ),
                "fused_rows_per_s": round(
                    fused_rate / k_learn * k_env * E, 1
                ),
            }
        row["guarded_vs_unguarded"] = round(
            row["guarded"]["fused_steps_per_s"]
            / max(row["unguarded"]["fused_steps_per_s"], 1e-9), 3
        )
        curve[str(E)] = row
    head = curve[str(max(int(k) for k in curve))]
    return {
        "fused_ab": curve,
        # Top-level gate key (scripts/ci_gate.sh): headline fused
        # grad-steps/s at the largest E, unguarded.
        "fused_steps_per_s": head["unguarded"]["fused_steps_per_s"],
        "fused_vs_dispatch": head["unguarded"]["fused_vs_dispatch"],
        "fused_guarded_ratio": head["guarded_vs_unguarded"],
    }


def phase_superstep() -> dict:
    """Compile-once multi-beat superstep A/B (BENCH_SUPERSTEP=1;
    docs/FUSED_BEAT.md §superstep): grad-steps/s at equal total work for
    B in BENCH_SUPERSTEP_BEATS (default 1,4,16), where

      B=1  — parallel/megastep.py run_beat: one dispatch per fused beat
             (today's steady-loop behavior, the oracle arm);
      B>1  — parallel/superstep.py run_superstep: B beats inside ONE
             donated-carry `lax.fori_loop` dispatch, stats stacked into
             a device-side carry, one host sync per superstep.

    What the superstep removes is per-dispatch host work (program launch,
    donation bookkeeping, the Python between beats), so the signal is
    dispatch_ms_per_beat falling ~/B while steps/s holds or rises. All
    arms are built and compiled up front and measured in ROUND-ROBIN
    best-of-N windows (same discipline as phase_fused: sequential
    per-arm measurement hands the warm slice a phantom win). CPU NOISE
    CAVEAT: on a CPU backend the per-beat compute is small enough that
    scheduler jitter can dominate the dispatch-overhead delta — the
    emitted rows carry a note flagging the measurement for the
    native-TPU verification backlog (ROADMAP), where per-dispatch
    overhead is both larger in absolute terms and stable. The headline
    superstep_steps_per_s (largest B, uniform, unguarded) lands at the
    top level, arming scripts/ci_gate.sh's higher-is-better superstep
    key once a BENCH_SUPERSTEP=1 bench becomes the baseline."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    from distributed_ddpg_tpu.actors.device_pool import DeviceActorPool
    from distributed_ddpg_tpu.config import DDPGConfig
    from distributed_ddpg_tpu.parallel import mesh as mesh_lib
    from distributed_ddpg_tpu.parallel.learner import ShardedLearner
    from distributed_ddpg_tpu.parallel.megastep import FusedMegastep
    from distributed_ddpg_tpu.parallel.superstep import FusedSuperstep
    from distributed_ddpg_tpu.replay.device import DeviceReplay

    seconds = float(os.environ.get("BENCH_SECONDS", "2"))
    beats_list = [
        int(x)
        for x in os.environ.get("BENCH_SUPERSTEP_BEATS", "1,4,16").split(",")
        if x
    ]
    E = int(os.environ.get("BENCH_SUPERSTEP_ENVS", "256"))
    k_env = int(os.environ.get("BENCH_SUPERSTEP_CHUNK", "4"))
    # k_learn=4 keeps per-dispatch host overhead (what the superstep
    # amortizes) a visible fraction of the beat on CPU (phase_fused's
    # rationale) — production chunks amortize further, so the A/B is
    # conservative.
    k_learn = int(os.environ.get("BENCH_SUPERSTEP_LEARN", "4"))
    batch = int(os.environ.get("BENCH_SUPERSTEP_BATCH", "256"))
    mesh = mesh_lib.make_mesh(
        data_axis=1, model_axis=1, devices=jax.devices()[:1]
    )

    def build(B):
        cfg = DDPGConfig(
            env_id="Pendulum-v1",
            actor_backend="device",
            num_actors=0,
            device_actor_envs=E,
            device_actor_chunk=k_env,
            learner_chunk=k_learn,
            actor_hidden=(64, 64),
            critic_hidden=(64, 64),
            batch_size=batch,
            # One B=16 superstep inserts 16*E*k_env rows; capacity must
            # dwarf a single dispatch so the ring isn't lapped mid-loop.
            replay_capacity=max(65_536, 8 * E * k_env * max(beats_list)),
            fused_chunk="off",
            fused_beat="on",
            superstep_beats=B,
        )
        pool = DeviceActorPool(cfg, mesh=mesh)
        learner = ShardedLearner(
            cfg, pool.obs_dim, pool.act_dim, pool.action_scale,
            action_offset=pool.action_offset, mesh=mesh,
            chunk_size=k_learn,
        )
        replay = DeviceReplay(
            cfg.replay_capacity, pool.obs_dim, pool.act_dim, mesh=mesh,
            block_size=1024, async_ship=False,
        )
        pool.set_params(learner.state.actor_params)
        while len(replay) < cfg.batch_size:
            pool.run_chunk(replay)
        if B == 1:
            step = FusedMegastep(cfg, learner, pool, replay)
            step_fn = step.run_beat
        else:
            step = FusedSuperstep(cfg, learner, pool, replay)
            step_fn = step.run_superstep
        step_fn()  # compile
        jax.block_until_ready(replay.storage)
        return step_fn, replay

    def window(step_fn, window_s, steps_per_call):
        t0 = time.perf_counter()
        iters = 0
        while time.perf_counter() - t0 < window_s:
            out = step_fn()
            iters += 1
        jax.block_until_ready(out.td_errors)
        dt = time.perf_counter() - t0
        return iters * steps_per_call / dt, 1000.0 * dt / iters

    arms = {B: build(B) for B in beats_list}
    repeats = int(os.environ.get("BENCH_SUPERSTEP_REPEATS", "3"))
    window_s = max(seconds / repeats, 0.5)
    rates = {B: 0.0 for B in arms}
    dispatch_ms = {B: float("inf") for B in arms}
    for _ in range(repeats):
        for B, (step_fn, _replay) in arms.items():
            rate, d_ms = window(step_fn, window_s, B * k_learn)
            rates[B] = max(rates[B], rate)
            dispatch_ms[B] = min(dispatch_ms[B], d_ms)
    for _step_fn, replay in arms.values():
        replay.close()

    curve = {}
    for B in beats_list:
        curve[str(B)] = {
            "superstep_beats": B,
            "steps_per_s": round(rates[B], 1),
            "rows_per_s": round(rates[B] / k_learn * k_env * E, 1),
            "dispatch_ms": round(dispatch_ms[B], 3),
            # The amortization headline: host+launch cost per fused beat.
            "dispatch_ms_per_beat": round(dispatch_ms[B] / B, 3),
        }
    b_lo, b_hi = min(beats_list), max(beats_list)
    return {
        "superstep_ab": curve,
        "superstep_steps_per_s": curve[str(b_hi)]["steps_per_s"],
        "superstep_vs_beat": round(
            rates[b_hi] / max(rates[b_lo], 1e-9), 3
        ),
        "superstep_note": (
            "CPU microbench: dispatch-overhead delta is noise-prone at "
            "this compute scale; flagged for native-TPU verification "
            "(ROADMAP backlog) where per-dispatch overhead dominates"
        ),
    }


_PHASES = {
    "native": phase_native,
    "probe": phase_probe,
    "jax": phase_jax,
    "ingest": phase_ingest,
    "scaling": phase_scaling,
    "study": phase_study,
    "serve": phase_serve,
    "devactor": phase_devactor,
    "sharded_replay": phase_sharded_replay,
    "fused": phase_fused,
    "superstep": phase_superstep,
    "tp": phase_tp,
}


# --------------------------------------------------------------------------
# Orchestrator
# --------------------------------------------------------------------------

def _run_phase(name: str, env_overrides: dict, timeout: float):
    """Run one phase in a subprocess; return (result_dict, None) or
    (None, error_string). Subprocess isolation means a wedged accelerator
    runtime is bounded by `timeout` instead of hanging the harness."""
    env = dict(os.environ)
    # Unfiltered tracebacks so a captured phase error names the actual
    # failing op/spec instead of JAX's "internal frames removed" stub
    # (ADVICE.md round 2).
    env.setdefault("JAX_TRACEBACK_FILTERING", "off")
    # Child arms faulthandler.dump_traceback_later just inside this deadline
    # (see main's --phase entry), so a wedged phase self-dumps every thread's
    # stack to stderr and exits BEFORE the parent's kill — the recorded
    # error then names the wedged call (backend init? compile? d2h?) instead of a
    # bare "timeout after Ns" (VERDICT.md r3 Weak #8).
    env["BENCH_PHASE_TIMEOUT"] = str(timeout)
    env.update({k: str(v) for k, v in env_overrides.items()})
    try:
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--phase", name],
            capture_output=True, text=True, timeout=timeout, env=env,
        )
    except subprocess.TimeoutExpired:
        return None, f"{name}: timeout after {timeout:.0f}s (no self-dump)"
    if proc.returncode != 0:
        text = (proc.stderr or proc.stdout or "").strip()
        lines = text.splitlines()
        if "Timeout (0:" in text or "Thread 0x" in text:
            # Self-dump fired: keep enough of the dump to see the wedged
            # frame on every thread (bounded so tpu_error stays readable).
            tail = " | ".join(lines[-25:])[-2500:]
        else:
            tail = " | ".join(lines[-3:])
        return None, f"{name}: rc={proc.returncode}: " + tail
    for line in reversed(proc.stdout.strip().splitlines()):
        line = line.strip()
        if line.startswith("{"):
            try:
                return json.loads(line), None
            except json.JSONDecodeError:
                continue
    return None, f"{name}: no JSON line in output"


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--phase", choices=sorted(_PHASES))
    args = parser.parse_args()

    if args.phase:
        deadline = float(os.environ.get("BENCH_PHASE_TIMEOUT", "0"))
        if deadline > 15:
            # Self-dump shortly before the parent would SIGKILL us, so
            # stderr carries all thread stacks (exit=True makes this an
            # _exit — a wedged PJRT call can't block teardown). The margin
            # scales: a flat -10s on a small deadline would kill a healthy
            # slow phase at a fraction of its granted time.
            import faulthandler

            faulthandler.dump_traceback_later(
                max(deadline - 10.0, 0.8 * deadline), exit=True
            )
        print(json.dumps(_PHASES[args.phase]()), flush=True)
        return 0

    result = {
        "metric": "learner_grad_steps_per_sec (HalfCheetah-v4 scale, "
        "2x256 MLPs, batch 64, replay-fed)",
        "unit": "grad_steps/s",
    }
    errors = []

    def note(msg):
        # Progress to stderr so an outer timeout that kills us mid-run
        # still leaves a trail of which phase we were in.
        print(f"[bench {time.strftime('%H:%M:%S')}] {msg}",
              file=sys.stderr, flush=True)

    # Accelerator first, the CPU-native baseline after. Honor an explicit
    # platform override; otherwise let the default platform resolve inside
    # the subprocess (where the trainer's rule refuses a CPU nobody asked
    # for — train.require_platform).
    accel_env = {}
    forced = os.environ.get("JAX_PLATFORMS") or os.environ.get("BENCH_PLATFORM")
    if forced:
        accel_env["JAX_PLATFORMS"] = forced
    # Probe the backend cheaply before committing to the expensive bench
    # run; a wedged TPU runtime then costs one short probe, not a full
    # bench timeout. 90s covers a cold init+compile with margin.
    accel = None
    probe = None
    # Accelerator-path errors are tracked separately from the shared
    # errors list so result["tpu_error"] can never pick up a later
    # CPU-native phase failure (the native phase now runs in between).
    accel_errors = []
    # Sections not run because the accelerator was unreachable are
    # recorded here as "skipped" markers, NOT error rows (probe_error
    # carries the one bounded failure record; ci_gate.sh keys off it).
    skipped = {}
    probe_timeout = float(os.environ.get("BENCH_PROBE_TIMEOUT", "90"))
    # Probe ONCE by default: against a wedged backend each attempt burns
    # the full probe timeout and self-dumps a full traceback.
    # BENCH_PROBE_ATTEMPTS=3 restores the retry behavior for
    # known-transient sites.
    probe_attempts = max(1, int(os.environ.get("BENCH_PROBE_ATTEMPTS", "1")))
    # BENCH_STUDY_ONLY=1 (with BENCH_STUDY=1): probe, then go STRAIGHT to
    # the study phase — no headline jax capture, no native baseline.
    study_only = (
        os.environ.get("BENCH_STUDY", "0") == "1"
        and os.environ.get("BENCH_STUDY_ONLY", "0") == "1"
    )
    for attempt in range(probe_attempts):
        note(f"probe attempt {attempt + 1} (timeout {probe_timeout:.0f}s)")
        probe, err = _run_phase("probe", accel_env, timeout=probe_timeout)
        if probe and probe.get("ok"):
            note(
                f"probe ok: {probe.get('platform')} "
                f"{probe.get('device_kind')}"
            )
            break
        probe = None
        # Bounded at append time: a probe self-dump is thousands of
        # chars, and these entries feed tpu_error/probe_error — the
        # full dump already went to stderr via note() trails.
        accel_errors.append(f"probe attempt {attempt + 1}: {str(err)[:500]}")
        note(f"probe failed: {str(err)[:200]}")
        if attempt < probe_attempts - 1:
            time.sleep(5 * (attempt + 1))
    if probe is None and accel_errors:
        # Structured probe-failure record: scripts/ci_gate.sh skips any
        # BENCH JSON carrying probe_error during baseline auto-selection.
        result["probe_error"] = {
            "attempts": len(accel_errors),
            "last": str(accel_errors[-1])[:500],
        }
    if probe and study_only:
        result["platform"] = probe["platform"]
        result["device_kind"] = probe["device_kind"]
        result["n_devices"] = probe["n_devices"]
    elif probe:
        note("accelerator measurement phase")
        accel, err = _run_phase("jax", accel_env, timeout=900)
        if not accel:
            accel_errors.append(err)
    # CPU-native baseline — the vs_baseline denominator
    # (JAX_PLATFORMS=cpu: independent of the accelerator's health).
    # Skipped in study-only mode: the slice's evidence is the study
    # points, not a baseline ratio.
    native = None
    if not study_only:
        note("native baseline phase")
        native, err = _run_phase("native", {"JAX_PLATFORMS": "cpu"}, timeout=600)
        if native:
            result["baseline_native_cpu"] = round(native["native_rate"], 1)
            note(f"native baseline: {native['native_rate']:.1f}/s")
        else:
            errors.append(err)

    if study_only and probe is None:
        result["tpu_error"] = "probe failed (see probe_error)"
        skipped["study"] = "probe failed"
        note("probe dead in BENCH_STUDY_ONLY mode: nothing to run")
    if accel is None and not study_only:
        # No accelerator result: no `value`, non-zero exit (below). When
        # the probe never passed, the structured probe_error IS the
        # failure record — tpu_error stays a short pointer instead of a
        # stacked dump tail. When the probe passed but the jax phase
        # died, the phase's self-dump tail is the evidence and rides
        # along (VERDICT.md r3 Weak #8).
        jax_errs = [e for e in accel_errors
                    if not str(e).startswith("probe attempt")]
        result["tpu_error"] = ("; ".join(jax_errs[-3:])
                               or "probe failed (see probe_error)")
        if probe is None:
            skipped["jax_accel"] = "probe failed"

    if accel:
        result["value"] = round(accel["rate"], 1)
        result["platform"] = accel["platform"]
        result["device_kind"] = accel["device_kind"]
        result["n_devices"] = accel["n_devices"]
        result["per_device_rate"] = round(accel["per_device_rate"], 1)
        for key in accel:
            # Phase breakdown (means + p50/p95/max tails), call counts,
            # and the full ingest_* family ride to the top-level record.
            if key.startswith(("t_dispatch", "t_ingest", "n_dispatch",
                               "n_ingest", "ingest_", "transfer_")) or key in (
                "chunk", "fused_chunk_active",
            ):
                result[key] = accel[key]
        if "mfu" in accel:
            result["mfu"] = round(accel["mfu"], 5)
        if native:
            result["vs_baseline"] = round(accel["rate"] / native["native_rate"], 2)

    # Study only makes sense against a healthy accelerator — with
    # tpu_error set each grid point would just re-fail or hang against
    # the dead platform.
    study = None
    want_study = os.environ.get("BENCH_STUDY", "0") == "1"
    study_viable = bool(accel or (study_only and probe)) and (
        "tpu_error" not in result
    )
    if want_study and not study_viable:
        skipped.setdefault("study", "accelerator unreachable")
    if want_study and study_viable:
        note("kernel study phase")
        # A filtered slice is one fused/scan pair (~2 min incl. compiles);
        # 480s keeps the runbook's 900s outer stage timeout strictly
        # dominant over worst-case probes (3x90s+15s) + this phase.
        study_timeout = 480 if os.environ.get("BENCH_STUDY_FILTER") else 1800
        study, err = _run_phase("study", accel_env, timeout=study_timeout)
        if study:
            result.update(study)
        else:
            errors.append(err)

    # Serve-path measurement (BENCH_SERVE=1; docs/SERVING.md): CPU-only
    # and accelerator-independent, so it runs after the accelerator capture.
    # The top-level serve_p95_ms / serve_queue_depth_p95 keys arm
    # ci_gate.sh's serve pins once this bench becomes the baseline.
    if os.environ.get("BENCH_SERVE", "0") == "1" and not study_only:
        note("serve bench phase")
        serve_res, err = _run_phase(
            "serve", {"JAX_PLATFORMS": "cpu"}, timeout=600
        )
        if serve_res:
            result.update(serve_res)
        else:
            errors.append(err)

    # Device-actor rollout A/B (BENCH_DEVACTOR=1; docs/DEVICE_ACTORS.md):
    # CPU-only and accelerator-independent, so it runs after the accelerator
    # capture. The top-level devactor_rows_per_s arms ci_gate.sh's
    # higher-is-better devactor key once this bench becomes the baseline.
    if os.environ.get("BENCH_DEVACTOR", "0") == "1" and not study_only:
        note("device-actor bench phase")
        dev_res, err = _run_phase(
            "devactor", {"JAX_PLATFORMS": "cpu"}, timeout=600
        )
        if dev_res:
            result.update(dev_res)
        else:
            errors.append(err)

    # Fused-megastep A/B (BENCH_FUSED=1; docs/FUSED_BEAT.md): CPU-only
    # and accelerator-independent. The top-level fused_steps_per_s arms
    # ci_gate.sh's higher-is-better fused key once this bench becomes the
    # baseline. ("off" keeps its legacy phase_jax meaning — megakernel
    # disable — and never arms this phase.)
    if os.environ.get("BENCH_FUSED", "0") == "1" and not study_only:
        note("fused-megastep bench phase")
        fused_res, err = _run_phase(
            "fused", {"JAX_PLATFORMS": "cpu"}, timeout=600
        )
        if fused_res:
            result.update(fused_res)
        else:
            errors.append(err)

    # Compile-once superstep A/B (BENCH_SUPERSTEP=1; docs/FUSED_BEAT.md):
    # CPU-only and accelerator-independent. The top-level superstep_steps_per_s
    # arms ci_gate.sh's higher-is-better superstep key once this bench
    # becomes the baseline.
    if os.environ.get("BENCH_SUPERSTEP", "0") == "1" and not study_only:
        note("superstep bench phase")
        sup_res, err = _run_phase(
            "superstep", {"JAX_PLATFORMS": "cpu"}, timeout=600
        )
        if sup_res:
            result.update(sup_res)
        else:
            errors.append(err)

    # Sharded-replay A/B (BENCH_SHARDED_REPLAY=1; docs/REPLAY_SHARDING.md):
    # CPU-only on the 8 virtual devices, accelerator-independent. The top-level
    # replay_ingest_bytes_per_row key arms ci_gate.sh's lower-is-better
    # sharded-replay pin once this bench becomes the baseline.
    if os.environ.get("BENCH_SHARDED_REPLAY", "0") == "1" and not study_only:
        note("sharded-replay bench phase")
        shard_res, err = _run_phase(
            "sharded_replay",
            {
                "JAX_PLATFORMS": "cpu",
                "XLA_FLAGS": (os.environ.get("XLA_FLAGS", "") +
                              " --xla_force_host_platform_device_count=8").strip(),
            },
            timeout=600,
        )
        if shard_res:
            result.update(shard_res)
        else:
            errors.append(err)

    # Tensor-parallel A/B (BENCH_TP=1; docs/MESH.md): CPU-only on the 8
    # virtual devices, accelerator-independent. The top-level
    # tp_param_bytes_per_device / tp_steps_per_s keys arm ci_gate.sh's
    # TP pins once this bench becomes the baseline.
    if os.environ.get("BENCH_TP", "0") == "1" and not study_only:
        note("tensor-parallel bench phase")
        tp_res, err = _run_phase(
            "tp",
            {
                "JAX_PLATFORMS": "cpu",
                "XLA_FLAGS": (os.environ.get("XLA_FLAGS", "") +
                              " --xla_force_host_platform_device_count=8").strip(),
            },
            timeout=600,
        )
        if tp_res:
            result.update(tp_res)
        else:
            errors.append(err)

    # study_only also implies no scaling phase: without this, a hand-run
    # slice missing BENCH_SCALING=0 would burn up to 900s of CPU scaling
    # AFTER the study points are measured but BEFORE the JSON is printed —
    # under the runbook's 900s outer timeout the evidence would be lost.
    if os.environ.get("BENCH_SCALING", "1") != "0" and not study_only:
        note("virtual-device scaling phase")
        scaling, err = _run_phase(
            "scaling",
            {
                "JAX_PLATFORMS": "cpu",
                "XLA_FLAGS": (os.environ.get("XLA_FLAGS", "") +
                              " --xla_force_host_platform_device_count=8").strip(),
            },
            timeout=900,
        )
        if scaling:
            result.update(scaling)
        else:
            errors.append(err)

    if skipped:
        result["skipped"] = skipped
    # Probe failures already live in the structured probe_error record;
    # repeating their dump tails as error rows makes the artifact
    # unusable as a baseline.
    error_rows = [e for e in accel_errors
                  if not str(e).startswith("probe attempt")] + errors
    if error_rows and "tpu_error" not in result:
        result["errors"] = error_rows[-3:]
    print(json.dumps(result), flush=True)
    if study_only:
        return 0 if study else 1
    return 0 if native and accel else 1


if __name__ == "__main__":
    sys.exit(main())
