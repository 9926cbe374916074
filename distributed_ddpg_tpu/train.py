"""Training driver + CLI — the reference's `train.py` equivalent
(SURVEY.md §2 #1, §3.1), re-shaped for the TPU topology.

Where the reference launches {ps|worker} roles over a TF ClusterSpec and
syncs through gRPC (SURVEY.md §3.1), this driver runs ONE learner process
(holding the sharded mesh learner) plus N actor subprocesses (ActorPool) —
params flow through shared memory, gradients through XLA collectives, and
the only CLI distinction left is `--backend {native,jax_tpu}`
(BASELINE.json:5): `native` is the pure-CPU numpy baseline, `jax_tpu` the
sharded JAX path.

Usage:
    python -m distributed_ddpg_tpu.train --env_id=Pendulum-v1 \
        --backend=jax_tpu --num_actors=4 --total_env_steps=100000
"""

from __future__ import annotations

import time

# Set-up span `setup_import` (metrics.SetupStages) starts here: what
# importing the trainer pulls in that the process had not loaded yet.
_IMPORT_T0 = time.perf_counter()

import contextlib
import json
import os
import sys
import threading
from typing import Dict, Optional

import numpy as np

# Nothing imported at module level may import JAX: ActorPool spawns its
# workers, a spawned worker re-imports the parent's main module, and under
# `python -m distributed_ddpg_tpu.train` that is this file (checkpoint.py
# pulls in jax, so it is imported where it is used; orbax it imports
# itself, and only in a run that has a checkpoint directory).
from distributed_ddpg_tpu import trace
from distributed_ddpg_tpu.config import DDPGConfig
from distributed_ddpg_tpu.envs import make, spec_of
from distributed_ddpg_tpu.metrics import (
    CompileCounter,
    GuardrailStats,
    LaunchQueue,
    MeshStats,
    MetricsLogger,
    PhaseTimers,
    PodStats,
    SetupStages,
    Timer,
)
from distributed_ddpg_tpu.ops import support_auto
from distributed_ddpg_tpu.ops.noise import OUNoise
from distributed_ddpg_tpu.replay import make_replay

# Exit-code contract: the constants — and the full per-code rationale —
# live in distributed_ddpg_tpu/exits.py (docs/RESILIENCE.md exit-code
# matrix). Re-exported here because train is the historical import site
# (tests, chaos children, operator scripts all say
# `from distributed_ddpg_tpu.train import EXIT_...`).
from distributed_ddpg_tpu.exits import (  # noqa: F401  (re-export)
    EXIT_NUMERIC,
    EXIT_POD_DEGRADED,
    EXIT_POD_SHRINK,
    EXIT_PREEMPTED,
)

_IMPORT_S = time.perf_counter() - _IMPORT_T0

# Shutdown reap bound for the async eval thread: evals run whole episodes,
# so teardown grants them real time to finish, but a wedged env must not
# hold the trainer's exit hostage — the thread is daemonized, so past this
# bound we abandon it and let interpreter exit reap it.
_EVAL_JOIN_S = 60.0


def _enable_faulthandler() -> None:
    """Stack dumps on demand (kill -USR1 <pid>) and on hard faults — a
    wedged driver must be debuggable without a debugger attached. Called
    from train() (CLI and ladder entries)."""
    import faulthandler
    import signal

    faulthandler.enable()
    if hasattr(signal, "SIGUSR1"):
        faulthandler.register(signal.SIGUSR1)


def require_platform() -> str:
    """Initialize the JAX backend and return its platform, refusing the
    one resolution that hides the device: the jax backends run on the CPU
    only when the CPU was ASKED for (jax_platforms leads with "cpu" — the
    JAX_PLATFORMS=cpu env var, or tests/conftest.py's config update).
    With nothing asked for, JAX itself drops to the CPU when no TPU
    initializes; a run that carried on from there would look healthy and
    measure nothing."""
    import jax

    asked = (jax.config.jax_platforms or "").split(",")[0]
    # Breadcrumb BEFORE the first backend touch: backend init is an
    # unbounded blocking call and the stall watchdog only arms later.
    print(
        f"[train] initializing JAX backend (jax_platforms={asked or 'unset'})",
        file=sys.stderr,
        flush=True,
    )
    platform = jax.default_backend()
    if platform != "tpu" and not (platform == asked == "cpu"):
        raise RuntimeError(
            f"JAX resolved platform {platform!r} but jax_platforms="
            f"{jax.config.jax_platforms!r} did not ask for it: no usable "
            "TPU was found and this run would silently use another "
            "device. Run on the chip, or ask for the CPU explicitly with "
            "JAX_PLATFORMS=cpu."
        )
    return platform


def device_facts() -> Dict[str, object]:
    """What the process runs on, as JAX reports it — stamped on the
    header/final records and the returned summary so no record has to be
    trusted to have come from the chip."""
    import jax

    dev = jax.devices()[0]
    return {
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "n_devices": jax.device_count(),
    }


def train(config: DDPGConfig) -> Dict[str, float]:
    entered_at = time.perf_counter()
    _enable_faulthandler()
    if config.backend == "native":
        return train_native(config)
    require_platform()
    return train_jax(config, entered_at=entered_at)


# ---------------------------------------------------------------------------
# --backend native: the measured CPU baseline (BASELINE.md)
# ---------------------------------------------------------------------------


def train_native(config: DDPGConfig) -> Dict[str, float]:
    from distributed_ddpg_tpu.learner import init_train_state
    from distributed_ddpg_tpu.native_backend import NativeLearner
    from distributed_ddpg_tpu.replay.nstep import NStepAccumulator

    env = make(config.env_id, seed=config.seed)
    spec = spec_of(env)
    # Param init is the only JAX use on the native path; pin it to the host
    # CPU so the baseline never touches (or waits on) an accelerator.
    import jax

    with jax.default_device(jax.local_devices(backend="cpu")[0]):
        state = init_train_state(config, spec.obs_dim, spec.act_dim, config.seed)
    learner = NativeLearner(config, state, spec.action_scale, spec.action_offset)
    replay = make_replay(config, spec.obs_dim, spec.act_dim)
    noise = OUNoise(
        (spec.act_dim,), config.ou_theta, config.ou_sigma, dt=config.ou_dt,
        seed=config.seed + 1,
    )
    nstep = NStepAccumulator(config.n_step, config.gamma)
    log = MetricsLogger(config.log_path)
    learn_timer = Timer()
    learn_steps = 0
    metrics: Dict[str, float] = {}
    ep_return, ep_returns = 0.0, []

    # learner.act is the deterministic policy (no OU noise) — the same
    # policy surface the jax path evaluates, so the two backends' eval
    # curves are directly comparable (the quality gate, BASELINE.md).
    eval_policy = learner.act

    obs, _ = env.reset(seed=config.seed)
    for step in range(1, config.total_env_steps + 1):
        action = learner.act(obs)[0] + noise() * spec.action_scale
        action = np.clip(action, spec.action_low, spec.action_high).astype(np.float32)
        next_obs, reward, terminated, truncated, _ = env.step(action)
        ep_return += reward
        for tr in nstep.push(obs[None], action[None], [reward], [terminated], next_obs[None]):
            replay.add(*tr)
        obs = next_obs
        if terminated or truncated:
            obs, _ = env.reset()
            noise.reset()
            nstep.reset()
            ep_returns.append(ep_return)
            ep_return = 0.0
        if (
            len(replay) >= max(config.replay_min_size, config.batch_size)
            and step % config.train_every == 0
        ):
            sample = replay.sample(config.batch_size)
            indices = sample.pop("indices")
            m = learner.step(sample)
            td = m.pop("td_errors")
            if config.prioritized:
                replay.update_priorities(indices, td)
            metrics = m
            learn_steps += 1
            learn_timer.tick()
        if step % max(1, config.eval_every) == 0:
            log.log(
                "train", step,
                learner_steps=learn_steps,
                learner_steps_per_sec=learn_timer.rate(),
                buffer_fill=len(replay),
                episode_return=(
                    float(np.mean(ep_returns)) if ep_returns else None
                ),
                **metrics,
            )
            ep_returns = []
            if learn_steps:  # past warmup: policy is being trained
                # Inline eval is off-path work: exclude its wall time from
                # the learner rate (the jax path runs evals on a background
                # thread for the same reason) so the reported baseline
                # steps/sec measures learning, not evaluation.
                t_eval = time.time()
                ret = _eval_numpy(eval_policy, config, spec)
                learn_timer.exclude(time.time() - t_eval)
                log.log("eval", step, eval_return=ret)
    rate = learn_timer.rate()
    final_return = _eval_numpy(eval_policy, config, spec)
    log.log(
        "final", config.total_env_steps,
        learner_steps_per_sec=rate, final_return=final_return,
    )
    log.close()
    return {
        "learner_steps_per_sec": rate,
        "learner_steps": learn_steps,
        "final_return": final_return,
    }


# ---------------------------------------------------------------------------
# --backend jax_tpu: async actors + sharded mesh learner
# ---------------------------------------------------------------------------


def train_jax(
    config: DDPGConfig, entered_at: Optional[float] = None
) -> Dict[str, float]:
    # `entered_at`: train()'s perf_counter reading at entry, so the
    # `setup_backend` span covers the backend start train() paid for.
    # Flight recorder (trace.py): armed for the whole device lifetime so
    # the watchdog's stall path below can ship the last-N-seconds
    # timeline with its stack dump. Exported on clean exit and on demand
    # (SIGUSR2 — the stack-dump sibling of _enable_faulthandler's
    # SIGUSR1, for peeking at a LIVE run's timeline without killing it).
    trace_path = ""
    if config.trace_dir:
        trace.configure(capacity=config.trace_events)
        trace_path = os.path.join(config.trace_dir, "trace.json")
        trace.install_signal_export(trace_path)

    # Stall watchdog (watchdog.py): covers the WHOLE device lifetime of
    # the impl below — learner construction, the first params d2h at
    # pool.start, every loop iteration, and teardown — any of which is
    # an unbounded blocking call that a wedged device turns into a
    # silent hang. The beat counter advances at each supervised
    # milestone; the wrapper guarantees the watchdog dies with the call
    # (a leaked watchdog would os._exit a process that already
    # recovered from an ordinary exception).
    _beat_n = [0]

    def _beat() -> None:
        _beat_n[0] += 1

    watchdog = None
    if config.watchdog_s > 0:
        from distributed_ddpg_tpu.watchdog import Watchdog

        watchdog = Watchdog(
            config.watchdog_s,
            progress=lambda: _beat_n[0],
            # Stall artifacts land next to the trace when tracing is on,
            # else next to checkpoints, else the cwd — a stall must always
            # leave its structured report somewhere findable.
            stall_dir=(config.trace_dir or config.checkpoint_dir or "."),
        ).start()

    def _grant(extra_s: float) -> None:
        if watchdog is not None:
            watchdog.grant(extra_s)

    # Set-up counters (docs/OBSERVABILITY.md §2): programs compiled, not
    # loaded from the cache, until the first chunk is read back.
    compiles = CompileCounter().install()
    try:
        return _train_jax_impl(config, _beat, _grant, entered_at, compiles)
    finally:
        compiles.uninstall()
        trace.set_annotator(None)
        if watchdog is not None:
            watchdog.stop()
        if trace_path:
            try:
                n = trace.export(trace_path)
                print(
                    f"[trace] {n} events -> {trace_path} "
                    "(load in ui.perfetto.dev)",
                    file=sys.stderr,
                )
            except Exception as e:
                # Diagnostics must never turn a finished run into a
                # failure (or mask an in-flight exception): a full disk
                # at export time loses the trace, not the run.
                print(f"[trace] export failed: {e!r}",
                      file=sys.stderr, flush=True)
            finally:
                trace.disable()


def write_chunk_ops(learner, config: DDPGConfig) -> str:
    """`chunk_ops.json` (trace.CHUNK_OPS_FILE) beside the records, and beside
    `trace.json` where --trace_dir is set: instruction name -> scope of the
    chunk program the learner launched (ShardedLearner.chunk_ops), so that
    whoever reads a device profile of this run reads `fusion.39` as `draw`
    (docs/OBSERVABILITY.md §5). Written once, after the loop has ended,
    where no rate and no set-up time looks. Returns the path beside the
    records, "" where there are none or nothing was launched. Diagnostics
    never fail a finished run."""
    if not config.log_path:
        return ""
    t0 = time.perf_counter()
    try:
        table = learner.chunk_ops()
        if table is None:
            return ""
        dirs = [os.path.dirname(config.log_path) or "."]
        if config.trace_dir:
            dirs.append(config.trace_dir)
        for d in dirs:
            os.makedirs(d, exist_ok=True)
            with open(os.path.join(d, trace.CHUNK_OPS_FILE), "w") as f:
                json.dump(table, f)
        path = os.path.join(dirs[0], trace.CHUNK_OPS_FILE)
        print(
            f"[trace] {len(table['ops'])} ops of {table['module']} by scope "
            f"-> {path} ({time.perf_counter() - t0:.3f} s)",
            file=sys.stderr,
        )
        return path
    except Exception as e:
        print(f"[trace] chunk_ops not written: {e!r}", file=sys.stderr, flush=True)
        return ""


def _train_jax_impl(
    config: DDPGConfig, _beat, _grant, entered_at, compiles
) -> Dict[str, float]:
    import jax

    # The bracket's second sink (trace.py): from here on every
    # trace.span in this process is also a profiler annotation, on the
    # device trace's clock; inert (well under a microsecond) while no
    # profiler session runs. Only the learner process does this: actor
    # workers import trace.py and must never load JAX.
    trace.set_annotator(jax.profiler.TraceAnnotation)
    # Set-up spans: disjoint stages on this thread, each ending where the
    # next begins; the last ends when the first chunk is read back.
    setup = SetupStages()
    setup.add("setup_import", _IMPORT_S)
    if entered_at is not None:
        # train() started the backend before anything could bracket it.
        setup.add("setup_backend", time.perf_counter() - entered_at)
    setup.stage("setup_import")
    from distributed_ddpg_tpu import checkpoint as ckpt_lib
    from distributed_ddpg_tpu.actors.policy import NumpyPolicy, flatten_params, layout_of
    from distributed_ddpg_tpu.actors.pool import ActorPool
    from distributed_ddpg_tpu.learner import delayed_updates, make_act_fn
    from distributed_ddpg_tpu.ops.polyak import target_copies
    from distributed_ddpg_tpu.parallel import multihost
    from distributed_ddpg_tpu.parallel.learner import (
        ShardedLearner,
        resolve_learner_chunk,
    )
    from distributed_ddpg_tpu.parallel.prefetch import ChunkPrefetcher

    from distributed_ddpg_tpu.replay.device import (
        DevicePrioritizedReplay,
        DeviceReplay,
    )
    from distributed_ddpg_tpu.types import ObsSpec, pack_batch_np

    if config.checkpoint_dir:
        # Beside the backend's start and the first compile, not in front
        # of them and not inside the first save (checkpoint.warm).
        ckpt_lib.warm()
    setup.stage("setup_backend")
    # The JAX runtime's own heartbeat killer must stay SLOWER than the
    # pod layer's worst-case detection (deadline + grace), or a peer
    # death during a granted window LOG(FATAL)s survivors before the
    # clean abort (docs/RESILIENCE.md pod rows). Derived here so the
    # contract holds with default config, not only when an operator
    # remembers the POD_RUNTIME_HEARTBEAT_TIMEOUT_S override.
    is_multi = multihost.initialize(
        runtime_heartbeat_timeout_s=(
            config.pod_collective_timeout_s + config.pod_startup_grace_s
            + 120.0
            if config.pod_collective_timeout_s > 0
            else None
        )
    )
    jax.devices()  # devices known: a no-op where train() started the backend
    setup.stage("setup_build")
    # --- chaos harness + preemption (docs/RESILIENCE.md) ---
    # The fault plan is parsed once; each recoverable component gets its
    # own call-site injector. SIGTERM flips a flag the loop polls at chunk
    # boundaries: the run takes ONE emergency checkpoint off the hot loop
    # and returns with summary["preempted"] set (main() exits
    # EXIT_PREEMPTED so drivers can tell "resumable" from "crashed").
    fault_plan = config.fault_plan()
    ckpt_fault = fault_plan.site("ckpt", "write") if fault_plan else None
    preempt = threading.Event()
    emergency_ckpt = [0]

    # --- telemetry plane (obs/; docs/OBSERVABILITY.md §4) ---
    # The health state machine is a process singleton (the watchdog and
    # multihost flip it from their own threads without plumbing); reset
    # here so back-to-back runs in one process (tests, notebooks) don't
    # inherit a previous run's latched `draining`.
    from distributed_ddpg_tpu.obs import ObsExporter, PodAggregator, health

    health.get().reset()
    obs_server: Optional[ObsExporter] = None

    # --- numerical-health guardrails (guardrails.py; docs/RESILIENCE.md) ---
    # The learner's chunk programs carry the on-device probe; this side
    # holds the host half: per-chunk health-word reads, the rolling
    # anomaly window that triggers rollback-repair, bad-row -> ingest-
    # source attribution, and the LR cooldown. All trigger inputs (health
    # counters, learn_steps) are replicated/identical across processes,
    # so a pod takes every rollback on the same chunk.
    guard_on = config.guardrails
    gstats = GuardrailStats()
    guard_window: list = []            # (learn_steps at read, anomaly count)
    guard_src_offenses: Dict[int, int] = {}
    numeric_failed = [False]
    lr_backoff_since = [-1]            # learn_steps at LR backoff; -1 = none
    # numeric:replay:inf@k (faults.py): poison the k-th ingested row's
    # reward to +inf at drain time — the deterministic bad-replay-row
    # chaos vector (device-replay path; ordinals are per process).
    numeric_replay_at = fault_plan.numeric_replay_rows() if fault_plan else ()
    ingested_rows = [0]

    # --- pod resilience (parallel/multihost.py; docs/RESILIENCE.md) ---
    # Multi-process only: arm the collective deadline (a hung DCN
    # collective surfaces as PodPeerLost within pod_collective_timeout_s
    # instead of blocking forever) and run the one-time startup barrier
    # with its own generous grace — startup skew under box load must be
    # absorbed here, not read as a dead peer by the per-beat deadline.
    # Single-process runs never configure the deadline, so every guarded
    # call short-circuits to a direct call (zero overhead).
    pod_stats = PodStats(seed=config.seed)
    pod_lost: list = [None]
    # Shrink-ready flag (EXIT_POD_SHRINK=78): set on a pod abort when a
    # complete replay slice set survives under checkpoint_dir — the
    # driver may relaunch SMALLER instead of waiting for the lost host.
    pod_shrink_ready = [False]

    def _slices_adoptable() -> bool:
        return bool(
            config.checkpoint_dir
            and config.replay_sharding == "sharded"
            and ckpt_lib.latest_complete_slice_step(config.checkpoint_dir)
            is not None
        )

    def _pod_degraded_early(e) -> Dict[str, float]:
        """Peer loss BEFORE the training stack exists (startup barrier /
        resume election): nothing to checkpoint, but the exit contract
        still applies — main()/the pod harness must see pod_degraded and
        exit EXIT_POD_DEGRADED (76), not a generic traceback the driver
        would misread as 'crash: diagnose' (docs/RESILIENCE.md)."""
        pod_lost[0] = e
        pod_stats.record_abort()
        # A prior incarnation may have left an adoptable slice set: a
        # bootstrap loss is still shrink-recoverable then (exit 78).
        pod_shrink_ready[0] = _slices_adoptable()
        print(
            f"[train] pod peer lost during pod bootstrap: {e}; exiting "
            f"{EXIT_POD_SHRINK if pod_shrink_ready[0] else EXIT_POD_DEGRADED}",
            file=sys.stderr, flush=True,
        )
        return {
            "learner_steps_per_sec": 0.0,
            "learner_steps": 0,
            "final_return": None,
            "param_checksum": 0.0,
            "preempted": False,
            "pod_degraded": True,
            "pod_shrink_ready": pod_shrink_ready[0],
            **pod_stats.snapshot(),
        }

    if is_multi:
        multihost.configure_pod(
            config.pod_collective_timeout_s, stats=pod_stats
        )
        try:
            multihost.startup_barrier(config.pod_startup_grace_s)
            # Clock-alignment handshake (docs/OBSERVABILITY.md §4): one
            # wall-clock allgather right after the barrier, while every
            # process is provably at the same program point. Each host
            # records its offset from host 0 into the flight recorder's
            # metadata so `tools.runs merge-trace` can fuse the per-host
            # Chrome traces onto one timeline without trusting NTP.
            clocks = multihost.clock_handshake()
            if clocks is not None:
                trace.set_meta(
                    process_index=jax.process_index(),
                    process_count=jax.process_count(),
                    clock_offset_ms=clocks["offset_ms"][jax.process_index()],
                    pod_wall_ms=clocks["wall_ms"],
                )
        except multihost.PodPeerLost as e:
            multihost.configure_pod(0.0)
            return _pod_degraded_early(e)

    def _grant_all(extra_s: float) -> None:
        """Extend BOTH stall detectors across a known-long window (first
        chunk XLA compile, support-expansion recompile): the watchdog and
        the pod collective deadline must agree that a compiling pod is
        not a wedged or dead one. The pod side gets pod_startup_grace_s —
        only the compile SKEW between processes can delay a collective,
        and the worst-case peer-loss detection latency stays the
        documented `pod_collective_timeout_s + grace` bound."""
        _grant(extra_s)
        if is_multi:
            multihost.grant(config.pod_startup_grace_s)

    env = make(config.env_id, seed=config.seed)
    spec = spec_of(env)
    # The observation's shape and dtype: a flat float vector's `obs_dim`, or
    # a pixel environment's byte frames (types.ObsSpec); a recurrent
    # configuration's rows hold windows of seq_len of them.
    obs_spec = ObsSpec.of_env(spec, config.window_steps)
    chunk = resolve_learner_chunk(config)
    min_fill = max(config.replay_min_size, config.batch_size)
    n_proc = jax.process_count()
    if (
        config.host_replay
        and config.distributional
        and config.v_support_auto
        and n_proc > 1
    ):
        # Fail FAST, before mesh/learner construction: host replay is
        # process-LOCAL (each process ingests its own actors), so the
        # auto-support warmup sizing and every data-corroboration check
        # would derive DIFFERENT bounds per replica — different compiled
        # Bellman targets on each process, a silent mesh fork. Device
        # replay is replicated (lockstep sync_ship), which is what makes
        # the decisions replica-identical.
        raise ValueError(
            "v_min/v_max=auto with --host_replay is not supported "
            "multi-process: per-process replay statistics would fork the "
            "replicas' compiled programs. Use the device replay path "
            "(default) or concrete v_min/v_max."
        )
    if (
        config.replay_sharding == "sharded"
        and config.distributional
        and config.v_support_auto
        and n_proc > 1
    ):
        # Same fail-fast discipline: the auto-support reward sampler reads
        # replay rows host-side (reward_sample), which in sharded mode is
        # an eager cross-shard gather — not routed through the lockstep
        # lane, so multi-process it could interleave with queued beats.
        raise ValueError(
            "v_min/v_max=auto with replay_sharding='sharded' is not "
            "supported multi-process: the support sizer's host-side "
            "reward reads are cross-shard gathers outside the lockstep "
            "lane. Use replicated replay or concrete v_min/v_max."
        )
    if (
        config.max_learn_ratio > 0.0
        and config.max_ingest_ratio > 0.0
        and chunk > (1.0 + config.max_learn_ratio * n_proc) * min_fill
    ):
        # With BOTH gates armed the first chunk must fit the combined
        # initial allowance: EACH process's ingest caps its local env steps
        # at W = max(replay_min, batch), the learner gate compares against
        # the global sum (n_proc * W at most initially), so it needs
        # chunk <= (1 + learn_ratio * n_proc) * W — otherwise neither
        # counter ever advances. (The config-level product >= 1 check can't
        # see the resolved chunk or process count, so the full condition
        # lives here.)
        raise ValueError(
            f"learner chunk {chunk} exceeds the initial gate allowance "
            f"(1 + max_learn_ratio * {n_proc}) * {min_fill} = "
            f"{(1.0 + config.max_learn_ratio * n_proc) * min_fill:.0f}: "
            "the run would livelock at startup. Lower learner_chunk or "
            "raise replay_min_size."
        )
    # SIGTERM handler: installed after the fail-fast config checks above
    # (an early ValueError must not leak the handler — its restore lives
    # in the teardown finally below) and before the first long-running
    # stage, so preemption covers learner construction and warmup too.
    import signal

    def _on_sigterm(*_):
        preempt.set()
        # /healthz must flip to `draining` on the FIRST scrape after the
        # signal — the supervisor that sent SIGTERM reads it as "ack,
        # winding down", distinct from degraded-but-recoverable.
        health.get().drain("preempted (SIGTERM)")
        print(
            "[train] SIGTERM: finishing the in-flight chunk, taking an "
            f"emergency checkpoint, exiting {EXIT_PREEMPTED} (resumable)",
            file=sys.stderr, flush=True,
        )

    prev_sigterm = None
    try:
        prev_sigterm = signal.signal(signal.SIGTERM, _on_sigterm)
    except ValueError:
        pass  # not on the main thread (embedded callers): no handler

    # --- unified transfer scheduler (transfer/; docs/TRANSFER.md) ---
    # One dispatch thread owns replay-ingest super-blocks, prefetch chunk
    # h2d, learner d2h accounting, and (multi-host) the lockstep ingest
    # collective's background beats. Off under strict_sync: scheduler
    # dispatch timing would make the metrics stream host-scheduling-
    # dependent, breaking the bit-identical-two-runs contract. Created
    # after the fail-fast config checks so an early ValueError cannot
    # leak the dispatch thread.
    transfer_sched = None
    if config.transfer_scheduler and not config.strict_sync:
        from distributed_ddpg_tpu.transfer import TransferScheduler

        transfer_sched = TransferScheduler(
            fault=(
                fault_plan.site("transfer", "dispatch")
                if fault_plan else None
            ),
            # Pod deadline on the lockstep lane (docs/RESILIENCE.md):
            # every multi-host collective beat is bounded, so an
            # in-flight beat whose peer died FAILS its ticket with
            # PodPeerLost instead of wedging the lane.
            lockstep_timeout_s=(
                config.pod_collective_timeout_s if is_multi else 0.0
            ),
        ).start()

    learner = ShardedLearner(
        config,
        obs_spec,
        spec.act_dim,
        spec.action_scale,
        spec.action_offset,
        chunk_size=chunk,
        replay_sharding=config.replay_sharding,
    )
    _beat()  # backend init + learner construction survived
    # Replay lives ON DEVICE (zero h2d in the steady state) for both
    # uniform and prioritized modes (replay/device.py; the PER priority
    # vector is device-resident too). config.host_replay forces the host
    # buffer + prefetch pipeline — the fallback for buffers beyond HBM.
    use_device_replay = not config.host_replay
    if use_device_replay:
        # Async ingest shipping (docs/INGEST.md): single-process only
        # (multi-host rows leave via the lockstep sync_ship collective)
        # and never under strict_sync — the shipper thread would make
        # row-landing timing (hence the sampled stream) a function of
        # host scheduling instead of the config.
        # `ring_obs`: the float32 words of a ring row one observation takes
        # (its float count; a byte frame stack's bytes over four), or for a
        # ring of windows the spec itself (types.packed_width).
        ring_obs = obs_spec if obs_spec.steps else obs_spec.words
        replay_kwargs = dict(
            mesh=learner.mesh,
            # the host's staging blocks: 1,024 rows, or 16 of a pixel
            # configuration's 127 KB rows or a recurrent one's 20 KB
            # windows, which no host worker fills
            block_size=16 if config.pixels or config.recurrent else 1024,
            async_ship=not is_multi and not config.strict_sync,
            max_coalesce=config.ingest_coalesce,
            fault=(
                fault_plan.site("shipper", "ship") if fault_plan else None
            ),
            # Transfer-scheduler policies (docs/TRANSFER.md): scheduled
            # ingest work items, adaptive coalesce cap, pooled staging
            # buffers, and (multi-host) background sync_ship beats. ALL
            # gated on the scheduler actually running: strict_sync and
            # transfer_scheduler=False must recover the PR-1 pipeline
            # verbatim (the adaptive cap is wall-clock-driven, so letting
            # it run under strict_sync would break the bit-identical-
            # metrics contract).
            scheduler=transfer_sched,
            adaptive_coalesce=transfer_sched is not None,
            host_pool=transfer_sched is not None,
            background_sync=config.sync_ship_background,
            # Guardrail bad-row attribution: map storage positions back
            # to the actor slot that produced them (guardrails.py).
            track_sources=(
                guard_on and config.guardrail_source_offenses > 0
            ),
            # Placement (docs/REPLAY_SHARDING.md): replicated (parity
            # oracle) or partitioned over the mesh's data axis.
            replay_sharding=config.replay_sharding,
        )
        device_replay = (
            DevicePrioritizedReplay(
                config.replay_capacity, ring_obs, spec.act_dim,
                alpha=config.per_alpha, eps=config.per_eps, **replay_kwargs,
            )
            if config.prioritized
            else DeviceReplay(
                config.replay_capacity, ring_obs, spec.act_dim,
                **replay_kwargs,
            )
        )
    else:
        device_replay = None
    replay = None if use_device_replay else make_replay(config, spec.obs_dim, spec.act_dim)
    # Checkpointable replay object. SHARDED replay spans processes — no
    # single writer can snapshot a multi-host ring — so its contents are
    # omitted from checkpoints' learner tree and persisted instead as
    # ALL-WRITER slices (docs/REPLAY_SHARDING.md): every shard owner
    # writes its position-indexed slice + digest sidecar next to the
    # checkpoint at the same cadence step, and a restore at ANY process
    # count M merges a verified complete set and reshards it to M
    # (replay/device.py merge_slice_states). Single-process sharded runs
    # take the same path so the wire format never depends on the process
    # count — the elastic shrink/grow contract (docs/RESILIENCE.md).
    sharded_multi = is_multi and config.replay_sharding == "sharded"
    slice_writer = use_device_replay and config.replay_sharding == "sharded"
    slice_fault = (
        fault_plan.slice_site(jax.process_index()) if fault_plan else None
    )
    if slice_writer and jax.process_index() == 0:
        print(
            "[replay] sharded mode: replay contents are "
            "omitted from checkpoints' learner tree; every process "
            "writes its replay slice (docs/REPLAY_SHARDING.md)",
            file=sys.stderr, flush=True,
        )

    def ckpt_replay():
        if slice_writer:
            return None
        return device_replay if use_device_replay else replay

    def write_replay_slices(step: int) -> None:
        """All-writer replay persistence: this process's slice of the
        sharded ring lands next to the learner checkpoint (atomic write +
        digest sidecar — checkpoint.write_replay_slice). A failed slice
        write costs this step's slice-set completeness, never the run:
        adoption falls back to the newest older complete set."""
        if not (slice_writer and config.checkpoint_dir
                and device_replay is not None):
            return
        try:
            ckpt_lib.write_replay_slice(
                config.checkpoint_dir, step,
                jax.process_index(), jax.process_count(),
                device_replay.slice_state_dict(), fault=slice_fault,
            )
        except Exception as e:
            print(
                f"[pod] replay slice write at step {step} failed "
                f"({e!r}); the step's slice set stays incomplete",
                file=sys.stderr, flush=True,
            )
    if config.strict_sync:
        # Lockstep debug mode (config.strict_sync): inline deterministic
        # actors — same surface, no processes, no races to win.
        from distributed_ddpg_tpu.actors.sync_pool import SyncActorPool

        pool = SyncActorPool(config, spec)
    else:
        pool = ActorPool(config, spec)
    # --- resume (SURVEY.md §3.5/§5: learner restart = checkpoint restore;
    # unlike the reference, replay contents come back too). The saved config
    # is validated first; env-step progress carries over so the TOTAL budget
    # (total_env_steps) spans crashes instead of restarting from zero. ---
    learn_steps = 0
    env_steps_offset = 0
    # Per-process emergency-checkpoint directory (pod abort): process 0
    # owns config.checkpoint_dir exactly as before; any OTHER survivor of
    # a pod abort writes into a proc<k> subdirectory, so a shared
    # filesystem never races two writers on the same step_N while
    # per-host local disks still each get a valid emergency checkpoint.
    pod_ckpt_dir = config.checkpoint_dir
    if is_multi and config.checkpoint_dir and jax.process_index() != 0:
        pod_ckpt_dir = os.path.join(
            config.checkpoint_dir, f"proc{jax.process_index()}"
        )
    resume_dir = config.checkpoint_dir
    resume_step: Optional[int] = None
    do_resume = False
    if config.resume and config.checkpoint_dir:
        if is_multi:
            # Coordinated resume (docs/RESILIENCE.md pod rows): gather
            # each process's manifest-valid steps (main dir + its own pod
            # emergency dir) and restore the greatest COMMON step — a
            # step newer on only some processes would fork the pod. This
            # is a collective: ALL processes take this path whether or
            # not they see checkpoints locally (a conditional collective
            # would deadlock the ones that do).
            main_steps = set(ckpt_lib.valid_steps(config.checkpoint_dir))
            own_steps = (
                set(ckpt_lib.valid_steps(pod_ckpt_dir))
                if pod_ckpt_dir != config.checkpoint_dir
                else set()
            )
            try:
                elected = multihost.elect_resume_step(main_steps | own_steps)
            except multihost.PodPeerLost as e:
                # A peer died before the pod even agreed on a resume
                # step: same exit contract as a mid-run loss, minus the
                # emergency checkpoint (no new progress exists yet). The
                # already-built pieces (SIGTERM handler, replay shipper,
                # transfer scheduler) sit ABOVE the main try/finally, so
                # they are torn down here — an embedded caller must get
                # its SIGTERM handler back (the installed one only sets a
                # dead run's preempt flag).
                if prev_sigterm is not None:
                    try:
                        signal.signal(signal.SIGTERM, prev_sigterm)
                    except (ValueError, TypeError):
                        pass
                if use_device_replay and device_replay is not None:
                    device_replay.close()
                if transfer_sched is not None:
                    transfer_sched.close()
                multihost.configure_pod(0.0)
                return _pod_degraded_early(e)
            if elected >= 0:
                do_resume = True
                resume_step = elected
                resume_dir = (
                    config.checkpoint_dir
                    if elected in main_steps
                    else pod_ckpt_dir
                )
                pod_stats.record_resume_elected(elected)
                trace.instant("pod_resume_elected", step=elected)
                print(
                    f"[pod] resume election: step {elected} is the newest "
                    "checkpoint valid on every process"
                )
        elif ckpt_lib.latest_step(config.checkpoint_dir) is not None:
            do_resume = True
    ckpt_meta: Dict[str, object] = {}
    if do_resume:
        restored, step, env_steps_offset = ckpt_lib.restore(
            resume_dir,
            learner.state,
            ckpt_replay(),
            step=resume_step,
            config=config,
            meta_out=ckpt_meta,
        )
        learner.state = jax.device_put(restored, learner._state_sharding)
        learn_steps = step
        if config.distributional and config.v_support_auto:
            # The RESOLVED support bounds ride the checkpoint: the restored
            # critic's logits are only meaningful over the atom values they
            # were trained against — re-deriving from reward statistics
            # cannot recover mean_q-driven expansions. Old checkpoints
            # without the field fall back to warmup re-derivation below.
            if "v_bounds" in ckpt_meta:
                learner.set_value_bounds(*ckpt_meta["v_bounds"])
                print(
                    "auto C51 support restored from checkpoint: "
                    f"[{learner.config.v_min:.1f}, {learner.config.v_max:.1f}]"
                )
        # Resumed progress counts against the uniform-warmup budget
        # (pool._spawn) — no random-action re-injection mid-training.
        pool.env_steps_offset = env_steps_offset
        print(
            f"resumed from {resume_dir} at learner step {step}, "
            f"env step {env_steps_offset}"
        )

    # --- replay slice adoption (elastic shrink/grow; docs/RESILIENCE.md
    # state machine, docs/REPLAY_SHARDING.md all-writer format) ---
    # A sharded-replay resume restored NO replay through the learner tree
    # (ckpt_replay() is None); the experience lives in the all-writer
    # slice sets instead. Adopt the newest complete, digest-verified set
    # at or below the restored learner step — possibly written by a
    # DIFFERENT process count n_prev: merge is position-driven, the load
    # reshards to today's count M. M < n_prev is a SHRINK (a peer's last
    # verified slice is adopted by the survivors; the run continues
    # degraded), M > n_prev is a GROW back toward full strength. The
    # election keeps adoption pod-atomic: either every process adopts the
    # same step or nobody does (a forked replay distribution is worse
    # than an empty one).
    if (
        do_resume
        and slice_writer
        and device_replay is not None
        and not ckpt_meta.get("ckpt_has_replay")
    ):
        sstep = ckpt_lib.latest_complete_slice_step(
            config.checkpoint_dir, at_or_below=learn_steps
        )
        if is_multi:
            try:
                elected_slice = multihost.elect_slice_step(sstep)
            except multihost.PodPeerLost as e:
                if prev_sigterm is not None:
                    try:
                        signal.signal(signal.SIGTERM, prev_sigterm)
                    except (ValueError, TypeError):
                        pass
                device_replay.close()
                if transfer_sched is not None:
                    transfer_sched.close()
                multihost.configure_pod(0.0)
                return _pod_degraded_early(e)
            sstep = elected_slice if elected_slice >= 0 else None
        if sstep is not None:
            from distributed_ddpg_tpu.replay.device import merge_slice_states

            slices = ckpt_lib.load_replay_slices(
                config.checkpoint_dir, sstep
            )
            device_replay.load_state_dict(merge_slice_states(slices))
            n_prev = len(slices)
            nprocs = jax.process_count()
            pod_stats.record_slice_adopted(sstep)
            trace.instant("pod_slice_adopted", step=sstep)
            print(
                f"[pod] adopted replay slices from step {sstep} "
                f"(written by {n_prev} process(es), resharded to "
                f"{nprocs})",
                file=sys.stderr, flush=True,
            )
            if nprocs < n_prev:
                pod_stats.record_shrink()
                print(
                    f"[pod] SHRINK: running at {nprocs}/{n_prev} "
                    "processes with the lost peer's replay adopted — "
                    "state degraded until a grow (docs/RESILIENCE.md)",
                    file=sys.stderr, flush=True,
                )
            elif nprocs > n_prev:
                pod_stats.record_grow()
                print(
                    f"[pod] GROW: resharded {n_prev}-writer replay to "
                    f"{nprocs} processes — state healthy",
                    file=sys.stderr, flush=True,
                )
        else:
            print(
                "[pod] no verified replay slice set to adopt at or below "
                f"step {learn_steps}; the buffer resumes empty "
                "(docs/REPLAY_SHARDING.md)",
                file=sys.stderr, flush=True,
            )

    # --- on-device vectorized actors (actors/device_pool.py;
    # docs/DEVICE_ACTORS.md) ---
    # config.actor_backend='device': rollouts run as jitted lax.scan
    # chunks over device_actor_envs vmapped JAX envs and scatter straight
    # into DeviceReplay's HBM ring (insert_device_rows) — no host staging,
    # no transfer-scheduler ingest class. Param refresh is a device-side
    # pointer swap from the learner's LIVE params (set_params — re-swapped
    # every chunk because the learner's dispatch donates the old state).
    # Built AFTER the resume block so the uniform-warmup budget nets out
    # restored progress. The host pool above still runs its num_actors
    # workers (0 = device-only run) and both sources feed the same ring.
    device_pool = None
    if config.actor_backend == "device":
        from distributed_ddpg_tpu.actors.device_pool import DeviceActorPool

        device_pool = DeviceActorPool(
            config,
            mesh=learner.mesh,
            fault=(
                fault_plan.site("devactor", "rollout") if fault_plan else None
            ),
            warmup_offset=env_steps_offset,
        )
        if "devactor_carry" in ckpt_meta:
            # Rollout-state resume (docs/DEVICE_ACTORS.md): restore the
            # pool's env carry + OU state (+ n-step window) so a resumed
            # device-actor run CONTINUES its episodes instead of restarting
            # E fresh ones (shape-validated; a changed E/env falls back to
            # fresh). Before the first swap, which primes a window that no
            # carry brought.
            device_pool.load_carry_state(ckpt_meta["devactor_carry"])
        device_pool.set_params(learner.policy_params(), learn_steps)
        _beat()  # rollout-program construction survived
    # Whether any host worker acts: with none (a device-only run) nobody
    # reads a broadcast, and the refresh is the pool's pointer swap.
    host_actors = config.num_actors > 0

    # --- fused training megastep (parallel/megastep.py; docs/FUSED_BEAT.md) ---
    # config.fused_beat: compile rollout + ring scatter + sample + the K
    # learner updates into ONE jitted program per steady-state iteration —
    # the host dispatches a single beat instead of three programs, with
    # zero host round-trips inside it. 'auto' fuses whenever the device-
    # actor + device-replay legs exist, the ratio gates are free-running
    # (a fused beat has a FIXED rollout:learn ratio the gates could not
    # throttle), and the Pallas megakernel is inactive (no slot for it
    # inside a larger program); 'on' forces it (config validation already
    # rejected impossible compositions). Guardrails thread THROUGH the
    # fused program (note_fused_health), so guardrails=True keeps the
    # fast path. Warmup below still uses the standalone rollout dispatch:
    # beats need the learner leg, which warmup by definition lacks.
    megastep = None
    if (
        device_pool is not None
        and use_device_replay
        and config.fused_beat != "off"
        # the beat composes the flat rollout and chunk bodies (config.py
        # refuses fused_beat='on' with a pixel or a recurrent configuration)
        and not config.pixels
        and not config.recurrent
        and (
            config.fused_beat == "on"
            or (
                not learner.fused_chunk_active
                and config.max_ingest_ratio == 0.0
                and config.max_learn_ratio == 0.0
            )
        )
    ):
        if config.superstep_beats > 1:
            # Compile-once multi-beat superstep (parallel/superstep.py):
            # B fused beats compose inside one donated-carry fori_loop —
            # one dispatch and ONE host sync point per B iterations.
            # FusedSuperstep is run_beat-shaped (train loop drives it
            # through the same after_chunk), so everything downstream —
            # fused_fields(), guardrail monitor, checkpoint cadence —
            # sees a beat that happens to advance B chunks.
            from distributed_ddpg_tpu.parallel.superstep import FusedSuperstep

            megastep = FusedSuperstep(
                config, learner, device_pool, device_replay
            )
        else:
            from distributed_ddpg_tpu.parallel.megastep import FusedMegastep

            megastep = FusedMegastep(
                config, learner, device_pool, device_replay
            )
        _beat()  # beat-program construction survived

    # Learner d2h pulls ride the scheduler's inline d2h class: absolute
    # priority (no queueing on the hot path), full transfer_* accounting.
    learner.transfer = transfer_sched

    # --- batched policy-inference service (serve/; docs/SERVING.md) ---
    # config.serve_actors: one InferenceServer in this process serves
    # mu(s) to the whole worker fleet through a dynamic batcher
    # (serve_max_batch / serve_max_latency_ms dispatch). Params refresh
    # from the SAME shared-memory broadcast buffer the workers poll
    # (pool.param_source()), batch applies ride the transfer scheduler's
    # `serve` class (byte-fair with ingest/prefetch, never ahead of
    # lockstep), and workers degrade to their local act() mirror when the
    # served path cannot answer (the failure contract the serve chaos
    # tests pin).
    serve_server = None
    serve_front = None
    if config.serve_actors:
        from distributed_ddpg_tpu.serve import InferenceServer, ServeFront

        serve_server = InferenceServer(
            pool.layout,
            spec.action_scale,
            spec.action_offset,
            max_batch=config.serve_max_batch,
            max_latency_s=config.serve_max_latency_ms / 1000.0,
            max_queue=config.serve_queue,
            backend=config.serve_backend,
            param_source=pool.param_source(),
            scheduler=transfer_sched,
            seed=config.seed,
            fault_batcher=(
                fault_plan.site("serve", "batcher") if fault_plan else None
            ),
            fault_dispatch=(
                fault_plan.site("serve", "dispatch") if fault_plan else None
            ),
            # jax backend under TP: serve over the learner's mesh so the
            # policy kernels stay 'model'-sharded at serve time too
            # (parallel/partition.py rule tables; docs/MESH.md). Gated on
            # model_axis > 1 — at 1 the specs are fully replicated and a
            # mesh-wide serve dispatch would only queue behind learner
            # chunks on every device for zero HBM benefit; the
            # single-device apply keeps serving off the training streams.
            mesh=(
                learner.mesh
                if config.serve_backend == "jax" and config.model_axis > 1
                else None
            ),
            # SAC serve head (docs/SERVING.md): the batch apply returns
            # [mean | log_std] rows and each client's action is sampled
            # server-side with a (seed, tenant, request_id) key —
            # serve_actors + sac is a supported pairing since PR 20.
            sac=config.gaussian_head,
            log_std_min=config.sac_log_std_min,
            log_std_max=config.sac_log_std_max,
            squash=not config.mpo,
        ).start()
        serve_front = ServeFront(
            serve_server, *pool.serve_channels()
        ).start()

    # --- network serving front (serve/front/; docs/SERVING.md §front) ---
    # front_port/front_http_port > 0: external framed-TCP + HTTP/JSON
    # ingress with versioned snapshots (canary promote) and per-tenant
    # QoS. Each active version runs its own InferenceServer engine fed by
    # the same layout; the learner's live params publish as version
    # "live-0" so the front serves from step one, and later snapshots
    # publish/promote through front_server's API (tools, tests). A bind
    # failure downgrades to a warning — ingress must never kill the run
    # it fronts (the obs/ exporter discipline).
    front_server = None
    if config.serve_actors and (config.front_port or config.front_http_port):
        from distributed_ddpg_tpu.actors.policy import flatten_params
        from distributed_ddpg_tpu.serve.front import FrontServer

        def _make_front_engine():
            return InferenceServer(
                pool.layout,
                spec.action_scale,
                spec.action_offset,
                max_batch=config.serve_max_batch,
                max_latency_s=config.serve_max_latency_ms / 1000.0,
                max_queue=config.serve_queue,
                backend=config.serve_backend,
                seed=config.seed,
                sac=config.gaussian_head,
                log_std_min=config.sac_log_std_min,
                log_std_max=config.sac_log_std_max,
                squash=not config.mpo,
            )

        try:
            front_server = FrontServer(
                _make_front_engine,
                port=config.front_port,
                http_port=config.front_http_port or None,
                timeout_s=config.front_timeout_s,
                canary_fraction=config.front_canary_fraction,
                canary_min_requests=config.front_canary_min_requests,
                canary_threshold=config.front_canary_threshold,
                tenants=config.front_tenants,
                default_priority=config.front_default_priority,
                shed_start=config.front_shed_start,
                seed=config.seed,
                fault_accept=(
                    fault_plan.site("front", "accept") if fault_plan else None
                ),
                fault_frame=(
                    fault_plan.site("front", "frame") if fault_plan else None
                ),
                canary_regressions=(
                    fault_plan.front_canary_regressions()
                    if fault_plan
                    else ()
                ),
            )
            front_server.publish(
                "live-0", flatten_params(learner.actor_params_to_host())
            )
            front_server.start()
            print(
                f"[front] serving ingress on tcp:{front_server.port} "
                f"http:{front_server.http_port or '-'} (stable=live-0)",
                file=sys.stderr, flush=True,
            )
        except OSError as e:
            front_server = None
            print(f"[front] ingress disabled (bind failed: {e})",
                  file=sys.stderr, flush=True)

    start_params = learner.actor_params_to_host()
    checksum_start = _param_checksum(start_params)
    pool.start(start_params)
    _beat()  # first params d2h survived (an observed wedge point)

    def run_facts() -> Dict[str, object]:
        """Where and how the learner runs, as observed (never as
        configured): device platform/kind/count, the resolved
        steps-per-dispatch, whether the Pallas megakernel is the chunk
        program and how many (8, 128) tiles its resident parameters take
        (null on the scan leg), and — from live sharding metadata, zero
        d2h — how many devices hold the TrainState (the least-spread leaf)
        and the replay ring. On the header and final records and the returned
        summary — what chip_smoke.py and every benchmark cell read to
        know a number came from the chip and from which learner leg."""
        facts = {
            **device_facts(),
            "learner_chunk": chunk,
            "fused_chunk_active": learner.fused_chunk_active,
            "kernel_state_tiles": learner.kernel_state_tiles,
            # How a launch's gathered rows reach the update: 'cut' where the
            # uniform scan chunk runs ops/chunk_front.py's one-pass kernel,
            # 'xla' for unpack_batch (the megakernel's own cuts, PER, the
            # guarded and the host-fed chunks).
            "chunk_front": (
                learner.chunk_front
                if use_device_replay and not config.prioritized
                else "xla"
            ),
            # The launched chunk executable's collective instructions and
            # how many of them are asynchronous (trace.chunk_ops_table);
            # null on one chip and, on the header, before the first launch.
            "chunk_collectives": learner.chunk_collectives(),
            # What one trip of the launched scan chunk's loop issues as
            # unfused arithmetic on scalars (the table's `scalars`); null on
            # the kernel leg and, on the header, before the first launch.
            "chunk_body_scalars": learner.chunk_body_scalars(),
            # And as relayouts of their own: the `copy`, `transpose` and
            # `reshape` instructions and the fusions that only move an
            # array, their count and their results' bytes (the table's
            # `copies`); null where `chunk_body_scalars` is.
            "chunk_body_copies": learner.chunk_body_copies(),
            # And as gathers: the `gather` instructions and the fusions that
            # hold one (the table's `gathers`); null where the two above are.
            "chunk_body_gathers": learner.chunk_body_gathers(),
            "state_devices": min(
                len(leaf.sharding.device_set)
                for leaf in jax.tree.leaves(learner.state)
            ),
        }
        if use_device_replay:
            facts["replay_devices"] = len(
                device_replay.storage.sharding.device_set
            )
        if config.redq:
            # Ensemble runs only: how many critics the state stacks and how
            # many of them each update's target draws.
            facts["critic_ensemble"] = config.critic_ensemble
            facts["target_subset"] = config.target_subset
        if config.crossq:
            # CrossQ runs only, and from the state: no target net is held.
            facts["crossq"] = learner.state.target_critic_params is None
        if config.mpo:
            # MPO runs only: the samples a state, how many dual values the
            # state holds (2 + 2 x dim(A)), and how the targets move.
            facts["mpo_samples"] = config.mpo_samples
            facts["mpo_duals"] = sum(
                int(np.size(x)) for x in jax.tree.leaves(learner.state.log_alpha)
            )
            facts["target_update_period"] = config.target_update_period
        if config.simba:
            # Residual runs only, from the state's own shapes: blocks and
            # stream width of each net, and the decay its optimiser applies.
            for net, params in (
                ("actor", learner.state.actor_params),
                ("critic", learner.state.critic_params),
            ):
                facts[f"simba_{net}_blocks"] = len(params) - 2
                facts[f"simba_{net}_width"] = int(params[0]["w"].shape[-1])
            facts["weight_decay"] = config.weight_decay
        if config.pixels:
            # Pixel runs only, from the state and the ring: the encoder's
            # width, the trunk's, and the bytes one ring row holds.
            critic = learner.state.critic_params
            facts["pixels"] = True
            facts["encoder_channels"] = int(critic["encoder"][0]["w"].shape[0])
            facts["feature_dim"] = int(critic["trunk"]["w"].shape[1])
            if use_device_replay:
                facts["ring_row_bytes"] = device_replay.row_bytes_device
        if config.recurrent:
            # Recurrent runs only, from the state and the ring: the window,
            # the memory's width, and the bytes one window row holds.
            facts["recurrent"] = True
            facts["seq_len"] = obs_spec.steps
            facts["rnn_hidden"] = int(
                learner.state.actor_params["lstm"]["w"].shape[-1] // 4
            )
            if use_device_replay:
                facts["ring_row_bytes"] = device_replay.row_bytes_device
        if device_pool is not None and device_pool.sigma_ends is not None:
            # The Gaussian ladder's ends, as the rollout program holds them.
            facts["devactor_sigma_min"], facts["devactor_sigma_max"] = (
                device_pool.sigma_ends
            )
        return facts

    log = MetricsLogger(config.log_path, header=run_facts())

    # --- live telemetry ingress (obs/exporter.py; docs/OBSERVABILITY.md
    # §4) --- config.obs_port > 0: a stdlib HTTP thread serves /metrics
    # (Prometheus text of the latest record per kind + run counters),
    # /healthz (the typed state machine scrapers gate canaries on), and
    # /trace (on-demand flight-recorder export). Started after the logger
    # so the very first scrape already sees the header record; a bind
    # failure (port taken) downgrades to a warning — telemetry must never
    # kill the run it observes.
    if config.obs_port > 0:
        try:
            obs_server = ObsExporter(
                config.obs_port,
                health=health.get(),
                latest_fn=log.latest,
                counters_fn=lambda: {
                    "t_unix_base": log.t_unix_base,
                    "process_index": jax.process_index(),
                    "process_count": jax.process_count(),
                    "preempt": int(preempt.is_set()),
                },
                trace_dir=(config.trace_dir or config.checkpoint_dir or "."),
            ).start()
            print(
                f"[obs] telemetry ingress on :{obs_server.port} "
                "(/metrics /healthz /trace)",
                file=sys.stderr, flush=True,
            )
        except OSError as e:
            obs_server = None
            print(f"[obs] exporter disabled (bind failed: {e})",
                  file=sys.stderr, flush=True)
    if serve_server is not None:
        # Live degraded probe: /healthz reads the serve queue AS OF the
        # scrape, not the last log cadence (serve/server.py overloaded).
        health.get().register_probe("serve_overloaded",
                                    serve_server.overloaded)

    learn_timer, env_timer = Timer(), Timer()
    phases = PhaseTimers()
    # Launches the device has not finished (metrics.LaunchQueue): what a
    # refresh waits out, and what learner_steps_per_sec must not count.
    launches = LaunchQueue()

    @contextlib.contextmanager
    def read_back(name: str, **args):
        """One read-back of the learner thread: every `refresh`, `sync`,
        `eval_snapshot` and `ckpt` site that fetches the state or the
        metrics goes through here, as every launch goes through
        `dispatch`. Inside the site's own phase the launches in flight
        are waited out one by one under `<name>_drain` (LaunchQueue.drain:
        a `launch_wait` span each, the device busy throughout), and only
        then the body runs: its d2h, fold and broadcast find nothing in
        flight, so their spans bracket the host's turnaround alone, and
        the instant the host finds the device dry is a span boundary."""
        with phases.phase(name, **args):
            with phases.phase(f"{name}_drain"):
                learn_timer.tick(launches.drain())
            yield

    saver = ckpt_lib.AsyncSaver()
    last_ckpt = learn_steps

    def recovery_fields() -> Dict[str, int]:
        """Cumulative fault-history counters for every train/final record
        (ISSUE: actor_respawns / actor_quarantined / ckpt_write_retries /
        emergency_ckpt) — `tools.runs summarize` renders them as the run's
        recovery digest."""
        return {
            **pool.recovery_counters(),
            "ckpt_write_retries": saver.write_retries,
            "emergency_ckpt": emergency_ckpt[0],
        }

    # A pixel configuration's evaluator acts through the learner's own apply
    # (encoder, trunk and head, no augmentation and no noise; the numpy
    # policy of the host workers has no convolution), jitted once a run; a
    # recurrent one's through the learner's one-step apply, carrying the
    # policy's memory over the episode (_RecurrentEvalPolicy).
    pixel_act = (
        make_act_fn(config, spec.action_scale, spec.action_offset)
        if config.pixels or config.recurrent else None
    )

    def eval_policy_of(host_params):
        """The evaluator's policy on a host copy of the tree that acts
        (learner.actor_params_to_host): the workers' numpy policy, or for a
        pixel configuration the learner's own apply, one byte frame stack a
        call."""
        if config.pixels:
            return lambda obs: np.asarray(
                pixel_act(host_params, np.asarray(obs)[None])
            )
        if config.recurrent:
            return _RecurrentEvalPolicy(
                pixel_act, host_params, config.rnn_hidden, spec.act_dim
            )
        policy = NumpyPolicy(
            layout_of(config, spec.obs_dim, spec.act_dim),
            spec.action_scale,
            spec.action_offset,
            gaussian=config.gaussian_head,
            squash=not config.mpo,
        )
        policy.load_flat(flatten_params(host_params))
        return policy

    # Periodic eval runs in a background thread on a PARAM SNAPSHOT
    # (SURVEY.md §5; VERDICT.md round-1 Weak #7: inline eval stalled the
    # learner for whole CPU episodes). Only the tiny flat-param copy happens
    # on the hot loop; if an eval is still running when the next cadence
    # fires, the new one is skipped — eval is a diagnostic, the learner has
    # priority.
    eval_thread: Dict[str, object] = {"t": None}

    def start_eval(at_step: int) -> None:
        t = eval_thread["t"]
        if t is not None and t.is_alive():
            return
        with read_back("eval_snapshot"):
            host_params = learner.actor_params_to_host()

        def _run():
            with trace.span("eval_rollout", step=at_step):
                log.log(
                    "eval", at_step,
                    eval_return=_eval_numpy(
                        eval_policy_of(host_params), config, spec
                    ),
                )

        if config.strict_sync:
            # Lockstep mode: eval runs synchronously so the metrics stream
            # (content AND order) is a pure function of the config.
            _run()
            return
        t = threading.Thread(target=_run, name="eval-worker", daemon=True)
        t.start()
        eval_thread["t"] = t

    profile_cm = (
        jax.profiler.trace(config.profile_dir)
        if config.profile_dir
        else contextlib.nullcontext()
    )

    # One lock serializes every host-replay access: the prefetch thread's
    # sampling vs this thread's inserts and priority updates (SURVEY.md §5
    # 'Race detection' row — the host buffer is the only shared mutable
    # state; everything device-side is functional). The device-replay path
    # has no shared host state at all.
    replay_lock = threading.Lock()

    # --- background lockstep sync_ship (docs/TRANSFER.md) ---
    # With the scheduler attached on a multi-host run, the per-chunk
    # sync_ship collective is issued as a BACKGROUND beat on the lockstep
    # lane (pending counts snapshot at issue time) and the learner only
    # gates its NEXT collective-bearing dispatch on the beat's enqueue —
    # the DCN wait overlaps chunk compute instead of blocking the loop.
    # Warmup keeps synchronous semantics: its loop condition reads the
    # replicated buffer fill, which must reflect the beat on every
    # process at the same iteration or the lockstep loop counts fork.
    bg_sync = (
        transfer_sched is not None
        and is_multi
        and use_device_replay
        and config.sync_ship_background
    )
    pending_beat: Dict[str, object] = {"t": None}
    # Globally-agreed env-step budget cache (multi-host: re-gathered every
    # 10th loop iteration). A cell, not a loop local, so devactor_step's
    # ingest gate can read the replica-identical value from after_chunk.
    cached_global = [0]

    def wait_beat() -> None:
        """Gate: resolve the outstanding background beat (if any) before
        the next collective-bearing dispatch / replica-state read. The
        residual non-overlapped cost lands in t_sync_ship_wait_*. The
        wait is bounded by the CONFIGURED pod deadline (multihost.
        wait_beat_ticket), and a timeout surfaces as typed PodPeerLost —
        the clean-abort path — not a raw TimeoutError."""
        t = pending_beat["t"]
        if t is not None:
            pending_beat["t"] = None
            with phases.phase("sync_ship_wait"):
                multihost.wait_beat_ticket(t)

    def transfer_fields() -> Dict[str, float]:
        """transfer_* observability for the JSONL records: scheduler
        counters + the replay-owned adaptive-coalesce/pool gauges."""
        if transfer_sched is None:
            return {}
        out = dict(transfer_sched.snapshot())
        if use_device_replay and device_replay is not None:
            out.update(device_replay.transfer_snapshot())
        return out

    def pod_fields() -> Dict[str, float]:
        """pod_* resilience counters (metrics.PodStats; docs/RESILIENCE.md
        pod rows) for every train/final record on multi-process runs —
        peer losses, coordinated aborts, the elected resume step, and the
        collective-deadline near-miss/slack telemetry. Single-process
        records stay clean — EXCEPT when elastic events (slice adoption,
        shrink/grow) happened: a pod shrunk to one process must still
        surface its degraded state (docs/RESILIENCE.md)."""
        return (
            pod_stats.snapshot()
            if is_multi or pod_stats.elastic_events()
            else {}
        )

    def guardrail_fields() -> Dict[str, int]:
        """guardrail_* numerical-health counters (metrics.GuardrailStats;
        docs/RESILIENCE.md 'Numerical health') for every train/final
        record when guardrails are armed. Records stay clean otherwise."""
        return gstats.snapshot() if guard_on else {}

    def serve_fields() -> Dict[str, float]:
        """serve_* inference-service counters (metrics.ServeStats;
        docs/SERVING.md) for every train/final record when serving is
        armed — request/batch totals, batch-fill, latency tails, queue
        depth, and the workers' local-act fallback count."""
        if serve_server is None:
            return {}
        out = {**serve_server.snapshot(), **pool.serve_counters()}
        if front_server is not None:
            # front_* + tenant_* ride the same record (metrics.FrontStats
            # / TenantStats; docs/SERVING.md 'Network front').
            out.update(front_server.snapshot())
        return out

    def devactor_fields() -> Dict[str, float]:
        """devactor_* observability (metrics.DevActorStats;
        docs/DEVICE_ACTORS.md) for every train/final record when the
        device-actor backend is armed — interval rows/s, per-chunk
        dispatch tails, episode stats, and the bounded-restart counter.
        Records stay clean on the host backend."""
        return device_pool.snapshot() if device_pool is not None else {}

    def fused_fields() -> Dict[str, float]:
        """fused_* observability (metrics.FusedBeatStats;
        docs/FUSED_BEAT.md) for every train/final record when the fused
        megastep is active — interval beats, grad-steps/s, rows/s, and
        the per-beat dispatch tails. Records stay clean on the
        dispatch-per-phase loop."""
        return megastep.snapshot() if megastep is not None else {}

    def delay_fields() -> Dict[str, int]:
        """`td3_actor_updates` (twin-critic runs), `redq_policy_updates`
        (ensemble runs, config.redq) or `crossq_policy_updates` (CrossQ runs
        with a delay) beside `learner_steps` on every
        train/final record (docs/OBSERVABILITY.md): how many of the
        learner's updates moved the actor (with TD3's targets, or REDQ's
        and CrossQ's temperature), cumulative from step 0. Host arithmetic on the step
        count the branch already carries (learner.delayed_updates, the rule
        the step's cond, the kernel's schedule and the actor's Adam count
        follow): no update pays for it. No other family's records have
        either key. A run whose targets are copied whole
        (config.target_update_period) has `target_copies` besides, the
        copies so far by the same arithmetic (ops/polyak.target_copies)."""
        copies = (
            {"target_copies": target_copies(learn_steps, config.target_update_period)}
            if config.target_update_period else {}
        )
        if config.twin_critic:
            key = "td3_actor_updates"
        elif config.redq:
            key = "redq_policy_updates"
        elif config.crossq and config.policy_delay > 1:
            key = "crossq_policy_updates"
        else:
            return copies
        return {key: delayed_updates(learn_steps, config.policy_delay), **copies}

    mesh_stats = MeshStats(
        learner.mesh.shape["data"], learner.mesh.shape["model"]
    )

    def mesh_fields() -> Dict[str, float]:
        """mesh_* placement facts (metrics.MeshStats; docs/MESH.md) for
        every train/final record: mesh shape plus the measured per-device
        TrainState bytes — the /model_axis HBM claim as an observation of
        the live tree's sharding metadata (zero d2h)."""
        return mesh_stats.snapshot(jax.tree.leaves(learner.state))

    def _guard_quarantine_sources() -> None:
        """Bad-row -> ingest-source attribution: fetch the offending
        replay indices the probe captured (the rare-path d2h), map them
        to the actor slots that produced them, and quarantine slots past
        the repeat-offender threshold through the pool's breaker
        machinery (probing un-quarantines a recovered slot later)."""
        if not use_device_replay or config.guardrail_source_offenses <= 0:
            return
        idx = learner.bad_indices()
        if not len(idx):
            return
        srcs = device_replay.sources_of(idx)
        for s in srcs:
            s = int(s)
            if s < 0:
                continue  # untracked: restored rows, padding, other procs
            guard_src_offenses[s] = guard_src_offenses.get(s, 0) + 1
            if guard_src_offenses[s] >= config.guardrail_source_offenses:
                guard_src_offenses[s] = 0  # a probed comeback re-counts
                if pool.quarantine_source(s, why="numeric"):
                    gstats.record_source_quarantine()

    def _numeric_abort(why: str) -> bool:
        """Rollback impossible (budget exhausted / nothing to restore):
        flag the documented EXIT_NUMERIC abort. Deliberately writes NO
        checkpoint — the live params are presumed poisoned, and the last
        retained pre-divergence checkpoint must stay the newest state a
        resume can find."""
        numeric_failed[0] = True
        trace.instant("numeric_abort", step=learn_steps)
        print(
            f"[guardrail] NUMERIC ABORT at learner step {learn_steps}: "
            f"{why}; exiting {EXIT_NUMERIC} (no checkpoint written — the "
            "last retained pre-divergence checkpoint stands)",
            file=sys.stderr, flush=True,
        )
        return True

    def _rollback_or_abort() -> bool:
        """Automatic rollback-repair (docs/RESILIENCE.md): restore the
        last manifest-valid checkpoint through the PR-4 fallback walk
        (pods elect the step through the PR-6 election so hosts never
        fork), reseed exploration so the repaired run draws a different
        batch stream, optionally back off the LRs for a cooldown, and
        quarantine the diverged-timeline checkpoints. Bounded by
        guardrail_max_rollbacks -> EXIT_NUMERIC. Returns True (the caller
        skips the rest of its chunk work) on both rollback and abort."""
        nonlocal learn_steps, last_ckpt, next_refresh, last_refresh_t
        if gstats.rollbacks >= config.guardrail_max_rollbacks:
            return _numeric_abort(
                f"rollback budget exhausted "
                f"({gstats.rollbacks}/{config.guardrail_max_rollbacks})"
            )
        if not config.checkpoint_dir:
            return _numeric_abort(
                "sustained divergence with no checkpoint_dir to roll "
                "back to"
            )
        wait_beat()  # no collective may be outstanding across the restore
        try:
            saver.wait()  # land (or surface) the in-flight cadence write
        except Exception as e:
            print(
                f"[guardrail] in-flight checkpoint write failed before "
                f"rollback ({e!r}); restoring from the last retained "
                "checkpoint",
                file=sys.stderr, flush=True,
            )
            saver.errors.clear()
        replay_obj = ckpt_replay()
        # Host-replay path: the prefetcher samples under replay_lock, so
        # the restore's load_state_dict must hold it too (the device
        # replay serializes on its own dispatch lock). Chunks already
        # prefetched from the pre-rollback buffer are stale-but-valid
        # replay data and may still be consumed.
        restore_lock = (
            contextlib.nullcontext() if use_device_replay else replay_lock
        )
        ckpt_meta: Dict[str, object] = {}
        try:
            if is_multi:
                # Coordinated rollback step (PR-6 election): every process
                # reaches this point on the same chunk (the trigger inputs
                # are replicated), gathers its manifest-valid steps, and
                # restores the greatest COMMON one. In bg_sync mode the
                # election rides the scheduler's lockstep lane like every
                # other host-initiated collective (docs/TRANSFER.md).
                steps_set = set(ckpt_lib.valid_steps(config.checkpoint_dir))

                def _elect() -> int:
                    return multihost.elect_resume_step(steps_set)

                elected = (
                    transfer_sched.run_ordered(
                        _elect, label="rollback_elect"
                    )
                    if bg_sync
                    else _elect()
                )
                if elected < 0:
                    return _numeric_abort(
                        "no manifest-valid checkpoint is common to every "
                        "process"
                    )
                with restore_lock:
                    restored, step, _env = ckpt_lib.restore(
                        config.checkpoint_dir, learner.state, replay_obj,
                        step=elected, config=config, meta_out=ckpt_meta,
                    )
            else:
                with restore_lock:
                    restored, step, _env = ckpt_lib.restore(
                        config.checkpoint_dir, learner.state, replay_obj,
                        step=None, config=config, meta_out=ckpt_meta,
                    )
        except (FileNotFoundError, RuntimeError) as e:
            return _numeric_abort(f"no restorable checkpoint ({e})")
        learner.state = jax.device_put(restored, learner._state_sharding)
        rolled_from = learn_steps
        learn_steps = step
        last_ckpt = step
        if (
            config.distributional and config.v_support_auto
            and "v_bounds" in ckpt_meta
        ):
            # The restored critic's logits are only meaningful over the
            # atom values it was trained against (resume-path rule).
            learner.set_value_bounds(*ckpt_meta["v_bounds"])
        learner.reset_guard()
        guard_window.clear()
        gstats.record_rollback(step)
        # Reseed exploration: restoring state alone would replay the
        # IDENTICAL sample stream into the identical divergence.
        learner.reseed(0x6A4D + gstats.rollbacks)
        if config.guardrail_lr_backoff < 1.0:
            learner.set_lr_scale(config.guardrail_lr_backoff)
            lr_backoff_since[0] = learn_steps
        if jax.process_index() == 0:
            # Diverged-timeline checkpoints must not win a later resume
            # race (a crash before the next clean save would otherwise
            # restore exactly the state just rolled away from).
            ckpt_lib.discard_above(config.checkpoint_dir, step)
        if host_actors:
            with read_back("refresh"):
                pool.broadcast(learner.actor_params_to_host(), learn_steps)
        if device_pool is not None:
            # The restored state is a fresh tree; swap the rollout's live
            # param pointer so the repaired policy acts immediately.
            device_pool.set_params(learner.policy_params(), learn_steps)
            if "devactor_carry" in ckpt_meta:
                # Roll the rollout state back with the learner: episodes
                # continue from the restored point, not from E resets.
                device_pool.load_carry_state(ckpt_meta["devactor_carry"])
        next_refresh = learn_steps + config.param_refresh_every
        last_refresh_t = time.perf_counter()
        # The rebuilt programs recompile at the next dispatch — same
        # allowance discipline as a support expansion.
        _grant_all(max(300.0, 2.0 * config.watchdog_s))
        trace.instant("rollback", step=step, rolled_from=rolled_from)
        print(
            f"[guardrail] ROLLBACK #{gstats.rollbacks}: restored "
            f"manifest-valid step {step} (diverged at ~{rolled_from}); "
            "exploration reseeded"
            + (
                f", LR x{config.guardrail_lr_backoff} until "
                f"{config.guardrail_lr_cooldown_steps} clean steps pass"
                if config.guardrail_lr_backoff < 1.0
                else ""
            ),
            file=sys.stderr, flush=True,
        )
        return True

    def _guardrail_monitor() -> bool:
        """Per-chunk health check: read the probe's health word (one tiny
        d2h — the only per-chunk sync guardrails add), difference it into
        the rolling anomaly window, attribute bad rows, and trigger
        rollback / LR-cooldown transitions. Returns True when this chunk's
        remaining work should be skipped (rollback or abort happened) —
        a replicated decision, so pods skip the same beats everywhere."""
        h = learner.poll_health()
        if h is None:
            return False
        delta = gstats.absorb(h)
        if delta["bad_rows"] > 0:
            _guard_quarantine_sources()
        if delta["anomalies"] > 0:
            # first_bad_beat: only present when a multi-beat superstep's
            # stacked health vector localized the first offending beat
            # (learner.poll_health); -1 / absent on scalar polls.
            first_bad = int(h.get("first_bad_beat", -1))
            trace.instant(
                "nan_batch", step=learn_steps,
                anomalies=delta["anomalies"],
                nonfinite=delta["nonfinite"], spikes=delta["spikes"],
                first_bad_beat=first_bad,
            )
            print(
                f"[guardrail] {delta['anomalies']} anomalous learner "
                f"step(s) in the chunk ending at {learn_steps} "
                f"(nonfinite {delta['nonfinite']}, z-spikes "
                f"{delta['spikes']}, bad replay rows {delta['bad_rows']})"
                + (
                    f", first bad beat {first_bad} of the superstep"
                    if first_bad >= 0
                    else ""
                )
                + " — update(s) dropped on device",
                file=sys.stderr, flush=True,
            )
            guard_window.append((learn_steps, delta["anomalies"]))
        # Effective window: never narrower than two sync points. Health
        # lands once per chunk stamped at the chunk's END (once per
        # SUPERSTEP — B chunks — when superstep_beats > 1), so a window
        # below that stride (TPU chunks auto-resolve to 800 vs the
        # 256-step default window) would prune every previous entry
        # immediately and the trigger could only ever see one poll.
        win = max(
            config.guardrail_rollback_window,
            2 * chunk * max(1, config.superstep_beats),
        )
        lo = learn_steps - win
        guard_window[:] = [(s, n) for s, n in guard_window if s > lo]
        handled = False
        if (
            config.guardrail_rollback_k > 0
            and sum(n for _, n in guard_window)
            >= config.guardrail_rollback_k
        ):
            handled = _rollback_or_abort()
        if (
            not handled
            and lr_backoff_since[0] >= 0
            and not guard_window
            and learn_steps - lr_backoff_since[0]
            >= config.guardrail_lr_cooldown_steps
        ):
            learner.set_lr_scale(1.0)
            lr_backoff_since[0] = -1
            gstats.record_lr_cooldown()
            trace.instant("lr_cooldown", step=learn_steps)
            _grant_all(max(300.0, 2.0 * config.watchdog_s))
            print(
                f"[guardrail] LR cooldown complete at step {learn_steps}:"
                " learning rates restored",
                file=sys.stderr, flush=True,
            )
        return handled

    def _poison_packed(packed):
        """numeric:replay:inf@k chaos (faults.py): the k-th ingested row
        (1-based, per process) lands with reward=+inf. Runs on the packed
        wire block just before add_packed, so the poisoned row takes the
        REAL ingest path into replay — the bad-row sample detector and
        its source attribution are exercised end to end."""
        base = ingested_rows[0]
        m = len(packed)
        reward_col = obs_spec.words + spec.act_dim
        for at in numeric_replay_at:
            if base < at <= base + m:
                packed[at - base - 1, reward_col] = np.inf
                print(
                    f"[chaos] numeric:replay:inf — poisoned ingested row "
                    f"{at} (reward=+inf)",
                    file=sys.stderr, flush=True,
                )
        ingested_rows[0] = base + m
        return packed

    def drain() -> int:
        # Ingest rate limiter (config.max_ingest_ratio): when the budget is
        # exhausted, skip draining — transports fill and workers block,
        # throttling env stepping until the learner catches up. The budget
        # also CAPS each drain (max_rows): after a long gap (first-chunk
        # compile) the rings hold thousands of buffered steps, and draining
        # them all at once would blow straight past the ratio (and possibly
        # total_env_steps) in one call.
        if (
            use_device_replay
            and is_multi
            and device_replay.pending_rows >= 8 * device_replay.block_size
        ):
            # Backpressure: sync_ship only moves min-over-processes blocks,
            # so a host whose actors outpace the slowest host would grow
            # _pending without bound. Stop draining instead — the rings
            # fill and that host's workers block until the pod catches up.
            return 0
        max_rows = None
        if config.max_ingest_ratio > 0.0:
            allowed = (
                max(config.replay_min_size, config.batch_size)
                + config.max_ingest_ratio * learn_steps
            )
            max_rows = int(allowed) - env_steps()
            if max_rows <= 0:
                return 0
        if use_device_replay:
            moved = 0
            track = guard_on and config.guardrail_source_offenses > 0
            for wid, batch in pool.drain_batches(
                max_rows=max_rows, with_sources=True
            ):
                packed = pack_batch_np(batch)
                if numeric_replay_at:
                    packed = _poison_packed(packed)
                device_replay.add_packed(
                    packed, source=wid if track else -1
                )
                moved += len(batch["reward"])
            return moved
        with replay_lock:
            return pool.drain_into(replay, max_rows=max_rows)

    def ingest_once(force_ship: bool = False, sync_wait: bool = True) -> int:
        """One ingest beat: drain actor transports (timed), then — multi-host
        only — the UNCONDITIONAL lockstep sync_ship collective. Every site
        that ingests on the hot path must go through here: the drain gate
        uses process-LOCAL counters, so the collective must not be skippable
        on some processes (replay/device.py sync_ship). Single-process,
        add_packed only stages into the host ring when the async shipper is
        on — the device work happens off this thread (docs/INGEST.md).

        sync_wait=False (steady-state loop, bg_sync mode) issues the
        collective as a background beat and leaves the ticket pending;
        wait_beat() resolves it before the next dispatch. Exactly one
        beat is ever outstanding — each issue waits its predecessor."""
        with phases.phase("ingest"):
            moved = drain()
            env_timer.tick(moved)
        if use_device_replay and is_multi:
            wait_beat()  # at most one outstanding beat (no-op if none)
            if bg_sync and not sync_wait and not force_ship:
                pending_beat["t"] = device_replay.sync_ship_begin()
            else:
                # force / warmup: synchronous semantics (still routed
                # through the lockstep lane in bg mode — replay/device.py
                # sync_ship keeps the collective order identical).
                device_replay.sync_ship(force=force_ship)
        return moved

    def buffer_fill() -> int:
        return len(device_replay) if use_device_replay else len(replay)

    def host_env_steps() -> int:
        """Env steps from the HOST pool only (process-local on multi-host
        — each process drains its own workers)."""
        return env_steps_offset + pool.steps_received

    def env_steps() -> int:
        n = host_env_steps()
        if device_pool is not None:
            # Device-actor steps are GLOBAL production (the rollout is one
            # SPMD program over the whole mesh), identical on every
            # process — added once here, never summed across processes.
            n += device_pool.steps_done
        return n

    def devactor_step(budget_now: Optional[int] = None) -> int:
        """One device-actor rollout chunk (actors/device_pool.py), gated
        by the same ingest-ratio budget the host drain honors. The gate's
        inputs must be replica-identical on multi-host (every process must
        dispatch the same global rollout programs in the same order):
        learn_steps and devactor steps are lockstep, and the env-step
        basis is the caller-provided globally-agreed budget_now when
        available, else the cached global gather (multi-host) or the local
        count (single-process — exact)."""
        if device_pool is None:
            return 0
        if config.max_ingest_ratio > 0.0:
            allowed = min_fill + config.max_ingest_ratio * learn_steps
            basis = budget_now
            if basis is None:
                basis = cached_global[0] if is_multi else env_steps()
            # The allowance is in rows: steps still in the envs' n-step
            # windows have written none yet (and a warm-up that counted
            # them could close the gate short of min_fill for good).
            basis -= device_pool.pending_rows
            # Any remaining allowance admits ONE chunk (bounded overshoot
            # of rows_per_chunk - 1, the host drain's one-queue-batch
            # semantics): an all-or-nothing gate would wedge warmup
            # whenever rows_per_chunk > min_fill — the allowance could
            # never open because learning hasn't started.
            if basis >= allowed:
                return 0
        if is_multi:
            # Ordering: a queued background sync_ship beat is a global
            # device program; the rollout dispatch must not race its
            # enqueue or per-process device-op order forks (the
            # docs/TRANSFER.md token protocol). No-op when none pending.
            wait_beat()
        with phases.phase("devactor"):
            rows = device_pool.run_chunk(device_replay, learn_steps)
        env_timer.tick(rows)
        return rows

    def global_env_steps() -> int:
        """SUM of env steps over processes, all-gathered so every process
        sees the identical number. The loop condition must be globally
        agreed — a process-local condition would let processes exit at
        different iterations and deadlock the rest on the next collective.
        (total_env_steps is therefore a GLOBAL budget on multi-host runs:
        64 actors across 4 hosts share it.) In bg_sync mode the gather
        runs on the scheduler's lockstep lane: with background sync_ship
        beats possibly queued, NO host-initiated collective may bypass
        the lane or the per-process collective order would fork
        (docs/TRANSFER.md)."""
        from distributed_ddpg_tpu.parallel.multihost import allgather_scalar

        def gather() -> int:
            # Host-pool steps are per-process (summed); device-actor steps
            # are already global (one SPMD rollout over the whole mesh,
            # the same count on every process) — added ONCE, not gathered.
            total = int(allgather_scalar(np.int64(host_env_steps())).sum())
            if device_pool is not None:
                total += device_pool.steps_done
            return total

        if bg_sync:
            return transfer_sched.run_ordered(
                gather, label="env_steps_allgather"
            )
        return gather()

    next_refresh = 0
    last_eval = 0
    last_refresh_t = 0.0
    last_log_t = 0.0
    # Fleet supervision cadence. Monitor must run on WALL CLOCK, not on
    # learner progress: with a rate cap armed, a fully-dead fleet freezes
    # learn_steps between log-cadence multiples, and a monitor called only
    # from the log gate would never run again — no respawns, run wedged
    # (observed live: crash+hang killed both workers during the first
    # compile; the learner sprinted to its cap and froze one chunk short
    # of the next 400-multiple).
    last_monitor_t = 0.0
    support_controller = support_auto.SupportController()
    # The most recent dispatch's output: the final record reports its
    # chunk-mean losses, so a run shorter than one log cadence (50
    # chunks) still says what the learner last computed.
    last_out: list = [None]
    # Compile apart from steady state, for the final record: seconds from
    # entering the steady loop until the first chunk had compiled, run and
    # had its params fetched (the first after_chunk's refresh d2h syncs
    # it), and the seconds the loop ran after that.
    loop_times: Dict[str, float] = {}

    # --- pod telemetry aggregation (obs/aggregate.py; docs/
    # OBSERVABILITY.md §4) --- multi-process only: on each log cadence
    # every process contributes a milli-scaled int64[4] snapshot (beat
    # time, ingest rate, transfer backlog, wall clock) over the SAME
    # uniform int64 allgather lane the env-step budget rides, and every
    # process computes the identical per-host spread + straggler verdict
    # from the gathered matrix. Rank 0 alone logs the `kind:"pod"`
    # record — the aggregation view is pod-global, one writer suffices.
    pod_agg = None
    if is_multi:
        pod_agg = PodAggregator(
            gather_fn=lambda vec: multihost.allgather_scalar(
                vec, label="pod_obs_gather"
            ),
            stats=pod_stats,
        )

    def after_chunk(out, indices, fused: bool = False,
                    beats: int = 1) -> None:
        # `beats`: how many fused beats the dispatch that produced `out`
        # advanced (a B-beat superstep passes B; everything else 1). All
        # step accounting scales by it; `out` is the FINAL beat's output,
        # which is exactly what B sequential after_chunk calls would have
        # left visible at this point.
        nonlocal learn_steps, last_ckpt, next_refresh, last_eval
        nonlocal last_refresh_t, last_log_t
        last_out[0] = out
        learn_steps += chunk * beats
        launches.add(jax.tree.leaves(out.metrics)[0], chunk * beats)
        if device_pool is not None:
            # Device-actor param refresh: pointer swap to the LIVE params,
            # re-done every chunk because the dispatch above DONATED the
            # previous TrainState (the stale tree is deleted — dispatching
            # a rollout against it would raise). Free: no copy, no d2h.
            # With no host worker this swap IS the topology's refresh, so
            # the `refresh` phase brackets it and the broadcast below (a
            # d2h that waits out the launch queue, for nobody) is not
            # issued.
            with (
                phases.phase("refresh", learner_step=learn_steps)
                if not host_actors else contextlib.nullcontext()
            ):
                device_pool.set_params(
                    learner.policy_params(), learn_steps
                )
        if guard_on and _guardrail_monitor():
            # Rolled back (or numeric-aborted): this chunk's `out` is
            # moot, the rollback already rebroadcast params, and skipping
            # the rest — including the per-chunk sync_ship beat — is a
            # REPLICATED decision (identical health counters everywhere),
            # so a pod's collective schedule stays aligned.
            return
        # Device rollout BEFORE the ingest beat: in bg_sync mode
        # ingest_once issues a background lockstep beat, and enqueuing the
        # rollout first keeps the per-process device-op order a pure
        # function of the (lockstep) iteration count. A FUSED beat already
        # ran the rollout + insert inside its one program, so only the
        # host-row ingest beat (drains + the unconditional multi-host
        # lockstep/shard_exchange collective) remains.
        if not fused:
            devactor_step()
        else:
            # The beat's in-program rollout produced its rows without a
            # devactor_step dispatch; keep the shared actor-rate meter
            # (actor_steps_per_sec) fed so a healthy fused run never
            # reads as a stalled actor fleet.
            env_timer.tick(device_pool.rows_per_chunk * beats)
        ingest_once(sync_wait=False)

        if config.prioritized and not use_device_replay:
            # Host PER: priorities live in the CPU sum-tree; the device path
            # updates its priority vector inside the fused chunk instead.
            with phases.phase("prio_update"):
                _host_per_update(out, indices)

        # param_refresh_every is in LEARNER STEPS (config.py); refresh on
        # every crossing of a multiple (chunks advance `chunk` steps at a
        # time). The wall-clock floor (param_refresh_interval_s) bounds the
        # refresh's pipeline-sync + d2h cost to a fixed fraction of wall
        # time — without it a per-chunk broadcast serializes the device
        # pipeline (each one waits out the in-flight chunk).
        # strict_sync ignores the wall-clock floors on refresh and logging:
        # both would make the training schedule (which params act, which
        # chunks log) a function of host timing instead of the config,
        # breaking the bit-identical-two-runs contract.
        now = time.perf_counter()
        if host_actors and learn_steps >= next_refresh and (
            config.strict_sync
            or now - last_refresh_t >= config.param_refresh_interval_s
        ):
            with read_back("refresh", learner_step=learn_steps):
                pool.broadcast(learner.actor_params_to_host(), learn_steps)
            next_refresh = learn_steps + config.param_refresh_every
            last_refresh_t = time.perf_counter()

        # Cadence = crossing a 50-chunk multiple, not landing on one: a
        # B-beat superstep advances chunk*B steps per call, and B need
        # not divide 50 — the `% == 0` form would skip every cadence
        # whose multiple falls strictly inside a superstep. For beats=1
        # the crossing test reduces to the exact `% == 0` it replaces.
        on_cadence = (
            learn_steps // (50 * chunk)
            != (learn_steps - chunk * beats) // (50 * chunk)
        )
        chunk_metrics = None
        support_metrics = {}
        if on_cadence and config.distributional and config.v_support_auto:
            # Replica-state read below (replay_data_bounds pulls reward
            # columns from the replicated storage): the outstanding
            # background beat must land first so every process reads the
            # identical buffer state at this cadence point.
            wait_beat()
            # Running expansion (ops/support_auto.py): mean_q drifting
            # toward a support edge means the critic is about to saturate
            # (projection clips, mean_q can never cross the edge) — push
            # that edge out geometrically. The check sits OUTSIDE the
            # wall-clock log gate below: the cadence and mean_q (pmean'd,
            # replicated) are identical on every process, so every replica
            # takes the same expansion on the same chunk — a per-process
            # wall-clock gate here would rebuild programs on some replicas
            # only and fork the mesh. Each expansion costs one XLA
            # recompile at the next dispatch, granted to the watchdog like
            # the initial compile.
            with read_back("sync", learner_step=learn_steps):
                chunk_metrics = learner.metrics_to_host(out)
            # data_bounds_fn: re-derive the rule-1 bound from the replay's
            # CURRENT rewards so a diverging critic can't drag the support
            # up (support_auto module docstring, seed-1 incident). The
            # reward column is replica-identical (replay is replicated /
            # lockstep-shipped across processes), so every replica still
            # takes the same decision and the mesh cannot fork.
            _support_source = device_replay if use_device_replay else replay
            grown = support_controller.check(
                learner.config.v_min,
                learner.config.v_max,
                chunk_metrics["mean_q"],
                learn_steps,
                data_bounds_fn=lambda: support_auto.replay_data_bounds(
                    _support_source, config.gamma, config.n_step
                ),
            )
            if grown is not None:
                learner.set_value_bounds(*grown)
                _grant_all(max(300.0, 2.0 * config.watchdog_s))
                print(
                    f"auto C51 support expanded to "
                    f"[{grown[0]:.1f}, {grown[1]:.1f}] "
                    f"(mean_q {chunk_metrics['mean_q']:.1f})"
                )
            support_metrics = dict(
                v_min=learner.config.v_min,
                v_max=learner.config.v_max,
                support_refusals=support_controller.refusals,
            )

        if on_cadence:
            # Reversible degraded conditions re-sampled every cadence
            # (obs/health.py note() both raises and clears): a pod that
            # shrank back to strength or a quarantine that lifted takes
            # /healthz back to `healthy` at the next cadence.
            health.get().note("pod_state_degraded", pod_stats.degraded)
            if guard_on:
                health.get().note(
                    "guardrail_quarantine", gstats.source_quarantines > 0
                )
        if on_cadence and pod_agg is not None:
            # Cross-host aggregation gather. Sits OUTSIDE the wall-clock
            # log gate below: that gate reads per-process wall time, so
            # processes disagree on it, and a collective issued under it
            # would fork the pod's collective order. Here the cadence
            # (replica-identical learn_steps) is the only gate. bg_sync
            # runs ride the scheduler's lockstep lane like every other
            # host-initiated collective (docs/TRANSFER.md).
            def _pod_collect():
                return pod_agg.collect(
                    beats=learn_steps // chunk,
                    ingest_rows=host_env_steps(),
                    transfer_backlog=(
                        sum(transfer_sched.queue_depths().values())
                        if transfer_sched is not None
                        else 0
                    ),
                )

            with phases.phase("pod_obs"):
                pod_record = (
                    transfer_sched.run_ordered(
                        _pod_collect, label="pod_obs_allgather"
                    )
                    if bg_sync
                    else _pod_collect()
                )
            if pod_record is not None and jax.process_index() == 0:
                log.log("pod", env_steps(), **pod_record)

        if on_cadence and (config.strict_sync or now - last_log_t >= 1.0):
            last_log_t = now
            pool.monitor()
            episodes = pool.episode_stats()
            mean_ret = (
                float(np.mean([e[1] for e in episodes])) if episodes else None
            )
            if chunk_metrics is None:
                with read_back("sync", learner_step=learn_steps):
                    chunk_metrics = learner.metrics_to_host(out)
            learn_timer.tick(launches.settle())
            log.log(
                "train", env_steps(),
                learner_steps=learn_steps,
                **delay_fields(),
                learner_steps_per_sec=learn_timer.rate(),
                actor_steps_per_sec=env_timer.rate(),
                buffer_fill=buffer_fill(),
                episode_return=mean_ret,
                **(pool if host_actors else device_pool).staleness(),
                **pool.nstep_counters(),
                **pool.policy_forward(),
                **recovery_fields(),
                **chunk_metrics,
                **support_metrics,
                **phases.snapshot(),
                **launches.snapshot(),
                # Ingest pipeline observability (replay/device.py
                # IngestStats): rows/sec shipped to HBM, coalesce factor,
                # staging-queue depth, producer stall time.
                **(
                    device_replay.ingest_snapshot()
                    if use_device_replay
                    else {}
                ),
                # Transfer-scheduler observability (docs/TRANSFER.md):
                # per-class dispatches/bytes/tails, queue depths, the
                # adaptive-coalesce trajectory, restart count.
                **transfer_fields(),
                # Pod resilience (docs/RESILIENCE.md pod rows).
                **pod_fields(),
                # Numerical health (docs/RESILIENCE.md; guardrails.py).
                **guardrail_fields(),
                # Inference serving (docs/SERVING.md; serve/).
                **serve_fields(),
                # Device-actor rollouts (docs/DEVICE_ACTORS.md).
                **devactor_fields(),
                # Fused megastep beats (docs/FUSED_BEAT.md).
                **fused_fields(),
                # Mesh placement facts (docs/MESH.md).
                **mesh_fields(),
            )

        # Periodic eval (SURVEY.md §2 #1 'periodic eval & checkpoint'):
        # deterministic CPU rollout of a param snapshot in a background
        # thread (start_eval above) — the learner keeps dispatching.
        if config.eval_every and env_steps() - last_eval >= config.eval_every:
            start_eval(env_steps())
            last_eval = env_steps()

        if (
            config.checkpoint_dir
            and learn_steps - last_ckpt >= config.checkpoint_every
        ):
            with read_back("ckpt"):
                # Learner state is replicated across processes, so ONE
                # writer suffices for the orbax tree (and shared-FS
                # writes must not collide). Async: only the HBM->host
                # snapshot happens here; the disk write runs on the
                # saver's thread (checkpoint.py AsyncSaver).
                if jax.process_index() == 0:
                    saver.save_async(
                        config.checkpoint_dir, learn_steps, learner.state,
                        ckpt_replay(), config,
                        env_steps=env_steps(),
                        devactor_state=(
                            device_pool.carry_state_dict()
                            if device_pool is not None
                            else None
                        ),
                        v_bounds=(
                            (learner.config.v_min, learner.config.v_max)
                            if config.distributional and config.v_support_auto
                            else None
                        ),
                        keep=config.checkpoint_keep,
                        retries=config.ckpt_write_retries,
                        backoff_s=config.ckpt_retry_backoff_s,
                        fault=ckpt_fault,
                    )
                # Sharded replay is NOT replicated: every shard owner
                # writes its slice at the same cadence step (all-writer,
                # docs/REPLAY_SHARDING.md). learn_steps is lockstep-
                # identical, so the slice sets line up by construction.
                write_replay_slices(learn_steps)
            last_ckpt = learn_steps

    def dispatch(run, *args, **kwargs):
        """One launch of the chunk program: every dispatch site goes
        through here. The span carries the launch's index since the run
        began, so the k-th `dispatch` on the profiler's host plane lies
        beside the k-th launch on the device plane, and the launches
        the device had not finished as this one was made."""
        learn_timer.tick(launches.poll())
        with phases.phase(
            "dispatch", chunk=launches.n_dispatched, in_flight=len(launches)
        ):
            return run(*args, **kwargs)

    def _host_per_update(out, indices) -> None:
        tds = np.asarray(out.td_errors).reshape(-1)
        with replay_lock:
            replay.update_priorities(indices.reshape(-1), tds)
            frac = min(1.0, env_steps() / config.total_env_steps)
            replay.set_beta(
                config.per_beta
                + frac * (config.per_beta_final - config.per_beta)
            )

    def _emergency_checkpoint() -> None:
        # --- emergency checkpoint (preemption + pod-abort contract) ---
        # One save OFF the hot loop, then a normal teardown. The
        # in-flight cadence write (if any) lands first; its failure
        # must not cost the emergency save. Same-step dedupe: if the
        # cadence already wrote exactly learn_steps, that checkpoint
        # IS the resumable state. Ordinarily only process 0 writes
        # (state is replicated); on a POD abort every survivor writes
        # one — process 0 into checkpoint_dir, the rest into their
        # proc<k> subdir (pod_ckpt_dir) — so a relaunched pod can
        # elect a common step even when each host keeps its own disk.
        _beat()
        try:
            saver.wait()
        except Exception as e:
            print(
                f"[train] in-flight checkpoint write failed during "
                f"preemption ({e!r}); writing the emergency "
                "checkpoint anyway",
                file=sys.stderr, flush=True,
            )
            saver.errors.clear()
        i_write = jax.process_index() == 0 or pod_lost[0] is not None
        my_dir = (
            config.checkpoint_dir if jax.process_index() == 0 else pod_ckpt_dir
        )
        # Sharded replay: every process (not just the learner-tree
        # writer) persists its slice — into the SHARED dir, where the
        # per-proc filenames cannot collide. On a pod abort the dead
        # peer's slice is of course absent, so THIS step's set stays
        # incomplete; adoption falls back to the last cadence step where
        # all writers landed (docs/REPLAY_SHARDING.md).
        write_replay_slices(learn_steps)
        if config.checkpoint_dir and i_write:
            if ckpt_lib.latest_step(my_dir) != learn_steps:
                with read_back("ckpt"):
                    ckpt_lib.save(
                        my_dir, learn_steps,
                        learner.state,
                        ckpt_replay(),
                        config,
                        env_steps=env_steps(),
                        devactor_state=(
                            device_pool.carry_state_dict()
                            if device_pool is not None
                            else None
                        ),
                        v_bounds=(
                            (learner.config.v_min, learner.config.v_max)
                            if config.distributional
                            and config.v_support_auto
                            else None
                        ),
                        keep=config.checkpoint_keep,
                        retries=config.ckpt_write_retries,
                        backoff_s=config.ckpt_retry_backoff_s,
                        fault=ckpt_fault,
                    )
            emergency_ckpt[0] = 1
            trace.instant("emergency_ckpt", step=learn_steps)
            print(
                f"[train] emergency checkpoint at learner step "
                f"{learn_steps} (env step {env_steps()}) — resumable",
                file=sys.stderr, flush=True,
            )

    prefetch = None
    try:
        # --- warmup: fill replay to the learning threshold (min_fill) ---
        # The per-iteration _beat below keeps the watchdog quiet even when
        # ingest_once() moves nothing, so a total actor-side stall (workers
        # heartbeating but producing no experience — e.g. every env wedged)
        # would otherwise burn the whole wall-clock budget unseen. The
        # secondary deadline catches that: no rows for 10x watchdog_s is a
        # loud RuntimeError (normal teardown runs — the learner thread
        # itself is healthy here, unlike the device wedges the watchdog's
        # os._exit exists for).
        stall_deadline = (
            10.0 * config.watchdog_s if config.watchdog_s > 0 else 0.0
        )
        last_moved_t = time.monotonic()

        def _check_actor_stall(where: str) -> None:
            if stall_deadline and time.monotonic() - last_moved_t > stall_deadline:
                raise RuntimeError(
                    f"{where}: no experience ingested for "
                    f"{stall_deadline:.0f}s (10x watchdog_s) with the "
                    "learner thread healthy — actor-side stall; aborting "
                    "instead of burning the wall-clock budget"
                )

        setup.stage("setup_fill")
        warm_it = 0
        while buffer_fill() < min_fill and not preempt.is_set():
            # Lockstep warmup ingest: loop count is driven by the
            # globally-replicated buffer size and `warm_it` advances
            # identically everywhere, so every process calls sync_ship
            # (inside ingest_once) the same number of times. Periodic
            # force pads a block from sub-block trickles so slow actors
            # still cross the threshold.
            moved = ingest_once(force_ship=(warm_it % 20 == 19))
            moved += devactor_step()
            _beat()
            pool.monitor()
            if (
                use_device_replay
                and not is_multi
                and buffer_fill() + device_replay.pending_rows >= min_fill
            ):
                # NOT gated on `moved`: this check races the async
                # shipper — at the instant it ships a block, the rows are
                # already popped from the ring (pending drops) but the
                # insert hasn't updated size yet (fill unchanged), so the
                # sum transiently under-counts. With a drain cap
                # (max_ingest_ratio) the crossing iteration can be the
                # LAST one with moved > 0, and a moved-gated check that
                # lost the race would never re-fire: sub-block remainder
                # rows sit staged forever while drains return 0 — a
                # warmup livelock (observed: fill 1024 + pending 476
                # against min_fill 1500, wedged). Re-evaluating every
                # iteration self-heals; flush() is idempotent-cheap when
                # there is nothing staged, and the loop exits as soon as
                # the fill crosses, so at most one padded ship happens.
                device_replay.flush()
            if moved:
                last_moved_t = time.monotonic()
            else:
                _check_actor_stall("warmup")
                time.sleep(0.05)
            warm_it += 1

        trace.instant("warmup_done", buffer_fill=buffer_fill())
        if use_device_replay and is_multi and fault_plan:
            # Pod chaos site (pod:<proc>:kill|hang@beat): armed at the
            # warmup/steady boundary — a lockstep point — so `@beat`
            # counts STEADY-STATE beats (one per learner chunk, the same
            # ordinal on every process) instead of depending on how many
            # wall-clock-paced warmup iterations actor startup needed.
            device_replay.arm_pod_fault(
                fault_plan.pod_site(jax.process_index())
            )
        if (
            config.distributional and learner.config.v_support_auto
            and not preempt.is_set()  # partial warmup: no stats to size from
        ):
            # C51 auto-support (ops/support_auto.py): size [v_min, v_max]
            # from the warmup replay's (n-step) reward statistics. Gated on
            # the LEARNER's config: a resume that restored checkpointed
            # bounds above has already resolved them, and re-deriving would
            # reinterpret the restored critic. Must happen before the first
            # dispatch: jit is lazy, so the rebuild costs no extra compile.
            source = device_replay if use_device_replay else replay
            v_lo, v_hi = support_auto.replay_data_bounds(
                source, config.gamma, config.n_step
            )
            learner.set_value_bounds(v_lo, v_hi)
            print(
                f"auto C51 support: [{v_lo:.1f}, {v_hi:.1f}] from warmup "
                "reward statistics"
            )

        prefetch = None
        if not use_device_replay and not preempt.is_set():
            prefetch = ChunkPrefetcher(
                replay, learner.put_chunk, learner.global_batch, chunk,
                # Double buffer: one chunk sampled and put while the
                # learner consumes the other.
                depth=2, lock=replay_lock,
                fault=(
                    fault_plan.site("prefetch", "sample")
                    if fault_plan else None
                ),
                # Single-process only: multi-host put_chunk is itself a
                # cross-process device op, and only the lockstep lane may
                # issue those off the learner thread (docs/TRANSFER.md).
                scheduler=(transfer_sched if not is_multi else None),
            ).start()

        # Rates below report the steady state, not compile/warmup time.
        learn_timer.reset()
        env_timer.reset()

        # The first dispatch includes the full XLA compile of the chunk
        # program (~20-40s single-chip; larger nets / multihost meshes can
        # take minutes) — grant the watchdog a one-time extra allowance so
        # a slow compile isn't killed as a false stall (same exit 70 as a
        # real wedge). Consumed by the first post-compile beat; steady-state
        # iterations run on the plain watchdog_s window.
        _grant_all(max(300.0, 2.0 * config.watchdog_s))

        with profile_cm:
            # Multi-host: the global budget is re-gathered every 10th
            # iteration, not every chunk — the cadence is deterministic in
            # the (lockstep) iteration count, so processes stay in step,
            # and the hot loop pays one budget collective per 10 chunks
            # instead of one per chunk. Overshoot is bounded by 10 chunks
            # of ingest — noise against BASELINE-scale budgets.
            it = 0
            last_budget = -1
            first_dispatch_done = False
            t_loop0 = time.monotonic()
            setup.stage("setup_first_chunk")
            while not preempt.is_set() and not numeric_failed[0]:
                _beat()
                # Wall-clock fleet supervision (see last_monitor_t note):
                # every iteration reaches this, including the rate-capped
                # ingest spin below — a dead fleet respawns even when the
                # learner cannot advance.
                if time.monotonic() - last_monitor_t >= 1.0:
                    last_monitor_t = time.monotonic()
                    pool.monitor()
                if is_multi:
                    if it % 10 == 0:
                        cached_global[0] = global_env_steps()
                    budget_now = cached_global[0]
                else:
                    budget_now = env_steps()
                if budget_now >= config.total_env_steps and learn_steps > 0:
                    trace.instant(
                        "budget_met", env_steps=budget_now,
                        learn_steps=learn_steps,
                    )
                    # `learn_steps > 0` guards the degenerate exit where fast
                    # actors deliver the entire env-step budget during warmup
                    # (max_ingest_ratio=0 = free ingest): a run that has met
                    # replay_min_size must take at least one gradient chunk
                    # before the budget break is honored, or it would report
                    # success with learner_steps=0. One chunk later the break
                    # fires; learn_steps advances in lockstep on multi-host,
                    # so every process exits on the same iteration.
                    break
                # Actor-stall coverage for EVERY post-warmup path (the
                # per-iteration _beat keeps the watchdog quiet whether or
                # not env steps arrive): with the default max_learn_ratio=0
                # the loop below dispatches forever on stale replay if all
                # workers wedge, and with a cap it spins in the ingest
                # branch — either way env-step progress is the one signal
                # that actors are alive, so it drives the stall clock.
                # AFTER the budget break: a budget already met during
                # warmup is a finishing run, not a stall. The first
                # dispatch resets the clock below (its XLA compile gets
                # the same allowance the watchdog grant gives it — a
                # compile longer than the deadline must not read as a
                # stalled actor fleet).
                if budget_now > last_budget:
                    last_budget = budget_now
                    last_moved_t = time.monotonic()
                else:
                    _check_actor_stall("train loop")
                if config.max_learn_ratio > 0.0 and learn_steps > 0 and (
                    learn_steps + chunk
                    > max(config.replay_min_size, config.batch_size)
                    + config.max_learn_ratio * budget_now
                ):
                    # Learner-rate cap (config.max_learn_ratio): ahead of
                    # the allowance — ingest instead of dispatching until
                    # env steps catch up. The decision uses budget_now,
                    # which is globally agreed on multi-host, so every
                    # process skips the same iterations and the SPMD
                    # collective schedule stays aligned (same reasoning as
                    # the loop-exit condition above).
                    moved_now = ingest_once(sync_wait=False)
                    moved_now += devactor_step(budget_now)
                    if not moved_now:
                        time.sleep(0.002)
                    it += 1
                    continue
                # Dispatch gate (bg_sync): the previous background beat
                # must be ENQUEUED before the next chunk dispatch so the
                # per-process device-op order stays identical everywhere
                # (docs/TRANSFER.md token protocol). No-op otherwise.
                wait_beat()
                if use_device_replay:
                    if megastep is not None and config.superstep_beats > 1:
                        # Multi-beat superstep (docs/FUSED_BEAT.md): B
                        # fused beats as ONE fori_loop program. The PER
                        # beta anneal rides in as a host-precomputed
                        # float32[B] vector reproducing the per-beat
                        # sequential schedule (globally-agreed budget_now
                        # so replicas never fork; rows advance
                        # rows_per_chunk per in-loop beat).
                        betas = None
                        if config.prioritized:
                            from distributed_ddpg_tpu.parallel.superstep \
                                import per_beat_betas

                            betas = per_beat_betas(
                                config, budget_now, megastep.beats,
                                device_pool.rows_per_chunk,
                            )
                        out = dispatch(megastep.run_superstep, betas=betas)
                        after_chunk(
                            out, None, fused=True, beats=megastep.beats
                        )
                    elif megastep is not None:
                        # Fused megastep (docs/FUSED_BEAT.md): rollout +
                        # scatter + sample + K updates in ONE program. The
                        # PER beta anneal rides in as a scalar exactly like
                        # the unfused dispatch (globally-agreed budget_now
                        # so replicas never fork).
                        beta = None
                        if config.prioritized:
                            frac = min(1.0, budget_now / config.total_env_steps)
                            beta = config.per_beta + frac * (
                                config.per_beta_final - config.per_beta
                            )
                        out = dispatch(megastep.run_beat, beta=beta)
                        # NOT the shared after_chunk call below: the beat
                        # already ran the rollout+insert, and running
                        # after_chunk twice would double every cadence.
                        after_chunk(out, None, fused=True)
                    elif config.prioritized:
                        # beta anneal rides in as a scalar arg. It must be
                        # computed from a globally-identical value
                        # (budget_now — cached global on multi-host), NOT
                        # process-local env steps: beta feeds the replicated
                        # IS weights, so divergent betas would fork the
                        # replicas.
                        frac = min(1.0, budget_now / config.total_env_steps)
                        beta = config.per_beta + frac * (
                            config.per_beta_final - config.per_beta
                        )
                        out = dispatch(
                            learner.run_sample_chunk_per, device_replay, beta
                        )
                        after_chunk(out, None)
                    else:
                        out = dispatch(
                            learner.run_sample_chunk, device_replay
                        )
                        after_chunk(out, None)
                else:
                    with phases.phase("sample_wait"):
                        device_chunk, indices = prefetch.next()
                    out = dispatch(learner.run_chunk_async, device_chunk)
                    after_chunk(out, indices)
                if not first_dispatch_done:
                    # The first dispatch blocks on the chunk program's XLA
                    # compile (minutes on big meshes); that time must not
                    # count against the actor-stall clock.
                    first_dispatch_done = True
                    last_moved_t = time.monotonic()
                    # Set-up ends here, and its compile counters with it.
                    setup.end()
                    compiles.uninstall()
                    loop_times["first_chunk_s"] = setup.spans[
                        "setup_first_chunk"
                    ]
                it += 1
            setup.end()  # a run that never dispatched (preempted in warm-up)
            if first_dispatch_done:
                loop_times["steady_s"] = (
                    time.monotonic() - t_loop0 - loop_times["first_chunk_s"]
                )

        if prefetch is not None:
            prefetch.stop()

        if preempt.is_set():
            _emergency_checkpoint()
    except multihost.PodPeerLost as e:
        # --- coordinated clean pod abort (docs/RESILIENCE.md pod rows) ---
        # A peer process died or hung mid-collective: every further
        # collective would block (or fork) the pod. Each survivor fails
        # the transfer scheduler's pending tickets (close() fails queued
        # work BEFORE the join — a queued lockstep beat must never fire
        # against a degraded pod), takes one emergency checkpoint through
        # the SIGTERM path's machinery, and exits EXIT_POD_DEGRADED so
        # the driver relaunches the whole pod; the resume election then
        # restores one common step everywhere.
        pod_lost[0] = e
        pod_stats.record_abort()
        preempt.set()  # downstream teardown follows the preemption shape
        _grant_all(max(300.0, 2.0 * config.watchdog_s))
        trace.instant("pod_abort", step=learn_steps)
        print(
            f"[train] pod peer lost: {e}; coordinated clean abort — "
            f"draining transfers, emergency checkpoint, exit "
            f"{EXIT_POD_DEGRADED}",
            file=sys.stderr, flush=True,
        )
        # The outstanding beat ticket (if any) is already failed or
        # failing under the same deadline — never re-wait it.
        pending_beat["t"] = None
        if prefetch is not None:
            try:
                prefetch.stop()
            except Exception:
                pass
        if transfer_sched is not None:
            transfer_sched.close()
        _emergency_checkpoint()
        # Shrink-readiness (docs/RESILIENCE.md state machine): with a
        # complete, digest-verified slice set on disk the dead peer's
        # replay is recoverable — exit EXIT_POD_SHRINK (78) so the
        # driver knows it may relaunch at N-1 instead of waiting for
        # the lost host. No set -> the existing 76 contract.
        pod_shrink_ready[0] = _slices_adoptable()
        if pod_shrink_ready[0]:
            print(
                f"[train] complete replay slice set on disk — "
                f"shrink-ready, exiting {EXIT_POD_SHRINK} (relaunch at "
                "any process count adopts it)",
                file=sys.stderr, flush=True,
            )
    finally:
        if prev_sigterm is not None:
            try:
                import signal as _signal

                _signal.signal(_signal.SIGTERM, prev_sigterm)
            except (ValueError, TypeError):
                pass
        _beat()  # each teardown stage gets a fresh watchdog allowance
        try:
            # Land the outstanding background sync_ship beat (every
            # process issued the same beats, so every process waits here)
            # before tearing down the machinery under it.
            wait_beat()
        except multihost.PodPeerLost as e:
            # A peer died between the loop's last gate and teardown:
            # record the degradation so the exit code still says 76, but
            # keep tearing down (the abort machinery already ran or the
            # run was otherwise complete).
            if pod_lost[0] is None:
                pod_lost[0] = e
                pod_stats.record_abort()
        except Exception:
            pass  # a failing beat must not mask the primary error
        pool.stop()
        _beat()
        if front_server is not None:
            # Network ingress first: stop accepting external traffic
            # before the in-process serving machinery flushes; in-flight
            # requests complete (FrontServer.stop closes every version
            # engine, each draining its batcher).
            front_server.stop()
        if serve_front is not None:
            # After the workers: no new requests can arrive. The front
            # stops first (nothing new enters the batcher), then the
            # server flushes — every accepted request completes before
            # the machinery under it (scheduler) is torn down.
            serve_front.stop()
        if serve_server is not None:
            serve_server.close()
        if use_device_replay and device_replay is not None:
            # Stop the async ingest shipper; add_packed falls back to
            # inline shipping for any teardown stragglers.
            device_replay.close()
        if transfer_sched is not None:
            # After the replay detaches: pending tickets fail loudly into
            # their waiters (a still-running prefetch worker dies with
            # TransferError instead of hanging).
            transfer_sched.close()
        # Land the in-flight checkpoint write (and surface its error, if
        # any) before callers read the directory back.
        saver.wait()
        _beat()
        t = eval_thread["t"]
        if t is not None:
            t.join(timeout=_EVAL_JOIN_S)
        if obs_server is not None and pod_lost[0] is None and not preempt.is_set():
            # Clean exits stop the ingress; a pod abort OR a preemption
            # deliberately keeps it serving — /healthz must answer through
            # the teardown window (pod_degraded_exit's rank-0 linger, the
            # post-SIGTERM checkpoint flush) so a supervisor can scrape
            # the draining verdict before the process disappears. The
            # server thread is a daemon; process exit reaps it.
            obs_server.stop()
        if is_multi:
            # Disarm the module-level pod deadline: a later single-process
            # train in the same interpreter must keep the zero-overhead
            # short-circuit path.
            multihost.configure_pod(0.0)

    # --- final eval with the trained policy (CPU, deterministic) ---
    # Skipped under preemption: the contract is "checkpoint and get out";
    # whole CPU eval episodes would hold the exit for seconds.
    _beat()
    last_metrics: Dict[str, float] = {}
    if preempt.is_set() or numeric_failed[0]:
        # Preemption: "checkpoint and get out". Numeric abort: the params
        # are presumed poisoned — an eval would score garbage. (A pod
        # abort rides the preempt flag: its last chunk may never finish,
        # so nothing here may wait on it.)
        final_return = None
    else:
        final_return = _eval_numpy(
            eval_policy_of(learner.actor_params_to_host()), config, spec
        )
        if last_out[0] is not None:
            last_metrics = learner.metrics_to_host(last_out[0])
    learn_timer.tick(launches.settle())
    rate = learn_timer.rate()
    setup_fields = {
        "setup_spans": {k: round(v, 3) for k, v in setup.spans.items()},
        "setup_compile_s": round(compiles.seconds, 3),
        "setup_programs_compiled": compiles.programs,
        **ckpt_lib.import_fields(),
    }
    # ONE serve/devactor snapshot shared by the final record and the
    # returned summary: both stats reset their interval reservoirs at
    # snapshot, so a second call would report zeroed tails.
    serve_final = serve_fields()
    devactor_final = devactor_fields()
    fused_final = fused_fields()
    # Ingest + replay-placement families (replay/device.py): short runs
    # can finish inside one log cadence, and the final record is where
    # tools.runs reads the placement facts (shard count, bytes/row)
    # regardless. ingest_* are interval-scoped, so one snapshot too.
    ingest_final = (
        device_replay.ingest_snapshot()
        if use_device_replay and device_replay is not None
        else {}
    )
    facts_final = run_facts()
    mesh_final = mesh_fields()
    log.log(
        "final", env_steps(),
        learner_steps=learn_steps,
        **delay_fields(),
        learner_steps_per_sec=rate,
        final_return=final_return,
        **facts_final,
        **loop_times,
        **setup_fields,
        **last_metrics,
        **recovery_fields(),
        **ingest_final,
        **phases.snapshot(),
        **launches.snapshot(),
        **transfer_fields(),
        **pod_fields(),
        **guardrail_fields(),
        **serve_final,
        **devactor_final,
        **fused_final,
        **mesh_final,
    )
    log.close()
    chunk_ops_path = write_chunk_ops(learner, config)
    # Checksum of the final actor params: lets determinism tests (and the
    # multi-host parity test — SPMD replicas must agree bit-for-bit)
    # compare end states without plumbing the whole state out.
    return {
        "learner_steps_per_sec": rate,
        "learner_steps": learn_steps,
        **delay_fields(),
        "final_return": final_return,
        "param_checksum": _param_checksum(learner.actor_params_to_host()),
        # The same sum over the params the run STARTED from (fresh init
        # or restore): differs from param_checksum iff the actor moved.
        "param_checksum_start": checksum_start,
        # Where this run's records went ("" = stdout only).
        "log_path": config.log_path,
        # The chunk program's parts by instruction name, beside the records
        # ("": no records file, or the learner launched no chunk program).
        "chunk_ops_path": chunk_ops_path,
        **facts_final,
        **loop_times,
        **setup_fields,
        **last_metrics,
        # Row accounting at exit: every env step handed to the replay is
        # either in the ring (buffer_fill, capacity permitting) or still
        # staged on the host (ingest_queue_rows) — anything else was lost.
        "env_steps": env_steps(),
        "buffer_fill": buffer_fill(),
        **ingest_final,
        **mesh_final,
        # ... or, for the device pool's steps, still in the envs' n-step
        # windows: taken, counted, and in no ring yet.
        **(
            {
                "ingest_queue_rows": ingest_final.get("ingest_queue_rows", 0)
                + device_pool.pending_rows
            }
            if device_pool is not None and device_pool.pending_rows
            else {}
        ),
        # A pod abort reuses the preemption machinery but is its OWN
        # documented exit (76 vs 75) — report exactly one of the two.
        "preempted": preempt.is_set() and pod_lost[0] is None,
        "pod_degraded": pod_lost[0] is not None,
        # Elastic-shrink readiness: a pod abort with a complete replay
        # slice set on disk exits 78 (relaunch smaller adopts it), 76
        # otherwise (docs/RESILIENCE.md).
        "pod_shrink_ready": bool(pod_shrink_ready[0]),
        # Numeric-health abort (EXIT_NUMERIC=77): guardrails exhausted the
        # rollback budget or had nothing to restore.
        "numeric_failed": numeric_failed[0],
        **recovery_fields(),
        **pod_fields(),
        **guardrail_fields(),
        **serve_final,
        **devactor_final,
        **fused_final,
        # Dispatch-gating fact for tests/operators: True = the fused
        # megastep carried the steady-state loop (docs/FUSED_BEAT.md).
        "fused_beat_active": megastep is not None,
    }


def pod_degraded_exit(linger_s: float = 10.0, code: int = EXIT_POD_DEGRADED) -> None:
    """Exit `code` (EXIT_POD_DEGRADED, or EXIT_POD_SHRINK when the run
    reported pod_shrink_ready) the SAFE way after a coordinated pod abort
    (train_jax returned pod_degraded=True; emergency checkpoint and logs
    already landed).

    os._exit, not sys.exit, for the same reason the stall watchdog uses
    it: the abandoned collective thread is still blocked inside the
    transport, and normal interpreter teardown destroys the distributed
    runtime under it — the process then dies by std::terminate/SIGABRT
    instead of the documented code (observed on the gloo chaos harness).

    Process 0 lingers briefly first: it hosts the coordination service,
    and its exit closes every peer's error-polling RPC — which the XLA
    client answers with LOG(FATAL), terminating survivors still writing
    THEIR emergency checkpoints. The aborts start near-simultaneously
    (same missed collective), so a short linger lets the peers finish."""
    drain_for_pod_exit(code)
    try:
        import jax

        if jax.process_count() > 1 and jax.process_index() == 0:
            time.sleep(linger_s)
    except Exception:
        pass
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)


def drain_for_pod_exit(code: int = EXIT_POD_DEGRADED) -> None:
    """Latch /healthz into `draining`, carrying the pod-abort verdict.

    The abort is terminal from here — the ingress (left serving through
    the linger window by train_jax's teardown) must answer a supervisor's
    scrape with "winding down, and THIS is why" (state=draining, the
    degraded reasons — e.g. pod_peer_lost — preserved in the snapshot),
    not a degraded-looking process it might still route around. drain()
    is first-wins, so a SIGTERM that already latched `preempted` keeps
    its attribution. Factored out of pod_degraded_exit so the linger
    contract is testable without os._exit (tests/test_obs.py)."""
    try:
        from distributed_ddpg_tpu.obs import health

        _state, reasons = health.get().state()
        health.get().drain(
            "; ".join(reasons) if reasons else f"pod abort (exit {code})"
        )
    except Exception:
        pass  # diagnostics must never block the documented exit


def _param_checksum(host_params) -> float:
    import jax

    return float(
        sum(np.abs(np.asarray(leaf)).sum() for leaf in jax.tree.leaves(host_params))
    )


class _RecurrentEvalPolicy:
    """The evaluator's policy of a recurrent configuration: the learner's
    own one-step apply (learner.make_act_fn) on a host copy of the actor's
    tree, with the memory it carries over an episode (the cell's state, the
    previous action and reward). `_eval_numpy` tells it each step's reward
    (`observe`) and each episode's start (`reset`)."""

    def __init__(self, act, params, units: int, act_dim: int):
        from distributed_ddpg_tpu.models.recurrent import zero_memory

        self._act, self._params = act, params
        self._zero = zero_memory(1, units, act_dim)
        self._memory = self._zero

    def reset(self) -> None:
        self._memory = self._zero

    def __call__(self, obs):
        action, (h, c) = self._act(
            self._params, np.asarray(obs, np.float32)[None], self._memory
        )
        self._memory = self._memory._replace(h=h, c=c)
        return np.asarray(action)

    def observe(self, action, reward) -> None:
        self._memory = self._memory._replace(
            prev_action=np.asarray(action, np.float32)[None],
            prev_reward=np.asarray([reward], np.float32),
        )


def _eval_numpy(policy, config: DDPGConfig, spec, episodes: Optional[int] = None) -> float:
    env = make(config.env_id, seed=config.seed + 777)
    returns = []
    stateful = hasattr(policy, "observe")  # a recurrent policy
    for ep in range(episodes or config.eval_episodes):
        obs, _ = env.reset(seed=config.seed + 777 + ep)
        if stateful:
            policy.reset()
        done, total = False, 0.0
        while not done:
            action = np.clip(policy(obs)[0], spec.action_low, spec.action_high)
            obs, r, terminated, truncated, _ = env.step(action)
            if stateful:
                policy.observe(action, r)
            total += r
            done = terminated or truncated
        returns.append(total)
    return float(np.mean(returns))


def main(argv=None) -> None:
    config = DDPGConfig.from_flags(argv if argv is not None else sys.argv[1:])
    summary = train(config)
    print({k: round(v, 3) if isinstance(v, float) else v for k, v in summary.items()})
    if summary.get("pod_degraded"):
        pod_degraded_exit(
            code=(
                EXIT_POD_SHRINK
                if summary.get("pod_shrink_ready")
                else EXIT_POD_DEGRADED
            )
        )
    if summary.get("numeric_failed"):
        # Documented numeric-health abort: the guardrails could not repair
        # a sustained divergence. Distinct from 75/76 (those are "relaunch
        # and resume"): a driver should inspect the guardrail_* counters
        # before pouring more compute onto a diverging config.
        sys.exit(EXIT_NUMERIC)
    if summary.get("preempted"):
        # The documented "preempted, resumable" exit — a driver retries
        # the run with the same checkpoint_dir instead of diagnosing it.
        sys.exit(EXIT_PREEMPTED)


if __name__ == "__main__":
    main()
