"""Child process for tests/test_multihost.py: one of N processes in a
jax.distributed CPU cluster (SURVEY.md §4 'Multi-host path tested with
jax.distributed.initialize across local subprocesses').

Each process contributes 2 fake CPU devices; the global (data=N*2, model=1)
mesh spans processes, so the learner's gradient AllReduce crosses the
process boundary (Gloo here, DCN on a real pod — parallel/multihost.py).
Runs one deterministic learner chunk and prints a parity line the parent
compares across processes and against a single-process run.

Usage: python multihost_child.py <process_id> <num_processes> <port> [mode]
  mode = chunk  (default): one deterministic learner chunk, parity line
  mode = replay: DeviceReplay lockstep ingest (sync_ship) + fused-sampling
                 chunk; asserts the replicated storage is identical and
                 contains BOTH processes' rows exactly once
  mode = train:  the FULL train_jax loop (actors + device replay + sharded
                 learner) across the process boundary; parity on the final
                 param checksum (VERDICT.md round-1 Missing #3)
  mode = fused:  the megakernel x mesh composition (fused_mesh, K-step
                 local SGD) on a mesh that SPANS processes — the
                 chunk-boundary param pmean crosses the process boundary
                 (Gloo here, DCN on a pod); parity on the end state
  mode = coalesce: coalesced lockstep sync_ship (super-block all-gather
                 insert with the on-device per-process interleave
                 transpose) vs the seed's serial max_coalesce=1 sequence
                 in the SAME cluster — storage/ptr/size must come out
                 bit-identical (docs/INGEST.md)
  mode = podtrain: the full train_jax loop under the POD-RESILIENCE
                 contract (docs/RESILIENCE.md pod rows): pod fault specs
                 (pod:<proc>:kill|hang@beat), collective deadline, and
                 checkpoint dirs arrive via POD_* env vars; the child
                 exits train.EXIT_POD_DEGRADED (76) when a peer is lost
                 and 0 on a clean (or resumed) completion. Parent:
                 tests/test_pod.py.

Every mode runs `multihost.startup_barrier` right after initialize: the
one-time generous rendezvous absorbs backend-init/import skew under box
load, which used to surface as startup heartbeat timeouts in these
children on contended hosts (CHANGES.md PR 5 note).
"""

import os
import sys

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"

import jax

jax.config.update("jax_platforms", "cpu")


def main() -> None:
    pid, nprocs, port = int(sys.argv[1]), int(sys.argv[2]), int(sys.argv[3])
    mode = sys.argv[4] if len(sys.argv) > 4 else "chunk"

    if nprocs > 1:
        # Exercise the production bootstrap via its env-var path.
        os.environ["JAX_COORDINATOR_ADDRESS"] = f"localhost:{port}"
        os.environ["JAX_NUM_PROCESSES"] = str(nprocs)
        os.environ["JAX_PROCESS_ID"] = str(pid)

        # The multiprocess CPU backend needs an explicit collectives
        # transport (the Gloo the module docstring's 'Gloo here, DCN on a
        # pod' refers to): without it, cross-process computations fail
        # with "Multiprocess computations aren't implemented on the CPU
        # backend". Set before the backend is created, and only on the
        # actual child path — gloo setup requires a distributed client,
        # so a single-process import of this module (the parity oracle)
        # must not inherit it.
        jax.config.update("jax_cpu_collectives_implementation", "gloo")

    from distributed_ddpg_tpu.parallel import multihost

    if nprocs > 1:
        assert multihost.initialize() is True
        info = multihost.process_info()
        assert info["process_count"] == nprocs, info
        assert info["global_device_count"] == 2 * nprocs, info

        # Startup hardening (ISSUE 6 satellite): rendezvous once with a
        # generous grace so a peer still paying backend-init/import cost
        # under box load doesn't turn the first real collective into a
        # "startup heartbeat timeout" flake. Distinct from (and much
        # larger than) any steady-state collective deadline the mode
        # then arms.
        multihost.startup_barrier(
            float(os.environ.get("POD_STARTUP_GRACE_S", "240"))
        )
    # nprocs == 1: no distributed bootstrap, no gloo, no barrier — the
    # shape of a supervisor's shrunk-to-one generation (ISSUE 19). The
    # run behaves like the elastic test's in-process M=1 adoption phase
    # (tests/test_pod.py test_two_process_elastic_shrink_then_grow).

    import numpy as np

    from distributed_ddpg_tpu.config import DDPGConfig
    from distributed_ddpg_tpu.parallel.learner import ShardedLearner

    if mode == "podtrain":
        run_pod_train(pid, tag=f"proc{pid}")
    elif mode == "chunk":
        run_parity_chunk(ShardedLearner, DDPGConfig, np, tag=f"proc{pid}")
    elif mode == "replay":
        run_replay_parity(pid, nprocs, tag=f"proc{pid}")
    elif mode == "coalesce":
        run_coalesced_ingest_parity(pid, tag=f"proc{pid}")
    elif mode == "bgsync":
        run_background_sync_ship_parity(pid, tag=f"proc{pid}")
    elif mode == "train":
        run_train_parity(tag=f"proc{pid}")
    elif mode == "fused":
        run_fused_mesh_parity(tag=f"proc{pid}")
    else:
        raise SystemExit(f"unknown mode {mode!r}")


def run_pod_train(pid: int, tag: str) -> None:
    """Full train_jax under the pod-resilience contract. Parameterized by
    env vars (the parent launches N identical children, so per-run knobs
    can't ride argv):

      POD_FAULTS          --faults plan (e.g. 'pod:1:kill@40'); same
                          string everywhere — only the targeted process
                          fires, every process ticks the beat ordinal
      POD_CKPT_DIR        shared checkpoint dir ('' = no checkpoints)
      POD_LOG_DIR         JSONL dir; this child writes proc<pid>.jsonl
      POD_TOTAL_STEPS     global env-step budget
      POD_TIMEOUT_S       pod_collective_timeout_s
      POD_STARTUP_GRACE_S pod_startup_grace_s (also the barrier above)
      POD_BG_SYNC         '1' = background sync_ship beats (the
                          production default). Default '0' here: chunk
                          execution overlapping lane beats can tickle a
                          pre-existing concurrent-gloo-collective race
                          on the multiprocess CPU backend (the PR-5
                          child-flake note), and THIS harness is pinning
                          the pod-abort contract, not the overlap.
      POD_OBS_PORT_BASE   when set, arm the telemetry ingress on port
                          base+pid (obs/exporter.py): the parent scrapes
                          /metrics and /healthz live during the drill
                          (tests/test_obs.py; docs/OBSERVABILITY.md §4)
      POD_TRACE_DIR       when set, arm the flight recorder with
                          trace_dir=<dir>/proc<pid> — each child exports
                          its own trace.json for the parent's merge-trace
                          assertion (clock-aligned pod timeline)

    Prints 'PODRESULT <tag> steps=<n> degraded=<0|1> elected=<step>
    adopted=<n> shrinks=<n> grows=<n> shrinkready=<0|1>' and exits with
    train.py's documented code (78 on pod degradation with a complete
    replay slice set on disk — relaunch-smaller-ready; 76 on pod
    degradation without one; 75 on preemption, 0 clean) so the parent
    asserts the REAL contract."""
    import tempfile

    from distributed_ddpg_tpu.config import DDPGConfig
    from distributed_ddpg_tpu.train import EXIT_PREEMPTED, train_jax

    # The multiprocess CPU backend races concurrently-executing
    # computations that both carry gloo collectives (async dispatch lets
    # the sync_ship insert / learner chunk still be executing when the
    # next host gather runs — observed as nondeterministic
    # `gloo EnforceNotMet op.preamble.length <= op.nbytes` stream
    # corruption). Synchronous dispatch serializes the per-process device
    # stream, so the only collective failures this harness sees are the
    # INJECTED ones under test. CPU-test-only: real TPU backends separate
    # collective channels in hardware.
    import jax as _jax

    _jax.config.update("jax_cpu_enable_async_dispatch", False)

    log_dir = os.environ.get("POD_LOG_DIR", "")
    obs_port_base = int(os.environ.get("POD_OBS_PORT_BASE", "0"))
    trace_root = os.environ.get("POD_TRACE_DIR", "")
    trace_dir = ""
    if trace_root:
        trace_dir = os.path.join(trace_root, f"proc{pid}")
        os.makedirs(trace_dir, exist_ok=True)
    config = DDPGConfig(
        backend="jax_tpu",
        env_id="Pendulum-v1",
        actor_hidden=(16, 16),
        critic_hidden=(16, 16),
        batch_size=16,
        num_actors=1,
        total_env_steps=int(os.environ.get("POD_TOTAL_STEPS", "200000")),
        replay_min_size=128,
        replay_capacity=8192,
        eval_every=0,
        eval_episodes=1,
        checkpoint_dir=os.environ.get("POD_CKPT_DIR", ""),
        # Small cadence so the pod has retained checkpoints besides the
        # emergency one — the resume election must pick among several.
        checkpoint_every=int(os.environ.get("POD_CKPT_EVERY", "64")),
        faults=os.environ.get("POD_FAULTS", ""),
        # Sharded device replay (docs/REPLAY_SHARDING.md): the sharded-
        # mode chaos run drives the SAME pod contract over the
        # shard_exchange beat lane (POD_REPLAY_SHARDING=sharded).
        replay_sharding=os.environ.get("POD_REPLAY_SHARDING", "replicated"),
        pod_collective_timeout_s=float(os.environ.get("POD_TIMEOUT_S", "20")),
        pod_startup_grace_s=float(
            os.environ.get("POD_STARTUP_GRACE_S", "240")
        ),
        sync_ship_background=os.environ.get("POD_BG_SYNC", "0") == "1",
        log_path=(
            os.path.join(log_dir, f"proc{pid}.jsonl")
            if log_dir
            else tempfile.mktemp(suffix=".jsonl")
        ),
        # The pod deadline owns hang detection here; the watchdog's
        # os._exit(70) would race the clean-abort path under test.
        watchdog_s=0.0,
        # Telemetry plane (obs/; docs/OBSERVABILITY.md §4): per-process
        # ingress port and per-process trace ring, both off unless the
        # parent opts in.
        obs_port=(obs_port_base + pid) if obs_port_base else 0,
        trace_dir=trace_dir,
    )
    out = train_jax(config)
    print(
        f"PODRESULT {tag} steps={out['learner_steps']} "
        f"degraded={int(bool(out.get('pod_degraded')))} "
        f"elected={out.get('pod_resume_step_elected', -1)} "
        f"adopted={out.get('pod_slices_adopted', 0)} "
        f"shrinks={out.get('pod_shrinks', 0)} "
        f"grows={out.get('pod_grows', 0)} "
        f"shrinkready={int(bool(out.get('pod_shrink_ready')))}",
        flush=True,
    )
    if out.get("pod_degraded"):
        # The documented exit discipline (leader linger + os._exit) —
        # the same call train.main() makes, including the elastic
        # shrink-ready 78/76 split (docs/RESILIENCE.md).
        from distributed_ddpg_tpu.train import (
            EXIT_POD_DEGRADED,
            EXIT_POD_SHRINK,
            pod_degraded_exit,
        )

        pod_degraded_exit(
            code=(
                EXIT_POD_SHRINK
                if out.get("pod_shrink_ready")
                else EXIT_POD_DEGRADED
            )
        )
    if out.get("preempted"):
        raise SystemExit(EXIT_PREEMPTED)


def run_fused_mesh_parity(tag: str) -> None:
    """Megakernel x mesh across the process boundary: every one of the 4
    global devices (2 per process) runs the whole K-step chunk in one
    pallas launch (interpret mode on CPU) on its own draws, then the
    chunk-boundary float-state pmean rides the cross-process collective.
    Identical replicated storage on both processes -> the per-device draws
    are a pure function of the replicated key stream -> both processes
    must print identical losses and end-state checksums; a fork means the
    boundary AllReduce or the axis-folded draw streams diverged."""
    import numpy as np

    from distributed_ddpg_tpu.config import DDPGConfig
    from distributed_ddpg_tpu.parallel.learner import ShardedLearner
    from distributed_ddpg_tpu.replay.device import DeviceReplay

    obs_dim, act_dim = 5, 2
    config = DDPGConfig(
        actor_hidden=(16, 16), critic_hidden=(16, 16), batch_size=8,
        seed=0, fused_chunk="on",
    )
    learner = ShardedLearner(
        config, obs_dim, act_dim, action_scale=1.0, chunk_size=2
    )
    assert learner.fused_mesh_active, (
        "fused_mesh must activate on the cross-process data mesh"
    )
    replay = DeviceReplay(
        256, obs_dim, act_dim, mesh=learner.mesh, block_size=64
    )
    rng = np.random.default_rng(7)
    width = replay.width
    # Multi-process add_packed only buffers host-side; rows land via the
    # lockstep sync_ship (same discipline as run_replay_parity — without
    # it the storage stays empty and the parity check is vacuous).
    replay.add_packed(rng.standard_normal((128, width)).astype(np.float32))
    moved = replay.sync_ship()
    moved += replay.sync_ship(force=True)
    assert moved > 0 and len(replay) > 0, (moved, len(replay))
    out = learner.run_sample_chunk(replay)
    import jax

    loss = float(jax.device_get(out.metrics["critic_loss"]))
    out2 = learner.run_sample_chunk(replay)
    loss2 = float(jax.device_get(out2.metrics["critic_loss"]))
    leaves = jax.tree.leaves(jax.device_get(learner.state.actor_params))
    checksum = float(sum(np.abs(leaf).sum() for leaf in leaves))
    print(f"PARITY {tag} {loss:.8f}/{loss2:.8f} {checksum:.6f}", flush=True)


def run_coalesced_ingest_parity(pid: int, tag: str) -> None:
    """Two DeviceReplay instances in the SAME jax.distributed cluster, fed
    identical per-process rows: `serial` ships with max_coalesce=1 (the
    seed's exact one-global-block-per-collective sequence), `coal` with
    max_coalesce=4 (super-block all-gather inserts whose on-device
    transpose must reproduce the serial per-process block interleave).
    Every process calls both replays' sync_ship at the same points, so the
    collective schedule stays lockstep; the parity line carries a local
    bit-identity verdict plus the coalesced storage checksum the parent
    compares across processes (replica consistency)."""
    import numpy as np

    from distributed_ddpg_tpu.config import DDPGConfig
    from distributed_ddpg_tpu.parallel.learner import ShardedLearner
    from distributed_ddpg_tpu.replay.device import DeviceReplay

    obs_dim, act_dim = 5, 2
    config = DDPGConfig(
        actor_hidden=(16, 16), critic_hidden=(16, 16), batch_size=16, seed=0
    )
    learner = ShardedLearner(config, obs_dim, act_dim, action_scale=1.0,
                             chunk_size=2)
    serial = DeviceReplay(8192, obs_dim, act_dim, mesh=learner.mesh,
                          block_size=128, max_coalesce=1)
    coal = DeviceReplay(8192, obs_dim, act_dim, mesh=learner.mesh,
                        block_size=128, max_coalesce=4)
    r = np.random.default_rng(50 + pid)
    # 5 full blocks (serial: 5 collectives; coal: one k=4 + one k=1) plus
    # a 37-row remainder for the force-padded block.
    rows = (0.1 * r.standard_normal((5 * 128 + 37, serial.width))).astype(
        np.float32
    )
    for rep in (serial, coal):
        rep.add_packed(rows.copy())
        moved = rep.sync_ship()
        moved += rep.sync_ship(force=True)
        assert moved == len(rows), (moved, len(rows))

    import jax

    s0 = np.asarray(jax.device_get(serial.storage))
    s1 = np.asarray(jax.device_get(coal.storage))
    identical = bool(
        np.array_equal(s0, s1)
        and int(jax.device_get(serial.ptr)) == int(jax.device_get(coal.ptr))
        and int(jax.device_get(serial.size)) == int(jax.device_get(coal.size))
    )
    checksum = float(np.abs(s1).sum())
    print(f"PARITY {tag} {int(identical)} {checksum:.4f}", flush=True)


def run_background_sync_ship_parity(pid: int, tag: str) -> None:
    """Background lockstep sync_ship (docs/TRANSFER.md) vs the synchronous
    reference IN THE SAME CLUSTER: `serial` ships with blocking learner-
    thread collectives (the PR-1 path), `bg` issues beats on the transfer
    scheduler's lockstep lane (sync_ship_begin, counts snapshot at token
    time) and only waits tickets at the gate points. Storage/ptr/size
    must come out bit-identical, and the replicas must agree. Per-process
    program order keeps the collective schedule consistent: every serial
    collective completes before any bg beat is issued."""
    import numpy as np

    from distributed_ddpg_tpu.config import DDPGConfig
    from distributed_ddpg_tpu.parallel.learner import ShardedLearner
    from distributed_ddpg_tpu.replay.device import DeviceReplay
    from distributed_ddpg_tpu.transfer import TransferScheduler

    obs_dim, act_dim = 5, 2
    config = DDPGConfig(
        actor_hidden=(16, 16), critic_hidden=(16, 16), batch_size=16, seed=0
    )
    learner = ShardedLearner(config, obs_dim, act_dim, action_scale=1.0,
                             chunk_size=2)
    serial = DeviceReplay(8192, obs_dim, act_dim, mesh=learner.mesh,
                          block_size=128, max_coalesce=4)
    sched = TransferScheduler().start()
    bg = DeviceReplay(8192, obs_dim, act_dim, mesh=learner.mesh,
                      block_size=128, max_coalesce=4,
                      scheduler=sched, background_sync=True)
    assert bg._bg_sync, "background beats must arm on a multi-process mesh"
    r = np.random.default_rng(70 + pid)
    rows = (0.1 * r.standard_normal((5 * 128 + 37, serial.width))).astype(
        np.float32
    )
    # Reference: synchronous beats, two waves + a force pad.
    serial.add_packed(rows[:300].copy())
    serial.sync_ship()
    serial.add_packed(rows[300:].copy())
    serial.sync_ship()
    serial.sync_ship(force=True)
    # Background: identical wave structure, beats issued WITHOUT waiting
    # (t1 resolves only after t2 was issued — genuinely overlapped), the
    # force beat routed synchronously through the same lane.
    bg.add_packed(rows[:300].copy())
    t1 = bg.sync_ship_begin()
    bg.add_packed(rows[300:].copy())
    t2 = bg.sync_ship_begin()
    moved1 = t1.result(timeout=240)
    moved2 = t2.result(timeout=240)
    moved3 = bg.sync_ship(force=True)
    assert moved1 + moved2 + moved3 == len(rows), (moved1, moved2, moved3)

    import jax

    s0 = np.asarray(jax.device_get(serial.storage))
    s1 = np.asarray(jax.device_get(bg.storage))
    identical = bool(
        np.array_equal(s0, s1)
        and int(jax.device_get(serial.ptr)) == int(jax.device_get(bg.ptr))
        and int(jax.device_get(serial.size)) == int(jax.device_get(bg.size))
    )
    snap = sched.snapshot()
    assert snap["transfer_lockstep_items"] == 3, snap
    sched.close()
    checksum = float(np.abs(s1).sum())
    print(f"PARITY {tag} {int(identical)} {checksum:.4f}", flush=True)


def run_replay_parity(pid: int, nprocs: int, tag: str) -> None:
    """Each process buffers DIFFERENT local rows (seeded by pid), then the
    lockstep sync_ship gathers them into the replicated storage. Asserts:
    size == sum of contributions, and the storage checksum equals the sum
    over ALL processes' rows (each process recomputes every process's rows
    from the seeds) — i.e. every row landed exactly once, identically on
    every replica. Then runs one fused-sampling learner chunk and prints
    its loss for cross-process comparison."""
    import numpy as np

    from distributed_ddpg_tpu.config import DDPGConfig
    from distributed_ddpg_tpu.parallel.learner import ShardedLearner
    from distributed_ddpg_tpu.replay.device import DeviceReplay

    obs_dim, act_dim = 5, 2
    config = DDPGConfig(
        actor_hidden=(16, 16), critic_hidden=(16, 16), batch_size=16, seed=0
    )
    learner = ShardedLearner(config, obs_dim, act_dim, action_scale=1.0,
                             chunk_size=2)
    rep = DeviceReplay(4096, obs_dim, act_dim, mesh=learner.mesh,
                       block_size=256)

    def rows_for(p: int) -> "np.ndarray":
        r = np.random.default_rng(100 + p)
        # Keep values in a sane range so the sampled learner chunk is finite.
        return (0.1 * r.standard_normal((300, rep.width))).astype(np.float32)

    rep.add_packed(rows_for(pid))
    assert len(rep) == 0, "multi-host add_packed must only buffer"
    moved = rep.sync_ship()          # min(300, 300) // 256 -> 1 block each
    assert moved == 256, moved
    moved2 = rep.sync_ship(force=True)   # remainders, padded
    assert moved2 == 44, moved2

    import jax

    size = len(rep)
    assert size == nprocs * 2 * 256, size  # 2 global blocks of nprocs*256
    storage = np.asarray(jax.device_get(rep.storage))[:size]
    got = float(np.abs(storage).sum())
    # Expected: every process's 300 real rows once, plus the force-padded
    # repetition of each remainder (tile(44 rows) -> 256 = 5x44 full + 36).
    expected = 0.0
    for p in range(nprocs):
        rows = rows_for(p)
        expected += float(np.abs(rows[:256]).sum())
        rem = rows[256:]
        reps = -(-256 // len(rem))
        expected += float(np.abs(np.tile(rem, (reps, 1))[:256]).sum())
    assert abs(got - expected) < 1e-2, (got, expected)

    out = learner.run_sample_chunk(rep)
    loss = float(jax.device_get(out.metrics["critic_loss"]))
    print(f"PARITY {tag} {loss:.8f} {got:.4f}", flush=True)


def run_train_parity(tag: str) -> None:
    """The full train_jax driver — actor pool, lockstep device-replay
    ingest, globally-budgeted loop — across the process boundary."""
    import tempfile

    from distributed_ddpg_tpu.config import DDPGConfig
    from distributed_ddpg_tpu.train import train_jax

    config = DDPGConfig(
        backend="jax_tpu",
        env_id="Pendulum-v1",
        actor_hidden=(16, 16),
        critic_hidden=(16, 16),
        batch_size=16,
        num_actors=1,
        total_env_steps=2500,   # GLOBAL budget (summed over processes)
        replay_min_size=128,
        replay_capacity=8192,
        eval_every=0,
        eval_episodes=1,
        log_path=tempfile.mktemp(suffix=".jsonl"),
        # Watchdog under LOCKSTEP collectives: a healthy 2-process run must
        # not false-fire (beats advance through the collective waits); a
        # genuinely wedged peer stalls both processes and both exit 70.
        watchdog_s=120.0,
    )
    out = train_jax(config)
    print(
        f"PARITY {tag} {out['learner_steps']} {out['param_checksum']:.6f}",
        flush=True,
    )


def run_parity_chunk(ShardedLearner, DDPGConfig, np, tag: str) -> None:
    """Deterministic 2-step chunk at batch 16 over however many devices are
    visible; prints 'PARITY <tag> <critic_loss> <param_checksum>'."""
    config = DDPGConfig(
        actor_hidden=(16, 16),
        critic_hidden=(16, 16),
        batch_size=16,
        seed=0,
    )
    learner = ShardedLearner(config, 5, 2, action_scale=1.0, chunk_size=2)
    rng = np.random.default_rng(0)
    k, b = 2, config.batch_size
    chunk = {
        "obs": rng.standard_normal((k, b, 5)).astype(np.float32),
        "action": rng.uniform(-1, 1, (k, b, 2)).astype(np.float32),
        "reward": rng.standard_normal((k, b)).astype(np.float32),
        "discount": np.full((k, b), 0.99, np.float32),
        "next_obs": rng.standard_normal((k, b, 5)).astype(np.float32),
        "weight": np.ones((k, b), np.float32),
    }
    out = learner.run_chunk(chunk)
    import jax

    loss = float(jax.device_get(out.metrics["critic_loss"]))
    leaves = jax.tree.leaves(jax.device_get(learner.state.actor_params))
    checksum = float(sum(np.abs(leaf).sum() for leaf in leaves))
    print(f"PARITY {tag} {loss:.8f} {checksum:.6f}", flush=True)


if __name__ == "__main__":
    main()
