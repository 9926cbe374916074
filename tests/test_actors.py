"""Actor-pool tests (SURVEY.md §4 'Fault/elastic tests'): workers stream
transitions, param broadcast reaches policies, a killed worker is respawned
and the learner side keeps running."""

import time

import numpy as np
import pytest

from distributed_ddpg_tpu.actors import NumpyPolicy, flatten_params, param_layout
from distributed_ddpg_tpu.actors.pool import ActorPool
from distributed_ddpg_tpu.config import DDPGConfig
from distributed_ddpg_tpu.envs import make, spec_of
from distributed_ddpg_tpu.learner import init_train_state
from distributed_ddpg_tpu.replay import UniformReplay

HID = (16, 16)


def _setup(num_actors=2, **kw):
    cfg = DDPGConfig(
        env_id="Pendulum-v1",
        actor_hidden=HID,
        critic_hidden=HID,
        num_actors=num_actors,
        replay_capacity=50_000,
        **kw,
    )
    env = make(cfg.env_id, seed=0, prefer_builtin=True)
    spec = spec_of(env)
    state = init_train_state(cfg, spec.obs_dim, spec.act_dim, seed=0)
    return cfg, spec, state


@pytest.mark.parametrize(
    "mode", ["boot", pytest.param("midrun", marks=pytest.mark.slow)]
)
def test_workers_exit_when_pool_dies_hard(mode):
    """Orphan guard (worker.py): a pool process that dies WITHOUT stop() —
    SIGKILL, or the stall watchdog's os._exit — must not leave workers
    running forever (observed in-round: 64 orphaned Humanoid workers after
    a hard kill). 'boot' kills the pool before workers finish booting
    (first loop-top guard catches it); 'midrun' kills it while workers are
    blocked in full-transport put() backpressure (the guarded timeout loop
    must catch it — a bare blocking put would hang forever)."""
    import os
    import subprocess
    import sys

    out = subprocess.run(
        [sys.executable, os.path.join(os.path.dirname(__file__), "orphan_child.py"),
         mode],
        capture_output=True,
        text=True,
        timeout=120,
        env={
            **os.environ,
            "PYTHONPATH": os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
            + os.pathsep
            + os.environ.get("PYTHONPATH", ""),
        },
    )
    assert out.returncode == 70, f"child failed to set up: {out.stderr[-2000:]}"
    pids = [int(p) for line in out.stdout.splitlines()
            if line.startswith("PIDS") for p in line.split()[1:]]
    assert pids, f"no worker pids reported: {out.stdout!r}"
    deadline = time.time() + 30
    while time.time() < deadline:
        alive = [p for p in pids if _pid_alive(p)]
        if not alive:
            return
        time.sleep(0.5)
    # Clean up before failing so orphans don't leak into other tests.
    for p in alive:
        try:
            os.kill(p, 9)
        except OSError:
            pass
    raise AssertionError(f"orphaned workers still alive after 30s: {alive}")


def _pid_alive(pid: int) -> bool:
    import os

    try:
        os.kill(pid, 0)
    except OSError:
        return False
    # A reaped-by-init zombie still answers signal 0; read the state.
    # No /proc (non-Linux): assume alive — the conservative answer keeps
    # the test honest instead of vacuously passing on live orphans.
    try:
        with open(f"/proc/{pid}/stat") as f:
            return f.read().split(")")[-1].split()[0] != "Z"
    except OSError:
        return not os.path.exists("/proc")


def test_numpy_policy_matches_jax_actor():
    import jax

    from distributed_ddpg_tpu.learner import make_act_fn

    cfg, spec, state = _setup()
    layout = param_layout(spec.obs_dim, spec.act_dim, HID)
    pol = NumpyPolicy(layout, spec.action_scale, spec.action_offset)
    pol.load_flat(flatten_params(jax.device_get(state.actor_params)))
    act = make_act_fn(cfg, spec.action_scale, spec.action_offset)
    obs = np.random.default_rng(0).standard_normal((7, spec.obs_dim)).astype(np.float32)
    np.testing.assert_allclose(
        pol(obs), np.asarray(act(state.actor_params, obs)), rtol=1e-5, atol=1e-6
    )


@pytest.mark.slow
@pytest.mark.parametrize("transport", ["shm", "queue"])
def test_pool_streams_transitions_and_respawns(transport):
    from distributed_ddpg_tpu import native

    if transport == "shm" and not native.available():
        pytest.skip("native toolchain unavailable")
    cfg, spec, state = _setup(
        num_actors=2, faults="worker:0:crash@200", transport=transport
    )
    replay = UniformReplay(cfg.replay_capacity, spec.obs_dim, spec.act_dim)
    import jax

    pool = ActorPool(cfg, spec, heartbeat_timeout=15.0)
    pool.start(jax.device_get(state.actor_params))
    try:
        deadline = time.time() + 60
        while len(replay) < 1000 and time.time() < deadline:
            pool.drain_into(replay)
            time.sleep(0.1)
        assert len(replay) >= 1000, f"only {len(replay)} transitions arrived"
        # Transitions must be sane Pendulum data.
        s = replay.sample(64)
        assert np.all(np.abs(s["action"]) <= 2.0 + 1e-5)
        assert np.all(s["reward"] <= 0.0)
        assert np.all((s["discount"] == 0.0) | (s["discount"] > 0.9))

        # Worker 0 crashes at step 200 (injected); monitor must respawn it
        # and data must keep flowing afterwards.
        time.sleep(0.5)
        stats = pool.monitor()
        deadline = time.time() + 30
        while stats["total_respawns"] == 0 and time.time() < deadline:
            time.sleep(0.5)
            stats = pool.monitor()
        assert stats["total_respawns"] >= 1, "injected-fault worker never respawned"
        before = len(replay)
        deadline = time.time() + 30
        while len(replay) < before + 200 and time.time() < deadline:
            pool.drain_into(replay)
            time.sleep(0.1)
        assert len(replay) >= before + 200, "no data after respawn"

        # Param broadcast: version bump reaches workers without error, and
        # subsequently drained experience carries a bounded staleness
        # (SURVEY.md §5 'params-staleness per actor').
        pool.broadcast(jax.device_get(state.actor_params), learner_step=500)
        deadline = time.time() + 30
        while pool.drain_into(replay) == 0 and time.time() < deadline:
            time.sleep(0.1)
        st = pool.staleness()
        assert 0 <= st["staleness_mean"] <= 500
        assert 0 <= st["staleness_max"] <= 500
        assert pool.episode_stats() is not None
    finally:
        pool.stop()


def test_nstep_counters_sum_over_actors_and_are_absent_at_one_step():
    """`nstep_rows` / `nstep_short_rows` (metrics.nstep_counters) through a
    pool: two inline actors on Pendulum (never terminates, truncated at 200
    steps) with n = 3 emit one row per env step, and the last n - 1 = 2
    windows of every finished episode are short; at n = 1 a pool reports
    neither key. The process pool shares the accumulator, the counts' layout
    and the function that sums them; its workers write a shared array at each
    flush."""
    import jax

    from distributed_ddpg_tpu.actors.sync_pool import SyncActorPool
    from distributed_ddpg_tpu.metrics import nstep_counters

    cfg, spec, state = _setup(num_actors=2, n_step=3)
    pool = SyncActorPool(cfg, spec).start(jax.device_get(state.actor_params))
    try:
        rows = sum(len(b["reward"]) for b in pool.drain_batches(max_rows=2 * 450))
        got = pool.nstep_counters()
    finally:
        pool.stop()
    # each actor took 450 steps: two whole episodes (200 rows each, 2 of them
    # short) and 50 steps of a third, whose last 2 windows are still pending
    assert got == {"nstep_rows": rows, "nstep_short_rows": 8}
    assert rows == 2 * (450 - 2)

    cfg1, spec1, _ = _setup(num_actors=2)
    assert SyncActorPool(cfg1, spec1).nstep_counters() == {}
    assert ActorPool(cfg1, spec1).nstep_counters() == {}
    counted = ActorPool(cfg, spec)
    assert counted.nstep_counters() == {"nstep_rows": 0, "nstep_short_rows": 0}
    assert nstep_counters([5, 1, 7, 2]) == {"nstep_rows": 12, "nstep_short_rows": 3}


def test_pool_rows_are_the_five_step_fold_of_the_raw_env_steps():
    """What reaches the ring against what the envs gave: two inline actors
    with n = 5 step Pendulum (truncated at 200 steps; one step of actor 0 is
    made to terminate), every raw env step is logged at the env's own
    `step`, and every row the pool delivers is recomputed from that log:
    R = sum_k gamma^k r_{t+k} over the steps the window holds, d = gamma^steps
    (0 where the window ends in the termination), next_obs = the observation
    `steps` on, never one from across a reset. The benchmark's on-chip check
    starts from rows the actors already folded, so this is where the fold
    itself is held."""
    import jax

    from distributed_ddpg_tpu.actors.sync_pool import SyncActorPool

    n, die_at = 5, 137
    cfg, spec, state = _setup(num_actors=2, n_step=n)
    pool = SyncActorPool(cfg, spec).start(jax.device_get(state.actor_params))
    logs = []
    for i, actor in enumerate(pool._actors):
        log, raw_step = [], actor.env.step

        def step(action, actor=actor, log=log, raw_step=raw_step, dies=i == 0):
            obs = actor.obs
            next_obs, reward, terminated, truncated, info = raw_step(action)
            terminated = terminated or (dies and len(log) == die_at)
            log.append((obs, float(reward), bool(terminated), bool(terminated or truncated), next_obs))
            return next_obs, reward, terminated, truncated, info

        actor.env.step = step
        logs.append(log)
    try:
        batch, = pool.drain_batches(max_rows=2 * 450)
    finally:
        pool.stop()
    first = {}  # raw obs -> (log, index): Pendulum's float observations do not repeat
    for log in logs:
        for t, (obs, *_rest) in enumerate(log):
            first[obs.tobytes()] = (log, t)
    g, short, ended_dead = cfg.gamma, 0, 0
    for obs, ret, disc, nobs in zip(batch["obs"], batch["reward"], batch["discount"], batch["next_obs"]):
        log, t = first[obs.tobytes()]
        steps = next(k + 1 for k in range(n) if k + 1 == n or log[t + k][3])
        dead = log[t + steps - 1][2]
        assert ret == pytest.approx(sum(g**k * log[t + k][1] for k in range(steps)), rel=1e-5, abs=1e-6)
        assert disc == pytest.approx(0.0 if dead else g**steps, rel=1e-6)
        np.testing.assert_array_equal(nobs, log[t + steps - 1][4])
        short += steps < n
        ended_dead += dead
    # actor 0: 138 steps to its death, then 200 and 112 more; actor 1: 200, 200, 50.
    # Every finished episode's last n - 1 windows are short, the death's n windows carry d = 0
    assert len(batch["reward"]) == 2 * 450 - 2 * (n - 1) and short == 4 * (n - 1) and ended_dead == n
